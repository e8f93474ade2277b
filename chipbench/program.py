"""The program's own spans, for the metric readers.

The program records its spans (`repro.analysis.spans`) while a
`jax.profiler` trace is being collected, so a traced run holds them and
an untraced one does not. `spans(records)` takes them from the recorder
once, after the window, and keeps them on the records as
`records.program`. A program without the recorder gives none, and a
reader then returns None.

A batch is one `service.search_batch` span: one call of the service per
micro-batch that the `Frontend` serves.
"""

from __future__ import annotations

BATCH = "service.search_batch"


def spans(rec) -> list:
    """Every span and `jit.compile` record of the run's traced window."""
    got = getattr(rec, "program", None)
    if got is None:
        try:
            from repro.analysis import spans as recorder
        except ImportError:              # a program that records no spans
            got = []
        else:
            got = recorder.stop()
        rec.program = got
    return got


def named(rec, name: str) -> list:
    return [s for s in spans(rec) if s.name == name]


def ms(found) -> float:
    """Summed duration of `found`, in ms."""
    return sum(s.end_ns - s.start_ns for s in found) * 1e-6


def per_batch_ms(rec, name: str) -> float | None:
    """Time in spans named `name`, per batch, in ms."""
    batches = len(named(rec, BATCH))
    return ms(named(rec, name)) / batches if batches else None


def window_ms(rec, name: str) -> float | None:
    """Time in spans named `name`, summed over the window, in ms."""
    return ms(named(rec, name)) if named(rec, BATCH) else None


def attr_sum(rec, name: str, attr: str) -> int | None:
    """The attribute `attr` summed over spans named `name`."""
    if not named(rec, BATCH):
        return None
    return sum(s.attrs.get(attr, 0) for s in named(rec, name))
