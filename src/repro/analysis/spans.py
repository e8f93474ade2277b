"""Span recorder: named, nested host intervals of the served path.

A span is one call of one phase (`searcher.decode`, `combine.call`, ...)
with its start and end on `time.perf_counter_ns()`, its own id, the id
of the span it runs under (a per-thread stack) and the id of the
`frontend.serve` batch it belongs to. Counts taken at a span's boundary
are its attributes (`s.set(docs=..., accepted=...)`), not counters of
their own. Spans are opened once per call, never per document.

Recording is process-wide, like the profiler it sits beside:

  * `start()` ... `stop() -> list[Span]` records explicitly;
  * while a `jax.profiler` trace is being collected spans are recorded
    too, and `stop()` hands them over, so a profile of the served path
    carries the program's spans with no switch of its own.

Every recorded span also opens a `jax.profiler.TraceAnnotation` of the
same name, which puts it on the device trace's clock: a profile shows
program spans over device ops, and an idle gap of the device can be
named by the span the host was in.

Compiles are recorded too: each `/jax/core/compile/backend_compile_duration`
event becomes a `jit.compile` record with JAX's `fun_name` and the
seconds taken, parented to the innermost span open on the compiling
thread (so a compile inside `combine.call` carries its `Q`, `L`, `W`).

Off — the default — `span()` returns one shared no-op object: no
annotation, no clock read, no record. The buffer holds at most `CAP`
spans and counts what it drops (`dropped()`).
"""

from __future__ import annotations

import sys
import threading
from itertools import count
from time import perf_counter_ns

CAP = 100_000
COMPILE = "jit.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_started = False
_epoch = 0             # bumped by start()/stop(): spans open across it drop
_buffer: list["Span"] = []
_dropped = 0
_ids = count(1)
_tls = threading.local()
_listening = False
_trace_enabled = None  # jax.profiler.TraceAnnotation.is_enabled, once loaded


class Span:
    """One recorded interval; a context manager while it is open."""

    __slots__ = ("name", "id", "parent", "batch", "start_ns", "end_ns",
                 "attrs", "_epoch", "_note")

    def __init__(self, name: str, starts_batch: bool = False) -> None:
        stack = _stack()
        up = stack[-1] if stack else None
        self.name = name
        self.id = next(_ids)
        self.parent = up.id if up is not None else None
        self.batch = self.id if starts_batch else \
            (up.batch if up is not None else None)
        self.start_ns = self.end_ns = 0
        self.attrs: dict = {}
        self._epoch = _epoch
        self._note = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        from jax import profiler
        self._note = profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        _stack().append(self)
        self.start_ns = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = perf_counter_ns()
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._note.__exit__(*exc)
        self._note = None
        _keep(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"batch={self.batch}, "
                f"{(self.end_ns - self.start_ns) * 1e-6:.3f} ms, "
                f"{self.attrs})")


class _Off:
    """The span handed out while nothing records."""

    __slots__ = ()

    def set(self, **_attrs) -> "_Off":
        return self

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *_exc) -> None:
        return None


NOOP = _Off()


def _stack() -> list[Span]:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _traced() -> bool:
    """True while a `jax.profiler` trace is being collected."""
    global _trace_enabled
    if _trace_enabled is None:
        jax = sys.modules.get("jax")
        if jax is None:              # no JAX loaded: nothing can trace
            return False
        _trace_enabled = jax.profiler.TraceAnnotation.is_enabled
    return _trace_enabled()


def span(name: str, starts_batch: bool = False):
    """A span named `name` under the innermost open one, or the no-op
    object when nothing records. `starts_batch` makes its id the batch
    id of every span opened under it."""
    if not (_started or _traced()):
        return NOOP
    _listen()
    return Span(name, starts_batch)


def _keep(s: Span) -> None:
    global _dropped
    if s._epoch != _epoch or not (_started or _traced()):
        return                       # recording ended while it was open
    if len(_buffer) < CAP:
        _buffer.append(s)
    else:
        _dropped += 1


def _on_duration(event: str, secs: float, **kwargs) -> None:
    if event != _COMPILE_EVENT or not (_started or _traced()):
        return
    end = perf_counter_ns()
    s = Span(COMPILE)
    s.start_ns, s.end_ns = end - int(secs * 1e9), end
    s.attrs.update(fun_name=kwargs.get("fun_name"), seconds=float(secs))
    _keep(s)


def _listen() -> None:
    global _listening
    if not _listening:
        _listening = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)


def start() -> None:
    """Record from now on, into an emptied buffer."""
    global _started, _epoch, _buffer, _dropped
    _listen()
    _buffer, _dropped = [], 0
    _epoch += 1
    _started = True


def stop() -> list[Span]:
    """End explicit recording; return what was recorded since `start()`
    (or, without one, since the last `stop()`) and empty the buffer."""
    global _started, _epoch, _buffer
    _started = False
    _epoch += 1
    out, _buffer = _buffer, []
    return out


def dropped() -> int:
    """Spans the buffer had no room for since the last `start()`."""
    return _dropped


__all__ = ["CAP", "COMPILE", "NOOP", "Span", "dropped", "span", "start",
           "stop"]
