"""Reduction of a profiler trace to the device's busy time, its operations
and the host's activity in its idle gaps.

The trace is the `.xplane.pb` that `jax.profiler` writes. Device planes
are those named `/device:<platform>:<n>`; their operations are the events
of the line `XLA Ops`. Busy time is the union of those events' intervals,
averaged over the device planes; an idle gap is a stretch of the traced
window that no device operation covers. A gap is named by what the host
was doing in it: the shortest host event that covers at least half of
it, or else the one that covers most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass

import numpy as np

DEVICE_OPS_LINE = "XLA Ops"
NO_HOST_SPAN = "host: no span"
# a Pallas kernel is one custom call of this target in the TPU trace
KERNEL_MARKER = 'custom_call_target="tpu_custom_call"'


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """A device operation's instruction name: the TPU trace names
        each operation by its whole HLO text, `%name = shape op(...)`."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def is_kernel(self) -> bool:
        return KERNEL_MARKER in self.name


@dataclass
class TraceSummary:
    window_s: float                     # extent of the trace
    busy_s: float                       # union of device ops, per device
    device_events: list[Event]          # every device operation
    idle_gaps: list[tuple[str, float]]  # host activity -> idle seconds

    def device_ops(self, top: int = 10) -> list[tuple[str, float]]:
        """Device seconds by operation name, largest first."""
        by_name: dict[str, float] = defaultdict(float)
        for e in self.device_events:
            by_name[e.op] += e.dur_ns * 1e-9
        return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> tuple[dict[str, list[Event]], list[Event]]:
    """(device plane name -> its operations, every host event)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            device[plane.name] = [e for line in plane.lines
                                  if line.name == DEVICE_OPS_LINE
                                  for e in _events(line)]
        elif plane.name.startswith("/host:"):
            host += [e for line in plane.lines
                     for e in _events(line)]
    return device, host


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _name_gap(s: float, e: float, host: list[Event], starts: np.ndarray,
              ends: np.ndarray, durs: np.ndarray) -> str:
    cover = np.minimum(e, ends) - np.maximum(s, starts)
    if not len(cover) or cover.max() <= 0:
        return NO_HOST_SPAN
    half = np.flatnonzero(cover >= 0.5 * (e - s))
    pick = half[np.argmin(durs[half])] if len(half) else np.argmax(cover)
    return host[pick].name


def reduce(path: str) -> TraceSummary:
    device, host = load(path)
    everything = [ev for evs in device.values() for ev in evs] + host
    if not everything:
        raise ValueError(f"{path} holds no events")
    t0 = min(ev.start_ns for ev in everything)
    t1 = max(ev.end_ns for ev in everything)
    starts = np.array([h.start_ns for h in host])
    ends = np.array([h.end_ns for h in host])
    durs = ends - starts
    busy = 0.0
    gaps: dict[str, float] = defaultdict(float)
    for evs in device.values():
        merged = _union([(ev.start_ns, ev.end_ns) for ev in evs])
        busy += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_name_gap(s, e, host, starts, ends, durs)] += \
                    (e - s) * 1e-9
    n_dev = max(1, len(device))
    return TraceSummary(
        window_s=(t1 - t0) * 1e-9,
        busy_s=busy * 1e-9 / n_dev,
        device_events=[ev for evs in device.values() for ev in evs],
        idle_gaps=sorted(((k, v / n_dev) for k, v in gaps.items()),
                         key=lambda kv: -kv[1]))
