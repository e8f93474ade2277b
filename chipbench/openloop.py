"""Open-loop load: requests are sent when they are due, whatever the
system is doing, and each is timed from its due time to its result, so a
stall delays the measured latency of every request behind it."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import NamedTuple


class Request(NamedTuple):
    due_s: float          # seconds after the window opens
    tree: tuple           # the generator's query tree (for the reference)
    text: str             # what the program is sent
    top_k: int | None


@dataclass
class Outcome:
    request: Request
    due: float                    # absolute, on the sender's clock
    sent: float = 0.0
    done: float | None = None
    result: object = None
    error: str | None = None      # "refused: ..." or the program's error
    refused: bool = False

    @property
    def answered(self) -> bool:
        return self.done is not None and self.error is None


def drive(submit, requests: list[Request], *, drain_s: float,
          window_s: float, refused: tuple[type, ...],
          clock=time.perf_counter) -> tuple[list[Outcome], float]:
    """Send each request through `submit(text, top_k) -> Future` at its
    due time; wait up to `drain_s` past the window's close for the last
    answers. Exceptions of the types in `refused`, raised by `submit`
    itself, mark the request refused at the door. An answer that comes
    after the drain is left out: its outcome stays unanswered. Returns
    the outcomes and the clock reading at which the window opened."""
    left = threading.Semaphore(0)
    lock = threading.Lock()
    closed = False
    outcomes: list[Outcome] = []

    def finish(o: Outcome, fut) -> None:
        now = clock()
        exc = fut.exception()
        with lock:
            if closed:
                return
            o.done = now
            if exc is None:
                o.result = fut.result()
            else:
                o.error = f"{type(exc).__name__}: {exc}"
        left.release()

    t0 = clock()
    for req in requests:
        o = Outcome(req, t0 + req.due_s)
        outcomes.append(o)
        wait = o.due - clock()
        if wait > 0:
            time.sleep(wait)
        o.sent = clock()
        try:
            fut = submit(req.text, req.top_k)
        except refused as exc:
            o.done, o.refused = o.sent, True
            o.error = f"refused: {type(exc).__name__}: {exc}"
            left.release()
            continue
        fut.add_done_callback(lambda f, o=o: finish(o, f))
    deadline = t0 + window_s + drain_s
    for _ in outcomes:
        if not left.acquire(timeout=max(0.0, deadline - clock())):
            break
    with lock:
        closed = True
    return outcomes, t0
