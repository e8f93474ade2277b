"""What the benchmark wraps around the program to count and time it.

- `CountingStore`: the object store as the reader sees it, counting every
  range read (`gets_per_query`).
- `Adapter`: the one object the `Frontend` serves from. It hands each
  micro-batch to the deployment's call and records the batch as the
  service layer's span, with the round-1 and round-2 fetch walls that the
  program reports in its `QueryStats`.
- `Compiles`: backend compilations and persistent-cache hits, from JAX's
  monitoring events (a copy of the counter in `chip_smoke.py`).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import jax

from repro.storage import BlobStore

BATCH_SPAN = "chipbench.service_batch"


class CountingStore(BlobStore):
    """Delegates to `inner` and counts `get_range` calls."""

    def __init__(self, inner: BlobStore) -> None:
        self.inner = inner
        self.gets = 0
        self._lock = threading.Lock()

    def get_range(self, req):
        with self._lock:
            self.gets += 1
        return self.inner.get_range(req)

    def put(self, name, data):
        self.inner.put(name, data)

    def size(self, name):
        return self.inner.size(name)

    def list(self, prefix=""):
        return self.inner.list(prefix)

    def delete(self, name):
        self.inner.delete(name)

    def exists(self, name):
        return self.inner.exists(name)

    def mtime(self, name):
        return self.inner.mtime(name)


@dataclass
class BatchSpan:
    start: float
    end: float
    round1_s: float
    round2_s: float


class Adapter:
    """`query_batch` for the `Frontend` over `call(texts, top_k)`."""

    def __init__(self, call, close=None) -> None:
        self._call = call
        self._close = close
        self.spans: list[BatchSpan] = []

    def query_batch(self, queries, top_k=None):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(BATCH_SPAN):
            results = self._call(list(queries), top_k)
        t1 = time.perf_counter()
        self.spans.append(BatchSpan(
            t0, t1,
            max((r.stats.lookup.elapsed_s for r in results), default=0.0),
            max((r.stats.docs.elapsed_s for r in results), default=0.0)))
        return results

    def close(self) -> None:
        if self._close is not None:
            self._close()


class Compiles:
    """Backend compilations and persistent-cache hits (a hit is not a
    compilation), from JAX's monitoring events."""

    def __init__(self) -> None:
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
