"""Static + dynamic correctness tooling for the engine's invariants.

Two halves, one goal — turn "the reviewer remembered" into "CI proves
it":

  * `analysis.lint` — an AST-based invariant linter with repo-specific
    rules (raw clocks, direct store calls, unregistered locks,
    deprecated-surface references, kernel/ref parity, swallowed
    exceptions).  Run via ``scripts/lint_invariants.py --strict``.
  * `analysis.locks` — `OrderedLock`, a named lock wrapper that
    maintains a global acquisition-order graph and fails fast on
    lock-order inversions (armed via ``REPRO_LOCK_CHECK=1``) instead of
    letting a deadlock hang the soak test.
  * `analysis.spans` — the span recorder of the served path: named,
    nested host intervals with their batch and attributes, on the
    profiler's clock (each opens a `jax.profiler.TraceAnnotation`),
    and a `jit.compile` record per backend compile.

The package imports nothing outside the standard library at import time
(`spans` loads JAX only once it records), so every layer — storage,
index, kernels, serving — can import it without cycles.
"""

from .locks import (LockOrderViolation, OrderedLock, arm, armed,
                    bind_telemetry, contention_summary, order_edges,
                    ordered_condition, reset)

__all__ = [
    "LockOrderViolation", "OrderedLock", "arm", "armed",
    "bind_telemetry", "contention_summary", "order_edges",
    "ordered_condition", "reset",
]
