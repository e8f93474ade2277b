"""JAX's persistent compilation cache, for entry-point scripts.

Call `enable_compile_cache()` once at the top of a script's `main`
(`chip_smoke.py`, `benchmarks/run.py`) — never from library code, so
`import repro` changes no JAX configuration. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads the directory from it and
nothing else is chosen here; otherwise the cache lives at the fixed path
`<checkout>/.jax_cache` (git-ignored). The path is part of each entry's
key, so it must not move between runs.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the cache on for every compile, however short; return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the intersect kernels compile in well under the 1 s default floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
