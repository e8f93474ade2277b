"""Pure-jnp oracles for the IoU combine kernel."""

from __future__ import annotations

import jax.numpy as jnp


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Per-element bit population count of a uint32 array."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> 24


def intersect_ref(bitmaps: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """L-way AND + popcount. bitmaps: (L, W) uint32 document bitsets.

    Returns (intersection bitmap (W,), total matching documents ()).
    """
    out = bitmaps[0]
    for l in range(1, bitmaps.shape[0]):
        out = jnp.bitwise_and(out, bitmaps[l])
    return out, jnp.sum(popcount(out), dtype=jnp.uint32)


def combine_batch_ref(bitmaps: jnp.ndarray, programs,
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the AND/OR/ANDNOT program evaluator.

    bitmaps: (Q, L, W) uint32; programs: (Q, S, 3) int rows of
    (opcode, slot_a, slot_b) — slots 0..L-1 are the layers, step s
    writes slot L+s, the last step's slot is the query's result.
    Returns (result bitmaps (Q, W), per-query counts (Q,)).
    """
    import numpy as np

    programs = np.asarray(programs)
    outs = []
    for q in range(bitmaps.shape[0]):
        slots = [bitmaps[q, l] for l in range(bitmaps.shape[1])]
        for op, a, b in programs[q]:
            va, vb = slots[int(a)], slots[int(b)]
            if op == 0:                                   # AND
                slots.append(jnp.bitwise_and(va, vb))
            elif op == 1:                                 # OR
                slots.append(jnp.bitwise_or(va, vb))
            else:                                         # ANDNOT
                slots.append(jnp.bitwise_and(va, jnp.bitwise_not(vb)))
        outs.append(slots[-1])
    out = jnp.stack(outs)
    return out, jnp.sum(popcount(out), axis=1, dtype=jnp.uint32)


def combine_cluster_ref(bitmaps: jnp.ndarray, programs,
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the cluster-fused program evaluator.

    bitmaps: (G, Q, L, W) uint32 — shard-unit g's layered bitsets for
    query q; programs: (G, Q, S, 3). Evaluates every (shard, query)
    program independently (`combine_batch_ref` per shard) and returns
    (result bitmaps (G, Q, W), counts (G, Q)).
    """
    outs, cnts = [], []
    for g in range(bitmaps.shape[0]):
        out, cnt = combine_batch_ref(bitmaps[g], programs[g])
        outs.append(out)
        cnts.append(cnt)
    return jnp.stack(outs), jnp.stack(cnts)
