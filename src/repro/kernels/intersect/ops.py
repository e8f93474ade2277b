"""Public ops for IoU intersection: bitmap conversion + kernel dispatch.

Whether the Pallas kernels run compiled or in the Pallas interpreter is
decided here and nowhere else, from the default backend: compiled
(Mosaic) on a TPU, interpreted on the CPU (where the tests run), and an
`UnsupportedBackendError` anywhere else. `impl="ref"` selects the
pure-jnp oracle explicitly; no backend falls back to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (OP_AND, OP_ANDNOT, OP_OR, combine_batch_pallas,
                     combine_cluster_pallas, intersect_batch_pallas,
                     intersect_pallas)
from .ref import (combine_batch_ref, combine_cluster_ref,
                  intersect_batch_ref, intersect_ref)


class UnsupportedBackendError(RuntimeError):
    """The default JAX backend can neither compile nor interpret the
    intersect kernels."""


def interpret_mode() -> bool:
    """True when the kernels must run in the Pallas interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise UnsupportedBackendError(
        f"intersect kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


def postings_to_bitmap(postings: list[np.ndarray], n_docs: int) -> np.ndarray:
    """Sorted doc-id arrays → (L, ceil(n_docs/32)) uint32 bitsets."""
    W = (n_docs + 31) // 32
    out = np.zeros((len(postings), W), dtype=np.uint32)
    for l, docs in enumerate(postings):
        docs = np.asarray(docs, dtype=np.uint64)
        np.bitwise_or.at(out[l], (docs // 32).astype(np.int64),
                         np.uint32(1) << (docs % 32).astype(np.uint32))
    return out


def postings_to_bitmap_batch(postings_batch: list[list[np.ndarray]],
                             n_docs: int) -> np.ndarray:
    """Ragged batch of doc-id lists → (Q, L_max, W) uint32 bitsets.

    Queries with fewer than L_max postings lists are padded with all-ones
    layers — the AND identity — so one fused kernel call handles a batch
    of queries with different term counts.
    """
    L_max = max(len(p) for p in postings_batch)
    W = (n_docs + 31) // 32
    out = np.full((len(postings_batch), L_max, W), 0xFFFFFFFF,
                  dtype=np.uint32)
    for q, posts in enumerate(postings_batch):
        out[q, :len(posts)] = postings_to_bitmap(posts, n_docs)
    return out


def bitmap_to_docs(bitmap: np.ndarray) -> np.ndarray:
    """Intersection bitset → sorted uint32 doc ids."""
    bits = np.unpackbits(
        np.asarray(bitmap, dtype=np.uint32).view(np.uint8), bitorder="little")
    return np.flatnonzero(bits).astype(np.uint32)


def intersect(bitmaps, impl: str = "pallas"):
    """(L, W) uint32 → (bitmap (W,), count ()). impl: pallas | ref."""
    bitmaps = jnp.asarray(bitmaps, dtype=jnp.uint32)
    if impl == "ref":
        return intersect_ref(bitmaps)
    return intersect_pallas(bitmaps, interpret=interpret_mode())


def intersect_batch(bitmaps, impl: str = "pallas"):
    """(Q, L, W) uint32 → (bitmaps (Q, W), counts (Q,)). impl: pallas | ref."""
    bitmaps = jnp.asarray(bitmaps, dtype=jnp.uint32)
    if impl == "ref":
        return intersect_batch_ref(bitmaps)
    return intersect_batch_pallas(bitmaps, interpret=interpret_mode())


def pack_programs(programs: list[list[tuple[int, int, int]]],
                  n_layers: int) -> np.ndarray:
    """Ragged per-query combine programs → one (Q, S_max, 3) int32 array.

    Each program row is (opcode, slot_a, slot_b); slots 0..n_layers-1
    are the query's input layers and step s writes slot n_layers+s.
    Shorter programs are padded with AND(result, result) — the identity
    — so the whole batch evaluates in one fused kernel call. An empty
    program (single-layer query) becomes AND(layer0, layer0).
    """
    S = max(1, max(len(p) for p in programs))
    out = np.empty((len(programs), S, 3), dtype=np.int32)
    for q, prog in enumerate(programs):
        for s in range(S):
            if s < len(prog):
                out[q, s] = prog[s]
            else:                 # chain the last result through: r & r
                prev = n_layers + s - 1 if s else 0
                out[q, s] = (OP_AND, prev, prev)
    return out


def pack_cluster_programs(programs: list[list[list[tuple[int, int, int]]]],
                          n_layers: int) -> np.ndarray:
    """Ragged per-(shard, query) programs → one (G, Q, S_max, 3) array.

    `programs[g][q]` is shard-unit g's combine program for query q; all
    groups must cover the same Q queries. Flattens through
    `pack_programs` so every program is padded to the cluster-wide
    S_max with the chained identity step (AND of the previous result
    with itself) — zero-padding here would overwrite each result slot
    with layer 0.
    """
    Q = len(programs[0])
    if any(len(g) != Q for g in programs):
        raise ValueError("all shard groups must carry the same Q queries")
    flat = pack_programs([p for g in programs for p in g], n_layers)
    return flat.reshape(len(programs), Q, flat.shape[1], 3)


W_CLASS_MIN = 1024     # words: the narrowest bitmap width class


def shape_class(n: int, least: int = 1) -> int:
    """The power of two at or above `n`, and at least `least`."""
    return max(least, 1 << max(n - 1, 0).bit_length())


def pad_to_shape_class(bitmaps: np.ndarray, programs: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Pad a combine batch to its shape class, so that a stream of
    batches of many shapes compiles a few kernels, not one per shape.

    bitmaps (..., Q, L, W) uint32 and programs (..., Q, S, 3) int32, as
    `combine_batch` and `combine_cluster` take them. Q, L and S go up to
    a power of two, W to one of at least `W_CLASS_MIN` words (as far as
    the kernel's own tiles pad a narrower one). Added queries, layers
    and words are zero; added steps chain the last result through
    (AND(r, r)), and step slots move up past the added layers. The
    result's first Q rows, cut to W words, and their counts are the
    unpadded batch's.
    """
    *lead, Q, L, W = bitmaps.shape
    S = programs.shape[-2]
    Qc, Lc, Sc = shape_class(Q), shape_class(L), shape_class(S)
    x = np.zeros((*lead, Qc, Lc, shape_class(W, W_CLASS_MIN)), np.uint32)
    x[..., :Q, :L, :W] = bitmaps
    chain = Lc + np.arange(Sc) - 1
    chain[0] = 0                       # an added query: AND(layer 0, 0)
    p = np.empty((*lead, Qc, Sc, 3), np.int32)
    p[..., 0] = OP_AND
    p[..., 1] = p[..., 2] = chain
    given = np.array(programs, dtype=np.int32)
    slots = given[..., 1:]
    slots[slots >= L] += Lc - L
    p[..., :Q, :S, :] = given
    return x, p


def combine_batch(bitmaps, programs, impl: str = "pallas"):
    """Evaluate per-query AND/OR/ANDNOT programs over layered bitsets.

    bitmaps: (Q, L, W) uint32; programs: (Q, S, 3) int32 (see
    `pack_programs`) → (result bitmaps (Q, W), counts (Q,)).
    impl: pallas | ref.
    """
    bitmaps = jnp.asarray(bitmaps, dtype=jnp.uint32)
    if impl == "ref":
        return combine_batch_ref(bitmaps, programs)
    return combine_batch_pallas(bitmaps, jnp.asarray(programs,
                                                     dtype=jnp.int32),
                                interpret=interpret_mode())


def combine_cluster(bitmaps, programs, impl: str = "pallas"):
    """Evaluate a whole cluster's combine round in one fused call.

    bitmaps: (G, Q, L, W) uint32 — axis 0 is the shard unit; programs:
    (G, Q, S, 3) int32 (`pack_programs` per shard, padded to a common
    S/L). Returns (result bitmaps (G, Q, W), counts (G, Q)) — the
    counts are the per-(shard, query) candidate totals that drive the
    global top-K sampling budget. impl: pallas | ref.
    """
    bitmaps = jnp.asarray(bitmaps, dtype=jnp.uint32)
    if impl == "ref":
        return combine_cluster_ref(bitmaps, programs)
    return combine_cluster_pallas(bitmaps, jnp.asarray(programs,
                                                       dtype=jnp.int32),
                                  interpret=interpret_mode())
