"""Public ops for IoU intersection: bitmap conversion + kernel dispatch.

Whether the Pallas kernels run compiled or in the Pallas interpreter is
decided here and nowhere else, from the default backend: compiled
(Mosaic) on a TPU, interpreted on the CPU (where the tests run), and an
`UnsupportedBackendError` anywhere else. `impl="ref"` selects the
pure-jnp oracle explicitly; no backend falls back to it.

Two batch entry points, `combine_batch` (one index unit) and
`combine_cluster` (every shard unit of a cluster), run the one kernel
body of `kernel.py`; `intersect`, one query's L-way AND, is its AND
chain. The shape a batch compiles at is decided here too, and nowhere
else: both batch ops pad every batch to its `launch_class` — Q a power
of two, L and S powers of two of at least 4, W the class of the index
unit's document count — and cut the result back to the batch's own rows
and words. A stream of batches then runs a few compiled programs, which
`warm_shape_classes` can compile ahead of the first batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (OP_AND, OP_ANDNOT, OP_OR, combine_batch_pallas,
                     combine_cluster_pallas)
from .ref import combine_batch_ref, combine_cluster_ref, intersect_ref


class UnsupportedBackendError(RuntimeError):
    """The default JAX backend can neither compile nor interpret the
    intersect kernels."""


def compiles() -> bool:
    """True when the kernels run compiled (on a TPU), where each new
    launch shape costs a compile."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """True when the kernels must run in the Pallas interpreter."""
    if compiles():
        return False
    backend = jax.default_backend()
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

    Queries with fewer than L_max postings lists are padded with zero
    layers, which a combine program never reads, so one fused kernel
    call handles a batch of queries with different term counts; a query
    with no layers reads as empty. Each layer is packed from a flag per
    document up to its query's largest id, so a query of a few ids costs
    a few words whatever W is.
    """
    L_max = max((len(p) for p in postings_batch), default=0)
    W = (n_docs + 31) // 32
    out = np.zeros((len(postings_batch), L_max, W), dtype=np.uint32)
    for q, posts in enumerate(postings_batch):
        top = max((int(np.max(d)) + 1 for d in posts if len(d)), default=0)
        flags = np.zeros(-(-top // 32) * 32, np.bool_)
        for l, docs in enumerate(posts):
            flags[docs] = True
            out[q, l, :len(flags) // 32] = np.packbits(
                flags, bitorder="little").view(np.uint32)
            flags[docs] = False
    return out


def bitmap_to_docs(bitmap: np.ndarray, n_bits: int | None = None,
                   ) -> np.ndarray:
    """Intersection bitset → sorted uint32 doc ids, below `n_bits` when
    given: only the words that hold them are unpacked, so a row of a
    few dozen keys in a wide launch costs a few words, not the row."""
    bitmap = np.asarray(bitmap, dtype=np.uint32)
    if n_bits is not None:
        bitmap = bitmap[:(n_bits + 31) // 32]
    bits = np.unpackbits(np.ascontiguousarray(bitmap).view(np.uint8),
                         bitorder="little")
    if n_bits is not None:
        bits = bits[:n_bits]
    return np.flatnonzero(bits).astype(np.uint32)


def intersect(bitmaps, impl: str = "pallas"):
    """(L, W) uint32 → (bitmap (W,), count ()). impl: pallas | ref.

    One query's L-way AND: the AND chain over its layers, run by the
    `combine_batch` kernel as a batch of one."""
    bitmaps = jnp.asarray(bitmaps, dtype=jnp.uint32)
    if impl == "ref":
        return intersect_ref(bitmaps)
    L = bitmaps.shape[0]
    chain = [(OP_AND, L + s - 1 if s else 0, s + 1) for s in range(L - 1)]
    out, counts = combine_batch_pallas(
        bitmaps[None], jnp.asarray(pack_programs([chain], L)),
        interpret=interpret_mode())
    return out[0], counts[0]


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
LS_CLASS_MIN = 4       # layers and program steps: the narrowest classes


def shape_class(n: int, least: int = 1) -> int:
    """The power of two at or above `n`, and at least `least`."""
    return max(least, 1 << max(n - 1, 0).bit_length())


def unit_words(n_docs: int) -> int:
    """The bitmap width class of an index unit of `n_docs` documents.

    A job's universe is the union of its candidate keys in one unit, so
    it never holds more keys than the unit has documents: every launch
    over the unit fits this one width."""
    return shape_class((n_docs + 31) // 32, W_CLASS_MIN)


def launch_class(Q: int, L: int, W: int, S: int, *,
                 n_docs: int) -> dict[str, int]:
    """The shape a launch of a batch of Q queries, L layers, W words and
    S program steps compiles at: Q a power of two; L and S powers of two
    of at least `LS_CLASS_MIN`; W the class of the unit's `n_docs`
    documents, or of W itself should a batch be wider than its unit.
    Returns `Qc`, `Lc`, `Wc` and `Sc`."""
    return dict(Qc=shape_class(Q), Lc=shape_class(L, LS_CLASS_MIN),
                Wc=max(unit_words(n_docs), shape_class(W, W_CLASS_MIN)),
                Sc=shape_class(S, LS_CLASS_MIN))


def pad_to_shape_class(bitmaps: np.ndarray, programs: np.ndarray,
                       n_docs: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad a batch to its `launch_class`, so that a stream of batches of
    many shapes runs a few compiled kernels, not one per shape.

    bitmaps (..., Q, L, W) uint32 and programs (..., Q, S, 3) int32, as
    `combine_batch` and `combine_cluster` take them. Added queries,
    layers and words are zero, added steps chain the last result through
    (AND(r, r)), and step slots move up past the added layers. The
    result's first Q rows, cut to W words, and their counts are the
    unpadded batch's.
    """
    *lead, Q, L, W = bitmaps.shape
    S = np.shape(programs)[-2]
    c = launch_class(Q, L, W, S, n_docs=n_docs)
    Qc, Lc, Sc = c["Qc"], c["Lc"], c["Sc"]
    x = np.zeros((*lead, Qc, Lc, c["Wc"]), np.uint32)
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


def _launch(kernel, bitmaps, programs, n_docs: int,
            ) -> tuple[np.ndarray, np.ndarray]:
    """Run `kernel` on the batch padded to its class; the result cut
    back to the batch's own Q rows and W words, in C order (the chip
    may hand a result back in another layout)."""
    interpret = interpret_mode()
    bitmaps = np.asarray(bitmaps, dtype=np.uint32)
    *_lead, Q, _L, W = bitmaps.shape
    x, p = pad_to_shape_class(bitmaps, programs, n_docs)
    out, counts = kernel(x, p, interpret=interpret)
    return (np.ascontiguousarray(np.asarray(out)[..., :Q, :W]),
            np.asarray(counts)[..., :Q])


def combine_batch(bitmaps, programs, impl: str = "pallas", *,
                  n_docs: int):
    """Evaluate per-query AND/OR/ANDNOT programs over layered bitsets.

    bitmaps: (Q, L, W) uint32; programs: (Q, S, 3) int32 (see
    `pack_programs`) → (result bitmaps (Q, W), counts (Q,)).
    impl: pallas | ref. `n_docs` is the document count of the index unit
    the batch's keys come from, which fixes the launch's width class
    (`launch_class`).
    """
    if impl == "ref":
        return combine_batch_ref(jnp.asarray(bitmaps, dtype=jnp.uint32),
                                 programs)
    return _launch(combine_batch_pallas, bitmaps, programs, n_docs)


def combine_cluster(bitmaps, programs, impl: str = "pallas", *,
                    n_docs: int):
    """Evaluate a whole cluster's combine round in one fused call.

    bitmaps: (G, Q, L, W) uint32 — axis 0 is the shard unit; programs:
    (G, Q, S, 3) int32 (`pack_programs` per shard, padded to a common
    S/L). Returns (result bitmaps (G, Q, W), counts (G, Q)) — the
    counts are the per-(shard, query) candidate totals that drive the
    global top-K sampling budget. impl: pallas | ref. `n_docs` is the
    largest unit's document count.
    """
    if impl == "ref":
        return combine_cluster_ref(jnp.asarray(bitmaps, dtype=jnp.uint32),
                                   programs)
    return _launch(combine_cluster_pallas, bitmaps, programs, n_docs)


def warm_shape_classes(n_docs: int, q_max: int, groups: int = 0,
                       ) -> list[tuple[int, ...]]:
    """Launch `combine_batch` once on zeros at every Q class up to
    `q_max`, at the L, S and W classes of a unit of `n_docs` documents,
    so that the jit cache holds every program a batch of up to `q_max`
    jobs over that unit asks for. `groups` > 0 warms `combine_cluster`
    over that many shard units (`n_docs` the largest unit's) in its
    place. Returns each launch's (G, Q) or (Q,) shape."""
    launched = []
    for Q in (1 << i for i in range(shape_class(q_max).bit_length())):
        if groups:
            combine_cluster(np.zeros((groups, Q, 1, 1), np.uint32),
                            np.zeros((groups, Q, 1, 3), np.int32),
                            n_docs=n_docs)
            launched.append((groups, Q))
        else:
            combine_batch(np.zeros((Q, 1, 1), np.uint32),
                          np.zeros((Q, 1, 3), np.int32), n_docs=n_docs)
            launched.append((Q,))
    return launched
