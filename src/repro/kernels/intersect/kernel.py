"""Pallas TPU kernels: L-way bitmap intersection + popcount.

The IoU Sketch query combine (paper §II-C): L superposts arrive as
document-space bitsets; the final postings list is their intersection.
On TPU we tile the document axis through VMEM in (8, 128)-aligned blocks
and fuse AND-reduce with population count in one pass, so candidate
counting (needed by top-K sampling, Eq. 6) costs no extra HBM traffic.

Two Mosaic kernels serve four entry points:

  * `intersect_batch_pallas` — a whole query batch: bitmaps (Q, L, W),
    2-D grid over (query, tile) so every query's AND tree runs in ONE
    `pallas_call` — the kernel-side half of the batched query engine
    (ragged batches are padded with all-ones layers, the AND identity);
  * `intersect_pallas` — one query, (L, W): a batch of one;
  * `combine_batch_pallas` — the query-planner generalization: each
    query carries a tiny compiled program of AND / OR / ANDNOT steps
    over its layers (the candidate-set algebra of an arbitrary boolean
    tree), evaluated slot-machine style per document tile. Programs are
    padded to one static step count; padding steps re-AND the running
    result with itself (the identity), so raggedness costs a few no-op
    vector ops, never a second `pallas_call`;
  * `combine_cluster_pallas` — the serving-tier generalization: a whole
    CLUSTER's combine work — every (shard, query) pair carries its own
    program over its own layers — runs as ONE `pallas_call`, the
    (shard, query) pairs flattened onto the batch kernel's query axis.
    The per-(shard, query) candidate counts it emits are exactly the
    round-1 statistics the global top-K sampling budget (paper Eq. 6)
    needs, so the scatter-gather path gets them with zero extra passes.

Layout: bitmaps (…, L, W) uint32, W = n_docs/32. The wrappers pad W to
a whole number of tiles and view it lane-dense as (…, W/128, 128); a
tile is (rows, 128) words with `rows` a multiple of 8, so every block's
last two dimensions are (8k, 128). Each grid step writes its tile of the
result and an (8, 128) tile of per-lane partial popcounts, which the
wrapper sums.

Combine programs travel by scalar prefetch (SMEM, one int32 triple per
step; a v5e has 1 MiB of SMEM, so a batch whose programs exceed
`PROGRAM_WORDS` is split along the query axis). The slot machine lives
in a VMEM scratch of L + S tile rows: slots 0..L-1 are the layers, step
s writes slot L+s, and operands are read by dynamic index into it.

VMEM per grid step, in (rows, 128) uint32 buffers of rows × 512 B:
2L for the double-buffered input block, L + S for the combine scratch,
2 for the output, plus 8 KiB of count tiles — (3L + S + 2) × rows ×
512 B in all. `_tile_rows` shrinks rows from 64 toward 8 to keep that
under `VMEM_BUDGET` (12 MiB, inside the 16 MiB scoped VMEM limit of a
TPU v5e). At L = S = 16 and rows = 64 it is 66 × 32 KiB ≈ 2.1 MiB; at
rows = 8 the budget holds 3L + S + 2 ≤ 3072, i.e. queries of up to ~750
leaves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import popcount

LANES = 128               # words per tile row (the vreg lane width)
SUBLANES = 8              # rows per vreg: tile rows come in multiples
MAX_TILE_ROWS = 64        # (64, 128) uint32 = 8192 words = 32 KiB a layer
VMEM_BUDGET = 12 << 20    # bytes; v5e's scoped VMEM limit is 16 MiB
PROGRAM_WORDS = 1 << 17   # int32 program words per call: half of SMEM

# opcodes of the combine program (shared with ops.compile/pack helpers)
OP_AND, OP_OR, OP_ANDNOT = 0, 1, 2


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_rows(W: int, n_buffers: int) -> int:
    """Rows per tile for a W-word document axis with `n_buffers`
    (rows, 128) uint32 buffers resident in VMEM per grid step."""
    rows = min(MAX_TILE_ROWS, _round_up(pl.cdiv(W, LANES), SUBLANES))
    while rows > SUBLANES and n_buffers * rows * LANES * 4 > VMEM_BUDGET:
        rows = max(SUBLANES, _round_up(rows // 2, SUBLANES))
    if n_buffers * rows * LANES * 4 > VMEM_BUDGET:
        raise ValueError(
            f"{n_buffers} tile buffers of {rows}x{LANES} words exceed the "
            f"{VMEM_BUDGET >> 20} MiB VMEM budget: too many query leaves "
            "for one fused combine")
    return rows


def _lane_dense(bitmaps: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(…, W) → (…, Wp/128, 128), W zero-padded to whole tiles."""
    W = bitmaps.shape[-1]
    pad = _round_up(W, rows * LANES) - W
    if pad:
        bitmaps = jnp.pad(bitmaps, [(0, 0)] * (bitmaps.ndim - 1) + [(0, pad)])
    return bitmaps.reshape(*bitmaps.shape[:-1], -1, LANES)


def _tile_counts(acc: jnp.ndarray) -> jnp.ndarray:
    """(rows, 128) words → (8, 128) per-lane partial popcounts."""
    bits = popcount(acc)
    out = bits[:SUBLANES]
    for r in range(SUBLANES, acc.shape[0], SUBLANES):
        out = out + bits[r:r + SUBLANES]
    return out


def _and_kernel(bm_ref, out_ref, cnt_ref):
    acc = bm_ref[0, 0]                      # (rows, 128) uint32
    for l in range(1, bm_ref.shape[1]):     # L is static — unrolled AND tree
        acc = jnp.bitwise_and(acc, bm_ref[0, l])
    out_ref[0] = acc
    cnt_ref[0] = _tile_counts(acc)


def _combine_kernel(prog_ref, bm_ref, out_ref, cnt_ref, slots_ref):
    """Evaluate one query's combine program on one document tile.

    `prog_ref` is the whole batch's flattened (Q·S·3) program in SMEM;
    `slots_ref` the (L + S, rows, 128) slot machine. Step operands are
    scalars read at run time, so one kernel instance serves every
    program shape of the batch — the unrolled loop is over the (static,
    padded) step count.
    """
    n_layers = bm_ref.shape[1]
    n_steps = slots_ref.shape[0] - n_layers
    slots_ref[:n_layers] = bm_ref[0]
    base = pl.program_id(0) * (n_steps * 3)
    acc = None
    for s in range(n_steps):
        op = prog_ref[base + 3 * s]
        a = slots_ref[prog_ref[base + 3 * s + 1]]
        b = slots_ref[prog_ref[base + 3 * s + 2]]
        # branch-free select on the scalar opcode: ANDNOT flips b, OR
        # picks the union
        flip = jnp.where(op == OP_ANDNOT, jnp.uint32(0xFFFFFFFF),
                         jnp.uint32(0))
        pick_or = jnp.where(op == OP_OR, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0))
        conj = jnp.bitwise_and(a, jnp.bitwise_xor(b, flip))
        acc = jnp.bitwise_or(jnp.bitwise_and(jnp.bitwise_or(a, b), pick_or),
                             jnp.bitwise_and(conj, jnp.bitwise_not(pick_or)))
        slots_ref[n_layers + s] = acc
    out_ref[0] = acc
    cnt_ref[0] = _tile_counts(acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_batch_pallas(bitmaps: jnp.ndarray, *, interpret: bool,
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """bitmaps: (Q, L, W) uint32 → (intersections (Q, W), counts (Q,)).

    Grid is (query, tile): program (q, i) ANDs the i-th document tile of
    query q's L layers and emits its partial popcounts — a whole batch of
    multi-term queries combines in one fused pass.
    """
    Q, L, W = bitmaps.shape
    rows = _tile_rows(W, 2 * L + 2)
    x = _lane_dense(bitmaps, rows)
    R = x.shape[2]
    n_tiles = R // rows
    out, counts = pl.pallas_call(
        _and_kernel,
        grid=(Q, n_tiles),
        in_specs=[pl.BlockSpec((1, L, rows, LANES),
                               lambda q, i: (q, 0, i, 0))],
        out_specs=[pl.BlockSpec((1, rows, LANES), lambda q, i: (q, i, 0)),
                   pl.BlockSpec((1, SUBLANES, LANES),
                                lambda q, i: (q, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Q, R, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((Q, n_tiles * SUBLANES, LANES),
                                        jnp.uint32)],
        interpret=interpret,
    )(x)
    return (out.reshape(Q, R * LANES)[:, :W],
            jnp.sum(counts, axis=(1, 2), dtype=jnp.uint32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_pallas(bitmaps: jnp.ndarray, *, interpret: bool,
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """bitmaps: (L, W) uint32 → (intersection (W,), total count ())."""
    out, counts = intersect_batch_pallas(bitmaps[None], interpret=interpret)
    return out[0], counts[0]


def _combine_call(programs: jnp.ndarray, x: jnp.ndarray, rows: int,
                  interpret: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One `pallas_call` over lane-dense (Q, L, R, 128) bitmaps."""
    Q, L, R, _ = x.shape
    S = programs.shape[1]
    n_tiles = R // rows
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Q, n_tiles),
            in_specs=[pl.BlockSpec((1, L, rows, LANES),
                                   lambda q, i, prog: (q, 0, i, 0))],
            out_specs=[pl.BlockSpec((1, rows, LANES),
                                    lambda q, i, prog: (q, i, 0)),
                       pl.BlockSpec((1, SUBLANES, LANES),
                                    lambda q, i, prog: (q, i, 0))],
            scratch_shapes=[pltpu.VMEM((L + S, rows, LANES), jnp.uint32)]),
        out_shape=[jax.ShapeDtypeStruct((Q, R, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((Q, n_tiles * SUBLANES, LANES),
                                        jnp.uint32)],
        interpret=interpret,
    )(programs.reshape(-1), x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_batch_pallas(bitmaps: jnp.ndarray, programs: jnp.ndarray, *,
                         interpret: bool,
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """bitmaps: (Q, L, W) uint32, programs: (Q, S, 3) int32 rows of
    (opcode, slot_a, slot_b) → (result bitmaps (Q, W), counts (Q,)).

    Grid is (query, tile): program (q, i) evaluates query q's combine
    program on its i-th document tile — a whole batch of arbitrary
    boolean trees (AND/OR/ANDNOT) combines in one fused pass. A batch
    whose programs outgrow `PROGRAM_WORDS` of SMEM runs as several
    `pallas_call`s over slices of the query axis.
    """
    Q, L, W = bitmaps.shape
    S = programs.shape[1]
    rows = _tile_rows(W, 3 * L + S + 2)
    x = _lane_dense(bitmaps, rows)
    chunk = max(1, PROGRAM_WORDS // (S * 3))
    parts = [_combine_call(programs[q:q + chunk], x[q:q + chunk], rows,
                           interpret) for q in range(0, Q, chunk)]
    out = jnp.concatenate([o for o, _c in parts])
    counts = jnp.concatenate([c for _o, c in parts])
    return (out.reshape(Q, -1)[:, :W],
            jnp.sum(counts, axis=(1, 2), dtype=jnp.uint32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_cluster_pallas(bitmaps: jnp.ndarray, programs: jnp.ndarray, *,
                           interpret: bool,
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """bitmaps: (G, Q, L, W) uint32, programs: (G, Q, S, 3) int32 →
    (result bitmaps (G, Q, W), counts (G, Q)).

    G indexes shard units, Q queries. The (shard, query) pairs flatten
    onto `combine_batch_pallas`'s query axis, so the whole cluster's
    combine round — every shard's every query's boolean program — runs
    in ONE fused pass; the (G, Q) candidate counts come back for free,
    feeding the Eq. 6 global top-K sampling budget without a second
    reduction pass.
    """
    G, Q, L, W = bitmaps.shape
    out, counts = combine_batch_pallas(
        bitmaps.reshape(G * Q, L, W),
        programs.reshape(G * Q, *programs.shape[2:]), interpret=interpret)
    return out.reshape(G, Q, W), counts.reshape(G, Q)
