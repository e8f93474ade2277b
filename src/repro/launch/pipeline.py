"""GPipe-style pipeline parallelism via shard_map + lax.ppermute.

The production mesh uses FSDP×TP; this module adds the PP axis as an
optional mode for depth-dominated models on slow inter-pod links. Each
pipeline stage owns a contiguous block of layers; microbatches stream
through with `collective_permute` hops; the classic GPipe schedule runs
n_micro + n_stages - 1 ticks, bubbles included. The implementation is a
self-contained MLP pipeline used by tests and by the §Perf discussion —
the same skeleton lifts onto the transformer layer body (stage fn =
scanned layer block).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _pipe_varying(x):
    """Mark an initial carry device-varying over 'pipe'."""
    return jax.lax.pcast(x, ("pipe",), to="varying")


def _stage_fn(w, x):
    """One pipeline stage: the layer block owned by this device."""
    return jnp.tanh(x @ w)


def reference_mlp(ws: jax.Array, x: jax.Array) -> jax.Array:
    """Unpipelined oracle: apply all stages sequentially."""
    for i in range(ws.shape[0]):
        x = _stage_fn(ws[i], x)
    return x


def pipelined_mlp(mesh: Mesh, ws: jax.Array, x: jax.Array,
                  n_micro: int) -> jax.Array:
    """GPipe over the 'pipe' mesh axis.

    ws: (n_stages, d, d) — stage i's weights live on pipe device i.
    x:  (batch, d) — split into n_micro microbatches.
    """
    n_stages = mesh.shape["pipe"]
    batch, d = x.shape
    assert batch % n_micro == 0
    micro = batch // n_micro
    xs = x.reshape(n_micro, micro, d)

    def stage_program(w, xs_local):
        # w: (1, d, d) this stage's block; xs_local: (n_micro, micro, d)
        # replicated input feed — stage 0 consumes it, others ignore.
        stage = jax.lax.axis_index("pipe")
        w = w[0]
        n_ticks = n_micro + n_stages - 1
        # initial carries must already be device-varying over 'pipe'
        buf = _pipe_varying(jnp.zeros((micro, d), xs_local.dtype))
        outs = _pipe_varying(jnp.zeros((n_micro, micro, d),
                                       xs_local.dtype))

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain); others use buf
            feed = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs_local[feed], buf)
            y = _stage_fn(w, x_in)
            # the last stage records its finished microbatch (select, not
            # cond: under shard_map both sides must share varying-ness)
            done_idx = t - (n_stages - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(done_idx, 0), axis=0)
            take = (stage == n_stages - 1) & (done_idx >= 0)
            outs = jnp.where(take, updated, outs)
            # everyone forwards downstream (ring permute; wraparound values
            # land on stage 0 which ignores its buf)
            buf = jax.lax.ppermute(
                y, "pipe",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        return outs

    spec_w = P("pipe", None, None)
    spec_x = P()          # replicated microbatch feed
    out = jax.jit(jax.shard_map(
        stage_program, mesh=mesh, in_specs=(spec_w, spec_x),
        out_specs=P("pipe", None, None)))(ws, xs)
    # out: (n_stages*n_micro, micro, d) — every stage wrote its copy; only
    # the LAST stage's block holds the real results.
    out = out.reshape(n_stages, n_micro, micro, d).at[-1].get(
        out_sharding=NamedSharding(mesh, P()))
    return out.reshape(batch, d)
