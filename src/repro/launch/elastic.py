"""Elastic scaling: pick a mesh for whatever devices are alive and
re-shard checkpoints onto it.

A 1000-node fleet loses nodes; the framework must keep training on what
remains. `choose_mesh` factorizes the live device count into (data, model)
preferring a target model-parallel width; `reshard_restore` loads any
checkpoint (saved from any topology — leaves are stored unsharded, the
separation-of-compute-and-storage way) onto the new mesh's shardings.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..models.common import rules_for
from ..training.checkpoint import CheckpointManager


def choose_mesh(n_devices: int | None = None, prefer_model: int = 16):
    """Largest (data, model) factorization with model | prefer_model."""
    n = n_devices or len(jax.devices())
    model = prefer_model
    while model > 1 and (n % model or model > n):
        model //= 2
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def reshard_restore(ckpt: CheckpointManager, model, mesh, step=None,
                    with_opt: bool = True):
    """Restore the latest (or given) checkpoint onto `mesh`."""
    from ..models.common import abstract_params
    import jax.numpy as jnp

    rules = rules_for(mesh)
    desc = model.param_desc()
    params_sh = rules.sharding_tree(desc)
    params_abs = abstract_params(desc)
    state_like = {"params": params_abs}
    shardings = {"params": params_sh}
    if with_opt:
        state_like["opt"] = {
            "m": params_abs, "v": params_abs,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}
        shardings["opt"] = {
            "m": params_sh, "v": params_sh,
            "step": jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())}
    state, manifest = ckpt.restore(state_like, step=step,
                                   shardings=shardings)
    return state, manifest
