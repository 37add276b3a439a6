"""Pure data-parallel outer loop with compressed gradient sync.

At 1000+ nodes the outer loop is plain DP over the ``pod``/``data`` axes
(each replica group holds a full model copy, TP inside). This module is the
explicit-collective version of that outer loop: fwd/bwd runs inside a
shard_map over the DP axis with *local* gradients, the sync is a visible
collective we control — which is where the int8 compression (compress.py)
plugs in. The lowered HLO then carries int8 all_to_all/all_gather instead
of f32 all-reduce: a 4x wire-byte cut, checkable in the dry-run.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..models.transformer import RunFlags
from ..models.model import build_loss_fn
from .compress import compressed_pmean_tree
from .optimizer import AdamWConfig, adamw_update


def build_ddp_train_step(cfg: ModelConfig, flags: RunFlags, oc: AdamWConfig,
                         mesh: jax.sharding.Mesh, dp_axis: str = "data",
                         compress: bool = True) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    params/opt replicated; batch sharded along ``dp_axis``. Gradients are
    averaged over the DP axis by the int8-compressed all-reduce (or exact
    pmean when ``compress=False``).
    """
    loss_fn = build_loss_fn(cfg, flags)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress:
            grads = compressed_pmean_tree(grads, dp_axis)
        else:
            grads = jax.lax.pmean(grads, dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
        new_p, new_s, metrics = adamw_update(oc, params, grads, opt_state)
        metrics["loss"] = loss
        return new_p, new_s, metrics

    def batch_spec(batch):
        return jax.tree.map(
            lambda x: P(dp_axis, *([None] * (x.ndim - 1))), batch)

    def step(params, opt_state, batch):
        rep = jax.tree.map(lambda _: P(), params)
        rep_o = jax.tree.map(lambda _: P(), opt_state)
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep_o, batch_spec(batch)),
            out_specs=(rep, rep_o,
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False)
        return fn(params, opt_state, batch)

    return step
