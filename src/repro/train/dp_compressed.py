"""Data-parallel train step with int8 error-feedback gradient compression.

A ``shard_map`` wrapper: each DP shard computes grads on its microbatch,
compresses, psums over the dp axis (int8 payload — 4× fewer bytes on the
slow cross-pod links), applies error feedback, then a replicated AdamW
update. Opt-in alternative to the GSPMD-managed pjit path for bandwidth-
constrained multi-pod DP of replicated-weight models (the small archs);
numerics validated against the uncompressed path in tests.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.collectives import compressed_psum, psum_mean
from .optimizer import AdamWConfig, adamw_update

Tree = Any


def make_dp_train_step(
    model,
    mesh,
    opt_cfg: Optional[AdamWConfig] = None,
    dp_axis: str = "data",
    compress: bool = True,
    remat: bool = False,
) -> Callable:
    """Returns step(params, opt_state, error_fb, batch) → (params, opt,
    error_fb, metrics). Params replicated; batch sharded over ``dp_axis``."""
    opt_cfg = opt_cfg or AdamWConfig()

    def loss_fn(params, mb):
        return model.loss(params, mb, remat=remat)

    def shard_body(params, opt_state, error_fb, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress:
            grads, error_fb = compressed_psum(grads, error_fb, dp_axis)
        else:
            grads = psum_mean(grads, dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
        new_params, new_opt, metrics = adamw_update(grads, opt_state, params, opt_cfg)
        return new_params, new_opt, error_fb, dict(metrics, loss=loss)

    rep = P()
    batch_spec = P(dp_axis)

    def batch_specs(batch):
        return {k: batch_spec for k in batch}

    def step(params, opt_state, error_fb, batch):
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(rep, rep, rep, {k: batch_spec for k in batch}),
            out_specs=(rep, rep, rep, rep),
            check_vma=False,
        )(params, opt_state, error_fb, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def init_error_feedback(params: Tree) -> Tree:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
