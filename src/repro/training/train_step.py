"""Sharded training step: loss → grads (with microbatch accumulation) →
AdamW update, built for pjit with explicit in/out shardings.

Microbatch gradient accumulation runs as ``lax.scan`` over microbatches —
with batch sharded over DP axes, XLA schedules each microbatch's gradient
reduce-scatter to overlap the next microbatch's compute (the standard
latency-hiding structure).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import (DeploymentConfig, batch_specs, param_specs)
from ..models.config import ModelConfig
from ..models.model import LMModel
from .optimizer import AdamWConfig, adamw_init, adamw_update

__all__ = ["make_train_step", "train_state_specs", "init_train_state"]


def train_state_specs(model: LMModel, deployment: DeploymentConfig) -> dict:
    pspecs = param_specs(model.logical_specs(), deployment)
    return {"params": pspecs,
            "m": pspecs,
            "v": pspecs,
            "step": P()}


def init_train_state(model: LMModel, key) -> dict:
    params = model.init(key)
    opt = adamw_init(params)
    return {"params": params, "m": opt["m"], "v": opt["v"], "step": opt["step"]}


def make_train_step(model: LMModel, deployment: DeploymentConfig, mesh: Mesh,
                    opt_cfg: Optional[AdamWConfig] = None, jit: bool = True):
    """Returns (train_step, state_specs, batch_spec_tree).

    ``train_step(state, batch) -> (state, metrics)``; batch is the GLOBAL
    batch {tokens/embeds, labels}, sharded per ``batch_specs``.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    n_micro = deployment.microbatches
    state_specs = train_state_specs(model, deployment)
    bspecs = batch_specs(model.cfg, deployment, kind="train")

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    cdt = deployment.model_options().policy.compute_dtype

    def _maybe_cast(params):
        if not deployment.cast_params_once:
            return params
        # one fp32->bf16 stream per STEP; microbatches then read bf16
        # weights (the in-layer .astype becomes a no-op)
        return jax.tree.map(
            lambda p: p.astype(cdt) if p.dtype == jnp.float32 and p.ndim > 1
            else p, params)

    def grads_of(params, batch):
        params = _maybe_cast(params)
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads
        # microbatch accumulation: split the per-device batch rows
        def split(x):
            b = x.shape[0]
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(acc, mb):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            acc_loss, acc_grads = acc
            return (acc_loss + loss,
                    jax.tree.map(jnp.add, acc_grads, grads)), metrics

        zero = (jnp.zeros(()),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (loss_sum, grad_sum), metrics = jax.lax.scan(body, zero, micro)
        inv = 1.0 / n_micro
        return loss_sum * inv, jax.tree.map(lambda x: x[-1], metrics), \
            jax.tree.map(lambda g: g * inv, grad_sum)

    def train_step(state, batch):
        loss, metrics, grads = grads_of(state["params"], batch)
        params, opt, opt_metrics = adamw_update(
            grads, {"m": state["m"], "v": state["v"], "step": state["step"]},
            state["params"], opt_cfg)
        new_state = {"params": params, "m": opt["m"], "v": opt["v"],
                     "step": opt["step"]}
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return new_state, out_metrics

    if not jit:
        return train_step, state_specs, bspecs

    metric_specs = {k: P() for k in
                    ("loss", "ce", "aux", "grad_norm", "lr")}
    step_jit = jax.jit(
        train_step,
        in_shardings=(jax.tree.map(lambda p: NamedSharding(mesh, p), state_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
                      jax.tree.map(lambda p: NamedSharding(mesh, p), bspecs,
                                   is_leaf=lambda x: isinstance(x, P))),
        out_shardings=(jax.tree.map(lambda p: NamedSharding(mesh, p), state_specs,
                                    is_leaf=lambda x: isinstance(x, P)),
                       jax.tree.map(lambda p: NamedSharding(mesh, p), metric_specs,
                                    is_leaf=lambda x: isinstance(x, P))),
        donate_argnums=(0,),
    )
    return step_jit, state_specs, bspecs
