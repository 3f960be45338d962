"""Mixture-of-Experts FFN with top-k routing.

Dispatch strategies (deployment-searchable):

* ``capacity`` — sort-based static-capacity dispatch (default).  Tokens are
  ranked within their expert group; tokens past the per-expert capacity
  ``C = ceil(T·k/E · capacity_factor)`` are dropped (standard TPU MoE
  practice — static shapes, no data-dependent memory).  Expert compute is a
  stacked einsum over the (E, C, d) buffer, sharded over experts (EP) when
  E divides the model axis, else over the expert hidden dim (TP).
* ``dense``    — every expert computes every token, masked combine.  The
  oracle used in tests; O(E/k) wasteful, never deployed.
* ``gmm``      — grouped matmul over the sorted token matrix (Pallas kernel
  or its XLA twin), skipping capacity padding.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import ParamDef
from .config import ModelConfig

__all__ = ["moe_defs", "moe_apply", "MoEOptions"]


@dataclass(frozen=True)
class MoEOptions:
    impl: str = "capacity"      # capacity | dense | gmm
    capacity_factor: float = 1.25
    min_capacity: int = 4       # capacity floor (matters for tiny token counts)
    gmm_impl: str = "xla"       # xla | pallas (inner grouped-matmul kernel)


def moe_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    defs = {
        "router": ParamDef((d, e), ("embed", "experts_router")),
        "w_gate": ParamDef((e, d, f), ("experts", "embed", "moe_mlp")),
        "w_up": ParamDef((e, d, f), ("experts", "embed", "moe_mlp")),
        "w_down": ParamDef((e, f, d), ("experts", "moe_mlp", "embed"), init="scaled"),
    }
    if cfg.shared_expert:
        defs["shared"] = {
            "w_gate": ParamDef((d, f), ("embed", "mlp")),
            "w_up": ParamDef((d, f), ("embed", "mlp")),
            "w_down": ParamDef((f, d), ("mlp", "embed"), init="scaled"),
        }
    return defs


def _router(params, xf: jax.Array, cfg: ModelConfig):
    """xf: (T, d) fp32.  Returns top-k (T,k) expert ids, combine weights, and
    the router aux loss (load-balancing, Switch-style)."""
    logits = xf @ params["router"].astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.experts_per_token
    weights, experts = jax.lax.top_k(probs, k)                  # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_e (fraction routed to e) * (mean prob of e)
    E = cfg.num_experts
    onehot = jax.nn.one_hot(experts[:, 0], E)                   # top-1 fraction
    aux = E * jnp.mean(onehot.mean(0) * probs.mean(0)) * E
    return experts, weights, aux


def moe_apply(params, x: jax.Array, cfg: ModelConfig, opts: MoEOptions):
    """x: (B,S,d) -> (y, aux_loss)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    experts, weights, aux = _router(params, xt.astype(jnp.float32), cfg)
    if opts.impl == "dense":
        y = _dense_moe(params, xt, experts, weights, cfg)
    elif opts.impl == "capacity":
        y = _capacity_moe(params, xt, experts, weights, cfg, opts)
    elif opts.impl == "gmm":
        y = _gmm_moe(params, xt, experts, weights, cfg, opts)
    else:
        raise ValueError(f"unknown moe impl {opts.impl!r}")
    if cfg.shared_expert:
        sp = params["shared"]
        cdt = x.dtype
        g = jnp.einsum("td,df->tf", xt, sp["w_gate"].astype(cdt))
        u = jnp.einsum("td,df->tf", xt, sp["w_up"].astype(cdt))
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                           sp["w_down"].astype(cdt))
    return y.reshape(B, S, d).astype(x.dtype), aux


def _expert_ffn(params, xs: jax.Array, cdt, opts: "MoEOptions" = None) -> jax.Array:
    """xs: (E, C, d) -> (E, C, d) through each expert's gated MLP.
    Uses the stacked grouped-matmul primitive (Pallas kernel on TPU)."""
    gi = opts.gmm_impl if opts is not None else "xla"
    g = ops.gmm_stacked(xs, params["w_gate"], impl=gi)
    u = ops.gmm_stacked(xs, params["w_up"], impl=gi)
    return ops.gmm_stacked((jax.nn.silu(g.astype(jnp.float32)) *
                            u.astype(jnp.float32)).astype(cdt),
                           params["w_down"], impl=gi)


def _dense_moe(params, xt, experts, weights, cfg):
    """Oracle: all experts on all tokens, masked combine."""
    cdt = xt.dtype
    E = cfg.num_experts
    ys = _expert_ffn(params, jnp.broadcast_to(xt, (E,) + xt.shape), cdt)  # (E,T,d)
    combine = jnp.zeros((xt.shape[0], E), jnp.float32)
    for i in range(cfg.experts_per_token):
        combine += jax.nn.one_hot(experts[:, i], E) * weights[:, i:i + 1]
    return jnp.einsum("te,etd->td", combine.astype(cdt), ys)


def _capacity_moe(params, xt, experts, weights, cfg, opts):
    """Sort-based static-capacity dispatch."""
    cdt = xt.dtype
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(opts.min_capacity, math.ceil(T * k / E * opts.capacity_factor))
    C = min(C, T)  # never more capacity than tokens

    flat_e = experts.reshape(T * k)                      # expert id per slot
    flat_w = weights.reshape(T * k)
    token_src = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e, stable=True)             # group by expert
    es, ws, src = flat_e[order], flat_w[order], token_src[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[es]                 # rank within expert
    keep = pos < C
    pos_c = jnp.where(keep, pos, 0)

    # scatter tokens into the (E, C, d) dispatch buffer
    buf = jnp.zeros((E, C, d), cdt)
    rows = xt[src] * keep[:, None].astype(cdt)
    buf = buf.at[es, pos_c].add(rows)                    # unique (es,pos) when kept

    ys = _expert_ffn(params, buf, cdt, opts)             # (E, C, d)

    y_tok = ys[es, pos_c] * (ws * keep)[:, None].astype(cdt)
    out = jnp.zeros((T, d), cdt).at[src].add(y_tok)
    return out


def _gmm_moe(params, xt, experts, weights, cfg, opts):
    """Grouped-matmul dispatch over sorted tokens (no capacity padding)."""
    cdt = xt.dtype
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    flat_e = experts.reshape(T * k)
    flat_w = weights.reshape(T * k)
    token_src = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    es, ws, src = flat_e[order], flat_w[order], token_src[order]
    group_sizes = jnp.bincount(flat_e, length=E)

    xs = xt[src]                                          # (T·k, d) sorted
    gi = opts.gmm_impl
    g = ops.gmm(xs, params["w_gate"], group_sizes, impl=gi)
    u = ops.gmm(xs, params["w_up"], group_sizes, impl=gi)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(cdt)
    y = ops.gmm(h, params["w_down"], group_sizes, impl=gi)
    y = y * ws[:, None].astype(cdt)
    return jnp.zeros((T, d), cdt).at[src].add(y)
