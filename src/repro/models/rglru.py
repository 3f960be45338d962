"""RG-LRU recurrent mixer block (RecurrentGemma / Griffin).

The temporal-mixing half of a recurrent layer:
``x -> {gate branch: linear -> GeLU} ⊙ {recurrent branch: linear -> conv1d(W) -> RG-LRU} -> out proj``

The RG-LRU recurrence itself lives in the kernels package (`ops.rglru`):
associative scan on the XLA path, blocked Pallas scan on TPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import ParamDef
from .config import ModelConfig

__all__ = ["rglru_defs", "rglru_apply", "rglru_decode", "init_rglru_state",
           "RGLRUOptions"]


@dataclass(frozen=True)
class RGLRUOptions:
    impl: str = "xla"        # ref | xla | pallas
    block_d: int = 256


def rglru_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    r = cfg.resolved_lru_dim
    w = cfg.conv_width
    return {
        "w_gate_branch": ParamDef((d, r), ("embed", "lru")),
        "w_rec_branch": ParamDef((d, r), ("embed", "lru")),
        "conv_w": ParamDef((w, r), (None, "lru"), init="scaled"),
        "conv_b": ParamDef((r,), ("lru",), init="zeros"),
        "log_lambda": ParamDef((r,), ("lru",), init="lru_lambda"),
        "w_gate_a": ParamDef((r, r), ("lru", "lru_in"), scale=0.5),
        "w_gate_x": ParamDef((r, r), ("lru", "lru_in"), scale=0.5),
        "w_out": ParamDef((r, d), ("lru", "embed"), init="scaled"),
    }


def _causal_conv(u: jax.Array, conv_w: jax.Array, conv_b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv1d.  u: (B,S,R); conv_w: (W,R).
    ``state``: (B, W-1, R) trailing inputs from the previous segment.
    Returns (out (B,S,R), new_state (B,W-1,R))."""
    W = conv_w.shape[0]
    B, S, R = u.shape
    if state is None:
        state = jnp.zeros((B, W - 1, R), u.dtype)
    ext = jnp.concatenate([state.astype(u.dtype), u], axis=1)  # (B, S+W-1, R)
    out = jnp.zeros_like(u)
    for i in range(W):
        out = out + ext[:, i:i + S, :] * conv_w[i][None, None, :].astype(u.dtype)
    out = out + conv_b[None, None, :].astype(u.dtype)
    new_state = ext[:, S:, :] if W > 1 else state
    return out, new_state


def _mix(params, u: jax.Array, opts: RGLRUOptions, h0, conv_state):
    """Shared recurrent-branch computation. u: (B,S,R) post-projection."""
    conv_out, new_conv = _causal_conv(u, params["conv_w"], params["conv_b"],
                                      conv_state)
    gate_a = jnp.einsum("bsr,rq->bsq", conv_out, params["w_gate_a"].astype(u.dtype))
    gate_x = jnp.einsum("bsr,rq->bsq", conv_out, params["w_gate_x"].astype(u.dtype))
    h, h_last = ops.rglru(conv_out, params["log_lambda"], gate_a, gate_x, h0,
                          impl=opts.impl, block_d=opts.block_d)
    return h, h_last, new_conv


def rglru_apply(params, x: jax.Array, cfg: ModelConfig, opts: RGLRUOptions) -> jax.Array:
    """Full-sequence mixer.  x: (B,S,d) -> (B,S,d)."""
    cdt = x.dtype
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, params["w_gate_branch"].astype(cdt)))
    u = jnp.einsum("bsd,dr->bsr", x, params["w_rec_branch"].astype(cdt))
    h, _, _ = _mix(params, u, opts, None, None)
    return jnp.einsum("bsr,rd->bsd", gate * h, params["w_out"].astype(cdt))


def init_rglru_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    r = cfg.resolved_lru_dim
    return {
        "h": jnp.zeros((batch, r), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, r), dtype),
    }


def rglru_decode(params, x: jax.Array, state: dict, cfg: ModelConfig,
                 opts: RGLRUOptions):
    """One-token step.  x: (B,1,d).  Returns (y, new_state)."""
    cdt = x.dtype
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, params["w_gate_branch"].astype(cdt)))
    u = jnp.einsum("bsd,dr->bsr", x, params["w_rec_branch"].astype(cdt))
    h, h_last, new_conv = _mix(params, u, opts, state["h"], state["conv"])
    y = jnp.einsum("bsr,rd->bsd", gate * h, params["w_out"].astype(cdt))
    return y, {"h": h_last, "conv": new_conv}
