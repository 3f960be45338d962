"""GQA attention layer (mixer half of a transformer layer).

Supports: causal/global, sliding-window (local), bidirectional (encoder),
rotary embeddings with partial-rotary fraction, and single-token decode over
either a full KV cache or a ring-buffer window cache.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import ParamDef, apply_rope, make_rope
from .config import ModelConfig

__all__ = ["attention_defs", "attention_apply", "attention_decode",
           "init_kv_cache", "AttnOptions"]


@dataclass(frozen=True)
class AttnOptions:
    """Deployment-searchable attention options."""

    impl: str = "xla"         # ref | xla | pallas
    q_chunk: int = 512
    kv_chunk: int = 512
    band_skip: bool = True
    # shard query heads over this mesh axis inside attention even when the
    # head count doesn't divide it (GSPMD pads) — rescues architectures like
    # llama4 (40 heads vs 16-way TP) from replicated attention compute
    shard_heads: Optional[str] = None
    shard_batch: tuple = ()


def _constrain_heads(x: jax.Array, opts: "AttnOptions") -> jax.Array:
    if opts.shard_heads is None:
        return x
    from jax.sharding import PartitionSpec as P
    bt = tuple(opts.shard_batch) or None
    return jax.lax.with_sharding_constraint(
        x, P(bt, None, opts.shard_heads, None))


def attention_defs(cfg: ModelConfig) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"), init="scaled"),
    }


def _project_qkv(params, x, cfg: ModelConfig, positions):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,Hkv,hd), rope applied."""
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cdt))
    sin, cos, rot_dim = make_rope(positions, cfg.resolved_head_dim,
                                  cfg.rope_theta, cfg.rotary_fraction)
    q = apply_rope(q, sin, cos, rot_dim)
    k = apply_rope(k, sin, cos, rot_dim)
    return q, k, v


def attention_apply(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                    window: Optional[int], opts: AttnOptions) -> jax.Array:
    """Full-sequence attention.  x: (B,S,d); positions: (B,S)."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    q = _constrain_heads(q, opts)
    out = ops.attention(
        q, k, v, causal=cfg.causal, window=window, impl=opts.impl,
        q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
        band_skip=opts.band_skip,
    )
    out = _constrain_heads(out, opts)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int,
                  window: Optional[int], dtype) -> dict:
    """KV cache for one attention layer.  Window layers use a ring buffer of
    capacity min(window, capacity) — this is what makes 5:1 local:global and
    1-attn:2-recurrent architectures cheap at long context."""
    c = min(window, capacity) if window is not None else capacity
    shape = (batch, c, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(params, x: jax.Array, cache: dict, index,
                     cfg: ModelConfig, window: Optional[int],
                     opts: AttnOptions):
    """One-token decode.  x: (B,1,d); index: absolute position (traced scalar).

    Keys are stored post-rope, so the ring buffer needs no position metadata
    beyond ``index``.  Returns (out (B,1,d), new_cache).
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), index, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)

    capacity = cache["k"].shape[1]
    ring = window is not None and capacity <= window
    slot = (index % capacity) if ring else index
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                           (0, slot, 0, 0))
    out = ops.decode_attention(q, k_cache, v_cache, index=index, window=window,
                               ring=ring, impl=opts.impl)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, {"k": k_cache, "v": v_cache}


def prefill_kv_cache(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                     window: Optional[int], capacity: int, opts: AttnOptions):
    """Full-sequence attention that also returns the populated KV cache."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    out = ops.attention(q, k, v, causal=cfg.causal, window=window,
                        impl=opts.impl, q_chunk=opts.q_chunk,
                        kv_chunk=opts.kv_chunk, band_skip=opts.band_skip)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    S = x.shape[1]
    c = min(window, capacity) if window is not None else capacity
    if S >= c:
        k_cache, v_cache = k[:, S - c:], v[:, S - c:]
        if window is not None:
            # ring layout: position p lives at slot p % c
            shift = (S - c) % c
            k_cache = jnp.roll(k_cache, shift, axis=1)
            v_cache = jnp.roll(v_cache, shift, axis=1)
    else:
        pad = [(0, 0), (0, c - S), (0, 0), (0, 0)]
        k_cache, v_cache = jnp.pad(k, pad), jnp.pad(v, pad)
    return y, {"k": k_cache, "v": v_cache}
