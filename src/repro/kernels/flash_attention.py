"""Pallas TPU flash attention (GQA, causal/window) with explicit VMEM tiling.

Grid: ``(batch·heads, q_blocks, kv_blocks)`` — kv innermost, so the online
softmax state (m, l, acc) lives in VMEM scratch across kv iterations of one
q block (TPU grid steps execute sequentially per core, so scratch carries).
BlockSpecs stage (block_q × D) of Q and (block_kv × D) of K/V into VMEM per
step; blocks are sized so the working set
``(block_q + 2·block_kv)·D + block_q·block_kv`` fits VMEM with
MXU-aligned (multiples of 128) matmul dims.

GQA is handled in the K/V index map: query head ``h`` reads kv head
``h // (H/Hkv)`` — no repeated-KV materialization in HBM.

Validated against ``ref.attention_ref``; off the TPU the kernel runs under
the Pallas interpreter (see :func:`.ops.interpret_mode`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode
from .ref import NEG_INF

__all__ = ["flash_attention"]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            q_offset: int, kv_len: Optional[int], nk: int,
            block_q: int, block_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (block_q, D)
    k = k_ref[0].astype(jnp.float32)                  # (block_kv, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) \
        + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), bool)
    if kv_len is not None:
        mask &= k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
        if not causal:
            mask &= (k_pos - q_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_scr[...] * corr + p.sum(axis=-1)
    acc_new = acc_scr[...] * corr[:, None] + p @ v

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_kv: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,Sq,H,D); k/v: (B,Sk,Hkv,D).  Forward only (pair with the XLA
    custom-VJP path for training; the kernel targets serving/prefill)."""
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_kv
    kv_len = Sk if pad_k else None
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    nq, nk = Sq_p // block_q, Sk_p // block_kv

    # head-major flattening: q rows B·H, kv rows B·Hkv
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq_p, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk_p, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk_p, D)

    def kv_row(h, i, j):
        b = h // H
        hh = h % H
        return (b * Hkv + hh // G, j, 0)

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        q_offset=q_offset, kv_len=kv_len, nk=nk, block_q=block_q,
        block_kv=block_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, D), kv_row),
            pl.BlockSpec((1, block_kv, D), kv_row),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, D), q.dtype),
        scratch_shapes=[
            # online-softmax state persists in VMEM across kv grid steps
            pltpu.VMEM((block_q,), jnp.float32),       # m
            pltpu.VMEM((block_q,), jnp.float32),       # l
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        interpret=interpret_mode(interpret),
    )(qr, kr, vr)
    out = out.reshape(B, H, Sq_p, D).transpose(0, 2, 1, 3)
    return out[:, :Sq]
