"""Pallas TPU grouped matmul for MoE expert compute.

TPU adaptation note (see DESIGN.md): GPU MegaBlocks-style gmm handles
*dynamic* group boundaries with data-dependent tile→expert maps.  On TPU the
production MoE path (``moe.py`` 'capacity' dispatch) produces a *static*
uniform-capacity layout (E, C, d), so the kernel is a block-tiled batched
matmul over experts — every matmul dim MXU-aligned, accumulation over the
contraction dim in fp32 VMEM scratch:

  grid = (E, C/block_m, f/block_n, d/block_k)   (k innermost)

The dynamic-group-sizes variant stays on the XLA path (`ref.gmm_ref`), which
is also the oracle this kernel is tested against (with groups padded to
capacity).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode

__all__ = ["gmm_stacked_pallas", "gmm_pallas"]


def _kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)      # (block_m, block_k)
    w = w_ref[0].astype(jnp.float32)      # (block_k, block_n)
    acc_scr[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def gmm_stacked_pallas(xs: jax.Array, w: jax.Array, *, block_m: int = 128,
                       block_n: int = 128, block_k: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """xs: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    E, C, d = xs.shape
    _, _, f = w.shape
    block_m = min(block_m, C)
    block_n = min(block_n, f)
    block_k = min(block_k, d)
    pad_m, pad_n, pad_k = (-C) % block_m, (-f) % block_n, (-d) % block_k
    if pad_m or pad_k:
        xs = jnp.pad(xs, ((0, 0), (0, pad_m), (0, pad_k)))
    if pad_n or pad_k:
        w = jnp.pad(w, ((0, 0), (0, pad_k), (0, pad_n)))
    Cp, dp, fp = C + pad_m, d + pad_k, f + pad_n
    nm, nn, nk = Cp // block_m, fp // block_n, dp // block_k

    kernel = functools.partial(_kernel, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(E, nm, nn, nk),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda e, m, n, k: (e, m, k)),
            pl.BlockSpec((1, block_k, block_n), lambda e, m, n, k: (e, k, n)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n), lambda e, m, n, k: (e, m, n)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, fp), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xs, w)
    return out[:, :C, :f]


def gmm_pallas(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
               block_m: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """Dynamic-group-size entry point: pads each group to the max group size
    into the stacked layout, runs the stacked kernel, then unpads.  (On TPU
    the capacity dispatch already produces the stacked layout directly —
    this wrapper exists for API parity with `ref.gmm_ref`.)"""
    T, d = x.shape
    E = w.shape[0]
    C = T  # worst case: everything in one group
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(T)
    in_group = (row[:, None] >= starts[None, :]) & (row[:, None] < ends[None, :])
    gid = jnp.argmax(in_group, axis=1)
    valid = in_group.any(axis=1)
    pos = row - starts[gid]
    xs = jnp.zeros((E, C, d), x.dtype).at[gid, pos].set(
        jnp.where(valid[:, None], x, 0))
    out_s = gmm_stacked_pallas(xs, w, block_m=block_m, interpret=interpret)
    out = out_s[gid, pos]
    return jnp.where(valid[:, None], out, 0).astype(x.dtype)
