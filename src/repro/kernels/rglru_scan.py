"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

Grid: ``(B, d_blocks, t_chunks)`` — time chunks innermost so the hidden
state carries across chunks in VMEM scratch; the feature dimension is tiled
into VPU-aligned ``block_d`` lanes (the recurrence is elementwise, so this is
a VPU kernel, not an MXU one — the matmuls around it live in the layer).

The gate nonlinearities (softplus/σ/exp) are fused *into* the scan kernel so
x, gate_a, gate_x stream HBM→VMEM exactly once — on TPU this recurrence is
purely memory-bound and the fusion is the whole perf story (≈4 reads + 1
write per element vs 7+ for the unfused XLA associative-scan path).

Within a chunk the recurrence is a sequential ``fori_loop`` over groups of
8 rows of the VMEM block: a_t·h + b_t at VPU width ``block_d``.

The initial and final states travel as ``(B, 1, D)`` arrays with
``(1, 1, block_d)`` blocks: Mosaic requires the last two block dims to be
divisible by (8, 128) or equal to the array's, and a ``(1, block_d)`` block
of a ``(B, D)`` array is neither once B > 1.  The loop reads its per-step
coefficients from VMEM scratch refs because Mosaic cannot take a dynamic
slice of a value.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode

__all__ = ["rglru_pallas"]


def _kernel(x_ref, ga_ref, gx_ref, la_ref, h0_ref, h_out_ref, h_last_ref,
            h_scr, a_scr, b_scr, t_scr, *, c: float, chunk_t: int,
            rows: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)   # (1, block_d)

    x = x_ref[0].astype(jnp.float32)        # (chunk_t, block_d)
    ga = ga_ref[0].astype(jnp.float32)
    gx = gx_ref[0].astype(jnp.float32)
    log_lam = la_ref[...].astype(jnp.float32)  # (1, block_d)

    # fused gate math (read-once streaming); staged in VMEM so the
    # sequential loop below reads its rows from refs
    a_scr[...] = jnp.exp(-c * jax.nn.softplus(log_lam) * jax.nn.sigmoid(ga))
    a = a_scr[...]
    b_scr[...] = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (
        jax.nn.sigmoid(gx) * x)

    def group(g, h):
        # ``rows`` sequential steps per iteration: Mosaic stores to a
        # dynamic row offset only in whole (8, 128) tiles, so each group's
        # rows collect in a tile scratch and are written back at once
        tile = pl.ds(pl.multiple_of(g * rows, rows), rows)
        a_t, b_t = a_scr[tile, :], b_scr[tile, :]
        for j in range(rows):
            h = a_t[j:j + 1] * h + b_t[j:j + 1]
            t_scr[j:j + 1, :] = h
        h_out_ref[0, tile, :] = t_scr[...].astype(h_out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk_t // rows, group, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == nt - 1)
    def _fin():
        h_last_ref[0] = h.astype(h_last_ref.dtype)


def rglru_pallas(x: jax.Array, log_a: jax.Array, gate_a: jax.Array,
                 gate_x: jax.Array, h0: Optional[jax.Array] = None, *,
                 block_d: int = 256, chunk_t: int = 128, c: float = 8.0,
                 interpret: Optional[bool] = None):
    """x/gate_a/gate_x: (B,S,D); log_a: (D,).  Returns (h (B,S,D), h_last (B,D))."""
    B, S, D = x.shape
    block_d = min(block_d, D)
    chunk_t = min(chunk_t, S)
    if D % block_d or S % chunk_t:
        raise ValueError(f"(S={S}, D={D}) must divide (chunk_t={chunk_t}, "
                         f"block_d={block_d})")
    nd, nt = D // block_d, S // chunk_t
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    h0 = h0.reshape(B, 1, D)

    rows = math.gcd(chunk_t, 8)
    kernel = functools.partial(_kernel, c=c, chunk_t=chunk_t, rows=rows,
                               nt=nt)
    h, h_last = pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, chunk_t, block_d), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, chunk_t, block_d), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, chunk_t, block_d), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, block_d), lambda b, d, t: (0, d)),
            pl.BlockSpec((1, 1, block_d), lambda b, d, t: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk_t, block_d), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, 1, block_d), lambda b, d, t: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), x.dtype),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32),
                        pltpu.VMEM((chunk_t, block_d), jnp.float32),
                        pltpu.VMEM((chunk_t, block_d), jnp.float32),
                        pltpu.VMEM((rows, block_d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, gate_a, gate_x, log_a.reshape(1, D), h0)
    return h, h_last.reshape(B, D)
