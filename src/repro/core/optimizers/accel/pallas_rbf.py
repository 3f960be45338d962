"""Pallas TPU kernel for the pairwise squared-distance / RBF kernel matrix.

At large history the GP fit is dominated by building the two Gram blocks
K(X, X) (|H|²·d) and K(Xc, X) (|pool|·|H|·d).  The numpy reference
materializes the full (M, N, d) broadcast difference before reducing — a
memory-bound O(M·N·d) temporary.  This kernel streams (block_m, d) ×
(block_n, d) tiles through VMEM and fuses the ``|a|² + |b|² − 2ab``
expansion with the exponential, so the MXU does the contraction and the
(M, N) output is written once.

Follows the repo kernel conventions (``src/repro/kernels/``): explicit
BlockSpecs, fp32 accumulation via ``preferred_element_type``, lane padding
to 128, the interpreter off the TPU (:func:`repro.kernels.ops.interpret_mode`)
so the kernel is testable everywhere, and a pure-jnp oracle
(:func:`rbf_matrix_jnp`) the pallas path is regression-gated against.
Import of pallas itself is deferred and failure-tolerant:
:func:`pallas_available` gates dispatch, and callers fall back to the jnp
path on any platform where pallas is absent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["rbf_matrix_jnp", "rbf_matrix_pallas", "pallas_available"]

#: TPU lane width — the trailing block dim must be a multiple of this.
_LANES = 128


def pallas_available() -> bool:
    """True when ``jax.experimental.pallas`` imports on this install."""
    try:  # pragma: no cover - trivially true on the baked toolchain
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401
        return True
    except Exception:  # pragma: no cover - pallas-less installs
        return False


def rbf_matrix_jnp(A: jax.Array, B: jax.Array, inv2ls2: jax.Array) -> jax.Array:
    """Pure-jnp oracle: ``exp(-d²(A, B) * inv2ls2)`` via the dot-expansion
    (no (M, N, d) temporary), where ``inv2ls2 = 1 / (2·ls²)``."""
    d2 = ((A * A).sum(-1)[:, None] + (B * B).sum(-1)[None, :]
          - 2.0 * A @ B.T)
    return jnp.exp(-jnp.maximum(d2, 0.0) * inv2ls2)


def _rbf_block(s_ref, a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)  # (block_m, d_pad)
    b = b_ref[...].astype(jnp.float32)  # (block_n, d_pad)
    # zero-padded feature columns contribute 0 to every distance term;
    # full-f32 MXU passes: the default single bf16 pass would cost the
    # distance expansion its parity with the float64 reference
    d2 = ((a * a).sum(axis=1)[:, None] + (b * b).sum(axis=1)[None, :]
          - 2.0 * jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                      precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32))
    o_ref[...] = jnp.exp(-jnp.maximum(d2, 0.0) * s_ref[0, 0])


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def _rbf_pallas_call(A, B, inv2ls2, *, block_m, block_n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, d = A.shape
    N = B.shape[0]
    bm, bn = min(block_m, M), min(block_n, N)
    pad_m, pad_n, pad_d = (-M) % bm, (-N) % bn, (-d) % _LANES
    if pad_m or pad_d:
        A = jnp.pad(A, ((0, pad_m), (0, pad_d)))
    if pad_n or pad_d:
        B = jnp.pad(B, ((0, pad_n), (0, pad_d)))
    Mp, Np, dp = M + pad_m, N + pad_n, d + pad_d
    scale = jnp.asarray(inv2ls2, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _rbf_block,
        grid=(Mp // bm, Np // bn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        interpret=interpret,
    )(scale, A.astype(jnp.float32), B.astype(jnp.float32))
    return out[:M, :N]


def rbf_matrix_pallas(A: jax.Array, B: jax.Array, inv2ls2, *,
                      block_m: int = 256, block_n: int = 256,
                      interpret=None) -> jax.Array:
    """Blocked pallas RBF Gram matrix; ``interpret=None`` follows the
    platform (:func:`repro.kernels.ops.interpret_mode`)."""
    from ....kernels.ops import interpret_mode
    return _rbf_pallas_call(A, B, jnp.asarray(inv2ls2, jnp.float32),
                            block_m=block_m, block_n=block_n,
                            interpret=interpret_mode(interpret))
