"""Jitted dispatch wrappers for the kernel package.

Every hot-spot has three interchangeable implementations selected by the
deployment configuration (and therefore searchable by the Discovery Space
machinery):

* ``ref``    — pure-jnp oracle (full materialization; tests/small shapes).
* ``xla``    — memory-bounded lax.scan implementations (production fallback,
               and what the CPU-only dry-run lowers).
* ``pallas`` — the TPU Pallas kernels with explicit VMEM BlockSpecs.

Whether a Pallas kernel is compiled or interpreted is not an option: it
follows the platform (:func:`interpret_mode`), so a kernel always compiles
on a TPU and runs under the Pallas interpreter everywhere else.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import xla_attn as _xla_attn

__all__ = ["attention", "decode_attention", "rglru", "gmm", "gmm_stacked",
           "interpret_mode"]


def interpret_mode(explicit: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs under the interpreter: on any backend
    but the TPU, the only one that compiles them.  An explicit value (a
    kernel's ``interpret=`` argument, which tests pin) wins."""
    if explicit is not None:
        return bool(explicit)
    return jax.default_backend() != "tpu"


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              q_offset: int = 0, impl: str = "xla",
              q_chunk: int = 512, kv_chunk: int = 512,
              band_skip: bool = True,
              interpret: Optional[bool] = None) -> jax.Array:
    """Full-sequence GQA attention.  q: (B,S,H,D); k/v: (B,S,Hkv,D)."""
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    if impl == "xla":
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        cq, ck = min(q_chunk, Sq), min(kv_chunk, Sk)
        pad_q = (-Sq) % cq
        pad_k = (-Sk) % ck
        if pad_q or pad_k:
            qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            out = _xla_attn.attention_banded(qp, kp, vp, causal, window,
                                             q_offset, cq, ck, band_skip, Sk)
            return out[:, :Sq]
        return _xla_attn.attention_banded(q, k, v, causal, window, q_offset,
                                          cq, ck, band_skip, None)
    if impl == "pallas":
        from . import flash_attention as _fa
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, block_q=q_chunk,
                                   block_kv=kv_chunk, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     index, window: Optional[int] = None, ring: bool = False,
                     impl: str = "xla") -> jax.Array:
    """One-token attention over a KV cache (all impls share the ref path —
    decode scores are O(S) and memory-light)."""
    return _ref.decode_attention_ref(q, k_cache, v_cache, index=index,
                                     window=window, ring=ring)


def rglru(x: jax.Array, log_a: jax.Array, gate_a: jax.Array, gate_x: jax.Array,
          h0: Optional[jax.Array] = None, *, impl: str = "xla",
          block_d: int = 256, interpret: Optional[bool] = None):
    """RG-LRU linear recurrence.  x/gates: (B,S,D); returns ((B,S,D), (B,D))."""
    if impl == "ref":
        return _ref.rglru_ref(x, log_a, gate_a, gate_x, h0)
    if impl == "xla":
        return _rglru_assoc(x, log_a, gate_a, gate_x, h0)
    if impl == "pallas":
        from . import rglru_scan as _rg
        return _rg.rglru_pallas(x, log_a, gate_a, gate_x, h0,
                                block_d=block_d, interpret=interpret)
    raise ValueError(f"unknown rglru impl {impl!r}")


def _rglru_assoc(x, log_a, gate_a, gate_x, h0=None, c: float = 8.0):
    """Parallel (associative-scan) RG-LRU — the XLA production path:
    O(S log S) depth instead of O(S) sequential steps."""
    B, S, D = x.shape
    xf = x.astype(jnp.float32)
    a_exp = -c * jax.nn.softplus(log_a.astype(jnp.float32))[None, None, :] * \
        jax.nn.sigmoid(gate_a.astype(jnp.float32))
    a = jnp.exp(a_exp)
    gated_x = jax.nn.sigmoid(gate_x.astype(jnp.float32)) * xf
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * gated_x
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0, :].add(a[:, 0, :] * h0.astype(jnp.float32))

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, b_l * a_r + b_r

    a_s, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(x.dtype), h[:, -1, :]


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
        impl: str = "xla", block_m: int = 128,
        interpret: Optional[bool] = None) -> jax.Array:
    """Grouped matmul: x (T,d) rows grouped contiguously; w (E,d,f)."""
    if impl in ("ref", "xla"):
        return _ref.gmm_ref(x, w, group_sizes)  # XLA path shares the oracle
    if impl == "pallas":
        from . import gmm as _gmm
        return _gmm.gmm_pallas(x, w, group_sizes, block_m=block_m,
                               interpret=interpret)
    raise ValueError(f"unknown gmm impl {impl!r}")


def gmm_stacked(xs: jax.Array, w: jax.Array, *, impl: str = "xla",
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                interpret: Optional[bool] = None) -> jax.Array:
    """Static-capacity grouped matmul: xs (E,C,d) × w (E,d,f) -> (E,C,f).
    This is the production MoE expert-compute primitive on TPU."""
    if impl in ("ref", "xla"):
        return jnp.einsum("ecd,edf->ecf", xs, w.astype(xs.dtype))
    if impl == "pallas":
        from . import gmm as _gmm
        return _gmm.gmm_stacked_pallas(xs, w, block_m=block_m, block_n=block_n,
                                       block_k=block_k, interpret=interpret)
    raise ValueError(f"unknown gmm impl {impl!r}")
