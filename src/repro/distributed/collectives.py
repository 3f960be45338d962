"""Compressed cross-pod gradient collectives with error feedback.

At multi-pod scale the pod axis rides on DCN (data-center network), ~10-25
GB/s per host vs 200 GB/s aggregate ICI — the cross-pod gradient all-reduce
is the scaling bottleneck.  Standard mitigation: quantize the cross-pod
reduction to int8 with per-tensor scales and keep an *error-feedback* buffer
so quantization error is re-injected next step (Seide et al. 2014; 1-bit
Adam lineage) — unbiased long-run updates at 4× less DCN traffic than bf16.

``compressed_psum`` is built on ``shard_map`` over the pod axis and is
numerically validated in tests (convergence of error feedback, exactness
for representable values).  The intra-pod (ICI) reductions stay full
precision — only the slow axis is compressed.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["quantize_int8", "dequantize_int8", "compressed_psum",
           "compressed_grad_sync"]


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jax.Array, axis_name: str,
                    error: Optional[jax.Array] = None):
    """int8-compressed psum over ``axis_name`` with error feedback.

    Must be called inside shard_map/pmap with ``axis_name`` bound.  Returns
    (mean-reduced x (fp32), new error-feedback buffer).

    The quantization scale is SHARED across the group (pmax of local amax —
    one tiny fp32 collective) so that summing int8 payloads and multiplying
    once by the shared scale is exact per member; each member's residual
    goes into its own error-feedback buffer.
    """
    xf = x.astype(jnp.float32)
    if error is not None:
        xf = xf + error
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(jax.lax.pmax(amax, axis_name), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    new_error = xf - q.astype(jnp.float32) * scale
    # int8 payloads summed in int32 (no overflow for <= 2^23 members)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    mean = qsum.astype(jnp.float32) * scale / n
    return mean, new_error


def compressed_grad_sync(grads, error_buffers, mesh: Mesh,
                         pod_axis: str = "pod"):
    """Apply compressed_psum across the pod axis to a gradient pytree.

    Gradients are assumed already reduced within each pod (pjit does that);
    this syncs pod-level partial means over the slow DCN axis.  Everything
    else (params etc.) is untouched.  Returns (synced grads, new errors).
    """
    flat, treedef = jax.tree.flatten(grads)
    err_flat = (jax.tree.leaves(error_buffers)
                if error_buffers is not None else [None] * len(flat))

    in_specs = tuple(P() for _ in flat)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(in_specs, in_specs),
        out_specs=(in_specs, in_specs),
    )
    def sync(gs, errs):
        outs, new_errs = [], []
        for g, e in zip(gs, errs):
            m, ne = compressed_psum(g, pod_axis, e)
            outs.append(m.astype(g.dtype))
            new_errs.append(ne)
        return tuple(outs), tuple(new_errs)

    err_in = tuple(jnp.zeros_like(g, jnp.float32) if e is None else e
                   for g, e in zip(flat, err_flat))
    outs, new_errs = sync(tuple(flat), err_in)
    return treedef.unflatten(list(outs)), treedef.unflatten(list(new_errs))
