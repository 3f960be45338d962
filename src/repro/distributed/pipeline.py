"""GPipe-style pipeline parallelism over a mesh axis (optional PP).

The default deployment is FSDP×TP; PP becomes attractive when per-layer
weights exceed what TP can hold or when cross-pod bandwidth makes FSDP
all-gathers dominant.  This module provides a minimal-but-real GPipe
schedule built on ``shard_map`` + ``ppermute``:

* the model's stages are split into S pipeline stages along the ``stage``
  mesh axis (each device group holds its stage's layers only);
* a microbatched forward runs the classic skewed schedule: at tick t, stage
  s processes microbatch t−s; activations move s→s+1 via ``ppermute``;
* bubble fraction = (S−1)/(M+S−1) with M microbatches (reported by
  :func:`bubble_fraction` and visible in the §Roofline analysis when PP is
  selected as a deployment dimension).

This is deliberately the simplest correct schedule (GPipe); the deployment
space exposes ``pp_microbatches`` so the search machinery can trade bubble
vs. activation memory.  Exercised by tests on a small (stage,) mesh.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "bubble_fraction"]


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_forward(stage_fn: Callable, num_stages: int,
                     num_microbatches: int, mesh: Mesh,
                     stage_axis: str = "stage"):
    """Build a pipelined forward.

    ``stage_fn(stage_params, x)`` applies ONE stage's layers to a microbatch
    activation ``x``; ``stage_params`` is the per-stage parameter slice
    (leading axis of size num_stages, sharded over the stage axis).

    Returns ``f(stage_params, x_microbatched)`` where ``x_microbatched`` has
    shape (num_microbatches·mb, ...) and is returned fully processed by all
    stages.
    """
    S, M = num_stages, num_microbatches

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(stage_axis), P(None)),
        out_specs=P(None),
    )
    def run(stage_params, xs):
        # stage_params: (1, ...) slice for this device's stage
        params_here = jax.tree.map(lambda a: a[0], stage_params)
        sid = jax.lax.axis_index(stage_axis)
        mb = xs.shape[0] // M
        micro = xs.reshape(M, mb, *xs.shape[1:])

        # skewed schedule: T = M + S - 1 ticks
        T = M + S - 1
        buf = jnp.zeros_like(micro[0])          # activation entering this stage
        outs = jnp.zeros_like(micro)            # completed microbatches (stage S-1)
        # carries become stage-varying inside the loop; mark them upfront
        buf = jax.lax.pcast(buf, (stage_axis,), to="varying")
        outs = jax.lax.pcast(outs, (stage_axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(micro, take, 0, keepdims=False)
            x_in = jnp.where(sid == 0, fresh, buf)
            active = (t - sid >= 0) & (t - sid < M)
            y = stage_fn(params_here, x_in)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch t-(S-1)
            done_idx = jnp.clip(t - (S - 1), 0, M - 1)
            record = (sid == S - 1) & (t - (S - 1) >= 0) & (t - (S - 1) < M)
            sel = (jnp.arange(M) == done_idx)[:, None, None] & record
            outs = jnp.where(sel, y[None], outs)
            # pass activations forward around the ring (stage s -> s+1)
            buf_next = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # only stage S-1 wrote real data, every other stage holds zeros —
        # a psum broadcasts the result to all stages
        outs = jax.lax.psum(outs, stage_axis)
        return outs.reshape(xs.shape)

    return run
