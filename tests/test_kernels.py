"""Kernel validation: Pallas (interpret=True) and XLA paths vs jnp oracles.

Sweeps shapes/dtypes per kernel and asserts allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm import gmm_pallas, gmm_stacked_pallas
from repro.kernels.ref import (attention_ref, decode_attention_ref, gmm_ref,
                               rglru_ref)
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.xla_attn import attention_banded


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


# ---------------------------------------------------------------- attention


ATTN_CASES = [
    # (B, Sq, Sk, H, Hkv, D, causal, window, dtype)
    (1, 64, 64, 4, 4, 32, True, None, jnp.float32),
    (2, 128, 128, 8, 2, 64, True, None, jnp.float32),
    (2, 128, 128, 8, 2, 64, True, 32, jnp.float32),
    (1, 96, 96, 4, 1, 16, True, None, jnp.float32),   # odd length, GQA=4
    (2, 64, 64, 4, 4, 32, False, None, jnp.float32),  # encoder
    (2, 64, 64, 4, 2, 32, True, None, jnp.bfloat16),
    (1, 128, 128, 2, 2, 128, True, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_attention_vs_ref(case, impl):
    B, Sq, Sk, H, Hkv, D, causal, window, dtype = case
    rng = np.random.default_rng(42)
    q = rand(rng, (B, Sq, H, D), dtype)
    k = rand(rng, (B, Sk, Hkv, D), dtype)
    v = rand(rng, (B, Sk, Hkv, D), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    got = ops.attention(q, k, v, causal=causal, window=window, impl=impl,
                        q_chunk=32, kv_chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_attention_banded_gradients_match_ref():
    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, 128, 4, 2, 32
    q = rand(rng, (B, S, H, D), jnp.float32)
    k = rand(rng, (B, S, Hkv, D), jnp.float32)
    v = rand(rng, (B, S, Hkv, D), jnp.float32)

    def loss_ref(q, k, v):
        return (attention_ref(q, k, v, causal=True, window=48) ** 2).sum()

    def loss_band(q, k, v):
        return (ops.attention(q, k, v, causal=True, window=48, impl="xla",
                              q_chunk=32, kv_chunk=32) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_band, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gb):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


@given(
    sq=st.integers(1, 5), sk=st.integers(1, 5),
    hkv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 4]),
    causal=st.booleans(),
    window=st.sampled_from([None, 8, 16]),
    seed=st.integers(0, 100),
)
@settings(max_examples=25, deadline=None)
@pytest.mark.slow
def test_property_banded_equals_ref(sq, sk, hkv, g, causal, window, seed):
    """Banded attention == oracle for arbitrary chunkings/shapes (queries at
    the causal suffix: q_offset = Sk - Sq >= 0; fully-masked rows are
    degenerate in the oracle and excluded by construction)."""
    Sq, Sk = sq * 16, sk * 16
    if Sq > Sk:
        Sq = Sk
    q_offset = Sk - Sq
    if not causal and window is not None and q_offset > 0:
        q_offset = 0
        Sq = Sk  # symmetric-window encoder: keep query/key sets aligned
    rng = np.random.default_rng(seed)
    q = rand(rng, (1, Sq, hkv * g, 16), jnp.float32)
    k = rand(rng, (1, Sk, hkv, 16), jnp.float32)
    v = rand(rng, (1, Sk, hkv, 16), jnp.float32)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)
    got = attention_banded(q, k, v, causal, window, q_offset, 16, 16, True, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_ring_buffer():
    """Ring-buffer window cache == full cache with window mask."""
    rng = np.random.default_rng(1)
    B, H, Hkv, D, S, W = 2, 4, 2, 32, 64, 16
    q = rand(rng, (B, 1, H, D), jnp.float32)
    k_full = rand(rng, (B, S, Hkv, D), jnp.float32)
    v_full = rand(rng, (B, S, Hkv, D), jnp.float32)
    index = S - 1
    ref = decode_attention_ref(q, k_full, v_full, index=index, window=W)
    # ring layout: position p at slot p % W; valid positions index-W+1..index
    slots = np.array([(index - ((index - s) % W)) for s in range(W)])
    k_ring = k_full[:, slots]
    v_ring = v_full[:, slots]
    got = decode_attention_ref(q, k_ring, v_ring, index=index, window=W, ring=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- RG-LRU


RGLRU_CASES = [
    (1, 64, 32, jnp.float32, None),
    # the two big-sequence cases compile for ~5-7 s each on CPU under the
    # xla impl; the small cases already cover both h0 modes + block_d < D,
    # so the big shapes run in the slow tier
    pytest.param((2, 128, 64, jnp.float32, "h0"), marks=pytest.mark.slow),
    pytest.param((2, 256, 128, jnp.bfloat16, None), marks=pytest.mark.slow),
    (1, 128, 96, jnp.float32, "h0"),   # block_d smaller than D
]


@pytest.mark.parametrize("case", RGLRU_CASES)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_rglru_vs_ref(case, impl):
    B, S, D, dtype, h0_kind = case
    rng = np.random.default_rng(7)
    x = rand(rng, (B, S, D), dtype)
    ga = rand(rng, (B, S, D), dtype)
    gx = rand(rng, (B, S, D), dtype)
    log_a = jnp.asarray(np.log(-np.log(rng.uniform(0.9, 0.999, D))), jnp.float32)
    h0 = rand(rng, (B, D), jnp.float32) if h0_kind else None
    ref_h, ref_last = rglru_ref(x, log_a, ga, gx, h0)
    got_h, got_last = ops.rglru(x, log_a, ga, gx, h0, impl=impl,
                                block_d=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got_h, np.float32),
                               np.asarray(ref_h, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(ref_last),
                               rtol=1e-3, atol=1e-3)


def test_rglru_pallas_chunking_invariance():
    rng = np.random.default_rng(3)
    B, S, D = 2, 128, 64
    x = rand(rng, (B, S, D), jnp.float32)
    ga = rand(rng, (B, S, D), jnp.float32)
    gx = rand(rng, (B, S, D), jnp.float32)
    log_a = jnp.asarray(np.log(-np.log(rng.uniform(0.9, 0.999, D))), jnp.float32)
    h1, l1 = rglru_pallas(x, log_a, ga, gx, block_d=64, chunk_t=128)
    h2, l2 = rglru_pallas(x, log_a, ga, gx, block_d=16, chunk_t=32)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- grouped matmul


GMM_CASES = [
    (4, 32, 16, 24, jnp.float32),
    (3, 64, 32, 48, jnp.float32),
    (2, 128, 64, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("case", GMM_CASES)
def test_gmm_stacked_vs_einsum(case):
    E, C, d, f, dtype = case
    rng = np.random.default_rng(11)
    xs = rand(rng, (E, C, d), dtype)
    w = rand(rng, (E, d, f), dtype)
    ref = jnp.einsum("ecd,edf->ecf", xs.astype(jnp.float32),
                     w.astype(jnp.float32))
    got = gmm_stacked_pallas(xs, w, block_m=16, block_n=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               **tol(dtype))


@given(e=st.integers(2, 5), t=st.integers(4, 24), seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
@pytest.mark.slow
def test_property_gmm_dynamic_groups(e, t, seed):
    rng = np.random.default_rng(seed)
    d, f = 8, 12
    sizes = rng.multinomial(t, np.ones(e) / e)
    x = rand(rng, (t, d), jnp.float32)
    w = rand(rng, (e, d, f), jnp.float32)
    gs = jnp.asarray(sizes)
    ref = gmm_ref(x, w, gs)
    got = gmm_pallas(x, w, gs, block_m=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_matches_dense_when_no_drops():
    """The capacity path equals the dense oracle when capacity is generous."""
    from repro.configs import get_config
    from repro.models import moe as moe_mod
    from repro.models.common import init_tree
    from repro.models.moe import MoEOptions

    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    params = init_tree(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    x = rand(rng, (2, 16, cfg.d_model), jnp.float32)
    y_dense, aux1 = moe_mod.moe_apply(params, x, cfg,
                                      MoEOptions(impl="dense"))
    y_cap, aux2 = moe_mod.moe_apply(
        params, x, cfg, MoEOptions(impl="capacity", capacity_factor=50.0,
                                   min_capacity=64))
    y_gmm, aux3 = moe_mod.moe_apply(params, x, cfg, MoEOptions(impl="gmm"))
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_gmm), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    assert np.isclose(float(aux1), float(aux2))


# ---------------------------------------------------------------- interpret


def test_pallas_interpret_mode_follows_the_platform():
    """Compiled on a TPU, interpreted everywhere else; an explicit argument
    still wins."""
    assert ops.interpret_mode() is (jax.default_backend() != "tpu")
    assert ops.interpret_mode() is True  # this suite runs on the CPU
    assert ops.interpret_mode(False) is False


@pytest.mark.parametrize("options", ["attention.AttnOptions", "moe.MoEOptions",
                                     "rglru.RGLRUOptions"])
def test_model_options_carry_no_interpret_switch(options):
    """Whether a kernel is interpreted is the platform's call, never a
    deployment option that could leave the interpreter on over a chip."""
    import dataclasses
    import importlib

    module, name = options.split(".")
    cls = getattr(importlib.import_module(f"repro.models.{module}"), name)
    assert "interpret" not in {f.name for f in dataclasses.fields(cls)}
