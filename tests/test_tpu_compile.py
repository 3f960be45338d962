"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (misaligned blocks, too
much fast memory, lowering rules Mosaic lacks) — all of which interpret
mode accepts.  Each test compiles one kernel with ``interpret=False`` and
checks that the compiled program holds it as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the one that does keeps it
until it exits.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_compiles_at_nano_100m_widths(one_chip, no_cache):
    from repro.kernels.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((8, 1024, 10, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, 2, 64), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=128,
                                        block_kv=128, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in text


def test_rbf_gram_compiles_at_ask_widths(one_chip, no_cache):
    from repro.core.optimizers.accel.pallas_rbf import _rbf_pallas_call
    a = jax.ShapeDtypeStruct((4096, 4), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((8192, 4), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda a, b, s: _rbf_pallas_call(a, b, s, block_m=256, block_n=256,
                                         interpret=False),
        a, b, s)
    assert "tpu_custom_call" in text


def test_gmm_stacked_compiles_at_granite_moe_widths(one_chip, no_cache):
    from repro.kernels.gmm import gmm_stacked_pallas
    xs = jax.ShapeDtypeStruct((40, 512, 1536), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((40, 1536, 512), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda xs, w: gmm_stacked_pallas(xs, w, interpret=False), xs, w)
    assert "tpu_custom_call" in text


def test_rglru_compiles_at_recurrentgemma_widths(one_chip, no_cache):
    """Batch > 1 with an initial state: the state blocks must satisfy
    Mosaic's (8, 128)-or-whole-dim rule for every batch row."""
    from repro.kernels.rglru_scan import rglru_pallas
    B, S, D = 2, 1024, 4096
    seq = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16, sharding=one_chip)
    lam = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, la, ga, gx, h: rglru_pallas(x, la, ga, gx, h,
                                              interpret=False),
        seq, lam, seq, seq, h0)
    assert "tpu_custom_call" in text
