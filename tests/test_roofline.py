"""Tests for the while-aware HLO analyzer and roofline accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import _ring_factor, roofline_terms
from repro.roofline.hlo_parse import analyze_hlo
from repro.roofline.hw import HW_V5E


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_xla_cost_analysis_counts_scan_once():
    """Documents the defect the parser exists to fix: XLA cost_analysis
    counts while bodies exactly once."""
    def scanned(x, ws):
        def body(c, w):
            return (c @ w).astype(c.dtype), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jnp.zeros((64, 64))
    ws = jnp.zeros((8, 64, 64))
    compiled = _compile(scanned, x, ws)
    flops_xla = compiled.cost_analysis().get("flops", 0.0)
    one_matmul = 2 * 64 * 64 * 64
    assert flops_xla == pytest.approx(one_matmul, rel=0.01)  # NOT ×8


@pytest.mark.parametrize("trips", [4, 8, 17])
def test_analyzer_scales_dot_flops_by_trip_count(trips):
    def scanned(x, ws):
        def body(c, w):
            return (c @ w).astype(c.dtype), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jnp.zeros((32, 32))
    ws = jnp.zeros((trips, 32, 32))
    a = analyze_hlo(_compile(scanned, x, ws).as_text())
    assert a.flops == pytest.approx(2 * 32 ** 3 * trips, rel=0.01)
    assert a.trip_counts == [trips]


def test_analyzer_nested_scans_multiply():
    def nested(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return (c2 @ w).astype(c2.dtype), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jnp.zeros((32, 32))
    ws = jnp.zeros((5, 32, 32))
    a = analyze_hlo(_compile(nested, x, ws).as_text())
    assert a.flops == pytest.approx(2 * 32 ** 3 * 5 * 3, rel=0.01)
    assert sorted(a.trip_counts) == [3, 5]


def test_analyzer_counts_collectives_with_groups():
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def f(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("data", None)))
        return y.sum()

    # single-device: no collectives expected — exercise the zero path
    a = analyze_hlo(_compile(lambda x: x.sum(), jnp.zeros((8, 8))).as_text())
    assert a.collectives == {}


def test_analyzer_dus_counts_update_slice_only():
    def f(buf, x):
        def body(b, i):
            b = jax.lax.dynamic_update_index_in_dim(b, x, i, 0)
            return b, None
        b, _ = jax.lax.scan(body, buf, jnp.arange(16))
        return b

    buf = jnp.zeros((16, 1024))
    x = jnp.zeros((1024,))
    a = analyze_hlo(_compile(f, buf, x).as_text())
    # traffic should be ~16 updates of 4KB (64KB), far below 16 full-buffer
    # writes (1MB)
    assert a.traffic_bytes < 0.5 * 16 * buf.size * 4


def test_roofline_terms_math():
    terms = roofline_terms(
        hlo_flops=197e12,          # exactly one chip-second of compute
        hlo_bytes=819e9,           # one chip-second of HBM
        collectives={"all-reduce": 100e9},
        group_sizes={"all-reduce": 16},
        hw=HW_V5E)
    compute_s, memory_s, collective_s = terms
    assert compute_s == pytest.approx(1.0)
    assert memory_s == pytest.approx(1.0)
    # all-reduce ring factor 2·15/16 over 4×50GB/s links
    assert collective_s == pytest.approx(100e9 * 2 * 15 / 16 / 200e9)


def test_ring_factors():
    assert _ring_factor("all-gather", 16) == pytest.approx(15 / 16)
    assert _ring_factor("reduce-scatter", 16) == 15
    assert _ring_factor("all-reduce", 2) == pytest.approx(1.0)
    assert _ring_factor("all-reduce", 1) == 0.0
    assert _ring_factor("collective-permute", 8) == 1.0
