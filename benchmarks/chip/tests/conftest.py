"""Tests of the chip benchmark's harness on the CPU, at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

import os
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DATA = HERE / "data"


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like root whose BENCHMARK.json names the test cells."""
    shutil.copy(DATA / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "benchmarks" / "chip").symlink_to(BENCH)
    return tmp_path


@pytest.fixture
def run_tiny(tiny_root):
    """Run a test cell through the harness, the chip check skipped."""
    def run(workload, seed=11, seconds=2.0, trace=False):
        import jax
        import harness
        return harness.run_cell(workload, seed, seconds, trace,
                                time.perf_counter(), jax.devices()[:1],
                                tiny_root, DATA)
    return run
