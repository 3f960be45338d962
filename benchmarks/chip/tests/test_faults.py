"""Faults planted under the timed path (``faults.py``): each must turn
``correct`` false.  The harness's look for a chip is skipped; the rest of a
run is driven as the benchmark drives it, on the CPU at small sizes, with
the program broken where it produces its answer.  ``calibrate.py`` reads
the same faults on the chip at each cell's own size."""

import numpy as np
import pytest


@pytest.mark.parametrize("fault", ["scale_ei", "half_history", "alter_store"])
def test_fault_turns_correct_false(run_tiny, fault):
    from faults import FAULTS
    with FAULTS[fault]():
        r = run_tiny("tiny.reuse", seed=2 ** 31 + 5, seconds=2.0)
    assert r["correct"] is False, r["checks"]
    failing = [n for n, c in r["checks"].items()
               if c["value"] is None or not np.isfinite(c["value"])
               or c["value"] > c["limit"]]
    assert failing, r["checks"]
