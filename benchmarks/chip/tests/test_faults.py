"""Faults planted under the timed path (``faults.py``): each must turn
``correct`` false.  The harness's look for a chip is skipped; the rest of a
run is driven as the benchmark drives it, on the CPU at small sizes, with
the program broken where it produces its answer.  ``calibrate.py`` reads
the same faults on the chip at each cell's own size."""

import numpy as np
import pytest


# the test cell of each optimizer family
CELLS = {"bogp": "tiny.reuse", "tpe": "tiny.tpe"}


def _cases():
    from faults import BY_FAMILY
    return [pytest.param(CELLS[family], fault, id=f"{CELLS[family]}-{fault}")
            for family, faults in BY_FAMILY.items() for fault in faults]


@pytest.mark.parametrize("cell,fault", _cases())
def test_fault_turns_correct_false(run_tiny, cell, fault):
    from faults import FAULTS
    with FAULTS[fault]():
        r = run_tiny(cell, seed=2 ** 31 + 5, seconds=2.0)
    assert r["correct"] is False, r["checks"]
    failing = [n for n, c in r["checks"].items()
               if c["value"] is None or not np.isfinite(c["value"])
               or c["value"] > c["limit"]]
    assert failing, r["checks"]
