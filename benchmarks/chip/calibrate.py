"""Readings that set the limits of a cell's check: the program's, the
control's and each planted fault's, over many seeds, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,3 --seconds 8

For each seed the cell's set-up and a short window run as in a benchmark
run; then the numbers are read twice on the same asks: once for the
program, once for the control: the reference one precision below the
program's, computed on the device in its place (the cell's optimizer
family decides: for BO-GP float32 with matmuls at "high", bf16_3x, below
the "highest" at which the ask runs; for TPE bfloat16 below float32).  On
the first three seeds each fault of ``faults.py`` that the cell's family
can have is then planted and the cell run again.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
FAULT_SEEDS = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    harness.use_compile_cache(ROOT)
    import jax
    from faults import BY_FAMILY, FAULTS
    from optimizers import family_name

    devices = jax.devices()[:1]

    def reading(seed, control=False):
        t0 = time.perf_counter()
        cell = harness.Cell(args.workload, devices)
        try:
            cell.setup(seed)
            t1 = time.perf_counter()
            window = cell.run_window(args.seconds)
            out = {"trials": len(window.trials), "setup_s": t1 - t0,
                   "program": cell.numbers()}
            if control:
                out["control"] = cell.numbers(control=True)
            return out
        finally:
            cell.close()

    traffic = harness.resolve(args.workload, ROOT)[3]
    faults = BY_FAMILY[family_name(traffic)]
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = dict(seed=seed, **reading(seed, control=True))
        if k < FAULT_SEEDS:
            for name in faults:
                with FAULTS[name]():
                    line[name] = reading(seed)["program"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
