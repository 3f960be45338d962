"""Chip benchmark of the configuration search: one cell per run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout; see ``harness.py`` for how a cell runs.  The run
needs as many TPU chips as the cell asks for and fails without a result
on anything else.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, last,
``checks``, each number the check compared with its limit.  With
``--trace 1`` the metrics are the cell's per-layer metrics, read from a
profiler trace of the window and the benchmark's host spans.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    _, cell, _, _, _ = harness.resolve(args.workload, ROOT)

    harness.use_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    want = int(cell["chips"])
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"no result: the cell needs {want} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, devices[:want], ROOT)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
