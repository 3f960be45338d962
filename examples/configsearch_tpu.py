# Deployment-configuration search on the production mesh — the paper's
# technique as a first-class framework feature (§Perf driver).
#
#   PYTHONPATH=src python examples/configsearch_tpu.py \
#       --arch granite-moe-3b-a800m --shape train_4k --trials 14
#
# Samples persist in experiments/tuning_store.db: rerunning (any optimizer)
# transparently reuses earlier compilations (paper Fig. 7 behaviour), and
# `--transfer-from <arch>` seeds a new architecture's search via RSSC.
#
# Run as a script it asks XLA for 512 placeholder host devices, before any
# backend is touched (jax locks the device count on first init).

import argparse
import json
import os

from repro.launch.mesh import make_production_mesh
from repro.tuning.hillclimb import hillclimb_cell, transfer_tuning

STORE = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "tuning_store.db")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "hillclimb")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-3b-a800m")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--trials", type=int, default=14)
    ap.add_argument("--optimizer", default="tpe",
                    choices=["tpe", "bo-gp", "bohb", "random"])
    ap.add_argument("--metric", default="step_time_s")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--transfer-from", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    if args.transfer_from:
        res = transfer_tuning(args.transfer_from, args.arch, args.shape, mesh,
                              store_path=STORE)
        print(json.dumps(res.summary(), indent=1))
        return

    result = hillclimb_cell(args.arch, args.shape, mesh,
                            optimizer=args.optimizer, trials=args.trials,
                            metric=args.metric, store_path=STORE,
                            seed=args.seed)
    os.makedirs(OUT_DIR, exist_ok=True)
    out = os.path.join(OUT_DIR,
                       f"{args.arch}__{args.shape}__{args.optimizer}.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[configsearch] log saved to {out}")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
