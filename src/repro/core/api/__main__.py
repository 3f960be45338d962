"""Spec-driven CLI: run investigations and inspect the space catalog.

::

    python -m repro.core.api run spec.json [--store PATH] [--dry-run]
                                           [--resume] [--out RESULT.json]
                                           [--profile DIR]
    python -m repro.core.api validate spec.json
    python -m repro.core.api catalog --store PATH
    python -m repro.core.api frontier --store PATH --space ID \
                                      --properties cost,p95 [--modes min,min]
    python -m repro.core.api record-trace spec.json --out trace.jsonl \
                                          [--n 50] [--seed 0]

``run`` executes the spec end to end over the given store (a fresh
in-memory store when omitted — fine for self-contained smoke specs, useless
for transfer, which needs the store holding the source data).  ``--dry-run``
prints the :meth:`~repro.core.api.investigation.Investigation.plan` —
engine dispatch, fleet, budget, and which catalog spaces transfer would
warm-start from — without measuring anything.  ``validate`` parses the spec
(strict: unknown fields and schema-version mismatches fail) and re-emits
its canonical JSON.  ``catalog`` lists every registered space in a store
with its measurement counts.  ``record-trace`` measures N sampled
configurations through the spec's first experiment/connector and captures
the actuation trace (phase outcomes, durations, retries, properties) to a
JSONL file replayable via the ``trace-replay`` factory — pay for a sweep
once, replay it forever.

``run --profile DIR`` runs the investigation inside a JAX profiler session
(host events on, the Python tracer off), writes the trace under
``DIR/plugins/profile/`` and the program's spans to ``DIR/spans.jsonl``
(:mod:`repro.core.tracing`), and prints one row per span name — count,
total, self, mean and p95 — and the counters.

Run as a script, ``run`` keeps the programs its connectors compile in the
persistent compilation cache (:mod:`repro.launch.compile_cache`).
"""

from __future__ import annotations

import argparse
import json
import sys

from ..store import open_store
from .catalog import SpaceCatalog
from .investigation import Investigation
from .spec import InvestigationSpec


def _load_spec(path: str) -> InvestigationSpec:
    try:
        return InvestigationSpec.load(path)
    except (ValueError, KeyError, OSError, json.JSONDecodeError) as err:
        raise SystemExit(f"error: bad spec {path!r}: {err}")


def _cmd_run(args) -> int:
    spec = _load_spec(args.spec)
    store = open_store(args.store) if args.store else None
    inv = Investigation(spec, store=store)
    plan = inv.plan()
    print(plan.describe())
    if args.dry_run:
        return 0
    if args.profile:
        result = _profiled(args.profile, lambda: inv.run(resume=args.resume))
    else:
        result = inv.run(resume=args.resume)
    summary = result.summary()
    print(f"\ninvestigation {spec.name!r} finished: "
          f"{summary['trials']} trials, "
          f"{summary['paid_measurements']} paid measurements", end="")
    if result.transfer is not None and result.transfer.applied:
        print(f" (transfer from {result.transfer.source_space_id[:12]}…: "
              f"{result.transfer.n_warm_trials} warm trials, "
              f"{result.transfer.paid} paid representatives)", end="")
    print()
    if spec.objective is not None and spec.objective.constraints:
        bounds = ", ".join(c.describe() for c in spec.objective.constraints)
        print(f"SLA: {bounds} — {summary['infeasible']} of "
              f"{summary['trials']} trials infeasible")
    best = summary["best"]
    if best is not None:
        label = "feasible " if summary["infeasible"] else ""
        print(f"best {label}{spec.objective_label()} = {best['value']:.4g} "
              f"at {best['configuration']}")
    elif spec.objective is not None and spec.objective.constraints:
        print("no feasible configuration found within budget")
    q = summary["prediction_quality"]
    if q is not None:
        print(f"prediction quality (surrogate vs later measurements): {q}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True, default=str)
        print(f"wrote {args.out}")
    return 0


def _profiled(directory: str, run):
    """``run()`` inside a JAX profiler session that records host events
    without the Python tracer (which times every Python call and would
    inflate host spans unevenly); then the trace and ``spans.jsonl`` in
    ``directory``, and the spans' table and counters on stdout."""
    import os

    import jax

    from .. import tracing

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    os.makedirs(directory, exist_ok=True)
    tracing.reset()
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        result = run()
    finally:
        jax.profiler.stop_trace()
    path = os.path.join(directory, "spans.jsonl")
    n = tracing.write_jsonl(path)
    print(f"\n{'span':<20} {'count':>7} {'total_ms':>11} {'self_ms':>11} "
          f"{'mean_ms':>9} {'p95_ms':>9}")
    for name, k, total, own, mean, p95 in tracing.summary(tracing.spans()):
        print(f"{name:<20} {k:>7} {1e3 * total:>11.3f} {1e3 * own:>11.3f} "
              f"{1e3 * mean:>9.3f} {1e3 * p95:>9.3f}")
    for name, value in sorted(tracing.counters().items()):
        print(f"counter {name} = {value}")
    dropped = tracing.dropped()
    print(f"wrote {n} spans to {path}"
          + (f" ({dropped} dropped: buffer full)" if dropped else "")
          + f"; profiler trace under {directory}")
    return result


def _cmd_validate(args) -> int:
    spec = _load_spec(args.spec)
    roundtrip = InvestigationSpec.loads(spec.dumps())
    assert roundtrip == spec, "spec does not round-trip"  # defensive
    print(spec.dumps())
    return 0


def _cmd_catalog(args) -> int:
    catalog = SpaceCatalog(open_store(args.store))
    entries = catalog.entries()
    if not entries:
        print("catalog is empty")
        return 0
    for e in entries:
        s = e.summary()
        print(f"{e.space_id}  dims={','.join(s['dimensions'])} "
              f"size={s['size']} properties={','.join(s['properties']) or '?'}"
              f" records={s['records']} measured={s['measured']}")
    return 0


def _cmd_frontier(args) -> int:
    properties = [p for p in args.properties.split(",") if p]
    modes = None
    if args.modes:
        modes = [m for m in args.modes.split(",") if m]
    store = open_store(args.store)
    front = store.frontier(args.space, properties, modes)
    if not front:
        print("frontier is empty (no configuration has measured values for "
              "every requested property)")
        return 0
    header = "  ".join(f"{p:>14}" for p in properties)
    print(f"{header}  configuration")
    for config, values in front:
        cells = "  ".join(f"{v:>14.6g}" for v in values)
        print(f"{cells}  {config.as_dict()}")
    print(f"{len(front)} non-dominated point(s)")
    return 0


def _cmd_record_trace(args) -> int:
    import numpy as np

    from ..connector import record_trace

    spec = _load_spec(args.spec)
    experiments = [e.build() for e in spec.experiments] \
        + [c.build() for c in spec.connectors]
    if not experiments:
        raise SystemExit("error: spec names no experiments/connectors "
                         "to record")
    experiment = experiments[0]
    rng = np.random.default_rng(args.seed)
    configs = spec.space.sample_configurations(rng, args.n)
    header, trials = record_trace(experiment, configs, path=args.out)
    ok = sum(1 for t in trials if t["properties"] is not None)
    print(f"recorded {len(trials)} trial(s) from {experiment.identifier} "
          f"({ok} ok, {len(trials) - ok} failed) -> {args.out}")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.core.api",
        description="Declarative Investigation runner + space-catalog tool")
    sub = parser.add_subparsers(dest="command", required=True)

    p_run = sub.add_parser("run", help="execute an InvestigationSpec")
    p_run.add_argument("spec", help="path to the spec JSON")
    p_run.add_argument("--store", default=None,
                       help="store path or server URL (tcp://host:port / "
                            "unix:///path.sock); overrides the spec's "
                            "'store' field (default: the spec's, else "
                            "in-memory)")
    p_run.add_argument("--dry-run", action="store_true",
                       help="print the plan (incl. transfer candidates) and "
                            "exit without measuring anything")
    p_run.add_argument("--resume", action="store_true",
                       help="fold everything already recorded in the space "
                            "into each member's history before the first ask")
    p_run.add_argument("--out", default=None,
                       help="write the result summary JSON here")
    p_run.add_argument("--profile", default=None, metavar="DIR",
                       help="run inside a JAX profiler session; write the "
                            "trace and the program's spans (spans.jsonl) "
                            "to DIR and print a table of them")
    p_run.set_defaults(fn=_cmd_run)

    p_val = sub.add_parser("validate",
                           help="strict-parse a spec and print canonical JSON")
    p_val.add_argument("spec")
    p_val.set_defaults(fn=_cmd_validate)

    p_cat = sub.add_parser("catalog", help="list a store's registered spaces")
    p_cat.add_argument("--store", required=True,
                       help="store path or server URL")
    p_cat.set_defaults(fn=_cmd_catalog)

    p_fr = sub.add_parser(
        "frontier",
        help="print a space's measured Pareto frontier over properties")
    p_fr.add_argument("--store", required=True,
                      help="store path or server URL")
    p_fr.add_argument("--space", required=True, help="space id")
    p_fr.add_argument("--properties", required=True,
                      help="comma-separated measured property names")
    p_fr.add_argument("--modes", default=None,
                      help="comma-separated min|max per property "
                           "(default all min)")
    p_fr.set_defaults(fn=_cmd_frontier)

    p_rt = sub.add_parser(
        "record-trace",
        help="measure sampled configurations and capture a replayable "
             "actuation trace")
    p_rt.add_argument("spec", help="path to the spec JSON (its first "
                                   "experiment/connector is recorded)")
    p_rt.add_argument("--out", required=True,
                      help="trace JSONL output path")
    p_rt.add_argument("--n", type=int, default=50,
                      help="distinct configurations to sample (default 50)")
    p_rt.add_argument("--seed", type=int, default=0,
                      help="sampling seed (default 0)")
    p_rt.set_defaults(fn=_cmd_record_trace)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    if sys.argv[1:2] == ["run"]:
        from ...launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    sys.exit(main())
