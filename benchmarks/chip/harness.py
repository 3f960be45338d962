"""One cell of the chip benchmark: set-up, the measured window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the configuration, whose ``kind`` names the
  module under ``kinds/`` that builds its space and experiment and brings
  its own checks (``Kind.checks(window)``: the numbers the kind's
  guarantees are held to, such as the store giving back what was told);
* ``traffic/<traffic>.json`` — the traffic mix: the optimizer family
  (``optimizer``, BO-GP where absent) with its settings and pool, the store
  each investigation starts from and how many trials it runs;
* ``limits/<workload>.json`` — the limit of each number the check compares;
* ``metrics/<metric>.py`` — the reader of one per-layer metric.

What each optimizer family brings — its spanned optimizer, the warm-up of
its device programs and the check of its answers against the reference —
is one entry of ``optimizers.FAMILIES``.  The numbers a cell compares are
its kind's checks and its family's answer gaps.

The window drives ``Investigation.from_components(...).run()`` — the engine
every investigation runs through — with the serial backend, batch 1, and a
SQLite store file, one investigation after another until ``--seconds``
have passed.  A trial lasts from the start of its ask to the start of the
next ask (or the investigation's return): ask, measure, record in the
store, tell.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT, bench_dir: Path = HERE) -> tuple:
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} (known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    # a cell without limits would compare nothing and pass: that is an error
    limits = load_json(bench_dir / "limits" / f"{workload}.json")
    if not limits:
        raise SystemExit(f"limits/{workload}.json names no number to compare")
    return bench, cell, config, traffic, limits


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, so only a cell's first run there
    compiles.  Call before JAX touches a device."""
    cache = str(root / ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_kind(name: str):
    return importlib.import_module(f"kinds.{name}")


def _sequence(seed: int, key: tuple) -> np.random.SeedSequence:
    return np.random.SeedSequence(seed % (1 << 64), spawn_key=key)


def seed_rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng(_sequence(seed, key))


def seed_int(seed: int, *key: int) -> int:
    return int(_sequence(seed, key).generate_state(1)[0] >> 1)


# spawn keys of the streams drawn from --seed
SURFACE, BASE, WINDOW, CHECK, WARM = range(5)
# trials of the investigation that warms the engine and the store in set-up
WARM_TRIALS = 4
# asks of the window compared with the reference: a sample drawn from the
# seed, and the last ask, which saw the longest history
CHECK_ASKS = 6


class Window:
    """What the window did: trial times, the investigations' stores."""

    def __init__(self):
        self.t0 = 0.0
        self.t1 = 0.0
        self.trials: list = []        # (start, end, Trial)
        self.investigations: list = []  # (DiscoverySpace, [Trial])
        self.attempted = 0
        self.record = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _investigation(kind, traffic, path: str, seed: int, stream: tuple,
                   record, asks: list):
    from optimizers import make_optimizer
    from repro.core import DiscoverySpace, SampleStore
    from repro.core.api import Investigation

    store = SampleStore(path)
    ds = DiscoverySpace(space=kind.space, actions=kind.actions, store=store)
    opt = make_optimizer(traffic, seed_int(seed, *stream), record, asks)
    inv = Investigation.from_components(
        ds, [opt], metric=kind.metric, mode=kind.mode,
        rngs=[seed_rng(seed, *stream, 1)], max_trials=int(traffic["trials"]),
        patience=int(traffic["trials"]), backend="serial", batch_size=1,
        warm_start=int(traffic["base_valued_trials"]) > 0)
    return ds, inv


def fill_base(kind, traffic, seed: int, workdir: str) -> tuple:
    """A store that already holds ``base_valued_trials`` measured trials
    with a value: the space's points in an order drawn from the seed, until
    that many of them deploy.  (path, points measured), or (None, 0) for
    traffic that starts empty."""
    want = int(traffic["base_valued_trials"])
    if not want:
        return None, 0
    from repro.core import DiscoverySpace, SampleStore
    path = os.path.join(workdir, "base.sqlite")
    ds = DiscoverySpace(space=kind.space, actions=kind.actions,
                        store=SampleStore(path))
    configs = list(kind.space.all_configurations())
    picked, have = [], 0
    for i in seed_rng(seed, BASE).permutation(len(configs)):
        picked.append(configs[i])
        have += kind.expected_value(configs[i]) is not None
        if have == want:
            break
    ds.sample_batch(picked, operation_id="base")
    ds.store.close()
    return path, len(picked)


def _new_store(base: str | None, workdir: str, tag: str) -> str:
    path = os.path.join(workdir, f"inv{tag}.sqlite")
    if base is not None:
        shutil.copyfile(base, path)
    return path


def run_investigations(kind, traffic, base, seed: int, stream0: int,
                       seconds: float, spans, capture, workdir: str) -> Window:
    """Investigations one after another until ``seconds`` have passed (or,
    with ``seconds`` infinite, one investigation)."""
    from optimizers import Record
    w = Window()
    w.t0 = time.perf_counter()
    w.record = Record(spans, capture, w.t0 + seconds)
    deadline = w.record.deadline
    i = 0
    while True:
        asks: list = []
        with spans.span("investigation"):
            path = _new_store(base, workdir, f"{stream0}-{i}")
            ds, inv = _investigation(kind, traffic, path, seed, (stream0, i),
                                     w.record, asks)
        result = inv.run()
        t_ret = time.perf_counter()
        told = [t for _, t in result.events]
        starts = [t for t, n in asks if n]
        ends = [t for t, _ in asks[1:]] + [t_ret]
        w.trials += [(s, e, t) for s, e, t in zip(starts, ends, told)]
        w.attempted += sum(n for _, n in asks)
        w.investigations.append((ds, told))
        i += 1
        if not np.isfinite(seconds) or time.perf_counter() >= deadline:
            break
    w.t1 = time.perf_counter()
    return w


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]) — every limited number must be
    present and at most its limit."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
        rows.append((name, value, limit))
    return ok, rows


def percentile(values: list, q: float) -> float:
    """The q-th percentile (linear interpolation between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    statistics, as the CPU's does not)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Cell:
    """One cell's run, in steps a caller can interleave: ``setup`` (set-up:
    the base store, every program the window can use), ``run_window``, and
    ``numbers`` (the comparisons with the reference, once the window has
    closed)."""

    def __init__(self, workload: str, devices, trace: bool = False,
                 root: Path = ROOT, bench_dir: Path = HERE):
        from optimizers import family
        (self.bench, self.cell, self.config, self.traffic,
         self.limits) = resolve(workload, root, bench_dir)
        self.family = family(self.traffic)
        self.workload = workload
        self.devices = devices
        self.trace = trace
        self.workdir = tempfile.mkdtemp(prefix="chipbench-")
        self.tracedir = os.path.join(self.workdir, "trace")
        self.window = None

    def setup(self, seed: int) -> None:
        from spans import Spans
        self.seed = seed
        self.spans = Spans(traced=self.trace)
        self.kind = load_kind(self.config["kind"]).Kind(
            self.config, seed_rng(seed, SURFACE), self.spans)
        traffic = self.traffic
        self.base, measured = fill_base(self.kind, traffic, seed, self.workdir)
        self.family.warm(self.kind, traffic, seed_rng(seed, WARM), measured)
        warm_traffic = dict(traffic, trials=WARM_TRIALS)
        run_investigations(self.kind, warm_traffic, self.base, seed, WARM,
                           float("inf"), Spans(), None, self.workdir)

    def run_window(self, seconds: float) -> Window:
        import jax
        from optimizers import Capture
        self.capture = Capture(seed_rng(self.seed, CHECK), CHECK_ASKS)
        if self.trace:
            jax.profiler.start_trace(self.tracedir)
        with self.spans.span("window"):
            self.window = run_investigations(
                self.kind, self.traffic, self.base, self.seed, WINDOW, seconds,
                self.spans, self.capture, self.workdir)
        if self.trace:
            jax.profiler.stop_trace()
        return self.window

    def numbers(self, control: bool = False) -> dict:
        """The numbers the check compares: the kind's checks of the window
        and the family's gaps between the captured answers and the
        reference; with ``control``, the control's readings of the same
        asks alone, in the program's place."""
        numbers = {} if control else self.kind.checks(self.window)
        numbers.update(self.family.gaps(self.config, self.traffic,
                                        self.capture.records(), control))
        return numbers

    def close(self) -> None:
        if self.window is not None:
            for ds, _ in self.window.investigations:
                ds.store.close()
        shutil.rmtree(self.workdir, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices, root: Path = ROOT,
             bench_dir: Path = HERE) -> dict:
    from spans import CompileCounter

    c = Cell(workload, devices, trace, root, bench_dir)
    try:
        with CompileCounter() as compiles:
            c.setup(seed)
            setup_s = time.perf_counter() - t_start
            snap = compiles.snapshot()
            n_names = len(compiles.names)
            window = c.run_window(seconds)
            in_window = compiles.since(snap)
            if in_window:
                print("compiled in the window: "
                      + ", ".join(compiles.names[n_names:]), file=sys.stderr)
        peak = memory_peak(devices)
        numbers = c.numbers()
        return report(c, window, numbers, setup_s, in_window, peak)
    finally:
        c.close()


def report(c: Cell, window: Window, numbers: dict, setup_s: float,
           in_window: int, peak: int) -> dict:
    """The result line: end-to-end metrics without tracing, per-layer
    metrics with it, the device, and last the numbers compared."""
    correct, rows = judge(numbers, c.limits)
    completed = len(window.trials)
    result = {
        "correct": bool(correct and completed > 0),
        "attempted": int(window.attempted),
        "failed": int(window.attempted - completed),
    }
    durations = [e - s for s, e, _ in window.trials]
    metrics: dict = {}
    device = dict(device_info(c.devices), memory_peak_bytes=peak)
    if not c.trace:
        e2e = {"trials_per_s": (completed / window.seconds, "trials/s"),
               "trial_ms.p95": (percentile(durations, 95) * 1e3
                                if durations else None, "ms"),
               "setup_s": (setup_s, "s")}
        for m in c.bench["end_to_end"]:
            if c.workload in m.get("workloads", [c.workload]):
                value, unit = e2e[m["name"]]
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": unit}
    else:
        import trace as trace_mod
        reduced = trace_mod.reduce(c.tracedir)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"window": window, "spans": c.spans, "trace": reduced,
               "config": c.config, "traffic": c.traffic, "cell": c.cell,
               "compiles_in_window": in_window,
               "peaks": trace_mod.peaks_for(c.devices[0].device_kind)}
        for m in c.bench["per_layer"]:
            if c.workload in m.get("workloads", [c.workload]):
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in rows}
    diag = {k: v for k, v in numbers.items() if k not in c.limits}
    print(f"set-up {setup_s:.3f} s; window: {completed} trials in "
          f"{window.seconds:.3f} s; compiles "
          f"in the window {in_window}; readings not compared: "
          f"{json.dumps(diag)}", file=sys.stderr)
    for name, value, limit in rows:
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    return result
