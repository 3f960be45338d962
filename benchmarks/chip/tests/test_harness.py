"""The harness's arithmetic, its trace reduction, its counts, and its
refusal to run without a chip."""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH, DATA, REPO


def test_percentile_and_judge():
    import harness
    assert harness.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    ok, rows = harness.judge({"a": 0.1, "b": 0.0}, {"a": 0.2, "b": 0})
    assert ok and rows == [("a", 0.1, 0.2), ("b", 0.0, 0)]
    assert not harness.judge({"a": 0.3}, {"a": 0.2})[0]
    assert not harness.judge({}, {"a": 0.2})[0]          # a missing number fails
    assert not harness.judge({"a": float("nan")}, {"a": 0.2})[0]


def test_gp_ask_counts_true_sizes():
    import flops
    n, pool, d = 1300, 900, 6
    f, b = flops.gp_ask(n, pool, d)
    want = (n * n * 13 + n ** 3 / 3 + 2 * n * n + pool * n * 13 + 2 * pool * n
            + pool * n * n)
    assert f == pytest.approx(want)
    assert b == pytest.approx(4 * ((n + pool) * d + n + pool) + 8 * n * n)
    # counted at the true history and pool, never at the padded buckets
    assert flops.gp_ask(2048, 1024, d)[0] > 2 * f
    # an ask that reuses the cached fit does the pass alone
    f0, b0 = flops.gp_ask(n, pool, d, refit=False)
    assert f0 == pytest.approx(pool * n * 13 + 2 * pool * n + pool * n * n)
    assert b0 == pytest.approx(b - 4 * n - 4 * n * n)


def test_refit_recorded_only_when_the_history_changes():
    import numpy as np
    from optimizers import Record, make_optimizer
    from spans import Spans
    traffic = {"backend": "numpy", "pool": 8,
               "gp": {"length_scale": 0.35, "noise": 1e-4, "xi": 0.01,
                      "n_initial": 3}}
    record = Record(Spans(), None, float("inf"))
    opt = make_optimizer(traffic, 0, record, [])
    rng = np.random.default_rng(0)
    X, y, Xc = rng.random((5, 2)), rng.random(5), rng.random((8, 2))
    opt._acquisition(X, y, Xc)
    opt._acquisition(X, y, Xc[:7])       # after a failed trial: same history
    opt._acquisition(X[:4], y[:4], Xc)
    assert record.sizes == [(5, 8, 2, True), (5, 7, 2, False), (4, 8, 2, True)]


def _load(name):
    import harness
    return harness.load_reader(name)


def test_metric_readers():
    import harness
    from spans import Spans
    w = harness.Window()
    w.t0, w.t1 = 10.0, 20.0
    spans = Spans()
    spans.records = [("ask", 11.0, 11.5), ("ask", 12.0, 12.25),
                     ("ask", 5.0, 6.0)]  # set-up: outside the window
    w.record = types.SimpleNamespace(sizes=[(100, 200, 6, True),
                                            (100, 199, 6, False)])
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
    trace = {"window_s": 10.0, "busy_s": 2.5,
             "module_time": {"jit__gp_fit": 0.002, "jit__gp_ei": 0.001}}
    ctx = {"window": w, "spans": spans, "trace": trace, "peaks": peaks,
           "compiles_in_window": 0}
    assert _load("ask_ms.mean")(ctx) == pytest.approx(375.0)
    assert _load("device_idle_share")(ctx) == pytest.approx(75.0)
    assert _load("compiles_in_window")(ctx) == 0.0
    assert _load("gp_device_ms.per_ask")(ctx) == pytest.approx(1.5)
    import flops
    least = sum(max(flops.gp_ask(*s)[0] / 1e12, flops.gp_ask(*s)[1] / 1e11)
                for s in w.record.sizes)
    assert _load("gp_ask_roofline")(ctx) == pytest.approx(100 * least / 0.003)
    # nothing to read: the reader returns nothing, never 0
    empty = dict(ctx, trace=dict(trace, module_time={}))
    assert _load("gp_device_ms.per_ask")(empty) is None
    assert _load("gp_ask_roofline")(empty) is None


def test_trace_summary_arithmetic():
    import trace
    data = {
        "window": (0.0, 10.0),
        "host": [("window", 0.0, 10.0), ("investigation", 0.0, 0.5),
                 ("ask", 1.0, 3.0), ("measure", 5.0, 8.0)],
        "chips": [{"name": "/device:TPU:0",
                   "ops": [("fusion", 1.0, 2.0), ("dot", 1.5, 2.5),
                           ("custom", 6.0, 7.0), ("before", -2.0, -1.0)],
                   "modules": [("jit__gp_fit(1)", 1.0, 2.5)]}],
    }
    s = trace.summarize(data)
    assert s["window_s"] == 10.0
    assert s["busy_s"] == pytest.approx(2.5)      # union: [1, 2.5] and [6, 7]
    assert s["module_time"] == {"jit__gp_fit": 1.5}
    # gaps: [0,1] mid 0.5 investigation, [2.5,6] mid 4.25 and [7,10] none
    assert s["idle"] == {"investigation": pytest.approx(1.0),
                         "engine_store": pytest.approx(6.5)}
    data["host"].append(("ask", 2.5, 6.0))
    s = trace.summarize(data)
    assert s["idle"]["ask"] == pytest.approx(3.5)
    assert s["breakdown"]["device_ops"][0] == ["jit__gp_fit/fusion", 1.0]
    assert trace.short_name('%checkpoint.8 = f32[8] custom-call(x), '
                            'custom_call_target="tpu_custom_call", a={}') \
        == "checkpoint.8 tpu_custom_call"


def test_trace_reduction_of_a_recorded_trace(tmp_path):
    """A trace recorded on a TPU v5e: two BO-GP asks and their tells, in a
    window that ``bench:window`` bounds."""
    import gzip

    import trace
    raw = gzip.decompress((DATA / "trace_tpu" / "window.xplane.pb.gz").read_bytes())
    (tmp_path / "window.xplane.pb").write_bytes(raw)
    out = trace.reduce(str(tmp_path))
    assert 0 < out["busy_s"] < out["window_s"]
    assert set(out["module_time"]) >= {"jit__gp_fit", "jit__gp_ei"}
    assert sum(v for _, v in out["breakdown"]["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_peaks_table():
    import trace
    assert trace.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        trace.peaks_for("cpu")


@pytest.mark.parametrize("tree", ["checkout", "benchmark_only"])
def test_no_tpu_no_result(tree, tmp_path):
    """Without a TPU the command exits non-zero and prints no result; so it
    does from a directory that holds only the benchmark's files."""
    import shutil
    root = REPO
    if tree == "benchmark_only":
        root = tmp_path
        shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
        shutil.copytree(BENCH, root / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(root / "benchmarks/chip/run.py"),
                        "--workload", "ask.bogp.cold", "--seed", "3000000000",
                        "--seconds", "1", "--trace", "0"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_tiny_reuse_cell(run_tiny):
    r = run_tiny("tiny.reuse", seconds=2.0)
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"trials_per_s", "trial_ms.p95", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["store_mismatch"]["value"] == 0
    assert r["checks"]["ei_gap"]["value"] < 1e-3


def test_tiny_reuse_cell_traced(run_tiny, monkeypatch):
    import trace
    v5e = trace.peaks_for("TPU v5 lite")
    monkeypatch.setattr(trace, "peaks_for", lambda kind: v5e)
    r = run_tiny("tiny.reuse", seconds=2.0, trace=True)
    assert r["correct"] is True, r
    assert set(r["metrics"]) == {"ask_ms.mean", "compiles_in_window"}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" in r["device"] and "window_s" in r["device"]
    assert "breakdown" in r


def test_missing_limits_is_an_error(tiny_root, tmp_path):
    """A cell with no limits file would compare nothing: it does not run."""
    import shutil

    import harness
    data = tmp_path / "data"
    shutil.copytree(DATA, data)
    (data / "limits" / "tiny.reuse.json").unlink()
    with pytest.raises(FileNotFoundError):
        harness.resolve("tiny.reuse", tiny_root, data)
    (data / "limits" / "tiny.reuse.json").write_text("{}")
    with pytest.raises(SystemExit):
        harness.resolve("tiny.reuse", tiny_root, data)


def test_same_seed_same_inputs(tiny_root):
    """The seed fixes the surface, the base store and the pool draws."""
    import jax
    import harness
    firsts = []
    for _ in range(2):
        c = harness.Cell("tiny.reuse", jax.devices()[:1], root=tiny_root, bench_dir=DATA)
        try:
            c.setup(4294967297)
            w = c.run_window(1.0)
            firsts.append([(t.configuration.digest, t.value)
                           for _, _, t in w.trials[:3]])
        finally:
            c.close()
    assert firsts[0] == firsts[1]
