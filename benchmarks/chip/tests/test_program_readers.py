"""The readers of the program's own spans and counters
(``repro.core.tracing``): their arithmetic on a fabricated window, their
silence on a program without tracing, and a traced test cell that reads
them all."""

import json

import pytest

from conftest import BENCH, DATA, REPO

READERS = ("ask_pool_ms.mean", "ask_encode_ms.mean", "ask_gp_host_ms.mean",
           "ask_self_ms.mean", "store_ms.per_trial",
           "engine_self_ms.per_trial", "resume_ms.per_investigation",
           "ask_copy_kb.per_ask")


def _load(name):
    import harness
    return harness.load_reader(name)


@pytest.fixture
def window_ctx(monkeypatch):
    """A window [10, 20] s with two benchmark asks and two trials, and the
    program's spans: a resume, two trials, and one ask before the window."""
    import harness
    from repro.core import tracing
    from spans import Spans
    S = tracing.Span
    records = [
        S("engine.resume", 10.0, 10.5, None, None),            # 0
        S("trial", 11.0, 12.0, None, 0),                       # 1
        S("ask", 11.0, 11.6, 1, 0),                            # 2
        S("ask.pool", 11.0, 11.1, 2, 0),
        S("ask.encode.history", 11.1, 11.2, 2, 0),
        S("ask.encode.pool", 11.2, 11.4, 2, 0),
        S("ask.fit", 11.4, 11.45, 2, 0),
        S("ask.ei", 11.45, 11.5, 2, 0),
        S("store.intern", 11.6, 11.65, 1, 0),
        S("store.record", 11.7, 11.8, 1, 0),
        S("tell", 11.8, 11.9, 1, 0),
        S("trial", 13.0, 13.5, None, 1),                       # 11
        S("ask", 13.0, 13.3, 11, 1),
        S("ask.pool", 13.0, 13.2, 12, 1),
        S("store.read", 13.3, 13.4, 11, 1),
        S("ask", 5.0, 6.0, None, None),                        # set-up
    ]
    monkeypatch.setattr(tracing, "spans", lambda: list(records))
    monkeypatch.setattr(tracing, "counters", lambda: {
        "device.h2d_bytes": 3000, "device.d2h_bytes": 1000, "gp.refit": 1})
    w = harness.Window()
    w.t0, w.t1 = 10.0, 20.0
    w.trials = [(11.0, 13.0, None), (13.0, 13.5, None)]
    spans = Spans()
    spans.records = [("ask", 11.01, 11.59), ("ask", 13.01, 13.29),
                     ("ask", 5.1, 5.9)]
    return {"window": w, "spans": spans}


def test_program_span_readers(window_ctx):
    ms = pytest.approx
    assert _load("ask_pool_ms.mean")(window_ctx) == ms(1e3 * 0.3 / 2)
    assert _load("ask_encode_ms.mean")(window_ctx) == ms(1e3 * 0.3 / 2)
    assert _load("ask_gp_host_ms.mean")(window_ctx) == ms(1e3 * 0.1 / 2)
    # ask self time: 0.6 - 0.5 in the first, 0.3 - 0.2 in the second
    assert _load("ask_self_ms.mean")(window_ctx) == ms(1e3 * 0.2 / 2)
    assert _load("store_ms.per_trial")(window_ctx) == ms(1e3 * 0.25 / 2)
    # trial self time: 1.0 - (0.6 + 0.05 + 0.1 + 0.1), 0.5 - (0.3 + 0.1)
    assert _load("engine_self_ms.per_trial")(window_ctx) == ms(
        1e3 * (0.15 + 0.1) / 2)
    assert _load("resume_ms.per_investigation")(window_ctx) == ms(500.0)
    assert _load("ask_copy_kb.per_ask")(window_ctx) == ms(4000 / 1e3 / 2)


def test_nothing_to_read(window_ctx, monkeypatch):
    """No span of the kind, or no tracing module: nothing, never 0."""
    from repro.core import tracing
    monkeypatch.setattr(tracing, "spans", lambda: [])
    monkeypatch.setattr(tracing, "counters", lambda: {})
    for name in READERS:
        assert _load(name)(window_ctx) is None, name
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(__import__("sys").modules, "repro.core.tracing", None)
    for name in READERS:
        assert _load(name)(window_ctx) is None, name


@pytest.fixture
def traced_root(tmp_path):
    """The test cell's benchmark with every program-span reader added."""
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]:
        if m["name"] in READERS:
            bench["per_layer"].append(dict(m, workloads=["tiny.reuse"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "benchmarks" / "chip").symlink_to(BENCH)
    return tmp_path


def test_traced_cell_reads_the_program_spans(traced_root, monkeypatch):
    import jax
    import harness
    import trace
    from repro.core import tracing
    v5e = trace.peaks_for("TPU v5 lite")
    monkeypatch.setattr(trace, "peaks_for", lambda kind: v5e)
    tracing.reset()
    c = harness.Cell("tiny.reuse", jax.devices()[:1], True, traced_root, DATA)
    try:
        c.setup(2 ** 31 + 7)
        # set-up runs with the profiler off: nothing recorded
        assert tracing.spans() == [] and tracing.counters() == {}
        w = c.run_window(2.0)
        r = harness.report(c, w, c.numbers(), 1.0, 0, 0)
        records = tracing.spans()
        bench_ask = sum(c.spans.durations("ask", w.t0, w.t1))
    finally:
        c.close()
        tracing.reset()
    assert r["correct"] is True, r
    assert set(READERS) <= set(r["metrics"]), r["metrics"]
    assert all(r["metrics"][m]["value"] > 0 for m in READERS)
    assert min(tracing.self_times(records)) >= 0
    for s in records:
        if s.parent is not None:
            p = records[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    # each benchmark ask lies inside a program ask
    program_ask = sum(s.t1 - s.t0 for s in records if s.name == "ask")
    assert program_ask >= bench_ask
