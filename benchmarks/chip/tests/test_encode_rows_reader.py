"""The reader of the program's ``encode.rows`` counter: its arithmetic on
a fabricated window, its silence on a program that does not count encoded
rows, and a traced test cell that reads it."""

import json

import pytest

from conftest import BENCH, DATA, REPO

NAME = "encode_rows.per_ask"


def _read(ctx):
    import harness
    return harness.load_reader(NAME)(ctx)


@pytest.fixture
def window_ctx(monkeypatch):
    """A window [10, 20] s with two benchmark asks inside it and one in
    set-up."""
    import harness
    from repro.core import tracing
    from spans import Spans
    monkeypatch.setattr(tracing, "counters", lambda: {
        "encode.rows": 2270, "encode.rows_reused": 4400,
        "device.h2d_bytes": 3000})
    w = harness.Window()
    w.t0, w.t1 = 10.0, 20.0
    spans = Spans()
    spans.records = [("ask", 11.01, 11.59), ("ask", 13.01, 13.29),
                     ("ask", 5.1, 5.9)]
    return {"window": w, "spans": spans}


def test_rows_per_ask(window_ctx):
    assert _read(window_ctx) == pytest.approx(2270 / 2)


def test_nothing_to_read(window_ctx, monkeypatch):
    """No such counter (a program that encodes every row on every ask), or
    no tracing module: nothing, never 0."""
    from repro.core import tracing
    monkeypatch.setattr(tracing, "counters",
                        lambda: {"device.h2d_bytes": 3000})
    assert _read(window_ctx) is None
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(__import__("sys").modules, "repro.core.tracing", None)
    assert _read(window_ctx) is None


@pytest.fixture
def traced_root(tmp_path):
    """The test cell's benchmark with this reader added."""
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]:
        if m["name"] == NAME:
            bench["per_layer"].append(dict(m, workloads=["tiny.reuse"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "benchmarks" / "chip").symlink_to(BENCH)
    return tmp_path


def test_traced_cell_reads_encoded_rows(traced_root, monkeypatch):
    """A traced window encodes rows (each investigation's resumed history
    and enumeration, then the trials told since the previous ask) and
    gathers others."""
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
        w = c.run_window(2.0)
        r = harness.report(c, w, c.numbers(), 1.0, 0, 0)
        counts = tracing.counters()
    finally:
        c.close()
        tracing.reset()
    assert r["correct"] is True, r
    assert r["metrics"][NAME]["value"] > 0
    assert counts["encode.rows_reused"] > 0
