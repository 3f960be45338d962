"""The program's spans and counters (``repro.core.tracing``): the off path,
the recorder, the spans a CPU investigation leaves, a profiler round trip
and ``run --profile``."""

import glob
import json
import os
import subprocess
import sys
import time

import pytest

from repro.core import (ActionSpace, Dimension, DiscoverySpace,
                        FunctionExperiment, ProbabilitySpace, SampleStore,
                        tracing)
from repro.core.api import Investigation
from repro.core.connector import Deployment, ExperimentConnector
from repro.core.connector.lifecycle import LifecycleExperiment
from repro.core.optimizers import GPBayesOpt
from repro.core.optimizers.base import run_optimizer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ASK_PARTS = {"ask.pool", "ask.encode.history", "ask.encode.pool", "ask.fit",
             "ask.ei", "ask.rank"}
STEP_PARTS = {"ask", "store.intern", "store.claim", "measure", "store.values",
              "store.record", "store.read", "tell"}


@pytest.fixture
def recorder():
    """Recording on, empty; off and empty again afterwards."""
    tracing.reset()
    tracing.enable()
    yield tracing
    tracing.enable(False)
    tracing.reset()


@pytest.fixture
def annotations(monkeypatch):
    """Count the profiler annotations the recorder creates."""
    import jax.profiler

    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            made.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(tracing, "_annotation", Counted)
    monkeypatch.setattr(tracing, "_profiler_on", Counted.is_enabled)
    return made


def _space(n=5):
    return ProbabilitySpace.make([
        Dimension.discrete("a", list(range(n))),
        Dimension.discrete("b", list(range(n))),
        Dimension.categorical("c", ["x", "y"])])


def _ds(store=None):
    def measure(config):
        v = config.as_dict()
        return {"lat": (v["a"] - 2) ** 2 + 0.5 * (v["b"] - 3) ** 2
                + (v["c"] == "y")}
    exp = FunctionExperiment(fn=measure, properties=("lat",), name="quad")
    return DiscoverySpace(space=_space(), actions=ActionSpace.make([exp]),
                          store=store or SampleStore(":memory:"))


def _gp():
    return GPBayesOpt(seed=0, backend="jax", max_candidates=64)


def _children(records) -> dict:
    out: dict = {}
    for i, s in enumerate(records):
        if s.parent is not None:
            out.setdefault(s.parent, []).append(i)
    return out


def _check_nesting(records) -> None:
    """Every span closed, inside its parent, with a parent's trial id; no
    self time negative."""
    assert all(s.t1 is not None and s.t1 >= s.t0 for s in records)
    for s in records:
        if s.parent is not None:
            p = records[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (p, s)
            assert s.trial == p.trial
    assert min(tracing.self_times(records)) >= 0.0


def test_import_loads_no_jax():
    code = ("import sys, repro.core, repro.core.tracing as t; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert not t.recording()")
    env = dict(os.environ, PYTHONPATH=SRC)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_off_path_is_one_shared_null_context():
    tracing.reset()
    assert not tracing.recording()
    a, b = tracing.span("a"), tracing.span("b")
    assert a is b
    with a:
        tracing.count("n", 3)
        tracing.trial(4)
    assert tracing.spans() == [] and tracing.counters() == {}


def test_investigation_off_records_nothing(annotations):
    tracing.reset()
    run_optimizer(_gp(), _ds(), "lat", max_trials=6, patience=6)
    assert tracing.spans() == [] and tracing.counters() == {}
    assert annotations == []


def test_nesting_parents_trials_and_self_time(recorder, annotations):
    tracing.trial(7)
    with tracing.span("a"):
        with tracing.span("b"):
            time.sleep(0.002)
        tracing.trial(8)
        with tracing.span("c"):
            time.sleep(0.001)
    rec = tracing.spans()
    assert [(s.name, s.parent, s.trial) for s in rec] == [
        ("a", None, 7), ("b", 0, 7), ("c", 0, 8)]
    own = tracing.self_times(rec)
    a, b, c = ((s.t1 - s.t0) for s in rec)
    assert own[1] == b and own[2] == c
    assert own[0] == pytest.approx(a - b - c) and own[0] >= 0
    assert annotations == ["repro:a", "repro:b", "repro:c"]


def test_self_time_takes_the_union_of_children():
    S = tracing.Span
    rec = [S("p", 0.0, 10.0, None, None), S("x", 1.0, 4.0, 0, None),
           S("y", 3.0, 6.0, 0, None), S("z", 8.0, 9.0, 0, None),
           S("open", 9.0, None, 0, None)]
    assert tracing.self_times(rec) == [pytest.approx(4.0), 3.0, 3.0, 1.0,
                                       None]


def test_counters_and_the_bounded_buffer(recorder, monkeypatch):
    tracing.count("x")
    tracing.count("x", 4)
    tracing.count("y", 2)
    assert tracing.counters() == {"x": 5, "y": 2}
    monkeypatch.setattr(tracing, "CAPACITY", 3)
    with tracing.span("kept"):
        for i in range(4):
            with tracing.span(f"s{i}"):
                with tracing.span("inner"):
                    pass
    rec = tracing.spans()
    assert [s.name for s in rec] == ["kept", "s0", "inner"]
    assert tracing.dropped() == 6
    tracing.reset()
    assert tracing.spans() == [] and tracing.dropped() == 0
    assert tracing.counters() == {}


def test_reset_drops_spans_left_open(recorder):
    with tracing.span("open"):
        tracing.reset()
        with tracing.span("after"):
            pass
    assert [(s.name, s.parent) for s in tracing.spans()] == [("after", None)]


def _trials(records) -> list:
    kids = _children(records)
    out = []
    for i, s in enumerate(records):
        if s.name != "trial":
            continue
        names = {records[j].name for j in kids.get(i, [])}
        ask = next(j for j in kids.get(i, []) if records[j].name == "ask")
        out.append((s, names, {records[j].name for j in kids.get(ask, [])}))
    return out


@pytest.mark.parametrize("engine", ["batched", "pipelined"])
def test_investigation_spans(recorder, engine):
    ds = _ds()
    kw = {} if engine == "batched" else {"max_inflight": 1}
    run = run_optimizer(_gp(), ds, "lat", max_trials=8, patience=8, **kw)
    assert run.num_trials == 8
    rec = tracing.spans()
    _check_nesting(rec)
    trials = _trials(rec)
    assert len(trials) == 8
    gp = [t for t in trials if "ask.ei" in t[2]]
    assert len(gp) == 8 - 3                # three random draws first
    for s, step, ask in trials:
        assert step >= STEP_PARTS, step
    for s, step, ask in gp:
        assert ask >= ASK_PARTS, ask
    assert len({s.trial for s, _, _ in trials}) == 8
    counts = tracing.counters()
    assert counts["gp.refit"] == len(gp)
    assert counts["device.h2d_bytes"] > 0 and counts["device.d2h_bytes"] > 0


def test_resume_span_holds_the_warm_fold(recorder):
    store = SampleStore(":memory:")
    run_optimizer(_gp(), _ds(store), "lat", max_trials=5, patience=5)
    tracing.reset()
    inv = Investigation.from_components(
        _ds(store), [_gp()], metric="lat", max_trials=3, patience=3,
        warm_start=True)
    inv.run()
    rec = tracing.spans()
    _check_nesting(rec)
    resume = [s for s in rec if s.name == "engine.resume"]
    assert len(resume) == 1 and resume[0].parent is None
    first = min(s.t0 for s in rec if s.name == "trial")
    assert resume[0].t1 <= first


class _Cloud(ExperimentConnector):
    name = "cloud"

    @property
    def observed_properties(self):
        return ("lat",)

    def provision(self, configuration):
        return Deployment(ident="d", configuration=configuration)

    def run(self, deployment):
        return {"lat": float(deployment.configuration["a"])}


def test_connector_phase_spans(recorder):
    exp = LifecycleExperiment(_Cloud())
    ds = DiscoverySpace(space=_space(), actions=ActionSpace.make([exp]))
    run_optimizer(GPBayesOpt(seed=0), ds, "lat", max_trials=2, patience=2)
    rec = tracing.spans()
    _check_nesting(rec)
    phases = [s for s in rec if s.name.startswith("connector.")]
    assert [s.name for s in phases] == 2 * [
        "connector.provision", "connector.run", "connector.parse",
        "connector.teardown"]
    assert {rec[s.parent].name for s in phases} == {"measure"}


def _xplane_events(directory: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    found: dict = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro:"):
                    found.setdefault(ev.name, set()).add(plane.name)
    return found


def test_profiler_session_records_without_enable(tmp_path):
    import jax

    tracing.reset()
    assert not tracing.recording()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert tracing.recording()
        with tracing.span("outer"):
            with tracing.span("inner"):
                tracing.count("c")
    finally:
        jax.profiler.stop_trace()
    try:
        assert not tracing.recording()
        assert [s.name for s in tracing.spans()] == ["outer", "inner"]
        assert tracing.counters() == {"c": 1}
    finally:
        tracing.reset()
    found = _xplane_events(str(tmp_path))
    assert set(found) == {"repro:outer", "repro:inner"}
    assert all(p.startswith("/host:") for planes in found.values()
               for p in planes)


def test_run_profile_writes_trace_and_spans(tmp_path, capsys):
    from repro.core.api.__main__ import main

    spec = json.loads(open(os.path.join(
        os.path.dirname(__file__), "..", "examples", "specs",
        "quickstart.json")).read())
    spec["optimizers"] = [{"name": "bo-gp", "seed": 0, "params": {},
                           "backend": "jax"}]
    spec["budget"] = {"max_trials": 6, "patience": 6, "min_trials": 1}
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    out = tmp_path / "prof"
    try:
        assert main(["run", str(path), "--profile", str(out)]) == 0
    finally:
        tracing.reset()
    lines = [json.loads(x) for x in
             (out / "spans.jsonl").read_text().splitlines()]
    spans, tail = lines[:-1], lines[-1]
    assert sum(s["name"] == "trial" for s in spans) == 6
    assert {"ask.fit", "ask.ei", "store.record", "tell"} <= {
        s["name"] for s in spans}
    assert all(s["self_s"] >= 0 for s in spans)
    assert tail["dropped"] == 0 and tail["counters"]["gp.refit"] == 3
    assert "repro:trial" in _xplane_events(str(out))
    printed = capsys.readouterr().out
    assert "counter device.h2d_bytes" in printed
    row = next(x for x in printed.splitlines() if x.startswith("trial "))
    assert row.split()[1] == "6"
