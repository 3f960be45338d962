"""Each configuration is encoded into the unit cube once per adapter.

The ask gathers its history rows (``_history_arrays``,
``_feasibility_arrays``) from the adapter's encoded trials and its pool
rows from the encoded enumeration of a finite space.  Every array it builds
must equal, bit for bit, encoding each row afresh with ``space.encode`` —
the arrays the ask built before the cache — so fits, EI, proposals and the
store are unchanged.  Sampled pools (spaces with a continuous dimension)
and ask-only stub adapters keep the per-row encode.
"""

import numpy as np
import pytest

from repro.core import (ActionSpace, Dimension, DiscoverySpace,
                        FunctionExperiment, MeasurementError,
                        ProbabilitySpace, SampleStore, tracing)
from repro.core.api.spec import ConstraintSpec, ObjectiveSpec
from repro.core.optimizers import GPBayesOpt, accel
from repro.core.optimizers.base import Optimizer, SearchAdapter


def mi_opt_space():
    """MI-OPT's shape: five discrete settings and a bool categorical."""
    return ProbabilitySpace.make([
        Dimension.discrete("max_batch", [4, 8, 16, 32, 64, 128, 256]),
        Dimension.discrete("max_batch_weight",
                           [19000, 50000, 100000, 1000000, 2000000, 2968750]),
        Dimension.discrete("max_concurrent", [64, 128, 320]),
        Dimension.discrete("max_new_tokens", [512, 1024, 1536]),
        Dimension.discrete("max_seq", [1024, 2048, 4096]),
        Dimension.categorical("flash_attention", [False, True]),
    ])


def mixed_space():
    """A continuous dimension beside finite ones: the pool is sampled."""
    return ProbabilitySpace.make([
        Dimension.discrete("cpu", [1, 2, 4, 8, 16]),
        Dimension.categorical("tier", ["gp", "burst", "spot"]),
        Dimension.continuous("frac", 0.0, 1.0),
    ])


SPACES = {"mi_opt": mi_opt_space, "mixed": mixed_space}


def _value(c):
    """A deterministic objective; about one point in five cannot deploy."""
    u = sum(float(v) if not isinstance(v, str) else len(v)
            for _, v in c.values)
    if int(u * 7) % 5 == 0:
        raise MeasurementError("does not deploy")
    return {"m": (u * 0.37) % 1.0, "lat": (u * 0.61) % 1.0}


def _deploys(c):
    try:
        _value(c)
    except MeasurementError:
        return False
    return True


def _ds(space, fn=_value):
    exp = FunctionExperiment(fn=fn, properties=("m", "lat"), name="enc")
    return DiscoverySpace(space=space, actions=ActionSpace.make([exp]),
                          store=SampleStore(":memory:"))


def _adapter(space, mode="min", objective=None, fn=_value):
    return SearchAdapter(_ds(space, fn), "m", mode, objective=objective)


def _draws(space, n, seed):
    rng = np.random.default_rng(seed)
    return space.sample_configurations(rng, n)


def _reference_history(adapter):
    ok = [t for t in adapter.trials if t.value is not None]
    X = np.stack([adapter.space.encode(t.configuration) for t in ok])
    y = np.array([adapter.signed(t.value) for t in ok])
    return X, y


def _reference_feasibility(adapter):
    labelled = [t for t in adapter.trials if t.feasible is not None]
    X = np.stack([adapter.space.encode(t.configuration) for t in labelled])
    z = np.array([1.0 if t.feasible else -1.0 for t in labelled])
    return X, z


def _same(got, want):
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


class Capturing(GPBayesOpt):
    """BO-GP that keeps the arrays each scored ask built."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.seen = []

    def _acquisition(self, X, y, Xc, best=None):
        self.seen.append({"X": X, "y": y, "Xc": Xc})
        return super()._acquisition(X, y, Xc, best)

    def _top_n(self, candidates, score, n):
        self.seen[-1]["candidates"] = list(candidates)
        return super()._top_n(candidates, score, n)


class AskOnly:
    """The surface an ask-only stub offers: a space, a history, the seen
    set; no encodings, no cached enumeration."""

    def __init__(self, inner):
        self._inner = inner
        self.space = inner.space
        self.trials = inner.trials
        self.pending = inner.pending
        self.mode = inner.mode

    def seen_digests(self):
        return self._inner.seen_digests()

    def signed(self, value):
        return self._inner.signed(value)


@pytest.fixture
def recorder():
    tracing.reset()
    tracing.enable()
    yield tracing
    tracing.enable(False)
    tracing.reset()


# -- history rows ------------------------------------------------------------


@pytest.mark.parametrize("mode", ["min", "max"])
@pytest.mark.parametrize("space", sorted(SPACES))
def test_history_arrays_after_tell_and_warm_start(space, mode):
    adapter = _adapter(SPACES[space](), mode)
    draws = _draws(adapter.space, 90, seed=1)
    adapter.evaluate_batch(draws[:30])
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))
    adapter.evaluate_batch(draws[30:31])
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))
    # warm_start appends directly, past tell(); two asks in a row see it
    adapter.warm_start([(c, 0.25 * i) for i, c in enumerate(draws[31:40])])
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))
    # past the first capacity (64 rows): the buffer grows, rows stay put
    adapter.evaluate_batch(draws[40:90])
    assert len(adapter.trials) > 64
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))


def test_history_arrays_after_foreign_fold_with_recovery():
    """A foreign failure folds as a value-None trial; once another
    operation measures the point, a recovery trial is appended: the
    valued rows gather both folds in history order."""
    calls = {"n": 0}

    def flaky(c):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MeasurementError("transient")
        return _value(c)

    ds = _ds(mi_opt_space(), fn=flaky)
    configs = _draws(ds.space, 20, seed=2)
    # the point that fails once must deploy when measured again
    configs.sort(key=lambda c: not _deploys(c))
    ds.sample_batch(configs[:1], operation_id="op-a")   # fails
    adapter = SearchAdapter(ds, "m", "min", optimizer_name="member")
    adapter.evaluate_batch(configs[1:8])
    assert adapter.sync_foreign() == 1
    assert adapter.trials[-1].value is None
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))
    ds.sample_batch(configs[:1], operation_id="op-b")   # now it measures
    ds.sample_batch(configs[8:12], operation_id="op-b")
    assert adapter.sync_foreign() >= 1
    assert adapter.trials[-5].configuration == configs[0]
    assert adapter.trials[-5].value is not None
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))


@pytest.mark.parametrize("space", sorted(SPACES))
def test_feasibility_arrays(space):
    objective = ObjectiveSpec(constraints=(ConstraintSpec("lat", "<=", 0.6),))
    adapter = _adapter(SPACES[space](), objective=objective)
    draws = _draws(adapter.space, 40, seed=3)
    adapter.evaluate_batch(draws[:25])
    adapter.warm_start([(c, 0.5) for c in draws[25:30]])   # unlabelled
    _same(Optimizer._feasibility_arrays(adapter),
          _reference_feasibility(adapter))
    adapter.evaluate_batch(draws[30:])
    _same(Optimizer._feasibility_arrays(adapter),
          _reference_feasibility(adapter))
    _same(Optimizer._history_arrays(adapter), _reference_history(adapter))


# -- pool rows ---------------------------------------------------------------


@pytest.mark.parametrize("max_candidates", [4096, 50])
@pytest.mark.parametrize("space", sorted(SPACES))
def test_scored_pool_matrix(space, max_candidates):
    """Every scored ask's X, y and Xc equal the per-row encodings of its
    history and of the candidates it ranks, with and without the
    ``max_candidates`` subsample, pending points filtered."""
    adapter = _adapter(SPACES[space]())
    adapter.evaluate_batch(_draws(adapter.space, 12, seed=4))
    opt = Capturing(seed=0, max_candidates=max_candidates)
    rng = np.random.default_rng(4)
    for step in range(6):
        history = _reference_history(adapter)
        batch = opt.ask(adapter, rng, n=2)
        rec = opt.seen[-1]
        _same([rec["X"], rec["y"]], history)
        want = np.stack([adapter.space.encode(c) for c in rec["candidates"]])
        _same([rec["Xc"]], [want])
        assert len(rec["Xc"]) <= max_candidates
        assert adapter.pending.isdisjoint(c.digest for c in rec["candidates"])
        if step == 2:
            adapter.pending.add(batch[1].digest)   # in flight, not told
        adapter.evaluate_batch(batch[:1])
    assert len(opt.seen) == 6


def test_enumeration_rows_equal_space_encode():
    for space in (mi_opt_space(), ProbabilitySpace.make([
            Dimension.categorical("only", ["x"]),
            Dimension.categorical("kind", ["a", "b", "c"]),
            Dimension.discrete("n", [0.5, 2.0, 3.0, 9.0])])):
        adapter = _adapter(space)
        adapter.unseen_pool()
        rows = np.arange(space.size)
        want = np.stack([space.encode(c) for c in space.all_configurations()])
        _same([adapter.encoded_enumeration(rows)], [want])


def test_unseen_rows_follow_the_pool():
    """The cached pool and its enumeration rows stay aligned through tells
    and the pending/exclude filter."""
    adapter = _adapter(mi_opt_space())
    configs = list(adapter.space.all_configurations())
    adapter.evaluate_batch(configs[5:300:7])
    adapter.pending.add(configs[11].digest)
    exclude = {configs[12].digest, configs[40].digest}
    pool, rows = Optimizer._unseen_candidates_rows(
        adapter, np.random.default_rng(0), 4096, exclude)
    assert [configs[i] for i in rows] == pool
    assert pool == Optimizer._unseen_candidates(
        adapter, np.random.default_rng(0), 4096, exclude)
    skip = adapter.seen_digests() | exclude
    assert pool == [c for c in configs if c.digest not in skip]


# -- ask-only stubs ----------------------------------------------------------


@pytest.mark.parametrize("space", sorted(SPACES))
def test_ask_only_stub_encodes_row_by_row(space, recorder):
    adapter = _adapter(SPACES[space]())
    adapter.evaluate_batch(_draws(adapter.space, 15, seed=5))
    stub = AskOnly(adapter)
    opt = Capturing(seed=0, max_candidates=64)
    opt.ask(stub, np.random.default_rng(5), n=1)
    rec = opt.seen[-1]
    _same([rec["X"], rec["y"]], _reference_history(adapter))
    _same([rec["Xc"]],
          [np.stack([adapter.space.encode(c) for c in rec["candidates"]])])
    counts = recorder.counters()
    assert counts["encode.rows"] == len(rec["X"]) + len(rec["Xc"])
    assert "encode.rows_reused" not in counts


# -- what the cache saves, and what it must not change -----------------------


def test_second_ask_encodes_only_the_new_trials(recorder):
    adapter = _adapter(mi_opt_space())
    adapter.evaluate_batch(_draws(adapter.space, 40, seed=6))
    opt = GPBayesOpt(seed=0, max_candidates=4096)
    rng = np.random.default_rng(6)
    adapter.evaluate_batch(opt.ask(adapter, rng))
    counts = recorder.counters()
    # the first ask: every trial, and the enumeration once
    assert counts["encode.rows"] == 40 + adapter.space.size
    recorder.reset()
    valued_before = sum(t.value is not None for t in adapter.trials[:-1])
    opt.ask(adapter, rng)
    counts = recorder.counters()
    assert counts["encode.rows"] == 1
    pool = adapter.space.size - len(adapter.trials)
    assert counts["encode.rows_reused"] == valued_before + pool


@pytest.mark.parametrize("backend", [
    "numpy", pytest.param("jax", marks=pytest.mark.skipif(
        not accel.jax_available(), reason="jax unavailable"))])
@pytest.mark.parametrize("space", sorted(SPACES))
def test_serial_proposals_unchanged(space, backend):
    """A serial BO-GP run proposes the same points with the same scores
    whether the ask gathers rows from the adapter or encodes every row on
    every ask (the ask-only path, as before the cache)."""
    runs = []
    for wrap in (lambda a: a, AskOnly):
        adapter = _adapter(SPACES[space]())
        asked = wrap(adapter)
        opt = GPBayesOpt(seed=0, backend=backend, max_candidates=256)
        rng = np.random.default_rng(7)
        seq = []
        for _ in range(14):
            batch = opt.ask(asked, rng, n=1)
            seq.append((batch[0].digest, batch[0].score))
            adapter.evaluate_batch(batch)
        runs.append(seq)
    assert runs[0] == runs[1]
