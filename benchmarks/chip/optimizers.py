"""The program's optimizers with the benchmark's spans and answer capture,
and what the harness needs of each optimizer family.

A traffic file names its family under ``optimizer`` (``bogp`` where the
key is absent).  :data:`FAMILIES` maps each family to three things:

* ``make`` — the program's optimizer, subclassed with :class:`Spanned`;
* ``warm`` — set-up's warm-up: every device program the traffic's sizes
  reach, compiled through the optimizer's own scoring call;
* ``gaps`` — the answer check: the widest gaps between the scores each
  captured ask ranked its pool by and the reference's (``reference.py``).

:class:`Spanned` adds, around the program's own ``ask``:

* a host span ``ask``;
* the window's deadline: an ask that starts after it proposes nothing, which
  the engine treats as an exhausted space and ends the investigation;
* a capture of the ask's answer (the scores it ranked its pool by) for a
  sample of asks drawn from the seed, kept by reservoir sampling so the
  window never holds more than ``keep`` of them.

The scores are the program's own: the subclasses only record the list of
candidates, the history the ask saw, the score vector ``_top_n`` receives
and the true sizes of the scoring call.
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple

import numpy as np

from repro.core.optimizers import TPE, GPBayesOpt


class Capture:
    """Reservoir sample of ask answers, the last scored ask always kept."""

    def __init__(self, rng: np.random.Generator, keep: int):
        self.rng = rng
        self.keep = keep
        self.seen = 0
        self.sample: list = []
        self.last = None

    def offer(self, record: dict) -> None:
        self.seen += 1
        self.last = record
        if len(self.sample) < self.keep - 1:
            self.sample.append(record)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.keep - 1:
                self.sample[j] = record

    def records(self) -> list:
        last = [] if any(self.last is r for r in self.sample) else [self.last]
        return self.sample + [r for r in last if r is not None]


class Record:
    """What a window's optimizers record: their spans, the deadline, the
    answer capture and the true sizes of every scored ask: ``(history,
    pool, dims, refit)`` for BO-GP, ``(good, bad, pool, numeric dims,
    categorical dims)`` for TPE."""

    def __init__(self, spans, capture: Capture | None, deadline: float):
        self.spans = spans
        self.capture = capture
        self.deadline = deadline
        self.sizes: list = []
        self.last_history = None


class Spanned:
    """A span, the deadline and a capture around a program optimizer's
    ``ask``; mixed in before the program's class."""

    def bench_init(self, record: Record | None, asks: list):
        self._record = record
        self._capture = record.capture if record is not None else None
        self._asks = asks
        self._adapter = None

    def ask(self, adapter, rng, n=1, **kwargs):
        """``asks`` gets ``[start, number proposed]`` for every call."""
        entry = [time.perf_counter(), 0]
        self._asks.append(entry)
        if entry[0] >= self._record.deadline:
            return []
        self._adapter = adapter
        with self._record.spans.span("ask"):
            out = super().ask(adapter, rng, n, **kwargs)
        entry[1] = len(out)
        return out

    def _top_n(self, candidates, score, n):
        if self._capture is not None:
            self._capture.offer({"trials": list(self._adapter.trials),
                                 "candidates": candidates, "scores": score})
        return super()._top_n(candidates, score, n)


class SpannedGP(Spanned, GPBayesOpt):
    """The program's BO-GP; records each scored ask's sizes and whether the
    program refits."""

    def _acquisition(self, X, y, Xc, best=None):
        if self._record is not None:
            # the program refits only when the history changed: a failed
            # trial adds no value, and the next ask reuses the cached fit
            last = self._record.last_history
            refit = last is None or not (np.array_equal(X, last[0])
                                         and np.array_equal(y, last[1]))
            self._record.last_history = (X, y)
            self._record.sizes.append((len(y), len(Xc), X.shape[1], refit))
        return super()._acquisition(X, y, Xc, best)


class SpannedTPE(Spanned, TPE):
    """The program's TPE; records each scored ask's good, bad and pool
    sizes and its numeric and categorical dimensions."""

    def _score(self, space, good, bad, candidates):
        if self._record is not None:
            num = sum(d.kind != "categorical" for d in space.dimensions)
            self._record.sizes.append((len(good), len(bad), len(candidates),
                                       num, len(space.dimensions) - num))
        return super()._score(space, good, bad, candidates)


# -- set-up warm-ups -------------------------------------------------------------


def _pool_buckets(kind, traffic, opt, base_measured: int) -> list:
    """Padding buckets of every pool the window's asks can score: the
    points left unmeasured, one fewer after each trial."""
    from repro.core.optimizers.accel import bucket
    trials = int(traffic["trials"])
    left = kind.space.size - base_measured
    return sorted({bucket(min(n, opt.max_candidates))
                   for n in range(max(left - trials, 1), left + 1)})


def _history_sizes(traffic, opt) -> range:
    """Valued trials an ask of the window can see."""
    base, trials = int(traffic["base_valued_trials"]), int(traffic["trials"])
    return range(max(base, opt.n_initial), base + trials + 1)


def warm_gp(kind, traffic, rng: np.random.Generator,
            base_measured: int) -> None:
    """Compile every BO-GP program the window can use: each padding bucket
    of the history and of the pool that the traffic's sizes reach."""
    from repro.core.optimizers.accel import bucket
    opt = make_optimizer(traffic, 0, None, [])
    h_buckets = {bucket(n) for n in _history_sizes(traffic, opt)}
    D = len(kind.space.dimensions)
    for hp in sorted(h_buckets):
        for cp in _pool_buckets(kind, traffic, opt, base_measured):
            X, y, Xc = rng.random((hp, D)), rng.random(hp), rng.random((cp, D))
            opt._acquisition(X, y, Xc)


def warm_tpe(kind, traffic, rng: np.random.Generator,
             base_measured: int) -> None:
    """Compile every TPE program the window can use: each triple of the
    good set's, the bad set's and the pool's padding buckets that the
    traffic's sizes reach, through the program's scoring call."""
    from repro.core.optimizers.accel import bucket
    opt = make_optimizer(traffic, 0, None, [])
    pairs = set()
    for n in _history_sizes(traffic, opt):
        good = max(1, int(np.ceil(opt.gamma * n)))  # TPE.ask's split
        pairs.add((bucket(good), bucket(n - good)))
    configs = list(kind.space.all_configurations())

    def draw(k):
        return [configs[i] for i in rng.integers(len(configs), size=k)]
    for gp, bp in sorted(pairs):
        for cp in _pool_buckets(kind, traffic, opt, base_measured):
            opt._score(kind.space, draw(gp), draw(bp), draw(cp))


# -- answer checks ----------------------------------------------------------------


# The BO-GP control's matmul precision: one step below the "highest"
# (float32) at which the ask's programs run
CONTROL_PRECISION = "high"


def ask_gaps(config, traffic, records: list, control: bool = False) -> dict:
    """Widest gaps between the expected improvement each captured ask
    ranked its pool by and the reference's; with ``control``, the reference
    computed on the device with its matmuls at ``CONTROL_PRECISION`` takes
    the program's place."""
    import reference
    enc = reference.Encoder(config["dimensions"])
    gp = traffic["gp"]
    hyper = dict(length_scale=gp["length_scale"], noise=gp["noise"], xi=gp["xi"])
    out: dict = {}
    for rec in records:
        valued = [t for t in rec["trials"] if t.value is not None]
        X = enc.matrix([t.configuration.as_dict() for t in valued])
        y = np.array([t.value for t in valued], np.float64)
        Xc = enc.matrix([c.as_dict() for c in rec["candidates"]])
        got = rec["scores"]
        if control:
            got = reference.gp_ei_device(X, y, Xc, precision=CONTROL_PRECISION,
                                         **hyper)
        if "reference" not in rec:
            rec["reference"] = np.nan_to_num(reference.gp_ei(X, y, Xc, **hyper),
                                             nan=0.0)
        want = rec["reference"]
        # EI is measured against its peak, or against a thousandth of the
        # history's spread where the peak is smaller: an improvement that
        # small cannot steer the search, and float32 cannot resolve it once
        # the pool is nearly exhausted
        scale = max(float(np.abs(want).max()), 1e-3 * float(y.std()), 1e-300)
        # a factorisation that failed (NaN) reads as an infinite gap
        gap = float(np.nan_to_num(np.abs(got - want).max() / scale, nan=np.inf))
        regret = float((want.max() - want[int(np.argmax(np.nan_to_num(got)))])
                       / scale)
        out["ei_gap"] = max(out.get("ei_gap", 0.0), gap)
        out["ei_regret"] = max(out.get("ei_regret", 0.0), regret)
    return out


def tpe_gaps(config, traffic, records: list, control: bool = False) -> dict:
    """Widest gaps, in nats, between the Parzen score log l(x) - log g(x)
    each captured ask ranked its pool by and the reference's, which splits
    the captured history itself; with ``control``, the reference computed
    on the device in bfloat16 takes the program's place.  ``tpe_regret``
    is the reference's best score less its score at the program's pick."""
    import reference
    settings = traffic["tpe"]
    dims = config["dimensions"]
    sign = 1.0 if config["mode"] == "min" else -1.0
    out: dict = {}
    for rec in records:
        valued = [t for t in rec["trials"] if t.value is not None]
        good, bad = reference.tpe_split(
            [sign * t.value for t in valued], settings["gamma"])
        sets = ([valued[i].configuration.as_dict() for i in good],
                [valued[i].configuration.as_dict() for i in bad],
                [c.as_dict() for c in rec["candidates"]])
        got = rec["scores"]
        if control:
            got = reference.tpe_score_device(
                dims, *sets, bandwidth=settings["bandwidth"])
        if "reference" not in rec:
            rec["reference"] = reference.tpe_score(
                dims, *sets, bandwidth=settings["bandwidth"])
        want = rec["reference"]
        gap = float(np.nan_to_num(np.abs(got - want).max(), nan=np.inf))
        regret = float(want.max() - want[int(np.argmax(np.nan_to_num(got)))])
        out["tpe_gap"] = max(out.get("tpe_gap", 0.0), gap)
        out["tpe_regret"] = max(out.get("tpe_regret", 0.0), regret)
    return out


# -- the families -----------------------------------------------------------------


class Family(NamedTuple):
    make: Callable   # (traffic, seed) -> a Spanned optimizer
    warm: Callable   # (kind, traffic, rng, base_measured) -> None
    gaps: Callable   # (config, traffic, records, control) -> {name: reading}


def _make_gp(traffic: dict, seed: int) -> SpannedGP:
    return SpannedGP(seed=seed, backend=traffic["backend"],
                     max_candidates=int(traffic["pool"]), **traffic["gp"])


def _make_tpe(traffic: dict, seed: int) -> SpannedTPE:
    return SpannedTPE(seed=seed, backend=traffic["backend"],
                      max_candidates=int(traffic["pool"]), **traffic["tpe"])


FAMILIES = {
    "bogp": Family(_make_gp, warm_gp, ask_gaps),
    "tpe": Family(_make_tpe, warm_tpe, tpe_gaps),
}


def family_name(traffic: dict) -> str:
    return traffic.get("optimizer", "bogp")


def family(traffic: dict) -> Family:
    name = family_name(traffic)
    if name not in FAMILIES:
        raise SystemExit(f"unknown optimizer family {name!r} "
                         f"(known: {sorted(FAMILIES)})")
    return FAMILIES[name]


def make_optimizer(traffic: dict, seed: int, record: Record | None,
                   asks: list):
    opt = family(traffic).make(traffic, seed)
    opt.bench_init(record, asks)
    return opt
