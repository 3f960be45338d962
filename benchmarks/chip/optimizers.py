"""The program's BO-GP optimizer with the benchmark's spans and answer
capture.

The subclass adds, around the program's own ``ask``:

* a host span ``ask``;
* the window's deadline: an ask that starts after it proposes nothing, which
  the engine treats as an exhausted space and ends the investigation;
* a capture of the ask's answer (the scores it ranked its pool by) for a
  sample of asks drawn from the seed, kept by reservoir sampling so the
  window never holds more than ``keep`` of them.

The scores are the program's own: the subclass only records the list of
candidates, the history the ask saw and the score vector ``_top_n``
receives.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.optimizers import GPBayesOpt


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
    answer capture and the true sizes of every scored ask."""

    def __init__(self, spans, capture: Capture | None, deadline: float):
        self.spans = spans
        self.capture = capture
        self.deadline = deadline
        self.sizes: list = []       # (history, pool, dims, refit)
        self.last_history = None


class SpannedGP(GPBayesOpt):
    """The program's BO-GP with a span, the deadline and a capture around
    its ``ask``."""

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

    def _top_n(self, candidates, score, n):
        if self._capture is not None:
            self._capture.offer({"trials": list(self._adapter.trials),
                                 "candidates": candidates, "scores": score})
        return GPBayesOpt._top_n(candidates, score, n)


def make_optimizer(traffic: dict, seed: int, record: Record | None,
                   asks: list):
    opt = SpannedGP(seed=seed, backend=traffic["backend"],
                    max_candidates=int(traffic["pool"]), **traffic["gp"])
    opt.bench_init(record, asks)
    return opt
