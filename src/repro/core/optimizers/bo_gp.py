"""Gaussian-process Bayesian optimization with expected improvement.

The skopt-BO family the paper evaluates (§V-B1).  Implementation: RBF + white
kernel GP on the unit-cube encoding of configurations, analytic EI
acquisition maximized over the pool of unsampled configurations.

Two interchangeable acquisition paths (see :mod:`.accel`):

* ``backend="numpy"`` (default) — the reference ``_fit_predict`` below:
  scipy Cholesky, per-candidate posterior, scipy-norm EI.
* ``backend="jax"``/``"pallas"`` — a jitted fit/score pair
  (:func:`.accel.gp_ei`): the Cholesky factorization, cached until the
  history changes, plus batched analytic EI over the *entire* candidate
  pool via a single forward triangular solve, with the Gram matrices
  optionally built by the blocked pallas RBF kernel.  Regression-gated
  draw-for-draw against the numpy path (same candidates, same rng stream,
  argmax-identical proposals at float32 tolerances).

Robustness (shared by both backends): a Gram matrix the jittered Cholesky
cannot factor, or an EI surface that is entirely NaN (e.g. a posterior
``std`` underflow when every history value is identical after campaign
foreign-folding), must never crash the worker — ``ask`` degrades to random
proposals for that step, and isolated NaN scores are zeroed by a
``np.nan_to_num`` guard before ranking.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
from scipy.linalg import cho_factor, cho_solve
from scipy.stats import norm

from .. import tracing
from .base import Optimizer, ScoredCandidate, SearchAdapter

__all__ = ["GPBayesOpt"]


class GPBayesOpt(Optimizer):
    name = "bo-gp"

    def __init__(self, seed: int = 0, n_initial: int = 3, length_scale: float = 0.35,
                 noise: float = 1e-4, xi: float = 0.01, backend: str = "numpy",
                 max_candidates: int = 512):
        super().__init__(seed, backend=backend, max_candidates=max_candidates)
        self.n_initial = n_initial
        self.length_scale = length_scale
        self.noise = noise
        self.xi = xi  # EI exploration offset
        # Accelerated-backend fit cache (one entry: the current factorization
        # as device buffers).  Any history change — every tell or foreign
        # fold — changes the content hash and replaces it, so repeated asks
        # against one fitted surrogate skip the O(|H|^3) refit.  The
        # feasibility classifier GP keeps its own single-entry cache — its
        # training set (±1 labels over labelled trials) changes on a
        # different schedule than the value history.
        self._accel_cache: dict = {}
        self._feas_cache: dict = {}

    # -- GP machinery -----------------------------------------------------------

    def _kernel(self, A: np.ndarray, B: np.ndarray) -> np.ndarray:
        # RBF kernel on unit cube
        d2 = ((A[:, None, :] - B[None, :, :]) ** 2).sum(-1)
        return np.exp(-0.5 * d2 / (self.length_scale ** 2))

    def _fit_predict(self, X: np.ndarray, y: np.ndarray, Xc: np.ndarray):
        """Posterior (mean, std) at ``Xc``, or None when the Gram matrix
        cannot be factored even after the jitter retry — the caller treats
        an unfittable model as "no model" and proposes randomly, instead of
        letting a second ``LinAlgError`` kill the worker (and with it the
        whole campaign member) mid-ask."""
        mu_y, sd_y = y.mean(), y.std() + 1e-12
        yn = (y - mu_y) / sd_y
        K = self._kernel(X, X) + self.noise * np.eye(len(X))
        try:
            cf = cho_factor(K, lower=True)
        except np.linalg.LinAlgError:
            try:
                cf = cho_factor(K + 1e-6 * np.eye(len(X)), lower=True)
            except np.linalg.LinAlgError:
                return None
        alpha = cho_solve(cf, yn)
        Ks = self._kernel(Xc, X)
        mean = Ks @ alpha
        v = cho_solve(cf, Ks.T)
        var = np.clip(1.0 - np.einsum("ij,ji->i", Ks, v), 1e-12, None)
        return mean * sd_y + mu_y, np.sqrt(var) * sd_y

    def _acquisition(self, X: np.ndarray, y: np.ndarray, Xc: np.ndarray,
                     best: Optional[float] = None) -> Optional[np.ndarray]:
        """EI over the whole encoded candidate pool, backend-dispatched;
        None signals an unfittable model (caller falls back to random).
        ``best`` overrides the incumbent EI improves on (constrained asks
        pass the best *feasible* value); default is the history minimum."""
        if self.backend != "numpy":
            from . import accel
            ei = accel.gp_ei(X, y, Xc, length_scale=self.length_scale,
                             noise=self.noise, xi=self.xi,
                             use_pallas=self.backend == "pallas",
                             cache=self._accel_cache, best=best)
            if ei is not None:
                return ei
        fit = self._fit_predict(X, y, Xc)
        if fit is None:
            return None
        mean, std = fit
        if best is None:
            best = y.min()
        # expected improvement for minimization
        z = (best - self.xi - mean) / std
        return (best - self.xi - mean) * norm.cdf(z) + std * norm.pdf(z)

    def _feasibility_weight(self, adapter: SearchAdapter,
                            Xc: np.ndarray) -> Optional[np.ndarray]:
        """P(feasible) over the candidate pool: a second GP regressed on ±1
        feasibility labels, squashed through the normal CDF (the
        constraint-classifier construction of Gardner et al. 2014).  None
        when weighting carries no signal — the labels are all one class —
        or the classifier GP cannot be fitted.  All-feasible callers then
        rank on EI alone; all-infeasible callers (no incumbent either) fall
        back to random exploration: the standardized-y GP fit degenerates
        on a constant label vector (posterior mean -1, std ~0 -> PoF = 0
        everywhere), and ranking on that flat surface would crawl the
        candidate pool in enumeration order instead of exploring."""
        Xf, z = self._feasibility_arrays(adapter)
        if len(z) == 0 or bool((z > 0).all()) or bool((z < 0).all()):
            return None
        if self.backend != "numpy":
            from . import accel
            pof = accel.gp_pof(Xf, z, Xc, length_scale=self.length_scale,
                               noise=self.noise,
                               use_pallas=self.backend == "pallas",
                               cache=self._feas_cache)
            if pof is not None:
                return pof
        fit = self._fit_predict(Xf, z, Xc)
        if fit is None:
            return None
        mean, std = fit
        return norm.cdf(mean / np.maximum(std, 1e-12))

    # -- proposal -----------------------------------------------------------------

    def ask(self, adapter: SearchAdapter, rng: np.random.Generator,
            n: int = 1) -> List[ScoredCandidate]:
        """Top-n expected improvement over one GP fit (the model only changes
        on tell, so one posterior serves the whole batch); candidates carry
        their EI as the acquisition score.

        History handling: the GP posterior fits ``_history_arrays`` — every
        valued trial in the adapter, own *and* campaign-foreign — so under
        cooperative sharing the incumbent ``best`` and the EI surface reflect
        the union of the fleet's measurements (and fleet history counts
        toward ``n_initial``, skipping redundant random warmup).  Sharing
        never consumes rng draws, so solo trajectories are unchanged.

        Encoding: ``X`` and the pool's ``Xc`` are gathered from the
        adapter's unit-cube rows (each trial encoded once, a finite space's
        enumeration encoded once), bit-identical to encoding every row on
        every ask; a sampled pool (continuous or mixed spaces) is encoded
        row by row.

        Degenerate fits degrade instead of crashing: an unfactorable Gram
        matrix or an all-NaN EI surface (posterior-std underflow on an
        all-equal history) falls back to random proposals for this step,
        and residual NaN scores are zeroed before ranking so ``_top_n``
        never sorts on NaN.

        Under a constrained objective (SLA bounds on the adapter's
        ``objective``) the acquisition is feasibility-weighted EI: the value
        GP still fits every valued trial (an infeasible measurement is real
        evidence about the objective surface), but EI improves on the best
        *feasible* incumbent and is multiplied by P(feasible) from a second
        GP classifying the constraint verdicts.  Before any feasible value
        exists, P(feasible) alone drives the search toward the feasible
        region.  The weighting never consumes rng draws, so unconstrained
        trajectories are unchanged draw-for-draw.
        """
        candidates, rows = self._unseen_candidates_rows(
            adapter, rng, self.max_candidates)
        if not candidates:
            return []
        X, y = self._history_arrays(adapter)
        if len(y) < self.n_initial:
            return self._random_n(candidates, rng, n)

        with tracing.span("ask.encode.pool"):
            if rows is not None:
                Xc = adapter.encoded_enumeration(rows)
            else:
                tracing.count("encode.rows", len(candidates))
                Xc = np.stack([adapter.space.encode(c) for c in candidates])
        if not self._constrained(adapter):
            ei = self._acquisition(X, y, Xc)
            if ei is None or bool(np.isnan(ei).all()):
                return self._random_n(candidates, rng, n)
            ei = np.nan_to_num(ei, nan=0.0)
            return self._top_n(candidates, ei, n)

        pof = self._feasibility_weight(adapter, Xc)
        best = self._best_feasible(adapter)
        if best is None:
            # nothing feasible measured yet: EI has no incumbent to improve
            # on — chase feasibility itself (or fall back to random when the
            # classifier has nothing to say either)
            if pof is None or bool(np.isnan(pof).all()):
                return self._random_n(candidates, rng, n)
            return self._top_n(candidates, np.nan_to_num(pof, nan=0.0), n)
        ei = self._acquisition(X, y, Xc, best=best)
        if ei is None or bool(np.isnan(ei).all()):
            return self._random_n(candidates, rng, n)
        score = np.clip(np.nan_to_num(ei, nan=0.0), 0.0, None)
        if pof is not None:
            score = score * np.nan_to_num(pof, nan=0.0)
        return self._top_n(candidates, score, n)
