"""Jitted GP posterior + batched analytic EI (the BO-GP ask hot path).

Two jitted device calls replace the numpy ``_fit_predict`` + EI sequence
in :mod:`..bo_gp`, split along the standard fit/predict seam (the same
separation sklearn's ``GaussianProcessRegressor`` and GPyTorch draw):

* :func:`_gp_fit` — masked standardization, RBF Gram build (jnp
  dot-expansion or the pallas kernel), Cholesky factorization with the
  factor explicitly inverted, and the ``alpha = K^-1 y`` weights.  Its
  result is cached (caller-owned dict, keyed by a content hash of the
  history) until the history changes, so asking repeatedly against one
  fitted surrogate — the benchmark's steady-state regime, and any
  multi-batch ask between tells — pays the O(|H|^3) factorization once.
  A campaign tell invalidates the key.
* :func:`_gp_ei` — cross-covariance to the *entire* candidate pool,
  posterior mean via the cached ``alpha``, posterior variance via a
  blocked lower-triangular product (``var_i = 1 - ||L^-1 k_i||^2``, at
  roughly a quarter of the flops a generic ``cho_solve`` against the pool
  would pay), and the analytic EI surface.

Shape bucketing
---------------

History and pool sizes change every ask; jitting on exact shapes would
recompile each step.  Inputs are therefore zero-padded to power-of-two
buckets with a validity mask, so a whole campaign reuses O(log |H|)
compiled programs.  Padding is exact, not approximate: padded history rows
are masked out of the standardization, carry an identity diagonal block in
K (their Cholesky factor is trivially 1), and have zero cross-covariance
columns, so ``alpha`` and the posterior over real candidates are bitwise
independent of the bucket size; padded *candidate* rows are simply sliced
off on the host.

Robustness mirrors the numpy reference: jnp.linalg.cholesky signals
failure with NaN (not an exception), which propagates into ``alpha`` — the
host wrapper detects it and refits once with the same 1e-6 jitter the
numpy path uses, and a second failure yields an all-NaN EI surface that
the caller's NaN guard converts into a random-proposal fallback.
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

try:  # pragma: no cover - exercised implicitly by backend gating
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    from jax.scipy.stats import norm as _jnorm
    HAVE_JAX = True
except Exception:  # pragma: no cover - jax-less installs
    HAVE_JAX = False

from ... import tracing
from . import bucket, full_precision

__all__ = ["gp_ei", "gp_pof", "bucket"]


if HAVE_JAX:

    def _rbf(A, B, inv2ls2, use_pallas):
        from .pallas_rbf import rbf_matrix_jnp, rbf_matrix_pallas
        if use_pallas:
            return rbf_matrix_pallas(A, B, inv2ls2)
        return rbf_matrix_jnp(A, B, inv2ls2)

    @functools.partial(jax.jit, static_argnames=("use_pallas",))
    @full_precision
    def _gp_fit(Xh, yh, mh, inv2ls2, noise, use_pallas):
        # masked standardization (matches y.mean()/y.std() over real rows)
        nh = mh.sum()
        mu = (yh * mh).sum() / nh
        sd = jnp.sqrt((((yh - mu) * mh) ** 2).sum() / nh) + 1e-12
        yn = (yh - mu) / sd * mh

        # Gram with an identity block over padded rows: valid block gets the
        # RBF + noise diagonal, padded diagonal is 1, padded off-diagonal 0
        pair = mh[:, None] * mh[None, :]
        K = _rbf(Xh, Xh, inv2ls2, use_pallas) * pair
        K = K + jnp.diag(noise * mh + (1.0 - mh))

        L = jnp.linalg.cholesky(K)
        eye = jnp.eye(K.shape[0], dtype=K.dtype)
        Linv = solve_triangular(L, eye, lower=True)
        w = Linv @ yn
        alpha = Linv.T @ w
        best = jnp.where(mh > 0, yh, jnp.inf).min()
        return Linv, alpha, mu, sd, best

    def _inv_quadform(Linv, Ks, nblocks=8):
        """Per-row ||Linv @ k_i||^2 for lower-triangular ``Linv`` and
        row-major ``Ks`` of shape (|pool|, |H|): block matmuls that skip
        the identically-zero upper blocks of ``Linv`` — ~half the flops of
        a dense product (or a triangular solve, which XLA:CPU runs at the
        same rate).  Everything stays pool-major, so only the small
        (bs, <=n) ``Linv`` block is ever transposed, and the per-block sum
        of squares never materializes the full (|pool|, |H|) product."""
        n = Linv.shape[0]
        bs = max(1, n // nblocks)
        q = jnp.zeros(Ks.shape[0], Ks.dtype)
        for lo in range(0, n, bs):
            Vi = Ks[:, :lo + bs] @ Linv[lo:lo + bs, :lo + bs].T
            q = q + (Vi * Vi).sum(axis=1)
        return q

    @functools.partial(jax.jit, static_argnames=("use_pallas",))
    @full_precision
    def _gp_ei(Linv, alpha, mu, sd, best, Xh, mh, Xc, inv2ls2, xi,
               use_pallas):
        Ks = _rbf(Xc, Xh, inv2ls2, use_pallas) * mh[None, :]
        mean = Ks @ alpha
        # One triangular product gives the variance:
        # k*^T K^-1 k* = ||L^-1 k*||^2, so the backward half of a
        # cho_solve — the same O(|H|^2 |pool|) again, and the single most
        # expensive op of the whole ask — is never needed.
        var = jnp.clip(1.0 - _inv_quadform(Linv, Ks), 1e-12, None)
        mean, std = mean * sd + mu, jnp.sqrt(var) * sd

        imp = best - xi - mean
        z = imp / std
        return imp * _jnorm.cdf(z) + std * _jnorm.pdf(z)

    @functools.partial(jax.jit, static_argnames=("use_pallas",))
    @full_precision
    def _gp_pof(Linv, alpha, mu, sd, Xh, mh, Xc, inv2ls2, use_pallas):
        # Same cached-fit posterior as _gp_ei, squashed to P(feasible):
        # the GP regresses ±1 feasibility labels, so Φ(mean/std) is the
        # posterior probability mass above the decision boundary at 0.
        Ks = _rbf(Xc, Xh, inv2ls2, use_pallas) * mh[None, :]
        mean = Ks @ alpha
        var = jnp.clip(1.0 - _inv_quadform(Linv, Ks), 1e-12, None)
        mean, std = mean * sd + mu, jnp.sqrt(var) * sd
        return _jnorm.cdf(mean / jnp.maximum(std, 1e-12))


def _history_key(X, y, H, D, length_scale, noise, use_pallas):
    """Content hash of the fit inputs — any tell/fold changes it."""
    digest = hashlib.blake2b(digest_size=16)
    digest.update(np.ascontiguousarray(X, np.float64).tobytes())
    digest.update(np.ascontiguousarray(y, np.float64).tobytes())
    return (H, D, float(length_scale), float(noise), bool(use_pallas),
            digest.digest())


def _sent(args) -> None:
    """Count the bytes of the host arrays among a jitted call's arguments:
    each is copied to the device on every call."""
    if tracing.recording():
        tracing.count("device.h2d_bytes", sum(
            a.nbytes for a in args if isinstance(a, (np.ndarray, np.generic))))


def _fit_cached(X: np.ndarray, y: np.ndarray, length_scale: float,
                noise: float, use_pallas: bool, cache: dict | None):
    """The (padded, jitted, NaN-retried) GP fit behind both scorers,
    served from ``cache`` while the history content hash matches."""
    H = len(y)
    D = X.shape[1]
    Hp = bucket(H)
    key = _history_key(X, y, H, D, length_scale, noise, use_pallas)
    fit = cache.get("fit") if cache is not None else None
    if fit is not None and fit[0] == key:
        tracing.count("gp.fit_cache_hit")
        return fit
    tracing.count("gp.refit")
    with tracing.span("ask.fit"):
        Xh = np.zeros((Hp, D), np.float32)
        Xh[:H] = X
        yh = np.zeros(Hp, np.float32)
        yh[:H] = y
        mh = np.zeros(Hp, np.float32)
        mh[:H] = 1.0
        inv2ls2 = np.float32(0.5 / (length_scale * length_scale))
        args = (Xh, yh, mh, inv2ls2, np.float32(noise))
        _sent(args)
        Linv, alpha, mu, sd, best = _gp_fit(*args, use_pallas)
        failed = bool(jnp.isnan(alpha).any())
        tracing.count("device.d2h_bytes", 1)
        if failed:
            # Cholesky failed (NaN factor): one jittered retry, exactly the
            # numpy reference's second cho_factor attempt.  If this also
            # fails, the NaN surface downstream triggers the random fallback.
            tracing.count("gp.fit_retry")
            args = (Xh, yh, mh, inv2ls2, np.float32(noise + 1e-6))
            _sent(args)
            Linv, alpha, mu, sd, best = _gp_fit(*args, use_pallas)
    fit = (key, Linv, alpha, mu, sd, best, Xh, mh, inv2ls2)
    if cache is not None:
        cache["fit"] = fit
    return fit


def _score_pool(name: str, program, Xc: np.ndarray, before: tuple,
                after: tuple, use_pallas: bool) -> np.ndarray:
    """Pad the candidate pool to its bucket, run ``program(*before, pool,
    *after)`` on the device, and read back the scores of the real
    candidates as float64 (padded rows are sliced off)."""
    with tracing.span(name):
        C = len(Xc)
        Xcp = np.zeros((bucket(C), Xc.shape[1]), np.float32)
        Xcp[:C] = Xc
        args = (*before, Xcp, *after)
        _sent(args)
        out = np.asarray(program(*args, use_pallas))
        tracing.count("device.d2h_bytes", out.nbytes)
        return out[:C].astype(np.float64)


def gp_ei(X: np.ndarray, y: np.ndarray, Xc: np.ndarray, *,
          length_scale: float, noise: float, xi: float,
          use_pallas: bool = False, cache: dict | None = None,
          best: float | None = None):
    """Batched EI over the whole candidate pool; returns a float64 numpy
    array of shape ``(len(Xc),)``, or None when jax is unavailable (caller
    falls back to the numpy reference path).

    ``cache`` is an optimizer-owned dict holding the fitted factorization
    (device buffers) from the previous call; it is reused when the history
    content hash matches and replaced otherwise, so it never grows beyond
    one fit.  ``best`` overrides the incumbent EI improves on (constrained
    asks pass the best *feasible* value — the history minimum may be an SLA
    violator); default is the fit's history minimum.
    """
    if not HAVE_JAX:  # pragma: no cover - jax-less installs
        return None
    fit = _fit_cached(X, y, length_scale, noise, use_pallas, cache)
    _, Linv, alpha, mu, sd, fit_best, Xh, mh, inv2ls2 = fit
    if best is not None:
        fit_best = np.float32(best)
    return _score_pool("ask.ei", _gp_ei, Xc,
                       (Linv, alpha, mu, sd, fit_best, Xh, mh),
                       (inv2ls2, np.float32(xi)), use_pallas)


def gp_pof(X: np.ndarray, z: np.ndarray, Xc: np.ndarray, *,
           length_scale: float, noise: float, use_pallas: bool = False,
           cache: dict | None = None):
    """P(feasible) over the whole candidate pool from a GP regressed on ±1
    feasibility labels ``z`` (the feasibility-weighted-EI classifier);
    float64 array of shape ``(len(Xc),)``, or None when jax is unavailable.

    Reuses the exact fit machinery (padding, caching, NaN retry) of
    :func:`gp_ei` — pass a *separate* cache dict, since the label vector
    changes on a different schedule than the value history.
    """
    if not HAVE_JAX:  # pragma: no cover - jax-less installs
        return None
    fit = _fit_cached(X, z, length_scale, noise, use_pallas, cache)
    _, Linv, alpha, mu, sd, _best, Xh, mh, inv2ls2 = fit
    return _score_pool("ask.pof", _gp_pof, Xc,
                       (Linv, alpha, mu, sd, Xh, mh), (inv2ls2,), use_pallas)
