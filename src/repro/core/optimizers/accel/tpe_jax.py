"""Vmapped TPE Parzen ratio (the TPE/BOHB ask hot path).

The numpy reference (:func:`..tpe.tpe_score`) loops dimensions in Python
and materializes a (|pool|, |obs|) temporary per dimension per density.
Here the whole score — per-dimension numeric KDEs and smoothed categorical
pmfs for BOTH the good and bad sets, evaluated for all candidates at once —
is a single jitted device call, vmapped over dimensions.

Encoding: numeric dimensions (discrete + continuous) stack into a
``(D_num, n)`` unit-interval matrix; categorical dimensions stack into a
``(D_cat, n)`` index matrix padded to the largest cardinality, with a
per-dimension category mask so the add-one smoothing never counts
nonexistent categories.  Observation counts are zero-padded to power-of-two
buckets (masked out of every sum), so compiled programs are reused across
history growth exactly as in :mod:`.gp_jax`.

The empty-observation case (n = 0 after masking) degrades to the uniform
prior — numeric density 1 on [0, 1], categorical pmf 1/k — matching the
numpy reference evaluated on an empty set, which is what TPE's degenerate-
split fallback scores against.
"""

from __future__ import annotations

import functools

import numpy as np

try:  # pragma: no cover - exercised implicitly by backend gating
    import jax
    import jax.numpy as jnp
    HAVE_JAX = True
except Exception:  # pragma: no cover - jax-less installs
    HAVE_JAX = False

from . import bucket, full_precision

__all__ = ["tpe_scores"]

_SQRT_2PI = float(np.sqrt(2.0 * np.pi))


if HAVE_JAX:

    def _log_parzen_numeric(u_obs, m_obs, u_cand, bw):
        """Masked 1-d Parzen log-density (uniform prior + one Gaussian per
        real observation) at candidate coordinates."""
        n = m_obs.sum()
        d = (u_cand[:, None] - u_obs[None, :]) / bw
        k = jnp.exp(-0.5 * d * d) / (bw * _SQRT_2PI) * m_obs[None, :]
        dens = (1.0 + k.sum(axis=1)) / (n + 1.0)
        return jnp.log(jnp.clip(dens, 1e-12, None))

    def _log_parzen_categorical(i_obs, m_obs, i_cand, k_mask):
        """Masked add-one categorical log-pmf at candidate indices."""
        oh = jax.nn.one_hot(i_obs, k_mask.shape[0]) * m_obs[:, None]
        counts = k_mask + oh.sum(axis=0) * k_mask
        pmf = counts / counts.sum()
        return jnp.log(jnp.clip(pmf[i_cand], 1e-12, None))

    @jax.jit
    @full_precision
    def _tpe_scores(g_num, g_m, b_num, b_m, c_num,
                    g_cat, b_cat, c_cat, k_masks, bw):
        score = jnp.zeros(c_num.shape[1] if c_num.shape[0]
                          else c_cat.shape[1])
        if g_num.shape[0]:  # static: number of numeric dimensions
            lnum = jax.vmap(_log_parzen_numeric, in_axes=(0, None, 0, None))
            score = score + (lnum(g_num, g_m, c_num, bw).sum(axis=0)
                             - lnum(b_num, b_m, c_num, bw).sum(axis=0))
        if g_cat.shape[0]:  # static: number of categorical dimensions
            lcat = jax.vmap(_log_parzen_categorical, in_axes=(0, None, 0, 0))
            score = score + (lcat(g_cat, g_m, c_cat, k_masks).sum(axis=0)
                             - lcat(b_cat, b_m, c_cat, k_masks).sum(axis=0))
        return score


def _encode(space, configs, n_pad, num_dims, cat_dims):
    """(numeric unit matrix, categorical index matrix, mask) zero-padded to
    ``n_pad`` observations."""
    n = len(configs)
    num = np.zeros((len(num_dims), n_pad), np.float32)
    cat = np.zeros((len(cat_dims), n_pad), np.int32)
    for j, dim in enumerate(num_dims):
        num[j, :n] = [dim.to_unit(c[dim.name]) for c in configs]
    for j, dim in enumerate(cat_dims):
        cat[j, :n] = [dim.values.index(c[dim.name]) for c in configs]
    mask = np.zeros(n_pad, np.float32)
    mask[:n] = 1.0
    return num, cat, mask


def tpe_scores(space, good_configs, bad_configs, candidates,
               bw: float = 0.12):
    """log l(x) - log g(x) per candidate as a float64 numpy array, or None
    when jax is unavailable (caller falls back to the numpy reference)."""
    if not HAVE_JAX:  # pragma: no cover - jax-less installs
        return None
    num_dims = [d for d in space.dimensions if d.kind != "categorical"]
    cat_dims = [d for d in space.dimensions if d.kind == "categorical"]
    gp, bp = bucket(len(good_configs)), bucket(len(bad_configs))
    cp = bucket(len(candidates))
    g_num, g_cat, g_m = _encode(space, good_configs, gp, num_dims, cat_dims)
    b_num, b_cat, b_m = _encode(space, bad_configs, bp, num_dims, cat_dims)
    c_num, c_cat, _ = _encode(space, candidates, cp, num_dims, cat_dims)
    k_max = max((d.cardinality for d in cat_dims), default=1)
    k_masks = np.zeros((len(cat_dims), k_max), np.float32)
    for j, dim in enumerate(cat_dims):
        k_masks[j, :dim.cardinality] = 1.0
    score = _tpe_scores(g_num, g_m, b_num, b_m, c_num,
                        g_cat, b_cat, c_cat, k_masks, np.float32(bw))
    return np.asarray(score)[:len(candidates)].astype(np.float64)
