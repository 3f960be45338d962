"""The plain reference for what the timed path produces: the score each
ask ranks its pool by.

Nothing here imports the program.  :func:`gp_ei` follows the published
method directly in float64 on the host, with no caching, padding,
bucketing or kernels: an RBF + white-noise Gaussian process on the
unit-cube encoding, its posterior by Cholesky, and the analytic expected
improvement (Jones et al. 1998) for minimisation.  :func:`gp_ei_device` is
the same computation in float32 on the device at a chosen matmul
precision: the check's control.

:func:`tpe_split` and :func:`tpe_score` are the tree-structured Parzen
estimator's ask (Bergstra et al. 2011) in float64 on the host: the
history split at its gamma-quantile into a good and a bad set, and log
l(x) - log g(x) per candidate from one Parzen density per dimension and
set.  :func:`tpe_score_device` is the same score in bfloat16 on the
device: the TPE check's control.
"""

from __future__ import annotations

import numpy as np


# -- encoding -----------------------------------------------------------------


class Encoder:
    """Unit-cube coordinates of a configuration: each dimension's value goes
    to its index over (count - 1).  ``dims`` is the configuration file's
    list of dimensions, each with its ``name`` and ``values``."""

    def __init__(self, dims: list):
        self.dims = [(d["name"], {v: i / max(len(d["values"]) - 1, 1)
                                  for i, v in enumerate(d["values"])})
                     for d in dims]

    def matrix(self, configs: list) -> np.ndarray:
        return np.array([[unit[c[name]] for name, unit in self.dims]
                         for c in configs], np.float64).reshape(len(configs),
                                                                len(self.dims))


# -- GP expected improvement ---------------------------------------------------


def gp_ei(X: np.ndarray, y: np.ndarray, Xc: np.ndarray, *, length_scale: float,
          noise: float, xi: float) -> np.ndarray:
    from scipy.linalg import cho_solve, cholesky, solve_triangular
    from scipy.stats import norm

    X, y, Xc = (np.asarray(a, np.float64) for a in (X, y, Xc))
    mu, sd = y.mean(), y.std() + 1e-12
    yn = (y - mu) / sd

    def rbf(A, B):
        d2 = (A * A).sum(-1)[:, None] + (B * B).sum(-1)[None, :] - 2.0 * A @ B.T
        return np.exp(-0.5 * np.maximum(d2, 0.0) / length_scale ** 2)

    K = rbf(X, X) + noise * np.eye(len(X))
    try:
        L = cholesky(K, lower=True)
    except np.linalg.LinAlgError:
        L = cholesky(K + 1e-6 * np.eye(len(X)), lower=True)
    alpha = cho_solve((L, True), yn)
    mean = np.empty(len(Xc))
    var = np.empty(len(Xc))
    for lo in range(0, len(Xc), 1024):  # blocks of the pool bound the memory
        Ks = rbf(Xc[lo:lo + 1024], X)
        mean[lo:lo + 1024] = Ks @ alpha
        V = solve_triangular(L, Ks.T, lower=True)  # k' K^-1 k = |L^-1 k|^2
        var[lo:lo + 1024] = 1.0 - (V * V).sum(0)
    mean = mean * sd + mu
    std = np.sqrt(np.clip(var, 1e-12, None)) * sd
    best = y.min()
    z = (best - xi - mean) / std
    return (best - xi - mean) * norm.cdf(z) + std * norm.pdf(z)


def gp_ei_device(X, y, Xc, *, length_scale: float, noise: float, xi: float,
                 precision: str) -> np.ndarray:
    """The same expected improvement in float32 on the device, every matmul
    at ``precision`` ("highest", "high" or "default"); the control of the
    check runs it below the precision the ask states."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve, solve_triangular
    from jax.scipy.stats import norm

    def rbf(A, B):
        d2 = (A * A).sum(-1)[:, None] + (B * B).sum(-1)[None, :] - 2.0 * A @ B.T
        return jnp.exp(-0.5 * jnp.maximum(d2, 0.0) / length_scale ** 2)

    def ei(X, y, Xc):
        mu, sd = y.mean(), y.std() + 1e-12
        K = rbf(X, X) + noise * jnp.eye(X.shape[0], dtype=X.dtype)
        L = jnp.linalg.cholesky(K)
        alpha = cho_solve((L, True), (y - mu) / sd)
        Ks = rbf(Xc, X)
        v = solve_triangular(L, Ks.T, lower=True)
        mean = (Ks @ alpha) * sd + mu
        std = jnp.sqrt(jnp.clip(1.0 - (v * v).sum(0), 1e-12, None)) * sd
        z = (y.min() - xi - mean) / std
        return (y.min() - xi - mean) * norm.cdf(z) + std * norm.pdf(z)

    with jax.default_matmul_precision(precision):
        out = jax.jit(ei)(*(jnp.asarray(a, jnp.float32) for a in (X, y, Xc)))
    return np.asarray(out, np.float64)


# -- TPE Parzen ratio -----------------------------------------------------------


def tpe_split(values, gamma: float) -> tuple:
    """(good, bad) indices of a history: the ceil(gamma * n) lowest values
    (at least one) are good, the rest bad.  ``values`` are in the
    minimising sense."""
    order = np.argsort(np.asarray(values, np.float64), kind="stable")
    n_good = max(1, int(np.ceil(gamma * len(order))))
    return order[:n_good], order[n_good:]


def _indices(dim: dict, configs: list) -> np.ndarray:
    index = {v: i for i, v in enumerate(dim["values"])}
    return np.array([index[c[dim["name"]]] for c in configs], np.int64)


def tpe_score(dims: list, good: list, bad: list, candidates: list, *,
              bandwidth: float) -> np.ndarray:
    """log l(x) - log g(x) at each candidate, in float64; ``dims`` is the
    configuration file's list of dimensions, the sets are lists of
    ``{name: value}``.

    Per dimension and set, summed over dimensions (the densities factorise):

    * a finite numeric dimension: each value goes to its index over
      (count - 1) in [0, 1]; the density is a uniform prior on [0, 1] plus
      one Gaussian of width ``bandwidth`` at each observation, over
      (observations + 1);
    * a categorical dimension: the add-one smoothed frequency of the
      candidate's category among the observations.

    An empty set leaves the prior alone: density 1, or 1 / count.  As the
    program states its TPE, and unlike Bergstra et al., the Gaussians are
    neither truncated to [0, 1] nor given a width per observation.
    """
    score = np.zeros(len(candidates))
    for dim in dims:
        k = len(dim["values"])
        c = _indices(dim, candidates)
        for obs, sign in ((good, 1.0), (bad, -1.0)):
            o = _indices(dim, obs)
            if dim["kind"] == "categorical":
                counts = 1.0 + np.bincount(o, minlength=k)
                logp = np.log(counts / counts.sum())[c]
            else:
                unit = 1.0 / max(k - 1, 1)
                d = (c[:, None] - o[None, :]) * unit / bandwidth
                kernel = np.exp(-0.5 * d * d) / (bandwidth * np.sqrt(2 * np.pi))
                logp = np.log((1.0 + kernel.sum(axis=1)) / (len(o) + 1))
            score += sign * logp
    return score


def tpe_score_device(dims: list, good: list, bad: list, candidates: list, *,
                     bandwidth: float) -> np.ndarray:
    """The same score on the device with the coordinates, the kernel and
    every sum in bfloat16, one precision below the float32 the program's
    scoring states: the TPE check's control.  One jitted call per set of
    sizes."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    sqrt_2pi = float(np.sqrt(2 * np.pi))

    def log_density(dim, o, c):
        k = len(dim["values"])
        if dim["kind"] == "categorical":
            counts = jnp.ones(k, bf16).at[o].add(jnp.ones(o.shape, bf16))
            return jnp.log(counts / counts.sum())[c]
        unit = jnp.asarray(1.0 / max(k - 1, 1), bf16)
        bw = jnp.asarray(bandwidth, bf16)
        d = (c[:, None].astype(bf16) * unit - o[None, :].astype(bf16) * unit) / bw
        kernel = jnp.exp(-0.5 * d * d) / (bw * jnp.asarray(sqrt_2pi, bf16))
        return jnp.log((1.0 + kernel.sum(axis=1, dtype=bf16))
                       / jnp.asarray(o.shape[0] + 1, bf16))

    @jax.jit
    def score(g, b, c):
        out = jnp.zeros(c.shape[0], bf16)
        for j, dim in enumerate(dims):
            out = (out + log_density(dim, g[:, j], c[:, j])
                   - log_density(dim, b[:, j], c[:, j]))
        return out

    def matrix(configs):
        return np.stack([_indices(dim, configs) for dim in dims], axis=1)
    out = score(matrix(good), matrix(bad), matrix(candidates))
    return np.asarray(out, np.float64)
