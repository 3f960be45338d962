"""The plain reference for what the timed path produces: the expected
improvement each ask ranks its pool by.

Nothing here imports the program.  :func:`gp_ei` follows the published
method directly in float64 on the host, with no caching, padding,
bucketing or kernels: an RBF + white-noise Gaussian process on the
unit-cube encoding, its posterior by Cholesky, and the analytic expected
improvement (Jones et al. 1998) for minimisation.  :func:`gp_ei_device` is
the same computation in float32 on the device at a chosen matmul
precision: the check's control.
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
