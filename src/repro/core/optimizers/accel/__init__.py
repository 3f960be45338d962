"""Accelerated ask backends for the optimizer suite.

Backend selection (an :class:`~repro.core.optimizers.base.Optimizer`
constructor arg, threaded through
:class:`~repro.core.api.spec.OptimizerSpec`):

* ``"numpy"``  — the reference implementation (default).  Always available;
  every other backend is regression-gated draw-for-draw against it.
* ``"jax"``    — jitted/vmapped hot paths on whatever device jax sees:
  :func:`gp_ei` fuses the GP Cholesky solve + batched analytic EI over the
  whole candidate pool into one device call; :func:`tpe_scores` evaluates
  every per-dimension Parzen density for all candidates at once.
* ``"pallas"`` — the jax backend with the pairwise-distance/RBF Gram
  matrices built by the blocked pallas kernel (:mod:`.pallas_rbf`), for
  the large-history regime where the Gram build dominates the GP fit.
  Degrades to ``"jax"`` on installs without pallas.

Missing-dependency policy (repo rule: never require packages the container
lacks): when jax itself is unavailable, :func:`resolve_backend` degrades
any accelerated choice to ``"numpy"`` with a one-time warning instead of
raising, and the scorer entry points return None so callers take the
reference path.

Import discipline: this package is imported by every optimizer
constructor, and ``repro.core`` is imported by every queue/process worker
the execution backends spawn — so nothing here may import jax at module
scope.  Backend probing uses ``importlib.util.find_spec`` (no import), and
the jitted implementations (:mod:`.gp_jax`, :mod:`.tpe_jax`) load on the
first accelerated scoring call.
"""

from __future__ import annotations

import functools
import importlib.util
import warnings

__all__ = ["BACKENDS", "jax_available", "pallas_available",
           "resolve_backend", "gp_ei", "gp_pof", "tpe_scores", "bucket",
           "full_precision"]

#: Every selectable ask backend, reference first.
BACKENDS = ("numpy", "jax", "pallas")

_warned: set = set()


def bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor) — the shape key the jitted
    scorers pad to, so compiled programs are reused as history grows."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def full_precision(fn):
    """Trace ``fn`` with full-float32 matmuls.  A TPU runs an f32 matmul as
    one bf16 pass by default, which would cost the Cholesky solve and the
    distance expansions their parity with the float64 numpy reference;
    elsewhere the setting changes nothing."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def jax_available() -> bool:
    """Cheap spec-level probe — deliberately does NOT import jax."""
    try:
        return importlib.util.find_spec("jax") is not None
    except (ImportError, ValueError):  # pragma: no cover - broken installs
        return False


def pallas_available() -> bool:
    """True when ``jax.experimental.pallas`` imports (this one does import
    jax — only called on an explicit pallas opt-in)."""
    if not jax_available():  # pragma: no cover - jax-less installs
        return False
    from .pallas_rbf import pallas_available as _pa
    return _pa()


def resolve_backend(backend: str) -> str:
    """Validate a backend name, degrading gracefully when the accelerator
    stack is missing: unknown names raise, unavailable ones warn once and
    fall back to the best available tier (pallas -> jax -> numpy)."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown ask backend {backend!r} (known: {BACKENDS})")
    if backend != "numpy" and not jax_available():  # pragma: no cover
        if backend not in _warned:
            _warned.add(backend)
            warnings.warn(f"ask backend {backend!r} needs jax, which is "
                          f"unavailable — falling back to 'numpy'")
        return "numpy"
    if backend == "pallas" and not pallas_available():  # pragma: no cover
        if backend not in _warned:
            _warned.add(backend)
            warnings.warn("pallas is unavailable — degrading the 'pallas' "
                          "backend to 'jax' (pure-jnp Gram build)")
        return "jax"
    return backend


def gp_ei(X, y, Xc, *, length_scale, noise, xi, use_pallas=False,
          cache=None, best=None):
    """Lazy dispatch to :func:`.gp_jax.gp_ei`; None when jax is missing.
    ``best`` overrides the incumbent EI improves on (constrained asks pass
    the best feasible value); default is the history minimum."""
    if not jax_available():  # pragma: no cover - jax-less installs
        return None
    from . import gp_jax
    return gp_jax.gp_ei(X, y, Xc, length_scale=length_scale, noise=noise,
                        xi=xi, use_pallas=use_pallas, cache=cache, best=best)


def gp_pof(X, z, Xc, *, length_scale, noise, use_pallas=False, cache=None):
    """Lazy dispatch to :func:`.gp_jax.gp_pof` — P(feasible) over the
    candidate pool from a GP on ±1 labels; None when jax is missing."""
    if not jax_available():  # pragma: no cover - jax-less installs
        return None
    from . import gp_jax
    return gp_jax.gp_pof(X, z, Xc, length_scale=length_scale, noise=noise,
                         use_pallas=use_pallas, cache=cache)


def tpe_scores(space, good_configs, bad_configs, candidates, bw=0.12):
    """Lazy dispatch to :func:`.tpe_jax.tpe_scores`; None when jax is
    missing."""
    if not jax_available():  # pragma: no cover - jax-less installs
        return None
    from . import tpe_jax
    return tpe_jax.tpe_scores(space, good_configs, bad_configs, candidates,
                              bw)
