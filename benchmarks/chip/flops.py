"""Operations and bytes of the work each device program needs, from shapes.

Counts are of the algorithm's necessary work at the true sizes, never at a
padded bucket, so a change that stops padding cannot read as a gain in
efficiency.  A multiply-add is two operations.
"""

from __future__ import annotations


def gp_ask(n: int, pool: int, dims: int, refit: bool = True) -> tuple:
    """(flops, bytes) of one expected-improvement pass over a history of
    ``n`` points and a pool of ``pool`` candidates in ``dims`` coordinates,
    in float32, with the GP fit before it where ``refit``.

    Counted for the fit: the Gram matrix (a 2*dims-operation distance and an
    exp per entry), its Cholesky factor (n^3/3) and the weights (two
    triangular solves, 2 n^2).  For the pass: the cross-covariance to the
    pool (2*dims + 1 per entry), the posterior mean (2 n per candidate) and
    the variance's triangular solve against every candidate (n^2 per
    candidate).  Left out: an explicit inverse of the factor, which the
    posterior does not need.  Bytes: the inputs and the scores once, and the
    factor written once by the fit and read once by the variance's solve.
    """
    flops = pool * n * (2 * dims + 1) + 2 * pool * n + pool * n * n
    bytes_ = 4 * (pool * dims + pool + n * dims) + 4 * n * n
    if refit:
        flops += n * n * (2 * dims + 1) + n ** 3 / 3 + 2 * n * n
        bytes_ += 4 * n + 4 * n * n
    return float(flops), float(bytes_)


def tpe_ask(good: int, bad: int, pool: int, num_dims: int,
            cat_dims: int) -> tuple:
    """(flops, bytes) of one Parzen-ratio pass, log l(x) - log g(x), over a
    pool of ``pool`` candidates against a good set of ``good`` and a bad
    set of ``bad`` observations, in float32.

    Counted for each numeric dimension: five operations for each pair of a
    candidate and an observation (the difference, its square, the scale by
    -1/(2 bandwidth^2), the exponential as one operation, the accumulation)
    and three for each candidate and set (the prior added, the division by
    the set's size plus one, the logarithm).  For each categorical
    dimension: one count per observation, and per candidate and set a
    look-up and a logarithm.  Then the score, one addition per candidate,
    dimension and set.  The Gaussian's constant factor folds into the
    scale.  Bytes: the coordinates of every observation and candidate read
    once (numeric float32, categorical int32) and the scores written once.
    """
    n = good + bad
    flops = num_dims * (5 * pool * n + 3 * 2 * pool)
    flops += cat_dims * (n + 2 * 2 * pool)
    flops += 2 * pool * (num_dims + cat_dims)
    bytes_ = 4 * (n + pool) * (num_dims + cat_dims) + 4 * pool
    return float(flops), float(bytes_)
