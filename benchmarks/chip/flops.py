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
