"""Useful floating-point operations of one ALS iteration.

Counted from the problem, not from any implementation, at the unpadded f:

- the two Hermitians: each rating adds an f x f outer product to its row's
  system on the solve-X half and to its column's on the accumulate-Theta
  half, a multiply and an add per entry: 2 * 2 * nnz * f^2 = 4 nnz f^2;
- the solves: one Cholesky factorization of an f x f system per row of
  either side, f^3 / 3 each: (m + n) f^3 / 3.

The right-hand sides (4 nnz f) and the triangular solves (2 f^2 per row)
are left out: both are lower-order terms.  Padding slots, padded f and
rows added to round shapes do no useful work and are not counted.
"""
from __future__ import annotations


def als_iteration_flops(m: int, n: int, nnz: int, f: int) -> float:
    return 4.0 * nnz * f * f + (m + n) * f ** 3 / 3.0
