"""The plain reference that decides ``correct``: float64 numpy solves of
sampled rows, built from the benchmark's own COO.

One ALS half-iteration solves, for every row u of one side,

    (F_u^T F_u + lam * n_u * I) x_u = F_u^T r_u

where F_u stacks the fixed factor's rows of u's rated columns and n_u is
u's rating count (weighted-lambda regularization, paper eq. 2-3); a row
with no ratings solves to x_u = 0.  Nothing here imports the program: the
ratings come from the generator's COO, not from the program's layout.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Csr:
    """One orientation of the ratings: ``cols[ptr[u]:ptr[u+1]]`` and
    ``vals[...]`` are row u's ratings."""

    ptr: np.ndarray
    cols: np.ndarray
    vals: np.ndarray

    @classmethod
    def from_coo(cls, rows, cols, vals, n_rows: int) -> "Csr":
        order = np.argsort(rows, kind="stable")
        ptr = np.zeros(n_rows + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n_rows), out=ptr[1:])
        return cls(ptr, cols[order], vals[order])

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.ptr)


def sample_rows(degrees: np.ndarray, count: int,
                rng: np.random.Generator) -> np.ndarray:
    """``count`` distinct rows across the whole degree range: half at even
    steps through the degree order (the highest degree always among them),
    half uniformly at random.  Fewer when the side has fewer rows."""
    m = degrees.size
    if m <= count:
        return np.arange(m)
    order = np.argsort(degrees, kind="stable")
    spread = order[np.linspace(0, m - 1, count // 2).astype(np.int64)]
    rest = np.setdiff1d(np.arange(m), spread)
    extra = rng.choice(rest, size=count - spread.size, replace=False)
    return np.sort(np.concatenate([spread, extra]))


def row_systems(fixed: np.ndarray, csr: Csr, rows: np.ndarray,
                lam: float) -> tuple[np.ndarray, np.ndarray]:
    """float64 ``(A [R, f, f], b [R, f])`` of ``rows`` given the ``fixed``
    other factor; a row without ratings gets ``A = I``, ``b = 0``."""
    f = fixed.shape[1]
    a = np.broadcast_to(np.eye(f), (rows.size, f, f)).copy()
    b = np.zeros((rows.size, f))
    for i, u in enumerate(rows):
        lo, hi = csr.ptr[u], csr.ptr[u + 1]
        if hi == lo:
            continue
        g = fixed[csr.cols[lo:hi]].astype(np.float64)
        a[i] = g.T @ g + lam * (hi - lo) * np.eye(f)
        b[i] = g.T @ csr.vals[lo:hi].astype(np.float64)
    return a, b


def solve_rows(fixed: np.ndarray, csr: Csr, rows: np.ndarray,
               lam: float) -> np.ndarray:
    """float64 solutions of ``rows`` given the ``fixed`` other factor."""
    a, b = row_systems(fixed, csr, rows, lam)
    return np.linalg.solve(a, b[..., None])[..., 0]


def backward_errors(solved: np.ndarray, a: np.ndarray,
                    b: np.ndarray) -> np.ndarray:
    """Per-row normwise backward error ``|A y - b| / (|A| |y| + |b|)``
    (Frobenius norm of A): how far the data of the row's system must move
    for ``y`` to solve it.  A sound float32 solve reads near float32
    rounding whatever A's condition; data computed at lower precision
    reads that precision's rounding."""
    y = solved.astype(np.float64)
    r = np.linalg.norm(np.einsum("rij,rj->ri", a, y) - b, axis=1)
    den = (np.linalg.norm(a, axis=(1, 2)) * np.linalg.norm(y, axis=1)
           + np.linalg.norm(b, axis=1))
    return np.where(den > 0, r / np.where(den > 0, den, 1.0), r)


def row_errors(solved: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-row relative error ``|solved - ref| / |ref|`` (2-norms); a row
    whose reference is 0 (no ratings) reads its absolute error."""
    diff = np.linalg.norm(solved.astype(np.float64) - ref, axis=1)
    norm = np.linalg.norm(ref, axis=1)
    return np.where(norm > 0, diff / np.where(norm > 0, norm, 1.0), diff)
