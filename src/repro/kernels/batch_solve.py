"""Pallas TPU kernel: batched SPD Cholesky solve (cuMF's batch_solve phase).

cuMF delegates ``A_u x_u = B_u`` to cuBLAS batched routines.  On TPU we give
the phase its own in-VMEM kernel: each grid step loads a batch of TB
(F x F) Hermitian systems, runs an unblocked right-looking Cholesky, then a
forward and a backward triangular solve, all without leaving VMEM (an F=128
fp32 tile is 64 KB — 0.4% of VMEM).

Dynamic scalar indexing on the lane dimension is not TPU-friendly, so every
row/column pick is a masked reduction against a 2-D ``broadcasted_iota``
and every triangular constraint a ``jnp.where`` mask — the standard trick
for in-kernel factorizations.  Vectors keep a unit dimension so that they
stay in the layout the reduction produced: a column of a ``[TB, F, F]``
block is ``[TB, F, 1]`` (F on sublanes), a row is ``[TB, 1, F]`` (F on
lanes).  Where a step needs the other orientation the vector is moved
through the diagonal (``_col_to_row`` / ``_row_to_col``), which adds only
exact zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat


def _iotas(F: int):
    """(sublane index [F, 1], lane index [1, F], diagonal mask [F, F])."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (F, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
    diag = (jax.lax.broadcasted_iota(jnp.int32, (F, F), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (F, F), 1))
    return sub, lane, diag


def _col_to_row(v, diag):
    """[TB, F, 1] -> [TB, 1, F]."""
    return jnp.sum(jnp.where(diag, v, 0.0), axis=1, keepdims=True)


def _row_to_col(v, diag):
    """[TB, 1, F] -> [TB, F, 1]."""
    return jnp.sum(jnp.where(diag, v, 0.0), axis=2, keepdims=True)


def _cholesky_inplace(A: jax.Array) -> jax.Array:
    """Right-looking Cholesky of a batch [TB, F, F]; returns lower L."""
    F = A.shape[-1]
    sub, lane, diag = _iotas(F)

    def body(j, carry):
        M, L = carry
        colj = jnp.sum(jnp.where(lane == j, M, 0.0), axis=2,
                       keepdims=True)                         # M[:, :, j]
        dj = jnp.sum(jnp.where(sub == j, colj, 0.0), axis=1,
                     keepdims=True)                           # M[:, j, j]
        dj = jnp.maximum(dj, 1e-20)
        c = jnp.where(sub >= j, colj * jax.lax.rsqrt(dj), 0.0)
        L = L + jnp.where(lane == j, c, 0.0)                  # L[:, :, j] = c
        ct = jnp.where(sub > j, c, 0.0)                       # strict trailing part
        M = M - ct * _col_to_row(ct, diag)
        return (M, L)

    _, L = jax.lax.fori_loop(0, F, body, (A, jnp.zeros_like(A)))
    return L


def _trsv_lower(L: jax.Array, b: jax.Array) -> jax.Array:
    """Solve L y = b (forward substitution); b, y are rows [TB, 1, F]."""
    F = L.shape[-1]
    sub, lane, _ = _iotas(F)

    def body(j, y):
        lrow = jnp.sum(jnp.where(sub == j, L, 0.0), axis=1,
                       keepdims=True)                         # L[j, :]
        s = jnp.sum(jnp.where(lane < j, lrow * y, 0.0), axis=2, keepdims=True)
        bj = jnp.sum(jnp.where(lane == j, b, 0.0), axis=2, keepdims=True)
        ljj = jnp.sum(jnp.where(lane == j, lrow, 0.0), axis=2, keepdims=True)
        yj = (bj - s) / jnp.maximum(ljj, 1e-20)
        return y + jnp.where(lane == j, yj, 0.0)

    return jax.lax.fori_loop(0, F, body, jnp.zeros_like(b))


def _trsv_upper_t(L: jax.Array, y: jax.Array) -> jax.Array:
    """Solve L^T x = y (back substitution on the transposed factor); y is a
    row [TB, 1, F], x is built as a column [TB, F, 1]."""
    F = L.shape[-1]
    sub, lane, diag = _iotas(F)

    def body(t, x):
        j = F - 1 - t
        lcol = jnp.sum(jnp.where(lane == j, L, 0.0), axis=2,
                       keepdims=True)                         # L[:, j] == L^T[j, :]
        s = jnp.sum(jnp.where(sub > j, lcol * x, 0.0), axis=1, keepdims=True)
        yj = jnp.sum(jnp.where(lane == j, y, 0.0), axis=2, keepdims=True)
        ljj = jnp.sum(jnp.where(sub == j, lcol, 0.0), axis=1, keepdims=True)
        xj = (yj - s) / jnp.maximum(ljj, 1e-20)
        return x + jnp.where(sub == j, xj, 0.0)

    return jax.lax.fori_loop(0, F, body, _row_to_col(jnp.zeros_like(y), diag))


def _batch_solve_kernel(a_ref, b_ref, x_ref):
    A = a_ref[...].astype(jnp.float32)        # [TB, F, F]
    b = b_ref[...].astype(jnp.float32)        # [TB, F]
    _, _, diag = _iotas(A.shape[-1])
    L = _cholesky_inplace(A)
    y = _trsv_lower(L, b[:, None, :])
    x_ref[...] = _col_to_row(_trsv_upper_t(L, y), diag)[:, 0, :]


def batch_solve_pallas(
    A: jax.Array,      # [m, F, F] SPD
    B: jax.Array,      # [m, F]
    *,
    tb: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """x_u = A_u^{-1} B_u for every u, one VMEM-resident batch per grid step."""
    m, F, _ = A.shape
    assert m % tb == 0, (m, tb)
    return compat.pallas_call(
        _batch_solve_kernel,
        grid=(m // tb,),
        in_specs=[
            pl.BlockSpec((tb, F, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, F), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, F), jnp.float32),
        interpret=interpret,
    )(A, B)
