"""Pallas TPU kernel: batched SPD Cholesky solve (cuMF's batch_solve phase).

cuMF delegates ``A_u x_u = B_u`` to cuBLAS batched routines.  ALS solves
tens of thousands of small (f x f) systems at once, so on the TPU the
systems go on the lanes: the kernel sees ``A`` as ``[f, f, m]`` (column,
row, system) and ``B`` as ``[f, m]`` (row, system).  A grid step holds
``T`` systems (a multiple of 128) in VMEM; the row index sits on sublanes
and the column index on the leading axis of a ``[f, f, T]`` scratch, where
an unblocked right-looking Cholesky runs in place.  Step j reads column j
(a leading-axis index) and its diagonal (one sublane), scales the column
by the square root of the diagonal, and subtracts its outer product from
the trailing columns, one ``[f, T]`` multiply-subtract per column.  Forward
and back substitution follow in the same layout.  Every pick is a
leading-axis index or a single-sublane read, so each operation is
full-width f32 work on the vector unit, with no lane reductions and no
padding of f.

The wrapper ``kernels.ops.batch_solve`` moves ``A`` and ``B`` into this
layout, pads the systems to a multiple of ``T`` with identity systems
(x = 0), and moves the solution back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels.budgets import BUDGETS, SOLVE_VMEM_LIMIT, footprint_bytes

LANES = 128
#: a column of a step, ``[f, T]`` f32, stays within this many vregs
#: (8 x 128 words each) so it is held in registers through its updates
COL_VREGS = 16


def lanes_per_step(f: int, m: int) -> int:
    """Systems per grid step for width ``f`` and ``m`` systems: 128 lanes
    times the largest power of two up to 8 that keeps a column within
    ``COL_VREGS`` vregs and the step within the kernel's VMEM budget, and
    no more lanes than ``m`` needs.  (Past f ~ 104 even 128 lanes exceed
    the budget: the driver's ``vmem/batch_solve_pallas`` record says so.)"""
    budget = BUDGETS["batch_solve_pallas"].vmem_limit
    k = 1
    while (2 * k <= 8 and -(-f // 8) * 2 * k <= COL_VREGS
           and footprint_bytes("batch_solve_pallas", f=f,
                               T=2 * k * LANES) <= budget):
        k *= 2
    return min(k * LANES, -(-m // LANES) * LANES)


def _batch_solve_kernel(a_ref, b_ref, x_ref, l_ref, y_ref):
    """One step: ``l_ref`` [f, f, T] (column, row, system); ``y_ref``
    [f, T]."""
    f = b_ref.shape[0]
    l_ref[...] = a_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 0)

    def factor(j, carry):
        d = l_ref[j, pl.ds(j, 1), :]                          # A_jj, [1, T]
        col = jnp.where(rows >= j, l_ref[j] / jnp.sqrt(d), 0.0)
        l_ref[j] = col                                        # L[:, j]

        def update(k, c):
            l_ref[k] = l_ref[k] - col * l_ref[j, pl.ds(k, 1), :]
            return c

        jax.lax.fori_loop(j + 1, f, update, 0)
        return carry

    jax.lax.fori_loop(0, f, factor, 0)

    # L y = b, column by column; L's column j is zero above row j
    y_ref[...] = b_ref[...]

    def forward(j, carry):
        yj = y_ref[pl.ds(j, 1), :] / l_ref[j, pl.ds(j, 1), :]
        y_ref[...] = y_ref[...] - l_ref[j] * yj
        y_ref[pl.ds(j, 1), :] = yj
        return carry

    jax.lax.fori_loop(0, f, forward, 0)

    # L^T x = y from the last row up; rows of x not yet solved are zero
    x_ref[...] = jnp.zeros_like(x_ref)

    def backward(t, carry):
        j = f - 1 - t
        s = jnp.sum(l_ref[j] * x_ref[...], axis=0, keepdims=True)
        x_ref[pl.ds(j, 1), :] = ((y_ref[pl.ds(j, 1), :] - s)
                                 / l_ref[j, pl.ds(j, 1), :])
        return carry

    jax.lax.fori_loop(0, f, backward, 0)


def batch_solve_pallas(
    At: jax.Array,     # [f, f, m] SPD systems, column-row-system
    Bt: jax.Array,     # [f, m]    right-hand sides, row-system
    *,
    interpret: bool = False,
) -> jax.Array:
    """``[f, m]`` solutions of every system; m a multiple of
    ``lanes_per_step(f, m)``."""
    f, _, m = At.shape
    T = lanes_per_step(f, m)
    assert m % T == 0, (m, T)
    return compat.pallas_call(
        _batch_solve_kernel,
        grid=(m // T,),
        in_specs=[
            pl.BlockSpec((f, f, T), lambda i: (0, 0, i)),
            pl.BlockSpec((f, T), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((f, T), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f, m), jnp.float32),
        scratch_shapes=[
            compat.vmem((f, f, T), jnp.float32),   # A, factored in place
            compat.vmem((f, T), jnp.float32),      # y of L y = b
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=SOLVE_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(At, Bt)
