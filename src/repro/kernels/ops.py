"""Jitted public wrappers around the Pallas kernels (+ shape plumbing).

``fused_herm`` / ``batch_solve`` are the two ops the rest of the framework
calls.  They handle:

- the theta gather (XLA DMA-gather == the paper's texture-cached read),
- padding m / K / F up to tile multiples (F to the MXU lane width),
- kernel-vs-oracle dispatch by ``mode``: ``"ref"`` (the default) is the
  jnp oracle in ``kernels.ref``; ``"kernel"`` runs the Pallas kernels,
  compiled by Mosaic on TPU and run by the Pallas interpreter on the CPU
  backend (``repro.compat.pallas_call``; any other backend raises);
  ``"kernel_interpret"`` asks for the interpreter on any backend.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.batch_solve import batch_solve_pallas
from repro.kernels.hermitian import fused_herm_pallas

Mode = Literal["kernel", "kernel_interpret", "ref"]


def default_mode() -> Mode:
    return "kernel" if jax.default_backend() == "tpu" else "ref"


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "mode", "tm", "tk", "f_mult", "diag_fallback"))
def fused_herm(
    theta: jax.Array,   # [n, f] feature matrix (the fixed side)
    idx: jax.Array,     # [m, K] padded column indices
    val: jax.Array,     # [m, K] padded rating values
    cnt: jax.Array,     # [m]    true nnz per row
    lam: float,
    *,
    mode: Mode = "ref",
    tm: int = 8,
    tk: int = 128,
    f_mult: int = 128,
    diag_fallback: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Return (A [m, f, f], B [m, f]) of paper eq. (2) with weighted-lambda reg.

    A_u = sum_{v: r_uv != 0} theta_v theta_v^T + lambda n_u I
    B_u = Theta^T R_{u*}^T

    ``diag_fallback`` puts I on the diagonal of empty rows so the solve stays
    nonsingular (x_u = 0).  SU-ALS shards set it to False: their partial A
    matrices are psum-reduced first and the guard is applied post-reduction
    (a locally-empty row may be nonempty globally).
    """
    m, K = idx.shape
    f = theta.shape[1]
    mask = kref.mask_from_cnt(cnt, K, theta.dtype)
    diag = lam * cnt.astype(jnp.float32)
    if diag_fallback:
        diag = jnp.where(cnt > 0, diag, 1.0)
    g = jnp.take(theta, idx, axis=0)          # [m, K, f] texture-gather analogue

    if mode == "ref":
        A, B = kref.herm_ref(g, val, mask, diag)
        return A, B

    F = _round_up(f, f_mult)
    Kp = _round_up(K, tk)
    mp = _round_up(m, tm)
    g = _pad_axis(_pad_axis(_pad_axis(g, 2, F), 1, Kp), 0, mp)
    val_p = _pad_axis(_pad_axis(val, 1, Kp), 0, mp)
    mask_p = _pad_axis(_pad_axis(mask, 1, Kp), 0, mp)
    diag_p = _pad_axis(diag, 0, mp)
    A, B = fused_herm_pallas(
        g, val_p, mask_p, diag_p, tm=tm, tk=tk,
        interpret=(mode == "kernel_interpret"))
    return A[:m, :f, :f], B[:m, :f]


@functools.partial(jax.jit, static_argnames=("mode", "tb"))
def batch_solve(
    A: jax.Array,  # [m, f, f]
    B: jax.Array,  # [m, f]
    *,
    mode: Mode = "ref",
    tb: int = 8,
) -> jax.Array:
    """x_u = A_u^{-1} B_u (batched Cholesky solve)."""
    if mode == "ref":
        return kref.batch_solve_ref(A, B)
    m, f, _ = A.shape
    mp = _round_up(m, tb)
    eye_pad = jnp.eye(f, dtype=A.dtype)[None]
    A_p = _pad_axis(A, 0, mp)
    # padded batch entries get I so the factorization stays nonsingular
    if mp != m:
        padmask = (jnp.arange(mp) < m).astype(A.dtype)[:, None, None]
        A_p = A_p * padmask + (1.0 - padmask) * eye_pad
    B_p = _pad_axis(B, 0, mp)
    x = batch_solve_pallas(A_p, B_p, tb=tb,
                           interpret=(mode == "kernel_interpret"))
    return x[:m]


def als_update_factor(
    theta: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    cnt: jax.Array,
    lam: float,
    *,
    mode: Mode = "ref",
    tm: int = 8,
    tk: int = 128,
    tb: int = 8,
    f_mult: int = 128,
) -> jax.Array:
    """One half-iteration: given fixed theta, solve all rows of X (paper Alg. 1/2)."""
    A, B = fused_herm(theta, idx, val, cnt, lam,
                      mode=mode, tm=tm, tk=tk, f_mult=f_mult)
    return batch_solve(A, B, mode=mode, tb=tb)
