"""Jitted public wrappers around the Pallas kernels (+ shape plumbing).

``fused_herm`` / ``batch_solve`` are the two ops the rest of the framework
calls; ``herm_lanes`` / ``batch_solve_lanes`` are the same two ops with the
systems on the lanes (``[f*f, m]`` or ``[f, f, m]``, and ``[f, m]``), the
layouts of the accumulate-Theta half and of the solve kernel.  They
handle:

- the theta gather (XLA DMA-gather == the paper's texture-cached read),
- padding m / K / F up to tile multiples (F to the MXU lane width) for
  the Hermitian, and the solve's move to its systems-on-lanes layout,
- ``jax.named_scope`` names for the ALS stages (``als.herm`` with its
  ``als.gather`` inside, ``als.solve``): HLO metadata only, so device
  profiles can split device time by stage; dispatch and numerics are the
  same without them,
- kernel-vs-oracle dispatch.  ``mode`` selects the Hermitian's
  implementation: ``"ref"`` (the default) is the jnp oracle in
  ``kernels.ref``; ``"kernel"`` runs the Pallas kernel, compiled by Mosaic
  on TPU and run by the Pallas interpreter on the CPU backend
  (``repro.compat.pallas_call``; any other backend raises);
  ``"kernel_interpret"`` asks for the interpreter on any backend.  The
  solve is decided by the backend alone: on a TPU it runs the Pallas
  kernel whatever ``mode`` is, elsewhere the oracle
  ``kref.batch_solve_ref``; ``"kernel_interpret"`` runs the kernel in the
  interpreter on any backend.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels import ref as kref
from repro.kernels.batch_solve import batch_solve_pallas, lanes_per_step
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
    with jax.named_scope("als.herm"):
        m, K = idx.shape
        f = theta.shape[1]
        mask = kref.mask_from_cnt(cnt, K, theta.dtype)
        diag = lam * cnt.astype(jnp.float32)
        if diag_fallback:
            diag = jnp.where(cnt > 0, diag, 1.0)
        with jax.named_scope("als.gather"):   # texture-gather analogue
            g = jnp.take(theta, idx, axis=0)  # [m, K, f]

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


#: widest f whose lanes Hermitian runs on the vector unit: an f x f outer
#: product fills at most (f / 128)^2 of the MXU, and HIGHEST pays six bf16
#: passes for it, so at small f the rows go on the lanes and each entry is
#: one exact f32 multiply-add
VPU_HERM_MAX_F = 32


def herm_lanes(
    theta: jax.Array,   # [n, f] feature matrix (the fixed side)
    idx: jax.Array,     # [K, m] padded column indices, slots x rows
    val: jax.Array,     # [K, m] padded rating values
    cnt: jax.Array,     # [m]    true nnz per row
    lam: float,
    *,
    mode: Mode = "ref",
    tm: int = 8,
    tk: int = 128,
    f_mult: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Partial ``(A [f*f, m], B [f, m])`` of paper eq. (3), rows on the
    lanes: ``A[:, u]`` is row u's ``sum_k theta_k theta_k^T + lam n_u I``,
    flattened, with no guard for empty rows (a partial of a sum over
    batches).

    The arithmetic follows f: up to :data:`VPU_HERM_MAX_F` (in mode
    ``"ref"``) each slot's outer product is a broadcast multiply-add over
    ``[f, f, m]``; wider, ``fused_herm``'s product (the MXU, or the Pallas
    kernel of ``mode``) followed by the transpose into this layout.  Both
    are float32 throughout.
    """
    K, m = idx.shape
    f = theta.shape[1]
    if mode != "ref" or f > VPU_HERM_MAX_F:
        A, B = fused_herm(theta, idx.T, val.T, cnt, lam, mode=mode, tm=tm,
                          tk=tk, f_mult=f_mult, diag_fallback=False)
        return A.reshape(m, f * f).T, B.T
    with jax.named_scope("als.herm"):
        mask = kref.mask_from_cnt(cnt, K, theta.dtype).T       # [K, m]
        with jax.named_scope("als.gather"):
            g = jnp.transpose(jnp.take(theta, idx, axis=0), (0, 2, 1))
        gm = g * mask[:, None, :]                               # [K, f, m]
        A = jnp.sum(gm[:, :, None, :] * g[:, None, :, :], axis=0)
        diag = lam * cnt.astype(jnp.float32)
        A = A + jnp.eye(f, dtype=A.dtype)[:, :, None] * diag[None, None, :]
        B = jnp.sum((val * mask)[:, None, :] * g, axis=0)
        return A.reshape(f * f, m), B


def _solve_kernel(mode: Mode) -> bool:
    """Whether ``batch_solve`` runs the Pallas kernel (else the oracle)."""
    return mode == "kernel_interpret" or compat.on_tpu()


def solve_width(m: int, f: int, mode: Mode = "ref") -> int:
    """Systems ``batch_solve`` factors for ``m`` systems of width ``f``:
    ``m`` rounded up to the kernel's systems per step where the kernel
    runs, else ``m``."""
    if not _solve_kernel(mode):
        return m
    return _round_up(m, lanes_per_step(f, m))


def _solve_lanes(At: jax.Array, Bt: jax.Array, mode: Mode) -> jax.Array:
    f, _, m = At.shape
    if not _solve_kernel(mode):
        return kref.batch_solve_ref(jnp.transpose(At, (2, 1, 0)), Bt.T).T
    mp = solve_width(m, f, mode)
    if mp != m:       # padded systems are I x = 0
        eye = jnp.broadcast_to(jnp.eye(f, dtype=At.dtype)[:, :, None],
                               (f, f, mp - m))
        At = jnp.concatenate([At, eye], axis=2)
        Bt = _pad_axis(Bt, 1, mp)
    x = batch_solve_pallas(At, Bt, interpret=(mode == "kernel_interpret"))
    return x[:, :m]


def batch_solve_lanes(
    At: jax.Array,  # [f, f, m] column, row, system
    Bt: jax.Array,  # [f, m]    row, system
    *,
    mode: Mode = "ref",
) -> jax.Array:
    """``[f, m]`` solutions of SPD systems held with the systems on the
    lanes, the layout the kernel computes in; see the module doc for which
    implementation runs.  Traced inside its callers' programs."""
    with jax.named_scope("als.solve"):
        return _solve_lanes(At, Bt, mode)


@functools.partial(jax.jit, static_argnames=("mode", "lanes"))
def batch_solve(
    A: jax.Array,  # [m, f, f]; with lanes [f, f, m]
    B: jax.Array,  # [m, f];    with lanes [f, m]
    *,
    mode: Mode = "ref",
    lanes: bool = False,
) -> jax.Array:
    """x_u = A_u^{-1} B_u (batched Cholesky solve): the lanes solve between
    the transposes into and out of its layout, ``[m, f]``; with ``lanes``
    the systems are already on the lanes and the result stays there,
    ``[f, m]`` (:func:`batch_solve_lanes`)."""
    if lanes:
        return batch_solve_lanes(A, B, mode=mode)
    with jax.named_scope("als.solve"):
        return _solve_lanes(jnp.transpose(A, (2, 1, 0)), B.T, mode).T


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
    f_mult: int = 128,
) -> jax.Array:
    """One half-iteration: given fixed theta, solve all rows of X (paper Alg. 1/2)."""
    A, B = fused_herm(theta, idx, val, cnt, lam,
                      mode=mode, tm=tm, tk=tk, f_mult=f_mult)
    return batch_solve(A, B, mode=mode)
