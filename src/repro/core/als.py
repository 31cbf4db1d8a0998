"""MO-ALS: the single-device ALS driver (paper Alg. 1 / Alg. 2).

The alternating structure is exactly the paper's: update X with Theta fixed
(eq. 2), then update Theta with X fixed (eq. 3), both through the fused
hermitian kernel + batched Cholesky solve.  The q-batching ("solve X in
batches when X is big and Theta fits", paper §3.4 'Limitation of MO-ALS')
is a ``lax.map`` over row blocks so memory stays bounded at m_b f^2.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.objective import rmse_padded
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class AlsConfig:
    f: int                    # latent dimension
    lam: float                # weighted-lambda regularization strength
    iters: int = 10           # full ALS iterations (each = update-X + update-Theta)
    batch_rows: int = 0       # q-batch size; 0 = solve all rows at once
    mode: str = "ref"         # Hermitian: ref | kernel | kernel_interpret
    tm: int = 8
    tk: int = 128
    f_mult: int = 128
    seed: int = 0
    init_scale: float = 0.3   # paper initializes factors U[0, 1]; we scale down


class AlsState(NamedTuple):
    x: jax.Array        # [m, f]
    theta: jax.Array    # [n, f]
    iteration: jax.Array  # scalar int32


def als_init(m: int, n: int, cfg: AlsConfig) -> AlsState:
    kx, kt = jax.random.split(jax.random.PRNGKey(cfg.seed))
    x = jax.random.uniform(kx, (m, cfg.f), jnp.float32) * cfg.init_scale
    theta = jax.random.uniform(kt, (n, cfg.f), jnp.float32) * cfg.init_scale
    return AlsState(x=x, theta=theta, iteration=jnp.int32(0))


def _map_row_blocks(solve_block, arrays, batch_rows: int, pad_vals=None):
    """Row-block scaffolding shared by the q-batched solves: pad the leading
    axis up to a multiple of ``batch_rows`` (with zeros, or a broadcast
    ``pad_vals[i]`` per array — e.g. I for Hermitians so padded solves stay
    nonsingular), ``lax.map`` over the blocks, unpad the result."""
    m = arrays[0].shape[0]
    nb = -(-m // batch_rows)
    pad = nb * batch_rows - m
    blocked = []
    for i, a in enumerate(arrays):
        pv = None if pad_vals is None else pad_vals[i]
        if pad:
            if pv is None:
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            else:
                a = jnp.concatenate(
                    [a, jnp.broadcast_to(pv, (pad,) + a.shape[1:])])
        blocked.append(a.reshape((nb, batch_rows) + a.shape[1:]))
    out = jax.lax.map(solve_block, tuple(blocked))
    return out.reshape((nb * batch_rows,) + out.shape[2:])[:m]


def _update_factor(theta, idx, val, cnt, cfg: AlsConfig) -> jax.Array:
    """Solve every row of one factor given the other side fixed."""
    solve = functools.partial(
        kops.als_update_factor, lam=cfg.lam, mode=cfg.mode,
        tm=cfg.tm, tk=cfg.tk, f_mult=cfg.f_mult)
    m = idx.shape[0]
    if cfg.batch_rows and cfg.batch_rows < m:
        return _map_row_blocks(
            lambda b: solve(theta, b[0].astype(jnp.int32),
                            b[1], b[2].astype(jnp.int32)),
            (idx.astype(jnp.int32), val, cnt.astype(jnp.int32)),
            cfg.batch_rows)
    return solve(theta, idx, val, cnt)


def update_rows(fixed, idx, val, cnt, cfg: AlsConfig) -> jax.Array:
    """Per-slice update entry point (out-of-core wave driver, solve side).

    Solves the rows of one factor slice given the ``fixed`` other factor —
    identical math to a full ``_update_factor`` call restricted to the slice,
    so streaming a factor in row slices reproduces the in-core trajectory.
    """
    return _update_factor(fixed, idx, val, cnt, cfg)


def partial_herm(x_batch, idx_loc, val_loc, cnt_loc, cfg: AlsConfig):
    """Per-batch partial Hermitian (out-of-core wave driver, accumulate side).

    ``idx_loc`` indexes into ``x_batch`` (batch-local user coordinates, the
    output of ``partition_padded`` on R^T).  Returns (A_j, B_j) partial sums;
    summing over all q batches reproduces the full-gather Hermitian because
    the weighted-lambda diagonal ``lam * cnt_loc`` also telescopes to
    ``lam * cnt_total`` — the same partial-sum scheme SU-ALS reduces over the
    "model" axis (eq. 5-7), serialized over waves instead.
    """
    return kops.fused_herm(
        x_batch, idx_loc, val_loc, cnt_loc, cfg.lam,
        mode=cfg.mode, tm=cfg.tm, tk=cfg.tk, f_mult=cfg.f_mult,
        diag_fallback=False)


def solve_accumulated(A, B, cnt_total, cfg: AlsConfig) -> jax.Array:
    """Solve a factor from accumulated partial Hermitians.

    Applies the globally-empty-row guard post-accumulation (a row empty in
    every batch gets A = I, B = 0 -> x = 0, matching ``fused_herm``'s
    ``diag_fallback``) then runs the batched Cholesky solve, in row blocks of
    ``cfg.batch_rows`` when set so the solve working set stays bounded.
    """
    f = A.shape[-1]
    empty = (cnt_total <= 0).astype(A.dtype)
    A = A + empty[:, None, None] * jnp.eye(f, dtype=A.dtype)[None, :, :]
    solve = functools.partial(kops.batch_solve, mode=cfg.mode)
    m = A.shape[0]
    if cfg.batch_rows and cfg.batch_rows < m:
        return _map_row_blocks(
            lambda ab: solve(ab[0], ab[1]), (A, B), cfg.batch_rows,
            pad_vals=(jnp.eye(f, dtype=A.dtype), None))
    return solve(A, B)


def solved_systems(m: int, cfg: AlsConfig) -> int:
    """Systems a solve of ``m`` rows factors: whole row blocks of
    ``cfg.batch_rows`` when it maps them (padded with identity systems),
    each widened to the solve's own step (``kops.solve_width``)."""
    if cfg.batch_rows and cfg.batch_rows < m:
        return (-(-m // cfg.batch_rows)
                * kops.solve_width(cfg.batch_rows, cfg.f, cfg.mode))
    return kops.solve_width(m, cfg.f, cfg.mode)


def als_iteration(state: AlsState, r, rt, cfg: AlsConfig) -> AlsState:
    """One full ALS iteration.  ``r`` / ``rt`` are (idx, val, cnt) triplets of
    R in row-major (users) and of R^T (items) respectively."""
    x = _update_factor(state.theta, r[0], r[1], r[2], cfg)
    theta = _update_factor(x, rt[0], rt[1], rt[2], cfg)
    return AlsState(x=x, theta=theta, iteration=state.iteration + 1)


def als_train(
    r, rt, m: int, n: int, cfg: AlsConfig,
    test: Optional[tuple] = None,
    callback=None,
) -> tuple[AlsState, list[dict]]:
    """Full training driver.  Returns (final state, per-iteration history).

    ``test`` is an optional (idx, val, cnt) triplet evaluated after every
    iteration (paper Fig. 6 protocol: test RMSE vs iteration)."""
    state = als_init(m, n, cfg)
    history: list[dict] = []
    for it in range(cfg.iters):
        state = als_iteration(state, r, rt, cfg)
        rec = {"iteration": it + 1}
        if test is not None:
            rec["test_rmse"] = float(
                rmse_padded(state.x, state.theta, test[0], test[1], test[2]))
        rec["train_rmse"] = float(
            rmse_padded(state.x, state.theta, r[0], r[1], r[2]))
        history.append(rec)
        if callback is not None:
            callback(state, rec)
    return state, history


def ell_triplet(ell) -> tuple[jax.Array, jax.Array, jax.Array]:
    """PaddedELL -> device triplet (idx, val, cnt)."""
    return (jnp.asarray(np.asarray(ell.idx), jnp.int32),
            jnp.asarray(np.asarray(ell.val), jnp.float32),
            jnp.asarray(np.asarray(ell.cnt), jnp.int32))


# ---------------------------------------------------------------------------
# Degree-binned dispatch: the same kernels, once per bin at that bin's K.
# One compiled shape per bin (bounded by n_bins); padding slots are exact
# zeros, so per-bin K changes no f32 sum and binned == unbinned numerically.
# ---------------------------------------------------------------------------

def update_factor_binned(fixed, binned, cfg: AlsConfig) -> jax.Array:
    """Solve one factor from a :class:`~repro.sparse.padded.BinnedELL`:
    dispatch ``als_update_factor`` once per degree bin at the bin's own K,
    scatter results back to original row order through ``binned.rows``."""
    out = jnp.zeros((binned.m, cfg.f), jnp.float32)
    for b, rows in zip(binned.bins, binned.rows):
        if b.m == 0:
            continue
        idx, val, cnt = ell_triplet(b)
        xb = _update_factor(fixed, idx, val, cnt, cfg)
        out = out.at[jnp.asarray(np.asarray(rows), jnp.int32)].set(xb)
    return out


def update_rows_binned(fixed, binned, cfg: AlsConfig) -> jax.Array:
    """Binned per-slice update (out-of-core wave driver, solve side): the
    slice arrives as a BinnedELL with slice-local row indices; results come
    back in slice row order, exactly like :func:`update_rows` on the
    uniform layout."""
    return update_factor_binned(fixed, binned, cfg)


def partial_herm_binned(x_batch, binned_loc, cfg: AlsConfig):
    """Binned per-batch partial Hermitian (accumulate side): run
    :func:`partial_herm` once per bin of the batch-local R^T shard and
    scatter-add into full-size (A_j, B_j), so the caller's per-batch
    ``A += A_j`` accumulation is layout-blind."""
    n, f = binned_loc.m, cfg.f
    A = jnp.zeros((n, f, f), jnp.float32)
    B = jnp.zeros((n, f), jnp.float32)
    for b, rows in zip(binned_loc.bins, binned_loc.rows):
        if b.m == 0:
            continue
        idx, val, cnt = ell_triplet(b)
        Ab, Bb = partial_herm(x_batch, idx, val, cnt, cfg)
        r = jnp.asarray(np.asarray(rows), jnp.int32)
        A = A.at[r].add(Ab)
        B = B.at[r].add(Bb)
    return A, B


def rmse_binned(x, theta, binned) -> float:
    """RMSE over the nonzeros of a BinnedELL (per-bin SSE, one sqrt)."""
    from repro.core.objective import _sq_err_padded

    sse, nnz = 0.0, 0
    for b, rows in zip(binned.bins, binned.rows):
        if b.m == 0:
            continue
        idx, val, cnt = ell_triplet(b)
        s, k = _sq_err_padded(x[jnp.asarray(np.asarray(rows), jnp.int32)],
                              theta, idx, val, cnt)
        sse += float(s)
        nnz += int(k)
    return (sse / max(nnz, 1)) ** 0.5


def als_train_binned(
    rb, rtb, cfg: AlsConfig,
    test: Optional[tuple] = None,
    callback=None,
) -> tuple[AlsState, list[dict]]:
    """In-core training driver over binned layouts: the same alternating
    schedule as :func:`als_train` with both half-updates dispatched per bin.
    ``rb`` / ``rtb`` are BinnedELLs of R (rows=users) and R^T (rows=items).
    """
    state = als_init(rb.m, rtb.m, cfg)
    history: list[dict] = []
    for it in range(cfg.iters):
        x = update_factor_binned(state.theta, rb, cfg)
        theta = update_factor_binned(x, rtb, cfg)
        state = AlsState(x=x, theta=theta, iteration=state.iteration + 1)
        rec = {"iteration": it + 1}
        if test is not None:
            rec["test_rmse"] = float(
                rmse_padded(state.x, state.theta, test[0], test[1], test[2]))
        rec["train_rmse"] = rmse_binned(state.x, state.theta, rb)
        history.append(rec)
        if callback is not None:
            callback(state, rec)
    return state, history
