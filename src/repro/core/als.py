"""MO-ALS: the single-device ALS driver (paper Alg. 1 / Alg. 2).

The alternating structure is exactly the paper's: update X with Theta fixed
(eq. 2), then update Theta with X fixed (eq. 3), both through the fused
hermitian kernel + batched Cholesky solve.  The q-batching ("solve X in
batches when X is big and Theta fits", paper §3.4 'Limitation of MO-ALS')
is a ``lax.map`` over row blocks so memory stays bounded at m_b f^2.
"""
from __future__ import annotations

import contextlib
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


def _map_row_blocks(solve_block, arrays, batch_rows: int):
    """Row-block scaffolding of the q-batched solves: pad the leading axis
    with zeros up to a multiple of ``batch_rows``, ``lax.map`` over the
    blocks, unpad the result."""
    m = arrays[0].shape[0]
    nb = -(-m // batch_rows)
    pad = nb * batch_rows - m
    blocked = []
    for a in arrays:
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
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


def solve_accumulated(A, B, cnt_total, cfg: AlsConfig) -> jax.Array:
    """Solve a factor from accumulated partial Hermitians held with the
    systems on the lanes (``A [f*f, n]``, ``B [f, n]``, ``cnt_total
    [n]``); returns ``[n, f]``.

    Applies the globally-empty-row guard post-accumulation (a row empty in
    every batch gets A = I, B = 0 -> x = 0, matching ``fused_herm``'s
    ``diag_fallback``) and hands the systems to the lanes solve as
    ``[f, f, n]``, with no transpose, in one program per shape.
    """
    return _solve_accumulated(A, B, cnt_total, mode=cfg.mode,
                              kernels=_kernels())


@functools.partial(jax.jit, static_argnames=("mode", "kernels"))
def _solve_accumulated(A, B, cnt_total, *, mode, kernels=()):
    f, n = B.shape
    empty = (cnt_total <= 0).astype(A.dtype)
    A = A.reshape(f, f, n)
    A = A + jnp.eye(f, dtype=A.dtype)[:, :, None] * empty[None, None, :]
    return kops.batch_solve(A, B, mode=mode, lanes=True).T


def _kernels() -> tuple:
    """The ``kernels.ops`` entry points that the lanes programs trace, as
    they stand when a program is called.  A static argument of each
    program (its cache key, unused in the body): an entry point replaced
    since the last trace, as a planted fault or another implementation
    replaces it, is traced afresh instead of served from JAX's cache, as
    the eager per-bin calls of :func:`update_factor_binned` see it."""
    return (kops.fused_herm, kops.herm_lanes, kops.batch_solve,
            kops.batch_solve_lanes)


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


class LanesBin(NamedTuple):
    """One degree bin of a binned shard (an R^T batch, or an R wave) in
    the accumulate update's layout: slots on the sublanes and rows on the
    lanes, rows padded to whole row blocks with ``cnt = 0`` (zero
    partials)."""

    idx: np.ndarray    # [K, rows_pad] int32, batch-local X rows
    val: np.ndarray    # [K, rows_pad] float32
    cnt: np.ndarray    # [rows_pad] int32
    #: [rows_pad] int32 accumulator column of each row (padding rows past
    #: the last column, dropped), or None where the rows are the columns
    #: ``[start, start + rows_pad)`` in order
    cols: Optional[np.ndarray]
    start: int
    block: int         # rows per block, a multiple of 128
    rows: int          # real rows
    nnz: int           # real ratings

    @property
    def slots(self) -> int:
        """Slots the update works through, padding included."""
        return int(self.idx.size)

    def to_device(self) -> "LanesBin":
        """The same bin with its arrays uploaded."""
        return self._replace(
            idx=jnp.asarray(self.idx), val=jnp.asarray(self.val),
            cnt=jnp.asarray(self.cnt),
            cols=None if self.cols is None else jnp.asarray(self.cols))


class AccLayout(NamedTuple):
    """Where each row's system lies in the lanes accumulators: the bins
    of one shard end to end, each padded to whole row blocks, so that
    that shard's bins add into contiguous runs of columns.  Padding
    columns count no rating and solve to 0."""

    width: int           # accumulator columns
    col_of: np.ndarray   # [n] int32 column of each row


def _block(rows: int, block_rows: int) -> int:
    """Rows per block of a bin of ``rows`` rows: ``block_rows`` (0: the
    whole bin), no wider than the bin, rounded up to 128 lanes."""
    wide = -(-rows // 128) * 128
    return -(-min(block_rows or wide, wide) // 128) * 128


def acc_layout(binned, block_rows: int) -> AccLayout:
    """The :class:`AccLayout` of a ``BinnedELL``'s bins."""
    col_of = np.zeros(binned.m, np.int32)
    width = 0
    for b, rows in zip(binned.bins, binned.rows):
        if b.m == 0:
            continue
        col_of[rows] = width + np.arange(b.m, dtype=np.int32)
        block = _block(b.m, block_rows)
        width += -(-b.m // block) * block
    return AccLayout(width=width, col_of=col_of)


def lanes_bins(binned, block_rows: int, layout: AccLayout) -> list:
    """The nonempty bins of a ``BinnedELL`` (a batch-local R^T shard, or
    a wave-local R slice) as :class:`LanesBin` tuples, in blocks of
    ``block_rows`` rows, with their columns in ``layout``: a run of
    columns where the bin's rows lie there in order (every bin of the
    shard the layout was made from), else one column per row."""
    out = []
    for b, rows in zip(binned.bins, binned.rows):
        if b.m == 0:
            continue
        block = _block(b.m, block_rows)
        pad = -(-b.m // block) * block - b.m
        cols = layout.col_of[rows]
        start = int(cols[0])
        if not (np.all(np.diff(cols) == 1)
                and start + b.m + pad <= layout.width):
            cols = np.concatenate([cols, layout.width + np.arange(
                pad, dtype=np.int32)]).astype(np.int32)
            start = 0
        else:
            cols = None
        out.append(LanesBin(
            idx=np.ascontiguousarray(np.pad(b.idx, ((0, pad), (0, 0))).T,
                                     np.int32),
            val=np.ascontiguousarray(np.pad(b.val, ((0, pad), (0, 0))).T,
                                     np.float32),
            cnt=np.pad(b.cnt, (0, pad)).astype(np.int32), cols=cols,
            start=start, block=block, rows=b.m, nnz=b.nnz))
    return out


def accumulate_bin(A, B, c, x_batch, lb: LanesBin, cfg: AlsConfig):
    """Add one bin's partial Hermitians into the accumulators ``A [f*f,
    w]``, ``B [f, w]``, ``c [w]`` (systems on the lanes), which it
    consumes: one program per bin shape that walks the bin in row blocks
    and adds each block's partials in place, into a run of columns or
    scattered to the bin's ``cols``.  ``lb``'s arrays may be on the host
    or the device.

    ``A`` is flat because XLA scatters along the lanes by laying the
    scattered array out with the columns major: ``[f*f, w]`` so laid out
    is ``[w, f*f]`` in (8, 128) tiles (1.23 GB at 2.4M columns, f = 10),
    where ``[f, f, w]`` would be ``[w, f, f]`` in tiles (19.7 GB).  A run
    of columns is added in place, with no such copy."""
    return _accumulate_bin(
        A, B, c, x_batch, lb.idx, lb.val, lb.cnt, lb.cols, lb.start,
        lam=cfg.lam, block=lb.block, mode=cfg.mode, tm=cfg.tm, tk=cfg.tk,
        f_mult=cfg.f_mult, kernels=_kernels())


@functools.partial(
    jax.jit,
    static_argnames=("lam", "block", "mode", "tm", "tk", "f_mult",
                     "kernels"),
    donate_argnums=(0, 1, 2))
def _accumulate_bin(A, B, c, x_batch, idx, val, cnt, cols, start, *, lam,
                    block, mode, tm, tk, f_mult, kernels=()):
    with jax.named_scope("als.accumulate"):
        def one_block(i, acc):
            A, B, c = acc
            lo = i * block
            ib, vb = (jax.lax.dynamic_slice_in_dim(a, lo, block, axis=1)
                      for a in (idx, val))
            cb = jax.lax.dynamic_slice_in_dim(cnt, lo, block)
            Ab, Bb = kops.herm_lanes(x_batch, ib, vb, cb, lam, mode=mode,
                                     tm=tm, tk=tk, f_mult=f_mult)
            parts = (Ab, Bb, cb.astype(c.dtype))
            if cols is None:      # a run of columns: in place, no scatter
                at = start + lo

                def add_run(a, p):
                    ax = a.ndim - 1
                    run = jax.lax.dynamic_slice_in_dim(a, at, block, ax)
                    return jax.lax.dynamic_update_slice_in_dim(
                        a, run + p, at, ax)

                return tuple(map(add_run, (A, B, c), parts))
            jb = jax.lax.dynamic_slice_in_dim(cols, lo, block)
            kw = dict(unique_indices=True, mode="drop")
            return (A.at[:, jb].add(Ab, **kw), B.at[:, jb].add(Bb, **kw),
                    c.at[jb].add(parts[2], **kw))

        return jax.lax.fori_loop(0, idx.shape[1] // block, one_block,
                                 (A, B, c))


def solve_rows_lanes(fixed, lbs, col_of, width: int, cfg: AlsConfig,
                     each_bin=None) -> jax.Array:
    """Solve the rows of one slice given the ``fixed`` other factor with
    the accumulate half's programs: the slice's :class:`LanesBin` bins
    ``lbs`` (``lanes_bins`` in the slice's own ``acc_layout``, ``width``
    columns) each added by :func:`accumulate_bin` into zero accumulators,
    the systems solved once by :func:`solve_accumulated`, and the
    solutions taken back into row order through the layout's ``col_of``.
    Returns ``[rows, f]``; a row with no rating solves to 0.
    ``each_bin(k, lb)``, where given, is a context manager entered around
    bin ``k``'s update."""
    f = cfg.f
    acc = (jnp.zeros((f * f, width), jnp.float32),
           jnp.zeros((f, width), jnp.float32),
           jnp.zeros((width,), jnp.float32))
    for k, lb in enumerate(lbs):
        with each_bin(k, lb) if each_bin else contextlib.nullcontext():
            acc = accumulate_bin(*acc, fixed, lb, cfg)
    return jnp.take(solve_accumulated(*acc, cfg), col_of, axis=0)
