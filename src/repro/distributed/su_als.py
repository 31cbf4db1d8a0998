"""SU-ALS (paper Alg. 3) under shard_map: data + model parallel ALS.

Axis mapping (paper -> mesh):

- cuMF's **p** (Theta column shards; each GPU computes *partial* A_u, B_u
  from only its local theta_v — eq. 5-7)  ==  the ``"model"`` mesh axis, and
  jointly ``("pod", "model")`` on the multi-pod mesh.
- cuMF's **q** (X row partitions, solved independently) == the ``"data"``
  mesh axis; q beyond the axis size runs in waves (out-of-core batching).

One update-X step inside shard_map (update-Theta is symmetric):

  1. local fused hermitian:  A_i, B_i from local columns        (Alg.3 L11)
  2. parallel reduction:     psum_scatter over the column axes  (L13-16,
     Fig. 5a == one-phase; model-then-pod == two-phase Fig. 5b)
  3. local batch solve on the owned row slice                   (L17)
  4. all_gather the solved slices back                          (L19)

The synchronization barrier of Alg. 3 line 12 is implicit in the dataflow.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.kernels import ops as kops


def _col_axes(mesh: Mesh) -> tuple[tuple[str, ...], object]:
    """(col_axes, col_dim spec entry) — cuMF's p axes, fast -> slow."""
    col_axes = tuple(a for a in ("model", "pod") if a in mesh.axis_names)
    col_dim = col_axes[::-1] if len(col_axes) > 1 else col_axes[0]
    return col_axes, col_dim


def su_als_update(
    theta_loc: jax.Array,   # [n_loc, f]   local Theta column shard (rows here)
    idx_loc: jax.Array,     # [m_loc, K]   shard-local padded indices
    val_loc: jax.Array,     # [m_loc, K]
    cnt_loc: jax.Array,     # [m_loc]      *local* nnz counts
    lam: float,
    *,
    col_axes: tuple[str, ...] = ("model",),   # cuMF p axes, fast -> slow
    scheme: str = "two_phase",                # "one_phase" | "two_phase"
    mode: str = "ref",
    tm: int = 8, tk: int = 128, f_mult: int = 128,
    row_block: int = 0,
) -> jax.Array:
    """Runs inside shard_map.  Returns x_loc [m_loc, f] (replicated over col_axes).

    ``row_block`` > 0 processes rows in blocks of that size (cuMF's m_b
    batching, Table 3): bounds the live Hermitian buffer at
    row_block * f^2 floats and pipelines reduction with compute."""
    if row_block and row_block < idx_loc.shape[0]:
        m_loc = idx_loc.shape[0]
        assert m_loc % row_block == 0, (m_loc, row_block)
        nb = m_loc // row_block
        blk = lambda a: a.reshape((nb, row_block) + a.shape[1:])

        def one(args):
            i, v, c = args
            return su_als_update(
                theta_loc, i, v, c, lam, col_axes=col_axes, scheme=scheme,
                mode=mode, tm=tm, tk=tk, f_mult=f_mult, row_block=0)

        out = lax.map(one, (blk(idx_loc), blk(val_loc), blk(cnt_loc)))
        return out.reshape(m_loc, -1)
    # (1) local partial Hermitians — eq. (5)-(7)
    A, B = kops.fused_herm(
        theta_loc, idx_loc, val_loc, cnt_loc, lam,
        mode=mode, tm=tm, tk=tk, f_mult=f_mult, diag_fallback=False)
    cnt_f = cnt_loc.astype(jnp.float32)

    # (2) parallel reduction of partial results — paper §4.2
    if scheme == "one_phase" or len(col_axes) == 1:
        # Fig. 5a: single reduce-scatter over the (joint) column axis.
        axes = col_axes if len(col_axes) > 1 else col_axes[0]
        A_r = lax.psum_scatter(A, axes, scatter_dimension=0, tiled=True)
        B_r = lax.psum_scatter(B, axes, scatter_dimension=0, tiled=True)
        c_r = lax.psum_scatter(cnt_f, axes, scatter_dimension=0, tiled=True)
    else:
        # Fig. 5b: two-phase, topology-aware — scatter over the fast
        # intra-pod axis first; only 1/p_fast-sized slices cross the slow link.
        fast, slow = col_axes[0], col_axes[1]
        A_r = lax.psum_scatter(A, fast, scatter_dimension=0, tiled=True)
        B_r = lax.psum_scatter(B, fast, scatter_dimension=0, tiled=True)
        c_r = lax.psum_scatter(cnt_f, fast, scatter_dimension=0, tiled=True)
        A_r = lax.psum_scatter(A_r, slow, scatter_dimension=0, tiled=True)
        B_r = lax.psum_scatter(B_r, slow, scatter_dimension=0, tiled=True)
        c_r = lax.psum_scatter(c_r, slow, scatter_dimension=0, tiled=True)

    # singular guard for globally-empty rows (x_u = 0)
    f = A_r.shape[-1]
    empty = (c_r <= 0).astype(A_r.dtype)
    A_r = A_r + empty[:, None, None] * jnp.eye(f, dtype=A_r.dtype)

    # (3) solve owned slice — Alg. 3 line 17, p-way parallel batch_solve
    x_slice = kops.batch_solve(A_r, B_r, mode=mode)

    # (4) collect solved slices — Alg. 3 line 19
    if scheme == "one_phase" or len(col_axes) == 1:
        axes = col_axes if len(col_axes) > 1 else col_axes[0]
        x_loc = lax.all_gather(x_slice, axes, axis=0, tiled=True)
    else:
        x_loc = lax.all_gather(x_slice, col_axes[1], axis=0, tiled=True)
        x_loc = lax.all_gather(x_loc, col_axes[0], axis=0, tiled=True)
    return x_loc


def make_su_als_fns(
    mesh: Mesh,
    lam: float,
    *,
    scheme: str = "two_phase",
    mode: str = "ref",
    tm: int = 8, tk: int = 128, f_mult: int = 128,
    row_block: int = 0,
):
    """Build (update_x, update_theta, iteration) jitted on ``mesh``.

    Expected global layouts (see repro.sparse.partition_padded):
      R rows grid:   idx/val [m, P*K] rows over "data", col blocks over col axes
                     cnt    [m, P]
      R^T rows grid: idxT/valT [n, P*KT] rows over "data", cols over col axes
      theta [n, f]: rows over col axes (the fixed side of update-X)
      x     [m, f]: rows over col axes (the fixed side of update-Theta)
    Returned factors are row-sharded over "data".
    """
    col_axes = tuple(a for a in ("model", "pod") if a in mesh.axis_names)
    # fast axis first (intra-pod "model"), then slow ("pod")
    update = functools.partial(
        su_als_update, lam=lam, col_axes=col_axes, scheme=scheme,
        mode=mode, tm=tm, tk=tk, f_mult=f_mult, row_block=row_block)

    cols_spec = col_axes if len(col_axes) == 1 else (col_axes[::-1],)
    # column-block dim of R shards over (pod, model): pod-major ordering
    col_dim = col_axes[::-1] if len(col_axes) > 1 else col_axes[0]

    in_specs = (
        P(col_dim, None),        # theta_loc: rows sharded over column axes
        P("data", col_dim),      # idx
        P("data", col_dim),      # val
        P("data", col_dim),      # cnt [m, P]
    )
    out_spec = P("data", None)

    def _wrap(theta, idx, val, cnt):
        def inner(t, i, v, c):
            return update(t, i, v, c[:, 0])
        return compat.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
            check_vma=False,
        )(theta, idx, val, cnt)

    data_rows = NamedSharding(mesh, P("data", None))
    col_rows = NamedSharding(mesh, P(col_dim, None))

    @functools.partial(jax.jit, out_shardings=data_rows)
    def update_x(theta, idx, val, cnt):
        return _wrap(theta, idx, val, cnt)

    @functools.partial(jax.jit, out_shardings=data_rows)
    def update_theta(x, idxT, valT, cntT):
        return _wrap(x, idxT, valT, cntT)

    @jax.jit
    def iteration(x, theta, r, rt):
        """One full ALS iteration; factors come in and leave row-sharded
        over "data"; the reshard to column-axis rows between half-steps is
        an explicit constraint (XLA inserts the all-to-all)."""
        theta_c = lax.with_sharding_constraint(theta, col_rows)
        x_new = _wrap(theta_c, *r)
        x_c = lax.with_sharding_constraint(x_new, col_rows)
        theta_new = _wrap(x_c, *rt)
        x_out = lax.with_sharding_constraint(x_new, data_rows)
        t_out = lax.with_sharding_constraint(theta_new, data_rows)
        return x_out, t_out

    return update_x, update_theta, iteration


def make_wave_update_fn(
    mesh: Mesh,
    lam: float,
    *,
    scheme: str = "two_phase",
    mode: str = "ref",
    tm: int = 8, tk: int = 128, f_mult: int = 128,
    row_block: int = 0,
):
    """Per-slice update entry point for the out-of-core wave driver.

    Bridges one host-resident wave slice onto the mesh: the slice's rating
    arrays (in the ``shard_ratings`` layout — idx/val ``[m_slice, P*K]``,
    cnt ``[m_slice, P]``) are placed row-sharded over ``"data"`` so each
    device on the axis takes one q-batch of the wave, the fixed factor is
    placed over the column axes, the shard-mapped SU-ALS update runs, and
    the solved rows come back to host for the driver to write into its
    factor store.  ``m_slice`` must divide the "data" axis size.
    """
    update_x, _, _ = make_su_als_fns(
        mesh, lam, scheme=scheme, mode=mode,
        tm=tm, tk=tk, f_mult=f_mult, row_block=row_block)
    col_axes, col_dim = _col_axes(mesh)
    rows_sh = NamedSharding(mesh, P("data", col_dim))
    fixed_sh = NamedSharding(mesh, P(col_dim, None))

    def update_slice(fixed, idx, val, cnt):
        fixed_d = jax.device_put(fixed, fixed_sh)
        idx_d = jax.device_put(idx, rows_sh)
        val_d = jax.device_put(val, rows_sh)
        cnt_d = jax.device_put(cnt, rows_sh)
        return np.asarray(update_x(fixed_d, idx_d, val_d, cnt_d))

    return update_slice


def make_wave_herm_fn(
    mesh: Mesh,
    lam: float,
    *,
    mode: str = "ref",
    tm: int = 8, tk: int = 128, f_mult: int = 128,
):
    """Accumulate-Theta mesh entry point for the out-of-core wave driver.

    One call computes the partial Hermitians of one wave on the real mesh:
    device (d, k) holds data-shard ``d``'s fresh X slice plus *only* model
    shard ``k``'s rows of that batch's R^T shard, and produces the partial
    (A, B) for its owned theta rows (eq. 5-7 with the weighted-lambda
    diagonal, which telescopes over data shards).  Crucially there is **no
    cross-device reduction inside the program**: the per-data-shard partials
    come back to the host with the "data" axis intact, where the driver
    accumulates them across waves and combines them once per half-iteration
    through ``distributed.reduce.topology_reduce`` — the paper's explicitly
    host-scheduled Fig. 5 reduction, rather than an opaque psum.

    Expected stacks (host or device):
      x_stack [n_data, rows, f]   fresh X slices, one per data shard
      idxT/valT [n_data, n, K]    R^T shards, theta rows over the col axes
      cntT   [n_data, n]          per-shard local nnz counts
    Returns host (A [n_data, n, f, f], B [n_data, n, f]) float32 partials.
    """
    _, col_dim = _col_axes(mesh)
    in_specs = (
        P("data", None, None),       # x_stack: replicated over col axes
        P("data", col_dim, None),    # idxT: theta rows over col axes
        P("data", col_dim, None),    # valT
        P("data", col_dim),          # cntT
    )
    out_specs = (P("data", col_dim, None, None),   # A partials, un-reduced
                 P("data", col_dim, None))         # B partials

    def inner(x_loc, i_loc, v_loc, c_loc):
        # diag_fallback=False: a locally-empty theta row may be nonempty
        # globally — the guard is applied after the topology reduce
        A, B = kops.fused_herm(
            x_loc[0], i_loc[0], v_loc[0], c_loc[0], lam,
            mode=mode, tm=tm, tk=tk, f_mult=f_mult, diag_fallback=False)
        return A[None], B[None]

    mapped = jax.jit(compat.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))
    x_sh = NamedSharding(mesh, P("data", None, None))
    rt_sh = NamedSharding(mesh, P("data", col_dim, None))
    cnt_sh = NamedSharding(mesh, P("data", col_dim))

    def herm_stack(x_stack, idxT, valT, cntT):
        A, B = mapped(jax.device_put(x_stack, x_sh),
                      jax.device_put(idxT, rt_sh),
                      jax.device_put(valT, rt_sh),
                      jax.device_put(cntT, cnt_sh))
        return np.asarray(A), np.asarray(B)

    return herm_stack


def shard_ratings(ell_parts, mesh: Mesh):
    """partition_padded output ([P, m, K] arrays) -> device arrays laid out
    for make_su_als_fns: idx/val [m, P*K] and cnt [m, P] with the right
    NamedSharding placements."""
    col_axes, col_dim = _col_axes(mesh)
    Pn, m, K = ell_parts.idx.shape
    idx = np.transpose(ell_parts.idx, (1, 0, 2)).reshape(m, Pn * K)
    val = np.transpose(ell_parts.val, (1, 0, 2)).reshape(m, Pn * K)
    cnt = np.transpose(ell_parts.cnt, (1, 0)).reshape(m, Pn)
    sh = NamedSharding(mesh, P("data", col_dim))
    return (jax.device_put(idx, sh), jax.device_put(val, sh),
            jax.device_put(cnt, sh))
