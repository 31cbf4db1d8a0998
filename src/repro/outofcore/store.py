"""Host-resident stores for out-of-core ALS (paper §4.4 "keep R and R^T").

The rating matrix lives in host memory in *both* orientations, pre-cut into
the shapes the wave driver streams.  Rows are grouped into ~log-spaced
degree bins, each padded at its own tight K (cuMF's degree binning applied
to the streaming layout); ``n_bins = 1`` is one bin per orientation.

- ``RatingStore.r`` — R row-major (rows = users), kept whole for
  evaluation and pricing.
- p = 1 (one device): ``r_binned`` (R binned, cut per solve-X wave by
  ``x_slice_binned``) and ``rt_binned`` (one ``BinnedELL`` per R^T
  user-batch of the plan's q, batch-local user coordinates, item rows
  binned by in-batch degree, for the accumulate-Theta half).
- p > 1 (mesh streaming): ``r_model_parts`` (R column-partitioned into the
  p theta shards, the solve-X half's mesh layout) and ``rt_stacked`` (one
  ``sparse.padded.BinShardStack`` per bin): bin caps are chosen globally
  across all q batches so every batch's bin presents one ``[rows_b, K_b]``
  shape the mesh herm stack can shard, while per-batch membership stays
  free (the ``items`` scatter map carries it).

``n_bins="auto"`` consults the layout autotuner (``repro.core.autotune``)
and records the chosen config in ``RatingStore.tune`` for the ledger.

Factors live in ``FactorStore`` as plain numpy arrays; the driver reads
slices onto device and writes solved slices back, so device memory only ever
holds the resident factor plus the streaming wave buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.sparse.padded import (BinnedELL, PaddedELL, bin_padded,
                                 bin_rows, csr_from_coo, pad_csr_fast,
                                 pad_rows, partition_padded,
                                 stack_binned_parts)

Triplet = Tuple[np.ndarray, np.ndarray, np.ndarray]


def triplet_nbytes(t: Triplet) -> int:
    return sum(int(a.nbytes) for a in t)


def binned_nbytes(binned: BinnedELL) -> int:
    """Streamed bytes of a BinnedELL's per-bin triplets (idx + val + cnt)."""
    return sum(int(b.idx.nbytes + b.val.nbytes + b.cnt.nbytes)
               for b in binned.bins)


@dataclasses.dataclass
class FactorStore:
    """Host-resident X [m_pad, f] and Theta [n, f] with slice IO."""

    x: np.ndarray
    theta: np.ndarray

    @classmethod
    def from_arrays(cls, x, theta) -> "FactorStore":
        # np.array (not asarray): jnp inputs arrive as read-only views and
        # the driver writes solved slices back in place
        return cls(x=np.array(x, np.float32, order="C"),
                   theta=np.array(theta, np.float32, order="C"))

    def factor(self, side: str) -> np.ndarray:
        assert side in ("x", "theta"), side
        return self.x if side == "x" else self.theta

    def read_slice(self, side: str, start: int, stop: int) -> np.ndarray:
        return np.ascontiguousarray(self.factor(side)[start:stop])

    def write_slice(self, side: str, start: int, stop: int, rows) -> None:
        arr = self.factor(side)
        assert stop - start == len(rows), (start, stop, len(rows))
        arr[start:stop] = np.asarray(rows, arr.dtype)

    # -- model-shard IO (mesh streaming): shard k of p owns the contiguous
    # row range [k*rows/p, (k+1)*rows/p) of a factor.  The ownership rule of
    # the p-sharded theta: only the owning model shard ever writes its range
    # (see repro.outofcore's module doc), so shard reads/writes never race.
    def shard_bounds(self, side: str, k: int, p: int) -> tuple[int, int]:
        rows = self.factor(side).shape[0]
        assert rows % p == 0, f"{side} rows={rows} not divisible by p={p}"
        assert 0 <= k < p, (k, p)
        npp = rows // p
        return k * npp, (k + 1) * npp

    def read_shard(self, side: str, k: int, p: int) -> np.ndarray:
        return self.read_slice(side, *self.shard_bounds(side, k, p))

    def write_shard(self, side: str, k: int, p: int, rows) -> None:
        self.write_slice(side, *self.shard_bounds(side, k, p), rows)

    @property
    def nbytes(self) -> int:
        return int(self.x.nbytes + self.theta.nbytes)


class RatingStore:
    """R in both orientations, pre-cut for a q-batch wave schedule.

    ``q`` is the plan's number of X-row batches.  Rows are padded with empty
    rows to ``m_pad`` (the next multiple of q) so every batch — and therefore
    every wave buffer — has identical shape; padded rows carry cnt = 0 and
    solve to x_u = 0 without touching Theta.

    Both orientations are degree-binned at ``n_bins`` (one bin at
    ``n_bins = 1``): the driver streams each wave bin-wise, so padded slots
    are the per-bin sum instead of every row padded to the top degree.  At
    ``p = 1`` R is re-binned from its padded rows (``bin_padded``) and
    each R^T batch is binned straight from its CSR (``rt_binned``).  With ``p > 1`` the
    theta half is binned batch-uniform instead (``rt_stacked``,
    globally-chosen caps) so the bins stream on a real (data, model) mesh;
    the solve-X half streams the mesh layout (``x_slice_mesh_triplet``).

    ``n_bins="auto"`` resolves the bin count (and bin ``k_multiple``)
    through ``repro.core.autotune.tune_als_layout`` — argmin of predicted
    streamed bytes over the config ladder, cached in ``tune_cache`` (a
    ``repro.core.autotune.TuneCache`` or path) — and records the decision
    in ``self.tune`` for the driver's ledger run context.
    """

    def __init__(self, r: PaddedELL, q: int, k_multiple: int = 8, p: int = 1,
                 n_bins=1, tune_cache=None):
        self.tune = None
        self._lanes = {}          # the lanes bins of both halves, made once
        if n_bins == "auto":
            from repro.core import autotune as _autotune
            res = _autotune.tune_als_layout(
                r, q=q, p=p, k_multiple=k_multiple, cache=tune_cache)
            n_bins = res.config.n_bins
            k_multiple = res.config.k_multiple
            self.tune = res.to_obj()
        assert q >= 1 and p >= 1 and n_bins >= 1
        self.m = r.m                       # true (unpadded) user count
        self.n = r.n_cols                  # item count
        self.q = q
        self.p = p
        self.n_bins = n_bins
        self.m_pad = -(-r.m // q) * q
        self.r = pad_rows(r, self.m_pad)   # rows = users, global item idx
        # R^T with n_cols = m_pad, column-partitioned into the q user-batches:
        # shard j holds the nonzeros of users [j*m_pad/q, (j+1)*m_pad/q) with
        # user coordinates re-based to the batch (eq. 5-7 partitioning, the
        # q axis instead of p).
        items, users, vals = self.r.transpose_coo()
        assert self.n % p == 0, f"n={self.n} not divisible by p={p}"
        # p > 1 (mesh streaming): R also column-partitioned into the p theta
        # shards (shard-local item coordinates) so solve-X waves can be cut
        # straight into the shard_map layout — the real eq. 5-7 p axis.
        self.r_model_parts = (partition_padded(self.r, p,
                                               k_multiple=k_multiple)
                              if p > 1 else None)
        # p = 1: both orientations binned, each R^T batch independently
        # (its item degrees are batch-local) straight from the batch's CSR,
        # so no [q, n, K_loc] R^T stack padded to the top in-batch degree
        # (tens of GB at Netflix scale under item skew) is ever built.
        # p > 1 (mesh): the theta half gets batch-uniform stacked bins —
        # caps chosen globally over all q batches, per-batch membership
        # carried by the stack's ``items`` map — cut from the uniform R^T
        # partition, which is not kept.
        self.r_binned = None
        self.rt_binned = None
        self.rt_stacked = None
        if p == 1:
            self.r_binned = bin_padded(self.r, n_bins, k_multiple=k_multiple)
            mq = self.m_pad // q
            batch = users // mq
            self.rt_binned = tuple(
                bin_rows(*csr_from_coo(items[batch == j],
                                       users[batch == j] - j * mq,
                                       vals[batch == j], self.n),
                         n_cols=mq, n_bins=n_bins, k_multiple=k_multiple)
                for j in range(q))
            return
        ptr, cc, vv = csr_from_coo(items, users, vals, self.n)
        rt = pad_csr_fast(ptr, cc, vv, n_cols=self.m_pad,
                          k_multiple=k_multiple)
        self.rt_stacked = stack_binned_parts(
            partition_padded(rt, q, k_multiple=k_multiple), n_bins,
            k_multiple=k_multiple, p=p)

    @property
    def nnz(self) -> int:
        return self.r.nnz

    @property
    def fill_r(self) -> float:
        """Padding overhead of the row-major orientation (solve-X waves):
        per-bin padded slots over nnz at p = 1, uniform-K fill of R at
        p > 1 (whose waves stream ``fill_r_model``)."""
        if self.r_binned is not None:
            return self.r_binned.fill
        return self.r.fill

    @property
    def fill_rt(self) -> float:
        """Padding overhead of the binned R^T shards: per-bin padded slots
        over nnz — feed this to ``plan_for(fill=...)`` so the eq. (8)
        budget prices what the driver actually streams."""
        bins = self.rt_binned if self.rt_binned is not None \
            else self.rt_stacked
        slots = sum(b.padded_slots for b in bins)
        return float(slots) / max(self.nnz, 1)

    @property
    def fill_r_model(self) -> float:
        """Padding overhead of the p column-partitioned R (mesh solve-X
        waves): every user row pads to its max in-shard degree."""
        if self.r_model_parts is None:
            return self.fill_r
        p, m, K_loc = self.r_model_parts.idx.shape
        return float(p * m * K_loc) / max(self.nnz, 1)

    @property
    def worst_fill(self) -> float:
        return max(self.fill_r, self.fill_rt, self.fill_r_model)

    def fill_breakdown(self) -> dict:
        """Per-component padding fills, keyed like the ledger records them.

        ``worst_fill`` is the max over these — the bound fed to
        ``plan_for(fill=...)`` — but each streamed component pays only its
        own fill, so the ledger records every component separately instead
        of letting one bad orientation smear the others (the old
        ``fill``/``worst_fill`` asymmetry).
        """
        out = {"r": self.fill_r, "rt": self.fill_rt}
        if self.r_model_parts is not None:
            out["r_model"] = self.fill_r_model
        return out

    def bin_fill_pairs(self) -> list:
        """Per-bin ``(padded_slots, nnz)`` of the worst-fill orientation —
        the ``plan_for(bin_fills=...)`` pricing input.  p = 1: their
        aggregate equals ``worst_fill``, so the planner prices exactly the
        binned bytes the driver streams.  p > 1 (stacked): the pairs price
        the batch-uniform theta-half stacks — the binned component of the
        mesh run (the solve-X side is priced by ``fill_r_model``)."""
        if self.rt_stacked is not None:
            return [(int(st.padded_slots), int(st.nnz))
                    for st in self.rt_stacked]
        if self.fill_r >= self.fill_rt:
            src = self.r_binned.bins
        else:
            src = [bb for b in self.rt_binned for bb in b.bins]
        return [(int(b.padded_slots), int(b.nnz)) for b in src]

    @property
    def host_nbytes(self) -> int:
        total = int(self.r.idx.nbytes + self.r.val.nbytes + self.r.cnt.nbytes)
        if self.r_model_parts is not None:
            total += int(self.r_model_parts.idx.nbytes
                         + self.r_model_parts.val.nbytes
                         + self.r_model_parts.cnt.nbytes)
        if self.r_binned is not None:
            total += binned_nbytes(self.r_binned)
            total += sum(binned_nbytes(b) for b in self.rt_binned)
        if self.rt_stacked is not None:
            total += sum(st.nbytes + st.items.nbytes
                         for st in self.rt_stacked)
        return total

    def x_slice_binned(self, row_start: int, row_stop: int) -> BinnedELL:
        """R rows for one solve-X wave slice, cut bin-wise: a BinnedELL
        whose per-bin rows are slice-local (congruent bin structure across
        waves — every wave carries all bins, possibly empty); p = 1."""
        return self.r_binned.row_slice(row_start, row_stop)

    def x_slice_mesh_triplet(self, row_start: int, row_stop: int) -> Triplet:
        """R rows for one solve-X wave slice in the ``shard_ratings`` mesh
        layout: idx/val ``[rows, p*K_loc]`` (shard-local item coordinates,
        the p column blocks laid out contiguously) and cnt ``[rows, p]``.
        Requires the store to have been built with ``p > 1``."""
        assert self.r_model_parts is not None, \
            "RatingStore was built with p=1; pass p to stream on a mesh"
        parts = self.r_model_parts
        p, _, K_loc = parts.idx.shape
        rows = row_stop - row_start
        idx = np.ascontiguousarray(
            np.transpose(parts.idx[:, row_start:row_stop], (1, 0, 2))
        ).reshape(rows, p * K_loc)
        val = np.ascontiguousarray(
            np.transpose(parts.val[:, row_start:row_stop], (1, 0, 2))
        ).reshape(rows, p * K_loc)
        cnt = np.ascontiguousarray(
            np.transpose(parts.cnt[:, row_start:row_stop], (1, 0)))
        return (idx.astype(np.int32, copy=False),
                val.astype(np.float32, copy=False),
                cnt.astype(np.int32, copy=False))

    def theta_batch_binned(self, j: int) -> BinnedELL:
        """Degree-binned R^T shard of user-batch ``j`` (batch-local user
        indices, item rows grouped by in-batch degree); p = 1.  Host views
        — the binned shards are precomputed at store build."""
        assert 0 <= j < self.q, (j, self.q)
        return self.rt_binned[j]

    def theta_acc_layout(self, block_rows: int):
        """Where each item's system lies in the accumulate-Theta half's
        lanes accumulators: ``core.als.acc_layout`` of batch 0's bins in
        row blocks of ``block_rows``, made once per store."""
        key = ("layout", block_rows)
        if key not in self._lanes:
            from repro.core.als import acc_layout
            self._lanes[key] = acc_layout(self.theta_batch_binned(0),
                                          block_rows)
        return self._lanes[key]

    def theta_batch_lanes(self, j: int, block_rows: int) -> list:
        """The nonempty bins of batch ``j``'s R^T shard in the accumulate
        update's layout (``core.als.lanes_bins``: slots on the sublanes,
        items on the lanes, row blocks of ``block_rows``), their columns
        in :meth:`theta_acc_layout`.  Host arrays, made once per store:
        the ratings never change between iterations."""
        key = (j, block_rows)
        if key not in self._lanes:
            from repro.core.als import lanes_bins
            self._lanes[key] = lanes_bins(self.theta_batch_binned(j),
                                          block_rows,
                                          self.theta_acc_layout(block_rows))
        return self._lanes[key]

    def _x_wave(self, row_start: int, row_stop: int, block_rows: int):
        key = ("x", row_start, row_stop, block_rows)
        if key not in self._lanes:
            from repro.core.als import acc_layout, lanes_bins
            bsl = self.x_slice_binned(row_start, row_stop)
            layout = acc_layout(bsl, block_rows)
            self._lanes[key] = (layout, lanes_bins(bsl, block_rows, layout))
        return self._lanes[key]

    def x_wave_layout(self, row_start: int, row_stop: int,
                      block_rows: int):
        """Where each row of the solve-X wave ``[row_start, row_stop)``
        lies in the wave's lanes accumulators: ``core.als.acc_layout`` of
        the wave's bin-wise cut (``x_slice_binned``) in row blocks of
        ``block_rows``, made once per store."""
        return self._x_wave(row_start, row_stop, block_rows)[0]

    def x_wave_lanes(self, row_start: int, row_stop: int,
                     block_rows: int) -> list:
        """The nonempty bins of the solve-X wave ``[row_start, row_stop)``
        in the accumulate update's layout (``core.als.lanes_bins``: slots
        on the sublanes, the wave's rows on the lanes, every bin a run of
        columns of :meth:`x_wave_layout`).  Host arrays, made once per
        store: the ratings never change between iterations."""
        return self._x_wave(row_start, row_stop, block_rows)[1]

    def theta_wave_stacked(self, batch_indices) -> list:
        """Per-bin stacked theta-half payloads of one mesh wave: for each
        bin, (idx ``[nbatch, rows_b, K_b]``, val, cnt ``[nbatch, rows_b]``,
        items ``[nbatch, rows_b]``) cut to the wave's batches — host views
        of the precomputed batch-uniform stacks (``items`` stays on host;
        it is the scatter map for the per-bin partials, not a transfer).
        Requires the store to have been built with ``p > 1``."""
        assert self.rt_stacked is not None, \
            "RatingStore was built with p=1; pass p to stream on a mesh"
        js = np.asarray(list(batch_indices), dtype=np.int64)
        assert js.size and js.min() >= 0 and js.max() < self.q, (js, self.q)
        return [(st.idx[js], st.val[js], st.cnt[js], st.items[js])
                for st in self.rt_stacked]


class TileStore:
    """Host-resident g x g ``BlockGrid`` tiles for the streaming SGD driver.

    The grid's stacked arrays already live in host memory in exactly the
    shapes the tile waves stream — one ``[mb, K]`` triplet per (user-block,
    item-block) tile — so the store is a thin per-tile view layer over the
    grid, the SGD counterpart of ``RatingStore``'s wave slicing.  Factor
    blocks live in a ``FactorStore`` whose X is ``[g*mb, f]`` and Theta is
    ``[g*nb, f]``; block ``i`` is the contiguous slice ``[i*mb, (i+1)*mb)``.
    """

    def __init__(self, grid):
        self.grid = grid

    @property
    def g(self) -> int:
        return self.grid.g

    @property
    def mb(self) -> int:
        return self.grid.mb

    @property
    def nb(self) -> int:
        return self.grid.nb

    @property
    def K(self) -> int:
        return self.grid.K

    @property
    def m(self) -> int:
        return self.grid.m

    @property
    def n(self) -> int:
        return self.grid.n

    @property
    def nnz(self) -> int:
        return self.grid.nnz

    @property
    def host_nbytes(self) -> int:
        return int(self.grid.idx.nbytes + self.grid.val.nbytes
                   + self.grid.cnt.nbytes)

    def tile_k(self, i: int, j: int) -> int:
        return self.grid.tile_k(i, j)

    def tile_triplet(self, i: int, j: int) -> Triplet:
        """Tile (i, j)'s (idx, val, cnt) as host views (no copy — the
        driver only reads them to stage device transfers).  On a per-tile-K
        grid the slot axis is sliced to the tile's own K: the trailing
        columns are all-padding, so the cut is exact and the wave streams
        only the slots its kernel shape dispatches."""
        assert 0 <= i < self.g and 0 <= j < self.g, (i, j, self.g)
        k = self.grid.tile_k(i, j)
        return (self.grid.idx[i, j, :, :k].astype(np.int32, copy=False),
                self.grid.val[i, j, :, :k].astype(np.float32, copy=False),
                self.grid.cnt[i, j].astype(np.int32, copy=False))
