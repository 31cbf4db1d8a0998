"""Host-resident stores for out-of-core ALS (paper §4.4 "keep R and R^T").

The rating matrix lives in host memory in *both* orientations, pre-cut into
the shapes the wave driver streams:

- ``RatingStore.r`` — R row-major (rows = users), sliced per wave with
  ``sparse.padded.row_slice`` for the solve-X half.
- ``RatingStore.rt_parts`` — R^T column-partitioned into the plan's q
  user-batches (``partition_padded``), one ``[n, K_loc]`` shard per batch
  with batch-local user coordinates, for the accumulate-Theta half (absent
  on a binned p = 1 store, which keeps R^T binned only).

With ``n_bins > 1`` both orientations additionally carry degree-binned
shards (``r_binned``, one ``BinnedELL`` per R^T user-batch in
``rt_binned``): the wave driver streams each wave's rows cut bin-wise
(``x_slice_binned`` / ``theta_batch_binned``) so heavy rows pay a large K
and light rows a small one — cuMF's degree binning applied to the
streaming layout.  With ``p > 1`` (mesh streaming) the theta half instead
carries batch-uniform stacked bins (``rt_stacked``, one
``sparse.padded.BinShardStack`` per bin): bin caps are chosen globally
across all q batches so every batch's bin presents one ``[rows_b, K_b]``
shape the mesh herm stack can shard, while per-batch membership stays
free (the ``items`` scatter map carries it).  ``n_bins="auto"`` consults
the layout autotuner (``repro.core.autotune``) and records the chosen
config in ``RatingStore.tune`` for the ledger.

Factors live in ``FactorStore`` as plain numpy arrays; the driver reads
slices onto device and writes solved slices back, so device memory only ever
holds the resident factor plus the streaming wave buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.sparse.padded import (BinnedELL, BinShardStack, PaddedELL,
                                 bin_padded, bin_rows, csr_from_coo,
                                 pad_csr_fast, pad_rows, partition_padded,
                                 row_slice, stack_binned_parts)

Triplet = Tuple[np.ndarray, np.ndarray, np.ndarray]


def _triplet(ell: PaddedELL) -> Triplet:
    # copy=False: the arrays are already int32/float32 out of pad_csr_fast /
    # partition_padded, and row_slice made the one deliberate copy — a
    # second astype copy per streamed wave would double host traffic
    return (ell.idx.astype(np.int32, copy=False),
            ell.val.astype(np.float32, copy=False),
            ell.cnt.astype(np.int32, copy=False))


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

    ``n_bins > 1`` additionally materializes degree-binned shards of both
    orientations (``bin_padded`` re-bins the uniform layouts in place, no
    COO round trip): the driver then streams each wave bin-wise through
    ``x_slice_binned`` / ``theta_batch_binned``, cutting padded slots from
    ``fill`` x nnz down to the per-bin sum.  With ``p > 1`` the theta half
    is binned batch-uniform instead (``rt_stacked``, globally-chosen caps)
    so the bins stream on a real (data, model) mesh; the solve-X half stays
    on the uniform mesh layout (``x_slice_mesh_triplet``).

    ``n_bins="auto"`` resolves the bin count (and bin ``k_multiple``)
    through ``repro.core.autotune.tune_als_layout`` — argmin of predicted
    streamed bytes over the config ladder, cached in ``tune_cache`` (a
    ``repro.core.autotune.TuneCache`` or path) — and records the decision
    in ``self.tune`` for the driver's ledger run context.
    """

    def __init__(self, r: PaddedELL, q: int, k_multiple: int = 8, p: int = 1,
                 n_bins=1, tune_cache=None):
        self.tune = None
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
        # n_bins > 1: degree-binned shards.  p = 1: both orientations, each
        # R^T shard binned independently (its item degrees are batch-local)
        # straight from the shard's CSR — the uniform [q, n, K_loc] stack
        # (``rt_parts``) pads every item to the top in-batch degree, tens of
        # GB at Netflix scale under item skew, so a binned p = 1 store never
        # builds it.  p > 1 (mesh): the theta half gets batch-uniform
        # stacked bins instead — caps chosen globally over all q batches,
        # per-batch membership carried by the stack's ``items`` map — while
        # the solve-X half keeps the uniform mesh layout (r_model_parts).
        self.rt_parts = None
        self.r_binned = None
        self.rt_binned = None
        self.rt_stacked = None
        if n_bins > 1 and p == 1:
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
        self.rt_parts = partition_padded(rt, q, k_multiple=k_multiple)
        if n_bins > 1:
            self.rt_stacked = stack_binned_parts(
                self.rt_parts, n_bins, k_multiple=k_multiple, p=p)

    @property
    def nnz(self) -> int:
        return self.r.nnz

    @property
    def fill_r(self) -> float:
        """Padding overhead of the row-major orientation (solve-X waves):
        per-bin padded slots over nnz when binned, uniform-K fill otherwise.
        """
        if self.r_binned is not None:
            return self.r_binned.fill
        return self.r.fill

    @property
    def fill_rt(self) -> float:
        """Padding overhead of the q-partitioned R^T shards.  Much worse than
        ``fill_r`` on power-law data: every item row pads to the max in-batch
        item degree — feed this to ``plan_for(fill=...)`` so the eq. (8)
        budget prices what the driver actually streams."""
        if self.rt_binned is not None:
            slots = sum(b.padded_slots for b in self.rt_binned)
            return float(slots) / max(self.nnz, 1)
        if self.rt_stacked is not None:
            slots = sum(st.padded_slots for st in self.rt_stacked)
            return float(slots) / max(self.nnz, 1)
        q, n, K_loc = self.rt_parts.idx.shape
        return float(q * n * K_loc) / max(self.nnz, 1)

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
        the ``plan_for(bin_fills=...)`` pricing input.  Requires a binned
        store.  p = 1: their aggregate equals ``worst_fill``, so the planner
        prices exactly the binned bytes the driver streams.  p > 1
        (stacked): the pairs price the batch-uniform theta-half stacks —
        the binned component of the mesh run (the uniform solve-X side is
        priced by ``fill_r_model``)."""
        if self.rt_stacked is not None:
            return [(int(st.padded_slots), int(st.nnz))
                    for st in self.rt_stacked]
        assert self.r_binned is not None, \
            "RatingStore was built with n_bins=1; pass n_bins to price bins"
        if self.fill_r >= self.fill_rt:
            src = self.r_binned.bins
        else:
            src = [bb for b in self.rt_binned for bb in b.bins]
        return [(int(b.padded_slots), int(b.nnz)) for b in src]

    @property
    def host_nbytes(self) -> int:
        total = int(self.r.idx.nbytes + self.r.val.nbytes + self.r.cnt.nbytes)
        if self.rt_parts is not None:
            total += int(self.rt_parts.idx.nbytes + self.rt_parts.val.nbytes
                         + self.rt_parts.cnt.nbytes)
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

    def x_slice_triplet(self, row_start: int, row_stop: int) -> Triplet:
        """R rows for one solve-X wave slice (global item indices)."""
        return _triplet(row_slice(self.r, row_start, row_stop))

    def x_slice_binned(self, row_start: int, row_stop: int) -> BinnedELL:
        """R rows for one solve-X wave slice, cut bin-wise: a BinnedELL
        whose per-bin rows are slice-local (congruent bin structure across
        waves — every wave carries all bins, possibly empty).  Requires the
        store to have been built with ``n_bins > 1``."""
        assert self.r_binned is not None, \
            "RatingStore was built with n_bins=1; pass n_bins to bin waves"
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

    def theta_batch_triplet(self, j: int) -> Triplet:
        """R^T shard of user-batch ``j`` (batch-local user indices).

        Returns host views into the precomputed shard stack (no per-wave
        copy — the driver only reads them to stage device transfers).
        A binned p = 1 store has no uniform stack; stream
        ``theta_batch_binned`` there."""
        assert self.rt_parts is not None, \
            "binned p = 1 RatingStore keeps R^T binned only"
        assert 0 <= j < self.q, (j, self.q)
        return (self.rt_parts.idx[j].astype(np.int32, copy=False),
                self.rt_parts.val[j].astype(np.float32, copy=False),
                self.rt_parts.cnt[j].astype(np.int32, copy=False))

    def theta_batch_binned(self, j: int) -> BinnedELL:
        """Degree-binned R^T shard of user-batch ``j`` (batch-local user
        indices, item rows grouped by in-batch degree).  Host views — the
        binned shards are precomputed at store build."""
        assert self.rt_binned is not None, \
            "RatingStore was built with n_bins=1; pass n_bins to bin shards"
        assert 0 <= j < self.q, (j, self.q)
        return self.rt_binned[j]

    def theta_wave_stacked(self, batch_indices) -> list:
        """Per-bin stacked theta-half payloads of one mesh wave: for each
        bin, (idx ``[nbatch, rows_b, K_b]``, val, cnt ``[nbatch, rows_b]``,
        items ``[nbatch, rows_b]``) cut to the wave's batches — host views
        of the precomputed batch-uniform stacks (``items`` stays on host;
        it is the scatter map for the per-bin partials, not a transfer).
        Requires the store to have been built with ``p > 1`` and
        ``n_bins > 1``."""
        assert self.rt_stacked is not None, \
            "RatingStore was built without stacked bins; pass p > 1 and " \
            "n_bins > 1 to stream binned waves on a mesh"
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
