"""Partition planner — paper eq. (8), generalized to a TPU mesh.

cuMF chooses p (Theta column shards == data parallelism) and q (X row
batches == model parallelism) so that a single device holds::

    m f / q  +  n f / p  +  |R^(ij)|  +  (m/q) f^2  +  (m/q) f  +  eps  <  C

with the best practices of §4.3:
  1. if p = 1 fits, stay on one device (SU-ALS degenerates to MO-ALS),
  2. stop growing q once p = 1 fits,
  3. otherwise start from p with n f / p ~ C/2 and pick the smallest q.

On a mesh, p maps to the "model" axis (and "pod" x "model" when multi-pod)
and q to the "data" axis; q larger than the data axis runs in waves
(elasticity, §4.4) — `waves` reports how many.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

GiB = 1 << 30


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    p: int                  # column shards of Theta (data parallelism)
    q: int                  # row shards/batches of X (model parallelism)
    bytes_per_device: int
    terms: dict
    fits: bool
    waves: int = 1          # q-batches executed per device wave (elasticity)

    def describe(self) -> str:
        t = ", ".join(f"{k}={v / GiB:.3f}GiB" for k, v in self.terms.items())
        return (f"p={self.p} q={self.q} waves={self.waves} "
                f"total={self.bytes_per_device / GiB:.3f}GiB fits={self.fits} [{t}]")


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def lanes_bytes(shape, dtype_bytes: int = 4) -> int:
    """Device bytes of an array of ``shape`` in the TPU's (8, 128) tiles
    of its last two axes (a 1-D array: whole 128-element rows)."""
    if len(shape) == 1:
        return _round_up(shape[0], 128) * dtype_bytes
    *lead, r, c = shape
    out = _round_up(r, 8) * _round_up(c, 128) * dtype_bytes
    for d in lead:
        out *= d
    return out


def streaming_acc_bytes(n: int, f: int, dtype_bytes: int = 4) -> int:
    """Resident accumulate-Theta state of a streaming run as it is laid
    out: ``A [f*f, n]``, ``B [f, n]``, ``c [n]``, the systems on the lanes
    and every array in whole tiles, plus the copy of ``A`` with the items
    major (``[n, f*f]`` in tiles) that a per-bin scatter-add works in, as
    the batches after the first do (GLOBAL size — the planner divides by p, each model shard owning only
    its theta rows' systems)."""
    return sum(lanes_bytes(s, dtype_bytes)
               for s in ((f * f, n), (f, n), (n,), (n, f * f)))


def _bytes_per_device(m, n, nnz, f, p, q, fill=1.5, dtype_bytes=4, eps=512 << 20,
                      buffers=1, acc_bytes=0, block_rows=0):
    terms = {
        "X_batch": m * f * dtype_bytes // q,
        "Theta_shard": n * f * dtype_bytes // p,
        # idx+val, padded; ``buffers`` > 1 models the §4.4 preload buffers an
        # out-of-core run keeps resident (current shard + prefetched next ones)
        "R_shard": int(2 * nnz * dtype_bytes * fill) // (p * q) * buffers,
        "A_batch": m * f * f * dtype_bytes // q,
        "B_batch": m * f * dtype_bytes // q,
        "eps": eps,
    }
    if block_rows:
        # Hermitians formed one row block at a time, in whole tiles: the
        # X half's [rows, f, f] or the Theta half's [f, f, rows], the larger
        rows = min(block_rows, -(-m // q))
        terms["A_batch"] = max(lanes_bytes((rows, f, f), dtype_bytes),
                               lanes_bytes((f, f, rows), dtype_bytes))
        terms["B_batch"] = max(lanes_bytes((rows, f), dtype_bytes),
                               lanes_bytes((f, rows), dtype_bytes))
    if acc_bytes:
        # streaming accumulate-Theta residents, p-sharded like Theta: each
        # model shard holds only its own theta rows' accumulated systems
        terms["Herm_acc"] = acc_bytes // p
    return sum(terms.values()), terms


def plan_partitions(
    m: int, n: int, nnz: int, f: int,
    hbm_bytes: int = 16 * GiB,
    n_model: int = 16,          # devices on the "model" axis (p candidates)
    n_data: int = 16,           # devices on the "data" axis (q waves base)
    fill: float = 1.5,
    dtype_bytes: int = 4,
    eps: int = 512 << 20,
) -> PartitionPlan:
    """Choose (p, q) per paper §4.3 for the given problem and mesh."""
    # Best practice 1/2: smallest q with p=1, if Theta fits a device.
    def fits(p, q):
        total, terms = _bytes_per_device(m, n, nnz, f, p, q, fill, dtype_bytes, eps)
        return total < hbm_bytes, total, terms

    if n * f * dtype_bytes + eps < hbm_bytes // 2:
        p = 1
        q = 1
        while True:
            ok, total, terms = fits(p, q)
            if ok:
                waves = -(-q // n_data)
                return PartitionPlan(p, q, total, terms, True, waves)
            q *= 2
            if q > 1 << 24:
                break

    # Best practice 3: p so that Theta shard ~ C/2, then smallest q.
    p = 1
    while n * f * dtype_bytes / p > hbm_bytes / 2 and p < n_model:
        p *= 2
    p = min(p, n_model)
    q = 1
    while q <= 1 << 24:
        ok, total, terms = fits(p, q)
        if ok:
            waves = -(-q // n_data)
            return PartitionPlan(p, q, total, terms, True, waves)
        q *= 2
    total, terms = _bytes_per_device(m, n, nnz, f, p, q, fill, dtype_bytes, eps)
    return PartitionPlan(p, q, total, terms, False, -(-q // n_data))


def plan_for(
    m: int, n: int, nnz: int, f: int,
    p: int, q: int,
    *,
    n_data: int = 16,
    hbm_bytes: int = 16 * GiB,
    fill: float = 1.5,
    dtype_bytes: int = 4,
    eps: int = 512 << 20,
    buffers: int = 1,
    acc_bytes: int = 0,
    bin_fills: Optional[Sequence[Tuple[int, int]]] = None,
    auto: bool = False,
    degrees=None,
    tune_cache=None,
    k_multiple: int = 8,
    block_rows: int = 0,
) -> PartitionPlan:
    """Cost a *given* (p, q) choice — the forced-plan entry point.

    ``plan_partitions`` searches for (p, q); this prices one the caller picked
    (tests force ``waves >= 2`` plans on in-core-sized data; the out-of-core
    example caps the simulated device).  ``buffers`` counts how many R-shard
    buffers stay device-resident at once: 1 is the in-core bound of eq. (8),
    an out-of-core run double-buffering ``depth`` shards ahead needs
    ``depth + 1`` (§4.4 preload).  ``acc_bytes`` prices the streaming
    accumulate-Theta residents (``streaming_acc_bytes(n, f)``) as their own
    p-sharded term — each model shard owns 1/p of the accumulated systems —
    instead of overloading the flat ``eps`` headroom.  ``block_rows`` (the
    solver's ``batch_rows``) prices the Hermitian working set as one row
    block of systems, as both halves form them, rather than a whole
    batch of them.

    ``bin_fills`` prices a degree-binned layout: per-bin ``(padded_slots,
    nnz)`` pairs (e.g. ``RatingStore.bin_fill_pairs()``) whose aggregate
    ``sum(slots) / sum(nnz)`` — the fill a binned store actually streams —
    overrides the scalar ``fill``.  On power-law data this is a multi-x
    reduction of the R_shard term, which is exactly where binning buys its
    capacity headroom.

    ``auto=True`` derives ``bin_fills`` itself: ``degrees`` (the per-row
    nnz counts) is swept through ``repro.core.autotune.tune_plan_fills`` —
    argmin of padded slots over the (n_bins, k_multiple) ladder, cached in
    ``tune_cache`` — and the winning rung's per-bin pairs price R_shard.
    """
    if auto:
        from repro.core import autotune as _autotune
        assert degrees is not None, \
            "plan_for(auto=True) needs degrees= (per-row nnz counts)"
        res = _autotune.tune_plan_fills(
            m, n, nnz, f, p, q, degrees=degrees, k_multiple=k_multiple,
            cache=tune_cache)
        want = res.config.to_obj()
        for cand in res.candidates:
            if cand["config"] == want:
                bin_fills = cand["bin_fills"]
                break
    if bin_fills:
        slots = sum(int(s) for s, _ in bin_fills)
        true_nnz = sum(int(z) for _, z in bin_fills)
        fill = slots / max(true_nnz, 1)
    total, terms = _bytes_per_device(
        m, n, nnz, f, p, q, fill, dtype_bytes, eps, buffers, acc_bytes,
        block_rows)
    return PartitionPlan(p, q, total, terms, total < hbm_bytes, -(-q // n_data))


# ---------------------------------------------------------------------------
# Schedule export: the planner's (q, waves) turned into explicit row ranges.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QBatch:
    """One of the q X-row batches (the §4.4 streaming unit)."""

    index: int       # global batch number in [0, q)
    row_start: int   # first X row of the batch (inclusive)
    row_stop: int    # one past the last X row (exclusive)

    @property
    def rows(self) -> int:
        return self.row_stop - self.row_start


def batch_ranges(m: int, q: int) -> Tuple[QBatch, ...]:
    """Split ``m`` rows into ``q`` balanced contiguous batches.

    Sizes differ by at most one row and every row lands in exactly one batch
    (the invariant the wave-coverage property test pins down).
    """
    assert m >= 0 and q >= 1, (m, q)
    base, rem = divmod(m, q)
    out = []
    start = 0
    for b in range(q):
        size = base + (1 if b < rem else 0)
        out.append(QBatch(index=b, row_start=start, row_stop=start + size))
        start += size
    assert start == m
    return tuple(out)


def export_schedule(
    plan: PartitionPlan, m: int, n_data: Optional[int] = None,
) -> Tuple[Tuple[QBatch, ...], ...]:
    """Explicit per-iteration wave schedule for a plan's q batches.

    Returns one tuple of QBatches per wave: wave ``w`` streams batches
    ``[w * n_data, min((w+1) * n_data, q))`` through the data axis — each
    device on the axis takes one batch per wave, so ``len(waves) * n_data >=
    q`` always, and ``len(waves) == plan.waves`` when ``n_data`` matches the
    axis size the plan was computed for (the default reconstructs it from
    ``plan.waves``).
    """
    q = plan.q
    if n_data is None:
        n_data = -(-q // plan.waves)
    assert n_data >= 1
    batches = batch_ranges(m, q)
    n_waves = -(-q // n_data)
    return tuple(
        batches[w * n_data:(w + 1) * n_data] for w in range(n_waves))
