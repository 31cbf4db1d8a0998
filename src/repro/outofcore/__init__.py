"""Out-of-core wave scheduling: factorize R larger than aggregate device HBM.

Implements cuMF's §4.3/§4.4 out-of-core batching as a first-class subsystem.
Paper vocabulary -> implementation map:

- **p** (Theta column shards, data parallelism): the planner's
  ``PartitionPlan.p``.  With ``p = 1`` the streaming driver executes one
  model-shard's view on a single simulated device; with ``mesh=`` (a real
  ``(data, model)`` mesh) and a ``RatingStore(p=...)``, every wave runs
  shard-mapped — solve-X through ``distributed.su_als.make_wave_update_fn``,
  accumulate-Theta through ``make_wave_herm_fn`` with the per-data-shard
  partials combined by ``distributed.reduce.topology_reduce``.

  **p-sharded theta ownership rules** (mesh streaming):

  1. ``FactorStore.theta`` stays one host array, but model shard ``k``
     *owns* the contiguous row range ``[k*n/p, (k+1)*n/p)`` —
     ``read_shard``/``write_shard`` are the only sanctioned shard IO.
  2. A device only ever materializes its own ``[n/p, f]`` theta shard
     (plus the wave's R / R^T slice for its coordinates); nothing outside
     the final all-gather of solved X rows replicates theta.
  3. Only the owning shard writes its theta rows, and only after the
     topology reduce of the half's full partial sums — so shard writes
     never race and never see partially-reduced systems.
- **q** (X row batches, model parallelism): ``PartitionPlan.q``, made
  explicit as ``core.partition.QBatch`` row ranges.  ``store.RatingStore``
  keeps R row-major for the solve-X half and R^T column-partitioned into the
  same q user-batches for the accumulate-Theta half, both degree-binned —
  the paper's "keep R and R^T in host memory".
- **waves** (q batches beyond the device axis, §4.4 elasticity):
  ``schedule.IterationSchedule.waves`` — each wave streams up to ``n_data``
  consecutive q-batches through the (simulated) devices; both iteration
  halves walk the same wave list.
- **preload** (§4.4 "hide load time behind compute"): the driver double-
  buffers the next wave's shards host->device through
  ``data.prefetch.Prefetcher`` while the current wave computes;
  ``core.partition.plan_for(buffers=depth + 2)`` prices the extra resident
  shard buffers in the eq. (8) budget (depth queued + one held by the
  loader thread + one being consumed).
- **checkpoint/restart** (§4.4 fault tolerance): every completed wave
  commits factors (+ Hermitian accumulators mid-half) through
  ``checkpoint.CheckpointManager``; a killed run resumes mid-iteration.
- **degree-binned layout** (§4.1 binning): ``RatingStore(n_bins=...)``
  keeps R and each R^T shard as ``sparse.padded.BinnedELL`` — rows grouped
  into ~log-spaced degree bins, each padded at its own tight K;
  ``n_bins = 1`` is one bin per orientation.  Layout ownership rules:

  1. The bins hold the same nonzeros as R (``RatingStore.r``, kept whole
     for evaluation and pricing); masked padding slots are exact zeros,
     so runs at any ``n_bins`` agree to float roundoff.
  2. Factors and checkpoints always live in ORIGINAL row order — the bin
     permutation (``perm``/``inv_perm``) never escapes the store; the
     ALS halves take per-bin results back through each layout's columns
     (checkpoints are layout-agnostic: a run resumes a checkpoint written
     at another ``n_bins``).
  3. The wave scheduler relies on stable grouping: each bin's original-row
     list ascends, so any wave range ``[start, stop)`` cuts every bin in
     one contiguous span (``bin_spans``) and per-wave byte/slot
     predictions stay exact.
  4. Planner pricing goes through ``RatingStore.bin_fill_pairs()`` ->
     ``plan_for(bin_fills=...)``; the ledger's ``fill_waste_ratio`` and
     per-component ``fill_bound/*`` records measure the binned layout.
  5. Mesh (``p > 1``): the theta half streams batch-uniform stacked bins
     (``RatingStore.rt_stacked``, bin caps chosen globally over all q
     batches so every batch's bin presents one shape the mesh herm stack
     can shard; per-batch membership varies and rides in each stack's
     ``items`` scatter map).  The solve-X half streams R cut into the p
     theta shards (``r_model_parts``).  Stack padding rows carry cnt = 0
     and contribute exact-zero partials, so the f64 accumulators — and
     therefore checkpoints and the topology reduce — hold the same
     systems at any ``n_bins``.
  6. ``n_bins="auto"`` (and the SGD side's ``per_tile_k="auto"``) route
     through ``repro.core.autotune``: argmin of predicted streamed bytes
     over the config ladder, cached per (shape, skew, topology, backend);
     the chosen config and cache hit/miss are recorded in the ledger run
     context (``autotune``).

  The SGD side gets the same treatment at tile granularity:
  ``sgd.blocking.block_coo(per_tile_k=True, degree_sort=True)`` records a
  ladder-quantized ``tile_K`` per tile (plus an optional descending-degree
  user placement), and the streaming SGD driver dispatches each wave's
  tiles in same-K groups sliced to their own K.

The subsystem is **solver-generic**: schedules are built from abstract wave
work items (``schedule.WaveItem``) and the drivers share one streaming
runtime (``runtime`` — meter, telemetry, per-wave checkpointer).  Beyond
the ALS halves above, ``run_streaming_sgd`` streams a CuMF_SGD
``BlockGrid``'s diagonal-set tiles (``schedule.TileWave``) through the same
budget — with ``mesh=`` each wave's tiles go one-per-device over the joint
(data, model) axes — so the SGD and hybrid solvers factorize matrices
larger than device memory too.
"""
from repro.outofcore.driver import run_streaming_als
from repro.outofcore.runtime import (MemoryMeter, SimulatedFailure,
                                     StreamTelemetry, WaveCheckpointer)
from repro.outofcore.schedule import (IterationSchedule, SgdEpochSchedule,
                                      TileWave, Wave, WaveItem,
                                      build_schedule, build_sgd_schedule,
                                      required_capacity_bytes,
                                      sgd_required_capacity_bytes)
from repro.outofcore.sgd_driver import run_streaming_sgd
from repro.outofcore.store import (FactorStore, RatingStore, TileStore,
                                   binned_nbytes)

__all__ = [
    "FactorStore", "IterationSchedule", "MemoryMeter", "RatingStore",
    "SgdEpochSchedule", "SimulatedFailure", "StreamTelemetry", "TileStore",
    "TileWave", "Wave", "WaveCheckpointer", "WaveItem", "binned_nbytes",
    "build_schedule", "build_sgd_schedule", "required_capacity_bytes",
    "run_streaming_als", "run_streaming_sgd", "sgd_required_capacity_bytes",
]
