"""Declared per-kernel VMEM budgets — the static memory contract.

The paper's memory model (eq. 5-8 and the §3.3 register/shared-memory
discussion) is what makes cuMF fast: every kernel's working set is sized
against a *declared* fast-memory capacity, not discovered by OOM.  On the
TPU port that capacity is VMEM (``launch.mesh.VMEM_BYTES`` = 16 MiB per
chip; duplicated here as a plain number so this module stays importable
without JAX — test_analysis cross-checks the two constants).

``repro.analysis``'s pallas-budget rule statically walks every
``pl.pallas_call`` site, resolves the BlockSpec / scratch / out-spec block
shapes against the ``dim_bounds`` declared here, and estimates the VMEM
footprint as::

    2 * (sum of in-spec blocks + sum of out-spec blocks) + scratch

The factor 2 models the Pallas pipeline's double buffering of streamed
blocks (the next grid step's tiles are DMA'd while the current one
computes); scratch is allocated once and carried across the grid.  Block
dtypes are taken from the ``compat.vmem(..., dtype)`` declaration for
scratch and assumed float32 (4 B) for streamed blocks — every kernel in
this repo streams f32.

Budgets are per *wrapper function* (the enclosing ``def`` of the
``pallas_call``).  ``dim_bounds`` are the worst-case tile sizes the
wrapper is allowed to be called with; the public wrappers enforce them by
construction (tm/tk/tb defaults, ``f_mult=128`` padding) except for the
SGD tile sizes mb/nb, which ``sgd.blocking`` keeps at or below the bound
for every grid the repo builds (g >= 2 over the bench shapes).

Degree-binned dispatch (``BinnedELL`` bins, per-tile-K SGD groups) needs
no budget entries of its own: each per-bin call is the same wrapper at a
*smaller* K (bins satisfy ``K_b <= K <= dim_bounds`` by construction, and
the ALS kernels' VMEM footprint is K-independent anyway — they stream
fixed [tm, tk] rating tiles and grid over K), so the uniform worst-case
bounds declared here dominate every binned call site.

Worst-case footprints under the declared bounds (the numbers the limits
are set against, with headroom for interpreter/layout slack):

- ``fused_herm_pallas``  (tm=8, tk=128, F=128): streamed in 520 KiB +
  out 516 KiB, doubled, + 516 KiB scratch ~= 2.53 MiB  -> limit 4 MiB.
- ``herm_hbm_accum``     (tm=8, tk=128, F=128): ~2.03 MiB (no scratch —
  that is the point of the Fig. 7 ablation)          -> limit 4 MiB.
- ``batch_solve_pallas`` (tb=8, F=128): ~1.02 MiB     -> limit 2 MiB.
- ``sgd_tile_pallas``    (mb=nb=1024, f=128): streamed in ~1.01 MiB +
  out 1 MiB, doubled, + 1 MiB scratch ~= 5.02 MiB     -> limit 8 MiB
  (the factors stream transposed, ``[f, mb]`` / ``[f, nb]``, and each ELL
  slot as a ``[1, 1, mb]`` row — the same element counts).

All well under the 16 MiB default scoped VMEM, with room for the
compiler's own temporaries — except the SGD tile's in-kernel ``[nb, mb]``
one-hot, which is no block and which the model does not see: that kernel
asks Mosaic for ``SGD_VMEM_LIMIT``.  A new kernel (or a tile-size bump)
that blows its limit fails the lint job before it ever reaches hardware;
``tests/test_tpu_compile.py`` compiles every kernel for a described v5e,
which also catches what this model cannot.
"""
from __future__ import annotations

import dataclasses

#: mirror of launch.mesh.VMEM_BYTES (kept import-free; cross-checked in
#: tests/test_analysis.py so the two cannot drift)
VMEM_BYTES = 16 * (1 << 20)

#: scoped-VMEM limit the SGD tile kernel asks Mosaic for: its in-kernel
#: ``[nb, mb]`` one-hot temporaries are not blocks, so the footprint model
#: below does not see them (19.4 MiB at mb = nb = 1024, F = 128 when
#: compiled for a v5e, against the 16 MiB default scoped limit)
SGD_VMEM_LIMIT = 32 * (1 << 20)


@dataclasses.dataclass(frozen=True)
class KernelBudget:
    """Static VMEM contract of one pallas_call wrapper."""

    vmem_limit: int              # bytes the estimated footprint must fit in
    dim_bounds: dict             # symbolic dim name -> worst-case value
    note: str = ""               # where the bound comes from


def footprint_bytes(name: str, **dims) -> int:
    """Estimated VMEM footprint of one launch of kernel ``name`` at the
    given tile sizes — the same ``2 * (in + out) + scratch`` double-buffer
    model the pallas-budget lint rule applies to the declared worst case
    (see the module doc), evaluated at a *run's* launch dims so the
    plan-vs-actual ledger can record budget-vs-launched.

    Dims are the symbolic names of ``BUDGETS[name].dim_bounds`` (``F`` is
    the f_mult-padded latent dim; the SGD kernel's ``K`` sizes the grid,
    not a block, and is not needed).  Block sums mirror the wrappers'
    BlockSpecs exactly: idx/val/mask/cnt stream as separate f32-width
    blocks, factor/accumulator tiles as [**, F] / [**, F, F] blocks.
    """
    if name == "fused_herm_pallas":
        tm, tk, F = dims["tm"], dims["tk"], dims["F"]
        inb = tm + tm * tk * F + 2 * tm * tk        # diag, g, val, mask
        out = tm * F * F + tm * F                   # A, B
        scratch = out                               # accA, accB carry
        return 4 * (2 * (inb + out) + scratch)
    if name == "herm_hbm_accum":
        tm, tk, F = dims["tm"], dims["tk"], dims["F"]
        inb = tm * tk * F + 2 * tm * tk             # g, val, mask
        out = tm * F * F + tm * F                   # A, B (HBM round-trip)
        return 4 * 2 * (inb + out)
    if name == "batch_solve_pallas":
        tb, F = dims["tb"], dims["F"]
        inb = tb * F * F + tb * F                   # A batch, B batch
        out = tb * F                                # solved rows
        return 4 * 2 * (inb + out)
    if name == "sgd_tile_pallas":
        mb, nb, f = dims["mb"], dims["nb"], dims["f"]
        fac = (mb + nb) * f                         # x^T + theta^T blocks
        inb = 1 + 3 * mb + fac                      # lr, slot rows, x0/t0
        out = fac
        scratch = fac                               # resident factor carry
        return 4 * (2 * (inb + out) + scratch)
    raise KeyError(f"no footprint model for kernel {name!r}; "
                   f"known: {sorted(BUDGETS)}")


BUDGETS: dict[str, KernelBudget] = {
    "fused_herm_pallas": KernelBudget(
        vmem_limit=4 * (1 << 20),
        dim_bounds={"tm": 8, "tk": 128, "F": 128},
        note="MO-ALS fused Hermitian (paper Alg. 2); F = f padded to the "
             "MXU lane width by ops.fused_herm(f_mult=128)",
    ),
    "herm_hbm_accum": KernelBudget(
        vmem_limit=4 * (1 << 20),
        dim_bounds={"tm": 8, "tk": 128, "F": 128},
        note="Fig. 7 no-registers ablation: per-bin kernel, accumulator "
             "round-trips HBM so no scratch term",
    ),
    "batch_solve_pallas": KernelBudget(
        vmem_limit=2 * (1 << 20),
        dim_bounds={"tb": 8, "F": 128},
        note="batched Cholesky solve; one [tb, F, F] system batch resident",
    ),
    "sgd_tile_pallas": KernelBudget(
        vmem_limit=8 * (1 << 20),
        dim_bounds={"mb": 1024, "nb": 1024, "f": 128, "K": 1 << 16},
        note="CuMF_SGD tile sweep: both factor blocks resident in scratch; "
             "mb/nb bound the block sizes sgd.blocking may produce (K only "
             "sizes the grid, not a block)",
    ),
}
