"""Pallas TPU kernel: fused get_hermitian_x + B_u (the cuMF hot spot).

cuMF's MO-ALS (paper Alg. 2) holds the f x f accumulator A_u in the GPU
register file across all rated items of a row and spills it to global
memory exactly once.  The TPU analogue implemented here:

- the accumulators ``accA [TM, F, F]`` and ``accB [TM, F]`` live in a VMEM
  scratch buffer across the entire inner (k-tile) grid dimension and are
  written to HBM once per row tile — the register-file trick, re-homed to
  the memory TPUs actually expose;
- the per-thread outer products ``theta_v theta_v^T`` are re-associated into
  ``TM`` batched ``[F, TK] x [TK, F]`` MXU matmuls (``dot_general`` with a
  batch dim) — a systolic array wants matmuls, not scalar FMAs.  B_u's
  rating vector enters as a ``[TM, 1, TK]`` operand so that every Mosaic
  matmul keeps a non-contracting dimension on both sides; all contractions
  run at ``HIGHEST`` precision (f32, not one bf16 pass);
- the rated feature rows arrive pre-gathered (``g = theta[idx]``, an XLA
  DMA-gather playing the role of the texture cache) and are streamed
  HBM -> VMEM tile by tile via BlockSpec (the shared-memory ``bin`` of the
  paper is the TK tile);
- B_u is fused into the same pass (beyond-paper: cuMF used a separate
  cuSPARSE call, costing a second sweep over R and Theta).

Grid: (m/TM, K/TK), row tiles major / k tiles minor, so the accumulator
carry is over the minor dimension ("arbitrary" semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat


def _herm_tile(g, v, msk):
    """(A, B) contributions of one ``[TM, TK]`` rating tile.

    A[u] = g_m[u]^T @ g[u]: TM batched [F,TK]x[TK,F] MXU matmuls.
    B[u] = (val*mask)[u] @ g[u]: TM batched [1,TK]x[TK,F] matmuls; the
    rating row is a ``[TM, 1, TK]`` operand and the unit dim is dropped
    afterwards.
    """
    gm = g * msk[..., None]
    a = jax.lax.dot_general(
        gm, g, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    b = jax.lax.dot_general(
        (v * msk)[:, None, :], g,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return a, b[:, 0, :]


def _fused_herm_kernel(diag_ref, g_ref, val_ref, mask_ref,
                       a_ref, b_ref, acc_a, acc_b, *, n_ktiles: int):
    """One (row-tile, k-tile) grid step."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_b[...] = jnp.zeros_like(acc_b)

    da, db = _herm_tile(g_ref[...], val_ref[...], mask_ref[...])
    acc_a[...] += da
    acc_b[...] += db

    @pl.when(k == n_ktiles - 1)
    def _epilogue():
        F = acc_a.shape[-1]
        eye = jnp.eye(F, dtype=jnp.float32)
        d = diag_ref[...]                # [TM, 1]
        a_ref[...] = acc_a[...] + d[:, :, None] * eye[None, :, :]
        b_ref[...] = acc_b[...]


def fused_herm_pallas(
    g: jax.Array,        # [m, K, F]  gathered theta rows
    val: jax.Array,      # [m, K]
    mask: jax.Array,     # [m, K]
    diag: jax.Array,     # [m]
    *,
    tm: int = 8,
    tk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """A_u = sum_k mask*g g^T + diag*I ; B_u = sum_k val*g.  See module doc."""
    m, K, F = g.shape
    assert m % tm == 0, (m, tm)
    assert K % tk == 0, (K, tk)
    n_ktiles = K // tk
    grid = (m // tm, n_ktiles)

    kernel = functools.partial(_fused_herm_kernel, n_ktiles=n_ktiles)
    out_shapes = (
        jax.ShapeDtypeStruct((m, F, F), jnp.float32),
        jax.ShapeDtypeStruct((m, F), jnp.float32),
    )
    a, b = compat.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, 1), lambda i, k: (i, 0)),          # diag [m,1]
            pl.BlockSpec((tm, tk, F), lambda i, k: (i, k, 0)),   # g
            pl.BlockSpec((tm, tk), lambda i, k: (i, k)),         # val
            pl.BlockSpec((tm, tk), lambda i, k: (i, k)),         # mask
        ],
        out_specs=(
            pl.BlockSpec((tm, F, F), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((tm, F), lambda i, k: (i, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            compat.vmem((tm, F, F), jnp.float32),  # accA — the «register file»
            compat.vmem((tm, F), jnp.float32),     # accB
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(diag[:, None], g, val, mask)
    return a, b


# ---------------------------------------------------------------------------
# Ablation variant: the «no registers» baseline of paper Fig. 7.
# The accumulator round-trips through HBM after every k tile (bin), exactly
# like Alg. 2 without the register optimization: f^2 global-memory traffic
# per bin instead of once per row.  Implemented as one pallas_call per k
# chunk with an XLA add in between, so the HBM traffic is real, not modeled.
# ---------------------------------------------------------------------------

def _herm_onebin_kernel(g_ref, val_ref, mask_ref, a_ref, b_ref):
    a_ref[...], b_ref[...] = _herm_tile(g_ref[...], val_ref[...],
                                        mask_ref[...])


def herm_hbm_accum(
    g: jax.Array, val: jax.Array, mask: jax.Array, diag: jax.Array,
    *, tm: int = 8, tk: int = 128, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fig. 7 ablation: accumulate A_u in HBM per bin (2.5x slower in paper)."""
    m, K, F = g.shape
    assert K % tk == 0
    acc_a = jnp.zeros((m, F, F), jnp.float32)
    acc_b = jnp.zeros((m, F), jnp.float32)
    onebin = compat.pallas_call(
        _herm_onebin_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, tk, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((tm, tk), lambda i: (i, 0)),
            pl.BlockSpec((tm, tk), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((tm, F, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((tm, F), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, F, F), jnp.float32),
            jax.ShapeDtypeStruct((m, F), jnp.float32),
        ),
        interpret=interpret,
    )
    for k0 in range(0, K, tk):
        da, db = onebin(g[:, k0:k0 + tk], val[:, k0:k0 + tk], mask[:, k0:k0 + tk])
        acc_a = acc_a + da          # HBM round trip per bin (the ablated cost)
        acc_b = acc_b + db
    eye = jnp.eye(F, dtype=jnp.float32)[None, :, :]
    return acc_a + diag[:, None, None] * eye, acc_b
