"""Pallas TPU kernel: batch-Hogwild SGD update over one rating tile.

CuMF_SGD keeps a (user-block, item-block) tile's factor slices resident
while a thread block sweeps its samples.  The TPU analogue:

- ``x`` and ``theta`` live in VMEM scratch, transposed (``[f, mb]``,
  ``[f, nb]``: users and items on lanes), across the entire ELL-slot grid
  dimension and are written back to HBM exactly once per tile (the same
  register-file re-homing as the hermitian kernel's accumulator); each
  slot streams in as ``[1, 1, mb]`` rows, so every block matches its array
  in the last two dimensions as Mosaic requires;
- the grid walks the K padded ELL slots ("arbitrary" semantics — the
  factor carry serializes them); one grid step updates all mb user rows
  concurrently, which is the batch of batch-Hogwild;
- the in-slot theta gather *and* scatter are both expressed as one-hot
  MXU matmuls (``P @ theta`` / ``P^T @ contrib`` with ``P [mb, nb]`` the
  slot's item-selection one-hot) — a systolic array wants matmuls, not
  per-row scatter ops — and item collisions inside a slot are resolved
  as the *mean* of the colliding gradients, exactly matching the oracle
  (``ref.sgd_block_ref``; summing instead diverges on power-law items).

The public wrapper pads mb/nb/f/K to tile multiples and dispatches
ref | kernel | kernel_interpret like the other ops; ``compat.pallas_call``
compiles on TPU and interprets on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels import ref as kref
from repro.kernels.budgets import SGD_VMEM_LIMIT
from repro.kernels.ops import _pad_axis, _round_up


def _sgd_tile_kernel(lr_ref, idx_ref, val_ref, mask_ref, x0_ref, t0_ref,
                     x_out, t_out, acc_x, acc_t, *, lam: float,
                     n_slots: int):
    """One ELL-slot grid step over a full tile.

    Factors are held transposed (``x^T [f, mb]``, ``theta^T [f, nb]``), so a
    slot's per-user vectors ``[1, mb]`` broadcast over sublanes and every
    matmul keeps a non-contracting dimension on both sides.
    """
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_x[...] = x0_ref[...]
        acc_t[...] = t0_ref[...]

    x = acc_x[...]                            # [f, mb]
    th = acc_t[...]                           # [f, nb]
    lr = lr_ref[...]                          # [1, 1]
    iv = idx_ref[0]                           # [1, mb]
    msk = mask_ref[0]                         # [1, mb]
    nb, mb = th.shape[1], x.shape[1]
    hi = jax.lax.Precision.HIGHEST
    # one-hot item selector of this slot, items on sublanes: [nb, mb]
    items = jax.lax.broadcasted_iota(jnp.int32, (nb, mb), 0)
    onehot = jnp.where(items == iv, msk, 0.0)
    tv = jax.lax.dot_general(                 # gather: [f, mb]
        th, onehot, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=hi, preferred_element_type=jnp.float32)
    e = (val_ref[0] - jnp.sum(x * tv, axis=0, keepdims=True)) * msk
    dx = msk * (e * tv - lam * x)
    acc_x[...] = x + lr * dx
    # theta side: mean of the colliding per-sample grads (see ref oracle),
    # both the grad sum and the collision count via one-hot MXU matmuls
    num = jax.lax.dot_general(                # scatter-sum: [f, nb]
        e * x, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=hi, preferred_element_type=jnp.float32)
    hits = jax.lax.dot_general(               # per-item hit count: [1, nb]
        jnp.ones((8, mb), jnp.float32), onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=hi, preferred_element_type=jnp.float32)[0:1]
    dt = num / jnp.maximum(hits, 1.0) \
        - lam * th * (hits > 0).astype(jnp.float32)
    acc_t[...] = th + lr * dt

    @pl.when(k == n_slots - 1)
    def _epilogue():
        x_out[...] = acc_x[...]
        t_out[...] = acc_t[...]


def sgd_tile_pallas(
    x: jax.Array,      # [mb, f]
    theta: jax.Array,  # [nb, f]
    idx: jax.Array,    # [mb, K] int32
    val: jax.Array,    # [mb, K]
    mask: jax.Array,   # [mb, K]
    lr: jax.Array,     # [1, 1]
    *,
    lam: float,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batch-Hogwild tile sweep; see module doc.  Shapes must be pre-padded.

    The kernel sees the factors transposed and the ELL slots as
    ``[K, 1, mb]`` rows, so each slot's block equals the array in its last
    two dimensions; the transposes here are plain XLA ops.
    """
    mb, K = idx.shape
    nb, f = theta.shape
    kernel = functools.partial(_sgd_tile_kernel, lam=lam, n_slots=K)
    slots = [a.T[:, None, :] for a in (idx, val, mask)]
    xt, tt = compat.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda k: (0, 0)),         # lr
            pl.BlockSpec((1, 1, mb), lambda k: (k, 0, 0)),  # idx slot
            pl.BlockSpec((1, 1, mb), lambda k: (k, 0, 0)),  # val slot
            pl.BlockSpec((1, 1, mb), lambda k: (k, 0, 0)),  # mask slot
            pl.BlockSpec((f, mb), lambda k: (0, 0)),        # x0^T
            pl.BlockSpec((f, nb), lambda k: (0, 0)),        # theta0^T
        ],
        out_specs=(
            pl.BlockSpec((f, mb), lambda k: (0, 0)),
            pl.BlockSpec((f, nb), lambda k: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((f, mb), jnp.float32),
            jax.ShapeDtypeStruct((f, nb), jnp.float32),
        ),
        scratch_shapes=[
            compat.vmem((f, mb), jnp.float32),   # resident x^T — the tile carry
            compat.vmem((f, nb), jnp.float32),   # resident theta^T
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            # the [nb, mb] one-hot and its temporaries outgrow the default
            # 16 MiB scoped VMEM at mb = nb = 1024 (v5e has 128 MiB)
            vmem_limit_bytes=SGD_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(lr, *slots, x.T, theta.T)
    return xt.T, tt.T


@functools.partial(
    jax.jit,
    static_argnames=("lam", "mode", "row_mult", "col_mult", "f_mult"))
def sgd_block_update(
    x: jax.Array,      # [mb, f]  user-block factor slice
    theta: jax.Array,  # [nb, f]  item-block factor slice
    idx: jax.Array,    # [mb, K]  block-local item indices
    val: jax.Array,    # [mb, K]
    cnt: jax.Array,    # [mb]
    lr: jax.Array,     # scalar learning rate (traced: no retrace per epoch)
    lam: float,
    *,
    mode: str = "ref",
    row_mult: int = 8,
    col_mult: int = 128,
    f_mult: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """One batch-Hogwild sweep over a tile; returns (x', theta').

    Padding is semantics-free by construction: padded ELL slots and
    padded user rows are masked out, padded theta rows are never
    selected (real ``idx < nb``), and padded feature columns start at 0
    and stay 0 under the multiplicative update.
    """
    mb, K = idx.shape
    nb, f = theta.shape
    lr = jnp.asarray(lr, jnp.float32)
    if mode == "ref":
        return kref.sgd_block_ref(x, theta, idx, val, cnt, lr, lam)
    mask = kref.mask_from_cnt(cnt, K, x.dtype)
    mbp = _round_up(mb, row_mult)
    nbp = _round_up(nb, col_mult)
    fp = _round_up(f, f_mult)
    x_p = _pad_axis(_pad_axis(x, 1, fp), 0, mbp)
    t_p = _pad_axis(_pad_axis(theta, 1, fp), 0, nbp)
    idx_p = _pad_axis(idx.astype(jnp.int32), 0, mbp)
    val_p = _pad_axis(val, 0, mbp)
    mask_p = _pad_axis(mask, 0, mbp)
    x_new, t_new = sgd_tile_pallas(
        x_p, t_p, idx_p, val_p, mask_p, lr.reshape(1, 1), lam=lam,
        interpret=(mode == "kernel_interpret"))
    return x_new[:mb, :f], t_new[:nb, :f]
