"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *definitions of correctness*: each kernel test sweeps shapes
and dtypes and asserts allclose against these functions.  They are also the
fallback execution path on backends without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mask_from_cnt(cnt: jax.Array, K: int, dtype=jnp.float32) -> jax.Array:
    """[m] counts -> [m, K] 0/1 validity mask."""
    k = jnp.arange(K, dtype=jnp.int32)
    return (k[None, :] < cnt[:, None]).astype(dtype)


def herm_ref(
    g: jax.Array,      # [m, K, F] gathered theta rows (garbage in padding slots)
    val: jax.Array,    # [m, K]    rating values (0 in padding)
    mask: jax.Array,   # [m, K]    1.0 where slot is a real nonzero
    diag: jax.Array,   # [m]       weighted-lambda diagonal (lambda * n_u, or 1 for empty rows)
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused get_hermitian + B_u kernel.

    A_u = sum_k mask[u,k] * g[u,k,:] g[u,k,:]^T + diag[u] * I
    B_u = sum_k val[u,k]  * g[u,k,:]
    """
    F = g.shape[-1]
    gm = g * mask[..., None]
    # HIGHEST: an f32 reference; the TPU default would run one bf16 pass
    hi = jax.lax.Precision.HIGHEST
    A = jnp.einsum("ukf,ukg->ufg", gm, g, precision=hi,
                   preferred_element_type=jnp.float32)
    A = A + diag[:, None, None] * jnp.eye(F, dtype=A.dtype)[None, :, :]
    B = jnp.einsum("uk,ukf->uf", val * mask, g, precision=hi,
                   preferred_element_type=jnp.float32)
    return A, B


def batch_solve_ref(A: jax.Array, B: jax.Array) -> jax.Array:
    """Oracle for batched SPD solve: x_u = A_u^{-1} B_u via Cholesky."""
    L = jax.lax.linalg.cholesky(A)
    y = jax.lax.linalg.triangular_solve(
        L, B[..., None], left_side=True, lower=True)
    x = jax.lax.linalg.triangular_solve(
        L, y, left_side=True, lower=True, transpose_a=True)
    return x[..., 0]


def fused_herm_gathered_ref(theta, idx, val, cnt, lam):
    """End-to-end oracle: gather + herm in one call (what ops.fused_herm computes)."""
    g = jnp.take(theta, idx, axis=0)
    mask = mask_from_cnt(cnt, idx.shape[1], theta.dtype)
    diag = jnp.where(cnt > 0, lam * cnt.astype(jnp.float32), 1.0)
    return herm_ref(g, val, mask, diag)


def sgd_block_ref(
    x: jax.Array,      # [mb, f]  user factors of this user block
    theta: jax.Array,  # [nb, f]  item factors of this item block
    idx: jax.Array,    # [mb, K]  block-local item index per slot (0 in padding)
    val: jax.Array,    # [mb, K]  rating (0 in padding)
    cnt: jax.Array,    # [mb]     true nnz per user row
    lr: jax.Array,     # scalar   learning rate
    lam: float,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the batch-Hogwild block update (CuMF_SGD, one tile).

    The K ELL slots are processed sequentially; within one slot all mb
    user rows update concurrently (the "batch" of batch-Hogwild).  Users
    are disjoint by construction; item collisions inside a slot are
    determinized as the *mean* of the colliding per-sample gradients
    (mini-batch semantics), all computed against the pre-slot factors:

        e      = r_uv - <x_u, theta_v>
        x_u   += lr * (e * theta_v - lam * x_u)
        th_v  += lr * (mean_{u in slot hits v} e * x_u - lam * theta_v)

    Averaging (not summing) the collisions is load-bearing: a power-law
    popular item can be hit by thousands of rows in one slot, and the
    summed update diverges at any useful lr.
    """
    K = idx.shape[1]
    nb = theta.shape[0]
    mask = mask_from_cnt(cnt, K, x.dtype)

    def slot(k, carry):
        x, th = carry
        iv = idx[:, k]                       # [mb]
        msk = mask[:, k]                     # [mb]
        tv = jnp.take(th, iv, axis=0)        # [mb, f]
        e = (val[:, k] - jnp.sum(x * tv, axis=-1)) * msk
        dx = msk[:, None] * (e[:, None] * tv - lam * x)
        num = jnp.zeros_like(th).at[iv].add(
            msk[:, None] * (e[:, None] * x))        # [nb, f] grad sums
        hits = jnp.zeros((nb,), x.dtype).at[iv].add(msk)
        dt = num / jnp.maximum(hits, 1.0)[:, None] \
            - lam * th * (hits > 0)[:, None]
        return x + lr * dx, th + lr * dt

    return jax.lax.fori_loop(0, K, slot, (x, theta))
