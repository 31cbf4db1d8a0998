"""Kernel-level microbenches: fused A+B pass (beyond-paper fusion) vs the
paper's two-pass structure, and the batched Cholesky solve that runs on
this backend (``kernels.ops.batch_solve``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref

from benchmarks.common import emit, time_fn


def _problem(m=2048, n=4096, K=256, f=64, seed=0):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (m, K)), jnp.int32)
    cnt = jnp.asarray(rng.integers(K // 2, K + 1, (m,)), jnp.int32)
    val = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    return theta, idx, val, cnt


@jax.jit
def one_pass(theta, idx, val, cnt):
    """Fused: A and B from one sweep (this repo's kernel structure)."""
    g = jnp.take(theta, idx, axis=0)
    mask = kref.mask_from_cnt(cnt, idx.shape[1], theta.dtype)
    diag = jnp.where(cnt > 0, 0.05 * cnt.astype(jnp.float32), 1.0)
    return kref.herm_ref(g, val, mask, diag)


@jax.jit
def two_pass(theta, idx, val, cnt):
    """cuMF structure: get_hermitian kernel + separate cuSPARSE B pass."""
    g = jax.lax.optimization_barrier(jnp.take(theta, idx, axis=0))
    mask = kref.mask_from_cnt(cnt, idx.shape[1], theta.dtype)
    gm = g * mask[..., None]
    A = jnp.einsum("ukf,ukg->ufg", gm, g)
    g2 = jax.lax.optimization_barrier(jnp.take(theta, idx, axis=0))
    B = jnp.einsum("uk,ukf->uf", val * mask, g2)
    diag = jnp.where(cnt > 0, 0.05 * cnt.astype(jnp.float32), 1.0)
    return A + diag[:, None, None] * jnp.eye(theta.shape[1]), B


def run():
    args = _problem()
    us1 = time_fn(one_pass, *args)
    us2 = time_fn(two_pass, *args)
    emit("kern_fused_AB_one_pass", us1, "passes=1")
    emit("kern_paper_two_pass", us2,
         f"passes=2;fusion_speedup={us2 / us1:.2f}x")
    A, B = one_pass(*args)
    us3 = time_fn(kops.batch_solve, A, B)
    m, f = B.shape
    emit("kern_batch_solve", us3,
         f"batch={m};f={f};gflops={(m * f**3 / 3) / (us3 * 1e-6) / 1e9:.1f}")


if __name__ == "__main__":
    run()
