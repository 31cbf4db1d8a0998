"""Objective and evaluation metrics for ALS MF (paper eq. (1))."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref


@functools.partial(jax.jit, static_argnames=())
def _sq_err_padded(x, theta, idx, val, cnt):
    """Sum of squared errors over the nonzeros of a PaddedELL batch.

    x     [m, f]  row factors for these rows
    theta [n, f]  column factors
    idx   [m, K], val [m, K], cnt [m]
    """
    g = jnp.take(theta, idx, axis=0)                    # [m, K, f]
    pred = jnp.einsum("uf,ukf->uk", x, g,
                      precision=jax.lax.Precision.HIGHEST)
    mask = kref.mask_from_cnt(cnt, idx.shape[1], x.dtype)
    err = (val - pred) * mask
    return jnp.sum(err * err), jnp.sum(cnt)


def rmse_padded(x, theta, idx, val, cnt) -> jax.Array:
    """Root mean squared error over the nonzeros of (idx, val, cnt)."""
    sse, n = _sq_err_padded(x, theta, idx, val, cnt)
    return jnp.sqrt(sse / jnp.maximum(n, 1))


def objective_j(x, theta, idx, val, cnt_rows, cnt_cols, lam) -> jax.Array:
    """Paper eq. (1): squared error + weighted-lambda regularizer.

    cnt_rows [m] = n_{x_u}; cnt_cols [n] = n_{theta_v}.
    """
    sse, _ = _sq_err_padded(x, theta, idx, val, cnt_rows)
    reg = lam * (
        jnp.sum(cnt_rows.astype(x.dtype) * jnp.sum(x * x, axis=1))
        + jnp.sum(cnt_cols.astype(x.dtype) * jnp.sum(theta * theta, axis=1))
    )
    return sse + reg
