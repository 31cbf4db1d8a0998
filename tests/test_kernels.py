"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles.

Shape/dtype sweeps via _propcheck (hypothesis when installed, a vendored
deterministic sweep otherwise); every kernel asserts allclose against the
ref.py oracle, per the repo contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.batch_solve import batch_solve_pallas
from repro.kernels.hermitian import fused_herm_pallas, herm_hbm_accum


def _problem(seed, m, n, K, f, frac_empty=0.2):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (m, K)), jnp.int32)
    cnt = jnp.asarray(
        np.where(rng.random(m) < frac_empty, 0, rng.integers(0, K + 1, m)),
        jnp.int32)
    val = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    val = val * (jnp.arange(K)[None] < cnt[:, None])
    return theta, idx, val, cnt


@settings(max_examples=12, deadline=None)
@given(
    m=st.sampled_from([8, 16, 24]),
    n=st.sampled_from([16, 40]),
    K=st.sampled_from([8, 16, 32]),
    f=st.sampled_from([4, 8, 12, 16]),
    seed=st.integers(0, 100),
)
def test_fused_herm_matches_oracle(m, n, K, f, seed):
    theta, idx, val, cnt = _problem(seed, m, n, K, f)
    A0, B0 = ops.fused_herm(theta, idx, val, cnt, 0.05, mode="ref")
    A1, B1 = ops.fused_herm(theta, idx, val, cnt, 0.05,
                            mode="kernel_interpret", tm=8, tk=8, f_mult=8)
    np.testing.assert_allclose(A0, A1, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(B0, B1, atol=2e-4, rtol=1e-4)


def test_fused_herm_weighted_lambda_diagonal():
    """A_u must carry lambda * n_u on the diagonal (paper eq. 2)."""
    theta, idx, val, cnt = _problem(3, 16, 32, 16, 8)
    lam = 0.7
    A, _ = ops.fused_herm(theta, idx, val, cnt, lam, mode="ref")
    g = jnp.take(theta, idx, axis=0)
    mask = kref.mask_from_cnt(cnt, idx.shape[1], jnp.float32)
    gm = g * mask[..., None]
    raw = jnp.einsum("ukf,ukg->ufg", gm, g)
    diag_expect = jnp.where(cnt > 0, lam * cnt.astype(jnp.float32), 1.0)
    got = jnp.diagonal(A - raw, axis1=1, axis2=2)
    np.testing.assert_allclose(
        got, jnp.broadcast_to(diag_expect[:, None], got.shape), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    m=st.sampled_from([8, 16]),
    f=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 100),
)
def test_batch_solve_matches_oracle(m, f, seed):
    rng = np.random.default_rng(seed)
    L = rng.standard_normal((m, f, f)) * 0.3
    A = jnp.asarray(L @ np.transpose(L, (0, 2, 1))
                    + 2.0 * np.eye(f)[None], jnp.float32)
    B = jnp.asarray(rng.standard_normal((m, f)), jnp.float32)
    x0 = kref.batch_solve_ref(A, B)
    x1 = ops.batch_solve(A, B, mode="kernel_interpret")
    np.testing.assert_allclose(x0, x1, atol=5e-4, rtol=5e-4)


def _backward_error(A, B, x):
    """Per-system normwise backward error ``|A x - b| / (|A| |x| + |b|)``
    in float64, Frobenius norm of A."""
    A, B, x = (np.asarray(a, np.float64) for a in (A, B, x))
    r = np.linalg.norm(np.einsum("uij,uj->ui", A, x) - B, axis=1)
    den = (np.linalg.norm(A, axis=(1, 2)) * np.linalg.norm(x, axis=1)
           + np.linalg.norm(B, axis=1))
    return np.where(den > 0, r / np.where(den > 0, den, 1.0), r)


def _solve_systems(kind, m, f, seed=0):
    """(A, B) of ``m`` width-``f`` systems: random SPD, weighted-lambda
    ALS systems with empty (identity) rows mixed in, or weighted-lambda
    systems of a single rating each (lambda = 0.05, the worst conditioned
    an ALS half makes)."""
    if kind == "spd":
        rng = np.random.default_rng(seed)
        L = rng.standard_normal((m, f, f)) * 0.3
        A = L @ np.transpose(L, (0, 2, 1)) + 2.0 * np.eye(f)[None]
        B = rng.standard_normal((m, f))
        return jnp.asarray(A, jnp.float32), jnp.asarray(B, jnp.float32)
    theta, idx, val, cnt = _problem(seed, m, 3 * f, 32, f,
                                    frac_empty=0.2)
    if kind == "one_rating":
        cnt = jnp.ones_like(cnt)
        val = val.at[:, 0].set(1.0)
    return kref.fused_herm_gathered_ref(theta, idx, val, cnt, 0.05)


@pytest.mark.parametrize("kind", ["spd", "empty_rows", "one_rating"])
@pytest.mark.parametrize("f", [10, 100])
def test_batch_solve_matches_oracle_cases(f, kind):
    """The systems-minor kernel against the oracle over two lane tiles with
    a ragged tail (m = 130), at the widths the cells run."""
    m = 130
    A, B = _solve_systems(kind, m, f)
    x0 = kref.batch_solve_ref(A, B)
    x1 = ops.batch_solve(A, B, mode="kernel_interpret")
    assert x1.shape == (m, f)
    # the forward error of two backward-stable f32 solves grows with
    # cond(A) (~1e3 for the weighted-lambda systems); the backward error
    # is the strict check
    np.testing.assert_allclose(x1, x0, atol=1e-4, rtol=2e-3)
    assert _backward_error(A, B, x1).max() <= 1e-6
    if kind == "empty_rows":
        empty = np.all(np.asarray(B) == 0, axis=1)
        assert empty.any() and np.all(np.asarray(x1)[empty] == 0)


def test_batch_solve_actually_solves():
    rng = np.random.default_rng(1)
    f, m = 12, 16
    L = rng.standard_normal((m, f, f)) * 0.4
    A = jnp.asarray(L @ np.transpose(L, (0, 2, 1)) + 3 * np.eye(f)[None],
                    jnp.float32)
    B = jnp.asarray(rng.standard_normal((m, f)), jnp.float32)
    x = ops.batch_solve(A, B, mode="kernel_interpret")
    np.testing.assert_allclose(jnp.einsum("uij,uj->ui", A, x), B,
                               atol=1e-3, rtol=1e-3)


def test_hbm_accum_ablation_matches():
    """Fig. 7 ablation kernel computes the same result (it is only slower)."""
    theta, idx, val, cnt = _problem(7, 16, 40, 24, 8)
    A0, B0 = ops.fused_herm(theta, idx, val, cnt, 0.05, mode="ref")
    g = jnp.take(theta, idx, axis=0)
    mask = kref.mask_from_cnt(cnt, idx.shape[1], jnp.float32)
    diag = jnp.where(cnt > 0, 0.05 * cnt.astype(jnp.float32), 1.0)
    A1, B1 = herm_hbm_accum(g, val, mask, diag, tm=8, tk=8, interpret=True)
    np.testing.assert_allclose(A0, A1, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(B0, B1, atol=2e-4, rtol=1e-4)


def test_padding_invariance():
    """fused_herm result must not depend on tile padding (tm/tk/f_mult)."""
    theta, idx, val, cnt = _problem(11, 24, 40, 24, 12)
    A0, B0 = ops.fused_herm(theta, idx, val, cnt, 0.05, mode="ref")
    for tm, tk, fm in [(8, 8, 8), (8, 16, 16), (16, 32, 32)]:
        A1, B1 = ops.fused_herm(theta, idx, val, cnt, 0.05,
                                mode="kernel_interpret", tm=tm, tk=tk,
                                f_mult=fm)
        np.testing.assert_allclose(A0, A1, atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(B0, B1, atol=2e-4, rtol=1e-4)


def test_als_update_factor_end_to_end():
    theta, idx, val, cnt = _problem(5, 16, 32, 16, 8)
    x_ref = kref.batch_solve_ref(*kref.fused_herm_gathered_ref(
        theta, idx, val, cnt, 0.05))
    x_kern = ops.als_update_factor(theta, idx, val, cnt, 0.05,
                                   mode="kernel_interpret",
                                   tm=8, tk=8, f_mult=8)
    np.testing.assert_allclose(x_ref, x_kern, atol=2e-3, rtol=2e-3)
