"""The yardstick's arithmetic: the float64 reference solve, the row
sample, the flop count and the lower-precision control."""
import numpy as np

import chipbench_tiny  # noqa: F401  (puts the checkout root on sys.path)
from chipbench import control, flops, reference


def _problem(rng, n_rows=40, n_cols=30, f=6, nnz=300):
    rows = rng.integers(0, n_rows, nnz)
    cols = rng.integers(0, n_cols, nnz)
    key = np.unique(rows * n_cols + cols)
    rows, cols = key // n_cols, key % n_cols
    vals = rng.standard_normal(rows.size).astype(np.float32)
    fixed = rng.uniform(0, 1, (n_cols, f)).astype(np.float32)
    return reference.Csr.from_coo(rows, cols, vals, n_rows), fixed, rows


def test_solve_rows_against_numpy_solve():
    rng = np.random.default_rng(0)
    csr, fixed, rows = _problem(rng)
    lam = 0.05
    want_rows = np.arange(40)
    got = reference.solve_rows(fixed, csr, want_rows, lam)
    for i, u in enumerate(want_rows):
        lo, hi = csr.ptr[u], csr.ptr[u + 1]
        if hi == lo:
            np.testing.assert_array_equal(got[i], 0.0)
            continue
        g = fixed[csr.cols[lo:hi]].astype(np.float64)
        a = sum(np.outer(r, r) for r in g) + lam * (hi - lo) * np.eye(6)
        b = sum(r * v for r, v in zip(g, csr.vals[lo:hi]))
        np.testing.assert_allclose(got[i], np.linalg.solve(a, b),
                                   rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(csr.degrees, np.bincount(rows,
                                                           minlength=40))


def test_row_errors_relative_and_absolute():
    ref = np.array([[3.0, 4.0], [0.0, 0.0]])
    got = np.array([[3.0, 4.5], [0.0, 1e-3]], np.float32)
    np.testing.assert_allclose(reference.row_errors(got, ref),
                               [0.1, 1e-3], rtol=1e-6)


def test_sample_rows_spans_degrees():
    deg = np.arange(1000) % 97
    deg[123] = 5000
    s = reference.sample_rows(deg, 64, np.random.default_rng(1))
    assert s.size == 64 and np.unique(s).size == 64
    assert 123 in s and deg[s].min() == 0
    small = reference.sample_rows(deg[:10], 64, np.random.default_rng(1))
    np.testing.assert_array_equal(small, np.arange(10))


def test_flops_hand_count():
    # 2 Hermitians x 5 ratings x 3^2 entries x (mul + add) = 180;
    # (4 + 2) Cholesky factorizations x 3^3 / 3 = 54
    assert flops.als_iteration_flops(4, 2, 5, 3) == 180 + 54


def test_control_reads_well_above_float32():
    rng = np.random.default_rng(3)
    csr, fixed, _ = _problem(rng, n_rows=60, n_cols=200, f=32, nnz=3000)
    rows = np.arange(60)
    a, b = reference.row_systems(fixed, csr, rows, 0.05)
    f32 = np.linalg.solve(a.astype(np.float32),
                          b.astype(np.float32)[..., None])[..., 0]
    ctrl = control.solve_rows_low(fixed, csr, rows, 0.05)
    assert ctrl.dtype == np.float32
    e32 = reference.backward_errors(f32, a, b).max()
    ectrl = reference.backward_errors(ctrl, a, b).max()
    assert ectrl > 10 * e32, (ectrl, e32)
    assert ectrl < 1e-4


def test_backward_error_reads_the_data_not_the_condition():
    rng = np.random.default_rng(4)
    q, _ = np.linalg.qr(rng.standard_normal((6, 6)))
    a = np.stack([q @ np.diag(np.logspace(0, k, 6)) @ q.T for k in (1, 8)])
    a = np.concatenate([a, np.eye(6)[None]])
    b = rng.standard_normal((3, 6))
    b[2] = 0.0                                      # a row without ratings
    x = np.linalg.solve(a, b[..., None])[..., 0]
    assert reference.backward_errors(x, a, b).max() < 1e-14
    e = rng.standard_normal((3, 6, 6))
    e = e + e.transpose(0, 2, 1)
    e *= 1e-6 * (np.linalg.norm(a, axis=(1, 2)) /
                 np.linalg.norm(e, axis=(1, 2)))[:, None, None]
    moved = np.linalg.solve(a + e, b[..., None])[..., 0]
    back = reference.backward_errors(moved, a, b)
    fwd = reference.row_errors(moved, x)
    assert np.all(back[:2] <= 1e-6) and np.all(back[:2] > 1e-9), back
    assert fwd[1] > 100 * back[1], (fwd, back)      # condition 1e8
    assert back[2] == 0.0
