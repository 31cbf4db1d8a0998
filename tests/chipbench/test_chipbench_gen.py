"""The benchmark's generator: synth's planted problem without the [nnz, f]
gathers, and seeds that change values and labels but never shapes."""
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout root on sys.path)
from chipbench import gen
from repro.sparse import synth


def _coo_of(ell):
    """(rows, cols, vals) of a PaddedELL's live slots, sorted by (row,
    col)."""
    live = ell.mask() > 0
    rows, cols, vals = np.nonzero(live)[0], ell.idx[live], ell.val[live]
    order = np.lexsort((cols, rows))
    return rows[order], cols[order], vals[order]


def _sorted(coo):
    order = np.lexsort((coo.cols, coo.rows))
    return coo.rows[order], coo.cols[order], coo.vals[order]


@pytest.mark.parametrize("seed,alpha_user", [(0, 0.0), (7, 0.0),
                                             (2**31 + 5, 0.6)])
def test_same_coo_as_synth(seed, alpha_user):
    m, n, nnz, f = 300, 120, 4000, 16
    spec = synth.SynthSpec("t", m, n, nnz, f, 0.05)
    r_tr, _, r_te, _ = synth.make_synthetic_ratings_binned(
        spec, 4, seed=seed, noise=0.1, alpha=0.8, alpha_user=alpha_user)
    train, test = gen.planted_ratings(m, n, nnz, f, seed, noise=0.1,
                                      alpha=0.8, alpha_user=alpha_user,
                                      chunk=333)
    for got, want in zip(_sorted(train), _coo_of(r_tr.to_padded())):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(_sorted(test), _coo_of(r_te)):
        np.testing.assert_array_equal(got, want)


def test_pattern_without_values_matches():
    a, _ = gen.planted_ratings(200, 50, 1500, 8, 3)
    b, _ = gen.planted_ratings(200, 50, 1500, 8, 3, values=False)
    np.testing.assert_array_equal(a.rows, b.rows)
    np.testing.assert_array_equal(a.cols, b.cols)
    assert b.vals.size == 0


CONFIG = {"m": 400, "n": 90, "nnz": 5000, "f": 12}
TRAFFIC = {"structure_seed": 0, "items": 0.8, "users": 0.0,
           "noise": 0.1, "test_frac": 0.1}


def test_seeds_share_degrees_not_values():
    a = gen.seeded_ratings(CONFIG, TRAFFIC, 11)
    b = gen.seeded_ratings(CONFIG, TRAFFIC, 2**31 + 11)
    again = gen.seeded_ratings(CONFIG, TRAFFIC, 11)
    m, n = CONFIG["m"], CONFIG["n"]
    # same user degrees in place, same item degree multiset relabelled
    np.testing.assert_array_equal(np.bincount(a.train.rows, minlength=m),
                                  np.bincount(b.train.rows, minlength=m))
    np.testing.assert_array_equal(
        np.sort(np.bincount(a.train.cols, minlength=n)),
        np.sort(np.bincount(b.train.cols, minlength=n)))
    assert not np.array_equal(a.train.cols, b.train.cols)
    assert not np.allclose(a.train.vals, b.train.vals)
    assert not np.allclose(a.x0, b.x0)
    for x, y in [(a.train.cols, again.train.cols),
                 (a.train.vals, again.train.vals), (a.x0, again.x0),
                 (a.theta0, again.theta0)]:
        np.testing.assert_array_equal(x, y)
    assert a.x0.dtype == np.float32 and a.x0.shape == (m, CONFIG["f"])
    assert 0.0 <= a.x0.min() and a.x0.max() < 0.3


PRIZE = {"median": 96, "mean": 209.25, "max": 17653}


def test_lognormal_sigma_meets_the_published_mean():
    n = 5000
    sigma = gen.lognormal_sigma(209.25 / 96, 17653 / 96, n)
    w = gen.degree_weights(n, PRIZE)
    assert 1.0 < sigma < 1.5
    np.testing.assert_allclose(w.mean(), 209.25 / 96, rtol=1e-9)
    assert w.max() <= 17653 / 96 and np.median(w) == pytest.approx(1.0)


def test_exact_degrees_sum_and_cap():
    p = np.array([0.5, 0.3, 0.15, 0.05])
    deg = gen.exact_degrees(p, 101, cap=40)
    assert deg.sum() == 101 and deg.max() <= 40
    np.testing.assert_array_equal(deg, [40, 37, 18, 6])


def test_distinct_items_holds_every_degree():
    rng = np.random.default_rng(5)
    n = 300
    item_p = np.arange(1, n + 1, dtype=np.float64) ** -1.5
    item_p /= item_p.sum()
    deg = np.array([n - 1, 0, 3, 150, 40])     # one row takes all but one
    rows, cols = gen.distinct_items(deg, item_p, rng, rounds=2)
    np.testing.assert_array_equal(np.bincount(rows, minlength=5), deg)
    key = rows * n + cols
    assert np.unique(key).size == key.size and np.all(np.diff(key) > 0)


def test_published_statistics_shape_both_sides():
    m, n, nnz = 3000, 800, 120_000
    items = {"median": 561, "mean": 5654.5, "max": 232944}
    train, test = gen.planted_ratings(m, n, nnz, 4, 9, alpha=items,
                                      alpha_user=PRIZE, values=False)
    rows = np.concatenate([train.rows, test.rows])
    cols = np.concatenate([train.cols, test.cols])
    assert rows.size == nnz
    assert np.unique(rows * n + cols).size == nnz
    users = np.bincount(rows, minlength=m)
    assert users.mean() / np.median(users) == pytest.approx(209.25 / 96,
                                                            rel=0.1)
    per_item = np.bincount(cols, minlength=n)
    assert per_item.mean() / np.median(per_item) > 2.0
