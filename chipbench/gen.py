"""Seeded rating generator for the benchmark: a chunked copy of the planted
low-rank recipe of ``repro.sparse.synth``.

:func:`planted_ratings` makes the same RNG calls in the same order as
``synth.make_synthetic_ratings_binned`` and returns the same train and test
COO for the same seed, but it never holds the ``[nnz, f]`` factor gathers:
the gather and the row-wise dot run in chunks of ``chunk`` ratings.  At
99M ratings and f = 100 the unchunked gathers alone are about 80 GB.

:func:`seeded_ratings` is what a benchmark run uses.  The sparsity pattern
and the train/test split come from the traffic file's ``structure_seed``;
``--seed`` relabels the items by a permutation and draws the planted
factors, the rating noise and the initial factors.  Every seed therefore
gives the program the same degree multisets, hence the same bins, waves
and compiled shapes, in another order, with other values.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

CHUNK = 1 << 20


@dataclasses.dataclass
class Coo:
    """Ratings as COO triplets (int64 rows/cols, float32 vals)."""

    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.rows.size)


def _power_law_probs(n: int, alpha: float, rng: np.random.Generator):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    rng.shuffle(p)
    return p / p.sum()


def lognormal_sigma(mean_ratio: float, cap_ratio: float, n: int) -> float:
    """The log-spread sigma at which ``n`` lognormal quantiles of median 1,
    clipped at ``cap_ratio``, have the mean ``mean_ratio`` (bisection)."""
    z = _normal_quantiles(n)
    lo, hi = 0.0, 8.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.minimum(np.exp(mid * z), cap_ratio).mean() < mean_ratio:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def _normal_quantiles(n: int) -> np.ndarray:
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv((i + 0.5) / n) for i in range(n)])


def degree_weights(n: int, stats: dict) -> np.ndarray:
    """Unnormalized sampling weights of ``n`` rows whose degrees follow
    published statistics ``{"median", "mean", "max"}``: the lognormal
    quantiles of median 1 whose mean, clipped at max / median, is
    mean / median.  Scaled to any number of ratings they keep the
    published median-to-mean and max-to-median ratios."""
    cap = stats["max"] / stats["median"]
    sigma = lognormal_sigma(stats["mean"] / stats["median"], cap, n)
    return np.minimum(np.exp(sigma * _normal_quantiles(n)), cap)


def _weights(n: int, law, rng: np.random.Generator) -> np.ndarray:
    """Row probabilities: a power law of exponent ``law`` over shuffled
    ranks (synth's recipe), or published degree statistics (a dict)."""
    if not isinstance(law, dict):
        return _power_law_probs(n, law, rng)
    p = degree_weights(n, law)
    rng.shuffle(p)
    return p / p.sum()


def exact_degrees(p: np.ndarray, total: int, cap: int) -> np.ndarray:
    """Integer degrees in proportion to ``p`` that sum to ``total``, none
    above ``cap``: what a capped row cannot hold goes to the others in
    proportion, and the largest remainders round up."""
    p = p / p.sum()
    fixed = np.zeros(p.size, bool)
    while True:
        left = total - cap * int(fixed.sum())
        want = np.where(fixed, float(cap), p / p[~fixed].sum() * left)
        over = ~fixed & (want > cap)
        if not over.any():
            break
        fixed |= over
    deg = np.floor(want).astype(np.int64)
    deg[np.argsort(deg - want, kind="stable")[:total - int(deg.sum())]] += 1
    return deg


def distinct_items(deg: np.ndarray, item_p: np.ndarray,
                   rng: np.random.Generator, rounds: int = 6):
    """(rows, cols) in which row u holds ``deg[u]`` distinct items drawn
    with probability ``item_p`` and without replacement: the first distinct
    items of a with-replacement stream (successive sampling), in rounds that
    top up what the last one left short.  Rows still short after
    ``rounds`` take the rest by Efraimidis-Spirakis keys over the items
    they lack, the same law.  Sorted by (row, col)."""
    m, n = deg.size, item_p.size
    got = np.zeros(0, np.int64)                 # sorted keys u * n + i
    need = deg.astype(np.int64).copy()
    for _ in range(rounds):
        todo = np.flatnonzero(need)
        if todo.size == 0:
            break
        users = np.repeat(todo, need[todo] + need[todo] // 4 + 4)
        keys = users * n + rng.choice(n, size=users.size, p=item_p)
        uniq, first = np.unique(keys, return_index=True)
        pos = np.minimum(np.searchsorted(got, uniq), max(got.size - 1, 0))
        fresh = got[pos] != uniq if got.size else np.ones(uniq.size, bool)
        uniq, first = uniq[fresh], first[fresh]
        uniq = uniq[np.argsort(first, kind="stable")]   # draw order
        u = uniq // n
        start = np.searchsorted(u, u, side="left") if u.size else u
        keep = uniq[np.arange(u.size) - start < need[u]]
        np.subtract.at(need, keep // n, 1)
        got = np.union1d(got, keep)
    logw, rest = np.log(item_p), [got]
    for u in np.flatnonzero(need):
        lo, hi = np.searchsorted(got, [u * n, (u + 1) * n])
        key = logw - np.log(-np.log(rng.random(n)))       # Gumbel
        key[got[lo:hi] - u * n] = -np.inf
        rest.append(u * n + np.argpartition(-key, need[u])[:need[u]])
    got = np.sort(np.concatenate(rest))
    return got // n, got % n


def planted_values(rows, cols, x_star, t_star, noise_draw, noise: float,
                   chunk: int = CHUNK) -> np.ndarray:
    """``<x*_u, theta*_v> / sqrt(f) + noise * noise_draw`` as float32, the
    synth expression evaluated ``chunk`` ratings at a time."""
    f = x_star.shape[1]
    out = np.empty(rows.size, np.float32)
    for lo in range(0, rows.size, chunk):
        hi = min(lo + chunk, rows.size)
        out[lo:hi] = (
            np.einsum("kf,kf->k", x_star[rows[lo:hi]], t_star[cols[lo:hi]])
            / np.sqrt(f) + noise * noise_draw[lo:hi]).astype(np.float32)
    return out


def planted_ratings(m: int, n: int, nnz: int, f: int, seed: int, *,
                    noise: float = 0.1, alpha: float = 0.8,
                    test_frac: float = 0.1, alpha_user=0.0,
                    values: bool = True,
                    chunk: int = CHUNK) -> tuple[Coo, Coo]:
    """(train, test) COO of synth's planted problem for ``seed``.

    ``alpha`` (items) and ``alpha_user`` are synth's power-law exponents
    (users uniform at 0), or dicts of published degree statistics for
    :func:`degree_weights`.  With user statistics every user holds exactly
    its share of ``nnz`` distinct items (:func:`distinct_items`) instead of
    synth's ``nnz`` independent draws less their repeats.  ``values=False`` makes every RNG call but
    skips the gathers, leaving ``vals`` empty: the pattern and split alone.
    """
    rng = np.random.default_rng(seed)
    x_star = rng.standard_normal((m, f)).astype(np.float32)
    t_star = rng.standard_normal((n, f)).astype(np.float32)
    if isinstance(alpha_user, dict):
        deg = exact_degrees(_weights(m, alpha_user, rng), nnz, n)
        rows, cols = distinct_items(deg, _weights(n, alpha, rng), rng)
    else:
        if alpha_user > 0.0:
            user_p = _power_law_probs(m, alpha_user, rng)
            rows = rng.choice(m, size=nnz, p=user_p).astype(np.int64)
        else:
            rows = rng.integers(0, m, size=nnz, dtype=np.int64)
        item_p = _weights(n, alpha, rng)
        cols = rng.choice(n, size=nnz, p=item_p).astype(np.int64)
        key = rows * n + cols
        _, uniq = np.unique(key, return_index=True)
        rows, cols = rows[uniq], cols[uniq]
    noise_draw = rng.standard_normal(len(rows))
    vals = (planted_values(rows, cols, x_star, t_star, noise_draw, noise,
                           chunk) if values else np.zeros(0, np.float32))
    n_test = int(len(rows) * test_frac)
    perm = rng.permutation(len(rows))
    test_sel, train_sel = perm[:n_test], perm[n_test:]

    def part(sel):
        return Coo(rows[sel], cols[sel], vals[sel] if values else vals)

    return part(train_sel), part(test_sel)


@dataclasses.dataclass
class Problem:
    """One run's data: training ratings and initial factors."""

    m: int
    n: int
    f: int
    train: Coo
    x0: np.ndarray       # [m, f] float32
    theta0: np.ndarray   # [n, f] float32


def seeded_ratings(config: dict, traffic: dict, seed: int,
                   chunk: int = CHUNK) -> Problem:
    """The pattern of ``traffic["structure_seed"]``, relabelled and valued
    by ``seed`` (see the module doc).  Initial factors are U[0, 0.3), the
    program's own ``als_init`` recipe, drawn from ``seed``."""
    m, n, f = config["m"], config["n"], config["f"]
    train, _ = planted_ratings(
        m, n, config["nnz"], f, traffic["structure_seed"],
        noise=traffic["noise"], alpha=traffic["items"],
        test_frac=traffic["test_frac"], alpha_user=traffic["users"],
        values=False, chunk=chunk)
    rng = np.random.default_rng(seed)
    relabel = rng.permutation(n)
    cols = relabel[train.cols]
    x_star = rng.standard_normal((m, f)).astype(np.float32)
    t_star = rng.standard_normal((n, f)).astype(np.float32)
    vals = planted_values(train.rows, cols, x_star, t_star,
                          rng.standard_normal(train.nnz), traffic["noise"],
                          chunk)
    del x_star, t_star
    x0 = (rng.random((m, f), np.float32) * np.float32(0.3))
    theta0 = (rng.random((n, f), np.float32) * np.float32(0.3))
    return Problem(m=m, n=n, f=f, train=Coo(train.rows, cols, vals),
                   x0=x0, theta0=theta0)
