"""Layout autotuner (``repro.core.autotune``): PR-10 acceptance suite.

The analytic sweep's whole claim is *exactness* — each ladder rung is priced
at the same integers ``predicted_stream_stats`` would derive from a real
store built at that config — so the core test here is a brute-force store
build per rung, on both p = 1 and p = 2 topologies.  The rest covers the
TuneCache contract (round-trip, key separation, stale-shape miss, foreign
schema) and the ``"auto"`` wiring through ``RatingStore`` / ``block_ell`` /
``plan_for``.
"""
import json

import numpy as np
import pytest

from repro.core import autotune as at
from repro.core.partition import plan_for
from repro.outofcore import RatingStore, build_schedule
from repro.outofcore.schedule import predicted_stream_stats
from repro.sgd import block_ell
from repro.sparse import synth

SPEC = synth.SynthSpec("oc", 96, 40, 1500, 8, 0.05)


def _problem(seed=0, alpha_user=0.0):
    return synth.make_synthetic_ratings(SPEC, seed=seed,
                                        alpha_user=alpha_user)


def _store_bytes(r, q, cfg, p):
    """Ground truth for one rung: build the real store, price its schedule."""
    store = RatingStore(r, q=q, p=p, k_multiple=cfg.k_multiple,
                        n_bins=cfg.n_bins)
    fill_kw = (dict(bin_fills=store.bin_fill_pairs()) if store.n_bins > 1
               else dict(fill=store.worst_fill))
    plan = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=p, q=q, n_data=2,
                    hbm_bytes=1 << 22, **fill_kw)
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    stats = predicted_stream_stats(store, sched, SPEC.f)
    return sum(stats["x_bytes"]) + sum(stats["t_bytes"])


# ---------------------------------------------------------------------------
# Analytic pricing: exact vs brute-force store builds
# ---------------------------------------------------------------------------

def _batch0_heavy(seed=0):
    """SPEC's 96 users x 40 items, where three items are rated by every
    user of batch 0 (users 0-23 at q = 4) and by nobody else, beside four
    light ratings a user: the q batches' top item degrees differ (24 in
    batch 0, a few elsewhere)."""
    from repro.sparse.padded import csr_from_coo, pad_csr_fast

    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for u in range(SPEC.m):
        heavy = np.arange(3) if u < SPEC.m // 4 else np.zeros(0, np.int64)
        light = rng.choice(np.arange(3, SPEC.n), 4, replace=False)
        cols.append(np.concatenate([heavy, light]))
        rows.append(np.full(cols[-1].size, u))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = rng.normal(size=rows.size).astype(np.float32)
    return pad_csr_fast(*csr_from_coo(rows, cols, vals, SPEC.m), SPEC.n)


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("problem", ["synth", "batch0_heavy"])
def test_analytic_pricing_matches_real_store_per_rung(problem, p):
    """Every ladder rung's analytic price equals, to the byte, what the
    schedule layer predicts for a real store built at that config — on
    one device (per-batch bins) and on a p = 2 mesh (stacked bins).  On
    ``batch0_heavy`` one R^T batch's top degree is far above the others',
    so pricing every batch at the top K of any batch, as a uniform
    ``[q, n, K_loc]`` R^T stack would stream, misses the store there."""
    r = _problem()[0] if problem == "synth" else _batch0_heavy()
    for cfg in at.als_ladder(8):
        priced = at.predicted_als_bytes(r, 4, cfg, p=p, f=SPEC.f)
        assert priced["bytes"] == _store_bytes(r, 4, cfg, p), cfg
    if problem == "batch0_heavy" and p == 1:
        from repro.outofcore.store import binned_nbytes
        from repro.sparse.padded import round_k

        store = RatingStore(r, q=4)
        k_loc = round_k(int(at._batch_item_degrees(r, 4).max()))
        assert k_loc == 24
        assert sum(binned_nbytes(b) for b in store.rt_binned) \
            < 4 * SPEC.n * (k_loc * 8 + 4)


@pytest.mark.parametrize("p", [1, 2])
def test_sweep_argmin_matches_brute_force(p):
    """The sweep keeps the rung a brute-force enumeration over real stores
    would keep: score == min over the ladder, every candidate priced."""
    r, _, _, _ = _problem()
    res = at.tune_als_layout(r, 4, p=p, f=SPEC.f)
    assert res.unit == "bytes" and res.mode == "analytic"
    assert not res.cache_hit
    ladder = at.als_ladder(8)
    assert len(res.candidates) == len(ladder)
    truth = {json.dumps(cfg.to_obj(), sort_keys=True):
             _store_bytes(r, 4, cfg, p) for cfg in ladder}
    assert res.score == min(truth.values())
    assert truth[json.dumps(res.config.to_obj(), sort_keys=True)] == res.score
    for cand in res.candidates:
        assert res.score <= cand["score"]
        assert cand["score"] == \
            truth[json.dumps(cand["config"], sort_keys=True)]
    # the skewed fixture must actually reward binning, or the sweep is moot
    assert res.config.n_bins > 1


def test_measured_mode_scores_seconds():
    """Measured mode (Alg. 2 proper) times one real wave per rung through
    the obs phase clock and argmins on seconds."""
    r, _, _, _ = _problem()
    ladder = [at.LayoutConfig(n_bins=1), at.LayoutConfig(n_bins=2)]
    res = at.tune_als_layout(r, 2, f=SPEC.f, ladder=ladder, mode="measured")
    assert res.unit == "seconds" and res.mode == "measured"
    secs = [c["seconds"] for c in res.candidates]
    assert len(secs) == 2 and all(s > 0 for s in secs)
    assert res.score == min(secs)


# ---------------------------------------------------------------------------
# TuneCache contract
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_key_separation(tmp_path):
    r, _, _, _ = _problem()
    path = str(tmp_path / "tune_cache.json")
    miss = at.tune_als_layout(r, 4, f=SPEC.f, cache=path)
    assert not miss.cache_hit
    hit = at.tune_als_layout(r, 4, f=SPEC.f, cache=path)
    assert hit.cache_hit
    assert hit.config == miss.config and hit.score == miss.score
    assert hit.key == miss.key
    # a different topology is a different problem class: q = 2 must miss
    other = at.tune_als_layout(r, 2, f=SPEC.f, cache=path)
    assert not other.cache_hit and other.key != miss.key
    # on-disk form: schema + provenance stamp survive the round trip
    with open(path) as fh:
        data = json.load(fh)
    assert data["schema"] == at.TUNECACHE_SCHEMA
    entry = data["entries"][miss.key]
    assert entry["config"] == miss.config.to_obj()
    assert {"git_sha", "timestamp", "jax", "backend",
            "schema"} <= set(entry["provenance"])
    # invalidation: the next touch re-tunes
    cache = at.TuneCache(path)
    cache.invalidate(miss.key)
    assert at.tune_als_layout(r, 4, f=SPEC.f, cache=cache).cache_hit is False


def test_stale_shape_or_skew_misses():
    """Keys bucket shapes to powers of two and fingerprint the degree skew:
    minor drift hits, a 2x scale change or a different skew profile misses."""
    r, _, _, _ = _problem()
    deg = r.cnt[:r.m]
    base = at.tune_key("als", r.m, r.n_cols, r.nnz, deg, q=4)
    # minor drift within the same power-of-two bucket still hits
    assert at.tune_key("als", r.m + 3, r.n_cols, r.nnz + 40, deg, q=4) == base
    # a real scale change misses
    assert at.tune_key("als", 2 * r.m, r.n_cols, r.nnz, deg, q=4) != base
    assert at.tune_key("als", r.m, r.n_cols, 2 * r.nnz, deg, q=4) != base
    # same shapes, flat instead of skewed degrees: different signature
    flat = np.full_like(deg, max(int(deg.mean()), 1))
    assert at.tune_key("als", r.m, r.n_cols, r.nnz, flat, q=4) != base
    # solvers never share entries
    assert at.tune_key("sgd", r.m, r.n_cols, r.nnz, deg, q=4) != base


def test_cache_ignores_foreign_schema(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text(json.dumps({"schema": "somebody/else-v9",
                                "entries": {"k": {}}}))
    cache = at.TuneCache(str(path))
    assert len(cache) == 0                     # a miss, not an error
    cache.put("k2", {"config": at.LayoutConfig().to_obj(), "score": 1})
    assert json.loads(path.read_text())["schema"] == at.TUNECACHE_SCHEMA


# ---------------------------------------------------------------------------
# "auto" wiring: store / planner / SGD grid
# ---------------------------------------------------------------------------

def test_store_auto_matches_explicit_best():
    """``RatingStore(n_bins="auto")`` builds exactly the store the sweep's
    winner describes and records the decision for the ledger."""
    r, _, _, _ = _problem()
    cache = at.TuneCache(None)
    res = at.tune_als_layout(r, 4, cache=cache)       # store's default f=16
    store = RatingStore(r, q=4, n_bins="auto", tune_cache=cache)
    assert store.tune is not None and store.tune["cache_hit"] is True
    assert store.tune["config"] == res.config.to_obj()
    assert store.tune["key"] == res.key
    explicit = RatingStore(r, q=4, n_bins=res.config.n_bins,
                           k_multiple=res.config.k_multiple)
    assert store.n_bins == explicit.n_bins
    assert store.bin_fill_pairs() == explicit.bin_fill_pairs()
    # hand-built stores carry no decision
    assert explicit.tune is None


def test_plan_for_auto_prices_winner_bin_fills():
    r, _, _, _ = _problem()
    deg = np.asarray(r.cnt[:r.m])
    auto = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, 1, 4, n_data=2,
                    hbm_bytes=1 << 22, auto=True, degrees=deg)
    res = at.tune_plan_fills(SPEC.m, SPEC.n, r.nnz, SPEC.f, 1, 4,
                             degrees=deg)
    want = res.config.to_obj()
    pairs = next(c["bin_fills"] for c in res.candidates
                 if c["config"] == want)
    manual = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, 1, 4, n_data=2,
                      hbm_bytes=1 << 22, bin_fills=pairs)
    assert auto.bytes_per_device == manual.bytes_per_device
    assert auto.terms == manual.terms
    # degrees are mandatory on the auto path
    with pytest.raises(AssertionError, match="degrees"):
        plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, 1, 4, auto=True)


def test_sgd_auto_picks_min_dispatched_slots(tmp_path):
    """``block_ell(per_tile_k="auto")`` returns the grid with the fewest
    dispatched slots over the (per_tile_k, degree_sort) ladder, stamps the
    decision on ``grid.tune``, and rebuilds identically from a cache hit."""
    r, _, _, _ = _problem(alpha_user=1.2)         # skew both axes
    cache = str(tmp_path / "cache.json")
    grid = block_ell(r, 4, per_tile_k="auto", tune_cache=cache)
    slots = {(ptk, ds): block_ell(r, 4, per_tile_k=ptk,
                                  degree_sort=ds).padded_slots
             for ptk, ds in at.SGD_LADDER}
    assert grid.padded_slots == min(slots.values())
    assert grid.tune is not None and grid.tune["unit"] == "slots"
    assert not grid.tune["cache_hit"]
    assert grid.tune["score"] == grid.padded_slots
    cfg = at.LayoutConfig.from_obj(grid.tune["config"])
    assert slots[(cfg.per_tile_k, cfg.degree_sort)] == grid.padded_slots
    # the skewed fixture must reward per-tile K, or the sweep is moot
    assert cfg.per_tile_k
    # cache hit: config-only entry, grid rebuilt to the same layout
    again = block_ell(r, 4, per_tile_k="auto", tune_cache=cache)
    assert again.tune["cache_hit"] is True
    assert again.tune["config"] == grid.tune["config"]
    assert again.padded_slots == grid.padded_slots
    np.testing.assert_array_equal(again.cnt, grid.cnt)
    np.testing.assert_array_equal(again.idx, grid.idx)
