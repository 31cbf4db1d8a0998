"""Plan-vs-actual ledger + bench history/regression gate (ISSUE 8).

Fast tier: record verdict semantics, validate_ledger's recompute-and-reject
behavior, merge_ledgers, the history append/compare round-trip, the regress
exit codes, the report CLI, and footprint_bytes against the budgets-file
docstring numbers.  Slow tier: real streaming ALS/SGD runs whose emitted
ledgers must validate with every exact record holding; a seeded
mis-prediction must exit nonzero through ``repro.obs.regress --ledger``.
Mesh tier: the 2x2-mesh run's ledger carries exact reduce fast/slow rows.
"""
import json
import os
import sys

import pytest

from repro.obs.ledger import (LEDGER_SCHEMA, Ledger, merge_ledgers,
                              validate_ledger)
from repro.obs.regress import (check_ledger, classify, compare_history,
                               load_history)
from repro.obs.regress import main as regress_main
from repro.obs.report import main as report_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)          # for benchmarks.history (no PYTHONPATH)


def make_ledger(**overrides):
    led = Ledger(solver="test", waves=3)
    led.record("bytes_streamed", 1000, overrides.get("measured_bytes", 1000),
               unit="bytes", check="exact")
    led.record("peak_device_bytes", 4096, overrides.get("measured_peak", 2048),
               unit="bytes", check="le")
    led.record("fill_waste_ratio", 1.25, overrides.get("measured_fill", 1.25),
               unit="ratio", check="rel", rel_tol=1e-9)
    return led


class TestLedgerRecords:
    def test_exact_check(self):
        led = Ledger()
        ok = led.record("a", 10, 10, unit="bytes")
        bad = led.record("b", 10, 11, unit="bytes")
        assert ok["ok"] and not bad["ok"]
        assert bad["drift"] == pytest.approx(0.1)
        assert not led.ok
        assert led.flags == ["error:b"]

    def test_le_check_is_a_bound_not_a_value(self):
        led = Ledger()
        under = led.record("peak", 100, 60, unit="bytes", check="le")
        at = led.record("cap", 100, 100, unit="bytes", check="le")
        over = led.record("blown", 100, 101, unit="bytes", check="le")
        assert under["ok"] and at["ok"] and not over["ok"]
        assert under["drift"] == pytest.approx(-0.4)

    def test_rel_check_tolerance(self):
        led = Ledger()
        inside = led.record("r1", 2.0, 2.0 + 1e-12, unit="x",
                            check="rel", rel_tol=1e-9)
        outside = led.record("r2", 2.0, 2.2, unit="x",
                             check="rel", rel_tol=0.05)
        assert inside["ok"] and not outside["ok"]

    def test_warn_severity_reports_but_does_not_fail(self):
        led = Ledger()
        led.record("hard", 5, 5, unit="n")
        led.record("soft", 1.0, 9.0, unit="s", check="rel", rel_tol=0.1,
                   severity="warn")
        assert led.ok                       # warn records never decide ok
        assert led.flags == ["warn:soft"]   # but they are still flagged
        obj = led.to_obj()
        summary = validate_ledger(obj)
        assert summary == {"records": 2, "errors": 0, "warnings": 1,
                           "ok": True}

    def test_zero_prediction_drift_is_null(self):
        led = Ledger()
        both_zero = led.record("z0", 0, 0, unit="bytes")
        surprise = led.record("z1", 0, 7, unit="bytes")
        assert both_zero["drift"] == 0.0 and both_zero["ok"]
        assert surprise["drift"] is None and not surprise["ok"]
        validate_ledger(led.to_obj())       # null drift round-trips

    def test_records_survive_json_round_trip(self):
        obj = json.loads(json.dumps(make_ledger().to_obj()))
        assert obj["schema"] == LEDGER_SCHEMA
        assert validate_ledger(obj)["ok"]


class TestValidateLedger:
    def test_rejects_wrong_schema(self):
        obj = make_ledger().to_obj()
        obj["schema"] = "nope"
        with pytest.raises(ValueError, match="schema"):
            validate_ledger(obj)

    def test_rejects_missing_record_key(self):
        obj = make_ledger().to_obj()
        del obj["records"][0]["drift"]
        with pytest.raises(ValueError, match="missing key"):
            validate_ledger(obj)

    def test_recomputes_verdicts_so_tampering_fails(self):
        """A hand-flipped ok is REJECTED (ValueError), not reported as a
        drift — the gate trusts the numbers, never the stored verdict."""
        obj = make_ledger(measured_bytes=999).to_obj()
        assert not obj["ok"]
        obj["records"][0]["ok"] = True       # tamper the record verdict
        with pytest.raises(ValueError, match="inconsistent"):
            validate_ledger(obj)

    def test_rejects_stale_overall_ok(self):
        obj = make_ledger().to_obj()
        obj["ok"] = False                    # numbers say True
        with pytest.raises(ValueError, match="overall ok"):
            validate_ledger(obj)

    def test_rejects_tampered_drift(self):
        obj = make_ledger().to_obj()
        obj["records"][1]["drift"] = 0.0     # peak drift is really -0.5
        with pytest.raises(ValueError, match="drift"):
            validate_ledger(obj)

    def test_rejects_non_numeric_measurement(self):
        obj = make_ledger().to_obj()
        obj["records"][0]["measured"] = "1000"
        with pytest.raises(ValueError, match="not a number"):
            validate_ledger(obj)


class TestMergeLedgers:
    def test_prefixes_and_conjunction(self):
        good = make_ledger().to_obj()
        bad = make_ledger(measured_bytes=1)
        bad.run["solver"] = "sgd"
        merged = merge_ledgers({"als": good, "sgd": bad.to_obj(),
                                "skipped": None})
        assert validate_ledger(merged)["records"] == 6
        names = [r["name"] for r in merged["records"]]
        assert "als/bytes_streamed" in names
        assert "sgd/bytes_streamed" in names
        assert not merged["ok"]
        assert "error:sgd/bytes_streamed" in merged["flags"]
        assert merged["run"]["sgd"]["solver"] == "sgd"


class TestRegressClassify:
    def test_key_taxonomy(self):
        # deterministic: pure shape functions, exact across runs
        for key in ("bytes_streamed_per_iter", "waves", "padded_slots",
                    "nnz_streamed", "n_data", "fits", "fill_waste_ratio"):
            assert classify(key) == "deterministic", key
        # times: warn-only (CI noise)
        for key in ("wall_seconds", "measured_iter_s", "epochs_per_sec",
                    "phase_seconds.solve"):
            assert classify(key) == "time", key
        # metered peaks are prefetch-timing dependent: noisy by override,
        # even though "bytes" would otherwise read deterministic
        assert classify("peak_device_bytes") == "noisy"
        assert classify("rmse") == "noisy"


def _history_entry(bench="bench_x", quick=True, **metrics):
    row = {"name": "row0", "bytes_streamed": 100, "waves": 4,
           "wall_seconds": 1.0, "rmse": 0.91}
    row.update(metrics)
    return {"schema": "repro.obs/bench-history-v1",
            "provenance": {"git_sha": "abc", "timestamp": "t",
                           "quick": quick, "backend": "cpu",
                           "device_count": 1, "jax": "0"},
            "bench": bench, "records": [row]}


class TestHistoryCompare:
    def test_first_run_seeds(self):
        lines, failures = compare_history([_history_entry()])
        assert failures == 0
        assert any(line.startswith("SEED") for line in lines)

    def test_identical_runs_pass(self):
        entries = [_history_entry(), _history_entry()]
        lines, failures = compare_history(entries)
        assert failures == 0
        assert any(line.startswith("OK") for line in lines)

    def test_deterministic_drift_fails(self):
        entries = [_history_entry(), _history_entry(bytes_streamed=101)]
        lines, failures = compare_history(entries)
        assert failures == 1
        assert any("bytes_streamed" in li and li.startswith("FAIL")
                   for li in lines)

    def test_time_jitter_warns_only(self):
        entries = [_history_entry(), _history_entry(wall_seconds=2.5)]
        lines, failures = compare_history(entries)       # 150% > 50% tol
        assert failures == 0
        assert any("wall_seconds" in li and li.startswith("WARN")
                   for li in lines)
        _, strict = compare_history(entries, strict_times=True)
        assert strict == 1

    def test_configs_compared_separately(self):
        # a quick run is never baselined against a full run
        entries = [_history_entry(quick=False, bytes_streamed=999),
                   _history_entry(quick=True)]
        _, failures = compare_history(entries)
        assert failures == 0

    def test_rolling_median_absorbs_one_outlier(self):
        entries = [_history_entry(wall_seconds=s)
                   for s in (1.0, 1.1, 9.0, 1.0, 1.05)]
        lines, failures = compare_history(entries, window=4)
        assert failures == 0
        assert not any(li.startswith("WARN") for li in lines)


class TestHistoryRoundTrip:
    def test_append_load_compare(self, tmp_path):
        from benchmarks.history import append_history, provenance, stamp

        prov = provenance(quick=True)
        assert prov["git_sha"] and prov["timestamp"]
        assert prov["quick"] is True
        records = [{"name": "r", "bytes_streamed": 64, "wall_seconds": 0.5}]
        stamp(records, prov)
        assert records[0]["provenance"] is prov
        path = tmp_path / "hist.jsonl"
        append_history(str(path), "bench_t", records, prov)
        append_history(str(path), "bench_t", records, prov)
        entries = load_history(str(path))
        assert len(entries) == 2
        assert entries[0]["bench"] == "bench_t"
        _, failures = compare_history(entries)
        assert failures == 0

    def test_bad_schema_line_rejected(self, tmp_path):
        path = tmp_path / "hist.jsonl"
        path.write_text('{"schema": "other", "records": []}\n')
        with pytest.raises(ValueError, match="schema"):
            load_history(str(path))


class TestRegressCli:
    def test_clean_ledger_exits_zero(self, tmp_path, capsys):
        path = tmp_path / "led.json"
        path.write_text(json.dumps(make_ledger().to_obj()))
        assert regress_main(["--ledger", str(path)]) == 0
        assert "PASS" in capsys.readouterr().out

    def test_seeded_misprediction_exits_nonzero(self, tmp_path, capsys):
        """THE acceptance check: build a ledger through the real API with a
        wrong prediction and the gate must hard-fail on it."""
        led = make_ledger(measured_bytes=1536)        # predicted 1000
        path = tmp_path / "led.json"
        path.write_text(json.dumps(led.to_obj()))
        lines, failures = check_ledger(str(path))
        assert failures == 1
        assert any("bytes_streamed" in li for li in lines)
        assert regress_main(["--ledger", str(path)]) == 1
        assert "FAIL" in capsys.readouterr().out

    def test_history_gate_exit_codes(self, tmp_path):
        path = tmp_path / "hist.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(_history_entry()) + "\n")
            f.write(json.dumps(_history_entry()) + "\n")
        assert regress_main(["--history", str(path)]) == 0
        with open(path, "a") as f:
            f.write(json.dumps(_history_entry(waves=5)) + "\n")
        assert regress_main(["--history", str(path)]) == 1

    def test_report_cli_renders_ledger(self, tmp_path, capsys):
        led = make_ledger()
        led.run["phase_seconds"] = {"driver": 2.0, "solve": 1.5}
        path = tmp_path / "led.json"
        path.write_text(json.dumps(led.to_obj()))
        assert report_main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "bytes_streamed" in out and "ok=True" in out
        assert "drift flags: none" in out


class TestVmemFootprints:
    def test_footprints_match_budget_docstring(self):
        """footprint_bytes reproduces the hand-derived MiB numbers the
        BUDGETS docstring records (the reprolint vmem rule's constants)."""
        from repro.kernels.budgets import BUDGETS, footprint_bytes

        mib = 2 ** 20
        cases = {
            "fused_herm_pallas": (dict(tm=8, tk=128, F=128), 2.53),
            "herm_hbm_accum": (dict(tm=8, tk=128, F=128), 2.023),
            "batch_solve_pallas": (dict(f=100, T=128), 14.89),
            "sgd_tile_pallas": (dict(mb=1024, nb=1024, f=128), 5.02),
        }
        for name, (dims, want_mib) in cases.items():
            assert dims == {k: v for k, v in
                            BUDGETS[name].dim_bounds.items() if k != "K"}
            got = footprint_bytes(name, **dims)
            assert got / mib == pytest.approx(want_mib, abs=5e-3), name
            assert got <= BUDGETS[name].vmem_limit, name
        with pytest.raises(KeyError):
            footprint_bytes("no_such_kernel", tm=1)


def _streaming_als_run():
    from repro.core import als as als_mod
    from repro.core.partition import plan_for
    from repro.outofcore import (RatingStore, build_schedule,
                                 run_streaming_als)
    from repro.sparse import synth

    spec = synth.SynthSpec("obs-oc", 96, 40, 1500, 8, 0.05)
    r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
    store = RatingStore(r, q=4)
    acc_eps = spec.n * (spec.f * spec.f + 3 * spec.f + 1) * 4
    plan = plan_for(spec.m, spec.n, r.nnz, spec.f, p=1, q=4, n_data=2,
                    fill=store.worst_fill, eps=acc_eps, buffers=4,
                    hbm_bytes=1 << 22)
    sched = build_schedule(plan, spec.m, spec.n, n_data=2)
    assert len(sched.waves) >= 2
    cfg = als_mod.AlsConfig(f=spec.f, lam=spec.lam, iters=2, mode="ref")
    return run_streaming_als(store, sched, cfg)


@pytest.mark.slow
class TestStreamingLedgers:
    def test_als_ledger_validates_and_exact_records_hold(self):
        _, _, tel = _streaming_als_run()
        obj = tel.ledger
        summary = validate_ledger(obj)
        assert summary["ok"] and summary["errors"] == 0
        recs = {r["name"]: r for r in obj["records"]}
        # every exact record holds with zero drift: predicted streamed
        # bytes / pad slots / nnz came from shapes alone and matched
        for name in ("bytes_streamed", "padded_slots", "nnz_streamed"):
            assert recs[name]["check"] == "exact"
            assert recs[name]["ok"] and recs[name]["drift"] == 0.0
        assert recs["bytes_streamed"]["measured"] == tel.bytes_streamed
        # power-law fill: waste is real, measured, and under the plan bound
        assert tel.fill_waste_ratio > 1.0
        assert recs["fill_waste_ratio"]["ok"]
        assert recs["worst_fill_bound"]["check"] == "le"
        assert recs["worst_fill_bound"]["ok"]
        assert recs["peak_device_bytes"]["check"] == "le"
        assert recs["peak_device_bytes"]["measured"] == tel.peak_bytes
        # kernel launches stayed inside their static VMEM budgets
        assert recs["vmem/fused_herm_pallas"]["ok"]
        assert recs["vmem/batch_solve_pallas"]["ok"]
        assert obj["run"]["solver"] == "als"
        assert obj["run"]["waves"] >= 2 and obj["run"]["iterations"] == 2

    def test_sgd_ledger_validates(self):
        from repro.outofcore import (TileStore, build_sgd_schedule,
                                     run_streaming_sgd)
        from repro.sgd import SgdConfig, block_ell
        from repro.sparse import synth

        spec = synth.SynthSpec("obs-sgd", 96, 40, 1500, 8, 0.05)
        r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
        grid = block_ell(r, g=4)
        sched = build_sgd_schedule(grid, spec.f, n_workers=2)
        cfg = SgdConfig(f=spec.f, lam=spec.lam, lr=0.1, epochs=2,
                        mode="ref", seed=1)
        _, _, tel = run_streaming_sgd(TileStore(grid), sched, cfg)
        obj = tel.ledger
        assert validate_ledger(obj)["ok"]
        recs = {r_["name"]: r_ for r_ in obj["records"]}
        for name in ("bytes_streamed", "padded_slots", "nnz_streamed"):
            assert recs[name]["ok"] and recs[name]["drift"] == 0.0
        # one full epoch touches every tile, so the grid fill IS the
        # measured waste — the bound is tight here, not just safe
        assert recs["worst_fill_bound"]["ok"]
        assert recs["fill_waste_ratio"]["ok"]
        assert recs["vmem/sgd_tile_pallas"]["ok"]
        assert obj["run"]["solver"] == "sgd"

    def test_emitted_ledger_file_gates_clean_then_fails_when_seeded(
            self, tmp_path):
        """End-to-end of the CI wiring: serialize a real run's ledger, gate
        it (exit 0); re-emit with one seeded mis-prediction through the
        same Ledger API and the gate must exit 1."""
        _, _, tel = _streaming_als_run()
        clean = tmp_path / "LEDGER_clean.json"
        clean.write_text(json.dumps(tel.ledger))
        assert regress_main(["--ledger", str(clean)]) == 0

        bad = Ledger(**tel.ledger["run"])
        for rec in tel.ledger["records"]:
            predicted = rec["predicted"]
            if rec["name"] == "bytes_streamed":
                predicted += 4096       # the seeded planner bug
            bad.record(rec["name"], predicted, rec["measured"],
                       unit=rec["unit"], check=rec["check"],
                       rel_tol=rec["rel_tol"], severity=rec["severity"])
        seeded = tmp_path / "LEDGER_seeded.json"
        seeded.write_text(json.dumps(bad.to_obj()))
        assert regress_main(["--ledger", str(seeded)]) == 1

    def test_hybrid_ledger_merges_both_phases(self):
        from repro.core import als as als_mod
        from repro.core.partition import plan_for
        from repro.outofcore import (RatingStore, TileStore, build_schedule,
                                     build_sgd_schedule)
        from repro.sgd import SgdConfig, block_ell, run_streaming_hybrid
        from repro.sparse import synth

        spec = synth.SynthSpec("obs-hy", 96, 40, 1500, 8, 0.05)
        r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
        store = RatingStore(r, q=4)
        acc_eps = spec.n * (spec.f * spec.f + 3 * spec.f + 1) * 4
        plan = plan_for(spec.m, spec.n, r.nnz, spec.f, p=1, q=4, n_data=2,
                        fill=store.worst_fill, eps=acc_eps, buffers=4,
                        hbm_bytes=1 << 22)
        als_sched = build_schedule(plan, spec.m, spec.n, n_data=2)
        grid = block_ell(r, g=4)
        sgd_sched = build_sgd_schedule(grid, spec.f, n_workers=2)
        als_cfg = als_mod.AlsConfig(f=spec.f, lam=spec.lam, iters=1,
                                    mode="ref")
        sgd_cfg = SgdConfig(f=spec.f, lam=spec.lam, lr=0.1, epochs=1,
                            mode="ref", seed=1)
        _, _, tel = run_streaming_hybrid(store, als_sched, TileStore(grid),
                                         sgd_sched, als_cfg, sgd_cfg)
        obj = tel.ledger
        assert validate_ledger(obj)["ok"]
        names = {rec["name"] for rec in obj["records"]}
        assert any(n.startswith("als/") for n in names)
        assert any(n.startswith("sgd/") for n in names)
        assert obj["run"]["als"]["solver"] == "als"
        assert obj["run"]["sgd"]["solver"] == "sgd"


@pytest.mark.mesh
def test_mesh_ledger_reduce_records_exact():
    """The acceptance run: --mesh 2,2-equivalent streaming on 8 forced
    host devices emits a validating ledger whose reduce fast/slow wire
    bytes are exact records that hold."""
    from tests.test_distributed import run_script

    out = run_script("""
import json
from repro.core import als as als_mod
from repro.core.partition import plan_for, streaming_acc_bytes
from repro.launch.mesh import make_mesh
from repro.obs.ledger import validate_ledger
from repro.outofcore import RatingStore, build_schedule, run_streaming_als
from repro.sparse import synth

n_data, p, q = 2, 2, 4
spec = synth.SynthSpec('netflix-mesh', 2048, 512, 80_000, 16, 0.05)
r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
store = RatingStore(r, q=q, p=p)
plan = plan_for(spec.m, spec.n, r.nnz, spec.f, p=p, q=q, n_data=n_data,
                fill=store.worst_fill, eps=0, buffers=4,
                acc_bytes=streaming_acc_bytes(spec.n, spec.f))
sched = build_schedule(plan, spec.m, spec.n, n_data=n_data)
mesh = make_mesh((n_data, p), ('data', 'model'))
cfg = als_mod.AlsConfig(f=spec.f, lam=spec.lam, iters=2, mode='ref')
_, _, tel = run_streaming_als(store, sched, cfg, mesh=mesh)
obj = tel.ledger
summary = validate_ledger(obj)
assert summary['ok'], obj['flags']
recs = {rec['name']: rec for rec in obj['records']}
for name in ('reduce_fast_bytes', 'reduce_slow_bytes',
             'bytes_streamed', 'padded_slots', 'nnz_streamed'):
    assert recs[name]['check'] == 'exact', name
    assert recs[name]['ok'] and recs[name]['drift'] == 0.0, name
assert recs['reduce_fast_bytes']['measured'] == tel.reduce_fast_bytes
assert recs['reduce_slow_bytes']['measured'] == tel.reduce_slow_bytes
assert obj['run']['p'] == p and obj['run']['mesh'] is True
print('MESH_LEDGER_OK', summary['records'])
""")
    assert "MESH_LEDGER_OK" in out
