"""The harness finds everything by name, BENCHMARK.json keeps to its
contract, and the command refuses to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import REPO
from chipbench import harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + [
        c["name"] for c in BENCH["configs"]] + [
        w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "chipbench" / "traffic" /
                f"{w['traffic']}.json").exists()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").exists()
    for m in metrics:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_metrics(workload):
    cell = harness.load_cell(str(REPO), workload)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(str(REPO), m["name"]))
    limits = cell.config["check"]["limits"]
    assert limits and all(v > 0 for v in limits.values())


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus entries in BENCHMARK.json, with no other edit."""
    root = tmp_path
    shutil.copytree(REPO / "chipbench", root / "chipbench")
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((REPO / bench["configs"][0]["file"]).read_text())
    cfg["f"] = 7
    (root / "chipbench/configs/dummy_cfg.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/dummy_mix.json").write_text(
        json.dumps({"structure_seed": 5, "items": 0.3,
                    "users": 0.0, "noise": 0.1, "test_frac": 0.1,
                    "checkpoint": False}))
    (root / "chipbench/metrics/dummy_metric.py").write_text(
        "def read(w):\n    return 2.0 * w.iterations\n")
    bench["configs"].append({"name": "dummy_cfg", "source": "x",
                             "file": "chipbench/configs/dummy_cfg.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dummy_cfg.dummy_mix",
                               "config": "dummy_cfg",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "als_iter_s",
                               "workloads": ["dummy_cfg.dummy_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(str(root), "dummy_cfg.dummy_mix")
    assert cell.config["f"] == 7
    assert cell.traffic["structure_seed"] == 5
    assert "dummy_metric" in [m["name"] for m in cell.per_layer]
    read = harness.load_reader(str(root), "dummy_metric")
    assert read(harness.Window(3, 1.0, {}, None, 0.0, 0.0, 1.0, None,
                               None, None)) == 6.0
    other = harness.load_cell(str(root), BENCH["workloads"][0]["name"])
    assert "dummy_metric" not in [m["name"] for m in other.per_layer]
    with pytest.raises(harness.BenchError):
        harness.load_cell(str(root), "no_such.cell")


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks(str(REPO), "TPU v5 lite")["bf16_flops"] > 0
    with pytest.raises(harness.BenchError):
        harness.load_peaks(str(REPO), "cpu")


def _run_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run_cmd(REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout, p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the paths: no program."""
    for d in BENCH["paths"]:
        shutil.copytree(REPO / d, tmp_path / d)
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run_cmd(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout, p.stdout
