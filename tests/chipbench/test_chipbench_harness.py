"""BENCHMARK.json resolves, by name, to the files of every cell (and so
do the cells prepared under ``chipbench/prepared/``), and the entry
point refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.bench import Spec  # noqa: E402
from test_chipbench_faults import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = spec().data  # BENCHMARK.json and the prepared cells
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / SPEC["command"][1]).resolve().is_relative_to(
        ROOT / "chipbench")
    assert 1 <= SPEC["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        for entry in SPEC[section]:
            assert set(entry) - {"workloads"} == want, entry
            for k in ("why", "source", "layer"):
                if k in entry:
                    assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    s = spec()
    cell = s.cell(workload)
    entry = s.workload(workload)
    assert entry["chips"] in (1, 4)
    assert s.traffic_path(cell.traffic_name).is_file()
    assert s.limits_path(workload).is_file()
    assert s.driver_path(cell.driver).is_file()
    assert cell.config and cell.traffic and cell.limits
    assert hasattr(s.driver(cell), "measure")


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_resolves_and_moves_a_reported_metric(metric):
    s = spec()
    assert callable(s.reader(metric["name"]).read)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert metric["moves"] in e2e and metric["moves"] != "setup_s"
    for w in metric["workloads"]:
        names = [m["name"] for m in s.cell(w).end_to_end]
        assert metric["moves"] in names, (metric["name"], w)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    s = spec()
    for w in WORKLOADS:
        cell = s.cell(w)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_added_config_mix_and_metric_are_found_with_no_code_edit(tmp_path):
    for p in ("chipbench",):
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    data = dict(SPEC)
    (tmp_path / "chipbench/configs/tiny.json").write_text(
        json.dumps({"num_agents": 8}))
    (tmp_path / "chipbench/traffic/r8.json").write_text(
        json.dumps({"driver": "price", "rollouts": 8}))
    (tmp_path / "chipbench/limits/price.tiny.r8.json").write_text(
        json.dumps({"flow_completion_rel_err": 1e-9}))
    (tmp_path / "chipbench/metrics/price.extra.py").write_text(
        "def read(reading):\n    return 42.0\n")
    data["configs"] = SPEC["configs"] + [{
        "name": "tiny", "source": "x", "file": "chipbench/configs/tiny.json",
        "reduced": [], "why": "x"}]
    data["workloads"] = SPEC["workloads"] + [{
        "name": "price.tiny.r8", "config": "tiny", "traffic": "r8",
        "chips": 1, "why": "x"}]
    data["per_layer"] = SPEC["per_layer"] + [{
        "name": "price.extra", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "pricing kernel",
        "moves": "price_rollouts_per_s"}]
    data["end_to_end"] = [
        dict(m, workloads=m["workloads"] + ["price.tiny.r8"])
        if m["name"] == "price_rollouts_per_s" else m
        for m in SPEC["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    found = Spec(tmp_path)
    cell = found.cell("price.tiny.r8")
    assert cell.config == {"num_agents": 8}
    assert cell.traffic["rollouts"] == 8
    assert "price.extra" in [m["name"] for m in cell.per_layer]
    assert found.reader("price.extra").read(None) == 42.0
    assert found.driver(cell).__file__.startswith(str(tmp_path))
    # A per-layer metric with no workloads list goes to every cell that
    # reports the end-to-end metric it moves, and to no other.
    train = found.cell("train.qwen2-0.5b.solo")
    assert "price.extra" not in [m["name"] for m in train.per_layer]


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         WORKLOADS[0], "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    return any(line.strip().startswith("{") for line in stdout.splitlines())


def test_entry_point_refuses_a_cpu():
    out = _run_entry(ROOT)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "needs 1 TPU chip" in out.stderr


def test_entry_point_fails_without_the_program(tmp_path):
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_entry(tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
