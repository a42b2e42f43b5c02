"""Every piece ``BENCHMARK.json`` names is found by name, and the
command refuses to run without a TPU or outside a full checkout."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["per_layer"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name(workload):
    cell = harness.load_cell(workload)
    wl = next(w for w in SPEC["workloads"] if w["name"] == workload)
    assert cell.config["name"] == wl["config"]
    assert cell.traffic["name"] == wl["traffic"]
    # every limit belongs to a number the comparison reads
    assert cell.limits and set(cell.limits) <= {
        "init_gap", "update1_gap", "err_gap"}
    # the cell's traffic keeps one client per group
    tr, pop = cell.traffic, cell.config["search"]["population"]
    assert round(tr["participation"] * tr["clients"]) % pop == 0


def test_config_files_as_listed():
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]


def test_unknown_workload_raises():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no-such-cell")


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_found_and_silent_without_data(metric):
    """A reader that finds nothing to read returns nothing, never 0."""
    read = harness.metric_reader(metric)
    empty = {"gens": 0, "window_s": 0.0, "spans": [], "trace": None,
             "flops": 0.0, "chips": 1, "calls": [],
             "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
             "config": harness.load_cell(WORKLOADS[0]).config}
    assert read(empty) is None


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_fails_without_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_command_fails_outside_a_checkout(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths
    lacks the system under test: no result, non-zero exit."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
