"""The harness finds every cell, configuration and metric from files alone,
refuses what it cannot measure, and each cell's run is correct at a tiny
size on the CPU (the measurement path itself refuses a non-TPU)."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
#: each configuration at a size a test run holds, offloading every call
TINY = {
    "parsec_si": {"ngrid": 2048, "nstates": 64, "nblock": 8,
                  "offload": {"threshold": 8}},
    "lsms_kkr_c64": {"n": 192, "nb": 48, "kkrsz": 8, "atoms_per_node": 2,
                     "offload": {"threshold": 8}},
}


@pytest.fixture(scope="module", autouse=True)
def x64():
    import jax
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def tiny_run(workload, trace=False, seed=2 ** 31 + 12345, **kw):
    config = run.cell(workload)["config"]
    return run.measure(workload, seed, 0.2, trace, overrides=TINY[config],
                       log=lambda _m: None, **kw)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_from_its_files(workload):
    wl = run.cell(workload)
    cfg = run.load_json("configs", wl["config"])
    app = run.load_module("apps", wl["config"])
    for fn in ("setup", "step", "close", "check", "flops_per_step",
               "control"):
        assert callable(getattr(app, fn))
    assert set(cfg["limits"]) and cfg["offload"]["policy"]
    entry = [c for c in BENCHMARK["configs"] if c["name"] == wl["config"]]
    assert entry and entry[0]["file"] == f"bench/configs/{wl['config']}.json"
    assert wl["why"] == [w for w in BENCHMARK["workloads"]
                         if w["name"] == workload][0]["why"]


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in BENCHMARK["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(run.load_module("metrics", metric).read)


def names(workload, per_layer):
    return [m["name"] for m in run.metrics_of(workload, per_layer)]


def test_metrics_of_a_cell_follow_their_workloads_key():
    assert "panel_share" in names("lsms_kkr_c64.atoms112", True)
    assert "panel_share" not in names("parsec_si.panel_reuse", True)
    assert names("parsec_si.panel_reuse", False) == ["step_s", "setup_s"]


def test_unknown_names_and_devices_are_errors():
    with pytest.raises(run.BenchError):
        run.cell("parsec_si.no_such_traffic")
    with pytest.raises(run.BenchError):
        run.load_module("metrics", "no_such_metric")
    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks("TPU v99 imaginary")
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_measurement_path_refuses_the_cpu():
    with pytest.raises(run.BenchError, match="no TPU"):
        run.chips(1)


def _bench(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result_and_nonzero_exit():
    out = _bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_is_correct_at_a_tiny_size(workload):
    line = tiny_run(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"step_s", "setup_s"}
    assert line["setup"]["window_compiles"] == 0
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_cell_reports_its_layers(workload):
    line = tiny_run(workload, trace=True)
    assert line["correct"], line["checks"]
    want = set(names(workload, per_layer=True))
    got = set(line["metrics"])
    # the roofline and the step's share of the peak need a chip's peaks
    assert got == want - {"gemm_roofline", "step_mfu"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10
