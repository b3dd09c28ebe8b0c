"""The harness finds configurations, traffic, limits and metrics by name,
and refuses to run without a chip."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_every_cell_loads_with_its_files():
    harness = load(os.path.join(BENCH, "harness.py"), "h_all")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.limits, w["name"]
        assert cell.reference().check
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        for name in names:
            assert os.path.isfile(os.path.join(BENCH, "metrics",
                                               f"{name}.py")), name


def test_a_new_config_traffic_and_metric_are_found_by_adding_files(tmp_path):
    """A later PR adds a file of each kind and entries in BENCHMARK.json,
    and edits no file that is there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "jacobi7-fp32-1024.json")))
    cfg["solver"]["grid"] = [256, 256, 256]
    (root / "benchmark/configs/jacobi7-fp32-256.json").write_text(
        json.dumps(cfg))
    (root / "benchmark/traffic/sweep5.json").write_text(
        json.dumps({"chunk_steps": 5}))
    (root / "benchmark/limits/jacobi7-fp32-256.sweep5.json").write_text(
        json.dumps({"field_rel_err": {"limit": 1e-5}}))
    (root / "benchmark/metrics/window.dispatches.py").write_text(
        "def read(ctx):\n    return ctx.window.dispatches\n")
    spec["configs"].append({"name": "jacobi7-fp32-256", "source": "x",
                            "file": "benchmark/configs/jacobi7-fp32-256.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "jacobi7-fp32-256.sweep5",
                              "config": "jacobi7-fp32-256",
                              "traffic": "sweep5", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "window.dispatches", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "solver", "moves": "setup_s",
                              "workloads": ["jacobi7-fp32-256.sweep5"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    harness = load(str(root / "benchmark/harness.py"), "h_new")
    cell = harness.load_cell("jacobi7-fp32-256.sweep5", str(root))
    assert cell.config["solver"]["grid"] == [256, 256, 256]
    assert cell.traffic == {"chunk_steps": 5}
    assert cell.limits == {"field_rel_err": 1e-5}
    got = harness.read_metrics(cell.per_layer,
                               NS(window=NS(dispatches=7), trace=None))
    assert got["window.dispatches"] == {"value": 7.0, "unit": "count"}
    # a metric whose reader finds nothing is left out of the line
    assert "device.idle_share" not in got
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_an_unknown_workload_or_device_kind_is_an_error():
    harness = load(os.path.join(BENCH, "harness.py"), "h_err")
    with pytest.raises(harness.SpecError):
        harness.load_cell("no-such.cell")
    with pytest.raises(harness.SpecError):
        harness.device_peaks("TPU v9 imaginary")
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "jacobi7-fp32-1024.sweep", "--seed", "3000000007", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
