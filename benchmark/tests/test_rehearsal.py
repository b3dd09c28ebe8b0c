"""Every cell end to end on the CPU at 16 or 32 cells per chip, the
kernels in interpret mode, four virtual devices for the four-chip cell:
the program passes, and the control and each fault the cell can have
come out not correct."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def rehearse(workload, *extra, seconds="0.01", local="32", trace="0"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HEAT3D_DIRECT_INTERPRET="1")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "rehearse.py"), "--cpu",
         "--local", local, "--workload", workload, "--seed", "3000000011",
         "--seconds", seconds, "--trace", trace, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_cell_runs_and_is_correct(workload):
    out = rehearse(workload)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == CELLS[workload]["chips"]
    assert set(out["metrics"]) == {"gcell_per_s_per_chip", "setup_s"}
    assert list(out)[-1] == "checks"  # the numbers compared come last
    for c in out["checks"].values():
        assert c["value"] < c["limit"]


def test_traced_run_reads_per_layer_metrics_and_a_breakdown():
    out = rehearse("jacobi7-fp32-1024.sweep", trace="1", seconds="0.2")
    assert out["correct"]
    # the CPU has no device plane: only the set-up metric has something
    assert set(out["metrics"]) == {"setup.compile_s"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_the_control_fails(workload):
    """The program's own bf16 storage path where fp32 is stated."""
    out = rehearse(workload, "--dtype", "bf16")
    assert not out["correct"], out["checks"]


FAULTS = [(w, f) for w in sorted(CELLS)
          for f in ("unchanged", "half_steps", "altered")] + [
    ("jacobi7-fp32-2x2x1.sweep", "no_exchange")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    out = rehearse(workload, "--fault", fault)
    assert not out["correct"], out["checks"]
