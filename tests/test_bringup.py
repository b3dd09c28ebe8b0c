"""Bring-up contracts that hold off the chip: the entry points refuse to
run on the CPU instead of reporting a CPU run as a chip run, the compile
cache lands where the entry points put it, health and device counts come
from this process, and an unknown chip is an error, never another chip's
figure."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "script", ["chip_smoke.py", "bench.py", "__graft_entry__.py"]
)
def test_entry_point_fails_off_the_chip(script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "HEAT3D_BENCH_GRID": "8"}
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert "gcell" not in out.stdout.lower()
    assert "TPU" in out.stderr


def test_chip_smoke_fails_without_the_repository(tmp_path):
    """Alone in a directory, the script cannot import the program."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
    )
    assert out.returncode != 0 and '"ok"' not in out.stdout


@pytest.fixture
def restore_cache_dir():
    import jax

    prior = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prior)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    import jax

    from heat3d_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_honors_env(monkeypatch, tmp_path, restore_cache_dir):
    import jax

    from heat3d_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing else
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """End to end in a fresh process: the CLI's compiles are written
    under ``JAX_COMPILATION_CACHE_DIR``."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "PYTHONPATH": REPO,
    }
    out = subprocess.run(
        [sys.executable, "-m", "heat3d_tpu", "--grid", "8", "--steps", "2",
         "--backend", "jnp"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert os.listdir(tmp_path)


def test_device_health_is_in_process():
    import jax

    from heat3d_tpu.utils.timing import device_health

    assert device_health() == ("cpu", len(jax.devices()))


def test_supervisor_and_elastic_probe_this_process():
    import jax

    from heat3d_tpu.resilience.elastic import probe_survivors
    from heat3d_tpu.resilience.supervisor import _default_probe

    assert _default_probe(None) == "cpu"
    assert _default_probe("tpu") is None
    assert probe_survivors() == len(jax.devices())


def test_vmem_budget_unknown_tpu_generation_is_an_error():
    from heat3d_tpu.analysis import vmem

    assert vmem.chip_vmem_budget_for("tpu-v5-lite") == 16 * 1024 * 1024
    assert vmem.chip_vmem_budget_for("cpu") == min(
        vmem.CHIP_VMEM_BYTES.values()
    )
    with pytest.raises(ValueError, match="no VMEM budget"):
        vmem.chip_vmem_budget_for("tpu-v9-imaginary")


def test_peaks_keyed_by_device_kind():
    from heat3d_tpu.obs.perf.roofline import peak_spec

    v5e = peak_spec("TPU v5 lite")
    assert v5e["mem_gbps"] == 819.0 and v5e["bf16_tflops"] == 197.0
    assert "TPU v5e" in v5e["source"]
    other = peak_spec("TPU v9 imaginary")
    assert other["mem_gbps"] is None and other["bf16_tflops"] is None


def test_cli_summary_records_resolved_route(capsys):
    from heat3d_tpu import cli

    assert cli.main(["--grid", "8", "--steps", "2"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # 'auto' off the chip: the jnp padded-block compute, no kernel
    assert summary["route"] == {
        "backend": "jnp", "kernel": None, "interpret": False, "layout": None,
        "precision": {
            "storage": "float32", "compute": "float32", "residual": "float32",
        },
    }
    assert summary["device_kind"] == "cpu"
    assert summary["warmup_s"] > 0
