"""Launch the real 8-device distributed checks in a CPU-mesh subprocess.

JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count=8 provide a
genuine 8-device mesh. This is the moral equivalent of the reference
class's ``mpirun -np 8`` single-node oversubscription test (SURVEY.md §4).
"""

import os
import pytest
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _cpu_mesh_env(ndev: int) -> dict:
    """Env for a genuine ndev-device virtual CPU mesh subprocess: force
    the CPU platform, size the host device count, and put the repo root
    on PYTHONPATH."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE), env.get("PYTHONPATH", "")]
    )
    return env


def test_deep_tb_on_cpu_mesh_tier1():
    """Tier-1 (unmarked) deep-tb acceptance: the k=3 and k=4 supersteps
    match k sequential steps AND the fp64 golden oracle on a REAL
    4-device CPU mesh — cross-device width-k ppermutes and shrinking
    mid-ring fills executing, not compile-only — plus the streamk kernel
    (interpret tier) on the same meshes, certifying its domain-edge ring
    pinning distinguishes interior shards from domain edges. Focused
    subprocess (4 devices) so it fits the tier-1 budget; the full
    8-device battery stays @slow."""
    env = _cpu_mesh_env(4)
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(HERE, "multidevice_checks.py"),
            "deep_tb",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, (
        f"deep-tb multidevice check failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    assert "deep_tb_tier1 OK" in proc.stdout
    assert "deep_tb_streamk_interpret OK" in proc.stdout


@pytest.mark.slow
def test_multidevice_checks_on_cpu_mesh():
    env = _cpu_mesh_env(8)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "multidevice_checks.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"multidevice checks failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "ALL MULTIDEVICE CHECKS PASSED" in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize(
    "ndev,mesh,kind,dtype",
    [
        (64, (4, 4, 4), "27pt", "fp32"),   # judged config 4 topology
        (128, (8, 4, 4), "7pt", "bf16"),   # judged config 5 topology
    ],
)
def test_judged_pod_topology_executes(ndev, mesh, kind, dtype):
    """EXECUTE (not just lower) the judged pod decompositions: a full
    distributed step over 64/128 virtual CPU devices at tiny scale must
    match the same grid run undecomposed. Upgrades configs 4-5 from
    compile-only evidence (docs/LOWERING.md) to executed evidence —
    bounded by host memory only because the blocks are tiny."""
    env = _cpu_mesh_env(ndev)
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from heat3d_tpu.core.config import (BoundaryCondition, GridConfig,
    MeshConfig, Precision, SolverConfig, StencilConfig)
from heat3d_tpu.parallel.step import make_step_fn
from heat3d_tpu.parallel.topology import build_mesh, field_sharding

mesh_shape = {mesh!r}
grid = tuple(4 * m for m in mesh_shape)
prec = Precision.bf16() if {dtype!r} == "bf16" else Precision.fp32()
host = np.random.default_rng(0).standard_normal(grid).astype(np.float32)

outs = {{}}
for shape in (mesh_shape, (1, 1, 1)):
    cfg = SolverConfig(grid=GridConfig(shape=grid),
        stencil=StencilConfig(kind={kind!r}, bc=BoundaryCondition.PERIODIC),
        mesh=MeshConfig(shape=shape), precision=prec, backend="jnp")
    m = build_mesh(cfg.mesh, devices=jax.devices()[: cfg.mesh.num_devices])
    step = jax.jit(make_step_fn(cfg, m, with_residual=True))
    u = jax.device_put(jnp.asarray(host, jnp.dtype(prec.storage)),
                       field_sharding(m, cfg.mesh))
    un, r = jax.block_until_ready(step(u))
    outs[shape] = (np.asarray(un.astype(jnp.float32)), float(r))

got, r_got = outs[mesh_shape]
want, r_want = outs[(1, 1, 1)]
np.testing.assert_array_equal(got, want)  # same math, same op order
np.testing.assert_allclose(r_got, r_want, rtol=1e-5)
print(f"POD TOPOLOGY OK: {{mesh_shape}} over {ndev} devices == (1,1,1)")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"pod-topology check failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    assert "POD TOPOLOGY OK" in proc.stdout
