"""The 1D slab deployment, 1024^3 on mesh (4,1,1), at a small aligned
size: its route against the float64 golden stepper on the 4-device CPU
mesh, the ``heat3d.shell`` scope in its lowered superstep, and the device
order ``build_mesh`` gives it on a 2x2 host."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    SolverConfig,
    StencilConfig,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# local block (8, 16, 128): nz a whole lane tile, so the ring planes take
# the aligned layout as at 1024^3; bc_value 0.5 tells the edge chips from
# the two middle chips, which have two interior x faces
GRID = (32, 16, 128)
STEPS = 7  # three supersteps and the trailing single step
# fp32 rounding over 7 updates of values of order 1: a few ulps a step.
# bf16 storage rounds every stored update by ~4e-3 and fails it.
REL_TOL = 1e-5


def _cfg(tb=2, kind="7pt", bc=BoundaryCondition.DIRICHLET, **kw):
    return SolverConfig(
        grid=GridConfig(shape=GRID),
        stencil=StencilConfig(kind=kind, bc=bc, bc_value=0.5),
        mesh=MeshConfig(shape=(4, 1, 1)),
        backend="auto",
        time_blocking=tb,
        **kw,
    )


_PROBE = r"""
import json
import sys
import jax
import numpy as np
from heat3d_tpu.core import golden
from heat3d_tpu.core.config import BoundaryCondition, Precision
from heat3d_tpu.models.heat3d import HeatSolver3D
from tests.test_slab_cell import STEPS, _cfg

tb, kind, bc = int(sys.argv[1]), sys.argv[2], BoundaryCondition(sys.argv[3])
out = {}
u0 = golden.random_init(_cfg().grid.shape, seed=11)
for name, precision in (("fp32", Precision.fp32()), ("bf16", Precision.bf16())):
    cfg = _cfg(tb, kind, bc, precision=precision)
    solver = HeatSolver3D(cfg, devices=jax.devices()[:4])
    got = np.asarray(solver.run(solver.init_state(u0), STEPS), np.float64)
    want = golden.run(u0, cfg.grid, cfg.stencil, STEPS, impl="numpy")
    out[name] = {
        "rel_err": float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
        "route": solver.route,
        "mesh_ids": [d.id for d in solver.mesh.devices.flat],
    }
out["device_ids"] = [d.id for d in jax.devices()[:4]]
print(json.dumps(out))
"""


@pytest.mark.parametrize("tb,kind,bc", [
    pytest.param(tb, kind, bc, id=f"tb{tb}-{kind}-{bc.value}")
    for tb, kind, bc in [
        (2, "7pt", BoundaryCondition.DIRICHLET),
        (1, "7pt", BoundaryCondition.DIRICHLET),
        (2, "27pt", BoundaryCondition.PERIODIC),
        (1, "27pt", BoundaryCondition.PERIODIC),
    ]
])
def test_slab_route_matches_golden_on_cpu_mesh(tb, kind, bc):
    """HeatSolver3D.run on (4,1,1) takes the faces-direct route (direct2
    at tb=2, direct at tb=1) with aligned planes (its kernels
    interpreted) and matches the float64 golden stepper to fp32 rounding
    after an odd step count; bf16 storage does not. CPU devices keep
    their order in the mesh."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        HEAT3D_DIRECT_INTERPRET="1",
        PYTHONPATH=os.pathsep.join([REPO, os.environ.get("PYTHONPATH", "")]),
    )
    env.pop("HEAT3D_NO_DIRECT", None)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    p = subprocess.run(
        [sys.executable, "-c", _PROBE, str(tb), kind, bc.value],
        cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["fp32"]["route"] == {
        "backend": "jnp", "kernel": "direct", "interpret": True,
        "layout": "aligned",
        "precision": {
            "storage": "float32", "compute": "float32", "residual": "float32",
        },
    }
    assert out["fp32"]["rel_err"] < REL_TOL, out["fp32"]
    assert out["bf16"]["rel_err"] > REL_TOL, out["bf16"]
    assert out["fp32"]["mesh_ids"] == out["device_ids"]


def test_slab_superstep_lowers_shells_under_their_scope(monkeypatch):
    """Lowered for the TPU over the (4,1,1) mesh, the superstep's shell
    patches carry ``heat3d.shell`` in their location metadata, and the
    bulk kernel's custom call is still named ``heat3d.stencil.direct2``,
    outside that scope."""
    import re

    from heat3d_tpu.parallel.step import make_superstep_fn
    from heat3d_tpu.parallel.topology import abstract_mesh, lower_for_mesh

    monkeypatch.setenv("HEAT3D_DIRECT_FORCE", "1")
    monkeypatch.delenv("HEAT3D_DIRECT_INTERPRET", raising=False)
    monkeypatch.delenv("HEAT3D_NO_DIRECT", raising=False)
    cfg = _cfg()
    fn = make_superstep_fn(cfg, abstract_mesh(cfg.mesh))
    assert fn.route["kernel"] == "direct" and fn.route["layout"] == "aligned"
    text = lower_for_mesh(
        fn, cfg.mesh, (GRID, jnp.float32, P("x", "y", "z"))
    ).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def scopes(line):
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        return locs.get(ref.group(1), "") if ref else line

    updates = [ln for ln in text.splitlines()
               if "stablehlo.dynamic_update_slice" in ln]
    assert len(updates) == 2  # the low and high x shells
    assert all("heat3d.shell" in scopes(ln) for ln in updates), updates
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert calls and all("heat3d.stencil.direct2" in ln for ln in calls)
    assert not any("heat3d.shell" in scopes(ln) for ln in calls)


class _Chip:
    """A device as ``build_mesh`` sees it on a TPU host: an id and ICI
    coords."""

    def __init__(self, id, coords):
        self.id = id
        self.coords = coords


# a 2x2 host as its ids run: (0,0) (1,0) (0,1) (1,1)
HOST_2X2 = [_Chip(i, [x, y, 0]) for i, (x, y) in
            enumerate([(0, 0), (1, 0), (0, 1), (1, 1)])]


def _hops(a, b):
    return sum(abs(p - q) for p, q in zip(a.coords, b.coords))


@pytest.mark.parametrize("shape", [(4, 1, 1), (1, 4, 1), (1, 1, 4)])
def test_build_mesh_puts_chain_neighbours_one_hop_apart(shape):
    """In id order the chain 0-1-2-3 crosses the 2x2 host's diagonal
    between its second and third device; the mesh takes the ring order."""
    from heat3d_tpu.parallel.topology import build_mesh

    assert _hops(HOST_2X2[1], HOST_2X2[2]) == 2
    chain = list(build_mesh(MeshConfig(shape=shape), HOST_2X2).devices.flat)
    assert [d.id for d in chain] == [0, 1, 3, 2]
    assert all(_hops(a, b) == 1 for a, b in zip(chain, chain[1:]))


def test_build_mesh_keeps_an_order_that_already_fits():
    """(2,2,1) in id order has every neighbour pair one hop apart: kept."""
    from heat3d_tpu.parallel.topology import build_mesh

    mesh = build_mesh(MeshConfig(shape=(2, 2, 1)), HOST_2X2)
    assert [d.id for d in mesh.devices.flat] == [0, 1, 2, 3]


def test_devices_without_coords_keep_their_order():
    from heat3d_tpu.parallel.topology import build_mesh, ici_order

    bare = [type("Dev", (), {"id": i})() for i in range(4)]
    assert ici_order(bare, (4, 1, 1)) == bare
    one = jax.devices()[:1]
    assert list(build_mesh(MeshConfig(shape=(1, 1, 1)), one).devices.flat) \
        == one
