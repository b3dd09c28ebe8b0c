"""The bf16 cell's configuration (``benchmark/configs/jacobi7-bf16-1024
.json``: bf16 storage, float32 stencil arithmetic and residual) on the
CPU, cut to a (16, 32, 128) grid, built and seeded by ``benchmark/run.py``
from its ``heat3d`` CLI settings, its direct kernels interpreted. One group
of the ``monitor50`` traffic, ``run(u, 49)`` then ``step_with_residual``,
passes the cell's limits against the storage-rounding reference
(``benchmark/references/jacobi_bf16.py``); the control, the stencil
arithmetic in bf16 (``--compute-dtype bf16``), fails one of them."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat3d_tpu import obs
from heat3d_tpu.core.config import GridConfig, Precision, SolverConfig
from heat3d_tpu.models.heat3d import HeatSolver3D

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "jacobi7-bf16-1024.monitor50"
GRID = [16, 32, 128]  # nz a whole lane tile: the aligned layout, as at 1024


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # run.py's dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


RUN = _load(os.path.join(BENCH, "run.py"), "bf16_cell_bench_run")
CONFIG = _json("configs", "jacobi7-bf16-1024.json")
LIMITS = {k: v["limit"] for k, v in _json("limits", f"{CELL}.json").items()}
EVERY = _json("traffic", "monitor50.json")["residual_every"]


def _solver(tb, **settings):
    """The solver ``run.py`` builds from the cell's configuration cut to
    GRID, at time-blocking ``tb``, with ``settings`` over its CLI
    settings; and that configuration."""
    config = dict(CONFIG, solver=dict(CONFIG["solver"], grid=GRID,
                                      time_blocking=tb, **settings))
    return RUN.build_solver(config, jax.devices()[:1]), config


def _group_checks(solver, config, seed):
    """One monitor50 group from the cell's seeded field, judged by the
    reference from the field kept before it."""
    u = RUN.make_field(solver, config, seed)
    kept = jax.jit(jnp.copy)(u)
    u = solver.run(u, EVERY - 1)
    u, r2 = solver.step_with_residual(u)
    reference = _load(
        os.path.join(BENCH, "references", f"{config['reference']}.py"),
        "bf16_cell_reference",
    )
    return reference.check(config, kept, u, EVERY, float(r2))


@pytest.mark.parametrize("tb", [1, 2])
def test_bf16_cell_passes_its_limits(monkeypatch, tb):
    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    monkeypatch.delenv("HEAT3D_NO_DIRECT", raising=False)
    solver, config = _solver(tb)
    route = solver.route
    assert (route["kernel"], route["layout"]) == ("direct", "aligned")
    assert route["precision"] == {
        "storage": "bfloat16", "compute": "float32", "residual": "float32",
    }
    found = _group_checks(solver, config, seed=3000000013 + tb)
    assert set(found) == set(LIMITS)
    for name, limit in LIMITS.items():
        assert found[name] <= limit, (name, found)


@pytest.mark.parametrize("tb", [1, 2])
def test_bf16_cell_control_fails_a_limit(monkeypatch, tb):
    """The stencil arithmetic in bf16, a lower precision than the
    configuration states: some number lies past its limit."""
    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    monkeypatch.delenv("HEAT3D_NO_DIRECT", raising=False)
    solver, config = _solver(tb, compute_dtype="bf16")
    assert solver.route["precision"]["compute"] == "bfloat16"
    found = _group_checks(solver, config, seed=3000000013 + tb)
    assert any(not found[name] <= limit for name, limit in LIMITS.items()), \
        found


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_route_and_dispatch_spans_read_the_precision(precision):
    """``solver.route["precision"]`` holds the config's dtypes, and every
    ``solver.dispatch`` span the field's storage dtype."""
    p = getattr(Precision, precision)()
    solver = HeatSolver3D(
        SolverConfig(grid=GridConfig.cube(8), backend="jnp", precision=p),
        devices=jax.devices()[:1],
    )
    assert solver.route["precision"] == {
        "storage": p.storage, "compute": p.compute, "residual": p.residual,
    }
    recs = obs.spans()
    mark = recs[-1]["id"] if recs else -1
    u = solver.run(solver.init_state("hot-cube"), 3)
    u, _ = solver.step_with_residual(u)
    np.asarray(solver.step(u))
    dispatches = [r for r in obs.spans()
                  if r["id"] > mark and r["name"] == "solver.dispatch"]
    assert [r["fields"]["program"] for r in dispatches] == [
        "run", "step_residual", "step",
    ]
    assert {r["fields"]["dtype"] for r in dispatches} == {p.storage}
