"""The solver's ping-pong pair: ``HeatSolver3D`` keeps a spare field
buffer, and its programs write each update into the buffer the field is
not in, so no program copies the field back into the buffer it came in
(``models/heat3d.py`` ``_on_pair``; ``parallel/step.py``
``make_pair_multistep_fn`` and ``make_pair_step_fn``).

Fields and residuals are compared bit for bit with the single-buffer
programs, ``make_multistep_fn`` and ``make_step_fn`` on a fresh copy of the
field, on the route every benchmark cell runs: the direct kernels
(interpreted here) on aligned planes. On the four-device mesh the
reference is the single-buffer loop for the pair trips, then the odd
superstep and the remainder steps each as a program of its own
(``_reference``): there XLA:CPU rounds the faces-direct superstep inside
the single-buffer loop's one-trip loop about 1 ulp away from the same
superstep alone, in about 1.5% of the cells, and the pair program's
superstep is the one alone, bit for bit. On the padded-block jnp route
XLA:CPU fuses the two loops' bodies differently and rounds some cells of
an even count 1 ulp apart, so that route is not compared bit for bit.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat3d_tpu import obs
from heat3d_tpu.core import golden
from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
from heat3d_tpu.models.heat3d import HeatSolver3D
from heat3d_tpu.parallel.step import (
    make_multistep_fn,
    make_step_fn,
    make_superstep_fn,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# nz a whole lane tile, so the ring planes take the aligned layout, as at
# 1024^3; the (2,2,1) mesh gets the same local block
LOCAL = (16, 16, 128)
COUNTS = (0, 1, 2, 3, 9, 25, 50)
TBS = (1, 2)


def _cfg(tb, mesh=(1, 1, 1)):
    return SolverConfig(
        grid=GridConfig(shape=tuple(n * m for n, m in zip(LOCAL, mesh))),
        mesh=MeshConfig(shape=mesh),
        backend="auto",
        time_blocking=tb,
    )


def _writes(n, tb):
    """Out-of-place updates ``run(u, n)`` makes: n // k supersteps and
    n % k single steps."""
    return n // tb + n % tb


def _count(name, **labels):
    return obs.REGISTRY.counter(name).value(**labels)


def _reference(solver):
    """``(u, n) -> u`` on the single-buffer programs: ``make_multistep_fn``
    for the pair trips' updates, then the odd hot update and the ``n % k``
    single steps, each dispatched as a program of its own."""
    cfg, mesh, compute = solver.cfg, solver.mesh, solver._compute
    k = cfg.time_blocking
    loop = jax.jit(make_multistep_fn(cfg, mesh, compute))
    step = jax.jit(make_step_fn(cfg, mesh, compute))
    hot = jax.jit(make_superstep_fn(cfg, mesh, compute)) if k > 1 else step

    def run(u, n):
        u = loop(u, jnp.int32(n // k // 2 * 2 * k))
        for fn in (hot,) * (n // k % 2) + (step,) * (n % k):
            u = fn(u)
        return u

    return run


@pytest.fixture(scope="module")
def kernel_route():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HEAT3D_DIRECT_INTERPRET", "1")
        mp.delenv("HEAT3D_NO_DIRECT", raising=False)
        yield


@pytest.fixture(scope="module")
def solvers(kernel_route):
    """A solver per tb, with the single-buffer loop of its config and
    ``_reference``."""
    out = {}
    for tb in TBS:
        solver = HeatSolver3D(_cfg(tb))
        assert solver.route["kernel"] == "direct", solver.route
        assert solver.route["layout"] == "aligned", solver.route
        loop = jax.jit(make_multistep_fn(solver.cfg, solver.mesh,
                                         solver._compute))
        out[tb] = solver, loop, _reference(solver)
    return out


@pytest.mark.parametrize("n", COUNTS)
@pytest.mark.parametrize("tb", TBS)
def test_run_is_bit_identical_to_the_single_buffer_loop(solvers, tb, n):
    """``run(u, n)`` returns the field the single-buffer loop computes
    from the same input (and ``_reference``'s), deletes the donated input,
    and counts a pair swap exactly when it makes an odd number of
    writes."""
    solver, loop, reference = solvers[tb]
    u0 = golden.random_init(solver.cfg.grid.shape, seed=100 + n)
    want = np.asarray(loop(solver.init_state(u0), jnp.int32(n)))
    np.testing.assert_array_equal(
        np.asarray(reference(solver.init_state(u0), n)), want)
    u = solver.init_state(u0)
    swaps = _count("solver_pair_swaps_total", program="run")
    got = solver.run(u, n)
    assert u.is_deleted()
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (_count("solver_pair_swaps_total", program="run") - swaps
            == _writes(n, tb) % 2)


@pytest.mark.parametrize("residual", [False, True], ids=["step", "residual"])
@pytest.mark.parametrize("tb", TBS)
def test_step_programs_are_bit_identical_to_make_step_fn(solvers, tb,
                                                         residual):
    """``step`` and ``step_with_residual`` return the field (and the
    residual) of ``make_step_fn``'s program, each a swap of the pair."""
    solver = solvers[tb][0]
    u0 = golden.random_init(solver.cfg.grid.shape, seed=7)
    one = jax.jit(make_step_fn(solver.cfg, solver.mesh, solver._compute,
                               with_residual=residual))
    want = one(solver.init_state(u0))
    program = "step_residual" if residual else "step"
    swaps = _count("solver_pair_swaps_total", program=program)
    u = solver.init_state(u0)
    got = solver.step_with_residual(u) if residual else solver.step(u)
    assert u.is_deleted()
    assert _count("solver_pair_swaps_total", program=program) - swaps == 1
    if residual:
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        got, want = got[0], want[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_one_spare_serves_a_mixed_sequence(kernel_route):
    """A solver builds its spare once for a run of ``run`` and
    ``step_with_residual`` calls of either parity, and once more when a
    field of another shape comes in; the field's values follow the same
    sequence on the single-buffer programs."""
    solver = HeatSolver3D(_cfg(2))
    loop = jax.jit(make_multistep_fn(solver.cfg, solver.mesh,
                                     solver._compute))
    res = jax.jit(make_step_fn(solver.cfg, solver.mesh, solver._compute,
                               with_residual=True))
    allocs = _count("solver_spare_allocs_total")
    u0 = golden.random_init(solver.cfg.grid.shape, seed=3)
    u, want = solver.init_state(u0), solver.init_state(u0)
    for n in (9, 0, 25, 4, 1):
        u = solver.run(u, n)
        want = loop(want, jnp.int32(n))
        u, r2 = solver.step_with_residual(u)
        want, want_r2 = res(want)
        assert float(r2) == float(want_r2)
    u = solver.step(u)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(loop(want, 1)))
    assert _count("solver_spare_allocs_total") - allocs == 1
    other = jnp.zeros((8,) + LOCAL[1:], jnp.float32)
    solver.run(other, 3)
    assert _count("solver_spare_allocs_total") - allocs == 2


@pytest.mark.parametrize("tb", TBS)
def test_residual_step_sums_in_the_kernel(solvers, tb, monkeypatch):
    """On one shard with the direct kernel, the residual step sums the
    kernel's change partials (``heat3d_solver_residuals_total{source=
    "kernel"}``) and gives the field and residual the reduce path gives
    (``HEAT3D_NO_DIRECT``: the padded-block step, then
    ``residual_sumsq``)."""
    solver = solvers[tb][0]
    u0 = golden.random_init(solver.cfg.grid.shape, seed=17)
    before = _count("solver_residuals_total", source="kernel")
    got, got_r2 = solver.step_with_residual(solver.init_state(u0))
    assert _count("solver_residuals_total", source="kernel") - before == 1
    monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    reduce = HeatSolver3D(_cfg(tb))
    before = _count("solver_residuals_total", source="reduce")
    want, want_r2 = reduce.step_with_residual(reduce.init_state(u0))
    assert _count("solver_residuals_total", source="reduce") - before == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_r2), float(want_r2), rtol=1e-5)


def test_converge_stops_where_the_reduce_path_does(kernel_route,
                                                   monkeypatch):
    """``run_to_convergence`` with a residual every 10 steps takes its
    residual step from the kernel's partials and stops at the same step,
    with the same residual, as on the reduce path: 50 steps, where the
    residual first falls under 0.55 (0.592 at 40, 0.505 at 50)."""
    from heat3d_tpu.core.config import RunConfig
    from heat3d_tpu.parallel.step import make_converge_fn

    def converge():
        cfg = dataclasses.replace(_cfg(2), run=RunConfig(residual_every=10))
        solver = HeatSolver3D(cfg)
        u = solver.init_state(golden.random_init(cfg.grid.shape, seed=5))
        return solver, solver.run_to_convergence(u, 0.55, 200)

    solver, got = converge()
    assert make_step_fn(solver.cfg, solver.mesh, solver._compute,
                        with_residual=True).route["residual"] == "kernel"
    monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    reduce, want = converge()
    assert reduce._residual_source == "reduce"
    assert got.steps == want.steps == 50
    np.testing.assert_allclose(got.residual, want.residual, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.u), np.asarray(want.u),
                               rtol=1e-5, atol=1e-6)


def test_cli_timed_loop_compiles_nothing(capsys):
    """The CLI's warm-up compiles every loop executable its timed loop
    dispatches (one per write structure of a group's step count,
    ``HeatSolver3D.warm_up``): 23 steps in groups of 6 at tb=2 run
    the loop for 5 and for 4 steps, and no dispatch span inside
    ``run_loop`` carries ``compile_s``."""
    from heat3d_tpu.cli import main

    recs = obs.spans()
    mark = recs[-1]["id"] if recs else -1
    assert main([
        "--grid", "16", "--steps", "23", "--residual-every", "6",
        "--time-blocking", "2", "--backend", "jnp",
    ]) == 0
    recs = [r for r in obs.spans() if r["id"] > mark]
    (loop,) = [r for r in recs if r["name"] == "run_loop"]
    inside = [r for r in recs if r["name"] == "solver.dispatch"
              and loop["start_ns"] <= r["start_ns"] <= loop["end_ns"]]
    assert {(r["fields"]["program"], r["fields"]["steps"])
            for r in inside} == {("run", 5), ("run", 4), ("step_residual", 1)}
    assert not [r for r in inside if "compile_s" in r["fields"]]
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["steps"] == 23


# ---- the four-device CPU mesh ----------------------------------------------

_PROBE = r"""
import json
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from heat3d_tpu.core import golden
from heat3d_tpu.models.heat3d import HeatSolver3D
from heat3d_tpu.parallel.step import make_step_fn
from tests.test_solver_pair import COUNTS, TBS, _cfg, _count, _reference

out = {}
for tb in TBS:
    solver = HeatSolver3D(_cfg(tb, (2, 2, 1)), devices=jax.devices()[:4])
    out[f"route{tb}"] = solver.route
    reference = _reference(solver)
    for n in COUNTS:
        u0 = golden.random_init(solver.cfg.grid.shape, seed=200 + n)
        want = reference(solver.init_state(u0), n)
        u = solver.init_state(u0)
        swaps = _count("solver_pair_swaps_total", program="run")
        got = solver.run(u, n)
        out[f"run{tb}-{n}"] = {
            "equal": bool(np.array_equal(np.asarray(got), np.asarray(want))),
            "deleted": u.is_deleted(),
            "swaps": _count("solver_pair_swaps_total", program="run") - swaps,
        }
    u0 = golden.random_init(solver.cfg.grid.shape, seed=9)
    for residual in (False, True):
        one = jax.jit(make_step_fn(solver.cfg, solver.mesh, solver._compute,
                                   with_residual=residual))
        want = one(solver.init_state(u0))
        u = solver.init_state(u0)
        got = solver.step_with_residual(u) if residual else solver.step(u)
        if not residual:
            got, want = (got,), (want,)
        out[f"step{tb}-{residual}"] = {
            "equal": all(bool(np.array_equal(np.asarray(g), np.asarray(w)))
                         for g, w in zip(got, want)),
            "deleted": u.is_deleted(),
        }
    allocs = _count("solver_spare_allocs_total")
    sources = {s: _count("solver_residuals_total", source=s)
               for s in ("kernel", "reduce")}
    u = solver.init_state(u0)
    for n in (9, 25, 2):
        u = solver.run(u, n)
        u, _ = solver.step_with_residual(u)
    out[f"allocs{tb}-mixed"] = _count("solver_spare_allocs_total") - allocs
    out[f"residuals{tb}"] = {
        s: _count("solver_residuals_total", source=s) - n
        for s, n in sources.items()
    }
    u = solver.run(jax.device_put(u, NamedSharding(solver.mesh, P())), 3)
    u = solver.run(u, 3)
    out[f"allocs{tb}-resharded"] = _count("solver_spare_allocs_total") - allocs
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh221():
    """One subprocess on four CPU devices checks every case; the tests
    below read its results."""
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
        [sys.executable, "-c", _PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n", COUNTS)
@pytest.mark.parametrize("tb", TBS)
def test_run_on_the_2x2x1_cpu_mesh(mesh221, tb, n):
    """On (2,2,1), faces-direct route: ``run(u, n)`` equals
    ``_reference`` bit for bit, deletes its input, and swaps the pair
    after an odd number of writes."""
    assert mesh221[f"route{tb}"]["kernel"] == "direct"
    assert mesh221[f"run{tb}-{n}"] == {
        "equal": True, "deleted": True, "swaps": _writes(n, tb) % 2,
    }


@pytest.mark.parametrize("residual", [False, True], ids=["step", "residual"])
@pytest.mark.parametrize("tb", TBS)
def test_step_programs_on_the_2x2x1_cpu_mesh(mesh221, tb, residual):
    assert mesh221[f"step{tb}-{residual}"] == {"equal": True, "deleted": True}


@pytest.mark.parametrize("tb", TBS)
def test_spare_on_the_2x2x1_cpu_mesh(mesh221, tb):
    """The solver that already holds a spare builds none over a mixed
    sequence, and one for a field that comes in replicated instead of
    sharded."""
    assert mesh221[f"allocs{tb}-mixed"] == 0
    assert (mesh221[f"allocs{tb}-resharded"]
            == mesh221[f"allocs{tb}-mixed"] + 1)


@pytest.mark.parametrize("tb", TBS)
def test_residual_source_on_the_2x2x1_cpu_mesh(mesh221, tb):
    """On (2,2,1) the shell patches rewrite cells after the kernel, so
    the residual step keeps the reduce: three steps, three counts."""
    assert mesh221[f"residuals{tb}"] == {"kernel": 0, "reduce": 3}
