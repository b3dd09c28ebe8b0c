"""Multi-level & implicit time integration (heat3d_tpu.timeint;
docs/INTEGRATORS.md): the wave family's leapfrog two-level carry (MMS
convergence order, reference-step parity, superstep consistency), the
matrix-free CG backward-Euler solve beyond the explicit CFL bound,
variable-coefficient flux fields, integrator threading through cache
keys / bench rows / provenance / regress / sweep journals / serve
buckets, and multi-level checkpoint semantics — plus the 4-device
CPU-mesh timeint battery subprocess (dist==solo bitwise, two-level
supervised resume, coef-field serve packing).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from heat3d_tpu import eqn, timeint
from heat3d_tpu.core import golden
from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.timeint import cg, coeffield, leapfrog

HERE = os.path.dirname(os.path.abspath(__file__))


def _wave_cfg(n=16, dt=0.01, tb=1, bc=BoundaryCondition.PERIODIC,
              bc_value=0.0, c=1.0, **kw):
    return SolverConfig(
        grid=GridConfig(shape=(n, n, n), dt=dt,
                        spacing=(1.0 / n, 1.0 / n, 1.0 / n)),
        stencil=StencilConfig(kind="7pt", bc=bc, bc_value=bc_value),
        equation="wave",
        eq_params=(("c", c),),
        integrator="leapfrog",
        backend="jnp",
        halo="ppermute",
        time_blocking=tb,
        **kw,
    )


def _cg_cfg(n=16, dt_mult=10.0, bc=BoundaryCondition.PERIODIC,
            bc_value=0.0, **kw):
    cfg = SolverConfig(
        grid=GridConfig(shape=(n, n, n),
                        spacing=(1.0 / n, 1.0 / n, 1.0 / n)),
        stencil=StencilConfig(kind="7pt", bc=bc, bc_value=bc_value),
        integrator="implicit-cg",
        backend="jnp",
        halo="ppermute",
        **kw,
    )
    return dataclasses.replace(
        cfg,
        grid=dataclasses.replace(cfg.grid,
                                 dt=dt_mult * cfg.grid.stable_dt()),
    )


def _mesh1(cfg):
    from heat3d_tpu.parallel.topology import build_mesh

    return build_mesh(cfg.mesh)


# ---- the registry -----------------------------------------------------------


def test_carry_levels():
    assert timeint.carry_levels("leapfrog") == 2
    assert timeint.carry_levels("explicit-euler") == 1
    assert timeint.carry_levels("implicit-cg") == 1


def test_pin_config_resolves_auto_knobs():
    """Non-default integrators never run the explicit-route tuner: auto
    knobs pin to the jnp + ppermute + tb=1 certified route."""
    cfg = dataclasses.replace(
        _wave_cfg(), backend="auto", halo="auto", time_blocking=0)
    pinned = timeint.pin_config(cfg)
    assert pinned.backend == "jnp"
    assert pinned.halo == "ppermute"
    assert pinned.time_blocking == 1
    already = _wave_cfg()
    assert timeint.pin_config(already) is already  # no-op fast path


def test_validate_config_rejections():
    with pytest.raises(ValueError, match="backend must be 'jnp'"):
        timeint.validate_config(
            dataclasses.replace(_wave_cfg(), backend="pallas"))
    with pytest.raises(ValueError, match="halo must be 'ppermute'"):
        timeint.validate_config(
            dataclasses.replace(_wave_cfg(), halo="dma"))
    with pytest.raises(ValueError, match="time_blocking=1"):
        timeint.validate_config(
            dataclasses.replace(_cg_cfg(), time_blocking=2))
    with pytest.raises(ValueError, match="overlap"):
        timeint.validate_config(
            dataclasses.replace(_wave_cfg(), overlap=True))


def test_family_integrator_coupling():
    """wave <-> leapfrog is config-time validation; implicit-cg is
    restricted to symmetric (CG_FAMILIES) operators."""
    with pytest.raises(ValueError, match="leapfrog"):
        dataclasses.replace(_wave_cfg(), integrator="explicit-euler")
    with pytest.raises(ValueError, match="first order"):
        SolverConfig(
            grid=GridConfig.cube(8, dt=0.01),
            integrator="leapfrog",
            backend="jnp",
            halo="ppermute",
        )
    with pytest.raises(ValueError, match="symmetry"):
        SolverConfig(
            grid=GridConfig.cube(8, dt=0.01),
            equation="advection-diffusion",
            integrator="implicit-cg",
            backend="jnp",
            halo="ppermute",
        )
    _wave_cfg()  # the legal pairing constructs


# ---- leapfrog ---------------------------------------------------------------


def test_leapfrog_step_matches_reference():
    """One sharded-builder step == the fp64 full-grid reference (pad +
    27 taps − u_prev), and the carry rotation (u_new, u) is copy-free:
    level 1 of the output is BITWISE the input's level 0."""
    import jax

    cfg = _wave_cfg(n=12, bc=BoundaryCondition.DIRICHLET, bc_value=0.1)
    rng = np.random.default_rng(3)
    u0 = rng.standard_normal((12, 12, 12)).astype(np.float32)
    um1 = rng.standard_normal((12, 12, 12)).astype(np.float32)
    step = jax.jit(timeint.make_step_fn(cfg, _mesh1(cfg)))
    out = step((u0, um1))
    taps = leapfrog.leapfrog_taps(cfg)
    want = leapfrog.reference_step(u0, um1, taps, periodic=False,
                                   bc_value=0.1)
    rel = np.max(np.abs(np.asarray(out[0], np.float64) - want)) / max(
        float(np.max(np.abs(want))), 1e-30)
    assert rel < 1e-5, f"leapfrog step vs fp64 reference rel {rel:.2e}"
    assert np.array_equal(np.asarray(out[1]), u0), "carry rotation"


def test_leapfrog_multistep_and_superstep_consistency():
    """The device-side multistep loop == the single step applied k times,
    and a tb=2 superstep (shrinking-ring recompute over the two-level
    k*r/(k-1)*r ghost plan) == two plain steps — to within f32 FMA
    association (XLA contracts the fori_loop body differently from the
    standalone step; the BITWISE program-equivalence contract is
    certified at f64 compute by the 4-device battery below)."""
    import jax
    import jax.numpy as jnp

    def _close(a, b, what):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        rel = np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30)
        assert rel < 1e-6, f"{what}: rel {rel:.2e}"

    cfg = _wave_cfg(n=12, bc=BoundaryCondition.DIRICHLET, bc_value=0.1)
    rng = np.random.default_rng(4)
    u0 = rng.standard_normal((12, 12, 12)).astype(np.float32)
    um1 = rng.standard_normal((12, 12, 12)).astype(np.float32)
    mesh = _mesh1(cfg)
    step = jax.jit(timeint.make_step_fn(cfg, mesh))
    ms = jax.jit(timeint.make_multistep_fn(cfg, mesh))
    c_loop = (u0, um1)
    for _ in range(5):
        c_loop = step(c_loop)
    c_ms = ms((u0, um1), jnp.int32(5))
    for lvl in (0, 1):
        _close(c_ms[lvl], c_loop[lvl], f"multistep level {lvl}")

    cfg2 = dataclasses.replace(cfg, time_blocking=2)
    ss = jax.jit(leapfrog.make_superstep_fn(cfg2, _mesh1(cfg2)))
    c_ss = ss((u0, um1))
    c_2 = step(step((u0, um1)))
    for lvl in (0, 1):
        _close(c_ss[lvl], c_2[lvl], f"superstep level {lvl}")


def test_leapfrog_mms_order2():
    """Second-order convergence on the wave family's plane-wave MMS:
    u = sin(k.x - omega t) with omega = c|k| (zero decay), dt ∝ h, so
    halving h must shrink the error ~4x (gate > 2.7). The fp64 reference
    step IS the builder's oracle (test_leapfrog_step_matches_reference),
    so the order transfers to the sharded program."""
    errs = []
    for n in (12, 24):
        shape = (n, n, n)
        spacing = (1.0 / n, 1.0 / n, 1.0 / n)
        dt = 1.0 / (4 * n)  # 0.25h — inside the 1/(c*sqrt(3))h bound
        cfg = _wave_cfg(n=n, dt=dt)
        k = golden.wavevector(shape, spacing, (1, 1, 0))
        mu, omega = eqn.mms_rates(cfg, k)
        assert mu == 0.0  # waves propagate, they do not decay
        taps = leapfrog.leapfrog_taps(cfg)
        u = golden.plane_wave(shape, spacing, (1, 1, 0))
        u_prev = golden.plane_wave(shape, spacing, (1, 1, 0), t=-dt,
                                   mu=mu, omega=omega)
        steps = 2 * n  # t_end = 0.5 exactly, at every resolution
        for _ in range(steps):
            u, u_prev = (
                leapfrog.reference_step(u, u_prev, taps, periodic=True),
                u,
            )
        want = golden.plane_wave(shape, spacing, (1, 1, 0),
                                 t=steps * dt, mu=mu, omega=omega)
        errs.append(np.max(np.abs(u - want)))
    ratio = errs[0] / max(errs[1], 1e-300)
    assert ratio > 2.7, f"leapfrog wave MMS not order 2: {errs} ({ratio:.2f})"


def test_wave_stable_dt_bound():
    """The wave family's CFL bound dt <= 1/(c*sqrt(sum 1/h^2)) drives the
    default dt; a leapfrog run at the bound stays bounded."""
    cfg = _wave_cfg(n=8, dt=None)
    dt = cfg.grid.effective_dt()
    n = 8
    want = 1.0 / (1.0 * np.sqrt(3.0 * n * n))
    assert dt <= want * (1 + 1e-12)


# ---- implicit CG ------------------------------------------------------------


def test_cg_step_matches_reference_and_converges():
    """One backward-Euler solve at 10x the explicit CFL bound matches the
    fp64 full-grid CG oracle (Dirichlet: boundary inflow enters via the
    zero-field trick), converges inside the iteration cap, and reports a
    psum-replicated relative residual under tol."""
    import jax

    cfg = _cg_cfg(n=12, dt_mult=10.0, bc=BoundaryCondition.DIRICHLET,
                  bc_value=0.5)
    rng = np.random.default_rng(5)
    u0 = rng.uniform(0.0, 1.0, (12, 12, 12)).astype(np.float32)
    step = jax.jit(cg.make_step_fn(cfg, _mesh1(cfg), with_stats=True))
    u1, iters, relres = step(u0)
    want = cg.reference_solve(u0, eqn.solver_taps(cfg), periodic=False,
                              bc_value=0.5)
    err = np.max(np.abs(np.asarray(u1, np.float64) - want))
    assert err < 5e-5, f"CG solve vs fp64 oracle err {err:.2e}"
    assert 1 <= int(iters) <= 64
    assert 0.0 <= float(relres) < 1e-5

    cfg_p = _cg_cfg(n=12, dt_mult=10.0)
    u1p = jax.jit(cg.make_step_fn(cfg_p, _mesh1(cfg_p)))(u0)
    want_p = cg.reference_solve(u0, eqn.solver_taps(cfg_p), periodic=True)
    err_p = np.max(np.abs(np.asarray(u1p, np.float64) - want_p))
    assert err_p < 5e-5, f"periodic CG solve err {err_p:.2e}"


def test_cg_mms_order2():
    """Backward Euler is O(dt) in time + O(h^2) in space; with dt ∝ h^2
    the total error is O(h^2) against the heat family's decaying
    plane-wave MMS — halving h must shrink the error ~4x (gate > 2.7)."""
    import jax
    import jax.numpy as jnp

    errs = []
    t_end = 1.0 / 36.0
    for n in (12, 24):
        shape = (n, n, n)
        spacing = (1.0 / n, 1.0 / n, 1.0 / n)
        dt = (1.0 / n) ** 2 / 6.0  # == the explicit bound, ∝ h^2
        cfg = _cg_cfg(n=n)
        cfg = dataclasses.replace(
            cfg, grid=dataclasses.replace(cfg.grid, dt=dt))
        steps = int(round(t_end / dt))
        assert abs(steps * dt - t_end) < 1e-12
        k = golden.wavevector(shape, spacing, (1, 1, 0))
        mu, omega = eqn.mms_rates(cfg, k)
        assert omega == 0.0 and mu > 0.0  # heat decays, it does not travel
        u0 = golden.plane_wave(shape, spacing, (1, 1, 0)).astype(np.float32)
        ms = jax.jit(timeint.make_multistep_fn(cfg, _mesh1(cfg)))
        u, _, _ = ms(u0, jnp.int32(steps))
        want = golden.plane_wave(shape, spacing, (1, 1, 0), t=t_end, mu=mu)
        errs.append(np.max(np.abs(np.asarray(u, np.float64) - want)))
    ratio = errs[0] / max(errs[1], 1e-300)
    assert ratio > 2.7, f"implicit-cg MMS not order 2: {errs} ({ratio:.2f})"


def test_cg_env_knobs(monkeypatch):
    monkeypatch.setenv("HEAT3D_CG_MAX_ITERS", "7")
    monkeypatch.setenv("HEAT3D_CG_TOL", "1e-3")
    assert cg.cg_settings() == (7, 1e-3)
    monkeypatch.delenv("HEAT3D_CG_MAX_ITERS")
    monkeypatch.delenv("HEAT3D_CG_TOL")
    assert cg.cg_settings() == (64, 1e-6)


def test_run_to_convergence_rejects_nonexplicit():
    from heat3d_tpu.models.heat3d import HeatSolver3D

    s = HeatSolver3D(_cg_cfg(n=8))
    u = s.init_state("hot-cube")
    with pytest.raises(ValueError, match="explicit-euler"):
        s.run_to_convergence(u, 1e-6, 10)


def test_solver_run_emits_cg_solve_event(tmp_path):
    """Every implicit-cg run() lands a cg_solve ledger event carrying the
    LAST solve's psum-replicated iteration count and relative residual —
    the stiff-dt convergence audit trail."""
    from heat3d_tpu import obs
    from heat3d_tpu.models.heat3d import HeatSolver3D

    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    try:
        s = HeatSolver3D(_cg_cfg(n=8, dt_mult=10.0))
        s.run(s.init_state("hot-cube"), 2)
    finally:
        obs.deactivate()
    with open(led) as fh:
        evs = [json.loads(line) for line in fh if line.strip()]
    solves = [e for e in evs if e.get("event") == "cg_solve"]
    assert solves, "no cg_solve event from an implicit-cg run"
    last = solves[-1]
    assert last["steps"] == 2
    assert 1 <= last["cg_iters"] <= 64
    assert 0.0 <= last["cg_relres"] < 1e-5


# ---- the two-level carry's state surfaces -----------------------------------


def test_leapfrog_init_state_levels():
    from heat3d_tpu.models.heat3d import HeatSolver3D

    s = HeatSolver3D(_wave_cfg(n=8))
    carry = s.init_state("hot-cube")
    assert isinstance(carry, tuple) and len(carry) == 2
    a0, a1 = np.asarray(carry[0]), np.asarray(carry[1])
    assert np.array_equal(a0, a1)  # cold start at rest
    assert carry[0] is not carry[1]  # distinct buffers (donation-safe)

    rng = np.random.default_rng(6)
    u0 = rng.standard_normal((8, 8, 8)).astype(np.float32)
    um1 = rng.standard_normal((8, 8, 8)).astype(np.float32)
    carry2 = s.init_state((u0, um1))
    assert np.array_equal(np.asarray(carry2[0]), u0)
    assert np.array_equal(np.asarray(carry2[1]), um1)
    with pytest.raises(ValueError, match="2 levels"):
        s.init_state((u0, um1, u0))


def test_multilevel_checkpoint_roundtrip_and_mismatch(tmp_path):
    """A leapfrog checkpoint writes one sub-level per carry level and
    round-trips BOTH levels bitwise; loading across integrators (either
    direction) raises MultiLevelCheckpointError BEFORE any shard read."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    cfg = _wave_cfg(n=8)
    s = HeatSolver3D(cfg)
    rng = np.random.default_rng(8)
    u0 = rng.standard_normal((8, 8, 8)).astype(np.float32)
    um1 = rng.standard_normal((8, 8, 8)).astype(np.float32)
    carry = s.init_state((u0, um1))
    path = str(tmp_path / "wave-ck")
    s.save_checkpoint(path, carry, 5)
    assert os.path.isdir(os.path.join(path, "level-1"))

    got, step = HeatSolver3D(cfg).load_checkpoint(path)
    assert step == 5
    assert np.array_equal(np.asarray(got[0]), u0)
    assert np.array_equal(np.asarray(got[1]), um1)

    cfg_exp = SolverConfig(
        grid=cfg.grid, stencil=cfg.stencil, mesh=cfg.mesh,
        backend="jnp", halo="ppermute",
    )
    with pytest.raises(timeint.MultiLevelCheckpointError, match="2 field"):
        HeatSolver3D(cfg_exp).load_checkpoint(path)

    path2 = str(tmp_path / "heat-ck")
    es = HeatSolver3D(cfg_exp)
    es.save_checkpoint(path2, es.init_state("hot-cube"), 3)
    with pytest.raises(timeint.MultiLevelCheckpointError, match="1 field"):
        HeatSolver3D(cfg).load_checkpoint(path2)


# ---- coefficient fields -----------------------------------------------------


def test_coef_field_initializers_and_bound():
    for name in coeffield.COEF_FIELDS:
        a = coeffield.make_coef_field(name, (8, 8, 8), seed=2)
        assert a.shape == (8, 8, 8) and a.dtype == np.float64
        assert float(a.min()) >= 0.5 - 1e-12
        assert float(a.max()) <= 1.5 + 1e-12
    with pytest.raises(ValueError):
        coeffield.make_coef_field("nope", (8, 8, 8))
    n = 8
    sp = (1.0 / n,) * 3
    want = 1.0 / (2.0 * 1.5 * sum(1.0 / h / h for h in sp))
    assert abs(coeffield.varcoef_stable_dt(1.5, sp) - want) < 1e-15


def test_varcoef_multistep_matches_reference():
    """The sharded flux-form update tracks the fp64 full-grid oracle; a
    uniform field reproduces the wave of constant-alpha diffusion the
    repo grew up on (same operator, float association aside)."""
    import jax
    import jax.numpy as jnp

    n = 10
    cfg = SolverConfig(
        grid=GridConfig(shape=(n, n, n), dt=5e-4,
                        spacing=(1.0 / n,) * 3),
        stencil=StencilConfig(kind="7pt", bc=BoundaryCondition.PERIODIC),
        backend="jnp",
        halo="ppermute",
    )
    rng = np.random.default_rng(9)
    u0 = rng.standard_normal((n, n, n)).astype(np.float32)
    a = coeffield.make_coef_field("layered", (n, n, n),
                                  seed=3).astype(np.float32)
    ms = jax.jit(coeffield.make_varcoef_multistep_fn(cfg, _mesh1(cfg)))
    got = np.asarray(ms(u0, a, jnp.int32(4)), np.float64)
    ref = u0.astype(np.float64)
    for _ in range(4):
        ref = coeffield.reference_varcoef_step(
            ref, a.astype(np.float64), cfg.grid.effective_dt(),
            cfg.grid.spacing, periodic=True, bc_value=0.0)
    rel = np.max(np.abs(got - ref)) / max(float(np.max(np.abs(ref))), 1e-30)
    assert rel < 1e-5, f"varcoef multistep vs fp64 oracle rel {rel:.2e}"


# ---- integrator threading (cache / bench / provenance / regress / sweep) ----


def test_cache_key_ti_leg():
    """Non-default integrators append a |ti:<name> leg; the default
    appends NOTHING, so every committed explicit entry stays addressable
    byte-for-byte."""
    from heat3d_tpu.tune.cache import cache_key

    k_wave = cache_key(_wave_cfg())
    assert k_wave.split("|")[-1] == "ti:leapfrog"
    k_cg = cache_key(_cg_cfg())
    assert k_cg.split("|")[-1] == "ti:implicit-cg"
    k_exp = cache_key(SolverConfig(grid=GridConfig.cube(16)))
    assert "ti:" not in k_exp
    assert len({k_wave, k_cg, k_exp}) == 3


def test_resolve_config_pins_nondefault(monkeypatch, tmp_path):
    """resolve_config never consults the cache for non-default
    integrators: auto knobs pin through timeint.pin_config and no cache
    file is touched."""
    from heat3d_tpu.tune.cache import resolve_config

    store = str(tmp_path / "tune.json")
    monkeypatch.setenv("HEAT3D_TUNE_CACHE", store)
    cfg = dataclasses.replace(
        _wave_cfg(), backend="auto", halo="auto", time_blocking=0)
    got = resolve_config(cfg)
    assert got == timeint.pin_config(cfg)
    assert got.backend == "jnp" and got.halo == "ppermute"
    assert got.time_blocking == 1
    assert not os.path.exists(store)  # the cache was never consulted


def test_provenance_requires_integrator_on_throughput_rows():
    from heat3d_tpu.analysis.provenance import check_row

    row = {
        "bench": "throughput", "ts": "2026-08-06T00:00:00Z",
        "platform": "cpu", "direct_path": False,
        "mehrstellen_route": False, "streamk_path": False,
        "streamk_emulated": False, "halo_plan": "monolithic",
        "chain_ops": 7, "batch_shape": [1], "members_per_step": 1,
        "sync_rtt_s": 0.0, "equation": "heat",
    }
    assert any("integrator" in p for p in check_row(dict(row)))
    row["integrator"] = "implicit-cg"
    assert not check_row(row)


def test_regress_keys_on_integrator():
    from heat3d_tpu.obs.perf.regress import row_key

    base = {
        "bench": "throughput", "stencil": "7pt", "grid": [64] * 3,
        "mesh": [1, 1, 1], "dtype": "float32", "platform": "cpu",
    }
    k_legacy = row_key(dict(base))  # legacy row: no field -> explicit
    k_exp = row_key({**base, "integrator": "explicit-euler"})
    k_cgk = row_key({**base, "integrator": "implicit-cg"})
    assert k_legacy == k_exp
    assert k_cgk != k_legacy


def test_sweepstate_ti_suffix():
    from heat3d_tpu.resilience.sweepstate import row_key

    k_exp = row_key(SolverConfig(grid=GridConfig.cube(16), backend="jnp"),
                    "throughput")
    assert ":ti" not in k_exp  # legacy journals stay addressable
    k_wave = row_key(_wave_cfg(), "throughput")
    assert ":tileapfrog" in k_wave
    k_cg = row_key(_cg_cfg(), "throughput")
    assert ":tiimplicit-cg" in k_cg


def test_bench_row_carries_integrator():
    from heat3d_tpu.analysis.provenance import check_row
    from heat3d_tpu.bench.harness import bench_throughput

    row = bench_throughput(_cg_cfg(n=8, dt_mult=5.0), steps=2, repeats=1,
                           warmup=0)
    assert row["integrator"] == "implicit-cg"
    assert not check_row(row)
    row_exp = bench_throughput(
        SolverConfig(grid=GridConfig.cube(8), backend="jnp"),
        steps=2, repeats=1, warmup=0)
    assert row_exp["integrator"] == "explicit-euler"


# ---- serve buckets ----------------------------------------------------------


def test_scenario_integrator_and_coef_field_buckets():
    """Integrator is structural (re-buckets requests); coef_field batches
    all-or-none; the ensemble packs the explicit sweep only."""
    from heat3d_tpu.serve.ensemble import EnsembleSolver
    from heat3d_tpu.serve.scenario import (
        Scenario,
        ScenarioBatch,
        request_bucket_key,
    )

    s = Scenario(coef_field=("checker", 3))
    assert s.coef_field == ("checker", 3, 0.5, 1.5)  # normalized
    with pytest.raises(ValueError):
        Scenario(coef_field=("nope",))
    with pytest.raises(ValueError):
        Scenario(integrator="rk4")

    base = SolverConfig(grid=GridConfig.cube(12), backend="jnp")
    with pytest.raises(ValueError, match="coef"):
        ScenarioBatch(base, [Scenario(coef_field="uniform"), Scenario()])
    with pytest.raises(ValueError, match="integrator"):
        ScenarioBatch(base, [Scenario(integrator="leapfrog"),
                             Scenario(integrator="implicit-cg")])

    keys = {
        request_bucket_key(base, Scenario()),
        request_bucket_key(base, Scenario(integrator="implicit-cg")),
        request_bucket_key(base, Scenario(coef_field="uniform")),
    }
    assert len(keys) == 3  # three distinct compiled-program buckets

    b = ScenarioBatch(base, [Scenario(integrator="implicit-cg"),
                             Scenario()])
    assert b.base.integrator == "implicit-cg"
    with pytest.raises(ValueError):
        EnsembleSolver(b)  # the ensemble packs the explicit sweep only


def test_serve_request_json_maps_integrator_and_coef_field():
    """The `serve --requests` JSON frontend must thread coef_field and
    integrator into the Scenario — otherwise a varcoef request silently
    packs with (and is served as) a constant-coefficient member."""
    from heat3d_tpu.serve.cli import _scenario_from_record
    from heat3d_tpu.serve.scenario import request_bucket_key

    s = _scenario_from_record(
        {"grid": 16, "steps": 5, "coef_field": ["checker", 3],
         "bc_value": 0.25}
    )
    assert s.coef_field == ("checker", 3, 0.5, 1.5)  # normalized tuple
    assert _scenario_from_record({"coef_field": "lognormal"}).coef_field == (
        "lognormal", 0, 0.5, 1.5
    )
    ti = _scenario_from_record({"integrator": "implicit-cg"})
    assert ti.integrator == "implicit-cg"
    plain = _scenario_from_record({"grid": 16, "steps": 5})
    assert plain.coef_field is None and plain.integrator is None

    base = SolverConfig(grid=GridConfig.cube(16), backend="jnp")
    assert request_bucket_key(base, s) != request_bucket_key(base, plain)


# ---- the 4-device CPU-mesh acceptance battery -------------------------------


def _cpu_mesh_env(ndev: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE), env.get("PYTHONPATH", "")]
    )
    env["HEAT3D_TUNE_CACHE"] = os.path.join(
        env.get("TMPDIR", "/tmp"), "timeint_check_tune_cache.json"
    )
    # the bitwise dist==solo contract for leapfrog/CG is certified at f64
    # COMPUTE over f32 storage (f32 FMA contraction differs across mesh
    # shapes on XLA:CPU) — the battery needs x64 enabled to honor it
    env["JAX_ENABLE_X64"] = "1"
    return env


def test_timeint_acceptance_on_cpu_mesh_tier1():
    """Tier-1 acceptance: on a REAL 4-device CPU mesh, (1) leapfrog
    (tb1 + the tb=2 two-level superstep), the CG solve at 15x CFL, and
    the varcoef flux update are dist==solo BITWISE, (2) an interrupted
    leapfrog supervised run resumes BOTH carry levels bitwise and a
    wrong-integrator generation is skipped without quarantine, (3) the
    serve tier packs per-member coefficient fields (fp64 oracle + B=1
    vs B=2 bitwise + plan-audit events)."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(HERE, "multidevice_checks.py"),
            "timeint",
        ],
        env=_cpu_mesh_env(4),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"timeint multidevice battery failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    for marker in (
        "timeint_dist_bitwise OK",
        "timeint_supervised_two_level_resume OK",
        "timeint_coef_serve_packing OK",
        "ALL MULTIDEVICE CHECKS PASSED",
    ):
        assert marker in proc.stdout, f"missing marker: {marker}"
