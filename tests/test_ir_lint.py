"""IR-level SPMD certification tests (tier-1, CPU): every checker family
fires on a seeded-violation program and stays quiet on the real judged
programs, the shard-varying-predicate collective is caught at the jaxpr
tier where the AST checker is provably blind, fingerprints anchor on
(checker, config-key, invariant) — never jaxpr text — and, the
acceptance gate, `heat3d lint --ir --json` is clean on this repo across
the judged matrix in a fresh multi-device process."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from heat3d_tpu.analysis import collectives as ast_collectives
from heat3d_tpu.analysis.ir import (
    IR_CHECKERS,
    collectives as irc,
    dtypeflow as ird,
    footprint as irf,
    jaxpr_tools as jt,
    memcontract as irm,
    programs as irp,
)
from heat3d_tpu.core.config import (
    GridConfig,
    MeshConfig,
    Precision,
    SolverConfig,
)
from heat3d_tpu.ops.stencil_jnp import apply_taps_padded, residual_sumsq
from heat3d_tpu.parallel.halo import exchange_halo
from heat3d_tpu.parallel.step import _solver_taps, make_step_fn
from heat3d_tpu.parallel.topology import abstract_mesh
from jax import shard_map

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

SPEC = P("x", "y", "z")


def _cfg(**kw):
    kw.setdefault("grid", GridConfig.cube(16))
    kw.setdefault("mesh", MeshConfig(shape=(2, 1, 1)))
    kw.setdefault("backend", "jnp")
    return SolverConfig(**kw)


def _case(fn, cfg, kind="step", key="seed", aval=None, **kw):
    """A ProgramCase over an ABSTRACT mesh — tracing needs no devices, so
    the in-process tests run multi-chip programs on the 1-CPU pytest
    box exactly like topology.lower_for_mesh does."""
    aval = aval or jax.ShapeDtypeStruct(
        cfg.padded_shape, jnp.dtype(cfg.precision.storage)
    )
    kw.setdefault(
        "mesh_sizes", dict(zip(cfg.mesh.axis_names, cfg.mesh.shape))
    )
    return irp.ProgramCase(
        key=key,
        cfg=cfg,
        kind=kind,
        path="tests/seeded.py",
        fn=fn,
        avals=(aval,),
        **kw,
    )


def _sharded(fn, cfg, out_specs=SPEC):
    return shard_map(
        fn,
        mesh=abstract_mesh(cfg.mesh),
        in_specs=SPEC,
        out_specs=out_specs,
        check_vma=False,
    )


def _codes(findings):
    return sorted({f.code for f in findings})


# ---- collective topology (ANL6xx) -----------------------------------------


def test_clean_judged_programs_have_no_collective_findings():
    """Negative: the real step/superstep builders over both halo
    orderings and a block mesh certify clean."""
    cases = []
    for mesh, tb, order in (
        ((2, 2, 1), 1, "axis"),
        ((2, 1, 1), 1, "pairwise"),
        ((2, 2, 2), 3, "axis"),
    ):
        cfg = _cfg(mesh=MeshConfig(shape=mesh), halo_order=order)
        cfg = dataclasses.replace(cfg, time_blocking=tb)
        from heat3d_tpu.parallel.step import make_superstep_fn

        builder = (
            make_superstep_fn(cfg, abstract_mesh(cfg.mesh))
            if tb > 1
            else make_step_fn(cfg, abstract_mesh(cfg.mesh))
        )
        cases.append(
            _case(builder, cfg, kind="superstep" if tb > 1 else "step")
        )
    assert irc.check_cases(cases) == []
    for case in cases:
        assert irf.check_case(case) == []
        assert ird.check_case(case) == []


def test_broken_permutation_fires_bijection_and_neighbor_graph():
    cfg = _cfg()

    def bad(u):
        # duplicate destination: not a bijection
        g = lax.ppermute(u[:1], "x", [(0, 1), (1, 1)])
        return u + g

    case = _case(_sharded(bad, cfg), cfg)
    codes = _codes(irc.check_cases([case]))
    assert "ANL601" in codes

    def wrong_graph(u):
        # wrap pair on a Dirichlet config: not the mesh neighbor graph
        g1 = lax.ppermute(u[:1], "x", [(0, 1), (1, 0)])
        g2 = lax.ppermute(u[-1:], "x", [(1, 0), (0, 1)])
        return u + g1 + g2

    case2 = _case(_sharded(wrong_graph, cfg), cfg)
    assert "ANL602" in _codes(irc.check_cases([case2]))


def test_missing_inverse_direction_fires_pair_checks():
    cfg = _cfg()
    taps = _solver_taps(cfg)

    def one_way(u):
        # only the low-side ghost travels; the high face never returns
        ghost = lax.ppermute(u[-1:], "x", [(0, 1)])
        up = jnp.concatenate([ghost, u, jnp.zeros_like(u[:1])], 0)
        up = jnp.pad(up, ((0, 0), (1, 1), (1, 1)))
        return apply_taps_padded(up, taps)

    case = _case(_sharded(one_way, cfg), cfg)
    codes = _codes(irc.check_cases([case]))
    assert "ANL605" in codes


def test_divergent_predicate_collective_caught_at_ir_not_ast(tmp_path):
    """THE acceptance hazard: a collective under a shard-varying traced
    predicate deadlocks a pod. The AST tier (ANL101-103) must prove
    blind — lax.cond is data flow, not Python control flow — while the
    IR tier catches it."""
    src = textwrap.dedent(
        """
        import jax
        from jax import lax

        def steppish(u):
            # traced conditional on a shard-varying value: every device
            # runs this PYTHON code identically, so the AST sees nothing
            return lax.cond(
                lax.axis_index("x") == 0,
                lambda v: lax.psum(v, "x"),
                lambda v: v,
                u,
            )
        """
    )
    path = tmp_path / "pkg" / "divergent.py"
    path.parent.mkdir(parents=True)
    path.write_text(src)
    ast_found = ast_collectives.check(str(tmp_path), files=[str(path)])
    assert ast_found == []  # the AST tier is provably blind here

    cfg = _cfg()

    def steppish(u):
        return lax.cond(
            lax.axis_index("x") == 0,
            lambda v: lax.psum(v, "x"),
            lambda v: v,
            u,
        )

    case = _case(
        _sharded(steppish, cfg, out_specs=P("x", None, None)), cfg
    )
    found = [f for f in irc.check_cases([case]) if f.code == "ANL606"]
    assert found, "IR tier must catch the divergent-predicate collective"
    assert "psum" in found[0].message


def test_divergent_while_predicate_caught():
    cfg = _cfg()

    def bad_loop(u):
        # loop bound derived from MY shard's data: trip counts diverge
        # and the psum inside desynchronizes
        n = jnp.max(u).astype(jnp.int32)

        def body(state):
            i, v = state
            return i + 1, v + lax.psum(v, "x")

        _, out = lax.while_loop(lambda s: s[0] < n, body, (0, u))
        return out

    case = _case(_sharded(bad_loop, cfg), cfg)
    assert "ANL606" in _codes(irc.check_cases([case]))


def test_uniform_pmax_bound_is_not_flagged():
    """The EnsembleSolver discipline: a loop bound made uniform by a
    pmax over the varying axis is NOT divergent (the taint is removed),
    so the masked-budget loop certifies clean."""
    cfg = _cfg()

    def good_loop(u):
        n = lax.pmax(jnp.max(u).astype(jnp.int32), "x")

        def body(state):
            i, v = state
            return i + 1, v + lax.psum(v, "x")

        _, out = lax.while_loop(lambda s: s[0] < n, body, (0, u))
        return out

    case = _case(_sharded(good_loop, cfg), cfg)
    assert [f for f in irc.check_cases([case]) if f.code == "ANL606"] == []


def test_unreplicated_unmapped_output_fires_replication_contract():
    cfg = _cfg()

    def local(u):
        # declared replicated (P()) but psum'd over x only... except the
        # value genuinely varies over nothing else here, so use raw sum
        return jnp.sum(u)  # varies over x, never reduced across devices

    case = _case(_sharded(local, cfg, out_specs=P()), cfg)
    assert "ANL607" in _codes(irc.check_cases([case]))


def test_partially_mapped_output_variation_fires_replication():
    """An output sharded over x whose value ALSO varies over sharded y
    (never reduced) is ill-defined stitching — the partial-mapping form
    of the check_vma=False debt."""
    cfg = _cfg(mesh=MeshConfig(shape=(2, 2, 1)))

    def local(u):
        return u * (1.0 + lax.axis_index("y"))

    case = _case(
        shard_map(
            local,
            mesh=abstract_mesh(cfg.mesh),
            in_specs=P("x", None, None),
            out_specs=P("x", None, None),
            check_vma=False,
        ),
        cfg,
        mesh_sizes={"x": 2, "y": 2, "z": 1},
    )
    found = [f for f in irc.check_cases([case]) if f.code == "ANL607"]
    assert found and "'y'" in found[0].message


def test_degraded_device_posture_warns_anl610():
    """A session whose backend initialized below the wanted device
    count must surface ANL610 — the matrix lost its block meshes and
    ensemble programs, and that must never read as a full clean."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run(
        [
            sys.executable, "-m", "heat3d_tpu.cli", "lint", "--ir",
            "--checker", "ir-collectives", "--json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        env=env,
    )
    payload = json.loads(out.stdout)
    assert any(f["code"] == "ANL610" for f in payload["findings"]), (
        out.stdout + out.stderr
    )
    assert out.returncode == 0  # warning severity: visible, not fatal


def test_residual_psum_axes_contract():
    """A residual psum over a PARTIAL axis set fails the replication
    contract; the real residual program passes."""
    cfg = _cfg(mesh=MeshConfig(shape=(2, 2, 1)))
    taps = _solver_taps(cfg)

    def partial_psum(u):
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, 1)
        new = apply_taps_padded(up, taps)
        r = residual_sumsq(new, u, jnp.dtype("float32"))
        return new, lax.psum(r, ("x",))  # forgot 'y'

    case = _case(
        shard_map(
            partial_psum,
            mesh=abstract_mesh(cfg.mesh),
            in_specs=SPEC,
            out_specs=(SPEC, P()),
            check_vma=False,
        ),
        cfg,
        kind="residual",
        mesh_sizes={"x": 2, "y": 2, "z": 1},
    )
    codes = _codes(irc.check_cases([case]))
    assert "ANL607" in codes

    good = _case(
        make_step_fn(cfg, abstract_mesh(cfg.mesh), with_residual=True),
        cfg,
        kind="residual",
        mesh_sizes={"x": 2, "y": 2, "z": 1},
    )
    assert irc.check_cases([good]) == []


# ---- halo footprint (ANL7xx) ----------------------------------------------


def _starved_superstep_case():
    """Claims time_blocking=2 but exchanges width-1 halos twice — the
    footprint a superstep refactor would produce if it forgot to widen
    the exchange."""
    cfg = dataclasses.replace(_cfg(), time_blocking=2)
    taps = _solver_taps(cfg)

    def starved(u):
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, width=1)
        mid = apply_taps_padded(up, taps)
        up2 = exchange_halo(mid, cfg.mesh, cfg.stencil.bc, 0.0, width=1)
        return apply_taps_padded(up2, taps)

    return _case(_sharded(starved, cfg), cfg, kind="superstep")


def test_insufficient_ghost_width_fires():
    codes = _codes(irf.check_case(_starved_superstep_case()))
    assert "ANL701" in codes
    assert "ANL703" in codes  # and the trapezoid chain is broken


def test_wasteful_ghost_width_warns():
    cfg = _cfg()  # tb=1: one application needs width 1
    taps = _solver_taps(cfg)

    def wasteful(u):
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, width=2)
        mid = apply_taps_padded(up, taps)
        return mid[1:-1, 1:-1, 1:-1]

    case = _case(_sharded(wasteful, cfg), cfg)
    found = irf.check_case(case)
    assert any(f.code == "ANL702" and f.severity == "warning" for f in found)


def test_footprint_radius_derivation():
    assert irf.tap_radius(_cfg()) == (1, 1, 1)
    from heat3d_tpu.core.config import StencilConfig

    assert irf.tap_radius(_cfg(stencil=StencilConfig("27pt"))) == (1, 1, 1)


# ---- dtype flow (ANL8xx) --------------------------------------------------


def test_fp64_leak_fires_alien_dtype():
    cfg = _cfg()
    taps = _solver_taps(cfg)

    def leaky(u):
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, 1)
        with jax.enable_x64(True):
            mid = apply_taps_padded(
                up, taps, compute_dtype=jnp.dtype("float64")
            )
        return mid.astype(u.dtype)

    with jax.enable_x64(True):
        case = _case(_sharded(leaky, cfg), cfg)
        case.jaxpr()  # trace inside the x64 context
    assert "ANL801" in _codes(ird.check_case(case))


def test_bf16_accumulation_leak_fires():
    cfg = _cfg(precision=Precision.bf16())
    taps = _solver_taps(cfg)

    def lossy(u):
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, 1)
        new = apply_taps_padded(up, taps, out_dtype=jnp.bfloat16)
        d = (new - u)
        # the local sum upcasts (jax auto-promotes small-float
        # accumulation) but the CROSS-DEVICE reduction runs in bf16 —
        # the forgotten upcast before the psum is the realistic leak
        r = jnp.sum(d * d).astype(jnp.bfloat16)
        return new, lax.psum(r, ("x", "y", "z"))

    case = _case(
        shard_map(
            lossy,
            mesh=abstract_mesh(cfg.mesh),
            in_specs=SPEC,
            out_specs=(SPEC, P()),
            check_vma=False,
        ),
        cfg,
        kind="residual",
    )
    assert "ANL802" in _codes(ird.check_case(case))


def test_missing_roundtrip_fires_and_real_superstep_clean():
    cfg = dataclasses.replace(
        _cfg(precision=Precision.bf16()), time_blocking=2
    )
    taps = _solver_taps(cfg)

    def no_roundtrip(u):
        # computes in f32 but never returns to bf16 between applications
        up = exchange_halo(u, cfg.mesh, cfg.stencil.bc, 0.0, 2)
        mid = apply_taps_padded(
            up, taps, compute_dtype=jnp.float32, out_dtype=jnp.float32
        )
        out = apply_taps_padded(
            mid, taps, compute_dtype=jnp.float32, out_dtype=jnp.float32
        )
        return out.astype(jnp.bfloat16)

    case = _case(_sharded(no_roundtrip, cfg), cfg, kind="superstep")
    assert "ANL803" in _codes(ird.check_case(case))

    from heat3d_tpu.parallel.step import make_superstep_fn

    good = _case(
        make_superstep_fn(cfg, abstract_mesh(cfg.mesh)),
        cfg,
        kind="superstep",
    )
    assert ird.check_case(good) == []


# ---- memory contract (ANL9xx) ---------------------------------------------


def _real_case_1dev(tb=1):
    cfg = dataclasses.replace(
        _cfg(mesh=MeshConfig(shape=(1, 1, 1))), time_blocking=tb
    )
    from heat3d_tpu.parallel.step import make_superstep_fn
    from heat3d_tpu.parallel.topology import build_mesh

    mesh = build_mesh(cfg.mesh)
    builder = (
        make_superstep_fn(cfg, mesh) if tb > 1 else make_step_fn(cfg, mesh)
    )
    case = _case(builder, cfg, kind="superstep" if tb > 1 else "step")
    case.compile = True
    return case


def test_memcontract_clean_on_real_program():
    found = irm.check_cases([_real_case_1dev(tb=2)], compile_enabled=True)
    assert [f for f in found if f.severity == "error"] == []
    assert any(f.code == "ANL904" for f in found)  # joined numbers


def test_memcontract_budget_overrun_fires(monkeypatch):
    case = _real_case_1dev(tb=2)
    monkeypatch.setattr(irm, "temp_model_bytes", lambda cfg: 1)
    found = irm.check_cases([case], compile_enabled=True)
    assert any(f.code == "ANL902" for f in found)


def test_memcontract_signature_drift_fires():
    """A program whose output is not the one-shard ping-pong contract
    (here: a doubled field) breaks the signature check."""
    cfg = _cfg(mesh=MeshConfig(shape=(1, 1, 1)))

    def doubled(u):
        return jnp.stack([u, u])  # two field copies out

    case = _case(doubled, cfg)
    case.compile = True
    found = irm.check_cases([case], compile_enabled=True)
    assert any(f.code == "ANL901" for f in found)


def test_gate_adjudication_fires_on_table_drift():
    found = irm.check_gate_adjudication(
        chip_table={"tpu-tiny": 4 * irm.MIB},
        budget_for=lambda gen: 32 * irm.MIB,
        live_generation="not-in-table",
    )
    assert [f.code for f in found] == ["ANL905"]
    assert found[0].severity == "error"
    # and the real gate resolves within every known generation
    assert irm.check_gate_adjudication() == []


def test_gate_adjudication_fires_on_live_override_above_capacity():
    """An operator HEAT3D_VMEM_BYTES override above the current part's
    VMEM is the mis-set knob the old ANL305 warning existed for — now an
    adjudicated error on the live resolution."""
    found = irm.check_gate_adjudication(
        live_generation="tpu-v5-lite",
        live_budget=64 * irm.MIB,
    )
    assert [f.code for f in found] == ["ANL905"]
    assert "HEAT3D_VMEM_BYTES" in found[0].message
    assert irm.check_gate_adjudication(
        live_generation="tpu-v5-lite", live_budget=16 * irm.MIB
    ) == []


def test_generation_aware_gate_budget(monkeypatch):
    from heat3d_tpu.analysis import vmem

    assert vmem.chip_vmem_budget_for("tpu-v5-lite") == 16 * 1024 * 1024
    assert vmem.chip_vmem_budget_for("tpu-v5p") == 32 * 1024 * 1024
    with pytest.raises(ValueError, match="no VMEM budget"):
        vmem.chip_vmem_budget_for("weird-part")
    monkeypatch.setenv("HEAT3D_VMEM_BYTES", str(7 * 1024 * 1024))
    assert vmem._chip_vmem_budget() == 7 * 1024 * 1024
    monkeypatch.delenv("HEAT3D_VMEM_BYTES")
    monkeypatch.setattr(
        "heat3d_tpu.tune.cache.chip_generation", lambda: "tpu-v5-lite"
    )
    assert vmem._chip_vmem_budget() == 16 * 1024 * 1024


# ---- fingerprints / framework ---------------------------------------------


def test_ir_fingerprints_anchor_on_config_key_not_trace_text():
    """Two findings for the same (checker, config-key, invariant) with
    different message text (jaxpr pretty-printer drift) share one
    fingerprint; a different config key separates them."""
    from heat3d_tpu.analysis.findings import Finding

    a = Finding(
        checker="ir-collectives", severity="error", path="p.py", line=0,
        code="ANL606", symbol="7pt/fp32/m2x1x1/tb1/axis/step|divergent",
        message="jax 0.4 spelling of the trace",
    )
    b = dataclasses.replace(a, message="jax 0.9 spelling, new pretty printer")
    c = dataclasses.replace(
        a, symbol="27pt/fp32/m2x1x1/tb1/axis/step|divergent"
    )
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()


def test_all_ir_findings_carry_config_key_symbols():
    """Checker discipline: every seeded finding has a symbol anchor
    (config-key|invariant), so no fingerprint ever rides on message
    text."""
    found = irc.check_cases([_case(_sharded(
        lambda u: lax.cond(
            lax.axis_index("x") == 0,
            lambda v: lax.psum(v, "x"),
            lambda v: v,
            u,
        ),
        _cfg(), out_specs=P("x", None, None)), _cfg())])
    found += irf.check_case(_starved_superstep_case())
    assert found
    for f in found:
        assert f.symbol and "|" in f.symbol


def test_ir_catalog_and_list():
    assert set(IR_CHECKERS) == {
        "ir-collectives", "ir-footprint", "ir-dtype", "ir-memory"
    }
    from heat3d_tpu.analysis.cli import main as lint_main

    assert lint_main(["--ir", "--list"]) == 0


# ---- acceptance ------------------------------------------------------------


def test_lint_ir_acceptance_clean_on_repo():
    """Acceptance: `heat3d lint --ir --json` certifies the repo's judged
    matrix with zero errors AND zero warnings in a fresh process — run
    exactly as CI runs it (the CLI forces its own multi-device CPU mesh,
    so a degraded single-shard matrix would surface as the ANL610
    warning and fail this test)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "heat3d_tpu.cli", "lint", "--ir", "--json"],
        cwd=REPO,
        capture_output=True,
        text=True,
        env=env,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["counts"]["error"] == 0
    assert payload["counts"]["warning"] == 0
    assert set(payload["checkers"]) == set(IR_CHECKERS)
    # the compiled memory-contract leg genuinely ran
    assert any(f["code"] == "ANL904" for f in payload["findings"])
