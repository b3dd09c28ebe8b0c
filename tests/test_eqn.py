"""The declarative equation frontend (heat3d_tpu.eqn; docs/EQUATIONS.md):
spec compiler bitwise contract, family registry, MMS convergence order,
cache-key fingerprinting, provenance threading, and the eqn-registry
lint — plus the 4-device CPU-mesh acceptance battery subprocess
(spec-vs-legacy heat bitwise, family golden/MMS e2e, serve traced-bind
with per-member spec coefficients).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.core import golden
from heat3d_tpu.core.stencils import STENCILS, stencil_taps
from heat3d_tpu import eqn

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg(
    family="heat",
    kind="7pt",
    params=(),
    alpha=1.0,
    dt=None,
    spacing=(1.0, 1.0, 1.0),
    **kw,
):
    return SolverConfig(
        grid=GridConfig.cube(16, alpha=alpha, dt=dt, spacing=spacing),
        stencil=StencilConfig(kind=kind),
        equation=family,
        eq_params=params,
        **kw,
    )


# ---- the bitwise contract ---------------------------------------------------


def test_heat_spec_taps_bitwise_equal_legacy():
    """The tentpole contract: the heat family's spec-compiled taps are
    BIT-identical to the legacy hardcoded stencil_taps derivation, for
    both kinds across alphas/dts/spacings (anisotropic spacing included
    for the separable 7pt)."""
    cases = [
        ("7pt", 1.0, None, (1.0, 1.0, 1.0)),
        ("7pt", 0.37, 0.01, (1.0, 1.25, 0.75)),
        ("7pt", 2.5, None, (0.5, 0.5, 0.5)),
        ("27pt", 1.0, None, (1.0, 1.0, 1.0)),
        ("27pt", 0.81, 0.003, (2.0, 2.0, 2.0)),
    ]
    for kind, alpha, dt, spacing in cases:
        cfg = _cfg(kind=kind, alpha=alpha, dt=dt, spacing=spacing)
        spec_taps = eqn.solver_taps(cfg)
        legacy = stencil_taps(
            STENCILS[kind], alpha, cfg.grid.effective_dt(), spacing
        )
        assert spec_taps.dtype == legacy.dtype == np.float64
        assert spec_taps.tobytes() == legacy.tobytes(), (
            f"{kind} alpha={alpha} spacing={spacing}"
        )


def test_legacy_env_arm(monkeypatch):
    """HEAT3D_EQN_LEGACY=1 runs the verbatim pre-spec derivation for
    heat (same bytes) and REJECTS non-heat families loudly."""
    cfg = _cfg(alpha=0.5)
    want = eqn.solver_taps(cfg)
    monkeypatch.setenv(eqn.ENV_LEGACY, "1")
    assert eqn.solver_taps(cfg).tobytes() == want.tobytes()
    with pytest.raises(ValueError, match="legacy"):
        eqn.solver_taps(_cfg(family="reaction-diffusion"))


# ---- registry + validation --------------------------------------------------


def test_registry_families_build_and_have_mms():
    assert set(eqn.FAMILIES) >= {
        "heat", "aniso-diffusion", "advection-diffusion",
        "reaction-diffusion",
    }
    for name, fam in eqn.FAMILIES.items():
        for kind in fam.kinds:
            # wave is second order in time: config-time validation pins
            # it to the leapfrog carry (docs/INTEGRATORS.md)
            extra = {"integrator": "leapfrog"} if name == "wave" else {}
            cfg = _cfg(family=name, kind=kind, **extra)
            taps = eqn.solver_taps(cfg)
            assert taps.shape == (3, 3, 3)
            mu, omega = eqn.mms_rates(cfg, (1.0, 2.0, 3.0))
            assert np.isfinite(mu) and np.isfinite(omega)


def test_config_validation_errors():
    with pytest.raises(ValueError, match="unknown equation family"):
        _cfg(family="navier-stokes")
    with pytest.raises(ValueError, match="unknown equation parameter"):
        _cfg(family="advection-diffusion", params=(("vq", 1.0),))
    with pytest.raises(ValueError, match="finite"):
        _cfg(family="advection-diffusion", params=(("vx", float("nan")),))
    with pytest.raises(ValueError, match="stencil kinds"):
        _cfg(family="aniso-diffusion", kind="27pt")
    with pytest.raises(ValueError, match="positive"):
        _cfg(family="aniso-diffusion", params=(("dx", -1.0),))


def test_default_dt_respects_family_stability_bound():
    """A non-heat family with a DEFAULT dt must reject parameters whose
    explicit-Euler bound falls below the diffusion-only derivation —
    the silent-divergence guard (a rate=-50 run used to exit 0 with
    residual inf). An explicit dt stays the author's contract, and heat
    defaults are untouched (its bound IS the derivation's)."""
    with pytest.raises(ValueError, match="explicit-Euler bound"):
        _cfg(family="reaction-diffusion", params=(("rate", -50.0),))
    with pytest.raises(ValueError, match="explicit-Euler bound"):
        _cfg(family="advection-diffusion", params=(("vx", 10.0),))
    # explicit dt under the bound: accepted and stable
    cfg = _cfg(
        family="reaction-diffusion", params=(("rate", -50.0),), dt=0.01
    )
    assert cfg.grid.effective_dt() == 0.01
    # heat never hits the check (default derivation == its own bound)
    _cfg(alpha=100.0)
    # the bounds themselves: reaction decay tightens, advection adds the
    # cell-Reynolds leg, aniso scales per axis
    fam = eqn.FAMILIES["reaction-diffusion"]
    assert fam.stable_dt({"rate": -1.0}, 1.0, (1.0, 1.0, 1.0)) == (
        pytest.approx(2.0 / 13.0)
    )
    fam = eqn.FAMILIES["advection-diffusion"]
    assert fam.stable_dt(
        {"vx": 10.0, "vy": 0.0, "vz": 0.0}, 1.0, (1.0, 1.0, 1.0)
    ) == pytest.approx(0.02)


def test_spec_validation():
    from heat3d_tpu.eqn.spec import EquationSpec, StencilSpec, Term

    with pytest.raises(ValueError, match="sum to 0"):
        StencilSpec(weights=np.ones((3, 3, 3)))
    w = np.zeros((3, 3, 3))
    w[0, 1, 1] = 1.0
    w[2, 1, 1] = 1.0  # not antisymmetric
    with pytest.raises(ValueError, match="antisymmetric"):
        StencilSpec(weights=w, scaling="gradient")
    w2 = np.zeros((3, 3, 3))
    w2[0, 0, 0] = 1.0  # off-axis gradient tap
    with pytest.raises(ValueError, match="face taps"):
        StencilSpec(weights=w2, scaling="gradient")
    with pytest.raises(ValueError, match="at least one term"):
        EquationSpec(family="x", terms=())
    ok = StencilSpec(weights=np.zeros((3, 3, 3)), scaling="none")
    with pytest.raises(ValueError, match="duplicate"):
        EquationSpec(
            family="x",
            terms=(Term("a", 1.0, ok), Term("a", 2.0, ok)),
        )


# ---- fingerprint + tune-cache key ------------------------------------------


def test_fingerprint_heat_is_bare_kind():
    assert eqn.fingerprint(_cfg(kind="7pt")) == "7pt"
    assert eqn.fingerprint(_cfg(kind="27pt")) == "27pt"


def test_fingerprint_families_key_on_params():
    a = eqn.fingerprint(_cfg(family="advection-diffusion"))
    b = eqn.fingerprint(
        _cfg(family="advection-diffusion", params=(("vx", 2.0),))
    )
    assert a.startswith("advection-diffusion:7pt:")
    assert a != b
    # deterministic across processes/sessions (content hash, not id)
    assert a == eqn.fingerprint(_cfg(family="advection-diffusion"))


def test_cache_key_stability_and_family_bucket():
    """Committed heat cache entries stay addressable: the key's stencil
    leg is the bare kind, byte-identical to the pre-eqn format; families
    get their own bucket."""
    from heat3d_tpu.tune.cache import cache_key, chip_generation

    cfg = _cfg(kind="27pt")
    key = cache_key(cfg)
    parts = key.split("|")
    assert parts[4] == "27pt" and parts[5] == "float32"
    # reconstruct the full legacy format — a change to any other leg
    # would also orphan committed entries
    assert key == (
        f"{chip_generation()}|p1|d{cfg.mesh.num_devices}"
        f"|g2^{round(np.log2(cfg.grid.num_cells))}|27pt|float32"
    )
    fam_key = cache_key(_cfg(family="reaction-diffusion"))
    assert "reaction-diffusion:7pt:" in fam_key
    assert fam_key != cache_key(_cfg())


def test_tune_show_apply_annotate_family(tmp_path, monkeypatch):
    from heat3d_tpu.tune import cache as tcache
    from heat3d_tpu.tune.cli import _entry_lines, _key_equation, main

    store = str(tmp_path / "cache.json")
    monkeypatch.setenv(tcache.ENV_CACHE, store)
    # a full-precision param value: apply must reconstruct the EXACT
    # fingerprint bucket, so the emitted --eq-param cannot round
    vx = 0.1234567890123
    cfg = _cfg(
        family="advection-diffusion", params=(("vx", vx),),
        backend="jnp", time_blocking=2,
    )
    key = tcache.cache_key(cfg)
    tcache.store_entry(key, cfg, 1.5, 1.0)
    assert _key_equation(key) == "advection-diffusion"
    assert _key_equation(tcache.cache_key(_cfg())) == "heat"
    entry = tcache.load(store)["entries"][key]
    # the entry persists the measured workload's equation context
    assert entry["config"]["equation"] == "advection-diffusion"
    assert entry["config"]["eq_params"] == [["vx", vx]]
    line = _entry_lines(key, entry)
    assert "equation=advection-diffusion" in line
    # apply emits the family + exact params so the winner reconstructs
    # the very bucket it was measured for
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["apply", "--key", key])
    assert rc == 0
    out = buf.getvalue()
    assert "--equation advection-diffusion" in out
    assert f"--eq-param vx={vx!r}" in out
    assert "--time-blocking 2" in out
    # round trip: parsing the emitted flag lands on the SAME cache key
    from heat3d_tpu.eqn.cli import parse_eq_params

    flag_val = out.split("--eq-param ")[1].split()[0]
    recon = _cfg(
        family="advection-diffusion", params=parse_eq_params([flag_val]),
        backend="jnp", time_blocking=2,
    )
    assert tcache.cache_key(recon) == key


# ---- MMS convergence order --------------------------------------------------


def _mms_error(family, params, n, wave=(1, 1, 0), kind="7pt"):
    """fp64 golden-stepper error vs the analytic plane wave at t_end,
    dt ∝ h^2 so spatial+temporal truncation are jointly 2nd order."""
    shape = (n, n, n)
    h = 1.0 / n
    spacing = (h, h, h)
    alpha = 0.01
    t_end = 0.04
    # steps ∝ n^2 EXACTLY so dt ∝ h^2 exactly — a rounded step count
    # would make the temporal error shrink at a ratio other than 4 and
    # pollute the measured order
    steps = max((n * n) // 16, 1)
    dt = t_end / steps
    cfg = SolverConfig(
        grid=GridConfig(shape=shape, spacing=spacing, alpha=alpha, dt=dt),
        stencil=StencilConfig(kind=kind, bc=BoundaryCondition.PERIODIC),
        equation=family,
        eq_params=params,
    )
    mu, omega = eqn.mms_rates(cfg, golden.wavevector(shape, spacing, wave))
    u0 = golden.plane_wave(shape, spacing, wave)
    got = golden.run(
        u0, cfg.grid, cfg.stencil, steps, impl="numpy",
        taps=eqn.solver_taps(cfg),
    )
    want = golden.plane_wave(
        shape, spacing, wave, t=t_end, mu=mu, omega=omega
    )
    return float(np.max(np.abs(got - want)))


@pytest.mark.parametrize(
    "family,params",
    [
        ("heat", ()),
        ("aniso-diffusion", (("dx", 1.0), ("dy", 0.6), ("dz", 0.3))),
        ("advection-diffusion", (("vx", 0.05), ("vy", 0.02), ("vz", 0.0))),
        ("reaction-diffusion", (("rate", -2.0),)),
    ],
)
def test_mms_convergence_order(family, params):
    """Halving h (with dt ∝ h^2) must shrink the plane-wave error ~4x —
    the 2nd-order accuracy certificate, per family, against the EXACT
    continuous solution (not a self-comparison)."""
    e_coarse = _mms_error(family, params, n=8)
    e_fine = _mms_error(family, params, n=16)
    ratio = e_coarse / max(e_fine, 1e-300)
    assert ratio > 2.7, (
        f"{family}: error ratio {ratio:.2f} (coarse {e_coarse:.3e}, "
        f"fine {e_fine:.3e}) — not converging at 2nd order"
    )


def test_mms_heat27_order():
    """The 27pt footprint through the same MMS harness (its own kinds
    leg of the heat family)."""
    e8 = _mms_error("heat", (), n=8, wave=(1, 0, 1), kind="27pt")
    e16 = _mms_error("heat", (), n=16, wave=(1, 0, 1), kind="27pt")
    assert e8 / max(e16, 1e-300) > 2.7


# ---- parametric-chain parity (the serve traced-bind enabler) ---------------


def test_asymmetric_taps_parametric_chain_parity():
    """apply_taps_padded_params reproduces apply_taps_padded for the
    ASYMMETRIC advection chain (no x/y factoring) — the property the
    ensemble traced bind relies on for spec-built families."""
    import jax.numpy as jnp

    from heat3d_tpu.core.stencils import flat_taps
    from heat3d_tpu.ops.stencil_jnp import (
        apply_taps_padded,
        apply_taps_padded_params,
        emission_positions,
    )

    cfg = _cfg(family="advection-diffusion", params=(("vx", 0.3),
                                                     ("vy", 0.1)))
    taps = eqn.solver_taps(cfg)
    flat = flat_taps(taps)
    positions = emission_positions(flat)
    weights = np.asarray(
        [taps[di + 1, dj + 1, dk + 1] for (di, dj, dk) in positions],
        dtype=np.float64,
    ).astype(np.float32)
    rng = np.random.default_rng(7)
    up = jnp.asarray(rng.standard_normal((10, 10, 10)), jnp.float32)
    baked = apply_taps_padded(up, taps, mehrstellen=False)
    traced = apply_taps_padded_params(up, flat, jnp.asarray(weights))
    assert np.array_equal(np.asarray(baked), np.asarray(traced))


def test_scenario_member_eq_params_overlay():
    from heat3d_tpu.serve.scenario import (
        Scenario,
        ScenarioBatch,
        solver_bucket_key,
    )

    base = _cfg(family="advection-diffusion", backend="jnp")
    batch = ScenarioBatch(
        base,
        [
            Scenario(alpha=0.4, eq_params=(("vx", 0.5),)),
            Scenario(alpha=0.4, eq_params=(("vx", 0.9), ("vy", 0.2))),
        ],
    )
    c0, c1 = batch.member_config(0), batch.member_config(1)
    assert dict(c0.eq_params)["vx"] == 0.5
    assert dict(c1.eq_params) == {"vx": 0.9, "vy": 0.2}
    t0, t1 = batch.member_taps(0), batch.member_taps(1)
    assert not np.array_equal(t0, t1)  # per-member spec coefficients
    # family + base params bucket; member eq_params do NOT
    assert solver_bucket_key(base) != solver_bucket_key(_cfg(backend="jnp"))


# ---- provenance threading ---------------------------------------------------


def test_provenance_requires_equation_on_throughput_rows():
    from heat3d_tpu.analysis.provenance import check_row

    row = {
        "bench": "throughput", "ts": "2026-08-04T00:00:00Z",
        "platform": "cpu", "direct_path": False,
        "mehrstellen_route": False, "streamk_path": False,
        "streamk_emulated": False, "halo_plan": "monolithic",
        "chain_ops": 7, "batch_shape": [1], "members_per_step": 1,
        "sync_rtt_s": 0.0, "integrator": "explicit-euler",
    }
    assert any("equation" in p for p in check_row(dict(row)))
    row["equation"] = "advection-diffusion"
    assert not check_row(row)


def test_regress_keys_on_equation():
    from heat3d_tpu.obs.perf.regress import row_key

    base = {
        "bench": "throughput", "stencil": "7pt", "grid": [64] * 3,
        "mesh": [1, 1, 1], "dtype": "float32", "platform": "cpu",
    }
    k_heat = row_key(dict(base))  # legacy row: no field -> heat
    k_heat2 = row_key({**base, "equation": "heat"})
    k_fam = row_key({**base, "equation": "reaction-diffusion"})
    assert k_heat == k_heat2
    assert k_fam != k_heat


def test_sweepstate_key_suffix():
    from heat3d_tpu.resilience.sweepstate import row_key

    heat_key = row_key(_cfg(backend="jnp"), "throughput")
    fam_key = row_key(
        _cfg(family="reaction-diffusion", backend="jnp"), "throughput"
    )
    assert ":eq" not in heat_key  # legacy journals stay addressable
    assert ":eqreaction-diffusion" in fam_key


def test_bench_row_carries_equation():
    from heat3d_tpu.bench.harness import bench_throughput

    cfg = _cfg(family="aniso-diffusion", backend="jnp")
    row = bench_throughput(cfg, steps=2, repeats=1, warmup=0)
    assert row["equation"] == "aniso-diffusion"
    from heat3d_tpu.analysis.provenance import check_row

    assert not check_row(row)


# ---- the eqn-registry lint --------------------------------------------------


def test_eqnlint_clean_on_repo():
    from heat3d_tpu.analysis.eqnlint import check

    root = os.path.dirname(HERE)
    assert check(root) == []


def test_eqnlint_seeded_drift_fires():
    from heat3d_tpu.analysis.eqnlint import check
    from heat3d_tpu.eqn.families import EquationFamily

    root = os.path.dirname(HERE)
    ghost = EquationFamily(
        name="ghost-eqn", description="x", kinds=("7pt",), defaults=(),
        build=lambda k, p, a: None, mms_rates=None,
    )
    fams = dict(eqn.FAMILIES)
    fams["ghost-eqn"] = ghost
    findings = check(
        root,
        families=fams,
        cli_choices=sorted(eqn.FAMILIES) + ["phantom-choice"],
        doc_text="| `heat` |\n| `stale-doc-family` |\n",
        tests_text="'heat'",
    )
    codes = {(f.code, f.symbol) for f in findings}
    assert ("ANL521", "ghost-eqn") in codes       # registered, not on CLI
    assert ("ANL521", "phantom-choice") in codes  # CLI choice unregistered
    assert ("ANL522", "ghost-eqn") in codes       # undocumented family
    assert ("ANL522", "stale-doc-family") in codes  # stale docs row
    assert ("ANL523", "ghost-eqn") in codes       # no MMS reference
    assert ("ANL524", "ghost-eqn") in codes       # untested family


def test_lint_cli_includes_eqn_registry():
    from heat3d_tpu.analysis import CHECKERS

    assert CHECKERS["eqn-registry"] == "heat3d_tpu.analysis.eqnlint"


# ---- eqn CLI ----------------------------------------------------------------


def test_eqn_cli_list_and_show(capsys):
    from heat3d_tpu.eqn.cli import main

    assert main(["list", "--json"]) == 0
    import json

    fams = json.loads(capsys.readouterr().out)
    assert {f["name"] for f in fams} == set(eqn.FAMILIES)
    assert main(
        ["show", "advection-diffusion", "--eq-param", "vx=0.5", "--json"]
    ) == 0
    rec = json.loads(capsys.readouterr().out)
    # eq_params is the EFFECTIVE set (one resolution rule —
    # eqn.resolved_params); the raw overrides ride beside it
    assert rec["eq_params"] == {"vx": 0.5, "vy": 0.0, "vz": 0.0}
    assert rec["eq_param_overrides"] == {"vx": 0.5}
    assert rec["num_taps"] == 7
    assert rec["fingerprint"].startswith("advection-diffusion:7pt:")
    assert main(["show", "no-such-family"]) == 2
    assert main(["show", "heat", "--eq-param", "bogus"]) == 2


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
    # isolate from any operator tune cache: the auto-knob arm must
    # exercise the static fallback, not a local winner
    env["HEAT3D_TUNE_CACHE"] = os.path.join(
        env.get("TMPDIR", "/tmp"), "eqn_check_tune_cache.json"
    )
    return env


def test_eqn_acceptance_on_cpu_mesh_tier1():
    """Tier-1 acceptance: on a REAL 4-device CPU mesh, (1) spec-compiled
    heat is bitwise-identical to the legacy hardcoded path across
    tb{1,2} x axis/pairwise x monolithic/partitioned plans, (2) every
    new family matches its fp64 golden/analytic MMS oracle end-to-end
    (halo plans + tuner resolution included), (3) the serve traced bind
    serves per-member spec coefficients (baked mode bitwise vs solo)."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(HERE, "multidevice_checks.py"),
            "eqn",
        ],
        env=_cpu_mesh_env(4),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"eqn multidevice battery failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    for marker in (
        "eqn_heat_spec_vs_legacy_bitwise OK",
        "eqn_families_golden_distributed OK",
        "eqn_serve_traced_bind OK",
    ):
        assert marker in proc.stdout
