"""Performance-observability tests (tier-1, CPU): the regression gate's
verdicts on synthetic history (injected drop fails, unchanged passes,
CPU-fallback rows never compare against TPU records), the roofline live
table from real cost_analysis numbers, bench rows carrying the
cost-analysis fields, profile capture recording artifact + overhead into
the ledger (and failing soft), multihost ledger merge with skew stats,
and the span<->cost keying of phase_programs."""

import json
import os
import sys

import pytest

from heat3d_tpu import obs
from heat3d_tpu.obs.perf import regress
from heat3d_tpu.obs.perf.merge import merge_ledgers

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.deactivate()
    yield
    obs.deactivate()


def _read(path):
    return [json.loads(ln) for ln in open(path) if ln.strip()]


def _tput_row(gcell, platform="tpu", **over):
    row = {
        "bench": "throughput",
        "ts": "2026-08-01T00:00:00Z",
        "platform": platform,
        "grid": [256, 256, 256],
        "stencil": "7pt",
        "mesh": [1, 1, 1],
        "dtype": "float32",
        "compute_dtype": "float32",
        "backend": "auto",
        "time_blocking": 2,
        "overlap": False,
        "halo": "ppermute",
        "gcell_per_sec_per_chip": gcell,
        "sync_rtt_s": 0.001,
    }
    row.update(over)
    return row


def _halo_row(p50_us, **over):
    row = {
        "bench": "halo",
        "ts": "2026-08-01T00:00:00Z",
        "platform": "tpu",
        "grid": [256, 256, 256],
        "mesh": [1, 1, 1],
        "dtype": "float32",
        "halo": "ppermute",
        "p50_us": p50_us,
        "sync_rtt_s": 0.001,
    }
    row.update(over)
    return row


# ---- the regression gate -------------------------------------------------


def test_regress_injected_drop_fails():
    """A 20% throughput drop against the committed record must FAIL."""
    report = regress.compare([_tput_row(80.0)], [_tput_row(100.0)])
    assert report["verdict"] == "fail"
    (c,) = report["comparisons"]
    assert c["status"] == "fail" and c["regression_pct"] == pytest.approx(20.0)


def test_regress_unchanged_run_passes():
    report = regress.compare([_tput_row(100.0)], [_tput_row(100.0)])
    assert report["verdict"] == "pass"
    assert report["comparisons"][0]["status"] == "pass"


def test_regress_improvement_passes():
    report = regress.compare([_tput_row(130.0)], [_tput_row(100.0)])
    assert report["verdict"] == "pass"
    assert report["comparisons"][0]["regression_pct"] < 0


def test_regress_warn_band():
    report = regress.compare([_tput_row(90.0)], [_tput_row(100.0)])
    assert report["verdict"] == "warn"


def test_regress_cpu_row_never_compares_against_tpu_record():
    """Platform-aware baselines: a CPU(-fallback) row against a committed
    TPU record is NO comparison at all — no_baseline, verdict pass."""
    report = regress.compare(
        [_tput_row(0.5, platform="cpu")], [_tput_row(100.0, platform="tpu")]
    )
    assert report["verdict"] == "pass"
    assert not report["comparisons"]
    assert report["no_baseline"] and report["no_baseline"][0]["platform"] == "cpu"


def test_regress_legacy_rows_default_to_tpu_platform():
    """Rows predating the platform field are the on-chip record by
    convention (bench.py's rule) — they DO baseline a TPU row."""
    legacy = _tput_row(100.0)
    legacy.pop("platform")
    report = regress.compare([_tput_row(70.0, platform="tpu")], [legacy])
    assert report["verdict"] == "fail"


def test_regress_halo_direction_and_rtt_exclusion():
    """Halo latency regresses UPWARD; rtt_dominated rows are excluded on
    both sides."""
    report = regress.compare([_halo_row(70.0)], [_halo_row(50.0)])
    assert report["verdict"] == "fail"  # 40% slower exchange
    report = regress.compare(
        [_halo_row(70.0, rtt_dominated=True)], [_halo_row(50.0)]
    )
    assert not report["comparisons"] and report["skipped"]
    report = regress.compare(
        [_halo_row(70.0)], [_halo_row(50.0, rtt_dominated=True)]
    )
    assert not report["comparisons"]  # baseline was a link artifact


def test_regress_best_of_history_is_the_baseline():
    hist = [_tput_row(80.0), _tput_row(100.0), _tput_row(60.0)]
    report = regress.compare([_tput_row(95.0)], hist)
    assert report["comparisons"][0]["baseline"] == 100.0
    assert report["verdict"] == "pass"


def test_regress_driver_artifact_history(tmp_path):
    """BENCH_*.json driver artifacts join the history; a cpu_fallback
    record is classed cpu and never baselines a TPU driver row."""
    art = tmp_path / "BENCH_r9.json"
    art.write_text(
        json.dumps(
            {
                "parsed": {
                    "metric": "gcell_updates_per_sec_per_chip",
                    "value": 100.0,
                    "detail": {
                        "grid": 1024, "dtype": "fp32", "time_blocking": 2,
                        "backend": "auto", "platform": "tpu",
                    },
                }
            }
        )
    )
    rows = regress.load_history([str(art)])
    assert rows and rows[0]["bench"] == "driver"
    cur = dict(rows[0], value=75.0, _src="now")
    report = regress.compare([cur], rows)
    assert report["verdict"] == "fail"
    # the same artifact flagged cpu_fallback classes as cpu: no baseline
    cur_cpu = dict(cur, cpu_fallback=True)
    report = regress.compare([cur_cpu], rows)
    assert not report["comparisons"] and report["no_baseline"]


def test_regress_cli_end_to_end(tmp_path, capsys):
    """The CLI: --start-line scopes current rows, earlier lines of the
    same file are history, --json emits the machine verdict, rc=1 only
    on fail."""
    from heat3d_tpu.obs.perf.regress import main as regress_main

    out = tmp_path / "results.jsonl"
    with open(out, "w") as f:
        f.write(json.dumps(_tput_row(100.0)) + "\n")  # prior session
        f.write(json.dumps(_tput_row(80.0)) + "\n")   # this session
    rc = regress_main([str(out), "--start-line", "2", "--history", "--json"])
    rep = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and rep["verdict"] == "fail"
    # unchanged session rc=0
    with open(out, "w") as f:
        f.write(json.dumps(_tput_row(100.0)) + "\n")
        f.write(json.dumps(_tput_row(100.0)) + "\n")
    rc = regress_main([str(out), "--start-line", "2", "--history", "--json"])
    rep = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and rep["verdict"] == "pass"


# ---- roofline -------------------------------------------------------------


def test_phase_programs_keyed_like_spans():
    """The cost-analysis compile targets share the named_phase keys —
    the span<->cost join contract."""
    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
    from heat3d_tpu.parallel.step import (
        PHASE_HALO,
        PHASE_RESIDUAL,
        PHASE_STENCIL,
        PHASE_STEP,
        phase_programs,
    )
    from heat3d_tpu.parallel.topology import build_mesh

    cfg = SolverConfig(
        grid=GridConfig.cube(8), mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp",
    )
    programs = phase_programs(cfg, build_mesh(cfg.mesh))
    assert {PHASE_STEP, PHASE_HALO, PHASE_STENCIL, PHASE_RESIDUAL} == set(
        programs
    )


def test_roofline_live_table_on_cpu(capsys):
    """Acceptance: `heat3d obs roofline` runs on CPU using cost_analysis
    numbers and prints a per-phase achieved-vs-peak table."""
    from heat3d_tpu.obs.perf.roofline import main as roofline_main

    rc = roofline_main(["--grid", "16", "--iters", "1", "--backend", "jnp"])
    out = capsys.readouterr().out
    assert rc == 0
    for phase in ("step", "halo_exchange", "stencil", "residual"):
        assert phase in out
    assert "%mem" in out and "GFLOP/s" in out  # achieved-vs-peak columns


def test_roofline_live_json_has_positive_costs(capsys):
    from heat3d_tpu.obs.perf.roofline import main as roofline_main

    rc = roofline_main(
        ["--grid", "16", "--iters", "1", "--backend", "jnp", "--json"]
    )
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip())
    by_phase = {r["phase"]: r for r in rep["phases"]}
    assert by_phase["stencil"]["flops"] and by_phase["stencil"]["flops"] > 0
    assert by_phase["step"]["bytes"] and by_phase["step"]["bytes"] > 0
    assert by_phase["stencil"]["seconds"] > 0


def test_roofline_row_mode_matches_promoted_script(tmp_path, capsys):
    """Row mode (the promoted scripts/roofline_check.py): prints the
    ceiling table for throughput rows; the script wrapper exposes the
    same main."""
    rows = tmp_path / "rows.jsonl"
    with open(rows, "w") as f:
        f.write(json.dumps(_tput_row(100.0, chain_ops=8)) + "\n")
    from heat3d_tpu.obs.perf.roofline import main as roofline_main

    rc = roofline_main([str(rows)])
    out = capsys.readouterr().out
    assert rc == 0 and "ceiling" in out and "achieved" in out
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "roofline_check", os.path.join(REPO, "scripts", "roofline_check.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main is roofline_main


def test_step_cost_fields_and_bench_row_schema(tmp_path):
    """Bench throughput rows carry the cost-analysis fields, and
    record_step_cost writes the step_cost ledger event."""
    from heat3d_tpu.bench.harness import bench_throughput
    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.obs.perf.roofline import record_step_cost, step_cost_fields

    cfg = SolverConfig(
        grid=GridConfig.cube(8), mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp",
    )
    fields = step_cost_fields(HeatSolver3D(cfg))
    assert fields["cost_flops_per_step"] > 0
    assert fields["cost_bytes_per_step"] > 0

    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    row = bench_throughput(cfg, steps=2, warmup=1, repeats=1)
    assert row["cost_flops_per_step"] == fields["cost_flops_per_step"]
    assert "cost_bytes_per_step" in row
    record_step_cost(HeatSolver3D(cfg))
    obs.deactivate()
    evs = _read(led)
    costs = [e for e in evs if e["event"] == "step_cost"]
    assert costs and costs[0]["ok"] is True
    assert costs[0]["cost_flops_per_step"] == fields["cost_flops_per_step"]
    # the mirrored bench_row event carries the fields too (summary joins)
    bench_rows = [e for e in evs if e["event"] == "bench_row"]
    assert bench_rows and bench_rows[0]["cost_flops_per_step"] == fields[
        "cost_flops_per_step"
    ]


def test_step_cost_fields_tb2_costs_the_superstep():
    """At time_blocking > 1 the cost fields must describe the program the
    loop actually runs — the k-update superstep normalized per update —
    not the single step (which the tb=2 loop never executes)."""
    import dataclasses

    import jax

    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.obs.perf.roofline import extract_cost, step_cost_fields
    from heat3d_tpu.parallel.step import make_superstep_fn

    cfg1 = SolverConfig(
        grid=GridConfig.cube(16), mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp",
    )
    cfg2 = dataclasses.replace(cfg1, time_blocking=2)
    f1 = step_cost_fields(HeatSolver3D(cfg1))
    solver2 = HeatSolver3D(cfg2)
    f2 = step_cost_fields(solver2)
    # per-update numbers == the SUPERSTEP program's cost / 2, and the
    # superstep (width-2 exchange + ghost-ring recompute) is a different
    # program from the single step — the fields must reflect that
    aval = jax.ShapeDtypeStruct(
        cfg2.padded_shape, solver2.storage_dtype, sharding=solver2.sharding
    )
    compiled = (
        jax.jit(make_superstep_fn(cfg2, solver2.mesh, solver2._compute))
        .lower(aval)
        .compile()
    )
    flops, bytes_ = extract_cost(compiled.cost_analysis())
    assert f2["cost_flops_per_step"] == pytest.approx(flops / 2)
    assert f2["cost_bytes_per_step"] == pytest.approx(bytes_ / 2)
    assert f2["cost_flops_per_step"] != f1["cost_flops_per_step"]


def test_step_cost_env_gate_and_fail_soft(tmp_path, monkeypatch):
    """HEAT3D_COST_ANALYSIS=0 skips; a broken solver degrades to an
    ok:false event, never an exception (acceptance: perf telemetry fails
    soft)."""
    from heat3d_tpu.obs.perf.roofline import record_step_cost

    monkeypatch.setenv("HEAT3D_COST_ANALYSIS", "0")
    assert record_step_cost(object()) is None
    monkeypatch.delenv("HEAT3D_COST_ANALYSIS")
    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    assert record_step_cost(object()) is None  # no .cfg: raises inside
    obs.deactivate()
    evs = [e for e in _read(led) if e["event"] == "step_cost"]
    assert evs and evs[0]["ok"] is False and "error" in evs[0]


def test_summary_roofline_section(tmp_path, capsys):
    """obs summary prints the roofline section from a step_cost event +
    run_loop span pair."""
    from heat3d_tpu.obs.cli import main as obs_main

    led = str(tmp_path / "led.jsonl")
    ledger = obs.activate(led)
    ledger.event(
        "step_cost", ok=True, platform="cpu", device_kind="cpu",
        cost_flops_per_step=2.0e9, cost_bytes_per_step=4.0e9,
    )
    with ledger.span("run_loop") as sp:
        sp.add(steps=10)
        import time

        time.sleep(0.01)
    obs.deactivate()
    rc = obs_main(["summary", led])
    out = capsys.readouterr().out
    assert rc == 0 and "roofline run_loop [cpu]" in out
    assert "GB/s" in out and "of peak" not in out  # no published CPU peak


def test_step_cost_fields_deep_tb_raw_vs_effective():
    """Deep-tb cost fields carry the redundant-compute honesty pair: the
    per-update flops stay RAW (what the chip executes) and the effective
    side discounts them by the analytic trapezoid frac."""
    import dataclasses

    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.obs.perf.roofline import step_cost_fields
    from heat3d_tpu.parallel.step import redundant_flops_frac

    cfg = SolverConfig(
        grid=GridConfig.cube(8), mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp", time_blocking=3,
    )
    f = step_cost_fields(HeatSolver3D(cfg))
    frac = redundant_flops_frac(cfg)
    assert 0.0 < frac < 1.0
    assert f["cost_redundant_flops_frac"] == frac
    assert f["cost_effective_flops_per_step"] == pytest.approx(
        f["cost_flops_per_step"] * (1 - frac)
    )
    f1 = step_cost_fields(
        HeatSolver3D(dataclasses.replace(cfg, time_blocking=1))
    )
    assert f1["cost_redundant_flops_frac"] == 0.0
    assert f1["cost_effective_flops_per_step"] == f1["cost_flops_per_step"]


def test_bench_rows_carry_redundant_frac_and_halo_bytes(tmp_path):
    """tb>1 throughput rows carry cost_redundant_flops_frac (required by
    scripts/check_provenance.py), halo rows carry the exchange program's
    cost_bytes_per_step, and the provenance lint enforces the tb>1 rule."""
    import dataclasses

    from heat3d_tpu.bench.harness import bench_halo, bench_throughput
    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig

    cfg = SolverConfig(
        grid=GridConfig.cube(8), mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp", time_blocking=2,
    )
    row = bench_throughput(cfg, steps=2, warmup=1, repeats=1)
    assert row["cost_redundant_flops_frac"] > 0
    assert row["streamk_path"] is False  # jnp backend pins the exchange path
    assert row["streamk_emulated"] is False
    halo = bench_halo(
        dataclasses.replace(cfg, time_blocking=1), iters=2, warmup=1, k=2
    )
    assert halo["cost_bytes_per_step"] > 0

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_provenance as cp
    finally:
        sys.path.pop(0)
    assert cp.check_row(row) == []
    assert cp.check_row(halo) == []
    broken = dict(row)
    broken.pop("cost_redundant_flops_frac")
    assert any(
        "cost_redundant_flops_frac" in p for p in cp.check_row(broken)
    )
    # tb=1 rows are exempt (the committed legacy record predates the field)
    tb1 = dict(row)
    tb1["time_blocking"] = 1
    tb1.pop("cost_redundant_flops_frac")
    assert cp.check_row(tb1) == []


def test_summary_roofline_halo_and_recompute_lines(tmp_path, capsys):
    """obs summary's roofline section prints (a) the halo p50's own
    achieved-vs-peak line from a halo bench_row's cost bytes and (b) the
    recompute discount on deep-tb throughput rows."""
    from heat3d_tpu.obs.cli import main as obs_main

    led = str(tmp_path / "led.jsonl")
    ledger = obs.activate(led)
    ledger.event(
        "bench_row", bench="halo", platform="tpu", device_kind="TPU v5 lite",
        grid=[32, 32, 32], p50_us=100.0, cost_bytes_per_step=2.0e6,
    )
    # rtt-dominated halo rows are excluded (the `obs regress` convention:
    # their p50 is dispatch overhead, not transport) — must NOT print
    ledger.event(
        "bench_row", bench="halo", platform="cpu", grid=[16, 16, 16],
        p50_us=5.0, cost_bytes_per_step=2.0e6, rtt_dominated=True,
    )
    ledger.event(
        "bench_row", bench="throughput", platform="cpu",
        grid=[32, 32, 32], time_blocking=4, steps=10, seconds_best=0.1,
        cost_flops_per_step=1.0e9, cost_bytes_per_step=2.0e9,
        cost_redundant_flops_frac=0.25,
    )
    obs.deactivate()
    rc = obs_main(["summary", led])
    out = capsys.readouterr().out
    assert rc == 0
    assert "roofline halo 32x32x32 p50 [TPU v5 lite]" in out
    assert "20.00 GB/s (2.4% of peak)" in out  # 2e6 B / 100 us vs 819 GB/s
    assert "halo 16x16x16" not in out  # rtt_dominated: excluded
    assert "tb=4 (25% recompute)" in out


# ---- profiling capture ----------------------------------------------------


def test_profile_capture_records_artifact_and_overhead(tmp_path):
    from heat3d_tpu.utils.timing import maybe_profile

    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    pdir = str(tmp_path / "trace")
    with maybe_profile(pdir):
        import jax.numpy as jnp

        (jnp.zeros((8, 8)) + 1).block_until_ready()
    obs.deactivate()
    evs = [e for e in _read(led) if e["event"] == "profile_capture"]
    assert len(evs) == 1
    e = evs[0]
    assert e["ok"] is True and e["dir"] == pdir
    assert e["enter_overhead_s"] >= 0 and e["exit_overhead_s"] >= 0
    # the artifact is the .xplane.pb summarize_trace.py reads
    assert e.get("artifact", "").endswith(".xplane.pb")
    assert os.path.exists(e["artifact"])


def test_profile_capture_fails_soft(tmp_path, capsys):
    """A profiler that cannot start must not kill the observed run: the
    body still executes and the ledger says capture degraded."""
    from heat3d_tpu.obs.perf.profiling import profile_capture

    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    ran = []
    # a FILE where the profiler wants a directory
    bad = tmp_path / "notadir"
    bad.write_text("x")
    with profile_capture(str(bad)):
        ran.append(True)
    obs.deactivate()
    assert ran == [True]
    evs = [e for e in _read(led) if e["event"] == "profile_capture"]
    assert len(evs) == 1
    assert evs[0]["ok"] is False and "error" in evs[0]
    # and the failed capture must not poison the process-wide profiler:
    # a later capture into a good dir still produces its artifact
    led2 = str(tmp_path / "led2.jsonl")
    obs.activate(led2)
    good = str(tmp_path / "trace2")
    with profile_capture(good):
        import jax.numpy as jnp

        (jnp.zeros((4, 4)) + 1).block_until_ready()
    obs.deactivate()
    evs2 = [e for e in _read(led2) if e["event"] == "profile_capture"]
    assert evs2 and evs2[0]["ok"] is True


def test_profile_capture_noop_without_dir():
    from heat3d_tpu.obs.perf.profiling import profile_capture

    with profile_capture(None):
        pass
    with profile_capture(""):
        pass


# ---- multihost ledger merge ----------------------------------------------


def _fake_ledger(path, proc, skew, events=("ledger_open", "run_start", "run_summary")):
    with open(path, "w") as f:
        for i, ev in enumerate(events):
            f.write(
                json.dumps(
                    {
                        "ts": 1000.0 + skew + i,
                        "run_id": f"run{proc}",
                        "proc": proc,
                        "seq": i,
                        "event": ev,
                        "kind": "point",
                    }
                )
                + "\n"
            )


def test_merge_timeline_and_skew(tmp_path):
    p0, p1 = str(tmp_path / "p0.jsonl"), str(tmp_path / "p1.jsonl")
    _fake_ledger(p0, 0, 0.0)
    _fake_ledger(p1, 1, 2.5)
    result = merge_ledgers([p0, p1])
    evs = result["events"]
    assert len(evs) == 6
    # one timeline: sorted by wall ts, src-tagged
    tss = [e["ts"] for e in evs]
    assert tss == sorted(tss)
    assert {e["src"] for e in evs} == {"p0.jsonl", "p1.jsonl"}
    stats = result["stats"]
    assert stats["anchor_event"] == "run_start"
    assert stats["max_skew_s"] == pytest.approx(2.5)
    assert stats["sources"]["p1.jsonl"]["skew_s"] == pytest.approx(2.5)
    assert stats["sources"]["p0.jsonl"]["skew_s"] == 0.0
    assert stats["anchor_spreads_s"]["run_start"] == pytest.approx(2.5)


def test_merge_cli_writes_lintable_file(tmp_path, capsys):
    from heat3d_tpu.obs import check as ledger_check
    from heat3d_tpu.obs.perf.merge import main as merge_main

    p0, p1 = str(tmp_path / "p0.jsonl"), str(tmp_path / "p1.jsonl")
    _fake_ledger(p0, 0, 0.0)
    _fake_ledger(p1, 1, 0.5)
    out = str(tmp_path / "merged.jsonl")
    rc = merge_main([p0, p1, "-o", out, "--json"])
    stats = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and stats["total_events"] == 6
    # the merged timeline still passes the ledger lint: per-(run_id, proc)
    # streams keep their seq order under the stable ts sort
    assert ledger_check.check_file(out) == []


def test_merge_missing_anchor_degrades(tmp_path):
    p0, p1 = str(tmp_path / "p0.jsonl"), str(tmp_path / "p1.jsonl")
    _fake_ledger(p0, 0, 0.0, events=("ledger_open", "run_start"))
    _fake_ledger(p1, 1, 1.0, events=("ledger_open",))
    stats = merge_ledgers([p0, p1])["stats"]
    assert stats["anchor_event"] == "ledger_open"  # first COMMON preference
    assert stats["max_skew_s"] == pytest.approx(1.0)


# ---- unified timeline (obs/perf/timeline.py) ------------------------------


def _fixture_ledger_events():
    """A deterministic two-run-segment ledger: one run_start point, a
    warmup span, and chunk spans with known t0/t1/ts placement. Spans are
    written at close (ts = wall at t1), so wall start is ts - dur_s."""
    evs = []
    t0 = 1000.0  # wall anchor

    def point(name, ts, **f):
        evs.append({"ts": ts, "run_id": "r1", "proc": 0, "seq": len(evs),
                    "event": name, "kind": "point", **f})

    def span(name, start, dur, **f):
        evs.append({"ts": start + dur, "run_id": "r1", "proc": 0,
                    "seq": len(evs), "event": name, "kind": "span",
                    "t0": 5.0 + (start - t0), "t1": 5.0 + (start - t0) + dur,
                    "dur_s": dur, "depth": 0, "status": "ok", **f})

    point("run_start", t0)
    span("warmup", t0 + 0.5, 0.25)
    span("chunk", t0 + 1.0, 0.4, steps=4)
    span("chunk", t0 + 1.5, 0.4, steps=4)
    point("run_summary", t0 + 2.0)
    return evs


def test_timeline_chrome_trace_golden(tmp_path):
    """Golden Chrome-trace export from a fixture ledger + fake profile
    totals: spans land as X slices at ts - dur with exact us placement,
    points as instants, and the profile's per-phase aggregate as its own
    labelled track."""
    from heat3d_tpu.obs.perf.timeline import timeline_events, to_chrome_trace

    tl = timeline_events(_fixture_ledger_events())
    doc = to_chrome_trace(tl, profile_totals={"stencil": 800.0, "halo_exchange": 200.0})
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    # base is the earliest wall time = run_start at t0
    x = [e for e in evs if e.get("ph") == "X"]
    inst = [e for e in evs if e.get("ph") == "i"]
    meta = [e for e in evs if e.get("ph") == "M"]
    warm = next(e for e in x if e["name"] == "warmup")
    assert warm["ts"] == pytest.approx(0.5e6) and warm["dur"] == pytest.approx(0.25e6)
    chunks = [e for e in x if e["name"] == "chunk"]
    assert [c["ts"] for c in chunks] == [pytest.approx(1.0e6), pytest.approx(1.5e6)]
    assert {e["name"] for e in inst} == {"run_start", "run_summary"}
    assert next(e for e in inst if e["name"] == "run_start")["ts"] == 0.0
    # profile aggregate track: its own pid, one named thread per phase
    names = {e["args"]["name"] for e in meta if e["name"] == "process_name"}
    assert "ledger/proc0" in names
    assert "device profile (per-phase aggregate)" in names
    prof = [e for e in x if e["name"] in ("stencil", "halo_exchange")]
    assert {e["name"]: e["dur"] for e in prof} == {
        "stencil": 800.0, "halo_exchange": 200.0}
    # the whole doc round-trips as JSON (what the CLI writes)
    json.loads(json.dumps(doc))


def test_timeline_cli_writes_trace_and_json(tmp_path, capsys):
    from heat3d_tpu.obs.perf import timeline

    led = tmp_path / "led.jsonl"
    with open(led, "w") as f:
        for e in _fixture_ledger_events():
            f.write(json.dumps(e) + "\n")
    out = tmp_path / "trace.json"
    assert timeline.main([str(led), "-o", str(out), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["events"] == 5 and rep["spans"] == 3
    assert rep["out"] == str(out)
    doc = json.load(open(out))
    assert any(e.get("name") == "chunk" for e in doc["traceEvents"])
    # unreadable ledger: rc 2, not a traceback
    assert timeline.main([str(tmp_path / "nope.jsonl"), "--json"]) == 2


def test_device_phase_totals_duck_typed_and_halo_fold():
    """The measured side of the roofline join, proto-free: device planes
    aggregate ONE line each, heat3d.halo.<axis> sub-scopes fold into
    halo_exchange, and unscoped time stays (unattributed)."""
    from types import SimpleNamespace

    from heat3d_tpu.obs.perf.timeline import (
        device_phase_totals,
        normalize_phase,
    )

    def ev(mid, dur_us):
        return SimpleNamespace(metadata_id=mid, duration_ps=dur_us * 1e6)

    meta = {
        1: SimpleNamespace(name="heat3d.step/heat3d.stencil/fusion.1"),
        2: SimpleNamespace(name="heat3d.halo_exchange/heat3d.halo.x/ppermute.2"),
        3: SimpleNamespace(name="heat3d.halo_exchange/heat3d.halo.y/ppermute.3"),
        4: SimpleNamespace(name="copy.9"),
    }
    plane = SimpleNamespace(
        name="/device:TPU:0",
        lines=[
            SimpleNamespace(name="XLA Ops",
                            events=[ev(1, 40.0), ev(2, 6.0), ev(3, 4.0), ev(4, 2.0)]),
            SimpleNamespace(name="XLA Modules", events=[ev(4, 52.0)]),
        ],
        event_metadata=meta,
    )
    totals = device_phase_totals(SimpleNamespace(planes=[plane]))
    assert totals["stencil"] == pytest.approx(40.0)
    assert totals["halo_exchange"] == pytest.approx(10.0)  # x + y folded
    assert totals["(unattributed)"] == pytest.approx(2.0)
    assert normalize_phase("heat3d.halo.z") == "halo_exchange"
    assert normalize_phase("heat3d.step") == "step"


def test_device_phase_totals_fold_named_kernels_into_their_phase():
    """A named Pallas kernel's op (``heat3d.stencil.<family>.<n>``, as
    XLA names the custom call after the kernel's ``name=``) lands in its
    phase: the stencil families in stencil, the halo DMA kernels in
    halo_exchange — no orphan ``stencil.<family>`` row."""
    from types import SimpleNamespace

    from heat3d_tpu.obs.perf.timeline import device_phase_totals

    def ev(mid, dur_us):
        return SimpleNamespace(metadata_id=mid, duration_ps=dur_us * 1e6)

    meta = {
        1: SimpleNamespace(name="heat3d.stencil.direct2.1"),
        2: SimpleNamespace(name="heat3d.step/heat3d.stencil.streamk.4"),
        3: SimpleNamespace(name="heat3d.stencil.direct.3"),
        5: SimpleNamespace(name="heat3d.halo.x.dma.2"),
    }
    plane = SimpleNamespace(
        name="/device:TPU:0",
        lines=[SimpleNamespace(
            name="XLA Ops",
            events=[ev(1, 30.0), ev(2, 10.0), ev(3, 7.0), ev(5, 3.0)],
        )],
        event_metadata=meta,
    )
    totals = device_phase_totals(SimpleNamespace(planes=[plane]))
    assert totals == pytest.approx(
        {"stencil": 47.0, "halo_exchange": 3.0}
    )


def _write_synthetic_xplane(tmp_path, stencil_us=40.0, halo_us=10.0,
                            step_us=0.0):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    )
    xs = xplane_pb2.XSpace()
    p = xs.planes.add()
    p.name = "/device:TPU:0"
    p.event_metadata[1].id = 1
    p.event_metadata[1].name = "heat3d.step/heat3d.stencil/fusion.1"
    p.event_metadata[2].id = 2
    p.event_metadata[2].name = "heat3d.halo_exchange/heat3d.halo.x/ppermute.3"
    p.event_metadata[3].id = 3
    p.event_metadata[3].name = "heat3d.step/copy.5"  # dispatch glue
    ln = p.lines.add()
    ln.name = "XLA Ops"
    for mid, us in ((1, stencil_us), (2, halo_us), (3, step_us)):
        if us <= 0:
            continue
        ev = ln.events.add()
        ev.metadata_id = mid
        ev.duration_ps = int(us * 1e6)
    path = tmp_path / "prof" / "t.xplane.pb"
    os.makedirs(path.parent, exist_ok=True)
    path.write_bytes(xs.SerializeToString())
    return str(path.parent)


def test_roofline_from_profile_join_acceptance(tmp_path, capsys):
    """THE acceptance criterion (ROADMAP PR 3 carry-over retired):
    `heat3d obs roofline --from-profile DIR` on a CPU-capture fixture
    prints a per-phase achieved-vs-peak table from MEASURED device times
    — stencil and halo rows with a fraction of each peak."""
    from heat3d_tpu.obs.perf import roofline

    prof = _write_synthetic_xplane(
        tmp_path, stencil_us=40.0, halo_us=10.0, step_us=2.0
    )
    rc = roofline.main(
        ["--from-profile", prof, "--grid", "16", "--steps", "4",
         "--backend", "jnp", "--json"]
    )
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["steps"] == 4
    by_phase = {r["phase"]: r for r in rep["phases"]}
    stencil, halo = by_phase["stencil"], by_phase["halo_exchange"]
    # measured device time from the fixture, split over 4 calls
    assert stencil["device_us"] == pytest.approx(40.0)
    assert stencil["calls"] == 4
    assert stencil["seconds"] == pytest.approx(10e-6)
    assert halo["device_us"] == pytest.approx(10.0)
    # achieved rates divide REAL cost_analysis numbers by measured time
    assert stencil["bytes"] and stencil["gbps"] == pytest.approx(
        stencil["bytes"] / 10e-6 / 1e9
    )
    assert halo["bytes"] and halo["gbps"] > 0
    # shares of attributed device time: 40/52, 10/52, 2/52
    assert stencil["share"] == pytest.approx(40 / 52, abs=1e-3)
    assert halo["share"] == pytest.approx(10 / 52, abs=1e-3)
    # the step scope's device time is EXCLUSIVE (dispatch glue only):
    # it reports time + share but NO achieved rate — full-program cost
    # over glue-only seconds would report absurd fractions of peak
    step = by_phase["step"]
    assert step["device_us"] == pytest.approx(2.0)
    assert step.get("seconds") is None and step.get("gflops") is None
    # and the human table renders with the peak columns
    rc = roofline.main(
        ["--from-profile", prof, "--grid", "16", "--steps", "4",
         "--backend", "jnp"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "roofline from profile" in out and "%mem" in out
    assert "stencil" in out and "halo_exchange" in out


def test_roofline_from_profile_steps_from_ledger(tmp_path, capsys):
    from heat3d_tpu.obs.perf import roofline

    prof = _write_synthetic_xplane(tmp_path)
    led = tmp_path / "led.jsonl"
    with open(led, "w") as f:
        for e in _fixture_ledger_events():  # run r1: two chunk spans x 4
            f.write(json.dumps(e) + "\n")
    rc = roofline.main(
        ["--from-profile", prof, "--ledger", str(led), "--grid", "16",
         "--backend", "jnp", "--json"]
    )
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["steps"] == 8
    # an APPEND-session ledger holds MANY run segments but the capture
    # covers one: the step count comes from the LAST segment with step
    # spans, and --run selects another explicitly
    with open(led, "a") as f:
        f.write(json.dumps({
            "ts": 2000.0, "run_id": "r2", "proc": 0, "seq": 0,
            "event": "run_loop", "kind": "span", "t0": 0.0, "t1": 0.3,
            "dur_s": 0.3, "depth": 0, "status": "ok", "steps": 3,
        }) + "\n")
    for flags, want in ((["--ledger", str(led)], 3),
                        (["--ledger", str(led), "--run", "r1"], 8)):
        rc = roofline.main(
            ["--from-profile", prof, "--grid", "16", "--backend", "jnp",
             "--json"] + flags
        )
        assert rc == 0
        rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rep["steps"] == want
    # a missing capture is a clean rc 1, not a traceback
    assert roofline.main(
        ["--from-profile", str(tmp_path / "empty"), "--grid", "16"]
    ) == 1
    # an unreadable ledger is a clean rc 2, not a traceback
    assert roofline.main(
        ["--from-profile", prof, "--ledger", str(tmp_path / "nope.jsonl"),
         "--grid", "16", "--backend", "jnp"]
    ) == 2
    # a --run id absent from the ledger degrades to steps=1 with an
    # honest note naming the run, not the false "no --steps/--ledger"
    rc = roofline.main(
        ["--from-profile", prof, "--ledger", str(led), "--run", "typo",
         "--grid", "16", "--backend", "jnp", "--json"]
    )
    assert rc == 0
    captured = capsys.readouterr()
    rep = json.loads(captured.out.strip().splitlines()[-1])
    assert rep["steps"] == 1
    assert "no ok step spans for run typo" in captured.err


# ---- drift / straggler detection ------------------------------------------


def _chunk_span(dur, steps=4, proc=0, src="", seq=0, ts=0.0):
    e = {"ts": ts, "run_id": "r1", "proc": proc, "seq": seq,
         "event": "chunk", "kind": "span", "t0": 0.0, "t1": dur,
         "dur_s": dur, "depth": 0, "status": "ok", "steps": steps}
    if src:
        e["src"] = src
    return e


def test_drift_detector_flags_injected_slowdown():
    """Steady 100ms/step chunks then a sustained 2x slowdown: every
    drifted sample past the seed window is flagged FAIL, and the rolling
    baseline is NOT poisoned by the flagged samples (the last anomaly
    still judges against the healthy baseline)."""
    from heat3d_tpu.obs.perf.timeline import detect_anomalies

    evs = [_chunk_span(0.4, seq=i, ts=float(i)) for i in range(6)]
    evs += [_chunk_span(0.8, seq=6 + i, ts=6.0 + i) for i in range(3)]
    anoms = detect_anomalies(evs)
    drifts = [a for a in anoms if a["kind_"] == "span_drift"]
    assert len(drifts) == 3
    for a in drifts:
        assert a["status"] == "fail"
        assert a["delta_pct"] == pytest.approx(100.0, abs=0.1)
        assert a["baseline_s"] == pytest.approx(0.1)  # per-step, unpoisoned
        assert a["per_step"] is True
    # a steady ledger detects nothing
    assert detect_anomalies(
        [_chunk_span(0.4, seq=i, ts=float(i)) for i in range(10)]
    ) == []


def test_drift_detector_warn_band_and_custom_tolerance():
    from heat3d_tpu.obs.perf.timeline import detect_anomalies

    evs = [_chunk_span(0.4, seq=i, ts=float(i)) for i in range(6)]
    evs.append(_chunk_span(0.44, seq=6, ts=6.0))  # +10%: warn band
    anoms = detect_anomalies(evs)
    assert [a["status"] for a in anoms] == ["warn"]
    # widened bands: the same ledger is clean
    assert detect_anomalies(evs, warn_pct=20.0, fail_pct=30.0) == []


def test_straggler_detector_on_merged_streams(tmp_path):
    """Two src-tagged streams (an obs-merge'd pod ledger): the host
    whose step p50 sits 2x above the fleet median is flagged; the
    anomalies land as obs_anomaly ledger events that pass the taxonomy
    lint."""
    from heat3d_tpu.obs.perf.timeline import detect_anomalies, emit_anomalies

    evs = []
    for i in range(5):
        evs.append(_chunk_span(0.4, proc=0, src="h0.jsonl", seq=i, ts=float(i)))
        evs.append(_chunk_span(0.4, proc=0, src="h1.jsonl", seq=i, ts=float(i)))
        evs.append(_chunk_span(0.8, proc=0, src="h2.jsonl", seq=i, ts=float(i)))
    anoms = detect_anomalies(evs)
    stragglers = [a for a in anoms if a["kind_"] == "host_straggler"]
    assert len(stragglers) == 1
    s = stragglers[0]
    assert s["src"] == "h2.jsonl" and s["status"] == "fail"
    assert s["delta_pct"] == pytest.approx(100.0, abs=0.1)

    led = str(tmp_path / "anom.jsonl")
    obs.activate(led, meta={"entry": "test"})
    emit_anomalies(anoms)
    obs.deactivate(rc=0)
    recorded = [e for e in _read(led) if e["event"] == "obs_anomaly"]
    assert len(recorded) == len(anoms)
    assert recorded[0]["kind_"] == "host_straggler"
    from heat3d_tpu.obs.check import main as check_main

    assert check_main(["--taxonomy", led]) == 0


def test_timeline_cli_multiledger_straggler(tmp_path, capsys):
    """Several ledger paths merge src-tagged on the way into the CLI, so
    the straggler surfaces from per-host files without a manual merge."""
    from heat3d_tpu.obs.perf import timeline

    for host, dur in (("h0", 0.4), ("h1", 0.4), ("h2", 1.2)):
        with open(tmp_path / f"{host}.jsonl", "w") as f:
            for i in range(5):
                f.write(json.dumps(_chunk_span(dur, seq=i, ts=float(i))) + "\n")
    rc = timeline.main(
        [str(tmp_path / f"{h}.jsonl") for h in ("h0", "h1", "h2")]
        + ["--json"]
    )
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["streams"] == 3
    stragglers = [
        a for a in rep["anomalies"] if a["kind_"] == "host_straggler"
    ]
    assert len(stragglers) == 1 and stragglers[0]["src"] == "h2.jsonl"


def test_summary_prints_anomaly_section(tmp_path, capsys):
    """obs summary gains the drift section: an injected-drift ledger
    prints ANOMALY lines from the same detector."""
    from heat3d_tpu.obs.cli import main as obs_main

    led = tmp_path / "led.jsonl"
    with open(led, "w") as f:
        for i in range(6):
            f.write(json.dumps(_chunk_span(0.4, seq=i, ts=float(i))) + "\n")
        f.write(json.dumps(_chunk_span(1.0, seq=6, ts=6.0)) + "\n")
    assert obs_main(["summary", str(led)]) == 0
    out = capsys.readouterr().out
    assert "ANOMALY" in out and "chunk" in out


# ---- SLOs (obs/perf/slo.py) ------------------------------------------------


def _slo_ledger(tmp_path, p95=0.2, step_dur=None):
    led = tmp_path / "slo_led.jsonl"
    evs = [
        {"ts": 1.0, "run_id": "r", "proc": 0, "seq": 0,
         "event": "serve_metrics_summary", "kind": "point",
         "buckets": {"((16, 16, 16), 'x')": {
             "count": 8, "p50_s": p95 / 2, "p95_s": p95, "max_s": p95}},
         "depth_max": 8, "batches": 2, "delivered": 8, "pending": 0},
    ]
    if step_dur is not None:
        evs.append({"ts": 2.0, "run_id": "r", "proc": 0, "seq": 1,
                    "event": "run_loop", "kind": "span", "t0": 0.0,
                    "t1": step_dur, "dur_s": step_dur, "depth": 0,
                    "status": "ok", "steps": 10})
    with open(led, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    return str(led)


def _slo_spec(tmp_path, max_s, name="queue-p95", **extra):
    spec = {"objectives": [
        {"name": name, "kind": "serve_latency", "percentile": 95,
         "max_s": max_s, **extra}]}
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    return str(path)


def test_slo_rc_semantics_pass_warn_breach(tmp_path, capsys):
    """rc mirrors obs regress: 1 ONLY on breach — pass, warn, and
    no-data all exit 0."""
    from heat3d_tpu.obs.perf import slo

    led = _slo_ledger(tmp_path, p95=0.2)
    # pass: 0.2 vs 1.0 ceiling (burn 0.2)
    assert slo.main([led, "--spec", _slo_spec(tmp_path, 1.0), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["verdict"] == "pass"
    assert rep["objectives"][0]["burn_rate"] == pytest.approx(0.2)
    # warn: 0.2 vs 0.21 ceiling (burn ~0.95 >= warn_ratio 0.9) — still rc 0
    assert slo.main([led, "--spec", _slo_spec(tmp_path, 0.21), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["verdict"] == "warn"
    # breach: 0.2 vs 0.1 ceiling — rc 1
    assert slo.main([led, "--spec", _slo_spec(tmp_path, 0.1), "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["verdict"] == "breach"
    assert rep["objectives"][0]["burn_rate"] == pytest.approx(2.0)
    # no data: a bucket filter matching nothing — rc 0, status no_data
    assert slo.main(
        [led, "--spec", _slo_spec(tmp_path, 0.1, bucket="(999,"), "--json"]
    ) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["verdict"] == "pass"
    assert rep["objectives"][0]["status"] == "no_data"
    # unreadable spec / ledger: rc 2 (a gate must not pass vacuously)
    assert slo.main([led, "--spec", str(tmp_path / "nope.json")]) == 2
    assert slo.main([str(tmp_path / "nope.jsonl")]) == 2


def test_slo_step_time_and_verdict_event(tmp_path, capsys):
    from heat3d_tpu.obs.perf import slo

    led = _slo_ledger(tmp_path, p95=0.2, step_dur=1.0)  # 0.1 s/step
    spec = tmp_path / "spec2.json"
    spec.write_text(json.dumps({"objectives": [
        {"name": "step-p95", "kind": "step_time", "percentile": 95,
         "max_s": 0.05}]}))
    out_led = str(tmp_path / "verdict_led.jsonl")
    obs.activate(out_led, meta={"entry": "test"})
    rc = slo.main([led, "--spec", str(spec), "--json"])
    obs.deactivate(rc=0)
    assert rc == 1  # 0.1 s/step vs 0.05 ceiling
    rep = json.loads(capsys.readouterr().out)
    assert rep["objectives"][0]["value"] == pytest.approx(0.1)
    # the verdict landed as a taxonomy-valid slo_verdict ledger event
    verdicts = [e for e in _read(out_led) if e["event"] == "slo_verdict"]
    assert verdicts and verdicts[0]["verdict"] == "breach"
    from heat3d_tpu.obs.check import main as check_main

    assert check_main(["--taxonomy", out_led]) == 0


def test_slo_halo_share_from_profile_and_no_data(tmp_path, capsys):
    from heat3d_tpu.obs.perf import slo

    led = _slo_ledger(tmp_path)
    spec = tmp_path / "spec3.json"
    spec.write_text(json.dumps({"objectives": [
        {"name": "halo-share", "kind": "halo_share", "max_frac": 0.15}]}))
    # without a profile: no_data, rc 0
    assert slo.main([led, "--spec", str(spec), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["objectives"][0]["status"] == "no_data"
    # with a capture where halo is 20% of attributed time: breach vs 0.15
    prof = _write_synthetic_xplane(tmp_path, stencil_us=40.0, halo_us=10.0)
    assert slo.main(
        [led, "--spec", str(spec), "--profile", prof, "--json"]
    ) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["objectives"][0]["value"] == pytest.approx(0.2)


def test_slo_serve_result_reconstruction_fallback():
    """Pre-summary ledgers still evaluate: serve_result queue latencies
    reconstruct one (all) pseudo-bucket."""
    from heat3d_tpu.obs.perf.slo import evaluate, load_spec

    evs = [
        {"event": "serve_result", "kind": "point", "queue_latency_s": v}
        for v in (0.1, 0.2, 0.3)
    ]
    spec = {"objectives": [
        {"name": "q", "kind": "serve_latency", "percentile": 95,
         "max_s": 1.0}]}
    rep = evaluate(evs, spec)
    o = rep["objectives"][0]
    assert o["bucket"] == "(all)" and o["value"] == pytest.approx(0.3)
    assert rep["sources"]["serve"] == "serve_result reconstruction"
    # default spec loads without any file and is marked as such
    assert load_spec(None).get("default_spec") is True


def test_slo_spec_validation_errors(tmp_path):
    from heat3d_tpu.obs.perf.slo import load_spec

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"objectives": [{"kind": "nope", "max_s": 1}]}))
    with pytest.raises(ValueError, match="kind"):
        load_spec(str(bad))
    bad.write_text(json.dumps({"objectives": [
        {"kind": "serve_latency", "percentile": 95}]}))
    with pytest.raises(ValueError, match="max_s"):
        load_spec(str(bad))
    bad.write_text(json.dumps({"objectives": [
        {"kind": "serve_latency", "percentile": 75, "max_s": 1.0}]}))
    with pytest.raises(ValueError, match="percentile"):
        load_spec(str(bad))


def test_drift_detector_never_crosses_run_boundaries():
    """An APPEND-session ledger holds many differently-configured runs
    (the suite ledger the CI timeline smoke reads): a grid-32 run at
    0.1 s/step followed by a grid-256 run at 0.5 s/step is two healthy
    runs, not drift — baselines are scoped per run segment, and the two
    sequential runs are ONE host identity, so no straggler either."""
    from heat3d_tpu.obs.perf.timeline import detect_anomalies

    evs = []
    for i in range(6):
        e = _chunk_span(0.4, seq=i, ts=float(i))
        e["run_id"] = "run-a"
        evs.append(e)
    for i in range(6):
        e = _chunk_span(2.0, seq=6 + i, ts=6.0 + i)  # 5x slower per step
        e["run_id"] = "run-b"
        evs.append(e)
    assert detect_anomalies(evs) == []
    # drift WITHIN one of the segments still fires, tagged with its run
    e = _chunk_span(4.0, seq=12, ts=12.0)
    e["run_id"] = "run-b"
    evs.append(e)
    anoms = detect_anomalies(evs)
    assert [a["status"] for a in anoms] == ["fail"]
    assert anoms[0]["run_id_"] == "run-b"
    assert anoms[0]["baseline_s"] == pytest.approx(0.5)
