"""Batched scenario engine (heat3d_tpu/serve/, docs/SERVING.md).

Acceptance battery for PR 7: the ensemble axis must be *provably* the
same math as B independent solo runs, and the queue must stream every
submitted scenario back in order. Tiers:

- in-process (1 device): scenario/batch validation, bucket keys, the
  batch-shape tune-cache key, queue e2e (packing, submission order,
  backpressure, snapshots, ledger events), ensemble bench-row
  provenance, obs summary/regress per-member reporting;
- subprocess (REAL 4-device CPU mesh): ``bind='baked'`` bitwise-equal
  to B independent :class:`HeatSolver3D` runs, and the vmapped
  ``bind='traced'`` program member-wise bitwise-INVARIANT to batch
  packing (B=3 equals three B=1 runs of the same parametric program),
  for 7pt and 27pt at tb in {1, 2} with heterogeneous ICs, boundary
  values, diffusivities, and step budgets.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from heat3d_tpu import obs
from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    RunConfig,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.serve.scenario import Scenario, ScenarioBatch, solver_bucket_key

HERE = os.path.dirname(os.path.abspath(__file__))


def _base(grid=10, kind="7pt", steps=4, tb=1, bc=BoundaryCondition.DIRICHLET):
    return SolverConfig(
        grid=GridConfig.cube(grid),
        stencil=StencilConfig(kind=kind, bc=bc),
        mesh=MeshConfig(shape=(1, 1, 1)),
        precision=Precision.fp32(),
        run=RunConfig(num_steps=steps),
        backend="jnp",
        halo="ppermute",
        time_blocking=tb,
    )


HETERO = [
    Scenario(init="hot-cube", alpha=0.3, bc_value=1.0, steps=4, seed=1),
    Scenario(init="gaussian", alpha=0.8, bc_value=0.0, steps=3, seed=2),
    Scenario(init="random", alpha=0.5, bc_value=-0.5, steps=2, seed=3),
]


# ---- scenario / batch validation -------------------------------------------


def test_scenario_rejects_degenerate_values():
    with pytest.raises(ValueError, match="alpha"):
        Scenario(alpha=0.0)
    with pytest.raises(ValueError, match="dt"):
        Scenario(dt=-0.1)
    with pytest.raises(ValueError, match="steps"):
        Scenario(steps=-1)


def test_batch_needs_members_and_shares_footprint():
    with pytest.raises(ValueError, match="at least one"):
        ScenarioBatch(_base(), [])
    # heterogeneous alpha/dt values share the footprint by construction
    ScenarioBatch(_base(), HETERO)


def test_member_config_is_the_solo_reference():
    batch = ScenarioBatch(_base(steps=7), HETERO)
    cfg1 = batch.member_config(1)
    assert cfg1.grid.alpha == 0.8
    assert cfg1.stencil.bc_value == 0.0
    assert cfg1.run.num_steps == 3
    assert cfg1.run.seed == 2
    # member without its own budget inherits the base's
    batch2 = ScenarioBatch(_base(steps=7), [Scenario(alpha=0.5)])
    assert batch2.member_steps(0) == 7


def test_bucket_key_separates_structure_not_values():
    a = ScenarioBatch(_base(grid=10), [Scenario(alpha=0.3)])
    b = ScenarioBatch(_base(grid=10), [Scenario(alpha=0.9, bc_value=2.0)])
    c = ScenarioBatch(_base(grid=12), [Scenario(alpha=0.3)])
    d = ScenarioBatch(_base(grid=10, kind="27pt"), [Scenario(alpha=0.3)])
    assert a.bucket_key() == b.bucket_key()  # values are runtime inputs
    assert a.bucket_key() != c.bucket_key()  # grid is structure
    assert a.bucket_key() != d.bucket_key()  # stencil kind is structure


# ---- EnsembleSolver configuration guards -----------------------------------


def test_ensemble_rejects_single_tenant_routes():
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    batch = ScenarioBatch(
        dataclasses_replace(_base(), backend="pallas"), HETERO
    )
    with pytest.raises(ValueError, match="backend"):
        EnsembleSolver(batch)
    with pytest.raises(ValueError, match="halo"):
        EnsembleSolver(
            ScenarioBatch(dataclasses_replace(_base(), halo="dma"), HETERO)
        )
    with pytest.raises(ValueError, match="overlap"):
        EnsembleSolver(
            ScenarioBatch(dataclasses_replace(_base(), overlap=True), HETERO)
        )


def dataclasses_replace(cfg, **kw):
    import dataclasses

    return dataclasses.replace(cfg, **kw)


def test_ensemble_batch_mesh_divisibility_and_baked_constraint():
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    batch = ScenarioBatch(_base(), HETERO)
    with pytest.raises(ValueError, match="divide"):
        EnsembleSolver(batch, batch_mesh=2)
    with pytest.raises(ValueError, match="batch_mesh=1"):
        EnsembleSolver(
            ScenarioBatch(_base(), HETERO + [Scenario(alpha=0.4)]),
            batch_mesh=2,
            bind="baked",
        )
    with pytest.raises(ValueError, match="devices"):
        # 3 members over batch_mesh=3 needs 3 devices; tier-1 has 1
        EnsembleSolver(batch, batch_mesh=3)


# ---- batch-shape tune-cache bucket -----------------------------------------


def test_cache_key_gains_batch_bucket_and_solo_stays_stable():
    from heat3d_tpu.tune.cache import cache_key

    cfg = _base()
    solo = cache_key(cfg)
    assert cache_key(cfg, batch_size=1) == solo  # committed entries stay valid
    b8 = cache_key(cfg, batch_size=8)
    assert b8 == solo + "|b2^3"
    # bucketed, not exact: 6 and 8 members share a program shape class
    assert cache_key(cfg, batch_size=6) == b8


# ---- single-device equivalence (the 4-device proof is the subprocess) ------


def test_baked_binding_bitwise_vs_solo_single_device():
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    batch = ScenarioBatch(_base(), HETERO)
    es = EnsembleSolver(batch, bind="baked")
    got = es.gather(es.run(es.init_state()))
    for m, sc in enumerate(HETERO):
        solo = HeatSolver3D(batch.member_config(m))
        want = solo.gather(
            solo.run(solo.init_state(sc.init), batch.member_steps(m))
        )
        np.testing.assert_array_equal(got[m], want)


def test_traced_binding_packing_invariant_single_device():
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    batch = ScenarioBatch(_base(), HETERO)
    got = None
    es = EnsembleSolver(batch, bind="traced")
    got = es.gather(es.run(es.init_state()))
    for m, sc in enumerate(HETERO):
        solo_b1 = EnsembleSolver(
            ScenarioBatch(_base(), [sc]), bind="traced"
        )
        want = solo_b1.gather(solo_b1.run(solo_b1.init_state()))[0]
        np.testing.assert_array_equal(got[m], want)


def test_member_residuals_match_solo():
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    batch = ScenarioBatch(_base(), HETERO)
    es = EnsembleSolver(batch, bind="baked")
    u = es.init_state()
    u2, r = es.step_with_member_residuals(u)
    assert r.shape == (3,)
    for m, sc in enumerate(HETERO):
        solo = HeatSolver3D(batch.member_config(m))
        _, r_solo = solo.step_with_residual(solo.init_state(sc.init))
        np.testing.assert_allclose(float(r[m]), float(r_solo), rtol=1e-6)
    # the supervised loop's scalar aggregate is the member sum
    _, r_agg = es.step_with_residual(u2)
    assert float(r_agg) >= 0.0


# ---- the queue --------------------------------------------------------------


def test_pad_pow2_buckets():
    from heat3d_tpu.serve.queue import _pad_pow2

    assert _pad_pow2(1, 64) == 1
    assert _pad_pow2(3, 64) == 4
    assert _pad_pow2(4, 64) == 4
    assert _pad_pow2(5, 64) == 8
    assert _pad_pow2(100, 64) == 64


def test_padded_size_divisible_by_batch_mesh():
    """A padded size the batch mesh cannot divide would fail every drain
    of that chunk — the rounding must honor batch_mesh even past the
    pow2 bucket (and past the cap if needed)."""
    from heat3d_tpu.serve.queue import _padded_size

    assert _padded_size(1, 64, 1) == 1
    assert _padded_size(1, 64, 2) == 2   # the wedge case: pow2(1)=1
    assert _padded_size(2, 64, 4) == 4
    assert _padded_size(3, 64, 3) == 6   # pow2(3)=4 -> next multiple of 3
    assert _padded_size(64, 64, 3) == 66  # cap may be exceeded to divide


def test_queue_e2e_buckets_pack_and_stream_in_submission_order(tmp_path):
    """The issue's queue acceptance: submit N heterogeneous scenarios
    across two shape buckets -> shape-bucketed batches -> every result
    streamed, in submission order, with the serve ledger events landed."""
    from heat3d_tpu.serve.queue import ScenarioQueue

    led = str(tmp_path / "serve.jsonl")
    obs.activate(led, meta={"entry": "test"})
    try:
        q = ScenarioQueue()
        base_a, base_b = _base(grid=10), _base(grid=12)
        # interleave buckets: a, b, a, b, a — order must still hold
        rids = [
            q.submit(base_a, HETERO[0]),
            q.submit(base_b, Scenario(alpha=0.6, steps=3, seed=4)),
            q.submit(base_a, HETERO[1]),
            q.submit(base_b, Scenario(alpha=0.9, steps=2, seed=5)),
            q.submit(base_a, HETERO[2]),
        ]
        assert rids == [0, 1, 2, 3, 4]
        assert len(q) == 5
        results = list(q.drain())
        assert len(q) == 0
    finally:
        obs.deactivate(rc=0)

    assert [r.request_id for r in results] == rids  # submission order
    by_id = {r.request_id: r for r in results}
    # bucket a packed 3 members, bucket b packed 2
    assert by_id[0].batch_size == 3 and by_id[2].batch_size == 3
    assert by_id[1].batch_size == 2 and by_id[3].batch_size == 2
    for r in results:
        assert r.field.shape == ((10,) * 3 if r.request_id % 2 == 0 else (12,) * 3)
        assert r.queue_latency_s >= 0.0

    events = [json.loads(line) for line in open(led) if line.strip()]
    names = [e.get("event") for e in events]
    assert names.count("serve_submit") == 5
    assert names.count("serve_batch_start") == 2
    assert names.count("serve_result") == 5
    spans = [
        e for e in events
        if e.get("event") == "serve_batch" and e.get("kind") == "span"
    ]
    assert len(spans) == 2


def test_queue_results_match_direct_ensemble():
    from heat3d_tpu.serve.ensemble import EnsembleSolver
    from heat3d_tpu.serve.queue import ScenarioQueue

    base = _base(grid=10)
    q = ScenarioQueue()
    for sc in HETERO:
        q.submit(base, sc)
    results = {r.request_id: r for r in q.drain()}
    # the queue pads 3 -> 4 members; the padded program's live members
    # must match the unpadded batch bitwise (padding is masked, and the
    # traced binding is packing-invariant)
    es = EnsembleSolver(ScenarioBatch(base, HETERO), bind="traced")
    want = es.gather(es.run(es.init_state()))
    for m in range(3):
        np.testing.assert_array_equal(results[m].field, want[m])


def test_queue_default_budget_survives_bucket_packing():
    """A steps=None scenario must run ITS base's num_steps even when
    packed with requests whose (structurally identical) base carries a
    different budget — the budget materializes at submit time, not from
    whichever request happens to lead the bucket."""
    from heat3d_tpu.serve.queue import ScenarioQueue

    q = ScenarioQueue()
    q.submit(_base(grid=10, steps=2), Scenario(alpha=0.5, seed=1))
    q.submit(_base(grid=10, steps=4), Scenario(alpha=0.5, seed=1))
    results = {r.request_id: r for r in q.drain()}
    assert results[0].steps == 2
    assert results[1].steps == 4
    # same scenario, different budgets -> genuinely different fields
    assert not np.array_equal(results[0].field, results[1].field)


def test_ensemble_pins_auto_knobs_to_the_chain():
    """backend='auto'/halo='auto' (the default config every serve
    request starts from) must pin to jnp/ppermute — never crash on a
    tune-cache winner that picked a single-tenant kernel route."""
    from heat3d_tpu.serve.ensemble import EnsembleSolver

    auto = dataclasses_replace(_base(), backend="auto", halo="auto")
    es = EnsembleSolver(ScenarioBatch(auto, HETERO))
    assert es.cfg.backend == "jnp"
    assert es.cfg.halo == "ppermute"


def test_drain_delivers_executed_batches_before_surfacing_a_failure():
    """One bucket failing to build must not destroy the batches that
    already executed: landed results stream out, THEN the error
    surfaces, and the failed bucket's requests stay pending (they were
    never executed) so a corrected drain can retry them."""
    from heat3d_tpu.serve.queue import ScenarioQueue

    q = ScenarioQueue()
    good = q.submit(_base(grid=10), HETERO[0])
    # tb=2 on a 2-cell grid fails the local-extent floor at solver build
    bad = q.submit(_base(grid=2, tb=2), HETERO[1])
    got = []
    with pytest.raises(ValueError, match="local extents"):
        for r in q.drain():
            got.append(r.request_id)
    assert got == [good]
    assert good not in q._pending and bad in q._pending


def test_queue_backpressure_and_depth_cap():
    from heat3d_tpu.serve.queue import ScenarioQueue

    q = ScenarioQueue(max_depth=2)
    base = _base()
    q.submit(base, HETERO[0])
    q.submit(base, HETERO[1])
    with pytest.raises(RuntimeError, match="queue full"):
        q.submit(base, HETERO[2])
    list(q.drain())
    q.submit(base, HETERO[2])  # drained queue accepts again


def test_queue_snapshots_and_residuals():
    from heat3d_tpu.serve.queue import ScenarioQueue

    q = ScenarioQueue(snapshot_every=2, with_residuals=True)
    base = _base(grid=8)
    q.submit(base, Scenario(alpha=0.5, steps=5, seed=1))
    q.submit(base, Scenario(alpha=0.3, steps=2, seed=2))
    results = {r.request_id: r for r in q.drain()}
    # 5 steps at snapshot stride 2 -> chunks after steps 2, 4, 5
    assert len(results[0].snapshots) == 3
    assert results[0].residual_sumsq is not None
    np.testing.assert_array_equal(results[0].snapshots[-1], results[0].field)
    # the 2-step member finished in the first chunk and then froze:
    # every later snapshot is its final field
    np.testing.assert_array_equal(results[1].snapshots[0], results[1].field)
    np.testing.assert_array_equal(results[1].snapshots[2], results[1].field)


def test_serve_cli_null_request_value_exits_clean(tmp_path, capsys):
    """A JSON null where a number belongs (the docstring's own `"dt":
    null` idiom misapplied to steps/alpha) must exit 2 with the clean
    error line, not a traceback."""
    from heat3d_tpu.serve.cli import main as serve_main

    p = tmp_path / "reqs.jsonl"
    p.write_text('{"grid": 12, "steps": null}\n')
    assert serve_main(["--requests", str(p)]) == 2
    assert "heat3d serve: error:" in capsys.readouterr().err


def test_serve_cli_smoke_streams_all_results(capsys):
    from heat3d_tpu.serve.cli import main as serve_main

    assert serve_main(["--smoke"]) == 0
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    assert [r["request_id"] for r in lines] == [0, 1, 2]
    assert all("field_mean" in r for r in lines)


# ---- ensemble bench-row provenance + per-member reporting ------------------


def test_bench_ensemble_row_passes_provenance_lint():
    from heat3d_tpu.analysis.provenance import check_row
    from heat3d_tpu.serve.bench import bench_ensemble_throughput

    row = bench_ensemble_throughput(
        ScenarioBatch(_base(grid=8), HETERO), steps=3, warmup=1, repeats=1
    )
    assert row["batch_shape"] == [3]
    assert row["members_per_step"] == 3
    assert check_row(row) == []


def test_solo_throughput_rows_carry_solo_batch_fields():
    """Every solo bench row must now say it aggregates one member —
    check_provenance requires the fields on ALL throughput rows."""
    from heat3d_tpu.analysis.provenance import check_row

    row = {
        "bench": "throughput", "platform": "cpu", "grid": [8, 8, 8],
        "stencil": "7pt", "mesh": [1, 1, 1], "dtype": "float32",
        "backend": "jnp", "time_blocking": 1, "halo": "ppermute",
        "steps": 3, "gcell_per_sec": 1.0, "sync_rtt_s": 1e-5,
        "ts": "2026-08-03T00:00:00Z",
        "chain_ops": "x", "mehrstellen_route": False,
        "direct_path": False, "streamk_path": False,
        "streamk_emulated": False, "halo_plan": "monolithic",
        "batch_shape": [1], "members_per_step": 1, "equation": "heat",
        "integrator": "explicit-euler",
    }
    assert check_row(row) == []
    bad = dict(row)
    del bad["batch_shape"], bad["members_per_step"]
    problems = check_row(bad)
    assert any("batch_shape" in p for p in problems)
    assert any("members_per_step" in p for p in problems)


def test_regress_keys_and_reports_split_batch_shapes():
    from heat3d_tpu.obs.perf.regress import compare, row_key

    solo = {
        "bench": "throughput", "platform": "cpu", "grid": [8, 8, 8],
        "stencil": "7pt", "dtype": "float32", "time_blocking": 1,
        "gcell_per_sec_per_chip": 1.0,
        "batch_shape": [1], "members_per_step": 1,
    }
    packed = dict(solo, batch_shape=[4], members_per_step=4,
                  gcell_per_sec_per_chip=2.0)
    assert row_key(solo) != row_key(packed)
    # legacy rows (no batch fields) key as solo — history stays usable
    legacy = {k: v for k, v in solo.items() if k != "batch_shape"}
    assert row_key(legacy) == row_key(solo)
    # rows written before a route was removed still carry its knob and
    # route fields; they key to the same baseline as rows without them
    retired = dict(solo, retired_route="off", retired_route_path=False)
    assert row_key(retired) == row_key(solo)
    # a packed row baselines only against packed history and reports the
    # per-member effective split
    report = compare([packed], [dict(packed, gcell_per_sec_per_chip=2.2)])
    (c,) = report["comparisons"]
    assert c["members_per_step"] == 4
    assert c["current_per_member"] == pytest.approx(0.5)
    # an ensemble aggregate never compares against a solo baseline
    report2 = compare([packed], [dict(solo, gcell_per_sec_per_chip=9.9)])
    assert not report2["comparisons"]
    assert report2["no_baseline"]


def test_obs_summary_prints_per_member_effective_rate():
    from heat3d_tpu.obs.cli import ensemble_lines

    events = [
        {"event": "bench_row", "bench": "throughput", "grid": [64, 64, 64],
         "gcell_per_sec": 8.0, "members_per_step": 4, "batch_mesh": 2},
        {"event": "bench_row", "bench": "throughput", "grid": [64, 64, 64],
         "gcell_per_sec": 3.0, "members_per_step": 1, "batch_shape": [1]},
    ]
    lines = ensemble_lines(events)
    assert len(lines) == 1  # solo rows don't get an ensemble line
    assert "B=4" in lines[0] and "2 Gcell/s/member" in lines[0]


# ---- the 4-device CPU-mesh acceptance --------------------------------------


def test_ensemble_equivalence_on_cpu_mesh_tier1():
    """THE acceptance proof (ISSUE 7): on a REAL 4-device CPU mesh, an
    EnsembleSolver over B=3 heterogeneous scenarios (distinct ICs,
    Dirichlet values, diffusivities, budgets) matches 3 independent
    HeatSolver3D runs BITWISE via the baked binding, and the vmapped
    traced program is member-wise bitwise-invariant to packing, for 7pt
    and 27pt at tb in {1, 2} — cross-device ppermutes under the batch
    axis executing, not compile-only."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE), env.get("PYTHONPATH", "")]
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "serve_checks.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, (
        f"ensemble equivalence failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    assert "ENSEMBLE EQUIVALENCE OK" in proc.stdout


def test_drain_emits_serve_metrics_summary_for_posthoc_slo(tmp_path):
    """The drain-final ledger event (ISSUE 8 satellite): per-bucket
    p50/p95/max queue latency + the depth high-water mark land in the
    ledger, and the SLO layer evaluates per-bucket objectives from those
    events ALONE — no live registry, no queue object."""
    from heat3d_tpu.obs.perf.slo import evaluate
    from heat3d_tpu.serve.queue import ScenarioQueue

    led = str(tmp_path / "serve.jsonl")
    obs.activate(led, meta={"entry": "test"})
    try:
        q = ScenarioQueue()
        base_a, base_b = _base(grid=10), _base(grid=12)
        for sc in HETERO:
            q.submit(base_a, sc)
        q.submit(base_b, Scenario(alpha=0.6, steps=2, seed=9))
        assert len(q) == 4
        list(q.drain())
        summary = q.metrics_summary()
    finally:
        obs.deactivate(rc=0)

    # the live summary: one bucket entry per structural key, full stats
    assert summary["depth_max"] == 4
    assert summary["delivered"] == 4 and summary["batches"] == 2
    assert len(summary["buckets"]) == 2
    for st in summary["buckets"].values():
        assert st["count"] >= 1
        assert 0.0 <= st["p50_s"] <= st["p95_s"] <= st["max_s"]

    events = [json.loads(line) for line in open(led) if line.strip()]
    finals = [e for e in events if e.get("event") == "serve_metrics_summary"]
    assert len(finals) == 1  # one per drain
    assert finals[0]["buckets"] == summary["buckets"]
    assert finals[0]["depth_max"] == 4

    # post-hoc SLO evaluation from the ledger events alone: the grid-10
    # bucket is addressable by substring, and generous ceilings pass
    spec = {"objectives": [
        {"name": "p95-grid10", "kind": "serve_latency", "percentile": 95,
         "max_s": 120.0, "bucket": "(10, 10, 10)"},
        {"name": "p50-all", "kind": "serve_latency", "percentile": 50,
         "max_s": 120.0},
    ]}
    rep = evaluate(events, spec)
    assert rep["verdict"] == "pass"
    assert rep["sources"]["serve"] == "serve_metrics_summary"
    by_name = {o["name"]: o for o in rep["objectives"]}
    assert "(10, 10, 10)" in by_name["p95-grid10"]["bucket"]
    assert by_name["p50-all"]["status"] == "ok"
    # a second drain appends a fresh cumulative summary
    obs.activate(led, meta={"entry": "test"})
    try:
        q.submit(base_a, HETERO[0])
        list(q.drain())
    finally:
        obs.deactivate(rc=0)
    events = [json.loads(line) for line in open(led) if line.strip()]
    finals = [e for e in events if e.get("event") == "serve_metrics_summary"]
    assert len(finals) == 2
    assert finals[1]["delivered"] == 5


def test_bucket_latency_reservoir_is_bounded(monkeypatch):
    """The per-bucket SLO stats reuse the metrics layer's sample cap: a
    service queue alive for millions of requests must not grow an
    unbounded latency list — count/max stay exact past the cap, the
    percentiles mark themselves clipped."""
    from heat3d_tpu.serve import queue as queue_mod

    monkeypatch.setattr(queue_mod, "HISTOGRAM_SAMPLE_CAP", 2)
    q = queue_mod.ScenarioQueue()
    base = _base(grid=10, steps=1)
    for sc in HETERO:
        q.submit(base, sc)
    list(q.drain())
    summary = q.metrics_summary()
    (st,) = summary["buckets"].values()
    assert st["count"] == 3 and st["clipped"] is True
    assert len(q._bucket_stats[next(iter(q._bucket_stats))]["samples"]) == 2


def test_serve_cli_slo_wiring_rc_semantics(tmp_path, capsys):
    """`heat3d serve --slo`: the spec validates BEFORE the drain (bad
    spec = clean rc 2, zero results executed), a breaching drain exits 1
    even though every result delivered, and a passing spec exits 0 with
    the verdict on stderr (stdout stays the pure result stream)."""
    from heat3d_tpu.serve.cli import main as serve_main

    breach = tmp_path / "breach.json"
    breach.write_text(json.dumps({"objectives": [
        {"name": "q95", "kind": "serve_latency", "percentile": 95,
         "max_s": 1e-9}]}))
    assert serve_main(["--smoke", "--slo", str(breach)]) == 1
    out, err = capsys.readouterr()
    assert len(out.strip().splitlines()) == 3  # all results delivered
    assert "BREACH" in err and "slo verdict: breach" in err

    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"objectives": [
        {"name": "q95", "kind": "serve_latency", "percentile": 95,
         "max_s": 120.0},
        {"name": "step95", "kind": "step_time", "percentile": 95,
         "max_s": 1e-9}]}))
    assert serve_main(["--smoke", "--slo", str(ok)]) == 0
    out, err = capsys.readouterr()
    assert len(out.strip().splitlines()) == 3
    assert "slo verdict: pass" in err
    # a mixed spec's non-serve objectives are NOT enforced at drain time
    # (no step spans here) and the verdict says so explicitly — a
    # breach-level step ceiling must not pass silently
    assert "step95 not evaluable at drain time" in err
    for line in out.strip().splitlines():
        json.loads(line)  # stdout is still pure JSON results

    # a missing/invalid spec fails BEFORE any batch executes
    assert serve_main(["--smoke", "--slo", str(tmp_path / "nope.json")]) == 2
    out, err = capsys.readouterr()
    assert out.strip() == "" and "--slo" in err
