"""Elastic-degradation tests (tier-1, CPU): the survivor-mesh re-plan
path (resilience/elastic.py + supervisor heal_mode), the
partial-device-loss injection primitive, the serve-tier requeue/degraded
machinery, the SLO serve_degraded objective, and the provenance rules
that keep degraded throughput labeled. The 4-device acceptance battery
(loss of 2 of 4 devices mid-run, bitwise re-stitch proof, engine requeue
under injected loss) runs in a CPU-mesh subprocess
(tests/elastic_checks.py); the weak-scaling chaos harness has its own
subprocess acceptance."""

import json
import os
import subprocess
import sys

import pytest

from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
from heat3d_tpu.resilience import elastic
from heat3d_tpu.resilience.faults import FaultPlan, _parse_spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _cpu_mesh_env(ndev: int) -> dict:
    env = dict(os.environ)
    env.pop("HEAT3D_FAULTS", None)
    env.pop("HEAT3D_HEAL_MODE", None)
    env.pop("HEAT3D_LEDGER", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join([REPO, env.get("PYTHONPATH", "")])
    return env


# ---- the 4-device acceptance battery ------------------------------------


def test_elastic_checks_on_cpu_mesh():
    """THE acceptance battery: lose 2 of 4 devices mid-run, re-factorize
    (4,1,1)->(2,1,1), bitwise-equal a fresh small-mesh run from the same
    checkpoint; auto mode degrades at the heal deadline; opt-in
    re-expand restores the mesh; the async engine requeues (not fails)
    under the same injected loss with the degraded window SLO-judged."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "elastic_checks.py")],
        env=_cpu_mesh_env(4),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"elastic checks failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    for marker in (
        "elastic_degrade_bitwise OK",
        "auto_mode_deadline_triggers_elastic OK",
        "elastic_replans_during_platform_outage OK",
        "reexpand_restores_full_mesh OK",
        "engine_requeue_and_degraded_slo OK",
        "ALL ELASTIC CHECKS PASSED",
    ):
        assert marker in proc.stdout


def test_weak_scaling_chaos_harness_end_to_end(tmp_path):
    """The chaos harness acceptance: scripts/weak_scaling.py on a
    4-device CPU mesh walks the rung ladder, injects a 2-device loss on
    the largest rung, and emits lint-clean rows — the healthy rungs plus
    one post_heal row carrying the degraded mesh, recovery seconds and
    post-degradation throughput."""
    out = str(tmp_path / "ws.jsonl")
    led = str(tmp_path / "ws.ledger.jsonl")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "scripts", "weak_scaling.py"),
            "--local", "8", "--meshes", "1x1x1,4x1x1", "--steps", "8",
            "--chaos", "keep=2", "--out", out, "--ledger", led,
            "--ckpt-root", str(tmp_path / "ck"),
        ],
        env=_cpu_mesh_env(4),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"weak_scaling failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )
    rows = [json.loads(ln) for ln in open(out) if ln.strip()]
    assert len(rows) == 3  # 2 healthy rungs + 1 post-heal row
    healthy = [r for r in rows if not r["post_heal"]]
    assert [r["mesh_shape"] for r in healthy] == [[1, 1, 1], [4, 1, 1]]
    assert healthy[0]["weak_efficiency"] == 1.0
    assert all(r["gcell_per_sec_per_chip"] > 0 for r in rows)
    (degraded,) = [r for r in rows if r["post_heal"]]
    assert degraded["mesh_shape"] == [2, 1, 1]
    assert degraded["survivors"] == 2
    assert degraded["recovery_s"] >= 0
    assert degraded["injected_mesh"] == [4, 1, 1]

    # every row passes the provenance lint (the post_heal labeling rule)
    from heat3d_tpu.analysis.provenance import check_file

    assert check_file(out) == []

    # the ledger carries the attribution trail the harness exists for
    evs = [json.loads(ln) for ln in open(led) if ln.strip()]
    assert any(e.get("event") == "elastic_refactor" for e in evs)
    assert any(e.get("event") == "degraded_mode_enter" for e in evs)


# ---- fault-injection primitive ------------------------------------------


def test_partial_device_loss_spec_parsing_and_validation():
    (f,) = _parse_spec("partial-device-loss:step=4:keep=2:down=1:restore=3")
    assert f.kind == "partial-device-loss"
    assert f.params == {"step": 4, "keep": 2, "down": 1, "restore": 3}
    (f,) = _parse_spec("partial-device-loss:batch=1:keep=1")
    assert f.params == {"batch": 1, "keep": 1}
    with pytest.raises(ValueError, match="keep"):
        _parse_spec("partial-device-loss:step=4")
    with pytest.raises(ValueError, match="exactly one"):
        _parse_spec("partial-device-loss:step=4:batch=1:keep=2")
    with pytest.raises(ValueError, match="exactly one"):
        _parse_spec("partial-device-loss:keep=2")


def test_partial_device_loss_fires_and_overrides_device_probe():
    from heat3d_tpu.resilience.faults import InjectedBackendLoss

    plan = FaultPlan(_parse_spec("partial-device-loss:step=4:keep=2"))
    assert plan.device_override() is None  # nothing fired yet
    plan.on_step(2)
    with pytest.raises(InjectedBackendLoss, match="2 device"):
        plan.on_step(4)
    plan.on_step(4)  # one-shot
    # down defaults to 0: a partial loss is not an outage
    assert plan.probe_override() is None
    # the shrunken set persists (restore unset)
    assert plan.device_override() == 2
    assert plan.device_override() == 2


def test_partial_device_loss_restore_decays():
    from heat3d_tpu.resilience.faults import InjectedBackendLoss

    plan = FaultPlan(
        _parse_spec("partial-device-loss:step=1:keep=3:restore=2")
    )
    with pytest.raises(InjectedBackendLoss):
        plan.on_step(1)
    assert plan.device_override() == 3
    assert plan.device_override() == 3
    assert plan.device_override() is None  # capacity "returned"


def test_serve_batch_hook_fires_on_batch_index():
    from heat3d_tpu.resilience.faults import InjectedBackendLoss

    plan = FaultPlan(_parse_spec("partial-device-loss:batch=1:keep=1"))
    plan.on_serve_batch(0)  # below the trigger
    with pytest.raises(InjectedBackendLoss):
        plan.on_serve_batch(1)
    plan.on_serve_batch(2)  # one-shot
    assert plan.device_override() == 1


# ---- heal-mode / deadline knobs -----------------------------------------


def test_resolve_heal_mode_env_and_validation(monkeypatch):
    monkeypatch.delenv(elastic.ENV_HEAL_MODE, raising=False)
    assert elastic.resolve_heal_mode() == "wait"
    assert elastic.resolve_heal_mode("elastic") == "elastic"
    monkeypatch.setenv(elastic.ENV_HEAL_MODE, "auto")
    assert elastic.resolve_heal_mode() == "auto"
    assert elastic.resolve_heal_mode("wait") == "wait"  # arg beats env
    monkeypatch.setenv(elastic.ENV_HEAL_MODE, "sideways")
    with pytest.raises(ValueError, match="sideways"):
        elastic.resolve_heal_mode()


def test_heal_deadline_env_knob(monkeypatch):
    monkeypatch.delenv(elastic.ENV_HEAL_DEADLINE, raising=False)
    assert elastic.default_heal_policy().deadline_s == 1800.0
    monkeypatch.setenv(elastic.ENV_HEAL_DEADLINE, "120")
    assert elastic.default_heal_policy().deadline_s == 120.0
    # garbage/non-positive overrides fall back, never kill the recovery
    monkeypatch.setenv(elastic.ENV_HEAL_DEADLINE, "soon")
    assert elastic.default_heal_policy().deadline_s == 1800.0
    monkeypatch.setenv(elastic.ENV_HEAL_DEADLINE, "-5")
    assert elastic.default_heal_policy().deadline_s == 1800.0


def test_supervisor_rejects_elastic_without_factory(tmp_path):
    """Bare run_supervised with heal_mode=elastic but no cfg->solver
    factory must refuse loudly, not silently behave like wait."""
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.resilience.supervisor import run_supervised

    solver = HeatSolver3D(
        SolverConfig(grid=GridConfig.cube(8), backend="jnp")
    )
    with pytest.raises(ValueError, match="make_solver_for"):
        run_supervised(
            solver, 4, str(tmp_path / "ck"), checkpoint_every=2,
            heal_mode="elastic",
        )


# ---- survivor-mesh candidates -------------------------------------------


def test_survivor_candidates_respect_restitch_contract():
    from heat3d_tpu.tune.space import survivor_candidates

    base = SolverConfig(
        grid=GridConfig.cube(8), mesh=MeshConfig(shape=(4, 1, 1)),
        backend="jnp",
    )
    # validate=False: structural + re-stitch gates only (the full
    # prune_reason build needs the 4-device subprocess tier)
    cands = survivor_candidates(base, 2, validate=False)
    assert cands and cands[0].mesh.shape == (2, 1, 1)
    assert all(c.padded_shape == base.padded_shape for c in cands)

    # a grid the survivor mesh would re-pad is NOT stitchable: excluded
    uneven = SolverConfig(
        grid=GridConfig(shape=(10, 8, 8)), mesh=MeshConfig(shape=(4, 1, 1)),
        backend="jnp",
    )
    assert uneven.padded_shape == (12, 8, 8)
    assert survivor_candidates(uneven, 2, validate=False) == []
    assert elastic.survivor_config(uneven, 0) is None
    assert survivor_candidates(base, 0, validate=False) == []


# ---- serve-tier degradation ---------------------------------------------


def test_is_backend_loss_classification():
    from heat3d_tpu.resilience.faults import InjectedBackendLoss
    from heat3d_tpu.serve.engine.core import is_backend_loss

    assert is_backend_loss(InjectedBackendLoss("gone"))
    assert not is_backend_loss(ValueError("bad config"))
    assert not is_backend_loss(RuntimeError("scenario bug"))

    class FakeXlaError(Exception):
        pass

    FakeXlaError.__module__ = "jaxlib.xla_extension"
    assert is_backend_loss(FakeXlaError("device lost"))


def test_serve_stats_degraded_accounting():
    from heat3d_tpu.serve.queue import ServeStats

    st = ServeStats()
    s = st.summary(pending=0)
    assert s["degraded"] is False and s["degraded_s"] == 0.0
    assert s["requeues"] == 0
    st.mark_degraded()
    st.mark_degraded(new=False)  # same chunk's second attempt: no new ref
    assert st.requeues == 2
    assert st.summary(pending=0)["degraded"] is True
    assert st.degraded_seconds() > 0
    st.clear_degraded()
    s = st.summary(pending=0)
    assert s["degraded"] is False and s["degraded_s"] > 0
    st.clear_degraded()  # idempotent

    # refcounted window: chunk A recovering must NOT stop the clock
    # while chunk B is still backing off
    st2 = ServeStats()
    st2.mark_degraded()  # chunk A
    st2.mark_degraded()  # chunk B (distinct chunk: new ref)
    st2.clear_degraded()  # A resolves
    assert st2.summary(pending=0)["degraded"] is True  # B still degraded
    st2.clear_degraded()  # B resolves
    assert st2.summary(pending=0)["degraded"] is False


def test_engine_requeue_single_device():
    """In-process engine requeue: first execution of the only bucket is
    lost (injected), the retry succeeds, every result delivers, nothing
    lands in failures."""
    from heat3d_tpu.resilience.retry import RetryPolicy
    from heat3d_tpu.serve.engine import AsyncServeEngine
    from heat3d_tpu.serve.scenario import Scenario

    base = SolverConfig(grid=GridConfig.cube(8), backend="jnp")
    plan = FaultPlan(_parse_spec("partial-device-loss:batch=0:keep=1"))
    eng = AsyncServeEngine(
        aot=False, autostart=False, faults=plan,
        retry_policy=RetryPolicy(
            max_attempts=3, base_delay_s=0.01, multiplier=1.0,
            max_delay_s=0.01,
        ),
    )
    r1 = eng.submit(base, Scenario(alpha=0.5, steps=3))
    r2 = eng.submit(base, Scenario(alpha=0.7, steps=4))
    got = [r.request_id for r in eng.drain()]
    eng.shutdown()
    assert got == [r1, r2]
    assert not eng.failures
    st = eng.stats()
    assert st["requeues"] == 1 and st["degraded_s"] > 0


def test_engine_scenario_error_still_fails_immediately():
    """A config that cannot build is a SCENARIO error: no requeue, the
    chunk fails on the first attempt exactly as before."""
    from heat3d_tpu.serve.engine import AsyncServeEngine
    from heat3d_tpu.serve.scenario import Scenario

    bad = SolverConfig(
        grid=GridConfig.cube(16), mesh=MeshConfig(shape=(8, 1, 1)),
        backend="jnp",
    )
    eng = AsyncServeEngine(aot=False, autostart=False)
    eng.submit(bad, Scenario(alpha=0.5, steps=2))
    with pytest.raises(RuntimeError, match="failed"):
        list(eng.drain())
    eng.shutdown()
    assert len(eng.failures) == 1
    assert eng.stats()["requeues"] == 0


def test_requeues_exhausted_fail_the_chunk():
    """Losses past the RetryPolicy attempt cap fail for real — retry
    forever would hide a dead backend behind backoff."""
    from heat3d_tpu.resilience.retry import RetryPolicy
    from heat3d_tpu.serve.engine import AsyncServeEngine
    from heat3d_tpu.serve.scenario import Scenario

    base = SolverConfig(grid=GridConfig.cube(8), backend="jnp")
    # three independent one-shot losses at consecutive batch indexes, cap 2:
    # attempt 1 requeues, the second loss exhausts the cap
    plan = FaultPlan(_parse_spec(
        "partial-device-loss:batch=0:keep=1,"
        "partial-device-loss:batch=1:keep=1"
    ))
    eng = AsyncServeEngine(
        aot=False, autostart=False, faults=plan,
        retry_policy=RetryPolicy(
            max_attempts=2, base_delay_s=0.01, multiplier=1.0,
            max_delay_s=0.01,
        ),
    )
    eng.submit(base, Scenario(alpha=0.5, steps=2))
    with pytest.raises(RuntimeError, match="failed"):
        list(eng.drain())
    eng.shutdown()
    assert len(eng.failures) == 1
    assert eng.stats()["requeues"] == 1
    # the requeued chunk FAILING resolves its degraded window: seconds
    # retained, but the clock must not keep running over healthy serving
    summary = eng.metrics_summary()
    assert summary["degraded"] is False and summary["degraded_s"] > 0


# ---- SLO serve_degraded objective ---------------------------------------


def test_slo_serve_degraded_spec_and_evaluation(tmp_path):
    from heat3d_tpu.obs.perf import slo as slo_mod

    # spec validation: max_s required, percentile NOT required
    spec_path = tmp_path / "slo.json"
    spec_path.write_text(json.dumps({
        "objectives": [
            {"name": "deg", "kind": "serve_degraded", "max_s": 60.0},
        ],
    }))
    spec = slo_mod.load_spec(str(spec_path))
    assert spec["objectives"][0]["kind"] == "serve_degraded"
    spec_path.write_text(json.dumps({
        "objectives": [{"kind": "serve_degraded", "max_s": 0}],
    }))
    with pytest.raises(ValueError, match="max_s"):
        slo_mod.load_spec(str(spec_path))

    def ev(degraded_s, degraded=False):
        return [{
            "event": "serve_metrics_summary",
            "buckets": {"(16, 16, 16)": {"count": 1, "p50_s": 0.1,
                                         "p95_s": 0.1, "max_s": 0.1}},
            "degraded": degraded, "degraded_s": degraded_s, "requeues": 2,
        }]

    spec = {"objectives": [
        {"name": "deg", "kind": "serve_degraded", "max_s": 10.0},
    ]}
    (obj,) = slo_mod.evaluate(ev(2.0), spec)["objectives"]
    assert obj["status"] == "ok" and obj["value"] == 2.0
    assert obj["requeues"] == 2
    (obj,) = slo_mod.evaluate(ev(15.0, degraded=True), spec)["objectives"]
    assert obj["status"] == "breach" and obj["still_degraded"] is True
    # a healthy drain reads 0.0 -> ok, never no_data
    (obj,) = slo_mod.evaluate(ev(0.0), spec)["objectives"]
    assert obj["status"] == "ok" and obj["value"] == 0.0
    # pre-elastic summaries (no degraded_s) are honest no_data
    legacy = [{"event": "serve_metrics_summary",
               "buckets": {"b": {"p50_s": 0.1, "p95_s": 0.1}}}]
    (obj,) = slo_mod.evaluate(legacy, spec)["objectives"]
    assert obj["status"] == "no_data"


# ---- provenance rules ----------------------------------------------------


def test_provenance_post_heal_and_weak_scaling_rules(tmp_path):
    from heat3d_tpu.analysis.provenance import check_file

    ws_good = {
        "bench": "weak_scaling", "ts": "2026-08-04T00:00:00Z",
        "platform": "cpu", "mesh_shape": [2, 1, 1],
        "gcell_per_sec_per_chip": 0.5, "post_heal": False,
    }
    ws_heal = {
        **ws_good, "post_heal": True, "recovery_s": 1.25,
    }
    rows = [
        ws_good,
        ws_heal,
        {k: v for k, v in ws_good.items() if k != "post_heal"},  # 3
        {**ws_heal, "recovery_s": None},                          # 4
        {k: v for k, v in ws_heal.items() if k != "mesh_shape"},  # 5
        {**ws_good, "gcell_per_sec_per_chip": None},              # 6
        {"bench": "weak_scaling", "platform": "cpu"},             # 7: no ts+
    ]
    p = tmp_path / "ws.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows))
    bad = check_file(str(p))
    assert {line for line, _ in bad} == {3, 4, 5, 6, 7}

    # a post_heal THROUGHPUT row without its mesh_shape fails too
    thr = {
        "bench": "throughput", "ts": "2026-08-04T00:00:00Z",
        "platform": "cpu", "direct_path": False,
        "mehrstellen_route": False, "streamk_path": False,
        "streamk_emulated": False, "halo_plan": "monolithic",
        "chain_ops": 7, "backend": "jnp", "sync_rtt_s": 0.01,
        "batch_shape": [1], "members_per_step": 1, "equation": "heat",
        "integrator": "explicit-euler",
    }
    p2 = tmp_path / "thr.jsonl"
    p2.write_text("\n".join(json.dumps(r) for r in [
        thr,
        {**thr, "post_heal": True},                          # 2: no mesh
        {**thr, "post_heal": True, "mesh_shape": [2, 1, 1]},  # 3: ok
    ]))
    bad = check_file(str(p2))
    assert [line for line, _ in bad] == [2]


# ---- obs summary section -------------------------------------------------


def test_obs_summary_elastic_section():
    from heat3d_tpu.obs.cli import elastic_lines

    events = [
        {"event": "elastic_refactor", "direction": "degrade",
         "old_mesh": [4, 1, 1], "new_mesh": [2, 1, 1], "survivors": 2,
         "restitch_s": 0.8, "step": 8},
        {"event": "degraded_mode_enter", "step": 8, "mesh": [2, 1, 1]},
    ]
    lines = elastic_lines(events)
    assert len(lines) == 3  # refactor + enter + still-degraded note
    assert "[4, 1, 1] -> [2, 1, 1]" in lines[0]
    assert "still degraded" in lines[2]
    events.append(
        {"event": "degraded_mode_exit", "step": 12, "mesh": [4, 1, 1],
         "degraded_s": 3.5}
    )
    lines = elastic_lines(events)
    assert len(lines) == 3 and "EXIT" in lines[2]
    assert elastic_lines([{"event": "run_start"}]) == []
