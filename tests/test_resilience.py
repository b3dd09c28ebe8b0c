"""Resilience-layer tests (tier-1, CPU): every failure path the subsystem
exists for, driven by deterministic fault injection — backend loss mid-run
resumes from checkpoint bit-for-bit, corrupted shards quarantine and fall
back a generation, SIGTERM mid-sweep leaves a resumable sweep state, and
the one RetryPolicy honors deadline budgets and backoff caps (with
injected clocks, so the whole policy is tested in milliseconds)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from heat3d_tpu.core.config import GridConfig, SolverConfig
from heat3d_tpu.resilience.faults import (
    FaultPlan,
    InjectedBackendLoss,
    _parse_spec,
    corrupt_one_shard,
)
from heat3d_tpu.resilience.retry import RetryPolicy
from heat3d_tpu.resilience.sweepstate import SweepState, row_key
from heat3d_tpu.utils import checkpoint as ckpt

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# The supervisor's test-speed heal policy: milliseconds, not minutes.
FAST_HEAL = RetryPolicy(
    base_delay_s=0.01, multiplier=1.5, max_delay_s=0.05, deadline_s=5.0
)


def tiny_solver(cfg=None):
    from heat3d_tpu.models.heat3d import HeatSolver3D

    return HeatSolver3D(
        cfg or SolverConfig(grid=GridConfig.cube(8), backend="jnp")
    )


# ---- RetryPolicy --------------------------------------------------------


class FakeClock:
    """Deterministic clock + sleep pair: sleep advances the clock."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def clock(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


def test_retry_backoff_schedule_and_validation():
    p = RetryPolicy(max_attempts=9, base_delay_s=2.0, multiplier=2.0,
                    max_delay_s=9.0)
    d = p.delays()
    assert [next(d) for _ in range(5)] == [2.0, 4.0, 8.0, 9.0, 9.0]
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=3, multiplier=0.5)
    with pytest.raises(ValueError):
        RetryPolicy()  # unbounded: no attempts cap AND no deadline


def test_retry_deadline_budget_clamps_last_sleep():
    """Sleeps clamp to the remaining deadline so the final attempt fires
    at the edge — the wait_for_backend contract."""
    fc = FakeClock()
    p = RetryPolicy(base_delay_s=4.0, multiplier=2.0, max_delay_s=6.0,
                    deadline_s=10.0)

    calls = []
    out = p.run(lambda: calls.append(1) and None,
                clock=fc.clock, sleep=fc.sleep)
    assert not out.ok and out.stop_reason == "deadline"
    # t=0 attempt, sleep 4; t=4 attempt, sleep min(6, 10-4)=6; t=10
    # attempt (the edge), then remaining <= 0 -> stop
    assert fc.sleeps == [4.0, 6.0]
    assert len(out.attempts) == 3
    assert [a.error for a in out.attempts] == [None, None, None]


def test_retry_first_attempt_always_runs_at_zero_deadline():
    fc = FakeClock()
    p = RetryPolicy(base_delay_s=1.0, deadline_s=0.0)
    n = []
    out = p.run(lambda: n.append(1), success=lambda v: False,
                clock=fc.clock, sleep=fc.sleep)
    assert len(n) == 1 and out.stop_reason == "deadline"


def test_retry_attempts_cap_success_and_records():
    fc = FakeClock()
    seq = iter([None, None, "tpu"])
    p = RetryPolicy(max_attempts=8, base_delay_s=1.0, multiplier=1.0,
                    max_delay_s=1.0)
    seen = []
    out = p.run(lambda: next(seq), on_attempt=seen.append,
                clock=fc.clock, sleep=fc.sleep)
    assert out.ok and out.value == "tpu" and out.stop_reason == "success"
    assert len(out.attempts) == 3 and out.attempts[-1].ok
    assert len(seen) == 3
    assert out.to_record()["attempts"] == 3

    exhausted = p.run(lambda: None, clock=fc.clock, sleep=fc.sleep)
    assert not exhausted.ok and exhausted.stop_reason == "attempts"
    assert len(exhausted.attempts) == 8


def test_retry_exception_counts_as_failed_attempt():
    fc = FakeClock()
    p = RetryPolicy(max_attempts=2, base_delay_s=0.0)

    def boom():
        raise OSError("probe spawn failed")

    out = p.run(boom, clock=fc.clock, sleep=fc.sleep)
    assert not out.ok
    assert out.attempts[0].error.startswith("OSError")
    assert out.to_record()["errors"]


def test_retry_jitter_bounded_and_deterministic():
    import random

    p = RetryPolicy(max_attempts=6, base_delay_s=10.0, multiplier=1.0,
                    max_delay_s=10.0, jitter_frac=0.2)
    runs = []
    for _ in range(2):
        fc = FakeClock()
        p.run(lambda: None, clock=fc.clock, sleep=fc.sleep,
              rng=random.Random(7))
        runs.append(fc.sleeps)
    assert runs[0] == runs[1]  # seeded rng -> same schedule
    assert all(8.0 <= s <= 10.0 for s in runs[0])  # cap bounds the high side
    assert len(set(runs[0])) > 1  # jitter actually varies


def test_retry_proceed_gate_gives_up():
    fc = FakeClock()
    p = RetryPolicy(max_attempts=10, base_delay_s=1.0)
    out = p.run(lambda: None, proceed=lambda: False,
                clock=fc.clock, sleep=fc.sleep)
    assert not out.ok and out.stop_reason == "gave_up"
    assert len(out.attempts) == 1  # the first attempt still ran


def test_retry_cli_prints_policy_delay():
    """The shell drivers' pacing goes through the same schedule."""
    from heat3d_tpu.resilience import retry

    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = retry._main(["--attempt", "2", "--base", "10", "--cap", "300",
                          "--jitter", "0"])
    assert rc == 0
    assert float(buf.getvalue()) == 15.0  # 10 * 1.5^1


# ---- FaultPlan ----------------------------------------------------------


def test_fault_spec_parsing_and_errors():
    faults = _parse_spec("backend-loss:step=8:down=2,sigterm:row=3")
    assert [f.kind for f in faults] == ["backend-loss", "sigterm"]
    assert faults[0].params == {"step": 8, "down": 2}
    with pytest.raises(ValueError):
        _parse_spec("no-such-fault:step=1")
    with pytest.raises(ValueError):
        _parse_spec("backend-loss:step=oops")
    with pytest.raises(ValueError):
        _parse_spec("backend-loss:rows=1")  # unknown param


def test_fault_one_shot_firing_and_state_dir(tmp_path):
    state = str(tmp_path / "fstate")
    os.makedirs(state)
    plan = FaultPlan(_parse_spec("backend-loss:step=4"), state_dir=state)
    plan.on_step(2)  # below the trigger: nothing
    with pytest.raises(InjectedBackendLoss):
        plan.on_step(4)
    plan.on_step(4)  # one-shot: no refire
    # a NEW plan (process restart) sees the marker and stays quiet
    plan2 = FaultPlan(_parse_spec("backend-loss:step=4"), state_dir=state)
    plan2.on_step(4)
    # down-probe override decays
    assert plan.probe_override() == "down"
    assert plan.probe_override() is None


# ---- SweepState ---------------------------------------------------------


def test_sweep_state_journal_and_torn_tail(tmp_path):
    path = str(tmp_path / "sweep.jsonl")
    s = SweepState(path)
    assert not s.is_done("a")
    s.mark_done("a", {"gcell": 1.0})
    s.mark_done("b")
    with open(path, "a") as f:
        f.write('{"key": "torn...')  # killed mid-append
    s2 = SweepState(path)
    assert s2.is_done("a") and s2.is_done("b")
    assert s2.record("a")["record"] == {"gcell": 1.0}
    assert s2.pending(["a", "b", "c"]) == ["c"]


def test_sweep_state_cli(tmp_path):
    from heat3d_tpu.resilience import sweepstate

    path = str(tmp_path / "s.jsonl")
    assert sweepstate._main(["done", path, "k1"]) == 1
    assert sweepstate._main(["mark", path, "k1"]) == 0
    assert sweepstate._main(["done", path, "k1"]) == 0


def test_row_key_covers_identity_knobs():
    import dataclasses

    cfg = SolverConfig(grid=GridConfig.cube(8))
    assert row_key(cfg) != row_key(dataclasses.replace(cfg, time_blocking=2))
    assert row_key(cfg) != row_key(cfg, "halo")
    assert row_key(cfg) == row_key(SolverConfig(grid=GridConfig.cube(8)))


# ---- checkpoint checksums ----------------------------------------------


def test_checkpoint_checksum_roundtrip_and_corruption(tmp_path, monkeypatch):
    import jax

    d = str(tmp_path / "ck")
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    u = jax.device_put(
        np.arange(4 * 4 * 4, dtype=np.float32).reshape(4, 4, 4), sh
    )
    ckpt.save(d, u, 5)
    assert os.path.exists(os.path.join(d, "shard_0_0_0.npy.crc32"))
    v, step, _ = ckpt.load(d, sh)
    assert step == 5 and np.array_equal(np.asarray(v), np.asarray(u))

    corrupt_one_shard(d)
    with pytest.raises(ckpt.ShardCorruptError):
        ckpt.load(d, sh)
    # the forensics escape hatch still reads the damaged bytes
    monkeypatch.setenv("HEAT3D_CKPT_VERIFY", "0")
    v2, _, _ = ckpt.load(d, sh)
    assert not np.array_equal(np.asarray(v2), np.asarray(u))


def test_quarantine_moves_out_of_load_path(tmp_path):
    d = tmp_path / "gen-1"
    d.mkdir()
    (d / "x").write_text("data")
    q1 = ckpt.quarantine(str(d), reason="bad crc")
    assert q1.endswith(".quarantined") and os.path.exists(q1)
    assert not d.exists()
    d.mkdir()
    q2 = ckpt.quarantine(str(d))
    assert q2.endswith(".quarantined.1")


# ---- the supervisor -----------------------------------------------------


def test_supervised_backend_loss_resumes_bitwise(tmp_path):
    """THE acceptance property: a run losing its backend at step N heals,
    resumes from the last generation, and finishes bit-for-bit equal to
    an uninterrupted supervised run on the same mesh."""
    from heat3d_tpu.resilience.supervisor import run_supervised

    clean = run_supervised(
        tiny_solver(), 12, str(tmp_path / "clean"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    plan = FaultPlan(_parse_spec("backend-loss:step=8:down=2"))
    faulted = run_supervised(
        tiny_solver(), 12, str(tmp_path / "faulted"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=plan,
    )
    assert faulted.steps_done == clean.steps_done == 12
    assert len(faulted.recoveries) == 1
    rec = faulted.recoveries[0]
    assert rec.kind == "backend-loss" and rec.resumed_from == 8
    assert rec.heal_attempts >= 3  # 2 injected down-probes + the heal
    assert np.array_equal(np.asarray(faulted.u), np.asarray(clean.u))
    assert faulted.residual == clean.residual
    # generations pruned to the newest keep=2
    gens = sorted(os.listdir(tmp_path / "faulted"))
    assert gens == ["gen-00000008", "gen-00000012"]


def test_supervised_recovery_rerecords_step_cost(tmp_path):
    """A recovery that REBUILDS the solver (make_solver) re-emits the
    step_cost ledger event tagged post_heal, so post-heal throughput is
    judged against the rebuilt program's cost model (ROADMAP
    'supervised-path step_cost'); the default reuse path does not."""
    from heat3d_tpu import obs
    from heat3d_tpu.resilience.supervisor import run_supervised

    led = str(tmp_path / "led.jsonl")
    obs.activate(led)
    plan = FaultPlan(_parse_spec("backend-loss:step=4"))
    res = run_supervised(
        tiny_solver(), 8, str(tmp_path / "ck"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=plan,
        make_solver=tiny_solver,
    )
    obs.deactivate()
    assert res.steps_done == 8 and len(res.recoveries) == 1
    evs = [
        json.loads(line)
        for line in open(led)
        if line.strip()
    ]
    costs = [
        e
        for e in evs
        if e.get("event") == "step_cost" and e.get("post_heal")
    ]
    assert len(costs) == 1
    c = costs[0]
    assert c["ok"] is True and c["step"] == 4
    assert c["cost_flops_per_step"] > 0
    # the reuse path (no make_solver) emits no post-heal event
    led2 = str(tmp_path / "led2.jsonl")
    obs.activate(led2)
    run_supervised(
        tiny_solver(), 8, str(tmp_path / "ck2"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu",
        faults=FaultPlan(_parse_spec("backend-loss:step=4")),
    )
    obs.deactivate()
    evs2 = [json.loads(line) for line in open(led2) if line.strip()]
    assert not any(
        e.get("event") == "step_cost" and e.get("post_heal") for e in evs2
    )


def test_supervised_hang_trips_watchdog_and_recovers(tmp_path):
    from heat3d_tpu.resilience.supervisor import run_supervised

    plan = FaultPlan(_parse_spec("hang:step=4"))
    res = run_supervised(
        tiny_solver(), 8, str(tmp_path / "ck"), checkpoint_every=2,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=plan,
        watchdog_s=0.05,
    )
    assert res.steps_done == 8
    assert [r.kind for r in res.recoveries] == ["hang"]
    assert res.recoveries[0].resumed_from == 4


def test_supervised_corrupt_generation_quarantines_and_falls_back(tmp_path):
    """A corrupted newest generation is detected by checksum, quarantined,
    and the PREVIOUS generation loads — the resumed run still finishes
    identically to a clean one."""
    from heat3d_tpu.resilience.supervisor import run_supervised

    root = str(tmp_path / "ck")
    first = run_supervised(
        tiny_solver(), 8, root, checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    assert sorted(os.listdir(root)) == ["gen-00000004", "gen-00000008"]
    corrupt_one_shard(os.path.join(root, "gen-00000008"))

    resumed = run_supervised(
        tiny_solver(), 12, root, checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    # fell back a generation: resumed at 4, not 8
    assert resumed.resumed_from == 4
    assert any(
        name.startswith("gen-00000008.quarantined")
        for name in os.listdir(root)
    )
    clean = run_supervised(
        tiny_solver(), 12, str(tmp_path / "clean"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    assert np.array_equal(np.asarray(resumed.u), np.asarray(clean.u))
    del first


def test_supervised_corrupt_shard_fault_hook(tmp_path):
    """The corrupt-shard FAULT (not hand-corruption) breaks the generation
    it fires on, and the next supervised invocation falls back."""
    from heat3d_tpu.resilience.supervisor import (
        load_latest_generation,
        run_supervised,
    )

    root = str(tmp_path / "ck")
    plan = FaultPlan(_parse_spec("corrupt-shard:save=2"))
    run_supervised(
        tiny_solver(), 8, root, checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=plan,
    )
    solver = tiny_solver()
    loaded, quarantined = load_latest_generation(solver, root)
    assert loaded is not None
    _, step = loaded
    assert step == 4 and len(quarantined) == 1


def test_supervised_refuses_backward_target(tmp_path):
    from heat3d_tpu.resilience.supervisor import run_supervised

    root = str(tmp_path / "ck")
    run_supervised(
        tiny_solver(), 6, root, checkpoint_every=3,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    with pytest.raises(ValueError, match="past the target"):
        run_supervised(
            tiny_solver(), 4, root, checkpoint_every=2,
            heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
        )


def test_supervised_max_recoveries_reraises(tmp_path):
    from heat3d_tpu.resilience.supervisor import run_supervised

    plan = FaultPlan(
        _parse_spec("backend-loss:step=2,backend-loss:step=2:down=1")
    )
    # two distinct loss faults at the same step but max_recoveries=1:
    # the second one must re-raise, not loop forever
    with pytest.raises(InjectedBackendLoss):
        run_supervised(
            tiny_solver(), 8, str(tmp_path / "ck"), checkpoint_every=2,
            heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=plan,
            max_recoveries=1,
        )


# ---- cross-mesh stitch resume through the supervisor --------------------


def test_supervised_resume_stitches_cross_mesh_checkpoint(tmp_path):
    """A generation saved under a DIFFERENT decomposition (here: a
    hand-built 2-block layout, as a pod checkpoint would leave) resumes
    onto this mesh through checkpoint.py's block stitching — the
    TPU->CPU cross-mesh heal path, minus the pod."""
    from heat3d_tpu.resilience.supervisor import run_supervised

    solver = tiny_solver()
    ref = run_supervised(
        solver, 8, str(tmp_path / "ref"), checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )

    # rebuild gen-4 as two x-blocks of the step-4 field + fresh manifest
    root = str(tmp_path / "ck")
    gen = os.path.join(root, "gen-00000004")
    os.makedirs(gen)
    src = np.array(
        np.load(os.path.join(str(tmp_path / "ref"), "gen-00000004",
                             "shard_0_0_0.npy"))
    )
    np.save(os.path.join(gen, "shard_0_0_0.npy"), src[:4])
    np.save(os.path.join(gen, "shard_4_0_0.npy"), src[4:])
    manifest = {
        "step": 4,
        "global_shape": [8, 8, 8],
        "dtype": "float32",
        "format": 1,
        "shards": [[0, 0, 0], [4, 0, 0]],
        "extra": {},
    }
    with open(os.path.join(gen, "manifest.json"), "w") as f:
        json.dump(manifest, f)

    resumed = run_supervised(
        tiny_solver(), 8, root, checkpoint_every=4,
        heal_policy=FAST_HEAL, probe=lambda: "cpu", faults=FaultPlan(),
    )
    assert resumed.resumed_from == 4
    assert np.array_equal(np.asarray(resumed.u), np.asarray(ref.u))


# ---- SIGTERM mid-sweep + CLI kill/resume (subprocess tier) --------------


def _cpu_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([REPO, env.get("PYTHONPATH", "")])
    env.update(extra or {})
    return env


def test_sigterm_mid_sweep_leaves_resumable_state(tmp_path):
    """SIGTERM mid-sweep: the killed session leaves a sweep-state journal;
    the rerun emits the journaled row VERBATIM (not re-measured) and
    measures only the missing rows."""
    state = str(tmp_path / "sweep.jsonl")
    fstate = str(tmp_path / "fstate")
    args = [
        sys.executable, "-m", "heat3d_tpu.bench", "--grid", "8",
        "--steps", "2", "--mesh", "1", "1", "1", "--backend", "jnp",
        "--bench", "all", "--sweep-state", state,
    ]
    env = _cpu_env({
        "HEAT3D_FAULTS": "sigterm:row=1",
        "HEAT3D_FAULT_STATE": fstate,
    })
    first = subprocess.run(
        args, env=env, capture_output=True, text=True, timeout=300, cwd=REPO
    )
    assert first.returncode == 3, first.stderr  # SIGTERM -> SystemExit(3)
    journal = SweepState(state)
    assert len(journal.keys()) == 1  # row 0 landed, row 1 was killed
    (key0,) = journal.keys()
    landed = journal.record(key0)["record"]

    second = subprocess.run(
        args, env=env, capture_output=True, text=True, timeout=300, cwd=REPO
    )
    assert second.returncode == 0, second.stderr
    rows = [json.loads(ln) for ln in second.stdout.strip().splitlines()
            if ln.startswith("{")]
    assert len(rows) == 2
    # completed row re-emitted from the journal, byte-identical timing
    # fields prove it was NOT re-measured
    assert rows[0] == landed
    assert {r["bench"] for r in rows} == {"throughput", "halo"}
    assert len(SweepState(state).keys()) == 2


@pytest.mark.slow
def test_cli_supervise_kill_and_resume_matches_clean(tmp_path):
    """CLI tier of the acceptance property: `--supervise` killed at step N
    by an injected SIGTERM resumes on relaunch and the final checkpoint's
    shard BYTES equal a never-killed run's."""
    def run_cli(ck, faults=None):
        env = _cpu_env(
            {"HEAT3D_FAULTS": faults,
             "HEAT3D_FAULT_STATE": str(tmp_path / "fstate")}
            if faults else {}
        )
        return subprocess.run(
            [sys.executable, "-m", "heat3d_tpu", "--grid", "8", "--steps",
             "8", "--backend", "jnp", "--checkpoint", ck,
             "--checkpoint-every", "2", "--supervise"],
            env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
        )

    clean = run_cli(str(tmp_path / "ck_clean"))
    assert clean.returncode == 0, clean.stderr

    killed = run_cli(str(tmp_path / "ck_kill"), faults="sigterm:step=4")
    assert killed.returncode == 3, killed.stderr
    gens = sorted(os.listdir(tmp_path / "ck_kill"))
    assert gens and gens[-1] < "gen-00000008"  # died before the end

    resumed = run_cli(str(tmp_path / "ck_kill"), faults="sigterm:step=4")
    assert resumed.returncode == 0, resumed.stderr
    summary = json.loads(
        [ln for ln in resumed.stdout.splitlines() if ln.startswith("{")][-1]
    )
    assert summary["supervised"]["steps_done"] == 8
    assert summary["supervised"]["start_step"] >= 4

    a = np.load(os.path.join(tmp_path, "ck_clean", "gen-00000008",
                             "shard_0_0_0.npy"))
    b = np.load(os.path.join(tmp_path, "ck_kill", "gen-00000008",
                             "shard_0_0_0.npy"))
    assert np.array_equal(a, b)  # bit-for-bit, same mesh


# ---- provenance lint ----------------------------------------------------


def _load_check_provenance():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_provenance", os.path.join(REPO, "scripts", "check_provenance.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_provenance_catches_null_ts_and_missing_routes(tmp_path):
    mod = _load_check_provenance()
    good = {
        "bench": "throughput", "ts": "2026-01-01T00:00:00Z",
        "platform": "tpu", "direct_path": True, "mehrstellen_route": False,
        "streamk_path": False, "streamk_emulated": False,
        "halo_plan": "monolithic",
        "chain_ops": 7, "backend": "auto", "sync_rtt_s": 7.5e-2,
        # ensemble-workload provenance (PR 7): required on every
        # throughput row — solo rows carry [1]/1
        "batch_shape": [1], "members_per_step": 1,
        # equation-family provenance (PR 11): required on every
        # throughput row — legacy rows key to heat downstream
        "equation": "heat",
        # time-integrator provenance (PR 19): required on every
        # throughput row — integrators share grids but not programs
        "integrator": "explicit-euler",
    }
    halo_good = {
        "bench": "halo", "ts": "2026-01-01T00:00:00Z", "platform": "tpu",
        "sync_rtt_s": 7.5e-2, "halo_plan": "monolithic",
    }
    rows = [
        good,
        {**good, "ts": None},                      # the VERDICT r5 defect
        {k: v for k, v in good.items() if k != "streamk_emulated"},
        {**good, "chain_ops": None},               # null ops on non-conv
        {**good, "chain_ops": None, "backend": "conv"},  # legal for conv
        halo_good,
        {k: v for k, v in halo_good.items() if k != "platform"},
        {"metric": "gcell_updates_per_sec_per_chip"},  # foreign line: pass
        # RTT provenance (obs PR): a bench row without its measured
        # sync_rtt_s cannot be audited for RTT domination
        {k: v for k, v in good.items() if k != "sync_rtt_s"},
        {**halo_good, "sync_rtt_s": None},
    ]
    p = tmp_path / "r.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows))
    bad = mod.check_file(str(p))
    assert [line for line, _ in bad] == [2, 3, 4, 7, 9, 10]
    assert mod.main([str(p)]) == 1

    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps(good) + "\n")
    assert mod.main([str(ok)]) == 0

    # --start-line scopes an APPEND session's lint to ITS rows: legacy
    # defects above the line must not keep a clean session red
    mixed = tmp_path / "mixed.jsonl"
    mixed.write_text(
        json.dumps({**good, "ts": None}) + "\n" + json.dumps(good) + "\n"
    )
    assert mod.main([str(mixed)]) == 1
    assert mod.main(["--start-line", "2", str(mixed)]) == 0


def test_fresh_bench_rows_pass_the_provenance_lint():
    """The lint and the harness must agree: a row the harness emits today
    passes the lint (streamk_emulated + ts + route fields present)."""
    from heat3d_tpu.bench.harness import bench_throughput

    mod = _load_check_provenance()
    cfg = SolverConfig(grid=GridConfig.cube(16), backend="jnp")
    r = bench_throughput(cfg, steps=2, warmup=1, repeats=1)
    assert r["streamk_emulated"] is False
    assert not mod.check_row(r), mod.check_row(r)
