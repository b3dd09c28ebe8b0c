"""Observability-layer tests (tier-1, CPU): the run ledger records spans
and events with schema-valid nesting, the metrics registry exports
Prometheus/JSON snapshots, the ledger lint catches seeded defects, the
obs CLI reconstructs step-latency percentiles that match the run's own
numbers, fault injection is itself observable, and the satellite fixes
(narrowed logging filter, per-backend RTT cache, summarize_trace
aggregation) cannot regress."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

from heat3d_tpu import obs
from heat3d_tpu.core.config import GridConfig, SolverConfig
from heat3d_tpu.obs import check as ledger_check
from heat3d_tpu.obs.ledger import Ledger
from heat3d_tpu.obs.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Every test starts and ends with no active ledger (the module-level
    singleton would otherwise leak spans across tests)."""
    obs.deactivate()
    yield
    obs.deactivate()


def _read(path):
    return [json.loads(ln) for ln in open(path) if ln.strip()]


# ---- ledger -------------------------------------------------------------


def test_ledger_events_spans_and_context(tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = obs.activate(p, meta={"entry": "test"})
    assert obs.get() is led and led.active
    led.set_context(generation=4)
    led.event("run_start", grid=[8, 8, 8])
    with led.span("outer", steps=2) as sp:
        with led.span("inner"):
            pass
        sp.add(note="x")
    assert sp.dur_s is not None and sp.dur_s >= 0
    with pytest.raises(RuntimeError, match="boom"):
        with led.span("fails"):
            raise RuntimeError("boom")
    obs.deactivate(rc=0)

    evs = _read(p)
    names = [e["event"] for e in evs]
    assert names == [
        "ledger_open", "run_start", "inner", "outer", "fails", "ledger_close",
    ]
    # envelope on every event; context tag on everything after set_context
    for e in evs:
        for f in ("ts", "run_id", "proc", "seq", "event", "kind"):
            assert f in e
    assert all(e["generation"] == 4 for e in evs[1:])
    assert len({e["run_id"] for e in evs}) == 1
    outer = evs[names.index("outer")]
    inner = evs[names.index("inner")]
    # spans written at close: child precedes parent, bounds nest
    assert inner["seq"] < outer["seq"]
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["note"] == "x" and outer["steps"] == 2
    failed = evs[names.index("fails")]
    assert failed["status"] == "error" and "boom" in failed["error"]
    # the freshly generated ledger passes its own lint (and the script
    # wrapper agrees — the CI gate and the library cannot drift)
    assert ledger_check.check_file(p) == []
    assert ledger_check.main([p]) == 0


def test_ledger_null_when_unconfigured_and_env_activation(tmp_path, monkeypatch):
    monkeypatch.delenv("HEAT3D_LEDGER", raising=False)
    led = obs.get()
    assert not led.active
    led.event("ignored")
    with led.span("ignored") as sp:
        pass
    assert sp.dur_s is not None  # null spans still time (callers use dur_s)

    p = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("HEAT3D_LEDGER", p)
    obs.deactivate()  # re-arm env detection
    led2 = obs.get()
    assert led2.active
    led2.event("hello")
    obs.deactivate()
    assert [e["event"] for e in _read(p)] == ["ledger_open", "hello",
                                             "ledger_close"]


def test_ledger_unserializable_span_field_salvaged_schema_valid(tmp_path):
    """A span field json cannot serialize (circular dict) is dropped, not
    the whole record's span fields — the salvage record must still pass
    the project's own lint (it gates the bench suite's rc)."""
    p = str(tmp_path / "l.jsonl")
    led = Ledger(p)
    circular: dict = {}
    circular["self"] = circular
    with led.span("chunk", steps=2, bad=circular):
        pass
    led.event("point_bad", bad=circular, fine=1)
    led.close()
    evs = _read(p)
    chunk = next(e for e in evs if e["event"] == "chunk")
    assert chunk["kind"] == "span" and chunk["status"] == "ok"
    assert all(f in chunk for f in ("t0", "t1", "dur_s", "depth"))
    assert chunk["malformed_fields"] == ["bad"] and chunk["steps"] == 2
    pt = next(e for e in evs if e["event"] == "point_bad")
    assert pt["fine"] == 1 and "bad" not in pt
    assert ledger_check.check_file(p) == []


# ---- spans: one API, three sinks -----------------------------------------


def _ring_since(mark):
    return [r for r in obs.spans() if r["id"] > mark]


def _last_id():
    recs = obs.spans()
    return recs[-1]["id"] if recs else -1


def test_spans_reach_the_ring_with_no_ledger_active(monkeypatch):
    monkeypatch.delenv("HEAT3D_LEDGER", raising=False)
    mark = _last_id()
    led = obs.get()
    assert not led.active
    with led.span("outer", steps=3) as outer:
        with led.span("inner") as inner:
            inner.add(note="x")
    with pytest.raises(RuntimeError, match="boom"):
        with led.span("fails"):
            raise RuntimeError("boom")
    recs = {r["name"]: r for r in _ring_since(mark)}
    assert set(recs) == {"outer", "inner", "fails"}
    assert recs["outer"]["parent"] is None
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert recs["inner"]["fields"] == {"note": "x"}
    assert recs["outer"]["fields"] == {"steps": 3}
    assert recs["outer"]["start_ns"] <= recs["inner"]["start_ns"]
    assert recs["inner"]["end_ns"] <= recs["outer"]["end_ns"]
    assert recs["fails"]["status"] == "error"
    assert "boom" in recs["fails"]["fields"]["error"]
    assert outer.dur_s is not None and outer.dur_s >= 0


def test_span_ring_stays_bounded():
    from heat3d_tpu.obs.trace import SpanRing

    ring = SpanRing(size=8)
    for i in range(20):
        ring.close(ring.open(f"s{i}", {}), ok=True)
    names = [r["name"] for r in ring.records()]
    assert names == [f"s{i}" for i in range(12, 20)]
    assert ring.innermost() is None


def test_span_ring_record_matches_its_trace_annotation(tmp_path):
    """The ring's clock is the profiler's: a span's ``start_ns`` equals
    its TraceAnnotation twin's offset plus the trace's
    ``profile_start_time``, within 1 ms, and so does its end."""
    import glob
    import time

    import jax

    mark = _last_id()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.get().span("twin_probe"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (rec,) = [r for r in _ring_since(mark) if r["name"] == "twin_probe"]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    (env,) = [p for p in pd.planes if p.name == "Task Environment"]
    t0 = dict(env.stats)["profile_start_time"]
    twins = [
        ev
        for p in pd.planes
        for line in p.lines
        for ev in line.events
        if ev.name == "heat3d.twin_probe"
    ]
    assert len(twins) == 1
    ev = twins[0]
    assert abs(t0 + ev.start_ns - rec["start_ns"]) < 1e6
    assert abs(t0 + ev.start_ns + ev.duration_ns - rec["end_ns"]) < 1e6


def test_first_dispatch_carries_compile_s_and_the_second_none():
    from heat3d_tpu.core.config import GridConfig, SolverConfig
    from heat3d_tpu.models.heat3d import HeatSolver3D

    solver = HeatSolver3D(
        SolverConfig(grid=GridConfig.cube(8), backend="jnp", time_blocking=2)
    )
    mark = _last_id()
    u = solver.init_state("hot-cube")
    u = solver.run(u, 3)
    u = solver.run(u, 3)
    u, _ = solver.step_with_residual(u)
    recs = _ring_since(mark)
    runs = [r for r in recs if r["name"] == "solver.dispatch"]
    assert [r["fields"]["program"] for r in runs] == [
        "run", "run", "step_residual",
    ]
    assert runs[0]["fields"]["compile_s"] > 0
    assert "compile_s" not in runs[1]["fields"]
    assert runs[2]["fields"]["compile_s"] > 0
    assert [r["fields"]["steps"] for r in runs] == [3, 3, 1]
    (init,) = [r for r in recs if r["name"] == "init_state"]
    assert init["end_ns"] <= runs[0]["start_ns"]


def test_converge_dispatch_span_reads_steps_after_it_closes():
    """The ``converge`` dispatch span closes when the program call
    returns; its ``steps`` (a device value) is read after the span and
    still reaches the ring record. The halo bytes of each program follow
    its loop: ``n // k`` width-k supersteps and ``n % k`` steps, and with
    ``residual_every = K`` groups of K - 1 loop updates and a residual
    step."""
    from heat3d_tpu.core.config import GridConfig, RunConfig, SolverConfig
    from heat3d_tpu.models.heat3d import HeatSolver3D

    solver = HeatSolver3D(SolverConfig(
        grid=GridConfig.cube(8), backend="jnp", time_blocking=2,
        run=RunConfig(residual_every=4),
    ))
    u = solver.init_state("hot-cube")
    mark = _last_id()
    result = solver.run_to_convergence(u, tol=0.0, max_steps=9)
    (rec,) = [r for r in _ring_since(mark) if r["name"] == "solver.dispatch"]
    assert rec["fields"]["program"] == "converge"
    assert rec["fields"]["steps"] == result.steps == 9
    # priced per exchange: 10 bytes at width 1, 30 at width 2
    solver._exchange_bytes = (10, 30)
    assert solver._halo_bytes("run", 5) == 2 * 30 + 10
    assert solver._halo_bytes("step_residual", 1) == 10
    # two groups of (30 + 10) + 10, then a cut group of one residual step
    assert solver._halo_bytes("converge", 9) == 2 * 50 + 10


_HALO_PROBE = """
import json, re
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from heat3d_tpu import obs
from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
from heat3d_tpu.models.heat3d import HeatSolver3D
from heat3d_tpu.parallel.step import make_superstep_fn

cfg = SolverConfig(grid=GridConfig(shape=(16, 16, 8)),
                   mesh=MeshConfig(shape=(2, 2, 1)), backend="auto",
                   time_blocking=2)
solver = HeatSolver3D(cfg)
u = solver.init_state("hot-cube")
u = jax.block_until_ready(solver.run(u, 2))
snap = obs.REGISTRY.snapshot()
superstep = make_superstep_fn(solver.cfg, solver.mesh)
x = jax.ShapeDtypeStruct(cfg.grid.shape, jnp.float32,
                         sharding=NamedSharding(solver.mesh, P("x", "y", "z")))
text = jax.jit(superstep).lower(x).compile().as_text()
permutes = re.findall(r"= (\\w+)\\[([\\d,]*)\\][^ ]* collective-permute\\(", text)
print(json.dumps({
    "route": solver.route,
    "counter_bytes": snap["heat3d_halo_bytes_total"]["values"][""],
    "steps": snap["heat3d_solver_steps_total"]["values"],
    "permutes": permutes,
}))
"""


def test_halo_counter_equals_the_superstep_collective_permute_bytes():
    """On a (2,2,1) CPU mesh with the faces-direct kernel route emulated
    (HEAT3D_DIRECT_INTERPRET), one superstep's dispatch adds to the halo
    bytes counter exactly the operand bytes of the collective-permutes in
    the compiled superstep program."""
    import subprocess

    import numpy as np

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        HEAT3D_DIRECT_INTERPRET="1",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=REPO,
    )
    p = subprocess.run(
        [sys.executable, "-c", _HALO_PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["route"]["kernel"] == "direct"
    itemsize = {"f32": 4}
    permute_bytes = sum(
        itemsize[dt] * int(np.prod([int(d) for d in dims.split(",")]))
        for dt, dims in out["permutes"]
    )
    # x faces (2,8,8) and x-extended y faces (12,2,8), both directions
    assert permute_bytes == 4 * 2 * (2 * 8 * 8 + 12 * 2 * 8)
    assert len(out["permutes"]) == 4
    assert out["counter_bytes"] == permute_bytes
    assert out["steps"] == {'{program="run"}': 2.0}


def test_metrics_export_unwritable_path_does_not_raise(tmp_path, monkeypatch):
    """export_at_exit on an unwritable HEAT3D_METRICS path logs and
    returns None — telemetry must not turn a completed run into a
    nonzero exit."""
    from heat3d_tpu.obs.metrics import export_at_exit

    # a FILE where a parent directory is needed fails for every uid
    # (root ignores directory modes, so chmod-based fixtures skip there)
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    monkeypatch.setenv("HEAT3D_METRICS", str(blocker / "m.json"))
    assert export_at_exit() is None


def test_check_ledger_start_line_scopes_report(tmp_path):
    """--start-line hides historical defects from APPEND resume sessions
    (full-file context still parsed) — same contract as
    check_provenance.py's scoping."""
    p = str(tmp_path / "l.jsonl")
    _write_ledger(p, [
        _envelope(0, "orphan", run_id="dead"),     # historical defect
        _envelope(0, "ledger_open", run_id="r9"),
        _envelope(1, "fine", run_id="r9"),
    ])
    assert ledger_check.main([p]) == 1
    assert ledger_check.main(["--start-line", "2", p]) == 0


def test_ledger_envelope_fields_never_clobbered(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.event("x", seq=999, run_id="fake", kind="span")
    led.close()
    evs = _read(led.path)
    x = evs[1]
    assert x["seq"] == 1 and x["run_id"] == led.run_id and x["kind"] == "point"


def test_ledger_fails_soft_never_kills_the_run(tmp_path, capsys):
    """Telemetry must not kill the run it observes: an unwritable path
    fails soft at activation (NULL ledger + stderr note), and a write
    error mid-run disables the ledger instead of raising."""
    blocker = tmp_path / "f"
    blocker.write_text("")
    led = obs.activate(str(blocker / "led.jsonl"))  # parent is a FILE
    assert not led.active
    led.event("still_fine")  # no-op, no raise
    assert "running without one" in capsys.readouterr().err

    p = str(tmp_path / "l.jsonl")
    led2 = Ledger(p)
    led2.event("before")
    real_f = led2._f

    def die(_):
        raise OSError(28, "No space left on device")

    led2._f = SimpleNamespace(
        closed=False, write=die, flush=lambda: None, close=real_f.close
    )
    led2.event("after")  # must not raise; ledger disables itself
    assert "disabled" in capsys.readouterr().err
    led2._f = real_f  # the real (now closed) file: later events drop
    led2.event("later")
    led2.close()
    assert [e["event"] for e in _read(p)] == ["ledger_open", "before"]


# ---- metrics registry ---------------------------------------------------


def test_metrics_counter_gauge_histogram_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("retries_total", "help text")
    c.inc()
    c.inc(2, reason="deadline")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("rtt_seconds")
    g.set(0.075, backend="tpu")
    h = reg.histogram("lat_seconds")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    snap = reg.snapshot()
    assert snap["heat3d_retries_total"]["values"][""] == 1
    assert snap["heat3d_retries_total"]["values"]['{reason="deadline"}'] == 2
    st = snap["heat3d_lat_seconds"]["values"][""]
    assert st["count"] == 5 and st["min"] == 1.0 and st["max"] == 100.0
    assert st["p50"] == 3.0 and st["p95"] == 100.0
    # same-name different-type registration is a bug, not a silent alias
    with pytest.raises(TypeError):
        reg.gauge("retries_total")


def test_metrics_prometheus_text_and_files(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total", "things").inc(3)
    reg.histogram("lat_seconds").observe(2.0)
    text = reg.to_prometheus_text()
    assert "# TYPE heat3d_a_total counter" in text
    assert "heat3d_a_total 3.0" in text
    assert "# TYPE heat3d_lat_seconds summary" in text
    assert 'heat3d_lat_seconds{quantile="0.5"} 2.0' in text
    prom = tmp_path / "m.prom"
    reg.write_snapshot(str(prom))
    assert prom.read_text() == text
    js = tmp_path / "m.json"
    reg.write_snapshot(str(js))
    assert json.loads(js.read_text())["heat3d_a_total"]["kind"] == "counter"


def test_histogram_cap_marks_clipped():
    from heat3d_tpu.obs.metrics import HISTOGRAM_SAMPLE_CAP

    reg = MetricsRegistry()
    h = reg.histogram("big")
    for i in range(HISTOGRAM_SAMPLE_CAP + 10):
        h.observe(float(i))
    st = h.stats()
    assert st["count"] == HISTOGRAM_SAMPLE_CAP + 10
    assert st["clipped"] is True


# ---- ledger lint --------------------------------------------------------


def _envelope(seq, event="e", kind="point", run_id="r1", **extra):
    rec = {
        "ts": 100.0 + seq, "run_id": run_id, "proc": 0, "seq": seq,
        "event": event, "kind": kind,
    }
    rec.update(extra)
    return rec


def _write_ledger(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_check_ledger_catches_seeded_defects(tmp_path):
    p = str(tmp_path / "bad.jsonl")
    span = dict(t0=1.0, t1=2.0, dur_s=1.0, depth=0, status="ok")
    _write_ledger(p, [
        _envelope(0, "ledger_open"),
        _envelope(1, "ok_span", kind="span", **span),
        {k: v for k, v in _envelope(2).items() if k != "run_id"},  # missing
        _envelope(3, kind="bogus"),                    # bad kind
        _envelope(4, "torn", kind="span", t0=5.0, t1=4.0, dur_s=-1.0,
                  depth=0, status="ok"),               # ends before start
        _envelope(2),                                  # seq regression
        _envelope(90, "orphan", run_id="r2"),          # no ledger_open
    ])
    msgs = [m for _, m in ledger_check.check_file(p)]
    assert any("missing required field 'run_id'" in m for m in msgs)
    assert any("not 'point' or 'span'" in m for m in msgs)
    assert any("ends before it starts" in m for m in msgs)
    assert any("not above seq" in m for m in msgs)
    assert any("no ledger_open" in m for m in msgs)
    assert ledger_check.main([p]) == 1


def test_check_ledger_span_nesting_rule(tmp_path):
    def spans(path, bounds):
        recs = [_envelope(0, "ledger_open")]
        for i, (t0, t1) in enumerate(bounds, start=1):
            recs.append(_envelope(
                i, f"s{i}", kind="span", t0=t0, t1=t1, dur_s=t1 - t0,
                depth=0, status="ok",
            ))
        _write_ledger(path, recs)

    ok = str(tmp_path / "nested.jsonl")
    # disjoint, contained, deeper-contained: a proper laminar family
    spans(ok, [(1.0, 2.0), (2.5, 6.0), (3.0, 4.0), (3.2, 3.8)])
    assert ledger_check.check_file(ok) == []

    bad = str(tmp_path / "overlap.jsonl")
    spans(bad, [(1.0, 3.0), (2.0, 4.0)])  # partial overlap
    msgs = [m for _, m in ledger_check.check_file(bad)]
    assert any("partially overlaps" in m for m in msgs)


def test_check_ledger_script_wrapper_on_fresh_ledger(tmp_path):
    """Satellite: the scripts/check_ledger.py entry point (the thing
    run_bench_suite.sh invokes) passes on a freshly generated ledger and
    fails on a torn one."""
    import subprocess

    p = str(tmp_path / "fresh.jsonl")
    led = obs.activate(p)
    with led.span("steps", steps=4):
        pass
    led.event("run_summary", steps=4)
    obs.deactivate(rc=0)

    script = os.path.join(REPO, "scripts", "check_ledger.py")
    r = subprocess.run(
        [sys.executable, script, p], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    with open(p, "a") as f:
        f.write('{"event": "torn"}\n')
    r2 = subprocess.run(
        [sys.executable, script, p], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert r2.returncode == 1


# ---- obs CLI ------------------------------------------------------------


def test_obs_cli_summary_tail_check(tmp_path, capsys):
    from heat3d_tpu.cli import main as heat3d_main

    p = str(tmp_path / "led.jsonl")
    led = obs.activate(p)
    led.event("run_start", grid=[8, 8, 8])
    for n in (4, 4, 2):
        with led.span("steps", steps=n):
            pass
    obs.deactivate()

    assert heat3d_main(["obs", "check", p]) == 0
    capsys.readouterr()
    assert heat3d_main(["obs", "summary", p]) == 0
    out = capsys.readouterr().out
    assert "run_start" in out and "steps" in out
    assert "step latency" in out
    assert heat3d_main(["obs", "tail", p, "-n", "2"]) == 0
    tail = capsys.readouterr().out
    assert len(tail.strip().splitlines()) == 2


def test_obs_cli_step_latency_reconstruction(tmp_path, capsys):
    """p50/p95 from span records with known durations: one sample per
    span, dur/steps — the documented reconstruction rule."""
    from heat3d_tpu.obs.cli import step_latencies

    events = [
        {"kind": "span", "event": "chunk", "status": "ok", "steps": 4,
         "dur_s": 0.4},
        {"kind": "span", "event": "chunk", "status": "ok", "steps": 2,
         "dur_s": 0.1},
        {"kind": "span", "event": "chunk", "status": "error", "steps": 4,
         "dur_s": 9.9},   # failed chunk: excluded
        {"kind": "span", "event": "ckpt_save", "status": "ok",
         "dur_s": 1.0},   # not a step span
        {"kind": "point", "event": "chunk", "steps": 4},
    ]
    lats = step_latencies(events)
    assert lats == [0.1, 0.05]


# ---- instrumented subsystems -------------------------------------------


def test_retry_policy_writes_ledger_events(tmp_path):
    from heat3d_tpu.resilience.retry import RetryPolicy

    p = str(tmp_path / "led.jsonl")
    obs.activate(p)
    calls = {"n": 0}

    def attempt():
        calls["n"] += 1
        return "up" if calls["n"] >= 3 else None

    policy = RetryPolicy(max_attempts=5, base_delay_s=0.0, max_delay_s=0.0)
    outcome = policy.run(attempt)
    obs.deactivate()
    assert outcome.ok
    evs = _read(p)
    attempts = [e for e in evs if e["event"] == "retry_attempt"]
    outcomes = [e for e in evs if e["event"] == "retry_outcome"]
    assert len(attempts) == 3
    assert [a["ok"] for a in attempts] == [False, False, True]
    assert outcomes[-1]["stop_reason"] == "success"


def test_fault_injection_is_observable(tmp_path):
    """Satellite: every fired fault leaves a fault_injected ledger event
    — asserting observability of the injection itself."""
    from heat3d_tpu.resilience.faults import FaultPlan, InjectedBackendLoss, _parse_spec

    p = str(tmp_path / "led.jsonl")
    obs.activate(p)
    plan = FaultPlan(_parse_spec("backend-loss:step=8:down=1"))
    plan.on_step(4)  # below the trigger: no event
    with pytest.raises(InjectedBackendLoss):
        plan.on_step(8)
    plan.on_step(9)  # one-shot: no second event
    obs.deactivate()
    faults = [e for e in _read(p) if e["event"] == "fault_injected"]
    assert len(faults) == 1
    assert faults[0]["kind_"] == "backend-loss"
    assert faults[0]["step"] == 8
    assert faults[0]["params"] == {"step": 8, "down": 1}


def test_checkpoint_save_load_quarantine_events(tmp_path):
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.resilience.faults import corrupt_one_shard
    from heat3d_tpu.utils import checkpoint as ckpt

    p = str(tmp_path / "led.jsonl")
    obs.activate(p)
    obs.REGISTRY.reset()
    solver = HeatSolver3D(SolverConfig(grid=GridConfig.cube(8), backend="jnp"))
    u = solver.init_state("hot-cube")
    ck = str(tmp_path / "ck")
    solver.save_checkpoint(ck, u, 3)
    solver.load_checkpoint(ck)
    corrupt_one_shard(ck)
    with pytest.raises(ckpt.ShardCorruptError):
        solver.load_checkpoint(ck)
    ckpt.quarantine(ck, reason="test")
    obs.deactivate()
    evs = _read(p)
    names = [e["event"] for e in evs]
    assert "ckpt_save" in names and "ckpt_load" in names
    assert "ckpt_corrupt" in names and "ckpt_quarantine" in names
    saves = [e for e in evs if e["event"] == "ckpt_save"]
    assert saves[0]["status"] == "ok" and saves[0]["step"] == 3
    assert saves[0]["shards"] >= 1 and saves[0]["bytes"] > 0
    loads = [e for e in evs if e["event"] == "ckpt_load"]
    assert loads[0]["status"] == "ok" and loads[-1]["status"] == "error"
    snap = obs.REGISTRY.snapshot()
    assert snap["heat3d_ckpt_writes_total"]["values"][""] == 1
    verify = snap["heat3d_ckpt_verify_total"]["values"]
    assert verify['{result="ok"}'] >= 1
    assert verify['{result="corrupt"}'] == 1
    assert snap["heat3d_ckpt_quarantine_total"]["values"][""] == 1
    assert ledger_check.check_file(p) == []


def test_bench_rows_carry_sync_rtt_and_land_in_ledger(tmp_path):
    from heat3d_tpu.bench.harness import bench_halo, bench_throughput

    p = str(tmp_path / "led.jsonl")
    obs.activate(p)
    cfg = SolverConfig(grid=GridConfig.cube(16), backend="jnp")
    t = bench_throughput(cfg, steps=2, warmup=1, repeats=1)
    h = bench_halo(cfg, iters=3, warmup=1)
    obs.deactivate()
    assert isinstance(t["sync_rtt_s"], float)
    assert isinstance(h["sync_rtt_s"], float)
    rows = [e for e in _read(p) if e["event"] == "bench_row"]
    assert {r["bench"] for r in rows} == {"throughput", "halo"}
    # the row's UTC measurement timestamp survives the envelope collision
    # as ts_ — the join key back to bench_results.jsonl
    assert all(r["ts_"] in (t["ts"], h["ts"]) for r in rows)
    # ... and the fresh rows pass the extended provenance lint
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_provenance", os.path.join(REPO, "scripts", "check_provenance.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert not mod.check_row(t), mod.check_row(t)
    assert not mod.check_row(h), mod.check_row(h)


# ---- satellite: sync_overhead per-backend cache -------------------------


def test_sync_overhead_cached_per_backend(monkeypatch):
    from heat3d_tpu.utils import timing

    timing.reset_sync_overhead_cache()
    calls = {"n": 0}
    real_force_sync = timing.force_sync

    def counting_force_sync(x):
        calls["n"] += 1
        return real_force_sync(x)

    monkeypatch.setattr(timing, "force_sync", counting_force_sync)
    r1 = timing.sync_overhead(samples=2)
    n_after_first = calls["n"]
    assert n_after_first == 3  # 1 warm + 2 samples
    r2 = timing.sync_overhead(samples=2)
    assert r2 == r1
    assert calls["n"] == n_after_first  # cached: no new syncs
    r3 = timing.sync_overhead(samples=2, refresh=True)
    assert calls["n"] == 2 * n_after_first
    assert isinstance(r3, float)
    # the measured RTT is published as a per-backend gauge
    import jax

    g = obs.REGISTRY.gauge("sync_rtt_seconds")
    assert g.value(backend=jax.default_backend()) is not None
    timing.reset_sync_overhead_cache()


# ---- satellite: narrowed _Process0Filter --------------------------------


def test_process0_filter_narrowed_exceptions(monkeypatch):
    import logging as pylogging

    from heat3d_tpu.utils.logging import _Process0Filter

    f = _Process0Filter()
    rec = pylogging.LogRecord("n", pylogging.INFO, "p", 1, "m", (), None)
    warn = pylogging.LogRecord("n", pylogging.WARNING, "p", 1, "m", (), None)
    assert f.filter(warn) is True  # WARNING+ always passes

    # expected failures (backend state not queryable) assume-coordinator
    import jax._src.xla_bridge as xb

    monkeypatch.setattr(
        xb, "backends_are_initialized",
        lambda: (_ for _ in ()).throw(RuntimeError("not ready")),
    )
    assert f.filter(rec) is True

    # an UNEXPECTED failure must propagate — the bare-except bug this
    # satellite fixes would have silently returned True here
    monkeypatch.setattr(
        xb, "backends_are_initialized",
        lambda: (_ for _ in ()).throw(ValueError("real bug")),
    )
    with pytest.raises(ValueError, match="real bug"):
        f.filter(rec)


# ---- satellite: summarize_trace aggregation ----------------------------


def _load_summarize_trace():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "summarize_trace", os.path.join(REPO, "scripts", "summarize_trace.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(metadata_id, duration_ps):
    return SimpleNamespace(metadata_id=metadata_id, duration_ps=duration_ps)


def test_summarize_trace_single_line_aggregation_rule():
    """The double-count fix: a plane carries several lines covering the
    SAME wall time; exactly ONE is aggregated — the op-level line when
    present, else the busiest."""
    mod = _load_summarize_trace()
    meta = {
        1: SimpleNamespace(name="fusion.1"),
        2: SimpleNamespace(name="heat3d.stencil/fusion.2"),
        3: SimpleNamespace(name="heat3d.stencil/heat3d.halo_exchange/ppermute.3"),
    }
    ops_line = SimpleNamespace(
        name="XLA Ops", events=[_ev(1, 2e6), _ev(2, 3e6), _ev(2, 1e6),
                                _ev(3, 4e6)]
    )
    module_line = SimpleNamespace(
        name="XLA Modules", events=[_ev(1, 10e6)]  # same wall time, coarser
    )
    steps_line = SimpleNamespace(name="Steps", events=[_ev(1, 10e6)])

    picked = mod.pick_line([module_line, ops_line, steps_line])
    assert picked is ops_line  # the op-level line wins over busier lines

    totals, counts = mod.aggregate_line(picked, meta)
    assert totals["heat3d.stencil/fusion.2"] == pytest.approx(4.0)  # us
    assert counts["heat3d.stencil/fusion.2"] == 2
    # the sum is ONE line's time, not all lines' (no double count)
    assert sum(totals.values()) == pytest.approx(10.0)

    # without an op line, the busiest line is aggregated
    assert mod.pick_line([module_line, steps_line]) in (module_line, steps_line)

    # phase attribution groups by the INNERMOST heat3d scope
    phases = mod.phase_totals(totals)
    assert phases["heat3d.stencil"] == pytest.approx(4.0)
    assert phases["heat3d.halo_exchange"] == pytest.approx(4.0)
    assert phases["(unattributed)"] == pytest.approx(2.0)
    # a host-plane python frame naming the FILE heat3d.py is not a phase
    assert mod.phase_name("$heat3d.py:301 run") is None
    assert mod.phase_name("heat3d.warmup") == "heat3d.warmup"
    # dotted sub-phases survive whole (the per-axis halo scopes), and the
    # innermost-token rule still applies across nested scopes; XLA's ".N"
    # op suffixes are not swallowed into the phase
    assert (
        mod.phase_name("heat3d.halo_exchange/heat3d.halo.x/ppermute.3")
        == "heat3d.halo.x"
    )
    assert mod.phase_name("heat3d.stencil/fusion.2") == "heat3d.stencil"


def test_summarize_trace_synthetic_xspace_proto(tmp_path, capsys):
    """Satellite, real-proto tier (skips when xplane_pb2 is absent — the
    duck-typed tests above cover the logic either way): a synthetic
    XSpace with an op line AND a same-wall-time module line summarizes to
    the op line's total only — the double-count fix."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    )
    mod = _load_summarize_trace()
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = "heat3d.stencil/fusion.1"
    plane.event_metadata[2].id = 2
    plane.event_metadata[2].name = "whole-module"
    ops = plane.lines.add()
    ops.name = "XLA Ops"
    ev = ops.events.add()
    ev.metadata_id = 1
    ev.duration_ps = int(7e6)  # 7 us
    mods = plane.lines.add()
    mods.name = "XLA Modules"
    ev2 = mods.events.add()
    ev2.metadata_id = 2
    ev2.duration_ps = int(7e6)  # same wall time, coarser granularity
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xs.SerializeToString())

    assert mod.summarize(str(path)) == 0
    out = capsys.readouterr().out
    assert "[line: XLA Ops]" in out
    assert "total 0.01 ms" in out  # 7 us, once — not 14 (double-counted)
    assert "heat3d.stencil" in out


def test_summarize_trace_plane_renders(capsys):
    mod = _load_summarize_trace()
    meta = {1: SimpleNamespace(name="heat3d.residual/reduce.1")}
    plane = SimpleNamespace(
        name="/device:TPU:0",
        lines=[SimpleNamespace(name="XLA Ops", events=[_ev(1, 5e6)])],
        event_metadata=meta,
    )
    mod.summarize_plane(plane)
    out = capsys.readouterr().out
    assert "heat3d.residual" in out and "by heat3d phase" in out


# ---- the acceptance criterion ------------------------------------------


def test_e2e_supervised_fault_ledger_reconstruction(tmp_path, monkeypatch):
    """End-to-end CPU acceptance: a supervised run with an injected
    backend loss (HEAT3D_FAULTS) produces a schema-valid ledger holding
    step spans, the fault event, retry attempts, the generation
    transitions, and checkpoint write/verify records — and `heat3d obs
    summary`'s reconstructed step-latency p50/p95 agree with the run's
    own metrics-registry numbers within 20%."""
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.obs.cli import read_ledger, step_latencies
    from heat3d_tpu.resilience.faults import FaultPlan
    from heat3d_tpu.resilience.retry import RetryPolicy
    from heat3d_tpu.resilience.supervisor import run_supervised

    monkeypatch.setenv("HEAT3D_FAULTS", "backend-loss:step=8:down=2")
    monkeypatch.delenv("HEAT3D_FAULT_STATE", raising=False)
    p = str(tmp_path / "led.jsonl")
    obs.activate(p, meta={"entry": "e2e"})
    obs.REGISTRY.reset()
    fast = RetryPolicy(
        base_delay_s=0.01, multiplier=1.5, max_delay_s=0.05, deadline_s=5.0
    )
    solver = HeatSolver3D(SolverConfig(grid=GridConfig.cube(8), backend="jnp"))
    res = run_supervised(
        solver, 12, str(tmp_path / "ck"), checkpoint_every=4,
        heal_policy=fast, probe=lambda: "cpu",
        faults=FaultPlan.from_env(),
    )
    metrics = obs.REGISTRY.snapshot()
    obs.get().event("metrics_summary", metrics=metrics)
    obs.deactivate(rc=0)

    assert res.steps_done == 12 and len(res.recoveries) == 1

    # 1. schema-valid
    assert ledger_check.check_file(p) == [], ledger_check.check_file(p)

    evs = read_ledger(p)
    names = [e["event"] for e in evs]
    # 2. step spans (the supervised chunks), including the one the fault
    # killed (status=error)
    chunks = [e for e in evs if e["event"] == "chunk"]
    assert len(chunks) == 4  # 0-4, 4-8, 8-FAIL, 8-12(rewound), 8-12... 3 ok
    assert [c["status"] for c in chunks].count("error") == 1
    # 3. the fault event, 4. retry attempts, 5. generation transitions,
    # 6. checkpoint writes + verified loads
    fault = next(e for e in evs if e["event"] == "fault_injected")
    assert fault["kind_"] == "backend-loss" and fault["step"] == 8
    retries = [e for e in evs if e["event"] == "retry_attempt"]
    assert len(retries) >= 3  # 2 injected down-probes + the heal
    gens = [e for e in evs if e["event"] == "generation_save"]
    assert [g["step"] for g in gens] == [4, 8, 12]
    assert "ckpt_save" in names and "ckpt_load" in names
    assert (
        metrics["heat3d_ckpt_verify_total"]["values"]['{result="ok"}'] >= 1
    )
    recovery = next(e for e in evs if e["event"] == "recovery")
    assert recovery["resumed_from"] == 8
    # events after the resume carry the generation context tag
    post = [e for e in evs if e.get("generation") == 8]
    assert any(e["event"] == "generation_save" and e["step"] == 12
               for e in post)

    # 7. obs-summary reconstruction vs the run's own numbers: identical
    # inputs (ok-chunk dur/steps), so well within the 20% criterion
    lats = step_latencies(evs)
    assert len(lats) == 3
    from heat3d_tpu.obs.metrics import percentile

    run_stats = metrics["heat3d_step_latency_seconds"]["values"][""]
    for q, key in ((50, "p50"), (95, "p95")):
        rebuilt = percentile(lats, q)
        own = run_stats[key]
        assert abs(rebuilt - own) <= 0.2 * own, (q, rebuilt, own)

    # ... and the obs CLI renders it without error
    from heat3d_tpu.obs.cli import main as obs_main

    assert obs_main(["summary", p]) == 0
    assert obs_main(["check", p]) == 0


def test_cli_run_writes_ledger_and_metrics_export(tmp_path, monkeypatch):
    """The solver CLI entry point: --ledger produces a lint-clean ledger
    with run_start/run_loop/run_summary/metrics_summary, and
    HEAT3D_METRICS exports a snapshot file at exit."""
    from heat3d_tpu.cli import main as heat3d_main

    p = str(tmp_path / "led.jsonl")
    prom = str(tmp_path / "m.prom")
    monkeypatch.setenv("HEAT3D_METRICS", prom)
    rc = heat3d_main([
        "--grid", "8", "--steps", "4", "--backend", "jnp", "--ledger", p,
    ])
    assert rc == 0
    assert ledger_check.check_file(p) == []
    evs = _read(p)
    names = [e["event"] for e in evs]
    for want in ("ledger_open", "run_start", "warmup", "run_loop",
                 "run_summary", "metrics_summary", "ledger_close"):
        assert want in names, (want, names)
    loop = next(e for e in evs if e["event"] == "run_loop")
    assert loop["steps"] == 4 and loop["status"] == "ok"
    summary = next(e for e in evs if e["event"] == "run_summary")
    assert summary["steps"] == 4 and "gcell_updates_per_sec" in summary
    close = next(e for e in evs if e["event"] == "ledger_close")
    assert close["rc"] == 0
    text = open(prom).read()
    assert "heat3d_step_latency_seconds" in text


def test_summarize_trace_promotion_wrapper_reexports():
    """ISSUE 8 satellite: the script is now a thin wrapper over the
    promoted core in heat3d_tpu/obs/perf/timeline.py — same helpers,
    same objects (the duck-typed tests above exercise them THROUGH the
    wrapper, so the promotion cannot drift silently)."""
    mod = _load_summarize_trace()
    from heat3d_tpu.obs.perf import timeline

    for name in ("pick_line", "aggregate_line", "phase_name",
                 "phase_totals", "summarize", "summarize_plane",
                 "find_xplane", "PHASE_RE"):
        assert getattr(mod, name) is getattr(timeline, name)
    assert mod.main is timeline.summarize_trace_main
