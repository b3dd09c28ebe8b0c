"""Unified performance timeline: one normalized event model over the run
ledger, ``obs merge``'d multihost streams, and ``--profile`` captures.

The paper's whole premise is overlap — compute hiding communication — yet
until this module no single command could answer "what fraction of a real
step was stencil vs halo vs stall". Three consumers share the model:

- **Chrome-trace export** (``heat3d obs timeline LEDGER -o trace.json``):
  ledger spans become nested ``X`` slices per process stream (multihost
  ledgers keep their ``src`` tags as separate process tracks), point
  events become instants, and a profile capture's per-phase device totals
  ride along as an aggregate track — one file, openable in Perfetto
  (ui.perfetto.dev) or ``chrome://tracing``.
- **Profile→roofline join**: :func:`profile_phase_totals` turns a
  ``--profile`` capture into measured device microseconds per ``heat3d.*``
  phase (the named-scope names ``parallel.step.PHASES`` pins), which
  ``obs roofline --from-profile`` divides cost-analysis FLOPs/bytes by —
  achieved-vs-peak from *measured device time*, not span wall-clock.
- **Drift/straggler detection** (:func:`detect_anomalies`): rolling
  per-span baselines over the per-step latency samples, classified with
  the same tolerance bands as ``obs regress`` (``band_status``), plus a
  cross-stream straggler check on merged multihost ledgers. Findings
  surface in ``obs summary``, in ``obs timeline --anomalies``, and as
  ``obs_anomaly`` ledger events.

The xplane-parsing core here is promoted from
``scripts/summarize_trace.py`` (now a thin same-flags wrapper), matching
the roofline/ab_decide promotion pattern: the aggregation stays pure and
duck-typed (``pick_line`` / ``aggregate_line`` / ``phase_totals``) so
tests drive it with synthetic plane objects when the ``xplane_pb2`` proto
module is absent.

Wall-time normalization: ledger spans carry ``t0``/``t1`` (per-process
monotonic) and ``ts`` (wall clock at write — spans are written AT CLOSE),
so a span's wall start is ``ts - dur_s`` without any cross-stream clock
fitting; cross-host placement inherits whatever wall-clock skew ``obs
merge`` already quantifies rather than pretending to correct it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

# ---- xplane parsing (promoted from scripts/summarize_trace.py) -------------

# innermost heat3d phase token in an op/metadata name: named_scope nests
# (heat3d.stencil/heat3d.halo_exchange/...), and the INNERMOST scope is
# the phase that op belongs to — findall + [-1] picks it. The (?!py\b)
# lookahead keeps host-plane PYTHON FRAMES ("$heat3d.py:301 run") from
# masquerading as a phase named "heat3d.py". Dotted sub-phases
# ("heat3d.halo.x") are one token: the continuation admits further
# components unless they open with a digit (XLA's ".N" op suffixes, as in
# "fusion.2", are not phase path components).
PHASE_RE = re.compile(
    r"heat3d\.(?!py\b)[A-Za-z_][A-Za-z0-9_]*"
    r"(?:\.(?!py\b)[A-Za-z_][A-Za-z0-9_]*)*"
)


def find_xplane(logdir: str):
    pats = os.path.join(logdir, "**", "*.xplane.pb")
    files = sorted(glob.glob(pats, recursive=True))
    return files[-1] if files else None


def pick_line(lines):
    """The ONE line to aggregate per plane. A device plane carries several
    lines covering the SAME wall time (XLA Modules / XLA Ops / Steps);
    summing across them would double-count. Pick the op-level line if
    present, else the busiest line. ``lines`` must be pre-filtered to
    non-empty (``ln.events``)."""

    def line_us(line):
        return sum(ev.duration_ps for ev in line.events) / 1e6

    ops = [ln for ln in lines if "op" in ln.name.lower()]
    return ops[0] if ops else max(lines, key=line_us)


def aggregate_line(line, event_metadata):
    """(totals_us, counts) per metadata name for one line's events.
    ``event_metadata`` is the plane's metadata_id -> metadata mapping
    (proto map or plain dict of objects with ``.name``)."""
    totals = defaultdict(float)
    counts = defaultdict(int)
    for ev in line.events:
        meta = event_metadata[ev.metadata_id]
        totals[meta.name] += ev.duration_ps / 1e6
        counts[meta.name] += 1
    return totals, counts


def phase_name(op_name: str):
    """The heat3d phase an op belongs to (its innermost ``heat3d.*`` scope
    token), or None for ops outside any named phase."""
    hits = PHASE_RE.findall(op_name)
    return hits[-1] if hits else None


def phase_totals(totals):
    """Group per-op totals by heat3d phase; unscoped time lands in
    ``(unattributed)``."""
    phases = defaultdict(float)
    for name, us in totals.items():
        phases[phase_name(name) or "(unattributed)"] += us
    return dict(phases)


def summarize_plane(plane, top: int = 25, out=None) -> None:
    out = out or sys.stdout
    lines = [ln for ln in plane.lines if ln.events]
    if not lines:
        return
    line = pick_line(lines)
    totals, counts = aggregate_line(line, plane.event_metadata)
    print(
        f"\n== {plane.name} [line: {line.name or '?'}] "
        f"(total {sum(totals.values())/1e3:.2f} ms)",
        file=out,
    )
    for name, us in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {us/1e3:9.3f} ms  x{counts[name]:<6} {name[:90]}", file=out)
    phases = phase_totals(totals)
    # a phase table with ONLY unattributed time is noise (a trace captured
    # without the named scopes); print it when any phase resolved
    if set(phases) - {"(unattributed)"}:
        total_us = sum(phases.values()) or 1.0
        print("  -- by heat3d phase --", file=out)
        for name, us in sorted(phases.items(), key=lambda kv: -kv[1]):
            print(
                f"  {us/1e3:9.3f} ms  {100.0 * us / total_us:5.1f}%  {name}",
                file=out,
            )


def _load_xspace(path: str):
    """Parse one ``.xplane.pb`` file; raises RuntimeError when the proto
    module is unavailable (callers decide whether that is fatal — the
    summarize CLI degrades to a TensorBoard pointer, the roofline join
    cannot run without it)."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # type: ignore
    except ImportError as e:
        raise RuntimeError(
            "xplane_pb2 unavailable — cannot parse the profile capture "
            f"({e}); open the trace in TensorBoard instead "
            f"(tensorboard --logdir {os.path.dirname(path)})"
        ) from None
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _device_planes(xs):
    """``(planes, host_fallback)``: the planes whose time is DEVICE time,
    or — when the capture has none (CPU-only runs) — every plane with
    lines, flagged as a host fallback so callers can treat its lines
    more skeptically (ONE selection rule for the summarize display and
    the roofline join)."""
    planes = [
        p
        for p in xs.planes
        if "TPU" in p.name or "/device" in p.name.lower()
    ]
    if planes:
        return planes, False
    return [p for p in xs.planes if p.lines], True


def summarize(path: str) -> int:
    try:
        xs = _load_xspace(path)
    except RuntimeError as e:
        # soft fallback: the capture itself succeeded, so don't fail the
        # calling script — just point at the trace
        print(e)
        return 0
    planes, _ = _device_planes(xs)
    for plane in planes:
        summarize_plane(plane)
    return 0


def summarize_trace_main(argv: Optional[List[str]] = None) -> int:
    """The historical ``scripts/summarize_trace.py`` surface, unchanged:
    one positional trace path (file or capture dir)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(
            "usage: summarize_trace.py TRACE_DIR_OR_XPLANE_PB — per-op and "
            "per-phase device time of a jax.profiler capture "
            "(heat3d_tpu/obs/perf/timeline.py)",
            file=sys.stderr,
        )
        return 2
    path = argv[0]
    if os.path.isdir(path):
        xp = find_xplane(path)
        if xp is None:
            print(f"no .xplane.pb under {path}")
            return 1
        path = xp
    print(f"trace: {path}")
    return summarize(path)


# ---- per-phase device totals (the roofline join's measured side) -----------


def normalize_phase(token: str) -> str:
    """Canonical phase key for a ``heat3d.*`` scope token: the prefix is
    stripped and the halo sub-scopes — per-axis (``halo.x``), the comm
    observatory's per-direction (``halo.x.lo``), per-sub-block
    (``halo.x.lo.p0``) and per-axis DMA (``halo.x.dma``) scopes — all
    fold into ``halo_exchange``, so the roofline/timeline joins keep
    attributing the finer-grained exchange scopes to the one exchange
    phase instead of ``(unattributed)``. A named Pallas kernel's op
    (``heat3d.stencil.<family>``) folds into ``stencil`` the same way.
    The names then join ``parallel.step.phase_programs`` / the ledger
    spans on one key."""
    if token.startswith("heat3d."):
        token = token[len("heat3d."):]
    if token == "halo" or token.startswith("halo."):
        return "halo_exchange"
    if token.startswith("stencil."):
        return "stencil"
    return token


def device_phase_totals(xs) -> Dict[str, float]:
    """Measured device microseconds per normalized phase, summed over the
    device planes of an XSpace-like object (duck-typed: tests drive it
    with synthetic planes). Unscoped device time lands in
    ``(unattributed)`` — the honest bucket for dispatch gaps and ops the
    named scopes don't cover.

    Host-plane-only captures (real CPU runs) contribute ONLY their
    op-level lines: the ``python`` frames line sums wall time across
    every host thread, which would fabricate "device" totals several
    times the run's wall clock — better an honest "no device events"
    than a confident wrong table."""
    planes, host_fallback = _device_planes(xs)
    out: Dict[str, float] = defaultdict(float)
    for plane in planes:
        lines = [ln for ln in plane.lines if ln.events]
        if host_fallback:
            lines = [ln for ln in lines if "op" in ln.name.lower()]
        if not lines:
            continue
        totals, _ = aggregate_line(pick_line(lines), plane.event_metadata)
        for phase, us in phase_totals(totals).items():
            key = (
                "(unattributed)"
                if phase == "(unattributed)"
                else normalize_phase(phase)
            )
            out[key] += us
    return dict(out)


def profile_phase_totals(path: str) -> Tuple[Dict[str, float], str]:
    """``(phase -> device us, artifact path)`` for a profile capture
    (``--profile DIR`` output, or one ``.xplane.pb`` directly). Raises
    RuntimeError when there is no artifact or no proto parser — the join
    consumers report that instead of printing an empty table."""
    artifact = path
    if os.path.isdir(path):
        artifact = find_xplane(path)
        if artifact is None:
            raise RuntimeError(f"no .xplane.pb under {path}")
    totals = device_phase_totals(_load_xspace(artifact))
    if not totals:
        raise RuntimeError(f"no device events in {artifact}")
    return totals, artifact


# ---- ledger -> normalized timeline ----------------------------------------


def timeline_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The normalized event model: one record per ledger event with a
    wall-clock placement. Spans are written at close, so wall start is
    ``ts - dur_s``; points sit at ``ts``. Events without a numeric ``ts``
    are dropped (the ledger lint flags them; the timeline stays
    best-effort). ``src`` survives from ``obs merge``'d streams."""
    out: List[Dict[str, Any]] = []
    for r in events:
        ts = r.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        rec = {
            "name": str(r.get("event", "?")),
            "kind": "span" if r.get("kind") == "span" else "point",
            "src": str(r.get("src", "")),
            "proc": r.get("proc", 0),
            "run_id": str(r.get("run_id", "")),
            "depth": r.get("depth", 0),
        }
        if rec["kind"] == "span" and isinstance(
            r.get("dur_s"), (int, float)
        ):
            rec["t_wall"] = float(ts) - float(r["dur_s"])
            rec["dur_s"] = float(r["dur_s"])
        else:
            # spans missing dur_s degrade to instants — best-effort, like
            # every other ledger reader
            rec["t_wall"] = float(ts)
            rec["dur_s"] = None
        rec["args"] = {
            k: v
            for k, v in r.items()
            if k
            not in (
                "ts", "run_id", "proc", "seq", "event", "kind",
                "t0", "t1", "dur_s", "depth", "src",
            )
        }
        out.append(rec)
    out.sort(key=lambda e: e["t_wall"])
    return out


def to_chrome_trace(
    tl_events: List[Dict[str, Any]],
    profile_totals: Optional[Dict[str, float]] = None,
) -> Dict[str, Any]:
    """Chrome-trace/Perfetto JSON (the legacy ``traceEvents`` format) from
    normalized timeline events. One integer pid per (src, proc) stream
    (named via ``M`` metadata events), spans as ``X`` complete events
    (nesting renders from time containment — the ledger guarantees proper
    per-thread nesting), points as ``i`` instants. A profile capture's
    per-phase totals export as ONE aggregate track: each phase is a slice
    whose duration is its total device time — honest about being an
    aggregate, not a placement (per-op placement lives in the xplane
    itself, which Perfetto opens natively). ``serve_span`` request-trace
    events (points carrying wall-clock ``t0_wall``/``t1_wall`` — see
    serve/queue.new_trace) additionally render as a per-request
    waterfall: one dedicated pid, one tid per request, the queue / pack /
    compute / deliver phases (and requeue gaps) as slices under the
    request's root span."""
    trace: List[Dict[str, Any]] = []
    req_spans = [
        e
        for e in tl_events
        if e["name"] == "serve_span"
        and isinstance(e["args"].get("t0_wall"), (int, float))
        and isinstance(e["args"].get("t1_wall"), (int, float))
    ]
    if tl_events:
        base = min(e["t_wall"] for e in tl_events)
        if req_spans:
            # a request's queue phase starts at submit — earlier than any
            # serve_span EMISSION ts; the origin must cover it
            base = min(
                base, min(e["args"]["t0_wall"] for e in req_spans)
            )
    else:
        base = 0.0
    pids: Dict[Tuple[str, Any], int] = {}
    req_ids = {id(e) for e in req_spans}
    for e in tl_events:
        if id(e) in req_ids:
            continue  # rendered on the waterfall track below, not as instants
        stream = (e["src"], e["proc"])
        if stream not in pids:
            pid = len(pids) + 1
            pids[stream] = pid
            label = e["src"] or "ledger"
            trace.append(
                {
                    "name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"{label}/proc{e['proc']}"},
                }
            )
        pid = pids[stream]
        ts_us = round((e["t_wall"] - base) * 1e6, 3)
        if e["dur_s"] is not None:
            trace.append(
                {
                    "name": e["name"], "ph": "X", "pid": pid, "tid": 0,
                    "ts": ts_us, "dur": round(e["dur_s"] * 1e6, 3),
                    "args": e["args"],
                }
            )
        else:
            trace.append(
                {
                    "name": e["name"], "ph": "i", "s": "p", "pid": pid,
                    "tid": 0, "ts": ts_us, "args": e["args"],
                }
            )
    if req_spans:
        # the per-request waterfall: one tid per request, phases as X
        # slices at their wall-clock bounds (the root "request" span
        # contains its phases by time, so Perfetto nests them)
        pid = len(pids) + 1
        trace.append(
            {
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "requests (serve traces)"},
            }
        )
        tids: Dict[Any, int] = {}
        for e in req_spans:
            a = e["args"]
            rid = a.get("request_id")
            if rid not in tids:
                tids[rid] = len(tids)
                trace.append(
                    {
                        "name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tids[rid],
                        "args": {
                            "name": f"req {rid} [{a.get('trace_id')}]"
                        },
                    }
                )
            t0w, t1w = float(a["t0_wall"]), float(a["t1_wall"])
            trace.append(
                {
                    "name": str(a.get("span", "?")), "ph": "X",
                    "pid": pid, "tid": tids[rid],
                    "ts": round((t0w - base) * 1e6, 3),
                    "dur": round(max(t1w - t0w, 0.0) * 1e6, 3),
                    "args": {
                        k: v
                        for k, v in a.items()
                        if k not in ("t0_wall", "t1_wall") and v is not None
                    },
                }
            )
    if profile_totals:
        pid = len(pids) + 2 if req_spans else len(pids) + 1
        trace.append(
            {
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "device profile (per-phase aggregate)"},
            }
        )
        for tid, (phase, us) in enumerate(
            sorted(profile_totals.items(), key=lambda kv: -kv[1])
        ):
            trace.append(
                {
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": phase},
                }
            )
            trace.append(
                {
                    "name": phase, "ph": "X", "pid": pid, "tid": tid,
                    "ts": 0.0, "dur": round(us, 3),
                    "args": {"aggregate_device_us": round(us, 3)},
                }
            )
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


# ---- drift / straggler detection ------------------------------------------

# how many leading samples seed a rolling baseline before judging starts
BASELINE_SAMPLES = 4


def _span_samples(
    events: List[Dict[str, Any]],
) -> Dict[Tuple[str, Any, str, str], List[float]]:
    """Ordered latency samples per (src, proc, run_id, span name): for
    the step spans (``obs.cli.STEP_SPANS``) the sample is per-step
    latency (dur_s / steps — the same rule ``obs summary`` reconstructs
    with); for every other ok span it is the raw duration. run_id is in
    the key because one ledger file holds MANY run segments (APPEND
    bench sessions, the suite ledger) with legitimately different step
    times — a baseline must never cross a run boundary, or every
    config change reads as drift."""
    from heat3d_tpu.obs.cli import STEP_SPANS

    out: Dict[Tuple[str, Any, str, str], List[float]] = defaultdict(list)
    for r in events:
        if r.get("kind") != "span" or r.get("status") != "ok":
            continue
        dur = r.get("dur_s")
        if not isinstance(dur, (int, float)):
            continue
        name = str(r.get("event"))
        key = (
            str(r.get("src", "")), r.get("proc", 0),
            str(r.get("run_id", "")), name,
        )
        if name in STEP_SPANS:
            steps = r.get("steps")
            if isinstance(steps, int) and steps > 0:
                out[key].append(float(dur) / steps)
        else:
            out[key].append(float(dur))
    return out


def detect_anomalies(
    events: List[Dict[str, Any]],
    warn_pct: Optional[float] = None,
    fail_pct: Optional[float] = None,
    baseline: int = BASELINE_SAMPLES,
) -> List[Dict[str, Any]]:
    """Step-time drift and host stragglers from a (possibly merged)
    ledger, classified with the SAME tolerance bands as ``obs regress``
    (latency regresses upward; default warn >8% / fail >15%).

    - **Drift** (``kind_: span_drift``): per (src, proc, run, span-name)
      stream — the run_id in the key keeps a baseline from crossing run
      boundaries, so an APPEND-session ledger of differently-configured
      runs doesn't read as drift — a rolling baseline (the p50 of the
      last ``baseline`` ACCEPTED samples; flagged samples don't poison
      it, so a sustained slowdown keeps firing instead of absorbing into
      the baseline) judges every sample after the seed window.
    - **Straggler** (``kind_: host_straggler``): with two or more
      distinct (src, proc) HOST identities carrying step samples (an
      ``obs merge``'d pod ledger, or multi-proc), each host's per-step
      p50 is judged against the fleet p50. Sequential runs in a
      single-host ledger are ONE identity — never compared against each
      other. DURATION-based, so it is immune to wall-clock skew.
    - **Late starter** (``kind_: start_straggler``): cross-host
      comparison of step-span WALL STARTS (``ts - dur_s``), matched by
      per-host sample index and judged as a fraction of the fleet's
      step-span p50. This one READS WALL CLOCKS, so it is exactly as
      trustworthy as the clocks are aligned: on raw merged ledgers a
      skewed host clock masquerades as a late starter, and ``obs merge
      --align`` / ``obs timeline --align`` is the documented cure (the
      tests pin both directions). Only LATE hosts flag — a fast clock
      reads as early, which is not a straggler.
    - **Slow link** (``kind_: link_straggler``): per-(axis, direction)
      ``comm_probe`` samples (the ``HEAT3D_COMM_PROBE`` probe — sub-block
      rows fold into their parent link) compared across hosts: each
      host's per-link p50 is judged against the fleet p50 for the SAME
      link, naming the slow link rather than just the slow host.
      DURATION-based like host_straggler, so skew-immune.

    All percentiles use ``obs.metrics.percentile`` (nearest-rank) — the
    one rule every obs reconstruction shares. Returns records ready to
    print (``format_anomaly``) or to emit as ``obs_anomaly`` ledger
    events (``emit_anomalies``); ``fail`` records sort first."""
    from heat3d_tpu.obs.cli import STEP_SPANS
    from heat3d_tpu.obs.metrics import percentile
    from heat3d_tpu.obs.perf.regress import (
        DEFAULT_FAIL_PCT,
        DEFAULT_WARN_PCT,
        band_status,
    )

    warn_pct = DEFAULT_WARN_PCT if warn_pct is None else warn_pct
    fail_pct = DEFAULT_FAIL_PCT if fail_pct is None else fail_pct
    anomalies: List[Dict[str, Any]] = []
    samples = _span_samples(events)

    for (src, proc, run_id, name), vals in sorted(samples.items()):
        if len(vals) <= baseline:
            continue
        accepted = list(vals[:baseline])
        for i, v in enumerate(vals[baseline:], start=baseline):
            base = percentile(accepted[-baseline:], 50)
            if base <= 0:
                accepted.append(v)
                continue
            delta = (v - base) / base * 100.0
            status = band_status(delta, warn_pct, fail_pct)
            if status == "pass":
                accepted.append(v)
                continue
            anomalies.append(
                {
                    "kind_": "span_drift",
                    "span": name,
                    "src": src,
                    "proc": proc,
                    "run_id_": run_id,
                    "sample": i,
                    "value_s": round(v, 9),
                    "baseline_s": round(base, 9),
                    "delta_pct": round(delta, 2),
                    "status": status,
                    "per_step": name in STEP_SPANS,
                }
            )

    # straggler: cross-HOST comparison of per-step p50s (runs merged per
    # host — every host mixes the same session's runs, so the comparison
    # stays apples-to-apples)
    step_streams: Dict[Tuple[str, Any], List[float]] = defaultdict(list)
    for (src, proc, run_id, name), vals in samples.items():
        if name in STEP_SPANS and vals:
            step_streams[(src, proc)].extend(vals)
    if len(step_streams) > 1:
        p50s = {
            k: percentile(v, 50) for k, v in sorted(step_streams.items())
        }
        fleet = percentile(list(p50s.values()), 50)
        if fleet > 0:
            for (src, proc), p50 in p50s.items():
                delta = (p50 - fleet) / fleet * 100.0
                status = band_status(delta, warn_pct, fail_pct)
                if status != "pass":
                    anomalies.append(
                        {
                            "kind_": "host_straggler",
                            "src": src,
                            "proc": proc,
                            "p50_s": round(p50, 9),
                            "fleet_p50_s": round(fleet, 9),
                            "delta_pct": round(delta, 2),
                            "status": status,
                        }
                    )
    # late starter: cross-host comparison of step-span WALL STARTS
    # (ts - dur_s), index-matched so step i is compared against the
    # fleet's step i. Judged as a fraction of the fleet step-span p50;
    # wall-clock-based by construction (see docstring) — feed it aligned
    # time (obs merge --align) on multihost ledgers.
    start_streams: Dict[Tuple[str, Any], List[float]] = defaultdict(list)
    span_durs: List[float] = []
    for r in events:
        if r.get("kind") != "span" or r.get("status") != "ok":
            continue
        if str(r.get("event")) not in STEP_SPANS:
            continue
        ts, dur = r.get("ts"), r.get("dur_s")
        if isinstance(ts, (int, float)) and isinstance(dur, (int, float)):
            start_streams[(str(r.get("src", "")), r.get("proc", 0))].append(
                float(ts) - float(dur)
            )
            span_durs.append(float(dur))
    if len(start_streams) > 1 and span_durs:
        fleet_dur = percentile(span_durs, 50)
        n = min(len(v) for v in start_streams.values())
        if fleet_dur > 0 and n > 0:
            hosts = sorted(start_streams)
            med = [
                percentile([start_streams[h][i] for h in hosts], 50)
                for i in range(n)
            ]
            for h in hosts:
                offs = [start_streams[h][i] - med[i] for i in range(n)]
                off = percentile(offs, 50)
                delta = off / fleet_dur * 100.0
                status = band_status(delta, warn_pct, fail_pct)
                if status != "pass":
                    anomalies.append(
                        {
                            "kind_": "start_straggler",
                            "src": h[0],
                            "proc": h[1],
                            "offset_s": round(off, 9),
                            "fleet_span_p50_s": round(fleet_dur, 9),
                            "delta_pct": round(delta, 2),
                            "status": status,
                        }
                    )

    # slow link: per-(axis, direction) comm_probe samples compared
    # across hosts — the link, not just the host, gets named. Sub-block
    # rows fold into their parent link (one attribution unit).
    by_link: Dict[
        Tuple[str, str], Dict[Tuple[str, Any], List[float]]
    ] = defaultdict(lambda: defaultdict(list))
    for r in events:
        if r.get("event") != "comm_probe":
            continue
        t, ax, dr = r.get("t_s"), r.get("axis_name"), r.get("direction")
        if (
            isinstance(t, (int, float))
            and t > 0
            and isinstance(ax, str)
            and dr in ("lo", "hi")
        ):
            by_link[(ax, str(dr))][
                (str(r.get("src", "")), r.get("proc", 0))
            ].append(float(t))
    for (ax, dr), hosts_d in sorted(by_link.items()):
        if len(hosts_d) < 2:
            continue  # a link seen by one host has no fleet to lag
        p50s = {h: percentile(v, 50) for h, v in sorted(hosts_d.items())}
        fleet = percentile(list(p50s.values()), 50)
        if fleet <= 0:
            continue
        for (src, proc), p50 in p50s.items():
            delta = (p50 - fleet) / fleet * 100.0
            status = band_status(delta, warn_pct, fail_pct)
            if status != "pass":
                anomalies.append(
                    {
                        "kind_": "link_straggler",
                        "src": src,
                        "proc": proc,
                        "axis": ax,
                        "direction": dr,
                        "p50_s": round(p50, 9),
                        "fleet_p50_s": round(fleet, 9),
                        "delta_pct": round(delta, 2),
                        "status": status,
                    }
                )

    anomalies.sort(key=lambda a: (a["status"] != "fail", -a["delta_pct"]))
    return anomalies


def format_anomaly(a: Dict[str, Any]) -> str:
    tag = {"fail": "ANOMALY", "warn": "drift?"}.get(a["status"], a["status"])
    who = f"{a['src'] + '/' if a.get('src') else ''}proc{a.get('proc', 0)}"
    if a.get("kind_") == "host_straggler":
        return (
            f"{tag} straggler {who}: step p50 {a['p50_s'] * 1e3:.3f}ms vs "
            f"fleet {a['fleet_p50_s'] * 1e3:.3f}ms ({a['delta_pct']:+.1f}%)"
        )
    if a.get("kind_") == "start_straggler":
        return (
            f"{tag} late starter {who}: steps begin "
            f"{a['offset_s'] * 1e3:+.3f}ms vs fleet "
            f"({a['delta_pct']:+.1f}% of a step span; wall-clock-based — "
            "align merged ledgers first)"
        )
    if a.get("kind_") == "link_straggler":
        return (
            f"{tag} slow link {a.get('axis')}.{a.get('direction')} {who}: "
            f"p50 {a['p50_s'] * 1e6:.1f}us vs fleet "
            f"{a['fleet_p50_s'] * 1e6:.1f}us ({a['delta_pct']:+.1f}%)"
        )
    unit = "/step" if a.get("per_step") else ""
    return (
        f"{tag} {a.get('span')} {who} sample {a.get('sample')}: "
        f"{a['value_s'] * 1e3:.3f}ms{unit} vs baseline "
        f"{a['baseline_s'] * 1e3:.3f}ms ({a['delta_pct']:+.1f}%)"
    )


def emit_anomalies(anomalies: List[Dict[str, Any]]) -> None:
    """Append each anomaly as an ``obs_anomaly`` ledger event (a no-op
    without an active ledger — detection is read-side, the events are for
    pipelines that run the detector right after the run they observed)."""
    from heat3d_tpu import obs

    for a in anomalies:
        obs.get().event("obs_anomaly", **a)


# ---- CLI -------------------------------------------------------------------


def _read_streams(
    paths: List[str], align: bool = False
) -> List[Dict[str, Any]]:
    """One ledger reads directly; several merge through
    ``obs.perf.merge.merge_ledgers`` so each keeps its ``src`` tag (the
    straggler detector and the per-stream tracks key on it).
    ``align=True`` merges onto the anchor-aligned clock (obs merge
    --align) so the wall-clock-based detectors judge estimated true
    time; it is meaningless (and ignored) for a single ledger."""
    if len(paths) == 1:
        from heat3d_tpu.obs.cli import read_ledger

        return read_ledger(paths[0])
    from heat3d_tpu.obs.perf.merge import merge_ledgers

    return merge_ledgers(paths, align=align)["events"]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="heat3d obs timeline",
        description="unified performance timeline: normalize a run "
        "ledger (or several multihost ledgers) plus an optional "
        "--profile capture into one event model; export Chrome-trace/"
        "Perfetto JSON and detect step-time drift / host stragglers",
    )
    ap.add_argument("ledgers", nargs="+", help="run ledger file(s); "
                    "several are src-tagged and merged (obs merge)")
    ap.add_argument("-o", "--out", default=None, metavar="TRACE.json",
                    help="write the Chrome-trace JSON here (open in "
                    "Perfetto: ui.perfetto.dev)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="profile capture dir (or .xplane.pb): adds the "
                    "per-phase device-time aggregate track and the phase "
                    "table")
    ap.add_argument("--align", action="store_true",
                    help="merge multiple ledgers onto the anchor-aligned "
                    "clock (obs merge --align) before detection, so a "
                    "skewed host clock cannot masquerade as a late "
                    "starter")
    ap.add_argument("--anomalies", action="store_true",
                    help="also emit obs_anomaly ledger events for every "
                    "detected drift/straggler (detection itself always "
                    "runs)")
    ap.add_argument("--warn-pct", type=float, default=None,
                    help="drift warn band (default: obs regress's 8)")
    ap.add_argument("--fail-pct", type=float, default=None,
                    help="drift fail band (default: obs regress's 15)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report instead of the text "
                    "summary")
    args = ap.parse_args(argv)

    try:
        events = _read_streams(args.ledgers, align=args.align)
    except OSError as e:
        print(f"timeline: cannot read ledger: {e}", file=sys.stderr)
        return 2
    if not events:
        print(f"timeline: no events in {' '.join(args.ledgers)}",
              file=sys.stderr)
        return 1

    tl = timeline_events(events)
    profile_totals: Optional[Dict[str, float]] = None
    profile_note = None
    if args.profile:
        try:
            profile_totals, artifact = profile_phase_totals(args.profile)
            profile_note = artifact
        except (RuntimeError, OSError) as e:
            # the ledger timeline is still worth exporting without the
            # device track — degrade with a note, like every obs reader
            print(f"timeline: profile ignored ({e})", file=sys.stderr)

    anomalies = detect_anomalies(
        events, warn_pct=args.warn_pct, fail_pct=args.fail_pct
    )
    if args.anomalies and anomalies:
        emit_anomalies(anomalies)

    out_path = None
    if args.out:
        doc = to_chrome_trace(tl, profile_totals)
        with open(args.out, "w") as f:
            json.dump(doc, f)
        out_path = args.out
        from heat3d_tpu import obs

        obs.get().event(
            "timeline_export",
            path=os.path.abspath(args.out),
            events=len(doc["traceEvents"]),
            streams=len({(e["src"], e["proc"]) for e in tl}),
            anomalies=len(anomalies),
        )

    spans = sum(1 for e in tl if e["dur_s"] is not None)
    if args.json:
        print(
            json.dumps(
                {
                    "events": len(tl),
                    "spans": spans,
                    "streams": len({(e["src"], e["proc"]) for e in tl}),
                    "out": out_path,
                    "profile": profile_note,
                    "phase_device_us": profile_totals,
                    "anomalies": anomalies,
                }
            )
        )
        return 0
    streams = len({(e["src"], e["proc"]) for e in tl})
    wall = tl[-1]["t_wall"] - tl[0]["t_wall"] if len(tl) > 1 else 0.0
    print(
        f"timeline: {len(tl)} events ({spans} spans) across {streams} "
        f"stream(s), {wall:.3f}s wall"
    )
    if profile_totals:
        total = sum(profile_totals.values()) or 1.0
        print(f"device time by phase ({profile_note}):")
        for phase, us in sorted(
            profile_totals.items(), key=lambda kv: -kv[1]
        ):
            print(f"  {us / 1e3:9.3f} ms  {100.0 * us / total:5.1f}%  {phase}")
    for a in anomalies[:10]:
        print("  " + format_anomaly(a))
    if len(anomalies) > 10:
        print(f"  ... ({len(anomalies) - 10} more anomalies)")
    if not anomalies:
        print("no drift/straggler anomalies detected")
    if out_path:
        print(f"wrote {out_path} (open in Perfetto: ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
