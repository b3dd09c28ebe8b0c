"""Benchmark implementations.

Reference parity (SURVEY.md §3.5): the reference times warmup-excluded
iterations between barriers and prints Gcell/s; halo latency is the p50 of
a separately timed exchange-only program (the MPI_Waitall cost the
CUDA-aware path exists to minimize). Here both are separately jitted XLA
programs timed with block_until_ready.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from heat3d_tpu import obs
from heat3d_tpu.core.config import SolverConfig
from heat3d_tpu.models.heat3d import HeatSolver3D
from heat3d_tpu.parallel.step import exchange
from heat3d_tpu.parallel.topology import build_mesh, field_sharding
from jax import shard_map
from heat3d_tpu.utils.timing import (
    calibrate_trip_count,
    force_sync,
    honest_time,
    percentile,
    sync_overhead,
)


def _ledger_bench_row(row: Dict) -> None:
    """Mirror a measured row into the run ledger. The row's ``ts`` (UTC
    measurement-time string, the provenance key check_provenance.py
    requires) collides with the ledger envelope's ``ts`` (unix float at
    write time) — respell it ``ts_`` (the documented trailing-underscore
    rule) so a consumer can still join ledger events to
    bench_results.jsonl rows by timestamp."""
    obs.get().event(
        "bench_row", **{("ts_" if k == "ts" else k): v for k, v in row.items()}
    )


def _utc_now() -> str:
    import datetime

    return (
        datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ")
    )


def bench_throughput(
    cfg: SolverConfig,
    steps: int = 50,
    warmup: int = 2,
    repeats: int = 3,
) -> Dict:
    """Gcell-updates/sec (total and per chip) of the compiled time loop.

    ``repeats`` timed runs of a device-side loop; the best run is reported
    (matching how the reference class reports its timing: minimum over
    repetitions cancels host jitter). ``steps`` is a floor: the step count
    is auto-calibrated UP until the program's device time swamps the host
    round trip (the loop takes its pair-trip count dynamically, and the
    executable of each new write structure is warmed before its timed
    call, ``HeatSolver3D.warm_up``) — without this, small grids finish
    in a few host round trips and every row is RTT-dominated no matter how
    the arithmetic subtracts it."""
    solver = HeatSolver3D(cfg)
    u = solver.init_state("hot-cube")

    # The multistep executable donates its input, so thread the field through
    # successive calls (physically: the run just keeps time-stepping).
    # force_sync plus subtraction of the measured host round trip gives
    # device time (whether the RTT subtraction stays is ROADMAP Queue 1
    # item 1's call).
    import time as _time

    for _ in range(warmup):
        u = solver.run(u, steps)
        force_sync(u)
    rtt = sync_overhead(probe=jnp.zeros((8, 128)))

    def _timed(n):
        nonlocal u
        solver.warm_up(int(n))
        t0 = _time.perf_counter()
        u = solver.run(u, jnp.int32(n))
        force_sync(u)
        return _time.perf_counter() - t0

    steps_requested = steps
    steps, raw = calibrate_trip_count(_timed, rtt, start=steps)
    raw_times = [raw] + [_timed(steps) for _ in range(repeats - 1)]
    times = [honest_time(t, rtt) for t in raw_times]
    best = min(times)
    rtt_dominated = min(raw_times) < 2 * rtt
    updates = cfg.grid.num_cells * steps
    gcells = updates / best / 1e9
    # the route the step builders recorded when they dispatched this
    # solver's loop: every provenance field reads that one record
    route = solver.route
    direct = route["kernel"] == "direct"
    streamk = route["kernel"] == "streamk"
    mehrstellen = _mehrstellen_route(cfg, route["kernel"])
    # the streamk route has an off-TPU tier (interpret mode under
    # HEAT3D_DIRECT_INTERPRET): record it EXPLICITLY so A/B tooling
    # cannot mistake an interpreted row for a real Mosaic-kernel row
    # without cross-checking the platform
    streamk_emulated = bool(streamk and route["interpret"])
    # cost-analysis provenance (obs/perf/roofline): XLA's own FLOPs/bytes
    # for ONE step of this config, so a row's achieved-vs-peak is
    # computable from the row alone (`obs summary` roofline section,
    # `obs roofline`). One extra step-program compile per row
    # (HEAT3D_COST_ANALYSIS=0 skips); failures leave the fields null with
    # the error recorded — telemetry never fails the row.
    cost_fields = {"cost_flops_per_step": None, "cost_bytes_per_step": None}
    try:
        from heat3d_tpu.obs.perf.roofline import (
            cost_analysis_enabled,
            step_cost_fields,
        )

        if cost_analysis_enabled():
            cost_fields.update(step_cost_fields(solver))
    except Exception as e:  # noqa: BLE001 - telemetry fails soft, incl.
        # import-time drift in the perf package: the measured row lands
        # with null cost fields + the error, never dies
        cost_fields["cost_analysis_error"] = (
            f"{type(e).__name__}: {str(e)[:120]}"
        )
    row = {
        "bench": "throughput",
        # measurement time (UTC): lets a later outage round's fallback
        # prove WHICH session a carried committed row came from
        "ts": _utc_now(),
        # platform provenance: bench_results.jsonl is the on-chip record
        # by convention, but only this field makes a stray CPU row
        # detectable (bench.py's fallback filters on it)
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "grid": list(cfg.grid.shape),
        "stencil": cfg.stencil.kind,
        # equation-family provenance (REQUIRED by check_provenance.py on
        # every throughput row): families share footprints but not
        # chains/stability envelopes, so a reaction-diffusion rate must
        # never baseline against — or masquerade as — a heat rate
        # (obs regress keys on it; legacy rows key to heat)
        "equation": cfg.equation,
        # integrator provenance (REQUIRED by check_provenance.py on every
        # throughput row): a CG solve's step does many matvecs and a
        # leapfrog step carries two levels — their Gcell/s must never
        # baseline against (or masquerade as) the explicit sweep (obs
        # regress keys on it; legacy rows key to explicit-euler)
        "integrator": cfg.integrator,
        "mesh": list(cfg.mesh.shape),
        "dtype": cfg.precision.storage,
        "compute_dtype": cfg.precision.compute,
        "backend": cfg.backend,
        "time_blocking": cfg.time_blocking,
        "overlap": cfg.overlap,
        "halo": cfg.halo,
        "halo_order": cfg.halo_order,
        # exchange-plan provenance (knob-drift + ROUTE_FIELDS contract):
        # a partitioned row's traffic is byte-identical to monolithic but
        # its message schedule is not — the A/B must be keyable from the
        # row alone. The EFFECTIVE mode is recorded (HEAT3D_NO_PLAN
        # degrades partitioned to the ad-hoc monolithic schedule; the
        # row must say what ran — docs/TUNING.md "Persistent exchange
        # plans")
        "halo_plan": _effective_halo_plan(cfg),
        "steps": steps,
        "steps_requested": steps_requested,
        # ensemble-workload provenance (REQUIRED by check_provenance.py on
        # every throughput row): the solo bench advances one member per
        # step call. Ensemble rows (serve.bench.bench_ensemble_throughput)
        # carry [B]/B here, and gcell_per_sec counts every member's
        # updates — per-member effective rate = gcell_per_sec /
        # members_per_step, which obs summary/regress report so a packed
        # batch's total can never masquerade as a single-run rate.
        "batch_shape": [1],
        "members_per_step": 1,
        "seconds_best": best,
        "seconds_all": times,
        "sync_rtt": rtt,
        # canonical RTT provenance field (seconds) — REQUIRED by
        # scripts/check_provenance.py on every bench row, so an
        # RTT-dominated sample is auditable from the row alone
        "sync_rtt_s": rtt,
        "rtt_dominated": rtt_dominated,
        "gcell_per_sec": gcells,
        "gcell_per_sec_per_chip": gcells / cfg.mesh.num_devices,
        # Emitted-chain provenance: the factoring knobs are env vars, so
        # without this a HEAT3D_FACTOR_Y=0 A/B row is indistinguishable
        # from a default suite row, and analysis tools re-deriving the op
        # count later (under a different env) would mislabel it.
        "chain_ops": _chain_ops(cfg, mehrstellen=mehrstellen),
        "mehrstellen_route": mehrstellen,
        # Same provenance need for the transport knob: HEAT3D_NO_DIRECT=1
        # A/B rows carry identical config fields to direct rows but run
        # the exchange path at ~2x the HBM traffic — record the RESOLVED
        # selection (the real selector, not the env) so the traffic model
        # can't mislabel them.
        "direct_path": direct,
        # deep-tb route provenance: whether the fused k-sweep streaming
        # kernel resolved (tb=3..4); without it a tb=3 row's traffic model
        # can't distinguish one fused sweep from k plain sweeps. The
        # _emulated twin marks interpret-tier resolutions.
        "streamk_path": streamk,
        "streamk_emulated": streamk_emulated,
        # redundant-compute honesty (required by check_provenance.py on
        # tb>1 rows): fraction of the superstep's executed stencil flops
        # that are ghost-ring recompute — the discount between this row's
        # measured Gcell/s and what the chip actually sustained
        "cost_redundant_flops_frac": _redundant_frac(cfg),
        **cost_fields,
    }
    _ledger_bench_row(row)
    obs.REGISTRY.histogram(
        "bench_step_latency_seconds", "bench throughput per-step latency"
    ).observe(best / steps)
    return row


def _effective_halo_plan(cfg: SolverConfig) -> str:
    """The ONE effective-mode rule (parallel.plan.effective_halo_plan):
    what the rows record is what executed, incl. the HEAT3D_NO_PLAN
    degradation. No fail-soft wrapper: the function is pure env+config
    inspection, and if parallel.plan itself cannot import, the solver
    that produced the measurement could not have run either."""
    from heat3d_tpu.parallel.plan import effective_halo_plan

    return effective_halo_plan(cfg)


def _redundant_frac(cfg: SolverConfig) -> float:
    """parallel.step.redundant_flops_frac, fail-open to 0.0 only for
    tb<=1 (where no superstep exists); tb>1 derivation is pure local
    arithmetic and cannot fail."""
    from heat3d_tpu.parallel.step import redundant_flops_frac

    return redundant_flops_frac(cfg)


def _chain_ops(
    cfg: SolverConfig, mehrstellen: bool = None, kernel: str = None
) -> int:
    """Vector ops/cell/update of the local compute this config runs under
    the CURRENT env: the mehrstellen separable route's canonical count
    when that route is what executes (knob on + taps decompose + the
    resolved local compute implements it — the jnp apply, or the q-ring
    direct kernels at tb=1/tb=2), else the tap chain's
    effective_num_taps.
    Recorded per row; scripts/roofline_check.py prefers this over
    re-derivation. ``mehrstellen`` takes a precomputed _mehrstellen_route
    result so one env evaluation feeds every provenance field; else
    ``kernel`` is the solver's recorded kernel family."""
    from heat3d_tpu.core.stencils import MEHRSTELLEN_OPS, chain_ops_for

    if cfg.backend == "conv":
        return None  # one conv op, not a tap chain — op count n/a
    if mehrstellen is None:
        mehrstellen = _mehrstellen_route(cfg, kernel)
    if mehrstellen:
        return MEHRSTELLEN_OPS
    if cfg.equation != "heat":
        # spec-built families: count the ACTUAL lowered chain (asymmetric
        # taps — e.g. advection — defeat the x/y factoring, so the heat
        # kind's nominal count would misstate the emitted ops)
        from heat3d_tpu.core.stencils import effective_num_taps
        from heat3d_tpu.parallel.step import _solver_taps

        return effective_num_taps(_solver_taps(cfg))
    return chain_ops_for(cfg.stencil.kind)


def _mehrstellen_route(cfg: SolverConfig, kernel: str = None) -> bool:
    """Whether the separable S+F route actually executes for this config:
    knob on, taps decompose, and the local compute implements it — the
    jnp apply (explicit --backend jnp, or auto off-TPU) or the q-ring
    direct kernels (tb=1 single step, tb=2 fused superstep; ``kernel``
    is the solver's recorded kernel family). The windowed exchange-path
    kernels keep the tap chain."""
    from heat3d_tpu.core.stencils import (
        decompose_mehrstellen,
        mehrstellen_enabled,
    )
    from heat3d_tpu.parallel.step import _solver_taps

    if not mehrstellen_enabled():
        return False
    # the solver's own tap construction, so route provenance can't diverge
    # from what executes
    if decompose_mehrstellen(_solver_taps(cfg)) is None:
        return False
    # the solver's own resolution (models.heat3d.resolved_backend_name):
    # auto falls back to the jnp apply whenever the Pallas kernels
    # can't run this config — in which case the route DOES execute
    from heat3d_tpu.models.heat3d import resolved_backend_name

    backend = resolved_backend_name(cfg)
    if backend == "jnp":
        return True
    return cfg.time_blocking in (1, 2) and kernel == "direct"


def bench_halo(
    cfg: SolverConfig,
    iters: int = 10,
    warmup: int = 2,
    k: Optional[int] = None,
) -> Dict:
    """p50/p95 latency of one full 3D ghost exchange (6 faces via 3
    axis-ordered ppermute pairs) — the judged halo-exchange latency metric.

    Methodology (the same trick ``bench_throughput`` uses): a DEVICE-SIDE
    ``fori_loop`` of ``k`` back-to-back exchanges is compiled as one XLA
    program, the whole program is timed with one sync, and the per-exchange
    latency is (wall - rtt) / k. The loop carry is the local block with
    each of its six boundary faces overwritten by the received ghost face
    on that side — ALL six ppermutes are data-live every iteration so XLA
    cannot DCE any of them, the carry shape stays fixed, and the
    non-exchange work charged per iteration is six FACE-sized in-place
    updates, not a volume reduction (which would inflate the judged p50
    by a volume's worth of HBM traffic). ``k`` is
    auto-scaled until device time swamps the host round trip, so
    ``rtt_dominated`` rows should only appear for
    micro-exchanges on extreme-RTT links.

    Tail honesty: averaging k exchanges per sample necessarily dilutes
    per-exchange latency spikes, so the tail field is named
    ``p95_mean_us`` — the 95th percentile of per-PROGRAM means — and must
    not be read as per-exchange tail latency (which is unobservable
    through a high-RTT host link; the judged metric is the p50).

    On a (1,1,1) mesh no collective executes (size-1 axes short-circuit to
    self-wrap / BC fill): such rows measure the local pad/crop cost only
    and are labeled ``ici: false``.
    """
    mesh = build_mesh(cfg.mesh)
    sharding = field_sharding(mesh, cfg.mesh)
    spec = P(*cfg.mesh.axis_names)
    local = cfg.local_shape

    # exchange routes through the configured transport (ppermute or the
    # Pallas remote-DMA kernels), so the judged halo p50 covers both tiers.
    nx, ny, nz = local

    def _loop(u_local, n):
        def body(_, u):
            p = exchange(u, cfg)  # (nx+2, ny+2, nz+2), ghosts filled
            # fold each received ghost face onto the carry's boundary face
            # (in-place DUS on the loop carry: face-sized writes only)
            out = u
            out = out.at[0].set(p[0, 1 : 1 + ny, 1 : 1 + nz])
            out = out.at[nx - 1].set(p[nx + 1, 1 : 1 + ny, 1 : 1 + nz])
            out = out.at[:, 0].set(p[1 : 1 + nx, 0, 1 : 1 + nz])
            out = out.at[:, ny - 1].set(p[1 : 1 + nx, ny + 1, 1 : 1 + nz])
            out = out.at[:, :, 0].set(p[1 : 1 + nx, 1 : 1 + ny, 0])
            out = out.at[:, :, nz - 1].set(p[1 : 1 + nx, 1 : 1 + ny, nz + 1])
            return out

        return jax.lax.fori_loop(0, n, body, u_local)

    run_n = jax.jit(
        shard_map(
            _loop,
            mesh=mesh,
            in_specs=(spec, P()),
            out_specs=spec,
            check_vma=False,
        )
    )
    u = jax.device_put(
        jnp.zeros(cfg.padded_shape, jnp.dtype(cfg.precision.storage)), sharding
    )
    import time as _time

    for _ in range(warmup):
        force_sync(run_n(u, jnp.int32(1)))
    rtt = sync_overhead(probe=jnp.zeros((8, 128)))

    def _timed(n):
        t0 = _time.perf_counter()
        force_sync(run_n(u, jnp.int32(n)))
        return _time.perf_counter() - t0

    if k is None:
        k, _ = calibrate_trip_count(_timed, rtt, start=25)
    raws = [_timed(k) for _ in range(iters)]
    times = [honest_time(t, rtt) / k for t in raws]
    rtt_dominated = min(raws) < 2 * rtt
    face_cells = (
        cfg.local_shape[1] * cfg.local_shape[2]
        + cfg.local_shape[0] * cfg.local_shape[2]
        + cfg.local_shape[0] * cfg.local_shape[1]
    )
    bytes_per_dev = 2 * face_cells * jnp.dtype(cfg.precision.storage).itemsize
    halo_hist = obs.REGISTRY.histogram(
        "halo_exchange_latency_seconds",
        "per-exchange halo latency (program mean)",
    )
    for t in times:
        halo_hist.observe(t)
    # cost-analysis provenance for halo rows (ROADMAP open item): XLA's
    # bytes for ONE exchange via the `halo_exchange` phase program, so the
    # halo p50 gets its own achieved-vs-peak fraction in `obs roofline` /
    # `obs summary` without joining against a throughput row. Same
    # fail-soft posture as the throughput cost fields.
    halo_cost = {"cost_bytes_per_step": None}
    try:
        from heat3d_tpu.obs.perf.roofline import (
            cost_analysis_enabled,
            halo_cost_fields,
        )

        if cost_analysis_enabled():
            halo_cost.update(halo_cost_fields(cfg))
    except Exception as e:  # noqa: BLE001 - telemetry fails soft
        halo_cost["cost_analysis_error"] = (
            f"{type(e).__name__}: {str(e)[:120]}"
        )
    # planned-exchange provenance + the plan's own transport model
    # (messages and boundary bytes per device per exchange) beside XLA's
    # cost bytes — the roofline's planned-exchange arm reads these. The
    # model prices the EFFECTIVE schedule (see _effective_halo_plan).
    # Fail-soft like every other telemetry field on the row.
    eff_hp = _effective_halo_plan(cfg)
    plan_fields = {}
    try:
        from heat3d_tpu.parallel.plan import plan_for

        t = plan_for(
            dataclasses.replace(cfg, halo_plan=eff_hp)
        ).traffic(
            cfg.local_shape, jnp.dtype(cfg.precision.storage).itemsize
        )
        plan_fields = {
            "plan_messages_per_exchange": t["messages"],
            "plan_bytes_per_device": t["bytes_per_device"],
        }
    except Exception as e:  # noqa: BLE001 - telemetry fails soft
        plan_fields = {
            "plan_model_error": f"{type(e).__name__}: {str(e)[:120]}"
        }
    row = {
        "bench": "halo",
        "ts": _utc_now(),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "grid": list(cfg.grid.shape),
        "mesh": list(cfg.mesh.shape),
        "dtype": cfg.precision.storage,
        "halo_order": cfg.halo_order,
        "halo_plan": eff_hp,
        **plan_fields,
        "iters": iters,
        "exchanges_per_program": k,
        "p50_us": percentile(times, 50) * 1e6,
        "p95_mean_us": percentile(times, 95) * 1e6,
        "min_us": min(times) * 1e6,
        "sync_rtt_us": rtt * 1e6,
        # canonical RTT provenance field, same contract as throughput rows
        "sync_rtt_s": rtt,
        "rtt_dominated": rtt_dominated,
        "ici": cfg.mesh.num_devices > 1,
        "halo_bytes_per_device": bytes_per_dev,
        **halo_cost,
    }
    _ledger_bench_row(row)
    # opt-in per-link probe (HEAT3D_COMM_PROBE): time each (axis,
    # direction, sub-block) collective as its own micro-program and emit
    # comm_probe rows beside this bench row — predicted-vs-achieved GB/s
    # per link (docs/OBSERVABILITY.md §9). maybe_probe is env-gated and
    # fails soft; the import guard covers torn installs the same way the
    # other telemetry on this row does.
    try:
        from heat3d_tpu.obs.comm.probe import maybe_probe

        maybe_probe(cfg)
    except Exception as e:  # noqa: BLE001 - telemetry fails soft
        print(f"bench: comm probe skipped ({e})", file=sys.stderr)
    return row


def run_suite(
    configs: List[SolverConfig],
    steps: int = 50,
    out=None,
    state_path: Optional[str] = None,
) -> List[Dict]:
    """Run throughput for each config + halo once per distinct exchange
    shape; emit one JSON line per result.

    The halo latency depends only on (grid, mesh, storage dtype, transport)
    — not on tb/backend/stencil — so configs differing only in those knobs
    share one halo row instead of re-measuring it (the duplicate-row noise
    in the round-2 tables).

    With ``state_path``, every landed row is journaled in a
    :class:`~heat3d_tpu.resilience.sweepstate.SweepState` and an
    interrupted sweep (SIGTERM, backend death) RESUMES AT THE FIRST
    MISSING ROW on the next invocation — completed rows are re-emitted
    from the journal, not re-measured. Fault hooks
    (``HEAT3D_FAULTS=sigterm:row=K``) fire per row so the resume path is
    testable on CPU."""
    from heat3d_tpu.resilience.faults import FaultPlan
    from heat3d_tpu.resilience.sweepstate import SweepState, row_key

    out = out or sys.stdout
    state = SweepState(state_path) if state_path else None
    plan = FaultPlan.from_env()
    results = []
    halo_seen = set()
    row_index = 0

    def one_row(key: str, measure) -> Dict:
        nonlocal row_index
        if state is not None:
            done = state.record(key)
            if done is not None and done.get("record") is not None:
                r = done["record"]
                results.append(r)
                print(json.dumps(r), file=out, flush=True)
                # re-emitted from the journal, NOT re-measured: the ledger
                # must distinguish the two or a resumed A/B session reads
                # as having measured rows it merely replayed
                obs.get().event("bench_row_replayed", key=key)
                return r
        plan.on_sweep_row(row_index)
        row_index += 1
        with obs.get().span("bench_row_measure", key=key):
            r = measure()
        results.append(r)
        print(json.dumps(r), file=out, flush=True)
        if state is not None:
            state.mark_done(key, r)
        return r

    for cfg in configs:
        one_row(
            row_key(cfg, "throughput"),
            lambda cfg=cfg: bench_throughput(cfg, steps=steps),
        )
        halo_key = (
            cfg.grid.shape, cfg.mesh.shape, cfg.precision.storage,
            cfg.halo, cfg.halo_order, _effective_halo_plan(cfg),
        )
        if halo_key not in halo_seen:
            halo_seen.add(halo_key)
            one_row(row_key(cfg, "halo"), lambda cfg=cfg: bench_halo(cfg))
    return results
