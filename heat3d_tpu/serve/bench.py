"""Ensemble throughput measurement — the batched counterpart of
``bench.harness.bench_throughput``, sharing its provenance discipline.

The row is a normal ``bench: throughput`` record (same ledger mirror,
same ``check_provenance.py`` contract) whose ``batch_shape`` /
``members_per_step`` fields carry the ensemble workload: ``gcell_per_sec``
counts EVERY member's cell updates, so the per-member effective rate is
``gcell_per_sec / members_per_step`` — ``heat3d obs summary`` and
``obs regress`` report that split so an ensemble win can never masquerade
as (or hide) a single-run regression.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp

from heat3d_tpu import obs
from heat3d_tpu.parallel.plan import effective_halo_plan
from heat3d_tpu.serve.ensemble import EnsembleSolver
from heat3d_tpu.serve.scenario import ScenarioBatch
from heat3d_tpu.utils.timing import (
    calibrate_trip_count,
    force_sync,
    honest_time,
    sync_overhead,
)


def bench_ensemble_throughput(
    batch: ScenarioBatch,
    steps: int = 50,
    warmup: int = 2,
    repeats: int = 3,
    batch_mesh: int = 1,
) -> Dict:
    """Gcell-updates/sec of the compiled ensemble loop (ALL members'
    updates counted; per-member effective rate = total / members). Same
    methodology as the solo bench: device-side loop, RTT-honest timing,
    best-of-repeats, auto-calibrated step count."""
    from heat3d_tpu.bench.harness import (
        _chain_ops,
        _ledger_bench_row,
        _utc_now,
    )
    from heat3d_tpu.parallel.step import redundant_flops_frac

    solver = EnsembleSolver(batch, batch_mesh=batch_mesh, bind="traced")
    cfg = solver.cfg
    B = solver.B
    u = solver.init_state()

    for _ in range(warmup):
        u = solver.run(u, steps)
        force_sync(u)
    rtt = sync_overhead(probe=jnp.zeros((8, 128)))

    def _timed(n):
        nonlocal u
        t0 = time.perf_counter()
        u = solver.run(u, int(n))
        force_sync(u)
        return time.perf_counter() - t0

    steps_requested = steps
    steps, raw = calibrate_trip_count(_timed, rtt, start=steps)
    raw_times = [raw] + [_timed(steps) for _ in range(repeats - 1)]
    times = [honest_time(t, rtt) for t in raw_times]
    best = min(times)
    rtt_dominated = min(raw_times) < 2 * rtt
    updates = B * cfg.grid.num_cells * steps
    gcells = updates / best / 1e9
    n_dev = solver.batch_mesh * cfg.mesh.num_devices
    row = {
        "bench": "throughput",
        "ts": _utc_now(),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "grid": list(cfg.grid.shape),
        "stencil": cfg.stencil.kind,
        # equation-family provenance, same contract as the solo harness
        # rows (check_provenance requires it; regress keys on it)
        "equation": cfg.equation,
        # integrator provenance (REQUIRED by check_provenance.py on every
        # throughput row): the ensemble packs the explicit sweep only,
        # but the row says so explicitly rather than by omission
        "integrator": cfg.integrator,
        "mesh": list(cfg.mesh.shape),
        "dtype": cfg.precision.storage,
        "compute_dtype": cfg.precision.compute,
        "backend": cfg.backend,
        "time_blocking": cfg.time_blocking,
        "overlap": cfg.overlap,
        "halo": cfg.halo,
        "halo_order": cfg.halo_order,
        # the EFFECTIVE plan mode (HEAT3D_NO_PLAN degrades partitioned
        # to the ad-hoc monolithic schedule — the solo harness's rule,
        # one source: parallel.plan.effective_halo_plan)
        "halo_plan": effective_halo_plan(cfg),
        "steps": steps,
        "steps_requested": steps_requested,
        "seconds_best": best,
        "seconds_all": times,
        "sync_rtt": rtt,
        "sync_rtt_s": rtt,
        "rtt_dominated": rtt_dominated,
        "gcell_per_sec": gcells,
        "gcell_per_sec_per_chip": gcells / n_dev,
        # the ensemble workload axis: total rate / members_per_step is the
        # per-member effective rate the obs reports print
        "batch_shape": [B],
        "members_per_step": B,
        "batch_mesh": solver.batch_mesh,
        # route provenance (check_provenance ROUTE_FIELDS): the ensemble
        # path is the parametric chain — no kernel route ever resolves
        "chain_ops": _chain_ops(cfg, mehrstellen=solver._mehrstellen),
        "mehrstellen_route": solver._mehrstellen,
        "direct_path": False,
        "streamk_path": False,
        "streamk_emulated": False,
        "cost_redundant_flops_frac": redundant_flops_frac(cfg),
        "cost_flops_per_step": None,
        "cost_bytes_per_step": None,
    }
    _ledger_bench_row(row)
    obs.REGISTRY.histogram(
        "bench_step_latency_seconds", "bench throughput per-step latency"
    ).observe(best / steps)
    return row
