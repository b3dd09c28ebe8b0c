"""Roofline attribution: what fraction of the hardware's peak did each
phase achieve?

Two complementary models, one module:

**Row model** (promoted from ``scripts/roofline_check.py``, which is now a
thin wrapper): the analytic HBM traffic model + VPU op-cost model of the
tap chain, applied to measured ``bench_results.jsonl`` rows — exact for
the step paths this framework emits, but blind to anything XLA adds.

**Compiled model** (new): FLOPs/bytes straight from
``compiled.cost_analysis()`` — XLA's own cost accounting of the real
executable — per PHASE program (``parallel.step.phase_programs``: the
compile targets are keyed by the same ``heat3d.*`` names the named-scope
spans and the profiler trace tables use, so a cost record joins a
measured span on one key). Combined with per-backend peak specs
(:data:`PEAK_SPECS`) this turns a measured phase time into
achieved-vs-peak fractions: the ``heat3d obs roofline`` live table, the
``roofline`` section of ``obs summary``, and the ``cost_flops_per_step``
/ ``cost_bytes_per_step`` fields on every bench row.

Caveat the numbers honestly: XLA's cost model sees custom calls (the
Mosaic/Pallas kernels) as opaque — flops on those routes are
underestimates; the bytes side and the jnp/conv routes are solid. Peak
specs are deliberately conservative defaults (env-overridable:
``HEAT3D_PEAK_MEM_GBPS`` / ``HEAT3D_PEAK_GFLOPS``); a fraction over 100%
means the chip beats the spec table, not a measurement bug.

Failure posture: cost analysis is telemetry — every consumer treats a
raised :func:`step_cost_fields` as "fields unavailable", never as a run
failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# ---- per-chip peak specs ----------------------------------------------------

# Published per-chip peaks the achieved fractions divide by, keyed by JAX's
# ``device_kind``. mem_gbps = HBM bandwidth; bf16_tflops = the MXU's bf16
# peak; vector_gflops = the VECTOR f32 rate the tap chains ride, which has
# no public figure (``heat3d obs roofline --calibrate`` measures it per
# chip). A kind missing here has no peak: its fractions read "not
# measured", never another chip's figure.
PEAK_SPECS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "mem_gbps": 819.0,
        "bf16_tflops": 197.0,
        "vector_gflops": None,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}
_NO_SPEC: Dict[str, Any] = {
    "mem_gbps": None,
    "bf16_tflops": None,
    "vector_gflops": None,
    "source": None,
}


def peak_spec(device_kind: str) -> Dict[str, Any]:
    """Peak spec for ``device_kind``; precedence per field: env override
    (``HEAT3D_PEAK_MEM_GBPS`` / ``HEAT3D_PEAK_GFLOPS``) > CALIBRATED
    vector peak of this chip generation (``heat3d obs roofline
    --calibrate``; measured on THIS chip beats any table) > the published
    table. Missing values stay None ("not measured")."""
    spec = dict(PEAK_SPECS.get(device_kind, _NO_SPEC))
    env_overridden = set()
    for env, key in (
        ("HEAT3D_PEAK_MEM_GBPS", "mem_gbps"),
        ("HEAT3D_PEAK_GFLOPS", "vector_gflops"),
    ):
        v = os.environ.get(env)
        if v:
            try:
                spec[key] = float(v)
                env_overridden.add(key)
            except ValueError:
                pass  # a bad override must not kill a report
    if "vector_gflops" not in env_overridden:
        # calibrated lookup only when the CURRENT process runs the chip
        # being asked about — a CPU box summarizing TPU rows must not
        # apply its own calibrated CPU peak to them
        try:
            import jax

            if jax.devices()[0].device_kind == device_kind:
                from heat3d_tpu.tune.cache import chip_generation, load_peak

                calibrated = load_peak(chip_generation())
                if calibrated:
                    spec["vector_gflops"] = calibrated
        except Exception:  # noqa: BLE001 - telemetry fails soft
            pass
    return spec


def _peak_text(v: Optional[float]) -> str:
    return "not measured" if v is None else f"{v:g}"


# ---- compiled-model cost extraction ----------------------------------------


def extract_cost(cost_analysis: Any) -> Tuple[Optional[float], Optional[float]]:
    """``(flops, bytes_accessed)`` from a ``compiled.cost_analysis()``
    dict; None for whatever the backend didn't report."""
    ca = cost_analysis
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    bytes_ = ca.get("bytes accessed")
    return (
        float(flops) if isinstance(flops, (int, float)) else None,
        float(bytes_) if isinstance(bytes_, (int, float)) else None,
    )


def cost_analysis_enabled() -> bool:
    """``HEAT3D_COST_ANALYSIS=0`` disables the per-row/per-run compiled
    cost accounting (it costs one extra step-program compile)."""
    return os.environ.get("HEAT3D_COST_ANALYSIS", "1").lower() not in (
        "0",
        "false",
    )


def step_cost_fields(solver) -> Dict[str, Optional[float]]:
    """Cost-analysis fields for the program ``solver``'s hot loop actually
    runs — whole-program (all shards) numbers from XLA's compiled cost
    model, normalized PER UPDATE. For ``time_blocking == 1`` that is the
    single-step executable; for ``time_blocking > 1`` it is the k-update
    superstep (``make_superstep_fn`` — one exchange amortized over k
    updates, ghost-ring recompute included) divided by k: costing the
    single step there would describe a program the bench never ran.
    Raises on any failure; callers treat that as "fields unavailable"
    (telemetry fails soft), never as a run failure."""
    import jax

    cfg = solver.cfg
    aval = jax.ShapeDtypeStruct(
        cfg.padded_shape, solver.storage_dtype, sharding=solver.sharding
    )
    if cfg.integrator != "explicit-euler":
        # the integrators' own one-update program
        program, updates = solver._step, 1
    else:
        from heat3d_tpu.parallel.step import make_step_fn, make_superstep_fn

        build = make_superstep_fn if cfg.time_blocking > 1 else make_step_fn
        program = jax.jit(build(cfg, solver.mesh, solver._compute))
        updates = cfg.time_blocking
    compiled = program.lower(aval).compile()
    flops, bytes_ = extract_cost(compiled.cost_analysis())
    # Raw-vs-effective honesty for temporally-blocked supersteps: the
    # XLA-counted flops are RAW (the chip executes the shrinking-ring
    # recompute trapezoid); the effective fraction discounts them to the
    # k useful sweeps (parallel.step.redundant_flops_frac) so a deep-tb
    # "win" that is mostly recompute is visible from the fields alone.
    from heat3d_tpu.parallel.step import redundant_flops_frac

    frac = redundant_flops_frac(cfg)
    raw_per_step = None if flops is None else flops / updates
    return {
        "cost_flops_per_step": raw_per_step,
        "cost_bytes_per_step": None if bytes_ is None else bytes_ / updates,
        "cost_redundant_flops_frac": frac,
        "cost_effective_flops_per_step": (
            None if raw_per_step is None else raw_per_step * (1.0 - frac)
        ),
    }


def halo_cost_fields(cfg) -> Dict[str, Optional[float]]:
    """Cost-analysis bytes for ONE ghost exchange of ``cfg`` — the
    ``halo_exchange`` phase program (``parallel.step.phase_programs``)
    compiled and read through XLA's cost model, so bench halo rows carry
    their own achieved-vs-peak denominator (ROADMAP "cost-analysis
    fields for halo rows"). The program includes the face-sized
    keep-alive writes that make every transport data-live (a small,
    honest overcount documented there). Raises on failure; callers treat
    that as "fields unavailable" (telemetry fails soft)."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.models.heat3d import _select_backend
    from heat3d_tpu.parallel.step import PHASE_HALO, phase_programs
    from heat3d_tpu.parallel.topology import build_mesh, field_sharding

    mesh = build_mesh(cfg.mesh)
    sharding = field_sharding(mesh, cfg.mesh)
    program = phase_programs(cfg, mesh, _select_backend(cfg))[PHASE_HALO]
    aval = jax.ShapeDtypeStruct(
        cfg.padded_shape, jnp.dtype(cfg.precision.storage), sharding=sharding
    )
    compiled = jax.jit(program).lower(aval).compile()
    _, bytes_ = extract_cost(compiled.cost_analysis())
    return {"cost_bytes_per_step": bytes_}


def record_step_cost(solver, **extra: Any) -> Optional[Dict[str, Any]]:
    """Compute :func:`step_cost_fields` for ``solver`` and append one
    ``step_cost`` ledger event (plus the platform, so ``obs summary`` can
    pick the right peak spec). Fails soft: any error becomes an
    ``ok: false`` event and a None return."""
    from heat3d_tpu import obs

    if not cost_analysis_enabled():
        return None
    if not obs.get().active:
        # the ledger event is this function's ONLY output: without an
        # active ledger the extra lower+compile of the step program (tens
        # of seconds at pod-scale grids) would buy a discarded event
        return None
    try:
        import jax

        fields = step_cost_fields(solver)
        fields["platform"] = jax.default_backend()
        fields["device_kind"] = jax.devices()[0].device_kind
    except Exception as e:  # noqa: BLE001 - telemetry fails soft
        obs.get().event(
            "step_cost", ok=False,
            error=f"{type(e).__name__}: {str(e)[:200]}",
        )
        return None
    obs.get().event("step_cost", ok=True, **fields, **extra)
    return fields


# ---- the live per-phase table ----------------------------------------------


def phase_costs_and_times(
    cfg, iters: int = 3, warmup: int = 1
) -> List[Dict[str, Any]]:
    """Compile each phase program of ``cfg``
    (:func:`heat3d_tpu.parallel.step.phase_programs`), read its
    cost_analysis, and time it: one record per phase with ``flops``,
    ``bytes``, ``seconds`` (best of ``iters``, RTT-subtracted), and the
    achieved rates. Runs on any platform — on CPU the numbers are XLA's
    CPU cost model over the same programs."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.models.heat3d import _select_backend
    from heat3d_tpu.parallel.step import phase_programs
    from heat3d_tpu.parallel.topology import build_mesh, field_sharding
    from heat3d_tpu.utils.timing import force_sync, honest_time, sync_overhead

    mesh = build_mesh(cfg.mesh)
    sharding = field_sharding(mesh, cfg.mesh)
    compute = _select_backend(cfg)
    programs = phase_programs(cfg, mesh, compute)
    u = jax.device_put(
        jnp.zeros(cfg.padded_shape, jnp.dtype(cfg.precision.storage)),
        sharding,
    )
    rtt = sync_overhead()
    import time as _time

    out = []
    for phase, fn in programs.items():
        jitted = jax.jit(fn)
        try:
            compiled = jitted.lower(u).compile()
            flops, bytes_ = extract_cost(compiled.cost_analysis())
        except Exception as e:  # noqa: BLE001 - keep the table best-effort
            out.append(
                {
                    "phase": phase,
                    "error": f"{type(e).__name__}: {str(e)[:200]}",
                }
            )
            continue
        for _ in range(warmup):
            force_sync(jitted(u))
        times = []
        for _ in range(iters):
            t0 = _time.perf_counter()
            force_sync(jitted(u))
            times.append(honest_time(_time.perf_counter() - t0, rtt))
        sec = min(times)
        rec = {
            "phase": phase,
            "flops": flops,
            "bytes": bytes_,
            "seconds": sec,
            "gflops": (flops / sec / 1e9) if flops else None,
            "gbps": (bytes_ / sec / 1e9) if bytes_ else None,
        }
        if phase == "step" and cfg.time_blocking > 1:
            # the step program is the k-update SUPERSTEP: its flops/gflops
            # are RAW (recompute trapezoid included). Attach the effective
            # side — useful updates per second and the recompute discount
            # — so the table can show both without re-deriving.
            from heat3d_tpu.parallel.step import redundant_flops_frac

            rec["updates_per_call"] = cfg.time_blocking
            rec["redundant_flops_frac"] = redundant_flops_frac(cfg)
            rec["eff_gcell_per_s"] = (
                cfg.grid.num_cells * cfg.time_blocking / sec / 1e9
            )
        out.append(rec)
    return out


def _pct(v: Optional[float], peak: Optional[float]) -> str:
    if v is None or not peak:
        return "-"
    return f"{v / peak:7.1%}"


def print_live_table(
    cfg, records: List[Dict[str, Any]], device_kind: str, out=None
) -> None:
    """The per-phase achieved-vs-peak table ``heat3d obs roofline``
    prints: phase, XLA-counted flops/bytes, measured time, achieved
    GFLOP/s and GB/s, and the fraction of each peak — plus which ceiling
    binds."""
    out = out or sys.stdout
    spec = peak_spec(device_kind)
    mem, vec = spec.get("mem_gbps"), spec.get("vector_gflops")
    grid = "x".join(str(g) for g in cfg.grid.shape)
    print(
        f"roofline [{device_kind}] grid={grid} stencil={cfg.stencil.kind} "
        f"dtype={cfg.precision.storage} tb={cfg.time_blocking} "
        f"backend={cfg.backend} "
        f"(peaks: mem {_peak_text(mem)} GB/s, "
        f"vector {_peak_text(vec)} GFLOP/s)",
        file=out,
    )
    print(
        f"{'phase':<16} {'flops':>12} {'bytes':>12} {'time':>10} "
        f"{'GFLOP/s':>9} {'GB/s':>8} {'%flops':>8} {'%mem':>8} {'bound':>6}",
        file=out,
    )
    for r in records:
        if "error" in r:
            print(f"{r['phase']:<16} error: {r['error']}", file=out)
            continue
        fm = _pct(r.get("gflops"), vec)
        bm = _pct(r.get("gbps"), mem)
        bound = "?"
        if r.get("gbps") is not None and mem:
            bound = "mem"
            if (
                r.get("gflops") is not None
                and vec
                and r["gflops"] / vec > r["gbps"] / mem
            ):
                bound = "flops"
        # Deep-tb honesty: the superstep's %flops is achieved-vs-peak on
        # RAW flops (what the chip executes); print the EFFECTIVE rate
        # (useful updates only) and the recompute discount next to it so
        # a tb=k row whose raw rate rides on ghost-ring recompute cannot
        # read as a clean win.
        eff = ""
        if r.get("eff_gcell_per_s") is not None:
            eff = (
                f"  eff {r['eff_gcell_per_s']:.3f} Gcell/s "
                f"({r.get('redundant_flops_frac', 0.0):.0%} recompute, "
                f"{r.get('updates_per_call')} upd/call)"
            )
        print(
            f"{r['phase']:<16} "
            f"{r['flops'] if r['flops'] is not None else '-':>12} "
            f"{r['bytes'] if r['bytes'] is not None else '-':>12} "
            f"{r['seconds'] * 1e3:>8.2f}ms "
            f"{r['gflops'] if r['gflops'] is not None else 0:>9.2f} "
            f"{r['gbps'] if r['gbps'] is not None else 0:>8.2f} "
            f"{fm:>8} {bm:>8} {bound:>6}{eff}",
            file=out,
        )


# ---- profile -> roofline join ----------------------------------------------


def phase_cost_records(cfg) -> Dict[str, Dict[str, Any]]:
    """Compile-only cost records per phase program — the COST side of the
    profile→roofline join (:func:`profile_join_records`): each phase of
    ``parallel.step.phase_programs`` lowered over an abstract sharded
    field and read through ``compiled.cost_analysis()``. No timing, no
    device_put — the measured side comes from the profile capture."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.models.heat3d import _select_backend
    from heat3d_tpu.parallel.step import phase_programs
    from heat3d_tpu.parallel.topology import build_mesh, field_sharding

    mesh = build_mesh(cfg.mesh)
    sharding = field_sharding(mesh, cfg.mesh)
    programs = phase_programs(cfg, mesh, _select_backend(cfg))
    aval = jax.ShapeDtypeStruct(
        cfg.padded_shape, jnp.dtype(cfg.precision.storage), sharding=sharding
    )
    out: Dict[str, Dict[str, Any]] = {}
    for phase, fn in programs.items():
        try:
            compiled = jax.jit(fn).lower(aval).compile()
            flops, bytes_ = extract_cost(compiled.cost_analysis())
            out[phase] = {"flops": flops, "bytes": bytes_}
        except Exception as e:  # noqa: BLE001 - keep the join best-effort
            out[phase] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            continue
    return out


def _phase_calls(phase: str, steps: int, tb: int) -> Optional[int]:
    """How many times the phase program ran across ``steps`` updates:
    one stencil sweep per update; one exchange per tb updates. None means "total time only, no achieved rate": residual
    cadence is run-configured and not recoverable from the capture, and
    the ``step`` scope's device time is EXCLUSIVE (ops inside the inner
    stencil/halo scopes attribute there, leaving only dispatch glue) —
    dividing the FULL step program's cost by glue-only time would report
    absurd fractions of peak."""
    from heat3d_tpu.parallel.step import PHASE_HALO, PHASE_STENCIL

    if phase == PHASE_STENCIL:
        return max(1, steps)
    if phase == PHASE_HALO:
        return max(1, steps // max(1, tb))
    return None


def profile_join_records(
    cfg, phase_us: Dict[str, float], steps: int
) -> List[Dict[str, Any]]:
    """THE join (ROADMAP carry-over from PR 3): measured per-phase DEVICE
    time from a ``--profile`` capture (``obs.perf.timeline
    .profile_phase_totals`` — keyed by the ``heat3d.*`` named scopes)
    against the cost-analysis FLOPs/bytes of the same-named phase
    programs. One record per phase: total device time, its share of
    attributed device time, per-call device seconds (``steps`` and
    ``cfg.time_blocking`` set the call counts), and the achieved
    GFLOP/s / GB/s those imply — "stencil at X% of HBM peak, halo at Y%"
    from measured times, not span wall-clock."""
    from heat3d_tpu.parallel.step import PHASES

    costs = phase_cost_records(cfg)
    tb = cfg.time_blocking
    attributed = sum(
        us for ph, us in phase_us.items() if ph != "(unattributed)"
    )
    records: List[Dict[str, Any]] = []
    order = (
        [ph for ph in PHASES if ph in costs]
        + [ph for ph in costs if ph not in PHASES]
        + [ph for ph in sorted(phase_us) if ph not in costs]
    )
    for phase in order:
        cost = costs.get(phase, {})
        us = phase_us.get(phase)
        rec: Dict[str, Any] = {
            "phase": phase,
            "device_us": None if us is None else round(us, 3),
            "share": (
                round(us / attributed, 4)
                if us is not None and attributed > 0
                and phase != "(unattributed)"
                else None
            ),
            "flops": cost.get("flops"),
            "bytes": cost.get("bytes"),
        }
        if cost.get("error"):
            rec["error"] = cost["error"]
        calls = _phase_calls(phase, steps, tb)
        if us is not None and calls:
            sec = us * 1e-6 / calls
            rec["calls"] = calls
            rec["seconds"] = sec
            flops, bytes_ = cost.get("flops"), cost.get("bytes")
            rec["gflops"] = (flops / sec / 1e9) if flops and sec > 0 else None
            rec["gbps"] = (bytes_ / sec / 1e9) if bytes_ and sec > 0 else None
        records.append(rec)
    return records


def print_profile_table(
    cfg, records: List[Dict[str, Any]], device_kind: str, steps: int,
    artifact: str, out=None,
) -> None:
    """The measured-device-time achieved-vs-peak table ``heat3d obs
    roofline --from-profile`` prints. Same peak specs and %-of-peak
    semantics as the live table; the time column is DEVICE time from the
    capture, divided over the calls the run made."""
    out = out or sys.stdout
    spec = peak_spec(device_kind)
    mem, vec = spec.get("mem_gbps"), spec.get("vector_gflops")
    grid = "x".join(str(g) for g in cfg.grid.shape)
    print(
        f"roofline from profile [{device_kind}] grid={grid} "
        f"stencil={cfg.stencil.kind} tb={cfg.time_blocking} steps={steps} "
        f"(peaks: mem {_peak_text(mem)} GB/s, "
        f"vector {_peak_text(vec)} GFLOP/s)\n"
        f"  measured device time: {artifact}",
        file=out,
    )
    print(
        f"{'phase':<16} {'dev total':>10} {'share':>6} {'per-call':>10} "
        f"{'GFLOP/s':>9} {'GB/s':>8} {'%flops':>8} {'%mem':>8}",
        file=out,
    )
    for r in records:
        if r.get("error"):
            print(f"{r['phase']:<16} cost error: {r['error']}", file=out)
            continue
        dev = (
            f"{r['device_us'] / 1e3:.3f}ms"
            if r.get("device_us") is not None
            else "-"
        )
        share = f"{r['share']:.1%}" if r.get("share") is not None else "-"
        per_call = (
            f"{r['seconds'] * 1e6:.1f}us" if r.get("seconds") else "-"
        )
        gf = f"{r['gflops']:.2f}" if r.get("gflops") is not None else "-"
        gb = f"{r['gbps']:.2f}" if r.get("gbps") is not None else "-"
        print(
            f"{r['phase']:<16} {dev:>10} {share:>6} {per_call:>10} "
            f"{gf:>9} {gb:>8} {_pct(r.get('gflops'), vec):>8} "
            f"{_pct(r.get('gbps'), mem):>8}",
            file=out,
        )


def _steps_from_ledger(path: str, run_id: Optional[str] = None) -> Optional[int]:
    """Stepped updates of ONE run segment — the ``steps`` fields of its
    ok run_loop/chunk spans. The profile bracket covers exactly one
    run's timed loop (``--profile``'s documented scope), but ledgers
    hold many segments (APPEND bench sessions thread one
    ``$HEAT3D_LEDGER`` through every row) — summing across them would
    inflate the step count and corrupt every per-call rate. Default:
    the LAST segment with step spans (the run that just wrote the
    capture); ``run_id`` selects another explicitly."""
    from heat3d_tpu.obs.cli import STEP_SPANS, read_ledger

    per_run: Dict[str, int] = {}
    order: List[str] = []
    for r in read_ledger(path):
        if (
            r.get("kind") == "span"
            and r.get("event") in STEP_SPANS
            and r.get("status") == "ok"
            and isinstance(r.get("steps"), int)
        ):
            rid = str(r.get("run_id"))
            if rid not in per_run:
                order.append(rid)
            per_run[rid] = per_run.get(rid, 0) + r["steps"]
    if run_id is not None:
        return per_run.get(str(run_id)) or None
    return (per_run[order[-1]] if order else 0) or None


# ---- peak calibration -------------------------------------------------------


def calibrate_vpu_peak(
    grid: int = 48,
    iters: int = 3,
    backend: str = "auto",
    cache_path: Optional[str] = None,
) -> Dict[str, Any]:
    """Derive a calibrated VPU vector peak from a measured COMPUTE-BOUND
    phase — the 27pt tb=1 stencil program (the densest tap chain; at
    these arithmetic intensities its achieved GFLOP/s is a floor on the
    sustainable vector rate, which is exactly what the roofline's
    "fraction of peak" should divide by; see --vpu-gops's no-default
    posture) — and store it per chip generation in the shared tuning
    cache (``tune.cache.store_peak``). Returns the record; raises when
    the stencil phase produced no usable flops/seconds (callers print
    the error; calibration is an explicit operator action, not
    fail-soft telemetry)."""
    from heat3d_tpu.core.config import (
        GridConfig,
        MeshConfig,
        Precision,
        RunConfig,
        SolverConfig,
        StencilConfig,
    )
    from heat3d_tpu.tune.cache import chip_generation, store_peak

    cfg = SolverConfig(
        grid=GridConfig.cube(grid),
        stencil=StencilConfig(kind="27pt"),
        mesh=MeshConfig(shape=(1, 1, 1)),
        precision=Precision.fp32(),
        run=RunConfig(num_steps=1),
        backend=backend,
        time_blocking=1,
    )
    records = phase_costs_and_times(cfg, iters=iters)
    stencil = next(
        (r for r in records if r.get("phase") == "stencil"), None
    )
    if not stencil or stencil.get("error"):
        raise RuntimeError(
            "calibration needs the stencil phase program: "
            f"{(stencil or {}).get('error', 'phase missing')}"
        )
    gflops = stencil.get("gflops")
    if not isinstance(gflops, (int, float)) or gflops <= 0:
        raise RuntimeError(
            "stencil phase reported no flops (XLA treats custom calls as "
            "opaque — calibrate with --backend jnp on that platform)"
        )
    chip = chip_generation()
    path = store_peak(
        chip,
        float(gflops),
        path=cache_path,
        source=f"27pt tb=1 {grid}^3 stencil phase, backend={backend}",
    )
    from heat3d_tpu import obs

    obs.get().event(
        "peak_calibrated", chip=chip, vector_gflops=float(gflops),
        grid=grid, backend=backend, path=path,
    )
    return {
        "chip": chip,
        "vector_gflops": float(gflops),
        "seconds": stencil.get("seconds"),
        "path": path,
    }


# ---- row model (promoted from scripts/roofline_check.py) -------------------


def bytes_per_cell_update(row) -> tuple:
    """Traffic model per path (BASELINE.md 'HBM traffic model')."""
    item = 2 if row["dtype"] == "bfloat16" else 4
    tb = row.get("time_blocking", 1)
    mesh = row.get("mesh", [1, 1, 1])
    single = all(m == 1 for m in mesh)
    halo = row.get("halo", "ppermute")
    overlap = row.get("overlap", False)
    # the direct kernels apply on unpadded shards for ppermute transport;
    # DMA transport and tb>2 keep the padded exchange (one extra volume
    # read+write per exchange). Prefer the RESOLVED selection the harness
    # recorded (exact even for HEAT3D_NO_DIRECT A/B rows); derive for
    # legacy rows.
    if row.get("streamk_path"):
        # fused k-sweep streaming kernel (deep tb): the width-k exchange
        # still materializes the padded copy (one r+w per superstep), but
        # the k updates then share ONE sweep of it — vs k sweeps on the
        # plain exchange path
        return 4 * item / tb, f"streamk(tb={tb})"
    direct = row.get("direct_path")
    if direct is None:
        direct = halo == "ppermute" and tb in (1, 2)
    if direct and not (overlap and tb == 2):
        per_update = 2 * item / tb  # one read + one write per sweep of tb
        path = f"direct{'' if tb == 1 else '2'}{'' if single else '+faces'}"
    else:
        # exchange path: padded copy (r+w) once per exchange + sweep per
        # update (tb updates share one exchange)
        per_update = 2 * item + 2 * item / tb
        path = f"exchange(tb={tb})"
    # planned-exchange arm: a partitioned plan ships the SAME boundary
    # bytes as monolithic in sub-block messages (the p50 A/B measures
    # schedule, not traffic) — label the path so partitioned rows are
    # attributable without changing the byte model
    if row.get("halo_plan") == "partitioned":
        path += "+planned-partitioned"
    return per_update, path


def vpu_ops_per_cell_update(row):
    """Vector ops/cell/update of the row's tap chain. Prefers the
    ``chain_ops`` the harness recorded at measurement time (exact even for
    factoring-knob A/B rows); falls back to re-deriving under the CURRENT
    factoring env for rows predating that field. Tap VALUES don't matter
    for the count, only which offsets are nonzero, so nominal
    alpha/dt/spacing are fine for the fallback."""
    if "chain_ops" in row:
        return row["chain_ops"]  # may be None: conv rows run no tap chain
    if row.get("backend") == "conv":
        return None
    from heat3d_tpu.core.stencils import chain_ops_for

    return chain_ops_for(row.get("stencil", "7pt"))


def iter_result_rows(path, kinds=None, start_line=1, stop_line=None):
    """Yield ``(lineno, row)`` bench rows from a results file, tolerating
    log-style line prefixes ("factor_y=0 tb=1: {...}" — the factoring A/B
    stages log their rows rather than appending them to the suite
    record). ``kinds`` filters on the ``bench`` field; the 1-indexed
    ``[start_line, stop_line)`` window is how the regression gate scopes
    "this session's rows" (the ONE parser both this module and
    obs/perf/regress.py read rows through)."""
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            if i < start_line or (stop_line is not None and i >= stop_line):
                continue
            line = line.strip()
            brace = line.find("{")
            if brace < 0:
                continue
            try:
                r = json.loads(line[brace:])
            except json.JSONDecodeError:
                continue
            if isinstance(r, dict) and (
                kinds is None or r.get("bench") in kinds
            ):
                yield i, r


def load_rows(paths: List[str]) -> List[Dict[str, Any]]:
    """Throughput rows from row files (see :func:`iter_result_rows`)."""
    return [
        r
        for results in paths
        for _, r in iter_result_rows(results, kinds=("throughput",))
    ]


def report_rows(rows, hbm_gbps: float, vpu_gops, out=None) -> None:
    out = out or sys.stdout
    print(
        f"{'grid':>6} {'dtype':>8} {'st':>4} {'tb':>2} {'path':>16} "
        f"{'B/cell/upd':>10} {'ops':>4} {'ceiling':>9} {'bind':>4} "
        f"{'measured':>9} {'achieved':>8}",
        file=out,
    )
    for r in rows:
        per_update, path = bytes_per_cell_update(r)
        bw_ceiling = hbm_gbps / per_update  # Gcell/s/chip
        ops = vpu_ops_per_cell_update(r)
        ceiling, bind = bw_ceiling, "hbm"
        # ops is None for conv rows (one XLA conv op, no tap chain): the
        # VPU model doesn't apply — report against the HBM ceiling only
        if vpu_gops is not None and ops is not None:
            vpu_ceiling = vpu_gops / ops
            if vpu_ceiling < bw_ceiling:
                ceiling, bind = vpu_ceiling, "vpu"
        meas = r["gcell_per_sec_per_chip"]
        grid = (
            r["grid"][0]
            if len(set(r["grid"])) == 1
            else "x".join(map(str, r["grid"]))
        )
        flag = " (RTT!)" if r.get("rtt_dominated") else ""
        # compute dtype doesn't change HBM traffic (storage dtype does),
        # but label it so bf16-compute A/B rows are tellable apart
        if r.get("compute_dtype", "float32") != "float32":
            flag = " (c=bf16)" + flag
        print(
            f"{grid:>6} {r['dtype']:>8} {r.get('stencil', '7pt'):>4} "
            f"{r.get('time_blocking', 1):>2} {path:>16} "
            f"{per_update:>10.1f} {'n/a' if ops is None else ops:>4} "
            f"{ceiling:>9.1f} {bind:>4} "
            f"{meas:>9.2f} {meas / ceiling:>7.1%}{flag}",
            file=out,
        )


def fit_op_cost(rows, out=None) -> None:
    """Least-squares time/cell/update = a + b*ops over rows that differ
    ONLY in their emitted chain (same grid/dtype/tb/path). A good linear
    fit is direct evidence the kernels are compute-bound in chain ops;
    a >> b would instead indict fixed per-cell cost (assembly/shifts)."""
    from collections import defaultdict

    out = out or sys.stdout
    groups = defaultdict(list)
    for r in rows:
        if r.get("rtt_dominated"):
            continue
        _, path = bytes_per_cell_update(r)
        # compute_dtype/backend in the key: a bf16-compute A/B row has the
        # same chain_ops as its fp32-compute twin but different per-op
        # cost — pooling them would corrupt the fit silently
        key = (
            tuple(r["grid"]), r["dtype"],
            r.get("compute_dtype", "float32"), r.get("backend", "auto"),
            r.get("time_blocking", 1), path,
        )
        ops = vpu_ops_per_cell_update(r)
        if ops is None:
            continue  # conv rows: no tap chain, nothing to fit against
        ns_per_cell = 1.0 / r["gcell_per_sec_per_chip"]  # ns/cell/update
        groups[key].append((ops, ns_per_cell))
    printed = False
    for key, pts in sorted(groups.items()):
        by_ops = {}
        for ops, t in pts:
            by_ops.setdefault(ops, []).append(t)
        if len(by_ops) < 2:
            continue
        xs, ys = zip(*((o, min(ts)) for o, ts in sorted(by_ops.items())))
        n = len(xs)
        mx, my = sum(xs) / n, sum(ys) / n
        sxx = sum((x - mx) ** 2 for x in xs)
        b = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx
        a = my - b * mx
        if n >= 3:
            ss_res = sum((y - (a + b * x)) ** 2 for x, y in zip(xs, ys))
            ss_tot = sum((y - my) ** 2 for y in ys) or 1e-30
            fit_q = f"R^2={1 - ss_res / ss_tot:.3f}"
        else:
            # a line through 2 points always "fits"; don't dress that up
            fit_q = "2-point (no linearity evidence)"
        grid, dtype, cdtype, backend, tb, path = key
        cflag = "" if cdtype == "float32" else f" c={cdtype}"
        glabel = (
            f"{grid[0]}^3"
            if len(set(grid)) == 1
            else "x".join(map(str, grid))
        )
        if b <= 0:
            # higher-ops rows timed FASTER: noise or a confound — that's
            # anti-evidence of compute-boundedness, not an infinite rate
            verdict = "non-positive slope — unfittable/not compute-bound"
        else:
            verdict = (
                f"marginal {1.0 / b:.0f} Gop/s, "
                f"fixed {a / (a + b * xs[0]):.0%} of the {xs[0]}-op chain"
            )
        print(
            f"\nfit {glabel} {dtype}{cflag} tb={tb} {path}: "
            f"t/cell = {a:.3f} + {b:.4f}*ops ns "
            f"({verdict}, {fit_q}, points={list(by_ops)})",
            file=out,
        )
        printed = True
    if not printed:
        print(
            "\nfit: no group has >=2 distinct chain_ops values "
            "(need factoring A/B rows, e.g. HEAT3D_FACTOR_Y=0)",
            file=sys.stderr,
        )


# ---- CLI -------------------------------------------------------------------


def _cfg_from_args(args):
    from heat3d_tpu.core.config import (
        GridConfig,
        MeshConfig,
        Precision,
        RunConfig,
        SolverConfig,
        StencilConfig,
    )

    return SolverConfig(
        grid=GridConfig.cube(args.grid),
        stencil=StencilConfig(kind=args.stencil),
        mesh=MeshConfig(shape=(1, 1, 1)),
        precision=Precision.bf16() if args.dtype == "bf16" else Precision.fp32(),
        run=RunConfig(num_steps=1),
        backend=args.backend,
        time_blocking=args.time_blocking,
    )


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="heat3d obs roofline",
        description="Achieved-vs-peak attribution. With row files: the "
        "analytic traffic/op-cost model over measured bench rows "
        "(scripts/roofline_check.py compatible). Without: compile this "
        "config's phase programs, read XLA's cost_analysis, time them, "
        "and print the per-phase achieved-vs-peak table (works on CPU).",
    )
    ap.add_argument(
        "results", nargs="*",
        help="row files (bench_results.jsonl / extracted A/B rows); "
        "empty selects the live per-phase mode",
    )
    ap.add_argument("--hbm-gbps", type=float, default=819.0,
                    help="chip HBM bandwidth (GB/s); v5e ~819, v5p ~2765")
    ap.add_argument("--vpu-gops", type=float, default=None,
                    help="VPU vector throughput (Gop/s, one op = one "
                    "full-width FMA or add); calibrate from a measured "
                    "compute-bound row — no default on purpose")
    ap.add_argument("--fit", action="store_true",
                    help="(row mode) fit time/cell/update = a + b*ops per "
                    "config group — linearity in ops IS the compute-bound "
                    "evidence")
    ap.add_argument("--grid", type=int, default=32,
                    help="(live mode) cube edge")
    ap.add_argument("--stencil", choices=["7pt", "27pt"], default="7pt")
    ap.add_argument("--dtype", choices=["fp32", "bf16"], default="fp32")
    ap.add_argument("--backend",
                    choices=["auto", "jnp", "pallas", "conv"], default="auto")
    ap.add_argument("--time-blocking", type=int, default=1)
    ap.add_argument("--iters", type=int, default=3,
                    help="(live mode) timing iterations per phase")
    ap.add_argument("--from-profile", default=None, metavar="DIR",
                    help="join MEASURED per-phase device times from a "
                    "--profile capture (dir or .xplane.pb) onto this "
                    "config's cost_analysis model — achieved-vs-peak from "
                    "device time, not span wall-clock (needs the config "
                    "flags to match the profiled run)")
    ap.add_argument("--steps", type=int, default=None,
                    help="(with --from-profile) updates the capture "
                    "covers; default: reconstructed from --ledger's "
                    "run_loop/chunk spans, else 1 (per-call rates then "
                    "read as per-capture)")
    ap.add_argument("--ledger", default=None,
                    help="(with --from-profile) run ledger of the "
                    "profiled run — supplies the step count (the LAST "
                    "run segment with step spans; --run selects another)")
    ap.add_argument("--run", default=None, metavar="RUN_ID",
                    help="(with --from-profile --ledger) the ledger run "
                    "segment the capture belongs to")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure a compute-bound 27pt tb=1 stencil phase "
                    "and cache its achieved GFLOP/s as this chip "
                    "generation's VPU peak (shared tuning-cache store; "
                    "later reports divide by it — ROADMAP 'calibrated "
                    "peak specs')")
    ap.add_argument("--cache", default=None,
                    help="(with --calibrate) tuning-cache store path "
                    "(default $HEAT3D_TUNE_CACHE)")
    ap.add_argument("--json", action="store_true",
                    help="(live mode) machine-readable records instead of "
                    "the table")
    args = ap.parse_args(argv)

    if args.calibrate:
        try:
            rec = calibrate_vpu_peak(
                grid=args.grid,
                iters=args.iters,
                backend=args.backend,
                cache_path=args.cache,
            )
        except Exception as e:  # noqa: BLE001 - report, don't traceback
            print(f"roofline --calibrate: {e}", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(rec))
        else:
            print(
                f"calibrated {rec['chip']}: vector peak "
                f"{rec['vector_gflops']:.2f} GFLOP/s "
                f"(stored in {rec['path']})"
            )
        return 0

    if args.from_profile:
        import jax

        from heat3d_tpu.obs.perf.timeline import profile_phase_totals

        try:
            phase_us, artifact = profile_phase_totals(args.from_profile)
        except (RuntimeError, OSError) as e:
            print(f"roofline --from-profile: {e}", file=sys.stderr)
            return 1
        steps = args.steps
        if steps is None and args.ledger:
            try:
                steps = _steps_from_ledger(args.ledger, run_id=args.run)
            except OSError as e:
                print(
                    f"roofline --from-profile: cannot read ledger: {e}",
                    file=sys.stderr,
                )
                return 2
            if steps is None:
                which = (
                    f"run {args.run}" if args.run else "any run segment"
                )
                print(
                    f"roofline --from-profile: no ok step spans for "
                    f"{which} in {args.ledger} — treating the capture as "
                    "ONE update (rates read as per-capture)",
                    file=sys.stderr,
                )
        elif steps is None:
            print(
                "roofline --from-profile: no --steps/--ledger — treating "
                "the capture as ONE update (rates read as per-capture)",
                file=sys.stderr,
            )
        steps = steps or 1
        cfg = _cfg_from_args(args)
        records = profile_join_records(cfg, phase_us, steps)
        platform = jax.default_backend()
        if args.json:
            print(
                json.dumps(
                    {
                        "platform": platform,
                        "artifact": artifact,
                        "steps": steps,
                        "phases": records,
                    }
                )
            )
        else:
            print_profile_table(
                cfg, records, jax.devices()[0].device_kind, steps, artifact
            )
        return 0

    if args.results:
        rows = load_rows(args.results)
        if not rows:
            print("no throughput rows found", file=sys.stderr)
            return 1
        report_rows(rows, args.hbm_gbps, args.vpu_gops)
        if args.fit:
            fit_op_cost(rows)
        return 0

    import jax

    cfg = _cfg_from_args(args)
    records = phase_costs_and_times(cfg, iters=args.iters)
    platform = jax.default_backend()
    if args.json:
        print(json.dumps({"platform": platform, "phases": records}))
    else:
        print_live_table(cfg, records, jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
