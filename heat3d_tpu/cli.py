"""Command-line driver — the reference's main() + mpirun surface.

Reference parity (SURVEY.md §2 C4/C12, §3.1): the reference is launched as
``mpirun -np P ./heat3d NX NY NZ NITER [Px Py Pz]``. Equivalent here::

    heat3d --grid 1024 --steps 1000 --mesh 4 1 1           # config 2 (slab)
    heat3d --grid 2048 --mesh 2 2 2                        # config 3
    heat3d --grid 4096 --stencil 27pt --mesh 4 4 4         # config 4
    heat3d --grid 4096 --dtype bf16 --mesh 4 4 4           # config 5
    heat3d --grid 128 --golden-check                       # config 1

One JAX device per v5p chip (two TensorCores): v5p-8 is 4 devices, v5p-128
is 64. One process per host on a pod slice; ``jax.distributed`` replaces
mpirun (BASELINE.json north star). All output is JSON on stdout, human logs on
stderr.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List, Optional

import jax
import numpy as np

from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    RunConfig,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu import obs
from heat3d_tpu.parallel import distributed
from heat3d_tpu.utils.logging import emit_json, get_logger
from heat3d_tpu.utils.timing import force_sync, maybe_profile

log = get_logger("heat3d.cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="heat3d",
        description="TPU-native 3D heat-equation solver "
        "(capabilities of the CUDA-aware-MPI reference, re-designed for TPU)",
    )
    p.add_argument(
        "--grid", type=int, nargs="+", default=[128],
        help="global interior grid: one int (cube) or three (NX NY NZ)",
    )
    p.add_argument("--spacing", type=float, nargs=3, default=[1.0, 1.0, 1.0])
    p.add_argument("--alpha", type=float, default=1.0, help="thermal diffusivity")
    p.add_argument("--dt", type=float, default=None, help="time step (default 0.9x stable)")
    p.add_argument("--stencil", choices=["7pt", "27pt"], default="7pt")
    # equation-family choices come from the LIVE registry (heat3d_tpu.eqn)
    # — the eqn-registry lint (ANL521) cross-checks this stays true
    from heat3d_tpu.eqn import FAMILIES

    p.add_argument(
        "--equation", choices=sorted(FAMILIES), default="heat",
        help="equation family the tap compiler lowers onto the stencil "
        "footprint (heat3d eqn list; docs/EQUATIONS.md). 'heat' is the "
        "legacy path, spec-authored",
    )
    p.add_argument(
        "--eq-param", action="append", default=[], metavar="NAME=VALUE",
        help="equation-family parameter override (repeatable), e.g. "
        "--eq-param vx=2.0; defaults per `heat3d eqn show FAMILY`",
    )
    from heat3d_tpu.core.config import INTEGRATORS

    p.add_argument(
        "--integrator", choices=list(INTEGRATORS), default="explicit-euler",
        help="time integrator (docs/INTEGRATORS.md): 'explicit-euler' "
        "(default, the tuned explicit route), 'leapfrog' (the wave "
        "family's two-level carry), 'implicit-cg' (matrix-free CG "
        "backward Euler — unconditionally stable, dt may exceed the "
        "explicit CFL bound; HEAT3D_CG_MAX_ITERS/HEAT3D_CG_TOL tune "
        "the solve)",
    )
    p.add_argument("--bc", choices=["dirichlet", "periodic"], default="dirichlet")
    p.add_argument("--bc-value", type=float, default=0.0)
    p.add_argument(
        "--mesh", type=int, nargs="+", default=None,
        help="device mesh Px Py Pz (one int = 1D slab; default: all devices, balanced 3D)",
    )
    p.add_argument("--dtype", choices=["fp32", "bf16"], default="fp32",
                   help="field storage dtype; residual always accumulates fp32")
    p.add_argument("--compute-dtype", choices=["fp32", "bf16"], default="fp32",
                   help="stencil compute dtype (bf16 halves VPU op width; "
                   "A/B knob for whether bf16 throughput is VPU- or "
                   "assembly-bound); residual still accumulates fp32")
    p.add_argument("--backend", choices=["auto", "jnp", "pallas", "conv"], default="auto")
    p.add_argument(
        "--dump-slice", nargs=3, metavar=("AXIS", "INDEX", "PATH"),
        default=None,
        help="after the run, save one global 2D plane as .npy: axis x|y|z "
        "(or 0|1|2), global index along it, output path — the reference "
        "class's visualization dump",
    )
    p.add_argument(
        "--dump-vtk", default=None, metavar="PATH",
        help="after the run, write the final field as legacy binary VTK "
        "STRUCTURED_POINTS (ParaView/VisIt — the reference class's "
        "visualization dump). Gathers the full field to the coordinator: "
        "meant for inspection-sized grids; use --dump-slice for planes of "
        "pod-scale fields",
    )
    p.add_argument("--overlap", action="store_true",
                   help="overlap halo exchange with interior compute "
                   "(interior/boundary split step)")
    p.add_argument("--halo", choices=["ppermute", "dma", "auto"],
                   default="ppermute",
                   help="ghost-exchange transport: XLA collective-permute, "
                   "Pallas remote-DMA kernels (TPU only), or 'auto' — "
                   "resolve through the tuning cache (heat3d tune; "
                   "docs/TUNING.md) with a ppermute fallback")
    p.add_argument("--halo-order", choices=["axis", "pairwise"],
                   default="axis",
                   help="halo-exchange ordering: 'axis' (x->y->z, "
                   "corner-propagating — required by 27pt) or 'pairwise' "
                   "(all six face permutes concurrent, stagger-tolerant; "
                   "7pt only — the tuner A/Bs the two)")
    p.add_argument("--halo-plan", choices=["monolithic", "partitioned", "auto"],
                   default="monolithic",
                   help="exchange-plan mode (parallel/plan.py): "
                   "'monolithic' (one collective per face), 'partitioned' "
                   "(each face ships as early-bird sub-blocks — more, "
                   "smaller messages overlapped with compute; "
                   "value-identical, pins the exchange path), or 'auto' "
                   "(resolve through the tuning cache; docs/TUNING.md)")
    p.add_argument("--time-blocking", type=int, default=1,
                   help="stencil updates per ghost exchange in the "
                   "fixed-step loop (k>1 = temporal blocking: width-k "
                   "halos, 1/k the messages; k=2 also fuses both updates "
                   "into one HBM sweep; k=0 = auto via the tuning cache; "
                   "convergence mode --tol checks the "
                   "residual every step and always runs single updates)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--init", default="hot-cube", help="hot-cube | gaussian | random")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tol", type=float, default=None,
                   help="run to convergence at this L2 residual instead of fixed steps")
    p.add_argument("--residual-every", type=int, default=0,
                   help="report residual every K steps (0 = only at end)")
    p.add_argument("--golden-check", action="store_true",
                   help="compare against the NumPy golden model (config 1 oracle)")
    p.add_argument("--checkpoint", default=None, help="checkpoint directory")
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--resume", action="store_true", help="resume from --checkpoint")
    p.add_argument(
        "--supervise", action="store_true",
        help="run under the resilience supervisor: checkpoint generations "
        "every --checkpoint-every steps into --checkpoint, watchdog the "
        "backend, auto-resume from the last good generation (quarantining "
        "corrupt ones). --steps is then the TARGET GLOBAL step: relaunching "
        "the same command after a kill finishes the run. See "
        "docs/RESILIENCE.md",
    )
    p.add_argument(
        "--watchdog", type=float, default=None, metavar="SECONDS",
        help="(with --supervise) per-chunk wall-clock budget; an overrun "
        "triggers a backend probe and, if it fails, checkpoint-resume "
        "recovery",
    )
    p.add_argument(
        "--max-recoveries", type=int, default=3,
        help="(with --supervise) give up after this many survived failures",
    )
    p.add_argument(
        "--heal-mode", choices=["wait", "elastic", "auto"], default=None,
        help="(with --supervise) what a confirmed backend loss means: "
        "'wait' = probe until the ORIGINAL backend returns (PR 1 "
        "behavior; the HEAT3D_HEAL_DEADLINE_S deadline re-raises), "
        "'elastic' = probe the DEVICE SET and re-plan the moment any "
        "survivors answer (never waits out a platform heal): "
        "re-factorize the mesh over the survivors, re-stitch the newest "
        "generation onto the degraded mesh, continue "
        "(docs/RESILIENCE.md \"Elastic degradation\"), 'auto' = wait "
        "first, degrade when the heal deadline expires or the backend "
        "heals smaller. Default $HEAT3D_HEAL_MODE, else wait",
    )
    p.add_argument(
        "--reexpand", action="store_true",
        help="(with --supervise --heal-mode elastic|auto) opt-in "
        "re-expand: while degraded, probe at each checkpoint boundary "
        "and re-factorize back onto the original mesh when full "
        "capacity returns (degraded_mode_exit ledger event)",
    )
    p.add_argument("--profile", "--profile-dir", dest="profile_dir",
                   default=None, metavar="DIR",
                   help="capture a jax.profiler trace (TensorBoard/"
                   "Perfetto) of the timed region into DIR; the artifact "
                   "path and the capture overhead are recorded into the "
                   "run ledger as a profile_capture event "
                   "(docs/OBSERVABILITY.md). --profile-dir is the legacy "
                   "spelling")
    p.add_argument(
        "--ledger", default=None, metavar="PATH",
        help="append the run ledger (JSONL span/event stream) here; "
        "defaults to $HEAT3D_LEDGER; inspect with `heat3d obs summary "
        "PATH` (docs/OBSERVABILITY.md)",
    )
    p.add_argument("--coordinator", default=None, help="multi-host coordinator addr:port")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    return p


def config_from_args(args) -> SolverConfig:
    grid_shape = tuple(args.grid * 3 if len(args.grid) == 1 else args.grid)
    if len(grid_shape) != 3:
        raise SystemExit("--grid takes 1 or 3 ints")
    if args.mesh is None:
        mesh = MeshConfig.for_devices(len(jax.devices()))
    elif len(args.mesh) == 1:
        mesh = MeshConfig.slab(args.mesh[0])
    elif len(args.mesh) == 3:
        mesh = MeshConfig(shape=tuple(args.mesh))
    else:
        raise SystemExit("--mesh takes 1 or 3 ints")
    return SolverConfig(
        grid=GridConfig(
            shape=grid_shape,
            spacing=tuple(args.spacing),
            alpha=args.alpha,
            dt=args.dt,
        ),
        stencil=StencilConfig(
            kind=args.stencil,
            bc=BoundaryCondition(args.bc),
            bc_value=args.bc_value,
        ),
        mesh=mesh,
        precision=Precision(
            storage="bfloat16" if args.dtype == "bf16" else "float32",
            compute="bfloat16"
            if getattr(args, "compute_dtype", "fp32") == "bf16"
            else "float32",
        ),
        run=RunConfig(
            num_steps=args.steps,
            tolerance=args.tol,
            seed=args.seed,
            checkpoint_path=args.checkpoint,
            checkpoint_every=args.checkpoint_every,
            residual_every=args.residual_every,
            profile_dir=args.profile_dir,
        ),
        backend=args.backend,
        overlap=args.overlap,
        halo=args.halo,
        time_blocking=args.time_blocking,
        halo_order=args.halo_order,
        halo_plan=args.halo_plan,
        equation=getattr(args, "equation", "heat"),
        eq_params=_parse_eq_params(getattr(args, "eq_param", [])),
        integrator=getattr(args, "integrator", "explicit-euler"),
    )


def _parse_eq_params(pairs) -> tuple:
    from heat3d_tpu.eqn.cli import parse_eq_params

    return parse_eq_params(pairs)


def main(argv: Optional[List[str]] = None) -> int:
    # `heat3d obs ...` — the ledger-inspection surface (summary/tail/check)
    # lives in its own subcommand parser, dispatched before the solver
    # parser ever sees the argv
    argv_l = list(sys.argv[1:] if argv is None else argv)
    if argv_l and argv_l[0] == "obs":
        from heat3d_tpu.obs.cli import main as obs_main

        return obs_main(argv_l[1:])
    # `heat3d tune ...` — the autotuner surface (run/show/apply/clear/lint),
    # dispatched the same way as `obs` (docs/TUNING.md)
    if argv_l and argv_l[0] == "tune":
        from heat3d_tpu.tune.cli import main as tune_main

        return tune_main(argv_l[1:])
    # `heat3d lint ...` — the static-analysis surface (docs/ANALYSIS.md):
    # SPMD-safety + invariant checkers, rc 1 only on error severity
    if argv_l and argv_l[0] == "lint":
        from heat3d_tpu.analysis.cli import main as lint_main

        return lint_main(argv_l[1:])
    # `heat3d serve ...` — the batched scenario engine's front-end
    # (queue scenario requests -> shape-bucketed batches -> streamed
    # results; docs/SERVING.md), dispatched like `obs`/`tune`
    if argv_l and argv_l[0] == "serve":
        from heat3d_tpu.serve.cli import main as serve_main

        return serve_main(argv_l[1:])
    # `heat3d eqn ...` — the declarative equation registry's inspection
    # surface (list/show; docs/EQUATIONS.md), dispatched like `obs`/`tune`
    if argv_l and argv_l[0] == "eqn":
        from heat3d_tpu.eqn.cli import main as eqn_main

        return eqn_main(argv_l[1:])
    from heat3d_tpu.resilience.faults import install_sigterm_exit
    from heat3d_tpu.utils.compile_cache import enable_compile_cache

    install_sigterm_exit()
    enable_compile_cache()
    try:
        rc = _main(argv_l)
    except (ValueError, NotImplementedError) as e:
        # Config/capability errors (indivisible periodic meshes, halo='dma'
        # off-TPU, time_blocking constraints, ...) exit cleanly instead of
        # dumping a traceback — the reference's argv validation, done right.
        print(f"heat3d: error: {e}", file=sys.stderr)
        obs.deactivate(rc=2, error=f"{type(e).__name__}: {str(e)[:200]}")
        return 2
    except BaseException as e:
        # the ledger must record HOW the run ended even on crashes and
        # SIGTERM (SystemExit): close-with-error, then re-raise
        obs.deactivate(rc=1, error=f"{type(e).__name__}: {str(e)[:200]}")
        raise
    obs.export_at_exit()
    obs.deactivate(rc=rc)
    return rc


def _main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    distributed.initialize(args.coordinator, args.num_processes, args.process_id)
    # activation order: after distributed.initialize (so the ledger pins
    # the real process index), before config validation (so a run dying
    # on a bad config still leaves a ledger_open + rc=2 close)
    ledger = obs.activate(args.ledger, meta={"entry": "solve"})
    cfg = config_from_args(args)
    # tuning-cache resolution of the auto knobs (backend='auto',
    # halo='auto', time_blocking=0) BEFORE run_start, so the ledger
    # records the config that actually runs; the hit/miss/stale event
    # lands just above it (heat3d_tpu.tune.cache — fails soft to the
    # static defaults, never the run)
    from heat3d_tpu.tune.cache import resolve_config

    cfg = resolve_config(cfg)
    if args.supervise:
        # resolve the env default NOW so run_start records the heal mode
        # that will actually govern (the same rule as the auto knobs
        # above); a bad HEAT3D_HEAL_MODE fails here, in ms, not mid-outage
        from heat3d_tpu.resilience.elastic import resolve_heal_mode

        args.heal_mode = resolve_heal_mode(args.heal_mode)
    ledger.event(
        "run_start",
        grid=list(cfg.grid.shape),
        stencil=cfg.stencil.kind,
        equation=cfg.equation,
        integrator=cfg.integrator,
        mesh=list(cfg.mesh.shape),
        dtype=cfg.precision.storage,
        backend=cfg.backend,
        halo=cfg.halo,
        halo_order=cfg.halo_order,
        halo_plan=cfg.halo_plan,
        overlap=cfg.overlap,
        time_blocking=cfg.time_blocking,
        steps=cfg.run.num_steps,
        supervise=bool(args.supervise),
        heal_mode=args.heal_mode,
    )

    dump_slice = None
    if args.dump_slice:
        # validate BEFORE the run so a bad flag fails in ms, not hours
        axis_s, index_s, dump_path = args.dump_slice
        axis = {"x": 0, "y": 1, "z": 2}.get(axis_s.lower())
        if axis is None:
            try:
                axis = int(axis_s)
            except ValueError:
                raise ValueError(
                    f"--dump-slice axis must be x|y|z or 0|1|2, got {axis_s!r}"
                ) from None
        if not 0 <= axis <= 2:
            raise ValueError(f"--dump-slice axis must be 0..2, got {axis}")
        try:
            index = int(index_s)
        except ValueError:
            raise ValueError(
                f"--dump-slice index must be an int, got {index_s!r}"
            ) from None
        if not 0 <= index < cfg.grid.shape[axis]:
            raise ValueError(
                f"--dump-slice index {index} outside grid extent "
                f"{cfg.grid.shape[axis]} on axis {axis}"
            )
        dump_slice = (axis, index, dump_path)

    if args.dump_vtk and distributed.is_coordinator():
        # validate writability BEFORE the run (same rule as --dump-slice):
        # a bad path must fail in ms, not after hours + a pod-wide gather
        try:
            with open(args.dump_vtk, "ab"):
                pass
        except OSError as e:
            raise ValueError(f"--dump-vtk path not writable: {e}") from None

    from heat3d_tpu.models.heat3d import HeatSolver3D

    log.info(
        "grid=%s stencil=%s mesh=%s dtype=%s backend=%s devices=%d",
        cfg.grid.shape, cfg.stencil.kind, cfg.mesh.shape,
        cfg.precision.storage, cfg.backend, len(jax.devices()),
    )
    if (
        cfg.run.tolerance is not None
        and cfg.time_blocking != 1
        and cfg.run.residual_every <= 1
    ):
        log.warning(
            "--time-blocking is inactive in convergence mode without "
            "--residual-every K>1: a per-step residual check forces single "
            "updates. Pass --residual-every K with K-1 a multiple of the "
            "blocking factor (the K-1 updates between residual checks run "
            "as supersteps) to recover temporal blocking + the copy-free "
            "carry"
        )
    solver = HeatSolver3D(cfg)

    # cost-analysis provenance: one step_cost ledger event (XLA-counted
    # FLOPs/bytes of the step executable) so `obs summary` can print the
    # run's achieved-vs-peak line. Telemetry fails soft, never the run —
    # the guard covers import-time drift in the perf package too (the
    # same posture bench.harness takes on its row cost fields).
    try:
        from heat3d_tpu.obs.perf.roofline import record_step_cost

        record_step_cost(solver)
    except Exception as e:  # noqa: BLE001 - telemetry fails soft
        log.warning("step_cost telemetry unavailable: %s", e)

    if args.supervise:
        return _main_supervised(args, cfg, solver, dump_slice)

    start_step = 0
    if args.resume and args.checkpoint:
        u, start_step = solver.load_checkpoint(args.checkpoint)
        log.info("resumed from %s at step %d", args.checkpoint, start_step)
    else:
        u = solver.init_state(args.init)

    profile_cm = maybe_profile(cfg.run.profile_dir)
    profile_cm.__enter__()
    try:
        u, elapsed, steps_done, residual, warmup_s = _timed_run(
            args, cfg, solver, u, start_step
        )
    finally:
        # exception-safe: a failed run must still close (and flush) the
        # profiler trace instead of losing it; the bracket covers exactly
        # warmup + the timed loop, as before (checkpoint/report IO stays
        # out of the trace)
        profile_cm.__exit__(None, None, None)

    if args.checkpoint:
        solver.save_checkpoint(args.checkpoint, u, steps_done)

    return _finish(
        args, cfg, solver, u, elapsed, steps_done, start_step, residual,
        dump_slice, extra_summary={"warmup_s": warmup_s},
    )


def _chunks(args, total: int):
    """The timed loop's dispatch groups, ``(n, want_residual)`` each:
    advance to the next reporting boundary — a residual point, a
    checkpoint point, or the end. The final step is always a residual
    step, so exactly ``total`` updates run — no overshoot."""
    done = 0
    while done < total:
        boundaries = [total]
        if args.residual_every:
            boundaries.append(
                (done // args.residual_every + 1) * args.residual_every
            )
        if args.checkpoint and args.checkpoint_every:
            boundaries.append(
                (done // args.checkpoint_every + 1) * args.checkpoint_every
            )
        nxt = min(boundaries)
        yield nxt - done, bool(
            nxt == total
            or (args.residual_every and nxt % args.residual_every == 0)
        )
        done = nxt


def _timed_run(args, cfg, solver, u, start_step):
    """Warmup + the timed stepping loop; returns
    ``(u, elapsed, steps_done, residual, warmup_s)`` — ``warmup_s`` is
    the compile plus the warmup executions."""
    # Warm up the executables this mode will use, outside the timed window
    # (SURVEY.md §3.5: warmup iterations excluded). The dummy field is built
    # per-shard (zeros callback) so no process ever materializes the full
    # global array — same rule as init_state.
    total = cfg.run.num_steps
    t_warm = time.perf_counter()
    with obs.get().span("warmup"):
        _dummy = solver.zeros_state

        if cfg.run.tolerance is not None:
            # while_loop cond is false at max_steps=0: compiles without
            # advancing
            solver.run_to_convergence(_dummy(), tol=1.0, max_steps=0)
        else:
            # the fixed-step loop has one executable per write structure
            # of its step count (HeatSolver3D.warm_up)
            for n in {n - 1 if want_residual else n
                      for n, want_residual in _chunks(args, total)}:
                solver.warm_up(n)
            jax.block_until_ready(solver.step_with_residual(_dummy()))
        force_sync(u)
    warmup_s = time.perf_counter() - t_warm

    residual = None
    # One span for the whole timed region ("run_loop", with a `steps`
    # field): the plain loop syncs the device only at the END, so per-chunk
    # sub-spans would record async dispatch time, not execution — the
    # honest per-step latency here is elapsed/steps, observed once. (The
    # SUPERVISED loop force_syncs every chunk and gets real per-chunk
    # spans — see resilience.supervisor.)
    with obs.get().span("run_loop", step_start=start_step) as run_span:
        t0 = time.perf_counter()
        if cfg.run.tolerance is not None:
            result = solver.run_to_convergence(
                u, tol=cfg.run.tolerance, max_steps=cfg.run.num_steps
            )
            u, residual = result.u, result.residual
            done = result.steps
        else:
            done = 0
            for n, want_residual in _chunks(args, total):
                nxt = done + n
                if want_residual:
                    if n > 1:
                        u = solver.run(u, n - 1)
                    u, r2 = solver.step_with_residual(u)
                    residual = float(np.sqrt(np.float64(r2)))
                    log.info(
                        "step %d residual %.6e", start_step + nxt, residual
                    )
                    obs.get().event(
                        "residual",
                        step=start_step + nxt,
                        residual_l2=residual,
                    )
                else:
                    u = solver.run(u, n)
                done = nxt
                if (
                    args.checkpoint
                    and args.checkpoint_every
                    and done % args.checkpoint_every == 0
                    and done < total  # final checkpoint written below
                ):
                    solver.save_checkpoint(
                        args.checkpoint, u, start_step + done
                    )
        force_sync(u)
        elapsed = time.perf_counter() - t0
        run_span.add(steps=done, elapsed_s=elapsed)
    if done:
        obs.REGISTRY.histogram(
            "step_latency_seconds",
            "per-step wall latency (chunk dur / steps)",
        ).observe(elapsed / done)
    return u, elapsed, start_step + done, residual, warmup_s


def _main_supervised(args, cfg, solver, dump_slice) -> int:
    """The --supervise path: the supervisor owns init/resume, checkpoint
    cadence, and recovery; this wrapper owns arg plumbing + reporting."""
    if not args.checkpoint:
        raise ValueError("--supervise requires --checkpoint DIR")
    import os

    from heat3d_tpu.resilience.supervisor import generation_dirs
    from heat3d_tpu.utils import checkpoint as ckpt

    if os.path.exists(
        os.path.join(args.checkpoint, ckpt.MANIFEST)
    ) and not generation_dirs(args.checkpoint):
        # a plain (flat) checkpoint lives here; the supervisor only scans
        # gen-* generations, so proceeding would silently restart at step
        # 0 and orphan the user's progress
        raise ValueError(
            f"--checkpoint {args.checkpoint} holds a plain checkpoint, "
            "not supervised generations — finish it with --resume "
            "(without --supervise), or point --supervise at a fresh "
            "directory"
        )
    if args.resume:
        log.info(
            "--resume is implied by --supervise (auto-resumes from the "
            "newest good generation)"
        )
    if jax.process_count() > 1:
        # single-controller only (supervisor.py docstring): per-process
        # supervisors would race quarantine renames and generation prunes,
        # and desynchronize the collective step loop on recovery
        raise ValueError(
            "--supervise is single-controller: multi-host launches must "
            "supervise from the launcher (relaunch-on-exit resumes from "
            "the shared generations) — drop --supervise here"
        )
    if cfg.run.tolerance is not None:
        raise ValueError(
            "--supervise drives the fixed-step loop; convergence mode "
            "(--tol) is not supervised yet — drop one of the two flags"
        )
    if cfg.run.residual_every:
        # don't silently eat a flag the plain loop honors: supervised
        # chunks land on checkpoint boundaries only; the run still
        # reports its final residual
        log.warning(
            "--residual-every is not supported under --supervise yet; "
            "only the final residual is reported"
        )
    if not args.checkpoint_every:
        # legal (auto-resume + final-checkpoint quarantine still work),
        # but the whole run is then ONE chunk: a mid-run kill restarts
        # from step 0 and any --watchdog budget covers the full run
        log.warning(
            "--supervise without --checkpoint-every K writes no mid-run "
            "generations: an outage loses the whole run, not K steps"
        )
    t0 = time.perf_counter()
    with maybe_profile(cfg.run.profile_dir):
        result = solver.run_supervised(
            total_steps=cfg.run.num_steps,
            ckpt_root=args.checkpoint,
            checkpoint_every=args.checkpoint_every,
            watchdog_s=args.watchdog,
            max_recoveries=args.max_recoveries,
            init=args.init,
            heal_mode=args.heal_mode,
            reexpand=args.reexpand,
            # the platform this run STARTED on: without it, a probe child
            # whose jax silently falls back to CPU would classify a real
            # TPU outage as "backend alive" (re-raise instead of recover)
            # and a heal-wait would accept CPU instantly. In-process
            # recovery stays same-platform; the TPU->CPU cross-mesh
            # resume is the RELAUNCH path (generations on disk).
            want_platform=jax.default_backend(),
        )
    elapsed = time.perf_counter() - t0
    # Honest timing: heal waits are SLEEP, not work — leave them out of
    # the throughput denominator (each recovery's wait is itemized in the
    # supervised record). What remains still includes compile and any
    # redone steps: supervised runs are a resilience surface, not a
    # benchmark — the flag below keeps the number from being mistaken
    # for a calibrated bench row downstream.
    heal_s = sum(r.heal_wait_s for r in result.recoveries)
    busy = max(elapsed - heal_s, 1e-9)
    if result.residual is not None:
        log.info(
            "step %d residual %.6e", result.steps_done, result.residual
        )
    supervised_record = result.to_record()
    supervised_record["timing_note"] = (
        "seconds excludes heal waits but includes compile and redone "
        "steps; not comparable to bench rows"
    )
    # report through the solver that PRODUCED u: a recovery may have
    # rebuilt it (cross-mesh heal), and gather/slice on the stale
    # instance would bind the dead mesh. Same rule for the CONFIG: an
    # elastic re-factorization changed the mesh, and the summary's
    # mesh/provenance must describe the run that finished, not the one
    # that was requested (degraded throughput labeled at the source —
    # the supervised record carries degraded/mesh_shape/refactors too)
    final_solver = result.solver or solver
    final_cfg = getattr(final_solver, "cfg", cfg)
    return _finish(
        args, final_cfg, final_solver, result.u, busy,
        result.steps_done, result.start_step, result.residual, dump_slice,
        extra_summary={"supervised": supervised_record},
    )


def _finish(
    args, cfg, solver, u, elapsed, steps_done, start_step, residual,
    dump_slice, extra_summary=None,
) -> int:
    """Post-run reporting shared by the plain and supervised paths:
    dumps, throughput summary, golden check, coordinator JSON."""
    slice_path = None
    if dump_slice is not None:
        axis, index, slice_path = dump_slice
        plane = solver.gather_slice(u, axis, index)  # all processes join
        if distributed.is_coordinator():
            np.save(slice_path, plane)
            log.info(
                "dumped slice axis=%d index=%d shape=%s -> %s",
                axis, index, plane.shape, slice_path,
            )

    vtk_path = None
    if args.dump_vtk:
        from heat3d_tpu.utils.vtkio import write_structured_points

        full = solver.gather(u)  # collective: all processes join
        if distributed.is_coordinator():
            write_structured_points(
                args.dump_vtk, full, spacing=cfg.grid.spacing
            )
            vtk_path = args.dump_vtk
            log.info("dumped VTK field %s -> %s", full.shape, vtk_path)

    cells = cfg.grid.num_cells
    updates = cells * max(steps_done - start_step, 1)
    n_dev = cfg.mesh.num_devices
    summary = {
        "grid": list(cfg.grid.shape),
        "stencil": cfg.stencil.kind,
        "equation": cfg.equation,
        "integrator": cfg.integrator,
        "mesh": list(cfg.mesh.shape),
        "dtype": cfg.precision.storage,
        "backend": cfg.backend,
        # the route the step builders dispatched ('auto' chooses; this
        # records what it chose)
        "route": solver.route,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "steps": steps_done - start_step,
        "seconds": elapsed,
        "residual_l2": residual,
        "gcell_updates_per_sec": updates / elapsed / 1e9,
        "gcell_updates_per_sec_per_chip": updates / elapsed / 1e9 / n_dev,
    }
    if slice_path is not None:
        summary["slice_path"] = slice_path
    if vtk_path is not None:
        summary["vtk_path"] = vtk_path
    if extra_summary:
        summary.update(extra_summary)

    if args.golden_check:
        if cfg.integrator != "explicit-euler":
            raise SystemExit(
                f"--golden-check covers the explicit-Euler oracle only "
                f"(integrator={cfg.integrator!r}); the per-integrator "
                "accuracy gates live in tests/test_timeint.py "
                "(docs/INTEGRATORS.md)"
            )
        from heat3d_tpu.core import golden

        # steps_done counts from t=0 even on --resume: the golden model must
        # advance the original init by the run's TOTAL step count, not just
        # the resumed segment.
        # the fp64 oracle steps the SPEC-compiled taps (identical to the
        # legacy derivation for heat), so --golden-check covers every
        # equation family, not just heat (docs/EQUATIONS.md)
        from heat3d_tpu import eqn

        g = golden.run(
            golden.make_init(args.init, cfg.grid.shape, seed=cfg.run.seed),
            cfg.grid, cfg.stencil, steps_done,
            taps=eqn.solver_taps(cfg),
        )
        got = solver.gather(u).astype(np.float64)
        err = float(np.max(np.abs(got - g)))
        rel = err / max(float(np.max(np.abs(g))), 1e-300)
        summary["golden_max_abs_err"] = err
        summary["golden_rel_err"] = rel
        # tolerance follows the loosest dtype in the chain: bf16 anywhere
        # (storage OR stencil compute) caps accuracy at bf16's ~3
        # decimal digits regardless of how the field is stored
        fp32_chain = (
            cfg.precision.storage == "float32"
            and cfg.precision.compute == "float32"
        )
        tol = 1e-5 if fp32_chain else 5e-2
        summary["golden_pass"] = bool(rel < tol)

    # the ledger's run_summary is the machine-readable mirror of the
    # stdout JSON (every process writes its own ledger; stdout stays
    # coordinator-only), followed by the final per-run metrics record
    obs.get().event("run_summary", **summary)
    obs.get().event("metrics_summary", metrics=obs.REGISTRY.snapshot())
    if distributed.is_coordinator():
        emit_json(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
