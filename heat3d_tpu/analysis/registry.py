"""Canonical registries the checkers (and the data lints) consume.

Three parallel vocabularies used to drift silently as PRs landed: ledger
event names (emitted in code, narrated in docs/OBSERVABILITY.md, pattern-
matched by ``obs summary``), ``HEAT3D_*`` environment knobs (read all
over, documented sporadically), and the config-knob surface (checked by
:mod:`heat3d_tpu.analysis.knobs` against live sources, not a registry).
This module is the single source of truth for the first two:

- :data:`LEDGER_EVENTS` — every event/span name the framework may emit.
  The taxonomy checker fails the lint when code emits an unregistered
  name (or the registered kind disagrees), and when a registered name is
  missing from the docs/OBSERVABILITY.md taxonomy table. The ledger data
  lint (``heat3d obs check --taxonomy`` / ``scripts/check_ledger.py
  --taxonomy``) flags unknown names in actual ledger files against the
  same registry.
- :data:`ENV_VARS` — every ``HEAT3D_*`` knob the framework reads.
  Same enforcement: referenced-but-unregistered fails, registered-but-
  undocumented fails, registered-but-unreferenced warns (stale entry).

Adding an event or env knob is a three-line change by design: emit it,
register it here, add its row to the docs/OBSERVABILITY.md taxonomy
table — and the lint holds the three together from then on.
"""

from __future__ import annotations

from typing import Any, Dict

# ---- ledger-event taxonomy -------------------------------------------------
# name -> {kind: point|span, module: emitter, desc, external: emitted by
# generated/child code the AST scan cannot see (registry + docs only)}

LEDGER_EVENTS: Dict[str, Dict[str, Any]] = {
    # lifecycle (obs/ledger.py writes the open/close frames itself)
    "ledger_open": {"kind": "point", "module": "obs/ledger.py",
                    "desc": "stream header: schema, pid, argv, meta"},
    "ledger_close": {"kind": "point", "module": "obs/ledger.py",
                     "desc": "stream footer: rc, error if any"},
    "run_start": {"kind": "point", "module": "cli.py",
                  "desc": "resolved config of the run about to execute"},
    "run_summary": {"kind": "point", "module": "cli.py",
                    "desc": "machine mirror of the stdout JSON summary"},
    "metrics_summary": {"kind": "point", "module": "cli.py, bench/__main__.py",
                        "desc": "final metrics-registry snapshot"},
    "residual": {"kind": "point", "module": "cli.py",
                 "desc": "mid-run L2 residual at a reporting boundary"},
    # stepping
    "warmup": {"kind": "span", "module": "cli.py",
               "desc": "executable warmup outside the timed window"},
    "run_loop": {"kind": "span", "module": "cli.py",
                 "desc": "the whole timed stepping loop (steps field)"},
    "chunk": {"kind": "span", "module": "resilience/supervisor.py",
              "desc": "one supervised checkpoint window (force-synced)"},
    "init_state": {"kind": "span", "module": "models/heat3d.py",
                   "desc": "sharded initial-state construction"},
    "solver.build": {"kind": "span", "module": "models/heat3d.py",
                     "desc": "HeatSolver3D construction: config "
                             "resolution, mesh, step builders (not their "
                             "compiles)"},
    "solver.dispatch": {"kind": "span", "module": "models/heat3d.py",
                        "desc": "one solver program dispatch (program: "
                                "run|step|step_residual|converge; steps; "
                                "dtype: the field's storage dtype; "
                                "compile_s when it compiled)"},
    "cg_solve": {"kind": "point", "module": "models/heat3d.py",
                 "desc": "implicit-cg run finished: steps, last solve's "
                         "iteration count and relative residual (the "
                         "stiff-dt convergence audit trail)"},
    # resilience
    "supervised_start": {"kind": "point", "module": "resilience/supervisor.py",
                         "desc": "supervisor engaged: target step, cadence"},
    "supervised_end": {"kind": "point", "module": "resilience/supervisor.py",
                       "desc": "supervisor done: steps, recoveries"},
    "fault_injected": {"kind": "point", "module": "resilience/faults.py",
                       "desc": "deterministic fault fired (kind_ field)"},
    "retry_attempt": {"kind": "point", "module": "resilience/retry.py",
                      "desc": "one RetryPolicy attempt (ok, delay)"},
    "retry_outcome": {"kind": "point", "module": "resilience/retry.py",
                      "desc": "RetryPolicy.run verdict (stop_reason)"},
    "heal_wait": {"kind": "span", "module": "resilience/supervisor.py",
                  "desc": "backend heal wait (priced outage)"},
    "recovery": {"kind": "point", "module": "resilience/supervisor.py",
                 "desc": "survived failure: kind_, resumed_from"},
    "generation_save": {"kind": "point", "module": "resilience/supervisor.py",
                        "desc": "checkpoint generation written"},
    # elastic degradation (resilience/elastic.py + supervisor)
    "elastic_refactor": {"kind": "point", "module": "resilience/elastic.py",
                         "desc": "survivor-mesh re-factorization: "
                                 "direction (degrade|expand), old/new "
                                 "mesh, survivors, re-stitch seconds"},
    "degraded_mode_enter": {"kind": "point",
                            "module": "resilience/supervisor.py",
                            "desc": "supervised run continuing on a "
                                    "survivor mesh (step, mesh, "
                                    "survivors)"},
    "degraded_mode_exit": {"kind": "point",
                           "module": "resilience/supervisor.py",
                           "desc": "re-expand restored the original "
                                   "mesh (step, degraded seconds)"},
    # checkpoints
    "ckpt_save": {"kind": "span", "module": "utils/checkpoint.py",
                  "desc": "checkpoint write (path, step)"},
    "ckpt_load": {"kind": "span", "module": "utils/checkpoint.py",
                  "desc": "checkpoint read (path)"},
    "ckpt_corrupt": {"kind": "point", "module": "utils/checkpoint.py",
                     "desc": "shard checksum mismatch detected"},
    "ckpt_quarantine": {"kind": "point", "module": "utils/checkpoint.py",
                        "desc": "corrupt generation renamed aside"},
    # bench
    "bench_row": {"kind": "point", "module": "bench/harness.py",
                  "desc": "full measured row mirrored into the ledger (ts_)"},
    "bench_row_measure": {"kind": "span", "module": "bench/harness.py",
                          "desc": "one row's measurement bracket"},
    "bench_row_replayed": {"kind": "point", "module": "bench/harness.py",
                           "desc": "row re-emitted from a sweep journal"},
    # perf observability
    "profile_capture": {"kind": "point", "module": "obs/perf/profiling.py",
                        "desc": "profiler bracket: artifact, overhead, ok"},
    "step_cost": {"kind": "point", "module": "obs/perf/roofline.py",
                  "desc": "XLA cost_analysis of the step executable"},
    "peak_calibrated": {"kind": "point", "module": "obs/perf/roofline.py",
                        "desc": "measured per-chip VPU peak stored"},
    "obs_anomaly": {"kind": "point", "module": "obs/perf/timeline.py",
                    "desc": "step-time drift or host straggler flagged "
                            "(kind_, delta_pct, regress bands)"},
    "timeline_export": {"kind": "point", "module": "obs/perf/timeline.py",
                        "desc": "Chrome-trace export written (path, "
                                "events, streams)"},
    "slo_verdict": {"kind": "point", "module": "obs/perf/slo.py",
                    "desc": "SLO evaluation: verdict + per-objective "
                            "burn rates"},
    # comm observatory (obs/comm/, docs/OBSERVABILITY.md §9)
    "comm_probe": {"kind": "point", "module": "obs/comm/probe.py",
                   "desc": "one probed halo link (axis, direction, "
                           "sub_block): plan-predicted bytes joined to "
                           "measured p50 time -> GB/s"},
    "clock_align": {"kind": "point", "module": "obs/perf/merge.py",
                    "desc": "merge --align applied: anchor event, "
                            "per-source offsets, confidence interval"},
    "adjudicate_verdict": {"kind": "point", "module":
                           "obs/comm/adjudicate.py",
                           "desc": "POD_RUNBOOK A/B stage verdicts "
                                   "(pass/fail/no-data per stage + rc)"},
    # exchange plans (parallel/plan.py)
    "exchange_plan_built": {"kind": "point", "module": "parallel/plan.py",
                            "desc": "persistent exchange plan constructed "
                                    "(mode, transport, width, messages) — "
                                    "once per plan key per run"},
    "plan_cache_hit": {"kind": "point", "module": "parallel/plan.py",
                       "desc": "exchange plan reused from the process "
                               "cache (once per plan key per run)"},
    # autotuning
    "tune_search_start": {"kind": "point", "module": "tune/measure.py",
                          "desc": "search opened: space, budget, key"},
    "tune_trial": {"kind": "point", "module": "tune/measure.py",
                   "desc": "one candidate: measured/pruned/dominated/error"},
    "tune_winner": {"kind": "point", "module": "tune/measure.py",
                    "desc": "search verdict: winning knobs + metric"},
    "tune_budget_exhausted": {"kind": "point", "module": "tune/measure.py",
                              "desc": "unmeasured candidates at budget end"},
    "tune_probe": {"kind": "span", "module": "tune/measure.py",
                   "desc": "short-probe bracket (early stopping)"},
    "tune_trial_measure": {"kind": "span", "module": "tune/measure.py",
                           "desc": "full trial measurement bracket"},
    "tune_cache_hit": {"kind": "point", "module": "tune/cache.py",
                       "desc": "auto knobs resolved from a cache entry"},
    "tune_cache_miss": {"kind": "point", "module": "tune/cache.py",
                        "desc": "no entry for this context (static fallback)"},
    "tune_cache_stale": {"kind": "point", "module": "tune/cache.py",
                         "desc": "entry rejected: jax/schema/env mismatch"},
    # IR lint (heat3d lint --ir)
    "ir_lint_start": {"kind": "point", "module": "analysis/ir/__init__.py",
                      "desc": "IR verifier opened: families, judged-"
                              "matrix case count, device posture"},
    "ir_lint_verdict": {"kind": "point", "module": "analysis/ir/__init__.py",
                        "desc": "IR verifier verdict: per-severity "
                                "finding counts per family set"},
    # kernel lint (heat3d lint --kernel)
    "kernel_lint_start": {"kind": "point",
                          "module": "analysis/kernel/__init__.py",
                          "desc": "kernel verifier opened: families, "
                                  "judged-kernel case count, device "
                                  "posture"},
    "kernel_lint_verdict": {"kind": "point",
                            "module": "analysis/kernel/__init__.py",
                            "desc": "kernel verifier verdict: per-"
                                    "severity finding counts per family "
                                    "set"},
    # serving (batched scenario engine)
    "serve_submit": {"kind": "point", "module": "serve/queue.py",
                     "desc": "scenario request enqueued (request_id, depth)"},
    "serve_batch_start": {"kind": "point", "module": "serve/queue.py",
                          "desc": "packed batch about to execute (members, "
                                  "padded size, request ids, bucket)"},
    "serve_batch": {"kind": "span", "module": "serve/queue.py",
                    "desc": "one packed batch's execution bracket"},
    "serve_result": {"kind": "point", "module": "serve/queue.py",
                     "desc": "one request delivered (queue latency)"},
    "serve_metrics_summary": {"kind": "point",
                              "module": "serve/queue.py, serve/engine/",
                              "desc": "drain-final per-bucket latency "
                                      "p50/p95/max + depth high-water "
                                      "mark (the SLO layer's source)"},
    # async serving engine (serve/engine/) + AOT cache (serve/aot.py)
    "serve_dispatch": {"kind": "point", "module": "serve/engine/core.py",
                       "desc": "dispatcher handed a packed chunk to a "
                               "bucket worker (request ids, in-flight "
                               "count at dispatch)"},
    "serve_requeue": {"kind": "point", "module": "serve/engine/core.py",
                      "desc": "backend-loss batch requeued with backoff "
                              "instead of failed (bucket, request ids, "
                              "attempt, backoff seconds) — opens the "
                              "degraded window the SLO serve_degraded "
                              "objective budgets"},
    "serve_batch_ready": {"kind": "point", "module": "serve/engine/core.py",
                          "desc": "a batch's device futures resolved in "
                                  "its worker (execute seconds; the "
                                  "dispatch->ready gap is the overlap "
                                  "window)"},
    "aot_cache_hit": {"kind": "point", "module": "serve/aot.py",
                      "desc": "serialized executables loaded — no trace, "
                              "no compile (measured load_s)"},
    "aot_cache_miss": {"kind": "point", "module": "serve/aot.py",
                       "desc": "no AOT store entry for this bucket key — "
                               "compiling fresh"},
    "aot_cache_stale": {"kind": "point", "module": "serve/aot.py",
                        "desc": "store entry unusable (jax/platform/"
                                "device drift, torn payload — reason "
                                "field); recompile fallback"},
    "aot_export": {"kind": "point", "module": "serve/aot.py",
                   "desc": "compiled executables serialized into the AOT "
                           "store (key, programs, bytes)"},
    "compile_stall": {"kind": "point", "module": "serve/aot.py",
                      "desc": "trace+compile stall actually paid for a "
                              "serving bucket (measured seconds; absent "
                              "on a warm AOT hit — the cold-start "
                              "acceptance signal)"},
    # sustained-traffic soak (serve/loadgen.py) + overload control
    "serve_shed": {"kind": "point",
                   "module": "serve/queue.py, serve/engine/core.py",
                   "desc": "a submission rejected by admission control "
                           "(reason depth|stream_cap, per-stream "
                           "occupancy) — shed traffic is accounted, "
                           "never silent"},
    "serve_admission": {"kind": "point", "module": "serve/engine/core.py",
                        "desc": "first submission admitted on a new "
                                "stream (its admission cap + the global "
                                "depth cap)"},
    "worker_scale": {"kind": "point", "module": "serve/engine/core.py",
                     "desc": "execution-slot count moved with load "
                             "(direction, slots from/to, backlog, last "
                             "batch-execute seconds)"},
    "aot_prewarm": {"kind": "point", "module": "serve/engine/core.py",
                    "desc": "an executable built/loaded ahead of traffic "
                            "(bucket, padded size, forecast members, "
                            "build seconds)"},
    "loadgen_start": {"kind": "point", "module": "serve/loadgen.py",
                      "desc": "soak replay begins: seed, duration, "
                              "arrival count, streams"},
    "soak_verdict": {"kind": "point", "module": "serve/loadgen.py",
                     "desc": "machine-checked soak outcome: accounting "
                             "(admitted + shed == submitted), order, "
                             "post-warmup compile stalls, sustained "
                             "member-Gcell/s, degraded seconds"},
    # request tracing + live monitoring (obs/burn.py, serve/loadgen.py)
    "serve_span": {"kind": "point", "module": "serve/queue.py",
                   "desc": "one phase of a request's trace (trace_id, "
                           "span queue|pack|compute|deliver|requeue_gap "
                           "under parent 'request'), written at delivery "
                           "with explicit t0_wall/t1_wall — a POINT "
                           "event, not a ledger span: per-request "
                           "windows from worker threads interleave and "
                           "would break laminar nesting"},
    "monitor_start": {"kind": "point", "module": "serve/loadgen.py",
                      "desc": "live SLO monitor attached to the soak "
                              "(fast/slow window seconds, burn "
                              "threshold, tick interval, abort flag, "
                              "objective names)"},
    "slo_burn_alert": {"kind": "point", "module": "serve/loadgen.py",
                       "desc": "an objective entered alerting: burn >= "
                               "threshold on BOTH sliding windows "
                               "(rising edge only — one event per "
                               "excursion, not per tick)"},
    "monitor_summary": {"kind": "point", "module": "serve/loadgen.py",
                        "desc": "monitor final state at soak end: alert "
                                "count, aborted flag, final verdict "
                                "from the shared SLO core (test-pinned "
                                "equal to post-hoc obs slo on the same "
                                "ledger)"},
}

# Wrapper functions whose first argument is an event name (the taxonomy
# checker treats `_event_once("tune_cache_miss", ...)` like
# `.event("tune_cache_miss", ...)`); `_write` carries (name, kind).
EVENT_WRAPPERS = ("_event_once",)


# ---- HEAT3D_* environment-knob registry ------------------------------------
# name -> {module: primary reader, desc}. The taxonomy checker scans
# heat3d_tpu/, bench.py and scripts/ for HEAT3D_* tokens and fails on any
# not registered here; docs/OBSERVABILITY.md must carry every row.

ENV_VARS: Dict[str, Dict[str, str]] = {
    "HEAT3D_LEDGER": {"module": "obs/ledger.py",
                      "desc": "run-ledger path (--ledger fallback)"},
    "HEAT3D_METRICS": {"module": "obs/metrics.py",
                       "desc": "metrics snapshot path (.prom = textfile)"},
    "HEAT3D_COST_ANALYSIS": {"module": "obs/perf/roofline.py",
                             "desc": "0 skips the step-cost compile"},
    "HEAT3D_PEAK_MEM_GBPS": {"module": "obs/perf/roofline.py",
                             "desc": "HBM peak override for roofline"},
    "HEAT3D_PEAK_GFLOPS": {"module": "obs/perf/roofline.py",
                           "desc": "VPU peak override for roofline"},
    "HEAT3D_CKPT_VERIFY": {"module": "utils/checkpoint.py",
                           "desc": "0 skips shard CRC verification"},
    "HEAT3D_COMM_PROBE": {"module": "obs/comm/probe.py",
                          "desc": "1 runs the per-link halo probe after "
                                  "bench_halo rows (comm_probe events)"},
    "HEAT3D_COMM_PROBE_ITERS": {"module": "obs/comm/probe.py",
                                "desc": "timed samples per probed link "
                                        "(default 5)"},
    "HEAT3D_COORDINATOR": {"module": "parallel/distributed.py",
                           "desc": "multihost coordinator addr:port"},
    "HEAT3D_NUM_PROCESSES": {"module": "parallel/distributed.py",
                             "desc": "multihost process count"},
    "HEAT3D_PROCESS_ID": {"module": "parallel/distributed.py",
                          "desc": "this host's process index"},
    "HEAT3D_AUTO_DISTRIBUTED": {"module": "parallel/distributed.py",
                                "desc": "1 = initialize() autodetect"},
    "HEAT3D_DEVICE_INIT": {"module": "models/heat3d.py",
                           "desc": "0 forces host-side state init"},
    "HEAT3D_FACTOR_7PT": {"module": "core/stencils.py",
                          "desc": "0 disables 7pt x-reflection factoring"},
    "HEAT3D_FACTOR_Y": {"module": "core/stencils.py",
                        "desc": "0 disables y-reflection factoring"},
    "HEAT3D_MEHRSTELLEN": {"module": "core/stencils.py",
                           "desc": "27pt separable-decomposition route"},
    "HEAT3D_NO_DIRECT": {"module": "parallel/step.py, ops/stencil_pallas.py",
                         "desc": "1 disables the direct kernel routes"},
    "HEAT3D_EQN_LEGACY": {"module": "eqn/__init__.py",
                          "desc": "1 routes the heat family through the "
                                  "verbatim pre-spec tap derivation (the "
                                  "eqn bitwise parity reference arm; "
                                  "non-heat families reject it)"},
    "HEAT3D_NO_PLAN": {"module": "parallel/plan.py",
                       "desc": "1 bypasses the exchange-plan layer (legacy "
                               "ad-hoc dispatch; partitioned degrades to "
                               "monolithic — the parity tests' reference "
                               "arm)"},
    "HEAT3D_PLAN_PART_MIN_BYTES": {
        "module": "parallel/plan.py",
        "desc": "partition granularity floor in bytes (default 1 MiB): "
                "faces below it ship whole even under "
                "halo_plan=partitioned; 0 forces genuine sub-blocks "
                "(the IR matrix sets it)"},
    "HEAT3D_DIRECT_INTERPRET": {"module": "parallel/step.py",
                                "desc": "1 routes kernels through the Pallas interpreter off-TPU (tests)"},
    "HEAT3D_DIRECT_FORCE": {"module": "parallel/step.py",
                            "desc": "1 selects real Mosaic kernels off-TPU (compile-only tests)"},
    "HEAT3D_VMEM_BYTES": {"module": "analysis/vmem.py",
                          "desc": "whole-chip VMEM budget override the IR memory-contract check (ANL905) adjudicates (default: per-generation table)"},
    "HEAT3D_FAULTS": {"module": "resilience/faults.py",
                      "desc": "deterministic fault-injection plan"},
    "HEAT3D_FAULT_STATE": {"module": "resilience/faults.py",
                           "desc": "fault-injection state file (fire-once)"},
    "HEAT3D_HEAL_MODE": {"module": "resilience/elastic.py",
                         "desc": "supervised heal mode default "
                                 "(wait|elastic|auto; --heal-mode "
                                 "overrides — docs/RESILIENCE.md "
                                 "\"Elastic degradation\")"},
    "HEAT3D_HEAL_DEADLINE_S": {"module": "resilience/elastic.py",
                               "desc": "heal-wait total deadline seconds "
                                       "(default 1800); in auto heal "
                                       "mode its expiry triggers the "
                                       "elastic fallback"},
    "HEAT3D_CG_MAX_ITERS": {"module": "timeint/cg.py",
                            "desc": "implicit-cg iteration cap per solve "
                                    "(default 64; SPMD-uniform — every "
                                    "device runs the masked loop to the "
                                    "same bound)"},
    "HEAT3D_CG_TOL": {"module": "timeint/cg.py",
                      "desc": "implicit-cg relative-residual stop "
                              "threshold (default 1e-6)"},
    "HEAT3D_TUNE_CACHE": {"module": "tune/cache.py",
                          "desc": "tuning-cache store path"},
    "HEAT3D_TUNE_DISABLE": {"module": "tune/cache.py",
                            "desc": "1 skips cache lookup (search driver sets it)"},
    "HEAT3D_BENCH_GRID": {"module": "bench.py",
                          "desc": "headline-bench grid edge override"},
    "HEAT3D_BENCH_STEPS": {"module": "bench.py",
                           "desc": "headline-bench step count"},
    "HEAT3D_BENCH_DTYPE": {"module": "bench.py",
                           "desc": "headline-bench dtype (fp32|bf16)"},
    "HEAT3D_BENCH_BACKEND": {"module": "bench.py",
                             "desc": "headline-bench backend override"},
    "HEAT3D_BENCH_TIME_BLOCKING": {"module": "bench.py",
                                   "desc": "headline-bench tb override"},
    "HEAT3D_SERVE_QUEUE": {"module": "serve/queue.py",
                           "desc": "pending-request depth cap (submit raises "
                                   "when full; default 1024)"},
    "HEAT3D_SERVE_MAX_BATCH": {"module": "serve/queue.py",
                               "desc": "members per packed batch cap "
                                       "(default 64)"},
    "HEAT3D_SERVE_MAX_PER_STREAM": {"module": "serve/engine/core.py",
                                    "desc": "per-stream open-request "
                                            "admission cap (default: the "
                                            "global depth cap; set lower "
                                            "for multi-tenant fairness)"},
    "HEAT3D_LOADGEN_SEED": {"module": "serve/loadgen.py",
                            "desc": "default seed for the soak arrival "
                                    "schedule (the spec's seed field "
                                    "wins)"},
    "HEAT3D_SERVE_WORKERS": {"module": "serve/engine/core.py",
                             "desc": "async engine concurrent batch-"
                                     "execution slots (default 2)"},
    "HEAT3D_AOT_CACHE": {"module": "serve/aot.py",
                         "desc": "AOT executable-store directory "
                                 "(default ~/.cache/heat3d/aot; 0/off "
                                 "disables persistence — stalls still "
                                 "measured)"},
    "HEAT3D_IR_DEVICES": {"module": "analysis/ir/programs.py",
                          "desc": "host-device count the IR lint forces "
                                  "for the judged meshes (default 4; "
                                  "only before jax initializes)"},
    "HEAT3D_IR_COMPILE": {"module": "analysis/ir/programs.py",
                          "desc": "0 skips the compiled memory-contract "
                                  "leg of heat3d lint --ir"},
    "HEAT3D_KERNEL_LINT_DEVICES": {"module": "analysis/kernel/programs.py",
                                   "desc": "host-device count the kernel "
                                           "lint forces for its judged "
                                           "rings (default 4; only "
                                           "before jax initializes)"},
    "HEAT3D_SLO_SPEC": {"module": "obs/perf/slo.py",
                        "desc": "SLO objective-spec path (obs slo / "
                                "serve --slo default)"},
    "HEAT3D_SLO_WARN_RATIO": {"module": "obs/perf/slo.py",
                              "desc": "warn at this fraction of an SLO "
                                      "ceiling (default 0.9)"},
    "HEAT3D_LEDGER_MAX_MB": {"module": "obs/ledger.py",
                             "desc": "size-capped ledger rollover: the "
                                     "live file rotates to "
                                     "<stem>.0.jsonl, .1, ... past this "
                                     "many MB (unset/0 = never; "
                                     "fail-soft — a failed rotation "
                                     "disables rotation, not the "
                                     "ledger)"},
    "HEAT3D_BURN_FAST_S": {"module": "obs/burn.py",
                           "desc": "burn-rate fast window seconds "
                                   "(default 60)"},
    "HEAT3D_BURN_SLOW_S": {"module": "obs/burn.py",
                           "desc": "burn-rate slow window seconds "
                                   "(default 300; clamped >= fast)"},
    "HEAT3D_BURN_THRESHOLD": {"module": "obs/burn.py",
                              "desc": "burn multiple both windows must "
                                      "reach to alert (default 1.0)"},
}


# ---- fail-soft contract ----------------------------------------------------
# The telemetry functions production code calls unconditionally; the
# documented invariant (docs/OBSERVABILITY.md "Failure posture") is that
# none of them can propagate an environmental failure (IO, serialization)
# to the instrumented run. Module path -> qualnames under contract.

FAIL_SOFT_CONTRACT: Dict[str, tuple] = {
    "heat3d_tpu/obs/ledger.py": (
        "activate",
        "get",
        "deactivate",
        "Ledger.event",
        "Ledger.span",
        "Ledger.set_context",
        "Ledger.close",
        "NullLedger.event",
        "NullLedger.span",
        "_SpanCtx.__enter__",
        "_SpanCtx.__exit__",
    ),
    "heat3d_tpu/obs/metrics.py": (
        "export_at_exit",
    ),
    "heat3d_tpu/obs/trace.py": (
        "named_phase",
        "open_span",
        "close_span",
        "spans",
        "_on_duration",
    ),
    "heat3d_tpu/obs/perf/profiling.py": (
        "profile_capture",
        "_ProfileCapture.__enter__",
        "_ProfileCapture.__exit__",
    ),
}

# Modules whose functions participate in fail-soft call-graph resolution
# (the contract functions may call helpers here; risk propagates through).
FAIL_SOFT_MODULES = tuple(FAIL_SOFT_CONTRACT)
