#!/usr/bin/env bash
# Run the benchmark suite on this machine's chips and regenerate the
# measured tables in BASELINE.md (SURVEY.md §2 C9, §5 "Metrics").
#
# Usage: scripts/run_bench_suite.sh [results.jsonl] [report.md]
# The report target defaults to BASELINE.md — the committed measured
# record. Pass a scratch path (or set REPORT_MD) for smoke/CPU runs so
# they don't clobber the on-chip tables.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${1:-bench_results.jsonl}
REPORT_MD=${2:-${REPORT_MD:-BASELINE.md}}
# One run ledger threads through every row's subprocess (the bench CLI
# activates $HEAT3D_LEDGER itself): the suite's A/B session — including
# rows replayed from a sweep journal vs freshly measured — reconstructs
# from this file alone (`heat3d obs summary`). Fresh sessions truncate it
# in lockstep with $OUT; APPEND sessions keep appending run segments.
LEDGER="${LEDGER:-${OUT%.jsonl}.ledger.jsonl}"
export HEAT3D_LEDGER="$LEDGER"
[[ -n "${APPEND:-}" ]] || : > "$LEDGER"
# ledger-lint scope: only the segments THIS session appends (same rule as
# LINT_FROM below) — a historical defect must not keep resumed sessions red
LEDGER_LINT_FROM=$(( $(wc -l < "$LEDGER" 2>/dev/null || echo 0) + 1 ))
# Row stderr lands here (NOT /dev/null): a failing row's traceback is the
# only evidence of WHY a session lost it. Fresh sessions truncate it in
# lockstep with $OUT (stale tracebacks misattribute failures); APPEND
# sessions keep it, and every session stamps a boundary header.
SUITE_LOG="${SUITE_LOG:-${OUT%.jsonl}.err.log}"
[[ -n "${APPEND:-}" ]] || : > "$SUITE_LOG"
echo "=== suite session $(date -u +%FT%TZ) (APPEND=${APPEND:-}) ===" >> "$SUITE_LOG"

# suite-level skip/fail notes go to OUR stderr (live view) AND the log —
# callers may truncate stderr, the log persists
note() { echo "$*" | tee -a "$SUITE_LOG" >&2; }

# APPEND=1 resumes an interrupted measurement session instead of
# truncating the rows a prior (e.g. interrupted) run already landed;
# configs already recorded in $OUT are skipped, not re-run (no duplicate
# table rows, no re-spending the session budget on finished rows)
[[ -n "${APPEND:-}" ]] || : > "$OUT"
# provenance-lint scope: only the rows THIS session appends. Pre-existing
# rows (a resumed session's earlier attempts, or the committed legacy
# record) were some other session's responsibility — linting them here
# would keep every APPEND session permanently red.
LINT_FROM=$(( $(wc -l < "$OUT" 2>/dev/null || echo 0) + 1 ))

# has_halo GRID DTYPE -> 0 if $OUT already has the halo row for this
# exchange shape (only consulted in APPEND mode). Checked separately from
# has_row because a bench=all rung killed between its two output lines
# leaves the throughput row without its paired halo row.
has_halo() {
  [[ -n "${APPEND:-}" && -s "$OUT" ]] || return 1
  python - "$OUT" "$@" <<'EOF'
import json, sys
out, grid, dtype = sys.argv[1:4]
want_dtype = {"fp32": "float32", "bf16": "bfloat16"}[dtype]
for line in open(out):
    try:
        r = json.loads(line)
    except json.JSONDecodeError:
        continue
    if (
        r.get("bench") == "halo"
        and r.get("grid") == [int(grid)] * 3
        and r.get("dtype") == want_dtype
    ):
        sys.exit(0)
sys.exit(1)
EOF
}

# has_row STENCIL GRID DTYPE TB COMPUTE OVERLAP -> 0 if $OUT already has a
# matching throughput row (only consulted in APPEND mode)
has_row() {
  [[ -n "${APPEND:-}" && -s "$OUT" ]] || return 1
  python - "$OUT" "$@" <<'EOF'
import json, sys
out, stencil, grid, dtype, tb, compute, overlap = sys.argv[1:8]
want_dtype = {"fp32": "float32", "bf16": "bfloat16"}[dtype]
want_compute = {"fp32": "float32", "bf16": "bfloat16"}[compute]
for line in open(out):
    try:
        r = json.loads(line)
    except json.JSONDecodeError:
        continue
    if (
        r.get("bench") == "throughput"
        and r.get("stencil") == stencil
        and r.get("grid") == [int(grid)] * 3
        and r.get("dtype") == want_dtype
        and r.get("compute_dtype", "float32") == want_compute
        and r.get("time_blocking", 1) == int(tb)
        and bool(r.get("overlap")) == (overlap == "1")
    ):
        sys.exit(0)
sys.exit(1)
EOF
}
[[ -f "$REPORT_MD" ]] || : > "$REPORT_MD"

# Single-chip sweep: the judged grid ladder at fp32+bf16, temporal blocking
# off/on (tb=2 = the fused one-sweep kernel, the headline setting), plus one
# overlap-split run (on one chip this isolates the split-step overhead; the
# comm-overlap benefit needs a pod). Each row emits throughput + halo p50.
# The multi-chip judged grids need a pod slice (same flags, bigger
# --grid/--mesh). Override with GRIDS/DTYPES/STEPS/TBS env vars
# (e.g. GRIDS=32 TBS=1 for a CPU smoke run).
for stencil in ${STENCILS:-7pt 27pt}; do
  for dtype in ${DTYPES:-fp32 bf16}; do
    # judged-floor grids FIRST: a short healthy window must land the
    # 1024^3 rows (the judged metric names 1024^3-4096^3) before the
    # small-grid context rows
    for grid in ${GRIDS:-1024 512 256}; do
      for tb in ${TBS:-1 2}; do
        # the 27pt ladder is VPU-bound and dtype/tb change little; bench
        # only its judged-flavor rows (fp32 plus the bf16 tb=2 row) at
        # 512+ to keep the suite under the measurement session budget
        if [[ $stencil == 27pt ]]; then
          [[ $grid -lt 512 ]] && continue
          [[ $dtype == bf16 && $tb == 1 ]] && continue
        fi
        # halo latency depends only on (grid, dtype), not stencil/tb: emit
        # one halo row per exchange shape (--bench all on the 7pt tb=1
        # pass), throughput-only otherwise — no duplicate halo rows
        bench=throughput
        [[ $stencil == 7pt && $tb == 1 ]] && bench=all
        if has_row "$stencil" "$grid" "$dtype" "$tb" fp32 0; then
          if [[ $bench == all ]] && ! has_halo "$grid" "$dtype"; then
            # resume edge: the prior run died between the throughput line
            # and the halo line — fill in just the missing halo row
            note "suite: backfilling halo row grid=$grid dtype=$dtype"
            timeout -k 30 "${ROW_TIMEOUT:-900}" \
              python -m heat3d_tpu.bench --grid "$grid" \
              --steps "${STEPS:-50}" --dtype "$dtype" --mesh 1 1 1 \
              --bench halo >> "$OUT" 2>>"$SUITE_LOG" \
              || note "suite: halo backfill failed grid=$grid (rc=$?)"
          else
            note "suite: already recorded $stencil grid=$grid dtype=$dtype tb=$tb"
          fi
          continue
        fi
        # a failing row (e.g. 1024^3 OOM on a small-HBM chip) skips, not
        # aborts; ROW_TIMEOUT bounds a row that hangs
        # (one stuck 1024^3 transfer must cost one row, not the stage)
        timeout -k 30 "${ROW_TIMEOUT:-900}" \
          python -m heat3d_tpu.bench --grid "$grid" --steps "${STEPS:-50}" \
          --stencil "$stencil" --dtype "$dtype" --time-blocking "$tb" \
          --mesh 1 1 1 --bench "$bench" \
          >> "$OUT" 2>>"$SUITE_LOG" \
          || note "suite: skipped $stencil grid=$grid dtype=$dtype tb=$tb (rc=$?)"
      done
    done
  done
done

# bf16-COMPUTE A/B (judged config 5 follow-up): same bf16 storage, stencil
# math in bf16 instead of fp32 — answers whether the bf16 tb=2 ceiling gap
# is VPU-width-limited (this row speeds up) or plane-assembly-limited (it
# doesn't). Accuracy gated by tests/test_solver.py bf16-compute tier.
if [[ -z "${SKIP_BF16_COMPUTE:-}" ]]; then
  for grid in ${GRIDS:-1024 512}; do
    [[ $grid -lt 512 ]] && continue
    if has_row 7pt "$grid" bf16 2 bf16 0; then
      note "suite: already recorded bf16-compute grid=$grid"
      continue
    fi
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
      python -m heat3d_tpu.bench --grid "$grid" --steps "${STEPS:-50}" \
      --dtype bf16 --compute-dtype bf16 --time-blocking 2 --mesh 1 1 1 \
      --bench throughput >> "$OUT" 2>>"$SUITE_LOG" \
      || note "suite: skipped bf16-compute grid=$grid (rc=$?)"
  done
fi

if [[ -z "${SKIP_OVERLAP:-}" ]]; then
  if has_row 7pt "${OVERLAP_GRID:-512}" fp32 1 fp32 1; then
    note "suite: already recorded overlap run"
  else
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
      python -m heat3d_tpu.bench --grid "${OVERLAP_GRID:-512}" \
      --steps "${STEPS:-50}" --overlap --mesh 1 1 1 --bench throughput \
      >> "$OUT" 2>>"$SUITE_LOG" \
      || note "suite: skipped overlap run (rc=$?)"
  fi
fi

# report refuses a zero-row rewrite itself (update_baseline_md), so a
# session whose every row skipped leaves the committed tables untouched
python -m heat3d_tpu.bench.report "$OUT" "$REPORT_MD"

# Roofline attribution of the session's rows (informational: achieved
# fraction of the traffic-model ceiling per row — the "where did the rest
# go" accounting; its rc must not gate the suite, a reporting bug loses
# nothing)
python -m heat3d_tpu.obs.cli roofline "$OUT" \
  || note "suite: roofline report failed (rc=$?)"

# Lints + the perf gate LAST (after the report, so failing them never
# loses the tables): provenance — rc 1 if any row THIS SESSION wrote has
# ts null/missing, lacks its route fields, or lacks sync_rtt_s (VERDICT
# r5 weak item 2, enforced going forward); ledger — rc 1 if the session's
# event stream is schema-invalid (missing fields, broken span nesting,
# torn run-ids); regress — rc 1 if any row this session measured regressed
# past the fail band against the committed same-platform history
# (platform-aware baselines: CPU smoke rows never compare against TPU
# records — they report no_baseline and pass). Their rc is the suite's rc
# under set -e; the regress JSON verdict also lands in the suite log.
python scripts/check_provenance.py --start-line "$LINT_FROM" "$OUT"
python scripts/check_ledger.py --start-line "$LEDGER_LINT_FROM" "$LEDGER"
# Static-analysis gate (docs/ANALYSIS.md): SPMD-safety + invariant
# checkers over the source tree; rc 1 only on unsuppressed error-severity
# findings, and that rc is the suite's rc. SKIP_STATIC_LINT=1 is the
# escape hatch for sessions that must land rows while a lint fix is in
# flight (scripts/lint_all.sh still runs it pre-merge).
if [[ -z "${SKIP_STATIC_LINT:-}" ]]; then
  python -m heat3d_tpu.cli lint --json | tee -a "$SUITE_LOG"
else
  note "suite: static lint skipped (SKIP_STATIC_LINT=1)"
fi
# IR-tier certification gate (docs/ANALYSIS.md "IR tier"): trace the
# judged step/superstep/ensemble matrix in a fresh process (so the
# multi-device CPU mesh can be forced) and certify collective topology,
# halo footprint, dtype flow and the compiled memory contract at the
# jaxpr/HLO level. Same rc policy as the static lint; its rc is the
# suite's rc. SKIP_IR_LINT=1 is the escape hatch.
if [[ -z "${SKIP_IR_LINT:-}" ]]; then
  python -m heat3d_tpu.cli lint --ir --json | tee -a "$SUITE_LOG"
else
  note "suite: IR lint skipped (SKIP_IR_LINT=1)"
fi
# Kernel-tier certification gate (docs/ANALYSIS.md "Kernel tier"): trace
# every repo Pallas kernel body in a fresh process (so the multi-device
# CPU rings can be forced) and certify DMA start/wait discipline,
# ring-slot happens-before, output coverage and remote-copy neighbor
# targets — the schedules interpret-tier parity cannot see. Same rc
# policy; its rc is the suite's rc. SKIP_KERNEL_LINT=1 is the escape
# hatch.
if [[ -z "${SKIP_KERNEL_LINT:-}" ]]; then
  python -m heat3d_tpu.cli lint --kernel --json | tee -a "$SUITE_LOG"
else
  note "suite: kernel lint skipped (SKIP_KERNEL_LINT=1)"
fi
python -m heat3d_tpu.obs.cli regress "$OUT" --start-line "$LINT_FROM" \
  --json | tee -a "$SUITE_LOG"

# SLO + timeline smoke (informational, AFTER the regress gate): evaluate
# the session ledger against the configured objectives ($HEAT3D_SLO_SPEC,
# else the built-in generous defaults — the path stays exercised either
# way) and export the session's Chrome-trace timeline next to the rows.
# Both fail SOFT (a breach on a smoke ledger is a note, not a gate);
# SKIP_SLO_SMOKE=1 skips. docs/OBSERVABILITY.md §7.
if [[ -z "${SKIP_SLO_SMOKE:-}" ]]; then
  python -m heat3d_tpu.obs.cli slo "$LEDGER" --json | tee -a "$SUITE_LOG" \
    || note "suite: slo smoke verdict nonzero (rc=$?) — informational"
  python -m heat3d_tpu.obs.cli timeline "$LEDGER" \
    -o "${OUT%.jsonl}.trace.json" >> "$SUITE_LOG" 2>&1 \
    || note "suite: timeline export failed (rc=$?) — informational"
else
  note "suite: slo/timeline smoke skipped (SKIP_SLO_SMOKE=1)"
fi

# Autotune smoke + cache-schema lint (informational, AFTER the gates so
# their rc still decides the suite): a budgeted `tune run` over the FULL
# extended time_blocking lattice (1..4 — deep tb included, so the
# search-measure-decide-cache loop AND the deep-tb validity pruning stay
# alive end to end) — on CPU its numbers are smoke, not record, so it
# writes a session-local store (never the operator's ~/.cache default)
# and both steps fail SOFT. SKIP_TUNE_SMOKE=1 skips; docs/TUNING.md.
if [[ -z "${SKIP_TUNE_SMOKE:-}" ]]; then
  TUNE_CACHE="${TUNE_CACHE:-${OUT%.jsonl}.tune_cache.json}"
  python -m heat3d_tpu.cli tune run --grid "${TUNE_GRID:-24}" \
    --steps "${TUNE_STEPS:-8}" --repeats 1 --probe-steps 4 \
    --budget-s "${TUNE_BUDGET_S:-45}" --knob time_blocking=1,2,3,4 \
    --cache "$TUNE_CACHE" --json >> "$SUITE_LOG" 2>&1 \
    || note "suite: tune smoke failed (rc=$?) — informational"
  python -m heat3d_tpu.cli tune lint --cache "$TUNE_CACHE" \
    >> "$SUITE_LOG" 2>&1 \
    || note "suite: tune cache-schema lint failed (rc=$?) — informational"
fi

# Exchange-plan A/B smoke (informational, beside the tune smoke): one
# tiny monolithic vs partitioned throughput pair through the persistent
# exchange plans (parallel/plan.py), judged by the tune/decide pairwise
# logic (scripts/ab_decide.py = thin wrapper) — keeps the plan knob's
# measure-decide loop alive end to end between chip sessions. On CPU the
# verdict is smoke, not record (docs/TUNING.md "Persistent exchange
# plans"; the pod A/B is POD_RUNBOOK stage 3-plan). Fails SOFT;
# SKIP_PLAN_SMOKE=1 skips.
if [[ -z "${SKIP_PLAN_SMOKE:-}" ]]; then
  PLAN_LOG="${OUT%.jsonl}.plan_ab.log"
  : > "$PLAN_LOG"
  for hp in monolithic partitioned; do
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
      python -m heat3d_tpu.bench --grid "${PLAN_GRID:-24}" \
      --steps "${PLAN_STEPS:-8}" --bench throughput --halo-plan "$hp" \
      2>>"$SUITE_LOG" | sed "s/^/halo_plan=$hp: /" >> "$PLAN_LOG" \
      || note "suite: plan smoke $hp failed (rc=$?) — informational"
  done
  python scripts/ab_decide.py "$PLAN_LOG" >> "$SUITE_LOG" 2>&1 \
    || note "suite: plan A/B decide failed (rc=$?) — informational"
fi

# Serve smoke (informational, beside the tune smoke): the built-in tiny
# multi-bucket batch through the batched scenario engine — submit ->
# shape-bucketed packing -> streamed results, CPU-safe and sub-minute —
# so the serving path (docs/SERVING.md) can't rot between serving
# sessions. Fails SOFT; SKIP_SERVE_SMOKE=1 skips.
if [[ -z "${SKIP_SERVE_SMOKE:-}" ]]; then
  python -m heat3d_tpu.cli serve --smoke >> "$SUITE_LOG" 2>&1 \
    || note "suite: serve smoke failed (rc=$?) — informational"
else
  note "suite: serve smoke skipped (SKIP_SERVE_SMOKE=1)"
fi

# Async-engine smoke + AOT cold/warm A/B (informational, beside the
# serve smoke; docs/SERVING.md "Async engine & cold start"): the same
# tiny multi-bucket batch through the always-on engine, run TWICE
# against one fresh session-local AOT store — the first run measures the
# compile stall and exports the executables, the second must load them
# back (aot.hits > 0, compile_stall_s == 0: the cold-start-elimination
# contract, machine-checked from the two --verdict JSON lines printed to
# the console). Also a budgeted engine-bucket `tune run --batch-members`
# so the b2^k batch-bucket entries the engine resolves through stay
# exercised (docs/TUNING.md). Fails SOFT; SKIP_ASYNC_SMOKE=1 skips.
if [[ -z "${SKIP_ASYNC_SMOKE:-}" ]]; then
  # always a suite-derived scratch path (never an operator override):
  # the A/B needs a guaranteed-cold store, and rm -rf on a caller-
  # supplied directory would delete a real accumulated AOT cache
  AOT_DIR="${OUT%.jsonl}.aot_cache"
  rm -rf "$AOT_DIR"
  ASYNC_COLD=$(HEAT3D_AOT_CACHE="$AOT_DIR" \
    python -m heat3d_tpu.cli serve --async --smoke --verdict \
    2>>"$SUITE_LOG" | tail -n 1) \
    || note "suite: async serve smoke (cold) failed (rc=$?) — informational"
  ASYNC_WARM=$(HEAT3D_AOT_CACHE="$AOT_DIR" \
    python -m heat3d_tpu.cli serve --async --smoke --verdict \
    2>>"$SUITE_LOG" | tail -n 1) \
    || note "suite: async serve smoke (warm) failed (rc=$?) — informational"
  echo "suite: async smoke cold verdict: $ASYNC_COLD"
  echo "suite: async smoke warm verdict: $ASYNC_WARM"
  python - "$ASYNC_COLD" "$ASYNC_WARM" <<'PYEOF' \
    || note "suite: AOT cold/warm A/B verdict failed — informational"
import json, sys
cold = json.loads(sys.argv[1])["serve_verdict"]
warm = json.loads(sys.argv[2])["serve_verdict"]
ca, wa = cold["engine"]["aot"], warm["engine"]["aot"]
ok = (cold["ok"] and warm["ok"] and wa["hits"] > 0
      and wa["compile_stall_s"] == 0)
print(json.dumps({"aot_cold_warm_ab": {
    "ok": ok,
    "cold_compile_stall_s": round(ca["compile_stall_s"], 3),
    "warm_hits": wa["hits"], "warm_load_s": round(wa["load_s"], 4),
    "warm_compile_stall_s": wa["compile_stall_s"]}}))
sys.exit(0 if ok else 1)
PYEOF
  TUNE_CACHE="${TUNE_CACHE:-${OUT%.jsonl}.tune_cache.json}"
  python -m heat3d_tpu.cli tune run --grid "${TUNE_GRID:-16}" \
    --batch-members 4 --steps 6 --repeats 1 --probe-steps 0 \
    --budget-s "${TUNE_BUDGET_S:-45}" --knob time_blocking=1,2 \
    --cache "$TUNE_CACHE" --json >> "$SUITE_LOG" 2>&1 \
    || note "suite: engine-bucket tune smoke failed (rc=$?) — informational"
else
  note "suite: async serve smoke skipped (SKIP_ASYNC_SMOKE=1)"
fi

# Equation-frontend smoke (informational, beside the serve smoke): one
# spec-built family end-to-end through the solver CLI with the fp64
# golden check — the declarative eqn subsystem (docs/EQUATIONS.md) can't
# rot between equation sessions. Sub-minute on CPU. Fails SOFT;
# SKIP_EQN_SMOKE=1 skips.
if [[ -z "${SKIP_EQN_SMOKE:-}" ]]; then
  python -m heat3d_tpu.cli --grid 24 --steps 5 \
    --equation advection-diffusion --golden-check >> "$SUITE_LOG" 2>&1 \
    || note "suite: eqn smoke failed (rc=$?) — informational"
else
  note "suite: eqn smoke skipped (SKIP_EQN_SMOKE=1)"
fi

# Time-integrator smoke (informational, beside the eqn smoke;
# docs/INTEGRATORS.md): the two non-default integrator families
# end-to-end through the solver CLI on a forced 4-device CPU mesh — a
# leapfrog wave run (the two-level (u, u_prev) carry through the
# sharded superstep) and an implicit-cg run at 10x the explicit CFL
# bound (dt 5/3 vs the 1/6 forward-Euler limit at unit spacing), whose
# cg_solve ledger event must record a converged solve (iterations
# within the HEAT3D_CG_MAX_ITERS cap, relative residual at tolerance)
# — the stiff-dt convergence contract, machine-checked. Always CPU
# (the path under test is the integrator plumbing, not the chip),
# sub-minute. Fails SOFT; SKIP_TIMEINT_SMOKE=1 skips.
if [[ -z "${SKIP_TIMEINT_SMOKE:-}" ]]; then
  TI_LED="${OUT%.jsonl}.timeint.ledger.jsonl"
  : > "$TI_LED"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.cli --grid 16 --steps 6 --mesh 4 1 1 \
    --backend jnp --equation wave --integrator leapfrog \
    >> "$SUITE_LOG" 2>&1 \
    || note "suite: leapfrog wave smoke failed (rc=$?) — informational"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    HEAT3D_LEDGER="$TI_LED" \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.cli --grid 16 --steps 4 --mesh 4 1 1 \
    --backend jnp --integrator implicit-cg --dt 1.6666667 \
    >> "$SUITE_LOG" 2>&1 \
    || note "suite: implicit-cg smoke run failed (rc=$?) — informational"
  python - "$TI_LED" <<'PYEOF' \
    || note "suite: timeint smoke verdict failed — informational"
import json, sys
evs = []
try:
    with open(sys.argv[1]) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    evs.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
except OSError:
    pass
cg = [e for e in evs if e.get("event") == "cg_solve"]
# the run's LAST solve is the audited one (a warmup call may log a
# zero-step event first)
last = cg[-1] if cg else {}
ok = (
    len(cg) >= 1
    and 1 <= last.get("cg_iters", 0) <= 64
    and 0.0 <= last.get("cg_relres", 1.0) <= 1e-5
)
print(json.dumps({"timeint_smoke": {
    "ok": ok, "cg_solves": len(cg),
    "cg_iters": last.get("cg_iters"),
    "cg_relres": last.get("cg_relres"),
}}))
sys.exit(0 if ok else 1)
PYEOF
else
  note "suite: timeint smoke skipped (SKIP_TIMEINT_SMOKE=1)"
fi

# Elastic-heal smoke (informational, beside the other smokes;
# docs/RESILIENCE.md "Elastic degradation"): a supervised run on a forced
# 4-device CPU mesh loses 2 devices mid-run (injected partial-device-loss)
# under --heal-mode elastic, must re-factorize onto the survivors and
# COMPLETE without operator action — machine-checked from the ledger
# (elastic_refactor + degraded_mode_enter + supervised_end at the target
# step) with the JSON verdict on the console. Always CPU (the path under
# test is the re-plan, not the chip), sub-minute. Fails SOFT;
# SKIP_ELASTIC_SMOKE=1 skips.
if [[ -z "${SKIP_ELASTIC_SMOKE:-}" ]]; then
  ELASTIC_LED="${OUT%.jsonl}.elastic.ledger.jsonl"
  ELASTIC_CK="${OUT%.jsonl}.elastic_ck"
  : > "$ELASTIC_LED"
  rm -rf "$ELASTIC_CK"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    HEAT3D_FAULTS="partial-device-loss:step=4:keep=2" \
    HEAT3D_LEDGER="$ELASTIC_LED" \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.cli --grid 8 --steps 8 --mesh 4 1 1 \
    --backend jnp --checkpoint "$ELASTIC_CK" --checkpoint-every 2 \
    --supervise --heal-mode elastic >> "$SUITE_LOG" 2>&1 \
    || note "suite: elastic smoke run failed (rc=$?) — informational"
  python - "$ELASTIC_LED" <<'PYEOF' \
    || note "suite: elastic smoke verdict failed — informational"
import json, sys
evs = []
try:
    with open(sys.argv[1]) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    evs.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
except OSError:
    pass
ref = [e for e in evs if e.get("event") == "elastic_refactor"]
ent = [e for e in evs if e.get("event") == "degraded_mode_enter"]
end = [e for e in evs if e.get("event") == "supervised_end"]
ok = (
    len(ref) >= 1 and len(ent) >= 1 and len(end) >= 1
    and end[-1].get("steps_done") == 8
    and ref[-1].get("new_mesh") == [2, 1, 1]
)
print(json.dumps({"elastic_smoke": {
    "ok": ok,
    "refactors": len(ref),
    "new_mesh": ref[-1].get("new_mesh") if ref else None,
    "restitch_s": ref[-1].get("restitch_s") if ref else None,
    "steps_done": end[-1].get("steps_done") if end else None,
    "degraded": end[-1].get("degraded") if end else None,
}}))
sys.exit(0 if ok else 1)
PYEOF
else
  note "suite: elastic smoke skipped (SKIP_ELASTIC_SMOKE=1)"
fi

# Sustained-soak smoke (informational; docs/SERVING.md "Load, overload &
# soak"): a seeded ~60s open-loop soak on a forced 4-device CPU mesh with
# a partial device loss injected mid-run — per-stream admission, fair
# packing, the pre-warm ladder, the requeue path and the machine-checked
# verdict all exercised in one bounded pass. The soak_smoke JSON line
# carries the conservation law (admitted + shed == submitted via the
# verdict's ok), the degraded window, and the zero-post-warmup-compile-
# stall criterion. Always CPU (the path under test is overload control,
# not the chip). Fails SOFT; SKIP_SOAK_SMOKE=1 skips.
if [[ -z "${SKIP_SOAK_SMOKE:-}" ]]; then
  SOAK_MIX="${OUT%.jsonl}.soak_mix.json"
  SOAK_AOT="${OUT%.jsonl}.soak_aot"
  rm -rf "$SOAK_AOT"
  cat > "$SOAK_MIX" <<'JSONEOF'
{
  "duration_s": 60,
  "seed": 42,
  "ramp": {"kind": "diurnal", "period_s": 30, "min_frac": 0.5},
  "engine": {"max_batch": 2, "max_per_stream": 4, "workers": 1},
  "streams": [
    {"name": "tenant-a", "rate_hz": 2.0,
     "scenarios": [
       {"grid": 16, "steps": 4, "alpha": 0.5, "seed": 1,
        "mesh": [4, 1, 1]},
       {"grid": 16, "steps": 3, "alpha": 0.8, "init": "gaussian",
        "seed": 2, "mesh": [4, 1, 1]}
     ]},
    {"name": "flood", "rate_hz": 4.0,
     "burst": {"every_s": 10, "len_s": 3, "multiplier": 5},
     "scenarios": [
       {"grid": 24, "steps": 20, "alpha": 0.3, "seed": 3,
        "mesh": [4, 1, 1]}
     ]}
  ]
}
JSONEOF
  SOAK_LINE=$(env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    HEAT3D_FAULTS="partial-device-loss:after=20:keep=2" \
    HEAT3D_AOT_CACHE="$SOAK_AOT" \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.cli serve --loadgen "$SOAK_MIX" \
    --duration "${SOAK_DURATION:-60}" --verdict \
    2>>"$SUITE_LOG" | tail -n 1) \
    || note "suite: soak smoke run failed (rc=$?) — informational"
  python - "$SOAK_LINE" <<'PYEOF' \
    || note "suite: soak smoke verdict failed — informational"
import json, sys
try:
    v = json.loads(sys.argv[1])["soak_verdict"]
except Exception:
    print(json.dumps({"soak_smoke": {"ok": False, "error": "no verdict"}}))
    sys.exit(1)
ok = bool(v.get("ok")) and v.get("slo") == "pass"
print(json.dumps({"soak_smoke": {
    "ok": ok, "arrivals": v.get("arrivals"),
    "submitted": v.get("submitted"), "admitted": v.get("admitted"),
    "shed": v.get("shed"), "requeues": v.get("requeues"),
    "degraded_s": v.get("degraded_s"),
    "compile_stall_after_warmup": v.get("compile_stall_after_warmup"),
    "sustained_member_gcell_per_s": v.get("sustained_member_gcell_per_s"),
    "slo": v.get("slo")}}))
sys.exit(0 if ok else 1)
PYEOF
else
  note "suite: soak smoke skipped (SKIP_SOAK_SMOKE=1)"
fi

# Monitored-soak smoke (informational; docs/OBSERVABILITY.md §8): the
# live SLO burn-rate leg, forced to alert — an impossible latency
# ceiling under --monitor --abort-on-burn must terminate the replay
# early (rc 1) with >=1 slo_burn_alert plus monitor_start /
# monitor_summary in the ledger and a machine-readable partial verdict
# (aborted == true). Proves the alerting path end-to-end the way the
# elastic smoke proves the failover path: by firing it. Always CPU.
# Fails SOFT; SKIP_MONITOR_SMOKE=1 skips.
if [[ -z "${SKIP_MONITOR_SMOKE:-}" ]]; then
  MON_MIX="${OUT%.jsonl}.monitor_mix.json"
  MON_LEDGER="${OUT%.jsonl}.monitor_ledger.jsonl"
  rm -f "$MON_LEDGER"
  cat > "$MON_MIX" <<'JSONEOF'
{
  "duration_s": 30,
  "seed": 7,
  "rate_hz": 3.0,
  "engine": {"max_batch": 2, "workers": 1},
  "monitor": {"interval_s": 0.25, "fast_window_s": 2, "slow_window_s": 4},
  "slo": {"objectives": [
    {"name": "impossible-p50", "kind": "serve_latency",
     "percentile": 50, "max_s": 0.000001}
  ]},
  "streams": [
    {"name": "tenant-a", "weight": 1,
     "scenarios": [{"grid": 12, "steps": 3, "alpha": 0.5, "seed": 1}]}
  ]
}
JSONEOF
  MON_RC=0
  MON_LINE=$(env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.cli serve --loadgen "$MON_MIX" \
    --monitor --abort-on-burn --verdict --ledger "$MON_LEDGER" \
    2>>"$SUITE_LOG" | tail -n 1) || MON_RC=$?
  # rc 1 is the EXPECTED outcome here (the soak is built to be aborted)
  [[ "$MON_RC" -eq 1 ]] \
    || note "suite: monitor smoke rc=$MON_RC (expected 1) — informational"
  python - "$MON_LINE" "$MON_LEDGER" <<'PYEOF' \
    || note "suite: monitor smoke verdict failed — informational"
import json, sys
try:
    v = json.loads(sys.argv[1])["soak_verdict"]
except Exception:
    print(json.dumps({"monitor_smoke": {"ok": False, "error": "no verdict"}}))
    sys.exit(1)
alerts = opens = summaries = 0
try:
    with open(sys.argv[2]) as f:
        for line in f:
            try:
                name = json.loads(line).get("event")
            except Exception:
                continue
            alerts += name == "slo_burn_alert"
            opens += name == "monitor_start"
            summaries += name == "monitor_summary"
except OSError:
    pass
mon = v.get("monitor") or {}
ok = (
    bool(v.get("aborted"))
    and v.get("abort_reason") == "slo_burn"
    and not v.get("ok")
    and alerts >= 1 and opens == 1 and summaries == 1
    and mon.get("alerts", 0) >= 1
)
print(json.dumps({"monitor_smoke": {
    "ok": ok, "aborted": v.get("aborted"), "partial": v.get("partial"),
    "alerts_in_ledger": alerts, "monitor": mon}}))
sys.exit(0 if ok else 1)
PYEOF
else
  note "suite: monitor smoke skipped (SKIP_MONITOR_SMOKE=1)"
fi

# Comm-probe smoke (informational; docs/OBSERVABILITY.md §9): the
# per-link halo probe on a forced 4-device CPU mesh — both x-axis links
# (lo, hi) must land comm_probe ledger events carrying plan-predicted
# bytes joined to a positive measured time, machine-checked with the
# JSON verdict on the console. Always CPU (the path under test is the
# probe plumbing, not the interconnect). Sub-minute. Fails SOFT;
# SKIP_COMM_SMOKE=1 skips.
if [[ -z "${SKIP_COMM_SMOKE:-}" ]]; then
  COMM_LED="${OUT%.jsonl}.comm.ledger.jsonl"
  : > "$COMM_LED"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    HEAT3D_COMM_PROBE_ITERS=3 \
    timeout -k 30 "${ROW_TIMEOUT:-900}" \
    python -m heat3d_tpu.obs.comm.probe --grid 16 --mesh 4 1 1 \
    --json --ledger "$COMM_LED" >> "$SUITE_LOG" 2>&1 \
    || note "suite: comm probe smoke failed (rc=$?) — informational"
  python - "$COMM_LED" <<'PYEOF' \
    || note "suite: comm probe verdict failed — informational"
import json, sys
rows = []
try:
    with open(sys.argv[1]) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    e = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if e.get("event") == "comm_probe":
                    rows.append(e)
except OSError:
    pass
links = sorted({(e.get("axis_name"), e.get("direction")) for e in rows})
ok = (
    links == [("x", "hi"), ("x", "lo")]
    and all(e.get("bytes_predicted", 0) > 0 for e in rows)
    and all(e.get("t_s", 0) > 0 for e in rows)
)
print(json.dumps({"comm_smoke": {
    "ok": ok, "rows": len(rows),
    "links": [".".join(l) for l in links],
    "gbps": [round(e.get("gbps", 0), 4) for e in rows],
}}))
sys.exit(0 if ok else 1)
PYEOF
else
  note "suite: comm probe smoke skipped (SKIP_COMM_SMOKE=1)"
fi
