"""IR-level SPMD certification — the program verifier behind
``heat3d lint --ir``.

Where the PR 6 checkers audit the repo's *source* (AST), this package
audits the *programs the source actually builds*: the judged config
matrix (:mod:`.programs`, pruned by the tuner's production validation)
is traced through ``make_step_fn`` / ``make_superstep_fn`` /
``EnsembleSolver`` to closed jaxprs, and four checker families certify
them — collective topology (ANL6xx), halo-footprint dataflow (ANL7xx),
dtype flow (ANL8xx) and the compiled memory contract (ANL9xx). Findings
report through the shared PR 6 framework (severity policy, inline +
baseline suppression, ``--json``) and fingerprint on
``(checker, config-key, invariant)`` — never on jaxpr pretty-printer
text, so baselines survive jax upgrades.

This is the certification layer the halo-path refactors (persistent
exchange plans — ROADMAP) land against: a change that
desynchronizes the exchange topology, starves a tap chain of ghost
width, leaks a dtype, or breaks the memory contract reds this lint on
CPU, before any pod session.
"""

from __future__ import annotations

from typing import List, Optional

from heat3d_tpu.analysis.findings import Finding

# checker name -> module path, mirroring analysis.CHECKERS (the CLI
# resolves lazily; tracing imports jax only when a family actually runs)
IR_CHECKERS = {
    "ir-collectives": "heat3d_tpu.analysis.ir.collectives",
    "ir-footprint": "heat3d_tpu.analysis.ir.footprint",
    "ir-dtype": "heat3d_tpu.analysis.ir.dtypeflow",
    "ir-memory": "heat3d_tpu.analysis.ir.memcontract",
}


def run_ir_checkers(root: str, names: List[str]) -> List[Finding]:
    """Trace the judged matrix ONCE, run every named family over it.
    Mirrors ``analysis.cli.run_checkers``: a crashed family is an ANL000
    error finding, never a silent green. Emits the ``ir_lint_start`` /
    ``ir_lint_verdict`` ledger events (fail-soft NullLedger when no
    ledger is active)."""
    findings: List[Finding] = []
    # The judged matrix's partitioned programs must carry GENUINE
    # sub-block permutes: FORCE the plan partition granularity floor to
    # zero for the whole verifier pass (the 16^3 judged faces would
    # otherwise ship whole — an operator's exported
    # HEAT3D_PLAN_PART_MIN_BYTES must not let the partition invariants
    # certify a degenerate schedule), and restore it afterwards so an
    # in-process caller's later plans keep the real floor (tracing is
    # lazy: the env must hold through the family loop, not just the
    # matrix build; plan cache keys include the floor, so no stale plan
    # can cross the restore).
    import os

    _FLOOR = "HEAT3D_PLAN_PART_MIN_BYTES"
    saved_floor = os.environ.get(_FLOOR)
    os.environ[_FLOOR] = "0"
    try:
        return _run_ir_checkers(root, names, findings)
    finally:
        if saved_floor is None:
            os.environ.pop(_FLOOR, None)
        else:
            os.environ[_FLOOR] = saved_floor


def _run_ir_checkers(
    root: str, names: List[str], findings: List[Finding]
) -> List[Finding]:
    import importlib

    from heat3d_tpu import obs
    from heat3d_tpu.analysis.ir import programs

    devices = None
    cases = None
    try:
        devices = programs.ensure_devices()
        cases = programs.judged_matrix()
    except Exception as e:  # noqa: BLE001 - surfaced as a finding
        findings.append(
            Finding(
                checker="ir-matrix",
                severity="error",
                path="heat3d_tpu/analysis/ir",
                line=0,
                code="ANL000",
                symbol="judged_matrix",
                message=(
                    f"judged-matrix build crashed: {type(e).__name__}: "
                    f"{e} — no IR program was certified (a broken "
                    "matrix is a silent green)"
                ),
            )
        )
        cases = []
    obs.get().event(
        "ir_lint_start",
        families=list(names),
        cases=len(cases),
        devices=devices,
    )
    want = programs.wanted_devices()
    if cases and devices is not None and devices < want:
        findings.append(
            Finding(
                checker="ir-matrix",
                severity="warning",
                path="heat3d_tpu/analysis/ir",
                line=0,
                code="ANL610",
                symbol="degraded-matrix",
                message=(
                    f"jax initialized with {devices} device(s) before "
                    f"the IR lint could force its {want}-device CPU "
                    "mesh (HEAT3D_IR_DEVICES): the judged matrix lost "
                    "its block/slab meshes and ensemble programs, so "
                    "part of the collective topology is NOT certified "
                    "this run — run `heat3d lint --ir` in a fresh "
                    "process"
                ),
            )
        )
    for name in names:
        try:
            mod = importlib.import_module(IR_CHECKERS[name])
            findings.extend(mod.check(root, cases=cases))
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            findings.append(
                Finding(
                    checker=name,
                    severity="error",
                    path="heat3d_tpu/analysis/ir",
                    line=0,
                    code="ANL000",
                    symbol=name,
                    message=(
                        f"checker crashed: {type(e).__name__}: {e} — fix "
                        "the checker (a broken lint is a silent green)"
                    ),
                )
            )
    counts = {"error": 0, "warning": 0, "info": 0}
    for f in findings:
        counts[f.severity] = counts.get(f.severity, 0) + 1
    obs.get().event("ir_lint_verdict", families=list(names), **counts)
    return findings
