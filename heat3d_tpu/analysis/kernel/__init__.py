"""Kernel-tier certification — the Pallas DMA/race verifier behind
``heat3d lint --kernel``.

The AST tier (PR 6) audits source, the IR tier (PR 9) audits the traced
programs — and both documented the same blind spot: ``pallas_call``
bodies were opaque, so every in-kernel DMA (the slab exchanges) was
certified only by interpret-tier *value* parity, which proves values
but not schedules. This package closes that: every repo Pallas kernel
body is traced to its jaxpr on CPU (:mod:`.programs` — kernel functions
over ``Ref``s trace without a TPU), a concrete interpreter replays the
full grid at every judged device position (:mod:`.interp`), and four
checker families certify the schedule —

- :mod:`.dma` (ANL1001-1005): every DMA start has exactly one matching
  wait on every control path, no wait-without-start, no semaphore
  aliasing across in-flight copies, balanced neighbor barriers;
- :mod:`.races` (ANL1011-1013): a happens-before graph over ``Ref``
  reads/writes and DMA edges proving the %3 VMEM rings never read a
  slot a still-in-flight copy or a recycled-slot write may clobber;
- :mod:`.coverage` (ANL1021-1023): each output element written exactly
  once across the grid, via index-map abstract interpretation;
- :mod:`.remote` (ANL1031-1033): every ``make_async_remote_copy``
  device target realizes the ±1 neighbor bijection
  ``parallel.halo.shift_perm`` builds, and plan-driven exchanges
  realize the ``ExchangePlan`` axis schedule.

Findings report through the shared framework (severity policy, inline +
baseline suppression, ``--json``) and fingerprint on
``(checker, kernel-case key, invariant)`` — never jaxpr text, the same
stability contract the IR tier pinned.
"""

from __future__ import annotations

from typing import List

from heat3d_tpu.analysis.findings import Finding

# checker name -> module path, mirroring analysis.CHECKERS / IR_CHECKERS
KERNEL_CHECKERS = {
    "kernel-dma": "heat3d_tpu.analysis.kernel.dma",
    "kernel-races": "heat3d_tpu.analysis.kernel.races",
    "kernel-coverage": "heat3d_tpu.analysis.kernel.coverage",
    "kernel-remote": "heat3d_tpu.analysis.kernel.remote",
}


def run_kernel_checkers(root: str, names: List[str]) -> List[Finding]:
    """Trace the judged kernel matrix ONCE, run every named family over
    it. Mirrors the AST/IR runners: a crashed family or a broken matrix
    is an ANL000 error finding, never a silent green. Emits the
    ``kernel_lint_start`` / ``kernel_lint_verdict`` ledger events
    (fail-soft NullLedger when no ledger is active)."""
    import importlib

    from heat3d_tpu import obs
    from heat3d_tpu.analysis.kernel import programs

    findings: List[Finding] = []
    devices = None
    cases = None
    try:
        devices = programs.ensure_devices()
        cases = programs.judged_kernels()
    except Exception as e:  # noqa: BLE001 - surfaced as a finding
        findings.append(
            Finding(
                checker="kernel-matrix",
                severity="error",
                path="heat3d_tpu/analysis/kernel",
                line=0,
                code="ANL000",
                symbol="judged_kernels",
                message=(
                    f"kernel-matrix build crashed: {type(e).__name__}: "
                    f"{e} — no kernel was certified (a broken matrix is "
                    "a silent green)"
                ),
            )
        )
        cases = []
    obs.get().event(
        "kernel_lint_start",
        families=list(names),
        cases=len(cases),
        devices=devices,
    )
    want = programs.wanted_devices()
    if cases and devices is not None and devices < want:
        findings.append(
            Finding(
                checker="kernel-matrix",
                severity="warning",
                path="heat3d_tpu/analysis/kernel",
                line=0,
                code="ANL1040",
                symbol="degraded-matrix",
                message=(
                    f"jax initialized with {devices} device(s) before the "
                    f"kernel lint could force its {want}-device CPU mesh "
                    "(HEAT3D_KERNEL_LINT_DEVICES): the judged matrix lost "
                    "its DMA exchange rings, so "
                    "the DMA/remote families certified almost nothing "
                    "this run — run `heat3d lint --kernel` in a fresh "
                    "process"
                ),
            )
        )
    for name in names:
        try:
            mod = importlib.import_module(KERNEL_CHECKERS[name])
            findings.extend(mod.check(root, cases=cases))
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            findings.append(
                Finding(
                    checker=name,
                    severity="error",
                    path="heat3d_tpu/analysis/kernel",
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
    obs.get().event("kernel_lint_verdict", families=list(names), **counts)
    return findings
