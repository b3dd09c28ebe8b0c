"""IR checker: memory contract — the compiler's own numbers cross-check
the static estimators.

The ANL3xx tier audits the repo's hand-built VMEM/traffic arithmetic
against capacity tables; this family closes the loop from the other
side, compiling representative judged programs and joining
``compiled.memory_analysis()`` / ``cost_analysis()`` against what the
static models promise:

- **ANL901** — program signature drift: the compiled step's per-device
  argument/output footprint must be exactly the field shard (one array
  in, one array out — plus the residual scalar on residual programs). A
  few stray KiB means the program grew an input nobody budgeted (a
  captured buffer, an accidental constant promotion).
- **ANL902** — temp-arena overrun: XLA's temp allocation for the
  exchange-path chain must fit the static model (the width-k padded
  slab in compute dtype, a second live slab for the ping-pong, one for
  the exchange concatenate, per application headroom). Exceeding it
  means the traced program materializes buffers the HBM budget tables
  never priced.
- **ANL903** — cost-model drift: ``cost_analysis`` flops vs the honest
  raw-trapezoid model (``parallel.step.superstep_cell_updates`` x
  ``core.stencils.chain_ops_for``) must agree within a wide band, and
  bytes accessed must at least cover reading+writing the shard. XLA's
  CPU flop counting is approximate — the band is a tripwire for
  order-of-magnitude drift (an accidentally unrolled loop, a doubled
  chain), not a precise audit.
- **ANL904** — (info) the joined numbers per compiled case, so the
  roofline's inputs are visible from the lint output.
- **ANL905** — VMEM budget adjudication: the generation-aware budget
  (``analysis.vmem.chip_vmem_budget_for``) must sit within every known
  generation's VMEM capacity — the machine-checked resolution of the old
  standing ANL305 warning.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from heat3d_tpu.analysis.findings import ERROR, INFO, WARNING, Finding

CHECKER = "ir-memory"

MIB = 1024 * 1024

# cost_analysis flops vs the static model: order-of-magnitude tripwire
_FLOPS_BAND = (0.1, 10.0)
# argument/output size slack: scalars, tuple metadata
_SIG_SLACK = 4096


def _finding(case_key, path, code, severity, invariant, message) -> Finding:
    return Finding(
        checker=CHECKER,
        severity=severity,
        path=path,
        line=0,
        code=code,
        symbol=f"{case_key}|{invariant}",
        message=f"[{case_key}] {message}",
    )


def _shard_bytes(cfg, dtype) -> int:
    n = int(np.prod(cfg.local_shape))
    return n * np.dtype(dtype).itemsize


def temp_model_bytes(cfg) -> int:
    """Static ceiling for XLA's temp arena on the exchange-path chain:
    the width-k padded slab (compute dtype) plus one live predecessor
    slab per concurrent stage, the exchange concatenate, and fixed
    headroom for masks/faces. Deliberately generous — the finding is for
    programs that materialize whole extra field copies, not for buffer
    assignment noise."""
    k = max(1, cfg.time_blocking)
    r = 1  # both stencil families are radius-1
    slab = int(
        np.prod([n + 2 * k * r for n in cfg.local_shape])
    ) * np.dtype(cfg.precision.compute).itemsize
    return (3 + k) * slab + 2 * MIB


def _check_compiled(case, out: List[Finding]) -> None:
    cfg = case.cfg
    compiled = case.compiled()
    storage = np.dtype(cfg.precision.storage)
    shard = _shard_bytes(cfg, storage)

    ma = compiled.memory_analysis()
    arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
    outb = int(getattr(ma, "output_size_in_bytes", 0) or 0)
    temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)

    if abs(arg - shard) > _SIG_SLACK or outb < shard or (
        outb - shard
    ) > _SIG_SLACK:
        out.append(
            _finding(
                case.key,
                case.path,
                "ANL901",
                ERROR,
                "program-signature",
                f"compiled per-device footprint drifted: arguments "
                f"{arg} B / outputs {outb} B vs the one-shard contract "
                f"{shard} B (local {cfg.local_shape}, {storage}): the "
                "program carries buffers the two-buffer ping-pong loop "
                "never budgeted",
            )
        )

    ceiling = temp_model_bytes(cfg)
    if temp > ceiling:
        out.append(
            _finding(
                case.key,
                case.path,
                "ANL902",
                WARNING,
                "temp-arena",
                f"XLA temp arena {temp / MIB:.2f} MiB exceeds the "
                f"static exchange-path model's {ceiling / MIB:.2f} MiB "
                f"(width-{cfg.time_blocking} slab + live stages): the "
                "program materializes buffers the HBM budget tables "
                "never priced",
            )
        )

    flops, bytes_ = _extract_cost(compiled)
    model = _flops_model(cfg)
    if flops and model:
        ratio = flops / model
        if not (_FLOPS_BAND[0] <= ratio <= _FLOPS_BAND[1]):
            out.append(
                _finding(
                    case.key,
                    case.path,
                    "ANL903",
                    WARNING,
                    "flops-model",
                    f"compiled flops {flops:.3g} vs the raw-trapezoid "
                    f"model {model:.3g} (ratio {ratio:.2f}) is outside "
                    f"the {_FLOPS_BAND} band: the static cost model no "
                    "longer describes this program",
                )
            )
    if bytes_ is not None and bytes_ < 2 * shard:
        out.append(
            _finding(
                case.key,
                case.path,
                "ANL903",
                WARNING,
                "bytes-floor",
                f"compiled bytes accessed {bytes_:.3g} below the "
                f"read+write floor {2 * shard} of one shard: the cost "
                "join under-reports traffic",
            )
        )
    out.append(
        _finding(
            case.key,
            case.path,
            "ANL904",
            INFO,
            "joined-numbers",
            f"compiled per-device: args {arg} B, out {outb} B, temp "
            f"{temp / MIB:.2f} MiB (model ceiling "
            f"{temp_model_bytes(cfg) / MIB:.2f}), flops {flops}, bytes "
            f"{bytes_} (flops model {model:.3g})",
        )
    )


def _extract_cost(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend may not report
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    bytes_ = ca.get("bytes accessed")
    return (
        float(flops) if isinstance(flops, (int, float)) else None,
        float(bytes_) if isinstance(bytes_, (int, float)) else None,
    )


def _flops_model(cfg) -> float:
    """Per-device raw flops of one superstep call: the recompute
    trapezoid (the honest PR 5 accounting) times the chain's ops/cell."""
    from heat3d_tpu.core.stencils import chain_ops_for
    from heat3d_tpu.parallel.step import superstep_cell_updates

    raw, _ = superstep_cell_updates(cfg)
    return float(raw) * float(chain_ops_for(cfg.stencil.kind))


def check_gate_adjudication(
    chip_table: Optional[Dict[str, int]] = None,
    budget_for=None,
    live_budget=None,
    live_generation=None,
) -> List[Finding]:
    """ANL905: the resolved VMEM budget vs chip capacity, from
    two sides. (a) Per generation, ``chip_vmem_budget_for`` vs the
    capacity table — tautological today (the function reads the table)
    but a tripwire against future edits that decouple them. (b) The
    LIVE resolution, ``HEAT3D_VMEM_BYTES`` override included: an
    operator override above the current part's capacity admits kernels
    Mosaic cannot allocate — the one mis-set knob the old
    ANL305 warning existed to prevent, now adjudicated instead of
    warned about. Parameterized for the seeded-violation tests."""
    from heat3d_tpu.analysis import vmem

    table = chip_table if chip_table is not None else vmem.CHIP_VMEM_BYTES
    budget_for = budget_for or vmem.chip_vmem_budget_for
    out: List[Finding] = []
    for gen, cap in sorted(table.items()):
        budget = budget_for(gen)
        if budget > cap:
            out.append(
                _finding(
                    "gate",
                    "heat3d_tpu/analysis/vmem.py",
                    "ANL905",
                    ERROR,
                    f"vmem-budget:{gen}",
                    f"the VMEM budget resolves {budget / MIB:.0f} MiB on "
                    f"{gen}, which has {cap / MIB:.0f} MiB VMEM: it "
                    "admits kernels Mosaic cannot allocate there "
                    "(generation table drifted)",
                )
            )
    if live_generation is None:
        from heat3d_tpu.tune.cache import chip_generation

        live_generation = chip_generation()
    if live_generation in table:
        resolved = (
            live_budget if live_budget is not None
            else vmem._chip_vmem_budget()
        )
        cap = table[live_generation]
        if resolved > cap:
            out.append(
                _finding(
                    "gate",
                    "heat3d_tpu/analysis/vmem.py",
                    "ANL905",
                    ERROR,
                    "vmem-budget:live",
                    f"the LIVE VMEM budget resolution is "
                    f"{resolved / MIB:.0f} MiB on this "
                    f"{live_generation} ({cap / MIB:.0f} MiB VMEM) — "
                    "HEAT3D_VMEM_BYTES is set above the part's "
                    "capacity, so it admits unallocatable "
                    "kernels; unset it or lower it",
                )
            )
    return out


def check_cases(
    cases: Sequence, compile_enabled: Optional[bool] = None
) -> List[Finding]:
    from heat3d_tpu.analysis.ir import programs

    if compile_enabled is None:
        compile_enabled = programs.compile_enabled()
    out: List[Finding] = []
    targets = [c for c in cases if c.compile]
    if not compile_enabled:
        out.append(
            _finding(
                "matrix",
                "heat3d_tpu/analysis/ir/memcontract.py",
                "ANL904",
                INFO,
                "compile-skipped",
                f"HEAT3D_IR_COMPILE=0: {len(targets)} compile targets "
                "skipped — memory/cost joins not certified this run",
            )
        )
        targets = []
    for case in targets:
        _check_compiled(case, out)
    out.extend(check_gate_adjudication())
    return out


def check(root: str, cases: Optional[Sequence] = None) -> List[Finding]:
    if cases is None:
        from heat3d_tpu.analysis.ir import programs

        programs.ensure_devices()
        cases = programs.judged_matrix()
    return check_cases(cases)
