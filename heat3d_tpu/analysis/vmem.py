"""Checker: Pallas VMEM budget estimator — over-budget kernels fail lint,
not a pod session.

Every Pallas kernel family in ``ops/`` gates itself on an empirically
tuned explicit-buffer budget (``stream_supported``/``streamk_supported``/
``direct_supported``'s ring + pipeline arithmetic) plus the shared Mosaic
scoped-stack budget for the tap chain. Those budgets are plain module
constants; nothing related them to what a chip actually *has* — a PR
nudging one past a generation's VMEM capacity would compile fine, pass
every CPU test, and first fail as a Mosaic allocation error on the pod.

Three audits:

1. **AST, ring-slot invariant** (ANL302): every ``pltpu.VMEM`` scratch
   ring in ``ops/`` whose leading dim is a literal must be the 3-slot
   ring the streaming schedule assumes (slot ``p % 3``; a 4-slot ring
   silently breaks the slot arithmetic, a 2-slot ring corrupts planes).
2. **AST, cost provenance** (ANL301): every ``pl.pallas_call`` carries a
   ``cost_estimate`` — the roofline/attribution path treats Mosaic calls
   as opaque without one.
3. **Arithmetic, budget-vs-capacity** (ANL303/304/305 + headroom info):
   drives the repo's OWN estimators (``_stream_vmem_bytes``,
   ``_stream2_vmem_bytes``, ``_streamk_vmem_bytes`` with its 3-slot rings
   at k ≤ 4, ``stencil_pallas_direct._vmem_bytes``) over the judged-config
   local shapes and checks each family's admit budget and the admitted
   worst-case footprints against per-chip-generation VMEM capacities
   (margin-adjusted: Mosaic needs headroom for spills and semaphores).
   The scoped tap-stack budget is checked against the compiler's 16 MiB
   scoped-vmem pool separately (it is a separate pool from the explicit
   buffers — see ops/stencil_pallas.py).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Tuple

from heat3d_tpu.analysis import astutil
from heat3d_tpu.analysis.findings import ERROR, INFO, Finding

CHECKER = "vmem-budget"

MIB = 1024 * 1024


# Per-generation VMEM budget (bytes/core): the scoped VMEM a Mosaic
# kernel gets by default (the v5e compiler refuses a kernel sized to
# 32 MiB with RESOURCE_EXHAUSTED). Keyed by the normalized
# chip-generation strings the tuning cache derives from device_kind
# (tune.cache.chip_generation). The budget audit below and the IR
# memory-contract check (ANL905) read it; no kernel gate does.
CHIP_VMEM_BYTES = {
    "tpu-v4": 16 * MIB,
    "tpu-v5-lite": 16 * MIB,
    "tpu-v5p": 32 * MIB,
    "tpu-v6-lite": 32 * MIB,
}


def chip_vmem_budget_for(generation: str) -> int:
    """The whole-chip VMEM budget of ``generation`` (a normalized
    ``tune.cache.chip_generation`` string) absent an env override. Off
    the chip it is the smallest budget in the table, so a shape admitted
    there fits every listed chip. A TPU generation missing from the
    table is an error: its budget is unknown."""
    if generation in CHIP_VMEM_BYTES:
        return CHIP_VMEM_BYTES[generation]
    if generation == "cpu":
        return min(CHIP_VMEM_BYTES.values())
    raise ValueError(
        f"no VMEM budget for chip generation {generation!r}: add it to "
        "analysis/vmem.CHIP_VMEM_BYTES (or set HEAT3D_VMEM_BYTES)"
    )


def _chip_vmem_budget() -> int:
    """The live whole-chip VMEM budget: ``HEAT3D_VMEM_BYTES`` (operator
    override) > the per-generation table keyed on the live chip
    generation."""
    import os

    env = os.environ.get("HEAT3D_VMEM_BYTES")
    if env:
        return int(env)
    from heat3d_tpu.tune.cache import chip_generation

    return chip_vmem_budget_for(chip_generation())


# Mosaic's default scoped-vmem pool (the tap-chain stack lives here — a
# separate pool from the explicit ring/pipeline buffers).
SCOPED_STACK_CAP = 16 * MIB

# fraction of capacity the explicit buffers may take (spill/semaphore
# headroom)
MARGIN = 0.85

# judged-config local blocks (BASELINE.json ladder): single-chip rows,
# the 1024^3 x-slab shard, and the pod-scale 3D-block shard
JUDGED_LOCAL_SHAPES: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 128),
    (256, 256, 256),
    (512, 512, 512),
    (1024, 1024, 1024),
    (128, 1024, 1024),
)
_ITEMSIZES = (4, 2)  # fp32, bf16 storage


def _ast_findings(root: str, files: Sequence[str]) -> List[Finding]:
    findings: List[Finding] = []
    for path in files:
        tree = astutil.parse_file(path)
        if tree is None:
            continue
        relpath = astutil.rel(root, path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.call_name(node) or ""
            tail = name.rsplit(".", 1)[-1]
            if tail == "pallas_call":
                kwargs = {kw.arg for kw in node.keywords}
                if "cost_estimate" not in kwargs:
                    findings.append(
                        Finding(
                            checker=CHECKER,
                            severity=ERROR,
                            path=relpath,
                            line=node.lineno,
                            code="ANL301",
                            symbol=_sym(node),
                            message=(
                                "pl.pallas_call without a cost_estimate: "
                                "XLA sees Mosaic calls as opaque, so this "
                                "kernel's flops/bytes vanish from roofline "
                                "attribution and step_cost provenance — "
                                "attach pl.CostEstimate(...)"
                            ),
                        )
                    )
            elif tail == "VMEM" and name.endswith("pltpu.VMEM"):
                slots = _leading_literal(node)
                if slots is not None and slots != 3:
                    findings.append(
                        Finding(
                            checker=CHECKER,
                            severity=ERROR,
                            path=relpath,
                            line=node.lineno,
                            code="ANL302",
                            symbol=_sym(node),
                            message=(
                                f"VMEM scratch ring has {slots} slots; the "
                                "streaming schedule's slot arithmetic "
                                "(plane p lives in slot p % 3) requires "
                                "exactly 3 — a different ring size breaks "
                                "plane residency silently"
                            ),
                        )
                    )
    return findings


def _sym(node: ast.AST) -> Optional[str]:
    fn = astutil.enclosing_function(node)
    return astutil.qualname(fn) if fn is not None else None


def _leading_literal(vmem_call: ast.Call) -> Optional[int]:
    """The first element of ``pltpu.VMEM((N, ...), dtype)`` when it is a
    literal int, else None (dynamic ring extents are shape math, not slot
    counts)."""
    if not vmem_call.args:
        return None
    shape = vmem_call.args[0]
    if isinstance(shape, ast.Tuple) and shape.elts:
        first = shape.elts[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, int):
            return first.value
    return None


def _budget_findings(
    chip_table: Dict[str, int], margin: float
) -> List[Finding]:
    """Drive the real estimator modules (imported, not parsed — the
    arithmetic IS the artifact under audit)."""
    from heat3d_tpu.ops import stencil_pallas as sp
    from heat3d_tpu.ops import stencil_pallas_direct as spd

    findings: List[Finding] = []
    budgets = [
        ("windowed per-step budget (_VMEM_STEP_BUDGET)",
         "heat3d_tpu/ops/stencil_pallas.py", sp._VMEM_STEP_BUDGET),
        ("streaming ring budget (_STREAM_VMEM_BUDGET)",
         "heat3d_tpu/ops/stencil_pallas.py", sp._STREAM_VMEM_BUDGET),
        ("fused stream2/streamk budget (_FUSED_STREAM_VMEM_BUDGET)",
         "heat3d_tpu/ops/stencil_pallas.py", sp._FUSED_STREAM_VMEM_BUDGET),
        ("direct-kernel ring budget (_VMEM_BUDGET)",
         "heat3d_tpu/ops/stencil_pallas_direct.py", spd._VMEM_BUDGET),
    ]
    floor_gen = min(chip_table, key=chip_table.get)
    for label, path, budget in budgets:
        for gen, cap in sorted(chip_table.items()):
            if budget > cap * margin:
                findings.append(
                    Finding(
                        checker=CHECKER,
                        severity=ERROR,
                        path=path,
                        line=0,
                        code="ANL303",
                        symbol=label.split("(")[-1].rstrip(")"),
                        message=(
                            f"{label} = {budget / MIB:.1f} MiB exceeds "
                            f"{margin:.0%} of {gen}'s {cap / MIB:.0f} MiB "
                            "VMEM: the gate would admit a kernel Mosaic "
                            "cannot allocate on that generation"
                        ),
                    )
                )
    if sp._TAP_STACK_BUDGET > SCOPED_STACK_CAP:
        findings.append(
            Finding(
                checker=CHECKER,
                severity=ERROR,
                path="heat3d_tpu/ops/stencil_pallas.py",
                line=0,
                code="ANL304",
                symbol="_TAP_STACK_BUDGET",
                message=(
                    f"tap-stack budget {sp._TAP_STACK_BUDGET / MIB:.1f} MiB "
                    f"exceeds Mosaic's {SCOPED_STACK_CAP / MIB:.0f} MiB "
                    "scoped-vmem pool — chains admitted by the gate would "
                    "fail scoped-stack reservation at compile"
                ),
            )
        )
    # The resolved budget vs capacity, including the live
    # HEAT3D_VMEM_BYTES override, is adjudicated by the IR
    # memory-contract checker (ANL905, analysis/ir/memcontract.py).

    # admitted worst-case footprints over the judged shapes: anything the
    # gates admit must fit the floor generation, with headroom reported
    floor_cap = chip_table[floor_gen]
    for shape in JUDGED_LOCAL_SHAPES:
        for item in _ITEMSIZES:
            families = []
            if sp.stream_supported(shape, item, item):
                families.append(
                    ("stream", sp._stream_vmem_bytes(shape, item, item))
                )
            if sp.stream2_supported(shape, item, item):
                families.append(
                    ("stream2", sp._stream2_vmem_bytes(shape, item, item))
                )
            for k in (2, 3, 4):
                if sp.streamk_supported(shape, k, item, item):
                    families.append(
                        (
                            f"streamk k={k}",
                            sp._streamk_vmem_bytes(shape, k, item, item),
                        )
                    )
            for family, footprint in families:
                if footprint > floor_cap * margin:
                    findings.append(
                        Finding(
                            checker=CHECKER,
                            severity=ERROR,
                            path="heat3d_tpu/ops/stencil_pallas.py",
                            line=0,
                            code="ANL306",
                            symbol=family,
                            message=(
                                f"{family} admits local shape {shape} "
                                f"itemsize {item} at "
                                f"{footprint / MIB:.1f} MiB — over "
                                f"{margin:.0%} of {floor_gen}'s "
                                f"{floor_cap / MIB:.0f} MiB VMEM"
                            ),
                        )
                    )
                elif footprint > floor_cap * margin * 0.95:
                    findings.append(
                        Finding(
                            checker=CHECKER,
                            severity=INFO,
                            path="heat3d_tpu/ops/stencil_pallas.py",
                            line=0,
                            code="ANL307",
                            symbol=family,
                            message=(
                                f"{family} at local shape {shape} itemsize "
                                f"{item} uses {footprint / MIB:.1f} MiB — "
                                f"within 5% of the {floor_gen} admit "
                                "ceiling (headroom watch)"
                            ),
                        )
                    )
    return findings


def check(
    root: str,
    files: Optional[Sequence[str]] = None,
    chip_table: Optional[Dict[str, int]] = None,
    margin: float = MARGIN,
    arithmetic: bool = True,
) -> List[Finding]:
    import os

    paths = list(
        files
        if files is not None
        else (
            p
            for p in astutil.iter_py_files(root, subdirs=("heat3d_tpu",))
            if os.sep + "ops" + os.sep in p
        )
    )
    findings = _ast_findings(root, paths)
    if arithmetic and files is None:
        if chip_table is None:
            chip_table = CHIP_VMEM_BYTES
        findings.extend(_budget_findings(chip_table, margin))
    elif arithmetic and chip_table is not None:
        findings.extend(_budget_findings(chip_table, margin))
    return findings
