#!/usr/bin/env python
"""Compile-only evidence for the judged pod configs: what XLA inserts.

Lowers the full distributed step for each judged multi-chip config
(BASELINE.json configs 2-5) over a device-free AbstractMesh — the
single-chip dev box's substitute for a pod (SURVEY.md §4, §7.0) — and
counts the collectives in the stablehlo text: ``collective_permute``
(the halo exchange: MPI_Isend/Irecv analogue riding ICI) and
``all_reduce`` (the fp32 residual: MPI_Allreduce analogue). Writes a
markdown table (default docs/LOWERING.md) so the ICI design is a
committed, regenerable artifact rather than an assertion.

Grids are scaled down (the judged GLOBAL grids don't fit one host's
tracing memory budget at fp32 x 4096^3; collective structure depends on
mesh topology + stencil + tb, not on the local block size — the real
grid only changes block shapes). The table records both the judged and
the lowered grid.

Usage: python scripts/lowering_report.py [out.md]
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.parallel.step import make_step_fn, make_superstep_fn
from heat3d_tpu.parallel.topology import abstract_mesh, lower_for_mesh

# (label, judged grid, mesh, stencil, precision, tb) — BASELINE.json
# configs
CONFIGS = [
    ("2: 1024^3 slab v5p-8", 1024, (8, 1, 1), "7pt", Precision.fp32(), 1),
    ("3: 2048^3 block v5p-8", 2048, (2, 2, 2), "7pt", Precision.fp32(), 1),
    ("4: 4096^3 27pt v5p-64", 4096, (4, 4, 4), "27pt", Precision.fp32(), 1),
    ("5: 4096^3 bf16 v5p-128", 4096, (8, 4, 4), "7pt", Precision.bf16(), 1),
    ("2+tb: 1024^3 slab, tb=2", 1024, (8, 1, 1), "7pt", Precision.fp32(), 2),
]


def count(txt: str, op: str) -> int:
    # Lowered.as_text() spells ops with '_' or '-' depending on the JAX
    # version/pipeline (the repo's lowering tests accept both for the
    # same reason); a spelling miss here would report a false regression
    pat = op.replace("_", "[_-]")
    return len(re.findall(rf"\b{pat}\b", txt))


def lower_one(label, judged, mesh_shape, kind, prec, tb):
    # small local blocks, same topology: collective structure is identical
    local = 8
    grid = tuple(local * m for m in mesh_shape)
    cfg = SolverConfig(
        grid=GridConfig(shape=grid),
        stencil=StencilConfig(kind=kind, bc=BoundaryCondition.DIRICHLET),
        mesh=MeshConfig(shape=mesh_shape),
        precision=prec,
        backend="jnp",
        time_blocking=tb,
    )
    am = abstract_mesh(cfg.mesh)
    if tb > 1:
        fn = make_superstep_fn(cfg, am)
    else:
        fn = make_step_fn(cfg, am, with_residual=True)
    dtype = jnp.dtype(prec.storage)
    txt = lower_for_mesh(fn, cfg.mesh, (grid, dtype, P("x", "y", "z"))).as_text()
    sharded_axes = sum(1 for m in mesh_shape if m > 1)
    return {
        "label": label,
        "judged": f"{judged}^3",
        "lowered": "x".join(map(str, grid)),
        "mesh": "x".join(map(str, mesh_shape)),
        "chips": cfg.mesh.num_devices,
        "stencil": kind,
        "dtype": str(dtype),
        "tb": tb,
        "permutes": count(txt, "collective_permute"),
        "allreduce": count(txt, "all_reduce"),
        "expect_permutes": 2 * sharded_axes,
    }


def main(argv=None) -> int:
    default_out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "LOWERING.md",
    )
    out_path = (argv or sys.argv[1:] or [default_out])[0]
    rows = [lower_one(*c) for c in CONFIGS]
    lines = [
        "# Lowering report — judged pod configs, compile-only evidence",
        "",
        "Regenerate: `python scripts/lowering_report.py`. Each row lowers",
        "the FULL distributed step over a device-free AbstractMesh of the",
        "judged topology and counts the collectives XLA inserted",
        "(`collective_permute` = the ghost-cell halo exchange riding ICI —",
        "the reference's CUDA-aware MPI_Isend/Irecv; `all_reduce` = the",
        "fp32 residual — its MPI_Allreduce). Expected permute count:",
        "2 directions per SHARDED mesh axis (size-1 axes short-circuit to",
        "local wraps/BC fills), independent of grid size; tb=2 supersteps",
        "exchange width-2 ghosts in the same 2-per-axis pattern.",
        "",
        "Beyond compile-only: the judged pod topologies also EXECUTE at",
        "tiny scale on virtual CPU meshes — (4,4,4) over 64 devices and",
        "(8,4,4) over 128 — bitwise-matching the undecomposed run",
        "(tests/test_multidevice.py::test_judged_pod_topology_executes).",
        "",
        "| Config | Judged grid | Lowered grid | Mesh | Chips | Stencil |"
        " Dtype | tb | collective_permute | all_reduce |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    ok = True
    for r in rows:
        want = r["expect_permutes"]
        flag = "" if r["permutes"] == want else f" (expected {want}!)"
        ok = ok and r["permutes"] == want
        lines.append(
            f"| {r['label']} | {r['judged']} | {r['lowered']} | {r['mesh']} |"
            f" {r['chips']} | {r['stencil']} | {r['dtype']} | {r['tb']} |"
            f" {r['permutes']}{flag} | {r['allreduce']} |"
        )
    lines.append("")
    text = "\n".join(lines)
    with open(out_path, "w") as f:
        f.write(text)
    print(text)
    print(f"wrote {out_path}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
