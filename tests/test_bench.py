"""Benchmark-as-test tier (SURVEY.md §4): the harness runs on tiny grids
and emits well-formed results."""

import json

import numpy as np

import pytest

from heat3d_tpu.bench.harness import bench_halo, bench_throughput
from heat3d_tpu.core.config import (
    GridConfig,
    MeshConfig,
    SolverConfig,
    StencilConfig,
)


def tiny_cfg():
    return SolverConfig(
        grid=GridConfig.cube(16), mesh=MeshConfig(shape=(1, 1, 1)), backend="jnp"
    )


@pytest.mark.tpu_smoke
def test_throughput_result_shape():
    r = bench_throughput(tiny_cfg(), steps=3, warmup=1, repeats=2)
    assert r["gcell_per_sec"] > 0
    assert r["gcell_per_sec_per_chip"] == r["gcell_per_sec"]
    assert len(r["seconds_all"]) == 2
    json.dumps(r)


def test_halo_result_shape():
    r = bench_halo(tiny_cfg(), iters=5, warmup=1)
    assert r["p50_us"] > 0
    assert r["p95_mean_us"] >= r["p50_us"] >= r["min_us"] * 0.99
    # 3 faces x 2 directions of a 16^3 local block, fp32
    assert r["halo_bytes_per_device"] == 2 * 3 * 16 * 16 * 4
    json.dumps(r)


def test_run_suite_dedupes_halo_rows():
    """Configs differing only in tb/backend/stencil share one halo row —
    the halo latency depends only on the exchange shape."""
    import dataclasses
    import io

    from heat3d_tpu.bench.harness import run_suite

    cfg = tiny_cfg()
    cfg2 = dataclasses.replace(cfg, time_blocking=2)
    buf = io.StringIO()
    results = run_suite([cfg, cfg2], steps=2, out=buf)
    kinds = [r["bench"] for r in results]
    assert kinds.count("throughput") == 2
    assert kinds.count("halo") == 1
    assert len(buf.getvalue().strip().splitlines()) == 3


def test_report_renders_and_updates_markers(tmp_path):
    from heat3d_tpu.bench import report

    results = tmp_path / "r.jsonl"
    results.write_text(
        json.dumps(
            {
                "bench": "throughput", "grid": [512, 512, 512],
                "stencil": "7pt", "mesh": [1, 1, 1], "dtype": "float32",
                "backend": "auto", "steps": 50, "gcell_per_sec": 31.0,
                "gcell_per_sec_per_chip": 31.0, "rtt_dominated": False,
            }
        )
        + "\n"
        + json.dumps(
            {
                "bench": "halo", "grid": [512, 512, 512], "mesh": [2, 2, 2],
                "dtype": "float32", "p50_us": 120.0, "p95_us": 150.0,
                "min_us": 100.0, "halo_bytes_per_device": 4096,
                "rtt_dominated": False,
            }
        )
        + "\nnot json\n"
    )
    md = tmp_path / "B.md"
    md.write_text("# B\n\nintro\n")
    report.main([str(results), str(md)])
    text = md.read_text()
    assert report.BEGIN in text and report.END in text
    assert "| 512³ | 7pt | 1×1×1 |" in text
    assert "| 512³ | 2×2×2 |" in text
    # second run replaces, not duplicates, the measured block
    report.main([str(results), str(md)])
    assert md.read_text().count(report.BEGIN) == 1


def test_report_route_column():
    """The throughput table renders the per-row route provenance
    (transport tier + compute route + emitted op count), and rows
    predating the provenance fields (the archived r2 record) render a
    placeholder instead of a misleading default."""
    from heat3d_tpu.bench.report import _fmt_route, render

    new_row = {
        "bench": "throughput", "grid": [512] * 3, "stencil": "27pt",
        "mesh": [1, 1, 1], "dtype": "float32", "backend": "auto",
        "steps": 50, "gcell_per_sec": 30.0, "gcell_per_sec_per_chip": 30.0,
        "rtt_dominated": False, "chain_ops": 15, "direct_path": True,
        "mehrstellen_route": False,
    }
    old_row = {k: v for k, v in new_row.items()
               if k not in ("chain_ops", "direct_path", "mehrstellen_route")}
    assert _fmt_route(new_row) == "direct chain(15)"
    assert _fmt_route({**new_row, "direct_path": False,
                       "mehrstellen_route": True, "chain_ops": 14}) == \
        "exch mehr(14)"
    assert _fmt_route(old_row) == "—"
    text = render([new_row, old_row])
    assert "| Route |" in text
    assert "direct chain(15)" in text


def test_report_empty_results_keep_measured_block(tmp_path):
    """A rowless session (every row skipped on a lost backend) must not
    erase the committed measured tables."""
    from heat3d_tpu.bench import report

    md = tmp_path / "B.md"
    md.write_text(
        f"# B\n\n{report.BEGIN}\n\n### Throughput (measured)\n\n"
        f"| real measured row |\n{report.END}\n"
    )
    report.update_baseline_md([], str(md))
    assert "real measured row" in md.read_text()
    # an already-empty block still renders the placeholder
    md.write_text(f"# B\n\n{report.BEGIN}\n{report.END}\n")
    report.update_baseline_md([], str(md))
    assert "(no benchmark results found)" in md.read_text()


def test_ab_decide_pairs_and_thresholds(tmp_path):
    """scripts/ab_decide.py pairs rows differing in exactly one knob,
    scopes to the LAST session by default, and thresholds small wins."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ab_decide", os.path.join(root, "scripts", "ab_decide.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    text = (
        "=== tpu_measure_all old ===\n"
        'factor_y=0 tb=1: {"gcell_per_sec_per_chip": 999.0}\n'  # stale
        "=== tpu_measure_all new ===\n"
        'factor_y=1 tb=1: {"gcell_per_sec_per_chip": 30.0}\n'
        'factor_y=0 tb=1: {"gcell_per_sec_per_chip": 25.0}\n'
        'factor_y=0 tb=2: {"gcell_per_sec_per_chip": 35.0}\n'  # 2-knob diff
        'direct: {"gcell_per_sec_per_chip": 80.0}\n'
        'exchange: {"gcell_per_sec_per_chip": 78.0}\n'
        "not an ab line\n"
    )
    entries = list(mod.parse_lines(text))
    # stale-session line excluded
    assert all(r["gcell_per_sec_per_chip"] != 999.0 for _, r in entries)
    decisions = mod.decide(entries, min_win_pct=5.0)
    by_knob = {(d["knob"], tuple(sorted(d["context"].items()))): d
               for d in decisions}
    fy = by_knob[("factor_y", (("tb", "1"),))]
    assert fy["winner"] == "1" and fy["decisive"]
    mode = by_knob[("mode", ())]
    assert mode["winner"] == "direct" and not mode["decisive"]
    # margin is symmetric: winner-vs-loser, not second-vs-first. 21 vs 20
    # is a 5.0% win whichever side carries the lower knob value.
    for hi_first in (True, False):
        a, b = (21.0, 20.0) if hi_first else (20.0, 21.0)
        d = mod.decide(
            [({"k": "0"}, {"gcell_per_sec_per_chip": a}),
             ({"k": "1"}, {"gcell_per_sec_per_chip": b})],
            min_win_pct=5.0,
        )[0]
        assert d["speedup_pct"] == 5.0 and d["decisive"]
    # rows differing in BOTH factor_y and tb never pair directly
    assert ("tb", (("factor_y", "0"),)) in by_knob  # same-knob tb pair OK
    assert ("factor_y", (("tb", "2"),)) not in by_knob


def test_scaling_rows_weak_and_strong():
    from heat3d_tpu.bench.report import render, scaling_rows

    def thr(grid, mesh, rate_per_chip):
        return {
            "bench": "throughput", "grid": grid, "mesh": mesh,
            "stencil": "7pt", "dtype": "float32", "backend": "auto",
            "time_blocking": 1, "steps": 10,
            "gcell_per_sec": rate_per_chip * int(np.prod(mesh)),
            "gcell_per_sec_per_chip": rate_per_chip,
        }

    results = [
        thr([64, 64, 64], [1, 1, 1], 10.0),    # weak baseline (local 64^3)
        thr([128, 64, 64], [1, 1, 1], 8.0),    # strong baseline (global)
        thr([128, 64, 64], [2, 1, 1], 9.5),    # 2-chip run, local 64^3
    ]
    rows = scaling_rows(results)
    by_mode = {r["mode"]: r for r in rows}
    assert by_mode["weak"]["efficiency"] == pytest.approx(9.5 / 10.0)
    assert by_mode["strong"]["efficiency"] == pytest.approx(9.5 / 8.0)
    assert by_mode["weak"]["chips"] == 2
    # efficiency table renders
    assert "Scaling efficiency" in render(results)
    # baselines with a different time_blocking don't match
    results[0]["time_blocking"] = 2
    assert all(r["mode"] != "weak" for r in scaling_rows(results))


def test_throughput_row_records_chain_ops(monkeypatch):
    """Rows carry the emitted chain's op count so factoring-knob A/B rows
    stay tellable apart after the env is gone (roofline_check prefers it)."""
    from heat3d_tpu.bench.harness import _chain_ops
    from heat3d_tpu.core.config import GridConfig, SolverConfig, StencilConfig

    cfg27 = SolverConfig(
        grid=GridConfig.cube(8), stencil=StencilConfig(kind="27pt")
    )
    monkeypatch.delenv("HEAT3D_FACTOR_Y", raising=False)
    assert _chain_ops(cfg27) == 15  # x+y-factored chain
    monkeypatch.setenv("HEAT3D_FACTOR_Y", "0")
    assert _chain_ops(cfg27) == 19  # x-factored only
    cfg7 = SolverConfig(grid=GridConfig.cube(8))
    assert _chain_ops(cfg7) == 7


def test_solver_records_direct_route(monkeypatch):
    """direct_path comes from the route the step builders recorded: the
    direct kernel when it can run (interpret mode stands in for TPU
    off-chip), none under HEAT3D_NO_DIRECT=1 — so A/B transport rows
    stay tellable apart in the traffic model."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    cfg = SolverConfig(grid=GridConfig.cube(16))
    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    monkeypatch.delenv("HEAT3D_NO_DIRECT", raising=False)
    # off the chip 'auto' keeps the jnp padded-block compute; the direct
    # kernel replaces it on the (1,1,1) mesh
    assert HeatSolver3D(cfg).route == {
        "backend": "jnp", "kernel": "direct", "interpret": True,
        "layout": "framed",  # nz = 16: not a whole lane tile
        "precision": {
            "storage": "float32", "compute": "float32", "residual": "float32",
        },
    }
    monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    assert HeatSolver3D(cfg).route["kernel"] is None


def _route(kernel, interpret=False, layout=None):
    return {
        "kernel": kernel,
        "interpret": interpret,
        "layout": layout if kernel is None else layout or "framed",
    }


DIRECT = _route("direct", layout="aligned")

# One row per distinct outcome of the step dispatcher: (mesh, grid, tb,
# config knobs, env, what the builder records). ``env`` is "force"
# (HEAT3D_DIRECT_FORCE: the Mosaic kernels off the chip), "interpret"
# (HEAT3D_DIRECT_INTERPRET), "no-direct" (force + HEAT3D_NO_DIRECT),
# "mehrstellen" (force + HEAT3D_MEHRSTELLEN), "no-direct-gate" (force,
# with direct_supported refusing the shape) or None (off the chip, no
# kernel). Grids with a local nz of 128 put the
# direct kernels' planes on the aligned layout. A refused combination
# expects a ValueError whose message matches the given pattern.
ROUTE_MAP = [
    pytest.param(mesh, grid, tb, {}, "force", DIRECT,
                 id=f"{'x'.join(map(str, mesh))}-tb{tb}")
    for mesh, grid in [
        ((1, 1, 1), (16, 16, 128)),
        ((2, 2, 1), (32, 32, 128)),
        ((4, 1, 1), (32, 16, 128)),
        ((2, 2, 2), (32, 32, 256)),
    ]
    for tb in (1, 2)
] + [
    pytest.param((1, 1, 1), (16, 16, 32), 1, {}, "force",
                 _route("direct", layout="framed"), id="framed"),
    pytest.param((1, 1, 1), (16, 16, 128), 1,
                 {"stencil": StencilConfig(kind="27pt")}, "mehrstellen",
                 _route("direct", layout="framed"), id="mehrstellen"),
    pytest.param((4, 1, 1), (32, 16, 128), 2, {}, "interpret",
                 _route("direct", True, "aligned"), id="interpret"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {"backend": "jnp"}, "force",
                 _route(None), id="backend-jnp"),
    pytest.param((1, 1, 1), (16, 16, 128), 1, {"backend": "conv"}, "force",
                 _route(None), id="backend-conv"),
    pytest.param((4, 1, 1), (30, 16, 128), 1, {}, "force", _route(None),
                 id="padded"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {"halo_order": "pairwise"},
                 "force", _route(None), id="pairwise"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {"halo_plan": "partitioned"},
                 "force", _route(None), id="partitioned"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {}, "no-direct", _route(None),
                 id="no-direct"),
    pytest.param((2, 2, 1), (32, 32, 128), 2, {}, None, _route(None),
                 id="off-tpu"),
    pytest.param((4, 1, 1), (32, 16, 128), 1, {"halo": "dma"}, "force",
                 _route(None), id="dma"),
    pytest.param((4, 1, 1), (32, 16, 128), 2, {"halo": "dma"}, "force",
                 _route("streamk"), id="dma-tb2"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {"overlap": True}, "force",
                 DIRECT, id="overlap-tb1"),
    pytest.param((2, 2, 1), (32, 32, 128), 1, {"overlap": True}, None,
                 _route(None), id="overlap-off-tpu"),
    pytest.param((2, 2, 1), (4, 4, 128), 1, {"overlap": True}, None,
                 "local blocks >= 3", id="overlap-small-block"),
    pytest.param((2, 2, 1), (32, 32, 128), 2, {"overlap": True}, "force",
                 "mutually exclusive", id="overlap-tb2"),
    pytest.param((4, 1, 1), (32, 16, 128), 1,
                 {"overlap": True, "halo": "dma"}, "force",
                 "cannot overlap with compute", id="overlap-dma"),
    pytest.param((1, 1, 1), (16, 16, 128), 3, {}, "force",
                 _route("streamk"), id="tb3"),
    pytest.param((1, 1, 1), (16, 16, 128), 4, {}, "force",
                 _route("streamk"), id="tb4"),
    pytest.param((1, 1, 1), (16, 16, 128), 5, {}, "force", _route(None),
                 id="tb5"),
    pytest.param((2, 2, 1), (32, 32, 128), 3, {}, None, _route(None),
                 id="tb3-off-tpu"),
    pytest.param((2, 2, 1), (32, 32, 128), 2, {}, "no-direct-gate",
                 _route("streamk"), id="direct-unsupported-tb2"),
]


@pytest.mark.parametrize("mesh,grid,tb,knobs,env,want", ROUTE_MAP)
def test_route_map(monkeypatch, mesh, grid, tb, knobs, env, want):
    """The step builders' route record over the dispatcher's outcomes:
    which kernel family (or the exchange path) each mesh, time-blocking
    depth, knob and environment takes, built over an abstract mesh and
    never lowered; refused combinations raise."""
    from heat3d_tpu.parallel.step import make_step_fn, make_superstep_fn
    from heat3d_tpu.parallel.topology import abstract_mesh

    for var in ("HEAT3D_DIRECT_FORCE", "HEAT3D_DIRECT_INTERPRET",
                "HEAT3D_NO_DIRECT", "HEAT3D_MEHRSTELLEN"):
        monkeypatch.delenv(var, raising=False)
    if env == "interpret":
        monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    elif env is not None:
        monkeypatch.setenv("HEAT3D_DIRECT_FORCE", "1")
    if env == "no-direct":
        monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    if env == "mehrstellen":
        monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    if env == "no-direct-gate":
        monkeypatch.setattr(
            "heat3d_tpu.ops.stencil_pallas_direct.direct_supported",
            lambda *a, **k: False,
        )

    def build():
        cfg = SolverConfig(
            grid=GridConfig(shape=grid), mesh=MeshConfig(shape=mesh),
            time_blocking=tb, **knobs,
        )
        builder = make_superstep_fn if tb > 1 else make_step_fn
        return builder(cfg, abstract_mesh(cfg.mesh)).route

    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            build()
    else:
        assert build() == want


def test_chain_ops_tracks_mehrstellen_route(monkeypatch):
    """chain_ops provenance must record what EXECUTES: the separable
    route's canonical 14-op count when the mehrstellen knob engages the
    jnp apply, the tap chain's count everywhere else (kernel backends
    ignore the knob; 7pt taps don't decompose)."""
    from heat3d_tpu.bench.harness import _chain_ops
    from heat3d_tpu.core.config import GridConfig, SolverConfig, StencilConfig
    from heat3d_tpu.core.stencils import MEHRSTELLEN_OPS

    cfg = SolverConfig(
        grid=GridConfig.cube(8), stencil=StencilConfig(kind="27pt"),
        backend="jnp",
    )
    monkeypatch.delenv("HEAT3D_MEHRSTELLEN", raising=False)
    monkeypatch.delenv("HEAT3D_FACTOR_Y", raising=False)
    assert _chain_ops(cfg) == 15
    monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    assert _chain_ops(cfg) == MEHRSTELLEN_OPS == 14
    # kernel backend keeps the chain regardless of the knob
    import dataclasses
    assert _chain_ops(dataclasses.replace(cfg, backend="pallas")) == 15
    # 7pt has no separable part
    cfg7 = SolverConfig(grid=GridConfig.cube(8), backend="jnp")
    assert _chain_ops(cfg7) == 7


