"""BC-fused direct streaming kernels: numerics (interpret mode), dispatch
wiring, and compiled-on-TPU parity.

The direct kernels read the UNPADDED field and synthesize domain ghosts
in-register (ops/stencil_pallas_direct.py), replacing exchange+kernel on
(1,1,1) meshes; equivalence to the jnp reference is to fp32 rounding-order
tolerance (FMA contraction differs between fused XLA loops and per-plane
kernel ops — ~1 ulp)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat3d_tpu.core import golden
from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.core.stencils import STENCILS, stencil_taps
from heat3d_tpu.ops.stencil_jnp import residual_sumsq, step_single_device
from heat3d_tpu.ops.stencil_pallas_direct import (
    apply_taps_direct,
    apply_taps_direct2,
    choose_chunk,
    direct_supported,
)

on_tpu = jax.devices()[0].platform == "tpu"


def _taps(kind, shape):
    g = GridConfig(shape=shape)
    return stencil_taps(STENCILS[kind], g.alpha, g.effective_dt(), g.spacing)


CASES = [
    (BoundaryCondition.DIRICHLET, 0.0),
    (BoundaryCondition.DIRICHLET, 1.5),
    (BoundaryCondition.PERIODIC, 0.0),
]


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 16, 32), (5, 16, 128), (3, 8, 8)])
@pytest.mark.parametrize("kind", ["7pt", "27pt"])
def test_direct_interpret_matches_jnp(shape, kind):
    u = jnp.asarray(golden.random_init(shape, seed=1))
    taps = _taps(kind, shape)
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        want = step_single_device(u, taps, bc, bcv)
        got = apply_taps_direct(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=f"{shape} {kind} {bc} {bcv}",
        )


@pytest.mark.parametrize(
    "shape",
    # nz = 128 and 256: the aligned layout (_aligned_layout)
    [(16, 16, 16), (8, 16, 32), (4, 4, 4), (4, 8, 128), (3, 16, 256)],
)
@pytest.mark.parametrize("kind", ["7pt", "27pt"])
def test_direct2_interpret_matches_two_steps(shape, kind):
    u = jnp.asarray(golden.random_init(shape, seed=3))
    taps = _taps(kind, shape)
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        want = step_single_device(
            step_single_device(u, taps, bc, bcv), taps, bc, bcv
        )
        got = apply_taps_direct2(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=f"{shape} {kind} {bc} {bcv}",
        )


def test_direct_bf16_storage_fp32_compute():
    shape = (16, 16, 16)
    u = jnp.asarray(golden.random_init(shape, seed=2), jnp.bfloat16)
    taps = _taps("7pt", shape)
    want = step_single_device(
        u, taps, BoundaryCondition.DIRICHLET, 0.5, Precision.bf16()
    )
    got = apply_taps_direct(
        u, taps, periodic=False, bc_value=0.5, out_dtype=jnp.bfloat16,
        interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        rtol=2e-2, atol=1e-2,  # one bf16 ulp of rounding-order headroom
    )


def test_chunking_feasibility():
    # judged grids fit VMEM via y-chunking, fp32 and bf16, both halo widths
    for edge in (256, 512, 1024):
        for itemsize in (4, 2):
            for halo in (1, 2):
                by = choose_chunk((edge,) * 3, halo, itemsize, itemsize)
                assert by is not None and edge % by == 0, (edge, itemsize, halo)
    # ny with no 8-multiple divisor runs single-chunk (full-extent blocks
    # are exempt from the sublane alignment rule)
    assert direct_supported((16, 12, 16), 1)
    # ...but multi-chunk never picks an unaligned by
    assert choose_chunk((16, 48, 16), 1) in (48, 40, 24, 16, 8)
    # width-2 ghosts would alias on sub-2 extents
    assert not direct_supported((1, 8, 8), 2)
    # odd ny < 8 runs in single-chunk mode (no sublane-aligned row blocks)
    assert direct_supported((6, 5, 8), 2)


def test_dispatch_used_on_111_mesh(monkeypatch):
    from heat3d_tpu.parallel.step import _direct_kernel_fn

    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(1, 1, 1)),
        backend="auto",
    )
    assert _direct_kernel_fn(cfg, 1) is not None
    assert _direct_kernel_fn(cfg, 2) is not None
    # env kill-switch honored
    monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    assert _direct_kernel_fn(cfg, 1) is None
    monkeypatch.delenv("HEAT3D_NO_DIRECT")
    # plain dispatch never fires off a (1,1,1) mesh (multi-chip goes through
    # the faces-direct step, which passes multichip=True), nor for the jnp
    # backend
    assert _direct_kernel_fn(
        dataclasses.replace(cfg, mesh=MeshConfig(shape=(2, 1, 1))), 1
    ) is None
    assert _direct_kernel_fn(
        dataclasses.replace(cfg, mesh=MeshConfig(shape=(2, 1, 1))), 1,
        multichip=True,
    ) is not None
    # overlap=True is satisfied by the (faces-)direct step for halo=1; the
    # tb=2 superstep keeps its overlap mutual exclusion
    assert _direct_kernel_fn(dataclasses.replace(cfg, overlap=True), 1) is not None
    assert _direct_kernel_fn(dataclasses.replace(cfg, overlap=True), 2) is None
    assert _direct_kernel_fn(dataclasses.replace(cfg, backend="jnp"), 1) is None


def test_solver_end_to_end_direct_interpret(monkeypatch):
    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    from heat3d_tpu.models.heat3d import HeatSolver3D

    for tb, steps in ((1, 3), (2, 5)):  # 5 = 2 supersteps + 1 trailing step
        cfg = SolverConfig(
            grid=GridConfig.cube(16),
            stencil=StencilConfig(kind="7pt", bc=BoundaryCondition.DIRICHLET),
            mesh=MeshConfig(shape=(1, 1, 1)),
            backend="auto",
            time_blocking=tb,
        )
        s = HeatSolver3D(cfg)
        u = s.run(s.init_state("gaussian"), steps)
        want = golden.run(
            golden.gaussian_init((16, 16, 16)).astype(np.float64),
            cfg.grid, cfg.stencil, steps,
        )
        np.testing.assert_allclose(
            s.gather(u), want, rtol=1e-5, atol=1e-6, err_msg=f"tb={tb}"
        )


@pytest.mark.tpu_smoke
@pytest.mark.skipif(not on_tpu, reason="needs TPU")
@pytest.mark.parametrize(
    "shape,layout", [((64, 64, 128), "aligned"), ((64, 64, 96), "framed")]
)
@pytest.mark.parametrize("kind", ["7pt", "27pt"])
def test_direct_compiled_on_tpu(kind, shape, layout):
    from heat3d_tpu.ops.stencil_pallas_direct import direct_layout

    u = jnp.asarray(golden.random_init(shape, seed=5))
    taps = _taps(kind, shape)
    assert direct_layout(shape, taps) == layout
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        want = step_single_device(u, taps, bc, bcv)
        got = jax.jit(
            lambda v: apply_taps_direct(v, taps, periodic=periodic, bc_value=bcv)
        )(u)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=f"{kind} {shape} {bc}",
        )


@pytest.mark.tpu_smoke
@pytest.mark.skipif(not on_tpu, reason="needs TPU")
@pytest.mark.parametrize(
    "shape,layout",
    [
        ((64, 64, 128), "aligned"),
        ((32, 2048, 256), "aligned"),
        ((64, 64, 96), "framed"),
        ((32, 2048, 96), "framed"),
    ],
)
@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_direct_change_partials_compiled_on_tpu(shape, layout, storage):
    """The compiled kernel's change partials: the field bit for bit as
    without them, their sum ``residual_sumsq`` of the stored field."""
    from heat3d_tpu.ops.stencil_pallas_direct import direct_layout

    u = jnp.asarray(golden.random_init(shape, seed=8), storage)
    taps = _taps("7pt", shape)
    assert direct_layout(shape, taps) == layout
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        call = functools.partial(
            apply_taps_direct, taps=taps, periodic=periodic, bc_value=bcv
        )
        plain = jax.jit(call)(u)
        out, partials = jax.jit(
            functools.partial(call, residual_dtype=jnp.float32)
        )(u)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(plain), err_msg=f"{shape} {bc}"
        )
        np.testing.assert_allclose(
            float(jnp.sum(partials)),
            float(residual_sumsq(out, u, jnp.float32)),
            rtol=1e-5, err_msg=f"{shape} {bc}",
        )


@pytest.mark.tpu_smoke
@pytest.mark.skipif(not on_tpu, reason="needs TPU")
@pytest.mark.parametrize(
    "shape,layout",
    [
        ((64, 64, 128), "aligned"),
        # multi-chunk: ghost-row pads and rotations across chunk borders
        ((32, 2048, 256), "aligned"),
        # nz not a whole lane tile: the ghost-framed planes, single- and
        # multi-chunk
        ((64, 64, 96), "framed"),
        ((32, 2048, 96), "framed"),
    ],
)
@pytest.mark.parametrize("kind", ["7pt", "27pt"])
def test_direct2_compiled_on_tpu(kind, shape, layout):
    from heat3d_tpu.ops.stencil_pallas_direct import direct_layout

    u = jnp.asarray(golden.random_init(shape, seed=6))
    taps = _taps(kind, shape)
    assert direct_layout(shape, taps) == layout
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        want = step_single_device(
            step_single_device(u, taps, bc, bcv), taps, bc, bcv
        )
        got = jax.jit(
            lambda v: apply_taps_direct2(v, taps, periodic=periodic, bc_value=bcv)
        )(u)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=f"{kind} {shape} {bc}",
        )


@pytest.mark.parametrize(
    "kind,nz",
    [
        pytest.param("7pt", 32, id="7pt"),
        pytest.param("27pt", 32, id="27pt"),
        pytest.param("7pt", 128, id="7pt-aligned"),
        pytest.param("27pt", 128, id="27pt-aligned"),
    ],
)
def test_multichunk_interpret_matches_jnp(kind, nz, monkeypatch):
    """Force n_chunks > 1 (ny=16, by=8) so the 8-row-aligned ghost-row
    blocks — the TPU-lowerable replacement for single-row BlockSpecs — are
    exercised numerically, top/bottom substitution and wrap included, in
    the framed (nz = 32) and the aligned (nz = 128) layout."""
    import heat3d_tpu.ops.stencil_pallas_direct as d

    monkeypatch.setattr(d, "choose_chunk", lambda *a, **k: 8)
    shape = (6, 16, nz)
    u = jnp.asarray(golden.random_init(shape, seed=9))
    taps = _taps(kind, shape)
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        want = step_single_device(u, taps, bc, bcv)
        got = d.apply_taps_direct(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=f"{kind} {bc} {bcv} (direct)",
        )
        want2 = step_single_device(want, taps, bc, bcv)
        got2 = d.apply_taps_direct2(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got2), np.asarray(want2), rtol=1e-6, atol=1e-6,
            err_msg=f"{kind} {bc} {bcv} (direct2)",
        )


def test_direct_kernels_cross_lower_for_tpu(monkeypatch):
    """Pallas->Mosaic lowering for the TPU target runs host-side, so the
    block-spec alignment rules are checkable without hardware (this caught
    the original single-row ghost BlockSpecs, which violated the
    8-divisible-sublane rule). Covers single- and multi-chunk modes, in
    the aligned layout (nz = 128, both storage widths: 8- and 16-row pad
    tiles) and the ghost-framed one (nz = 96)."""
    import heat3d_tpu.ops.stencil_pallas_direct as d

    shape = (16, 32, 128)
    taps = _taps("27pt", shape)
    routes = [
        (shape, "aligned", jnp.float32),
        (shape, "aligned", jnp.bfloat16),
        ((16, 32, 96), "framed", jnp.float32),
    ]
    for by in (32, 8):  # single-chunk, then 4-chunk
        monkeypatch.setattr(d, "choose_chunk", lambda *a, _by=by, **k: _by)
        for periodic in (False, True):
            for fn in (d.apply_taps_direct, d.apply_taps_direct2):
                for ushape, layout, storage in routes:
                    assert d.direct_layout(ushape, taps) == layout
                    u = jax.ShapeDtypeStruct(ushape, storage)
                    low = jax.jit(
                        lambda v, f=fn, p=periodic: f(
                            v, taps, periodic=p, bc_value=0.5
                        )
                    ).trace(u).lower(lowering_platforms=("tpu",))
                    assert "tpu_custom_call" in low.as_text(), (
                        by, periodic, fn, layout, storage,
                    )
        u = jax.ShapeDtypeStruct(shape, jnp.float32)
        # mehrstellen q-ring variants (tb=1 and the fused tb=2 kernel)
        monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
        for periodic in (False, True):
            for fn in (d.apply_taps_direct, d.apply_taps_direct2):
                low = jax.jit(
                    lambda v, f=fn, p=periodic: f(
                        v, taps, periodic=p, bc_value=0.5
                    )
                ).trace(u).lower(lowering_platforms=("tpu",))
                assert "tpu_custom_call" in low.as_text(), (
                    by, periodic, fn, "mehr",
                )
        monkeypatch.delenv("HEAT3D_MEHRSTELLEN")


@pytest.mark.parametrize("shape", [(8, 16, 32), (5, 16, 128)])
def test_direct_mehrstellen_interpret_matches_chain(shape, monkeypatch):
    """HEAT3D_MEHRSTELLEN=1 routes the tb=1 direct kernel through the
    q-ring S+F variant: same math as the tap chain to FMA-reordering
    rounding, and bitwise-equal to the jnp mehrstellen apply's op order
    contract (both implement the canonical order)."""
    u = jnp.asarray(golden.random_init(shape, seed=3))
    taps = _taps("27pt", shape)
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        monkeypatch.delenv("HEAT3D_MEHRSTELLEN", raising=False)
        chain = apply_taps_direct(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
        got = apply_taps_direct(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(chain), rtol=3e-6, atol=3e-6,
            err_msg=f"mehrstellen vs chain bc={bc} bcv={bcv}",
        )
        want = step_single_device(u, taps, bc, bcv)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-6, atol=3e-6,
            err_msg=f"mehrstellen kernel vs jnp-mehrstellen bc={bc}",
        )


def test_direct_mehrstellen_multichunk_interpret(monkeypatch):
    """Chunked-column mode (by < ny): the per-chunk q planes are built
    from framed planes whose ghost rows carry real neighbor data, so the
    2D convs match the global jnp result across chunk borders."""
    from heat3d_tpu.ops import stencil_pallas_direct as d

    shape = (6, 32, 16)
    u = jnp.asarray(golden.random_init(shape, seed=4))
    taps = _taps("27pt", shape)
    monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    # force multi-chunk: shrink the VMEM budget so by=8 chunks are chosen
    monkeypatch.setattr(d, "_VMEM_BUDGET", 120 * 1024)
    by = d.choose_chunk(shape, 1, 4, 4, n_taps=15, q_ring=True)
    assert by is not None and by < shape[1], by
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        got = apply_taps_direct(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        monkeypatch.delenv("HEAT3D_MEHRSTELLEN", raising=False)
        want = step_single_device(u, taps, bc, bcv)
        monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-6, atol=3e-6,
            err_msg=f"multichunk mehrstellen bc={bc}",
        )


@pytest.mark.parametrize("shape", [(8, 16, 32), (6, 16, 128)])
def test_direct2_mehrstellen_interpret_matches_two_steps(shape, monkeypatch):
    """tb=2 q-ring route: the fused two-update kernel under
    HEAT3D_MEHRSTELLEN=1 equals two jnp mehrstellen steps (the storage
    round-trip between updates is preserved), both BCs."""
    u = jnp.asarray(golden.random_init(shape, seed=6))
    taps = _taps("27pt", shape)
    monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        got = apply_taps_direct2(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        want = step_single_device(
            step_single_device(u, taps, bc, bcv), taps, bc, bcv
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-6, atol=3e-6,
            err_msg=f"direct2 mehrstellen bc={bc} bcv={bcv}",
        )


def test_direct2_mehrstellen_multichunk_interpret(monkeypatch):
    """tb=2 q-ring route in chunked-column mode: stage (b)'s per-chunk
    edge-row pinning must land BEFORE its ring_qb build, so the cached
    conv matches the pinned plane across chunk borders."""
    from heat3d_tpu.ops import stencil_pallas_direct as d

    shape = (6, 32, 16)
    u = jnp.asarray(golden.random_init(shape, seed=7))
    taps = _taps("27pt", shape)
    monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    monkeypatch.setattr(d, "_VMEM_BUDGET", 150 * 1024)
    by = d.choose_chunk(shape, 2, 4, 4, q_ring=True)
    assert by is not None and by < shape[1], by
    for bc, bcv in CASES:
        periodic = bc is BoundaryCondition.PERIODIC
        got = apply_taps_direct2(
            u, taps, periodic=periodic, bc_value=bcv, interpret=True
        )
        want = step_single_device(
            step_single_device(u, taps, bc, bcv), taps, bc, bcv
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-6, atol=3e-6,
            err_msg=f"multichunk direct2 mehrstellen bc={bc}",
        )


# XLA's CPU backend contracts a multiply and an add into one FMA where it
# fuses them, and the two layouts fuse differently; with instruction
# fusion off every product is rounded before it is summed, as the canonical
# order says, so the two routes can be compared bit for bit.
_NO_FMA_CONTRACTION = {"xla_disable_hlo_passes": "cpu-instruction-fusion"}


def _unfused(fn, u):
    return np.asarray(
        jax.jit(fn).lower(u).compile(_NO_FMA_CONTRACTION)(u), np.float32
    )


@pytest.mark.parametrize(
    "kind,case,storage",
    [
        (kind, case, "float32")
        for kind in ("7pt", "27pt")
        for case in range(len(CASES))
    ]
    + [("7pt", 1, "bfloat16"), ("27pt", 2, "bfloat16")],
)
def test_aligned_matches_framed_bitwise(kind, case, storage, monkeypatch):
    """The aligned layout (rotated tap terms on tile-aligned planes) sums
    the same values in the same order as the framed one (misaligned slices
    of ghost-framed planes): both kernels, single- and multi-chunk, give
    the same bits."""
    import heat3d_tpu.ops.stencil_pallas_direct as d

    bc, bcv = CASES[case]
    periodic = bc is BoundaryCondition.PERIODIC
    for shape, by in (((5, 16, 128), None), ((6, 16, 256), 8)):
        taps = _taps(kind, shape)
        u = jnp.asarray(golden.random_init(shape, seed=11), storage)
        if by is not None:
            monkeypatch.setattr(d, "choose_chunk", lambda *a, **k: by)
        for fn in (d.apply_taps_direct, d.apply_taps_direct2):
            run = lambda v, f=fn: f(  # noqa: E731
                v, taps, periodic=periodic, bc_value=bcv, interpret=True
            )
            assert d.direct_layout(shape, taps) == "aligned"
            aligned = _unfused(run, u)
            with monkeypatch.context() as m:
                m.setattr(d, "_aligned_layout", lambda nz, q_ring: False)
                framed = _unfused(run, u)
            np.testing.assert_array_equal(
                aligned, framed, err_msg=f"{fn.__name__} {shape} by={by}"
            )


@pytest.mark.parametrize("tb", [1, 2])
def test_direct_bf16_storage_aligned(tb):
    """bf16 storage on the aligned layout (16-row pad tiles), fp32 math:
    within one bf16 ulp of rounding-order headroom of the jnp steps."""
    shape = (6, 16, 128)
    u = jnp.asarray(golden.random_init(shape, seed=2), jnp.bfloat16)
    taps = _taps("7pt", shape)
    want = u
    for _ in range(tb):
        want = step_single_device(
            want, taps, BoundaryCondition.DIRICHLET, 0.5, Precision.bf16()
        )
    fn = apply_taps_direct if tb == 1 else apply_taps_direct2
    got = fn(
        u, taps, periodic=False, bc_value=0.5, out_dtype=jnp.bfloat16,
        interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        rtol=2e-2, atol=1e-2,
    )


@pytest.mark.parametrize("nz,layout", [(128, "aligned"), (32, "framed")])
@pytest.mark.parametrize("tb", [1, 2])
def test_solver_route_records_layout(nz, layout, tb, monkeypatch):
    """The solver's route names the plane layout its direct kernel builds:
    aligned where nz is a whole number of lane tiles, framed elsewhere."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    monkeypatch.delenv("HEAT3D_NO_DIRECT", raising=False)
    cfg = SolverConfig(
        grid=GridConfig(shape=(8, 8, nz)), time_blocking=tb
    )
    route = HeatSolver3D(cfg).route
    assert route["kernel"] == "direct"
    assert route["layout"] == layout


# (shape, kind, case in CASES, storage, forced chunk height or None,
# mehrstellen): the aligned, framed and mehrstellen emit routes, one and
# several chunk columns, and a single chunk whose height is no multiple
# of 8 (its partials fold to one row)
PARTIAL_CASES = [
    pytest.param((5, 16, 128), "7pt", 1, "float32", None, False,
                 id="aligned-dirichlet"),
    pytest.param((6, 16, 256), "27pt", 2, "float32", 8, False,
                 id="aligned-periodic-multichunk"),
    pytest.param((6, 16, 32), "7pt", 0, "float32", None, False,
                 id="framed-dirichlet"),
    pytest.param((6, 16, 32), "27pt", 2, "float32", 8, False,
                 id="framed-periodic-multichunk"),
    pytest.param((4, 12, 16), "7pt", 1, "float32", None, False,
                 id="framed-rows12"),
    pytest.param((5, 16, 128), "27pt", 1, "float32", None, True,
                 id="mehrstellen-dirichlet"),
    pytest.param((6, 16, 32), "27pt", 2, "float32", 8, True,
                 id="mehrstellen-periodic-multichunk"),
    pytest.param((6, 16, 128), "7pt", 1, "bfloat16", None, False,
                 id="bf16-aligned-dirichlet"),
    pytest.param((6, 16, 32), "7pt", 2, "bfloat16", 8, False,
                 id="bf16-framed-periodic-multichunk"),
]


@pytest.mark.parametrize(
    "shape,kind,case,storage,by,mehrstellen", PARTIAL_CASES
)
def test_direct_change_partials(shape, kind, case, storage, by, mehrstellen,
                                monkeypatch):
    """With ``residual_dtype`` the tb=1 kernel returns the same field bits
    as without it, and partial sums of the change that add up, per output
    plane and chunk column, to ``residual_sumsq`` of the stored field
    against the input (bf16 storage: the stored bf16 values) in float32."""
    import heat3d_tpu.ops.stencil_pallas_direct as d

    if by is not None:
        monkeypatch.setattr(d, "choose_chunk", lambda *a, **k: by)
    if mehrstellen:
        monkeypatch.setenv("HEAT3D_MEHRSTELLEN", "1")
    else:
        monkeypatch.delenv("HEAT3D_MEHRSTELLEN", raising=False)
    bc, bcv = CASES[case]
    u = jnp.asarray(golden.random_init(shape, seed=13), storage)
    taps = _taps(kind, shape)
    layout = "aligned" if shape[2] % 128 == 0 and not mehrstellen else "framed"
    assert d.direct_layout(shape, taps) == layout
    call = functools.partial(
        d.apply_taps_direct, u, taps,
        periodic=bc is BoundaryCondition.PERIODIC, bc_value=bcv,
        interpret=True,
    )
    plain = call()
    out, partials = call(residual_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    assert out.dtype == u.dtype and partials.dtype == jnp.float32

    nx, ny, nz = shape
    rows = 1 if partials.shape[1] == 1 else 8
    chunks = partials.shape[1] // rows
    lanes = 128 if nz % 128 == 0 else nz
    assert partials.shape == (nx, rows * chunks, lanes)
    assert chunks == (ny // by if by else 1)
    want = float(residual_sumsq(out, u, jnp.float32))
    np.testing.assert_allclose(
        float(jnp.sum(partials)), want, rtol=1e-5
    )
    # each block holds its own plane's and chunk column's cells
    change = (np.asarray(out, np.float64) - np.asarray(u, np.float64)) ** 2
    np.testing.assert_allclose(
        np.asarray(partials, np.float64)
        .reshape(nx, chunks, rows * lanes).sum(-1),
        change.reshape(nx, chunks, ny // chunks * nz).sum(-1),
        rtol=1e-5, atol=1e-6 * want,
    )


def test_change_partials_count_in_the_vmem_budget():
    """The partials' double-buffered block counts in the tb=1 kernel's
    VMEM footprint (an (8, 128) block, or (8, nz) where nz is no whole
    number of lane tiles), so the gate budgets what the residual step
    builds; at 1024^3 it leaves the chunk height as it was."""
    import heat3d_tpu.ops.stencil_pallas_direct as d

    for nz, lanes in ((1024, 128), (96, 96)):
        plain = d._vmem_bytes(128, nz, 1, 4, 4, aligned=nz % 128 == 0)
        with_partials = d._vmem_bytes(
            128, nz, 1, 4, 4, aligned=nz % 128 == 0, partial_itemsize=4
        )
        assert with_partials - plain == 2 * 8 * d._round_up(lanes, 128) * 4
    shape = (1024, 1024, 1024)
    assert d.choose_chunk(shape, aligned=True) == d.choose_chunk(
        shape, aligned=True, partial_itemsize=4
    )
    assert d.direct_supported(shape, partial_itemsize=4)
