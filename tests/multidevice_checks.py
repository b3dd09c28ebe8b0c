"""Real multi-device distributed checks, run on an 8-device CPU mesh.

Executed as a subprocess by tests/test_multidevice.py (env
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8) —
the software
equivalent of the reference class's ``mpirun -np 8`` oversubscription test
(SURVEY.md §4): the decomposed run must reproduce the undecomposed run.

Not named test_* so pytest does not collect it in the main process.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

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
from heat3d_tpu.ops.stencil_jnp import step_single_device
from heat3d_tpu.parallel.halo import exchange_halo
from heat3d_tpu.parallel.step import make_multistep_fn, make_step_fn
from heat3d_tpu.parallel.topology import build_mesh, field_sharding
from jax import shard_map


def check_step_matches_single_device():
    """Decomposed step == undecomposed step, across mesh shapes, stencils,
    BCs, and precisions — the '-np 1 vs -np P' oracle."""
    grid = (16, 16, 16)
    u_host = golden.random_init(grid, seed=7)
    for mesh_shape in [(8, 1, 1), (2, 2, 2), (1, 2, 4), (2, 4, 1)]:
        for kind in ("7pt", "27pt"):
            for bc, bcv in [
                (BoundaryCondition.DIRICHLET, 0.0),
                (BoundaryCondition.DIRICHLET, 1.5),
                (BoundaryCondition.PERIODIC, 0.0),
            ]:
                cfg = SolverConfig(
                    grid=GridConfig(shape=grid),
                    stencil=StencilConfig(kind=kind, bc=bc, bc_value=bcv),
                    mesh=MeshConfig(shape=mesh_shape),
                    backend="jnp",
                )
                mesh = build_mesh(cfg.mesh)
                sharding = field_sharding(mesh, cfg.mesh)
                u = jax.device_put(jnp.asarray(u_host), sharding)
                got = jax.jit(make_step_fn(cfg, mesh))(u)
                taps = stencil_taps(
                    STENCILS[kind], cfg.grid.alpha, cfg.grid.effective_dt(),
                    cfg.grid.spacing,
                )
                want = step_single_device(jnp.asarray(u_host), taps, bc, bcv)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
                    err_msg=f"mesh={mesh_shape} kind={kind} bc={bc} bcv={bcv}",
                )
    print("step_matches_single_device OK")


def check_faces_direct_step_distributed():
    """Multi-chip direct step (faces-only exchange + BC-fused bulk kernel +
    shell patches, interpret-mode kernel) == the single-device oracle across
    mesh shapes, stencils, and BCs. Exercises exchange_halo_faces' corner
    propagation and _padded_slab reassembly on real device boundaries."""
    import os

    from heat3d_tpu.parallel.step import _direct_kernel_fn

    prior = os.environ.get("HEAT3D_DIRECT_INTERPRET")
    os.environ["HEAT3D_DIRECT_INTERPRET"] = "1"
    try:
        grid = (16, 16, 16)
        u_host = golden.random_init(grid, seed=23)
        for mesh_shape in [(8, 1, 1), (2, 2, 2), (1, 2, 4), (2, 4, 1)]:
            for kind in ("7pt", "27pt"):
                for bc, bcv in [
                    (BoundaryCondition.DIRICHLET, 1.5),
                    (BoundaryCondition.PERIODIC, 0.0),
                ]:
                    cfg = SolverConfig(
                        grid=GridConfig(shape=grid),
                        stencil=StencilConfig(kind=kind, bc=bc, bc_value=bcv),
                        mesh=MeshConfig(shape=mesh_shape),
                        backend="auto",
                    )
                    assert _direct_kernel_fn(cfg, 1, multichip=True) is not None
                    mesh = build_mesh(cfg.mesh)
                    u = jax.device_put(
                        jnp.asarray(u_host), field_sharding(mesh, cfg.mesh)
                    )
                    got = jax.jit(make_step_fn(cfg, mesh))(u)
                    taps = stencil_taps(
                        STENCILS[kind], cfg.grid.alpha,
                        cfg.grid.effective_dt(), cfg.grid.spacing,
                    )
                    want = step_single_device(jnp.asarray(u_host), taps, bc, bcv)
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(want),
                        rtol=1e-6, atol=1e-6,
                        err_msg=f"mesh={mesh_shape} kind={kind} bc={bc}",
                    )
        # bf16 storage: faces-direct == exchange path to bf16 rounding
        cfg = SolverConfig(
            grid=GridConfig(shape=grid),
            stencil=StencilConfig(kind="7pt"),
            mesh=MeshConfig(shape=(2, 2, 2)),
            precision=Precision.bf16(),
            backend="auto",
        )
        assert _direct_kernel_fn(cfg, 1, multichip=True) is not None
        mesh = build_mesh(cfg.mesh)
        u = jax.device_put(
            jnp.asarray(u_host, jnp.bfloat16), field_sharding(mesh, cfg.mesh)
        )
        got = jax.jit(make_step_fn(cfg, mesh))(u)
        import dataclasses as _dc

        want_bf16 = jax.jit(make_step_fn(_dc.replace(cfg, backend="jnp"), mesh))(u)
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32),
            np.asarray(want_bf16, dtype=np.float32),
            rtol=2e-2, atol=1e-2,
        )
    finally:
        if prior is None:
            os.environ.pop("HEAT3D_DIRECT_INTERPRET", None)
        else:
            os.environ["HEAT3D_DIRECT_INTERPRET"] = prior
    print("faces_direct_step_distributed OK (incl. bf16)")


def check_faces_direct_superstep_distributed():
    """Multi-chip tb=2 faces-direct superstep (width-2 faces exchange +
    fused direct2 bulk kernel + 2-deep shell patches, interpret-mode
    kernel) == two plain exchange-path steps, across mesh shapes, stencils,
    and BCs."""
    import dataclasses
    import os

    from heat3d_tpu.parallel.step import _direct_kernel_fn, make_superstep_fn

    prior = os.environ.get("HEAT3D_DIRECT_INTERPRET")
    os.environ["HEAT3D_DIRECT_INTERPRET"] = "1"
    try:
        grid = (16, 16, 16)
        u_host = golden.random_init(grid, seed=31)
        for mesh_shape in [(8, 1, 1), (2, 2, 2), (1, 2, 4), (2, 4, 1)]:
            for kind in ("7pt", "27pt"):
                for bc, bcv in [
                    (BoundaryCondition.DIRICHLET, 1.5),
                    (BoundaryCondition.PERIODIC, 0.0),
                ]:
                    cfg = SolverConfig(
                        grid=GridConfig(shape=grid),
                        stencil=StencilConfig(kind=kind, bc=bc, bc_value=bcv),
                        mesh=MeshConfig(shape=mesh_shape),
                        backend="auto",
                        time_blocking=2,
                    )
                    assert _direct_kernel_fn(cfg, 2, multichip=True) is not None
                    mesh = build_mesh(cfg.mesh)
                    u = jax.device_put(
                        jnp.asarray(u_host), field_sharding(mesh, cfg.mesh)
                    )
                    got = jax.jit(make_superstep_fn(cfg, mesh))(u)
                    cfg1 = dataclasses.replace(
                        cfg, time_blocking=1, backend="jnp"
                    )
                    s1 = jax.jit(make_step_fn(cfg1, mesh))
                    want = s1(s1(u))
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(want),
                        rtol=1e-6, atol=1e-6,
                        err_msg=f"mesh={mesh_shape} kind={kind} bc={bc}",
                    )
    finally:
        if prior is None:
            os.environ.pop("HEAT3D_DIRECT_INTERPRET", None)
        else:
            os.environ["HEAT3D_DIRECT_INTERPRET"] = prior
    print("faces_direct_superstep_distributed OK")


def check_overlap_step_distributed():
    """Overlap (interior/boundary split) step == unsplit step on real
    multi-device meshes — the correctness half of SURVEY.md §7.3 item 2."""
    import dataclasses

    # 24 along x so the (8,1,1) slab still leaves a >=3-cell local interior
    grid = (24, 16, 16)
    u_host = golden.random_init(grid, seed=13)
    for mesh_shape in [(8, 1, 1), (2, 2, 2), (1, 2, 4)]:
        for kind in ("7pt", "27pt"):
            for bc in (BoundaryCondition.DIRICHLET, BoundaryCondition.PERIODIC):
                cfg = SolverConfig(
                    grid=GridConfig(shape=grid),
                    stencil=StencilConfig(kind=kind, bc=bc),
                    mesh=MeshConfig(shape=mesh_shape),
                    backend="jnp",
                )
                mesh = build_mesh(cfg.mesh)
                u = jax.device_put(
                    jnp.asarray(u_host), field_sharding(mesh, cfg.mesh)
                )
                got = jax.jit(
                    make_step_fn(dataclasses.replace(cfg, overlap=True), mesh)
                )(u)
                want = jax.jit(make_step_fn(cfg, mesh))(u)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
                    err_msg=f"mesh={mesh_shape} kind={kind} bc={bc}",
                )
    print("overlap_step_distributed OK")


def check_uneven_decomposition():
    """Grids not divisible by the mesh run via bc-value storage padding and
    still match the golden model on the true extents (SURVEY.md §7.3 item 4,
    which the reference class sidesteps by requiring divisibility)."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    for grid, mesh_shape in [
        ((10, 16, 16), (8, 1, 1)),   # padding thicker than some local blocks
        ((9, 10, 11), (2, 2, 2)),
        ((24, 9, 10), (1, 2, 4)),
    ]:
        for kind in ("7pt", "27pt"):
            for bc_value in (0.0, 0.5):
                cfg = SolverConfig(
                    grid=GridConfig(shape=grid),
                    stencil=StencilConfig(
                        kind=kind, bc=BoundaryCondition.DIRICHLET,
                        bc_value=bc_value,
                    ),
                    mesh=MeshConfig(shape=mesh_shape),
                    backend="jnp",
                )
                solver = HeatSolver3D(cfg)
                u = solver.init_state("gaussian")
                u = solver.run(u, 3)
                want = golden.run(
                    golden.gaussian_init(grid).astype(np.float64),
                    cfg.grid, cfg.stencil, 3,
                )
                np.testing.assert_allclose(
                    solver.gather(u), want, rtol=1e-5, atol=1e-6,
                    err_msg=f"grid={grid} mesh={mesh_shape} kind={kind} "
                    f"bc_value={bc_value}",
                )
    print("uneven_decomposition OK")


def check_device_init_distributed():
    """The on-device hot-cube/zeros builders (models.heat3d._device_field)
    == the host block path, bitwise, on real multi-device meshes including
    uneven decompositions (storage padding pinned at bc_value lives on
    shards the device path must also pin)."""
    import os

    from heat3d_tpu.models.heat3d import HeatSolver3D

    for grid, mesh_shape in [
        ((16, 16, 16), (2, 2, 2)),
        ((9, 10, 11), (2, 2, 2)),    # uneven: padding on every axis
        ((10, 16, 16), (8, 1, 1)),   # padding thicker than some blocks
    ]:
        for prec, bc_value in [
            (Precision.fp32(), 0.0),
            (Precision.bf16(), 1.5),
        ]:
            cfg = SolverConfig(
                grid=GridConfig(shape=grid),
                stencil=StencilConfig(
                    kind="7pt", bc=BoundaryCondition.DIRICHLET,
                    bc_value=bc_value,
                ),
                mesh=MeshConfig(shape=mesh_shape),
                precision=prec,
                backend="jnp",
            )
            solver = HeatSolver3D(cfg)
            prior = os.environ.get("HEAT3D_DEVICE_INIT")
            os.environ["HEAT3D_DEVICE_INIT"] = "0"
            try:
                host_hot = np.asarray(solver.init_state("hot-cube"))
                host_zero = np.asarray(solver.zeros_state())
                os.environ["HEAT3D_DEVICE_INIT"] = "1"
                dev_hot = np.asarray(solver.init_state("hot-cube"))
                dev_zero = np.asarray(solver.zeros_state())
            finally:
                if prior is None:
                    os.environ.pop("HEAT3D_DEVICE_INIT", None)
                else:
                    os.environ["HEAT3D_DEVICE_INIT"] = prior
            np.testing.assert_array_equal(
                dev_hot, host_hot,
                err_msg=f"hot-cube grid={grid} mesh={mesh_shape}",
            )
            np.testing.assert_array_equal(
                dev_zero, host_zero,
                err_msg=f"zeros grid={grid} mesh={mesh_shape}",
            )
    print("device_init_distributed OK")


def check_time_blocking_distributed():
    """Temporally-blocked supersteps == plain steps on real multi-device
    meshes, including uneven decompositions (where the intermediate's
    padding/ghost pinning is the subtle part)."""
    import dataclasses

    for grid, mesh_shape, kind, bc, k in [
        ((16, 16, 16), (2, 2, 2), "7pt", BoundaryCondition.DIRICHLET, 2),
        ((16, 16, 16), (2, 2, 2), "27pt", BoundaryCondition.PERIODIC, 2),
        ((16, 16, 16), (8, 1, 1), "27pt", BoundaryCondition.DIRICHLET, 2),
        ((10, 9, 16), (2, 2, 2), "7pt", BoundaryCondition.DIRICHLET, 2),  # uneven
        # k=3: real cross-device width-3 ppermutes + 2-then-1-ring mid fills
        ((16, 16, 16), (2, 2, 2), "7pt", BoundaryCondition.DIRICHLET, 3),
        ((16, 16, 16), (2, 2, 2), "27pt", BoundaryCondition.PERIODIC, 3),
        ((16, 16, 16), (2, 2, 2), "7pt", BoundaryCondition.DIRICHLET, 4),
    ]:
        cfg = SolverConfig(
            grid=GridConfig(shape=grid),
            stencil=StencilConfig(kind=kind, bc=bc, bc_value=0.5
                                  if bc is BoundaryCondition.DIRICHLET else 0.0),
            mesh=MeshConfig(shape=mesh_shape),
            backend="jnp",
        )
        cfg2 = dataclasses.replace(cfg, time_blocking=k)
        u_host = golden.random_init(grid, seed=17)
        from heat3d_tpu.models.heat3d import HeatSolver3D

        s1 = HeatSolver3D(cfg)
        s2 = HeatSolver3D(cfg2)
        u1 = s1.run(s1.init_state(u_host), 5)
        u2 = s2.run(s2.init_state(u_host), 5)
        np.testing.assert_allclose(
            s1.gather(u1), s2.gather(u2), rtol=1e-6, atol=1e-6,
            err_msg=f"grid={grid} mesh={mesh_shape} kind={kind} bc={bc}",
        )
    print("time_blocking_distributed OK")


def check_bf16_distributed():
    grid = (16, 16, 16)
    cfg = SolverConfig(
        grid=GridConfig(shape=grid),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(2, 2, 2)),
        precision=Precision.bf16(),
        backend="jnp",
    )
    mesh = build_mesh(cfg.mesh)
    u_host = golden.gaussian_init(grid)
    u = jax.device_put(
        jnp.asarray(u_host, jnp.bfloat16), field_sharding(mesh, cfg.mesh)
    )
    got, r2 = jax.jit(make_step_fn(cfg, mesh, with_residual=True))(u)
    assert got.dtype == jnp.bfloat16
    assert r2.dtype == jnp.float32
    # single-device same policy
    cfg1 = SolverConfig(
        grid=GridConfig(shape=grid), stencil=cfg.stencil,
        mesh=MeshConfig(shape=(1, 1, 1)), precision=cfg.precision, backend="jnp",
    )
    mesh1 = build_mesh(cfg1.mesh, devices=jax.devices()[:1])
    want, r2_1 = jax.jit(make_step_fn(cfg1, mesh1, with_residual=True))(
        jax.device_put(jnp.asarray(u_host, jnp.bfloat16),
                       field_sharding(mesh1, cfg1.mesh))
    )
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
    )
    # the 8-way psum reduces partial sums in a different order than the
    # single-device sum — identical values, different fp32 rounding path
    assert float(r2) == pytest.approx(float(r2_1), rel=1e-5)
    print("bf16_distributed OK")


def check_halo_ghost_identity():
    """Rank-constant shards: after exchange, each ghost layer holds the
    neighbor's rank id (periodic wrap included) — the direct analogue of the
    reference's ghost-fill correctness check (SURVEY.md §4)."""
    mesh_cfg = MeshConfig(shape=(2, 2, 2))
    mesh = build_mesh(mesh_cfg)
    local = (4, 4, 4)
    grid = tuple(l * p for l, p in zip(local, mesh_cfg.shape))

    def rank_field():
        # global array whose value in each shard is its linear device index
        def linear_rank(x, y, z):
            return (x // local[0]) * 4 + (y // local[1]) * 2 + (z // local[2])

        idx = np.indices(grid)
        return jnp.asarray(linear_rank(*idx).astype(np.float32))

    u = jax.device_put(rank_field(), field_sharding(mesh, mesh_cfg))

    for bc in (BoundaryCondition.PERIODIC, BoundaryCondition.DIRICHLET):
        f = jax.jit(
            shard_map(
                lambda x: exchange_halo(x, mesh_cfg, bc, bc_value=-1.0),
                mesh=mesh,
                in_specs=P("x", "y", "z"),
                out_specs=P("x", "y", "z"),
            )
        )
        padded = f(u)  # global (2*(4+2),)*3 array of per-shard padded blocks
        blocks = np.asarray(padded).reshape(2, 6, 2, 6, 2, 6).transpose(
            0, 2, 4, 1, 3, 5
        )  # [px,py,pz][local 6,6,6]
        for px in range(2):
            for py in range(2):
                for pz in range(2):
                    b = blocks[px, py, pz]
                    me = px * 4 + py * 2 + pz
                    assert (b[1:-1, 1:-1, 1:-1] == me).all()
                    # x-low ghost: neighbor (px-1, py, pz); with size-2 axes,
                    # periodic wrap neighbor == the other device
                    for axis, (lo_nb, hi_nb) in enumerate(
                        [
                            ((1 - px) * 4 + py * 2 + pz,) * 2,
                            (px * 4 + (1 - py) * 2 + pz,) * 2,
                            (px * 4 + py * 2 + (1 - pz),) * 2,
                        ]
                    ):
                        coord = (px, py, pz)[axis]
                        sl_lo = [slice(1, -1)] * 3
                        sl_hi = [slice(1, -1)] * 3
                        sl_lo[axis] = 0
                        sl_hi[axis] = 5
                        lo = b[tuple(sl_lo)]
                        hi = b[tuple(sl_hi)]
                        if bc is BoundaryCondition.PERIODIC:
                            assert (lo == lo_nb).all(), (axis, coord, "lo")
                            assert (hi == hi_nb).all(), (axis, coord, "hi")
                        else:
                            # domain-boundary ghosts hold bc_value, interior
                            # ghosts hold the neighbor id
                            assert (lo == (-1.0 if coord == 0 else lo_nb)).all()
                            assert (hi == (-1.0 if coord == 1 else hi_nb)).all()
    print("halo_ghost_identity OK")


def check_multistep_vs_golden():
    grid = (16, 16, 16)
    cfg = SolverConfig(
        grid=GridConfig(shape=grid),
        stencil=StencilConfig(kind="27pt", bc=BoundaryCondition.PERIODIC),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
    )
    mesh = build_mesh(cfg.mesh)
    u_host = golden.gaussian_init(grid)
    u = jax.device_put(jnp.asarray(u_host), field_sharding(mesh, cfg.mesh))
    got = jax.jit(make_multistep_fn(cfg, mesh))(u, jnp.int32(5))
    want = golden.run(u_host.astype(np.float64), cfg.grid, cfg.stencil, 5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    print("multistep_vs_golden OK")


def check_dma_halo_ring_interpret():
    """Pallas RDMA halo exchange (interpret mode) on a real 8-device ring ==
    the ppermute exchange, for every array axis (width-1 zero-staging fast
    path and axis-leading slab staging alike) and ghost widths 1..3, periodic and Dirichlet.

    jax 0.9's interpret mode cannot discharge remote DMA on meshes with >1
    named axis (dma_start_p NotImplementedError, MESH and LOGICAL device-id
    forms alike — verified; the check binds to the shard_map MESH, so even
    an (8,1,1) 3-named-axis mesh is rejected, which is why no full-step
    DMA execution check exists off-TPU), so multi-axis composition executes
    only on real multi-chip hardware; here each array axis is driven on a
    1D mesh and the 3D composition is covered by the TPU lowering tests
    (tests/test_distributed.py)."""
    from jax.sharding import Mesh, NamedSharding

    from heat3d_tpu.ops.halo_pallas import exchange_axis_dma
    from heat3d_tpu.parallel.halo import exchange_axis

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
    # 4 cells/shard on the ring axis: admits width 4 (the deep-tb slab)
    base = (32, 32, 32)
    u_host = golden.random_init(base, seed=3)
    for axis in range(3):
        spec = P(*["x" if a == axis else None for a in range(3)])
        u = jax.device_put(jnp.asarray(u_host), NamedSharding(mesh, spec))
        for periodic in (True, False):
            for width in (1, 2, 3, 4):
                got = jax.jit(
                    shard_map(
                        lambda x: exchange_axis_dma(
                            x, axis, "x", 8, ("x",), periodic, 1.5,
                            width=width, interpret=True,
                        ),
                        mesh=mesh, in_specs=spec, out_specs=spec,
                        check_vma=False,
                    )
                )(u)
                want = jax.jit(
                    shard_map(
                        lambda x: exchange_axis(
                            x, axis, "x", 8, periodic, 1.5, width=width
                        ),
                        mesh=mesh, in_specs=spec, out_specs=spec,
                        check_vma=False,
                    )
                )(u)
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want),
                    err_msg=f"axis={axis} periodic={periodic} width={width}",
                )
    print("dma_halo_ring_interpret OK (axes 0-2, widths 1-4)")


def check_sharded_checkpoint_roundtrip():
    import tempfile

    from heat3d_tpu.utils import checkpoint as ckpt

    mesh_cfg = MeshConfig(shape=(2, 2, 2))
    mesh = build_mesh(mesh_cfg)
    sharding = field_sharding(mesh, mesh_cfg)
    u_host = golden.random_init((8, 8, 8), seed=3)
    u = jax.device_put(jnp.asarray(u_host), sharding)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, u, step=42)
        u2, step, _ = ckpt.load(d, sharding)
        assert step == 42
        np.testing.assert_array_equal(np.asarray(u2), np.asarray(u))
    print("sharded_checkpoint_roundtrip OK")


def check_gather_slice_distributed():
    """gather_slice on a real (2,2,2) mesh == the golden field's plane,
    including an uneven (bc-padded) decomposition whose padding must be
    stripped from the plane."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    for grid in ((8, 8, 8), (10, 9, 8)):
        cfg = SolverConfig(
            grid=GridConfig(shape=grid),
            mesh=MeshConfig(shape=(2, 2, 2)),
            backend="jnp",
        )
        solver = HeatSolver3D(cfg)
        u = solver.run(solver.init_state("gaussian"), 2)
        full = solver.gather(u)
        for axis, index in ((0, 0), (1, grid[1] - 1), (2, grid[2] // 2)):
            plane = solver.gather_slice(u, axis, index)
            idx = tuple(index if a == axis else slice(None) for a in range(3))
            np.testing.assert_array_equal(plane, full[idx])
    print("gather_slice_distributed OK")


def check_deep_tb_tier1():
    """Tier-1 deep-tb certification on REAL multi-device meshes: the k=3
    and k=4 supersteps (jnp ring-recompute path — the route every
    non-TPU platform runs) match k sequential ``make_step_fn`` steps,
    AND both match the fp64 NumPy golden oracle, with cross-device
    width-k ppermutes and 2-then-1-ring mid fills actually executing.
    Focused and fast so test_multidevice.py can run it UNMARKED (tier-1)
    in a 4-device subprocess."""
    import dataclasses

    from heat3d_tpu.models.heat3d import HeatSolver3D

    for k, steps, grid, mesh_shape, bc, bcv in (
        (3, 6, (8, 8, 8), (2, 2, 1), BoundaryCondition.DIRICHLET, 0.5),
        (3, 3, (12, 8, 8), (4, 1, 1), BoundaryCondition.PERIODIC, 0.0),
        (4, 5, (8, 8, 8), (2, 2, 1), BoundaryCondition.DIRICHLET, 0.0),
    ):
        cfg = SolverConfig(
            grid=GridConfig(shape=grid),
            stencil=StencilConfig(bc=bc, bc_value=bcv),
            mesh=MeshConfig(shape=mesh_shape),
            backend="jnp",
        )
        cfgk = dataclasses.replace(cfg, time_blocking=k)
        u_host = golden.random_init(grid, seed=23)
        s1, sk = HeatSolver3D(cfg), HeatSolver3D(cfgk)
        got = sk.gather(sk.run(sk.init_state(u_host), steps))
        want = s1.gather(s1.run(s1.init_state(u_host), steps))
        label = f"k={k} mesh={mesh_shape} bc={bc}"
        np.testing.assert_allclose(
            got, want, rtol=1e-6, atol=1e-6,
            err_msg=f"superstep != sequential steps ({label})",
        )
        want64 = golden.run(
            u_host.astype(np.float64), cfg.grid, cfg.stencil, steps
        )
        np.testing.assert_allclose(
            got, want64, rtol=1e-4, atol=1e-5,
            err_msg=f"superstep != fp64 golden ({label})",
        )
    print("deep_tb_tier1 OK")


def check_deep_tb_streamk_interpret():
    """The fused k-sweep streamk kernel on REAL multi-device meshes via
    the interpret tier: the kernel's domain-edge detection (axis_index
    gating in _pin_out_of_domain) must pin intermediate rings ONLY at
    domain-edge shards and leave exchanged-ghost rings intact at interior
    shards — a (1,1,1) mesh can't tell those apart (every boundary is a
    domain edge there). Nonzero Dirichlet bc_value makes a wrong interior
    pin numerically loud. Parity target: k sequential jnp steps."""
    import dataclasses
    import os

    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.parallel.step import _fused_streamk_fn

    saved = {
        k: os.environ.get(k)
        for k in ("HEAT3D_DIRECT_INTERPRET", "HEAT3D_NO_DIRECT")
    }
    os.environ["HEAT3D_DIRECT_INTERPRET"] = "1"
    os.environ["HEAT3D_NO_DIRECT"] = "1"  # pin the streamk route
    try:
        for k, grid, mesh_shape, bc, bcv in (
            (3, (12, 8, 8), (4, 1, 1), BoundaryCondition.DIRICHLET, 0.5),
            (4, (8, 8, 8), (2, 2, 1), BoundaryCondition.DIRICHLET, 0.25),
            (3, (12, 8, 8), (4, 1, 1), BoundaryCondition.PERIODIC, 0.0),
        ):
            cfgk = SolverConfig(
                grid=GridConfig(shape=grid),
                stencil=StencilConfig(bc=bc, bc_value=bcv),
                mesh=MeshConfig(shape=mesh_shape),
                backend="auto",
                time_blocking=k,
            )
            assert _fused_streamk_fn(cfgk) is not None, (
                f"streamk did not resolve under interpret (k={k})"
            )
            cfg1 = dataclasses.replace(
                cfgk, time_blocking=1, backend="jnp"
            )
            u_host = golden.random_init(grid, seed=29)
            sk, s1 = HeatSolver3D(cfgk), HeatSolver3D(cfg1)
            got = sk.gather(sk.run(sk.init_state(u_host), k))
            want = s1.gather(s1.run(s1.init_state(u_host), k))
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-6,
                err_msg=(
                    f"streamk superstep != sequential steps "
                    f"(k={k} mesh={mesh_shape} bc={bc})"
                ),
            )
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
    print("deep_tb_streamk_interpret OK")


def _run_solver(cfg, u_host, steps):
    from heat3d_tpu.models.heat3d import HeatSolver3D

    s = HeatSolver3D(cfg)
    return s.gather(s.run(s.init_state(u_host), steps))


def check_plan_bitwise_parity():
    """Plan-built step/superstep programs are BITWISE-identical to the
    ad-hoc exchange path (HEAT3D_NO_PLAN=1 — the pre-plan dispatch kept
    verbatim) on real multi-device meshes, across stencils, temporal
    blocking depths and both halo orderings — the tentpole acceptance
    criterion of the persistent-exchange-plan refactor."""
    import dataclasses
    import os

    from heat3d_tpu.parallel import plan as hplan

    grid = (16, 16, 16)
    u_host = golden.random_init(grid, seed=31)
    combos = [
        ("7pt", 1, "axis", (4, 1, 1)),
        ("7pt", 1, "pairwise", (2, 2, 1)),
        ("7pt", 2, "axis", (2, 2, 1)),
        ("7pt", 3, "axis", (4, 1, 1)),
        ("7pt", 4, "axis", (2, 2, 1)),
        ("27pt", 1, "axis", (2, 2, 1)),
        ("27pt", 2, "axis", (4, 1, 1)),
    ]
    for kind, tb, ho, mesh_shape in combos:
        cfg = SolverConfig(
            grid=GridConfig(shape=grid),
            stencil=StencilConfig(kind=kind, bc_value=0.5),
            mesh=MeshConfig(shape=mesh_shape),
            backend="jnp",
            time_blocking=tb,
            halo_order=ho,
        )
        steps = max(3, tb + 1)
        hplan.clear_plan_cache()
        got = _run_solver(cfg, u_host, steps)
        os.environ["HEAT3D_NO_PLAN"] = "1"
        try:
            want = _run_solver(cfg, u_host, steps)
        finally:
            del os.environ["HEAT3D_NO_PLAN"]
        assert np.array_equal(got, want), (
            f"plan-built program != ad-hoc exchange path bitwise "
            f"({kind} tb={tb} {ho} mesh={mesh_shape})"
        )
    print("plan_bitwise_parity OK")


def check_plan_partitioned_identity():
    """halo_plan='partitioned' (early-bird sub-block sends) is VALUE-
    (indeed bitwise-) identical to 'monolithic' on every judged shape,
    including the uneven decomposition whose padded shards exercise the
    bc-pin masks, pairwise ordering, deep temporal blocking, and
    periodic wrap rings. The partition granularity floor is zeroed so
    the 16^3 faces genuinely split into sub-block permutes (the default
    1 MiB floor would ship them whole)."""
    import dataclasses
    import os

    from heat3d_tpu.parallel import plan as hplan

    os.environ[hplan.ENV_PART_MIN_BYTES] = "0"
    hplan.clear_plan_cache()
    combos = [
        ((16, 16, 16), "7pt", 1, "axis", (4, 1, 1), "dirichlet", 0.5),
        ((18, 18, 18), "7pt", 1, "axis", (4, 1, 1), "dirichlet", 0.25),
        ((16, 16, 16), "27pt", 1, "axis", (2, 2, 1), "dirichlet", 0.0),
        ((16, 16, 16), "7pt", 3, "axis", (2, 2, 1), "dirichlet", 0.5),
        ((16, 16, 16), "7pt", 1, "pairwise", (4, 1, 1), "dirichlet", 0.0),
        ((16, 16, 16), "7pt", 2, "axis", (4, 1, 1), "periodic", 0.0),
    ]
    for grid, kind, tb, ho, mesh_shape, bc, bcv in combos:
        base = SolverConfig(
            grid=GridConfig(shape=grid),
            stencil=StencilConfig(
                kind=kind, bc=BoundaryCondition(bc), bc_value=bcv
            ),
            mesh=MeshConfig(shape=mesh_shape),
            backend="jnp",
            time_blocking=tb,
            halo_order=ho,
        )
        u_host = golden.random_init(grid, seed=37)
        steps = max(3, tb + 1)
        mono = _run_solver(
            dataclasses.replace(base, halo_plan="monolithic"), u_host, steps
        )
        part = _run_solver(
            dataclasses.replace(base, halo_plan="partitioned"), u_host, steps
        )
        assert np.array_equal(mono, part), (
            f"partitioned != monolithic ({grid} {kind} tb={tb} {ho} "
            f"mesh={mesh_shape} bc={bc})"
        )
    del os.environ[hplan.ENV_PART_MIN_BYTES]
    print("plan_partitioned_identity OK")


def check_plan_ensemble_parity():
    """The serve ensemble's traced-bind path consumes plans too: the
    batched run program is bitwise-identical to the ad-hoc exchange
    build (HEAT3D_NO_PLAN=1), and partitioned plans are member-wise
    bitwise-identical to monolithic — on the hybrid b=2 x (2,1,1) mesh,
    where the spatial ring and the batch axis coexist. Granularity
    floor zeroed so the partitioned arm genuinely splits faces."""
    import dataclasses
    import os

    from heat3d_tpu.parallel import plan as hplan

    os.environ[hplan.ENV_PART_MIN_BYTES] = "0"
    hplan.clear_plan_cache()
    from heat3d_tpu.serve.ensemble import EnsembleSolver
    from heat3d_tpu.serve.scenario import Scenario, ScenarioBatch

    def run_ensemble(halo_plan):
        base = SolverConfig(
            grid=GridConfig.cube(16),
            mesh=MeshConfig(shape=(2, 1, 1)),
            backend="jnp",
            time_blocking=2,
            halo_plan=halo_plan,
        )
        batch = ScenarioBatch(
            base,
            [
                Scenario(alpha=0.3, bc_value=1.0, steps=5),
                Scenario(alpha=0.5, steps=7),
            ],
        )
        es = EnsembleSolver(batch, batch_mesh=2)
        return es.gather(es.run(es.init_state(), None))

    got = run_ensemble("monolithic")
    os.environ["HEAT3D_NO_PLAN"] = "1"
    try:
        want = run_ensemble("monolithic")
    finally:
        del os.environ["HEAT3D_NO_PLAN"]
    assert np.array_equal(got, want), (
        "ensemble plan-built run != ad-hoc exchange build bitwise"
    )
    part = run_ensemble("partitioned")
    assert np.array_equal(got, part), (
        "ensemble partitioned != monolithic member-wise"
    )
    del os.environ[hplan.ENV_PART_MIN_BYTES]
    print("plan_ensemble_parity OK")


def check_eqn_heat_spec_vs_legacy_bitwise():
    """The declarative equation frontend reproduces the legacy hardcoded
    heat path BITWISE on real multi-device meshes — the eqn tentpole
    acceptance criterion. The default run compiles the heat spec
    (eqn.solver_taps); the reference arm (HEAT3D_EQN_LEGACY=1) runs the
    verbatim pre-spec stencil_taps derivation. Arms span 7pt/27pt x
    tb{1,2} x axis/pairwise x monolithic/partitioned plans (the
    partition floor zeroed so sub-block permutes genuinely issue)."""
    import os

    from heat3d_tpu.parallel import plan as hplan

    grid = (16, 16, 16)
    u_host = golden.random_init(grid, seed=41)
    combos = [
        ("7pt", 1, "axis", "monolithic", (4, 1, 1)),
        ("7pt", 1, "pairwise", "monolithic", (2, 2, 1)),
        ("7pt", 1, "axis", "partitioned", (2, 2, 1)),
        ("7pt", 2, "axis", "monolithic", (2, 2, 1)),
        ("7pt", 2, "axis", "partitioned", (4, 1, 1)),
        ("27pt", 1, "axis", "monolithic", (2, 2, 1)),
        ("27pt", 1, "axis", "partitioned", (4, 1, 1)),
        ("27pt", 2, "axis", "monolithic", (4, 1, 1)),
    ]
    os.environ[hplan.ENV_PART_MIN_BYTES] = "0"
    try:
        for kind, tb, ho, hp, mesh_shape in combos:
            cfg = SolverConfig(
                grid=GridConfig(shape=grid),
                stencil=StencilConfig(kind=kind, bc_value=0.5),
                mesh=MeshConfig(shape=mesh_shape),
                backend="jnp",
                time_blocking=tb,
                halo_order=ho,
                halo_plan=hp,
                equation="heat",
            )
            steps = max(3, tb + 1)
            hplan.clear_plan_cache()
            got = _run_solver(cfg, u_host, steps)
            os.environ["HEAT3D_EQN_LEGACY"] = "1"
            try:
                want = _run_solver(cfg, u_host, steps)
            finally:
                del os.environ["HEAT3D_EQN_LEGACY"]
            assert np.array_equal(got, want), (
                f"spec-compiled heat != legacy hardcoded path bitwise "
                f"({kind} tb={tb} {ho} {hp} mesh={mesh_shape})"
            )
    finally:
        del os.environ[hplan.ENV_PART_MIN_BYTES]
    print("eqn_heat_spec_vs_legacy_bitwise OK")


def check_eqn_families_golden_distributed():
    """Every spec-built family advances correctly end-to-end on a real
    4-device mesh: the distributed fp32 run matches the fp64 golden
    stepper driven with the SAME spec-compiled taps (machinery parity —
    halo plans, supersteps, padding pins all carrying the new taps), and
    the periodic plane-wave arm tracks the family's analytic MMS
    solution. One arm runs the auto knobs (halo='auto',
    time_blocking=0) so tuner resolution of an eqn config is exercised,
    and one runs a partitioned plan."""
    import dataclasses

    from heat3d_tpu import eqn

    grid = (16, 16, 16)
    # (family, params, tb, plan, mesh, dt) — reaction combos pass an
    # explicit dt: their decay rates tighten the explicit-Euler bound
    # below the default diffusion-only derivation, which config
    # validation now (correctly) rejects for non-heat families
    combos = [
        ("aniso-diffusion", (), 1, "monolithic", (2, 2, 1), None),
        ("advection-diffusion", (("vx", 0.8), ("vy", 0.4)), 1,
         "partitioned", (4, 1, 1), None),
        ("advection-diffusion", (), 2, "monolithic", (2, 2, 1), None),
        ("reaction-diffusion", (("rate", -0.7),), 1, "monolithic",
         (4, 1, 1), 0.3),
        ("reaction-diffusion", (), 2, "partitioned", (2, 2, 1), 0.3),
    ]
    import os

    from heat3d_tpu.parallel import plan as hplan

    os.environ[hplan.ENV_PART_MIN_BYTES] = "0"
    try:
        for fam, params, tb, hp, mesh_shape, dt in combos:
            cfg = SolverConfig(
                grid=GridConfig(shape=grid, alpha=0.4, dt=dt),
                stencil=StencilConfig(kind="7pt", bc_value=0.25),
                mesh=MeshConfig(shape=mesh_shape),
                backend="jnp",
                time_blocking=tb,
                halo_plan=hp,
                equation=fam,
                eq_params=params,
            )
            hplan.clear_plan_cache()
            u_host = golden.random_init(grid, seed=43)
            steps = 6
            got = _run_solver(cfg, u_host, steps).astype(np.float64)
            want = golden.run(
                u_host, cfg.grid, cfg.stencil, steps,
                taps=eqn.solver_taps(cfg),
            )
            rel = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
            assert rel < 1e-5, (
                f"{fam} tb={tb} {hp} mesh={mesh_shape}: distributed run "
                f"diverges from the fp64 golden oracle (rel {rel:.2e})"
            )
    finally:
        del os.environ[hplan.ENV_PART_MIN_BYTES]

    # tuner-resolution arm: auto knobs on an eqn config resolve through
    # the cache (miss -> static fallback) and the run still matches gold
    cfg = SolverConfig(
        grid=GridConfig(shape=grid, alpha=0.4),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(2, 2, 1)),
        backend="jnp",
        halo="auto",
        time_blocking=0,
        equation="advection-diffusion",
    )
    u_host = golden.random_init(grid, seed=44)
    got = _run_solver(cfg, u_host, 5).astype(np.float64)
    resolved = dataclasses.replace(cfg, halo="ppermute", time_blocking=1)
    want = golden.run(
        u_host, resolved.grid, resolved.stencil, 5,
        taps=eqn.solver_taps(resolved),
    )
    rel = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert rel < 1e-5, f"auto-knob eqn run diverges from gold (rel {rel:.2e})"

    # MMS arm: periodic plane wave vs the analytic solution (loose bound
    # — the convergence-order discipline lives in tests/test_eqn.py; this
    # proves the DISTRIBUTED program tracks the same continuous solution)
    n = 16
    shape = (n, n, n)
    spacing = (1.0 / n, 1.0 / n, 1.0 / n)
    cfg = SolverConfig(
        grid=GridConfig(shape=shape, spacing=spacing, alpha=0.01,
                        dt=2e-4),
        stencil=StencilConfig(kind="7pt", bc=BoundaryCondition.PERIODIC),
        mesh=MeshConfig(shape=(2, 2, 1)),
        backend="jnp",
        equation="advection-diffusion",
        eq_params=(("vx", 0.5), ("vy", 0.25), ("vz", 0.0)),
    )
    wave = (1, 1, 0)
    steps = 50
    t_end = steps * cfg.grid.effective_dt()
    mu, omega = eqn.mms_rates(cfg, golden.wavevector(shape, spacing, wave))
    u0 = golden.plane_wave(shape, spacing, wave)
    got = _run_solver(cfg, u0.astype(np.float32), steps).astype(np.float64)
    want = golden.plane_wave(shape, spacing, wave, t=t_end, mu=mu,
                             omega=omega)
    err = np.max(np.abs(got - want))
    assert err < 5e-2, (
        f"distributed advection-diffusion run does not track the "
        f"analytic plane wave (max err {err:.3e})"
    )
    print("eqn_families_golden_distributed OK")


def check_eqn_serve_traced_bind():
    """Per-member spec coefficients through the serve traced bind: an
    advection-diffusion batch whose members carry DIFFERENT velocities
    (Scenario.eq_params) runs as ONE compiled parametric program on the
    hybrid b=2 x (2,1,1) mesh, and each member matches its own solo
    HeatSolver3D run; the baked certification mode is bitwise-identical
    to the solo runs by construction."""
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.serve.ensemble import EnsembleSolver
    from heat3d_tpu.serve.scenario import Scenario, ScenarioBatch

    base = SolverConfig(
        grid=GridConfig.cube(16, alpha=0.4),
        mesh=MeshConfig(shape=(2, 1, 1)),
        backend="jnp",
        equation="advection-diffusion",
    )
    members = [
        Scenario(alpha=0.4, steps=5, eq_params=(("vx", 0.5),)),
        Scenario(alpha=0.3, steps=5, eq_params=(("vx", 1.0), ("vy", 0.5))),
    ]
    batch = ScenarioBatch(base, members)

    solos = []
    for i in range(len(members)):
        cfg_i = batch.member_config(i)
        s = HeatSolver3D(cfg_i)
        solos.append(s.gather(s.run(s.init_state("hot-cube"), 5)))

    es = EnsembleSolver(batch, batch_mesh=2, bind="traced")
    fields = es.gather(es.run(es.init_state(), None))
    for i, solo in enumerate(solos):
        rel = np.max(np.abs(fields[i].astype(np.float64) - solo)) / max(
            float(np.max(np.abs(solo))), 1e-30
        )
        assert rel < 1e-5, (
            f"traced-bind member {i} (own velocity) diverges from its "
            f"solo run (rel {rel:.2e})"
        )

    es_baked = EnsembleSolver(batch, batch_mesh=1, bind="baked")
    fields_b = es_baked.gather(es_baked.run(es_baked.init_state(), None))
    for i, solo in enumerate(solos):
        assert np.array_equal(fields_b[i], solo.astype(fields_b.dtype)), (
            f"baked member {i} != its solo run bitwise"
        )
    print("eqn_serve_traced_bind OK")


def check_timeint_dist_bitwise():
    """Leapfrog (tb=1 and the tb=2 two-level ring superstep), the
    matrix-free CG solve at 15x the explicit CFL bound, and the
    variable-coefficient flux update all run on a REAL (2,2,1) mesh
    BITWISE-identical to the (1,1,1) solo run. Leapfrog/CG certify at
    f32 storage with f64 compute/residual (the battery env sets
    JAX_ENABLE_X64): at f32 compute XLA:CPU contracts the tap-sweep FMAs
    differently across mesh shapes (1-ulp drift), so bitwise solo==dist
    is the f64-compute tier's contract; the varcoef flux update is
    bitwise even at plain f32."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding

    from heat3d_tpu import timeint
    from heat3d_tpu.timeint import cg, coeffield

    n = 12
    rng = np.random.default_rng(7)
    prec = Precision(storage="float32", compute="float64",
                     residual="float64")
    mesh_s = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                  ("x", "y", "z"))
    mesh_d = Mesh(np.array(jax.devices()[:4]).reshape(2, 2, 1),
                  ("x", "y", "z"))
    sh_s = NamedSharding(mesh_s, P("x", "y", "z"))
    sh_d = NamedSharding(mesh_d, P("x", "y", "z"))

    # leapfrog: tb=1 (plain steps) and tb=2 (shrinking-ring superstep,
    # the k*r / (k-1)*r two-level ghost plan) — both carry levels bitwise
    for tb in (1, 2):
        cfg = SolverConfig(
            grid=GridConfig(shape=(n, n, n), dt=0.01,
                            spacing=(1 / n, 1 / n, 1 / n)),
            stencil=StencilConfig(kind="7pt",
                                  bc=BoundaryCondition.DIRICHLET,
                                  bc_value=0.1),
            mesh=MeshConfig(shape=(1, 1, 1)),
            backend="jnp",
            halo="ppermute",
            time_blocking=tb,
            equation="wave",
            eq_params=(("c", 1.0),),
            integrator="leapfrog",
            precision=prec,
        )
        cfg_d = dataclasses.replace(cfg, mesh=MeshConfig(shape=(2, 2, 1)))
        u0 = rng.standard_normal((n, n, n)).astype(np.float32)
        um1 = rng.standard_normal((n, n, n)).astype(np.float32)
        ms_s = jax.jit(timeint.make_multistep_fn(cfg, mesh_s))
        ms_d = jax.jit(timeint.make_multistep_fn(cfg_d, mesh_d))
        c_s = ms_s((jax.device_put(u0, sh_s), jax.device_put(um1, sh_s)),
                   jnp.int32(7))
        c_d = ms_d((jax.device_put(u0, sh_d), jax.device_put(um1, sh_d)),
                   jnp.int32(7))
        for lvl in (0, 1):
            assert np.array_equal(np.asarray(c_s[lvl]),
                                  np.asarray(c_d[lvl])), (
                f"leapfrog tb={tb} carry level {lvl}: dist != solo bitwise"
            )

    # implicit CG at 15x CFL: field bitwise AND the psum-replicated
    # convergence decision identical (same iteration count on every mesh)
    cfg_c = SolverConfig(
        grid=GridConfig(shape=(n, n, n), spacing=(1 / n, 1 / n, 1 / n)),
        stencil=StencilConfig(kind="7pt", bc=BoundaryCondition.DIRICHLET,
                              bc_value=0.5),
        mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp",
        halo="ppermute",
        integrator="implicit-cg",
        precision=prec,
    )
    cfg_c = dataclasses.replace(
        cfg_c,
        grid=dataclasses.replace(cfg_c.grid,
                                 dt=15 * cfg_c.grid.stable_dt()),
    )
    cfg_cd = dataclasses.replace(cfg_c, mesh=MeshConfig(shape=(2, 2, 1)))
    u0c = rng.uniform(0.0, 1.0, (n, n, n)).astype(np.float32)
    u1s, it_s, rr_s = jax.jit(
        cg.make_step_fn(cfg_c, mesh_s, with_stats=True)
    )(jax.device_put(u0c, sh_s))
    u1d, it_d, _ = jax.jit(
        cg.make_step_fn(cfg_cd, mesh_d, with_stats=True)
    )(jax.device_put(u0c, sh_d))
    assert np.array_equal(np.asarray(u1s), np.asarray(u1d)), (
        "implicit-cg solve: dist != solo bitwise"
    )
    assert int(it_s) == int(it_d) and 1 <= int(it_s) <= 64, (
        f"CG iteration counts differ across meshes "
        f"({int(it_s)} vs {int(it_d)})"
    )
    assert float(rr_s) < 1e-5

    # varcoef flux update: bitwise at plain f32 (one association order)
    cfg_v = SolverConfig(
        grid=GridConfig(shape=(n, n, n), dt=5e-4,
                        spacing=(1 / n, 1 / n, 1 / n)),
        stencil=StencilConfig(kind="7pt", bc=BoundaryCondition.PERIODIC),
        mesh=MeshConfig(shape=(1, 1, 1)),
        backend="jnp",
        halo="ppermute",
    )
    cfg_vd = dataclasses.replace(cfg_v, mesh=MeshConfig(shape=(2, 2, 1)))
    a = coeffield.make_coef_field("checker", (n, n, n),
                                  seed=1).astype(np.float32)
    uv = rng.standard_normal((n, n, n)).astype(np.float32)
    r_s = jax.jit(coeffield.make_varcoef_multistep_fn(cfg_v, mesh_s))(
        jax.device_put(uv, sh_s), jax.device_put(a, sh_s), jnp.int32(5))
    r_d = jax.jit(coeffield.make_varcoef_multistep_fn(cfg_vd, mesh_d))(
        jax.device_put(uv, sh_d), jax.device_put(a, sh_d), jnp.int32(5))
    assert np.array_equal(np.asarray(r_s), np.asarray(r_d)), (
        "varcoef flux update: dist != solo bitwise"
    )
    print("timeint_dist_bitwise OK")


def check_timeint_supervised_two_level_resume():
    """A leapfrog run interrupted at step 4 and resumed to step 8 lands
    BITWISE on the uninterrupted run's final carry — BOTH levels restored
    from the two-level checkpoint generation. A newer generation written
    by a DIFFERENT integrator (single-level explicit-euler) is skipped
    (MultiLevelCheckpointError — wrong shape of state, not corrupt
    shards) WITHOUT being quarantined and stays on disk."""
    import dataclasses
    import os
    import shutil
    import tempfile

    from heat3d_tpu import timeint
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.resilience.supervisor import load_latest_generation

    n = 12
    cfg = SolverConfig(
        grid=GridConfig(shape=(n, n, n), dt=0.01,
                        spacing=(1 / n, 1 / n, 1 / n)),
        stencil=StencilConfig(kind="7pt",
                              bc=BoundaryCondition.DIRICHLET,
                              bc_value=0.1),
        mesh=MeshConfig(shape=(2, 2, 1)),
        backend="jnp",
        halo="ppermute",
        equation="wave",
        eq_params=(("c", 1.0),),
        integrator="leapfrog",
    )
    tmp = tempfile.mkdtemp(prefix="timeint_resume_")
    try:
        root_a = os.path.join(tmp, "a")
        res_a = HeatSolver3D(cfg).run_supervised(
            8, root_a, checkpoint_every=2)
        assert res_a.steps_done == 8 and not res_a.resumed_from

        # a run that ENDS at step 4, resumed to 8 (its last update and
        # residual come from the loop program + the carry, so the step-4
        # generation is the uninterrupted run's step-4 state), and a run
        # interrupted mid-flight by a backend loss past step 5
        from heat3d_tpu.resilience.faults import FaultPlan, _parse_spec
        from heat3d_tpu.resilience.retry import RetryPolicy

        root_b = os.path.join(tmp, "b")
        res_half = HeatSolver3D(cfg).run_supervised(
            4, root_b, checkpoint_every=2)
        assert res_half.steps_done == 4
        res_b = HeatSolver3D(cfg).run_supervised(
            8, root_b, checkpoint_every=2)
        assert res_b.resumed_from == 4 and res_b.steps_done == 8
        res_c = HeatSolver3D(cfg).run_supervised(
            8, os.path.join(tmp, "c"), checkpoint_every=2,
            faults=FaultPlan(_parse_spec("backend-loss:step=5")),
            heal_policy=RetryPolicy(base_delay_s=0.01, deadline_s=5.0),
            probe=lambda: "cpu",
        )
        assert res_c.steps_done == 8 and len(res_c.recoveries) == 1
        assert 0 < res_c.recoveries[0].resumed_from < 8, res_c.recoveries
        for res in (res_b, res_c):
            for lvl in (0, 1):
                ga = res_a.solver.gather(res_a.u[lvl])
                gb = res.solver.gather(res.u[lvl])
                assert np.array_equal(ga, gb), (
                    f"resumed carry level {lvl} != uninterrupted run bitwise"
                )

        # a NEWER single-level (explicit-euler) generation must be
        # skipped in place, never quarantined
        cfg_exp = dataclasses.replace(
            cfg, equation="heat", eq_params=(),
            integrator="explicit-euler")
        es = HeatSolver3D(cfg_exp)
        fake = os.path.join(root_b, "gen-00000012")
        es.save_checkpoint(fake, es.init_state("hot-cube"), 12)
        lf = HeatSolver3D(cfg)
        try:
            lf.load_checkpoint(fake)
            raise AssertionError(
                "single-level checkpoint loaded into a two-level carry")
        except timeint.MultiLevelCheckpointError:
            pass
        loaded, quarantined = load_latest_generation(lf, root_b)
        assert loaded is not None, "no generation loaded after skip"
        carry, step = loaded
        assert step == 8, f"expected resume at step 8, got {step}"
        assert quarantined == [], (
            f"level-mismatch generation was quarantined: {quarantined}")
        assert os.path.isdir(fake), "skipped generation must stay on disk"
        assert isinstance(carry, tuple) and len(carry) == 2
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("timeint_supervised_two_level_resume OK")


def check_timeint_coef_serve_packing():
    """Per-member coefficient fields through the serve traced route on a
    real (2,2,1) spatial mesh: each member of a B=2 coef-field batch
    matches its own fp64 flux-form oracle, a B=1 batch reproduces the
    packed member BITWISE (packing invariance), and the run's halo
    traffic lands in the plan-audit ledger (exchange_plan_built /
    plan_cache_hit) exactly like the solution field's."""
    import json
    import os
    import tempfile

    from heat3d_tpu import obs
    from heat3d_tpu.serve.ensemble import EnsembleSolver
    from heat3d_tpu.serve.scenario import Scenario, ScenarioBatch
    from heat3d_tpu.timeint import coeffield

    base = SolverConfig(
        grid=GridConfig.cube(12),
        mesh=MeshConfig(shape=(2, 2, 1)),
        backend="jnp",
    )
    members = [
        Scenario(init="hot-cube", coef_field=("checker", 0, 0.5, 1.5),
                 bc_value=0.25, steps=5),
        Scenario(init="gaussian", coef_field=("lognormal", 7, 0.3, 2.0),
                 bc_value=0.0, steps=5, seed=1),
    ]
    batch = ScenarioBatch(base, members)
    assert batch.has_coef_fields

    tmp = tempfile.mkdtemp(prefix="timeint_serve_")
    led = os.path.join(tmp, "led.jsonl")
    obs.activate(led)
    try:
        es = EnsembleSolver(batch)
        out = es.gather(es.run(es.init_state()))
    finally:
        obs.deactivate()

    for m in range(2):
        a = batch.member_coef_field(m)
        u_ref = golden.make_init(
            members[m].init, base.grid.shape, seed=members[m].seed
        ).astype(np.float64)
        dt = batch.member_dt(m)
        for _ in range(members[m].steps):
            u_ref = coeffield.reference_varcoef_step(
                u_ref, a, dt, base.grid.spacing, periodic=False,
                bc_value=members[m].bc_value,
            )
        rel = np.max(np.abs(out[m] - u_ref)) / max(
            float(np.max(np.abs(u_ref))), 1e-30)
        assert rel < 1e-5, (
            f"coef-field member {m} diverges from its fp64 flux oracle "
            f"(rel {rel:.2e})")

    for m in range(2):
        b1 = ScenarioBatch(base, [members[m]])
        e1 = EnsembleSolver(b1)
        o1 = e1.gather(e1.run(e1.init_state()))[0]
        assert np.array_equal(o1, out[m]), (
            f"coef-field member {m}: B=1 != packed B=2 bitwise")

    with open(led) as fh:
        evs = [json.loads(line) for line in fh if line.strip()]
    plan_evs = [e for e in evs
                if e.get("event") in ("exchange_plan_built",
                                      "plan_cache_hit")]
    assert plan_evs, "no plan-audit events from the coef-field run"
    print("timeint_coef_serve_packing OK")


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "eqn":
        # focused tier-1 entry (tests/test_eqn.py runs it unmarked on a
        # 4-device mesh): the declarative-equation acceptance battery —
        # spec-vs-legacy heat bitwise, family golden/MMS e2e, serve
        # traced-bind with per-member spec coefficients
        n = len(jax.devices())
        assert n >= 4, f"expected >= 4 CPU devices, got {n}"
        check_eqn_heat_spec_vs_legacy_bitwise()
        check_eqn_families_golden_distributed()
        check_eqn_serve_traced_bind()
        print("ALL MULTIDEVICE CHECKS PASSED")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "plan":
        # focused tier-1 entry (tests/test_plan.py runs it unmarked on a
        # 4-device mesh): the persistent-exchange-plan acceptance battery
        n = len(jax.devices())
        assert n >= 4, f"expected >= 4 CPU devices, got {n}"
        check_plan_bitwise_parity()
        check_plan_partitioned_identity()
        check_plan_ensemble_parity()
        print("ALL MULTIDEVICE CHECKS PASSED")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "timeint":
        # focused tier-1 entry (tests/test_timeint.py runs it unmarked on
        # a 4-device mesh with JAX_ENABLE_X64=1): the multi-level /
        # implicit integration battery — leapfrog + CG + varcoef
        # dist==solo bitwise, two-level supervised resume with the
        # level-mismatch skip, coef-field serve packing/oracle/plan-audit
        n = len(jax.devices())
        assert n >= 4, f"expected >= 4 CPU devices, got {n}"
        check_timeint_dist_bitwise()
        check_timeint_supervised_two_level_resume()
        check_timeint_coef_serve_packing()
        print("ALL MULTIDEVICE CHECKS PASSED")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "deep_tb":
        # focused tier-1 entry (test_multidevice.py runs it unmarked on a
        # 4-device mesh; the full 8-device battery stays slow-marked)
        n = len(jax.devices())
        assert n >= 4, f"expected >= 4 CPU devices, got {n}"
        check_deep_tb_tier1()
        check_deep_tb_streamk_interpret()
        print("ALL MULTIDEVICE CHECKS PASSED")
        return
    n = len(jax.devices())
    assert n == 8, f"expected 8 CPU devices, got {n} ({jax.devices()})"
    check_step_matches_single_device()
    check_faces_direct_step_distributed()
    check_faces_direct_superstep_distributed()
    check_overlap_step_distributed()
    check_uneven_decomposition()
    check_device_init_distributed()
    check_time_blocking_distributed()
    check_bf16_distributed()
    check_halo_ghost_identity()
    check_multistep_vs_golden()
    check_dma_halo_ring_interpret()
    check_sharded_checkpoint_roundtrip()
    check_gather_slice_distributed()
    print("ALL MULTIDEVICE CHECKS PASSED")


if __name__ == "__main__":
    main()
    sys.exit(0)
