"""Distributed-path tests.

Executable tier: the full shard_map machinery on a (1,1,1) mesh must equal
the single-device step bitwise (the '-np 1 vs -np P' check, SURVEY.md §4).
Compile-only tier: multi-chip meshes lower via AbstractMesh with the
expected collectives present — the single-chip substitute for a pod
(SURVEY.md §7.0: no multi-device simulation exists on this box).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.core import golden
from heat3d_tpu.core.stencils import STENCILS, stencil_taps
from heat3d_tpu.ops.stencil_jnp import step_single_device
from heat3d_tpu.parallel.halo import exchange_halo
from heat3d_tpu.parallel.step import (
    make_converge_fn,
    make_multistep_fn,
    make_step_fn,
    make_superstep_fn,
)
from heat3d_tpu.parallel.topology import abstract_mesh, build_mesh, lower_for_mesh
from jax import shard_map
from jax.sharding import PartitionSpec as P


def solo_cfg(n=8, kind="7pt", bc=BoundaryCondition.DIRICHLET, bc_value=0.0,
             precision=Precision.fp32()):
    return SolverConfig(
        grid=GridConfig.cube(n),
        stencil=StencilConfig(kind=kind, bc=bc, bc_value=bc_value),
        mesh=MeshConfig(shape=(1, 1, 1)),
        precision=precision,
        backend="jnp",
    )


# ---- executable on this box ------------------------------------------------


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
@pytest.mark.parametrize(
    "bc,bc_value",
    [
        (BoundaryCondition.DIRICHLET, 0.0),
        (BoundaryCondition.DIRICHLET, 2.0),
        (BoundaryCondition.PERIODIC, 0.0),
    ],
)
def test_sharded_equals_single_device(kind, bc, bc_value):
    cfg = solo_cfg(kind=kind, bc=bc, bc_value=bc_value)
    mesh = build_mesh(cfg.mesh)
    step = make_step_fn(cfg, mesh)
    u = jnp.asarray(golden.random_init((8, 8, 8), seed=4))
    got = jax.jit(step)(u)
    taps = stencil_taps(STENCILS[kind], 1.0, cfg.grid.effective_dt(), (1.0,) * 3)
    want = step_single_device(u, taps, bc, bc_value)
    # Same math and precision; XLA may fuse the two programs differently
    # (observed: 1-ulp fma differences), so compare at ulp scale, not bitwise.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_halo_111_mesh_equals_pad():
    # On a (1,1,1) mesh the halo exchange must reproduce pad_local exactly:
    # periodic wrap = self-exchange, Dirichlet = bc fill.
    from heat3d_tpu.ops.stencil_jnp import pad_local

    u = jnp.asarray(golden.random_init((5, 6, 7), seed=9))
    for bc, bcv in [
        (BoundaryCondition.PERIODIC, 0.0),
        (BoundaryCondition.DIRICHLET, 0.0),
        (BoundaryCondition.DIRICHLET, 3.5),
    ]:
        cfg = MeshConfig(shape=(1, 1, 1))
        mesh = build_mesh(cfg)
        f = shard_map(
            lambda x: exchange_halo(x, cfg, bc, bcv),
            mesh=mesh,
            in_specs=P("x", "y", "z"),
            out_specs=P("x", "y", "z"),
        )
        got = f(u)
        want = pad_local(u, bc, bcv)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
@pytest.mark.parametrize(
    "bc,bc_value",
    [
        (BoundaryCondition.DIRICHLET, 0.0),
        (BoundaryCondition.DIRICHLET, 2.0),
        (BoundaryCondition.PERIODIC, 0.0),
    ],
)
def test_overlap_step_equals_unsplit(kind, bc, bc_value):
    """The interior/boundary-split overlap step computes cell-for-cell the
    same expression as the unsplit step — results must agree to ulp."""
    import dataclasses

    cfg = solo_cfg(kind=kind, bc=bc, bc_value=bc_value)
    cfg_ov = dataclasses.replace(cfg, overlap=True)
    mesh = build_mesh(cfg.mesh)
    u = jnp.asarray(golden.random_init((8, 8, 8), seed=21))
    got = jax.jit(make_step_fn(cfg_ov, mesh))(u)
    want = jax.jit(make_step_fn(cfg, mesh))(u)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_overlap_rejects_tiny_local_blocks():
    import dataclasses

    cfg = dataclasses.replace(solo_cfg(n=2), overlap=True)
    with pytest.raises(ValueError, match="overlap"):
        make_step_fn(cfg, build_mesh(cfg.mesh))


def test_overlap_multichip_lowers_with_collectives():
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
        overlap=True,
    )
    am = abstract_mesh(cfg.mesh)
    step = make_step_fn(cfg, am, with_residual=True)
    lowered = lower_for_mesh(
        step, cfg.mesh, (cfg.grid.shape, jnp.float32, P("x", "y", "z"))
    )
    txt = lowered.as_text()
    assert "collective-permute" in txt or "collective_permute" in txt


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
@pytest.mark.parametrize(
    "bc,bc_value",
    [
        (BoundaryCondition.DIRICHLET, 0.0),
        (BoundaryCondition.DIRICHLET, 2.0),
        (BoundaryCondition.PERIODIC, 0.0),
    ],
)
@pytest.mark.parametrize("steps", [1, 2, 5])
@pytest.mark.parametrize("k", [2, 3, 5])
def test_time_blocking_equals_single_steps(kind, bc, bc_value, steps, k):
    """The temporally-blocked loop (k updates per width-k exchange) must
    reproduce the plain per-step loop for any remainder."""
    import dataclasses

    cfg = solo_cfg(kind=kind, bc=bc, bc_value=bc_value)
    cfgk = dataclasses.replace(cfg, time_blocking=k)
    mesh = build_mesh(cfg.mesh)
    u = jnp.asarray(golden.random_init((8, 8, 8), seed=33))
    got = jax.jit(make_multistep_fn(cfgk, mesh))(u, jnp.int32(steps))
    want = jax.jit(make_multistep_fn(cfg, mesh))(u, jnp.int32(steps))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_time_blocking_rejects_overlap():
    import dataclasses

    base = dataclasses.replace(solo_cfg(), time_blocking=2)
    mesh = build_mesh(base.mesh)
    # halo='dma' composes with time blocking (width-k slab exchange); only
    # the overlap split remains mutually exclusive with the superstep
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_superstep_fn(dataclasses.replace(base, overlap=True), mesh)


def test_overlap_tb_out_of_scope_still_errors():
    """overlap+time_blocking keeps the mutual-exclusion config error on a
    block mesh too."""
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
        time_blocking=2,
        overlap=True,
    )
    am = abstract_mesh(cfg.mesh)
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_superstep_fn(cfg, am)


def test_overlap_dma_out_of_scope_still_errors():
    """overlap with halo='dma' is refused when the config is built: the
    side-effecting DMA exchange kernels cannot overlap with compute."""
    with pytest.raises(ValueError, match="cannot overlap with compute"):
        SolverConfig(
            grid=GridConfig.cube(16),
            stencil=StencilConfig(kind="27pt"),
            mesh=MeshConfig(shape=(2, 2, 2)),
            backend="jnp",
            halo="dma",
            overlap=True,
        )


@pytest.mark.parametrize(
    "n,k,ok",
    [
        (2, 2, False),  # below the 3-cell interior floor
        (3, 2, True),
        (3, 3, True),
        (3, 4, False),  # k ghost layers don't fit
        (4, 4, True),
        (4, 5, False),
    ],
)
def test_deep_tb_local_extent_validation(n, k, ok):
    """The superstep needs local extents >= max(3, k): k ghost layers
    plus a genuine interior for the shrinking recompute rings."""
    import dataclasses

    cfg = dataclasses.replace(solo_cfg(n=n), time_blocking=k)
    mesh = build_mesh(cfg.mesh)
    if ok:
        make_superstep_fn(cfg, mesh)  # builds without raising
    else:
        with pytest.raises(ValueError, match="needs local extents"):
            make_superstep_fn(cfg, mesh)


def test_pairwise_rejects_deep_tb():
    """halo_order='pairwise' stays excluded for every tb > 1 — the deep
    supersteps' shrinking rings read edge/corner ghosts only axis-ordered
    exchange fills (config validation, shared with the tuner's pruning)."""
    for k in (2, 3, 4):
        with pytest.raises(ValueError, match="pairwise"):
            SolverConfig(
                grid=GridConfig.cube(8),
                mesh=MeshConfig(shape=(1, 1, 1)),
                halo_order="pairwise",
                time_blocking=k,
            )


def test_superstep_cell_updates_and_redundant_frac():
    """The trapezoid cost model: raw counts the shrinking-ring recompute,
    effective the k useful sweeps, and the frac is their honest gap."""
    import dataclasses

    from heat3d_tpu.parallel.step import (
        redundant_flops_frac,
        superstep_cell_updates,
    )

    cfg1 = solo_cfg(n=8)
    raw, eff = superstep_cell_updates(cfg1)
    assert raw == eff == 512 and redundant_flops_frac(cfg1) == 0.0
    cfg3 = dataclasses.replace(cfg1, time_blocking=3)
    raw, eff = superstep_cell_updates(cfg3)
    # applications over 12^3, 10^3, 8^3 vs 3 useful 8^3 sweeps
    assert raw == 12**3 + 10**3 + 8**3
    assert eff == 3 * 8**3
    assert redundant_flops_frac(cfg3) == pytest.approx(1 - eff / raw)
    # deeper k, larger frac; bigger blocks, smaller frac
    cfg4 = dataclasses.replace(cfg1, time_blocking=4)
    assert redundant_flops_frac(cfg4) > redundant_flops_frac(cfg3)
    big = dataclasses.replace(solo_cfg(n=64), time_blocking=4)
    assert redundant_flops_frac(big) < redundant_flops_frac(cfg4)


def test_residual_psum_replicated():
    cfg = solo_cfg()
    mesh = build_mesh(cfg.mesh)
    step = make_step_fn(cfg, mesh, with_residual=True)
    u = jnp.asarray(golden.gaussian_init((8, 8, 8)))
    u2, r2 = jax.jit(step)(u)
    want = float(jnp.sum((u2.astype(jnp.float32) - u) ** 2))
    assert float(r2) == pytest.approx(want, rel=1e-6)


def test_convergence_residual_decreases():
    cfg = solo_cfg()
    mesh = build_mesh(cfg.mesh)
    conv = jax.jit(make_converge_fn(cfg, mesh))
    u = jnp.asarray(golden.gaussian_init((8, 8, 8)))
    u1, s1, r1 = conv(u, jnp.int32(3), jnp.float32(0.0))
    u2, s2, r2 = conv(u1, jnp.int32(3), jnp.float32(0.0))
    assert int(s1) == 3 and int(s2) == 3
    assert float(r2) < float(r1)
    # generous tol converges immediately-ish
    _, s3, _ = conv(u2, jnp.int32(50), jnp.float32(1e3))
    assert int(s3) <= 1


def test_multistep_traced_step_count():
    cfg = solo_cfg()
    mesh = build_mesh(cfg.mesh)
    ms = jax.jit(make_multistep_fn(cfg, mesh))
    step = jax.jit(make_step_fn(cfg, mesh))
    u = jnp.asarray(golden.random_init((8, 8, 8), seed=11))
    got = ms(u, jnp.int32(3))
    want = step(step(step(u)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


# ---- compile-only: multi-chip meshes (SURVEY.md §4 distributed tier) -------


@pytest.mark.parametrize(
    "mesh_shape,kind",
    [
        ((8, 1, 1), "7pt"),   # config 2: 1D slab v5p-8
        ((2, 2, 2), "7pt"),   # config 3: 3D block v5p-8
        ((4, 4, 4), "27pt"),  # config 4: v5p-64
    ],
)
def test_multichip_step_lowers_with_collectives(mesh_shape, kind):
    n = 16 if max(mesh_shape) <= 4 else 32
    cfg = SolverConfig(
        grid=GridConfig.cube(max(n, max(mesh_shape) * 2)),
        stencil=StencilConfig(kind=kind),
        mesh=MeshConfig(shape=mesh_shape),
        backend="jnp",
    )
    am = abstract_mesh(cfg.mesh)
    step = make_step_fn(cfg, am, with_residual=True)
    lowered = lower_for_mesh(
        step, cfg.mesh,
        (cfg.grid.shape, jnp.float32, P("x", "y", "z")),
    )
    txt = lowered.as_text()
    assert "collective-permute" in txt or "collective_permute" in txt
    assert "all-reduce" in txt or "all_reduce" in txt  # the residual psum


def test_bf16_strong_scale_config_lowers():
    # config 5: bf16 stencil + fp32 residual on a 128-chip mesh
    cfg = SolverConfig(
        grid=GridConfig.cube(256),
        mesh=MeshConfig(shape=(8, 4, 4)),
        precision=Precision.bf16(),
        backend="jnp",
    )
    am = abstract_mesh(cfg.mesh)
    step = make_step_fn(cfg, am, with_residual=True)
    lowered = lower_for_mesh(
        step, cfg.mesh, (cfg.grid.shape, jnp.bfloat16, P("x", "y", "z"))
    )
    txt = lowered.as_text()
    assert "bf16" in txt
    assert "f32" in txt  # fp32 residual accumulation survives


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
def test_dma_halo_step_lowers_for_multichip_tpu(kind):
    """The Pallas RDMA halo path (halo='dma') composes with the full step
    and lowers to Mosaic (tpu_custom_call) for a (2,2,2) mesh — the
    compile-only tier for the CUDA-aware-analogue transport."""
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        stencil=StencilConfig(kind=kind),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
        halo="dma",
    )
    am = abstract_mesh(cfg.mesh)
    step = make_step_fn(cfg, am, with_residual=True)
    lowered = lower_for_mesh(
        step, cfg.mesh, (cfg.grid.shape, jnp.float32, P("x", "y", "z"))
    )
    txt = lowered.as_text()
    assert "tpu_custom_call" in txt  # the Mosaic DMA kernels
    assert "all-reduce" in txt or "all_reduce" in txt  # residual psum


@pytest.mark.parametrize("width", [2, 3])
def test_dma_halo_superstep_lowers_for_multichip_tpu(width):
    """Temporal blocking over the RDMA transport: the width-k slab exchange
    (ops/halo_pallas.py) composes with the k-update superstep and lowers to
    Mosaic for a (2,2,2) mesh. Execution parity for the width-k DMA kernels
    is covered per-axis on the 8-device CPU ring (multidevice_checks) since
    interpret mode cannot discharge multi-axis remote DMA (jax 0.9)."""
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        stencil=StencilConfig(kind="27pt"),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
        halo="dma",
        time_blocking=width,
    )
    am = abstract_mesh(cfg.mesh)
    step = make_superstep_fn(cfg, am)
    lowered = lower_for_mesh(
        step, cfg.mesh, (cfg.grid.shape, jnp.float32, P("x", "y", "z"))
    )
    txt = lowered.as_text()
    assert "tpu_custom_call" in txt  # the Mosaic DMA kernels


_D, _P = BoundaryCondition.DIRICHLET, BoundaryCondition.PERIODIC

# (kind, mesh, [(bc, bc_value, time_blocking), ...]): the (2,2,2) block
# mesh at both BCs and depths, and the (8,1,1) x-slab mesh at local
# extent 4 along x
FACES_DIRECT_LOWERING = [
    pytest.param(
        kind, (2, 2, 2),
        [(bc, 0.5, tb) for bc in (_D, _P) for tb in (1, 2)], id=kind,
    )
    for kind in ("7pt", "27pt")
] + [
    pytest.param(kind, (8, 1, 1), cases, id=f"{kind}-8x1x1-{tag}")
    for kind in ("7pt", "27pt")
    for tag, cases in [
        ("dirichlet-tb1", [(_D, 1.5, 1)]),
        ("periodic-tb1", [(_P, 0.0, 1)]),
        ("tb2", [(_D, 0.5, 2)]),
    ]
]


@pytest.mark.parametrize("kind,mesh,cases", FACES_DIRECT_LOWERING)
def test_faces_direct_step_lowers_for_multichip_tpu(
    kind, mesh, cases, monkeypatch
):
    """The multi-chip faces-direct step and tb=2 superstep — Mosaic direct
    kernels + faces-only ppermute exchange + shell patches — lower for a
    TPU mesh (HEAT3D_DIRECT_FORCE selects the real kernels off-hardware;
    pallas->Mosaic lowering runs host-side, so block-spec violations
    surface here, not on the chip)."""
    monkeypatch.setenv("HEAT3D_DIRECT_FORCE", "1")
    from heat3d_tpu.parallel.step import _direct_kernel_fn

    for bc, bc_value, tb in cases:
        cfg = SolverConfig(
            grid=GridConfig.cube(32),
            stencil=StencilConfig(kind=kind, bc=bc, bc_value=bc_value),
            mesh=MeshConfig(shape=mesh),
            backend="auto",
            time_blocking=tb,
        )
        assert _direct_kernel_fn(cfg, tb, multichip=True) is not None
        am = abstract_mesh(cfg.mesh)
        step = (
            make_superstep_fn(cfg, am) if tb == 2
            else make_step_fn(cfg, am, with_residual=True)
        )
        assert step.route["kernel"] == "direct"
        txt = lower_for_mesh(
            step, cfg.mesh, (cfg.grid.shape, jnp.float32, P("x", "y", "z"))
        ).as_text()
        assert "tpu_custom_call" in txt  # Mosaic direct kernel
        assert "collective_permute" in txt  # faces exchange


def test_faces_direct_step_materializes_no_padded_volume(monkeypatch):
    """The architectural property, checked mechanically in the lowered HLO:
    the exchange path concatenates a full (n+2)^3 padded copy of every
    shard per step; the faces-direct path's largest concatenate is a
    3-thick boundary slab. (32^3 over (2,2,2): local 16^3, padded 18^3.)"""
    import re

    def concat_shapes(cfg):
        am = abstract_mesh(cfg.mesh)
        txt = lower_for_mesh(
            make_step_fn(cfg, am), cfg.mesh,
            (cfg.grid.shape, jnp.float32, P("x", "y", "z")),
        ).as_text()
        return {
            tuple(map(int, m))
            for m in re.findall(
                r"stablehlo\.concatenate.*?->\s*tensor<(\d+)x(\d+)x(\d+)xf32>",
                txt,
            )
        }

    monkeypatch.setenv("HEAT3D_DIRECT_FORCE", "1")
    cfg = SolverConfig(
        grid=GridConfig.cube(32), stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(2, 2, 2)), backend="auto",
    )
    direct_shapes = concat_shapes(cfg)
    assert all(min(s) <= 3 for s in direct_shapes), direct_shapes

    import dataclasses

    monkeypatch.setenv("HEAT3D_NO_DIRECT", "1")
    exchange_shapes = concat_shapes(
        dataclasses.replace(cfg, backend="jnp")
    )
    assert (18, 18, 18) in exchange_shapes, exchange_shapes


def test_unknown_halo_transport_rejected():
    with pytest.raises(ValueError, match="halo transport"):
        SolverConfig(grid=GridConfig.cube(8), halo="nccl")


def test_multistep_loop_is_device_side():
    cfg = SolverConfig(
        grid=GridConfig.cube(16),
        mesh=MeshConfig(shape=(2, 2, 2)),
        backend="jnp",
    )
    am = abstract_mesh(cfg.mesh)
    ms = make_multistep_fn(cfg, am)
    lowered = lower_for_mesh(
        ms, cfg.mesh,
        (cfg.grid.shape, jnp.float32, P("x", "y", "z")),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    assert "while" in lowered.as_text()


@pytest.mark.skipif(
    jax.devices()[0].platform != "tpu", reason="needs the TPU compiler"
)
def test_multistep_pair_loop_compiles_copy_free():
    """Regression for the round-2 profile finding: a single-buffer while
    carry makes XLA clone the full volume every iteration (the stencil
    custom-call cannot write into the buffer it reads; measured 38-49% of
    step time). The ping-pong pair carry (_pingpong_loop) must compile to
    a main loop body of exactly two stencil custom-calls and ZERO
    full-volume copies; only the bounded trailing-remainder loops may
    keep one."""
    import re

    n = 128
    cfg = SolverConfig(grid=GridConfig.cube(n), mesh=MeshConfig(shape=(1, 1, 1)))
    mesh = build_mesh(cfg.mesh)
    run = make_multistep_fn(cfg, mesh)
    u = jnp.ones((n, n, n), jnp.float32)
    txt = (
        jax.jit(run, donate_argnums=0)
        .lower(u, jnp.int32(20))
        .compile()
        .as_text()
    )
    cur = None
    copies: dict = {}
    calls: dict = {}
    for ln in txt.splitlines():
        if ln.rstrip().endswith("{"):
            cur = ln.split()[0]
        if re.search(r"= f32\[%d,%d,%d\]\S* copy\(" % (n, n, n), ln):
            copies[cur] = copies.get(cur, 0) + 1
        if "custom-call" in ln:
            calls[cur] = calls.get(cur, 0) + 1
    pair_bodies = [c for c, k in calls.items() if k == 2]
    assert pair_bodies, f"no two-call pair-loop body found in: {calls}"
    for c in pair_bodies:
        assert copies.get(c, 0) == 0, (
            f"full-volume copy reappeared in pair-loop body {c}: {copies}"
        )


@pytest.mark.skipif(
    jax.devices()[0].platform != "tpu", reason="needs the TPU compiler"
)
def test_solver_programs_dispatched_copy_no_field():
    """Every program ``HeatSolver3D`` dispatches for ``run(u, 9)``,
    ``run(u, 25)``, ``run(u, 51)`` (tb=2: an even and an odd superstep
    count, each with a remainder step), ``step_with_residual`` and
    ``step`` compiles with no full-volume copy of the field: the solver
    keeps the ping-pong pair across dispatches, so no program copies the
    field back into the buffer it came in. The programs are recorded as
    the solver calls them, by the shapes and static arguments of each
    call, and compiled again from those."""
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from tests.test_chip_compile import full_volume_copies

    n = 512
    cfg = SolverConfig(grid=GridConfig.cube(n), time_blocking=2)
    solver = HeatSolver3D(cfg)
    dispatched = []

    def recorded(program):
        def call(*args, **static):
            avals = [
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                if isinstance(a, jax.Array) else a
                for a in args
            ]
            dispatched.append((program, avals, static))
            return program(*args, **static)

        return call

    solver._multistep_cache = recorded(solver._multistep)
    solver._step = recorded(solver._step)
    solver._step_res = recorded(solver._step_res)
    u = solver.init_state("hot-cube")
    for steps in (9, 25, 51):
        u = solver.run(u, steps)
    u, _ = solver.step_with_residual(u)
    jax.block_until_ready(solver.step(u))
    assert len(dispatched) == 5
    for program, avals, static in dispatched:
        text = program.lower(*avals, **static).compile().as_text()
        assert "tpu_custom_call" in text
        assert full_volume_copies(text, (n, n, n)) == 0, (
            text.splitlines()[0][:80], static
        )
