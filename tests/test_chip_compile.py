"""Compile the main path's kernels and steps at real size for a v5e.

Nothing runs: the TPU compiler compiles for a described ``v5e:2x2``
topology (the on-chip-measurement guide, section 2), so what the chip's
compiler would refuse (tiling, VMEM, HBM) fails here at no chip time. The
topology is described inside a fixture, never at import: only one process
may load the TPU library, and pytest-xdist workers all import this file.
Off the chip the code's own device probes see the CPU, so each test
steers them itself: ``HEAT3D_DIRECT_FORCE`` selects the Mosaic kernels,
and ``tune.cache.chip_generation`` reads the v5e generation.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from heat3d_tpu.core.config import (
    GridConfig,
    MeshConfig,
    Precision,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.core.stencils import STENCILS, stencil_taps

V5E_KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture
def v5e(monkeypatch, topo):
    import heat3d_tpu.tune.cache as tcache

    monkeypatch.setenv("HEAT3D_DIRECT_FORCE", "1")
    monkeypatch.delenv("HEAT3D_DIRECT_INTERPRET", raising=False)
    monkeypatch.setattr(tcache, "chip_generation", lambda: "tpu-v5-lite")
    assert topo.devices[0].device_kind == V5E_KIND
    return topo


def _compile_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
@pytest.mark.parametrize("tb", [1, 2])
def test_direct_kernel_compiles_at_1024(v5e, kind, storage, tb):
    """The one-chip hot path (cells a-c): direct (tb=1) and direct2
    (tb=2) at 1024^3, on the aligned plane layout."""
    from heat3d_tpu.ops.stencil_pallas_direct import (
        apply_taps_direct,
        apply_taps_direct2,
        direct_layout,
    )

    kernel = apply_taps_direct if tb == 1 else apply_taps_direct2
    taps = stencil_taps(STENCILS[kind], 0.1, 0.1, (1.0, 1.0, 1.0))
    assert direct_layout((1024, 1024, 1024), taps) == "aligned"
    x = jax.ShapeDtypeStruct(
        (1024, 1024, 1024), jnp.dtype(storage),
        sharding=SingleDeviceSharding(v5e.devices[0]),
    )
    text = _compile_text(lambda u: kernel(u, taps, bc_value=0.0), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["7pt", "27pt"])
@pytest.mark.parametrize("tb", [1, 2])
def test_bf16_compute_direct_kernel_compiles_at_1024(v5e, kind, tb):
    """The ``--compute-dtype bf16`` A/B (the bf16 cell's control): bf16
    storage and bf16 tap arithmetic on the aligned layout, whose rolls
    Mosaic takes in 32-bit data only."""
    from heat3d_tpu.ops.stencil_pallas_direct import (
        apply_taps_direct,
        apply_taps_direct2,
    )

    kernel = apply_taps_direct if tb == 1 else apply_taps_direct2
    taps = stencil_taps(STENCILS[kind], 0.1, 0.1, (1.0, 1.0, 1.0))
    x = jax.ShapeDtypeStruct(
        (1024, 1024, 1024), jnp.bfloat16,
        sharding=SingleDeviceSharding(v5e.devices[0]),
    )
    text = _compile_text(
        lambda u: kernel(u, taps, bc_value=0.0, compute_dtype=jnp.bfloat16),
        x,
    )
    assert "tpu_custom_call" in text


def _sharded_step(topo, cfg):
    from heat3d_tpu.parallel.step import make_step_fn, make_superstep_fn
    from heat3d_tpu.parallel.topology import build_mesh

    mesh = build_mesh(cfg.mesh, topo.devices[: cfg.mesh.num_devices])
    build = make_superstep_fn if cfg.time_blocking == 2 else make_step_fn
    fn = build(cfg, mesh)
    x = jax.ShapeDtypeStruct(
        cfg.grid.shape, jnp.dtype(cfg.precision.storage),
        sharding=NamedSharding(mesh, P(*cfg.mesh.axis_names)),
    )
    return fn, x


# the two four-chip cells: the 2x2x2 block run cut to (2,2,1), and the
# 1D slab run as published; the first keeps its case ids
FOUR_CHIP_CELLS = [
    pytest.param(tb, mesh, grid, id=f"{tb}{tag}")
    for mesh, grid, tag in [
        ((2, 2, 1), (2048, 2048, 1024), ""),
        ((4, 1, 1), (1024, 1024, 1024), "-4x1x1"),
    ]
    for tb in (1, 2)
]


@pytest.mark.parametrize("tb,mesh,grid", FOUR_CHIP_CELLS)
def test_block_mesh_step_compiles_at_cell_d(v5e, tb, mesh, grid):
    """The four-chip cells, 1024^3 per chip on (2,2,1) and 256x1024x1024
    per chip on (4,1,1), through the faces-direct kernel step and its
    collectives."""
    from heat3d_tpu.parallel.step import _direct_kernel_fn

    cfg = SolverConfig(
        grid=GridConfig(shape=grid),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=mesh),
        precision=Precision.fp32(),
        backend="auto",
        time_blocking=tb,
    )
    assert _direct_kernel_fn(cfg, tb, multichip=True) is not None
    text = _compile_text(*_sharded_step(v5e, cfg))
    assert "tpu_custom_call" in text
    assert "collective-permute" in text or "collective_permute" in text


def test_direct2_superstep_kernel_keeps_its_name(v5e):
    """The direct2 kernel's ``name=`` names its compiled custom call
    ``%heat3d.stencil.direct2.<n>`` in the (2,2,1) superstep: the
    ``%heat3d.stencil.`` prefix the benchmark's kernel readers match."""
    cfg = SolverConfig(
        grid=GridConfig(shape=(2048, 2048, 1024)),
        stencil=StencilConfig(kind="7pt"),
        mesh=MeshConfig(shape=(2, 2, 1)),
        precision=Precision.fp32(),
        backend="auto",
        time_blocking=2,
    )
    text = _compile_text(*_sharded_step(v5e, cfg))
    calls = re.findall(
        r"(%[\w.-]+) = [^\n]* custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"',
        text,
    )
    assert calls and all(
        re.fullmatch(r"%heat3d\.stencil\.direct2\.\d+", c) for c in calls
    ), calls


def test_build_mesh_orders_the_described_host(v5e):
    """The described 2x2 host's devices carry real v5e coords: (4,1,1)
    comes out with every chain neighbour one ICI hop apart, (2,2,1) in
    id order, as before."""
    from heat3d_tpu.parallel.topology import build_mesh

    devs = v5e.devices[:4]
    chain = list(build_mesh(MeshConfig(shape=(4, 1, 1)), devs).devices.flat)
    assert all(
        sum(abs(p - q) for p, q in zip(a.coords, b.coords)) == 1
        for a, b in zip(chain, chain[1:])
    ), [d.coords for d in chain]
    block = build_mesh(MeshConfig(shape=(2, 2, 1)), devs)
    assert list(block.devices.flat) == list(devs)


def full_volume_copies(text, shape, dtype="f32"):
    """Copies of a whole ``shape`` block of ``dtype`` (HLO's name) in HBM
    in compiled HLO text: ``copy`` and ``copy-start`` whose destination is
    in the default memory space (a layout without ``S(n)``; an ``S(1)``
    destination is a prefetch into on-chip memory)."""
    dims = r"%s\[%s\]" % (dtype, ",".join(map(str, shape)))
    copies = re.findall(r"= %s(\{[^}]*\}) copy\(" % dims, text)
    starts = re.findall(r"= \(%s(\{[^}]*\}), [^\n]* copy-start\(" % dims, text)
    return sum("S(" not in layout for layout in copies + starts)


# n = 9, 50, 51 at tb=2: (odd, rest) = (0, 1), (1, 0), (1, 1)
PAIR_PROGRAMS = ["run-9", "run-50", "run-51", "step", "residual"]


def _mesh_field(topo, cfg):
    from heat3d_tpu.parallel.topology import build_mesh

    mesh = build_mesh(cfg.mesh, topo.devices[: cfg.mesh.num_devices])
    return mesh, jax.ShapeDtypeStruct(
        cfg.grid.shape, jnp.dtype(cfg.precision.storage),
        sharding=NamedSharding(mesh, P(*cfg.mesh.axis_names)),
    )


def _pair_program(topo, cfg, program):
    """A solver program as ``HeatSolver3D`` jits it, lowered for the
    described chips: the pair loop for ``run(u, n)`` or a pair step."""
    from heat3d_tpu.parallel.step import (
        make_pair_multistep_fn,
        make_pair_step_fn,
    )

    mesh, x = _mesh_field(topo, cfg)
    if program.startswith("run-"):
        hot, rest = divmod(int(program[4:]), cfg.time_blocking)
        run = jax.jit(make_pair_multistep_fn(cfg, mesh),
                      donate_argnums=(0, 1), static_argnames=("odd", "rest"))
        return run.lower(x, x, jax.ShapeDtypeStruct((), jnp.int32),
                         odd=hot % 2, rest=rest)
    step = make_pair_step_fn(cfg, mesh, with_residual=program == "residual")
    return jax.jit(step, donate_argnums=(0, 1), keep_unused=True).lower(x, x)


@pytest.mark.parametrize("program", PAIR_PROGRAMS)
def test_solver_programs_copy_no_field_at_1024(v5e, program):
    """The one-chip cells' programs, 1024^3 at tb=2: the fixed-step loop
    at each (odd, rest) and both step programs write every update into
    the buffer the field is not in, so the compiled program holds no
    full-volume copy and no temporary field; the pair comes back
    aliased to the two donated inputs."""
    _copies_no_field(v5e, Precision.fp32(), program, "f32")


def _copies_no_field(topo, precision, program, dtype):
    """``program`` of the one-chip cells at 1024^3, tb=2, in
    ``precision``: a kernel, no full-volume copy of ``dtype`` (HLO's
    name), no temporary field, the pair aliased to its inputs."""
    cfg = SolverConfig(grid=GridConfig.cube(1024), time_blocking=2,
                       backend="auto", precision=precision)
    compiled = _pair_program(topo, cfg, program).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert full_volume_copies(text, (1024,) * 3, dtype) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)" in text


@pytest.mark.parametrize("program", ["run-49", "residual"])
def test_bf16_cell_programs_copy_no_field_at_1024(v5e, program):
    """The bf16 cell's two programs (``monitor50``: ``run(u, 49)``, then
    the residual step), 1024^3 in bf16 storage at tb=2: the direct
    kernels, no full-volume copy, the pair aliased to its inputs."""
    _copies_no_field(v5e, Precision.bf16(), program, "bf16")


def test_single_buffer_loop_copies_the_field(v5e):
    """The control: ``make_multistep_fn``'s one-buffer tail (the odd
    superstep and the remainder step, in loops of at most one trip)
    copies the field, and the count above sees it."""
    from heat3d_tpu.parallel.step import make_multistep_fn

    cfg = SolverConfig(grid=GridConfig.cube(1024), time_blocking=2,
                       backend="auto")
    mesh, x = _mesh_field(v5e, cfg)
    text = (
        jax.jit(make_multistep_fn(cfg, mesh), donate_argnums=0)
        .lower(x, jax.ShapeDtypeStruct((), jnp.int32))
        .compile()
        .as_text()
    )
    assert full_volume_copies(text, (1024,) * 3) >= 1


@pytest.mark.parametrize("program", ["run-51", "residual"])
@pytest.mark.parametrize("mesh,grid", [
    ((2, 2, 1), (2048, 2048, 1024)), ((4, 1, 1), (1024, 1024, 1024)),
], ids=["2x2x1", "4x1x1"])
def test_four_chip_solver_programs_copy_no_field(v5e, mesh, grid, program):
    """The four-chip cells' loop at an odd superstep count with a
    remainder step, and their residual step: no full-volume copy of the
    local block on the faces-direct route."""
    cfg = SolverConfig(grid=GridConfig(shape=grid), mesh=MeshConfig(shape=mesh),
                       time_blocking=2, backend="auto")
    text = _pair_program(v5e, cfg, program).compile().as_text()
    assert "tpu_custom_call" in text
    assert full_volume_copies(text, cfg.local_shape) == 0


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_residual_kernel_compiles_at_1024(v5e, storage):
    """The residual step's kernel, the tb=1 direct kernel with its
    change-residual partials, at 1024^3 on the aligned layout: one (8,
    128) float32 block per output plane and chunk column."""
    from heat3d_tpu.ops.stencil_pallas_direct import apply_taps_direct

    taps = stencil_taps(STENCILS["7pt"], 0.1, 0.1, (1.0, 1.0, 1.0))
    x = jax.ShapeDtypeStruct(
        (1024, 1024, 1024), jnp.dtype(storage),
        sharding=SingleDeviceSharding(v5e.devices[0]),
    )
    fn = lambda u: apply_taps_direct(  # noqa: E731
        u, taps, bc_value=0.0, residual_dtype=jnp.float32
    )
    out, partials = jax.eval_shape(fn, x)
    assert out.shape == x.shape and out.dtype == x.dtype
    assert partials.shape[0] == 1024 and partials.shape[2] == 128
    assert partials.shape[1] % 8 == 0 and partials.dtype == jnp.float32
    assert "tpu_custom_call" in _compile_text(fn, x)


def test_residual_step_reads_the_field_once_at_1024(v5e):
    """The one-chip residual step at 1024^3 sums the kernel's partials:
    no op but the kernel reads the incoming field (the reduce over both
    fields is gone), and the kernel keeps its ``%heat3d.stencil.direct``
    name."""
    cfg = SolverConfig(grid=GridConfig.cube(1024), time_blocking=2,
                       backend="auto")
    text = _pair_program(v5e, cfg, "residual").compile().as_text()
    entry = text[text.index("\nENTRY"):]
    field = re.search(
        r"(%[\w.-]+) = f32\[1024,1024,1024\]\S* parameter\(0\)", entry
    ).group(1)
    readers = [
        line for line in entry.splitlines()[1:]
        if re.search(re.escape(field) + r"(?![\w.])", line)
        and " parameter(0)" not in line
    ]
    assert readers and all(
        "tpu_custom_call" in r or r.lstrip().startswith("ROOT") for r in readers
    ), readers
    assert re.search(r"%heat3d\.stencil\.direct\.\d+ = ", entry)
