"""Fused in-kernel RDMA superstep: the ExchangePlan-scheduled variant of
the fused DMA-overlap kernels (the paper's endgame — halo exchange and
stencil sweep in ONE Pallas kernel, with the sends riding the audited
plan schedule).

ops/stencil_dma_fused already fuses transfer and sweep for the ``--halo
dma --overlap`` route, but its two remote copies are a fixed monolithic
protocol: one descriptor per face, outside the ``ExchangePlan``'s
vocabulary. This module keeps that module's sweep/emit bodies VERBATIM
(imported, not copied — the ring schedule is the audited invariant) and
swaps only the transfer protocol: the x-face pushes are split into the
plan's per-sub-block decomposition (``ExchangePlan.face_partition_bounds``
— ``halo_plan=partitioned`` defines the sub-blocks, monolithic is the
degenerate single range), every (direction, sub-block) descriptor issued
at grid step (0, 0) so all sends are in flight before the first interior
plane emits — the in-kernel analogue of the plan's early-bird partitioned
ppermutes, and the CUDA-aware ``MPI_Isend``-per-block pattern of the
partitioned-MPI stencil literature.

Semaphore discipline (the invariant ``heat3d lint --kernel`` certifies):
each (direction, sub-block) copy owns its OWN completion count — flat
``DMA((2 * nparts,))`` semaphore arrays indexed ``dir * nparts + p`` with
static indices, so no two in-flight transfers alias one cell (ANL1003)
and each direction's wait drains exactly its own descriptors. The
neighbor barrier, ring-position arithmetic, Dirichlet read-side
substitution and ghost-landing outputs are unchanged from the template
kernels.

Scope: the 1D x-slab meshes (``fused_rdma_supported`` delegates to the
template gates — nx >= 2 / 4, VMEM-feasible chunking incl. the resident
ghost reserve), temporal blocking k <= 2. Values are certified bitwise
against the unfused plan-driven route on a real 4-device CPU ring in
interpret mode (tests/multidevice_checks.py); off-TPU dispatch runs the
pure-XLA reference contracts below, exactly like the streamk and
fused-DMA routes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from heat3d_tpu.core.stencils import effective_num_taps, flat_taps
from heat3d_tpu.ops.stencil_pallas_direct import _row_block_specs
from heat3d_tpu.ops.stencil_dma_fused import (
    _fused2_kernel,
    _fused2_kernel_single,
    _fused_choose_chunk,
    _fused_kernel,
    _fused_kernel_single,
    fused_dma2_supported,
    fused_dma_supported,
    reference_fused_step_xla,
    reference_fused_superstep_xla,
)

# Own collective classes: make_multistep_fn can compile this route's
# superstep + remainder step alongside the stencil_dma_fused pair in one
# program, and the barrier semaphore is keyed by id (0..2 per-axis halo,
# 3/4 fused-DMA step/superstep).
_COLLECTIVE_ID = 5
_COLLECTIVE_ID_TB2 = 6


def plan_send_bounds(
    plan, local_shape, itemsize: int
) -> Tuple[Tuple[int, int], ...]:
    """The static (start, end) y-ranges the x-face sends ship as — the
    plan's sub-block decomposition (``halo_plan=partitioned``), or the
    degenerate whole-face range (monolithic / no plan). Python ints: the
    kernel unrolls one descriptor per range at trace time."""
    if plan is None:
        return ((0, int(local_shape[1])),)
    return plan.face_partition_bounds(0, local_shape, itemsize)


def fused_rdma_supported(
    local_shape: Tuple[int, int, int],
    mesh_shape: Tuple[int, int, int],
    taps: np.ndarray,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    compute_itemsize: int = 4,
) -> bool:
    """Same scope as the template kernel (1D x-slab ring, nx >= 2,
    VMEM-feasible chunking): the planned schedule changes how the faces
    ship, not what the sweep needs resident."""
    return fused_dma_supported(
        local_shape, mesh_shape, taps,
        in_itemsize, out_itemsize, compute_itemsize,
    )


def fused_rdma2_supported(
    local_shape: Tuple[int, int, int],
    mesh_shape: Tuple[int, int, int],
    taps: np.ndarray,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    compute_itemsize: int = 4,
) -> bool:
    return fused_dma2_supported(
        local_shape, mesh_shape, taps,
        in_itemsize, out_itemsize, compute_itemsize,
    )


def _planned_rdma(
    u_any, glo_ref, ghi_ref, send_sem, recv_sem, *, nx, width,
    axis_name, mesh_axes, axis_size, use_barrier, bounds,
):
    """The plan-scheduled RDMA protocol, signature-compatible with
    stencil_dma_fused._rdma_halo (the kernels' ``rdma_factory`` seam):
    symmetric ring pushes, but each face ships as ``len(bounds)``
    per-sub-block descriptors. Cell layout is FLAT and static —
    hi-neighbor pushes (whose completion is my LOW ghost) own cells
    ``[0, nparts)``, lo-neighbor pushes (my HIGH ghost) own
    ``[nparts, 2*nparts)`` — so every transfer has its own completion
    count and each wait retires exactly its direction's descriptors."""
    my = lax.axis_index(axis_name)
    nparts = len(bounds)

    def neighbor(delta):
        idx = lax.rem(my + delta + axis_size, axis_size)
        if len(mesh_axes) == 1:
            return idx
        return {axis_name: idx}

    def copies(to_hi):
        base = 0 if to_hi else nparts
        dst_ref = glo_ref if to_hi else ghi_ref
        x0 = nx - width if to_hi else 0
        descs = []
        for p, (a, b) in enumerate(bounds):
            if width == 1:  # integer-indexed 2D strip matching the dst
                src = u_any.at[x0, pl.ds(a, b - a)]
                dst = dst_ref.at[pl.ds(a, b - a)]
            else:
                src = u_any.at[pl.ds(x0, width), pl.ds(a, b - a)]
                dst = dst_ref.at[pl.ds(0, width), pl.ds(a, b - a)]
            descs.append(
                pltpu.make_async_remote_copy(
                    src_ref=src,
                    dst_ref=dst,
                    send_sem=send_sem.at[base + p],
                    recv_sem=recv_sem.at[base + p],
                    device_id=neighbor(+1 if to_hi else -1),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            )
        return descs

    def start():
        if use_barrier:
            # same cross-call buffer-reuse guard as the template: nobody
            # pushes into a peer's ghost buffers until that peer entered
            # this kernel (skipped in interpret mode)
            barrier = pltpu.get_barrier_semaphore()
            for delta in (-1, +1):
                pltpu.semaphore_signal(
                    barrier,
                    inc=1,
                    device_id=neighbor(delta),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            pltpu.semaphore_wait(barrier, 2)
        # EVERY sub-block descriptor of both directions is in flight
        # before the sweep's first plane — the early-bird schedule
        for desc in copies(to_hi=True):
            desc.start()
        for desc in copies(to_hi=False):
            desc.start()

    def wait_hi_ghost():
        for desc in copies(to_hi=False):
            desc.wait()

    def wait_lo_ghost():
        for desc in copies(to_hi=True):
            desc.wait()

    return my, start, wait_hi_ghost, wait_lo_ghost


def apply_step_fused_rdma(
    u: jax.Array,
    taps: np.ndarray,
    *,
    plan=None,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """One stencil update of an x-slab shard with the plan-scheduled
    in-kernel RDMA overlapped under the sweep. Must run inside shard_map
    over a mesh whose axis 0 has ``axis_size`` devices (axes 1/2 size 1
    — the fused_rdma route has no 3D shell-patch arm). ``plan`` is the
    ``ExchangePlan`` whose sub-block decomposition the sends ride; None
    (or a monolithic plan) ships whole faces."""
    nx, ny, nz = u.shape
    out_dtype = out_dtype or u.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    by = _fused_choose_chunk(
        u.shape, 1, u.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        effective_num_taps(taps), jnp.dtype(compute_dtype).itemsize,
    )
    if by is None:
        raise ValueError(f"no VMEM-feasible chunking for {u.shape}")
    n_chunks = ny // by
    single = n_chunks == 1
    bounds = plan_send_bounds(plan, u.shape, u.dtype.itemsize)
    nparts = len(bounds)

    # same stream schedule as apply_step_fused_dma: local planes, ghosts
    # as stream positions nx / nx+1, planes 0/1 re-streamed for the wrap
    def x_of(i):
        return jnp.where(
            i <= nx - 1, i, jnp.clip(i - (nx + 2), 0, nx - 1)
        )

    def o_of(i):
        return jnp.where(
            i <= nx, jnp.clip(i - 1, 1, nx - 1), 0
        )

    kernel = functools.partial(
        _fused_kernel if not single else _fused_kernel_single,
        taps_flat=flat,
        nx=nx,
        by=by,
        nz=nz,
        n_chunks=n_chunks,
        axis_name=axis_name,
        mesh_axes=tuple(mesh_axes),
        axis_size=axis_size,
        periodic=periodic,
        bc_value=bc_value,
        compute_dtype=compute_dtype,
        out_dtype=jnp.dtype(out_dtype),
        use_barrier=not interpret,
        rdma_factory=functools.partial(_planned_rdma, bounds=bounds),
    )
    in_specs = [
        pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # RDMA face source
    ]
    operands = (u, u)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u, u)
    out, _glo, _ghi = pl.pallas_call(
        kernel,
        name="heat3d.stencil.fused_rdma",
        grid=(n_chunks, nx + 4),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, by, nz), lambda j, i: (o_of(i), j, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
            jax.ShapeDtypeStruct((ny, nz), u.dtype),  # low ghost landing
            jax.ShapeDtypeStruct((ny, nz), u.dtype),  # high ghost landing
        ),
        scratch_shapes=[
            pltpu.VMEM((3, by + 2, nz + 2), u.dtype),
            pltpu.SemaphoreType.DMA((2 * nparts,)),
            pltpu.SemaphoreType.DMA((2 * nparts,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=_COLLECTIVE_ID,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(flat) * nx * ny * nz,
            bytes_accessed=nx * ny * nz
            * (u.dtype.itemsize + jnp.dtype(out_dtype).itemsize),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
    return out


def apply_superstep_fused_rdma(
    u: jax.Array,
    taps: np.ndarray,
    *,
    plan=None,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """TWO fused updates of an x-slab shard in one HBM sweep with the
    plan-scheduled width-2 RDMA overlapped under phase A — the tb=2
    composition of the fused superstep (k <= 2 is the route's temporal
    blocking ceiling)."""
    nx, ny, nz = u.shape
    out_dtype = out_dtype or u.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    by = _fused_choose_chunk(
        u.shape, 2, u.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        effective_num_taps(taps), jnp.dtype(compute_dtype).itemsize,
    )
    if by is None:
        raise ValueError(f"no VMEM-feasible chunking for {u.shape}")
    n_chunks = ny // by
    single = n_chunks == 1
    bounds = plan_send_bounds(plan, u.shape, u.dtype.itemsize)
    nparts = len(bounds)

    def x_of(i):
        return jnp.where(
            i <= nx - 1, i, jnp.clip(i - (nx + 4), 0, nx - 1)
        )

    def o_of(i):
        return jnp.where(
            i <= nx + 1,
            jnp.clip(i - 2, 2, nx - 1),
            jnp.where(i <= nx + 6, 0, 1),
        )

    kernel = functools.partial(
        _fused2_kernel if not single else _fused2_kernel_single,
        taps_flat=flat,
        nx=nx,
        by=by,
        nz=nz,
        n_chunks=n_chunks,
        axis_name=axis_name,
        mesh_axes=tuple(mesh_axes),
        axis_size=axis_size,
        periodic=periodic,
        bc_value=bc_value,
        compute_dtype=compute_dtype,
        storage_dtype=u.dtype,
        out_dtype=jnp.dtype(out_dtype),
        use_barrier=not interpret,
        rdma_factory=functools.partial(_planned_rdma, bounds=bounds),
    )
    in_specs = [
        pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # RDMA slab source
    ]
    operands = (u, u)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u, u)
    out, _glo, _ghi = pl.pallas_call(
        kernel,
        name="heat3d.stencil.fused_rdma2",
        grid=(n_chunks, nx + 8),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, by, nz), lambda j, i: (o_of(i), j, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
            jax.ShapeDtypeStruct((2, ny, nz), u.dtype),  # low ghost slab
            jax.ShapeDtypeStruct((2, ny, nz), u.dtype),  # high ghost slab
        ),
        scratch_shapes=[
            pltpu.VMEM((3, by + 4, nz + 4), u.dtype),
            pltpu.VMEM((3, by + 2, nz + 2), u.dtype),
            pltpu.SemaphoreType.DMA((2 * nparts,)),
            pltpu.SemaphoreType.DMA((2 * nparts,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=_COLLECTIVE_ID_TB2,
        ),
        cost_estimate=pl.CostEstimate(
            # RAW flops (the streamk convention): mids sweep the
            # one-ring-padded volume
            flops=2 * len(flat)
            * ((nx + 2) * (ny + 2) * (nz + 2) + nx * ny * nz),
            bytes_accessed=nx * ny * nz
            * (u.dtype.itemsize + jnp.dtype(out_dtype).itemsize),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
    return out


def reference_fused_rdma_step_xla(
    u, taps, *, plan=None, **kw
):
    """Pure-XLA reference contract for the off-TPU tiers: the fused RDMA
    step's VALUES are plan-independent (the plan only reschedules how the
    same face bytes ship), so the fused-DMA reference is the oracle."""
    return reference_fused_step_xla(u, taps, **kw)


def reference_fused_rdma_superstep_xla(
    u, taps, *, plan=None, **kw
):
    return reference_fused_superstep_xla(u, taps, **kw)
