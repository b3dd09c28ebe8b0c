"""Pallas RDMA halo exchange — the CUDA-aware/GPUDirect analogue, v2 path.

Reference parity (SURVEY.md §2 C2/C6, §5 "Distributed communication
backend"): the reference's defining feature is CUDA-aware MPI — device
pointers handed straight to MPI_Isend/Irecv so halo faces move NIC<->GPU
with no host staging. The TPU-native moral equivalent is kernel-initiated
inter-chip DMA: ``pltpu.make_async_remote_copy`` pushes my boundary face
slab over ICI directly into the neighbor chip's ghost buffer, synchronized
by DMA semaphores (SURVEY.md §7.1 item 7; the v1 path compiles
``lax.ppermute`` to the same ICI transfers but through XLA's collective
machinery).

Exchange structure mirrors parallel.halo: one kernel per mesh axis,
axis-ordered so edge/corner ghosts propagate (27-point stencil support),
width-k slabs so temporal blocking composes (k ghost rings per exchange
— the deep-tb supersteps at k = 3..4 ride this same slab path, feeding
either the jnp ring recompute or the fused k-sweep streamk kernel;
interpret-certified at widths 1..4 on the 1D ring,
tests/multidevice_checks.py).
Faces are staged axis-leading — shape (k, A, B) with the two in-plane dims
as the (sublane, lane) pair — the device-side analogue of the reference's
pack kernels; staging is what keeps a width-k z-face from degenerating into
a (nx, ny, k) buffer whose k-element minor dim would tile-pad to 128 lanes.
Each kernel sends my low slab to the low neighbor's high-ghost buffer and
my high slab to the high neighbor's low-ghost buffer, then waits for the
symmetric receives. Non-periodic domain edges overwrite the ghost with the
boundary value after the (torus-symmetric) transfers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from heat3d_tpu.core.config import BoundaryCondition, MeshConfig
from heat3d_tpu.obs.trace import named_phase


def _exchange_body(
    src_lo,
    src_hi,
    lo_ref,
    hi_ref,
    send_sem,
    recv_sem,
    *,
    axis_name: str,
    mesh_axes,
    size: int,
    periodic: bool,
    bc_value: float,
    use_barrier: bool,
):
    """Shared ring-exchange body: push ``src_hi`` to the high neighbor's
    low-ghost buffer and ``src_lo`` to the low neighbor's high-ghost buffer,
    then wait for the symmetric receives. Sources stay in ANY/HBM — the DMA
    descriptors read them directly (strided faces included).

    Every device exchanges ring-wise in both directions, including the
    domain-edge wrap (the ICI torus has those links anyway); non-periodic
    edge ghosts are overwritten with the BC value afterwards. Keeping the
    transfer pattern fully symmetric avoids conditional DMAs, which both
    Mosaic's collective matching and interpret mode handle poorly."""
    my = lax.axis_index(axis_name)

    def neighbor(delta):
        # Dict form of a MESH device id: only the communication axis moves.
        # (Scalar form on 1-axis meshes — interpret mode's discharge rule
        # only handles that shape.)
        idx = lax.rem(my + delta + size, size)
        if len(mesh_axes) == 1:
            return idx
        return {axis_name: idx}

    # Neighbor barrier: nobody starts pushing into a peer's ghost buffers
    # until that peer has entered this kernel (guards against cross-call
    # buffer reuse races). Skipped in interpret mode, whose emulation is
    # synchronous and lacks barrier-semaphore support.
    if use_barrier:
        barrier = pltpu.get_barrier_semaphore()
        for delta in (-1, +1):
            pltpu.semaphore_signal(
                barrier,
                inc=1,
                device_id=neighbor(delta),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, 2)

    rdma_hi = pltpu.make_async_remote_copy(  # my high face -> hi nb's lo ghost
        src_ref=src_hi,
        dst_ref=lo_ref,
        send_sem=send_sem.at[0],
        recv_sem=recv_sem.at[0],
        device_id=neighbor(+1),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma_lo = pltpu.make_async_remote_copy(  # my low face -> lo nb's hi ghost
        src_ref=src_lo,
        dst_ref=hi_ref,
        send_sem=send_sem.at[1],
        recv_sem=recv_sem.at[1],
        device_id=neighbor(-1),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma_hi.start()
    rdma_lo.start()
    rdma_hi.wait()  # my send_sem[0] + my recv_sem[0] (lo nb's push into lo_ref)
    rdma_lo.wait()

    if not periodic:

        @pl.when(my == 0)
        def _fill_lo():
            lo_ref[...] = jnp.full(lo_ref.shape, bc_value, lo_ref.dtype)

        @pl.when(my == size - 1)
        def _fill_hi():
            hi_ref[...] = jnp.full(hi_ref.shape, bc_value, hi_ref.dtype)


def _slab_exchange_kernel(lo_face, hi_face, lo_ref, hi_ref, send_sem,
                          recv_sem, **kw):
    """Width-k path: exchange pre-staged axis-leading (k, A, B) slabs."""
    _exchange_body(
        lo_face, hi_face, lo_ref, hi_ref, send_sem, recv_sem, **kw
    )


def _face_exchange_kernel(u_ref, lo_ref, hi_ref, send_sem, recv_sem, *,
                          axis: int, **kw):
    """Width-1 fast path: DMA single ghost faces STRAIGHT out of the
    ANY/HBM-resident ``u_ref`` — no pack staging at all (the closest
    analogue of CUDA-aware MPI's zero-staging device-pointer sends; a TPU
    DMA descriptor handles the strided face natively). Faces are
    integer-indexed to 2D (A, B) refs so the ghost buffers tile VMEM as
    (8, 128) planes with no size-1 dim in the tiled trailing pair."""
    n = u_ref.shape[axis]
    idx_lo = tuple(0 if a == axis else slice(None) for a in range(3))
    idx_hi = tuple(n - 1 if a == axis else slice(None) for a in range(3))
    _exchange_body(
        u_ref.at[idx_lo], u_ref.at[idx_hi], lo_ref, hi_ref, send_sem,
        recv_sem, **kw,
    )


def _exchange_axis_dma_width1(
    u, axis, axis_name, axis_size, mesh_axes, periodic, bc_value, interpret
):
    plane_shape = tuple(s for a, s in enumerate(u.shape) if a != axis)
    slab_shape = tuple(1 if a == axis else s for a, s in enumerate(u.shape))
    kernel = functools.partial(
        _face_exchange_kernel,
        axis=axis,
        axis_name=axis_name,
        mesh_axes=tuple(mesh_axes),
        size=axis_size,
        periodic=periodic,
        bc_value=bc_value,
        use_barrier=not interpret,
    )
    plane_elems = plane_shape[0] * plane_shape[1]
    ghost_lo, ghost_hi = pl.pallas_call(
        kernel,
        name=f"heat3d.halo.{axis_name}.dma",
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(plane_shape, u.dtype),
            jax.ShapeDtypeStruct(plane_shape, u.dtype),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=axis,
        ),
        # pure data movement: two faces read + two ghost planes written
        # (per-chip view; the remote write lands in the neighbor's count).
        # Recorded so the exchange shows up honestly in cost_analysis
        # joins — the vmem lint requires every kernel to carry one.
        cost_estimate=pl.CostEstimate(
            flops=0,
            bytes_accessed=4 * plane_elems * u.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(u)
    return lax.concatenate(
        [ghost_lo.reshape(slab_shape), u, ghost_hi.reshape(slab_shape)],
        dimension=axis,
    )


def _to_axis_leading(face: jax.Array, axis: int) -> jax.Array:
    """Move the exchange axis to the front: (.., k at axis, ..) -> (k, A, B).
    The device-side pack step (reference parity: the CUDA pack kernels that
    feed MPI contiguous buffers — SURVEY.md §3.2)."""
    if axis == 0:
        return face
    perm = (axis,) + tuple(a for a in range(3) if a != axis)
    return jnp.transpose(face, perm)


def _from_axis_leading(slab: jax.Array, axis: int) -> jax.Array:
    if axis == 0:
        return slab
    inv = [0, 0, 0]
    perm = (axis,) + tuple(a for a in range(3) if a != axis)
    for i, p in enumerate(perm):
        inv[p] = i
    return jnp.transpose(slab, inv)


def exchange_axis_dma(
    u: jax.Array,
    axis: int,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool,
    bc_value: float = 0.0,
    width: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """DMA-backed analogue of parallel.halo.exchange_axis: grow ``u`` by
    ``width`` ghost layers along ``axis``, filled from mesh neighbors over
    ICI. Must run inside shard_map."""
    n = u.shape[axis]
    if width > n:
        raise ValueError(f"halo width {width} > local extent {n} on axis {axis}")
    if axis_size == 1:
        # Degenerate ring: no remote party. Same semantics as the ppermute
        # path's special cases.
        lo_face = lax.slice_in_dim(u, 0, width, axis=axis)
        hi_face = lax.slice_in_dim(u, n - width, n, axis=axis)
        if periodic:
            ghost_lo, ghost_hi = hi_face, lo_face
        else:
            ghost_lo = jnp.full_like(lo_face, bc_value)
            ghost_hi = jnp.full_like(hi_face, bc_value)
        return lax.concatenate([ghost_lo, u, ghost_hi], dimension=axis)

    # per-axis comm scope (halo.<axis>.dma): both directions are fused
    # inside one DMA kernel here, so the axis is the finest HONEST
    # attribution unit on this transport — unlike the ppermute path's
    # per-direction scopes (normalize_phase folds both spellings into
    # halo_exchange for the coarse joins)
    if width == 1:
        with named_phase(f"halo.{axis_name}.dma"):
            # zero-staging fast path: faces DMA'd straight out of u
            return _exchange_axis_dma_width1(
                u, axis, axis_name, axis_size, mesh_axes, periodic,
                bc_value, interpret,
            )

    with named_phase(f"halo.{axis_name}.dma"):
        return _exchange_axis_dma_slab(
            u, axis, axis_name, axis_size, mesh_axes, periodic, bc_value,
            width, interpret,
        )


def _exchange_axis_dma_slab(
    u: jax.Array,
    axis: int,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool,
    bc_value: float,
    width: int,
    interpret: bool,
) -> jax.Array:
    """Width-k slab exchange body (split out of ``exchange_axis_dma`` so
    the per-axis comm scope wraps it cleanly)."""
    n = u.shape[axis]
    lo_face = _to_axis_leading(lax.slice_in_dim(u, 0, width, axis=axis), axis)
    hi_face = _to_axis_leading(
        lax.slice_in_dim(u, n - width, n, axis=axis), axis
    )
    kernel = functools.partial(
        _slab_exchange_kernel,
        axis_name=axis_name,
        mesh_axes=tuple(mesh_axes),
        size=axis_size,
        periodic=periodic,
        bc_value=bc_value,
        use_barrier=not interpret,
    )
    slab_elems = lo_face.shape[0] * lo_face.shape[1] * lo_face.shape[2]
    ghost_lo, ghost_hi = pl.pallas_call(
        kernel,
        name=f"heat3d.halo.{axis_name}.dma",
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(lo_face.shape, u.dtype),
            jax.ShapeDtypeStruct(hi_face.shape, u.dtype),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=axis,
        ),
        # pure data movement: two width-k slabs read + two written
        cost_estimate=pl.CostEstimate(
            flops=0,
            bytes_accessed=4 * slab_elems * u.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(lo_face, hi_face)
    return lax.concatenate(
        [
            _from_axis_leading(ghost_lo, axis),
            u,
            _from_axis_leading(ghost_hi, axis),
        ],
        dimension=axis,
    )


def exchange_halo_dma(
    u: jax.Array,
    mesh_cfg: MeshConfig,
    bc: BoundaryCondition,
    bc_value: float = 0.0,
    width: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Full 3D DMA ghost exchange: local (nx,ny,nz) -> (nx+2w,ny+2w,nz+2w).
    Axis-ordered like the ppermute path so corner ghosts propagate (each
    later axis exchanges the already-ghost-grown slab). Must run inside
    shard_map over the mesh in ``mesh_cfg``."""
    periodic = bc is BoundaryCondition.PERIODIC
    for axis, (axis_name, axis_size) in enumerate(
        zip(mesh_cfg.axis_names, mesh_cfg.shape)
    ):
        u = exchange_axis_dma(
            u,
            axis,
            axis_name,
            axis_size,
            mesh_cfg.axis_names,
            periodic,
            bc_value,
            width=width,
            interpret=interpret,
        )
    return u


def exchange_halo_dma_planned(
    u: jax.Array,
    plan,
    bc_value: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Plan-driven DMA exchange: consume an
    :class:`~heat3d_tpu.parallel.plan.ExchangePlan`'s precomputed axis
    schedule (corner-propagation order, axis names/sizes, width) instead
    of re-deriving them from the mesh config on every trace — the step
    builders hand every transport the same plan object. DMA plans are
    monolithic by construction (``plan.build_plan`` rejects partitioned
    DMA: the slab kernels stage and ship whole faces). Must run inside
    shard_map over the plan's mesh."""
    if plan.transport != "dma" or plan.mode != "monolithic":
        raise ValueError(
            f"exchange_halo_dma_planned wants a monolithic DMA plan, got "
            f"transport={plan.transport!r} mode={plan.mode!r}"
        )
    mesh_axes = plan.mesh.axis_names
    for spec in plan.axis_specs:
        u = exchange_axis_dma(
            u,
            spec.axis,
            spec.name,
            spec.size,
            mesh_axes,
            plan.periodic,
            bc_value,
            width=plan.width,
            interpret=interpret,
        )
    return u
