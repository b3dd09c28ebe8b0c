"""Fused DMA-overlap stencil kernel: remote face copies + interior sweep +
shell emission in ONE Pallas kernel (SURVEY.md §7.1 item 7).

Reference parity (SURVEY.md §3.2 hot-spot analysis): the optimized CUDA
variants of the reference class run the interior-update kernel on one
stream while the halo faces exchange on another, then update the boundary.
The ppermute transports get this overlap from XLA's async collectives (the
faces-direct step); the RDMA transport (ops/halo_pallas) could not — its
exchange kernel starts AND waits its DMAs before any compute runs. This
kernel closes that gap for the slab-decomposed configs (both stencil
families): the two
x-face remote copies are issued at grid step 0, the streaming sweep then
emits every x-interior output plane (1 .. nx-2) — which depend only on
local planes — while the faces are in flight over ICI, and only the last
few grid steps wait on the receive semaphores and emit the two shard-
boundary planes. At 1024^3-scale shards the transfer (a few MB per face)
hides under the multi-ms bulk sweep with three orders of magnitude of
slack.

The scheduling trick that keeps the kernel small: the sweep's 3-slot input
ring treats the arriving ghost planes as ordinary planes of the stream.
Step i <= nx-1 stores local plane i; step nx stores the HIGH ghost (acting
as "plane nx", so emitting output nx-1 at step nx is the ring's standard
emit); steps nx+2 / nx+3 re-load planes 0 / 1 around the LOW ghost stored
at step nx+1, making output 0's emit at step nx+3 the same slot pattern
{-1: (i+1)%3, 0: (i+2)%3, 1: i%3} as every other emission. One uniform
emit path, outputs ordered interior-first — overlap falls out of the index
maps instead of a second kernel.

Scope (the dispatch gate `fused_dma_supported` enforces this): a mesh
sharded along axis 0 only (the judged 1D slab decomposition; y/z stay
domain boundaries synthesized in-register exactly as
ops/stencil_pallas_direct does), unpadded shards, nx >= 2. BOTH judged
stencil families qualify: an x-slab mesh has no corner neighbors — the
received x-ghost plane is the complete neighbor data, and its y/z frame
(which the 27-point x-plane taps read) is a domain boundary synthesized
from the resident plane. Must run inside shard_map.
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
from heat3d_tpu.ops.stencil_pallas import _plane_taps
from heat3d_tpu.ops.stencil_pallas_direct import (
    _chunk_ghost_rows,
    _plane_bytes,
    _row_block_specs,
    _store_framed_plane,
    _store_input_plane,
    choose_chunk,
)

# The two resident (ny, nz) ghost planes live OUTSIDE choose_chunk's
# ring/pipeline budget; their own ceiling keeps the kernel's total VMEM
# well inside the chip's (ghosts are 4 MB each at 1024^2 fp32).
_GHOST_BUDGET = 16 * 1024 * 1024

# Per-generation VMEM budget (bytes/core) the fused kernels are gated
# against: the scoped VMEM a Mosaic kernel gets by default (the v5e
# compiler refuses a fused slab kernel sized to 32 MiB with
# RESOURCE_EXHAUSTED). Keyed by the normalized chip-generation strings
# the tuning cache derives from device_kind (tune.cache.chip_generation).
# THE single source: the vmem-budget lint (analysis/vmem.py) audits the
# kernel admit budgets against this same table, and the IR
# memory-contract checker adjudicates the resolved fused-DMA budget
# against it per generation.
CHIP_VMEM_BYTES = {
    "tpu-v4": 16 * 1024 * 1024,
    "tpu-v5-lite": 16 * 1024 * 1024,
    "tpu-v5p": 32 * 1024 * 1024,
    "tpu-v6-lite": 32 * 1024 * 1024,
}


def chip_vmem_budget_for(generation: str) -> int:
    """The whole-chip VMEM budget the fused gate uses on ``generation``
    (a normalized ``tune.cache.chip_generation`` string) absent an env
    override. Off the chip (the interpreter, and compile-only tests that
    do not steer the generation) it is the smallest budget in the table,
    so a shape admitted there fits every listed chip. A TPU generation
    missing from the table is an error: its budget is unknown."""
    if generation in CHIP_VMEM_BYTES:
        return CHIP_VMEM_BYTES[generation]
    if generation == "cpu":
        return min(CHIP_VMEM_BYTES.values())
    raise ValueError(
        f"no VMEM budget for chip generation {generation!r}: add it to "
        "ops/stencil_dma_fused.CHIP_VMEM_BYTES (or set HEAT3D_VMEM_BYTES)"
    )


def _chip_vmem_budget() -> int:
    """Whole-chip VMEM ceiling the COMBINED fused-kernel footprint (resident
    ghosts + ring/pipeline + emit-chain scoped stack) is gated against.
    Resolution order: ``HEAT3D_VMEM_BYTES`` (operator override) > the
    per-generation table above keyed on the live chip generation."""
    import os

    env = os.environ.get("HEAT3D_VMEM_BYTES")
    if env:
        return int(env)
    from heat3d_tpu.tune.cache import chip_generation

    return chip_vmem_budget_for(chip_generation())


def fused_vmem_refusal(local_shape, halo, in_itemsize, out_itemsize,
                       n_taps, compute_itemsize):
    """None when the fused kernels' chunking fits the VMEM budget, else
    the reason, naming the budget — the error an explicitly requested
    fused route raises instead of running another route in its place."""
    if _fused_choose_chunk(
        local_shape, halo, in_itemsize, out_itemsize, n_taps,
        compute_itemsize,
    ) is not None:
        return None
    from heat3d_tpu.tune.cache import chip_generation

    ghosts = 2 * halo * _plane_bytes(local_shape[1], local_shape[2],
                                     in_itemsize)
    return (
        f"local block {tuple(local_shape)} at halo width {halo} does not "
        f"fit the fused kernel's VMEM budget of {_chip_vmem_budget()} "
        f"bytes on {chip_generation()!r} (resident ghost planes "
        f"{ghosts} bytes, ghost cap {_GHOST_BUDGET})"
    )


def _fused_choose_chunk(
    local_shape, halo, in_itemsize, out_itemsize, n_taps, compute_itemsize,
):
    """The fused kernels' chunk chooser: choose_chunk's separate
    ring/stack ceilings PLUS the combined whole-chip constraint with the
    resident ghost buffers (which live outside the ring budget) as the
    reserve — so ``by`` shrinks to a combined-feasible size on
    smaller-VMEM chips rather than the route being rejected. The ONE
    entry both the dispatch gates and the kernel builders call, so they
    cannot drift. Returns ``by`` or None (ghost budget busted or no
    feasible chunking)."""
    ny, nz = local_shape[1], local_shape[2]
    ghost_bytes = 2 * halo * _plane_bytes(ny, nz, in_itemsize)
    if ghost_bytes > _GHOST_BUDGET:
        return None
    return choose_chunk(
        local_shape, halo, in_itemsize, out_itemsize,
        n_taps=n_taps, compute_itemsize=compute_itemsize,
        reserve_bytes=ghost_bytes, total_budget=_chip_vmem_budget(),
    )

# collective_id: the per-axis halo kernels use 0..2; each fused kernel is
# its own collective class — distinct ids even though the two never
# synchronize with each other, because make_multistep_fn compiles BOTH
# (tb=2 superstep + tb=1 remainder step) into one program and the barrier
# semaphore is keyed by id.
_COLLECTIVE_ID = 3
_COLLECTIVE_ID_TB2 = 4


def fused_dma_supported(
    local_shape: Tuple[int, int, int],
    mesh_shape: Tuple[int, int, int],
    taps: np.ndarray,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    compute_itemsize: int = 4,
) -> bool:
    """Any 3x3x3 tap set qualifies: on a 1D x-slab mesh the received
    x-ghost plane IS the complete neighbor data (no corner neighbors
    exist — y/z are domain boundaries whose frame is synthesized
    in-register), so the 27-point family rides the same kernel."""
    nx, ny, nz = local_shape
    if nx < 2:
        return False  # the re-loaded planes 0/1 must be distinct x-planes
    if mesh_shape[0] < 2 or mesh_shape[1] != 1 or mesh_shape[2] != 1:
        return False  # scope: 1D slab decomposition along x
    return (
        _fused_choose_chunk(
            local_shape, 1, in_itemsize, out_itemsize,
            effective_num_taps(taps), compute_itemsize,
        )
        is not None
    )


def fused_dma_3d_supported(
    local_shape: Tuple[int, int, int],
    mesh_shape: Tuple[int, int, int],
    taps: np.ndarray,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    compute_itemsize: int = 4,
) -> bool:
    """Scope gate for the 3D-block generalization of the fused DMA-overlap
    step (parallel/step._local_step_fused_dma_3d): a mesh sharded along x
    (>= 2 devices) AND at least one of y/z — the judged block
    decompositions (BASELINE.json configs 3-5). The kernel itself is the
    unchanged x-slab kernel (its in-register y/z frame synthesis is wrong
    only in the outermost shell of each sharded y/z axis, which the step
    recomputes from ppermute'd faces and patches); the pure x-slab scope
    stays with ``fused_dma_supported`` so the two dispatch routes are
    mutually exclusive."""
    nx, ny, nz = local_shape
    if nx < 2:
        return False
    if mesh_shape[0] < 2 or (mesh_shape[1] == 1 and mesh_shape[2] == 1):
        return False  # x-sharded 3D/2D blocks only; x-slabs use the
        # dedicated route (no shell patches)
    return (
        _fused_choose_chunk(
            local_shape, 1, in_itemsize, out_itemsize,
            effective_num_taps(taps), compute_itemsize,
        )
        is not None
    )


def substitute_dirichlet_x_edges(
    glo, ghi, *, axis_name, axis_size, periodic, bc_value
):
    """The READ side of the ghost-landing contract, in ONE place: the
    RDMA ring copy always runs (torus-symmetric, keeping the semaphores
    drained), so at Dirichlet x-edge devices the landed buffers hold wrap
    data and every consumer — the kernel in-register, the reference
    contract, the 3D route's shell-patch glue — must substitute bc_value
    before reading. Periodic rings pass through (wrap data is genuine)."""
    if periodic:
        return glo, ghi
    my = lax.axis_index(axis_name)
    bc = jnp.asarray(bc_value, glo.dtype)
    glo = jnp.where(my == 0, jnp.full_like(glo, bc), glo)
    ghi = jnp.where(my == axis_size - 1, jnp.full_like(ghi, bc), ghi)
    return glo, ghi


def reference_fused_step_xla(
    u, taps, *, axis_name, axis_size, mesh_axes, periodic, bc_value,
    compute_dtype=jnp.float32, out_dtype=None, return_ghosts=False,
    interpret=True,
):
    """Pure-XLA reference implementation of apply_step_fused_dma's
    CONTRACT, used to certify the 3D route's glue on multi-axis CPU
    meshes (jax-0.9 interpret mode cannot discharge remote DMA on a
    >1-named-axis mesh; the kernel's own RDMA mechanics are certified on
    the 1D ring, where interpret works — tests/multidevice_checks.py).

    Semantics mirrored exactly: the x ghost planes arrive by torus ring
    transfer (the landed buffers hold wrap data even at Dirichlet
    x-edges), Dirichlet x-edge devices READ bc_value instead, and every
    plane's y/z frame — the ghost planes' included — is synthesized as a
    DOMAIN boundary (local wrap / bc), which the 3D route's shell patches
    then correct on sharded y/z axes."""
    from heat3d_tpu.ops.stencil_jnp import apply_taps_padded

    out_dtype = out_dtype or u.dtype
    nx = u.shape[0]
    ring_fwd = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    ring_bwd = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    glo = lax.ppermute(u[nx - 1 : nx], axis_name, ring_fwd)
    ghi = lax.ppermute(u[0:1], axis_name, ring_bwd)
    rlo, rhi = substitute_dirichlet_x_edges(
        glo, ghi, axis_name=axis_name, axis_size=axis_size,
        periodic=periodic, bc_value=bc_value,
    )
    stack = jnp.concatenate([rlo, u, rhi], axis=0)  # (nx+2, ny, nz)
    if periodic:
        padded = jnp.pad(stack, ((0, 0), (1, 1), (1, 1)), mode="wrap")
    else:
        padded = jnp.pad(
            stack, ((0, 0), (1, 1), (1, 1)),
            constant_values=np.asarray(bc_value),
        )
    out = apply_taps_padded(
        padded, taps, compute_dtype=compute_dtype, out_dtype=out_dtype
    )
    if return_ghosts:
        return out, glo[0], ghi[0]
    return out


def reference_fused_superstep_xla(
    u, taps, *, axis_name, axis_size, mesh_axes, periodic, bc_value,
    compute_dtype=jnp.float32, out_dtype=None, interpret=True,
):
    """Pure-XLA reference for apply_superstep_fused_dma's RESULT contract:
    two reference steps. The fused superstep is certified result-equal to
    two plain steps on the 1D ring (tests/multidevice_checks.py —
    including the mid's storage-dtype round trip, which two full steps
    reproduce exactly), so the off-TPU emulation tier runs the
    composition instead of the kernel."""
    out_dtype = out_dtype or u.dtype
    for _ in range(2):
        u = reference_fused_step_xla(
            u, taps, axis_name=axis_name, axis_size=axis_size,
            mesh_axes=mesh_axes, periodic=periodic, bc_value=bc_value,
            compute_dtype=compute_dtype, out_dtype=out_dtype,
        )
    return u


def _rdma_halo(
    u_any, glo_ref, ghi_ref, send_sem, recv_sem, *, nx, width,
    axis_name, mesh_axes, axis_size, use_barrier,
):
    """The kernels' shared RDMA protocol, in ONE place (the semaphore/
    barrier choreography is the trickiest invariant here): symmetric ring
    pushes as in ops/halo_pallas._exchange_body — my high ``width``-slab
    -> hi neighbor's low-ghost buffer (its completion on MY recv_sem[0]
    is my LOW ghost arriving), and vice versa. Returns
    ``(my, start, wait_hi_ghost, wait_lo_ghost)``; descriptors are rebuilt
    at each use site — they are just op emitters over the same refs and
    semaphores."""
    my = lax.axis_index(axis_name)

    def neighbor(delta):
        idx = lax.rem(my + delta + axis_size, axis_size)
        if len(mesh_axes) == 1:
            return idx
        return {axis_name: idx}

    def src(lo):
        if width == 1:  # integer-indexed 2D face matching the plane dst
            return u_any.at[0 if lo else nx - 1]
        return u_any.at[pl.ds(0 if lo else nx - width, width)]

    def copy_to_hi_neighbor():
        return pltpu.make_async_remote_copy(
            src_ref=src(lo=False),
            dst_ref=glo_ref,
            send_sem=send_sem.at[0],
            recv_sem=recv_sem.at[0],
            device_id=neighbor(+1),
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    def copy_to_lo_neighbor():
        return pltpu.make_async_remote_copy(
            src_ref=src(lo=True),
            dst_ref=ghi_ref,
            send_sem=send_sem.at[1],
            recv_sem=recv_sem.at[1],
            device_id=neighbor(-1),
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    def start():
        if use_barrier:
            # Neighbor barrier: nobody pushes into a peer's ghost buffers
            # until that peer has entered this kernel (cross-call buffer
            # reuse race guard). Skipped in interpret mode (synchronous
            # emulation, no barrier-semaphore support).
            barrier = pltpu.get_barrier_semaphore()
            for delta in (-1, +1):
                pltpu.semaphore_signal(
                    barrier,
                    inc=1,
                    device_id=neighbor(delta),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            pltpu.semaphore_wait(barrier, 2)
        copy_to_hi_neighbor().start()
        copy_to_lo_neighbor().start()

    # send_sem[1] + recv_sem[1]: my HIGH ghost has landed
    wait_hi_ghost = lambda: copy_to_lo_neighbor().wait()  # noqa: E731
    # send_sem[0] + recv_sem[0]: my LOW ghost has landed
    wait_lo_ghost = lambda: copy_to_hi_neighbor().wait()  # noqa: E731
    return my, start, wait_hi_ghost, wait_lo_ghost


def _fused_kernel(
    u_win,
    u_any,
    top_ref,
    bot_ref,
    out_ref,
    glo_ref,
    ghi_ref,
    ring,
    send_sem,
    recv_sem,
    *,
    taps_flat,
    nx,
    by,
    nz,
    n_chunks,
    axis_name,
    mesh_axes,
    axis_size,
    periodic,
    bc_value,
    compute_dtype,
    out_dtype,
    use_barrier,
    rdma_factory=None,
):
    j = pl.program_id(0)
    i = pl.program_id(1)
    bc = u_win.dtype.type(bc_value)
    # rdma_factory lets a caller swap the transfer schedule under the
    # UNCHANGED sweep/emit body (ops/stencil_fused_rdma rides the
    # ExchangePlan's per-sub-block decomposition through here); the
    # default is this module's monolithic two-descriptor protocol.
    my, start_rdma, wait_hi_ghost, wait_lo_ghost = (
        rdma_factory or _rdma_halo
    )(
        u_any, glo_ref, ghi_ref, send_sem, recv_sem, nx=nx, width=1,
        axis_name=axis_name, mesh_axes=mesh_axes, axis_size=axis_size,
        use_barrier=use_barrier,
    )

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _start():
        start_rdma()

    # Waits, placed AFTER the whole interior sweep: the hi ghost ("plane
    # nx") is first read at step (0, nx), the lo ghost at (0, nx+1). Only
    # chunk column 0 waits — the semaphores are consumed once; later
    # columns read the already-landed buffers.
    @pl.when(jnp.logical_and(j == 0, i == nx))
    def _wait_hi():
        wait_hi_ghost()

    @pl.when(jnp.logical_and(j == 0, i == nx + 1))
    def _wait_lo():
        wait_lo_ghost()

    chunk = u_win[0]  # (by, nz)
    top, bot = _chunk_ghost_rows(chunk, top_ref, bot_ref, 1, periodic, bc)
    if not periodic:
        top = jnp.where(j == 0, jnp.full_like(top, bc), top)
        bot = jnp.where(j == n_chunks - 1, jnp.full_like(bot, bc), bot)

    # Dirichlet domain edges: the torus-symmetric wrap transfer still
    # arrives (and is waited, keeping the semaphores drained), but the
    # ghost VALUES are the boundary condition.
    is_lo_edge = jnp.logical_and(jnp.logical_not(periodic), my == 0)
    is_hi_edge = jnp.logical_and(
        jnp.logical_not(periodic), my == axis_size - 1
    )

    ny = by * n_chunks

    def ghost_chunk(ref, edge):
        g = ref[pl.ds(j * by, by), :]
        return jnp.where(edge, jnp.full_like(g, bc), g)

    def ghost_plane_rows(ref, edge):
        """The (1, nz) y-ghost rows above/below chunk j of a received
        ghost plane. The full (ny, nz) plane is resident, so neighbor
        rows are direct reads; at the y DOMAIN boundary the row wraps
        (periodic — y is unsharded, so the wrap is genuine data) or is
        the boundary value. A Dirichlet-edge device's whole ghost plane
        is bc, rows included."""
        if periodic:
            ti = lax.rem(j * by - 1 + ny, ny)
            bi = lax.rem(j * by + by, ny)
            return ref[pl.ds(ti, 1), :], ref[pl.ds(bi, 1), :]
        fill = jnp.full((1, nz), bc, u_win.dtype)
        ti = jnp.maximum(j * by - 1, 0)
        bi = jnp.minimum(j * by + by, ny - 1)
        topg = jnp.where(
            jnp.logical_or(j == 0, edge), fill, ref[pl.ds(ti, 1), :]
        )
        botg = jnp.where(
            jnp.logical_or(j == n_chunks - 1, edge),
            fill,
            ref[pl.ds(bi, 1), :],
        )
        return topg, botg

    real_plane = i <= nx - 1
    for k in range(3):

        @pl.when(jnp.logical_and(real_plane, lax.rem(i, 3) == k))
        def _store_local(k=k):
            _store_framed_plane(ring, k, chunk, top, bot, bc, periodic, 1)

    # Step nx: the HIGH ghost enters the ring as "plane nx"; step nx+1 the
    # LOW ghost as the future "plane -1"; steps nx+2 / nx+3 re-load planes
    # 0 / 1 (the window fetches them via the index map — `chunk` already
    # holds the right data). Ghost planes are framed like every other
    # plane — their y/z frame is a DOMAIN boundary on an x-slab mesh, so
    # wrap/bc synthesis from the resident full plane is exact, which is
    # what lets the 27-point family (whose x-planes read their frames)
    # ride this kernel.
    for k in range(3):

        @pl.when(jnp.logical_and(i == nx, lax.rem(i, 3) == k))
        def _store_hi(k=k):
            gt, gb = ghost_plane_rows(ghi_ref, is_hi_edge)
            _store_framed_plane(
                ring, k, ghost_chunk(ghi_ref, is_hi_edge), gt, gb,
                bc, periodic, 1,
            )

        @pl.when(jnp.logical_and(i == nx + 1, lax.rem(i, 3) == k))
        def _store_lo(k=k):
            gt, gb = ghost_plane_rows(glo_ref, is_lo_edge)
            _store_framed_plane(
                ring, k, ghost_chunk(glo_ref, is_lo_edge), gt, gb,
                bc, periodic, 1,
            )

        @pl.when(jnp.logical_and(i >= nx + 2, lax.rem(i, 3) == k))
        def _store_reload(k=k):
            _store_framed_plane(ring, k, chunk, top, bot, bc, periodic, 1)

    # Uniform emission: planes (i-2, i-1, i) live in slots ((k+1)%3,
    # (k+2)%3, k) for every emitting step — interior outputs i-1 at
    # i in [2, nx-1], output nx-1 at i == nx (hi ghost = plane nx), and
    # output 0 at i == nx+3 (lo ghost / plane 0 / plane 1).
    emit = jnp.logical_or(
        jnp.logical_and(i >= 2, i <= nx), i == nx + 3
    )
    for k in range(3):

        @pl.when(jnp.logical_and(emit, lax.rem(i, 3) == k))
        def _emit(k=k):
            slots = {-1: (k + 1) % 3, 0: (k + 2) % 3, 1: k}
            planes = {
                d: ring[s].astype(compute_dtype) for d, s in slots.items()
            }
            res = _plane_taps(planes, taps_flat, by, nz, compute_dtype)
            out_ref[0] = res.astype(out_dtype)


def apply_step_fused_dma(
    u: jax.Array,
    taps: np.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
    return_ghosts: bool = False,
) -> jax.Array:
    """One stencil update of an x-slab shard with kernel-initiated halo
    DMA overlapped under the sweep. Must run inside shard_map over a mesh
    whose axis 0 has ``axis_size`` devices; axes 1/2 may be sharded too
    when the caller patches the y/z shells (the 3D route,
    ``fused_dma_3d_supported`` — the kernel treats y/z as domain
    boundaries either way).

    ``return_ghosts=True`` additionally returns the two landed ghost
    planes ``(out, glo, ghi)``, each (ny, nz) — the x-neighbor faces the
    RDMA delivered. NOTE: on Dirichlet x-edge devices the buffers hold the
    torus wrap transfer (the ring copy always runs to keep the semaphores
    drained); the kernel substitutes bc_value when READING, and a caller
    reusing the buffers (the 3D route's shell patches) must do the same."""
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

    # Input plane fetched per step: local planes for the sweep, planes 0/1
    # again for the final emit, in-range dummies on the ghost-store steps.
    def x_of(i):
        return jnp.where(
            i <= nx - 1, i, jnp.clip(i - (nx + 2), 0, nx - 1)
        )

    # Output plane per step, shaped so every window run's LAST step is its
    # write: i=0..1 idle under block 1 (written at i=2), interior writes
    # i-1, block nx-1 written at i=nx, block 0 idle nx+1..nx+2 and written
    # at nx+3.
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
    )
    in_specs = [
        pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # DMA face source
    ]
    operands = (u, u)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u, u)
    out, glo, ghi = pl.pallas_call(
        kernel,
        name="heat3d.stencil.fused_dma",
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
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
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
    if return_ghosts:
        return out, glo, ghi
    return out


def _fused_kernel_single(
    u_win, u_any, out_ref, glo_ref, ghi_ref, ring, send_sem, recv_sem,
    **params,
):
    """Single-chunk-column variant: no ghost-row refs (derived in-kernel)."""
    _fused_kernel(
        u_win, u_any, None, None, out_ref, glo_ref, ghi_ref, ring,
        send_sem, recv_sem, **params,
    )


# ---------------------------------------------------------------------------
# tb=2: the fused two-update superstep with the same DMA overlap.
#
# Same stream trick, width-2: the grid is (n_chunks, nx+8), and every step
# stores ONE input "stream position" — local planes 0..nx-1 (phase A, the
# overlap window), then the two HIGH ghost planes (positions nx, nx+1),
# then the two LOW ghosts (-2, -1) and re-loads of planes 0..3 (the
# epilogue). Mids (centered at the previous position) and outputs
# (centered two back) fire wherever three contiguous stream positions are
# resident, so phase A emits outputs 2..nx-3 from purely local data while
# the four face planes fly over ICI; steps nx/nx+1 finish outputs
# nx-2/nx-1 (first wait), and the epilogue recomputes mids -1..2 to emit
# outputs 0/1 — the standard recompute-the-ghost-ring trick of the
# temporally-blocked superstep, done inside the same kernel.


def fused_dma2_supported(
    local_shape: Tuple[int, int, int],
    mesh_shape: Tuple[int, int, int],
    taps: np.ndarray,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    compute_itemsize: int = 4,
) -> bool:
    nx, ny, nz = local_shape
    if nx < 4:
        return False  # epilogue re-streams planes 0..3 as distinct planes
    if mesh_shape[0] < 2 or mesh_shape[1] != 1 or mesh_shape[2] != 1:
        return False
    return (
        _fused_choose_chunk(
            local_shape, 2, in_itemsize, out_itemsize,
            effective_num_taps(taps), compute_itemsize,
        )
        is not None
    )


def _fused2_kernel(
    u_win,
    u_any,
    top_ref,
    bot_ref,
    out_ref,
    glo_ref,
    ghi_ref,
    ring_a,
    ring_b,
    send_sem,
    recv_sem,
    *,
    taps_flat,
    nx,
    by,
    nz,
    n_chunks,
    axis_name,
    mesh_axes,
    axis_size,
    periodic,
    bc_value,
    compute_dtype,
    storage_dtype,
    out_dtype,
    use_barrier,
    rdma_factory=None,
):
    j = pl.program_id(0)
    i = pl.program_id(1)
    bc_s = u_win.dtype.type(bc_value)
    ny = by * n_chunks
    # same swappable transfer schedule as _fused_kernel (the planned
    # per-sub-block variant lives in ops/stencil_fused_rdma)
    my, start_rdma, wait_hi_ghost, wait_lo_ghost = (
        rdma_factory or _rdma_halo
    )(
        u_any, glo_ref, ghi_ref, send_sem, recv_sem, nx=nx, width=2,
        axis_name=axis_name, mesh_axes=mesh_axes, axis_size=axis_size,
        use_barrier=use_barrier,
    )

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _start():
        start_rdma()

    # First reads of the ghost slabs: hi at step nx, lo at step nx+2.
    @pl.when(jnp.logical_and(j == 0, i == nx))
    def _wait_hi():
        wait_hi_ghost()

    @pl.when(jnp.logical_and(j == 0, i == nx + 2))
    def _wait_lo():
        wait_lo_ghost()

    chunk = u_win[0]  # (by, nz)
    top, bot = _chunk_ghost_rows(chunk, top_ref, bot_ref, 2, periodic, bc_s)
    if not periodic:
        top = jnp.where(j == 0, jnp.full_like(top, bc_s), top)
        bot = jnp.where(j == n_chunks - 1, jnp.full_like(bot, bc_s), bot)

    is_lo_edge = jnp.logical_and(jnp.logical_not(periodic), my == 0)
    is_hi_edge = jnp.logical_and(
        jnp.logical_not(periodic), my == axis_size - 1
    )

    def ghost_slab_chunk(ref, q):
        return ref[q, pl.ds(j * by, by), :]

    def ghost_slab_rows(ref, q):
        """(2, nz) y-ghost rows above/below chunk j of ghost slab plane
        ``q`` — domain wrap (periodic y is unsharded) or bc rows."""
        def row(r):
            if periodic:
                return ref[q, pl.ds(lax.rem(r + ny, ny), 1), :]
            fill = jnp.full((1, nz), bc_s, u_win.dtype)
            oob = jnp.logical_or(r < 0, r >= ny)
            return jnp.where(
                oob, fill, ref[q, pl.ds(jnp.clip(r, 0, ny - 1), 1), :]
            )

        topg = lax.concatenate([row(j * by - 2), row(j * by - 1)], 0)
        botg = lax.concatenate([row(j * by + by), row(j * by + by + 1)], 0)
        return topg, botg

    # Stream-position source per step: local planes (phase A and the
    # epilogue re-loads arrive via the BlockSpec window), ghost slab
    # planes at steps nx..nx+3. `ghost_x` marks DOMAIN ghost planes
    # (Dirichlet edge devices only — elsewhere the DMA'd wrap content is
    # real neighbor data).
    is_ghost_step = jnp.logical_and(i >= nx, i <= nx + 3)
    ghost_x = jnp.logical_or(
        jnp.logical_and(is_hi_edge, jnp.logical_and(i >= nx, i <= nx + 1)),
        jnp.logical_and(
            is_lo_edge, jnp.logical_and(i >= nx + 2, i <= nx + 3)
        ),
    )
    for k in range(3):

        @pl.when(jnp.logical_and(
            jnp.logical_not(is_ghost_step), lax.rem(i, 3) == k
        ))
        def _store_local(k=k):
            _store_input_plane(
                ring_a, k, chunk, top, bot, bc_s, periodic, 2,
                ghost_x=jnp.zeros((), jnp.bool_),
            )

    # Ghost-slab stores sit OUTSIDE the ring-slot loop: `nx + step_off`
    # is a Python int, so the slot is static — one traced body per ghost
    # step instead of three (two statically-dead) per slot.
    for step_off, ref_sel, q in (
        (0, "hi", 0), (1, "hi", 1), (2, "lo", 0), (3, "lo", 1)
    ):

        @pl.when(i == nx + step_off)
        def _store_ghost(
            k=(nx + step_off) % 3, ref_sel=ref_sel, q=q
        ):
            ref = ghi_ref if ref_sel == "hi" else glo_ref
            gt, gb = ghost_slab_rows(ref, q)
            _store_input_plane(
                ring_a, k, ghost_slab_chunk(ref, q), gt, gb, bc_s,
                periodic, 2, ghost_x=ghost_x,
            )

    # Mid centered at the previous stream position, from inputs at steps
    # (i-2, i-1, i) in slots {-1: (i+1)%3, 0: (i+2)%3, +1: i%3}; stored in
    # slot (i-1)%3 so three consecutive mids coexist. Fires wherever three
    # CONTIGUOUS stream positions are resident: phase A + the high ghosts
    # (steps 2..nx+1 -> mids 1..nx) and the epilogue re-stream (steps
    # nx+4..nx+7 -> mids -1..2).
    mid_fire = jnp.logical_or(
        jnp.logical_and(i >= 2, i <= nx + 1), i >= nx + 4
    )
    # mid's stream-center position (phase A / epilogue mapping)
    m_pos = jnp.where(i <= nx + 1, i - 1, i - (nx + 5))
    # a domain-ghost mid plane (the intermediate's Dirichlet x-ghost):
    # pinned to bc exactly as _fill_mid_ghosts sees it in the unfused
    # superstep — only the edge devices' out-of-domain centers
    mid_ghost = jnp.logical_or(
        jnp.logical_and(is_lo_edge, m_pos == -1),
        jnp.logical_and(is_hi_edge, m_pos == nx),
    )
    for k in range(3):  # k == i % 3

        @pl.when(jnp.logical_and(mid_fire, lax.rem(i, 3) == k))
        def _mid(k=k):
            slots = {-1: (k + 1) % 3, 0: (k + 2) % 3, 1: k}
            planes = {
                d: ring_a[s].astype(compute_dtype) for d, s in slots.items()
            }
            mid = _plane_taps(
                planes, taps_flat, by + 2, nz + 2, compute_dtype
            )
            slot = (k + 2) % 3  # == (i-1) % 3

            @pl.when(mid_ghost)
            def _bc_mid():
                ring_b[slot] = jnp.full(
                    (by + 2, nz + 2), bc_s, storage_dtype
                )

            @pl.when(jnp.logical_not(mid_ghost))
            def _real_mid():
                # round-trip through storage dtype so fused == unfused;
                # Dirichlet pins the intermediate's domain ghost ring
                # (lane columns always; rows on edge chunk columns)
                ring_b[slot] = mid.astype(storage_dtype)
                if not periodic:
                    edge_col = jnp.full((by + 2, 1), bc_s, storage_dtype)
                    ring_b[slot, :, 0:1] = edge_col
                    ring_b[slot, :, nz + 1 : nz + 2] = edge_col
                    edge_row = jnp.full((1, nz + 2), bc_s, storage_dtype)

                    @pl.when(j == 0)
                    def _top_row():
                        ring_b[slot, 0:1, :] = edge_row

                    @pl.when(j == n_chunks - 1)
                    def _bot_row():
                        ring_b[slot, by + 1 : by + 2, :] = edge_row

    # Output centered two stream positions back, from mids stored at steps
    # (i-2, i-1, i) in slots {-1: i%3, 0: (i+1)%3, +1: (i+2)%3}. Fires
    # where three consecutive mids exist: steps 4..nx+1 (outputs 2..nx-1)
    # and nx+6..nx+7 (outputs 0..1).
    out_fire = jnp.logical_or(
        jnp.logical_and(i >= 4, i <= nx + 1), i >= nx + 6
    )
    for k in range(3):

        @pl.when(jnp.logical_and(out_fire, lax.rem(i, 3) == k))
        def _out(k=k):
            slots = {-1: k, 0: (k + 1) % 3, 1: (k + 2) % 3}
            planes = {
                d: ring_b[s].astype(compute_dtype) for d, s in slots.items()
            }
            res = _plane_taps(planes, taps_flat, by, nz, compute_dtype)
            out_ref[0] = res.astype(out_dtype)


def _fused2_kernel_single(
    u_win, u_any, out_ref, glo_ref, ghi_ref, ring_a, ring_b, send_sem,
    recv_sem, **params,
):
    """Single-chunk-column variant: no ghost-row refs (derived in-kernel)."""
    _fused2_kernel(
        u_win, u_any, None, None, out_ref, glo_ref, ghi_ref, ring_a,
        ring_b, send_sem, recv_sem, **params,
    )


def apply_superstep_fused_dma(
    u: jax.Array,
    taps: np.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    mesh_axes,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """TWO fused stencil updates of an x-slab shard in one HBM sweep, with
    the width-2 halo DMA overlapped under the phase-A interior sweep.
    Must run inside shard_map over a mesh whose axis 0 has ``axis_size``
    devices (axes 1/2 size 1)."""
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
    )
    in_specs = [
        pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # DMA slab source
    ]
    operands = (u, u)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u, u)
    out, _glo, _ghi = pl.pallas_call(
        kernel,
        name="heat3d.stencil.fused_dma2",
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
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=_COLLECTIVE_ID_TB2,
        ),
        cost_estimate=pl.CostEstimate(
            # RAW flops (the streamk convention — see obs/perf/roofline's
            # effective discount): mids sweep the one-ring-padded volume
            flops=2 * len(flat)
            * ((nx + 2) * (ny + 2) * (nz + 2) + nx * ny * nz),
            bytes_accessed=nx * ny * nz
            * (u.dtype.itemsize + jnp.dtype(out_dtype).itemsize),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
    return out
