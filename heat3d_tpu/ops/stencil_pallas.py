"""Pallas TPU stencil kernel — the native compute path.

Reference parity (SURVEY.md §2 C1): the reference's CUDA ``__global__``
Jacobi kernel (one thread per cell, 3D thread blocks). The TPU-native
formulation tiles the ghost-padded local block over a 1D Pallas grid of
x-slabs; each program holds a halo-overlapped input window in VMEM —
``Element``-indexed BlockSpecs give the overlapping reads, Mosaic's grid
pipeline double-buffers the HBM->VMEM streaming — and evaluates the
3x3x3 taps as statically-unrolled shifted-slice FMAs on the VPU. The y
and z axes stay whole: they are the (sublane, lane) dims, where Mosaic
requires provably-aligned window offsets (see choose_blocks), and ±1
shifts along them are cheap in-register sublane/lane shifts.

The kernel computes in ``compute_dtype`` (fp32 even for bf16 storage by
default — BASELINE.json config 5's "bf16 stencil + fp32 residual" policy)
and writes ``out_dtype``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas import Element as _Element

from heat3d_tpu.core.config import SolverConfig
from heat3d_tpu.core.stencils import (
    STENCILS,
    accumulate_taps,
    effective_num_taps,
    flat_taps,
    nonzero_taps,
)

# VMEM working-set budget for one grid step, empirically tuned: the
# pipeline needs two in-flight input windows plus the output tile, and
# Mosaic wants headroom for spills, so aim the *per-step* set under ~5 MB.
_VMEM_STEP_BUDGET = 5 * 1024 * 1024

_LANE = 128
_SUBLANE = 8


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _divisors_desc(n: int, cap: int):
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            yield d


def _vmem_step_bytes(
    bx: int, by: int, nz: int, in_itemsize: int, out_itemsize: int
) -> int:
    """Estimate one grid step's VMEM footprint with TPU tile padding."""
    in_bytes = (
        (bx + 2) * _round_up(by + 2, _SUBLANE) * _round_up(nz + 2, _LANE) * in_itemsize
    )
    out_bytes = bx * _round_up(by, _SUBLANE) * _round_up(nz, _LANE) * out_itemsize
    return in_bytes + out_bytes


def choose_blocks(
    local_shape: Tuple[int, int, int],
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
) -> Optional[Tuple[int, int]]:
    """Pick (bx, by) output-tile sizes for a (nx, ny, nz) local block, or
    None if no x-tiling fits the VMEM budget. ``by`` is always ``ny``.

    Constraints established empirically on v5-lite hardware: the trailing two
    dims of the overlapped (Element) input window must be 8/128-divisible or
    full-extent (Pallas lowering check), AND Mosaic must prove the sublane
    window *offset* divisible by 8. A tiled y can never satisfy both —
    (by+2) % 8 == 0 and by % 8 == 0 are mutually exclusive — so the y window
    is always full-extent with a literal-0 offset (trivially provable; this
    also covers odd ny such as the 62^3 overlap-step interior). Tiling
    therefore happens only along x, the untiled leading dim, where offsets
    are unconstrained."""
    nx, ny, nz = local_shape
    for bx in _divisors_desc(nx, 256):
        if (
            _vmem_step_bytes(bx, ny, nz, in_itemsize, out_itemsize)
            <= _VMEM_STEP_BUDGET
            # 3D tap chain: ~n_taps live (bx, ny, nz) temporaries on the
            # Mosaic scoped stack
            and bx * _tap_stack_bytes(ny, nz, n_taps, compute_itemsize)
            <= _TAP_STACK_BUDGET
        ):
            return bx, ny
    return None


def pallas_supported(cfg: SolverConfig) -> Tuple[bool, str]:
    """Can the Pallas kernel run this config's local blocks?"""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        return False, f"platform is {platform!r}, kernel targets TPU"
    if jnp.dtype(cfg.precision.storage).itemsize not in (2, 4):
        return False, f"unsupported storage dtype {cfg.precision.storage}"
    itemsize = jnp.dtype(cfg.precision.storage).itemsize
    n_taps = effective_num_taps(STENCILS[cfg.stencil.kind].weights)
    c_item = jnp.dtype(cfg.precision.compute).itemsize
    import os

    if (
        cfg.mesh.shape == (1, 1, 1)
        and not cfg.is_padded
        # overlap=True rides the direct kernel for tb=1 (the tb=2 superstep
        # keeps its overlap mutual exclusion, checked below)
        and not (cfg.overlap and cfg.time_blocking != 1)
        and cfg.halo == "ppermute"
        and not os.environ.get("HEAT3D_NO_DIRECT")
    ):
        # same gate as parallel.step._direct_kernel_fn: only report the
        # direct kernel as support when the dispatch will actually take it
        # for EVERY step shape this config runs (tb>=3 supersteps ride the
        # fused streamk kernel or the padded compute, never the direct
        # kernel), else large single-shard configs would trace into the
        # (infeasible) windowed kernel instead of falling back
        from heat3d_tpu.ops.stencil_pallas_direct import direct_supported

        d1 = direct_supported(
            cfg.local_shape, 1, itemsize, itemsize, n_taps, c_item,
            taps=STENCILS[cfg.stencil.kind].weights,
        )
        if cfg.time_blocking == 1 and d1:
            return True, ""
        if (
            cfg.time_blocking == 2
            and d1
            and direct_supported(
                cfg.local_shape, 2, itemsize, itemsize, n_taps, c_item,
                taps=STENCILS[cfg.stencil.kind].weights,
            )
        ):
            return True, ""
    if stream_supported(cfg.local_shape, itemsize, itemsize, n_taps, c_item):
        return True, ""  # streaming kernel: no Element windows needed
    blocks = choose_blocks(cfg.local_shape, itemsize, itemsize, n_taps, c_item)
    if blocks is None:
        return False, f"no streaming ring or block tiling of {cfg.local_shape} fits VMEM"
    return True, ""


def _stream_vmem_bytes(
    shape: Tuple[int, int, int], in_itemsize: int, out_itemsize: int
) -> int:
    """VMEM footprint of the streaming kernel: a 3-plane ring buffer plus
    the double-buffered in/out plane pipeline, with TPU tile padding."""
    ny, nz = shape[1], shape[2]
    plane_in = _round_up(ny + 2, _SUBLANE) * _round_up(nz + 2, _LANE) * in_itemsize
    plane_out = _round_up(ny, _SUBLANE) * _round_up(nz, _LANE) * out_itemsize
    return 3 * plane_in + 2 * plane_in + 2 * plane_out


# Streaming kernel explicit-buffer budget (ring + pipeline), empirically
# tuned to leave Mosaic headroom.
_STREAM_VMEM_BUDGET = 12 * 1024 * 1024

# Fused multi-update streaming kernels (stream2 / streamk): the extra
# intermediate rings buy a slightly higher explicit-buffer ceiling. One
# named constant shared by both gates (and audited against per-chip
# VMEM capacities by `heat3d lint`'s vmem-budget checker).
_FUSED_STREAM_VMEM_BUDGET = 13 * 1024 * 1024

# Mosaic reserves scoped-VMEM *stack* for the tap chain's plane-sized
# compute-dtype temporaries — empirically ~n_taps live planes. The stack
# pool is capped by the compiler at 16 MB (its default scoped-vmem limit
# — a separate pool from the explicit ring/pipeline buffers above, which
# is why explicit budget + stack budget may legitimately sum past 16):
# the 27-tap chain at 512x512 fp32 planes reserved 34.4 MB against that
# cap and failed to compile. The budget leaves margin for the model's
# ~20% underestimate of that measurement. Shared by every kernel family:
# the streaming kernels here cannot shrink their full-extent-y planes,
# so an over-budget chain makes them unsupported (callers fall back);
# the direct kernels shrink their chunk height instead.
_TAP_STACK_BUDGET = 11 * 1024 * 1024


def _tap_stack_bytes(
    rows: int, lanes: int, n_taps: int, compute_itemsize: int = 4
) -> int:
    return (
        n_taps
        * _round_up(rows, _SUBLANE)
        * _round_up(lanes, _LANE)
        * compute_itemsize
    )


def stream_supported(
    shape: Tuple[int, int, int],
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
) -> bool:
    ny, nz = shape[1], shape[2]
    return (
        _stream_vmem_bytes(shape, in_itemsize, out_itemsize)
        <= _STREAM_VMEM_BUDGET
        and _tap_stack_bytes(ny, nz, n_taps, compute_itemsize)
        <= _TAP_STACK_BUDGET
    )


def _stream_kernel(in_ref, out_ref, scratch, *, taps_by_di, ny, nz,
                   compute_dtype, out_dtype):
    """Streaming x-plane stencil: grid step i loads padded plane i into a
    3-slot VMEM ring; once 3 planes are resident, emits output plane i-2.

    Every HBM plane is fetched exactly once (the windowed kernel re-fetches
    overlap planes), which matters when bandwidth is the roofline. Slot
    arithmetic is unrolled into three pl.when branches so all scratch
    indices are static.
    """
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 3)
    for k in range(3):

        @pl.when(slot == k)
        def _store(k=k):
            scratch[k] = in_ref[0]

    for k in range(3):

        @pl.when(jnp.logical_and(i >= 2, slot == k))
        def _compute(k=k):
            # i % 3 == k  =>  padded planes (i-2, i-1, i) live in slots
            # ((k+1)%3, (k+2)%3, k).
            planes = {
                -1: scratch[(k + 1) % 3].astype(compute_dtype),
                0: scratch[(k + 2) % 3].astype(compute_dtype),
                1: scratch[k].astype(compute_dtype),
            }
            out_ref[0] = _plane_taps(
                planes, taps_by_di, ny, nz, compute_dtype
            ).astype(out_dtype)


def apply_taps_pallas_stream(
    up: jax.Array,
    taps: np.ndarray,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Streaming-form Pallas stencil: ghost-padded (nx+2, ny+2, nz+2) block
    in, (nx, ny, nz) interior update out. One grid step per padded x-plane;
    output plane i-2 is emitted at step i (steps 0-1 prime the ring)."""
    nxp, nyp, nzp = up.shape
    nx, ny, nz = nxp - 2, nyp - 2, nzp - 2
    out_dtype = out_dtype or up.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)

    kernel = functools.partial(
        _stream_kernel,
        taps_by_di=flat,
        ny=ny,
        nz=nz,
        compute_dtype=compute_dtype,
        out_dtype=jnp.dtype(out_dtype),
    )
    flops_per_cell = 2 * len(flat)
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.stream",
        grid=(nxp,),
        in_specs=[pl.BlockSpec((1, nyp, nzp), lambda i: (i, 0, 0))],
        # Steps 0-1 park on output plane 0; step 2 overwrites it with the
        # real value before the block is ever flushed (the index only
        # changes at step 3).
        out_specs=pl.BlockSpec(
            (1, ny, nz), lambda i: (jnp.maximum(i - 2, 0), 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
        scratch_shapes=[pltpu.VMEM((3, nyp, nzp), up.dtype)],
        cost_estimate=pl.CostEstimate(
            flops=flops_per_cell * nx * ny * nz,
            bytes_accessed=nxp * nyp * nzp * up.dtype.itemsize
            + nx * ny * nz * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(up)


def _stream2_vmem_bytes(
    shape: Tuple[int, int, int], in_itemsize: int, out_itemsize: int
) -> int:
    """VMEM footprint of the fused two-step kernel: input ring (3) + its
    pipeline (2), intermediate ring (3), output pipeline (2)."""
    ny, nz = shape[1], shape[2]
    plane_a = _round_up(ny + 4, _SUBLANE) * _round_up(nz + 4, _LANE) * in_itemsize
    plane_b = _round_up(ny + 2, _SUBLANE) * _round_up(nz + 2, _LANE) * in_itemsize
    plane_o = _round_up(ny, _SUBLANE) * _round_up(nz, _LANE) * out_itemsize
    return 5 * plane_a + 3 * plane_b + 2 * plane_o


def stream2_supported(
    shape: Tuple[int, int, int],
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
) -> bool:
    ny, nz = shape[1], shape[2]
    return (
        _stream2_vmem_bytes(shape, in_itemsize, out_itemsize)
        <= _FUSED_STREAM_VMEM_BUDGET
        and _tap_stack_bytes(ny + 2, nz + 2, n_taps, compute_itemsize)
        <= _TAP_STACK_BUDGET
    )


def _plane_taps(plane_values, taps_flat, ny, nz, compute_dtype):
    """Apply the 3x3x3 taps to a dict of three x-planes, producing the
    (ny, nz) update of the middle plane's interior window, in the canonical
    core.stencils.accumulate_taps order (shared with the jnp path so
    cross-implementation comparisons agree to FMA rounding)."""
    cache = {}

    def term(di, dj, dk):
        if di == "xsum":
            if "p" not in cache:
                cache["p"] = plane_values[-1] + plane_values[1]
            src = cache["p"]
        else:
            src = plane_values[di]
        if dj == "ysum":
            key = ("ys", di)
            if key not in cache:  # (ny, nz+2)
                cache[key] = src[0:ny] + src[2 : 2 + ny]
            return cache[key][:, 1 + dk : 1 + dk + nz]
        return src[1 + dj : 1 + dj + ny, 1 + dk : 1 + dk + nz]

    return accumulate_taps(taps_flat, term, compute_dtype)


def _stream2_kernel(
    in_ref,
    out_ref,
    ring_a,
    ring_b,
    *,
    taps_flat,
    nx,
    ny,
    nz,
    compute_dtype,
    storage_dtype,
    out_dtype,
    periodic,
    bc_value,
    axis_names,
):
    """Fused two-update streaming stencil (temporal blocking).

    Grid step i: (a) load width-2-padded input plane i into a 3-slot ring;
    (b) once 3 input planes are resident, compute intermediate plane
    m = i-2 — one ghost ring wide, (ny+2, nz+2) — into a second ring,
    pinning Dirichlet domain-ghost cells to bc_value exactly as the unfused
    sequence would (edge-ness per axis comes from lax.axis_index, so the
    same kernel serves single-device and interior/edge shards); (c) once 3
    intermediate planes exist, emit output plane o = i-4. Both updates
    happen per HBM sweep: bytes/update halve vs the single-step kernel.
    """
    i = pl.program_id(0)
    bc = compute_dtype(bc_value)

    def edges(axis_name):
        idx = jax.lax.axis_index(axis_name)
        size = jax.lax.axis_size(axis_name)
        return idx == 0, idx == size - 1

    for k in range(3):

        @pl.when(jax.lax.rem(i, 3) == k)
        def _load(k=k):
            ring_a[k] = in_ref[0]

    # (b) intermediate plane m = i-2 from input planes (i-2, i-1, i).
    for k in range(3):  # k == i % 3

        @pl.when(jnp.logical_and(i >= 2, jax.lax.rem(i, 3) == k))
        def _mid(k=k):
            planes = {
                -1: ring_a[(k + 1) % 3].astype(compute_dtype),
                0: ring_a[(k + 2) % 3].astype(compute_dtype),
                1: ring_a[k].astype(compute_dtype),
            }
            mid = _plane_taps(planes, taps_flat, ny + 2, nz + 2, compute_dtype)
            if not periodic:
                m = i - 2
                x_lo, x_hi = edges(axis_names[0])
                y_lo, y_hi = edges(axis_names[1])
                z_lo, z_hi = edges(axis_names[2])
                ghost_plane = jnp.logical_or(
                    jnp.logical_and(m == 0, x_lo),
                    jnp.logical_and(m == nx + 1, x_hi),
                )
                row = jax.lax.broadcasted_iota(jnp.int32, (ny + 2, 1), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (1, nz + 2), 1)
                ring = jnp.logical_or(
                    jnp.logical_or(
                        jnp.logical_and(row == 0, y_lo),
                        jnp.logical_and(row == ny + 1, y_hi),
                    ),
                    jnp.logical_or(
                        jnp.logical_and(col == 0, z_lo),
                        jnp.logical_and(col == nz + 1, z_hi),
                    ),
                )
                mid = jnp.where(jnp.logical_or(ghost_plane, ring), bc, mid)
            # round-trip through storage dtype so fused == unfused bitwise
            ring_b[(k + 1) % 3] = mid.astype(storage_dtype)  # slot (i-2)%3

    # (c) output plane o = i-4 from intermediate planes (i-4, i-3, i-2).
    for k in range(3):  # k == i % 3; (i-4)%3 == (k+2)%3, (i-3)%3 == k

        @pl.when(jnp.logical_and(i >= 4, jax.lax.rem(i, 3) == k))
        def _out(k=k):
            planes = {
                -1: ring_b[(k + 2) % 3].astype(compute_dtype),
                0: ring_b[k].astype(compute_dtype),
                1: ring_b[(k + 1) % 3].astype(compute_dtype),
            }
            out_ref[0] = _plane_taps(
                planes, taps_flat, ny, nz, compute_dtype
            ).astype(out_dtype)


def apply_taps_pallas_stream2(
    up2: jax.Array,
    taps: np.ndarray,
    mesh_axis_names=("x", "y", "z"),
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused two-update Pallas stencil: width-2 ghost-padded
    (nx+4, ny+4, nz+4) block in, (nx, ny, nz) double-updated interior out.
    Must run inside shard_map over mesh_axis_names (size-1 axes included) so
    the kernel can detect domain edges for Dirichlet ghost pinning.

    NOTE: production dispatch (parallel.step._fused_streamk_fn) now routes
    tb=2 through :func:`apply_taps_pallas_streamk` with k=2 — the same
    ring structure and slot arithmetic, generalized. This specialization
    stays as the readable two-stage form and the cross-check the streamk
    tests certify against."""
    nx, ny, nz = up2.shape[0] - 4, up2.shape[1] - 4, up2.shape[2] - 4
    out_dtype = out_dtype or up2.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    kernel = functools.partial(
        _stream2_kernel,
        taps_flat=flat,
        nx=nx,
        ny=ny,
        nz=nz,
        compute_dtype=compute_dtype,
        storage_dtype=up2.dtype,
        out_dtype=jnp.dtype(out_dtype),
        periodic=periodic,
        bc_value=bc_value,
        axis_names=tuple(mesh_axis_names),
    )
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.stream2",
        grid=(nx + 4,),
        in_specs=[pl.BlockSpec((1, ny + 4, nz + 4), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, ny, nz), lambda i: (jnp.maximum(i - 4, 0), 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((3, ny + 4, nz + 4), up2.dtype),
            pltpu.VMEM((3, ny + 2, nz + 2), up2.dtype),
        ],
        cost_estimate=pl.CostEstimate(
            # RAW flops (the streamk convention): the mid stage sweeps the
            # one-ring-padded volume, so the recompute trapezoid is what
            # executes — obs/perf/roofline discounts by the analytic frac
            # to get effective flops, which double-counts if this
            # estimate were effective-only
            flops=2 * len(flat)
            * ((nx + 2) * (ny + 2) * (nz + 2) + nx * ny * nz),
            bytes_accessed=(nx + 4) * (ny + 4) * (nz + 4) * up2.dtype.itemsize
            + nx * ny * nz * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(up2)


def _streamk_vmem_bytes(
    shape: Tuple[int, int, int], k: int, in_itemsize: int, out_itemsize: int
) -> int:
    """VMEM footprint of the fused k-sweep kernel: width-k input ring (3)
    + its pipeline (2), one 3-slot intermediate ring per inner stage
    (widths shrink by one ghost ring per stage), output pipeline (2)."""
    ny, nz = shape[1], shape[2]

    def plane(r):
        return (
            _round_up(ny + 2 * r, _SUBLANE)
            * _round_up(nz + 2 * r, _LANE)
            * in_itemsize
        )

    mids = sum(3 * plane(r) for r in range(1, k))  # stages 1..k-1
    plane_o = _round_up(ny, _SUBLANE) * _round_up(nz, _LANE) * out_itemsize
    return 5 * plane(k) + mids + 2 * plane_o


def streamk_supported(
    shape: Tuple[int, int, int],
    k: int,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
) -> bool:
    """Can the fused k-sweep streaming kernel run a (nx, ny, nz) local
    block? Mirrors stream2_supported's two ceilings: the explicit
    ring/pipeline buffers and Mosaic's scoped stack for the widest
    emitted plane (stage 1's, carrying k-1 ghost rings)."""
    if k < 2:
        return False
    ny, nz = shape[1], shape[2]
    return (
        min(shape) >= k
        and _streamk_vmem_bytes(shape, k, in_itemsize, out_itemsize)
        <= _FUSED_STREAM_VMEM_BUDGET
        and _tap_stack_bytes(
            ny + 2 * (k - 1), nz + 2 * (k - 1), n_taps, compute_itemsize
        )
        <= _TAP_STACK_BUDGET
    )


def _streamk_kernel(
    in_ref,
    out_ref,
    *rings,
    taps_flat,
    k,
    nx,
    ny,
    nz,
    compute_dtype,
    storage_dtype,
    out_dtype,
    periodic,
    bc_value,
    axis_names,
):
    """Fused k-update streaming stencil (deep temporal blocking) — the
    k-sweep generalization of _stream2_kernel.

    Uniform coordinate scheme: stage 0 is the width-k-padded input stream
    (planes 0 .. nx+2k-1), stage j (1 <= j <= k) holds planes carrying
    r = k-j ghost rings, each (ny+2r, nz+2r); stage-j plane p lives in
    ring slot p % 3, and at grid step i stage j produces its plane
    i - 2j from stage j-1's planes (i-2j, i-2j+1, i-2j+2) — the standard
    3-plane emit shifted by 2 per stage, so the trapezoid of shrinking
    ghost rings streams through VMEM with every HBM plane fetched once
    and the k updates fused into one sweep. Stage k writes out_ref.

    Dirichlet intermediates are pinned exactly as the unfused sequence's
    _fill_mid_ghosts sees them — every cell whose GLOBAL index falls
    outside the domain (up to r rings deep at domain-edge shards) holds
    bc_value, and each intermediate round-trips through the storage
    dtype — so fused == unfused bitwise on the jnp contract.
    """
    i = pl.program_id(0)
    bc = compute_dtype(bc_value)

    def edges(axis_name):
        idx = jax.lax.axis_index(axis_name)
        size = jax.lax.axis_size(axis_name)
        return idx == 0, idx == size - 1

    x_lo, x_hi = edges(axis_names[0])
    y_lo, y_hi = edges(axis_names[1])
    z_lo, z_hi = edges(axis_names[2])

    for kk in range(3):

        @pl.when(jax.lax.rem(i, 3) == kk)
        def _load(kk=kk):
            rings[0][kk] = in_ref[0]

    def _pin_out_of_domain(plane, m, r):
        """bc-pin the out-of-domain cells of a stage plane: plane index
        ``m`` (stage coords: local x = m - r), in-plane rows/cols with
        local y/z outside [0, n), at domain-edge shards only."""
        ghost_plane = jnp.logical_or(
            jnp.logical_and(m < r, x_lo),
            jnp.logical_and(m >= nx + r, x_hi),
        )
        row = jax.lax.broadcasted_iota(jnp.int32, (ny + 2 * r, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, nz + 2 * r), 1)
        ring = jnp.logical_or(
            jnp.logical_or(
                jnp.logical_and(row < r, y_lo),
                jnp.logical_and(row >= ny + r, y_hi),
            ),
            jnp.logical_or(
                jnp.logical_and(col < r, z_lo),
                jnp.logical_and(col >= nz + r, z_hi),
            ),
        )
        return jnp.where(jnp.logical_or(ghost_plane, ring), bc, plane)

    for j in range(1, k + 1):
        r = k - j  # ghost rings the stage-j planes still carry
        fire = i >= 2 * j
        for kk in range(3):  # kk == i % 3

            @pl.when(jnp.logical_and(fire, jax.lax.rem(i, 3) == kk))
            def _stage(j=j, r=r, kk=kk):
                # stage j-1 planes (i-2j, i-2j+1, i-2j+2) in slots p%3
                slots = {
                    -1: (kk + j) % 3,
                    0: (kk + j + 1) % 3,
                    1: (kk + j + 2) % 3,
                }
                src = rings[j - 1]
                planes = {
                    d: src[s].astype(compute_dtype) for d, s in slots.items()
                }
                res = _plane_taps(
                    planes, taps_flat, ny + 2 * r, nz + 2 * r, compute_dtype
                )
                if j == k:
                    out_ref[0] = res.astype(out_dtype)
                else:
                    if not periodic:
                        res = _pin_out_of_domain(res, i - 2 * j, r)
                    # round-trip through storage dtype so fused == unfused
                    rings[j][(kk + j) % 3] = res.astype(storage_dtype)


def streamk_cost_estimate(
    local_shape: Tuple[int, int, int],
    k: int,
    n_taps: int,
    in_itemsize: int,
    out_itemsize: int,
) -> Tuple[int, int]:
    """(flops, bytes_accessed) of one fused k-sweep call: the RAW
    trapezoid — stage j applies the taps over the (n+2r)^3 extent it
    emits (r = k-j shrinking ghost rings), which is what the chip
    executes; bytes are one width-k padded read + one interior write."""
    nx, ny, nz = local_shape
    flops = sum(
        2
        * n_taps
        * (nx + 2 * r)
        * (ny + 2 * r)
        * (nz + 2 * r)
        for r in range(k)
    )
    bytes_accessed = (
        (nx + 2 * k) * (ny + 2 * k) * (nz + 2 * k) * in_itemsize
        + nx * ny * nz * out_itemsize
    )
    return flops, bytes_accessed


def apply_taps_pallas_streamk(
    upk: jax.Array,
    taps: np.ndarray,
    k: int,
    mesh_axis_names=("x", "y", "z"),
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused k-update Pallas stencil: width-k ghost-padded
    (nx+2k, ny+2k, nz+2k) block in, (nx, ny, nz) k-times-updated interior
    out — one HBM sweep for k temporal-blocking updates (bytes/update cut
    k-fold vs the single-step kernel, at the cost of the shrinking-ring
    recompute trapezoid; see streamk_cost_estimate). Must run inside
    shard_map over mesh_axis_names (size-1 axes included) so the kernel
    can detect domain edges for Dirichlet ghost pinning."""
    if k < 2:
        raise ValueError(f"streamk kernel wants k >= 2, got {k}")
    nx, ny, nz = (s - 2 * k for s in upk.shape)
    out_dtype = out_dtype or upk.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    kernel = functools.partial(
        _streamk_kernel,
        taps_flat=flat,
        k=k,
        nx=nx,
        ny=ny,
        nz=nz,
        compute_dtype=compute_dtype,
        storage_dtype=upk.dtype,
        out_dtype=jnp.dtype(out_dtype),
        periodic=periodic,
        bc_value=bc_value,
        axis_names=tuple(mesh_axis_names),
    )
    flops, bytes_accessed = streamk_cost_estimate(
        (nx, ny, nz), k, len(flat), upk.dtype.itemsize,
        jnp.dtype(out_dtype).itemsize,
    )
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.streamk",
        grid=(nx + 2 * k,),
        in_specs=[
            pl.BlockSpec((1, ny + 2 * k, nz + 2 * k), lambda i: (i, 0, 0))
        ],
        out_specs=pl.BlockSpec(
            (1, ny, nz), lambda i: (jnp.maximum(i - 2 * k, 0), 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((3, ny + 2 * r, nz + 2 * r), upk.dtype)
            for r in range(k, 0, -1)  # input ring (r=k) + stages 1..k-1
        ],
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=bytes_accessed,
            transcendentals=0,
        ),
        interpret=interpret,
    )(upk)


def _stencil_kernel(in_ref, out_ref, *, taps, bx, by, nz, compute_dtype, out_dtype):
    """One (bx, by, nz) output tile from a (bx+2, by+2, nz+2) input window.

    The tap loop unrolls at trace time; each term is a static shifted slice
    of the VMEM window, so Mosaic sees a chain of vector FMAs (z shifts are
    lane shifts, y shifts sublane shifts, x shifts plane selects).
    """
    flat = tuple((di, dj, dk, w) for (di, dj, dk), w in taps)
    cache = {}

    def plane(di):  # (bx, by+2, nz+2) in compute dtype; factored dis only
        if di == "xsum":
            if "p" not in cache:
                cache["p"] = in_ref[0:bx].astype(compute_dtype) + in_ref[
                    2 : 2 + bx
                ].astype(compute_dtype)
            return cache["p"]
        assert di == 0, di
        if "m" not in cache:
            cache["m"] = in_ref[1 : 1 + bx].astype(compute_dtype)
        return cache["m"]

    def term(di, dj, dk):
        if dj == "ysum":  # only emitted for the factored planes (xsum, 0)
            key = ("ys", di)
            if key not in cache:  # (bx, by, nz+2)
                src = plane(di)
                cache[key] = src[:, 0:by] + src[:, 2 : 2 + by]
            return cache[key][:, :, 1 + dk : 1 + dk + nz]
        if di in ("xsum", 0):
            return plane(di)[:, 1 + dj : 1 + dj + by, 1 + dk : 1 + dk + nz]
        return in_ref[
            1 + di : 1 + di + bx, 1 + dj : 1 + dj + by, 1 + dk : 1 + dk + nz
        ].astype(compute_dtype)

    out_ref[:] = accumulate_taps(flat, term, compute_dtype).astype(out_dtype)


def apply_taps_pallas(
    up: jax.Array,
    taps: np.ndarray,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas analogue of ops.stencil_jnp.apply_taps_padded: ghost-padded
    (nx+2, ny+2, nz+2) block in, (nx, ny, nz) interior update out.

    Dispatches to the streaming ring kernel (every HBM plane fetched once)
    when its VMEM ring fits, else the windowed x-slab kernel."""
    nx, ny, nz = up.shape[0] - 2, up.shape[1] - 2, up.shape[2] - 2
    out_dtype = out_dtype or up.dtype
    tap_list = tuple(nonzero_taps(taps))
    c_item = jnp.dtype(compute_dtype).itemsize
    if stream_supported(
        (nx, ny, nz), up.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        n_taps=effective_num_taps(taps), compute_itemsize=c_item,
    ):
        return apply_taps_pallas_stream(
            up, taps, compute_dtype=compute_dtype, out_dtype=out_dtype,
            interpret=interpret,
        )
    compute_dtype = jnp.dtype(compute_dtype).type
    blocks = choose_blocks(
        (nx, ny, nz), up.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        n_taps=effective_num_taps(taps), compute_itemsize=c_item,
    )
    if blocks is None:
        raise ValueError(f"no VMEM-feasible tiling for local shape {(nx, ny, nz)}")
    bx, by = blocks

    kernel = functools.partial(
        _stencil_kernel,
        taps=tap_list,
        bx=bx,
        by=by,
        nz=nz,
        compute_dtype=compute_dtype,
        out_dtype=jnp.dtype(out_dtype),
    )
    flops_per_cell = 2 * len(tap_list)
    # y/z windows are full-extent with literal-0 offsets (see choose_blocks);
    # the grid walks x only.
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.taps",
        grid=(nx // bx,),
        in_specs=[
            pl.BlockSpec(
                (_Element(bx + 2), _Element(by + 2), _Element(nz + 2)),
                lambda i: (i * bx, 0, 0),
            )
        ],
        out_specs=pl.BlockSpec((bx, by, nz), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops_per_cell * nx * ny * nz,
            bytes_accessed=(nx + 2) * (ny + 2) * (nz + 2) * up.dtype.itemsize
            + nx * ny * nz * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(up)


def make_pallas_compute(cfg: SolverConfig, interpret: bool = False):
    """Build the LocalCompute callable for parallel.step: same signature as
    apply_taps_padded, kernel-backed."""

    def compute(up, taps, compute_dtype=jnp.float32, out_dtype=None):
        return apply_taps_pallas(
            up, taps, compute_dtype=compute_dtype, out_dtype=out_dtype,
            interpret=interpret,
        )

    return compute
