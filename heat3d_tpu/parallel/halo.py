"""Ghost-cell halo exchange over ICI — the CUDA-aware MPI_Isend/Irecv analogue.

Reference parity (SURVEY.md §2 C2, §3.2): per face the reference packs the
boundary layer into a contiguous device buffer, posts CUDA-aware
Isend/Irecv with device pointers, Waitalls, and unpacks into the ghost
layer. The TPU-native formulation is one ``lax.ppermute`` per (axis,
direction) inside ``shard_map``: XLA compiles each permute into an ICI DMA
between neighbor chips — pack/unpack, transport, and sync all collapse
into the collective.

Key structural property: exchanges are **axis-ordered** (x, then y, then
z), each operating on the array *already padded by previous axes*. The face
slabs therefore carry prior ghosts with them, which propagates edge- and
corner-ghost data in 3 exchanges instead of 26 — required by the 27-point
stencil (SURVEY.md §7.3 item 1) and exactly equivalent to a global
pad-then-shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from heat3d_tpu.core.config import BoundaryCondition, MeshConfig
from heat3d_tpu.obs.trace import named_phase


def shift_perm(n: int, direction: int, periodic: bool):
    """Permutation (source, dest) pairs shifting data one step along a ring
    of size n. ``direction=+1``: device i's slab goes to device i+1 (so the
    receiver sees its *low*-side neighbor's face). Non-periodic drops the
    wrap pair; undelivered ppermute outputs are zero-filled, which is the
    Dirichlet-0 ghost for free (nonzero BC values are patched by the
    caller).

    Public because it IS the mesh neighbor graph: the IR collective-
    topology checker (``heat3d lint --ir``, analysis/ir/collectives.py)
    proves every traced ppermute permutation equals one of these shifts
    — verifying the compiled exchange against the same source of truth
    the exchange is built from."""
    if periodic:
        return [(i, (i + direction) % n) for i in range(n)]
    if direction > 0:
        return [(i, i + 1) for i in range(n - 1)]
    return [(i, i - 1) for i in range(1, n)]


def exchange_axis(
    u: jax.Array,
    axis: int,
    axis_name: str,
    axis_size: int,
    periodic: bool,
    bc_value: float = 0.0,
    width: int = 1,
    ghosts_fn=None,
) -> jax.Array:
    """Pad local block ``u`` with ``width`` ghost layers along ``axis``,
    filled from the neighbors along mesh axis ``axis_name`` (or the BC at
    the domain boundary). Must run inside shard_map. Returns u grown by
    2*width on ``axis``. width > 1 serves temporal blocking (several stencil
    applications per exchange — fewer, larger messages).

    ``ghosts_fn`` overrides the communication core (an
    :class:`~heat3d_tpu.parallel.plan.ExchangePlan` supplies its
    precomputed-permutation or partitioned-sub-block form); signature
    ``(lo_face, hi_face, axis, axis_name, axis_size, periodic, bc_value)``,
    default :func:`axis_ghosts` (which ignores ``axis``)."""
    n = u.shape[axis]
    if n < width:
        raise ValueError(
            f"halo width {width} exceeds local extent {n} on axis {axis}"
        )
    # per-axis scope nested under heat3d.halo_exchange: trace tools can
    # attribute ICI time to the axis whose permutes carry it
    with named_phase(f"halo.{axis_name}"):
        lo_face = lax.slice_in_dim(u, 0, width, axis=axis)
        hi_face = lax.slice_in_dim(u, n - width, n, axis=axis)
        if ghosts_fn is None:
            ghost_lo, ghost_hi = axis_ghosts(
                lo_face, hi_face, axis_name, axis_size, periodic, bc_value
            )
        else:
            ghost_lo, ghost_hi = ghosts_fn(
                lo_face, hi_face, axis, axis_name, axis_size, periodic,
                bc_value,
            )
        return lax.concatenate([ghost_lo, u, ghost_hi], dimension=axis)


def axis_ghosts(
    lo_face: jax.Array,
    hi_face: jax.Array,
    axis_name: str,
    axis_size: int,
    periodic: bool,
    bc_value: float = 0.0,
    perms=None,
):
    """The communication core of one axis exchange: given my two boundary
    faces, return my two ghost faces (neighbor data, wrap, or the BC).
    Must run inside shard_map. ``perms`` takes precomputed
    ``(shift_perm(+1), shift_perm(-1))`` pairs (an
    :class:`~heat3d_tpu.parallel.plan.ExchangePlan` builds them once per
    run instead of once per trace); ``None`` derives them in place —
    identical values either way."""
    if axis_size == 1 and periodic:
        # self-wrap: my own faces are my ghosts
        return hi_face, lo_face
    if axis_size == 1:
        return (
            jnp.full_like(lo_face, bc_value),
            jnp.full_like(hi_face, bc_value),
        )
    if perms is None:
        perm_up = shift_perm(axis_size, +1, periodic)
        perm_down = shift_perm(axis_size, -1, periodic)
    else:
        perm_up, perm_down = perms
    # per-direction scopes nested under halo.<axis>: each ppermute's
    # device time attributes to the LINK that carried it ("lo" = the
    # transfer filling my low ghost — the same link key the comm-probe
    # rows and the link_straggler detector use; normalize_phase folds
    # halo.* back into halo_exchange for the coarse joins)
    with named_phase(f"halo.{axis_name}.lo"):
        # my low ghost = low neighbor's high face: shift high faces "up" (+1)
        ghost_lo = lax.ppermute(hi_face, axis_name, perm_up)
    with named_phase(f"halo.{axis_name}.hi"):
        # my high ghost = high neighbor's low face: shift low faces "down" (-1)
        ghost_hi = lax.ppermute(lo_face, axis_name, perm_down)
    return substitute_domain_bc(
        ghost_lo, ghost_hi, axis_name, axis_size, periodic, bc_value
    )


def substitute_domain_bc(
    ghost_lo: jax.Array,
    ghost_hi: jax.Array,
    axis_name: str,
    axis_size: int,
    periodic: bool,
    bc_value=0.0,
):
    """Domain-edge BC substitution over freshly exchanged ghost faces —
    the ONE tail every ppermute-built ghost pair (monolithic or
    partitioned sub-block assembly, parallel/plan.py) runs, so the edge
    semantics cannot diverge between plan modes. bc_value may be a
    TRACED scalar (the batched ensemble path threads a per-member
    boundary value through vmap — serve/ensemble.py); the 0.0 fast path
    then cannot be decided at trace time, and substituting
    unconditionally is value-identical (undelivered ppermute outputs are
    zero-filled, so where(edge, 0.0, ghost) == ghost)."""
    if not periodic and (isinstance(bc_value, jax.Array) or bc_value != 0.0):
        idx = lax.axis_index(axis_name)
        ghost_lo = jnp.where(idx == 0, jnp.full_like(ghost_lo, bc_value), ghost_lo)
        ghost_hi = jnp.where(
            idx == axis_size - 1, jnp.full_like(ghost_hi, bc_value), ghost_hi
        )
    return ghost_lo, ghost_hi


def exchange_halo(
    u: jax.Array,
    mesh_cfg: MeshConfig,
    bc: BoundaryCondition,
    bc_value: float = 0.0,
    width: int = 1,
    ghosts_fn=None,
) -> jax.Array:
    """Full 3D ghost exchange: local (nx,ny,nz) -> (nx+2w,ny+2w,nz+2w),
    ghosts filled from mesh neighbors / the boundary condition. Axis-ordered
    so the result equals a global pad-then-shard (corner ghosts included).
    Must run inside shard_map over the mesh in ``mesh_cfg``. ``ghosts_fn``
    swaps the per-axis communication core (see :func:`exchange_axis`)."""
    periodic = bc is BoundaryCondition.PERIODIC
    for axis, (axis_name, axis_size) in enumerate(
        zip(mesh_cfg.axis_names, mesh_cfg.shape)
    ):
        u = exchange_axis(
            u, axis, axis_name, axis_size, periodic, bc_value, width,
            ghosts_fn=ghosts_fn,
        )
    return u


def exchange_halo_pairwise(
    u: jax.Array,
    mesh_cfg: MeshConfig,
    bc: BoundaryCondition,
    bc_value: float = 0.0,
    width: int = 1,
    ghosts_fn=None,
) -> jax.Array:
    """Neighbor-pairwise ghost exchange: all six face ppermutes issued
    concurrently from the RAW boundary faces, with no cross-axis data
    dependence — the stagger-tolerant ordering (a host arriving one
    exchange latency late delays only its own pairs, not a 3-deep axis
    chain; ROADMAP "skew-aware halo tuning"). The price: corner and edge
    ghost regions carry ``bc_value`` instead of diagonal-neighbor data,
    so this ordering is only valid for face-only stencils (7pt) at
    ``time_blocking <= 1`` — ``SolverConfig.__post_init__`` enforces it.
    For those configs the padded result is value-identical to
    :func:`exchange_halo` on every cell the stencil reads (a step's
    output may still differ in final-ulp rounding: the differently
    shaped pad/concat graph can change XLA's fusion/FMA contraction).
    Must run inside shard_map over the mesh in ``mesh_cfg``."""
    periodic = bc is BoundaryCondition.PERIODIC
    with named_phase("halo_exchange"):
        ghosts = []
        for axis, (name, size) in enumerate(
            zip(mesh_cfg.axis_names, mesh_cfg.shape)
        ):
            n = u.shape[axis]
            if n < width:
                raise ValueError(
                    f"halo width {width} exceeds local extent {n} on "
                    f"axis {axis}"
                )
            lo = lax.slice_in_dim(u, 0, width, axis=axis)
            hi = lax.slice_in_dim(u, n - width, n, axis=axis)
            # every axis_ghosts call reads only the RAW faces of u: the
            # six permutes have no data dependence on each other, so
            # XLA is free to run them all concurrently
            if ghosts_fn is None:
                ghosts.append(
                    axis_ghosts(lo, hi, name, size, periodic, bc_value)
                )
            else:
                ghosts.append(
                    ghosts_fn(lo, hi, axis, name, size, periodic, bc_value)
                )
        out = u
        for axis, (glo, ghi) in enumerate(ghosts):
            # earlier axes already grew `out` by 2*width; the raw-face
            # ghosts are padded with bc_value over those extents (the
            # corner/edge zones a face-only stencil never reads)
            pads = [
                (width, width) if prev < axis else (0, 0)
                for prev in range(3)
            ]
            if any(p != (0, 0) for p in pads):
                glo = jnp.pad(glo, pads, constant_values=bc_value)
                ghi = jnp.pad(ghi, pads, constant_values=bc_value)
            out = lax.concatenate([glo, out, ghi], dimension=axis)
        return out


def exchange_halo_faces(
    u: jax.Array,
    mesh_cfg: MeshConfig,
    bc: BoundaryCondition,
    bc_value: float = 0.0,
    width: int = 1,
):
    """Faces-only ghost exchange: the six width-``w`` ghost faces of the
    axis-ordered exchange WITHOUT materializing the padded volume (whose
    concatenate is a full read+write of the field — the dominant HBM cost
    of the exchange path; see ops/stencil_pallas_direct.py).

    Returns ``(xlo, xhi, ylo, yhi, zlo, zhi)`` with the progressive
    extension the axis ordering implies: x faces are raw (w, ny, nz), y
    faces x-extended (nx+2w, w, nz), z faces x+y-extended
    (nx+2w, ny+2w, w) — exactly the slices the width-w padded array would
    have, corners included (the later-axis send faces are built by
    concatenating the earlier ghosts onto the boundary slab, which is how
    corner data propagates here). Must run inside shard_map over the mesh
    in ``mesh_cfg``."""
    periodic = bc is BoundaryCondition.PERIODIC
    names, sizes = mesh_cfg.axis_names, mesh_cfg.shape
    w = width
    if min(u.shape) < w:
        raise ValueError(
            f"halo width {w} exceeds a local extent of {u.shape}"
        )
    with named_phase("halo_exchange"):
        return _exchange_halo_faces(u, names, sizes, periodic, bc_value, w)


def _exchange_halo_faces(u, names, sizes, periodic, bc_value, w):
    xlo, xhi = axis_ghosts(
        u[:w], u[-w:], names[0], sizes[0], periodic, bc_value
    )
    # y send faces carry the x ghosts (corner propagation)
    y_lo_send = lax.concatenate([xlo[:, :w], u[:, :w], xhi[:, :w]], 0)
    y_hi_send = lax.concatenate([xlo[:, -w:], u[:, -w:], xhi[:, -w:]], 0)
    ylo, yhi = axis_ghosts(
        y_lo_send, y_hi_send, names[1], sizes[1], periodic, bc_value
    )
    # z send faces carry the x AND y ghosts
    mid_lo = lax.concatenate([xlo[:, :, :w], u[:, :, :w], xhi[:, :, :w]], 0)
    mid_hi = lax.concatenate([xlo[:, :, -w:], u[:, :, -w:], xhi[:, :, -w:]], 0)
    z_lo_send = lax.concatenate([ylo[:, :, :w], mid_lo, yhi[:, :, :w]], 1)
    z_hi_send = lax.concatenate([ylo[:, :, -w:], mid_hi, yhi[:, :, -w:]], 1)
    zlo, zhi = axis_ghosts(
        z_lo_send, z_hi_send, names[2], sizes[2], periodic, bc_value
    )
    return xlo, xhi, ylo, yhi, zlo, zhi
