"""The distributed stencil step: shard_map(halo exchange -> stencil -> psum).

Reference parity (SURVEY.md §3.2): one reference iteration is
``exchange_halos(u); jacobi_step<<<...>>>(u_new, u); swap; [residual +
MPI_Allreduce]``. Here the whole iteration is one SPMD program: ghost
exchange (ppermute), tap application (jnp slices or the Pallas kernel),
and the fp32 residual psum, all inside ``jax.shard_map`` over the
(x, y, z) mesh. The time loop wraps it in ``lax.fori_loop`` under jit, so
Python launches the entire run once (SURVEY.md §1 L4 mapping).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from heat3d_tpu.core.config import (
    BoundaryCondition,
    MeshConfig,
    Precision,
    SolverConfig,
)
from heat3d_tpu.core.stencils import STENCILS, effective_num_taps
from heat3d_tpu.obs.trace import named_phase, scoped
from heat3d_tpu.ops.stencil_jnp import apply_taps_padded, residual_sumsq
from jax import shard_map

# Local compute on a ghost-padded block: (up, taps, compute_dtype, out_dtype) -> interior
LocalCompute = Callable[..., jax.Array]

_logged_paths: set = set()


def _log_step_path_once(msg: str) -> None:
    """INFO-log a step-path selection once per process (make_step_fn is
    built several times per solver — step / residual / converge)."""
    if msg not in _logged_paths:
        _logged_paths.add(msg)
        from heat3d_tpu.utils.logging import get_logger

        get_logger(__name__).info("%s", msg)


def _routed(fn, kernel, interpret=False, layout=None, residual=None):
    """Record on a built step function the route its builder dispatched:
    ``kernel`` is the Pallas kernel family ('direct', 'streamk'), or None
    for the exchange path with the padded-block compute; ``interpret``
    says the kernel runs off-TPU under HEAT3D_DIRECT_INTERPRET;
    ``layout`` is the direct kernels' VMEM plane layout, 'aligned' or
    'framed' (ops.stencil_pallas_direct.direct_layout). Every other kernel
    family builds framed planes, and there is no layout without a kernel.
    A step with a residual also records where the residual is summed:
    ``residual`` 'kernel' (the direct kernel's partial sums) or 'reduce'
    (``residual_sumsq`` over both fields after the update).
    The CLI summary, bench rows and chip_smoke read this one record."""
    fn.route = {
        "kernel": kernel,
        "interpret": bool(interpret),
        "layout": None if kernel is None else layout or "framed",
    }
    if residual is not None:
        fn.route["residual"] = residual
    return fn


def _solver_taps(cfg: SolverConfig) -> np.ndarray:
    """The config's update taps, via the declarative equation frontend
    (heat3d_tpu.eqn): the spec compiler lowers ``cfg.equation`` onto the
    stencil footprint — bit-identical to the old inline ``stencil_taps``
    call for the heat family (docs/EQUATIONS.md; ``HEAT3D_EQN_LEGACY=1``
    inside eqn keeps the verbatim legacy derivation as the parity arm)."""
    from heat3d_tpu import eqn

    return eqn.solver_taps(cfg)


def _pin_padding(
    u_new: jax.Array, cfg: SolverConfig, bc_value=None
) -> jax.Array:
    """For uneven decompositions, re-pin storage-padding cells (global index
    >= grid extent) to bc_value after each update. Real cells adjacent to
    the true boundary then read bc_value from their padded neighbors —
    exactly the Dirichlet ghost — and padded cells contribute zero to the
    residual (old == new == bc_value). Must run inside shard_map.
    ``bc_value`` overrides the config's (may be a TRACED scalar — the
    batched ensemble path's per-member boundary value, serve/ensemble)."""
    if not cfg.is_padded:
        return u_new
    if bc_value is None:
        bc_value = cfg.stencil.bc_value
    mask = None
    for axis, (name, g, n) in enumerate(
        zip(cfg.mesh.axis_names, cfg.grid.shape, cfg.local_shape)
    ):
        if g == cfg.padded_shape[axis]:
            continue
        global_idx = lax.axis_index(name) * n + jnp.arange(n)
        shape = [1, 1, 1]
        shape[axis] = n
        m = (global_idx < g).reshape(shape)
        mask = m if mask is None else jnp.logical_and(mask, m)
    return jnp.where(mask, u_new, jnp.asarray(bc_value, u_new.dtype))


def exchange(
    u_local: jax.Array, cfg: SolverConfig, width: int = 1
) -> jax.Array:
    """Ghost exchange via this config's persistent :class:`ExchangePlan`
    (heat3d_tpu.parallel.plan): transport (cfg.halo), ordering
    (cfg.halo_order) and plan mode (cfg.halo_plan — monolithic face
    collectives or partitioned early-bird sub-block sends) are all
    resolved ONCE per (mesh, bc, width, knobs) and reused by every step,
    superstep, phase and bench program in the process. ``HEAT3D_NO_PLAN``
    falls back to the legacy ad-hoc dispatch (bitwise-identical on the
    monolithic path — the parity tests' reference arm). The
    ``heat3d.halo_exchange`` named scope brackets every transport so a
    profiler trace attributes the permutes/DMAs to OUR phase, not to raw
    XLA op names (scripts/summarize_trace.py groups on it)."""
    from heat3d_tpu.parallel.plan import exchange_with_plan

    with named_phase("halo_exchange"):
        return exchange_with_plan(u_local, cfg, width)


def _pin_outside_domain(
    arr: jax.Array, cfg: SolverConfig, local_indices, bc_value=None
) -> jax.Array:
    """Pin cells of ``arr`` whose GLOBAL index lies outside the domain to
    bc_value (Dirichlet; periodic has no out-of-domain cells — wrap ghosts
    are genuine). ``local_indices[a]`` gives each dim's local indices
    (local i maps to global device_start + i). Must run inside shard_map.
    ``bc_value`` overrides the config's (may be a TRACED scalar — the
    batched ensemble path's per-member boundary value, serve/ensemble)."""
    if cfg.stencil.bc is BoundaryCondition.PERIODIC:
        return arr
    if bc_value is None:
        bc_value = cfg.stencil.bc_value
    mask = None
    for axis, (name, g, n) in enumerate(
        zip(cfg.mesh.axis_names, cfg.grid.shape, cfg.local_shape)
    ):
        global_idx = lax.axis_index(name) * n + local_indices[axis]
        m = jnp.logical_and(global_idx >= 0, global_idx < g)
        shape = [1, 1, 1]
        shape[axis] = arr.shape[axis]
        m = m.reshape(shape)
        mask = m if mask is None else jnp.logical_and(mask, m)
    return jnp.where(mask, arr, jnp.asarray(bc_value, arr.dtype))


def _fill_mid_ghosts(
    mid: jax.Array, cfg: SolverConfig, rings: int = 1, bc_value=None
) -> jax.Array:
    """Between the applications of a temporally-blocked superstep, pin the
    cells of the ring-carrying intermediate that are NOT true interior
    cells — global domain ghosts (Dirichlet rings) and uneven-decomposition
    padding — back to bc_value, exactly as the unfused sequence sees them.
    ``mid`` carries ``rings`` ghost rings: local index i maps to global
    index device_start + i - rings."""
    return _pin_outside_domain(
        mid,
        cfg,
        [jnp.arange(-rings, n + rings) for n in cfg.local_shape],
        bc_value=bc_value,
    )


def _local_stepk(
    u_local: jax.Array,
    taps: np.ndarray,
    cfg: SolverConfig,
    compute_padded: LocalCompute,
) -> jax.Array:
    """One temporally-blocked superstep: ``k = cfg.time_blocking`` stencil
    updates per ghost exchange — the overlapping-halo trick (exchange
    width-k ghosts, apply the stencil k times; application j consumes the
    ring application j-1 produced). Cuts ICI messages per update k-fold at
    the cost of recomputing shrinking ghost rings."""
    k = cfg.time_blocking
    compute_dtype = jnp.dtype(cfg.precision.compute)
    out_dtype = jnp.dtype(cfg.precision.storage)
    cur = exchange(u_local, cfg, width=k)
    with named_phase("stencil"):
        for j in range(k):
            cur = compute_padded(
                cur, taps, compute_dtype=compute_dtype, out_dtype=out_dtype
            )
            rings = k - 1 - j  # ghost rings still carried by cur
            if rings > 0:
                cur = _fill_mid_ghosts(cur, cfg, rings)
        return _pin_padding(cur, cfg)


def _local_step(
    u_local: jax.Array,
    taps: np.ndarray,
    cfg: SolverConfig,
    compute_padded: LocalCompute,
) -> jax.Array:
    up = exchange(u_local, cfg)
    with named_phase("stencil"):
        u_new = compute_padded(
            up,
            taps,
            compute_dtype=jnp.dtype(cfg.precision.compute),
            out_dtype=jnp.dtype(cfg.precision.storage),
        )
        return _pin_padding(u_new, cfg)


def _kernel_env_gate(cfg: SolverConfig):
    """Shared dispatch gate for the Mosaic kernel routes: returns
    ``(ok, interpret)`` — ok=False when the config/env rules out any real
    kernel (backend, padding, platform), interpret=True when
    HEAT3D_DIRECT_INTERPRET routes the kernel through the Pallas
    interpreter off-TPU (tests)."""
    import os

    if cfg.backend not in ("pallas", "auto"):
        return False, False
    if cfg.is_padded:
        return False, False
    if cfg.halo_order != "axis":
        # the direct/streamk kernel families synthesize or patch ghosts
        # assuming axis-ordered corner propagation; the pairwise ordering
        # A/B is an EXCHANGE-path knob, so it pins the exchange path
        return False, False
    if cfg.halo_plan == "partitioned":
        # partitioned early-bird sends are likewise an exchange-path
        # structure (the kernels never issue per-face collectives to
        # partition) — the A/B must measure the exchange path, not
        # silently run a kernel that ignores the knob
        return False, False
    interpret = bool(os.environ.get("HEAT3D_DIRECT_INTERPRET"))
    forced = bool(os.environ.get("HEAT3D_DIRECT_FORCE"))
    if not interpret and not forced and jax.devices()[0].platform != "tpu":
        return False, False
    return True, interpret


def _direct_kernel_fn(
    cfg: SolverConfig, halo: int, multichip: bool = False,
    residual_dtype=None,
):
    """Return the BC-fused direct Pallas kernel for this config, or None.

    On a (1, 1, 1) mesh every shard boundary is a domain boundary, so the
    kernel can synthesize the ghosts in-register and skip the ghost-padded
    copy that ``exchange`` materializes (its concatenates are full-volume
    HBM writes) — halving (tb=1) or quartering (tb=2) traffic on the
    bandwidth-bound roofline. ``halo`` = updates fused per HBM sweep (1|2).

    With ``multichip=True`` (the faces+shells steps — _local_step_direct_faces
    for halo=1, _local_superstep_direct_faces for halo=2) any mesh
    qualifies: the kernel computes the bulk and the exchanged faces patch
    the shard-boundary shells.

    With ``residual_dtype`` (tb=1, one shard) the kernel also returns its
    change-residual partial sums in that dtype, and the gate budgets them.
    """
    import os

    if os.environ.get("HEAT3D_NO_DIRECT"):
        return None
    if not multichip and cfg.mesh.shape != (1, 1, 1):
        return None
    if cfg.halo != "ppermute":
        return None
    # overlap=True is satisfied BY the faces-direct step (the kernel has no
    # data dependence on the face ppermutes, so XLA runs them concurrently);
    # only the tb=2 superstep keeps its overlap mutual exclusion
    if cfg.overlap and halo != 1:
        return None
    # HEAT3D_DIRECT_INTERPRET exercises this dispatch path off-TPU (tests);
    # HEAT3D_DIRECT_FORCE selects the real (Mosaic) kernels off-TPU for
    # compile-only cross-lowering tests
    ok, interpret = _kernel_env_gate(cfg)
    if not ok:
        return None
    try:
        from heat3d_tpu.ops.stencil_pallas_direct import (
            apply_taps_direct,
            apply_taps_direct2,
            direct_supported,
        )
    except ImportError:
        return None
    itemsize = jnp.dtype(cfg.precision.storage).itemsize
    n_taps = effective_num_taps(STENCILS[cfg.stencil.kind].weights)
    c_item = jnp.dtype(cfg.precision.compute).itemsize
    # taps: weights suffice for the gate's mehrstellen predicate — the
    # update taps T = I + c*W decompose iff W does (affine in the center)
    partial_itemsize = (
        0 if residual_dtype is None else jnp.dtype(residual_dtype).itemsize
    )
    if not direct_supported(
        cfg.local_shape, halo, itemsize, itemsize, n_taps, c_item,
        taps=STENCILS[cfg.stencil.kind].weights,
        partial_itemsize=partial_itemsize,
    ):
        return None
    import functools

    kernel = apply_taps_direct if halo == 1 else apply_taps_direct2
    if residual_dtype is not None:
        kernel = functools.partial(kernel, residual_dtype=residual_dtype)
    return functools.partial(kernel, interpret=True) if interpret else kernel


def _padded_slab(
    u: jax.Array, faces, axis: int, start: int, w: int = 1,
    thickness: int = None,
) -> jax.Array:
    """``thickness``-thick slice [start, start+thickness) along ``axis`` of
    the VIRTUAL width-``w`` ghost-padded array (in padded coordinates),
    fully w-padded in the other two axes — reassembled from the local block
    and the six ``exchange_halo_faces(width=w)`` faces, without the padded
    volume ever existing. Default thickness 2w+1 (one output plane's
    dependence)."""
    thickness = thickness if thickness is not None else 2 * w + 1
    xlo, xhi, ylo, yhi, zlo, zhi = faces
    nx, ny, nz = u.shape
    s = slice(start, start + thickness)
    rng = range(start, start + thickness)
    if axis == 0:
        parts = []
        for p in rng:
            if p < w:
                parts.append(xlo[p : p + 1])
            elif p >= nx + w:
                parts.append(xhi[p - nx - w : p - nx - w + 1])
            else:
                parts.append(u[p - w : p - w + 1])
        core = lax.concatenate(parts, 0)  # (thickness, ny, nz)
        core = lax.concatenate([ylo[s], core, yhi[s]], 1)
        return lax.concatenate([zlo[s], core, zhi[s]], 2)
    if axis == 1:

        def xrow(p):  # x-extended row at padded y coord p: (nx+2w, 1, nz)
            if p < w:
                return ylo[:, p : p + 1]
            if p >= ny + w:
                return yhi[:, p - ny - w : p - ny - w + 1]
            q = p - w
            return lax.concatenate(
                [xlo[:, q : q + 1], u[:, q : q + 1], xhi[:, q : q + 1]], 0
            )

        core = lax.concatenate([xrow(p) for p in rng], 1)
        return lax.concatenate([zlo[:, s], core, zhi[:, s]], 2)

    def xycol(p):  # x+y-extended column at padded z coord p: (nx+2w, ny+2w, 1)
        if p < w:
            return zlo[:, :, p : p + 1]
        if p >= nz + w:
            return zhi[:, :, p - nz - w : p - nz - w + 1]
        q = p - w
        mid = lax.concatenate(
            [xlo[:, :, q : q + 1], u[:, :, q : q + 1], xhi[:, :, q : q + 1]], 0
        )
        return lax.concatenate(
            [ylo[:, :, q : q + 1], mid, yhi[:, :, q : q + 1]], 1
        )

    return lax.concatenate([xycol(p) for p in rng], 2)


def _local_step_direct_faces(
    u_local: jax.Array,
    taps: np.ndarray,
    cfg: SolverConfig,
    direct,
) -> jax.Array:
    """Multi-chip direct step: faces-only exchange + BC-fused bulk kernel +
    shard-boundary shell patches.

    The direct kernel sweeps the UNPADDED local block (its in-register
    domain-BC ghosts are exact on axes of mesh size 1, wrong only in the
    outermost shell of sharded axes), while the six ghost faces travel over
    ICI with no data dependence between them — XLA runs the collectives
    under the kernel. The thin shells of sharded axes are then recomputed
    from virtual padded slabs and patched in. Vs the exchange path this
    removes the full-volume padded concatenate (≈half the HBM traffic of a
    step) and overlaps comm with compute; vs the overlap split it needs no
    zero-init/interior DUS of the full volume. Arithmetic matches the
    unsplit step (same taps, same op order per cell) to FMA rounding.
    """
    from heat3d_tpu.parallel.halo import exchange_halo_faces

    periodic = cfg.stencil.bc is BoundaryCondition.PERIODIC
    compute_dtype = jnp.dtype(cfg.precision.compute)
    out_dtype = jnp.dtype(cfg.precision.storage)
    faces = exchange_halo_faces(
        u_local, cfg.mesh, cfg.stencil.bc, cfg.stencil.bc_value
    )
    out = direct(
        u_local,
        taps,
        periodic=periodic,
        bc_value=cfg.stencil.bc_value,
        compute_dtype=compute_dtype,
        out_dtype=out_dtype,
    )
    # the kernel's in-register ghosts are wrong only in the 1-deep
    # shells of sharded axes: recompute those from virtual padded slabs
    # over the exchanged faces and patch them in
    with named_phase("shell"):
        for axis, size in enumerate(cfg.mesh.shape):
            if size == 1:
                continue  # kernel's local BC/wrap is already exact here
            n = u_local.shape[axis]
            for start, pos in ((0, 0), (n - 1, n - 1)):
                slab = _padded_slab(u_local, faces, axis, start)
                shell = apply_taps_padded(
                    slab, taps, compute_dtype=compute_dtype,
                    out_dtype=out_dtype,
                )
                idx = [0, 0, 0]
                idx[axis] = pos
                out = lax.dynamic_update_slice(out, shell, tuple(idx))
        return out


def _pin_slab_mid(
    mid: jax.Array, cfg: SolverConfig, axis: int, start: int
) -> jax.Array:
    """Dirichlet ghost pinning for a slab-shaped superstep intermediate:
    the slab analogue of _fill_mid_ghosts. ``mid`` carries one ghost ring;
    along ``axis`` its plane q maps to local index start + q - 1 (``start``
    in width-2 padded coordinates), on the other axes index r maps to local
    r - 1."""
    return _pin_outside_domain(
        mid,
        cfg,
        [
            start - 1 + jnp.arange(mid.shape[a])
            if a == axis
            else jnp.arange(mid.shape[a]) - 1
            for a in range(3)
        ],
    )


def _local_superstep_direct_faces(
    u_local: jax.Array,
    taps: np.ndarray,
    cfg: SolverConfig,
    direct2,
) -> jax.Array:
    """Multi-chip fused two-update superstep without the padded copy:
    width-2 faces-only exchange + BC-fused direct2 bulk kernel + 2-deep
    shard-boundary shell patches.

    The direct2 kernel's local ghost synthesis is wrong only where a
    two-step dependence (distance <= 2) reaches across a sharded axis — the
    outermost TWO planes per side. Those are recomputed from 6-thick
    virtual width-2 padded slabs (faces carry 2-deep neighbor data,
    corners included): apply taps, pin the slab intermediate's domain
    ghosts (storage-dtype round trip like the unfused sequence), apply taps
    again, patch in. One exchange and one HBM sweep per TWO updates. The
    patches (slab assembly, both tap applications, the pin, the update)
    carry the ``heat3d.shell`` scope; the bulk kernel does not."""
    from heat3d_tpu.parallel.halo import exchange_halo_faces

    periodic = cfg.stencil.bc is BoundaryCondition.PERIODIC
    compute_dtype = jnp.dtype(cfg.precision.compute)
    out_dtype = jnp.dtype(cfg.precision.storage)
    faces = exchange_halo_faces(
        u_local, cfg.mesh, cfg.stencil.bc, cfg.stencil.bc_value, width=2
    )
    out = direct2(
        u_local,
        taps,
        periodic=periodic,
        bc_value=cfg.stencil.bc_value,
        compute_dtype=compute_dtype,
        out_dtype=out_dtype,
    )
    with named_phase("shell"):
        for axis, size in enumerate(cfg.mesh.shape):
            if size == 1:
                continue  # kernel's local BC/wrap is already exact here
            n = u_local.shape[axis]
            for start in (0, n - 2):  # width-2 padded coords; final planes
                # env-default route: the direct2 bulk kernel follows the
                # mehrstellen knob (q-ring variant), so patched cells
                # follow it too (cross-kernel ulp-match contract)
                slab = _padded_slab(
                    u_local, faces, axis, start, w=2, thickness=6
                )
                mid = apply_taps_padded(
                    slab, taps, compute_dtype=compute_dtype,
                    out_dtype=out_dtype,
                )
                mid = _pin_slab_mid(mid, cfg, axis, start)
                shell = apply_taps_padded(
                    mid, taps, compute_dtype=compute_dtype,
                    out_dtype=out_dtype,
                )
                idx = [0, 0, 0]
                idx[axis] = start  # local planes [start, start+2)
                out = lax.dynamic_update_slice(out, shell, tuple(idx))
        return out


def _fused_streamk_fn(cfg: SolverConfig):
    """Return the fused k-sweep streaming kernel entry for this config's
    ``time_blocking = k`` (2 <= k <= 4), or None.

    One width-k ghost exchange, then ONE HBM sweep applies the stencil k
    times with shrinking ghost rings resident in VMEM
    (ops/stencil_pallas.apply_taps_pallas_streamk — the k-generalization
    of the stream2 kernel; at k=2 this IS the exchange-path fused
    two-update route, dispatched after the no-padded-copy direct2
    kernel). Gated by the shared ``_kernel_env_gate`` (backend, padding,
    halo_order, platform/emulation env) plus the kernel's own VMEM
    feasibility; off-TPU with no emulation env the route stands down and
    the jnp ring-recompute superstep (_local_stepk) runs instead."""
    k = cfg.time_blocking
    if k not in (2, 3, 4):
        return None
    if cfg.overlap:
        # make_superstep_fn refuses overlap before any streamk dispatch
        return None
    ok, interpret = _kernel_env_gate(cfg)
    if not ok:
        return None
    try:
        from heat3d_tpu.ops.stencil_pallas import (
            apply_taps_pallas_streamk,
            streamk_supported,
        )
    except ImportError:
        return None
    itemsize = jnp.dtype(cfg.precision.storage).itemsize
    n_taps = effective_num_taps(STENCILS[cfg.stencil.kind].weights)
    c_item = jnp.dtype(cfg.precision.compute).itemsize
    if not streamk_supported(
        cfg.local_shape, k, itemsize, itemsize, n_taps, c_item
    ):
        return None
    import functools

    if interpret:
        return functools.partial(apply_taps_pallas_streamk, interpret=True)
    return apply_taps_pallas_streamk


def superstep_cell_updates(cfg: SolverConfig) -> tuple:
    """(raw, effective) cell updates ONE superstep call executes per
    device — the honest accounting of deep temporal blocking's redundant
    ring recompute.

    ``effective`` is the k useful sweeps over the local block (what the
    simulation advances); ``raw`` is the recompute trapezoid every
    tb-superstep implementation pays — application j (of k) updates the
    (n + 2r)-extent slab still carrying r = k-1-j ghost rings, whether
    as jnp ring recompute (_local_stepk), the fused streaming kernels'
    in-VMEM stages, or the direct kernels' synthesized-ghost sweeps. At
    k <= 1 raw == effective. Fractions derived from these are
    scale-free per device, so they also describe the whole mesh."""
    k = max(1, cfg.time_blocking)
    nx, ny, nz = cfg.local_shape
    effective = k * nx * ny * nz
    raw = sum(
        (nx + 2 * r) * (ny + 2 * r) * (nz + 2 * r) for r in range(k)
    )
    return raw, effective


def redundant_flops_frac(cfg: SolverConfig) -> float:
    """Fraction of a superstep's executed stencil FLOPs that are
    redundant ghost-ring recompute (0.0 at time_blocking <= 1) — the
    ``cost_redundant_flops_frac`` bench-row field and the roofline
    report's raw-vs-effective discount. A tb=k "win" whose measured
    Gcell/s rides mostly on this recompute is visible as a large frac
    next to a modest effective rate."""
    raw, effective = superstep_cell_updates(cfg)
    return 0.0 if raw <= effective else 1.0 - effective / raw


def _local_step_overlap(
    u_local: jax.Array,
    taps: np.ndarray,
    cfg: SolverConfig,
    compute_padded: LocalCompute,
) -> jax.Array:
    """Comm/compute-overlapped local step (SURVEY.md §3.2 "optimized variants
    ... interior-update kernel on one CUDA stream while faces exchange on
    another, then boundary-update").

    The interior cells (local indices 1..n-2 per axis) read only local data,
    so their update carries **no data dependence on the ppermutes** — XLA's
    async collectives (collective-permute-start/done) can run the ICI
    transfers concurrently with the interior sweep. Only the 1-cell boundary
    shell waits for ghosts. The assembled result is arithmetically identical
    to the unsplit step (same taps, same op order per cell).
    """
    nx, ny, nz = u_local.shape
    compute_dtype = jnp.dtype(cfg.precision.compute)
    out_dtype = jnp.dtype(cfg.precision.storage)

    # Ghost exchange: the transfers this step overlaps with.
    up = exchange(u_local, cfg)

    # Interior update from the local block alone (u_local acts as its own
    # ghost-padded input for the (nx-2, ny-2, nz-2) interior) — the bulk of
    # the FLOPs, scheduled while faces are in flight.
    with named_phase("stencil"):
        interior = compute_padded(
            u_local, taps, compute_dtype=compute_dtype, out_dtype=out_dtype
        )
    out = jnp.zeros((nx, ny, nz), out_dtype)
    out = lax.dynamic_update_slice(out, interior, (1, 1, 1))

    # Boundary shell: six thickness-1 faces from the ghost-padded block.
    # Edge/corner cells land in two or three face slabs; each computes the
    # identical value, so overlapping writes are benign. Faces are thin VPU
    # work — always the jnp path, even when the interior runs Pallas; the
    # route must then match the interior's (a windowed-kernel interior
    # runs the tap chain, so its faces pin mehrstellen=False).
    face_mehrstellen = None if compute_padded is apply_taps_padded else False
    for axis, n in enumerate((nx, ny, nz)):
        for start, pos in ((0, 0), (n - 1, n - 1)):
            slab = lax.slice_in_dim(up, start, start + 3, axis=axis)
            face = apply_taps_padded(
                slab, taps, compute_dtype=compute_dtype, out_dtype=out_dtype,
                mehrstellen=face_mehrstellen,
            )
            idx = [0, 0, 0]
            idx[axis] = pos
            out = lax.dynamic_update_slice(out, face, tuple(idx))
    return _pin_padding(out, cfg)


def make_step_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
    with_residual: bool = False,
):
    """Build the sharded one-step function ``u -> u_new`` (or
    ``u -> (u_new, residual_sumsq)``) over global arrays sharded
    P('x','y','z'). Not jitted — callers compose it under jit."""
    taps = _solver_taps(cfg)
    spec = P(*cfg.mesh.axis_names)
    axes = cfg.mesh.axis_names
    local_step = _local_step
    kernel = None
    layout = None
    # the single-shard direct step that also returns the kernel's
    # change-residual partials, where a residual is asked for
    partial_step = None
    direct = _direct_kernel_fn(cfg, halo=1, multichip=True)
    if direct is not None:
        from heat3d_tpu.ops.stencil_pallas_direct import direct_layout

        kernel = "direct"
        layout = direct_layout(cfg.local_shape, taps)
        interpret = _kernel_env_gate(cfg)[1]
        _log_step_path_once(
            "step path: %s direct kernel (no padded copy)"
            % (
                "single-shard"
                if cfg.mesh.shape == (1, 1, 1)
                else "faces-direct multi-chip"
            )
        )
        if cfg.mesh.shape == (1, 1, 1):
            periodic = cfg.stencil.bc is BoundaryCondition.PERIODIC

            def single_shard(kernel_fn):
                def local_step(u_local, taps, cfg, compute_padded):
                    with named_phase("stencil"):
                        return kernel_fn(
                            u_local,
                            taps,
                            periodic=periodic,
                            bc_value=cfg.stencil.bc_value,
                            compute_dtype=jnp.dtype(cfg.precision.compute),
                            out_dtype=jnp.dtype(cfg.precision.storage),
                        )

                return local_step

            local_step = single_shard(direct)
            # every cell of the one shard is a kernel output cell, so the
            # kernel's partials are the whole residual; on a multi-chip
            # mesh the shell patches rewrite cells after the kernel
            if with_residual:
                partials = _direct_kernel_fn(
                    cfg, halo=1, residual_dtype=cfg.precision.residual
                )
                if partials is not None:
                    partial_step = single_shard(partials)

        else:

            def local_step(u_local, taps, cfg, compute_padded):
                with named_phase("stencil"):
                    return _local_step_direct_faces(
                        u_local, taps, cfg, direct
                    )

    if cfg.overlap and direct is None:
        # jnp interior/boundary split — the portable overlap form; when
        # the direct kernel dispatched above, the faces-direct step
        # already overlaps the face ppermutes with the bulk sweep
        if min(cfg.local_shape) < 3:
            raise ValueError(
                f"overlap=True needs local blocks >= 3 per axis to have "
                f"an interior, got {cfg.local_shape}"
            )
        local_step = _local_step_overlap

    # check_vma=False: pallas_call inside shard_map would otherwise require a
    # `vma` annotation on its out_shape (jax 0.9), and the kernel is built
    # mesh-agnostic. The unmapped residual out_spec stays sound: psum over all
    # mesh axes makes it replicated by construction.
    if with_residual:
        residual_dtype = jnp.dtype(cfg.precision.residual)

        def local(u_local):
            if partial_step is not None:
                u_new, partials = partial_step(
                    u_local, taps, cfg, compute_padded
                )
            else:
                u_new = local_step(u_local, taps, cfg, compute_padded)
            with named_phase("residual"):
                if partial_step is not None:
                    r = jnp.sum(partials, dtype=residual_dtype)
                else:
                    r = residual_sumsq(u_new, u_local, residual_dtype)
                # MPI_Allreduce analogue (SURVEY.md §3.3)
                r = lax.psum(r, axes)
            return u_new, r

        # scoped(PHASE_STEP, ...): the whole-step heat3d.step named scope
        # (trace-time metadata only) — profiled ops outside the inner
        # stencil/halo/residual scopes (dispatch glue, padding pins)
        # attribute to "step" instead of (unattributed), which is what the
        # profile→roofline join keys on (obs/perf/timeline.py)
        return _routed(
            scoped(
                PHASE_STEP,
                shard_map(
                    local, mesh=mesh, in_specs=spec, out_specs=(spec, P()),
                    check_vma=False,
                ),
            ),
            kernel,
            kernel is not None and interpret,
            layout,
            residual="reduce" if partial_step is None else "kernel",
        )

    def local(u_local):
        return local_step(u_local, taps, cfg, compute_padded)

    return _routed(
        scoped(
            PHASE_STEP,
            shard_map(
                local, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            ),
        ),
        kernel,
        kernel is not None and interpret,
        layout,
    )


def make_pair_step_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
    with_residual: bool = False,
):
    """Build one update over the ping-pong pair, ``(u, spare) -> (u,
    u_new)`` (or ``(u, u_new, residual_sumsq)``): ``make_step_fn``'s
    update written into the spare's buffer, whose contents are never
    read, and both buffers returned in order. A caller that donates both
    (``HeatSolver3D.step``, ``step_with_residual``; jit them with
    ``keep_unused=True``, since the spare is only a buffer) pays no copy:
    ``u`` stays in its own buffer, alive for the residual, which reads
    both (on one shard inside the direct kernel). The program is named
    ``wrapper``, as the step builders' ``scoped`` programs are (jitted:
    ``jit_wrapper``), the name the benchmark's ``solver.residual_share``
    finds the residual step by."""
    step = make_step_fn(cfg, mesh, compute_padded, with_residual=with_residual)

    def wrapper(u, spare):
        del spare  # its buffer takes the update
        out = step(u)
        return (u, *out) if with_residual else (u, out)

    wrapper.route = step.route
    return wrapper


def make_superstep_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
):
    """Build the sharded temporally-blocked superstep ``u -> u_after_k_steps``
    for ``k = cfg.time_blocking`` (see _local_stepk). Composes with either
    halo transport (ppermute or the width-k DMA slab exchange); requires no
    overlap split and local extents >= k."""
    if cfg.overlap:
        raise ValueError(
            f"time_blocking={cfg.time_blocking} and overlap=True are "
            "mutually exclusive — the superstep already restructures the "
            "exchange/compute schedule"
        )
    # k ghost layers must fit the local block AND the shrinking-ring
    # intermediates need a genuine interior to recompute into: below 3
    # cells per axis a superstep's first application already consumes the
    # whole block (the same floor the overlap split enforces)
    min_extent = max(3, cfg.time_blocking)
    if min(cfg.local_shape) < min_extent:
        raise ValueError(
            f"time_blocking={cfg.time_blocking} needs local extents >= "
            f"{min_extent} (k ghost layers plus the shrinking recompute "
            f"rings), got {cfg.local_shape}"
        )
    taps = _solver_taps(cfg)
    spec = P(*cfg.mesh.axis_names)

    # k=2 with the BC-fused direct2 kernel: both updates in one sweep of the
    # UNPADDED field — no width-2 ghost copy at all. On multi-chip meshes
    # the faces-direct superstep patches the 2-deep shard-boundary shells.
    if cfg.time_blocking == 2:
        direct2 = _direct_kernel_fn(cfg, halo=2, multichip=True)
        if direct2 is not None:
            if cfg.mesh.shape == (1, 1, 1):
                _log_step_path_once(
                    "superstep path: single-shard fused direct2 kernel"
                )
                periodic2 = cfg.stencil.bc is BoundaryCondition.PERIODIC

                def local2(u_local):
                    with named_phase("stencil"):
                        return direct2(
                            u_local,
                            taps,
                            periodic=periodic2,
                            bc_value=cfg.stencil.bc_value,
                            compute_dtype=jnp.dtype(cfg.precision.compute),
                            out_dtype=jnp.dtype(cfg.precision.storage),
                        )

            else:
                _log_step_path_once(
                    "superstep path: faces-direct fused direct2 kernel "
                    "(multi-chip, no padded copy)"
                )

                def local2(u_local):
                    with named_phase("stencil"):
                        return _local_superstep_direct_faces(
                            u_local, taps, cfg, direct2
                        )

            from heat3d_tpu.ops.stencil_pallas_direct import direct_layout

            return _routed(
                scoped(
                    PHASE_STEP,
                    shard_map(
                        local2, mesh=mesh, in_specs=spec, out_specs=spec,
                        check_vma=False,
                    ),
                ),
                "direct",
                _kernel_env_gate(cfg)[1],
                direct_layout(cfg.local_shape, taps),
            )

    # The fused k-sweep streaming kernel (k=2..4): keeps the width-k
    # padded slab resident in VMEM and applies the stencil k times with
    # shrinking ghost rings — one exchange AND one HBM sweep per k
    # updates. Composes with either exchange transport (ppermute or the
    # width-k DMA slab kernels); stands down (jnp ring recompute below)
    # off-TPU or when the slab busts the VMEM gate. k=2 reaches here only
    # when the direct2 kernel above didn't dispatch (its no-padded-copy
    # form is strictly better in that scope).
    fusedk = _fused_streamk_fn(cfg)
    if fusedk is not None:
        k = cfg.time_blocking
        interpret = _kernel_env_gate(cfg)[1]
        _log_step_path_once(
            "superstep path: fused %d-sweep streaming kernel (width-%d "
            "slab resident in VMEM, shrinking-ring recompute)%s"
            % (k, k, " [interpret]" if interpret else "")
        )
        periodic_k = cfg.stencil.bc is BoundaryCondition.PERIODIC

        def localk(u_local):
            upk = exchange(u_local, cfg, width=k)
            with named_phase("stencil"):
                return fusedk(
                    upk,
                    taps,
                    k,
                    mesh_axis_names=cfg.mesh.axis_names,
                    periodic=periodic_k,
                    bc_value=cfg.stencil.bc_value,
                    compute_dtype=jnp.dtype(cfg.precision.compute),
                    out_dtype=jnp.dtype(cfg.precision.storage),
                )

        return _routed(
            scoped(
                PHASE_STEP,
                shard_map(
                    localk, mesh=mesh, in_specs=spec, out_specs=spec,
                    check_vma=False,
                ),
            ),
            "streamk",
            interpret,
        )

    # Fallback: k compute_padded applications with jnp ring recompute —
    # still cuts the exchanges k-fold, runs anywhere.
    def local(u_local):
        return _local_stepk(u_local, taps, cfg, compute_padded)

    return _routed(
        scoped(
            PHASE_STEP,
            shard_map(
                local, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            ),
        ),
        None,
    )


def _pingpong_loop(step_fn, a: jax.Array, b: jax.Array, trips):
    """Apply ``step_fn`` ``2 * trips`` times over the ping-pong pair
    ``(a, b)``, the field starting in ``a``; returns the pair in buffer
    order, the field back in ``a``.

    A single-buffer ``fori_loop(0, n, lambda _, v: step_fn(v), u)`` forces
    XLA to insert a full-volume copy every iteration: the while carry is a
    fixed buffer, the stencil custom-call cannot write its output into the
    buffer it is reading, so copy-insertion clones the carry before each
    call (measured at 38–49% of step time on-chip — see BASELINE.md). With
    a pair carry the two calls per iteration alternate buffers — each
    writes into the buffer whose contents are already dead — and buffer
    assignment elides the copy entirely (verified: the compiled pair-loop
    body is two custom-calls, zero copies). This is the reference's
    ``swap(u_old, u_new)`` pointer swap (SURVEY.md §1 L0) done the XLA way.
    ``b``'s contents are never read."""

    def body2(_, ab):
        a, b = ab
        b = step_fn(a)
        a = step_fn(b)
        return (a, b)

    return lax.fori_loop(0, trips, body2, (a, b))


def _alternate(step_fns, a: jax.Array, b: jax.Array):
    """Apply each of ``step_fns`` in turn, out of place, the field
    starting in ``a`` and moving to the other buffer at every write;
    returns the pair in buffer order (the field in ``b`` after an odd
    number of writes). The count is static, so every write has a dead
    buffer to go to and no write pays a copy."""
    pair = [a, b]
    for i, fn in enumerate(step_fns):
        pair[(i + 1) % 2] = fn(pair[i % 2])
    return tuple(pair)


def pair_plan(num_steps: int, k: int):
    """``(trips, odd, rest)`` of :func:`make_pair_multistep_fn` for
    ``num_steps`` updates at ``time_blocking`` k: ``2 * trips + odd`` hot
    updates, then ``rest`` single steps. The field ends in the spare's
    buffer when ``odd + rest`` is odd."""
    if num_steps < 0:
        raise ValueError(f"num_steps must be >= 0, got {num_steps}")
    hot, rest = divmod(num_steps, k)
    trips, odd = divmod(hot, 2)
    return trips, odd, rest


def make_pair_multistep_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
):
    """Build the fixed-step loop over the ping-pong pair,
    ``(a, b, trips, odd, rest) -> (a, b)``: the field starts in ``a``,
    ``b`` is a spare buffer of the same shape whose contents are never
    read, and both are returned in buffer order. The loop makes ``2 *
    trips`` hot updates (``trips`` traced), then ``odd`` (0 or 1) more,
    then ``rest`` single steps; ``odd`` and ``rest`` are static. A hot
    update is the k-update superstep at ``cfg.time_blocking == k > 1``
    (1/k the exchanges) and the single step at k = 1, so ``num_steps``
    updates take ``pair_plan(num_steps, k)``.
    Every update writes into the buffer the field is not in, so the field
    ends in ``b`` after an odd number ``2 * trips + odd + rest`` of writes
    and in ``a`` otherwise, and the program copies nothing: the caller
    keeps both buffers and donates both (``HeatSolver3D.run``).

    The program is named ``runk`` at k > 1 and ``run`` at k = 1 (jitted:
    ``jit_runk`` / ``jit_run``), names the benchmark's trace readers
    find it by. ``hot`` and ``step`` are the two update functions."""
    step = make_step_fn(cfg, mesh, compute_padded, with_residual=False)
    k = cfg.time_blocking
    # the superstep carries the loop: its route is the run's
    hot = make_superstep_fn(cfg, mesh, compute_padded) if k > 1 else step

    def run(a, b, trips, odd, rest):
        a, b = _pingpong_loop(hot, a, b, trips)
        return _alternate((hot,) * odd + (step,) * rest, a, b)

    run.__name__ = run.__qualname__ = "runk" if k > 1 else "run"
    run.route = hot.route
    run.hot, run.step = hot, step
    return run


def make_multistep_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
):
    """Build ``(u, num_steps) -> u_after`` with the loop *inside* the
    compiled program and ``num_steps`` traced, so one executable serves
    any step count (the reference recompiles nothing either — its loop is
    host-side; ours is device-side, SURVEY.md §3.2 TPU mapping).

    A thin use of :func:`make_pair_multistep_fn`: its pair loop on a
    zero-filled scratch buffer for the even part of the hot updates, then
    the traced tail — an odd superstep and the ``num_steps % k`` single
    steps — each in a ≤1- or ≤(k-1)-trip loop on the one buffer, which
    XLA copies back once a trip. ``HeatSolver3D`` runs the pair form
    itself, with the parity static and both buffers its own, and pays no
    copy."""
    pair = make_pair_multistep_fn(cfg, mesh, compute_padded)
    k = cfg.time_blocking

    def run(u, num_steps):
        hot_updates = num_steps // k
        u, _ = pair(u, jnp.zeros_like(u), hot_updates // 2, 0, 0)
        u = lax.fori_loop(0, hot_updates % 2, lambda _, v: pair.hot(v), u)
        if k > 1:
            u = lax.fori_loop(0, num_steps % k, lambda _, v: pair.step(v), u)
        return u

    run.route = pair.route
    return run


def make_converge_fn(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
):
    """Build ``(u, max_steps, tol) -> (u, steps_taken, last_residual)``:
    iterate until the global L2 residual of one update drops below tol —
    the convergence-mode path (SURVEY.md §3.3; fixed-step benchmark mode
    never syncs and uses make_multistep_fn instead).

    With ``cfg.run.residual_every = K > 1`` the while body advances K-1
    updates through the fixed-step machinery — the copy-free ping-pong
    pair carry AND temporal-blocking supersteps both apply — then runs one
    residual step, so the psum + its convergence check happen every K
    updates instead of every update. This is exactly the reference class's
    cadence ("every k iters: residual + MPI_Allreduce", SURVEY.md §3.2);
    the run may overshoot the tol crossing by up to K-1 updates but never
    exceeds max_steps, and ``steps_taken`` counts real updates exactly.

    With K <= 1 (the default) the residual is checked after EVERY update:
    single-buffer carry (its per-iteration XLA copy is dominated by the
    per-step psum sync) and no temporal blocking."""
    step_r = make_step_fn(cfg, mesh, compute_padded, with_residual=True)
    every = max(1, cfg.run.residual_every or 1)

    if every > 1:
        multistep = make_multistep_fn(cfg, mesh, compute_padded)

        def run(u, max_steps, tol):
            def cond(state):
                _, i, r2 = state
                return jnp.logical_and(i < max_steps, r2 > tol * tol)

            def body(state):
                u, i, _ = state
                # leave one update for the residual step; never pass
                # max_steps even when it isn't a multiple of K
                n = jnp.minimum(jnp.int32(every - 1), max_steps - 1 - i)
                u = multistep(u, n)
                u_new, r2 = step_r(u)
                return u_new, i + n + 1, r2

            init = (
                u, jnp.zeros((), jnp.int32), jnp.full((), jnp.inf, jnp.float32)
            )
            u, steps, r2 = lax.while_loop(cond, body, init)
            return u, steps, jnp.sqrt(r2)

        run.route = {**multistep.route, "residual": step_r.route["residual"]}
        return run

    def run(u, max_steps, tol):
        def cond(state):
            _, i, r2 = state
            return jnp.logical_and(i < max_steps, r2 > tol * tol)

        def body(state):
            u, i, _ = state
            u_new, r2 = step_r(u)
            return u_new, i + 1, r2

        init = (u, jnp.zeros((), jnp.int32), jnp.full((), jnp.inf, jnp.float32))
        u, steps, r2 = lax.while_loop(cond, body, init)
        return u, steps, jnp.sqrt(r2)

    run.route = step_r.route
    return run


# ---- span <-> cost-analysis keying ------------------------------------------

# The named_phase brackets above (obs/trace.py), the profiler trace's
# per-phase table (scripts/summarize_trace.py), the ledger spans, and the
# per-phase compile targets below all share these names — a cost_analysis()
# record joins a measured span on ONE key (obs/perf/roofline.py consumes).
PHASE_STEP = "step"
PHASE_STENCIL = "stencil"
# the faces-direct routes' shard-boundary shell patches, nested inside
# the stencil scope; no compile target of its own (phase_programs)
PHASE_SHELL = "shell"
PHASE_HALO = "halo_exchange"
PHASE_RESIDUAL = "residual"

# The canonical phase vocabulary, in roofline-table order — the
# profile→roofline join iterates it and keys per-phase call counts on
# the PHASE_* constants (obs/perf/roofline.profile_join_records /
# _phase_calls); obs/perf/timeline.normalize_phase folds trace scopes
# onto the same names.
PHASES = (
    PHASE_STEP, PHASE_STENCIL, PHASE_SHELL, PHASE_HALO, PHASE_RESIDUAL,
)


def phase_programs(
    cfg: SolverConfig,
    mesh: Mesh,
    compute_padded: LocalCompute = apply_taps_padded,
):
    """Un-jitted compile targets per phase, each a callable over the
    sharded global field (storage layout, ``cfg.padded_shape``):

    - ``step``: the full iteration program this config's hot loop runs —
      the single step (exchange + stencil [+ padding pin]) at
      ``time_blocking == 1``, the k-update SUPERSTEP at k > 1 (one
      exchange amortized over k updates, ghost-ring recompute included;
      costing the single step there would describe a program the loop
      never runs). Costs and timings are per CALL — at k > 1 one call is
      k updates; divide by k for per-update numbers
      (``obs.perf.roofline.step_cost_fields`` does).
    - ``halo_exchange``: the ghost exchange alone (whichever transport
      ``cfg.halo`` selects), cropped back to the local block so the
      program has a data-live consumer of every received face.
    - ``stencil``: the local tap application alone on locally-padded
      blocks (no collective) — the compute leg of the roofline.
    - ``residual``: the fp32 reduction + psum alone.

    Callers jit + ``.lower(u).compile().cost_analysis()`` each to get the
    FLOPs/bytes the roofline report divides measured span time by.
    """
    taps = _solver_taps(cfg)
    spec = P(*cfg.mesh.axis_names)
    compute_dtype = jnp.dtype(cfg.precision.compute)
    out_dtype = jnp.dtype(cfg.precision.storage)

    def _sharded(f, out_specs=spec):
        return shard_map(
            f, mesh=mesh, in_specs=spec, out_specs=out_specs, check_vma=False
        )

    def _halo_only(u_local):
        # every received ghost face is folded onto the block boundary
        # (face-sized writes, the same keep-alive trick bench_halo uses) so
        # XLA cannot DCE any of the six transports out of the program
        nx, ny, nz = u_local.shape
        p = exchange(u_local, cfg)
        out = u_local
        out = out.at[0].add(p[0, 1 : 1 + ny, 1 : 1 + nz])
        out = out.at[nx - 1].add(p[nx + 1, 1 : 1 + ny, 1 : 1 + nz])
        out = out.at[:, 0].add(p[1 : 1 + nx, 0, 1 : 1 + nz])
        out = out.at[:, ny - 1].add(p[1 : 1 + nx, ny + 1, 1 : 1 + nz])
        out = out.at[:, :, 0].add(p[1 : 1 + nx, 1 : 1 + ny, 0])
        out = out.at[:, :, nz - 1].add(p[1 : 1 + nx, 1 : 1 + ny, nz + 1])
        return out

    def _stencil_only(u_local):
        with named_phase("stencil"):
            return compute_padded(
                jnp.pad(u_local, 1),  # local ghost fill: no collective
                taps,
                compute_dtype=compute_dtype,
                out_dtype=out_dtype,
            )

    def _residual_only(u_local):
        with named_phase("residual"):
            r = residual_sumsq(
                u_local, u_local * 1, jnp.dtype(cfg.precision.residual)
            )
            return lax.psum(r, cfg.mesh.axis_names)

    return {
        PHASE_STEP: (
            make_superstep_fn(cfg, mesh, compute_padded)
            if cfg.time_blocking > 1
            else make_step_fn(cfg, mesh, compute_padded)
        ),
        PHASE_HALO: _sharded(_halo_only),
        PHASE_STENCIL: _sharded(_stencil_only),
        PHASE_RESIDUAL: _sharded(_residual_only, out_specs=P()),
    }
