"""Persistent halo-exchange plans — setup amortized across the run,
partitioned early-bird sends as a tuned knob.

"Persistent and Partitioned MPI for Stencil Communication" (PAPERS.md)
shows two wins for repeated ghost exchanges: amortize the exchange
*setup* across steps (persistent channels: the neighbor graph, buffer
slices and message schedule are built once, not per iteration) and ship
each face as *partitions* that leave as soon as their tile of data is
ready (early-bird sends) instead of when the whole face is assembled.
Our pre-plan equivalent of the setup cost was re-deriving the six
``shift_perm`` permutations, face slices and corner-propagation order
inside every step trace; this module hoists all of it into an
:class:`ExchangePlan` built once per (mesh, boundary condition, width-k,
halo ordering, transport, plan mode) and reused by every step,
superstep, phase, bench and ensemble program in the process
(``plan_for`` caches; the ``exchange_plan_built`` / ``plan_cache_hit``
ledger events audit reuse — one build per plan key per run is the
contract the tests pin).

Plan modes (the ``halo_plan`` config knob, ``auto`` resolved through
the tuning cache like every other knob — docs/TUNING.md):

- ``monolithic`` — one collective per face, exactly the pre-plan
  exchange structure; plan-built programs are BITWISE-identical to the
  ad-hoc path (the permutations and slices are precomputed, the traced
  ops are the same).
- ``partitioned`` — each face at or above the granularity floor
  (:data:`DEFAULT_PART_MIN_BYTES`, ``HEAT3D_PLAN_PART_MIN_BYTES``) is
  split into :data:`DEFAULT_PARTITIONS`
  sub-blocks and every sub-block ships as its OWN ppermute, issued from
  its own strip of the boundary (the early-bird ordering: no sub-block's
  transfer waits for the whole face, the first consumer of each landed
  sub-block is the ghost concatenate, and the interior sweep carries no
  dependence on any of them — XLA's async collective-permutes overlap
  the transport with the remaining compute; compose with ``overlap=True``
  for the interior/boundary-tiled sweep). The assembled ghost faces are
  bitwise-identical to the monolithic exchange (ppermute is pure data
  movement), so partitioned A/Bs are value-safe on every stencil,
  ordering and decomposition — the tuner decides where the message-size
  trade wins. ``partitioned`` pins the exchange path (the kernel
  families synthesize ghosts in-kernel — ``parallel.step``'s shared
  kernel gate stands them down) and requires the ppermute transport
  (the DMA slab kernels are monolithic by construction; config-rejected).

``HEAT3D_NO_PLAN=1`` bypasses the plan layer entirely (the legacy
ad-hoc dispatch — the reference arm of the plan-vs-ad-hoc parity tests
and a production escape hatch; ``halo_plan='partitioned'`` then degrades
to the monolithic ad-hoc path).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

from heat3d_tpu.core.config import BoundaryCondition, MeshConfig, SolverConfig
from heat3d_tpu.obs.trace import named_phase
from heat3d_tpu.parallel.halo import (
    axis_ghosts,
    exchange_halo,
    exchange_halo_pairwise,
    shift_perm,
    substitute_domain_bc,
)

HALO_PLANS = ("monolithic", "partitioned", "auto")

# sub-blocks per face in partitioned mode: 2 halves the message size
# (first half lands while the second is in flight) without fragmenting
# faces below useful DMA granularity on the judged shard shapes
DEFAULT_PARTITIONS = 2

# granularity floor: a face below this many bytes ships whole even under
# halo_plan='partitioned' — sub-messages smaller than this cannot
# pipeline usefully (per-collective setup dominates transport; the
# partitioned-MPI literature sizes partitions to network granularity
# for the same reason, and the CPU A/B at smoke sizes measures exactly
# that overhead regime). 1 MiB keeps every pod-scale judged face
# partitioned (a 1024^2 fp32 slab face is 4 MiB) while small-face
# exchanges keep the monolithic schedule. HEAT3D_PLAN_PART_MIN_BYTES
# overrides (0 forces genuine sub-blocks everywhere — the IR matrix and
# the identity tests use it so partitioned programs are certified with
# real sub-block permutes, not the degenerate schedule).
DEFAULT_PART_MIN_BYTES = 1 << 20

ENV_NO_PLAN = "HEAT3D_NO_PLAN"
ENV_PART_MIN_BYTES = "HEAT3D_PLAN_PART_MIN_BYTES"


def part_min_bytes() -> int:
    """The effective partition granularity floor (env override or the
    default). Never raises — a malformed override falls back."""
    raw = os.environ.get(ENV_PART_MIN_BYTES)
    if raw is None or raw == "":
        return DEFAULT_PART_MIN_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_PART_MIN_BYTES


def partition_bounds(extent: int, parts: int) -> Tuple[Tuple[int, int], ...]:
    """Split ``[0, extent)`` into up to ``parts`` contiguous sub-ranges,
    as even as possible, never empty (an extent smaller than ``parts``
    yields ``extent`` unit ranges — the degenerate plan is still valid)."""
    p = max(1, min(int(parts), int(extent)))
    base, rem = divmod(int(extent), p)
    bounds = []
    start = 0
    for i in range(p):
        step = base + (1 if i < rem else 0)
        bounds.append((start, start + step))
        start += step
    return tuple(bounds)


@dataclasses.dataclass(frozen=True)
class AxisExchangeSpec:
    """Everything one axis's exchange needs, precomputed: the mesh axis,
    its precomputed ±1 ring/line permutations (``None`` on size-1 axes —
    no remote party), and the in-plane dim partitioned sub-blocks split
    along (the first non-exchange dim; irrelevant in monolithic mode)."""

    axis: int
    name: str
    size: int
    perm_up: Optional[Tuple[Tuple[int, int], ...]]  # shift_perm(size, +1)
    perm_down: Optional[Tuple[Tuple[int, int], ...]]  # shift_perm(size, -1)
    part_dim: int


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """One persistent exchange schedule. ``apply`` must run inside
    shard_map over the plan's mesh; ``bc_value`` stays an apply-time
    argument (it may be a TRACED scalar — the ensemble's per-member
    boundary value), so one plan serves every tenant of a mesh shape."""

    mesh: MeshConfig
    bc: BoundaryCondition
    width: int
    halo_order: str  # 'axis' | 'pairwise'
    transport: str  # 'ppermute' | 'dma'
    mode: str  # 'monolithic' | 'partitioned'
    partitions: int
    min_part_bytes: int  # faces below this ship whole (granularity floor)
    axis_specs: Tuple[AxisExchangeSpec, ...]

    @property
    def periodic(self) -> bool:
        return self.bc is BoundaryCondition.PERIODIC

    @property
    def key(self) -> str:
        """Stable human-readable plan identity (ledger event key)."""
        m = "x".join(str(p) for p in self.mesh.shape)
        return (
            f"m{m}|{self.bc.value}|w{self.width}|{self.halo_order}"
            f"|{self.transport}|{self.mode}"
            + (
                # the granularity floor changes the executed schedule, so
                # two plans differing only in it must not alias to one
                # audit-event key (the reuse contract counts per key)
                f"|p{self.partitions}|f{self.min_part_bytes}"
                if self.mode == "partitioned"
                else ""
            )
        )

    # ---- execution --------------------------------------------------------

    def apply(self, u, bc_value: Any = 0.0):
        """Ghost-grow ``u`` by ``width`` on every axis through this plan's
        schedule: (nx,ny,nz) -> (nx+2w,ny+2w,nz+2w). Must run inside
        shard_map over the mesh the plan was built for."""
        if self.transport == "dma":
            from heat3d_tpu.ops.halo_pallas import exchange_halo_dma_planned

            return exchange_halo_dma_planned(u, self, bc_value)
        ghosts_fn = (
            self._partitioned_ghosts
            if self.mode == "partitioned"
            else self._monolithic_ghosts
        )
        if self.halo_order == "pairwise":
            return exchange_halo_pairwise(
                u, self.mesh, self.bc, bc_value, self.width,
                ghosts_fn=ghosts_fn,
            )
        return exchange_halo(
            u, self.mesh, self.bc, bc_value, self.width, ghosts_fn=ghosts_fn
        )

    def _spec(self, axis: int) -> AxisExchangeSpec:
        return self.axis_specs[axis]

    def _monolithic_ghosts(
        self, lo_face, hi_face, axis, axis_name, axis_size, periodic, bc_value
    ):
        """One collective per face, permutation precomputed — the ad-hoc
        exchange structure with the per-trace setup hoisted into the
        plan (bitwise-identical traced ops)."""
        spec = self._spec(axis)
        return axis_ghosts(
            lo_face, hi_face, axis_name, axis_size, periodic, bc_value,
            perms=(spec.perm_up, spec.perm_down),
        )

    def _partitioned_ghosts(
        self, lo_face, hi_face, axis, axis_name, axis_size, periodic, bc_value
    ):
        """Early-bird partitioned sends: each face sub-block is its own
        ppermute pair, issued from its own boundary strip. The assembled
        ghost faces equal the monolithic exchange bitwise (ppermute moves
        values unchanged; the domain-edge BC substitution is the SHARED
        ``substitute_domain_bc`` tail ``axis_ghosts`` applies to the
        whole face)."""
        from jax import lax

        if axis_size == 1:
            # degenerate ring: nothing to partition, same special cases
            return axis_ghosts(
                lo_face, hi_face, axis_name, axis_size, periodic, bc_value
            )
        spec = self._spec(axis)
        pd = spec.part_dim
        bounds = partition_bounds(
            lo_face.shape[pd],
            self._face_partitions(lo_face.shape, lo_face.dtype.itemsize),
        )
        glo_parts, ghi_parts = [], []
        for i, (a, b) in enumerate(bounds):
            lo_p = lax.slice_in_dim(lo_face, a, b, axis=pd)
            hi_p = lax.slice_in_dim(hi_face, a, b, axis=pd)
            # per-sub-block scopes (halo.<axis>.<dir>.p<i>, degenerate
            # single-block schedules keep the plain per-direction name):
            # each early-bird send's device time attributes to ITS
            # sub-block — the granularity the partitioned-MPI trade
            # actually lives at (normalize_phase folds all of them back
            # into halo_exchange for the coarse joins)
            blk = f".p{i}" if len(bounds) > 1 else ""
            with named_phase(f"halo.{axis_name}.lo{blk}"):
                # my low ghost = low neighbor's high face (shift up)
                glo_parts.append(
                    lax.ppermute(hi_p, axis_name, spec.perm_up)
                )
            with named_phase(f"halo.{axis_name}.hi{blk}"):
                ghi_parts.append(
                    lax.ppermute(lo_p, axis_name, spec.perm_down)
                )
        if len(bounds) == 1:
            ghost_lo, ghost_hi = glo_parts[0], ghi_parts[0]
        else:
            ghost_lo = lax.concatenate(glo_parts, dimension=pd)
            ghost_hi = lax.concatenate(ghi_parts, dimension=pd)
        return substitute_domain_bc(
            ghost_lo, ghost_hi, axis_name, axis_size, periodic, bc_value
        )

    def _face_partitions(self, face_shape, itemsize: int) -> int:
        """Sub-blocks for a face of this shape: the requested partition
        count, gated by the granularity floor (a face too small to
        pipeline ships whole — the monolithic schedule, same values)."""
        elems = 1
        for s in face_shape:
            elems *= int(s)
        if elems * itemsize < self.min_part_bytes:
            return 1
        return self.partitions

    # ---- cost/footprint metadata -----------------------------------------

    def messages_per_exchange(self) -> int:
        """Collectives (or DMA pairs) one full exchange issues per device
        at the SCHEDULE ceiling (the granularity floor may ship small
        faces whole — :meth:`traffic` prices the shape-aware count)."""
        n = 0
        for spec in self.axis_specs:
            if spec.size <= 1:
                continue
            if self.mode == "partitioned":
                n += 2 * self.partitions
            else:
                n += 2
        return n

    def traffic(self, local_shape, itemsize: int) -> Dict[str, int]:
        """Per-device transport model of ONE exchange: messages issued and
        boundary bytes sent, accounting the progressive face extension
        axis ordering implies (later faces carry earlier ghosts) and the
        partition granularity floor. The roofline's planned-exchange arm
        and the halo bench rows record this beside XLA's cost-analysis
        bytes."""
        ext = list(local_shape)
        w = self.width
        messages = 0
        bytes_sent = 0
        for spec in self.axis_specs:
            if spec.size > 1:
                face_shape = [
                    w if d == spec.axis else ext[d] for d in range(3)
                ]
                face = face_shape[0] * face_shape[1] * face_shape[2]
                if self.mode == "partitioned":
                    nparts = len(partition_bounds(
                        ext[spec.part_dim],
                        self._face_partitions(face_shape, itemsize),
                    ))
                else:
                    nparts = 1
                messages += 2 * nparts
                bytes_sent += 2 * face * itemsize
            if self.halo_order == "axis":
                ext[spec.axis] += 2 * w
        return {"messages": messages, "bytes_per_device": bytes_sent}

    def describe(self) -> Dict[str, Any]:
        """The built-plan record (the ``exchange_plan_built`` payload)."""
        return {
            "mesh": list(self.mesh.shape),
            "bc": self.bc.value,
            "width": self.width,
            "halo_order": self.halo_order,
            "transport": self.transport,
            "mode": self.mode,
            "partitions": (
                self.partitions if self.mode == "partitioned" else 1
            ),
            "min_part_bytes": self.min_part_bytes,
            "messages_per_exchange": self.messages_per_exchange(),
        }


def build_plan(
    mesh_cfg: MeshConfig,
    bc: BoundaryCondition,
    width: int = 1,
    halo_order: str = "axis",
    transport: str = "ppermute",
    mode: str = "monolithic",
    partitions: int = DEFAULT_PARTITIONS,
    min_part_bytes: Optional[int] = None,
) -> ExchangePlan:
    """Uncached plan constructor: precompute every permutation, the axis
    schedule and the partition dims for this exchange shape."""
    if mode not in ("monolithic", "partitioned"):
        raise ValueError(
            f"plan mode must be monolithic|partitioned, got {mode!r} "
            "(resolve 'auto' through the tuning cache before building)"
        )
    if mode == "partitioned" and transport != "ppermute":
        raise ValueError(
            "halo_plan='partitioned' applies to the ppermute transport; "
            "the DMA slab kernels ship whole faces by construction"
        )
    periodic = bc is BoundaryCondition.PERIODIC
    specs = []
    for axis, (name, size) in enumerate(
        zip(mesh_cfg.axis_names, mesh_cfg.shape)
    ):
        if size > 1:
            up = tuple(shift_perm(size, +1, periodic))
            down = tuple(shift_perm(size, -1, periodic))
        else:
            up = down = None
        # partition along the first in-plane dim (x faces split along y,
        # y/z faces along x): a fixed rule the IR partition checker can
        # re-derive from the sub-block shapes alone
        part_dim = min(d for d in range(3) if d != axis)
        specs.append(
            AxisExchangeSpec(
                axis=axis, name=name, size=size,
                perm_up=up, perm_down=down, part_dim=part_dim,
            )
        )
    return ExchangePlan(
        mesh=mesh_cfg,
        bc=bc,
        width=int(width),
        halo_order=halo_order,
        transport=transport,
        mode=mode,
        partitions=int(partitions),
        min_part_bytes=(
            part_min_bytes() if min_part_bytes is None else int(min_part_bytes)
        ),
        axis_specs=tuple(specs),
    )


# ---- the process plan cache -------------------------------------------------

_PLAN_CACHE: Dict[Tuple, ExchangePlan] = {}

# per-run dedup of the audit events: exchange() runs several times per
# trace (ping-pong loop bodies, residual programs, phase programs), and
# the reuse contract is "one exchange_plan_built per plan key per run"
_EVENT_ONCE: set = set()


def _event_once(name: str, key: str, **fields: Any) -> None:
    from heat3d_tpu import obs

    ledger = obs.get()
    tag = (ledger.run_id, name, key)
    if tag in _EVENT_ONCE:
        return
    _EVENT_ONCE.add(tag)
    ledger.event(name, key=key, **fields)


def clear_plan_cache() -> None:
    """Drop every cached plan (tests; plans are content-addressed, so
    production never needs this)."""
    _PLAN_CACHE.clear()


def drop_plans_for_mesh(mesh_shape) -> int:
    """Forget every cached plan keyed to ``mesh_shape`` — the elastic
    re-factorization hook (resilience/elastic.py): after a survivor-mesh
    re-plan the dead mesh's precomputed permutations can never be
    exchanged again in this process, and the survivor mesh builds fresh
    plans (audited by their own ``exchange_plan_built`` events). Returns
    how many plans were dropped."""
    shape = tuple(mesh_shape)
    gone = [k for k in _PLAN_CACHE if k[0] == shape]
    for k in gone:
        del _PLAN_CACHE[k]
    return len(gone)


def resolve_halo_plan(cfg: SolverConfig) -> str:
    """The concrete plan mode for ``cfg``: the tuning cache resolves
    ``'auto'`` at the entry points (tune.cache.resolve_config); any
    ``'auto'`` still standing here takes the static fallback
    (monolithic) — same belt-and-braces posture as the other knobs."""
    mode = getattr(cfg, "halo_plan", "monolithic")
    return "monolithic" if mode == "auto" else mode


def effective_halo_plan(cfg: SolverConfig) -> str:
    """The plan mode that actually EXECUTES for ``cfg`` in the current
    env: ``'auto'`` takes the static fallback, and ``HEAT3D_NO_PLAN``
    degrades partitioned to the ad-hoc monolithic schedule. Bench rows
    and sweep journals record THIS value — provenance must say which
    schedule ran, not which was requested (a requested-partitioned row
    measured on the ad-hoc path masquerading as partitioned would
    corrupt the very A/B the knob exists for)."""
    if os.environ.get(ENV_NO_PLAN):
        return "monolithic"
    return resolve_halo_plan(cfg)


def _plan_args(cfg: SolverConfig, width: int) -> Dict[str, Any]:
    """:func:`build_plan`'s keyword arguments for ``cfg``'s exchange at
    ``width`` ghost layers: the one transport and mode rule."""
    return {
        "width": int(width),
        "halo_order": cfg.halo_order,
        "transport": "dma" if cfg.halo == "dma" else "ppermute",
        "mode": resolve_halo_plan(cfg),
    }


def plan_for(cfg: SolverConfig, width: int = 1) -> ExchangePlan:
    """The cached plan for ``cfg``'s exchange at ``width`` ghost layers.

    Cache key = everything that shapes the exchange (mesh, BC, width,
    ordering, transport, plan mode) and nothing that doesn't (bc_value,
    dtype, grid size — the plan is shape-agnostic until ``apply``).
    Emits ``exchange_plan_built`` on a genuine build and
    ``plan_cache_hit`` on reuse, each once per (run, plan key)."""
    args = _plan_args(cfg, width)
    key = (
        cfg.mesh.shape,
        cfg.mesh.axis_names,
        cfg.stencil.bc,
        args["width"],
        args["halo_order"],
        args["transport"],
        args["mode"],
        DEFAULT_PARTITIONS,
        part_min_bytes(),  # env-overridable floor keys its own plans
    )
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _event_once("plan_cache_hit", plan.key)
        return plan
    plan = build_plan(cfg.mesh, cfg.stencil.bc, **args)
    _PLAN_CACHE[key] = plan
    _event_once("exchange_plan_built", plan.key, **plan.describe())
    return plan


def exchange_bytes(cfg: SolverConfig, width: int = 1) -> int:
    """Bytes one device sends in one exchange of ``cfg``'s field at
    ``width`` ghost layers, as :meth:`ExchangePlan.traffic` prices it:
    every face of a sharded axis in both directions, later axes' faces
    carrying the earlier ghosts. The fused and faces-direct kernel routes
    move the same faces. Priced from shapes on an uncached plan (no plan
    event); zero on a mesh of one device."""
    import jax.numpy as jnp

    plan = build_plan(cfg.mesh, cfg.stencil.bc, **_plan_args(cfg, width))
    itemsize = jnp.dtype(cfg.precision.storage).itemsize
    return plan.traffic(cfg.local_shape, itemsize)["bytes_per_device"]


def adhoc_exchange(u, cfg: SolverConfig, width: int = 1, bc_value: Any = None):
    """The pre-plan dispatch, kept verbatim as the ``HEAT3D_NO_PLAN``
    escape hatch and the reference arm of the plan-vs-ad-hoc parity
    tests (``halo_plan='partitioned'`` degrades to the monolithic ad-hoc
    structure here — the legacy path has no partitioned form)."""
    bcv = cfg.stencil.bc_value if bc_value is None else bc_value
    if cfg.halo == "dma":
        from heat3d_tpu.ops.halo_pallas import exchange_halo_dma

        return exchange_halo_dma(
            u, cfg.mesh, cfg.stencil.bc, bcv, width=width
        )
    if cfg.halo_order == "pairwise":
        return exchange_halo_pairwise(
            u, cfg.mesh, cfg.stencil.bc, bcv, width
        )
    return exchange_halo(u, cfg.mesh, cfg.stencil.bc, bcv, width)


def exchange_with_plan(
    u, cfg: SolverConfig, width: int = 1, bc_value: Any = None
):
    """Plan-routed ghost exchange: the ONE entry every step, superstep,
    phase, bench and ensemble program goes through. Must run inside
    shard_map over ``cfg.mesh``."""
    if os.environ.get(ENV_NO_PLAN):
        return adhoc_exchange(u, cfg, width, bc_value)
    bcv = cfg.stencil.bc_value if bc_value is None else bc_value
    return plan_for(cfg, width).apply(u, bcv)
