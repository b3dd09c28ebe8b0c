"""The search space: a declarative knob lattice over ``SolverConfig``
with validity pruning.

A candidate is ``base config + knob overrides``. Pruning reuses the
framework's OWN validation instead of a parallel rule set that would
drift: ``SolverConfig.__post_init__`` rejects structurally invalid
combos (pairwise ordering with a corner-reading stencil, dma+pairwise,
dma+overlap, ...), and :func:`prune_reason` then builds the solver and
forces the multistep program the hot loop would run — every capability
gate the real run would hit (dma off-TPU, pallas unsupported here,
overlap local-extent minima, overlap/tb mutual exclusion) raises the
same ``ValueError`` it would raise in production, and
the candidate is pruned with that exact message instead of burning
measurement time. Solver construction builds jit WRAPPERS only (no
trace, no compile), so pruning costs milliseconds per candidate.

Two consumers share this pruning so their config universes cannot
drift: the measurement driver (:mod:`heat3d_tpu.tune.measure`) and the
IR verifier's judged matrix (:mod:`heat3d_tpu.analysis.ir.programs`,
``heat3d lint --ir``) — a config the tuner would measure is exactly a
config the verifier certifies, with the same validity rules.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

from heat3d_tpu.core.config import MeshConfig, SolverConfig, dims_create

# The default knob lattice. `mesh` is deliberately absent: factorization
# candidates depend on the device count (see mesh_candidates) and default
# to "don't search" — an explicit topology is usually the operator's call.
DEFAULT_KNOBS: Dict[str, Tuple[Any, ...]] = {
    "backend": ("jnp", "pallas", "conv"),
    "halo": ("ppermute", "dma"),
    "overlap": (False, True),
    # deep temporal blocking searched to k=4: tb=3..4 ride the fused
    # k-sweep streaming kernel on TPU (jnp ring recompute elsewhere);
    # undersized local extents and pairwise+deep-tb combos are pruned by
    # the production validation (prune_reason forces the real superstep
    # build). The measured winner already pays the redundant ring
    # recompute, so the search needs no cost-model correction — but the
    # row it lands carries cost_redundant_flops_frac for the report.
    "time_blocking": (1, 2, 3, 4),
    "halo_order": ("axis", "pairwise"),
    # persistent-exchange-plan mode (parallel/plan.py): partitioned =
    # early-bird sub-block sends (more, smaller messages; pins the
    # exchange path). Value-identical to monolithic by construction, so
    # the A/B is purely a transport-schedule measurement; dma+partitioned
    # combos are config-rejected and pruned.
    "halo_plan": ("monolithic", "partitioned"),
}

# knob-value parsers for CLI `--knob name=v1,v2` strings
_BOOL = {"0": False, "false": False, "1": True, "true": True}


def parse_knob_values(name: str, spec: str) -> Tuple[Any, ...]:
    """Parse a CLI value list for ``name``: ``overlap=0,1``,
    ``time_blocking=1,2``, ``mesh=8x1x1,2x2x2``, ``halo=ppermute,dma``."""
    vals: List[Any] = []
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if name == "overlap":
            try:
                vals.append(_BOOL[tok.lower()])
            except KeyError:
                raise ValueError(
                    f"overlap value {tok!r} (want 0/1/true/false)"
                ) from None
        elif name == "time_blocking":
            k = int(tok)
            if k < 1:
                raise ValueError(
                    "searched time_blocking values must be concrete "
                    "(>= 1): 0 means 'resolve through the cache this "
                    "search is about to write'"
                )
            vals.append(k)
        elif name == "mesh":
            dims = tuple(int(x) for x in tok.lower().split("x"))
            if len(dims) != 3:
                raise ValueError(f"mesh value {tok!r} (want PxQxR)")
            vals.append(dims)
        else:
            if name in ("halo", "halo_plan") and tok == "auto":
                raise ValueError(
                    f"searched {name} values must be concrete: 'auto' "
                    "means 'resolve through the cache this search is "
                    "about to write'"
                )
            vals.append(tok)
    if not vals:
        raise ValueError(f"no values for knob {name!r}")
    return tuple(vals)


def check_concrete(space: Dict[str, Sequence[Any]]) -> None:
    """Reject non-concrete knob values in a programmatic search space
    (``time_blocking`` 0, ``halo`` 'auto'): a trial measuring 'auto'
    would silently measure whatever the solver statically resolves while
    labeling the row with the auto sentinel — mislabeled provenance and a
    cache entry resolution must then reject as unresolved."""
    for name, values in space.items():
        for v in values:
            if (name == "time_blocking" and isinstance(v, int) and v < 1) or (
                name in ("halo", "halo_plan") and v == "auto"
            ):
                raise ValueError(
                    f"search space knob {name}={v!r} is not concrete — "
                    "auto sentinels cannot be measured as candidates"
                )


def mesh_candidates(num_devices: int) -> Tuple[Tuple[int, int, int], ...]:
    """Distinct factorization candidates for ``num_devices``: the 1D
    x-slab (the reference's default), the balanced 3D block
    (MPI_Dims_create analogue), and the 2D pencil between them."""
    out = [(num_devices, 1, 1), dims_create(num_devices)]
    for px in range(2, num_devices + 1):
        if num_devices % px == 0:
            out.append((px, num_devices // px, 1))
            break
    seen: List[Tuple[int, int, int]] = []
    for m in out:
        if m not in seen:
            seen.append(m)
    return tuple(seen)


def survivor_candidates(
    base: SolverConfig, num_devices: int, validate: bool = True
) -> List[SolverConfig]:
    """Certified degraded configs for ``base`` over ``num_devices``
    surviving devices — the elastic-degradation re-plan source
    (``resilience/elastic.py``; docs/RESILIENCE.md "Elastic
    degradation").

    Candidates are the same factorizations the tuner searches
    (:func:`mesh_candidates`, slab-first), filtered by THREE production
    gates so a degraded run only ever lands on a config a normal run
    could have used:

    - ``SolverConfig.__post_init__`` (structural validity — the
      ``apply_knobs`` path);
    - the **re-stitch contract**: the candidate's ``padded_shape`` must
      equal ``base``'s, because the checkpoint being stitched onto the
      survivor mesh was saved in ``base``'s storage shape (cross-mesh
      resume across different bc-paddings is unsupported —
      ``HeatSolver3D.load_checkpoint`` rejects it);
    - :func:`prune_reason` building the real solver (capability gates:
      backend, transport, local-extent minima for the configured
      time_blocking).
    """
    out: List[SolverConfig] = []
    if num_devices < 1:
        return out
    for m in mesh_candidates(num_devices):
        try:
            cfg = apply_knobs(base, {"mesh": m})
        except ValueError:
            continue
        if cfg.padded_shape != base.padded_shape:
            continue
        if validate and prune_reason(cfg) is not None:
            continue
        out.append(cfg)
    return out


def apply_knobs(base: SolverConfig, knobs: Dict[str, Any]) -> SolverConfig:
    """``base`` with ``knobs`` overridden (``mesh`` takes a (Px,Py,Pz)
    tuple). Raises ``ValueError`` for structurally invalid combos —
    ``SolverConfig.__post_init__`` is the single source of those rules."""
    kw: Dict[str, Any] = {}
    for k, v in knobs.items():
        if k == "mesh":
            kw["mesh"] = MeshConfig(shape=tuple(v))
        else:
            kw[k] = v
    return dataclasses.replace(base, **kw)


def knob_label(base: SolverConfig, space: Dict[str, Sequence[Any]],
               overrides: Dict[str, Any]) -> Dict[str, str]:
    """The FULL knob assignment of a candidate as strings (base values
    fill the knobs not overridden) — the shape ``tune.decide.pair_rows``
    pairs on, so every searched knob appears in every label."""
    label: Dict[str, str] = {}
    for name in space:
        if name in overrides:
            v = overrides[name]
        elif name == "mesh":
            v = base.mesh.shape
        else:
            v = getattr(base, name)
        if name == "mesh":
            v = "x".join(str(x) for x in v)
        elif isinstance(v, bool):
            v = int(v)
        label[name] = str(v)
    return label


def prune_reason(cfg: SolverConfig) -> Optional[str]:
    """Why ``cfg`` cannot run in the CURRENT environment, or None.

    Builds the solver and forces the multistep program (jit wrappers
    only — nothing traces or compiles), so the gates are the production
    gates: backend capability, transport/platform rules, overlap and
    temporal-blocking constraints, mesh/device availability."""
    from heat3d_tpu.models.heat3d import HeatSolver3D

    try:
        solver = HeatSolver3D(cfg)
        # the superstep/time-blocking constraints are validated lazily on
        # first use of the fixed-step loop — force them now
        solver._multistep  # noqa: B018 - building IS the validation
    except (ValueError, NotImplementedError, ImportError) as e:
        return f"{type(e).__name__}: {str(e)[:160]}"
    return None


@dataclasses.dataclass(frozen=True)
class Candidate:
    knobs: Dict[str, str]  # full stringified knob assignment (the label)
    overrides: Dict[str, Any]  # the raw knob overrides applied to base
    cfg: Optional[SolverConfig]  # None when construction itself failed
    prune: Optional[str]  # why it was pruned, or None = measurable


def enumerate_candidates(
    base: SolverConfig,
    space: Optional[Dict[str, Sequence[Any]]] = None,
    validate: bool = True,
) -> List[Candidate]:
    """The pruned candidate list for ``base`` over ``space`` (default
    :data:`DEFAULT_KNOBS`). The FIRST candidate is always ``base`` itself
    (the static default — the speedup-vs-default reference, never
    pruned for capability unless it genuinely cannot run). Duplicates
    (overrides reproducing the base config) are dropped."""
    space = dict(space if space is not None else DEFAULT_KNOBS)
    check_concrete(space)
    names = list(space)
    out: List[Candidate] = []
    seen: set = set()

    def add(overrides: Dict[str, Any]) -> None:
        label = knob_label(base, space, overrides)
        try:
            cfg = apply_knobs(base, overrides)
        except ValueError as e:
            out.append(
                Candidate(label, overrides, None, f"invalid: {str(e)[:160]}")
            )
            return
        if cfg in seen:
            return
        seen.add(cfg)
        reason = prune_reason(cfg) if validate else None
        out.append(Candidate(label, overrides, cfg, reason))

    add({})  # the static default rides first
    for values in itertools.product(*(space[n] for n in names)):
        add(dict(zip(names, values)))
    return out
