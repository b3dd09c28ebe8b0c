"""The judged program matrix the IR checkers certify.

Every case is a REAL program the production code builds —
``make_step_fn`` / ``make_superstep_fn`` (with and without the residual
psum) and the ``EnsembleSolver`` traced-bind executables — traced to a
closed jaxpr over a multi-device CPU mesh. Validity pruning reuses
``tune.space.enumerate_candidates`` (which builds the real solver and
raises the production error message), so the matrix can never drift from
what the framework actually accepts.

Device posture: the IR lint wants >= 4 host devices so the judged meshes
((2,2,1), (4,1,1), the b=2 x (2,1,1) ensemble hybrid) and their
collectives are real. :func:`ensure_devices` forces
``--xla_force_host_platform_device_count`` through ``XLA_FLAGS``
(``HEAT3D_IR_DEVICES``, default 4) — but only when the jax backend has
not initialized yet; a session that already booted single-device gets a
degraded single-shard matrix and the runner surfaces that as a warning
finding instead of silently certifying nothing.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

ENV_DEVICES = "HEAT3D_IR_DEVICES"
ENV_COMPILE = "HEAT3D_IR_COMPILE"

# grid edge for the judged matrix: small enough to trace in milliseconds,
# large enough that local extents admit tb up to 4 on every judged mesh
_GRID = 16
_GRID_UNEVEN = 18  # not divisible by 4 -> exercises the padded-shard pins


def wanted_devices() -> int:
    """The device count the full judged matrix needs (the (2,2,1) /
    (4,1,1) meshes and the ensemble hybrid all factor into 4)."""
    return int(os.environ.get(ENV_DEVICES, "4") or 4)


def ensure_devices() -> int:
    """Force a multi-device CPU backend for the judged meshes when still
    possible; returns the visible device count either way. (Shared
    implementation: analysis/hostdev.py — `lint --all` resolves the
    max posture across tiers through the same helper.)"""
    from heat3d_tpu.analysis.hostdev import ensure_host_devices

    return ensure_host_devices(wanted_devices())


def compile_enabled() -> bool:
    """``HEAT3D_IR_COMPILE=0`` skips the compiled memory-contract leg
    (trace-only lint — e.g. a laptop run that only wants the jaxpr
    families)."""
    return os.environ.get(ENV_COMPILE, "1").lower() not in ("0", "false")


@dataclasses.dataclass
class ProgramCase:
    """One traced program under certification.

    ``key`` is the config-key half of every finding fingerprint —
    checkers anchor findings on ``(checker, key, invariant)``, never on
    jaxpr pretty-printer text, so baselines survive jax upgrades."""

    key: str
    cfg: Any  # SolverConfig
    kind: str  # step | superstep | residual | ensemble_run | ensemble_residual
    path: str  # repo-relative builder module (finding location)
    fn: Any = None
    avals: Tuple[Any, ...] = ()
    compile: bool = False  # memory-contract leg compiles this case
    spatial_axes: Tuple[str, ...] = ("x", "y", "z")
    batch_axes: Tuple[str, ...] = ()
    mesh_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)
    # how many independent array exchanges one dynamic exchange group
    # legitimately carries: the leapfrog two-level carry (levels at
    # widths k*r and (k-1)*r — footprint checks that exact pair), the
    # CG solve's constant-build + initial-matvec pair, the varcoef
    # solution + coefficient-field pair. The collective checkers widen
    # the one-permute-pair-per-face expectation by this factor.
    carry_levels: int = 1
    _jaxpr: Any = None

    @property
    def k(self) -> int:
        return max(1, self.cfg.time_blocking)

    def jaxpr(self):
        if self._jaxpr is None:
            import jax

            self._jaxpr = jax.make_jaxpr(self.fn)(*self.avals)
        return self._jaxpr

    def compiled(self):
        import jax

        return jax.jit(self.fn).lower(*self.avals).compile()


def _case_key(cfg, kind: str) -> str:
    mesh = "x".join(str(p) for p in cfg.mesh.shape)
    dt = "bf16" if cfg.precision.storage == "bfloat16" else "fp32"
    bits = [
        cfg.stencil.kind,
        dt,
    ]
    if cfg.equation != "heat":
        # equation leg only when non-default (heat), so every fingerprint
        # minted before the eqn subsystem stays stable — the halo_plan
        # rule below, same reason
        bits.insert(0, cfg.equation)
    if cfg.integrator != "explicit-euler":
        # integrator leg only when non-default, same stability rule
        bits.insert(0, cfg.integrator)
    bits += [
        f"g{cfg.grid.shape[0]}",
        f"m{mesh}",
        f"tb{cfg.time_blocking}",
        cfg.halo_order,
    ]
    if cfg.halo_plan != "monolithic":
        # plan-mode key leg only when non-default, so every fingerprint
        # minted before the knob existed stays stable
        bits.append(cfg.halo_plan)
    if cfg.overlap:
        bits.append("overlap")
    bits.append(kind)
    return "/".join(bits)


def _solver_cases(
    base, space: Dict[str, Sequence[Any]], compile_keys: Sequence[str]
) -> List[ProgramCase]:
    """Expand one base config over ``space`` with the tuner's production
    validity pruning, building a traced case per surviving candidate."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.parallel.step import make_step_fn, make_superstep_fn
    from heat3d_tpu.parallel.topology import build_mesh
    from heat3d_tpu.tune.space import enumerate_candidates

    cases: List[ProgramCase] = []
    seen: set = set()
    for cand in enumerate_candidates(base, space, validate=True):
        if cand.prune is not None or cand.cfg is None or cand.cfg in seen:
            continue
        seen.add(cand.cfg)
        cfg = cand.cfg
        mesh = build_mesh(cfg.mesh)
        aval = jax.ShapeDtypeStruct(
            cfg.padded_shape, jnp.dtype(cfg.precision.storage)
        )
        mesh_sizes = dict(zip(cfg.mesh.axis_names, cfg.mesh.shape))
        kind = "superstep" if cfg.time_blocking > 1 else "step"
        builder = (
            make_superstep_fn(cfg, mesh)
            if cfg.time_blocking > 1
            else make_step_fn(cfg, mesh)
        )
        key = _case_key(cfg, kind)
        cases.append(
            ProgramCase(
                key=key,
                cfg=cfg,
                kind=kind,
                path="heat3d_tpu/parallel/step.py",
                fn=builder,
                avals=(aval,),
                compile=key in compile_keys,
                spatial_axes=cfg.mesh.axis_names,
                mesh_sizes=mesh_sizes,
            )
        )
        if cfg.time_blocking == 1 and not cfg.overlap:
            rkey = _case_key(cfg, "residual")
            cases.append(
                ProgramCase(
                    key=rkey,
                    cfg=cfg,
                    kind="residual",
                    path="heat3d_tpu/parallel/step.py",
                    fn=make_step_fn(cfg, mesh, with_residual=True),
                    avals=(aval,),
                    compile=rkey in compile_keys,
                    spatial_axes=cfg.mesh.axis_names,
                    mesh_sizes=mesh_sizes,
                )
            )
    return cases


def _ensemble_cases(num_devices: int) -> List[ProgramCase]:
    """The traced-bind EnsembleSolver executables: the pure-spatial
    factorization and the hybrid batch x space mesh (halo collectives
    must stay on the spatial axes)."""
    if num_devices < 4:
        return []
    from heat3d_tpu.core.config import (
        GridConfig,
        MeshConfig,
        SolverConfig,
    )
    from heat3d_tpu.serve.ensemble import BATCH_AXIS, EnsembleSolver
    from heat3d_tpu.serve.scenario import Scenario, ScenarioBatch

    cases: List[ProgramCase] = []
    members = [
        Scenario(alpha=0.3, bc_value=1.0, steps=5),
        Scenario(alpha=0.5, steps=7),
    ]
    for label, mesh_shape, batch_mesh in (
        ("b1xm2x2x1", (2, 2, 1), 1),
        ("b2xm2x1x1", (2, 1, 1), 2),
    ):
        base = SolverConfig(
            grid=GridConfig.cube(_GRID),
            mesh=MeshConfig(shape=mesh_shape),
            backend="jnp",
            time_blocking=2,
        )
        es = EnsembleSolver(
            ScenarioBatch(base, members), batch_mesh=batch_mesh
        )
        mesh_sizes = {BATCH_AXIS: batch_mesh}
        mesh_sizes.update(zip(base.mesh.axis_names, mesh_shape))
        for name, fn, args in es.ir_programs():
            cases.append(
                ProgramCase(
                    key=f"ensemble/{label}/tb{es.cfg.time_blocking}/{name}",
                    cfg=es.cfg,
                    kind=f"ensemble_{name}",
                    path="heat3d_tpu/serve/ensemble.py",
                    fn=fn,
                    avals=tuple(args),
                    spatial_axes=es.cfg.mesh.axis_names,
                    batch_axes=(BATCH_AXIS,),
                    mesh_sizes=mesh_sizes,
                )
            )
    # the variable-coefficient traced bind (PR 19): per-member FIELD
    # arrays ride as a fourth runtime input sharded like the solution —
    # its exchange topology (two ghost rides per update through one
    # plan) certifies beside the constant-coefficient programs
    vc_base = SolverConfig(
        grid=GridConfig.cube(_GRID),
        mesh=MeshConfig(shape=(2, 2, 1)),
        backend="jnp",
    )
    vc_members = [
        Scenario(coef_field=("checker", 0, 0.5, 1.5), steps=5),
        Scenario(coef_field=("lognormal", 3, 0.4, 1.8), steps=7,
                 bc_value=1.0),
    ]
    es = EnsembleSolver(ScenarioBatch(vc_base, vc_members), batch_mesh=1)
    mesh_sizes = {BATCH_AXIS: 1}
    mesh_sizes.update(zip(vc_base.mesh.axis_names, (2, 2, 1)))
    for name, fn, args in es.ir_programs():
        cases.append(
            ProgramCase(
                key=f"ensemble/coef-field/b1xm2x2x1/{name}",
                cfg=es.cfg,
                kind=f"ensemble_{name}",
                path="heat3d_tpu/serve/ensemble.py",
                fn=fn,
                avals=tuple(args),
                spatial_axes=es.cfg.mesh.axis_names,
                batch_axes=(BATCH_AXIS,),
                mesh_sizes=mesh_sizes,
                carry_levels=2,  # solution + field per update
            )
        )
    return cases


def judged_matrix(num_devices: Optional[int] = None) -> List[ProgramCase]:
    """The full certification matrix for the current device posture."""
    import jax

    from heat3d_tpu.core.config import (
        GridConfig,
        MeshConfig,
        Precision,
        SolverConfig,
        StencilConfig,
    )

    n = len(jax.devices()) if num_devices is None else num_devices
    if n >= 4:
        meshes = [(2, 2, 1), (4, 1, 1)]
    elif n >= 2:
        meshes = [(2, 1, 1)]
    else:
        meshes = [(1, 1, 1)]

    # The compiled (memory-contract) subset: one representative per
    # structural family — exchange step, deep-tb superstep, corner-reading
    # stencil, mixed precision, residual reduction.
    compile_keys = {
        _case_key(c, k)
        for c, k in _compile_targets(meshes[0])
    }

    cases: List[ProgramCase] = []
    for mesh_shape in meshes:
        mesh = MeshConfig(shape=mesh_shape)
        base7 = SolverConfig(
            grid=GridConfig.cube(_GRID), mesh=mesh, backend="jnp"
        )
        base27 = dataclasses.replace(base7, stencil=StencilConfig("27pt"))
        base_bf16 = dataclasses.replace(base7, precision=Precision.bf16())
        cases += _solver_cases(
            base7,
            {
                "time_blocking": (1, 2, 3, 4),
                "halo_order": ("axis", "pairwise"),
                "overlap": (False, True),
                # plan-built programs certify beside the classic path:
                # partitioned sub-block permutes must still compose to
                # the exact inverse-pair ring shifts (ANL601-607) and
                # the full ghost footprint (ANL701)
                "halo_plan": ("monolithic", "partitioned"),
            },
            compile_keys,
        )
        cases += _solver_cases(
            base27,
            {
                "time_blocking": (1, 2, 3),
                "halo_plan": ("monolithic", "partitioned"),
            },
            compile_keys,
        )
        cases += _solver_cases(
            base_bf16, {"time_blocking": (1, 2)}, compile_keys
        )
    # the spec-built arm (PR 11): one program family per registered
    # non-heat equation — the eqn compiler's lowered taps must yield
    # CERTIFIED programs (neighbor-graph bijections, ghost footprint,
    # dtype contract), not just tested ones. Asymmetric chains
    # (advection) and center-shifted taps (reaction) ride the same
    # judged invariants as heat; heat itself IS the base7/base27 matrix
    # above (its spec lowers bit-identically).
    from heat3d_tpu.eqn import FAMILIES

    eqn_mesh = MeshConfig(shape=meshes[0])
    for fam_name in sorted(FAMILIES):
        if fam_name == "heat":
            continue
        if fam_name == "wave":
            # wave's update is the leapfrog two-level carry, not the
            # explicit sweep (the config layer couples them) — its
            # programs certify in _timeint_cases below
            continue
        fam = FAMILIES[fam_name]
        cases += _solver_cases(
            SolverConfig(
                grid=GridConfig.cube(_GRID),
                stencil=StencilConfig(fam.kinds[0]),
                mesh=eqn_mesh,
                backend="jnp",
                equation=fam_name,
            ),
            {"time_blocking": (1, 2)},
            compile_keys,
        )
    # one uneven decomposition: storage padding + bc-pin masks in the IR
    if n >= 4:
        cases += _solver_cases(
            SolverConfig(
                grid=GridConfig.cube(_GRID_UNEVEN),
                mesh=MeshConfig(shape=(4, 1, 1)),
                backend="jnp",
            ),
            {
                "time_blocking": (1, 3),
                "halo_plan": ("monolithic", "partitioned"),
            },
            compile_keys,
        )
    cases += _timeint_cases(n)
    cases += _ensemble_cases(n)
    return cases


def _timeint_cases(num_devices: int) -> List[ProgramCase]:
    """The time-integrator program families (PR 19): the wave leapfrog
    two-level carry (step, superstep, residual), the implicit-CG
    keep-masked solve, and the variable-coefficient flux step — traced
    over the widest judged mesh. Kinds are integrator-prefixed ON
    PURPOSE: the exact ``step``/``superstep`` round-trip budget (ANL803)
    is an explicit-sweep contract (leapfrog legitimately up-converts two
    carry levels per application), while the generic collective /
    replication / alien-dtype invariants judge every kind — and the
    ``*_residual`` kinds keep the full residual-psum contract
    (ANL607/ANL802)."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.core.config import GridConfig, MeshConfig, SolverConfig
    from heat3d_tpu.parallel.topology import build_mesh
    from heat3d_tpu.timeint import cg as ti_cg
    from heat3d_tpu.timeint import coeffield, leapfrog

    if num_devices >= 4:
        mesh_shape = (2, 2, 1)
    elif num_devices >= 2:
        mesh_shape = (2, 1, 1)
    else:
        mesh_shape = (1, 1, 1)
    mesh_cfg = MeshConfig(shape=mesh_shape)
    cases: List[ProgramCase] = []

    def add(cfg, kind, path, fn, avals, levels=1):
        cases.append(
            ProgramCase(
                key=_case_key(cfg, kind),
                cfg=cfg,
                kind=kind,
                path=path,
                fn=fn,
                avals=avals,
                spatial_axes=cfg.mesh.axis_names,
                mesh_sizes=dict(zip(cfg.mesh.axis_names, cfg.mesh.shape)),
                carry_levels=levels,
            )
        )

    wave = SolverConfig(
        grid=GridConfig.cube(_GRID),
        mesh=mesh_cfg,
        backend="jnp",
        equation="wave",
        integrator="leapfrog",
    )
    mesh = build_mesh(wave.mesh)
    aval = jax.ShapeDtypeStruct(
        wave.padded_shape, jnp.dtype(wave.precision.storage)
    )
    carry = (aval, aval)
    lf_path = "heat3d_tpu/timeint/leapfrog.py"
    add(wave, "leapfrog_step", lf_path,
        leapfrog.make_step_fn(wave, mesh), (carry,), levels=2)
    add(wave, "leapfrog_residual", lf_path,
        leapfrog.make_step_fn(wave, mesh, with_residual=True), (carry,),
        levels=2)
    wave2 = dataclasses.replace(wave, time_blocking=2)
    add(wave2, "leapfrog_superstep", lf_path,
        leapfrog.make_superstep_fn(wave2, mesh), (carry,), levels=2)

    cgc = SolverConfig(
        grid=GridConfig.cube(_GRID),
        mesh=mesh_cfg,
        backend="jnp",
        integrator="implicit-cg",
    )
    cg_path = "heat3d_tpu/timeint/cg.py"
    # CG's top level runs TWO exchanges (the zero-field boundary-inflow
    # build and the initial-residual matvec) in one group; the fori body
    # group has its own single matvec exchange
    add(cgc, "cg_step", cg_path, ti_cg.make_step_fn(cgc, mesh), (aval,),
        levels=2)
    add(cgc, "cg_residual", cg_path,
        ti_cg.make_step_fn(cgc, mesh, with_residual=True), (aval,),
        levels=2)

    vc = SolverConfig(
        grid=GridConfig.cube(_GRID), mesh=mesh_cfg, backend="jnp"
    )
    # solution + coefficient field both ride the plan each update
    add(vc, "coef_step", "heat3d_tpu/timeint/coeffield.py",
        coeffield.make_varcoef_step_fn(vc, mesh), (aval, aval), levels=2)
    return cases


def _compile_targets(mesh_shape) -> List[Tuple[Any, str]]:
    from heat3d_tpu.core.config import (
        GridConfig,
        MeshConfig,
        Precision,
        SolverConfig,
        StencilConfig,
    )

    mesh = MeshConfig(shape=mesh_shape)
    base = SolverConfig(grid=GridConfig.cube(_GRID), mesh=mesh, backend="jnp")
    return [
        (base, "step"),
        (base, "residual"),
        (dataclasses.replace(base, time_blocking=3), "superstep"),
        (
            dataclasses.replace(base, stencil=StencilConfig("27pt")),
            "step",
        ),
        (
            dataclasses.replace(
                base, precision=Precision.bf16(), time_blocking=2
            ),
            "superstep",
        ),
    ]
