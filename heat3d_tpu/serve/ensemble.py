"""EnsembleSolver — the leading batch axis through the distributed step.

One compiled SPMD program advances B independent scenarios (distinct
initial conditions, Dirichlet boundary values, diffusivities/timesteps,
step budgets) over one structural config. The batch dimension threads
through the existing machinery: the local per-member update IS the
portable chain step (``parallel.step._local_step`` / ``_local_stepk``
semantics — exchange, tap chain, ghost-ring pinning), ``vmap``-mapped
over the members a device holds, inside one ``shard_map`` over a mesh
that can factorize over batch, space, or both.

Two coefficient-binding modes, because XLA treats constants and
parameters differently at codegen:

- ``bind='traced'`` (default — the serving mode): per-member taps,
  boundary values, and budgets are RUNTIME INPUTS of one compiled
  program, so a shape bucket compiles once and serves any member values
  (the compile-amortization the queue exists for). Member results are
  bitwise-invariant to batch packing (B=1 equals B=64 member-wise, and
  both equal the same parametric program with no batch axis at all), and
  match solo :class:`HeatSolver3D` runs to final-ulp rounding — NOT
  bitwise, because the solo program bakes its coefficients as XLA
  constants and constant-vs-parameter codegen may contract FMAs
  differently.
- ``bind='baked'``: per-member coefficients are compile-time constants,
  and each member runs ITS OWN executable — literally the solo
  ``make_pair_multistep_fn`` program over the spatial mesh, driven through
  the ensemble's batched state layout. Bitwise-identical to B
  independent :class:`HeatSolver3D` runs BY CONSTRUCTION (the tier-1
  acceptance proof; stacking members into one XLA module was measured
  to perturb cross-member fusion by a final ulp on CPU, so the
  certification mode refuses to share a module), at the price of B
  compiles + B dispatches per call. Requires the batch axis unsharded
  (``batch_mesh == 1``: per-member constants cannot vary across the
  devices of one SPMD program).

Batch-aware sharding: ``batch_mesh = Pb`` builds the 4-axis mesh
``('b', 'x', 'y', 'z')`` over ``Pb * Px*Py*Pz`` devices — pure batch
parallel (Pb = ndev, spatial mesh (1,1,1): zero halo traffic), pure
spatial (Pb = 1), or hybrid. Halo collectives run over the spatial axes
only; members are independent, so the batch axis needs no communication
beyond the residual psum. The tune cache resolves ``auto`` knobs through
a batch-shape-bucketed key (``tune.cache.cache_key(batch_size=B)``).

Scope: the ensemble path runs the portable jnp chain compute on the
axis-ordered ppermute exchange. The Pallas kernel routes (direct,
streamk, DMA) bake taps into kernel constants and stay single-tenant —
the ensemble's win is packing + compile amortization, not kernel fusion.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from heat3d_tpu import obs
from heat3d_tpu.core import golden
from heat3d_tpu.core.config import SolverConfig
from heat3d_tpu.core.stencils import (
    decompose_mehrstellen,
    flat_taps,
    mehrstellen_enabled,
)
from heat3d_tpu.obs.trace import named_phase
from heat3d_tpu.ops.stencil_jnp import (
    _apply_mehrstellen_padded,
    apply_taps_padded,
    apply_taps_padded_params,
    emission_positions,
    residual_sumsq,
)
from heat3d_tpu.parallel.plan import exchange_with_plan
from heat3d_tpu.parallel.step import (
    _fill_mid_ghosts,
    _pin_padding,
    _solver_taps,
    pair_plan,
)
from heat3d_tpu.serve.scenario import ScenarioBatch
from heat3d_tpu.utils import checkpoint as ckpt
from jax import shard_map
from heat3d_tpu.utils.logging import get_logger

log = get_logger(__name__)

BATCH_AXIS = "b"


def _resolve_base(base: SolverConfig, batch_size: int) -> SolverConfig:
    """Auto-knob resolution for the ensemble. ``backend='auto'`` and
    ``halo='auto'`` are pinned to the chain/ppermute FIRST — the solo
    tune cache's winner for them is typically a single-tenant kernel
    route (pallas/dma), which the ensemble cannot run; letting the cache
    resolve them would turn a default config into a constructor error.
    Only ``time_blocking=0`` then resolves through the batch-bucketed
    cache key (the same belt-and-braces posture as HeatSolver3D's
    constructor: resolution is optional, and an unimportable tune
    package must not break serving)."""
    kw = {}
    if base.backend == "auto":
        kw["backend"] = "jnp"
    if base.halo == "auto":
        kw["halo"] = "ppermute"
    if kw:
        base = dataclasses.replace(base, **kw)
    try:
        from heat3d_tpu.tune.cache import resolve_config

        return resolve_config(base, batch_size=batch_size)
    except Exception:  # noqa: BLE001 - resolution is optional
        if base.time_blocking == 0:
            return dataclasses.replace(base, time_blocking=1)
        return base


class EnsembleSolver:
    """B scenarios, one compiled program. See the module docstring.

    Usage::

        batch = ScenarioBatch(SolverConfig(grid=GridConfig.cube(64),
                                           backend="jnp"),
                              [Scenario(alpha=0.3, bc_value=1.0),
                               Scenario(alpha=0.5, steps=200)])
        es = EnsembleSolver(batch)          # traced binding, batch_mesh=1
        u = es.init_state()                 # (B, *padded_shape), sharded
        u = es.run(u, es.budgets)           # per-member budgets (masked)
        fields = es.gather(u)               # (B, *grid) on host
    """

    def __init__(
        self,
        batch: ScenarioBatch,
        batch_mesh: int = 1,
        bind: str = "traced",
        devices=None,
    ):
        if bind not in ("traced", "baked"):
            raise ValueError(f"bind must be traced|baked, got {bind!r}")
        self.batch = batch
        self.B = len(batch)
        self.bind = bind
        self.batch_mesh = int(batch_mesh)
        cfg = _resolve_base(batch.base, self.B)
        if cfg.integrator != "explicit-euler":
            raise ValueError(
                f"integrator={cfg.integrator!r}: the ensemble packs the "
                "explicit sweep (and its variable-coefficient flux form); "
                "the leapfrog two-level carry and the CG solve are "
                "single-tenant — run them through HeatSolver3D "
                "(docs/INTEGRATORS.md)"
            )
        self._varcoef = bool(getattr(batch, "has_coef_fields", False))
        if self._varcoef and bind != "traced":
            raise ValueError(
                "coefficient-field members need bind='traced': the field "
                "arrays are runtime inputs of one shared program (the "
                "baked binding dispatches constant-coefficient solo "
                "executables)"
            )
        if cfg.backend in ("pallas", "conv"):
            # only an EXPLICIT kernel/conv request reaches here —
            # 'auto' was pinned to the chain before cache resolution
            raise ValueError(
                f"backend={cfg.backend!r} bakes its coefficients into the "
                "kernel/conv program; the ensemble threads per-member "
                "coefficients as runtime inputs — use backend 'jnp' (or "
                "'auto', which the ensemble pins to the chain)"
            )
        if cfg.halo != "ppermute":
            raise ValueError(
                f"halo={cfg.halo!r}: the ensemble path runs the portable "
                "axis-ordered ppermute exchange (the DMA kernels are "
                "single-tenant)"
            )
        if cfg.halo_order != "axis":
            raise ValueError(
                "halo_order='pairwise' is a single-tenant exchange A/B "
                "knob; the ensemble pins axis ordering"
            )
        if cfg.overlap:
            raise ValueError(
                "overlap=True splits the step for a single tenant; the "
                "ensemble's members already fill the schedule — drop it"
            )
        # the ensemble's compute route is the chain; record it concretely
        cfg = dataclasses.replace(cfg, backend="jnp")
        k = cfg.time_blocking
        if self._varcoef and k > 1:
            raise ValueError(
                f"time_blocking={k} with coefficient fields: the "
                "superstep ring recompute carries the solution only — "
                "the flux form needs the field's ghosts every update "
                "(tb=1; docs/INTEGRATORS.md)"
            )
        if k > 1 and min(cfg.local_shape) < max(3, k):
            raise ValueError(
                f"time_blocking={k} needs local extents >= {max(3, k)} "
                f"(k ghost layers plus the shrinking recompute rings), "
                f"got {cfg.local_shape}"
            )
        self.cfg = cfg
        self.k = max(1, k)

        if self.batch_mesh < 1 or self.B % self.batch_mesh:
            raise ValueError(
                f"batch_mesh={batch_mesh} must divide the batch size "
                f"{self.B}"
            )
        if bind == "baked" and self.batch_mesh != 1:
            raise ValueError(
                "bind='baked' needs batch_mesh=1: members sharded across "
                "devices would need per-device constants, which one SPMD "
                "program cannot carry — use bind='traced' to factorize "
                "the mesh over batch"
            )
        total = self.batch_mesh * cfg.mesh.num_devices
        avail = list(devices) if devices is not None else jax.devices()
        if len(avail) < total:
            raise ValueError(
                f"ensemble mesh b={self.batch_mesh} x space "
                f"{cfg.mesh.shape} needs {total} devices, only "
                f"{len(avail)} visible"
            )
        dev = np.asarray(avail[:total]).reshape(
            (self.batch_mesh,) + cfg.mesh.shape
        )
        self.mesh = Mesh(dev, (BATCH_AXIS,) + cfg.mesh.axis_names)
        self.spec = P(BATCH_AXIS, *cfg.mesh.axis_names)
        self.sharding = NamedSharding(self.mesh, self.spec)
        self._member_spec = NamedSharding(self.mesh, P(BATCH_AXIS))

        self._build_coefficients()
        self._build_programs()

    # ---- coefficient packing ---------------------------------------------

    def _build_coefficients(self) -> None:
        cfg = self.cfg
        compute_dtype = jnp.dtype(cfg.precision.compute)
        storage_dtype = jnp.dtype(cfg.precision.storage)
        self.budgets = np.asarray(
            [self.batch.member_steps(m) for m in range(self.B)],
            dtype=np.int32,
        )
        self._BCV = np.asarray(
            [m.bc_value for m in self.batch.members], dtype=np.float64
        ).astype(storage_dtype)
        self._BCV_dev = jax.device_put(
            jnp.asarray(self._BCV), self._member_spec
        )
        if self._varcoef:
            # per-member coefficient FIELDS, (B, *padded), sharded like
            # the solution. Storage padding stays ZERO: a=0 kills the
            # face flux out of the pad — the same Dirichlet rule the
            # ghost exchange applies (timeint.coeffield). Fields are
            # deterministic from the scenario spec tuples, so rebinds
            # and supervised restarts rebuild them — never checkpointed.
            A = np.zeros((self.B,) + tuple(cfg.padded_shape), np.float64)
            sl = tuple(slice(0, g) for g in cfg.grid.shape)
            for m in range(self.B):
                A[(m,) + sl] = self.batch.member_coef_field(m)
            self._A = A.astype(storage_dtype)
            self._DT = np.asarray(
                [self.batch.member_dt(m) for m in range(self.B)],
                dtype=np.float64,
            ).astype(compute_dtype)
            self._W = self._COEF = None
            self._mehrstellen = False
            self._A_dev = jax.device_put(jnp.asarray(self._A), self.sharding)
            self._DT_dev = jax.device_put(
                jnp.asarray(self._DT), self._member_spec
            )
            return
        nominal = _solver_taps(cfg)
        self._flat = flat_taps(nominal)
        positions = emission_positions(self._flat)
        member_taps = [self.batch.member_taps(m) for m in range(self.B)]
        # host-side double -> compute-dtype cast, ONE rounding — exactly
        # jnp.asarray(python_float, compute_dtype) on the baked path
        self._W = np.asarray(
            [
                [t[di + 1, dj + 1, dk + 1] for (di, dj, dk) in positions]
                for t in member_taps
            ],
            dtype=np.float64,
        ).astype(compute_dtype)
        # the separable S+F route follows the same env gate as the solo
        # apply; members share decomposability (same stencil kind, same
        # footprint), so the route is uniform across the batch
        coeffs = [decompose_mehrstellen(t) for t in member_taps]
        self._mehrstellen = mehrstellen_enabled() and all(
            c is not None for c in coeffs
        )
        self._COEF = (
            np.asarray(coeffs, dtype=np.float64).astype(compute_dtype)
            if self._mehrstellen
            else None
        )
        # upload ONCE per (re)bind: the arrays are fixed for the batch,
        # and run()/step calls may fire many times per bind (the queue's
        # snapshot loop, the bench's timed repeats)
        self._W_dev = jax.device_put(jnp.asarray(self._W), self._member_spec)
        self._C_dev = (
            jax.device_put(jnp.asarray(self._COEF), self._member_spec)
            if self._COEF is not None
            else jnp.zeros((self.B, 1), jnp.float32)  # placeholder, unused
        )

    @property
    def storage_dtype(self):
        return jnp.dtype(self.cfg.precision.storage)

    # ---- the member update (traced binding) ------------------------------

    def _member_apply(self, up, w, coef):
        cfg = self.cfg
        compute_dtype = jnp.dtype(cfg.precision.compute)
        out_dtype = jnp.dtype(cfg.precision.storage)
        if self._mehrstellen:
            return _apply_mehrstellen_padded(
                up.astype(compute_dtype), tuple(coef), compute_dtype
            ).astype(out_dtype)
        return apply_taps_padded_params(
            up, self._flat, w, compute_dtype=compute_dtype,
            out_dtype=out_dtype,
        )

    def _member_step(self, ul, w, coef, bcv):
        """One member's single update — the parametric mirror of
        ``parallel.step._local_step`` (same exchange, same chain emission,
        same padding pin; coefficients traced). The exchange rides the
        shared persistent plan (parallel.plan) with the member's TRACED
        boundary value as the apply-time argument, so one plan serves
        every member and every bucket of this mesh shape."""
        cfg = self.cfg
        with named_phase("halo_exchange"):
            up = exchange_with_plan(ul, cfg, 1, bcv)
        with named_phase("stencil"):
            out = self._member_apply(up, w, coef)
            return _pin_padding(out, cfg, bc_value=bcv)

    def _member_superstep(self, ul, w, coef, bcv):
        """One member's k-update superstep — the parametric mirror of
        ``parallel.step._local_stepk`` (width-k exchange, shrinking
        ghost-ring recompute, storage-dtype round trips)."""
        cfg, k = self.cfg, self.k
        with named_phase("halo_exchange"):
            cur = exchange_with_plan(ul, cfg, k, bcv)
        with named_phase("stencil"):
            for j in range(k):
                cur = self._member_apply(cur, w, coef)
                rings = k - 1 - j
                if rings > 0:
                    cur = _fill_mid_ghosts(cur, cfg, rings, bc_value=bcv)
            return _pin_padding(cur, cfg, bc_value=bcv)

    def _vmapped(self, member_fn):
        if self._mehrstellen:
            return lambda u_b, W_b, C_b, bc_b: jax.vmap(member_fn)(
                u_b, W_b, C_b, bc_b
            )
        # no coef array: close a None in per member (vmap cannot map None)
        return lambda u_b, W_b, C_b, bc_b: jax.vmap(
            lambda u, w, bc: member_fn(u, w, None, bc)
        )(u_b, W_b, bc_b)

    # ---- compiled programs ------------------------------------------------

    def _coef_args(self):
        """The coefficient-argument triple, uploaded once per (re)bind
        in _build_coefficients: ``(W, COEF, BCV)`` on the constant-
        coefficient route, ``(A, DT, BCV)`` on the variable-coefficient
        one — same arity, so the compiled-program plumbing (run /
        residual / IR / AOT) is route-agnostic."""
        if self._varcoef:
            return self._A_dev, self._DT_dev, self._BCV_dev
        return self._W_dev, self._C_dev, self._BCV_dev

    def _build_programs(self) -> None:
        cfg, k, B = self.cfg, self.k, self.B
        spec = self.spec
        mspec = P(BATCH_AXIS)
        res_dtype = jnp.dtype(cfg.precision.residual)
        spatial_axes = cfg.mesh.axis_names

        if self.bind == "traced":
            if self._varcoef:
                # variable-coefficient flux form: the member update is
                # timeint.coeffield's local step with the member's FIELD
                # shard, dt, and boundary value all traced; k==1 is
                # enforced at construction, so the superstep IS the step
                from heat3d_tpu.timeint.coeffield import _local_flux_update

                def member_vc(u, a, dtm, bcv):
                    return _local_flux_update(
                        u, a, cfg, dtm, exchange_with_plan, bc_value=bcv
                    )

                def step_v(u_b, A_b, DT_b, bc_b):
                    return jax.vmap(member_vc)(u_b, A_b, DT_b, bc_b)

                super_v = step_v
            else:
                step_v = self._vmapped(self._member_step)
                super_v = self._vmapped(self._member_superstep)

            def local_run(u_b, W_b, C_b, bc_b, budget_b):
                # loop bounds must be SPMD-uniform: a device's local
                # budget max would differ across the batch axis and
                # desynchronize the halo collectives — pmax makes the
                # trip count global, the per-member mask does the rest
                n_super = budget_b // k
                bound = lax.pmax(
                    jnp.max(n_super, initial=jnp.int32(0)), BATCH_AXIS
                )

                def body(i, ub):
                    stepped = super_v(ub, W_b, C_b, bc_b)
                    keep = (i < n_super)[:, None, None, None]
                    return jnp.where(keep, stepped, ub)

                u = lax.fori_loop(0, bound, body, u_b)
                if k > 1:
                    rem = budget_b % k
                    rbound = lax.pmax(
                        jnp.max(rem, initial=jnp.int32(0)), BATCH_AXIS
                    )

                    def rem_body(i, ub):
                        stepped = step_v(ub, W_b, C_b, bc_b)
                        keep = (i < rem)[:, None, None, None]
                        return jnp.where(keep, stepped, ub)

                    u = lax.fori_loop(0, rbound, rem_body, u)
                return u

            def local_step_res(u_b, W_b, C_b, bc_b):
                new = step_v(u_b, W_b, C_b, bc_b)
                r = jax.vmap(
                    lambda a, b: residual_sumsq(a, b, res_dtype)
                )(new, u_b)
                return new, lax.psum(r, spatial_axes)

            # the field array shards like the solution; scalar
            # per-member coefficients shard over the batch axis only
            coef_specs = (
                (spec, mspec, mspec)
                if self._varcoef
                else (mspec, mspec, mspec)
            )
            self._run_p = jax.jit(
                shard_map(
                    local_run,
                    mesh=self.mesh,
                    in_specs=(spec,) + coef_specs + (mspec,),
                    out_specs=spec,
                    check_vma=False,
                ),
                donate_argnums=0,
            )
            self._step_res_p = jax.jit(
                shard_map(
                    local_step_res,
                    mesh=self.mesh,
                    in_specs=(spec,) + coef_specs,
                    out_specs=(spec, P(BATCH_AXIS)),
                    check_vma=False,
                ),
                donate_argnums=0,
            )
            return

        # ---- baked binding: one SOLO executable per member --------------
        # The whole point of this binding is bitwise identity with B
        # independent HeatSolver3D runs, so each member gets the EXACT
        # solo programs — the pair loop and pair residual step over the
        # spatial mesh, jitted as the solver jits them — dispatched from
        # the batched state (slice member in, run with a zero spare, stack
        # back out; pure data movement).
        from heat3d_tpu.parallel.step import (
            make_pair_multistep_fn,
            make_pair_step_fn,
        )

        member_cfgs = [self.batch.member_config(m) for m in range(B)]
        space_dev = np.asarray(self.mesh.devices)[0]
        self._space_mesh = Mesh(space_dev, cfg.mesh.axis_names)
        self._space_sharding = NamedSharding(
            self._space_mesh, P(*cfg.mesh.axis_names)
        )
        self._member_run = [
            jax.jit(
                make_pair_multistep_fn(c, self._space_mesh, apply_taps_padded),
                donate_argnums=(0, 1), static_argnames=("odd", "rest"),
            )
            for c in member_cfgs
        ]
        self._member_step_res = [
            jax.jit(
                make_pair_step_fn(
                    c, self._space_mesh, apply_taps_padded,
                    with_residual=True,
                ),
                donate_argnums=(0, 1), keep_unused=True,
            )
            for c in member_cfgs
        ]
        self._stack = jax.jit(
            lambda *xs: jnp.stack(xs), out_shardings=self.sharding
        )

    def ir_programs(self):
        """The traced-bind executables as ``(name, fn, example_args)``
        triples for the IR verifier (``heat3d lint --ir``,
        analysis/ir/programs.py): the run program (masked superstep +
        remainder loops under SPMD-uniform pmax bounds) and the residual
        probe. Abstract args only — nothing executes; the verifier
        traces these to closed jaxprs and certifies the collective
        topology / footprint / dtype flow the queue actually serves.
        Baked binding dispatches the solo executables, which the solver
        matrix already certifies — only the traced binding has an
        ensemble-specific program to verify."""
        if self.bind != "traced":
            return []
        u = jax.ShapeDtypeStruct(
            (self.B,) + tuple(self.cfg.padded_shape), self.storage_dtype
        )
        W, C, BCV = self._coef_args()
        budgets = jax.ShapeDtypeStruct((self.B,), jnp.int32)
        return self._program_triples(u, W, C, BCV, budgets)

    # ---- AOT export/adoption (serve/aot.py) -------------------------------

    # name -> dispatcher attribute: THE registry of the traced bind's
    # shared programs. ir_programs, aot_programs, and adopt_executables
    # all derive from it, so a new program (a superstep variant, say)
    # added here + in _program_triples' arg map is certified AND
    # AOT-cached — there is no third hand-kept list to miss.
    _PROGRAM_ATTRS = (("run", "_run_p"), ("step_residual", "_step_res_p"))

    def _program_triples(self, u, W, C, BCV, budgets):
        """(name, dispatcher, args) for every shared traced-bind
        program, given the caller's avals (the IR verifier passes plain
        shapes, the AOT cache sharding-annotated ones)."""
        args = {
            "run": (u, W, C, BCV, budgets),
            "step_residual": (u, W, C, BCV),
        }
        return [
            (name, getattr(self, attr), args[name])
            for name, attr in self._PROGRAM_ATTRS
        ]

    def aot_programs(self):
        """The traced-bind programs as ``(name, jit_fn, abstract_args)``
        for ahead-of-time compilation: ``fn.lower(*args).compile()``
        yields exactly the executable the first :meth:`run` /
        :meth:`step_with_member_residuals` call would have compiled.
        Args are sharding-annotated ``ShapeDtypeStruct``s (the compiled
        program is layout-strict, so the abstract avals must pin the
        same shardings the runtime inputs carry). Baked binding has no
        shared program to AOT — its solo executables are per-member."""
        if self.bind != "traced":
            return []

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        u = jax.ShapeDtypeStruct(
            (self.B,) + tuple(self.cfg.padded_shape),
            self.storage_dtype,
            sharding=self.sharding,
        )
        W, C, BCV = (sds(a) for a in self._coef_args())
        budgets = jax.ShapeDtypeStruct(
            (self.B,), jnp.int32, sharding=self._member_spec
        )
        return self._program_triples(u, W, C, BCV, budgets)

    def adopt_executables(self, programs) -> None:
        """Swap AOT-compiled executables in for the jit dispatchers —
        the cold-start elimination hook (serve/aot.py): after adoption,
        the first request dispatches straight into the loaded PJRT
        executable with no trace and no compile. Coefficient REBINDS
        (the queue/engine's bucket reuse) survive adoption: rebinding
        replaces the uploaded arrays, not the programs."""
        if self.bind != "traced":
            raise ValueError(
                "adopt_executables: only the traced binding has shared "
                "programs (baked dispatches per-member solo executables)"
            )
        known = dict(self._PROGRAM_ATTRS)
        unknown = sorted(set(programs) - set(known))
        if unknown:
            raise ValueError(
                f"adopt_executables: unknown program name(s) {unknown} "
                f"(have {sorted(known)})"
            )
        for name, comp in programs.items():
            setattr(self, known[name], comp)

    # ---- stepping ---------------------------------------------------------

    def _budget_host(self, steps: Union[int, Sequence[int], None]):
        if steps is None:
            return self.budgets
        if np.isscalar(steps) or getattr(steps, "ndim", 1) == 0:
            return np.full((self.B,), int(steps), np.int32)
        b = np.asarray(steps, np.int32)
        if b.shape != (self.B,):
            raise ValueError(
                f"per-member steps must have shape ({self.B},), got "
                f"{b.shape}"
            )
        return b

    def run(self, u: jax.Array, steps: Union[int, Sequence[int], None] = None):
        """Advance every member by its budget. ``steps``: a scalar (all
        members), a per-member sequence, or ``None`` (each scenario's own
        budget). Members advance through supersteps for ``budget // k``
        then single steps for the remainder — the exact update sequence a
        solo run of that budget executes; finished members freeze bitwise
        while the rest run on."""
        budgets = self._budget_host(steps)
        if self.bind == "traced":
            W, C, BCV = self._coef_args()
            b_dev = jax.device_put(
                jnp.asarray(budgets, jnp.int32), self._member_spec
            )
            return self._run_p(u, W, C, BCV, b_dev)
        outs = []
        k = self.cfg.time_blocking
        for m in range(self.B):
            um = jax.device_put(u[m], self._space_sharding)
            trips, odd, rest = pair_plan(int(budgets[m]), k)
            pair = self._member_run[m](
                um, jnp.zeros_like(um), jnp.int32(trips), odd=odd, rest=rest
            )
            outs.append(pair[(odd + rest) % 2])
        return self._stack(*outs)

    def step_with_residual(self, u: jax.Array):
        """One update for every member; returns ``(u_new, r2)`` where
        ``r2`` is the ENSEMBLE-AGGREGATE residual sum-of-squares (a
        scalar — the supervised loop's convergence/health number; use
        :meth:`step_with_member_residuals` for per-member values)."""
        u, r = self.step_with_member_residuals(u)
        return u, jnp.sum(r)

    def step_with_member_residuals(self, u: jax.Array):
        """One update for every member; returns ``(u_new, r2_members)``
        with ``r2_members`` shape (B,): each member's global residual
        sum-of-squares (psum over the spatial mesh only)."""
        if self.bind == "traced":
            W, C, BCV = self._coef_args()
            return self._step_res_p(u, W, C, BCV)
        outs, rs = [], []
        for m in range(self.B):
            um = jax.device_put(u[m], self._space_sharding)
            _, new, r = self._member_step_res[m](um, jnp.zeros_like(um))
            outs.append(new)
            rs.append(jnp.asarray(r))
        return self._stack(*outs), jnp.stack(rs)

    # ---- state ------------------------------------------------------------

    def init_state(self, init=None) -> jax.Array:
        """The sharded (B, *padded_shape) initial ensemble field. ``init``
        None or ``"scenario"`` builds each member's own IC from its
        scenario spec; a string or array overrides every member (the
        supervised-restart path). Built per-shard — no process ever holds
        the full batch."""
        with obs.get().span(
            "init_state",
            init="scenario" if init in (None, "scenario") else (
                init if isinstance(init, str) else "array"
            ),
            grid=list(self.cfg.grid.shape),
            members=self.B,
        ):
            return self._from_member_blocks(init)

    def _member_block(self, m: int, clipped, init_override):
        true_shape = self.cfg.grid.shape
        init = init_override
        if init in (None, "scenario"):
            init = self.batch.members[m].init
        if isinstance(init, np.ndarray):
            if init.shape != true_shape:
                raise ValueError(
                    f"scenario {m}: init shape {init.shape} != grid "
                    f"{true_shape}"
                )
            return init[clipped].astype(self.storage_dtype)
        return golden.make_init_block(
            init, true_shape, clipped, seed=self.batch.members[m].seed
        ).astype(self.storage_dtype)

    def _from_member_blocks(self, init_override=None) -> jax.Array:
        cfg = self.cfg
        true_shape = cfg.grid.shape
        storage_shape = cfg.padded_shape
        B = self.B

        def cb(idx):
            bsl, sp = idx[0], idx[1:]
            b0 = 0 if bsl.start is None else bsl.start
            b1 = B if bsl.stop is None else bsl.stop
            starts = [0 if s.start is None else s.start for s in sp]
            stops = [
                n if s.stop is None else s.stop
                for s, n in zip(sp, storage_shape)
            ]
            shape = tuple(b - a for a, b in zip(starts, stops))
            clipped = tuple(
                slice(a, min(b, g))
                for a, b, g in zip(starts, stops, true_shape)
            )
            local = tuple(slice(0, c.stop - c.start) for c in clipped)
            blocks = []
            for m in range(b0, b1):
                # uneven-decomposition padding pins at the MEMBER's bc
                block = np.full(
                    shape,
                    self.batch.members[m].bc_value,
                    self.storage_dtype,
                )
                if all(c.stop > c.start for c in clipped):
                    block[local] = self._member_block(m, clipped, init_override)
                blocks.append(block)
            return np.stack(blocks)

        return jax.make_array_from_callback(
            (B,) + storage_shape, self.sharding, cb
        )

    def zeros_state(self) -> jax.Array:
        """All-zero TRUE grids (padding at each member's bc) — cheap
        warmup input for the donated executables."""
        return self._from_member_blocks(np.zeros(self.cfg.grid.shape,
                                                 self.storage_dtype))

    # ---- IO ---------------------------------------------------------------

    def gather(self, u: jax.Array) -> np.ndarray:
        """All member fields on host, (B, *grid), storage padding
        stripped. Multi-host safe (collective when shards are remote)."""
        if u.is_fully_addressable:
            full = np.asarray(jax.device_get(u))
        else:
            from jax.experimental import multihost_utils

            full = np.asarray(multihost_utils.process_allgather(u, tiled=True))
        want = (self.B,) + self.cfg.grid.shape
        if full.shape != want:
            full = full[
                (slice(None),) + tuple(slice(0, g) for g in self.cfg.grid.shape)
            ]
        return full

    def gather_member(self, u: jax.Array, m: int) -> np.ndarray:
        """One member's field on host, (nx, ny, nz)."""
        if not 0 <= m < self.B:
            raise ValueError(f"member {m} outside batch of {self.B}")
        return self.gather(u)[m]

    def save_checkpoint(self, path: str, u: jax.Array, step: int) -> None:
        ckpt.save(
            path, u, step,
            extra={"config": repr(self.cfg), "members": self.B},
        )

    def load_checkpoint(self, path: str) -> Tuple[jax.Array, int]:
        u, step, _ = ckpt.load(path, self.sharding)
        want = (self.B,) + self.cfg.padded_shape
        if tuple(u.shape) != want:
            raise ValueError(
                f"checkpoint {path} holds a {tuple(u.shape)} field but this "
                f"ensemble's storage shape is {want} (B={self.B}, grid "
                f"{self.cfg.grid.shape} on mesh {self.cfg.mesh.shape}) — "
                "wrong checkpoint for this batch"
            )
        if u.dtype != self.storage_dtype:
            u = u.astype(self.storage_dtype)
        return u, step

    def run_supervised(
        self,
        total_steps: int,
        ckpt_root: str,
        checkpoint_every: int = 0,
        **kwargs,
    ):
        """Run the whole ensemble to global step ``total_steps`` under the
        resilience supervisor — generations carry the batch axis, so a
        supervised ensemble heals exactly like a single run (checkpoint
        every K steps, auto-resume from the newest good generation,
        quarantine corrupt ones). The ensemble advances in LOCKSTEP here
        (``total_steps`` for every member); per-member budgets are a
        :meth:`run` feature."""
        from heat3d_tpu.resilience.supervisor import run_supervised

        kwargs.setdefault(
            "make_solver",
            lambda: EnsembleSolver(
                self.batch, batch_mesh=self.batch_mesh, bind=self.bind
            ),
        )
        kwargs.setdefault("init", "scenario")
        return run_supervised(
            self, total_steps, ckpt_root, checkpoint_every, **kwargs
        )
