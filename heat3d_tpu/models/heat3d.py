"""HeatSolver3D — the flagship model: explicit 3D heat diffusion, any judged
configuration (grid size, 7/27-point stencil, mesh decomposition, mixed
precision), one API.

Reference parity (SURVEY.md §2 C4, §3.1-3.3): everything the reference's
main() does — topology setup, allocation, init, the time loop, residual
checks, final report — except re-shaped as a library class whose hot path
is a single compiled XLA program per run, launched once from Python.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from heat3d_tpu import obs
from heat3d_tpu.core import golden
from heat3d_tpu.core.config import Precision, SolverConfig
from heat3d_tpu.parallel.plan import exchange_bytes
from heat3d_tpu.parallel.step import (
    make_converge_fn,
    make_pair_multistep_fn,
    make_pair_step_fn,
    pair_plan,
)
from heat3d_tpu.parallel.topology import build_mesh, field_sharding
from heat3d_tpu.utils import checkpoint as ckpt
from jax import shard_map
from heat3d_tpu.utils.logging import get_logger

log = get_logger(__name__)


def _device_init_enabled() -> bool:
    import os

    return os.environ.get("HEAT3D_DEVICE_INIT", "1").lower() not in (
        "0",
        "false",
    )


def resolved_backend_name(cfg: SolverConfig) -> str:
    """The concrete backend NAME this config's compute resolves to —
    ``_select_backend``'s 'auto' rule (pallas where supported, else jnp)
    as a name instead of a callable, so consumers that must RECORD the
    route (the tuner's cache entries, provenance fields) share the one
    rule instead of re-implementing it."""
    if cfg.backend != "auto":
        return cfg.backend
    from heat3d_tpu.ops.stencil_pallas import pallas_supported

    return "pallas" if pallas_supported(cfg)[0] else "jnp"


def _select_backend(cfg: SolverConfig):
    """Resolve the compute backend to a padded-block compute callable.

    'jnp'    — portable shifted-slice path (ops.stencil_jnp).
    'pallas' — the Pallas TPU kernel (ops.stencil_pallas).
    'conv'   — one XLA conv_general_dilated (MXU on TPU) — the measured
               A/B reference for what the chains/kernels buy.
    'auto'   — pallas on TPU when the local block meets the kernel's layout
               constraints, else jnp (``resolved_backend_name`` is the
               name-returning form of this rule).
    """
    from heat3d_tpu.ops.stencil_jnp import apply_taps_conv_padded, apply_taps_padded

    if cfg.backend == "jnp":
        return apply_taps_padded
    if cfg.backend == "conv":
        return apply_taps_conv_padded
    if cfg.backend not in ("pallas", "auto"):
        raise ValueError(
            f"unknown backend {cfg.backend!r} (want auto|jnp|pallas|conv)"
        )
    from heat3d_tpu.ops.stencil_pallas import (
        make_pallas_compute,
        pallas_supported,
    )

    ok, why = pallas_supported(cfg)
    if ok:
        return make_pallas_compute(cfg)
    if cfg.backend == "pallas":
        raise ValueError(f"pallas backend unsupported here: {why}")
    log.info("auto backend: padded-block compute on the jnp route (%s)", why)
    return apply_taps_padded


@dataclasses.dataclass
class RunResult:
    u: jax.Array
    steps: int
    residual: Optional[float] = None


class HeatSolver3D:
    """Assembles mesh + sharded step functions for one SolverConfig.

    Usage::

        cfg = SolverConfig(grid=GridConfig.cube(128))
        solver = HeatSolver3D(cfg)
        u = solver.init_state("hot-cube")
        u = solver.run(u, num_steps=100)
    """

    def __init__(self, cfg: SolverConfig, devices=None):
        with obs.get().span(
            "solver.build",
            grid=list(cfg.grid.shape),
            mesh=list(cfg.mesh.shape),
        ):
            self._build(cfg, devices)

    def _build(self, cfg: SolverConfig, devices) -> None:
        # Auto knobs (backend='auto', halo='auto', time_blocking=0)
        # resolve through the tuning cache — the safety net for library
        # users; the CLIs resolve at their entry points so their rows and
        # run_start events record concrete routes. resolve_config fails
        # soft; the belt-and-braces fallback below covers even an
        # unimportable tune package (the solver must never require it).
        # Non-default integrators pin their autos directly instead (the
        # tuner's cached knobs describe the explicit program family) and
        # validate against the timeint builders' structural scope.
        if cfg.integrator != "explicit-euler":
            from heat3d_tpu import timeint

            cfg = timeint.pin_config(cfg)
            timeint.validate_config(cfg)
        else:
            try:
                from heat3d_tpu.tune.cache import resolve_config

                cfg = resolve_config(cfg)
            except Exception:  # noqa: BLE001 - resolution is optional
                if cfg.halo == "auto" or cfg.time_blocking == 0:
                    cfg = dataclasses.replace(
                        cfg,
                        halo="ppermute" if cfg.halo == "auto" else cfg.halo,
                        time_blocking=(
                            1 if cfg.time_blocking == 0 else cfg.time_blocking
                        ),
                    )
        if cfg.halo == "dma":
            platform = jax.devices()[0].platform
            if platform != "tpu":
                raise ValueError(
                    f"halo='dma' needs TPU hardware (Mosaic remote-DMA "
                    f"kernels); platform is {platform!r} — use "
                    "halo='ppermute'"
                )
        self.cfg = cfg
        self.mesh = build_mesh(cfg.mesh, devices)
        self.sharding = field_sharding(self.mesh, cfg.mesh)
        # Built on first use: the fixed-step loop validates time_blocking
        # constraints (halo transport, local extents) that convergence-mode
        # runs never exercise.
        self._multistep_cache = None
        # the ping-pong pair's other buffer (explicit Euler): see _on_pair
        self._spare = None
        self._carry_res = None
        self._route = None
        self._device_field_cache = {}
        # bytes one device sends per exchange at width 1 and at width k
        # (see _halo_bytes); None for the integrators' own programs
        self._exchange_bytes = None
        if cfg.integrator != "explicit-euler":
            from heat3d_tpu import timeint

            self._compute = None
            self._step = jax.jit(
                timeint.make_step_fn(cfg, self.mesh), donate_argnums=0
            )
            self._step_res = jax.jit(
                timeint.make_step_fn(cfg, self.mesh, with_residual=True),
                donate_argnums=0,
            )
            self._residual_source = "reduce"
            # convergence mode is steady-state machinery: wave runs
            # oscillate forever and an implicit solve's change residual
            # measures dt, not proximity to steady state
            self._converge = None
            return
        compute = _select_backend(cfg)
        self._compute = compute
        # Each program takes the field and the solver's spare buffer, both
        # donated, and writes every update into the buffer the field is
        # not in: the reference's swap(u_old, u_new) (SURVEY.md §1 L0
        # mapping) kept across dispatches, so no program copies the field
        # back into the buffer it came in (_on_pair).
        self._step = jax.jit(
            make_pair_step_fn(cfg, self.mesh, compute),
            donate_argnums=(0, 1), keep_unused=True,
        )
        step_res = make_pair_step_fn(
            cfg, self.mesh, compute, with_residual=True
        )
        # where the residual step sums its residual: 'kernel' (the direct
        # kernel's partials) or 'reduce' (heat3d_solver_residuals_total)
        self._residual_source = step_res.route["residual"]
        self._step_res = jax.jit(
            step_res, donate_argnums=(0, 1), keep_unused=True
        )
        converge = make_converge_fn(cfg, self.mesh, compute)
        self._exchange_bytes = (
            exchange_bytes(cfg, 1), exchange_bytes(cfg, cfg.time_blocking)
        )
        self._converge_route = converge.route
        self._converge = jax.jit(converge, donate_argnums=0)

    @property
    def _multistep(self):
        if self._multistep_cache is None:
            if self.cfg.integrator != "explicit-euler":
                from heat3d_tpu import timeint

                run = timeint.make_multistep_fn(self.cfg, self.mesh)
                jitted = jax.jit(run, donate_argnums=0)
            else:
                # one executable per (odd, rest) of the plan (pair_plan)
                run = make_pair_multistep_fn(
                    self.cfg, self.mesh, self._compute
                )
                jitted = jax.jit(
                    run, donate_argnums=(0, 1),
                    static_argnames=("odd", "rest"),
                )
            # the integrators' own programs dispatch no kernel family
            self._route = getattr(
                run, "route",
                {"kernel": None, "interpret": False, "layout": None},
            )
            self._multistep_cache = jitted
        return self._multistep_cache

    @property
    def route(self) -> dict:
        """The route this solver's time loop runs, as its step builders
        recorded it when they dispatched: ``backend`` is the padded-block
        compute ('auto' resolved), ``kernel`` the Pallas kernel family of
        the loop's hot step (the superstep at tb > 1) or None for the
        exchange path, and ``interpret`` whether that kernel runs under
        the Pallas interpreter off-TPU. The loop that ran last is the one
        reported; before any run, the fixed-step loop is built (not
        compiled) to ask it. ``layout`` is the kernel's VMEM plane layout
        ('aligned' or 'framed', None without a kernel). After a
        convergence run, ``residual`` says where its residual steps summed
        the residual: 'kernel' (the direct kernel's partials) or 'reduce'.
        Where the devices carry ICI ``coords`` (TPU), ``mesh_coords``
        lists them in the mesh's row-major order
        (``parallel.topology.ici_order``). ``precision`` holds the
        config's ``storage``, ``compute`` and ``residual`` dtypes."""
        if self._route is None:
            _ = self._multistep
        route = {"backend": resolved_backend_name(self.cfg), **self._route}
        route["precision"] = dataclasses.asdict(self.cfg.precision)
        coords = [getattr(d, "coords", None) for d in self.mesh.devices.flat]
        if all(c is not None for c in coords):
            route["mesh_coords"] = [list(c) for c in coords]
        return route

    # ---- state -----------------------------------------------------------

    @property
    def storage_dtype(self):
        return jnp.dtype(self.cfg.precision.storage)

    def init_state(self, init: Union[str, np.ndarray] = "hot-cube") -> jax.Array:
        """Build the sharded initial field. A string selects a named
        initializer (core.golden.INITIALIZERS); an array is used directly.

        Initializers whose values are exactly representable constants
        (``hot-cube``) are built ON DEVICE — a jitted elementwise iota
        program under ``out_shardings``, so no host buffer is materialized
        and no bulk host->device transfer happens (at 1024^3 the host path
        ships 4 GiB through the link before the first step can run; the
        device path ships nothing, and GSPMD partitions the iota masks with
        zero communication). The result is bitwise-identical to the host
        path; ``HEAT3D_DEVICE_INIT=0`` forces the host path for A/B.
        Everything else (value-generating initializers, explicit arrays)
        materializes per-shard via make_array_from_callback, so no process
        ever holds the full 4096^3 field either way (SURVEY.md §2 C8).

        Storage is ``cfg.padded_shape``; for uneven decompositions the
        region beyond ``cfg.grid.shape`` is pinned at bc_value (see
        parallel.step._pin_padding).

        Under ``integrator='leapfrog'`` the state is the TWO-LEVEL carry
        ``(u, u_prev)``: a single initializer yields a zero-velocity
        start (u_prev a copy of u — distinct buffers, so the donated
        step may alias either); a TUPLE of two initializers sets the
        levels independently (the MMS gates seed u(0) and u(-dt))."""
        if self.cfg.integrator == "leapfrog":
            if isinstance(init, tuple):
                if len(init) != 2:
                    raise ValueError(
                        f"leapfrog init tuple must have 2 levels (u, "
                        f"u_prev), got {len(init)}"
                    )
                return tuple(self._init_level(lv) for lv in init)
            u0 = self._init_level(init)
            return (u0, jnp.copy(u0))
        return self._init_level(init)

    def _init_level(self, init: Union[str, np.ndarray]) -> jax.Array:
        true_shape = self.cfg.grid.shape
        with obs.get().span(
            "init_state",
            init=init if isinstance(init, str) else "array",
            grid=list(true_shape),
        ):
            if isinstance(init, np.ndarray):
                if init.shape != true_shape:
                    raise ValueError(
                        f"init shape {init.shape} != grid {true_shape}"
                    )
                arr = init.astype(self.storage_dtype)
                return self._sharded_from_blocks(
                    lambda clipped: arr[clipped]
                )
            if init == "hot-cube" and _device_init_enabled():
                return self._device_field(hot_cube=True)
            name, seed = init, self.cfg.run.seed
            return self._sharded_from_blocks(
                lambda clipped: golden.make_init_block(
                    name, true_shape, clipped, seed=seed
                ).astype(self.storage_dtype)
            )

    def _device_field(self, hot_cube: bool) -> jax.Array:
        """All-zero (or hot-cube) TRUE grid in storage layout, built on
        device: elementwise over coordinate iotas, jitted with
        ``out_shardings``, bitwise-equal to the host block path (the only
        values are 0, 1, and bc_value — exactly representable in every
        storage dtype)."""
        jitted = self._device_field_cache.get(hot_cube)
        if jitted is not None:
            return jitted()
        storage = self.cfg.padded_shape
        true_shape = self.cfg.grid.shape
        bc_value = self.cfg.stencil.bc_value
        dtype = self.storage_dtype

        def build():
            in_true = None
            in_cube = None
            for ax, nt in enumerate(true_shape):
                io = jax.lax.broadcasted_iota(jnp.int32, storage, ax)
                t = io < nt
                in_true = t if in_true is None else in_true & t
                if hot_cube:
                    # same bounds arithmetic as golden.make_init_block
                    g0 = int(nt * (0.5 - 0.25 / 2))
                    g1 = max(int(nt * (0.5 + 0.25 / 2)), g0 + 1)
                    c = (io >= g0) & (io < g1)
                    in_cube = c if in_cube is None else in_cube & c
            val = jnp.zeros(storage, dtype)
            if hot_cube:
                val = jnp.where(in_cube, jnp.ones((), dtype), val)
            return jnp.where(in_true, val, jnp.full((), bc_value, dtype))

        jitted = jax.jit(build, out_shardings=self.sharding)
        self._device_field_cache[hot_cube] = jitted
        return jitted()

    def _sharded_from_blocks(self, true_block_fn) -> jax.Array:
        """Build a sharded storage-layout field from a function evaluating
        blocks of the TRUE grid. Regions beyond ``cfg.grid.shape`` (uneven-
        decomposition padding) are filled with bc_value; each shard callback
        clips its storage-index slices against the true extents."""
        true_shape = self.cfg.grid.shape
        storage_shape = self.cfg.padded_shape
        bc_value = self.cfg.stencil.bc_value

        def cb(idx):
            starts = [0 if s.start is None else s.start for s in idx]
            stops = [
                n if s.stop is None else s.stop
                for s, n in zip(idx, storage_shape)
            ]
            block = np.full(
                tuple(b - a for a, b in zip(starts, stops)),
                bc_value,
                self.storage_dtype,
            )
            clipped = tuple(
                slice(a, min(b, g))
                for a, b, g in zip(starts, stops, true_shape)
            )
            if all(c.stop > c.start for c in clipped):
                local = tuple(slice(0, c.stop - c.start) for c in clipped)
                block[local] = true_block_fn(clipped)
            return block

        return jax.make_array_from_callback(storage_shape, self.sharding, cb)

    def zeros_state(self) -> jax.Array:
        """An all-zero TRUE grid in storage layout (padding at bc_value) —
        cheap warmup input for the donated executables. Built on device
        (no host buffer, no transfer) unless HEAT3D_DEVICE_INIT=0. A
        two-level tuple under ``integrator='leapfrog'``, like
        :meth:`init_state`."""
        if _device_init_enabled():
            z = self._device_field(hot_cube=False)
        else:
            z = self._sharded_from_blocks(
                lambda clipped: np.zeros(
                    tuple(c.stop - c.start for c in clipped),
                    self.storage_dtype,
                )
            )
        if self.cfg.integrator == "leapfrog":
            return (z, jnp.copy(z))
        return z

    # ---- stepping --------------------------------------------------------

    def _halo_bytes(self, program: str, steps: int) -> Optional[int]:
        """Bytes one device sends over the halo in ``steps`` updates of
        ``program``, priced from shapes (``plan.exchange_bytes``), never
        read from the device; None for the integrators' own programs. The
        fixed-step loop makes ``steps // k`` width-k supersteps, then
        ``steps % k`` single steps; with ``residual_every = K > 1`` the
        convergence loop makes groups of K - 1 loop updates and one
        residual step (a run cut by max_steps ends in a shorter group);
        every other dispatch exchanges once per update."""
        if self._exchange_bytes is None:
            return None
        one, wide = self._exchange_bytes
        k = self.cfg.time_blocking

        def loop(n):
            return n // k * wide + n % k * one

        every = max(1, self.cfg.run.residual_every or 1)
        if program == "run":
            return loop(steps)
        if program == "converge" and every > 1:
            full, rest = divmod(steps, every)
            return full * (loop(every - 1) + one) + (
                loop(rest - 1) + one if rest else 0
            )
        return steps * one

    def _dispatch_span(self, program: str, **fields):
        """The ``solver.dispatch`` span of one dispatch of ``program``,
        with the field's storage ``dtype``."""
        return obs.get().span(
            "solver.dispatch", program=program,
            dtype=self.cfg.precision.storage, **fields,
        )

    def _dispatched(self, program: str, steps: int) -> None:
        """Count one dispatch of ``program`` that made ``steps`` updates:
        ``heat3d_solver_steps_total{program}`` and
        ``heat3d_halo_bytes_total``."""
        obs.REGISTRY.counter("solver_steps_total").inc(steps, program=program)
        nbytes = self._halo_bytes(program, steps)
        if nbytes is not None:
            obs.REGISTRY.counter("halo_bytes_total").inc(nbytes)

    def _take_spare(self, u: jax.Array) -> jax.Array:
        """The pair's spare buffer for field ``u``: the one the solver
        holds, or, where it holds none of ``u``'s shape, dtype and
        sharding, a new one filled with zeros
        (``heat3d_solver_spare_allocs_total``). Its contents are never
        read."""
        spare, self._spare = self._spare, None
        if (
            spare is None
            or spare.is_deleted()
            or spare.shape != u.shape
            or spare.dtype != u.dtype
            or not spare.sharding.is_equivalent_to(u.sharding, u.ndim)
        ):
            spare = jax.jit(jnp.zeros_like, out_shardings=u.sharding)(u)
            obs.REGISTRY.counter("solver_spare_allocs_total").inc()
        return spare

    def _on_pair(self, program: str, fn, u: jax.Array, writes: int,
                 *args, **static) -> tuple:
        """Dispatch the pair program ``fn(u, spare, *args, **static)``,
        which makes ``writes`` out-of-place updates, each into the buffer
        the field is not in, and returns both buffers in order (then any
        further outputs). After an odd count the field is in the spare's
        buffer (``heat3d_solver_pair_swaps_total{program}``: each such
        dispatch is a full-field copy saved). The buffer the field is not
        in becomes the next spare. Returns the field and the further
        outputs."""
        out = fn(u, self._take_spare(u), *args, **static)
        swapped = writes % 2
        self._spare = out[1 - swapped]
        if swapped:
            obs.REGISTRY.counter("solver_pair_swaps_total").inc(
                program=program
            )
        return (out[swapped], *out[2:])

    def warm_up(self, num_steps: int) -> None:
        """Dispatch once, on a zero field, the loop program that
        ``run(u, num_steps)`` takes, with the fewest updates that take it,
        so that a timed ``run`` compiles nothing. Under explicit Euler the
        loop has one executable per ``(odd, rest)`` of its plan
        (``parallel.step.pair_plan``); the integrators' loops take the
        count traced, so their one executable is warmed with no update."""
        n = 0
        if self.cfg.integrator == "explicit-euler":
            _, odd, rest = pair_plan(num_steps, self.cfg.time_blocking)
            n = odd * self.cfg.time_blocking + rest
        jax.block_until_ready(self.run(self.zeros_state(), n))

    def step(self, u: jax.Array) -> jax.Array:
        with self._dispatch_span("step", steps=1):
            if self.cfg.integrator != "explicit-euler":
                u = self._step(u)
            else:
                (u,) = self._on_pair("step", self._step, u, 1)
        self._dispatched("step", 1)
        return u

    def step_with_residual(self, u: jax.Array) -> Tuple[jax.Array, jax.Array]:
        with self._dispatch_span("step_residual", steps=1):
            if self.cfg.integrator != "explicit-euler":
                out = self._step_res(u)
            else:
                out = self._on_pair("step_residual", self._step_res, u, 1)
        self._dispatched("step_residual", 1)
        obs.REGISTRY.counter("solver_residuals_total").inc(
            source=self._residual_source
        )
        return out

    def carry_residual(self, carry: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """The global change residual (sum of squares) between the two
        levels of a multi-level carry: what the residual step computes
        for its update, read off the carry the time loop already holds.
        A supervised run ends through ``run`` + this, so its last state
        comes from the loop program an uninterrupted run uses — XLA:CPU
        rounds the separate residual-step program up to a few ulps away
        on a sharded mesh, which a resume would then carry forward."""
        if self._carry_res is None:
            from jax.sharding import PartitionSpec as P

            from heat3d_tpu.ops.stencil_jnp import residual_sumsq

            axes = self.cfg.mesh.axis_names
            dtype = jnp.dtype(self.cfg.precision.residual)

            def local(u, u_prev):
                return jax.lax.psum(residual_sumsq(u, u_prev, dtype), axes)

            self._carry_res = jax.jit(shard_map(
                local, mesh=self.mesh, in_specs=(P(*axes), P(*axes)),
                out_specs=P(), check_vma=False,
            ))
        return self._carry_res(*carry[:2])

    def run(self, u: jax.Array, num_steps: int) -> jax.Array:
        """num_steps updates as one device-side loop (benchmark mode: no
        mid-loop host syncs — SURVEY.md §3.3). Under explicit Euler the
        loop runs over the field and the solver's spare buffer, and the
        field comes back in whichever of the two the last update wrote
        (``_on_pair``); ``u`` is donated, as in every program. Under
        ``integrator='implicit-cg'`` each update is a CG solve; the last
        solve's iteration count and relative residual come back with the
        field and land in the ledger as a ``cg_solve`` event (the one
        host sync happens after the loop, where the caller consumes the
        field anyway)."""
        steps = int(num_steps)
        with self._dispatch_span("run", steps=steps):
            if self.cfg.integrator != "explicit-euler":
                out = self._multistep(u, jnp.int32(steps))
            else:
                trips, odd, rest = pair_plan(steps, self.cfg.time_blocking)
                (out,) = self._on_pair(
                    "run", self._multistep, u, 2 * trips + odd + rest,
                    jnp.int32(trips), odd=odd, rest=rest,
                )
        self._dispatched("run", steps)
        if self.cfg.integrator == "implicit-cg":
            u, iters, relres = out
            obs.get().event(
                "cg_solve",
                steps=steps,
                cg_iters=int(iters),
                cg_relres=float(relres),
            )
            return u
        return out

    def run_to_convergence(
        self, u: jax.Array, tol: float, max_steps: int
    ) -> RunResult:
        if self._converge is None:
            raise ValueError(
                f"run_to_convergence needs integrator='explicit-euler' "
                f"(got {self.cfg.integrator!r}): wave runs oscillate "
                "instead of converging, and an implicit solve's change "
                "residual measures dt, not steady-state proximity — use "
                "fixed-step run() (docs/INTEGRATORS.md)"
            )
        self._route = self._converge_route
        with self._dispatch_span("converge") as sp:
            u, steps, res = self._converge(
                u, jnp.int32(max_steps), jnp.float32(tol)
            )
        # the step count is a device value: read after the span closes
        # (the ring record still takes the field)
        steps = int(steps)
        sp.add(steps=steps)
        self._dispatched("converge", steps)
        return RunResult(u=u, steps=steps, residual=float(res))

    def run_supervised(
        self,
        total_steps: int,
        ckpt_root: str,
        checkpoint_every: int = 0,
        **kwargs,
    ):
        """Run to global step ``total_steps`` under the resilience
        supervisor: checkpoint generations every ``checkpoint_every``
        steps into ``ckpt_root``, auto-resume from the newest good
        generation (quarantining corrupt ones), survive backend
        loss/hang by waiting for heal and resuming. ``total_steps`` is
        the TARGET GLOBAL step — a resumed run finishes the original
        run, it does not append to it. See
        :func:`heat3d_tpu.resilience.supervisor.run_supervised` for the
        knobs; by default a recovery rebuilds a fresh solver for this
        config (re-resolving devices, so a TPU->CPU heal cross-mesh
        stitch-resumes through ``utils.checkpoint``'s block stitching).
        """
        from heat3d_tpu.resilience.supervisor import run_supervised

        kwargs.setdefault("make_solver", lambda: HeatSolver3D(self.cfg))
        # the elastic path (heal_mode='elastic'|'auto') needs a
        # config-parameterized factory: a survivor-mesh re-factorization
        # rebuilds the solver on the DEGRADED config, not this one
        # (resilience/elastic.py; docs/RESILIENCE.md)
        kwargs.setdefault("make_solver_for", lambda cfg: HeatSolver3D(cfg))
        kwargs.setdefault("base_cfg", self.cfg)
        return run_supervised(
            self, total_steps, ckpt_root, checkpoint_every, **kwargs
        )

    # ---- IO --------------------------------------------------------------

    def gather(self, u: jax.Array) -> np.ndarray:
        """Fetch the full field to host (small grids / tests only), with any
        uneven-decomposition storage padding stripped. Multi-host safe: when
        shards live on other processes this is a collective
        (process_allgather), so every process must call it. A multi-level
        carry gathers level 0 (the current field)."""
        if isinstance(u, tuple):
            u = u[0]
        if u.is_fully_addressable:
            full = np.asarray(jax.device_get(u))
        else:
            from jax.experimental import multihost_utils

            full = np.asarray(multihost_utils.process_allgather(u, tiled=True))
        if full.shape != self.cfg.grid.shape:
            full = full[tuple(slice(0, g) for g in self.cfg.grid.shape)]
        return full

    def gather_slice(self, u: jax.Array, axis: int, index: int) -> np.ndarray:
        """One global 2D plane of the field on the host — the reference
        class's visualization dump (SURVEY.md §4: correctness by "visual/
        numeric inspection of dumped slices") without materializing the
        full global array anywhere. ``index`` is a GLOBAL coordinate along
        ``axis``. Multi-host safe: the replicated out_sharding makes XLA
        gather just this plane to every process, so all processes must
        call it (like :meth:`gather`)."""
        if isinstance(u, tuple):
            u = u[0]
        g = self.cfg.grid.shape
        if not 0 <= axis <= 2:
            raise ValueError(f"slice axis must be 0..2, got {axis}")
        if not 0 <= index < g[axis]:
            raise ValueError(
                f"slice index {index} outside grid extent {g[axis]} on "
                f"axis {axis}"
            )
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec

        # XLA's sharding propagation cannot slice a sharded dim to size 1
        # (ShardingTypeError), so extract per-shard: the one device row
        # holding the plane contributes it, everyone else zeros, and a psum
        # along the slice axis broadcasts it — traffic is one plane, never
        # the volume.
        names = self.cfg.mesh.axis_names
        axis_name = names[axis]

        def local_plane(x):
            i = lax.axis_index(axis_name)
            nloc = x.shape[axis]
            li = index - i * nloc  # storage coords == physical coords
            ok = jnp.logical_and(li >= 0, li < nloc)
            piece = lax.dynamic_index_in_dim(
                x, jnp.clip(li, 0, nloc - 1), axis, keepdims=False
            )
            piece = jnp.where(ok, piece, jnp.zeros_like(piece))
            return lax.psum(piece, axis_name)

        out_names = tuple(n for a, n in enumerate(names) if a != axis)
        plane = jax.jit(
            shard_map(
                local_plane,
                mesh=self.mesh,
                in_specs=PartitionSpec(*names),
                out_specs=PartitionSpec(*out_names),
                check_vma=False,
            ),
            out_shardings=NamedSharding(self.mesh, PartitionSpec()),
        )(u)
        keep = [s for a, s in enumerate(g) if a != axis]
        # strip any uneven-decomposition storage padding from the plane
        return np.asarray(plane)[: keep[0], : keep[1]]

    def save_checkpoint(self, path: str, u, step: int) -> None:
        """Checkpoint the state. A multi-level carry (leapfrog) writes
        level 0 at the generation top — manifest extra records
        ``levels``/``integrator`` — and each further level as a full
        per-shard checkpoint under ``<path>/level-<i>/``, so every level
        keeps the per-shard CRC sidecars and the cross-mesh re-stitch of
        ``utils.checkpoint`` unchanged."""
        import os

        from heat3d_tpu import timeint

        levels = timeint.carry_levels(self.cfg.integrator)
        if levels == 1:
            ckpt.save(path, u, step, extra={"config": repr(self.cfg)})
            return
        ckpt.save(
            path,
            u[0],
            step,
            extra={
                "config": repr(self.cfg),
                "levels": levels,
                "integrator": self.cfg.integrator,
            },
        )
        for lv in range(1, levels):
            ckpt.save(
                os.path.join(path, f"level-{lv}"),
                u[lv],
                step,
                extra={"level": lv, "integrator": self.cfg.integrator},
            )

    def load_checkpoint(self, path: str):
        """Load a checkpoint saved by :meth:`save_checkpoint`. The level
        structure is validated BEFORE any shard read: a manifest whose
        ``levels`` count disagrees with this integrator's carry, a
        missing level directory, a level step drift, or a per-level
        shard-shape mismatch raises
        :class:`heat3d_tpu.timeint.MultiLevelCheckpointError` — a
        ValueError, so the supervisor's resume scan skips the generation
        in place (the shards are not PROVEN corrupt; quarantine stays
        reserved for checksum/torn-manifest damage)."""
        import os

        from heat3d_tpu import timeint

        levels = timeint.carry_levels(self.cfg.integrator)
        man = ckpt.load_manifest(path)
        found = int((man.get("extra") or {}).get("levels", 1))
        if found != levels:
            raise timeint.MultiLevelCheckpointError(
                f"checkpoint {path} holds {found} field level(s) but "
                f"integrator {self.cfg.integrator!r} carries {levels} — "
                "wrong integrator for this checkpoint (docs/INTEGRATORS.md)"
            )
        u, step = self._load_level(path)
        if levels == 1:
            return u, step
        state = [u]
        for lv in range(1, levels):
            lp = os.path.join(path, f"level-{lv}")
            try:
                ulv, step_lv = self._load_level(
                    lp, error_cls=timeint.MultiLevelCheckpointError
                )
            except ckpt.ShardCorruptError:
                raise  # proven damage: let the supervisor quarantine
            except FileNotFoundError as e:
                raise timeint.MultiLevelCheckpointError(
                    f"checkpoint {path} is missing level {lv} "
                    f"({lp}): {e}"
                ) from e
            if step_lv != step:
                raise timeint.MultiLevelCheckpointError(
                    f"checkpoint {path} level {lv} is at step {step_lv} "
                    f"but level 0 is at step {step} — torn multi-level "
                    "save"
                )
            state.append(ulv)
        return tuple(state), step

    def _load_level(self, path: str, error_cls=ValueError):
        u, step, _ = ckpt.load(path, self.sharding)
        if tuple(u.shape) != self.cfg.padded_shape:
            # fail loudly: silently stepping a wrong-shape field would
            # finish "successfully" with metrics computed from the
            # CONFIGURED grid — an inflated/garbage summary with clean
            # provenance (and the supervised auto-resume path reaches
            # here without any --resume flag). Distinguish the two causes:
            # the same grid saved under a DIFFERENT mesh's bc-padding is a
            # known cross-mesh limitation, not a wrong checkpoint.
            # padding only ever rounds the grid UP, so saved >= grid on
            # every dim is consistent with "same grid, other mesh"; any
            # smaller dim proves a different grid outright
            same_grid_other_padding = all(
                s >= g for s, g in zip(u.shape, self.cfg.grid.shape)
            )
            hint = (
                "the checkpoint was padded for a different mesh "
                "(cross-mesh resume across bc-paddings is unsupported — "
                "use a grid divisible by both meshes, or consolidate and "
                "re-grid)"
                if same_grid_other_padding
                else "wrong checkpoint for this run"
            )
            raise error_cls(
                f"checkpoint {path} holds a {tuple(u.shape)} field but "
                f"this config's storage shape is {self.cfg.padded_shape} "
                f"(grid {self.cfg.grid.shape} on mesh {self.cfg.mesh.shape})"
                f" — {hint}"
            )
        if u.dtype != self.storage_dtype:
            u = u.astype(self.storage_dtype)
        return u, step
