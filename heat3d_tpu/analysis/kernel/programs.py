"""The judged kernel matrix the kernel-tier checkers certify.

Every case is a REAL ``pallas_call`` the production dispatch can build —
the streaming stencil ring (``_stream``), the fused two-update and
k-update supersteps (``_stream2`` / ``_streamk`` at k = 2..4), the
direct in-kernel-BC kernels (single- and multi-chunk, mehrstellen q-ring
included), and both DMA halo-exchange kernels (width-1 zero-staging and
the width-k slab path, plus the plan-driven multi-axis composition) —
traced to a closed jaxpr on CPU
(kernel bodies over ``Ref``s trace without a TPU; shapes mirror the
interpret-tier parity matrix in tests/multidevice_checks.py) and handed
to the checkers as :class:`KernelCase` records.

Tracing uses ``interpret=False`` deliberately: the interpret flag elides
the neighbor-barrier choreography (``use_barrier``), and the kernel tier
exists precisely to certify the schedule the HARDWARE runs, not the one
the emulator runs.

Device posture mirrors the IR tier: the DMA cases want a >= 4-device CPU
backend for their judged ring meshes (``HEAT3D_KERNEL_LINT_DEVICES``,
default 4, forced only while jax is uninitialized); a session that
already booted smaller degrades the matrix and the runner surfaces that
as a warning finding (ANL1040), never a silent green.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

ENV_DEVICES = "HEAT3D_KERNEL_LINT_DEVICES"


def wanted_devices() -> int:
    """Device count the full kernel matrix needs (the size-4 rings and
    the (2,2,1) planned-exchange mesh both factor into 4)."""
    return int(os.environ.get(ENV_DEVICES, "4") or 4)


def ensure_devices() -> int:
    """Force a multi-device CPU backend for the judged ring meshes when
    still possible; returns the visible device count either way."""
    from heat3d_tpu.analysis.hostdev import ensure_host_devices

    return ensure_host_devices(wanted_devices())


@dataclasses.dataclass(frozen=True)
class CommAxis:
    """Expected remote-exchange schedule of ONE pallas call: the mesh
    axis its DMAs must move along (±1 ring shifts only)."""

    name: str
    size: int


@dataclasses.dataclass
class KernelCase:
    """One traced kernel program under certification.

    ``key`` is the kernel half of every finding fingerprint — checkers
    anchor findings on ``(checker, key, invariant)``, never on jaxpr
    pretty-printer text, so baselines survive jax upgrades (the same
    contract the IR tier pinned)."""

    key: str
    path: str  # repo-relative module of the kernel body
    entry: str  # public entry symbol (docs/messages)
    build: Callable[[], Tuple[Any, Tuple[Any, ...]]]  # () -> (fn, avals)
    ctxs: Tuple[Dict[str, Tuple[int, int]], ...] = ({},)
    comm: Tuple[CommAxis, ...] = ()  # per-pallas-call expected axis, in order
    plan_key: Optional[str] = None  # ExchangePlan key when plan-driven
    _calls: Any = None
    _sims: Any = None

    def calls(self) -> List[Any]:
        """The case's ``pallas_call`` eqns, in trace order."""
        if self._calls is None:
            import jax

            fn, avals = self.build()
            jaxpr = jax.make_jaxpr(fn)(*avals)
            self._calls = collect_pallas_calls(jaxpr.jaxpr)
            if not self._calls:
                raise ValueError(
                    f"kernel case {self.key}: traced program contains no "
                    "pallas_call — the matrix entry is stale"
                )
        return self._calls

    def sims(self, call_index: int) -> List[Any]:
        """All-device-position simulations of one pallas call (memoized)."""
        from heat3d_tpu.analysis.kernel import interp

        if self._sims is None:
            self._sims = {}
        if call_index not in self._sims:
            eqn = self.calls()[call_index]
            self._sims[call_index] = [
                interp.simulate(eqn, ctx) for ctx in self.ctxs
            ]
        return self._sims[call_index]


def collect_pallas_calls(jaxpr) -> List[Any]:
    """Every pallas_call eqn under ``jaxpr``, depth-first in program
    order (shard_map/jit/cond bodies included)."""
    import jax.extend.core as jcore

    out: List[Any] = []

    def sub(params):
        for v in params.values():
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, jcore.ClosedJaxpr):
                        yield x.jaxpr
                    elif isinstance(x, jcore.Jaxpr):
                        yield x

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for sj in sub(eqn.params):
                walk(sj)

    walk(jaxpr)
    return out


def ring_ctxs(axes: Sequence[Tuple[str, int]]) -> Tuple[Dict, ...]:
    """Every device position of a (small) mesh: the remote checker needs
    the full ring to prove the neighbor bijection, and the race/DMA
    checkers get every edge/interior control path for free."""
    names = [n for n, _ in axes]
    return tuple(
        {n: (i, s) for (n, s), i in zip(axes, pos)}
        for pos in itertools.product(*[range(s) for _, s in axes])
    )


# local shapes: small enough to simulate in milliseconds, large enough
# that every ring primes fully and the deep-tb epilogues are distinct
# phases (nx >= 2k + 2 for streamk)
_SHAPE = (8, 8, 128)


def _taps(kind: str):
    from heat3d_tpu.core.stencils import STENCILS, stencil_taps

    return stencil_taps(STENCILS[kind], 0.1, 0.05, (1.0, 1.0, 1.0))


def _mesh(shape: Tuple[int, ...], names: Tuple[str, ...]):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    n = 1
    for s in shape:
        n *= s
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


def _sharded(fn, mesh, spec):
    from jax import shard_map

    return shard_map(
        fn, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )


def _stream_case(kind: str) -> KernelCase:
    def build():
        import jax
        import jax.numpy as jnp

        from heat3d_tpu.ops.stencil_pallas import apply_taps_pallas_stream

        taps = _taps(kind)
        nx, ny, nz = _SHAPE
        aval = jax.ShapeDtypeStruct((nx + 2, ny + 2, nz + 2), jnp.float32)
        return (lambda u: apply_taps_pallas_stream(u, taps)), (aval,)

    return KernelCase(
        key=f"stream/{kind}",
        path="heat3d_tpu/ops/stencil_pallas.py",
        entry="apply_taps_pallas_stream",
        build=build,
    )


def _stream2_case() -> KernelCase:
    axes = (("x", 2), ("y", 1), ("z", 1))

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from heat3d_tpu.ops.stencil_pallas import apply_taps_pallas_stream2

        taps = _taps("7pt")
        mesh = _mesh((2, 1, 1), ("x", "y", "z"))
        nx, ny, nz = _SHAPE
        aval = jax.ShapeDtypeStruct(
            (2 * (nx + 4), ny + 4, nz + 4), jnp.float32
        )
        fn = _sharded(
            lambda u: apply_taps_pallas_stream2(
                u, taps, ("x", "y", "z"), periodic=False, bc_value=1.5
            ),
            mesh,
            P("x", None, None),
        )
        return fn, (aval,)

    return KernelCase(
        key="stream2/7pt",
        path="heat3d_tpu/ops/stencil_pallas.py",
        entry="apply_taps_pallas_stream2",
        build=build,
        ctxs=ring_ctxs(axes),
    )


def _streamk_case(kind: str, k: int, periodic: bool) -> KernelCase:
    axes = (("x", 2), ("y", 1), ("z", 1))

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from heat3d_tpu.ops.stencil_pallas import apply_taps_pallas_streamk

        taps = _taps(kind)
        mesh = _mesh((2, 1, 1), ("x", "y", "z"))
        nx, ny, nz = _SHAPE
        aval = jax.ShapeDtypeStruct(
            (2 * (nx + 2 * k), ny + 2 * k, nz + 2 * k), jnp.float32
        )
        fn = _sharded(
            lambda u: apply_taps_pallas_streamk(
                u, taps, k, ("x", "y", "z"), periodic=periodic, bc_value=1.5
            ),
            mesh,
            P("x", None, None),
        )
        return fn, (aval,)

    tag = "/periodic" if periodic else ""
    return KernelCase(
        key=f"streamk{k}/{kind}{tag}",
        path="heat3d_tpu/ops/stencil_pallas.py",
        entry="apply_taps_pallas_streamk",
        build=build,
        ctxs=ring_ctxs(axes),
    )


def _direct_case(
    kind: str, periodic: bool, shape=None, tag="", residual=False,
) -> KernelCase:
    """The tb=1 direct kernel; ``residual`` adds its change-residual
    partials, the second output the residual step's kernel writes."""
    shape = shape or _SHAPE

    def build():
        import jax
        import jax.numpy as jnp

        from heat3d_tpu.ops.stencil_pallas_direct import apply_taps_direct

        taps = _taps(kind)
        aval = jax.ShapeDtypeStruct(shape, jnp.float32)
        extra = {"residual_dtype": jnp.float32} if residual else {}
        return (
            lambda u: apply_taps_direct(
                u, taps, periodic=periodic, bc_value=1.5, **extra
            )
        ), (aval,)

    ptag = "/periodic" if periodic else ""
    rtag = "/residual" if residual else ""
    return KernelCase(
        key=f"direct/{kind}{ptag}{rtag}{tag}",
        path="heat3d_tpu/ops/stencil_pallas_direct.py",
        entry="apply_taps_direct",
        build=build,
    )


def _direct2_case(kind: str) -> KernelCase:
    def build():
        import jax
        import jax.numpy as jnp

        from heat3d_tpu.ops.stencil_pallas_direct import apply_taps_direct2

        taps = _taps(kind)
        aval = jax.ShapeDtypeStruct(_SHAPE, jnp.float32)
        return (
            lambda u: apply_taps_direct2(
                u, taps, periodic=False, bc_value=1.5
            )
        ), (aval,)

    return KernelCase(
        key=f"direct2/{kind}",
        path="heat3d_tpu/ops/stencil_pallas_direct.py",
        entry="apply_taps_direct2",
        build=build,
    )


def _dma_axis_case(width: int, size: int, periodic: bool) -> KernelCase:
    axes = (("x", size),)

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from heat3d_tpu.ops.halo_pallas import exchange_axis_dma

        mesh = _mesh((size,), ("x",))
        nx, ny, nz = _SHAPE
        aval = jax.ShapeDtypeStruct((size * nx, ny, nz), jnp.float32)
        fn = _sharded(
            lambda u: exchange_axis_dma(
                u, 0, "x", size, ("x",), periodic, 1.5, width=width
            ),
            mesh,
            P("x", None, None),
        )
        return fn, (aval,)

    tag = "/periodic" if periodic else ""
    name = "dma-face" if width == 1 else "dma-slab"
    return KernelCase(
        key=f"{name}/w{width}/x{size}{tag}",
        path="heat3d_tpu/ops/halo_pallas.py",
        entry=(
            "_face_exchange_kernel" if width == 1 else "_slab_exchange_kernel"
        ),
        build=build,
        ctxs=ring_ctxs(axes),
        comm=(CommAxis("x", size),),
    )


def _dma_planned_case() -> Tuple[KernelCase, Any]:
    """The plan-driven multi-axis DMA composition on a (2,2,1) block
    mesh: the traced per-axis kernel sequence must realize the
    ``ExchangePlan``'s axis schedule (the corner-propagation order)."""
    from heat3d_tpu.core.config import BoundaryCondition, MeshConfig
    from heat3d_tpu.parallel.plan import build_plan

    mesh_cfg = MeshConfig(shape=(2, 2, 1))
    plan = build_plan(
        mesh_cfg, BoundaryCondition.DIRICHLET, width=1, transport="dma"
    )
    axes = tuple(zip(mesh_cfg.axis_names, mesh_cfg.shape))

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from heat3d_tpu.ops.halo_pallas import exchange_halo_dma_planned

        mesh = _mesh(mesh_cfg.shape, mesh_cfg.axis_names)
        nx, ny, nz = _SHAPE
        aval = jax.ShapeDtypeStruct((2 * nx, 2 * ny, nz), jnp.float32)
        fn = _sharded(
            lambda u: exchange_halo_dma_planned(u, plan, bc_value=1.5),
            mesh,
            P("x", "y", None),
        )
        return fn, (aval,)

    case = KernelCase(
        key="dma-plan/m2x2x1/w1",
        path="heat3d_tpu/ops/halo_pallas.py",
        entry="exchange_halo_dma_planned",
        build=build,
        ctxs=ring_ctxs(axes),
        comm=tuple(
            CommAxis(spec.name, spec.size)
            for spec in plan.axis_specs
            if spec.size > 1
        ),
        plan_key=plan.key,
    )
    return case, plan


@functools.lru_cache(maxsize=1)
def _cached_matrix() -> Tuple[KernelCase, ...]:
    import jax

    n = len(jax.devices())
    cases: List[KernelCase] = [
        _stream_case("7pt"),
        _stream_case("27pt"),
        _direct_case("7pt", periodic=False),
        _direct_case("7pt", periodic=True),
        _direct_case("27pt", periodic=False),
        _direct_case("7pt", periodic=False, residual=True),
        _direct_case("27pt", periodic=True, residual=True),
        _direct2_case("7pt"),
    ]
    if n >= 2:
        cases += [
            _stream2_case(),
            _streamk_case("27pt", 2, periodic=False),
            _streamk_case("7pt", 3, periodic=True),
            _streamk_case("7pt", 4, periodic=False),
        ]
    if n >= 4:
        cases += [
            _dma_axis_case(width=1, size=4, periodic=False),
            _dma_axis_case(width=2, size=4, periodic=True),
            _dma_axis_case(width=4, size=4, periodic=False),
            _dma_planned_case()[0],
        ]
        cases += [
            _direct_case(
                "7pt", periodic=False, shape=(8, 1024, 512), tag="/chunked",
                residual=residual,
            )
            for residual in (False, True)
        ]
    return tuple(cases)


def judged_kernels() -> List[KernelCase]:
    """The full kernel certification matrix for the current device
    posture (degraded below 4 devices — the runner warns)."""
    return list(_cached_matrix())
