"""heat3d_tpu — a TPU-native 3D heat-equation framework.

A ground-up re-design of the capability set of the reference repo
``fredrickhang/Cuda-aware-MPI-on-3D-heate-quation`` (CUDA kernels +
CUDA-aware MPI halo exchange + MPI_Cart_create 3D decomposition) as an
idiomatic JAX/XLA/Pallas program:

- the CUDA 7-point Jacobi stencil kernel        -> Pallas TPU kernels: BC-fused direct
  streaming kernels reading the unpadded field (``ops.stencil_pallas_direct``,
  single- and fused two-update forms) plus exchange-padded kernels (``ops.stencil_pallas``)
- CUDA-aware MPI_Isend/Irecv ghost-cell exchange -> ``shard_map`` + ``lax.ppermute``
  over ICI (``parallel.halo``), with a Pallas ``make_async_remote_copy`` tier
- MPI_Cart_create 3D Cartesian decomposition     -> ``jax.sharding.Mesh`` mapped onto
  the TPU torus (``parallel.topology``)
- the mpirun driver + time-stepping loop         -> ``jax.distributed`` entrypoint and a
  jit-compiled ``lax.fori_loop`` time loop (``models.heat3d``, ``cli``); the
  pointer swap is a ping-pong pair the solver keeps across dispatches, each
  update written into the buffer the field is not in, so no program copies
  the field (``parallel.step.make_pair_multistep_fn``)

The reference mount is empty in this environment (see SURVEY.md §0); the
capability spec is BASELINE.json's north star and config matrix, and
reference-parity notes in docstrings cite SURVEY.md sections instead of
file:line.
"""

from heat3d_tpu.core.config import (
    BoundaryCondition,
    GridConfig,
    MeshConfig,
    Precision,
    RunConfig,
    SolverConfig,
    StencilConfig,
)
from heat3d_tpu.core.stencils import STENCILS, Stencil, stencil_taps
from heat3d_tpu.models.heat3d import HeatSolver3D

__version__ = "0.4.0"

__all__ = [
    "BoundaryCondition",
    "GridConfig",
    "MeshConfig",
    "Precision",
    "RunConfig",
    "SolverConfig",
    "StencilConfig",
    "STENCILS",
    "Stencil",
    "stencil_taps",
    "HeatSolver3D",
]
