"""Cartesian device topology — the MPI_Cart_create / MPI_Cart_shift analogue.

Reference parity (SURVEY.md §2 C3): the reference builds a 3D Cartesian
communicator and derives 6 neighbor ranks per rank. Here the topology is a
``jax.sharding.Mesh`` with axes ('x','y','z'); neighbor relationships are
implicit in the ppermute permutations built by ``parallel.halo``, and XLA
maps the logical mesh onto the physical TPU torus (the "maps directly onto
the v5p 3D torus mesh" part of BASELINE.json's north star).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec

from heat3d_tpu.core.config import MeshConfig

# the largest device list whose order is searched for ICI neighbours (one
# host); above it the given order stands
MAX_ORDERED_DEVICES = 8


def build_mesh(cfg: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build the device mesh for (Px, Py, Pz).

    With no explicit device list and a mesh spanning every visible device,
    defer to ``jax.make_mesh`` (which picks an ICI-friendly physical
    ordering on TPU). Otherwise take the first Px*Py*Pz devices, ordered
    by :func:`ici_order` — the MPI_Cart_create(reorder=1) analogue: on
    devices that carry ICI ``coords``, mesh neighbours land on ICI
    neighbours where such an order exists; everywhere else (CPU devices,
    an order that already has it) the given order stands.
    """
    n = cfg.num_devices
    if devices is None:
        avail = jax.devices()
        if len(avail) == n:
            return jax.make_mesh(cfg.shape, cfg.axis_names)
        if len(avail) < n:
            raise ValueError(
                f"mesh {cfg.shape} needs {n} devices, only {len(avail)} visible"
            )
        devices = avail[:n]
    dev = np.asarray(ici_order(list(devices), tuple(cfg.shape)))
    return Mesh(dev.reshape(cfg.shape), cfg.axis_names)


def _hops(a, b) -> int:
    """ICI hops between two devices by their ``coords`` (no torus wrap)."""
    return sum(abs(int(p) - int(q)) for p, q in zip(a.coords, b.coords))


def _neighbour_pairs(shape: Tuple[int, ...]) -> List[Tuple[int, int]]:
    """(lower, higher) flat row-major positions of every pair of mesh
    neighbours: adjacent along one axis, no wrap."""
    idx = np.arange(int(np.prod(shape))).reshape(shape)
    pairs = []
    for axis, size in enumerate(shape):
        if size > 1:
            lo = np.take(idx, range(size - 1), axis=axis).ravel()
            hi = np.take(idx, range(1, size), axis=axis).ravel()
            pairs += [(int(a), int(b)) for a, b in zip(lo, hi)]
    return pairs


def ici_order(devices: List, shape: Tuple[int, ...]) -> List:
    """``devices`` in an order whose row-major reshape to ``shape`` puts
    every pair of mesh neighbours at most one ICI hop apart, read from the
    devices' ``coords``: the first such order among the permutations of
    the given list in lexicographic order, so the given order itself
    wherever it already has this. The given order also stands when no
    order has it, when a device carries no ``coords`` (CPU), and above
    :data:`MAX_ORDERED_DEVICES` devices. On a 2x2 v5e host, ids (0,0)
    (1,0) (0,1) (1,1) become the ring (0,0) (1,0) (1,1) (0,1) for a
    (4,1,1) chain, and stay as they are for (2,2,1)."""
    if (
        len(devices) > MAX_ORDERED_DEVICES
        or any(getattr(d, "coords", None) is None for d in devices)
    ):
        return devices
    # each position's neighbours at lower positions, checked when it fills
    below: List[List[int]] = [[] for _ in devices]
    for a, b in _neighbour_pairs(shape):
        below[b].append(a)

    def extend(order, rest):
        if not rest:
            return order
        p = len(order)
        for i, d in enumerate(rest):
            if all(_hops(d, order[q]) <= 1 for q in below[p]):
                found = extend(order + [d], rest[:i] + rest[i + 1:])
                if found is not None:
                    return found
        return None

    return extend([], devices) or devices


def abstract_mesh(cfg: MeshConfig) -> AbstractMesh:
    """Device-free mesh for compile-only lowering of multi-chip programs —
    how multi-chip paths are validated on a single-chip dev box
    (SURVEY.md §4 'Distributed-without-cluster', §7.0)."""
    return AbstractMesh(tuple(cfg.shape), tuple(cfg.axis_names))


def lower_for_mesh(fn, cfg: MeshConfig, *avals, platform: str = "tpu"):
    """Lower ``fn`` (built over ``abstract_mesh(cfg)``) for an N-device mesh
    with zero devices present, returning the Lowered object. The text of the
    lowering is what tests assert collectives/shardings on — the
    single-chip-dev-box substitute for running on a pod (SURVEY.md §4).
    Each aval is a (shape, dtype, PartitionSpec) triple or ShapeDtypeStruct.
    """
    am = abstract_mesh(cfg)
    args = []
    for a in avals:
        if isinstance(a, jax.ShapeDtypeStruct):
            args.append(a)
        else:
            shape, dtype, spec = a
            args.append(
                jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(am, spec))
            )
    return jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,))


def partition_spec(cfg: MeshConfig) -> PartitionSpec:
    """The field's sharding: block-decompose all three spatial dims over the
    mesh axes — the direct image of the reference's 3D block decomposition."""
    return PartitionSpec(*cfg.axis_names)


def field_sharding(mesh: Mesh, cfg: MeshConfig) -> NamedSharding:
    return NamedSharding(mesh, partition_spec(cfg))
