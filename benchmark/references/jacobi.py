"""Plain reference: explicit-Euler heat diffusion with a 7- or 27-point
Laplacian and Dirichlet ghosts, in float32, imported from nothing of the
program.

One step is ``u_new[c] = sum_d T[d] * u[c + d]`` over the 3x3x3
neighbourhood, ``T = I + dt * alpha * W / h^2``, with every cell outside
the grid at ``bc_value``. ``W`` is the Laplacian at unit spacing:

- 7pt: centre -6, faces 1;
- 27pt (isotropic): centre -64/15, faces 7/15, edges 1/10, corners 1/30.

``dt`` is 0.9 times the explicit-Euler bound ``1 / (2 alpha sum(1/h^2))``,
the CLI's default. The residual of a step is the float32 sum of squares
of ``u_new - u``.

``check`` redoes the window's last dispatch: from the field the window
kept before it (``kept``) for ``steps`` steps, and compares with what the
program produced (``got``). It runs in slabs along an axis the mesh does
not split, each slab with ``steps`` extra planes on both sides (their
values go wrong by one plane a step from a cut, and are not compared), so
that it fits beside the two fields on the chip. Each slab steps on the
chips that hold it, with one-plane faces exchanged between them by
``ppermute`` in the order x, y, z, so edges and corners travel too.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

AXES = ("rx", "ry", "rz")
# planes of one slab; with the 2 * steps halo planes, one slab of a
# 1024 x 1024 plane is about 1 GiB of float32 at steps = 50
SLAB_PLANES = 128


def laplacian(kind: str) -> np.ndarray:
    w = np.zeros((3, 3, 3))
    for d in itertools.product((-1, 0, 1), repeat=3):
        far = sum(map(abs, d))
        if kind == "7pt":
            w[d[0] + 1, d[1] + 1, d[2] + 1] = {0: -6.0, 1: 1.0}.get(far, 0.0)
        elif kind == "27pt":
            w[d[0] + 1, d[1] + 1, d[2] + 1] = {
                0: -64.0 / 15.0, 1: 7.0 / 15.0, 2: 1.0 / 10.0, 3: 1.0 / 30.0
            }[far]
        else:
            raise ValueError(f"no reference stencil {kind!r}")
    return w


def taps(solver: dict):
    """((di, dj, dk), weight) of the update, weights as float32."""
    h = solver.get("spacing", [1.0, 1.0, 1.0])
    if len(set(h)) != 1:
        raise ValueError(f"the reference takes uniform spacing, got {h}")
    alpha = float(solver.get("alpha", 1.0))
    dt = 0.9 / (2.0 * alpha * sum(1.0 / x**2 for x in h))
    t = dt * alpha * laplacian(solver["stencil"]) / h[0] ** 2
    t[1, 1, 1] += 1.0
    return [
        (d, np.float32(t[d[0] + 1, d[1] + 1, d[2] + 1]))
        for d in itertools.product((-1, 0, 1), repeat=3)
        if t[d[0] + 1, d[1] + 1, d[2] + 1] != 0.0
    ]


def mesh_of(x: jax.Array, mesh_shape) -> Mesh:
    """A mesh of the devices that hold ``x``, each at the position of the
    block it holds, so that the reference moves no data to start."""
    shape = x.shape
    local = [n // m for n, m in zip(shape, mesh_shape)]
    grid = np.empty(tuple(mesh_shape), dtype=object)
    for dev, idx in x.sharding.devices_indices_map(shape).items():
        pos = tuple((s.start or 0) // b for s, b in zip(idx, local))
        grid[pos] = dev
    return Mesh(grid, AXES)


def _ghosts(v, ax, n_ax, bc):
    """The planes just below and above ``v`` along ``ax``: the neighbour
    chip's boundary plane, or ``bc`` outside the grid."""
    lo = lax.slice_in_dim(v, 0, 1, axis=ax)
    hi = lax.slice_in_dim(v, v.shape[ax] - 1, v.shape[ax], axis=ax)
    edge = jnp.full_like(lo, bc)
    if n_ax == 1:
        return edge, edge
    name = AXES[ax]
    from_below = lax.ppermute(hi, name, [(i, i + 1) for i in range(n_ax - 1)])
    from_above = lax.ppermute(lo, name, [(i + 1, i) for i in range(n_ax - 1)])
    i = lax.axis_index(name)
    return (jnp.where(i == 0, edge, from_below),
            jnp.where(i == n_ax - 1, edge, from_above))


def _step(v, tap_list, mesh_shape, bc):
    p = v
    for ax in range(3):
        lo, hi = _ghosts(p, ax, mesh_shape[ax], bc)
        p = jnp.concatenate([lo, p, hi], axis=ax)
    n = v.shape
    out = None
    for (a, b, c), w in tap_list:
        t = w * p[1 + a:1 + a + n[0], 1 + b:1 + b + n[1], 1 + c:1 + c + n[2]]
        out = t if out is None else out + t
    return out


@functools.lru_cache(maxsize=None)
def _slab_fn(mesh, tap_key, mesh_shape, bc, axis, width, planes, steps,
             residual):
    tap_list = [(d, np.float32(w)) for d, w in tap_key]
    names = P(*AXES)

    def local(kept, got, x0):
        n = kept.shape[axis]
        start = jnp.clip(x0 - steps, 0, n - width)
        v = lax.dynamic_slice_in_dim(kept, start, width, axis).astype(
            jnp.float32)
        prev, v = lax.fori_loop(
            0, steps,
            lambda _, pv: (pv[1], _step(pv[1], tap_list, mesh_shape, bc)),
            (v, v))
        off = x0 - start
        want = lax.dynamic_slice_in_dim(v, off, planes, axis)
        have = lax.dynamic_slice_in_dim(got, x0, planes, axis).astype(
            jnp.float32)
        err = lax.pmax(jnp.max(jnp.abs(have - want)), AXES)
        scale = lax.pmax(jnp.max(jnp.abs(want)), AXES)
        finite = lax.pmin(jnp.all(jnp.isfinite(have)).astype(jnp.int32),
                          AXES)
        r2 = jnp.float32(0)
        if residual:
            d = want - lax.dynamic_slice_in_dim(prev, off, planes, axis)
            r2 = lax.psum(jnp.sum(d * d, dtype=jnp.float32), AXES)
        return err, scale, finite, r2

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(names, names, P()),
        out_specs=(P(), P(), P(), P()), check_vma=False,
    ))


def check(config: dict, kept: jax.Array, got: jax.Array, steps: int,
          residual: float | None) -> dict:
    """The numbers compared: ``field_rel_err``, the largest gap over every
    cell over the largest value of the reference's field, and
    ``residual_rel_err`` where the dispatch read a residual back."""
    solver = config["solver"]
    mesh_shape = tuple(solver["mesh"])
    bc = float(solver.get("bc_value", 0.0))
    mesh = mesh_of(kept, mesh_shape)
    free = [a for a in range(3) if mesh_shape[a] == 1]
    if not free:
        raise ValueError("the reference slabs along an axis the mesh does "
                         f"not split; mesh {mesh_shape} splits all three")
    axis = free[0]
    n = kept.shape[axis]
    planes = math.gcd(n, SLAB_PLANES)
    width = min(n, planes + 2 * steps)
    tap_key = tuple((d, float(w)) for d, w in taps(solver))
    fn = _slab_fn(mesh, tap_key, mesh_shape, bc, axis, width, planes, steps,
                  residual is not None)
    err, scale, finite, r2 = 0.0, 0.0, True, 0.0
    for x0 in range(0, n, planes):
        e, s, f, r = (float(v) for v in fn(kept, got, jnp.int32(x0)))
        err = math.nan if math.isnan(e) or math.isnan(err) else max(err, e)
        scale = max(scale, s)
        finite = finite and bool(f)
        r2 += r
    out = {"field_rel_err": err / scale if finite and scale else math.inf}
    if residual is not None:
        out["residual_rel_err"] = abs(residual - r2) / r2 if r2 else math.inf
    return out
