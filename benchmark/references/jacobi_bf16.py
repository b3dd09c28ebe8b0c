"""Plain reference for a field stored in bfloat16: explicit-Euler heat
diffusion as ``jacobi.py`` steps it, with every step's result rounded to
bfloat16, imported from nothing of the program.

One step reads the bfloat16 field, computes ``sum_d T[d] * u[c + d]`` in
float32 (the taps of ``jacobi.py``, every cell outside the grid at
``bc_value`` rounded to bfloat16), and stores the result rounded to
bfloat16: the storage of config 5, "bf16 stencil + fp32 residual norm",
with its arithmetic in float32. The residual of a step is the float32 sum
of squares of the difference of the two stored fields.

``check`` redoes the window's last dispatch from the field the window kept
before it, in ``jacobi.py``'s slabs (``SLAB_PLANES`` planes plus ``steps``
halo planes on both sides, faces exchanged by ``ppermute``), the slab held
in bfloat16. The program sums the taps in another order, so a cell whose
float32 sum lies next to a rounding boundary may round one bfloat16 ulp
the other way, up to 2^-8 of its value; such flips spread a little with
each step. ``field_rel_err`` (the largest gap over the largest value)
reads a single flip, so the check also gives ``field_rms_rel_err``, the
root-mean-square gap over the root-mean-square value, which a few flips
hardly move and a lower precision moves everywhere.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

STORAGE = jnp.bfloat16


def _load_jacobi():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "jacobi.py")
    spec = importlib.util.spec_from_file_location("bench_reference_jacobi_f32",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


jacobi = _load_jacobi()
AXES = jacobi.AXES
SLAB_PLANES = jacobi.SLAB_PLANES


@functools.lru_cache(maxsize=None)
def _slab_fn(mesh, tap_key, mesh_shape, bc, axis, width, planes, steps,
             residual):
    tap_list = [(d, np.float32(w)) for d, w in tap_key]
    names = P(*AXES)

    def step(v):
        f = jacobi._step(v.astype(jnp.float32), tap_list, mesh_shape, bc)
        return f.astype(STORAGE)

    def total(x):
        return lax.psum(jnp.sum(x * x, dtype=jnp.float32), AXES)

    def local(kept, got, x0):
        n = kept.shape[axis]
        start = jnp.clip(x0 - steps, 0, n - width)
        v = lax.dynamic_slice_in_dim(kept, start, width, axis).astype(STORAGE)
        prev, v = lax.fori_loop(0, steps, lambda _, pv: (pv[1], step(pv[1])),
                                (v, v))
        off = x0 - start
        want = lax.dynamic_slice_in_dim(v, off, planes, axis).astype(
            jnp.float32)
        have = lax.dynamic_slice_in_dim(got, x0, planes, axis).astype(
            jnp.float32)
        gap = have - want
        err = lax.pmax(jnp.max(jnp.abs(gap)), AXES)
        scale = lax.pmax(jnp.max(jnp.abs(want)), AXES)
        finite = lax.pmin(jnp.all(jnp.isfinite(have)).astype(jnp.int32),
                          AXES)
        r2 = jnp.float32(0)
        if residual:
            r2 = total(want - lax.dynamic_slice_in_dim(prev, off, planes,
                                                       axis).astype(
                                                           jnp.float32))
        return err, scale, total(gap), total(want), finite, r2

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(names, names, P()),
        out_specs=(P(),) * 6, check_vma=False,
    ))


def check(config: dict, kept: jax.Array, got: jax.Array, steps: int,
          residual: float | None) -> dict:
    """The numbers compared: ``field_rel_err``, ``field_rms_rel_err`` and,
    where the dispatch read a residual back, ``residual_rel_err``."""
    solver = config["solver"]
    mesh_shape = tuple(solver["mesh"])
    bc = float(np.float32(STORAGE(float(solver.get("bc_value", 0.0)))))
    mesh = jacobi.mesh_of(kept, mesh_shape)
    free = [a for a in range(3) if mesh_shape[a] == 1]
    if not free:
        raise ValueError("the reference slabs along an axis the mesh does "
                         f"not split; mesh {mesh_shape} splits all three")
    axis = free[0]
    n = kept.shape[axis]
    planes = math.gcd(n, SLAB_PLANES)
    width = min(n, planes + 2 * steps)
    tap_key = tuple((d, float(w)) for d, w in jacobi.taps(solver))
    fn = _slab_fn(mesh, tap_key, mesh_shape, bc, axis, width, planes, steps,
                  residual is not None)
    err, scale, gap2, want2, finite, r2 = 0.0, 0.0, 0.0, 0.0, True, 0.0
    for x0 in range(0, n, planes):
        e, s, g, w, f, r = (float(v) for v in fn(kept, got, jnp.int32(x0)))
        err = math.nan if math.isnan(e) or math.isnan(err) else max(err, e)
        scale = max(scale, s)
        gap2 += g
        want2 += w
        finite = finite and bool(f)
        r2 += r
    ok = finite and scale > 0 and not math.isnan(err)
    out = {
        "field_rel_err": err / scale if ok else math.inf,
        "field_rms_rel_err": math.sqrt(gap2 / want2) if ok else math.inf,
    }
    if residual is not None:
        out["residual_rel_err"] = abs(residual - r2) / r2 if r2 else math.inf
    return out
