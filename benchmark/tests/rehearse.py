"""Drive ``run.py`` off the chip, or with the timed path broken, for the
tests and for the readings that set the limits.

    python benchmark/tests/rehearse.py --workload W --seed N --seconds S \
        [--trace 0|1] [--cpu [--local 16]] [--fault NAME] [--dtype bf16]

- ``--cpu``: skips the harness's look for a chip (the CPU devices stand in,
  with the v5e's peaks) and cuts the grid to ``--local`` cells per chip on
  every axis. Run it with ``JAX_PLATFORMS=cpu``, ``HEAT3D_DIRECT_INTERPRET=1``
  and, for a four-chip cell, ``XLA_FLAGS=--xla_force_host_platform_device_
  count=4``. A test-only path: ``run.py`` has no such option.
- ``--fault``: breaks the timed path underneath the harness, while the
  program is traced; the reference is traced after it is restored:
  ``unchanged`` (the dispatch returns its input), ``half_steps`` (each
  dispatch makes half its steps), ``no_exchange`` (every ``ppermute`` of
  the program delivers zeros), ``altered`` (one cell of the output of
  each program a dispatch runs is changed by 1e-3).
- ``--dtype bf16``: the control, the program's own bfloat16 storage path
  in place of the float32 the configuration states.

Several ``--seed`` values run in one process, one result line each.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import run  # noqa: E402

FAULTS = ("unchanged", "half_steps", "no_exchange", "altered")


def cpu_chip(chips):
    import jax

    devs = jax.devices()
    if len(devs) < chips:
        raise run.NoChip(f"{chips} CPU devices wanted, {len(devs)} found")
    return devs[:chips], harness.device_peaks("TPU v5 lite")


def shrink(cell: harness.Cell, local: int) -> None:
    solver = cell.config["solver"]
    solver["grid"] = [local * m for m in solver["mesh"]]


@contextlib.contextmanager
def fault(name):
    """Break the program's timed path; restored before the reference."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from heat3d_tpu.models.heat3d import HeatSolver3D

    saved = []

    def patch(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "unchanged":
        patch(HeatSolver3D, "run", lambda self, u, n: u)
        real_res = HeatSolver3D.step_with_residual
        patch(HeatSolver3D, "step_with_residual",
              lambda self, u: (u, real_res(self, jnp.copy(u))[1]))
    elif name == "half_steps":
        real = HeatSolver3D.run
        patch(HeatSolver3D, "run", lambda self, u, n: real(self, u, n // 2))
    elif name == "no_exchange":
        patch(lax, "ppermute", lambda x, *a, **k: jnp.zeros_like(x))
    elif name == "altered":
        real = HeatSolver3D.run
        bump = jax.jit(lambda u: u.at[1, 2, 3].add(jnp.asarray(1e-3,
                                                               u.dtype)))
        real_res = HeatSolver3D.step_with_residual
        patch(HeatSolver3D, "run", lambda self, u, n: bump(real(self, u, n)))
        patch(HeatSolver3D, "step_with_residual",
              lambda self, u: (lambda ur: (bump(ur[0]), ur[1]))(
                  real_res(self, u)))
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--local", type=int, default=16)
    p.add_argument("--fault", choices=FAULTS)
    p.add_argument("--dtype", choices=("bf16",))
    args = p.parse_args(argv)

    load_cell = harness.load_cell

    def cell_for_run(name, root=harness.ROOT):
        cell = load_cell(name, root)
        if args.cpu:
            shrink(cell, args.local)
        if args.dtype:
            cell.config["solver"]["dtype"] = args.dtype
        return cell

    harness.load_cell = cell_for_run
    if args.cpu:
        run.require_chip = cpu_chip
    if args.fault:
        run.import_program()
        broken = fault(args.fault)
        real_judge = run.judge

        def judge(cell, res):
            broken.__exit__(None, None, None)
            return real_judge(cell, res)

        run.judge = judge
    for seed in args.seed:
        if args.fault:
            broken = fault(args.fault)
            broken.__enter__()
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=args.trace)
        out = {"seed": seed, "variant": args.fault or args.dtype or "program",
               **run.run(ns)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
