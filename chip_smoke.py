"""Chip smoke: drive the solver's main path once on a TPU and check it.

    python chip_smoke.py              # one chip: cells (a)-(c) + 256^3 golden
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Every phase goes through the entry points a user calls (the ``heat3d``
CLI's ``main`` and ``HeatSolver3D``) at the sizes ROADMAP.md's cells name.
Earlier lines report device, route, compile seconds, steps, seconds, a
smoke-only Gcell/s and ``peak_bytes_in_use``; the last line is one JSON
object ``{"ok": true, "device": {...}}``. Without a TPU, or without the
rest of the repository beside it, the script exits nonzero and prints no
result. One process owns the chip: nothing here starts a child.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# fp32 tolerance of a sharded solver run against a one-device run
# (tests/multidevice_checks.py check_uneven_decomposition)
SHARDED_RTOL, SHARDED_ATOL = 1e-5, 1e-6


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cli(argv, label: str, dev) -> tuple[dict, list]:
    """One ``heat3d`` CLI run in this process. Returns its JSON summary
    and the residuals its ledger recorded, as ``[(step, value), ...]``."""
    from heat3d_tpu import cli
    from heat3d_tpu.obs.cli import read_ledger

    with tempfile.TemporaryDirectory() as tmp:
        ledger = os.path.join(tmp, "ledger.jsonl")
        out = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out):
            rc = cli.main(list(argv) + ["--ledger", ledger])
        wall = time.perf_counter() - t0
        check(rc == 0, f"{label}: heat3d {' '.join(argv)} exited {rc}")
        summary = json.loads(out.getvalue().strip().splitlines()[-1])
        residuals = [
            (e["step"], e["residual_l2"])
            for e in read_ledger(ledger)
            if e.get("event") == "residual"
        ]
    route = summary["route"]
    say(
        f"{label}: grid={summary['grid']} stencil={summary['stencil']} "
        f"dtype={summary['dtype']} mesh={summary['mesh']} route={route} "
        f"compile+warmup_s={summary['warmup_s']:.3f} steps={summary['steps']} "
        f"seconds={summary['seconds']:.6f} "
        f"smoke_only_gcell_s_per_chip="
        f"{summary['gcell_updates_per_sec_per_chip']:.3f} "
        f"residuals={residuals} wall_s={wall:.3f} "
        f"peak_bytes_in_use={peak_bytes(dev)}"
    )
    return summary, residuals


def check_kernel_route(route: dict, label: str, kernel: str = "direct",
                       backend: str | None = "pallas") -> None:
    """The route the step builders recorded when they dispatched."""
    if backend is not None:
        check(route["backend"] == backend, f"{label}: backend {route}")
    check(route["kernel"] == kernel, f"{label}: {kernel} kernel not taken: "
          f"{route}")
    check(not route["interpret"], f"{label}: interpret mode on: {route}")


@contextlib.contextmanager
def watchdog(label: str, seconds: int):
    """Exit the process (dumping every thread's stack) if ``label`` has
    not finished within ``seconds``: a run that hangs on the chip ends
    here, named, instead of at the caller's time limit."""
    import faulthandler

    say(f"{label}: watchdog {seconds} s")
    faulthandler.dump_traceback_later(seconds, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def check_falling(residuals, label: str) -> None:
    import math

    vals = [r for _, r in residuals]
    check(len(vals) >= 2, f"{label}: want >= 2 residuals, got {residuals}")
    check(all(math.isfinite(v) for v in vals), f"{label}: {residuals}")
    check(vals[-1] < vals[0], f"{label}: residual did not fall: {residuals}")


def hlo_and_sync_check(cfg, dev, steps: int) -> None:
    """The compiled time loop holds the Mosaic kernel, and
    ``block_until_ready`` waits as long as a host readback does."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.utils.timing import force_sync

    solver = HeatSolver3D(cfg, devices=[dev])
    u = solver.init_state("hot-cube")
    t0 = time.perf_counter()
    text = (
        jax.jit(lambda x, n: solver.run(x, n))
        .lower(u, jnp.int32(steps)).compile().as_text()
    )
    say(f"hlo: compile_s={time.perf_counter() - t0:.3f} "
        f"tpu_custom_call={'tpu_custom_call' in text}")
    check("tpu_custom_call" in text, "compiled time loop has no Mosaic kernel")
    u = solver.run(u, steps)  # warm the solver's own executable
    force_sync(u)
    times = {}
    for name, wait in (
        ("block_until_ready", jax.block_until_ready),
        ("host_readback", force_sync),
        ("block_until_ready_2", jax.block_until_ready),
        ("host_readback_2", force_sync),
    ):
        t0 = time.perf_counter()
        u = solver.run(u, steps)
        wait(u)
        times[name] = time.perf_counter() - t0
    say(f"sync: {steps} steps, seconds by wait: {times}")
    del u, solver
    gc.collect()


def one_chip(dev) -> None:
    from heat3d_tpu.cli import build_parser, config_from_args
    from heat3d_tpu.tune.cache import resolve_config

    cell_a = ["--grid", "1024", "--stencil", "7pt", "--dtype", "fp32",
              "--bc", "dirichlet", "--init", "hot-cube", "--mesh", "1", "1",
              "1", "--backend", "auto", "--time-blocking", "2"]
    # golden first: the small route check before the 8 GiB ones
    g_argv = ["--grid", "256"] + cell_a[2:] + ["--steps", "20",
                                               "--golden-check"]
    s, res = run_cli(g_argv + ["--residual-every", "10"], "golden-256", dev)
    check_kernel_route(s["route"], "golden-256")
    say(f"golden-256: golden_rel_err={s['golden_rel_err']:.3e} "
        f"golden_pass={s['golden_pass']}")
    check(s["golden_pass"], f"golden check failed: {s}")
    check_falling(res, "golden-256")

    s, res = run_cli(cell_a + ["--steps", "20", "--residual-every", "10"],
                     "cell-a 7pt fp32 1024^3", dev)
    check_kernel_route(s["route"], "cell-a")
    check_falling(res, "cell-a")

    cfg = resolve_config(config_from_args(build_parser().parse_args(
        cell_a + ["--steps", "20"])))
    hlo_and_sync_check(cfg, dev, steps=20)

    cell_b = [a if a != "7pt" else "27pt" for a in cell_a]
    s, res = run_cli(cell_b + ["--steps", "6", "--residual-every", "3"],
                     "cell-b 27pt fp32 1024^3", dev)
    check_kernel_route(s["route"], "cell-b")
    check_falling(res, "cell-b")

    cell_c = (["--grid", "512", "1024", "1024"] + cell_a[2:]
              + ["--dtype", "bf16"])
    s, res = run_cli(cell_c + ["--steps", "6", "--residual-every", "3"],
                     "cell-c bf16 512x1024x1024", dev)
    check_kernel_route(s["route"], "cell-c")
    check_falling(res, "cell-c")


def four_chips(devs) -> None:
    """Cell (d) at full size, then sharded runs against one-chip runs of
    the same problem on ``devs[0]``. Fields stay on the devices: the
    comparison runs there too."""
    import jax
    import jax.numpy as jnp

    from heat3d_tpu.cli import build_parser, config_from_args
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.tune.cache import resolve_config

    with watchdog("cell-d", 300):
        s, res = run_cli(
            ["--grid", "2048", "2048", "1024", "--mesh", "2", "2", "1",
             "--stencil", "7pt", "--dtype", "fp32", "--time-blocking", "2",
             "--steps", "6", "--residual-every", "3"],
            "cell-d (2,2,1) 2048x2048x1024", devs[0],
        )
    # 'auto' keeps the jnp padded-block compute here (no streaming ring
    # of a 1024^3 shard fits VMEM); the faces-direct kernel runs the bulk
    check_kernel_route(s["route"], "cell-d", backend=None)
    check_falling(res, "cell-d")

    @jax.jit
    def errors(got, want):
        diff = jnp.abs(got - want)
        bad = diff > SHARDED_ATOL + SHARDED_RTOL * jnp.abs(want)
        return jnp.max(diff), jnp.sum(bad), jnp.all(jnp.isfinite(got))

    def field(argv, devices, steps):
        cfg = resolve_config(config_from_args(build_parser().parse_args(
            argv + ["--steps", str(steps)])))
        check(cfg.padded_shape == cfg.grid.shape, f"padded: {cfg}")
        t0 = time.perf_counter()
        solver = HeatSolver3D(cfg, devices=devices)
        u = solver.run(solver.init_state("hot-cube"), steps)
        jax.block_until_ready(u)
        route = solver.route
        say(f"  {cfg.mesh.shape} halo={cfg.halo} overlap={cfg.overlap} "
            f"tb={cfg.time_blocking}: "
            f"route={route} {steps} steps, "
            f"wall_s={time.perf_counter() - t0:.3f}")
        check(not route["interpret"], f"interpret mode on: {route}")
        del solver
        gc.collect()
        return u, route

    def compare(label, argv, meshes, steps):
        with watchdog(f"{label} one chip", 300):
            want, _ = field(argv + ["--mesh", "1", "1", "1"], devs[:1], steps)
        for mesh in meshes:
            name = f"{label} mesh={mesh}"
            with watchdog(name, 300):
                got, _ = field(argv + ["--mesh", *map(str, mesh)], devs,
                               steps)
            err, n_bad, finite = errors(
                got, jax.device_put(want, got.sharding))
            say(f"{name}: max_abs_err vs one chip = {float(err):.3e}, "
                f"cells outside rtol={SHARDED_RTOL} atol={SHARDED_ATOL}"
                f": {int(n_bad)}")
            check(bool(finite), f"{name}: field not finite")
            check(int(n_bad) == 0, f"{name}: {int(n_bad)} cells differ")
            del got
        del want
        gc.collect()

    base = ["--stencil", "7pt", "--dtype", "fp32", "--time-blocking", "2"]
    compare("sharded 1024^3", ["--grid", "1024"] + base,
            [(2, 2, 1), (4, 1, 1)], steps=6)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, HERE)
    try:
        import heat3d_tpu
    except ImportError as e:
        print(f"chip_smoke: the repository is not beside this script: {e}",
              file=sys.stderr)
        return 1
    if not os.path.abspath(heat3d_tpu.__file__).startswith(HERE + os.sep):
        print(f"chip_smoke: heat3d_tpu comes from {heat3d_tpu.__file__}, "
              f"not from {HERE}", file=sys.stderr)
        return 1
    from heat3d_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = devs[: args.chips]
    say(f"device kind={devs[0].device_kind!r} platform={platform} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache}")
    t0 = time.perf_counter()
    try:
        one_chip(devs[0]) if args.chips == 1 else four_chips(devs)
    except (SmokeFailure, AssertionError, ValueError) as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
