"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the solver from the cell's configuration, makes the field on
the device from ``--seed``, and warms up one dispatch of every program the
window uses. The window then drives the cell's traffic for ``--seconds``
(``window.py``). Afterwards the plain reference redoes the window's last
dispatch and decides ``correct``. With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.

Without a TPU, with fewer chips than the cell asks for, with a device
kind missing from ``peaks.json``, or without the program beside it, the
run exits nonzero and prints no result. The last line of standard output
is one JSON object; the numbers compared, each with its limit, are the
last lines of standard error and the last key of that object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import tracing  # noqa: E402
from window import Driver  # noqa: E402

COMPILE_EVENTS = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(Exception):
    pass


def require_chip(chips: int):
    """The cell's devices and their peaks, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        peaks = harness.device_peaks(devs[0].device_kind)
    except harness.SpecError as e:
        raise NoChip(str(e)) from e
    return devs[:chips], peaks


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every compilation cached. The program's own entry points read
    ``JAX_COMPILATION_CACHE_DIR``, so it is set to the same place."""
    import jax

    os.makedirs(harness.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return harness.CACHE_DIR


class CompileLog:
    """Seconds of tracing, lowering and compiling (or loading from the
    cache) per phase, from ``jax.monitoring``; and the compilations."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.seconds: Dict[str, float] = {}
        self.compiles: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event.startswith(COMPILE_EVENTS):
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) \
                + duration
        if event == BACKEND_COMPILE:
            self.compiles[self.phase] = self.compiles.get(self.phase, 0) + 1


def import_program():
    sys.path.insert(0, harness.ROOT)
    try:
        import heat3d_tpu
    except ImportError as e:
        raise NoChip(f"the program is not beside the benchmark: {e}") from e
    where = os.path.abspath(heat3d_tpu.__file__)
    if not where.startswith(harness.ROOT + os.sep):
        raise NoChip(f"heat3d_tpu comes from {where}, not {harness.ROOT}")


def cli_argv(solver: Dict[str, Any]):
    argv = []
    for key, value in solver.items():
        argv.append("--" + key.replace("_", "-"))
        argv += [str(v) for v in value] if isinstance(value, list) \
            else [str(value)]
    return argv


def build_solver(config: Dict[str, Any], devs):
    """The solver as the ``heat3d`` CLI builds it from these settings."""
    from heat3d_tpu.cli import build_parser, config_from_args
    from heat3d_tpu.models.heat3d import HeatSolver3D
    from heat3d_tpu.tune.cache import resolve_config

    args = build_parser().parse_args(cli_argv(config["solver"]))
    cfg = resolve_config(config_from_args(args))
    if cfg.padded_shape != cfg.grid.shape:
        raise harness.SpecError(f"grid {cfg.grid.shape} does not divide over "
                                f"mesh {cfg.mesh.shape}")
    return HeatSolver3D(cfg, devices=devs)


def make_field(solver, config: Dict[str, Any], seed: int):
    """The configuration's initializer plus seeded uniform noise in
    ``[0, perturbation)``, made on the device with the solver's sharding
    in one jitted call: no host array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    amp = float(config.get("perturbation", 0.0))

    @functools.partial(jax.jit, out_shardings=solver.sharding,
                       donate_argnums=0)
    def perturb(u, key):
        noise = jax.random.uniform(key, u.shape, jnp.float32, 0.0, amp)
        return (u.astype(jnp.float32) + noise).astype(u.dtype)

    key = jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)),
                             np.uint32((seed >> 32) & 0xFFFFFFFF))
    return perturb(solver.init_state(config["init"]), key)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: harness.Cell
    window: Any  # window.WindowResult
    setup_s: float
    compile: CompileLog
    peaks: Dict[str, Any]
    chips: int
    global_cells: int
    local_cells: int
    itemsize: int
    trace: Optional[tracing.Trace] = None


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def judge(cell: harness.Cell, res) -> Dict[str, Dict[str, float]]:
    found = cell.reference().check(cell.config, res.kept, res.u,
                                   res.last_steps, res.last_residual)
    missing = set(cell.limits) - set(found)
    if missing:
        raise harness.SpecError(f"the reference gave no {sorted(missing)}")
    # a NaN or infinite reading fails its limit, and is printed as text:
    # strict JSON has no such numbers
    return {k: {"value": found[k] if math.isfinite(found[k])
                else str(found[k]), "limit": cell.limits[k]}
            for k in cell.limits}


def run(args) -> Dict[str, Any]:
    import jax

    cell = harness.load_cell(args.workload)
    enable_cache()
    devs, peaks = require_chip(cell.chips)
    import_program()
    compile_log = CompileLog()
    solver = build_solver(cell.config, devs)
    say(f"cell {cell.name} seed {args.seed}: device {devs[0].device_kind} "
        f"x{len(devs)} route {solver.route} cache {harness.CACHE_DIR}")
    driver = (Driver(solver, cell.traffic, jax.profiler.TraceAnnotation)
              if args.trace else Driver(solver, cell.traffic))
    u = make_field(solver, cell.config, args.seed)
    u = driver.warm_up(u)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    compile_log.phase = "window"
    setup_s = time.perf_counter() - T_START
    try:
        with driver.annotate("bench.window"):
            res = driver.run(u, args.seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    compile_log.phase = "after"
    del u
    peak = memory_peak(devs)
    slowest = max(range(res.dispatches), key=res.durations.__getitem__)
    say(f"window: {res.steps} steps in {res.dispatches} dispatches, "
        f"{res.seconds:.6f} s (dispatch s: fastest {min(res.durations):.6f},"
        f" slowest {res.durations[slowest]:.6f} at #{slowest}); "
        f"compilations in the window: "
        f"{compile_log.compiles.get('window', 0)}; setup {setup_s:.6f} s "
        f"(compile {compile_log.seconds.get('setup', 0.0):.6f} s); "
        f"peak_bytes_in_use {peak}")
    trace = None
    if trace_dir:
        trace = tracing.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    cfg = solver.cfg
    ctx = Context(
        cell=cell, window=res, setup_s=setup_s, compile=compile_log,
        peaks=peaks, chips=len(devs),
        global_cells=math.prod(cfg.grid.shape),
        local_cells=math.prod(cfg.local_shape),
        itemsize=jax.numpy.dtype(cfg.precision.storage).itemsize,
        trace=trace,
    )
    metrics = harness.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ctx)
    del solver, driver
    t_check = time.perf_counter()
    checks = judge(cell, res)
    say(f"reference check: {time.perf_counter() - t_check:.6f} s")
    correct = all(isinstance(c["value"], float) and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": res.dispatches,
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if trace is not None:
        device["busy_s"] = tracing.busy_s(trace)
        device["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": tracing.top_ops(trace),
                            "idle_gaps": tracing.idle_gaps(trace)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        out = run(args)
    except NoChip as e:
        say(f"no result: {e}")
        return 1
    except harness.SpecError as e:
        say(f"no result: {e}")
        return 2
    for name, c in out["checks"].items():
        say(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
