"""Reading a profiler trace into intervals, and the interval arithmetic
the per-layer metrics share.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
with ``jax.profiler.ProfileData`` (nothing else needed). A device is a
plane named ``/device:TPU:<n>``. Its operations are the events of its
``XLA Ops`` line, each named by the HLO instruction's text (``%name =
shape opcode(...)``); the ``while``, ``conditional`` and ``call`` ops
that contain others are left out, so an interval is time a leaf op ran.
Its programs are the events of its ``XLA Modules`` line (``jit_<fn>(id)``).
The host's spans are the benchmark's own ``TraceAnnotation`` events, named
``bench.*``, on any line of the ``/host:CPU`` plane. All times are
nanoseconds on the trace's clock (the chip's events are placed on the
host's clock by the profiler).

The idea follows ``heat3d_tpu/obs/perf/timeline.py``'s
``device_phase_totals`` (duration per op, per device plane), copied and
extended with the window, unions and overlaps; nothing is imported from
the program.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
CONTAINERS = (" while(", " conditional(", " call(")
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench."
WINDOW = "bench.window"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    stats: Dict[str, object]

    def text(self) -> str:
        """The op's name and every string it carries, for matching."""
        return " ".join([self.name] + [str(v) for v in self.stats.values()
                                       if isinstance(v, str)])

    @property
    def short(self) -> str:
        """The HLO instruction's name, ``%heat3d.stencil.16``."""
        return self.name.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]  # plane name -> leaf ops, by start
    modules: Dict[str, List[Op]]  # plane name -> program executions
    host: List[Op]  # bench.* annotations
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(root: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the binding cannot convert
        return {}


def from_profile(data) -> Trace:
    """A ``Trace`` from a ``ProfileData``-shaped object (``planes`` with
    ``name`` and ``lines``; lines with ``name`` and ``events``; events with
    ``name``, ``start_ns``, ``duration_ns`` and ``stats``)."""
    devices: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                events = [Op(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             _stats(e)) for e in line.events]
                if line.name in OP_LINES:
                    ops += [o for o in events
                            if not any(c in o.name for c in CONTAINERS)]
                elif line.name in MODULE_LINES:
                    mods += events
            devices[plane.name] = sorted(ops, key=lambda o: o.start)
            modules[plane.name] = sorted(mods, key=lambda o: o.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [Op(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {}) for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    windows = [h for h in host if h.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW} span")
    w = windows[0]
    return Trace(devices=devices, modules=modules,
                 host=sorted(host, key=lambda o: o.start),
                 window=(w.start, w.end))


def read(root: str) -> Trace:
    from jax.profiler import ProfileData

    path = find_xplane(root)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return from_profile(ProfileData.from_file(path))


# ---- interval arithmetic ---------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Overlapping and touching intervals merged, sorted."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def minus(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of union(a) that no interval of b covers."""
    out = []
    bs = union(b)
    for lo, hi in union(a):
        cur = lo
        for c, d in bs:
            if d <= cur or c >= hi:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < hi:
            out.append((cur, hi))
    return out


def spans(ops: Iterable[Op]) -> List[Interval]:
    return [(o.start, o.end) for o in ops]


def matching(ops: Iterable[Op], patterns: Iterable[str]) -> List[Op]:
    pats = tuple(patterns)
    return [o for o in ops if any(p in o.text() for p in pats)]


def in_window(trace: Trace, ops: Iterable[Op]) -> List[Op]:
    lo, hi = trace.window
    return [o for o in ops if o.end > lo and o.start < hi]


def inside(ops: Iterable[Op], spans_: Iterable[Interval]) -> List[Op]:
    """The ops that start inside one of ``spans_``."""
    ivs = union(spans_)
    return [o for o in ops if any(a <= o.start < b for a, b in ivs)]


# ---- breakdown -------------------------------------------------------------


def top_ops(trace: Trace, n: int = 10) -> List[List[object]]:
    """The device operations that took most time in the window, in seconds
    summed per HLO instruction name and averaged over the devices."""
    total: Dict[str, float] = defaultdict(float)
    for ops in trace.devices.values():
        for o in ops:
            for a, b in clip([(o.start, o.end)], trace.window):
                total[o.short] += (b - a) * 1e-9
    k = max(len(trace.devices), 1)
    return [[name, s / k] for name, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List[object]]:
    """Device idle time in the window, in seconds summed per host phase
    (the innermost ``bench.*`` span around each gap's midpoint, else
    ``host.other``) and averaged over the devices, longest first."""
    total: Dict[str, float] = defaultdict(float)
    inner = [h for h in trace.host if h.name != WINDOW]
    for ops in trace.devices.values():
        busy = clip(spans(ops), trace.window)
        for a, b in minus([trace.window], busy):
            mid = (a + b) / 2
            around = [h for h in inner if h.start <= mid <= h.end]
            phase = (min(around, key=lambda h: h.end - h.start).name
                     if around else "host.other")
            total[phase] += (b - a) * 1e-9
    k = max(len(trace.devices), 1)
    return [[name, s / k] for name, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    k = max(len(trace.devices), 1)
    return sum(length(clip(spans(ops), trace.window))
               for ops in trace.devices.values()) * 1e-9 / k
