"""Run one traced cell on the chip and write what its trace holds, for
reading a trace by hand before writing readers against it.

    python benchmark/tests/trace_probe.py --workload W --seed N \
        --seconds S --out chiprun_out/probe.json

Writes the raw ``.xplane.pb`` beside ``--out`` and, per plane: its lines with their event counts; for device planes
the distinct op names with their summed durations and one event's stats;
for the host plane the ``bench.*`` spans. Then prints the run's result
line as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
import tracing  # noqa: E402


def summary(data) -> dict:
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            entry = {"events": len(events)}
            if plane.name.startswith(tracing.DEVICE_PREFIX) or \
                    line.name in tracing.OP_LINES:
                names = defaultdict(lambda: [0, 0.0, None])
                for e in events:
                    n = names[e.name]
                    n[0] += 1
                    n[1] += e.duration_ns * 1e-9
                    if n[2] is None:
                        n[2] = {k: str(v)[:300] for k, v in
                                tracing._stats(e).items()}
                entry["ops"] = sorted(
                    ([k, *v] for k, v in names.items()),
                    key=lambda r: -r[2])[:60]
                entry["first_last_ns"] = [events[0].start_ns,
                                          events[-1].start_ns] \
                    if events else None
            elif plane.name == tracing.HOST_PLANE:
                entry["bench"] = [
                    [e.name, e.start_ns, e.duration_ns] for e in events
                    if e.name.startswith(tracing.HOST_PREFIX)][:40]
            lines[line.name] = entry
        out[plane.name] = lines
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    real_read = tracing.read

    def read(root):
        from jax.profiler import ProfileData

        path = tracing.find_xplane(root)
        data = ProfileData.from_file(path)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copy(path, args.out + ".xplane.pb")
        with open(args.out, "w") as f:
            json.dump(summary(data), f, indent=1)
        return real_read(root)

    tracing.read = read
    out = run.run(argparse.Namespace(workload=args.workload, seed=args.seed,
                                     seconds=args.seconds, trace=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
