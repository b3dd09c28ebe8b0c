"""Everything the benchmark finds by name: BENCHMARK.json, the cell's
configuration, its traffic mix, its limits, its reference and the
metric readers.

Nothing here names a cell, a configuration or a metric. A later PR adds
one by adding a file and an entry in BENCHMARK.json:

- ``benchmark/configs/<config>.json``: the configuration as it is run
  (``solver`` holds the ``heat3d`` CLI's settings), with ``reference``
  naming its plain reference ``benchmark/references/<reference>.py``;
- ``benchmark/traffic/<traffic>.json``: parameters of the one general
  window driver (``window.py``);
- ``benchmark/limits/<workload>.json``: the limit of each number that
  decides ``correct``;
- ``benchmark/metrics/<metric>.py``: a reader with ``read(ctx)`` that
  returns the metric's value, or None where it finds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


class SpecError(Exception):
    """A missing or malformed benchmark file: the run prints no result."""


def load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def reference(self):
        ref = self.config["reference"]
        return load_module(
            os.path.join(BENCH_DIR, "references", f"{ref}.py"),
            f"bench_reference_{ref}",
        )


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names an unknown config "
                        f"{w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{w['traffic']}.json"))
    limits = load_json(os.path.join(BENCH_DIR, "limits", f"{workload}.json"))
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits={k: float(v["limit"]) for k, v in limits.items()},
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric(name: str):
    """The reader of metric ``name``, ``benchmark/metrics/<name>.py``."""
    return load_module(
        os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
    )


def read_metrics(metrics: List[Dict[str, Any]], ctx) -> Dict[str, Any]:
    """``{name: {"value", "unit"}}`` for every metric whose reader finds
    something to read; a reader that returns None leaves its metric out."""
    out = {}
    for m in metrics:
        value = metric(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_peaks(kind: str) -> Dict[str, Any]:
    """The row of ``peaks.json`` for ``kind``; an unknown kind is an error,
    never another chip's figure."""
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in peaks:
        raise SpecError(f"device kind {kind!r} is not in benchmark/peaks.json"
                        f" (have {sorted(peaks)})")
    return peaks[kind]
