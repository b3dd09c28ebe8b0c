"""The tuning cache: measured winning configs, keyed by what determines
them; auto knobs resolve through it.

Persistent/partitioned stencil-communication work (PAPERS.md) shows the
winning transport/overlap choice is topology- and size-dependent — so the
cache key is exactly that context:

    <chip generation>|p<processes>|d<devices>|g2^<bucket>|<equation fingerprint>|<dtype>

- **chip generation**: ``jax.devices()[0].device_kind`` normalized
  (``tpu-v5-lite`` / ``cpu`` / ...) — a v5e winner must not steer a v5p.
- **p/d**: process count and device count (the topology scale). The mesh
  FACTORIZATION is a searched knob, so it lives in the entry, not the key.
- **g2^bucket**: round(log2(grid cells per device)) — configs of similar
  per-chip working set share a winner; a 1024^3 entry must not steer a
  32^3 smoke run.
- **equation fingerprint/dtype**: the compute shape and HBM traffic
  class. The fingerprint (``eqn.fingerprint``) is the bare stencil kind
  for heat (committed entries stay addressable) and
  ``family:kind:spec-hash`` for spec-built families (docs/EQUATIONS.md).

Entry schema (``lint`` checks it; ``schema`` guards forward drift)::

    {"schema": 1,
     "entries": {"<key>": {
         "config": {"backend": ..., "halo": ..., "overlap": ...,
                    "time_blocking": ..., "halo_order": ..., "mesh": [..]},
         "gcell_per_sec_per_chip": <winner metric>,
         "default_gcell_per_sec_per_chip": <static-default metric or null>,
         "provenance": {"run_id": ..., "ts": ..., "jax_version": ...,
                        "platform": ..., "chip": ...}}},
     "peaks": {"<chip>": {"vector_gflops": <calibrated>,
                          "provenance": {...}}}}

``peaks`` is the calibrated per-chip peak-spec store
(``heat3d obs roofline --calibrate`` writes it;
``obs.perf.roofline.peak_spec`` reads it) — one store, one lint, one
provenance discipline for everything the tuner measures.

Resolution (:func:`resolve_config`) replaces ONLY the auto knobs —
``backend='auto'``, ``halo='auto'``, ``time_blocking=0``,
``halo_plan='auto'`` — with the cached winner's values; explicit knobs
are never overridden, and the mesh is never swapped (an explicitly
chosen decomposition is the user's call; ``tune apply`` emits it as a
flag instead). Every resolution lands
in the run ledger as ``tune_cache_hit`` / ``tune_cache_miss`` /
``tune_cache_stale`` (stale = jax-version mismatch, schema drift, or a
cached knob invalid in the current env, e.g. ``halo='dma'`` off-TPU);
misses and staleness fall back to the static defaults (halo
``ppermute``, time_blocking 1, halo_plan ``monolithic``, backend left
``auto``). Resolution fails soft: no cache error can kill the run being
configured.

``HEAT3D_TUNE_CACHE`` overrides the store path (default
``~/.cache/heat3d/tune_cache.json``); ``HEAT3D_TUNE_DISABLE=1`` skips
cache lookup entirely (the search driver sets it around its own trials
so an existing entry cannot steer the measurements that would replace
it).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Any, Dict, List, Optional, Tuple

from heat3d_tpu.core.config import SolverConfig

ENV_CACHE = "HEAT3D_TUNE_CACHE"
ENV_DISABLE = "HEAT3D_TUNE_DISABLE"
SCHEMA_VERSION = 1

# the knobs an entry's config must carry (lint + resolution contract)
CONFIG_KNOBS = (
    "backend", "halo", "overlap", "time_blocking", "halo_order", "halo_plan",
)

# in-process memo: (path) -> (mtime_ns, doc). One stat per lookup instead
# of one parse per solver construction (backend='auto' is the default
# everywhere, so resolution runs on nearly every build).
_DOC_CACHE: Dict[str, Tuple[int, Dict[str, Any]]] = {}


def cache_path(explicit: Optional[str] = None) -> str:
    """The store path: explicit arg > $HEAT3D_TUNE_CACHE > the per-user
    default."""
    if explicit:
        return explicit
    env = os.environ.get(ENV_CACHE)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "heat3d", "tune_cache.json"
    )


def chip_generation() -> str:
    """Normalized accelerator generation (``tpu-v5-lite`` / ``cpu`` /
    ``unknown``) — the hardware axis of the cache key. Never raises (a
    cache key must be computable even when the backend is wedged)."""
    try:
        import jax

        d = jax.devices()[0]
        kind = getattr(d, "device_kind", "") or d.platform
        return str(kind).strip().lower().replace(" ", "-") or "unknown"
    except Exception:  # noqa: BLE001 - key derivation fails soft
        return "unknown"


def _grid_bucket(cfg: SolverConfig) -> int:
    cells = max(cfg.grid.num_cells // max(cfg.mesh.num_devices, 1), 1)
    return round(math.log2(cells))


def cache_key(cfg: SolverConfig, batch_size: int = 1) -> str:
    """The lookup key for ``cfg`` in the CURRENT environment (chip
    generation and process count are read live — the same config keys
    differently on different hardware, by design).

    ``batch_size`` > 1 appends a batch-shape bucket (``b2^<round(log2
    B)>``) — the ensemble engine's workload axis (serve/ensemble): a
    winner measured for one solo run must not steer a 64-member packed
    batch (whose per-chip working set and halo:compute ratio differ), and
    vice versa. Solo keys stay byte-identical to the pre-batch format so
    every committed cache entry remains addressable.

    The stencil leg is the EQUATION FINGERPRINT (``eqn.fingerprint``):
    the bare stencil kind for the heat family — byte-identical to every
    committed pre-eqn key — and ``<family>:<kind>:<spec hash>`` for
    spec-built families, so an advection winner can never steer a heat
    run of the same footprint (their chain structure and stability
    envelope differ)."""
    try:
        import jax

        procs = int(jax.process_count())
    except Exception:  # noqa: BLE001
        procs = 1
    from heat3d_tpu import eqn

    parts = [
        chip_generation(),
        f"p{procs}",
        f"d{cfg.mesh.num_devices}",
        f"g2^{_grid_bucket(cfg)}",
        eqn.fingerprint(cfg),
        cfg.precision.storage,
    ]
    if batch_size > 1:
        parts.append(f"b2^{round(math.log2(batch_size))}")
    # integrator leg only when non-default (docs/INTEGRATORS.md): every
    # committed explicit-euler key stays byte-identical, and a winner
    # measured for one integrator's program family can never steer
    # another's (a leapfrog carry and a CG solve have different
    # halo:compute ratios than the explicit sweep)
    if cfg.integrator != "explicit-euler":
        parts.append(f"ti:{cfg.integrator}")
    return "|".join(parts)


def config_knobs(cfg: SolverConfig) -> Dict[str, Any]:
    """The judged knob values of ``cfg`` as a plain dict (entry payload).

    ``equation``/``eq_params`` are workload CONTEXT, not searched knobs
    (the key's fingerprint leg buckets on them) — persisted so ``tune
    apply`` can reconstruct the measured workload's exact flag line
    (the eq_params values feed the fingerprint hash; re-deriving them
    from apply-time flags would silently address a different bucket).
    Resolution never applies them (they are not in CONFIG_KNOBS)."""
    return {
        "backend": cfg.backend,
        "halo": cfg.halo,
        "overlap": bool(cfg.overlap),
        "time_blocking": int(cfg.time_blocking),
        "halo_order": cfg.halo_order,
        "halo_plan": cfg.halo_plan,
        "mesh": list(cfg.mesh.shape),
        "equation": cfg.equation,
        "eq_params": [[k, v] for k, v in cfg.eq_params],
        # workload context like equation: the key's ti leg buckets on it,
        # resolution never applies it (not in CONFIG_KNOBS)
        "integrator": cfg.integrator,
    }


# ---- store IO ---------------------------------------------------------------


def _empty_doc() -> Dict[str, Any]:
    return {"schema": SCHEMA_VERSION, "entries": {}, "peaks": {}}


def load(path: Optional[str] = None) -> Dict[str, Any]:
    """The parsed store document (empty document for a missing/unreadable
    file — a broken cache degrades to "no cache", never to a crash)."""
    p = cache_path(path)
    try:
        st = os.stat(p)
    except OSError:
        return _empty_doc()
    memo = _DOC_CACHE.get(p)
    if memo is not None and memo[0] == st.st_mtime_ns:
        return memo[1]
    try:
        with open(p) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return _empty_doc()
    if not isinstance(doc, dict):
        doc = _empty_doc()
    doc.setdefault("schema", SCHEMA_VERSION)
    # normalize, don't just default: a hand-edited store with a non-dict
    # entries/peaks section must degrade to "no cache" for every reader
    # (show/apply/resolve), not crash one of them — lint reports it
    for section in ("entries", "peaks"):
        if not isinstance(doc.get(section), dict):
            doc[section] = {}
    _DOC_CACHE[p] = (st.st_mtime_ns, doc)
    return doc


def _save(doc: Dict[str, Any], path: Optional[str] = None) -> str:
    """Atomic write (tmp + rename): a reader never sees a torn store, and
    a crash mid-write leaves the previous winners intact."""
    p = cache_path(path)
    d = os.path.dirname(os.path.abspath(p))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".tune_cache.", dir=d)
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, p)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _DOC_CACHE.pop(p, None)
    return p


def _provenance(**extra: Any) -> Dict[str, Any]:
    import datetime

    prov: Dict[str, Any] = {
        "ts": datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%SZ"
        ),
        "chip": chip_generation(),
    }
    try:
        import jax

        prov["jax_version"] = jax.__version__
        prov["platform"] = jax.default_backend()
    except Exception:  # noqa: BLE001
        prov["jax_version"] = None
        prov["platform"] = None
    from heat3d_tpu import obs

    prov["run_id"] = obs.get().run_id
    prov.update(extra)
    return prov


def store_entry(
    key: str,
    winner_cfg: SolverConfig,
    metric: float,
    default_metric: Optional[float] = None,
    path: Optional[str] = None,
    **prov_extra: Any,
) -> str:
    """Write/overwrite the winner for ``key``; returns the store path."""
    doc = dict(load(path))
    entries = dict(doc.get("entries") or {})
    entries[key] = {
        "config": config_knobs(winner_cfg),
        "gcell_per_sec_per_chip": float(metric),
        "default_gcell_per_sec_per_chip": (
            None if default_metric is None else float(default_metric)
        ),
        "provenance": _provenance(**prov_extra),
    }
    doc["entries"] = entries
    return _save(doc, path)


def store_peak(
    chip: str,
    vector_gflops: float,
    path: Optional[str] = None,
    **prov_extra: Any,
) -> str:
    """Record a calibrated VPU peak for ``chip`` (the shared store's
    ``peaks`` section — ``obs roofline --calibrate`` writes through
    here)."""
    doc = dict(load(path))
    peaks = dict(doc.get("peaks") or {})
    peaks[chip] = {
        "vector_gflops": float(vector_gflops),
        "provenance": _provenance(**prov_extra),
    }
    doc["peaks"] = peaks
    return _save(doc, path)


def load_peak(chip: str, path: Optional[str] = None) -> Optional[float]:
    """The calibrated VPU peak for ``chip``, or None. Never raises."""
    try:
        rec = (load(path).get("peaks") or {}).get(chip)
        v = rec.get("vector_gflops") if isinstance(rec, dict) else None
        return float(v) if isinstance(v, (int, float)) and v > 0 else None
    except Exception:  # noqa: BLE001 - peak lookup is telemetry
        return None


# ---- schema lint ------------------------------------------------------------


def lint(path: Optional[str] = None) -> List[str]:
    """Schema defects of the store at ``path`` (empty list = clean; a
    missing store is clean — there is nothing to corrupt)."""
    p = cache_path(path)
    if not os.path.exists(p):
        return []
    try:
        with open(p) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable store: {type(e).__name__}: {e}"]
    bad: List[str] = []
    if not isinstance(doc, dict):
        return ["store is not a JSON object"]
    if doc.get("schema") != SCHEMA_VERSION:
        bad.append(
            f"schema {doc.get('schema')!r} != {SCHEMA_VERSION} "
            "(regenerate with `heat3d tune run`)"
        )
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        bad.append("'entries' is not an object")
        entries = {}
    for key, e in entries.items():
        where = f"entry {key!r}"
        if not isinstance(e, dict):
            bad.append(f"{where}: not an object")
            continue
        cfgd = e.get("config")
        if not isinstance(cfgd, dict):
            bad.append(f"{where}: missing config")
        else:
            for k in CONFIG_KNOBS:
                if k not in cfgd:
                    bad.append(f"{where}: config missing knob {k!r}")
            tb = cfgd.get("time_blocking")
            if tb is not None and (not isinstance(tb, int) or tb < 1):
                bad.append(f"{where}: time_blocking {tb!r} not an int >= 1")
            for knob in ("backend", "halo", "halo_plan"):
                if cfgd.get(knob) == "auto":
                    bad.append(
                        f"{where}: {knob}='auto' is not a concrete route "
                        "(entries must store what executes)"
                    )
        if not isinstance(e.get("gcell_per_sec_per_chip"), (int, float)):
            bad.append(f"{where}: missing numeric gcell_per_sec_per_chip")
        prov = e.get("provenance")
        if not isinstance(prov, dict):
            bad.append(f"{where}: missing provenance")
        elif not prov.get("jax_version"):
            bad.append(f"{where}: provenance missing jax_version")
    peaks = doc.get("peaks")
    if peaks is not None and not isinstance(peaks, dict):
        bad.append("'peaks' is not an object")
    for chip, rec in (peaks or {}).items():
        if not (
            isinstance(rec, dict)
            and isinstance(rec.get("vector_gflops"), (int, float))
            and rec["vector_gflops"] > 0
        ):
            bad.append(f"peak {chip!r}: missing positive vector_gflops")
    return bad


# ---- resolution -------------------------------------------------------------


def _static_fallback(cfg: SolverConfig) -> SolverConfig:
    """The pre-tuner defaults for the auto knobs (backend keeps its own
    'auto' semantics — models.heat3d._select_backend resolves it)."""
    kw: Dict[str, Any] = {}
    if cfg.halo == "auto":
        kw["halo"] = "ppermute"
    if cfg.time_blocking == 0:
        kw["time_blocking"] = 1
    if cfg.halo_plan == "auto":
        kw["halo_plan"] = "monolithic"
    return dataclasses.replace(cfg, **kw) if kw else cfg


def _auto_knobs(cfg: SolverConfig) -> List[str]:
    autos = []
    if cfg.backend == "auto":
        autos.append("backend")
    if cfg.halo == "auto":
        autos.append("halo")
    if cfg.time_blocking == 0:
        autos.append("time_blocking")
    if cfg.halo_plan == "auto":
        autos.append("halo_plan")
    return autos


def resolve_config(
    cfg: SolverConfig, path: Optional[str] = None, batch_size: int = 1
) -> SolverConfig:
    """Resolve ``cfg``'s auto knobs through the tuning cache.

    No auto knobs -> returned unchanged (fast path, no IO). Otherwise the
    cache entry for :func:`cache_key` supplies the values; ledger events
    record the outcome (``tune_cache_hit`` with the applied knobs,
    ``tune_cache_miss``, or ``tune_cache_stale`` with the reason). Any
    failure — unreadable store, stale entry, cached knob invalid in this
    env — falls back to :func:`_static_fallback`. Never raises.
    ``batch_size`` routes ensemble workloads (serve/ensemble) to their
    own batch-shape-bucketed entries — see :func:`cache_key`.

    Non-default integrators never consult the cache: every committed
    entry describes the explicit program family, so their autos pin
    through ``timeint.pin_config`` (jnp + ppermute + tb=1) instead —
    the one rule shared with the solver constructor."""
    if cfg.integrator != "explicit-euler":
        from heat3d_tpu import timeint

        return timeint.pin_config(cfg)
    try:
        autos = _auto_knobs(cfg)
        if not autos or os.environ.get(ENV_DISABLE):
            return _static_fallback(cfg)
        return _resolve(cfg, autos, path, batch_size=batch_size)
    except Exception:  # noqa: BLE001 - resolution must never kill a run
        try:
            return _static_fallback(cfg)
        except Exception:  # noqa: BLE001
            return cfg


# per-process dedup of resolution events: backend='auto' is the default
# everywhere and resolution runs at the entry point AND the solver
# constructor, so without this every ordinary run would ledger the same
# miss twice (keyed per run_id so a new ledger segment re-emits)
_EVENT_ONCE: set = set()


def _event_once(name: str, key: str, **fields: Any) -> None:
    from heat3d_tpu import obs

    ledger = obs.get()
    tag = (ledger.run_id, name, key)
    if tag in _EVENT_ONCE:
        return
    _EVENT_ONCE.add(tag)
    ledger.event(name, key=key, **fields)


def _resolved_invalid(resolved: SolverConfig) -> Optional[str]:
    """Why the cache-resolved config cannot BUILD in this environment, or
    None. Runs the real builders (mesh + backend selection + the
    multistep program — jit wrappers only, nothing compiles), so the
    gates are the production gates: a cached backend='pallas' the current
    local shape doesn't support, or a cached overlap/tb combination,
    degrades to the static fallback instead of
    killing the run at solver construction."""
    try:
        from heat3d_tpu.models.heat3d import _select_backend
        from heat3d_tpu.parallel.step import make_multistep_fn
        from heat3d_tpu.parallel.topology import build_mesh

        mesh = build_mesh(resolved.mesh)
        make_multistep_fn(resolved, mesh, _select_backend(resolved))
    except Exception as e:  # noqa: BLE001 - any build failure = stale
        return f"{type(e).__name__}: {str(e)[:160]}"
    return None


def _resolve(
    cfg: SolverConfig,
    autos: List[str],
    path: Optional[str],
    batch_size: int = 1,
) -> SolverConfig:
    p = cache_path(path)
    key = cache_key(cfg, batch_size=batch_size)
    entry = (load(p).get("entries") or {}).get(key)
    if not isinstance(entry, dict):
        _event_once(
            "tune_cache_miss",
            key,
            path=p,
            cache_present=os.path.exists(p),
            autos=autos,
        )
        return _static_fallback(cfg)

    def _stale(reason: str) -> SolverConfig:
        _event_once(
            "tune_cache_stale", key, path=p, reason=reason, autos=autos
        )
        return _static_fallback(cfg)

    prov = entry.get("provenance") or {}
    try:
        import jax

        jv = jax.__version__
    except Exception:  # noqa: BLE001
        jv = None
    if jv is not None and prov.get("jax_version") != jv:
        # a different jax may route/compile differently: the measured
        # winner is evidence about a stack that no longer exists
        return _stale(
            f"jax_version {prov.get('jax_version')!r} != {jv!r}"
        )
    cfgd = entry.get("config")
    if not isinstance(cfgd, dict) or any(k not in cfgd for k in CONFIG_KNOBS):
        return _stale("entry config missing knobs (schema drift)")
    kw: Dict[str, Any] = {}
    for knob in autos:
        kw[knob] = cfgd[knob]
    # an entry must supply CONCRETE values for the knobs it resolves —
    # a cached 'auto'/0 would loop the question back to the cache (or,
    # for backend, emit a hit that resolved nothing)
    if (
        kw.get("halo") == "auto"
        or kw.get("backend") == "auto"
        or kw.get("time_blocking") == 0
        or kw.get("halo_plan") == "auto"
    ):
        return _stale("entry carries unresolved auto knobs")
    try:
        resolved = dataclasses.replace(cfg, **kw)
    except (ValueError, TypeError) as e:
        return _stale(f"cached knobs invalid here: {e}")
    # env gate the resolution can check cheaply: a cached DMA transport is
    # only runnable on TPU (mirrors HeatSolver3D's constructor check,
    # which the build validation below cannot see — the dma import is
    # trace-time)
    if resolved.halo == "dma":
        try:
            import jax

            platform = jax.devices()[0].platform
        except Exception:  # noqa: BLE001
            platform = "unknown"
        if platform != "tpu":
            return _stale(f"cached halo='dma' but platform is {platform!r}")
    # ... and the full build gates: the key buckets grid shapes (and the
    # entry may predate an env change), so the cached knobs can be
    # invalid for THIS exact config even on the same hardware
    reason = _resolved_invalid(resolved)
    if reason is not None:
        return _stale(f"cached knobs do not build here: {reason}")
    # hits are NOT deduped: a hit consumes the auto knobs it applies, so
    # the constructor's safety net has nothing left to re-resolve — and
    # distinct hits (different auto sets) are each worth a record
    from heat3d_tpu import obs

    obs.get().event(
        "tune_cache_hit",
        key=key,
        path=p,
        applied={k: kw[k] for k in autos},
        gcell_per_sec_per_chip=entry.get("gcell_per_sec_per_chip"),
        cached_ts=prov.get("ts"),
        cached_run_id=prov.get("run_id"),
    )
    return resolved
