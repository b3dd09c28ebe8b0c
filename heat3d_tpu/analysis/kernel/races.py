"""kernel-races (ANL1011-1013) — happens-before over ring slots and DMA
edges.

The %3 VMEM ring discipline every streaming kernel shares (``_stream``,
``_stream2``, ``_streamk`` k=2..4, the direct kernels' rings): plane
``p`` lives in slot ``p % 3``,
written once per grid step, and every consumer stage at step ``i`` reads
three *consecutively produced* planes — the writes from steps ``i-2``,
``i-1``, ``i`` of the same chunk column. This checker rebuilds the
happens-before graph from the simulated effect timeline
(:mod:`..interp`) and proves it:

- **ANL1011** — a read of a scratch plane no write (kernel store or
  completed DMA) ever produced on this control path: the stage fired
  before its ring primed (the classic off-by-one in a ``pl.when`` fire
  predicate).
- **ANL1012** — a read (or kernel write) of a buffer a still-in-flight
  DMA copy may write: the write-before-read hazard. THE interpret-tier
  blind spot — interpret mode completes copies synchronously at
  ``start()``, so value-parity tests pass while hardware races (the
  blindness-proof test pins this).
- **ANL1013** — a ring read observing a *stale or colliding* slot: the
  producing write is more than the ring's 3-step window behind the read
  (or in another chunk column), or two planes of one firing stage
  observe writes from the same step — the slot was reused before its
  last consumer, i.e. a later stage may overwrite data still needed
  (loop-order and ring-size bugs).

The lag rule is deliberately semantic-free: it never re-derives what
plane a read *should* see (that is the parity tests' job) — it proves
the schedule shape every 3-slot ring must have, which is exactly what
parity cannot prove.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from heat3d_tpu.analysis.findings import ERROR, Finding

CHECKER = "kernel-races"

# reads within this many trailing-grid-dim steps of the producing write
# are ring-consistent (3-slot ring: the value written at step i is
# legitimately consumed at steps i, i+1, i+2)
RING_WINDOW = 2


def _is_ring(info) -> bool:
    return (
        info.role == "scratch"
        and info.sem_kind is None
        and len(info.shape) == 3
        and info.shape[0] == 3
    )


def _overlaps(a, b) -> bool:
    """Do two plane ids possibly alias? Unknown/whole (None) aliases
    everything; slices by range; ints exactly."""
    if a is None or b is None:
        return True
    ar = (a, a + 1) if isinstance(a, int) else (a[1], a[1] + a[2])
    br = (b, b + 1) if isinstance(b, int) else (b[1], b[1] + b[2])
    return ar[0] < br[1] and br[0] < ar[1]


def replay(rec):
    """Walk one simulation's timeline once, classifying every effect.

    Returns ``(write_log, hazards)``: ``write_log`` maps
    ``(ref, plane)`` -> ordered list of write times (kernel stores AND
    completed DMA landings — a landing commits at its recv wait, which
    is when its content is safe to read); ``hazards`` is a list of
    ``(kind, ev, detail)`` in-flight violations found along the way.
    Memoized per record: the coverage checker re-reads the same
    timelines (once per output), and the chunked cases' event streams
    are the dominant cost of the whole ``--kernel`` run."""
    cached = getattr(rec, "_replay_cache", None)
    if cached is not None:
        return cached
    writes: Dict[Tuple[int, Any], List[Tuple[Tuple[int, ...], int]]] = {}
    # recv-cell -> (dst ref, dst plane, start event); send-cell -> src ref
    in_flight: Dict[Any, Tuple[int, Any, Any]] = {}
    fragile_src: Dict[Any, Tuple[int, Any, Any]] = {}
    hazards: List[Tuple[str, Any, str]] = []

    def in_flight_on(ref, plane):
        for cell, (dref, dplane, sev) in in_flight.items():
            if dref == ref and _overlaps(dplane, plane):
                return cell, sev
        return None

    def fragile_on(ref, plane):
        for cell, (sref, splane, sev) in fragile_src.items():
            if sref == ref and _overlaps(splane, plane):
                return cell, sev
        return None

    def log_write(ref, plane, time, order):
        writes.setdefault((ref, plane), []).append((time, order))

    for ev in rec.events:
        if ev.kind == "write":
            hit = in_flight_on(ev.ref, ev.plane)
            if hit is not None:
                hazards.append(
                    (
                        "write-in-flight-dst",
                        ev,
                        f"kernel write at grid{ev.time} lands in a buffer "
                        f"a DMA started at grid{hit[1].time} is still "
                        "writing",
                    )
                )
            frag = fragile_on(ev.ref, ev.plane)
            if frag is not None:
                hazards.append(
                    (
                        "write-in-flight-src",
                        ev,
                        f"kernel write at grid{ev.time} mutates the "
                        f"source of a DMA started at grid{frag[1].time} "
                        "before its send wait — the transfer may ship "
                        "either value",
                    )
                )
            log_write(ev.ref, ev.plane, ev.time, ev.order)
        elif ev.kind == "read":
            hit = in_flight_on(ev.ref, ev.plane)
            if hit is not None:
                hazards.append(
                    (
                        "read-in-flight-dst",
                        ev,
                        f"read at grid{ev.time} of a buffer a DMA started "
                        f"at grid{hit[1].time} is still writing (no recv "
                        "wait between them)",
                    )
                )
        elif ev.kind == "dma_start":
            recv = ev.info.get("recv_cell")
            if recv is not None:
                in_flight[recv] = (ev.ref, ev.plane, ev)
            send = ev.info.get("send_cell")
            src = ev.info.get("src")
            if src is not None:
                # local copies have no send sem: the src stays fragile
                # until the recv wait retires the transfer
                fragile_src[send if send is not None else recv] = (
                    src,
                    ev.info.get("src_plane"),
                    ev,
                )
        elif ev.kind == "dma_wait":
            cell = ev.info.get("recv_cell")
            if cell in in_flight:
                dref, dplane, _sev = in_flight.pop(cell)
                log_write(dref, dplane, ev.time, ev.order)
                # a local copy's recv wait releases its source too
                fragile_src.pop(cell, None)
            else:
                fragile_src.pop(cell, None)
    rec._replay_cache = (writes, hazards)
    return writes, hazards


def _last_write_before(writes, ref, plane, order):
    """(time, order) of the newest write to (ref, plane) — exact plane,
    whole-ref, or overlapping slice — before program order ``order``."""
    best = None
    for (wref, wplane), log in writes.items():
        if wref != ref or not _overlaps(wplane, plane):
            continue
        for t, o in log:
            if o < order and (best is None or o > best[1]):
                best = (t, o)
    return best


def _finding(case, code, invariant, message) -> Finding:
    return Finding(
        checker=CHECKER,
        severity=ERROR,
        path=case.path,
        line=0,
        code=code,
        symbol=f"{case.key}|{invariant}",
        message=f"[{case.key}] {case.entry}: {message}",
    )


def check_case(case) -> List[Finding]:
    findings: List[Finding] = []
    seen: set = set()

    def emit(code, invariant, message):
        key = (code, invariant)
        if key in seen:
            return
        seen.add(key)
        findings.append(_finding(case, code, invariant, message))

    for ci in range(len(case.calls())):
        for rec in case.sims(ci):
            writes, hazards = replay(rec)
            for kind, ev, detail in hazards:
                emit(
                    "ANL1012",
                    f"call{ci}|{kind}|ref{ev.ref}",
                    f"call #{ci} (device {rec.ctx or 'solo'}): {detail} — "
                    "interpret-mode parity cannot see this (its DMA "
                    "completes synchronously); hardware races",
                )
            # ring-slot lag discipline
            groups: Dict[Tuple, List[Tuple[int, int]]] = {}
            for ev in rec.events:
                if ev.kind != "read":
                    continue
                info = rec.refs[ev.ref]
                if info.role == "scratch" and info.sem_kind is None:
                    w = _last_write_before(writes, ev.ref, ev.plane, ev.order)
                    if w is None:
                        emit(
                            "ANL1011",
                            f"call{ci}|uninitialized|ref{ev.ref}|"
                            f"plane{ev.plane}",
                            f"call #{ci} at grid{ev.time} (device "
                            f"{rec.ctx or 'solo'}): read of scratch "
                            f"ref{ev.ref} plane {ev.plane} that no write "
                            "ever produced on this control path — the "
                            "stage fires before its ring primes",
                        )
                        continue
                    if not _is_ring(info) or not isinstance(ev.plane, int):
                        continue
                    wt, _wo = w
                    same_col = wt[:-1] == ev.time[:-1]
                    lag = ev.time[-1] - wt[-1] if same_col else None
                    if lag is None or lag < 0 or lag > RING_WINDOW:
                        lag_desc = "cross-column" if lag is None else str(lag)
                        emit(
                            "ANL1013",
                            f"call{ci}|stale-slot|ref{ev.ref}",
                            f"call #{ci} at grid{ev.time} (device "
                            f"{rec.ctx or 'solo'}): ring ref{ev.ref} slot "
                            f"{ev.plane} observes the write from "
                            f"grid{wt} — outside the 3-slot window "
                            f"(lag {lag_desc}), so the consumer reads a "
                            "plane the ring already recycled (or a later "
                            "stage's overwrite)",
                        )
                        continue
                    groups.setdefault(
                        (ci, ev.ref, ev.time, ev.branch), []
                    ).append((int(ev.plane), int(lag)))
            for (gci, ref, time, _branch), pairs in groups.items():
                by_plane = dict(pairs)
                if len(by_plane) < 2:
                    continue
                lags = list(by_plane.values())
                if len(set(lags)) != len(lags):
                    emit(
                        "ANL1013",
                        f"call{gci}|slot-collision|ref{ref}",
                        f"call #{gci} at grid{time} (device "
                        f"{rec.ctx or 'solo'}): one stage reads ring "
                        f"ref{ref} planes {sorted(by_plane)} that observe "
                        f"writes from the same step (lags {lags}) — "
                        "distinct planes of a 3-slot ring must carry "
                        "distinct steps; a slot was recycled under a "
                        "still-pending consumer",
                    )
    return findings


def check(root: str, cases=None) -> List[Finding]:
    from heat3d_tpu.analysis.kernel import programs

    if cases is None:
        cases = programs.judged_kernels()
    findings: List[Finding] = []
    for case in cases:
        findings.extend(check_case(case))
    return findings
