"""kernel-remote (ANL1031-1033) — remote-copy targets certified against
the mesh neighbor graph and the ExchangePlan schedule.

``make_async_remote_copy`` takes a raw ``device_id`` — nothing stops a
kernel from shipping a face to the wrong chip, and no parity test run
on a synchronous emulator will catch a target that is merely *shifted*
(every device still receives exactly one face; the values are wrong
only at scale, on hardware, under a real mesh). This checker evaluates
each copy's ``device_id`` expression concretely at EVERY device
position of the case's ring and proves:

- **ANL1031** — each remote-copy program point realizes a bijection
  equal to one of the two ±1 ring shifts
  :func:`heat3d_tpu.parallel.halo.shift_perm` builds — the SAME
  neighbor-graph source the ppermute exchange and the IR tier's ANL601
  certify against, so all three tiers answer to one oracle. (The
  kernels always run the torus-symmetric transfer — Dirichlet edges
  substitute values after the wait — so the kernel-side contract is the
  periodic shift.)
- **ANL1032** — a plan-driven exchange must realize the
  ``ExchangePlan``'s axis schedule: one kernel per sharded axis, in the
  plan's corner-propagation order, each moving data along exactly that
  axis (a dict ``device_id`` touching any other mesh axis fires).
- **ANL1033** — direction completeness: every exchange kernel must
  carry BOTH ring directions (on a size-2 ring the two shifts coincide
  — the self-inverse case ANL604 pinned at the IR tier — and one class
  suffices).
"""

from __future__ import annotations

from typing import Dict, List, Set, Tuple

from heat3d_tpu.analysis.findings import ERROR, Finding

CHECKER = "kernel-remote"


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


def _target_on_axis(device_id, axis_name):
    """The target coordinate along ``axis_name``, or a reason string.

    Scalar device ids address the (single) shard_map mesh axis; dict
    (MESH partial) ids must move ONLY the exchange axis."""
    if isinstance(device_id, dict):
        if set(device_id) != {axis_name}:
            return None, (
                f"device_id moves mesh axes {sorted(device_id)} — the "
                f"exchange axis is {axis_name!r}"
            )
        v = device_id[axis_name]
        if not isinstance(v, int):
            return None, "device_id not concretely evaluable"
        return v, None
    if isinstance(device_id, int):
        return device_id, None
    return None, "device_id not concretely evaluable"


def check_case(case) -> List[Finding]:
    from heat3d_tpu.parallel.halo import shift_perm

    findings: List[Finding] = []
    seen: set = set()

    def emit(code, invariant, message):
        key = (code, invariant)
        if key in seen:
            return
        seen.add(key)
        findings.append(_finding(case, code, invariant, message))

    if not case.comm:
        return findings
    calls = case.calls()
    if len(calls) != len(case.comm):
        sched = (
            f"plan {case.plan_key}" if case.plan_key else "expected schedule"
        )
        emit(
            "ANL1032",
            "schedule|call-count",
            f"traced program has {len(calls)} exchange kernel(s) but the "
            f"{sched} wants {len(case.comm)} — an axis exchange is "
            "missing or duplicated",
        )
        return findings
    for ci, axis in enumerate(case.comm):
        # pairs per remote-copy program point, aggregated over every
        # device position of the ring
        by_pt: Dict[Tuple, Set[Tuple[int, int]]] = {}
        for rec in case.sims(ci):
            my = rec.ctx.get(axis.name, (None, None))[0]
            for ev in rec.events:
                if ev.kind != "dma_start" or not ev.info.get("remote"):
                    continue
                tgt, reason = _target_on_axis(
                    ev.info.get("device_id"), axis.name
                )
                if tgt is None:
                    emit(
                        "ANL1032" if "axes" in (reason or "") else "ANL1031",
                        f"call{ci}|offaxis|pt{ev.pt}",
                        f"call #{ci} (device {rec.ctx}): {reason}",
                    )
                    continue
                if my is None:
                    emit(
                        "ANL1031",
                        f"call{ci}|noctx",
                        f"call #{ci}: device context lacks the exchange "
                        f"axis {axis.name!r} — matrix entry is stale",
                    )
                    continue
                by_pt.setdefault(ev.pt, set()).add((my, int(tgt)))
        if not by_pt:
            emit(
                "ANL1033",
                f"call{ci}|no-remote-copies",
                f"call #{ci}: exchange kernel issues no remote copies at "
                "all on any device position",
            )
            continue
        # the kernel-side contract is the torus shift (Dirichlet edges
        # substitute values after the wait; the transfer always runs)
        shifts = {
            +1: frozenset(shift_perm(axis.size, +1, True)),
            -1: frozenset(shift_perm(axis.size, -1, True)),
        }
        dirs_found: Set[int] = set()
        for pt, pairs in sorted(by_pt.items()):
            fp = frozenset(pairs)
            matched = [d for d, s in shifts.items() if fp == s]
            if not matched:
                emit(
                    "ANL1031",
                    f"call{ci}|non-neighbor|pt{pt}",
                    f"call #{ci} remote copy at pt{pt}: device targets "
                    f"{sorted(pairs)} are not the ±1 neighbor bijection "
                    f"shift_perm({axis.size}, ±1) on axis "
                    f"{axis.name!r} — the face lands on the wrong chip",
                )
                continue
            dirs_found.update(matched)
        # size-2 rings are exempt: the +1 and -1 shifts coincide
        # (self-inverse), so one matched class covers both directions
        if axis.size > 2 and dirs_found != {+1, -1}:
            emit(
                "ANL1033",
                f"call{ci}|one-way",
                f"call #{ci}: only direction(s) {sorted(dirs_found)} are "
                "exchanged — a halo exchange must push both ring "
                "directions or one face of every shard stays stale",
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
