"""Seconds of set-up spent inside the program, from its own spans: the
``solver.build`` span (``HeatSolver3D`` construction), the ``init_state``
spans, and the first ``solver.dispatch`` span of each program (the
``program`` field: ``run``, ``step_residual``, ...), whose compiles or
cache loads fall inside it. The rest of ``setup_s`` lies outside the
program: imports, backend start, the benchmark's own field noise and
copies.

Read after the window from the program's span ring,
``heat3d_tpu.obs.spans()``. A program without the ring, or without these
spans, leaves this metric out. Moves ``setup_s``."""

SETUP_SPANS = ("solver.build", "init_state")
DISPATCH = "solver.dispatch"


def read(ctx):
    from heat3d_tpu import obs

    if not hasattr(obs, "spans"):
        return None
    total, found, seen = 0.0, False, set()
    for s in obs.spans():
        if s["name"] == DISPATCH:
            program = s["fields"].get("program")
            if program in seen:
                continue
            seen.add(program)
        elif s["name"] not in SETUP_SPANS:
            continue
        found = True
        total += (s["end_ns"] - s["start_ns"]) * 1e-9
    return total if found else None
