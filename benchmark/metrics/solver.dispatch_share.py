"""Share of the window the host spends inside the program's dispatch
calls, in %: the summed durations of the ``solver.dispatch`` spans that
did not compile (no ``compile_s`` field, so the warm-up dispatches are
left out) over the window's seconds. A dispatch span ends when the
program call returns, before the benchmark blocks or reads back, so this
is host time the program takes to launch its work, not device time.

Read after the window from the program's span ring,
``heat3d_tpu.obs.spans()``. A program without the ring, or without the
span, leaves this metric out."""

DISPATCH = "solver.dispatch"


def read(ctx):
    from heat3d_tpu import obs

    if not hasattr(obs, "spans"):
        return None
    spans = [s for s in obs.spans() if s["name"] == DISPATCH]
    if not spans:
        return None
    ns = sum(s["end_ns"] - s["start_ns"] for s in spans
             if "compile_s" not in s["fields"])
    return 100.0 * ns * 1e-9 / ctx.window.seconds
