"""Halo bytes one chip sends per solver step, in 1e6: the program's
``heat3d_halo_bytes_total`` counter over its ``heat3d_solver_steps_total``
(all programs), read from ``heat3d_tpu.obs.REGISTRY`` after the window.
Each ``solver.dispatch`` adds the traffic of its route's exchanges as the
exchange plan prices it from shapes (every face of a sharded axis in both
directions); nothing is read from the device. The warm-up dispatch counts
in both, so the ratio needs no window bounds. A program without these
counters leaves this metric out."""

BYTES = "heat3d_halo_bytes_total"
STEPS = "heat3d_solver_steps_total"


def read(ctx):
    from heat3d_tpu import obs

    snap = obs.REGISTRY.snapshot()
    if BYTES not in snap or STEPS not in snap:
        return None
    steps = sum(snap[STEPS]["values"].values())
    if steps <= 0:
        return None
    return sum(snap[BYTES]["values"].values()) / steps / 1e6
