"""Share of the traced window in which a halo op runs on a device's core
while no stencil kernel event runs there, in %, on the worst device.

Halo ops are the ``XLA Ops`` events of the collective-permutes (their
start and done instructions, where the core issues or waits on the
transfer) and the ops XLA names after the program's ``heat3d.halo*``
scopes (``parallel/halo.py``, ``parallel/plan.py``); kernel events are
those of ``stencil_roofline``. The transfers in flight (the trace's
``Async XLA Ops`` line) are not counted: while one is in flight the core
runs other ops or is idle, and idle time is ``device.idle_share``'s."""

import harness
import tracing

HALO = ("collective-permute", "%heat3d.halo")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    kernels = harness.metric("stencil_roofline").kernels
    window = t.window[1] - t.window[0]
    shares = []
    for ops in t.devices.values():
        ops = tracing.in_window(t, ops)
        halo = tracing.matching(ops, HALO)
        if not halo:
            continue
        exposed = tracing.minus(tracing.clip(tracing.spans(halo), t.window),
                                tracing.spans(kernels(ops)))
        shares.append(100.0 * tracing.length(exposed) / window)
    return max(shares) if shares else None
