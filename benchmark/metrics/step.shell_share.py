"""Share of the traced window in which the step program runs its small
ops, in %, on the worst device: the leaf ops inside executions of the
solver's multistep loop (``jit_runk``, or ``jit_run`` at tb=1, on the
``XLA Modules`` line) that are neither a stencil kernel event (those of
``stencil_roofline``), nor a ``%copy``, nor a collective (by the HLO
instruction's name: an operand's name may be a collective's); the union
of their intervals, clipped to the window, over the window.

On the four-chip faces-direct routes these are the shard-boundary shell
patches (slab assembly, both tap applications, the mid pin, the
``dynamic-update-slice``), plus the small glue around the face permutes
(slices, Dirichlet edge selects) and the scratch buffer's fill once a
dispatch. The program brackets the patches in the ``heat3d.shell``
named scope, but the v5e trace gives an op no scope: an ``XLA Ops``
event carries its HLO instruction's text (``%fusion.322 = ...``) without
metadata, and its stats only its offset and duration. So the ops are
found by what they are not, and the reader reads the same ops in a
program that has no such scope.
"""

import harness
import tracing

LOOP_PROGRAMS = ("jit_runk(", "jit_run(")
COPY = "%copy"
COLLECTIVES = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all",
)


def small_ops(ops):
    kernels = set(map(id, harness.metric("stencil_roofline").kernels(ops)))
    return [o for o in ops
            if id(o) not in kernels and not o.short.startswith(COPY)
            and not any(c in o.short for c in COLLECTIVES)]


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    window = t.window[1] - t.window[0]
    shares = []
    for plane, ops in t.devices.items():
        runs = [m for m in t.modules.get(plane, [])
                if m.name.startswith(LOOP_PROGRAMS)]
        own = tracing.inside(tracing.in_window(t, ops), tracing.spans(runs))
        if not own:
            continue
        small = tracing.clip(tracing.spans(small_ops(own)), t.window)
        shares.append(100.0 * tracing.length(small) / window)
    return max(shares) if shares else None
