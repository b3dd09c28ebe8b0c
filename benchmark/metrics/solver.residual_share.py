"""Share of device busy time spent in the residual step's program beyond
its stencil sweep, in %, on the worst device: the ops that run inside
executions of that program (its residual reduction, the ``psum``, the
copy of its output) less its stencil kernel events (those of
``stencil_roofline``), over the union of all leaf ops in the window.

The program is found by the name the trace prints for it on the ``XLA
Modules`` line: ``HeatSolver3D._step_res`` is ``jax.jit`` of the step
builders' ``wrapper`` (``parallel/step.py``), printed ``jit_wrapper(<id>)``.
The window's other programs are ``jit_runk`` (the multistep loop) and the
benchmark's own ``jit_copy``. A renamed program leaves this metric silent.
"""

import harness
import tracing

RESIDUAL_PROGRAM = "jit_wrapper("


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    kernels = harness.metric("stencil_roofline").kernels
    shares = []
    for plane, ops in t.devices.items():
        runs = [m for m in t.modules.get(plane, [])
                if m.name.startswith(RESIDUAL_PROGRAM)]
        ops = tracing.in_window(t, ops)
        own = tracing.inside(ops, tracing.spans(runs))
        if not own:
            continue
        extra = tracing.minus(tracing.clip(tracing.spans(own), t.window),
                              tracing.spans(kernels(own)))
        busy = tracing.length(tracing.clip(tracing.spans(ops), t.window))
        shares.append(100.0 * tracing.length(extra) / busy)
    return max(shares) if shares else None
