"""Share of the traced window in which no operation ran on a device:
1 - (union of the device's ``XLA Ops`` intervals) / window, in %, on the
worst device. Overlapping ops count once."""

import tracing


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    window = t.window[1] - t.window[0]
    return max(
        100.0 * (1.0 - tracing.length(tracing.clip(tracing.spans(ops),
                                                   t.window)) / window)
        for ops in t.devices.values()
    )
