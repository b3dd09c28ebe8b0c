"""The stencil kernel's share of its roofline on the HBM bound alone, in %:
bytes over kernel time over the chip's HBM peak (``peaks.json``).

- Kernel events: ops whose HLO text is a ``tpu_custom_call`` (a Pallas
  kernel) named ``%heat3d.stencil.<n>``. XLA names the custom call after
  the innermost named scope, and the program calls the direct and direct2
  kernels of ``ops/stencil_pallas_direct.py`` under ``heat3d.stencil``;
  the kernels themselves carry no ``name=``.
- Bytes: each event sweeps the chip's whole local block once, reading it
  and writing it: 2 x itemsize x local cells per event. Counted per
  event, so a tb=2 superstep (two updates, one sweep) and a tb=1 step each
  count one sweep; in the sweep traffic the events number steps /
  time_blocking. Rows a kernel reads twice are not counted, so the bytes
  are a floor and the share cannot pass the true HBM share.
- Kernel time: the summed device durations of those events.
- The v5e's f32 vector peak is not published, so no compute bound is
  read: this is the HBM bound only, a lower bound of the roofline share.
  On four chips the faces-direct route's shell patches and face copies
  are other ops and are not counted.

Taken on each device; the lowest is reported.
"""

import tracing

KERNEL_NAME = "%heat3d.stencil."
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def kernels(ops):
    return [o for o in ops
            if o.name.startswith(KERNEL_NAME) and KERNEL_TARGET in o.name]


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    peak = float(ctx.peaks["hbm_bytes_per_s"])
    shares = []
    for ops in t.devices.values():
        ks = kernels(tracing.in_window(t, ops))
        seconds = sum(o.end - o.start for o in ks) * 1e-9
        if seconds > 0:
            moved = len(ks) * 2 * ctx.itemsize * ctx.local_cells
            shares.append(100.0 * moved / seconds / peak)
    return min(shares) if shares else None
