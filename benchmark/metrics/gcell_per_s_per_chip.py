"""Every cell update the window completed (steps x global cells), over
the window's seconds (first dispatch to the return of the last block, on
the host's clock) and over the chips, in 1e9 per second per chip. Not a
best-of and not a median of chunks."""


def read(ctx):
    w = ctx.window
    return w.steps * ctx.global_cells / w.seconds / ctx.chips / 1e9
