"""Seconds from the first line of ``run.py`` to the window's first
dispatch: imports, backend start, compilation or cache load, the field
made on the device, and the warm-up dispatch."""


def read(ctx):
    return ctx.setup_s
