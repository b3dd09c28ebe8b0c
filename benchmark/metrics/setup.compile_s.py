"""Seconds of tracing, lowering and compiling or loading from the
persistent cache inside set-up, summed from ``jax.monitoring``'s
``/jax/core/compile/*`` duration events. Moves ``setup_s``."""


def read(ctx):
    return ctx.compile.seconds.get("setup", 0.0)
