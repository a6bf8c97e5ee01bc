"""Backend compiles inside the measured window (jax.monitoring); warming
and priming should leave none."""


def read(ctx):
    return ctx.window_compiles
