"""Share of the traced window in which no operation ran on the cell's
chips (1 - busy union / window), in %."""


def read(ctx):
    if ctx.events is None or not ctx.events.ops:
        return None
    busy = ctx.events.busy_seconds(ctx.cell["chips"])
    return 100.0 * (1.0 - busy / ctx.window_s)
