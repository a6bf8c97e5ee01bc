"""Time the process spent in Python's cyclic collector, on any thread,
from the start of the window to the engine's last batch in it, in ms
(``ServeMetrics`` ``gc_pause_ms``, from the ``gc.callbacks`` hook that
also writes the ``host.gc`` spans)."""


def read(ctx):
    return ctx.serve.get("gc_pause_ms")
