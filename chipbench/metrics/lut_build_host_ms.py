"""Host time of the eager LUT build a batch, in ms: the window's
``index.lut_build`` spans (``Index.search`` around ``_build_luts``) in
all, over the engine's batches (``ServeMetrics`` ``batches``)."""
from chipbench.metrics._spans import durations_ns


def read(ctx):
    spans = durations_ns(ctx, "index.lut_build")
    batches = ctx.serve.get("batches", 0)
    return sum(spans) / 1e6 / batches if spans and batches else None
