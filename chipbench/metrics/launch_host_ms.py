"""Median host time of one batch launch, in ms: the duration of the
engine's ``serve.execute`` spans (``ServeEngine._execute``: the LUT
build, the stage-1 launch and stage 2 of ``Index.search``), over the
window's batches. A few ms is dispatch; a reading near a batch's stage-1
device time means the launch waited for stage 1 (the dedup rerank's
``np.unique`` on its candidates)."""
import numpy as np

from chipbench.metrics._spans import durations_ns


def read(ctx):
    spans = durations_ns(ctx, "serve.execute")
    return float(np.median(spans)) / 1e6 if spans else None
