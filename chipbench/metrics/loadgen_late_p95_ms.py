"""95th percentile of how late the load generator sent a request after
its due time, in ms: a starved generator is not a fast server."""

import numpy as np


def read(ctx):
    late = ctx.lateness_ms()
    return float(np.percentile(late, 95)) if late else None
