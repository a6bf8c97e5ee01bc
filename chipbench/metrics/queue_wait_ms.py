"""Median queue wait of the window's requests, in ms: submit to the
launch of the request's batch (``ServeMetrics`` ``queue_wait_p50_ms``,
from ``Request.t_launch - Request.t_submit``)."""
import math


def read(ctx):
    value = ctx.serve.get("queue_wait_p50_ms")
    return None if value is None or math.isnan(value) else value
