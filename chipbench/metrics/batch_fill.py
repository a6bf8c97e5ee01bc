"""Share of the query rows of the window's batches that carried a real
query (``ServeMetrics`` real / (real + padded)), in %: the rest is
bucket padding the device scans for nothing."""


def read(ctx):
    real = ctx.serve.get("real_queries", 0)
    total = real + ctx.serve.get("padded_queries", 0)
    return 100.0 * real / total if total else None
