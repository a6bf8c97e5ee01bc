"""Rows the dedup rerank decoded a served query: the engine's
``rerank_decoded_rows`` (``index.rerank.RERANK``: unique candidate rows
padded to the decode ladder, pad queries' candidates included) over the
queries served in the window."""


def read(ctx):
    rows = ctx.serve.get("rerank_decoded_rows")
    served = ctx.served_queries()
    return rows / served if rows is not None and served else None
