"""Flat stage 1: the ADC scan of every code for every query, and its
top-L (``kernels/topl_scan.py`` in the program)."""

#: the kernel's operations in a device trace (the Pallas call of the
#: program's ``adc_scan_topl_pallas``)
OP = r"^%adc_scan_topl_pallas\b"


def work(*, q: int, n: int, m: int, k: int, topl: int):
    """(ops, bytes) of one call over ``q`` queries and ``n`` codes of
    ``m`` bytes: one add per table entry gathered; the codes read once,
    the (q, m, k) float32 tables read once, the (q, topl) scores and ids
    written once."""
    ops = q * n * m
    nbytes = n * m + q * m * k * 4 + q * topl * 8
    return ops, nbytes


def window(ctx):
    """The window's work: ``batches`` calls, each reading every code once,
    for the queries served (pad rows of a batch are not work)."""
    cfg = ctx.config
    calls, real = ctx.serve.get("batches", 0), ctx.serve.get("real_queries", 0)
    if not calls:
        return None
    shape = dict(n=cfg["n_per_chip"] * cfg["shards"], m=cfg["num_codebooks"],
                 k=cfg["codebook_size"], topl=cfg["rerank"])
    ops, nbytes = work(q=real, **shape)
    return ops, nbytes + (calls - 1) * work(q=0, **shape)[1]
