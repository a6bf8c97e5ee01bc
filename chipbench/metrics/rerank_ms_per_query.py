"""Device time of the stage-2 rerank programs (the configuration's
``rerank_programs`` pattern of program names) over the queries served in
the traced window, in ms a query."""


def read(ctx):
    if ctx.events is None:
        return None
    sec = ctx.events.module_seconds(ctx.config["rerank_programs"],
                                    ctx.cell["chips"])
    served = ctx.served_queries()
    return None if sec is None or not served else 1e3 * sec / served
