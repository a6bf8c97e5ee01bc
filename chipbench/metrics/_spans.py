"""The program's host spans (``jax.profiler.TraceAnnotation``) in a
traced window: the durations of the spans of one name. A span's name is
the part of a host event's name before any ``#`` of TraceMe metadata
(the profiler's converter already keeps arguments such as ``batch=<seq>``
apart, as event stats)."""


def durations_ns(ctx, name: str) -> list[int]:
    """Durations of the window's spans named ``name``, in ns; empty when
    the run was not traced or the program has no such span."""
    if ctx.events is None:
        return []
    return [dur for _, event, _, dur in ctx.events.host
            if event.split("#", 1)[0] == name]
