"""What the three ``program_*`` readers share: the PROGRAM's own spans
(``paddle_tpu.observability.tracing``) of the traced window. The program
records them while the profiler is on, on the host clock of the tracer's
``t_start`` / ``t_stop``; a program without that buffer (an older
checkout) gives None, and its readers report nothing."""


def recorded(ctx, names):
    """The program's spans of ``names`` that lie inside the traced window,
    oldest first; None where there is no traced window or the program keeps
    no spans."""
    tracer = ctx.outcome.tracer
    if tracer is None or tracer.t_start is None or tracer.t_stop is None:
        return None
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return None
    read = getattr(tracing, "recorded", None)
    if read is None:
        return None
    return [r for r in read(tracer.t_start, tracer.t_stop)
            if r.name in names]

