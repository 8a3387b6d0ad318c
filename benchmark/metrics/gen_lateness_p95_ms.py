"""How late the load generator ran: 95th percentile over the window's
requests of the time of ``submit`` less the time the request was due."""


from serve import percentile


def read(ctx):
    return percentile([(t.submitted - t.due) * 1e3
                       for t in ctx.outcome.extra.get("tracks", ())
                       if t.in_window and t.submitted is not None], 95)
