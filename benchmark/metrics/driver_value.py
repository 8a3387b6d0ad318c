"""A number the driver worked out over the whole window (a tail that is too
unsteady to be held to a bound, reported beside the bounded metrics)."""


def read(ctx, key):
    return ctx.outcome.end_to_end.get(key)
