"""A kernel's share of the device's busy time in the traced window."""


def read(ctx, pattern):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    seconds = ctx.trace.kernel_seconds(ctx.pattern(pattern))
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
