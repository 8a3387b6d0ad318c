"""One of the driver's counters over another, in percent: counts that the
program keeps itself and the driver hands back for the whole run (lead-in
and window), such as the engine's ``prefix_hit_tokens`` over
``prompt_tokens_total``."""


def read(ctx, num, den):
    counters = ctx.outcome.counters
    if num not in counters or not counters.get(den):
        return None
    return 100.0 * counters[num] / counters[den]
