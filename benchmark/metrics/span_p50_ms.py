"""Median duration, in ms, of the benchmark's spans of one name inside the
window, optionally only those whose attribute passes a test."""
import statistics


def read(ctx, span, where=None):
    rows = ctx.spans(span)
    if where == "admitted":
        rows = [s for s in rows if s.attrs.get("admitted")]
    elif where == "not_admitted":
        rows = [s for s in rows if not s.attrs.get("admitted")
                and s.attrs.get("decoded")]
    if not rows:
        return None
    return statistics.median(s.seconds for s in rows) * 1e3
