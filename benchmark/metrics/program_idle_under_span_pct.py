"""The share of the traced window in which the device ran nothing WHILE the
program was inside one of the named spans (``spans``) and outside every one
of ``but``: the spans' intervals less those of ``but`` and less the device's
busy intervals (the union of its operations'), by overlap and not by a gap's
middle. ``spans: [eng_step], but: [the read-backs]`` is the idle time that
the engine's own host work accounts for."""
import harness
from trace_reduce import _union


def _minus(a, b):
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def read(ctx, spans, but=()):
    red = ctx.trace
    if red is None or red.window_s <= 0 or not red.events:
        return None
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, set(spans) | set(but))
    if not rows or not any(r.name in spans for r in rows):
        return None
    w = red.window_s

    def clipped(intervals):
        """Merged, inside the window, the empty ones dropped."""
        return _union((max(s, 0.0), min(e, w)) for s, e in intervals
                      if min(e, w) > max(s, 0.0))

    def of(names):
        return [(ctx.seconds_into_trace(r.t0), ctx.seconds_into_trace(r.t1))
                for r in rows if r.name in names]

    busy = [(s, s + d) for _, s, d, _ in red.events]
    idle_inside = _minus(clipped(of(spans)), clipped(of(but) + busy))
    return 100.0 * sum(e - s for s, e in idle_inside) / w
