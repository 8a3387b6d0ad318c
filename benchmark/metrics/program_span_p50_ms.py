"""Median over the traced steps, in ms, of the program's spans of the given
names, their durations summed a step: spans that share a parent are one
step's, a span without a parent is a step of its own."""
import statistics

import harness


def read(ctx, spans):
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, set(spans))
    if not rows:
        return None
    steps = {}
    for r in rows:
        key = r.parent_id or r.span_id
        steps[key] = steps.get(key, 0.0) + (r.t1 - r.t0)
    return statistics.median(steps.values()) * 1e3
