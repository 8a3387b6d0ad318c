"""The whole serving step's share of the chip's peak for a block-diffusion
model with routed experts: 2 x the parameters a row multiplies
(``archs/<arch>.py::active_matmul_params``: attention, router, the experts
chosen for it and the head) x the rows computed for live slots, over the
``eng_step`` spans' wall seconds x the peak bf16 FLOP/s. The rows come from
the engine's running total ``rows_computed`` on ``eng_step`` (block and
commit passes, prefills): a step's rows are its total less the previous
step's, so the traced window's first step is left out of both sides."""
import harness


def read(ctx):
    if ctx.peaks is None:
        return None
    count = getattr(ctx.cell.arch, "active_matmul_params", None)
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, {"eng_step"})
    rows = [r for r in rows or () if "rows_computed" in r.attrs]
    if count is None or len(rows) < 2:
        return None
    done = rows[-1].attrs["rows_computed"] - rows[0].attrs["rows_computed"]
    wall = sum(r.t1 - r.t0 for r in rows[1:])
    if wall <= 0 or done <= 0:
        return None
    return (100.0 * 2.0 * count(ctx.cell.config) * done
            / (wall * ctx.peaks["bf16_flops_per_s"]))
