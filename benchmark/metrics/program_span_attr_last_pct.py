"""One attribute over another, in percent, of the LAST of the program's
spans of one name in the traced window: running totals that the program
keeps since it was built, read where the window ends (``eng_step``'s
``slot_steps`` over ``slot_capacity``: how full the decode batch has been
over the whole run, lead-in and window). A mean over the traced steps
alone swung two-fold by seed."""
import harness


def read(ctx, span, num, den):
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, {span})
    rows = [r for r in rows or () if num in r.attrs and r.attrs.get(den)]
    if not rows:
        return None
    return 100.0 * rows[-1].attrs[num] / rows[-1].attrs[den]
