"""How far a block pass of a block-diffusion model is from its memory
floor: over the ``eng_block_pass`` spans of the traced window, the bytes a
pass must read from HBM (the weights it streams,
``archs/<arch>.py::pass_weight_bytes`` of the experts its routing touched,
and the pages its attention read, K and V in every layer) over the peak
bandwidth, over the passes' wall seconds. The spans hold the host's upload
and read-back too, so the share is of the pass as the engine runs it."""
import harness


def read(ctx):
    if ctx.peaks is None:
        return None
    spans = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                                "block_spans.py")
    rows = spans.passes(ctx, ("eng_block_pass",))
    weigh = getattr(ctx.cell.arch, "pass_weight_bytes", None)
    if rows is None or weigh is None:
        return None
    cfg = ctx.cell.config
    nbytes = sum(weigh(cfg, r.attrs["experts_touched"])
                 + r.attrs.get("kv_pages", 0) * spans.page_bytes(ctx)
                 for r in rows)
    wall = sum(r.t1 - r.t0 for r in rows)
    if wall <= 0:
        return None
    return 100.0 * nbytes / (ctx.peaks["hbm_bytes_per_s"] * wall)
