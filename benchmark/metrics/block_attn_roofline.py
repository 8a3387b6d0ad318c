"""The paged-attention kernel's share of its roofline in the block and
commit passes of a block-diffusion model (``block_length`` rows a slot
under the block horizon). Least time: for every such pass of the traced
window, the larger of FLOPs over the peak and bytes over the bandwidth,
summed. Bytes are the whole pages the pass's attention read (the spans'
``kv_pages``, every layer, K and V: the kernel copies whole pages); FLOPs
are ``block_length`` rows of every slot against every key of those pages,
QK^T and PV. Over the kernel's device time, found by ``pattern`` (the
result's first axis is the slot: the prefill's blocked kernel leads with
1)."""
import harness
import work


def read(ctx, pattern):
    if ctx.trace is None or ctx.peaks is None:
        return None
    spans = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                                "block_spans.py")
    rows = spans.passes(ctx)
    if rows is None:
        return None
    seconds = ctx.trace.kernel_seconds(ctx.pattern(pattern))
    if seconds <= 0:
        return None
    cfg = ctx.cell.config
    p = cfg["engine"]["page_size"]
    least = 0.0
    for r in rows:
        pages = r.attrs.get("kv_pages", 0)
        flops = work.paged_decode_flops(
            pages * p * cfg["block_length"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["head_dim"])
        least += work.least_seconds(flops, pages * spans.page_bytes(ctx),
                                    ctx.peaks)
    return 100.0 * least / seconds if least else None
