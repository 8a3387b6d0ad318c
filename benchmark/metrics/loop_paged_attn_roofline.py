"""The paged-attention (decode) kernel's share of its roofline in the traced
window, for a model that runs its layers more than once:
``paged_attn_roofline.py`` with the live context read in all ``cache_layers``
(loops x layers) entries, one kernel call each, and the kv heads and head
size taken from the configuration's own keys. Least time: for every
``engine.step`` span of the traced window the larger of FLOPs over the peak
and bytes over the bandwidth, summed; over the kernel's device time, found
by ``pattern`` (the decode call's result has one row a slot)."""
import harness
import work


def read(ctx, pattern):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds = ctx.trace.kernel_seconds(ctx.pattern(pattern))
    if seconds <= 0:
        return None
    loop = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                               "loop_depth.py")
    depth = loop.cache_layers(ctx)
    if depth is None:
        return None
    cfg = ctx.cell.config
    d = cfg["head_dim"]
    least = 0.0
    for s in ctx.spans("engine.step", traced_only=True):
        live = s.attrs.get("decode_ctx", 0)
        if live:
            least += work.least_seconds(
                work.paged_decode_flops(live, depth,
                                        cfg["num_attention_heads"], d),
                work.paged_decode_bytes(live, depth,
                                        cfg["num_key_value_heads"], d,
                                        loop.kv_itemsize(ctx)),
                ctx.peaks)
    return 100.0 * least / seconds if least else None
