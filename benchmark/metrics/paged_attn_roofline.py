"""The paged-attention (decode) kernel's share of its roofline in the traced
window.

Least time: for every ``engine.step`` span of the traced window, the decode
call reads the live context (the tokens each slot really holds, every layer,
K and V: not the grid the kernel walks); the larger of FLOPs over the peak
and bytes over the bandwidth, summed. Over the kernel's device time in the
trace. ``step()`` reads its tokens back before it returns and the trace
starts and stops between steps, so the window holds whole steps only. The
kernel is found by ``pattern`` (this file's, since no ``pallas_call`` of the
program carries a name yet): the decode kernel's result has one row a slot,
the prefill's flash kernel one row in all, and that one is left out of both
sides."""
import work


def read(ctx, pattern):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds = ctx.trace.kernel_seconds(ctx.pattern(pattern))
    if seconds <= 0:
        return None
    cfg = ctx.cell.config
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    item = {"bf16": 2, "f32": 4, "int8": 1}[cfg["engine"]["kv_dtype"]]
    least = 0.0
    for s in ctx.spans("engine.step", traced_only=True):
        live = s.attrs.get("decode_ctx", 0)
        if live:
            least += work.least_seconds(
                work.paged_decode_flops(live, layers, heads, d),
                work.paged_decode_bytes(live, layers, heads, d, item),
                ctx.peaks)
    return 100.0 * least / seconds if least else None
