"""The flash-attention kernels' share of their roofline in the traced window:
the least time for the FLOPs and bytes that attention over the batch needs,
forward and backward in every layer of every traced step (the forward that
recompute runs again is NOT credited), over the kernels' device time. Both
sides hold whole steps only: the ``train.step`` spans that lie inside the
traced window, and the kernel events from the first of those spans on (the
step in flight when the trace starts is on neither side). The kernels are
found by ``pattern`` (this file's: no ``pallas_call`` of the program carries
a name yet)."""
import work


def read(ctx, pattern):
    if ctx.trace is None or ctx.peaks is None:
        return None
    steps = ctx.spans("train.step", traced_only=True)
    if not steps:
        return None
    seconds = ctx.trace.kernel_seconds(
        ctx.pattern(pattern), t_from=ctx.seconds_into_trace(steps[0].t0))
    if seconds <= 0:
        return None
    cfg, mix = ctx.cell.config, ctx.cell.mix
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    causal = ctx.cell.arch.CAUSAL
    b, t = mix["batch"], mix["seq"]
    least = 0.0
    for backward in (False, True):
        flops = work.attention_flops(b, heads, t, t, d, causal, backward)
        if backward:  # the count of 7 holds forward and backward together
            flops -= work.attention_flops(b, heads, t, t, d, causal, False)
        least += work.least_seconds(
            flops, work.attention_bytes(b, heads, t, t, d, 2, backward),
            ctx.peaks)
    return 100.0 * least * cfg["num_hidden_layers"] * len(steps) / seconds
