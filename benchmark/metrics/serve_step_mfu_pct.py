"""The whole serving step's share of the chip's peak: model FLOPs of the
prompt and output tokens computed in the traced window over (summed wall time
of the ``step()`` calls that did work x peak FLOP/s)."""
import work


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell.config
    n = ctx.cell.arch.matmul_params(cfg)
    flops = wall = 0.0
    for s in ctx.spans("engine.step", traced_only=True):
        admitted = s.attrs.get("admitted", ())
        tokens = s.attrs.get("decoded", 0) + sum(
            p - c for p, c in admitted)
        if not tokens:
            continue
        keys = s.attrs.get("decode_ctx", 0) + sum(
            (p * (p + 1) - c * (c + 1)) / 2 for p, c in admitted)
        flops += work.forward_flops(n, cfg["num_hidden_layers"],
                                    cfg["hidden_size"], tokens, keys)
        wall += s.seconds
    if wall <= 0:
        return None
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
