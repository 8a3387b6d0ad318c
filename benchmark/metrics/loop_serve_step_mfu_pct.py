"""The whole serving step's share of the chip's peak for a model that runs
its layers more than once: model FLOPs of the prompt and output tokens
computed in the traced window over (summed wall time of the ``step()`` calls
that did work x peak FLOP/s). ``serve_step_mfu_pct.py`` with the two depths
apart: the architecture's ``matmul_params`` counts every loop's products,
and the attention term runs over its ``cache_layers`` (loops x layers), not
over the configuration's ``num_hidden_layers``."""
import harness
import work


def read(ctx):
    if ctx.peaks is None:
        return None
    depth = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                                "loop_depth.py").cache_layers(ctx)
    if depth is None:
        return None
    cfg = ctx.cell.config
    n = ctx.cell.arch.matmul_params(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    flops = wall = 0.0
    for s in ctx.spans("engine.step", traced_only=True):
        admitted = s.attrs.get("admitted", ())
        tokens = s.attrs.get("decoded", 0) + sum(p - c for p, c in admitted)
        if not tokens:
            continue
        keys = s.attrs.get("decode_ctx", 0) + sum(
            (p * (p + 1) - c * (c + 1)) / 2 for p, c in admitted)
        flops += work.forward_flops(n, depth, width, tokens, keys)
        wall += s.seconds
    if wall <= 0:
        return None
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
