"""The whole training step's share of the chip's peak: model FLOPs a token
(6N + 12 L h T, the attention term halved where the architecture is causal,
recompute not credited) x tokens a second of the traced window, over the
peak."""
import work


def read(ctx):
    if ctx.peaks is None:
        return None
    steps = ctx.spans("train.step", traced_only=True)
    if not steps:
        return None
    cfg, mix, arch = ctx.cell.config, ctx.cell.mix, ctx.cell.arch
    per_token = work.train_flops_per_token(
        arch.matmul_params(cfg), cfg["num_hidden_layers"], cfg["hidden_size"],
        mix["seq"], arch.CAUSAL)
    tokens = len(steps) * mix["batch"] * mix["seq"]
    seconds = sum(s.seconds for s in steps)
    return 100.0 * per_token * tokens / seconds / ctx.peaks["bf16_flops_per_s"]
