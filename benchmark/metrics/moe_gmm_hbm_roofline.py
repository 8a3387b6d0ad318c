"""The grouped-matmul (expert) kernel's share of its roofline in the block
and commit passes of the traced window: the least time of the bytes those
passes' grouped products must move (the weights of the experts that the
routing touched, ``archs/<arch>.py::expert_bytes``, and each routed row in
and out of both products) at the peak HBM bandwidth, over the kernel's
device time. Its events are found by ``pattern``, whose ``$rows`` is a
pass's routed rows (num_slots x block_length x num_experts_per_tok): a
prefill's products have more rows and are left out of both sides."""
import string

import harness


def read(ctx, pattern):
    if ctx.trace is None or ctx.peaks is None:
        return None
    spans = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                                "block_spans.py")
    rows = spans.passes(ctx)
    weigh = getattr(ctx.cell.arch, "expert_bytes", None)
    if rows is None or weigh is None:
        return None
    cfg = ctx.cell.config
    routed = (cfg["engine"]["num_slots"] * cfg["block_length"]
              * cfg["num_experts_per_tok"])
    seconds = ctx.trace.kernel_seconds(string.Template(
        ctx.pattern(pattern)).safe_substitute(rows=routed))
    if seconds <= 0:
        return None
    item = {"bfloat16": 2, "float32": 4}[cfg["dtype"]]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nbytes = 0.0
    for r in rows:
        # a commit pass's last layer runs no experts
        ran = cfg["num_hidden_layers"] - (r.name == "eng_block_commit")
        # gate-up: h in, 2F out; down: F in, h out; a row a routed copy
        nbytes += weigh(cfg, r.attrs["experts_touched"]) + (
            ran * routed * (h + 2 * f + f + h) * item)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
