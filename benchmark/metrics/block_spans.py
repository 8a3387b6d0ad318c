"""What the ``block_*`` and ``moe_*`` readers share: the block-diffusion
engine's pass spans of the traced window (``eng_block_pass``,
``eng_block_commit``: ``paddle_tpu/inference/engine.py``), each with the
experts its routing touched and the KV pages its attention read. None where
the program records no such span (an older checkout, another model): their
readers then report nothing."""
import harness

PASSES = ("eng_block_pass", "eng_block_commit")


def passes(ctx, names=PASSES):
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, set(names))
    rows = [r for r in rows or () if "experts_touched" in r.attrs]
    return rows or None


def kv_itemsize(ctx):
    return {"bf16": 2, "f32": 4, "int8": 1}[
        ctx.cell.config["engine"]["kv_dtype"]]


def page_bytes(ctx):
    """Bytes of one page slot of every layer's pool, K and V."""
    cfg = ctx.cell.config
    return (cfg["engine"]["page_size"] * cfg["num_hidden_layers"] * 2
            * cfg["num_key_value_heads"] * cfg["head_dim"] * kv_itemsize(ctx))
