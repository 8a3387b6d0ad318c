"""How far a plain decode step is from its memory floor, for a model that
runs its layers more than once. Over the ``engine.step`` spans of the traced
window that admitted nothing and decoded something: the bytes a step must
read from HBM (the weights of one pass, every loop's stream of the layers
counted: ``archs/<arch>.py::decode_pass_weight_bytes``; and the live context
in all ``cache_layers`` entries, K and V) over the peak bandwidth, over the
steps' wall seconds. A step is bound by that stream where MFU reads ~1%; the
steps' seconds hold the host's turn too, so the share is of the step, not of
the device's busy time."""
import harness
import work


def read(ctx):
    if ctx.peaks is None:
        return None
    loop = harness.load_module(ctx.cell.root, ctx.cell.paths, "metrics",
                               "loop_depth.py")
    depth = loop.cache_layers(ctx)
    weigh = getattr(ctx.cell.arch, "decode_pass_weight_bytes", None)
    if depth is None or weigh is None:
        return None
    cfg = ctx.cell.config
    weights = weigh(cfg)
    nbytes = wall = 0.0
    for s in ctx.spans("engine.step", traced_only=True):
        if s.attrs.get("admitted") or not s.attrs.get("decoded"):
            continue
        nbytes += weights + work.paged_decode_bytes(
            s.attrs.get("decode_ctx", 0), depth, cfg["num_key_value_heads"],
            cfg["head_dim"], loop.kv_itemsize(ctx))
        wall += s.seconds
    if wall <= 0:
        return None
    return 100.0 * nbytes / (ctx.peaks["hbm_bytes_per_s"] * wall)
