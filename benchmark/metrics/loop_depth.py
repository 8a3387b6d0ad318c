"""What the three ``loop_*`` readers share: the cache's depth of a model
that runs its layers more than once, ``loops x layers`` entries, counted by
the architecture's file (``archs/<arch>.py::cache_layers``) and checked
against what the PROGRAM says it holds (``eng_step``'s attr ``cache_layers``
in the traced window). None where either is missing or they disagree: an
architecture without the count, a program that writes no such attr (an older
checkout), or a pool of another depth than the yardstick counts work for."""
import harness


def cache_layers(ctx):
    count = getattr(ctx.cell.arch, "cache_layers", None)
    if count is None:
        return None
    rows = harness.load_module(
        ctx.cell.root, ctx.cell.paths, "metrics",
        "program_spans.py").recorded(ctx, {"eng_step"})
    said = [r.attrs["cache_layers"] for r in rows or ()
            if "cache_layers" in r.attrs]
    want = count(ctx.cell.config)
    return want if said and said[-1] == want else None


def kv_itemsize(ctx):
    return {"bf16": 2, "f32": 4, "int8": 1}[
        ctx.cell.config["engine"]["kv_dtype"]]
