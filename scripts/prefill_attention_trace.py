#!/usr/bin/env python3
"""The tail prefill's attention INSIDE the engine's prefill programs, timed
from a device trace.

A kernel compiled alone proves nothing about VMEM or time (PERF.md, PRs 27
and 30), so this drives the serving engine's own ``prefill_b<bucket>``
programs at the serving cell's widths (GPT-3 1.3B: 16 heads of 128, 8 slots
x 2048, page 16, a bf16 pool) and reads, for each (bucket, tokens cached)
case, the attention kernel's events and the whole program's busy time on
the device's ``XLA Ops`` line, never the host's clock. One process on one
TPU; each variant is an engine of its own over the same model:

  * ``blocked``: the tree as it is (``KVPool.attend_block``: one slot's
    pages gathered, ``prefill_attention`` over them);
  * ``blocked:<rows>x<keys>``: the same with the kernel's row and key
    blocks pinned, for a sweep (the program itself takes them from the
    call's shapes: ``prefill_attention._block_sizes``);
  * ``paged``: the prefill through the paged kernel at ``T = bucket``, what
    every prefill ran until PR 34.

Each variant's last-token logits are compared with the first variant's.

    python3 scripts/prefill_attention_trace.py \
        [--variants paged,blocked,blocked:256x256] [--layers 24] \
        [--out chiprun_out/prefill_attention_trace.json]

Nothing here is a benchmark cell; PERF.md quotes it.
"""
import argparse
import gc
import json
import os
import re
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: (bucket, tokens cached before the tail)
CASES = [(128, 0), (128, 128), (512, 0), (512, 128), (1024, 0), (1024, 128),
         (1024, 1024)]


def build_model(layers, tiny):
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    wide = dict(vocab_size=50304, hidden_size=2048, num_attention_heads=16,
                intermediate_size=8192, max_position_embeddings=2048)
    small = dict(vocab_size=64, hidden_size=64, num_attention_heads=4,
                 intermediate_size=64, max_position_embeddings=2048)
    model = GPTForCausalLM(GPTConfig(
        num_hidden_layers=layers, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, **(small if tiny else wide),
    )).astype("bfloat16")
    model.eval()
    return model


def set_variant(variant, saved):
    """Point the tree at ``variant``; ``saved`` holds what the tree has."""
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_pool import KVPool
    from paddle_tpu.ops.pallas import prefill_attention as pf

    KVPool.attend_block, pf._block_sizes = saved
    kind, _, blocks = variant.partition(":")
    if kind == "paged":
        KVPool.attend_block = (
            lambda self, q, layer, row, cached_len, kernel, block=1:
            self.attend(q, layer, row[None], jnp.reshape(cached_len, (1,)),
                        kernel, block))
    elif blocks:
        bq, bk = (int(n) for n in blocks.split("x"))
        pf._block_sizes = lambda rows, keys: (
            min(bq, pf._round_up(rows, 16)), min(bk, pf._round_up(keys, 128)))
    elif kind != "blocked":
        sys.exit(f"unknown variant {variant!r}")


def prefill_args(eng, bucket, cached):
    """The program's arguments for a full tail of ``bucket`` tokens behind
    ``cached`` cached ones, in pages 1, 2, ... of the pool."""
    import numpy as np

    name = f"prefill_b{bucket}"
    inputs = list(eng._example_inputs(name))
    page = eng.config.page_size
    row = np.zeros_like(inputs[3])
    used = -(-(cached + bucket) // page)
    row[:used] = 1 + np.arange(used)
    ids = np.random.default_rng(bucket + cached).integers(
        1, 60, (1, bucket)).astype(np.int32)
    inputs[:4] = [ids, np.int32(cached), np.int32(cached + bucket), row]
    return eng._state_vals(), eng.kv, eng._pack(name, inputs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="paged,blocked")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "prefill_attention_trace.json"))
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="a tiny model in interpret mode: logits compared, "
                         "nothing timed")
    args = ap.parse_args()
    import jax
    import numpy as np

    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.inference.kv_pool import KVPool
    from paddle_tpu.ops.pallas import prefill_attention as pf

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import trace_reduce as tr

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit("prefill_attention_trace.py measures a TPU")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    model = build_model(args.layers, args.rehearse_on_cpu)
    saved = (KVPool.attend_block, pf._block_sizes)
    rows, first = [], {}
    for variant in args.variants.split(","):
        set_variant(variant, saved)
        eng = DecodeEngine(model, EngineConfig(
            num_slots=8, max_length=2048, page_size=16, kv_dtype="bf16",
            prompt_buckets=(128, 512, 1024), attn_kernel="pallas"))
        for bucket, cached in CASES:
            name = f"prefill_b{bucket}"
            _, logits = eng._run(name, *prefill_args(eng, bucket, cached))
            logits = np.asarray(logits, np.float32)  # compiles, warms
            ref = first.setdefault((bucket, cached), logits)
            row = {"variant": variant, "bucket": bucket, "cached": cached,
                   "layers": args.layers,
                   "logits_maxdiff_vs_first": float(
                       np.abs(logits - ref).max()),
                   "argmax_equal": bool(logits.argmax() == ref.argmax())}
            if not args.rehearse_on_cpu:
                with tempfile.TemporaryDirectory(
                        dir=os.path.dirname(args.out)) as d:
                    with jax.profiler.trace(d):
                        for _ in range(args.calls):
                            out = eng._run(
                                name, *prefill_args(eng, bucket, cached))
                            jax.block_until_ready(out)
                    red = tr.reduce_trace(tr.find_xplane(d))
                ms = [1e3 * sec for _, _, sec, text in red.events if re.search(
                    r"(prefill|paged)_attention[.\d]* = ", text)]
                if len(ms) != args.calls * args.layers:
                    sys.exit(f"{variant} {name}: {len(ms)} kernel events "
                             f"for {args.calls} x {args.layers} calls")
                row["kernel_ms_p50"] = statistics.median(ms)
                row["kernel_ms_max"] = max(ms)
                row["program_busy_ms"] = 1e3 * red.busy_s / args.calls
                row["top_ops_ms_a_call"] = {
                    k: round(1e3 * v / args.calls, 4)
                    for k, v in red.top_ops(8)}
            rows.append(row)
            print(json.dumps(row), flush=True)
        del eng
        gc.collect()
    set_variant("blocked", saved)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind}, "rows": rows}, f,
                  indent=1)
    print(json.dumps({"ok": True, "device": dev.device_kind,
                      "rows": len(rows)}))


if __name__ == "__main__":
    main()
