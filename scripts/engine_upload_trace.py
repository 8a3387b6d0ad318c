#!/usr/bin/env python3
"""How a decode pass's host inputs reach the device, timed three ways.

On the ``gpt3_1p3b_serve`` engine (benchmark/configs, 8 slots x 2048, page
16: a page table of 8 x 128 int32), with every slot decoding, each way runs
``--calls`` times and its median host time, from ``time.perf_counter``, is
reported:

- ``a_eight_asarray_ms``: the pass's eight host arrays (tokens, positions,
  tables, keys, temperature, top-k, top-p, greedy), one ``jnp.asarray``
  each: what a pass paid before its inputs were packed;
- ``b_one_asarray_ms``: the same bytes packed into one int32 array
  (``DecodeEngine._pack``, timed apart as ``b_pack_ms``), one
  ``jnp.asarray``;
- ``c_eight_numpy_jit_ms`` / ``c_one_numpy_jit_ms``: the eight arrays, or
  the one packed array, handed to a jitted call as numpy, which moves them
  itself, less the same call on arrays already on the device
  (``*_device_jit_ms``); the call's work is a few scalar adds.

Then the engine's own decode pass, packed: ``pass_upload_ms``,
``pass_dispatch_ms``, ``pass_readback_ms`` (medians), and
``pass_numpy_dispatch_ms``, the dispatch of the same program handed the
packed numpy array straight. The cost of a transfer is per array where
``b`` is far under ``a``.

    python3 scripts/engine_upload_trace.py [--calls 200] \\
        [--out chiprun_out/engine_upload_trace.json]

``--rehearse-on-cpu`` runs the same steps on a tiny GPT (no number of it is
a device's).
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_engine(rehearse):
    """The cell's engine with the seed's weights, or a tiny one."""
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "benchmark"))
    if not rehearse:
        import harness
        import serve

        return serve.build_engine(
            harness.resolve("serve_short_1p3b_knee80"), 1)[2]
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=2048, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    return DecodeEngine(model, EngineConfig(
        num_slots=8, max_length=2048, page_size=16, kv_dtype="bf16",
        prompt_buckets=(128,)))


def median_ms(fn, calls):
    """Median host time of ``fn()`` in ms, over ``calls`` calls after two
    unmeasured ones."""
    for _ in range(2):
        fn()
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        fn()
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "engine_upload_trace.json"))
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit("engine_upload_trace.py measures a TPU")
    eng = build_engine(args.rehearse_on_cpu)
    eng.warmup()
    rng = np.random.default_rng(0)
    for i in range(eng.config.num_slots):
        eng.submit(rng.integers(1, 100, 100).astype(np.int32),
                   max_new_tokens=1000, do_sample=bool(i % 2),
                   temperature=0.8, top_p=0.95, seed=i)
    while eng._waiting:
        eng.step()
    _, host = eng._decode_inputs(eng._epoch)
    packed = eng._pack("decode", host)
    sync = jax.block_until_ready
    row = {"device": str(jax.devices()[0].device_kind),
           "calls": args.calls, "arrays": len(host),
           "bytes": int(packed.nbytes)}
    row["a_eight_asarray_ms"] = median_ms(
        lambda: [jnp.asarray(a) for a in host], args.calls)
    row["b_pack_ms"] = median_ms(
        lambda: eng._pack("decode", host), args.calls)
    row["b_one_asarray_ms"] = median_ms(
        lambda: jnp.asarray(packed), args.calls)

    @jax.jit
    def few_adds(*xs):
        return sum(jnp.asarray(x).reshape(-1)[0].astype(jnp.int32)
                   for x in xs)

    on_dev = [jnp.asarray(a) for a in host]
    packed_dev = jnp.asarray(packed)
    for name, xs in (("eight_numpy", host), ("eight_device", on_dev),
                     ("one_numpy", (packed,)), ("one_device", (packed_dev,))):
        row[f"c_{name}_jit_ms"] = median_ms(
            lambda xs=xs: sync(few_adds(*xs)), args.calls)
    for n in ("eight", "one"):
        row[f"c_{n}_numpy_jit_ms"] = row.pop(f"c_{n}_numpy_jit_ms") - row[
            f"c_{n}_device_jit_ms"]

    # the engine's packed decode pass, its three parts timed apart; the
    # same slots and positions every call, so the pool's pages see the
    # same writes again
    parts = {"upload": [], "dispatch": [], "readback": [],
             "numpy_dispatch": []}
    state = eng._state_vals()
    for i in range(args.calls + 2):
        t0 = time.perf_counter()
        dev = eng._upload(packed)
        t1 = time.perf_counter()
        nxt, _ = eng._run("decode", state, eng.kv, dev)
        t2 = time.perf_counter()
        np.asarray(nxt)
        t3 = time.perf_counter()
        nxt, _ = eng._run("decode", state, eng.kv, packed)
        t4 = time.perf_counter()
        np.asarray(nxt)
        if i >= 2:
            for k, v in (("upload", t1 - t0), ("dispatch", t2 - t1),
                         ("readback", t3 - t2), ("numpy_dispatch", t4 - t3)):
                parts[k].append(1e3 * v)
    for k, v in parts.items():
        row[f"pass_{k}_ms"] = statistics.median(v)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(row, f, indent=1)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
