#!/usr/bin/env python3
"""The memory gate of a training cell: after a few steps, every key of the
device's ``memory_stats()``, beside it the live array bytes and the compiled
step's ``memory_analysis()``; then hold a 4 GiB array and run a step. If
that step fails for memory although ``peak_bytes_in_use`` + 4 GiB is far
under the chip's, the allocator's peak misses the program's temporaries.

    python3 benchmark/tools/memory_probe.py --workload <cell> [--registry R]
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--registry", default="BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--hold-gib", type=float, default=4.0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import amp

    import traffic
    import train

    print("cache", harness.configure_cache(), flush=True)
    cell = harness.resolve(args.workload, registry=args.registry)
    prog = train.Program(cell, args.seed)
    feed = traffic.TrainBatches(cell.mix, args.seed,
                                cell.config["vocab_size"])
    for _ in range(3):
        batch = feed.next()
        loss = float(prog(batch))
    dev = jax.devices()[0]
    print("after 3 steps, loss", loss, "memory_stats", dev.memory_stats(),
          flush=True)
    print("live array bytes", sum(a.nbytes for a in jax.live_arrays()),
          flush=True)
    tensors = [paddle.to_tensor(b) for b in batch]
    if prog.amp:
        with amp.auto_cast(enable=True, dtype="bfloat16", level=prog.amp):
            print("memory_analysis", prog.step.memory_analysis(*tensors))
            text = prog.step._compiled_for(*tensors).as_text()
    else:
        print("memory_analysis", prog.step.memory_analysis(*tensors))
        text = prog.step._compiled_for(*tensors).as_text()
    print("tpu_custom_call count in the compiled step",
          text.count("tpu_custom_call"), flush=True)
    n = int(args.hold_gib * 2**30) // 4
    hold = jnp.zeros((n,), jnp.float32)
    hold.block_until_ready()
    print(f"holding {hold.nbytes} bytes; memory_stats", dev.memory_stats(),
          flush=True)
    try:
        loss = float(prog(feed.next()))
        print("step with the array held: ok, loss", loss)
    except Exception as e:  # noqa: BLE001 — the probe reports what failed
        print("step with the array held: FAILED:", type(e).__name__,
              str(e)[:600])
    print("memory_stats", dev.memory_stats(), flush=True)


if __name__ == "__main__":
    main()
