#!/usr/bin/env python3
"""The serving programs' sampler alone, timed from a device trace.

For each case, a jitted call of ``inference/engine.py::_sample_tokens`` runs
``--calls`` times under ``jax.profiler.trace``. The time of a call is the
device's busy time (the union of its ``XLA Ops`` events) over the calls,
never the host's clock. Half the rows sample at temperature 0.8 and top-p
0.95 and half are greedy, as in the serving cells' mixes, with no top-k;
the ``_topk`` cases set top-k 50 on the sampled rows.

With ``--parent DIR`` (an unpacked ``git archive`` of another commit) the
same cases run on that tree's sampler too. Each tree runs in a child process
of its own, one after the other, since a process imports one ``paddle_tpu``
and holds the chip until it exits. The two trees' tokens are compared row
for row.

    python3 scripts/sample_trace.py [--parent _tree/parent] \\
        [--out chiprun_out/sample_trace.json]

The cases are the serving cells' shapes: the GPT-3 1.3B prefill and decode
step (vocab 50,304), the Ouro-2.6B decode step (49,152) and SDAR's block
pass (16 slots x 4 positions over 151,936). Nothing here is a benchmark
cell; PERF.md quotes it.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (rows, vocab, top-k of the sampled rows)
CASES = {
    "gpt_prefill": (1, 50304, 0),
    "gpt_decode": (8, 50304, 0),
    "ouro_decode": (8, 49152, 0),
    "sdar_block": (64, 151936, 0),
    "gpt_decode_topk": (8, 50304, 50),
    "sdar_block_topk": (64, 151936, 50),
}


def child(tree, cases, calls, out):
    """Times ``tree``'s sampler at ``cases``; writes rows to ``out``."""
    sys.path.insert(0, tree)
    sys.path.insert(1, os.path.join(ROOT, "benchmark"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce as tr
    from paddle_tpu.inference.engine import _sample_tokens

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("sample_trace.py measures a TPU")
    fn = jax.jit(_sample_tokens)
    rows = []
    for name in cases:
        n, v, k = CASES[name]
        rng = np.random.default_rng(n * v + k)
        sampled = (np.arange(n) % 2 == 1) | (n == 1)  # a prefill samples
        args = (jnp.asarray(rng.normal(0.0, 0.9, (n, v)), jnp.float32),
                jax.random.split(jax.random.key(n + v), n),
                jnp.asarray(np.where(sampled, 0.8, 1.0), jnp.float32),
                jnp.asarray(np.where(sampled, k, 0), jnp.int32),
                jnp.asarray(np.where(sampled, 0.95, 1.0), jnp.float32),
                jnp.asarray(~sampled))
        tokens = np.asarray(fn(*args))  # compiles, warms
        row = {"case": name, "rows": n, "vocab": v, "top_k": k,
               "tokens": tokens.tolist()}
        with tempfile.TemporaryDirectory(dir=os.path.dirname(out)) as d:
            with jax.profiler.trace(d):
                for _ in range(calls):
                    fn(*args).block_until_ready()
            red = tr.reduce_trace(tr.find_xplane(d))
        row["device_ms_a_call"] = 1e3 * red.busy_s / calls
        row["top_ops_ms_a_call"] = [
            (op, 1e3 * s / calls) for op, s in red.top_ops(4)]
        rows.append(row)
        print(json.dumps({k_: v_ for k_, v_ in row.items()
                          if k_ != "tokens"}), flush=True)
    with open(out, "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind}, "rows": rows}, f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="root of another commit's tree")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sample_trace.json"))
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    cases = args.cases.split(",")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    if args.child:
        return child(args.child, cases, args.calls, args.out)
    trees = {"change": ROOT}
    if args.parent:
        trees["parent"] = os.path.abspath(args.parent)
    results = {}
    for tag, tree in trees.items():
        part = f"{args.out}.{tag}"
        cmd = [sys.executable, os.path.abspath(__file__), "--child", tree,
               "--cases", args.cases, "--calls", str(args.calls),
               "--out", part]
        print(f"# {tag}: {tree}", flush=True)
        subprocess.run(cmd, check=True)
        with open(part) as f:
            results[tag] = json.load(f)
    rows = []
    for i, name in enumerate(cases):
        row = {"case": name}
        for tag, res in results.items():
            r = res["rows"][i]
            row.update({f"{tag}_{k}": v for k, v in r.items()
                        if k in ("device_ms_a_call", "top_ops_ms_a_call")})
        if "parent" in results:
            a, b = (results[t]["rows"][i]["tokens"]
                    for t in ("parent", "change"))
            row["tokens_equal"] = sum(x == y for x, y in zip(a, b))
            row["tokens"] = len(a)
            row["speedup"] = (row["parent_device_ms_a_call"]
                              / row["change_device_ms_a_call"])
        rows.append(row)
        print(json.dumps(row), flush=True)
    with open(args.out, "w") as f:
        json.dump({"device": results["change"]["device"], "rows": rows}, f,
                  indent=1)
    print(json.dumps({"ok": True, "device": results["change"]["device"],
                      "cases": len(rows)}))


if __name__ == "__main__":
    main()
