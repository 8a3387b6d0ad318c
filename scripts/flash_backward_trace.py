#!/usr/bin/env python3
"""The flash-attention backward alone, timed per kernel from a device trace.

For each case a jitted forward and backward of
``ops/pallas/flash_attention.py::flash_attention`` (one ``jax.vjp`` with a
random cotangent) runs ``--calls`` times under ``jax.profiler.trace``. Each
flash kernel's time a call is the summed device time of its events
(matched by the ``pallas_call`` name in the HLO line), beside the device's
busy time a call. Never the host's clock.

With ``--parent DIR`` (an unpacked ``git archive`` of another commit) the
same cases run on that tree's kernels too. Each tree runs in a child process
of its own, one after the other, since a process imports one ``paddle_tpu``
and holds the chip until it exits. The two trees' dQ, dK and dV are compared
on the first 16 batch-heads: the largest difference and the share of
elements that are bit for bit equal.

    python3 scripts/flash_backward_trace.py [--parent _tree/parent] \\
        [--out chiprun_out/flash_backward_trace.json]

The cases: the ERNIE training cell's attention (batch 32, 1,024 tokens, 12
heads of 64, not causal), where one key block covers every key, and GPT-3
1.3B at 2,048 tokens (batch 4, 16 heads of 128, causal), where the
backward's keys take two blocks. Nothing here is a benchmark cell; PERF.md
quotes it.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (batch, tokens, heads, head_dim, causal)
CASES = {
    "ernie_b32_t1024_h12_d64": (32, 1024, 12, 64, False),
    "gpt1p3b_b4_t2048_h16_d128": (4, 2048, 16, 128, True),
}
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv", "flash_attention_bwd_fused")
#: batch-heads of each gradient kept for the comparison of the two trees
KEEP = 16


def child(tree, cases, calls, out):
    """Times ``tree``'s flash kernels at ``cases``; writes rows to ``out``
    and the first ``KEEP`` batch-heads of each gradient beside it."""
    sys.path.insert(0, tree)
    sys.path.insert(1, os.path.join(ROOT, "benchmark"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce as tr
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("flash_backward_trace.py measures a TPU")
    rows, grads = [], {}
    for name in cases:
        b, t, h, d, causal = CASES[name]
        keys = jax.random.split(jax.random.key(b * t + h * d), 4)
        q, k, v, do = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                       for kk in keys)

        @jax.jit
        def fwd_bwd(q, k, v, do):
            _, vjp = jax.vjp(
                lambda *a: flash_attention(*a, causal=causal), q, k, v)
            return vjp(do)

        out_grads = jax.block_until_ready(fwd_bwd(q, k, v, do))  # compiles
        for g, x in zip(("dq", "dk", "dv"), out_grads):
            x = jnp.swapaxes(x, 1, 2).reshape(b * h, t, d)[:KEEP]
            grads[f"{name}.{g}"] = np.asarray(x.astype(jnp.float32))
        with tempfile.TemporaryDirectory(dir=os.path.dirname(out)) as td:
            with jax.profiler.trace(td):
                for _ in range(calls):
                    jax.block_until_ready(fwd_bwd(q, k, v, do))
            red = tr.reduce_trace(tr.find_xplane(td))
        row = {"case": name, "busy_ms_a_call": 1e3 * red.busy_s / calls}
        for kern in KERNELS:
            pattern = rf"%\w*{kern}_*(\.\d+)? = [^\n]*tpu_custom_call"
            row[f"{kern}_ms_a_call"] = (
                1e3 * red.kernel_seconds(pattern) / calls)
        row["top_ops_ms_a_call"] = [
            (op, 1e3 * s / calls) for op, s in red.top_ops(6)]
        rows.append(row)
        print(json.dumps(row), flush=True)
    np.savez(out + ".npz", **grads)
    with open(out, "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind}, "rows": rows}, f)


def compare(parent_npz, change_npz, name):
    import numpy as np

    a, b = np.load(parent_npz), np.load(change_npz)
    out = {}
    for g in ("dq", "dk", "dv"):
        x, y = a[f"{name}.{g}"], b[f"{name}.{g}"]
        out[f"{g}_max_abs_diff"] = float(np.max(np.abs(x - y)))
        out[f"{g}_bit_equal_share"] = float(np.mean(x == y))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="root of another commit's tree")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "flash_backward_trace.json"))
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
            row.update({f"{tag}_{k}": v for k, v in res["rows"][i].items()
                        if k != "case"})
        if "parent" in results:
            row.update(compare(f"{args.out}.parent.npz",
                               f"{args.out}.change.npz", name))
            row["busy_speedup"] = (row["parent_busy_ms_a_call"]
                                   / row["change_busy_ms_a_call"])
        rows.append(row)
        print(json.dumps(row), flush=True)
    for tag in trees:  # ~60 MiB of gradients a tree; the rows keep the verdict
        os.remove(f"{args.out}.{tag}.npz")
    with open(args.out, "w") as f:
        json.dump({"device": results["change"]["device"], "rows": rows}, f,
                  indent=1)
    print(json.dumps({"ok": True, "device": results["change"]["device"],
                      "cases": len(rows)}))


if __name__ == "__main__":
    main()
