#!/usr/bin/env python3
"""The paged-attention kernel alone, timed from a device trace.

One process on one TPU: for each case a jitted call of the kernel runs
``--calls`` times under ``jax.profiler.trace``; the time of a call is the
duration of its ``paged_attention`` event on the device's ``XLA Ops`` line,
never the host's clock. With ``--parent DIR`` (an unpacked ``git archive`` of
another commit) that tree's kernel runs the same inputs, the two outputs are
compared on the rows of live slots (within float32 tolerance and equal
argmax: since PR 36 the online softmax rescales a block of pages, not a
page, so the order of its roundings differs), and each is compared with
the einsum oracle of THIS tree, run at float32 ``highest``. Beside the 4-D call on one layer's pool
(``change``) this tree's kernel also runs as the engine calls it since PR
30 (``stacked``): the same pool as layer 1 of a stacked ``[3, N, Hkv, P, D]``
array, the layer index a traced argument; its rows are compared bit for bit
with the 4-D call's.

    python3 scripts/paged_attention_trace.py [--parent _tree/parent] \
        [--out chiprun_out/paged_attention_trace.json]

The cases are the serving cells' shapes. GPT-3 1.3B (16 heads of 128, page
16, a table of 128 page slots, a bf16 pool of 1025 pages): the decode call
at contexts like the cell's, idle, and at a full pool; the call at what a
prefill bucket's row counts were. Ouro-2.6B (the same heads and page, a
table of 32 page slots, 257 pages): the call that a decode pass makes 192
times, at the cell's ~300 tokens a slot and at a full pool. Nothing here is
a benchmark cell; PERF.md quotes it.
"""
import argparse
import importlib.util
import json
import os
import re
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

H, D, PAGE, MAX_PAGES, SLOTS = 16, 128, 16, 128, 8
#: the stacked call's pool: the case's pool at LAYER, other pages elsewhere
LAYERS, LAYER = 3, 1

#: name -> (slots, T, tokens cached in each slot before the T rows, pool[,
#: the table's page slots: MAX_PAGES unless given])
CASES = {
    "decode_cell": (8, 1, [100, 300, 500, 700, 0, 0, 0, 0], "bf16"),
    "decode_idle": (8, 1, [0] * 8, "bf16"),
    "decode_full": (8, 1, [2047] * 8, "bf16"),
    "decode_cell_int8": (8, 1, [100, 300, 500, 700, 0, 0, 0, 0], "int8"),
    "verify_k4_cell": (8, 5, [100, 300, 500, 700, 0, 0, 0, 0], "bf16"),
    "loop_decode_cell": (8, 1, [300, 180, 420, 260, 340, 220, 380, 300],
                         "bf16", 32),
    "loop_decode_full": (8, 1, [511] * 8, "bf16", 32),
    "prefill128_cached0": (1, 128, [0], "bf16"),
    "prefill512_cached0": (1, 512, [0], "bf16"),
    "prefill512_cached128": (1, 512, [128], "bf16"),
    "prefill1024_cached0": (1, 1024, [0], "bf16"),
    "prefill1024_cached1024": (1, 1024, [1024], "bf16"),
}


def load_kernel(root, tag):
    path = os.path.join(root, "paddle_tpu", "ops", "pallas",
                        "paged_attention.py")
    spec = importlib.util.spec_from_file_location(f"paged_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_case(rng, s, t, cached, pool, max_pages=MAX_PAGES):
    import jax.numpy as jnp
    import numpy as np

    n = 1 + SLOTS * max_pages
    q = rng.standard_normal((s, t, H, D)).astype(np.float32)
    table = np.zeros((s, max_pages), np.int32)
    free = iter(rng.permutation(np.arange(1, n)))
    for i, c in enumerate(cached):
        if c or t > 1:  # an idle slot keeps a table of trash pages
            for j in range(-(-(c + t) // PAGE)):
                table[i, j] = next(free)
    shape = (n, H, PAGE, D)
    scales = ()
    if pool == "int8":
        kp, vp = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                  for _ in range(2))
        scales = tuple(jnp.asarray(
            rng.uniform(0.005, 0.03, shape[:3]), jnp.float32)
            for _ in range(2))
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                  for _ in range(2))
    return (jnp.asarray(q, jnp.bfloat16), kp, vp, jnp.asarray(table),
            jnp.asarray(cached, jnp.int32)) + scales


def stacked(pool):
    """The case's pool as layer LAYER of a stacked one; the other layers
    hold the same pages rolled, so a wrong layer index changes the rows."""
    import jax.numpy as jnp

    return jnp.stack([pool if i == LAYER else jnp.roll(pool, i + 1, axis=0)
                      for i in range(LAYERS)])


def kernel_event_ms(trace_dir):
    """Durations, in ms, of the kernel's events on chip 0, by the
    benchmark's own reduction of a trace."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import trace_reduce as tr

    events = tr.reduce_trace(tr.find_xplane(trace_dir)).events
    return [1e3 * seconds for name, _, seconds, _ in events
            if re.search(r"paged_attention[.\d]* = ", name)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="root of another commit's tree")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "paged_attention_trace.json"))
    ap.add_argument("--budget-mib", type=float,
                    help="this tree's kernel under another VMEM budget")
    ap.add_argument("--block-keys", type=int,
                    help="this tree's kernel under another cap on a "
                         "block's keys (a sweep's hook, not the program's)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="interpret mode, outputs compared, nothing timed")
    args = ap.parse_args()
    import jax
    import numpy as np

    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.op import raw

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit("paged_attention_trace.py measures a TPU")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    change = load_kernel(ROOT, "change")
    if args.budget_mib:
        change._VMEM_BUDGET = int(args.budget_mib * 2 ** 20)
    if args.block_keys:
        change._BLOCK_KEYS = args.block_keys
    kernels = {"change": change.paged_attention,
               "stacked": change.paged_attention}
    if args.parent:
        kernels["parent"] = load_kernel(
            os.path.abspath(args.parent), "parent").paged_attention
    rows = []
    for name in args.cases.split(","):
        s, t, cached, pool, *table_width = CASES[name]
        case = make_case(np.random.default_rng(len(name) + t), s, t, cached,
                         pool, *table_width)
        with jax.default_matmul_precision("highest"):
            # float32 queries of the same values: the oracle's result
            # takes its queries' dtype
            oracle = np.asarray(raw(F.paged_attention(
                case[0].astype("float32"), *case[1:5],
                k_scales=case[5] if pool == "int8" else None,
                v_scales=case[6] if pool == "int8" else None,
                kernel="einsum")), np.float32)
        live = [i for i, c in enumerate(cached) if c or t > 1] or [0]
        row = {"case": name, "slots": s, "T": t, "cached": cached,
               "pool": pool}
        outs = {}
        for tag, kernel in kernels.items():
            def call(q, kp, vp, table, start, *rest, _k=kernel, _tag=tag):
                kw = {}
                if _tag == "stacked":
                    kw["layer"], rest = rest[0], rest[1:]
                if rest:
                    kw.update(k_scales=rest[0], v_scales=rest[1])
                return _k(q, kp, vp, table, start, **kw)

            fn, inputs = jax.jit(call), case
            if tag == "stacked":
                inputs = (case[0], stacked(case[1]), stacked(case[2]),
                          *case[3:5], np.int32(LAYER), *case[5:])
            outs[tag] = np.asarray(fn(*inputs))  # compiles, warms
            row[tag + "_oracle_maxdiff"] = float(
                np.abs(outs[tag][live] - oracle[live]).max())
            row[tag + "_oracle_equal_argmax"] = bool(np.array_equal(
                outs[tag][live].argmax(-1), oracle[live].argmax(-1)))
            if args.rehearse_on_cpu:
                continue
            with tempfile.TemporaryDirectory(
                    dir=os.path.dirname(args.out)) as d:
                with jax.profiler.trace(d):
                    for _ in range(args.calls):
                        fn(*inputs).block_until_ready()
                ms = kernel_event_ms(d)
            if len(ms) != args.calls:
                sys.exit(f"{name}/{tag}: {len(ms)} kernel events in the "
                         f"trace of {args.calls} calls")
            row[tag + "_ms_p50"] = statistics.median(ms)
            row[tag + "_ms_min"] = min(ms)
            row[tag + "_ms_max"] = max(ms)
        row["stacked_bit_equal"] = bool(np.array_equal(
            outs["stacked"], outs["change"]))
        if "parent" in outs:
            if not args.rehearse_on_cpu:
                row["speedup_p50"] = (row["parent_ms_p50"]
                                      / row["change_ms_p50"])
            row["live_rows_within_tolerance"] = bool(np.allclose(
                outs["parent"][live], outs["change"][live], atol=2e-5,
                rtol=1e-4))
            row["live_rows_equal_argmax"] = bool(np.array_equal(
                outs["parent"][live].argmax(-1),
                outs["change"][live].argmax(-1)))
            row["live_rows_maxdiff"] = float(np.abs(
                outs["parent"][live] - outs["change"][live]).max())
        rows.append(row)
        print(json.dumps(row), flush=True)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind}, "rows": rows}, f,
                  indent=1)
    print(json.dumps({"ok": True, "device": dev.device_kind,
                      "cases": len(rows)}))


if __name__ == "__main__":
    main()
