#!/usr/bin/env python3
"""``limits.py``'s serving readings for a configuration that leaves the
reference no room BESIDE its engine (Ouro-2.6B: 5.3 GB of weights and a 6.5
GB pool, then the reference's own 5.3 GB of stacked weights, on a 16 GB
chip): per seed the window at the cell's own load, then the engine and the
model are dropped as ``serve.run`` drops them, and only then the program's
readings against the reference and, for the first ``--control`` seeds, the
int8 and float8 controls' at the same positions and the fault 'top-p left
out'. A seed pays a set-up of its own (the programs come from the compile
cache after the first).

    python3 benchmark/tools/limits_engine_dropped.py \
        --workload serve_reason_ouro2p6b_saturated --seeds 1,2,3 --control 3

Writes chiprun_out/limits_<cell>.json, rows as ``limits.py`` writes them.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402


def one_seed(cell, seed, controls, seconds, lead):
    import jax

    import serve
    import weights

    cfg, mix = cell.config, cell.mix
    t0 = time.perf_counter()
    model, _, engine = serve.build_engine(cell, seed)
    engine.warmup()
    setup = time.perf_counter() - t0
    tracks = serve.make_tracks(mix, seed, lead, seconds, cfg["vocab_size"])
    serve.drive(engine, mix, tracks, lead, seconds, harness.Spans())
    e2e, failed, counts = serve.end_to_end(tracks, lead, seconds)
    engine.run()  # drain: every request of the window finishes
    for t in tracks:  # what finished after the loop stopped looking
        if t.tokens is None and t.rid in engine._requests:
            toks, done, _ = serve.emitted(engine, t.rid)
            if done:
                t.tokens = list(toks)
    k = mix["check"]["sample"]
    greedy = serve.check_sample(tracks, seed, k)
    sampled = serve.check_sample(tracks, seed, k, greedy=False)
    del engine, model
    gc.collect()
    jax.clear_caches()
    kw = {"ref_weights": weights.make(
        cell.arch.weight_spec(cfg, stacked=True), seed, cfg["dtype"]),
        "pad_to": serve.reference_pad(mix)}
    row = {"seed": seed, "failed": failed, "setup_s": setup, **e2e, **counts,
           "program": serve.reference_readings(cell, seed, greedy, sampled,
                                               **kw)}
    if controls:
        for quant in ("int8", "fp8"):
            row["control_" + quant] = serve.reference_readings(
                cell, seed, greedy, sampled, quant=quant, **kw)
        row["fault_no_top_p"] = serve.reference_readings(
            cell, seed, (), sampled, no_top_p=True, **kw)
    del kw
    gc.collect()
    jax.clear_caches()
    row["seconds"] = time.perf_counter() - t0
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--registry", default="BENCHMARK.json")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--lead-in", type=float, default=None,
                    help="a lead-in other than the mix's (0 reads the same "
                         "tokens in less chip time)")
    args = ap.parse_args()
    import jax

    import paddle_tpu  # noqa: F401

    if jax.devices()[0].platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit("limits_engine_dropped.py reads the chip")
    print("cache", harness.configure_cache(), flush=True)
    cell = harness.resolve(args.workload, registry=args.registry)
    seconds = args.seconds or harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json"))["run_seconds"]
    lead = float(cell.mix["lead_in_s"] if args.lead_in is None
                 else args.lead_in)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows.append(one_seed(cell, seed, i < args.control, seconds, lead))
        print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits_{cell.name}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
