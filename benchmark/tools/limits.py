#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two readings a limit is
set from: what sound runs of the program give over a dozen seeds (the lower
reading is the largest), and what the control gives on a few (the upper is
the smallest). One process and one set-up for all seeds, since set-up is
long: the seed's weights are loaded into the one program between runs.

    python3 benchmark/tools/limits.py --workload serve_short_1p3b_knee80 \
        --seeds 11,12,...  --control 3 --seconds 51

Serving: per seed a window at the cell's own load, the samples of finished
greedy and sampled requests, the program's readings against the reference
(``serve.reference_readings``) and, for the first ``--control`` seeds, the
int8 and float8 controls' at the same positions and the fault 'top-p left
out'. Training: per seed the program's first
steps against the reference's and, for the first ``--control`` seeds, the
controls (the reference with int8 and with float8 matrix products) and the
fault 'half of the batch left out', each in the program's place.
Writes chiprun_out/limits_<cell>.json.
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


def serve_readings(cell, seeds, n_control, seconds, lead_in=None):
    import serve
    import weights

    cfg, mix = cell.config, cell.mix
    model, names, engine = serve.build_engine(cell, seeds[0])
    print("warmup", engine.warmup(), flush=True)
    spans, rows = harness.Spans(), []
    lead = float(mix["lead_in_s"] if lead_in is None else lead_in)
    k = mix["check"]["sample"]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        serve.load_weights(model, names, cell, seed)
        engine.release_prefix_cache()
        tracks = serve.make_tracks(mix, seed, lead, seconds, cfg["vocab_size"])
        serve.drive(engine, mix, tracks, lead, seconds, spans)
        e2e, failed, counts = serve.end_to_end(tracks, lead, seconds)
        engine.run()  # drain: every request of the window finishes
        for t in tracks:  # what finished after the loop stopped looking
            if t.tokens is None and t.rid in engine._requests:
                toks, done, _ = serve.emitted(engine, t.rid)
                if done:
                    t.tokens = list(toks)
        greedy = serve.check_sample(tracks, seed, k)
        sampled = serve.check_sample(tracks, seed, k, greedy=False)
        kw = {"ref_weights": weights.make(
            cell.arch.weight_spec(cfg, stacked=True), seed, cfg["dtype"]),
            "pad_to": serve.reference_pad(mix)}
        row = {"seed": seed, "failed": failed, **e2e, **counts,
               "program": serve.reference_readings(cell, seed, greedy,
                                                   sampled, **kw)}
        if i < n_control:
            for quant in ("int8", "fp8"):
                row["control_" + quant] = serve.reference_readings(
                    cell, seed, greedy, sampled, quant=quant, **kw)
            row["fault_no_top_p"] = serve.reference_readings(
                cell, seed, (), sampled, no_top_p=True, **kw)
        del kw
        gc.collect()
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def train_readings(cell, seeds, n_control, seconds, skip=()):
    import jax

    import traffic
    import train

    cfg, mix = cell.config, cell.mix
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog = train.Program(cell, seed)
        feed = traffic.TrainBatches(mix, seed, cfg["vocab_size"])
        batches, first = train.first_steps(prog, feed, seed)
        del prog
        gc.collect()
        jax.clear_caches()
        heads = cfg.get("num_attention_heads", 1)
        ref = train.reference_steps(cell, seed, batches)
        _, detail = train.compare(first, ref, {}, heads)
        row = {"seed": seed, "program": detail["all"],
               "leaves": [detail[k] for k in ("grad_leaf", "diff_leaf",
                                              "change_leaf")],
               "left_out": detail["leaves_left_out"],
               "losses": first[0], "reference_losses": ref[0]}
        if i < n_control:
            # the controls and the planted fault: the reference in the
            # program's place
            for name, kw in (("control_int8", {"quant": "int8"}),
                             ("control_fp8", {"quant": "fp8"}),
                             ("fault_half_batch",
                              {"rows": slice(0, max(mix["batch"] // 2, 1))})):
                if name in skip:
                    continue
                other = train.reference_steps(cell, seed, batches, **kw)
                row[name] = train.compare(other, ref, {}, heads)[1]["all"]
                del other
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--registry", default="BENCHMARK.json")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--lead-in", type=float, default=None,
                    help="serving: a lead-in other than the mix's (0 reads "
                         "the same tokens in less chip time)")
    ap.add_argument("--skip", default="",
                    help="training: controls to leave out, e.g. control_int8")
    args = ap.parse_args()
    import jax

    import paddle_tpu  # noqa: F401

    if jax.devices()[0].platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit("limits.py reads the chip")
    print("cache", harness.configure_cache(), flush=True)
    cell = harness.resolve(args.workload, registry=args.registry)
    seconds = args.seconds or harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json"))["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    read = {"serve": serve_readings, "train": train_readings}[cell.mix["kind"]]
    extra = ({"skip": args.skip.split(",")} if cell.mix["kind"] == "train"
             else {"lead_in": args.lead_in})
    rows = read(cell, seeds, args.control, seconds, **extra)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits_{cell.name}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
