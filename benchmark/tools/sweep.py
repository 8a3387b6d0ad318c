#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains: one process, one
set-up, a few rates. A rate is sustained when the backlog (requests submitted
and not finished) at the window's end is no larger than at its start. Run
once when a cell is defined; the cell then fixes its rate in its mix file.

    python3 benchmark/tools/sweep.py --workload serve_short_1p3b_knee80 \
        --seed 1 --rates 10.5,11,11.5,12,12.5,10.5,11,11.5,12,12.5

Rate i runs on seed + i, so a rate named twice is read on two seeds. The
defaults are the serving cells' own window and lead-in: windows of 25 s with
a lead-in of 8 s found the plateau and could not place the knee (PERF.md
section 4, PR 32).
"""
import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402
import serve  # noqa: E402


def backlog(tracks, t):
    n = 0
    for tr in tracks:
        if tr.submitted is None or tr.submitted > t:
            continue
        done_at = tr.times[-1] if tr.tokens is not None else float("inf")
        n += done_at > t
    return n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="10.5,11,11.5,12,12.5")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--lead-in", type=float, default=27.0)
    ap.add_argument("--describe-trace", action="store_true")
    args = ap.parse_args()
    import jax

    import paddle_tpu  # noqa: F401

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep.py measures a TPU")
    print("cache", harness.configure_cache(), flush=True)
    cell = harness.resolve(args.workload)
    cfg = cell.config
    t0 = time.perf_counter()
    model, _, engine = serve.build_engine(cell, args.seed)
    print(f"model and weights {time.perf_counter() - t0:.1f} s", flush=True)
    t0 = time.perf_counter()
    print("warmup", engine.warmup(), f"{time.perf_counter() - t0:.1f} s",
          flush=True)
    print("memory_stats", jax.devices()[0].memory_stats(), flush=True)
    spans = harness.Spans()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        engine.release_prefix_cache()
        tracks = serve.make_tracks(mix, args.seed + i, args.lead_in,
                                   args.seconds, cfg["vocab_size"])
        tracer = None
        if args.describe_trace and i == 0:
            tracer = harness.Tracer(True, os.path.join(
                harness.ROOT, ".bench_out", "sweep_trace"))
        n0 = len(spans.rows)
        serve.drive(engine, mix, tracks, args.lead_in, args.seconds, spans,
                    tracer)
        e2e, failed, counts = serve.end_to_end(tracks, args.lead_in,
                                               args.seconds)
        steps = [s for s in spans.rows[n0:] if s.name == "engine.step"]
        dec = sorted(s.seconds for s in steps if not s.attrs.get("admitted")
                     and s.attrs.get("decoded"))
        adm = sorted(s.seconds for s in steps if s.attrs.get("admitted"))
        b0 = backlog(tracks, args.lead_in)
        b1 = backlog(tracks, args.lead_in + args.seconds)
        print(f"rate {rate}: backlog {b0} -> {b1} "
              f"({'sustained' if b1 <= b0 else 'NOT sustained'}); "
              f"{ {k: round(v, 2) for k, v in e2e.items()} }; failed "
              f"{failed}; {counts}; decode step p50 "
              f"{dec[len(dec) // 2] * 1e3 if dec else None} ms n={len(dec)}; "
              f"admit step p50 {adm[len(adm) // 2] * 1e3 if adm else None} "
              f"ms n={len(adm)}", flush=True)
        if tracer is not None:
            import trace_reduce

            path = trace_reduce.find_xplane(tracer.dir)
            out = os.path.join(harness.ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "trace_describe.txt"), "w") as f:
                f.write(trace_reduce.describe(path, top=40))
            red = trace_reduce.reduce_trace(path, harness.Spans.NAMES)
            print("traced window", red.window_s, "busy", red.busy_s,
                  "top", red.top_ops(12), "idle", red.top_idle(), flush=True)
        engine.run()  # drain what the window left
    print("memory_stats", jax.devices()[0].memory_stats(), flush=True)
    print("engine stats", engine.stats(), flush=True)


if __name__ == "__main__":
    main()
