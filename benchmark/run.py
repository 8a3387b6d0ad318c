#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the chip(s) the cell asks for: with no TPU, or too few, it exits
non-zero and prints no result. Builds the cell's configuration, warms only
the shapes its traffic uses (set-up), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of its standard output. Everything that belongs to
one configuration, one traffic mix or one per-layer metric is a file that
this finds by the name in BENCHMARK.json (benchmark/README.md).
"""
import time

T0 = time.perf_counter()  # process start, as near as Python lets us stand

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import (ReadCtx, Spans, Tracer, load_json, configure_cache,  # noqa: E402
                     device_info, load_reader, peaks_for, resolve, say)

# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(cell, seed: int, seconds: float, trace: bool) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    import jax

    driver = importlib.import_module(cell.mix["kind"])  # serve | train
    out_dir = os.path.join(cell.root, ".bench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    spans = Spans()
    tracer = Tracer(trace, os.path.join(out_dir, "trace"))
    dev = jax.devices()[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}; cache {configure_cache()}")
    outcome = driver.run(cell, seed, seconds, spans, tracer, T0)
    outcome.spans, outcome.tracer = spans, tracer
    for k, v in sorted(outcome.counters.items()):
        say(f"counter {k} = {v}")
    device = {**device_info(), "memory_peak_bytes": outcome.memory_peak_bytes}
    metrics = {}
    result = {}
    if trace:
        red = tracer.reduce()
        if red is None or red.busy_s <= 0:
            raise SystemExit("run.py: the traced window holds no device "
                             "operation")
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        # a rehearsal off the chip has no peaks: share readers return None
        peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
        ctx = ReadCtx(cell, outcome, red, peaks, seconds)
        for m in cell.per_layer:
            read, args = load_reader(cell, m["name"])
            value = read(ctx, **args)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in red.top_idle(10)]}
    else:
        for m in cell.end_to_end:
            value = (outcome.setup_s if m["name"] == "setup_s"
                     else outcome.end_to_end.get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    correct = all(v is not None and v <= lim
                  for _, v, lim in outcome.compared) and bool(outcome.compared)
    compared = {n: {"value": v, "limit": lim}
                for n, v, lim in outcome.compared}
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics, "device": device,
              **result, "compared": compared}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--registry", default="BENCHMARK.json",
                    help="another registry (the CPU rehearsal's)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="skip the look for a chip: a rehearsal, whose "
                         "numbers are never device numbers")
    args = ap.parse_args(argv)
    cell = resolve(args.workload, ROOT, args.registry)
    seconds = args.seconds
    if seconds is None:
        seconds = load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]

    import jax

    import paddle_tpu  # noqa: F401 — picks the default PRNG before a backend

    devices = jax.devices()
    if not args.rehearse_on_cpu:
        if devices[0].platform != "tpu":
            print(f"run.py: no TPU: jax found {devices[0].platform!r}. "
                  "Nothing was run.", file=sys.stderr)
            return 2
        if len(devices) < cell.chips:
            print(f"run.py: cell {cell.name} needs {cell.chips} chips, jax "
                  f"found {len(devices)}. Nothing was run.", file=sys.stderr)
            return 2
    result = run_cell(cell, args.seed, seconds, bool(args.trace))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
