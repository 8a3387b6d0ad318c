#!/usr/bin/env python3
"""What two sets of runs of one cell say about a bound: per end-to-end
metric each set's median, its spread as the contract measures it (first to
third quartile of ``statistics.quantiles(values, n=4)`` over the median) and
as the driver's refusals word it (max less min, leaving out the run farthest
from the median where that narrows it); per compared number the largest
sound reading beside its limit; what the traced runs printed.

    python3 benchmark/tools/spreads.py chiprun_out/sets_<cell>.jsonl
"""
import json
import statistics
import sys


def iqr_share(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def range_share(values):
    """Max less min over the median, without the run farthest from it."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1]
    return (max(kept) - min(kept)) / med


def main(path):
    rows = [json.loads(line) for line in open(path)]
    bad = [(r["set"], r["seed"]) for r in rows
           if r["rc"] or not r["line"]["correct"] or r["line"]["failed"]]
    print(f"{len(rows)} runs; rc != 0, correct false or failed > 0: {bad}")
    sets = {s: [r["line"] for r in rows if r["set"] == s and not r["rc"]]
            for s in ("1", "2")}
    for name in sorted({n for ls in sets.values() for l in ls
                        for n in l["metrics"]}):
        widest = 0.0
        for s, lines in sets.items():
            v = [l["metrics"][name]["value"] for l in lines]
            if len(v) < 3:
                continue
            widest = max(widest, iqr_share(v))
            print(f"{name} set {s}: median {statistics.median(v):.6g} "
                  f"quartile spread {100 * iqr_share(v):.3f}% range less "
                  f"farthest {100 * range_share(v):.3f}% values "
                  f"{[round(x, 4) for x in v]}")
        print(f"{name}: five times the wider quartile spread = "
              f"{100 * 5 * widest:.2f}%")
    sound = [r["line"] for r in rows if not r["rc"]]
    for name in sound[0]["compared"] if sound else ():
        got = [l["compared"][name] for l in sound]
        print(f"compared {name}: largest "
              f"{max(c['value'] for c in got)} of {len(got)} runs, limit "
              f"{got[0]['limit']}")
    peaks = {l["device"]["memory_peak_bytes"] for l in sound}
    print(f"memory_peak_bytes {min(peaks)}..{max(peaks)}")
    for r in rows:
        if r["set"] == "trace" and not r["rc"]:
            d, m = r["line"]["device"], r["line"]["metrics"]
            print(f"traced seed {r['seed']}: busy {d['busy_s']:.4f} of "
                  f"{d['window_s']:.4f} s; "
                  + "; ".join(f"{k} {v['value']:.5g}" for k, v in m.items()))


if __name__ == "__main__":
    main(sys.argv[1])
