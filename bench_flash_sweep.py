"""Flash-attention block-size sweep on the TPU.

Runs bench.py's kernel microbench (fwd + fwd/bwd vs XLA) for each
block-size combination in a FRESH subprocess, one after another — the env
knobs (PADDLE_TPU_FLASH_BLOCK_Q/K, PADDLE_TPU_FLASH_BWD_BLOCK_Q/K) are read
at trace time, so per-config process isolation is what makes the sweep
honest. This parent never touches JAX, so each child gets the chip; a child
that finds no TPU fails, and so does the sweep. Results append to
FLASH_SWEEP.json (seq -> config -> timings); the best bwd config found
should then be baked into ops/pallas/flash_attention.py defaults and
re-proven by a full bench.py run.

Usage: python bench_flash_sweep.py [seq ...]   (default: 1024 2048)
"""
import itertools
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "FLASH_SWEEP.json")

# bwd-focused grid: fwd already measured best at 1024x1024 on v5e;
# the bwd kernel's larger per-tile working set may prefer smaller tiles
GRID = [
    dict(fq=1024, fk=1024, bq=1024, bk=1024),  # current default
    dict(fq=1024, fk=1024, bq=512, bk=1024),
    dict(fq=1024, fk=1024, bq=1024, bk=512),
    dict(fq=1024, fk=1024, bq=512, bk=512),
    dict(fq=1024, fk=1024, bq=256, bk=512),
    dict(fq=1024, fk=1024, bq=512, bk=256),
]


def main():
    seqs = [int(a) for a in sys.argv[1:]] or [1024, 2048]
    try:
        with open(OUT) as f:
            results = json.load(f)
    except (OSError, ValueError):
        results = {}
    for seq, cfg in itertools.product(seqs, GRID):
        child = dict(os.environ)
        child["PADDLE_TPU_FLASH_BLOCK_Q"] = str(cfg["fq"])
        child["PADDLE_TPU_FLASH_BLOCK_K"] = str(cfg["fk"])
        child["PADDLE_TPU_FLASH_BWD_BLOCK_Q"] = str(cfg["bq"])
        child["PADDLE_TPU_FLASH_BWD_BLOCK_K"] = str(cfg["bk"])
        phase = f"micro:{seq}"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), phase],
            env=child, check=True, capture_output=True, text=True,
            timeout=900).stdout
        r = json.loads(out.strip().splitlines()[-1])[phase]
        key = f"seq{seq}"
        name = f"f{cfg['fq']}x{cfg['fk']}_b{cfg['bq']}x{cfg['bk']}"
        results.setdefault(key, {})[name] = r
        print(json.dumps({"seq": seq, "config": name,
                          "pallas_fwdbwd_ms": r["pallas_fwdbwd_ms"],
                          "speedup_fwdbwd": r["speedup_fwdbwd"]}),
              flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
    # summary: best bwd config per seq
    for key, rs in results.items():
        good = {n: v for n, v in rs.items()
                if isinstance(v, dict) and v.get("pallas_fwdbwd_ms")}
        if good:
            best = min(good, key=lambda n: good[n]["pallas_fwdbwd_ms"])
            print(f"# {key}: best {best} @ {good[best]['pallas_fwdbwd_ms']}ms "
                  f"(default f1024x1024_b1024x1024: "
                  f"{good.get('f1024x1024_b1024x1024', {}).get('pallas_fwdbwd_ms')}ms)")


if __name__ == "__main__":
    main()
