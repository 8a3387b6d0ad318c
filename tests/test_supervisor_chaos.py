"""Kill -9 soak for the fleet supervisor's flip transaction: a scripted
colocation run (train phases at journal-directed widths, interleaved
with idempotent serve phases) is SIGKILLed at EVERY named flip fence —
``plan``, ``drain``, ``quiesce``, ``resize``, ``commit``, ``finalize`` —
and relaunched (chaos disarmed via PADDLE_RESTART_COUNT).

The relaunched supervisor's ``recover()`` must resolve the interrupted
flip (roll forward at/past ``commit``, roll back before it) such that:

* the training-loss trajectory is BIT-EQUAL to an unkilled reference
  run — widths are applied exactly-once, no phase trains at a
  half-flipped width;
* the served-request ledger holds exactly the reference's request ids,
  each EXACTLY once — nothing dropped, nothing duplicated;
* the journal is left with no pending flip and the same committed-flip
  count as the reference.

A second sweep targets the SECOND flip of the run (the opposite
direction) via PADDLE_CHAOS_FLIP_SKIP, so both to_training and
to_serving transactions take kills.

Marked slow+chaos (boots fresh interpreters):
    pytest tests/test_supervisor_chaos.py --runslow
"""
import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FENCES = ("plan", "drain", "quiesce", "resize", "commit", "finalize")

#: the scripted run: (target training width, cumulative train steps)
#: per phase — four flips total, alternating directions
HARNESS = textwrap.dedent("""
    import hashlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.environ["PT_REPO"])
    from paddle_tpu.distributed.fleet.supervisor import (
        FleetSupervisor, FlipDecision, SupervisorConfig,
        _atomic_write_json, _read_json)

    state = sys.argv[1]
    train_path = os.path.join(state, "train_state.json")
    ledger_path = os.path.join(state, "ledger.jsonl")

    # recover() runs inside the constructor: an interrupted flip is
    # resolved before the script below ever looks at the roles doc
    sup = FleetSupervisor(
        os.path.join(state, "journal"),
        config=SupervisorConfig(hysteresis_s=0.0, cooldown_s=0.0,
                                breaker_max_flips=100),
        roles={"e0": "serving", "e1": "serving"}, training_width=0)

    def width():
        return int(sup.roles_doc.get("training_width", 0))

    def ensure_width(target):
        # idempotent desired-state convergence: a rolled-FORWARD
        # recovery already reached the target (no double flip); a
        # rolled-BACK one retries the flip exactly once
        for _ in range(4):
            w = width()
            if w == target:
                return
            d = "to_training" if target > w else "to_serving"
            sup.flip(FlipDecision(d, "e1", f"script->{target}"))
        raise SystemExit(f"ensure_width({target}) did not converge")

    def train(upto_steps):
        st = _read_json(train_path) or {"loss": 1.0, "hist": []}
        w = width()
        while len(st["hist"]) < upto_steps:
            step = len(st["hist"])
            # the recurrence DEPENDS on the width: trajectory equality
            # proves every phase trained at exactly the scripted width
            st["loss"] = 0.9 * st["loss"] + 1.0 / (w + 1) + 0.001 * step
            st["hist"].append(st["loss"])
            _atomic_write_json(train_path, st)

    def serve(phase):
        have = set()
        if os.path.exists(ledger_path):
            with open(ledger_path) as f:
                have = {json.loads(ln)["rid"] for ln in f if ln.strip()}
        with open(ledger_path, "a") as f:
            for j in range(4):
                rid = f"p{phase}r{j}"
                if rid in have:
                    continue   # exactly-once: replayed phases dedup
                tok = hashlib.md5(rid.encode()).hexdigest()[:8]
                f.write(json.dumps({"rid": rid, "tok": tok}) + "\\n")
                f.flush()

    PHASES = [(1, 3), (0, 6), (1, 9), (0, 12)]
    # durable phase cursor: a relaunch resumes at the interrupted
    # phase instead of replaying the width schedule from the top
    prog_path = os.path.join(state, "progress.json")
    start = int((_read_json(prog_path) or {}).get("next", 0))
    for i, (target_w, steps) in enumerate(PHASES):
        if i < start:
            continue
        ensure_width(target_w)
        train(steps)
        serve(i)
        _atomic_write_json(prog_path, {"next": i + 1})
    print(json.dumps({
        "hist": (_read_json(train_path) or {})["hist"],
        "flips": sup.roles_doc.get("flips_committed"),
        "pending": sup.journal.pending(),
    }))
""")


def _launch(state_dir, extra_env):
    env = {**os.environ, "PT_REPO": REPO}
    env.pop("PADDLE_CHAOS", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", HARNESS, str(state_dir)],
        capture_output=True, text=True, env=env, timeout=180)


def _finish(state_dir):
    """The clean (relaunched / reference) run's final report."""
    proc = _launch(state_dir, {"PADDLE_RESTART_COUNT": "1"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _ledger_rids(state_dir):
    with open(os.path.join(state_dir, "ledger.jsonl")) as f:
        return [json.loads(ln)["rid"] for ln in f if ln.strip()]


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    d = tmp_path_factory.mktemp("ref")
    out = _finish(d)
    rids = _ledger_rids(d)
    assert out["flips"] == 4 and out["pending"] is None
    assert len(rids) == len(set(rids)) == 16
    return {"hist": out["hist"], "rids": sorted(rids)}


CASES = [(f, 0) for f in FENCES] + [("quiesce", 1), ("commit", 1)]


@pytest.mark.parametrize("fence,skip", CASES,
                         ids=[f"{f}-flip{n + 1}" for f, n in CASES])
def test_sigkill_at_fence_recovers_bit_equal(tmp_path, reference,
                                             fence, skip):
    chaos_env = {
        "PADDLE_CHAOS": "1",
        "PADDLE_CHAOS_FLIP_MODE": "kill",
        "PADDLE_CHAOS_FLIP_AT": fence,
        "PADDLE_CHAOS_FLIP_SKIP": str(skip),
        "PADDLE_RESTART_COUNT": "0",
    }
    killed = _launch(tmp_path, chaos_env)
    # the fence must actually have fired — a soak that never kills
    # proves nothing
    assert killed.returncode == -signal.SIGKILL, (
        fence, skip, killed.returncode, killed.stdout, killed.stderr)
    # mid-flip state on disk now; relaunch with chaos disarmed
    out = _finish(tmp_path)
    assert out["pending"] is None
    assert out["flips"] == 4
    # bit-equal trajectory: every phase trained at the scripted width,
    # flips applied exactly once (JSON floats round-trip exactly)
    assert out["hist"] == reference["hist"]
    # zero dropped, zero duplicated requests
    rids = _ledger_rids(tmp_path)
    assert sorted(rids) == reference["rids"]
    assert len(rids) == len(set(rids))


def test_latency_mode_delays_without_killing(tmp_path):
    out = _launch(tmp_path, {
        "PADDLE_CHAOS": "1",
        "PADDLE_CHAOS_FLIP_MODE": "latency",
        "PADDLE_CHAOS_FLIP_AT": "commit",
        "PADDLE_CHAOS_FLIP_LATENCY_MS": "30",
        "PADDLE_RESTART_COUNT": "0",
    })
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["flips"] == 4 and report["pending"] is None
