"""Kill -9 soak for the online weight-flip transaction: a scripted
continuous-learning run (three weight epochs published into a live
decode engine, each followed by a greedy decode) is SIGKILLed at EVERY
named weight fence — ``publish``, ``stream``, per-frame ``wt:<seq>``,
``commit``, ``swap``, ``finalize`` — and relaunched (chaos disarmed via
PADDLE_RESTART_COUNT).

The relaunched publisher's ``recover()`` + ``ensure_epoch`` convergence
must leave durable state indistinguishable from an unkilled run:

* per-epoch greedy decode is BIT-EQUAL to the reference — every phase
  decoded on exactly its scripted epoch's weights, never a half-staged
  shadow;
* the decode ledger holds exactly the reference's request ids, each
  EXACTLY once — nothing dropped, nothing duplicated;
* the weight journal ends with no pending transaction and exactly one
  committed history entry per epoch (``close_weights`` dedups by id, so
  a recovery retirement and its re-publish collapse to one entry).

A second sweep targets the SECOND flip via PADDLE_CHAOS_WEIGHT_SKIP.

Marked slow+chaos (boots fresh interpreters):
    pytest tests/test_online_chaos.py --runslow
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

HARNESS = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.environ["PT_REPO"])
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.supervisor import (
        FlipJournal, _atomic_write_json, _read_json)
    from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                             SamplingParams)
    from paddle_tpu.serving.online import EngineSink, OnlineCoordinator
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    state = sys.argv[1]
    ledger_path = os.path.join(state, "ledger.jsonl")
    prog_path = os.path.join(state, "progress.json")

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    model.eval()
    # epoch-0 base snapshot BEFORE any flip: params_for(E) is a pure
    # function of it, so a relaunch recomputes identical epoch weights
    base = {n: np.asarray(p._value, np.float32)
            for n, p in model.named_parameters()}

    def params_for(epoch):
        return {n: v + 0.01 * epoch * np.sign(v) for n, v in base.items()}

    eng = DecodeEngine(model, EngineConfig(num_slots=2, max_length=64))
    journal = FlipJournal(os.path.join(state, "journal"))
    coord = OnlineCoordinator(journal, {"engine0": EngineSink(eng)})
    # resolve any transaction a kill left open before touching weights
    coord.recover()

    prompt = np.arange(1, 8, dtype=np.int64)

    def decode(epoch):
        have = {}
        if os.path.exists(ledger_path):
            with open(ledger_path) as f:
                have = {json.loads(ln)["rid"]: json.loads(ln)["tokens"]
                        for ln in f if ln.strip()}
        rid = f"e{epoch}"
        if rid in have:
            return   # exactly-once: a replayed phase must not re-append
        r = eng.submit(prompt, SamplingParams(max_new_tokens=6))
        eng.run()
        tokens = [int(t) for t in eng.result(r)]
        with open(ledger_path, "a") as f:
            f.write(json.dumps({"rid": rid, "tokens": tokens}) + "\\n")
            f.flush()

    EPOCHS = (1, 2, 3)
    start = int((_read_json(prog_path) or {}).get("next", 0))
    for i, epoch in enumerate(EPOCHS):
        if i < start:
            continue
        # idempotent convergence: a fresh process's engine restarts at
        # epoch 0, so the publish replays bit-equal weights; engines
        # already past the target no-op through the exactly-once guards
        coord.ensure_epoch(epoch, params_for(epoch))
        assert eng.weight_epoch == epoch, (eng.weight_epoch, epoch)
        decode(epoch)
        _atomic_write_json(prog_path, {"next": i + 1})
    print(json.dumps({
        "epoch": eng.weight_epoch,
        "pending": journal.pending_weights(),
        "history": [[h["id"], h["outcome"]]
                    for h in journal.weight_history()],
    }))
""")


def _launch(state_dir, extra_env):
    env = {**os.environ, "PT_REPO": REPO}
    env.pop("PADDLE_CHAOS", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", HARNESS, str(state_dir)],
        capture_output=True, text=True, env=env, timeout=300)


def _finish(state_dir):
    proc = _launch(state_dir, {"PADDLE_RESTART_COUNT": "1"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _ledger(state_dir):
    with open(os.path.join(state_dir, "ledger.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    d = tmp_path_factory.mktemp("ref")
    out = _finish(d)
    rows = _ledger(d)
    assert out["epoch"] == 3 and out["pending"] is None
    assert out["history"] == [["wt-1", "committed"], ["wt-2", "committed"],
                              ["wt-3", "committed"]]
    rids = [r["rid"] for r in rows]
    assert rids == ["e1", "e2", "e3"]
    # three distinct epochs must decode three distinct streams, or the
    # bit-equality below would vacuously pass on frozen weights
    assert len({tuple(r["tokens"]) for r in rows}) > 1
    return {"rows": rows}


#: one kill at every named fence, plus mid-stream per-frame kills
#: (wt:1 = the first begin frame, wt:9 = mid-leaf) and second-flip
#: variants via the skip counter
CASES = ([(f, 0) for f in ("publish", "stream", "wt:1", "wt:9",
                           "commit", "swap", "finalize")]
         + [("swap", 1), ("stream", 1)])


@pytest.mark.parametrize("fence,skip", CASES,
                         ids=[f"{f.replace(':', '')}-flip{n + 1}"
                              for f, n in CASES])
def test_sigkill_at_weight_fence_recovers_bit_equal(tmp_path, reference,
                                                    fence, skip):
    chaos_env = {
        "PADDLE_CHAOS": "1",
        "PADDLE_CHAOS_WEIGHT_MODE": "kill",
        "PADDLE_CHAOS_WEIGHT_AT": fence,
        "PADDLE_CHAOS_WEIGHT_SKIP": str(skip),
        "PADDLE_RESTART_COUNT": "0",
    }
    killed = _launch(tmp_path, chaos_env)
    # the fence must actually have fired — a soak that never kills
    # proves nothing
    assert killed.returncode == -signal.SIGKILL, (
        fence, skip, killed.returncode, killed.stdout, killed.stderr)
    # mid-transaction state on disk now; relaunch with chaos disarmed
    out = _finish(tmp_path)
    assert out["pending"] is None
    assert out["epoch"] == 3
    # exactly-once flips: one committed entry per epoch, no strays
    assert out["history"] == [["wt-1", "committed"], ["wt-2", "committed"],
                              ["wt-3", "committed"]]
    # per-epoch greedy decode is bit-equal to the unkilled reference,
    # with zero dropped and zero duplicated requests
    assert _ledger(tmp_path) == reference["rows"]


def test_latency_mode_delays_without_killing(tmp_path):
    out = _launch(tmp_path, {
        "PADDLE_CHAOS": "1",
        "PADDLE_CHAOS_WEIGHT_MODE": "latency",
        "PADDLE_CHAOS_WEIGHT_AT": "commit",
        "PADDLE_CHAOS_WEIGHT_LATENCY_MS": "30",
        "PADDLE_RESTART_COUNT": "0",
    })
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["epoch"] == 3 and report["pending"] is None
