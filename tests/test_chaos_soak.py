"""End-to-end kill -9 soak: the acceptance test for the crash-safety
pipeline (docs/FAULT_TOLERANCE.md).

Each run launches a real training script through the launch CLI with the
chaos harness armed: the worker is SIGKILLed mid-training (or mid-save),
the supervisor relaunches it (PADDLE_RESTART_COUNT=1 disarms chaos), and
training resumes from the newest committed checkpoint. The final state
dict must be BITWISE IDENTICAL to an uninterrupted reference run — resume
is exact, not approximate.

Marked slow+chaos: each case boots ~2 fresh interpreters; run with
    pytest tests/test_chaos_soak.py --runslow
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

TOTAL_STEPS = 12

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.environ["PT_REPO"])
    import _cpu_mesh_flags; _cpu_mesh_flags.apply(n_devices=1)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.testing import chaos

    ckpt_dir, out_path, total = sys.argv[1], sys.argv[2], int(sys.argv[3])
    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    step_fn = TrainStep(model, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))

    elastic = ElasticManager(ckpt_dir, save_interval=2, max_to_keep=2)
    start = elastic.resume(model, opt)
    for step in range(start, total):
        chaos.step_fence(step)
        float(step_fn(x, y))
        elastic.maybe_save(step, model, opt)
    elastic.flush()
    np.savez(out_path, **{k: np.asarray(v.numpy())
                          for k, v in model.state_dict().items()})
""")


def _run(tmp_path, tag, total=TOTAL_STEPS, chaos_env=None, max_restarts=3):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    ckpt = tmp_path / f"ckpt_{tag}"
    out = tmp_path / f"final_{tag}.npz"
    env = {k: v for k, v in os.environ.items() if not k.startswith("PADDLE_CHAOS")}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PT_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    env.update(chaos_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", str(max_restarts), "--restart_backoff", "0.1",
         str(worker), str(ckpt), str(out), str(total)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=env["PT_REPO"])
    assert proc.returncode == 0, (
        f"launch rc={proc.returncode}\nstdout:\n{proc.stdout[-2000:]}"
        f"\nstderr:\n{proc.stderr[-4000:]}")
    return np.load(out), ckpt, proc


def _assert_bitwise_equal(got, want):
    assert sorted(got.files) == sorted(want.files)
    for k in want.files:
        a, b = got[k], want[k]
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes(), f"state {k} differs after resume"


def test_kill9_soak_bitwise_identical(tmp_path):
    """N=5 runs, each SIGKILLed at a different step, all must land on the
    reference run's exact final weights (acceptance criterion)."""
    ref, _, _ = _run(tmp_path, "ref")
    for kill_step in (2, 4, 5, 8, 11):
        got, _, proc = _run(
            tmp_path, f"kill{kill_step}",
            chaos_env={
                "PADDLE_CHAOS": "1",
                "PADDLE_CHAOS_SEED": str(kill_step),
                "PADDLE_CHAOS_KILL_STEP": str(kill_step),
            })
        assert "SIGKILL" in proc.stderr  # the fault actually fired
        assert "relaunching" in proc.stderr
        _assert_bitwise_equal(got, ref)


@pytest.mark.parametrize("mode", ["crash", "torn"])
def test_kill_during_save_never_restores_damage(tmp_path, mode):
    """A kill DURING the checkpoint commit (or a legacy torn write) must
    leave nothing restorable under the final name; the relaunch resumes
    from the previous committed step and still converges bitwise."""
    ref, _, _ = _run(tmp_path, f"ref_{mode}")
    got, ckpt, proc = _run(
        tmp_path, f"save_{mode}",
        chaos_env={
            "PADDLE_CHAOS": "1",
            "PADDLE_CHAOS_CKPT_MODE": mode,
            "PADDLE_CHAOS_CKPT_STEP": "5",
        })
    assert "SIGKILL" in proc.stderr
    _assert_bitwise_equal(got, ref)
    # whatever remains on disk is committed-and-verified only
    from paddle_tpu.distributed.checkpoint import manifest

    for name in os.listdir(ckpt):
        if name.startswith("step_"):
            ok, why = manifest.verify(os.path.join(ckpt, name), deep=True)
            assert ok, f"{name} left damaged but discoverable: {why}"


def test_chaos_faults_land_in_telemetry(tmp_path):
    """S4 of docs/OBSERVABILITY.md: with telemetry on, an injected kill
    leaves an auditable ``chaos_fault`` event in the victim's JSONL — the
    unbuffered append survives the SIGKILL that follows it — and the
    supervisor's ``worker_relaunch`` + the resumed worker's
    ``elastic_resume`` land after it, yielding the fault-vs-recovery
    timeline."""
    tdir = tmp_path / "telemetry"
    ref, _, _ = _run(tmp_path, "tel_ref")
    got, _, proc = _run(
        tmp_path, "tel",
        chaos_env={
            "PADDLE_CHAOS": "1",
            "PADDLE_CHAOS_SEED": "7",
            "PADDLE_CHAOS_KILL_STEP": "4",
            "PADDLE_TPU_TELEMETRY_DIR": str(tdir),
        })
    assert "SIGKILL" in proc.stderr
    _assert_bitwise_equal(got, ref)

    lines = (tdir / "events_rank0.jsonl").read_text().splitlines()
    evs = [json.loads(l) for l in lines if l.strip()]
    kinds = [e["kind"] for e in evs]
    fault_i = kinds.index("chaos_fault")
    relaunch_i = kinds.index("worker_relaunch")
    assert fault_i < relaunch_i, kinds
    fault = evs[fault_i]
    assert fault["fault"] == "kill_step" and fault["step"] == 4
    assert fault["attempt"] == 0
    assert evs[relaunch_i]["attempt"] == 1
    assert "elastic_resume" in kinds[relaunch_i:], kinds
    # fault accounting survives into the event stream even though the
    # process was killed before any flush could write the textfile
    assert any(e["kind"] == "chaos_fault" for e in evs)


def test_corrupt_checkpoint_never_restored(tmp_path):
    """Silent byte corruption after a commit: the next resume must reject
    the damaged checkpoint on checksum and fall back — the run still ends
    bitwise-equal because resume re-trains from the older step."""
    ref, _, _ = _run(tmp_path, "ref_c")
    got, _, proc = _run(
        tmp_path, "corrupt",
        chaos_env={
            "PADDLE_CHAOS": "1",
            "PADDLE_CHAOS_CKPT_MODE": "corrupt",
            "PADDLE_CHAOS_CKPT_STEP": "5",
            "PADDLE_CHAOS_KILL_STEP": "7",
        })
    assert "checksum mismatch" in proc.stderr
    _assert_bitwise_equal(got, ref)
