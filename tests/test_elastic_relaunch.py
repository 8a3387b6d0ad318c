"""Elastic supervision: the launcher watches its worker, relaunches on
failure, and training resumes from the latest checkpoint.

Reference test model: the elastic/controller tests kill worker processes
and assert the pod restarts within its retry budget
(`fleet/elastic/manager.py`, launch `controllers/`); VERDICT r2 #5's
done-criterion: kill a child mid-training and observe resume.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import json, os, sys

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.jit import TrainStep

    work = sys.argv[1]
    crash_at = int(sys.argv[2])
    total = int(sys.argv[3])
    restart = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))

    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    loss_fn = lambda m, x, y: ((m(x) - y) ** 2).mean()
    step_fn = TrainStep(model, loss_fn, opt)

    elastic = ElasticManager(os.path.join(work, "ckpt"), save_interval=2,
                             max_to_keep=5)
    start = elastic.resume(model, opt)

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))

    losses = []
    for step in range(start, total):
        losses.append(float(step_fn(x, y)))
        elastic.maybe_save(step, model, opt)
        if restart == 0 and step == crash_at:
            # simulated hard fault: no cleanup, no final checkpoint
            os._exit(17)

    with open(os.path.join(work, "done.json"), "w") as f:
        json.dump({"restart": restart, "resumed_from": start,
                   "final_loss": losses[-1]}, f)
""")


@pytest.mark.slow
def test_kill_midtraining_resumes_from_checkpoint(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "2", "--restart_backoff", "0.1",
         str(script), str(tmp_path), "7", "20"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "relaunching (1/2)" in p.stderr

    done = json.loads((tmp_path / "done.json").read_text())
    # the relaunched worker resumed from the latest checkpoint (steps 0..7
    # ran, saves at step 1,3,5,7 -> resume at 8), not from scratch
    assert done["restart"] == 1
    assert done["resumed_from"] == 8
    assert done["final_loss"] < 1.0


def test_restart_budget_exhausted_propagates_rc(tmp_path):
    script = tmp_path / "always_die.py"
    script.write_text("import os\nos._exit(9)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "1", "--restart_backoff", "0.05", str(script)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 9
    assert "budget (1) exhausted" in p.stderr


def test_operator_kill_stops_job_without_relaunch(tmp_path):
    """SIGTERM to the LAUNCHER must tear the job down (no relaunch of a
    deliberately killed worker) and exit 128+signum."""
    import signal
    import time

    script = tmp_path / "sleeper.py"
    ready = tmp_path / "ready"
    script.write_text(
        f"import time, pathlib\npathlib.Path({str(ready)!r}).touch()\n"
        "time.sleep(60)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "3", str(script)],
        env=env, stderr=subprocess.PIPE, text=True,
    )
    deadline = time.monotonic() + 90
    while not ready.exists() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert ready.exists(), "worker never spawned"
    p.send_signal(signal.SIGTERM)
    rc = p.wait(timeout=60)
    stderr = p.stderr.read()
    assert rc == 128 + signal.SIGTERM, (rc, stderr[-500:])
    assert "relaunching" not in stderr


@pytest.mark.fast
def test_clean_exit_no_restart(tmp_path):
    script = tmp_path / "ok.py"
    script.write_text("print('fine')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "3", str(script)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0
    assert "relaunching" not in p.stderr
    assert "fine" in p.stdout


@pytest.mark.fast
def test_save_with_extra_payload_roundtrips(tmp_path):
    """A snapshot saved with extra=... must stay restorable (the extra keys
    exist only on disk, not in the live tree) and hand the payload back."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.elastic import ElasticManager

    paddle.seed(0)
    model = nn.Linear(4, 3)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    mgr = ElasticManager(str(tmp_path / "ckpt"), save_interval=1)
    rng_state = np.arange(5, dtype=np.uint32)
    mgr.save(3, model, opt, extra={"rng": rng_state, "epoch": np.int64(2)})

    paddle.seed(1)
    model2 = nn.Linear(4, 3)
    opt2 = paddle.optimizer.SGD(learning_rate=0.1, parameters=model2.parameters())
    extras = {}
    nxt = ElasticManager(str(tmp_path / "ckpt")).resume(model2, opt2, extra_out=extras)
    assert nxt == 4
    np.testing.assert_array_equal(np.asarray(extras["rng"]), rng_state)
    assert int(extras["epoch"]) == 2
    np.testing.assert_allclose(
        np.asarray(model2.weight._value), np.asarray(model.weight._value))
