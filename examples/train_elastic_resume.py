"""Fault-tolerant training: kill mid-run, relaunch, resume from checkpoint.

The launch CLI supervises the worker (bounded-retry relaunch on nonzero
exit — the reference's elastic controllers' watch loop); the worker's
ElasticManager checkpoints model+optimizer every N steps with orbax and
resumes from the newest complete checkpoint. This script demonstrates
the WHOLE cycle in one process tree: the chaos harness
(paddle_tpu.testing.chaos, armed via PADDLE_CHAOS_KILL_STEP) SIGKILLs the
first worker attempt at step 7; the supervisor relaunches; the second
attempt (chaos disarms itself on PADDLE_RESTART_COUNT>0) resumes from the
last committed checkpoint and finishes. See docs/FAULT_TOLERANCE.md.

Run:  JAX_PLATFORMS=cpu python examples/train_elastic_resume.py
"""
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORKER = r'''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)

import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.elastic import ElasticManager
from paddle_tpu.jit import TrainStep
from paddle_tpu.testing import chaos

work = sys.argv[1]
restart = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))

paddle.seed(0)
model = paddle.nn.Linear(4, 4)
opt = paddle.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
step_fn = TrainStep(model, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)

elastic = ElasticManager(os.path.join(work, "ckpt"), save_interval=2)
start = elastic.resume(model, opt)  # 0 on the fresh attempt
print(f"[worker attempt {restart}] resuming from step {start}", flush=True)

rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))
y = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))

for step in range(start, 15):
    chaos.step_fence(step)  # SIGKILL here on attempt 0 (PADDLE_CHAOS_KILL_STEP)
    loss = float(step_fn(x, y))
    elastic.maybe_save(step, model, opt)

with open(os.path.join(work, "done.json"), "w") as f:
    json.dump({"attempt": restart, "resumed_from": start,
               "final_loss": loss}, f)
print(f"[worker attempt {restart}] finished; loss={loss:.5f}", flush=True)
'''


def main():
    work = tempfile.mkdtemp(prefix="elastic_demo_")
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(WORKER % {"repo": REPO})

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # arm the chaos harness: kill -9 the worker at step 7, first attempt only
    env["PADDLE_CHAOS"] = "1"
    env["PADDLE_CHAOS_KILL_STEP"] = "7"
    # the launch CLI supervises: SIGKILL -> nonzero rc -> relaunch, budget 2
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--max_restarts", "2", "--restart_backoff", "0.2",
           script, work]
    print("launching:", " ".join(cmd))
    rc = subprocess.call(cmd, env=env, cwd=REPO)
    assert rc == 0, f"supervised job failed rc={rc}"

    with open(os.path.join(work, "done.json")) as f:
        done = json.load(f)
    print("result:", done)
    assert done["attempt"] == 1, "should have finished on the relaunch"
    assert done["resumed_from"] > 0, "should have resumed from a checkpoint"
    print("kill-and-resume cycle complete: attempt 1 resumed from step",
          done["resumed_from"])


if __name__ == "__main__":
    main()
