"""2-process telemetry worker for test_telemetry_fleet.py.

Launched twice by the launch CLI with PADDLE_TPU_TELEMETRY_DIR set and the
heartbeat watchdog armed: trains a tiny TrainStep (jit compile + hot
steps), saves per-rank elastic checkpoints, lets a few heartbeats land,
then runs an explicit fleet_sync so rank 0 merges both snapshots into
fleet_metrics.json — the acceptance path of docs/OBSERVABILITY.md.
"""
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
kept = [t for t in os.environ.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    kept + ["--xla_force_host_platform_device_count=1"])

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.distributed.fleet.elastic import ElasticManager  # noqa: E402
from paddle_tpu.jit import TrainStep  # noqa: E402

STEPS = 4


def main():
    ckpt_root = sys.argv[1]
    dist.init_parallel_env()  # starts the watchdog + telemetry atexit hook
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()

    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step_fn = TrainStep(model, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
    rng = np.random.default_rng(rank)
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))

    elastic = ElasticManager(os.path.join(ckpt_root, f"rank{rank}"),
                             save_interval=2, max_to_keep=2)
    start = elastic.resume(model, opt)
    for step in range(start, STEPS):
        float(step_fn(x, y))
        elastic.maybe_save(step, model, opt)
    elastic.flush()

    time.sleep(0.6)  # a few heartbeats so the age gauges are exported
    obs.fleet_sync()
    if rank == 0:
        print(json.dumps({"ok": True}), flush=True)


if __name__ == "__main__":
    main()
