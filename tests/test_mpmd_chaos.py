"""Kill -9 soak for the MPMD pipeline: a worker training per-stage
programs over async boundary queues is SIGKILLed mid-tick (stage 0, some
microbatches already forwarded, unacked activations in the queues). The
relaunched worker (chaos disarmed via PADDLE_RESTART_COUNT) restores
every stage at ``latest_common_step`` from the per-stage shards, replays
the interrupted step from its first microbatch and must land on the
reference run's exact final loss and weights — a stage fault never costs
more than the uncheckpointed step.

Marked slow+chaos (boots fresh interpreters):
    pytest tests/test_mpmd_chaos.py --runslow
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

TOTAL_STEPS = 5
KILL_STEP = 2

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.environ["PT_REPO"])
    import _cpu_mesh_flags; _cpu_mesh_flags.apply(n_devices=8)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \\
        import SpmdPipeline
    from paddle_tpu.distributed.mpmd import MpmdPipeline
    from paddle_tpu.framework.op import raw

    shard_dir, out_path, total = sys.argv[1], sys.argv[2], int(sys.argv[3])
    fault_step = int(os.environ.get("SOAK_FAULT_STEP", "-1"))

    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 4, "mp_degree": 1, "pp_degree": 2}
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    blocks = [nn.Sequential(nn.Linear(16, 16), nn.Tanh())
              for _ in range(6)]
    pipe = SpmdPipeline(blocks, num_stages=2, num_microbatches=4,
                        num_virtual_stages=1, schedule="1f1b")
    paddle.seed(100)
    head = nn.Linear(16, 1)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2,
        parameters=pipe.parameters() + head.parameters())
    mp = MpmdPipeline(pipe, head=head)  # widths: PADDLE_TPU_MPMD_STAGES
    x = np.random.RandomState(0).randn(8, 16).astype("float32")

    # shards are written EXPLICITLY after opt.step() so each one holds
    # post-update params + optimizer accumulators — the ctor's shard_dir
    # auto-save would checkpoint pre-update params without opt state
    start = mp.restore_shards(shard_dir, opt) or 0
    loss = None
    for step in range(start, total):
        if step == fault_step:
            # arm mid-run: PADDLE_CHAOS_MPMD_AT indexes ops within ONE
            # step, so the fence must go live only once THIS step's tick
            # loop starts; the relaunch re-arms but chaos.armed() stays
            # False on attempt != 0, so the replay runs clean
            os.environ["PADDLE_CHAOS"] = "1"
        loss = mp.train_batch(x)
        opt.step()
        opt.clear_grad()
        mp.save_shards(shard_dir, opt)
    state = {f"w{i}": np.asarray(raw(p))
             for i, p in enumerate(mp.parameters())}
    np.savez(out_path, loss=np.float64(loss), **state)
""")


def _run(tmp_path, tag, chaos_env=None):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    shards = tmp_path / f"shards_{tag}"
    out = tmp_path / f"final_{tag}.npz"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_CHAOS", "SOAK_"))}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PT_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    env.update(chaos_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "3", "--restart_backoff", "0.1",
         "--mpmd_stages", "2,2",
         str(worker), str(shards), str(out), str(TOTAL_STEPS)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=env["PT_REPO"])
    assert proc.returncode == 0, (
        f"launch rc={proc.returncode}\nstdout:\n{proc.stdout[-2000:]}"
        f"\nstderr:\n{proc.stderr[-4000:]}")
    return np.load(out), shards, proc


def _assert_bitwise_equal(got, want):
    assert sorted(got.files) == sorted(want.files)
    for k in want.files:
        a, b = got[k], want[k]
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes(), f"state {k} differs after resume"


def test_kill_mid_tick_recovers_bit_equal(tmp_path):
    ref, _, _ = _run(tmp_path, "ref")
    got, shards, proc = _run(
        tmp_path, "kill",
        chaos_env={
            "SOAK_FAULT_STEP": str(KILL_STEP),
            "PADDLE_CHAOS_MPMD_MODE": "kill",
            "PADDLE_CHAOS_MPMD_STAGE": "0",
            # op 3 of stage 0's 1f1b tick list: two microbatches already
            # forwarded into the act queue, none of the backwards done
            "PADDLE_CHAOS_MPMD_AT": "3",
        })
    assert "SIGKILL" in proc.stderr  # the fault actually fired mid-tick
    assert "relaunching" in proc.stderr
    _assert_bitwise_equal(got, ref)
    # both stages committed shards the relaunch could agree on
    assert sorted(os.listdir(shards)) == ["stage_0", "stage_1"]


def test_boundary_latency_fault_is_survivable(tmp_path):
    """A 300 ms stall at a stage fence only slows the step down — well
    inside the queue deadline, so the run completes on attempt 0."""
    ref, _, _ = _run(tmp_path, "lat_ref")
    got, _, proc = _run(
        tmp_path, "lat",
        chaos_env={
            "SOAK_FAULT_STEP": "1",
            "PADDLE_CHAOS_MPMD_MODE": "latency",
            "PADDLE_CHAOS_MPMD_STAGE": "1",
            "PADDLE_CHAOS_MPMD_AT": "1",
            "PADDLE_CHAOS_MPMD_LATENCY_MS": "300",
        })
    assert "SIGKILL" not in proc.stderr
    _assert_bitwise_equal(got, ref)
