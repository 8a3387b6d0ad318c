"""Kill -9 soak for the live-resize path: a worker training on a dp2xmp2
mesh shrinks itself to a 2-device dp mesh mid-run via
ElasticManager.live_resize; the chaos harness SIGKILLs it at a
mid-reshard leaf fence on the first attempt. The relaunched worker
(chaos disarmed) must resume from the newest VERIFIED checkpoint, redo
the resize cleanly and land on the reference run's exact final weights —
a fault mid-reshard never costs more than the uncheckpointed steps.

Marked slow+chaos (boots fresh interpreters):
    pytest tests/test_reshard_chaos.py --runslow
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

TOTAL_STEPS = 12
RESHARD_STEP = 6

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.environ["PT_REPO"])
    import _cpu_mesh_flags; _cpu_mesh_flags.apply(n_devices=8)
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.framework.op import raw
    from paddle_tpu.jit import TrainStep

    ckpt_dir, out_path, total = sys.argv[1], sys.argv[2], int(sys.argv[3])
    RESHARD = int(sys.argv[4])
    DEVS = np.array(jax.devices())
    MESH_A = Mesh(DEVS[:4].reshape(2, 2), ("dp", "mp"))
    MESH_B = Mesh(DEVS[:2].reshape(2), ("dp",))

    def build(mesh, wspec):
        paddle.seed(0)
        m = nn.Linear(16, 16)
        for _, p in m.named_parameters():
            v = raw(p)
            s = wspec if v.ndim == 2 else P(wspec[-1])
            p._rebind(jax.device_put(v, NamedSharding(mesh, s)))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=m.parameters())
        return m, opt

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 16)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((8, 16)).astype("float32"))
    loss_fn = lambda m, a, b: ((m(a) - b) ** 2).mean()

    model, opt = build(MESH_A, P("dp", "mp"))
    elastic = ElasticManager(ckpt_dir, save_interval=2, max_to_keep=2)
    start = elastic.resume(model, opt)
    # the kill fires at the RESHARD step before any save could outrun it,
    # so a relaunch always lands back in the phase-A range
    assert start <= RESHARD, f"resumed at {start}, past the resize point"
    step_fn = TrainStep(model, loss_fn, opt)
    for step in range(start, total):
        if step == RESHARD:
            # live shrink n=4 -> n=2: no disk in the happy path; chaos
            # fences fire inside reshard_state at every leaf barrier
            src = elastic.capture(model, opt)
            model, opt = build(MESH_B, P("dp"))
            nxt = elastic.live_resize(step - 1, src, model, opt)
            assert nxt == step, (nxt, step)
            step_fn = TrainStep(model, loss_fn, opt)
        float(step_fn(x, y))
        elastic.maybe_save(step, model, opt)
    elastic.flush()
    np.savez(out_path, **{k: np.asarray(v.numpy())
                          for k, v in model.state_dict().items()})
""")


def _run(tmp_path, tag, chaos_env=None):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    ckpt = tmp_path / f"ckpt_{tag}"
    out = tmp_path / f"final_{tag}.npz"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_CHAOS")}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PT_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    env.update(chaos_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restarts", "3", "--restart_backoff", "0.1",
         str(worker), str(ckpt), str(out), str(TOTAL_STEPS),
         str(RESHARD_STEP)],
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


@pytest.mark.parametrize("fence", [0, 2])
def test_kill_mid_reshard_recovers_bitwise(tmp_path, fence):
    ref, _, _ = _run(tmp_path, f"ref{fence}")
    got, ckpt, proc = _run(
        tmp_path, f"kill{fence}",
        chaos_env={
            "PADDLE_CHAOS": "1",
            "PADDLE_CHAOS_RESHARD_MODE": "kill",
            "PADDLE_CHAOS_RESHARD_AT": str(fence),
        })
    assert "SIGKILL" in proc.stderr  # the fault actually fired mid-reshard
    assert "relaunching" in proc.stderr
    _assert_bitwise_equal(got, ref)
    # nothing half-resharded was ever committed: every surviving
    # checkpoint verifies
    from paddle_tpu.distributed.checkpoint import manifest

    steps = [n for n in os.listdir(ckpt) if n.startswith("step_")]
    assert steps, "no checkpoint survived the kill"
    for name in steps:
        ok, why = manifest.verify(os.path.join(ckpt, name), deep=True)
        assert ok, f"{name} damaged but discoverable: {why}"


def test_reshard_latency_fault_is_survivable(tmp_path):
    """An injected mid-reshard stall shorter than the deadline only slows
    the resize down — the run completes on attempt 0, bitwise equal."""
    ref, _, _ = _run(tmp_path, "lat_ref")
    got, _, proc = _run(
        tmp_path, "lat",
        chaos_env={
            "PADDLE_CHAOS": "1",
            "PADDLE_CHAOS_RESHARD_MODE": "latency",
            "PADDLE_CHAOS_RESHARD_AT": "1",
            "PADDLE_CHAOS_RESHARD_LATENCY_MS": "300",
        })
    assert "SIGKILL" not in proc.stderr
    _assert_bitwise_equal(got, ref)
