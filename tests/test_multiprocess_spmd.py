"""Two-process SPMD parity (VERDICT r4 #4; reference pattern:
`test_dist_base.py` localhost-subprocess training, SURVEY.md §4).

Two OS processes x 4 virtual CPU devices each form ONE 8-device mesh
through the launch CLI's rank negotiation + `jax.distributed.initialize`
(distributed/env.py), train the loss-parity tiny GPT dp2 x mp4, and the
trajectory must match the same model trained single-process on 8
devices. This exercises the REAL multi-host code path end-to-end:
TCPStore rank negotiation, the JAX coordination service, gloo-backed
cross-process CPU collectives, and multi-host array construction
(mesh.global_device_put).
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "spmd_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_pair(port, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nnodes", "2", "--master", f"127.0.0.1:{port}", WORKER]
    procs = [subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, cwd=REPO)
             for _ in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


@pytest.mark.slow
def test_two_process_spmd_matches_single_process():
    port = _free_port()
    outs = _launch_pair(port)
    for rc, out, err in outs:
        assert rc == 0, f"worker rc={rc}\nstdout:{out[-800:]}\nstderr:{err[-1500:]}"
    lines = [l for rc, out, _ in outs for l in out.splitlines()
             if l.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line: {lines}"
    losses = json.loads(lines[0])["losses"]
    assert len(losses) == 5 and all(np.isfinite(losses)), losses

    # single-process baseline: same model/data on this process's 8 devices
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=2, mp_degree=4, pp_degree=1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(1234)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(
        model, lambda m, ids, lbl: m(ids, labels=lbl), opt)
    rng = np.random.default_rng(42)
    base = []
    for _ in range(5):
        ids = paddle.to_tensor(
            rng.integers(0, 64, (8, 16)).astype(np.int32))
        base.append(float(step(ids, ids)))

    np.testing.assert_allclose(
        losses, base, rtol=5e-3, atol=1e-5,
        err_msg="2-process x 4-device trajectory diverged from "
                "single-process 8-device")
    assert losses[-1] < losses[0]
