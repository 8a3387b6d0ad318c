"""Multi-process SPMD worker for test_multiprocess_spmd.py.

Launched twice (2 OS processes x 4 virtual CPU devices each) by the
launch CLI; trains the loss-parity tiny GPT over the resulting 8-device
global mesh and prints the loss trajectory as one JSON line from
process 0. Mirrors the reference's `test_dist_base.py` worker half
(same-seeded model + data on every rank).
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
kept = [t for t in os.environ.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    kept + ["--xla_force_host_platform_device_count=4"])

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM  # noqa: E402

STEPS, BATCH, SEQ, VOCAB = 5, 8, 16, 64


def main():
    dist.init_parallel_env()  # bootstraps jax.distributed from PADDLE_* env
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=2, mp_degree=4, pp_degree=1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(1234)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl),
                               opt)
    rng = np.random.default_rng(42)
    losses = []
    for _ in range(STEPS):
        ids = paddle.to_tensor(
            rng.integers(0, VOCAB, (BATCH, SEQ)).astype(np.int32))
        losses.append(float(step(ids, ids)))
    if jax.process_index() == 0:
        print(json.dumps({"losses": losses}), flush=True)


if __name__ == "__main__":
    main()
