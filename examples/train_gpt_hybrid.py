"""End-to-end hybrid-parallel GPT training on a device mesh.

Run on the 8-virtual-device CPU mesh (no TPU needed):

    JAX_PLATFORMS=cpu python examples/train_gpt_hybrid.py

On a real TPU slice, drop the env var — the same script uses every chip
jax can see. The parallel plan (dp x mp x pp, plus ZeRO optimizer-state
sharding when the device count allows) is data-size agnostic: fleet
places parameters/optimizer state, DistTrainStep compiles ONE SPMD
program per batch signature and XLA inserts all collectives.
"""
import os
import sys

# runnable straight from the repo checkout, no install needed
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # emulated-mesh preamble: pin the cpu backend BEFORE jax backend init
    # and apply the shared flags (8 virtual devices + the XLA CPU
    # collective-watchdog relaxation) — see _cpu_mesh_flags.py
    import _cpu_mesh_flags

    _cpu_mesh_flags.apply()
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM


def main():
    import jax

    n = len(jax.devices())
    mp = 2 if n % 2 == 0 else 1
    pp = 2 if (n // mp) % 2 == 0 else 1
    sharding = 2 if (n // (mp * pp)) % 2 == 0 else 1  # ZeRO optimizer states
    dp = n // (mp * pp * sharding)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs.update(dp_degree=dp, mp_degree=mp, pp_degree=pp)
    strategy.hybrid_configs["sharding_degree"] = sharding
    fleet.init(is_collective=True, strategy=strategy)
    print(f"mesh: dp={dp} mp={mp} pp={pp} sharding={sharding} "
          f"over {n} devices")

    paddle.seed(0)
    cfg = GPTConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=4, max_position_embeddings=256,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        sequence_parallel=mp > 1)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl),
                               opt)

    rng = np.random.default_rng(0)
    # batch must divide evenly over the data axes (dp x sharding)
    d = dp * sharding
    batch, seq = d * max(4, 8 // d), 65
    for it in range(10):
        tokens = rng.integers(0, 512, (batch, seq)).astype(np.int32)
        # next-token objective: inputs see tokens[:-1], labels are the
        # SHIFTED tokens[1:] (causal LM; unshifted labels would train an
        # identity copy)
        ids = paddle.to_tensor(tokens[:, :-1])
        labels = paddle.to_tensor(tokens[:, 1:])
        loss = step(ids, labels)
        print(f"step {it}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
