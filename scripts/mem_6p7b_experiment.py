"""Memory-headroom experiment for the 6.7B-geometry pp2xsharding4 config
(VERDICT r4 #3): measure per-device live bytes for combinations of
{ZeRO stage 1 vs 3} x {recompute on/off} via compile-only memory_analysis.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python scripts/mem_6p7b_experiment.py [stage] [recompute]
Prints one JSON line per variant.
"""
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
_kept = [t for t in os.environ.get("XLA_FLAGS", "").split()
         if not t.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    _kept + ["--xla_force_host_platform_device_count=8"])
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# NOTE: intentionally mirrors bench_configs.run_gpt_6p7b_ppsharding (same
# strategy/config/step construction) with stage/recompute as parameters and
# a compile-only measurement — keep the two in sync when the shared setup
# changes. Honors BENCH_67B_LAYERS like the bench harness.
def run(stage: int, recompute: bool, layers: int = 16):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    batch, seq = 2, 64
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=1, mp_degree=1, pp_degree=2)
    s.hybrid_configs["sharding_degree"] = 4
    s.sharding_configs["stage"] = stage
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    cfg = GPTConfig.gpt3_6p7b(
        vocab_size=50304, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, num_hidden_layers=layers,
        use_recompute=recompute)
    model = GPTForCausalLM(cfg).bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl),
                               opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, 50000, (batch, seq)).astype(np.int32))
    t0 = time.perf_counter()
    mem = step.memory_analysis(ids, ids)
    compile_s = time.perf_counter() - t0
    out = {"stage": stage, "recompute": recompute, "layers": layers,
           "compile_s": round(compile_s, 1),
           "live_gib": round(mem["live_size_in_bytes"] / 2**30, 3)}
    out.update(mem)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    stage = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    rec = (sys.argv[2].lower() in ("1", "true", "yes")) \
        if len(sys.argv) > 2 else True
    layers = int(sys.argv[3]) if len(sys.argv) > 3 else int(
        os.environ.get("BENCH_67B_LAYERS", "16"))
    run(stage, rec, layers)
