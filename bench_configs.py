"""Secondary BASELINE benchmarks (BASELINE.md configs 1/2/4).

`bench.py` is the ERNIE fine-tune headline. This harness covers the other
workloads the north star names:

- resnet50        ResNet-50 classification images/sec, single device
                  (the vision half of the north star)
- bert_mlm_dp     BERT-base MLM pretraining step, data-parallel over all
                  visible devices (config 2)
- gpt_1p3b_dpmp   GPT-3 1.3B, dp2 x mp4 on the 8-virtual-device CPU mesh —
                  schedule sanity for the hybrid path (config 4). This one
                  is DESIGNED for the CPU mesh (`chip_smoke.py --chips 4`
                  runs the same model on four real chips).

Each config runs in its own subprocess, one after another; this parent
never touches JAX, so each child gets the chip. A "tpu" config fails
without a TPU (no CPU fallback, no shrinking); the "cpu_mesh" configs pin
the virtual CPU mesh themselves. Each child prints one JSON line naming
its device; a failed config makes the exit code non-zero.

Usage: python bench_configs.py [config ...]   (default: all)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def _sync(x):
    """Fence on the step's loss and read it back."""
    import jax

    return float(jax.block_until_ready(x._value))


def _timed_steps(step_fn, args, warmup, iters):
    """Returns (sec/step, final_loss, compile_s). compile_s is the fenced
    first call (compile + first step), measured only when this call
    performs the warmup — with warmup=0 the caller already compiled and
    ran the first step itself (compile_s is None; no extra step runs)."""
    compile_s = None
    if warmup >= 1:
        t0 = time.perf_counter()
        out = step_fn(*args)
        _sync(out)
        compile_s = time.perf_counter() - t0
        for _ in range(warmup - 1):
            out = step_fn(*args)
        _sync(out)  # fence warmup so the timed loop starts clean
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step_fn(*args)
    final = _sync(out)
    return (time.perf_counter() - t0) / iters, final, compile_s


def _peak_bf16():
    import jax

    import bench

    return bench._peak_flops(jax.devices()[0].device_kind)


# --------------------------------------------------------------------------
# config bodies (run inside the child subprocess)
# --------------------------------------------------------------------------
def run_resnet50():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps, warmup = 20, 3

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
        weight_decay=1e-4, multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(model, lambda m, x, y: paddle.nn.functional.cross_entropy(m(x), y), opt)

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((batch, 3, 224, 224)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)).astype(np.int32))

    def one():
        with amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            return step(x, y)

    dt, loss, compile_s = _timed_steps(one, (), warmup, steps)
    with amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
        flops = float(step.cost_analysis(x, y)["flops"])
    mfu = flops / dt / _peak_bf16()
    return {
        "metric": "resnet50 images/sec (O2 bf16, 224x224, fwd+bwd+momentum)",
        "value": round(batch / dt, 1), "unit": "images/s",
        "step_time_ms": round(dt * 1e3, 2), "batch": batch,
        "compile_s": round(compile_s, 1),
        "mfu": round(mfu, 4), "loss": round(loss, 4),
    }


def run_bert_mlm_dp():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import BertConfig, BertForMaskedLM

    import jax

    ndev = len(jax.devices())
    per_dev = int(os.environ.get("BENCH_BATCH", "64"))
    batch, seq = per_dev * ndev, 128
    steps, warmup = 20, 3

    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=ndev, mp_degree=1, pp_degree=1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    cfg = BertConfig(
        vocab_size=30592, hidden_size=768, num_hidden_layers=12,
        num_attention_heads=12, intermediate_size=3072,
        max_position_embeddings=512,
        hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.0)
    model = BertForMaskedLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl), opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 30000, (batch, seq)).astype(np.int32))
    # MLM: 15% positions carry labels, rest ignore_index
    lbl = np.where(rng.random((batch, seq)) < 0.15,
                   rng.integers(0, 30000, (batch, seq)), -100).astype(np.int32)
    lbl = paddle.to_tensor(lbl)

    def one():
        with amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            return step(ids, lbl)

    dt, loss, compile_s = _timed_steps(one, (), warmup, steps)
    return {
        "metric": f"bert-base MLM tokens/sec (O2 bf16, seq128, dp{ndev})",
        "value": round(batch * seq / dt, 1), "unit": "tokens/s",
        "step_time_ms": round(dt * 1e3, 2), "global_batch": batch,
        "compile_s": round(compile_s, 1),
        "dp_degree": ndev, "loss": round(loss, 4),
    }


def run_gpt_1p3b_dpmp():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    import jax

    assert len(jax.devices()) >= 8, "needs the 8-virtual-device CPU mesh"
    batch, seq = 8, 128

    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=2, mp_degree=4, pp_degree=1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    cfg = GPTConfig.gpt3_1p3b(
        vocab_size=50304, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, fold_layers=True)
    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=2e-4, parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl), opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 50000, (batch, seq)).astype(np.int32))

    t0 = time.perf_counter()
    loss0 = _sync(step(ids, ids))
    compile_s = time.perf_counter() - t0
    dt, loss, _ = _timed_steps(step, (ids, ids), 0, 1)
    return {
        "metric": "gpt3-1.3B dp2xmp4 step time (schedule sanity, CPU mesh)",
        "value": round(dt * 1e3, 1), "unit": "ms/step",
        "n_params": n_params, "batch": batch, "seq": seq,
        "compile_s": round(compile_s, 1),
        "loss_first": round(loss0, 4), "loss_second": round(loss, 4),
        "sanity": bool(np.isfinite(loss) and loss != loss0),
    }


def run_gpt_6p7b_ppsharding():
    """BASELINE config 5: GPT-3 6.7B, pipeline x ZeRO sharding, CPU-mesh
    schedule sanity. bf16 parameters/optimizer-state (the TPU-idiomatic
    large-model configuration) so the host copy of every virtual-device
    shard fits in RAM; one step, tiny batch — this validates the pp x
    sharding program, not throughput.

    NOTE: the full 32-layer run is OOM-killed on this box (round 4,
    125GB host RAM: 8 emulated devices each hold their own buffer copies,
    so the one-host footprint is ~8x a real per-chip footprint) —
    BENCH_67B_LAYERS shrinks the stack while keeping the true 6.7B layer
    geometry (hidden 4096, 32 heads, ffn 16384). The committed artifact
    uses 16 layers (3.4B params, ~117GB peak); gpt_6p7b_ppsharding_lite
    records the 8-layer variant."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    import jax

    assert len(jax.devices()) >= 8, "needs the 8-virtual-device CPU mesh"
    batch, seq = 2, 64

    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=1, mp_degree=1, pp_degree=2)
    s.hybrid_configs["sharding_degree"] = 4
    # ZeRO-3 + block recompute: the r4 stage-1/no-remat configuration
    # measured 15.88 GiB per device — over v5e's 16 GiB; stage 3 shards
    # the bf16 params over the sharding axis (GroupSharded "p_g_os"
    # semantics) and remat drops block activations, landing the same 16L
    # geometry at ~6.5 GiB (tests/test_memory_analysis.py pins <= 14 GiB)
    s.sharding_configs["stage"] = 3
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    # default 16: the full 32-layer stack is OOM-killed on this box (see
    # docstring); set BENCH_67B_LAYERS=32 on a host with >250GB RAM
    layers = int(os.environ.get("BENCH_67B_LAYERS", "16"))
    cfg = GPTConfig.gpt3_6p7b(
        vocab_size=50304, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, num_hidden_layers=layers,
        use_recompute=True)
    model = GPTForCausalLM(cfg).bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl), opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 50000, (batch, seq)).astype(np.int32))
    t0 = time.perf_counter()
    loss0 = _sync(step(ids, ids))
    compile_s = time.perf_counter() - t0
    # second step: the done-criterion is a finite DECREASING loss
    dt, loss1, _ = _timed_steps(step, (ids, ids), 0, 1)
    mem = step.memory_analysis(ids, ids)
    return {
        "metric": (
            f"gpt3-6.7B-geometry ({layers}L) pp2xsharding4 "
            "(schedule sanity, CPU mesh)"),
        "value": round(compile_s, 1), "unit": "s (compile+first step)",
        "step_time_ms": round(dt * 1e3, 1),
        "n_params": n_params, "batch": batch, "seq": seq,
        "num_layers": layers,
        "loss_first": round(loss0, 4), "loss_second": round(loss1, 4),
        "per_device_live_bytes": mem.get("live_size_in_bytes"),
        "sanity": bool(np.isfinite(loss0) and np.isfinite(loss1)
                       and loss1 < loss0),
    }


def run_gpt_6p7b_ppsharding_lite():
    os.environ.setdefault("BENCH_67B_LAYERS", "8")
    return run_gpt_6p7b_ppsharding()


def _run_gpt_singlechip(metric_name, env_prefix, cfg_factory,
                        default_batch):
    """Shared single-chip GPT trainer bench: fwd+bwd+AdamW as one program,
    bf16 params AND bf16 Adam moments, block recompute, tok/s + analytic
    model-flops MFU. Env knobs (per config):
    {PREFIX}_LAYERS / {PREFIX}_SEQ / {PREFIX}_RECOMPUTE
    ("full"/"full_attn"/"core_attn"/"none") / {PREFIX}_BATCH (falls back
    to the shared BENCH_BATCH)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.text.models import GPTForCausalLM

    # per-config knobs only; the ONE shared fallback is BENCH_BATCH (a
    # global BENCH_LAYERS/SEQ/RECOMPUTE leaking into every config would
    # silently change which geometry a named bench measures)
    e = lambda k, d: os.environ.get(f"{env_prefix}_{k}", d)
    layers = int(e("LAYERS", "24"))
    batch = int(e("BATCH", os.environ.get("BENCH_BATCH", default_batch)))
    seq = int(e("SEQ", "1024"))
    granularity = e("RECOMPUTE", "full")
    steps, warmup = 20, 3

    paddle.seed(0)
    cfg_kw = dict(
        num_hidden_layers=layers,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        fold_layers=True, use_recompute=granularity != "none",
        recompute_granularity=(granularity if granularity != "none"
                               else "full"))
    # the factory owns max_position_embeddings (the named geometries say
    # 2048); only grow it when the benched sequence wouldn't fit
    cfg = cfg_factory(**cfg_kw)
    if seq > cfg.max_position_embeddings:
        cfg = cfg_factory(max_position_embeddings=seq, **cfg_kw)
    model = GPTForCausalLM(cfg).bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=2e-4,
                                 parameters=model.parameters())
    step = TrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl), opt)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50000, (batch, seq + 1)).astype(np.int32)
    ids = paddle.to_tensor(tokens[:, :-1])
    lbl = paddle.to_tensor(tokens[:, 1:])

    dt, loss, compile_s = _timed_steps(step, (ids, lbl), warmup, steps)
    # Analytic model flops: XLA cost_analysis counts a lax.scan body ONCE,
    # so a folded+remat'd stack under-reports by ~L x. Standard accounting
    # (6N per token fwd+bwd, + the causal-attention quadratic term); remat
    # recompute is intentionally NOT credited (model-flops MFU convention).
    h, L = cfg.hidden_size, layers
    tokens_per_step = batch * seq
    flops = (6.0 * n_params * tokens_per_step
             + 12.0 * L * h * seq * tokens_per_step)
    mfu = flops / dt / _peak_bf16()
    mem = step.memory_analysis(ids, lbl)["live_size_in_bytes"]
    return {
        "metric": (f"{metric_name} ({layers}L) single-chip tokens/s "
                   "(bf16 params+moments, remat, fwd+bwd+AdamW)"),
        "value": round(batch * seq / dt, 1), "unit": "tokens/s",
        "step_time_ms": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "n_params": n_params, "batch": batch, "seq": seq,
        "num_layers": layers, "recompute": granularity,
        "mfu": round(mfu, 4),
        "per_device_live_bytes": mem,
        "loss": round(loss, 4),
        "sanity": bool(np.isfinite(loss)),
    }


def run_gpt_760m_singlechip():
    """A real GPT geometry on ONE chip.
    GPT-760M (hidden 1536, 24L, 16 heads): ~1.5 GiB bf16 params + ~3 GiB
    bf16 moments + remat'd activations fits a 16 GiB v5e with room for
    the seq-1024 batch."""
    from paddle_tpu.text.models import GPTConfig

    def factory(**kw):
        return GPTConfig(vocab_size=50304, hidden_size=1536,
                         num_attention_heads=16, intermediate_size=6144,
                         **kw)

    return _run_gpt_singlechip("gpt-760M-geometry", "BENCH_760M",
                               factory, "8")


def run_gpt_1p3b_singlechip():
    """The full GPT-3 1.3B geometry (BASELINE config 4's model) on ONE
    chip: bf16 params (~2.6 GiB) + bf16 Adam moments (~5.2 GiB) + full
    block recompute leaves headroom for seq-1024 activations on a 16 GiB
    v5e. Complements the CPU-mesh dp2xmp4 schedule sanity."""
    from paddle_tpu.text.models import GPTConfig

    def factory(**kw):
        return GPTConfig.gpt3_1p3b(vocab_size=50304, **kw)

    return _run_gpt_singlechip("gpt3-1.3B", "BENCH_1P3B", factory, "4")


CONFIGS = {
    "resnet50": (run_resnet50, "tpu"),
    "bert_mlm_dp": (run_bert_mlm_dp, "tpu"),
    "gpt_1p3b_dpmp": (run_gpt_1p3b_dpmp, "cpu_mesh"),
    "gpt_6p7b_ppsharding": (run_gpt_6p7b_ppsharding, "cpu_mesh"),
    "gpt_6p7b_ppsharding_lite": (run_gpt_6p7b_ppsharding_lite, "cpu_mesh"),
    "gpt_760m_singlechip": (run_gpt_760m_singlechip, "tpu"),
    "gpt_1p3b_singlechip": (run_gpt_1p3b_singlechip, "tpu"),
}


# --------------------------------------------------------------------------
# parent: one child per config, strictly one after another
# --------------------------------------------------------------------------
def _child_env(kind):
    env = dict(os.environ)
    if kind == "cpu_mesh":
        env["JAX_PLATFORMS"] = "cpu"
        import _cpu_mesh_flags

        _cpu_mesh_flags.apply(env)
    return env


def main():
    names = sys.argv[1:] or list(CONFIGS)
    failed = []
    for name in names:
        env = _child_env(CONFIGS[name][1])
        env["BENCH_CONFIG_CHILD"] = name
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=int(os.environ.get("BENCH_CONFIG_TIMEOUT", "3000")),
        ).returncode
        if rc:
            failed.append(name)
    if failed:
        sys.exit(f"bench_configs: failed: {', '.join(failed)}")


def _child(name):
    import jax

    from paddle_tpu.runtime import jax_cache

    fn, kind = CONFIGS[name]
    jax_cache.configure()
    d = jax.devices()[0]
    if kind == "tpu" and d.platform != "tpu":
        sys.exit(f"bench_configs {name}: measures a TPU; jax found "
                 f"{d.platform!r}. Nothing was measured.")
    entry = fn()
    entry.update(config=name, device={
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())})
    print(json.dumps(entry), flush=True)


if __name__ == "__main__":
    name = os.environ.pop("BENCH_CONFIG_CHILD", None)
    if name:
        _child(name)
    else:
        main()
