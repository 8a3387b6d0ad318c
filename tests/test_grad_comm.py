"""Gradient-communication layer (distributed/grad_comm).

Three tiers, mirroring docs/GRAD_COMM.md:
  * pure-python/jax units — bucket layouts, pack/unpack round trips, wire
    quantization, the env/strategy config grammar;
  * explicit data-parallel step numerics on the 8-device CPU mesh — the
    bucketed/ZeRO exchange must reproduce the GSPMD baseline losses (f32
    bit-comparable, bf16/int8 within wire tolerance);
  * compiled-HLO attribution — comm_analysis.bucket_traffic must see the
    per-bucket collectives and the ZeRO reduce-scatter/all-gather split,
    and payload bytes must honor reduced-precision wire dtypes.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import comm_analysis as ca
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import grad_comm as gc
from paddle_tpu.distributed import mesh as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ================================================================= units ====
def test_build_buckets_order_preserving_and_size_targeted():
    assert gc.build_buckets([4, 4, 4, 4], 8) == [[0, 1], [2, 3]]
    # an oversized tensor closes the current bucket and rides alone
    assert gc.build_buckets([4, 100, 4], 8) == [[0], [1], [2]]
    assert gc.build_buckets([], 8) == []
    # everything fits: one bucket, original order
    assert gc.build_buckets([1, 2, 3], 1 << 20) == [[0, 1, 2]]


def test_make_layouts_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    leaves = [jnp.asarray(rng.standard_normal(s).astype(np.float32))
              for s in [(3, 4), (5,), (2, 2, 2)]]
    (lay,) = gc.make_layouts([l.shape for l in leaves], [4] * 3, 1 << 20)
    assert lay.total == 12 + 5 + 8 and lay.offsets == (0, 12, 17)
    flat = gc.pack_bucket(leaves, lay)
    assert flat.shape == (25,)
    out = dict(gc.unpack_bucket(flat, lay))
    for i, l in enumerate(leaves):
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(l))


def test_make_layouts_lead_dims_and_indices():
    # pipeline-stacked leaves: dim 0 (the layer dim) survives pack/unpack,
    # offsets/sizes count elements per lead-slice
    shapes = [(2, 3, 4), (2, 5)]
    (lay,) = gc.make_layouts(shapes, [4, 4], 1 << 20, lead_dims=1,
                             indices=[7, 9])
    assert lay.indices == (7, 9) and lay.sizes == (12, 5) and lay.total == 17
    rng = np.random.RandomState(1)
    leaves = {7: jnp.asarray(rng.standard_normal((2, 3, 4)).astype(np.float32)),
              9: jnp.asarray(rng.standard_normal((2, 5)).astype(np.float32))}
    flat = gc.pack_bucket(leaves, lay, lead_dims=1)
    assert flat.shape == (2, 17)
    out = dict(gc.unpack_bucket(flat, lay, lead_dims=1))
    for i in (7, 9):
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(leaves[i]))


def test_shard_layout_roundtrip():
    rng = np.random.RandomState(2)
    leaves = [jnp.asarray(rng.standard_normal(s).astype(np.float32))
              for s in [(4, 3), (8,)]]
    lay = gc.make_shard_layout([0, 1], [l.shape for l in leaves], [0, 0], 2)
    assert lay.block == (12 + 8) // 2 and lay.total == 20
    flat = gc.pack_shard_major(leaves, lay)
    # shard block s holds shard s of EVERY leaf (contiguous per rank)
    blk0 = flat[:lay.block]
    pairs = dict(gc.unpack_shard_block(blk0, lay))
    np.testing.assert_array_equal(np.asarray(pairs[0]),
                                  np.asarray(leaves[0][:2]))
    np.testing.assert_array_equal(np.asarray(pairs[1]),
                                  np.asarray(leaves[1][:4]))
    out = dict(gc.unpack_gathered(flat, lay))
    for i, l in enumerate(leaves):
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(l))
    with pytest.raises(ValueError, match="not divisible"):
        gc.make_shard_layout([0], [(5, 3)], [0], 2)


def test_quantize_roundtrip():
    v = jnp.asarray(np.random.RandomState(3).standard_normal(64).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(gc.quantize_roundtrip(v, "f32")),
                                  np.asarray(v))
    b = gc.quantize_roundtrip(v, "bf16")
    assert float(jnp.max(jnp.abs(b - v))) <= float(jnp.max(jnp.abs(v))) / 128
    q = gc.quantize_roundtrip(v, "int8")
    step = float(jnp.max(jnp.abs(v))) / 127.0
    assert float(jnp.max(jnp.abs(q - v))) <= step / 2 + 1e-7
    # all-zero input must not divide by zero
    z = gc.quantize_roundtrip(jnp.zeros(4), "int8")
    np.testing.assert_array_equal(np.asarray(z), np.zeros(4, np.float32))


def test_quantize_with_feedback_conserves_signal():
    v = jnp.asarray(np.random.RandomState(4).standard_normal(32).astype(np.float32))
    res = jnp.asarray(np.random.RandomState(5).standard_normal(32).astype(np.float32)) * 0.01
    q, new_res = gc.quantize_with_feedback(v, res, "int8")
    # sent + carried == intended: the quantization error is never dropped
    np.testing.assert_allclose(np.asarray(q + new_res), np.asarray(v + res),
                               atol=1e-6)


def test_wire_cast_quantizes_cotangent_only():
    v = jnp.asarray(np.random.RandomState(6).standard_normal(16).astype(np.float32))
    ct = jnp.asarray(np.random.RandomState(7).standard_normal(16).astype(np.float32))
    out, vjp = jax.vjp(lambda x: gc.wire_cast(x, "bf16"), v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(v))  # identity fwd
    (g,) = vjp(ct)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray(gc.quantize_roundtrip(ct, "bf16")))
    assert not np.array_equal(np.asarray(g), np.asarray(ct))


def test_psum_quantized_matches_per_contributor_quantization():
    from paddle_tpu.distributed.collective import psum_quantized

    rng = np.random.RandomState(8)
    vals = rng.standard_normal((8, 5)).astype(np.float32)
    out = jax.pmap(lambda v: psum_quantized(v, "i", "bf16"), axis_name="i")(vals)
    expected = np.asarray(
        sum(gc.quantize_roundtrip(jnp.asarray(v), "bf16") for v in vals))
    np.testing.assert_allclose(np.asarray(out[0]), expected, atol=1e-6)


# ======================================================== config grammar ====
def _cfg(monkeypatch, env):
    if env is None:
        monkeypatch.delenv("PADDLE_TPU_GRAD_COMM", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_GRAD_COMM", env)
    return gc.resolve_config(fleet.DistributedStrategy())


def test_resolve_config_defaults(monkeypatch):
    cfg = _cfg(monkeypatch, None)
    assert not cfg.enable and cfg.wire_dtype == "f32"
    # the correctness fixes default ON independently of `enable`
    assert cfg.zero_update and cfg.pipeline_batch_shard
    assert not cfg.quantized and cfg.wire_itemsize == 4


def test_resolve_config_bare_modes(monkeypatch):
    assert not _cfg(monkeypatch, "off").enable
    assert _cfg(monkeypatch, "on").enable
    cfg = _cfg(monkeypatch, "bf16")
    assert cfg.enable and cfg.wire_dtype == "bf16" and cfg.wire_itemsize == 2
    assert _cfg(monkeypatch, "int8").wire_itemsize == 1


def test_resolve_config_kv_grammar(monkeypatch):
    cfg = _cfg(monkeypatch, "wire=int8,bucket_mb=8,ef=1,zero=0,batch_shard=0")
    assert cfg.enable and cfg.wire_dtype == "int8" and cfg.bucket_mb == 8.0
    assert cfg.error_feedback and not cfg.zero_update
    assert not cfg.pipeline_batch_shard
    # bare mode tokens compose with k=v ones
    cfg = _cfg(monkeypatch, "on,bucket_mb=2")
    assert cfg.enable and cfg.bucket_mb == 2.0 and cfg.wire_dtype == "f32"


def test_resolve_config_rejects_bad_tokens(monkeypatch):
    with pytest.raises(ValueError, match="bad token"):
        _cfg(monkeypatch, "frobnicate")
    with pytest.raises(ValueError, match="unknown key"):
        _cfg(monkeypatch, "frobnicate=1")
    with pytest.raises(ValueError, match="wire"):
        _cfg(monkeypatch, "wire=f64")


def test_resolve_config_reads_strategy(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_GRAD_COMM", raising=False)
    s = fleet.DistributedStrategy()
    s.grad_comm = True
    s.grad_comm_configs["wire_dtype"] = "bf16"
    cfg = gc.resolve_config(s)
    assert cfg.enable and cfg.wire_dtype == "bf16"
    # reference knob honored as the bucket-size default
    s.fuse_grad_size_in_MB = 16
    assert gc.resolve_config(s).bucket_mb == 16.0


# ============================================= explicit DP step numerics ====
_VOCAB = 32


class _Net(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.emb = paddle.nn.Embedding(_VOCAB, 16)
        self.l1 = paddle.nn.Linear(16, 24)
        self.l2 = paddle.nn.Linear(24, 16)
        self.norm = paddle.nn.LayerNorm(16)
        self.head = paddle.nn.Linear(16, _VOCAB)

    def forward(self, ids):
        h = self.emb(ids)
        h = paddle.nn.functional.gelu(self.l1(h))
        h = self.norm(self.l2(h))
        return self.head(h)


def _loss_fn(m, ids, lbl):
    logits = m(ids)
    return paddle.nn.functional.cross_entropy(
        logits.reshape([-1, _VOCAB]), lbl.reshape([-1]))


def _run(monkeypatch, mode, dp, sh, *, steps=3, clip_norm=None):
    """Init fleet on (dp, sharding) axes, train `steps` fixed batches under
    PADDLE_TPU_GRAD_COMM=`mode`; returns (step, losses, ids)."""
    monkeypatch.setenv("PADDLE_TPU_GRAD_COMM", mode)
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=dp, mp_degree=1, pp_degree=1,
                            sharding_degree=sh)
    if sh > 1:
        s.sharding_configs.update(stage=2)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    model = _Net()
    clip = (paddle.nn.ClipGradByGlobalNorm(clip_norm)
            if clip_norm is not None else None)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters(), grad_clip=clip)
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model, _loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, _VOCAB, (16, 4)).astype(np.int32))
    losses = [float(step(ids, ids)) for _ in range(steps)]
    assert all(np.isfinite(losses))
    return step, losses, ids


_BASELINES = {}


def _baseline(monkeypatch, dp, sh, clip_norm=None):
    """GSPMD-path losses (grad_comm off), cached per mesh geometry."""
    key = (dp, sh, clip_norm)
    if key not in _BASELINES:
        step, losses, _ = _run(monkeypatch, "off", dp, sh, clip_norm=clip_norm)
        assert step._grad_comm_plan is None  # really the fallback path
        _BASELINES[key] = losses
    return _BASELINES[key]


@pytest.mark.slow
def test_explicit_f32_matches_gspmd_zero_path(monkeypatch):
    base = _baseline(monkeypatch, 4, 2)
    step, losses, ids = _run(monkeypatch, "f32", 4, 2)
    plan = step._grad_comm_plan
    assert plan is not None and len(plan.zero_layouts) >= 1
    assert plan.axes == ("dp", "sharding") and plan.nshards == 2
    np.testing.assert_allclose(losses, base, atol=1e-5, rtol=0)
    # the compiled exchange is the ZeRO decomposition: psum_scatter(grad)
    # over sharding -> psum over dp -> all_gather(updated params)
    hlo = step._compiled_for(ids, ids).as_text()
    colls = ca.collective_traffic(hlo, M.get_global_mesh())
    kinds = {(c["kind"], c["axes"]) for c in colls}
    assert ("reduce-scatter", ("sharding",)) in kinds
    assert ("all-gather", ("sharding",)) in kinds
    assert any(k == "all-reduce" and a == ("dp",) for k, a in kinds)
    bt = ca.bucket_traffic(colls)
    assert bt["n_buckets"] >= 2 and bt["per_axis"].get("sharding", 0) > 0


def test_explicit_pure_dp_tail_path_matches(monkeypatch):
    base = _baseline(monkeypatch, 8, 1)
    step, losses, _ = _run(monkeypatch, "f32", 8, 1)
    plan = step._grad_comm_plan
    assert plan is not None and not plan.zero_layouts and plan.tail_layouts
    np.testing.assert_allclose(losses, base, atol=1e-5, rtol=0)


def test_small_buckets_compile_to_separate_collectives(monkeypatch):
    # ~per-parameter buckets: the exchange must stay split in the HLO (the
    # overlap lever), and every reduction must ride only data axes
    step, losses, ids = _run(monkeypatch, "on,bucket_mb=0.001", 8, 1)
    plan = step._grad_comm_plan
    assert plan.n_buckets >= 2
    np.testing.assert_allclose(losses, _baseline(monkeypatch, 8, 1),
                               atol=1e-5, rtol=0)
    # read the split off the LOWERED module: what the framework emitted.
    # XLA:CPU's all-reduce combiner merges the buckets again in the
    # optimised HLO of this jaxlib, which says nothing about the TPU
    hlo = step._lower_for(ids, ids).as_text(dialect="hlo")
    bt = ca.bucket_traffic(ca.collective_traffic(hlo, M.get_global_mesh()))
    assert bt["n_buckets"] >= 3  # the buckets + the scalar loss reduction
    assert set(bt["per_axis"]) == {"dp"}


def test_bf16_wire_close_to_f32(monkeypatch):
    base = _baseline(monkeypatch, 4, 2)
    step, losses, _ = _run(monkeypatch, "bf16", 4, 2)
    assert step._grad_comm_plan.bytes_wire * 2 == step._grad_comm_plan.bytes_f32
    np.testing.assert_allclose(losses, base, atol=5e-3, rtol=0)


def test_int8_error_feedback_converges(monkeypatch):
    _, losses, _ = _run(monkeypatch, "wire=int8,ef=1", 8, 1, steps=4)
    assert losses[-1] < losses[0]


def test_global_norm_clip_matches_gspmd(monkeypatch):
    base = _baseline(monkeypatch, 4, 2, clip_norm=0.5)
    _, losses, _ = _run(monkeypatch, "f32", 4, 2, clip_norm=0.5)
    np.testing.assert_allclose(losses, base, atol=1e-5, rtol=0)


def test_hapi_model_comm_traffic_report(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_GRAD_COMM", "f32")
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=8)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    net = _Net()
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=net.parameters()),
        loss=paddle.nn.CrossEntropyLoss())
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, _VOCAB, (16, 4)).astype(np.int32))
    lbl = paddle.to_tensor(
        np.random.RandomState(1).randint(0, _VOCAB, (16, 4, 1)).astype(np.int64))
    report = model.comm_traffic(ids, lbl)
    assert report["grad_exchange"]["n_buckets"] >= 1
    assert report["grad_exchange"]["quantized_fraction"] == 0.0
    assert any("dp" in k for k in report["per_axis"])


# ==================================================== HLO wire attribution ==
def _dp8_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))


def _ar_line(shape):
    return (f"  %ar = {shape} all-reduce({shape} %p), "
            "replica_groups=[1,8]<=[8], to_apply=%add\n")


def test_payload_bytes_honor_wire_dtype():
    assert ca._line_payload(_ar_line("f32[1000]{0}")) == (4000, "f32")
    assert ca._line_payload(_ar_line("bf16[1000]{0}")) == (2000, "bf16")
    assert ca._line_payload(_ar_line("s8[1000]{0}")) == (1000, "s8")
    # combined (tuple-shaped) collectives sum elements
    line = ("  %ar = (bf16[100]{0}, bf16[50]{0}) all-reduce(...), "
            "replica_groups=[1,8]<=[8], to_apply=%add\n")
    assert ca._line_payload(line) == (300, "bf16")


def test_quantized_allreduce_payload_regression():
    """A reduced-precision DP gradient exchange must move < 55% of the f32
    baseline bytes (ISSUE 4 acceptance bar for the wire compression)."""
    mesh = _dp8_mesh()
    f32 = ca.bucket_traffic(ca.collective_traffic(_ar_line("f32[1000]{0}"), mesh))
    for shape, ratio in [("bf16[1000]{0}", 0.5), ("s8[1000]{0}", 0.25)]:
        q = ca.bucket_traffic(ca.collective_traffic(_ar_line(shape), mesh))
        assert q["payload_bytes"] < 0.55 * f32["payload_bytes"]
        assert q["payload_bytes_f32"] == f32["payload_bytes"]
        assert abs(q["quantized_fraction"] - (1 - ratio)) < 1e-9
    assert f32["quantized_fraction"] == 0.0


# ============================================== DP-scaling proxy (slow) =====
_SCALING_WORKER = textwrap.dedent("""\
    import json, os, sys
    sys.path.insert(0, sys.argv[2])
    os.environ["JAX_PLATFORMS"] = "cpu"
    import _cpu_mesh_flags
    n = int(sys.argv[1])
    _cpu_mesh_flags.apply(os.environ, n)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(dp_degree=n, mp_degree=1, pp_degree=1,
                            sharding_degree=1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)

    class Net(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = paddle.nn.Embedding(32, 16)
            self.l1 = paddle.nn.Linear(16, 24)
            self.head = paddle.nn.Linear(24, 32)

        def forward(self, ids):
            return self.head(paddle.nn.functional.gelu(self.l1(self.emb(ids))))

    model = Net()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)

    def loss_fn(m, ids, lbl):
        return paddle.nn.functional.cross_entropy(
            m(ids).reshape([-1, 32]), lbl.reshape([-1]))

    step = fleet.DistTrainStep(model, loss_fn, opt)
    assert step._grad_comm_plan is not None
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 32, (32, 4)).astype(np.int32))
    losses = [float(step(ids, ids)) for _ in range(3)]
    print(json.dumps(losses))
""")


@pytest.mark.slow
def test_dp_scaling_fixed_loss_across_device_counts(tmp_path):
    """Multichip DP-scaling proxy: the SAME fixed global batch trained on
    n=8 and n=16 emulated chips through the bucketed exchange must produce
    the same losses — chip count is a throughput knob, not a numerics one."""
    worker = tmp_path / "scaling_worker.py"
    worker.write_text(_SCALING_WORKER)
    out = {}
    for n in (8, 16):
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["PADDLE_TPU_GRAD_COMM"] = "f32"
        proc = subprocess.run(
            [sys.executable, str(worker), str(n), REPO],
            capture_output=True, text=True, env=env, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        out[n] = json.loads(proc.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(out[8], out[16], atol=1e-5, rtol=0)
