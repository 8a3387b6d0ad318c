"""Tensor-parallel (mp-sharded) decode engine (docs/SERVING.md).

Gates the sharded-serving promises: a dp1 x mp2 engine — paged KV pools
split over kv heads under GSPMD, attention output replicated by an exact
all-gather — produces BIT-EQUAL token streams to the single-device
engine with prefix caching and speculation on, while compiling exactly
the same ``buckets_used + 2`` programs (sharding must not add recompile
churn), and an mp degree that does not divide the kv heads is rejected
loudly at construction.
"""
import jax
import numpy as np
import pytest

import paddle_tpu.inference as inference
from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                         SamplingParams)
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

VOCAB = 61


@pytest.fixture(scope="module")
def model():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    # shield the model build from any hybrid-parallel group / global mesh
    # a fleet test left behind (same idiom as test_decode_engine)
    prev = get_hybrid_communicate_group()
    prev_mesh = _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    try:
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        m.eval()
        yield m
        inference.disable_decode_engine(m)
    finally:
        set_hybrid_communicate_group(prev)
        _mesh.set_global_mesh(prev_mesh)


def _mp_mesh(mp):
    from paddle_tpu.distributed.mesh import build_mesh

    return build_mesh((1, mp), ("dp", "mp"), devices=jax.devices()[:mp])


def _workload():
    """Mixed greedy/sampled requests sharing a 32-token prefix (2 full
    pages) so the prefix cache AND both samplers are exercised."""
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, VOCAB, size=32, dtype=np.int64)
    reqs = []
    for i, tail in enumerate((9, 17, 5)):
        prompt = np.concatenate(
            [prefix, rng.integers(1, VOCAB, size=tail, dtype=np.int64)])
        reqs.append((prompt, SamplingParams(
            max_new_tokens=10, do_sample=(i % 2 == 1), temperature=0.8,
            top_k=8, seed=100 + i)))
    return reqs


def _drain(eng, reqs):
    rids = [eng.submit(p, params) for p, params in reqs]
    eng.run()
    return [eng.result(r) for r in rids]


CFG = dict(num_slots=2, max_length=64, page_size=16, prefix_cache=True,
           speculate_k=2, spec_adaptive=False)


@pytest.mark.slow
def test_mp2_bit_equal_with_prefix_and_speculation(model):
    reqs = _workload()
    ref = DecodeEngine(model, EngineConfig(**CFG))
    want = _drain(ref, reqs)

    eng = DecodeEngine(model, EngineConfig(**CFG, mesh=_mp_mesh(2)))
    got = _drain(eng, reqs)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)

    # sharding must not change WHAT compiles: same program set, exactly
    # len(buckets used) + decode + verify on both engines
    assert eng.stats()["compiled"] == ref.stats()["compiled"]
    buckets_used = sum(1 for name in eng.stats()["compiled"]
                      if name.startswith("prefill_"))
    assert eng.compile_count == buckets_used + 2

    # the KV pool really is split over the mp axis
    from paddle_tpu.distributed.mesh import P
    assert all(a.sharding.spec == P(None, None, "mp")
               for a in jax.tree.leaves(eng.kv))
    assert eng._mp_degree == 2

    # prefix sharing survived sharding (2 full pages of shared prefix,
    # second+third request each reuse them)
    assert eng.stats()["prefix_hit_tokens"] == ref.stats()["prefix_hit_tokens"]
    assert eng.stats()["prefix_hit_tokens"] >= 32


def test_mp_must_divide_kv_heads(model):
    # 4 kv heads cannot split 8 ways: loud ValueError at construction,
    # not a silent wrong-shard layout
    with pytest.raises(ValueError, match="divide"):
        DecodeEngine(model, EngineConfig(
            num_slots=2, max_length=64, mesh=_mp_mesh(8)))


@pytest.fixture(scope="module")
def model64():
    """Vocab-64 twin of ``model``: the quantized logit recombination needs
    vocab divisible by the mp degree (61 deliberately is not)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    prev = get_hybrid_communicate_group()
    prev_mesh = _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    try:
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        m.eval()
        yield m
        inference.disable_decode_engine(m)
    finally:
        set_hybrid_communicate_group(prev)
        _mesh.set_global_mesh(prev_mesh)


def _workload64():
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, 64, size=32, dtype=np.int64)
    reqs = []
    for i, tail in enumerate((9, 17, 5)):
        prompt = np.concatenate(
            [prefix, rng.integers(1, 64, size=tail, dtype=np.int64)])
        reqs.append((prompt, SamplingParams(
            max_new_tokens=10, do_sample=(i % 2 == 1), temperature=0.8,
            top_k=8, seed=100 + i)))
    return reqs


def test_logit_wire_config_resolution(model64, monkeypatch):
    # pinned "off" and "f32" both mean the exact-path program
    eng = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2),
                                             logit_wire="off"))
    assert eng._logit_wire == "f32"
    # explicit int8 sticks; without an mp axis the wire is forced exact
    eng2 = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2),
                                              logit_wire="int8"))
    assert eng2._logit_wire == "int8" and eng2._logit_verify
    single = DecodeEngine(model64, EngineConfig(**CFG, logit_wire="int8"))
    assert single._logit_wire == "f32"
    # None resolves from the ambient mp_comm config (env grammar)
    monkeypatch.setenv("PADDLE_TPU_MP_COMM", "int8,verify=off")
    amb = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2)))
    assert amb._logit_wire == "int8" and not amb._logit_verify
    with pytest.raises(ValueError, match="logit_wire"):
        DecodeEngine(model64, EngineConfig(**CFG, logit_wire="fp8"))


def test_mp2_sampler_bisects_with_no_collective_a_step(model64):
    """The vocab-sharded logits are gathered once before ``sample``: its
    top-k and top-p bisections (a ``while`` each) reduce every row on one
    shard, with no all-reduce in their loops (a sort paid the same one
    gather)."""
    import re

    eng = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2)))
    with eng._mesh_ctx():
        text = eng._jitted("decode").lower(
            *eng._example_args("decode")).compile().as_text()
    bodies = {}
    for comp in re.split(r"\n(?=\S)", text):
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", comp)
        bodies[name.group(1) if name else ""] = comp

    def reached(name, seen):
        if name in bodies and name not in seen:
            seen.add(name)
            for callee in re.findall(r"%([\w.\-]+)", bodies[name]):
                if callee in bodies:
                    reached(callee, seen)
        return seen

    loops = re.findall(r" while\([^\n]*body=%?([\w.\-]+)", text)
    assert len(loops) >= 2  # the two bisections, at the least
    coll = re.compile(r" (all-reduce|all-gather|all-to-all|reduce-scatter|"
                      r"collective-permute)(-start)?\(")
    inside = [c for b in loops for c in reached(b, set())
              if coll.search(bodies[c])]
    assert re.search(coll, text) and inside == []


@pytest.mark.slow
def test_mp2_int8_logit_wire_bit_equal(model64, monkeypatch, tmp_path):
    """ISSUE 13: int8 absmax logit recombination + exact-argmax verify
    keeps the mp-sharded engine greedy BIT-EQUAL to the single-device
    engine (the PR 9 contract), and the wire gauge is recorded."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    from paddle_tpu import observability as _obs

    _obs.reset()
    reqs = _workload64()
    ref = DecodeEngine(model64, EngineConfig(**CFG))
    want = _drain(ref, reqs)

    eng = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2),
                                             logit_wire="int8"))
    got = _drain(eng, reqs)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert _obs.gauge("serving_logit_wire_bytes").value() > 0

    # mp_comm=off restores the exact program byte-for-byte
    off = DecodeEngine(model64, EngineConfig(**CFG, mesh=_mp_mesh(2),
                                             logit_wire="off"))
    got_off = _drain(off, reqs)
    for w, g in zip(want, got_off):
        np.testing.assert_array_equal(w, g)


def test_admission_backoff_replaces_hot_spin(model):
    """A pages-starved engine must back off (bounded sleep + histogram),
    not hot-spin: admission_waits advances while the waiting request
    cannot be admitted, and the request still completes once capacity
    frees up."""
    eng = DecodeEngine(model, EngineConfig(num_slots=2, max_length=64,
                                           page_size=16))
    # swallow every free page so admission CANNOT succeed
    held = eng.pool.alloc(eng.pool.available())
    assert held and eng.pool.available() == 0
    rid = eng.submit(np.arange(1, 9, dtype=np.int64),
                     SamplingParams(max_new_tokens=4))
    for _ in range(3):
        assert eng.step()  # waiting work exists -> engine stays busy
    assert eng.admission_waits >= 3
    assert 0.0 < eng.admission_wait_s <= 3 * 0.05  # bounded backoff
    for pg in held:
        eng.pool.decref(pg)
    eng.run()
    assert len(eng.result(rid)) == 12
    # backoff resets once admission succeeds
    assert eng._backoff_s == 0.0
