"""Pallas decode kernel plane: fused paged attention vs the einsum
oracle (docs/SERVING.md §kernel plane).

The fused kernel (paddle_tpu/ops/pallas/paged_attention.py) streams KV
pages at their stored dtype — int8 dequant fused against per-page absmax
scales — and must be an exact drop-in for the einsum reference: f32
outputs within tolerance and greedy argmax BIT-EQUAL across the shape
grid (page size x GQA group x int8/raw x decode/verify T). Off-TPU the
kernel runs in Pallas interpret mode, which is what these tests
exercise. Routing (resolve_attn_kernel / PADDLE_TPU_ATTN_KERNEL /
EngineConfig.attn_kernel) and the engine end-to-end greedy streams are
gated here too; the compile-count invariant (buckets_used + 2) must be
unchanged by the kernel choice.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.inference as inference
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.op import raw
from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                         SamplingParams)
from paddle_tpu.nn.functional import attention as attn_mod
from paddle_tpu.ops.pallas import paged_attention as pa_kernel
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

VOCAB = 61


# ---------------------------------------------------------------------------
# functional parity: fused kernel vs einsum oracle
# ---------------------------------------------------------------------------


def _case(rng, *, t, hkv, group, page_size, max_pages=3, int8=False, d=16,
          s=2):
    """Random paged-cache case: q [S,T,H,D], pools [N,Hkv,P,D], page
    table with per-slot context lengths (tail pages left on the trash
    page 0), start positions placing the T query rows at the context
    tail — the decode (T=1) and speculative verify (T=k+1) layouts."""
    h = hkv * group
    n = 1 + s * max_pages  # page 0 is the reserved trash page
    q = rng.standard_normal((s, t, h, d)).astype(np.float32)
    ctx = rng.integers(t, max_pages * page_size + 1, size=s)
    start = (ctx - t).astype(np.int32)
    table = np.zeros((s, max_pages), np.int32)
    perm = rng.permutation(np.arange(1, n))
    nxt = 0
    for i in range(s):
        used = -(-int(ctx[i]) // page_size)
        table[i, :used] = perm[nxt:nxt + used]
        nxt += used
    if int8:
        kp = rng.integers(-127, 128, (n, hkv, page_size, d), np.int32)
        vp = rng.integers(-127, 128, (n, hkv, page_size, d), np.int32)
        kp, vp = kp.astype(np.int8), vp.astype(np.int8)
        ks = rng.uniform(0.005, 0.03, (n, hkv, page_size)).astype(np.float32)
        vs = rng.uniform(0.005, 0.03, (n, hkv, page_size)).astype(np.float32)
    else:
        kp = rng.standard_normal((n, hkv, page_size, d)).astype(np.float32)
        vp = rng.standard_normal((n, hkv, page_size, d)).astype(np.float32)
        ks = vs = None
    return q, kp, vp, ks, vs, table, start


def _run(kernel, q, kp, vp, ks, vs, table, start):
    out = F.paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(start),
        k_scales=None if ks is None else jnp.asarray(ks),
        v_scales=None if vs is None else jnp.asarray(vs),
        kernel=kernel)
    return np.asarray(raw(out))


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("t", [1, 3])
def test_kernel_matches_einsum_oracle(page_size, group, int8, t):
    rng = np.random.default_rng(page_size * 100 + group * 10 + int8 * 5 + t)
    case = _case(rng, t=t, hkv=2, group=group, page_size=page_size,
                 int8=int8)
    got = _run("pallas", *case)
    ref = _run("einsum", *case)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-4)
    # greedy contract: the fused path must not flip an argmax
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))


def test_kernel_under_jit_matches_eager():
    """The engine runs the kernel inside jit-compiled decode programs;
    traced and eager results must agree (interpret mode composes with
    jit on CPU)."""
    rng = np.random.default_rng(3)
    q, kp, vp, ks, vs, table, start = _case(
        rng, t=1, hkv=2, group=2, page_size=8, int8=True)

    def f(q_, kp_, vp_, ks_, vs_, tb, sp):
        return pa_kernel.paged_attention(q_, kp_, vp_, tb, sp,
                                         k_scales=ks_, v_scales=vs_)

    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(table),
            jnp.asarray(start))
    eager = np.asarray(f(*args))
    jitted = np.asarray(jax.jit(f)(*args))
    np.testing.assert_allclose(jitted, eager, atol=1e-6)


def test_scales_must_come_in_pairs():
    rng = np.random.default_rng(0)
    q, kp, vp, ks, vs, table, start = _case(
        rng, t=1, hkv=2, group=1, page_size=8, int8=True)
    with pytest.raises(ValueError, match="together"):
        F.paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(table), jnp.asarray(start),
                          k_scales=jnp.asarray(ks))


# ---------------------------------------------------------------------------
# mask fill constant + kernel selection knob
# ---------------------------------------------------------------------------


def test_mask_fill_value_shared_and_finite():
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        v = pa_kernel.mask_fill_value(dt)
        assert v == float(jnp.finfo(dt).min) * 0.5
        assert np.isfinite(np.asarray(v, dt))  # no -inf NaN hazards
    # the einsum ops fill with the same constant the kernel masks with
    assert attn_mod._MASK_FILL == pa_kernel.mask_fill_value(jnp.float32)


def test_resolve_attn_kernel_precedence(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_ATTN_KERNEL", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # auto off-TPU -> einsum oracle (this suite runs on CPU)
    assert jax.default_backend() != "tpu"
    assert F.resolve_attn_kernel() == "einsum"
    assert F.resolve_attn_kernel("auto") == "einsum"
    # the interpret test hook flips auto to the kernel
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert F.resolve_attn_kernel() == "pallas"
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    # env beats auto; explicit arg beats env
    monkeypatch.setenv("PADDLE_TPU_ATTN_KERNEL", "pallas")
    assert F.resolve_attn_kernel() == "pallas"
    assert F.resolve_attn_kernel("einsum") == "einsum"
    with pytest.raises(ValueError, match="unknown attention kernel"):
        F.resolve_attn_kernel("cuda")


# ---------------------------------------------------------------------------
# engine end-to-end: greedy streams bit-equal across kernels
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    prev = get_hybrid_communicate_group()
    prev_mesh = _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    try:
        paddle.seed(11)
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


def _prompt(rng, n):
    return rng.integers(1, VOCAB, n, dtype=np.int64)


def _drain(eng, prompts, max_new=8, **kw):
    rids = [eng.submit(p, SamplingParams(max_new_tokens=max_new, **kw))
            for p in prompts]
    eng.run()
    return [eng.result(r) for r in rids]


def test_engine_config_and_env_routing(model, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_ATTN_KERNEL", raising=False)
    eng = DecodeEngine(model, EngineConfig(num_slots=2, max_length=64,
                                           attn_kernel="pallas"))
    assert eng.stats()["attn_kernel"] == "pallas"
    # no config knob -> the env decides at engine construction
    monkeypatch.setenv("PADDLE_TPU_ATTN_KERNEL", "pallas")
    eng = DecodeEngine(model, EngineConfig(num_slots=2, max_length=64))
    assert eng.stats()["attn_kernel"] == "pallas"
    monkeypatch.delenv("PADDLE_TPU_ATTN_KERNEL")
    eng = DecodeEngine(model, EngineConfig(num_slots=2, max_length=64))
    assert eng.stats()["attn_kernel"] == "einsum"


def test_engine_mp_sharded_pool_auto_serves_einsum_explicit_pallas_raises(
        model, monkeypatch):
    """A kernel asked for by name that cannot run is an error, never a
    quiet switch to the einsum oracle."""
    from paddle_tpu.distributed.mesh import build_mesh

    mesh = build_mesh((1, 2), ("dp", "mp"), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="mp-sharded"):
        DecodeEngine(model, EngineConfig(num_slots=2, max_length=64,
                                         attn_kernel="pallas", mesh=mesh))
    # auto resolving to the kernel (here through the routing test hook)
    # may still give way under mp, and says so in stats()
    monkeypatch.delenv("PADDLE_TPU_ATTN_KERNEL", raising=False)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    # an engine with a mesh commits its model's weights to that mesh: give
    # it a model of its own, not the module's shared one
    own = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, max_position_embeddings=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    eng = DecodeEngine(own, EngineConfig(num_slots=2, max_length=64,
                                         mesh=mesh))
    assert eng.stats()["attn_kernel"] == "einsum"


def test_engine_greedy_bit_equal_pallas_vs_einsum(model):
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, n) for n in (5, 11)]
    cfg = dict(num_slots=2, max_length=64, page_size=8)
    ref = _drain(DecodeEngine(model, EngineConfig(
        attn_kernel="einsum", **cfg)), prompts, max_new=8)
    got = _drain(DecodeEngine(model, EngineConfig(
        attn_kernel="pallas", **cfg)), prompts, max_new=8)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_engine_pallas_int8_speculative_bit_equal_and_compile_gate(model):
    """The heavy corner in one pass: int8 KV pools (dequant fused in the
    kernel vs materialized by the oracle), speculative verify (T=k+1
    rows through the same program), prefix caching — greedy streams
    bit-equal, and the compiled-program count invariant (used prefill
    buckets + ONE decode + ONE verify) is unchanged by the kernel."""
    rng = np.random.default_rng(8)
    motif = _prompt(rng, 4)
    prompts = ([np.concatenate([np.tile(motif, 4), _prompt(rng, 2)])
                for _ in range(3)]
               + [np.tile(motif, 7)[:26] for _ in range(2)])
    cfg = dict(num_slots=3, max_length=96, page_size=8, speculate_k=3,
               spec_adaptive=False, prefix_cache=True, kv_dtype="int8")
    ref_eng = DecodeEngine(model, EngineConfig(attn_kernel="einsum", **cfg))
    ref = _drain(ref_eng, prompts, max_new=10)
    eng = DecodeEngine(model, EngineConfig(attn_kernel="pallas", **cfg))
    got = _drain(eng, prompts, max_new=10)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    st = eng.stats()
    assert st["attn_kernel"] == "pallas"
    assert st["verify_steps"] > 0
    buckets_used = sum(1 for name in st["compiled"]
                       if name.startswith("prefill_"))
    assert st["compile_count"] == buckets_used + 2, st["compiled"]
    # fused dequant saves the per-step f32 pool materialization
    assert eng._fused_dequant_bytes_step > 0
