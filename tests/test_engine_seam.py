"""The serving seam: a model file gives the block, ``KVPool`` hides the format.

(1) A decoder whose block the engine has never seen (parallel residual:
``x + attn(ln(x)) + mlp(ln(x))``), defined HERE and given to ``DecodeEngine``
as ``embed`` / ``layer`` / ``head`` and its geometry, is served greedy-equal
to its own uncached forward: a new block shape is a model file and no engine
edit. (2) ``KVPool`` alone: the two writes, the read, the page handoff and
the trash page. (3) The pool is ONE pytree argument of every program, and
the program's donated input aliases its output.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.grad_comm import (dequantize_absmax,
                                              quantize_absmax)
from paddle_tpu.framework.core import Tensor
from paddle_tpu.framework.op import raw
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.inference.kv_pool import KV_DTYPES, TRASH_PAGE, KVPool
from paddle_tpu.nn import functional as F

VOCAB, HIDDEN, HEADS, LAYERS, POSITIONS = 53, 32, 4, 2, 64


@pytest.fixture(scope="module", autouse=True)
def _no_ambient_mesh():
    """A file that ran before in this worker may have left its mesh (and
    Fleet group) set: every case here is one device's."""
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    prev, prev_mesh = get_hybrid_communicate_group(), _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    yield
    set_hybrid_communicate_group(prev)
    _mesh.set_global_mesh(prev_mesh)


# -- (1) a block shape the engine does not know ------------------------------


class ParallelBlock(nn.Layer):
    """``x + attn(ln(x)) + mlp(ln(x))``: one norm, both branches read it."""

    def __init__(self):
        super().__init__()
        self.ln = nn.LayerNorm(HIDDEN)
        self.qkv = nn.Linear(HIDDEN, 3 * HIDDEN)
        self.out = nn.Linear(HIDDEN, HIDDEN)
        self.up = nn.Linear(HIDDEN, 4 * HIDDEN)
        self.down = nn.Linear(4 * HIDDEN, HIDDEN)

    def forward(self, x, attend):
        b, t = x.shape[0], x.shape[1]
        y = self.ln(x)
        qkv = self.qkv(y).reshape([b, t, 3, HEADS, HIDDEN // HEADS])
        o = attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return (x + self.out(o.reshape([b, t, HIDDEN]))
                + self.down(F.gelu(self.up(y))))


class ParallelLM(nn.Layer):
    """Its own decode adapter: the three callbacks and the geometry."""

    num_layers, num_heads, num_kv_heads = LAYERS, HEADS, HEADS
    head_dim, max_positions = HIDDEN // HEADS, POSITIONS

    def __init__(self):
        super().__init__()
        self.tok = nn.Embedding(VOCAB, HIDDEN)
        self.pos = nn.Embedding(POSITIONS, HIDDEN)
        self.blocks = nn.LayerList([ParallelBlock() for _ in range(LAYERS)])
        self.norm = nn.LayerNorm(HIDDEN)
        self.lm_head = nn.Linear(HIDDEN, VOCAB)

    def decode_adapter(self):
        return self

    def embed(self, ids, positions):
        return self.tok(ids) + self.pos(Tensor(jnp.asarray(positions)))

    def layer(self, l, x, positions, attend):
        return self.blocks[l](x, attend)

    def head(self, x):
        return self.lm_head(self.norm(x))

    def forward(self, ids):
        """Uncached: every position again, full causal attention."""
        causal = lambda q, k, v: F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=False)
        positions = np.arange(ids.shape[1], dtype=np.int32)
        x = self.embed(ids, positions)
        for l in range(LAYERS):
            x = self.layer(l, x, positions, causal)
        return self.head(x)


@pytest.fixture(scope="module")
def toy():
    paddle.seed(33)
    model = ParallelLM()
    model.eval()
    return model


def _is_own_greedy(model, served, prompt_len):
    """``served`` (prompt + answer) is what the model's own forward picks:
    under causal attention ONE pass over it gives every step's logits, and
    each served token is the argmax after the tokens before it."""
    logits = raw(model(Tensor(jnp.asarray([served[:-1]], jnp.int32))))
    picks = np.asarray(jnp.argmax(logits[0, prompt_len - 1:], axis=-1))
    return picks.tolist() == list(served[prompt_len:])


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["decode", "verify_k2"])
def test_parallel_residual_model_is_served_with_no_engine_edit(
        toy, speculate_k):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, VOCAB, 11), rng.integers(0, VOCAB, 21),
               np.tile([7, 8, 9], 4)]  # the last gives the n-gram draft a hit
    eng = DecodeEngine(toy, EngineConfig(
        num_slots=2, max_length=48, page_size=4, min_bucket=8,
        speculate_k=speculate_k, spec_adaptive=False))
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    for rid, prompt in zip(rids, prompts):
        served = eng.result(rid).tolist()
        assert served[:len(prompt)] == list(prompt) and len(served) == len(
            prompt) + 9
        assert _is_own_greedy(toy, served, len(prompt))
    compiled = eng.stats()["compiled"]
    assert "decode" in compiled and any(
        name.startswith("prefill_b") for name in compiled)
    assert (eng.verify_steps > 0) == (speculate_k > 0)
    assert ("verify_k2" in compiled) == (speculate_k > 0)


# -- (2) KVPool alone ---------------------------------------------------------

L, N, HKV, P, D, MP = 2, 12, 2, 4, 8, 5


def _stored(x, kv_dtype):
    """What a pool of that dtype gives back of the float rows ``x``."""
    if kv_dtype == "int8":
        return np.asarray(dequantize_absmax(*quantize_absmax(x, axis=-1)))
    return np.asarray(x.astype(KV_DTYPES[kv_dtype]), np.float32)


def _dense_attention(q, k, v, n_ctx):
    """q [T, H, D] at positions n_ctx - T ..., over k, v [n_ctx, Hkv, D]."""
    t, h, d = q.shape
    k, v = (np.repeat(a, h // a.shape[1], axis=1) for a in (k, v))
    s = np.einsum("thd,chd->htc", q, k) / np.sqrt(d)
    ctx = np.arange(n_ctx)[None, None, :]
    row = (n_ctx - t + np.arange(t))[None, :, None]
    s = np.where(ctx <= row, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("htc,chd->thd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_pool_writes_then_read_match_dense_attention(kv_dtype):
    """A prompt block (bucket 8, 6 real tokens), then two decode rows with
    an idle slot beside them, then the read on both kernels."""
    rng = np.random.default_rng(2)
    layer, true_len = 1, 6
    pool = KVPool.zeros(L, N, HKV, P, D, kv_dtype)
    row = np.array([3, 7, 9, 0, 0], np.int32)
    k, v = (jnp.asarray(rng.standard_normal((1, 8, HKV, D)), jnp.float32)
            for _ in "kv")
    pool = pool.write_block(layer, k, v, jnp.asarray(row), 0, true_len)
    tables = jnp.asarray(np.stack([row, np.zeros(MP, np.int32)]))
    new = []
    for pos in (6, 7):  # slot 0 decodes, slot 1 is idle: trash page
        kt, vt = (jnp.asarray(rng.standard_normal((2, 1, HKV, D)),
                              jnp.float32) for _ in "kv")
        pool = pool.write_tokens(layer, kt, vt, tables,
                                 jnp.asarray([[pos], [0]], jnp.int32))
        new.append((kt[0], vt[0]))
    k_all = jnp.concatenate([k[0, :true_len]] + [a for a, _ in new])
    v_all = jnp.concatenate([v[0, :true_len]] + [b for _, b in new])
    q = jnp.asarray(rng.standard_normal((2, 1, 2 * HKV, D)), jnp.float32)
    want = _dense_attention(np.asarray(q[0]), _stored(k_all, kv_dtype),
                            _stored(v_all, kv_dtype), 8)
    starts = jnp.asarray([7, 0], jnp.int32)
    for kernel in ("einsum", "pallas"):
        got = raw(pool.attend(Tensor(q), layer, tables, starts, kernel))
        np.testing.assert_allclose(np.asarray(got[0], np.float32), want,
                                   rtol=2e-5, atol=2e-5, err_msg=kernel)
    # nothing was written to another layer
    for a, fresh in zip(jax.tree.leaves(pool), jax.tree.leaves(
            KVPool.zeros(L, N, HKV, P, D, kv_dtype))):
        np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                      np.asarray(fresh[0], np.float32))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_padded_tail_writes_only_the_requests_pages_and_the_trash_page(
        kv_dtype):
    """Bucket 16, 5 real tokens after 4 cached: the request's page 1 and 2
    take them; the bucket's other two blocks go to the trash page, not to
    whatever the row names next (page 11 is another request's)."""
    rng = np.random.default_rng(3)
    pool = KVPool.zeros(L, N, HKV, P, D, kv_dtype)
    before = [np.asarray(a, np.float32) for a in jax.tree.leaves(pool)]
    row = jnp.asarray([5, 2, 8, 11, 0], jnp.int32)
    k, v = (jnp.asarray(rng.standard_normal((1, 16, HKV, D)), jnp.float32)
            for _ in "kv")
    pool = pool.write_block(0, k, v, row, 4, 9)
    for a, b in zip(jax.tree.leaves(pool), before):
        a = np.asarray(a, np.float32)
        touched = [n for n in range(N) if not np.array_equal(a[0, n], b[0, n])]
        assert set(touched) <= {2, 8, TRASH_PAGE} and {2, 8} <= set(touched)
        np.testing.assert_array_equal(a[1], b[1])


def _random_pool(kv_dtype, seed):
    rng = np.random.default_rng(seed)
    pool = KVPool.zeros(L, N, HKV, P, D, kv_dtype)
    for layer in range(L):
        k, v = (jnp.asarray(rng.standard_normal((1, 8, HKV, D)), jnp.float32)
                for _ in "kv")
        pool = pool.write_block(layer, k, v, jnp.asarray([4, 6], jnp.int32),
                                0, 8)
    return pool


@pytest.mark.parametrize("src,dst", [("bf16", "bf16"), ("int8", "int8"),
                                     ("f32", "int8"), ("int8", "bf16")])
def test_export_import_round_trip(src, dst):
    payload = _random_pool(src, 7).export_pages([4, 6])
    assert payload["pool_dtype"] == src
    assert set(payload) == {"pool_dtype", "k", "v"} | (
        {"ks", "vs"} if src == "int8" else set())
    assert payload["k"].shape == (L, 2, HKV, P, D)
    got = KVPool.zeros(L, N, HKV, P, D, dst).import_pages(
        [9, 1], payload).export_pages([9, 1])
    if src == dst:  # verbatim: the handoff is bit-equal
        want = payload
    elif dst == "int8":  # requantized a token row, as write_block does
        want = {}
        for name in "kv":
            q, scale = quantize_absmax(jnp.asarray(payload[name]), axis=-1)
            want[name], want[name + "s"] = q, scale[..., 0]
    else:  # dequantized, then stored at the pool's dtype
        want = {name: dequantize_absmax(
            jnp.asarray(payload[name]),
            jnp.asarray(payload[name + "s"])[..., None]).astype(
                KV_DTYPES[dst]) for name in "kv"}
    assert got["pool_dtype"] == dst
    for name in set(got) - {"pool_dtype"}:
        assert got[name].dtype == np.asarray(want[name]).dtype, name
        np.testing.assert_array_equal(
            np.asarray(got[name], np.float32),
            np.asarray(want[name], np.float32), err_msg=name)


# -- (3) one pytree argument, donated as one ---------------------------------


@pytest.mark.parametrize("kv_dtype,leaves", [("bf16", 2), ("int8", 4)])
def test_pool_is_one_donated_argument_of_a_program(toy, kv_dtype, leaves):
    pool = KVPool.zeros(L, N, HKV, P, D, kv_dtype)
    flat, tree = jax.tree.flatten(pool)
    assert len(flat) == leaves
    assert isinstance(jax.tree.unflatten(tree, flat), KVPool)
    eng = DecodeEngine(toy, EngineConfig(
        num_slots=2, max_length=16, page_size=4, kv_dtype=kv_dtype,
        donate=True))
    args = eng._example_args("decode")
    assert args[1] is eng.kv and len(jax.tree.leaves(args[1])) == leaves
    text = eng._build_decode().lower(*args).as_text()
    # every leaf of the pool, and nothing else, is given up to a result
    assert text.count("tf.aliasing_output") == leaves
    main = text[text.index("func.func public @main("):]
    state = len(jax.tree.leaves(args[0]))
    for i in range(leaves):
        arg = re.search(rf"%arg{state + i}: [^%]*", main).group(0)
        assert f"tf.aliasing_output = {i} : i32" in arg
    # after the pool, the pass's host inputs as ONE packed int32 array
    assert len(args) == 3 and args[2].dtype == np.int32 and args[2].ndim == 1
    last = re.search(rf"%arg{state + leaves}: tensor<(\d+)xi32>", main)
    assert last and int(last.group(1)) == args[2].size
    assert f"%arg{state + leaves + 1}:" not in main


# -- (4) the two depths: a model without loops compiles no loop ---------------


def _tiny_lm(kind):
    from paddle_tpu.text import models

    paddle.seed(7)
    if kind == "toy":
        return ParallelLM()
    if kind == "gpt":
        return models.GPTForCausalLM(models.GPTConfig(
            vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=LAYERS,
            num_attention_heads=HEADS, intermediate_size=4 * HIDDEN,
            max_position_embeddings=POSITIONS, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
    if kind == "llama":
        return models.LlamaForCausalLM(models.LlamaConfig(
            vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=LAYERS,
            num_attention_heads=HEADS, max_position_embeddings=POSITIONS,
            use_flash_attention=False))
    return models.OuroForCausalLM(models.OuroConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=88,
        num_hidden_layers=LAYERS, num_attention_heads=HEADS,
        num_key_value_heads=HEADS, head_dim=HIDDEN // HEADS,
        max_position_embeddings=POSITIONS, total_ut_steps=3))


@pytest.mark.parametrize("kind,loops", [("gpt", 1), ("llama", 1), ("toy", 1),
                                        ("ouro", 3)])
def test_only_an_adapter_that_states_loops_compiles_a_loop(kind, loops):
    """An adapter states its weight layers; one that runs them more than
    once states ``loops`` and ``close_loop`` too, and the pool is ``loops x
    num_layers`` entries deep. Without them ``_forward`` traces the layers
    once and no loop construct reaches the decode program's text: GPT's and
    Llama's programs are what they were. With them the loops are ONE
    ``while`` whose carry holds the pool, closed under ``loop_close``."""
    model = _tiny_lm(kind)
    model.eval()
    eng = DecodeEngine(model, EngineConfig(
        num_slots=2, max_length=16, page_size=4, donate=True))
    assert eng.stats()["loops"] == loops
    assert eng.stats()["cache_layers"] == loops * LAYERS == eng.kv.shape[0]
    lowered = eng._build_decode().lower(*eng._example_args("decode"))
    text = lowered.as_text(debug_info=True)
    # a loop over the layers is a ``while`` that carries the pool (the
    # sampler's threefry rounds are whiles too, over a few scalars)
    pool = "tensor<" + "x".join(str(n) for n in eng.kv.shape) + "x"
    carrying = [ln for ln in text.splitlines()
                if "stablehlo.while" in ln and pool in ln]
    assert len(carrying) == (1 if loops > 1 else 0)
    assert ("loop_close" in text) == (loops > 1)
    assert text.count("tf.aliasing_output") == 2  # the pool is still donated
