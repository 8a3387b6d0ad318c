"""Ouro, the looped decoder (text/models/ouro.py): program against the plain
reference (benchmark/reference/ouro.py, which imports nothing of the
program) on seeded weights at a tiny size, for 1, 2 and 4 loops.

(1) The model's own forward: logits and the exit distribution. (2) Served:
``DecodeEngine``'s prefill then decode through the paged pool against the
reference's full forward at every served position, on both attention
kernels a CPU runs. (3) The cache is ``loops x layers`` entries deep: each
(loop, layer) writes its own entry and reads no other. (4) A shared prefix
and the page handoff between engines at that depth. (5) A threshold below 1
is refused.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor
from paddle_tpu.framework.op import raw
from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                         SamplingParams)
from paddle_tpu.text.models import OuroConfig, OuroForCausalLM
from paddle_tpu.text.models import ouro as ouro_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

#: hidden 64, 4 heads of 16, ffn 176, vocab 128, 3 layers
TINY = dict(vocab_size=128, hidden_size=64, intermediate_size=176,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, max_position_embeddings=128,
            rms_norm_eps=1e-6, rope_theta=1000000, early_exit_threshold=1,
            tie_word_embeddings=False, dtype="float32")
LOOPS = [1, 2, 4]


def _bench_module(*parts):
    """A file of the benchmark by its place (its own imports, ``weights``,
    are found beside it: the directory goes LAST on the path)."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def _no_ambient_mesh():
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    prev, prev_mesh = get_hybrid_communicate_group(), _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    yield
    set_hybrid_communicate_group(prev)
    _mesh.set_global_mesh(prev_mesh)


@pytest.fixture(scope="module")
def bench():
    return (_bench_module("archs", "ouro.py"),
            _bench_module("reference", "ouro.py"), _bench_module("weights.py"))


@pytest.fixture(scope="module")
def made(bench):
    """``made(loops)`` -> (model with the seed's weights, the reference's
    stacked copy of them, the reference's keyword arguments)."""
    arch, _, weights = bench
    cache = {}

    def make(loops):
        if loops not in cache:
            cfg = dict(TINY, total_ut_steps=loops)
            model, names = arch.serve_program(cfg)
            model.eval()
            w = weights.make(arch.weight_spec(cfg, stacked=False), 11,
                             "float32")
            missing, unexpected = model.set_state_dict(
                {names[k]: v for k, v in w.items()})
            assert not missing and not unexpected
            stacked = weights.make(arch.weight_spec(cfg, stacked=True), 11,
                                   "float32")
            cache[loops] = model, stacked, arch.reference_args(cfg)
        return cache[loops]

    return make


def _ids(n, seed=5):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], n)


# -- (1) the model's own forward ----------------------------------------------


@pytest.mark.parametrize("loops", LOOPS)
def test_forward_logits_and_exit_pdf_match_the_reference(bench, made, loops):
    _, ref, _ = bench
    model, w, kw = made(loops)
    ids = _ids(23)
    got = raw(model(Tensor(jnp.asarray([ids], jnp.int32))))[0]
    want = ref.logits(w, jnp.asarray(ids), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    pdf = np.asarray(raw(model.exit_pdf(
        Tensor(jnp.asarray([ids], jnp.int32)))))[:, 0]
    want_pdf = np.asarray(ref.exit_pdf(w, jnp.asarray(ids), **kw))
    assert pdf.shape == want_pdf.shape == (loops, len(ids))
    np.testing.assert_allclose(pdf, want_pdf, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(pdf.sum(0), 1.0, rtol=1e-5)
    if loops > 1:  # the gate is not a constant: random weights move it
        assert np.ptp(pdf[0]) > 1e-4


def test_reference_loss_is_the_last_loops_cross_entropy(bench, made):
    _, ref, _ = bench
    _, w, kw = made(2)
    ids = np.stack([_ids(9, 1), _ids(9, 2)])
    labels = np.roll(ids, -1, axis=1)
    lg = jnp.stack([ref.logits(w, jnp.asarray(r), **kw) for r in ids])
    want = -np.mean(np.take_along_axis(
        np.asarray(jax.nn.log_softmax(lg, -1)), labels[..., None], -1))
    got = float(ref.loss(w, jnp.asarray(ids), jnp.asarray(labels), **kw))
    assert abs(got - want) < 1e-5 * abs(want)
    q = float(ref.loss(w, jnp.asarray(ids), jnp.asarray(labels),
                       quant="int8", **kw))
    assert q != got  # the control rounds


# -- (2) served: prefill then decode through the paged pool -------------------


def _engine(model, kernel="einsum", **kw):
    cfg = dict(num_slots=2, max_length=48, page_size=4, min_bucket=8,
               attn_kernel=kernel)
    cfg.update(kw)
    return DecodeEngine(model, EngineConfig(**cfg))


@pytest.mark.parametrize("kernel", ["einsum", "pallas"])
@pytest.mark.parametrize("loops", LOOPS)
def test_served_tokens_are_the_references_at_every_position(
        bench, made, loops, kernel):
    """Three prompts over two slots: every served token is the reference's
    best at its position (its logit within rounding of the reference's
    largest), the reference seeing the whole served sequence at once."""
    _, ref, _ = bench
    model, w, kw = made(loops)
    eng = _engine(model, kernel)
    prompts = [_ids(11, 1), _ids(21, 2), _ids(5, 3)]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    st = eng.stats()
    assert st["loops"] == loops
    assert st["cache_layers"] == loops * TINY["num_hidden_layers"]
    assert st["cache_layers"] == eng.kv.shape[0]
    assert st["kv_bytes_per_token"] == st["cache_layers"] * 2 * 4 * 16 * 4
    for rid, prompt in zip(rids, prompts):
        served = eng.result(rid)
        assert served[:len(prompt)].tolist() == prompt.tolist()
        assert len(served) == len(prompt) + 9
        lg = np.asarray(ref.logits(w, jnp.asarray(served[:-1]), **kw))
        at = lg[len(prompt) - 1:]
        chosen = np.take_along_axis(
            at, served[len(prompt):, None].astype(np.int64), -1)[:, 0]
        assert np.max(at.max(-1) - chosen) < 1e-4


# -- (3) one cache entry a (loop, layer) --------------------------------------


@pytest.mark.parametrize("loops", [2, 4])
def test_each_loop_and_layer_writes_its_own_entry_and_reads_no_other(
        made, loops, monkeypatch):
    model, _, _ = made(loops)
    n_layers, page = TINY["num_hidden_layers"], 4
    prompt = _ids(13, 7)
    # the keys of every application of a layer, in the order (u, l), from
    # the model's own uncached forward
    keys = []
    real = ouro_mod._causal_attention
    monkeypatch.setattr(ouro_mod, "_causal_attention", lambda q, k, v: (
        keys.append(np.asarray(raw(k))[0]), real(q, k, v))[1])
    model(Tensor(jnp.asarray([prompt], jnp.int32)))
    monkeypatch.undo()
    assert len(keys) == loops * n_layers
    eng = _engine(model)
    rid = eng.submit(prompt, max_new_tokens=4)
    eng.step()  # the prefill, and one decode pass
    row = eng._tables[eng._requests[rid].slot]
    pages = row[:-(-len(prompt) // page)]
    pool_k = np.asarray(eng.kv.k)  # [T * L, N, Hkv, P, D]
    for entry, want in enumerate(keys):  # want [T, Hkv, D]
        got = np.swapaxes(pool_k[entry, pages], 1, 2).reshape(
            -1, *want.shape[1:])[:len(prompt)]
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-5,
                                   err_msg=f"entry {entry}")
    # no two entries hold the same keys: no entry was written twice over
    flat = pool_k[:, pages].reshape(loops * n_layers, -1)
    assert len({a.tobytes() for a in flat}) == loops * n_layers
    # reading entry e with every OTHER entry poisoned changes nothing
    rng = np.random.default_rng(0)
    q = Tensor(jnp.asarray(rng.standard_normal((2, 1, 4, 16)), jnp.float32))
    tables = jnp.asarray(eng._tables)
    at = jnp.asarray([len(prompt), 0], jnp.int32)
    for kernel in ("einsum", "pallas"):
        for entry in range(loops * n_layers):
            others = jnp.arange(loops * n_layers) != entry
            poisoned = jax.tree.map(
                lambda a: jnp.where(
                    others.reshape((-1,) + (1,) * (a.ndim - 1)), jnp.nan, a),
                eng.kv)
            want = raw(eng.kv.attend(q, entry, tables, at, kernel))
            got = raw(poisoned.attend(q, entry, tables, at, kernel))
            assert np.isfinite(np.asarray(got[0])).all()
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(want[0]))


# -- (4) a shared prefix, and the handoff between engines ---------------------


@pytest.mark.parametrize("loops", [2, 4])
def test_shared_prefix_and_page_handoff_are_bit_equal(made, loops):
    model, _, _ = made(loops)
    depth = loops * TINY["num_hidden_layers"]
    head = _ids(12, 21)  # three full pages
    a = np.concatenate([head, _ids(5, 22)])
    b = np.concatenate([head, _ids(7, 23)])
    alone = []
    for p in (a, b):  # each on a fresh engine: nothing to share
        eng = _engine(model)
        rid = eng.submit(p, max_new_tokens=8)
        eng.run()
        alone.append(eng.result(rid).tolist())
    eng = _engine(model)
    ra = eng.submit(a, max_new_tokens=8)
    eng.run()
    rb = eng.submit(b, max_new_tokens=8)
    eng.run()
    assert eng.stats()["prefix_hit_tokens"] == 12
    assert [eng.result(ra).tolist(), eng.result(rb).tolist()] == alone
    # prefill on one engine, decode on another: the pages of all T * L
    # entries travel
    src, dst = _engine(model), _engine(model)
    params = SamplingParams(max_new_tokens=8)
    payload = src.prefill_export(a, params)
    assert payload["k"].shape[0] == depth
    assert payload["k"].shape[1] == -(-len(a) // 4)
    rid = dst.try_import_prefill(a, params, payload)
    assert rid is not None
    dst.run()
    assert dst.result(rid).tolist() == alone[0]
    assert not any(n.startswith("prefill") for n in dst.stats()["compiled"])


# -- (5) what is not served ----------------------------------------------------


def test_a_threshold_below_one_is_refused_at_construction():
    cfg = {k: v for k, v in TINY.items() if k != "dtype"}
    cfg.update(total_ut_steps=2, early_exit_threshold=0.9)
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        OuroForCausalLM(OuroConfig(**cfg))
    paddle.seed(1)
    cfg["early_exit_threshold"] = 1.0
    assert OuroForCausalLM(OuroConfig(**cfg)).decode_adapter().loops == 2


def test_the_rope_table_is_a_buffer_of_the_programs_and_no_state(made):
    """One table for the model (a table a layer would be 48 of them),
    derived from the config and so left out of ``state_dict``: a sublayer's
    non-persistable buffer; the engine still hands it to its programs as an
    argument, not as a constant baked into each."""
    model, _, _ = made(2)
    assert not [k for k in model.state_dict() if "rope" in k]
    assert {n for n, _ in model.named_buffers()} == {
        "model.rope_cos", "model.rope_sin"}
    eng = _engine(model)
    assert {"model.rope_cos", "model.rope_sin"} <= set(eng.state_keys())
