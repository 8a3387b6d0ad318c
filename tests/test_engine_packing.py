"""One packed upload a pass (inference/engine.py: ``_layout``,
``_pack_fields``, ``_unpack_fields``).

Each engine program takes its per-pass host inputs as ONE int32 array: the
host lays the fields end to end, the program cuts them back out bit for bit.
(1) The round trip of every program kind's layout, on values awkward for a
bit-cast. (2) A prefill, a decode pass, a block pass and a commit each make
exactly one host-to-device transfer: counted on the upload spans (``arrays``,
``bytes``), in ``stats()["host_uploads"]`` and at ``jnp.asarray`` itself.
(3) A tiny engine serves the tokens that the engine served when each field
was its own argument (recorded from that engine on these seeds), through the
decode, verify and block programs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import engine as engine_mod
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.observability import tracing
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.text.models.sdar import SDARConfig, SDARForCausalLM

KINDS = ["decode", "prefill_b16", "verify_k2", "block_b4", "commit_b4"]

#: float32 values whose bits a cast would lose: -0.0, a denormal, the
#: smallest normal, 1.0, top-p 0.95, infinities and a NaN with a payload
F32_BITS = np.array([0x80000000, 0x00000001, 0x00800000, 0x3F800000,
                     0x3F733333, 0x7F800000, 0xFF800000, 0x7FC00123],
                    np.uint32)
#: key words with the high bit set, beside 0 and 1
U32 = np.array([0xFFFFFFFF, 0x80000000, 0x9E3779B9, 0, 1], np.uint32)
I32 = np.array([np.iinfo(np.int32).min, -1, 0, 1, np.iinfo(np.int32).max],
               np.int32)

#: what the engine served on these seeds when each field was an argument
#: of its own (``_serve`` below: the decode and verify programs' streams are
#: one, speculation changes how many tokens a step emits, not which)
RECORDED = {
    "decode": [[12, 57, 57, 57, 12, 12, 12, 12, 12],
               [5, 10, 19, 20, 49, 57, 57, 21, 21, 59, 23],
               [34, 36, 55, 10, 19, 9, 46]],
    "block": [[72, 82, 60, 72, 72, 105, 121, 82, 21],
              [55, 9, 124, 114, 52, 88, 57, 96, 4, 51, 121],
              [76, 72, 72, 40, 22, 22, 22]],
}
REQUESTS = [dict(max_new_tokens=9),
            dict(max_new_tokens=11, do_sample=True, temperature=0.8,
                 top_k=20, top_p=0.95, seed=3),
            dict(max_new_tokens=7, do_sample=True, temperature=1.3,
                 top_p=0.9, seed=11)]


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
def gpt():
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def sdar():
    paddle.seed(7)
    m = SDARForCausalLM(SDARConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=128,
        initializer_range=0.5, block_length=4, denoise_steps=4,
        mask_token_id=127, remasking="low_confidence_dynamic",
        confidence_threshold=0.3))
    m.eval()
    return m


def _engine(model, **kw):
    return DecodeEngine(model, EngineConfig(
        num_slots=2, max_length=64, page_size=8, min_bucket=8, **kw))


def _awkward(shape, dt, salt):
    """A field of ``shape`` and ``dt`` cycling through the awkward values,
    shifted by ``salt`` so that no two fields are alike."""
    n = int(np.prod(shape))
    idx = (np.arange(n) + salt) % 97
    if dt == np.float32:
        return F32_BITS[idx % len(F32_BITS)].view(np.float32).reshape(shape)
    if dt == np.uint32:
        return U32[idx % len(U32)].reshape(shape)
    if dt == np.bool_:
        return (idx % 3 == 1).reshape(shape)
    return I32[idx % len(I32)].reshape(shape)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype.itemsize == 4 else a


# -- (1) the layout's round trip ---------------------------------------------


@pytest.mark.parametrize("name", KINDS)
def test_pack_and_unpack_round_trip_every_program_kind(gpt, sdar, name):
    eng = _engine(sdar if name[0] in "bc" else gpt)
    layout = eng._layout(name)
    fields = [_awkward(shape, dt, i) for i, (shape, dt) in enumerate(layout)]
    assert {dt for _, dt in layout} <= {np.dtype(t) for t in (
        np.int32, np.float32, np.uint32, np.bool_)}
    packed = eng._pack(name, fields)
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert packed.size == sum(int(np.prod(s)) for s, _ in layout)
    unpack = jax.jit(lambda p: engine_mod._unpack_fields(layout, p))
    got = unpack(jnp.asarray(packed))
    assert len(got) == len(layout)
    for (shape, dt), want, out in zip(layout, fields, got):
        assert out.shape == shape and out.dtype == dt
        np.testing.assert_array_equal(_bits(out), _bits(want))
    # the synthetic inputs of warmup pack to the same layout
    assert eng._example_args(name)[2].shape == packed.shape


def test_pack_refuses_a_field_of_another_shape(gpt):
    eng = _engine(gpt)
    fields = list(eng._example_inputs("decode"))
    fields[2] = fields[2][:, :-1]  # a table row one page short
    with pytest.raises(ValueError, match="packed field"):
        eng._pack("decode", fields)


def test_the_layout_follows_the_programs_shapes(gpt):
    eng = _engine(gpt)
    s, mp = 2, eng._mp
    lay = {n: [shape for shape, _ in eng._layout(n)] for n in KINDS}
    assert lay["decode"][:4] == [(s,), (s,), (s, mp), (s, 2)]
    assert lay["verify_k2"][0] == (s, 3)
    assert lay["prefill_b16"][:5] == [(1, 16), (), (), (mp,), (2,)]
    assert lay["block_b4"][:2] == [(s, 4), (s, 4)]
    assert lay["block_b4"][-1] == (s,)
    assert lay["commit_b4"] == [(s, 4), (s,), (s, mp)]


# -- (2) one transfer a pass --------------------------------------------------


@pytest.fixture
def traced(monkeypatch, tmp_path):
    """Spans recorded (the telemetry directory set) and every
    ``jnp.asarray`` the engine calls counted."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    tracing._buffer.clear()
    calls = []

    class CountingJnp:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def asarray(*a, **kw):
            calls.append(a[0])
            return jnp.asarray(*a, **kw)

    monkeypatch.setattr(engine_mod, "jnp", CountingJnp())
    yield calls
    tracing._buffer.clear()


UPLOADS = ("eng_prefill_prep", "eng_decode_upload", "eng_verify_upload",
           "eng_block_upload", "eng_commit_upload")


def _check_one_transfer_each(eng, calls, passes):
    """Every upload span made one transfer of its packed bytes, and the
    engine's count and ``jnp.asarray``'s agree with the passes run."""
    spans = [r for r in tracing.recorded() if r.name in UPLOADS]
    assert spans and all(r.attrs["arrays"] == 1 for r in spans)
    assert [r.attrs["bytes"] for r in spans] == [a.nbytes for a in calls]
    assert all(a.dtype == np.int32 and a.ndim == 1 for a in calls)
    assert eng.stats()["host_uploads"] == len(calls) == len(spans) == passes
    return {n: sum(r.name == n for r in spans) for n in UPLOADS}


def test_a_prefill_and_a_decode_pass_each_upload_once(gpt, traced):
    eng = _engine(gpt)
    eng.warmup()
    assert eng.stats()["host_uploads"] == 0 and not traced  # warmup: none
    eng.submit(np.arange(1, 12), max_new_tokens=4)
    eng.step()  # the admission's prefill, then a decode pass
    assert eng.stats()["host_uploads"] == 2
    eng.step()  # a decode pass alone
    assert eng.stats()["host_uploads"] == 3
    while eng.step():
        pass
    got = _check_one_transfer_each(eng, traced, 1 + eng.decode_steps)
    assert got["eng_prefill_prep"] == 1
    assert got["eng_decode_upload"] == eng.decode_steps == 3


def test_a_verify_pass_uploads_once(gpt, traced):
    eng = _engine(gpt, speculate_k=2)
    eng.submit(np.tile(np.arange(1, 5), 4), max_new_tokens=12)
    eng.run()
    got = _check_one_transfer_each(eng, traced, 1 + eng.decode_steps)
    assert got["eng_verify_upload"] == eng.verify_steps > 0


def test_block_and_commit_passes_upload_once(sdar, traced):
    eng = _engine(sdar)
    eng.submit(np.arange(1, 10), max_new_tokens=9)
    eng.step()  # the prefill of the prompt's whole blocks, a block pass
    assert eng.stats()["host_uploads"] == 2
    while eng.step():
        pass
    passes = 1 + eng.decode_steps + eng.commit_passes
    got = _check_one_transfer_each(eng, traced, passes)
    assert got["eng_block_upload"] == eng.decode_steps
    assert got["eng_commit_upload"] == eng.commit_passes > 0


# -- (3) the same tokens as when each field was its own argument --------------


def _serve(model, vocab, **kw):
    eng = _engine(model, **kw)
    rng = np.random.default_rng(5)
    pre = rng.integers(1, vocab - 1, 16)
    prompts = [np.concatenate([pre, rng.integers(1, vocab - 1, n)])
               for n in (3, 9, 5)]
    rids = [eng.submit(p, **r) for p, r in zip(prompts, REQUESTS)]
    eng.run()
    return [eng.result(r)[len(p):].tolist() for r, p in zip(rids, prompts)]


@pytest.mark.parametrize("program,speculate_k", [("decode", 0),
                                                 ("verify", 2)])
def test_served_tokens_are_the_unpacked_engines(gpt, program, speculate_k):
    assert _serve(gpt, 61, speculate_k=speculate_k) == RECORDED["decode"]


def test_served_block_tokens_are_the_unpacked_engines(sdar):
    assert _serve(sdar, 127) == RECORDED["block"]
