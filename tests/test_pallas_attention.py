"""Pallas serving kernel plane: fused paged attention (decode, verify) and
the tail prefill's blocked attention vs the einsum oracle
(docs/SERVING.md §kernel plane).

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
from paddle_tpu.inference.kv_pool import KVPool
from paddle_tpu.ops.pallas import paged_attention as pa_kernel
from paddle_tpu.ops.pallas import prefill_attention as pf_kernel
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

VOCAB = 61


# ---------------------------------------------------------------------------
# functional parity: fused kernel vs einsum oracle
# ---------------------------------------------------------------------------


def _case(rng, *, t, hkv, group, page_size, max_pages=3, int8=False, d=16,
          s=2, ctx=None):
    """Random paged-cache case: q [S,T,H,D], pools [N,Hkv,P,D], page
    table with per-slot context lengths (tail pages left on the trash
    page 0), start positions placing the T query rows at the context
    tail — the decode (T=1), speculative verify (T=k+1) and tail prefill
    (S=1, T=bucket) layouts. ``ctx`` fixes the contexts (one a slot, the
    T rows included); ``None`` in it is an idle slot: position 0, every
    table entry the trash page."""
    h = hkv * group
    if ctx is None:
        ctx = rng.integers(t, max_pages * page_size + 1, size=s)
    s = len(ctx)
    n = 1 + s * max_pages  # page 0 is the reserved trash page
    q = rng.standard_normal((s, t, h, d)).astype(np.float32)
    start = np.array([0 if c is None else c - t for c in ctx], np.int32)
    table = np.zeros((s, max_pages), np.int32)
    perm = rng.permutation(np.arange(1, n))
    nxt = 0
    for i in range(s):
        used = 0 if ctx[i] is None else -(-int(ctx[i]) // page_size)
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


def _assert_matches_oracle(case):
    got = _run("pallas", *case)
    ref = _run("einsum", *case)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-4)
    # greedy contract: the fused path must not flip an argmax
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))
    return got


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("t", [1, 3])
def test_kernel_matches_einsum_oracle(page_size, group, int8, t):
    rng = np.random.default_rng(page_size * 100 + group * 10 + int8 * 5 + t)
    _assert_matches_oracle(_case(rng, t=t, hkv=2, group=group,
                                 page_size=page_size, int8=int8))


def _call_shapes(spec):
    """(rows8, d, p, kv_itemsize, has_scales) of a ``_case(**spec)``."""
    return (pa_kernel._ceil8(spec["t"] * spec["group"]), 16,
            spec["page_size"], 1 if spec["int8"] else 4, spec["int8"])


def _force_heads(monkeypatch, heads, spec, expect=None):
    """The VMEM budget that leaves ``heads`` kv heads a grid step, the
    pages a block held at what the unforced call takes."""
    shapes = _call_shapes(spec)
    assert pa_kernel._heads_per_step(spec["hkv"], *shapes) == spec["hkv"]
    ppb = pa_kernel._pages_per_block(*shapes[:3], spec["hkv"], *shapes[3:])
    monkeypatch.setattr(pa_kernel, "_VMEM_BUDGET",
                        heads * pa_kernel._bytes_per_head(*shapes))
    assert pa_kernel._heads_per_step(spec["hkv"], *shapes) == (
        expect or heads)
    assert pa_kernel._pages_per_block(
        *shapes[:3], expect or heads, *shapes[3:]) < ppb
    monkeypatch.setattr(pa_kernel, "_pages_per_block", lambda *a: ppb)


#: What a grid that follows the live KV can get wrong: where the last live
#: page slot lies, a table far wider than it, a slot with nothing live, the
#: prefill's one slot of many rows, and a head block smaller than the heads
#: (``heads``: kv heads a grid step, forced through the VMEM budget).
LIVE_KV_CASES = {
    "wide_table_contexts_of_1_to_3_pages": dict(
        t=1, ctx=[3, 20, 9], max_pages=16),
    "wide_table_verify": dict(t=3, ctx=[9, 24], max_pages=16, group=2),
    "idle_slot_beside_a_full_one": dict(t=1, ctx=[None, 64], max_pages=8),
    "idle_slot_beside_a_full_one_verify": dict(
        t=3, ctx=[64, None], max_pages=8),
    "every_slot_idle": dict(t=1, ctx=[None, None], max_pages=4),
    "context_ends_on_a_page_boundary": dict(t=1, ctx=[16, 8], max_pages=6),
    "context_one_token_past_a_page_boundary": dict(
        t=1, ctx=[17, 9], max_pages=6),
    "verify_rows_straddle_a_page_boundary": dict(
        t=3, ctx=[17, 18], max_pages=6),
    "prefill_nothing_cached": dict(t=32, ctx=[32], max_pages=8),
    "prefill_two_cached_pages": dict(t=32, ctx=[48], max_pages=8),
    "prefill_short_of_its_bucket_gqa_int8": dict(
        t=32, ctx=[40], max_pages=12, group=2, int8=True),
    "four_heads_in_blocks_of_two": dict(
        t=1, ctx=[21, 5], max_pages=4, hkv=4, heads=2),
    "six_heads_budget_for_four_takes_three": dict(
        t=3, ctx=[30, 12], max_pages=4, hkv=6, heads=4, expect_heads=3),
    "four_heads_one_a_step": dict(
        t=1, ctx=[11, 32], max_pages=4, hkv=4, heads=1),
    "int8_scales_four_heads_a_step": dict(
        t=1, ctx=[21, 5], max_pages=4, hkv=4, int8=True),
    "int8_scales_in_blocks_of_two": dict(
        t=3, ctx=[21, 5], max_pages=4, hkv=4, group=2, int8=True, heads=2),
}


@pytest.mark.parametrize("name", list(LIVE_KV_CASES))
def test_kernel_matches_einsum_oracle_where_the_grid_follows_live_kv(
        name, monkeypatch):
    spec = dict(LIVE_KV_CASES[name])
    heads = spec.pop("heads", None)
    expect = spec.pop("expect_heads", heads)
    spec = dict(dict(hkv=2, group=1, page_size=8, int8=False), **spec)
    case = _case(np.random.default_rng(len(name)), **spec)
    whole = _assert_matches_oracle(case)
    if heads is None:
        return
    # a smaller head block is the same arithmetic a head: bit-equal, at
    # the whole call's pages a block (the budget that leaves fewer heads
    # would leave fewer pages too, and rescale in another order)
    _force_heads(monkeypatch, heads, spec, expect)
    np.testing.assert_array_equal(_assert_matches_oracle(case), whole)


#: What the body's own walk can get wrong (PR 36): where a slot's live
#: pages end inside its last block, a buffer handed from one grid step to
#: the next, an idle slot between live ones, and page slots of a block
#: that are never fetched. ``keys``: the most keys a block (pages of 8, so
#: 32 is 4 pages a block); slots' live pages in the comments.
BLOCK_WALK_CASES = {
    # 2 of 4, idle, 4 of 4, 5 = one block and a page, 8 = two blocks
    "ends_mid_block_on_its_edge_and_a_page_past_it": dict(
        t=1, ctx=[16, None, 32, 33, 64], max_pages=12, keys=32),
    "verify_rows_reach_into_the_next_block": dict(
        t=3, ctx=[33, None, 34, 32], max_pages=12, keys=32),
    "idle_slots_first_and_last": dict(
        t=1, ctx=[None, 70, None, 9, None], max_pages=12, keys=32),
    "one_page_a_block": dict(
        t=1, ctx=[16, None, 33], max_pages=6, keys=8),
    "two_pages_a_block_gqa_int8": dict(
        t=3, ctx=[40, None, 17, 16], max_pages=6, keys=16, group=2,
        int8=True),
    "a_block_wider_than_the_table": dict(
        t=1, ctx=[20, None, 5], max_pages=3, keys=128),
    "head_blocks_hand_the_buffer_on": dict(
        t=1, ctx=[33, None, 16], max_pages=12, keys=32, hkv=4, heads=2),
    "head_blocks_hand_the_buffer_on_int8": dict(
        t=3, ctx=[33, 8, None], max_pages=12, keys=32, hkv=4, heads=1,
        int8=True),
}


@pytest.mark.parametrize("name", list(BLOCK_WALK_CASES))
def test_kernel_matches_einsum_oracle_where_the_body_walks_blocks(
        name, monkeypatch):
    spec = dict(BLOCK_WALK_CASES[name])
    keys, heads = spec.pop("keys"), spec.pop("heads", None)
    spec = dict(dict(hkv=2, group=1, page_size=8, int8=False), **spec)
    monkeypatch.setattr(pa_kernel, "_BLOCK_KEYS", keys)
    shapes = _call_shapes(spec)
    assert pa_kernel._pages_per_block(
        *shapes[:3], spec["hkv"], *shapes[3:]) == keys // 8
    if heads is not None:
        _force_heads(monkeypatch, heads, spec)
    case = _case(np.random.default_rng(len(name)), **spec)
    # an unreferenced page of NaN: no entry of the table names it (dead
    # entries are the trash page, 0), so nothing may read it, and what a
    # block's unfetched page slots leave in VMEM must not reach a row
    q, kp, vp, ks, vs, table, start = case
    if not spec["int8"]:
        spare = np.full((1,) + kp.shape[1:], np.nan, np.float32)
        kp, vp = np.concatenate([kp, spare]), np.concatenate([vp, spare])
    got = _assert_matches_oracle((q, kp, vp, ks, vs, table, start))
    assert np.isfinite(got).all()


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("q_dtype,pool_dtype", [
    ("bfloat16", "bfloat16"),  # the cells' pair: the first product unwidened
    ("float32", "bfloat16"),
    ("bfloat16", "float32"),
])
def test_kernel_matches_einsum_oracle_at_the_dtypes_queries_and_pool_come_in(
        q_dtype, pool_dtype, t):
    """bf16 queries on a bf16 pool enter the first product as they are
    (bf16 x bf16 into a float32 accumulator is the same products
    exactly); every other pair meets in float32. The oracle runs on the
    values the kernel is handed, widened; the result is float32."""
    q, kp, vp, _, _, table, start = _case(
        np.random.default_rng(t), t=t, hkv=2, group=2, page_size=8,
        ctx=[40, None, 17, 64], max_pages=10)
    q = jnp.asarray(q, q_dtype)
    kp, vp = jnp.asarray(kp, pool_dtype), jnp.asarray(vp, pool_dtype)
    got = np.asarray(pa_kernel.paged_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(start)))
    assert got.dtype == np.float32
    ref = _run("einsum", q.astype(jnp.float32), kp.astype(jnp.float32),
               vp.astype(jnp.float32), None, None, table, start)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))


def test_a_row_count_that_fills_the_budget_leaves_one_page_a_block(
        monkeypatch):
    """The same budget gives a decode call blocks of several pages and a
    call of many rows blocks of ONE page, with every head still in the
    step: 1 is legal and works."""
    spec = dict(hkv=2, group=1, page_size=8, int8=False)
    monkeypatch.setattr(
        pa_kernel, "_VMEM_BUDGET", 1 + 2 * pa_kernel._bytes_per_head(
            *_call_shapes(dict(spec, t=64))))
    assert pa_kernel.block_shape(1, 2, 2, 16, 8, 4, False) == (2, 16)
    assert pa_kernel.block_shape(64, 2, 2, 16, 8, 4, False) == (2, 1)
    _assert_matches_oracle(_case(
        np.random.default_rng(0), t=64, ctx=[90], max_pages=12, **spec))


#: The engine's call since PR 30: the stacked ``[L, N, Hkv, P, D]`` pool and
#: a layer index that the index maps read. The same blocks reach the same
#: body, so it equals the 4-D call on ``pool[l]`` bit for bit.
STACKED_CASES = {
    "decode": dict(t=1, ctx=[3, 20, None, 9], max_pages=16),
    "decode_first_layer": dict(t=1, ctx=[7, 12], max_pages=4, layer=0),
    "decode_layer_traced_under_jit": dict(
        t=1, ctx=[3, 20, None, 9], max_pages=16, traced=True),
    "verify_k4_rows_cross_a_page": dict(
        t=5, ctx=[17, 40, None], max_pages=8),
    "prefill_nothing_cached": dict(t=32, ctx=[32], max_pages=8),
    "prefill_128_cached": dict(t=32, ctx=[160], max_pages=24),
    "int8_decode": dict(t=1, ctx=[21, None, 5], max_pages=4, int8=True),
    "int8_verify_k4_traced": dict(
        t=5, ctx=[21, 13], max_pages=4, int8=True, traced=True),
    "gqa_decode": dict(t=1, ctx=[11, 30], max_pages=4, group=4),
    "gqa_prefill_128_cached_int8": dict(
        t=32, ctx=[160], max_pages=24, group=2, int8=True),
    "four_heads_in_blocks_of_two": dict(
        t=1, ctx=[21, 5], max_pages=4, hkv=4, heads=2),
}


def _stacked(rng, pool, layers, layer):
    """``pool`` as layer ``layer`` of a stack whose other layers hold
    other values, so a wrong layer index cannot pass."""
    other = rng.permutation(pool.reshape(-1)).reshape(pool.shape)
    return jnp.stack([jnp.asarray(pool if i == layer else np.roll(
        other, i, axis=0)) for i in range(layers)])


@pytest.mark.parametrize("name", list(STACKED_CASES))
def test_stacked_pool_call_equals_the_4d_call_on_its_layer(
        name, monkeypatch):
    spec = dict(STACKED_CASES[name])
    layer, traced = spec.pop("layer", 2), spec.pop("traced", False)
    heads = spec.pop("heads", None)
    spec = dict(dict(hkv=2, group=1, page_size=8, int8=False), **spec)
    rng = np.random.default_rng(len(name))
    q, kp, vp, ks, vs, table, start = _case(rng, **spec)
    if heads is not None:
        _force_heads(monkeypatch, heads, spec)
    kw = {} if ks is None else dict(k_scales=jnp.asarray(ks),
                                    v_scales=jnp.asarray(vs))
    rest = (jnp.asarray(table), jnp.asarray(start))
    flat = np.asarray(pa_kernel.paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), *rest, **kw))
    kst, vst = _stacked(rng, kp, 3, layer), _stacked(rng, vp, 3, layer)

    def call(l):
        return pa_kernel.paged_attention(
            jnp.asarray(q), kst, vst, *rest, layer=l, **kw)

    got = jax.jit(call)(jnp.int32(layer)) if traced else call(layer)
    np.testing.assert_array_equal(np.asarray(got), flat)
    assert not np.array_equal(np.asarray(call((layer + 1) % 3)), flat)


@pytest.mark.parametrize("kernel", ["pallas", "einsum"])
def test_functional_call_takes_the_stacked_pool_on_both_kernels(kernel):
    """``F.paged_attention(..., layer=l)``: the fused kernel reads the
    layer in place, the oracle slices it; each equals its own 4-D call."""
    rng = np.random.default_rng(11)
    q, kp, vp, ks, vs, table, start = _case(
        rng, t=3, hkv=2, group=2, page_size=8, int8=True)
    flat = _run(kernel, q, kp, vp, ks, vs, table, start)
    got = F.paged_attention(
        jnp.asarray(q), _stacked(rng, kp, 2, 1), _stacked(rng, vp, 2, 1),
        jnp.asarray(table), jnp.asarray(start), k_scales=jnp.asarray(ks),
        v_scales=jnp.asarray(vs), kernel=kernel, layer=1)
    np.testing.assert_array_equal(np.asarray(raw(got)), flat)


@pytest.mark.parametrize("stack,layer", [(True, None), (False, 0)],
                         ids=["stacked_without_layer", "flat_with_layer"])
def test_a_layer_index_goes_with_a_stacked_pool_and_only_with_it(
        stack, layer):
    rng = np.random.default_rng(0)
    q, kp, vp, _, _, table, start = _case(
        rng, t=1, hkv=2, group=1, page_size=8)
    if stack:
        kp, vp = kp[None], vp[None]
    with pytest.raises(ValueError, match="layer"):
        pa_kernel.paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(start), layer=layer)


@pytest.mark.parametrize("shape,heads", [
    # (hkv, rows8, d, p, kv_itemsize, has_scales) at the serving cell's
    # widths: decode and verify take every head, a prefill what fits
    ((16, 8, 128, 16, 2, False), 16),
    ((16, 8, 128, 16, 1, True), 16),
    ((8, 24, 128, 16, 2, False), 8),     # GQA 32/8, verify k=4
    ((16, 128, 128, 16, 2, False), 16),  # bucket 128
    ((16, 512, 128, 16, 2, False), 4),
    ((16, 1024, 128, 16, 2, False), 2),
    ((16, 1024, 128, 16, 1, True), 2),
    ((8, 2048, 128, 16, 2, False), 1),   # GQA 32/8 at bucket 512
    ((16, 8192, 128, 16, 2, False), 1),  # never none, whatever the rows
])
def test_heads_per_step_follows_the_calls_shapes(shape, heads):
    got = pa_kernel._heads_per_step(*shape)
    assert got == heads and shape[0] % got == 0
    assert (got == 1 or got * pa_kernel._bytes_per_head(*shape[1:])
            <= pa_kernel._VMEM_BUDGET)


@pytest.mark.parametrize("shape,pages", [
    # (rows8, d, p, hb, kv_itemsize, has_scales): 128 keys a block at the
    # serving cells' decode and verify shapes, fewer where the rows fill
    # the budget, never none
    ((8, 128, 16, 16, 2, False), 8),     # GPT-3 1.3B and Ouro-2.6B decode
    ((8, 128, 16, 16, 1, True), 8),      # the int8 pool with its scales
    ((24, 128, 16, 8, 2, False), 8),     # GQA 32/8, verify k=4
    ((8, 64, 32, 12, 2, False), 4),      # pages of 32 keys
    ((8, 128, 128, 16, 2, False), 1),    # a page as wide as a block
    ((8, 128, 256, 16, 2, False), 1),    # and wider: one page, not none
    ((512, 128, 16, 4, 2, False), 8),    # what a prefill bucket was
    ((1024, 128, 16, 2, 2, False), 8),
    ((2048, 128, 16, 1, 2, False), 8),
    ((2568, 128, 16, 1, 2, False), 4),   # the rows leave four pages,
    ((2584, 128, 16, 1, 2, False), 2),   # two,
    ((2600, 128, 16, 1, 2, False), 1),   # one
    ((8192, 128, 16, 1, 2, False), 1),   # never none, whatever the rows
])
def test_pages_per_block_follows_the_calls_shapes(shape, pages):
    rows8, d, p, hb, item, scales = shape
    got = pa_kernel._pages_per_block(*shape)
    assert got == pages and got & (got - 1) == 0
    assert got == 1 or got * p <= pa_kernel._BLOCK_KEYS
    assert (got == 1 or hb * pa_kernel._bytes_per_head(
        rows8, d, p, item, scales, got) <= pa_kernel._VMEM_BUDGET)
    assert (2 * got * p > pa_kernel._BLOCK_KEYS
            or hb * pa_kernel._bytes_per_head(
                rows8, d, p, item, scales, 2 * got)
            > pa_kernel._VMEM_BUDGET)


def test_bytes_per_head_covers_the_walks_buffers_under_the_chips_limit():
    """What the estimate has to stay above is what the kernel allocates
    for sure: both buffers of K and V at the stored dtype, the scale rows,
    the double-buffered query and result blocks and the m / l / acc
    scratch; Mosaic's temporaries come on top (PR 27, my chip run:
    "Scoped allocation with size 21.47M and limit 16.00M" for a body that
    compiled alone). At the cells' bf16 shapes the whole step stays under
    a half of the budget, and the budget under the chip's 16 MiB."""
    for rows8, d, p, hb, item, scales in (
            (8, 128, 16, 16, 2, False), (8, 128, 16, 16, 1, True),
            (512, 128, 16, 4, 2, False)):
        ppb = pa_kernel._pages_per_block(rows8, d, p, hb, item, scales)
        allocated = hb * (2 * 2 * ppb * p * d * item      # K, V buffers
                          + 2 * 2 * rows8 * d * 4         # q, result
                          + rows8 * (2 * 128 + d) * 4)    # m, l, acc
        if scales:
            allocated += 2 * 2 * ppb * 8 * max(128, hb * p) * 4
        estimate = hb * pa_kernel._bytes_per_head(
            rows8, d, p, item, scales, ppb)
        assert allocated < estimate <= pa_kernel._VMEM_BUDGET
        if rows8 == 8 and not scales:
            assert estimate < pa_kernel._VMEM_BUDGET // 2
    assert pa_kernel._VMEM_BUDGET < 16 * 2 ** 20


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
# the tail prefill's block read: blocked kernel vs einsum oracle
# ---------------------------------------------------------------------------

#: The prefill's cases, as the paged ones above: ``t`` rows (the bucket)
#: whose last sits at context ``ctx`` - 1, over a table of ``max_pages``
#: pages of 8. ``q`` / ``pool``: the dtypes the two arrive in (bf16 with
#: bf16 is the pair that enters the first product unwidened); ``blocks``:
#: the largest (row, key) blocks, made small so that a test's few rows are
#: several blocks.
BLOCK_READ_CASES = {
    "nothing_cached": dict(t=32, ctx=32, max_pages=8),
    "128_cached": dict(t=32, ctx=160, max_pages=24),
    "short_of_its_bucket": dict(t=32, ctx=40, max_pages=12),
    "cached_len_plus_bucket_at_max_length": dict(t=32, ctx=96, max_pages=12),
    "gqa": dict(t=32, ctx=48, max_pages=8, group=4),
    "int8": dict(t=32, ctx=160, max_pages=24, int8=True),
    "int8_gqa_bf16_queries": dict(
        t=32, ctx=72, max_pages=12, group=2, int8=True, q="bfloat16"),
    "bf16_queries_on_a_bf16_pool": dict(
        t=32, ctx=160, max_pages=24, q="bfloat16", pool="bfloat16"),
    "float32_queries_on_a_bf16_pool": dict(
        t=32, ctx=48, max_pages=8, pool="bfloat16"),
    "bf16_queries_on_a_float32_pool": dict(
        t=32, ctx=48, max_pages=8, q="bfloat16"),
    "rows_and_keys_that_no_block_divides": dict(
        t=40, ctx=90, max_pages=13, group=3),
    "four_row_blocks_three_key_blocks": dict(
        t=64, ctx=300, max_pages=40, group=2, blocks=(32, 128)),
    "row_blocks_of_gqa_rows_straddle_positions": dict(
        t=24, ctx=280, max_pages=40, group=3, blocks=(16, 128)),
    "every_key_block_live_for_the_last_row_block_only": dict(
        t=256, ctx=256, max_pages=32, hkv=1, blocks=(64, 128)),
    "int8_several_blocks": dict(
        t=48, ctx=200, max_pages=40, int8=True, blocks=(16, 128)),
}


def _slot_keys(pool, row):
    """``KVPool.attend_block``'s gather, in numpy: one slot's pages
    [N, Hkv, P, ...] -> contiguous keys [Hkv, MP * P, ...]."""
    g = np.swapaxes(pool[row], 0, 1)
    return g.reshape(g.shape[0], -1, *g.shape[3:])


@pytest.mark.parametrize("name", list(BLOCK_READ_CASES))
def test_block_read_matches_einsum_oracle(name, monkeypatch):
    spec = dict(BLOCK_READ_CASES[name])
    blocks = spec.pop("blocks", None)
    qdt, pdt = spec.pop("q", "float32"), spec.pop("pool", "float32")
    ctx = spec.pop("ctx")
    spec = dict(dict(hkv=2, group=1, page_size=8, int8=False), **spec)
    q, kp, vp, ks, vs, table, start = _case(
        np.random.default_rng(len(name)), ctx=[ctx], **spec)
    if blocks:
        monkeypatch.setattr(pf_kernel, "_BLOCK_Q", blocks[0])
        monkeypatch.setattr(pf_kernel, "_BLOCK_K", blocks[1])
        assert pf_kernel._block_sizes(
            spec["t"] * spec["group"], table.shape[1] * 8) == blocks
        assert table.shape[1] * 8 > blocks[1]
    q = jnp.asarray(q, qdt)
    if not spec["int8"]:
        kp, vp = jnp.asarray(kp, pdt), jnp.asarray(vp, pdt)
    # the oracle on the values the kernel is handed, widened
    ref = _run("einsum", q.astype(jnp.float32), kp, vp, ks, vs, table, start)
    kw = {} if ks is None else dict(
        k_scales=jnp.asarray(_slot_keys(ks, table[0])),
        v_scales=jnp.asarray(_slot_keys(vs, table[0])))
    got = np.asarray(pf_kernel.prefill_attention(
        q[0], jnp.asarray(_slot_keys(np.asarray(kp), table[0])),
        jnp.asarray(_slot_keys(np.asarray(vp), table[0])),
        jnp.int32(start[0]), **kw))[None]
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))
    # the paged kernel, which the prefill ran until PR 34, agrees too
    paged = _run("pallas", q.astype(jnp.float32), kp, vp, ks, vs, table,
                 start)
    np.testing.assert_allclose(got, paged, atol=2e-5, rtol=1e-4)


def test_block_read_skips_key_blocks_past_the_causal_horizon(monkeypatch):
    """A key block wholly past its row block's horizon is neither
    multiplied (NaNs there change nothing; masked to p = 0 they would
    still poison the second product) nor fetched (the index map stands
    still on the last live block)."""
    monkeypatch.setattr(pf_kernel, "_BLOCK_K", 128)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((128, 2, 16)), jnp.float32)
    k, v = (rng.standard_normal((2, 512, 16)).astype(np.float32)
            for _ in range(2))
    clean = np.asarray(pf_kernel.prefill_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.int32(100)))
    # the last row sits at position 227, in key block 1 of 4
    assert int(pf_kernel._last_key_block(jnp.int32(100), 0, 128, 128, 1,
                                         4)) == 1
    k[:, 256:], v[:, 256:] = np.nan, np.nan
    dirty = np.asarray(pf_kernel.prefill_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.int32(100)))
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("rows,keys,want", [
    (1024, 2048, (512, 1024)),  # the serving cell's buckets
    (512, 2048, (512, 1024)),
    (128, 2048, (128, 1024)),
    (2048, 2048, (512, 1024)),  # GQA 32/8 at bucket 512
    (40, 104, (48, 128)),       # keys padded to the lane width
    (96, 1536, (96, 512)),
    (96, 768, (96, 256)),
    (8, 1152, (16, 128)),
])
def test_block_sizes_follow_the_calls_shapes(rows, keys, want):
    assert pf_kernel._block_sizes(rows, keys) == want


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_pool_block_read_gathers_its_slot_and_its_layer(kv_dtype):
    """``KVPool.attend_block`` end to end on a stacked pool: write a
    prefix and a tail through ``write_block``, read the tail back on both
    kernels. Other layers and other slots' pages hold other values, so a
    wrong gather cannot pass."""
    rng = np.random.default_rng(3)
    layers, hkv, p, d, mp = 3, 2, 8, 16, 12
    pool = KVPool.zeros(layers, 1 + 2 * mp, hkv, p, d, kv_dtype)
    row = np.zeros(mp, np.int32)
    row[:7] = rng.permutation(np.arange(1, 1 + 2 * mp))[:7]
    row = jnp.asarray(row)
    for layer in range(layers):
        for at, n in ((0, 16), (16, 40)):  # 2 cached pages, a tail of 40
            k, v = (jnp.asarray(rng.standard_normal((1, n, hkv, d)),
                                jnp.float32) for _ in range(2))
            pool = pool.write_block(layer, k, v, row, jnp.int32(at),
                                    jnp.int32(at + n - 3))
    q = jnp.asarray(rng.standard_normal((1, 40, 2 * hkv, d)), jnp.float32)
    got, ref = (np.asarray(raw(pool.attend_block(
        q, 1, row, jnp.int32(16), kernel))) for kernel in (
        "pallas", "einsum"))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))
    other = np.asarray(raw(pool.attend_block(
        q, 2, row, jnp.int32(16), "pallas")))
    assert not np.allclose(other, got, atol=1e-3)


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


def test_engine_greedy_bit_equal_over_a_cached_prefix_and_compile_gate(
        model):
    """The prefill's block read behind a cached prefix (``cached_len`` 16
    and 24 on the later prompts, tails in two more buckets): greedy streams
    bit-equal to the einsum engine's, and the program count is still the
    prefill buckets used + ONE decode + ONE verify."""
    rng = np.random.default_rng(9)
    shared = _prompt(rng, 26)
    prompts = [shared, np.concatenate([shared[:16], _prompt(rng, 3)]),
               np.concatenate([shared[:24], _prompt(rng, 35)])]
    cfg = dict(num_slots=2, max_length=96, page_size=8, prefix_cache=True,
               speculate_k=2, spec_adaptive=False,
               prompt_buckets=(8, 32, 64))
    streams, engines = [], []
    for kernel in ("einsum", "pallas"):
        eng = DecodeEngine(model, EngineConfig(attn_kernel=kernel, **cfg))
        # one at a time: a later prompt finds the earlier one's pages
        streams.append([_drain(eng, [p], max_new=6)[0] for p in prompts])
        engines.append(eng)
    for a, b in zip(*streams):
        np.testing.assert_array_equal(a, b)
    for eng in engines:
        st = eng.stats()
        assert st["prefix_hit_tokens"] == 16 + 24
        buckets_used = [n for n in st["compiled"] if n.startswith("prefill_")]
        assert len(buckets_used) == 3, st["compiled"]
        assert st["compile_count"] == len(buckets_used) + 2, st["compiled"]


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
