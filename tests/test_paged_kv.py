"""Paged KV cache: allocator, prefix sharing, speculation (docs/SERVING.md).

Gates the paged-serving promises on top of test_decode_engine.py's
contiguous-era guarantees: the free-list allocator never double-allocates
and never leaks (refcounts reach zero on eviction), copy-on-write prefix
sharing keeps shared pages immutable while requests diverge after the
shared blocks, greedy output is BIT-EQUAL with prefix caching and
speculative decode on or off, and the compiled-program count stays O(1)
in requests/lengths (prefill buckets + one decode + one verify).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.inference as inference
from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                         PagePool, PrefixRegistry,
                                         SamplingParams)
from paddle_tpu.inference.kv_pool import KVPool
from paddle_tpu.distributed.grad_comm import quantize_absmax
from paddle_tpu.text.generation import prompt_lookup_draft
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

VOCAB = 61


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


def _pool_invariant(pool: PagePool):
    live = int((pool._ref[1:] > 0).sum())
    assert pool.available() + live == pool.num_pages - 1
    free_set = set(pool._free)
    assert len(free_set) == len(pool._free), "free list has duplicates"
    assert 0 not in free_set, "trash page on the free list"
    for p in free_set:
        assert pool.refcount(p) == 0, f"page {p} free but referenced"


# ---------------------------------------------------------------------------
# allocator unit tests
# ---------------------------------------------------------------------------


def test_pagepool_never_double_allocates():
    pool = PagePool(64)
    rng = np.random.default_rng(0)
    held = []  # list of allocations (lists of page ids)
    for _ in range(500):
        if held and rng.random() < 0.45:
            for p in held.pop(rng.integers(len(held))):
                pool.decref(p)
        else:
            got = pool.alloc(int(rng.integers(1, 6)))
            if got is not None:
                held.append(got)
        live = [p for pages in held for p in pages]
        assert len(live) == len(set(live)), "page handed out twice"
        _pool_invariant(pool)
    for pages in held:
        for p in pages:
            pool.decref(p)
    assert pool.available() == pool.num_pages - 1


def test_pagepool_refcount_discipline():
    pool = PagePool(8)
    (a,) = pool.alloc(1)
    pool.incref(a)
    pool.decref(a)
    assert pool.refcount(a) == 1 and a not in pool._free
    pool.decref(a)
    assert pool.refcount(a) == 0 and a in pool._free
    with pytest.raises(ValueError):
        pool.decref(a)  # already free
    with pytest.raises(ValueError):
        pool.incref(a)  # sharing can only extend a live allocation
    assert pool.alloc(100) is None  # never partial
    assert pool.available() == pool.num_pages - 1


def test_prefix_registry_lru_eviction_drops_refcounts():
    pool = PagePool(16)
    reg = PrefixRegistry(pool, capacity=2)
    pages = pool.alloc(3)
    keys = [bytes([i]) * 16 for i in range(3)]
    for k, p in zip(keys, pages):
        reg.register(k, p)
        pool.decref(p)  # registry reference keeps it alive
    # capacity 2: the oldest entry was evicted and its page freed
    assert len(reg) == 2
    assert pool.refcount(pages[0]) == 0 and pages[0] in pool._free
    assert reg.lookup_chain(keys[:1]) == []
    hit = reg.lookup_chain([keys[1]])
    assert hit == [pages[1]] and pool.refcount(pages[1]) == 2
    pool.decref(pages[1])
    reg.clear()
    assert pool.available() == pool.num_pages - 1
    _pool_invariant(pool)


def test_prefix_block_keys_chain():
    p = np.arange(48, dtype=np.int64)
    a = PrefixRegistry.block_keys(p, 16)
    b = PrefixRegistry.block_keys(p.copy(), 16)
    assert a == b and len(a) == 3
    q = p.copy()
    q[20] += 1  # mutate block 1: its key and every later key must change
    c = PrefixRegistry.block_keys(q, 16)
    assert c[0] == a[0] and c[1] != a[1] and c[2] != a[2]
    # chain hash: equal block contents at different depths don't collide
    r = np.concatenate([p[16:32], p[16:32], p[16:32]])
    d = PrefixRegistry.block_keys(r, 16)
    assert len(set(d)) == 3


def test_prompt_lookup_draft():
    ctx = np.array([5, 6, 7, 1, 2, 5, 6, 7, 9, 4, 5, 6, 7], np.int64)
    d = prompt_lookup_draft(ctx, 3)
    # most recent earlier [5, 6, 7] is at index 5 -> followed by 9, 4, 5
    assert d.tolist() == [9, 4, 5]
    assert prompt_lookup_draft(np.array([1, 2, 3, 4]), 3) is None
    short = prompt_lookup_draft(np.array([8, 1, 8]), 4)
    assert short.tolist() == [1, 8, 8, 8]  # padded with the last token


# ---------------------------------------------------------------------------
# engine-level guarantees
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_greedy_bitequal_prefix_cache_on_off(model):
    rng = np.random.default_rng(3)
    shared = _prompt(rng, 32)
    prompts = [np.concatenate([shared, _prompt(rng, 6)]) for _ in range(5)]
    off = DecodeEngine(model, EngineConfig(
        num_slots=2, max_length=64, page_size=8, prefix_cache=False))
    ref = _drain(off, prompts)
    on = DecodeEngine(model, EngineConfig(
        num_slots=2, max_length=64, page_size=8, prefix_cache=True))
    out = _drain(on, prompts)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
    assert on.stats()["prefix_hit_tokens"] > 0
    assert off.stats()["prefix_hit_tokens"] == 0


@pytest.mark.slow
def test_greedy_bitequal_speculation_on_off(model):
    rng = np.random.default_rng(4)
    # repetitive prompts give the n-gram draft something to match
    motif = _prompt(rng, 5)
    prompts = [np.concatenate([np.tile(motif, 5), _prompt(rng, 3)])
               for _ in range(3)]
    off = DecodeEngine(model, EngineConfig(
        num_slots=3, max_length=96, page_size=8, speculate_k=0))
    ref = _drain(off, prompts, max_new=16)
    on = DecodeEngine(model, EngineConfig(
        num_slots=3, max_length=96, page_size=8, speculate_k=3,
        spec_adaptive=False))
    out = _drain(on, prompts, max_new=16)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
    st = on.stats()
    assert st["verify_steps"] > 0 and st["spec_accepted"] > 0


def test_cow_divergence_after_shared_prefix(model):
    """Requests sharing full prompt blocks must diverge freely after the
    shared prefix without corrupting it for later readers."""
    rng = np.random.default_rng(5)
    shared = _prompt(rng, 16)  # exactly 2 full pages of 8
    tails = [_prompt(rng, 4) for _ in range(3)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    ref_eng = DecodeEngine(model, EngineConfig(
        num_slots=1, max_length=64, page_size=8, prefix_cache=False))
    ref = _drain(ref_eng, prompts)
    eng = DecodeEngine(model, EngineConfig(
        num_slots=3, max_length=64, page_size=8, prefix_cache=True))
    # all three run CONCURRENTLY off the same shared pages
    out = _drain(eng, prompts)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(out[0][16:], out[1][16:]), (
        "distinct tails should diverge")
    # a late reader of the shared prefix still sees the original blocks
    # (decode writes of the finished requests never touched them)
    late = _drain(eng, [prompts[0]])
    np.testing.assert_array_equal(late[0], ref[0])
    assert eng.stats()["prefix_hit_tokens"] > 0


def test_shared_pages_counted_and_released(model):
    rng = np.random.default_rng(6)
    shared = _prompt(rng, 16)
    prompts = [np.concatenate([shared, _prompt(rng, 4)]) for _ in range(4)]
    eng = DecodeEngine(model, EngineConfig(
        num_slots=4, max_length=64, page_size=8, prefix_cache=True))
    rids = [eng.submit(p, SamplingParams(max_new_tokens=6))
            for p in prompts]
    eng.step()  # admit everyone
    assert eng.pool.shared_pages() == 2  # the two full prefix pages
    eng.run()
    for r in rids:
        eng.result(r)
    # registry still pins the prefix; dropping it frees every page
    eng.release_prefix_cache()
    assert eng.pool.available() == eng.pool.num_pages - 1
    _pool_invariant(eng.pool)


@pytest.mark.slow
def test_admission_waits_for_pages_then_recovers(model):
    """A pool too small for all slots at once must queue, not deadlock or
    double-book: every request still completes."""
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, 20) for _ in range(6)]
    # each request needs ceil((20 + 8) / 8) = 4 pages; 9 usable pages
    # -> at most 2 requests in flight although there are 4 slots
    eng = DecodeEngine(model, EngineConfig(
        num_slots=4, max_length=64, page_size=8, num_pages=10,
        prefix_cache=False))
    outs = _drain(eng, prompts)
    assert len(outs) == 6
    assert eng.stats()["peak_running"] <= 2
    assert eng.pool.available() == 9
    ref_eng = DecodeEngine(model, EngineConfig(
        num_slots=1, max_length=64, page_size=8, prefix_cache=False))
    ref = _drain(ref_eng, prompts)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_compile_count_o1_with_speculation(model):
    """Compiled programs: one per used prefill tail bucket + ONE decode +
    ONE verify — invariant in request count and request lengths."""
    rng = np.random.default_rng(8)
    motif = _prompt(rng, 4)
    eng = DecodeEngine(model, EngineConfig(
        num_slots=3, max_length=96, page_size=8, speculate_k=3,
        spec_adaptive=False, prefix_cache=True))
    prompts = ([np.concatenate([np.tile(motif, 4), _prompt(rng, 2)])
                for _ in range(4)]
               + [np.tile(motif, 7)[:26] for _ in range(3)])
    _drain(eng, prompts, max_new=12)
    st = eng.stats()
    buckets_used = sum(1 for name in st["compiled"]
                       if name.startswith("prefill_"))
    assert st["verify_steps"] > 0
    assert st["compile_count"] == buckets_used + 2, st["compiled"]
    before = st["compile_count"]
    # more work with the same shapes -> zero new programs
    _drain(eng, [np.concatenate([np.tile(motif, 4), _prompt(rng, 2)])
                 for _ in range(4)], max_new=12)
    assert eng.stats()["compile_count"] == before


@pytest.mark.slow
def test_quick_churn_no_leaked_pages(model):
    """Tier-1-sized churn: random lengths and budgets through a small
    pool; the free list must account for every page afterwards."""
    rng = np.random.default_rng(9)
    eng = DecodeEngine(model, EngineConfig(
        num_slots=3, max_length=64, page_size=8, prefix_cache=True,
        prefix_registry_blocks=6))
    shared = _prompt(rng, 24)
    for round_ in range(4):
        prompts = [
            np.concatenate([shared[:8 * rng.integers(0, 4)],
                            _prompt(rng, int(rng.integers(1, 12)))])
            for _ in range(5)
        ]
        _drain(eng, prompts, max_new=int(rng.integers(1, 8)))
        _pool_invariant(eng.pool)
        assert len(eng.registry) <= 6
    eng.release_prefix_cache()
    assert eng.pool.available() == eng.pool.num_pages - 1
    # freed slots must leave zeroed page-table rows (writes -> trash)
    assert (eng._tables == 0).all()


def _scatter_token_write(cache, scales, layer, kv, tables, positions, int8,
                         page_size):
    """The token write as it was until PR 30: one scatter whose update
    window is ``[Hkv, D]``. Kept here as the oracle of the in-place
    writes that replaced it."""
    pg = jnp.take_along_axis(tables, positions // page_size, axis=1)
    off = positions % page_size
    if int8:
        q, scale = quantize_absmax(kv, axis=-1)
        return (cache.at[layer, pg, :, off, :].set(q.astype(cache.dtype)),
                scales.at[layer, pg, :, off].set(scale[..., 0]))
    return cache.at[layer, pg, :, off, :].set(kv.astype(cache.dtype)), scales


def _pool_token_write(cache, scales, layer, kv, tables, positions, int8,
                      page_size):
    """``KVPool.write_tokens`` on a pool whose K and V both start as
    ``cache`` and both take ``kv``."""
    assert (scales is not None) == int8 and cache.shape[3] == page_size
    return KVPool(cache, cache, scales, scales).write_tokens(
        layer, kv, kv, tables, positions)


#: (T, position of each slot's first row; None = an idle slot, whose zeroed
#: table row sends its write to the trash page)
TOKEN_WRITES = {
    "decode": (1, [5, 16, 40]),
    "decode_idle_slots_write_the_trash_page": (1, [None, 23, None, None]),
    "decode_every_slot_idle": (1, [None, None]),
    "decode_first_and_last_offset_of_a_page": (1, [8, 15, 0]),
    "verify_k4": (5, [3, 17]),
    "verify_k4_rows_cross_a_page_boundary": (5, [6, 13, None]),
    "verify_k4_ends_on_the_tables_last_page": (5, [43, None]),
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("name", list(TOKEN_WRITES))
def test_token_write_in_place_equals_the_scatter_it_replaced(
        name, kv_dtype, jit):
    t, first = TOKEN_WRITES[name]
    rng = np.random.default_rng(len(name) + t)
    layers, layer, hkv, p, d, mp = 3, 1, 2, 8, 16, 6
    s, int8 = len(first), kv_dtype == "int8"
    n = 1 + s * mp
    tables = np.zeros((s, mp), np.int32)
    free = iter(rng.permutation(np.arange(1, n)))
    for i, pos in enumerate(first):
        if pos is not None:
            for j in range((pos + t - 1) // p + 1):
                tables[i, j] = next(free)
    positions = jnp.asarray(
        [[0 if pos is None else pos + j for j in range(t)]
         for pos in first], jnp.int32)
    shape = (layers, n, hkv, p, d)
    if int8:
        cache = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = jnp.asarray(rng.uniform(0.01, 0.03, shape[:-1]),
                             jnp.float32)
    else:
        cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        scales = None
    kv = jnp.asarray(rng.standard_normal((s, t, hkv, d)), jnp.float32)
    args = (cache, scales, layer, kv, jnp.asarray(tables), positions, int8, p)
    new, old = _pool_token_write, _scatter_token_write
    if jit:  # both: a fused quantize rounds its scale in another order
        new, old = (jax.jit(f, static_argnums=(2, 6, 7)) for f in (new, old))
    got, want = new(*args), old(*args)
    # several idle slots write the trash page's first row, in the scatter
    # in no stated order: every other page has to agree, bit for bit
    for g, w, before in zip(
            (got.k, got.k_scales, got.v, got.v_scales), want * 2,
            (cache, scales) * 2):
        if w is None:
            assert g is None
            continue
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        live = [pos is not None for pos in first]
        if any(live):  # something was written, and only in this layer
            assert not np.array_equal(g[layer], np.asarray(
                before, np.float32)[layer])
        np.testing.assert_array_equal(
            np.delete(g, layer, 0),
            np.delete(np.asarray(before, np.float32), layer, 0))
        # the trash page took nothing but row 0 (an idle slot's position)
        np.testing.assert_array_equal(
            g[layer, 0, :, 1:], np.asarray(before, np.float32)[
                layer, 0, :, 1:])


def test_transformer_paged_cache_matches_static():
    """nn-layer PagedCache (pool + identity page table) is bit-identical
    to the contiguous static cache — including an odd page size that
    does not divide max_length."""
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.framework.op import raw
    from paddle_tpu.nn.layers.transformer import (TransformerDecoder,
                                                  TransformerDecoderLayer)

    import paddle_tpu as paddle

    paddle.seed(3)
    B, T, E, H = 2, 5, 16, 4
    dec = TransformerDecoder(
        TransformerDecoderLayer(E, H, 32, dropout=0.0), 2)
    dec.eval()
    rng = np.random.default_rng(0)
    x = Tensor(jnp.asarray(rng.standard_normal((B, T, E)), jnp.float32))
    mem = Tensor(jnp.asarray(rng.standard_normal((B, 3, E)), jnp.float32))
    static = dec.gen_cache(mem, max_length=8)
    paged = dec.gen_cache(mem, max_length=8, page_size=3)
    pool_k = raw(paged[0][0].k)
    assert pool_k.shape == (1 + B * 3, H, 3, E // H)  # trash page + 3/row
    for t in range(T):
        xt = Tensor(raw(x)[:, t:t + 1])
        os_, static = dec(xt, mem, cache=static, cache_position=t)
        op, paged = dec(xt, mem, cache=paged, cache_position=t)
        np.testing.assert_array_equal(np.asarray(raw(os_)),
                                      np.asarray(raw(op)))


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_churn_soak_no_leaks(model):
    """Long mixed soak: prefix sharing + speculation + tiny pool +
    registry eviction pressure, with the allocator invariant checked
    after every round and zero pages leaked at the end."""
    rng = np.random.default_rng(10)
    eng = DecodeEngine(model, EngineConfig(
        num_slots=4, max_length=96, page_size=8, num_pages=40,
        prefix_cache=True, prefix_registry_blocks=8, speculate_k=3,
        spec_adaptive=False))
    shared = _prompt(rng, 48)
    for round_ in range(12):
        prompts = []
        for _ in range(int(rng.integers(3, 8))):
            cut = 8 * int(rng.integers(0, 7))
            prompts.append(np.concatenate(
                [shared[:cut], _prompt(rng, int(rng.integers(1, 16)))]))
        _drain(eng, prompts, max_new=int(rng.integers(1, 12)),
               eos_token_id=int(rng.integers(1, VOCAB)))
        _pool_invariant(eng.pool)
        if round_ % 5 == 4:
            eng.release_prefix_cache()
            assert eng.pool.available() == eng.pool.num_pages - 1
    eng.release_prefix_cache()
    assert eng.pool.available() == eng.pool.num_pages - 1
    assert (eng._tables == 0).all()
