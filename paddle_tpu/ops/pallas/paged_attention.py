"""Fused paged-attention decode/verify — Pallas TPU kernel.

The serving decode hot loop above this kernel (paged KV pool, speculative
verify, int8 KV wire) is shape-static; the einsum reference path
(``paddle_tpu/nn/functional/attention.py::_paged_attention_op``) pays for
that by materializing the gathered K/V pages as f32 ``[S, Hkv, MP*P, D]``
tensors plus a dense ``[S, Hkv, G, T, MP*P]`` logits tensor in HBM every
step, and — under int8 KV — by a separate whole-pool dequant pass.

This kernel fuses the whole per-slot pipeline into one Pallas program
that walks the KV that is live, not the page table's capacity:

  * the grid is one step a slot (times the blocks of kv heads, where the
    rows leave room for fewer than all of them: ``_heads_per_step``). The
    pools stay in HBM (``memory_space=pl.ANY``) and the BODY walks the
    slot's page slots up to its last live one, ``(start_position + T - 1)
    // P``, in blocks of ``_pages_per_block`` pages (8 pages = 128 keys at
    the serving cells' shapes; from the call's shapes alone, against the
    same VMEM budget as the head block): an idle slot costs one block of
    one page's copy, not the table's width, and no grid step is skipped
    because none is made;
  * page-table-aware gather by manual DMA: a page of the ``[N, Hkv, P,
    D]`` pool is contiguous over its kv heads, so each live page slot of
    a block is ONE ``pltpu.make_async_copy`` of ``hb`` heads of page
    ``page_table[s, j]`` into its place in a ``[hb, ppb, P, D]`` VMEM
    buffer, at the STORED dtype; the gathered f32 copies never exist.
    Two buffers: the next block's copies (or the first block's of the
    NEXT grid step) are started before this block's products;
  * GQA-native query folding: the G query heads sharing a kv head ride in
    the kernel's row dimension (``rows = T * G``) — kv heads are never
    replicated in HBM;
  * online (streaming) softmax across the blocks: running max /
    denominator / accumulator live in VMEM scratch, so no ``[.., MP*P]``
    logits tensor is written to HBM; a block's logits are ``[hb, rows,
    128]``, a full lane width, and each of its two products meets the MXU
    with 128 keys. Accumulation, logits, softmax state and probabilities
    are float32; bf16 queries meet a bf16 pool unwidened in the first
    product (the same products exactly); into the second the float32
    probabilities go as their three bf16 terms against V's bf16 values
    (a bf16 or int8 pool; a float32 pool meets them in float32), which
    is the float32 product in one pass over V and not the one bf16 term
    that the MXU takes of a float32 operand by default;
  * fused int8 dequant: when per-[page, head] absmax scales are passed, a
    key's scale multiplies its logit and a value's scale its probability
    on the way into the second product (one factor of a whole row of K or
    V either way) — the f32 pool is never materialized, in HBM or VMEM;
  * decode (T=1) and speculative verify (T=k+1) are the SAME kernel: all
    T positions score in one pass, each row masked at its own causal
    horizon ``start_position + t``; a block-diffusion model's block pass
    (T = the block length, ``block`` > 1) rounds that horizon up to the
    end of the row's block of ``block`` positions (``block_horizon``), so
    that every row of a block sees all of its keys. It is correct at any T, and until PR
    34 the engine's tail prefill (S=1, T=bucket) ran it too, at under 2%
    of the MXU: a prefill wants many rows against long contiguous key
    blocks, and now gathers its slot's pages and runs
    ``prefill_attention.py``;
  * the engine's stacked ``[L, N, Hkv, P, D]`` pool is read in place: the
    layer index travels as a scalar-prefetch operand to where a page's
    copy starts, so no caller slices a layer out (a slice is a copy of
    1/L of the pool before every call);
  * page slots of a slot's last block past its last live page are not
    fetched: their keys mask themselves by position, and their V rows (or
    V's scales) are zeroed in the buffer, since ``0 x`` what VMEM held is
    NaN for a NaN.

The einsum op remains the reference oracle: greedy argmax must agree
everywhere (tests/test_pallas_attention.py), raw outputs agree to f32
tolerance (online vs dense softmax differ in ulps only, and so do two
walks that rescale at other block edges).

Runs off-TPU via ``interpret=True`` (the default there), per the repo's
robustness rule that every Pallas call site declares its interpret mode
(scripts/check_robustness.py); on a TPU it compiles for real or fails.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mask_fill_value(dtype=jnp.float32) -> float:
    """Dtype-aware masked-logit fill, shared by the einsum oracle and the
    Pallas kernel so masked-logit semantics cannot drift between paths.

    Half of ``finfo.min``: large enough that ``exp(fill - row_max)``
    underflows to exactly 0.0 for any realistic logit (so masked keys
    contribute nothing to either the dense or the online softmax), while
    ``fill - row_max`` and the online-softmax rescale ``exp(m_prev - m_new)``
    stay finite even when a row is still all-masked (m_prev == fill).
    """
    return float(jnp.finfo(jnp.dtype(dtype)).min) * 0.5


def _ceil8(n):
    return max(8, (n + 7) // 8 * 8)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


#: What one call's grid step may take of the 16 MiB of VMEM that a kernel
#: may hold on a v5e; the margin is for what ``_bytes_per_head`` leaves out.
_VMEM_BUDGET = 14 * 2 ** 20

#: The most keys in a block of pages: one vreg's lanes and one pass of the
#: MXU's 128 columns. The body's work is the BLOCK's, whatever is live in
#: it, so a wider block adds dead keys to a slot's last one, and a
#: narrower one more waits on copies. Swept on a v5e at the serving cells'
#: shapes (scripts/paged_attention_trace.py --block-keys; PERF.md, PR 36;
#: ms a call at 64 | 128 | 256 keys): every slot idle 0.0078 | 0.0093 |
#: 0.0125, the GPT cell's contexts 0.0270 | 0.0243 | 0.0262, a full pool
#: 0.218 | 0.179 | 0.179, the looped cell's 0.0368 | 0.0315 | 0.0316.
_BLOCK_KEYS = 128


def _bytes_per_head(rows8, d, p, kv_itemsize, has_scales, ppb=1):
    """VMEM that one kv head adds to a grid step whose blocks are ``ppb``
    pages: the query and result blocks (float32, double-buffered), the
    m / l / acc scratch, the body's temporaries (logits, probabilities and
    their selects: about four ``[rows8, keys]`` float32 arrays, a lane
    width at least), and a page's share ``ppb`` times: K and V in both
    buffers at the stored dtype, a float32 copy of each (what a pair that
    meets in float32 is widened to), and with scales their rows in both
    buffers (counted at a padded tile a head). The kernel compiled ALONE
    needs less, because XLA then keeps the small query and result arrays
    in VMEM and nothing double-buffers them: a lone compile that passes
    proves nothing about the budget (PR 27, on the chip)."""
    per_head = 4 * rows8 * (4 * d + 2 * 128 + d + 4 * max(128, ppb * p))
    per_page = 2 * 2 * p * d * kv_itemsize + 2 * p * d * 4
    if has_scales:
        per_page += 2 * 2 * p * 128 * 4
    return per_head + ppb * per_page


def _heads_per_step(hkv, rows8, d, p, kv_itemsize, has_scales):
    """How many kv heads one grid step takes: the most that divides
    ``hkv`` and keeps what grows with it under ``_VMEM_BUDGET`` at one
    page a block. Decode and verify (8-16 rows) take every head; a call
    of a prefill's row count takes a few or one."""
    hb = max(1, min(hkv, _VMEM_BUDGET // _bytes_per_head(
        rows8, d, p, kv_itemsize, has_scales)))
    while hkv % hb:
        hb -= 1
    return hb


def _pages_per_block(rows8, d, p, hb, kv_itemsize, has_scales):
    """How many pages one block of the body's walk holds: the largest
    power of two, up to ``_BLOCK_KEYS`` keys, that ``hb`` heads keep under
    ``_VMEM_BUDGET``. 8 pages of 16 keys in decode and verify at the
    serving cells' shapes; 1 where the rows leave no more."""
    ppb = 1
    while (2 * ppb * p <= _BLOCK_KEYS and hb * _bytes_per_head(
            rows8, d, p, kv_itemsize, has_scales, 2 * ppb) <= _VMEM_BUDGET):
        ppb *= 2
    return ppb


def block_shape(t, heads, kv_heads, d, p, kv_itemsize, has_scales):
    """``(hb, ppb)`` of a call of ``t`` rows a slot: the kv heads a grid
    step takes and the pages a block of its walk holds, from the call's
    shapes alone. The engine counts the page slots its passes walked with
    the same ``ppb`` (``DecodeEngine.kv_block_pages``)."""
    rows8 = _ceil8(t * (heads // kv_heads))
    hb = _heads_per_step(kv_heads, rows8, d, p, kv_itemsize, has_scales)
    return hb, _pages_per_block(rows8, d, p, hb, kv_itemsize, has_scales)


def block_horizon(position, block):
    """The last key position a query at ``position`` sees when positions
    are counted in blocks of ``block`` from 0 and a block's queries see
    all of its keys: the end of the query's block. ``block`` 1 is plain
    causal and returns ``position`` itself, traced as before."""
    if block == 1:
        return position
    return (position // block + 1) * block - 1


def _last_live_page(sp_ref, s_i, t, page_size, num_page_slots, block=1):
    """The last page slot that any query row of slot ``s_i`` can see: row
    ``t - 1`` sits at position ``start_position + t - 1``, whose horizon is
    the end of its block. Everything past it is masked for every row, so
    the walk neither fetches nor multiplies it. An idle slot (position 0)
    has one live page slot (a block's worth of keys, which a page holds)."""
    return jnp.minimum(
        jax.lax.div(block_horizon(sp_ref[s_i] + (t - 1), block), page_size),
        num_page_slots - 1)


def _paged_kernel(
    *refs, scale, num_page_slots, groups, rows, t, fill, has_scales,
    block=1,
):
    """One grid step = one (slot, block of kv heads) pair; the body walks
    the slot's live pages in blocks of ``ppb``.

    The pools stay in HBM. Block b of slot s is page slots ``b * ppb ..
    b * ppb + ppb - 1`` of its table: each live one is ONE copy of ``hb``
    heads of physical page ``page_table[s, j]`` (contiguous in the
    page-major pool) into its place in a ``[hb, ppb, P, D]`` VMEM buffer,
    which the body reads as ``[hb, ppb * P, D]`` keys. There are two such
    buffers: the next block's copies, or the first block's of the NEXT
    grid step, start before this block's products, and ``par_ref`` hands
    the buffer's parity from one grid step to the next. m / l / acc
    scratch carries the online softmax across blocks, one row block a kv
    head. Row r of a head's folded query block is (draft position t =
    r // groups, query head h_kv * groups + r % groups); the keys of block
    b sit at ``b * ppb * P + offset`` in the sequence's virtual key order
    — exactly the gathered-layout positions the einsum oracle masks.

    The walk ends at the slot's last live page (``_last_live_page``).
    Page slots of the last block past it are not fetched; their K rows
    are masked like every key past a row's horizon, and their V rows (or
    V's scales) are zeroed in the buffer, since ``0 x`` whatever VMEM
    held is not 0 for a NaN. Such a page would have contributed ``p = 0``
    and ``alpha = 1`` exactly.
    """
    if has_scales:
        (pt_ref, sp_ref, ly_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems, par_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (pt_ref, sp_ref, ly_ref, q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, par_ref, m_scr, l_scr, acc_scr) = refs
    s_idx, h_idx = pl.program_id(0), pl.program_id(1)
    num_s, num_h = pl.num_programs(0), pl.num_programs(1)
    _, hb, ppb, page_size, _ = k_buf.shape
    block_keys = ppb * page_size

    def live_pages(s_i, b):
        """How many page slots of block ``b`` of slot ``s_i`` are live."""
        last = _last_live_page(sp_ref, s_i, t, page_size, num_page_slots,
                               block)
        return jnp.clip(last + 1 - b * ppb, 0, ppb)

    def copies(s_i, h_i, b, buf, i):
        """The copies of page slot ``i`` of block ``b`` of (slot ``s_i``,
        head block ``h_i``) into buffer ``buf``: ``hb`` heads of one page,
        contiguous in the page-major pool, to their place among the
        block's keys."""
        page = pt_ref[s_i, b * ppb + i]
        heads = pl.ds(h_i * hb, hb)
        made = [
            pltpu.make_async_copy(
                k_hbm.at[ly_ref[0], page, heads], k_buf.at[buf, :, i],
                sems.at[0, buf]),
            pltpu.make_async_copy(
                v_hbm.at[ly_ref[0], page, heads], v_buf.at[buf, :, i],
                sems.at[1, buf])]
        if has_scales:
            made += [
                pltpu.make_async_copy(
                    ks_hbm.at[page, h_i], ks_buf.at[buf, i],
                    sems.at[0, buf]),
                pltpu.make_async_copy(
                    vs_hbm.at[page, h_i], vs_buf.at[buf, i],
                    sems.at[1, buf])]
        return made

    def start(s_i, h_i, b, buf):
        def page(i, carry):
            for copy in copies(s_i, h_i, b, buf, i):
                copy.start()
            return carry

        jax.lax.fori_loop(0, live_pages(s_i, b), page, 0)

    def wait(b, buf):
        def landed(i, carry):
            for copy in copies(s_idx, h_idx, b, buf, i):
                copy.wait()
            return carry

        def never_fetched(i, carry):
            if has_scales:  # int8 V is finite; its scale need not be
                vs_buf[buf, i] = jnp.zeros(vs_buf.shape[2:], vs_buf.dtype)
            else:
                v_buf[buf, :, i] = jnp.zeros(
                    (hb,) + v_buf.shape[3:], v_buf.dtype)
            return carry

        live = live_pages(s_idx, b)
        jax.lax.fori_loop(0, live, landed, 0)
        jax.lax.fori_loop(live, ppb, never_fetched, 0)

    def key_row(scale_buf, buf):
        """A block's scales, a row of ``hb * P`` a page, as one row a
        head over the block's keys: [hb, 1, ppb * P]."""
        pages = scale_buf[buf]
        return jnp.concatenate([jnp.stack([
            pages[i][:, h * page_size:(h + 1) * page_size]
            for h in range(hb)]) for i in range(ppb)], axis=-1)

    @pl.when(jnp.logical_and(s_idx == 0, h_idx == 0))
    def _first_block_of_the_call():
        par_ref[0] = 0
        start(s_idx, h_idx, 0, 0)

    m_scr[:] = jnp.full_like(m_scr, fill)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    parity = par_ref[0]
    num_blocks = jax.lax.div(
        _last_live_page(sp_ref, s_idx, t, page_size, num_page_slots, block),
        ppb) + 1
    # the grid step after this one, whose first block this one's last
    # block prefetches
    h_next = jax.lax.rem(h_idx + 1, num_h)
    s_next = s_idx + jax.lax.div(h_idx + 1, num_h)

    def walk(b, carry):
        buf = jax.lax.rem(parity + b, 2)

        @pl.when(b + 1 < num_blocks)
        def _next_block():
            start(s_idx, h_idx, b + 1, 1 - buf)

        @pl.when(jnp.logical_and(b + 1 == num_blocks, s_next < num_s))
        def _next_grid_step():
            start(s_next, h_next, 0, 1 - buf)

        wait(b, buf)
        q = q_ref[0]  # [hb, rows8, d], bfloat16 or float32
        k, v = k_buf[buf], v_buf[buf]
        if k.dtype != q.dtype:
            k = k.astype(jnp.float32)
        # A bf16 or int8 V holds bf16 values, and the MXU multiplies bf16:
        # left to itself Mosaic rounds a float32 operand to ONE bf16 term
        # (read on the chip, PR 36: 8e-4 off the oracle, as the kernel
        # before it). So the float32 probabilities go in as the three bf16
        # terms of a float32 product's own passes, whose sum is p to
        # 2^-24, stacked on the row axis: one pass over V as the weights.
        split = v.dtype != jnp.float32
        v = v.astype(jnp.bfloat16 if split else jnp.float32)
        # [hb, ppb, P, D] -> [hb, ppb * P, D]: the block's keys in order
        k = k.reshape(hb, block_keys, k.shape[-1])
        v = v.reshape(hb, block_keys, v.shape[-1])
        s_log = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [hb, rows8, block_keys]
        if has_scales:
            # fused absmax dequant: a key's scale is one factor of its
            # whole row of K, so it multiplies the key's logit, and a
            # value's scale its probability on the way into the second
            # product — the f32 pool never exists, in HBM or in VMEM
            s_log = s_log * key_row(ks_buf, buf)

        shape = s_log.shape[1:]
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qpos = block_horizon(sp_ref[s_idx] + row // groups, block)
        kpos = b * block_keys + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        # causal at each row's own horizon (its block's end); padding rows (row >= rows) are
        # fully masked and sliced off by the wrapper. Trash, unallocated
        # and unfetched page slots mask themselves: their virtual
        # positions exceed the horizon.
        mask = jnp.logical_and(kpos <= qpos, row < rows)
        s_log = jnp.where(mask[None], s_log, fill)

        m_prev = m_scr[:, :, :1]  # [hb, rows8, 1]
        l_prev = l_scr[:, :, :1]
        m_cur = jnp.max(s_log, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # dead rows (still all-masked) would get p = exp(fill - fill) = 1
        # per key; gate on the raw logit so they contribute l = 0 and emit
        # zeros
        p = jnp.where(s_log > fill * 0.5, jnp.exp(s_log - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        p_v = p * key_row(vs_buf, buf) if has_scales else p
        if split:
            hi = p_v.astype(jnp.bfloat16).astype(jnp.float32)
            mid = (p_v - hi).astype(jnp.bfloat16).astype(jnp.float32)
            lo = p_v - hi - mid
            terms = jnp.concatenate([hi, mid, lo], axis=1).astype(
                jnp.bfloat16)  # [hb, 3 * rows8, block_keys]
        else:
            terms = p_v
        pv = jax.lax.dot_general(
            terms, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if split:
            r8 = p_v.shape[1]
            pv = pv[:, :r8] + (pv[:, r8:2 * r8] + pv[:, 2 * r8:])
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, num_blocks, walk, 0)
    par_ref[0] = jax.lax.rem(parity + num_blocks, 2)
    safe = jnp.maximum(l_scr[:, :, :1], 1e-30)
    o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)


def paged_attention(
    q,
    k_pool,
    v_pool,
    page_table,
    start_position,
    *,
    layer=None,
    scale=None,
    k_scales=None,
    v_scales=None,
    block=1,
    interpret=None,
):
    """Fused paged attention over a page-table-indirected KV pool.

    Args:
        q: ``[S, T, H, D]`` queries — T=1 for plain decode, T=k+1 for
            speculative verify (all draft positions scored in one pass);
            any T is correct (T=bucket with S=1 was the tail prefill's
            call until it got ``prefill_attention``).
        k_pool, v_pool: ``[N, Hkv, P, D]`` page pools in their STORED
            dtype (f32, bf16, or int8 when scales are passed), or the
            engine's stacked ``[L, N, Hkv, P, D]`` pools with ``layer``.
        page_table: ``[S, MP]`` int32 — page slot j of sequence s lives
            in physical page ``page_table[s, j]`` (0 = trash page).
        start_position: ``[S]`` int32 — tokens already cached per slot;
            draft position t attends keys ``<= start_position + t``.
        layer: which layer of a stacked pool to attend over (an int or
            an int32 scalar, traced or not); required with 5-D pools and
            refused with 4-D ones. It reaches the body as a third
            scalar-prefetch operand and indexes the pool where a page's
            copy starts, so the pool is never sliced.
        scale: logit scale; defaults to ``1/sqrt(D)``.
        k_scales, v_scales: optional ``[N, Hkv, P]`` f32 absmax scales —
            passing them turns on fused int8 dequant (both or neither).
            ONE layer's slab also beside a stacked pool: stacked, its
            trailing-1 reshape below would pad every lane to 128.
        block: positions are counted in blocks of this many from 0, and
            a query sees every key of its own block (``block_horizon``):
            a block-diffusion model's block pass. 1 (the default) is
            plain causal.
        interpret: force pallas interpret mode; default: interpret
            everywhere except on a real TPU backend.

    Returns:
        ``[S, T, H, D]`` f32 attention output.

    The work follows the live KV, not the table's width: the grid is one
    step a slot (and block of kv heads: as many as fit,
    ``_heads_per_step``), and the body walks the slot's page slots up to
    ``(start_position + T - 1) // P`` in blocks of ``_pages_per_block``
    pages; both sizes come from the call's shapes alone.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    if (k_pool.ndim == 5) != (layer is not None):
        raise ValueError(
            "a stacked [L, N, Hkv, P, D] pool takes a layer index, and "
            f"only it does: pool rank {k_pool.ndim}, layer {layer!r}")
    if layer is None:  # one layer's pool is a stack of one: a bitcast
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    _, t, h, d = q.shape
    hkv, p = k_pool.shape[2:4]
    if h % hkv:
        raise ValueError(f"num heads {h} not divisible by kv heads {hkv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb, ppb = block_shape(t, h, hkv, d, p, k_pool.dtype.itemsize,
                          k_scales is not None)
    return _walk_call(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        start_position.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), k_scales, v_scales,
        scale=float(scale) if scale is not None else 1.0 / math.sqrt(d),
        hb=hb, ppb=ppb, interpret=interpret, block=int(block))


@functools.partial(
    jax.jit, static_argnames=("scale", "hb", "ppb", "interpret", "block"))
def _walk_call(q, k_pool, v_pool, page_table, start_position, layer,
               k_scales, v_scales, *, scale, hb, ppb, interpret, block=1):
    """The call at its block sizes. Jitted, so that a program which makes
    it once a layer traces and lowers the kernel once, not once a layer
    (the GPT decode program unrolls 24; XLA inlines the calls: the
    compiled program is the same)."""
    s, t, h, d = q.shape
    _, n, hkv, p, _ = k_pool.shape
    mp = page_table.shape[1]
    groups = h // hkv
    rows = t * groups
    rows8 = _ceil8(rows)
    has_scales = k_scales is not None

    # bf16 x bf16 into a float32 accumulator is the same products exactly
    # as the widened pair's; every other pair meets in float32
    if (has_scales or q.dtype != jnp.bfloat16
            or k_pool.dtype != jnp.bfloat16):
        q = q.astype(jnp.float32)
    # GQA-native folding: [S, T, H, D] -> [S, Hkv, T*G, D]; the G query
    # heads of a kv head travel as kernel rows, so kv pages are read once
    # per kv head — never replicated across query heads.
    qg = q.reshape(s, t, hkv, groups, d)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(s, hkv, rows, d)
    if rows8 != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows8 - rows), (0, 0)))

    def q_index(s_i, h_i, pt_ref, sp_ref, ly_ref):
        return (s_i, h_i, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hb, rows8, d), q_index), in_hbm, in_hbm]
    args = [qg, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, hb, ppb, p, d), k_pool.dtype),
               pltpu.VMEM((2, hb, ppb, p, d), v_pool.dtype)]
    if has_scales:
        # a copy's last axis is whole lane widths: a page's scales of one
        # head block travel as ONE row [1, hb * P], padded out to 128s
        lanes = -(-hb * p // 128) * 128
        in_specs += [in_hbm, in_hbm]
        args += [jnp.pad(
            a.astype(jnp.float32).reshape(n, hkv // hb, 1, hb * p),
            ((0, 0), (0, 0), (0, 0), (0, lanes - hb * p)))
            for a in (k_scales, v_scales)]
        scratch += [pltpu.VMEM((2, ppb, 1, lanes), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),  # (K | V, buffer)
        pltpu.SMEM((1,), jnp.int32),      # the next block's buffer
        _scratch((hb, rows8, 128)),
        _scratch((hb, rows8, 128)),
        _scratch((hb, rows8, d)),
    ]

    kernel = functools.partial(
        _paged_kernel, scale=scale, num_page_slots=mp, groups=groups,
        rows=rows, t=t, fill=mask_fill_value(jnp.float32),
        has_scales=has_scales, block=block,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, hkv // hb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, rows8, d), q_index),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, hkv, rows8, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(page_table, start_position, layer, *args)
    out = out[:, :, :rows]
    return out.reshape(s, hkv, t, groups, d).transpose(
        0, 2, 1, 3, 4).reshape(s, t, h, d)
