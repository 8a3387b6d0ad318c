"""Fused paged-attention decode/verify — Pallas TPU kernel.

The serving decode hot loop above this kernel (paged KV pool, speculative
verify, int8 KV wire) is shape-static; the einsum reference path
(``paddle_tpu/nn/functional/attention.py::_paged_attention_op``) pays for
that by materializing the gathered K/V pages as f32 ``[S, Hkv, MP*P, D]``
tensors plus a dense ``[S, Hkv, G, T, MP*P]`` logits tensor in HBM every
step, and — under int8 KV — by a separate whole-pool dequant pass.

This kernel fuses the whole per-slot pipeline into one Pallas program
whose grid follows the KV that is live, not the page table's capacity:

  * page-table-aware gather: the K/V pool blocks are addressed through a
    scalar-prefetched page table (``pltpu.PrefetchScalarGridSpec``), so
    pages stream HBM→VMEM at their STORED dtype and the gathered f32
    copies never exist;
  * GQA-native query folding: the G query heads sharing a kv head ride in
    the kernel's row dimension (``rows = T * G``) — kv heads are never
    replicated in HBM;
  * online (streaming) softmax across the page grid dimension: running
    max / denominator / accumulator live in VMEM scratch, so no
    ``[.., MP*P]`` logits tensor is written to HBM;
  * fused int8 dequant: when per-[page, head] absmax scales are passed,
    ``int8 * scale`` happens on the VMEM-resident page right before the
    QK / PV dots — the f32 pool is never materialized;
  * decode (T=1) and speculative verify (T=k+1) are the SAME kernel: all
    T positions score in one pass, each row masked at its own causal
    horizon ``start_position + t``. It is correct at any T, and until PR
    34 the engine's tail prefill (S=1, T=bucket) ran it too, at under 2%
    of the MXU: a prefill wants many rows against long contiguous key
    blocks, the opposite of this grid, and now gathers its slot's pages
    and runs ``prefill_attention.py``;
  * few, full grid steps: a page of the ``[N, Hkv, P, D]`` pool is
    contiguous over its kv heads, so one step fetches a block of heads of
    a page in one DMA and batches its two products over them. The block
    is as many heads as the call's shapes leave room for in VMEM
    (``_heads_per_step``): all of them in decode and verify, a few at a
    prefill's row count;
  * the engine's stacked ``[L, N, Hkv, P, D]`` pool is read in place: the
    layer index travels as a scalar-prefetch operand into the index maps,
    so no caller slices a layer out (a slice is a copy of 1/L of the pool
    before every call);
  * no work past a slot's last live page ``(start_position + T - 1) //
    P``: the index maps stand still there (a block whose index does not
    change is not fetched again) and the body is skipped, so an idle slot
    costs one page and not the table's width.

The einsum op remains the bit-equality reference oracle: greedy argmax
must agree everywhere (tests/test_pallas_attention.py), raw outputs agree
to f32 tolerance (online vs dense softmax differ in ulps only).

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


def _bytes_per_head(rows8, d, p, kv_itemsize, has_scales):
    """VMEM that one kv head adds to a grid step: the query and result
    blocks (float32, double-buffered), the m / l / acc scratch, the body's
    temporaries (logits, probabilities and their selects: about four
    ``[rows8, 128]`` float32 arrays), and the double-buffered K and V pages
    with their scale columns (a ``[P, 1]`` column pads out to 128 lanes).
    Inside the engine's bucket-512 prefill program Mosaic counted 21.47 MiB
    for 8 heads of 512 rows (PR 27, on the chip); this gives 22.1. The
    kernel compiled ALONE needs less, because XLA then keeps the small
    query and result arrays in VMEM and nothing double-buffers them: a
    lone compile that passes proves nothing about the budget."""
    per_head = 4 * rows8 * (4 * d + 2 * 128 + d + 4 * 128)
    per_head += 2 * 2 * p * d * kv_itemsize
    if has_scales:
        per_head += 2 * 2 * p * 128 * 4
    return per_head


def _heads_per_step(hkv, rows8, d, p, kv_itemsize, has_scales):
    """How many kv heads one grid step takes: the most that divides
    ``hkv`` and keeps what grows with it under ``_VMEM_BUDGET``. Decode
    and verify (8-16 rows) take every head; a prefill, whose row block is
    the whole bucket, takes a few or one."""
    hb = max(1, min(hkv, _VMEM_BUDGET // _bytes_per_head(
        rows8, d, p, kv_itemsize, has_scales)))
    while hkv % hb:
        hb -= 1
    return hb


def _last_live_page(sp_ref, s_i, t, page_size, num_page_slots):
    """The last page slot that any query row of slot ``s_i`` can see: row
    ``t - 1`` sits at position ``start_position + t - 1``. Everything past
    it is masked for every row, so the grid neither fetches nor multiplies
    it. An idle slot (position 0) has one live page slot."""
    return jnp.minimum(
        jax.lax.div(sp_ref[s_i] + (t - 1), page_size), num_page_slots - 1)


def _paged_kernel(
    *refs, scale, page_size, num_page_slots, groups, rows, t, fill,
    has_scales,
):
    """One grid step = one (slot, block of kv heads, page_slot) triple.

    Grid is (S, Hkv // hb, MP) with the page dimension innermost; m/l/acc
    scratch carries the online softmax across page slots, one row block a
    kv head. A page of the pool is contiguous over its kv heads, so one
    step fetches ``hb`` heads of a page in one DMA and the two products
    are batched over the head axis. Row r of a head's folded query block
    is (draft position t = r // groups, query head h_kv * groups +
    r % groups); kv positions on page slot j are ``j * page_size +
    offset`` in the sequence's virtual key order — exactly the
    gathered-layout positions the einsum oracle masks.

    Page slots past the slot's last live one (``_last_live_page``) do no
    work: the index maps hold the last live page's block, which is
    therefore not fetched again, and the body's products and softmax
    update are skipped. Such a page would have contributed ``p = 0`` and
    ``alpha = 1`` exactly, so leaving it out changes no bit of a row.
    """
    if has_scales:
        (pt_ref, sp_ref, ly_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (pt_ref, sp_ref, ly_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    del pt_ref, ly_ref  # consumed by the BlockSpec index maps, not the body
    s_idx = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, fill)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j <= _last_live_page(sp_ref, s_idx, t, page_size,
                                  num_page_slots))
    def _page():
        q = q_ref[0]  # [hb, rows8, d] f32
        k = k_ref[0].astype(jnp.float32)  # [hb, page_size, d]
        v = v_ref[0].astype(jnp.float32)
        if has_scales:
            # fused absmax dequant: int8 page * per-[page, head] scale, on
            # the VMEM-resident block — the f32 pool never exists in HBM
            k = k * ks_ref[0]  # scale block [hb, page_size, 1]
            v = v * vs_ref[0]
        s_log = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [hb, rows8, page_size]

        shape = s_log.shape[1:]
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qpos = sp_ref[s_idx] + row // groups
        kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # causal at each row's own horizon; padding rows (row >= rows) are
        # fully masked and sliced off by the wrapper. Trash/unallocated
        # page slots up to the last live one mask themselves: their
        # virtual positions exceed the horizon.
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
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_page_slots - 1)
    def _emit():
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
            refused with 4-D ones. It reaches the index maps as a third
            scalar-prefetch operand and the pool's block squeezes the
            layer axis, so the pool is never sliced.
        scale: logit scale; defaults to ``1/sqrt(D)``.
        k_scales, v_scales: optional ``[N, Hkv, P]`` f32 absmax scales —
            passing them turns on fused int8 dequant (both or neither).
            ONE layer's slab also beside a stacked pool: stacked, its
            trailing-1 reshape below would pad every lane to 128.
        interpret: force pallas interpret mode; default: interpret
            everywhere except on a real TPU backend.

    Returns:
        ``[S, T, H, D]`` f32 attention output.

    The work follows the live KV, not the table's width: a slot's page
    slots past ``(start_position + T - 1) // P`` are neither fetched nor
    multiplied, and a grid step takes as many kv heads of a page as fit
    (``_heads_per_step``, from the call's shapes alone).
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    if (k_pool.ndim == 5) != (layer is not None):
        raise ValueError(
            "a stacked [L, N, Hkv, P, D] pool takes a layer index, and "
            f"only it does: pool rank {k_pool.ndim}, layer {layer!r}")
    if layer is None:  # one layer's pool is a stack of one: a bitcast
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    s, t, h, d = q.shape
    _, n, hkv, p, _ = k_pool.shape
    mp = page_table.shape[1]
    if h % hkv:
        raise ValueError(f"num heads {h} not divisible by kv heads {hkv}")
    groups = h // hkv
    rows = t * groups
    rows8 = _ceil8(rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sc = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    fill = mask_fill_value(jnp.float32)
    has_scales = k_scales is not None
    hb = _heads_per_step(hkv, rows8, d, p, k_pool.dtype.itemsize, has_scales)

    # GQA-native folding: [S, T, H, D] -> [S, Hkv, T*G, D]; the G query
    # heads of a kv head travel as kernel rows, so kv pages are read once
    # per kv head — never replicated across query heads.
    qg = q.astype(jnp.float32).reshape(s, t, hkv, groups, d)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(s, hkv, rows, d)
    if rows8 != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows8 - rows), (0, 0)))

    def q_index(s_i, h_i, j, pt_ref, sp_ref, ly_ref):
        return (s_i, h_i, 0, 0)

    def page_index(s_i, h_i, j, pt_ref, sp_ref, ly_ref):
        # the page-table gather: grid step (s, h, j) streams physical
        # page pt[s, j] for head block h straight from the pool; past the
        # last live page slot the index stands still, and a block whose
        # index does not change is not fetched again
        live = jnp.minimum(j, _last_live_page(sp_ref, s_i, t, p, mp))
        return (pt_ref[s_i, live], h_i, 0, 0)

    def pool_index(s_i, h_i, j, pt_ref, sp_ref, ly_ref):
        return (ly_ref[0],) + page_index(s_i, h_i, j, pt_ref, sp_ref, ly_ref)

    in_specs = [
        pl.BlockSpec((1, hb, rows8, d), q_index),
        # the layer axis is squeezed: the body sees [1, hb, P, D] as ever
        pl.BlockSpec((None, 1, hb, p, d), pool_index),
        pl.BlockSpec((None, 1, hb, p, d), pool_index),
    ]
    args = [qg, k_pool, v_pool]
    if has_scales:
        # trailing singleton dim: per-row stats blocks must keep their
        # last two dims equal to the array dims for Mosaic tiling
        in_specs.append(pl.BlockSpec((1, hb, p, 1), page_index))
        in_specs.append(pl.BlockSpec((1, hb, p, 1), page_index))
        args.append(k_scales.astype(jnp.float32).reshape(n, hkv, p, 1))
        args.append(v_scales.astype(jnp.float32).reshape(n, hkv, p, 1))

    kernel = functools.partial(
        _paged_kernel, scale=sc, page_size=p, num_page_slots=mp,
        groups=groups, rows=rows, t=t, fill=fill, has_scales=has_scales,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, hkv // hb, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, rows8, d), q_index),
        scratch_shapes=[
            _scratch((hb, rows8, 128)),
            _scratch((hb, rows8, 128)),
            _scratch((hb, rows8, d)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, hkv, rows8, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), start_position.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *args)
    out = out[:, :, :rows]
    return out.reshape(s, hkv, t, groups, d).transpose(
        0, 2, 1, 3, 4).reshape(s, t, h, d)
