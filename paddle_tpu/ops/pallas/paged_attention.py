"""Fused paged-attention decode/verify — Pallas TPU kernel.

The serving decode hot loop above this kernel (paged KV pool, speculative
verify, int8 KV wire) is shape-static; the einsum reference path
(``paddle_tpu/nn/functional/attention.py::_paged_attention_op``) pays for
that by materializing the gathered K/V pages as f32 ``[S, Hkv, MP*P, D]``
tensors plus a dense ``[S, Hkv, G, T, MP*P]`` logits tensor in HBM every
step, and — under int8 KV — by a separate whole-pool dequant pass.

This kernel fuses the whole per-(slot, kv-head) pipeline into one Pallas
program:

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
    k+1 draft positions score in one pass, each row masked at its own
    causal horizon ``start_position + t``.

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


def _paged_kernel(
    *refs, scale, page_size, num_page_slots, groups, rows, fill, has_scales,
):
    """One grid step = one (slot, kv_head, page_slot) triple.

    Grid is (S, Hkv, MP) with the page dimension innermost; m/l/acc
    scratch carries the online softmax across page slots. Row r of the
    folded query block is (draft position t = r // groups, query head
    h_kv * groups + r % groups); kv positions on page slot j are
    ``j * page_size + offset`` in the sequence's virtual key order —
    exactly the gathered-layout positions the einsum oracle masks.
    """
    if has_scales:
        (pt_ref, sp_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (pt_ref, sp_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    del pt_ref  # consumed by the BlockSpec index maps, not the body
    s_idx = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, fill)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # [rows8, d] f32
    k = k_ref[0, 0].astype(jnp.float32)  # [page_size, d]
    v = v_ref[0, 0].astype(jnp.float32)
    if has_scales:
        # fused absmax dequant: int8 page * per-[page, head] scale, on the
        # VMEM-resident block — the f32 pool never exists in HBM
        k = k * ks_ref[0, 0]  # scale block [page_size, 1]
        v = v * vs_ref[0, 0]
    s_log = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [rows8, page_size]

    row = jax.lax.broadcasted_iota(jnp.int32, s_log.shape, 0)
    qpos = sp_ref[s_idx] + row // groups
    kpos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s_log.shape, 1)
    # causal at each row's own horizon; padding rows (row >= rows) are
    # fully masked and sliced off by the wrapper. Trash/unallocated page
    # slots mask themselves: their virtual positions exceed the horizon.
    mask = jnp.logical_and(kpos <= qpos, row < rows)
    s_log = jnp.where(mask, s_log, fill)

    m_prev = m_scr[:, :1]  # [rows8, 1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s_log, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # dead rows (still all-masked) would get p = exp(fill - fill) = 1 per
    # key; gate on the raw logit so they contribute l = 0 and emit zeros
    p = jnp.where(s_log > fill * 0.5, jnp.exp(s_log - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_page_slots - 1)
    def _emit():
        safe = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / safe).astype(o_ref.dtype)


def paged_attention(
    q,
    k_pool,
    v_pool,
    page_table,
    start_position,
    *,
    scale=None,
    k_scales=None,
    v_scales=None,
    interpret=None,
):
    """Fused paged attention over a page-table-indirected KV pool.

    Args:
        q: ``[S, T, H, D]`` queries — T=1 for plain decode, T=k+1 for
            speculative verify (all draft positions scored in one pass).
        k_pool, v_pool: ``[N, Hkv, P, D]`` page pools in their STORED
            dtype (f32, bf16, or int8 when scales are passed).
        page_table: ``[S, MP]`` int32 — page slot j of sequence s lives
            in physical page ``page_table[s, j]`` (0 = trash page).
        start_position: ``[S]`` int32 — tokens already cached per slot;
            draft position t attends keys ``<= start_position + t``.
        scale: logit scale; defaults to ``1/sqrt(D)``.
        k_scales, v_scales: optional ``[N, Hkv, P]`` f32 absmax scales —
            passing them turns on fused int8 dequant (both or neither).
        interpret: force pallas interpret mode; default: interpret
            everywhere except on a real TPU backend.

    Returns:
        ``[S, T, H, D]`` f32 attention output.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    s, t, h, d = q.shape
    n, hkv, p, _ = k_pool.shape
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

    # GQA-native folding: [S, T, H, D] -> [S, Hkv, T*G, D]; the G query
    # heads of a kv head travel as kernel rows, so kv pages are read once
    # per kv head — never replicated across query heads.
    qg = q.astype(jnp.float32).reshape(s, t, hkv, groups, d)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(s, hkv, rows, d)
    if rows8 != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows8 - rows), (0, 0)))

    def q_index(s_i, h_i, j, pt_ref, sp_ref):
        return (s_i, h_i, 0, 0)

    def pool_index(s_i, h_i, j, pt_ref, sp_ref):
        # the page-table gather: grid step (s, h, j) streams physical
        # page pt[s, j] for kv head h straight from the pool
        return (pt_ref[s_i, j], h_i, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rows8, d), q_index),
        pl.BlockSpec((1, 1, p, d), pool_index),
        pl.BlockSpec((1, 1, p, d), pool_index),
    ]
    args = [qg, k_pool, v_pool]
    has_scales = k_scales is not None
    if has_scales:
        # trailing singleton dim: per-row stats blocks must keep their
        # last two dims equal to the array dims for Mosaic tiling
        in_specs.append(pl.BlockSpec((1, 1, p, 1), pool_index))
        in_specs.append(pl.BlockSpec((1, 1, p, 1), pool_index))
        args.append(k_scales.astype(jnp.float32).reshape(n, hkv, p, 1))
        args.append(v_scales.astype(jnp.float32).reshape(n, hkv, p, 1))

    kernel = functools.partial(
        _paged_kernel, scale=sc, page_size=p, num_page_slots=mp,
        groups=groups, rows=rows, fill=fill, has_scales=has_scales,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows8, d), q_index),
        scratch_shapes=[
            _scratch((rows8, 128)),
            _scratch((rows8, 128)),
            _scratch((rows8, d)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, hkv, rows8, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), start_position.astype(jnp.int32), *args)
    out = out[:, :, :rows]
    return out.reshape(s, hkv, t, groups, d).transpose(
        0, 2, 1, 3, 4).reshape(s, t, h, d)
