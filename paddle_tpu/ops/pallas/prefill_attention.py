"""Blocked causal attention for the serving engine's tail prefill — Pallas
TPU kernel.

A prefill (one slot, ``T = bucket`` query rows from position
``cached_len`` on) wants the opposite of what the paged kernel
(``paged_attention.py``) gives decode in every dimension of its grid: many
rows against keys that, once the slot's pages are gathered
(``inference/kv_pool.py::KVPool.attend_block``), lie contiguous. So it has
a kernel of its own, with the paged kernel's arithmetic and a flash
kernel's grid:

  * grid ``(kv head, row block, key block)``, key axis innermost; row
    blocks of up to 512 rows and key blocks of 128-1024 keys
    (``_block_sizes``, from the call's shapes alone), so the first
    product fills the MXU's columns and the float32 accumulator is
    rescaled once a few hundred keys, not once a 16-key page;
  * a row block's key blocks past its own causal horizon ``cached_len +
    last row of the block`` are neither fetched nor multiplied: the index
    map stands still there (a block whose index does not change is not
    fetched again) and the body is skipped. ``cached_len`` is a traced
    int32, so it travels as a scalar-prefetch operand;
  * key blocks wholly at or below the block's FIRST row's horizon take the
    body without the mask's iotas, compares and selects;
  * block-causal where the model asks for it (``block`` > 1, a
    block-diffusion model's prefill of whole blocks): a row's horizon is
    rounded up to the end of its block of ``block`` positions, the paged
    kernel's ``block_horizon``; ``block`` 1 is plain causal;
  * GQA-native as the paged kernel: the G query heads of a kv head ride in
    the row dimension (``rows = T * G``);
  * the same arithmetic as the paged kernel and the einsum oracle
    (``nn/functional/attention.py``): float32 accumulation, float32
    probabilities into the second product, ``mask_fill_value``, dead rows
    emit zeros, int8 keys dequantised against their absmax scale rows on
    the VMEM-resident block. One thing differs, and is exact: where q and
    the keys BOTH arrive as bfloat16 they enter the first product as they
    are (a product of two bfloat16 values is exact in float32); any other
    pair is widened to float32 first, as the paged kernel widens all.

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

from .paged_attention import block_horizon, mask_fill_value

#: the largest row and key blocks (``_block_sizes``). Swept on a v5e inside
#: the engine's own prefill programs at GPT-3 1.3B widths
#: (scripts/prefill_attention_trace.py; PERF.md, PR 34; ms a bucket-1024
#: call with 0 / 128 / 1024 tokens cached): 128 x 128 0.395 / 0.455 /
#: 0.873, 256 x 256 0.214 / 0.284 / 0.495, 512 x 512 0.115 / 0.181 /
#: 0.247, 512 x 1024 0.083 / 0.118 / 0.152, 1024 x 1024 0.074 / 0.137 /
#: 0.136 (its [1024, 1024] float32 temporaries are 4 MB each). The body is
#: bound by the VPU's work on the logits, and a step costs ~2 us before
#: its first product: few large steps win over skipping masked halves.
_BLOCK_Q = 512
_BLOCK_K = 1024


def _round_up(n, m):
    return -(-n // m) * m


def _block_sizes(rows, keys):
    """(row block, key block) of a call with ``rows`` folded query rows
    over ``keys`` contiguous keys. Key blocks are the largest of 1024 / 512
    / 256 / 128 that divides the keys once those are padded to the lane
    width; the row block is the whole (sublane-padded) row count up to
    512, so that a short bucket is one block and a long one few."""
    keys_p = _round_up(keys, 128)
    block_k = next(b for b in (1024, 512, 256, 128)
                   if b <= _BLOCK_K and keys_p % b == 0)
    return min(_BLOCK_Q, _round_up(rows, 16)), block_k


def _last_key_block(cached_len, i, block_q, block_k, groups, num_k,
                    block=1):
    """The last key block that any row of row block ``i`` can see: its
    last row sits at position ``cached_len + ((i + 1) * block_q - 1) //
    groups`` and sees to the end of its block. Single source for the
    body's gate and the index maps' clamp, so the two cannot drift."""
    horizon = block_horizon(
        cached_len + ((i + 1) * block_q - 1) // groups, block)
    return jnp.minimum(jax.lax.div(horizon, block_k), num_k - 1)


def _prefill_kernel(
    *refs, scale, block_q, block_k, groups, rows, keys, keys_p, fill,
    has_scales, block=1,
):
    """One grid step = one (kv head, row block, key block) triple; m / l /
    acc scratch carries the online softmax across a row block's key blocks.
    Row r of a head's folded query block is (position cached_len + r //
    groups, query head h_kv * groups + r % groups)."""
    if has_scales:
        (cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        cl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    num_k = pl.num_programs(2)
    cached_len = cl_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, fill)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(masked):
        q = q_ref[0]  # [block_q, d], bfloat16 or float32
        k, v = k_ref[0], v_ref[0].astype(jnp.float32)  # [block_k, d]
        if has_scales:
            k = k.astype(jnp.float32) * ks_ref[0]  # scale column [block_k, 1]
            v = v * vs_ref[0]
        elif k.dtype != q.dtype:
            k = k.astype(jnp.float32)
        s_log = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if masked:
            row = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s_log.shape, 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s_log.shape, 1)
            # causal at each row's own horizon; padding rows (row >= rows)
            # are fully masked and sliced off by the wrapper
            mask = jnp.logical_and(
                kpos <= block_horizon(cached_len + row // groups, block),
                row < rows)
            if keys_p != keys:
                mask = jnp.logical_and(mask, kpos < keys)
            s_log = jnp.where(mask, s_log, fill)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s_log, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_log - m_new)
        if masked:
            # dead rows (still all-masked) would get p = exp(fill - fill)
            # = 1 per key; gate on the raw logit so they contribute l = 0
            # and emit zeros
            p = jnp.where(s_log > fill * 0.5, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    live = j <= _last_key_block(cached_len, i, block_q, block_k, groups,
                                num_k, block)
    # every key of the block at or below the horizon of the block's FIRST
    # row, no padding row and no padding key: nothing to mask
    clear = jnp.logical_and(
        (j + 1) * block_k - 1 <= block_horizon(
            cached_len + (i * block_q) // groups, block),
        jnp.logical_and((i + 1) * block_q <= rows,
                        (j + 1) * block_k <= keys))
    pl.when(jnp.logical_and(live, clear))(lambda: update(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(clear)))(
        lambda: update(True))

    @pl.when(j == num_k - 1)
    def _emit():
        safe = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / safe).astype(o_ref.dtype)


def prefill_attention(
    q,
    k,
    v,
    cached_len,
    *,
    scale=None,
    k_scales=None,
    v_scales=None,
    block=1,
    interpret=None,
):
    """Causal attention of one sequence's new rows over its contiguous
    keys.

    Args:
        q: ``[T, H, D]`` queries; row t sits at position ``cached_len + t``.
        k, v: ``[Hkv, K, D]`` keys and values of positions ``0 .. K - 1``
            at their STORED dtype (f32, bf16, or int8 when scales are
            passed); a key block wholly past its row block's horizon is
            never read, whatever it holds.
        cached_len: int32 scalar (traced or not): tokens before the first
            row; row t attends keys ``<= cached_len + t``.
        scale: logit scale; defaults to ``1/sqrt(D)``.
        k_scales, v_scales: optional ``[Hkv, K]`` f32 absmax scales —
            passing them turns on fused int8 dequant (both or neither).
        block: a row sees to the end of its block of this many positions
            (counted from 0); 1 (the default) is plain causal.
        interpret: force pallas interpret mode; default: interpret
            everywhere except on a real TPU backend.

    Returns:
        ``[T, H, D]`` f32 attention output.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    t, h, d = q.shape
    hkv, keys, _ = k.shape
    if h % hkv:
        raise ValueError(f"num heads {h} not divisible by kv heads {hkv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _block_sizes(t * (h // hkv), keys)
    return _blocked_call(
        q, k, v, jnp.asarray(cached_len, jnp.int32).reshape(1), k_scales,
        v_scales, scale=float(scale) if scale is not None else (
            1.0 / math.sqrt(d)),
        block_q=block_q, block_k=block_k, interpret=interpret,
        block=int(block))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret", "block"))
def _blocked_call(q, k, v, cached_len, k_scales, v_scales, *, scale,
                  block_q, block_k, interpret, block=1):
    """The call at its block sizes. Jitted, so that a program which makes
    it once a layer traces and lowers the kernel once, not once a layer
    (the engine's prefill programs lower in 0.9 s for 1.7; XLA inlines the
    calls: the compiled program is the same)."""
    t, h, d = q.shape
    hkv, keys, _ = k.shape
    groups = h // hkv
    rows = t * groups
    has_scales = k_scales is not None
    rows_p, keys_p = _round_up(rows, block_q), _round_up(keys, block_k)

    if has_scales or q.dtype != jnp.bfloat16 or k.dtype != jnp.bfloat16:
        q = q.astype(jnp.float32)
    # GQA-native folding: [T, H, D] -> [Hkv, T*G, D]
    qg = q.reshape(t, hkv, groups, d).transpose(1, 0, 2, 3).reshape(
        hkv, rows, d)
    if rows_p != rows:
        qg = jnp.pad(qg, ((0, 0), (0, rows_p - rows), (0, 0)))
    args = [qg, k, v]
    if has_scales:
        # trailing singleton dim: a per-key column broadcasts over d
        args += [a.astype(jnp.float32)[..., None] for a in (
            k_scales, v_scales)]
    if keys_p != keys:  # zeros, so that a masked key's value is finite
        args[1:] = [jnp.pad(a, ((0, 0), (0, keys_p - keys), (0, 0)))
                    for a in args[1:]]

    def q_index(h_i, i, j, cl_ref):
        return (h_i, i, 0)

    def key_index(h_i, i, j, cl_ref):
        return (h_i, jnp.minimum(j, _last_key_block(
            cl_ref[0], i, block_q, block_k, groups, keys_p // block_k,
            block)), 0)

    in_specs = [pl.BlockSpec((1, block_q, d), q_index),
                pl.BlockSpec((1, block_k, d), key_index),
                pl.BlockSpec((1, block_k, d), key_index)]
    if has_scales:
        in_specs += [pl.BlockSpec((1, block_k, 1), key_index)] * 2

    kernel = functools.partial(
        _prefill_kernel, scale=scale, block_q=block_q, block_k=block_k,
        groups=groups, rows=rows, keys=keys, keys_p=keys_p,
        fill=mask_fill_value(jnp.float32), has_scales=has_scales,
        block=block,
    )
    scratch = [pltpu.VMEM(s, jnp.float32) for s in (
        (block_q, 128), (block_q, 128), (block_q, d))]
    out = pl.pallas_call(
        kernel,
        # a leading 1, as the paged kernel's prefill call had: the slot
        # axis of the DECODE kernel's result is what device traces tell
        # the two by (benchmark/metrics/paged_attn_roofline.json)
        out_shape=jax.ShapeDtypeStruct((1, hkv, rows_p, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, rows_p // block_q, keys_p // block_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, block_q, d), lambda h_i, i, j, cl_ref: (0, h_i, i, 0)),
            scratch_shapes=scratch,
        ),
        interpret=interpret,
        name="prefill_attention",
    )(cached_len, *args)
    out = out[0, :, :rows]
    return out.reshape(hkv, t, groups, d).transpose(1, 0, 2, 3).reshape(
        t, h, d)
