"""Flash attention — Pallas TPU kernels (forward AND backward).

Reference capability (SURVEY.md §2.3 "CP" row, §5 "Long-context"): Paddle
wraps the external flashattn CUDA library
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu` and
`flash_attn_grad_kernel.cu`, exposed via
`python/paddle/nn/functional/flash_attention.py`).

TPU-native design: online-softmax blockwise kernels written in Pallas.
Q/K/V blocks stream HBM→VMEM per grid step; the MXU does the
[block_q, d] x [d, block_k] logits and the [block_q, block_k] x [block_k, d]
accumulation in fp32; running max/denominator live in VMEM scratch across
the innermost grid dimension.

Causal block skipping is done in the BlockSpec index maps, not just with
pl.when: grid steps whose K/V block lies entirely above the diagonal have
their index map clamped to the last valid block, and Pallas elides the
HBM→VMEM copy when consecutive steps map to the same block — so dead blocks
cost neither bandwidth nor MXU time (compute is additionally gated with
pl.when).

Backward is the standard recompute-based flash backward: the forward also
emits the per-row logsumexp (LSE); backward recomputes P = exp(S - LSE)
blockwise (no O(T^2) HBM tensor is ever materialized) and accumulates
dQ in one kernel (`flash_attention_bwd_dq`, grid over K blocks innermost)
and dK/dV in a second (`flash_attention_bwd_dkv`, grid over Q blocks
innermost), all in fp32 VMEM scratch. When one backward key block covers
every key (Tk <= block_k) and no bias gradient is wanted, each dK/dV tile
already holds a q block's whole dS, so a single kernel
(`flash_attention_bwd_fused`) writes dQ = dS·K beside dK/dV and the dQ
kernel, which would recompute P and dS on the same tile, is not launched.

Supported: causal (incl. tq != tk, bottom-right aligned), additive bias /
boolean mask broadcastable over batch and head, GQA/MQA (num_kv_heads
divides num_heads), bias gradient.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (fwd TF/s at b8/s2048/h16/d64, causal): blocks 128 -> 4.1,
# 256 -> 6.8, 512 -> 10.2, 1024 -> 12.9 (vs XLA-unfused 8.6, official jax
# pallas kernel at its defaults 5.8). Per-grid-step overhead dominates small
# blocks; 1024 keeps the fp32 logits tile at 4MB of VMEM and is clamped to
# the (padded) sequence length for short inputs.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
# The fused backward's step at 1024 x 1024 tiles and head_dim 64 holds
# 16.1 MiB of scoped VMEM (compiled for a v5e), just over Mosaic's default
# limit of 16 MiB; a v5e core has 128 MiB.
FUSED_BWD_VMEM_LIMIT = 32 * 1024 * 1024


def _env_block(name, default):
    import os

    try:
        v = int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default
    # Mosaic needs the block's second-to-last dim divisible by 8; zero or
    # negative values would divide-by-zero in the pad math
    return _ceil8(v)


def _blocks_fwd():
    """Forward block sizes; env-tunable (PADDLE_TPU_FLASH_BLOCK_Q/K) for
    on-chip sweeps. Read at TRACE time: a changed env var does not retrace
    an already-compiled shape — sweep in fresh processes."""
    bq = _env_block("PADDLE_TPU_FLASH_BLOCK_Q", DEFAULT_BLOCK_Q)
    bk = _env_block("PADDLE_TPU_FLASH_BLOCK_K", DEFAULT_BLOCK_K)
    return bq, bk


def _blocks_bwd():
    """Backward block sizes; default to the forward's, separately tunable
    (PADDLE_TPU_FLASH_BWD_BLOCK_Q/K) — the bwd kernel's working set is
    ~2.5x the fwd's per tile, so its optimum can sit one size lower."""
    fq, fk = _blocks_fwd()
    bq = _env_block("PADDLE_TPU_FLASH_BWD_BLOCK_Q", fq)
    bk = _env_block("PADDLE_TPU_FLASH_BWD_BLOCK_K", fk)
    return bq, bk


def _ceil8(n):
    return max(8, (n + 7) // 8 * 8)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _causal_run(qi, ki, block_q, block_k, tq, tk):
    """kv block `ki` overlaps q block `qi`'s visible region (bottom-right
    aligned). Single source of truth for every pl.when gate; the index-map
    clamps below are its inverse images, so gates and clamps cannot drift."""
    return ki * block_k <= qi * block_q + block_q - 1 + (tk - tq)


def _causal_last_kv(qi, block_q, block_k, tq, tk, nk):
    """Largest kv block with _causal_run(qi, ki) true (clamped to grid)."""
    last = (qi * block_q + block_q - 1 + (tk - tq)) // block_k
    return jnp.minimum(nk - 1, jnp.maximum(last, 0))


def _causal_first_q(ki, block_q, block_k, tq, tk, nq):
    """Smallest q block with _causal_run(qi, ki) true (clamped to grid)."""
    first = (ki * block_k - (tk - tq)) // block_q
    return jnp.minimum(jnp.maximum(first, 0), nq - 1)


def _mask_for(qi, ki, block_q, block_k, tq, tk, causal, shape):
    """Validity mask for a [block_q, block_k] logits tile."""
    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = jnp.logical_and(k_idx < tk, q_idx < tq)
    if causal:
        mask = jnp.logical_and(mask, k_idx <= q_idx + (tk - tq))
    return mask


def _logits(q, k, scale, bias_ref):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    return s


# ---------------------------------------------------------------- forward

def _fwd_kernel(
    *refs, scale, causal, tq, tk, block_q, block_k, num_k_blocks, has_bias,
):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        bias_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a key block strictly above the diagonal contributes nothing
    run = _causal_run(qi, ki, block_q, block_k, tq, tk) if causal else (ki >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        s = _logits(q, k, scale, bias_ref)
        mask = _mask_for(qi, ki, block_q, block_k, tq, tk, causal, s.shape)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [block_q, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # dead rows (all keys at NEG_INF, e.g. a fully-masked-out query via a
        # bool-mask-folded bias) would get p = exp(s - m_new) = 1 for EVERY
        # key; gate on the raw logit so they contribute l = 0 and emit zeros
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _emit():
        l = l_scr[:, :1]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        # lse_ref block is [1, block_q, 1]: per-row stats travel with a
        # trailing singleton dim because Mosaic requires a block's last two
        # dims to be (divisible by 8, divisible by 128) OR equal to the
        # array dims — a (1, block_q) row block is rejected on real TPU
        # (interpret mode does not enforce this)
        lse_ref[0] = jnp.where(l > 0, m_scr[:, :1] + jnp.log(safe), NEG_INF)


def _bh_kv(b, n_heads, n_kv_heads):
    """Flattened-[batch*head] index → flattened-[batch*kv_head] index."""
    group = n_heads // n_kv_heads
    return b // n_heads * n_kv_heads + (b % n_heads) // group


def _bh_bias(b, n_heads, bias_b, bias_h):
    return (b // n_heads) % bias_b * bias_h + (b % n_heads) % bias_h


def _make_index_maps(causal, tq, tk, nq, nk, block_q, block_k, n_heads,
                     n_kv_heads, bias_b, bias_h, bias_tq, bias_tk):
    """Shared K/V + bias BlockSpec index maps with the causal diagonal clamp.

    Grid steps whose K/V block is entirely above the diagonal are clamped to
    the last valid block; Pallas elides the HBM copy for repeated indices,
    so dead blocks cost no bandwidth. Used identically by the forward and
    the dQ backward so their block-skipping can never diverge.

    Bias pages keep singleton broadcast dims (batch/head via _bh_bias,
    Tq/Tk by pinning the block index to 0) so a (B,1,1,Tk) padding mask is
    never materialized to O(B*H*Tq*Tk).
    """

    def kv_index(b, i, j):
        bkv = _bh_kv(b, n_heads, n_kv_heads)
        if causal:
            j = jnp.minimum(j, _causal_last_kv(i, block_q, block_k, tq, tk, nk))
        return (bkv, j, 0)

    def bias_index(b, i, j):
        _, jj, _ = kv_index(b, i, j)
        return (
            _bh_bias(b, n_heads, bias_b, bias_h),
            i if bias_tq > 1 else 0,
            jj if bias_tk > 1 else 0,
        )

    return kv_index, bias_index


def _bias_block(block_q, block_k, bias_tq, bias_tk):
    return (1, block_q if bias_tq > 1 else 1, block_k if bias_tk > 1 else 1)


def _pad_bias(bias, pad_q, pad_k):
    return jnp.pad(bias, (
        (0, 0),
        (0, pad_q if bias.shape[1] > 1 else 0),
        (0, pad_k if bias.shape[2] > 1 else 0),
    ))


def _fa_forward(q, k, v, bias, causal, scale, n_heads, n_kv_heads,
                bias_b, bias_h, block_q, block_k, interpret):
    """q: [B*H, Tq, D]; k,v: [B*Hkv, Tk, D]; bias: [Bb*Hb, Tq, Tk] or None.

    Returns (o [B*H, Tq, D], lse [B*H, Tq_padded] fp32).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, _ceil8(tq))
    block_k = min(block_k, _ceil8(tk))
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    bias_tq = bias.shape[1] if bias is not None else 1
    bias_tk = bias.shape[2] if bias is not None else 1
    if bias is not None and (pad_q or pad_k):
        bias = _pad_bias(bias, pad_q, pad_k)
    nq, nk = (tq + pad_q) // block_q, (tk + pad_k) // block_k

    kv_index, bias_index = _make_index_maps(
        causal, tq, tk, nq, nk, block_q, block_k, n_heads, n_kv_heads,
        bias_b, bias_h, bias_tq, bias_tk,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec(_bias_block(block_q, block_k, bias_tq, bias_tk),
                         bias_index)
        )
        args.append(bias)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, tq=tq, tk=tk,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        has_bias=bias is not None,
    )
    o, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq + pad_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq + pad_q, 1), jnp.float32),
        ],
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
            _scratch((block_q, d)),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return (o[:, :tq] if pad_q else o), lse[:, :, 0]


# ---------------------------------------------------------------- backward

def _bwd_p_ds(q, k, v, do, lse, delta, bias_ref, mask, scale):
    """Recompute P and dS for one [block_q, block_k] tile (all fp32)."""
    s = _logits(q, k, scale, bias_ref)
    # the s-threshold gate mirrors the forward: dead rows (lse == NEG_INF,
    # s ~= NEG_INF) must recompute p = 0, not exp(s - lse) = 1
    p = jnp.where(
        jnp.logical_and(mask, s > NEG_INF * 0.5), jnp.exp(s - lse), 0.0
    )  # lse: [block_q, 1]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)  # delta: [block_q, 1]
    return p, ds


def _dq_kernel(
    *refs, scale, causal, tq, tk, block_q, block_k, num_k_blocks, has_bias,
    has_dbias,
):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    bias_ref = refs[i] if has_bias else None
    i += int(has_bias)
    do_ref, lse_ref, delta_ref, dq_ref = refs[i:i + 4]
    i += 4
    dbias_ref = refs[i] if has_dbias else None
    acc_scr = refs[-1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _causal_run(qi, ki, block_q, block_k, tq, tk) if causal else (ki >= 0)

    @pl.when(run)
    def _step():
        mask = _mask_for(qi, ki, block_q, block_k, tq, tk, causal,
                         (block_q, block_k))
        _, ds = _bwd_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0].astype(jnp.float32),
            lse_ref[0], delta_ref[0], bias_ref, mask, scale,
        )
        if dbias_ref is not None:
            dbias_ref[0] = ds.astype(dbias_ref.dtype)
        acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    if dbias_ref is not None:
        @pl.when(jnp.logical_not(run))
        def _dead_bias():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(ki == num_k_blocks - 1)
    def _emit():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, scale, causal, tq, tk, block_q, block_k, num_q_blocks, has_bias,
    has_dq,
):
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    bias_ref = refs[i] if has_bias else None
    i += int(has_bias)
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref = refs[i:i + 5]
    i += 5
    dq_ref = refs[i] if has_dq else None
    dk_scr, dv_scr = refs[-2:]
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _causal_run(qj, ki, block_q, block_k, tq, tk) if causal else (qj >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        mask = _mask_for(qj, ki, block_q, block_k, tq, tk, causal,
                         (block_q, block_k))
        p, ds = _bwd_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do,
            lse_ref[0], delta_ref[0], bias_ref, mask, scale,
        )
        if dq_ref is not None:
            # the one key block is every key: this tile's dS·K is the whole
            # dQ of its q block, as the dQ kernel would accumulate it.
            # Before dV and dK: the step then holds 16.1 MiB of VMEM, not
            # the 17.9 it takes when dQ comes last
            dq_ref[0] = (jax.lax.dot_general(
                ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale).astype(dq_ref.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    if dq_ref is not None:
        # causal with tq > tk: a q block that sees no key has a zero dQ
        @pl.when(jnp.logical_not(run))
        def _dead_rows():
            dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(qj == num_q_blocks - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, bias, o, lse, do, causal, scale, n_heads,
                 n_kv_heads, bias_b, bias_h, bias_grad, block_q, block_k,
                 interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, _ceil8(tq))
    block_k = min(block_k, _ceil8(tk))
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    tqp, tkp = tq + pad_q, tk + pad_k

    # delta_i = rowsum(dO * O) — tiny elementwise reduce; let XLA fuse it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, pad_q), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q)))
        # lse is produced padded by the forward
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    bias_tq = bias.shape[1] if bias is not None else 1
    bias_tk = bias.shape[2] if bias is not None else 1
    if bias is not None and (pad_q or pad_k):
        bias = _pad_bias(bias, pad_q, pad_k)
    if lse.shape[1] < tqp:
        lse = jnp.pad(lse, ((0, 0), (0, tqp - lse.shape[1])))
    elif lse.shape[1] > tqp:
        # residual lse is padded to the FORWARD block grid, which can be
        # wider than the backward's when bwd blocks are tuned smaller
        lse = lse[:, :tqp]
    # per-row stats enter the kernels with a trailing singleton dim (see
    # the forward's lse out_spec for the Mosaic tiling rule)
    lse = lse[:, :, None]
    delta = delta[:, :, None]
    nq, nk = tqp // block_q, tkp // block_k
    has_bias = bias is not None
    # dbias needs a per-(batch*q-head) [Tq, Tk] dS tensor in HBM — O(B*H*T^2),
    # far beyond the bias itself. Only pay it when the bias actually needs a
    # gradient (mask-derived biases never do).
    want_dbias = has_bias and bias_grad

    # one key block holds every key: the dK/dV kernel's tile is all a q
    # block's dQ needs, so dQ comes out of that kernel and its own pass
    # (which would recompute P and dS on the same tile) is not launched.
    # A trained bias keeps the dQ kernel: it writes the dS tensor dbias sums.
    fuse_dq = nk == 1 and not want_dbias
    dbias = None
    if not fuse_dq:
        # ---- dQ: grid (bh, q blocks, k blocks innermost)
        kv_index, bias_index = _make_index_maps(
            causal, tq, tk, nq, nk, block_q, block_k, n_heads, n_kv_heads,
            bias_b, bias_h, bias_tq, bias_tk,
        )
        q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
        row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
        in_specs = [
            q_spec,
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ]
        args = [q, k, v]
        if has_bias:
            in_specs.append(
                pl.BlockSpec(_bias_block(block_q, block_k, bias_tq, bias_tk),
                             bias_index)
            )
            args.append(bias)
        in_specs += [q_spec, row_spec, row_spec]
        args += [do, lse, delta]

        out_shape = [jax.ShapeDtypeStruct((bh, tqp, d), q.dtype)]
        out_specs = [q_spec]
        if want_dbias:
            out_shape.append(
                jax.ShapeDtypeStruct((bh, tqp, tkp), jnp.float32))
            out_specs.append(
                pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b, i, j))
            )

        dq_kernel = functools.partial(
            _dq_kernel, scale=scale, causal=causal, tq=tq, tk=tk,
            block_q=block_q, block_k=block_k, num_k_blocks=nk,
            has_bias=has_bias, has_dbias=want_dbias,
        )
        dq_out = pl.pallas_call(
            dq_kernel,
            out_shape=out_shape,
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[_scratch((block_q, d))],
            interpret=interpret,
            name="flash_attention_bwd_dq",
        )(*args)
        if want_dbias:
            dq, ds_full = dq_out
            dbias = ds_full[:, :tq, :tk].reshape(
                bh // n_heads, n_heads, tq, tk
            )
            if bias_b == 1:
                dbias = dbias.sum(0, keepdims=True)
            if bias_h == 1:
                dbias = dbias.sum(1, keepdims=True)
            if bias_tq == 1:
                dbias = dbias.sum(2, keepdims=True)
            if bias_tk == 1:
                dbias = dbias.sum(3, keepdims=True)
            dbias = dbias.reshape(bias_b * bias_h, bias_tq, bias_tk)
        else:
            (dq,) = dq_out
        dq = dq[:, :tq]

    # ---- dK/dV: grid (bh over *q heads*, k blocks, q blocks innermost);
    # GQA: per-q-head partials are group-summed after the kernel.
    def kv_index2(b, i, j):
        return (_bh_kv(b, n_heads, n_kv_heads), i, 0)

    def q_index2(b, i, j):
        if causal:
            j = jnp.maximum(j, _causal_first_q(i, block_q, block_k, tq, tk, nq))
        return (b, j, 0)

    def row_index2(b, i, j):
        _, jj, _ = q_index2(b, i, j)
        return (b, jj, 0)

    in_specs2 = [
        pl.BlockSpec((1, block_q, d), q_index2),
        pl.BlockSpec((1, block_k, d), kv_index2),
        pl.BlockSpec((1, block_k, d), kv_index2),
    ]
    args2 = [q, k, v]
    if has_bias:
        def bias_index2(b, i, j):
            _, jj, _ = q_index2(b, i, j)
            return (
                _bh_bias(b, n_heads, bias_b, bias_h),
                jj if bias_tq > 1 else 0,
                i if bias_tk > 1 else 0,
            )

        in_specs2.append(
            pl.BlockSpec(_bias_block(block_q, block_k, bias_tq, bias_tk),
                         bias_index2)
        )
        args2.append(bias)
    in_specs2 += [
        pl.BlockSpec((1, block_q, d), q_index2),
        pl.BlockSpec((1, block_q, 1), row_index2),
        pl.BlockSpec((1, block_q, 1), row_index2),
    ]
    args2 += [do, lse, delta]

    kv_out_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    out_shape2 = [
        jax.ShapeDtypeStruct((bh, tkp, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, tkp, d), jnp.float32),
    ]
    out_specs2 = [kv_out_spec, kv_out_spec]
    if fuse_dq:
        # each step writes its own q block: not q_index2, whose causal
        # clamp maps the dead leading blocks onto the first live one
        out_shape2.append(jax.ShapeDtypeStruct((bh, tqp, d), q.dtype))
        out_specs2.append(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)))
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, tq=tq, tk=tk,
        block_q=block_q, block_k=block_k, num_q_blocks=nq, has_bias=has_bias,
        has_dq=fuse_dq,
    )
    dkv_out = pl.pallas_call(
        dkv_kernel,
        out_shape=out_shape2,
        grid=(bh, nk, nq),
        in_specs=in_specs2,
        out_specs=out_specs2,
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=interpret,
        name="flash_attention_bwd_fused" if fuse_dq
        else "flash_attention_bwd_dkv",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_BWD_VMEM_LIMIT) if fuse_dq else None,
    )(*args2)
    if fuse_dq:
        dk, dv, dq = dkv_out
        dq = dq[:, :tq]
    else:
        dk, dv = dkv_out
    dk, dv = dk[:, :tk], dv[:, :tk]
    group = n_heads // n_kv_heads
    if group > 1:
        batch = bh // n_heads
        dk = dk.reshape(batch, n_kv_heads, group, tk, d).sum(2).reshape(-1, tk, d)
        dv = dv.reshape(batch, n_kv_heads, group, tk, d).sum(2).reshape(-1, tk, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), dbias


# ---------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _fa(q, k, v, bias, causal, scale, n_heads, n_kv_heads, bias_b, bias_h,
        bias_grad, interpret):
    o, _ = _fa_forward(
        q, k, v, bias, causal, scale, n_heads, n_kv_heads, bias_b, bias_h,
        *_blocks_fwd(), interpret,
    )
    return o


def _fa_fwd(q, k, v, bias, causal, scale, n_heads, n_kv_heads, bias_b,
            bias_h, bias_grad, interpret):
    o, lse = _fa_forward(
        q, k, v, bias, causal, scale, n_heads, n_kv_heads, bias_b, bias_h,
        *_blocks_fwd(), interpret,
    )
    return o, (q, k, v, bias, o, lse)


def _fa_bwd(causal, scale, n_heads, n_kv_heads, bias_b, bias_h, bias_grad,
            interpret, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv, dbias = _fa_backward(
        q, k, v, bias, o, lse, do, causal, scale, n_heads, n_kv_heads,
        bias_b, bias_h, bias_grad, *_blocks_bwd(), interpret,
    )
    if bias is None:
        dbias = None
    elif dbias is None:  # bias present but bias_grad=False: zero cotangent
        dbias = jnp.zeros_like(bias)
    else:
        dbias = dbias.astype(bias.dtype)
    return dq, dk, dv, dbias


_fa.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------- public API

def flash_attention(q, k, v, causal: bool = False, scale=None, bias=None,
                    mask=None, bias_needs_grad: bool = True):
    """Blockwise (flash) attention.

    Args:
      q: [B, Tq, H, D] (paddle flash-attention layout).
      k, v: [B, Tk, Hkv, D]; Hkv may divide H (GQA/MQA).
      causal: bottom-right-aligned causal masking.
      scale: logits scale, default 1/sqrt(D).
      bias: additive logits bias, [B|1, H|1, Tq|1, Tk|1]. Broadcast
        (singleton) dims are honored inside the kernel via the BlockSpec
        index maps — a (B,1,1,Tk) padding mask stays O(B*Tk) in HBM.
      mask: boolean keep-mask, same broadcastable shape; folded into bias
        (never differentiated).
      bias_needs_grad: set False for non-trained biases — the dbias pass
        materializes an O(B*H*Tq*Tk) buffer that is then skipped entirely.

    Query rows with no visible keys (causal with Tq > Tk, or a fully-masked
    row) return zeros (the reference dense softmax would produce NaN).

    Returns [B, Tq, H, D].
    """
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv != 0:
        raise ValueError(f"num_heads {h} not divisible by num_kv_heads {hkv}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = jax.default_backend() != "tpu"

    bias_grad = bias_needs_grad and bias is not None
    if mask is not None:
        neg = jnp.asarray(NEG_INF, jnp.float32)
        m = jnp.where(mask, 0.0, neg)
        bias = m if bias is None else bias + m

    bias_b = bias_h = 1
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias.ndim != 4:
            raise ValueError(f"bias must be rank-4, got {bias.shape}")
        bias_b, bias_h = int(bias.shape[0]), int(bias.shape[1])
        if bias_b not in (1, b) or bias_h not in (1, h):
            raise ValueError(
                f"bias dims ({bias_b}, {bias_h}) must broadcast over "
                f"batch={b} / heads={h} (per-kv-head bias pages are "
                "unsupported)"
            )
        if (bias.shape[2] not in (1, tq)
                or bias.shape[3] not in (1, k.shape[1])):
            raise ValueError(
                f"bias seq dims {bias.shape[2:]} must broadcast over "
                f"(Tq={tq}, Tk={k.shape[1]})"
            )
        # merge batch/head pages; keep Tq/Tk singleton dims un-materialized
        bias = bias.reshape(bias_b * bias_h, bias.shape[2], bias.shape[3])

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, x.shape[1], x.shape[-1])

    o = _fa(
        fold(q), fold(k), fold(v), bias, bool(causal), float(scale),
        h, hkv, bias_b, bias_h, bias_grad, interpret,
    )
    return jnp.swapaxes(o.reshape(b, h, tq, d), 1, 2)
