"""Attention functionals.

Reference: ``python/paddle/nn/functional/flash_attention.py`` (wrapping the
external flashattn CUDA lib — SURVEY.md §2.3 "CP", §5 "Long-context").
TPU-native design: the public API lowers to (a) a Pallas flash-attention
kernel on TPU (paddle_tpu/ops/pallas/flash_attention.py) when shapes allow,
else (b) a jnp reference path that XLA still fuses well.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from ...framework.op import defop, raw
from ...ops.pallas.paged_attention import mask_fill_value

#: masked-logit fill for the f32 decode/paged logits, shared with the
#: Pallas kernel (ops/pallas/paged_attention.py) so masked-key semantics
#: cannot drift between the oracle and the fused path
_MASK_FILL = mask_fill_value(jnp.float32)

#: accepted values for the paged-attention kernel knob
ATTN_KERNELS = ("auto", "pallas", "einsum")

_USE_PALLAS = True


def _auto_partitioned() -> bool:
    """Is this trace part of a program GSPMD will partition — a global mesh
    of several devices is active and some axis of it is not manual
    (shard_map) here?"""
    from ...distributed import mesh as _mesh

    m = _mesh.get_global_mesh()
    if m is None or m.empty or m.size == 1:
        return False
    manual = _mesh.manual_axis_names()
    return any(n > 1 and a not in manual for a, n in m.shape.items())


def _pallas_backend_ok() -> bool:
    """Can the flash kernel serve long sequences in the program being
    traced? Yes on a TPU, in a program for one device: a Mosaic refusal
    then surfaces as the compile error of the program that holds the
    kernel — never as a quiet switch to the XLA path. Not in a program
    GSPMD partitions: the TPU compiler refuses it at lowering ("Mosaic
    kernels cannot be automatically partitioned"), so Fleet hybrid steps
    route XLA attention until the kernel is wrapped in a shard_map over the
    batch and head axes (ROADMAP.md S5). Off-TPU the kernel would run in
    Pallas interpret mode, orders of magnitude slower than the fused XLA
    softmax-attention, so it is not routed; set
    PADDLE_TPU_PALLAS_INTERPRET=1 to force the routed kernel in interpret
    mode (kernel-routing tests).
    """
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu" and not _auto_partitioned()


def _sdpa_reference(q, k, v, mask, dropout_p, causal, scale, key=None):
    # q,k,v: [B, T, H, D] (paddle flash-attention layout)
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,T,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        logits = jnp.where(cm, logits, jnp.asarray(-jnp.inf, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.asarray(-jnp.inf, logits.dtype))
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B,T,H,D]


@defop(amp="white", name="sdpa_op")
def _sdpa(q, k, v, mask, key, dropout_p, causal, scale, use_pallas):
    if mask is not None and mask.dtype != jnp.bool_:
        # mask semantics on every path: never differentiated (keeps grads
        # identical between the Pallas route and the reference fallback)
        mask = jax.lax.stop_gradient(mask)
    # Shape gate, measured on v5e (full fwd+bwd wrt q,k,v, causal, d=64,
    # in-jit repetition): s128 b256 pallas 12.3ms vs XLA 4.8 (0.39x);
    # s512 b64 10.2 vs 9.2 (0.90x); s1024 b16 7.3 vs 9.4 (1.29x);
    # s2048 b8 11.8 vs 17.8 (1.51x). Short sequences are per-grid-step
    # overhead-bound in the kernel while the XLA softmax fuses well; from
    # ~1k tokens the kernel wins and avoids the O(T^2) HBM logits
    # round-trip entirely.
    long_seq = max(q.shape[1], k.shape[1]) >= 1024 or (
        os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"  # test hook
    )
    pallas_ok = use_pallas and long_seq and dropout_p == 0.0 and (
        mask is None or getattr(mask, "ndim", 0) == 4
    ) and _pallas_backend_ok()
    if pallas_ok:
        from ...ops.pallas.flash_attention import flash_attention as _fa

        if mask is None:
            return _fa(q, k, v, causal=causal, scale=scale)
        if mask.dtype == jnp.bool_:
            return _fa(q, k, v, causal=causal, scale=scale, mask=mask)
        # paddle attn_mask semantics: an additive mask, not a trained
        # bias — skip the O(B*H*T^2) dbias pass in backward
        return _fa(q, k, v, causal=causal, scale=scale, bias=mask,
                   bias_needs_grad=False)
    return _sdpa_reference(q, k, v, mask, dropout_p, causal, scale, key)


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, scale=None, name=None
):
    """paddle.nn.functional.scaled_dot_product_attention parity.

    Layout [batch, seq, heads, head_dim] (matches paddle flash attention).
    `attn_mask` carries mask semantics (paddle parity): it is never
    differentiated, on any backend path. Use
    `ops.pallas.flash_attention.flash_attention(bias=...)` for a trained
    attention bias.
    """
    from ...framework import rng as _rng

    if (
        attn_mask is not None
        and getattr(attn_mask, "stop_gradient", True) is False
        and getattr(attn_mask, "dtype", None) != jnp.bool_
    ):
        import warnings

        warnings.warn(
            "attn_mask has stop_gradient=False but scaled_dot_product_"
            "attention treats float masks as non-differentiable (mask "
            "semantics); its gradient will be zero. Use ops.pallas."
            "flash_attention.flash_attention(bias=...) for a trained bias.",
            stacklevel=2,
        )
    p = float(dropout_p) if training else 0.0
    rng_key = _rng.next_key() if p > 0 else None
    return _sdpa(
        query, key, value, attn_mask, rng_key,
        dropout_p=p, causal=bool(is_causal), scale=scale, use_pallas=_USE_PALLAS,
    )


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, fixed_seed_offset=None, rng_name="", training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    if return_softmax:
        return out, None
    return out, None


def _mp_degree_for(hkv: int):
    """(mesh, mp) when a global mesh with an mp axis that divides the kv
    heads is active, else (None, 1). Decode attention shards over kv
    heads: each mp shard owns whole GQA groups, so the per-shard math is
    exactly the single-device math restricted to its head block."""
    from ...distributed import mesh as _mesh

    m = _mesh.get_global_mesh()
    if m is None or m.empty:
        return None, 1
    mp = _mesh.mesh_axis_size("mp", m)
    if mp <= 1 or hkv % mp != 0:
        return None, 1
    return m, mp


def _shard_heads(x, axis: int, mesh):
    """Constraint hint: shard `x` over the mp axis along `axis` (kv/query
    heads). GSPMD propagates the layout through the einsums, so the
    O(H·T·K) logits/probs never materialize replicated."""
    from ...distributed import mesh as _mesh

    spec = [None] * x.ndim
    spec[axis] = "mp"
    return _mesh.sharding_constraint(x, _mesh.P(*spec), mesh)


def _replicate(x, mesh):
    """Constraint hint: force `x` replicated. Placed on the attention
    OUTPUT so GSPMD emits an exact all-gather (pure concatenation over the
    head axis — bitwise-identical to single-device) instead of a psum of
    partial projections, whose float reduction order would drift."""
    from ...distributed import mesh as _mesh

    return _mesh.sharding_constraint(x, _mesh.P(), mesh)


@defop(amp="white", name="decode_attention_op")
def _decode_attention_op(q, ck, cv, cache_position, scale):
    """Single-token decode attention against a static slot-indexed cache.

    q: [S, 1, H, D] (one new token per slot); ck/cv: [S, Hkv, T, D]
    (one layer's slice of the serving engine's [L, S, Hkv, T, D] cache);
    cache_position: [S] int — the position the current token was written
    at, so keys at positions > cache_position[s] (stale slot garbage or
    other requests' leftovers) are masked out per slot. GQA-native: query
    heads are grouped onto their kv head, no head replication in HBM.
    """
    s_, _, h, d = q.shape
    hkv, t = ck.shape[1], ck.shape[2]
    group = h // hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    mesh, mp = _mp_degree_for(hkv)
    qf = q[:, 0].astype(jnp.float32).reshape(s_, hkv, group, d)
    if mesh is not None:
        qf = _shard_heads(qf, 1, mesh)
        ck = _shard_heads(ck, 1, mesh)
        cv = _shard_heads(cv, 1, mesh)
    logits = jnp.einsum("shgd,shtd->shgt", qf, ck.astype(jnp.float32)) * sc
    mask = jnp.arange(t)[None, None, None, :] \
        <= cache_position[:, None, None, None]
    logits = jnp.where(mask, logits, _MASK_FILL)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shgt,shtd->shgd", probs, cv.astype(jnp.float32))
    out = out.reshape(s_, 1, h, d).astype(q.dtype)
    return out if mesh is None else _replicate(out, mesh)


def decode_attention(query, cache_k, cache_v, cache_position, scale=None,
                     name=None):
    """One-step KV-cached attention for serving decode (the decode-shape
    companion of :func:`scaled_dot_product_attention`; see
    docs/SERVING.md). Shapes: ``query`` [S, 1, H, D]; ``cache_k/v``
    [S, Hkv, T_max, D]; ``cache_position`` [S] int32 (per-slot position of
    the token being decoded)."""
    return _decode_attention_op(query, cache_k, cache_v, cache_position,
                                scale)


def asked_attn_kernel(kernel=None) -> str:
    """What was asked of the paged-attention kernel knob, unresolved: the
    explicit ``kernel`` arg (engine config) > ``PADDLE_TPU_ATTN_KERNEL``
    env > ``'auto'``."""
    mode = str(kernel or os.environ.get("PADDLE_TPU_ATTN_KERNEL")
               or "auto").lower()
    if mode not in ATTN_KERNELS:
        raise ValueError(
            f"unknown attention kernel {mode!r}; expected one of "
            f"{ATTN_KERNELS} (PADDLE_TPU_ATTN_KERNEL / engine attn_kernel)")
    return mode


def resolve_attn_kernel(kernel=None) -> str:
    """Resolve the paged-attention kernel knob to ``'pallas'`` or
    ``'einsum'``.

    Precedence as in :func:`asked_attn_kernel`. ``auto`` routes to the
    fused Pallas kernel on a real TPU backend and to the einsum oracle
    everywhere else — off-TPU the kernel runs in Pallas interpret mode,
    orders of magnitude slower than the fused XLA einsum path.
    ``PADDLE_TPU_PALLAS_INTERPRET=1`` (the kernel-routing test hook)
    makes ``auto`` pick the kernel in interpret mode.
    """
    mode = asked_attn_kernel(kernel)
    if mode != "auto":
        return mode
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "einsum"


@defop(amp="white", name="paged_attention_pallas_op")
def _paged_attention_pallas_op(q, pk, pv, k_scales, v_scales, page_table,
                               start_position, scale, layer=None, block=1):
    """Fused-kernel twin of :func:`_paged_attention_op`: the pool streams
    HBM→VMEM at its stored dtype (int8 dequant fused against the absmax
    scales inside the kernel) and the softmax runs online — no gathered
    f32 K/V and no dense logits tensor in HBM. Oracle contract: greedy
    argmax bit-equal to the einsum op, raw outputs within f32 tolerance
    (tests/test_pallas_attention.py)."""
    from ...ops.pallas import paged_attention as _pa

    out = _pa.paged_attention(
        q, pk, pv, page_table, start_position, layer=layer, scale=scale,
        k_scales=k_scales, v_scales=v_scales, block=block)
    return out.astype(q.dtype)


@defop(amp="white", name="paged_attention_op")
def _paged_attention_op(q, pk, pv, k_scales, v_scales, page_table,
                        start_position, scale, block=1):
    """KV-cached attention through a block/page-granular cache.

    q: [S, T, H, D] — T new tokens per slot (T=1 decode, T=k+1 speculative
    verify, T=bucket tail prefill with S=1); pk/pv: [N, Hkv, P, D] — ONE
    layer's slice of the engine's [L, N, Hkv, P, D] page pool;
    page_table: [S, MP] int32 — per-slot page ids in sequence order, so
    virtual key position j lives in page page_table[s, j // P] at offset
    j % P (unallocated entries point at the reserved trash page 0 and are
    masked); start_position: [S] int — query row i of slot s sits at
    global position start_position[s] + i and attends to key positions
    <= its own (causal over the virtual sequence), or with ``block`` > 1
    every key up to the end of its block of ``block`` positions
    (block-causal, a block-diffusion model's block pass). GQA-native: query
    heads are grouped onto their kv head, no head replication in HBM.
    """
    s_, t, h, d = q.shape
    hkv, p = pk.shape[1], pk.shape[2]
    mp = page_table.shape[1]
    group = h // hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    mesh, mp_deg = _mp_degree_for(hkv)
    if k_scales is not None:
        # int8 absmax pool: the oracle dequantizes up front (the fused
        # Pallas path instead multiplies per-page inside the kernel)
        pk = pk.astype(jnp.float32) * k_scales[..., None]
        pv = pv.astype(jnp.float32) * v_scales[..., None]

    def gather(pool):
        if mesh is not None:
            pool = _shard_heads(pool, 1, mesh)  # [N, Hkv, P, D]
        g = pool[page_table]                   # [S, MP, Hkv, P, D]
        g = jnp.swapaxes(g, 1, 2)              # [S, Hkv, MP, P, D]
        g = g.reshape(s_, hkv, mp * p, d)
        return g if mesh is None else _shard_heads(g, 1, mesh)

    k = gather(pk).astype(jnp.float32)
    v = gather(pv).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(s_, t, hkv, group, d)
    if mesh is not None:
        qf = _shard_heads(qf, 2, mesh)
    logits = jnp.einsum("sthgd,shkd->shgtk", qf, k) * sc
    qpos = start_position[:, None] + jnp.arange(t)[None, :]       # [S, T]
    if block > 1:
        qpos = (qpos // block + 1) * block - 1  # the end of the row's block
    mask = jnp.arange(mp * p)[None, None, :] <= qpos[:, :, None]  # [S, T, K]
    logits = jnp.where(mask[:, None, None, :, :], logits, _MASK_FILL)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shgtk,shkd->sthgd", probs, v)
    out = out.reshape(s_, t, h, d).astype(q.dtype)
    return out if mesh is None else _replicate(out, mesh)


def paged_attention(query, pool_k, pool_v, page_table, start_position,
                    scale=None, k_scales=None, v_scales=None, kernel=None,
                    layer=None, block=1, name=None):
    """Multi-token KV-cached attention against a paged cache (the
    page-granular companion of :func:`decode_attention`; see
    docs/SERVING.md §paged cache). ``query`` [S, T, H, D]; ``pool_k/v``
    [N, Hkv, page_size, D]; ``page_table`` [S, max_pages] int32;
    ``start_position`` [S] int32 (global position of each slot's first
    query row). Serves the decode step (T=1) and the speculative verify
    step (T=k+1) of the serving engine; it is correct at any T, and the
    prefix-cached tail prefill (S=1, T=bucket) ran through it until PR 34.
    On the fused path the prefill now gathers its slot's pages and runs
    :func:`prefill_attention` (MXU-sized key blocks, the causal horizon
    skipped); on the einsum path, and under an mp-sharded pool, it still
    runs this op.

    With ``layer`` (an int or int32 scalar) ``pool_k/v`` are the engine's
    stacked [L, N, Hkv, page_size, D] pools: the fused kernel reads that
    layer in place through its index maps, so a serving program never
    slices its pool (docs/SERVING.md §paged cache); the einsum oracle
    slices it. The scales stay ONE layer's.

    ``k_scales``/``v_scales`` ([N, Hkv, page_size] f32, both or neither)
    mark the pools as int8 absmax-quantized. ``kernel`` picks the
    implementation (see :func:`resolve_attn_kernel`): the fused Pallas
    kernel streams pages at their stored dtype with dequant fused in;
    the einsum oracle dequantizes up front. An mp-sharded pool always
    takes the einsum path — the GSPMD sharding annotations live there.

    ``block`` > 1 makes the mask block-causal: positions are counted in
    blocks of ``block`` from 0 and a query sees every key up to the end of
    its own block (a block-diffusion model's block pass); 1 is causal."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    choice = resolve_attn_kernel(kernel)
    if choice == "pallas":
        _, mp_deg = _mp_degree_for(pool_k.shape[-3])
        if mp_deg == 1:
            return _paged_attention_pallas_op(
                query, pool_k, pool_v, k_scales, v_scales, page_table,
                start_position, scale, layer, block)
    if layer is not None:
        pool_k, pool_v = pool_k[layer], pool_v[layer]
    return _paged_attention_op(query, pool_k, pool_v, k_scales, v_scales,
                               page_table, start_position, scale, block)


@defop(amp="white", name="prefill_attention_pallas_op")
def _prefill_attention_pallas_op(q, k, v, k_scales, v_scales, cached_len,
                                 scale, block=1):
    from ...ops.pallas import prefill_attention as _pf

    out = _pf.prefill_attention(
        q[0], k, v, cached_len, scale=scale, k_scales=k_scales,
        v_scales=v_scales, block=block)
    return out[None].astype(q.dtype)


def prefill_attention(query, keys, values, cached_len, scale=None,
                      k_scales=None, v_scales=None, block=1, name=None):
    """Causal attention of ONE sequence's new rows over its contiguous
    keys: what the serving engine's tail prefill runs on its fused path
    (docs/SERVING.md §paged cache), in row blocks against key blocks of
    128 keys or more (ops/pallas/prefill_attention.py). ``query``
    [1, T, H, D], row t at position ``cached_len + t``; ``keys`` /
    ``values`` [Hkv, K, D] at their stored dtype, positions 0 .. K - 1 of
    the sequence (``KVPool.attend_block`` gathers a slot's pages into
    them); ``cached_len`` an int32 scalar; ``k_scales``/``v_scales``
    ([Hkv, K] f32, both or neither) mark int8 absmax-quantized keys.
    :func:`paged_attention` with ``kernel="einsum"`` on the same pages is
    its oracle: greedy argmax equal, raw outputs within f32 tolerance
    (tests/test_pallas_attention.py). ``block`` as in
    :func:`paged_attention`."""
    return _prefill_attention_pallas_op(
        query, keys, values, k_scales, v_scales, cached_len, scale, block)


@defop(name="sparse_attention_op")
def _sparse_attention(q, k, v, offset, columns, key_padding_mask, attn_mask):
    # q/k/v: [B, H, T, D] (paddle sparse_attention layout); CSR pattern
    # [B, H, T+1] / [B, H, nnz] selects which keys each query attends to.
    b, h, t, d = q.shape
    nnz = columns.shape[-1]
    pos = jnp.arange(nnz)
    # row of each nnz entry: offset is monotone per (b, h)
    row = jax.vmap(jax.vmap(
        lambda off: jnp.searchsorted(off, pos, side="right") - 1))(offset)
    mask = jnp.zeros((b, h, t, t), bool)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(h)[None, :, None]
    mask = mask.at[bi, hi, row, columns].set(True)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    neg = jnp.asarray(mask_fill_value(logits.dtype), logits.dtype)
    logits = jnp.where(mask, logits, neg)
    if key_padding_mask is not None:
        logits = jnp.where(key_padding_mask[:, None, None, :] != 0, logits, neg)
    if attn_mask is not None:
        logits = jnp.where(attn_mask[None, None] != 0, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with an empty pattern produce zeros, not NaN
    probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """paddle.nn.functional.sparse_attention parity: attention restricted
    to a per-(batch, head) CSR pattern over keys. Reference: a CUDA
    block-sparse kernel (sparse_attention op, sm>=70 only); TPU-native
    lowering is the masked dense form — the MXU wins nothing from
    unstructured sparsity, and XLA fuses mask+softmax+matmul into the
    same fused attention it runs for dense."""
    return _sparse_attention(query, key, value, sparse_csr_offset,
                             sparse_csr_columns, key_padding_mask, attn_mask)
