"""SDAR (Cheng et al. 2025, "SDAR: A Synergistic Diffusion-AutoRegression
Paradigm", arXiv:2510.06303; JetLM/SDAR-30B-A3B-Chat, ``model_type:
sdar_moe``) as a plain reference: float32 ``jax.numpy`` at ``highest`` matmul
precision, no kernels, no cache, no batching. It imports nothing of the
program. Sizes are the published ``config.json``'s; a line marked *assumed*
is not in that file (the configuration file lists each under ``assumed``).

    x = E[ids]                              no position table
    for l in 0 .. L-1:
      a = RMSNorm(x; g1_l)                  eps 1e-6
      q, k, v = a Wq_l, a Wk_l, a Wv_l      no biases; H heads, Hkv kv heads of d
      q, k = RMSNorm_d(q; gq_l), RMSNorm_d(k; gk_l)
                                            *assumed*: Qwen3-MoE's per-head QK
                                            norm (SDAR-30B-A3B is converted from
                                            Qwen3-30B-A3B)
      o = softmax(rope(q) rope(k)^T / sqrt(d) + mask) v
                                            RoPE theta 1e6, rotate-half; head i
                                            reads kv head i // (H / Hkv)
      x = x + o Wo_l
      b = RMSNorm(x; g2_l)
      r = softmax(b Wr_l)                   over all E experts
      S = top_k(r), w_e = r_e / sum_S r     norm_topk_prob
      x = x + sum_{e in S} w_e (silu(b G_e) * (b U_e)) D_e
    logits = RMSNorm(x; gf) W_head          untied

The mask is by blocks of ``block`` positions counted from 0 (*assumed*: the
block length, 4, is SDAR's generation default as recalled). A position is
generated in place (the logits AT p give p's token, *assumed*), as the mask
token's embedding until it is unmasked, while every earlier block is clean
and the positions of its own block are in the state of that pass.

``logits_in_order(w, ids, order)`` gives each position's logits in the state
of the pass that unmasked it, from ``order [T]``: that pass's index within
the block (0 the block's first), or -1 for a position known when its block
began. It computes them as ONE forward over a clean stream joined to
``copies`` copies of it: copy j holds, in every block, the mask token where
``order >= j``; a query of copy j sees the CLEAN keys of earlier blocks and
copy j's keys of its own block; position p's logits are copy ``order[p]``'s.
``logits(w, ids)`` is that under the ``sequential`` rule, where the state in
which p was drawn follows from p alone (the positions of its block before it
known, p and after masked): its row r is position r + 1's, the convention of
``serve.py``'s readings.

Experts are computed as a loop over all E experts with weight 0 where a
token did not route: the plain form. Weights come stacked (``h.<leaf>`` with
a leading layer axis) in the type the configuration serves and are widened to
float32 one layer, and within it one expert, at a time: float32 copies of
six layers would not fit beside anything.

``quant`` is the CONTROL, not the reference (``reference/gpt.py``'s
``_linear``): every matrix product of a linear layer (router, experts and
head included) with its weights rounded by output channel and its input by
row under absmax scales, to int8 (W8A8) or to float8 e4m3, straight-through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.gpt import _linear as _gpt_linear  # the controls' rounding

LAYER_LEAVES = ("ln1.g", "q.w", "k.w", "v.w", "o.w", "qn.g", "kn.g",
                "ln2.g", "router.w", "gate_up.w", "down.w")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _linear(x, w, quant):
    return _gpt_linear(x, w, None, quant)  # no layer of this model has a bias


def _rope(x, theta):
    """``x [..., T, H, d]`` at positions 0..T-1 (rotate-half)."""
    t, _, d = x.shape[-3:]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def experts(b, lw, top_k, quant=None):
    """The routed SwiGLU experts of one layer ``lw`` over tokens ``b [N, h]``
    (float32, after the layer's second RMSNorm): what the layer's whole set
    of experts adds to the stream."""
    n = b.shape[0]
    r = jax.nn.softmax(_linear(b, lw["router.w"].astype(jnp.float32), quant),
                       axis=-1)
    topv, topi = jax.lax.top_k(r, top_k)
    wt = jnp.zeros_like(r).at[jnp.arange(n)[:, None], topi].set(
        topv / topv.sum(-1, keepdims=True))  # [N, E], 0 where unrouted
    f = lw["down.w"].shape[1]

    def one(acc, e):
        gu = _linear(b, lw["gate_up.w"][e].astype(jnp.float32), quant)
        m = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        y = _linear(m, lw["down.w"][e].astype(jnp.float32), quant)
        return acc + wt[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(b), jnp.arange(wt.shape[1]))
    return out


def layer(xs, lw, blk, heads, kv_heads, head_dim, eps, theta, top_k,
          quant=None):
    """One layer over the joined streams ``xs [1 + C, T, h]``: row 0 the
    clean stream, row 1 + j copy j. ``blk [T]`` is each position's block."""
    c1, t, _ = xs.shape
    ln = {k: lw[k].astype(jnp.float32) for k in ("ln1.g", "qn.g", "kn.g",
                                                 "ln2.g")}
    a = _rms(xs, ln["ln1.g"], eps)

    def proj(name, n):
        return _linear(a, lw[name].astype(jnp.float32), quant).reshape(
            c1, t, n, head_dim)

    q = _rope(_rms(proj("q.w", heads), ln["qn.g"], eps), theta)
    k = _rope(_rms(proj("k.w", kv_heads), ln["kn.g"], eps), theta)
    v = proj("v.w", kv_heads)
    k, v = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (k, v))
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
    same = blk[None, :] == blk[:, None]  # [q, k]
    before = blk[None, :] < blk[:, None]
    # the clean stream: block-causal over itself
    s0 = jnp.einsum("qhd,khd->hqk", q[0], k[0]) * scale
    p0 = jax.nn.softmax(jnp.where(same | before, s0, -jnp.inf), axis=-1)
    o0 = jnp.einsum("hqk,khd->qhd", p0, v[0])
    # the copies: the clean keys of earlier blocks, their own of their block
    sc = jnp.einsum("cqhd,khd->chqk", q[1:], k[0]) * scale
    sm = jnp.einsum("cqhd,ckhd->chqk", q[1:], k[1:]) * scale
    p = jax.nn.softmax(jnp.concatenate(
        [jnp.where(before, sc, -jnp.inf), jnp.where(same, sm, -jnp.inf)],
        axis=-1), axis=-1)
    om = (jnp.einsum("chqk,khd->cqhd", p[..., :t], v[0])
          + jnp.einsum("chqk,ckhd->cqhd", p[..., t:], v[1:]))
    o = jnp.concatenate([o0[None], om]).reshape(c1, t, heads * head_dim)
    xs = xs + _linear(o, lw["o.w"].astype(jnp.float32), quant)
    b = _rms(xs, ln["ln2.g"], eps).reshape(c1 * t, -1)
    return xs + experts(b, lw, top_k, quant).reshape(xs.shape)


_STATIC = ("block", "mask_id", "copies", "heads", "kv_heads", "head_dim",
           "eps", "theta", "top_k", "quant")


@functools.partial(jax.jit, static_argnames=_STATIC)
def logits_in_order(w, ids, order, *, block, mask_id, copies, heads,
                    kv_heads, head_dim, eps=1e-6, theta=1e6, top_k=8,
                    quant=None):
    """``ids [T]``, ``order [T]`` -> logits ``[T, V]`` (float32): row p is
    position p's in the state of the pass ``order[p]`` of its block that
    unmasked it (the clean stream's where ``order[p]`` is -1). ``copies``
    is one more than the largest pass index."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        pos = jnp.arange(t)
        blk = pos // block
        x = w["wte"][ids].astype(jnp.float32)
        m = w["wte"][mask_id].astype(jnp.float32)
        masked = order[None, :] >= jnp.arange(copies)[:, None]  # [C, T]
        xs = jnp.concatenate(
            [x[None], jnp.where(masked[..., None], m, x[None])])
        layers = {k: w["h." + k] for k in LAYER_LEAVES}
        step = functools.partial(
            layer, blk=blk, heads=heads, kv_heads=kv_heads,
            head_dim=head_dim, eps=eps, theta=theta, top_k=top_k,
            quant=quant)
        xs, _ = jax.lax.scan(lambda c, lw: (step(c, lw), None), xs, layers)
        # each position's row of the stream it was drawn in
        x = jnp.take_along_axis(
            xs, (order + 1)[None, :, None].clip(0, copies), axis=0)[0]
        x = _rms(x, w["lnf.g"].astype(jnp.float32), eps)
        return _linear(x, w["head.w"].astype(jnp.float32), quant)


def logits(w, ids, *, block, mask_id, heads, kv_heads, head_dim, eps=1e-6,
           theta=1e6, top_k=8, quant=None):
    """``ids [T]`` -> ``[T, V]`` under the ``sequential`` rule: row r is
    position r + 1's logits in the state it was drawn in (the positions of
    its block before it known, it and after masked); the last row is 0."""
    order = jnp.arange(ids.shape[0], dtype=jnp.int32) % block
    lg = logits_in_order(w, ids, order, block=block, mask_id=mask_id,
                         copies=block, heads=heads, kv_heads=kv_heads,
                         head_dim=head_dim, eps=eps, theta=theta,
                         top_k=top_k, quant=quant)
    return jnp.concatenate([lg[1:], jnp.zeros_like(lg[:1])])
