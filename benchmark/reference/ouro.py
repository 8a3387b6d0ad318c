"""Ouro (Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; ByteDance/Ouro-2.6B, ``model_type: ouro``) as a
plain reference: float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernels, no cache, no batching. It imports nothing of the program. Sizes are
the published ``config.json``'s; a line marked *assumed* is not in that file
and is written from the model's published ``modeling_ouro.py``, as recalled,
and section 3 of the paper (the configuration file lists each under
``assumed``).

    x = E[ids]                              no position table
    for u in 0 .. T-1:                      T = total_ut_steps; the SAME L
      for l in 0 .. L-1:                    layers' weights every time
        a = RMSNorm(x; g1_l)                eps 1e-6
        q, k, v = a Wq_l, a Wk_l, a Wv_l    no biases; H heads, Hkv kv heads of d
        o = softmax(rope(q) rope(k)^T / sqrt(d) + causal) v
                                            RoPE theta 1e6, rotate-half pairing;
                                            over the keys that loop u of layer l
                                            made, no other loop's (*assumed*: a
                                            cache holds one entry a (loop, layer))
        x = x + RMSNorm(o Wo_l; g2_l)       *assumed*: sandwich norm, a second
        m = RMSNorm(x; g3_l)                RMSNorm closes each sublayer
        x = x + RMSNorm((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      x = RMSNorm(x; gf)                    *assumed*: the final norm closes
                                            EVERY loop, its output enters the next
      lambda_u = sigmoid(x w_exit + b_exit) the exit gate, one number a token
    logits = x W_head                       after the last loop; untied head

    p_u = lambda_u prod_{j<u} (1 - lambda_j), the last loop takes what is
    left. A token leaves at the first u whose cumulated p reaches
    early_exit_threshold; the published threshold is 1, so every token runs
    all T loops and the gate cannot change a logit.

With no cache "the keys that loop u of layer l made" is plain causal
attention inside each application of a layer: what the program's paged pool
must reproduce with ``T * L`` entries.

Weights come stacked (``h.<leaf>`` with a leading layer axis) in the type the
configuration serves; a layer is cast to float32 as it is used (a
``lax.scan`` over the stack inside a Python loop over ``u``).

``quant`` is the CONTROL, not the reference (``reference/gpt.py``'s
``_linear``): every matrix product of a linear layer (and the head) with its
weights rounded by output channel and its input rounded by row under absmax
scales, to int8 (W8A8) or to float8 e4m3, straight-through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.gpt import _linear as _gpt_linear  # the controls' rounding

LAYER_LEAVES = ("ln1.g", "q.w", "k.w", "v.w", "o.w", "ln2.g", "ln3.g",
                "gate.w", "up.w", "down.w", "ln4.g")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _linear(x, w, quant):
    return _gpt_linear(x, w, None, quant)  # no layer of this model has a bias


def _rope(x, theta):
    """``x [T, H, d]`` at positions 0..T-1: pair (i, i + d/2) turns by
    ``p / theta^(2i/d)`` (rotate-half)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def block(x, lw, heads, kv_heads, head_dim, eps, theta, quant=None):
    """One application of one layer over one sequence ``x [T, h]``."""
    t = x.shape[0]
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    a = _rms(x, f32["ln1.g"], eps)
    q = _rope(_linear(a, f32["q.w"], quant).reshape(t, heads, head_dim),
              theta)
    k = _rope(_linear(a, f32["k.w"], quant).reshape(t, kv_heads, head_dim),
              theta)
    v = _linear(a, f32["v.w"], quant).reshape(t, kv_heads, head_dim)
    k, v = (jnp.repeat(y, heads // kv_heads, axis=1) for y in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, heads * head_dim)
    x = x + _rms(_linear(o, f32["o.w"], quant), f32["ln2.g"], eps)
    m = _rms(x, f32["ln3.g"], eps)
    m = jax.nn.silu(_linear(m, f32["gate.w"], quant)) * _linear(
        m, f32["up.w"], quant)
    return x + _rms(_linear(m, f32["down.w"], quant), f32["ln4.g"], eps)


def _closed(w, ids, loops, quant, remat, **kw):
    """The hidden state that closes each loop: ``[loops, T, h]``."""
    x = w["wte"][ids].astype(jnp.float32)
    layers = {k: w["h." + k] for k in LAYER_LEAVES}
    step = functools.partial(block, quant=quant, **kw)
    if remat:
        step = jax.checkpoint(step)
    closed = []
    for _ in range(loops):
        x, _ = jax.lax.scan(lambda c, lw: (step(c, lw), None), x, layers)
        x = _rms(x, w["lnf.g"].astype(jnp.float32), kw["eps"])
        closed.append(x)
    return jnp.stack(closed)


_STATIC = ("loops", "heads", "kv_heads", "head_dim", "eps", "theta", "quant")


@functools.partial(jax.jit, static_argnames=_STATIC)
def logits(w, ids, *, loops, heads, kv_heads, head_dim, eps=1e-6,
           theta=1e6, quant=None):
    """``ids [T]`` -> logits ``[T, V]`` (float32) of the last loop. Padding
    on the right does not reach the positions before it (causal)."""
    with jax.default_matmul_precision("highest"):
        x = _closed(w, ids, loops, quant, False, heads=heads,
                    kv_heads=kv_heads, head_dim=head_dim, eps=eps,
                    theta=theta)[-1]
        return _linear(x, w["head.w"].astype(jnp.float32), quant)


@functools.partial(jax.jit, static_argnames=_STATIC[:-1])
def exit_pdf(w, ids, *, loops, heads, kv_heads, head_dim, eps=1e-6,
             theta=1e6):
    """``ids [T]`` -> the exit distribution ``[loops, T]``."""
    with jax.default_matmul_precision("highest"):
        x = _closed(w, ids, loops, None, False, heads=heads,
                    kv_heads=kv_heads, head_dim=head_dim, eps=eps,
                    theta=theta)
        lam = jax.nn.sigmoid(
            jnp.dot(x, w["exit.w"].astype(jnp.float32))[..., 0]
            + w["exit.b"].astype(jnp.float32))
        stay = jnp.cumprod(1.0 - lam, axis=0)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def loss(w, ids, labels, *, loops, heads, kv_heads, head_dim, eps=1e-6,
         theta=1e6, quant=None):
    """Mean next-token cross-entropy of the LAST loop's logits over a batch
    ``ids, labels [B, T]``, each row on its own: not the published training
    objective (the expected loss over exit steps plus an entropy term, which
    the ``config`` does not state); for the tests."""
    with jax.default_matmul_precision("highest"):
        def one(i):
            x = _closed(w, i, loops, quant, True, heads=heads,
                        kv_heads=kv_heads, head_dim=head_dim, eps=eps,
                        theta=theta)[-1]
            return _linear(x, w["head.w"].astype(jnp.float32), quant)

        logp = jax.nn.log_softmax(jax.vmap(one)(ids), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
