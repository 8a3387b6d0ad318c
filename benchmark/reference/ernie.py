"""ERNIE 3.0 base for sequence classification as a plain reference: float32
``jax.numpy`` at ``highest`` matmul precision, no kernels. Written from the
published description (Sun et al. 2021, ERNIE 3.0; the encoder is BERT's,
Devlin et al. 2019, with one more embedding table for the task type); it
imports nothing of the program.

    x_0 = LN(W_te[ids] + W_pe[0..T) + W_type[0] + W_task[0])     eps 1e-12
    a   = softmax(q k^T / sqrt(d)) v W_o + b_o      bidirectional, H heads
    x   = LN(x + a; g1, b1)                          post-LN
    x   = LN(x + gelu(x W_1 + b_1) W_2 + b_2; g2, b2)            gelu by erf
    pooled = tanh(x_L[0] W_p + b_p) ;  logits = pooled W_c + b_c
    loss = mean cross-entropy over the rows

No dropout: the configuration that is measured states 0 (PERF.md says why).
The fused projection's layout is the GPT reference's: column
``(head * 3 + j) * d + e``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.gpt import _linear, _ln

LAYER_LEAVES = ("qkv.w", "qkv.b", "out.w", "out.b", "ln1.g", "ln1.b",
                "fc1.w", "fc1.b", "fc2.w", "fc2.b", "ln2.g", "ln2.b")


def block(x, lw, heads, eps, quant=None):
    t, h = x.shape
    d = h // heads
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    qkv = _linear(x, f32["qkv.w"], f32["qkv.b"], quant).reshape(t, heads, 3, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    a = _linear(o.reshape(t, h), f32["out.w"], f32["out.b"], quant)
    x = _ln(x + a, f32["ln1.g"], f32["ln1.b"], eps)
    m = jax.nn.gelu(_linear(x, f32["fc1.w"], f32["fc1.b"], quant),
                    approximate=False)
    m = _linear(m, f32["fc2.w"], f32["fc2.b"], quant)
    return _ln(x + m, f32["ln2.g"], f32["ln2.b"], eps)


def class_logits(w, ids, *, heads, eps, quant=None):
    """One row ``ids [T]`` -> class logits ``[C]``."""
    f = lambda k: w[k].astype(jnp.float32)  # noqa: E731
    t = ids.shape[0]
    x = f("wte")[ids] + f("wpe")[:t] + f("wtype")[0] + f("wtask")[0]
    x = _ln(x, f("lne.g"), f("lne.b"), eps)
    layers = {k: w["h." + k] for k in LAYER_LEAVES}
    step = jax.checkpoint(
        functools.partial(block, heads=heads, eps=eps, quant=quant))
    x, _ = jax.lax.scan(lambda c, lw: (step(c, lw), None), x, layers)
    pooled = jnp.tanh(_linear(x[0], f("pool.w"), f("pool.b"), quant))
    return _linear(pooled, f("cls.w"), f("cls.b"), quant)


def loss(w, ids, labels, *, heads, eps=1e-12, quant=None):
    """Mean cross-entropy over a batch ``ids [B, T]``, ``labels [B]``."""
    with jax.default_matmul_precision("highest"):
        lg = jax.lax.map(lambda i: class_logits(
            w, i, heads=heads, eps=eps, quant=quant), ids)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
