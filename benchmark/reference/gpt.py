"""GPT-3 (Brown et al. 2020; the GPT-2 block of Radford et al. 2019) as a
plain reference: float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernels, no cache, no batching. Written from the published equations; it
imports nothing of the program.

    x_0   = W_te[ids] + W_pe[0..T)
    a     = LN(x; g1, b1)                       pre-LN, eps 1e-5
    q,k,v = split(a W_qkv + b_qkv)              H heads of d = h / H
    x     = x + softmax(q k^T / sqrt(d) + causal) v  W_o + b_o
    x     = x + gelu(LN(x; g2, b2) W_1 + b_1) W_2 + b_2     gelu by erf
    logits = LN(x_L; gf, bf) W_te^T             the head is tied

Layout of the fused projection, stated here because it is a convention and
not mathematics: column ``(head * 3 + j) * d + e`` of ``W_qkv`` is element
``e`` of head ``head`` of q (j = 0), k (1) or v (2).

Weights come stacked (``h.<leaf>`` with a leading layer axis) in the type the
configuration serves; each layer is cast to float32 as it is used, so that
the reference fits beside nothing else on one chip.

``quant`` is the CONTROL, not the reference: every matrix product of a
linear layer (and the head) with its weights rounded by output channel and
its input rounded by row under absmax scales, to int8 (W8A8) or to float8
e4m3: the steps below bfloat16 that would tempt a later PR. Rounding is
straight-through, so that the control has a gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1.g", "ln1.b", "qkv.w", "qkv.b", "out.w", "out.b",
                "ln2.g", "ln2.b", "fc1.w", "fc1.b", "fc2.w", "fc2.b")


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through for training


def _fake_fp8(x, axis):
    """Round to float8 e4m3 (3 mantissa bits) under an absmax scale."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, b, quant):
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"no control precision {quant!r}")
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return y if b is None else y + b


def block(x, lw, heads, eps, quant=None):
    """One decoder layer over one sequence ``x [T, h]`` (float32)."""
    t, h = x.shape
    d = h // heads
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    a = _ln(x, f32["ln1.g"], f32["ln1.b"], eps)
    qkv = _linear(a, f32["qkv.w"], f32["qkv.b"], quant).reshape(t, heads, 3, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h)
    x = x + _linear(o, f32["out.w"], f32["out.b"], quant)
    m = _ln(x, f32["ln2.g"], f32["ln2.b"], eps)
    m = jax.nn.gelu(_linear(m, f32["fc1.w"], f32["fc1.b"], quant),
                    approximate=False)
    return x + _linear(m, f32["fc2.w"], f32["fc2.b"], quant)


def _forward(w, ids, heads, eps, quant, remat):
    t = ids.shape[0]
    x = (w["wte"][ids].astype(jnp.float32)
         + w["wpe"][:t].astype(jnp.float32))
    layers = {k: w["h." + k] for k in LAYER_LEAVES}
    step = functools.partial(block, heads=heads, eps=eps, quant=quant)
    if remat:  # the backward of a deep model at full width must fit
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(lambda c, lw: (step(c, lw), None), x, layers)
    x = _ln(x, w["lnf.g"].astype(jnp.float32),
            w["lnf.b"].astype(jnp.float32), eps)
    return _linear(x, w["wte"].astype(jnp.float32).T, None, quant)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "quant"))
def logits(w, ids, *, heads, eps=1e-5, quant=None):
    """``ids [T]`` -> logits ``[T, V]`` (float32) of one sequence. Padding
    on the right does not reach the positions before it (causal)."""
    with jax.default_matmul_precision("highest"):
        return _forward(w, ids, heads, eps, quant, remat=False)


def loss(w, ids, labels, *, heads, eps=1e-5, quant=None):
    """Mean next-token cross-entropy over a batch ``ids, labels [B, T]``,
    each row on its own (no batching inside the model)."""
    with jax.default_matmul_precision("highest"):
        lg = jax.vmap(lambda i: _forward(w, i, heads, eps, quant, True))(ids)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
