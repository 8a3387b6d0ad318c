"""AdamW (Loshchilov & Hutter 2019, Algorithm 2) in float32, one leaf at a
time, as the plain reference's optimizer. Decay is decoupled and applied to
every leaf, which is what the configurations here state.

    p <- p (1 - lr wd)
    m <- b1 m + (1 - b1) g ;  v <- b2 v + (1 - b2) g^2
    p <- p - lr (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("t", "b1", "b2", "eps", "wd"))
def update(p, m, v, g, lr, *, t, b1=0.9, b2=0.999, eps=1e-8, wd=0.01):
    """One step ``t`` (1-based) of one leaf; returns (p, m, v)."""
    p = p * (1.0 - lr * wd)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v
