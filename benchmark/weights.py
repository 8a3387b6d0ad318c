"""Weights from the seed, on the device, in one jitted call, in the type the
configuration states. The benchmark makes them; the program and the plain
reference are each handed their own copy made from the same seed.

A weight spec is ``{name: (shape, mean, std)}`` under the benchmark's own
names. An architecture's file (``archs/<arch>.py``) states it and maps the
names onto the program's parameters.
"""
from __future__ import annotations

import zlib


def with_layers(spec, layer, n, stacked):
    """``spec`` with the per-layer leaves ``layer`` added ``n`` times over:
    stacked, each gets one leading layer axis (``h.<leaf>``), else one leaf a
    layer (``h<i>.<leaf>``)."""
    if stacked:
        spec.update({f"h.{k}": ((n,) + s, m, d)
                     for k, (s, m, d) in layer.items()})
    else:
        for i in range(n):
            spec.update({f"h{i}.{k}": val for k, val in layer.items()})
    return spec


def layer_of(name):
    """'h3.qkv.w' -> ('qkv.w', 3): a per-layer leaf's key is folded from its
    base name and then its layer, so that the stacked leaf 'h.qkv.w' holds
    the same values as the leaves 'h0.qkv.w', 'h1.qkv.w', ... do."""
    head, _, rest = name.partition(".")
    if head.startswith("h") and head[1:].isdigit():
        return rest, int(head[1:])
    return None


def make(spec: dict, seed: int, dtype: str) -> dict:
    """Every leaf of ``spec`` in ONE jitted call: ``mean + std * normal``
    from a threefry key folded with the leaf's name, rounded to ``dtype``.
    threefry, named: the process default on a TPU may be rbg, whose bits
    depend on how a call is batched."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    names = sorted(spec)

    def crc(s):
        return zlib.crc32(s.encode()) & 0x7FFFFFFF

    def build(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = {}
        for name in names:
            shape, mean, std = spec[name]
            if name.startswith("h."):  # stacked: one leading layer axis
                base = jax.random.fold_in(key, crc(name[2:]))
                x = jax.vmap(lambda i: jax.random.normal(
                    jax.random.fold_in(base, i), shape[1:], jnp.float32))(
                        jnp.arange(shape[0]))
            elif layer_of(name):
                rest, i = layer_of(name)
                x = jax.random.normal(jax.random.fold_in(
                    jax.random.fold_in(key, crc(rest)), i), shape,
                    jnp.float32)
            else:
                x = jax.random.normal(jax.random.fold_in(key, crc(name)),
                                      shape, jnp.float32)
            out[name] = (mean + std * x).astype(dt)
        return out

    seed = int(seed)  # any whole number: the high bits are folded in
    key = jax.random.key_data(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31))
    return jax.jit(build)(key)
