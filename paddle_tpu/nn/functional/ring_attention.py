"""Ring attention — context-parallel exact attention for long sequences.

Reference capability (SURVEY.md §2.3 "Context parallel / ring attention",
§5 "Long-context"): PaddleNLP's `RingFlashAttention` rotates KV blocks
between ranks with NCCL P2P while each rank computes blockwise flash
attention over its resident queries; core Paddle only supplies the p2p ops
and flash kernel.

TPU-native design — first-class here: inside `shard_map` with the sequence
dim sharded over a mesh axis, KV blocks rotate around the ring with
`lax.ppermute` (collective-permute — a single ICI hop per step, the
optimal pattern on the torus) while an online-softmax accumulator combines
per-block results; causal masking is applied at *global* sequence positions
so the result is bitwise the same math as dense causal attention. The loop
is unrolled over the (static) ring size so XLA overlaps each ppermute with
the previous block's compute.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def ring_flash_attention(
    q, k, v, axis_name: str, causal: bool = False, scale: Optional[float] = None
):
    """Exact attention over a ring; call inside shard_map.

    q, k, v: rank-local [B, T_local, H, D] (global seq = ring_size * T_local,
    sharded contiguously in rank order over `axis_name`).
    Returns the rank-local [B, T_local, H, D] output block.
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, tl, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * h, tl, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * h, tl, d)

    m = jnp.full((b * h, tl, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b * h, tl, 1), jnp.float32)
    acc = jnp.zeros((b * h, tl, d), jnp.float32)

    q_pos = rank * tl + lax.broadcasted_iota(jnp.int32, (tl, tl), 0)
    perm = [(i, (i + 1) % n) for i in range(n)]

    k_cur, v_cur = kf, vf
    for step in range(n):
        # after `step` rotations we hold the block that started on rank - step
        src = (rank - step) % n
        s = jnp.einsum("bqd,bkd->bqk", qf, k_cur).astype(jnp.float32) * scale
        if causal:
            k_pos = src * tl + lax.broadcasted_iota(jnp.int32, (tl, tl), 1)
            s = jnp.where((k_pos <= q_pos)[None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqk,bkd->bqd", p.astype(v_cur.dtype), v_cur)
        m = m_new
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return jnp.swapaxes(out.reshape(b, h, tl, d), 1, 2)


def context_parallel_attention(q, k, v, causal: bool = False, scale=None, axis_name: str = "sep"):
    """Dense-equivalent attention with the sequence sharded over `axis_name`
    of the global mesh. Wraps ring_flash_attention in shard_map; usable both
    eagerly (via an internal jit) and inside a compiled step.

    This is how long-context models exceed single-chip HBM limits: activations
    never materialize the full sequence on one chip (SURVEY.md §5).
    """
    from ...distributed import mesh as _mesh
    from jax.sharding import PartitionSpec as P

    m = _mesh.get_global_mesh()
    if m is None or axis_name not in m.shape or m.shape[axis_name] == 1:
        from .attention import _sdpa_reference

        return _sdpa_reference(q, k, v, None, 0.0, causal, scale)

    spec = P(None, axis_name, None, None)
    mapped = jax.shard_map(
        lambda a, b_, c: ring_flash_attention(a, b_, c, axis_name, causal, scale),
        mesh=m,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )
    return jax.jit(mapped)(q, k, v)
