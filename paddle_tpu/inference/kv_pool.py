"""The paged KV pool's format, in one place.

``KVPool`` holds one K and one V page pool ``[L, num_pages, Hkv, page_size,
D]`` at the store dtype, plus one absmax scale slab ``[L, num_pages, Hkv,
page_size]`` each when the store is int8. Everything that depends on that
format lives here: allocation and the mp commitment, the two writes, the
two reads, and the page handoff between engines. ``DecodeEngine`` carries the
pool through its compiled programs as ONE (donated) pytree argument and
never indexes its arrays; a model whose cache has another format (a latent
cache, a window) writes its pool class beside this one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import mesh as _mesh
from ..distributed.grad_comm import dequantize_absmax, quantize_absmax
from ..nn import functional as F

__all__ = ["KVPool", "KV_DTYPES", "TRASH_PAGE"]

KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}

#: the reserved all-garbage page every unallocated page-table entry (and
#: every masked scatter) points at; never handed out by the allocator
TRASH_PAGE = 0


def active_mp_mesh():
    """The active mesh when it has an mp axis of degree > 1, else None:
    without one every sharding hint of the serving programs is a no-op."""
    m = _mesh.get_global_mesh()
    if m is None or m.empty or _mesh.mesh_axis_size("mp", m) <= 1:
        return None
    return m


def _block_write(cache, scales, layer, kv, row, cached_len, true_len):
    """One of K/V of ``KVPool.write_block``."""
    x = kv[0]  # [TB, Hkv, D]
    tb, hkv, d = x.shape
    p = cache.shape[3]
    nb = -(-tb // p)
    if nb * p != tb:
        x = jnp.pad(x, ((0, nb * p - tb), (0, 0), (0, 0)))
    blk = jnp.swapaxes(x.reshape(nb, p, hkv, d), 1, 2)  # [nb, Hkv, P, D]
    mp = row.shape[0]
    g = cached_len // p + jnp.arange(nb)
    need = (true_len + p - 1) // p  # pages with any real prompt content
    idx = jnp.where(g < need, row[jnp.minimum(g, mp - 1)], TRASH_PAGE)
    if scales is not None:
        q, scale = quantize_absmax(blk, axis=-1)  # scale [nb, Hkv, P, 1]
        cache = cache.at[layer, idx].set(q.astype(cache.dtype))
        scales = scales.at[layer, idx].set(scale[..., 0])
        return cache, scales
    return cache.at[layer, idx].set(blk.astype(cache.dtype)), scales


def _token_write(cache, scales, layer, kv, tables, positions):
    """One of K/V of ``KVPool.write_tokens``.

    One in-place ``dynamic_update_slice`` of ``[1, 1, Hkv, 1, D]`` a
    (slot, token), unrolled. NOT one scatter: its ``[Hkv, D]`` update
    window makes XLA:TPU keep the pool with heads beside the lane axis
    (``{4,2,3,1,0}``), which is neither the layout the pool arrives in nor
    the one the paged kernel reads, so a pass then copies the whole pool
    in and out and re-lays a layer of it before each kernel call (30 of
    a 37 ms pass: PERF.md, PR 30). And NOT a ``fori_loop``, whose carry
    takes that layout too. tests/test_tpu_aot_compile.py holds the
    engine's compiled programs to it."""
    page_size = cache.shape[3]
    pg = jnp.take_along_axis(tables, positions // page_size, axis=1)
    off = positions % page_size
    if scales is not None:
        kv, scale = quantize_absmax(kv, axis=-1)  # scale [S, T, Hkv, 1]
    rows = kv.astype(cache.dtype)[:, :, None, None, :, None, :]
    for s_i in range(pg.shape[0]):
        for t_i in range(pg.shape[1]):
            at = (layer, pg[s_i, t_i], 0, off[s_i, t_i])
            cache = jax.lax.dynamic_update_slice(
                cache, rows[s_i, t_i], at + (0,))
            if scales is not None:
                scales = jax.lax.dynamic_update_slice(
                    scales, scale[s_i, t_i, None, None], at)
    return cache, scales


@jax.tree_util.register_pytree_node_class
class KVPool:
    """K and V page pools (and their int8 scale slabs) as one pytree: the
    leaves are the arrays held, ``None`` scales being no leaf. Every
    method that changes the pool returns a new one (traced or eager)."""

    def __init__(self, k, v, k_scales=None, v_scales=None):
        self.k, self.v, self.k_scales, self.v_scales = (
            k, v, k_scales, v_scales)

    def tree_flatten(self):
        return (self.k, self.v, self.k_scales, self.v_scales), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @classmethod
    def zeros(cls, cache_layers, num_pages, num_kv_heads, page_size,
              head_dim, kv_dtype, mesh=None):
        """An empty pool, ``cache_layers`` entries deep: one a layer, or one
        a (loop, layer) where a model runs its layers more than once; on a
        ``mesh`` committed kv-head-sharded ONCE (``P(None, None, "mp")``:
        GQA groups stay whole per shard)."""
        shape = (cache_layers, num_pages, num_kv_heads, page_size, head_dim)
        arrays = [jnp.zeros(shape, KV_DTYPES[kv_dtype]) for _ in "kv"]
        if kv_dtype == "int8":
            arrays += [jnp.ones(shape[:-1], jnp.float32) for _ in "kv"]
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            kv_sh = NamedSharding(mesh, PartitionSpec(None, None, "mp"))
            arrays = [jax.device_put(a, kv_sh) for a in arrays]
        return cls(*arrays)

    # -- sizes --------------------------------------------------------------

    @property
    def shape(self):
        """``(L, num_pages, Hkv, page_size, D)``."""
        return self.k.shape

    @property
    def kv_dtype(self) -> str:
        return next(n for n, d in KV_DTYPES.items() if self.k.dtype == d)

    @property
    def bytes_per_token(self) -> int:
        """What one cached token holds over every entry, K and V and, for
        an int8 store, their scales."""
        l, _, hkv, _, d = self.shape
        per_head = d * self.k.dtype.itemsize + (
            0 if self.k_scales is None else self.k_scales.dtype.itemsize)
        return 2 * l * hkv * per_head

    @property
    def dequantized_bytes(self) -> int:
        """Bytes of both pools dequantized to f32, what an unfused read of
        an int8 pool materializes a pass; 0 for a float pool."""
        if self.k_scales is None:
            return 0
        return 2 * int(np.prod(self.shape)) * 4

    # -- traced inside the engine's compiled programs -----------------------

    def pin(self):
        """Trailing constraints pinning a RETURNED pool to the kv-head-
        sharded layout it was committed with, so the compiled program's
        output shardings match its input shardings and the cache-carry
        loop never flaps between layouts (a flap would recompile, breaking
        the buckets_used + 2 program-count gate). No-op without an active
        mp mesh."""
        m = active_mp_mesh()
        if m is None:
            return self
        kv = _mesh.P(None, None, "mp")  # [L, N, Hkv, ...]: shard kv heads
        return jax.tree.map(
            lambda a: _mesh.sharding_constraint(a, kv, m), self)

    def write_block(self, layer, k, v, row, cached_len, true_len):
        """Write a prompt tail k, v [1, TB, Hkv, D] (positions cached_len
        ... cached_len + TB - 1) into the pages ``row[cached_len//P + j]``.
        Pages holding padding only (entirely >= true_len) are redirected
        to the trash page so a padded tail bucket can never scribble past
        the request's allocation."""
        kc, ks = _block_write(self.k, self.k_scales, layer, k, row,
                              cached_len, true_len)
        vc, vs = _block_write(self.v, self.v_scales, layer, v, row,
                              cached_len, true_len)
        return KVPool(kc, vc, ks, vs)

    def write_tokens(self, layer, k, v, tables, positions):
        """Write k, v [S, T, Hkv, D] at absolute positions [S, T] through
        the page tables [S, MP] (decode T=1, verify T=k+1). Inactive slots
        carry zeroed table rows, so their writes land on the trash page."""
        kc, ks = _token_write(self.k, self.k_scales, layer, k, tables,
                              positions)
        vc, vs = _token_write(self.v, self.v_scales, layer, v, tables,
                              positions)
        return KVPool(kc, vc, ks, vs)

    def attend(self, q, layer, tables, positions, kernel, block=1):
        """One layer of paged attention, q [S, T, H, D] from ``positions``
        [S] on (block-causal over blocks of ``block`` positions where that
        is > 1: ``F.paged_attention``). The fused Pallas path hands the kernel the whole STORED
        pool and the layer's index, which its index maps read, so the pool
        is never sliced — plus the layer's absmax scale slabs when int8
        (1 MB, sliced: the kernel wants them with a trailing 1, which the
        stacked slab could not take without padding every lane), so
        dequant happens against the VMEM-resident page inside the kernel;
        the einsum oracle dequantizes the layer's view up front. The
        kernel is pinned explicitly so an ambient PADDLE_TPU_ATTN_KERNEL
        cannot diverge a program from the engine's resolved (and
        AOT-cache-keyed) choice."""
        ks, vs = self.k_scales, self.v_scales
        if kernel == "pallas":
            return F.paged_attention(
                q, self.k, self.v, tables, positions, layer=layer,
                k_scales=None if ks is None else ks[layer],
                v_scales=None if vs is None else vs[layer],
                kernel="pallas", block=block)
        k, v = self.k[layer], self.v[layer]
        if ks is not None:
            k = dequantize_absmax(k, ks[layer][..., None])
            v = dequantize_absmax(v, vs[layer][..., None])
        return F.paged_attention(q, k, v, tables, positions,
                                 kernel="einsum", block=block)

    def attend_block(self, q, layer, row, cached_len, kernel, block=1):
        """One layer of a prompt tail's attention: q [1, TB, H, D] from
        position ``cached_len`` on, over the pages of ``row`` [MP], the
        tail's own (``write_block`` comes first) and the cached prefix's
        alike. The fused path gathers THIS slot's pages of the layer into
        contiguous keys ``[Hkv, MP * P, D]`` at the stored dtype (an
        eighth of a layer's pool at the serving cell's 8 slots; int8 with
        its scale rows, dequantised inside the kernel) and runs the
        blocked prefill kernel over them, which reads key blocks the MXU
        can fill and stops at each row block's causal horizon; the pool
        keeps its one layout. The einsum oracle is ``attend``'s, on a
        table of one slot."""
        if kernel != "pallas":
            return self.attend(q, layer, row[None],
                               jnp.reshape(cached_len, (1,)), kernel, block)

        def keys(cache):  # [L, N, Hkv, P, ...] -> [Hkv, MP * P, ...]
            g = jnp.swapaxes(cache[layer, row], 0, 1)
            return g.reshape(g.shape[0], -1, *g.shape[3:])

        ks, vs = self.k_scales, self.v_scales
        return F.prefill_attention(
            q, keys(self.k), keys(self.v), cached_len,
            k_scales=None if ks is None else keys(ks),
            v_scales=None if vs is None else keys(vs), block=block)

    # -- the handoff between engines (eager) --------------------------------

    def export_pages(self, idx) -> dict:
        """Pages ``idx`` of every layer as host arrays, bit-equal to what
        the pool holds: ``k``, ``v`` ``[L, n, Hkv, P, D]`` (and ``ks``,
        ``vs`` of an int8 pool) under the pool's ``pool_dtype``."""
        idx = jnp.asarray(idx)
        out = {"pool_dtype": self.kv_dtype}
        for name, a in zip(("k", "v", "ks", "vs"), jax.tree.leaves(self)):
            out[name] = np.asarray(jnp.take(a, idx, axis=1))
        return out

    def import_pages(self, idx, payload: dict):
        """The pool with an ``export_pages`` payload written to pages
        ``idx``. int8 into int8 copies the quantized slabs and their
        scales verbatim and float into float casts (bit-equal when the
        dtypes match); a float payload into an int8 pool is requantized at
        the same per-[page, head, token] granularity ``write_block`` uses;
        an int8 payload into a float pool is dequantized."""
        idx = jnp.asarray(np.asarray(idx, np.int32))
        k, v = jnp.asarray(payload["k"]), jnp.asarray(payload["v"])
        ks = vs = None
        if "ks" in payload:
            ks = jnp.asarray(payload["ks"], jnp.float32)
            vs = jnp.asarray(payload["vs"], jnp.float32)
        if self.k_scales is None and ks is not None:
            k = dequantize_absmax(k, ks[..., None])
            v = dequantize_absmax(v, vs[..., None])
        elif self.k_scales is not None and ks is None:
            (k, ks), (v, vs) = (quantize_absmax(
                a.astype(jnp.float32), axis=-1) for a in (k, v))
            ks, vs = ks[..., 0], vs[..., 0]
        new = [a.at[:, idx].set(b.astype(a.dtype)) for a, b in zip(
            jax.tree.leaves(self), (k, v, ks, vs))]
        return KVPool(*new)
