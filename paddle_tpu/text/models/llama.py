"""Llama family — modern decoder-only LM: RoPE + RMSNorm + SwiGLU + GQA.

Reference capability: the Paddle ecosystem's Llama lives in PaddleNLP
(`LlamaModel`/`LlamaForCausalLM` built from the same fleet mpu layers as
GPT, with fused rope and GQA via its flash-attention integration). Core
Paddle provides the building blocks (mpu layers, flash_attn kernels).

TPU-native design mirrors paddle_tpu's GPT: mpu layer classes as sharding
annotations, bf16-friendly [B, T, H, D] attention layout. Grouped-query
attention runs through the Pallas flash kernel's native GQA path
(ops/pallas/flash_attention.py — kv heads selected in the BlockSpec index
map, no head replication in HBM); rotary embeddings are applied on the
fly from a per-block cos/sin cache (a read-only buffer, so the decoder
stacks under SpmdPipeline including its buffers).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ... import nn
from ...distributed import mesh as _mesh
from ...distributed.fleet.layers.mpu import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    mark_activation,
)
from ...distributed.fleet.utils import recompute as _recompute
from ...framework.core import Tensor
from ...framework.op import defop, raw
from ...nn import functional as F
from ...nn import initializer as I
from ...profiler import scope


class LlamaConfig:
    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_size: int = 768,
        intermediate_size: Optional[int] = None,
        num_hidden_layers: int = 12,
        num_attention_heads: int = 12,
        num_key_value_heads: Optional[int] = None,
        max_position_embeddings: int = 2048,
        rms_norm_eps: float = 1e-6,
        rope_theta: float = 10000.0,
        initializer_range: float = 0.02,
        tie_word_embeddings: bool = False,
        use_flash_attention: bool = True,
        use_recompute: bool = False,
        sequence_parallel: bool = False,
        fold_layers: bool = False,
        recompute_granularity: str = "full",
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # Llama SwiGLU sizing: 8/3 * h rounded up to a multiple of 256
        self.intermediate_size = intermediate_size or (
            (int(8 * hidden_size / 3) + 255) // 256 * 256
        )
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        assert num_attention_heads % self.num_key_value_heads == 0
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings
        self.use_flash_attention = use_flash_attention
        self.use_recompute = use_recompute
        # see GPTConfig.recompute_granularity ("full" is required for the
        # folded/stacked layer forms; dots-saveable stacks across layers)
        self.recompute_granularity = recompute_granularity
        self.sequence_parallel = sequence_parallel
        # one lax.scan over layer-stacked params without pp: compile time
        # O(1) in depth (see GPTConfig.fold_layers; same scan machinery)
        self.fold_layers = fold_layers


def _rope_cache(max_t: int, dim: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(max_t, dtype=np.float64)
    freqs = np.outer(t, inv)  # [T, dim/2]
    return (np.cos(freqs).astype(np.float32),
            np.sin(freqs).astype(np.float32))


@defop(name="apply_rope")
def _apply_rope(x, cos, sin, name=None):
    """x: [B, T, H, D]; cos/sin: [Tmax, D/2] → rotate pairs (interleaved
    halves, the Llama convention)."""
    import jax.numpy as jnp

    t = x.shape[1]
    d2 = x.shape[-1] // 2
    c = cos[:t][None, :, None, :]  # [1, T, 1, D/2]
    s = sin[:t][None, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(
        x.dtype
    )


@defop(name="gqa_flash_attention")
def _gqa_attention(q, k, v, causal=True):
    """[B, T, H, D] x [B, T, Hkv, D] — Pallas flash kernel, native GQA."""
    from ...ops.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=causal)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        kv_h = self.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv_h, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv_h, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)
        cos, sin = _rope_cache(
            config.max_position_embeddings, self.head_dim, config.rope_theta
        )
        import jax.numpy as jnp

        self.register_buffer("rope_cos", Tensor(jnp.asarray(cos)))
        self.register_buffer("rope_sin", Tensor(jnp.asarray(sin)))
        self.use_flash = config.use_flash_attention

    def forward(self, x):
        b, t, h = x.shape
        q = self.q_proj(x).reshape([b, t, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        q = _apply_rope(q, self.rope_cos, self.rope_sin)
        k = _apply_rope(k, self.rope_cos, self.rope_sin)
        if self.use_flash:
            o = _gqa_attention(q, k, v, causal=True)
        else:
            from ... import tensor as pt

            group = self.num_heads // self.num_kv_heads
            o = F.scaled_dot_product_attention(
                q,
                pt.repeat_interleave(k, group, axis=2),
                pt.repeat_interleave(v, group, axis=2),
                is_causal=True,
                training=self.training,
            )
        return self.o_proj(o.reshape([b, t, h]))


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(i, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    """Pre-RMSNorm block — structurally uniform → SpmdPipeline-stackable
    (its rope caches stack as read-only buffers)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.mlp = LlamaMLP(config)
        self._use_recompute = config.use_recompute
        self._recompute_granularity = getattr(
            config, "recompute_granularity", "full")
        self._sequence_parallel = config.sequence_parallel

    def _block(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        if self._sequence_parallel:
            x = mark_activation(x, seq_mp=True)
        return x

    def forward(self, x):
        if self._use_recompute:
            return _recompute(self._block, x,
                              granularity=self._recompute_granularity)
        return self._block(x)


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(std=config.initializer_range)),
        )
        blocks = [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        pp = _mesh.mesh_axis_size("pp")
        if pp > 1 and config.num_hidden_layers % pp == 0:
            from ...distributed.fleet.meta_parallel.pipeline_parallel import (
                SpmdPipeline,
            )

            self.layers = SpmdPipeline(
                blocks, num_stages=pp, recompute_block=config.use_recompute,
                recompute_granularity=getattr(
                    config, "recompute_granularity", "full"),
                # per-model overrides; None defers to DistributedStrategy
                # pipeline_configs / PADDLE_TPU_PP_SCHEDULE
                num_virtual_stages=getattr(config, "virtual_pp_degree", None),
                schedule=getattr(config, "pp_schedule", None),
            )
        else:
            if pp > 1:
                import warnings

                warnings.warn(
                    f"num_hidden_layers={config.num_hidden_layers} not "
                    f"divisible by pp_degree={pp}: Llama decoder runs "
                    "WITHOUT pipeline partitioning"
                )
            from ...distributed.fleet.meta_parallel.pipeline_parallel import (
                fold_or_list,
            )

            self.layers = fold_or_list(
                blocks, getattr(config, "fold_layers", False),
                recompute=config.use_recompute,
                recompute_granularity=getattr(
                    config, "recompute_granularity", "full"))
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        from ...distributed.fleet.meta_parallel.pipeline_parallel import (
            run_stack,
        )

        x = self.embed_tokens(input_ids)
        x = run_stack(self.layers, x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False,
            )
        self.criterion = ParallelCrossEntropy(ignore_index=-100)

    def _logits(self, hidden):
        if self.config.tie_word_embeddings:
            w = self.llama.embed_tokens.weight
            logits = F.linear(hidden, w.t())
            return mark_activation(logits, last_mp=True)
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, loss_mask=None):
        hidden = self.llama(input_ids)
        logits = self._logits(hidden)
        if labels is not None:
            loss = self.criterion(logits, labels)
            if loss_mask is not None:
                lm = loss_mask.reshape(loss.shape)
                return (loss * lm).sum() / lm.sum().clip(min=1.0)
            # average over VALID tokens: ignore_index positions contribute
            # zero loss and must not deflate the mean (HF Llama semantics)
            valid = (labels.reshape(loss.shape) != -100).astype(loss.dtype)
            return loss.sum() / valid.sum().clip(min=1.0)
        return logits


# ---------------------------------------------------------------------------
# KV-cache incremental decoding (reference: PaddleNLP Llama `use_cache` path
# over fused attention with cache_kv). TPU shape discipline: caches are
# PREALLOCATED [B, Tmax, Hkv, D] buffers updated in place by position, so a
# jitted decode step has one fixed signature for the whole generation.
# ---------------------------------------------------------------------------

@defop(name="rope_at")
def _apply_rope_at(x, cos, sin, pos):
    """Rotate a single-step [B, 1, H, D] tensor at absolute position `pos`."""
    import jax
    import jax.numpy as jnp

    d2 = x.shape[-1] // 2
    c = jax.lax.dynamic_slice_in_dim(cos, pos, 1, 0)[None, :, None, :]
    s = jax.lax.dynamic_slice_in_dim(sin, pos, 1, 0)[None, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


@defop(name="rope_positions")
def _apply_rope_positions(x, cos, sin, positions):
    """Rotate [B, T, H, D] at explicit ABSOLUTE positions — the serving
    engine's form of rope: `positions` is an int array [T] (shared across
    the batch, prefill) or [B, T] (per-slot decode), gathered from the
    cos/sin cache instead of sliced, so per-slot decode positions stay a
    single compiled program."""
    import jax.numpy as jnp

    d2 = x.shape[-1] // 2
    pos = jnp.asarray(positions)
    c = jnp.take(cos, pos, axis=0)[..., None, :]  # [(B,) T, 1, D/2]
    s = jnp.take(sin, pos, axis=0)[..., None, :]
    if pos.ndim == 1:
        c, s = c[None], s[None]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


@defop(name="cache_write")
def _cache_write(cache, kv, pos):
    """cache [B, Tmax, Hkv, D] <- kv [B, T, Hkv, D] at [pos : pos+T]."""
    import jax

    return jax.lax.dynamic_update_slice_in_dim(cache, kv.astype(cache.dtype), pos, 1)


def _decode_attention(q, ck, cv, pos):
    """One-step attention against the cache: q [B, 1, H, D] over
    ck/cv [B, Tmax, Hkv, D], positions > pos masked out.

    Thin adapter over ``F.decode_attention`` — the single decode-shape
    reference oracle (nn/functional/attention.py, GQA-native: no head
    replication): swap the cache to its [B, Hkv, Tmax, D] layout and
    broadcast the scalar position per slot. The head grouping (query
    head h -> kv head h // group) is identical on both sides."""
    import jax.numpy as jnp

    b = raw(q).shape[0]
    ckt = jnp.swapaxes(raw(ck), 1, 2)  # [B, Hkv, Tmax, D]
    cvt = jnp.swapaxes(raw(cv), 1, 2)
    return F.decode_attention(q, ckt, cvt, jnp.full((b,), pos, jnp.int32))


def _attn_prefill(attn: "LlamaAttention", x, cache):
    b, t, h = x.shape
    q = attn.q_proj(x).reshape([b, t, attn.num_heads, attn.head_dim])
    k = attn.k_proj(x).reshape([b, t, attn.num_kv_heads, attn.head_dim])
    v = attn.v_proj(x).reshape([b, t, attn.num_kv_heads, attn.head_dim])
    q = _apply_rope(q, attn.rope_cos, attn.rope_sin)
    k = _apply_rope(k, attn.rope_cos, attn.rope_sin)
    cache["k"] = _cache_write(cache["k"], k, 0)
    cache["v"] = _cache_write(cache["v"], v, 0)
    if attn.use_flash:
        o = _gqa_attention(q, k, v, causal=True)
    else:
        from ... import tensor as pt

        group = attn.num_heads // attn.num_kv_heads
        o = F.scaled_dot_product_attention(
            q, pt.repeat_interleave(k, group, axis=2),
            pt.repeat_interleave(v, group, axis=2), is_causal=True,
            training=False,
        )
    return attn.o_proj(o.reshape([b, t, h]))


def _attn_decode(attn: "LlamaAttention", x, cache, pos: int):
    b, t, h = x.shape  # t == 1
    q = attn.q_proj(x).reshape([b, t, attn.num_heads, attn.head_dim])
    k = attn.k_proj(x).reshape([b, t, attn.num_kv_heads, attn.head_dim])
    v = attn.v_proj(x).reshape([b, t, attn.num_kv_heads, attn.head_dim])
    q = _apply_rope_at(q, attn.rope_cos, attn.rope_sin, pos=pos)
    k = _apply_rope_at(k, attn.rope_cos, attn.rope_sin, pos=pos)
    cache["k"] = _cache_write(cache["k"], k, pos)
    cache["v"] = _cache_write(cache["v"], v, pos)
    o = _decode_attention(q, cache["k"], cache["v"], pos=pos)
    return attn.o_proj(o.reshape([b, t, h]))


def _layer_step(layer: "LlamaDecoderLayer", x, cache, pos: Optional[int]):
    h = layer.input_layernorm(x)
    if pos is None:
        a = _attn_prefill(layer.self_attn, h, cache)
    else:
        a = _attn_decode(layer.self_attn, h, cache, pos)
    x = x + a
    return x + layer.mlp(layer.post_attention_layernorm(x))


def _llama_cached_forward(self, input_ids, caches, pos: Optional[int]):
    if not isinstance(self.layers, nn.LayerList):
        raise NotImplementedError(
            "KV-cache decoding requires the non-pipelined decoder "
            "(pp_degree=1); pipelined serving uses generate_padded"
        )
    x = self.embed_tokens(input_ids)
    for blk, cache in zip(self.layers, caches):
        x = _layer_step(blk, x, cache, pos)
    return self.norm(x)


def _llama_init_cache(self, batch_size: int, max_length: int):
    from ..generation import alloc_kv_caches

    c = self.config
    return alloc_kv_caches(
        c.num_hidden_layers, batch_size, max_length, c.num_key_value_heads,
        c.hidden_size // c.num_attention_heads,
    )


def _llama_generate(self, input_ids, max_new_tokens: int = 32,
                    do_sample: bool = False, top_k: int = 0, top_p: float = 1.0,
                    temperature: float = 1.0, eos_token_id=None,
                    pad_token_id=None, seed=None):
    """KV-cached generation: one prefill over the prompt, then one-token
    decode steps against the preallocated caches (see
    text.generation.run_cached_generation for the shared loop)."""
    from ..generation import run_cached_generation

    return run_cached_generation(
        self,
        lambda ids, caches, pos: _llama_cached_forward(self.llama, ids, caches, pos),
        lambda b, n: _llama_init_cache(self.llama, b, n),
        self._logits,
        input_ids, max_new_tokens=max_new_tokens, do_sample=do_sample,
        top_k=top_k, top_p=top_p, temperature=temperature,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, seed=seed,
    )


LlamaForCausalLM.generate = _llama_generate


# ---------------------------------------------------------------------------
# What the serving engine needs of a model (inference/engine.py; the GPT
# twin in gpt.py has the contract). Rope is applied inside ``layer`` at the
# engine's explicit positions, [T] or per-slot [B, T], so prefill buckets
# and per-slot decode share one code path.
# ---------------------------------------------------------------------------


class _LlamaDecodeAdapter:
    def __init__(self, lm: "LlamaForCausalLM"):
        if not isinstance(lm.llama.layers, nn.LayerList):
            raise NotImplementedError(
                "the decode engine requires the non-pipelined, unfolded "
                "decoder (pp_degree=1, fold_layers=False)"
            )
        cfg = lm.config
        self.lm = lm
        self.blocks = list(lm.llama.layers)
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.max_positions = cfg.max_position_embeddings

    def embed(self, input_ids, positions):
        return self.lm.llama.embed_tokens(input_ids)

    def layer(self, l, x, positions, attend):
        blk = self.blocks[l]
        attn = blk.self_attn
        b, t = x.shape[0], x.shape[1]
        with scope("qkv"):
            h = blk.input_layernorm(x)
            q = attn.q_proj(h).reshape([b, t, attn.num_heads, attn.head_dim])
            k = attn.k_proj(h).reshape(
                [b, t, attn.num_kv_heads, attn.head_dim])
            v = attn.v_proj(h).reshape(
                [b, t, attn.num_kv_heads, attn.head_dim])
            q = _apply_rope_positions(q, attn.rope_cos, attn.rope_sin,
                                      positions)
            k = _apply_rope_positions(k, attn.rope_cos, attn.rope_sin,
                                      positions)
        o = attend(q, k, v)
        with scope("attn_out"):
            x = x + attn.o_proj(
                o.reshape([b, t, attn.num_heads * attn.head_dim]))
        with scope("mlp"):
            return x + blk.mlp(blk.post_attention_layernorm(x))

    def head(self, x):
        return self.lm._logits(self.lm.llama.norm(x))


def _llama_decode_adapter(self):
    return _LlamaDecodeAdapter(self)


LlamaForCausalLM.decode_adapter = _llama_decode_adapter
