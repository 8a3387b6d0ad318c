"""GPT family — decoder-only LM, hybrid-parallel-ready (the flagship model).

Reference capability (SURVEY.md §6 workloads "GPT-3 1.3B (dp+mp)",
"GPT-3 6.7B (pp+sharding)"): the Paddle ecosystem's GPT lives in
PaddleNLP/fleetx (`GPTModel`, `GPTForPretraining`, `GPTPretrainingCriterion`)
built from fleet mpu layers (`VocabParallelEmbedding`,
`ColumnParallelLinear`/`RowParallelLinear`) with 1F1B pipeline and
sequence-parallel options.

TPU-native design: the same layer classes (they ARE sharding annotations
here), flash attention on the MXU-friendly [B, T, H, D] layout, bf16-first,
and the transformer body built as a list of identical blocks so
`SpmdPipeline` can stack them (layer-dim scan → one compiled block, or pp
circular schedule over the mesh). The causal mask is folded into attention
(no materialized [T,T] mask tensor in HBM).
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from ... import nn
from ...nn import functional as F
from ...nn import initializer as I
from ...framework.core import Tensor
from ...distributed import mesh as _mesh
from ...distributed.fleet.layers.mpu import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    mark_activation,
    mp_wire_linear,
)
from ...distributed.fleet.utils import recompute as _recompute
from ...profiler import scope


class GPTConfig:
    """Static model hyperparameters (mirrors PaddleNLP GPTConfig fields)."""

    def __init__(
        self,
        vocab_size: int = 50304,
        hidden_size: int = 768,
        num_hidden_layers: int = 12,
        num_attention_heads: int = 12,
        intermediate_size: Optional[int] = None,
        hidden_act: str = "gelu",
        max_position_embeddings: int = 1024,
        hidden_dropout_prob: float = 0.1,
        attention_probs_dropout_prob: float = 0.1,
        initializer_range: float = 0.02,
        use_recompute: bool = False,
        use_flash_attention: bool = True,
        sequence_parallel: bool = False,
        tie_word_embeddings: bool = True,
        layer_norm_epsilon: float = 1e-5,
        fold_layers: bool = False,
        recompute_granularity: str = "full",
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_act = hidden_act
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        # recompute_granularity (reference GPT knob, same default): "full"
        # saves only block inputs — the OOM-safe choice, and REQUIRED for
        # folded/stacked layers where saved intermediates stack across the
        # lax.scan layer dim (see fleet/utils/recompute_helper.py);
        # "full_attn"/"core_attn" keep matmul outputs (dots_saveable) —
        # on an UNFOLDED stack with HBM headroom they trade memory for a
        # faster backward (no matmul re-execution) and are the better pick.
        self.recompute_granularity = recompute_granularity
        self.use_flash_attention = use_flash_attention
        self.sequence_parallel = sequence_parallel
        self.tie_word_embeddings = tie_word_embeddings
        self.layer_norm_epsilon = layer_norm_epsilon
        # fold_layers: build the decoder as ONE lax.scan over layer-stacked
        # parameters even without pipeline parallelism. XLA then compiles a
        # single block body instead of num_hidden_layers unrolled copies —
        # compile time drops from O(layers) to O(1) (the jax large-model
        # idiom; same mechanism SpmdPipeline uses per stage). Checkpoint
        # keys become the stacked `decoder.*__stacked` form.
        self.fold_layers = fold_layers

    # canonical sizes (PaddleNLP gpt configs / GPT-3 table)
    @staticmethod
    def gpt2_small(**kw):
        return GPTConfig(hidden_size=768, num_hidden_layers=12, num_attention_heads=12, **kw)

    @staticmethod
    def gpt3_1p3b(**kw):
        kw.setdefault("num_hidden_layers", 24)
        kw.setdefault("max_position_embeddings", 2048)
        return GPTConfig(hidden_size=2048, num_attention_heads=16, **kw)

    @staticmethod
    def gpt3_6p7b(**kw):
        kw.setdefault("num_hidden_layers", 32)
        return GPTConfig(hidden_size=4096, num_attention_heads=32,
                         max_position_embeddings=2048, **kw)


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(std=config.initializer_range)),
        )
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(std=config.initializer_range)),
        )
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        from ... import tensor as pt

        if position_ids is None:
            seq = input_ids.shape[1]
            position_ids = pt.arange(0, seq, dtype="int64")
        emb = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return self.dropout(emb)


class GPTAttention(nn.Layer):
    """Causal self-attention: fused mp-sharded QKV projection + flash kernel."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = h // self.num_heads
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, gather_output=False)
        self.out_proj = RowParallelLinear(h, h, input_is_parallel=True)
        self.dropout_p = config.attention_probs_dropout_prob
        self.use_flash = config.use_flash_attention

    def forward(self, x):
        b, t, h = x.shape
        qkv = self.qkv_proj(x)  # [b, t, 3h] (hidden mp-sharded)
        # head-major fused layout [H, 3, d]: an mp shard of the flat 3h dim
        # is a whole group of heads, so the reshape keeps the activation
        # sharded instead of forcing a GSPMD re-replication all-gather
        qkv = qkv.reshape([b, t, self.num_heads, 3, self.head_dim])
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]  # [b, t, H, d]
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=self.dropout_p, is_causal=True, training=self.training
        )
        out = out.reshape([b, t, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(config.hidden_size, config.intermediate_size, gather_output=False)
        self.fc_out = RowParallelLinear(config.intermediate_size, config.hidden_size, input_is_parallel=True)
        self.act = F.gelu if config.hidden_act == "gelu" else getattr(F, config.hidden_act)

    def forward(self, x):
        return self.fc_out(self.act(self.fc_in(x)))


class GPTDecoderLayer(nn.Layer):
    """Pre-LN block. Structurally uniform across depth → SpmdPipeline-stackable."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self._use_recompute = config.use_recompute
        self._recompute_granularity = getattr(
            config, "recompute_granularity", "full")
        self._sequence_parallel = config.sequence_parallel

    def _block(self, x):
        x = x + self.dropout(self.attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        if self._sequence_parallel:
            x = mark_activation(x, seq_mp=True)
        return x

    def forward(self, x):
        if self._use_recompute:
            return _recompute(self._block, x,
                              granularity=self._recompute_granularity)
        return self._block(x)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        blocks = [GPTDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        pp = _mesh.mesh_axis_size("pp")
        if pp > 1 and config.num_hidden_layers % pp == 0:
            from ...distributed.fleet.meta_parallel.pipeline_parallel import SpmdPipeline

            self.decoder = SpmdPipeline(
                blocks, num_stages=pp, recompute_block=config.use_recompute,
                recompute_granularity=getattr(
                    config, "recompute_granularity", "full"),
                # per-model overrides; None defers to DistributedStrategy
                # pipeline_configs / PADDLE_TPU_PP_SCHEDULE
                num_virtual_stages=getattr(config, "virtual_pp_degree", None),
                schedule=getattr(config, "pp_schedule", None),
            )
        else:
            from ...distributed.fleet.meta_parallel.pipeline_parallel import (
                fold_or_list,
            )

            self.decoder = fold_or_list(
                blocks, getattr(config, "fold_layers", False),
                recompute=config.use_recompute,
                recompute_granularity=getattr(
                    config, "recompute_granularity", "full"))
        self.final_layernorm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        from ...distributed.fleet.meta_parallel.pipeline_parallel import (
            run_stack,
        )

        x = self.embeddings(input_ids, position_ids)
        x = run_stack(self.decoder, x)
        return self.final_layernorm(x)


class GPTPretrainingCriterion(nn.Layer):
    """Masked LM loss over mp-sharded logits (reference:
    GPTPretrainingCriterion with c_softmax_with_cross_entropy)."""

    def __init__(self, config: Optional[GPTConfig] = None):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-100)

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)
        if loss_mask is not None:
            lm = loss_mask.reshape(loss.shape)
            return (loss * lm).sum() / lm.sum().clip(min=1.0)
        return loss.mean()


class GPTForCausalLM(nn.Layer):
    """GPT with a (tied) LM head — PaddleNLP GPTForCausalLM/GPTForPretraining."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False, gather_output=False
            )
        self.criterion = GPTPretrainingCriterion(config)

    def _logits(self, hidden):
        if self.config.tie_word_embeddings:
            emb = self.gpt.embeddings.word_embeddings
            w = emb.weight  # [V, h], mp-sharded on V
            # column-form tied head: rides the quantized backward wire
            # when the mp_comm activation wire is on (exact F.linear off)
            logits = mp_wire_linear(hidden, w.t(), emb.world_size)
            return mark_activation(logits, last_mp=True)
        return self.lm_head(hidden)

    def forward(self, input_ids, position_ids=None, labels=None, loss_mask=None):
        hidden = self.gpt(input_ids, position_ids)
        logits = self._logits(hidden)
        if labels is not None:
            return self.criterion(logits, labels, loss_mask)
        return logits


GPTLMHeadModel = GPTForCausalLM
GPTForPretraining = GPTForCausalLM


# ---------------------------------------------------------------------------
# KV-cache incremental decoding (mirrors llama.py; shares the cache-write and
# cache-attention defops and the text.generation loop). llama never imports
# gpt, so this import is cycle-free.
# ---------------------------------------------------------------------------
from .llama import _cache_write, _decode_attention  # noqa: E402

def _gpt_qkv(attn: "GPTAttention", x):
    """The SAME projection+split GPTAttention.forward performs (one place)."""
    b, t, _ = x.shape
    qkv = attn.qkv_proj(x).reshape([b, t, attn.num_heads, 3, attn.head_dim])
    return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]


def _gpt_attn_cached(attn: "GPTAttention", x, cache, pos):
    """Prefill (pos None) or one-step decode (pos int) against the cache."""
    b, t, h = x.shape
    q, k, v = _gpt_qkv(attn, x)
    cache["k"] = _cache_write(cache["k"], k, 0 if pos is None else pos)
    cache["v"] = _cache_write(cache["v"], v, 0 if pos is None else pos)
    if pos is None:
        o = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=False
        )
    else:
        o = _decode_attention(q, cache["k"], cache["v"], pos=pos)
    return attn.out_proj(o.reshape([b, t, h]))


def _gpt_cached_forward(model: "GPTModel", input_ids, caches, pos):
    from ... import tensor as pt

    if not isinstance(model.decoder, nn.LayerList):
        raise NotImplementedError(
            "KV-cache decoding requires the non-pipelined decoder "
            "(pp_degree=1); pipelined serving uses generate_padded"
        )
    if pos is None:
        x = model.embeddings(input_ids)
    else:
        position_ids = pt.arange(pos, pos + 1, dtype="int64")
        x = model.embeddings(input_ids, position_ids)
    for blk, cache in zip(model.decoder, caches):
        x = x + _gpt_attn_cached(blk.attn, blk.ln_1(x), cache, pos)
        x = x + blk.mlp(blk.ln_2(x))
    return model.final_layernorm(x)


def _gpt_init_cache(model: "GPTModel", batch_size: int, max_length: int):
    from ..generation import alloc_kv_caches

    c = model.config
    return alloc_kv_caches(
        c.num_hidden_layers, batch_size, max_length, c.num_attention_heads,
        c.hidden_size // c.num_attention_heads,
    )


def _gpt_generate(self, input_ids, max_new_tokens: int = 32,
                  do_sample: bool = False, top_k: int = 0, top_p: float = 1.0,
                  temperature: float = 1.0, eos_token_id=None,
                  pad_token_id=None, seed=None):
    from ..generation import run_cached_generation

    return run_cached_generation(
        self,
        lambda ids, caches, pos: _gpt_cached_forward(self.gpt, ids, caches, pos),
        lambda b, n: _gpt_init_cache(self.gpt, b, n),
        self._logits,
        input_ids, max_new_tokens=max_new_tokens, do_sample=do_sample,
        top_k=top_k, top_p=top_p, temperature=temperature,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, seed=seed,
    )


GPTForCausalLM.generate = _gpt_generate


# ---------------------------------------------------------------------------
# What the serving engine needs of a model (inference/engine.py,
# docs/SERVING.md "Serving a new model"): ``embed``, the model's own block as
# ``layer`` with the engine's paged ``attend(q, k, v)`` where full attention
# stood, ``head``, and the geometry that sizes the KV pool. The engine owns
# slots, pages and sampling; the block equation is written here.
# ---------------------------------------------------------------------------


class _GPTDecodeAdapter:
    def __init__(self, lm: "GPTForCausalLM"):
        if not isinstance(lm.gpt.decoder, nn.LayerList):
            raise NotImplementedError(
                "the decode engine requires the non-pipelined, unfolded "
                "decoder (pp_degree=1, fold_layers=False)"
            )
        cfg = lm.config
        self.lm = lm
        self.blocks = list(lm.gpt.decoder)
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.max_positions = cfg.max_position_embeddings

    def embed(self, input_ids, positions):
        """input_ids Tensor [B, T]; positions int array [T] or [B, T] with
        a DIFFERENT offset per row (the engine's speculative verify step):
        the learned position table gathers per element."""
        import jax.numpy as jnp

        return self.lm.gpt.embeddings(
            input_ids, Tensor(jnp.asarray(positions)))

    def layer(self, l, x, positions, attend):
        blk = self.blocks[l]
        attn = blk.attn
        with scope("qkv"):
            q, k, v = _gpt_qkv(attn, blk.ln_1(x))
        o = attend(q, k, v)
        with scope("attn_out"):
            b, t = o.shape[0], o.shape[1]
            x = x + attn.out_proj(
                o.reshape([b, t, attn.num_heads * attn.head_dim]))
        with scope("mlp"):
            return x + blk.mlp(blk.ln_2(x))

    def head(self, x):
        return self.lm._logits(self.lm.gpt.final_layernorm(x))


def _gpt_decode_adapter(self):
    return _GPTDecodeAdapter(self)


GPTForCausalLM.decode_adapter = _gpt_decode_adapter
