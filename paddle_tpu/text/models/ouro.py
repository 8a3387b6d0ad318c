"""Ouro — a looped decoder-only LM (Zhu et al. 2025, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741; ByteDance/Ouro-2.6B,
``model_type: ouro``): ONE stack of ``num_hidden_layers`` layers run
``total_ut_steps`` times over the same weights, an exit gate after each run.

    x = E[ids]                                   no position table; RoPE on q, k
    for u in 0 .. T-1:                           the SAME L layers every time
      for l in 0 .. L-1:
        x = x + RMSNorm(attn_l(RMSNorm(x; g1_l)); g2_l)      sandwich norm:
        x = x + RMSNorm(swiglu_l(RMSNorm(x; g3_l)); g4_l)    four norms a layer
      x = RMSNorm(x; gf)                         closes EVERY loop
      lambda_u = sigmoid(x w_gate + b_gate)      the exit gate
    logits = x W_head                            after the last loop, untied

Attention in loop ``u`` of layer ``l`` is over the keys that loop ``u`` of
layer ``l`` wrote and no other loop's: a cache holds one entry a (loop,
layer), ``u * L + l``. A token leaves at the first loop whose cumulated exit
mass ``p_u = lambda_u * prod_{j<u} (1 - lambda_j)`` reaches
``early_exit_threshold``. The published threshold is 1: every token runs all
loops and the gate changes no logit, so neither ``forward`` nor the serving
engine's programs compute it (``exit_pdf`` does). A threshold below 1 means
tokens of one batch leaving at different loops, which nothing here
schedules: it is refused at construction, not served wrongly.

Rope helpers, RMSNorm and the SwiGLU MLP are llama.py's and ``nn``'s.
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import nn
from ...distributed.fleet.layers.mpu import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ...framework.core import Tensor
from ...framework.op import raw
from ...nn import functional as F
from ...nn import initializer as I
from ...profiler import scope
from .llama import (LlamaMLP, _apply_rope, _apply_rope_positions,
                    _rope_cache)


class OuroConfig:
    def __init__(
        self,
        vocab_size: int = 49152,
        hidden_size: int = 2048,
        intermediate_size: int = 5632,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 16,
        num_key_value_heads: int = 16,
        head_dim: int = 128,
        max_position_embeddings: int = 65536,
        rms_norm_eps: float = 1e-6,
        rope_theta: float = 1000000.0,
        total_ut_steps: int = 4,
        early_exit_threshold: float = 1.0,
        initializer_range: float = 0.02,
        tie_word_embeddings: bool = False,
    ):
        if num_attention_heads % num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if total_ut_steps < 1:
            raise ValueError(f"total_ut_steps must be >= 1, got "
                             f"{total_ut_steps}")
        if tie_word_embeddings:
            raise NotImplementedError("Ouro's output head is untied")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = early_exit_threshold
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings


class OuroAttention(nn.Layer):
    """q, k, v and the output projection, no biases; rope comes from the
    model's one table (a table a layer would be 48 of them)."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        q_h, kv_h = (n * self.head_dim
                     for n in (self.num_heads, self.num_kv_heads))
        self.q_proj = ColumnParallelLinear(h, q_h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv_h, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv_h, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(q_h, h, has_bias=False, input_is_parallel=True)

    def qkv(self, x, rope):
        b, t = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, t, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        return rope(q), rope(k), v


class OuroDecoderLayer(nn.Layer):
    """The sandwich-norm block: a second RMSNorm closes each sublayer
    before its residual."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        norm = lambda: nn.RMSNorm(config.hidden_size,
                                  epsilon=config.rms_norm_eps)
        self.input_layernorm = norm()
        self.self_attn = OuroAttention(config)
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm = norm()
        self.mlp = LlamaMLP(config)
        self.post_attention_layernorm_2 = norm()

    def forward(self, x, rope, attend):
        """``rope(t)`` rotates q or k at the caller's positions;
        ``attend(q, k, v)`` stands where causal attention does: full in the
        model's own forward, the engine's paged read when served."""
        attn = self.self_attn
        with scope("qkv"):
            q, k, v = attn.qkv(self.input_layernorm(x), rope)
        o = attend(q, k, v)
        with scope("attn_out"):
            b, t = o.shape[0], o.shape[1]
            x = x + self.input_layernorm_2(attn.o_proj(
                o.reshape([b, t, attn.num_heads * attn.head_dim])))
        with scope("mlp"):
            return x + self.post_attention_layernorm_2(
                self.mlp(self.post_attention_layernorm(x)))


def _causal_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    if group > 1:
        from ... import tensor as pt

        k, v = (pt.repeat_interleave(a, group, axis=2) for a in (k, v))
    return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                          training=False)


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(
                initializer=I.Normal(std=config.initializer_range)))
        self.layers = nn.LayerList(
            [OuroDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1)
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.head_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(jnp.asarray(cos)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(jnp.asarray(sin)),
                             persistable=False)

    def forward(self, input_ids):
        """The hidden state that closes each loop: ``T`` tensors [B, S, h]."""
        rope = lambda t: _apply_rope(t, self.rope_cos, self.rope_sin)
        x = self.embed_tokens(input_ids)
        closed = []
        for _ in range(self.config.total_ut_steps):
            for blk in self.layers:
                x = blk(x, rope, _causal_attention)
            x = self.norm(x)
            closed.append(x)
        return closed


class OuroForCausalLM(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        if config.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={config.early_exit_threshold}: below "
                "1 a token leaves the loop at the first step whose cumulated "
                "exit mass reaches it, so the tokens of one batch run "
                "different numbers of loops; nothing here schedules that "
                "(ROADMAP Reach). Only the published threshold 1, every "
                "token through all total_ut_steps loops, is run.")
        self.config = config
        self.model = OuroModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=False)

    def forward(self, input_ids):
        """Logits of the last loop [B, S, V]."""
        return self.lm_head(self.model(input_ids)[-1])

    def exit_pdf(self, input_ids):
        """The exit distribution [T, B, S]: ``p_u = lambda_u * prod_{j<u}
        (1 - lambda_j)``, the last loop taking the remaining mass."""
        lam = [raw(F.sigmoid(self.model.early_exit_gate(x)))[..., 0]
               for x in self.model(input_ids)]
        stay, pdf = jnp.ones_like(lam[0]), []
        for l in lam[:-1]:
            pdf.append(l * stay)
            stay = stay * (1.0 - l)
        return Tensor(jnp.stack(pdf + [stay]))

    def decode_adapter(self):
        return _OuroDecodeAdapter(self)


# ---------------------------------------------------------------------------
# What the serving engine needs of a model (inference/engine.py; gpt.py has
# the contract). A looped model states its two depths apart: ``num_layers``
# layers of weights, run ``loops`` times, so the engine's pool is ``loops *
# num_layers`` entries deep; ``close_loop`` is what ends each run of the
# stack. The gate is not computed: at threshold 1 it cannot change a logit.
# ---------------------------------------------------------------------------


class _OuroDecodeAdapter:
    def __init__(self, lm: OuroForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.blocks = list(lm.model.layers)
        self.num_layers = cfg.num_hidden_layers
        self.loops = cfg.total_ut_steps
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings

    def embed(self, input_ids, positions):
        return self.lm.model.embed_tokens(input_ids)

    def layer(self, l, x, positions, attend):
        m = self.lm.model
        rope = lambda t: _apply_rope_positions(t, m.rope_cos, m.rope_sin,
                                               positions)
        return self.blocks[l](x, rope, attend)

    def close_loop(self, x):
        return self.lm.model.norm(x)

    def head(self, x):
        return self.lm.lm_head(x)
