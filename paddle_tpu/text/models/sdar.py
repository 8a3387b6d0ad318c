"""SDAR — a decoder that writes by diffusion over blocks (Cheng et al. 2025,
"SDAR: A Synergistic Diffusion-AutoRegression Paradigm", arXiv:2510.06303;
JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``): Qwen3-MoE's block, under
a block-causal mask, generating ``block_length`` positions at a time by
unmasking them over ``denoise_steps`` passes.

    x = E[ids]                                no position table; RoPE on q, k
    for l in 0 .. L-1:
      a = RMSNorm(x)
      q, k, v = a Wq, a Wk, a Wv              H heads, Hkv kv heads of d; no bias
      q, k = RMSNorm_d(q), RMSNorm_d(k)       each head on its own (QK norm)
      o = softmax(rope(q) rope(k)^T / sqrt(d) + blockmask) v
      x = x + o Wo
      b = RMSNorm(x)
      x = x + routed_experts(b)               top-8 of 128 SwiGLU experts,
                                              weights renormalised over the 8
    logits = RMSNorm(x) W_head                untied

Block mask: positions are counted in blocks of ``block_length`` from 0; a
query sees every key of its own block and of the blocks before it. A
position being generated enters as the mask token's embedding; the logits
AT a position predict that position's token (no shift).

Generation (``inference/engine.py``'s block pass): the prompt's whole blocks
are prefilled; the block holding the prompt's tail starts with the tail known
and the rest masked; each pass draws a token at every masked position and
unmasks ``k_s`` of them (``k_s`` splits the block's masked count evenly over
``denoise_steps``, the remainder to the earliest passes), picked by
``remasking``: ``sequential`` the leftmost, ``low_confidence_static`` the most
probable drawn tokens, ``low_confidence_dynamic`` every one whose drawn token
has probability above ``confidence_threshold`` when there are at least
``k_s``, else as static. A block with no mask left is committed: one pass
over its final tokens writes its keys and values.

The expert layer is one expert-parallel rank's (``incubate/moe.py``
``routed_experts``): it routes over all ``num_experts`` and computes the part
that the experts of ``expert_range`` give; held whole, that is the layer.
Rope helpers are llama.py's, RMSNorm is ``nn``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...distributed.fleet.layers.mpu import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ...framework.core import Tensor
from ...framework.op import defop
from ...incubate.moe import routed_experts
from ...nn import initializer as I
from ...profiler import scope
from .llama import _apply_rope_positions, _rope_cache

#: the unmasking rules a block pass knows
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")


class SDARConfig:
    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2048,
        intermediate_size: int = 6144,
        moe_intermediate_size: int = 768,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 32,
        num_key_value_heads: int = 4,
        head_dim: int = 128,
        num_experts: int = 128,
        num_experts_per_tok: int = 8,
        norm_topk_prob: bool = True,
        decoder_sparse_step: int = 1,
        mlp_only_layers=(),
        max_position_embeddings: int = 32768,
        rms_norm_eps: float = 1e-6,
        rope_theta: float = 1000000.0,
        attention_bias: bool = False,
        tie_word_embeddings: bool = False,
        initializer_range: float = 0.02,
        block_length: int = 4,
        denoise_steps: int = 4,
        mask_token_id: int = 151669,
        remasking: str = "low_confidence_dynamic",
        confidence_threshold: float = 0.9,
        expert_range=None,
        expert_dtype: str = "float32",
    ):
        if num_attention_heads % num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if decoder_sparse_step != 1 or list(mlp_only_layers):
            raise NotImplementedError(
                "every layer routed (decoder_sparse_step 1, no "
                "mlp_only_layers) is the only layout built")
        if not norm_topk_prob:
            raise NotImplementedError("norm_topk_prob false is not built")
        if attention_bias or tie_word_embeddings:
            raise NotImplementedError(
                "SDAR's projections have no bias and its head is untied")
        if remasking not in REMASKING:
            raise ValueError(f"remasking must be one of {REMASKING}, got "
                             f"{remasking!r}")
        if block_length < 1 or denoise_steps < 1:
            raise ValueError("block_length and denoise_steps must be >= 1")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.block_length = block_length
        self.denoise_steps = denoise_steps
        self.mask_token_id = mask_token_id
        self.remasking = remasking
        self.confidence_threshold = confidence_threshold
        lo, hi = expert_range or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"expert_range {(lo, hi)} outside "
                             f"0..{num_experts}")
        self.expert_range = (lo, hi)
        #: std of every matrix's normal initial values; 0 makes them 0, for
        #: a model whose weights are loaded next (no draw of 4B numbers)
        self.initializer_range = initializer_range
        #: the experts' parameters are made in this dtype (they are most of
        #: the model: made in float32 and cast after, they would need twice
        #: the memory that the served model holds)
        self.expert_dtype = expert_dtype


def _matrix_attr(config: SDARConfig):
    """Every matrix's initial values: normal(0, initializer_range), or 0."""
    std = config.initializer_range
    return nn.ParamAttr(initializer=I.Normal(std=std) if std
                        else I.Constant(0.0))


class SDARAttention(nn.Layer):
    """q, k, v and the output projection, no biases, and the per-head
    RMSNorm of q and k."""

    def __init__(self, config: SDARConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        q_h, kv_h = (n * self.head_dim
                     for n in (self.num_heads, self.num_kv_heads))
        w = lambda: _matrix_attr(config)
        self.q_proj = ColumnParallelLinear(h, q_h, w(), has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv_h, w(), has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv_h, w(), has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(q_h, h, w(), has_bias=False, input_is_parallel=True)
        self.q_norm = nn.RMSNorm(self.head_dim, epsilon=config.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, epsilon=config.rms_norm_eps)

    def qkv(self, x, rope):
        b, t = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, t, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, t, self.num_kv_heads, self.head_dim])
        return rope(self.q_norm(q)), rope(self.k_norm(k)), v


class SDARSparseMoE(nn.Layer):
    """The router over all ``num_experts`` and the experts of
    ``expert_range``, each expert's gate and up projections side by side
    (``w_gate_up [E_held, H, 2F]``) and its down projection (``w_down
    [E_held, F, H]``)."""

    def __init__(self, config: SDARConfig):
        super().__init__()
        h, f = config.hidden_size, config.moe_intermediate_size
        lo, hi = config.expert_range
        self.top_k = config.num_experts_per_tok
        self.expert_range = (lo, hi)
        self.router = self.create_parameter(
            [h, config.num_experts], attr=_matrix_attr(config))
        self.w_gate_up = self.create_parameter(
            [hi - lo, h, 2 * f], attr=_matrix_attr(config),
            dtype=config.expert_dtype)
        self.w_down = self.create_parameter(
            [hi - lo, f, h], attr=_matrix_attr(config),
            dtype=config.expert_dtype)

    def forward(self, x):
        b, t, h = x.shape
        y = routed_experts(x.reshape([b * t, h]), self.router,
                           self.w_gate_up, self.w_down, self.top_k,
                           self.expert_range)
        return y.reshape([b, t, h])


class SDARDecoderLayer(nn.Layer):
    def __init__(self, config: SDARConfig):
        super().__init__()
        norm = lambda: nn.RMSNorm(config.hidden_size,
                                  epsilon=config.rms_norm_eps)
        self.input_layernorm = norm()
        self.self_attn = SDARAttention(config)
        self.post_attention_layernorm = norm()
        self.mlp = SDARSparseMoE(config)

    def forward(self, x, rope, attend):
        """``rope(t)`` rotates q or k at the caller's positions;
        ``attend(q, k, v)`` stands where block-causal attention does: full
        in the model's own forward, the engine's paged read when served."""
        attn = self.self_attn
        with scope("qkv"):
            q, k, v = attn.qkv(self.input_layernorm(x), rope)
        o = attend(q, k, v)
        with scope("attn_out"):
            b, t = o.shape[0], o.shape[1]
            x = x + attn.o_proj(
                o.reshape([b, t, attn.num_heads * attn.head_dim]))
        with scope("mlp"):
            return x + self.mlp(self.post_attention_layernorm(x))


@defop(name="block_causal_attention")
def block_causal_attention(q, k, v, block: int):
    """Full attention of [B, T, H, D] over [B, T, Hkv, D] under the block
    mask: key j is seen from query i iff ``j // block <= i // block``."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(q.shape[-1]))
    blk = jnp.arange(t) // block
    s = jnp.where(blk[None, :] <= blk[:, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32))
    return o.astype(q.dtype)


class SDARModel(nn.Layer):
    def __init__(self, config: SDARConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_matrix_attr(config))
        self.layers = nn.LayerList(
            [SDARDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.head_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(jnp.asarray(cos)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(jnp.asarray(sin)),
                             persistable=False)

    def forward(self, input_ids):
        """The final hidden state [B, T, h] of a clean (unmasked) stream
        under the block mask."""
        t = input_ids.shape[1]
        pos = jnp.arange(t, dtype=jnp.int32)
        rope = lambda a: _apply_rope_positions(a, self.rope_cos,
                                               self.rope_sin, pos)
        attend = lambda q, k, v: block_causal_attention(
            q, k, v, self.config.block_length)
        x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x, rope, attend)
        return self.norm(x)


class SDARForCausalLM(nn.Layer):
    def __init__(self, config: SDARConfig):
        super().__init__()
        self.config = config
        self.model = SDARModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, _matrix_attr(config),
            has_bias=False, gather_output=False)

    def forward(self, input_ids):
        """Logits [B, T, V] of a clean stream: position p's row is what the
        model gives for the token AT p once p's block is being denoised
        with every position of it known (SDAR predicts in place)."""
        return self.lm_head(self.model(input_ids))

    def decode_adapter(self):
        return _SDARDecodeAdapter(self)


# ---------------------------------------------------------------------------
# What the serving engine needs of a model (inference/engine.py; gpt.py has
# the contract). A block-diffusion model states ``block_length`` and the
# settings of its block pass; the engine then generates a block at a time.
# ---------------------------------------------------------------------------


class _SDARDecodeAdapter:
    def __init__(self, lm: SDARForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.blocks = list(lm.model.layers)
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        self.block_length = cfg.block_length
        self.denoise_steps = cfg.denoise_steps
        self.mask_token_id = cfg.mask_token_id
        self.remasking = cfg.remasking
        self.confidence_threshold = cfg.confidence_threshold
        lo, hi = cfg.expert_range
        #: experts held a layer, what the engine's expert counts are over
        self.num_experts = hi - lo

    def embed(self, input_ids, positions):
        return self.lm.model.embed_tokens(input_ids)

    def layer(self, l, x, positions, attend):
        m = self.lm.model
        rope = lambda t: _apply_rope_positions(t, m.rope_cos, m.rope_sin,
                                               positions)
        return self.blocks[l](x, rope, attend)

    def head(self, x):
        return self.lm.lm_head(self.lm.model.norm(x))
