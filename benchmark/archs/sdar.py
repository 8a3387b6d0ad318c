"""Architecture ``sdar``: the block-diffusion decoder of Cheng et al. 2025
(JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``), Qwen3-MoE's block under a
block-causal mask, as the program builds it and as the yardstick counts it
(``archs/gpt.py`` says what an architecture file gives). Its layers hold
``num_experts`` routed SwiGLU experts, of which ``num_experts_per_tok`` run
for a token: so the counts below hold the parameters a token multiplies
(``active_matmul_params``) apart from those a pass must stream
(``pass_weight_bytes``, from the experts the routing touched).
"""
import weights

CAUSAL = True

_LEAF = {"ln1.g": "input_layernorm.weight",
         "q.w": "self_attn.q_proj.weight", "k.w": "self_attn.k_proj.weight",
         "v.w": "self_attn.v_proj.weight", "o.w": "self_attn.o_proj.weight",
         "qn.g": "self_attn.q_norm.weight", "kn.g": "self_attn.k_norm.weight",
         "ln2.g": "post_attention_layernorm.weight",
         "router.w": "mlp.router", "gate_up.w": "mlp.w_gate_up",
         "down.w": "mlp.w_down"}
_TOP = {"wte": "model.embed_tokens.weight", "lnf.g": "model.norm.weight",
        "head.w": "lm_head.weight"}


def _itemsize(cfg):
    return {"bfloat16": 2, "float32": 4}[cfg["dtype"]]


def _attn_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_attention_heads"] * d + 2 * h * (
        cfg["num_key_value_heads"] * d)


def _expert_params(cfg):
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def weight_spec(cfg: dict, stacked: bool) -> dict:
    """Token table, untied head, final norm, and per layer two RMSNorm gains,
    q / k / v / o, the per-head q and k norm gains, the router [h, E] and the
    experts stacked ``[E, ...]``: gate and up side by side ``[E, h, 2F]``,
    down ``[E, F, h]``. Matrices normal(0, 0.02), gains 1 + normal(0, 0.02)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    v, std = cfg["vocab_size"], cfg.get("initializer_range", 0.02)
    spec = {"wte": ((v, h), 0.0, std), "head.w": ((h, v), 0.0, std),
            "lnf.g": ((h,), 1.0, std)}
    layer = {"ln1.g": ((h,), 1.0, std), "ln2.g": ((h,), 1.0, std),
             "qn.g": ((d,), 1.0, std), "kn.g": ((d,), 1.0, std),
             "q.w": ((h, cfg["num_attention_heads"] * d), 0.0, std),
             "k.w": ((h, cfg["num_key_value_heads"] * d), 0.0, std),
             "v.w": ((h, cfg["num_key_value_heads"] * d), 0.0, std),
             "o.w": ((cfg["num_attention_heads"] * d, h), 0.0, std),
             "router.w": ((h, e), 0.0, std),
             "gate_up.w": ((e, h, 2 * f), 0.0, std),
             "down.w": ((e, f, h), 0.0, std)}
    return weights.with_layers(spec, layer, cfg["num_hidden_layers"], stacked)


def matmul_params(cfg: dict) -> int:
    """Parameters held in a matrix multiplication: every layer's attention,
    router and ALL its experts, and the untied head."""
    return (cfg["num_hidden_layers"] * (
        _attn_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts"] * _expert_params(cfg))
        + cfg["vocab_size"] * cfg["hidden_size"])


def active_matmul_params(cfg: dict) -> int:
    """Parameters a token multiplies: every layer's attention, its router
    and ``num_experts_per_tok`` experts, and the head (a block pass reads
    logits at every row)."""
    return (cfg["num_hidden_layers"] * (
        _attn_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * _expert_params(cfg))
        + cfg["vocab_size"] * cfg["hidden_size"])


def expert_bytes(cfg: dict, touched: int) -> int:
    """Weight bytes of ``touched`` experts (summed over layers) at the
    configuration's ``dtype``: what the grouped products must stream."""
    return touched * _expert_params(cfg) * _itemsize(cfg)


def pass_weight_bytes(cfg: dict, touched: int) -> int:
    """Weight bytes one block pass must stream from HBM: every layer's
    attention, router and norms, the experts the routing touched (summed
    over layers), the final norm and the head. The token table's rows are a
    gather of a few KB and are left out."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    per_layer = _attn_params(cfg) + h * cfg["num_experts"] + 2 * h + 2 * d
    return (cfg["num_hidden_layers"] * per_layer * _itemsize(cfg)
            + expert_bytes(cfg, touched)
            + (cfg["vocab_size"] * h + h) * _itemsize(cfg))


def reference_args(cfg: dict) -> dict:
    return {"block": cfg["block_length"], "mask_id": cfg["mask_token_id"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"]}


def serve_program(cfg: dict):
    """The model that ``DecodeEngine`` serves through ``decode_adapter()``.
    Its own initial weights are replaced by the seed's
    (``serve.load_weights``), and the chip could not hold both at once: so
    it is built on the host with zeros for weights (``initializer_range``
    0, no draw of 4.4B numbers), and only the rope tables, which are not
    weights, go to the device here."""
    import jax

    from paddle_tpu.text.models import SDARConfig, SDARForCausalLM

    with jax.default_device(jax.devices("cpu")[0]):
        model = SDARForCausalLM(SDARConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg["norm_topk_prob"],
            decoder_sparse_step=cfg["decoder_sparse_step"],
            mlp_only_layers=cfg["mlp_only_layers"],
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            attention_bias=cfg["attention_bias"],
            tie_word_embeddings=cfg["tie_word_embeddings"],
            initializer_range=0.0,
            block_length=cfg["block_length"],
            denoise_steps=cfg["denoise_steps"],
            mask_token_id=cfg["mask_token_id"],
            remasking=cfg["remasking"],
            confidence_threshold=cfg["confidence_threshold"],
            expert_dtype=cfg["dtype"]))
    device = jax.devices()[0]
    for b in model.buffers():
        b._rebind(jax.device_put(b._value, device))
    names = dict(_TOP)
    for i in range(cfg["num_hidden_layers"]):
        names.update({f"h{i}.{k}": f"model.layers.{i}.{v}"
                      for k, v in _LEAF.items()})
    return model, names
