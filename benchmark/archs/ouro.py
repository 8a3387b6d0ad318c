"""Architecture ``ouro``: the looped decoder of Zhu et al. 2025 (ByteDance/
Ouro-2.6B), as the program builds it and as the yardstick counts it
(``archs/gpt.py`` says what an architecture file gives). One stack of
``num_hidden_layers`` layers is run ``total_ut_steps`` times over the same
weights, and a (loop, layer) has a cache entry of its own: so the counts
below hold two depths apart, ``num_hidden_layers`` where weights are counted
and ``cache_layers`` where keys are.
"""
import weights

CAUSAL = True

_LEAF = {"ln1.g": "input_layernorm.weight",
         "q.w": "self_attn.q_proj.weight", "k.w": "self_attn.k_proj.weight",
         "v.w": "self_attn.v_proj.weight", "o.w": "self_attn.o_proj.weight",
         "ln2.g": "input_layernorm_2.weight",
         "ln3.g": "post_attention_layernorm.weight",
         "gate.w": "mlp.gate_proj.weight", "up.w": "mlp.up_proj.weight",
         "down.w": "mlp.down_proj.weight",
         "ln4.g": "post_attention_layernorm_2.weight"}
_TOP = {"wte": "model.embed_tokens.weight", "lnf.g": "model.norm.weight",
        "exit.w": "model.early_exit_gate.weight",
        "exit.b": "model.early_exit_gate.bias", "head.w": "lm_head.weight"}


def _widths(cfg):
    d = cfg["head_dim"]
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d)


def weight_spec(cfg: dict, stacked: bool) -> dict:
    """Token table, untied head, final norm, exit gate, and per layer four
    RMSNorm gains, q / k / v / o and the three SwiGLU matrices, no biases:
    matrices normal(0, 0.02); the gains that OPEN a sublayer and the final
    norm's 1 + normal(0, 0.02); the gains that CLOSE a sublayer (ln2, ln4)
    ``(1 + normal(0, 0.02)) / sqrt(2 L)``. That is GPT-2's scaling of the
    residual branches at initialisation, put where a sandwich norm leaves
    it: a closing RMSNorm cancels any scale of Wo and Wd, so the closing
    gain carries it. At closing gains of 1 each of a loop's 2 L sublayers
    adds a unit-RMS vector to the stream and the stack amplifies a rounding
    a hundredfold over four loops: the served bf16 logits then lie 0.17 on
    average below the float32 reference's best (read on the chip, PERF.md
    section 6, PR 35), where ``correct`` cannot tell bfloat16 from int8."""
    h, f, qh, kvh = _widths(cfg)
    v, std = cfg["vocab_size"], cfg.get("initializer_range", 0.02)
    spec = {"wte": ((v, h), 0.0, std), "head.w": ((h, v), 0.0, std),
            "lnf.g": ((h,), 1.0, std), "exit.w": ((h, 1), 0.0, std),
            "exit.b": ((1,), 0.0, std)}
    layer = {"q.w": ((h, qh), 0.0, std), "k.w": ((h, kvh), 0.0, std),
             "v.w": ((h, kvh), 0.0, std), "o.w": ((qh, h), 0.0, std),
             "gate.w": ((h, f), 0.0, std), "up.w": ((h, f), 0.0, std),
             "down.w": ((f, h), 0.0, std)}
    close = 1.0 / (2.0 * cfg["num_hidden_layers"]) ** 0.5
    layer.update({"ln1.g": ((h,), 1.0, std), "ln3.g": ((h,), 1.0, std),
                  "ln2.g": ((h,), close, std * close),
                  "ln4.g": ((h,), close, std * close)})
    return weights.with_layers(spec, layer, cfg["num_hidden_layers"], stacked)


def _layer_matmul_params(cfg: dict) -> int:
    h, f, qh, kvh = _widths(cfg)
    return 2 * h * qh + 2 * h * kvh + 3 * h * f


def matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication for every token, EVERY loop's
    products counted: the layers' matrices ``total_ut_steps`` times, and the
    untied head once. The exit gate is left out (it is not computed at
    threshold 1), as are the embedding lookup and the norms."""
    return (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
            * _layer_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def cache_layers(cfg: dict) -> int:
    """Entries of the KV cache a token holds: one a (loop, layer)."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def decode_pass_weight_bytes(cfg: dict) -> int:
    """Weight bytes one decode pass must stream from HBM at the
    configuration's ``dtype``: the layers' matrices and gains once a loop
    (a pass of 48 layers does not stay on the chip for the next), the final
    norm with them, the head once. The token table's rows are a gather of a
    few KB and are left out."""
    h = cfg["hidden_size"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["dtype"]]
    per_loop = cfg["num_hidden_layers"] * (
        _layer_matmul_params(cfg) + 4 * h) + h
    return itemsize * (cfg["total_ut_steps"] * per_loop
                       + cfg["vocab_size"] * h)


def reference_args(cfg: dict) -> dict:
    return {"loops": cfg["total_ut_steps"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"])}


def serve_program(cfg: dict):
    """The model that ``DecodeEngine`` serves through ``decode_adapter()``."""
    from paddle_tpu.text.models import OuroConfig, OuroForCausalLM

    model = OuroForCausalLM(OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        total_ut_steps=cfg["total_ut_steps"],
        early_exit_threshold=cfg["early_exit_threshold"],
        initializer_range=cfg.get("initializer_range", 0.02),
        tie_word_embeddings=cfg["tie_word_embeddings"]))
    names = dict(_TOP)
    for i in range(cfg["num_hidden_layers"]):
        names.update({f"h{i}.{k}": f"model.layers.{i}.{v}"
                      for k, v in _LEAF.items()})
    return model, names
