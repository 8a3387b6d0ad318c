"""Architecture ``gpt``: the GPT-3 decoder (Brown et al. 2020), as the
program builds it and as the yardstick counts it. A configuration names its
architecture under ``arch``; the harness finds ``archs/<arch>.py`` and
``reference/<arch>.py`` over the benchmark's directories by that name, so a
later PR brings a new architecture as two files and edits none.

What an architecture file gives (README.md, "An architecture"):

- ``CAUSAL``: whether a token attends only to what came before it (the
  attention term of every FLOP count is halved then);
- ``weight_spec(cfg, stacked)``: ``{leaf: (shape, mean, std)}`` under the
  benchmark's own names, per-layer leaves as ``h<i>.<leaf>`` or, stacked, as
  ``h.<leaf>`` with a leading layer axis (the reference's layout);
- ``matmul_params(cfg)``: the N of 6N;
- ``reference_args(cfg)``: keyword arguments of the plain reference's
  ``loss`` and ``logits``;
- ``train_program(cfg)`` -> (model, {leaf: the program's parameter name},
  whether the program's per-layer leaves are stacked);
- ``serve_program(cfg)`` -> (model, names), where the program serves it.
"""
import weights

CAUSAL = True

_LEAF = {"ln1.g": "ln_1.weight", "ln1.b": "ln_1.bias",
         "qkv.w": "attn.qkv_proj.weight", "qkv.b": "attn.qkv_proj.bias",
         "out.w": "attn.out_proj.weight", "out.b": "attn.out_proj.bias",
         "ln2.g": "ln_2.weight", "ln2.b": "ln_2.bias",
         "fc1.w": "mlp.fc_in.weight", "fc1.b": "mlp.fc_in.bias",
         "fc2.w": "mlp.fc_out.weight", "fc2.b": "mlp.fc_out.bias"}
_TOP = {"wte": "gpt.embeddings.word_embeddings.weight",
        "wpe": "gpt.embeddings.position_embeddings.weight",
        "lnf.g": "gpt.final_layernorm.weight",
        "lnf.b": "gpt.final_layernorm.bias"}


def weight_spec(cfg: dict, stacked: bool) -> dict:
    """Token and position tables, and per layer LN, fused QKV, output
    projection, LN, two-matrix GELU MLP."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    f, n = cfg["intermediate_size"], cfg["num_hidden_layers"]
    std = cfg.get("initializer_range", 0.02)
    spec = {"wte": ((v, h), 0.0, std),
            "wpe": ((cfg["max_position_embeddings"], h), 0.0, std),
            "lnf.g": ((h,), 1.0, std), "lnf.b": ((h,), 0.0, std)}
    layer = {"ln1.g": ((h,), 1.0, std), "ln1.b": ((h,), 0.0, std),
             "qkv.w": ((h, 3 * h), 0.0, std), "qkv.b": ((3 * h,), 0.0, std),
             "out.w": ((h, h), 0.0, std), "out.b": ((h,), 0.0, std),
             "ln2.g": ((h,), 1.0, std), "ln2.b": ((h,), 0.0, std),
             "fc1.w": ((h, f), 0.0, std), "fc1.b": ((f,), 0.0, std),
             "fc2.w": ((f, h), 0.0, std), "fc2.b": ((h,), 0.0, std)}
    return weights.with_layers(spec, layer, n, stacked)


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication for every token: the
    layers' matrices, plus the output head (tied to the token table, but
    multiplied all the same). Embedding lookups, biases and norms are left
    out. Copied in spirit from bench_configs.py."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)
            + cfg["vocab_size"] * h)


def reference_args(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "eps": cfg["layer_norm_epsilon"]}


def _config(cfg: dict, **kw):
    from paddle_tpu.text.models import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_act=cfg["hidden_act"],
        max_position_embeddings=cfg["max_position_embeddings"],
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
        initializer_range=cfg["initializer_range"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"], **kw)


def serve_program(cfg: dict):
    """The unfolded model that ``DecodeEngine`` serves."""
    from paddle_tpu.text.models import GPTForCausalLM

    names = dict(_TOP)
    for i in range(cfg["num_hidden_layers"]):
        names.update({f"h{i}.{k}": f"gpt.decoder.{i}.{v}"
                      for k, v in _LEAF.items()})
    return GPTForCausalLM(_config(cfg)), names


def train_program(cfg: dict):
    """The folded model (one stacked leaf a kind, a leading layer axis)."""
    from paddle_tpu.text.models import GPTForCausalLM

    t = cfg["train"]
    model = GPTForCausalLM(_config(
        cfg, fold_layers=t["fold_layers"], use_recompute=t["recompute"],
        recompute_granularity=t["recompute_granularity"]))
    names = dict(_TOP)
    names.update({f"h.{k}": "gpt.decoder." + v.replace(".", "__")
                  for k, v in _LEAF.items()})
    return model, names, True
