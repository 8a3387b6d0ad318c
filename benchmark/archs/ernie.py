"""Architecture ``ernie``: the ERNIE 3.0 base encoder (BERT blocks, post-LN,
plus a task-type table) with a tanh pooler over the first token and a linear
classifier. What an architecture file gives: ``archs/gpt.py``."""
import weights

CAUSAL = False

_LEAF = {"qkv.w": "attention.qkv_proj.weight",
         "qkv.b": "attention.qkv_proj.bias",
         "out.w": "attention.out_proj.weight",
         "out.b": "attention.out_proj.bias",
         "ln1.g": "ln_1.weight", "ln1.b": "ln_1.bias",
         "fc1.w": "fc_in.weight", "fc1.b": "fc_in.bias",
         "fc2.w": "fc_out.weight", "fc2.b": "fc_out.bias",
         "ln2.g": "ln_2.weight", "ln2.b": "ln_2.bias"}
_E = "ernie.embeddings."
_TOP = {"wte": _E + "word_embeddings.weight",
        "wpe": _E + "position_embeddings.weight",
        "wtype": _E + "token_type_embeddings.weight",
        "wtask": _E + "task_type_embeddings.weight",
        "lne.g": _E + "layer_norm.weight", "lne.b": _E + "layer_norm.bias",
        "pool.w": "ernie.pooler.dense.weight",
        "pool.b": "ernie.pooler.dense.bias",
        "cls.w": "classifier.weight", "cls.b": "classifier.bias"}
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "intermediate_size", "hidden_act", "hidden_dropout_prob",
    "attention_probs_dropout_prob", "max_position_embeddings",
    "type_vocab_size", "task_type_vocab_size", "use_task_id",
    "initializer_range", "layer_norm_eps")


def weight_spec(cfg: dict, stacked: bool) -> dict:
    h, f, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    c, std = cfg["num_classes"], cfg.get("initializer_range", 0.02)
    spec = {"wte": ((cfg["vocab_size"], h), 0.0, std),
            "wpe": ((cfg["max_position_embeddings"], h), 0.0, std),
            "wtype": ((cfg["type_vocab_size"], h), 0.0, std),
            "wtask": ((cfg["task_type_vocab_size"], h), 0.0, std),
            "lne.g": ((h,), 1.0, std), "lne.b": ((h,), 0.0, std),
            "pool.w": ((h, h), 0.0, std), "pool.b": ((h,), 0.0, std),
            "cls.w": ((h, c), 0.0, std), "cls.b": ((c,), 0.0, std)}
    layer = {"qkv.w": ((h, 3 * h), 0.0, std), "qkv.b": ((3 * h,), 0.0, std),
             "out.w": ((h, h), 0.0, std), "out.b": ((h,), 0.0, std),
             "ln1.g": ((h,), 1.0, std), "ln1.b": ((h,), 0.0, std),
             "fc1.w": ((h, f), 0.0, std), "fc1.b": ((f,), 0.0, std),
             "fc2.w": ((f, h), 0.0, std), "fc2.b": ((h,), 0.0, std),
             "ln2.g": ((h,), 1.0, std), "ln2.b": ((h,), 0.0, std)}
    return weights.with_layers(spec, layer, n, stacked)


def matmul_params(cfg: dict) -> int:
    """The layers' matrices; the pooler and the classifier touch one token a
    row, and embedding lookups, biases and norms are left out."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)


def reference_args(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"], "eps": cfg["layer_norm_eps"]}


def train_program(cfg: dict):
    """``ErnieForSequenceClassification``, layers unfolded."""
    from paddle_tpu.text.models import (ErnieConfig,
                                        ErnieForSequenceClassification)

    model = ErnieForSequenceClassification(
        ErnieConfig(**{k: cfg[k] for k in _CONFIG_KEYS}),
        num_classes=cfg["num_classes"])
    names = dict(_TOP)
    for i in range(cfg["num_hidden_layers"]):
        names.update({f"h{i}.{k}": f"ernie.encoder.{i}.{v}"
                      for k, v in _LEAF.items()})
    return model, names, False
