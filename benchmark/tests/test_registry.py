"""A later PR adds a cell, a mix and a per-layer metric as files and entries,
and edits no file that is there."""
import json
import os
import shutil

import harness
from conftest import BENCH, ROOT


def _files(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            if "__pycache__" not in d:
                p = os.path.join(d, n)
                with open(p, "rb") as f:
                    out[os.path.relpath(p, root)] = f.read()
    return out


def test_a_cell_a_mix_and_a_metric_are_added_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _files(os.path.join(root, "benchmark"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        reg = json.load(f)
    entries = json.loads(json.dumps(reg))  # what was there, to compare

    # the later PR's files: a directory of its own, or new files beside
    os.makedirs(os.path.join(root, "bench_more", "traffic"))
    os.makedirs(os.path.join(root, "bench_more", "metrics"))
    with open(os.path.join(BENCH, "traffic", "short_knee80.json")) as f:
        mix = json.load(f)
    mix["prompt_len"]["median"] = 900
    # a mix lists the metrics its cells report beyond those whose entries
    # name the cell
    mix["metrics"] = ["queue_wait_p95_ms", "window_tokens_per_s",
                      "serve_itl_p95_ms"]
    with open(os.path.join(root, "bench_more", "traffic", "long.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench_more", "metrics",
                           "queue_wait_p95_ms.json"), "w") as f:
        json.dump({"reader": "queue_wait_p95_ms.py", "args": {"q": 95}}, f)
    with open(os.path.join(root, "bench_more", "metrics",
                           "queue_wait_p95_ms.py"), "w") as f:
        f.write("def read(ctx, q):\n    return float(q)\n")
    # and its entries
    reg["paths"].append("bench_more")
    reg["workloads"].append({
        "name": "serve_long_1p3b", "config": "gpt3_1p3b_serve",
        "traffic": "long", "chips": 1, "why": "long prompts"})
    reg["per_layer"].append({
        "name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serve scheduler",
        "moves": "serve_itl_p95_ms", "workloads": []})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(reg, f)

    names = [c.name for c in harness.list_cells(root)]
    assert names == [w["name"] for w in entries["workloads"]] + [
        "serve_long_1p3b"]
    cell = harness.resolve("serve_long_1p3b", root)
    assert cell.mix["prompt_len"]["median"] == 900
    assert cell.config["hidden_size"] == 2048
    # the metric is listed by the cell's own mix, not by an edited entry
    assert "queue_wait_p95_ms" in [m["name"] for m in cell.per_layer]
    read, args = harness.load_reader(cell, "queue_wait_p95_ms")
    assert read(None, **args) == 95.0
    # the cells that were there neither gain the metric nor change
    old = harness.resolve(entries["workloads"][0]["name"], root)
    assert "queue_wait_p95_ms" not in [m["name"] for m in old.per_layer]
    assert _files(os.path.join(root, "benchmark")) == before
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert reg[key][:len(entries[key])] == entries[key]


def test_every_cell_of_the_benchmark_resolves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        reg = json.load(f)
    for cell in harness.list_cells(ROOT):
        assert cell.mix["kind"] in ("serve", "train")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            read, args = harness.load_reader(cell, m["name"])
            assert callable(read)
        assert os.path.exists(harness.find_file(
            ROOT, reg["paths"], "limits", cell.name + ".json"))
    for m in reg["per_layer"]:  # no reader file without an entry's name
        assert m["moves"] in {e["name"] for e in reg["end_to_end"]}


def test_every_entry_that_names_a_cell_is_reported_by_it():
    """A per-layer entry's ``workloads`` lists the cells that report it, and
    each of those cells reports the end-to-end metric that the entry moves
    (one name a metric: a quantity that moves ``serve_itl_p95_ms`` in one
    cell and ``serve_tokens_per_s`` in another is two entries, ``x`` and
    ``x.tput``). Every cell finds its mix, its limits and its
    configuration."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        reg = json.load(f)
    files = {c["name"]: c["file"] for c in reg["configs"]}
    for w in reg["workloads"]:
        cell = harness.resolve(w["name"], ROOT)
        reported = {m["name"] for m in cell.per_layer}
        judged = {m["name"] for m in cell.end_to_end}
        named = [m for m in reg["per_layer"]
                 if w["name"] in m.get("workloads", ())]
        assert named
        for m in named:
            assert m["name"] in reported, (w["name"], m["name"])
            assert m["moves"] in judged, (w["name"], m["name"])
        assert cell.mix and harness.load_limits(cell)
        assert os.path.exists(os.path.join(ROOT, files[w["config"]]))
        assert cell.config["arch"]
    # no entry names a cell that is not there
    cells = {w["name"] for w in reg["workloads"]}
    for m in reg["end_to_end"] + reg["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]


def test_the_tail_cell_and_the_saturated_cell_are_judged_apart():
    """Below the knee the tail is judged and tokens/s is the offered load;
    above it tokens/s completed is judged and the tail swings with the
    smallest change, so it is per-layer there (``itl_p95_ms.tput``)."""
    def judged(name):
        return {m["name"] for m in harness.resolve(name, ROOT).end_to_end}

    assert judged("serve_short_1p3b_knee80") == {"serve_itl_p95_ms",
                                                 "setup_s"}
    assert judged("serve_short_1p3b_saturated") == {"serve_tokens_per_s",
                                                    "setup_s"}
    tail = harness.resolve("serve_short_1p3b_knee80", ROOT)
    sat = harness.resolve("serve_short_1p3b_saturated", ROOT)
    assert tail.config == sat.config
    differ = {k for k in tail.mix if tail.mix[k] != sat.mix[k]}
    assert differ == {"rate_per_s", "doc"}
    assert sat.mix["rate_per_s"] > tail.mix["rate_per_s"]
    at_tail = {m["name"] for m in tail.per_layer}
    at_sat = {m["name"] for m in sat.per_layer}
    # the same layers in both, but the judged number itself, and the TTFT's
    # tail: per-layer metrics come from traced runs, and above the knee the
    # first tokens owed at the close wait for the profiler to stop
    shared = at_tail - {"window_tokens_per_s", "ttft_p95_ms"}
    assert {n + ".tput" for n in shared} == at_sat - {"itl_p95_ms.tput"}
    for name in shared:  # the same reader
        assert (harness.load_reader(tail, name)[1]
                == harness.load_reader(sat, name + ".tput")[1])
        assert (harness.load_reader(tail, name)[0]
                is harness.load_reader(sat, name + ".tput")[0])


def test_a_cell_judged_on_tokens_per_s_prints_it_with_trace_0():
    """The rehearsal's cell that is judged on ``serve_tokens_per_s``:
    ``serve.end_to_end`` computes it in every run, and the line carries it
    only where the cell's ``end_to_end`` names it. A CPU run: the number is
    never written down."""
    import run
    from conftest import REHEARSAL

    cell = harness.resolve("rehearse_serve_saturated", registry=REHEARSAL)
    out = run.run_cell(cell, 2147483659, 3.0, False)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["metrics"]["serve_tokens_per_s"]["unit"] == "tokens/s"
    assert {"itl_p95_ms.tput", "decode_step_p50_ms.tput"} <= {
        m["name"] for m in cell.per_layer}


# -- a new architecture, as files only ---------------------------------------

BOW_ARCH = '''"""A bag-of-tokens classifier: the toy architecture a later PR might bring."""
CAUSAL = False


def weight_spec(cfg, stacked):
    h, std = cfg["hidden_size"], 0.02
    return {"wte": ((cfg["vocab_size"], h), 0.0, std),
            "fc.w": ((h, h), 0.0, std), "fc.b": ((h,), 0.0, std),
            "cls.w": ((h, cfg["num_classes"]), 0.0, std),
            "cls.b": ((cfg["num_classes"],), 0.0, std)}


def matmul_params(cfg):
    return 0  # its matrices touch one pooled row a sequence


def reference_args(cfg):
    return {}


def train_program(cfg):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    class Bow(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(cfg["vocab_size"], cfg["hidden_size"])
            self.fc = nn.Linear(cfg["hidden_size"], cfg["hidden_size"])
            self.cls = nn.Linear(cfg["hidden_size"], cfg["num_classes"])

        def forward(self, ids, labels=None):
            pooled = paddle.tanh(self.fc(self.emb(ids).mean(axis=1)))
            return F.cross_entropy(self.cls(pooled), labels)

    names = {"wte": "emb.weight", "fc.w": "fc.weight", "fc.b": "fc.bias",
             "cls.w": "cls.weight", "cls.b": "cls.bias"}
    return Bow(), names, False
'''

BOW_REFERENCE = '''"""The toy's plain reference: float32 jax.numpy, nothing of the program."""
import jax
import jax.numpy as jnp


def loss(w, ids, labels, *, quant=None):
    with jax.default_matmul_precision("highest"):
        pooled = jnp.tanh(w["wte"][ids].mean(1) @ w["fc.w"] + w["fc.b"])
        logp = jax.nn.log_softmax(pooled @ w["cls.w"] + w["cls.b"], axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
'''


def test_an_architecture_is_added_as_files_and_runs(tmp_path):
    """A configuration of an architecture that no file of the benchmark
    knows: ``archs/bow.py`` and ``reference/bow.py`` in a directory of the
    later PR's own, a configuration, a cell and its limits. The harness
    builds it, drives it and holds it to its reference, and no file that was
    there is edited."""
    import run
    from conftest import REHEARSAL

    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(os.path.join(root, "benchmark"))
    more = os.path.join(root, "bench_more")
    for sub in ("archs", "reference", "configs", "limits"):
        os.makedirs(os.path.join(more, sub))
    with open(os.path.join(more, "archs", "bow.py"), "w") as f:
        f.write(BOW_ARCH)
    with open(os.path.join(more, "reference", "bow.py"), "w") as f:
        f.write(BOW_REFERENCE)
    with open(os.path.join(more, "configs", "tiny_bow.json"), "w") as f:
        json.dump({"arch": "bow", "vocab_size": 512, "hidden_size": 32,
                   "num_classes": 2, "dtype": "float32",
                   "train": {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                             "weight_decay": 0.01}}, f)
    with open(os.path.join(more, "limits", "rehearse_bow.json"), "w") as f:
        json.dump({"grad_norm_worst_leaf": 0.01, "grad_diff_rel": 0.01,
                   "change_norm_worst_leaf": 0.01}, f)
    with open(os.path.join(ROOT, REHEARSAL)) as f:
        reg = json.load(f)
    reg["paths"].insert(0, "bench_more")
    reg["configs"].append({
        "name": "tiny_bow", "source": "none: a toy", "reduced": [],
        "file": "bench_more/configs/tiny_bow.json", "why": "a new arch"})
    reg["workloads"].append({
        "name": "rehearse_bow", "config": "tiny_bow",
        "traffic": "tiny_finetune", "chips": 1, "why": "a new arch"})
    for m in reg["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("rehearse_bow")
    with open(os.path.join(more, "REGISTRY.json"), "w") as f:
        json.dump(reg, f)

    cell = harness.resolve("rehearse_bow", root, "bench_more/REGISTRY.json")
    assert cell.arch.CAUSAL is False and cell.arch.matmul_params({}) == 0
    assert cell.reference.__file__.startswith(more)
    out = run.run_cell(cell, 3, 1.0, False)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["compared"]) == {
        "grad_norm_worst_leaf", "grad_diff_rel", "change_norm_worst_leaf"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert _files(os.path.join(root, "benchmark")) == before
