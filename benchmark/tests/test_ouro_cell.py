"""The looped configuration's pieces of the yardstick (PR 35): the
architecture file's counts against the figures they were reckoned from, the
three ``loop_*`` readers on hand-made spans, and the CPU rehearsal of a tiny
looped cell end to end (a registry file of its own; never a device number)."""
import json
import os

import numpy as np
import pytest

import harness
import trace_reduce as tr
import work
from conftest import BENCH, ROOT
from paddle_tpu.observability import tracing

CELL = "serve_reason_ouro2p6b_saturated"
OURO_REHEARSAL = "benchmark/tests/rehearsal/REGISTRY_ouro.json"
LOOP_METRICS = ["loop_serve_step_mfu_pct", "loop_paged_attn_roofline",
                "loop_decode_step_hbm_roofline"]
PAGED = ('%paged_attention.26 = f32[8,16,8,128]{3,2,1,0:T(8,128)S(1)} '
         'custom-call(bf16[192,257,16,16,128]{4,3,2,1,0} %p), '
         'custom_call_target="tpu_custom_call"')
PREFILL = ('%prefill_attention.24 = f32[1,16,256,128]{3,2,1,0:T(8,128)S(1)} '
           'custom-call(bf16[1,16,256,128]{3,2,1,0} %q), '
           'custom_call_target="tpu_custom_call"')


# -- the counts ---------------------------------------------------------------


def test_the_architectures_counts_are_the_issues_figures():
    cell = harness.resolve(CELL, ROOT)
    cfg, arch = cell.config, cell.arch
    layer = 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    spec = arch.weight_spec(cfg, stacked=True)
    n = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert n == 48 * layer + 2 * 100_663_296 + 2048 + 2049 == 2_667_974_657
    assert set(arch.weight_spec(cfg, stacked=False)) >= {
        "wte", "head.w", "lnf.g", "exit.w", "exit.b", "h0.ln4.g", "h47.q.w"}
    assert arch.matmul_params(cfg) == 4 * 48 * (
        4 * 2048**2 + 3 * 2048 * 5632) + 49152 * 2048 == 9_965_666_304
    assert arch.cache_layers(cfg) == 192
    # 1,572,864 B of cache a token; 4 x 4.93 GB + 0.2 GB of weights a pass
    assert work.paged_decode_bytes(1, 192, 16, 128, 2) == 1_572_864
    assert arch.decode_pass_weight_bytes(cfg) == 2 * (
        4 * (48 * layer + 2048) + 49152 * 2048) == 19_934_494_720
    assert arch.reference_args(cfg) == {
        "loops": 4, "heads": 16, "kv_heads": 16, "head_dim": 128,
        "eps": 1e-6, "theta": 1e6}
    assert arch.CAUSAL


def test_the_configuration_is_the_catalogs_row_and_nothing_is_cut():
    reg = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in reg["configs"]}["ouro_2p6b_serve"]
    assert entry["reduced"] == []
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    want = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 5632, "max_position_embeddings": 65536,
            "max_window_layers": 48, "model_type": "ouro",
            "num_attention_heads": 16, "num_hidden_layers": 48,
            "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
            "rope_scaling": None, "rope_theta": 1000000,
            "sliding_window": None, "tie_word_embeddings": False,
            "total_ut_steps": 4, "early_exit_threshold": 1,
            "use_sliding_window": False, "vocab_size": 49152}
    assert {k: cfg[k] for k in want} == want
    assert cfg["layer_types"] == ["full_attention"] * 48
    e = cfg["engine"]
    pages = e["num_slots"] * e["max_length"] // e["page_size"] + 1
    per_pool = 192 * pages * 16 * e["page_size"] * 128
    assert per_pool == 1_616_904_192 < 2**31
    assert {"cache", "sandwich_norm", "loop_close", "weights",
            "engine"} <= set(cfg["assumed"])
    mix = harness.resolve(CELL, ROOT).mix
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= e["max_length"])
    assert mix["prompt_len"]["max"] <= max(e["prompt_buckets"])


def test_the_cell_reports_the_named_metrics_and_not_the_two_left_out():
    cell = harness.resolve(CELL, ROOT)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert set(LOOP_METRICS) <= names
    assert not names & {"paged_attn_roofline.tput", "serve_step_mfu_pct.tput"}
    assert {"paged_attn_time_pct.tput", "slot_occupancy_pct.tput",
            "kv_live_page_pct.tput", "itl_p95_ms.tput"} <= names
    for name in names:  # every one has its reader file
        harness.load_reader(cell, name)
    # the accepted cells report what they did: none of the new three
    for other in ("serve_short_1p3b_saturated", "serve_short_1p3b_knee80"):
        got = {m["name"] for m in harness.resolve(other, ROOT).per_layer}
        assert not got & set(LOOP_METRICS)


# -- the three readers on hand-made spans -------------------------------------


class _Tracer:
    t_start, t_stop = 100.0, 105.0


class _Spans:
    def __init__(self, rows):
        self.rows = rows

    def named(self, name, t_from=None, t_to=None):
        return [s for s in self.rows if s.name == name
                and s.t0 >= t_from and s.t1 <= t_to]


@pytest.fixture(autouse=True)
def _own_buffer():
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


def _ctx(events, spans, said=192):
    """``said``: the ``cache_layers`` the program's ``eng_step`` carries
    (None: a program that writes no such attr, as the parent)."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    attrs = {} if said is None else {"cache_layers": said, "loops": 4}
    tracing._buffer.append(tracing.Recorded(
        "eng_step", 100.2, 100.3, "trace", "s1", None, attrs))
    red = tr.Reduced(window_s=5.0, busy_s=4.0,
                     events=[(t[:20], s, d, t) for s, d, t in events])
    outcome = harness.Outcome(
        setup_s=1.0, end_to_end={}, attempted=1, failed=0, compared=[],
        counters={}, window=(60.0, 105.0), memory_peak_bytes=0,
        spans=_Spans(spans), tracer=_Tracer())
    return harness.ReadCtx(harness.resolve(CELL, ROOT), outcome, red, peaks,
                           45.0)


def _read(ctx, metric):
    read, args = harness.load_reader(ctx.cell, metric)
    return read(ctx, **args)


#: two plain decode steps of 8 slots at ~300 tokens each, 70 ms; one step
#: that admitted a 100-token prompt with 64 cached, 150 ms; one step that
#: began before the trace did and is left out
STEPS = [harness.Span("engine.step", 100.50, 100.57,
                      {"decode_ctx": 2400, "decoded": 8}),
         harness.Span("engine.step", 100.57, 100.64,
                      {"decode_ctx": 2408, "decoded": 8}),
         harness.Span("engine.step", 100.64, 100.79,
                      {"decode_ctx": 2100, "decoded": 7,
                       "admitted": [(100, 64)]}),
         harness.Span("engine.step", 99.9, 100.1,
                      {"decode_ctx": 9e9, "decoded": 8})]
#: 192 decode calls a pass take 3 x 45 ms here; the prefill's call is not one
EVENTS = [(0.5, 0.045, PAGED), (0.57, 0.045, PAGED), (0.64, 0.045, PAGED),
          (0.70, 0.01, PREFILL)]


def test_the_three_loop_readers_count_both_depths_apart():
    ctx = _ctx(EVENTS, STEPS)
    peaks = ctx.peaks
    n = 9_965_666_304
    tokens = [8, 8, 7 + 36]
    keys = [2400, 2408, 2100 + (100 * 101 - 64 * 65) / 2]
    flops = sum(2.0 * n * t + 4.0 * 192 * 2048 * k
                for t, k in zip(tokens, keys))
    mfu = _read(ctx, "loop_serve_step_mfu_pct")
    assert mfu == pytest.approx(100 * flops / (0.29 * 197e12))
    # the accepted reader's count at 48 layers would miss 3/4 of the keys
    assert work.forward_flops(n, 48, 2048, 8, 2400) < work.forward_flops(
        n, 192, 2048, 8, 2400)
    least = sum(work.least_seconds(
        work.paged_decode_flops(live, 192, 16, 128),
        work.paged_decode_bytes(live, 192, 16, 128, 2), peaks)
        for live in (2400, 2408, 2100))
    roof = _read(ctx, "loop_paged_attn_roofline")
    assert roof == pytest.approx(100 * least / 0.135)
    # plain decode steps only: the admit step is on neither side
    nbytes = 2 * 19_934_494_720 + (2400 + 2408) * 1_572_864
    hbm = _read(ctx, "loop_decode_step_hbm_roofline")
    assert hbm == pytest.approx(100 * nbytes / (819e9 * 0.14))
    # numbers a chip could give: each share is under 100 by construction
    assert 0 < mfu < 5 and 0 < roof < 20 and 30 < hbm < 100


@pytest.mark.parametrize("said", [None, 48, 191])
def test_the_loop_readers_say_nothing_where_the_program_disagrees(said):
    """The parent writes no ``cache_layers``; a program whose pool is
    another depth than the architecture file counts is not read either."""
    ctx = _ctx(EVENTS, STEPS, said=said)
    for metric in LOOP_METRICS:
        assert _read(ctx, metric) is None


def test_the_loop_readers_find_nothing_in_an_empty_window():
    ctx = _ctx([], [])
    for metric in LOOP_METRICS:
        assert _read(ctx, metric) is None
    ctx.peaks = None  # a rehearsal off the chip has no peaks
    for metric in LOOP_METRICS:
        assert _read(ctx, metric) is None


# -- the rehearsal, end to end ------------------------------------------------


def test_rehearsal_of_a_tiny_looped_cell_runs_end_to_end_and_is_correct():
    import run

    cell = harness.resolve("rehearse_ouro_serve", registry=OURO_REHEARSAL)
    assert cell.config["arch"] == "ouro"
    assert cell.arch.cache_layers(cell.config) == 8
    out = run.run_cell(cell, 3, 4.0, True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    got = set(out["metrics"])
    # off the chip there are no peaks: the three shares say nothing, the
    # spans' and counters' readers do
    assert not got & set(LOOP_METRICS)
    assert {"slot_occupancy_pct.tput", "kv_live_page_pct.tput",
            "decode_step_p50_ms.tput", "eng_decode_host_p50_ms.tput",
            "prefix_hit_pct.tput"} <= got
    assert out["metrics"]["slot_occupancy_pct.tput"]["value"] > 60
    steps = [r for r in tracing.recorded() if r.name == "eng_step"]
    assert steps and all(r.attrs["cache_layers"] == 8
                         and r.attrs["loops"] == 4 for r in steps)
    out = run.run_cell(cell, 4, 3.0, False)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["correct"] is True
