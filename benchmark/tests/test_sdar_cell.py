"""The block-diffusion configuration's pieces of the yardstick: the
architecture file's counts against the figures they were reckoned from, the
configuration against the catalog's row, the five new readers on hand-made
spans, the reference's row convention against ``serve.py``'s readings, and
the CPU rehearsal of a tiny block-diffusion cell end to end (a registry file
of its own; never a device number)."""
import json
import os

import numpy as np
import pytest

import harness
import serve
import trace_reduce as tr
import traffic
from conftest import BENCH, ROOT
from paddle_tpu.observability import tracing

CELL = "serve_chat_sdar30b_saturated"
SDAR_REHEARSAL = "benchmark/tests/rehearsal/REGISTRY_sdar.json"
NEW = ["moe_gmm_hbm_roofline", "moe_experts_touched_pct",
       "block_attn_roofline", "block_pass_hbm_roofline",
       "block_serve_step_mfu_pct"]
PAGED = ('%paged_attention.26 = f32[16,4,32,128]{3,2,1,0:T(8,128)S(1)} '
         'custom-call(bf16[6,1025,4,16,128]{4,3,2,1,0} %p), '
         'custom_call_target="tpu_custom_call"')
GMM = ('%grouped_matmul_fwd.4 = bf16[512,1536]{1,0:T(8,128)(2,1)S(1)} '
       'custom-call(%copy.1, %fusion.10), custom_call_target="tpu_custom_call"')
GMM_PREFILL = ('%grouped_matmul_fwd.9 = bf16[4096,1536]{1,0} '
               'custom-call(%copy.2), custom_call_target="tpu_custom_call"')


# -- the counts and the configuration -----------------------------------------


def test_the_architectures_counts_are_the_reckoned_figures():
    cell = harness.resolve(CELL, ROOT)
    cfg, arch = cell.config, cell.arch
    attn, router, norms = 18_874_368, 262_144, 4_352
    expert = 4_718_592
    layer = attn + router + norms + 128 * expert
    assert layer == 623_120_640
    spec = arch.weight_spec(cfg, stacked=True)
    n = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert n == 6 * layer + 622_329_856 + 2048 == 4_361_055_744
    assert 2 * n / 17.18e9 > 0.5  # over half the chip in weights alone
    assert arch.matmul_params(cfg) == 6 * (attn + router + 128 * expert) + (
        151936 * 2048)
    assert arch.active_matmul_params(cfg) == 6 * (
        attn + router + 8 * expert) + 151936 * 2048 == 652_476_416
    assert arch.expert_bytes(cfg, 1) == 2 * expert
    # every expert of every layer touched: 8.10 GB a pass; 98% of them,
    # what 64 rows x 8 choices reach (1 - (120/128)^64), 7.98 GB
    assert arch.pass_weight_bytes(cfg, 768) == 2 * (
        6 * (attn + router + norms) + 768 * expert + 151936 * 2048 + 2048)
    assert 7.9e9 < arch.pass_weight_bytes(cfg, round(
        768 * (1 - (120 / 128) ** 64))) < 8.0e9
    assert arch.reference_args(cfg) == {
        "block": 4, "mask_id": 151669, "heads": 32, "kv_heads": 4,
        "head_dim": 128, "eps": 1e-6, "theta": 1e6, "top_k": 8}


def test_the_configuration_is_the_catalogs_row_with_its_depth_cut():
    reg = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in reg["configs"]}["sdar_30b_a3b_serve"]
    assert entry["reduced"] == ["num_hidden_layers"]
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    want = {"attention_bias": False, "decoder_sparse_step": 1,
            "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "max_position_embeddings": 32768,
            "max_window_layers": 48, "mlp_only_layers": [],
            "model_type": "sdar_moe", "moe_intermediate_size": 768,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts": 128, "num_experts_per_tok": 8,
            "num_hidden_layers": 6, "num_key_value_heads": 4,
            "rms_norm_eps": 1e-06, "rope_scaling": None,
            "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "vocab_size": 151936}
    assert {k: cfg[k] for k in want} == want
    assert {"cut", "qk_norm", "block_length", "denoise_steps",
            "mask_token_id", "remasking", "weights",
            "engine"} <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"]
    e = cfg["engine"]
    pages = e["num_slots"] * e["max_length"] // e["page_size"] + 1
    assert pages * e["page_size"] * 6 * 2048 == 201_523_200
    assert e["page_size"] % cfg["block_length"] == 0
    mix = harness.resolve(CELL, ROOT).mix
    assert mix["output_len"]["min"] == mix["output_len"]["max"] == 256
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= e["max_length"])
    assert mix["prompt_len"]["max"] <= max(e["prompt_buckets"])


def test_the_cell_reports_the_named_metrics_and_not_the_two_left_out():
    cell = harness.resolve(CELL, ROOT)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert not names & {"paged_attn_roofline.tput", "serve_step_mfu_pct.tput",
                        "eng_decode_host_p50_ms.tput"}
    for name in names:
        harness.load_reader(cell, name)
    for other in ("serve_short_1p3b_saturated", "serve_reason_ouro2p6b_saturated"):
        got = {m["name"] for m in harness.resolve(other, ROOT).per_layer}
        assert not got & set(NEW)


# -- the five readers on hand-made spans --------------------------------------


class _Tracer:
    t_start, t_stop = 100.0, 105.0


@pytest.fixture(autouse=True)
def _own_buffer():
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


def _ctx(events, spans=()):
    """``spans``: the program's recorded spans (name, t0, t1, attrs)."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    for i, (name, t0, t1, attrs) in enumerate(spans):
        tracing._buffer.append(tracing.Recorded(
            name, t0, t1, "trace", f"s{i}", None, attrs))
    red = tr.Reduced(window_s=5.0, busy_s=4.0,
                     events=[(t[:20], s, d, t) for s, d, t in events])
    outcome = harness.Outcome(
        setup_s=1.0, end_to_end={}, attempted=1, failed=0, compared=[],
        counters={}, window=(60.0, 105.0), memory_peak_bytes=0,
        spans=None, tracer=_Tracer())
    return harness.ReadCtx(harness.resolve(CELL, ROOT), outcome, red, peaks,
                           51.0)


def _read(ctx, metric):
    read, args = harness.load_reader(ctx.cell, metric)
    return read(ctx, **args)


#: two block passes (16 ms, 700 and 760 experts touched, 800 and 804 pages
#: read) and a commit (12 ms, 630 touched, 810 pages) inside three steps
SPANS = [("eng_step", 100.10, 100.14, {"rows_computed": 1000,
          "experts_touched": 5000, "experts_capacity": 7000}),
         ("eng_block_pass", 100.20, 100.216,
          {"experts_touched": 700, "kv_pages": 800}),
         ("eng_step", 100.19, 100.22, {"rows_computed": 1064,
          "experts_touched": 5700, "experts_capacity": 7768}),
         ("eng_block_commit", 100.23, 100.242,
          {"experts_touched": 630, "kv_pages": 810}),
         ("eng_block_pass", 100.25, 100.266,
          {"experts_touched": 760, "kv_pages": 804}),
         ("eng_step", 100.225, 100.27, {"rows_computed": 1192,
          "experts_touched": 7090, "experts_capacity": 9408})]
#: the grouped kernel 2 x 12 calls a pass at 1 ms, a prefill's call left
#: out; the paged kernel 6 calls a pass at 0.05 ms
EVENTS = ([(0.2 + i * 1e-3, 1e-3, GMM) for i in range(12 + 10 + 12)]
          + [(0.3, 0.9e-3, GMM_PREFILL)]
          + [(0.2 + i * 1e-3, 0.05e-3, PAGED) for i in range(6 + 5 + 6)])


def test_the_five_block_readers_count_what_the_passes_did():
    ctx = _ctx(EVENTS, SPANS)
    cfg, arch, peaks = ctx.cell.config, ctx.cell.arch, ctx.peaks
    bw = peaks["hbm_bytes_per_s"]
    rows = 16 * 4 * 8 * (2048 + 1536 + 768 + 2048) * 2
    gmm_bytes = sum(arch.expert_bytes(cfg, t) + n * rows
                    for t, n in ((700, 6), (630, 5), (760, 6)))
    got = _read(ctx, "moe_gmm_hbm_roofline")
    assert got == pytest.approx(100 * gmm_bytes / bw / (34 * 1e-3))
    assert _read(ctx, "moe_experts_touched_pct") == pytest.approx(
        100 * 7090 / 9408)
    page = 16 * 6 * 2 * 4 * 128 * 2
    least = sum(max(4.0 * pages * 16 * 4 * 6 * 32 * 128
                    / peaks["bf16_flops_per_s"], pages * page / bw)
                for pages in (800, 810, 804))
    assert _read(ctx, "block_attn_roofline") == pytest.approx(
        100 * least / (17 * 0.05e-3))
    nbytes = (arch.pass_weight_bytes(cfg, 700) + 800 * page
              + arch.pass_weight_bytes(cfg, 760) + 804 * page)
    assert _read(ctx, "block_pass_hbm_roofline") == pytest.approx(
        100 * nbytes / (bw * 0.032))
    mfu = _read(ctx, "block_serve_step_mfu_pct")
    assert mfu == pytest.approx(100 * 2 * 652_476_416 * 192 / (
        (0.03 + 0.045) * peaks["bf16_flops_per_s"]))
    # numbers a chip could give: the shares are under 100 by construction
    for metric in NEW:
        assert 0 < _read(ctx, metric) < 100


def test_the_block_readers_say_nothing_without_the_programs_spans():
    """The parent's program records no block spans and no running totals
    of rows and experts: every new reader reports nothing."""
    ctx = _ctx(EVENTS, [("eng_step", 100.1, 100.2, {"slot_steps": 3})])
    for metric in NEW:
        assert _read(ctx, metric) is None
    ctx = _ctx([], [])
    ctx.peaks = None
    for metric in NEW:
        assert _read(ctx, metric) is None


# -- the reference's row convention against serve.py's readings ---------------


def test_sequential_rows_read_no_gap_on_the_programs_own_float32_tokens():
    """The tiny cell in float32: the program's greedy tokens under the
    ``sequential`` rule, read by ``serve.reference_readings`` as every
    cell's are, lie on the reference's best at every position (row r is
    position r + 1's logits in the state that drew it)."""
    cell = harness.resolve("rehearse_sdar_serve", registry=SDAR_REHEARSAL)
    cell.config = dict(cell.config, dtype="float32",
                       engine=dict(cell.config["engine"], kv_dtype="f32"))
    _, _, engine = serve.build_engine(cell, 5)
    rng = np.random.default_rng(1)
    tracks = []
    for i, n in enumerate((17, 22, 9)):
        req = traffic.ServeRequest(
            due=0.0, prompt=rng.integers(1, 4096, n).astype(np.int32),
            max_new_tokens=16, greedy=True, seed=i, shared=-1)
        t = serve.Track(req=req, due=0.0, in_window=True)
        t.rid = engine.submit(req.prompt, serve.sampling_params(cell.mix,
                                                                req))
        tracks.append(t)
    engine.run()
    for t in tracks:
        t.tokens = list(engine._requests[t.rid].tokens)
        assert len(t.tokens) == 16
    got = serve.reference_readings(cell, 5, tracks, pad_to=128)
    assert got["greedy_tokens"] == 48
    assert got["logit_gap_max"] < 1e-4


# -- the rehearsal, end to end ------------------------------------------------


def test_rehearsal_of_a_tiny_block_diffusion_cell_runs_end_to_end():
    import run

    cell = harness.resolve("rehearse_sdar_serve", registry=SDAR_REHEARSAL)
    assert cell.config["arch"] == "sdar"
    out = run.run_cell(cell, 3, 4.0, True)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    # off the chip there are no peaks: the shares say nothing, the spans'
    # and counters' readers do
    assert not got & (set(NEW) - {"moe_experts_touched_pct"})
    assert {"moe_experts_touched_pct", "slot_occupancy_pct.tput",
            "kv_live_page_pct.tput", "decode_step_p50_ms.tput",
            "prefix_hit_pct.tput"} <= got
    steps = [r for r in tracing.recorded() if r.name == "eng_step"]
    assert steps and all(r.attrs["block_length"] == 4 for r in steps
                         if "block_length" in r.attrs)
    assert {r.name for r in tracing.recorded()} >= {
        "eng_block_pass", "eng_block_commit"}
    out = run.run_cell(cell, 4, 3.0, False)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["correct"] is True
