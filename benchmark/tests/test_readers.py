"""The per-layer readers that turn a reduced trace into a share, on hand-made
traces: which events and which steps each side of a share holds."""
import json
import os

import pytest

import harness
import trace_reduce as tr
import work
from conftest import BENCH, ROOT

PAGED = ('%pure.40 = f32[8,16,8,128]{3,2,1,0:T(8,128)S(1)} custom-call('
         's32[8,128]{1,0:T(8,128)S(1)} %copy-done.4, bf16[1025,16,16,128]'
         '{3,2,1,0:T(8,128)(2,1)} %copy_bitcast_fusion.13), '
         'custom_call_target="tpu_custom_call", frontend_attributes={}')
PREFILL = ('%pure.7 = f32[1,16,512,128]{3,2,1,0:T(8,128)} custom-call('
           'bf16[1,16,512,128]{3,2,1,0} %q), '
           'custom_call_target="tpu_custom_call"')
FLASH = ('%pure.3 = bf16[384,1024,64]{2,1,0:T(8,128)(2,1)} custom-call('
         'bf16[384,1024,64]{2,1,0} %q), custom_call_target="tpu_custom_call"')
OTHER = "%fusion.9 = f32[32,1024]{1,0} fusion(f32[32,1024]{1,0} %p)"


class _Tracer:
    t_start, t_stop = 100.0, 105.0


class _Spans:
    def __init__(self, rows):
        self.rows = rows

    def named(self, name, t_from=None, t_to=None):
        return [s for s in self.rows if s.name == name
                and s.t0 >= t_from and s.t1 <= t_to]


def _ctx(cell_name, events, spans, busy_s=4.0):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    red = tr.Reduced(window_s=5.0, busy_s=busy_s,
                     events=[(t[:20], s, d, t) for s, d, t in events])
    outcome = harness.Outcome(
        setup_s=1.0, end_to_end={}, attempted=1, failed=0, compared=[],
        counters={}, window=(60.0, 105.0), memory_peak_bytes=0,
        spans=_Spans(spans), tracer=_Tracer())
    return harness.ReadCtx(harness.resolve(cell_name, ROOT), outcome, red,
                           peaks, 45.0)


def _read(ctx, metric):
    read, args = harness.load_reader(ctx.cell, metric)
    return read(ctx, **args)


def test_the_paged_kernels_share_leaves_the_prefill_kernel_out():
    steps = [harness.Span("engine.step", 100.5, 100.7,
                          {"decode_ctx": 2000, "decoded": 4}),
             harness.Span("engine.step", 100.7, 101.0,
                          {"decode_ctx": 2100, "decoded": 4,
                           "admitted": [(512, 128)]}),
             harness.Span("engine.step", 99.9, 100.1, {"decode_ctx": 9e9})]
    ctx = _ctx("serve_short_1p3b_knee80",
               [(0.5, 0.15, PAGED), (0.7, 0.15, PAGED), (0.85, 0.05, PREFILL),
                (0.9, 0.02, OTHER)], steps)
    assert "f32\\[8," in ctx.pattern("= f32\\[$num_slots,")
    peaks = ctx.peaks
    least = sum(work.least_seconds(
        work.paged_decode_flops(live, 24, 16, 128),
        work.paged_decode_bytes(live, 24, 16, 128, 2), peaks)
        for live in (2000, 2100))  # the span that began before the trace: no
    assert _read(ctx, "paged_attn_roofline") == pytest.approx(
        100 * least / 0.30)
    assert _read(ctx, "paged_attn_time_pct") == pytest.approx(100 * 0.30 / 4)


def test_the_flash_kernels_share_holds_whole_steps_on_both_sides():
    # the step in flight when the trace starts ends 0.2 s into it: its
    # kernel time is left out, as its span is
    steps = [harness.Span("train.step", 99.9, 100.2),
             harness.Span("train.step", 100.2, 100.5),
             harness.Span("train.step", 100.5, 100.8)]
    ctx = _ctx("train_ernie3_base_seq1024_o1",
               [(0.05, 0.04, FLASH), (0.25, 0.08, FLASH), (0.3, 0.1, OTHER),
                (0.55, 0.08, FLASH)], steps)
    peaks = ctx.peaks
    fwd = work.attention_flops(32, 12, 1024, 1024, 64, False, False)
    both = work.attention_flops(32, 12, 1024, 1024, 64, False, True)
    least = (work.least_seconds(fwd, work.attention_bytes(
        32, 12, 1024, 1024, 64, 2, False), peaks)
        + work.least_seconds(both - fwd, work.attention_bytes(
            32, 12, 1024, 1024, 64, 2, True), peaks))
    assert _read(ctx, "flash_attn_roofline") == pytest.approx(
        100 * least * 12 * 2 / 0.16)
    # a share of busy time needs no whole steps: every event counts
    assert _read(ctx, "flash_attn_time_pct") == pytest.approx(100 * 0.20 / 4)


def test_a_reader_that_finds_nothing_returns_nothing():
    ctx = _ctx("serve_short_1p3b_knee80", [(0.9, 0.02, OTHER)], [])
    for metric in ("paged_attn_roofline", "paged_attn_time_pct",
                   "serve_step_mfu_pct"):
        assert _read(ctx, metric) is None
