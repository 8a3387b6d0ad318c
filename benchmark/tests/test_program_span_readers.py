"""The readers of the PROGRAM's own spans and counts, on hand-made spans,
device intervals and counters; then the CPU rehearsal end to end, which has
to print all seven metrics that are read from inside the program."""
import json
import os
import subprocess
import sys

import pytest

import harness
import trace_reduce as tr
from conftest import ROOT
from paddle_tpu.observability import tracing

SPANS_REHEARSAL = "benchmark/tests/rehearsal/REGISTRY_spans.json"
NEW_SERVE = ["eng_decode_readback_p50_ms", "eng_decode_host_p50_ms",
             "eng_decode_upload_p50_ms", "idle_in_engine_host_pct",
             "slot_occupancy_pct", "prefix_hit_pct"]
NEW_TRAIN = ["train_dispatch_host_p50_ms"]


class _Tracer:
    t_start, t_stop = 100.0, 105.0


@pytest.fixture(autouse=True)
def _own_buffer():
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


def _span(name, t0, t1, parent=None, sid=None, **attrs):
    sid = sid or f"{name}@{t0}"
    tracing._buffer.append(
        tracing.Recorded(name, t0, t1, "trace", sid, parent, attrs))
    return sid


def _ctx(cell_name, device=(), counters=None, tracer=_Tracer):
    """``device``: [(seconds into the trace, seconds)] of device work."""
    red = tr.Reduced(window_s=5.0, busy_s=sum(d for _, d in device),
                     events=[("op", s, d, "op") for s, d in device])
    outcome = harness.Outcome(
        setup_s=1.0, end_to_end={}, attempted=1, failed=0, compared=[],
        counters=counters or {}, window=(60.0, 105.0), memory_peak_bytes=0,
        tracer=tracer() if tracer else None)
    return harness.ReadCtx(harness.resolve(cell_name, ROOT), outcome, red,
                           None, 45.0)


def _read(ctx, metric):
    read, args = harness.load_reader(ctx.cell, metric)
    return read(ctx, **args)


def _decode_step(t0, prep, upload, dispatch, readback, append, **attrs):
    t = t0
    root = _span("eng_step", t0, t0 + prep + upload + dispatch + readback
                 + append + 0.001, **attrs)
    for name, d in (("prep", prep), ("upload", upload),
                    ("dispatch", dispatch), ("readback", readback),
                    ("append", append)):
        _span("eng_decode_" + name, t, t + d, parent=root)
        t += d
    return root


def test_span_medians_sum_a_steps_parts_and_keep_to_the_traced_window():
    _decode_step(99.0, 9, 9, 9, 9, 9)  # began before the trace: left out
    _decode_step(100.0, 0.002, 0.001, 0.003, 0.190, 0.001)
    _decode_step(100.3, 0.004, 0.002, 0.003, 0.180, 0.001)
    _decode_step(100.6, 0.003, 0.003, 0.003, 0.200, 0.001)
    ctx = _ctx("serve_short_1p3b_knee80")
    assert _read(ctx, "eng_decode_readback_p50_ms") == pytest.approx(190.0)
    assert _read(ctx, "eng_decode_upload_p50_ms") == pytest.approx(2.0)
    # prep + upload + dispatch + append of each step: 7, 10, 10 ms
    assert _read(ctx, "eng_decode_host_p50_ms") == pytest.approx(10.0)
    # a span without a parent is a step of its own
    for t0, d in ((101.0, 0.002), (101.3, 0.004), (101.6, 0.003)):
        root = _span("train_step", t0, t0 + d)
        _span("train_dispatch", t0, t0 + d / 2, parent=root)
    ctx = _ctx("train_ernie3_base_seq1024_o1")
    assert _read(ctx, "train_dispatch_host_p50_ms") == pytest.approx(3.0)


def test_idle_under_a_span_is_by_overlap_and_leaves_the_read_backs_out():
    # the device works 0.1-0.9 and 1.0-1.9 s into the trace; idle: 0-0.1,
    # 0.9-1.0, 1.9-5.0
    device = [(0.1, 0.8), (1.0, 0.9), (-0.5, 0.55)]  # the last: 0-0.05 busy
    # step 1 covers 0.06-0.95: idle under it 0.06-0.1 and 0.9-0.95, of
    # which its read-back (0.2-0.93) takes 0.9-0.93
    s1 = _span("eng_step", 100.06, 100.95)
    _span("eng_decode_readback", 100.2, 100.93, parent=s1)
    # step 2 covers 0.95-2.0: idle under it 0.95-1.0 and 1.9-2.0; its
    # prefill's read-back takes 1.95-2.0. The gap 0.9-1.0 has its middle in
    # step 2 only: overlap gives each step its part
    s2 = _span("eng_step", 100.95, 102.0)
    _span("eng_prefill_readback", 101.95, 102.0, parent=s2)
    ctx = _ctx("serve_short_1p3b_knee80", device)
    idle_host = (0.04 + 0.02) + (0.05 + 0.05)
    assert _read(ctx, "idle_in_engine_host_pct") == pytest.approx(
        100 * idle_host / 5.0)
    read, _ = harness.load_reader(ctx.cell, "idle_in_engine_host_pct")
    assert read(ctx, spans=["eng_step"]) == pytest.approx(
        100 * (0.04 + 0.05 + 0.05 + 0.1) / 5.0)
    # never more than the device's idle share of the window
    assert read(ctx, spans=["eng_step"]) <= 100 * (1 - 1.75 / 5.0)


def test_occupancy_and_the_counter_ratio():
    # running totals: the last span of the traced window is the whole run's
    _span("eng_step", 99.9, 100.1, slot_steps=9, slot_capacity=10)  # before
    _span("eng_step", 100.0, 100.2, slot_steps=400, slot_capacity=1600)
    _span("eng_step", 100.2, 100.4, slot_steps=406, slot_capacity=1608)
    _span("eng_step", 100.4, 100.6, slot_steps=410, slot_capacity=1616)
    _span("eng_step", 100.6, 100.8, num_slots=8)  # a span without them
    _span("eng_step", 104.9, 105.1, slot_steps=1, slot_capacity=2)  # after
    ctx = _ctx("serve_short_1p3b_knee80", counters={
        "prefix_hit_tokens": 1280, "prompt_tokens_total": 10240})
    assert _read(ctx, "slot_occupancy_pct") == pytest.approx(
        100 * 410 / 1616)
    assert _read(ctx, "prefix_hit_pct") == pytest.approx(12.5)
    ctx = _ctx("serve_short_1p3b_knee80", counters={
        "prefix_hit_tokens": 0, "prompt_tokens_total": 10240})
    assert _read(ctx, "prefix_hit_pct") == 0.0  # a count that reads 0


@pytest.mark.parametrize("metric", NEW_SERVE + NEW_TRAIN)
def test_a_reader_that_finds_nothing_returns_none_never_0(metric,
                                                          monkeypatch):
    cell = ("train_ernie3_base_seq1024_o1" if metric in NEW_TRAIN
            else "serve_short_1p3b_knee80")
    device = [(0.1, 0.8)]
    # no span of the name in the traced window (one before it), no counter
    _span("eng_step", 90.0, 90.2, slot_steps=2, slot_capacity=8)
    _span("train_step", 90.0, 90.2)
    assert _read(_ctx(cell, device), metric) is None
    # no traced window at all
    _span("eng_step", 100.0, 100.2, slot_steps=2, slot_capacity=8)
    assert _read(_ctx(cell, device, tracer=None), metric) is None
    # a program that keeps no spans (the parent of the PR that brought
    # them): the readers find nothing and do not raise
    monkeypatch.delattr(tracing, "recorded")
    assert _read(_ctx(cell, device), metric) is None


@pytest.mark.parametrize("cell,metrics", [
    ("rehearse_serve", NEW_SERVE), ("rehearse_ernie_o1", NEW_TRAIN)])
def test_the_rehearsal_prints_the_new_metrics_end_to_end(cell, metrics):
    """``run.py --rehearse-on-cpu --trace 1`` in a process of its own, as
    the driver runs it: the profiler switches the program's spans on, the
    readers find them. A CPU run: the numbers are never device numbers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_TELEMETRY_DIR", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--rehearse-on-cpu",
         "--registry", SPANS_REHEARSAL, "--workload", cell, "--seed", "5",
         "--seconds", "7", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"]
    assert set(metrics) <= set(got), sorted(got)
    for name in metrics:
        assert got[name]["value"] > 0
    if cell == "rehearse_serve":
        assert (got["idle_in_engine_host_pct"]["value"]
                <= got["device_idle_pct.serve"]["value"])
        assert got["slot_occupancy_pct"]["value"] <= 100
        host = got["eng_decode_host_p50_ms"]["value"]
        assert got["eng_decode_upload_p50_ms"]["value"] < host
