"""The block-diffusion engine's host turn, read from the program's own spans
(``inference/engine.py::_step_block``, ``_step_commit``): the five entries
of the SDAR cell on hand-made span trees against values reckoned by hand,
what they say of a program without the new spans, which cells report them,
and the CPU rehearsal of a tiny block-diffusion cell with the five entries
added, end to end (never a device number)."""
import json
import os
import subprocess
import sys

import pytest

import harness
import trace_reduce as tr
from conftest import ROOT
from paddle_tpu.observability import tracing

CELL = "serve_chat_sdar30b_saturated"
SDAR_REHEARSAL = "benchmark/tests/rehearsal/REGISTRY_sdar.json"
NEW = ["block_host_p50_ms", "block_upload_p50_ms", "block_readback_p50_ms",
       "block_commit_p50_ms", "idle_in_block_host_pct"]
BLOCK = ("prep", "upload", "dispatch", "readback", "append")
COMMIT = ("prep", "upload", "dispatch", "readback")


class _Tracer:
    t_start, t_stop = 100.0, 105.0


@pytest.fixture(autouse=True)
def _own_buffer():
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


def _span(name, t0, t1, parent=None):
    sid = f"{name}@{t0}"
    tracing._buffer.append(
        tracing.Recorded(name, t0, t1, "trace", sid, parent, {}))
    return sid


def _parts(prefix, names, parent, t0, ms):
    """Children ``prefix + name`` of ``parent`` back to back from ``t0``,
    ``ms`` milliseconds each; returns where the last ends."""
    t = t0
    for name, d in zip(names, ms):
        _span(prefix + name, t, t + d / 1e3, parent)
        t += d / 1e3
    return t


def _pass(step, t0, ms, name="eng_block_pass", prefix="eng_block_",
          names=BLOCK):
    sp = _span(name, t0, t0 + sum(ms) / 1e3 + 1e-5, step)
    return _parts(prefix, names, sp, t0, ms)


def _commit(step, t0, ms):
    return _pass(step, t0, ms, "eng_block_commit", "eng_commit_", COMMIT)


def _ctx(device=(), tracer=_Tracer):
    """``device``: [(seconds into the trace, seconds)] of device work."""
    red = tr.Reduced(window_s=5.0, busy_s=sum(d for _, d in device),
                     events=[("op", s, d, "op") for s, d in device])
    outcome = harness.Outcome(
        setup_s=1.0, end_to_end={}, attempted=1, failed=0, compared=[],
        counters={}, window=(60.0, 105.0), memory_peak_bytes=0,
        tracer=tracer() if tracer else None)
    return harness.ReadCtx(harness.resolve(CELL, ROOT), outcome, red, None,
                           51.0)


def _read(ctx, metric):
    read, args = harness.load_reader(ctx.cell, metric)
    return read(ctx, **args)


def test_the_five_names_are_the_sdar_cells_alone():
    reg = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in reg["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["better"] == "lower"
    for w in reg["workloads"]:
        got = {m["name"] for m in harness.resolve(w["name"], ROOT).per_layer}
        if w["name"] == CELL:
            assert set(NEW) <= got
            for name in NEW:
                harness.load_reader(harness.resolve(CELL, ROOT), name)
        else:
            assert not got & set(NEW), w["name"]


def test_the_medians_sum_a_passs_parts_and_a_commits():
    # a pass that began before the traced window: left out
    _pass(_span("eng_step", 99.0, 99.5), 99.0, (9, 9, 9, 9, 9))
    # three steps: a round's start (a commit, then the pass), two passes
    s1 = _span("eng_step", 100.10, 100.15)
    t = _commit(s1, 100.10, (0.5, 0.4, 0.6, 10.5))  # 12.0 ms
    _pass(s1, t, (1.0, 2.0, 0.5, 14.0, 1.0))
    s2 = _span("eng_step", 100.20, 100.23)
    _pass(s2, 100.20, (0.8, 2.2, 0.4, 15.0, 1.6))
    s3 = _span("eng_step", 100.30, 100.33)
    _pass(s3, 100.30, (1.2, 2.6, 0.6, 12.0, 1.2))
    s4 = _span("eng_step", 100.40, 100.44)
    _commit(s4, 100.40, (0.7, 0.3, 0.5, 11.5))  # 13.0 ms
    ctx = _ctx()
    # prep + upload + dispatch + append a pass: 4.5, 5.0, 5.6 ms
    assert _read(ctx, "block_host_p50_ms") == pytest.approx(5.0)
    assert _read(ctx, "block_upload_p50_ms") == pytest.approx(2.2)
    assert _read(ctx, "block_readback_p50_ms") == pytest.approx(14.0)
    # the median of two commits, each its four parts summed
    assert _read(ctx, "block_commit_p50_ms") == pytest.approx(12.5)


def test_idle_under_the_host_turn_leaves_the_three_waits_out():
    # one round-start step 0.100-0.140 s into the trace: a commit whose
    # read-back is 0.1025-0.1128, an admit whose prefill's read-back is
    # 0.114-0.115, then a pass whose read-back is 0.1185-0.1325
    s1 = _span("eng_step", 100.100, 100.140)
    _commit(s1, 100.100, (1.0, 1.0, 0.5, 10.3))
    admit = _span("eng_admit", 100.1135, 100.1155, s1)
    _span("eng_prefill_readback", 100.114, 100.115, admit)
    _pass(s1, 100.115, (1.0, 2.0, 0.5, 14.0, 1.0))
    # the device: the commit 0.101-0.1127, the prefill 0.1138-0.1149, the
    # pass 0.116-0.1322
    device = [(0.101, 0.0117), (0.1138, 0.0011), (0.116, 0.0162)]
    # the host outside its waits: 0.100-0.1025, 0.1128-0.114,
    # 0.115-0.1185 and 0.1325-0.140; less the device's work, idle is
    # 0.100-0.101, 0.1128-0.1138, 0.115-0.116 and 0.1325-0.140
    idle = 0.001 + 0.001 + 0.001 + 0.0075
    got = _read(_ctx(device), "idle_in_block_host_pct")
    assert got == pytest.approx(100 * idle / 5.0)
    # at most the device's idle share of the window
    assert got <= 100 * (1 - sum(d for _, d in device) / 5.0)


def test_a_program_without_the_new_spans_reads_what_it_has():
    """The parent of the change that brought the parts: a pass with only
    upload, dispatch and read-back under it and a commit with no children.
    The readers do not raise; a metric whose spans are all missing reports
    nothing; and with no traced window or no buffer, nothing at all."""
    s1 = _span("eng_step", 100.10, 100.15)
    _span("eng_block_commit", 100.10, 100.112, s1)
    sp = _span("eng_block_pass", 100.115, 100.134, s1)
    _parts("eng_block_", ("upload", "dispatch", "readback"), sp, 100.116,
           (2.0, 0.5, 14.0))
    ctx = _ctx([(0.116, 0.0162)])
    assert _read(ctx, "block_commit_p50_ms") is None
    assert _read(ctx, "block_host_p50_ms") == pytest.approx(2.5)
    assert _read(ctx, "idle_in_block_host_pct") > 0
    for metric in NEW:
        assert _read(_ctx([(0.116, 0.0162)], tracer=None), metric) is None
    tracing._buffer.clear()
    for metric in NEW:
        assert _read(ctx, metric) is None


def test_the_rehearsal_prints_the_five_end_to_end(tmp_path):
    """The tiny block-diffusion cell with the five entries added to its
    registry, ``run.py --rehearse-on-cpu --trace 1`` in a process of its
    own: the profiler switches the program's spans on and the readers find
    them. A CPU run: the numbers are never device numbers."""
    reg = harness.load_json(os.path.join(ROOT, SDAR_REHEARSAL))
    cell = reg["workloads"][0]["name"]
    main = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    reg["per_layer"] += [dict(m, workloads=[cell]) for m in main["per_layer"]
                         if m["name"] in NEW]
    path = tmp_path / "REGISTRY.json"
    path.write_text(json.dumps(reg))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_TELEMETRY_DIR", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--rehearse-on-cpu",
         "--registry", str(path), "--workload", cell, "--seed", "2147483911",
         "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    assert all(got[name] > 0 for name in NEW)
    assert got["block_upload_p50_ms"] < got["block_host_p50_ms"]
    assert got["idle_in_block_host_pct"] <= got["device_idle_pct.serve.tput"]
