"""Unified telemetry (docs/OBSERVABILITY.md): the metrics registry, the
env-gated facade + exporters, fleet snapshot merging, instrumented hot
paths (jit dispatch, checkpoints, watchdog, chaos, hapi callbacks), and
the profiler satellites (scheduler step-0 state, summary sorting/units,
load_profiler_result, worker-named exports).

The 2-process end-to-end acceptance run lives in
tests/test_telemetry_fleet.py; this file is in-process."""
import json
import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability.metrics import MetricsRegistry, labelkey_str
from paddle_tpu.observability.fleet import merge_snapshots


# ---------------------------------------------------------------------------
# helpers / fixtures
# ---------------------------------------------------------------------------
@pytest.fixture
def tdir(tmp_path, monkeypatch):
    """Telemetry enabled into a fresh dir, registry reset around the test."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    obs.reset()
    yield tmp_path
    obs.reset()


def _events(tdir, rank=0):
    p = tdir / f"events_rank{rank}.jsonl"
    if not p.exists():
        return []
    return [json.loads(l) for l in p.read_text().splitlines() if l.strip()]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(2, op="get")
    c.inc(3, op="get")
    assert c.value() == 1
    assert c.value(op="get") == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3)
    g.set(1.5)
    g.inc(0.5)
    assert g.value() == 2.0
    assert g.value(rank=9) is None


def test_histogram_bounded_reservoir_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", reservoir=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count() == 100
    s = h.snapshot()["series"][""]
    assert s["count"] == 100 and s["sum"] == sum(range(100))
    assert s["min"] == 0.0 and s["max"] == 99.0 and s["mean"] == 49.5
    # reservoir keeps only the newest 8 observations (92..99)
    assert s["values"] == [float(v) for v in range(92, 100)]
    assert 92.0 <= s["p50"] <= s["p90"] <= s["p99"] <= 99.0


def test_metric_name_convention_enforced():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("BadName")


def test_kind_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    # catalog pins the declared kind (and supplies default help)
    reg2 = MetricsRegistry(catalog={"y_total": ("counter", "y help")})
    with pytest.raises(ValueError):
        reg2.gauge("y_total")
    assert reg2.counter("y_total").help == "y help"


def test_labelkey_is_order_independent():
    reg = MetricsRegistry()
    c = reg.counter("k_total")
    c.inc(1, b="2", a="1")
    c.inc(1, a="1", b="2")
    assert c.value(a="1", b="2") == 2
    snap = c.snapshot()
    assert list(snap["values"]) == [labelkey_str((("a", "1"), ("b", "2")))]


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("foo_total", "total foos").inc(2, op="get")
    reg.gauge("bar").set(1.5)
    h = reg.histogram("baz_seconds")
    h.observe(0.5)
    h.observe(1.5)
    text = reg.to_prometheus()
    assert "# HELP paddle_tpu_foo_total total foos" in text
    assert 'paddle_tpu_foo_total{op="get"} 2' in text
    assert "paddle_tpu_bar 1.5" in text
    assert "# TYPE paddle_tpu_baz_seconds summary" in text
    assert "paddle_tpu_baz_seconds_count 2" in text
    assert "paddle_tpu_baz_seconds_sum 2" in text
    assert 'paddle_tpu_baz_seconds{quantile="0.50"}' in text
    assert "paddle_tpu_baz_seconds_min 0.5" in text
    assert "paddle_tpu_baz_seconds_max 1.5" in text


# ---------------------------------------------------------------------------
# env-gated facade + exporters
# ---------------------------------------------------------------------------
def test_enabled_records_exports_and_logs_events(tdir):
    obs.inc("store_reconnect_total")
    obs.set_gauge("heartbeat_age_seconds", 0.25, rank=0)
    obs.observe("store_op_seconds", 0.01, op="get")
    obs.event("watchdog_start", interval=1.0)
    with obs.timed("checkpoint_save_seconds") as t:
        pass
    assert t.seconds is not None and t.seconds >= 0
    obs.record_compile("train_step", 0.5, signature="sig " * 200)

    path = obs.flush()
    text = open(path).read()
    assert path == str(tdir / "metrics_rank0.prom")
    assert "paddle_tpu_store_reconnect_total 1" in text
    assert 'paddle_tpu_heartbeat_age_seconds{rank="0"} 0.25' in text
    assert 'paddle_tpu_store_op_seconds_count{op="get"} 1' in text

    evs = _events(tdir)
    kinds = [e["kind"] for e in evs]
    assert "watchdog_start" in kinds and "xla_compile" in kinds
    for e in evs:  # every record carries the envelope
        assert {"ts", "kind", "rank", "pid"} <= set(e)
    compile_ev = next(e for e in evs if e["kind"] == "xla_compile")
    assert compile_ev["where"] == "train_step"
    assert len(compile_ev["signature"]) <= 240  # truncated, not unbounded

    assert obs.registry().get("xla_compile_total").value(
        where="train_step") == 1
    snap = obs.snapshot()
    assert snap["rank"] == 0 and "store_op_seconds" in snap["metrics"]


def test_concurrent_flush_is_safe(tdir):
    """The watchdog beat thread and the main thread (fleet_sync / atexit)
    flush in the same process; a pid-only tmp name let the loser of the
    write->rename race hit FileNotFoundError and kill the worker."""
    obs.inc("store_reconnect_total")
    errors = []

    def spin():
        try:
            for _ in range(60):
                obs.flush()
        except Exception as e:  # pragma: no cover - the regression itself
            errors.append(e)

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    prom = (tdir / "metrics_rank0.prom").read_text()
    assert "paddle_tpu_store_reconnect_total" in prom
    assert not [p for p in tdir.iterdir() if ".tmp." in p.name]


def test_disabled_is_inert(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    obs.reset()
    obs.inc("store_reconnect_total")
    obs.observe("store_op_seconds", 0.01, op="get")
    obs.event("watchdog_start", interval=1.0)
    with obs.timed("checkpoint_save_seconds") as t:
        pass
    assert t.seconds is None
    assert obs.flush() is None
    assert obs.registry().get("store_reconnect_total") is None
    assert not any(tmp_path.iterdir())


def _record_metrics():
    obs.observe("train_step_seconds", 0.01)
    obs.inc("xla_compile_total")


def _record_spans():
    with obs.span("train_step"):
        pass
    obs.record_span("train_step", dur_s=0.01)


@pytest.mark.parametrize("record", [_record_metrics, _record_spans],
                         ids=["metrics", "spans"])
def test_disabled_adds_no_measurable_overhead(monkeypatch, record):
    """Acceptance guard: with telemetry off, a recording call must stay a
    single env lookup (a span: that and one static call that asks the
    profiler whether a session records) — no locks, registry writes, or
    file I/O. 20us/call is ~40x the observed cost, loose enough for a
    loaded CI box while still catching any accidental I/O on the disabled
    path."""
    from paddle_tpu.observability import tracing

    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    obs.reset()
    before = len(tracing.recorded())
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        record()
    per_call = (time.perf_counter() - t0) / (2 * n)
    assert per_call < 20e-6, \
        f"disabled telemetry costs {per_call * 1e6:.2f}us per call"
    assert obs.registry().get("train_step_seconds") is None
    assert len(tracing.recorded()) == before


# ---------------------------------------------------------------------------
# fleet merge + single-process sync
# ---------------------------------------------------------------------------
def _snap(rank, step_mean, count=4):
    series = {"count": count, "sum": count * step_mean, "min": step_mean,
              "max": step_mean, "mean": step_mean, "p50": step_mean,
              "p90": step_mean, "p99": step_mean,
              "values": [step_mean] * min(count, 4)}
    return {"rank": rank, "ts": 0.0, "metrics": {
        "train_step_seconds": {"type": "histogram", "help": "",
                               "series": {"": series}},
        "xla_compile_total": {"type": "counter", "help": "",
                              "values": {"where=train_step": 1 + rank}},
        "heartbeat_age_seconds": {"type": "gauge", "help": "",
                                  "values": {f"rank={rank}": 0.1}},
    }}


def test_merge_snapshots_aggregates_and_flags_stragglers():
    doc = merge_snapshots({0: _snap(0, 0.01), 1: _snap(1, 0.02)},
                          world_size=3)
    assert doc["schema"] == 1 and doc["world_size"] == 3
    assert doc["missing_ranks"] == [2]

    agg = doc["aggregate"]["train_step_seconds"][""]
    assert agg["per_rank"] == {"0": 0.01, "1": 0.02}
    assert agg["min"] == 0.01 and agg["max"] == 0.02
    assert agg["min_rank"] == 0 and agg["max_rank"] == 1
    assert abs(agg["mean"] - 0.015) < 1e-12

    cnt = doc["aggregate"]["xla_compile_total"]["where=train_step"]
    assert cnt["per_rank"] == {"0": 1, "1": 2}

    # rank 1 runs 2x the fleet-mean step time -> straggler
    assert len(doc["stragglers"]) == 1
    s = doc["stragglers"][0]
    assert s["rank"] == 1 and s["metric"] == "train_step_seconds"
    assert s["slowdown"] > 1.3
    assert set(doc["ranks"]) == {"0", "1"}


def test_merge_snapshots_no_false_stragglers():
    doc = merge_snapshots({0: _snap(0, 0.01), 1: _snap(1, 0.011)},
                          world_size=2)
    assert doc["stragglers"] == [] and doc["missing_ranks"] == []


def test_merge_weights_straggler_mean_by_sample_count():
    """The straggler fleet mean is weighted by each rank's histogram
    sample count: a nearly-idle rank (2 fast steps against 100-step
    peers) must not drag the mean down and flag healthy ranks."""
    doc = merge_snapshots({0: _snap(0, 0.1, count=100),
                           1: _snap(1, 0.1, count=100),
                           2: _snap(2, 0.01, count=2)}, world_size=3)
    slot = doc["aggregate"]["train_step_seconds"][""]
    # unweighted mean-of-means would be 0.07 and flag ranks 0+1 at the
    # default 1.2x; the sample-weighted mean is the true per-step mean
    want = (100 * 0.1 + 100 * 0.1 + 2 * 0.01) / 202
    assert slot["weighted_mean"] == pytest.approx(want)
    assert doc["stragglers"] == []


def test_merge_skewed_counts_still_flag_real_straggler():
    # a genuine 2x straggler with equal weight stays flagged, and the
    # record carries its sample count + the weighted fleet mean
    doc = merge_snapshots({0: _snap(0, 0.1, count=100),
                           1: _snap(1, 0.1, count=100),
                           2: _snap(2, 0.2, count=100)}, world_size=3)
    assert [s["rank"] for s in doc["stragglers"]] == [2]
    s = doc["stragglers"][0]
    want = (100 * 0.1 + 100 * 0.1 + 100 * 0.2) / 300
    assert s["fleet_mean_seconds"] == pytest.approx(want)
    assert s["samples"] == 100
    assert s["slowdown"] == pytest.approx(0.2 / want)


def test_merge_zero_sample_counts_fall_back_unweighted():
    # snapshots whose series carry no counts (all zero) keep the old
    # unweighted mean instead of dividing by zero
    doc = merge_snapshots({0: _snap(0, 0.01, count=0),
                           1: _snap(1, 0.02, count=0)}, world_size=2)
    slot = doc["aggregate"]["train_step_seconds"][""]
    assert slot["weighted_mean"] == pytest.approx(0.015)
    assert [s["rank"] for s in doc["stragglers"]] == [1]


def test_straggler_threshold_env_override(monkeypatch, capsys):
    from paddle_tpu.observability.fleet import straggler_threshold

    monkeypatch.delenv("PADDLE_TPU_STRAGGLER_FACTOR", raising=False)
    assert straggler_threshold() == 1.2
    monkeypatch.setenv("PADDLE_TPU_STRAGGLER_FACTOR", "1.5")
    assert straggler_threshold() == 1.5
    # <= 1.0 would flag every rank; unparseable is operator error — both
    # diagnose to stderr and fall back rather than poison the merge
    for bad in ("0.5", "1.0", "abc"):
        monkeypatch.setenv("PADDLE_TPU_STRAGGLER_FACTOR", bad)
        assert straggler_threshold() == 1.2
        assert "invalid PADDLE_TPU_STRAGGLER_FACTOR" in capsys.readouterr().err


def test_merge_snapshots_honors_straggler_factor(monkeypatch):
    # rank 1 at 2x fleet mean: flagged at the default 1.2, ignored at 4x
    monkeypatch.setenv("PADDLE_TPU_STRAGGLER_FACTOR", "4.0")
    doc = merge_snapshots({0: _snap(0, 0.01), 1: _snap(1, 0.02)},
                          world_size=2)
    assert doc["stragglers"] == []
    monkeypatch.delenv("PADDLE_TPU_STRAGGLER_FACTOR")
    doc = merge_snapshots({0: _snap(0, 0.01), 1: _snap(1, 0.02)},
                          world_size=2)
    assert [s["rank"] for s in doc["stragglers"]] == [1]


def test_fleet_sync_single_process_writes_locally(tdir, monkeypatch):
    monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
    obs.observe("train_step_seconds", 0.01)
    path = obs.fleet_sync()
    assert path == str(tdir / "fleet_metrics.json")
    doc = json.load(open(path))
    assert doc["world_size"] == 1 and doc["missing_ranks"] == []
    assert "train_step_seconds" in doc["aggregate"]
    # the per-rank prom textfile rides along with every sync
    assert (tdir / "metrics_rank0.prom").exists()


# ---------------------------------------------------------------------------
# instrumented hot paths (in-process)
# ---------------------------------------------------------------------------
def test_train_step_dispatch_instrumentation(tdir):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = TrainStep(model, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 4), np.float32))
    for _ in range(3):
        float(step(x, y))

    reg = obs.registry()
    # 1 compile (the miss), 2 recorded hot steps — the miss step is billed
    # to xla_compile_seconds, never double-counted in train_step_seconds
    assert reg.get("xla_compile_total").value(where="train_step") == 1
    assert reg.get("train_step_seconds").count() == 2
    ev = [e for e in _events(tdir) if e["kind"] == "xla_compile"]
    assert len(ev) == 1 and ev[0]["where"] == "train_step"
    assert ev[0]["seconds"] > 0


def test_checkpoint_save_restore_instrumentation(tdir):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint

    path = str(tdir / "ckpt" / "step_1")
    state = {"w": paddle.to_tensor(np.arange(8, dtype=np.float32))}
    checkpoint.save_state_dict(state, path)
    checkpoint.load_state_dict(path, state)

    reg = obs.registry()
    assert reg.get("checkpoint_save_seconds").count() == 1
    assert reg.get("checkpoint_save_bytes_total").value() > 0
    assert reg.get("checkpoint_restore_seconds").count() == 1
    kinds = [e["kind"] for e in _events(tdir)]
    assert "checkpoint_save" in kinds and "checkpoint_restore" in kinds
    save_ev = next(e for e in _events(tdir) if e["kind"] == "checkpoint_save")
    assert save_ev["path"] == path and save_ev["bytes"] > 0


class _DictStore:
    """In-memory stand-in for the heartbeat TCPStore."""

    def __init__(self):
        self.d = {}

    def set(self, k, v):
        self.d[k] = v if isinstance(v, bytes) else str(v).encode()

    def get(self, k, timeout=None):
        return self.d[k]

    def check(self, k):
        return k in self.d


def test_watchdog_stall_telemetry(tdir):
    """S4: the beat loop exports this rank's own heartbeat-age gauge and a
    stalled peer produces a rank_stalled JSONL diagnosis BEFORE on_stall
    (the default handler os._exit()s, skipping atexit)."""
    from paddle_tpu.runtime.watchdog import HeartbeatWatchdog

    stalled_seen = {}
    done = threading.Event()

    def on_stall(stalled, grace):
        stalled_seen.update(stalled)
        done.set()

    wd = HeartbeatWatchdog(_DictStore(), rank=0, world_size=2,
                           interval=0.05, miss=2, on_stall=on_stall).start()
    try:
        assert done.wait(10), "monitor never declared the silent peer stalled"
    finally:
        wd.stop()
    assert 1 in stalled_seen

    reg = obs.registry()
    assert reg.get("heartbeat_age_seconds").value(rank=0) is not None  # self
    assert reg.get("heartbeat_age_seconds").value(rank=1) is not None  # peer
    assert reg.get("heartbeat_beats_total").value() >= 1
    assert reg.get("watchdog_poll_age_seconds").count(rank=1) >= 1

    evs = _events(tdir)
    assert any(e["kind"] == "watchdog_start" for e in evs)
    st = [e for e in evs if e["kind"] == "rank_stalled"]
    assert st and "1" in st[-1]["stalled"] and st[-1]["monitor_rank"] == 0
    # the beat loop flushes, so the prom file is live mid-run
    assert (tdir / "metrics_rank0.prom").exists()


def test_chaos_fault_records_telemetry(tdir, monkeypatch):
    from paddle_tpu.testing import chaos

    monkeypatch.setenv("PADDLE_CHAOS", "1")
    monkeypatch.setenv("PADDLE_CHAOS_STORE_DROP", "1.0")
    monkeypatch.delenv("PADDLE_RESTART_COUNT", raising=False)
    chaos.reset()
    try:
        assert chaos.store_should_drop()
    finally:
        chaos.reset()
    assert obs.registry().get("chaos_fault_total").value(
        fault="store_drop") == 1
    ev = [e for e in _events(tdir) if e["kind"] == "chaos_fault"]
    assert ev and ev[0]["fault"] == "store_drop" and ev[0]["attempt"] == 0


def test_telemetry_logger_callback(tdir, monkeypatch):
    from paddle_tpu.hapi import callbacks as C

    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e9")
    tl = C.TelemetryLogger()
    tl.set_params({"epochs": 1, "steps": 1})
    tl.on_train_begin()
    tl.on_train_batch_begin(0)
    time.sleep(0.005)
    tl.on_train_batch_end(0, {"loss": 0.5, "batch_size": 16,
                              "step_flops": 2.0e6})
    tl.on_train_end()

    reg = obs.registry()
    assert reg.get("train_tokens_per_second").value() > 0
    assert reg.get("train_flops_per_second").value() > 0
    assert reg.get("train_mfu").value() > 0

    evs = _events(tdir)
    runs = [e for e in evs if e["kind"] == "train_run"]
    assert [e["phase"] for e in runs] == ["begin", "end"]
    step_ev = next(e for e in evs if e["kind"] == "train_step")
    assert step_ev["loss"] == 0.5
    assert step_ev["tokens_per_second"] > 0 and step_ev["mfu"] > 0
    assert (tdir / "metrics_rank0.prom").exists()  # on_train_end flushes


def test_config_callbacks_auto_appends_telemetry_logger():
    from paddle_tpu.hapi import callbacks as C

    lst = C.config_callbacks(verbose=0)
    assert sum(isinstance(c, C.TelemetryLogger) for c in lst.callbacks) == 1
    # an explicit instance is not duplicated
    mine = C.TelemetryLogger()
    lst2 = C.config_callbacks(callbacks=[mine], verbose=0)
    tls = [c for c in lst2.callbacks if isinstance(c, C.TelemetryLogger)]
    assert tls == [mine]


# ---------------------------------------------------------------------------
# profiler satellites (S1-S3)
# ---------------------------------------------------------------------------
def _stubbed(prof):
    prof._start_trace = lambda: setattr(prof, "_tracing", True)
    prof._stop_trace = lambda: setattr(prof, "_tracing", False)
    return prof


def test_profiler_applies_step0_scheduler_state():
    """The step-0 state is applied at start() — with skip_first=1 the first
    step must run CLOSED (pre-fix it silently recorded)."""
    from paddle_tpu import profiler as P

    sched = P.make_scheduler(record=1, skip_first=1)
    prof = _stubbed(P.Profiler(scheduler=sched))
    prof.start()
    for _ in range(3):
        prof.step()
    prof.stop()
    assert prof._state_history == [
        P.ProfilerState.CLOSED,
        P.ProfilerState.RECORD_AND_RETURN,
        P.ProfilerState.RECORD_AND_RETURN,
        P.ProfilerState.RECORD_AND_RETURN,
    ]


def test_profiler_state_sequence_matches_scheduler():
    from paddle_tpu import profiler as P

    sched = P.make_scheduler(closed=1, ready=1, record=2, repeat=1)
    prof = _stubbed(P.Profiler(scheduler=sched))
    prof.start()
    assert not prof._tracing  # step 0 is CLOSED, not silently recording
    for _ in range(5):
        prof.step()
    prof.stop()
    S = P.ProfilerState
    assert prof._state_history == [
        S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN,
        S.CLOSED, S.CLOSED,
    ]


def test_summary_sorted_by_and_time_unit(capsys):
    from paddle_tpu import profiler as P

    P.reset_host_events()
    try:
        for _ in range(3):
            with P.RecordEvent("aa_fast"):
                pass
        with P.RecordEvent("bb_slow"):
            pass
        # inject the durations: a sleep here races the other xdist workers
        # (a descheduled "fast" event has outlasted a 20 ms sleep)
        P._host_events["aa_fast"][1] = 3e-3
        P._host_events["bb_slow"][1] = 2e-2

        prof = P.Profiler(timer_only=True)
        prof.start()
        prof.step()
        prof.stop()

        by_total = prof.summary(sorted_by="total")
        assert by_total.index("bb_slow") < by_total.index("aa_fast")
        by_calls = prof.summary(sorted_by=P.SortedKeys.Calls)
        assert by_calls.index("aa_fast") < by_calls.index("bb_slow")
        by_name = prof.summary(sorted_by="name")
        assert by_name.index("aa_fast") < by_name.index("bb_slow")
        by_avg = prof.summary(sorted_by="avg")
        assert by_avg.index("bb_slow") < by_avg.index("aa_fast")

        assert "total us" in prof.summary(time_unit="us")
        assert "total s" in prof.summary(time_unit="s")
        with pytest.raises(ValueError):
            prof.summary(sorted_by="bogus")
        with pytest.raises(ValueError):
            prof.summary(time_unit="minutes")

        P.reset_host_events()
        assert "aa_fast" not in prof.summary()
    finally:
        P.reset_host_events()
        capsys.readouterr()


def test_load_profiler_result(tmp_path):
    from paddle_tpu import profiler as P

    doc = {"traceEvents": [
        {"name": "op_a", "ph": "X", "ts": 10, "dur": 5},
        {"name": "op_a", "ph": "X", "ts": 20, "dur": 7},
        {"name": "op_b", "ph": "X", "ts": 30, "dur": 2},
    ]}
    (tmp_path / "host_trace.json").write_text(json.dumps(doc))

    for target in (str(tmp_path), str(tmp_path / "host_trace.json")):
        res = P.load_profiler_result(target)
        assert len(res) == 3
        assert res.names() == ["op_a", "op_b"]
        assert res.count("op_a") == 2
        assert res.total_duration("op_a") == 12.0
        assert res.time_range() == (10, 32)

    named = tmp_path / "named"
    named.mkdir()
    (named / "w3_host_trace.json").write_text(json.dumps(doc))
    assert P.load_profiler_result(str(named)).count("op_b") == 1

    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        P.load_profiler_result(str(empty))


def test_export_chrome_tracing_worker_name(tmp_path, monkeypatch):
    from paddle_tpu import profiler as P

    handler = P.export_chrome_tracing(str(tmp_path), worker_name="w7")
    prof = P.Profiler(on_trace_ready=handler)
    # the config is live from construction (the host trace is written in
    # _stop_trace, BEFORE on_trace_ready fires)
    assert prof._export_dir == str(tmp_path)
    assert prof._worker_name == "w7"

    monkeypatch.setattr(P._runtime, "trace_stop", lambda: None)
    monkeypatch.setattr(
        P._runtime, "trace_export",
        lambda: [{"name": "x", "ph": "X", "ts": 0, "dur": 1}])
    prof._stop_trace()
    res = P.load_profiler_result(str(tmp_path))
    assert res.path.endswith("w7_host_trace.json")
    assert res.count("x") == 1
