"""The program's own spans (docs/OBSERVABILITY.md section 8): off unless
somebody is tracing, one tree a ``DecodeEngine.step()`` and a warm
``TrainStep`` call while a profiler session records, on the profiler's own
timeline, and ``engine.last_step`` whether or not anybody traces."""
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.jit import TrainStep
from paddle_tpu.observability import tracing
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

VOCAB = 61
DECODE_PARTS = ["eng_decode_prep", "eng_decode_upload", "eng_decode_dispatch",
                "eng_decode_readback", "eng_decode_append"]
PREFILL_PARTS = ["eng_prefill_prep", "eng_prefill_dispatch",
                 "eng_prefill_readback"]
TRAIN_PARTS = ["train_gather", "train_dispatch", "train_writeback"]


@pytest.fixture(autouse=True)
def _nobody_traces(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


@pytest.fixture(scope="module")
def engine():
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    # as tests/test_decode_engine.py: no group or mesh of an earlier test
    prev, prev_mesh = get_hybrid_communicate_group(), _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    try:
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        m.eval()
        eng = DecodeEngine(m, EngineConfig(
            num_slots=2, max_length=64, page_size=8, prompt_buckets=(16,)))
        eng.warmup()
        yield eng
    finally:
        set_hybrid_communicate_group(prev)
        _mesh.set_global_mesh(prev_mesh)


@pytest.fixture(scope="module")
def train_step():
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(
        model, lambda m, x, y: nn.functional.cross_entropy(m(x), y), opt)
    rng = np.random.default_rng(0)
    batch = (rng.standard_normal((8, 16)).astype("float32"),
             rng.integers(0, 4, (8,)).astype("int64"))
    step(*batch)  # the miss: a 'compile' span's, not a train_step
    return step, batch


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int64)


def _children(rows, root):
    return [r for r in rows if r.parent_id == root.span_id]


def _assert_tree(rows, root, names):
    """``root``'s children are ``names`` in order, each inside the one
    before's end and the root, and together no longer than the root."""
    kids = sorted(_children(rows, root), key=lambda r: r.t0)
    assert [k.name for k in kids] == names
    t = root.t0
    for k in kids:
        assert t <= k.t0 <= k.t1 <= root.t1
        assert k.trace_id == root.trace_id
        t = k.t1
    assert sum(k.t1 - k.t0 for k in kids) <= root.t1 - root.t0


# -- off -------------------------------------------------------------------


def test_nobody_traces_and_the_hot_paths_leave_the_buffer_empty(
        engine, train_step):
    assert not tracing.active()
    engine.submit(_prompt(5, 1), max_new_tokens=3)
    while engine.step():
        pass
    step, batch = train_step
    step(*batch)
    with profiler.RecordEvent("user_region"):
        pass
    assert tracing.recorded() == []


def test_the_gauges_scan_nothing_unless_telemetry_is_on(engine, tmp_path,
                                                       monkeypatch):
    """The four gauges' arguments cost a scan of the running requests and
    of the pool's refcounts: only where telemetry is on. The peaks that
    ``stats()`` reports are kept either way."""
    from paddle_tpu import observability as _obs

    scans = []
    real = engine.pool.shared_pages
    monkeypatch.setattr(engine.pool, "shared_pages",
                        lambda: scans.append(1) or real())
    engine.peak_running = engine.peak_pages_in_use = 0
    engine.submit(_prompt(5, 3), max_new_tokens=3)
    while engine.step():
        pass
    assert scans == []
    assert engine.stats()["peak_running"] == 1
    assert engine.stats()["peak_pages_in_use"] >= 1
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    _obs.reset()
    try:
        engine.submit(_prompt(5, 4), max_new_tokens=3)
        while engine.step():
            pass
        assert scans
        gauges = _obs.snapshot()["metrics"]
        assert gauges["serving_kv_pages_shared"]["type"] == "gauge"
        assert gauges["serving_batch_occupancy"]["values"]
    finally:
        _obs.reset()
        tracing._buffer.clear()


def test_active_follows_the_profiler_and_the_telemetry_directory(
        tmp_path, monkeypatch):
    assert not tracing.active()
    with jax.profiler.trace(str(tmp_path / "p")):
        assert tracing.active()
    assert not tracing.active()
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "t"))
    assert tracing.active()


# -- on: the trees ------------------------------------------------------------


def test_each_engine_step_is_one_tree_on_the_profilers_timeline(
        engine, tmp_path):
    rid = engine.submit(_prompt(11, 2), max_new_tokens=4)
    with jax.profiler.trace(str(tmp_path)):
        t_from = time.perf_counter()
        steps = 0
        while engine.step():
            steps += 1
        t_to = time.perf_counter()
    rows = tracing.recorded(t_from, t_to)
    roots = [r for r in rows if r.name == "eng_step"]
    # the last call finds the engine idle, and leaves nothing
    assert len(roots) == steps == 3
    assert all(r.parent_id is None for r in roots)
    assert len({r.trace_id for r in roots}) == len(roots)
    first = roots[0]
    _assert_tree(rows, first, ["eng_admit"] + DECODE_PARTS)
    admit = _children(rows, first)[0]
    _assert_tree(rows, admit, PREFILL_PARTS)
    assert admit.attrs["rid"] == rid and admit.attrs["admitted"]
    assert admit.attrs["prompt_len"] == 11 and admit.attrs["bucket"] == 16
    assert admit.attrs["cached_len"] == 0 and admit.attrs["queue_s"] > 0
    assert all(k.attrs["rid"] == rid for k in _children(rows, admit))
    assert first.attrs["running"] == 1 and first.attrs["num_slots"] == 2
    assert first.attrs["waiting"] == 0
    assert first.attrs["context_tokens"] == 12
    assert first.attrs["emitted"] == {rid: 2}
    for root in roots[1:]:
        _assert_tree(rows, root, DECODE_PARTS)
        assert root.attrs["emitted"] == {rid: 1}
    # running totals: one slot of two advanced in each of this run's passes
    assert (roots[-1].attrs["slot_steps"] - first.attrs["slot_steps"]
            == len(roots) - 1)
    assert (roots[-1].attrs["slot_capacity"] - first.attrs["slot_capacity"]
            == 2 * (len(roots) - 1))
    assert (first.attrs["slot_steps"], first.attrs["slot_capacity"]) == (
        engine.slot_steps - 2, 2 * (engine.decode_steps - 2))
    # and of page slots: the running slot sits at positions 11, 12, 13 of
    # pages of 8 (two live page slots), the idle one reads one, of 2 x 8
    assert (roots[-1].attrs["kv_pages_live"] - first.attrs["kv_pages_live"]
            == 3 * (len(roots) - 1))
    assert (roots[-1].attrs["kv_pages_capacity"]
            - first.attrs["kv_pages_capacity"] == 16 * (len(roots) - 1))
    assert roots[-1].attrs["kv_pages_live"] == engine.kv_pages_live
    # the same spans lie in the .xplane.pb, on a host plane
    files = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert files
    host_events = {}
    for plane in jax.profiler.ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host_events[ev.name] = host_events.get(ev.name, 0) + 1
    assert host_events.get("eng_step") == len(roots)
    assert host_events.get("eng_decode_readback") == steps


def test_a_warm_train_step_is_one_tree_on_the_profilers_timeline(
        train_step, tmp_path):
    step, batch = train_step
    with jax.profiler.trace(str(tmp_path)):
        step(*batch)
        step(*batch)
        with profiler.RecordEvent("user_region"):
            pass
    rows = tracing.recorded()
    roots = [r for r in rows if r.name == "train_step"]
    assert len(roots) == 2
    for root in roots:
        assert root.parent_id is None
        _assert_tree(rows, root, TRAIN_PARTS)
    # a user's annotation goes through the same primitive
    assert [r.name for r in rows if r.parent_id is None][-1] == "user_region"
    files = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = {ev.name
             for plane in jax.profiler.ProfileData.from_file(files[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"train_step", "user_region", *TRAIN_PARTS} <= names


def test_the_jsonl_sink_drains_the_same_records(train_step, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    step, batch = train_step
    step(*batch)
    rows = tracing.recorded()
    on_disk = tracing.load_spans(str(tmp_path))
    assert [r.name for r in rows] == TRAIN_PARTS + ["train_step"]
    assert [(s["name"], s["span_id"], s["parent_id"]) for s in on_disk] == [
        (r.name, r.span_id, r.parent_id) for r in rows]
    for s, r in zip(on_disk, rows):
        assert s["dur_s"] == pytest.approx(r.t1 - r.t0, abs=1e-8)
    assert tracing.validate_trees(on_disk) == []


def test_a_trees_spans_reach_the_sink_with_their_root(tmp_path, monkeypatch):
    """One append a tree, not one a span: what finishes inside a ``with
    span(...)`` waits for the root; a span outside any is written at once."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    with tracing.span("eng_step"):
        with tracing.span("eng_decode_prep"):
            pass
        tracing.record_span("srv_prefill", dur_s=0.01)
        assert tracing.load_spans(str(tmp_path)) == []
        assert len(tracing.recorded()) == 2  # the buffer does not wait
    assert [s["name"] for s in tracing.load_spans(str(tmp_path))] == [
        "eng_decode_prep", "srv_prefill", "eng_step"]
    tracing.end_span(tracing.start_span("srv_queue"))
    assert tracing.load_spans(str(tmp_path))[-1]["name"] == "srv_queue"


def test_an_idle_engine_polled_with_the_sink_on_writes_nothing(
        engine, tmp_path, monkeypatch):
    """serving/worker.py polls ``step()`` every 5 ms while idle."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    assert tracing.active()
    for _ in range(50):
        assert engine.step() is False
    assert tracing.recorded() == []
    assert tracing.load_spans(str(tmp_path)) == []
    assert os.listdir(str(tmp_path)) == []


def test_a_long_tree_does_not_hold_its_lines_for_ever(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    with tracing.span("eng_step"):
        for _ in range(tracing._HELD_MAX - 1):
            tracing.record_span("srv_prefill", dur_s=0.0)
        assert tracing.load_spans(str(tmp_path)) == []
        tracing.record_span("srv_prefill", dur_s=0.0)
        assert len(tracing.load_spans(str(tmp_path))) == tracing._HELD_MAX
    assert len(tracing.load_spans(str(tmp_path))) == tracing._HELD_MAX + 1


def test_record_event_is_never_a_parent_on_the_stack(train_step, tmp_path,
                                                     monkeypatch):
    """``begin``/``end`` is no ``with``: a region left open for an epoch, or
    ended out of order, must not hold the spans under it back from the sink
    nor become their parent."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    step, batch = train_step
    epoch, inner = profiler.RecordEvent("epoch"), profiler.RecordEvent("inner")
    epoch.begin()
    inner.begin()
    step(*batch)
    # the step's tree is a root of its own, and on disk while the region runs
    on_disk = tracing.load_spans(str(tmp_path))
    assert [s["name"] for s in on_disk] == TRAIN_PARTS + ["train_step"]
    assert on_disk[-1]["parent_id"] is None
    epoch.end()  # out of order
    inner.end()
    assert tracing._stack() == []
    step(*batch)
    on_disk = tracing.load_spans(str(tmp_path))
    assert [s["name"] for s in on_disk[4:]] == [
        "epoch", "inner"] + TRAIN_PARTS + ["train_step"]
    assert all(s["parent_id"] is None for s in on_disk
               if s["name"] in ("epoch", "inner", "train_step"))
    assert tracing.validate_trees(on_disk) == []


def test_a_span_exited_out_of_order_leaves_no_stale_parent(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        a, b = tracing.span("eng_step"), tracing.span("eng_admit")
        a.__enter__()
        b.__enter__()
        a.__exit__(None, None, None)
        b.__exit__(None, None, None)
        assert tracing._stack() == []
        with tracing.span("eng_step") as root:
            pass
    assert tracing.recorded()[-1].span_id == root.span_id
    assert tracing.recorded()[-1].parent_id is None


def test_recorded_keeps_to_the_asked_interval():
    a = tracing.Recorded("a", 1.0, 2.0, "t", "s1", None, {})
    b = tracing.Recorded("b", 3.0, 4.0, "t", "s2", None, {})
    tracing._buffer.extend([a, b])
    assert tracing.recorded() == [a, b]
    assert tracing.recorded(2.5) == [b]
    assert tracing.recorded(None, 2.0) == [a]
    assert tracing.recorded(1.5, 3.5) == []


# -- the step's report, always on ------------------------------------------------


def test_last_step_agrees_with_the_request_table_on_every_step(engine):
    """Mixed admit / decode run: three requests over two slots, so the
    third is admitted only when a slot frees."""
    rids = [engine.submit(_prompt(n, 10 + n), max_new_tokens=k)
            for n, k in ((9, 3), (13, 6), (6, 4))]
    seen = {rid: [] for rid in rids}
    status = {rid: "waiting" for rid in rids}
    admitted, finished = [], []
    while engine.step():
        rep = engine.last_step
        for rid, toks in rep.tokens.items():
            seen[rid].extend(toks)
        admitted += rep.admitted
        finished += rep.finished
        for rid in rep.admitted:
            assert status[rid] == "waiting"
            status[rid] = "running"
        for rid in rep.finished:
            assert status[rid] == "running"
            status[rid] = "done"
        for rid in rids:
            req = engine._requests[rid]
            assert seen[rid] == req.tokens
            assert status[rid] == req.status
        assert set(rep.tokens) <= set(rids)
    assert admitted == rids and sorted(finished) == rids
    assert [len(seen[r]) for r in rids] == [3, 6, 4]
    assert engine.last_step.tokens == {}  # the idle call did nothing
    assert not tracing.recorded()


def test_kv_page_counts_follow_the_positions_of_each_pass(engine):
    """``kv_pages_live`` is what the paged kernel has to read: a slot's
    page slots up to its last query row's, one for a slot that sits the
    pass out; ``kv_pages_capacity`` is what the tables hold."""
    before = engine.stats()
    passes0 = engine.decode_steps
    engine.submit(_prompt(9, 4), max_new_tokens=6)  # positions 9 .. 13
    engine.submit(_prompt(15, 5), max_new_tokens=3)  # positions 15, 16
    while engine.step():
        pass
    after = engine.stats()
    live = sum(p // 8 + 1 for p in (9, 10, 11, 12, 13, 15, 16))
    idle = 3  # the second slot, once the shorter answer has left
    assert after["kv_pages_live"] - before["kv_pages_live"] == live + idle
    assert (after["kv_pages_capacity"] - before["kv_pages_capacity"]
            == (engine.decode_steps - passes0) * 2 * 8)
    assert engine.decode_steps - passes0 == 5
    # a verify pass of k + 1 rows reaches k tokens further, never past the
    # table's width
    live0 = engine.kv_pages_live
    engine._count_kv_pages(np.array([0, 13, 63]), 4)
    assert engine.kv_pages_live - live0 == 1 + 3 + 8
    engine.kv_pages_live = live0


def test_kv_block_pages_are_the_page_slots_of_the_walks_blocks(
        engine, monkeypatch, tmp_path):
    """``kv_block_pages`` is what the paged kernel's walk takes for the
    same passes: whole blocks of the kernel's own pages a block for the
    pass's shapes (here 2, through a cap of 16 keys on pages of 8; the
    tiny model's 16 would make every slot one block as wide as its
    table), never past the table; ``eng_step`` carries it."""
    from paddle_tpu.ops.pallas import paged_attention as pa_kernel

    monkeypatch.setattr(pa_kernel, "_BLOCK_KEYS", 16)
    engine._pages_per_block.clear()
    try:
        ppb = pa_kernel.block_shape(1, 4, 4, 8, 8, 4, False)[1]
        assert ppb == 2
        before = engine.stats()
        engine.submit(_prompt(9, 4), max_new_tokens=6)   # positions 9 .. 13
        engine.submit(_prompt(15, 5), max_new_tokens=4)  # 15, 16, 17
        with jax.profiler.trace(str(tmp_path)):
            while engine.step():
                pass
        after = engine.stats()
        live = [p // 8 + 1 for p in (9, 10, 11, 12, 13, 15, 16, 17)]
        live += [1, 1]  # the second slot, once the shorter answer has left
        assert after["kv_pages_live"] - before["kv_pages_live"] == sum(live)
        walked = sum(-(-n // ppb) * ppb for n in live)
        assert walked == 2 * 6 + 2 * 4 + 2 * 2
        assert after["kv_block_pages"] - before["kv_block_pages"] == walked
        assert (after["kv_pages_live"] <= after["kv_block_pages"]
                <= after["kv_pages_capacity"])
        last = [r for r in tracing.recorded() if r.name == "eng_step"][-1]
        assert last.attrs["kv_block_pages"] == engine.kv_block_pages
        # a verify pass of k + 1 rows has its own pages a block, and a
        # block never counts page slots that the table does not have
        blocks0 = engine.kv_block_pages
        engine._pages_per_block[4] = 3
        engine._count_kv_pages(np.array([0, 13, 63]), 4)
        assert engine.kv_block_pages - blocks0 == 3 + 3 + 8
        engine.kv_block_pages = blocks0
        engine.kv_pages_live = after["kv_pages_live"]
    finally:
        engine._pages_per_block.clear()


# -- names inside the compiled programs --------------------------------------------


def test_op_scopes_gives_a_fusion_its_owner(train_step, engine):
    step, batch = train_step
    text = step._compiled_for(*batch).as_text()
    scopes = profiler.op_scopes(text)
    owners = set(scopes.values())
    assert {"optimizer_update", "jvp(forward_loss)",
            "transpose(jvp(forward_loss))"} <= owners
    fusions = [n for n in scopes if "fusion" in n]
    assert fusions and any(scopes[n] == "optimizer_update" for n in fusions)
    # the text's own op_name says the same of each
    for name, owner in scopes.items():
        if owner == "optimizer_update":
            assert re.search(rf'%?{re.escape(name)} = .*op_name="[^"]*'
                             r'/optimizer_update/', text)
    decode = set(profiler.op_scopes(engine.program_text("decode")).values())
    assert {"embed", "qkv", "kv_write", "attend", "attn_out", "mlp",
            "lm_head", "sample"} <= decode


def test_op_scopes_on_plain_text():
    text = (
        '  %multiply_add_fusion.3 = f32[2]{0} fusion(f32[2]{0} %a), '
        'kind=kLoop, calls=%c, metadata={op_name="jit(step)/'
        'optimizer_update/add" source_file="x.py" source_line=1}\n'
        '  ROOT %exp.1 = f32[] exponential(f32[] %a), metadata={op_name='
        '"jit(step)/jvp(forward_loss)/jit(log_softmax)/exp"}\n'
        '  %lr.1 = f32[] parameter(0), metadata={op_name="lr"}\n'
        '  %copy.2 = f32[2]{0} copy(f32[2]{0} %a)\n')
    assert profiler.op_scopes(text) == {
        "multiply_add_fusion.3": "optimizer_update",
        "exp.1": "jvp(forward_loss)"}
