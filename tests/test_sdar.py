"""SDAR, block diffusion with routed experts (text/models/sdar.py): program
against the plain reference (benchmark/reference/sdar.py, which imports
nothing of the program) on seeded weights at a tiny size.

(1) The model's own forward is the reference's clean stream. (2) Served:
``DecodeEngine``'s prefill, block passes and commits against the reference
at every generated position, in the state of the pass that unmasked it (the
engine records which), under each unmasking rule, on both attention paths a
CPU runs; a prompt whose tail opens the first block, the mask token's id as
a prompt token, a shared prefix. (3) The expert layer of one rank: the
parts that all expert ranges give add up to the uncut reference layer. (4)
Both kernels' block horizon against a plain mask at 8 kv groups. (5) What a
block engine refuses. (6) The block engine's span tree while a profiler
records (docs/OBSERVABILITY.md section 8), nothing while nobody traces, and
the program's spans mapped onto their profiler events through the
benchmark tracer's one clock anchor.

Tolerances: the program runs in float32 and the reference in float32 at
``highest`` precision, so they differ by summation order alone, ~1e-6 on
logits of size ~1: 1e-4 leaves that a hundredfold and is passed by a wide
margin by the same program in bfloat16 (test_bfloat16_program_is_outside).
"""
import glob
import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.core import Tensor
from paddle_tpu.framework.op import raw
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.incubate.moe import count_experts, routed_experts
from paddle_tpu.nn import functional as F
from paddle_tpu.observability import tracing
from paddle_tpu.ops.pallas.paged_attention import paged_attention
from paddle_tpu.ops.pallas.prefill_attention import prefill_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

#: hidden 64, 4 heads of 16 on 2 kv heads, 8 experts of 32, top 2, vocab
#: 128 (the mask token 127), 2 layers, blocks of 4 over 4 passes
TINY = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
            decoder_sparse_step=1, mlp_only_layers=[],
            max_position_embeddings=128, rms_norm_eps=1e-6,
            rope_theta=1000000, attention_bias=False,
            tie_word_embeddings=False, block_length=4, denoise_steps=4,
            mask_token_id=127, remasking="sequential",
            confidence_threshold=0.9, dtype="float32")
MASK = TINY["mask_token_id"]
TOL = 1e-4


def _bench_module(*parts):
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def _no_ambient_mesh():
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        get_hybrid_communicate_group, set_hybrid_communicate_group)

    prev, prev_mesh = get_hybrid_communicate_group(), _mesh.get_global_mesh()
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    yield
    set_hybrid_communicate_group(prev)
    _mesh.set_global_mesh(prev_mesh)


@pytest.fixture(scope="module")
def bench():
    return (_bench_module("archs", "sdar.py"),
            _bench_module("reference", "sdar.py"), _bench_module("weights.py"))


@pytest.fixture(scope="module")
def made(bench):
    """``made(**cfg)`` -> (model with the seed's weights, the reference's
    stacked copy of them, the reference's keyword arguments)."""
    arch, _, weights = bench
    cache = {}

    def make(dtype="float32", **over):
        key = (dtype,) + tuple(sorted(over.items()))
        if key not in cache:
            cfg = dict(TINY, **over)
            model, names = arch.serve_program(cfg)
            model = model.astype(dtype)
            model.eval()
            w = weights.make(arch.weight_spec(cfg, stacked=False), 11, dtype)
            missing, unexpected = model.set_state_dict(
                {names[k]: v for k, v in w.items()})
            assert not missing and not unexpected
            stacked = weights.make(arch.weight_spec(cfg, stacked=True), 11,
                                   "float32")
            cache[key] = model, stacked, arch.reference_args(cfg)
        return cache[key]

    return make


def _ids(n, seed=5):
    return np.random.default_rng(seed).integers(0, MASK, n).astype(np.int32)


# -- (1) the model's own forward ----------------------------------------------


def test_forward_is_the_references_clean_stream(bench, made):
    _, ref, _ = bench
    model, w, kw = made()
    ids = _ids(22)
    got = raw(model(Tensor(jnp.asarray([ids]))))[0]
    want = ref.logits_in_order(w, jnp.asarray(ids),
                               jnp.full(len(ids), -1, jnp.int32), copies=1,
                               **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)


# -- (2) served: prefill, block passes and commits ----------------------------


def _engine(model, kernel="einsum", **kw):
    cfg = dict(num_slots=2, max_length=64, page_size=8, min_bucket=8,
               attn_kernel=kernel, kv_dtype="f32")
    cfg.update(kw)
    return DecodeEngine(model, EngineConfig(**cfg))


def _serve(eng, prompts, n, **sampling):
    """Run ``prompts`` to completion; returns their request ids and, a
    request, {position: the engine's logits row in the pass that unmasked
    it}."""
    rids = [eng.submit(p, max_new_tokens=n, seed=7 + i, **sampling)
            for i, p in enumerate(prompts)]
    rows = {r: {} for r in rids}
    b = eng.adapter.block_length
    while eng._running or eng._waiting:
        slots = {r.req_id: s for s, r in eng._running.items()}
        seen = {r: set(eng._requests[r].unmask_pass) for r in rids}
        eng.step()
        lg = None
        for r in rids:
            req = eng._requests[r]
            for p in set(req.unmask_pass) - seen[r]:
                lg = np.asarray(eng._last_logits) if lg is None else lg
                rows[r][p] = lg[slots.get(r, req.slot), p % b]
    return rids, rows


def _state(req, n, b):
    """ids and unmask order over the request's blocks: the prompt and
    served tokens, and past the budget in the last block what the engine
    unmasked there (position still masked: order past every pass)."""
    p0 = len(req.prompt)
    end = -(-(p0 + n) // b) * b
    ids = np.zeros(end, np.int32)
    ids[:p0], ids[p0:p0 + n] = req.prompt, req.tokens
    order = np.full(end, -1, np.int32)
    for q in range(p0, end):
        order[q] = req.unmask_pass.get(q, 99)
        if q >= p0 + n and q in req.unmask_pass:
            ids[q] = req.block.tokens[q % b]
    copies = int(order[order < 99].max()) + 2
    return ids, np.where(order == 99, copies - 1, order), copies


PROMPTS = [np.r_[_ids(9, 1), MASK, _ids(1, 2)],  # tail of 3 opens a block
           _ids(8, 3),                           # whole blocks only
           np.r_[_ids(16, 4), _ids(5, 6)]]       # shares 2 pages with #4
SHARED = np.r_[_ids(16, 4), _ids(7, 8)]


@pytest.mark.parametrize("rule,kernel", [
    ("sequential", "einsum"), ("sequential", "pallas"),
    ("low_confidence_static", "einsum"), ("low_confidence_dynamic", "pallas")])
def test_served_logits_are_the_references_in_the_state_of_each_pass(
        bench, made, rule, kernel):
    """Every generated position's logits, in the pass that unmasked it,
    are the reference's in the state the engine recorded; greedy tokens
    are their argmax; exactly ``max_new_tokens`` a request."""
    _, ref, _ = bench
    # dynamic at a threshold a 128-token vocabulary reaches, so that a pass
    # unmasks more than its share
    model, w, kw = made(remasking=rule, confidence_threshold=0.03)
    eng = _engine(model, kernel)
    n, b = 9, 4
    rids, rows = _serve(eng, PROMPTS + [SHARED], n)
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 16
    assert st["commit_passes"] > 0 and st["block_length"] == b
    assert 0 < st["experts_touched"] <= st["experts_capacity"]
    more = 0
    for rid in rids:
        req = eng._requests[rid]
        assert len(req.tokens) == n
        ids, order, copies = _state(req, n, b)
        want = np.asarray(ref.logits_in_order(
            w, jnp.asarray(ids), jnp.asarray(order), copies=copies, **kw))
        got = rows[rid]
        assert set(range(len(req.prompt), len(req.prompt) + n)) <= set(got)
        for q, row in got.items():
            np.testing.assert_allclose(row, want[q], rtol=TOL, atol=TOL,
                                       err_msg=f"rid {rid} position {q}")
        for q in range(len(req.prompt), len(req.prompt) + n):
            assert ids[q] == np.argmax(got[q])  # greedy
        passes = np.bincount(order[order >= 0])
        more += int((passes > 1).any())
    if rule == "sequential":
        # the pass that unmasks p is its offset less the known tail: p's
        # state follows from p alone, what reference.logits recomputes
        req = eng._requests[rids[0]]
        ids, order, copies = _state(req, n, b)
        assert [order[q] for q in range(11, 20)] == [0, 0, 1, 2, 3, 0, 1, 2,
                                                     3]
        seq = np.asarray(ref.logits(w, jnp.asarray(ids), **kw))
        full = np.asarray(ref.logits_in_order(
            w, jnp.asarray(ids), jnp.asarray(order), copies=copies, **kw))
        np.testing.assert_allclose(seq[10:19], full[11:20], rtol=TOL,
                                   atol=TOL)
    elif rule == "low_confidence_dynamic":
        assert more  # some pass took every position above the threshold


def test_a_shared_prefix_and_the_mask_id_in_a_prompt_change_no_token(made):
    model, _, _ = made()
    alone = []
    for p in PROMPTS[2:] + [SHARED]:
        eng = _engine(model)
        rid = eng.submit(p, max_new_tokens=6)
        eng.run()
        alone.append(eng.result(rid).tolist())
    eng = _engine(model)
    rids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS[2:] + [SHARED]]
    eng.run()
    assert [eng.result(r).tolist() for r in rids] == alone
    assert eng.stats()["prefix_hit_tokens"] == 16
    # the mask token's id as the known tail of the first block is a token
    eng = _engine(model)
    p = np.r_[_ids(9, 1), MASK]
    rid = eng.submit(p, max_new_tokens=3)
    eng.run()
    assert eng.result(rid)[:10].tolist() == p.tolist()
    assert len(eng.result(rid)) == 13


def test_bfloat16_program_is_outside_the_tolerance(bench, made):
    """The same program one precision below the stated float32 fails the
    comparison above: the tolerance parts the two."""
    _, ref, _ = bench
    model, w, kw = made(dtype="bfloat16")
    eng = _engine(model, kv_dtype="bf16")
    rids, rows = _serve(eng, PROMPTS[:1], 9)
    req = eng._requests[rids[0]]
    ids, order, copies = _state(req, 9, 4)
    want = np.asarray(ref.logits_in_order(
        w, jnp.asarray(ids), jnp.asarray(order), copies=copies, **kw))
    err = max(np.abs(r - want[q]).max() for q, r in rows[rids[0]].items())
    assert err > 10 * TOL


# -- (3) the expert layer of one rank -----------------------------------------


def test_expert_ranges_add_up_to_the_uncut_reference_layer(bench):
    _, ref, weights = bench
    h, f, e, k = 64, 32, 8, 2
    spec = {"router.w": ((h, e), 0.0, 0.3), "gate_up.w": ((e, h, 2 * f),
            0.0, 0.2), "down.w": ((e, f, h), 0.0, 0.2)}
    lw = weights.make(spec, 3, "float32")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((24, h)),
                    jnp.float32)
    want = np.asarray(ref.experts(x, lw, k))
    parts, touched = [], []
    for lo, hi in [(0, 3), (3, 4), (4, 8)]:
        with count_experts() as counts:
            parts.append(np.asarray(routed_experts(
                x, lw["router.w"], lw["gate_up.w"][lo:hi],
                lw["down.w"][lo:hi], k, (lo, hi))))
        touched += counts
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    whole = routed_experts(x, lw["router.w"], lw["gate_up.w"], lw["down.w"],
                           k)
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-4, atol=1e-5)
    # every expert is some range's, and 24 tokens x 2 reach all 8 here
    assert sum(int(c) for c in touched) == 8
    assert all(np.abs(p).max() > 0 for p in parts)
    with pytest.raises(ValueError, match="expert_range"):
        routed_experts(x, lw["router.w"], lw["gate_up.w"][:3],
                       lw["down.w"][:3], k, (0, 4))


# -- (4) both kernels' block horizon ------------------------------------------


def _plain_block_mask(qpos, kpos, block):
    return kpos // block <= qpos // block


@pytest.mark.parametrize("block", [1, 4])
def test_paged_kernel_block_horizon_against_a_plain_mask(block):
    """8 query heads a kv head, 4 rows a slot from a block's start: the
    kernel (interpret mode) against dense attention under the plain mask."""
    rng = np.random.default_rng(block)
    s, t, hkv, g, d, p, mp = 3, 4, 2, 8, 16, 8, 4
    n = 1 + s * mp
    kp, vp = (jnp.asarray(rng.standard_normal((n, hkv, p, d)), jnp.float32)
              for _ in "kv")
    table = jnp.asarray(1 + np.arange(s * mp).reshape(s, mp), jnp.int32)
    start = jnp.asarray([0, 8, 20], jnp.int32)
    q = jnp.asarray(rng.standard_normal((s, t, hkv * g, d)), jnp.float32)
    got = paged_attention(q, kp, vp, table, start, block=block,
                          interpret=True)
    keys = np.asarray(kp)[np.asarray(table)].transpose(0, 2, 1, 3, 4).reshape(
        s, hkv, mp * p, d)
    vals = np.asarray(vp)[np.asarray(table)].transpose(0, 2, 1, 3, 4).reshape(
        s, hkv, mp * p, d)
    for i in range(s):
        qpos = int(start[i]) + np.arange(t)
        m = _plain_block_mask(qpos[:, None], np.arange(mp * p)[None], block)
        for h in range(hkv * g):
            sc = np.asarray(q[i, :, h]) @ keys[i, h // g].T / np.sqrt(d)
            sc = np.where(m, sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            want = (pr / pr.sum(-1, keepdims=True)) @ vals[i, h // g]
            np.testing.assert_allclose(np.asarray(got[i, :, h]), want,
                                       rtol=1e-5, atol=1e-5)
    # the einsum oracle takes the same horizon
    ora = F.paged_attention(q, kp, vp, table, start, kernel="einsum",
                            block=block)
    np.testing.assert_allclose(np.asarray(raw(ora)), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block", [1, 4])
def test_prefill_kernel_block_horizon_against_a_plain_mask(block):
    rng = np.random.default_rng(10 + block)
    t, hkv, g, d, keys, cached = 12, 2, 8, 16, 40, 8
    q = jnp.asarray(rng.standard_normal((t, hkv * g, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((hkv, keys, d)), jnp.float32)
            for _ in "kv")
    got = np.asarray(prefill_attention(q, k, v, cached, block=block,
                                       interpret=True))
    qpos = cached + np.arange(t)
    m = _plain_block_mask(qpos[:, None], np.arange(keys)[None], block)
    for h in range(hkv * g):
        sc = np.asarray(q[:, h]) @ np.asarray(k[h // g]).T / np.sqrt(d)
        sc = np.where(m, sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = (pr / pr.sum(-1, keepdims=True)) @ np.asarray(v[h // g])
        np.testing.assert_allclose(got[:, h], want, rtol=1e-5, atol=1e-5)


# -- (5) what a block engine refuses ------------------------------------------


def test_a_block_engine_refuses_what_it_cannot_serve(made):
    model, _, _ = made()
    with pytest.raises(ValueError, match="must divide page_size"):
        _engine(model, page_size=6)
    with pytest.raises(ValueError, match="speculate_k"):
        _engine(model, speculate_k=2)
    eng = _engine(model)
    with pytest.raises(NotImplementedError, match="hands no prefill"):
        eng.prefill_export(_ids(9), max_new_tokens=4)
    with pytest.raises(ValueError, match="remasking"):
        from paddle_tpu.text.models import SDARConfig

        SDARConfig(remasking="random")


# -- (6) the block engine's span tree -----------------------------------------

BLOCK_PARTS = ["eng_block_prep", "eng_block_upload", "eng_block_dispatch",
               "eng_block_readback", "eng_block_append"]
COMMIT_PARTS = ["eng_commit_prep", "eng_commit_upload", "eng_commit_dispatch",
                "eng_commit_readback"]


@pytest.fixture
def _nobody_traces(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    tracing._buffer.clear()
    yield
    tracing._buffer.clear()


def _submit_two(eng):
    """One greedy and one sampled request, 9 tokens each: three rounds of
    four passes, two of them opened by a commit."""
    return [eng.submit(PROMPTS[0], max_new_tokens=9),
            eng.submit(PROMPTS[1], max_new_tokens=9, seed=3,
                       temperature=0.8, top_p=0.95)]


def _host_events(trace_dir):
    """{name: [(start_ns, end_ns)]} of the host planes' events, oldest
    first, in the one ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    out = {}
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def _parts_in_order(rows, parent, names):
    """``parent``'s children are ``names`` in order, each starting after the
    one before it ends, all inside the parent."""
    kids = sorted((r for r in rows if r.parent_id == parent.span_id),
                  key=lambda r: r.t0)
    assert [k.name for k in kids] == names
    t = parent.t0
    for k in kids:
        assert t <= k.t0 <= k.t1 <= parent.t1
        assert k.trace_id == parent.trace_id
        t = k.t1


def test_each_block_step_is_one_tree_with_every_part_of_its_passes(
        made, tmp_path, _nobody_traces):
    model, _, _ = made()
    eng = _engine(model)
    _submit_two(eng)
    with jax.profiler.trace(str(tmp_path)):
        t_from = time.perf_counter()
        steps = 0
        while eng.step():
            steps += 1
        t_to = time.perf_counter()
    rows = tracing.recorded(t_from, t_to)
    roots = [r for r in rows if r.name == "eng_step"]
    assert len(roots) == steps and steps >= 12
    assert all(r.parent_id is None for r in roots)
    assert len({r.trace_id for r in roots}) == len(roots)
    by_id = {r.span_id: r for r in rows}
    # every span of the run is one step's: its chain of parents ends there
    for r in rows:
        while r.parent_id is not None:
            r = by_id[r.parent_id]
        assert r.name == "eng_step"
    passes = [r for r in rows if r.name == "eng_block_pass"]
    commits = [r for r in rows if r.name == "eng_block_commit"]
    assert len(passes) == steps and len(commits) == 2
    for root in roots:
        kids = [k.name for k in sorted(
            (r for r in rows if r.parent_id == root.span_id),
            key=lambda r: r.t0)]
        # a round's start: the commit, the admissions, then the pass
        assert kids[-1] == "eng_block_pass"
        assert set(kids[:-1]) <= {"eng_block_commit", "eng_admit"}
        assert kids.count("eng_block_commit") <= 1
        if "eng_block_commit" in kids:
            assert kids[0] == "eng_block_commit"
    for sp in passes:
        _parts_in_order(rows, sp, BLOCK_PARTS)
        assert {"live", "rows", "final", "experts_touched",
                "kv_pages"} <= set(sp.attrs)
    for sp in commits:
        _parts_in_order(rows, sp, COMMIT_PARTS)
        assert {"slots", "rows", "experts_touched",
                "kv_pages"} <= set(sp.attrs)
    # the same names lie on the profiler's host plane, as many of each
    host = _host_events(str(tmp_path))
    for name in ["eng_step", "eng_block_pass", "eng_block_commit",
                 *BLOCK_PARTS, *COMMIT_PARTS]:
        assert len(host.get(name, ())) == sum(r.name == name for r in rows), \
            name


def test_nobody_traces_a_block_engine_and_its_tokens_are_the_same(
        made, tmp_path, _nobody_traces):
    model, _, _ = made()
    served = []
    for traced in (True, False):
        eng = _engine(model)
        rids = _submit_two(eng)
        if traced:
            with jax.profiler.trace(str(tmp_path)):
                eng.run()
            assert tracing.recorded()
            tracing._buffer.clear()
        else:
            assert not tracing.active()
            eng.run()
            assert tracing.recorded() == []
        served.append([eng.result(r).tolist() for r in rids])
    assert served[0] == served[1]


def test_program_spans_map_onto_their_profiler_events_by_the_tracers_anchor(
        made, tmp_path, _nobody_traces):
    """The benchmark's readers lay the program's buffer (``perf_counter``)
    on the device's events (the profiler's clock) through ONE anchor:
    ``harness.Tracer.t_start``, read just after the window's annotation
    opens. Every program span of the window, mapped so, lies within 0.5 ms
    of its own event on the host plane, at its start and at its end."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    harness = importlib.import_module("harness")
    trace_reduce = importlib.import_module("trace_reduce")
    model, _, _ = made()
    eng = _engine(model)
    _submit_two(eng)
    for _ in range(4):  # the first round: its programs compile here
        eng.step()
    tracer = harness.Tracer(True, str(tmp_path / "trace"))
    tracer.start()
    for _ in range(2):  # the next round's start (its commit) and a pass
        eng.step()
    tracer.stop()
    host = _host_events(tracer.dir)
    (w0, _), = host[trace_reduce.WINDOW_SPAN]
    rows = [r for r in tracing.recorded(tracer.t_start, tracer.t_stop)
            if r.name.startswith("eng_")]
    names = {r.name for r in rows}
    assert {"eng_step", "eng_block_commit", *BLOCK_PARTS,
            *COMMIT_PARTS} <= names
    worst = 0.0
    for name in names:
        mine = sorted((r.t0, r.t1) for r in rows if r.name == name)
        theirs = host[name]
        assert len(mine) == len(theirs), name
        for (t0, t1), (s, e) in zip(mine, theirs):
            for t, ns in ((t0, s), (t1, e)):
                worst = max(worst, abs((t - tracer.t_start) - (ns - w0) / 1e9))
    assert worst < 0.5e-3
