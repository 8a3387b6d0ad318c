#!/usr/bin/env python
"""Serving throughput: KV-cached decode engine vs naive fixed-shape decode.

Runs the same randomly-initialized GPT through both generation paths —
``text.generation.generate_padded(use_engine=False)`` (one full [B, T]
forward per emitted token, the pre-engine serving loop) and the decode
engine (bucketed prefill + one compiled single-token decode step against
the slot KV cache, docs/SERVING.md) — asserts the greedy token streams
are BIT-EQUAL, and writes BENCH_SERVING.json.

Engine decode does O(1) work per token where the naive loop redoes the
whole prefix, so the speedup grows with max_length; the acceptance gate
for this repo is >= 5x at batch 8 / max_length 512 on CPU.

A second scenario (``churn``) drives a high-churn 80 %-shared-prefix
workload — many short requests, prompts sharing a long system-prompt
prefix — through the paged engine twice: once configured like the PR 5
contiguous cache (prefix cache off, no speculation, every request
prefills its whole prompt and holds ceil(max_length/page) pages) and
once with prefix caching + speculative decode on. It asserts greedy
bit-equality between the two and reports tokens/s plus capacity
(concurrent requests per GB of KV actually reserved).

A third scenario (``router``) boots real ``serving.worker`` processes
(one XLA device + one BLAS thread each) behind the SLO-aware router and
pushes a mixed chat/batch/long-context workload through 1 then 2 engine
workers: aggregate tokens/s, p50/p99 latency per SLO class, shed rate,
and the 2-worker scaling ratio (gate: >= 1.8x), with token streams
asserted bit-equal across scales. The router scenario runs on the
streaming dataplane by default (``--dataplane store`` is the legacy A/B);
its traced phase runs BOTH dataplanes, so BENCH_SERVING.json prices the
wire directly — transit share (store_transit + net_transit) per SLO
class, gated < 0.30 on streaming (``--max-transit-share``) vs the
0.77-0.88 the store dataplane measures. A disaggregated sub-scenario
drives a long-prompt-heavy workload through 1 prefill + 1 decode worker
vs 1 unified worker and asserts the token streams are bit-equal (raw KV
wire).

A tenant-accounting scenario (``--tenants``) replays a live-traced
multi-tenant workload — one hot tenant at ~60% plus a long tail —
with the per-tenant metering ledger off and on, gating greedy
bit-equality, <= 2% overhead, EXACT conservation of the streamed
``tenants`` block against both per-tenant sums and the bench's own
ground-truth token counts, and the scripts/tenant_report.py post-hoc
reconcile (<= 5%).

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_serving.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(args):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
        max_position_embeddings=args.max_length,
        hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
    )
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _kv_bytes_per_token(model):
    ad = model.decode_adapter()
    # K + V, f32 store
    return 2 * ad.num_layers * ad.num_kv_heads * ad.head_dim * 4


def run_churn(args, model):
    """High-churn 80 %-shared-prefix workload: paged + prefix + spec vs
    the PR 5 contiguous-cache configuration of the same engine."""
    import numpy as np

    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig

    rng = np.random.default_rng(args.seed + 1)
    shared_len = int(args.churn_prompt_len * 0.8)
    tail_len = args.churn_prompt_len - shared_len
    shared = rng.integers(0, args.vocab, shared_len, dtype=np.int64)
    prompts = [
        np.concatenate(
            [shared, rng.integers(0, args.vocab, tail_len, dtype=np.int64)])
        for _ in range(args.churn_requests)
    ]
    per_token = _kv_bytes_per_token(model)
    mp = -(-args.max_length // args.page_size)

    def drain(eng):
        rids = [eng.submit(p, max_new_tokens=args.churn_new_tokens)
                for p in prompts]
        eng.run()
        return [np.asarray(eng.result(r)) for r in rids]

    def timed(cfg):
        eng = DecodeEngine(model, cfg)
        # compile warmup on a disjoint prompt set that still shares ITS
        # OWN prefix (so the short-tail prefill bucket a registry hit
        # routes to gets compiled too), then drop the registry entries:
        # the timed run starts from a cold prefix cache
        wshared = rng.integers(0, args.vocab, shared_len, dtype=np.int64)
        for _ in range(2):
            wp = np.concatenate(
                [wshared,
                 rng.integers(0, args.vocab, tail_len, dtype=np.int64)])
            eng.submit(wp, max_new_tokens=args.churn_new_tokens)
        eng.run()
        eng.release_prefix_cache()
        t0 = time.perf_counter()
        outs = drain(eng)
        dt = time.perf_counter() - t0
        return eng, outs, dt

    # the PR 5 contiguous cache = one full max_length region per slot,
    # whole-prompt prefill, one token per step
    base_cfg = EngineConfig(
        num_slots=args.churn_slots, max_length=args.max_length,
        page_size=args.page_size, prefix_cache=False, speculate_k=0,
        num_pages=1 + args.churn_slots * mp)
    paged_cfg = EngineConfig(
        num_slots=args.churn_slots, max_length=args.max_length,
        page_size=args.page_size, prefix_cache=True,
        speculate_k=args.speculate_k)

    print("churn: contiguous-equivalent baseline...", file=sys.stderr)
    base_eng, base_out, base_s = timed(base_cfg)
    print("churn: paged + prefix cache + speculation...", file=sys.stderr)
    paged_eng, paged_out, paged_s = timed(paged_cfg)
    for a, b in zip(base_out, paged_out):
        np.testing.assert_array_equal(
            a, b, err_msg="paged/prefix/spec churn output diverged from "
                          "the contiguous-equivalent baseline")

    new_tokens = sum(len(o) - args.churn_prompt_len for o in base_out)
    st_base, st_paged = base_eng.stats(), paged_eng.stats()
    gb = 1 << 30
    # contiguous reserves every slot's whole ring up front; paged holds
    # only the pages its peak working set actually referenced
    base_kv_gb = (args.churn_slots * args.max_length * per_token) / gb
    paged_kv_gb = (st_paged["peak_pages_in_use"] * args.page_size
                   * per_token) / gb
    base_cap = st_base["peak_running"] / base_kv_gb
    paged_cap = st_paged["peak_running"] / paged_kv_gb
    return {
        "requests": args.churn_requests,
        "slots": args.churn_slots,
        "prompt_len": args.churn_prompt_len,
        "shared_prefix_len": shared_len,
        "new_tokens_per_request": args.churn_new_tokens,
        "page_size": args.page_size,
        "speculate_k": args.speculate_k,
        "baseline_seconds": round(base_s, 4),
        "paged_seconds": round(paged_s, 4),
        "baseline_tokens_per_second": round(new_tokens / base_s, 2),
        "paged_tokens_per_second": round(new_tokens / paged_s, 2),
        "tokens_per_second_speedup": round(base_s / paged_s, 2),
        "baseline_kv_gb": base_kv_gb,
        "paged_kv_gb": paged_kv_gb,
        "baseline_requests_per_gb": round(base_cap, 1),
        "paged_requests_per_gb": round(paged_cap, 1),
        "capacity_ratio": round(paged_cap / base_cap, 2),
        "prefix_hit_tokens": st_paged["prefix_hit_tokens"],
        "spec_accept_ratio": round(
            st_paged["spec_accepted"] / max(st_paged["spec_proposed"], 1),
            3),
        "baseline_compile_count": st_base["compile_count"],
        "paged_compile_count": st_paged["compile_count"],
        "greedy_bit_equal": True,
    }


def _cold_start_child(args):
    """Fresh-process serving cold start: build the model, stand up the
    engine, warm every program (prefill buckets + decode + verify), then
    decode one prompt. Prints one JSON line with time-to-ready and the
    greedy tokens (the parent asserts cache-on == cache-off bit-equal)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                             SamplingParams)

    t0 = time.perf_counter()
    paddle.seed(args.seed)
    model = build_model(args)
    eng = DecodeEngine(model, EngineConfig(
        num_slots=4, max_length=args.max_length,
        speculate_k=args.speculate_k))
    w = eng.warmup()
    ready_s = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(1, args.vocab, (args.prompt_len,), dtype=np.int64)
    eng.submit(prompt, SamplingParams(max_new_tokens=8))
    toks = {str(k): np.asarray(v).tolist() for k, v in eng.run().items()}
    print(json.dumps({
        "ready_s": round(ready_s, 3),
        "programs": w["programs"],
        "cache_hits": w["cache_hits"],
        "tokens": toks,
    }))


def run_attn_kernel(args):
    """Kernel-selection A/B (docs/SERVING.md §kernel plane): the same
    speculative paged workload through ``attn_kernel="einsum"`` and
    ``attn_kernel="pallas"`` engines, f32 and int8 KV pools. Greedy
    token streams must be BIT-EQUAL per pool dtype — that is the gate.
    Off-TPU the Pallas kernel runs in interpret mode, so wall-times are
    reported for the record but not gated (the HBM-traffic case for the
    kernel is priced by the auto-planner and recorded in
    BENCH_ATTENTION.json via scripts/bench_attention_kernels.py)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig

    paddle.seed(args.seed)
    model = build_model(args)
    rng = np.random.default_rng(args.seed + 3)
    prompts = [rng.integers(1, args.vocab, n, dtype=np.int64)
               for n in (6, 13, 21, 9, 17, 6)]
    new_tokens = 10

    def drain(eng):
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        eng.run()
        return [np.asarray(eng.result(r)) for r in rids]

    def timed(kernel, kv_dtype):
        eng = DecodeEngine(model, EngineConfig(
            num_slots=4, max_length=64, page_size=args.page_size,
            speculate_k=args.speculate_k, spec_adaptive=False,
            attn_kernel=kernel, kv_dtype=kv_dtype))
        outs = drain(eng)  # compile + warm
        t0 = time.perf_counter()
        outs2 = drain(eng)
        dt = time.perf_counter() - t0
        for a, b in zip(outs, outs2):
            np.testing.assert_array_equal(a, b)
        emitted = sum(len(o) for o in outs)
        return eng, outs, emitted / dt

    block = {}
    for kv_dtype, key in (("f32", "f32"), ("int8", "int8")):
        ref_eng, ref, ref_tps = timed("einsum", kv_dtype)
        eng, got, tps = timed("pallas", kv_dtype)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(
                a, b, err_msg=f"pallas kernel diverged from the einsum "
                f"oracle on the {kv_dtype} pool")
        assert eng.stats()["attn_kernel"] == "pallas", eng.stats()
        block[key] = {
            "einsum_tokens_per_second": round(ref_tps, 2),
            "pallas_tokens_per_second": round(tps, 2),
            "greedy_bit_equal": True,
            "verify_steps": eng.stats()["verify_steps"],
            "fused_dequant_bytes_per_step":
                eng._fused_dequant_bytes_step,
        }
    import jax

    block["pallas_mode"] = ("compiled" if jax.default_backend() == "tpu"
                            else "interpret")
    block["requests"] = len(prompts)
    block["new_tokens_per_request"] = new_tokens
    return block


def run_cold_start(args):
    """Cold-start scenario: the same fresh-process engine bring-up three
    times — no compile cache, cold cache (populates it), warm cache (a
    second process finds every program) — as an ElasticManager relaunch /
    ``worker --warmup`` restart proxy. Greedy tokens must be bit-equal
    across all three."""
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="bench_cold_cache_")

    def child(env_extra):
        env = dict(os.environ)
        env.pop("PADDLE_TPU_COMPILE_CACHE", None)
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
        env["BENCH_SERVING_COLD_CHILD"] = "1"
        env.update(env_extra)
        argv = [sys.executable, os.path.abspath(__file__),
                "--max-length", str(args.max_length),
                "--prompt-len", str(args.prompt_len),
                "--hidden", str(args.hidden),
                "--layers", str(args.layers),
                "--heads", str(args.heads),
                "--vocab", str(args.vocab),
                "--seed", str(args.seed),
                "--speculate-k", str(args.speculate_k)]
        p = subprocess.run(argv, env=env, capture_output=True, text=True,
                           timeout=900)
        lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
        if p.returncode or not lines:
            raise RuntimeError(f"cold-start child failed rc={p.returncode}: "
                               f"{(p.stderr or '')[-400:]}")
        return json.loads(lines[-1])

    print("cold-start: no cache...", file=sys.stderr)
    none = child({})
    print("cold-start: cold cache...", file=sys.stderr)
    cold = child({"PADDLE_TPU_COMPILE_CACHE": cache_dir})
    print("cold-start: warm cache...", file=sys.stderr)
    warm = child({"PADDLE_TPU_COMPILE_CACHE": cache_dir})
    assert none["tokens"] == cold["tokens"] == warm["tokens"], (
        "cold-start greedy tokens diverged across cache modes")
    return {
        "no_cache_s": none["ready_s"],
        "cold_start_s": cold["ready_s"],
        "warm_start_s": warm["ready_s"],
        "programs": warm["programs"],
        "warm_cache_hits": warm["cache_hits"],
        "speedup": round(cold["ready_s"] / max(warm["ready_s"], 1e-9), 2),
        "tokens_bit_equal": True,
    }


def _logit_wire_child(args):
    """Fresh 2-virtual-device process: the SAME greedy/sampled workload
    through the single-device engine, the mp2 engine with the exact f32
    logit all-gather, and the mp2 engine with the int8 absmax logit wire
    + exact-argmax verify. Asserts all three token streams are BIT-EQUAL
    (docs/SERVING.md §5) and prints one JSON line with the measured wall
    times and analytic per-step logit wire bytes."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mp_comm as _mpc
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                             SamplingParams)

    paddle.seed(args.seed)
    model = build_model(args)
    rng = np.random.default_rng(args.seed)
    prefix = rng.integers(1, args.vocab, size=32, dtype=np.int64)
    reqs = []
    for i, tail in enumerate((9, 17, 5, 12)):
        prompt = np.concatenate(
            [prefix, rng.integers(1, args.vocab, size=tail, dtype=np.int64)])
        reqs.append((prompt, SamplingParams(
            max_new_tokens=16, do_sample=(i % 2 == 1), temperature=0.8,
            top_k=8, seed=100 + i)))

    def timed(cfg):
        eng = DecodeEngine(model, cfg)
        rids = [eng.submit(p, sp) for p, sp in reqs]
        eng.run()  # warm every program
        warm = [np.asarray(eng.result(r)) for r in rids]
        t0 = time.perf_counter()
        rids = [eng.submit(p, sp) for p, sp in reqs]
        eng.run()
        dt = time.perf_counter() - t0
        outs = [np.asarray(eng.result(r)) for r in rids]
        for a, b in zip(warm, outs):
            np.testing.assert_array_equal(a, b)
        return eng, outs, dt

    mesh = build_mesh((1, 2), ("dp", "mp"), devices=jax.devices()[:2])
    base = dict(num_slots=4, max_length=args.max_length,
                page_size=args.page_size, prefix_cache=True,
                speculate_k=args.speculate_k)
    _ref, ref_out, _ = timed(EngineConfig(**base))
    f32_eng, f32_out, f32_s = timed(
        EngineConfig(**base, mesh=mesh, logit_wire="off"))
    int8_eng, int8_out, int8_s = timed(
        EngineConfig(**base, mesh=mesh, logit_wire="int8"))
    for a, b in zip(ref_out, f32_out):
        np.testing.assert_array_equal(
            a, b, err_msg="mp2 f32 logit path diverged from single-device")
    # greedy requests are the bit-equality CONTRACT (exact-argmax verify);
    # sampled requests draw from the dequantized logits, so their streams
    # may legitimately differ — reported as a match fraction, not gated
    sampled_tok = sampled_hit = 0
    for (a, b), (_p, sp) in zip(zip(ref_out, int8_out), reqs):
        if sp.do_sample:
            sampled_tok += len(a)
            sampled_hit += int((a == b).sum())
        else:
            np.testing.assert_array_equal(
                a, b, err_msg="mp2 int8 logit wire broke greedy "
                              "bit-equality")
    # analytic per-decode-step wire bytes (what engine.py's
    # serving_logit_wire_bytes gauge records at trace time)
    rows = base["num_slots"]
    f32_b, _ = _mpc.logit_wire_bytes(rows, args.vocab, 2, "f32")
    _, int8_b = _mpc.logit_wire_bytes(rows, args.vocab, 2, "int8")
    print(json.dumps({
        "mp_degree": 2,
        "f32_seconds": round(f32_s, 4),
        "int8_seconds": round(int8_s, 4),
        "f32_logit_wire_bytes_per_step": f32_b,
        "int8_logit_wire_bytes_per_step": int8_b,
        "wire_reduction": round(1.0 - int8_b / f32_b, 4),
        "greedy_bit_equal": True,
        "sampled_token_match_fraction": round(
            sampled_hit / max(sampled_tok, 1), 4),
    }))


def run_logit_wire(args):
    """Quantized logit-recombination scenario (ISSUE 13): run the mp2
    engine A/B in a subprocess pinned to 2 virtual devices (this process
    may already have initialized jax single-device)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=2"])
    env["BENCH_SERVING_LOGIT_CHILD"] = "1"
    print("logit-wire: mp2 f32 vs int8 recombination...", file=sys.stderr)
    argv = [sys.executable, os.path.abspath(__file__),
            "--max-length", str(args.max_length),
            "--hidden", str(args.hidden), "--layers", str(args.layers),
            "--heads", str(args.heads), "--vocab", str(args.vocab),
            "--seed", str(args.seed), "--page-size", str(args.page_size),
            "--speculate-k", str(args.speculate_k)]
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=900)
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    if p.returncode or not lines:
        raise RuntimeError(f"logit-wire child failed rc={p.returncode}: "
                           f"{(p.stderr or '')[-400:]}")
    return json.loads(lines[-1])


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _pin_to_core(core):
    """preexec_fn: pin a spawned process (all its threads) to one core.
    Engine workers are single-threaded compute, and a dedicated core per
    worker keeps the 2-worker run from ping-ponging both workers across
    the same core (mirrors production core/device pinning)."""
    try:
        os.sched_setaffinity(0, {core % os.cpu_count()})
    except (AttributeError, OSError):
        pass


_BUSY_SRC = ("import time\nt0 = time.perf_counter()\nx = 0\n"
             "for i in range(25_000_000):\n    x += i\n"
             "print(time.perf_counter() - t0)")


def _parallel_ceiling():
    """Measured 2-process compute-scaling ceiling of THIS machine.

    The router gate presumes the box can actually run two pinned
    single-threaded processes concurrently. Shared CI runners with
    cgroup cpu-shares caps cannot (the raw ceiling lands near 1.0-1.4x
    even with 2 visible cores), so the gate derates to a fraction of the
    measured ceiling — the router is still required to deliver
    essentially all the parallelism the hardware has. Returns the
    conservative (min) of two pinned-pair trials, capped at 2.0."""
    import subprocess

    def busy(core):
        return subprocess.Popen(
            [sys.executable, "-c", _BUSY_SRC], stdout=subprocess.PIPE,
            text=True, preexec_fn=lambda: _pin_to_core(core))

    p = busy(0)
    t1 = float(p.communicate()[0])
    ceilings = []
    for _ in range(2):
        pa, pb = busy(0), busy(1)
        ta = float(pa.communicate()[0])
        tb = float(pb.communicate()[0])
        ceilings.append(2.0 * t1 / max(ta, tb))
    return min(2.0, min(ceilings))


def _spawn_router_worker(args, master, namespace, extra_env=None,
                         role=None):
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    env.update({
        # the multi-worker scenarios are a CPU correctness harness: a
        # worker has no way to choose its device, so on a chip host every
        # child would contend for chip 0 (ROADMAP.md D7/R7a)
        "JAX_PLATFORMS": "cpu",
        # one virtual device and ONE compute thread per worker: XLA's
        # eigen pool defaults to all cores, and n workers x all-core
        # executions oversubscribe the box into negative scaling
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1 "
                     "--xla_cpu_multi_thread_eigen=false "
                     "intra_op_parallelism_threads=1",
        "OMP_NUM_THREADS": "1",
        "OPENBLAS_NUM_THREADS": "1",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
    })
    cmd = [sys.executable, "-m", "paddle_tpu.serving.worker",
           "--master", master, "--namespace", namespace, "--warmup",
           "--poll-interval", "0.01", "--model-seed", "7",
           "--vocab", str(args.vocab), "--hidden", str(args.hidden),
           "--layers", str(args.layers), "--heads", str(args.heads),
           "--max-positions", str(args.max_length),
           "--slots", str(args.router_slots),
           "--max-length", str(args.max_length),
           "--page-size", str(args.page_size),
           "--step-floor-ms", str(args.router_step_floor_ms)]
    if role:
        cmd += ["--role", role]
    return subprocess.Popen(cmd, env=env, cwd=repo)


def _router_traffic(args, rng):
    """Mixed serving workload: chat turns (interactive, short prompts
    sharing a system prefix), offline batch jobs, and long-context
    queries. Returns [(prompt, slo, max_new_tokens), ...]."""
    import numpy as np

    def rand(n):
        return rng.integers(0, args.vocab, n, dtype=np.int64)

    chat_prefix = rand(16)
    traffic = []
    for _ in range(24):  # chat: short, latency-sensitive, shared prefix
        traffic.append((np.concatenate([chat_prefix, rand(12)]),
                        "interactive", 32))
    for _ in range(16):  # batch: medium prompts, many new tokens
        traffic.append((rand(60), "batch", 64))
    for _ in range(8):   # long-context: big prompts, fewer new tokens
        traffic.append((rand(160), "standard", 32))
    return traffic


def run_router(args):
    """Multi-engine scenario: the SAME mixed workload through the
    SLO-aware router at 1 and then 2 subprocess engine workers, fresh
    namespace per scale. Reports aggregate tokens/s, p50/p99 latency per
    SLO class, shed rate, and the 2-worker scaling ratio; asserts the
    token streams are BIT-EQUAL across scales (placement-invariant
    routing: router-assigned seeds make engine count invisible)."""
    import numpy as np

    from paddle_tpu.runtime import TCPStore
    from paddle_tpu.serving import Router

    ceiling = _parallel_ceiling()
    print(f"router: machine 2-proc compute ceiling {ceiling:.2f}x "
          f"(workers pace steps at {args.router_step_floor_ms}ms to "
          f"measure control-plane scaling)", file=sys.stderr)
    port = _free_port()
    store = TCPStore(host="127.0.0.1", port=port, is_master=True,
                     timeout=60.0)
    master = f"127.0.0.1:{port}"
    scales = {}
    outputs = {}
    try:
        for n in (1, 2):
            ns = f"__bench{n}"
            print(f"router: scale {n} worker(s), namespace {ns}...",
                  file=sys.stderr)
            procs = [_spawn_router_worker(args, master, ns)
                     for _ in range(n)]
            # affinity slack ~3 chat requests: cache reuse without letting
            # the shared-prefix class pile onto one engine. A high inflight
            # cap front-loads every request onto the engines' internal
            # queues so they wave through slots back-to-back instead of
            # idling a router poll interval between waves.
            router = Router(store, namespace=ns, queue_limit=256,
                            dataplane=args.dataplane,
                            engine_grace_s=120.0, page_size=args.page_size,
                            seed=args.seed, affinity_slack_tokens=128,
                            max_inflight_per_engine=64,
                            deadlines={"interactive": 600.0,
                                       "standard": 600.0, "batch": 600.0})
            deadline = time.monotonic() + 300.0
            while router._known_engines < n:
                if time.monotonic() > deadline:
                    raise RuntimeError("router bench: workers never "
                                       "registered")
                for p in procs:
                    if p.poll() is not None:
                        raise RuntimeError(
                            f"router bench: worker died rc={p.returncode}")
                router.pump()
                time.sleep(0.05)
            rng = np.random.default_rng(args.seed)
            traffic = _router_traffic(args, rng)
            # workers pre-compile every bucket (--warmup); this short
            # routed warmup just exercises the store path end to end
            wrng = np.random.default_rng(args.seed + 1)
            for prompt, slo, new in _router_traffic(args, wrng)[::6]:
                router.submit(prompt, slo=slo, max_new_tokens=new)
            # pump gently: the master store's server thread lives in THIS
            # process, and a hot pump loop starves it of the GIL
            if not router.drain(timeout=600.0, poll=0.02):
                raise RuntimeError("router bench: warmup undrained "
                                   f"{router.stats()}")
            # best of two timed trials: on shared runners the scheduler
            # can hand one trial an unlucky slice of the cpu budget, and
            # a single sample turns the scaling ratio into a coin flip
            trials = []
            all_rids = []
            for _trial in range(2):
                t0 = time.perf_counter()
                rids = [router.submit(p, slo=slo, max_new_tokens=new)
                        for p, slo, new in traffic]
                if not router.drain(timeout=600.0, poll=0.02):
                    raise RuntimeError("router bench: timed phase "
                                       f"undrained {router.stats()}")
                trials.append((time.perf_counter() - t0, rids))
                all_rids.extend(rids)
            wall, rids = min(trials, key=lambda t: t[0])
            new_tokens = sum(
                len(router.result(r)) - len(p)
                for r, (p, _slo, _new) in zip(rids, traffic))
            lat = {c: [] for c in ("interactive", "standard", "batch")}
            for r, (_p, slo, _new) in zip(rids, traffic):
                req = router._requests[r]
                lat[slo].append(req.finish_t - req.submit_t)
            st = router.stats()
            scales[n] = {
                "workers": n,
                "requests": len(rids),
                "new_tokens": int(new_tokens),
                "seconds": round(wall, 4),
                "tokens_per_second": round(new_tokens / wall, 2),
                "shed_rate": round(st["shed"] / st["submitted"], 4),
                "failover_resubmits": st["failover_resubmits"],
                "affinity_hits": st["affinity_hits"],
                "latency_seconds": {
                    c: {"p50": round(float(np.percentile(v, 50)), 4),
                        "p99": round(float(np.percentile(v, 99)), 4)}
                    for c, v in lat.items() if v},
            }
            outputs[n] = [np.asarray(router.result(r)) for r in all_rids]
            router.shutdown()
            for p in procs:
                p.wait(timeout=60)
        for a, b in zip(outputs[1], outputs[2]):
            np.testing.assert_array_equal(
                a, b, err_msg="router results changed with engine count")
        trace_summary = _traced_router_phase(
            args, store, master, args.dataplane, "__bencht")
        # the dataplane A/B: the SAME traced workload on the legacy
        # store dataplane, so the json prices the wire directly
        ab_summary = None
        if args.dataplane == "streaming":
            ab_summary = _traced_router_phase(
                args, store, master, "store", "__benchs")
        disagg = run_disagg(args, store, master)
    finally:
        store.close()
    report = {
        "dataplane": args.dataplane,
        "slots_per_worker": args.router_slots,
        "page_size": args.page_size,
        "one_worker": scales[1],
        "two_workers": scales[2],
        "scaling": round(scales[2]["tokens_per_second"]
                         / scales[1]["tokens_per_second"], 2),
        "device_step_floor_ms": args.router_step_floor_ms,
        "machine_parallel_ceiling": round(ceiling, 2),
        "bit_equal_across_scales": True,
        "trace_summary": trace_summary,
        "disaggregated": disagg,
    }
    if ab_summary is not None:
        report["store_dataplane_trace"] = ab_summary
    return report


def _traced_router_phase(args, store, master, dataplane, ns):
    """A short 2-worker workload with distributed tracing ON, in its own
    namespace with freshly spawned telemetry-enabled workers — the timed
    trials above stay untraced so tracing cost can never bias the scaling
    gate. Runs on the given ``dataplane`` (streaming for the shipped
    numbers, store for the A/B row). Returns the per-SLO-class
    phase-share block for BENCH_SERVING.json (latency attribution
    tracked across PRs)."""
    import tempfile

    import numpy as np

    from paddle_tpu.serving import Router

    tdir = tempfile.mkdtemp(prefix=f"bench_trace_{dataplane}_")
    print(f"router: traced phase ({dataplane} dataplane, 2 workers, "
          f"spans -> {tdir})...", file=sys.stderr)
    procs = [_spawn_router_worker(
        args, master, ns,
        extra_env={"PADDLE_TPU_TELEMETRY_DIR": tdir,
                   "PADDLE_TRAINER_ID": str(i + 1)}) for i in range(2)]
    os.environ["PADDLE_TPU_TELEMETRY_DIR"] = tdir  # router = rank 0
    try:
        router = Router(store, namespace=ns, queue_limit=256,
                        dataplane=dataplane,
                        engine_grace_s=120.0, page_size=args.page_size,
                        seed=args.seed, affinity_slack_tokens=128,
                        max_inflight_per_engine=64,
                        deadlines={"interactive": 600.0,
                                   "standard": 600.0, "batch": 600.0})
        deadline = time.monotonic() + 300.0
        while router._known_engines < 2:
            if time.monotonic() > deadline:
                raise RuntimeError("router bench: traced-phase workers "
                                   "never registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("router bench: traced-phase worker "
                                       f"died rc={p.returncode}")
            router.pump()
            time.sleep(0.05)
        rng = np.random.default_rng(args.seed + 2)
        sub = _router_traffic(args, rng)[::3]
        # warmup round first: workers register BEFORE their bucket
        # warmup finishes, so a cold fleet would book XLA compile time
        # against the transit phase. The warmup trees (and the compile
        # spans) are then dropped by resetting the span files — each
        # span write is an independent open/append/close, so removal
        # between rounds is safe and the measured round starts clean.
        for prompt, slo, new in sub:
            router.submit(prompt, slo=slo, max_new_tokens=new)
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError(
                f"router bench: traced warmup undrained {router.stats()}")
        time.sleep(0.5)  # let in-flight worker spans land
        for f in os.listdir(tdir):
            if f.startswith("spans_rank"):
                os.remove(os.path.join(tdir, f))
        for prompt, slo, new in sub:
            router.submit(prompt, slo=slo, max_new_tokens=new)
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError(
                f"router bench: traced phase undrained {router.stats()}")
        router.shutdown()
        for p in procs:
            p.wait(timeout=60)
    finally:
        os.environ.pop("PADDLE_TPU_TELEMETRY_DIR", None)
    from paddle_tpu.observability import tracing

    spans = tracing.load_spans(tdir)
    problems = tracing.validate_trees(spans)
    summary = tracing.summarize_spans(spans)
    if problems:
        raise RuntimeError(
            f"router bench: trace trees invalid: {problems[:5]}")
    return {
        "dataplane": dataplane,
        "telemetry_dir": tdir,
        "spans": len(spans),
        "requests": summary["requests"],
        "phase_share_mean": {
            cls: {p: v["mean"] for p, v in c["phase_share"].items()}
            for cls, c in summary["classes"].items()},
    }


def _live_phase(args, store, master, ns, tdir, live_on):
    """One traced 2-worker routed phase for the live-plane A/B. Both
    sides trace spans to ``tdir`` (the baseline is the traced bench, so
    the delta prices ONLY the live plane, not tracing itself); the
    live_on side additionally ships tele frames and aggregates
    ``fleet_health.json`` on the router. Returns (best wall seconds,
    new tokens, outputs, health doc or None, root count)."""
    import numpy as np

    from paddle_tpu.serving import Router

    extra = {"PADDLE_TPU_TELEMETRY_DIR": tdir}
    if live_on:
        extra["PADDLE_TPU_LIVE_TELEMETRY"] = "1"
    procs = [_spawn_router_worker(
        args, master, ns,
        extra_env=dict(extra, PADDLE_TRAINER_ID=str(i + 1)))
        for i in range(2)]
    os.environ.update(extra)  # router = rank 0
    health = None
    try:
        router = Router(store, namespace=ns, queue_limit=256,
                        dataplane=args.dataplane,
                        engine_grace_s=120.0, page_size=args.page_size,
                        seed=args.seed, affinity_slack_tokens=128,
                        max_inflight_per_engine=64,
                        deadlines={"interactive": 600.0,
                                   "standard": 600.0, "batch": 600.0})
        if live_on:
            from paddle_tpu.observability import live
            # wide window so slow boxes can't age the first trial's
            # roots out before the reconcile read; tight health cadence
            # so the post-drain pump converges quickly
            router._live_agg = live.LiveAggregator(window_s=600.0,
                                                   health_interval_s=0.5)
        deadline = time.monotonic() + 300.0
        while router._known_engines < 2:
            if time.monotonic() > deadline:
                raise RuntimeError("router bench: live-plane workers "
                                   "never registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("router bench: live-plane worker "
                                       f"died rc={p.returncode}")
            router.pump()
            time.sleep(0.05)
        rng = np.random.default_rng(args.seed + 4)
        sub = _router_traffic(args, rng)[::3]
        for prompt, slo, new in sub:  # warmup: store path + any residual
            router.submit(prompt, slo=slo, max_new_tokens=new)
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError("router bench: live-plane warmup "
                               f"undrained {router.stats()}")
        trials = []
        all_rids = []
        for _trial in range(2):
            t0 = time.perf_counter()
            rids = [router.submit(p, slo=slo, max_new_tokens=new)
                    for p, slo, new in sub]
            if not router.drain(timeout=600.0, poll=0.02):
                raise RuntimeError("router bench: live-plane phase "
                                   f"undrained {router.stats()}")
            trials.append((time.perf_counter() - t0, rids))
            all_rids.extend(rids)
        wall, rids = min(trials, key=lambda t: t[0])
        new_tokens = sum(len(router.result(r)) - len(p)
                         for r, (p, _s, _n) in zip(rids, sub))
        outputs = [np.asarray(router.result(r)) for r in all_rids]
        roots = 3 * len(sub)  # warmup round + two timed trials
        if live_on:
            # keep pumping until every root's tele frame has landed in
            # the aggregate and a health doc covering them is on disk
            hp = os.path.join(tdir, "fleet_health.json")
            deadline = time.monotonic() + 90.0
            while time.monotonic() < deadline:
                router.pump()
                time.sleep(0.02)
                if not os.path.exists(hp):
                    continue
                with open(hp) as f:
                    health = json.load(f)
                total = sum(c["requests"]
                            for c in health.get("classes", {}).values())
                if total >= roots:
                    break
            else:
                raise RuntimeError(
                    "router bench: fleet_health.json never converged "
                    f"({health and health.get('classes')})")
        router.shutdown()
        for p in procs:
            p.wait(timeout=60)
    finally:
        for k in extra:
            os.environ.pop(k, None)
    return wall, int(new_tokens), outputs, health, roots


def run_live_plane(args):
    """Live-telemetry-plane A/B: the SAME traced 2-worker workload with
    the live plane off and on. Gates that the plane is (a) free at the
    request path — tokens/s within ``--max-live-overhead`` of live-off
    and greedy outputs BIT-EQUAL — and (b) honest: the streamed
    ``fleet_health.json`` burn rates reconcile with the post-hoc span
    summary to within 5%."""
    import tempfile

    import numpy as np

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.protocol import SLO_OBJECTIVES
    from paddle_tpu.runtime import TCPStore

    port = _free_port()
    store = TCPStore(host="127.0.0.1", port=port, is_master=True,
                     timeout=60.0)
    master = f"127.0.0.1:{port}"
    try:
        print("router: live-plane A/B, live OFF (traced baseline)...",
              file=sys.stderr)
        off_dir = tempfile.mkdtemp(prefix="bench_live_off_")
        off_wall, off_tokens, off_out, _h, _r = _live_phase(
            args, store, master, "__benchl0", off_dir, live_on=False)
        print("router: live-plane A/B, live ON...", file=sys.stderr)
        on_dir = tempfile.mkdtemp(prefix="bench_live_on_")
        on_wall, on_tokens, on_out, health, roots = _live_phase(
            args, store, master, "__benchl1", on_dir, live_on=True)
    finally:
        store.close()
    for a, b in zip(off_out, on_out):
        np.testing.assert_array_equal(
            a, b, err_msg="token streams changed with the live "
                          "telemetry plane enabled")
    spans = tracing.load_spans(on_dir)
    posthoc = tracing.summarize_spans(spans,
                                      objectives=dict(SLO_OBJECTIVES))
    reconcile = {}
    worst = 0.0
    for cls, ent in sorted(health["classes"].items()):
        post = posthoc["classes"][cls]
        row = {"requests_live": ent["requests"],
               "requests_posthoc": post["requests"]}
        for key in ("frac_over_target", "burn_rate_latency",
                    "frac_unavailable", "burn_rate_availability"):
            lv = ent["objectives"][key]
            pv = post["objectives"][key]
            if max(abs(lv), abs(pv)) > 1e-9:
                worst = max(worst, abs(lv - pv) / max(abs(pv), 1e-9))
            row[key] = {"live": lv, "posthoc": pv}
        lp = ent["latency_seconds"]["p95"]
        pp = post["latency_seconds"]["p95"]
        row["latency_p95_seconds"] = {"live": lp, "posthoc": pp}
        reconcile[cls] = row
    requests_match = all(
        r["requests_live"] == r["requests_posthoc"]
        for r in reconcile.values())
    off_tps = off_tokens / off_wall
    on_tps = on_tokens / on_wall
    return {
        "workers": 2,
        "requests_per_phase": roots,
        "live_off": {"seconds": round(off_wall, 4),
                     "new_tokens": off_tokens,
                     "tokens_per_second": round(off_tps, 2)},
        "live_on": {"seconds": round(on_wall, 4),
                    "new_tokens": on_tokens,
                    "tokens_per_second": round(on_tps, 2),
                    "spans": len(spans),
                    "health_sources": len(health.get("sources", {}))},
        "overhead_frac": round(1.0 - on_tps / off_tps, 4),
        "greedy_bit_equal": True,
        "burn_reconcile": reconcile,
        "burn_reconcile_requests_match": requests_match,
        "burn_reconcile_worst_rel_diff": round(worst, 4),
    }


def _gate_live_plane(args, block):
    rc = 0
    if (args.max_live_overhead
            and block["overhead_frac"] > args.max_live_overhead):
        print(f"FAIL: live-plane overhead {block['overhead_frac']:.4f} "
              f"> max {args.max_live_overhead} of live-off tokens/s",
              file=sys.stderr)
        rc = 1
    if not block["burn_reconcile_requests_match"]:
        print("FAIL: live health request counts diverged from the "
              "post-hoc trace summary", file=sys.stderr)
        rc = 1
    if block["burn_reconcile_worst_rel_diff"] > 0.05:
        print(f"FAIL: live burn rates off by "
              f"{block['burn_reconcile_worst_rel_diff']:.4f} rel from "
              "the post-hoc summary (max 0.05)", file=sys.stderr)
        rc = 1
    return rc


def _tenant_traffic(args, rng):
    """Multi-tenant mix over the routed workload: one hot tenant takes
    ~60% of requests across every SLO class and a long tail of
    background tenants splits the rest — the shape the heavy-hitter
    sketch is built for. Returns [(prompt, slo, new, tenant), ...]."""
    tail = ("bravo", "coyote", "delta", "echo")
    out = []
    for i, (prompt, slo, new) in enumerate(_router_traffic(args, rng)[::3]):
        tenant = "acme" if i % 5 < 3 else tail[(i // 5) % len(tail)]
        out.append((prompt, slo, new, tenant))
    return out


def _tenant_phase(args, store, master, ns, tdir, accounting_on):
    """One live-traced 2-worker routed phase for the tenant-accounting
    A/B. BOTH sides run the live telemetry plane and submit the same
    tenant labels (identical wire records), so the delta prices ONLY
    the metering ledger + its tele-frame shipping. Returns (best wall
    seconds, new tokens, outputs, health doc, roots, expected per-
    tenant {prefill, requests}, measured per-tenant decode tokens)."""
    import numpy as np

    from paddle_tpu.observability import live
    from paddle_tpu.serving import Router

    extra = {"PADDLE_TPU_TELEMETRY_DIR": tdir,
             "PADDLE_TPU_LIVE_TELEMETRY": "1",
             "PADDLE_TPU_TENANT_ACCOUNTING": "1" if accounting_on else "0"}
    procs = [_spawn_router_worker(
        args, master, ns,
        extra_env=dict(extra, PADDLE_TRAINER_ID=str(i + 1)))
        for i in range(2)]
    os.environ.update(extra)  # router = rank 0
    health = None
    try:
        router = Router(store, namespace=ns, queue_limit=256,
                        dataplane=args.dataplane,
                        engine_grace_s=120.0, page_size=args.page_size,
                        seed=args.seed, affinity_slack_tokens=128,
                        max_inflight_per_engine=64,
                        deadlines={"interactive": 600.0,
                                   "standard": 600.0, "batch": 600.0})
        router._live_agg = live.LiveAggregator(window_s=600.0,
                                               health_interval_s=0.5)
        deadline = time.monotonic() + 300.0
        while router._known_engines < 2:
            if time.monotonic() > deadline:
                raise RuntimeError("router bench: tenant-phase workers "
                                   "never registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("router bench: tenant-phase worker "
                                       f"died rc={p.returncode}")
            router.pump()
            time.sleep(0.05)
        rng = np.random.default_rng(args.seed + 6)
        sub = _tenant_traffic(args, rng)
        rounds = []
        # warmup round (compile + store path), then two timed trials;
        # the ledger meters ALL of them, so conservation is checked
        # against every round's prompts and outputs
        rids = [router.submit(p, slo=slo, max_new_tokens=new,
                              tenant=tenant)
                for p, slo, new, tenant in sub]
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError("router bench: tenant warmup "
                               f"undrained {router.stats()}")
        rounds.append(rids)
        trials = []
        for _trial in range(2):
            t0 = time.perf_counter()
            rids = [router.submit(p, slo=slo, max_new_tokens=new,
                                  tenant=tenant)
                    for p, slo, new, tenant in sub]
            if not router.drain(timeout=600.0, poll=0.02):
                raise RuntimeError("router bench: tenant phase "
                                   f"undrained {router.stats()}")
            trials.append((time.perf_counter() - t0, rids))
            rounds.append(rids)
        wall, rids = min(trials, key=lambda t: t[0])
        new_tokens = sum(len(router.result(r)) - len(p)
                         for r, (p, _s, _n, _t) in zip(rids, sub))
        outputs = [np.asarray(router.result(r))
                   for rnd in rounds for r in rnd]
        roots = len(rounds) * len(sub)
        expected = {}
        decode_by_tenant = {}
        for rnd in rounds:
            for r, (p, _slo, _new, tenant) in zip(rnd, sub):
                ent = expected.setdefault(tenant,
                                          {"requests": 0,
                                           "prefill_tokens": 0})
                ent["requests"] += 1
                ent["prefill_tokens"] += int(len(p))
                decode_by_tenant[tenant] = (
                    decode_by_tenant.get(tenant, 0)
                    + len(router.result(r)) - len(p))
        # pump until a health doc covering every root (and, with the
        # ledger on, every metered request) has landed on disk
        hp = os.path.join(tdir, "fleet_health.json")
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            router.pump()
            time.sleep(0.02)
            if not os.path.exists(hp):
                continue
            with open(hp) as f:
                health = json.load(f)
            total = sum(c["requests"]
                        for c in health.get("classes", {}).values())
            metered = (health.get("tenants", {})
                       .get("fleet", {}).get("requests", 0))
            if total >= roots and (not accounting_on or metered >= roots):
                break
        else:
            raise RuntimeError(
                "router bench: tenant-phase fleet_health.json never "
                f"converged (accounting_on={accounting_on}, "
                f"{health and health.get('tenants', {}).get('fleet')})")
        router.shutdown()
        for p in procs:
            p.wait(timeout=60)
    finally:
        for k in extra:
            os.environ.pop(k, None)
    return (wall, int(new_tokens), outputs, health, roots, expected,
            decode_by_tenant)


def run_tenants(args):
    """Per-tenant accounting A/B: the SAME live-traced multi-tenant
    workload with the metering ledger off and on. Gates that the
    ledger is (a) free at the request path — tokens/s within
    ``--max-tenant-overhead`` of ledger-off and greedy outputs
    BIT-EQUAL — (b) conservative: every int field of the streamed
    ``tenants`` block sums EXACTLY across tenants to the fleet total,
    and requests/prefill/decode match the bench's own ground truth —
    and (c) honest post hoc: scripts/tenant_report.py reconciles the
    event log against the live ledger to within 5%."""
    import subprocess
    import tempfile

    import numpy as np

    from paddle_tpu.observability.accounting import INT_FIELDS
    from paddle_tpu.runtime import TCPStore

    port = _free_port()
    store = TCPStore(host="127.0.0.1", port=port, is_master=True,
                     timeout=60.0)
    master = f"127.0.0.1:{port}"
    try:
        print("router: tenant-accounting A/B, ledger OFF (live "
              "baseline)...", file=sys.stderr)
        off_dir = tempfile.mkdtemp(prefix="bench_tenant_off_")
        off_wall, off_tokens, off_out, _h, _r, _e, _d = _tenant_phase(
            args, store, master, "__bencht0", off_dir, accounting_on=False)
        print("router: tenant-accounting A/B, ledger ON...",
              file=sys.stderr)
        on_dir = tempfile.mkdtemp(prefix="bench_tenant_on_")
        (on_wall, on_tokens, on_out, health, roots, expected,
         decode_by_tenant) = _tenant_phase(
            args, store, master, "__bencht1", on_dir, accounting_on=True)
    finally:
        store.close()
    for a, b in zip(off_out, on_out):
        np.testing.assert_array_equal(
            a, b, err_msg="token streams changed with tenant "
                          "accounting enabled")
    tn = health["tenants"]
    fleet, per_tenant = tn["fleet"], tn["per_tenant"]
    # conservation: int fields sum EXACTLY across tenants to the fleet
    # total, and the ledger agrees with the bench's own ground truth
    problems = []
    for f in INT_FIELDS:
        if fleet[f] != sum(c[f] for c in per_tenant.values()):
            problems.append(f"fleet {f} {fleet[f]} != per-tenant sum")
    if fleet["requests"] != roots:
        problems.append(f"fleet requests {fleet['requests']} != {roots}")
    exp_prefill = sum(e["prefill_tokens"] for e in expected.values())
    if fleet["prefill_tokens"] != exp_prefill:
        problems.append(f"fleet prefill {fleet['prefill_tokens']} != "
                        f"submitted prompt tokens {exp_prefill}")
    exp_decode = sum(decode_by_tenant.values())
    if fleet["decode_tokens"] != exp_decode:
        problems.append(f"fleet decode {fleet['decode_tokens']} != "
                        f"served new tokens {exp_decode}")
    for tenant, ent in sorted(expected.items()):
        cell = per_tenant.get(tenant)
        if cell is None:
            problems.append(f"tenant {tenant} missing from ledger")
            continue
        for f, want in (("requests", ent["requests"]),
                        ("prefill_tokens", ent["prefill_tokens"]),
                        ("decode_tokens", decode_by_tenant[tenant])):
            if cell[f] != want:
                problems.append(
                    f"tenant {tenant} {f} {cell[f]} != {want}")
    conservation_exact = not problems
    for p in problems:
        print(f"tenant conservation: {p}", file=sys.stderr)
    top = tn["top"]
    hot_rank0 = bool(top) and top[0]["tenant"] == "acme"
    # post-hoc reconcile: event log vs the live ledger, priced the same
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report_path = os.path.join(on_dir, "tenant_report.json")
    rc = subprocess.call(
        [sys.executable, os.path.join(repo, "scripts", "tenant_report.py"),
         on_dir, "--health", os.path.join(on_dir, "fleet_health.json"),
         "--out", report_path, "--max-rel-diff", "0.05"], cwd=repo)
    reconcile_worst = None
    if os.path.exists(report_path):
        with open(report_path) as f:
            reconcile_worst = (json.load(f).get("reconcile", {})
                               .get("worst_rel_diff"))
    off_tps = off_tokens / off_wall
    on_tps = on_tokens / on_wall
    return {
        "workers": 2,
        "requests_per_phase": roots,
        "tenants": {t: e["requests"] for t, e in sorted(expected.items())},
        "hot_tenant": "acme",
        "accounting_off": {"seconds": round(off_wall, 4),
                           "new_tokens": off_tokens,
                           "tokens_per_second": round(off_tps, 2)},
        "accounting_on": {"seconds": round(on_wall, 4),
                          "new_tokens": on_tokens,
                          "tokens_per_second": round(on_tps, 2)},
        "overhead_frac": round(1.0 - on_tps / off_tps, 4),
        "greedy_bit_equal": True,
        "conservation_exact": conservation_exact,
        "conservation_problems": problems,
        "fleet": fleet,
        "per_tenant": per_tenant,
        "hot_tenant_rank0": hot_rank0,
        "heavy_hitter_top": [
            {k: r[k] for k in ("tenant", "rank", "device_seconds",
                               "sketch_count", "sketch_error")
             if k in r} for r in top[:3]],
        "tenant_report_rc": rc,
        "reconcile_worst_rel_diff": reconcile_worst,
    }


def _gate_tenants(args, block):
    rc = 0
    if (args.max_tenant_overhead
            and block["overhead_frac"] > args.max_tenant_overhead):
        print(f"FAIL: tenant-accounting overhead "
              f"{block['overhead_frac']:.4f} > max "
              f"{args.max_tenant_overhead} of ledger-off tokens/s",
              file=sys.stderr)
        rc = 1
    if not block["conservation_exact"]:
        print("FAIL: per-tenant ledger does not conserve — per-tenant "
              "sums or bench ground truth diverged from fleet totals",
              file=sys.stderr)
        rc = 1
    if not block["hot_tenant_rank0"]:
        print("FAIL: heavy-hitter sketch did not rank the hot tenant "
              "first", file=sys.stderr)
        rc = 1
    if block["tenant_report_rc"] != 0:
        print(f"FAIL: tenant_report.py reconcile rc="
              f"{block['tenant_report_rc']} (event log vs live ledger "
              "off by more than 5%)", file=sys.stderr)
        rc = 1
    return rc


class _PacedTrainer:
    """Emulated data-parallel training job riding the serving fleet:
    fixed global batch, so the wall time of one optimizer step is
    ``base_step_s / width`` and steps/s is proportional to the number
    of devices currently lent to training. ``resize`` is the
    supervisor executor's resize hook."""

    def __init__(self, base_step_s):
        self.base_step_s = base_step_s
        self.width = 0
        self.steps = 0
        self._due = None

    def resize(self, source_width, target_width):
        self.width = int(target_width)
        self._due = None

    def tick(self):
        if self.width < 1:
            self._due = None
            return
        now = time.monotonic()
        if self._due is None:
            self._due = now + self.base_step_s / self.width
        while now >= self._due:
            self.steps += 1
            self._due += self.base_step_s / self.width


def _autoscale_traffic(args, rng):
    """The bursty side of the colocation experiment: per burst,
    ``--autoscale-burst`` latency-sensitive chat requests with unique
    prompts (no shared prefix — affinity must not serialize the burst
    onto one engine). Burst peaks need BOTH engines to stay inside the
    interactive latency target; the lulls between bursts are the slack
    the autoscaler should lend to training."""
    import numpy as np

    bursts = []
    n_bursts = args.autoscale_cycles * 3
    for _ in range(n_bursts):
        burst = []
        for _ in range(args.autoscale_burst):
            plen = int(rng.integers(16, 25))
            prompt = rng.integers(0, args.vocab, plen, dtype=np.int64)
            burst.append((prompt, "interactive", 20))
        bursts.append(burst)
    return bursts


def _autoscale_phase(args, mode, bursts):
    """One colocation phase over the shared burst schedule. ``mode``:

    * ``static_serving`` — both engines serve, no training (2+0);
    * ``static_split``   — one serves, one trains all run (1+1);
    * ``colocated``      — the fleet supervisor flips the second engine
      between roles off the live plane's fleet_health.json.

    Returns the per-mode measurement row plus the raw outputs for the
    bit-equal gate."""
    import tempfile

    import numpy as np

    from paddle_tpu.distributed.fleet.supervisor import (
        FleetSupervisor, StoreFleetExecutor, SupervisorConfig,
        read_health)
    from paddle_tpu.observability import live
    from paddle_tpu.runtime import TCPStore
    from paddle_tpu.serving import Router
    from paddle_tpu.serving.protocol import SLO_OBJECTIVES

    ns = f"__bencha_{mode}"
    tdir = tempfile.mkdtemp(prefix=f"bench_autoscale_{mode}_")
    wargs = argparse.Namespace(**vars(args))
    wargs.router_slots = 4
    wargs.router_step_floor_ms = args.autoscale_step_floor_ms
    port = _free_port()
    store = TCPStore(host="127.0.0.1", port=port, is_master=True,
                     timeout=60.0)
    master = f"127.0.0.1:{port}"
    extra = {"PADDLE_TPU_TELEMETRY_DIR": tdir,
             "PADDLE_TPU_LIVE_TELEMETRY": "1"}
    procs = [_spawn_router_worker(
        wargs, master, ns, extra_env=dict(extra,
                                          PADDLE_TRAINER_ID=str(i + 1)))
        for i in range(2)]
    os.environ.update(extra)
    health_path = os.path.join(tdir, "fleet_health.json")
    cycle_s = args.autoscale_cycle_s
    burst_gap_s = 0.8
    trainer = _PacedTrainer(args.autoscale_train_step_ms / 1000.0)
    try:
        # a tight inflight cap keeps burst overflow in the ADMISSION
        # queue, where the live plane's queue gauge (and therefore the
        # supervisor's backlog signal) can see it
        router = Router(store, namespace=ns, queue_limit=512,
                        dataplane=args.dataplane,
                        engine_grace_s=120.0, page_size=args.page_size,
                        seed=args.seed, affinity_slack_tokens=64,
                        max_inflight_per_engine=6,
                        deadlines={"interactive": 600.0,
                                   "standard": 600.0, "batch": 600.0})
        # short window so burst-era samples age out within one lull and
        # the supervisor sees a calm fleet before the next cycle
        router._live_agg = live.LiveAggregator(window_s=8.0,
                                               health_interval_s=0.2)
        deadline = time.monotonic() + 300.0
        while router._known_engines < 2:
            if time.monotonic() > deadline:
                raise RuntimeError("autoscale bench: workers never "
                                   "registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("autoscale bench: worker died "
                                       f"rc={p.returncode}")
            router.pump()
            time.sleep(0.05)
        names = sorted(router._engines)
        executor = StoreFleetExecutor(
            store, namespace=ns, router=router,
            resize_fn=trainer.resize,
            pump=lambda: (router.pump(), trainer.tick()), poll_s=0.02)
        # store-path warmup with BOTH engines serving (workers already
        # pre-compiled their buckets via --warmup). Batch class: the
        # first requests pay one-off transport setup that would blow
        # the interactive target and poison the burn window the
        # supervisor steers by
        wrng = np.random.default_rng(args.seed + 8)
        for _ in range(6):
            plen = int(wrng.integers(16, 25))
            router.submit(wrng.integers(0, args.vocab, plen,
                                        dtype=np.int64),
                          slo="batch", max_new_tokens=20)
        if not router.drain(timeout=120.0, poll=0.02):
            raise RuntimeError("autoscale bench: warmup undrained "
                               f"{router.stats()}")
        sup = None
        if mode != "static_serving":
            # lend names[-1] to training before the clock starts
            if not executor.drain(names[-1], deadline_s=10.0):
                raise RuntimeError("autoscale bench: initial drain of "
                                   f"{names[-1]} timed out")
            trainer.resize(0, 1)
        if mode == "colocated":
            sup = FleetSupervisor(
                os.path.join(tdir, "journal"), executor=executor,
                config=SupervisorConfig(
                    high_burn=1.0, low_burn=0.75, queue_high=6,
                    hysteresis_s=0.25, cooldown_s=1.5,
                    breaker_window_s=60.0, breaker_max_flips=10,
                    min_serving=1, drain_timeout_s=5.0,
                    namespace=ns),
                health_path=health_path,
                roles={names[0]: "serving", names[-1]: "training"},
                training_width=1)
        trainer.steps = 0
        events = [c * cycle_s + b * burst_gap_s
                  for c in range(args.autoscale_cycles)
                  for b in range(3)]
        t_end = args.autoscale_cycles * cycle_s
        submitted = []
        last_health, peak_burn, peak_backlog = {}, 0.0, 0
        next_ctl = 0.0
        ei = 0
        t0 = time.monotonic()
        while True:
            now = time.monotonic() - t0
            if ei < len(events) and now >= events[ei]:
                for prompt, slo, new in bursts[ei]:
                    rid = router.submit(prompt, slo=slo,
                                        max_new_tokens=new)
                    submitted.append((rid, prompt, slo))
                ei += 1
            if now >= next_ctl:
                next_ctl = now + 0.1
                last_health = read_health(health_path) or last_health
                sig = FleetSupervisor._signals(last_health)
                peak_burn = max(peak_burn, sig["max_burn"])
                peak_backlog = max(peak_backlog, sig["admission_backlog"])
                if sup is not None:
                    sup.tick(last_health, time.monotonic())
            router.pump()
            trainer.tick()
            time.sleep(0.01)
            if now >= t_end and ei == len(events):
                break
        wall = time.monotonic() - t0
        steps = trainer.steps
        if not router.drain(timeout=120.0, poll=0.02):
            raise RuntimeError(f"autoscale bench: {mode} undrained "
                               f"{router.stats()}")
        goodput_tokens = new_tokens = 0
        misses = 0
        outputs = []
        for rid, prompt, slo in submitted:
            req = router._requests[rid]
            out = np.asarray(router.result(rid))
            outputs.append(out)
            produced = len(out) - len(prompt)
            new_tokens += produced
            target = SLO_OBJECTIVES[slo]["latency_target_s"]
            if req.finish_t - req.submit_t <= target:
                goodput_tokens += produced
            else:
                misses += 1
        row = {
            "new_tokens": int(new_tokens),
            "goodput_tokens": int(goodput_tokens),
            "seconds": round(wall, 4),
            "goodput_tokens_per_second": round(goodput_tokens / wall, 2),
            "slo_miss_frac": round(misses / max(1, len(submitted)), 4),
            "train_steps": int(steps),
            "train_steps_per_second": round(steps / wall, 2),
            "final_max_burn": round(
                FleetSupervisor._signals(last_health)["max_burn"], 3),
            "peak_burn": round(peak_burn, 3),
            "peak_admission_backlog": int(peak_backlog),
            "failover_resubmits":
                router.counters.get("failover_resubmits", 0),
        }
        if sup is not None:
            doc = sup.roles_doc
            hist = sup.journal.history()
            if sup.journal.pending() is not None:
                raise RuntimeError("autoscale bench: supervisor left a "
                                   "pending flip in the journal")
            row["flips_committed"] = int(doc.get("flips_committed", 0))
            row["flip_directions"] = sorted(
                {e.get("direction") for e in hist
                 if e.get("outcome") == "committed"})
            row["rollbacks"] = sum(
                1 for e in hist if e.get("outcome") != "committed")
        # lift any standing drain order so both workers see the
        # shutdown broadcast promptly
        executor.activate(names[-1], "serving")
        for _ in range(20):
            router.pump()
            time.sleep(0.02)
        router.shutdown()
        for p in procs:
            p.wait(timeout=60)
    finally:
        for k in extra:
            os.environ.pop(k, None)
        store.close()
    return row, outputs


def run_autoscale(args):
    """Train/serve colocation A/B/C (docs/COLOCATION.md): the SAME
    bursty interactive workload plus a width-paced training job under
    (a) both engines statically serving, (b) a static 1+1 split, and
    (c) the fleet supervisor autoscaling roles off fleet_health.json.

    Score = SLO-goodput tokens/s normalized to the all-serving split
    PLUS training steps/s normalized to the static 1+1 split — goodput,
    because a response landing past its latency target is worthless to
    the caller, which is exactly the cost the colocated fleet avoids by
    borrowing the training engine at burst peaks and handing it back in
    the lulls. Gates: the colocated score beats BOTH statics, its burn
    ends under objective, and greedy outputs stay bit-equal."""
    import numpy as np

    rng = np.random.default_rng(args.seed + 9)
    bursts = _autoscale_traffic(args, rng)
    rows, outputs = {}, {}
    for mode in ("static_serving", "static_split", "colocated"):
        print(f"autoscale: {mode} phase "
              f"({args.autoscale_cycles} cycles x "
              f"{args.autoscale_cycle_s:.0f}s)...", file=sys.stderr)
        rows[mode], outputs[mode] = _autoscale_phase(args, mode, bursts)
    for mode in ("static_split", "colocated"):
        for a, b in zip(outputs["static_serving"], outputs[mode]):
            np.testing.assert_array_equal(
                a, b, err_msg=f"token streams changed under {mode} "
                              "role management")
    base_tps = rows["static_serving"]["goodput_tokens_per_second"]
    base_sps = rows["static_split"]["train_steps_per_second"]
    for row in rows.values():
        row["score"] = round(
            row["goodput_tokens_per_second"] / max(base_tps, 1e-9)
            + row["train_steps_per_second"] / max(base_sps, 1e-9), 4)
    best_static = max(rows["static_serving"]["score"],
                      rows["static_split"]["score"])
    colo = rows["colocated"]
    return {
        "workers": 2,
        "cycles": args.autoscale_cycles,
        "cycle_seconds": args.autoscale_cycle_s,
        "bursts_per_cycle": 3,
        "burst_requests": args.autoscale_burst,
        "slo_class": "interactive",
        "device_step_floor_ms": args.autoscale_step_floor_ms,
        "train_base_step_ms": args.autoscale_train_step_ms,
        "score_definition": ("goodput_tokens_per_second / static_serving"
                            " + train_steps_per_second / static_split"),
        "modes": rows,
        "best_static_score": best_static,
        "colocated_score": colo["score"],
        "colocated_margin": round(colo["score"] - best_static, 4),
        "greedy_bit_equal": True,
        "burn_under_objective": colo["final_max_burn"] < 1.0,
    }


def _gate_autoscale(args, block):
    rc = 0
    colo = block["modes"]["colocated"]
    if block["colocated_margin"] <= args.min_colocation_margin:
        print(f"FAIL: colocated score {block['colocated_score']} does "
              f"not beat best static split "
              f"{block['best_static_score']} by more than "
              f"{args.min_colocation_margin}", file=sys.stderr)
        rc = 1
    if not block["burn_under_objective"]:
        print(f"FAIL: colocated fleet ended with burn "
              f"{colo['final_max_burn']} >= 1.0 (over objective)",
              file=sys.stderr)
        rc = 1
    if colo.get("flips_committed", 0) < 2 or sorted(
            colo.get("flip_directions", [])) != ["to_serving",
                                                 "to_training"]:
        print("FAIL: supervisor never closed the loop in both "
              f"directions ({colo.get('flips_committed')} flips, "
              f"{colo.get('flip_directions')})", file=sys.stderr)
        rc = 1
    return rc


def run_disagg(args, store, master):
    """Disaggregated prefill/decode sub-scenario: the SAME long-prompt-
    heavy workload through 1 unified worker and through 1 prefill + 1
    decode worker (prefill streams finished KV pages to decode over the
    transport, raw wire). Token streams must be BIT-EQUAL — the
    disaggregation guarantee — and the report carries both tokens/s
    (the prefill offload frees the decode engine's step budget)."""
    import numpy as np

    from paddle_tpu.serving import Router

    def rand(rng, n):
        return rng.integers(0, args.vocab, n, dtype=np.int64)

    results = {}
    outputs = {}
    for label, roles in (("unified", [None]),
                         ("disaggregated", ["prefill", "decode"])):
        ns = f"__benchg{label[0]}"
        print(f"router: disagg scenario, {label} fleet "
              f"({len(roles)} worker(s))...", file=sys.stderr)
        procs = [_spawn_router_worker(args, master, ns, role=r)
                 for r in roles]
        router = Router(store, namespace=ns, queue_limit=256,
                        engine_grace_s=120.0, page_size=args.page_size,
                        seed=args.seed, affinity_slack_tokens=128,
                        max_inflight_per_engine=64,
                        prefill_threshold_tokens=96,
                        deadlines={"interactive": 600.0,
                                   "standard": 600.0, "batch": 600.0})
        deadline = time.monotonic() + 300.0
        while router._known_engines < len(roles):
            if time.monotonic() > deadline:
                raise RuntimeError("router bench: disagg workers never "
                                   "registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("router bench: disagg worker died "
                                       f"rc={p.returncode}")
            router.pump()
            time.sleep(0.05)
        rng = np.random.default_rng(args.seed + 3)
        traffic = ([(rand(rng, 160), "standard", 32) for _ in range(10)]
                   + [(rand(rng, 40), "interactive", 16)
                      for _ in range(6)])
        # warmup pass exercises the KV-stream path end to end before
        # timing (first import compiles the pool write)
        for prompt, slo, new in traffic[::5]:
            router.submit(prompt, slo=slo, max_new_tokens=new)
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError("router bench: disagg warmup undrained "
                               f"{router.stats()}")
        t0 = time.perf_counter()
        rids = [router.submit(p, slo=slo, max_new_tokens=new)
                for p, slo, new in traffic]
        if not router.drain(timeout=600.0, poll=0.02):
            raise RuntimeError("router bench: disagg phase undrained "
                               f"{router.stats()}")
        wall = time.perf_counter() - t0
        new_tokens = sum(len(router.result(r)) - len(p)
                         for r, (p, _s, _n) in zip(rids, traffic))
        st = router.stats()
        outputs[label] = [np.asarray(router.result(r)) for r in rids]
        results[label] = {
            "workers": len(roles),
            "requests": len(rids),
            "new_tokens": int(new_tokens),
            "seconds": round(wall, 4),
            "tokens_per_second": round(new_tokens / wall, 2),
            "disagg_dispatches": st["disagg_dispatches"],
        }
        router.shutdown()
        for p in procs:
            p.wait(timeout=60)
    for a, b in zip(outputs["unified"], outputs["disaggregated"]):
        np.testing.assert_array_equal(
            a, b, err_msg="disaggregated prefill/decode diverged from "
                          "the unified fleet")
    assert results["disaggregated"]["disagg_dispatches"] > 0
    return {
        "prefill_threshold_tokens": 96,
        "kv_wire": "raw",
        "unified": results["unified"],
        "disaggregated": results["disaggregated"],
        "bit_equal": True,
    }


def _replay_args(args):
    """Reduced-size argument set for the embedded replay legs: the full
    1M-request run (plus the subprocess scaling leg) is
    scripts/bench_replay.py's job -> BENCH_REPLAY.json; this block is
    the smoke-sized version that rides in BENCH_SERVING.json."""
    import bench_replay
    r = bench_replay.build_parser().parse_args([])
    r.requests = args.replay_requests
    r.determinism_requests = min(20_000, args.replay_requests)
    r.quota_requests = min(15_000, args.replay_requests)
    r.dispatch_requests = min(10_000, args.replay_requests)
    r.budget_s = 300.0
    return r


def run_replay(args):
    import bench_replay
    rargs = _replay_args(args)
    print(f"[bench] replay: {rargs.requests}-request stub-tier legs "
          "(throughput/determinism/quota/dispatch)...", file=sys.stderr)
    block = {
        "requests": rargs.requests,
        "throughput": bench_replay.run_throughput(rargs),
        "determinism": bench_replay.run_determinism(rargs),
        "quota": bench_replay.run_quota(rargs),
        "dispatch": bench_replay.run_dispatch(rargs),
        "full_bench": "scripts/bench_replay.py -> BENCH_REPLAY.json "
                      "(1M requests + 2-leaf scaling leg)",
    }
    return block


def _gate_replay(args, block):
    import bench_replay
    # bench_replay's own gate handles the missing scaling leg
    return bench_replay.gate(_replay_args(args), block)


# ---------------------------------------------------------------------------
# online: zero-drain weight flips vs drain-and-restart (docs/ONLINE.md)
# ---------------------------------------------------------------------------

def _online_cfg(args, max_len):
    from paddle_tpu.text.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_hidden_layers=args.layers, num_attention_heads=args.heads,
        max_position_embeddings=max_len,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


def _online_snap(model):
    import numpy as np
    return {n: np.asarray(p._value, np.float32).copy()
            for n, p in model.named_parameters()}


def _online_set(model, params):
    import jax.numpy as jnp
    import numpy as np
    for n, p in model.named_parameters():
        p._value = jnp.asarray(params[n],
                               np.asarray(p._value).dtype)


def _online_bf16(params):
    """What an engine actually holds after a bf16-wire flip: replay
    references and the drain-restart baseline must round the same way or
    the bit-equality legs compare against weights no engine ever ran."""
    import jax.numpy as jnp
    import numpy as np
    return {n: np.asarray(jnp.asarray(v, jnp.bfloat16)).astype(np.float32)
            for n, v in params.items()}


def _online_train(args, cfg, batches, on_epoch=None):
    """One deterministic AdamW run over the scripted batches. Returns
    (params-per-epoch, loss trajectory). ``on_epoch(e, params)`` fires
    after each epoch's steps — the online phase publishes from it."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTForCausalLM
    paddle.seed(args.seed + 41)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    params = {0: _online_snap(model)}
    losses = []
    for e in range(1, args.online_epochs + 1):
        for ids_np in batches[e]:
            ids = paddle.to_tensor(ids_np)
            loss = model(ids, labels=ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        params[e] = _online_snap(model)
        if on_epoch is not None:
            on_epoch(e, params[e])
    return params, losses


class _OnlineDriver:
    """Single-threaded wave driver over a DecodeEngine: submits a wave,
    steps until done, records per-request latency, final tokens and the
    PINNED epoch the engine decoded the request on.

    ``step_floor_s`` paces step-to-step intervals the way the router
    scenario's --router-step-floor-ms does: it emulates an accelerator-
    bound step so the flip-window gate measures the weight stream's
    control-plane cost against realistic step times — host-side frame
    applies overlap device compute and hide in the floor's slack."""

    def __init__(self, engine, new_tokens, step_floor_s=0.0):
        self.engine = engine
        self.new_tokens = new_tokens
        self.step_floor_s = step_floor_s
        self._not_before = 0.0
        self._t_sub = {}
        self._tag = {}
        self.pending = set()
        self.results = {}   # key -> {"tokens", "epoch", "tag"}
        self.latencies = []  # (tag, seconds)

    def submit_wave(self, keys, prompts, tag):
        import time
        from paddle_tpu.inference.engine import SamplingParams
        for key, prompt in zip(keys, prompts):
            rid = self.engine.submit(
                prompt, SamplingParams(max_new_tokens=self.new_tokens))
            self._t_sub[rid] = (key, time.perf_counter())
            self._tag[rid] = tag
            self.pending.add(rid)

    def step(self):
        import time
        if self.step_floor_s:
            now = time.perf_counter()
            if now < self._not_before:
                time.sleep(self._not_before - now)
            self._not_before = time.perf_counter() + self.step_floor_s
        self.engine.step()
        now = time.perf_counter()
        for rid in [r for r in self.pending
                    if self.engine._requests[r].status == "done"]:
            self.pending.discard(rid)
            key, t0 = self._t_sub.pop(rid)
            tag = self._tag.pop(rid)
            if key in self.results:
                raise RuntimeError(f"duplicate completion for {key}")
            self.results[key] = {
                "tokens": [int(t) for t in self.engine.result(rid)],
                "epoch": int(self.engine._requests[rid].epoch),
                "tag": tag,
            }
            self.latencies.append((tag, now - t0))

    def run_until_idle(self):
        while self.pending:
            self.step()


class _SteppingSink:
    """EngineSink that keeps the engine decoding between wt frames — the
    single-threaded analogue of a worker applying the stream between
    poll rounds, at the worker's per-round frame budget
    (worker._WT_FRAMES_PER_POLL). This is the zero-drain property the
    goodput gate measures."""

    _FRAMES_PER_STEP = 2

    def __init__(self, inner, driver):
        self._inner = inner
        self._driver = driver
        self._frames = 0
        self.name = inner.name

    @property
    def known_epoch(self):
        return self._inner.known_epoch

    @known_epoch.setter
    def known_epoch(self, value):
        self._inner.known_epoch = value

    def send(self, frame):
        if self._driver.pending and self._frames % self._FRAMES_PER_STEP == 0:
            self._driver.step()
        self._frames += 1
        self._inner.send(frame)

    def pump(self):
        self._inner.pump()

    def collect_acks(self):
        return self._inner.collect_acks()

    def close(self):
        self._inner.close()


def run_online(args):
    """A/B the continuous-learning loop: identical wave workloads and
    identical trainer schedules served (a) through zero-drain journaled
    weight flips into ONE live engine and (b) by draining and rebuilding
    a fresh engine per epoch. Then replays every epoch on a fresh engine
    for the bit-equality legs."""
    import tempfile
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.supervisor import FlipJournal
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.serving.online import EngineSink, OnlineCoordinator
    from paddle_tpu.text.models.gpt import GPTForCausalLM

    EP = args.online_epochs
    W = args.online_waves
    slots = 4
    new_tok = args.online_new_tokens
    plen = args.prompt_len
    max_len = max(64, 1 << (plen + new_tok - 1).bit_length())
    cfg = _online_cfg(args, max_len)
    ecfg = EngineConfig(num_slots=slots, max_length=max_len)

    # scripted, phase-independent inputs
    rng = np.random.default_rng(args.seed + 77)
    prompts = {(e, w): [rng.integers(1, args.vocab, plen).astype(np.int64)
                        for _ in range(slots)]
               for e in range(EP + 1) for w in range(W)}
    drng = np.random.default_rng(args.seed + 99)
    batches = {e: [drng.integers(0, args.vocab, (4, 16)).astype(np.int32)
                   for _ in range(args.online_train_steps)]
               for e in range(1, EP + 1)}

    print(f"[bench] online: {EP} weight flips x {W} waves x {slots} reqs "
          f"(zero-drain vs drain-restart)...", file=sys.stderr)

    # offline trainer run: the loss-parity reference AND the baseline's
    # per-epoch weights
    params_off, losses_off = _online_train(args, cfg, batches)

    def wave_keys(e, w):
        return [(e, w, i) for i in range(slots)]

    # ---- phase A: one live engine, flips overlap the last wave --------
    model = GPTForCausalLM(cfg)
    model.eval()
    _online_set(model, params_off[0])
    eng = DecodeEngine(model, ecfg)
    eng.warmup()
    floor_s = args.online_step_floor_ms / 1e3
    driver = _OnlineDriver(eng, new_tok, floor_s)
    journal = FlipJournal(os.path.join(tempfile.mkdtemp(), "journal"))
    coord = OnlineCoordinator(
        journal, {"engine0": _SteppingSink(EngineSink(eng), driver)},
        yield_fn=lambda: driver.step() if driver.pending else None)
    cc0 = eng.compile_count
    flip_secs = []

    def publish(e, params):
        flip_secs.append(coord.publish_epoch(e, params)["seconds"])

    # trainer built before the clock; its step work runs inside the
    # timed window at the same schedule points as the baseline's
    paddle.seed(args.seed + 41)
    trainer = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=trainer.parameters())
    losses_on = []
    t0 = time.perf_counter()
    for e in range(EP + 1):
        for w in range(W):
            flip_wave = (w == W - 1) and e < EP
            if flip_wave:
                # train at the wave boundary (engine idle), then let the
                # flip's wt stream overlap the wave it precedes
                for ids_np in batches[e + 1]:
                    ids = paddle.to_tensor(ids_np)
                    loss = trainer(ids, labels=ids)
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                    losses_on.append(float(loss))
            driver.submit_wave(wave_keys(e, w), prompts[(e, w)],
                               "flip" if flip_wave else "steady")
            if flip_wave:
                for _ in range(3):
                    driver.step()
                publish(e + 1, _online_snap(trainer))
            driver.run_until_idle()
    online_s = time.perf_counter() - t0
    compile_stable = eng.compile_count == cc0
    online_results = driver.results
    online_lat = driver.latencies
    weight_history = [[h["id"], h["outcome"]]
                      for h in journal.weight_history()]

    # ---- phase B: drain, rebuild, re-warm per epoch -------------------
    model_b = GPTForCausalLM(cfg)
    model_b.eval()
    _online_set(model_b, params_off[0])
    eng_b = DecodeEngine(model_b, ecfg)
    eng_b.warmup()
    driver_b = _OnlineDriver(eng_b, new_tok, floor_s)
    paddle.seed(args.seed + 41)
    trainer_b = GPTForCausalLM(cfg)
    opt_b = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=trainer_b.parameters())
    losses_b = []
    compiles_b = 0
    t0 = time.perf_counter()
    for e in range(EP + 1):
        for w in range(W):
            flip_wave = (w == W - 1) and e < EP
            if flip_wave:
                for ids_np in batches[e + 1]:
                    ids = paddle.to_tensor(ids_np)
                    loss = trainer_b(ids, labels=ids)
                    loss.backward()
                    opt_b.step()
                    opt_b.clear_grad()
                    losses_b.append(float(loss))
            driver_b.submit_wave(wave_keys(e, w), prompts[(e, w)],
                                 "flip" if flip_wave else "steady")
            driver_b.run_until_idle()
            if flip_wave:
                # the drain already happened (wave ran to completion);
                # restart: fresh engine on the new weights, recompile
                compiles_b += eng_b.compile_count
                _online_set(model_b, _online_bf16(params_off[e + 1]))
                results_b, lat_b = driver_b.results, driver_b.latencies
                eng_b = DecodeEngine(model_b, ecfg)
                eng_b.warmup()
                driver_b = _OnlineDriver(eng_b, new_tok, floor_s)
                # carry the ledgers across restarts
                driver_b.results, driver_b.latencies = results_b, lat_b
    baseline_s = time.perf_counter() - t0
    compiles_b += eng_b.compile_count

    tokens = sum(len(r["tokens"]) - plen for r in online_results.values())
    tokens_b = sum(len(r["tokens"]) - plen
                   for r in driver_b.results.values())

    # ---- gates' raw material ------------------------------------------
    expected_keys = {(e, w, i) for e in range(EP + 1) for w in range(W)
                     for i in range(slots)}
    zero_dropped_dup = set(online_results) == expected_keys

    # pinned-epoch attribution: wave W-1 of epoch e admits BEFORE the
    # flip to e+1 lands, so every request of epoch-e waves decodes on e
    epochs_ok = all(r["epoch"] == e
                    for (e, _w, _i), r in online_results.items())

    # per-epoch bit-equal replay: ONE fresh engine re-runs the epoch
    # history through the same flip machinery and must reproduce every
    # wave bit-for-bit
    model_r = GPTForCausalLM(cfg)
    model_r.eval()
    _online_set(model_r, params_off[0])
    eng_r = DecodeEngine(model_r, ecfg)
    eng_r.warmup()
    driver_r = _OnlineDriver(eng_r, new_tok)
    coord_r = OnlineCoordinator(
        FlipJournal(os.path.join(tempfile.mkdtemp(), "journal")),
        {"engine0": EngineSink(eng_r)})
    replay_ok = True
    for e in range(EP + 1):
        if e > 0:
            coord_r.publish_epoch(e, params_off[e])
        for w in range(W):
            driver_r.submit_wave(wave_keys(e, w), prompts[(e, w)],
                                 "steady")
            driver_r.run_until_idle()
    for key, r in online_results.items():
        if driver_r.results[key]["tokens"] != r["tokens"]:
            replay_ok = False
    phases_equal = all(
        driver_b.results[key]["tokens"] == r["tokens"]
        for key, r in online_results.items())

    loss_parity = (losses_on == losses_off and losses_b == losses_off)

    def _p95(tag, lats):
        vals = [s for t, s in lats if t == tag]
        return float(np.percentile(vals, 95)) if vals else 0.0

    steady_p95 = _p95("steady", online_lat)
    flip_p95 = _p95("flip", online_lat)
    goodput = tokens / online_s
    goodput_b = tokens_b / baseline_s
    return {
        "epochs": EP,
        "waves_per_epoch": W,
        "wave_requests": slots,
        "new_tokens": new_tok,
        "train_steps_per_epoch": args.online_train_steps,
        "requests_total": len(expected_keys),
        "online": {
            "seconds": online_s,
            "tokens": tokens,
            "goodput_tokens_per_second": goodput,
            "flip_seconds": flip_secs,
            "steady_p95_s": steady_p95,
            "flip_window_p95_s": flip_p95,
            "compile_count_stable": compile_stable,
            "weight_history": weight_history,
        },
        "drain_restart": {
            "seconds": baseline_s,
            "tokens": tokens_b,
            "goodput_tokens_per_second": goodput_b,
            "compile_count_total": compiles_b,
        },
        "goodput_ratio": goodput / goodput_b if goodput_b else 0.0,
        "flip_window_p95_ratio": (flip_p95 / steady_p95
                                  if steady_p95 else 0.0),
        "zero_dropped_duplicated": zero_dropped_dup,
        "pinned_epochs_correct": epochs_ok,
        "per_epoch_bit_equal_replay": replay_ok,
        "greedy_bit_equal_across_phases": phases_equal,
        "trainer_loss_bit_equal_offline": loss_parity,
    }


def _gate_online(args, block):
    rc = 0
    ratio = block["goodput_ratio"]
    if args.min_online_goodput_ratio and ratio < args.min_online_goodput_ratio:
        print(f"FAIL: online goodput ratio {ratio:.2f}x < "
              f"{args.min_online_goodput_ratio}x drain-restart",
              file=sys.stderr)
        rc = 1
    p95r = block["flip_window_p95_ratio"]
    if args.max_online_flip_p95_ratio and p95r > args.max_online_flip_p95_ratio:
        print(f"FAIL: flip-window p95 {p95r:.2f}x steady-state > "
              f"{args.max_online_flip_p95_ratio}x", file=sys.stderr)
        rc = 1
    for flag in ("zero_dropped_duplicated", "pinned_epochs_correct",
                 "per_epoch_bit_equal_replay",
                 "greedy_bit_equal_across_phases",
                 "trainer_loss_bit_equal_offline"):
        if not block[flag]:
            print(f"FAIL: online {flag} is false", file=sys.stderr)
            rc = 1
    if not block["online"]["compile_count_stable"]:
        print("FAIL: online flips recompiled the engine", file=sys.stderr)
        rc = 1
    history = block["online"]["weight_history"]
    want = [[f"wt-{e}", "committed"]
            for e in range(1, block["epochs"] + 1)]
    if history != want:
        print(f"FAIL: weight journal history {history} != {want}",
              file=sys.stderr)
        rc = 1
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-length", type=int, default=512)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-speedup", type=float, default=5.0,
                    help="fail unless engine/naive tokens-per-second "
                         "ratio reaches this (0 disables)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--speculate-k", type=int, default=4)
    ap.add_argument("--churn-requests", type=int, default=48)
    ap.add_argument("--churn-slots", type=int, default=8)
    ap.add_argument("--churn-prompt-len", type=int, default=120)
    ap.add_argument("--churn-new-tokens", type=int, default=8)
    ap.add_argument("--min-churn-speedup", type=float, default=1.1,
                    help="fail unless the churn scenario's paged/baseline "
                         "tokens-per-second ratio reaches this (0 "
                         "disables)")
    ap.add_argument("--min-capacity-ratio", type=float, default=1.5,
                    help="fail unless paged requests-per-GB beats the "
                         "contiguous baseline by this factor (0 disables)")
    ap.add_argument("--router-slots", type=int, default=8,
                    help="decode slots per engine worker in the router "
                         "scenario")
    ap.add_argument("--router-step-floor-ms", type=float, default=60.0,
                    help="pace each engine step to at least this wall time "
                         "(emulating accelerator-bound steps) so the "
                         "scaling gate measures the router control plane, "
                         "not the host's cpu-share throttle; must exceed "
                         "the CONTENDED per-step host cost (~50ms on a "
                         "throttled 2-core CI box) or the floor never "
                         "dominates; 0 = raw compute")
    ap.add_argument("--min-router-scaling", type=float, default=1.8,
                    help="fail unless 2-worker router tokens/s reaches "
                         "this multiple of 1 worker (0 disables)")
    ap.add_argument("--dataplane", choices=("streaming", "store"),
                    default="streaming",
                    help="router dataplane for the serving scenario; "
                         "streaming also runs a store-dataplane traced "
                         "A/B phase for the transit comparison")
    ap.add_argument("--max-transit-share", type=float, default=0.30,
                    help="fail if any SLO class attributes more than this "
                         "share of request latency to transit "
                         "(store_transit + net_transit) on the streaming "
                         "dataplane (0 disables)")
    ap.add_argument("--skip-router", action="store_true",
                    help="skip the multi-engine router scenario")
    ap.add_argument("--router-only", action="store_true",
                    help="run only the router scenario (faster iteration)")
    ap.add_argument("--skip-naive", action="store_true",
                    help="run only the churn scenario (faster iteration)")
    ap.add_argument("--logit-wire-only", action="store_true",
                    help="run only the mp2 quantized-logit-recombination "
                         "scenario and merge the logit_wire block into the "
                         "existing BENCH_SERVING.json")
    ap.add_argument("--skip-logit-wire", action="store_true",
                    help="skip the logit-wire scenario in the full run")
    ap.add_argument("--attn-kernel-only", action="store_true",
                    help="run only the attention kernel-selection A/B "
                    "(einsum oracle vs fused Pallas kernel, f32 + int8 "
                    "pools, greedy bit-equal gate)")
    ap.add_argument("--skip-attn-kernel", action="store_true",
                    help="skip the attention-kernel A/B in the full run")
    ap.add_argument("--cold-start-only", action="store_true",
                    help="run only the fresh-process cold-start scenario "
                         "(warm vs cold AOT compile cache) and merge the "
                         "cold_start block into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--skip-cold-start", action="store_true",
                    help="skip the cold-start scenario in the full run")
    ap.add_argument("--live-plane-only", action="store_true",
                    help="run only the live-telemetry-plane A/B (traced "
                         "2-worker workload, live off vs on) and merge "
                         "the live_plane block into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--skip-live-plane", action="store_true",
                    help="skip the live-plane scenario in the full run")
    ap.add_argument("--tenants-only", action="store_true",
                    help="run only the per-tenant accounting A/B (live-"
                         "traced 2-worker multi-tenant workload, ledger "
                         "off vs on; gates conservation, overhead, and "
                         "the post-hoc reconcile) and merge the tenants "
                         "block into the existing BENCH_SERVING.json")
    ap.add_argument("--tenants", action="store_true",
                    help="alias for --tenants-only")
    ap.add_argument("--skip-tenants", action="store_true",
                    help="skip the tenant-accounting scenario in the "
                         "full run")
    ap.add_argument("--autoscale-only", action="store_true",
                    help="run only the train/serve colocation autoscale "
                         "A/B/C (static 2+0, static 1+1, supervisor-"
                         "colocated) and merge the colocation block into "
                         "the existing BENCH_SERVING.json")
    ap.add_argument("--autoscale", action="store_true",
                    help="alias for --autoscale-only")
    ap.add_argument("--skip-autoscale", action="store_true",
                    help="skip the colocation autoscale scenario in the "
                         "full run")
    ap.add_argument("--autoscale-cycles", type=int, default=2,
                    help="burst/lull cycles per colocation phase")
    ap.add_argument("--autoscale-cycle-s", type=float, default=12.0,
                    help="seconds per colocation cycle (3 bursts at the "
                         "front, lull for the rest)")
    ap.add_argument("--autoscale-burst", type=int, default=14,
                    help="interactive requests per burst; sized so one "
                         "engine blows the latency target and two hold it")
    ap.add_argument("--autoscale-step-floor-ms", type=float, default=25.0,
                    help="engine step pacing for the colocation phases "
                         "(4 slots/worker; lower than the router "
                         "scenario's so bursts drain inside the target)")
    ap.add_argument("--autoscale-train-step-ms", type=float, default=50.0,
                    help="emulated training step wall time at width 1 "
                         "(fixed global batch: step time scales 1/width)")
    ap.add_argument("--min-colocation-margin", type=float, default=0.0,
                    help="fail unless the colocated score beats the best "
                         "static split by more than this")
    ap.add_argument("--replay-only", action="store_true",
                    help="run only the reduced workload-replay legs "
                         "(front-tier throughput, determinism, quota, "
                         "heap-vs-scan dispatch; docs/REPLAY.md) and "
                         "merge the replay block into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--replay", action="store_true",
                    help="alias for --replay-only")
    ap.add_argument("--skip-replay", action="store_true",
                    help="skip the workload-replay legs in the full run")
    ap.add_argument("--online-only", action="store_true",
                    help="run only the online continuous-learning A/B "
                         "(zero-drain journaled weight flips into one "
                         "live engine vs drain-and-restart per epoch; "
                         "docs/ONLINE.md) and merge the online block "
                         "into the existing BENCH_SERVING.json")
    ap.add_argument("--online", action="store_true",
                    help="alias for --online-only")
    ap.add_argument("--skip-online", action="store_true",
                    help="skip the online weight-flip scenario in the "
                         "full run")
    ap.add_argument("--online-epochs", type=int, default=3,
                    help="weight flips per phase (epochs 1..N)")
    ap.add_argument("--online-waves", type=int, default=2,
                    help="decode waves per epoch; the last wave of each "
                         "epoch overlaps its flip")
    ap.add_argument("--online-new-tokens", type=int, default=16,
                    help="greedy tokens per online-scenario request")
    ap.add_argument("--online-train-steps", type=int, default=2,
                    help="AdamW steps between flips")
    ap.add_argument("--online-step-floor-ms", type=float, default=20.0,
                    help="pace online-scenario engine steps to at least "
                         "this wall time (emulating accelerator-bound "
                         "steps, like --router-step-floor-ms) so the "
                         "flip-window gate measures the weight stream's "
                         "cost against realistic step times; 0 = raw "
                         "compute")
    ap.add_argument("--min-online-goodput-ratio", type=float, default=2.0,
                    help="fail unless zero-drain goodput reaches this "
                         "multiple of drain-and-restart (0 disables)")
    ap.add_argument("--max-online-flip-p95-ratio", type=float,
                    default=1.10,
                    help="fail if flip-window request p95 exceeds this "
                         "multiple of steady-state p95 (0 disables)")
    ap.add_argument("--replay-requests", type=int, default=100_000,
                    help="stream length for the embedded replay "
                         "throughput leg (the full 1M-request run lives "
                         "in scripts/bench_replay.py -> BENCH_REPLAY.json)")
    ap.add_argument("--max-live-overhead", type=float, default=0.02,
                    help="fail if enabling the live telemetry plane "
                         "costs more than this fraction of live-off "
                         "tokens/s (0 disables)")
    ap.add_argument("--max-tenant-overhead", type=float, default=0.02,
                    help="fail if enabling the per-tenant accounting "
                         "ledger costs more than this fraction of "
                         "ledger-off tokens/s (0 disables)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_SERVING.json"))
    args = ap.parse_args(argv)

    if os.environ.get("BENCH_SERVING_COLD_CHILD"):
        _cold_start_child(args)
        return 0
    if os.environ.get("BENCH_SERVING_LOGIT_CHILD"):
        _logit_wire_child(args)
        return 0
    if args.logit_wire_only:
        block = run_logit_wire(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["logit_wire"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"logit_wire": block}, indent=2))
        return 0
    if args.live_plane_only:
        block = run_live_plane(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["live_plane"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"live_plane": block}, indent=2))
        return _gate_live_plane(args, block)
    if args.tenants_only or args.tenants:
        block = run_tenants(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["tenants"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"tenants": block}, indent=2))
        return _gate_tenants(args, block)
    if args.autoscale_only or args.autoscale:
        block = run_autoscale(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["colocation"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"colocation": block}, indent=2))
        return _gate_autoscale(args, block)
    if args.online_only or args.online:
        block = run_online(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["online"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"online": block}, indent=2))
        return _gate_online(args, block)
    if args.replay_only or args.replay:
        block = run_replay(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["replay"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"replay": block}, indent=2))
        return _gate_replay(args, block)
    if args.attn_kernel_only:
        block = run_attn_kernel(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["attn_kernel"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"attn_kernel": block}, indent=2))
        return 0
    if args.cold_start_only:
        block = run_cold_start(args)
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["cold_start"] = block
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"cold_start": block}, indent=2))
        return 0

    import numpy as np

    import paddle_tpu.inference as inference
    from paddle_tpu.text import generation

    model = build_model(args)
    if args.router_only:
        report = {
            "model": {"hidden": args.hidden, "layers": args.layers,
                      "heads": args.heads, "vocab": args.vocab},
            "max_length": args.max_length,
            "backend": os.environ.get("JAX_PLATFORMS", "default"),
            "router": run_router(args),
        }
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps(report, indent=2))
        return _gate_router(args, report["router"])
    if args.skip_naive:
        report = {
            "model": {"hidden": args.hidden, "layers": args.layers,
                      "heads": args.heads, "vocab": args.vocab},
            "max_length": args.max_length,
            "backend": os.environ.get("JAX_PLATFORMS", "default"),
            "churn": run_churn(args, model),
        }
        if not args.skip_router:
            report["router"] = run_router(args)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps(report, indent=2))
        return _gate_churn(args, report["churn"]) or (
            0 if args.skip_router else _gate_router(args, report["router"]))
    rng = np.random.default_rng(args.seed)
    ids = rng.integers(0, args.vocab, (args.batch, args.prompt_len),
                       dtype=np.int64)
    new_tokens = args.batch * (args.max_length - args.prompt_len)

    def run_naive():
        return generation.generate_padded(
            model, ids, max_length=args.max_length, use_engine=False)

    engine = inference.enable_decode_engine(
        model, num_slots=args.batch, max_length=args.max_length)

    def run_engine():
        return generation.generate_padded(
            model, ids, max_length=args.max_length)

    # warm both paths (compile), then time a second run of each
    print("warming naive fixed-shape loop...", file=sys.stderr)
    out_naive = run_naive()
    t0 = time.perf_counter()
    out_naive2 = run_naive()
    naive_s = time.perf_counter() - t0

    print("warming decode engine...", file=sys.stderr)
    out_engine = run_engine()
    compile_count = engine.stats()["compile_count"]
    t0 = time.perf_counter()
    out_engine2 = run_engine()
    engine_s = time.perf_counter() - t0

    np.testing.assert_array_equal(out_naive, out_naive2)
    np.testing.assert_array_equal(out_engine, out_engine2)
    np.testing.assert_array_equal(
        out_naive, out_engine,
        err_msg="engine greedy decode diverged from the naive loop")

    naive_tps = new_tokens / naive_s
    engine_tps = new_tokens / engine_s
    speedup = engine_tps / naive_tps
    report = {
        "batch": args.batch,
        "max_length": args.max_length,
        "prompt_len": args.prompt_len,
        "model": {"hidden": args.hidden, "layers": args.layers,
                  "heads": args.heads, "vocab": args.vocab},
        "new_tokens_per_run": new_tokens,
        "naive_seconds": round(naive_s, 4),
        "engine_seconds": round(engine_s, 4),
        "naive_tokens_per_second": round(naive_tps, 2),
        "engine_tokens_per_second": round(engine_tps, 2),
        "speedup": round(speedup, 2),
        "engine_compile_count": compile_count,
        "greedy_bit_equal": True,
        "backend": os.environ.get("JAX_PLATFORMS", "default"),
    }
    inference.disable_decode_engine(model)
    report["churn"] = run_churn(args, model)
    if not args.skip_attn_kernel:
        report["attn_kernel"] = run_attn_kernel(args)
    if not args.skip_logit_wire:
        report["logit_wire"] = run_logit_wire(args)
    if not args.skip_cold_start:
        report["cold_start"] = run_cold_start(args)
    if not args.skip_router:
        report["router"] = run_router(args)
    if not args.skip_live_plane:
        report["live_plane"] = run_live_plane(args)
    if not args.skip_tenants:
        report["tenants"] = run_tenants(args)
    if not args.skip_autoscale:
        report["colocation"] = run_autoscale(args)
    if not args.skip_replay:
        report["replay"] = run_replay(args)
    if not args.skip_online:
        report["online"] = run_online(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    if args.min_speedup and speedup < args.min_speedup:
        print(f"FAIL: speedup {speedup:.2f}x < required "
              f"{args.min_speedup}x", file=sys.stderr)
        return 1
    rc = _gate_churn(args, report["churn"])
    if not args.skip_router:
        rc = rc or _gate_router(args, report["router"])
    if not args.skip_live_plane:
        rc = rc or _gate_live_plane(args, report["live_plane"])
    if not args.skip_tenants:
        rc = rc or _gate_tenants(args, report["tenants"])
    if not args.skip_autoscale:
        rc = rc or _gate_autoscale(args, report["colocation"])
    if not args.skip_replay:
        rc = rc or _gate_replay(args, report["replay"])
    if not args.skip_online:
        rc = rc or _gate_online(args, report["online"])
    return rc


def _gate_router(args, router):
    if (args.min_router_scaling
            and router["scaling"] < args.min_router_scaling):
        print(f"FAIL: router scaling {router['scaling']}x < required "
              f"{args.min_router_scaling}x (machine 2-proc compute "
              f"ceiling {router['machine_parallel_ceiling']}x)",
              file=sys.stderr)
        return 1
    if (args.max_transit_share and router.get("dataplane") == "streaming"
            and router.get("trace_summary")):
        rc = 0
        for cls, shares in router["trace_summary"]["phase_share_mean"].items():
            transit = (shares.get("store_transit", 0.0)
                       + shares.get("net_transit", 0.0))
            if transit >= args.max_transit_share:
                print(f"FAIL: {cls} transit share {transit:.3f} >= max "
                      f"{args.max_transit_share} on the streaming dataplane",
                      file=sys.stderr)
                rc = 1
        if rc:
            return rc
    return 0


def _gate_churn(args, churn):
    ok = 0
    if (args.min_churn_speedup
            and churn["tokens_per_second_speedup"] < args.min_churn_speedup):
        print(f"FAIL: churn speedup {churn['tokens_per_second_speedup']}x "
              f"< required {args.min_churn_speedup}x", file=sys.stderr)
        ok = 1
    if (args.min_capacity_ratio
            and churn["capacity_ratio"] < args.min_capacity_ratio):
        print(f"FAIL: capacity ratio {churn['capacity_ratio']}x < required "
              f"{args.min_capacity_ratio}x", file=sys.stderr)
        ok = 1
    return ok


if __name__ == "__main__":
    sys.exit(main())
