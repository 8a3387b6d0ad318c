#!/usr/bin/env python3
"""The quickest proof that the train and serve hot paths still start on a TPU.

    python chip_smoke.py             one chip: ERNIE-3.0-base train steps
                                     through TrainStep and the flash kernel,
                                     then GPT-3 1.3B requests through
                                     DecodeEngine -> paged KV -> the Pallas
                                     paged-attention kernel
    python chip_smoke.py --chips 4   one four-chip host, and nothing else:
                                     Fleet dp2 x mp2 GPT-3 1.3B training
                                     against one device, the mp2 engine
                                     against the one-device engine, and where
                                     the bytes of each sit

Full model widths, random weights from --seed, a handful of steps and
requests. It is a smoke test: the times and bytes it prints are
observations, not benchmark results. It needs the chip: with no TPU it
exits non-zero at once and prints no result. Every check of every phase is
printed; any failed check or exception makes the exit code non-zero. On
success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Everything runs in this one process, which holds the chip(s); each phase
drops its model, optimizer and engines before the next.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import time
import traceback
from dataclasses import dataclass, field

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: what a Mosaic kernel looks like in a compiled TPU program's text
KERNEL_MARKER = "tpu_custom_call"

#: two greedy streams may part where the reference logits of the two
#: candidate tokens are closer than this (random weights at these widths
#: give flat logits, and bf16 paths differ in the last bits)
NEAR_TIE = 0.05

#: dp2 x mp2 against one device, as tests/test_loss_parity.py holds it
LOSS_RTOL = 5e-3


# ---------------------------------------------------------------------------
# sizes: the defaults are what the chip runs; tests shrink them for the CPU
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TrainSizes:
    #: ErnieConfig overrides; none = ERNIE-3.0-base as bench.py builds it
    #: (12L / 768h / 12 heads / 3072 ffn, vocab 40064)
    config: dict = field(default_factory=dict)
    #: (batch, seq, flash kernel expected in the compiled step)
    runs: tuple = ((256, 128, False), (32, 1024, True))
    steps: int = 8
    lr: float = 1e-5


def _gpt_1p3b(**kw):
    """GPT-3 1.3B: 24L / 2048h / 16 heads (head_dim 128), positions 2048."""
    from paddle_tpu.text.models import GPTConfig

    return GPTConfig.gpt3_1p3b(
        vocab_size=50304, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, **kw)


@dataclass(frozen=True)
class ServeSizes:
    config: object = _gpt_1p3b  # () -> GPTConfig
    num_slots: int = 8
    max_length: int = 2048
    page_size: int = 16
    #: (prompt length, max_new_tokens); more requests than slots. Even
    #: indices decode greedy, odd ones sample with a fixed seed.
    requests: tuple = ((5, 16), (40, 32), (200, 24), (700, 16), (1500, 16),
                       (33, 24), (12, 16), (1100, 16), (90, 32), (260, 16),
                       (1500, 16), (64, 64), (90, 32), (33, 24))
    #: request (index) whose prompt starts with this many tokens of
    #: request (index)'s prompt, so that prefix sharing runs
    shared_prefix: tuple = (10, 4, 1024)
    #: indices checked against the same request run alone in a fresh
    #: engine; both were admitted into a freed slot, not the first wave
    alone_greedy: int = 12
    alone_sampled: int = 13
    #: short greedy request checked against the einsum-oracle engine
    oracle: int = 6
    #: second pass: int8 KV pool + speculative verify
    spec_max_length: int = 512
    spec_k: int = 4


@dataclass(frozen=True)
class MultiSizes:
    config: object = _gpt_1p3b  # (**kw) -> GPTConfig
    batch: int = 4
    seq: int = 1024
    steps: int = 3
    lr: float = 2e-4
    num_slots: int = 4
    max_length: int = 512
    page_size: int = 16
    requests: tuple = ((12, 16), (100, 24), (9, 16))


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


class Report:
    """Every check is printed as it is made; failures are kept."""

    def __init__(self):
        self.failed = []
        self._t0 = time.perf_counter()

    def check(self, name, ok, detail=""):
        """Printed with the seconds since the start, which also says how
        long each compile-laden segment between two checks took."""
        print(f"[{'ok' if ok else 'FAIL'}] {name}" +
              (f": {detail}" if detail else "") +
              f" @{time.perf_counter() - self._t0:.0f}s", flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def run(self, name, phase, *args):
        """One phase: an exception in it is a failed check, and whatever it
        left on the device is dropped before the next phase."""
        import jax

        print(f"== {name}", flush=True)
        try:
            phase(self, *args)
        except Exception as e:  # noqa: BLE001 — reported, never swallowed
            traceback.print_exc()
            self.check(name, False, f"{type(e).__name__}: {e}"[:500])
        finally:
            gc.collect()
            jax.clear_caches()


def _say(msg):
    print(f"   {msg}", flush=True)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _gib(n):
    return "n/a" if n is None else f"{n / 2**30:.2f} GiB"


# What only a chip can show. tests/test_chip_smoke.py runs the phases tiny
# on the CPU and replaces these three from its side; the program has no
# option that relaxes them.


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def _kernel_in(program_text: str) -> bool:
    return KERNEL_MARKER in program_text


def _donated(eng) -> bool:
    return eng._donate


# ---------------------------------------------------------------------------
# train phase: ERNIE through amp O2 + AdamW + TrainStep
# ---------------------------------------------------------------------------


def train_phase(report, sizes: TrainSizes, seed: int):
    import jax

    import bench
    from paddle_tpu import amp

    for batch, seq, want_kernel in sizes.runs:
        tag = f"train b{batch}xseq{seq}"
        one_step, step, (ids, y) = bench._ernie_step(
            batch, seq, lr=sizes.lr, seed=seed, **sizes.config)
        losses, times = [], []
        for _ in range(sizes.steps):
            t0 = time.perf_counter()
            loss = one_step()
            jax.block_until_ready(loss._value)
            times.append(time.perf_counter() - t0)
            losses.append(float(loss._value))
        _say(f"{tag}: first step (compile + run) {times[0]:.1f} s, steady "
             f"step {np.median(times[1:]) * 1e3:.1f} ms fenced by "
             f"block_until_ready, peak HBM {_gib(_peak_bytes())}")
        _say(f"{tag}: losses {' '.join(f'{v:.4f}' for v in losses)}")
        report.check(f"{tag} losses finite", bool(np.all(np.isfinite(losses))))
        report.check(f"{tag} loss fell", losses[-1] < losses[0],
                     f"{losses[0]:.4f} -> {losses[-1]:.4f}")
        with amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            text = step._compiled_for(ids, y).as_text()
        report.check(
            f"{tag} flash kernel {'in' if want_kernel else 'not in'} the "
            "compiled step", _kernel_in(text) == want_kernel)
        del one_step, step, ids, y, loss
        gc.collect()
        jax.clear_caches()


# ---------------------------------------------------------------------------
# serve phase: GPT through DecodeEngine
# ---------------------------------------------------------------------------


def _build_gpt(config, seed, **kw):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(seed)
    model = GPTForCausalLM(config(**kw)).bfloat16()
    model.eval()
    return model


def _workload(requests, vocab, seed, sample_odd=True, shared_prefix=None):
    """[(prompt, SamplingParams)] from the seed: greedy, or with
    ``sample_odd`` the odd indices sampled with a fixed per-request seed."""
    from paddle_tpu.inference.engine import SamplingParams

    rng = np.random.default_rng(seed)
    reqs = []
    for i, (t0, new) in enumerate(requests):
        prompt = rng.integers(1, vocab, t0, dtype=np.int64)
        reqs.append((prompt, SamplingParams(
            max_new_tokens=new, do_sample=sample_odd and i % 2 == 1,
            temperature=0.8, top_k=40, top_p=0.95, seed=1000 + i)))
    if shared_prefix:
        dst, src, n = shared_prefix
        reqs[dst][0][:n] = reqs[src][0][:n]
    return reqs


def _drain(eng, reqs):
    """submit() every request, run() to completion; tokens per request."""
    rids = [eng.submit(p, params) for p, params in reqs]
    eng.run()
    return [eng.result(r)[len(p):] for r, (p, _) in zip(rids, reqs)]


def _reference_logits(model, ids):
    """Next-token logits after ``ids`` from the plain cache-free forward."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.op import raw

    with paddle.no_grad():
        out = model(paddle.to_tensor(np.asarray(ids, np.int32)[None]))
    return np.asarray(raw(out)[0, -1].astype(jnp.float32))


def _check_same_tokens(report, name, got, want, prompt, model,
                       near_tie_ok=True):
    """Identical, or parted at a near-tie of the reference logits."""
    if np.array_equal(got, want):
        return report.check(name, True, f"{len(got)} tokens identical")
    i = int(np.argmax(np.asarray(got) != np.asarray(want)))
    a, b = int(got[i]), int(want[i])
    if not near_tie_ok:
        return report.check(name, False, f"token {i}: {a} vs {b}")
    logits = _reference_logits(model, np.concatenate([prompt, got[:i]]))
    gap = abs(float(logits[a]) - float(logits[b]))
    return report.check(
        name, gap <= NEAR_TIE,
        f"parted at token {i} ({a} vs {b}): reference logit gap {gap:.4f}, "
        f"near-tie margin {NEAR_TIE}, logits std {logits.std():.3f}")


def _engine(model, sizes, **kw):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig

    return DecodeEngine(model, EngineConfig(**{**dict(
        num_slots=sizes.num_slots, max_length=sizes.max_length,
        page_size=sizes.page_size), **kw}))


def _check_kernel_engine(report, tag, eng, program):
    kernel = eng.stats()["attn_kernel"]
    report.check(f"{tag} attn_kernel resolved to pallas",
                 kernel == "pallas", f"got {kernel!r}")
    report.check(f"{tag} paged kernel in the compiled {program} program",
                 _kernel_in(eng.program_text(program)))
    report.check(f"{tag} KV pools donated", _donated(eng))


def serve_phase(report, sizes: ServeSizes, seed: int):
    import jax

    model = _build_gpt(sizes.config, seed)
    vocab = model.config.vocab_size
    reqs = _workload(sizes.requests, vocab, seed,
                     shared_prefix=sizes.shared_prefix)

    # -- the main wave: bf16 pool, kernel left at auto ----------------------
    eng = _engine(model, sizes, kv_dtype="bf16")
    t0 = time.perf_counter()
    warm = eng.warmup()
    _say(f"serve: warmup compiled {warm['programs']} programs "
         f"({warm['buckets']} prefill buckets + decode) in "
         f"{time.perf_counter() - t0:.1f} s")
    compiled = eng.compile_count
    t0 = time.perf_counter()
    out = _drain(eng, reqs)
    wall = time.perf_counter() - t0
    st = eng.stats()
    _say(f"serve: {len(reqs)} requests on {sizes.num_slots} slots, "
         f"{st['total_tokens']} tokens in {wall:.2f} s, {st['decode_steps']} "
         f"decode steps, steady decode step "
         f"{(eng._t_decode_ema or 0) * 1e3:.1f} ms (host clock, token "
         f"read-back included), peak HBM {_gib(_peak_bytes())}")
    _say(f"serve: peak running {st['peak_running']}, prefix hit tokens "
         f"{st['prefix_hit_tokens']}, peak pages {st['peak_pages_in_use']}"
         f"/{st['num_pages']}")
    report.check("serve every request finished with its token count",
                 [len(t) for t in out] == [n for _, n in sizes.requests])
    report.check("serve tokens inside the vocabulary",
                 all(0 <= int(t) < vocab for ts in out for t in ts))
    report.check("serve compile count did not grow after warmup",
                 eng.compile_count == compiled,
                 f"{compiled} -> {eng.compile_count}")
    report.check("serve waiting requests were admitted into freed slots",
                 st["peak_running"] == sizes.num_slots
                 and len(reqs) > sizes.num_slots)
    report.check("serve prefix sharing ran", st["prefix_hit_tokens"] > 0)
    report.check("serve finished requests were evicted",
                 st["running"] == 0 and st["waiting"] == 0
                 and len(eng._free) == sizes.num_slots)
    _check_kernel_engine(report, "serve", eng, "decode")
    del eng
    gc.collect()

    # -- the same requests alone, in a fresh engine -------------------------
    alone = _engine(model, sizes, kv_dtype="bf16")
    for i, near_tie_ok in ((sizes.alone_greedy, True),
                           (sizes.alone_sampled, False)):
        kind = "sampled" if reqs[i][1].do_sample else "greedy"
        _check_same_tokens(
            report, f"serve {kind} request {i} == the same request alone",
            out[i], _drain(alone, [reqs[i]])[0], reqs[i][0], model,
            near_tie_ok=near_tie_ok)
    del alone
    gc.collect()

    # -- the einsum oracle on the same weights ------------------------------
    oracle = _engine(model, sizes, kv_dtype="bf16", attn_kernel="einsum")
    i = sizes.oracle
    _check_same_tokens(
        report, f"serve greedy request {i} == einsum oracle",
        out[i], _drain(oracle, [reqs[i]])[0], reqs[i][0], model)
    del oracle
    gc.collect()
    jax.clear_caches()

    # -- int8 pool + speculative verify: the kernel's other two variants ----
    k = sizes.spec_k
    spec = _engine(model, sizes, max_length=sizes.spec_max_length,
                   kv_dtype="int8", speculate_k=k, spec_adaptive=False)
    rng = np.random.default_rng(seed + 1)
    motif = rng.integers(1, vocab, 8, dtype=np.int64)
    from paddle_tpu.inference.engine import SamplingParams

    # repeated motifs, so that the prompt-lookup draft has something to
    # propose and the verify program runs
    spec_reqs = [(np.tile(motif, 6)[:40 + 3 * j],
                  SamplingParams(max_new_tokens=24))
                 for j in range(sizes.num_slots + 2)]
    spec_out = _drain(spec, spec_reqs)
    st = spec.stats()
    _say(f"serve int8+spec: {st['verify_steps']} verify steps of "
         f"{st['decode_steps']}, {st['spec_accepted']}/{st['spec_proposed']} "
         f"drafts accepted, peak HBM {_gib(_peak_bytes())}")
    report.check("serve int8+spec every request finished",
                 [len(t) for t in spec_out] == [24] * len(spec_reqs))
    report.check("serve int8+spec verify program ran", st["verify_steps"] > 0)
    _check_kernel_engine(report, "serve int8+spec", spec, f"verify_k{k}")


# ---------------------------------------------------------------------------
# --chips 4: Fleet hybrid training and the mp-sharded engine
# ---------------------------------------------------------------------------


def _coords(mesh):
    """The mesh's devices in mesh order, with their chip coordinates."""
    return "device coordinates " + " ".join(
        f"{d.id}:{getattr(d, 'coords', None)}" for d in mesh.devices.flat)


def _device_bytes(arrays):
    """{device id: bytes} over the addressable shards of ``arrays``."""
    per = {}
    for a in arrays:
        for sh in a.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def _check_spread(report, name, arrays, devices):
    """Arrays meant to be sharded really are: no device holds ~all of one,
    and the devices of the mesh hold about the same."""
    import jax

    arrays = [a for a in arrays if isinstance(a, jax.Array)]
    sharded = [a for a in arrays if not a.sharding.is_fully_replicated]
    worst = max((max(s.data.nbytes for s in a.addressable_shards) / a.nbytes
                 for a in sharded), default=1.0)
    per = _device_bytes(arrays)
    held = [per.get(d.id, 0) for d in devices]
    _say(f"{name}: bytes per device " +
         " ".join(f"{d.id}:{_gib(b)}" for d, b in zip(devices, held)) +
         f"; {len(sharded)}/{len(arrays)} arrays sharded, the largest "
         f"shard is {worst:.2f} of its array")
    report.check(f"{name} sharded, nothing piled on one device",
                 bool(sharded) and worst <= 0.6
                 and min(held) > 0 and max(held) <= 1.1 * min(held))


def _train_losses(report, sizes, seed, hybrid):
    """Losses of ``steps`` AdamW steps on one fixed batch: on one device
    through TrainStep, or over the Fleet mesh ``hybrid`` describes."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.jit import TrainStep

    if hybrid:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs.update(hybrid)
        fleet.init(is_collective=True, strategy=strategy)
        mesh = _mesh.get_global_mesh()
        _say("mesh " + " ".join(f"{k}={v}" for k, v in mesh.shape.items()
                                if v > 1) + ", " + _coords(mesh))
    model = _build_gpt(sizes.config, seed, fold_layers=True,
                       use_recompute=True, recompute_granularity="full")
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=sizes.lr,
                                 parameters=model.parameters())
    loss_fn = lambda m, ids, lbl: m(ids, labels=lbl)  # noqa: E731
    if hybrid:
        fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(opt)
        step = fleet.DistTrainStep(model, loss_fn, opt)
    else:
        step = TrainStep(model, loss_fn, opt)
    tokens = np.random.default_rng(seed).integers(
        0, model.config.vocab_size, (sizes.batch, sizes.seq + 1))
    ids = paddle.to_tensor(tokens[:, :-1].astype(np.int32))
    lbl = paddle.to_tensor(tokens[:, 1:].astype(np.int32))
    losses, times = [], []
    for _ in range(sizes.steps):
        t0 = time.perf_counter()
        loss = step(ids, lbl)
        jax.block_until_ready(loss._value)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss._value))
    where = "dp2 x mp2" if hybrid else "one device"
    _say(f"train {where}: first step (compile + run) {times[0]:.1f} s, "
         f"steady step {np.median(times[1:]) * 1e3:.1f} ms, losses "
         + " ".join(f"{v:.4f}" for v in losses))
    if hybrid:
        devices = list(mesh.devices.flat)
        _check_spread(report, "multi parameters",
                      [p._value for p in model.parameters()], devices)
        _check_spread(report, "multi optimizer state",
                      jax.tree.leaves(opt.functional_states()), devices)
    return losses


def multichip_phase(report, sizes: MultiSizes, seed: int):
    import jax

    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet.topology import (
        set_hybrid_communicate_group)
    from paddle_tpu.distributed.mesh import build_mesh

    ref = _train_losses(report, sizes, seed, hybrid=None)
    gc.collect()
    jax.clear_caches()
    got = _train_losses(report, sizes, seed,
                        hybrid=dict(dp_degree=2, mp_degree=2, pp_degree=1))
    report.check("multi losses finite", bool(np.all(np.isfinite(got))))
    report.check(
        f"multi dp2 x mp2 losses == one device (rtol {LOSS_RTOL})",
        bool(np.allclose(got, ref, rtol=LOSS_RTOL, atol=1e-5)),
        f"{got} vs {ref}")
    report.check("multi loss fell", got[-1] < got[0])
    # serving is its own deployment: leave the training mesh behind
    set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    gc.collect()
    jax.clear_caches()

    model = _build_gpt(sizes.config, seed)
    reqs = _workload(sizes.requests, model.config.vocab_size, seed,
                     sample_odd=False)
    one = _engine(model, sizes, kv_dtype="bf16")
    want = _drain(one, reqs)
    _say("serve one device: attention kernel "
         + one.stats()["attn_kernel"])
    del one
    gc.collect()
    mesh = build_mesh((1, 2), ("dp", "mp"), devices=jax.devices()[:2])
    eng = _engine(model, sizes, kv_dtype="bf16", mesh=mesh)
    got = _drain(eng, reqs)
    _say(f"serve dp1 x mp2: attention kernel {eng.stats()['attn_kernel']}, "
         + _coords(mesh))
    for i, (prompt, _) in enumerate(reqs):
        _check_same_tokens(
            report, f"multi mp2 engine request {i} == one-device engine",
            got[i], want[i], prompt, model)
    _check_spread(report, "multi KV pool", jax.tree.leaves(eng.kv),
                  list(mesh.devices.flat))


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def build_native():
    """The native runtime is built here from the committed sources, never
    trusted from a copied build directory. Says loudly what came of it."""
    make = subprocess.run(["make", "-C", os.path.join(REPO, "csrc")],
                          capture_output=True, text=True)
    if make.returncode:
        print(f"NATIVE RUNTIME NOT BUILT (make -C csrc exited "
              f"{make.returncode}): {make.stderr.strip()[-300:]}", flush=True)
    from paddle_tpu import runtime

    return runtime.native_available()


def describe(cache_dir, native):
    import importlib.metadata as md

    import jax

    d = jax.devices()[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(jax.devices())}")
    print("versions: " + " ".join(
        f"{m}={md.version(m)}" for m in ("jax", "jaxlib", "libtpu")))
    print(f"default PRNG impl: {jax.config.jax_default_prng_impl}")
    print(f"native runtime available: {native}")
    print(f"compile cache: {cache_dir}", flush=True)


def main(argv=None, *, train=TrainSizes(), serve=ServeSizes(),
         multi=MultiSizes()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    import paddle_tpu  # noqa: F401 — picks the default PRNG before a backend
    from paddle_tpu.runtime import jax_cache

    devices = jax.devices()
    if _platform() != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{_platform()!r}. Nothing was run.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {len(devices)}. Nothing was run.", file=sys.stderr)
        return 2
    describe(jax_cache.configure(), build_native())

    report = Report()
    if args.chips == 4:
        report.run("four chips: Fleet dp2 x mp2 and the mp2 engine",
                   multichip_phase, multi, args.seed)
    else:
        report.run("train: ERNIE-3.0-base through TrainStep",
                   train_phase, train, args.seed)
        report.run("serve: GPT-3 1.3B through DecodeEngine",
                   serve_phase, serve, args.seed)
    if report.failed:
        print(f"chip_smoke: {len(report.failed)} failed: "
              + "; ".join(report.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
