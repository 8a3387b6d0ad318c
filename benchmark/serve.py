"""The serving driver: one process holds the chip, builds the configuration's
``DecodeEngine``, offers the mix's open-loop load through ``submit`` and
``step``, stamps every token on the host clock as the step that emitted it
returns, and checks greedy tokens against the plain reference afterwards.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import traffic
import weights

#: how long past the window's close a request due inside it is waited for
ANSWER_WAIT_S = 60.0


# ---------------------------------------------------------------------------
# the program, and the one place that reads what it does not report
# ---------------------------------------------------------------------------


def load_weights(model, names, cell, seed: int):
    """The seed's weights into the program's model, by its public
    ``set_state_dict``; the engine reads the live values at every call."""
    cfg = cell.config
    w = weights.make(cell.arch.weight_spec(cfg, stacked=False), seed,
                     cfg["dtype"])
    missing, unexpected = model.set_state_dict(
        {names[k]: v for k, v in w.items()})
    if missing or unexpected:
        raise RuntimeError(f"weights do not cover the model: missing "
                           f"{missing[:3]}, unexpected {unexpected[:3]}")


def build_engine(cell, seed: int):
    """(model, {leaf: the program's parameter name}, engine)."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig

    cfg = cell.config
    model, names = cell.arch.serve_program(cfg)
    model = model.astype(cfg["dtype"])
    model.eval()
    load_weights(model, names, cell, seed)
    e = dict(cfg["engine"])
    want = e.pop("require_attn_kernel", None)
    if e.get("prompt_buckets"):
        e["prompt_buckets"] = tuple(e["prompt_buckets"])
    engine = DecodeEngine(model, EngineConfig(**e))
    got = engine.stats()["attn_kernel"]
    if want and got != want:
        raise SystemExit(f"serve.py: attn_kernel resolved to {got!r}, the "
                         f"configuration requires {want!r}")
    return model, names, engine


def emitted(engine, rid):
    """(tokens so far, done, prompt tokens served from the prefix cache) of
    one request. The engine has no public
    per-step report of emitted tokens, so this reads its request table:
    the ONE place where the benchmark looks inside the program."""
    req = engine._requests[rid]
    return req.tokens, req.status == "done", req.cached_len


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------


@dataclass
class Track:
    req: traffic.ServeRequest
    due: float  # on the loop's clock
    in_window: bool
    rid: int = -1
    submitted: float = None
    times: list = field(default_factory=list)  # one stamp a token
    tokens: list = None  # set when done


def sampling_params(mix, r):
    from paddle_tpu.inference.engine import SamplingParams

    s = mix.get("sampling", {})
    return SamplingParams(
        max_new_tokens=r.max_new_tokens, do_sample=not r.greedy,
        temperature=s.get("temperature", 1.0), top_k=s.get("top_k", 0),
        top_p=s.get("top_p", 1.0), seed=r.seed)


def drive(engine, mix, tracks, lead_in_s, seconds, spans, tracer=None):
    """Offer ``tracks`` (sorted by due time) in an open loop from now:
    lead-in ``[0, lead_in_s)``, window ``[lead_in_s, lead_in_s + seconds)``,
    then only the wait for the first tokens that are still owed. Returns the
    loop's zero on the host clock and the per-step rows."""
    pending = deque(tracks)
    live = {}
    steps = []
    t_zero = time.perf_counter()
    close = lead_in_s + seconds
    while True:
        now = time.perf_counter() - t_zero
        if pending and pending[0].due <= now:  # every one is due by close
            with spans.span("submit"):
                while pending and pending[0].due <= now:
                    t = pending.popleft()
                    t.rid = engine.submit(t.req.prompt,
                                          sampling_params(mix, t.req))
                    t.submitted = time.perf_counter() - t_zero
                    live[t.rid] = t
        if now >= close:
            if tracer is not None:
                tracer.stop()
            owed = any(t.in_window and not t.times and t.tokens is None
                       for t in live.values())
            if not owed or now >= close + ANSWER_WAIT_S:
                break
        elif tracer is not None and now >= lead_in_s:
            tracer.tick(now - lead_in_s, seconds)
        if not live:
            nxt = pending[0].due if pending else close
            with spans.span("wait"):
                time.sleep(min(max(nxt - now, 0.0), 0.005, max(close - now, 0)))
            continue
        with spans.span("engine.step") as sp:
            engine.step()
        with spans.span("stamp"):
            t_step = time.perf_counter() - t_zero
            admitted, decoded, decode_ctx = [], 0, 0
            for rid, t in list(live.items()):
                toks, done, cached = emitted(engine, rid)
                new = len(toks) - len(t.times)
                if new:
                    plen = len(t.req.prompt)
                    if not t.times:
                        admitted.append((plen, cached))
                        new_decoded = new - 1
                    else:
                        new_decoded = new
                    # a decoded token at position p attends to p + 1 keys
                    for j in range(new_decoded):
                        decode_ctx += plen + len(toks) - 1 - j
                    decoded += new_decoded
                    t.times.extend([t_step] * new)
                if done:
                    t.tokens = list(toks)
                    del live[rid]
            sp.attrs.update(admitted=admitted, decoded=decoded,
                            decode_ctx=decode_ctx, t=t_step)
            steps.append(sp)
    return t_zero, steps


def percentile(values, q):
    """Nearest-rank percentile of all the values."""
    v = sorted(values)
    if not v:
        return None
    return v[min(int(np.ceil(q / 100.0 * len(v))) - 1, len(v) - 1)]


def end_to_end(tracks, lead_in_s, seconds):
    lo, hi = lead_in_s, lead_in_s + seconds
    n_tokens, gaps, ttft, failed = 0, [], [], 0
    for t in tracks:
        stamps = t.times
        n_tokens += sum(lo <= s < hi for s in stamps)
        gaps += [b - a for a, b in zip(stamps, stamps[1:]) if lo <= b < hi]
        if t.in_window:
            if stamps:
                ttft.append(stamps[0] - t.due)
            else:  # never answered: as long as we waited for it
                failed += 1
                ttft.append(hi + ANSWER_WAIT_S - t.due)
    out = {"serve_tokens_per_s": n_tokens / seconds}
    if gaps:
        out["serve_itl_p95_ms"] = percentile(gaps, 95) * 1e3
    if ttft:
        out["serve_ttft_p95_ms"] = percentile(ttft, 95) * 1e3
        out["serve_ttft_p50_ms"] = percentile(ttft, 50) * 1e3
    # where the judged tail sits among the gaps: on the edge between two
    # kinds of step (a plain step, an admit of one bucket or another) it
    # swings from seed to seed, inside one kind it does not
    around = {f"itl_p{q}_ms": percentile(gaps, q) * 1e3
              for q in (50, 90, 93, 97, 99) if gaps}
    return out, failed, {"window_tokens": n_tokens, "itl_gaps": len(gaps),
                         "ttft_samples": len(ttft), **around}


def window_steps(steps, lead_in_s, seconds, buckets):
    """Where the window's seconds went, by kind of step: plain decode steps,
    and admit steps by the largest of the configuration's prompt buckets
    that their admits' uncached tails need. A run that completes
    fewer tokens than its neighbours shows here whether its steps were
    slower, or heavier, or fewer."""
    lo, hi = lead_in_s, lead_in_s + seconds
    inside = [s for s in steps if lo <= s.attrs["t"] < hi]
    out = {"window_steps": len(inside),
           "window_step_s": sum(s.seconds for s in inside),
           "window_step_max_ms": max((s.seconds for s in inside),
                                     default=0.0) * 1e3,
           "window_steps_over_150_ms": sum(s.seconds > 0.15 for s in inside),
           "window_admits": sum(len(s.attrs["admitted"]) for s in inside)}
    for s in inside:
        kind = "decode"
        if s.attrs["admitted"]:
            tail = max(p - c for p, c in s.attrs["admitted"])
            kind = "admit_b%s" % next(
                (b for b in sorted(buckets) if b >= tail), "over")
        out[f"window_{kind}_steps"] = out.get(f"window_{kind}_steps", 0) + 1
        out[f"window_{kind}_s"] = out.get(f"window_{kind}_s", 0.0) + s.seconds
    return out


def make_tracks(mix, seed, lead_in_s, seconds, vocab):
    """The window's requests, and before them the lead-in: the requests of
    the window's own last ``lead_in_s`` seconds over again (the same sizes
    and kinds at the same spacing, new token ids), as if the window's
    schedule had run once before. With a lead-in as long as the longest
    request stays, what is in flight when the window opens matches what is
    in flight when it closes, so that the tokens the window emits do not
    hang on which long answers happen to straddle its ends."""
    sysp = traffic.system_prompts(mix, seed, vocab)
    inside = traffic.serve_window(mix, seed, seconds, vocab, sysp)
    tracks = [Track(req=r, due=lead_in_s + r.due, in_window=True)
              for r in inside]
    rng = np.random.default_rng([int(seed), 17])
    for r in inside:
        if r.due < seconds - lead_in_s:
            continue
        prompt = rng.integers(1, vocab, len(r.prompt),
                              dtype=np.int64).astype(np.int32)
        if r.shared >= 0:
            prompt[:len(sysp[r.shared])] = sysp[r.shared]
        again = traffic.ServeRequest(
            due=r.due - (seconds - lead_in_s), prompt=prompt,
            max_new_tokens=r.max_new_tokens, greedy=r.greedy,
            seed=int(rng.integers(0, 2**31 - 1)), shared=r.shared)
        tracks.append(Track(req=again, due=again.due, in_window=False))
    tracks.sort(key=lambda t: t.due)
    return tracks


# ---------------------------------------------------------------------------
# correct: served greedy tokens against the plain reference
# ---------------------------------------------------------------------------


def check_sample(tracks, seed, k, greedy=True):
    """``k`` finished requests of one kind (greedy, or sampled) drawn from
    the seed, the longest among them."""
    done = [t for t in tracks
            if t.tokens is not None and t.req.greedy == greedy]
    if not done:
        return []
    done.sort(key=lambda t: -(len(t.req.prompt) + len(t.tokens)))
    rng = np.random.default_rng([int(seed), 13 if greedy else 19])
    rest = done[1:]
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [done[0]] + [rest[i] for i in sorted(pick)]


def _nucleus_edge(lq, temperature, top_p):
    """The least likely token that a sampler keeps under ``top_p`` (the
    program's rule: a token stays while the mass above it is under top_p)."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(lq / temperature, axis=-1)
    sp = jnp.sort(p, axis=-1)[:, ::-1]
    keep = (jnp.cumsum(sp, axis=-1) - sp) < top_p
    thr = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1, keepdims=True)
    return jnp.argmin(jnp.where(p >= thr, p, jnp.inf), axis=-1)


def _readings_at(lg, lq, next_ids, first, count, key, *, greedy, control,
                 temp, top_p):
    """One request's readings over the positions ``[first, first + count)``
    of its padded sequence, at the padded shape whatever the request: one
    compiled program a kind of reading, found in the compile cache by every
    later run (sliced to each request's own length, every new length
    compiled a dozen small programs, 40-80 s of a run's check: PERF.md
    section 6, PR 32). ``lg``: the reference's logits ``[T, V]``, ``lq`` the
    control's (``lg`` again where there is none), ``next_ids [T]`` the token
    that followed each position."""
    import jax
    import jax.numpy as jnp

    at = jnp.arange(lg.shape[0])
    at = (at >= first) & (at < first + count)
    if greedy:
        chosen = jnp.argmax(lq, axis=-1) if control == "quant" else next_ids
        gap = (lg.max(-1)
               - jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0])
        gap = jnp.where(at, gap, 0.0)
        return gap.max(), gap.sum()
    if control == "no_top_p":
        chosen = jax.random.categorical(key, lg / temp, axis=-1)
    elif control == "quant":
        chosen = _nucleus_edge(lq, temp, top_p)
    else:
        chosen = next_ids
    p = jax.nn.softmax(lg / temp, axis=-1)
    p_tok = jnp.take_along_axis(p, chosen[:, None], -1)
    above = jnp.sum(jnp.where(p > p_tok, p, 0.0), axis=-1)
    return jnp.max(jnp.where(at, above, 0.0)), jnp.float32(0.0)


def reference_readings(cell, seed, greedy, sampled=(), quant=None,
                       no_top_p=False, ref_weights=None, pad_to=None):
    """The reference's logits over prompt + served tokens of each request of
    the two samples, one pass a request, and from them:

    - ``logit_gap_max``, ``logit_gap_mean``: over the served GREEDY tokens,
      the gap by which a served token's logit lies below the reference's
      best: the widest, and the mean over all of them;
    - ``sampled_top_p_excess_max``: over the served SAMPLED tokens, by how
      much the mass that the reference's tempered distribution puts above
      the token passes the mix's ``top_p`` (0 where it does not): a sampler
      that keeps to its nucleus serves no token outside it.

    With ``quant`` the CONTROL's readings instead, at the same positions: the
    gap of the token that the lower precision puts first, and the excess of
    the least likely token that the lower precision's nucleus keeps. With
    ``no_top_p`` the fault 'top-p left out': tokens drawn from the
    reference's own tempered distribution, unfiltered."""
    import jax
    import jax.numpy as jnp

    ref, cfg = cell.reference, cell.config
    kw = cell.arch.reference_args(cfg)
    samp = cell.mix.get("sampling", {})
    temp, top_p = samp.get("temperature", 1.0), samp.get("top_p", 1.0)
    w = ref_weights or weights.make(cell.arch.weight_spec(cfg, stacked=True),
                                    seed, cfg["dtype"])
    pad = pad_to or -(-max(len(t.req.prompt) + len(t.tokens)
                           for t in list(greedy) + list(sampled)) // 128) * 128
    control = "quant" if quant else "no_top_p" if no_top_p else None
    read = jax.jit(_readings_at, static_argnames=(
        "greedy", "control", "temp", "top_p"))
    gap_max = gap_sum = excess = 0.0
    n_greedy = n_sampled = 0
    key = jax.random.key(int(seed) & 0x7FFFFFFF, impl="threefry2x32")
    for t in list(greedy) + list(sampled):
        plen, served = len(t.req.prompt), np.asarray(t.tokens, np.int32)
        ids = np.zeros(pad + 1, np.int32)
        ids[:plen] = t.req.prompt
        ids[plen:plen + len(served)] = served
        lg = ref.logits(w, jnp.asarray(ids[:pad]), **kw)
        lq = (ref.logits(w, jnp.asarray(ids[:pad]), quant=quant, **kw)
              if quant else lg)
        key, sub = jax.random.split(key)
        a, b = read(lg, lq, jnp.asarray(ids[1:]), plen - 1, len(served), sub,
                    greedy=t.req.greedy, control=control, temp=temp,
                    top_p=top_p)
        if t.req.greedy:
            gap_max = max(gap_max, float(a))
            gap_sum += float(b)
            n_greedy += len(served)
        else:
            excess = max(excess, float(a) - top_p)
            n_sampled += len(served)
    return {"logit_gap_max": gap_max if n_greedy else None,
            "logit_gap_mean": gap_sum / n_greedy if n_greedy else None,
            "sampled_top_p_excess_max": excess if n_sampled else None,
            "greedy_tokens": n_greedy, "sampled_tokens": n_sampled}


def reference_pad(mix):
    """One shape for the reference whatever the sample: the longest prompt
    and answer the mix can draw, so that its program is compiled once and
    then served from the compile cache."""
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    return -(-longest // 128) * 128


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell, seed, seconds, spans, tracer, t_process):
    import jax

    from harness import Outcome, load_limits, memory_peak_bytes, say

    cfg, mix = cell.config, cell.mix
    t0 = time.perf_counter()
    model, _, engine = build_engine(cell, seed)
    say(f"setup: model and weights {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    warm = engine.warmup()
    say(f"setup: warm-up of {warm['programs']} programs "
        f"{time.perf_counter() - t0:.1f} s")
    compiled = engine.compile_count
    lead = float(mix["lead_in_s"])
    tracks = make_tracks(mix, seed, lead, seconds, cfg["vocab_size"])
    t_zero, steps = drive(engine, mix, tracks, lead, seconds, spans, tracer)
    window = (t_zero + lead, t_zero + lead + seconds)
    e2e, failed, counts = end_to_end(tracks, lead, seconds)
    st = engine.stats()
    counters = {
        **counts, "compiles_in_window": engine.compile_count - compiled,
        "decode_steps": st["decode_steps"],
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "prompt_tokens_total": st["prompt_tokens_total"],
        "peak_running": st["peak_running"],
        "requests_lead_in": sum(not t.in_window for t in tracks),
        "requests_window": sum(t.in_window for t in tracks),
        "requests_finished": sum(t.tokens is not None for t in tracks),
        "requests_running_at_end": st["running"],
        "requests_waiting_at_end": st["waiting"],
        "gen_lateness_max_ms": max(
            [(t.submitted - t.due) * 1e3 for t in tracks
             if t.submitted is not None], default=0.0)}
    counters.update(window_steps(
        steps, lead, seconds, cfg["engine"].get("prompt_buckets", ())))
    if counters["compiles_in_window"]:
        raise SystemExit("serve.py: a program compiled inside the window")
    peak = memory_peak_bytes(cell.chips)
    # the reference runs only now: the peak is read, the program's state goes
    k = mix["check"]["sample"]
    greedy = check_sample(tracks, seed, k)
    sampled = check_sample(tracks, seed, k, greedy=False)
    wrong_len = sum(len(t.tokens) != t.req.max_new_tokens
                    for t in tracks if t.tokens is not None)
    out_of_vocab = sum(not 0 <= int(x) < cfg["vocab_size"]
                       for t in tracks if t.tokens for x in t.tokens)
    del engine, model
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    got = (reference_readings(cell, seed, greedy, sampled,
                              pad_to=reference_pad(mix))
           if greedy or sampled else {})
    say(f"check: reference over {len(greedy)} greedy and {len(sampled)} "
        f"sampled requests, {got.get('greedy_tokens', 0)} and "
        f"{got.get('sampled_tokens', 0)} served tokens, "
        f"{time.perf_counter() - t0:.1f} s")
    limits = load_limits(cell)
    # the limits file names the numbers that are compared; a number it names
    # and the run could not read is not correct
    compared = [(n, got.get(n), limits[n])
                for n in ("logit_gap_max", "logit_gap_mean",
                          "sampled_top_p_excess_max") if n in limits]
    compared += [("wrong_length_requests", wrong_len, 0),
                 ("tokens_outside_vocab", out_of_vocab, 0)]
    compared += [(f"{kind}_tokens_short_of_sample",
                  max(limits[f"{kind}_tokens_checked_min"]
                      - got.get(f"{kind}_tokens", 0), 0), 0)
                 for kind in ("greedy", "sampled")
                 if f"{kind}_tokens_checked_min" in limits]
    return Outcome(
        setup_s=window[0] - t_process, end_to_end=e2e,
        attempted=counters["requests_window"], failed=failed,
        compared=compared, counters=counters, window=window,
        memory_peak_bytes=peak,
        extra={"steps": steps, "tracks": tracks, "t_zero": t_zero,
               "lead_in_s": lead})
