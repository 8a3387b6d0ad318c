"""The training driver: one process holds the chip, builds the configuration's
model, optimizer and compiled ``TrainStep``, drives that one object through
its first steps (which the plain reference follows), and hands the same
object to the measured window. A new batch every step, made on the host from
the seed and sent.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import traffic
import weights

#: steps the program takes before the window, and how many of them the
#: reference follows (PERF.md section 2 says why two and not three)
FIRST_STEPS = 3
REFERENCE_STEPS = 2


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


class Program:
    """Model, optimizer and the compiled step: built once, driven from the
    seed through its first steps, then timed. The same object throughout.
    ``train.amp``: none (parameters in ``dtype``, no autocast), "O1"
    (float32 parameters, bf16 autocast) or "O2" (``amp.decorate``: bf16
    parameters, ``multi_precision`` asked of the optimizer)."""

    def __init__(self, cell, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu import amp
        from paddle_tpu.jit import TrainStep

        cfg, mix = cell.config, cell.mix
        t = cfg["train"]
        paddle.seed(int(seed) & 0x7FFFFFFF)
        self.cfg, self.amp = cfg, t.get("amp")
        self.model, self.names, stacked = cell.arch.train_program(cfg)
        self.model.train()
        self.spec = cell.arch.weight_spec(cfg, stacked)
        self.opt = paddle.optimizer.AdamW(
            learning_rate=mix["lr"], beta1=t["beta1"], beta2=t["beta2"],
            epsilon=t["epsilon"], weight_decay=t["weight_decay"],
            parameters=self.model.parameters(),
            multi_precision=bool(t.get("multi_precision")))
        if self.amp == "O2":
            self.model, self.opt = amp.decorate(
                self.model, self.opt, level="O2", dtype="bfloat16")
        else:
            self.model.astype(cfg["dtype"])
        w = weights.make(self.spec, seed, cfg["dtype"])
        missing, unexpected = self.model.set_state_dict(
            {self.names[k]: v for k, v in w.items()})
        if missing or unexpected:
            raise RuntimeError(f"weights do not cover the model: missing "
                               f"{missing[:3]}, unexpected {unexpected[:3]}")
        del w
        self.step = TrainStep(
            self.model, lambda m, ids, lbl: m(ids, labels=lbl), self.opt)
        self.beta1 = t["beta1"]

    def __call__(self, batch):
        """One step through the window's own call; the loss, not fetched."""
        import paddle_tpu as paddle
        from paddle_tpu import amp

        batch = [paddle.to_tensor(b) for b in batch]
        if self.amp:
            with amp.auto_cast(enable=True, dtype="bfloat16",
                               level=self.amp):
                return self.step(*batch)._value
        return self.step(*batch)._value

    def params(self) -> dict:
        """Benchmark leaf name -> the live parameter value."""
        by_name = dict(self.model.named_parameters())
        return {k: by_name[v]._value for k, v in self.names.items()}

    def first_gradient(self) -> dict:
        """After ONE step: the first gradient as the optimizer got it (Adam's
        first moment is then (1 - beta1) g), on the host in float32, in the
        reference's layout (``h.<leaf>`` stacked over the layers)."""
        order = {id(p): i for i, p in enumerate(self.opt._parameter_list)}
        by_name = dict(self.model.named_parameters())
        states = self.opt.functional_states()
        flat = {k: np.asarray(states[order[id(by_name[v])]]["moment1"],
                              np.float32) / (1.0 - self.beta1)
                for k, v in self.names.items()}
        return stack_layers(flat, self.cfg.get("num_hidden_layers", 0))


# ---------------------------------------------------------------------------
# norms a leaf: a stacked leaf counts one leaf a layer
# ---------------------------------------------------------------------------


def stack_layers(flat: dict, n_layers: int) -> dict:
    """``h<i>.<leaf>`` of every layer into one ``h.<leaf>`` with a leading
    layer axis; leaves that are stacked already, and the others, as is."""
    out = {k: v for k, v in flat.items() if weights.layer_of(k) is None}
    for rest in {weights.layer_of(k)[0] for k in flat
                 if weights.layer_of(k) is not None}:
        out["h." + rest] = np.stack([flat[f"h{i}.{rest}"]
                                     for i in range(n_layers)])
    return out


def _parts(name, x, heads):
    """One leaf as the leaves that are compared: a stacked leaf gives one a
    layer, and the fused projection one each for q, k and v (the reference's
    layout: column ``(head * 3 + j) * d + e``). The split is what lets the
    rule on the reference's gradient find the key's bias, which softmax
    leaves without a gradient, inside the fused leaf."""
    layers = ([(f"h{i}.{name[2:]}", x[i]) for i in range(x.shape[0])]
              if name.startswith("h.") else [(name, x)])
    for flat, a in layers:
        if flat.endswith(("qkv.w", "qkv.b")):
            a = a.reshape(a.shape[:-1] + (heads, 3, a.shape[-1] // heads // 3))
            for j, part in enumerate("qkv"):
                yield f"{flat}.{part}", a[..., j, :]
        else:
            yield flat, a


def leaf_norms(tree: dict, minus: dict = None, heads: int = 1) -> dict:
    """{compared leaf: norm} in float32, of ``tree`` or of ``tree - minus``.
    numpy leaves are worked on the host, device leaves on the device."""
    import jax
    import jax.numpy as jnp

    def norms(xp, a, b):
        out = {}
        for k, x in a.items():
            x = x.astype(xp.float32)
            if b is not None:
                x = x - b[k].astype(xp.float32)
            for flat, part in _parts(k, x, heads):
                out[flat] = xp.sqrt(xp.sum(xp.square(part)))
        return out

    if all(isinstance(v, np.ndarray) for v in tree.values()):
        return {k: float(v) for k, v in norms(np, tree, minus).items()}
    got = jax.jit(lambda a, b: norms(jnp, a, b))(tree, minus)
    return {k: float(v) for k, v in got.items()}


def worst_gap(got: dict, want: dict, skip=(), diff: dict = None):
    """The worst leaf's gap between the program's norm and the reference's
    (or, with ``diff``, the worst leaf's norm of their difference), against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, leaf)."""
    med = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        if k in skip:
            continue
        gap = (abs(got[k] - w) if diff is None else diff[k]) / max(w, med)
        if gap > worst:
            worst, where = gap, k
    return worst, where


# ---------------------------------------------------------------------------
# the plain reference's first steps
# ---------------------------------------------------------------------------


def reference_steps(cell, seed, batches, quant=None, rows=None):
    """The reference through ``len(batches)`` steps: float32 parameters on
    the device, AdamW's moments on the HOST and moved a leaf at a time (three
    float32 copies of a 1.3B model do not fit beside its gradient on one
    chip). ``rows`` plants the fault 'part of the batch left out'. Returns
    losses, the first gradient (on the host) and the change's norms, a leaf."""
    import jax
    import jax.numpy as jnp

    from reference import adamw

    cfg, mix, ref = cell.config, cell.mix, cell.reference
    t = cfg["train"]
    spec = cell.arch.weight_spec(cfg, True)
    kw = cell.arch.reference_args(cfg)
    p = {k: v.astype(jnp.float32)
         for k, v in weights.make(spec, seed, cfg["dtype"]).items()}
    heads = cfg.get("num_attention_heads", 1)
    grad = jax.jit(jax.value_and_grad(
        lambda w, ids, lbl: ref.loss(w, ids, lbl, quant=quant, **kw)))
    m = {k: np.zeros(s[0], np.float32) for k, s in spec.items()}
    v = {k: np.zeros(s[0], np.float32) for k, s in spec.items()}
    lr = jnp.float32(mix["lr"])
    losses, first_grad = [], None
    for step, (ids, lbl) in enumerate(batches, start=1):
        if rows is not None:
            ids, lbl = ids[rows], lbl[rows]
        loss, g = grad(p, jnp.asarray(ids), jnp.asarray(lbl))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: np.asarray(x) for k, x in g.items()}
        for k in sorted(p):
            p[k], mk, vk = adamw.update(
                p[k], jnp.asarray(m[k]), jnp.asarray(v[k]), g.pop(k), lr,
                t=step, b1=t["beta1"], b2=t["beta2"], eps=t["epsilon"],
                wd=t["weight_decay"])
            m[k], v[k] = np.asarray(mk), np.asarray(vk)
    change = {}
    for k in sorted(p):  # the seed's leaf again, one at a time
        p0 = weights.make({k: spec[k]}, seed, cfg["dtype"])
        change.update(leaf_norms({k: p.pop(k)}, p0, heads))
    return losses, first_grad, change


def compare(first, ref, limits, heads):
    """The numbers compared, of those a limits file names: each reference
    step's loss; the first gradient's norm by the worst leaf; the norm of its
    difference from the reference's, over all leaves together and by the
    worst leaf; the change's norm by the worst leaf. ``first`` and ``ref``
    are both (losses, first gradient, change's norms)."""
    losses, grad, change = ref
    gnorm = leaf_norms(grad, heads=heads)
    med = float(np.median(list(gnorm.values())))
    # a leaf whose gradient is nought to rounding moves by round-off alone
    still = {k for k, g in gnorm.items() if g < 1e-3 * med}
    rows = [(f"loss_step{i + 1}_rel", abs(first[0][i] - want) / abs(want))
            for i, want in enumerate(losses)]
    g_gap, g_leaf = worst_gap(leaf_norms(first[1], heads=heads), gnorm)
    diff = leaf_norms(first[1], grad, heads)
    d_gap, d_leaf = worst_gap(None, gnorm, diff=diff)
    c_gap, c_leaf = worst_gap(first[2], change, skip=still)
    norm = lambda d: float(np.sqrt(sum(v * v for v in d.values())))  # noqa: E731
    rows += [("grad_norm_worst_leaf", g_gap),
             ("grad_diff_rel", norm(diff) / norm(gnorm)),
             ("grad_diff_worst_leaf", d_gap),
             ("change_norm_worst_leaf", c_gap)]
    rows = [(n, v, limits.get(n)) for n, v in rows]
    return ([r for r in rows if r[2] is not None],
            {"grad_leaf": g_leaf, "diff_leaf": d_leaf, "change_leaf": c_leaf,
             "leaves_left_out": len(still), "all": rows})


# ---------------------------------------------------------------------------
# the first steps and the window
# ---------------------------------------------------------------------------


def first_steps(prog: Program, feed, seed):
    """Drive the program through FIRST_STEPS steps by the window's own call
    and feed. Returns the batches the reference is to follow and, as
    ``reference_steps`` does, (losses, the first gradient as the optimizer
    got it, the change's norms after REFERENCE_STEPS)."""
    losses, batches, grad, change = [], [], None, None
    heads = prog.cfg.get("num_attention_heads", 1)
    for i in range(FIRST_STEPS):
        batch = feed.next()
        batches.append(batch)
        losses.append(float(prog(batch)))
        if i == 0:
            grad = prog.first_gradient()
        if i + 1 == REFERENCE_STEPS:
            p0 = weights.make(prog.spec, seed, prog.cfg["dtype"])
            change = leaf_norms(prog.params(), p0, heads)
            del p0
    return batches[:REFERENCE_STEPS], (losses, grad, change)


def window(prog: Program, feed, seconds, spans, tracer):
    """Steps until ``seconds`` have passed, one step in flight ahead of the
    one being waited for, the last one fenced. Returns (t_open, t_close,
    steps finished)."""
    import jax

    from harness import Span

    t_open = time.perf_counter()
    done, ahead, t_prev = 0, None, t_open
    while True:
        now = time.perf_counter() - t_open
        if now >= seconds:
            break
        tracer.tick(now, seconds)
        with spans.span("make_batch"):
            batch = feed.next()
        with spans.span("dispatch"):
            loss = prog(batch)
        if ahead is not None:
            with spans.span("fence"):
                jax.block_until_ready(ahead)
            t = time.perf_counter()
            spans.rows.append(Span("train.step", t_prev, t))
            done, t_prev = done + 1, t
        ahead = loss
    jax.block_until_ready(ahead)
    t_close = time.perf_counter()
    spans.rows.append(Span("train.step", t_prev, t_close))
    tracer.stop()
    return t_open, t_close, done + 1


def run(cell, seed, seconds, spans, tracer, t_process):
    import jax

    from harness import Outcome, load_limits, memory_peak_bytes, say

    cfg, mix = cell.config, cell.mix
    t0 = time.perf_counter()
    prog = Program(cell, seed)
    say(f"setup: model, weights, optimizer {time.perf_counter() - t0:.1f} s")
    feed = traffic.TrainBatches(mix, seed, cfg["vocab_size"])
    t0 = time.perf_counter()
    batches, first = first_steps(prog, feed, seed)
    say(f"setup: first {FIRST_STEPS} steps (compile and run) "
        f"{time.perf_counter() - t0:.1f} s; losses {first[0]}")
    compiled = len(prog.step._cache)
    t_open, t_close, steps = window(prog, feed, seconds, spans, tracer)
    if len(prog.step._cache) != compiled:
        raise SystemExit("train.py: a program compiled inside the window")
    tokens = steps * feed.tokens_per_step
    e2e = {"train_tokens_per_s": tokens / (t_close - t_open)}
    counters = {"steps_in_window": steps, "tokens_in_window": tokens,
                "window_s": t_close - t_open, "compiles_in_window": 0}
    peak = memory_peak_bytes(cell.chips)
    del prog
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    ref = reference_steps(cell, seed, batches)
    say(f"check: reference through {REFERENCE_STEPS} steps "
        f"{time.perf_counter() - t0:.1f} s; losses {ref[0]}")
    compared, detail = compare(first, ref, load_limits(cell),
                               cfg.get("num_attention_heads", 1))
    say(f"check: {detail}")
    return Outcome(
        setup_s=t_open - t_process, end_to_end=e2e, attempted=steps, failed=0,
        compared=compared, counters=counters, window=(t_open, t_close),
        memory_peak_bytes=peak,
        extra={"tokens_per_step": feed.tokens_per_step, "steps": steps})
