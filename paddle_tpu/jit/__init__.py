"""paddle.jit parity: trace-and-compile stateful Layer programs.

Reference: ``python/paddle/jit/`` — dy2static rewrites Python AST into a
static Program, which the StandaloneExecutor runs (SURVEY.md §2.2 "Dy2Static",
§3.4). TPU-native design (SURVEY.md §7 "Design stance"): ``to_static`` LIFTS a
stateful Layer computation into a pure function of (params, buffers, args,
rng_key), traces it ONCE with jax, and caches the compiled XLA executable per
input signature — the "static graph mode" IS the jit cache. No AST rewriting:
data-dependent Python control flow simply triggers a retrace per branch taken
(guard semantics), and `.numpy()` inside a traced region raises with guidance.

``TrainStep`` is the training analogue: forward + backward + optimizer update
fused into ONE compiled program (the per-op dispatch loop of the reference's
DyGraph — §3.1 step 5 — disappears; XLA schedules the whole step).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..framework import rng as _rng
from ..runtime import compile_cache as _compile_cache
from ..framework.core import Tensor, TraceHostSyncError, no_grad
from ..framework.op import raw
from ..nn.layer import Layer


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape=None, dtype="float32", name=None, stop_gradient=True):
        from ..framework.dtypes import convert_dtype

        self.shape = list(shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def _is_tensor(x):
    return isinstance(x, Tensor)


def _collect_layers(obj) -> List[Layer]:
    if isinstance(obj, Layer):
        return [obj]
    self_obj = getattr(obj, "__self__", None)
    if isinstance(self_obj, Layer):
        return [self_obj]
    # function closures may reference layers
    layers = []
    closure = getattr(obj, "__closure__", None) or ()
    for cell in closure:
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        if isinstance(v, Layer):
            layers.append(v)
    g = getattr(obj, "__globals__", None)
    return layers


class TracedLayer:
    """The product of ``to_static``: a signature-cached compiled callable."""

    def __init__(self, fn: Callable, layers: Optional[Sequence[Layer]] = None, full_graph=True):
        self._fn = fn
        self._orig_fn = fn
        self._layers = list(layers) if layers is not None else _collect_layers(fn)
        self._cache = {}
        self._last_out_tree = None
        self._eager_fallback = False
        self._tried_dy2static = False
        functools.update_wrapper(self, fn, updated=[])

    def _state_tensors(self):
        tensors, is_buffer = [], []
        seen = set()
        for layer in self._layers:
            for _, p in layer.named_parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    tensors.append(p)
                    is_buffer.append(False)
            for _, b in layer.named_buffers():
                if id(b) not in seen:
                    seen.add(id(b))
                    tensors.append(b)
                    is_buffer.append(True)
        return tensors, is_buffer

    def __call__(self, *args, **kwargs):
        from ..framework import op as _op

        if _op._capture_program is not None:
            # static Program capture is active: run eagerly so this
            # callable's ops are recorded (a jit trace would freeze its
            # output as a capture-time constant)
            return self._fn(*args, **kwargs)
        if self._eager_fallback or not _to_static_enabled:
            return self._fn(*args, **kwargs)
        from .dy2static import Dy2StaticError

        try:
            return self._traced_call(*args, **kwargs)
        except (TraceHostSyncError, Dy2StaticError):
            # dy2static (SURVEY.md §7 hard-part #1): the trace hit a host
            # sync (`if tensor:`, `while tensor:`, `.numpy()`). First try
            # the AST conversion (Python control flow -> lax.cond/
            # while_loop, mirroring the reference's program_translator);
            # only if the CONVERTED function still host-syncs (e.g. a
            # genuine `.numpy()` call) — or a LATER retrace of the
            # converted fn hits a structural Dy2StaticError — fall back to
            # eager like the reference's dygraph fallback.
            if not self._tried_dy2static:
                self._tried_dy2static = True
                from .dy2static import convert_to_static

                converted = convert_to_static(self._orig_fn)
                if converted is not None:
                    # drop executables compiled against the original fn
                    self._fn = converted
                    self._cache.clear()
                    try:
                        return self._traced_call(*args, **kwargs)
                    except (TraceHostSyncError, Dy2StaticError):
                        self._fn = self._orig_fn
                        self._cache.clear()
            else:
                # a later-signature retrace failed: revert to the original
                # for the eager fallback below
                self._fn = self._orig_fn
                self._cache.clear()
            import warnings

            warnings.warn(
                f"to_static({getattr(self._fn, '__name__', self._fn)!r}): a "
                "host sync point (.numpy()/float()/`if tensor:`) was hit "
                "during tracing and dy2static conversion could not compile "
                "it; falling back to EAGER execution for this callable. Use "
                "paddle_tpu.static.nn.cond/while_loop/switch_case to keep "
                "data-dependent control flow compiled.",
                stacklevel=2,
            )
            self._eager_fallback = True
            from .dy2static import _log_conversion

            _log_conversion(
                self._orig_fn, "fallback",
                reason="host sync survived dy2static conversion; whole "
                       "callable runs eagerly")
            return self._fn(*args, **kwargs)

    def conversion_report(self) -> dict:
        """Which callees compiled and which fell back (VERDICT r4 weak #6:
        a mostly-fallen-back model must be inspectable, not silent).

        Returns ``{"entry": qualname, "entry_mode": "compiled"|"eager",
        "n_converted": int, "n_fallback": int, "callees": {qualname:
        {status, reason?, notes?}}}``. ``callees`` is the process-wide
        convert_call/convert_to_static decision log — populated as traces
        run, so call it AFTER the first execution."""
        from .dy2static import conversion_log

        log = conversion_log()
        n_conv = sum(1 for v in log.values() if v["status"] == "converted")
        return {
            "entry": getattr(self._orig_fn, "__qualname__",
                             repr(self._orig_fn)),
            "entry_mode": "eager" if self._eager_fallback else "compiled",
            "n_converted": n_conv,
            "n_fallback": len(log) - n_conv,
            "callees": log,
        }

    def _traced_call(self, *args, **kwargs):
        state, is_buffer = self._state_tensors()
        state_vals = [t._value for t in state]
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)
        arg_vals = [l._value if isinstance(l, Tensor) else l for l in leaves]
        # traced leaves: Tensors and ndarray-likes; python scalars stay static
        arr_idx = [
            i
            for i, (l, v) in enumerate(zip(leaves, arg_vals))
            if isinstance(l, Tensor) or isinstance(v, (np.ndarray, jax.Array))
        ]
        tensor_flags = tuple(isinstance(leaves[i], Tensor) for i in arr_idx)
        arr_vals = [jnp.asarray(arg_vals[i]) for i in arr_idx]
        static_part = tuple(
            (i, arg_vals[i]) for i in range(len(arg_vals)) if i not in set(arr_idx)
        )
        training = tuple(l.training for l in self._layers)
        key = (
            treedef,
            tuple((tuple(v.shape), str(v.dtype)) for v in arr_vals),
            static_part,
            training,
            len(state_vals),
        )
        entry = self._cache.get(key)
        miss_t0 = None
        if entry is None:
            # cache miss = an XLA (re)compile; the jit wrapper is lazy, so
            # the timer must span the first jitted call below too
            miss_t0 = time.perf_counter()
            entry = self._compile(treedef, arr_idx, tensor_flags, static_part, state, is_buffer)
            self._cache[key] = entry
        jitted, out_tree_box = entry
        rng_key = _rng.next_key()
        outs_flat, new_state = jitted(state_vals, arr_vals, rng_key)
        if miss_t0 is not None:
            _obs.record_compile(
                "to_static", time.perf_counter() - miss_t0,
                signature=f"{getattr(self._fn, '__qualname__', self._fn)} "
                          f"cache_size={len(self._cache)}")
        for t, v, buf in zip(state, new_state, is_buffer):
            t._value = v
        out_tree = out_tree_box[0]
        wrapped = [Tensor(o) if hasattr(o, "shape") else o for o in outs_flat]
        return jax.tree_util.tree_unflatten(out_tree, wrapped)

    def _compile(self, treedef, arr_idx, tensor_flags, static_part, state, is_buffer):
        fn = self._fn
        out_tree_box = [None]
        static_map = dict(static_part)

        def pure(state_vals, arr_vals, rng_key):
            vals = dict(static_map)
            for i, v, was_t in zip(arr_idx, arr_vals, tensor_flags):
                vals[i] = Tensor(v) if was_t else v
            rebuilt = [vals[i] for i in range(len(vals))]
            a, k = jax.tree_util.tree_unflatten(treedef, rebuilt)
            originals = [t._value for t in state]
            with _rng.trace_key_scope(rng_key):
                try:
                    for t, sv in zip(state, state_vals):
                        t._value = sv
                    out = fn(*a, **k)
                    new_state = [t._value for t in state]
                finally:
                    for t, ov in zip(state, originals):
                        t._value = ov
            out_leaves, out_tree = jax.tree_util.tree_flatten(
                out, is_leaf=_is_tensor
            )
            out_tree_box[0] = out_tree
            out_vals = [o._value if isinstance(o, Tensor) else o for o in out_leaves]
            return out_vals, new_state

        jitted = jax.jit(pure)
        return jitted, out_tree_box

    # introspection helpers (paddle parity-ish)
    @property
    def program_cache_size(self):
        return len(self._cache)


_to_static_enabled = True


def enable_to_static(enable: bool = True):
    """paddle.jit.enable_to_static parity: a global kill-switch for
    ``to_static`` (debugging aid — with it off, decorated functions run
    eagerly; already-built TracedLayers bypass their compiled cache)."""
    global _to_static_enabled
    _to_static_enabled = True if enable else False


def to_static(function=None, input_spec=None, build_strategy=None, full_graph=True, backend=None, **kwargs):
    """paddle.jit.to_static parity: decorator or direct call on Layer/function."""

    def deco(fn):
        if isinstance(fn, Layer):
            traced = TracedLayer(fn.forward, layers=[fn])
            fn.forward = traced
            return fn
        return TracedLayer(fn)

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn=None):
    if fn is None:
        return lambda f: f
    return fn


def ignore_module(modules):
    pass


from .save_load import save, load, TranslatedLayer  # noqa: E402
from .dy2static import (  # noqa: E402,F401  (debug verbosity parity)
    get_code_level,
    get_verbosity,
    set_code_level,
    set_verbosity,
)


class TrainStep:
    """Fused, compiled train step: forward + grad + optimizer in one XLA program.

    TPU-native replacement for the reference's per-op DyGraph train loop
    (SURVEY.md §3.2). Under a device mesh, the same class compiles the SPMD
    program (sharded params in = sharded params out) — used by fleet.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate=True):
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._cache = {}
        self._donate = donate
        # stable state ordering
        self._params = [p for p in optimizer._parameter_list]
        seen = {id(p) for p in self._params}
        self._buffers = [b for _, b in model.named_buffers() if id(b) not in seen]
        self._extra_params = [
            p for _, p in model.named_parameters() if id(p) not in seen
        ]

    def __call__(self, *batch):
        batch_vals = self._place_batch(
            [raw(b) if isinstance(b, Tensor) else jnp.asarray(b) for b in batch])
        key = tuple((tuple(v.shape), str(v.dtype)) for v in batch_vals)
        loss_val = self._dispatch(key, self._compile, batch_vals)
        return Tensor(loss_val)

    def _dispatch(self, key, build, batch_vals):
        """Shared plumbing for the single-step and multi-step paths: state
        extraction, cache get-or-compile, rng draw, and the write-back of
        params/buffers/optimizer states. Returns the jitted fn's first
        output (loss scalar or per-step losses). A warm call is one
        ``train_step`` span over its three parts: the HOST's time to
        dispatch the step, not the step's (nothing here waits for the
        device)."""
        t0 = time.perf_counter()
        jitted = self._cache.get(key)
        if jitted is None:
            return self._dispatch_miss(key, build, batch_vals, t0)
        with _obs.span("train_step"):
            with _obs.span("train_gather"):
                args = self._gather(batch_vals)
            with _obs.span("train_dispatch"):
                out, new_p, new_b, new_st = jitted(*args)
            with _obs.span("train_writeback"):
                self._write_back(new_p, new_b, new_st)
        _obs.observe("train_step_seconds", time.perf_counter() - t0)
        return out

    def _gather(self, batch_vals):
        """The compiled step's arguments from the live state."""
        return (
            [p._value for p in self._params],
            [b._value for b in self._buffers + self._extra_params],
            self._opt.functional_states(), batch_vals,
            jnp.asarray(self._opt.get_lr(), jnp.float32), _rng.next_key())

    def _write_back(self, new_p, new_b, new_st):
        for p, v in zip(self._params, new_p):
            p._value = v
        for b, v in zip(self._buffers + self._extra_params, new_b):
            b._value = v
        self._opt.load_functional_states(new_st)

    def _dispatch_miss(self, key, build, batch_vals, t0):
        """The first call of a signature: build, load or compile, run.
        Tracked apart from the warm steps so that it does not pollute the
        steady-state step-time distribution (record_compile also emits the
        'compile' span)."""
        args = self._gather(batch_vals)
        jitted = build()
        aot_hit = None
        aot = _compile_cache.resolve()
        if aot is not None:
            try:
                lowered = jitted.lower(*args)
                ckey = aot.key_for(lowered, config=self._aot_key_parts(),
                                   mesh=self._aot_mesh())
                jitted, aot_hit = aot.load_or_compile(
                    lowered, ckey, where="train_step")
            except Exception:  # noqa: BLE001
                # the cache must never break training — fall back to
                # the plain jit path (first call compiles normally)
                jitted, aot_hit = build(), None
        self._cache[key] = jitted
        out, new_p, new_b, new_st = jitted(*args)
        self._write_back(new_p, new_b, new_st)
        _obs.record_compile("train_step", time.perf_counter() - t0,
                            signature=f"{type(self).__name__} {key!r}",
                            cache_hit=aot_hit)
        return out

    def _place_batch(self, batch_vals):
        """Hook: distributed subclasses place the batch on the data mesh axes
        (fleet.DistTrainStep)."""
        return batch_vals

    def _aot_key_parts(self):
        """Semantic fingerprint parts for the persistent AOT compile cache
        (``runtime.compile_cache``). The lowered-module hash covers program
        structure; subclasses add strategy/topology knobs so a changed
        layout misses even before lowering diverges."""
        return {"step": type(self).__name__, "donate": bool(self._donate)}

    def _aot_mesh(self):
        """Hook: the mesh whose axis names/sizes key the AOT cache entry
        (fleet.DistTrainStep returns the global mesh)."""
        return None

    def _compiled_for(self, *batch):
        """Lower+compile the step for this batch signature (cached) and
        return the XLA Compiled object for introspection."""
        lowered, key = self._lower_for(*batch, _with_key=True)
        cache = self.__dict__.setdefault("_introspect_compiled", {})
        if key not in cache:
            cache[key] = lowered.compile()
        return cache[key]

    def _lower_for(self, *batch, _with_key=False):
        """The jax Lowered object (pre-optimization StableHLO) for this
        batch signature — program structure BEFORE XLA fusion/CSE.
        Lowerings and compiles are cached per signature: cost_analysis +
        memory_analysis + as_text on one step must not trigger repeated
        multi-second XLA compiles."""
        p_vals = [p._value for p in self._params]
        b_vals = [b._value for b in self._buffers + self._extra_params]
        opt_states = self._opt.functional_states()
        batch_vals = [raw(b) if isinstance(b, Tensor) else jnp.asarray(b) for b in batch]
        batch_vals = self._place_batch(batch_vals)
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        key = tuple((tuple(v.shape), str(v.dtype)) for v in batch_vals)
        jitted = self._cache.get(key)
        if jitted is None:
            jitted = self._compile()
            self._cache[key] = jitted
        elif not hasattr(jitted, "lower"):
            # the dispatch cache may hold an AOT Compiled (persistent
            # compile-cache path) — lower from a fresh traceable jit
            # without evicting the warm executable
            jitted = self._compile()
        rng_key = _rng.next_key()
        lcache = self.__dict__.setdefault("_introspect_lowered", {})
        if key not in lcache:
            lcache[key] = jitted.lower(
                p_vals, b_vals, opt_states, batch_vals, lr, rng_key)
        if _with_key:
            return lcache[key], key
        return lcache[key]

    def cost_analysis(self, *batch):
        """XLA cost analysis (flops, bytes accessed) of the compiled step for
        this batch signature. Feeds MFU reporting (bench.py); the reference
        has no per-program cost introspection — this rides XLA's
        ``compiled.cost_analysis()`` (same source as hapi.flops)."""
        cost = self._compiled_for(*batch).cost_analysis()
        # jax returns either a dict or a one-element list of dicts
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})

    def memory_analysis(self, *batch):
        """PER-DEVICE memory footprint of the compiled step, from XLA's
        CompiledMemoryStats: argument/output/temp/code bytes. Under a mesh
        the compiled program is the per-device SPMD program, so ZeRO
        sharding and rematerialization wins are directly measurable here
        (the quantitative counterpart of the reference's GroupSharded
        memory claims; `paddle.device.cuda.memory_*` report the live PJRT
        allocator numbers at runtime)."""
        m = self._compiled_for(*batch).memory_analysis()
        fields = (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
        out = {f: int(getattr(m, f, 0)) for f in fields}
        out["live_size_in_bytes"] = (
            out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            + out["temp_size_in_bytes"] - out["alias_size_in_bytes"]
        )
        return out

    # -- compiled multi-step loops (scan over steps) ------------------------
    def repeat(self, n, *batch):
        """Run ``n`` optimizer steps on the SAME batch inside ONE compiled
        program (lax.scan carrying params/buffers/opt-states); returns the
        per-step losses as a length-``n`` Tensor.

        This is the TPU-idiomatic training-loop shape (MaxText-style
        scan-over-steps): per-step host dispatch disappears.
        The learning rate is held constant within the compiled window;
        step LR schedulers between windows. Per-step dropout keys are
        folded from one base key (jax.random.fold_in on the step index).
        """
        return self._run_multi(int(n), False, batch)

    def run_steps(self, *stacked_batch):
        """Like ``repeat`` but every batch argument carries a leading
        [n_steps, ...] axis: step i consumes slice i (scan over the data).
        Returns the per-step losses."""
        n = int(raw(stacked_batch[0]).shape[0])
        return self._run_multi(n, True, stacked_batch)

    def _run_multi(self, n, stacked, batch):
        batch_vals = [raw(b) if isinstance(b, Tensor) else jnp.asarray(b) for b in batch]
        if stacked:
            short = [i for i, v in enumerate(batch_vals)
                     if v.ndim == 0 or v.shape[0] != n]
            if short:
                raise ValueError(
                    f"run_steps: batch args {short} have leading axis "
                    f"{[batch_vals[i].shape[0] for i in short]} != {n} "
                    "(every arg must stack one slice per step — JAX's "
                    "clamping gather would otherwise silently repeat the "
                    "last slice)"
                )
            # placement of each per-step slice happens inside the scan body
        else:
            batch_vals = self._place_batch(batch_vals)
        key = ("multi", stacked, n,
               tuple((tuple(v.shape), str(v.dtype)) for v in batch_vals))
        losses = self._dispatch(
            key, lambda: self._jit(self._build_multi(n, stacked)),
            batch_vals)
        return Tensor(losses)

    def _build_multi(self, n, stacked):
        step = self._build_step()
        place = self._place_batch

        def multi(p_vals, b_vals, opt_states, batch_vals, lr, rng_key):
            def body(carry, i):
                p, b, st = carry
                bv = [v[i] for v in batch_vals] if stacked else batch_vals
                if stacked:
                    bv = place(bv)
                loss, p2, b2, st2 = step(
                    p, b, st, bv, lr, jax.random.fold_in(rng_key, i))
                return (p2, b2, st2), loss

            (p, b, st), losses = jax.lax.scan(
                body, (p_vals, b_vals, opt_states), jnp.arange(n))
            return losses, p, b, st

        return multi

    def _compile(self):
        return self._jit(self._build_step())

    def _make_loss_of(self, changed_cell=None):
        """The pure (train_vals, (b_vals, batch, key)) -> (loss, new_b)
        closure shared by every step builder. ``changed_cell`` (a list)
        receives, at trace time, one tuple of per-buffer "was mutated"
        flags — identity comparison during tracing is a static fact, and
        distributed builders use it to decide which buffers need a
        cross-replica mean without burning collectives on constants."""
        model, loss_fn = self._model, self._loss_fn
        params, buffers = self._params, self._buffers + self._extra_params

        def loss_of(train_vals, fixed):
            b_vals, batch_vals, rng_key = fixed
            orig_p = [p._value for p in params]
            orig_b = [b._value for b in buffers]
            with _rng.trace_key_scope(rng_key):
                try:
                    for p, v in zip(params, train_vals):
                        p._value = v
                    for b, v in zip(buffers, b_vals):
                        b._value = v
                    batch_t = [Tensor(v) for v in batch_vals]
                    with jax.named_scope("forward_loss"):
                        loss = loss_fn(model, *batch_t)
                    loss_val = raw(loss)
                    new_b = [b._value for b in buffers]
                finally:
                    for p, v in zip(params, orig_p):
                        p._value = v
                    for b, v in zip(buffers, orig_b):
                        b._value = v
            if changed_cell is not None:
                changed_cell[:] = [tuple(
                    nv is not v for nv, v in zip(new_b, b_vals))]
            return loss_val, new_b

        return loss_of

    def _build_step(self):
        opt = self._opt
        trainable = [p.trainable for p in self._params]
        loss_of = self._make_loss_of()

        def step(p_vals, b_vals, opt_states, batch_vals, lr, rng_key):
            (loss_val, new_b), grads = jax.value_and_grad(loss_of, has_aux=True)(
                p_vals, (b_vals, batch_vals, rng_key)
            )
            grads = [g if t else None for g, t in zip(grads, trainable)]
            with jax.named_scope("optimizer_update"):
                new_p, new_st = opt.functional_step(
                    p_vals, grads, opt_states, lr)
            return loss_val, new_p, new_b, new_st

        return step

    def _jit(self, step):
        donate = (0, 2) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)
