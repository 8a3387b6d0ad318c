"""Optimizers (paddle.optimizer parity).

Reference: ``python/paddle/optimizer/`` — SGD/Momentum/Adagrad/Adam/AdamW/
Adamax/Lamb/RMSProp, LRScheduler family, grad clip (SURVEY.md §2.2).

TPU-native design: each optimizer's math is one pure jnp update rule
(`_rule`). The eager ``step()`` applies it per parameter (like the reference's
per-param adam op); ``paddle_tpu.jit.TrainStep`` calls the same rule inside
the compiled train step, where XLA fuses all parameter updates into one
program (the reference needs a separate fused multi_tensor_adam for this —
here it falls out of compilation).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtypes as _dtypes
from ..framework.core import Tensor, no_grad
from ..framework.op import raw
from . import lr  # noqa: F401
from .lr import LRScheduler


# ------------------------------------------------------------- grad clip ----
class ClipGradBase:
    def __call__(self, params_grads):
        raise NotImplementedError


class ClipGradByValue(ClipGradBase):
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def __call__(self, params_grads):
        return [(p, None if g is None else jnp.clip(g, self.min, self.max)) for p, g in params_grads]


class ClipGradByNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
                continue
            n = jnp.sqrt(jnp.sum(jnp.square(g)))
            scale = jnp.minimum(self.clip_norm / jnp.maximum(n, 1e-12), 1.0)
            out.append((p, g * scale))
        return out


class ClipGradByGlobalNorm(ClipGradBase):
    """Global-norm clip (reference: ClipGradByGlobalNorm — the hybrid-parallel
    default). Under SPMD the norm over sharded grads is computed by XLA with
    an implicit all-reduce."""

    def __init__(self, clip_norm, group_name="default_group"):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        sq = [jnp.sum(jnp.square(g.astype(jnp.float32))) for p, g in params_grads if g is not None]
        if not sq:
            return params_grads
        gnorm = jnp.sqrt(sum(sq))
        scale = self.clip_norm / jnp.maximum(gnorm, self.clip_norm)
        return [(p, None if g is None else (g * scale).astype(g.dtype)) for p, g in params_grads]


# ------------------------------------------------------------ regularizer ----
class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, p, g):
        return g + self.coeff * p


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, p, g):
        return g + self.coeff * jnp.sign(p)


# --------------------------------------------------------------- optimizer --
class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, name=None):
        if parameters is None:
            raise ValueError("parameters must be provided (list of Parameters)")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if isinstance(weight_decay, (float, int)):
            self._regularizer = L2Decay(float(weight_decay))
            self._coupled_wd = None
        else:
            self._regularizer = weight_decay  # L1Decay/L2Decay instance or None
            self._coupled_wd = None
        self._accumulators: List[dict] = [None] * len(self._parameter_list)
        self._use_master_weights = False
        self._master = {}

    # -- lr ----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("set_lr cannot be used with an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- state -------------------------------------------------------------
    def _init_state(self, p) -> dict:
        return {}

    def _rule(self, p, g, st, lr):
        """Pure update rule: (param, grad, state, lr) -> (new_param, new_state)."""
        raise NotImplementedError

    # -- eager step (DyGraph parity: reads .grad, updates in place) ---------
    @no_grad()
    def step(self):
        pg = [(p, raw(p.grad) if p.grad is not None else None) for p in self._parameter_list if p.trainable]
        if self._grad_clip is not None:
            vals = [(raw(p), g) for p, g in pg]
            clipped = self._grad_clip(vals)
            pg = [(p, cg) for (p, _), (_, cg) in zip(pg, clipped)]
        lr = self.get_lr()
        grad_by_id = {id(q): gg for q, gg in pg}
        for i, p in enumerate(self._parameter_list):
            if not p.trainable:
                continue
            g = grad_by_id.get(id(p))
            if g is None:
                continue
            plr = lr * p.optimize_attr.get("learning_rate", 1.0)
            if self._accumulators[i] is None:
                self._accumulators[i] = self._init_state(p)
            pv = raw(p)
            if self._use_master_weights and pv.dtype != jnp.float32:
                mv = self._master.get(i)
                if mv is None:
                    mv = pv.astype(jnp.float32)
                g32 = g.astype(jnp.float32)
                g32 = self._apply_decay(mv, g32, p)
                new_m, self._accumulators[i] = self._rule(mv, g32, self._accumulators[i], plr)
                self._master[i] = new_m
                p._rebind(new_m.astype(pv.dtype))
            else:
                g = self._apply_decay(pv, g.astype(pv.dtype), p)
                new_p, self._accumulators[i] = self._rule(pv, g, self._accumulators[i], plr)
                p._rebind(new_p)

    def _apply_decay(self, pv, g, p):
        reg = p.regularizer or self._regularizer
        if reg is not None:
            g = reg(pv, g)
        return g

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()

    # -- functional step (used by paddle_tpu.jit.TrainStep) -----------------
    def functional_states(self):
        for i, p in enumerate(self._parameter_list):
            if self._accumulators[i] is None:
                self._accumulators[i] = self._init_state(p)
        return list(self._accumulators)

    def load_functional_states(self, states):
        self._accumulators = list(states)

    def functional_step(self, param_vals, grad_vals, states, lr):
        """Pure: lists of values -> (new_params, new_states). No side effects."""
        if self._grad_clip is not None:
            clipped = self._grad_clip(list(zip(param_vals, grad_vals)))
            grad_vals = [g for _, g in clipped]
        return self.functional_update(param_vals, grad_vals, states, lr)

    def functional_update(self, param_vals, grad_vals, states, lr):
        """functional_step minus grad clip: the raw per-parameter rule.
        Distributed callers that clip on a different data layout (e.g. the
        ZeRO shard-local update in fleet, where the global norm is a scalar
        psum over shard blocks) clip first, then call this directly."""
        new_ps, new_sts = [], []
        for p, pv, g, st in zip(self._parameter_list, param_vals, grad_vals, states):
            if g is None or not p.trainable:
                new_ps.append(pv)
                new_sts.append(st)
                continue
            plr = lr * p.optimize_attr.get("learning_rate", 1.0)
            g = self._apply_decay(pv, g.astype(pv.dtype), p)
            np_, nst = self._rule(pv, g, st, plr)
            new_ps.append(np_)
            new_sts.append(nst)
        return new_ps, new_sts

    # -- serialization -------------------------------------------------------
    def state_dict(self):
        out = {}
        for i, st in enumerate(self._accumulators):
            if st is None:
                continue
            name = self._parameter_list[i].name or f"param_{i}"
            for k, v in st.items():
                # COPY array leaves: under TrainStep the live state buffers
                # are donated to the next compiled step, which would delete
                # a by-reference checkpoint out from under the caller
                out[f"{name}.{k}"] = (
                    v if isinstance(v, (int, float)) else Tensor(jnp.array(v))
                )
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        sched_state = state.get("LR_Scheduler")
        if sched_state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(sched_state)
        for i, p in enumerate(self._parameter_list):
            name = p.name or f"param_{i}"
            st = self._init_state(p)
            found = False
            for k in list(st):
                key = f"{name}.{k}"
                if key in state:
                    v = state[key]
                    st[k] = raw(v) if isinstance(v, Tensor) else v
                    found = True
            if found:
                self._accumulators[i] = st


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._use_master_weights = bool(multi_precision)

    def _rule(self, p, g, st, lr):
        return p - lr * g, st


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None, use_nesterov=False,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(raw(p))}

    def _rule(self, p, g, st, lr):
        v = self._momentum * st["velocity"] + g
        if self._nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None, weight_decay=None,
                 grad_clip=None, initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, p):
        return {"moment": jnp.full_like(raw(p), self._init_acc)}

    def _rule(self, p, g, st, lr):
        m = st["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self._epsilon), {"moment": m}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._use_master_weights = multi_precision

    def _init_state(self, p):
        pv = raw(p)
        dt = jnp.float32 if self._use_master_weights else pv.dtype
        return {
            "moment1": jnp.zeros(pv.shape, dt),
            "moment2": jnp.zeros(pv.shape, dt),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _rule(self, p, g, st, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        b1p = st["beta1_pow"] * b1
        b2p = st["beta2_pow"] * b2
        m1 = b1 * st["moment1"] + (1 - b1) * g
        m2 = b2 * st["moment2"] + (1 - b2) * jnp.square(g)
        mhat = m1 / (1 - b1p)
        vhat = m2 / (1 - b2p)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + eps)
        return new_p.astype(p.dtype), {"moment1": m1, "moment2": m2, "beta1_pow": b1p, "beta2_pow": b2p}


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=0.01, lr_ratio=None, apply_decay_param_fun=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, None, grad_clip,
                         lazy_mode, multi_precision, name)
        self._wd = float(weight_decay) if isinstance(weight_decay, (int, float)) else 0.01
        self._apply_decay_fn = apply_decay_param_fun
        self._lr_ratio = lr_ratio
        self._decay_skip = set()
        if apply_decay_param_fun is not None:
            for p in self._parameter_list:
                if not apply_decay_param_fun(p.name or ""):
                    self._decay_skip.add(id(p))

    def functional_step(self, param_vals, grad_vals, states, lr):
        # decoupled decay folded into _rule via closure over per-call flag
        return super().functional_step(param_vals, grad_vals, states, lr)

    def _rule(self, p, g, st, lr):
        decay = getattr(self, "_current_decay", self._wd)
        if decay:
            # lr is an f32 array: without the cast a bf16 parameter comes
            # back f32, doubling its bytes and recompiling the next step
            p = (p * (1.0 - lr * decay)).astype(p.dtype)
        return super()._rule(p, g, st, lr)

    @no_grad()
    def step(self):
        # set per-param decay flags around the base step
        base_step = super().step
        orig = self._wd
        # base class handles the loop; per-param skip via _current_decay
        # simplest: temporarily zero decay for skipped params by monkey flag
        if not self._decay_skip:
            base_step()
            return
        # slow path with per-param decay decisions
        for i, p in enumerate(self._parameter_list):
            self._current_decay = 0.0 if id(p) in self._decay_skip else self._wd
            # apply one-param step by faking a single-item list
            if p.grad is None or not p.trainable:
                continue
            if self._accumulators[i] is None:
                self._accumulators[i] = self._init_state(p)
            g = raw(p.grad)
            if self._grad_clip is not None:
                g = self._grad_clip([(raw(p), g)])[0][1]
            new_p, self._accumulators[i] = self._rule(raw(p), g.astype(raw(p).dtype), self._accumulators[i], self.get_lr() * p.optimize_attr.get("learning_rate", 1.0))
            p._rebind(new_p)
        self._current_decay = orig


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, p):
        pv = raw(p)
        return {"moment": jnp.zeros_like(pv), "inf_norm": jnp.zeros_like(pv), "beta1_pow": jnp.ones((), jnp.float32)}

    def _rule(self, p, g, st, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        b1p = st["beta1_pow"] * b1
        m = b1 * st["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * st["inf_norm"], jnp.abs(g) + eps)
        new_p = p - (lr / (1 - b1p)) * m / u
        return new_p, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0, centered=False,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _init_state(self, p):
        pv = raw(p)
        st = {"mean_square": jnp.zeros_like(pv), "velocity": jnp.zeros_like(pv)}
        if self._centered:
            st["mean_grad"] = jnp.zeros_like(pv)
        return st

    def _rule(self, p, g, st, lr):
        ms = self._rho * st["mean_square"] + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * st["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            mg = None
            denom = jnp.sqrt(ms + self._epsilon)
        v = self._momentum * st["velocity"] + lr * g / denom
        new_st = {"mean_square": ms, "velocity": v}
        if mg is not None:
            new_st["mean_grad"] = mg
        return p - v, new_st


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-06, parameters=None, grad_clip=None, exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, p):
        pv = raw(p)
        st = {"moment1": jnp.zeros_like(pv), "moment2": jnp.zeros_like(pv),
              "beta1_pow": jnp.ones((), jnp.float32), "beta2_pow": jnp.ones((), jnp.float32)}
        if self._exclude_fn is not None and self._exclude_fn(p.name or ""):
            # jit-static exclusion marker (pytree structure, not a bool
            # leaf — see Lars._init_state)
            st["wd_excluded"] = ()
        return st

    def _rule(self, p, g, st, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = 0.0 if "wd_excluded" in st else self._wd
        b1p = st["beta1_pow"] * b1
        b2p = st["beta2_pow"] * b2
        m1 = b1 * st["moment1"] + (1 - b1) * g
        m2 = b2 * st["moment2"] + (1 - b2) * jnp.square(g)
        mhat = m1 / (1 - b1p)
        vhat = m2 / (1 - b2p)
        r = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, dict(st, moment1=m1, moment2=m2,
                                        beta1_pow=b1p, beta2_pow=b2p)


class Lars(Optimizer):
    """LARS momentum (You et al. 2017): layerwise trust-ratio scaling for
    large-batch training. Reference:
    ``paddle/fluid/optimizer.py::LarsMomentumOptimizer`` /
    ``lars_momentum_op`` (enabled by DistributedStrategy's lars flag).

    local_lr = lr * lars_coeff * ||p|| / (||g|| + lars_weight_decay*||p||)
    v        = momentum * v + local_lr * (g + lars_weight_decay * p)
    p       -= v
    Parameters matched by ``exclude_from_weight_decay`` (substring on the
    param name, as upstream) run with lars_weight_decay = 0 but KEEP the
    trust-ratio local lr (upstream zeroes only the decay term).
    """

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=0.0,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._momentum = float(momentum)
        self._coeff = float(lars_coeff)
        self._lars_wd = float(lars_weight_decay)
        self._epsilon = float(epsilon)
        self._exclude = list(exclude_from_weight_decay or [])
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        st = {"velocity": jnp.zeros_like(raw(p))}
        if any(s in (p.name or "") for s in self._exclude):
            # the exclusion marker must be STATIC under jit (a bool leaf
            # would become a traced array and `if excluded:` would raise
            # TracerBoolConversionError in jit.TrainStep) — encode it as
            # pytree STRUCTURE: an empty-tuple entry carries no leaves but
            # survives the functional state round-trip
            st["excluded"] = ()
        return st

    def _rule(self, p, g, st, lr):
        wd = 0.0 if "excluded" in st else self._lars_wd
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        denom = g_norm + wd * p_norm + self._epsilon
        local_lr = jnp.where(
            (p_norm > 0) & (denom > 0),
            lr * self._coeff * p_norm / denom, lr)
        v = self._momentum * st["velocity"] + local_lr * (g + wd * p)
        return p - v, dict(st, velocity=v)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon

    def _init_state(self, p):
        pv = raw(p)
        return {"avg_squared_grad": jnp.zeros_like(pv), "avg_squared_update": jnp.zeros_like(pv)}

    def _rule(self, p, g, st, lr):
        asg = self._rho * st["avg_squared_grad"] + (1 - self._rho) * jnp.square(g)
        update = -jnp.sqrt((st["avg_squared_update"] + self._epsilon) / (asg + self._epsilon)) * g
        asu = self._rho * st["avg_squared_update"] + (1 - self._rho) * jnp.square(update)
        return p + lr * update, {"avg_squared_grad": asg, "avg_squared_update": asu}


class ASGD(Optimizer):
    """Stochastic Average Gradient descent (reference:
    ``python/paddle/optimizer/asgd.py``). ``d`` holds the running SUM of the
    last ``batch_num`` gradients via a rotating slot buffer ``ys``:
    ``d <- d - ys[t % n] + g; ys[t % n] <- g; param <- param - lr * d / m``
    with ``m`` the number of batches seen, saturating at ``batch_num``.
    The slot write is a ``dynamic_update_slice`` on a state scalar, so the
    rule jits. Memory note (as upstream documents): state is
    ``batch_num x`` the parameter size."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._batch_num = int(batch_num)
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        pv = raw(p)
        dt = jnp.float32 if self._use_master_weights else pv.dtype
        return {"d": jnp.zeros(pv.shape, dt),
                "ys": jnp.zeros((self._batch_num,) + tuple(pv.shape), dt),
                "step": jnp.zeros((), jnp.int32)}

    def _rule(self, p, g, st, lr):
        slot = st["step"] % self._batch_num
        y_old = jax.lax.dynamic_index_in_dim(st["ys"], slot, 0,
                                             keepdims=False)
        d = st["d"] - y_old + g
        ys = jax.lax.dynamic_update_index_in_dim(st["ys"], g, slot, 0)
        m = jnp.minimum(st["step"] + 1, self._batch_num).astype(p.dtype)
        new_p = p - lr * d / m
        return new_p.astype(p.dtype), {"d": d, "ys": ys,
                                       "step": st["step"] + 1}


class Rprop(Optimizer):
    """Resilient backpropagation (reference: ``python/paddle/optimizer/rprop.py``).
    Maintains a per-element step size that grows by ``etas[1]`` while the
    gradient keeps its sign and shrinks by ``etas[0]`` on a sign flip (the
    flipped gradient is dropped for that element); the update uses only the
    gradient's sign. Batch-size independent — full-batch contract as upstream
    documents."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_range = (float(learning_rate_range[0]), float(learning_rate_range[1]))
        self._etas = (float(etas[0]), float(etas[1]))
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        pv = raw(p)
        dt = jnp.float32 if self._use_master_weights else pv.dtype
        return {"prev_grad": jnp.zeros(pv.shape, dt),
                "lrs": jnp.full(pv.shape, float(self.get_lr()), dt)}

    def _rule(self, p, g, st, lr):
        sign = jnp.sign(st["prev_grad"] * g)
        lo, hi = self._lr_range
        neg, pos = self._etas
        factor = jnp.where(sign > 0, pos, jnp.where(sign < 0, neg, 1.0))
        lrs = jnp.clip(st["lrs"] * factor, lo, hi)
        g_eff = jnp.where(sign < 0, 0.0, g)  # drop sign-flipped elements
        new_p = p - lrs * jnp.sign(g_eff)
        return new_p.astype(p.dtype), {"prev_grad": g_eff, "lrs": lrs}


class NAdam(Optimizer):
    """Adam with Nesterov momentum and the Dozat momentum schedule
    (reference: ``python/paddle/optimizer/nadam.py``):
    ``mu_t = beta1 * (1 - 0.5 * 0.96^(t * decay))``."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._psi = float(momentum_decay)
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        pv = raw(p)
        dt = jnp.float32 if self._use_master_weights else pv.dtype
        return {"moment1": jnp.zeros(pv.shape, dt),
                "moment2": jnp.zeros(pv.shape, dt),
                "mu_product": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32),
                "step": jnp.zeros((), jnp.float32)}

    def _rule(self, p, g, st, lr):
        b1, b2, eps, psi = self._beta1, self._beta2, self._epsilon, self._psi
        t = st["step"] + 1.0
        mu_t = b1 * (1.0 - 0.5 * jnp.power(0.96, t * psi))
        mu_next = b1 * (1.0 - 0.5 * jnp.power(0.96, (t + 1.0) * psi))
        mu_prod = st["mu_product"] * mu_t
        b2p = st["beta2_pow"] * b2
        m1 = b1 * st["moment1"] + (1 - b1) * g
        m2 = b2 * st["moment2"] + (1 - b2) * jnp.square(g)
        mhat = mu_next * m1 / (1 - mu_prod * mu_next) + (1 - mu_t) * g / (1 - mu_prod)
        vhat = m2 / (1 - b2p)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + eps)
        return new_p.astype(p.dtype), {
            "moment1": m1, "moment2": m2, "mu_product": mu_prod,
            "beta2_pow": b2p, "step": t}


class RAdam(Optimizer):
    """Rectified Adam (reference: ``python/paddle/optimizer/radam.py``):
    rectifies the adaptive term's variance when enough steps have accrued
    (rho_t > 4), otherwise falls back to un-adapted momentum SGD. The
    branch is a ``jnp.where`` on state scalars, so the rule stays one
    compiled program."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._use_master_weights = bool(multi_precision)

    def _init_state(self, p):
        pv = raw(p)
        dt = jnp.float32 if self._use_master_weights else pv.dtype
        return {"moment1": jnp.zeros(pv.shape, dt),
                "moment2": jnp.zeros(pv.shape, dt),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32),
                "step": jnp.zeros((), jnp.float32)}

    def _rule(self, p, g, st, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        t = st["step"] + 1.0
        b1p = st["beta1_pow"] * b1
        b2p = st["beta2_pow"] * b2
        m1 = b1 * st["moment1"] + (1 - b1) * g
        m2 = b2 * st["moment2"] + (1 - b2) * jnp.square(g)
        mhat = m1 / (1 - b1p)
        rho_inf = 2.0 / (1.0 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * b2p / (1.0 - b2p)
        r_num = (rho_t - 4.0) * (rho_t - 2.0) * rho_inf
        r_den = (rho_inf - 4.0) * (rho_inf - 2.0) * rho_t
        rect = jnp.sqrt(jnp.maximum(r_num, 0.0) / jnp.maximum(r_den, eps))
        vhat = jnp.sqrt(m2 / (1 - b2p))
        adaptive = rect * mhat / (vhat + eps)
        new_p = p - lr * jnp.where(rho_t > 4.0, adaptive, mhat)
        return new_p.astype(p.dtype), {
            "moment1": m1, "moment2": m2, "beta1_pow": b1p,
            "beta2_pow": b2p, "step": t}


class LBFGS(Optimizer):
    """L-BFGS with backtracking (Armijo) line search.

    Reference: ``python/paddle/optimizer/lbfgs.py``. Unlike the first-order
    optimizers above, each `step(closure)` re-evaluates the loss: pass a
    closure that recomputes loss (and grads via backward), the standard
    paddle/torch LBFGS contract. The two-loop recursion runs on host over
    device arrays — dimensions involved are (history, params), not tokens,
    so there is nothing for the MXU here.
    """

    def __init__(self, learning_rate=1.0, max_iter=20, tolerance_grad=1e-7,
                 tolerance_change=1e-9, history_size=100, line_search_fn=None,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self.max_iter = max_iter
        self.tol_grad = tolerance_grad
        self.tol_change = tolerance_change
        self.history_size = history_size
        self.line_search_fn = line_search_fn
        self._s, self._y = [], []
        self._prev_flat = None
        self._prev_grad = None

    def _flat(self, vals):
        import jax.numpy as jnp

        return jnp.concatenate([jnp.reshape(v, (-1,)) for v in vals])

    def _unflat(self, flat):
        import jax.numpy as jnp

        out, off = [], 0
        for p in self._parameter_list:
            n = int(np.prod(p.shape)) if p.shape else 1
            out.append(jnp.reshape(flat[off:off + n], p.shape))
            off += n
        return out

    def _direction(self, g):
        import jax.numpy as jnp

        q = g
        alphas = []
        for s, y in reversed(list(zip(self._s, self._y))):
            rho = 1.0 / jnp.maximum(jnp.vdot(y, s), 1e-10)
            a = rho * jnp.vdot(s, q)
            alphas.append((a, rho, s, y))
            q = q - a * y
        if self._s:
            s, y = self._s[-1], self._y[-1]
            q = q * (jnp.vdot(s, y) / jnp.maximum(jnp.vdot(y, y), 1e-10))
        for a, rho, s, y in reversed(alphas):
            b = rho * jnp.vdot(y, q)
            q = q + s * (a - b)
        return -q

    def step(self, closure=None):
        import jax.numpy as jnp

        if closure is None:
            raise ValueError("LBFGS.step requires a closure returning the loss")
        loss = closure()
        flat = self._flat([raw(p) for p in self._parameter_list])
        grads = [
            raw(p.grad) if p.grad is not None else jnp.zeros(p.shape)
            for p in self._parameter_list
        ]
        g = self._flat(grads)
        if float(jnp.max(jnp.abs(g))) <= self.tol_grad:
            return loss
        if self._prev_flat is not None:
            s = flat - self._prev_flat
            y = g - self._prev_grad
            if float(jnp.vdot(s, y)) > 1e-10:
                self._s.append(s)
                self._y.append(y)
                if len(self._s) > self.history_size:
                    self._s.pop(0)
                    self._y.pop(0)
        d = self._direction(g)
        lr = self.get_lr()
        f0 = float(raw(loss))
        gtd = float(jnp.vdot(g, d))
        t = lr
        new_flat = flat
        for trial in range(10):  # backtracking Armijo
            new_flat = flat + t * d
            for p, v in zip(self._parameter_list, self._unflat(new_flat)):
                p._rebind(v)
            self.clear_grad()
            f1 = float(raw(closure()))
            if f1 <= f0 + 1e-4 * t * gtd:
                break
            if trial < 9:
                t *= 0.5
        # record the point the parameters are ACTUALLY at — a mismatched
        # _prev_flat would corrupt the next (s, y) curvature pair
        self._prev_flat = new_flat
        self._prev_grad = self._flat([
            raw(p.grad) if p.grad is not None else jnp.zeros(p.shape)
            for p in self._parameter_list
        ])
        return loss
