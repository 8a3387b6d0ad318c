"""Layer: the module base class (paddle.nn.Layer parity).

Reference: ``python/paddle/nn/layer/layers.py`` (SURVEY.md §2.2 "nn").
TPU-native design: a Layer is a *pytree of parameters* — parameters/buffers
are plain Tensors; ``paddle_tpu.jit`` lifts them into functional pytrees to
compile whole train steps (§7 step 3), so the same Layer serves the eager and
the captured execution mode.
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..framework import dtypes as _dtypes
from ..framework.core import Tensor
from ..framework.op import raw
from . import initializer as I


class Parameter(Tensor):
    """Trainable tensor (paddle Parameter parity): stop_gradient=False."""

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.split_axis = None  # tensor-parallel shard axis, set by mpu layers

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


class ParamAttr:
    """paddle.ParamAttr parity."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        do_model_average=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = _dtypes.convert_dtype(dtype)
        self._parameters: "collections.OrderedDict[str, Parameter]" = collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = collections.OrderedDict()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name = name_scope or self.__class__.__name__.lower()

    # ---------------------------------------------------------------- attrs --
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            buffers.pop(name, None) if buffers else None
            layers.pop(name, None) if layers else None
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                del params[name]
            if buffers is not None and name in buffers:
                if value is None:
                    del buffers[name]
                elif isinstance(value, Tensor):
                    buffers[name] = value
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._sub_layers)

    # ----------------------------------------------------------- creation ----
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        dtype = _dtypes.convert_dtype(dtype) or self._dtype
        attr = attr if isinstance(attr, ParamAttr) else ParamAttr(name=attr if isinstance(attr, str) else None)
        init = attr.initializer or default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        value = init(tuple(int(s) for s in shape), dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = getattr(attr, "need_clip", True)
        return p

    def add_parameter(self, name, parameter):
        setattr(self, name, parameter)
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        object.__setattr__(self, name, sublayer)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        elif tensor is not None:
            tensor.persistable = True  # buffers are state, not activations
        object.__setattr__(self, name, tensor)
        return tensor

    # -------------------------------------------------------------- traversal -
    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else prefix + "." + name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = lname if not prefix else prefix + "." + lname
                for n, p in layer.named_parameters(prefix=sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (prefix + name if not prefix else prefix + "." + name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = lname if not prefix else prefix + "." + lname
                yield from layer.named_buffers(prefix=sub_prefix)

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for layer in self._sub_layers.values():
            if layer is not None:
                out.extend(layer.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = name if not prefix else prefix + "." + name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ------------------------------------------------------------- modes -----
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ------------------------------------------------------------ state ------
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        out = destination if destination is not None else collections.OrderedDict()
        for n, p in self.named_parameters(prefix=structured_name_prefix.rstrip("."), include_sublayers=include_sublayers):
            out[n] = p
        # a buffer is non-persistable in the layer that registered it
        transient = {id(l._buffers[n]) for l in self.sublayers(include_self=True)
                     for n in l._non_persistable_buffer_names}
        for n, b in self.named_buffers(prefix=structured_name_prefix.rstrip("."), include_sublayers=include_sublayers):
            if id(b) not in transient:
                out[n] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, t in own.items():
            if k in state_dict:
                v = state_dict[k]
                vv = raw(v) if isinstance(v, Tensor) else jnp.asarray(v)
                if tuple(vv.shape) != tuple(t._value.shape):
                    raise ValueError(
                        f"shape mismatch for {k}: checkpoint {tuple(vv.shape)} vs model {tuple(t._value.shape)}"
                    )
                t._rebind(jnp.asarray(vv, t._value.dtype))
            else:
                missing.append(k)
        for k in state_dict:
            if k not in own:
                unexpected.append(k)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ------------------------------------------------------------ dtype/dev --
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = _dtypes.convert_dtype(dtype)
            for t in list(self.parameters()) + list(self.buffers()):
                if _dtypes.is_floating_point(t.dtype):
                    t._rebind(t._value.astype(dt))
            for l in self.sublayers(include_self=True):
                l._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    # ------------------------------------------------------------- hooks -----
    def register_forward_pre_hook(self, hook):
        hid = len(self._forward_pre_hooks)
        self._forward_pre_hooks[hid] = hook

        return _HookRemover(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = len(self._forward_post_hooks)
        self._forward_post_hooks[hid] = hook
        return _HookRemover(self._forward_post_hooks, hid)

    # -------------------------------------------------------------- call -----
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def _run_with_hooks(self, forward, inputs, kwargs):
        """The hook protocol around an arbitrary forward callable — the ONE
        definition of pre/post-hook semantics (dy2static's convert_call
        routes converted forwards through here too)."""
        for hook in self._forward_pre_hooks.values():
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        out = forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def __call__(self, *inputs, **kwargs):
        return self._run_with_hooks(self.forward, inputs, kwargs)

    def full_name(self):
        return self._name

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = self.__class__.__name__ + "(" + extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class _HookRemover:
    def __init__(self, d, k):
        self._d, self._k = d, k

    def remove(self):
        self._d.pop(self._k, None)


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, tuple):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __setitem__(self, idx, layer):
        self.add_sublayer(str(idx), layer)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


class LayerDict(Layer):
    """paddle.nn.LayerDict parity: an ordered dict of sublayers."""

    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        layer = self._sub_layers[key]
        del self._sub_layers[key]
        return layer

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def update(self, sublayers):
        items = sublayers.items() if hasattr(sublayers, "items") else sublayers
        for k, v in items:
            self.add_sublayer(k, v)
