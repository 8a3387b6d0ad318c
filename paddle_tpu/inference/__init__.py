"""paddle.inference parity: Config / create_predictor serving path.

Reference: ``paddle/fluid/inference/`` AnalysisPredictor + C API
(``paddle_inference_api.h``) — load a saved program + params, run IR
optimization passes, execute with zero-copy input/output handles
(SURVEY.md §2.1 "Inference engine", §2.4 item 14). TPU-native design: the
saved artifact is already the optimized program (StableHLO from jit.save);
"analysis passes" are XLA's compilation pipeline, so the predictor is a thin
executable cache with Paddle's handle-based API on top. Works on TPU or CPU
PJRT backends; batch-size changes just select a new cached executable (or
reuse one, if the model was exported batch-polymorphic).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..jit.save_load import TranslatedLayer, load as _jit_load


class Config:
    """paddle.inference.Config parity (GPU/TensorRT knobs are accepted and
    recorded but are no-ops: XLA owns optimization on TPU)."""

    def __init__(self, prog_file: Optional[str] = None, params_file: Optional[str] = None):
        # paddle accepts Config(model_dir) or Config(prog_file, params_file);
        # we accept a path PREFIX (as written by jit.save) in either slot.
        if prog_file is not None and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[: -len(".pdmodel")]
        self._prefix = prog_file
        self._memory_optim = True
        self._ir_optim = True
        self._device = None  # None → default jax backend
        self._num_threads = 1
        self._tensorrt = False

    # --- model location ---
    def set_model(self, prog_file, params_file=None):
        if prog_file.endswith(".pdmodel"):
            prog_file = prog_file[: -len(".pdmodel")]
        self._prefix = prog_file

    def model_dir(self):
        return self._prefix

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def params_file(self):
        return (self._prefix or "") + ".pdiparams"

    # --- device selection ---
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # "GPU" slot maps to the accelerator backend (TPU here)
        self._device = "tpu"

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self):
        return self._device == "tpu"

    def set_cpu_math_library_num_threads(self, n):
        self._num_threads = n

    # --- optimization knobs (XLA always optimizes; recorded for parity) ---
    def enable_memory_optim(self, x=True):
        self._memory_optim = x

    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def switch_use_feed_fetch_ops(self, x=False):
        pass

    def switch_specify_input_names(self, x=True):
        pass

    def enable_tensorrt_engine(self, *args, **kwargs):
        self._tensorrt = True  # no-op: XLA fusion replaces TRT subgraphs

    def tensorrt_engine_enabled(self):
        return self._tensorrt

    # --- serving decode engine (inference/engine.py, docs/SERVING.md) ---
    def enable_decode_engine(self, num_slots: int = 8, max_length: int = 512,
                             kv_dtype: str = "f32", **kw):
        """Record decode-engine settings; `enable_decode_engine(model,
        config)` (module level) builds the engine from them and attaches
        it, after which text.generation.generate()/generate_padded() route
        through the KV-cached continuous-batching loop."""
        self._engine_kwargs = dict(
            num_slots=num_slots, max_length=max_length, kv_dtype=kv_dtype,
            **kw)

    def decode_engine_enabled(self) -> bool:
        return getattr(self, "_engine_kwargs", None) is not None

    def decode_engine_config(self):
        """EngineConfig built from enable_decode_engine() settings."""
        from .engine import EngineConfig

        return EngineConfig(**getattr(self, "_engine_kwargs", {}) or {})

    def summary(self):
        return (
            f"Config(prefix={self._prefix}, device={self._device or 'default'}, "
            f"memory_optim={self._memory_optim}, ir_optim={self._ir_optim})"
        )


class _IOHandle:
    """Zero-copy-style input/output handle (copy_from_cpu/copy_to_cpu parity).

    Reference: ``ZeroCopyTensor`` in paddle_inference_api.h — named handles
    that stage host buffers in and device buffers out.
    """

    def __init__(self, name):
        self.name = name
        self._value = None
        self._shape = None
        #: bumped on every copy_from_cpu — Predictor.run only device_puts
        #: handles whose version moved since the last call
        self._version = 0

    def reshape(self, shape):
        self._shape = tuple(shape)

    def copy_from_cpu(self, arr: np.ndarray):
        arr = np.asarray(arr)
        if self._shape is not None and tuple(arr.shape) != self._shape:
            arr = arr.reshape(self._shape)
        self._value = arr
        self._version += 1

    def copy_to_cpu(self) -> np.ndarray:
        # outputs stay device-resident until someone actually asks for the
        # host copy (np.asarray on a jax array is the D2H transfer)
        return np.asarray(self._value)

    def shape(self):
        v = self._value
        return list(v.shape) if v is not None else list(self._shape or [])


class Predictor:
    """paddle.inference predictor over a jit.save'd StableHLO artifact."""

    def __init__(self, config: Config, _layer: Optional[TranslatedLayer] = None):
        if _layer is None and not config._prefix:
            raise ValueError("Config has no model path; use Config(prefix) or set_model")
        self._config = config
        self._layer: TranslatedLayer = _layer if _layer is not None else _jit_load(config._prefix)
        self._input_names = self._layer.input_names
        self._inputs: Dict[str, _IOHandle] = {
            n: _IOHandle(n) for n in self._input_names
        }
        self._outputs: Dict[str, _IOHandle] = {}
        self._output_names: List[str] = []
        #: name -> (handle version, device-resident array). Params already
        #: live on device inside the TranslatedLayer; this closes the other
        #: half of the loop so repeated run() calls with unchanged inputs
        #: do zero H2D transfers.
        self._dev_inputs: Dict[str, tuple] = {}

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name) -> _IOHandle:
        return self._inputs[name]

    def _device_input(self, name):
        """The handle's value as a device array, re-transferred only when
        copy_from_cpu bumped its version since the previous run()."""
        import jax

        h = self._inputs[name]
        ver, arr = self._dev_inputs.get(name, (None, None))
        if ver != h._version:
            v = h._value
            arr = v if isinstance(v, jax.Array) else jax.device_put(v)
            self._dev_inputs[name] = (h._version, arr)
        return arr

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """Execute. Either stage inputs via handles then run(), or pass a list
        of arrays positionally (newer paddle.inference allows both)."""
        if inputs is not None:
            if len(inputs) != len(self._input_names):
                raise ValueError(
                    f"run() got {len(inputs)} inputs, model expects "
                    f"{len(self._input_names)}: {self._input_names}"
                )
            for n, a in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(a)
        missing = [n for n in self._input_names if self._inputs[n]._value is None]
        if missing:
            raise RuntimeError(f"inputs not set: {missing}")
        out = self._layer.forward(
            *[self._device_input(n) for n in self._input_names])
        import jax

        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: hasattr(x, "_value")
        )
        self._output_names = [f"fetch_{i}" for i in range(len(leaves))]
        self._outputs = {}
        for n, leaf in zip(self._output_names, leaves):
            h = _IOHandle(n)
            # keep the DEVICE array; copy_to_cpu does the host transfer
            h._value = leaf._value if hasattr(leaf, "_value") else leaf
            self._outputs[n] = h
        if inputs is not None:
            return [self._outputs[n].copy_to_cpu() for n in self._output_names]
        return True

    def get_output_names(self) -> List[str]:
        return list(self._output_names)

    def get_output_handle(self, name) -> _IOHandle:
        return self._outputs[name]


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def enable_decode_engine(model, config: Optional[Config] = None, **kw):
    """Attach a KV-cached continuous-batching decode engine to a live
    causal LM (a model whose ``decode_adapter()`` gives the engine its
    ``embed``, its own block as ``layer``, ``head`` and the KV pool's
    geometry: GPTForCausalLM, LlamaForCausalLM; docs/SERVING.md "Serving a
    new model"). After this, ``text.generation.generate`` /
    ``generate_padded`` route through the engine automatically; the
    engine is also returned for direct ``submit()``/``step()``/``run()``
    driving. Settings come from ``config.enable_decode_engine(...)`` when
    a Config is given, else from keyword args (EngineConfig fields).

    See docs/SERVING.md."""
    from .engine import DecodeEngine

    if config is not None and config.decode_engine_enabled():
        engine = DecodeEngine(model, config.decode_engine_config())
    else:
        engine = DecodeEngine(model, **kw)
    model._decode_engine = engine
    return engine


def disable_decode_engine(model):
    """Detach the engine; generation falls back to the legacy loops."""
    if getattr(model, "_decode_engine", None) is not None:
        model._decode_engine = None


class PredictorPool:
    """paddle.inference.PredictorPool parity: N predictors over ONE loaded
    artifact — the deserialized module and its jit-compiled executable are
    shared; each pool member only has its own input/output handle staging."""

    def __init__(self, config: Config, size: int = 1):
        shared = _jit_load(config._prefix)
        self._preds = [Predictor(config, _layer=shared) for _ in range(size)]

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx]


def get_version():
    import jax

    return f"paddle_tpu-inference (jax {jax.__version__})"
