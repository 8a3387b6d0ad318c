"""Device management (paddle.device parity).

Reference: ``python/paddle/device/`` (SURVEY.md §2.2). On TPU, placement is
owned by PJRT/jax; set_device selects the default jax device. CUDA-named
entry points are kept for script compatibility and map to the TPU device
(per BASELINE.json's north star: scripts run unchanged with set_device('tpu')).
"""
from __future__ import annotations

import jax

from ..framework.core import CPUPlace, Place, TPUPlace

_current = None


def set_device(device: str):
    """paddle.set_device parity: 'tpu', 'tpu:0', 'cpu', 'gpu:0'→tpu.

    Asking for an accelerator that is not there, or an index past the last
    device, raises: a script must not believe it trains on a chip while it
    runs on the host."""
    global _current
    kind, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if kind in ("gpu", "cuda", "xpu", "npu"):
        kind = "tpu"
    if kind == "cpu":
        devs = jax.devices("cpu")
    else:
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                f"set_device({device!r}): no accelerator is attached "
                f"(jax.devices() reports platform "
                f"{jax.devices()[0].platform!r}); use set_device('cpu') to "
                "run on the host")
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range, "
            f"{len(devs)} {kind} device(s) present")
    dev = devs[idx]
    jax.config.update("jax_default_device", dev)
    _current = f"{kind}:{idx}"
    return Place(kind, idx)


def get_device() -> str:
    if _current is not None:
        return _current
    d = jax.devices()[0]
    return ("cpu" if d.platform == "cpu" else "tpu") + f":{d.id}"


def get_all_custom_device_type():
    return ["tpu"]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_distribute() -> bool:
    return True


def device_count() -> int:
    return len([d for d in jax.devices() if d.platform != "cpu"]) or len(jax.devices())


class _Event:
    """Stream event parity shim. XLA's static schedule replaces explicit
    stream/event management (reference: paddle/fluid/platform streams)."""

    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._t = None

    def record(self, stream=None):
        import time

        jax.effects_barrier()
        self._t = time.perf_counter()

    def synchronize(self):
        pass

    def query(self):
        return True

    def elapsed_time(self, end):
        return (end._t - self._t) * 1000.0 if self._t and end._t else 0.0


class _Stream:
    def __init__(self, device=None, priority=None):
        pass

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        e = event or _Event()
        e.record()
        return e


def synchronize(device=None):
    """Block until all queued device work completes."""
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        except Exception:
            break
    (jax.device_put(0) + 0).block_until_ready()


class cuda:
    """paddle.device.cuda compatibility namespace (maps to the TPU device)."""

    Event = _Event
    Stream = _Stream

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def empty_cache():
        pass  # PJRT owns the allocator

    @staticmethod
    def memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return int(stats.get("bytes_in_use", 0)) if stats else 0

    @staticmethod
    def max_memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return int(stats.get("peak_bytes_in_use", 0)) if stats else 0

    @staticmethod
    def memory_reserved(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return int(stats.get("bytes_limit", 0)) if stats else 0

    @staticmethod
    def max_memory_reserved(device=None):
        return cuda.memory_reserved(device)

    @staticmethod
    def get_device_properties(device=None):
        d = jax.devices()[0]

        class _Props:
            name = getattr(d, "device_kind", "tpu")
            major, minor = 0, 0
            total_memory = cuda.memory_reserved()
            multi_processor_count = 1

        return _Props()

    @staticmethod
    def get_device_name(device=None):
        return getattr(jax.devices()[0], "device_kind", "tpu")

    @staticmethod
    def get_device_capability(device=None):
        return (0, 0)


class tpu:
    """First-class TPU namespace: device stats straight from PJRT."""

    synchronize = staticmethod(synchronize)
    Event = _Event
    Stream = _Stream

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def memory_stats(device=None):
        d = jax.devices()[0]
        return getattr(d, "memory_stats", lambda: {})() or {}


def host_memory_stats() -> dict:
    """Host staging-arena stats from the native runtime (csrc allocator);
    the host-side analogue of paddle.device.cuda.memory_stats."""
    from .. import runtime

    return runtime.host_memory_stats()


def get_all_device_type():
    """Device types this build can drive (paddle.device.get_all_device_type)."""
    import jax

    out = ["cpu"]
    try:
        if jax.default_backend() == "tpu":
            out.append("tpu")
    except Exception:
        pass
    return out


def get_available_device():
    """Device strings currently visible (paddle.device.get_available_device)."""
    import jax

    try:
        return [f"{d.platform}:{d.id}" for d in jax.devices()]
    except Exception:
        return ["cpu:0"]


# paddle.device.Stream / Event parity (reference: python/paddle/device/
# __init__.py). On TPU there are no user-managed streams — XLA owns the
# schedule — so these are the same API-complete no-op classes the cuda/tpu
# sub-namespaces expose.
Stream = _Stream
Event = _Event


def stream_guard(stream):
    import contextlib

    return contextlib.nullcontext(stream)


def current_stream(device=None):
    return _Stream(device)
