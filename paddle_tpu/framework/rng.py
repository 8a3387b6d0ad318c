"""Random number state management.

Reference capability: Paddle's global/generator seeds (``paddle.seed``) and
Fleet's ``RNGStatesTracker`` for tensor-parallel dropout
(``python/paddle/distributed/fleet/layers/mpu/random.py`` — SURVEY.md §2.3 "TP").

TPU-native design: JAX's splittable counter-based PRNG. A global ``Generator``
holds a base key + a monotonically increasing offset; each random op folds the
offset in. Inside a captured/compiled program (``paddle_tpu.jit``), the step
machinery seeds a *trace-scoped* key so every compiled call sees fresh
randomness via an explicit key argument (stateful RNG inside an XLA program
would bake constants into the executable). Named-axis generators mirror the
reference's RNGStatesTracker: the "local" generator additionally folds in the
process/mesh coordinate so tensor-parallel dropout masks are decorrelated.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

import jax


def _configure_default_prng():
    """TPU-idiomatic PRNG selection (measured on v5e, round 3).

    JAX's default threefry2x32 PRNG is computed in plain vector ops and is
    expensive on TPU: for the ERNIE-base headline bench, dropout-mask
    generation alone cost ~36ms of a 234ms train step (measured in-session
    on a v5e chip, 2026-07-31; the committed bench artifact refreshes on
    the next successful real-chip run). The ``rbg`` impl rides the
    hardware RNG instruction and took the same step to 198ms
    (+18% throughput) with the same statistical contract Paddle offers
    (deterministic per seed; streams are not bit-stable across XLA
    versions, which the reference never guaranteed across cuDNN versions
    either).

    Selection, most-specific wins:

    1. ``PADDLE_TPU_PRNG_IMPL`` env: applied verbatim (``threefry`` is
       actively set, so the opt-out wins even if something else flipped
       the jax default earlier).
    2. Deference: if the application configured the PRNG itself — jax's
       native ``JAX_DEFAULT_PRNG_IMPL`` env, or ``jax.config`` no longer
       at its threefry default when paddle imports — leave it alone.
    3. Auto: rbg, but only when a TPU is *plausibly present* (libtpu
       importable, a TPU env marker, or JAX_PLATFORMS's primary
       platform says tpu) AND the primary platform is not cpu.
       The 8-virtual-device CPU test mesh pins ``JAX_PLATFORMS=cpu`` and
       a CPU-only dev box has no TPU markers — both keep threefry, so
       recorded CPU trajectories stay stable. ``JAX_PLATFORMS="tpu,cpu"``
       (cpu as fallback only) still selects rbg.

    No jax backend is initialized here — the decision reads only env vars
    and the config default, so importing paddle stays cheap.

    Known limit: an in-process ``jax.config.update("jax_default_prng_impl",
    "threefry2x32")`` before importing paddle is indistinguishable from the
    untouched default (jax does not expose "was it set"), so it does not
    defer; pin ``PADDLE_TPU_PRNG_IMPL=threefry`` (or jax's own
    ``JAX_DEFAULT_PRNG_IMPL``) for a guaranteed opt-out.
    """
    explicit = os.environ.get("PADDLE_TPU_PRNG_IMPL", "").strip().lower()
    if explicit in ("threefry", "default"):
        explicit = "threefry2x32"
    impl = explicit
    if not impl:
        if os.environ.get("JAX_DEFAULT_PRNG_IMPL"):
            return  # app configured jax's own env knob: defer
        try:
            if jax.config.jax_default_prng_impl != "threefry2x32":
                return  # app already changed the default in-process: defer
        except AttributeError:
            return
        primary = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
        if primary == "cpu" or not _tpu_plausible(primary):
            return
        impl = "rbg"
    try:
        jax.config.update("jax_default_prng_impl", impl)
    except Exception as e:
        if explicit:
            import warnings

            warnings.warn(
                f"PADDLE_TPU_PRNG_IMPL={explicit!r} was rejected by JAX "
                f"({e}); keeping the default PRNG", RuntimeWarning)
        # implicit auto-selection: very old jax / unknown impl — keep default


def _tpu_plausible(primary_platform: str) -> bool:
    """Cheap TPU-presence heuristics that never initialize a backend."""
    if primary_platform == "tpu":
        return True
    for var in ("TPU_NAME", "TPU_WORKER_ID", "TPU_SKIP_MDS_QUERY",
                "CLOUD_TPU_TASK_ID"):
        if os.environ.get(var):
            return True
    try:
        import importlib.util

        if importlib.util.find_spec("libtpu") is None:
            return False
    except (ImportError, ValueError):
        return False
    # an installed libtpu wheel alone is not presence (TPU docker image on
    # a CPU VM): require a local accelerator device node to go with it
    import glob

    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*"))


_configure_default_prng()


class Generator:
    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = jax.random.key(seed)
        self._offset = 0
        self._lock = threading.Lock()

    def manual_seed(self, seed: int):
        self._seed = seed
        self._key = jax.random.key(seed)
        self._offset = 0
        return self

    def get_state(self):
        return (self._seed, self._offset)

    def set_state(self, state):
        self._seed, self._offset = state
        self._key = jax.random.key(self._seed)

    def next_key(self):
        with self._lock:
            off = self._offset
            self._offset += 1
        return jax.random.fold_in(self._key, off)

    @property
    def initial_seed(self):
        return self._seed


# LAZY: creating a Generator touches the XLA backend (jax.random.key), and
# backend init must not happen at import time — multi-host workers need
# jax.distributed.initialize() to run first (distributed/env.py).
_default_generator = None
_default_lock = threading.Lock()


def _default() -> Generator:
    global _default_generator
    if _default_generator is None:
        with _default_lock:
            if _default_generator is None:
                _default_generator = Generator(0)
    return _default_generator


# Trace-scoped key: when paddle_tpu.jit traces a function, it installs a key
# here (a tracer); random ops consume splits of it instead of the global state.
_trace_state = threading.local()


def default_generator() -> Generator:
    return _default()


def seed(value: int) -> Generator:
    """Set the global random seed (paddle.seed parity)."""
    return _default().manual_seed(int(value))


def get_rng_state():
    return _default().get_state()


def set_rng_state(state):
    _default().set_state(state)


@contextlib.contextmanager
def trace_key_scope(key):
    """Install a trace-scoped RNG key (used by the jit machinery)."""
    prev = getattr(_trace_state, "key", None)
    prev_n = getattr(_trace_state, "n", 0)
    _trace_state.key = key
    _trace_state.n = 0
    try:
        yield
    finally:
        _trace_state.key = prev
        _trace_state.n = prev_n


def in_trace_scope() -> bool:
    return getattr(_trace_state, "key", None) is not None


def next_key(generator: Optional[Generator] = None):
    """Produce a fresh PRNG key for one random op."""
    tk = getattr(_trace_state, "key", None)
    if tk is not None:
        n = _trace_state.n
        _trace_state.n = n + 1
        return jax.random.fold_in(tk, n)
    return (generator or _default()).next_key()
