"""paddle.profiler parity — tracing & performance summaries.

Reference capability (SURVEY.md §5 "Tracing/profiling"):
`paddle.profiler.Profiler` with host tracer (scoped `RecordEvent`) + CUPTI
device tracer, Chrome-trace export, scheduler (`make_scheduler`), and
`summary()` tables.

TPU-native design: the device tracer is the XLA/PJRT profiler
(`jax.profiler.start_trace` → XPlane, viewable in TensorBoard/Perfetto/xprof);
host annotations are `jax.profiler.TraceAnnotation`s, which the runtime
stitches into the same timeline. The host-side op timer used for `summary()`
is a lightweight wall-clock aggregator (the per-op C++ timer of the
reference is meaningless under whole-program XLA execution — the compiled
step is the unit)."""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import time
from enum import Enum
from typing import Callable, Iterable, List, Optional, Union

import jax

from .. import runtime as _runtime
from ..observability import tracing as _tracing


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int = 0, ready: int = 0, record: int = 1, repeat: int = 0, skip_first: int = 0):
    """paddle.profiler.make_scheduler parity: step-state machine."""
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


class SortedKeys(Enum):
    """paddle.profiler.SortedKeys parity (host-timer subset)."""

    CPUTotal = 0
    CPUAvg = 1
    Calls = 2
    Name = 3


#: string aliases accepted anywhere a SortedKeys is (paddle passes enums;
#: ad-hoc scripts pass strings)
_SORT_ALIASES = {
    "total": SortedKeys.CPUTotal,
    "avg": SortedKeys.CPUAvg,
    "count": SortedKeys.Calls,
    "calls": SortedKeys.Calls,
    "name": SortedKeys.Name,
}


def _resolve_sort(sorted_by) -> SortedKeys:
    if sorted_by is None:
        return SortedKeys.CPUTotal
    if isinstance(sorted_by, SortedKeys):
        return sorted_by
    key = _SORT_ALIASES.get(str(sorted_by).lower())
    if key is None:
        raise ValueError(
            f"summary(sorted_by={sorted_by!r}): expected a SortedKeys or one "
            f"of {sorted(_SORT_ALIASES)}")
    return key


_TIME_UNITS = {"s": 1.0, "ms": 1e3, "us": 1e6}


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory: keep the XPlane/trace files under dir_name;
    `worker_name` prefixes the host-trace file (``{worker}_host_trace.json``)
    so multi-worker runs exporting into a shared dir don't clobber each
    other. The config is also applied at Profiler construction (via the
    attribute below) — the host trace is written during ``_stop_trace``,
    BEFORE the on_trace_ready callback fires."""

    def handler(prof):
        prof._export_dir = dir_name
        if worker_name:
            prof._worker_name = worker_name

    handler._export_config = (dir_name, worker_name)
    return handler


class RecordEvent:
    """Scoped host annotation (reference: platform::RecordEvent).

    Shows up in the XLA trace timeline and in Profiler.summary(). It is a
    span of ``observability.tracing``: a user's annotation and the
    engine's own land in one buffer (``tracing.recorded()``) and on one
    timeline. ``begin``/``end`` may be far apart, out of order or on two
    threads, so the event is an explicit handle and never a parent on the
    nesting stack: spans that finish under it stay roots of their own and
    reach the JSONL sink as they finish.
    """

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._span = None
        self._t0 = None

    def begin(self):
        self._span = _tracing.start_span(self.name)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        # FFI timestamp only when the native tracer is actually recording —
        # two ctypes calls + a mutex per event is real overhead in an
        # untraced training loop.
        self._t0_ns = _runtime.now_ns() if _runtime.trace_enabled() else None
        _host_events[self.name][0] += 1

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            _tracing.end_span(self._span)
            _host_events[self.name][1] += time.perf_counter() - self._t0
            if self._t0_ns is not None and _runtime.trace_enabled():
                import threading as _threading

                _runtime.trace_record(
                    self.name,
                    self._t0_ns,
                    _runtime.now_ns() - self._t0_ns,
                    tid=_threading.get_ident() % (1 << 31),
                )
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


_host_events = collections.defaultdict(lambda: [0, 0.0])  # name -> [count, secs]


def reset_host_events() -> None:
    """Clear the process-global RecordEvent aggregator. The aggregator is
    deliberately process-wide (mirrors the reference's global host tracer),
    so back-to-back Profiler runs — and test cases — must reset it between
    runs or the second summary() reports the first run's counts too."""
    _host_events.clear()


class Profiler:
    def __init__(
        self,
        *,
        targets: Optional[Iterable] = None,
        scheduler=None,
        on_trace_ready: Optional[Callable] = None,
        timer_only: bool = False,
        record_shapes: bool = False,
        profile_memory: bool = False,
        with_flops: bool = False,
    ):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo, repeat=1)
        else:
            self._scheduler = None
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
        self._worker_name = None
        # export_chrome_tracing carries its config on the handler: apply it
        # NOW, not at stop() — the host trace file is written in
        # _stop_trace, before the on_trace_ready callback runs
        cfg = getattr(on_trace_ready, "_export_config", None)
        if cfg is not None:
            self._export_dir = cfg[0]
            self._worker_name = cfg[1]
        self._step = 0
        self._tracing = False
        self._step_times = []
        self._last_step_t = None
        #: every scheduler state as applied, in order — step 0's state first
        #: (tests pin the sequence against make_scheduler's)
        self._state_history: List[ProfilerState] = []

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        self._last_step_t = time.perf_counter()
        if self._timer_only:
            return self
        if self._scheduler is None:
            self._start_trace()
        else:
            # the scheduler's step-0 state applies to the FIRST step, which
            # runs between start() and the first step() call — consulting
            # only inside step() (post-increment) skipped it entirely and
            # shifted skip_first by one
            self._apply_state(self._scheduler(self._step))
        return self

    def stop(self):
        if self._tracing:
            self._stop_trace()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1
        if self._scheduler is not None and not self._timer_only:
            self._apply_state(self._scheduler(self._step))

    def _apply_state(self, state: ProfilerState):
        self._state_history.append(state)
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            if not self._tracing:
                self._start_trace()
        elif self._tracing:
            self._stop_trace()

    def _start_trace(self):
        _runtime.trace_start()
        try:
            jax.profiler.start_trace(self._export_dir)
            self._tracing = True
        except Exception:
            # keep the native host tracer symmetric with the failed device
            # trace — otherwise it stays on (and accumulating) for the rest
            # of the process.
            _runtime.trace_stop()
            self._tracing = False

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._tracing = False
        _runtime.trace_stop()
        # Export host RecordEvents as a chrome trace alongside the XPlane
        # files (reference: chrometracing_logger.cc output).
        events = _runtime.trace_export()
        if events:
            fname = (f"{self._worker_name}_host_trace.json"
                     if self._worker_name else "host_trace.json")
            os.makedirs(self._export_dir, exist_ok=True)
            with open(os.path.join(self._export_dir, fname), "w") as f:
                json.dump({"traceEvents": events}, f)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting ----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms"):
        """Host-timer report. `sorted_by` orders the event table
        (SortedKeys or "total"/"avg"/"count"/"name"; default total time,
        descending); `time_unit` is one of "s"/"ms"/"us"."""
        unit = str(time_unit).lower()
        if unit not in _TIME_UNITS:
            raise ValueError(
                f"summary(time_unit={time_unit!r}): expected one of "
                f"{sorted(_TIME_UNITS)}")
        scale = _TIME_UNITS[unit]
        key = _resolve_sort(sorted_by)
        lines = ["-- paddle_tpu profiler summary " + "-" * 30]
        if self._step_times:
            ts = self._step_times
            lines.append(
                f"steps: {len(ts)}  avg: {sum(ts) / len(ts) * scale:.2f} {unit}  "
                f"min: {min(ts) * scale:.2f} {unit}  max: {max(ts) * scale:.2f} {unit}"
            )
        if _host_events:
            items = list(_host_events.items())
            if key is SortedKeys.Name:
                items.sort(key=lambda kv: kv[0])
            elif key is SortedKeys.Calls:
                items.sort(key=lambda kv: (-kv[1][0], kv[0]))
            elif key is SortedKeys.CPUAvg:
                items.sort(key=lambda kv: (-kv[1][1] / max(kv[1][0], 1), kv[0]))
            else:
                items.sort(key=lambda kv: (-kv[1][1], kv[0]))
            lines.append(f"{'event':40s} {'count':>8s} "
                         f"{'total ' + unit:>12s} {'avg ' + unit:>12s}")
            for name, (cnt, secs) in items:
                lines.append(
                    f"{name:40s} {cnt:8d} {secs * scale:12.2f} "
                    f"{secs * scale / max(cnt, 1):12.2f}")
        if self._tracing or os.path.isdir(self._export_dir):
            lines.append(f"device trace (XPlane): {self._export_dir}")
        out = "\n".join(lines)
        print(out)
        return out


_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*\bop_name="([^"]*)"', re.M)
_JIT_WRAPPER = re.compile(r"^p?jit\(.*\)$")

#: ``with scope("mlp"):`` names a part of a jitted step (the engine's
#: programs, a model's serving block); ``op_scopes`` reads it back
scope = jax.named_scope


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: innermost scope} over a compiled program's text
    (``compiled.as_text()``: ``TrainStep._compiled_for(...)``,
    ``DecodeEngine.program_text(...)``).

    A device trace names an operation by its HLO line and carries no
    ``op_name``, so the ``jax.named_scope`` a fusion came from is found
    here: ``%multiply_add_fusion.3 = ... metadata={op_name=
    "jit(step)/optimizer_update/add"}`` gives ``{"multiply_add_fusion.3":
    "optimizer_update"}``. The scope is the last part of the path before
    the primitive, ``jit(...)`` wrappers left out; jax's own transforms
    stay (``transpose(jvp(forward_loss))`` is the backward pass). An
    instruction outside every scope is left out."""
    out = {}
    for name, op_name in _HLO_OP_NAME.findall(hlo_text):
        scopes = [p for p in op_name.split("/")[:-1]
                  if not _JIT_WRAPPER.match(p)]
        if scopes:
            out[name] = scopes[-1]
    return out


@contextlib.contextmanager
def profile(dir_name: str = "/tmp/paddle_tpu_profile"):
    """Simple context: trace everything inside to `dir_name`."""
    jax.profiler.start_trace(dir_name)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_profiler(dir_name: str = "/tmp/paddle_tpu_profile"):
    jax.profiler.start_trace(dir_name)


def stop_profiler(*a, **k):
    jax.profiler.stop_trace()


class ProfilerResult:
    """Programmatic view of an exported host chrome trace
    (``host_trace.json`` / ``{worker}_host_trace.json``).

    The device-side XPlane files stay in TensorBoard/xprof territory; this
    covers the host RecordEvent timeline — enough for tests and scripted
    assertions ("did my_region run 5 times and stay under 2ms?")."""

    def __init__(self, path: str, events: List[dict]):
        self.path = path
        #: raw chrome-trace event dicts (name/ph/ts/dur in microseconds)
        self.events = events

    def __len__(self) -> int:
        return len(self.events)

    def names(self) -> List[str]:
        return sorted({e.get("name") for e in self.events if e.get("name")})

    def _named(self, name: str) -> List[dict]:
        return [e for e in self.events if e.get("name") == name]

    def count(self, name: str) -> int:
        return len(self._named(name))

    def total_duration(self, name: str) -> float:
        """Summed duration of complete ("ph": "X") events, microseconds."""
        return float(sum(e.get("dur", 0) for e in self._named(name)
                         if e.get("ph", "X") == "X"))

    def time_range(self) -> Optional[tuple]:
        """(first_ts, last_end_ts) over all events, microseconds."""
        spans = [(e["ts"], e["ts"] + e.get("dur", 0))
                 for e in self.events if "ts" in e]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def load_profiler_result(file_path: str) -> ProfilerResult:
    """Reload an exported host trace for programmatic assertions
    (paddle.profiler.load_profiler_result parity, host-trace scope).

    Accepts the JSON file itself or the export directory — in a directory,
    ``host_trace.json`` is preferred, else the lexicographically first
    ``*_host_trace.json`` (worker-named exports)."""
    path = file_path
    if os.path.isdir(path):
        default = os.path.join(path, "host_trace.json")
        if os.path.isfile(default):
            path = default
        else:
            named = sorted(n for n in os.listdir(path)
                           if n.endswith("_host_trace.json"))
            if not named:
                raise FileNotFoundError(
                    f"load_profiler_result({file_path!r}): no "
                    "host_trace.json or *_host_trace.json in directory")
            path = os.path.join(file_path, named[0])
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    return ProfilerResult(path, list(events))
