"""What the entry point and the drivers share: the registry (BENCHMARK.json
and the files it names), spans, the tracer, and the shapes of what a driver
hands back and a per-layer reader is handed."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import string
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: seconds of the measured window that a --trace 1 run has the profiler on
TRACED_SECONDS = 5.0
#: 1 keeps TraceAnnotations and drops the runtime's own host events
HOST_TRACER_LEVEL = 2


# ---------------------------------------------------------------------------
# the registry: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    end_to_end: list  # entries of BENCHMARK.json that this cell reports
    per_layer: list
    root: str
    paths: list

    @property
    def arch(self):
        """``archs/<arch>.py`` of the configuration's ``arch``: what the
        program builds and what the yardstick counts (archs/gpt.py says what
        such a file gives)."""
        return load_module(self.root, self.paths, "archs",
                           self.config["arch"] + ".py")

    @property
    def reference(self):
        """``reference/<arch>.py``: the plain reference of the same name."""
        return load_module(self.root, self.paths, "reference",
                           self.config["arch"] + ".py")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_file(root, paths, *parts):
    """The first ``<path>/<parts...>`` that exists over the benchmark's
    directories: a later PR brings a directory of its own."""
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.exists(cand):
            return cand
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {paths} in {root}")


_MODULES = {}


def load_module(root, paths, *parts):
    """The Python file ``<path>/<parts...>``, found over the benchmark's
    directories and loaded by its place, once: whatever belongs to one
    architecture or one metric is a file found by its name."""
    path = find_file(root, paths, *parts)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(parts).replace(".", "_"), path)
        _MODULES[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_MODULES[path])
    return _MODULES[path]


def _reports(metric, cell_name, mix):
    cells = metric.get("workloads")
    return (cells is None or cell_name in cells
            or metric["name"] in mix.get("metrics", ()))


def resolve(workload, root=ROOT, registry="BENCHMARK.json") -> Cell:
    reg = load_json(os.path.join(root, registry))
    cells = {w["name"]: w for w in reg["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no cell {workload!r}; cells: "
                         + ", ".join(sorted(cells)))
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in reg["configs"]}[w["config"]]
    paths = reg["paths"]
    mix = load_json(find_file(root, paths, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in reg["end_to_end"] if _reports(m, workload, mix)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in reg["per_layer"]
                 if m["moves"] in names and _reports(m, workload, mix)]
    return Cell(name=workload, chips=w["chips"], config_name=w["config"],
                config=load_json(os.path.join(root, cfg_entry["file"])),
                mix_name=w["traffic"], mix=mix, end_to_end=e2e,
                per_layer=per_layer, root=root, paths=paths)


def list_cells(root=ROOT, registry="BENCHMARK.json"):
    reg = load_json(os.path.join(root, registry))
    return [resolve(w["name"], root, registry) for w in reg["workloads"]]


def load_reader(cell: Cell, metric_name: str):
    """``metrics/<name>.json`` names the reader file and its arguments;
    the reader's ``read(ctx, **args)`` returns a number or None."""
    spec = load_json(find_file(cell.root, cell.paths, "metrics",
                           metric_name + ".json"))
    mod = load_module(cell.root, cell.paths, "metrics", spec["reader"])
    return mod.read, spec.get("args", {})


def load_limits(cell: Cell) -> dict:
    """``limits/<cell>.json``: the limits ``correct`` holds the cell to."""
    return load_json(find_file(cell.root, cell.paths, "limits",
                               cell.name + ".json"))


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"run.py: no peaks for device kind {device_kind!r} "
                         "in benchmark/peaks.json: add it with its source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# spans, counters and the tracer: what the drivers are handed
# ---------------------------------------------------------------------------


@dataclass
class Span:
    name: str
    t0: float
    t1: float = 0.0
    attrs: dict = field(default_factory=dict)

    @property
    def seconds(self):
        return self.t1 - self.t0


class Spans:
    """The benchmark's own spans, kept in memory on the host clock and
    written into the profiler's trace as ``TraceAnnotation``s, so that an
    idle gap of the device can be laid to what the host was doing."""

    NAMES = ("submit", "engine.step", "stamp", "make_batch", "dispatch",
             "fence", "wait")

    def __init__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self.rows = []

    @contextlib.contextmanager
    def span(self, name, **attrs):
        row = Span(name, time.perf_counter(), attrs=attrs)
        with self._annotation(name):
            try:
                yield row
            finally:
                row.t1 = time.perf_counter()
                self.rows.append(row)

    def named(self, name, t_from=None, t_to=None):
        return [s for s in self.rows if s.name == name
                and (t_from is None or s.t0 >= t_from)
                and (t_to is None or s.t1 <= t_to)]


class Tracer:
    """The profiler, on for the last ``TRACED_SECONDS`` of the window of a
    ``--trace 1`` run. The python tracer is off: its events would be most of
    the file and slow the host."""

    def __init__(self, enabled, out_dir):
        self.enabled, self.dir = bool(enabled), out_dir
        self.t_start = self.t_stop = None
        self._ctx = None

    def start(self):
        import jax

        from trace_reduce import WINDOW_SPAN

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ctx = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ctx.__enter__()
        self.t_start = time.perf_counter()

    def tick(self, t_in_window, seconds):
        """Called between steps: starts the trace when the window has
        ``TRACED_SECONDS`` left."""
        if (self.enabled and self.t_start is None
                and t_in_window >= max(seconds - TRACED_SECONDS, 0.0)):
            self.start()

    def stop(self):
        if self._ctx is None:
            return
        import jax

        self.t_stop = time.perf_counter()
        self._ctx.__exit__(None, None, None)
        self._ctx = None
        jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce

        if self.t_start is None:
            return None
        import jax

        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(self.dir), host_spans=Spans.NAMES,
            allow_cpu=jax.devices()[0].platform == "cpu")
        shutil.rmtree(self.dir, ignore_errors=True)  # write little to disk
        return red


@dataclass
class Outcome:
    """What a driver hands back."""

    setup_s: float
    end_to_end: dict  # {metric name: value}, every one the driver knows
    attempted: int
    failed: int
    compared: list  # [(name, value, limit)]: correct iff every value <= limit
    counters: dict
    window: tuple  # (t0, t1) of the measured window on the host clock
    memory_peak_bytes: int
    spans: Spans = None
    tracer: Tracer = None
    extra: dict = field(default_factory=dict)  # what readers may want


@dataclass
class ReadCtx:
    """What a per-layer reader is handed."""

    cell: Cell
    outcome: Outcome
    trace: object  # trace_reduce.Reduced or None
    peaks: dict
    seconds: float

    def pattern(self, template: str) -> str:
        """A metric file's kernel pattern with ``$key`` filled from the
        configuration (top-level numbers, and its ``engine`` group's): until
        the program names its kernels, one is told from another by a shape
        that the configuration decides."""
        cfg = self.cell.config
        values = {**cfg, **cfg.get("engine", {})}
        if "hidden_size" in cfg and "num_attention_heads" in cfg:
            values["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
        return string.Template(template).safe_substitute(
            {k: v for k, v in values.items() if isinstance(v, (int, str))})

    def seconds_into_trace(self, t_host: float) -> float:
        """A time on the host's clock as seconds into the traced window."""
        return t_host - self.outcome.tracer.t_start

    def spans(self, name, traced_only=False):
        t0, t1 = self.outcome.window
        if traced_only:
            tr = self.outcome.tracer
            if tr is None or tr.t_start is None:
                return []
            t0, t1 = tr.t_start, tr.t_stop
        return self.outcome.spans.named(name, t0, t1)


def say(msg):
    print(msg, flush=True)


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips):
    """The peak on the fullest chip, read when the window closes and before
    the reference runs. The allocator's ``peak_bytes_in_use`` leaves out what
    a loaded program reserves for its temporaries (``bytes_reserved``), which
    nothing else can have (PERF.md section 6, the memory gate): so the larger
    of that peak and of what is held right now, reserved bytes included."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("bytes_in_use", 0))
                   + int(st.get("bytes_reserved", 0)))
    return peak


def configure_cache():
    """The compile cache at the place the program gives: a fixed directory
    inside the checkout, or JAX_COMPILATION_CACHE_DIR where that is set."""
    from paddle_tpu.runtime import jax_cache

    return jax_cache.configure()


