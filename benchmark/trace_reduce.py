"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, kernel time by pattern, the operations that took most time, and the
idle gaps by the host span that covered them.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What a v5e
trace looks like (looked at by hand, PERF.md section 6): one plane
``/device:TPU:<n>`` a chip, whose line ``XLA Ops`` holds one event for every
executed HLO operation, control-flow operations enclosing their bodies';
host planes ``/host:CPU`` hold ``TraceAnnotation`` events, one line a thread,
on the same clock. ``python benchmark/trace_reduce.py <file>`` prints what a
trace holds.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: the host span the harness puts around the traced window
WINDOW_SPAN = "bench.traced_window"


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the device planes
    #: [(name, self seconds)] summed over events and averaged over chips
    op_self_s: dict = field(default_factory=dict)
    #: every device op event of chip 0: (name, start_s, dur_s, text) where
    #: text is the name with its string stats, for pattern matching
    events: list = field(default_factory=list)
    #: [(host span name, idle seconds)]
    idle_by_span: dict = field(default_factory=dict)
    n_devices: int = 0

    def kernel_seconds(self, pattern: str, t_from: float = None) -> float:
        """Summed device time of the events of chip 0 that match, of those
        that start ``t_from`` seconds into the window or later."""
        rx = re.compile(pattern)
        return sum(d for _, s, d, text in self.events
                   if (t_from is None or s >= t_from) and rx.search(text))

    def top_ops(self, k=10):
        return sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:k]

    def top_idle(self, k=10):
        return sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:k]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly nested intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """{name: self ns}: an event's duration less that of the events nested
    in it (a while loop encloses its body's operations)."""
    out = {}
    stack = []  # [end, name, self]
    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            _, n, self_ns = stack.pop()
            out[n] = out.get(n, 0) + max(self_ns, 0)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    for _, n, self_ns in stack:
        out[n] = out.get(n, 0) + max(self_ns, 0)
    return out


_OPCODE = re.compile(r"[\})\]] ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """An XLA op event is named by its whole HLO line. For the breakdown:
    the name without its number, the opcode (a custom call's target) and the
    result's shape, so that the 24 layers' calls of one kernel are one row.
    ``%pure.47 = f32[8,16,8,128]{...} custom-call(...)`` becomes
    ``pure custom-call:tpu_custom_call f32[8,16,8,128]``."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text[:120]
    base = re.sub(r"\.\d+$", "", lhs.lstrip("%"))
    m = _OPCODE.search(rhs)
    op = m.group(1) if m else "?"
    tgt = _TARGET.search(rhs)
    if tgt:
        op += ":" + tgt.group(1)
    shape = rhs.split("{", 1)[0].split(" ", 1)[0][:48]
    return f"{base} {op} {shape}"


def _event_text(ev) -> str:
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if isinstance(v, str) and v:
                parts.append(f"{k}={v}")
    except Exception:  # noqa: BLE001 — a stat that cannot be decoded
        pass
    return " ".join(parts)


def reduce_trace(path: str, host_spans=(), allow_cpu=False) -> Reduced:
    """``host_spans``: the names of the benchmark's own ``TraceAnnotation``
    spans; an idle gap goes to the innermost of them that covers its middle,
    or to ``(no span)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans, window = {}, [], None
    names = set(host_spans)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.start_ns, ev.duration_ns, ev.name,
                         _event_text(ev)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in names:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if not device_ops and allow_cpu:
        # the CPU rehearsal only: XLA:CPU's executor threads stand in for a
        # device, so that the path from trace to line runs end to end
        for plane in data.planes:
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    device_ops.setdefault("/host:CPU", []).extend(
                        (ev.start_ns, ev.duration_ns, ev.name,
                         _event_text(ev)) for ev in line.events
                        if ev.duration_ns > 0)
    if not device_ops:
        raise ValueError(f"{path}: no '{OPS_LINE}' line on any device plane")
    if window is None:  # a trace the harness did not make: all of it
        lo = min(e[0] for ops in device_ops.values() for e in ops)
        hi = max(e[0] + e[1] for ops in device_ops.values() for e in ops)
        window = (lo, hi)
    w0, w1 = window
    busy, self_ns, first = [], {}, None
    for plane in sorted(device_ops):
        ops = [(s, d, n, t) for s, d, n, t in device_ops[plane]
               if s + d > w0 and s < w1]
        merged = _union((max(s, w0), min(s + d, w1)) for s, d, _, _ in ops)
        busy.append(sum(e - s for s, e in merged))
        for n, v in _self_times(
                [(s, d, short_name(n)) for s, d, n, _ in ops]).items():
            self_ns[n] = self_ns.get(n, 0) + v
        if first is None:
            first = (ops, merged)
    ops, merged = first
    idle = {}
    edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
    spans.sort()
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid, name = (g0 + g1) / 2, "(no span)"
        for s, e, n in spans:  # sorted by start: the last cover is innermost
            if s > mid:
                break
            if e >= mid:
                name = n
        idle[name] = idle.get(name, 0) + (g1 - g0)
    n = len(device_ops)
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / n / 1e9,
        op_self_s={k: v / n / 1e9 for k, v in self_ns.items()},
        events=[(nm, (s - w0) / 1e9, d / 1e9, t) for s, d, nm, t in ops],
        idle_by_span={k: v / 1e9 for k, v in idle.items()}, n_devices=n)


def describe(path: str, top=25) -> str:
    """What a trace holds: planes, lines, event counts, the commonest names
    and one event's stats a line. For the look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            tot = {}
            for ev in evs:
                c = tot.setdefault(ev.name, [0, 0])
                c[0] += 1
                c[1] += ev.duration_ns
            for name, (cnt, ns) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {ns / 1e6:10.3f} ms x{cnt:<6} {name[:120]}")
            if evs and plane.name.startswith("/device:"):
                big = max(evs, key=lambda e: e.duration_ns)
                out.append(f"    stats of {big.name[:60]!r}: "
                           + _event_text(big)[:600])
    return "\n".join(out)


if __name__ == "__main__":
    p = sys.argv[1]
    print(describe(find_xplane(p) if os.path.isdir(p) else p))
