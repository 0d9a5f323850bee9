"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.  Two stages, so that the arithmetic can be checked
on a few hand-written events and the reader on one small recorded trace:

1. :func:`read_xplane` — planes/lines/events to plain tuples, nothing else;
2. :func:`summarize` — busy union, idle gaps, self time per operation.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed HLO operation (nested where an operation such
as ``while`` contains others).  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` names, which all start with ``bench.``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
# an event of the XLA Ops line is named by its whole HLO instruction:
#   %spd_solve_lanes.24 = f32[32,128,128]{...} custom-call(...), custom_call_target="tpu_custom_call", ...
HLO_TEXT = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\]).*? ([a-z][\w\-]*)\(")
HLO_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name):
    """``%spd_solve_lanes.24 f32[32,128,128] custom-call:tpu_custom_call``
    from the HLO text; other names unchanged."""
    m = HLO_TEXT.match(name)
    if not m:
        return name[:120]
    out = f"{m.group(1)} {m.group(2)} {m.group(3)}"
    target = HLO_TARGET.search(name)
    return (out + ":" + target.group(1) if target else out)[:120]


@dataclass
class RawTrace:
    """device -> [(name, start_ns, dur_ns)] of its ``XLA Ops`` line;
    host -> [(name, start_ns, dur_ns)] of the benchmark's annotations."""

    device_ops: dict = field(default_factory=dict)
    host_spans: list = field(default_factory=list)
    lines_seen: dict = field(default_factory=dict)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw = RawTrace()
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        raw.lines_seen[plane.name] = []
        for line in plane.lines:
            raw.lines_seen[plane.name].append(line.name)
            if dev and line.name == OPS_LINE:
                ops = raw.device_ops.setdefault(int(dev.group(1)), [])
                for ev in line.events:
                    ops.append((short_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)))
            elif not dev:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        raw.host_spans.append((ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)))
    return raw


def busy_union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly nested intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def self_times(ops):
    """``{name: self_ns}``: an operation's duration less what the operations
    nested inside it cover, so that a ``while`` and its body are not counted
    twice."""
    spans = []          # [(name, dur, [ns covered by direct children])]
    stack = []          # [(end, covered_cell)] of the enclosing operations
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            stack[-1][1][0] += dur
        covered = [0]
        spans.append((name, dur, covered))
        stack.append((start + dur, covered))
    out = {}
    for name, dur, covered in spans:
        out[name] = out.get(name, 0) + max(dur - covered[0], 0)
    return out


@dataclass
class TraceSummary:
    window_s: float          # first device op start to last device op end
    busy_s: float            # union of busy intervals, mean over devices
    n_devices: int
    op_self_s: dict          # name -> seconds, summed over devices
    idle_gaps: list          # [(host span name or "(no bench span)", seconds)]
    n_ops: int

    @property
    def idle_pct(self):
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self, pattern):
        """Self seconds of operations whose name matches, as a mean over
        the devices."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_self_s.items()
                   if rx.search(name)) / self.n_devices

    def top_ops(self, n=10):
        ranked = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [(name, s / self.n_devices) for name, s in ranked]


def summarize(raw, window_ns=None, max_gaps=10):
    """``window_ns=(t0, t1)`` clips to a window on the trace's own clock;
    by default the window runs from the first device operation to the end
    of the last."""
    if not raw.device_ops or not any(raw.device_ops.values()):
        raise ValueError("the trace holds no device operation "
                         f"(planes and lines seen: {raw.lines_seen})")
    starts = [o[1] for ops in raw.device_ops.values() for o in ops]
    ends = [o[1] + o[2] for ops in raw.device_ops.values() for o in ops]
    t0, t1 = window_ns or (min(starts), max(ends))
    busy_ns, op_self, gaps = 0, {}, []
    for ops in raw.device_ops.values():
        ops = [(n, max(s, t0), min(s + dur, t1) - max(s, t0))
               for n, s, dur in ops if s < t1 and s + dur > t0]
        merged = busy_union((s, s + dur) for _, s, dur in ops)
        busy_ns += sum(e - s for s, e in merged)
        for key, ns in self_times(ops).items():
            op_self[key] = op_self.get(key, 0.0) + ns * 1e-9
        edges = [(t0, t0)] + merged + [(t1, t1)]
        gaps += [(a[1], b[0]) for a, b in zip(edges, edges[1:])
                 if b[0] > a[1]]
    n_dev = len(raw.device_ops)
    by_span = {}
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, cover = "(no bench span)", 0
        for name, s, dur in raw.host_spans:
            ov = min(g1, s + dur) - max(g0, s)
            if ov > cover:
                best, cover = name, ov
        by_span[best] = by_span.get(best, 0.0) + (g1 - g0) * 1e-9 / n_dev
    return TraceSummary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy_ns * 1e-9 / n_dev,
        n_devices=n_dev, op_self_s=op_self,
        idle_gaps=sorted(by_span.items(), key=lambda kv: -kv[1])[:max_gaps],
        n_ops=sum(len(o) for o in raw.device_ops.values()))


def profiler_options():
    """Device tracing on, Python tracer off (it would record every call of
    the load generator), host TraceMe level 1 for the ``bench.*`` spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
