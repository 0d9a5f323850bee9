"""Process-wide metrics registry + span tracing + JSONL/Prometheus sinks.

The TPU-native analog of the reference stack's SparkListener event bus +
Codahale MetricsSystem (SURVEY.md §5.1/§5.5): one in-process registry that
the instrumented hot paths (trainer, serve, ingest, checkpoint) write to
with plain dict/lock operations — no I/O, no jax imports — and that a run
drains to disk exactly once, at finalize:

- ``events.jsonl``   — append-only event log (spans, gauge sets, iteration
  records, a final ``snapshot`` of every counter/gauge/histogram),
- ``metrics.prom``   — Prometheus text exposition of the same registry,
- ``run_manifest.json`` — config / mesh / versions / git (obs.manifest).

Histograms use FIXED log-scale buckets (4 per decade, 1e-6..1e6 seconds
or bytes) so two runs' exposition files are always mergeable — the
Prometheus ``le`` contract.

``span(name)`` records wall-clock tree-structured spans (a thread-local
stack gives each event its ``path``) and applies ``jax.named_scope``
when jax is already imported, so host spans and device-trace scopes
share names (docs/observability.md's Perfetto walkthrough relies on
this); ``unscoped_span`` is the same without the scope, for spans that
lie around ``lower()`` (obs/phases.py: the phases of a serving start).
Metric/event NAMES are validated against tpu_als.obs.schema at
call time; ``scripts/check_obs_schema.py`` validates call sites
statically.
"""

from __future__ import annotations

import bisect
import contextlib
import json
import os
import sys
import threading
import time

from tpu_als.obs import schema

# 4 buckets per decade over 1e-6 .. 1e6 (49 upper bounds; the 50th
# bucket is +Inf).  Fixed — never derived from data — so exposition
# files from different runs share the same `le` grid.
BUCKET_BOUNDS = tuple(10.0 ** (e / 4.0) for e in range(-24, 25))

# in-memory event cap: a registry that is never finalized (library use,
# the test suite) must not grow without bound; finalize() reports drops
_MAX_EVENTS = 100_000

# events.jsonl rotation bound (bytes).  When an incremental finalize
# would grow the file past this, the current file is renamed to the
# next events.NNN.jsonl and a fresh events.jsonl starts — long-horizon
# soaks finalize per chaos window, so one trail never grows unbounded.
# Env-overridable; 0 disables rotation.
ROTATE_ENV = "TPU_ALS_OBS_ROTATE_BYTES"
_ROTATE_BYTES = 8 << 20


def _rotate_bound():
    raw = os.environ.get(ROTATE_ENV)
    if raw is None:
        return _ROTATE_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        return _ROTATE_BYTES


def maybe_rotate(run_dir, bound=None):
    """Rotate ``<run_dir>/events.jsonl`` to ``events.NNN.jsonl`` when it
    has reached ``bound`` bytes.  Returns the rotated-to path or None.
    Readers (report/explain/verdict) list ``events.*.jsonl`` sorted and
    read them before the live file, so rotation is transparent."""
    if bound is None:
        bound = _rotate_bound()
    if not bound:
        return None
    live = os.path.join(run_dir, "events.jsonl")
    try:
        if os.path.getsize(live) < bound:
            return None
    except OSError:
        return None
    n = 0
    while True:
        cand = os.path.join(run_dir, f"events.{n:03d}.jsonl")
        if not os.path.exists(cand):
            break
        n += 1
    os.replace(live, cand)
    return cand


def _labels_key(labels):
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _render_labels(lkey):
    if not lkey:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in lkey) + "}"


def _prom_name(name):
    return "tpu_als_" + name.replace(".", "_")


def _fmt(v):
    return f"{v:.10g}"


class _Hist:
    __slots__ = ("counts", "sum", "count", "min", "max")

    def __init__(self):
        self.counts = [0] * (len(BUCKET_BOUNDS) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, v):
        self.counts[bisect.bisect_left(BUCKET_BOUNDS, v)] += 1
        self.sum += v
        self.count += 1
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    def quantile(self, q):
        """Upper bucket bound at quantile ``q`` (0..1) — the standard
        bucketed estimate; the overflow bucket reports the observed max."""
        if self.count == 0:
            return float("nan")
        target = q * self.count
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            # acc > 0 guards q=0: target is 0 there, and an empty prefix
            # must not report the first bucket's bound as the minimum
            if acc >= target and acc > 0:
                if i < len(BUCKET_BOUNDS):
                    return BUCKET_BOUNDS[i]
                return self.max
        return self.max

    def state(self):
        return {"count": self.count, "sum": self.sum,
                "min": self.min if self.count else None,
                "max": self.max if self.count else None,
                "p50": self.quantile(0.5) if self.count else None,
                "p95": self.quantile(0.95) if self.count else None}


class MetricsRegistry:
    """Counters + gauges + histograms + events + spans, one lock.

    Hot-path calls (counter/gauge/histogram/emit/span) do dict writes
    only; nothing touches the filesystem until :meth:`finalize`.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters = {}     # (name, labels_key) -> float
        self._gauges = {}       # (name, labels_key) -> float
        self._hists = {}        # (name, labels_key) -> _Hist
        self._events = []
        self._dropped = 0
        self._flushed = 0       # events already written to disk
        self._run_dir = None
        self._manifest = None
        self._local = threading.local()

    # -- instruments ---------------------------------------------------
    def counter(self, name, value=1, **labels):
        schema.check_metric(name, "counter")
        schema.check_labels(name, labels)
        key = (name, _labels_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + value

    def gauge(self, name, value, **labels):
        schema.check_metric(name, "gauge")
        schema.check_labels(name, labels)
        key = (name, _labels_key(labels))
        with self._lock:
            self._gauges[key] = value
        # gauges are point-in-time: each set is also an event, so the
        # JSONL alone carries the history (summarize reads these)
        self.emit("metric", kind="gauge", name=name, value=value,
                  labels=dict(labels))

    def histogram(self, name, value, **labels):
        self.histogram_many(name, (value,), **labels)

    def histogram_many(self, name, values, **labels):
        """Every value of ``values`` into one series: one schema check
        and one lock for a batch's worth of observations (the serving
        engine's per-ticket latencies)."""
        schema.check_metric(name, "histogram")
        schema.check_labels(name, labels)
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Hist()
            for v in values:
                h.observe(float(v))

    def histogram_quantile(self, name, q, **labels):
        """Bucketed quantile estimate of a recorded histogram series
        (exact label match; NaN when the series has no observations).
        What ``serve-bench`` reads its p50/p99 from."""
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
        return h.quantile(q) if h is not None else float("nan")

    def histogram_count(self, name, **labels):
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
        return h.count if h is not None else 0

    def counter_value(self, name, **labels):
        key = (name, _labels_key(labels))
        with self._lock:
            return self._counters.get(key, 0)

    def counter_series(self, name):
        """``[(labels, value)]`` of every series of counter ``name``:
        what an in-process reader sums without a trail."""
        with self._lock:
            return [(dict(lk), v) for (n, lk), v in self._counters.items()
                    if n == name]

    def emit(self, etype, **fields):
        """Append one event; returns the event dict (with its ts)."""
        schema.check_event(etype, fields)
        ev = {"ts": round(time.time(), 6), "type": etype, **fields}
        with self._lock:
            if len(self._events) >= _MAX_EVENTS:
                self._dropped += 1
            else:
                self._events.append(ev)
        return ev

    # -- span tracing --------------------------------------------------
    @contextlib.contextmanager
    def span(self, name, **labels):
        """Record a wall-clock span; nest for tree structure (the event's
        ``path`` is the '/'-joined stack, ``t0`` its start in
        ``perf_counter`` seconds).  When jax is already imported the
        span is also a ``jax.named_scope`` (operations traced inside
        carry the name) and a ``TraceAnnotation`` (the span sits on the
        profiler's timeline, on the device trace's clock) — but obs
        never imports jax itself (it must stay importable in processes
        that keep jax out, e.g. bench.py's probe)."""
        with self._span(name, labels, scoped=True):
            yield

    @contextlib.contextmanager
    def unscoped_span(self, name, **labels):
        """:meth:`span` WITHOUT the ``jax.named_scope``: for a span
        around ``lower()``, where a scope would put the span's name into
        the ``op_name`` of every operation traced inside it — and so
        into the programs' texts and their cache keys.  Yields the dict
        of the event's further fields: what the body puts there is
        written with the ``span`` event as the span closes (``seconds``
        there stands in place of the span's own)."""
        with self._span(name, labels, scoped=False) as fields:
            yield fields

    def open_spans(self):
        """The names of the calling thread's open spans, outermost
        first."""
        return tuple(getattr(self._local, "stack", ()))

    @contextlib.contextmanager
    def _span(self, name, labels, scoped):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append(name)
        path = "/".join(stack)
        scope = annotation = contextlib.nullcontext()
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                if scoped:
                    scope = jax.named_scope(name)
                annotation = jax.profiler.TraceAnnotation(name)
            except Exception:
                pass
        fields = {}
        t0 = time.perf_counter()
        try:
            with scope, annotation:
                yield fields
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            self.emit("span", **{"name": name, "path": path,
                                 "t0": round(t0, 6), "seconds": round(dt, 6),
                                 **labels, **fields})

    # -- run lifecycle -------------------------------------------------
    def configure(self, run_dir, config=None, argv=None):
        """Point the registry at a run directory and capture the start-of-
        run manifest.  No files are written until :meth:`finalize` — the
        CLI's ``--output`` is atomically REPLACED by the model save
        (io.checkpoint.atomic_install), so anything written into it
        before that would be destroyed."""
        from tpu_als.obs.manifest import build_manifest

        with self._lock:
            self._run_dir = run_dir
            self._manifest = build_manifest(config=config, argv=argv)

    def active(self):
        return self._run_dir is not None

    def deconfigure(self):
        """Detach the run directory (accumulated state stays).  The CLI
        calls this after finalize so one process issuing several
        commands (the test suite, notebooks) never writes a later
        command's events into an earlier command's run dir."""
        with self._lock:
            self._run_dir = None
            self._manifest = None

    def update_manifest(self, **fields):
        with self._lock:
            if self._manifest is not None:
                self._manifest.update(fields)

    def snapshot(self):
        """Registry state as plain JSON-ready dicts."""
        with self._lock:
            return {
                "counters": {n + _render_labels(lk): v
                             for (n, lk), v in sorted(self._counters.items())},
                "gauges": {n + _render_labels(lk): v
                           for (n, lk), v in sorted(self._gauges.items())},
                "histograms": {n + _render_labels(lk): h.state()
                               for (n, lk), h in sorted(self._hists.items())},
            }

    def prometheus_text(self):
        """Prometheus text exposition of the whole registry (names
        prefixed ``tpu_als_``, dots -> underscores; counters get the
        conventional ``_total`` suffix)."""
        out = []
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: (list(h.counts), h.sum, h.count)
                     for k, h in self._hists.items()}
        by_name = {}
        for (n, lk), v in counters.items():
            by_name.setdefault((n, "counter"), []).append((lk, v))
        for (n, lk), v in gauges.items():
            by_name.setdefault((n, "gauge"), []).append((lk, v))
        for (n, lk), v in hists.items():
            by_name.setdefault((n, "histogram"), []).append((lk, v))
        for (n, kind), series in sorted(by_name.items()):
            pn = _prom_name(n)
            if kind == "counter":
                pn += "_total"
            decl = schema.METRICS.get(n)
            if decl is not None:
                out.append(f"# HELP {pn} {decl[2]}")
            out.append(f"# TYPE {pn} {kind}")
            for lk, v in sorted(series):
                if kind == "histogram":
                    counts, hsum, count = v
                    acc = 0
                    for bound, c in zip(BUCKET_BOUNDS, counts):
                        acc += c
                        lab = _render_labels(lk + (("le", _fmt(bound)),))
                        out.append(f"{pn}_bucket{lab} {acc}")
                    lab = _render_labels(lk + (("le", "+Inf"),))
                    out.append(f"{pn}_bucket{lab} {count}")
                    out.append(f"{pn}_sum{_render_labels(lk)} "
                               f"{_fmt(hsum)}")
                    out.append(f"{pn}_count{_render_labels(lk)} {count}")
                else:
                    out.append(f"{pn}{_render_labels(lk)} {_fmt(v)}")
        return "\n".join(out) + "\n"

    def finalize(self):
        """Drain the registry to the configured run dir: append new
        events to ``events.jsonl`` (with a final ``snapshot`` event),
        rewrite ``metrics.prom`` and ``run_manifest.json``.  Idempotent
        — a second call appends only events recorded since the first.
        A full ``events.jsonl`` (``TPU_ALS_OBS_ROTATE_BYTES``) rotates
        to ``events.NNN.jsonl`` first — see :func:`maybe_rotate`.
        Multi-process: only process 0 writes (peers share the dir)."""
        with self._lock:
            run_dir = self._run_dir
        if run_dir is None:
            return None
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                if jax.process_count() > 1 and jax.process_index() != 0:
                    return None
            except Exception:
                pass
        snap = self.snapshot()
        if self._dropped:
            snap["events_dropped"] = self._dropped
        self.emit("snapshot", **snap)
        os.makedirs(run_dir, exist_ok=True)
        with self._lock:
            pending = self._events[self._flushed:]
            self._flushed = len(self._events)
            manifest = dict(self._manifest or {})
        manifest["finished_at"] = round(time.time(), 6)
        from tpu_als.obs.manifest import late_device_info

        manifest.update(late_device_info())
        maybe_rotate(run_dir)
        with open(os.path.join(run_dir, "events.jsonl"), "a") as f:
            for ev in pending:
                f.write(json.dumps(ev) + "\n")
        with open(os.path.join(run_dir, "metrics.prom"), "w") as f:
            f.write(self.prometheus_text())
        with open(os.path.join(run_dir, "run_manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        return run_dir
