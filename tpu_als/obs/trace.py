"""Measurement-side tracing: fence-timed stage spans + flight recorder.

Two instruments that turn the passive obs registry into a profiler:

- ``stage(name)``: a context manager that times one ALS stage between
  ``block_until_ready`` fences and records the wall-clock into the
  ``train.stage_seconds{stage=name}`` histogram plus the span tree from
  PR 1.  Stage names match ``perf/roofline.py`` stage names exactly so
  ``tpu_als observe attribution`` can join measured seconds against the
  modeled floor.  Fencing is what makes the numbers mean anything: JAX
  dispatch is async, so without a fence the "gather time" is just the
  enqueue time of the gather.
- ``FlightRecorder``: a bounded ring of per-request span records for the
  serving engine.  Recording is always-on and cheap (a dict append under
  a lock); ``dump(trigger)`` emits the not-yet-dumped tail as
  schema-registered ``flight_record`` events, so an SLO breach leaves
  the last N request traces in the obs trail instead of vanishing into
  a p99 bucket.

Arming: the attributed training path is OFF unless explicitly enabled
(``enable_stage_attribution()`` or ``TPU_ALS_STAGE_ATTRIBUTION=1``).
When disarmed nothing here is ever reached from the hot path — the
fused jitted step is untouched (pinned by an unchanged-jaxpr test in
tests/test_attribution.py, the same discipline resilience.faults uses).

This module must stay importable without jax (bench.py-style callers);
jax is looked up via ``sys.modules`` only when fencing.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time

from tpu_als import obs

_ENV_FLAG = "TPU_ALS_STAGE_ATTRIBUTION"
_armed = False


def enable_stage_attribution():
    """Arm the attributed (decomposed, fence-timed) training path."""
    global _armed
    _armed = True


def disable_stage_attribution():
    global _armed
    _armed = False


def stage_attribution_armed():
    """True when stage attribution is on — explicitly or via the
    ``TPU_ALS_STAGE_ATTRIBUTION`` env knob (any value but ''/'0')."""
    return _armed or os.environ.get(_ENV_FLAG, "0") not in ("", "0")


@contextlib.contextmanager
def stage_attribution():
    """Scoped arming for tests and the attribution CLI."""
    was = _armed
    enable_stage_attribution()
    try:
        yield
    finally:
        if not was:
            disable_stage_attribution()


def fence(x):
    """``jax.block_until_ready`` on any pytree, if jax is loaded;
    returns ``x`` either way (host values pass through untouched)."""
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.block_until_ready(x)
    return x


@contextlib.contextmanager
def stage(name, sink=None):
    """Fence-timed stage span.

    Yields a ``keep(x)`` callable; the body passes every device output
    it wants attributed through it.  On exit the kept values are
    ``block_until_ready``'d, and the fence-to-fence wall clock lands in
    ``train.stage_seconds{stage=name}``, the obs span tree (span name
    ``attr.<name>``), and ``sink[name]`` when a dict is given (the
    attribution runner's per-iteration accumulator).
    """
    pending = []

    def keep(x):
        pending.append(x)
        return x

    # tal: disable=timer-brackets-span -- deliberate: the clock MUST
    # bracket the span enter/exit emissions.  The attribution coverage
    # contract (tests/test_attribution.py: stage sums >= 90% of the wall
    # iteration) attributes ALL armed-path time to stages; excluding the
    # two JSONL writes per stage leaves them unattributed and breaks the
    # bound on fast (CPU) iterations.
    t0 = time.perf_counter()
    with obs.span("attr." + name, stage=name):
        yield keep
        fence(pending)
    dt = time.perf_counter() - t0
    obs.histogram("train.stage_seconds", dt, stage=name)
    if sink is not None:
        sink[name] = sink.get(name, 0.0) + dt


# Per-request span breakdown every flight record carries.  The tuple's
# source of truth lives in the stdlib-only schema module so the jax-free
# static check (analysis/vocab.py) can pin it against FLIGHT_RESERVED.
SPAN_KEYS = obs.schema.SERVE_SPAN_KEYS


class FlightRecorder:
    """Bounded ring of per-request span records.

    ``record(...)`` is the always-on cheap path (called once per request
    outcome); ``dump(trigger)`` emits every not-yet-dumped record in the
    ring as a ``flight_record`` event.  A monotonic watermark guarantees
    each record is emitted at most once, so repeated triggers (every
    request breaching a tiny SLO) cost O(new records), not O(ring).

    ``span_keys`` names the breakdown each record carries — the serving
    request spans by default; the live updater records its own
    (queue_wait/quarantine/foldin/publish) through the same ring, and
    the serving engine keeps a second one of per-BATCH records keyed by
    ``SERVE_BATCH_SPAN_KEYS``.

    ``labels`` is the recorder's STRUCTURAL attribution (e.g.
    ``tenant=<name>`` on a tenant-built engine's ring): stamped into
    every record at construction time rather than re-passed per call,
    so a new record site cannot forget the tenant and strand a dump
    event unattributable (the disjointness of label keys, span keys and
    the record's own fields is pinned by ``check_tenant_vocabulary``).
    """

    def __init__(self, capacity=64, span_keys=SPAN_KEYS, labels=None):
        self._ring = collections.deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._span_keys = tuple(span_keys)
        self._labels = dict(labels) if labels else {}
        self._seq = 0
        self._dumped_seq = 0

    def record(self, status, spans, *, e2e_seconds=None, path=None,
               **extra):
        """Append one request trace. ``spans`` maps the recorder's span
        keys -> seconds (missing/None = not reached, e.g. a shed never
        queues)."""
        with self._lock:
            self._seq += 1
            rec = {"seq": self._seq, "status": status,
                   "spans": {k: spans.get(k) for k in self._span_keys},
                   "e2e_seconds": e2e_seconds, "path": path}
            rec.update(self._labels)
            rec.update(extra)
            self._ring.append(rec)
            return self._seq

    def dump(self, trigger):
        """Emit the not-yet-dumped tail as flight_record events; returns
        the number emitted."""
        with self._lock:
            recs = [dict(r) for r in self._ring
                    if r["seq"] > self._dumped_seq]
            self._dumped_seq = self._seq
        for r in recs:
            obs.emit("flight_record", trigger=trigger, **r)
        return len(recs)

    def records(self):
        """The ring's records, oldest first (copies): for a reader in the
        process that wants them without a trigger."""
        with self._lock:
            return [dict(r) for r in self._ring]

    def __len__(self):
        with self._lock:
            return len(self._ring)
