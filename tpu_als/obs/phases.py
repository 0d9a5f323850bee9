"""A serving start tiled by phases, each measured where its work happens.

:func:`phase` is the one instrument: a ``with`` block around one step of
a start (``obs.schema.START_PHASES``: ``ServingEngine.publish`` and its
warm-ups, ``FoldInServer(...)`` and ``prewarm``, ``LiveUpdater.start``)
that writes ONE record as it closes —

- wall seconds and the calling thread's CPU seconds;
- the bytes handed to the device inside it (the growth of
  ``device.placed_bytes``, which :func:`count_placed` counts where a table
  goes up) and the device bytes in use as it closes
  (``memory_stats()["bytes_in_use"]``, the largest over the local
  devices; 0 where the backend reports none);
- what the compile ledger (``obs/compiles.py``) saw meanwhile (the
  process's: a start is one thread's, warm up before the traffic): programs
  that reached the backend's compile call, of which the cache held
  (``cache_hits``) or did not (``cache_misses``), and the seconds traced,
  lowered and in that call

— as the ``span`` event (the tree by ``path``), and as exact sums an
in-process reader takes without a trail: the counters
``start.seconds{path}`` and ``start.placed_bytes{path}``, ``path`` the
'/'-joined ``start.*`` phases open on the thread (a phase with no '/' is
a start's top level; one that is no other's prefix is a leaf).  It is
also a ``TraceAnnotation`` — a profile taken across a start shows the
phases above the transfers and first executions they caused — and NOT a
``jax.named_scope``: phases lie around ``lower()``, and a scope's name
would enter every ``op_name`` traced inside, the pinned programs' texts
and the ``serve.*`` / ``live.*`` scope vocabulary with them
(``MetricsRegistry.unscoped_span``).

A few dozen clock reads and counter adds a START: none a request, none a
micro-batch.
"""

from __future__ import annotations

import contextlib
import sys
import time

from tpu_als import obs
from tpu_als.obs import compiles, schema

PLACED = "device.placed_bytes"


def count_placed(table, nbytes):
    """Count ``nbytes`` handed to the device for ``table`` (``users`` |
    ``catalog`` | ``index`` | ``histories`` | ``fold_fixed``), at a start
    or under traffic."""
    obs.counter(PLACED, int(nbytes), table=table)


def placed_bytes():
    """Every byte :func:`count_placed` has counted, all tables."""
    return sum(v for _, v in obs.counter_series(PLACED))


def device_bytes_in_use():
    """``bytes_in_use`` of the fullest local device; 0 where jax is not
    imported or the backend keeps no such statistic (the CPU's)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    return max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in jax.local_devices()), default=0)


def device_peak_bytes():
    """``peak_bytes_in_use`` of the fullest local device since the
    process began (what a landing reads as it ends); 0 where
    :func:`device_bytes_in_use` is."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    return max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()), default=0)


@contextlib.contextmanager
def phase(name, **labels):
    """One phase of a start (module docstring); ``name`` from
    ``obs.schema.START_PHASES``."""
    schema.check_start_phase(name)
    ledger = compiles.install()
    with obs.unscoped_span(name, **labels) as fields:
        path = "/".join(n for n in obs.open_spans()
                        if n.startswith("start."))
        mark, placed0 = ledger.now(), placed_bytes()
        cpu0, t0 = time.thread_time(), time.perf_counter()
        try:
            yield
        finally:
            # the body's own interval: what closing the record costs (the
            # device's statistics, the ledger's reading) is the parent's
            seconds = time.perf_counter() - t0
            cpu = time.thread_time() - cpu0
            handed = placed_bytes() - placed0
            did = ledger.since(mark)
            fields.update(
                seconds=round(seconds, 6), cpu_seconds=round(cpu, 6),
                placed_bytes=handed,
                device_bytes_in_use=device_bytes_in_use(),
                programs=did["programs"], cache_hits=did["cache_hits"],
                cache_misses=did["cache_misses"],
                trace_s=round(did["trace_s"], 6),
                lower_s=round(did["lower_s"], 6),
                compile_s=round(did["compile_s"], 6))
            obs.counter("start.seconds", seconds, path=path)
            obs.counter("start.placed_bytes", handed, path=path)
