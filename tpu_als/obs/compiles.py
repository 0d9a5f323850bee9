"""The compile ledger: every program JAX traces, lowers, compiles or
fetches in this process, named by the program itself.

``jax.monitoring`` hands every listener three duration events of the
compile path with the program's name (``fun_name``: ``my_prog`` traced,
``jit(my_prog)`` lowered and compiled) and, on the thread that compiles
and inside the backend's compile call, the persistent compilation
cache's own events.  :func:`install` registers ONE duration listener and
ONE event listener, once a process (``ServingEngine.__init__`` calls it,
and every start phase as it opens, a ``FoldInServer``'s first among
them; a second call returns the same :class:`Ledger`), and the ledger
keeps:

- the process's totals, readable at any instant (:meth:`Ledger.now`;
  :meth:`Ledger.since` is the difference over an interval, which a start
  phase's record carries: ``obs/phases.py``);
- one record a program NAME (:meth:`Ledger.programs`: calls and seconds
  by stage, cache hits and misses);
- the counters ``jax.programs{stage, cache, when}`` and
  ``jax.program_seconds{stage, when}`` (``when`` = ``traffic`` |
  ``before``; no program name in a label) and ONE
  ``jax_program`` event a backend-compile call: ``fun_name``, the three
  durations of that program, ``cache`` (``hit`` | ``miss`` | ``off``: the
  call went by no persistent cache) and ``phase`` — the innermost
  ``start.*`` phase open on the compiling thread, ``traffic`` where none
  is and an engine is started (:func:`traffic`), ``None`` before;
- a program that reaches the backend's compile call under ``traffic``
  also raises a ``warning`` event (``what="jax.compile"``): which
  program it was is the operator's answer to "one compilation inside the
  window".

Nothing fires outside the compile path: a process that compiles nothing
calls neither listener (``listener_calls`` in :meth:`Ledger.now` counts
every invocation, whatever the event).  stdlib + ``jax.monitoring``,
imported at :func:`install`.
"""

from __future__ import annotations

import logging
import threading
import time

from tpu_als import obs

log = logging.getLogger(__name__)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
STAGES = {TRACE: "trace", LOWER: "lower", COMPILE: "compile"}
# inside the backend's compile call, on its thread: the persistent cache
# held the executable; it did not, and the compiled one was written there
# (neither: the call went by no cache — none configured, or an entry under
# the cache's thresholds)
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

TOTALS = ("programs", "trace_s", "lower_s", "compile_s", "cache_hits",
          "cache_misses", "listener_calls")

_install_lock = threading.Lock()
_ledger = None
_engines_started = 0


def install():
    """The process's :class:`Ledger`, its listeners registered at the
    first call."""
    global _ledger
    with _install_lock:
        if _ledger is None:
            import jax.monitoring

            _ledger = Ledger()
            jax.monitoring.register_event_duration_secs_listener(
                _ledger._on_duration)
            jax.monitoring.register_event_listener(_ledger._on_event)
        return _ledger


def traffic(started):
    """An engine started (``True``) or stopped: while one is started, a
    compilation outside every start phase is ``traffic``'s."""
    global _engines_started
    with _install_lock:
        _engines_started = max(0, _engines_started + (1 if started else -1))


def _program(fun_name):
    """``jit(my_prog)`` -> ``my_prog``: the three stages' one name."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _phase():
    for name in reversed(obs.open_spans()):
        if name.startswith("start."):
            return name
    return "traffic" if _engines_started else None


class Ledger:
    """What :func:`install` returns; see the module docstring."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(TOTALS, 0)
        self._programs = {}
        self._local = threading.local()

    # -- readers -------------------------------------------------------
    def now(self):
        """The process's totals: ``programs`` (backend-compile calls),
        ``trace_s`` (nested traces counted once), ``lower_s``,
        ``compile_s`` (the backend's compile call, a cache's fetch
        included), ``cache_hits``, ``cache_misses`` (compiled, and
        written to the cache), ``listener_calls``."""
        with self._lock:
            return dict(self._totals)

    def since(self, mark):
        """The totals' growth since ``mark``, a reading of
        :meth:`now`."""
        now = self.now()
        return {k: now[k] - mark[k] for k in now}

    def programs(self):
        """``{program name: {compiles, trace_s, lower_s, compile_s,
        cache_hits, cache_misses}}``, every stage of every program this
        process met (a function only ever traced inside another has
        ``compiles`` 0 and its own ``trace_s``)."""
        with self._lock:
            return {name: dict(rec) for name, rec in self._programs.items()}

    # -- listeners -----------------------------------------------------
    def _thread(self):
        """The calling thread's own: ``cache`` (how the compile call in
        progress met the cache), ``pending`` (program name -> trace and
        lower seconds not yet followed by a compile call) and ``traced``
        (``(closed at, seconds)`` of the traces since the last compile
        call, for the nesting)."""
        local = self._local
        if not hasattr(local, "pending"):
            local.cache, local.pending, local.traced = "off", {}, []
        return local

    def _on_event(self, event, **_):
        with self._lock:
            self._totals["listener_calls"] += 1
        if event == CACHE_HIT:
            self._thread().cache = "hit"
        elif event == CACHE_MISS:
            self._thread().cache = "miss"

    def _on_duration(self, event, duration, fun_name=None, **_):
        # JAX calls this inside its compile path: whatever goes wrong in
        # the ledger must not fail the caller's compile
        try:
            self._record(event, duration, fun_name)
        except Exception:
            log.exception("compile ledger: %s not recorded", event)

    def _record(self, event, duration, fun_name):
        stage = STAGES.get(event)
        if stage is None or fun_name is None:
            with self._lock:
                self._totals["listener_calls"] += 1
            return
        name, local = _program(fun_name), self._thread()
        mine = local.pending.setdefault(name, {"trace": 0.0, "lower": 0.0})
        own = duration
        if stage == "trace":
            # a function traced inside another closes first, and the
            # outer's seconds hold the inner's: the totals take each
            # second once, a program's own record all of its own
            now, nested = time.perf_counter(), local.traced
            while nested and nested[-1][0] > now - duration:
                own -= nested.pop()[1]
            nested.append((now, duration))
            del nested[:-64]
            own = max(own, 0.0)
        cache, phase = None, _phase()
        if stage == "compile":
            cache = local.cache
            local.cache, local.pending, local.traced = "off", {}, []
        else:
            mine[stage] += duration
        with self._lock:
            rec = self._programs.setdefault(name, {
                "compiles": 0, "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
            rec[stage + "_s"] += duration
            if stage == "compile":
                rec["compiles"] += 1
                rec["cache_hits"] += cache == "hit"
                rec["cache_misses"] += cache == "miss"
            totals = self._totals
            totals["listener_calls"] += 1
            totals[stage + "_s"] += own
            if stage == "compile":
                totals["programs"] += 1
                totals["cache_hits"] += cache == "hit"
                totals["cache_misses"] += cache == "miss"
        when = "traffic" if phase == "traffic" else "before"
        obs.counter("jax.program_seconds", own, stage=stage, when=when)
        if stage != "compile":
            obs.counter("jax.programs", stage=stage, when=when)
            return
        obs.counter("jax.programs", stage=stage, cache=cache, when=when)
        obs.emit("jax_program", fun_name=name, trace_s=mine["trace"],
                 lower_s=mine["lower"], compile_s=duration, cache=cache,
                 phase=phase)
        if phase == "traffic":
            obs.emit("warning", what="jax.compile", reason=(
                f"{name} reached the backend's compile call under "
                "traffic"), fun_name=name, seconds=duration, cache=cache,
                phase=phase)
