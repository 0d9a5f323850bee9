"""Platform detection shared by the Pallas/XLA kernel dispatchers."""

from __future__ import annotations

import os
import time
import warnings

import jax


class ProbeCache(dict):
    """A named per-kernel probe cache: ``key -> bool`` outcome, plus a
    ``meta`` side-table (``key -> {"seconds", "reason"}``) recording how
    each outcome was reached — ``reason`` is the cause in words (which
    ladder rung validated, the compiler's refusal, the two timings of a
    lost timing probe), so a verdict is never a bare bool.  Still a plain dict to callers —
    :func:`probe_kernel`'s ``(cache, key, probe)`` contract is unchanged —
    but named caches registered here are enumerable (``probe_caches``),
    clearable for tests (``clear_probe_caches``), and bankable into the
    persistent plan cache (``snapshot_probes`` / ``seed_probes``).
    """

    def __init__(self, name):
        super().__init__()
        self.name = name
        self.meta = {}


_PROBE_CACHES: dict = {}      # name -> ProbeCache (one registry per process)


def probe_cache(name):
    """The process-wide named probe cache, created on first use.  Each
    Pallas module binds its ``_AVAILABLE`` (and timing) dict here so every
    probe verdict in the process is reachable from one registry instead of
    five private module globals."""
    c = _PROBE_CACHES.get(name)
    if c is None:
        c = _PROBE_CACHES[name] = ProbeCache(name)
    return c


def probe_caches():
    """Snapshot view of the registry: ``{name: ProbeCache}``."""
    return dict(_PROBE_CACHES)


def clear_probe_caches(name=None):
    """Empty one named cache (or all of them) IN PLACE — module globals
    keep their identity, so clearing is safe mid-process (tests, ``tpu_als
    plan clear``)."""
    targets = ([_PROBE_CACHES[name]] if name is not None
               else list(_PROBE_CACHES.values()))
    for c in targets:
        c.clear()
        c.meta.clear()


def snapshot_probes():
    """Bankable probe outcomes: ``{name: {repr(key): bool}}``."""
    out = {}
    for name, c in _PROBE_CACHES.items():
        entries = {repr(key): bool(val) for key, val in c.items()}
        if entries:
            out[name] = entries
    return out


def probe_timings():
    """``{name: {repr(key): seconds}}`` for probes that actually executed
    (provenance for the plan cache)."""
    out = {}
    for name, c in _PROBE_CACHES.items():
        t = {repr(k): m["seconds"] for k, m in c.meta.items()
             if m.get("seconds") is not None}
        if t:
            out[name] = t
    return out


def seed_probes(snapshot):
    """Install banked outcomes (a :func:`snapshot_probes` payload) into the
    registry.  In-process verdicts win — a key already probed THIS process
    is never overwritten by a banked one.  Returns the number of keys
    seeded."""
    import ast

    n = 0
    for name, entries in (snapshot or {}).items():
        cache = probe_cache(name)
        for key_repr, val in entries.items():
            try:
                key = ast.literal_eval(key_repr)
            except (ValueError, SyntaxError):
                continue                      # unparseable key: skip, reprobe
            if key not in cache:
                cache[key] = bool(val)
                cache.meta[key] = {"seconds": None, "seeded": True,
                                   "reason": "banked verdict (plan cache)"}
                n += 1
    return n


def compiler_refusal(e):
    """The first line of the message when ``e`` is the chip compiler's
    refusal of a kernel, else None.  The compiler speaks from three
    layers: XLA (``JaxRuntimeError`` — a scoped-VMEM RESOURCE_EXHAUSTED,
    a kernel that faults when it runs), Mosaic (``MosaicError``,
    ``MLIRError``) and the Pallas TPU lowering (``NotImplementedError``
    for a primitive it lacks, its ``LoweringException``).  Anything else
    raised inside a probe — an ``AttributeError``, a wrong shape — is a
    bug in the probe and must propagate, not be filed as a verdict."""
    mod = type(e).__module__ or ""
    if (isinstance(e, (jax.errors.JaxRuntimeError, NotImplementedError))
            or mod.startswith(("jax._src.pallas", "jaxlib.mlir"))):
        lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
        return f"{type(e).__name__}: {lines[0] if lines else ''}"[:300]
    return None


def _refused(label, e):
    """The verdict string for a kernel whose probe raised ``e``: warns
    once with the compiler's message, or re-raises what is no refusal."""
    refusal = compiler_refusal(e)
    if refusal is None:
        raise e
    warnings.warn(
        f"Pallas kernel {label} refused by the compiler — callers take "
        f"the next rung or backend in preference order for this process: "
        f"{refusal}", stacklevel=4)
    return f"compiler refused: {refusal}"


def try_rung(notes, label, attempt):
    """Run one rung of a probe ladder.  ``attempt()`` compiles and runs
    one kernel variant and returns whether its output was correct.
    Records under ``notes[label]`` why the rung won or lost and returns
    the bool; only the compiler's refusal is caught (:func:`_refused`)."""
    try:
        ok = bool(attempt())
    except Exception as e:
        notes[label] = _refused(label, e)
        return False
    notes[label] = "compiled and validated" if ok else "wrong result"
    return ok


def ladder_reason(notes):
    """One string for a ladder's ``notes`` (``try_rung``), rung order."""
    return "; ".join(f"{label}: {why}" for label, why in notes.items())


def probe_kernel(cache, key, probe):
    """Shared compile-and-run probe scaffolding for Pallas kernels: off-TPU
    → False; on TPU run ``probe()`` once per process and cache the
    outcome, so every ``resolve_solve_path`` call in a process sees the
    same answer.  ``probe`` returns truthy only when the kernel output is
    CORRECT, not merely finite — either a bool or ``(bool, reason)``.

    The compiler's refusal of the kernel caches False, with the first
    line of its message as the reason and one warning naming the kernel —
    silent degradation is how a run ends up on a path nobody chose.  Any
    other exception propagates (see :func:`compiler_refusal`).
    """
    if key not in cache:
        from jax._src.core import trace_state_clean

        if not trace_state_clean():
            # a probe fired while TRACING (solve_spd's auto dispatch runs
            # inside jit): the probe's own concrete arrays would become
            # tracers of the ambient trace and its block_until_ready /
            # comparison would raise.  Degrade THIS trace only, cache
            # nothing, and tell the developer to prewarm
            # (make_step/train_sharded call resolve_solve_path eagerly,
            # fold_in calls ops.solve.prewarm_solve — hitting this warning
            # means a new call path skipped that).
            warnings.warn(
                f"Pallas kernel probe {key} requested inside a jit trace; "
                "using the fallback path for this trace WITHOUT caching. "
                "Prewarm probes eagerly (tpu_als.core.als."
                "resolve_solve_path) before tracing.", stacklevel=2)
            return False
        if not on_tpu():
            cache[key] = False
            _note_probe(cache, key, seconds=None, reason="no TPU")
        else:
            t0 = time.perf_counter()
            try:
                out = probe()
            except Exception as e:
                out = (False, _refused(
                    f"{getattr(cache, 'name', 'probe')}{key}", e))
            ok, reason = out if isinstance(out, tuple) else (out, None)
            cache[key] = bool(ok)
            _note_probe(
                cache, key, seconds=time.perf_counter() - t0,
                reason=reason or ("compiled and validated" if ok
                                  else "wrong result"))
    return cache[key]


def _note_probe(cache, key, *, seconds, reason):
    """Record probe provenance on a registered :class:`ProbeCache`; plain
    dicts (tests pass bare ``{}``) are left untouched."""
    meta = getattr(cache, "meta", None)
    if meta is not None:
        meta[key] = {"seconds": seconds, "reason": reason}


def fence(x):
    """End a timed region: block until every array in ``x`` is computed,
    and return ``x``.  JAX dispatch is asynchronous, so a clock read
    without this measures the enqueue.  ``block_until_ready`` waits on
    the buffers' own ready events, which on a locally attached chip fire
    when the producing program has finished — no readback is needed to
    be sure.  The ONE way timed regions end in bench.py, chip_smoke.py,
    the autotuner and the timing probes."""
    return jax.block_until_ready(x)


_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".bench_cache", "xla_cache")


def enable_persistent_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    reads it and no directory is set in code; otherwise the cache lives
    at one fixed place inside the checkout (``.bench_cache/xla_cache``,
    git-ignored) resolved from this file — the path is part of the
    cache's key, so a directory named after the working directory, a pid
    or the time would never hit.  The thresholds admit fast compiles
    too.  Called by chip_smoke.py, bench.py and the CLI."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def on_tpu():
    """True when the first device JAX reports is a TPU chip.  A backend
    that fails to initialise raises from here: every ``interpret = not
    on_tpu()`` site would otherwise run the Pallas interpreter on the CPU
    without a word."""
    return jax.devices()[0].platform == "tpu"
