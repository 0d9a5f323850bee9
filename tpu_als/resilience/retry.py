"""Retry policies: jittered exponential backoff with timeouts + budgets.

The reference stack retries for free at the task level (Spark reruns a
failed task up to ``spark.task.maxFailures`` times from RDD lineage,
SURVEY.md §5.3); our JAX port has no task scheduler, so transient
failures — a flaky DCN rendezvous, a blip on the checkpoint filesystem,
a slow NFS read — must be retried at the call site.  This module is the
ONE implementation every site uses (multihost init, checkpoint save/load,
stream chunk reads), so retry semantics and observability are identical
everywhere.

Deliberately stdlib-only and jax-free, loadable standalone.  Obs events
are emitted only when ``tpu_als.obs`` is already in ``sys.modules`` —
true for every in-library call site; a standalone load passes its own
``on_attempt`` hook instead.
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time


class RetryExhausted(RuntimeError):
    """Every attempt failed.  ``last`` is the final exception,
    ``attempts`` how many were made."""

    def __init__(self, what, attempts, last):
        super().__init__(
            f"{what}: all {attempts} attempt(s) failed; last error: "
            f"{type(last).__name__}: {last}")
        self.what = what
        self.attempts = attempts
        self.last = last


class AttemptTimeout(TimeoutError):
    """One attempt exceeded the policy's per-call timeout.  The worker
    thread may still be running (Python cannot kill it); the attempt is
    abandoned and counted as failed."""


class RetryPolicy:
    """Backoff schedule + budgets.

    ``max_attempts``: total tries (1 = no retry).
    ``base_delay`` / ``factor`` / ``max_delay``: attempt k (0-based)
    sleeps ``min(max_delay, base_delay * factor**k)`` before attempt
    k+1, scaled by the jitter draw.  ``factor=1`` gives a constant wait.
    ``jitter``: fraction of the delay drawn uniformly in
    ``[1-jitter, 1+jitter]`` from a dedicated ``random.Random(seed)`` —
    deterministic per policy instance, never global RNG state.
    ``timeout``: per-attempt wall-clock budget; the attempt runs on a
    daemon thread and :class:`AttemptTimeout` counts as a failure (a
    HUNG call — a wedged collective, a dead NFS mount — becomes a
    retryable error instead of wedging the trainer).  ``None`` calls
    inline (zero thread overhead).
    ``retry_on``: exception classes that count as transient.  Anything
    else propagates immediately — a ``CheckpointCorrupt`` or
    ``ValueError`` is a fact about the data, not the weather.
    ``sleep``: injectable for tests.
    ``deterministic``: when True the jitter for attempt k is a pure
    function of ``(seed, k)`` — a fresh ``random.Random`` keyed on both
    — instead of a draw from the policy's stateful stream.  Two policies
    with the same seed then produce byte-identical schedules REGARDLESS
    of how many draws either has already made, so a traced run replays
    its retry timeline exactly.  ``None`` (the default) resolves from
    the ``TPU_ALS_TRACE`` env var at construction: tracing on means
    deterministic schedules.
    """

    def __init__(self, max_attempts=3, base_delay=0.05, factor=2.0,
                 max_delay=5.0, jitter=0.25, timeout=None,
                 retry_on=(OSError, TimeoutError), seed=0,
                 sleep=time.sleep, deterministic=None):
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if base_delay < 0 or max_delay < 0:
            raise ValueError("delays must be >= 0")
        if not 0.0 <= jitter <= 1.0:
            raise ValueError("jitter must be in [0, 1]")
        self.max_attempts = int(max_attempts)
        self.base_delay = float(base_delay)
        self.factor = float(factor)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self.timeout = timeout
        self.retry_on = tuple(retry_on)
        self.seed = seed
        self.sleep = sleep
        if deterministic is None:
            deterministic = bool(os.environ.get("TPU_ALS_TRACE"))
        self.deterministic = bool(deterministic)
        self._rng = random.Random(seed)

    def delay(self, attempt):
        """Backoff before attempt ``attempt + 1`` (0-based), jittered."""
        d = min(self.max_delay, self.base_delay * self.factor ** attempt)
        if self.jitter:
            if self.deterministic:
                # int-mix the (seed, attempt) pair: stable across
                # processes (no hash salt) and a legal Random seed
                u = random.Random(
                    int(self.seed) * 1_000_003 + attempt).random()
            else:
                u = self._rng.random()
            d *= 1.0 + self.jitter * (2.0 * u - 1.0)
        return d


def _call_with_timeout(fn, args, kwargs, seconds, what):
    """Run ``fn`` on a daemon thread, bounding THIS caller's wait —
    shared by every timed retry."""
    box = {}

    def run():
        try:
            box["v"] = fn(*args, **kwargs)
        except BaseException as e:  # re-raised on the caller's thread
            box["e"] = e

    t = threading.Thread(target=run, daemon=True, name=f"retry:{what}")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise AttemptTimeout(
            f"{what}: attempt exceeded {seconds}s timeout")
    if "e" in box:
        raise box["e"]
    return box["v"]


def _obs():
    """tpu_als.obs, but ONLY if it is already imported (keeps this
    module loadable from jax-free contexts)."""
    return sys.modules.get("tpu_als.obs")


def retry_call(fn, *args, policy=None, what=None, on_attempt=None,
               **kwargs):
    """Call ``fn(*args, **kwargs)`` under ``policy``.

    On each FAILED attempt emits a ``retry_attempt`` obs event and calls
    ``on_attempt(info_dict)`` if given.  When the budget is
    exhausted emits ``retry_exhausted`` and raises
    :class:`RetryExhausted` from the last error.
    """
    policy = policy or RetryPolicy()
    what = what or getattr(fn, "__name__", "call")
    last = None
    for attempt in range(policy.max_attempts):
        t0 = time.monotonic()
        try:
            if policy.timeout is not None:
                return _call_with_timeout(fn, args, kwargs,
                                          policy.timeout, what)
            return fn(*args, **kwargs)
        except policy.retry_on as e:
            last = e
            info = {
                "what": what,
                "attempt": attempt + 1,
                "attempts": policy.max_attempts,
                "elapsed_seconds": round(time.monotonic() - t0, 6),
                "reason": f"{type(e).__name__}: {e}",
            }
            obs = _obs()
            if obs is not None:
                try:
                    obs.emit("retry_attempt", **info)
                except Exception:
                    pass  # bookkeeping must never mask the retried call
            if on_attempt is not None:
                on_attempt(dict(info))
            if attempt + 1 < policy.max_attempts:
                policy.sleep(policy.delay(attempt))
    obs = _obs()
    if obs is not None:
        try:
            obs.emit("retry_exhausted", what=what,
                     attempts=policy.max_attempts,
                     reason=f"{type(last).__name__}: {last}")
        except Exception:
            pass
    raise RetryExhausted(what, policy.max_attempts, last) from last
