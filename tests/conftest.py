"""Test harness: force an 8-device CPU mesh before JAX initializes.

This is the direct analog of the reference stack's
``local-cluster[2,1,1024]`` test masters (SURVEY.md §4): multi-device
semantics exercised in one process, no real TPU pod required.  Must run
before any ``import jax`` in the test session.
"""

import os
import threading
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Hermetic execution planner: without this, every resolve_solve_path call
# in the suite would read/write the developer's real autotune cache
# (~/.cache/tpu_als/plan) and test outcomes would depend on what previous
# runs banked there.  One throwaway dir per session keeps the suite
# cold-start deterministic; tests that need their own cache (or the
# disarmed mode) monkeypatch TPU_ALS_PLAN_CACHE on top.
if "TPU_ALS_PLAN_CACHE" not in os.environ:
    import tempfile

    os.environ["TPU_ALS_PLAN_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="tpu_als_plan_test_"), "plan")

import jax  # noqa: E402

# the suite runs on the CPU backend whatever the shell exports
jax.config.update("jax_platforms", "cpu")
# in-process CLI calls turn on the persistent compilation cache
# (utils.platform.enable_persistent_compile_cache); the suite's thousands
# of small CPU executables must not pile up in the checkout
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_ratings(rng, num_users=60, num_items=40, rank=4, density=0.3, noise=0.0):
    """Synthetic low-rank ground truth — the reference test protocol
    (ALSSuite.genFactors/testALS, SURVEY.md §4.1)."""
    Ustar = rng.normal(0, 1.0 / np.sqrt(rank), (num_users, rank)).astype(np.float32)
    Vstar = rng.normal(0, 1.0 / np.sqrt(rank), (num_items, rank)).astype(np.float32)
    full = Ustar @ Vstar.T
    mask = rng.random((num_users, num_items)) < density
    # guarantee every user/item has at least one rating
    mask[np.arange(num_users), rng.integers(0, num_items, num_users)] = True
    mask[rng.integers(0, num_users, num_items), np.arange(num_items)] = True
    u, i = np.nonzero(mask)
    r = full[u, i] + noise * rng.normal(size=len(u)).astype(np.float32)
    return u.astype(np.int64), i.astype(np.int64), r.astype(np.float32), Ustar, Vstar


def assert_topk_within_contract(s, ix, U, V, valid, k):
    """THE serving top-k agreement (tpu_als/serving/index.py, module
    docstring): against ``chunked_topk_scores`` the sentinels sit in the
    same places, real scores lie within ``SCORE_ULPS`` units in the last
    place of the row's largest score, and the indices are identical on
    every row whose top-(k+1) exact scores are pairwise separated by
    more than twice that tolerance."""
    import jax.numpy as jnp

    from tpu_als.ops.topk import chunked_topk_scores, topk_validity
    from tpu_als.serving.index import SCORE_ULPS

    s, ix = np.asarray(s), np.asarray(ix)
    kk = min(k + 1, np.asarray(V).shape[0])
    ref_s, ref_ix = (np.asarray(a) for a in chunked_topk_scores(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(valid), kk))
    real = topk_validity(ref_s)
    np.testing.assert_array_equal(topk_validity(s), real[:, :k])
    np.testing.assert_array_equal(s[~real[:, :k]], ref_s[:, :k][~real[:, :k]])
    top = np.where(real, np.abs(ref_s), 0.0).max(axis=1).astype(np.float32)
    tol = (SCORE_ULPS * np.spacing(top))[:, None]
    diff = np.where(real[:, :k], np.abs(s - ref_s[:, :k]), 0.0)
    assert (diff <= tol).all(), (
        f"scores {diff.max():.3g} apart, allowed {SCORE_ULPS} ulp")
    gaps = np.where(real[:, 1:] & real[:, :-1],
                    ref_s[:, :-1] - ref_s[:, 1:], np.inf)
    clear = (gaps > 2 * tol).all(axis=1)
    np.testing.assert_array_equal(np.where(real[:, :k], ix, -1)[clear],
                                  np.where(real[:, :k], ref_ix[:, :k],
                                           -1)[clear])
    return int(clear.sum())


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables_per_module(request):
    """Drop jax's compiled-program caches after compile-heavy modules.

    The CPU harness compiles thousands of tiny executables in ONE
    process across 35+ modules; jaxlib's CPU JIT segfaults inside
    ``backend_compile_and_load`` once too many live executables
    accumulate — reproducibly at the same compile in two full-suite
    runs (test_stream_io's first fold-in jit, test ~380 of 408), while
    every subset of the suite passes.  Clearing after every module that
    ran a ``slow``-marked test (the interpret-mode Pallas, spawned-
    process, and e2e modules are where the executables pile up) keeps
    the live count at fast-tier levels — which ran the whole history of
    this repo without ever hitting the limit — while the fast tier
    itself (``-m "not slow"``) pays no recompiles at all.  TPU/bench
    runs never load this conftest and are unaffected.
    """
    yield
    mod = request.node
    for item in request.session.items:
        if (item.getparent(pytest.Module) is mod
                and item.get_closest_marker("slow") is not None):
            jax.clear_caches()
            return


class CompileCount:
    """Backend compilations, from JAX's own monitoring events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        self.n += event == "/jax/core/compile/backend_compile_duration"


class GatedResponses:
    """Stands between a ``ServingEngine``'s dispatch and its readback:
    every batch the engine dispatches comes back as a stub whose
    ``__array__`` (what ``np.asarray`` calls: the engine's one
    device→host transfer) blocks until the test releases it, so a test
    decides when each batch's readback ends, and with what.

    ``gates[i]`` belongs to the i-th dispatch; ``gates[i].entered`` is
    set once its readback has begun; ``release(i)`` lets it return the
    real response, ``release(i, error)`` makes it raise; ``open()``
    releases every gate there is and every later one at its birth."""

    class _Gate:
        def __init__(self, resp):
            self.resp, self.error = resp, None
            self.entered, self.released = (threading.Event(),
                                           threading.Event())

        def __array__(self, dtype=None, copy=None):
            self.entered.set()
            if not self.released.wait(30.0):
                raise TimeoutError("the test never released this readback")
            if self.error is not None:
                raise self.error
            return np.asarray(self.resp)

    def __init__(self, eng):
        self.gates, self._open = [], False
        dispatch = eng._dispatch

        def gated(*args):
            resp, *rest = dispatch(*args)
            gate = self._Gate(resp)
            if self._open:
                gate.released.set()
            self.gates.append(gate)
            return (gate, *rest)

        eng._dispatch = gated

    def release(self, i, error=None):
        self.gates[i].error = error
        self.gates[i].released.set()

    def open(self):
        self._open = True
        for gate in self.gates:
            gate.released.set()

    def wait_dispatched(self, n, timeout=10.0):
        """Until ``n`` batches have been dispatched."""
        deadline = time.monotonic() + timeout
        while len(self.gates) < n:
            assert time.monotonic() < deadline, (
                f"{len(self.gates)} batches dispatched, not {n}")
            time.sleep(0.001)
