"""Chaos matrix for the resilience subsystem (SURVEY.md §5.3 parity).

Every named fault point is exercised with at least one injected failure,
asserting either retry-to-success or a clean typed error — never a raw
traceback from numpy/jax internals.  Fast single-shot cases run in
tier 1; the exhaustive point × mode matrix is ``slow``.
"""

import os
import time

import numpy as np
import pytest

from tpu_als.resilience import faults
from tpu_als.resilience.faults import FaultSpecError, InjectedFault
from tpu_als.resilience.retry import (
    AttemptTimeout,
    RetryExhausted,
    RetryPolicy,
    retry_call,
)


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with the harness disarmed — a leaked
    spec would fault unrelated tests in the same process."""
    faults.clear()
    yield
    faults.clear()


def _fast():
    """No-sleep retry policy for chaos cases."""
    return RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0)


# ---------------------------------------------------------------------------
# spec grammar


def test_parse_minimal_rule_defaults_to_once():
    rules = faults.parse_spec("checkpoint.write=raise")
    rule = rules["checkpoint.write"]
    assert rule.mode == "raise" and rule.sched == "nth" and rule.k == 1


def test_parse_full_grammar():
    rules = faults.parse_spec(
        "checkpoint.write=raise@nth=3;"
        "ingest.read_chunk=corrupt@first=2;"
        "comm.ring_step=hang:0.5@every=4;"
        "serve.gather=raise@prob=0.25,seed=7;"
        "multihost.init=raise@once")
    assert rules["checkpoint.write"].k == 3
    assert rules["ingest.read_chunk"].sched == "first"
    assert rules["comm.ring_step"].hang_seconds == 0.5
    assert rules["serve.gather"].prob == 0.25
    assert rules["multihost.init"].k == 1


@pytest.mark.parametrize("bad", [
    "nonsense",                      # not POINT=MODE
    "no.such.point=raise",           # unknown point
    "checkpoint.write=explode",      # unknown mode
    "checkpoint.write=hang:abc",     # non-numeric hang
    "checkpoint.write=hang:-1",      # negative hang
    "checkpoint.write=raise@nth=0",  # K < 1
    "checkpoint.write=raise@nth=x",  # non-integer K
    "checkpoint.write=raise@sometimes",            # unknown sched
    "checkpoint.write=raise@prob=2.0",             # P out of range
    "checkpoint.write=raise@prob=0.5,sneed=3",     # bad seed key
    "checkpoint.write=raise;checkpoint.write=corrupt",  # duplicate
    " ; ;",                          # empty
])
def test_parse_rejects_malformed_specs(bad):
    with pytest.raises(FaultSpecError):
        faults.parse_spec(bad)


def test_install_from_env_arms_and_unset_disarms():
    faults.install_from_env({faults.ENV_VAR: "serve.gather=raise"})
    assert faults.active() and faults.armed("serve.gather")
    faults.install_from_env({})
    assert not faults.active()


def test_schedules_fire_deterministically():
    faults.install("checkpoint.write=raise@nth=2")
    assert faults.check("checkpoint.write") is None
    with pytest.raises(InjectedFault):
        faults.check("checkpoint.write")
    assert faults.check("checkpoint.write") is None
    assert faults.hits("checkpoint.write") == (3, 1)

    faults.install("checkpoint.write=corrupt@first=2")
    assert [faults.check("checkpoint.write") for _ in range(4)] == \
        ["corrupt", "corrupt", None, None]

    faults.install("checkpoint.write=corrupt@every=2")
    assert [faults.check("checkpoint.write") for _ in range(4)] == \
        [None, "corrupt", None, "corrupt"]


def test_prob_schedule_replays_exactly():
    def pattern():
        faults.install("serve.gather=corrupt@prob=0.5,seed=11")
        return [faults.check("serve.gather") for _ in range(32)]

    first = pattern()
    assert first == pattern()          # pure function of (spec, hit)
    assert "corrupt" in first and None in first


def test_disarmed_check_is_none_and_cheap():
    assert not faults.active()
    assert faults.check("comm.ring_step") is None
    assert not faults.armed("comm.ring_step")
    assert faults.hits("comm.ring_step") == (0, 0)


def test_injected_fault_is_transient_ioerror():
    faults.install("multihost.init=raise")
    with pytest.raises(IOError) as ei:
        faults.check("multihost.init")
    assert ei.value.point == "multihost.init" and ei.value.hit == 1


def test_hang_mode_stalls_then_continues():
    faults.install("serve.gather=hang:0.05")
    t0 = time.monotonic()
    assert faults.check("serve.gather") is None
    assert time.monotonic() - t0 >= 0.04
    assert faults.hits("serve.gather") == (1, 1)


# ---------------------------------------------------------------------------
# retry policies


def test_backoff_schedule_without_jitter_is_exact():
    p = RetryPolicy(base_delay=0.1, factor=2.0, max_delay=0.5, jitter=0.0)
    assert [p.delay(k) for k in range(4)] == [0.1, 0.2, 0.4, 0.5]


def test_jitter_is_deterministic_per_seed():
    a = RetryPolicy(base_delay=1.0, jitter=0.25, seed=3)
    b = RetryPolicy(base_delay=1.0, jitter=0.25, seed=3)
    da, db = [a.delay(0) for _ in range(5)], [b.delay(0) for _ in range(5)]
    assert da == db
    assert all(0.75 <= d <= 1.25 for d in da)


def test_deterministic_jitter_is_drawcount_independent():
    """Deterministic mode: the jitter for attempt k is a pure function
    of (seed, k), so two same-seed policies agree byte for byte even
    after one has already drawn — the replay property traced runs
    need.  The stateful default walks its stream instead."""
    a = RetryPolicy(base_delay=1.0, jitter=0.25, seed=3,
                    deterministic=True)
    b = RetryPolicy(base_delay=1.0, jitter=0.25, seed=3,
                    deterministic=True)
    for _ in range(7):
        a.delay(0)   # burn draws on a only
    assert ([a.delay(k) for k in range(5)]
            == [b.delay(k) for k in range(5)])
    c = RetryPolicy(base_delay=1.0, jitter=0.25, seed=3,
                    deterministic=False)
    assert len({c.delay(0) for _ in range(5)}) > 1


def test_deterministic_jitter_resolves_from_trace_env(monkeypatch):
    monkeypatch.delenv("TPU_ALS_TRACE", raising=False)
    assert RetryPolicy().deterministic is False
    monkeypatch.setenv("TPU_ALS_TRACE", "1")
    assert RetryPolicy().deterministic is True
    # an explicit argument beats the env resolution
    assert RetryPolicy(deterministic=False).deterministic is False


def test_retry_succeeds_after_transient_failures():
    calls, infos = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("blip")
        return "ok"

    slept = []
    policy = RetryPolicy(max_attempts=4, base_delay=0.01, jitter=0.0,
                         sleep=slept.append)
    assert retry_call(flaky, policy=policy, what="t",
                      on_attempt=infos.append) == "ok"
    assert len(calls) == 3 and len(slept) == 2
    assert [i["attempt"] for i in infos] == [1, 2]
    assert infos[0]["what"] == "t" and "OSError: blip" in infos[0]["reason"]


def test_retry_exhausted_carries_last_error():
    def always():
        raise OSError("down")

    with pytest.raises(RetryExhausted) as ei:
        retry_call(always, policy=_fast(), what="t")
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last, OSError)
    assert ei.value.__cause__ is ei.value.last


def test_non_retryable_error_propagates_immediately():
    calls = []

    def fatal():
        calls.append(1)
        raise ValueError("a fact about the data")

    with pytest.raises(ValueError):
        retry_call(fatal, policy=_fast())
    assert len(calls) == 1


def test_per_attempt_timeout_counts_as_failure():
    policy = RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0,
                         timeout=0.05)
    with pytest.raises(RetryExhausted) as ei:
        retry_call(time.sleep, 5.0, policy=policy, what="hung")
    assert isinstance(ei.value.last, AttemptTimeout)


def test_retry_emits_obs_events():
    from tpu_als import obs

    reg = obs.reset()
    with pytest.raises(RetryExhausted):
        retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                   policy=RetryPolicy(max_attempts=2, base_delay=0.0,
                                      jitter=0.0), what="t")
    kinds = [e["type"] for e in reg._events]
    assert kinds.count("retry_attempt") == 2
    assert kinds.count("retry_exhausted") == 1


# ---------------------------------------------------------------------------
# fault point: checkpoint.write / checkpoint.rename


def _save(path, rng, iteration=1, **kw):
    from tpu_als.io.checkpoint import save_factors

    ids = np.arange(10)
    F = rng.normal(size=(10, 3)).astype(np.float32)
    save_factors(path, ids, F, ids, F, params={}, iteration=iteration,
                 **kw)
    return F


def test_checkpoint_write_transient_error_is_retried(rng, tmp_path):
    from tpu_als.io.checkpoint import load_factors

    path = str(tmp_path / "ck")
    faults.install("checkpoint.write=raise@nth=1")
    F = _save(path, rng, retry_policy=_fast())
    reached, fired = faults.hits("checkpoint.write")
    assert fired == 1 and reached >= 2      # failed once, then succeeded
    manifest, _, U, _, _ = load_factors(path)
    np.testing.assert_array_equal(U, F)


def test_checkpoint_write_corruption_detected_and_quarantined(
        rng, tmp_path):
    from tpu_als.io.checkpoint import CheckpointCorrupt, load_factors

    path = str(tmp_path / "ck")
    faults.install("checkpoint.write=corrupt@nth=1")
    _save(path, rng)                # torn npz slips past the writer
    faults.clear()
    with pytest.raises(CheckpointCorrupt) as ei:
        load_factors(path, retry_policy=_fast())
    assert "digest mismatch" in ei.value.reason
    # forensics copy moved aside, primary gone
    qdir = tmp_path / ".corrupt"
    assert qdir.is_dir() and list(qdir.iterdir())
    assert not os.path.exists(path)


def test_checkpoint_rename_crash_window_leaves_old_loadable(
        rng, tmp_path):
    from tpu_als.io.checkpoint import load_factors

    path = str(tmp_path / "ck")
    F1 = _save(path, rng, iteration=1)
    faults.install("checkpoint.rename=raise@nth=1")
    with pytest.raises(RetryExhausted):
        # max_attempts=1: the crash lands mid-swap and stays there
        _save(path, rng, iteration=2,
              retry_policy=RetryPolicy(max_attempts=1))
    faults.clear()
    # primary gone, .old holds the complete previous generation
    assert not os.path.exists(os.path.join(path, "manifest.json"))
    manifest, _, U, _, _ = load_factors(path)
    assert manifest["iteration"] == 1
    np.testing.assert_array_equal(U, F1)


def test_checkpoint_rename_retry_completes_the_swap(rng, tmp_path):
    from tpu_als.io.checkpoint import load_factors

    path = str(tmp_path / "ck")
    _save(path, rng, iteration=1)
    faults.install("checkpoint.rename=raise@nth=1")
    _save(path, rng, iteration=2, retry_policy=_fast())
    faults.clear()
    manifest, *_ = load_factors(path)
    assert manifest["iteration"] == 2


def test_discover_resume_picks_newest_valid_generation(rng, tmp_path):
    from tpu_als.io.checkpoint import discover_resume

    ck = str(tmp_path / "als_checkpoint")
    _save(ck, rng, iteration=5)
    assert discover_resume(str(tmp_path)) == ck
    # also accepts the checkpoint dir itself
    assert discover_resume(ck) == ck


def test_discover_resume_quarantines_corrupt_generation(rng, tmp_path):
    from tpu_als.io.checkpoint import discover_resume

    ck = str(tmp_path / "als_checkpoint")
    _save(ck, rng, iteration=5)
    with open(os.path.join(ck, "user_factors.npz"), "ab") as f:
        f.write(b"bitrot")          # digest mismatch
    assert discover_resume(str(tmp_path)) is None
    assert (tmp_path / ".corrupt").is_dir()


def test_discover_resume_empty_dir_is_none(tmp_path):
    from tpu_als.io.checkpoint import discover_resume

    assert discover_resume(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# fault point: ingest.read_chunk


def _ratings_csv(tmp_path, rows=200):
    lines = [f"u{k % 17},i{k % 11},{(k % 5) + 1.0}" for k in range(rows)]
    p = tmp_path / "ratings.csv"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def test_ingest_chunk_read_retried_to_identical_result(tmp_path):
    from tpu_als.io.stream import stream_ingest

    path = _ratings_csv(tmp_path)
    want = stream_ingest(path, chunk_bytes=256)
    faults.install("ingest.read_chunk=raise@nth=2")
    got = stream_ingest(path, chunk_bytes=256, retry_policy=_fast())
    reached, fired = faults.hits("ingest.read_chunk")
    assert fired == 1 and reached > fired
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_ingest_chunk_corruption_is_a_typed_parse_error(tmp_path):
    from tpu_als.io.stream import stream_ingest

    path = _ratings_csv(tmp_path)
    faults.install("ingest.read_chunk=corrupt@nth=1")
    with pytest.raises(ValueError, match="malformed"):
        stream_ingest(path, chunk_bytes=256, retry_policy=_fast())


def test_ingest_chunk_retry_exhaustion_surfaces(tmp_path):
    from tpu_als.io.stream import stream_ingest

    path = _ratings_csv(tmp_path)
    faults.install("ingest.read_chunk=raise@first=5")
    with pytest.raises(RetryExhausted):
        stream_ingest(path, chunk_bytes=256, retry_policy=_fast())


# ---------------------------------------------------------------------------
# fault point: multihost.init


def test_multihost_init_retries_rendezvous():
    from tpu_als.parallel.multihost import init_distributed

    faults.install("multihost.init=raise@first=2")
    pid, pcount = init_distributed(retry_policy=_fast())
    assert (pid, pcount) == (0, 1)
    assert faults.hits("multihost.init") == (3, 2)


def test_multihost_init_exhaustion_raises():
    from tpu_als.parallel.multihost import init_distributed

    faults.install("multihost.init=raise@first=99")
    with pytest.raises(RetryExhausted):
        init_distributed(retry_policy=RetryPolicy(max_attempts=2,
                                                  base_delay=0.0,
                                                  jitter=0.0))


# ---------------------------------------------------------------------------
# fault point: comm.ring_step


def _ring_step_inputs(rng, armed_spec=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_als.core.als import AlsConfig
    from tpu_als.parallel.comm import shard_csr_grid
    from tpu_als.parallel.data import partition_balanced
    from tpu_als.parallel.mesh import AXIS, make_mesh
    from tpu_als.parallel.trainer import make_ring_step, stacked_counts

    D, rank = 8, 4
    u = rng.integers(0, 24, 300)
    i = rng.integers(0, 16, 300)
    r = np.abs(rng.normal(size=300)).astype(np.float32) + 0.1
    upart = partition_balanced(np.bincount(u, minlength=24), D)
    ipart = partition_balanced(np.bincount(i, minlength=16), D)
    cfg = AlsConfig(rank=rank, max_iter=1, reg_param=0.1, seed=0)
    ugrid = shard_csr_grid(upart, ipart, u, i, r, min_width=4)
    igrid = shard_csr_grid(ipart, upart, i, u, r, min_width=4)
    mesh = make_mesh(D)
    leading = NamedSharding(mesh, P(AXIS))
    U = jax.device_put(
        jnp.ones((upart.padded_rows, rank), jnp.float32), leading)
    V = jax.device_put(
        jnp.ones((ipart.padded_rows, rank), jnp.float32), leading)
    ub = jax.device_put(ugrid.device_buckets(), leading)
    ib = jax.device_put(igrid.device_buckets(), leading)
    uc = jax.device_put(
        jnp.asarray(stacked_counts(upart, u, r)), leading)
    ic = jax.device_put(
        jnp.asarray(stacked_counts(ipart, i, r)), leading)
    if armed_spec:
        faults.install(armed_spec)
    step = make_ring_step(mesh, ugrid, igrid, cfg)
    return step, (U, V, ub, ib, uc, ic)


def test_ring_step_disarmed_returns_raw_jitted(rng):
    step, args = _ring_step_inputs(rng)
    # the disarmed builder must hand back the jitted callable itself —
    # that is the "traced jaxprs unchanged" guarantee test_comm_audit
    # relies on (a wrapper would hide .lower from the audit)
    assert hasattr(step, "lower")
    U, V = step(*args)
    assert np.isfinite(np.asarray(U)).all()


def test_ring_step_injected_failure_raises_typed(rng):
    step, args = _ring_step_inputs(rng, "comm.ring_step=raise@nth=1")
    assert not hasattr(step, "lower")   # chaos wrapper installed
    with pytest.raises(InjectedFault):
        step(*args)


def test_ring_step_corruption_detected_as_factors_corrupt(rng):
    from tpu_als.parallel.trainer import FactorsCorrupt

    step, args = _ring_step_inputs(rng, "comm.ring_step=corrupt@nth=2")
    U, V = step(*args)                  # hit 1: clean
    assert np.isfinite(np.asarray(U)).all()
    with pytest.raises(FactorsCorrupt):
        step(U, V, *args[2:])           # hit 2: poisoned reduction


# ---------------------------------------------------------------------------
# fault point: serve.gather (degraded-mode serving)


def _serve_setup(rng):
    from tpu_als.parallel import serve
    from tpu_als.parallel.mesh import make_mesh

    serve.reset_last_good()
    U = rng.normal(size=(12, 4)).astype(np.float32)
    V = rng.normal(size=(20, 4)).astype(np.float32)
    return serve, U, V, make_mesh(8)


@pytest.mark.parametrize("mode", ["raise", "corrupt"])
def test_serve_degrades_to_last_good_catalog(rng, mode):
    from tpu_als import obs

    serve, U, V, mesh = _serve_setup(rng)
    reg = obs.reset()
    s0, i0 = serve.topk_sharded(U, V, 5, mesh)     # primes _last_good
    faults.install(f"serve.gather={mode}@nth=1")
    s1, i1, info = serve.topk_sharded(U, V, 5, mesh, return_info=True)
    assert info["degraded"] and info["reason"]
    np.testing.assert_allclose(s1, s0, atol=1e-5)  # same catalog served
    assert reg.snapshot()["counters"]["serve.degraded"] == 1
    assert "serve_degraded" in [e["type"] for e in reg._events]


def test_serve_without_cache_raises_shard_lost(rng):
    serve, U, V, mesh = _serve_setup(rng)
    faults.install("serve.gather=raise@nth=1")
    with pytest.raises(serve.ServeShardLost):
        serve.topk_sharded(U, V, 5, mesh)


def test_serve_recovers_after_fault_clears(rng):
    serve, U, V, mesh = _serve_setup(rng)
    s0, _ = serve.topk_sharded(U, V, 5, mesh)
    faults.install("serve.gather=raise@nth=1")
    _, _, info = serve.topk_sharded(U, V, 5, mesh, return_info=True)
    assert info["degraded"]
    s2, _, info2 = serve.topk_sharded(U, V, 5, mesh, return_info=True)
    assert not info2["degraded"]
    np.testing.assert_array_equal(s2, s0)


def test_last_good_cache_keyed_by_mesh(rng):
    """Two meshes in one process (a pod host serving two slices) must
    never answer from each other's cached catalog: priming mesh A leaves
    mesh B with nothing to degrade onto."""
    import jax

    serve, U, V, _ = _serve_setup(rng)
    from tpu_als.parallel.mesh import make_mesh

    mesh_a = make_mesh(devices=jax.devices()[:4])
    mesh_b = make_mesh(devices=jax.devices()[4:8])
    s0, _ = serve.topk_sharded(U, V, 5, mesh_a)    # primes A only
    faults.install("serve.gather=raise@first=2")
    with pytest.raises(serve.ServeShardLost):      # B has no last-good
        serve.topk_sharded(U, V, 5, mesh_b)
    s1, _, info = serve.topk_sharded(U, V, 5, mesh_a, return_info=True)
    assert info["degraded"]                        # A degrades onto A's
    np.testing.assert_allclose(s1, s0, atol=1e-5)


def test_last_good_cache_bounded_per_mesh(rng):
    """The degraded cache holds ONE entry per mesh — the newest
    successful serve, whatever strategy produced it — and that entry
    backs any strategy's failover (a catalog of generation g is correct
    for every strategy; the answer is already flagged degraded).
    Per-strategy entries only multiplied full-catalog retention."""
    serve, U, V, mesh = _serve_setup(rng)
    serve.topk_sharded(U, V, 5, mesh, strategy="all_gather")
    serve.topk_sharded(U, V, 5, mesh, strategy="ring")
    with serve._last_good_lock:
        assert len(serve._last_good) == 1       # bounded: one per mesh
        (Vg, validg), = serve._last_good.values()
    assert Vg.shape == V.shape
    faults.install("serve.gather=raise@nth=1")
    s, _, info = serve.topk_sharded(U, V, 5, mesh, strategy="all_gather",
                                    return_info=True)
    assert info["degraded"]                     # ring's newest catalog
    assert s.shape == (U.shape[0], 5)           # backs any failover


# ---------------------------------------------------------------------------
# fault points: serving.publish / serving.score live with the engine
# tests in tests/test_serving.py (the serving subsystem owns them)


# ---------------------------------------------------------------------------
# preemption primitives (the end-to-end kill-and-resume lives in
# tests/test_resume.py)


def test_preemption_guard_records_signal():
    import signal

    from tpu_als.resilience import preempt

    assert preempt.installed() is None and not preempt.enabled()
    with preempt.PreemptionGuard() as g:
        assert preempt.installed() is g and preempt.enabled()
        assert not preempt.pending(1)
        signal.raise_signal(signal.SIGTERM)
        assert g.triggered() and g.signum == signal.SIGTERM
        assert preempt.pending(2)
    assert preempt.installed() is None


def test_preempt_env_knob_fires_at_exact_iteration(monkeypatch):
    from tpu_als.resilience import preempt

    monkeypatch.setenv(preempt.ENV_PREEMPT_AT, "3")
    assert preempt.enabled()
    assert not preempt.pending(2)
    assert preempt.pending(3)


@pytest.mark.parametrize("bad", ["three", "0", "-2", "2.5"])
def test_preempt_at_malformed_is_typed_error(monkeypatch, bad):
    """A deterministic-preemption knob that silently fails to fire is
    the worst chaos tooling: the malformed value is a typed error at
    arm time (guard entry) AND at every poll, never a no-op."""
    from tpu_als.resilience import preempt

    monkeypatch.setenv(preempt.ENV_PREEMPT_AT, bad)
    with pytest.raises(preempt.PreemptAtError):
        preempt.preempt_at()
    with pytest.raises(preempt.PreemptAtError):
        with preempt.PreemptionGuard():
            pass
    assert preempt.installed() is None   # arm-time failure leaks nothing
    with pytest.raises(preempt.PreemptAtError):
        preempt.pending(1)
    assert isinstance(preempt.PreemptAtError("x"), ValueError)


def test_preempt_at_unset_empty_and_valid(monkeypatch):
    from tpu_als.resilience import preempt

    monkeypatch.delenv(preempt.ENV_PREEMPT_AT, raising=False)
    assert preempt.preempt_at() is None
    monkeypatch.setenv(preempt.ENV_PREEMPT_AT, "")
    assert preempt.preempt_at() is None
    monkeypatch.setenv(preempt.ENV_PREEMPT_AT, "4")
    assert preempt.preempt_at() == 4


def test_preempted_is_systemexit_with_distinct_code():
    from tpu_als.resilience import preempt

    p = preempt.Preempted(7, "/tmp/ck")
    assert isinstance(p, SystemExit) and p.code == preempt.EXIT_PREEMPTED
    assert "/tmp/ck" in str(p) and p.iteration == 7


def test_estimator_preempts_at_iteration_boundary(rng, tmp_path,
                                                  monkeypatch):
    import tpu_als
    from tests.conftest import make_ratings
    from tpu_als.io.checkpoint import load_factors
    from tpu_als.resilience import preempt

    u, i, r, _, _ = make_ratings(rng, num_users=40, num_items=25, rank=3)
    frame = {"user": u, "item": i, "rating": r}
    monkeypatch.setenv(preempt.ENV_PREEMPT_AT, "3")
    als = tpu_als.ALS(rank=3, maxIter=8, regParam=0.01, seed=1,
                      checkpointDir=str(tmp_path), checkpointInterval=100)
    with pytest.raises(preempt.Preempted) as ei:
        als.fit(frame)
    assert ei.value.iteration == 3
    manifest, *_ = load_factors(str(tmp_path / "als_checkpoint"))
    assert manifest["iteration"] == 3


# ---------------------------------------------------------------------------
# elastic mesh training: the detect -> classify -> reschedule primitives
# (the end-to-end loss -> reform -> bitwise resume lives in the
# device-loss scenario, tests/test_scenarios.py)


@pytest.fixture
def _no_lost():
    from tpu_als.resilience import elastic

    elastic.clear_lost()
    yield elastic
    elastic.clear_lost()


def test_lost_registry_roundtrip(_no_lost):
    elastic = _no_lost
    assert elastic.lost_devices() == frozenset()
    elastic.mark_lost(2, 5)
    assert elastic.lost_devices() == frozenset({2, 5})
    elastic.clear_lost()
    assert elastic.lost_devices() == frozenset()


def test_victim_index_validates():
    from tpu_als.resilience import elastic

    assert elastic._victim_index(4, environ={}) == 3
    assert elastic._victim_index(
        4, environ={elastic.ENV_LOST_DEVICE: "1"}) == 1
    with pytest.raises(ValueError, match="not an integer"):
        elastic._victim_index(4, environ={elastic.ENV_LOST_DEVICE: "x"})
    with pytest.raises(ValueError, match="out of range"):
        elastic._victim_index(4, environ={elastic.ENV_LOST_DEVICE: "4"})


def test_classify_reports_only_dead_peers(_no_lost):
    import jax

    elastic = _no_lost
    devices = jax.devices()[:4]
    policy = RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0)
    assert elastic.classify(devices, policy=policy) == ()
    elastic.mark_lost(devices[2].id)
    assert elastic.classify(devices, policy=policy) == (
        int(devices[2].id),)


def test_surviving_devices_preserve_mesh_order(_no_lost):
    from tpu_als.parallel.mesh import make_mesh

    elastic = _no_lost
    mesh = make_mesh(4)
    flat = list(mesh.devices.flat)
    elastic.mark_lost(flat[1].id)
    survivors = elastic.surviving_devices(mesh)
    assert [int(d.id) for d in survivors] == [
        int(d.id) for d in (flat[0], flat[2], flat[3])]


def _probe_fast(max_attempts=2):
    return RetryPolicy(max_attempts=max_attempts, base_delay=0.0,
                       jitter=0.0, sleep=lambda s: None,
                       retry_on=(OSError, TimeoutError))


def test_wrap_step_transient_failure_retried_in_place(_no_lost):
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.resilience import elastic

    mesh = make_mesh(2)
    calls = []

    def step(U, V):
        calls.append(1)
        if len(calls) < 2:
            raise OSError("ICI hiccup")   # every peer probes healthy
        return U, V

    wrapped = elastic.wrap_step(step, mesh, policy=_probe_fast())
    assert wrapped(1, 2) == (1, 2)
    assert len(calls) == 2


def test_wrap_step_dead_peer_raises_device_lost(_no_lost):
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.resilience import elastic
    from tpu_als.resilience.elastic import DeviceLost

    mesh = make_mesh(4)
    faults.install("mesh.device_lost=corrupt@once")
    wrapped = elastic.wrap_step(lambda U, V: (U, V), mesh,
                                policy=_probe_fast())
    with pytest.raises(DeviceLost) as ei:
        wrapped(0, 0)
    assert ei.value.lost == (int(mesh.devices.flat[-1].id),)
    assert ei.value.surviving == 3
    assert isinstance(ei.value.__cause__, elastic.ProbeFailed)


def test_elastic_vocabulary_pins_hold():
    """The recovery-trail names are a cross-process contract (the
    device-loss scenario counts them in events.jsonl): the explicit
    vocab pin must hold — declared AND emitted/consulted."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_tal_vocab_elastic_test",
        os.path.join(repo, "tpu_als", "analysis", "vocab.py"))
    vocab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vocab)
    assert vocab.check_elastic_vocabulary(repo) == []


def test_wrap_step_transient_budget_exhausts(_no_lost):
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.resilience import elastic

    mesh = make_mesh(2)

    def step(U, V):
        raise OSError("persistent but no peer is dead")

    wrapped = elastic.wrap_step(step, mesh, policy=_probe_fast(),
                                max_transient=2)
    with pytest.raises(OSError, match="persistent"):
        wrapped(0, 0)


# ---------------------------------------------------------------------------
# the full point × mode matrix (slow tier): every fault point fires under
# both raise and corrupt and ends in a retry/recovery or a typed error


_MATRIX_TYPED = {
    "checkpoint.write": ("CheckpointCorrupt",),
    "checkpoint.rename": ("RetryExhausted",),
    "ingest.read_chunk": ("ValueError", "RetryExhausted"),
    "multihost.init": ("RetryExhausted",),
    "comm.ring_step": ("InjectedFault", "FactorsCorrupt"),
    "serve.gather": ("ServeShardLost",),
}


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["raise", "corrupt"])
@pytest.mark.parametrize("point", faults.FAULT_POINTS)
def test_chaos_matrix(point, mode, rng, tmp_path):
    """Arm one (point, mode) pair, drive the owning subsystem, and
    assert the outcome is recovery or a typed error from the resilience
    vocabulary — never an untyped crash."""
    from tpu_als.io.checkpoint import CheckpointCorrupt, load_factors
    from tpu_als.io.stream import stream_ingest
    from tpu_als.parallel import serve
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.parallel.multihost import init_distributed
    from tpu_als.parallel.trainer import FactorsCorrupt

    typed = (InjectedFault, RetryExhausted, CheckpointCorrupt,
             FactorsCorrupt, serve.ServeShardLost, ValueError)
    spec = f"{point}={mode}@first=99"   # fire on EVERY hit
    one_shot = RetryPolicy(max_attempts=1)

    try:
        if point in ("checkpoint.write", "checkpoint.rename"):
            faults.install(spec)
            path = str(tmp_path / "ck")
            _save(path, rng, retry_policy=one_shot)
            faults.clear()
            load_factors(path, retry_policy=one_shot)
        elif point == "ingest.read_chunk":
            path = _ratings_csv(tmp_path)
            faults.install(spec)
            stream_ingest(path, chunk_bytes=256, retry_policy=one_shot)
        elif point == "multihost.init":
            faults.install(spec)
            init_distributed(retry_policy=one_shot)
        elif point == "comm.ring_step":
            step, args = _ring_step_inputs(rng, spec)
            step(*args)
        elif point in ("serving.publish", "serving.score"):
            # raise -> InjectedFault out of publish/serve_batch;
            # corrupt -> stale-index detection + exact-path fallback
            # (the request is still answered — recovery, not an error)
            from tpu_als.serving import ServingEngine

            eng = ServingEngine(k=3, buckets=(8,), max_wait_s=0.0)
            faults.install(spec)
            eng.publish(rng.normal(size=(6, 3)).astype(np.float32),
                        rng.normal(size=(12, 3)).astype(np.float32))
            t = eng.submit(0)
            eng.serve_batch(eng.batcher.next_batch(timeout=1.0))
            t.result(timeout=1.0)
        else:  # serve.gather
            serve.reset_last_good()
            U = rng.normal(size=(8, 3)).astype(np.float32)
            V = rng.normal(size=(12, 3)).astype(np.float32)
            faults.install(spec)
            serve.topk_sharded(U, V, 4, make_mesh(8))
    except typed:
        pass                      # a clean, typed failure is a pass
    reached, fired = (faults.hits(point) if faults.active()
                      else (1, 1))  # cleared above ⇒ already asserted
    assert fired >= 1, f"{point}={mode} never fired"
