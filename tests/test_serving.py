"""Online serving subsystem tests (tpu_als/serving/).

Three layers: the int8 candidate index's agreement contract against the
exact kernel — scores within SCORE_ULPS, ids equal on rows without
near-ties (property sweep over shapes, validity masks, and adversarial
duplicate-score inputs), the micro-batching admission
queue (bucketing, shedding, deadlines), and the engine loop
(publish/swap, stale-index fallback, fault points, the serve-bench
CLI).
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from tests.conftest import GatedResponses, assert_topk_within_contract
from tpu_als import obs
from tpu_als.ops.topk import NEG_INF, chunked_topk_scores, topk_validity
from tpu_als.resilience import faults
from tpu_als.resilience.faults import InjectedFault
from tpu_als.serving import (
    DeadlineExceeded,
    Int8CandidateIndex,
    MicroBatcher,
    NoModelPublished,
    Overloaded,
    ServingEngine,
    bucket_for,
)


@pytest.fixture(autouse=True)
def _fresh():
    """Disarmed faults + a fresh metrics registry per test (counters
    are asserted exactly)."""
    faults.clear()
    reg = obs.reset()
    yield reg
    faults.clear()


def _exact(U, V, valid, k):
    s, ix = chunked_topk_scores(jnp.asarray(U), jnp.asarray(V),
                                jnp.asarray(valid), k)
    return np.asarray(s), np.asarray(ix)


# ---------------------------------------------------------------------------
# int8 index + exact rescore vs exact kernel (the acceptance property)


@pytest.mark.parametrize("n,Ni,r,k,sk,seed", [
    (1, 50, 4, 5, 20, 0),
    (13, 257, 24, 10, 40, 1),
    (33, 1000, 64, 10, 64, 2),
    (8, 96, 8, 8, 96, 3),       # shortlist == catalog: unconditional
    (5, 7, 3, 7, 7, 4),         # k == catalog size
])
def test_int8_rescore_matches_exact_random(n, Ni, r, k, sk, seed):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n, r)).astype(np.float32)
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    valid = np.ones(Ni, bool)
    idx = Int8CandidateIndex(V, valid, shortlist_k=sk)
    s, ix = idx.topk(U, k)
    assert_topk_within_contract(s, ix, U, V, valid, k)


@pytest.mark.parametrize("seed", range(4))
def test_int8_rescore_matches_exact_duplicate_scores(seed):
    # adversarial ties: the catalog is a few distinct rows repeated, so
    # exact scores collide in whole groups; duplicates quantize
    # identically, so the shortlist keeps enough of each group and the
    # returned SCORES (with multiplicity) must still match to SCORE_ULPS
    rng = np.random.default_rng(100 + seed)
    base = rng.normal(size=(6, 8)).astype(np.float32)
    V = base[rng.integers(0, 6, 120)]
    U = np.concatenate([rng.normal(size=(5, 8)), base[:3]]).astype(
        np.float32)
    valid = np.ones(120, bool)
    idx = Int8CandidateIndex(V, valid, shortlist_k=60)
    k = 12
    s, ix = idx.topk(U, k)
    ref_s, ref_ix = _exact(U, V, valid, k)
    assert_topk_within_contract(s, ix, U, V, valid, k)
    # tied indices may differ, but each must earn its claimed score
    full = U.astype(np.float64) @ V.astype(np.float64).T
    np.testing.assert_allclose(
        np.take_along_axis(full, np.asarray(ix), axis=1), ref_s,
        rtol=1e-5, atol=1e-5)


def test_int8_rescore_sparse_validity(rng):
    U = rng.normal(size=(9, 16)).astype(np.float32)
    V = rng.normal(size=(200, 16)).astype(np.float32)
    valid = rng.random(200) < 0.3
    idx = Int8CandidateIndex(V, valid, shortlist_k=48)
    s, ix = idx.topk(U, 8)
    assert_topk_within_contract(s, ix, U, V, valid, 8)
    assert valid[np.asarray(ix)[topk_validity(np.asarray(s))]].all()


def test_int8_fewer_valid_than_k_leaves_sentinels(rng):
    U = rng.normal(size=(4, 8)).astype(np.float32)
    V = rng.normal(size=(50, 8)).astype(np.float32)
    valid = np.zeros(50, bool)
    valid[[7, 21, 40]] = True
    idx = Int8CandidateIndex(V, valid, shortlist_k=10)
    s, ix = idx.topk(U, 5)
    assert_topk_within_contract(s, ix, U, V, valid, 5)  # incl. sentinels
    s = np.asarray(s)
    mask = topk_validity(s)
    np.testing.assert_array_equal(mask, np.tile([True] * 3 + [False] * 2,
                                                (4, 1)))
    assert np.isin(np.asarray(ix)[mask], [7, 21, 40]).all()


def test_int8_all_invalid_catalog(rng):
    U = rng.normal(size=(3, 4)).astype(np.float32)
    V = rng.normal(size=(20, 4)).astype(np.float32)
    idx = Int8CandidateIndex(V, np.zeros(20, bool), shortlist_k=8)
    s, _ = idx.topk(U, 4)
    assert not topk_validity(np.asarray(s)).any()
    np.testing.assert_array_equal(np.asarray(s),
                                  np.full((3, 4), NEG_INF, np.float32))


def test_int8_index_guards():
    with pytest.raises(ValueError, match="empty catalog"):
        Int8CandidateIndex(np.zeros((0, 4), np.float32))
    idx = Int8CandidateIndex(np.ones((10, 4), np.float32), shortlist_k=4)
    with pytest.raises(ValueError, match="exceeds shortlist_k"):
        idx.topk(np.ones((2, 4), np.float32), 6)
    # shortlist is capped by the catalog
    assert Int8CandidateIndex(np.ones((5, 4), np.float32),
                              shortlist_k=64).shortlist_k == 5


def test_packed_transport_carries_floats_as_int_bits(rng):
    """The engine's one-array request and response layouts are INT32:
    floats ride as integer bits, never ids as float bits.  A small int
    viewed as f32 is a subnormal and a TPU flushes it to zero — every id
    arrived as 0 on the chip (PERF.md, PR 22), which no CPU run shows."""
    from tpu_als.serving.engine import _pack_response, _select_packed

    U = rng.normal(size=(5, 4)).astype(np.float32)
    row = np.array([1.0, -2.0, 3.5, 1e-3], np.float32)
    packed = np.zeros((3, 6), np.int32)
    packed[0, 4] = 3                          # a request by user id
    packed[1, :4] = row.view(np.int32)        # a request by vector
    packed[1, 5] = 1
    Q = np.asarray(_select_packed(jnp.asarray(U), jnp.asarray(packed)))
    np.testing.assert_array_equal(Q[0], U[3])
    np.testing.assert_array_equal(Q[1], row)
    np.testing.assert_array_equal(Q[2], U[0])   # pad slot: id 0
    resp = _pack_response(jnp.asarray([[1.5, -2.0]]), jnp.asarray([[7, 1]]))
    assert resp.dtype == jnp.int32
    resp = np.asarray(resp)
    np.testing.assert_array_equal(resp[:, :2].view(np.float32),
                                  [[1.5, -2.0]])
    np.testing.assert_array_equal(resp[:, 2:], [[7, 1]])


# ---------------------------------------------------------------------------
# admission queue


def test_bucket_for():
    assert bucket_for(1, (8, 32, 128)) == 8
    assert bucket_for(8, (8, 32, 128)) == 8
    assert bucket_for(9, (8, 32, 128)) == 32
    assert bucket_for(128, (8, 32, 128)) == 128
    with pytest.raises(ValueError, match="largest bucket"):
        bucket_for(129, (8, 32, 128))


def test_batcher_coalesces_and_stamps(_fresh):
    b = MicroBatcher(buckets=(4, 8), max_wait_s=0.01)
    tickets = [b.submit(i) for i in range(3)]
    batch = b.next_batch(timeout=1.0)
    assert [t.payload for t in batch] == [0, 1, 2]
    assert all(t.t_dequeue is not None for t in batch)
    assert b.depth() == 0
    assert _fresh.histogram_count("serving.enqueue_seconds") == 3
    assert tickets[0] is batch[0]


def test_batcher_caps_dequeue_at_largest_bucket():
    b = MicroBatcher(buckets=(2, 4), max_wait_s=0.0)
    for i in range(6):
        b.submit(i)
    assert len(b.next_batch(timeout=1.0)) == 4
    assert len(b.next_batch(timeout=1.0)) == 2


def test_batcher_sheds_when_full(_fresh):
    b = MicroBatcher(buckets=(8,), max_queue=2, max_wait_s=0.0)
    b.submit(0)
    b.submit(1)
    with pytest.raises(Overloaded):
        b.submit(2)
    assert _fresh.snapshot()["counters"]["serving.shed"] == 1


def test_batcher_timeout_returns_none():
    b = MicroBatcher(max_wait_s=0.0)
    assert b.next_batch(timeout=0.01) is None


def test_batcher_close_drains_then_stops():
    b = MicroBatcher(buckets=(8,), max_wait_s=0.0)
    b.submit(0)
    b.close()
    assert len(b.next_batch(timeout=0.1)) == 1
    assert b.next_batch(timeout=0.1) is None
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(1)


def test_batcher_rejects_bad_buckets():
    with pytest.raises(ValueError, match="sorted and unique"):
        MicroBatcher(buckets=(32, 8))


# -- what closes a batch: the head's own clock (ISSUE 29) -------------------
# A ticket's age is set by backdating ``t_submit``: no sleep stands in for
# the time the engine thread was away.


def _aged(b, payload, age_s):
    t = b.submit(payload)
    t.t_submit -= age_s
    return t


def test_batcher_old_head_pops_at_once_with_everything_queued(
        _fresh, monkeypatch):
    b = MicroBatcher(buckets=(4, 8), max_wait_s=0.5)
    _aged(b, 0, 0.6)                 # queued while a batch was scored
    b.submit(1)
    b.submit(2)                      # young ones ride along
    monkeypatch.setattr(b._cond, "wait", lambda *a: pytest.fail(
        "a head older than max_wait_s entered a wait"))
    batch = b.next_batch(timeout=1.0)
    assert [t.payload for t in batch] == [0, 1, 2]
    idle_s, waiting, coalesce_s = b.last_wait
    assert (idle_s, waiting) == (0.0, 3) and coalesce_s < 0.25
    assert b.closed_by == "age" and 0.6 <= b.head_wait < 0.85
    assert _fresh.counter_value("serving.batch_closed", by="age") == 1


def test_batcher_young_head_waits_only_the_rest_of_its_window():
    b = MicroBatcher(buckets=(4, 8), max_wait_s=1.0)
    _aged(b, 0, 0.8)
    assert len(b.next_batch(timeout=1.0)) == 1
    # ~0.2 s, the remainder; the old rule waited all of max_wait_s again
    assert 0.15 <= b.last_wait[2] < 0.7
    assert b.closed_by == "wait" and 0.8 <= b.head_wait < 0.9


@pytest.mark.parametrize("coalesce", [True, False])
def test_batcher_first_arrival_into_an_empty_queue(coalesce):
    """The timed rule holds it for company; a consumer that waits for
    nothing (the engine's loop, a slot in hand) takes it alone."""
    b = MicroBatcher(buckets=(4, 8), max_wait_s=0.5)

    def arrivals():
        time.sleep(0.05)
        b.submit(0)
        time.sleep(0.05)
        b.submit(1)                  # inside the first one's window

    th = threading.Thread(target=arrivals)
    th.start()
    batch = b.next_batch(timeout=5.0, coalesce=coalesce)
    idle_s, waiting, coalesce_s = b.last_wait
    th.join()
    assert idle_s >= 0.04 and waiting == 1 and b.head_wait < 0.25
    if coalesce:
        # the lone request is the head, its age ~0: all of max_wait_s
        assert [t.payload for t in batch] == [0, 1]
        assert coalesce_s >= 0.4 and b.closed_by == "wait"
    else:
        assert [t.payload for t in batch] == [0]
        assert coalesce_s < 0.04 and b.closed_by == "slot"
        assert [t.payload for t in b.next_batch(timeout=1.0)] == [1]


@pytest.mark.parametrize("how", ["full_on_arrival", "fills_in_the_wait",
                                 "closed"])
def test_batcher_full_and_closed_close_as_before(how):
    b = MicroBatcher(buckets=(2, 4), max_wait_s=30.0)
    b.submit(0)
    if how == "full_on_arrival":
        for i in range(1, 5):
            b.submit(i)
        later = None
    elif how == "fills_in_the_wait":
        later = threading.Timer(
            0.05, lambda: [b.submit(i) for i in range(1, 5)])
    else:
        later = threading.Timer(0.05, b.close)
    if later:
        later.start()
    batch = b.next_batch(timeout=5.0)
    if later:
        later.join()
    assert b.last_wait[2] < 10.0     # nowhere near max_wait_s
    if how == "closed":
        assert b.closed_by == "closed" and len(batch) == 1
        assert b.next_batch(timeout=0.1) is None
    else:
        assert b.closed_by == "full" and len(batch) == 4
        assert b.depth() == 1


def test_batch_closed_counts_every_batch_and_the_record_says_why(
        rng, _fresh):
    eng = ServingEngine(k=5, buckets=(2, 4), shortlist_k=32,
                        max_wait_s=0.2, tenant="t")
    eng.publish(rng.normal(size=(8, 8)).astype(np.float32),
                rng.normal(size=(300, 8)).astype(np.float32))
    b = eng.batcher
    _aged(b, 0, 0.3)                               # age
    _drain_one(eng)
    b.submit(1)                                    # wait (all 0.2 s of it)
    _drain_one(eng)
    for i in range(5):                             # full, then age
        _aged(b, i, 0.3)
    _drain_one(eng)
    _drain_one(eng)
    b.submit(6)
    b.close()                                      # closed
    _drain_one(eng)
    by = {w: _fresh.counter_value("serving.batch_closed", by=w, tenant="t")
          for w in ("age", "wait", "full", "closed")}
    assert by == {"age": 2, "wait": 1, "full": 1, "closed": 1}
    assert sum(by.values()) == eng._batch_seq == 5
    assert _fresh.histogram_count("serving.batch_rows", tenant="t") == 5
    recs = eng.batch_flight.records()
    assert [r["closed_by"] for r in recs] == [
        "age", "wait", "full", "age", "closed"]
    assert [r["waiting"] for r in recs] == [1, 1, 5, 1, 1]
    assert all(r["head_wait"] >= 0.3 for r in (recs[0], recs[2], recs[3]))
    assert 0 <= recs[1]["head_wait"] < 0.1
    assert recs[1]["spans"]["serve.batch.coalesce"] >= 0.15
    assert all(r["spans"]["serve.batch.coalesce"] < 0.1
               for r in recs if r["closed_by"] != "wait")


# -- a consumer with a pipeline to ask waits for nothing (ISSUE 35) ---------
# ``max_wait_s`` is so large that one timed wait would fail the test.

PATIENT = 30.0


def _closed_by(reg):
    """``serving.batch_closed`` by label, the ways that counted."""
    counts = {w: reg.counter_value("serving.batch_closed", by=w)
              for w in ("slot", "full", "closed", "age", "wait")}
    return {w: n for w, n in counts.items() if n}


@pytest.mark.parametrize("queued,closing,by,rows", [
    (1, False, "slot", 1),           # dispatched alone
    (3, False, "slot", 3),           # whatever coalesced meanwhile
    (5, False, "slot", 4),           # just over the second-largest bucket:
    (8, False, "slot", 4),           # that bucket full, the rest next time
    (9, False, "slot", 9),           # over half of the largest: all of them
    (20, False, "full", 16),         # capped at the largest bucket
    (2, True, "closed", 2),
])
def test_batcher_without_coalescing_pops_what_is_queued_at_once(
        _fresh, monkeypatch, queued, closing, by, rows):
    b = MicroBatcher(buckets=(2, 4, 16), max_wait_s=PATIENT)
    for i in range(queued):
        b.submit(i)
    if closing:
        b.close()
    monkeypatch.setattr(b._cond, "wait", lambda *a: pytest.fail(
        "a consumer that holds a slot entered a timed wait"))
    batch = b.next_batch(timeout=1.0, coalesce=False)
    assert [t.payload for t in batch] == list(range(rows))
    idle_s, waiting, coalesce_s = b.last_wait
    assert (idle_s, waiting) == (0.0, queued) and coalesce_s < 0.25
    assert b.closed_by == by and b.depth() == queued - rows
    assert _closed_by(_fresh) == {by: 1}


def test_a_caller_that_coalesces_pops_the_largest_bucket_mostly_empty():
    """The cut to the bucket below is for the consumer that is back at
    once; a scheduler's round takes what is queued, as it always did."""
    b = MicroBatcher(buckets=(2, 4, 16), max_wait_s=0.5)
    _aged(b, 0, 0.6)
    for i in range(1, 5):
        b.submit(i)
    assert len(b.next_batch(timeout=1.0)) == 5 and b.closed_by == "age"


# ---------------------------------------------------------------------------
# engine


def _engine(rng, n=40, Ni=300, r=8, k=5, quantize=True, max_wait_s=0.0,
            buckets=(8, 32), **kw):
    eng = ServingEngine(k=k, buckets=buckets, shortlist_k=32,
                        max_wait_s=max_wait_s, **kw)
    U = rng.normal(size=(n, r)).astype(np.float32)
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    eng.publish(U, V, quantize=quantize)
    return eng, U, V


def _drain_one(eng):
    """Pump one batch through the engine synchronously (no thread)."""
    batch = eng.batcher.next_batch(timeout=1.0)
    assert batch is not None
    eng.serve_batch(batch)
    return batch


@pytest.mark.parametrize("quantize", [True, False])
def test_engine_roundtrip_ids_and_foldin_rows(rng, quantize):
    eng, U, V = _engine(rng, quantize=quantize)
    valid = np.ones(V.shape[0], bool)
    t_id = eng.submit(7)
    t_row = eng.submit(U[3] * 0.5)       # a fold-in vector payload
    _drain_one(eng)
    queries = np.stack([U[7], U[3] * 0.5])
    ref_s, ref_ix = _exact(queries, V, valid, eng.k)
    for j, t in enumerate([t_id, t_row]):
        s, ix = t.result(timeout=1.0)
        np.testing.assert_allclose(s, ref_s[j], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(ix, ref_ix[j])


def test_engine_threaded_recommend(rng, _fresh):
    eng, U, V = _engine(rng)
    with eng:
        s, ix = eng.recommend(11, timeout=5.0)
    assert s.shape == (5,) and ix.shape == (5,)
    ref_s, _ = _exact(U[11:12], V, np.ones(V.shape[0], bool), 5)
    np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
    snap = _fresh.snapshot()
    assert snap["counters"]["serving.requests"] == 1
    assert snap["histograms"]["serving.e2e_seconds"]["count"] == 1
    assert snap["histograms"]['serving.score_seconds{path="int8"}'][
        "count"] == 1


def test_engine_per_request_k_trims(rng):
    eng, _, _ = _engine(rng, k=8)
    t = eng.submit(0, k=3)
    _drain_one(eng)
    s, ix = t.result(timeout=1.0)
    assert s.shape == (3,) and ix.shape == (3,)


def test_engine_submit_guards(rng):
    eng = ServingEngine(k=5)
    with pytest.raises(NoModelPublished):
        eng.submit(0)
    eng.publish(np.ones((4, 6), np.float32), np.ones((9, 6), np.float32))
    with pytest.raises(ValueError, match="outside the published table"):
        eng.submit(4)
    with pytest.raises(ValueError, match="payload shape"):
        eng.submit(np.ones(5, np.float32))
    with pytest.raises(ValueError, match="per-request k"):
        eng.submit(0, k=6)


def test_engine_deadline_expires_in_queue(rng, _fresh):
    eng, _, _ = _engine(rng)
    t = eng.submit(0, deadline_s=0.0)
    time.sleep(0.01)
    _drain_one(eng)
    with pytest.raises(DeadlineExceeded):
        t.result(timeout=1.0)
    assert _fresh.snapshot()["counters"]["serving.expired"] == 1


def test_engine_publish_swaps_atomically(rng, _fresh):
    eng, U, V = _engine(rng)
    t1 = eng.submit(0)
    _drain_one(eng)
    V2 = V * -1.0                        # same shape: no recompile path
    assert eng.publish(U, V2) == 2
    t2 = eng.submit(0)
    _drain_one(eng)
    s1, _ = t1.result(timeout=1.0)
    s2, _ = t2.result(timeout=1.0)
    ref2, _ = _exact(U[:1], V2, np.ones(V.shape[0], bool), eng.k)
    np.testing.assert_allclose(s2, ref2[0], rtol=1e-5, atol=1e-6)
    assert not np.allclose(s1, s2)
    snap = _fresh.snapshot()
    assert snap["counters"]["serving.publishes"] == 2
    seqs = [e["seq"] for e in _fresh._events
            if e["type"] == "serving_publish"]
    assert seqs == [1, 2]


def test_engine_stale_index_falls_back_to_exact(rng, _fresh):
    eng, U, V = _engine(rng, quantize=True)
    V2 = rng.normal(size=V.shape).astype(np.float32)
    eng.publish(U, V2, quantize=False)   # index carried but stale
    t = eng.submit(2)
    _drain_one(eng)
    s, ix = t.result(timeout=1.0)
    # served the NEW catalog on the exact path, not the stale index
    ref_s, ref_ix = _exact(U[2:3], V2, np.ones(V.shape[0], bool), eng.k)
    np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
    snap = _fresh.snapshot()
    assert snap["counters"]["serving.fallback_exact"] == 1
    assert snap["histograms"]['serving.score_seconds{path="exact"}'][
        "count"] == 1


def test_engine_publish_corrupt_fault_first_publish_goes_indexless(
        rng, _fresh):
    """A torn FIRST publish has no prior generation to carry: the
    publish goes out with ``index=None`` (never an in-place mutation of
    a live index), requests take the exact path directly — no stale
    index exists, so nothing counts as a fallback."""
    faults.install("serving.publish=corrupt@nth=1")
    eng, U, V = _engine(rng, quantize=True)
    assert eng.published_index is None
    t = eng.submit(1)
    _drain_one(eng)
    s, _ = t.result(timeout=1.0)
    ref_s, _ = _exact(U[1:2], V, np.ones(V.shape[0], bool), eng.k)
    np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
    assert "serving.fallback_exact" not in _fresh.snapshot()["counters"]
    pub = [e for e in _fresh._events if e["type"] == "serving_publish"]
    assert pub and pub[-1]["quantized"] is False


def test_engine_publish_corrupt_fault_carries_stale_index(rng, _fresh):
    """A torn publish AFTER a healthy one carries the previous
    generation's index untouched — stale by seq, detected on the score
    path, counted as an exact fallback.  The prior generation's index
    object itself must stay intact (the old in-place ``seq = -1``
    corruption poisoned it for any still-serving reader)."""
    eng, U, V = _engine(rng, quantize=True)
    first = eng.published_index
    first_seq = first.seq
    faults.install("serving.publish=corrupt@nth=1")
    eng.publish(U, V, quantize=True)
    assert eng.published_index is first          # carried, not rebuilt
    assert first.seq == first_seq                # and NOT mutated
    t = eng.submit(1)
    _drain_one(eng)
    s, _ = t.result(timeout=1.0)
    ref_s, _ = _exact(U[1:2], V, np.ones(V.shape[0], bool), eng.k)
    np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
    assert _fresh.snapshot()["counters"]["serving.fallback_exact"] == 1


def test_engine_score_corrupt_fault_forces_exact(rng, _fresh):
    eng, U, V = _engine(rng, quantize=True)
    faults.install("serving.score=corrupt@nth=1")
    t = eng.submit(1)
    _drain_one(eng)
    t.result(timeout=1.0)
    assert _fresh.snapshot()["counters"]["serving.fallback_exact"] == 1


def test_engine_score_raise_fault_fails_waiting_callers(rng):
    eng, _, _ = _engine(rng)
    faults.install("serving.score=raise@nth=1")
    with eng:
        t = eng.submit(0)
        with pytest.raises(InjectedFault):
            t.result(timeout=5.0)
        # the loop survives the fault: the next request is served
        s, _ = eng.recommend(1, timeout=5.0)
    assert s.shape == (5,)


def test_engine_warmup_records_no_latency_samples(rng, _fresh):
    eng, _, _ = _engine(rng)
    eng.warmup()
    snap = _fresh.snapshot()
    assert "serving.score_seconds" not in str(snap["histograms"])
    assert snap["histograms"].get("serving.e2e_seconds") is None


def test_engine_small_catalog_skips_index(rng):
    eng = ServingEngine(k=10, buckets=(8,), max_wait_s=0.0)
    eng.publish(rng.normal(size=(4, 3)).astype(np.float32),
                rng.normal(size=(6, 3)).astype(np.float32))
    # catalog (6) < k (10): exact path, sentinel-padded like the kernel
    t = eng.submit(0)
    _drain_one(eng)
    s, _ = t.result(timeout=1.0)
    assert topk_validity(s).sum() == 6


# ---------------------------------------------------------------------------
# two batches in flight (ISSUE 33): the engine thread dispatches, a
# completion thread reads back.  GatedResponses holds each batch's readback
# until the test lets it go; with ``max_wait_s`` 0 a lone request is a batch.


def _one_batch_each(eng, gated, payloads):
    """Submit ``payloads`` one at a time, each only once the one before
    has been dispatched: a batch apiece, in this order."""
    tickets = []
    for j, payload in enumerate(payloads):
        tickets.append(eng.submit(payload))
        gated.wait_dispatched(j + 1)
    return tickets


def test_next_batch_is_dispatched_while_the_last_is_read_back(rng, _fresh):
    eng, U, V = _engine(rng)
    gated = GatedResponses(eng)
    with eng:
        a = eng.submit(3)
        gated.wait_dispatched(1)
        assert gated.gates[0].entered.wait(10.0)   # a's readback: blocked
        b = eng.submit(4)
        gated.wait_dispatched(2)                   # and b is on the device
        assert not a.done() and not b.done()
        assert b.t_dequeue is not None
        # the completion thread holds neither the table's lock nor a
        # table: a publisher gets in while it sits in a readback
        assert eng._table_lock.acquire(timeout=5.0)
        eng._table_lock.release()
        gated.open()
        ref_s, ref_ix = _exact(U[3:5], V, np.ones(V.shape[0], bool), eng.k)
        for j, t in enumerate((a, b)):
            s, ix = t.result(timeout=10.0)
            np.testing.assert_allclose(s, ref_s[j], rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(ix, ref_ix[j])
    recs = eng.batch_flight.records()
    assert [r["in_flight"] for r in recs] == [0, 1]
    assert [r["handoff_wait"] for r in recs] == [0.0, 0.0]
    assert {n: _fresh.counter_value("serving.batch_overlap", in_flight=n)
            for n in (0, 1, 2)} == {0: 1, 1: 1, 2: 0}
    # b waited for the completion thread (it was in a's readback): its
    # whole life is longer than its four phases; a's is not, but by a hop
    phases = ("serve.batch.stage", "serve.batch.dispatch",
              "serve.batch.readback", "serve.batch.complete")
    for r in recs:
        assert r["spans"]["serve.batch"] >= sum(r["spans"][p]
                                                for p in phases)
        assert r["completion_idle"] >= 0.0
    assert recs[1]["completion_idle"] == pytest.approx(0.0, abs=0.05)


def test_each_batch_in_flight_is_staged_into_an_array_of_its_own(rng):
    """The upload may read the host's buffer after ``device_put`` has
    returned (on the CPU the device array IS the buffer): the next batch
    of the bucket must not be staged over it."""
    eng, U, V = _engine(rng)
    staged, inner = [], eng._dispatch

    def keeping(m, st, *rest):
        staged.append(st)
        return inner(m, st, *rest)

    eng._dispatch = keeping
    gated = GatedResponses(eng)
    with eng:
        a, b = _one_batch_each(eng, gated, (3, 4))
        rank = U.shape[1]
        assert not np.shares_memory(staged[0], staged[1])
        assert (staged[0][0, rank], staged[1][0, rank]) == (3, 4)
        gated.open()
        ref_s, _ = _exact(U[3:5], V, np.ones(V.shape[0], bool), eng.k)
        np.testing.assert_allclose(a.result(timeout=10.0)[0], ref_s[0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(b.result(timeout=10.0)[0], ref_s[1],
                                   rtol=1e-5, atol=1e-6)


def test_a_third_batch_waits_for_one_of_two_in_flight(rng, _fresh):
    eng, _, _ = _engine(rng)
    gated = GatedResponses(eng)
    with eng:
        a, b = _one_batch_each(eng, gated, (0, 1))
        c = eng.submit(2)
        time.sleep(0.2)
        # two in flight: c is neither dispatched nor even dequeued
        assert len(gated.gates) == 2 and c.t_dequeue is None
        assert eng._handed - eng._completed == 2
        gated.release(0)
        gated.wait_dispatched(3)                   # a completed: c goes
        assert a.result(timeout=10.0) and not b.done()
        gated.open()
        assert b.result(timeout=10.0) and c.result(timeout=10.0)
    recs = eng.batch_flight.records()
    assert [r["in_flight"] for r in recs] == [0, 1, 1]
    assert recs[0]["handoff_wait"] == recs[1]["handoff_wait"] == 0.0
    assert 0.15 < recs[2]["handoff_wait"] < 5.0
    # c queued meanwhile: the wait is in its queue wait, as a request
    # behind a busy engine's always was
    assert 0.15 < c.t_dequeue - c.t_submit <= recs[2]["handoff_wait"]
    assert _fresh.counter_value("serving.batch_overlap", in_flight=2) == 0


def test_completions_arrive_in_dispatch_order(rng):
    eng, _, _ = _engine(rng)
    gated = GatedResponses(eng)
    with eng:
        a, b = _one_batch_each(eng, gated, (0, 1))
        gated.release(1)                # the later batch's response first
        time.sleep(0.1)
        assert not b.done() and not gated.gates[1].entered.is_set()
        gated.release(0)
        sa, _ = a.result(timeout=10.0)
        sb, _ = b.result(timeout=10.0)
        assert a.t_done <= b.t_done
    assert [r["batch"] for r in eng.batch_flight.records()] == [1, 2]
    # each ticket holds views of its OWN batch's buffer
    assert sa.base is not None and sb.base is not None
    assert not np.shares_memory(sa, sb)


@pytest.mark.parametrize("half", ["dispatch", "readback"])
def test_an_error_in_either_half_fails_that_batch_alone(rng, _fresh, half):
    eng, U, V = _engine(rng)
    gated = GatedResponses(eng)
    boom = RuntimeError("boom in " + half)
    if half == "dispatch":
        inner, calls = eng._dispatch, []

        def first_raises(*args):
            calls.append(1)
            if len(calls) == 1:
                raise boom
            return inner(*args)

        eng._dispatch = first_raises
    with eng:
        if half == "dispatch":
            a = eng.submit(0)
            with pytest.raises(RuntimeError, match="boom in dispatch"):
                a.result(timeout=10.0)
            b = eng.submit(1)
            gated.open()
        else:
            a, b = _one_batch_each(eng, gated, (0, 1))
            gated.release(1)
            gated.release(0, error=boom)
            with pytest.raises(RuntimeError, match="boom in readback"):
                a.result(timeout=10.0)
        s, ix = b.result(timeout=10.0)             # the next is answered
        ref_s, _ = _exact(U[1:2], V, np.ones(V.shape[0], bool), eng.k)
        np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
        # and the loop goes on, with both slots free again
        gated.open()
        assert eng.recommend(2, timeout=10.0)[0].shape == (5,)
    assert eng._handed == eng._completed
    warn = [e for e in _fresh._events if e["type"] == "warning"]
    assert len(warn) == 1 and warn[0]["what"] == "serving.batch" \
        and "boom in " + half in warn[0]["reason"]
    failed = [r for r in eng.flight.records() if r["status"] == "failed"]
    assert len(failed) == 1 and failed[0]["error"] == "RuntimeError"
    assert [r["status"] for r in eng.batch_flight.records()] == ["ok", "ok"]


@pytest.mark.parametrize("what", ["expired", "score_fault", "readback"])
def test_batches_that_never_complete_normally_give_their_slot_back(
        rng, what):
    """More such batches in a row than there are slots, then a request
    that must still be answered."""
    eng, _, _ = _engine(rng)
    gated = GatedResponses(eng)
    if what == "score_fault":
        faults.install("serving.score=raise@every=1")
    with eng:
        for j in range(3):
            if what == "expired":
                t = eng.submit(j, deadline_s=0.0)
                with pytest.raises(DeadlineExceeded):
                    t.result(timeout=10.0)
            elif what == "score_fault":
                with pytest.raises(InjectedFault):
                    eng.recommend(j, timeout=10.0)
            else:
                t = eng.submit(j)
                gated.wait_dispatched(j + 1)
                gated.release(j, error=ValueError("torn transfer"))
                with pytest.raises(ValueError, match="torn"):
                    t.result(timeout=10.0)
        faults.clear()
        gated.open()
        assert eng.recommend(5, timeout=10.0)[0].shape == (5,)
    assert eng._handed == eng._completed == (4 if what == "readback" else 1)


def test_stop_answers_what_is_queued_and_what_is_in_flight(rng):
    eng, U, V = _engine(rng)
    gated = GatedResponses(eng)
    eng.start()
    a, b = _one_batch_each(eng, gated, (0, 1))     # two in flight
    queued = [eng.submit(j) for j in (2, 3, 4)]    # and three admitted
    engine_thread, completer = eng._thread, eng._completer
    stopper = threading.Thread(target=eng.stop)
    stopper.start()
    time.sleep(0.05)
    assert stopper.is_alive()                      # draining, not dropping
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(5)
    gated.open()
    stopper.join(10.0)
    assert not stopper.is_alive()
    assert not engine_thread.is_alive() and not completer.is_alive()
    ref_s, _ = _exact(U[:5], V, np.ones(V.shape[0], bool), eng.k)
    for j, t in enumerate([a, b] + queued):
        assert t.done()
        np.testing.assert_allclose(t.result(timeout=0)[0], ref_s[j],
                                   rtol=1e-5, atol=1e-6)
    assert eng._handed == eng._completed


def test_stop_gives_up_at_its_timeout_on_both_threads_together(rng):
    eng, _, _ = _engine(rng)
    gated = GatedResponses(eng)
    eng.start()
    a, = _one_batch_each(eng, gated, (0,))
    completer = eng._completer
    t0 = time.monotonic()
    eng.stop(drain_timeout_s=0.3)                  # a's readback never ends
    assert 0.25 < time.monotonic() - t0 < 2.0      # one budget, not two
    assert completer.is_alive() and not a.done()
    gated.open()
    completer.join(10.0)
    assert not completer.is_alive() and a.done()


# -- a batch closes when the pipeline can take it (ISSUE 35) ----------------
# The engine thread takes a slot, then pops what is queued at once; requests
# coalesce only while it is away.  ``max_wait_s`` is PATIENT throughout: a
# single timed wait would run every ``result(timeout=10)`` out.


def test_with_a_slot_free_a_lone_request_is_dispatched_without_waiting(
        rng, _fresh):
    eng, U, V = _engine(rng, max_wait_s=PATIENT)
    with eng:
        for u in (3, 4):            # the second finds a warm program
            t = eng.submit(u)
            s, ix = t.result(timeout=10.0)
        assert t.t_dequeue - t.t_submit < 0.5
        ref_s, ref_ix = _exact(U[4:5], V, np.ones(V.shape[0], bool), eng.k)
        np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(ix, ref_ix[0])
    recs = eng.batch_flight.records()
    assert [(r["closed_by"], r["rows"], r["waiting"], r["in_flight"],
             r["handoff_wait"]) for r in recs] == [("slot", 1, 1, 0, 0.0)] * 2
    for r in recs:
        assert r["spans"]["serve.batch.coalesce"] < 0.01 * PATIENT
        assert 0 <= r["head_wait"] < 0.5
    assert _closed_by(_fresh) == {"slot": 2}


def test_arrivals_while_both_slots_are_taken_ride_one_next_batch(
        rng, _fresh):
    eng, U, V = _engine(rng, max_wait_s=PATIENT)
    gated = GatedResponses(eng)
    with eng:
        a, b = _one_batch_each(eng, gated, (0, 1))  # a batch each: alone
        later = [eng.submit(j) for j in range(2, 7)]
        time.sleep(0.2)
        # both slots taken: the five wait in the queue, for a slot and
        # not for each other
        assert len(gated.gates) == 2
        assert all(t.t_dequeue is None for t in later)
        gated.release(0)
        gated.wait_dispatched(3)                    # a completed: ALL five
        assert len({t.t_dequeue for t in later}) == 1
        last = eng.submit(7)                        # b still in flight
        time.sleep(0.1)
        assert last.t_dequeue is None
        gated.open()
        tickets = [a, b] + later + [last]
        ref_s, ref_ix = _exact(U[:8], V, np.ones(V.shape[0], bool), eng.k)
        for j, t in enumerate(tickets):
            s, ix = t.result(timeout=10.0)
            np.testing.assert_allclose(s, ref_s[j], rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(ix, ref_ix[j])
        done = [t.t_done for t in tickets]
        assert done == sorted(done)                 # answered in order
    recs = eng.batch_flight.records()
    assert [(r["rows"], r["waiting"], r["closed_by"]) for r in recs] == [
        (1, 1, "slot"), (1, 1, "slot"), (5, 5, "slot"), (1, 1, "slot")]
    assert [r["in_flight"] for r in recs[:3]] == [0, 1, 1]
    assert recs[0]["handoff_wait"] == recs[1]["handoff_wait"] == 0.0
    # the queue did the coalescing, and the record says so
    assert 0.15 < recs[2]["handoff_wait"] < 5.0
    assert 0.15 < recs[2]["head_wait"] < 5.0
    assert all(r["spans"]["serve.batch.coalesce"] < 0.01 * PATIENT
               for r in recs)
    assert _closed_by(_fresh) == {"slot": 4}


@pytest.mark.parametrize("how", ["full", "closed", "rung_below"])
def test_full_and_closed_close_the_engines_batches_as_before(
        rng, _fresh, how):
    """40 requests queue behind two taken slots.  The largest bucket
    fills (``full``), or ``stop`` drains what is there (``closed``); on
    the default ladder, where 40 rows would ride bucket 128 a third
    full, the bucket below goes full and the other 8 ride the next."""
    eng, _, _ = _engine(rng, max_wait_s=PATIENT, buckets=(
        (8, 32, 128) if how == "rung_below" else (8, 32)))
    gated = GatedResponses(eng)
    eng.start()
    a, b = _one_batch_each(eng, gated, (0, 1))
    later = [eng.submit(j % 40) for j in range(3 if how == "closed" else 40)]
    if how == "closed":
        stopper = threading.Thread(target=eng.stop)
        stopper.start()
        time.sleep(0.05)
        gated.open()
        stopper.join(10.0)
        assert not stopper.is_alive() and all(t.done() for t in later)
        want = [(1, "slot"), (1, "slot"), (3, "closed")]
    else:
        gated.open()
        for t in later:
            t.result(timeout=10.0)
        eng.stop()
        want = [(1, "slot"), (1, "slot"),
                (32, "full" if how == "full" else "slot"), (8, "slot")]
        done = [t.t_done for t in later]
        assert done == sorted(done)                 # answered in order
    recs = eng.batch_flight.records()
    assert [(r["rows"], r["closed_by"]) for r in recs] == want
    assert sum(_closed_by(_fresh).values()) == len(want)
    assert not {"age", "wait"} & set(_closed_by(_fresh))


@pytest.mark.parametrize("what", ["expired", "shed"])
def test_expiry_and_shedding_behind_two_taken_slots(rng, _fresh, what):
    """Neither moved: a request whose deadline passes while it waits for
    a slot is failed where its batch is staged, its neighbours answered;
    a full queue refuses at admission."""
    eng, _, _ = _engine(rng, max_wait_s=PATIENT, max_queue=3)
    gated = GatedResponses(eng)
    with eng:
        _one_batch_each(eng, gated, (0, 1))
        if what == "expired":
            dead = eng.submit(2, deadline_s=0.05)
            alive = eng.submit(3)
            time.sleep(0.1)
            gated.open()
            with pytest.raises(DeadlineExceeded):
                dead.result(timeout=10.0)
            assert alive.result(timeout=10.0)[0].shape == (5,)
            assert dead.t_dequeue == alive.t_dequeue    # one batch
            assert _fresh.counter_value("serving.expired") == 1
            assert eng.batch_flight.records()[2]["rows"] == 1
        else:
            queued = [eng.submit(j) for j in (2, 3, 4)]
            with pytest.raises(Overloaded):
                eng.submit(5)
            assert _fresh.counter_value("serving.shed") == 1
            gated.open()
            assert all(t.result(timeout=10.0) for t in queued)
            assert eng.batch_flight.records()[2]["rows"] == 3


@pytest.mark.parametrize("rows", [1, 5, 20, 32])
def test_threads_and_serve_batch_answer_bit_equal(rng, rows):
    """The same batch through the two threads and through a synchronous
    ``serve_batch``: one implementation, so the same bits."""
    answers = []
    for threaded in (False, True):
        eng = ServingEngine(k=5, buckets=(8, 32), shortlist_k=32,
                            max_wait_s=0.3)
        r = np.random.default_rng(7)
        U = r.normal(size=(40, 8)).astype(np.float32)
        V = r.normal(size=(300, 8)).astype(np.float32)
        eng.publish(U, V)
        payloads = [j if j % 3 else U[j] * 0.5 for j in range(rows)]
        if threaded:
            # admitted before the threads start: the engine thread finds
            # them all queued and pops them as ONE batch (with the loop
            # running, the first would ride alone: ISSUE 35)
            tickets = [eng.submit(p) for p in payloads]
            with eng:
                got = [t.result(timeout=10.0) for t in tickets]
        else:
            tickets = [eng.submit(p) for p in payloads]
            eng.serve_batch(eng.batcher.next_batch(timeout=1.0))
            got = [t.result(timeout=0) for t in tickets]
        rec, = eng.batch_flight.records()
        assert rec["rows"] == rows and rec["in_flight"] == 0
        assert rec["closed_by"] == (
            "full" if rows == 32 else "slot" if threaded else "wait")
        answers.append(got)
    for (s0, i0), (s1, i1) in zip(*answers):
        np.testing.assert_array_equal(s0, s1)
        np.testing.assert_array_equal(i0, i1)


def test_many_submitters_against_the_two_threads(rng):
    """Stress: more submitting threads than cores, the interpreter
    changing hands every 100 us.  Every request is answered with its own
    user's scores, never more than two batches are in flight, and
    batches complete in the order they were dispatched."""
    eng = ServingEngine(k=5, buckets=(8, 32), shortlist_k=32,
                        max_wait_s=0.0005, flight_capacity=1 << 14)
    U = rng.normal(size=(40, 8)).astype(np.float32)
    V = rng.normal(size=(300, 8)).astype(np.float32)
    eng.publish(U, V)
    eng.warmup()
    ref_s, ref_ix = _exact(U, V, np.ones(300, bool), 5)
    peak, errors = [0], []
    finish = eng._finish

    def watching(flown, *args):
        peak[0] = max(peak[0], eng._handed - eng._completed)
        return finish(flown, *args)

    eng._finish = watching
    per_thread, n_threads = 150, 2 * (os.cpu_count() or 4)

    def ask(seed):
        r = np.random.default_rng(seed)
        try:
            for _ in range(per_thread):
                u = int(r.integers(40))
                s, ix = eng.recommend(u, timeout=30.0)
                np.testing.assert_allclose(s, ref_s[u], rtol=1e-5,
                                           atol=1e-6)
                np.testing.assert_array_equal(ix, ref_ix[u])
        except Exception as e:      # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        with eng:
            threads = [threading.Thread(target=ask, args=(j,))
                       for j in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120.0)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    recs = eng.batch_flight.records()
    assert sum(r["rows"] for r in recs) == per_thread * n_threads
    assert [r["batch"] for r in recs] == list(range(1, len(recs) + 1))
    assert {r["in_flight"] for r in recs} <= {0, 1}
    assert 1 <= peak[0] <= 2
    assert eng._handed == eng._completed == len(recs)


# ---------------------------------------------------------------------------
# serve-bench CLI (the SLO report the acceptance criteria name)


def test_serve_bench_cli_reports_from_histograms(tmp_path, capsys):
    from tpu_als.cli import main

    bank = tmp_path / "BENCH_serve_test.json"
    main(["serve-bench", "--users", "300", "--items", "800",
          "--rank", "8", "--k", "5", "--shortlist-k", "32",
          "--qps", "400", "--duration", "0.25", "--slo-ms", "5000",
          "--foldin-frac", "0.2", "--buckets", "8,32",
          "--bench-json", str(bank)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "serve_e2e_p99_ms"
    assert out["value"] > 0 and out["p50_ms"] > 0
    assert out["scored"] > 0
    assert out["slo_met"] is True        # 5s SLO on a toy config
    assert 0.0 <= out["shed_rate"] <= 1.0
    banked = json.loads(bank.read_text())
    assert banked["banked_by"] == "tpu_als serve-bench"
    assert banked["banked_at"].endswith("+00:00")
    assert banked["value"] == out["value"]


def test_serve_bench_cli_exact_path(capsys):
    from tpu_als.cli import main

    main(["serve-bench", "--users", "100", "--items", "200",
          "--rank", "4", "--qps", "300", "--duration", "0.1",
          "--slo-ms", "5000", "--exact", "--buckets", "8"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["config"]["path"] == "exact"
    assert out["scored"] > 0


# ---------------------------------------------------------------------------
# flight recorder (per-request span breakdowns dumped on SLO breach)


def test_flight_recorder_ring_and_watermark(_fresh):
    from tpu_als.obs.trace import SPAN_KEYS, FlightRecorder

    fr = FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("ok", {"score": 0.001 * (i + 1)}, e2e_seconds=0.01)
    assert len(fr) == 4                       # bounded ring
    assert fr.dump("slo_breach") == 4
    evs = [e for e in _fresh._events if e["type"] == "flight_record"]
    # capacity evicted seqs 1-2; unknown span keys are dropped, the
    # record always carries the full SPAN_KEYS vocabulary
    assert [e["seq"] for e in evs] == [3, 4, 5, 6]
    assert all(set(e["spans"]) == set(SPAN_KEYS) for e in evs)
    assert all(e["trigger"] == "slo_breach" for e in evs)
    # monotonic watermark: a repeat trigger re-emits nothing
    assert fr.dump("slo_breach") == 0
    fr.record("ok", {"score": 1.0})
    assert fr.dump("shed") == 1               # only the new record
    evs = [e for e in _fresh._events if e["type"] == "flight_record"]
    assert len(evs) == 5 and evs[-1]["trigger"] == "shed"


def test_engine_slo_breach_dumps_span_breakdowns(rng, _fresh):
    """The acceptance shape: a forced breach (microsecond SLO) leaves
    the last N per-request traces in the obs trail, each with the full
    admission/queue_wait/score/respond breakdown."""
    eng, _, _ = _engine(rng, slo_s=1e-7)
    n = 10
    with eng:
        for j in range(n):
            eng.recommend(j, timeout=5.0)
    dumped = [e for e in _fresh._events if e["type"] == "flight_record"]
    assert all(e["trigger"] == "slo_breach" and e["status"] == "ok"
               and e["path"] == "int8" for e in dumped)
    # the same breach dumps the per-batch records beside the requests'
    evs = [e for e in dumped if "admission" in e["spans"]]
    batches = {e["batch"]: e for e in dumped if e not in evs}
    assert len(evs) >= 8
    for e in evs:
        for k in ("admission", "queue_wait", "score", "respond"):
            assert e["spans"][k] is not None and e["spans"][k] >= 0
        # rescore is fused into the int8 top-k kernel and was never
        # measured: the key is gone, not None
        assert "rescore" not in e["spans"]
        assert e["e2e_seconds"] > 0
        # the batch a slow request rode, and where that batch's time went
        b = batches[e["batch"]]
        assert set(b["spans"]) == set(obs.schema.SERVE_BATCH_SPAN_KEYS)
        assert b["rows"] >= 1 and b["bucket"] in (8, 32)
    # and the spans after the submit stamp compose the e2e they explain
    # (what is left is the batch's staging, between the dequeue and the
    # dispatch)
    spans = evs[-1]["spans"]
    parts = spans["queue_wait"] + spans["score"] + spans["respond"]
    staging = batches[evs[-1]["batch"]]["spans"]["serve.batch.stage"]
    assert parts <= evs[-1]["e2e_seconds"] + 1e-9
    assert evs[-1]["e2e_seconds"] <= parts + staging + 1e-3


def test_engine_loose_slo_dumps_nothing(rng, _fresh):
    eng, _, _ = _engine(rng, slo_s=60.0)
    with eng:
        eng.recommend(0, timeout=5.0)
    assert not [e for e in _fresh._events if e["type"] == "flight_record"]
    # recording is still always-on: the trace sits in the ring, undumped
    assert len(eng.flight) == 1


def test_engine_shed_dumps_flight_record(rng, _fresh):
    eng, _, _ = _engine(rng, max_queue=2)
    with pytest.raises(Overloaded):
        for _ in range(50):                   # engine loop not running
            eng.submit(0)
    evs = [e for e in _fresh._events if e["type"] == "flight_record"]
    assert len(evs) == 1
    assert evs[0]["status"] == "shed" and evs[0]["trigger"] == "shed"
    assert evs[0]["spans"]["admission"] is not None
    assert evs[0]["spans"]["score"] is None   # never reached the scorer


def test_engine_expired_ticket_flight_record(rng, _fresh):
    eng, _, _ = _engine(rng, slo_s=1e-7)
    t_dead = eng.submit(0, deadline_s=0.0)
    t_ok = eng.submit(1)
    time.sleep(0.01)
    _drain_one(eng)
    with pytest.raises(DeadlineExceeded):
        t_dead.result(timeout=1.0)
    t_ok.result(timeout=1.0)
    evs = [e for e in _fresh._events if e["type"] == "flight_record"]
    statuses = {e["status"] for e in evs}
    assert statuses == {"expired", "ok"}
    exp = next(e for e in evs if e["status"] == "expired")
    assert exp["spans"]["queue_wait"] is not None
    assert exp["spans"]["score"] is None


def test_serve_bench_forced_breach_emits_flight_records(capsys):
    """ISSUE acceptance: serve-bench under a forced SLO breach reports
    flight_record events covering at least the last 8 requests."""
    from tpu_als.cli import main

    main(["serve-bench", "--users", "100", "--items", "300",
          "--rank", "4", "--qps", "300", "--duration", "0.1",
          "--slo-ms", "0.000001", "--buckets", "8"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["slo_met"] is False
    assert out["scored"] >= 8
    assert out["flight_records"] >= min(out["scored"], 8)
