"""Live pipeline tests (tpu_als/live/ + the incremental index).

Three layers:

1. the DELTA-INDEX contract — ``with_updates``/``compact`` top-k is
   bitwise-equal to a full ``build_index`` rebuild of the same catalog
   (property matrix: touched-rows-only, append-only, mixed, second-
   generation merges, compaction, invalid rows, duplicate scores),
2. the engine's incremental publish modes
   (retag/delta/compact/full/none) and the live-path warmup,
3. the :class:`LiveUpdater` loop — admission + shed, quarantine,
   freshness measurement, SLO-breach flight dumps — plus the planner
   cadence and the bounded fold-in stats ring.
"""

import json
import time

import numpy as np
import pytest

import jax.numpy as jnp

from tests.conftest import GatedResponses
from tpu_als import obs, plan
from tpu_als.api.estimator import ALSModel
from tpu_als.core.ratings import IdMap
from tpu_als.live import LiveUpdater
from tpu_als.live.updater import LIVE_SPAN_KEYS
from tpu_als.obs.trace import FlightRecorder
from tpu_als.ops.topk import topk_validity
from tpu_als.serving import Overloaded, ServingEngine, build_index
from tpu_als.stream.microbatch import FoldInServer


@pytest.fixture(autouse=True)
def _fresh():
    reg = obs.reset()
    yield reg


# ---------------------------------------------------------------------------
# 1. the delta-index bitwise contract


def _assert_same_topk(idx, ref, U, k):
    """Scores bitwise-equal; indices equal wherever scores are unique
    (ties may legitimately resolve differently across kernels, but the
    tied index must still earn its score)."""
    s, ix = np.asarray(idx.topk(U, k)[0]), np.asarray(idx.topk(U, k)[1])
    rs, rix = np.asarray(ref.topk(U, k)[0]), np.asarray(ref.topk(U, k)[1])
    np.testing.assert_array_equal(s, rs)
    for row in range(s.shape[0]):
        real = topk_validity(s[row])
        if len(np.unique(s[row][real])) == real.sum():
            np.testing.assert_array_equal(ix[row][real], rix[row][real])


def _queries(rng, n, r):
    return jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))


@pytest.mark.parametrize("Ni,r,sk", [(64, 4, 16), (200, 8, 64),
                                     (33, 3, 8)])
def test_delta_touched_rows_only_matches_rebuild(rng, Ni, r, sk):
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    idx = build_index(V, shortlist_k=sk, seq=1)
    rows = np.sort(rng.choice(Ni, size=max(1, Ni // 8), replace=False))
    V2 = V.copy()
    V2[rows] = rng.normal(size=(len(rows), r)).astype(np.float32)
    upd = idx.with_updates(rows.astype(np.int64), V2[rows], seq=2)
    assert upd.delta_count == len(rows)
    assert idx.delta_count == 0          # the source index is untouched
    ref = build_index(V2, shortlist_k=sk, seq=2)
    _assert_same_topk(upd, ref, _queries(rng, 9, r), 5)


def test_delta_append_only_new_rows_matches_rebuild(rng):
    Ni, r = 80, 6
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    idx = build_index(V, shortlist_k=24, seq=1)
    V2 = np.concatenate(
        [V, rng.normal(size=(7, r)).astype(np.float32)])
    rows = np.arange(Ni, Ni + 7, dtype=np.int64)
    upd = idx.with_updates(rows, V2[rows], seq=2)
    assert upd.n_items == Ni + 7 and upd.n_base == Ni
    ref = build_index(V2, shortlist_k=24, seq=2)
    _assert_same_topk(upd, ref, _queries(rng, 6, r), 5)


def test_delta_mixed_and_second_generation_merge(rng):
    """Touched + appended in one update, then a SECOND update touching
    an overlapping set — the merged segment must still be newest-wins
    bitwise-equal to a rebuild."""
    Ni, r = 100, 5
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    idx = build_index(V, shortlist_k=32, seq=1)
    V2 = np.concatenate(
        [V, rng.normal(size=(4, r)).astype(np.float32)])
    rows1 = np.array([3, 50, 99, 100, 101, 102, 103], dtype=np.int64)
    V2[rows1[:3]] = rng.normal(size=(3, r)).astype(np.float32)
    g1 = idx.with_updates(rows1, V2[rows1], seq=2)
    V3 = V2.copy()
    rows2 = np.array([3, 7, 101], dtype=np.int64)   # overlaps gen 1
    V3[rows2] = rng.normal(size=(3, r)).astype(np.float32)
    g2 = g1.with_updates(rows2, V3[rows2], seq=3)
    assert g2.delta_count == len(set(rows1) | set(rows2))
    ref = build_index(V3, shortlist_k=32, seq=3)
    _assert_same_topk(g2, ref, _queries(rng, 8, r), 5)


def test_compact_is_bitwise_identical_to_rebuild(rng):
    """Compaction folds the segment back WITHOUT re-quantizing: the
    compacted base arrays must be byte-identical to a from-scratch
    rebuild of the same catalog (per-row quantization is row-local)."""
    Ni, r = 90, 4
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    idx = build_index(V, shortlist_k=16, seq=1)
    V2 = np.concatenate(
        [V, rng.normal(size=(5, r)).astype(np.float32)])
    rows = np.array([0, 17, 44, 89, 90, 91, 92, 93, 94], dtype=np.int64)
    V2[rows[:4]] = rng.normal(size=(4, r)).astype(np.float32)
    comp = idx.with_updates(rows, V2[rows], seq=2).compact(seq=3)
    assert comp.delta_count == 0 and comp.n_items == Ni + 5
    ref = build_index(V2, shortlist_k=16, seq=3)
    np.testing.assert_array_equal(np.asarray(comp.Vq),
                                  np.asarray(ref.Vq))
    np.testing.assert_array_equal(np.asarray(comp.sv),
                                  np.asarray(ref.sv))
    np.testing.assert_array_equal(np.asarray(comp.valid),
                                  np.asarray(ref.valid))
    np.testing.assert_array_equal(np.asarray(comp.V),
                                  np.asarray(ref.V))
    _assert_same_topk(comp, ref, _queries(rng, 7, r), 5)


def test_delta_invalid_rows_never_surface(rng):
    """Rows updated with valid_rows=False (retired items) must never
    appear in the top-k — matching a rebuild with the same mask."""
    Ni, r, k = 40, 4, 5
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    idx = build_index(V, shortlist_k=Ni, seq=1)
    rows = np.arange(0, 10, dtype=np.int64)
    mask2 = np.ones(Ni, dtype=bool)
    mask2[rows] = False
    upd = idx.with_updates(rows, V[rows],
                           valid_rows=np.zeros(10, bool), seq=2)
    ref = build_index(V, item_valid=mask2, shortlist_k=Ni, seq=2)
    U = _queries(rng, 6, r)
    _assert_same_topk(upd, ref, U, k)
    _, ix = upd.topk(U, k)
    assert not np.isin(np.asarray(ix), rows).any()


def test_delta_duplicate_scores_stay_bitwise_equal(rng):
    """Adversarial ties: identical rows live in both the base and the
    delta segment — scores must still be bitwise-equal to a rebuild."""
    Ni, r = 48, 4
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    V[24:] = V[:24]                      # every score duplicated
    idx = build_index(V, shortlist_k=Ni, seq=1)
    rows = np.arange(12, 36, dtype=np.int64)
    upd = idx.with_updates(rows, V[rows], seq=2)   # same values -> ties
    ref = build_index(V, shortlist_k=Ni, seq=2)
    _assert_same_topk(upd, ref, _queries(rng, 10, r), 6)


def test_delta_append_gap_raises(rng):
    V = rng.normal(size=(30, 4)).astype(np.float32)
    idx = build_index(V, shortlist_k=8, seq=1)
    with pytest.raises(ValueError, match="append gap"):
        idx.with_updates(np.array([33], dtype=np.int64),
                         rng.normal(size=(1, 4)).astype(np.float32))


def test_delta_input_duplicates_newest_wins(rng):
    V = rng.normal(size=(30, 4)).astype(np.float32)
    idx = build_index(V, shortlist_k=8, seq=1)
    old = rng.normal(size=(1, 4)).astype(np.float32)
    new = rng.normal(size=(1, 4)).astype(np.float32)
    upd = idx.with_updates(np.array([5, 5], dtype=np.int64),
                           np.concatenate([old, new]), seq=2)
    assert upd.delta_count == 1
    V2 = V.copy()
    V2[5] = new[0]
    ref = build_index(V2, shortlist_k=8, seq=2)
    _assert_same_topk(upd, ref, _queries(rng, 4, 4), 5)


def test_retag_shares_arrays_and_quantizes_nothing(rng):
    V = rng.normal(size=(30, 4)).astype(np.float32)
    idx = build_index(V, shortlist_k=8, seq=1)
    tagged = idx.retag(7)
    assert tagged.seq == 7 and idx.seq == 1
    assert tagged.Vq is idx.Vq and tagged.sv is idx.sv


def test_nbytes_quantized_counts_the_delta(rng):
    V = rng.normal(size=(30, 4)).astype(np.float32)
    idx = build_index(V, shortlist_k=8, seq=1)
    upd = idx.with_updates(np.arange(6, dtype=np.int64),
                           V[:6], seq=2)
    assert upd.nbytes_quantized() > idx.nbytes_quantized()


def test_live_delta_index_contract_is_registered():
    from tpu_als.analysis import contracts

    assert "live_delta_index" in contracts.names()
    res = contracts.verify("live_delta_index")
    assert res.ok, res


# ---------------------------------------------------------------------------
# 2. engine incremental publish


def _published_engine(rng, n=24, Ni=300, r=6, k=5):
    eng = ServingEngine(k=k, buckets=(8,), shortlist_k=32,
                        max_wait_s=0.0)
    U = rng.normal(size=(n, r)).astype(np.float32)
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    eng.publish(U, V)
    return eng, U, V


def test_publish_update_retag_delta_compact_modes(rng, _fresh):
    eng, U, V = _published_engine(rng)
    seq0 = eng.published_seq
    # user-only fold-in: nothing in the catalog changed -> retag
    seq, mode = eng.publish_update(U * 1.01, V)
    assert (seq, mode) == (seq0 + 1, "retag")
    # touched items -> delta segment, O(touched) re-quantization
    V2 = V.copy()
    V2[:8] = rng.normal(size=(8, V.shape[1])).astype(np.float32)
    seq, mode = eng.publish_update(U, V2, touched_items=np.arange(8))
    assert mode == "delta"
    assert eng.published_index.delta_count == 8
    # crossing the planner cadence's threshold folds the segment back
    cad = plan.resolve_live_cadence()
    n_big = int(max(cad["compact_min_rows"],
                    cad["compact_delta_frac"] * 300)) + 8
    V3 = V2.copy()
    V3[:n_big] = rng.normal(size=(n_big, V.shape[1])).astype(np.float32)
    seq, mode = eng.publish_update(U, V3,
                                   touched_items=np.arange(n_big))
    assert mode == "compact"
    assert eng.published_index.delta_count == 0
    # every mode priced in the publish histogram, trail carries modes
    pubs = [e for e in _fresh._events if e["type"] == "serving_publish"]
    assert [e["mode"] for e in pubs[-3:]] == ["retag", "delta",
                                              "compact"]
    priced = sum(
        _fresh.histogram_count("serving.publish_seconds", mode=m)
        for m in ("full", "retag", "delta", "compact", "none"))
    assert priced >= 4


@pytest.mark.parametrize("publishes", [1, 3])
def test_publish_between_two_batches_in_flight_gives_each_its_generation(
        rng, _fresh, publishes):
    """Batch A is dispatched and its readback held; ``publishes`` row
    writes rewrite user 3 (each donates the table A was dispatched
    with); batch B is dispatched behind them while A is still in
    flight.  A answers from the generation it was dispatched with, B
    reads the writes, and the untouched user's answers are bit-equal."""
    eng, U, V = _published_engine(rng)
    gated = GatedResponses(eng)
    with eng:
        # both requests into ONE batch: with a slot free the engine
        # thread pops what is queued at once (ISSUE 35), so the pair is
        # admitted under the queue's own lock
        with eng.batcher._cond:
            A = [eng.submit(3), eng.submit(5)]
        gated.wait_dispatched(1)
        assert gated.gates[0].entered.wait(10.0)
        U2 = U.copy()
        for g in range(1, publishes + 1):
            U2[3] = -g * U[3]
            seq, mode = eng.publish_update(U2, V, touched_users=[3])
            assert mode == "retag"
        with eng.batcher._cond:
            B = [eng.submit(3), eng.submit(5)]
        gated.wait_dispatched(2)        # behind the writes, A not read back
        assert not A[0].done()
        gated.open()
        (sa3, ia3), (sa5, ia5) = (t.result(timeout=10.0) for t in A)
        (sb3, ib3), (sb5, ib5) = (t.result(timeout=10.0) for t in B)
    assert _fresh.counter_value("serving.user_table_writes",
                                how="inplace") == publishes
    np.testing.assert_array_equal(sa5, sb5)         # untouched: the same
    np.testing.assert_array_equal(ia5, ib5)         # bits from both
    full = V.astype(np.float64) @ U[3].astype(np.float64)
    np.testing.assert_allclose(sa3, np.sort(full)[::-1][:5], rtol=1e-4)
    np.testing.assert_allclose(                     # read-your-writes
        sb3, np.sort(-publishes * full)[::-1][:5], rtol=1e-4)
    assert not np.array_equal(ia3, ib3)
    # each ticket names the generation that answered it
    assert {t.seq for t in A} == {seq - publishes}
    assert {t.seq for t in B} == {seq}
    recs = eng.batch_flight.records()
    assert [(r["rows"], r["in_flight"], r["closed_by"]) for r in recs] == [
        (2, 0, "slot"), (2, 1, "slot")]


def test_publish_update_delta_serves_bitwise_vs_rebuild(rng):
    eng, U, V = _published_engine(rng)
    V2 = V.copy()
    V2[5:15] = rng.normal(size=(10, V.shape[1])).astype(np.float32)
    eng.publish_update(U, V2, touched_items=np.arange(5, 15))
    idx = eng.published_index
    assert idx.delta_count == 10
    ref = build_index(V2, shortlist_k=idx.shortlist_k, seq=idx.seq)
    _assert_same_topk(idx, ref, _queries(rng, 8, V.shape[1]), eng.k)


def test_publish_update_malformed_update_falls_back_full(rng, _fresh):
    eng, U, V = _published_engine(rng)
    # a touched row beyond the catalog with the gap never filled is a
    # caller bug: the engine must refuse the delta and rebuild
    seq, mode = eng.publish_update(
        U, V, touched_items=np.array([V.shape[0] + 3]))
    assert mode == "full"
    warn = [e for e in _fresh._events if e["type"] == "warning"
            and e.get("what") == "serving.publish_update"]
    assert warn and "outside the catalog" in warn[-1]["reason"]


def test_publish_update_without_usable_index_is_full(rng):
    eng = ServingEngine(k=5, buckets=(8,), shortlist_k=32,
                        max_wait_s=0.0)
    U = rng.normal(size=(10, 4)).astype(np.float32)
    V = rng.normal(size=(60, 4)).astype(np.float32)
    eng.publish(U, V, quantize=False)       # serving exact: no index
    seq, mode = eng.publish_update(U, V)
    assert mode == "full"
    assert eng.published_index.seq == seq


def test_publish_update_tiny_catalog_is_none(rng):
    eng = ServingEngine(k=5, buckets=(8,), shortlist_k=32,
                        max_wait_s=0.0)
    U = rng.normal(size=(4, 3)).astype(np.float32)
    V = rng.normal(size=(3, 3)).astype(np.float32)
    eng.publish(U, V)
    seq, mode = eng.publish_update(U, V)
    assert mode == "none" and eng.published_index is None


def test_warmup_live_precompiles_without_touching_the_index(rng):
    eng, U, V = _published_engine(rng, Ni=80)
    idx, seq = eng.published_index, eng.published_seq
    Q = _queries(rng, 4, V.shape[1])
    s0, i0 = (np.asarray(a) for a in idx.topk(Q, eng.k))
    eng.warmup_live(max_delta_rows=4)
    # warmup publishes nothing: the same generation, the same catalog,
    # the same answers — with spare rows and an empty segment under it
    live = eng.published_index
    assert (eng.published_seq, live.seq, live.n_items) == (seq, seq, 80)
    assert live.delta_count == 0 and live.delta_slots >= 4
    assert live.n_base > 80 and live.V.shape[0] == live.n_base
    s1, i1 = (np.asarray(a) for a in live.topk(Q, eng.k))
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(i0, i1)
    # the delta path it warmed serves correctly afterwards
    V2 = V.copy()
    V2[:3] = rng.normal(size=(3, V.shape[1])).astype(np.float32)
    eng.publish_update(U, V2, touched_items=np.arange(3))
    ref = build_index(V2, shortlist_k=idx.shortlist_k,
                      seq=eng.published_seq)
    _assert_same_topk(eng.published_index, ref,
                      _queries(rng, 4, V.shape[1]), eng.k)


# ---------------------------------------------------------------------------
# 3. the LiveUpdater loop


def _live_stack(rng, users=24, items=20, r=4, k=5, **updater_kw):
    U = rng.normal(size=(users, r)).astype(np.float32)
    V = rng.normal(size=(items, r)).astype(np.float32)
    model = ALSModel(
        r, IdMap(ids=np.arange(users)), IdMap(ids=np.arange(items)),
        U, V, {"userCol": "u", "itemCol": "i", "ratingCol": "rt",
               "regParam": 0.05, "implicitPrefs": False,
               "alpha": 1.0, "nonnegative": False})
    eng = ServingEngine(k=k, buckets=(8,), shortlist_k=16,
                        max_wait_s=0.0)
    eng.publish(U, V)
    srv = FoldInServer(model)
    upd = LiveUpdater(eng, srv, max_batch=8, max_wait_ms=5.0,
                      **updater_kw)
    return upd, eng, srv, model


def _drain(upd, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while upd.queue_depth and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)


def test_updater_folds_publishes_and_measures_freshness(rng, _fresh):
    upd, eng, srv, model = _live_stack(rng, fold_items=True)
    with upd:
        for j in range(12):
            upd.submit(j % 24, j % 20, 3.0)
        upd.submit(3, 777, 4.5)             # a NEW catalog item
        _drain(upd)
    assert _fresh.histogram_count("live.freshness_seconds") == 13
    ups = [e for e in _fresh._events if e["type"] == "live_update"]
    assert ups and all(e["mode"] in ("retag", "delta", "compact")
                       for e in ups)
    assert sum(e["events"] for e in ups) == 13
    assert eng.published_index.n_items == 21    # the append is servable
    # both fold directions count their ratings (user side sees all 13;
    # the item side sees them again)
    assert _fresh.counter_value("foldin.ratings") >= 13


def test_updater_quarantines_poison_before_the_factors(rng, _fresh):
    upd, eng, srv, model = _live_stack(rng)
    U_before = np.asarray(model._U).copy()
    with upd:
        upd.submit(0, 0, float("nan"))
        upd.submit(1, 1, float("inf"))
        upd.submit(2, 2, 1e9)               # out of range
        _drain(upd)
    assert _fresh.counter_value("ingest.quarantined_rows") == 3
    q = [e for e in _fresh._events if e["type"] == "ingest_quarantined"]
    assert q and q[0]["path"] == "live"
    assert sum(e["rows"] for e in q) == 3
    # an all-poison batch folds nothing: the factors are untouched
    np.testing.assert_array_equal(np.asarray(model._U), U_before)
    assert _fresh.counter_value("foldin.ratings") == 0


def test_updater_sheds_at_capacity_with_typed_overload(rng, _fresh):
    upd, *_ = _live_stack(rng, max_queue=2)
    # not started: the queue cannot drain, so capacity is deterministic
    upd.submit(0, 0, 1.0)
    upd.submit(1, 1, 1.0)
    with pytest.raises(Overloaded):
        upd.submit(2, 2, 1.0)
    assert _fresh.counter_value("live.shed") == 1


def test_updater_submit_after_stop_raises(rng):
    upd, *_ = _live_stack(rng)
    upd.start()
    upd.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        upd.submit(0, 0, 1.0)


def test_updater_slo_breach_emits_and_dumps_flight_ring(rng, _fresh):
    upd, *_ = _live_stack(rng, fold_items=True, slo_s=1e-9)
    with upd:
        upd.submit(0, 0, 3.0)
        upd.submit(1, 3, 2.0)
        _drain(upd)
    breaches = [e for e in _fresh._events
                if e["type"] == "live_freshness_breach"]
    assert breaches
    assert breaches[0]["freshness_seconds"] > breaches[0]["slo_s"]
    dumps = [e for e in _fresh._events if e["type"] == "flight_record"
             and e.get("trigger") == "freshness_breach"]
    assert dumps
    for d in dumps:
        assert set(d["spans"]) == set(LIVE_SPAN_KEYS)
        assert d["spans"]["foldin"] is not None


def _wait_for(pred, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while not pred() and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert pred(), "condition not reached before timeout"


def test_updater_loop_survives_processing_errors(rng, _fresh):
    """Queue drain is NOT processing completion, so each step waits on
    the obs trail itself before mutating the fold path."""
    upd, eng, srv, model = _live_stack(rng)

    def _warns():
        return [e for e in _fresh._events if e["type"] == "warning"
                and e.get("what") == "live.update"]

    with upd:
        upd.submit(0, 0, 3.0)
        _wait_for(lambda: _fresh.histogram_count(
            "live.freshness_seconds") == 1)
        srv.model = None                    # sabotage the fold path
        upd.submit(1, 1, 3.0)
        _wait_for(lambda: len(_warns()) >= 1)
        srv.model = model                   # and the loop still serves
        upd.submit(2, 2, 3.0)
        _wait_for(lambda: _fresh.histogram_count(
            "live.freshness_seconds") == 2)
    assert _warns()
    assert _fresh.histogram_count("live.freshness_seconds") == 2


def test_foldin_stats_ring_is_bounded(rng):
    upd, eng, srv, model = _live_stack(rng)
    srv.stats = type(srv.stats)(maxlen=3)
    for j in range(6):
        srv.update({"u": np.array([j % 24]), "i": np.array([j % 20]),
                    "rt": np.array([3.0], dtype=np.float32)})
    assert len(srv.stats) == 3
    srv2 = FoldInServer(model, stats_window=5)
    assert srv2.stats.maxlen == 5


def test_resolve_live_cadence_defaults_and_overrides():
    cad = plan.resolve_live_cadence()
    assert set(cad) == set(plan.DEFAULT_LIVE_CADENCE)
    assert cad["max_batch"] >= 1 and cad["max_wait_ms"] > 0
    merged = plan.resolve_live_cadence(requested={"max_batch": 7})
    assert merged["max_batch"] == 7
    assert merged["compact_min_rows"] == cad["compact_min_rows"]


def test_flight_recorder_custom_span_keys():
    fr = FlightRecorder(4, span_keys=("alpha", "beta"))
    fr.record("ok", {"alpha": 0.5}, note=1)
    fr.dump("test_trigger")
    recs = [e for e in obs.default_registry()._events
            if e["type"] == "flight_record"]
    assert recs and set(recs[0]["spans"]) == {"alpha", "beta"}
    assert recs[0]["spans"]["beta"] is None


# ---------------------------------------------------------------------------
# serve-bench --update-qps (the live SLO report)


def test_serve_bench_cli_live_mode_reports_freshness(tmp_path, capsys):
    from tpu_als.cli import main

    bank = tmp_path / "BENCH_live_test.json"
    main(["serve-bench", "--users", "64", "--items", "48",
          "--rank", "4", "--k", "5", "--shortlist-k", "16",
          "--qps", "30", "--duration", "0.4", "--slo-ms", "5000",
          "--buckets", "8",
          "--update-qps", "50", "--update-items",
          "--update-poison-frac", "0.1",
          "--update-max-batch", "8", "--update-max-wait-ms", "10",
          "--freshness-slo-ms", "30000",
          "--bench-json", str(bank)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "live_freshness_p99_ms"
    assert out["value"] > 0 and out["slo_met"] is True
    assert out["live"]["events_scored"] > 0
    assert out["live"]["quarantined_rows"] >= 1
    assert set(out["live"]["publish_modes"]) <= {"retag", "delta",
                                                 "compact"}
    assert out["live"]["publish_delta_ms"] > 0
    assert out["serve"]["p99_ms"] > 0
    banked = json.loads(bank.read_text())
    assert banked["banked_at"].endswith("+00:00")
    assert banked["metric"] == "live_freshness_p99_ms"
