"""Recommend only what the user has not rated (PR 39): the engine through
``submit`` against the plain float64 reference
(``benchmark/reference/topk_unseen.py``) at small size — by id, by vector
with ``exclude``, both at once, on all three buckets; histories shorter
and longer than the shortlist and longer than the catalog less k; an
excluded id that is its block's maximum, a winning block excluded whole,
ties across an excluded id; the exact fallback; histories swapped with
the generation; what is refused.  ZERO excluded ids returned in every
case."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import topk_unseen as ref  # noqa: E402
from tpu_als import obs  # noqa: E402
from tpu_als.ops.topk import (  # noqa: E402
    NEG_INF,
    NOT_AN_ID,
    chunked_topk_scores,
    excluded_mask,
    exclusion_plan,
    shortlist_plan,
    topk_validity,
)
from tpu_als.resilience import faults  # noqa: E402
from tpu_als.serving import engine as engine_mod  # noqa: E402
from tpu_als.serving.engine import MAX_EXCLUDE, ServingEngine  # noqa: E402
from tpu_als.serving.index import (  # noqa: E402
    SCORE_ULPS,
    Int8CandidateIndex,
    _topk_jit,
)

K, SK = 10, 64
N_USERS, N_ITEMS, RANK = 160, 36_864, 16   # 288 blocks of 128: two stages
BUCKETS = (8, 32, 128)
ROWS = {8: 5, 32: 20, 128: 70}             # requests that ride each bucket


def csr(histories):
    indptr = np.concatenate([[0], np.cumsum([len(h) for h in histories])])
    indices = (np.concatenate([np.sort(h) for h in histories])
               if len(histories) else np.empty(0))
    return indptr.astype(np.int64), indices.astype(np.int32)


@pytest.fixture(scope="module")
def factors():
    rng = np.random.default_rng(39)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    return U, V, (U.astype(np.float64) @ V.astype(np.float64).T)


@pytest.fixture(scope="module")
def eng():
    """One engine for the module: its programs compile once (by the jit
    call: no ``warmup``), and a test publishes the histories it needs."""
    assert shortlist_plan(N_ITEMS, SK).stages == 2
    return ServingEngine(k=K, buckets=BUCKETS, shortlist_k=SK)


def top_histories(scores, lengths):
    """Each user's history: that user's ``length`` BEST items — what a
    fitted model ranks highest, and the worst case for the shortlist."""
    return [np.argsort(-scores[u], kind="stable")[:n]
            for u, n in enumerate(lengths)]


def ask(eng, requests):
    """Submit ``[(payload, exclude)]`` at once, serve them as ONE batch on
    the caller's thread, return ``[(scores, ids)]``."""
    tickets = [eng.submit(p, exclude=e) for p, e in requests]
    batch = eng.batcher.next_batch(timeout=0, coalesce=False)
    assert len(batch) == len(requests)
    eng.serve_batch(batch)
    return [t.result(timeout=0) for t in tickets]


def held_to_reference(answers, Q, V, excluded, min_recall=1.0):
    scores = np.stack([a[0] for a in answers])
    ids = np.stack([a[1] for a in answers])
    real = np.asarray(topk_validity(scores))
    assert ref.seen_returned(ids, excluded, real) == 0
    ref_s, ref_i = ref.exact_topk(Q, V, K, excluded)
    assert ref.recall(np.where(real, ids, -1), ref_i) >= min_recall
    own = ref.own_scores(Q, V, np.where(real, ids, 0))
    assert np.abs(np.where(real, scores - own, 0)).max() < 1e-4
    assert (np.diff(scores, axis=1) <= 0).all()     # the sentinel is least
    # a slot is real exactly where the reference has an id left for it
    assert (real == (ref_i >= 0)).all()
    return ids, ref_i


@pytest.mark.parametrize("bucket", BUCKETS)
def test_by_id_by_vector_and_both_against_the_reference(eng, factors, bucket):
    U, V, scores = factors
    rng = np.random.default_rng(bucket)
    hist = top_histories(scores, rng.integers(0, 200, N_USERS))
    eng.publish(U, V, user_seen=csr(hist))
    requests, Q, excluded = [], [], []
    for j in range(ROWS[bucket]):
        u = int(rng.integers(0, N_USERS))
        own = np.argsort(-scores[u])[200:200 + int(rng.integers(1, 65))]
        if j % 3 == 0:          # by id: the resident history
            requests.append((u, None))
            excluded.append(hist[u])
        elif j % 3 == 1:        # by vector with its own list
            requests.append((U[u], own))
            excluded.append(own)
        else:                   # both at once
            requests.append((u, own))
            excluded.append(np.concatenate([hist[u], own]))
        Q.append(U[u])
    answers = ask(eng, requests)
    ids, ref_i = held_to_reference(answers, np.stack(Q), V, excluded, 0.99)
    rec = eng.batch_flight.records()[-1]
    assert rec["bucket"] == bucket and rec["path"] == "int8"


@pytest.mark.parametrize("length", [0, 1, K, SK - K + 1, 2 * SK, 500])
def test_a_history_of_the_users_best_items(eng, factors, length):
    """Top 64 then filter would be right up to 64 - 10 ids and wrong from
    55 on: the answer is the next ten whatever the length."""
    U, V, scores = factors
    hist = top_histories(scores, [length] * N_USERS)
    eng.publish(U, V, user_seen=csr(hist))
    users = [3, 77, 159]
    answers = ask(eng, [(u, None) for u in users])
    ids, _ = held_to_reference(answers, U[users], V,
                               [hist[u] for u in users], 0.99)
    for u, got in zip(users, ids):
        want = np.argsort(-scores[u], kind="stable")[length:length + K]
        assert len(set(got) & set(want)) >= K - 1


def test_a_history_longer_than_the_catalog_less_k_leaves_sentinels(factors):
    U, V, scores = factors
    small = V[:300]
    hist = [np.arange(300)[np.arange(300) % 60 != u % 60][:295]
            for u in range(N_USERS)]
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, small, user_seen=csr(hist))
    (s, i), = ask(eng, [(5, None)])
    left = np.setdiff1d(np.arange(300), hist[5])
    assert len(left) == 5
    real = np.asarray(topk_validity(s))
    assert real.sum() == 5 and set(i[real]) == set(left)
    assert (s[~real] == np.float32(NEG_INF)).all()
    held_to_reference([(s, i)], U[[5]], small, [hist[5]])
    # and the request's own list takes the rest: nothing is left
    (s, i), = ask(eng, [(5, left)])
    assert not np.asarray(topk_validity(s)).any()


def test_an_excluded_id_that_is_its_blocks_maximum(eng, factors):
    U, V, scores = factors
    u = 11
    best = int(np.argmax(scores[u]))
    block = np.arange(best // 128 * 128, best // 128 * 128 + 128)
    second = int(block[np.argsort(-scores[u][block])[1]])
    hist = [np.array([best]) if v == u else np.empty(0, int)
            for v in range(N_USERS)]
    eng.publish(U, V, user_seen=csr(hist))
    (s, i), = ask(eng, [(u, None)])
    assert best not in i
    if second in np.argsort(-scores[u])[1:K + 1]:
        assert second in i      # the block's next best is still found
    held_to_reference([(s, i)], U[[u]], V, [hist[u]])


def test_all_of_a_winning_block_excluded(eng, factors):
    U, V, scores = factors
    u = 23
    best = int(np.argmax(scores[u]))
    block = np.arange(best // 128 * 128, best // 128 * 128 + 128)
    hist = [block if v == u else np.empty(0, int) for v in range(N_USERS)]
    eng.publish(U, V, user_seen=csr(hist))
    (s, i), = ask(eng, [(u, None)])
    assert not set(i) & set(block)
    held_to_reference([(s, i)], U[[u]], V, [hist[u]])


@pytest.mark.parametrize("quantize", [True, False])
def test_ties_across_an_excluded_id(factors, quantize):
    """Three identical items tie; the middle one is excluded: the two
    that are left come back, the lower id first."""
    U, V, scores = factors
    u = 31
    V = V.copy()
    a, b, c = 1000, 1001, 1002
    V[[a, b, c]] = U[u] / np.linalg.norm(U[u])     # the three best, tied
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, V, quantize=quantize)
    (s, i), = ask(eng, [(u, [b])])
    assert list(i[:2]) == [a, c] and s[0] == s[1] and b not in i
    (s, i), = ask(eng, [(u, [a])])
    assert list(i[:2]) == [b, c]


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_exact_fallback_excludes(eng, factors, bucket):
    U, V, scores = factors
    rng = np.random.default_rng(100 + bucket)
    hist = top_histories(scores, rng.integers(0, 300, N_USERS))
    eng.publish(U, V, quantize=False, user_seen=csr(hist))
    users = [int(u) for u in rng.integers(0, N_USERS, ROWS[bucket])]
    answers = ask(eng, [(u, None) for u in users])
    ids, ref_i = held_to_reference(answers, U[users], V,
                                   [hist[u] for u in users])
    assert (ids == ref_i).all()
    assert eng.batch_flight.records()[-1]["path"] == "exact"


def test_a_stale_index_falls_back_and_still_excludes(eng, factors):
    U, V, scores = factors
    hist = top_histories(scores, [120] * N_USERS)
    eng.publish(U, V, user_seen=csr(hist))
    before = obs.counter_value("serving.fallback_exact") or 0
    faults.install("serving.score=corrupt@nth=1")
    try:
        answers = ask(eng, [(7, None), (U[8], hist[8][:64])])
    finally:
        faults.clear()
    assert obs.counter_value("serving.fallback_exact") == before + 2
    held_to_reference(answers, U[[7, 8]], V, [hist[7], hist[8][:64]])


def test_a_second_publish_swaps_the_histories_with_the_generation(
        eng, factors):
    U, V, scores = factors
    first = top_histories(scores, [30] * N_USERS)
    second = [np.argsort(-scores[u], kind="stable")[5:40]
              for u in range(N_USERS)]
    eng.publish(U, V, user_seen=csr(first))
    (_, a), = ask(eng, [(9, None)])
    eng.publish(U, V, user_seen=csr(second))
    (_, b), = ask(eng, [(9, None)])
    best = np.argsort(-scores[9], kind="stable")
    assert not set(a) & set(first[9]) and not set(b) & set(second[9])
    assert set(best[:5]) <= set(b)      # the first five are servable again
    eng.publish(U, V)                   # and no histories: nothing excluded
    (_, c), = ask(eng, [(9, None)])
    assert list(c) == list(best[:K])


def test_the_pipelined_engine_excludes(factors):
    U, V, scores = factors
    rng = np.random.default_rng(5)
    hist = top_histories(scores, rng.integers(0, 150, N_USERS))
    eng = ServingEngine(k=K, buckets=(8, 32), shortlist_k=SK)
    eng.publish(U, V, user_seen=csr(hist))
    eng.warmup()
    users = [int(u) for u in rng.integers(0, N_USERS, 60)]
    with eng:
        tickets = [eng.submit(u) for u in users]
        answers = [t.result(timeout=30) for t in tickets]
    held_to_reference(answers, U[users], V, [hist[u] for u in users], 0.99)
    assert all(len(key) == 3 for key in eng._pinned)


def test_warmup_pins_and_announces_the_programs_that_exclude(factors):
    U, V, scores = factors
    hist = top_histories(scores, [0] * (N_USERS - 1) + [600])
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, V, user_seen=csr(hist))
    seen = []
    real_emit = obs.emit

    def emit(etype, **fields):
        seen.append((etype, fields))
        return real_emit(etype, **fields)

    obs_emit, engine_mod.obs.emit = engine_mod.obs.emit, emit
    try:
        eng.warmup()
    finally:
        engine_mod.obs.emit = obs_emit
    assert sorted(eng._pinned, key=str) == [
        (8, "exact", 4096), (8, "int8", 4096), (8, "int8", 512),
        (8, "int8", 64)]
    events = [f for e, f in seen if e == "serving_exclusion"]
    assert [(f["path"], f["history_pad"]) for f in events] == [
        ("int8", 64), ("int8", 512), ("int8", 4096), ("exact", 4096)]
    plan = exclusion_plan(8, 64 + MAX_EXCLUDE, N_ITEMS, 128)
    assert events[0]["mask_bytes"] == plan.mask_bytes == 4 * 9 * 8 * 128
    assert events[0]["keys"] == 8 * 128
    # a batch rides the pad that holds its longest history
    before = obs.counter_value("serving.exclusion_upload_bytes") or 0
    ask(eng, [(0, None), (1, None)])
    assert eng.batch_flight.records()[-1]["path"] == "int8"
    ask(eng, [(N_USERS - 1, None)])
    assert (obs.counter_value("serving.exclusion_upload_bytes")
            == before + 2 * 4 * 8 * MAX_EXCLUDE)
    assert obs.histogram_count("serving.excluded_ids",
                               source="history") >= 3


def test_an_engine_without_histories_runs_what_it_ran(factors, monkeypatch):
    """No histories published and no ``exclude``: the staging layout, the
    pins and the program are the parent's; a request with a list rides
    the wide layout for its batch alone."""
    U, V, scores = factors
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, V)
    eng.warmup()
    assert sorted(eng._pinned) == [(8, "exact"), (8, "int8")]
    staged = []
    real = ServingEngine._staged

    def spy(live, B, rank, wide=False):
        staged.append(real(live, B, rank, wide).shape)
        return real(live, B, rank, wide)

    monkeypatch.setattr(ServingEngine, "_staged", staticmethod(spy))
    (_, a), = ask(eng, [(4, None)])
    (_, b), = ask(eng, [(4, np.argsort(-scores[4])[:3])])
    (_, c), = ask(eng, [(4, None)])
    assert staged == [(8, RANK + 2), (8, RANK + 2 + MAX_EXCLUDE),
                      (8, RANK + 2)]
    best = np.argsort(-scores[4], kind="stable")
    assert list(a) == list(c) == list(best[:K])
    assert list(b) == list(best[3:3 + K])


def test_exclude_lists_that_are_refused(eng, factors):
    U, V, _ = factors
    eng.publish(U, V)
    with pytest.raises(ValueError, match="may bring 64"):
        eng.submit(0, exclude=np.arange(MAX_EXCLUDE + 1))
    with pytest.raises(ValueError, match="outside the published catalog"):
        eng.submit(0, exclude=[N_ITEMS])
    with pytest.raises(ValueError, match="outside the published catalog"):
        eng.submit(0, exclude=[-1])
    # the same id twice is one id: 64 distinct ids pass
    t = eng.submit(0, exclude=list(range(MAX_EXCLUDE)) * 2)
    assert len(t.exclude) == MAX_EXCLUDE
    eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))


@pytest.mark.parametrize("what", ["descending", "twice", "outside",
                                  "indptr", "negative"])
def test_histories_that_are_refused(eng, factors, what):
    U, V, _ = factors
    indptr, indices = csr([np.array([3, 9, 27])] * N_USERS)
    if what == "descending":
        indices[:3] = [9, 3, 27]
    elif what == "twice":
        indices[:3] = [3, 3, 27]
    elif what == "outside":
        indices[2] = N_ITEMS
    elif what == "negative":
        indices[0] = -1
    else:
        indptr = indptr[:-1]
    with pytest.raises(ValueError, match="user_seen"):
        eng.publish(U, V, user_seen=(indptr, indices))


def test_what_cannot_take_histories_yet_says_so(factors):
    U, V, scores = factors
    hist = csr(top_histories(scores, [5] * N_USERS))
    # (a mesh engine takes histories and lists since PR 52:
    # tests/test_serve_mesh_unseen.py; what it still refuses is there)
    none = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    none.publish(U, V)
    with pytest.raises(NotImplementedError, match="holds no histories"):
        none.publish_update(U, V, touched_users=[0],
                            seen_appended=([0], [1]))
    # a generation with histories takes publish_update since PR 42 and a
    # catalog that moves since PR 47 (tests/test_live_items_unseen.py);
    # made ready for its histories alone it gets no segment ...
    top = top_histories(scores, [5] * N_USERS)
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, V, user_seen=hist)
    eng.warmup_histories()
    assert eng.published_index.delta_slots == 0
    assert eng.publish_update(U, V, touched_users=[0]) == (2, "retag")
    # ... and takes one at the first row that moves: user 0's best item,
    # twice as long, lives in a slot now and is still not returned
    V2 = V.copy()
    V2[top[0][0]] *= 2
    assert eng.publish_update(U, V2, touched_items=[top[0][0]]) == (3, "delta")
    assert eng.published_index.delta_count == 1
    held_to_reference(ask(eng, [(0, None)]), U[:1], V2, [top[0]])
    # made ready for both, it has its segment from the start
    both = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    both.publish(U, V, user_seen=hist)
    both.warmup_live()
    assert both.published_index.delta_slots > 0
    # (histories of 5 ids with room to grow ride the pads 64 and 128)
    assert set(both._pinned) == {(8, "int8_delta", 64),
                                 (8, "int8_delta", 128), (8, "exact", 128)}
    assert both.publish_update(U, V2, touched_items=[top[0][0]])[1] == "delta"
    held_to_reference(ask(both, [(0, None)]), U[:1], V2, [top[0]])
    # a request's own list on an index with a segment: the id in a slot
    # and the id in the base are both gone
    live = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    live.publish(U, V)
    live.warmup_live()
    assert live.publish_update(U, V2, touched_items=[top[0][0]])[1] == "delta"
    held_to_reference(ask(live, [(0, top[0][:2])]), U[:1], V2, [top[0][:2]])


@pytest.mark.parametrize("shape", [(8, 4096, 128, 100), (5, 300, 300, 40),
                                   (3, 16384, 8192, 700),
                                   (8, 128 * 70, 128, 3000)])
def test_excluded_mask_is_the_brute_force_mask(shape):
    n, columns, block, width = shape
    rng = np.random.default_rng(width)
    seen = np.full((n, width), NOT_AN_ID, np.int32)
    for b in range(n):
        m = rng.integers(0, width + 1)
        seen[b, :m] = rng.choice(columns, m, replace=True)   # repeats too
    own = rng.integers(-5, columns + 5, (n, 7)).astype(np.int32)
    got = np.asarray(excluded_mask((jnp.asarray(seen), jnp.asarray(own)),
                                   columns, block))
    assert got.shape == (columns // block, n, block)
    want = np.zeros((n, columns), bool)
    for b in range(n):
        for x in np.concatenate([seen[b], own[b]]):
            if 0 <= x < columns:
                want[b, x] = True
    assert (got.transpose(1, 0, 2).reshape(n, columns) == want).all()


def test_the_rule_is_valid_false_for_that_row_alone(factors):
    """``chunked_topk_scores(seen=...)`` row by row against the same
    function with ``item_valid`` cleared at that row's ids, and
    ``shortlist_rescore`` against itself with the ids cleared for every row:
    bit for bit."""
    U, V, scores = factors
    rng = np.random.default_rng(8)
    n = 4
    seen = np.full((n, 300), NOT_AN_ID, np.int32)
    for b in range(n):
        m = [0, 7, 120, 300][b]
        seen[b, :m] = np.argsort(-scores[b])[:m]
    valid = jnp.asarray(rng.random(N_ITEMS) < 0.9)
    s, i = chunked_topk_scores(jnp.asarray(U[:n]), jnp.asarray(V), valid, K,
                               seen=(jnp.asarray(seen),))
    for b in range(n):
        cleared = np.asarray(valid).copy()
        cleared[seen[b][seen[b] < N_ITEMS]] = False
        rs, ri = chunked_topk_scores(jnp.asarray(U[:n]), jnp.asarray(V),
                                     jnp.asarray(cleared), K)
        assert (np.asarray(s[b]) == np.asarray(rs[b])).all()
        assert (np.asarray(i[b]) == np.asarray(ri[b])).all()
    idx = Int8CandidateIndex(V, item_valid=np.asarray(valid), shortlist_k=SK)
    same = np.tile(seen[2], (n, 1))
    s, i = _topk_jit(jnp.asarray(U[:n]), idx.Vq, idx.sv, idx.V, idx.valid,
                     k=K, shortlist_k=SK, seen=(jnp.asarray(same),))
    cleared = np.asarray(idx.valid).copy()
    cleared[seen[2][seen[2] < N_ITEMS]] = False
    rs, ri = _topk_jit(jnp.asarray(U[:n]), idx.Vq, idx.sv, idx.V,
                       jnp.asarray(cleared), k=K, shortlist_k=SK)
    assert (np.asarray(s) == np.asarray(rs)).all()
    assert (np.asarray(i) == np.asarray(ri)).all()


@pytest.mark.parametrize("state", ["free_slots", "overridden", "appended",
                                   "full"])
@pytest.mark.parametrize("n_items,stages", [(2048, 1), (N_ITEMS, 2)])
def test_seen_and_a_segment_together_against_the_exact_scan(state, n_items,
                                                            stages):
    """``shortlist_rescore`` with ``seen`` AND ``delta`` (the engine
    refuses the combination still; the one pipeline traces it): against
    ``chunked_topk_scores(seen=...)`` over the catalog as the index holds
    it, for every seeded state of the segment, histories that name
    overridden and appended ids among each row's best."""
    from tests.test_live_items import segment_states
    from tpu_als.serving.index import build_index

    rng = np.random.default_rng(46 + n_items)
    V = rng.normal(size=(n_items, RANK)).astype(np.float32)
    idx = segment_states(build_index(V, shortlist_k=SK), V, rng,
                         slots=64)[state]
    assert idx.shortlist_plan(rows=9).stages == stages
    now, ok = idx.rows(np.arange(idx.n_items))      # the catalog it serves
    Q = rng.normal(size=(9, RANK)).astype(np.float32)
    best = np.argsort(-np.where(ok, Q @ now.T, -np.inf), axis=1)
    hist = np.full((9, 128), NOT_AN_ID, np.int32)
    own = np.full((9, 8), NOT_AN_ID, np.int32)
    for b, m in enumerate([0, 1, 5, K, 40, 100, 128, 3, 77]):
        hist[b, :m] = best[b, :2 * m:2]             # every other of its best
        own[b, :b % 8] = best[b, 1:2 * (b % 8):2]
    if idx.delta_count:     # and ids the segment holds, best or not
        hist[:, -4:] = idx.d_rows[-4:]
    seen = (jnp.asarray(hist), jnp.asarray(own))
    s, i = _topk_jit(jnp.asarray(Q), idx.Vq, idx.sv, idx.V, idx.valid, k=K,
                     shortlist_k=SK, delta=idx._seg, last_id=idx._last_id(),
                     seen=seen)
    rs, ri = chunked_topk_scores(jnp.asarray(Q), jnp.asarray(now),
                                 jnp.asarray(ok), K, seen=seen)
    s, i, rs, ri = (np.asarray(a) for a in (s, i, rs, ri))
    assert np.array_equal(i, ri)
    tol = SCORE_ULPS * np.spacing(np.abs(rs).max(axis=1))[:, None]
    assert (np.abs(s - rs) <= tol).all()
    for b in range(9):
        assert not set(i[b]) & (set(hist[b]) | set(own[b]))
    if state in ("appended", "full"):       # the segment does answer
        assert np.isin(i, idx.d_rows).any()
