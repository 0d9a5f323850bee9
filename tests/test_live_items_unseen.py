"""The storefront whose catalog also moves (PR 47), at small size on the
CPU, against ``benchmark/reference/live_items_unseen.py`` (numpy float64,
nothing of the program): ``ALSModel`` + ``FoldInServer(base_history=)`` +
``LiveUpdater(fold_items=True)`` + ``ServingEngine`` with
``publish(user_seen=)``, wired as their users wire them.

(1) every fold of a stream of 48 publishes and three compactions is the
    float64 fold of ALL of its entity's ratings — a user's over the resident
    history and the run's events, an item's only where none of its ratings
    is resident — and every answer is the exact top-k of ITS generation's
    catalog less what its user had rated by then;
(2) the rater of a new item never gets it back: in the segment, after the
    compaction that moved it, where the item was appended in that very
    publish — on histories made of each user's BEST items and items folded
    to score above them, so that an unmasked slot WOULD be returned;
(3) an item a resident rating names keeps its row, bit for bit;
(4) one generation, three parts: a reader hammering one user across 200
    publishes that each move the user's row, append an item that row ranks
    first and add its id never sees two generations in one answer;
(5) an id at or above the catalog's size at start, at every history pad;
(6) a rating with both sides unknown waits, and its id joins with the
    publish that makes its item servable;
(7) every seeded state of the segment under the engine's own staging.
"""

from __future__ import annotations

import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import live_items_unseen as ref  # noqa: E402
from tests.conftest import CompileCount  # noqa: E402
from tests.test_live_items import segment_states  # noqa: E402
from tests.test_live_unseen import wait_for  # noqa: E402
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs  # noqa: E402
from tpu_als.serving import ServingEngine, build_index  # noqa: E402
from tpu_als.serving.engine import _Published  # noqa: E402

K, RANK, REG, SK = 10, 16, 0.1, 256
N_USERS, N_ITEMS, MAX_BATCH = 48, 4096, 8
# resident lengths on both sides of the engine's pads (64 / 512)
LENGTHS = [0, 3, 10, 40, 63, 64, 5, 300]


def make_stack(seed=0, quantize=True, fold_items=True):
    """``(rng, U, V, (indptr, indices, stars), model, engine, server,
    updater)``: each user's history is that user's BEST items (an engine
    that forgot an id would return it), the engine published with them;
    the updater closes a batch when it is full (its wait is longer than
    the test), so the generations are the test's to cut."""
    rng = np.random.default_rng(seed)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    lengths = np.resize(LENGTHS, N_USERS)
    best = np.argsort(-(U.astype(np.float64) @ V.T.astype(np.float64)),
                      axis=1, kind="stable")
    items = [np.sort(best[u, :n]) for u, n in enumerate(lengths)]
    indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    indices = np.concatenate(items).astype(np.int32)
    stars = rng.integers(1, 6, len(indices)).astype(np.float32)
    model = ALSModel(
        RANK, IdMap(ids=np.arange(N_USERS)), IdMap(ids=np.arange(N_ITEMS)),
        U.copy(), V.copy(),
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
         "implicitPrefs": False, "alpha": 1.0, "nonnegative": False})
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK, max_wait_s=0.0)
    eng.publish(U, V, user_seen=(indptr, indices), quantize=quantize)
    srv = FoldInServer(model, base_history=(indptr, indices, stars))
    upd = LiveUpdater(eng, srv, max_batch=MAX_BATCH, max_wait_ms=600e3,
                      fold_items=fold_items, flight_capacity=4096)
    return rng, U, V, (indptr, indices, stars), model, eng, srv, upd


class Tap:
    """Every ``publish_update`` goes through to the engine; what it named
    is kept by ORIGINAL id: ``log[j] = (seq, {user: row}, {item: row},
    {(user, item)} appended)``."""

    def __init__(self, eng, model):
        self.log, self._publish, self._model = [], eng.publish_update, model
        eng.publish_update = self

    def __call__(self, U, V, *, touched_items=None, touched_users=None,
                 seen_appended=((), ()), **kw):
        seq, mode = self._publish(
            U, V, touched_items=touched_items, touched_users=touched_users,
            seen_appended=seen_appended, **kw)
        m = self._model
        tu = np.asarray(touched_users, np.int64)
        ti = np.asarray(() if touched_items is None else touched_items,
                        np.int64)
        who, what = (np.asarray(a, np.int64) for a in seen_appended)
        self.log.append((
            seq,
            dict(zip(m._user_map.to_original(tu).tolist(), np.array(U[tu]))),
            dict(zip(m._item_map.to_original(ti).tolist(), np.array(V[ti]))),
            set(zip(m._user_map.to_original(who).tolist(),
                    m._item_map.to_original(what).tolist()))))
        return seq, mode


def publish_batch(upd, eng, batch):
    """``batch`` (at most ``MAX_BATCH`` events) folded and published as ONE
    generation: a short one is closed by a quarantined filler."""
    seq0 = eng.published_seq
    for ev in batch:
        upd.submit(*ev)
    for _ in range(MAX_BATCH - len(batch)):
        upd.submit(0, 0, float("nan"))      # quarantined: folds nothing
    wait_for(lambda: eng.published_seq > seq0)
    return eng.published_seq


def ask(eng, model, user):
    """One request by id for ``user`` (original id): ``(seq, scores,
    original item ids)``."""
    t = eng.submit(int(model._user_map.to_dense([user])[0]))
    scores, ids = t.result(timeout=10.0)
    return t.seq, np.array(scores), model._item_map.to_original(
        np.asarray(ids, np.int64))


def unrated(rng, rep_ids, n_items):
    have = set(rep_ids)
    while True:
        i = int(rng.integers(0, n_items))
        if i not in have:
            return i


def held_to_its_generation(rep, user, gen, scores, ids, k=K):
    """One answer against the reference: nothing its user had rated by
    generation ``gen``, the exact top-k of that generation's catalog less
    it, the scores the dot products with that generation's rows."""
    q = rep.user_row(user, gen)[None]
    mine = rep.ids(user, gen)
    assert not set(ids.tolist()) & set(mine.tolist()), (user, gen)
    _, want, _ = ref.exact_topk_left(q, [gen], rep, k, [mine])
    assert set(ids.tolist()) == set(want[0].tolist()), (user, gen)
    own = ref.own_scores(q, [gen], ids[None], rep)[0]
    assert np.abs(scores - own).max() < 1e-4 * max(np.abs(own).max(), 1.0)


# -- (1) (2) (3) (6) the deployment, publish by publish -------------------------

N_BATCHES = 48


def seeded_batches(rng, hist, n_batches=N_BATCHES):
    """Batches of eight events: a known user on a NEW item (ids
    ``N_ITEMS``, + 1, ... in arrival order), a known user on one of the
    run's new items again, a new user on a catalog item, five known users
    on catalog items they have not rated — and, spread over batches 3, 5
    and 7, a rating with BOTH sides unknown, its user's first usable
    rating, and the item's first usable one."""
    indptr, indices, _ = hist
    rated = {u: set(indices[indptr[u]:indptr[u + 1]].tolist())
             for u in range(N_USERS)}
    next_user, next_item, new_items = N_USERS, N_ITEMS, []
    stranger = both = None
    batches = []

    def fresh(u):
        i = unrated(rng, rated.setdefault(u, set()), N_ITEMS)
        rated[u].add(i)
        return i

    def star():
        return float(rng.integers(1, 6))

    for b in range(n_batches):
        users = rng.permutation(N_USERS)[:7].tolist()
        batch = [(users[0], next_item, 5.0)]
        rated[users[0]].add(next_item)
        new_items.append(next_item)
        next_item += 1
        again = [i for i in new_items[:-1]
                 if i not in rated[users[1]] and i != both]
        if again:
            i = again[int(rng.integers(0, len(again)))]
            rated[users[1]].add(i)
            batch.append((users[1], i, 5.0))
        batch.append((next_user, fresh(next_user), star()))
        next_user += 1
        batch += [(u, fresh(u), star()) for u in users[2:7]]
        if b == 3:
            stranger, both = next_user, next_item
            next_user, next_item = next_user + 1, next_item + 1
            batch[-1] = (stranger, both, 5.0)
        elif b == 5:
            batch[-1] = (stranger, fresh(stranger), 4.0)
        elif b == 7:
            batch[-1] = (users[6], both, 5.0)
            rated[users[6]].add(both)
        batches.append(batch[:MAX_BATCH])
    return batches, (stranger, both)


@pytest.fixture(scope="module")
def streamed():
    reg = obs.reset()
    rng, U, V, hist, model, eng, srv, upd = make_stack(seed=47)
    tap = Tap(eng, model)
    srv.prewarm(rows=(MAX_BATCH,), sides=("user", "item"))
    batches, (stranger, both) = seeded_batches(rng, hist)
    upd.start()     # warmup_publish + warmup_live: catalog AND histories
    eng.start()
    pinned = set(eng._pinned)
    compiles = CompileCount()
    answers, waited = [], {}
    try:
        for b, batch in enumerate(batches):
            publish_batch(upd, eng, batch)
            waited[b] = (srv.events_waiting, len(upd._not_joined[0]))
            for user in sorted({ev[0] for ev in batch}):
                if model._user_map.to_dense([user])[0] >= 0:
                    answers.append((user, b + 1, *ask(eng, model, user)))
    finally:
        upd.stop(drain_timeout_s=30.0)
    sizes = [len(b) for b in batches]
    events = [ev for b in batches for ev in b]
    rep = ref.replay(U, V, hist, *(np.array(c) for c in zip(*events)),
                     sizes, REG,
                     published=[(us, its) for _, us, its, _ in tap.log])
    out = dict(reg=reg, U0=U, V0=V, hist=hist, model=model, eng=eng, srv=srv,
               upd=upd, tap=tap, rep=rep, batches=batches, answers=answers,
               compiled=compiles.n, pinned=pinned, stranger=stranger,
               both=both, waited=waited)
    yield out
    eng.stop()


def test_every_fold_is_over_all_of_its_entitys_ratings(streamed):
    rep, reg = streamed["rep"], streamed["reg"]
    assert rep.missing == rep.unasked == 0
    assert len(rep.fold_err[0]) > 300 and len(rep.fold_err[1]) > 150
    assert max(rep.fold_err[0]) < 1e-4
    # an item's fold is held to its own conditioning (reference docstring)
    assert rep.item_err_over_kappa().max() < 8.0
    assert 20 < np.median(rep.item_kappa) and max(rep.fold_err[1]) < 2e-3
    # the counters are the replay's counts: one per rating and side that
    # entered a fold, the item folds by kind, the events of items that a
    # resident rating names
    assert reg.counter_value("foldin.ratings") == rep.entered
    assert rep.folds["first"] == N_BATCHES + 1 and rep.folds["again"] > 40
    for kind, n in rep.folds.items():
        assert reg.counter_value("live.items_folded", kind=kind) == n
    assert (reg.counter_value("live.items_left_to_refit")
            == rep.left_to_refit > 40)
    assert reg.counter_value("live.items_appended") == rep.folds["first"]


def test_every_answer_is_its_generations(streamed):
    """By id for every user of every batch, after its publish: the
    generation that answered is the batch's (``Ticket.seq``), and the
    answer is that generation's in all three parts."""
    seqs = [seq for seq, *_ in streamed["tap"].log]
    assert len(streamed["answers"]) > 300
    for user, gen, seq, scores, ids in streamed["answers"]:
        assert seq == seqs[gen - 1]
        held_to_its_generation(streamed["rep"], user, gen, scores, ids)


def test_the_rater_of_a_new_item_never_gets_it_back(streamed):
    """Guarantee 4, with teeth: of the answers to a user who had rated one
    of the run's new items, most WOULD hold such an item were its slot (or,
    after a compaction, its column) not masked — its row was folded from
    that user's five stars."""
    rep = streamed["rep"]
    would, asked = 0, 0
    for user, gen, _, _, ids in streamed["answers"]:
        mine = [i for i in rep.ids(user, gen).tolist() if i >= N_ITEMS
                and rep.catalog_as_of(gen)[2] > 0
                and i in rep.catalog_as_of(gen)[0]]
        if not mine:
            continue
        asked += 1
        assert not set(ids.tolist()) & set(mine)
        rest = np.setdiff1d(rep.ids(user, gen), mine)
        _, unmasked, _ = ref.exact_topk_left(
            rep.user_row(user, gen)[None], [gen], rep, K, [rest])
        would += bool(set(unmasked[0].tolist()) & set(mine))
    assert asked > 60 and would > 0.8 * asked, (asked, would)
    # ... across compactions, and while ids named the segment's slots
    reg = streamed["reg"]
    compactions = [e for e in reg._events if e["type"] == "serving_compaction"]
    assert len(compactions) >= 2
    assert reg.counter_value("live.history_segment_ids") > 40
    assert (reg.counter_value("live.history_appended_ids")
            == sum(len(j) for j in rep.joined))


def test_an_item_with_resident_ratings_keeps_its_row_bit_for_bit(streamed):
    rep, m, eng = streamed["rep"], streamed["model"], streamed["eng"]
    named = np.flatnonzero(rep.rated_before)
    events = {i for b in streamed["batches"] for _, i, _ in b}
    assert len(events & set(named.tolist())) > 40     # they WERE rated
    np.testing.assert_array_equal(m._V[named], streamed["V0"][named])
    rows, ok = eng.published_index.rows(named)
    np.testing.assert_array_equal(rows, streamed["V0"][named])
    assert ok.all()
    for _, _, items, _ in streamed["tap"].log:
        assert not set(items) & set(named.tolist())


def test_the_ids_join_with_the_publish_that_makes_their_item_servable(
        streamed):
    """What every publish handed the engine for the histories is the
    reference's: a batch's own events' pairs, but a pair one side of which
    had no row — a rating with both sides unknown — with the publish that
    gives it one."""
    rep, tap = streamed["rep"], streamed["tap"]
    for b, (_, _, _, said) in enumerate(tap.log):
        assert said == rep.joined[b], b
    stranger, both = streamed["stranger"], streamed["both"]
    # batch 3: neither side has a row; nothing folded, nothing joined
    assert streamed["waited"][3] == (2, 1)
    assert (stranger, both) not in rep.joined[3] | rep.joined[5]
    # batch 5: the user has a row, the item none: the pair still waits
    assert streamed["waited"][5] == (1, 1)
    # batch 7: a known user rates the item: folded over BOTH ratings, and
    # both pairs join
    assert streamed["waited"][7] == (0, 0)
    assert (stranger, both) in rep.joined[7]
    assert streamed["model"]._item_map.to_dense([both])[0] != both
    seen = streamed["eng"]._model.seen
    row = int(streamed["model"]._user_map.to_dense([stranger])[0])
    assert seen.lengths[row] == len(rep.ids(stranger))


def test_nothing_compiles_and_every_batch_rides_the_new_pin(streamed):
    assert streamed["compiled"] == 0
    pads = streamed["eng"]._model.seen.pads
    assert streamed["pinned"] == {(8, "int8_delta", p) for p in pads} | {
        (8, "exact", pads[-1])}
    assert set(streamed["eng"]._pinned) == streamed["pinned"]   # none dropped
    # (the test's own reads of the tables compile beside the traffic:
    # what="jax.compile", since ISSUE 55)
    assert not [e for e in streamed["reg"]._events if e["type"] == "warning"
                and e["what"] != "jax.compile"]


# -- (4) one generation, three parts ------------------------------------------

def test_the_row_the_catalog_and_the_id_are_swapped_in_together():
    """A reader asks for ONE user as fast as it can while 200 publishes
    each give that user a new row, APPEND an item that row ranks first and
    add its id: every answer is the exact top-k of ONE generation — its
    row's scores over its catalog less its history — whichever answered.
    A new row with the catalog before it scores wrong, a new catalog with
    the history before it returns the appended item."""
    rng, U, V, hist, model, eng, srv, _ = make_stack(seed=5)
    eng.warmup_publish(8)
    eng.warmup_live(max_rows=8)
    eng.start()
    user, n = LENGTHS.index(10), 200
    Uh = np.zeros((eng._model.U.shape[0], RANK), np.float32)
    Uh[:N_USERS] = U
    Vh = np.zeros((N_ITEMS + n, RANK), np.float32)
    Vh[:N_ITEMS] = V
    rep = ref.Replay(U, V, *hist, REG)
    state = {eng.published_seq: (Uh[user].copy(), N_ITEMS, rep.ids(user))}
    answers, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            t = eng.submit(user)
            scores, ids = t.result(timeout=10.0)
            answers.append((t.seq, np.array(scores), np.array(ids)))

    thread = threading.Thread(target=reader)
    thread.start()
    modes = []
    try:
        for j in range(n):
            x = rng.standard_normal(RANK).astype(np.float32)
            new = N_ITEMS + j
            Uh[user], Vh[new] = x, x / 2        # scores |x|^2 / 2: first
            seq, mode = eng.publish_update(
                Uh[:N_USERS], Vh[:new + 1], touched_users=[user],
                touched_items=[new], seen_appended=([user], [new]))
            modes.append(mode)
            state[seq] = (x, new + 1, np.r_[state[seq - 1][2], new])
    finally:
        stop.set()
        thread.join(30.0)
        eng.stop()
    assert modes.count("compact") >= 2 and "full" not in modes
    assert len({a[0] for a in answers}) > 20, "the reader saw few generations"
    for seq, scores, ids in answers:
        x, size, mine = state[seq]
        own = x.astype(np.float64) @ Vh[ids].T.astype(np.float64)
        assert np.abs(scores - own).max() < 1e-4 * np.abs(own).max(), seq
        assert ids.max() < size and not set(ids.tolist()) & set(mine.tolist())
        s = x.astype(np.float64) @ Vh[:size].T.astype(np.float64)
        s[mine] = -np.inf
        assert set(ids.tolist()) == set(np.argsort(-s)[:K].tolist()), seq
    assert eng._model.seen.lengths[user] == 10 + n


# -- (5) an id at or above the catalog's size at start, at every pad ----------

@pytest.mark.parametrize("length,appends,pad", [(10, 1, 64), (63, 2, 512),
                                                (64, 1, 512), (300, 1, 512)])
def test_a_history_may_name_an_appended_item_at_every_pad(length, appends,
                                                          pad):
    """The user's by-id answer lacks the appended items while they live in
    the segment, after the compaction that moved them into the base, and
    on the exact fallback — at the pad its history rides (63 + 2 crosses
    from 64 to 512)."""
    from tpu_als.resilience import faults

    rng, U, V, hist, model, eng, srv, _ = make_stack(seed=length)
    eng.warmup_live(max_rows=8)
    assert eng._model.seen.pads == (64, 512)
    user = LENGTHS.index(length)
    new = N_ITEMS + np.arange(appends)
    V2 = np.concatenate([V, np.tile(U[user] / 2, (appends, 1))])
    eng.publish_update(U, V2, touched_items=new,
                       seen_appended=([user] * appends, new))
    assert eng._model.seen.pad_for([length + appends]) == pad
    rep = ref.Replay(U, V, *hist, REG)
    mine = np.r_[rep.ids(user), new]
    s = U[user].astype(np.float64) @ V2.T.astype(np.float64)
    assert set(np.argsort(-s)[:appends].tolist()) == set(new.tolist())
    s[mine] = -np.inf
    want = set(np.argsort(-s)[:K].tolist())

    def answer():
        t = eng.submit(user)
        eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))
        return set(np.asarray(t.result(timeout=0)[1]).tolist())

    assert eng.published_index.delta_count == appends
    assert answer() == want                 # the slots masked
    eng._compact_live()
    assert eng.published_index.delta_count == 0
    assert answer() == want                 # the base's spare columns masked
    faults.install("serving.score=corrupt@nth=1")
    try:
        assert answer() == want             # the exact fallback
    finally:
        faults.clear()
    # a by-vector request with the ids in its own list, and without
    for exclude, gone in ((new, True), (None, False)):
        t = eng.submit(U[user], exclude=exclude)
        eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))
        ids = set(np.asarray(t.result(timeout=0)[1]).tolist())
        assert bool(ids & set(new.tolist())) != gone


# -- (7) every seeded state of the segment, under the engine's staging --------

@pytest.mark.parametrize("state", ["free_slots", "overridden", "appended",
                                   "full"])
def test_the_engine_excludes_on_every_state_of_the_segment(state):
    """A generation whose index is in each of ``segment_states`` (installed
    as :class:`_Published`: no publish leaves a segment FULL) with
    histories of each user's best items, ids the segment holds among
    them: every by-id answer is the exact top-k of the catalog the index
    serves less that history."""
    rng = np.random.default_rng(7)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((8, RANK)).astype(np.float32)
    idx = segment_states(build_index(V, shortlist_k=SK), V, rng,
                         slots=64)[state]
    now, ok = idx.rows(np.arange(idx.n_items))
    s = np.where(ok, U.astype(np.float64) @ now.T.astype(np.float64), -np.inf)
    best = np.argsort(-s, axis=1, kind="stable")
    hist = [np.unique(np.r_[best[u, :n:2], idx.d_rows[-4:]])
            for u, n in enumerate([0, 2, 10, 40, 120, 128, 6, 200])]
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK)
    eng.publish(U, V)
    m = eng._model
    more = idx.n_base - N_ITEMS
    seen = eng._place_seen(
        (np.concatenate([[0], np.cumsum([len(h) for h in hist])]),
         np.concatenate(hist).astype(np.int32)), len(U), idx.n_items)
    eng._model = _Published(
        m.seq, m.U, m.n_users, jnp.pad(m.V, ((0, more), (0, 0))),
        jnp.pad(m.valid, (0, more)), idx.retag(m.seq), idx.n_items, seen)
    tickets = [eng.submit(u) for u in range(len(U))]
    eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))
    for u, t in enumerate(tickets):
        ids = np.asarray(t.result(timeout=0)[1])
        left = s[u].copy()
        left[hist[u]] = -np.inf
        assert set(ids.tolist()) == set(np.argsort(-left)[:K].tolist()), u
    if state in ("appended", "full"):       # the segment does answer
        assert any(np.isin(np.asarray(t.result(0)[1]), idx.d_rows).any()
                   for t in tickets)
