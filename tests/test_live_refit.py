"""A refit LANDS on the running live deployment (``LiveUpdater.land``), at
a small size: rating events of known and new users on known and new items
through ``LiveUpdater`` into a started ``ServingEngine``, two whole new
model generations swapped in meanwhile, each with the events since its
snapshot folded onto it again — against the plain float64 replay
``benchmark/reference/refit_replay.py`` (nothing of the program in it).
What a landing keeps (every admitted event, once; the live capacities; the
pins), what it replaces (every table, the index) and what it leaves behind
(nothing)."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.reference import refit_replay as ref
from tests.test_live_deployment import wait_for
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs
from tpu_als.core import foldin as core_foldin
from tpu_als.obs import compiles
from tpu_als.obs.schema import LIVE_LANDING_SPAN_KEYS
from tpu_als.serving import ServingEngine, build_index
from tpu_als.serving.engine import _arrays_of
from tpu_als.serving.index import Int8CandidateIndex

N_USERS, N_ITEMS, RANK, K = 400, 300, 16, 10
REG = 0.1
PARAMS = {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
          "implicitPrefs": False, "alpha": 1.0, "nonnegative": False}


class Tap:
    """Between the updater and the engine: what each ``publish_update``
    published, by seq, as ``({user id: row}, {item id: row})``."""

    def __init__(self, engine, model):
        self._engine, self._model, self.log = engine, model, {}

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def publish_update(self, U, V, *, touched_items=None, touched_users=None,
                       **kw):
        out = self._engine.publish_update(
            U, V, touched_items=touched_items, touched_users=touched_users,
            **kw)
        m = self._model
        tu, ti = (np.empty(0, np.int64) if t is None
                  else np.unique(np.asarray(t, np.int64))
                  for t in (touched_users, touched_items))
        self.log[int(out[0])] = (
            dict(zip(m._user_map.to_original(tu).tolist(), np.array(U[tu]))),
            dict(zip(m._item_map.to_original(ti).tolist(), np.array(V[ti]))))
        return out


class Deployment:
    """Engine, fold-in server and updater over seeded factors, started;
    ``feed`` hands events over and waits until they are published."""

    def __init__(self, seed=0, fold_items=True, refits=True):
        self.rng = rng = np.random.default_rng(seed)
        self.U0 = rng.normal(size=(N_USERS, RANK)).astype(np.float32)
        self.V0 = (rng.normal(size=(N_ITEMS, RANK))
                   / np.sqrt(RANK)).astype(np.float32)
        self.model = ALSModel(
            RANK, IdMap(ids=np.arange(N_USERS)),
            IdMap(ids=np.arange(N_ITEMS)), self.U0.copy(), self.V0.copy(),
            dict(PARAMS))
        self.eng = ServingEngine(k=K, buckets=(8,), shortlist_k=64,
                                 max_wait_s=0.0)
        self.eng.publish(self.U0, self.V0)
        self.srv = FoldInServer(self.model)
        self.srv.prewarm(rows=(64,), sides=("user", "item") if fold_items
                         else ("user",))
        self.eng.warmup()
        self.tap = Tap(self.eng, self.model)
        self.upd = LiveUpdater(self.tap, self.srv, max_batch=8,
                               max_wait_ms=2.0, fold_items=fold_items,
                               flight_capacity=4096, refits=refits)
        self.fold_items = fold_items
        self.events = []            # every admitted event, in order
        self.refits = []            # (U', V') of each landing
        self.upd.start()
        self.eng.start()

    def batches(self):
        return [r for r in self.upd.flight.records()
                if r.get("status") == "ok"]

    def feed(self, events):
        for ev in events:
            self.upd.submit(*ev)
        self.events += list(events)
        wait_for(lambda: sum(r["events"] for r in self.batches())
                 == len(self.events))

    def refit(self):
        """A whole new fit of the rows the model holds now: seeded draws
        (a landing's work does not depend on how they were made)."""
        m = self.model
        nu, ni = len(m._user_map), len(m._item_map)
        U = self.rng.normal(size=(nu, RANK)).astype(np.float32)
        V = (self.rng.normal(size=(ni, RANK))
             / np.sqrt(RANK)).astype(np.float32)
        return ALSModel(RANK, IdMap(ids=m._user_map.ids[:nu].copy()),
                        IdMap(ids=m._item_map.ids[:ni].copy()), U, V,
                        dict(PARAMS))

    def land(self, refit, snapshot):
        rec = self.upd.land(refit, snapshot)
        self.refits.append((refit._U, refit._V))
        return rec

    def steps(self):
        """``(steps, published)`` for the replay: the batches and the
        landings in the order of their publish seqs."""
        steps = [(r["seq"], r["events"], self.tap.log[r["seq"]])
                 for r in self.batches()]
        for rec, (U, V) in zip(self.upd.landings, self.refits):
            caught = ({}, {})       # id -> its rows, a fold each, in order
            for side, (ids, rows) in zip(caught, (rec["catchup"]["users"],
                                                  rec["catchup"]["items"])):
                for e, x in zip(ids.tolist(), rows):
                    side.setdefault(e, []).append(x)
            steps.append((rec["seq"], {"snapshot": rec["snapshot"],
                                       "U": U, "V": V}, caught))
        steps.sort(key=lambda s: s[0])
        return [s[1] for s in steps], [s[2] for s in steps]

    def replay(self, follow=True, **how):
        steps, published = self.steps()
        users, items, stars = zip(*self.events)
        return ref.replay(self.U0, self.V0, users, items, stars, steps, REG,
                          fold_items=self.fold_items,
                          published=published if follow else None, **how)

    def served_users(self, ids):
        rows = self.model._user_map.to_dense(ids)
        return self.eng.user_rows(rows)

    def served_items(self, ids):
        rows, ok = self.eng.published_index.rows(
            self.model._item_map.to_dense(ids))
        assert ok.all()
        return rows

    def stop(self):
        self.upd.stop()
        self.eng.stop()


def events_of(rng, n, users, items):
    return [(int(rng.choice(users)), int(rng.choice(items)),
             float(rng.integers(1, 6))) for _ in range(n)]


@pytest.fixture(scope="module")
def run():
    """One deployment through two landings; the tests below read it."""
    mp = pytest.MonkeyPatch()
    # tables of a few hundred rows go up in chunks of a fixed size, as the
    # real ones do (32,768 rows): appended rows then change no chunk's shape
    mp.setattr(core_foldin, "PLACE_CHUNK", 64)
    d = Deployment()
    rng, seen = d.rng, {}
    known_u, known_i = np.arange(N_USERS), np.arange(N_ITEMS)
    try:
        # A: before the first snapshot — the refit knows all of it
        d.feed(events_of(rng, 12, known_u, known_i)
               + [(N_USERS, 5, 4.0), (157, N_ITEMS, 5.0)])
        seen["before"] = (157, N_USERS)     # rated before the snapshot
        snap1 = d.upd.mark()
        refit1 = d.refit()
        # B: after it — what the landing folds again.  A new user on a
        # known item and then on a new one; a known user on a new item
        # whose second rater is new and rates nothing else (two rounds)
        d.feed(events_of(rng, 10, known_u[:50], known_i[:40])
               + [(N_USERS + 1, 9, 2.0)])
        d.feed([(N_USERS + 1, N_ITEMS + 1, 5.0), (151, N_ITEMS + 2, 3.0)])
        d.feed([(N_USERS + 2, N_ITEMS + 2, 4.0), (153, 17, 1.0)])
        seen["after"] = (153, N_USERS + 1, N_USERS + 2)
        seen["asked_before"] = {
            u: d.eng.recommend(int(d.model._user_map.to_dense([u])[0]))
            for u in seen["after"]}
        # events admitted WHILE the refit lands wait in the queue
        during = events_of(rng, 6, known_u[60:100], known_i[50:90])
        real = d.srv.land

        def land_with_arrivals(refit, users=(), items=()):
            for ev in during:
                d.upd.submit(*ev)
            return real(refit, users, items)

        mp.setattr(d.srv, "land", land_with_arrivals)
        old = d.eng._model
        old_arrays = _arrays_of(old)
        old_tables = d.srv.device_tables()
        shapes = [a.shape for a in _arrays_of(old)]
        seen["land1"] = d.land(refit1, snap1)
        mp.setattr(d.srv, "land", real)
        seen["asked_after"] = {
            u: d.eng.recommend(int(d.model._user_map.to_dense([u])[0]))
            for u in seen["after"] + seen["before"]}
        seen["gen1"] = (old.seq + 1,
                        [a.shape for a in _arrays_of(d.eng._model)], shapes,
                        [a.is_deleted() for a in old_arrays],
                        [a.is_deleted() for a in old_tables])
        d.events += during
        wait_for(lambda: sum(r["events"] for r in d.batches())
                 == len(d.events))
        # C: the folds after a landing read the landed tables
        d.feed(events_of(rng, 8, known_u[:50], known_i[:40])
               + [(N_USERS + 3, 3, 5.0)])
        snap2 = d.upd.mark()
        refit2 = d.refit()
        d.feed(events_of(rng, 6, known_u[:50], known_i[:40])
               + [(21, N_ITEMS + 3, 4.0)])
        # a compaction is due: the segment holds rows as the refit lands
        seen["segment_rows"] = d.eng.published_index.delta_count
        seen["land2"] = d.land(refit2, snap2)
        seen["segment_after"] = d.eng.published_index.delta_count
        d.feed(events_of(rng, 6, known_u[:50], known_i[:40]))
        d.upd.stop()
        seen["rep"] = d.replay()
        seen["free"] = d.replay(follow=False)
        yield d, seen
    finally:
        d.stop()
        mp.undo()


def test_two_landings_and_every_event_in_one_step(run):
    d, seen = run
    assert len(d.upd.landings) == 2
    assert obs.counter_value("live.landings") >= 2
    steps, _ = d.steps()
    assert sum(s for s in steps if not isinstance(s, dict)) == len(d.events)
    # the landing with events queued: they were folded after it
    assert seen["land1"]["catchup_events"] == 15
    assert d.upd.queue_depth == 0


def test_every_fold_and_every_catchup_fold_is_the_rules(run):
    _, seen = run
    rep = seen["rep"]
    assert rep.missing == rep.unasked == 0
    assert rep.catchup_missing == rep.catchup_unasked == 0
    for errs in rep.fold_err + rep.catchup_err:
        assert errs and max(errs) < 1e-4
    # who the catch-ups folded: as many as the rule asks for
    for rec, (events, users, items) in zip(run[0].upd.landings,
                                           rep.catchup_sizes):
        assert (rec["catchup_events"], rec["catchup_users"],
                rec["catchup_items"]) == (events, users, items)


def test_second_round_for_ratings_of_entities_new_since_the_snapshot(run):
    d, seen = run
    # user N_USERS + 2 rated one item, itself new since the snapshot; user
    # N_USERS + 1 a known item and a new one: the second round folds both
    # over what the first could not use, and the item over both its raters
    assert seen["land1"]["rounds"] == 2
    assert seen["land2"]["rounds"] == 2   # user 21 rated a new item
    folds = seen["land1"]["catchup"]["users"][0].tolist()
    assert folds.count(N_USERS + 1) == 2 and folds.count(N_USERS + 2) == 1
    assert folds.count(151) == 1 and folds.count(153) == 1
    assert seen["land1"]["catchup"]["items"][0].tolist().count(
        N_ITEMS + 2) == 2


def test_no_event_lost_and_none_applied_twice(run):
    d, _ = run
    by_user, by_item = {}, {}
    for u, i, r in d.events:
        by_user.setdefault(u, []).append((i, r))
        by_item.setdefault(i, []).append((u, r))
    for kept, items_side in ((by_user, False), (by_item, True)):
        for e, ratings in kept.items():
            ids, stars = d.srv.history_of(e, items_side=items_side)
            assert list(zip(ids.tolist(), stars.tolist())) == ratings


def test_rows_untouched_since_the_snapshot_are_the_refits_bit_for_bit(run):
    d, seen = run
    rep, (U2, V2) = seen["rep"], d.refits[-1]
    users = np.array([u for u in range(len(U2)) if u not in rep.touched[0]])
    items = np.array([i for i in range(len(V2)) if i not in rep.touched[1]])
    assert len(users) > 300 and len(items) > 200
    assert np.array_equal(d.served_users(users), U2[users])
    assert np.array_equal(d.served_items(items), V2[items])
    # the fold-in server's own tables and the host's, the same
    Ud, Vd = d.srv.device_tables()
    assert np.array_equal(np.asarray(Ud)[users], U2[users])
    assert np.array_equal(np.asarray(Vd)[items], V2[items])
    assert np.array_equal(d.model._U[users], U2[users])


def test_touched_rows_are_the_replays(run):
    d, seen = run
    for rep in (seen["rep"], seen["free"]):
        users = np.array(sorted(rep.rows[0]))
        items = np.array(sorted(rep.rows[1]))
        assert len(users) and len(items)
        for served, side, ids in ((d.served_users(users), 0, users),
                                  (d.served_items(items), 1, items)):
            want = np.stack([rep.rows[side][e] for e in ids.tolist()])
            err = (np.linalg.norm(served - want, axis=1)
                   / np.linalg.norm(want, axis=1))
            assert err.max() < 1e-3
    assert len(d.model._item_map) == seen["rep"].n_items[-1]


def test_a_rating_answered_with_before_a_landing_is_not_forgotten(run):
    d, seen = run
    # the generation the first landing installed, rebuilt from the replay
    steps, published = d.steps()
    upto = next(i for i, s in enumerate(steps) if isinstance(s, dict)) + 1
    users, items, stars = zip(*d.events)
    rep = ref.replay(d.U0, d.V0, users, items, stars, steps[:upto], REG,
                     published=published[:upto])
    V = rep.final_catalog()
    for u, (scores, ids) in seen["asked_after"].items():
        row = rep.row(0, u)
        if u in seen["after"]:      # folded again: its rating is in the row
            assert u in rep.rows[0]
        want = np.sort(V @ np.asarray(row, np.float64))[::-1][:K]
        assert np.allclose(scores, want, rtol=2e-2, atol=2e-2)
        assert np.allclose(scores, V[ids] @ np.asarray(row, np.float64),
                           rtol=2e-2, atol=2e-2)
    # and it IS another answer than the one before the landing
    for u, (scores, _) in seen["asked_before"].items():
        assert not np.allclose(scores, seen["asked_after"][u][0])


def test_a_fold_after_a_landing_reads_the_landed_tables(run):
    d, seen = run
    # the control: the same journal held to catch-ups and folds over the
    # tables as they stood BEFORE each landing does not pass
    stale = d.replay(catchup="stale")
    assert max(stale.catchup_err[0] + stale.catchup_err[1]) > 1e-2
    assert max(seen["rep"].fold_err[0] + seen["rep"].fold_err[1]) < 1e-4


def test_a_landing_left_out_of_the_catch_up_is_seen(run):
    d, _ = run
    none = d.replay(catchup="none")
    assert none.catchup_unasked > 0


def test_a_landing_compiles_nothing(run):
    _, seen = run
    assert seen["land1"]["programs"] == 0
    assert seen["land2"]["programs"] == 0


def test_a_landing_keeps_the_capacities_and_leaves_nothing_behind(run):
    _, seen = run
    seq, shapes, shapes_before, engine_gone, server_gone = seen["gen1"]
    assert shapes == shapes_before
    assert all(engine_gone) and all(server_gone)
    assert seen["land1"]["seq"] == seq


def test_a_landing_while_a_compaction_is_due_starts_an_empty_segment(run):
    _, seen = run
    assert seen["segment_rows"] > 0
    assert seen["segment_after"] == 0


def test_landing_record_and_spans(run):
    d, seen = run
    rec = seen["land2"]
    for key in ("pause", "tables", "place", "catchup", "users", "catalog",
                "index", "lock_wait", "swap", "release", "whole"):
        assert rec["seconds"][key] >= 0.0
    assert rec["seconds"]["whole"] >= rec["seconds"]["server"]
    # the fold-in server's two tables, and the catalog's valid bits
    cap_u, cap_v = (t.shape[0] for t in d.srv.device_tables())
    assert rec["placed_bytes"] == (
        4 * RANK * (rec["users"] + rec["items"]) + cap_v)
    # three tables copied on the device: U, V and the index's own V
    assert rec["copied_bytes"] == 4 * RANK * (cap_u + 2 * cap_v)
    assert set(LIVE_LANDING_SPAN_KEYS) >= {
        "live.landing", "live.landing.swap", "live.landing.catchup"}


def test_landing_spans_on_the_profilers_timeline(tmp_path):
    import jax

    from benchmark import program_spans
    from benchmark import trace as tr

    d = Deployment(seed=3)
    try:
        d.feed(events_of(d.rng, 6, np.arange(50), np.arange(40)))
        snap = d.upd.mark()
        d.feed(events_of(d.rng, 6, np.arange(50), np.arange(40)))
        jax.profiler.start_trace(str(tmp_path))
        try:
            d.land(d.refit(), snap)
        finally:
            jax.profiler.stop_trace()
    finally:
        d.stop()
    spans = program_spans.read(tr.find_xplane(str(tmp_path)),
                               prefix="live.landing")
    names = {s[0] for s in spans}
    assert names == set(LIVE_LANDING_SPAN_KEYS)
    whole = next(s for s in spans if s[0] == "live.landing")
    assert whole[3]["catchup_events"] == 6


def test_user_only_updater_lands_too():
    d = Deployment(seed=1, fold_items=False)
    try:
        d.feed(events_of(d.rng, 10, np.arange(60), np.arange(N_ITEMS))
               + [(N_USERS, 3, 5.0)])
        snap = d.upd.mark()
        d.feed(events_of(d.rng, 6, np.arange(60), np.arange(N_ITEMS))
               + [(N_USERS + 1, 4, 2.0)])
        rec = d.land(d.refit(), snap)
        d.feed(events_of(d.rng, 6, np.arange(60), np.arange(N_ITEMS)))
        d.upd.stop()
        rep = d.replay()
        assert rec["catchup_items"] == 0 and rec["catchup_users"] > 0
        assert rep.missing == rep.unasked == 0
        assert rep.catchup_missing == rep.catchup_unasked == 0
        assert max(rep.fold_err[0] + rep.catchup_err[0]) < 1e-4
        U1, V1 = d.refits[-1]
        quiet = np.array([u for u in range(len(U1))
                          if u not in rep.touched[0]])
        assert np.array_equal(d.served_users(quiet), U1[quiet])
        assert np.array_equal(d.served_items(np.arange(N_ITEMS)), V1)
    finally:
        d.stop()


def test_refit_with_its_ids_in_another_order():
    d = Deployment(seed=2)
    try:
        d.feed(events_of(d.rng, 8, np.arange(60), np.arange(40))
               + [(N_USERS, 3, 5.0)])
        snap = d.upd.mark()
        d.feed(events_of(d.rng, 5, np.arange(60), np.arange(40)))
        straight = d.refit()
        pu = d.rng.permutation(len(straight._U))
        pi = d.rng.permutation(len(straight._V))
        shuffled = ALSModel(
            RANK, IdMap(ids=straight._user_map.ids[pu]),
            IdMap(ids=straight._item_map.ids[pi]), straight._U[pu],
            straight._V[pi], dict(PARAMS))
        d.upd.land(shuffled, snap)
        d.refits.append((straight._U, straight._V))
        d.upd.stop()
        rep = d.replay()
        assert rep.catchup_missing == rep.catchup_unasked == 0
        quiet = np.array([u for u in range(len(straight._U))
                          if u not in rep.touched[0]])
        assert np.array_equal(d.served_users(quiet), straight._U[quiet])
    finally:
        d.stop()


def test_what_a_landing_refuses():
    d = Deployment(seed=4)
    try:
        d.feed(events_of(d.rng, 4, np.arange(60), np.arange(40)))
        with pytest.raises(ValueError, match="no snapshot"):
            d.upd.land(d.refit(), 3)
        snap = d.upd.mark()
        alien = d.refit()
        alien._user_map.ids[0] = 10 ** 6
        with pytest.raises(ValueError, match="does not"):
            d.upd.land(alien, snap)
        other = d.refit()
        other._U = np.zeros((len(other._U), RANK + 1), np.float32)
        with pytest.raises(ValueError, match="rank"):
            d.upd.land(other, snap)
        # a refused landing changed nothing: the same snapshot still lands
        assert d.upd.land(d.refit(), snap)["catchup_events"] == 0
        d.upd.stop()
        with pytest.raises(RuntimeError, match="not running"):
            d.upd.land(d.refit(), 0)
    finally:
        d.stop()


def test_landing_under_histories_is_refused():
    rng = np.random.default_rng(5)
    U = rng.normal(size=(40, 8)).astype(np.float32)
    V = rng.normal(size=(70, 8)).astype(np.float32)
    eng = ServingEngine(k=5, buckets=(8,))
    eng.publish(U, V, user_seen=(np.zeros(41, np.int64),
                                 np.empty(0, np.int32)))
    model = ALSModel(8, IdMap(ids=np.arange(40)), IdMap(ids=np.arange(70)),
                     U.copy(), V.copy(), dict(PARAMS))
    upd = LiveUpdater(eng, FoldInServer(model))
    with pytest.raises(NotImplementedError, match="histories"):
        upd.land(model, 0)


def test_index_over_a_table_is_build_and_reserve_bit_for_bit():
    rng = np.random.default_rng(6)
    V = rng.normal(size=(5000, 32)).astype(np.float32)
    table = np.zeros((6144, 32), np.float32)
    table[:5000] = V
    valid = np.zeros(6144, bool)
    valid[:5000] = True
    import jax.numpy as jnp

    over = Int8CandidateIndex.over(jnp.asarray(table), valid, 5000,
                                   shortlist_k=64, seq=3, slots=128)
    built = build_index(V, shortlist_k=64, seq=3).reserve(6144, 128)
    for name in ("V", "Vq", "sv", "valid"):
        a, b = np.asarray(getattr(over, name)), np.asarray(getattr(built,
                                                                   name))
        assert a.shape == b.shape and np.array_equal(a, b), name
    assert (over.n_items, over.delta_slots, over.delta_count) == (
        5000, 128, 0)
    q = rng.normal(size=(4, 32)).astype(np.float32)
    for x, y in zip(over.topk(q, 10), built.topk(q, 10)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_publish_on_a_live_engine_rides_the_pins():
    """A whole publish on a generation made ready for a moving catalog
    keeps its capacities: every pinned program takes the new tables."""
    d = Deployment(seed=7)
    try:
        eng, m = d.eng, d.model
        before = [a.shape for a in _arrays_of(eng._model)]
        pins = dict(eng._pinned)
        ledger = compiles.install()
        mark = ledger.now()
        eng.publish(-m._U, m._V)
        assert [a.shape for a in _arrays_of(eng._model)] == before
        scores, ids = eng.recommend(5)
        assert ledger.since(mark)["programs"] == 0
        assert eng._pinned == pins          # none was refused and dropped
        want = np.sort(m._V @ -m._U[5])[::-1][:K]
        assert np.allclose(scores, want, rtol=2e-2, atol=2e-2)
    finally:
        d.stop()


def test_catalog_goes_up_in_chunks(monkeypatch):
    monkeypatch.setattr(core_foldin, "PLACE_CHUNK", 128)
    rng = np.random.default_rng(8)
    U = rng.normal(size=(50, 8)).astype(np.float32)
    V = rng.normal(size=(1000, 8)).astype(np.float32)
    valid = rng.random(1000) < 0.9
    eng = ServingEngine(k=5, buckets=(8,))
    eng.publish(U, V, item_valid=valid)
    assert np.array_equal(np.asarray(eng._model.V), V)
    assert np.array_equal(np.asarray(eng._model.valid), valid)
