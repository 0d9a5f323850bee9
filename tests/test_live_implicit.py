"""The live write path under IMPLICIT feedback (``implicitPrefs``), at a small
size: a fold reads ``F^T F`` of its whole fixed table, and the fold-in server
keeps that Gram matrix on the device beside each table — computed whole only
where the table is placed whole, moved by the rows every write-back wrote
(``core.foldin.write_rows(yty=)``).  Held here: the kept matrices against
``compute_yty`` of their tables after every batch, every published row against
the plain ``jax.numpy`` fold (``benchmark/reference/foldin_implicit.py``),
which whole-table programs run and when, the live deployment end to end
against the float64 replay (``foldin_implicit_replay.py``), the reference
itself, and that an explicit server keeps no Gram matrix and runs the parent's
row writes."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import foldin as ref_explicit
from benchmark.reference import foldin_implicit as ref_rule
from benchmark.reference import foldin_implicit_replay as ref_replay
from benchmark.reference.foldin_replay import published_of
from tests.conftest import CompileCount
from tests.test_live_items import batches_of, exact_topk, seeded_events
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs
from tpu_als.core import foldin
from tpu_als.core.ratings import row_capacity
from tpu_als.obs.schema import LIVE_FOLDIN_YTY_SCOPE, START_PHASES
from tpu_als.ops.solve import compute_yty
from tpu_als.serving import ServingEngine
from tpu_als.stream import microbatch

N_USERS, N_ITEMS, RANK, K = 600, 900, 16, 10
REG, ALPHA = 0.1, 40.0


def make_model(seed=0, implicit=True, n_users=N_USERS, n_items=N_ITEMS):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, RANK)).astype(np.float32)
    V = (rng.normal(size=(n_items, RANK)) / np.sqrt(RANK)).astype(np.float32)
    model = ALSModel(
        RANK, IdMap(ids=np.arange(n_users)), IdMap(ids=np.arange(n_items)),
        U.copy(), V.copy(),
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
         "implicitPrefs": implicit, "alpha": ALPHA, "nonnegative": False})
    return rng, U, V, model


def frame(events):
    u, i, r = zip(*events)
    return {"u": np.array(u), "i": np.array(i),
            "r": np.array(r, np.float32)}


def full_runs():
    return {(lb["side"], lb["when"]): v
            for lb, v in obs.counter_series("foldin.yty_full")}


class Mirror:
    """The rule kept plainly beside a ``FoldInServer``: every entity's
    ratings in arrival order and, for one batch and side, what the plain
    ``jax.numpy`` fold gives for each touched entity over its usable
    ratings — from the server's own host tables as they stand when asked
    (ask BEFORE the program folds that side)."""

    def __init__(self, model):
        self.m, self.hist = model, ({}, {})

    def expect(self, events, items_side):
        m, side = self.m, int(items_side)
        emap, fmap = ((m._item_map, m._user_map) if items_side
                      else (m._user_map, m._item_map))
        F = np.array(m._U if items_side else m._V)
        G = ref_rule.gram_jnp(F)
        for ev in events:
            self.hist[side].setdefault(ev[side], []).append(
                (ev[1 - side], ev[2]))
        want = {}
        for e in sorted({ev[side] for ev in events}):
            rows = fmap.to_dense([o for o, _ in self.hist[side][e]])
            ok = rows >= 0
            if ok.any():
                stars = np.array([r for _, r in self.hist[side][e]])[ok]
                want[e] = np.asarray(ref_rule.fold_jnp(
                    F, rows[ok], stars, REG, ALPHA, G))
        return emap, want

    def check(self, found, items_side, sample=None):
        emap, want = found
        table = self.m._V if items_side else self.m._U
        for e in sorted(want)[::sample or 1]:
            np.testing.assert_allclose(
                table[emap.to_dense([e])[0]], want[e], rtol=2e-4,
                atol=1e-6 * float(np.abs(want[e]).max()))
        return len(want)


def assert_kept_grams_are_their_tables(srv):
    """(a): each kept Gram matrix against ``compute_yty`` of its table as
    it lies on the device, spare rows and all, and against the host's."""
    for items_side, dev_attr, host in ((False, "_V", srv.model._V),
                                       (True, "_Ud", srv.model._U)):
        table = np.asarray(getattr(srv, dev_attr) + 0)
        np.testing.assert_array_equal(table[:len(host)], host)
        assert not table[len(host):].any()
        with jax.default_matmul_precision("highest"):
            want = np.asarray(compute_yty(jnp.asarray(table)))
        got = np.asarray(srv.yty(items_side))
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()


# (a) and (b): the server alone, batch by batch


def test_the_kept_grams_follow_both_tables_through_every_kind_of_batch():
    obs.reset()
    rng, U, V, model = make_model(seed=1)
    srv = FoldInServer(model)
    srv.prewarm(rows=(8, 64), sides=("user", "item"))
    assert full_runs() == {("item", "start"): 1, ("user", "start"): 1}
    assert_kept_grams_are_their_tables(srv)
    mirror, next_user, next_item, folds = Mirror(model), N_USERS, N_ITEMS, 0
    rows0 = {lb["side"]: v for lb, v in obs.counter_series("foldin.yty_rows")}
    for b in range(8):
        events = []
        for j in range(int(rng.integers(3, 14))):      # pads 8 and 64
            user, item = int(rng.integers(0, 40)), int(rng.integers(0, 60))
            x = rng.random()
            if x < 0.2:
                user, next_user = next_user, next_user + 1
            elif x < 0.4:
                item, next_item = next_item, next_item + 1
            elif x < 0.5 and next_item > N_ITEMS:      # a new item, again
                item = int(rng.integers(N_ITEMS, next_item))
            events.append((user, item, float(rng.integers(1, 6))))
        for items_side, fold in ((False, srv.update), (True,
                                                       srv.update_items)):
            expected = mirror.expect(events, items_side)
            touched = fold(frame(events))
            assert sorted(touched.tolist()) == sorted(expected[1])
            folds += mirror.check(expected, items_side)
            assert_kept_grams_are_their_tables(srv)
    assert folds > 100 and next_user > N_USERS + 5 and next_item > N_ITEMS + 5
    moved = {lb["side"]: v - rows0.get(lb["side"], 0)
             for lb, v in obs.counter_series("foldin.yty_rows")}
    assert moved["user"] > 40 and moved["item"] > 40
    # (b) rows were written, and no table was read whole again
    assert full_runs() == {("item", "start"): 1, ("user", "start"): 1}


def test_past_the_spare_rows_the_gram_is_computed_whole_with_its_table():
    obs.reset()
    rng, U, V, model = make_model(seed=2)
    srv = FoldInServer(model)
    srv.prewarm(rows=(8,), sides=("user", "item"))
    cap = row_capacity(N_USERS)
    assert int(srv._Ud.shape[0]) == cap
    mirror = Mirror(model)
    # more new users than the user table has spare rows, one batch
    events = [(N_USERS + j, int(rng.integers(0, N_ITEMS)),
               float(rng.integers(1, 6))) for j in range(cap - N_USERS + 3)]
    for items_side, fold in ((False, srv.update), (True, srv.update_items)):
        expected = mirror.expect(events, items_side)
        fold(frame(events))
        assert mirror.check(expected, items_side, sample=37) > 8
        assert_kept_grams_are_their_tables(srv)
    assert int(srv._Ud.shape[0]) > cap
    assert full_runs() == {("item", "start"): 1, ("user", "start"): 1,
                           ("user", "placed"): 1}
    # and on from the re-placed table by rows again
    events = [(5, 7, 4.0), (N_USERS + 1, N_ITEMS, 5.0), (9, N_ITEMS, 1.0)]
    for items_side, fold in ((False, srv.update), (True, srv.update_items)):
        expected = mirror.expect(events, items_side)
        fold(frame(events))
        mirror.check(expected, items_side)
        assert_kept_grams_are_their_tables(srv)
    assert sum(full_runs().values()) == 3


def test_after_start_no_program_reads_a_table_whole(monkeypatch):
    """(b) by the calls themselves: ``whole_yty`` runs where a table is
    placed, the fold program is always HANDED its Gram matrix, and nothing
    on the write path calls ``compute_yty`` over a table."""
    obs.reset()
    rng, U, V, model = make_model(seed=3)
    whole, handed = [], []
    real_whole, real_fold = microbatch.whole_yty, microbatch.fold_in

    def counted(table):
        whole.append(tuple(table.shape))
        return real_whole(table)

    def watched(F, *rows, **how):
        handed.append(how["YtY"] is not None)
        return real_fold(F, *rows, **how)

    monkeypatch.setattr(microbatch, "whole_yty", counted)
    monkeypatch.setattr(microbatch, "fold_in", watched)
    srv = FoldInServer(model)
    srv.prewarm(rows=(8,), sides=("user", "item"))
    assert whole == [(row_capacity(N_ITEMS), RANK),
                     (row_capacity(N_USERS), RANK)]
    names = {e["name"] for e in obs.default_registry()._events
             if e["type"] == "span"}
    assert "start.foldin_server.yty" in names
    assert "start.foldin_server.yty" in START_PHASES
    compiles = CompileCount()
    for b in range(5):
        events = [(int(rng.integers(0, N_USERS + 2)),
                   int(rng.integers(0, N_ITEMS + 2)),
                   float(rng.integers(1, 6))) for _ in range(6)]
        srv.update(frame(events))
        srv.update_items(frame(events))
    assert len(whole) == 2 and all(handed) and len(handed) > 10
    assert compiles.n == 0


def test_a_growth_prewarm_runs_the_grown_tables_whole_program_at_start():
    obs.reset()
    _, _, _, model = make_model(seed=4)
    srv = FoldInServer(model)
    srv.prewarm(rows=(8,), sides=("user",), growth=1)
    # V^T V at construction, and the program a re-placement would run
    assert full_runs() == {("item", "start"): 2}


def test_the_gram_carrying_write_is_the_plain_write_and_the_update():
    rng = np.random.default_rng(5)
    table = rng.normal(size=(64, RANK)).astype(np.float32)
    table[50:] = 0.0
    vals = rng.normal(size=(8, RANK)).astype(np.float32)
    rows = [3, 55, 17]                  # 55: a spare row; 5 rows of padding
    G0 = foldin.whole_yty(jnp.asarray(table))
    want = table.copy()
    want[rows] = vals[:3]
    for write in (
            lambda t, g: foldin.write_rows(t, rows, vals[:3], yty=g),
            lambda t, g: foldin.write_placed_rows(t, rows, jnp.asarray(vals),
                                                  yty=g)):
        out, G = write(jnp.asarray(table), G0)
        np.testing.assert_array_equal(np.asarray(out), want)
        np.testing.assert_allclose(
            np.asarray(G), want.astype(np.float64).T @ want, rtol=1e-5,
            atol=1e-4)
    plain = foldin.write_rows(jnp.asarray(table), rows, vals[:3])
    np.testing.assert_array_equal(np.asarray(plain), want)
    # no rows: the matrix comes back bit for bit
    _, same = foldin.write_rows(jnp.asarray(table), [], vals[:0], pad=8,
                                yty=G0)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(G0))
    text = foldin._scatter_rows_yty.lower(
        jnp.asarray(table), G0, jnp.zeros(8, jnp.int32),
        jnp.asarray(vals)).compile().as_text()
    assert LIVE_FOLDIN_YTY_SCOPE in text and "live.foldin.scatter" in text


def test_whole_yty_in_steps_is_the_one_product(monkeypatch):
    rng = np.random.default_rng(6)
    table = rng.normal(size=(1000, RANK)).astype(np.float32)
    want = table.astype(np.float64).T @ table
    monkeypatch.setattr(foldin, "YTY_CHUNK", 256)     # 3 steps and a tail
    jax.clear_caches()
    try:
        got = np.asarray(foldin.whole_yty(jnp.asarray(table)))
    finally:
        jax.clear_caches()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


# (c) the live deployment end to end, the ten guarantees


def make_stack(seed=0, max_batch=8, max_wait_ms=2.0):
    rng, U, V, model = make_model(seed)
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=N_ITEMS,
                        max_wait_s=0.0)
    eng.publish(U, V)
    srv = FoldInServer(model)
    srv.prewarm(rows=(max_batch,), sides=("user", "item"))
    eng.warmup()
    upd = LiveUpdater(eng, srv, max_batch=max_batch, max_wait_ms=max_wait_ms,
                      fold_items=True, flight_capacity=4096)
    return rng, U, V, model, eng, srv, upd


@pytest.fixture(scope="module")
def streamed():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=0)
    events = seeded_events(rng, 400)
    compiles = CompileCount()
    published, publish = [], eng.publish_update

    def tapped(U, V, *, touched_items=None, touched_users=None, **kw):
        out = publish(U, V, touched_items=touched_items,
                      touched_users=touched_users, **kw)
        published.append(tuple(
            dict(zip(ids.to_original(np.asarray(rows, np.int64)).tolist(),
                     np.array(table[np.asarray(rows, np.int64)])))
            for ids, rows, table in (
                (model._user_map, touched_users, U),
                (model._item_map, () if touched_items is None
                 else touched_items, V))))
        return out

    eng.publish_update = tapped
    eng.start()
    upd.start()
    warm, full0 = compiles.n, full_runs()
    try:
        for j, (u, i, r) in enumerate(events):
            upd.submit(u, i, r)
            if j % 10 == 0:
                eng.recommend(int(rng.integers(0, N_USERS)), timeout=10.0)
    finally:
        upd.stop(drain_timeout_s=30.0)
    users, items, stars = zip(*events)
    sizes = [len(b) for b in batches_of(upd, events)]
    rep = ref_replay.replay(U, V, users, items, stars, sizes, REG, ALPHA,
                            published=published)
    yield dict(reg=reg, rng=rng, U0=U, V0=V, model=model, eng=eng, srv=srv,
               upd=upd, events=events, compiled=compiles.n - warm,
               full0=full0, rep=rep,
               # (before any test's own jax.numpy runs beside the engine)
               warnings=[e for e in reg._events if e["type"] == "warning"])
    eng.stop()


def test_every_published_row_is_the_implicit_fold_of_its_ratings(streamed):
    """(2), (3), (7): every fold the rule asks for was published and none
    besides, each within a float32 fold of the float64 IMPLICIT fold of the
    same ratings over the same published rows with the replay's own G —
    and far from the explicit rule's."""
    rep, m = streamed["rep"], streamed["model"]
    assert rep.missing == rep.unasked == 0
    errs = rep.fold_err[0] + rep.fold_err[1]
    assert len(errs) > 300 and max(errs) < 2e-4
    assert len(m._user_map) == N_USERS + len(rep.dense_users)
    assert len(m._item_map) == rep.n_items[-1] > N_ITEMS + 20
    for side, table, emap in ((0, m._U, m._user_map),
                              (1, m._V, m._item_map)):
        rows = (rep.user_rows, rep.item_rows)[side]
        ids = sorted(rows)
        np.testing.assert_array_equal(
            table[emap.to_dense(ids)],
            np.array([rows[e] for e in ids], np.float32))
    # the explicit fold of a user's ratings is another row altogether
    u = next(u for u, _, _ in streamed["events"] if u < N_USERS)
    mine = [(i, r) for w, i, r in streamed["events"] if w == u]
    Vf = rep.final_catalog()
    explicit = ref_explicit.fold(Vf, *zip(*mine), REG)
    assert np.linalg.norm(rep.user_rows[u] - explicit) > 0.5 * np.linalg.norm(
        explicit)


def test_every_rating_enters_each_fold_once_and_none_is_shed(streamed):
    reg, events = streamed["reg"], streamed["events"]
    assert reg.histogram_count("live.freshness_seconds") == len(events)
    assert reg.counter_value("live.shed") == 0
    assert reg.counter_value("foldin.ratings") == streamed["rep"].entered
    assert streamed["rep"].entered <= 2 * len(events)


def test_the_kept_grams_after_the_drain_are_the_final_tables(streamed):
    """(10): read back, against the float64 Gram matrices of the final
    published tables; the replay's own moved matrices against the same."""
    rep, srv = streamed["rep"], streamed["srv"]
    assert max(rep.gram_drift()) < 1e-12
    for side, items_side in ((0, True), (1, False)):
        want = ref_rule.gram(rep.final_table(side))
        assert ref_replay.rel_err(np.asarray(srv.yty(items_side)),
                                  want) < 5e-6
        # and the start's value is far from it: a frozen matrix shows
        start = ref_rule.gram((streamed["U0"], streamed["V0"])[side])
        assert ref_replay.rel_err(start, want) > 1e-2
    assert_kept_grams_are_their_tables(srv)


def test_nothing_compiles_and_no_table_is_read_whole_under_traffic(streamed):
    assert streamed["compiled"] == 0
    assert full_runs() == streamed["full0"] == {("item", "start"): 1,
                                                ("user", "start"): 1}
    reg = streamed["reg"]
    assert not streamed["warnings"]
    moved = dict((lb["side"], v)
                 for lb, v in reg.counter_series("foldin.yty_rows"))
    recs = [r for r in streamed["upd"].flight.records()
            if r.get("status") == "ok"]
    assert moved["item"] == sum(r["items"] for r in recs)
    assert moved["user"] >= len(recs)


def test_the_engines_answers_are_the_replays(streamed):
    """(1), (4)-(6), (8): by id for touched and new users, and by a vector
    along each new item's own factor: the exact top-k of the replay's final
    catalog, each id with its own score."""
    eng, m, rep = streamed["eng"], streamed["model"], streamed["rep"]
    Vf = rep.final_catalog()
    V = dict(enumerate(Vf))
    touched = sorted(rep.user_rows)[:40]
    queries = [(int(m._user_map.to_dense([u])[0]), rep.user_rows[u])
               for u in touched]
    new_items = [i for i in sorted(rep.item_rows) if i >= N_ITEMS][:20]
    for i in new_items:         # at a planted row's length
        q = rep.item_rows[i] / np.linalg.norm(rep.item_rows[i]) * 4.0
        queries.append((q.astype(np.float32), q))
    assert len(new_items) == 20 and len(touched) == 40
    for payload, q in queries:
        s, ix = eng.recommend(payload, timeout=10.0)
        want_s, want_i = exact_topk(q, V, len(Vf))
        scale = float(np.abs(want_s).max())
        own = Vf[ix] @ np.asarray(q, np.float64)
        np.testing.assert_allclose(s, own, rtol=1e-3, atol=1e-3 * scale)
        np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-3 * scale)
        assert len(set(ix.tolist()) & set(want_i.tolist())) >= K - 1
    assert eng.published_index.n_items == len(Vf)
    # a ticket names the ONE generation that answered it
    t = eng.submit(int(m._user_map.to_dense([touched[0]])[0]))
    t.result(timeout=10.0)
    assert t.seq is not None and t.seq >= len(rep.n_items)


# (d) the reference itself


def test_the_float64_reference_is_the_jax_numpy_one():
    rng = np.random.default_rng(7)
    F = rng.standard_normal((500, RANK)).astype(np.float32)
    G = ref_rule.gram(F, block=128)
    np.testing.assert_allclose(G, F.astype(np.float64).T @ F, rtol=1e-12)
    np.testing.assert_allclose(ref_rule.gram_jnp(F), G, rtol=2e-6, atol=1e-4)
    for n in (1, 9, 70):
        ids = rng.choice(500, n, replace=False)
        r = rng.integers(1, 6, n).astype(np.float32)
        r[::4] *= -1       # confidence without a vote, out of n_pos
        x = ref_rule.fold(F, ids, r, REG, ALPHA, G)
        np.testing.assert_allclose(
            ref_rule.fold_jnp(F, ids, r, REG, ALPHA, G), x, rtol=2e-4,
            atol=1e-6)
        Fk, c = F[ids].astype(np.float64), 1 + ALPHA * np.abs(r)
        p = (r > 0).astype(float)
        A = G + (Fk * (c - 1)[:, None]).T @ Fk + REG * p.sum() * np.eye(RANK)
        np.testing.assert_allclose(A @ x, Fk.T @ (c * p), rtol=1e-9,
                                   atol=1e-9)


def test_the_replays_moved_gram_is_gram_of_its_final_tables():
    rng = np.random.default_rng(8)
    U0 = rng.standard_normal((30, 6)).astype(np.float32)
    V0 = (rng.standard_normal((50, 6)) / 2).astype(np.float32)
    users, items = rng.integers(0, 34, 120), rng.integers(0, 55, 120)
    stars = rng.integers(1, 6, 120).astype(np.float32)
    sizes = [7] * 16 + [8]
    free = ref_replay.replay(U0, V0, users, items, stars, sizes, REG, ALPHA)
    journal = [tuple({e: x.astype(np.float32) for e, x in side.items()}
                     for side in batch) for batch in published_of(free, 17)]
    rep = ref_replay.replay(U0, V0, users, items, stars, sizes, REG, ALPHA,
                            published=journal)
    assert rep.missing == rep.unasked == 0 and rep.entered == free.entered
    assert len(rep.user_rows) > 20 and len(rep.item_rows) > 30
    assert rep.final_table(0).shape[0] > 30 < rep.final_table(1).shape[0] - 20
    assert max(rep.gram_drift()) < 1e-12
    # one precision step down is seen in every fold
    low = ref_replay.replay(U0, V0, users, items, stars, sizes, REG, ALPHA,
                            operand_dtype="bfloat16", gram_dtype="bfloat16")
    held = ref_replay.replay(U0, V0, users, items, stars, sizes, REG, ALPHA,
                             published=published_of(low, 17))
    assert float(np.median(held.fold_err[0] + held.fold_err[1])) > 1e-4


# (e) an explicit server is the parent's


# sha256 of the lowered (StableHLO) text of ``core.foldin._scatter_rows`` at
# the live cells' shapes (user table 1,730,560 rows, catalog 1,529,856; pads
# 8 / 64 / 512), taken on the parent commit (a5db68a): the plain row write
# every explicit server runs did not move when the Gram-carrying one came
PARENT_SCATTER_ROWS = {
    (1730560, 8): "9a08e837def2d537", (1730560, 64): "4b822ba2f1f64e18",
    (1730560, 512): "54934504c269471d",
    (1529856, 8): "abc7abccf03d3958", (1529856, 64): "e1991ce7039d28bd",
    (1529856, 512): "f732f4a4103ce3df",
}


@pytest.mark.parametrize("cap,pad", sorted(PARENT_SCATTER_ROWS))
def test_the_plain_row_write_lowers_to_the_parents_text(cap, pad):
    assert cap in (row_capacity(1703438), row_capacity(1505938))
    text = foldin._scatter_rows.lower(
        jax.ShapeDtypeStruct((cap, 256), jnp.float32),
        jax.ShapeDtypeStruct((pad,), jnp.int32),
        jax.ShapeDtypeStruct((pad, 256), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_SCATTER_ROWS[cap, pad]


def test_an_explicit_server_keeps_no_gram_and_runs_the_plain_writes(
        monkeypatch):
    obs.reset()
    rng, U, V, model = make_model(seed=9, implicit=False)

    def never(*a, **k):
        raise AssertionError("an explicit server ran a Gram program")

    monkeypatch.setattr(microbatch, "whole_yty", never)
    monkeypatch.setattr(foldin, "_scatter_rows_yty", never)
    plain, real = [], foldin._scatter_rows
    monkeypatch.setattr(foldin, "_scatter_rows",
                        lambda *a: plain.append(1) or real(*a))
    srv = FoldInServer(model)
    srv.prewarm(rows=(8,), sides=("user", "item"))
    warmed = len(plain)
    assert warmed == 4                  # two tables, both forms of the call
    for b in range(3):
        events = [(int(rng.integers(0, N_USERS + 2)),
                   int(rng.integers(0, N_ITEMS + 2)),
                   float(rng.integers(1, 6))) for _ in range(6)]
        srv.update(frame(events))
        srv.update_items(frame(events))
    assert len(plain) == warmed + 6
    assert srv.yty() is None and srv.yty(items_side=True) is None
    assert srv._yty == {}
    assert not obs.counter_series("foldin.yty_full")
    assert not obs.counter_series("foldin.yty_rows")
    names = {e["name"] for e in obs.default_registry()._events
             if e["type"] == "span"}
    assert "start.foldin_server.yty" not in names
