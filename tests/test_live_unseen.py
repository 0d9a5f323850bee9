"""The live deployment that knows its histories (PR 42), at small size on
the CPU, against ``benchmark/reference/live_unseen.py`` (numpy float64,
nothing of the program):

(a) a fold is over the user's WHOLE history — the resident ratings and the
    run's events — at widths on both sides of every pad boundary and for a
    user who crosses the top resident rung;
(b) after each publish a request by id excludes the resident AND the
    appended ids and nothing else, a user appended in the run included, and
    the answer's ``Ticket.seq`` tells which generation that was;
(c) the swap is one generation: a reader hammering one user across 200
    publishes never sees a row with another generation's history;
(d) nothing compiles after ``warmup_live``, across appends that cross every
    pad and move runs;
(e) an event on an item its user has rated replaces that rating;
(f) the control: histories frozen at publish fail the benchmark's checks;
(g) is in ``tests/test_chip_compile.py`` (the programs' text).
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import live_unseen as ref  # noqa: E402
from tests.conftest import CompileCount  # noqa: E402
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs  # noqa: E402
from tpu_als.core.ratings import growth_pads, rung_for  # noqa: E402
from tpu_als.obs.schema import (  # noqa: E402
    LIVE_BATCH_SPAN_KEYS,
    LIVE_FOLDIN_SPAN_KEYS,
    LIVE_HISTORY_SPAN_KEYS,
    LIVE_PHASE_SPAN_KEYS,
)
from tpu_als.serving import ServingEngine  # noqa: E402
from tpu_als.serving.engine import history_pads  # noqa: E402
from tpu_als.stream import microbatch  # noqa: E402

K, RANK, REG = 10, 16, 0.1
N_USERS, N_ITEMS = 96, 4096
# resident lengths on both sides of the fold's pads (8 / 64 / 512) and of
# the engine's (64 / 512); the longest, 512, fills the top resident rung:
# the rung above it (1,024) is what its growth rides
LENGTHS = [0, 1, 7, 8, 9, 62, 63, 64, 65, 500, 511, 512]
TOP = 1024


def wait_for(pred, timeout=30.0):
    deadline = time.perf_counter() + timeout
    while not pred() and time.perf_counter() < deadline:
        time.sleep(0.002)
    assert pred(), "condition not reached before the timeout"


def make_stack(seed=0, max_batch=8, quantize=True, base=True, buckets=(8,)):
    """``(rng, V, hist, model, engine, server, updater)``: factors planted
    from the histories (a user's own items score highest: an engine that
    forgot an id would return it), the engine published with them."""
    rng = np.random.default_rng(seed)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    lengths = np.array((LENGTHS * (N_USERS // len(LENGTHS) + 1))[:N_USERS])
    items = [np.sort(rng.choice(N_ITEMS, n, replace=False)) for n in lengths]
    indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    indices = np.concatenate(items).astype(np.int32)
    stars = rng.integers(1, 6, len(indices)).astype(np.float32)
    U = np.stack([(stars[indptr[u]:indptr[u + 1], None] * V[items[u]]).sum(0)
                  if lengths[u] else V[u] for u in range(N_USERS)]
                 ).astype(np.float32)
    model = ALSModel(
        RANK, IdMap(ids=np.arange(N_USERS)), IdMap(ids=np.arange(N_ITEMS)),
        U.copy(), V.copy(),
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
         "implicitPrefs": False, "alpha": 1.0, "nonnegative": False})
    eng = ServingEngine(k=K, buckets=buckets, shortlist_k=256,
                        max_wait_s=0.0)
    eng.publish(U, V, user_seen=(indptr, indices), quantize=quantize)
    srv = FoldInServer(model, base_history=(indptr, indices, stars)
                       if base else None)
    upd = LiveUpdater(eng, srv, max_batch=max_batch, max_wait_ms=2.0)
    return rng, V, ref.Histories(indptr, indices, stars), model, eng, srv, upd


def unrated(rng, hist, user, n=1):
    """``n`` items ``user`` has not rated, as of now."""
    have = set(hist.ids(user).tolist())
    out = []
    while len(out) < n:
        i = int(rng.integers(0, N_ITEMS))
        if i not in have:
            have.add(i)
            out.append(i)
    return out


def frame(users, items, stars):
    return {"u": np.asarray(users), "i": np.asarray(items),
            "r": np.asarray(stars, np.float32)}


# -- the ladders ---------------------------------------------------------------

def test_the_ladder_gets_one_rung_for_the_growth_of_its_longest():
    assert growth_pads(4096) == (8, 64, 512, 4096, 8192)
    assert growth_pads(3000) == (8, 64, 512, 4096)
    assert growth_pads(512) == (8, 64, 512, TOP)
    assert history_pads(4096) == (64, 512, 4096)
    assert history_pads(4096, grows=True) == (64, 512, 4096, 8192)
    assert history_pads(3) == (64,)
    assert history_pads(3, grows=True) == (64, 128)
    assert rung_for(4097, growth_pads(4096)) == 8192
    assert rung_for(9000, growth_pads(4096)) == 32768     # nothing warmed
    assert rung_for(5, ()) == 8


# -- (a) the fold is over the whole history ------------------------------------

@pytest.mark.parametrize("length", [7, 8, 63, 64, 511, 512])
def test_a_fold_is_over_resident_history_and_events(length):
    """Two events for a user whose resident history ends at a pad's edge:
    the first fold rides the rung that holds ``length + 1``, the second
    may cross into the next — for 512 the rung above every resident
    history — and each row is the float64 fold of ALL the ratings."""
    rng, V, hist, model, eng, srv, _ = make_stack(seed=length)
    user = LENGTHS.index(length)
    assert len(hist.ids(user)) == length
    for seq in (1, 2):
        item, = unrated(rng, hist, user)
        star = float(rng.integers(1, 6))
        touched = srv.update(frame([user], [item], [star]))
        hist.publish(seq, [user], [item], [star])
        assert list(touched) == [user]
        want = ref.fold(V, hist, user, REG)
        assert ref.row_rel_err(model._U[user], want) < 1e-4
        assert srv.stats[-1][3] == rung_for(length + seq, srv._widths)
        who, what = srv.last_appended
        assert list(who) == [user] and list(what) == [item]
    # NOT the fold over the events alone, which is what a server without
    # the resident history publishes
    alone = ref.fold_events_only(V, hist, user, REG)
    assert ref.row_rel_err(model._U[user], alone) > 0.05


def test_many_long_histories_go_in_several_calls(monkeypatch):
    """A batch whose gather would pass ``FOLD_ELEMENTS`` is folded longest
    first, each call as many users as its width allows; the rows are those
    of one call."""
    monkeypatch.setattr(microbatch, "FOLD_ELEMENTS", 8 * TOP)
    rng, V, hist, model, eng, srv, _ = make_stack(seed=3)
    # 16 users whose histories pass 512 with this batch (those at 511 rate
    # twice), three short ones
    users = [u for u in range(N_USERS) if len(hist.ids(u)) == 512]
    users += [u for u in range(N_USERS) if len(hist.ids(u)) == 511] * 2
    users += [1, 2, 3]
    assert len(set(users)) == 19
    items = []
    for u in users:
        items.append(next(i for i in unrated(rng, hist, u, 2)
                          if (u, i) not in zip(users, items)))
    stars = rng.integers(1, 6, len(users)).astype(np.float32)
    widths0 = obs.histogram_count("foldin.history_width", side="user")
    srv.update(frame(users, items, stars))
    hist.publish(1, users, items, stars)
    for u in set(users):
        assert ref.row_rel_err(model._U[u], ref.fold(V, hist, u, REG)) < 1e-4
    # the 16 at the top rung eight a call, the three short ones after
    assert obs.histogram_count("foldin.history_width",
                               side="user") - widths0 == 3
    assert srv._rows_at(TOP) == 8 and srv._rows_at(512) is None


def test_a_base_history_needs_keep_history():
    rng, V, hist, model, *_ = make_stack()
    with pytest.raises(ValueError, match="keep_history"):
        FoldInServer(model, keep_history=False,
                     base_history=(hist.indptr, hist.indices, hist.stars))


# -- (e) one rating a user and item ---------------------------------------------

def test_rating_an_item_again_replaces_the_rating_and_adds_no_id():
    rng, V, hist, model, eng, srv, _ = make_stack(seed=5)
    user = LENGTHS.index(63)
    old = int(hist.ids(user)[10])           # a resident rating
    new, = unrated(rng, hist, user)
    folded0 = obs.counter_value("foldin.ratings")
    # one batch: the resident item again, a new item, the new item again
    events = ([user] * 3, [old, new, new], [1.0, 5.0, 2.0])
    srv.update(frame(*events))
    hist.publish(1, *events)
    items, stars = hist.ratings(user)
    assert len(items) == 64 and stars[10] == 1.0 and stars[-1] == 2.0
    assert ref.row_rel_err(model._U[user], ref.fold(V, hist, user, REG)) < 1e-4
    who, what = srv.last_appended
    assert list(who) == [user] and list(what) == [new]
    # every admitted event was folded, one of them into a new id
    assert obs.counter_value("foldin.ratings") - folded0 == 3
    # the next batch: the run's own rating again
    srv.update(frame([user], [new], [4.0]))
    hist.publish(2, [user], [new], [4.0])
    assert len(srv.last_appended[0]) == 0
    assert ref.row_rel_err(model._U[user], ref.fold(V, hist, user, REG)) < 1e-4
    # a server without a base history keeps every event as a rating
    *_, plain, _ = make_stack(seed=5, base=False)
    plain.update(frame([user] * 2, [new, new], [5.0, 2.0]))
    assert len(plain.last_appended[0]) == 2
    assert len(plain.history_of(user)[0]) == 2


# -- (b) (d) the deployment, event by event -------------------------------------

@pytest.fixture(scope="module")
def streamed():
    """The stack as its users wire it, 90 events one by one — for users at
    every pad's edge, the one at the top resident rung, and two users the
    model has never seen — each followed by a request by id for its user
    once its publish is out."""
    reg = obs.reset()
    rng, V, hist, model, eng, srv, upd = make_stack(seed=11)
    srv.prewarm(rows=(8,))
    upd.start()             # warmup_publish + warmup_live
    eng.start()
    compiles = CompileCount()
    new_a, new_b = N_USERS + 7, N_USERS + 3
    edge = [LENGTHS.index(n) for n in (0, 8, 63, 64, 511, 512)]
    asked, moved0 = [], obs.counter_value("live.history_relocations")
    try:
        for step in range(90):
            user = (edge + [new_a, new_b])[step % 8]
            item, = unrated(rng, hist, user)
            star = float(rng.integers(1, 6))
            seq0 = eng.published_seq
            upd.submit(user, item, star)
            wait_for(lambda: eng.published_seq > seq0)
            hist.publish(eng.published_seq, [user], [item], [star])
            row = int(model._user_map.to_dense([user])[0])
            t = eng.submit(row)
            scores, ids = t.result(timeout=10.0)
            # the publish that folded the event is the one that answers
            assert t.seq == eng.published_seq == seq0 + 1
            asked.append((user, row, t.seq, np.array(scores), np.array(ids),
                          model._U[row].copy()))
    finally:
        upd.stop(drain_timeout_s=30.0)
    out = dict(reg=reg, V=V, hist=hist, model=model, eng=eng, asked=asked,
               compiled=compiles.n, new=(new_a, new_b),
               moved=obs.counter_value("live.history_relocations") - moved0)
    yield out
    eng.stop()


def test_every_answer_excludes_the_history_of_its_generation(streamed):
    V, hist = streamed["V"], streamed["hist"]
    for user, row, seq, scores, ids, x in streamed["asked"]:
        mine = hist.ids(user, seq)
        assert len(mine) < len(hist.ids(user)) or seq >= 83
        assert not set(ids.tolist()) & set(mine.tolist())
        _, want = ref.exact_topk_left(x[None], V, K, [mine])
        # ... and nothing else: the exact top-k of the ids LEFT
        assert set(ids.tolist()) == set(want[0].tolist()), (user, seq)
        assert np.abs(scores - x.astype(np.float64) @ V[ids].T).max() < 1e-4
        assert ref.row_rel_err(x, ref.fold(V, hist, user, REG, seq)) < 1e-4


def test_a_user_appended_in_the_run_has_a_history_too(streamed):
    model, hist, eng = streamed["model"], streamed["hist"], streamed["eng"]
    seen = eng._model.seen
    for user in streamed["new"]:
        row = int(model._user_map.to_dense([user])[0])
        assert row >= N_USERS
        assert seen.lengths[row] == len(hist.ids(user)) >= 11
    # arrays of one shape all through: what warmup_live laid out
    assert seen.runs[0].shape == seen.runs[1].shape == (eng._model.U.shape[0],)
    assert seen.pads == (64, 512, TOP)


def test_nothing_compiles_after_warmup_live(streamed):
    """Appends crossed the pads 64 and 512 (the users at 63 and 511), the
    top resident rung (the user at 512), gave two new users their first
    runs and moved runs that were full: no program was compiled."""
    assert streamed["compiled"] == 0
    assert streamed["moved"] >= 2       # the new users' runs outgrew 8 + 8
    widths = {u: len(streamed["hist"].ids(u)) for u, *_ in streamed["asked"]}
    assert widths[LENGTHS.index(512)] > 512
    assert widths[LENGTHS.index(63)] > 64


def test_a_publish_sends_what_it_appends(streamed):
    """O(ids appended): 160 bytes a publish of one id (five int32 entries
    padded to 8), whatever the history holds."""
    reg = streamed["reg"]
    assert reg.counter_value("live.history_appended_ids") == 90
    sent = reg.counter_value("live.history_h2d_bytes")
    assert sent <= 90 * 160 + 8 * streamed["moved"]


# -- (c) one generation, both halves --------------------------------------------

def test_the_row_and_the_id_are_swapped_in_together():
    """A reader asks for ONE user as fast as it can while 200 publishes
    each give that user a new row AND add the item that row ranks first:
    every answer is the exact top-k of ONE generation — its row's scores
    over the ids its history leaves — whichever generation answered."""
    rng, V, hist, model, eng, srv, _ = make_stack(seed=7, quantize=False)
    eng.warmup_publish(8)
    eng.warmup_live(max_rows=8)
    eng.start()
    user, n = LENGTHS.index(9), 200
    Uh = np.zeros((eng._model.U.shape[0], RANK), np.float32)
    Uh[:N_USERS] = model._U
    rows = {eng.published_seq: Uh[user].copy()}
    answers, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            t = eng.submit(user)
            scores, ids = t.result(timeout=10.0)
            answers.append((t.seq, np.array(scores), np.array(ids)))

    thread = threading.Thread(target=reader)
    thread.start()
    try:
        for _ in range(n):
            x = rng.standard_normal(RANK).astype(np.float32)
            s = x.astype(np.float64) @ V.T.astype(np.float64)
            s[hist.ids(user)] = -np.inf
            item = int(np.argmax(s))        # what the new row wants most
            Uh[user] = x
            seq, _ = eng.publish_update(
                Uh[:N_USERS], V, touched_users=[user],
                seen_appended=([user], [item]))
            rows[seq] = x
            hist.publish(seq, [user], [item], [5.0])
    finally:
        stop.set()
        thread.join(30.0)
        eng.stop()
    assert len({a[0] for a in answers}) > 20, "the reader saw few generations"
    for seq, scores, ids in answers:
        x = rows[seq].astype(np.float64)
        own = x @ V[ids].T.astype(np.float64)
        # the row of generation seq ...
        assert np.abs(scores - own).max() < 1e-4 * np.abs(own).max(), seq
        # ... over the history of generation seq, nothing more or less
        _, want = ref.exact_topk_left(x[None], V, K, [hist.ids(user, seq)])
        assert list(ids) == list(want[0]), seq
    m = eng._model
    assert m.seen.lengths[user] == 9 + n


# -- what is still refused, and the timeline ------------------------------------

def test_what_histories_still_refuse_says_so():
    """Since PR 47 a generation that holds histories takes a catalog that
    moves — a row named, a row appended, an updater that folds items
    (``tests/test_live_items_unseen.py`` holds the answers to the
    reference) — and what is left refused is an id for a generation that
    holds no history, and an id outside the catalog."""
    rng, V, hist, model, eng, srv, upd = make_stack(seed=2)
    Uh = model._U
    assert eng.publish_update(Uh, V, touched_items=[0],
                              touched_users=[0]) == (2, "delta")
    longer = np.concatenate([V, V[:1]])
    assert eng.publish_update(Uh, longer, touched_users=[0]) == (3, "delta")
    assert eng._model.n_items == N_ITEMS + 1
    # an id may name the row this very publish appends, and no further
    eng.publish_update(Uh, np.concatenate([longer, V[:1]]), touched_users=[0],
                       seen_appended=([0], [N_ITEMS + 1]))
    with pytest.raises(ValueError, match="seen_appended"):
        eng.publish_update(Uh, np.concatenate([longer, V[:1]]),
                           touched_users=[0],
                           seen_appended=([0], [N_ITEMS + 2]))
    items = LiveUpdater(eng, srv, fold_items=True).start()
    items.stop()
    assert eng.published_index.delta_slots > 0
    assert eng.published_seq == 4           # the warm-ups published nothing
    plain = ServingEngine(k=K, buckets=(8,), shortlist_k=256)
    plain.publish(Uh, V)
    with pytest.raises(NotImplementedError, match="user_seen"):
        plain.publish_update(Uh, V, touched_users=[0],
                             seen_appended=([0], [1]))
    with pytest.raises(ValueError, match="seen_appended"):
        eng.publish_update(Uh, V, touched_users=[0],
                           seen_appended=([0], [N_ITEMS]))


def test_an_engine_nobody_warmed_lays_the_histories_out_at_the_first_publish():
    """``publish_update`` on histories as published (no ``warmup_live``):
    laid out to grow then, with a warning, and the answer is right."""
    reg = obs.reset()
    rng, V, hist, model, eng, srv, _ = make_stack(seed=4)
    user = LENGTHS.index(64)
    item, = unrated(rng, hist, user)
    assert eng._model.seen.room is None
    eng.publish_update(model._U, V, touched_users=[user],
                       seen_appended=([user], [item]))
    hist.publish(2, [user], [item], [5.0])
    assert eng._model.seen.room is not None
    warn, = [e for e in reg._events if e["type"] == "warning"
             and e["what"] == "serving.publish_update"]
    assert "laid out anew" in warn["reason"]
    scores, ids = _serve_one(eng, user)
    assert item not in ids
    _, want = ref.exact_topk_left(model._U[user][None], V, K,
                                  [hist.ids(user)])
    assert set(ids.tolist()) == set(want[0].tolist())


def _serve_one(eng, payload):
    t = eng.submit(payload)
    eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))
    return t.result(timeout=0)


def test_the_publish_writes_its_history_span(tmp_path):
    """Under a profiler the updater's timeline holds
    ``live.batch.publish.history`` inside every publish, with the ids it
    appended and the thread's CPU time."""
    import glob

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import program_spans

    rng, V, hist, model, eng, srv, upd = make_stack(seed=9)
    srv.prewarm(rows=(8,))
    upd.start()
    eng.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for user in (3, 4, 5):
            seq0 = eng.published_seq
            upd.submit(user, unrated(rng, hist, user)[0], 5.0)
            wait_for(lambda: eng.published_seq > seq0)
    finally:
        jax.profiler.stop_trace()
        upd.stop()
        eng.stop()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = program_spans.read(path, prefix="live.")
    names = {s[0] for s in spans}
    assert names == set(LIVE_BATCH_SPAN_KEYS + LIVE_FOLDIN_SPAN_KEYS
                        + LIVE_HISTORY_SPAN_KEYS + LIVE_PHASE_SPAN_KEYS)
    history = [s for s in spans if s[0] == LIVE_HISTORY_SPAN_KEYS[0]]
    assert len(history) == 3
    for s in history:
        assert s[3]["ids"] == 1 and s[3]["users"] == 1
        assert s[3]["relocated"] == 0
        assert 0 <= s[3]["cpu_us"] <= s[3]["wall_us"] + 10_000
    publishes = [s for s in spans if s[0] == "live.batch.publish"]
    for h, p in zip(history, publishes):    # inside its publish
        assert p[1] <= h[1] and h[1] + h[2] <= p[1] + p[2]


# -- (f) the control, through the benchmark's own runner -------------------------

def test_histories_frozen_at_publish_fail_the_benchmarks_check(tmp_path,
                                                               capsys):
    """The benchmark's cell at tiny size with the appends off (the rows
    move, the histories stay as published — what the engine did before it
    could append): ``correct`` reads false, by the check that asks touched
    users again after the drain; with the appends on the same run is
    correct (``benchmark/tests/test_serve_live_unseen.py``)."""
    import json

    from benchmark.tests import test_serve_live_unseen as cell

    line = cell.run(cell.make_root(tmp_path, appends=False))
    compared = {s["check"]: s for s in map(json.loads,
                                           capsys.readouterr().out.splitlines())
                if s.get("what") == "compared"}
    assert line["correct"] is False
    back = compared["rated_in_the_run_returned_after_drain"]
    assert not back["holds"] and back["value"] >= 1
    # the folds themselves were right: only the histories were stale
    assert compared["fold_row_rel_err_max"]["holds"]
    assert compared["events_admitted_not_folded"]["holds"]
