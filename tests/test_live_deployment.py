"""The live deployment (BASELINE config 4) at a small size: rating events
through ``LiveUpdater`` into a started ``ServingEngine``, against a plain
float64 fold-in and exact top-k — what is folded, in which order, what a
request sees after a publish, and that every per-batch cost is
O(touched rows): no compile, no table upload, no table copy (the device's
user table is donated to the row write), no re-sort."""

from __future__ import annotations

import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import CompileCount
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs
from tpu_als.core import foldin
from tpu_als.core.ratings import (
    LIVE_PADS,
    pad_for,
    pads_up_to,
    row_capacity,
)
from tpu_als.obs.schema import (
    LIVE_BATCH_SPAN_KEYS,
    LIVE_FOLDIN_SPAN_KEYS,
    LIVE_PHASE_SPAN_KEYS,
)
from tpu_als.serving import ServingEngine
from tpu_als.serving.engine import _scatter_users

N_USERS, N_ITEMS, RANK, K = 2000, 3000, 32, 10
REG = 0.1
N_EVENTS, NEW_SHARE, HEAVY = 480, 0.1, 7     # HEAVY: one client's id


def reference_fold(V, items, ratings, reg=REG):
    """x = (Vu^T Vu + reg * n * I)^-1 Vu^T r over ALL of a user's events,
    float64."""
    Vu = np.asarray(V, np.float64)[np.asarray(items)]
    A = Vu.T @ Vu + reg * len(items) * np.eye(Vu.shape[1])
    return np.linalg.solve(A, Vu.T @ np.asarray(ratings, np.float64))


def exact_topk(q, V, k=K):
    s = np.asarray(V, np.float64) @ np.asarray(q, np.float64)
    ids = np.argsort(-s, kind="stable")[:k]
    return s[ids], ids


def seeded_events(rng, n=N_EVENTS):
    """(user id, item id, stars) in arrival order: a tenth from users the
    model has never seen (ids N_USERS, N_USERS + 1, ... as they arrive), a
    quarter of the rest from one heavy client."""
    events, next_new = [], N_USERS
    for _ in range(n):
        u = rng.random()
        if u < NEW_SHARE:
            user, next_new = next_new, next_new + 1
        elif u < NEW_SHARE + 0.25:
            user = HEAVY
        else:
            user = int(rng.integers(0, N_USERS))
        events.append((user, int(rng.integers(0, N_ITEMS)),
                       float(rng.integers(1, 6))))
    return events


def wait_for(pred, timeout=20.0):
    deadline = time.perf_counter() + timeout
    while not pred() and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert pred(), "condition not reached before the timeout"


def make_stack(seed=0, max_batch=8, max_wait_ms=2.0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(N_USERS, RANK)).astype(np.float32)
    V = (rng.normal(size=(N_ITEMS, RANK)) / np.sqrt(RANK)).astype(np.float32)
    model = ALSModel(
        RANK, IdMap(ids=np.arange(N_USERS)), IdMap(ids=np.arange(N_ITEMS)),
        U.copy(), V.copy(),
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
         "implicitPrefs": False, "alpha": 1.0, "nonnegative": False})
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=N_ITEMS,
                        max_wait_s=0.0)
    eng.publish(U, V)
    srv = FoldInServer(model)
    srv.prewarm(rows=(max_batch,))
    eng.warmup()
    eng.warmup_publish(max_batch)
    upd = LiveUpdater(eng, srv, max_batch=max_batch, max_wait_ms=max_wait_ms)
    return rng, U, V, model, eng, srv, upd


@pytest.fixture(scope="module")
def drained():
    """480 events through a running updater beside a running engine, one
    request by id after every tenth event; then ``stop()`` drains."""
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack()
    events = seeded_events(rng)
    compiles = CompileCount()
    eng.start()
    upd.start()
    first_publish, pins = None, dict(eng._pinned)
    try:
        for j, (u, i, r) in enumerate(events):
            upd.submit(u, i, r)
            if j == 0:
                wait_for(lambda: reg.histogram_count(
                    "live.freshness_seconds") >= 1)
                first_publish = compiles.n
            if j % 10 == 0:
                eng.recommend(int(rng.integers(0, N_USERS)), timeout=10.0)
    finally:
        upd.stop(drain_timeout_s=30.0)
    by_user = {}
    for u, i, r in events:
        by_user.setdefault(u, []).append((i, r))
    out = dict(reg=reg, U0=U, V=V, model=model, eng=eng, srv=srv,
               events=events, by_user=by_user,
               compiles_after_first=compiles.n - first_publish,
               pins_before=pins)
    yield out
    eng.stop()


def test_every_touched_user_matches_the_float64_fold(drained):
    m, V = drained["model"], drained["V"]
    assert len(drained["by_user"]) > 100
    for user, evs in drained["by_user"].items():
        row = m._U[m._user_map.to_dense([user])[0]]
        want = reference_fold(V, [i for i, _ in evs], [r for _, r in evs])
        np.testing.assert_allclose(row, want, rtol=2e-4, atol=2e-5)


def test_untouched_users_keep_their_rows(drained):
    m = drained["model"]
    untouched = np.setdiff1d(np.arange(N_USERS), list(drained["by_user"]))
    np.testing.assert_array_equal(m._U[untouched], drained["U0"][untouched])


def test_a_users_events_are_kept_in_arrival_order(drained):
    for user, evs in drained["by_user"].items():
        items, stars = drained["srv"].history_of(user)
        assert items.tolist() == [i for i, _ in evs]
        assert stars.tolist() == [r for _, r in evs]


def test_no_event_is_lost_or_folded_twice(drained):
    reg = drained["reg"]
    assert reg.histogram_count("live.freshness_seconds") == N_EVENTS
    assert reg.counter_value("foldin.ratings") == N_EVENTS
    assert reg.counter_value("live.shed") == 0
    srv = drained["srv"]
    assert sum(len(srv.history_of(user)[0]) for user in srv._history) \
        == N_EVENTS


def test_served_by_id_is_the_fold_of_all_the_users_events(drained):
    m, V, eng = drained["model"], drained["V"], drained["eng"]
    for user in [HEAVY] + sorted(drained["by_user"])[-20:]:
        evs = drained["by_user"][user]
        want_s, want_i = exact_topk(
            reference_fold(V, [i for i, _ in evs], [r for _, r in evs]), V)
        s, ix = eng.recommend(int(m._user_map.to_dense([user])[0]),
                              timeout=10.0)
        assert len(set(ix.tolist()) & set(want_i.tolist())) >= K - 1
        np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)


def test_appended_users_are_servable_by_id(drained):
    m, eng = drained["model"], drained["eng"]
    new = sorted(u for u in drained["by_user"] if u >= N_USERS)
    assert len(new) >= 15
    dense = m._user_map.to_dense(new)
    # in arrival order behind the users the model came with
    assert dense.tolist() == list(range(N_USERS, N_USERS + len(new)))
    assert len(m._U) == len(m._user_map) == N_USERS + len(new)
    assert eng._model.n_users == N_USERS + len(new)
    for d in dense[[0, -1]]:
        s, ix = eng.recommend(int(d), timeout=10.0)
        want_s, _ = exact_topk(m._U[d], drained["V"])
        np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)
    with pytest.raises(ValueError, match="outside the published table"):
        eng.submit(N_USERS + len(new))


def test_nothing_compiles_after_the_first_publish(drained):
    """>= 50 publishes, users appended in many of them, requests between
    them: every program was compiled and run before the stream.  (At
    most 8 events a batch, so 480 events make 60 publishes or more
    however full the batches run: on the CPU a row write waits for the
    scoring program that reads the donated table, the next fold behind
    it, and the batches fill up.)"""
    reg = drained["reg"]
    publishes = reg.counter_value("serving.publishes") - 1
    assert publishes >= 50
    # every one of them wrote its rows into the live table
    assert reg.counter_value("serving.user_table_writes",
                             how="inplace") == publishes
    assert reg.counter_value("serving.user_table_writes",
                             how="replaced") == 0
    assert drained["compiles_after_first"] == 0
    # the pinned scoring executables outlive every publish: no shape moved
    assert drained["eng"]._pinned.keys() == drained["pins_before"].keys()
    assert all(drained["eng"]._pinned[k] is v
               for k, v in drained["pins_before"].items())


def test_a_publish_uploads_the_touched_rows_not_the_table(drained):
    reg = drained["reg"]
    sent = reg.counter_value("live.publish_h2d_bytes")
    publishes = reg.counter_value("serving.publishes") - 1
    # at most 8 events a batch -> 8 rows a publish, padded to 8: ids + rows
    assert 0 < sent <= publishes * 8 * (4 * RANK + 4)
    assert sent < 4 * N_USERS * RANK


def test_a_request_dequeued_after_a_publish_sees_it():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=1)
    before = eng.recommend(3, timeout=10.0) if eng.start() else None
    with upd:
        upd.submit(3, 11, 5.0)
        upd.submit(3, 12, 1.0)
        wait_for(lambda: reg.histogram_count("live.freshness_seconds") == 2)
        s, ix = eng.recommend(3, timeout=10.0)
    eng.stop()
    want_s, want_i = exact_topk(reference_fold(V, [11, 12], [5.0, 1.0]), V)
    np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)
    assert ix.tolist() == want_i.tolist()
    assert not np.allclose(before[0], s)


def _values(table):
    """A device table's values WITHOUT a view of its buffer: on the CPU
    ``np.asarray(table)`` shares the buffer and stays cached on the
    array, and a buffer with such a reference cannot be donated (the row
    write would quietly copy)."""
    return np.asarray(table + 0)


@pytest.mark.parametrize("pad", LIVE_PADS)
def test_the_row_write_is_in_place(pad):
    """The table is donated: aliased to the result in the compiled
    program, which holds no copy of it; the result IS the operand's
    buffer, and the operand's handle is deleted."""
    U = jnp.ones((N_USERS, RANK), jnp.float32)
    rows = jnp.full(pad, N_USERS, jnp.int32).at[0].set(3)
    vals = jnp.full((pad, RANK), 2.0, jnp.float32)
    text = _scatter_users.lower(U, rows, vals).compile().as_text()
    assert "input_output_alias" in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"f32[{N_USERS},{RANK}]" in ln]
    where = U.unsafe_buffer_pointer()
    out = _scatter_users(U, rows, vals)
    assert out.unsafe_buffer_pointer() == where and U.is_deleted()
    want = np.ones((N_USERS, RANK), np.float32)
    want[3] = 2.0
    np.testing.assert_array_equal(np.asarray(out), want)


def test_a_row_write_publish_changes_the_named_rows_and_nothing_else():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=2)
    old = eng._model
    before = _values(old.U)
    rows = np.array([5, N_USERS], dtype=np.int64)      # one touched, one new
    U2 = np.concatenate([U, np.ones((1, RANK), np.float32)])
    U2[5] = 2.0
    seq, mode = eng.publish_update(U2, V, touched_users=rows)
    new = eng._model
    assert (seq, mode) == (old.seq + 1, "retag")
    assert new.U.shape == old.U.shape and new.n_users == N_USERS + 1
    after = np.asarray(new.U)
    np.testing.assert_array_equal(after[rows], U2[rows])
    others = np.setdiff1d(np.arange(len(before)), rows)
    np.testing.assert_array_equal(after[others], before[others])
    np.testing.assert_array_equal(before[N_USERS], 0.0)
    # the old generation's table went into the new one: its handle is
    # deleted, its shape still reads
    assert old.U.is_deleted() and old.U.shape == new.U.shape
    assert reg.counter_value("serving.user_table_writes",
                             how="inplace") == 1
    pub = [e for e in reg._events if e["type"] == "serving_publish"][-1]
    assert pub["users"] == "inplace" and pub["seq"] == seq
    # nothing of the catalog was sent again, nor quantized
    assert new.V is old.V and new.valid is old.valid
    assert new.index.Vq is old.index.Vq


def _dispatch_by_id(eng, user):
    """One bucket-8 batch asking for ``user``, dispatched as the engine
    thread would and NOT read back: the packed response on the device."""
    st = np.zeros((8, RANK + 2), np.int32)
    st[0, RANK] = user
    with eng._table_lock:
        return eng._dispatch(eng._model, st, 8, None, 0)[0]


def test_a_batch_dispatched_before_a_publish_answers_from_the_old_rows():
    rng, U, V, model, eng, srv, upd = make_stack(seed=2)
    in_flight = _dispatch_by_id(eng, 5)
    U2 = U.copy()
    U2[5] = 2.0
    eng.publish_update(U2, V, touched_users=np.array([5]))
    behind = _dispatch_by_id(eng, 5)
    for resp, row in ((in_flight, U[5]), (behind, U2[5])):
        resp = np.asarray(resp)
        want_s, want_i = exact_topk(row, V)
        np.testing.assert_allclose(resp[0, :K].view(np.float32), want_s,
                                   rtol=1e-3, atol=1e-4)
        assert resp[0, K:].tolist() == want_i.tolist()


def test_a_publish_with_no_row_to_write_carries_the_table():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=2)
    old = eng._model
    seq, mode = eng.publish_update(U, V, touched_users=np.array([], int))
    assert (seq, mode) == (old.seq + 1, "retag")
    assert eng._model.U is old.U and not old.U.is_deleted()
    assert reg.counter_value("serving.user_table_writes",
                             how="carried") == 1
    assert reg.counter_value("live.publish_h2d_bytes") == 0


def test_warmup_publish_leaves_the_table_and_the_engine_servable():
    rng, U, V, model, eng, srv, upd = make_stack(seed=2)
    old = eng._model
    before = _values(old.U)
    eng.warmup_publish(LIVE_PADS[-1])
    m = eng._model
    # the same generation over the same values, in the same buffer
    assert (m.seq, m.n_users, m.index) == (old.seq, old.n_users, old.index)
    np.testing.assert_array_equal(np.asarray(m.U), before)
    assert old.U.is_deleted()
    with eng:
        s, ix = eng.recommend(5, timeout=10.0)
    want_s, want_i = exact_topk(U[5], V)
    np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)
    assert ix.tolist() == want_i.tolist()


def test_serving_by_id_under_200_row_writes_never_meets_a_deleted_table():
    """One thread asks by id without pause for rows that every publish
    rewrites to ``g * w``, another publishes g = 1..200: no request
    fails, every answer's scores are ONE generation's (k scores, one g),
    no older than the generation that was live when the request was
    submitted, and never older than the answer before."""
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=8)
    rows = np.arange(4)
    w = rng.normal(size=RANK).astype(np.float32)
    base = eng.published_seq
    answers, errors, done = [], [], threading.Event()

    def ask():
        j = 0
        while not done.is_set():
            live = eng.published_seq - base
            try:
                s, ix = eng.recommend(int(rows[j % 4]), timeout=10.0)
            except Exception as e:      # noqa: BLE001 — the test's subject
                errors.append(e)
                return
            if live:    # generation 0 holds the seed's rows, not g * w
                answers.append((live, s, ix))
            j += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # threads change hands mid-publish
    try:
        with eng:
            reader = threading.Thread(target=ask)
            reader.start()
            for g in range(1, 201):
                U[rows] = g * w
                eng.publish_update(U, V, touched_users=rows)
                time.sleep(0.002)
            done.set()
            reader.join(30.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not reader.is_alive()
    assert not [e for e in reg._events if e["type"] == "warning"]
    assert reg.counter_value("serving.user_table_writes",
                             how="inplace") == 200
    assert len(answers) >= 10
    last = 0
    for live, s, ix in answers:
        g = s / (np.asarray(V, np.float64)[ix] @ w.astype(np.float64))
        assert np.abs(g - np.round(g[0])).max() < 1e-3 * g[0], g
        assert live <= round(g[0]) <= 200 and last <= round(g[0])
        last = round(g[0])
    assert len({round(float(s[0] / (V[ix[0]].astype(np.float64) @ w)))
                for _, s, ix in answers}) >= 2


def test_a_row_write_that_fails_after_the_donation_replaces_the_table(
        monkeypatch):
    """The donated table is gone and the write raised: the publish still
    lands, on a table placed anew from the host's under the same lock,
    says so, and counts ``replaced``; without a host table to place
    from (``warmup_publish``) the warning names the state and the error
    is raised."""
    import tpu_als.serving.engine as engine_module

    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=9)
    old = eng._model

    def donate_then_raise(table, rows, vals):
        table.delete()
        raise RuntimeError("injected after the donation")

    monkeypatch.setattr(engine_module, "_scatter_users", donate_then_raise)
    U2 = U.copy()
    U2[5] = 2.0
    seq, mode = eng.publish_update(U2, V, touched_users=np.array([5]))
    m = eng._model
    assert (seq, mode) == (old.seq + 1, "retag") and old.U.is_deleted()
    assert m.U.shape == old.U.shape and not m.U.is_deleted()
    np.testing.assert_array_equal(np.asarray(m.U[:N_USERS]), U2)
    warn = [e for e in reg._events if e["type"] == "warning"
            and e["what"] == "serving.publish_update"]
    assert len(warn) == 1 and "after donating" in warn[0]["reason"] \
        and "re-placed whole" in warn[0]["reason"]
    assert reg.counter_value("serving.user_table_writes",
                             how="replaced") == 1
    assert reg.counter_value("serving.user_table_writes",
                             how="inplace") == 0
    pub = [e for e in reg._events if e["type"] == "serving_publish"][-1]
    assert pub["users"] == "replaced"
    with eng:
        s, ix = eng.recommend(5, timeout=10.0)
    want_s, want_i = exact_topk(U2[5], V)
    np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)
    assert ix.tolist() == want_i.tolist()
    with pytest.raises(RuntimeError, match="injected"):
        eng.warmup_publish(8)
    assert "NO user table" in [e for e in reg._events
                               if e["type"] == "warning"][-1]["reason"]


def test_a_row_write_that_fails_before_the_donation_changes_nothing(
        monkeypatch):
    import tpu_als.serving.engine as engine_module

    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=9)
    old = eng._model

    def refuse(table, rows, vals):
        raise RuntimeError("injected before the donation")

    monkeypatch.setattr(engine_module, "_scatter_users", refuse)
    with pytest.raises(RuntimeError, match="injected"):
        eng.publish_update(U * 2, V, touched_users=np.array([5]))
    assert eng._model is old and not old.U.is_deleted()
    assert eng.published_seq == old.seq
    assert not [e for e in reg._events if e["type"] == "warning"]


def test_the_wait_for_the_table_lock_is_in_the_batchs_record():
    """A batch that finds the lock taken waits in ``serve.batch.stage``,
    and its record says how long (``lock_wait``); a batch that finds it
    free waits microseconds."""
    rng, U, V, model, eng, srv, upd = make_stack(seed=9)
    with eng:
        eng.recommend(5, timeout=10.0)
        free = eng.batch_flight.records()[-1]
        with eng._table_lock:
            t = eng.submit(5)
            time.sleep(0.2)
        t.result(10.0)
        held = eng.batch_flight.records()[-1]
    assert 0.0 <= free["lock_wait"] < 0.01
    assert 0.05 < held["lock_wait"] <= held["spans"]["serve.batch.stage"]
    assert held["batch"] == free["batch"] + 1


@pytest.mark.parametrize("case", ["row_outside", "past_capacity", "shrunk"])
def test_user_rows_the_engine_cannot_write_replace_the_table(case):
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=3)
    old = eng._model
    cap = int(old.U.shape[0])
    if case == "row_outside":
        U2, rows = U, np.array([N_USERS + 4])
    elif case == "past_capacity":
        U2 = np.concatenate([U, np.ones((cap, RANK), np.float32)])
        rows = np.arange(N_USERS, len(U2))
    else:
        U2, rows = U[:100], np.array([3])
    eng.publish_update(U2, V, touched_users=rows)
    warn = [e for e in reg._events if e["type"] == "warning"
            and e.get("what") == "serving.publish_update"]
    assert warn and "re-placed whole" in warn[-1]["reason"]
    m = eng._model
    assert m.n_users == len(U2) and m.U.shape[0] >= len(U2)
    np.testing.assert_array_equal(np.asarray(m.U[:len(U2)]), U2)
    assert reg.counter_value("live.publish_h2d_bytes") >= U2.nbytes
    # a new table beside the old one, which is whole for whoever holds it
    assert reg.counter_value("serving.user_table_writes",
                             how="replaced") == 1
    np.testing.assert_array_equal(np.asarray(old.U[:N_USERS]), U)


def test_publish_without_row_list_keeps_shape_and_counts_the_table():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=4)
    old = eng._model
    eng.publish_update(U * 2, V)
    assert eng._model.U.shape == old.U.shape
    assert reg.counter_value("live.publish_h2d_bytes") == U.nbytes
    assert reg.counter_value("serving.user_table_writes",
                             how="replaced") == 1
    assert not old.U.is_deleted()


def test_appends_land_in_spare_rows_of_one_buffer():
    rng, U, V, model, eng, srv, upd = make_stack(seed=5)
    buf, ids_buf = model._U.base, model._user_map.ids.base
    assert buf.shape[0] == row_capacity(N_USERS) == eng._model.U.shape[0]
    for j in range(20):
        srv.update({"u": np.array([N_USERS + j, 4]), "i": np.array([j, j]),
                    "r": np.array([4.0, 2.0], np.float32)})
    assert model._U.base is buf and model._user_map.ids.base is ids_buf
    assert len(model._U) == N_USERS + 20
    np.testing.assert_array_equal(buf[N_USERS + 20:], 0.0)
    # past the spare rows: one re-reserve, the rows carried
    k = buf.shape[0] - len(model._U) + 1
    srv.update({"u": np.arange(10 ** 6, 10 ** 6 + k), "i": np.zeros(k, int),
                "r": np.full(k, 3.0, np.float32)})
    assert model._U.base is not buf and len(model._U) == len(buf) + 1
    np.testing.assert_array_equal(model._U[:N_USERS + 20],
                                  buf[:N_USERS + 20])


def test_id_map_takes_appends_without_sorting_again():
    ids = np.random.default_rng(0).permutation(50_000) * 3
    m = IdMap(ids=ids.copy())
    assert m.to_dense([ids[7], 1]).tolist() == [7, -1]
    base = m._lookup
    got = m.append([1, 100_000_000, 4])
    assert got.tolist() == [50_000, 50_001, 50_002]
    assert m._lookup is base                 # the sorted base was kept
    assert m.to_dense([4, ids[9], 100_000_000, 1, 2]).tolist() == \
        [50_002, 9, 50_001, 50_000, -1]
    assert m.to_original([50_001]).tolist() == [100_000_000]
    for j in range(5000):                    # past a sixteenth: folded in
        m.append([200_000_000 + j])
    assert m._lookup is None
    assert m.to_dense([200_000_000 + 4999, ids[3]]).tolist() == \
        [50_003 + 4999, 3]
    assert len(m) == 55_003


def test_id_map_append_before_any_lookup_and_reserve():
    m = IdMap(ids=np.array([5, 3, 9]))
    m.reserve(64)
    buf = m.ids.base
    assert m.append([1, 2]).tolist() == [3, 4] and m.ids.base is buf
    assert m.to_dense([1, 2, 3, 5, 9, 4]).tolist() == [3, 4, 1, 0, 2, -1]


def test_pads_and_capacities():
    assert [pad_for(n) for n in (0, 1, 8, 9, 64, 65, 512, 513)] == \
        [8, 8, 8, 64, 64, 512, 512, 4096]
    assert LIVE_PADS == pads_up_to(512) == (8, 64, 512)
    assert pads_up_to(3) == (8,) and pads_up_to(70) == (8, 64, 512)
    assert row_capacity(1_703_438) == 1_730_560
    for n in (1, 24, 2000, 10 ** 6):
        cap = row_capacity(n)
        assert cap % 512 == 0 and cap - n >= max(1024, n >> 6)


@pytest.mark.parametrize("n", [0, 5, 64, 100, 128, 200])
def test_a_table_placed_in_chunks_is_the_padded_table(n, monkeypatch):
    """Whole chunks, a last chunk that overlaps the one before, a table
    smaller than a chunk, an empty one: the table padded with zero rows,
    from one program, the buffer donated to every write."""
    monkeypatch.setattr(foldin, "PLACE_CHUNK", 64)
    F = np.random.default_rng(n).normal(size=(n, RANK)).astype(np.float32)
    before = foldin._write_rows._cache_size()
    got = foldin.place_rows(F, capacity=256)
    want = jnp.pad(jnp.asarray(F), ((0, 256 - n), (0, 0)))
    assert got.shape == (256, RANK) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert foldin._write_rows._cache_size() - before <= 1
    chunk = jnp.zeros((min(64, n), RANK), jnp.float32)
    text = foldin._write_rows.lower(got, chunk, 0).compile().as_text()
    assert "input_output_alias" in text


def test_prewarm_runs_the_ladder_and_names_each_solve():
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=6, max_batch=70)
    said = [e for e in reg._events if e["type"] == "foldin_solve_path"]
    assert sorted((e["rows"], e["width"]) for e in said) == \
        [(n, w) for n in LIVE_PADS for w in LIVE_PADS]
    assert all(e["path"] == "einsum+xla_cholesky" and e["rank"] == RANK
               and e["side"] == "user" for e in said)
    before = foldin._fold_in_jit._cache_size()
    srv.update({"u": np.arange(70), "i": np.arange(70),
                "r": np.full(70, 3.0, np.float32)})
    assert foldin._fold_in_jit._cache_size() == before


def test_a_handful_of_systems_solves_on_xla_whatever_the_probes_say(
        monkeypatch):
    monkeypatch.setattr(foldin, "auto_solve_backend", lambda rank: "lanes")
    assert foldin.solve_path(256, 8)[:2] == ("xla", "einsum+xla_cholesky")
    assert foldin.solve_path(256, 512)[0] == "xla"
    assert foldin.solve_path(256, 513)[:2] == ("lanes",
                                               "einsum+pallas_lanes")
    assert foldin.solve_path(256, 8, nonnegative=True)[1] == "einsum+nnls"


def test_the_programs_name_their_halves_in_every_op_name():
    V = jnp.zeros((64, 8), jnp.float32)
    z = jnp.zeros((8, 8), jnp.float32)
    text = foldin._fold_in_jit.lower(
        V, foldin.pack_rows(z.astype(jnp.int32), z, z), 0.1,
        backend="xla").as_text(debug_info=True)
    assert "live.foldin.gram" in text and "live.foldin.solve" in text
    from tpu_als.serving.engine import _scatter_users

    text = _scatter_users.lower(V, jnp.zeros(8, jnp.int32), z).as_text(
        debug_info=True)
    assert "live.publish.scatter" in text


def _live_spans(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("live."):
                    spans.append((ev.name, ev.start_ns, ev.duration_ns,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda s: s[1])


def test_the_updaters_cycle_is_on_the_profilers_timeline(tmp_path):
    reg = obs.reset()
    rng, U, V, model, eng, srv, upd = make_stack(seed=7, max_batch=4,
                                                 max_wait_ms=20.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with upd:
            for batch in ([(1, 2, 3.0), (1, 3, 4.0), (N_USERS, 5, 5.0)],
                          [(2, 2, 1.0)]):
                n = reg.histogram_count("live.freshness_seconds")
                for e in batch:
                    upd.submit(*e)
                wait_for(lambda: reg.histogram_count(
                    "live.freshness_seconds") == n + len(batch))
    finally:
        jax.profiler.stop_trace()
    spans = _live_spans(str(tmp_path))
    names = [s[0] for s in spans]
    # (with the batch's phases, ISSUE 54: tests/test_live_phase_spans.py)
    assert set(names) == set(LIVE_BATCH_SPAN_KEYS + LIVE_FOLDIN_SPAN_KEYS
                             + LIVE_PHASE_SPAN_KEYS)
    for phase in ("live.batch", "live.batch.coalesce", "live.batch.foldin",
                  "live.batch.publish", "live.batch.foldin.readback"):
        assert names.count(phase) == 2
    # the fold's wait for the device lies inside the fold, and the three
    # spans of the thread's work say what CPU time it had in them
    for fold, back in zip(*([s for s in spans if s[0] == name] for name in
                            ("live.batch.foldin",
                             "live.batch.foldin.readback"))):
        assert fold[1] <= back[1] and back[1] + back[2] <= fold[1] + fold[2]
        assert back[3] == {"side": "users"}
    for name, _, dur, stats in spans:
        if name in ("live.batch", "live.batch.foldin", "live.batch.publish"):
            assert 0 <= stats["cpu_us"] <= stats["wall_us"] + 100, stats
            assert stats["wall_us"] <= dur / 1e3 + 1, (name, stats)
    for rec in upd.flight.records():
        for phase in ("foldin", "publish"):
            assert 0 <= rec[phase + "_cpu"] <= rec["spans"][phase] + 1e-4
    first, second = [s for s in spans if s[0] == "live.batch"]
    assert (first[3]["events"], first[3]["users"], first[3]["new_users"],
            first[3]["width"], first[3]["mode"]) == (3, 2, 1, 8, "retag")
    assert (second[3]["seq"], second[3]["events"],
            second[3]["new_users"]) == (first[3]["seq"] + 1, 1, 0)
    for whole in (first, second):
        inside = [s for s in spans if s[0] in ("live.batch.foldin",
                                               "live.batch.publish")
                  and whole[1] <= s[1] and s[1] + s[2] <= whole[1] + whole[2]]
        assert [s[0] for s in inside] == ["live.batch.foldin",
                                          "live.batch.publish"]
        assert inside[0][1] + inside[0][2] <= inside[1][1]
