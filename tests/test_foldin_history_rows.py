"""A fold maps its events, not its users' histories (ISSUE 56).

``FoldInServer`` keeps a touched entity's ratings as the fixed side's table
rows (``stream.microbatch._Ratings``).  (i) Over seeded event streams the
planes it hands to ``fold_in``, the factors it writes, ``last_appended``,
``last_items``, ``events_waiting`` and the folds' ``entered`` are those of
:class:`Plain`, which keeps ``(original ids, stars)`` lists and maps ALL of
them every fold — the algorithm the server ran before, written out plainly;
(ii) the second fold of a user with 4,096 resident ratings sends its one
event's id through ``to_dense`` and nothing else, and the counter and the
span say so.
"""

from __future__ import annotations

import glob
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import program_spans  # noqa: E402
from tpu_als import ALSModel, FoldInServer, IdMap, obs  # noqa: E402
from tpu_als.core.foldin import fold_in  # noqa: E402
from tpu_als.core.ratings import (  # noqa: E402
    growth_pads,
    pad_for,
    row_capacity,
    rung_for,
)
from tpu_als.stream import microbatch  # noqa: E402

RANK, REG = 8, 0.1
N_USERS, N_ITEMS, BATCHES = 40, 120, 14
PARAMS = {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": REG,
          "implicitPrefs": False, "alpha": 1.0, "nonnegative": False}


def frame(users, items, stars):
    return {"u": np.asarray(users, np.int64), "i": np.asarray(items, np.int64),
            "r": np.asarray(stars, np.float32)}


def world(seed, base):
    """``(model, base history or None, user ids, item ids)``: original
    ids that are NOT their table rows, in no order."""
    rng = np.random.default_rng(seed)
    user_ids = 1000 + 7 * rng.permutation(N_USERS)
    item_ids = 5000 + 3 * rng.permutation(N_ITEMS)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 3).astype(np.float32)
    hist = None
    if base:
        # the last users have no resident row; items 90.. are rated by
        # nobody, so the item side may fold them
        lengths = rng.integers(0, 20, N_USERS - 6)
        lengths[:3] = [0, 8, 70]
        runs = [np.sort(rng.choice(90, n, replace=False)) for n in lengths]
        hist = (np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64),
                np.concatenate(runs).astype(np.int32),
                rng.integers(1, 6, int(lengths.sum())).astype(np.float32))
    model = ALSModel(RANK, IdMap(ids=user_ids.copy()),
                     IdMap(ids=item_ids.copy()), U, V, dict(PARAMS))
    return model, hist, user_ids, item_ids


def stream(seed, user_ids, item_ids, hist):
    """``BATCHES`` batches of ``(users, items, stars)``: few users, so
    they return; ids neither map holds, from small pools, so they return
    too — before and after they got a row; an item twice in one batch;
    with a base history items the user has rated before the run."""
    rng = np.random.default_rng(seed + 100)
    regulars = user_ids[:8]
    strangers, novelties = 90000 + np.arange(4), 70000 + np.arange(5)
    for b in range(BATCHES):
        users, items = [], []
        for _ in range(int(rng.integers(5, 12))):
            users.append(int(rng.choice(strangers) if rng.random() < 0.2
                             else rng.choice(regulars)))
            items.append(int(rng.choice(novelties) if rng.random() < 0.25
                             else rng.choice(item_ids[60:])))
        # the same pair twice in one batch
        users.append(users[0])
        items.append(items[0])
        if hist is not None:
            # a resident rating again: user row 2 has seventy
            indptr, indices, _ = hist
            users.append(int(user_ids[2]))
            items.append(int(item_ids[indices[indptr[2] + b]]))
        yield users, items, rng.integers(1, 6, len(users)).astype(np.float32)


class Plain:
    """The server's rule with nothing kept in table rows: an entity's
    history is ``[original ids], [stars]``, and every fold looks ALL of
    them up (dicts), filters, and lays them out."""

    def __init__(self, model, hist, user_ids, item_ids):
        self.rows = {"user": {int(e): k for k, e in enumerate(user_ids)},
                     "item": {int(e): k for k, e in enumerate(item_ids)}}
        self.table = {}
        for side, fac in (("user", model._U), ("item", model._V)):
            self.table[side] = np.zeros(
                (row_capacity(len(fac)), RANK), np.float32)
            self.table[side][:len(fac)] = fac
        self.base, self.item_ids = hist, item_ids
        self.widths = () if hist is None else growth_pads(
            int(np.diff(hist[0]).max(initial=0)))
        self.rated_before = set() if hist is None else set(hist[1].tolist())
        self.history = {"user": {}, "item": {}}
        self.used = {"user": {}, "item": {}}
        self.waiting = {"user": {}, "item": {}}
        self.seen = {"replaced": 0, "twice": 0, "waited": 0}

    @property
    def events_waiting(self):
        return sum(sum(w.values()) for w in self.waiting.values())

    def resident(self, user):
        indptr, indices, stars = self.base
        row = self.rows["user"].get(user)
        if row is None or row >= len(indptr) - 1:
            return [], []
        at = slice(int(indptr[row]), int(indptr[row + 1]))
        self.used["user"][user] = at.stop - at.start
        return self.item_ids[indices[at]].tolist(), stars[at].tolist()

    def fold(self, side, users, items, stars):
        """One ``update`` (``side`` "user") or ``update_items``: what the
        server must have handed the program and left behind."""
        other = "item" if side == "user" else "user"
        solved, fixed = (users, items) if side == "user" else (items, users)
        events = list(zip(solved, fixed, np.asarray(stars).tolist()))
        out = {"planes": None, "appended": ([], []), "entered": None,
               "items": {"first": 0, "again": 0, "left_to_refit": 0}}
        if side == "item" and self.base is not None:
            left = [self.rows["item"].get(s) in self.rated_before
                    for s, _, _ in events]
            out["items"]["left_to_refit"] = sum(left)
            events = [e for e, gone in zip(events, left) if not gone]
        if not events:
            return out
        known = self.rows[other]
        for _, f, _ in events:
            if f not in known:
                self.waiting[other][f] = self.waiting[other].get(f, 0) + 1
        one_rating = self.base is not None and side == "user"
        touched = sorted({s for s, _, _ in events})
        entered = 0
        for e in touched:
            if e not in self.history[side]:
                self.history[side][e] = (
                    self.resident(e) if one_rating else ([], []))
        first = {e: len(self.history[side][e][0]) for e in touched}
        for s, f, r in events:                      # arrival order
            ids, st = self.history[side][s]
            if one_rating and f in ids:
                st[ids.index(f)] = r
                entered += f in known
                self.seen["replaced" if ids.index(f) < first[s]
                          else "twice"] += 1
            else:
                ids.append(f)
                st.append(r)
                out["appended"][0].append(s)
                out["appended"][1].append(f)
        folded = []
        for e in touched:
            ids, st = self.history[side][e]
            usable = [(known[f], r) for f, r in zip(ids, st) if f in known]
            before = self.used[side].get(e, 0)
            entered += len(usable) - before
            self.used[side][e] = len(usable)
            if usable:
                folded.append((e, usable))
                # an old rating that waited for its other side enters now
                new = sum(1 for s, _, _ in events if s == e)
                self.seen["waited"] += len(usable) - before > new
        if not folded:
            return out
        n_pad = pad_for(len(folded))
        w = rung_for(max(len(u) for _, u in folded), self.widths)
        cols = np.zeros((n_pad, w), np.int32)
        vals, mask = (np.zeros((n_pad, w), np.float32) for _ in range(2))
        for k, (_, usable) in enumerate(folded):
            cols[k, :len(usable)] = [row for row, _ in usable]
            vals[k, :len(usable)] = [r for _, r in usable]
            mask[k, :len(usable)] = 1.0
        x = np.asarray(fold_in(self.table[other], cols, vals, mask, REG,
                               implicit_prefs=False, alpha=1.0,
                               nonnegative=False, YtY=None))
        for k, (e, _) in enumerate(folded):
            if e not in self.rows[side]:
                self.rows[side][e] = len(self.rows[side])
                self.waiting[side].pop(e, None)
                out["items"]["first"] += 1
            else:
                out["items"]["again"] += 1
            self.table[side][self.rows[side][e]] = x[k]
        if side == "user":
            out["items"] = None
        out.update(planes=(cols, vals, mask), entered=entered,
                   touched=[e for e, _ in folded])
        return out


@pytest.fixture
def handed(monkeypatch):
    """Every ``(cols, vals, mask)`` the server hands the public
    ``fold_in``, copied."""
    calls = []

    def recording(F, cols, vals, mask, *args, **kwargs):
        calls.append(tuple(np.array(a) for a in (cols, vals, mask)))
        return fold_in(F, cols, vals, mask, *args, **kwargs)
    monkeypatch.setattr(microbatch, "fold_in", recording)
    return calls


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("base", [False, True], ids=["no_base", "base"])
@pytest.mark.parametrize("sides", [("user",), ("user", "item")],
                         ids=["users", "both"])
def test_a_fold_takes_what_the_plain_rule_takes(handed, sides, base, seed):
    model, hist, user_ids, item_ids = world(seed, base)
    srv = FoldInServer(model, base_history=hist)
    plain = Plain(model, hist, user_ids, item_ids)
    for users, items, stars in stream(seed, user_ids, item_ids, hist):
        for side in sides:
            del handed[:]
            n_stats = len(srv.stats)
            moved = (srv.update if side == "user" else srv.update_items)(
                frame(users, items, stars))
            want = plain.fold(side, users, items, stars)
            if want["planes"] is None:
                assert not handed and len(moved) == 0
            else:
                (got,) = handed         # one call a fold at these sizes
                for mine, theirs in zip(got, want["planes"]):
                    assert mine.dtype == theirs.dtype
                    np.testing.assert_array_equal(mine, theirs)
                assert moved.tolist() == want["touched"]
                assert srv.stats[-1][0] == want["entered"]
                assert len(srv.stats) == n_stats + 1
            if side == "user":
                who, what = srv.last_appended
                assert (who.tolist(), what.tolist()) == want["appended"]
            else:
                assert srv.last_items == want["items"]
            assert srv.events_waiting == plain.events_waiting
        for side, fac, id_map in (("user", model._U, model._user_map),
                                  ("item", model._V, model._item_map)):
            assert {int(e): k for k, e in enumerate(id_map.ids)} \
                == plain.rows[side]
            np.testing.assert_array_equal(
                fac, plain.table[side][:len(fac)])
    # the histories, read back as original ids, are the plain lists
    for side, kept in (("user", srv._history), ("item", srv._item_history)):
        assert sorted(kept) == sorted(plain.history[side])
        for e, (ids, st) in plain.history[side].items():
            got_ids, got_stars = srv.history_of(e, items_side=side == "item")
            assert (got_ids.tolist(), got_stars.tolist()) == (ids, st)
    # and the stream met what the rule is about (only an item fold gives
    # a new item its row: a users-only server's held ratings wait on)
    assert (plain.seen["waited"] > 0) == ("item" in sides)
    if base:
        assert plain.seen["replaced"] > 0 and plain.seen["twice"] > 0


def test_the_second_fold_of_a_long_history_maps_its_event(tmp_path):
    rng = np.random.default_rng(7)
    n_items, long = 6000, 4096
    item_ids = 5000 + 3 * rng.permutation(n_items)
    model = ALSModel(
        RANK, IdMap(ids=100 + np.arange(4)), IdMap(ids=item_ids.copy()),
        rng.standard_normal((4, RANK)).astype(np.float32),
        (rng.standard_normal((n_items, RANK)) / 3).astype(np.float32),
        dict(PARAMS))
    hist = (np.array([0, long]), np.arange(long, dtype=np.int32),
            rng.integers(1, 6, long).astype(np.float32))
    obs.reset()
    srv = FoldInServer(model, base_history=hist)
    looked_up, to_dense = [], model._item_map.to_dense
    model._item_map.to_dense = lambda ids, **kw: (
        looked_up.append(np.size(ids)), to_dense(ids, **kw))[1]
    srv.update(frame([100], [item_ids[long]], [5.0]))       # first touch
    assert sum(looked_up) == 1
    del looked_up[:]
    mapped0 = obs.counter_value("foldin.ids_mapped", side="user")
    assert mapped0 == 1

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.update(frame([100], [item_ids[long + 1]], [3.0]))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    span, = [s for s in program_spans.read(path, prefix=("live.",))
             if s[0] == "live.batch.foldin.map"]
    stats = span[-1]
    mapped = obs.counter_value("foldin.ids_mapped", side="user") - mapped0
    assert sum(looked_up) == mapped == int(stats["mapped"]) <= 2
    assert int(stats["ratings"]) == long + 2
    assert len(srv.history_of(100)[0]) == long + 2
