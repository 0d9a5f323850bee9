"""The cell on which refits land, at tiny size on the CPU: the runner end to
end (two landings in the window, one in the traced stream), the replay
reference with landings against the sibling's where nothing lands, and
``correct`` shown to fail — a landing that leaves the catch-up out, a
catch-up folded over the catalog as it stood before the landing, the
reference one precision step down."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import foldin_replay as ref_items
from benchmark.reference import refit_replay as ref_replay
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_live_items import (
    ITEMS_CONFIG,
    ITEMS_TRAFFIC,
    failed_checks,
    said_by,
)

BIG_SEED = 2 ** 31 + 4321
CELL = "tiny-r16-live-refit.serve-foldin-refit"
REFIT_CONFIG = dict(
    ITEMS_CONFIG, refit={"lands": True},
    refit_generation_interval_s=0.4, refit_snapshot_lag_s=0.2,
    correct=dict(ITEMS_CONFIG["correct"],
                 catchup_user_row_rel_err_median=1e-4,
                 catchup_user_row_rel_err_max=2e-3,
                 catchup_item_row_rel_err_median=1e-4,
                 catchup_item_row_rel_err_max=2e-3,
                 refit_row_rel_err_max=1e-6))
REFIT_TRAFFIC = dict(ITEMS_TRAFFIC, kind="serve_live_refit",
                     land_at_s=[0.3, 0.7], snapshot_lag_s=0.2,
                     refit_rows="held at snapshot", trace_land_at_s=0.1)


@pytest.fixture
def root(tmp_path, monkeypatch):
    # tables of a few hundred rows go up in chunks of a fixed size, as the
    # real ones do (32,768 rows): a row appended then changes no chunk's
    # shape, and a landing compiles nothing
    from tpu_als.core import foldin

    monkeypatch.setattr(foldin, "PLACE_CHUNK", 64)
    return tiny.make_root(
        tmp_path,
        dict(tiny.TINY_CONFIGS, **{"tiny-r16-live-refit": REFIT_CONFIG}),
        dict(tiny.TINY_TRAFFIC, **{"serve-foldin-refit": REFIT_TRAFFIC}),
        tiny.TINY_CELLS + [("tiny-r16-live-refit", "serve-foldin-refit")])


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def test_refit_cell_runs_and_is_correct(root, capsys):
    line = run(root)
    said = said_by(capsys)
    assert line["correct"] is True, [s for s in said
                                     if s.get("what") == "compared"]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    assert line["failed"] == 0 and live["shed"] == 0
    assert live["admitted"] == live["events"] == 72
    landings, = [s for s in said if s.get("what") == "landings"]
    assert landings["asked"] == landings["landed"] == 2
    assert not landings["errors"]
    assert all(r["catchup_events"] > 0 for r in landings["records"])
    ref, = [s for s in said if s.get("what") == "reference"]
    # answers of all three eras were held to their own catalogs
    assert ref["eras_sampled"] == [0, 1, 2]
    names = {s["check"] for s in said if s.get("what") == "compared"}
    assert {"score_rel_err", "recall_at_k", "ids_in_catalog",
            "answers_with_a_generation",
            "answers_scored_of_their_generation", "events_shed",
            "events_folded_off_by", "events_admitted_not_in_a_publish",
            "landings_off_by", "landings_in_window_off_by",
            "programs_compiled_in_landings",
            "fold_user_row_rel_err_max", "fold_item_row_rel_err_max",
            "catchup_user_row_rel_err_median",
            "catchup_user_row_rel_err_max",
            "catchup_item_row_rel_err_max", "catchup_folds_without_a_row",
            "catchup_rows_without_a_fold", "refit_row_rel_err_max",
            "refit_rows_not_bit_for_bit", "refit_rows_compared",
            "foldin_score_rel_err", "foldin_item_score_rel_err",
            "foldin_item_row_rel_err_max", "catalog_size_off_by",
            "compilations_in_window"} <= names


def test_traced_refit_run_reports_the_landing_metrics(root, monkeypatch,
                                                      capsys):
    fake_device_trace(monkeypatch)
    line = run(root, trace=True)
    m = line["metrics"]
    assert line["correct"] is True, [s for s in said_by(capsys)
                                     if s.get("what") == "compared"]
    for name in ("live_landing_ms", "live_landing_locked_ms",
                 "live_landing_catchup_ms", "live_landing_placed_gb"):
        assert m[name]["value"] > 0, name
    assert m["live_landing_compiles"]["value"] >= 0
    assert m["live_landing_peak_gb"]["value"] == 0     # the CPU keeps none
    assert (m["live_landing_locked_ms"]["value"]
            < m["live_landing_ms"]["value"])
    # the sibling's metrics are read beside them
    for name in ("live_batch_host_ms", "live_publish_ms", "serve_queue_ms"):
        assert m[name]["value"] > 0, name
    # the CPU's file has no device plane: the join is left out
    assert "serve_life_beside_landing_ms" not in m


def test_a_landing_without_its_catch_up_is_not_correct(root, monkeypatch,
                                                       capsys):
    """The events since the snapshot lost: the landed generation is the
    refit's rows and nothing else."""
    from tpu_als.stream import microbatch

    real = microbatch.FoldInServer.land

    def forgetful(self, refit, users=(), items=()):
        none = np.empty(0, np.int64)
        return real(self, refit, none, none)

    monkeypatch.setattr(microbatch.FoldInServer, "land", forgetful)
    line = run(root)
    assert line["correct"] is False
    assert "catchup_folds_without_a_row" in failed_checks(said_by(capsys))


def test_a_catch_up_over_the_old_catalog_is_not_correct(root, monkeypatch,
                                                        capsys):
    """The catch-up folded BEFORE the tables are replaced: every row it
    makes regresses on the generation that is about to go."""
    from tpu_als.stream import microbatch

    real_land = microbatch.FoldInServer.land
    real_fold = microbatch.FoldInServer._fold

    def stale(self, refit, users=(), items=()):
        before = tuple(np.array(t) for t in self.device_tables())

        def fold(self, F, rows, YtY):
            old = before[0] if F.shape == before[0].shape and np.allclose(
                np.asarray(F[:4]), np.asarray(self._Ud[:4])) else before[1]
            return real_fold(self, old, rows, YtY)

        monkeypatch.setattr(microbatch.FoldInServer, "_fold", fold)
        try:
            return real_land(self, refit, users, items)
        finally:
            monkeypatch.setattr(microbatch.FoldInServer, "_fold", real_fold)

    monkeypatch.setattr(microbatch.FoldInServer, "land", stale)
    line = run(root)
    assert line["correct"] is False
    failed = failed_checks(said_by(capsys))
    assert {"catchup_user_row_rel_err_max",
            "catchup_item_row_rel_err_max"} & failed


def events(rng, n, n_users, n_items):
    return (rng.integers(0, n_users + 3, n), rng.integers(0, n_items + 3, n),
            rng.integers(1, 6, n).astype(np.float64))


def test_replay_without_a_landing_is_the_siblings():
    rng = np.random.default_rng(0)
    U = rng.normal(size=(30, 4)).astype(np.float32)
    V = rng.normal(size=(20, 4)).astype(np.float32)
    users, items, stars = events(rng, 40, 30, 20)
    sizes = [7, 9, 11, 13]
    mine = ref_replay.replay(U, V, users, items, stars, sizes, 0.1)
    theirs = ref_items.replay(U, V, users, items, stars, sizes, 0.1)
    assert mine.entered == theirs.entered and mine.widest == theirs.widest
    assert mine.n_items == theirs.n_items
    for side, rows in enumerate((theirs.user_rows, theirs.item_rows)):
        assert set(mine.rows[side]) == set(rows)
        for e, x in rows.items():
            assert np.array_equal(mine.rows[side][e], x)
    # (the sibling's catalog is by id, this one by table row: an item
    # appended out of the order of its id lies elsewhere)
    Vf = mine.final_catalog()
    assert len(Vf) == mine.n_items[-1]
    for i, x in theirs.item_rows.items():
        assert np.array_equal(Vf[mine.table_row(1, i)], x)


def test_replay_of_a_landing_by_hand():
    """Two users, two items, rank 2: the landed rows by the rule, worked
    out with ``np.linalg.solve``."""
    U0 = np.array([[1.0, 0.0], [0.0, 1.0]], np.float32)
    V0 = np.array([[1.0, 1.0], [1.0, -1.0]], np.float32)
    U1, V1 = 2 * U0 + 1, 3 * V0 - 1
    # event 0 before the snapshot, event 1 after it, event 2 after the
    # landing; user 2 is new with event 1
    users, items, stars = [0, 2, 1], [0, 1, 1], [5.0, 3.0, 1.0]
    steps = [1, 1, {"snapshot": 1, "U": U1, "V": V1}, 1]
    rep = ref_replay.replay(U0, V0, users, items, stars, steps, 0.5)

    def fold(F, r):
        F, r = np.asarray(F, np.float64), np.asarray(r, np.float64)
        return np.linalg.solve(F.T @ F + 0.5 * len(r) * np.eye(2), F.T @ r)

    assert rep.catchup_sizes == [(1, 1, 1)]
    # user 0 rated before the snapshot only: the refit's row, as it is
    assert np.array_equal(rep.row(0, 0), U1[0])
    # user 2 is folded again, over the refit's catalog
    x2 = fold(V1[[1]], [3.0])
    assert np.allclose(rep.row(0, 2), x2)
    assert rep.table_row(0, 2) == 2
    # item 1 after the landing: its catch-up over user 2's new row, then
    # the batch's fold over users 2 and 1 — user 1 folds first in its
    # batch, over item 1 as the catch-up left it
    caught = fold(x2[None], [3.0])
    x1 = fold(caught[None], [1.0])
    y1 = fold(np.stack([x2, x1]), [3.0, 1.0])
    assert np.allclose(rep.row(1, 1), y1)
    # item 0 had no event after the snapshot
    assert np.array_equal(rep.row(1, 0), V1[0])
    era, moved, rows, size = rep.catalog_as_of(3)
    assert era.first == 3 and moved.tolist() == [1] and size == 2
    assert np.allclose(rows[0], caught)


def test_controls_change_what_a_landing_publishes():
    rng = np.random.default_rng(1)
    U = rng.normal(size=(30, 4)).astype(np.float32)
    V = rng.normal(size=(20, 4)).astype(np.float32)
    users, items, stars = events(rng, 40, 30, 20)
    steps = [10, 10, {"snapshot": 8, "U": U[::-1].copy(),
                      "V": V[::-1].copy()}, 10, 10]
    rule = ref_replay.replay(U, V, users, items, stars, steps, 0.1)
    for how in ({"catchup": "none"}, {"catchup": "stale"},
                {"operand_dtype": "bfloat16"}):
        journal = ref_replay.replay(U, V, users, items, stars, steps, 0.1,
                                    **how).journal
        held = ref_replay.replay(U, V, users, items, stars, steps, 0.1,
                                 published=journal)
        worst = max(held.catchup_err[0] + held.catchup_err[1] + [0.0])
        assert held.catchup_missing > 0 or worst > 1e-3, how
    own = ref_replay.replay(U, V, users, items, stars, steps, 0.1,
                            published=rule.journal)
    assert own.catchup_missing == own.catchup_unasked == 0
    assert max(own.catchup_err[0] + own.catchup_err[1]) < 1e-12


def _catalog_and_queries(seed=3, k=5):
    from benchmark.reference import topk as ref_topk

    rng = np.random.default_rng(seed)
    V = rng.normal(size=(400, 8))
    Q = rng.normal(size=(24, 8))
    return V, Q, ref_topk, k


def test_recall_by_score_is_recall_where_no_two_rows_score_alike():
    V, Q, ref_topk, k = _catalog_and_queries()
    ref_s, ref_i = ref_topk.exact_topk(Q, V, k)
    largest = float(np.abs(ref_s).max())
    lowest = np.argmin(Q @ V.T, axis=1)
    for spoiled in (0, 1, 3):
        ids = ref_i.copy()
        ids[:, k - spoiled:] = lowest[:, None] if spoiled else ids[:, k:]
        if spoiled > 1:         # the same wrong id more than once
            ids[:, k - 1] = -1  # ... and no id at all
        own = np.where(ids >= 0, ref_topk.own_scores(
            Q, V, np.clip(ids, 0, None)), np.nan)
        got = ref_replay.recall_by_score(own, ids, ref_s, largest)
        assert got == pytest.approx(ref_topk.recall(ids, ref_i))
        assert got == pytest.approx(1 - spoiled / k)


def test_recall_by_score_counts_a_tie_at_the_kth_place_once():
    """Rows equal bit for bit, as a catch-up makes them: whichever of them
    an answer holds, it has found that place — and holding one of them
    twice finds it once."""
    V, Q, ref_topk, k = _catalog_and_queries()
    ref_s, ref_i = ref_topk.exact_topk(Q, V, k)
    V[-6:] = V[ref_i[0, k - 1]]         # six more rows of query 0's k-th
    ref_s, ref_i = ref_topk.exact_topk(Q, V, k)
    largest = float(np.abs(ref_s).max())
    twin = np.array([i for i in range(len(V) - 6, len(V))
                     if i not in ref_i[0]][:1])
    ids = ref_i.copy()
    ids[0, np.flatnonzero(np.isin(ref_i[0], np.arange(len(V) - 6, len(V)))
                          | (ref_s[0] == ref_s[0, k - 1]))[-1]] = twin[0]
    assert ref_topk.recall(ids, ref_i) < 1.0       # id by id: a miss
    own = ref_topk.own_scores(Q, V, ids)
    assert ref_replay.recall_by_score(own, ids, ref_s, largest) == 1.0
    ids[0, 0] = ids[0, 1]               # one id twice: one place lost
    own = ref_topk.own_scores(Q, V, ids)
    assert ref_replay.recall_by_score(own, ids, ref_s, largest) == \
        pytest.approx(1 - 1 / (k * len(Q)))
