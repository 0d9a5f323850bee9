"""The cell whose catalog moves, at tiny size on the CPU: the runner end to
end, the replay reference against ``reference/foldin.py``, and ``correct``
shown to fail — a program that drops new-item events from the user's
history, one that serves a request from two generations, the reference one
precision step down."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import foldin as ref_foldin
from benchmark.reference import foldin_replay as ref_replay
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_live import LIVE_CONFIG, LIVE_TRAFFIC

BIG_SEED = 2 ** 31 + 4321
CELL = "tiny-r16-live-items.serve-foldin-items"
ITEMS_CONFIG = dict(
    LIVE_CONFIG, live=dict(LIVE_CONFIG["live"], fold_items=True),
    # the CPU multiplies f32 exactly: a fold of the program's reads 1e-6 of
    # its row (1e-4 the worst), one with float8 operands 2e-2
    correct=dict(LIVE_CONFIG["correct"], foldin_item_score_rel_err=1e-3,
                 foldin_item_recall_at_k=0.95,
                 fold_user_row_rel_err_median=1e-4,
                 fold_user_row_rel_err_max=2e-3,
                 fold_item_row_rel_err_median=1e-4,
                 fold_item_row_rel_err_max=2e-3,
                 foldin_item_row_rel_err_max=1e-6))
ITEMS_TRAFFIC = dict(
    LIVE_TRAFFIC, kind="serve_live_items",
    events=dict(LIVE_TRAFFIC["events"], new_item_share=0.084,
                check_items=32))


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(
        tmp_path,
        dict(tiny.TINY_CONFIGS, **{"tiny-r16-live-items": ITEMS_CONFIG}),
        dict(tiny.TINY_TRAFFIC, **{"serve-foldin-items": ITEMS_TRAFFIC}),
        tiny.TINY_CELLS + [("tiny-r16-live-items", "serve-foldin-items")])


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def said_by(capsys):
    return [harness.json.loads(s)
            for s in capsys.readouterr().out.splitlines()]


def failed_checks(said):
    return {s["check"] for s in said
            if s.get("what") == "compared" and not s["holds"]}


def test_items_cell_runs_and_is_correct(root, capsys):
    line = run(root)
    said = said_by(capsys)
    assert line["correct"] is True, [s for s in said
                                     if s.get("what") == "compared"]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    assert line["attempted"] == 200 + live["in_window"]
    assert line["failed"] == 0 and live["shed"] == 0
    assert live["admitted"] == live["events"] == 72
    assert live["new_users"] >= 1 and live["new_items"] >= 1
    assert live["publishes"] >= 5 and live["waiting"] >= 0
    assert {m for m, _ in live["publish_modes"]} <= {"delta", "compact"}
    ref, = [s for s in said if s.get("what") == "reference"]
    assert ref["generations"] >= 2 and ref["last"] > ref["first"]
    names = {s["check"] for s in said if s.get("what") == "compared"}
    assert {"score_rel_err", "recall_at_k", "ids_in_catalog",
            "answers_with_a_generation", "untouched_requests_compared",
            "events_shed", "events_folded_off_by",
            "events_admitted_without_freshness",
            "events_admitted_not_in_a_publish", "foldin_score_rel_err",
            "foldin_recall_at_k", "foldin_item_score_rel_err",
            "foldin_item_recall_at_k", "foldin_item_row_rel_err_max",
            "fold_user_row_rel_err_median", "fold_user_row_rel_err_max",
            "fold_item_row_rel_err_median", "fold_item_row_rel_err_max",
            "folds_without_a_published_row",
            "rows_published_without_a_fold",
            "foldin_item_rows_served", "foldin_unanswered",
            "catalog_size_off_by", "compilations_in_window"} <= names


def test_traced_items_run_reports_the_new_layer_metrics(root, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(root, trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    for name in ("live_catalog_h2d_mb", "live_items_foldin_ms",
                 "live_publish_h2d_mb", "live_batch_host_ms",
                 "live_freshness_p90_ms", "serve_queue_ms"):
        assert m[name]["value"] > 0, name
    assert (m["live_items_foldin_ms"]["value"]
            < m["live_batch_host_ms"]["value"])
    # padded rows of rank 16, never the 200-row catalog
    assert m["live_catalog_h2d_mb"]["value"] < 1e-6 * 200 * 16 * 4
    # the CPU's file has no device plane: the device reading is left out
    assert "live_catalog_write_device_ms" not in m


def test_a_program_that_drops_new_item_events_is_not_correct(
        root, monkeypatch, capsys):
    """The hole the configuration closed, reopened: a rating whose item has
    no factor yet is dropped before it reaches its user's history."""
    from tpu_als.stream import microbatch

    real = microbatch.FoldInServer._fold_batch

    def lossy(self, batch, items_side):
        if not items_side:
            known = self.model._item_map.to_dense(
                np.asarray(batch["item"])) >= 0
            batch = {k: np.asarray(v)[known] for k, v in batch.items()}
        return real(self, batch, items_side)

    monkeypatch.setattr(microbatch.FoldInServer, "_fold_batch", lossy)
    line = run(root)
    assert line["correct"] is False
    failed = failed_checks(said_by(capsys))
    # counted, and seen in the fold of every user whose history has the hole
    assert {"events_folded_off_by", "fold_user_row_rel_err_max"} <= failed


def test_a_request_served_from_two_generations_is_not_correct(
        root, monkeypatch, capsys):
    """The stamp taken from one generation and the scores from another:
    every ticket names the generation BEFORE the one that scored it."""
    from tpu_als.serving import engine

    real = engine.ServingEngine._begin

    def torn(self, batch, seq, whole, handoff_wait=0.0):
        flown = real(self, batch, seq, whole, handoff_wait)
        for t in batch:
            if t.seq is not None and t.seq > 3:
                t.seq -= 3
        return flown

    monkeypatch.setattr(engine.ServingEngine, "_begin", torn)
    line = run(root)
    assert line["correct"] is False
    assert failed_checks(said_by(capsys)) & {"score_rel_err", "recall_at_k",
                                             "ids_in_catalog"}


def test_control_replay_one_precision_down_fails_the_limits(root):
    from benchmark.runners import serve_live_items as runner_mod

    _, _, runner, cell = harness.open_cell(root, CELL, BIG_SEED, 1.0, False,
                                           require_tpu=False)
    out = runner.run(cell)
    a = out.artifacts
    held = {}
    for dtype in (None, "float8_e4m3fn"):
        held_to, _, _ = runner_mod.replay_of(
            a["streams"], a["updater"], a["tap"], a["model"], a["U"],
            a["V"], cell.config, operand_dtype=dtype)
        held[dtype] = {c.name: c.holds for c in runner_mod.fold_checks(
            held_to, cell.config["correct"])}
    assert all(held[None].values()), held[None]
    # every fold of the control's is off, on both sides
    assert not held["float8_e4m3fn"]["fold_user_row_rel_err_median"]
    assert not held["float8_e4m3fn"]["fold_item_row_rel_err_median"]
    assert held["float8_e4m3fn"]["folds_without_a_published_row"]


def test_the_replay_is_the_fold_reference_where_no_item_is_touched():
    """Users only, one batch or many: each user's row is
    ``reference/foldin.py``'s fold of ALL that user's events."""
    rng = np.random.default_rng(0)
    U0 = rng.normal(size=(30, 6)).astype(np.float32)
    V0 = rng.normal(size=(50, 6)).astype(np.float32)
    users = rng.integers(0, 34, 80)          # 30..33: new users
    items = rng.integers(0, 50, 80)
    stars = rng.integers(1, 6, 80).astype(np.float32)
    by_user = {}
    for u, i, r in zip(users.tolist(), items.tolist(), stars.tolist()):
        have = by_user.setdefault(u, ([], []))
        have[0].append(i)
        have[1].append(r)
    for sizes in ([80], [7] * 10 + [10]):
        rep = ref_replay.replay(U0, V0, users, items, stars, sizes, 0.1,
                                fold_items=False)
        assert sorted(rep.user_rows) == sorted(by_user)
        want = ref_foldin.fold_users(V0, by_user, sorted(by_user), 0.1)
        np.testing.assert_allclose(
            [rep.user_rows[u] for u in sorted(by_user)], want, rtol=1e-10)
        assert rep.entered == 80 and rep.waiting == 0 and not rep.item_log
        assert rep.n_items[-1] == 50


def test_the_replay_holds_a_rating_until_its_other_side_has_a_factor():
    U0 = np.eye(4, dtype=np.float32)
    V0 = np.eye(4, dtype=np.float32)[:3]
    # user 1 rates NEW item 3; later rates item 0
    rep = ref_replay.replay(U0, V0, [1, 2, 1], [3, 0, 0], [5.0, 1.0, 2.0],
                            [1, 1, 1], 0.1)
    # item 3 was folded from user 1's seeded row, in the first batch
    np.testing.assert_allclose(
        rep.item_log[0][2][0], ref_foldin.fold(U0, [1], [5.0], 0.1))
    assert rep.item_log[0][1].tolist() == [3] and rep.n_items == [4, 4, 4]
    # user 1's fold in batch 3 is over BOTH ratings: item 3's row as batch
    # 1 left it, item 0's as batch 2 left it
    F = np.stack([rep.item_log[0][2][0], rep.item_log[1][2][0]])
    np.testing.assert_allclose(rep.user_rows[1],
                               ref_foldin.fold(F, [0, 1], [5.0, 2.0], 0.1))
    assert rep.entered == 6 and rep.waiting == 0
    ids, rows, size = rep.catalog_as_of(1)
    assert ids.tolist() == [0, 3] and size == 4
    np.testing.assert_array_equal(rows[0], V0[0])
    assert rep.catalog_as_of(0)[0].tolist() == [0]      # item 3 not yet


def test_the_replay_follows_published_rows_and_holds_each_fold_to_its_own():
    """Given what a program published, an error is one fold's: a row that is
    off in batch 1 is charged to batch 1 alone, and the folds after it are
    computed from the row that was published."""
    rng = np.random.default_rng(3)
    U0 = rng.standard_normal((6, 4)).astype(np.float32)
    V0 = rng.standard_normal((5, 4)).astype(np.float32)
    events = ([1, 2, 1, 2], [0, 0, 3, 0], [5.0, 4.0, 3.0, 1.0], [2, 1, 1])
    own = ref_replay.replay(U0, V0, *events, 0.1)
    assert not own.fold_err[0] and not own.fold_err[1]
    published = ref_replay.published_of(own, 3)
    same = ref_replay.replay(U0, V0, *events, 0.1, published=published)
    assert max(same.fold_err[0] + same.fold_err[1]) < 1e-12
    assert same.missing == same.unasked == 0
    # a program that published item 0's row of batch 0 10 % off and folded
    # on from it: the first pass follows the row, the second holds to it
    first = [(dict(published[0][0]), dict(published[0][1])), ({}, {}),
             ({}, {})]
    first[0][1][0] = first[0][1][0] * 1.1
    went_on = ref_replay.replay(U0, V0, *events, 0.1, published=first)
    assert went_on.missing == 4       # batches 1 and 2: the rule's own rows
    off = ref_replay.published_of(went_on, 3)
    rep = ref_replay.replay(U0, V0, *events, 0.1, published=off)
    assert rep.fold_err[1][0] == pytest.approx(0.1)
    assert max(rep.fold_err[0] + rep.fold_err[1][1:]) < 1e-12
    assert rep.unasked == rep.missing == 0
    # what generation 1 served of item 0 is what was published, and user 2's
    # fold of batch 1 is over that row
    ids, rows, _ = rep.catalog_as_of(1)
    np.testing.assert_allclose(rows[ids.tolist().index(0)], off[0][1][0])
    assert not np.allclose(rep.user_log[1][2][0], own.user_log[1][2][0])
    # a row nobody asked for, and a fold nobody published
    off[1][0][4] = U0[4]
    del off[2][1][0]
    rep = ref_replay.replay(U0, V0, *events, 0.1, published=off)
    assert rep.unasked == 1 and rep.missing == 1
