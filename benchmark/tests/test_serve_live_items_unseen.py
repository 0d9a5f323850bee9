"""The cell whose catalog moves under histories that grow, at tiny size on the
CPU: the runner end to end through the harness; the CONTROLS (histories
frozen at publish; the slot mask off; bfloat16 operands in the item fold);
the three new layer readers on the run's own counters and trace; a program
without the path refused before set-up."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_live_unseen import (
    CONFIG as UNSEEN_CONFIG,
    TRAFFIC as UNSEEN_TRAFFIC,
    compared,
    said_by,
)

BIG_SEED = 2 ** 31 + 4747
CELL = "tiny-r16-live-items-unseen.serve-foldin-all"
CONFIG = dict(
    UNSEEN_CONFIG,
    live=dict(UNSEEN_CONFIG["live"], fold_items=True),
    # the CPU multiplies f32 exactly: the program's item folds read a few
    # units of kappa * 2^-24, bfloat16 operands thousands
    correct=dict(UNSEEN_CONFIG["correct"], item_fold_c=100.0,
                 foldin_item_row_rel_err_max=1e-6))
TRAFFIC = dict(
    UNSEEN_TRAFFIC, kind="serve_live_items_unseen",
    events=dict(UNSEEN_TRAFFIC["events"], new_item_share=0.15,
                new_item_again_share=0.15, check_items=32))


def make_root(tmp_path, **mix):
    return tiny.make_root(
        tmp_path,
        dict(tiny.TINY_CONFIGS, **{"tiny-r16-live-items-unseen": CONFIG}),
        dict(tiny.TINY_TRAFFIC, **{"serve-foldin-all": dict(TRAFFIC, **mix)}),
        tiny.TINY_CELLS + [("tiny-r16-live-items-unseen",
                            "serve-foldin-all")])


def run(root, trace=False, seconds=2.0):
    return harness.run_cell(root, CELL, BIG_SEED, seconds, trace,
                            require_tpu=False)


def test_cell_runs_and_is_correct(tmp_path, capsys):
    line = run(make_root(tmp_path))
    said = said_by(capsys)
    checks = compared(said)
    assert line["correct"] is True, [c for c in checks.values()
                                     if not c["holds"]]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    assert line["failed"] == 0 and live["shed"] == 0
    assert live["admitted"] == live["events"] > 100
    assert live["new_items"] >= 8 and live["new_users"] >= 1
    assert live["segment_counts_agree"] is True
    moved = live["in_window_counters"]
    assert moved["live.items_left_to_refit"] > 0
    assert moved["live.items_folded.first"] > 0
    assert moved["live.items_folded.again"] > 0
    assert moved["live.history_segment_ids"] > 0
    assert {"seen_returned", "seen_returned_all_answers", "recall_at_k",
            "recall_at_k_after_drain", "recall_at_k_raters",
            "rated_in_the_run_returned_after_drain",
            "new_item_returned_to_its_rater", "fold_row_rel_err_max",
            "item_fold_err_over_kappa_max", "folds_without_a_published_row",
            "rows_published_without_a_fold", "events_folded_off_by",
            "items_left_to_refit_off_by", "item_folds_off_by",
            "appended_pairs_not_the_replays", "foldin_item_row_rel_err_max",
            "items_left_to_refit_row_moved_by", "catalog_size_off_by",
            "new_items_whose_row_is_not_their_id",
            "answers_with_their_generation",
            "compilations_in_window"} <= set(checks)
    found, = [s for s in said if s.get("what") == "reference"]
    assert found["folds"]["item"] >= 20 and found["raters_asked"] >= 8
    assert found["item_err_over_kappa"]["100"] < 20


def test_histories_frozen_at_publish_are_not_correct(tmp_path, capsys):
    """CONTROL: the rows and the catalog move and the histories do not: a
    new item goes back to its rater, in the window or after the drain."""
    line = run(make_root(tmp_path, appends=False), seconds=4.0)
    said = said_by(capsys)
    failed = {c for c, s in compared(said).items() if not s["holds"]}
    assert line["correct"] is False
    assert {"rated_in_the_run_returned_after_drain",
            "new_item_returned_to_its_rater"} <= failed
    found, = [s for s in said if s.get("what") == "reference"]
    assert found["raters_given_a_new_item_back"] >= 3


def test_traced_run_reports_the_new_layer_metrics(tmp_path, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(make_root(tmp_path), trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    assert m["live_history_segment_ids"]["value"] > 0
    assert 0 < m["live_items_left_to_refit_pct"]["value"] < 100
    assert m["live_publish_items"]["value"] > 0
    for name in ("live_catalog_h2d_mb", "live_items_foldin_ms",
                 "live_history_append_ms", "live_history_h2d_kb",
                 "live_fold_width_p90", "serve_excluded_ids_p90",
                 "serve_exclude_h2d_kb", "serve_score_hbm_pct",
                 "live_freshness_p90_ms", "live_publish_ms"):
        assert m[name]["value"] > 0, name


def test_a_program_without_the_path_is_refused_before_setup(tmp_path,
                                                            monkeypatch):
    from tpu_als.serving.engine import ServingEngine

    monkeypatch.delattr(ServingEngine, "warmup_histories")
    with pytest.raises(harness.BenchmarkError, match="warmup_histories"):
        run(make_root(tmp_path))
