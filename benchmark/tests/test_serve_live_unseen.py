"""The live-unseen cell at tiny size on the CPU: the runner end to end through
the harness; the three CONTROLS (histories frozen at publish, the fold over
the run's events alone, the reference one precision step down); the four new
layer readers on the run's own counters and trace; the byte count behind
``live_fold_hbm_pct``; a program without the path refused before set-up."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness, peaks, peaks_live_unseen
from benchmark.reference import live_unseen as ref
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_unseen import UNSEEN_CONFIG, UNSEEN_TRAFFIC

BIG_SEED = 2 ** 31 + 4242
CELL = "tiny-r16-live-unseen.serve-foldin-unseen"
CONFIG = dict(
    UNSEEN_CONFIG,
    als={"rank": 16, "implicitPrefs": False, "regParam": 0.1,
         "nonnegative": False},
    live={"max_batch": 256, "max_wait_ms": 20, "max_queue": 4096,
          "fold_items": False, "keep_history": True, "rating_range": [1, 5],
          "star_shares": [0.10, 0.05, 0.08, 0.17, 0.60]},
    # the CPU multiplies f32 exactly: the program's folds read 1e-6
    correct=dict(UNSEEN_CONFIG["correct"], fold_row_rel_err_max=1e-4))
TRAFFIC = dict(
    UNSEEN_TRAFFIC, kind="serve_live_unseen",
    events={"rate_per_s": 60, "new_user_share": 0.1, "item_zipf_s": 1.1,
            "drain_timeout_s": 20.0, "check_users": 32, "check_longest": 8})


def make_root(tmp_path, **mix):
    return tiny.make_root(
        tmp_path, dict(tiny.TINY_CONFIGS, **{"tiny-r16-live-unseen": CONFIG}),
        dict(tiny.TINY_TRAFFIC,
             **{"serve-foldin-unseen": dict(TRAFFIC, **mix)}),
        tiny.TINY_CELLS + [("tiny-r16-live-unseen", "serve-foldin-unseen")])


def run(root, trace=False, seconds=1.0):
    return harness.run_cell(root, CELL, BIG_SEED, seconds, trace,
                            require_tpu=False)


def said_by(capsys):
    return [harness.json.loads(s)
            for s in capsys.readouterr().out.splitlines()]


def compared(said):
    return {s["check"]: s for s in said if s.get("what") == "compared"}


def test_cell_runs_and_is_correct(tmp_path, capsys):
    line = run(make_root(tmp_path))
    said = said_by(capsys)
    checks = compared(said)
    assert line["correct"] is True, [c for c in checks.values()
                                     if not c["holds"]]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    assert line["attempted"] == 200 + live["in_window"]
    assert line["failed"] == 0 and live["shed"] == 0
    assert live["admitted"] == live["events"] == 72
    assert live["new_users"] >= 1 and live["publishes"] >= 5
    # O(ids appended): five int32 a padded entry
    sent = live["in_window_counters"]["live.history_h2d_bytes"]
    assert 0 < sent <= 20 * 8 * live["publishes_in_window"] * 8
    assert {"seen_returned", "seen_returned_all_answers", "recall_at_k",
            "seen_returned_after_drain", "recall_at_k_after_drain",
            "seen_returned_longest", "recall_at_k_longest",
            "rated_in_the_run_returned_after_drain", "fold_row_rel_err_max",
            "folds_without_a_published_row", "events_admitted_not_folded",
            "appended_pairs_not_their_batchs_events",
            "answers_with_their_generation",
            "compilations_in_window"} <= set(checks)
    found, = [s for s in said if s.get("what") == "reference"]
    assert found["folds"] >= 60 and found["fold_row_rel_err"]["100"] < 1e-5
    # the folds ride the histories' widths, not the batch's
    assert live["fold_width"]["90"] >= 64


def test_histories_frozen_at_publish_are_not_correct(tmp_path, capsys):
    """CONTROL (i), tier-1 test (f): the rows move and the histories do not
    (the parent's engine, had it taken the publish): what was just rated
    comes back, in the window AND after the drain."""
    # a window long enough for touched users to ask again
    line = run(make_root(tmp_path, appends=False), seconds=4.0)
    said = said_by(capsys)
    failed = {c for c, s in compared(said).items() if not s["holds"]}
    assert line["correct"] is False
    assert {"seen_returned_all_answers",
            "rated_in_the_run_returned_after_drain"} <= failed
    found, = [s for s in said if s.get("what") == "reference"]
    assert found["rated_back"] >= 5


def test_the_fold_over_the_events_alone_is_not_correct(tmp_path, capsys):
    """CONTROL (ii): a server without the resident history (the ``-live``
    sibling's rule) publishes rows fitted to the run's events: the fold
    check reads it, by orders of magnitude."""
    line = run(make_root(tmp_path, fold_base=False))
    checks = compared(said_by(capsys))
    assert line["correct"] is False
    assert not checks["fold_row_rel_err_max"]["holds"]
    assert checks["fold_row_rel_err_max"]["value"] > 0.1
    # the ids were still appended: the rule itself holds
    assert checks["seen_returned_all_answers"]["holds"]


def test_traced_run_reports_the_new_layer_metrics(tmp_path, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(make_root(tmp_path), trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    assert 0 < m["live_history_append_ms"]["value"] < 50
    assert 0 < m["live_history_h2d_kb"]["value"] <= 1.28
    # histories of up to 700: widths up to 4,096, a bucketed upper bound
    assert 64 <= m["live_fold_width_p90"]["value"] <= 5624
    for name in ("live_freshness_p90_ms", "live_batch_host_ms",
                 "live_publish_ms", "live_publish_h2d_mb",
                 "serve_excluded_ids_p90", "serve_exclude_h2d_kb",
                 "serve_score_hbm_pct", "serve_queue_ms"):
        assert m[name]["value"] > 0, name
    # the fake trace names no fold-in program: the share has nothing to read
    assert "live_fold_hbm_pct" not in m


def _share(ratings, rows, rank, device_s):
    return 100.0 * peaks_live_unseen.fold_bytes(ratings, rows, rank) / (
        device_s * peaks.DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"])


@pytest.mark.parametrize("real,rows,width", [(4097, 1, 8192), (40, 8, 64),
                                             (8 * 4096, 8, 4096)])
def test_fold_bytes_are_the_least(real, rows, width):
    """``live_fold_hbm_pct`` counts the real ratings' rows once and the
    solved rows once: a program that reads every gathered row at least
    once — padding and all — at no more than the chip's bandwidth reads at
    most 100, whatever its padding."""
    rank, bw = 256, peaks.DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    n_pad = max(8, rows)
    # the fastest such program: every padded row read once, every row
    # written once, at the peak
    fastest_s = 4 * rank * (n_pad * width + n_pad) / bw
    assert _share(real, rows, rank, fastest_s) <= 100.0
    assert peaks_live_unseen.fold_bytes(real, rows, rank) \
        == 4 * rank * (real + rows)


def test_reference_histories_replace_a_rating_and_add_no_id():
    indptr = np.array([0, 3, 3])
    h = ref.Histories(indptr, np.array([5, 7, 9], np.int32),
                      np.array([1.0, 2.0, 3.0], np.float32))
    h.publish(2, [0, 0, 1], [7, 11, 4], [5.0, 4.0, 1.0])
    h.publish(3, [0], [11], [2.0])
    assert list(h.ids(0, 1)) == [5, 7, 9]
    assert list(h.ids(0, 2)) == [5, 7, 9, 11] == list(h.ids(0))
    assert list(h.ratings(0, 2)[1]) == [1.0, 5.0, 3.0, 4.0]
    assert list(h.ratings(0)[1]) == [1.0, 5.0, 3.0, 2.0]
    assert list(h.ids(1, 1)) == [] and list(h.ids(1)) == [4]
    assert list(h.ids(7)) == []             # a user the share never held
    V = np.random.default_rng(0).standard_normal((12, 4))
    x = ref.fold(V, h, 0, 0.1)
    Vu = V[[5, 7, 9, 11]]
    want = np.linalg.solve(Vu.T @ Vu + 0.4 * np.eye(4),
                           Vu.T @ np.array([1.0, 5.0, 3.0, 2.0]))
    assert np.allclose(x, want)
    assert ref.row_rel_err(x, want) < 1e-12


def test_control_reference_one_precision_down_fails_the_fold_limit():
    """CONTROL (iii) at small size: bfloat16 fold operands miss the float64
    fold by 1e-3 and more, float32 ones by 1e-6: a limit between them tells
    the two apart."""
    rng = np.random.default_rng(5)
    V = (rng.standard_normal((3000, 16)) / 4).astype(np.float32)
    indptr = np.array([0, 600])
    h = ref.Histories(indptr, rng.choice(3000, 600, replace=False).astype(
        np.int32), rng.integers(1, 6, 600).astype(np.float32))
    exact = ref.fold(V, h, 0, 0.1)
    low = ref.fold(V, h, 0, 0.1, operand_dtype="bfloat16")
    f32 = ref.foldin.fold(V.astype(np.float32), *h.ratings(0), 0.1)
    assert ref.row_rel_err(low, exact) > 1e-3
    assert ref.row_rel_err(f32, exact) < 1e-6


def test_a_program_without_the_path_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit under this PR's benchmark files: a
    ``publish_update`` without ``seen_appended`` is a ``BenchmarkError``
    (exit 1 of ``run.py``) before any set-up, not a hang or a thread left
    running."""
    import threading

    from tpu_als.serving.engine import ServingEngine

    def publish_update(self, U, V, *, touched_items=None, touched_users=None,
                       item_valid=None, trace=None):
        raise AssertionError("not reached")

    monkeypatch.setattr(ServingEngine, "publish_update", publish_update)
    before = threading.active_count()
    with pytest.raises(harness.BenchmarkError, match="seen_appended"):
        run(make_root(tmp_path))
    assert threading.active_count() == before


def test_the_control_script_reads_the_runs_artifacts(tmp_path):
    """``chip_readings_live_unseen.lower_precision`` on a tiny run: bfloat16
    fold operands read far above the program's folds; the int4 + float8
    answers exclude what they were to."""
    from benchmark.tests import chip_readings_live_unseen as readings

    _, _, runner, cell = harness.open_cell(
        make_root(tmp_path), CELL, BIG_SEED, 1.0, False, require_tpu=False)
    outcome = runner.run(cell)
    assert all(c.holds for c in outcome.checks)
    found = readings.lower_precision(outcome.artifacts, cell, every=4)
    low = found["bfloat16_fold_row_rel_err"]
    assert low["folds"] >= 15
    assert low["min"] > 10 * max(outcome.artifacts["fold_errs"])
    assert found["int4+float8_e4m3fn"]["seen_returned"] == 0
