"""The implicit-feedback live cell at tiny size on the CPU: the runner end to
end, its three layer metrics, and ``correct`` shown to fail — the Gram
matrices frozen at their start value, the explicit rule on this
configuration, the reference one precision step down.  (The reference itself
is held to the plain ``jax.numpy`` rule, and its replay's moved Gram matrices
to ``gram()`` of the final tables, in tier-1: ``tests/test_live_implicit.py``.)"""

from __future__ import annotations

import pytest

from benchmark import harness
from benchmark.reference import foldin_implicit as ref_rule
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_live_items import (
    ITEMS_CONFIG,
    ITEMS_TRAFFIC,
    failed_checks,
    said_by,
)

BIG_SEED = 2 ** 31 + 4321
CELL = "tiny-r16-live-implicit.serve-foldin-implicit"
IMPLICIT_CONFIG = dict(
    ITEMS_CONFIG,
    als=dict(ITEMS_CONFIG["als"], implicitPrefs=True, alpha=40),
    # the CPU multiplies f32 exactly: a kept Gram matrix reads 1e-7 of the
    # float64 one, one frozen at start 1e-1 and more at this size
    correct=dict(ITEMS_CONFIG["correct"], gram_user_rel_err=1e-5,
                 gram_item_rel_err=1e-5, replay_gram_drift=1e-9))
IMPLICIT_TRAFFIC = dict(ITEMS_TRAFFIC, kind="serve_live_implicit")


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(
        tmp_path,
        dict(tiny.TINY_CONFIGS, **{"tiny-r16-live-implicit": IMPLICIT_CONFIG}),
        dict(tiny.TINY_TRAFFIC,
             **{"serve-foldin-implicit": IMPLICIT_TRAFFIC}),
        tiny.TINY_CELLS + [("tiny-r16-live-implicit",
                            "serve-foldin-implicit")])


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def test_implicit_cell_runs_and_is_correct(root, capsys):
    line = run(root)
    said = said_by(capsys)
    assert line["correct"] is True, [s for s in said
                                     if s.get("what") == "compared"]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    assert line["failed"] == 0 and live["shed"] == 0
    assert live["admitted"] == live["events"] == 72
    assert live["new_users"] >= 1 and live["new_items"] >= 1
    assert live["yty_full_in_window"] == 0
    ref, = [s for s in said if s.get("what") == "reference"]
    assert set(ref["gram_rel_err"]) == {"user", "item"}
    assert max(ref["gram_rel_err"].values()) < 1e-5
    assert max(ref["replay_gram_drift"].values()) < 1e-9
    names = {s["check"] for s in said if s.get("what") == "compared"}
    assert {"fold_user_row_rel_err_median", "fold_user_row_rel_err_max",
            "fold_item_row_rel_err_median", "fold_item_row_rel_err_max",
            "folds_without_a_published_row", "events_folded_off_by",
            "events_shed", "gram_user_rel_err", "gram_item_rel_err",
            "gram_matrices_kept", "replay_gram_drift",
            "yty_full_counter_kept", "yty_full_in_window",
            "score_rel_err", "recall_at_k", "foldin_item_rows_served",
            "compilations_in_window"} <= names


def test_traced_implicit_run_reports_the_new_layer_metrics(root, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(root, trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    assert m["live_yty_full_passes"]["value"] == 0
    assert m["start_yty_s"]["value"] > 0
    for name in ("live_items_foldin_ms", "live_batch_host_ms",
                 "live_freshness_p90_ms", "start_foldin_server_s"):
        assert m[name]["value"] > 0, name
    # the CPU's file has no device plane: the device reading is left out
    assert "live_yty_device_ms" not in m


def test_the_yty_device_reader_reads_the_gram_carrying_writes_runs(
        monkeypatch):
    """``live_yty_device_ms`` on a synthetic timeline: the runs of
    ``jit__scatter_rows_yty`` (never the plain write's) a ``live.batch``."""
    import importlib.util
    import os

    from benchmark import live_spans, program_spans
    from benchmark import trace as tr

    spec = importlib.util.spec_from_file_location(
        "yty_reader", os.path.join(tiny.BENCH, "layer_metrics",
                                   "live_yty_device_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    monkeypatch.setattr(live_spans, "traced_cycle", lambda ctx: {"batches": 4})
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(program_spans, "_planes", lambda path: "planes")
    runs = {"jit__scatter_rows_yty": {0: [(0, 30_000), (100_000, 150_000)]},
            "jit__scatter_rows": {0: [(200_000, 900_000)]}}
    monkeypatch.setattr(live_spans, "module_runs",
                        lambda planes, name: runs.get(name, {}))

    class Ctx:
        class cell:
            root = "/nowhere"

    assert reader.read(Ctx) == pytest.approx(1e-6 * 80_000 / 4)
    runs.pop("jit__scatter_rows_yty")
    assert reader.read(Ctx) is None


def test_a_gram_frozen_at_its_start_value_is_not_correct(
        root, capsys):
    """Control (i), as ``chip_readings_live_implicit.py`` makes it."""
    from benchmark.tests import chip_readings_live_implicit as readings

    undo = readings.freeze_gram()
    try:
        line = run(root)
    finally:
        undo()
    assert line["correct"] is False
    failed = failed_checks(said_by(capsys))
    # read back after the drain, and seen in the folds that read it
    assert {"gram_user_rel_err", "gram_item_rel_err"} <= failed
    assert failed & {"fold_user_row_rel_err_max", "fold_item_row_rel_err_max"}
    assert not failed & {"events_shed", "events_folded_off_by",
                         "replay_gram_drift"}


def test_the_explicit_rule_on_this_configuration_is_not_correct(
        root, capsys):
    _, _, runner, cell = harness.open_cell(root, CELL, BIG_SEED, 1.0, False,
                                           require_tpu=False)
    out = runner.run(cell, program_als=dict(cell.config["als"],
                                            implicitPrefs=False))
    failed = {c.name for c in out.checks if not c.holds}
    # every fold is another rule's, and an explicit server keeps no Gram
    assert {"fold_user_row_rel_err_median", "fold_item_row_rel_err_median",
            "gram_matrices_kept"} <= failed
    assert not failed & {"events_shed", "events_folded_off_by"}
    capsys.readouterr()


def test_control_replay_one_precision_down_fails_the_limits(root):
    from benchmark.runners import serve_live_implicit as runner_mod

    _, _, runner, cell = harness.open_cell(root, CELL, BIG_SEED, 1.0, False,
                                           require_tpu=False)
    a = runner.run(cell).artifacts
    kept = (a["streams"], a["updater"], a["tap"], a["model"], a["U"],
            a["V"], cell.config)
    held = {}
    for dtype in (None, "float8_e4m3fn"):
        held_to, _, _ = runner_mod.replay_of(*kept, operand_dtype=dtype,
                                             gram_dtype=dtype)
        held[dtype] = {c.name: c.holds for c in runner_mod.fold_checks(
            held_to, cell.config["correct"])}
    assert all(held[None].values()), held[None]
    assert not held["float8_e4m3fn"]["fold_user_row_rel_err_median"]
    assert not held["float8_e4m3fn"]["fold_item_row_rel_err_median"]
    assert held["float8_e4m3fn"]["folds_without_a_published_row"]
    # a Gram matrix of float8 rows, held as the program's is
    low = [ref_rule.gram(held_to.final_table(side),
                         operand_dtype="float8_e4m3fn") for side in (0, 1)]
    checks, _ = runner_mod.gram_checks(None, a["replay"],
                                       cell.config["correct"], 0, kept=low)
    assert {c.name for c in checks if not c.holds} == {
        "gram_user_rel_err", "gram_item_rel_err"}
    # the chip's readings script reads the same controls, by what is rounded
    from benchmark.tests import chip_readings_live_implicit as readings

    found = readings.precision_controls(runner_mod, a, cell.config)
    lim = cell.config["correct"]
    for what in ("all", "folds"):
        assert (found[what]["fold_user_row_rel_err_median"]
                > lim["fold_user_row_rel_err_median"]), what
    assert set(found["gram_of_lower_rows"]) == {"gram_user_rel_err",
                                                "gram_item_rel_err"}
