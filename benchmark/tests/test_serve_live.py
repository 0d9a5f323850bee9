"""The live cell at tiny size on the CPU: the runner end to end, the
read-your-writes comparison shown to fail (a fold left out; the reference one
precision step down), and the ``live.`` span reader on the run's own trace."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness, live_spans
from benchmark.reference import foldin as ref_foldin
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace

BIG_SEED = 2 ** 31 + 4321
CELL = "tiny-r16-live.serve-foldin"
LIVE_CONFIG = {
    "num_users": 300, "num_items": 200,
    "als": {"rank": 16, "implicitPrefs": False, "regParam": 0.1,
            "nonnegative": False},
    "live": {"max_batch": 256, "max_wait_ms": 20, "max_queue": 4096,
             "fold_items": False, "keep_history": True,
             "rating_range": [1, 5],
             "star_shares": [0.10, 0.05, 0.08, 0.17, 0.60]},
    "serving": {"k": 10},
    # the CPU multiplies f32 exactly: the program reads 1e-6, float8 1e-2
    "correct": {"score_rel_err": 1e-4, "recall_at_k": 0.9,
                "foldin_score_rel_err": 1e-3, "foldin_recall_at_k": 0.95}}
LIVE_TRAFFIC = dict(
    tiny.TINY_TRAFFIC["serve-steady"], kind="serve_live", check_requests=32,
    events={"rate_per_s": 60, "new_user_share": 0.1, "item_zipf_s": 1.1,
            "drain_timeout_s": 20.0, "check_users": 32})


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(
        tmp_path, dict(tiny.TINY_CONFIGS, **{"tiny-r16-live": LIVE_CONFIG}),
        dict(tiny.TINY_TRAFFIC, **{"serve-foldin": LIVE_TRAFFIC}),
        tiny.TINY_CELLS + [("tiny-r16-live", "serve-foldin")])


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def test_live_cell_runs_and_is_correct(root, capsys):
    line = run(root)
    said = [harness.json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert line["correct"] is True, [s for s in said
                                     if s.get("what") == "compared"]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    live, = [s for s in said if s.get("what") == "live"]
    # the window's 200 requests and those of the 72 events due in it
    assert line["attempted"] == 200 + live["in_window"]
    assert line["failed"] == 0 and 40 <= live["in_window"] <= 72
    assert live["admitted"] == live["events"] == 72 and live["shed"] == 0
    assert live["new_users"] >= 1 and live["publishes"] >= 5
    assert live["freshness_ms"]["90"] > 0
    names = {s["check"] for s in said if s.get("what") == "compared"}
    assert {"score_rel_err", "recall_at_k", "ids_in_catalog",
            "untouched_requests_compared", "events_shed",
            "events_admitted_not_folded", "events_admitted_without_freshness",
            "events_admitted_not_in_a_publish", "foldin_score_rel_err",
            "foldin_recall_at_k", "foldin_ids_in_catalog",
            "foldin_unanswered", "compilations_in_window"} <= names


def test_traced_live_run_reports_the_live_layer_metrics(root, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(root, trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    for name in ("live_freshness_p90_ms", "live_batch_host_ms",
                 "live_publish_ms", "live_publish_h2d_mb",
                 "serve_queue_ms", "serve_p95_ms", "gen_late_p99_ms"):
        assert m[name]["value"] > 0, name
    assert m["live_publish_ms"]["value"] < m["live_batch_host_ms"]["value"]
    # a publish sends padded rows of rank 16, never the 300-row table
    assert m["live_publish_h2d_mb"]["value"] < 1e-6 * 300 * 16 * 4
    # the CPU's file has no device plane: the device reading is left out
    assert "live_foldin_device_ms" not in m
    ctx = harness.LayerContext(
        harness.Cell(CELL, {}, {}, 1, 0, 1.0, True, root, "", 0.0, None),
        {}, object(), "cpu")
    c = live_spans.traced_cycle(ctx)
    assert c["batches"] >= 3 and c["events"] >= c["users"] >= c["batches"]
    assert 0 < c["publish_ns"] < c["batch_ns"]
    assert c["foldin_device_ns"] is None


def test_a_fold_that_changes_nothing_is_not_correct(root, monkeypatch,
                                                    capsys):
    """The timed path broken where it is produced: the fold-in returns the
    rows it found, so a request after the publish is answered from factors
    without the events."""
    import jax.numpy as jnp

    from tpu_als.stream import microbatch

    monkeypatch.setattr(
        microbatch, "fold_in",
        lambda F, cols, vals, mask, *a, **kw: jnp.zeros(
            (cols.shape[0], F.shape[1]), jnp.float32))
    line = run(root)
    said = [harness.json.loads(s) for s in capsys.readouterr().out.splitlines()]
    failed = {s["check"] for s in said
              if s.get("what") == "compared" and not s["holds"]}
    assert line["correct"] is False
    assert failed == {"foldin_score_rel_err", "foldin_recall_at_k"}


def test_a_lost_event_is_not_correct(root, monkeypatch, capsys):
    """Every fifth admitted event dropped before the fold: the counts of
    (b) disagree."""
    from tpu_als.live import updater

    real, seen = updater.LiveUpdater._process, [0]

    def lossy(self, batch, whole):
        keep = []
        for e in batch:
            seen[0] += 1
            if seen[0] % 5:
                keep.append(e)
        return real(self, keep or batch[:1], whole)

    monkeypatch.setattr(updater.LiveUpdater, "_process", lossy)
    line = run(root)
    said = [harness.json.loads(s) for s in capsys.readouterr().out.splitlines()]
    failed = {s["check"] for s in said
              if s.get("what") == "compared" and not s["holds"]}
    assert line["correct"] is False
    assert {"events_admitted_not_folded",
            "events_admitted_without_freshness"} <= failed


def test_control_fold_one_precision_down_fails_the_limits(root):
    """The reference in the program's place with float8 operands: beyond
    the tiny limits by one of them at least; at float64 it holds them."""
    from benchmark.runners import serve_live

    _, _, runner, cell = harness.open_cell(root, CELL, BIG_SEED, 1.0, False,
                                           require_tpu=False)
    out = runner.run(cell)
    a = out.artifacts
    held = {}
    for dtype in (None, "float8_e4m3fn"):
        checks, _ = serve_live.read_your_writes(
            None, a["model"], a["by_user"], a["V"], cell.config,
            cell.traffic, cell.seed, operand_dtype=dtype,
            answers=a["read_your_writes"])
        held[dtype] = {c.name: c.holds for c in checks}
    assert all(held[None].values())
    assert not all(held["float8_e4m3fn"].values())


def test_reference_fold_is_the_normal_equations():
    rng = np.random.default_rng(0)
    V = rng.normal(size=(50, 6))
    items, r = [3, 9, 3, 40], [5.0, 1.0, 4.0, 2.0]
    x = ref_foldin.fold(V, items, r, 0.1)
    Vu = V[items]
    np.testing.assert_allclose(
        (Vu.T @ Vu + 0.1 * 4 * np.eye(6)) @ x, Vu.T @ np.array(r), rtol=1e-10)
    # order does not matter; a repeated item counts twice
    np.testing.assert_allclose(
        x, ref_foldin.fold(V, items[::-1], r[::-1], 0.1), rtol=1e-10)
    assert not np.allclose(x, ref_foldin.fold(V, [3, 9, 40], [5.0, 1.0, 2.0],
                                              0.1))
