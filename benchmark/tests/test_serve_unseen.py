"""The unseen cell at tiny size on the CPU: the runner end to end through the
harness, the CONTROL (the rule off: the same checks must fail), the
reference one precision step down, same seed same inputs, and the three
layer readers on the run's own counters."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness, histories
from benchmark.reference import topk as ref_plain
from benchmark.reference import topk_unseen as ref
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace

BIG_SEED = 2 ** 31 + 3939
CELL = "tiny-r16-unseen.serve-unseen"
UNSEEN_CONFIG = {
    "num_users": 400, "num_items": 3000, "num_ratings": 12000,
    "als": {"rank": 16},
    "histories": {"user_power": 0.9, "length_range": [1, 700],
                  "item_zipf_s": 1.1, "rating_range": [1, 5],
                  "star_shares": [0.10, 0.05, 0.08, 0.17, 0.60]},
    "serving": {"k": 10},
    # the CPU multiplies f32 exactly: the program reads 1e-6
    "correct": {"score_rel_err": 1e-4, "recall_at_k": 0.9,
                "recall_at_k_longest": 0.9, "seen_returned": 0}}
UNSEEN_TRAFFIC = dict(
    {k: v for k, v in tiny.TINY_TRAFFIC["serve-steady"].items()
     if k != "zipf_s"},
    kind="serve_unseen", exclude_ids=64, check_requests=48, check_longest=8)


def make_root(tmp_path, **mix):
    return tiny.make_root(
        tmp_path, dict(tiny.TINY_CONFIGS, **{"tiny-r16-unseen": UNSEEN_CONFIG}),
        dict(tiny.TINY_TRAFFIC,
             **{"serve-unseen": dict(UNSEEN_TRAFFIC, **mix)}),
        tiny.TINY_CELLS + [("tiny-r16-unseen", "serve-unseen")])


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def said_by(capsys):
    return [harness.json.loads(s)
            for s in capsys.readouterr().out.splitlines()]


def test_unseen_cell_runs_and_is_correct(tmp_path, capsys):
    line = run(make_root(tmp_path))
    said = said_by(capsys)
    compared = {s["check"]: s for s in said if s.get("what") == "compared"}
    assert line["correct"] is True, compared
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    assert line["attempted"] == 200 and line["failed"] == 0
    assert {"seen_returned", "seen_returned_longest",
            "seen_returned_all_answers", "recall_at_k",
            "recall_at_k_longest", "score_rel_err", "score_rel_err_longest",
            "ids_in_catalog", "longest_unanswered",
            "compilations_in_window"} <= set(compared)
    assert compared["seen_returned_all_answers"]["value"] == 0
    window, = [s for s in said if s.get("what") == "window"]
    # size-biased: the requests' histories are far longer than the users'
    assert window["history_ids"]["50"] >= 20
    assert window["exclusion_upload_bytes"] > 0


def test_the_rule_off_is_not_correct(tmp_path, capsys):
    """The CONTROL: nothing published or sent to exclude (the parent's
    semantics) returns the users' own items, and guarantees (1) and (2)
    fail, on the sample, on the longest histories and over all answers."""
    line = run(make_root(tmp_path, rule=False))
    said = said_by(capsys)
    failed = {s["check"] for s in said
              if s.get("what") == "compared" and not s["holds"]}
    assert line["correct"] is False
    assert {"seen_returned", "seen_returned_all_answers",
            "recall_at_k"} <= failed
    found, = [s for s in said if s.get("what") == "reference"]
    assert found["by_id_with_seen_share"] >= 0.25


def test_traced_unseen_run_reports_its_layer_metrics(tmp_path, monkeypatch):
    fake_device_trace(monkeypatch)
    line = run(make_root(tmp_path), trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    # a bucketed upper bound of the requests' 90th percentile of up to 700
    assert 50 <= m["serve_excluded_ids_p90"]["value"] <= 1000
    # 8 or 32 rows of 64 int32 a batch
    assert 2.0 <= m["serve_exclude_h2d_kb"]["value"] <= 8.2
    for name in ("serve_queue_ms", "serve_p95_ms", "gen_late_p99_ms",
                 "serve_score_device_ms", "device_idle_pct.serve"):
        assert m[name]["value"] > 0, name
    # the least bytes (3,000 rows of 16 + 5 bytes, the batch's ids) over the
    # fake trace's busy time a batch, times the peak the fake gives a CPU
    busy_s = 1e-3 * m["serve_score_device_ms"]["value"]
    assert m["serve_score_hbm_pct"]["value"] == pytest.approx(
        100 * (3000 * 21 + 4 * m_ids(line)) / (busy_s * 819e9), rel=1e-6)


def m_ids(line):
    """The traced stream's excluded ids a batch, back from the share."""
    m = line["metrics"]
    least = (m["serve_score_hbm_pct"]["value"] / 100 * 819e9
             * 1e-3 * m["serve_score_device_ms"]["value"])
    ids = (least - 3000 * 21) / 4
    assert 1 <= ids <= 8 * 764      # a batch of 8 rows of at most 700 + 64
    return ids


def test_a_program_without_the_rule_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit under this PR's benchmark files: no ``user_seen``
    in ``publish`` is an exit 1 of ``run.py`` (``BenchmarkError``) before
    any work, not a hang or a ``TypeError``."""
    from tpu_als.serving.engine import ServingEngine

    def publish(self, U, V, item_valid=None, quantize=True):
        raise AssertionError("not reached")

    monkeypatch.setattr(ServingEngine, "publish", publish)
    with pytest.raises(harness.BenchmarkError, match="user_seen"):
        run(make_root(tmp_path))


def test_same_seed_same_inputs():
    a = histories.seeded_histories(UNSEEN_CONFIG, BIG_SEED)
    b = histories.seeded_histories(UNSEEN_CONFIG, BIG_SEED)
    c = histories.seeded_histories(UNSEEN_CONFIG, BIG_SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    # the multiset of lengths comes from no seed
    assert np.array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(c[0])))
    indptr, indices, stars = a
    assert indptr[-1] == len(indices) == len(stars) == 12000
    for u in range(400):
        row = indices[indptr[u]:indptr[u + 1]]
        assert (np.diff(row) > 0).all() and 1 <= len(row) <= 700
    assert set(np.unique(stars)) <= {1.0, 2.0, 3.0, 4.0, 5.0}


def test_planted_factors_are_the_histories_weighted_sum():
    indptr, indices, stars = histories.seeded_histories(UNSEEN_CONFIG, 7)
    V = np.random.default_rng(0).standard_normal((3000, 16)).astype(
        np.float32)
    U = histories.planted_user_factors(indptr, indices, stars, V, chunk=1000)
    for u in (0, 17, 399):
        lo, hi = indptr[u], indptr[u + 1]
        want = (stars[lo:hi, None] * V[indices[lo:hi]]).sum(0)
        assert np.allclose(U[u], want, rtol=1e-4, atol=1e-4)


def test_reference_without_exclusions_is_the_plain_reference():
    rng = np.random.default_rng(1)
    Q, V = rng.standard_normal((9, 8)), rng.standard_normal((700, 8))
    s, i = ref.exact_topk(Q, V, 10, [[] for _ in Q], item_block=128)
    ps, pi = ref_plain.exact_topk(Q, V, 10)
    assert np.array_equal(i, pi) and np.allclose(s, ps)


def test_reference_excludes_and_pads_with_sentinels():
    rng = np.random.default_rng(2)
    Q, V = rng.standard_normal((3, 8)), rng.standard_normal((40, 8))
    full = np.argsort(-(Q @ V.T), axis=1, kind="stable")
    excluded = [full[0, :5], np.arange(35), []]
    s, i = ref.exact_topk(Q, V, 10, excluded, item_block=16)
    assert list(i[0]) == list(full[0, 5:15])
    assert list(i[1][:5]) == [x for x in full[1] if x >= 35]
    assert (i[1][5:] == -1).all() and np.isneginf(s[1][5:]).all()
    assert list(i[2]) == list(full[2, :10])
    assert ref.seen_returned(i, excluded, i >= 0) == 0
    assert ref.seen_returned(full[:, :10], excluded) \
        == 5 + int((full[1, :10] < 35).sum())
    assert ref.recall(i, i) == 1.0
    assert ref.recall(full[:, :10], i) < 1.0


def test_control_reference_one_precision_down_fails_the_limits():
    """The reference in the program's place one step down (int4 shortlist,
    float8 rescore), the same ids excluded: no excluded id comes back, and
    the sibling's limits still tell it from a sound answer."""
    rng = np.random.default_rng(3)
    V = (rng.standard_normal((6000, 64)) / 8).astype(np.float32)
    hist = [rng.choice(6000, n, replace=False) for n in (3, 40, 900, 0)]
    stars = [rng.integers(1, 6, len(h)) for h in hist]
    Q = np.stack([(s[:, None] * V[h]).sum(0) if len(h) else V[0]
                  for h, s in zip(hist, stars)]).astype(np.float32)
    exact = ref.exact_topk(Q, V, 10, hist)
    s, i = ref.lower_precision_topk(Q, V, 10, hist, shortlist_k=64,
                                    shortlist_bits=4,
                                    rescore_dtype="float8_e4m3fn")
    assert ref.seen_returned(i, hist) == 0
    own = ref.own_scores(Q, V, i)
    err = (np.abs(s - own).max(axis=1) / np.abs(exact[0]).max(axis=1)).max()
    assert err > 0.007 or ref.recall(i, exact[1]) < 0.98
