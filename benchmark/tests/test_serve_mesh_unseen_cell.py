"""The four-chip unseen cell's own pieces at tiny size on the CPU: the
histories and planted factors made by parts, the runner end to end on four
virtual devices through the harness, the CONTROL (the rule off: the same
checks must fail), the two readers this cell adds and the mesh and unseen
readers it joins, and what a program before PR 52 does with the cell."""

from __future__ import annotations

import os

# four CPU devices for the mesh runner; read when the backend starts, which
# no module of these tests does while it is imported
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np   # noqa: E402
import pytest        # noqa: E402

from benchmark import harness, histories_by_shard, peaks     # noqa: E402
from benchmark import trace as tr                            # noqa: E402
from benchmark.tests import tiny                             # noqa: E402

BIG_SEED = 2 ** 31 + 5252
CELL = "tiny-r16-mesh-unseen.serve-unseen-mesh"
CONFIG = {
    "num_users": 403, "num_items": 3001, "num_ratings": 12000,
    "als": {"rank": 16},
    "histories": {"user_power": 0.9, "length_range": [1, 700],
                  "item_zipf_s": 1.1, "rating_range": [1, 5],
                  "star_shares": [0.10, 0.05, 0.08, 0.17, 0.60]},
    "serving": {"k": 10},
    # the CPU multiplies f32 exactly: the program reads 1e-6
    "correct": {"score_rel_err": 1e-4, "recall_at_k": 0.9,
                "recall_at_k_longest": 0.9, "seen_returned": 0}}
TRAFFIC = dict(
    {k: v for k, v in tiny.TINY_TRAFFIC["serve-steady"].items()
     if k != "zipf_s"},
    kind="serve_mesh_unseen", exclude_ids=64, history_pads=[64, 512, 4096],
    check_requests=32, check_longest=8)


@pytest.fixture
def four_devices():
    import jax

    if jax.device_count() < 4:
        pytest.skip("the backend started with fewer than four CPU devices")


def make_root(tmp_path, **mix):
    root = tiny.make_root(
        tmp_path, dict(tiny.TINY_CONFIGS, **{"tiny-r16-mesh-unseen": CONFIG}),
        dict(tiny.TINY_TRAFFIC,
             **{"serve-unseen-mesh": dict(TRAFFIC, **mix)}),
        tiny.TINY_CELLS + [("tiny-r16-mesh-unseen", "serve-unseen-mesh")])
    # ``make_root`` writes every tiny cell with one chip
    path = os.path.join(root, "BENCHMARK.json")
    manifest = harness.load_json(path)
    for w in manifest["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 4
    with open(path, "w") as f:
        harness.json.dump(manifest, f)
    return root


def run(root, trace=False):
    return harness.run_cell(root, CELL, BIG_SEED, 1.0, trace,
                            require_tpu=False)


def said_by(capsys):
    return [harness.json.loads(s)
            for s in capsys.readouterr().out.splitlines()]


def test_same_seed_same_histories_whatever_the_parts():
    a = histories_by_shard.seeded_histories(CONFIG, BIG_SEED)
    b = histories_by_shard.seeded_histories(CONFIG, BIG_SEED)
    c = histories_by_shard.seeded_histories(CONFIG, BIG_SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    # the multiset of lengths comes from no seed
    assert np.array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(c[0])))
    indptr, indices, stars = a
    assert indptr[-1] == len(indices) == len(stars) == 12000
    assert indices.dtype == np.int32 and stars.dtype == np.float32
    for u in range(CONFIG["num_users"]):
        row = indices[indptr[u]:indptr[u + 1]]
        assert (np.diff(row) > 0).all() and 1 <= len(row) <= 700
    assert 0 <= indices.min() and indices.max() < CONFIG["num_items"]
    assert set(np.unique(stars)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    # five stars most of the time, as the configuration's histogram
    assert 0.5 < (stars == 5.0).mean() < 0.7


@pytest.mark.parametrize("shape", [(403, 12000, 0.9, 1, 700),
                                   (1000, 5000, 0.9, 1, 4096),
                                   (300, 12000, 1.1, 20, 150),
                                   (50_000, 524_288, 0.9, 1, 4096)])
def test_history_lengths_are_the_generators_degrees(shape):
    """The closed form of the bisection gives ``datagen.
    power_law_degrees``'s multiset, entity for entity."""
    from benchmark import datagen

    want = datagen.power_law_degrees(*shape)
    got = histories_by_shard.history_lengths(*shape)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert got.sum() == shape[1] and (np.diff(got) <= 0).all()
    with pytest.raises(ValueError, match="do not fit"):
        histories_by_shard.history_lengths(10, 5, 0.9, 1, 4)


def test_planted_factors_are_the_histories_weighted_sum():
    indptr, indices, stars = histories_by_shard.seeded_histories(CONFIG, 7)
    V = np.random.default_rng(0).standard_normal((3001, 16)).astype(
        np.float32)
    U = histories_by_shard.planted_user_factors(indptr, indices, stars, V)
    again = histories_by_shard.planted_user_factors(indptr, indices, stars,
                                                    V, parts=3)
    assert U.dtype == np.float32 and U.tobytes() == again.tobytes()
    for u in (0, 17, 402):
        lo, hi = indptr[u], indptr[u + 1]
        want = (stars[lo:hi, None] * V[indices[lo:hi]]).sum(0)
        assert np.allclose(U[u], want, rtol=1e-4, atol=1e-4)


def test_mesh_unseen_cell_runs_and_is_correct(four_devices, tmp_path, capsys):
    line = run(make_root(tmp_path))
    said = said_by(capsys)
    compared = {s["check"]: s for s in said if s.get("what") == "compared"}
    assert line["correct"] is True, compared
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    assert line["attempted"] == 200 and line["failed"] == 0
    assert line["device"]["count"] >= 4
    assert {"seen_returned", "seen_returned_longest",
            "seen_returned_all_answers", "recall_at_k",
            "recall_at_k_longest", "score_rel_err", "score_rel_err_longest",
            "ids_in_catalog", "longest_unanswered",
            "compilations_in_window"} <= set(compared)
    assert compared["seen_returned_all_answers"]["value"] == 0
    window, = [s for s in said if s.get("what") == "window"]
    assert window["history_ids"]["50"] >= 20
    assert window["exclusion_upload_bytes"] > 0
    assert window["mesh_exchange_bytes"] > 0
    assert window["mesh_history_bytes"] > 0
    assert window["compile_in_window"]["compilations"] == 0
    setup, = [s for s in said if s.get("what") == "setup"]
    assert {"histories_s", "item_factors_s", "planted_s", "publish_s",
            "warmup_s", "warm_batches_s"} <= set(setup)
    after = [s["after"] for s in said if s.get("what") == "memory"]
    assert after == ["publish", "window"]


def test_the_rule_off_is_not_correct(four_devices, tmp_path, capsys):
    """The CONTROL: nothing published or sent to exclude returns the
    users' own items, and guarantees (1) and (2) fail."""
    line = run(make_root(tmp_path, rule=False))
    said = said_by(capsys)
    failed = {s["check"] for s in said
              if s.get("what") == "compared" and not s["holds"]}
    assert line["correct"] is False
    assert {"seen_returned", "seen_returned_all_answers"} <= failed
    assert failed & {"recall_at_k", "recall_at_k_longest"}
    window, = [s for s in said if s.get("what") == "window"]
    assert window["mesh_history_bytes"] == 0


def test_traced_run_reports_the_new_and_the_joined_metrics(
        four_devices, tmp_path, monkeypatch):
    real = tr.read_xplane

    def read(path):
        raw = real(path)
        t0 = min([s[1] for s in raw.host_spans] or [0])
        # named as the reader names an ``XLA Ops`` event: the instruction,
        # its result's shape, its opcode
        ops = [("%fusion.1 = f32[8,64]{1,0} fusion(%p), kind=kLoop", 0,
                1_000_000),
               ("%psum.21 = s32[8,82]{1,0} all-reduce(%p), channel_id=1",
                2_000_000, 50_000),
               ("%psum.22 = s32[8,512]{1,0:T(8,128)S(1)} all-reduce(%f), "
                "channel_id=1", 2_100_000, 30_000),
               ("%psum.23 = f32[8,16]{1,0} all-reduce(%s), channel_id=1",
                2_200_000, 20_000)]
        raw.device_ops = {
            d: [(tr.short_name(name), t0 + at, ns + (100_000 * d if not at
                                                     else 0))
                for name, at, ns in ops] for d in range(4)}
        assert raw.device_ops[0][2][0] == "%psum.22 s32[8,512] all-reduce"
        return raw

    monkeypatch.setattr(tr, "read_xplane", read)
    monkeypatch.setitem(peaks.DEVICE_PEAKS, "cpu",
                        peaks.DEVICE_PEAKS["TPU v5 lite"])
    line = run(make_root(tmp_path), trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    assert {"serve_history_exchange_kb", "serve_history_exchange_device_ms",
            "serve_collective_device_ms", "serve_mesh_exchange_kb",
            "serve_excluded_ids_p90", "serve_exclude_h2d_kb",
            "serve_score_hbm_pct", "serve_score_device_ms"} <= set(m)
    # the lists' all-reduce alone, of the three: 30 of the 100 us a batch
    assert m["serve_history_exchange_device_ms"]["value"] == pytest.approx(
        0.3 * m["serve_collective_device_ms"]["value"])
    # 1.5 x bucket x pad x 4 bytes a batch of 8 or 32 rows at pad 64 or 512
    assert 1.5 * 8 * 64 * 4e-3 <= m["serve_history_exchange_kb"]["value"] \
        <= 1.5 * 32 * 4096 * 4e-3
    # ONE shard's columns (of 4 x 768 for 3,001 items), the batch's ids
    busy_s = 1e-3 * m["serve_score_device_ms"]["value"]
    least = m["serve_score_hbm_pct"]["value"] / 100 * 819e9 * busy_s
    ids = (least - 768 * 21) / 4
    assert 1 <= ids <= 32 * 764


def test_history_exchange_reader_reads_nothing_where_nothing_is():
    reader = harness.load_module(os.path.join(
        tiny.BENCH, "layer_metrics", "serve_history_exchange_device_ms.py"),
        "history_exchange_device_ms")
    counters = harness.load_module(os.path.join(
        tiny.BENCH, "layer_metrics", "serve_history_exchange_kb.py"),
        "history_exchange_kb")
    ctx = harness.LayerContext(None, {}, None, "cpu")
    assert reader.read(ctx) is None and counters.read(ctx) is None

    class Trace:
        def op_seconds(self, pattern):
            import re
            ops = {"%psum.2 s32[128,4096] all-reduce": 0.004,
                   "%psum.1 s32[128,322] all-reduce": 0.001,
                   "%all-gather.1 s32[512,10] all-gather": 0.002}
            return sum(s for n, s in ops.items() if re.search(pattern, n))

    ctx = harness.LayerContext(
        None, {"batches": 4, "history_pads": [64, 512, 4096]}, Trace(), "x")
    assert reader.read(ctx) == pytest.approx(1.0)
    ctx.counters["history_pads"] = []
    assert reader.read(ctx) is None
    ctx = harness.LayerContext(None, {"mesh_history_bytes": 196_608 * 10,
                                      "window_batches": 10}, None, "x")
    assert counters.read(ctx) == pytest.approx(196.608)


def test_a_program_before_pr_52_is_refused_at_once(monkeypatch):
    """The parent commit under this PR's benchmark files: the runner asks
    the engine before any factor is drawn, and the engine's own
    ``NotImplementedError`` ends the run."""
    from benchmark.runners import serve_mesh_unseen
    from tpu_als import obs

    class Refuses:
        def publish(self, U, V, user_seen=None):
            assert U.shape == (1, 16) and user_seen is not None
            raise NotImplementedError("publish(user_seen=...) on a mesh")

    serve_mesh_unseen.refused_at_once(Refuses(), 16, 10)    # asked nothing
    monkeypatch.delitem(obs.schema.METRICS, "serving.mesh_history_bytes")
    with pytest.raises(NotImplementedError, match="on a mesh"):
        serve_mesh_unseen.refused_at_once(Refuses(), 16, 10)
