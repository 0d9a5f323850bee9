"""The four-chip cell's own pieces at tiny size on the CPU: the blocked
reference against ``reference/topk.py``, its control shown to fail the
configuration's limits, the runner end to end on four virtual devices, and
the three readers this cell adds."""

from __future__ import annotations

import os

# four CPU devices for the mesh runner; read when the backend starts, which
# no module of these tests does while it is imported
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np   # noqa: E402
import pytest        # noqa: E402

from benchmark import harness                                # noqa: E402
from benchmark import trace as tr                            # noqa: E402
from benchmark.reference import topk, topk_blocked           # noqa: E402
from benchmark.tests import tiny                             # noqa: E402

BIG_SEED = 2 ** 31 + 777
CELL = "tiny-r16-mesh.serve-steady-mesh"
MESH_CONFIG = {"num_users": 301, "num_items": 203, "als": {"rank": 16},
               "serving": {"k": 10},
               "correct": {"score_rel_err": 1e-4, "recall_at_k": 0.9}}
MESH_TRAFFIC = dict(tiny.TINY_TRAFFIC["serve-steady"], kind="serve_mesh",
                    check_requests=32)


def test_blocked_reference_equals_the_plain_one():
    rng = np.random.default_rng(3)
    Q = rng.standard_normal((37, 24)).astype(np.float32)
    V = rng.standard_normal((1003, 24)).astype(np.float32)
    want_s, want_i = topk.exact_topk(Q, V, 10)
    for block in (64, 250, 1003, 4096):       # ragged, whole, one block
        got_s, got_i = topk_blocked.exact_topk(Q, V, 10, item_block=block)
        assert np.array_equal(got_i, want_i)
        np.testing.assert_allclose(got_s, want_s, rtol=1e-12)
    ids = rng.integers(0, 1003, (37, 10))
    np.testing.assert_allclose(topk_blocked.own_scores(Q, V, ids),
                               topk.own_scores(Q, V, ids), rtol=1e-12)
    assert topk_blocked.recall(want_i, want_i) == 1.0
    kw = dict(shortlist_k=64, shortlist_bits=4,
              rescore_dtype="float8_e4m3fn")
    a_s, a_i = topk.lower_precision_topk(Q, V, 10, **kw)
    b_s, b_i = topk_blocked.lower_precision_topk(Q, V, 10, item_block=250,
                                                 **kw)
    assert np.array_equal(a_i, b_i)
    np.testing.assert_allclose(a_s, b_s, rtol=1e-12)


def test_control_one_precision_down_fails_the_mesh_configurations_limits():
    from benchmark.runners import serve_mesh

    cfg = harness.load_json(os.path.join(
        tiny.BENCH, "configs", "amazon23-r256-host4of16.json"))
    rng = np.random.default_rng(2)
    U = rng.standard_normal((128, 256)).astype(np.float32)
    V = (rng.standard_normal((4000, 256)) / 16).astype(np.float32)
    scores, ids = topk_blocked.lower_precision_topk(
        U, V, 10, shortlist_k=64, shortlist_bits=4,
        rescore_dtype="float8_e4m3fn", item_block=1500)
    checks = serve_mesh.compare_answers(scores, ids, U, V, 10, cfg["correct"])
    assert not all(c.holds for c in checks)
    exact = topk_blocked.exact_topk(U, V, 10, item_block=1500)
    assert all(c.holds for c in serve_mesh.compare_answers(
        *exact, U, V, 10, cfg["correct"]))
    # an id outside the catalog fails its own check and breaks nothing else
    bad = exact[1].copy()
    bad[0, 0] = len(V)
    failed = [c.name for c in serve_mesh.compare_answers(
        exact[0], bad, U, V, 10, cfg["correct"]) if not c.holds]
    assert "ids_in_catalog" in failed


def test_host_factors_depend_on_the_seed_alone():
    from benchmark.runners import serve_mesh

    U, V = serve_mesh.host_factors(1000, 700, 16, BIG_SEED)
    U2, V2 = serve_mesh.host_factors(1000, 700, 16, BIG_SEED)
    U3, _ = serve_mesh.host_factors(1000, 700, 16, BIG_SEED + 1)
    assert U.tobytes() == U2.tobytes() and V.tobytes() == V2.tobytes()
    assert not np.array_equal(U, U3)
    assert U.dtype == V.dtype == np.float32
    assert abs(U.std() - 1.0) < 0.02 and abs(V.std() - 0.25) < 0.01


@pytest.fixture
def root(tmp_path):
    import jax

    if jax.device_count() < 4:
        pytest.skip("the backend started with fewer than four CPU devices")
    return tiny.make_root(
        tmp_path, dict(tiny.TINY_CONFIGS, **{"tiny-r16-mesh": MESH_CONFIG}),
        dict(tiny.TINY_TRAFFIC, **{"serve-steady-mesh": MESH_TRAFFIC}),
        tiny.TINY_CELLS + [("tiny-r16-mesh", "serve-steady-mesh")])


def four_chips(root):
    """``make_root`` writes every tiny cell with one chip."""
    path = os.path.join(root, "BENCHMARK.json")
    manifest = harness.load_json(path)
    for w in manifest["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 4
    with open(path, "w") as f:
        harness.json.dump(manifest, f)
    return root


def test_mesh_cell_runs_and_is_correct(root, capsys):
    line = harness.run_cell(four_chips(root), CELL, BIG_SEED, 1.0, False,
                            require_tpu=False)
    said = [harness.json.loads(s)
            for s in capsys.readouterr().out.splitlines()]
    assert line["correct"] is True, [s for s in said
                                     if s.get("what") == "compared"]
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    assert line["attempted"] == 200 and line["failed"] == 0
    assert line["device"]["count"] >= 4
    window, = [s for s in said if s.get("what") == "window"]
    assert window["mesh_exchange_bytes"] > 0
    assert window["compile_in_window"]["compilations"] == 0
    after = [s["after"] for s in said if s.get("what") == "memory"]
    assert after == ["publish", "window"]


def test_traced_mesh_run_reports_the_three_new_metrics(root, monkeypatch):
    real = tr.read_xplane

    def read(path):
        raw = real(path)
        t0 = min([s[1] for s in raw.host_spans] or [0])
        raw.device_ops = {
            d: [("%fusion.1 = f32[8,64] fusion(", t0, 1_000_000 + 100_000 * d),
                ("%all-reduce.2 = f32[8,16] all-reduce(", t0 + 2_000_000,
                 50_000)] for d in range(4)}
        return raw

    monkeypatch.setattr(tr, "read_xplane", read)
    line = harness.run_cell(four_chips(root), CELL, 7, 1.0, True,
                            require_tpu=False)
    m = line["metrics"]
    # no TPU plane in a CPU's trace: the skew's reader reads the file itself
    assert {"serve_collective_device_ms", "serve_mesh_exchange_kb"} <= set(m)
    assert "serve_device_skew_pct" not in m
    batches = 0.05 * 4 / 4 / m["serve_collective_device_ms"]["value"]
    assert batches == pytest.approx(round(batches))
    assert m["serve_mesh_exchange_kb"]["value"] > 0


def test_device_skew_from_busy_intervals(monkeypatch, tmp_path):
    from benchmark import program_spans
    from benchmark.harness import LayerContext

    reader = harness.load_module(os.path.join(
        tiny.BENCH, "layer_metrics", "serve_device_skew_pct.py"), "skew")
    cell = type("C", (), {"root": str(tmp_path)})()
    ctx = LayerContext(cell, {}, object(), "cpu")
    assert reader.read(ctx) is None                     # no trace file
    monkeypatch.setattr(tr, "find_xplane", lambda d: "x")
    busy = {0: [(0, 100), (50, 120)], 1: [(0, 100)], 2: [(0, 90)],
            3: [(10, 100)]}
    monkeypatch.setattr(program_spans, "device_busy", lambda p: busy)
    assert reader.read(ctx) == pytest.approx(100 * (120 - 90) / 100)
    monkeypatch.setattr(program_spans, "device_busy", lambda p: {0: [(0, 9)]})
    assert reader.read(ctx) is None                     # one chip
    assert reader.read(LayerContext(cell, {}, None, "cpu")) is None
