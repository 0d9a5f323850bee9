"""The ``start_*`` layer metrics: on hand-made counter rows the arithmetic
(top level, leaves, the places, the unsplit share, what is before traffic);
every reader ``None`` on a program without the counters and on an empty
registry; a float from each on a tiny engine's own start on the CPU; the
traced run of the tiny cell that folds users AND items reports all eleven;
the manifest's last eleven entries."""

from __future__ import annotations

import os

import numpy as np
import pytest

from benchmark import harness, start_phases as sp
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import fake_device_trace
from benchmark.tests.test_serve_live_items_unseen import make_root, run

SERVING = ("start_program_s", "start_publish_s", "start_place_s",
           "start_placed_gb", "start_pins_s", "start_first_runs_s",
           "start_programs_s", "start_cache_misses", "start_unsplit_pct")
LIVE = ("start_foldin_server_s", "start_prewarm_s")
NEW_METRICS = SERVING[:-1] + LIVE + SERVING[-1:]      # the manifest's order

SECONDS = {
    "start.publish": 1.0,
    "start.publish/start.publish.users": 0.4,
    "start.publish/start.publish.catalog": 0.3,
    "start.publish/start.publish.index": 0.25,
    "start.publish/start.publish.index/start.publish.index.place": 0.2,
    "start.publish/start.publish.index/start.publish.index.quantize": 0.05,
    "start.foldin_server": 2.0,
    "start.foldin_server/start.foldin_server.reserve": 0.5,
    "start.foldin_server/start.foldin_server.place": 1.4,
    "start.prewarm": 1.0,
    "start.prewarm/start.prewarm.place": 0.6,
    "start.prewarm/start.prewarm.programs": 0.4,
    "start.updater": 4.0,
    "start.updater/start.warmup_live": 4.0,
    "start.updater/start.warmup_live/start.pin": 1.5,
    "start.updater/start.warmup_live/start.first_run": 2.5,
}


def reader(name):
    return harness.load_module(
        os.path.join(tiny.BENCH, "layer_metrics", name + ".py"),
        "start_reader_" + name).read(None)


@pytest.fixture
def counters(monkeypatch):
    """The program's counters replaced by hand-made rows."""
    rows = {
        "start.seconds": [({"path": p}, v) for p, v in SECONDS.items()],
        "start.placed_bytes": [({"path": "start.publish"}, 4e9),
                               ({"path": "start.publish/start.publish."
                                 "users"}, 2e9),
                               ({"path": "start.foldin_server"}, 1.5e9)],
        "jax.program_seconds": [
            ({"stage": "trace", "when": "before"}, 1.0),
            ({"stage": "compile", "when": "before"}, 2.5),
            ({"stage": "compile", "when": "traffic"}, 9.0)],
        "jax.programs": [
            ({"stage": "lower", "when": "before"}, 7),
            ({"stage": "compile", "cache": "hit", "when": "before"}, 5),
            ({"stage": "compile", "cache": "miss", "when": "before"}, 2),
            ({"stage": "compile", "cache": "off", "when": "before"}, 1),
            ({"stage": "compile", "cache": "miss", "when": "traffic"}, 4)],
    }
    monkeypatch.setattr(sp, "series", lambda name: rows.get(name))
    return rows


def test_the_arithmetic_on_hand_made_rows(counters):
    assert reader("start_program_s") == pytest.approx(8.0)
    assert reader("start_publish_s") == pytest.approx(1.0)
    # users, catalog, the index's copy, both of the fold-in server's
    assert reader("start_place_s") == pytest.approx(0.4 + 0.3 + 0.2 + 1.4
                                                    + 0.6)
    assert reader("start_placed_gb") == pytest.approx(5.5)   # top level only
    assert reader("start_pins_s") == pytest.approx(1.5)
    assert reader("start_first_runs_s") == pytest.approx(2.5)
    assert reader("start_foldin_server_s") == pytest.approx(2.0)
    assert reader("start_prewarm_s") == pytest.approx(1.0)
    assert reader("start_programs_s") == pytest.approx(3.5)
    assert reader("start_cache_misses") == pytest.approx(3.0)
    # publish 0.05 of its own, the fold-in server 0.1: 0.15 of 8 s
    assert reader("start_unsplit_pct") == pytest.approx(100 * 0.15 / 8.0)


def test_every_reader_is_none_without_the_counters(monkeypatch):
    from tpu_als import obs

    obs.reset()                           # a program that has not started
    assert [reader(n) for n in NEW_METRICS] == [None] * len(NEW_METRICS)
    monkeypatch.delattr(obs, "counter_series")        # before ISSUE 55
    assert [reader(n) for n in NEW_METRICS] == [None] * len(NEW_METRICS)


def test_every_serving_reader_reads_a_tiny_engines_own_start():
    from tpu_als import obs
    from tpu_als.serving.engine import ServingEngine

    obs.reset()
    rng = np.random.default_rng(0)
    U = rng.standard_normal((40, 8)).astype(np.float32)
    V = rng.standard_normal((600, 8)).astype(np.float32)
    engine = ServingEngine(k=5, buckets=(8,))
    engine.publish(U, V)
    engine.warmup()
    got = {n: reader(n) for n in SERVING}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["start_program_s"] >= got["start_publish_s"] > 0
    assert got["start_pins_s"] > 0 and got["start_programs_s"] > 0
    assert got["start_place_s"] > 0
    assert got["start_placed_gb"] == pytest.approx(
        1e-9 * (U.nbytes + 2 * V.nbytes + 600))
    assert 0 <= got["start_unsplit_pct"] < 50
    assert got["start_first_runs_s"] == 0.0       # warmup() runs none
    assert [reader(n) for n in LIVE] == [0.0, 0.0]


def test_traced_run_reports_all_eleven(tmp_path, monkeypatch):
    from tpu_als import obs

    obs.reset()
    fake_device_trace(monkeypatch)
    line = run(make_root(tmp_path), trace=True)
    m = line["metrics"]
    assert line["correct"] is True
    assert set(NEW_METRICS) <= set(m)
    for name in NEW_METRICS:
        if name not in ("start_cache_misses", "start_unsplit_pct"):
            assert m[name]["value"] > 0, name
    assert m["start_unsplit_pct"]["value"] < 25
    assert m["start_program_s"]["value"] >= sum(
        m[n]["value"] for n in ("start_publish_s", "start_foldin_server_s",
                                "start_prewarm_s"))


def test_the_manifest_ends_with_the_eleven_each_with_a_file():
    manifest = tiny.real_manifest()
    last = manifest["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in last] == list(NEW_METRICS)
    cells = [w["name"] for w in manifest["workloads"]]
    live = [c for c in cells if "-live" in c]
    for m in last:
        assert (m["moves"], m["better"], m["source"]) == (
            "setup_s", "lower", "program_counter")
        assert m["workloads"] == (live if m["name"] in LIVE else cells)
        assert m["layer"] == ("live write path" if m["name"] in LIVE
                              else "serving path")
        assert os.path.exists(os.path.join(
            tiny.BENCH, "layer_metrics", m["name"] + ".py"))
