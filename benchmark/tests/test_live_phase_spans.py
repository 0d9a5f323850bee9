"""``benchmark/live_phase_spans.py`` on hand-made tuples: a batch's phases add
up to the batch, a span's time is its own, the three serving batches of a
synthetic timeline are classed under ``.writes``, ``.history`` and ``none``
and the metrics come out as the arithmetic says; every reader ``None``
without a ``live.batch`` span, or without its phases (the parent's trace)."""

from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from benchmark import harness, live_phase_spans as lp
from benchmark.tests import tiny
from benchmark.tests.test_pipeline_spans import batch, span

NEW_METRICS = (
    "live_prepare_ms", "live_fold_host_ms", "live_fold_call_ms",
    "live_publish_lock_wait_ms", "live_publish_locked_ms", "live_record_ms",
    "live_host_cpu_ms", "live_batch_unsplit_pct",
    "serve_life_beside_live_ms", "serve_stage_lock_wait_p99_ms")
US = 1000        # the timeline below is written in microseconds


def live_batch(t0, prepare=50):
    """One ``live.batch`` of a user-only updater, 1,000 us long, in us
    from ``t0``: 35 us of it under no phase (10 after prepare, 10 at the
    fold's end, 5 at the publish's end, 10 at its own end), and 5 us of
    prepare's span outside the interval it timed (its stamp)."""
    def at(name, start, dur, **stats):
        return span(name, (t0 + start) * US, dur * US, **stats)

    return [
        at("live.batch", 0, 1000, seq=1, cpu_us=600, wall_us=1000),
        at("live.batch.prepare", 0, prepare, cpu_us=40, wall_us=prepare - 5),
        at("live.batch.foldin", 60, 500, cpu_us=300, wall_us=500),
        at("live.batch.foldin.group", 60, 40, side="users", cpu_us=40,
           wall_us=40),
        at("live.batch.foldin.history", 100, 200, side="users", cpu_us=150,
           wall_us=200),
        at("live.batch.foldin.map", 300, 20, side="users", cpu_us=20,
           wall_us=20),
        at("live.batch.foldin.pack", 320, 30, side="users", cpu_us=30,
           wall_us=30),
        at("live.batch.foldin.readback", 350, 150, side="users"),
        at("live.batch.foldin.call", 350, 50, side="users", rows=8,
           width=64, calls=1, cpu_us=20, wall_us=50),
        at("live.batch.foldin.write_back", 500, 50, side="users", cpu_us=30,
           wall_us=50),
        at("live.batch.publish", 560, 340, cpu_us=200, wall_us=340),
        at("live.batch.publish.users", 560, 40, cpu_us=40, wall_us=40),
        at("live.batch.publish.send", 600, 50, cpu_us=45, wall_us=50),
        at("live.batch.publish.ride", 610, 30, bytes=320, cpu_us=25,
           wall_us=30),
        at("live.batch.publish.lock_wait", 650, 10, cpu_us=1, wall_us=10),
        at("live.batch.publish.writes", 660, 200, programs=1, cpu_us=90,
           wall_us=200),
        at("live.batch.publish.after", 860, 35, cpu_us=20, wall_us=35),
        at("live.batch.record", 900, 90, cpu_us=50, wall_us=90)]


def served(seq, t0, life, lock_wait_us):
    """A serving batch of bucket 8 that lives ``life`` us from ``t0``."""
    spans = batch(seq, t0 * US, stage=10 * US, upload=5 * US,
                  launch=20 * US, after=5 * US, readback=(life - 48) * US,
                  complete=8 * US)
    return [(n, s, d, dict(st, lock_wait_us=lock_wait_us)
             if n == "serve.batch.stage" else st) for n, s, d, st in spans]


def timeline():
    live = ([span("live.idle", 0, 1000 * US)] + live_batch(1000)
            + [span("live.idle", 2000 * US, 1000 * US)])
    serve = (served(1, 1700, 100, 150)      # under .writes (1,660-1,860)
             + served(2, 1150, 140, 2)      # under .history (1,100-1,300)
             + served(3, 2100, 60, 1))      # the updater idles
    return sorted(serve, key=lambda s: s[1]), sorted(live,
                                                     key=lambda s: s[1])


def test_a_spans_time_is_its_own_and_the_phases_add_up_to_the_batch():
    _, live = timeline()
    found = lp.phases(live)
    assert found["batches"] == 1 and found["batch_ns"] == 1000 * US
    wall = {name: ns[0] // US for name, ns in found["wall_ns"].items()}
    assert wall == {
        "live.batch/own": 20, "live.batch.foldin/own": 10,
        "live.batch.publish/own": 5, "stamps": 5, "live.batch.prepare": 45,
        "live.batch.foldin.group": 40, "live.batch.foldin.history": 200,
        "live.batch.foldin.map": 20, "live.batch.foldin.pack": 30,
        "live.batch.foldin.readback": 100,      # 150 less the call's 50
        "live.batch.foldin.call": 50, "live.batch.foldin.write_back": 50,
        "live.batch.publish.users": 40,
        "live.batch.publish.send": 20,          # 50 less the ride's 30
        "live.batch.publish.ride": 30, "live.batch.publish.lock_wait": 10,
        "live.batch.publish.writes": 200, "live.batch.publish.after": 35,
        "live.batch.record": 90}
    assert sum(wall.values()) == 1000
    # CPU: a span's own is its stat less the stamped spans inside it, the
    # call's through the unstamped readback to the fold
    cpu = found["cpu_us"]
    assert cpu["live.batch.publish.send"] == 45 - 25
    assert cpu["live.batch.foldin.history"] == 150
    assert cpu["live.batch/own"] == 600 - 40 - 300 - 200 - 50
    assert cpu["live.batch.foldin/own"] == 300 - 40 - 150 - 20 - 30 - 20 - 30
    assert cpu["live.batch.publish/own"] == 200 - 40 - 45 - 1 - 90 - 20
    assert sum(cpu.values()) == 600
    assert found["children_cpu_us"] == 40 + 300 + 200 + 50
    assert "live.batch.foldin.readback" not in cpu
    # the timeline: disjoint, by start, ``none`` outside the batch
    cut = found["segments"]
    assert all(a[2] <= b[1] for a, b in zip(cut, cut[1:]))
    assert cut[0] == ("none", 0, 1000 * US)
    assert cut[-1] == ("none", 2000 * US, 3000 * US)
    assert sum(e - s for name, s, e in cut if name != "none") == 1000 * US


def test_three_serving_batches_are_classed_and_the_metrics_follow():
    serve, live = timeline()
    out = lp.summary(serve, live)
    assert out["life_table"] == {
        "live.batch.foldin.history": (1, pytest.approx(0.14),
                                      pytest.approx(0.14)),
        "live.batch.publish.writes": (1, pytest.approx(0.1),
                                      pytest.approx(0.1)),
        "none": (1, pytest.approx(0.06), pytest.approx(0.06))}
    want = {"live_prepare_ms": 0.045,
            "live_fold_host_ms": 0.04 + 0.2 + 0.02 + 0.03 + 0.05,
            "live_fold_call_ms": 0.05,
            "live_publish_lock_wait_ms": 0.01,
            "live_publish_locked_ms": 0.2,
            "live_record_ms": 0.09,
            # prepare, the fold, the publish, record: the batch's children
            "live_host_cpu_ms": (40 + 300 + 200 + 50) / 1000,
            "live_batch_unsplit_pct": 3.5,
            # median(100, 140) - 60
            "serve_life_beside_live_ms": 0.06,
            # numpy's 99th percentile of (1, 2, 150) us: 2 + 0.98 x 148
            "serve_stage_lock_wait_p99_ms": 0.14704}
    assert set(want) == set(NEW_METRICS)
    for name, value in want.items():
        assert out[name] == pytest.approx(value), name
    assert out["phase_table"]["live.batch.foldin.history"] == (
        pytest.approx(0.2), pytest.approx(0.15))


def test_medians_are_over_the_batches_and_a_missing_phase_counts_as_zero():
    """Two batches: the second prepares for 60 us (up to its fold), and an
    item fold of the first alone adds 10 us of ``.group`` at the fold's
    end."""
    first, second = live_batch(0), live_batch(5000, prepare=60)
    first.append(span("live.batch.foldin.group", 550 * US, 10 * US,
                      side="items", cpu_us=5, wall_us=10))
    out = lp.summary([], sorted(first + second, key=lambda s: s[1]))
    assert out["live_prepare_ms"] == pytest.approx(0.05)      # 45 | 55
    assert out["live_fold_host_ms"] == pytest.approx(0.345)   # 350 | 340
    assert out["live_batch_unsplit_pct"] == pytest.approx(
        100 * (25 + 25) / 2000)
    assert out["serve_life_beside_live_ms"] is None
    assert out["serve_stage_lock_wait_p99_ms"] is None
    assert out["life_table"] == {}


def test_a_batch_under_several_phases_goes_to_the_one_that_covers_most():
    _, live = timeline()
    cut = lp.phases(live)["segments"]
    starts = [s for _, s, _ in cut]

    def where(t0, t1):
        return lp.classed((t0 * US, t1 * US), cut, starts)

    assert where(1290, 1330) == "live.batch.foldin.map"    # 10 | 20 | 10
    assert where(1640, 1700) == "live.batch.publish.writes"
    assert where(900, 1040) == "none"                      # 100 of 140
    assert where(1960, 2030) == "none"                     # 30 | 10 | 30
    assert where(1050, 1060) == "live.batch/own"
    assert where(5000, 5100) == "none"                     # past the file


def test_no_live_batch_or_none_of_its_phases_reads_none(tmp_path):
    """A cell without an updater, the parent's trace (``live.batch`` with
    ``.foldin`` and ``.publish`` and nothing under them), a ``--trace 0``
    run, a checkout with no trace: every reader says nothing."""
    serve, live = timeline()
    assert lp.summary(serve, []) is None
    parents = [s for s in live if s[0] in (
        "live.idle", "live.batch", "live.batch.foldin",
        "live.batch.foldin.readback", "live.batch.publish")]
    assert lp.summary(serve, parents) is None
    cell = SimpleNamespace(root=str(tmp_path))
    for trace in (None, object()):
        ctx = harness.LayerContext(cell, {}, trace, "cpu")
        assert lp.traced(ctx) is None
        for name in NEW_METRICS:
            reader = harness.load_module(os.path.join(
                tiny.BENCH, "layer_metrics", name + ".py"), "test_" + name)
            assert reader.read(ctx) is None, name


def test_the_new_metrics_close_the_manifest_for_the_four_live_cells():
    manifest = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    last = manifest["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in last] == list(NEW_METRICS)
    live = [w["name"] for w in manifest["workloads"]
            if "-live" in w["config"]]
    assert len(live) == 4
    for m in last:
        assert (m["source"], m["moves"], m["better"]) == (
            "program_span", "serve_p90_ms", "lower")
        assert sorted(m["workloads"]) == sorted(live)
        assert os.path.exists(os.path.join(tiny.BENCH, "layer_metrics",
                                           m["name"] + ".py"))


def test_a_reader_prints_its_table_beside_its_value(tmp_path, capsys,
                                                    monkeypatch):
    """``live_batch_unsplit_pct`` prints the run's ``phase_table`` line and
    ``serve_life_beside_live_ms`` its ``life_table``, through the cell's
    ``say`` (the trace itself is ``pipeline_spans``' to read: here the
    synthetic timeline stands for it)."""
    import json

    monkeypatch.setattr(lp, "traced", lambda ctx: lp.summary(*timeline()))
    cell = harness.Cell("c", {}, {}, 1, 0, 1.0, True, str(tmp_path),
                        tiny.BENCH, 0.0, None)
    ctx = harness.LayerContext(cell, {}, object(), "cpu")

    def read(name):
        reader = harness.load_module(os.path.join(
            tiny.BENCH, "layer_metrics", name + ".py"), "said_" + name)
        value = reader.read(ctx)
        return value, capsys.readouterr().out.strip().splitlines()

    value, said = read("live_batch_unsplit_pct")
    line, = map(json.loads, said)
    assert value == pytest.approx(3.5)
    assert (line.pop("cell"), line.pop("what"),
            line.pop("batches")) == ("c", "phase_table", 1)
    assert line["live.batch.foldin.history"] == [0.2, 0.15]
    assert line["live.batch/own"] == [0.02, 0.01]
    value, said = read("serve_life_beside_live_ms")
    line, = map(json.loads, said)
    assert value == pytest.approx(0.06)
    assert (line.pop("cell"), line.pop("what"),
            line.pop("batches")) == ("c", "life_table", 3)
    assert line == {"live.batch.foldin.history": [1, 0.14, 0.14],
                    "live.batch.publish.writes": [1, 0.1, 0.1],
                    "none": [1, 0.06, 0.06]}
    assert read("live_record_ms") == (pytest.approx(0.09), [])
