"""``benchmark/program_spans.py``: the arithmetic on hand-written events, the
readers on a run without a device plane (every one ``None``), and the reader
on a trace this repo's serving engine recorded on the chip."""

from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from benchmark import harness, program_spans as ps
from benchmark import trace as tr
from benchmark.tests import tiny

DATA = os.path.join(tiny.HERE, "data")


def span(name, start, dur, **stats):
    return (name, start, dur, stats)


def test_a_gap_under_two_spans_splits_by_overlap():
    # busy 0-100 and 200-300: one gap, 100-200
    got = ps.attribute([(0, 100), (200, 300)],
                       [span("serve.batch.complete", 90, 40),    # 100-130
                        span("serve.batch.coalesce", 130, 50),   # 130-180
                        span("serve.batch.stage", 180, 15),      # 180-195
                        span("serve.batch.dispatch", 195, 30)],  # 195-200
                       (0, 300))
    assert got == {"serve.batch.complete": 30, "serve.batch.coalesce": 50,
                   "serve.batch.stage": 15, "serve.batch.dispatch": 5,
                   "unattributed": 0}


def test_a_gap_under_no_span_is_unattributed():
    got = ps.attribute([(0, 100), (200, 300), (400, 450)],
                       [span("serve.idle", 310, 50)], (0, 450))
    assert got == {"serve.idle": 50, "unattributed": 150}
    # spans of one name met in any order, and twice over, count once
    twice = ps.attribute([(0, 100), (200, 300)],
                         [span("serve.idle", 150, 20),
                          span("serve.idle", 120, 40)], (0, 300))
    assert twice == {"serve.idle": 50, "unattributed": 50}


def test_nested_device_operations_count_once_and_the_window_clips():
    # a while 0-100 with its body inside, then 150-200
    busy = [(0, 100), (10, 40), (50, 90), (150, 200)]
    got = ps.attribute(busy, [span("serve.batch.readback", 0, 400)],
                       (0, 200))
    assert got == {"serve.batch.readback": 50, "unattributed": 0}
    # a window inside the trace: the gap 100-150 is cut at 120, and the
    # window's own edges open no gap
    cut = ps.attribute(busy, [span("serve.batch.readback", 0, 400)],
                       (20, 120))
    assert cut == {"serve.batch.readback": 20, "unattributed": 0}
    assert ps.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10


def batch(t, device_ms=40):
    """The dispatch and readback spans of a batch launched at ``t``."""
    return [span("serve.batch.dispatch", t, 10),
            span("serve.batch.readback", t + 10, device_ms + 10)]


def test_the_device_clock_is_moved_to_where_the_spans_allow_it():
    spans = batch(0) + batch(100)          # readback ends 60, dispatch 100
    assert ps.no_batch_in_flight(spans) == [(60, 100)]
    # a device clock 7 early: the second program "starts" at 95, before the
    # call that launched it.  Shifts of 5 to 15 put no operation between
    # the batches; the least of them is taken
    assert ps.clock_shift_ns([(-5, 45), (95, 145)], [(60, 100)]) == 5
    # a clock that is late leaves the first program running at 60
    assert ps.clock_shift_ns([(30, 70), (120, 170)], [(60, 100)]) == -20
    # already possible: still the earliest the device can have run, so that
    # two recordings read alike
    assert ps.clock_shift_ns([(12, 45), (102, 145)], [(60, 100)]) == -2
    # nothing to go by: one batch, or no device operation
    assert ps.clock_shift_ns([(0, 10)], []) == 0
    assert ps.clock_shift_ns([], [(60, 100)]) == 0
    # a shift by a whole cycle would serve as well; the nearest is taken
    many = [s for t in range(0, 1000, 100) for s in batch(t)]
    busy = [(t + 3 - 7, t + 43 - 7) for t in range(0, 1000, 100)]
    assert ps.clock_shift_ns(busy, ps.no_batch_in_flight(many),
                             limit_ns=250) == 4
    # no shift empties the forbidden stretches: the one that leaves least
    assert ps.clock_shift_ns([(0, 80)], [(60, 100)], limit_ns=15) == -15


def test_readers_say_nothing_without_a_trace_or_a_device_plane(tmp_path):
    """A ``--trace 0`` run, a checkout with no trace, and a trace recorded
    on the CPU (host spans and no ``/device:TPU`` plane) each read as
    ``None``: the result line leaves the metric out."""
    import jax

    cell = SimpleNamespace(root=str(tmp_path))
    assert ps.traced_cycle(harness.LayerContext(cell, {}, None, "cpu")) is None
    ctx = harness.LayerContext(cell, {}, object(), "cpu")
    assert ps.traced_cycle(ctx) is None
    trace_dir = tmp_path / ".bench_cache" / "runs" / "trace"
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=tr.profiler_options())
    try:
        with jax.profiler.TraceAnnotation("serve.batch", seq=1, bucket=8,
                                          rows=5):
            with jax.profiler.TraceAnnotation("serve.batch.stage"):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(trace_dir))
    spans = ps.read(path)
    assert [s[0] for s in spans] == ["serve.batch", "serve.batch.stage"]
    assert spans[0][3] == {"seq": 1, "bucket": 8, "rows": 5}
    assert ps.device_busy(path) == {} and ps.cycle(path) is None
    assert ps.traced_cycle(ctx) is None
    for name in ("serve_gap_stage_ms", "serve_gap_unattributed_ms",
                 "serve_batch_host_ms", "serve_batch_fill_pct"):
        reader = harness.load_module(os.path.join(
            tiny.BENCH, "layer_metrics", name + ".py"), "test_" + name)
        assert reader.read(ctx) is None


def test_reader_on_a_trace_recorded_on_the_chip():
    """The engine thread's spans beside the device's operations, as this
    PR's serving engine wrote them on a v5e (``expect.json`` says how the
    file was cut)."""
    path = os.path.join(DATA, "serve_v5e_spans.xplane.pb")
    expect = harness.load_json(os.path.join(DATA,
                                            "serve_v5e_spans.expect.json"))
    c = ps.cycle(path)
    assert c["batches"] == expect["batches"]
    assert (c["rows"], c["bucket_rows"]) == (expect["rows"],
                                             expect["bucket_rows"])
    assert c["host_ns"] == expect["host_ns"]
    # the recording's device clock ran 1.2 ms early: every program began
    # before the dispatch span of the call that launched it
    assert c["clock_shift_ns"] == {0: expect["clock_shift_ns"]}
    busy = tr.busy_union(ps.device_busy(path)[0])
    dispatch = [s for s in ps.read(path) if s[0] == "serve.batch.dispatch"]
    begins = [b[0] for a, b in zip(busy, busy[1:]) if b[0] - a[1] > 2e6]
    assert len(begins) == len(dispatch) - 1
    early = [d[1] - b for d, b in zip(dispatch[1:], begins)]
    assert min(early) > 0 and max(early) == expect["clock_shift_ns"]
    assert c["gap_ns"] == pytest.approx(expect["gap_ns"], rel=1e-9)
    # the gaps by span add up to the idle device of ``trace.summarize``
    s = tr.summarize(tr.read_xplane(path))
    idle_ns = (s.window_s - s.busy_s) * 1e9
    assert sum(c["gap_ns"].values()) == pytest.approx(idle_ns, rel=1e-6)
    # under no span: the engine thread between its spans (``next_batch``
    # after the pop, the turn of its loop): 7.4 % here
    assert c["gap_ns"]["unattributed"] < 0.1 * idle_ns
    names = {s[0] for s in ps.read(path)}
    assert names == {"serve.idle", "serve.batch.coalesce", "serve.batch",
                     "serve.batch.stage", "serve.batch.dispatch",
                     "serve.batch.readback", "serve.batch.complete"} - set(
                         expect["spans_absent"])
