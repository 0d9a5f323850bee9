"""``benchmark/pipeline_spans.py``: the join by ``seq`` and by order and the
clock's shift on hand-made tuples, the segments adding up to a batch's life
to the nanosecond, the readers on a run without the spans (every one
``None``), and the reader — with the OLD readers beside it — on a trace the
pipelined engine recorded on the chip."""

from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from benchmark import harness, live_spans, pipeline_spans as pl
from benchmark import program_spans as ps
from benchmark.tests import tiny

DATA = os.path.join(tiny.HERE, "data")
PROGRAM = "jit__serve_int8_packed"
NEW_METRICS = (
    "serve_slot_wait_ms", "serve_upload_ms", "serve_launch_ms",
    "serve_life_ms", "serve_life_launch_lag_ms",
    "serve_life_ready_unread_ms", "serve_life_readback_tail_ms",
    "serve_dispatch_cpu_pct", "serve_complete_cpu_pct",
    "serve_clock_slack_ms", "live_batch_cpu_pct", "live_foldin_readback_ms")


def span(name, start, dur, **stats):
    return (name, start, dur, stats)


def batch(seq, t0, *, stage=10, upload=5, launch=20, after=5, wait=0,
          readback=60, complete=8, bucket=8, program=PROGRAM):
    """The spans of one batch staged at ``t0``: stage, then dispatch
    (upload, launch, ``after`` more), and ``wait`` later on the completion
    thread readback and complete."""
    d0 = t0 + stage
    tl = d0 + upload
    t2 = tl + launch + after
    t3 = t2 + wait
    return [span("serve.batch", t0, t2 - t0, seq=seq, bucket=bucket, rows=3),
            span("serve.batch.stage", t0, stage, seq=seq, cpu_us=0),
            span("serve.batch.dispatch", d0, t2 - d0, seq=seq, cpu_us=0),
            span("serve.batch.dispatch.upload", d0, upload, seq=seq),
            span("serve.batch.dispatch.launch", tl, launch, seq=seq,
                 program=program, pinned=1),
            span("serve.batch.readback", t3, readback, seq=seq),
            span("serve.batch.complete", t3 + readback, complete, seq=seq)]


def test_two_batches_in_flight_join_their_runs_and_share_one_clock():
    """Batch 2 is staged and dispatched under batch 1's readback; the
    device ran them back to back.  Its clock is 7 early: batch 1's program
    "starts" before its launch span until shifted."""
    spans = sorted(batch(1, 0) + batch(2, 50, wait=40), key=lambda s: s[1])
    whole, launches = pl.batches(spans)
    assert sorted(whole) == [1, 2]
    b1, b2 = whole[1], whole[2]
    assert (b1.T0, b1.TL, b1.T2, b1.T3, b1.T4, b1.T5) == (0, 15, 40, 40,
                                                          100, 108)
    assert (b2.T0, b2.TL, b2.T3, b2.T4) == (50, 65, 130, 190)
    assert launches == {PROGRAM: [(15, 1), (65, 2)]}
    # on its own clock the device ran 13-63 and 63-113
    runs = {PROGRAM: {0: [(13, 63, 901), (63, 113, 902)]}}
    # d >= 15 - 13 and >= 65 - 63; d <= 100 - 63 and <= 190 - 113
    pairs = pl.pair_by_order(launches[PROGRAM], runs[PROGRAM][0], whole)
    assert pairs == [(1, 15, 100, 13, 63), (2, 65, 190, 63, 113)]
    assert pl.shift_interval(pairs) == (2, 37, 2, 1)
    assert pl.device_runs(launches, runs, whole) == (
        {1: (13, 63), 2: (63, 113)}, (2, 37, 2, 1))
    out = pl.life(spans, [span("pipe.slot_wait", 41, 9, seq=2)], runs)
    assert (out["batches"], out["joined"], out["shift_ns"],
            out["slack_ns"], out["bound_by"]) == (2, 2, 2, 35, (2, 1))
    assert out["slot_wait_ns"] == 9 and out["bucket"] == 8
    (_, one), (_, two) = out["lives"]
    assert one == {"prelaunch": 15, "launch_lag": 0, "device": 50,
                   "ready_unread": 0, "readback_tail": 35, "complete": 8}
    # batch 2's answer lay ready from 115 until its readback began at 130
    assert two == {"prelaunch": 15, "launch_lag": 0, "device": 50,
                   "ready_unread": 15, "readback_tail": 60, "complete": 8}
    # the host's own enqueue of each run, by its run_id: a program cannot
    # have started before it, which is later than the launch span's start
    tighter = pl.life(spans, [], runs, {901: 19, 902: 75})
    assert (tighter["shift_ns"], tighter["slack_ns"]) == (12, 25)
    assert [seg["launch_lag"] for _, seg in tighter["lives"]] == [10, 10]
    # an enqueue outside its batch's life contradicts the pair: not used
    assert pl.life(spans, [], runs, {901: 14, 902: 191})["shift_ns"] == 2


def test_the_segments_add_up_to_the_batchs_life_to_the_nanosecond():
    spans = []
    for k in range(40):
        spans += batch(k, 1_000 * k + 7 * (k % 5), stage=31 + k % 3,
                       upload=17, launch=203 + k, after=3, wait=11 * (k % 4),
                       readback=400 + 13 * (k % 7), complete=29)
    spans.sort(key=lambda s: s[1])
    whole, launches = pl.batches(spans)
    # a device clock 1,234,567 early, a lag that varies
    runs = {PROGRAM: {0: [(b.TL + 90 + 5 * (b.seq % 3) - 1_234_567,
                           b.TL + 390 - 1_234_567, b.seq)
                          for b in sorted(whole.values(),
                                          key=lambda b: b.TL)]}}
    out = pl.life(spans, [], runs)
    assert out["joined"] == 40 and out["shift_ns"] == 1_234_567 - 90
    for b, seg in out["lives"]:
        assert sum(seg[name] for name in pl.SEGMENTS) == b.T5 - b.T0
        assert all(seg[name] >= 0 for name in pl.SEGMENTS)
    # what does not depend on the shift: from the launch to the readback's end
    again = pl.life(spans, [], {PROGRAM: {0: [
        (s + 999, e + 999, k) for s, e, k in runs[PROGRAM][0]]}})
    assert again["shift_ns"] == out["shift_ns"] - 999
    assert [seg for _, seg in again["lives"]] == [
        seg for _, seg in out["lives"]]


def test_on_four_devices_a_batchs_run_is_earliest_start_to_latest_end():
    """The chips of a host are recorded on one clock: one interval holds
    the pairs of all four, and a batch runs from the first chip's start to
    the last chip's end."""
    spans = sorted(batch(1, 0, program="jit_serve_mesh_int8")
                   + batch(2, 200, program="jit_serve_mesh_int8"),
                   key=lambda s: s[1])
    whole, launches = pl.batches(spans)
    # the devices' clock is 1000 early; chip d starts d later than chip 0
    runs = {"jit_serve_mesh_int8": {
        d: [(20 + d - 1000, 70 - d - 1000, 7),
            (220 + 2 * d - 1000, 270 + 3 * d - 1000, 8)]
        for d in range(4)}}
    found, interval = pl.device_runs(launches, runs, whole)
    assert found == {1: (-980, -930), 2: (-780, -721)}
    # TL 15 and 215, T4 100 and 300: chip 0 starts first (d >= 995), chip
    # 3 ends batch 2 last (d <= 300 + 721)
    assert interval == (995, 1021, 2, 2)
    out = pl.life(spans, [], runs)
    assert (out["shift_ns"], out["slack_ns"]) == (995, 26)
    assert [seg["device"] for _, seg in out["lives"]] == [50, 59]
    assert [seg["launch_lag"] for _, seg in out["lives"]] == [0, 0]
    # a chip whose clock sits elsewhere leaves no shift that suits all
    runs["jit_serve_mesh_int8"][3] = [(s + 500, e + 500, k) for s, e, k
                                      in runs["jit_serve_mesh_int8"][3]]
    assert pl.life(spans, [], runs)["slack_ns"] < 0


def test_the_streams_ends_may_cut_a_launch_from_its_run_but_no_more():
    spans = []
    for k in range(8):
        spans += batch(k, 1_000 * k + 37 * (k % 3))
    spans.sort(key=lambda s: s[1])
    whole, launches = pl.batches(spans)
    runs = [(b.TL + 9, b.TL + 59, 100 + b.seq)
            for b in sorted(whole.values(), key=lambda b: b.TL)]

    def paired(ls, rs):
        return {p[0]: p[3:] for p in pl.pair_by_order(ls, rs, whole)}

    full = paired(launches[PROGRAM], runs)
    assert full == {b.seq: (b.TL + 9, b.TL + 59) for b in whole.values()}
    # the trace began after batch 0's launch span and before its run; its
    # end lost the last run: every batch still finds its own
    early_run = [(-500, -450, 99)] + runs[:-1]
    assert paired(launches[PROGRAM], early_run) == {
        seq: run for seq, run in full.items() if seq != 7}
    # a launch span lost at the start instead
    assert paired(launches[PROGRAM][1:], runs) == {
        seq: run for seq, run in full.items() if seq != 0}
    # five runs short: more than the stream's two ends can have cut
    assert pl.pair_by_order(launches[PROGRAM], runs[:3], whole) is None
    assert pl.device_runs(launches, {PROGRAM: {0: runs[:3]}}, whole) is None
    assert pl.life(spans, [], {PROGRAM: {0: runs[:3]}}) is None
    # no run of the program at all, no launch span at all
    assert pl.life(spans, [], {}) is None
    old = [s for s in spans if "dispatch." not in s[0]]
    assert pl.life(old, [], {PROGRAM: {0: runs}}) is None


def test_a_batch_cut_by_the_traces_edge_still_orders_the_launches():
    """A batch whose readback fell outside the file is no whole batch, but
    its launch answers to a run: the others keep theirs."""
    spans = sorted(batch(1, 0) + batch(2, 300) + [
        s for s in batch(3, 600) if s[0] not in (
            "serve.batch.readback", "serve.batch.complete")],
        key=lambda s: s[1])
    whole, launches = pl.batches(spans)
    assert sorted(whole) == [1, 2] and len(launches[PROGRAM]) == 3
    runs = {PROGRAM: {0: [(20, 70, 1), (320, 370, 2), (620, 670, 3)]}}
    out = pl.life(spans, [], runs)
    assert out["joined"] == 2 and out["batches"] == 3


def test_medians_are_over_the_most_ridden_bucket_and_cpu_is_a_share():
    spans = []
    for k in range(5):
        spans += batch(k, 1_000 * k, bucket=8, upload=5)
    spans += batch(5, 5_000, bucket=32, upload=50, launch=200, readback=600)
    spans.sort(key=lambda s: s[1])
    whole, _ = pl.batches(spans)
    runs = {PROGRAM: {0: [(b.TL + 10, b.TL + 40, b.seq) for b in sorted(
        whole.values(), key=lambda b: b.TL)]}}
    out = pl.life(spans, [], runs)
    assert out["bucket"] == 8 and out["joined"] == 6
    assert out["median_ns"]["upload"] == 5
    assert out["median_ns"]["launch"] == 20
    assert out["median_ns"]["life"] == 108
    stamped = [span("serve.batch.complete", 0, 130_000, seq=1, cpu_us=80,
                    wall_us=100),
               span("serve.batch.complete", 0, 330_000, seq=2, cpu_us=120,
                    wall_us=300),
               span("serve.batch.complete", 0, 999_000, seq=3)]
    assert pl.cpu_pct(stamped, "serve.batch.complete") == 50.0
    assert pl.cpu_pct(stamped, "serve.batch.dispatch") is None


def test_readers_say_nothing_without_a_trace_or_the_spans(tmp_path):
    """A ``--trace 0`` run, a checkout with no trace, and a trace of a
    program recorded on the CPU (host spans and no ``/device:TPU`` plane)
    each read as ``None``: the result line leaves the metrics out."""
    import jax

    from benchmark import trace as tr

    cell = SimpleNamespace(root=str(tmp_path))
    assert pl.traced(harness.LayerContext(cell, {}, None, "cpu")) is None
    ctx = harness.LayerContext(cell, {}, object(), "cpu")
    assert pl.traced(ctx) is None
    trace_dir = tmp_path / ".bench_cache" / "runs" / "trace"
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=tr.profiler_options())
    try:
        with jax.profiler.TraceAnnotation("serve.batch", seq=1, bucket=8,
                                          rows=5):
            with jax.profiler.TraceAnnotation("serve.batch.dispatch"):
                jax.numpy.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("live.batch", seq=1):
            pass
    finally:
        jax.profiler.stop_trace()
    assert pl.traced(ctx) is None           # no device plane
    for name in NEW_METRICS:
        reader = harness.load_module(os.path.join(
            tiny.BENCH, "layer_metrics", name + ".py"), "test_" + name)
        assert reader.read(ctx) is None, name


def test_every_new_metric_is_in_the_manifest_with_a_file():
    manifest = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for name in NEW_METRICS:
        m = entries[name]
        assert m["source"] == "program_span"
        assert os.path.exists(os.path.join(tiny.BENCH, "layer_metrics",
                                           name + ".py"))
        live = name.startswith("live_")
        assert sorted(m["workloads"]) == sorted(
            c for c in cells if not live or "live" in c)
    assert [m["name"] for m in manifest["per_layer"]][-12:] == list(
        NEW_METRICS)


FIXTURE = os.path.join(DATA, "serve_v5e_pipeline.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """(what ``read`` finds in the fixture, ``expect.json``): the pipelined
    engine beside a live updater on a v5e, as this PR's program wrote it
    (``expect.json`` says how the file was cut)."""
    return pl.read(FIXTURE), harness.load_json(
        os.path.join(DATA, "serve_v5e_pipeline.expect.json"))


def test_reader_on_a_trace_the_pipelined_engine_recorded_on_the_chip(
        recorded):
    (spans, pipe_spans, live, runs, enqueued), expect = recorded
    out = pl.life(spans, pipe_spans, runs, enqueued)
    lives = out.pop("lives")
    assert json_like(out) == expect["life"]
    # every batch whole in the file found its run, by order — and the
    # runtime's own enqueue of that run (joined by run_id) lies inside the
    # batch's life, for every one of them: the order join is right
    whole, launches = pl.batches(spans)
    assert out["joined"] == len(whole) == len(lives) > 80
    (program, ls), = launches.items()
    (device, rs), = runs[program].items()
    pairs = pl.pair_by_order(ls, sorted(rs), whole, enqueued)
    assert [p[1] > tl for p, (tl, _) in zip(pairs, ls)].count(True) == len(
        pairs) == expect["checked_by_run_id"]
    # the six segments: none negative, adding up to the nanosecond
    for b, seg in lives:
        assert sum(seg[name] for name in pl.SEGMENTS) == b.T5 - b.T0
        assert min(seg.values()) >= 0
    # a program's run is the device's whole busy time for that batch: the
    # device segment is the module event's own duration
    by_seq = {p[0]: p[4] - p[3] for p in pairs}
    assert all(seg["device"] == by_seq[b.seq] for b, seg in lives)
    # two batches in flight: most batches are launched before the batch
    # before has been read back
    over = sum(b.TL < a.T4 for (a, _), (b, _) in zip(lives, lives[1:]))
    assert over == expect["launched_under_a_readback"] > 0.8 * len(lives)
    # the clock's interval is narrower with the enqueue's start than with
    # the launch span's
    plain = pl.life(spans, pipe_spans, runs)
    assert plain["slack_ns"] == expect["slack_ns_by_spans_alone"] \
        > out["slack_ns"] > 0
    assert plain["median_ns"]["device"] == out["median_ns"]["device"]
    for name, value in expect["cpu_pct"].items():
        assert pl.cpu_pct(live if name.startswith("live.") else spans,
                          name) == pytest.approx(value)
    assert pl.cpu_pct(spans, "serve.batch") is None


def json_like(value):
    """``value`` as JSON would hand it back (tuples as lists, int keys as
    strings)."""
    import json

    return json.loads(json.dumps(value))


def test_the_old_readers_read_the_new_programs_trace_as_before(recorded):
    """``program_spans.cycle`` and ``live_spans.cycle`` on the new
    program's trace: the two child spans add two keys to ``gap_ns`` (idle
    under them, which lies under ``dispatch`` too) and nothing else moves:
    ``host_ns`` is the four old phases', and ``pipe.slot_wait`` is no
    ``serve.`` span.  (The old keys no longer add up to the device's idle
    time, here or on the parent's trace: with two threads a gap under two
    spans counts twice, PERF.md section 7.)"""
    _, expect = recorded
    c = ps.cycle(FIXTURE)
    old = {"serve.idle", "serve.batch.coalesce", "serve.batch.stage",
           "serve.batch.dispatch", "serve.batch.readback",
           "serve.batch.complete", "unattributed"}
    assert set(c["gap_ns"]) - old == {pl.UPLOAD, pl.LAUNCH}
    assert c["gap_ns"][pl.UPLOAD] + c["gap_ns"][pl.LAUNCH] <= c["gap_ns"][
        pl.DISPATCH]
    spans = ps.read(FIXTURE)
    assert {s[0] for s in spans} == (old - {"unattributed"}) | {
        "serve.batch", pl.UPLOAD, pl.LAUNCH}
    assert (c["batches"], c["rows"], c["bucket_rows"], c["host_ns"]) == (
        expect["old_cycle"]["batches"], expect["old_cycle"]["rows"],
        expect["old_cycle"]["bucket_rows"], expect["old_cycle"]["host_ns"])
    assert c["gap_ns"] == pytest.approx(expect["old_cycle"]["gap_ns"])
    assert json_like(live_spans.cycle(FIXTURE)) == expect["old_live_cycle"]
    assert pl.read(FIXTURE)[2]          # and the reader's own live. spans
