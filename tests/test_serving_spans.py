"""The serving engine's batch cycle as spans on the profiler's clock, the
per-batch flight record that keeps the same durations without a profiler,
``Ticket.t_done``, and what the engine thread's instrumentation writes
into the registry per batch (ISSUE 25)."""

from __future__ import annotations

import glob
import os
import time

import jax
import numpy as np
import pytest

from tests.conftest import GatedResponses
from tpu_als import obs
from tpu_als.obs import tracing
from tpu_als.obs.schema import (
    EVENTS,
    PIPE_SPAN_KEYS,
    SERVE_BATCH_SPAN_KEYS,
    SERVE_DISPATCH_SPAN_KEYS,
    SERVE_SPAN_KEYS,
)
from tpu_als.serving import MicroBatcher, ServingEngine
from tpu_als.serving.batcher import Ticket

PHASES = ("serve.batch.stage", "serve.batch.dispatch",
          "serve.batch.readback", "serve.batch.complete")
UPLOAD, LAUNCH = SERVE_DISPATCH_SPAN_KEYS
# every span the engine writes for a batch: all carry its ``seq``, and
# the four phases ``cpu_us`` beside ``wall_us``
OF_A_BATCH = ("serve.batch",) + PHASES + SERVE_DISPATCH_SPAN_KEYS
BATCHES = ((5, 8), (20, 32), (32, 32))      # (rows, the bucket they ride)


def _engine(max_wait_s=0.0, **kw):
    rng = np.random.default_rng(0)
    eng = ServingEngine(k=5, buckets=(8, 32), shortlist_k=32,
                        max_wait_s=max_wait_s, **kw)
    # a catalog large enough that a batch takes milliseconds: the ring's
    # clock reads are compared with the spans' to within one
    eng.publish(rng.normal(size=(40, 16)).astype(np.float32),
                rng.normal(size=(100_000, 16)).astype(np.float32))
    return eng


def _drain(eng, rows):
    tickets = [eng.submit(j % 40) for j in range(rows)]
    eng.serve_batch(eng.batcher.next_batch(timeout=1.0))
    return tickets


def _aged_drain(eng, rows):
    """``_drain`` for an engine whose ``max_wait_s`` is long: the head is
    backdated, so the timed rule of a caller that drives ``next_batch``
    itself pops at once (``age``)."""
    tickets = [eng.submit(j % 40) for j in range(rows)]
    tickets[0].t_submit -= 2 * eng.batcher.max_wait_s
    eng.serve_batch(eng.batcher.next_batch(timeout=1.0))
    return tickets


def _serve_spans(trace_dir):
    """[(name, start_ns, dur_ns, stats)] of the ``serve.`` and ``pipe.``
    spans of every host line of the trace, by start."""
    return [s[:4] for s in _spans_by_line(trace_dir)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three batches driven through ``next_batch`` + ``serve_batch`` under
    the profiler: (the trace's serve.* spans, the engine, the tickets of
    each batch)."""
    obs.reset()
    eng = _engine()
    for rows, _ in BATCHES:          # each bucket's program, compiled
        _drain(eng, rows)
    eng.flight.dump("warm")          # what follows is the traced batches'
    eng.batch_flight.dump("warm")
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        tickets = [_drain(eng, rows) for rows, _ in BATCHES]
    finally:
        jax.profiler.stop_trace()
    return _serve_spans(trace_dir), eng, tickets


def test_every_span_of_the_cycle_once_per_batch(traced):
    spans, _, _ = traced
    names = [s[0] for s in spans]
    for name in SERVE_BATCH_SPAN_KEYS:
        if name != "serve.idle":     # the queue was never empty
            assert names.count(name) == len(BATCHES), name
    assert set(names) <= set(SERVE_BATCH_SPAN_KEYS
                             + SERVE_DISPATCH_SPAN_KEYS)
    whole = [s for s in spans if s[0] == "serve.batch"]
    seqs = [s[3]["seq"] for s in whole]
    assert seqs == list(range(seqs[0], seqs[0] + len(BATCHES)))
    assert [(s[3]["rows"], s[3]["bucket"]) for s in whole] == list(BATCHES)
    assert all(s[3]["path"] == "int8" for s in whole)
    coalesce = [s for s in spans if s[0] == "serve.batch.coalesce"]
    assert [s[3]["waiting"] for s in coalesce] == [r for r, _ in BATCHES]


def test_phases_lie_inside_their_batch_disjoint_and_cover_it(traced):
    spans, _, _ = traced
    for _, b0, bdur, _ in (s for s in spans if s[0] == "serve.batch"):
        inside = [s for s in spans
                  if s[0] in PHASES and b0 <= s[1] < b0 + bdur]
        assert [s[0] for s in inside] == list(PHASES)    # in this order
        for (_, s0, d0, _), (_, s1, _, _) in zip(inside, inside[1:]):
            assert s0 + d0 <= s1                         # disjoint
        assert inside[-1][1] + inside[-1][2] <= b0 + bdur
        assert sum(s[2] for s in inside) >= 0.95 * bdur
    # the coalescing wait ends before its batch starts
    for c, b in zip((s for s in spans if s[0] == "serve.batch.coalesce"),
                    (s for s in spans if s[0] == "serve.batch")):
        assert c[1] + c[2] <= b[1]


def _spans_by_line(trace_dir):
    """[(name, start_ns, dur_ns, stats, line)] of the ``serve.`` and
    ``pipe.`` spans of the trace, by start, each with the host line it
    sits on."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for p, plane in enumerate(jax.profiler.ProfileData.from_file(path).planes):
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("serve.", "pipe.")):
                    spans.append((ev.name, ev.start_ns, ev.duration_ns,
                                  dict(ev.stats), (p, n)))
    return sorted(spans, key=lambda s: s[1])


def test_started_engine_writes_the_same_spans_from_two_threads(tmp_path):
    """The engine thread writes ``serve.batch`` around stage + dispatch,
    the completion thread ``readback`` and ``complete`` with the batch's
    ``seq``, on a line of its own; batch 2's stage and dispatch lie
    UNDER batch 1's readback, which is the point; no span has a name the
    trace readers do not know.  The coalesce span is still written around
    each pop, however short: with a slot free the loop waits for nothing
    (``closed_by`` ``slot``; ISSUE 35), though ``max_wait_s`` is 30 s."""
    obs.reset()
    eng = _engine(max_wait_s=30.0)
    _aged_drain(eng, 5)                             # compiled
    first = eng._batch_seq + 1
    gated = GatedResponses(eng)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with eng:
            a = eng.submit(0)
            gated.wait_dispatched(1)
            assert gated.gates[0].entered.wait(10.0)
            b = eng.submit(1)
            gated.wait_dispatched(2)
            gated.open()
            a.result(timeout=10.0), b.result(timeout=10.0)
    finally:
        jax.profiler.stop_trace()
    spans = _spans_by_line(str(tmp_path))
    assert {s[0] for s in spans} <= set(
        SERVE_BATCH_SPAN_KEYS + SERVE_DISPATCH_SPAN_KEYS + PIPE_SPAN_KEYS)
    by_seq = {}
    for name, start, dur, stats, line in spans:
        if name in ("serve.batch", "serve.batch.readback",
                    "serve.batch.complete"):
            assert name not in by_seq.setdefault(stats["seq"], {})
            by_seq[stats["seq"]][name] = (start, start + dur, line)
    assert sorted(by_seq) == [first, first + 1]
    whole = [s for s in spans if s[0] == "serve.batch"]
    assert [(s[3]["rows"], s[3]["bucket"], s[3]["path"])
            for s in whole] == [(1, 8, "int8")] * 2
    for seq, own in by_seq.items():
        b0, b1, engine_line = own["serve.batch"]
        inside = [s[0] for s in spans if s[0] in PHASES
                  and b0 <= s[1] < b1 and s[4] == engine_line]
        assert inside == list(PHASES[:2])           # stage, dispatch
        r0, r1, line = own["serve.batch.readback"]
        c0, c1, cline = own["serve.batch.complete"]
        assert line == cline != engine_line
        assert b1 <= r0 and r1 <= c0                # handed over, in order
    # batch 2 was staged and dispatched while batch 1 was read back
    r0, r1, _ = by_seq[first]["serve.batch.readback"]
    b0, b1, _ = by_seq[first + 1]["serve.batch"]
    assert r0 < b0 and b1 < r1
    # and completed after it
    assert by_seq[first]["serve.batch.complete"][1] <= \
        by_seq[first + 1]["serve.batch.readback"][0]
    # the records keep the spans' durations; ``serve.batch`` is the whole
    # life there, for batch 2 far longer than its engine-thread span
    recs = {r["batch"]: r for r in eng.batch_flight.records()}
    for seq, own in by_seq.items():
        for name in ("serve.batch.readback", "serve.batch.complete"):
            assert recs[seq]["spans"][name] * 1e9 == pytest.approx(
                own[name][1] - own[name][0], abs=1e6), name
        life = recs[seq]["spans"]["serve.batch"] * 1e9
        assert life == pytest.approx(
            own["serve.batch.complete"][1] - own["serve.batch"][0], abs=1e6)
    assert [recs[first + j]["in_flight"] for j in (0, 1)] == [0, 1]
    coalesce = [s for s in spans if s[0] == "serve.batch.coalesce"]
    assert len(coalesce) == 2 and all(s[4] == engine_line for s in coalesce)
    for (_, c0, dur, stats, _), seq in zip(coalesce, sorted(by_seq)):
        assert (stats["closed_by"], stats["waiting"]) == ("slot", 1)
        assert stats["closed_by"] == recs[seq]["closed_by"]
        assert stats["head_wait"] == pytest.approx(recs[seq]["head_wait"])
        assert dur < 0.3e9                          # 1 % of max_wait_s
        assert recs[seq]["spans"]["serve.batch.coalesce"] * 1e9 == \
            pytest.approx(dur, abs=1e6)
        assert c0 + dur <= by_seq[seq]["serve.batch"][0]


@pytest.fixture(scope="module")
def pipelined(tmp_path_factory):
    """A started engine under the profiler with both slots taken: batch 1
    is held in its readback, batch 2 dispatched behind it, and the
    engine thread waits for a slot before it can pop the third request:
    (the trace's spans with their lines, the engine, its first seq)."""
    obs.reset()
    eng = _engine(max_wait_s=30.0)
    _aged_drain(eng, 5)                             # compiled
    first = eng._batch_seq + 1
    gated = GatedResponses(eng)
    trace_dir = str(tmp_path_factory.mktemp("pipelined"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with eng:
            a = eng.submit(0)
            gated.wait_dispatched(1)
            assert gated.gates[0].entered.wait(10.0)
            b = eng.submit(1)
            gated.wait_dispatched(2)
            c = eng.submit(2)
            time.sleep(0.02)            # the thread stands at the slots
            assert len(gated.gates) == 2
            gated.open()
            for t in (a, b, c):
                t.result(timeout=10.0)
    finally:
        jax.profiler.stop_trace()
    return _spans_by_line(trace_dir), eng, first


@pytest.fixture(params=["serve_batch", "pipelined"])
def either(request):
    """(spans, engine) of the synchronous ``serve_batch`` run and of the
    started engine's: the same rules hold for both."""
    if request.param == "serve_batch":
        spans, eng, _ = request.getfixturevalue("traced")
        return spans, eng
    spans, eng, _ = request.getfixturevalue("pipelined")
    return [s[:4] for s in spans], eng


def _by_seq(spans):
    """{seq: {name: (start, end)}} of the spans a batch is made of."""
    out = {}
    for name, start, dur, stats in spans:
        if name in OF_A_BATCH:
            own = out.setdefault(stats["seq"], {})
            assert name not in own, (name, stats["seq"])
            own[name] = (start, start + dur)
    return out


def test_every_span_of_a_batch_carries_its_seq(either):
    spans, _ = either
    batches = _by_seq(spans)
    assert len(batches) == 3
    for seq, own in batches.items():
        assert set(own) == set(OF_A_BATCH), seq
    # and a span with no seq belongs to no batch
    assert all("seq" in s[3] for s in spans if s[0] in OF_A_BATCH)


def test_upload_and_launch_lie_inside_dispatch_and_do_not_overlap(either):
    spans, _ = either
    for seq, own in _by_seq(spans).items():
        d0, d1 = own["serve.batch.dispatch"]
        (u0, u1), (l0, l1) = own[UPLOAD], own[LAUNCH]
        assert d0 <= u0 <= u1 <= l0 <= l1 <= d1, seq
    launches = [s[3] for s in spans if s[0] == LAUNCH]
    assert {(st["program"], st["pinned"]) for st in launches} == {
        ("jit__serve_int8_packed", 0)}      # nobody called warmup()
    uploads = [s[3] for s in spans if s[0] == UPLOAD]
    # the staged array: [bucket, rank + 2] of int32
    assert {st["bytes"] for st in uploads} <= {8 * 18 * 4, 32 * 18 * 4}


def test_upload_says_how_the_staged_batch_reached_the_device(either):
    """Since PR 41 the staged array rides the scoring call as its host
    argument: every upload span says ``how`` = ``call`` beside ``seq``
    and ``bytes``, the batch's record mirrors it as ``upload_how``, and
    ``obs/schema.py`` declares both."""
    import inspect

    from tpu_als.obs import schema

    spans, eng = either
    uploads = [s[3] for s in spans if s[0] == UPLOAD]
    assert len(uploads) == 3
    for stats in uploads:
        assert {"seq", "bytes", "how"} <= set(stats), stats
        assert stats["how"] == "call"
    by_batch = {r["batch"]: r for r in eng.batch_flight.records()}
    for stats in uploads:
        assert by_batch[stats["seq"]]["upload_how"] == stats["how"]
    assert "upload_how = call|put_one|put" in " ".join(
        EVENTS["flight_record"][1].split())
    # (the span's stats are declared in SERVE_DISPATCH_SPAN_KEYS' comment)
    assert "how = call" in " ".join(
        inspect.getsource(schema).replace("#", " ").split())


@pytest.mark.parametrize("name", PHASES)
def test_cpu_us_lies_within_wall_us_within_the_spans_duration(
        traced, pipelined, name):
    """``cpu_us`` is the thread's own CPU time over an interval inside the
    span, ``wall_us`` the wall time of that interval: never more CPU than
    wall (the two clocks are read a call apart), nor more wall than the
    span lasted."""
    own = [s for s in traced[0] + [p[:4] for p in pipelined[0]]
           if s[0] == name]
    assert len(own) == 6
    for _, _, dur, stats in own:
        assert 0 <= stats["cpu_us"] <= stats["wall_us"] + 100, stats
        assert stats["wall_us"] <= dur / 1e3 + 1, stats
    if name == "serve.batch.readback":
        # the gated readback blocked for the test's 20 ms: wall, not CPU
        held = max(own, key=lambda s: s[2])
        assert held[2] > 15e6 and held[3]["cpu_us"] < 5e3


@pytest.mark.parametrize("run", ["serve_batch", "pipelined"])
def test_slot_wait_is_a_span_only_while_both_slots_are_taken(
        traced, pipelined, run):
    if run == "serve_batch":        # its caller's thread takes no slot
        assert not [s for s in traced[0] if s[0] in PIPE_SPAN_KEYS]
        return
    spans, eng, first = pipelined
    waits = [s for s in spans if s[0] == "pipe.slot_wait"]
    assert waits and all(set(PIPE_SPAN_KEYS) >= {s[0]} for s in waits)
    third, = [w for w in waits if w[3]["seq"] == first + 2]
    assert third[2] > 15e6              # the 20 ms the test held batch 1
    rec, = [r for r in eng.batch_flight.records()
            if r["batch"] == first + 2]
    assert rec["handoff_wait"] * 1e9 == pytest.approx(third[2], abs=1e6)
    batches = _by_seq([s[:4] for s in spans])
    engine_line = third[4]
    for _, w0, dur, stats, line in waits:
        assert line == engine_line and stats["seq"] > first + 1
        # both slots: two batches dispatched and not yet completed
        flying = [seq for seq, own in batches.items()
                  if own["serve.batch.dispatch"][1] <= w0
                  < own["serve.batch.complete"][1]]
        assert len(flying) == 2, (stats, flying)
        # it ends when the older of them has completed
        assert batches[min(flying)]["serve.batch.complete"][1] <= \
            w0 + dur + 1e6
        # between two batches: under no span of the engine thread's
        for name, s0, sdur, _, sline in spans:
            if sline == line and name.startswith("serve."):
                assert s0 + sdur <= w0 or w0 + dur <= s0, name


def test_the_batch_record_carries_the_split_and_the_cpu_time(either):
    """``upload`` + ``launch`` within ``dispatch`` on every record; the CPU
    seconds beside each phase's wall seconds on those a profiler watched,
    ``None`` on the others (the warm batches here): the CPU clock is a
    system call and is read only where a trace holds it."""
    _, eng = either
    declared = EVENTS["flight_record"][1]
    warm = eng.batch_flight.records()[0]
    assert set(warm["cpu"].values()) == {None} and warm["upload"] > 0
    records = eng.batch_flight.records()[-3:]
    for rec in records:
        for field in ("upload", "launch", "cpu"):
            assert field in rec and f"{field}" in declared
        spans = rec["spans"]
        assert 0 < rec["upload"] and 0 < rec["launch"]
        assert rec["upload"] + rec["launch"] <= spans["serve.batch.dispatch"]
        assert set(rec["cpu"]) == {p.rsplit(".", 1)[1] for p in PHASES}
        for phase in PHASES:
            cpu = rec["cpu"][phase.rsplit(".", 1)[1]]
            assert 0 <= cpu <= spans[phase] + 1e-4, phase
    assert "upload / launch" in declared and "cpu = {stage, dispatch, " \
        "readback, complete}" in " ".join(declared.split())


def test_no_profiler_no_cpu_clock(monkeypatch):
    """The thread-CPU clock is a system call (7-30 us on the chip's
    sandboxed host, PERF.md section 6, PR 36): a batch that no profiler
    session watches reads it not once, on either thread."""
    def read(*_):
        raise AssertionError("the CPU clock was read with no profiler on")

    eng = _engine()
    _drain(eng, 5)                   # compiled
    monkeypatch.setattr(time, "thread_time_ns", read)
    tickets = _drain(eng, 5)
    with eng:
        tickets.append(eng.submit(3))
        tickets[-1].result(timeout=10.0)
    assert all(t.done() and t.result() is not None for t in tickets)
    for rec in eng.batch_flight.records():
        assert set(rec["cpu"].values()) == {None}
        assert rec["upload"] + rec["launch"] <= rec["spans"][
            "serve.batch.dispatch"]


def test_serve_batch_writes_the_started_engines_spans_but_the_slot_wait(
        traced, pipelined):
    sync = {s[0] for s in traced[0]}
    # (the started engine's queue ran empty; ``traced``'s never did)
    started = {s[0] for s in pipelined[0]} - {"serve.idle"}
    assert started - sync == set(PIPE_SPAN_KEYS)
    assert sync - started == set()


def test_a_warmed_engine_launches_its_pinned_program(tmp_path):
    eng = _engine()
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _drain(eng, 5)
    finally:
        jax.profiler.stop_trace()
    launch, = [s[3] for s in _serve_spans(str(tmp_path)) if s[0] == LAUNCH]
    assert (launch["program"], launch["pinned"]) == (
        "jit__serve_int8_packed", 1)


def test_batch_ring_keeps_the_spans_durations(traced):
    spans, eng, _ = traced
    reg = obs.reset()
    assert eng.batch_flight.dump("test") == len(BATCHES)
    records = [e for e in reg._events if e["type"] == "flight_record"]
    by_seq = {}
    for name, _, dur, stats in spans:
        if name == "serve.batch":
            by_seq[stats["seq"]] = stats
    for rec in records:
        assert set(rec["spans"]) == set(SERVE_BATCH_SPAN_KEYS)
        stats = by_seq[rec["batch"]]
        assert (rec["rows"], rec["bucket"], rec["path"]) == (
            stats["rows"], stats["bucket"], stats["path"])
        assert rec["waiting"] == rec["rows"] and rec["t0"] > 0
    # the ring's clock reads bracket the annotations: each duration is
    # its span's plus the few microseconds the brackets cost
    whole = [s for s in spans if s[0] == "serve.batch"]
    for rec, (_, b0, bdur, _) in zip(records, whole):
        for name in ("serve.batch",) + PHASES:
            span_ns = next(s[2] for s in spans if s[0] == name
                           and b0 <= s[1] < b0 + bdur)
            assert rec["spans"][name] * 1e9 == pytest.approx(
                span_ns, abs=1e6), name
        assert sum(rec["spans"][p] for p in PHASES) == pytest.approx(
            rec["spans"]["serve.batch"], abs=1e-6)
    coalesce = [s for s in spans if s[0] == "serve.batch.coalesce"]
    for rec, (_, _, dur, _) in zip(records, coalesce):
        assert rec["spans"]["serve.batch.coalesce"] * 1e9 == pytest.approx(
            dur, abs=1e6)


def test_coalesce_span_and_record_say_what_closed_the_batch(traced):
    """``closed_by`` / ``head_wait`` ride the coalesce span and the
    batch's record alike (ISSUE 29); with ``max_wait_s`` 0 every head
    has had its wait on arrival, and 32 rows fill the largest bucket."""
    spans, eng, tickets = traced
    coalesce = [s[3] for s in spans if s[0] == "serve.batch.coalesce"]
    records = eng.batch_flight.records()[-len(BATCHES):]
    for stats, rec, batch in zip(coalesce, records, tickets):
        assert stats["closed_by"] == rec["closed_by"] == (
            "full" if len(batch) == 32 else "age")
        assert stats["head_wait"] == pytest.approx(rec["head_wait"])
        # the head's age on arrival is the start of its queue wait
        assert 0 <= rec["head_wait"] <= (batch[0].t_dequeue
                                         - batch[0].t_submit)


def test_request_records_name_the_batch_they_rode(traced):
    _, eng, tickets = traced
    reg = obs.reset()
    # the request ring holds the last 64 requests: all of the last batch
    # (32) and of the one before (20)
    eng.flight.dump("test")
    records = [e for e in reg._events if e["type"] == "flight_record"]
    assert all(set(r["spans"]) == set(SERVE_SPAN_KEYS) for r in records)
    last = eng._batch_seq
    assert [r["batch"] for r in records[-52:]] == [last - 1] * 20 + [last] * 32


def test_ticket_t_done_orders_the_flight_records_latencies(traced):
    _, eng, tickets = traced
    for batch in tickets:
        assert all(t.t_submit <= t.t_dequeue <= t.t_done for t in batch)
        # completed in order: each answer is stamped as it is given
        done = [t.t_done for t in batch]
        assert done == sorted(done)
    reg = obs.reset()
    last = _drain(eng, 20)
    eng.flight.dump("test")
    records = [e for e in reg._events if e["type"] == "flight_record"][-20:]
    for t, rec in zip(last, records):
        assert rec["e2e_seconds"] == pytest.approx(t.t_done - t.t_submit)
    # the last ticket of a batch was submitted later and answered later;
    # its latency counts the completion loop before it, its ``respond``
    # is the part of the latency after the scores were in hand
    assert records[-1]["spans"]["respond"] >= records[0]["spans"]["respond"]
    gap = (last[-1].t_done - last[0].t_done) - (last[-1].t_submit
                                                - last[0].t_submit)
    assert records[-1]["e2e_seconds"] - records[0]["e2e_seconds"] == \
        pytest.approx(gap)
    # what follows the submit stamp adds up to the latency, but for the
    # batch's staging between the dequeue and the dispatch
    for rec in records:
        after = sum(rec["spans"][k] for k in ("queue_wait", "score",
                                              "respond"))
        assert after <= rec["e2e_seconds"] + 1e-9


def test_a_failed_ticket_is_stamped_too():
    t = Ticket(0, None, None)
    assert t.t_done is None
    t.fail(RuntimeError("x"))
    assert t.t_done >= t.t_submit and t.done()


@pytest.mark.parametrize("rows", [1, 20])
def test_one_registry_event_per_batch_whatever_its_size(rows):
    """The engine thread writes its phases as annotations and one ring
    append: the only event a batch leaves in the registry's bounded list
    is the ``queue_depth`` gauge's, as before, and the per-ticket
    latencies reach their histograms whole."""
    eng = _engine()
    _drain(eng, rows)                # compiled
    reg = obs.reset()
    _drain(eng, rows)
    assert [(e["type"], e.get("name")) for e in reg._events] == [
        ("metric", "serving.queue_depth")]
    hists = reg.snapshot()["histograms"]
    assert hists["serving.enqueue_seconds"]["count"] == rows
    assert hists["serving.e2e_seconds"]["count"] == rows
    assert hists["serving.batch_rows"]["count"] == 1
    assert hists['serving.score_seconds{path="int8"}']["count"] == 1
    assert len(eng.batch_flight) == 2 and len(eng.flight) == 2 * rows


def test_an_idle_engine_thread_says_so(traced):
    """``serve.idle`` is the wait on an EMPTY queue (its seconds reach the
    next batch's record); a timeout leaves no batch."""
    del traced                       # after the module's traced run
    b = MicroBatcher(buckets=(8,), max_wait_s=0.0)
    assert b.next_batch(timeout=0.02) is None
    b.submit(0)
    assert len(b.next_batch(timeout=1.0)) == 1
    idle_s, waiting, coalesce_s = b.last_wait
    assert idle_s >= 0.02 and waiting == 1 and 0 <= coalesce_s < 0.01
    b.submit(1)
    b.next_batch(timeout=1.0)
    assert b.last_wait[0] == 0.0     # counted once


def test_histogram_many_is_histogram_in_one_call():
    values = [1e-5, 3e-4, 3e-4, 0.02, 7.0]
    one, many = obs.MetricsRegistry(), obs.MetricsRegistry()
    for v in values:
        one.histogram("serving.e2e_seconds", v, tenant="a")
    many.histogram_many("serving.e2e_seconds", values, tenant="a")
    assert many.prometheus_text() == one.prometheus_text()
    assert many.snapshot() == one.snapshot()
    with pytest.raises(KeyError):
        many.histogram_many("serving.nonsense", values)
    with pytest.raises(ValueError):
        many.histogram_many("serving.e2e_seconds", values, shard=1)


def test_obs_span_writes_t0_and_an_annotation_of_its_name(tmp_path):
    reg = obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    before = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("train.fit"):
            with obs.span("train.iteration", iteration=3):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    inner, outer = [e for e in reg._events if e["type"] == "span"]
    assert (inner["name"], inner["path"]) == ("train.iteration",
                                              "train.fit/train.iteration")
    assert before <= outer["t0"] <= inner["t0"] <= time.perf_counter()
    assert inner["t0"] + inner["seconds"] <= outer["t0"] + outer[
        "seconds"] + 1e-5
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = {ev.name: ev.duration_ns
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name in ("train.fit", "train.iteration")}
    assert set(found) == {"train.fit", "train.iteration"}
    assert found["train.iteration"] == pytest.approx(
        inner["seconds"] * 1e9, abs=200e3)


def test_serve_score_trace_span_names_its_batch():
    obs.reset()
    tracing.reset_trace_ids(seed=0)
    eng = _engine()
    with tracing.traced():
        _drain(eng, 3)
    reg = obs.default_registry()
    scored = [e for e in reg._events if e["type"] == "trace_span"
              and e["name"] == "serve.score"]
    assert [e["batch"] for e in scored] == [eng._batch_seq] * 3


def test_vocab_pins_the_profiler_span_names(tmp_path):
    from tpu_als.analysis import vocab

    assert vocab.check_trace_vocabulary() == []
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from jax.profiler import TraceAnnotation\n"
        'with TraceAnnotation("serve.batch.stage"):\n'
        '    with TraceAnnotation("serve.bogus", rows=3):\n'
        "        pass\n")
    msgs = [m for _, m in vocab.check_file(str(bad))]
    assert len(msgs) == 1 and "SERVE_BATCH_SPAN_KEYS" in msgs[0]
    assert "'serve.bogus'" in msgs[0]
