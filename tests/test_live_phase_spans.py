"""One ``live.batch`` tiled by phase, and the serving side's wait for the
table lock on its stage span (ISSUE 54), at a small size.

(i) under a profiler every ``live.batch`` of the four updater kinds holds
each phase once a side folded, the children of a span are disjoint and lie
inside it, each phase carries ``cpu_us`` and ``wall_us``, and what lies
under no phase is under a tenth of the batches; (ii) ``serve.batch.stage``
carries ``lock_wait_us``, the batch record's ``lock_wait``; (iii) with the
table lock held for 5 ms it reads 5,000 at least; (iv) the names stand once,
in ``obs/schema.py``, and the code opens each of them at one site."""

from __future__ import annotations

import glob
import os
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from tests import test_live_placements as stacks
from tpu_als import obs
from tpu_als.obs import schema
from tpu_als.serving.engine import cpu_mark, stamp_cpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark import live_phase_spans, program_spans  # noqa: E402

PHASES = schema.LIVE_PHASE_SPAN_KEYS
FOLD = tuple(p for p in PHASES if p.startswith("live.batch.foldin."))
ROUNDS, BATCHES = 3, 4


def traced(tmp_path, work):
    """The ``live.`` and ``serve.`` spans of ``work()`` under a profiler."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return program_spans.read(path, prefix=("live.", "serve."))


@pytest.fixture(scope="module", params=stacks.KINDS)
def batches(request, tmp_path_factory):
    """``(kind, [the tree of each round's spans])``: ``ROUNDS`` times
    ``BATCHES`` batches of 56 events (the six seeded ones of the
    placements' test as one: the phases' work grows with a batch, the
    Python between them does not) through an updater's own ``_process``
    on this thread, each inside the ``live.batch`` span
    ``LiveUpdater._run`` opens."""
    kind = request.param
    obs.reset()
    model, eng, srv, upd, n_users, n_items = stacks.build(kind)
    rounds = []
    for r in range(ROUNDS):
        todo = [sum(stacks.seeded_batches(
            54 + BATCHES * r + b, len(model._user_map) + 100 * b,
            len(model._item_map) + 100 * b, "items" in kind), [])
            for b in range(BATCHES)]

        def work():
            for seq, batch in enumerate(todo):
                with TraceAnnotation("live.batch", seq=seq) as whole:
                    mark = cpu_mark()
                    upd._process([(u, i, s, time.perf_counter(), None)
                                  for u, i, s in batch], whole)
                    stamp_cpu(whole, mark)

        spans = traced(tmp_path_factory.mktemp(f"{kind}-{r}"), work)
        rounds.append(live_phase_spans.tree(spans))
    return kind, rounds


def children(nodes, parent):
    return [n for n in nodes if n[4] == parent]


def test_every_batch_holds_each_phase_once_a_side_folded(batches):
    kind, rounds = batches
    sides = ("users", "items") if "items" in kind else ("users",)
    for nodes in rounds:
        roots = [i for i, n in enumerate(nodes) if n[0] == "live.batch"]
        assert len(roots) == BATCHES
        for root in roots:
            own = []            # the batch's spans, whatever their depth
            for i, n in enumerate(nodes):
                up = i
                while nodes[up][4] is not None:
                    up = nodes[up][4]
                if up == root and i != root:
                    own.append(n)
            names = [n[0] for n in own]
            for name in set(PHASES) - set(FOLD) - {
                    "live.batch.publish.ride"}:
                assert names.count(name) == 1, (kind, name)
            # the publish's one array goes up once: with the catalog's
            # segment, or by itself
            assert names.count("live.batch.publish.ride") == 1
            for side in sides:
                fold = [n[0] for n in own if n[3].get("side") == side
                        and n[0] in FOLD]
                # a side with nothing to fold stops early: every item
                # left to the refit in its first phase, no rating usable
                # in its third
                assert sorted(fold) in map(sorted, (FOLD, FOLD[:3],
                                                    FOLD[:1])), (kind, fold)
            assert any(n[3].get("side") == "users"
                       and n[0] == "live.batch.foldin.call" for n in own)


def test_children_are_disjoint_and_inside_their_parent(batches):
    _, rounds = batches
    for nodes in rounds:
        for i, (name, start, end, _, parent) in enumerate(nodes):
            if parent is not None:
                assert nodes[parent][1] <= start
                assert end <= nodes[parent][2], (name, nodes[parent][0])
            kids = sorted(children(nodes, i), key=lambda n: n[1])
            for a, b in zip(kids, kids[1:]):
                assert a[2] <= b[1], (a[0], b[0])
        by_name = {n[0]: nodes[n[4]][0] for n in nodes if n[4] is not None}
        assert by_name["live.batch.foldin.call"] == \
            "live.batch.foldin.readback"
        assert by_name["live.batch.publish.lock_wait"] == \
            by_name["live.batch.publish.writes"] == "live.batch.publish"
        assert by_name["live.batch.record"] == "live.batch"


def test_each_phase_carries_its_cpu_time_beside_its_wall_time(batches):
    _, rounds = batches
    for nodes in rounds:
        for name, start, end, stats, _ in nodes:
            if name not in PHASES:
                continue
            assert 0 <= stats["cpu_us"] <= stats["wall_us"] + 100, name
            assert stats["wall_us"] <= (end - start) / 1e3 + 1, name
        calls = [n[3] for n in nodes if n[0] == "live.batch.foldin.call"]
        assert all(c["calls"] == 1 and c["rows"] in (8, 64)
                   and c["width"] >= 8 for c in calls)
        writes = [n[3] for n in nodes
                  if n[0] == "live.batch.publish.writes"]
        assert all(w["programs"] >= 1 for w in writes)


def test_under_a_tenth_of_a_batch_lies_under_no_phase(batches):
    """The best of the rounds: a stop of the process between two phases
    (six test workers share the machine) is no property of the program."""
    kind, rounds = batches
    shares = []
    for nodes in rounds:
        found = live_phase_spans.phases(
            [(n[0], n[1], n[2] - n[1], n[3]) for n in nodes])
        assert found["batches"] == BATCHES
        walls = found["wall_ns"]
        # phases, unsplit and the instrument's own stamps: all of it
        assert sum(map(sum, walls.values())) == found["batch_ns"]
        assert min(walls["stamps"]) >= 0
        shares.append(live_phase_spans.unsplit(
            {name: sum(ns) for name, ns in walls.items()})
            / found["batch_ns"])
    assert min(shares) < 0.10, (kind, shares)


def serve_some(eng, n_users, rng, n=3):
    """``n`` batches of two requests by id, through ``serve_batch`` on
    this thread; their tickets."""
    tickets = []
    for _ in range(n):
        tickets += [eng.submit(int(u)) for u in rng.integers(0, n_users, 2)]
        eng.serve_batch(eng.batcher.next_batch(timeout=0, coalesce=False))
    return tickets


@pytest.mark.parametrize("kind", ["live", "live-unseen"])
def test_the_stage_span_carries_the_records_lock_wait(kind, tmp_path):
    """With histories (``excluded`` on the same span) and without."""
    obs.reset()
    model, eng, srv, upd, n_users, _ = stacks.build(kind)
    rng = np.random.default_rng(54)
    spans = traced(tmp_path, lambda: [
        t.result(timeout=0) for t in serve_some(eng, n_users, rng)])
    stages = {s[3]["seq"]: s[3] for s in spans
              if s[0] == "serve.batch.stage"}
    records = {r["batch"]: r for r in eng.batch_flight.records()
               if r["batch"] in stages}
    assert len(stages) == 3 and sorted(records) == sorted(stages)
    for seq, stats in stages.items():
        assert abs(stats["lock_wait_us"]
                   - 1e6 * records[seq]["lock_wait"]) <= 1
        assert ("excluded" in stats) == (kind == "live-unseen")


def test_a_lock_held_for_5_ms_reads_5000_on_the_stage_span(tmp_path):
    obs.reset()
    model, eng, srv, upd, n_users, _ = stacks.build("live")
    rng = np.random.default_rng(55)
    serve_some(eng, n_users, rng, n=1)      # nothing left to load

    def work():
        eng.submit(3)
        batch = eng.batcher.next_batch(timeout=0, coalesce=False)
        served = threading.Thread(target=eng.serve_batch, args=(batch,))
        with eng._table_lock:               # as a publish's writes hold it
            served.start()
            # the thread is at the lock within a millisecond or two, and
            # waits out what is left of these
            time.sleep(0.03)
        served.join(10.0)
        assert not served.is_alive()

    spans = traced(tmp_path, work)
    stage, = [s for s in spans if s[0] == "serve.batch.stage"]
    assert stage[3]["lock_wait_us"] >= 5000
    assert stage[2] >= 5_000_000            # and the span holds the wait
    record = eng.batch_flight.records()[-1]
    assert record["lock_wait"] >= 0.005


def test_the_names_stand_once_in_the_schema_and_once_in_the_code():
    assert len(set(PHASES)) == len(PHASES)
    others = (schema.LIVE_BATCH_SPAN_KEYS + schema.LIVE_ITEM_SPAN_KEYS
              + schema.LIVE_HISTORY_SPAN_KEYS + schema.LIVE_FOLDIN_SPAN_KEYS
              + schema.SERVE_BATCH_SPAN_KEYS)
    assert not set(PHASES) & set(others)
    assert all(p.startswith("live.batch.") for p in PHASES)
    opened = []
    for sub in ("live", "stream", "serving"):
        for path in glob.glob(os.path.join(ROOT, "tpu_als", sub, "*.py")):
            with open(path, encoding="utf-8") as f:
                opened += re.findall(r'\bStamped\(\s*"([^"]+)"', f.read())
    # (a landing's steps are stamped too: their own tuple, ISSUE 59)
    landing = [name for name in opened if name.startswith("live.landing")]
    assert set(landing) <= set(schema.LIVE_LANDING_SPAN_KEYS)
    assert sorted(n for n in opened if n not in landing) == sorted(PHASES)
    # and the static check of the vocabulary knows the tuple
    from tpu_als.analysis import vocab

    assert ("LIVE_PHASE_SPAN_KEYS",
            ("live", "stream", "serving")) in vocab.PROFILER_SPAN_TUPLES
    assert not [e for e in vocab.check_trace_vocabulary()
                if "LIVE_PHASE_SPAN_KEYS" in e]
