"""A serving start tiled by phases (``tpu_als/obs/phases.py``): for the
start of the deployment that folds users AND items under histories that
grow, every ``start.*`` phase's children sum to it within the unsplit
share and every name stands in ``obs/schema.py``; the record carries what
it says (seconds, CPU, bytes placed, the ledger's difference) as the
``span`` event and as exact sums by path; ``device.placed_bytes`` equals
the tables' bytes; a phase is a ``TraceAnnotation`` and NO named scope — a
pinned program's text is the same byte for byte with the phases open and
closed; the static vocabulary check knows the names."""

from __future__ import annotations

import glob
import os
import re

import jax
import numpy as np
import pytest

from tests.test_serving_dispatch import (
    K, N_ITEMS, N_USERS, PIN_BUCKETS, RANK, warmed)
from tpu_als import ALSModel, FoldInServer, IdMap, obs
from tpu_als.obs import schema
from tpu_als.obs.phases import phase, placed_bytes
from tpu_als.serving.engine import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORD = ("cpu_seconds", "placed_bytes", "device_bytes_in_use", "programs",
          "cache_hits", "cache_misses", "trace_s", "lower_s", "compile_s")


def start_path(event):
    return "/".join(p for p in event["path"].split("/")
                    if p.startswith("start."))


@pytest.fixture(scope="module")
def started():
    """``(span events, start.seconds by path, start.placed_bytes by path)``
    of one whole start: publish with histories, the fold-in server, both
    sides prewarmed, ``LiveUpdater.start`` with ``fold_items``.

    JAX's in-memory caches are emptied first (as
    ``tests.test_serving_pins.warm_start`` does): the worker that runs
    this file may have warmed an engine of the same kind for another file
    (``tests/test_serving_dispatch.py``, ``test_serving_pins.py``), and a
    lowering those caches answer reaches neither the backend's compile
    call nor the ledger — ``start.pin`` then closes with ``programs`` 0 and
    ``lower_s`` 0, and the milliseconds it is left with fall under the
    tiling's absolute slack."""
    jax.clear_caches()
    obs.reset()
    warmed("segment_grown")
    spans = [e for e in obs.default_registry()._events
             if e["type"] == "span" and e["name"].startswith("start.")]
    by_path = [{labels["path"]: v for labels, v in obs.counter_series(name)}
               for name in ("start.seconds", "start.placed_bytes")]
    return spans, *by_path


def test_every_phase_is_declared_and_carries_its_record(started):
    spans, _, _ = started
    names = {e["name"] for e in spans}
    assert names <= set(schema.START_PHASES)
    assert {"start.publish", "start.publish.users",
            "start.publish.histories.check", "start.publish.histories.place",
            "start.publish.catalog", "start.publish.index.place",
            "start.publish.index.quantize", "start.foldin_server.reserve",
            "start.foldin_server.place", "start.foldin_server.history",
            "start.prewarm.reserve", "start.prewarm.place",
            "start.prewarm.programs",
            "start.prewarm.writes", "start.updater", "start.warmup_publish",
            "start.warmup_live.reserve", "start.warmup_histories.plan",
            "start.warmup_histories.place", "start.pin",
            "start.first_run"} <= names
    for e in spans:
        assert set(RECORD) <= set(e), e["name"]
        assert e["seconds"] >= 0 and e["cpu_seconds"] >= 0
    # the ledger's difference: the pins were lowered and compiled here
    pins = [e for e in spans if e["name"] == "start.pin"]
    assert len(pins) == obs.counter_value("serving.pins", source="compiled")
    assert len(pins) >= 3 * len(PIN_BUCKETS)    # the pads + the fallback
    assert all(e["programs"] == 1 and e["lower_s"] > 0 for e in pins)
    sides = {e.get("side") for e in spans if e["name"] == "start.prewarm"}
    assert sides == {"user", "item", "both"}


def test_children_tile_their_parent(started):
    spans, seconds, _ = started
    # the counters are the events' exact sums
    summed = {}
    for e in spans:
        summed[start_path(e)] = summed.get(start_path(e), 0) + e["seconds"]
    assert seconds.keys() == summed.keys()
    for path, total in summed.items():
        assert seconds[path] == pytest.approx(total, abs=1e-5)
    parents = {p for p in seconds if any(q.startswith(p + "/")
                                         for q in seconds)}
    assert {"start.publish", "start.foldin_server", "start.prewarm",
            "start.updater"} == {p for p in seconds if "/" not in p}
    for parent in parents:
        children = sum(v for p, v in seconds.items()
                       if p.startswith(parent + "/")
                       and "/" not in p[len(parent) + 1:])
        assert children <= seconds[parent] + 1e-3, parent
        assert seconds[parent] - children <= 0.1 * seconds[parent] + 0.02, \
            parent
    top = sum(v for p, v in seconds.items() if "/" not in p)
    held = sum(v for p, v in seconds.items() if p not in parents)
    assert 0 <= top - held <= 0.1 * top


def test_placed_bytes_are_the_tables_bytes(started):
    _, _, placed = started
    U, V = 4 * N_USERS * RANK, 4 * N_ITEMS * RANK
    assert placed["start.publish/start.publish.users"] == U
    assert placed["start.publish/start.publish.catalog"] == V + N_ITEMS
    assert placed["start.publish/start.publish.index/"
                  "start.publish.index.place"] == V
    assert placed["start.foldin_server/start.foldin_server.place"] == V
    assert placed["start.prewarm/start.prewarm.place"] == U   # the item side
    assert placed["start.prewarm/start.prewarm.programs"] == 0
    # a parent holds its children's
    assert placed["start.publish"] == sum(
        v for p, v in placed.items()
        if p.startswith("start.publish/") and p.count("/") == 1)
    assert placed["start.foldin_server"] == V
    by_table = {labels["table"]: v for labels, v
                in obs.counter_series("device.placed_bytes")}
    assert by_table["users"] == U and by_table["index"] == V
    assert by_table["catalog"] == V + N_ITEMS
    assert by_table["fold_fixed"] == U + V


@pytest.mark.parametrize("histories", [False, True])
def test_an_engines_placed_bytes_with_and_without_histories(histories):
    obs.reset()
    rng = np.random.default_rng(3)
    U = rng.standard_normal((30, 8)).astype(np.float32)
    V = rng.standard_normal((200, 8)).astype(np.float32)
    indptr = np.arange(31, dtype=np.int64) * 2
    indices = np.tile(np.array([3, 7], np.int32), 30)
    engine = ServingEngine(k=5, buckets=(8,))
    engine.publish(U, V, user_seen=(indptr, indices) if histories else None)
    by_table = {labels["table"]: v for labels, v
                in obs.counter_series("device.placed_bytes")}
    assert by_table.pop("users") == U.nbytes
    assert by_table.pop("catalog") == V.nbytes + 200
    assert by_table.pop("index") == V.nbytes
    if histories:
        seen = engine._model.seen
        assert by_table.pop("histories") == (seen.runs.nbytes
                                             + seen.indices.nbytes)
    assert not by_table
    assert placed_bytes() == sum(
        v for labels, v in obs.counter_series("start.placed_bytes")
        if labels["path"] == "start.publish")


def test_a_fold_in_server_counts_both_fixed_sides():
    obs.reset()
    rng = np.random.default_rng(4)
    U = rng.standard_normal((30, 8)).astype(np.float32)
    V = rng.standard_normal((200, 8)).astype(np.float32)
    model = ALSModel(
        8, IdMap(ids=np.arange(30)), IdMap(ids=np.arange(200)), U, V,
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": 0.1,
         "implicitPrefs": True, "alpha": 1.0, "nonnegative": False})
    server = FoldInServer(model)
    assert placed_bytes() == V.nbytes
    server.prewarm(rows=(8,), widths=(8,), sides=("user", "item"))
    assert placed_bytes() == V.nbytes + U.nbytes
    assert obs.counter_value("device.placed_bytes",
                             table="fold_fixed") == V.nbytes + U.nbytes
    names = {e["name"] for e in obs.default_registry()._events
             if e["type"] == "span"}
    assert "start.foldin_server.yty" in names           # implicit


def _pinned_texts(engine, *arounds):
    """The exact program's compiled text, lowered anew under each of
    ``arounds`` (from ONE line: the text holds its callers' line
    numbers)."""
    m = engine._model
    fn, args, statics = engine._exact_call(m, engine._proto(8, m.rank))
    texts = []
    for around in arounds:
        jax.clear_caches()
        with around:
            texts.append(fn.lower(*args, **statics).compile().as_text())
    return texts


def test_a_phase_is_no_named_scope():
    """The pinned program's text, byte for byte, with the start phases
    open and closed, and no phase's name in it.  (JAX 0.9.0 starts a
    ``jit``'s name stack afresh, so an outer ``obs.span`` reads the same
    here; a phase opens no scope whatever a later JAX does with one.)"""
    import contextlib

    rng = np.random.default_rng(5)
    engine = ServingEngine(k=K, buckets=(8,))
    engine.publish(rng.standard_normal((20, RANK)).astype(np.float32),
                   rng.standard_normal((300, RANK)).astype(np.float32))
    both = contextlib.ExitStack()
    both.enter_context(phase("start.warmup"))
    both.enter_context(phase("start.pin"))
    closed, opened = _pinned_texts(engine, contextlib.nullcontext(), both)
    assert opened == closed and "start." not in closed
    spans = [e["name"] for e in obs.default_registry()._events
             if e["type"] == "span"][-2:]
    assert spans == ["start.pin", "start.warmup"]       # they were open
    # and directly: obs.span opens a scope, a phase opens none
    from jax._src.source_info_util import current_name_stack

    with phase("start.warmup"):
        assert str(current_name_stack()) == ""
    with obs.span("start.warmup"):
        assert "start.warmup" in str(current_name_stack())


def test_a_phase_is_on_the_profilers_timeline(tmp_path):
    from benchmark import program_spans, trace as tr

    jax.profiler.start_trace(str(tmp_path))
    try:
        with phase("start.warmup"):
            with phase("start.pin"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = [s[0] for s in program_spans.read(
        tr.find_xplane(str(tmp_path)), prefix="start.")]
    assert sorted(names) == ["start.pin", "start.warmup"]


def test_an_undeclared_phase_is_refused():
    with pytest.raises(KeyError, match="START_PHASES"):
        with phase("start.nothing_declared"):
            pass


def test_the_names_stand_once_in_the_schema_and_are_all_opened(tmp_path):
    assert len(set(schema.START_PHASES)) == len(schema.START_PHASES)
    assert all(p.startswith("start.") for p in schema.START_PHASES)
    opened = set()
    for sub in ("serving", "stream", "live", "core"):
        for path in glob.glob(os.path.join(ROOT, "tpu_als", sub, "*.py")):
            with open(path, encoding="utf-8") as f:
                opened |= set(re.findall(r'\bphase\(\s*"([^"]+)"', f.read()))
    assert opened == set(schema.START_PHASES)
    # the static check of the vocabulary knows them
    from tpu_als.analysis import vocab

    assert not vocab.check_file(os.path.join(
        ROOT, "tpu_als", "serving", "engine.py"))
    bad = tmp_path / "undeclared.py"
    bad.write_text('with phase("start.not_in_the_schema"):\n    pass\n')
    found = vocab.check_file(str(bad))
    assert len(found) == 1 and "start.not_in_the_schema" in found[0][1]
