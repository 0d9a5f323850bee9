"""A warm start LOADS its pinned programs (PR 51): where JAX's persistent
compilation cache has a directory, ``ServingEngine``'s warm-ups keep
each pinned executable, serialized, under ``<directory>/tpu_als_pins``
(``serving.pins``), and a later warm-up whose key finds its file loads
it and neither traces nor lowers.

For an engine of each benchmark cell's kind (``tests.test_serving_
dispatch.KINDS``: the plain int8 and exact pair, the one with a delta
segment, the ones that exclude histories as published and grown, the
mesh's sharded pair, the catalog that moves under histories that grow),
warmed as its cell warms it: a second engine in the same process and a
second PROCESS load every pin, lower none of the scoring programs, hold
executables whose text is the compiled ones' and answer bit for bit as
the engine that compiled them.  An edited key part is a miss and
compiles; a file that does not load is ``unreadable``, compiles and is
written over; with no cache directory nothing is read or written; a
shape-changing publish after a loaded pin still drops to ``jit``.

Run as ``python -m tests.test_serving_pins <directory>`` this file is the
second process: it warms an engine of every kind with its store under
``<directory>`` and prints what it found, one JSON line.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from tests.test_serving_dispatch import (
    K, KINDS, N_ITEMS, N_USERS, RANK, packed, pins_of, requests, serve_now,
    warmed)
from tpu_als import obs
from tpu_als.serving import pins
from tpu_als.serving.engine import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_FLAGS = {"jax_enable_compilation_cache": True,
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}


@contextlib.contextmanager
def jax_flags(**new):
    old = {name: getattr(jax.config, name) for name in new}
    for name, value in new.items():
        jax.config.update(name, value)
    try:
        yield
    finally:
        for name, value in old.items():
            jax.config.update(name, value)


@contextlib.contextmanager
def compile_cache(path, **flags):
    """JAX's persistent compilation cache as a deployment turns it on
    (``utils.platform.enable_persistent_compile_cache``), in ``path``;
    afterwards as the suite runs (``tests/conftest.py``: off)."""
    with jax_flags(jax_compilation_cache_dir=path and str(path),
                   **{**_CACHE_FLAGS, **flags}):
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            compilation_cache.reset_cache()


class Watch:
    """While entered: the ``serving_pin`` events emitted, and how many of
    the scoring programs (modules named ``...serve_...``: the four the
    engine pins) JAX lowered, from its own monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.events, self.lowered, self._on = [], [], False
        jax.monitoring.register_event_duration_secs_listener(self._lowering)

    def _lowering(self, event, duration, fun_name="", **_):
        if self._on and event == self.EVENT and "serve_" in fun_name:
            self.lowered.append(fun_name)

    def __enter__(self):
        self._emit, self._on = obs.emit, True

        def emit(etype, **fields):
            if etype == "serving_pin":
                self.events.append(fields)
            return self._emit(etype, **fields)

        obs.emit = emit
        return self

    def __exit__(self, *exc):
        obs.emit, self._on = self._emit, False

    @property
    def sources(self):
        return [e["source"] for e in self.events]


def text_digests(eng):
    return {str(key): hashlib.sha256(c.as_text().encode()).hexdigest()
            for key, c in eng._pinned.items()}


def answers(eng, users):
    """One batch by id (of users with histories where the kind has them)
    and by vector, as the rows of the packed response."""
    ids = [users[n] for n in sorted(users)][:4] or [0, 7, N_USERS - 1]
    vector = np.random.default_rng(51).standard_normal(RANK)
    return packed(serve_now(eng, ids + [vector.astype(np.float32)]))


def warm_start(kind, store):
    """An engine of ``kind`` warmed with its pins' store under
    ``store``, and what the warm-up did: ``(engine, users, watch)``.
    JAX's in-memory caches are emptied first: a lowering they would
    answer emits no event, and a count of none would say nothing."""
    jax.clear_caches()
    with compile_cache(store), Watch() as watch:
        eng, users = warmed(kind)
    return eng, users, watch


# -- a second engine in the same process --------------------------------------

@pytest.fixture(scope="module", params=list(KINDS))
def pair(request, tmp_path_factory):
    """Two engines of one kind over one store: the first compiles its
    pins into it, the second finds them there."""
    kind = request.param
    store = tmp_path_factory.mktemp(f"pins_{kind}")
    reg = obs.reset()
    try:
        first, users, cold = warm_start(kind, store)
        second, _, warm = warm_start(kind, store)
        counted = {source: reg.counter_value("serving.pins", source=source)
                   for source in ("loaded", "compiled", "unreadable")}
    finally:
        obs.reset()
    return dict(kind=kind, store=store, first=first, second=second,
                users=users, cold=cold, warm=warm, counted=counted)


def test_a_second_engine_loads_every_pin(pair):
    cold, warm = pair["cold"], pair["warm"]
    # (the segment's kind pins twice: ``warmup()``, then ``warmup_live``
    # at the grown shapes)
    assert len(cold.events) >= len(pins_of(pair["kind"])) > 0
    assert set(cold.sources) == {"compiled"}
    assert warm.sources == ["loaded"] * len(cold.events)
    assert pair["counted"] == {"loaded": len(warm.events),
                               "compiled": len(cold.events),
                               "unreadable": 0}
    assert set(pair["second"]._pinned) == set(pair["first"]._pinned)
    for was, now in zip(cold.events, warm.events):
        assert (was["bucket"], was["path"], was["pad"]) == (
            now["bucket"], now["path"], now["pad"])
        assert now["bytes"] == was["bytes"] > 0 and now["seconds"] > 0


def test_a_loaded_warm_up_lowers_no_scoring_program(pair):
    assert len(pair["cold"].lowered) == len(pair["cold"].events)
    assert pair["warm"].lowered == []


def test_the_store_holds_one_whole_file_a_pin(pair):
    """Nothing but the keys' files under the compile cache's directory:
    no temporary file is left behind."""
    names = os.listdir(os.path.join(pair["store"], pins.STORE))
    assert len(names) == len(pair["cold"].events)
    assert all(len(n) == 64 and int(n, 16) >= 0 for n in names)


def test_a_loaded_pin_is_the_compiled_executable(pair):
    """The same program, and the same answers bit for bit, through the
    engines' own dispatch — which leaves every loaded pin in place."""
    first, second = pair["first"], pair["second"]
    assert text_digests(second) == text_digests(first)
    pinned = set(second._pinned)
    got, want = answers(second, pair["users"]), answers(first, pair["users"])
    assert got.shape == (len(got), 2 * K) and np.array_equal(got, want)
    assert set(second._pinned) == pinned


# -- a second process ----------------------------------------------------------

def report(root):
    """What this process's warm-ups did, by kind (``__main__``)."""
    out = {}
    for kind in KINDS:
        eng, users, watch = warm_start(kind, os.path.join(root, kind))
        out[kind] = dict(sources=watch.sources, lowered=watch.lowered,
                         text=text_digests(eng),
                         answers=answers(eng, users).tolist())
    return out


@pytest.fixture(scope="module")
def processes(tmp_path_factory):
    """Two processes, one after the other, over one directory."""
    root = str(tmp_path_factory.mktemp("pins_processes"))
    runs = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-m", "tests.test_serving_pins", root],
            cwd=REPO, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        runs.append(json.loads(p.stdout.splitlines()[-1]))
    return runs


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_second_process_loads_every_pin(processes, kind):
    cold, warm = (run[kind] for run in processes)
    assert set(cold["sources"]) == {"compiled"}
    assert len(cold["lowered"]) == len(cold["sources"])
    assert warm["sources"] == ["loaded"] * len(cold["sources"])
    assert warm["lowered"] == []
    assert warm["text"] == cold["text"]
    assert warm["answers"] == cold["answers"]


def test_the_processes_answer_as_this_one(processes, pair):
    assert processes[1][pair["kind"]]["answers"] == answers(
        pair["first"], pair["users"]).tolist()


# -- the key -------------------------------------------------------------------

def small(k=K, items=N_ITEMS):
    """An engine with two pins, published and not warmed."""
    rng = np.random.default_rng(51)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    eng = ServingEngine(k=k, buckets=(8,), shortlist_k=32)
    eng.publish(U, V[:items])
    return eng


def warm_up(eng):
    """``eng.warmup()``: where its two pins came from."""
    with Watch() as watch:
        eng.warmup()
    return watch.sources


@pytest.mark.parametrize("part", ["static", "shape", "source", "flag", "env"])
def test_an_edited_key_part_is_a_miss_and_compiles(tmp_path, monkeypatch,
                                                   part):
    with compile_cache(tmp_path):
        assert warm_up(small()) == ["compiled"] * 2
        assert warm_up(small()) == ["loaded"] * 2
        with contextlib.ExitStack() as edits:
            eng = small(**{"static": dict(k=K + 1),
                           "shape": dict(items=N_ITEMS - 128)}.get(part, {}))
            if part == "source":
                edits.enter_context(monkeypatch.context()).setattr(
                    pins, "source_digest", lambda: "an edited tree's")
            if part == "flag":
                edits.enter_context(jax_flags(
                    jax_default_matmul_precision="highest"))
            if part == "env":
                edits.enter_context(monkeypatch.context()).setenv(
                    "LIBTPU_INIT_ARGS", "--xla_some_flag=1")
            assert warm_up(eng) == ["compiled"] * 2
        # the files of the tree as it stands were not touched
        assert warm_up(small()) == ["loaded"] * 2


def _toy(a, b, *, n):
    return a * n + b[0]


@pytest.mark.parametrize("edit", [
    "none", "dtype", "weak_type", "shape", "tree", "sharding", "committed",
    "static", "function"])
def test_the_key_reads_what_lower_reads(edit):
    toy = jax.jit(_toy, static_argnames=("n",))
    a, b = jnp.ones((4, 2)), (jnp.ones(2),)
    base = pins.key(toy, (a, b), dict(n=2))
    fn, args, statics = toy, (a, b), dict(n=2)
    if edit == "dtype":
        args = (a.astype(jnp.int32), b)
    elif edit == "weak_type":
        args = (a, (2.0,))
        base = pins.key(toy, (a, (np.float32(2.0),)), statics)
    elif edit == "shape":
        args = (jnp.ones((4, 3)), b)
    elif edit == "tree":
        args = (a, [b[0]])
    elif edit == "sharding":
        args = (jax.device_put(a, jax.devices()[1]), b)
        base = pins.key(toy, (jax.device_put(a, jax.devices()[0]), b),
                        statics)
    elif edit == "committed":
        args = (jax.device_put(a, jax.devices()[0]), b)
    elif edit == "static":
        statics = dict(n=3)
    elif edit == "function":
        fn = pins.built(jax.jit(_toy, static_argnames=("n",)), small, 8)
    assert (pins.key(fn, args, statics) == base) == (edit == "none")


def test_a_built_program_is_named_by_its_builder_and_parameters():
    """The mesh's programs are built anew from their parameters: their
    qualified name says none of them."""
    mesh4, mesh2 = jax.make_mesh((4,), ("x",)), jax.make_mesh((2,), ("x",))
    keys = {pins.key(pins.built(jax.jit(_toy, static_argnames=("n",)),
                                small, *params), (jnp.ones(2), (1,)), {"n": 1})
            for params in [(mesh4, 8), (mesh4, 9), (mesh2, 8), (mesh4, 8)]}
    assert len(keys) == 3


# -- files that do not load ----------------------------------------------------

@pytest.mark.parametrize("damage", ["truncated", "empty", "foreign"])
def test_a_file_that_does_not_load_is_compiled_and_written_over(tmp_path,
                                                                damage):
    store = tmp_path / pins.STORE
    reg = obs.reset()
    try:
        with compile_cache(tmp_path):
            assert warm_up(small()) == ["compiled"] * 2
            whole = {p.name: p.read_bytes() for p in store.iterdir()}
            damaged = {name: {
                "truncated": blob[:len(blob) // 2], "empty": b"",
                "foreign": zlib.compress(pickle.dumps(
                    (b"another runtime's", None, None)))}[damage]
                for name, blob in whole.items()}
            for p in store.iterdir():
                p.write_bytes(damaged[p.name])
            eng = small()
            assert warm_up(eng) == ["unreadable"] * 2
            assert reg.counter_value("serving.pins", source="unreadable") == 2
            # written over, whole (the next start loads them, below): no
            # other file, and none the damaged one
            assert {p.name for p in store.iterdir()} == set(whole)
            assert all(len(p.read_bytes()) > len(damaged[p.name])
                       for p in store.iterdir())
            assert np.array_equal(answers(eng, {}), answers(small(), {}))
            assert warm_up(small()) == ["loaded"] * 2
    finally:
        obs.reset()


# -- no compile cache, no store -------------------------------------------------

@pytest.mark.parametrize("how", ["no_directory", "cache_off"])
def test_without_a_compile_cache_nothing_is_read_or_written(
        tmp_path, monkeypatch, how):
    """Today's path, untouched: ``lower().compile()`` a pin, every
    start."""
    def never(*args, **kw):
        raise AssertionError("the store was touched")

    monkeypatch.setattr(pins, "deserialize_and_load", never)
    monkeypatch.setattr(pins, "_write", never)
    monkeypatch.chdir(tmp_path)
    cache = (compile_cache(None) if how == "no_directory" else
             compile_cache(tmp_path, jax_enable_compilation_cache=False))
    with cache, Watch() as watch:
        assert pins.store_dir() is None
        for _ in range(2):
            small().warmup()
    assert watch.sources == ["compiled"] * 4
    assert [e["bytes"] for e in watch.events] == [0] * 4
    assert os.listdir(tmp_path) == []


def test_the_suite_runs_without_a_store():
    """``tests/conftest.py`` turns the compile cache off: no other test
    of the suite reads or writes a pin."""
    assert pins.store_dir() is None


# -- a loaded pin's life ---------------------------------------------------------

def test_a_shape_changing_publish_after_a_loaded_pin_drops_to_jit(tmp_path):
    with compile_cache(tmp_path):
        assert warm_up(small()) == ["compiled"] * 2
        eng = small()
        assert warm_up(eng) == ["loaded"] * 2
    rng = np.random.default_rng(7)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = (rng.standard_normal((N_ITEMS + 256, RANK)) / 4).astype(np.float32)
    eng.publish(U, V)                   # shapes changed: the pins are stale
    fresh = ServingEngine(k=K, buckets=(8,), shortlist_k=32)
    fresh.publish(U, V)                 # never warmed: jit serves
    payloads = requests(np.random.default_rng(8), 5)
    assert np.array_equal(packed(serve_now(eng, payloads)),
                          packed(serve_now(fresh, payloads)))
    assert (8, "int8") not in eng._pinned and (8, "exact") in eng._pinned
    # and a warm-up pins again, at the new shapes
    with compile_cache(tmp_path):
        assert warm_up(eng) == ["compiled"] * 2
    assert np.array_equal(packed(serve_now(eng, payloads)),
                          packed(serve_now(fresh, payloads)))
    assert (8, "int8") in eng._pinned


if __name__ == "__main__":
    # (the suite's backend, CPU and 8 devices, by ``tests.conftest``,
    # which ``tests.test_serving_dispatch`` imports)
    print(json.dumps(report(sys.argv[1])))
