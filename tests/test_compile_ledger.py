"""The compile ledger (``tpu_als/obs/compiles.py``): every program JAX
traces, lowers, compiles or fetches is named by the program itself —
trace, lower and compile told apart, a nested trace counted once, the
persistent cache's hit, miss and absence told apart; a program that
compiles once an engine is started raises ONE ``warning`` with its name
and ``phase="traffic"``, one inside a start phase is that phase's;
nothing fires where nothing compiles; ``install`` is idempotent."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_serving_pins import compile_cache
from tpu_als import obs
from tpu_als.obs import compiles
from tpu_als.obs.phases import phase
from tpu_als.serving.engine import ServingEngine


@pytest.fixture
def ledger():
    obs.reset()
    return compiles.install()


def events_of(etype, **match):
    return [e for e in obs.default_registry()._events if e["type"] == etype
            and all(e.get(k) == v for k, v in match.items())]


def test_install_registers_its_listeners_once(ledger):
    assert compiles.install() is ledger
    x = jnp.ones(7)         # made before the mark: programs of its own
    mark = ledger.now()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    # one backend-compile call: a second pair of listeners would count two
    assert ledger.since(mark)["programs"] == 1


def test_a_program_is_named_and_its_stages_told_apart(ledger):
    @jax.jit
    def ledger_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def ledger_outer(x):
        return ledger_inner(x).sum()

    x = jnp.ones(64)        # made before the mark: its own programs
    mark = ledger.now()
    counted = {what: obs.counter_value(*what[:1], **dict(what[1:]))
               for what in (
        ("jax.programs", ("stage", "compile"), ("cache", "off"),
         ("when", "before")),
        ("jax.program_seconds", ("stage", "lower"), ("when", "before")))}
    ledger_outer(x).block_until_ready()
    did, programs = ledger.since(mark), ledger.programs()
    assert did["programs"] == 1 and did["cache_hits"] == 0
    outer, inner = programs["ledger_outer"], programs["ledger_inner"]
    assert outer["compiles"] == 1 and inner["compiles"] == 0
    assert outer["trace_s"] > 0 and outer["lower_s"] > 0 \
        and outer["compile_s"] > 0
    # the inner function is traced INSIDE the outer: the program's own
    # record holds all of its trace, the totals each second once
    assert 0 < inner["trace_s"] <= outer["trace_s"]
    assert did["trace_s"] == pytest.approx(outer["trace_s"], rel=1e-3)
    assert did["lower_s"] == pytest.approx(outer["lower_s"])
    assert did["compile_s"] == pytest.approx(outer["compile_s"])
    event, = events_of("jax_program", fun_name="ledger_outer")
    assert event["cache"] == "off" and event["phase"] is None
    assert event["trace_s"] == pytest.approx(outer["trace_s"])
    assert event["lower_s"] == pytest.approx(outer["lower_s"])
    assert event["compile_s"] == pytest.approx(outer["compile_s"])
    grown = [obs.counter_value(*what[:1], **dict(what[1:])) - before
             for what, before in counted.items()]
    assert grown == [1, pytest.approx(outer["lower_s"])]
    # a second call compiles nothing and fires nothing
    mark = ledger.now()
    ledger_outer(x).block_until_ready()
    assert ledger.since(mark) == dict.fromkeys(compiles.TOTALS, 0)


def test_the_persistent_caches_miss_then_hit(ledger, tmp_path):
    @jax.jit
    def ledger_cached(x):
        return jnp.cos(x) @ x

    x = jnp.ones(32)
    with compile_cache(tmp_path / "cache"):
        mark = ledger.now()
        ledger_cached(x).block_until_ready()
        first = ledger.since(mark)
        jax.clear_caches()          # the process forgets; the directory not
        mark = ledger.now()
        ledger_cached(x).block_until_ready()
        second = ledger.since(mark)
    assert (first["programs"], first["cache_misses"],
            first["cache_hits"]) == (1, 1, 0)
    assert (second["programs"], second["cache_misses"],
            second["cache_hits"]) == (1, 0, 1)
    assert [e["cache"] for e in events_of(
        "jax_program", fun_name="ledger_cached")] == ["miss", "hit"]
    rec = ledger.programs()["ledger_cached"]
    assert (rec["compiles"], rec["cache_hits"], rec["cache_misses"]) \
        == (2, 1, 1)
    assert obs.counter_value("jax.programs", stage="compile", cache="hit",
                             when="before") == 1


def test_a_compile_under_traffic_is_named_in_one_warning(ledger):
    rng = np.random.default_rng(0)
    engine = ServingEngine(k=3, buckets=(8,))
    engine.publish(rng.standard_normal((20, 4)).astype(np.float32),
                   rng.standard_normal((50, 4)).astype(np.float32))
    engine.warmup()

    @jax.jit
    def compiled_under_traffic(x):
        return x - 1

    @jax.jit
    def compiled_in_a_phase(x):
        return x + 2

    x = jnp.ones(5)
    with engine:
        engine.recommend(1)
        assert not events_of("warning", what="jax.compile")
        compiled_under_traffic(x).block_until_ready()
        with phase("start.warmup"):
            compiled_in_a_phase(x).block_until_ready()
    warning, = events_of("warning", what="jax.compile")
    assert warning["fun_name"] == "compiled_under_traffic"
    assert warning["phase"] == "traffic" and warning["seconds"] > 0
    assert "compiled_under_traffic" in warning["reason"]
    event, = events_of("jax_program", fun_name="compiled_under_traffic")
    assert event["phase"] == "traffic"
    event, = events_of("jax_program", fun_name="compiled_in_a_phase")
    assert event["phase"] == "start.warmup"
    assert obs.counter_value("jax.programs", stage="compile", cache="off",
                             when="traffic") == 1
    assert obs.counter_value("jax.program_seconds", stage="compile",
                             when="traffic") == warning["seconds"]
    # the engine stopped: a later compile is nobody's traffic
    jax.jit(lambda x: x / 7)(x).block_until_ready()
    assert len(events_of("warning", what="jax.compile")) == 1


def test_serving_a_warmed_engine_calls_neither_listener(ledger):
    rng = np.random.default_rng(1)
    engine = ServingEngine(k=3, buckets=(8,))
    U = rng.standard_normal((20, 4)).astype(np.float32)
    engine.publish(U, rng.standard_normal((50, 4)).astype(np.float32))
    engine.warmup()
    with engine:
        engine.recommend(0)             # the pinned program's first run
        mark = ledger.now()
        for user in range(20):
            engine.recommend(user)
            engine.recommend(U[user])
        assert ledger.since(mark)["listener_calls"] == 0
