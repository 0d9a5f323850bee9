"""Production-day scenario harness (tpu_als/scenario/).

Three layers under test:

1. the harness mechanics themselves — spec validation, ``$key`` bound
   resolution, delta-based counter/event judging, the obs trail
   (``scenario_start``/``scenario_phase``/``scenario_assert``/
   ``scenario_end``), fault-arming scope, LIFO cleanups — via tiny
   inline specs that never touch jax;
2. the five NAMED scenarios, each run end to end in-process (the same
   code path ``tpu_als scenario run`` takes) — including the
   preempt-under-serve acceptance property (bitwise resume while
   serving kept answering) and the subprocess-based pytest port of the
   chaos_smoke kill-and-resume flow;
3. the CLI error contract — unknown scenario names and unparseable
   ``TPU_ALS_FAULT_SPEC`` fail with one typed line and exit 2, never a
   traceback.

Plus the degraded-mode serving coverage ISSUE 6 asks for: the
``serve.degraded`` counter and ``serve_degraded`` event in ONE process,
with the shard loss injected through the fault harness.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_als import obs, scenario
from tpu_als.resilience import faults
from tpu_als.scenario.spec import (
    Assertion,
    Phase,
    ScenarioSpec,
    evaluate_assertion,
    resolve_bound,
)

pytestmark = pytest.mark.scenario

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh():
    """Disarmed faults + a fresh registry per test (scenario runs judge
    counter DELTAS, but a clean slate keeps failures readable)."""
    faults.clear()
    reg = obs.reset()
    yield reg
    faults.clear()


def _cli(args, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-c",
         "import sys; from tpu_als.cli import main; main(sys.argv[1:])"]
        + args, capture_output=True, text=True, env=env)


# ---------------------------------------------------------------------------
# 1. harness mechanics (jax-free inline specs)


def test_registry_has_the_issue_scenarios():
    for name in ("traffic-spike", "preempt-under-serve", "torn-publish",
                 "cold-start", "preempt-resume", "flight-recorder",
                 "continuous-freshness"):
        assert scenario.get_scenario(name).name == name


def test_unknown_scenario_is_typed_and_lists_available():
    with pytest.raises(scenario.UnknownScenario) as ei:
        scenario.get_scenario("no-such")
    assert ei.value.name == "no-such"
    assert "traffic-spike" in str(ei.value)
    assert set(ei.value.available) == set(scenario.names())


def test_assertion_rejects_unknown_kind_and_op():
    with pytest.raises(ValueError, match="unknown kind"):
        Assertion("x", "vibes", value=1)
    with pytest.raises(ValueError, match="unknown op"):
        Assertion("x", "fact", op="~=", fact="f", value=1)


def test_resolve_bound_config_reference():
    assert resolve_bound("$slo_ms", {"slo_ms": 250.0}) == 250.0
    assert resolve_bound(42, {}) == 42
    with pytest.raises(scenario.ScenarioError, match="not set"):
        resolve_bound("$missing", {})


def _tiny_spec(phases, assertions, fault_spec=None, defaults=None):
    return ScenarioSpec(name="tiny", doc="inline test spec",
                        phases=tuple(phases),
                        assertions=tuple(assertions),
                        fault_spec=fault_spec,
                        defaults=defaults or {})


def test_run_scenario_obs_trail_and_delta_counters(_fresh):
    reg = _fresh
    # pre-scenario traffic: the delta baseline must exclude this
    reg.counter("serving.requests", 100)

    def work(ctx):
        ctx.registry.counter("serving.requests", 7)
        ctx.facts["answered"] = 7

    spec = _tiny_spec(
        [Phase("work", work)],
        [Assertion("delta_counted", "counter", metric="serving.requests",
                   op="==", value=7),
         Assertion("fact_bound", "fact", fact="answered", op=">=",
                   value="$floor")],
        defaults={"floor": 5})
    result = scenario.run_scenario(spec)
    assert result["passed"]
    types = [e["type"] for e in reg._events]
    assert types.count("scenario_start") == 1
    assert types.count("scenario_phase") == 1
    assert types.count("scenario_assert") == 2
    assert types.count("scenario_end") == 1
    end = [e for e in reg._events if e["type"] == "scenario_end"][-1]
    assert end["passed"] is True


def test_run_scenario_failed_assertion_fails_verdict():
    spec = _tiny_spec(
        [Phase("noop", lambda ctx: None)],
        [Assertion("missing_fact", "fact", fact="never_set", op="==",
                   value=1)])
    result = scenario.run_scenario(spec)
    assert not result["passed"]
    rec = result["assertions"][0]
    assert rec["error"] == "fact 'never_set' was never recorded"
    with pytest.raises(scenario.ScenarioFailed, match="missing_fact"):
        scenario.run_scenario(spec, raise_on_fail=True)


def test_run_scenario_phase_failure_is_typed_and_cleans_up(_fresh):
    reg = _fresh
    stopped = []

    def start(ctx):
        ctx.defer(lambda: stopped.append("a"))
        ctx.defer(lambda: stopped.append("b"))

    def boom(ctx):
        raise RuntimeError("shard on fire")

    spec = _tiny_spec([Phase("start", start), Phase("boom", boom)],
                      [Assertion("never", "fact", fact="x", value=1)],
                      fault_spec="serve.gather=raise")
    with pytest.raises(scenario.PhaseFailed, match="shard on fire"):
        scenario.run_scenario(spec)
    assert stopped == ["b", "a"]          # LIFO
    assert not faults.active()            # chaos never leaks out
    end = [e for e in reg._events if e["type"] == "scenario_end"][-1]
    assert end["passed"] is False and "shard on fire" in end["error"]


def test_run_scenario_restores_prior_fault_arming():
    faults.install("checkpoint.write=raise")
    spec = _tiny_spec(
        [Phase("check", lambda ctx: ctx.facts.__setitem__(
            "armed", faults.armed("serve.gather")))],
        [Assertion("scenario_chaos_armed", "fact", fact="armed",
                   op="==", value=True)],
        fault_spec="serve.gather=corrupt")
    assert scenario.run_scenario(spec)["passed"]
    # after the run: the scenario's arming is gone; with no env spec the
    # harness is fully disarmed (install_from_env semantics)
    assert not faults.armed("serve.gather")


def test_quantile_assertion_scales_to_ms(_fresh):
    reg = _fresh
    for v in (0.010, 0.020, 0.030):
        reg.histogram("serving.e2e_seconds", v)
    spec = _tiny_spec([Phase("noop", lambda ctx: None)],
                      [Assertion("p99_ms", "quantile",
                                 metric="serving.e2e_seconds", q=0.99,
                                 scale_ms=True, op="<=", value=50.0)])
    result = scenario.run_scenario(spec)
    assert result["passed"]
    assert 10.0 <= result["assertions"][0]["observed"] <= 50.0


def test_ratio_assertion_empty_denominator_is_zero():
    spec = _tiny_spec([Phase("noop", lambda ctx: None)],
                      [Assertion("shed_rate", "ratio",
                                 num="serving.shed",
                                 den=("serving.shed",
                                      "serving.requests"),
                                 op="<=", value=0.5)])
    result = scenario.run_scenario(spec)
    assert result["passed"]
    assert result["assertions"][0]["observed"] == 0.0


def test_bank_result_contract(tmp_path):
    spec = _tiny_spec([Phase("noop", lambda ctx: None)], [])
    result = scenario.run_scenario(spec)
    path = tmp_path / "BENCH_scenario_tiny.json"
    banked = scenario.bank_result(result, str(path))
    import json

    on_disk = json.loads(path.read_text())
    assert on_disk["metric"] == "scenario_tiny"
    assert on_disk["value"] == 1 and on_disk["unit"] == "pass"
    assert "+00:00" in on_disk["banked_at"]      # absolute UTC, not naive
    assert on_disk["platform"] == banked["platform"]


# ---------------------------------------------------------------------------
# 2. the named scenarios, end to end


def test_traffic_spike_scenario_passes():
    result = scenario.run_scenario(
        scenario.get_scenario("traffic-spike"),
        config={"base_s": 0.4, "spike_s": 0.6})
    assert result["passed"], result["assertions"]
    assert result["facts"]["hard_failures"] == 0


def test_torn_publish_scenario_passes(_fresh):
    reg = _fresh
    result = scenario.run_scenario(scenario.get_scenario("torn-publish"))
    assert result["passed"], result["assertions"]
    # the obs trail the ISSUE names: serve.degraded + serving_publish
    assert reg.counter_value("serve.degraded") >= 1
    assert any(e["type"] == "serve_degraded" for e in reg._events)
    assert sum(e["type"] == "serving_publish" for e in reg._events) >= 2


def test_cold_start_scenario_passes():
    result = scenario.run_scenario(scenario.get_scenario("cold-start"))
    assert result["passed"], result["assertions"]
    assert result["facts"]["new_user_served"] is True
    assert 0 < result["facts"]["freshness_ms"] <= 5000


def test_flight_recorder_scenario_passes(_fresh):
    """ISSUE 7 acceptance: forced SLO breaches leave flight_record
    events with full per-request span breakdowns (>= last 8 requests),
    asserted from the obs trail by the scenario's own assertions."""
    reg = _fresh
    result = scenario.run_scenario(scenario.get_scenario("flight-recorder"))
    assert result["passed"], result["assertions"]
    assert result["facts"]["complete_breach_records"] >= 8
    assert result["facts"]["hard_failures"] == 0
    records = [e for e in reg._events if e["type"] == "flight_record"]
    assert all(r["trigger"] == "slo_breach" for r in records)
    # the breach dumps the engine's per-batch records too
    requests = [r for r in records if "admission" in r["spans"]]
    assert len(requests) >= 8
    assert {r["batch"] for r in requests} <= {
        r["batch"] for r in records if r not in requests}
    for r in requests:
        assert all(r["spans"][k] is not None for k in
                   ("admission", "queue_wait", "score", "respond"))


def test_continuous_freshness_scenario_passes(_fresh):
    """ISSUE 11 acceptance: a sustained rating stream under live serve
    load — freshness p99 under the SLO, zero torn publishes, every
    publish incremental (retag/delta/compact, never a full rebuild),
    and the poison quarantine counted exactly — all judged from the
    obs trail by the scenario's own assertions."""
    reg = _fresh
    result = scenario.run_scenario(
        scenario.get_scenario("continuous-freshness"))
    assert result["passed"], result["assertions"]
    f = result["facts"]
    assert f["all_incremental"] is True
    assert f["new_user_served"] is True
    assert f["hard_failures"] == 0
    # the trail carries the live vocabulary end to end
    assert reg.histogram_count("live.freshness_seconds") > 0
    assert any(e["type"] == "live_update" for e in reg._events)
    assert any(e["type"] == "ingest_quarantined"
               and e["path"] == "live" for e in reg._events)


def test_preempt_under_serve_acceptance():
    """The ISSUE's acceptance property: bitwise-equal factors vs an
    unpreempted run, while serving returned answers throughout (shed or
    degraded allowed, hard failures not)."""
    result = scenario.run_scenario(
        scenario.get_scenario("preempt-under-serve"))
    assert result["passed"], result["assertions"]
    f = result["facts"]
    assert f["resume_bitwise"] is True
    assert f["preempted"] is True
    assert f["served_during_train"] >= 1
    assert f["serve_hard_failures"] == 0


def test_preempt_resume_scenario_subprocess():
    """The pytest port of chaos_smoke stage 3: same scenario, same
    assertions (preempted CLI train exits 43; --resume auto discovers
    the checkpoint and saves a model), via real CLI subprocesses."""
    result = scenario.run_scenario(scenario.get_scenario("preempt-resume"))
    assert result["passed"], result["assertions"]
    f = result["facts"]
    assert f["preempt_exit_code"] == 43
    assert f["resume_exit_code"] == 0
    assert f["resume_discovered"] is True and f["model_saved"] is True


def test_device_loss_scenario_subprocess():
    """Elastic training acceptance (PR 18 tentpole): a device dies
    mid-fit, the run COMPLETES (exit 0, not a crash), the recovery tree
    (device_lost -> mesh_reformed -> elastic_resume) is re-derivable
    from events.jsonl alone, and the final factors are bitwise equal to
    a fresh shrunk-mesh fit resumed from the same checkpoint."""
    result = scenario.run_scenario(scenario.get_scenario("device-loss"))
    assert result["passed"], result["assertions"]
    f = result["facts"]
    assert f["elastic_exit_code"] == 0
    assert (f["device_lost_events"] == f["mesh_reformed_events"]
            == f["elastic_resume_events"] == 1)
    assert f["resume_from_checkpoint"] is True
    assert f["factors_bitwise_equal"] is True


# ---------------------------------------------------------------------------
# degraded-mode serving, single process (ISSUE 6 satellite)


def test_serve_degraded_counter_and_event_single_process(_fresh):
    from tpu_als.parallel import serve
    from tpu_als.parallel.mesh import make_mesh

    reg = _fresh
    serve.reset_last_good()
    rng = np.random.default_rng(0)
    U = rng.normal(size=(16, 8)).astype(np.float32)
    V = rng.normal(size=(24, 8)).astype(np.float32)
    mesh = make_mesh(8)
    # hit 1 clean (primes last-good), hit 2 a ServeShardLost via the
    # fault harness — all in THIS process
    faults.install("serve.gather=corrupt@nth=2")
    _, ix_good = serve.topk_sharded(U, V, 5, mesh)
    before = reg.counter_value("serve.degraded")
    _, ix, info = serve.topk_sharded(U, V, 5, mesh, return_info=True)
    assert info["degraded"] is True
    assert reg.counter_value("serve.degraded") == before + 1
    ev = [e for e in reg._events if e["type"] == "serve_degraded"]
    assert ev and "ServeShardLost" in ev[-1]["reason"]
    # degraded answers come from the last-good catalog == same catalog
    np.testing.assert_array_equal(ix, ix_good)


# ---------------------------------------------------------------------------
# 3. CLI error contract (typed, non-zero, no traceback)


def test_cli_unknown_scenario_exits_2_and_lists_names():
    p = _cli(["scenario", "run", "definitely-not-a-scenario"])
    assert p.returncode == 2
    assert "unknown scenario" in p.stderr
    for name in scenario.names():
        assert name in p.stderr
    assert "Traceback" not in p.stderr


@pytest.mark.parametrize("argv", [
    ["scenario", "run", "torn-publish"],
    ["serve-bench", "--users", "10", "--items", "20", "--rank", "4",
     "--duration", "0.1"],
])
def test_cli_rejects_unparseable_fault_spec(argv):
    p = _cli(argv, env_extra={"TPU_ALS_FAULT_SPEC": "not=a@spec="})
    assert p.returncode == 2
    assert "FaultSpecError" in p.stderr
    assert "TPU_ALS_FAULT_SPEC" in p.stderr
    assert "Traceback" not in p.stderr


def test_import_with_bad_env_spec_warns_and_disarms():
    """A library import (no CLI front door) must neither die with a
    traceback nor silently arm garbage: faults end up DISARMED with a
    RuntimeWarning pointing at the env var."""
    p = subprocess.run(
        [sys.executable, "-W", "always", "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from tpu_als.resilience import faults\n"
         "sys.exit(0 if not faults.active() else 3)" % _REPO],
        capture_output=True, text=True,
        env={**os.environ, "TPU_ALS_FAULT_SPEC": "garbage"})
    assert p.returncode == 0, p.stderr
    assert "IGNORED" in p.stderr and "RuntimeWarning" in p.stderr
