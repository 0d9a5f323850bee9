"""Multi-tenant control-plane tests (tpu_als/tenancy/).

Five layers:

1. the REGISTRY contract — spec validation (name slug, weight,
   guardrail mode), duplicate/unknown-tenant typing, register → first
   publish, remove → lifecycle teardown, shape-class report,
2. the SCHEDULER policy — stride fair-share (weighted goodput under
   contention, min-vtime floor for joiners), typed per-tenant
   :class:`TenantOverloaded`, per-batch fault isolation,
3. the LABEL vocabulary — serving.*/live.* series carry tenant=<name>,
   unregistered label keys raise at write time, the static
   check_tenant_vocabulary / call-site rule catch the same drift
   offline,
4. seq-space NAMESPACING — one tenant's publishes never advance a
   neighbor's sequence, and same-shaped tenants share one plan entry,
5. the tenant-isolation scenario is registered with the fault-matrix
   assertions the smoke gate runs.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

from tpu_als import obs, plan
from tpu_als.tenancy import (DuplicateTenant, FairShareScheduler,
                             MultiTenantEngine, TenancyError, Tenant,
                             TenantOverloaded, TenantRegistry,
                             TenantSpec, UnknownTenant)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh():
    reg = obs.reset()
    yield reg


def _factors(rng, users=32, items=48, rank=8):
    return (rng.normal(size=(users, rank)).astype(np.float32),
            rng.normal(size=(items, rank)).astype(np.float32))


# ---------------------------------------------------------------------------
# 1. registry


def test_spec_validates_name_weight_mode():
    with pytest.raises(ValueError, match="must match"):
        TenantSpec(name="Bad Name!")
    with pytest.raises(ValueError, match="must match"):
        TenantSpec(name="")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec(name="a", weight=0)
    with pytest.raises(ValueError, match="guardrail_mode"):
        TenantSpec(name="a", guardrail_mode="yolo")
    assert TenantSpec(name="team-a_01").weight == 1.0


def test_register_publishes_and_emits(_fresh):
    rng = np.random.default_rng(0)
    U, V = _factors(rng)
    reg = TenantRegistry()
    t = reg.register(TenantSpec(name="a"), U, V)
    assert t.engine.published_seq == 1
    assert t.engine.tenant == "a"
    assert "a" in reg and len(reg) == 1
    evs = [e for e in _fresh._events
           if e.get("type") == "tenant_registered"]
    assert evs and evs[0]["tenant"] == "a"
    assert evs[0]["shape_class"] == t.shape_class


def test_duplicate_and_unknown_are_typed():
    rng = np.random.default_rng(0)
    U, V = _factors(rng)
    reg = TenantRegistry()
    reg.register(TenantSpec(name="a"), U, V)
    with pytest.raises(DuplicateTenant):
        reg.register(TenantSpec(name="a"), U, V)
    with pytest.raises(UnknownTenant) as ei:
        reg.get("ghost")
    assert ei.value.available == ("a",)
    assert isinstance(ei.value, TenancyError)


def test_remove_tears_down_and_emits(_fresh):
    rng = np.random.default_rng(0)
    U, V = _factors(rng)
    reg = TenantRegistry()
    reg.register(TenantSpec(name="a"), U, V)
    reg.remove("a")
    assert len(reg) == 0
    with pytest.raises(UnknownTenant):
        reg.remove("a")
    assert any(e.get("type") == "tenant_removed"
               for e in _fresh._events)


def test_register_is_publish_before_visible(monkeypatch):
    """The churn invariant (PR 18): a tenant is never observable in the
    registry before its engine's first publish completes, and a failed
    publish leaves no zombie — the engine is stopped and the name is
    immediately reusable."""
    from tpu_als.serving.engine import ServingEngine

    rng = np.random.default_rng(0)
    U, V = _factors(rng)
    reg = TenantRegistry()

    seen = {}
    real_publish = ServingEngine.publish

    def spying_publish(self, *a, **kw):
        seen["visible_during_publish"] = "a" in reg
        return real_publish(self, *a, **kw)

    monkeypatch.setattr(ServingEngine, "publish", spying_publish)
    reg.register(TenantSpec(name="a"), U, V)
    assert seen["visible_during_publish"] is False

    stopped = {}
    real_stop = ServingEngine.stop

    def failing_publish(self, *a, **kw):
        raise RuntimeError("boom: torn first publish")

    def spying_stop(self, *a, **kw):
        stopped["called"] = True
        return real_stop(self, *a, **kw)

    monkeypatch.setattr(ServingEngine, "publish", failing_publish)
    monkeypatch.setattr(ServingEngine, "stop", spying_stop)
    with pytest.raises(RuntimeError, match="torn first publish"):
        reg.register(TenantSpec(name="b"), U, V)
    assert "b" not in reg
    assert stopped.get("called") is True

    monkeypatch.setattr(ServingEngine, "publish", real_publish)
    monkeypatch.setattr(ServingEngine, "stop", real_stop)
    assert reg.register(TenantSpec(name="b"), U, V).name == "b"


def test_tenant_churn_snapshots_always_servable():
    """Register/remove churn on one name while a reader thread takes
    registry snapshots: every tenant a snapshot ever exposes has a
    published generation (``published_seq >= 1``), so the scheduler can
    never pick up a tenant mid-construction."""
    import threading

    rng = np.random.default_rng(0)
    U, V = _factors(rng, users=8, items=8, rank=4)
    reg = TenantRegistry()
    reg.register(TenantSpec(name="stable"), U, V)
    bad, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            for t in reg.tenants():
                if t.engine.published_seq < 1:
                    bad.append(t.name)

    r = threading.Thread(target=reader)
    r.start()
    try:
        for _ in range(25):
            reg.register(TenantSpec(name="churn"), U, V)
            reg.remove("churn")
    finally:
        stop.set()
        r.join()
    assert not bad, f"snapshot exposed unpublished tenants: {bad}"
    assert reg.names() == ("stable",)


def test_same_shape_tenants_share_plan_entry():
    rng = np.random.default_rng(0)
    reg = TenantRegistry()
    U, V = _factors(rng)
    reg.register(TenantSpec(name="a"), U, V)
    reg.register(TenantSpec(name="b"), *_factors(rng))
    U2, V2 = _factors(rng, users=4096, items=8192)
    reg.register(TenantSpec(name="big"), U2, V2)
    classes = reg.shape_classes()
    shared = [v for v in classes.values() if set(v) >= {"a", "b"}]
    assert shared, classes
    assert reg.get("a").engine.batcher.buckets \
        == reg.get("b").engine.batcher.buckets
    # and the planner resolution is tenant-blind: same inputs, same plan
    p1 = plan.resolve_tenant_plan(rank=8, n_users=32, n_items=48)
    p2 = plan.resolve_tenant_plan(rank=8, n_users=32, n_items=48)
    assert p1 == p2


def test_attach_live_is_tenant_labeled_and_single():
    rng = np.random.default_rng(0)
    U, V = _factors(rng)
    reg = TenantRegistry()
    reg.register(TenantSpec(name="a", fold_items=True,
                            freshness_slo_s=2.0), U, V)

    class _FakeFoldin:
        pass

    upd = reg.attach_live("a", _FakeFoldin())
    assert upd.tenant == "a"
    assert upd.fold_items is True
    assert upd.slo_s == 2.0
    with pytest.raises(TenancyError, match="already has"):
        reg.attach_live("a", _FakeFoldin())


# ---------------------------------------------------------------------------
# 2. scheduler policy


def _mk_tenant(name, weight=1.0, depth=1):
    class _B:
        def __init__(self, d):
            self._d = d

        def depth(self):
            return self._d

    class _E:
        def __init__(self, d):
            self.batcher = _B(d)

    return Tenant(spec=TenantSpec(name=name, weight=weight),
                  engine=_E(depth))


def test_stride_pick_prefers_min_vtime_then_name():
    s = FairShareScheduler()
    a, b = _mk_tenant("a"), _mk_tenant("b")
    a.vtime, b.vtime = 5.0, 3.0
    assert s.pick([a, b]).name == "b"
    b.vtime = 5.0
    assert s.pick([a, b]).name == "a"       # deterministic tie-break


def test_stride_charge_is_weighted(_fresh):
    s = FairShareScheduler()
    heavy, light = _mk_tenant("heavy", weight=2.0), _mk_tenant("light")
    s.charge(heavy, 8)
    s.charge(light, 8)
    assert heavy.vtime == 4.0 and light.vtime == 8.0
    assert heavy.served_rows == light.served_rows == 8
    assert _fresh.counter_value("tenancy.served_rows",
                                tenant="heavy") == 8


def test_joiner_floored_to_virtual_clock():
    s = FairShareScheduler()
    old = _mk_tenant("old")
    for _ in range(10):
        s.charge(s.pick([old]), 10)
    assert old.vtime == 100.0
    new = _mk_tenant("new")
    picked = s.pick([old, new])
    # the newcomer is floored to the global virtual clock (old's vtime
    # at its LAST pick) — it competes from now, not from a 100-row
    # catch-up monopoly
    assert new.vtime == 90.0
    assert picked.name == "new"
    # ...while a tenant that stayed in the rotation keeps its earned
    # deficit: the weighted shares are never clipped by the floor
    s.charge(picked, 10)
    assert s.pick([old, new]).name == "new"
    assert new.vtime == 100.0


def test_weighted_fair_share_under_contention():
    rng = np.random.default_rng(1)
    eng = MultiTenantEngine()
    eng.add_tenant(TenantSpec(name="heavy", weight=3.0, k=5),
                   *_factors(rng))
    eng.add_tenant(TenantSpec(name="light", weight=1.0, k=5),
                   *_factors(rng))
    eng.warmup()
    with eng:
        tickets = []
        for j in range(60):
            tickets.append(eng.submit("heavy", j % 32))
            tickets.append(eng.submit("light", j % 32))
        for t in tickets:
            t.result(timeout=30.0)
    h = eng.tenant("heavy")
    li = eng.tenant("light")
    assert h.served_rows == li.served_rows == 60
    # equal rows at 3x weight -> one third the virtual time charged
    assert h.vtime == pytest.approx(li.vtime / 3.0)


def test_old_head_does_not_hold_the_shared_scheduler_thread(_fresh):
    """One thread serves every tenant: a backlogged tenant whose oldest
    request has had its ``max_wait_s`` pops at once (ISSUE 29) instead
    of stalling its neighbors' queues for another ``max_wait_s``."""
    rng = np.random.default_rng(5)
    eng = MultiTenantEngine()
    eng.add_tenant(TenantSpec(name="patient", k=5, max_wait_s=20.0),
                   *_factors(rng))
    eng.add_tenant(TenantSpec(name="other", k=5, max_wait_s=0.0),
                   *_factors(rng))
    eng.warmup()
    old = eng.submit("patient", 0)
    old.t_submit -= 21.0             # queued while the thread was away
    neighbor = eng.submit("other", 0)
    t0 = time.perf_counter()
    assert eng._drain_round()        # the scheduler thread's own round
    assert time.perf_counter() - t0 < 10.0
    assert old.done() and neighbor.done()
    assert eng.tenant("patient").engine.batcher.closed_by == "age"
    assert _fresh.counter_value("serving.batch_closed", by="age",
                                tenant="patient") == 1


def test_tenant_overloaded_is_typed_and_isolated():
    rng = np.random.default_rng(2)
    eng = MultiTenantEngine()
    eng.add_tenant(TenantSpec(name="small", k=5, max_queue=2),
                   *_factors(rng))
    eng.add_tenant(TenantSpec(name="roomy", k=5), *_factors(rng))
    eng.warmup()
    # engine NOT started: small's queue fills and stays full
    with pytest.raises(TenantOverloaded) as ei:
        for _ in range(10):
            eng.submit("small", 0)
    assert ei.value.tenant == "small"
    from tpu_als.serving import Overloaded
    assert isinstance(ei.value, Overloaded)   # old handlers still catch
    # the neighbor's budget is untouched
    t = eng.submit("roomy", 0)
    assert obs.counter_value("serving.shed", tenant="small") == 1
    assert obs.counter_value("serving.shed", tenant="roomy") == 0
    with eng:                                  # drain what was admitted
        t.result(timeout=10.0)


def test_batch_fault_isolated_to_one_tenant(_fresh):
    rng = np.random.default_rng(3)
    eng = MultiTenantEngine()
    eng.add_tenant(TenantSpec(name="sick", k=5), *_factors(rng))
    eng.add_tenant(TenantSpec(name="well", k=5), *_factors(rng))
    eng.warmup()
    from tpu_als.resilience import faults
    with eng:
        faults.install("serving.score=raise@once")
        try:
            bad = eng.submit("sick", 0)
            with pytest.raises(faults.InjectedFault):
                bad.result(timeout=10.0)
        finally:
            faults.clear()
        s, ix = eng.recommend("well", 0, timeout=10.0)
        assert np.isfinite(np.asarray(s)).all()
        # the sick tenant recovers on its next batch too
        s2, _ = eng.recommend("sick", 1, timeout=10.0)
        assert np.isfinite(np.asarray(s2)).all()
    assert _fresh.counter_value("tenancy.batch_errors",
                                tenant="sick") == 1
    assert _fresh.counter_value("tenancy.batch_errors",
                                tenant="well") == 0


# ---------------------------------------------------------------------------
# 3. label vocabulary, runtime + static


def test_serving_metrics_carry_tenant_label(_fresh):
    rng = np.random.default_rng(4)
    eng = MultiTenantEngine()
    eng.add_tenant(TenantSpec(name="a", k=5), *_factors(rng))
    eng.warmup()
    with eng:
        eng.recommend("a", 0, timeout=10.0)
    assert _fresh.counter_value("serving.requests", tenant="a") == 1
    assert _fresh.histogram_count("serving.e2e_seconds", tenant="a") == 1
    # the UNLABELED series is a different series: single-tenant engines
    # keep writing it, per-tenant reads never see their neighbors
    assert _fresh.counter_value("serving.requests") == 0


def test_unregistered_label_key_raises():
    with pytest.raises(ValueError, match="does not declare"):
        obs.counter("ingest.rows", 1, tenant="a")
    with pytest.raises(ValueError, match="does not declare"):
        obs.histogram("train.stage_seconds", 0.1, tenant="a",
                      stage="solve")
    # declared keys still work
    obs.histogram("train.stage_seconds", 0.1, stage="solve")
    obs.histogram("serving.publish_seconds", 0.1, mode="full",
                  tenant="a")


def _load_vocab():
    spec = importlib.util.spec_from_file_location(
        "_tal_vocab_test", os.path.join(REPO, "tpu_als", "analysis",
                                        "vocab.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tenant_vocabulary_pins_hold():
    vocab = _load_vocab()
    assert vocab.check_tenant_vocabulary(REPO) == []
    # the pin actually bites: a schema missing the mode key fails it
    schema, _ = vocab.load_registries(REPO)
    assert "mode" in schema.LABELS["serving.publish_seconds"]
    assert "tenant" in schema.LABELS["serving.publish_seconds"]
    for name in schema.METRICS:
        if name.startswith(("serving.", "live.")):
            assert name in schema.TENANT_LABELED, name


def test_callsite_rule_flags_unregistered_tenant_label(tmp_path):
    vocab = _load_vocab()
    bad = tmp_path / "bad_site.py"
    bad.write_text(
        "from tpu_als import obs\n"
        "obs.counter('ingest.rows', 5, tenant='a')\n"
        "obs.histogram('serving.e2e_seconds', 0.1, tenant='a')\n")
    errs = vocab.check_file(str(bad), repo=REPO)
    assert len(errs) == 1
    lineno, msg = errs[0]
    assert lineno == 2 and "tenant=" in msg and "ingest.rows" in msg


# ---------------------------------------------------------------------------
# 4. seq-space namespacing


def test_publish_seq_spaces_are_namespaced(_fresh):
    rng = np.random.default_rng(5)
    eng = MultiTenantEngine()
    Ua, Va = _factors(rng)
    Ub, Vb = _factors(rng)
    eng.add_tenant(TenantSpec(name="a", k=5), Ua, Va)
    eng.add_tenant(TenantSpec(name="b", k=5), Ub, Vb)
    assert eng.published_seq("a") == eng.published_seq("b") == 1
    eng.publish("a", Ua, Va)
    eng.publish("a", Ua, Va)
    assert eng.published_seq("a") == 3
    assert eng.published_seq("b") == 1      # untouched by the neighbor
    seq, mode = eng.publish_update("b", Ub, Vb)
    assert (seq, eng.published_seq("a")) == (2, 3)
    pubs = [e for e in _fresh._events
            if e.get("type") == "serving_publish"]
    assert {e.get("tenant") for e in pubs} == {"a", "b"}
    eng.stop()


# ---------------------------------------------------------------------------
# 5. scenario registration


def test_tenant_isolation_scenario_registered():
    from tpu_als.scenario import get_scenario

    s = get_scenario("tenant-isolation")
    assert [p.name for p in s.phases] == [
        "solo-baseline", "multi-tenant-start", "fault-storm",
        "tenant-churn", "judge"]
    checks = {a.check for a in s.assertions}
    assert {"b_topk_bitwise", "b_p99_under_slo", "b_zero_shed",
            "a_spike_shed", "a_quarantine_attributed",
            "sentinel_tripped", "rolled_back"} <= checks
    # the storm arms its faults IN PHASE, scoped to tenant A — a
    # spec-level fault_spec would poison the solo baseline too
    assert s.fault_spec is None
