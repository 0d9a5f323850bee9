"""Execution planner (tpu_als.plan, docs/planner.md): the persistent
autotune cache, the seed-and-walk resolve discipline, and every dispatch
site that consults it.

The load-bearing pins, straight from the subsystem's contract:

- EQUIVALENCE: warm cache, cold cache, and planner-off must resolve the
  exact same plan at every dispatch site — the cache supplies probe
  outcomes, never a different answer.
- ZERO PROBES WARM: a separate process resolving the same plan key must
  perform no probe executions, asserted from the obs event trail
  (``plan_cache_hit`` present, ``plan_probe`` absent).
- NEVER TRUST CORRUPTION: a corrupt or schema-mismatched entry is typed
  (``PlanCacheCorrupt``), quarantined to ``.corrupt/``, and reprobed —
  never crashed on, never silently steering a plan.
- OFF IS FREE: ``TPU_ALS_PLAN_CACHE=off`` leaves the training step's
  traced jaxpr byte-identical (the ne_audit/attribution discipline).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_als import ALS, obs, plan
from tpu_als.core.als import AlsConfig, init_factors, make_step
from tpu_als.core.als import resolve_solve_path
from tpu_als.core.ratings import build_csr_buckets
from tpu_als.plan import cache as plan_cache
from tpu_als.plan.cache import ENV_VAR, PlanCacheCorrupt
from tpu_als.serving.batcher import DEFAULT_BUCKETS
from tpu_als.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plan_state(monkeypatch, tmp_path):
    """Each test gets its own cache dir, an empty probe registry, and a
    clean obs registry — planner state is exactly what the test builds."""
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "plan"))
    platform.clear_probe_caches()
    obs.reset()
    yield
    platform.clear_probe_caches()
    obs.reset()


def _events(etype):
    return [e for e in obs.default_registry()._events if e["type"] == etype]


def _problem(nU=60, nI=40, nnz=800, seed=0):
    gen = np.random.default_rng(seed)
    u = gen.integers(0, nU, nnz)
    i = gen.integers(0, nI, nnz)
    r = gen.uniform(0.5, 5.0, nnz).astype(np.float32)
    ucsr = build_csr_buckets(u, i, r, nU, min_width=4, chunk_elems=1 << 12)
    icsr = build_csr_buckets(i, u, r, nI, min_width=4, chunk_elems=1 << 12)
    return ucsr, icsr


# -- cache layer (stdlib-only): mode, roundtrip, validation, quarantine ----

def test_mode_and_off_values(monkeypatch):
    for v in ("off", "OFF", "0", "none", "disabled", " Off "):
        monkeypatch.setenv(ENV_VAR, v)
        assert plan_cache.mode() == "off"
        assert plan_cache.cache_dir() is None
        assert not plan.armed()
        with pytest.raises(RuntimeError, match="disarmed"):
            plan_cache.entry_path({"rank": 4})
    monkeypatch.setenv(ENV_VAR, "/tmp/somewhere")
    assert plan_cache.mode() == "/tmp/somewhere"
    assert plan.armed()


def test_key_digest_stable_and_shape_class():
    k1 = {"rank": 4, "dtype": "float32"}
    assert plan_cache.key_digest(k1) == plan_cache.key_digest(dict(k1))
    assert plan_cache.key_digest(k1) != plan_cache.key_digest(
        {"rank": 8, "dtype": "float32"})
    assert plan.shape_class() == "generic"
    # log2 bucketing: near sizes share a class, order-of-magnitude don't
    a = plan.shape_class(n_users=1000, n_items=500, nnz=10_000)
    b = plan.shape_class(n_users=1023, n_items=400, nnz=12_000)
    c = plan.shape_class(n_users=100_000, n_items=500, nnz=10_000)
    assert a == b != c
    assert plan.shape_class(n_users=1000) == "u2^9.i?.nnz?"


def _entry_for(key, resolved="xla"):
    return {
        "schema_version": plan_cache.SCHEMA_VERSION,
        "plan_key": key,
        "probes": {"pallas_topk": {"(8, 5)": True}},
        "components": {"topk:k=5": {
            "resolved": resolved,
            "provenance": {"banked_at": "2026-08-05T00:00:00+00:00"},
        }},
    }


def test_store_load_roundtrip_atomic(tmp_path):
    key = plan.plan_key(rank=8, dtype="float32")
    path = plan_cache.store_entry(key, _entry_for(key))
    assert os.path.basename(path).startswith("plan_")
    doc = plan_cache.load_entry(key)
    assert doc["components"]["topk:k=5"]["resolved"] == "xla"
    # no temp litter from the atomic-rename discipline
    leftovers = [n for n in os.listdir(os.path.dirname(path)) if ".tmp." in n]
    assert leftovers == []
    # absent key reads as None, not an error
    assert plan_cache.load_entry(plan.plan_key(rank=99, dtype="float32")) \
        is None


@pytest.mark.parametrize("mutate,match", [
    (lambda d: d.update(schema_version=999), "schema_version"),
    (lambda d: d.update(plan_key={"rank": -1}), "plan_key mismatch"),
    (lambda d: d.update(probes={"pallas_topk": {"k": "yes"}}),
     "not {key: bool}"),
    (lambda d: d["components"]["topk:k=5"].pop("resolved"),
     "no resolved plan"),
    (lambda d: d["components"]["topk:k=5"].update(provenance={}),
     "banked_at"),
])
def test_schema_violations_are_typed(mutate, match):
    key = plan.plan_key(rank=8, dtype="float32")
    path = plan_cache.store_entry(key, _entry_for(key))
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    mutate(doc)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    with pytest.raises(PlanCacheCorrupt, match=match) as ei:
        plan_cache.load_entry(key)
    assert ei.value.path == path


def test_unparseable_json_is_typed_and_quarantine_keeps_evidence():
    key = plan.plan_key(rank=8, dtype="float32")
    path = plan_cache.store_entry(key, _entry_for(key))
    with open(path, "w", encoding="utf-8") as f:
        f.write("{ this is not json")
    with pytest.raises(PlanCacheCorrupt, match="unreadable JSON"):
        plan_cache.load_entry(key)
    dest = plan_cache.quarantine(path, "unreadable JSON")
    assert not os.path.exists(path)          # moved, not copied
    assert os.path.exists(dest)
    with open(dest + ".reason", encoding="utf-8") as f:
        assert "unreadable" in f.read()
    assert plan_cache.quarantine(path, "again") is None   # already gone


def test_list_entries_renders_corrupt_without_raising(tmp_path):
    key = plan.plan_key(rank=8, dtype="float32")
    plan_cache.store_entry(key, _entry_for(key))
    bad = os.path.join(plan_cache.cache_dir(), "plan_deadbeef00.json")
    with open(bad, "w", encoding="utf-8") as f:
        f.write("garbage")
    entries = plan_cache.list_entries()
    kinds = sorted(type(doc).__name__ for _, doc in entries)
    assert kinds == ["PlanCacheCorrupt", "dict"]
    assert plan_cache.clear() == 2           # both files removed
    assert plan_cache.list_entries() == []


# -- planner resolve discipline: cold banks, warm seeds, corrupt reprobes --

def test_cold_resolve_banks_with_provenance_and_emits_trail():
    out = plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    assert out == "xla"
    miss = _events("plan_cache_miss")
    assert len(miss) == 1 and miss[0]["reason"] == "absent"
    probes = _events("plan_probe")
    assert any(e["kernel"] == "walk:topk:k=5" for e in probes)
    res = _events("plan_resolved")
    assert len(res) == 1 and res[0]["source"] == "probe"
    entry = plan_cache.load_entry(plan.plan_key(rank=8, dtype="float32"))
    comp = entry["components"]["topk:k=5"]
    assert comp["resolved"] == "xla"
    prov = comp["provenance"]
    assert prov["banked_at"] and prov["walk_seconds"] >= 0
    assert prov["model"]["proposal"] in ("pallas", "xla")


def test_warm_resolve_hits_and_runs_zero_probes():
    plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    platform.clear_probe_caches()            # simulate a fresh process
    obs.reset()
    out = plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    assert out == "xla"
    assert len(_events("plan_cache_hit")) == 1
    assert _events("plan_probe") == []       # the warm-start contract
    res = _events("plan_resolved")
    assert len(res) == 1 and res[0]["source"] == "cache"
    assert _events("plan_cache_miss") == []


def test_new_component_on_existing_entry_is_component_absent():
    plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    obs.reset()
    plan.resolve_topk(rank=8, k=64, walk=lambda: "xla")
    miss = _events("plan_cache_miss")
    assert len(miss) == 1 and miss[0]["reason"] == "component_absent"
    entry = plan_cache.load_entry(plan.plan_key(rank=8, dtype="float32"))
    assert set(entry["components"]) == {"topk:k=5", "topk:k=64"}


def test_corrupt_entry_is_quarantined_and_reprobed_never_crashed_on():
    """The satellite's negative test: garbage in the cache file must not
    crash the resolve OR steer the plan — quarantine, miss with
    reason='corrupt', rewalk, rebank."""
    first = plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    key = plan.plan_key(rank=8, dtype="float32")
    path = plan_cache.entry_path(key)
    with open(path, "w", encoding="utf-8") as f:
        f.write("{ this is not json")
    obs.reset()
    again = plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    assert again == first == "xla"
    miss = _events("plan_cache_miss")
    assert len(miss) == 1 and miss[0]["reason"] == "corrupt"
    warn = _events("warning")
    assert any("quarantined" in e.get("reason", "") for e in warn)
    qdir = os.path.join(os.path.dirname(path), ".corrupt")
    assert any(n.endswith(".reason") for n in os.listdir(qdir))
    # and the entry was re-banked valid
    assert plan_cache.load_entry(key)["components"]["topk:k=5"][
        "resolved"] == "xla"


def test_schema_mismatch_entry_also_quarantines_and_reprobes():
    plan.resolve_topk(rank=8, k=5, walk=lambda: "xla")
    path = plan_cache.entry_path(plan.plan_key(rank=8, dtype="float32"))
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    doc["schema_version"] = 999              # written by a different build
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    obs.reset()
    assert plan.resolve_topk(rank=8, k=5, walk=lambda: "xla") == "xla"
    assert _events("plan_cache_miss")[0]["reason"] == "corrupt"
    assert not os.path.exists(path) or \
        plan_cache.load_entry(plan.plan_key(rank=8, dtype="float32"))


def test_disarmed_resolvers_return_none_or_defaults(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "off")
    assert plan.resolve_training(rank=8, compute_dtype="float32",
                                 label="x", walk=lambda: {"a": 1}) is None
    assert plan.resolve_topk(rank=8, k=5, walk=lambda: "xla") is None
    assert plan.resolve_serving_buckets() == tuple(DEFAULT_BUCKETS)
    assert _events("plan_cache_hit") == _events("plan_cache_miss") == []


# -- equivalence at every dispatch site ------------------------------------

@pytest.mark.parametrize("cfg,rank", [
    (AlsConfig(rank=8), 8),
    (AlsConfig(rank=8, cg_iters=3, cg_mode="matfree"), 8),
    (AlsConfig(rank=8, nonnegative=True), 8),
    (AlsConfig(rank=160, compute_dtype="bfloat16"), 160),
])
def test_resolve_solve_path_equivalence(monkeypatch, tmp_path, cfg, rank):
    """Warm == cold == off, per config: the planner supplies probe
    outcomes, never a different answer."""
    monkeypatch.setenv(ENV_VAR, "off")
    off = resolve_solve_path(cfg, rank)
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "equiv"))
    cold = resolve_solve_path(cfg, rank)
    platform.clear_probe_caches()
    obs.reset()
    warm = resolve_solve_path(cfg, rank)
    assert off == cold == warm
    assert len(_events("plan_cache_hit")) == 1    # the warm one hit
    assert _events("plan_probe") == []


def test_topk_scores_auto_matches_planner_off(monkeypatch, tmp_path, rng):
    U = jnp.array(rng.normal(size=(6, 8)).astype(np.float32))
    V = jnp.array(rng.normal(size=(30, 8)).astype(np.float32))
    valid = jnp.ones((30,), dtype=bool)
    from tpu_als.ops.topk import auto_topk_backend, topk_scores

    assert auto_topk_backend(8, 5) == "xla"       # CPU: never pallas
    armed = topk_scores(U, V, valid, 5)
    assert len(_events("plan_resolved")) == 1     # went through the planner
    monkeypatch.setenv(ENV_VAR, "off")
    off = topk_scores(U, V, valid, 5)
    for a, b in zip(jax.tree_util.tree_leaves(armed),
                    jax.tree_util.tree_leaves(off)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_topk_auto_under_trace_skips_planner(rng):
    """A traced call must not touch the planner's disk I/O — it walks the
    in-process caches exactly as before."""
    U = jnp.array(rng.normal(size=(6, 8)).astype(np.float32))
    V = jnp.array(rng.normal(size=(30, 8)).astype(np.float32))
    valid = jnp.ones((30,), dtype=bool)
    from tpu_als.ops.topk import topk_scores

    jax.jit(lambda u, v: topk_scores(u, v, valid, 5))(U, V)
    assert _events("plan_resolved") == []
    assert plan_cache.list_entries() == []


def test_gather_strategy_explicit_passthrough_and_model_auto():
    assert plan.resolve_gather_strategy(
        requested="ring", n_users=100, n_items=50, rank=8,
        n_devices=4) == "ring"
    assert plan_cache.list_entries() == []        # passthrough banks nothing
    choice = plan.resolve_gather_strategy(
        requested="auto", n_users=50_000, n_items=4_000, rank=64,
        n_devices=4)
    assert choice in plan.GATHER_CANDIDATES
    model = plan.gather_model(n_users=50_000, n_items=4_000, rank=64,
                              n_devices=4)
    # the verdict is ALWAYS the deterministic model's (multi-host safety)
    assert choice == model["proposal"]
    # the bank carries provenance for plan show
    key = plan.plan_key(
        rank=64, dtype="float32",
        shape_class=plan.shape_class(n_users=50_000, n_items=4_000),
        mesh_shape=(4,))
    entry = plan_cache.load_entry(key)
    assert entry["components"]["gather:D=4"]["resolved"] == choice


def test_gather_auto_identical_with_and_without_cache(monkeypatch):
    kw = dict(requested="auto", n_users=10_000, n_items=2_000, rank=32,
              n_devices=8, implicit=True)
    armed = plan.resolve_gather_strategy(**kw)
    rearmed = plan.resolve_gather_strategy(**kw)     # warm path
    monkeypatch.setenv(ENV_VAR, "off")
    off = plan.resolve_gather_strategy(**kw)
    assert armed == rearmed == off


def test_gather_auto_rejected_in_multiprocess_gate():
    from tpu_als.api.fitting import check_multiprocess_gate

    est = ALS(gatherStrategy="auto")
    with pytest.raises(ValueError, match="auto"):
        check_multiprocess_gate(est)


def test_serving_buckets_default_banked_and_requested():
    assert plan.resolve_serving_buckets(requested=[4, 16]) == (4, 16)
    assert plan.resolve_serving_buckets() == tuple(DEFAULT_BUCKETS)
    # the bucket ladder is configuration-like: a banked ladder WINS
    key = plan.plan_key(rank=0, dtype="float32")
    path = plan_cache.entry_path(key)
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    doc["components"]["serving_buckets"]["resolved"] = [4, 16, 64]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    assert plan.resolve_serving_buckets() == (4, 16, 64)


def test_serving_engine_default_buckets_come_from_planner():
    from tpu_als.serving.engine import ServingEngine

    eng = ServingEngine(k=5)
    assert tuple(eng.batcher.buckets) == tuple(DEFAULT_BUCKETS)
    assert tuple(ServingEngine(k=5, buckets=(8, 32)).batcher.buckets) \
        == (8, 32)


# -- off is free: the traced training step is byte-identical ---------------

def test_planner_off_training_step_jaxpr_byte_identical(monkeypatch,
                                                        tmp_path):
    """The ne_audit-style pin: arming the planner may change WHERE probe
    verdicts come from, never the traced graph of the step itself."""
    ucsr, icsr = _problem()
    cfg = AlsConfig(rank=4, max_iter=2)
    nU, nI = ucsr.num_rows, icsr.num_rows
    ub = jax.device_put(ucsr.device_buckets())
    ib = jax.device_put(icsr.device_buckets())
    ku, kv = jax.random.split(jax.random.PRNGKey(cfg.seed))
    U0 = init_factors(ku, nU, cfg.rank)
    V0 = init_factors(kv, nI, cfg.rank)

    monkeypatch.setenv(ENV_VAR, "off")
    step = make_step(ub, ib, nU, nI, cfg,
                     ucsr.chunk_elems, icsr.chunk_elems)
    disarmed = str(jax.make_jaxpr(step)(U0, V0))

    monkeypatch.setenv(ENV_VAR, str(tmp_path / "armed"))
    step = make_step(ub, ib, nU, nI, cfg,
                     ucsr.chunk_elems, icsr.chunk_elems)
    armed = str(jax.make_jaxpr(step)(U0, V0))
    assert disarmed == armed


# -- probe registry (satellite: five module caches, one registry) ----------

def test_probe_registry_enumerable_and_clearable_in_place():
    c = platform.probe_cache("t_reg")
    assert platform.probe_cache("t_reg") is c
    c["k"] = True
    c.meta["k"] = {"seconds": 0.1, "reason": "compiled and validated"}
    assert "t_reg" in platform.probe_caches()
    platform.clear_probe_caches("t_reg")
    assert platform.probe_cache("t_reg") is c    # identity preserved
    assert not c and not c.meta


def test_all_pallas_modules_share_the_registry():
    from tpu_als.ops import (pallas_gather_ne, pallas_lanes,
                             pallas_lanes_blocked, pallas_solve,
                             pallas_topk)

    for mod in (pallas_gather_ne, pallas_lanes,
                pallas_lanes_blocked, pallas_solve, pallas_topk):
        cache = mod._AVAILABLE
        assert isinstance(cache, platform.ProbeCache)
        assert platform.probe_cache(cache.name) is cache
    assert platform.probe_cache("pallas_gather_ne_speed") \
        is pallas_gather_ne._FASTER
    assert platform.probe_cache("pallas_gather_solve") \
        is pallas_gather_ne._SOLVE_AVAILABLE
    assert platform.probe_cache("pallas_gather_solve_speed") \
        is pallas_gather_ne._SOLVE_FASTER


def test_probe_kernel_contract_unchanged_for_plain_dicts():
    d = {}
    assert platform.probe_kernel(d, "k", lambda: True) is False  # off-TPU
    assert d == {"k": False}                 # cached; no meta attribute


def test_probe_kernel_notes_provenance_on_registered_caches():
    c = platform.probe_cache("t_pk")
    assert platform.probe_kernel(c, ("r", 8), lambda: True) is False
    assert c.meta[("r", 8)] == {"seconds": None, "reason": "no TPU"}


def test_snapshot_banks_verdicts_and_seed_in_process_wins():
    c = platform.probe_cache("t_snap")
    c[("a", 1)] = True
    c.meta[("a", 1)] = {"seconds": 0.5, "reason": "compiled and validated"}
    c["refused"] = False
    c.meta["refused"] = {"seconds": 1.0, "reason": "compiler refused: x"}
    snap = platform.snapshot_probes()
    assert snap["t_snap"] == {repr(("a", 1)): True, "'refused'": False}
    assert platform.probe_timings()["t_snap"] == {repr(("a", 1)): 0.5,
                                                  "'refused'": 1.0}
    platform.clear_probe_caches("t_snap")
    c["refused"] = True                      # this process's own verdict
    n = platform.seed_probes({"t_snap": {repr(("a", 1)): True,
                                         "'refused'": False,
                                         "<unparseable": True}})
    assert n == 1                            # own verdict kept, junk skipped
    assert c[("a", 1)] is True and c["refused"] is True
    assert c.meta[("a", 1)]["seeded"]
    assert c.meta[("a", 1)]["reason"] == "banked verdict (plan cache)"


# -- whole-plan assembly + CLI verbs ---------------------------------------

def test_resolve_execution_plan_and_summary():
    ep = plan.resolve_execution_plan(rank=8, k=5, n_users=20_000,
                                     n_items=2_000, n_devices=4)
    assert ep.solve["resolved_solve_path"]
    assert ep.topk_backend == "xla"
    assert ep.gather_strategy in plan.GATHER_CANDIDATES
    assert ep.serving_buckets == tuple(DEFAULT_BUCKETS)
    s = ep.summary()
    assert s["resolved_solve_path"] == ep.solve["resolved_solve_path"]
    # off: same plan, no planner involvement
    os.environ[ENV_VAR] = "off"
    try:
        ep_off = plan.resolve_execution_plan(rank=8, k=5, n_users=20_000,
                                             n_items=2_000, n_devices=4)
    finally:
        del os.environ[ENV_VAR]
    assert ep_off.solve == ep.solve
    assert ep_off.topk_backend == ep.topk_backend
    assert ep_off.gather_strategy == ep.gather_strategy
    assert ep_off.serving_buckets == ep.serving_buckets


def test_cli_plan_warm_show_clear(capsys):
    from tpu_als.cli import main as cli_main

    cli_main(["plan", "warm", "--rank", "8", "--k", "5"])
    warm = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert warm["topk_backend"] == "xla"
    assert warm["serving_buckets"] == list(DEFAULT_BUCKETS)
    assert warm["mode"] != "off"

    bad = os.path.join(plan_cache.cache_dir(), "plan_deadbeef00.json")
    with open(bad, "w", encoding="utf-8") as f:
        f.write("garbage")
    cli_main(["plan", "show"])
    show = json.loads(capsys.readouterr().out)
    assert show["mode"] == plan_cache.cache_dir()
    good = [e for e in show["entries"] if "components" in e]
    corrupt = [e for e in show["entries"] if "corrupt" in e]
    assert good and corrupt                   # both rendered, nothing raised
    assert all("banked_at" in c for e in good
               for c in e["components"].values())

    cli_main(["plan", "clear"])
    cleared = json.loads(capsys.readouterr().out)
    assert cleared["cleared_entries"] == 2
    assert plan_cache.list_entries() == []


# -- the cross-process warm-start pin --------------------------------------

def test_cross_process_warm_start_zero_probe_executions(tmp_path):
    """Process 1 resolves cold and banks; process 2 on the same plan key
    must resolve with ZERO probe executions — pinned from the obs event
    trail: plan_cache_hit present, plan_probe absent."""
    plandir = str(tmp_path / "xproc")
    env = {**os.environ, ENV_VAR: plandir, "JAX_PLATFORMS": "cpu"}
    trails = []
    for run in ("cold", "warm"):
        obs_dir = str(tmp_path / f"obs_{run}")
        p = subprocess.run(
            [sys.executable, "-m", "tpu_als.cli", "plan", "warm",
             "--rank", "8", "--k", "5", "--obs-dir", obs_dir],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr
        with open(os.path.join(obs_dir, "events.jsonl"),
                  encoding="utf-8") as f:
            trails.append([json.loads(ln) for ln in f if ln.strip()])

    cold, warm = trails

    def of(trail, etype):
        return [e for e in trail if e["type"] == etype]

    assert of(cold, "plan_cache_miss") and of(cold, "plan_probe")
    assert all(e["source"] == "probe" for e in of(cold, "plan_resolved"))

    assert of(warm, "plan_cache_hit")
    assert of(warm, "plan_probe") == []       # zero probe executions
    assert of(warm, "plan_cache_miss") == []
    resolved = of(warm, "plan_resolved")
    assert resolved and all(e["source"] == "cache" for e in resolved)
    # and the two processes resolved the SAME plan
    cold_plans = {e["component"]: e["resolved"]
                  for e in of(cold, "plan_resolved")}
    warm_plans = {e["component"]: e["resolved"]
                  for e in of(warm, "plan_resolved")}
    assert cold_plans == warm_plans
