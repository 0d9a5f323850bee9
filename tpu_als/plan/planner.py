"""Cost-model-driven execution planner.

One resolve discipline for every dispatch decision in the stack — solve
backend, NE build path, top-k backend, gather strategy, serving bucket
plan: **the roofline model proposes, a probe confirms, and the verdict
persists.**

Mechanics per component:

- The *plan key* is (device kind, jax version, rank/dtype, shape class,
  mesh shape) — everything a probe verdict can legitimately depend on.
- A warm cache entry (tpu_als.plan.cache) seeds the in-process probe
  registry (tpu_als.utils.platform) with the banked verdicts, so the
  existing probe walks — ``core.als.resolve_solve_path``,
  ``ops.solve.auto_solve_backend``, ``ops.topk`` — run as pure cache
  reads: zero probe executions, and the resolved path is byte-for-byte
  what a cold walk on the same key selects (the walk still computes the
  verdict; the cache only supplies the probe outcomes it would have
  measured).  ``plan_cache_hit`` is emitted, ``plan_probe`` is not —
  the cross-process warm-start test pins exactly that trail.
- A cold resolve emits ``plan_cache_miss``, runs the walk, emits one
  ``plan_probe`` per newly cached kernel verdict plus one for the walk
  itself, and banks the registry snapshot with full provenance (probe
  timings, ``banked_at``, the roofline model's proposal next to the
  probe's verdict).  Transient-failure verdicts are never banked
  (platform.snapshot_probes).
- ``TPU_ALS_PLAN_CACHE=off`` disarms everything: every consult returns
  immediately and the dispatch sites behave exactly as before the
  planner existed (tests pin the training-step jaxpr byte-identical).

Gather strategy is the one component whose verdict is always the
model's, never the bank's: it costs no probe, and in a multi-process
fit every host must reach the same answer even when their caches
disagree — a banked verdict steering collectives would be a
distributed hang waiting to happen.  The cache entry is provenance for
``plan show`` there, not authority.
"""

from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass, field
from datetime import datetime, timezone

from tpu_als import obs
from tpu_als.plan import cache as plan_cache

PlanCacheCorrupt = plan_cache.PlanCacheCorrupt

# auto-tune-on-miss opt-in: with TPU_ALS_AUTOTUNE=1 an armed resolve
# whose entry has no banked kernel config runs the measured-timing
# search (perf.autotune) and banks the winner; anything else keeps the
# hand-picked kernel constants — and with the gate off the dispatch
# sites never even consult the bank, so the training-step jaxpr stays
# byte-identical to the pre-autotune tree (tests pin this the
# plan_cache_off way)
AUTOTUNE_ENV = "TPU_ALS_AUTOTUNE"


def autotune_enabled():
    return os.environ.get(AUTOTUNE_ENV, "") == "1"

# tie-break preference when the comm model scores candidates equal — a
# SUBSET of parallel.trainer.GATHER_STRATEGIES (the authoritative
# table): all_to_all is excluded because its byte model needs built
# A2aCsr plans the planner doesn't have at pick time
GATHER_CANDIDATES = ("all_gather", "all_gather_chunked", "ring_overlap",
                     "ring")


def mode():
    """``"off"`` or the active cache directory."""
    return plan_cache.mode()


def armed():
    return plan_cache.mode() != "off"


def _now():
    return datetime.now(timezone.utc).isoformat(timespec="seconds")


def _device_kind():
    import jax

    try:
        d = jax.devices()[0]
        return f"{d.platform}:{d.device_kind}"
    except RuntimeError:
        return "unknown"


def shape_class(n_users=None, n_items=None, nnz=None):
    """Coarse log2 bucketing so near-identical problem sizes share a plan
    entry; ``"generic"`` when the resolve site has no shapes (the probe
    verdicts themselves key on rank/dtype only)."""
    if n_users is None and n_items is None and nnz is None:
        return "generic"

    def b(x):
        return "?" if not x else f"2^{int(math.log2(max(1, int(x))))}"

    return f"u{b(n_users)}.i{b(n_items)}.nnz{b(nnz)}"


def plan_key(*, rank, dtype, shape_class="generic", mesh_shape=None,
             device_count=None):
    # device_count is its own key component (default: the mesh_shape
    # product) so elastic reformation — same mesh RANK, fewer devices —
    # re-derives the shard plan instead of replaying a stale entry
    if device_count is None and mesh_shape:
        device_count = 1
        for n in mesh_shape:
            device_count *= int(n)
    return {
        "device_kind": _device_kind(),
        "jax_version": plan_cache._jax_version(),
        "rank": int(rank),
        "dtype": str(dtype),
        "shape_class": shape_class,
        "mesh_shape": list(mesh_shape) if mesh_shape else None,
        "device_count": int(device_count) if device_count else None,
    }


def _key_str(key):
    mesh = key.get("mesh_shape")
    dc = key.get("device_count")
    return (f"{key['device_kind']}|jax{key['jax_version']}"
            f"|r{key['rank']}|{key['dtype']}|{key['shape_class']}"
            f"|mesh{'x'.join(map(str, mesh)) if mesh else '-'}"
            f"|D{dc if dc else '-'}")


def _summ(resolved):
    if isinstance(resolved, dict):
        return str(resolved.get("resolved_solve_path", resolved))
    return str(resolved)


def _jsonable(x):
    import json

    return json.loads(json.dumps(x, default=str))


def _load_or_quarantine(key):
    """``(entry_or_None, miss_reason_or_None)`` — a corrupt entry is moved
    to ``.corrupt/`` (never crashed on, never trusted) and reads as a
    miss with reason ``"corrupt"`` so the walk reprobes."""
    try:
        return plan_cache.load_entry(key), None
    except PlanCacheCorrupt as e:
        qpath = plan_cache.quarantine(e.path, e.reason)
        obs.emit("warning", what="plan_cache",
                 reason=f"quarantined corrupt entry to {qpath}: {e.reason}")
        return None, "corrupt"


def _resolve_component(key, component, walk, *, model=None,
                       use_banked=False):
    """The shared resolve discipline.  On a cache hit the banked probe
    verdicts are seeded and ``walk()`` re-derives the plan from them
    (``use_banked=True`` trusts the banked resolved value instead —
    only for configuration-like components such as the bucket ladder).
    On a miss the walk runs cold, its probe spend is emitted, and the
    verdict + registry snapshot are banked with provenance."""
    from tpu_als.utils import platform

    entry, reason = _load_or_quarantine(key)
    if entry is not None and component in entry["components"]:
        seeded = platform.seed_probes(entry.get("probes") or {})
        obs.emit("plan_cache_hit", key=_key_str(key), component=component,
                 path=plan_cache.entry_path(key), seeded=seeded)
        resolved = (entry["components"][component]["resolved"]
                    if use_banked else walk())
        obs.emit("plan_resolved", key=_key_str(key), component=component,
                 source="cache", resolved=_summ(resolved))
        return resolved

    obs.emit("plan_cache_miss", key=_key_str(key), component=component,
             reason=(reason or "absent") if entry is None
             else "component_absent")
    before = {n: set(c) for n, c in platform.probe_caches().items()}
    t0 = time.perf_counter()
    resolved = walk()
    walk_s = time.perf_counter() - t0
    executed = []
    for name, c in platform.probe_caches().items():
        for k in c:
            if k in before.get(name, ()):
                continue
            m = c.meta.get(k, {})
            obs.emit("plan_probe", kernel=f"{name}:{k!r}",
                     outcome=bool(c[k]), seconds=m.get("seconds") or 0.0)
            executed.append(f"{name}:{k!r}")
    obs.emit("plan_probe", kernel=f"walk:{component}",
             outcome=_summ(resolved), seconds=walk_s)

    if entry is None:
        entry = {"schema_version": plan_cache.SCHEMA_VERSION,
                 "plan_key": key, "probes": {}, "components": {}}
    for name, outcomes in platform.snapshot_probes().items():
        entry["probes"].setdefault(name, {}).update(outcomes)
    entry["components"][component] = {
        "resolved": _jsonable(resolved),
        "provenance": {
            "banked_at": _now(),
            "walk_seconds": round(walk_s, 6),
            "probes_executed": executed,
            "probe_timings": _jsonable(platform.probe_timings()),
            "model": _jsonable(model) if model is not None else None,
        },
    }
    try:
        plan_cache.store_entry(key, entry)
    except OSError as e:
        obs.emit("warning", what="plan_cache",
                 reason=f"could not bank plan entry: {e}")
    obs.emit("plan_resolved", key=_key_str(key), component=component,
             source="probe", resolved=_summ(resolved))
    return resolved


# -- component resolvers (one per dispatch site) ------------------------


def resolve_training(*, rank, compute_dtype, label, walk):
    """Consulted by ``core.als.resolve_solve_path`` when armed.  ``walk``
    is the legacy probe walk (``_resolve_solve_path_walk``); its return
    dict is the verdict, warm or cold."""
    if not armed():
        return None
    key = plan_key(rank=rank, dtype=compute_dtype)
    return _resolve_component(key, f"training:{label}", walk,
                              model=training_model(rank, compute_dtype))


def training_model(rank, compute_dtype):
    """The roofline proposal for the training resolve: modeled NE-build
    HBM bytes of the gather-fused kernel vs the einsum build at the
    timing probe's shapes (perf.roofline closed forms), plus the solve
    preference ladder.  The probe walk confirms or overrules — both are
    banked so ``plan show`` can display prediction vs measured."""
    import importlib

    # perf.__init__ rebinds the package attribute 'roofline' to the
    # function, so attribute-style module imports resolve wrong here
    rl = importlib.import_module("tpu_als.perf.roofline")

    db = 2 if "bfloat16" in str(compute_dtype) else 4
    n, w = 2048, 256                 # faster_than_einsum's probe instance
    P = n * w
    fused = rl.fused_ne_kernel_bytes(P, n, rank, db)
    einsum = rl.einsum_ne_build_bytes(P, n, rank, db)
    return {
        "ne_bytes": {"gather_fused": fused, "einsum": einsum},
        "ne_proposal": "gather_fused" if fused < einsum else "einsum",
        "solve_preference": (["lanes"] if rank <= 128
                             else ["lanes_blocked"]) + ["pallas", "xla"],
    }


def resolve_topk(*, rank, k, walk):
    """Consulted by ``ops.topk.topk_scores`` (eager 'auto' dispatch) and
    by ``plan warm``; ``walk`` is ``ops.topk.auto_topk_backend``."""
    if not armed():
        return None
    key = plan_key(rank=rank, dtype="float32")
    model = {"proposal": "pallas" if int(k) <= 128 else "xla",
             "reason": "pallas top-k holds k<=128 in lanes; larger k "
                       "falls back to the chunked XLA path"}
    return _resolve_component(key, f"topk:k={int(k)}", walk, model=model)


def gather_model(*, n_users, n_items, rank, n_devices, implicit=False):
    """Closed-form per-device collective bytes for one full ALS iteration
    per candidate strategy (the balanced-shard, one-row-tile case of
    ``parallel.trainer.comm_bytes_per_iter``) and the ranked proposal."""
    D = max(1, int(n_devices))
    fb = 4 * int(rank)
    ru = -(-int(n_users) // D)
    ri = -(-int(n_items) // D)
    ag = (D - 1) * ri * fb + (D - 1) * ru * fb
    ring = D * ri * fb + D * ru * fb
    psum = 4 * (D - 1) / D * rank * rank * 4 if implicit else 0
    by = {"all_gather": ag + psum, "all_gather_chunked": ag + psum,
          "ring_overlap": ring + psum, "ring": ring + psum}
    proposal = min(GATHER_CANDIDATES, key=lambda s: by[s])
    return {"comm_bytes_per_iter": by, "proposal": proposal,
            "n_devices": D}


def resolve_gather_strategy(*, requested="auto", n_users, n_items, rank,
                            n_devices, implicit=False):
    """An explicit strategy passes through untouched.  ``"auto"`` is the
    comm model's pick — deterministic across hosts by construction (see
    module docstring: the bank is provenance here, never authority)."""
    if requested != "auto":
        return requested
    model = gather_model(n_users=n_users, n_items=n_items, rank=rank,
                         n_devices=n_devices, implicit=implicit)
    choice = model["proposal"]
    if armed():
        key = plan_key(
            rank=rank, dtype="float32",
            shape_class=shape_class(n_users=n_users, n_items=n_items),
            mesh_shape=(n_devices,))
        _resolve_component(key, f"gather:D={int(n_devices)}",
                           walk=lambda: choice, model=model)
    return choice


def _ladder_from_observed(observed):
    """Pow2-rounded quantile ladder from an observed request-size mix.

    One bucket per {p50, p90, p99, max} of the observed batch sizes,
    each rounded UP to the next power of two (one pinned executable per
    rung, pad waste bounded by 2x at every quantile the traffic
    actually hits).  Returns None when there is nothing to learn from.
    """
    from tpu_als.core.ratings import _next_pow2

    xs = sorted(int(s) for s in observed if int(s) > 0)
    if not xs:
        return None
    rungs = {int(_next_pow2(xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]))
             for q in (0.50, 0.90, 0.99, 1.0)}
    return tuple(sorted(rungs))


def resolve_serving_buckets(*, rank=0, requested=None, observed=None):
    """Serving batch-bucket ladder.  Explicit buckets pass through;
    ``observed`` (a sequence of served batch sizes, e.g. drained from
    the ``serving.batch_rows`` histogram after a bench run) derives a
    pow2 quantile ladder and re-banks it so later default resolutions
    inherit the measured mix; the bare default consults the bank (a
    previously recorded ladder wins) and falls back to
    ``serving.batcher.DEFAULT_BUCKETS``."""
    from tpu_als.serving.batcher import DEFAULT_BUCKETS

    if requested is not None:
        return tuple(int(b) for b in requested)
    if observed is not None:
        ladder = _ladder_from_observed(observed) or tuple(DEFAULT_BUCKETS)
        if armed():
            key = plan_key(rank=int(rank or 0), dtype="float32")
            entry, _ = _load_or_quarantine(key)
            if entry is None:
                entry = {"schema_version": plan_cache.SCHEMA_VERSION,
                         "plan_key": key, "probes": {}, "components": {}}
            entry["components"]["serving_buckets"] = {
                "resolved": [int(b) for b in ladder],
                "provenance": {
                    "banked_at": _now(),
                    "walk_seconds": 0.0,
                    "probes_executed": [],
                    "probe_timings": {},
                    "model": {"observed_n": len(list(observed)),
                              "reason": "pow2 quantile ladder "
                                        "(p50/p90/p99/max) from the "
                                        "observed request-size mix"},
                },
            }
            try:
                plan_cache.store_entry(key, entry)
            except OSError as e:
                obs.emit("warning", what="plan_cache",
                         reason=f"could not bank observed ladder: {e}")
            obs.emit("plan_resolved", key=_key_str(key),
                     component="serving_buckets", source="observed",
                     resolved=_summ(list(ladder)))
        return ladder
    if not armed():
        return tuple(DEFAULT_BUCKETS)
    key = plan_key(rank=int(rank or 0), dtype="float32")
    model = {"proposal": list(DEFAULT_BUCKETS),
             "reason": "geometric ladder bounds pad waste to ~4x worst "
                       "case while keeping one executable per bucket "
                       "(docs/serving.md)"}
    resolved = _resolve_component(key, "serving_buckets",
                                  walk=lambda: list(DEFAULT_BUCKETS),
                                  model=model, use_banked=True)
    return tuple(int(b) for b in resolved)


def resolve_kernel_config(*, rank, compute_dtype="float32", budget_s=None,
                          space=None, force=False, tune=None, timer=None,
                          n=256, w=64, k=3, seed=0):
    """The measured-timing autotune component (``"kernel_config"``):
    the fused-solve kernel knobs (panel / vmem_budget / max_wc / pump
    depth / factor-table dtype) resolved through the plan cache.

    Warm path: a banked, non-invalidated config returns as a pure cache
    read — ``plan_cache_hit`` + ``plan_resolved(source="cache")``, ZERO
    tuning executions (autotune_smoke pins the trail).  Cold path: only
    when tuning is requested (``tune=True``, the ``plan tune`` CLI, or
    the ``TPU_ALS_AUTOTUNE=1`` auto-tune-on-miss gate) the search runs
    (``perf.autotune.tune``), the winner is banked with measured-vs-
    modeled provenance, and ``plan_tuned`` +
    ``plan_resolved(source="measured")`` are emitted.  Returns None —
    "keep the hand-picked constants" — when disarmed, or when nothing
    is banked and tuning was not requested.

    The never-override rule: an ``interpret``-sourced verdict (CPU
    interpreter timings) never replaces a banked ``device`` (on-chip)
    measurement — the fresh result is discarded with a warning and the
    banked config stands, even under ``force``.
    """
    if not armed():
        return None
    if tune is None:
        tune = autotune_enabled()
    key = plan_key(rank=int(rank), dtype=str(compute_dtype))
    entry, _ = _load_or_quarantine(key)
    comp = (entry or {}).get("components", {}).get("kernel_config")
    prov = (comp or {}).get("provenance") or {}
    if comp is not None and not prov.get("invalidated") and not force:
        obs.emit("plan_cache_hit", key=_key_str(key),
                 component="kernel_config",
                 path=plan_cache.entry_path(key), seeded=0)
        obs.emit("plan_resolved", key=_key_str(key),
                 component="kernel_config", source="cache",
                 resolved=_summ(comp["resolved"]))
        return dict(comp["resolved"])
    if not tune:
        return dict(comp["resolved"]) if comp is not None \
            and not prov.get("invalidated") else None

    from tpu_als.perf import autotune

    obs.emit("plan_cache_miss", key=_key_str(key),
             component="kernel_config",
             reason="invalidated" if prov.get("invalidated")
             else ("forced" if (force and comp is not None)
                   else ("component_absent" if entry is not None
                         else "absent")))
    kwargs = dict(rank=int(rank), compute_dtype=str(compute_dtype),
                  space=space, timer=timer, n=n, w=w, k=k, seed=seed)
    if budget_s is not None:
        kwargs["budget_s"] = float(budget_s)
    verdict = autotune.tune(**kwargs)
    if prov.get("source") == "device" and verdict["source"] == "interpret":
        obs.emit("warning", what="plan_cache",
                 reason="interpret-mode autotune verdict discarded — the "
                        "banked on-chip kernel config stands "
                        "(never-override rule)")
        return dict(comp["resolved"])
    if entry is None:
        entry = {"schema_version": plan_cache.SCHEMA_VERSION,
                 "plan_key": key, "probes": {}, "components": {}}
    ratio = (verdict["measured_seconds"] / verdict["model_seconds"]
             if verdict["model_seconds"] else None)
    entry["components"]["kernel_config"] = {
        "resolved": _jsonable(verdict["config"]),
        "provenance": {
            "banked_at": _now(),
            "source": verdict["source"],
            "measured_seconds": verdict["measured_seconds"],
            "model_seconds": verdict["model_seconds"],
            "default_seconds": verdict["default_seconds"],
            "ratio": ratio,
            "tune_seconds": round(verdict["tune_seconds"], 6),
            "trials": len(verdict["trials"]),
            "walk_seconds": round(verdict["tune_seconds"], 6),
            "probes_executed": [],
            "model": {"shape": verdict["shape"],
                      "reason": "one-at-a-time measured search over "
                                "perf.autotune.SPACE; model_seconds is "
                                "the fused_solve_kernel_bytes closed "
                                "form at the winning config's padded "
                                "shapes"},
        },
    }
    try:
        plan_cache.store_entry(key, entry)
    except OSError as e:
        obs.emit("warning", what="plan_cache",
                 reason=f"could not bank tuned kernel config: {e}")
    obs.emit("plan_tuned", key=_key_str(key), component="kernel_config",
             source=verdict["source"], config=_jsonable(verdict["config"]),
             measured_seconds=verdict["measured_seconds"],
             model_seconds=verdict["model_seconds"])
    obs.emit("plan_resolved", key=_key_str(key), component="kernel_config",
             source="measured", resolved=_summ(verdict["config"]))
    return dict(verdict["config"])


def invalidate_kernel_config(*, rank, compute_dtype="float32",
                             reason="drift"):
    """The re-plan trigger: mark the banked kernel config stale (the
    measured/modeled ratio left its band — ``observe regress --trend``
    or the attribution gap table) so the next armed resolve re-tunes
    instead of riding it.  Returns True when an entry was invalidated."""
    if not armed():
        return False
    key = plan_key(rank=int(rank), dtype=str(compute_dtype))
    entry, _ = _load_or_quarantine(key)
    comp = (entry or {}).get("components", {}).get("kernel_config")
    if comp is None:
        return False
    prov = comp.setdefault("provenance", {})
    if prov.get("invalidated"):
        return False
    prov["invalidated"] = {"at": _now(), "reason": str(reason)}
    try:
        plan_cache.store_entry(key, entry)
    except OSError as e:
        obs.emit("warning", what="plan_cache",
                 reason=f"could not mark kernel config stale: {e}")
        return False
    obs.emit("warning", what="plan_cache",
             reason=f"kernel config invalidated ({reason}) — next armed "
                    "resolve re-tunes")
    return True


# live-pipeline cadence: micro-batch accumulation + index compaction.
# The batch bounds are the measured sweet spot on CPU (fold-in p50 82 ms
# amortizes over ~256 events).  The compaction threshold was a quarter
# of the catalog while a compaction copied the catalog on the device;
# since it scatters the segment into donated base arrays (PR 34) it
# costs what the segment holds, and what is left to weigh is what every
# BATCH pays for the segment's slots (a second int8 GEMM, the override
# mask, a longer shortlist): an 8,192nd of the catalog — 184 rows at
# 1.5 M items, 512 slots with one max_batch of room — keeps that within
# noise of the base kernel on a v5e (PERF.md section 6, PR 34).
DEFAULT_LIVE_CADENCE = {
    "max_batch": 256,
    "max_wait_ms": 50.0,
    "compact_delta_frac": 2.0 ** -13,
    "compact_min_rows": 64,
}


def resolve_live_cadence(*, rank=0, requested=None):
    """Live fold-in → publish cadence: micro-batch bounds for the
    updater and the compaction threshold for the delta index.  Explicit
    cadence passes through; the default consults the bank (a recorded
    cadence for this device/rank wins) and falls back to
    ``DEFAULT_LIVE_CADENCE``."""
    if requested is not None:
        out = dict(DEFAULT_LIVE_CADENCE)
        out.update(requested)
    elif not armed():
        out = dict(DEFAULT_LIVE_CADENCE)
    else:
        key = plan_key(rank=int(rank or 0), dtype="float32")
        model = {"proposal": dict(DEFAULT_LIVE_CADENCE),
                 "reason": "accumulate ~max_batch events or max_wait_ms "
                           "(whichever first) per fold-in; compact the "
                           "delta segment past max(compact_min_rows, "
                           "compact_delta_frac * catalog) "
                           "(docs/serving.md)"}
        out = dict(_resolve_component(key, "live_cadence",
                                      walk=lambda: dict(
                                          DEFAULT_LIVE_CADENCE),
                                      model=model, use_banked=True))
    return {"max_batch": int(out["max_batch"]),
            "max_wait_ms": float(out["max_wait_ms"]),
            "compact_delta_frac": float(out["compact_delta_frac"]),
            "compact_min_rows": int(out["compact_min_rows"])}


def resolve_tenant_plan(*, rank, n_users=None, n_items=None,
                        requested_buckets=None, requested_cadence=None):
    """Per-tenant execution plan for the multi-tenant control plane:
    the serving bucket ladder + live cadence this tenant's engine and
    updater run with, plus the tenant's ``shape_class``.

    The bucket/cadence components key on (device, jax, rank, dtype) —
    deliberately NOT on the tenant's name — so every same-shaped tenant
    resolves to the SAME plan entry (one probe walk total, zero for
    warm caches) and, with equal buckets/rank/catalog shape-class,
    shares the process-global compiled scoring executables.  That
    compile sharing is what makes N tenants on one mesh cheaper than N
    processes (docs/tenancy.md).
    """
    sc = shape_class(n_users=n_users, n_items=n_items)
    return {
        "shape_class": sc,
        "buckets": resolve_serving_buckets(rank=rank,
                                           requested=requested_buckets),
        "cadence": resolve_live_cadence(rank=rank,
                                        requested=requested_cadence),
    }


def clear():
    """Drop the on-disk entries AND the in-process probe registry (the
    ``plan clear`` CLI verb).  Returns the number of files removed."""
    from tpu_als.utils import platform

    n = plan_cache.clear()
    platform.clear_probe_caches()
    return n


# -- whole-plan assembly (CLI `plan warm` / `plan show`) ----------------


@dataclass(frozen=True)
class ExecutionPlan:
    """Everything the planner decides, assembled in one place."""

    key: dict
    solve: dict | None                # resolve_solve_path verdict dict
    topk_backend: str | None
    gather_strategy: str | None
    serving_buckets: tuple
    notes: dict = field(default_factory=dict)
    kernel_config: dict | None = None  # tuned knobs (None = hand-picked)

    def summary(self):
        return {
            "key": _key_str(self.key),
            "resolved_solve_path": (self.solve or {}).get(
                "resolved_solve_path"),
            "topk_backend": self.topk_backend,
            "gather_strategy": self.gather_strategy,
            "serving_buckets": list(self.serving_buckets),
            "kernel_config": self.kernel_config,
        }


def resolve_execution_plan(*, rank=128, compute_dtype="float32",
                           solve_backend="auto", cg_iters=0,
                           cg_mode="dense", nonnegative=False, k=10,
                           n_users=None, n_items=None, n_devices=1):
    """Resolve the full plan for one configuration — the ``plan warm``
    entry point.  Every component goes through its real dispatch-site
    walk (``resolve_solve_path`` consults the planner itself), so
    warming here is exactly the resolve training/serving will perform."""
    from tpu_als.core.als import AlsConfig, resolve_solve_path
    from tpu_als.ops.topk import auto_topk_backend

    cfg = AlsConfig(rank=int(rank), solve_backend=solve_backend,
                    cg_iters=int(cg_iters), cg_mode=cg_mode,
                    nonnegative=bool(nonnegative),
                    compute_dtype=compute_dtype)
    solve = resolve_solve_path(cfg, int(rank))
    if armed():
        topk = resolve_topk(rank=int(rank), k=int(k),
                            walk=lambda: auto_topk_backend(int(rank),
                                                           int(k)))
    else:
        topk = auto_topk_backend(int(rank), int(k))
    gather = None
    if n_devices and int(n_devices) > 1 and n_users and n_items:
        gather = resolve_gather_strategy(
            requested="auto", n_users=int(n_users), n_items=int(n_items),
            rank=int(rank), n_devices=int(n_devices))
    buckets = resolve_serving_buckets(rank=int(rank))
    # warm read always when armed; the measured search itself only runs
    # behind the TPU_ALS_AUTOTUNE=1 opt-in (resolve_kernel_config)
    kcfg = (resolve_kernel_config(rank=int(rank),
                                  compute_dtype=compute_dtype)
            if armed() else None)
    return ExecutionPlan(
        key=plan_key(rank=int(rank), dtype=compute_dtype),
        solve=solve, topk_backend=topk, gather_strategy=gather,
        serving_buckets=buckets,
        notes={"mode": mode()},
        kernel_config=kcfg)
