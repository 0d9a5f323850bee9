#!/usr/bin/env python
"""Headline benchmark: ALS iterations/sec @ rank=128, MovieLens-25M scale,
implicit feedback (alpha=40) — BASELINE.json config 2 on one TPU core.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "iters/sec", "vs_baseline": N, ...}

``vs_baseline`` caveat (documented in BASELINE.md): the reference publishes
no numbers and Spark cannot run in this environment, so the baseline is the
north-star's comparison point — 8-executor Spark ALS on ML-25M at rank=128 —
taken as 60 s/iteration (0.0167 iters/sec), a deliberately conservative
figure for a well-tuned 8-executor cluster on a ~25M-rating, rank-128
problem (Spark shuffles the factor messages twice per iteration and solves
per-row with LAPACK dppsv).  The north-star bar is >=20x.

Device: a run measures the device JAX finds and names it in its JSON
(``device``: platform, device_kind, count).  Without a TPU and without
``--platform cpu`` the script exits non-zero and prints no number; a
failure inside a run is a traceback and a non-zero exit, never a value
from an earlier run.

Usage:
  python bench.py [--small] [--iters N]        # headline iters/sec
  python bench.py --mode rmse [--small]        # held-out RMSE (explicit ALS)
"""

import argparse
import datetime as _dt
import json
import os
import sys
import time


SPARK_8EXEC_ITERS_PER_SEC = 1.0 / 60.0  # documented proxy, see module doc


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def device_info():
    """The device this run measures, in the device's own words."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}


def mfu_pct_vs_bf16_peak(flops_per_sec, n_chips=1):
    """Advisory utilisation against the bf16 peak of the device that is
    there (tpu_als.perf.roofline.DEVICE_PEAKS — an unknown accelerator is
    an error, not a v5e; a CPU smoke run gets None)."""
    import jax

    from tpu_als.perf.roofline import device_peaks

    d = jax.devices()[0]
    if d.platform == "cpu":     # --platform cpu: no device metric at all
        return None
    peak = device_peaks(d.device_kind)["bf16_flops"]
    return round(100.0 * flops_per_sec / (n_chips * peak), 2)


# headline sweep step -> the flag overrides it measured
_SWEEP_FLAGS = {
    "headline_f32": {},
    "headline_bf16": {"compute_dtype": "bfloat16"},
    "headline_wg15": {"width_growth": 1.5},
    "headline_bf16_wg15": {"compute_dtype": "bfloat16",
                           "width_growth": 1.5},
    "headline_cg2": {"cg_iters": 2},
    "headline_cg3": {"cg_iters": 3},
    "headline_cg2_dense": {"cg_iters": 2, "cg_mode": "dense"},
    "headline_cg2_bf16": {"cg_iters": 2, "compute_dtype": "bfloat16"},
    # overlapped comm/compute step variants (ISSUE 2): measured through
    # the sharded step even on one core (all visible devices) — on a
    # single chip this prices the restructured step body (the overlap
    # benefit itself needs a pod, where the collective is nonzero).
    # Not auto-selectable: the blockwise/streamed accumulation's f32
    # reduction order differs from the exact reference path.
    "headline_ringdb": {"gather_strategy": "ring_overlap"},
    "headline_agchunk": {"gather_strategy": "all_gather_chunked"},
    # DMA-gather fused NE build (ops/pallas_gather_ne): forces the
    # kernel so the sweep measures it even where the in-process timing
    # probe would keep auto on einsum.  Not auto-selectable here: wide
    # multi-chunk buckets accumulate in a different f32 order than the
    # exact path (same bar as ringdb/agchunk) — production selection is
    # the in-process faster_than_einsum probe, which also revalidates
    # numerics on-device.
    "headline_gather": {"solve_backend": "gather_fused"},
    # whole-iteration fusion (gather -> Gram -> in-VMEM Cholesky solve,
    # ops/pallas_gather_ne.gather_solve): forced for the same reason —
    # the sweep banks its number even where the in-process
    # solve_faster_than_unfused probe would keep auto on the shallower
    # path
    "headline_gather_solve": {"solve_backend": "gather_fused_solve"},
    # the queued bf16-before-gather A/B: the upcast-solve-downcast gate
    # in ops/solve.py (PR 8) keeps the factorization at f32, so the only
    # delta is the gathered-stream bytes — halved
    "headline_gather_bf16": {"solve_backend": "gather_fused",
                             "compute_dtype": "bfloat16"},
    # fused-COMM ring (PR 15): the shard rotation rides the kernel's own
    # remote-DMA ring (solve_backend='gather_fused_ring') instead of an
    # XLA-level ppermute around it.  Measured through the sharded ring
    # step over all visible devices, like ringdb; on one chip this
    # prices the restructured kernel, on a pod the true in-kernel
    # overlap.  Not auto-selectable (same bar as ringdb/gather: the ring
    # accumulates shard Grams in rotation order — a different f32
    # association than the exact reference path).
    "headline_ring_fused": {"gather_strategy": "ring",
                            "solve_backend": "gather_fused_ring"},
}
# quality gate for auto-selection: held-out RMSE (stars) the matching
# rmse evidence must beat.  The known-good band is ~0.43 (BASELINE row
# 2); 0.50 rejects anything that regressed quality materially.
_RMSE_GATE = 0.50

# configs eligible for auto-selection, mapped to the sweep QUALITY step
# that must validate them (None = quality-neutral: f32 exact is the
# reference config, and the width ladder changes padding only — masked
# rows, numerics-identical).  Anything not listed (cg3, cg2_dense) has
# no matching quality step and never auto-selects.
_AUTO_SELECTABLE = {
    "headline_f32": None,
    "headline_wg15": None,
    "headline_cg2": "rmse_cg2",
    "headline_bf16": "rmse_bf16",
    "headline_bf16_wg15": "rmse_bf16",
    "headline_cg2_bf16": "rmse_cg2_bf16",
}


def _last_json(path):
    try:
        with open(path) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return None
    for ln in reversed(lines):
        if ln.startswith("{"):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
    return None


def best_measured_flags(sweep_dir="sweep_logs"):
    """Flag overrides of the fastest VALIDATED headline config in a
    finished sweep — or None when no evidence exists.

    The driver's end-of-round capture runs ``python bench.py`` with
    default flags; when the opportunistic sweep (scripts/sweep_tpu.sh)
    already measured a faster configuration on THIS chip, defaulting to
    the conservative exact path would throw that evidence away.
    Selection is evidence-bound per config (_AUTO_SELECTABLE): a
    candidate counts only if its sweep step produced a value, and any
    numerics-changing winner (cg and/or bf16) additionally requires ITS
    matching rmse step to exist and beat the gate — a fastest-but-
    unvalidated winner keeps the defaults rather than silently demoting
    to a slower validated config.  Explicit user flags always win —
    callers only consult this when every relevant flag is at its
    default.
    """
    import os

    best_name, best_val = None, 0.0
    for name in _AUTO_SELECTABLE:
        j = _last_json(os.path.join(sweep_dir, name + ".out"))
        if j and j.get("value"):
            if j["value"] > best_val:
                best_name, best_val = name, j["value"]
    if best_name is None:
        return None
    flags = dict(_SWEEP_FLAGS[best_name])
    if not _quality_validated(best_name, sweep_dir):
        log(f"sweep winner {best_name} lacks quality evidence "
            f"({_AUTO_SELECTABLE[best_name]} missing or > {_RMSE_GATE}); "
            "keeping defaults")
        return None
    log(f"auto-selected sweep-validated config {best_name} "
        f"({best_val} iters/sec measured): {flags}")
    return flags


def _quality_validated(name, sweep_dir):
    """The single evidence bar shared by auto-selection AND the
    provenance block: a numerics-changing headline config counts only if
    its matching rmse sweep step exists and beats the gate."""
    import os

    quality_step = _AUTO_SELECTABLE[name]
    if quality_step is None:
        return True
    q = _last_json(os.path.join(sweep_dir, quality_step + ".out"))
    return bool(q and q.get("value") and q["value"] <= _RMSE_GATE)


def synthetic_cached(nU, nI, nnz, seed=0):
    """(u, i, r) triples of ``synthetic_movielens``, memoized to disk.

    Every mode re-synthesizes the full ML-25M-scale dataset (tens of
    seconds).  The cache key is the full parameter tuple; the
    generator is deterministic per seed, so the cache is exact.  Falls
    back to direct synthesis on any IO problem.
    """
    import os

    import numpy as np

    from tpu_als.io.movielens import synthetic_movielens

    cache = os.path.join(".bench_cache", f"synth_{nU}_{nI}_{nnz}_{seed}.npz")
    try:
        d = np.load(cache, allow_pickle=False)
        log(f"synthetic triples from cache ({cache})")
        return d["u"], d["i"], d["r"]
    except Exception:
        pass
    frame = synthetic_movielens(nU, nI, nnz, seed=seed)
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    r = np.asarray(frame["rating"])
    try:
        os.makedirs(".bench_cache", exist_ok=True)
        # tmp must END in .npz or np.savez appends the suffix itself
        tmp = cache + f".{os.getpid()}.tmp.npz"
        np.savez(tmp, u=u, i=i, r=r)
        os.replace(tmp, cache)
    except Exception as e:
        log(f"synthetic cache write skipped: {e}")
    return u, i, r


def analytic_flops_per_iter(nnz, n_users, n_items, rank, implicit):
    """Useful (unpadded) FLOPs in one full ALS iteration.

    Per half-step: normal-equation build = 2·nnz·r² (the nwr,nws->nrs
    contraction) + 2·nnz·r (rhs); solves = r³/3 MACs ≈ 2r³/3 FLOPs per
    entity + 2·2r² substitution; implicit adds one YᵀY (2·N·r²) per side.
    Matches the roofline arithmetic in VERDICT.md (round 1, Weak #2).
    """
    r = rank
    ne = 2 * (2 * nnz * r * r + 2 * nnz * r)          # both half-steps
    solves = (n_users + n_items) * (2 * r ** 3 / 3 + 4 * r * r)
    yty = 2 * (2 * (n_users + n_items) * r * r) if implicit else 0
    return float(ne + solves + yty)


def _ab_specs(args, allow_wg=True, allow_strategy=True):
    """Parse ``--ab`` into (spec, flag-override) pairs.

    Specs are the suffixes of the canonical sweep step names ('exact' =
    the default f32 exact path), so one combined run writes evidence the
    name-keyed selection machinery (best_measured_flags) already
    understands.  ``allow_wg=False``
    rejects width-growth specs for modes whose measure() cannot rebuild
    the blocked containers — banking a default-ladder run under a wg15
    name would be fabricated evidence."""
    out = []
    for spec in [s for s in (args.ab or "").split(",") if s]:
        name = _canonical_name("headline", spec)
        if name not in _SWEEP_FLAGS:
            raise SystemExit(f"unknown --ab spec {spec!r} "
                             f"(known: exact, "
                             f"{', '.join(k[len('headline_'):] for k in _SWEEP_FLAGS if k != 'headline_f32')})")
        overrides = _SWEEP_FLAGS[name]
        if not allow_wg and "width_growth" in overrides:
            raise SystemExit(f"--ab spec {spec!r} changes width_growth, "
                             "which this mode measures only at its "
                             "--width-growth flag; run it as a separate "
                             "step instead")
        if not allow_strategy and "gather_strategy" in overrides:
            raise SystemExit(f"--ab spec {spec!r} selects a sharded "
                             "gather strategy; only headline mode has the "
                             "sharded measurement path — banking it here "
                             "would mislabel a default-path run")
        out.append((spec, overrides))
    return out


def _canonical_name(mode, spec):
    """The sweep-step name a variant's evidence is filed under — shared by
    spec parsing and banking so the two can never disagree about where
    auto-selection will look."""
    if mode == "headline":
        return "headline_f32" if spec == "exact" else f"headline_{spec}"
    return "rmse" if spec == "exact" else f"rmse_{spec}"


def _ab_log_path(mode, spec, ab_dir):
    """Canonical evidence file for a variant: the SAME path the separate
    sweep step for this config would have written."""
    return os.path.join(ab_dir, _canonical_name(mode, spec) + ".out")


# the flags a banked variant's canonical name encodes; when --ab-dir is
# set, every one of these must sit at its canonical value so the ONLY
# thing distinguishing variants is the spec name itself.  Model/scale
# flags (rank, iteration counts, reg) are guarded too: a rank-64 or
# 3-iter run banked under headline_cg2 would read as full-scale rank-128
# evidence downstream — the exact mislabeling this check exists to stop.
# Canonical values follow scripts/sweep_resume.sh's step commands, not
# argparse defaults (the sweep runs --iters 5 / --iters-rmse 12).
_AB_BASE_DEFAULTS = {"cg_iters": 0, "cg_mode": "matfree",
                     "compute_dtype": "float32", "width_growth": 2.0,
                     "solve_backend": "auto", "rank": 128}
_AB_MODE_DEFAULTS = {"headline": {"iters": 5},
                     "rmse": {"iters_rmse": 12, "reg": 0.02}}


def _check_ab_bankable(args, mode):
    """Banked evidence is keyed purely by spec name; a non-default base
    flag would leak into every non-overridden variant and file a
    measurement under a name that promises a different config (the
    advisor's 'fabricated evidence' case).  Refuse up front.

    --small runs are exempt: _bank_variant never banks them, so no
    mislabeled evidence is possible and a smoke run may use any
    rank/iteration scale it likes."""
    if not args.ab_dir or getattr(args, "small", False):
        return
    required = {**_AB_BASE_DEFAULTS, **_AB_MODE_DEFAULTS.get(mode, {})}
    off = {k: getattr(args, k, v) for k, v in required.items()
           if getattr(args, k, v) != v}
    if off:
        raise SystemExit(
            f"--ab-dir banking requires canonical base flags; these are "
            f"off-canonical: {off}.  Encode the config as an --ab spec "
            "instead (e.g. cg2_bf16), or drop --ab-dir.")


def _bank_variant(mode, spec, ab_dir, result, metric, small=False):
    """Append a variant's JSON line to its canonical sweep log the moment
    it finishes — a failure later in the A/B run must not cost the
    variants already measured.  Errors are NOT banked (_last_json reads
    the last line; a null would mask earlier good evidence), and neither
    are --small runs (canonical logs carry full-scale evidence only —
    a smoke number must never win auto-selection)."""
    if not ab_dir or small or result.get("value") is None:
        return
    path = _ab_log_path(mode, spec, ab_dir)
    os.makedirs(ab_dir, exist_ok=True)
    with open(path, "a") as f:
        # absolute bank-time stamp: a banked line outlives the run that
        # wrote it, so it must never be a relative phrase
        f.write(json.dumps({
            **result, "metric": metric,
            "banked_by": f"{mode} --ab",
            "banked_at": _dt.datetime.now(
                _dt.timezone.utc).isoformat(timespec="seconds"),
        }) + "\n")
    log(f"banked {spec} -> {path}")


def _already_banked(mode, spec, ab_dir):
    """A previous run — a partially-failed A/B retry OR a dedicated sweep
    step for the same config — already banked this variant in its
    canonical log; a retry should spend its time only on the
    missing ones.  Small-scale smoke lines never count (their metric
    carries the ``_small`` suffix), and neither does a line whose
    recorded config contradicts the canonical one the file name promises
    (a stale or mislabeled bank must not short-circuit a real retry)."""
    if not ab_dir:
        return None
    j = _last_json(_ab_log_path(mode, spec, ab_dir))
    ok = (j and j.get("value") is not None and not j.get("error")
          and not str(j.get("metric", "")).endswith("_small"))
    if not ok:
        return None
    from tpu_als.io.movielens import ML25M_SHAPE

    cfg = j.get("config", {}) or {}
    canonical = {"rank": _AB_BASE_DEFAULTS["rank"],
                 "users": ML25M_SHAPE[0], "items": ML25M_SHAPE[1]}
    if mode == "rmse":
        # the rmse config block records its iteration count and reg
        # under these keys; a short-iteration or off-reg line must not
        # stand in for the canonical 12-iter quality gate
        canonical.update(iters=_AB_MODE_DEFAULTS["rmse"]["iters_rmse"],
                         reg_param=_AB_MODE_DEFAULTS["rmse"]["reg"])
    mismatch = {k: cfg[k] for k, v in canonical.items()
                if cfg.get(k) is not None and cfg[k] != v}
    if mismatch:
        log(f"banked {spec} line ignored: config mismatch {mismatch}")
        return None
    return j


def _run_ab(specs, measure, mode, metric, args, summary_key):
    """The shared A/B driver: measure each spec (skipping ones a prior
    run banked), bank each success immediately, and return the primary
    result.  If ANY variant failed, the primary carries an ``error``
    field: the sweep runner's done-check then retries the step instead of
    silently parking the lost variants (the banked ones are skipped on
    that retry, so a flap costs only the missing measurements)."""
    _check_ab_bankable(args, mode)
    primary, ab, failed = None, {}, []
    for spec, overrides in specs:
        # a --small smoke must actually RUN its variants — full-scale
        # prior evidence is not a substitute for the code path
        prior = (None if args.small
                 else _already_banked(mode, spec, args.ab_dir))
        if prior is not None:
            log(f"=== A/B variant {spec}: already banked "
                f"({prior['value']}), skipping ===")
            ab[spec] = {"value": prior["value"], "banked": "prior run"}
            if primary is None:
                primary = prior
            continue
        log(f"=== A/B variant {spec}: {overrides or 'defaults'} ===")
        try:
            res = measure(overrides)
        except Exception as e:          # noqa: BLE001 — one broken
            log(f"variant {spec} FAILED: {e!r}")   # variant must not
            ab[spec] = {"error": repr(e)}          # cost the others
            failed.append(spec)
            continue
        _bank_variant(mode, spec, args.ab_dir, res, metric,
                      small=bool(args.small))
        ab[spec] = {"value": res["value"],
                    summary_key: res["config"][summary_key]}
        if primary is None:
            primary = res
    if primary is None:
        raise RuntimeError(f"every A/B variant failed: {ab}")
    primary.setdefault("config", {})["ab"] = ab
    if failed:
        # a partial A/B is NOT done: surface the loss where the runner's
        # step_ok sees it (banked variants survive in their own logs)
        primary["error"] = f"ab variants failed: {failed}"
    return primary


def run_headline(args):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpu_als.core.als import AlsConfig, make_step, init_factors
    from tpu_als.core.ratings import build_csr_buckets
    from tpu_als.io.movielens import ML25M_SHAPE

    nU, nI, nnz = ML25M_SHAPE
    if args.small:
        nU, nI, nnz = nU // 25, nI // 25, nnz // 25

    devs = jax.devices()
    log(f"devices: {devs}")
    t0 = time.time()
    u, i, r = synthetic_cached(nU, nI, nnz, seed=0)
    log(f"synthesized {nnz:,} ratings ({time.time()-t0:.1f}s)")

    blocked = {}   # width_growth -> staged (ucsr, icsr, ub, ib)

    def staged(width_growth):
        if width_growth not in blocked:
            # one ladder resident at a time: both full-scale padded-CSR
            # bucket sets at once (~2x ≈ 1 GB+) is HBM a 7-variant A/B
            # doesn't have to spare; specs are ordered same-wg-together
            # so eviction happens at most once
            blocked.clear()
            t0 = time.time()
            ucsr = build_csr_buckets(u, i, r, nU, width_growth=width_growth)
            icsr = build_csr_buckets(i, u, r, nI, width_growth=width_growth)
            log(f"blocked (wg {width_growth}): user waste "
                f"{ucsr.padded_nnz/ucsr.nnz:.2f}x, item waste "
                f"{icsr.padded_nnz/icsr.nnz:.2f}x ({time.time()-t0:.1f}s)")
            ub = jax.device_put(ucsr.device_buckets())
            ib = jax.device_put(icsr.device_buckets())
            blocked[width_growth] = (ucsr, icsr, ub, ib)
        return blocked[width_growth]

    sharded_blocked = {}   # strategy -> staged sharded containers

    def staged_sharded(strategy):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_als.parallel.data import partition_balanced, shard_csr
        from tpu_als.parallel.mesh import AXIS, make_mesh
        from tpu_als.parallel.trainer import stacked_counts

        if strategy not in sharded_blocked:
            sharded_blocked.clear()   # one strategy's containers resident
            D = len(devs)
            mesh = make_mesh(D)
            leading = NamedSharding(mesh, P(AXIS))
            t0 = time.time()
            upart = partition_balanced(np.bincount(u, minlength=nU), D)
            ipart = partition_balanced(np.bincount(i, minlength=nI), D)
            if strategy in ("ring", "ring_overlap"):
                from tpu_als.parallel.comm import shard_csr_grid

                ush = shard_csr_grid(upart, ipart, u, i, r)
                ish = shard_csr_grid(ipart, upart, i, u, r)
                counts = (
                    jax.device_put(
                        stacked_counts(upart, u, r, positive_only=True),
                        leading),
                    jax.device_put(
                        stacked_counts(ipart, i, r, positive_only=True),
                        leading))
            else:
                ush = shard_csr(upart, ipart, u, i, r)
                ish = shard_csr(ipart, upart, i, u, r)
                counts = None
            ub = jax.device_put(ush.device_buckets(), leading)
            ib = jax.device_put(ish.device_buckets(), leading)
            log(f"sharded blocked ({strategy}, {D} device(s)): "
                f"{time.time()-t0:.1f}s")
            sharded_blocked[strategy] = (mesh, leading, upart, ipart,
                                         ush, ish, ub, ib, counts)
        return sharded_blocked[strategy]

    def measure_sharded(strategy, cfg):
        """Overlap-variant measurement through the sharded step over all
        visible devices.  On one chip the collective is intra-device (the
        A/B prices the restructured step body — an upper bound on the
        single-chip cost); on a pod it measures the real overlap."""
        from tpu_als.core.als import resolve_solve_path
        from tpu_als.parallel.trainer import (
            _slot_init,
            comm_bytes_per_iter,
            make_chunked_gather_step,
            make_ring_step,
        )
        from tpu_als.utils.platform import fence

        (mesh, leading, upart, ipart, ush, ish, ub, ib,
         counts) = staged_sharded(strategy)
        key = jax.random.PRNGKey(0)
        ku, kv = jax.random.split(key)
        U = jax.device_put(_slot_init(ku, upart, cfg.rank), leading)
        V = jax.device_put(_slot_init(kv, ipart, cfg.rank), leading)
        if strategy in ("ring", "ring_overlap"):
            step = make_ring_step(mesh, ush, ish, cfg,
                                  overlap=(strategy == "ring_overlap"))
            step_args = (ub, ib) + counts
        else:
            step = make_chunked_gather_step(mesh, ush, ish, cfg)
            step_args = (ub, ib)
        backends = resolve_solve_path(cfg, cfg.rank, matfree_capable=False)
        log(f"resolved backends ({strategy}): {backends}")

        t0 = time.time()
        U, V = step(U, V, *step_args)
        fence(U)
        log(f"warmup (compile + 1 iter): {time.time()-t0:.1f}s")

        t0 = time.time()
        for _ in range(args.iters):
            U, V = step(U, V, *step_args)
        fence(U)
        dt = time.time() - t0
        checksum = float(jnp.sum(jnp.abs(U)))
        iters_per_sec = args.iters / dt
        log(f"{args.iters} iters in {dt:.2f}s -> {iters_per_sec:.3f} "
            f"iters/sec (checksum {checksum:.4g})")

        flops = analytic_flops_per_iter(nnz, nU, nI, cfg.rank,
                                        implicit=True)
        achieved = flops * iters_per_sec
        padded = (sum(b.mask.size for b in ush.buckets)
                  + sum(b.mask.size for b in ish.buckets))
        return {
            "value": round(iters_per_sec, 4),
            "unit": "iters/sec",
            "vs_baseline": round(
                iters_per_sec / SPARK_8EXEC_ITERS_PER_SEC, 2),
            "baseline_note": "baseline = assumed 60 s/iter for 8-executor "
                             "Spark ALS on ML-25M rank=128 (reference "
                             "publishes no numbers; Spark not runnable "
                             "here — see BASELINE.md)",
            "config": {
                "users": nU, "items": nI, "ratings": nnz, "rank": args.rank,
                "implicit": True, "alpha": 40.0,
                "device": str(jax.devices()[0]),
                "seconds_per_iter": round(dt / args.iters, 3),
                "compute_dtype": str(cfg.compute_dtype),
                "width_growth": args.width_growth,
                "gather_strategy": strategy,
                "devices": int(mesh.devices.size),
                "comm_bytes_per_iter": comm_bytes_per_iter(
                    strategy, upart, ipart, cfg.rank,
                    user_container=ush, item_container=ish,
                    implicit=True),
                "padding_waste": round(padded / (2.0 * nnz), 3),
                "tflops_per_iter_analytic": round(flops / 1e12, 3),
                "achieved_tflops": round(achieved / 1e12, 3),
                "mfu_pct_vs_bf16_peak": mfu_pct_vs_bf16_peak(achieved),
                "cg_iters": cfg.cg_iters, "cg_mode": cfg.cg_mode,
                **backends,
            },
        }

    def measure(overrides):
        """One full headline measurement at args+overrides; the expensive
        shared state (synthesis, blocking, staged buckets) is reused, so
        an A/B variant costs one compile + the timed iterations instead
        of a whole process."""
        from tpu_als.core.als import resolve_solve_path
        from tpu_als.utils.platform import fence

        wg = overrides.get("width_growth", args.width_growth)
        cdt = overrides.get("compute_dtype", args.compute_dtype)
        sb = overrides.get("solve_backend", args.solve_backend)
        strategy = overrides.get("gather_strategy")
        if strategy is not None:
            return measure_sharded(strategy, AlsConfig(
                rank=args.rank, max_iter=1, reg_param=0.01,
                implicit_prefs=True, alpha=40.0, seed=0,
                solve_backend=sb, compute_dtype=cdt,
                cg_iters=overrides.get("cg_iters", args.cg_iters),
                cg_mode=overrides.get("cg_mode", args.cg_mode)))
        ucsr, icsr, ub, ib = staged(wg)
        cfg = AlsConfig(rank=args.rank, max_iter=1, reg_param=0.01,
                        implicit_prefs=True, alpha=40.0, seed=0,
                        solve_backend=sb,
                        compute_dtype=cdt,
                        cg_iters=overrides.get("cg_iters", args.cg_iters),
                        cg_mode=overrides.get("cg_mode", args.cg_mode))
        key = jax.random.PRNGKey(0)
        ku, kv = jax.random.split(key)
        U = init_factors(ku, nU, cfg.rank)
        V = init_factors(kv, nI, cfg.rank)
        step = make_step(ub, ib, nU, nI, cfg,
                         ucsr.chunk_elems, icsr.chunk_elems)
        backends = resolve_solve_path(cfg, cfg.rank)
        log(f"resolved backends: {backends}")

        t0 = time.time()
        U, V = step(U, V)
        fence(U)
        log(f"warmup (compile + 1 iter): {time.time()-t0:.1f}s")

        t0 = time.time()
        for _ in range(args.iters):
            U, V = step(U, V)
        fence(U)
        dt = time.time() - t0
        checksum = float(jnp.sum(jnp.abs(U)))
        iters_per_sec = args.iters / dt
        log(f"{args.iters} iters in {dt:.2f}s -> {iters_per_sec:.3f} "
            f"iters/sec (checksum {checksum:.4g})")

        flops = analytic_flops_per_iter(nnz, nU, nI, cfg.rank,
                                        implicit=True)
        achieved = flops * iters_per_sec
        return {
            "value": round(iters_per_sec, 4),
            "unit": "iters/sec",
            "vs_baseline": round(
                iters_per_sec / SPARK_8EXEC_ITERS_PER_SEC, 2),
            "baseline_note": "baseline = assumed 60 s/iter for 8-executor "
                             "Spark ALS on ML-25M rank=128 (reference "
                             "publishes no numbers; Spark not runnable "
                             "here — see BASELINE.md)",
            "config": {
                "users": nU, "items": nI, "ratings": nnz, "rank": args.rank,
                "implicit": True, "alpha": 40.0,
                "device": str(jax.devices()[0]),
                "seconds_per_iter": round(dt / args.iters, 3),
                "compute_dtype": cdt,
                "width_growth": wg,
                "padding_waste": round(
                    (ucsr.padded_nnz + icsr.padded_nnz) / (2.0 * nnz), 3),
                "tflops_per_iter_analytic": round(flops / 1e12, 3),
                "achieved_tflops": round(achieved / 1e12, 3),
                "mfu_pct_vs_bf16_peak": mfu_pct_vs_bf16_peak(achieved),
                "cg_iters": cfg.cg_iters, "cg_mode": cfg.cg_mode,
                **backends,
            },
        }

    specs = _ab_specs(args)
    if not specs:
        return measure({})
    return _run_ab(specs, measure, "headline",
                   "als_iters_per_sec_rank128_ml25m_implicit",
                   args, "seconds_per_iter")


def run_serve(args):
    """recommendForAllUsers throughput at ML-25M scale: score every user
    against the full 59k-item catalog and keep a running top-10 — the
    reference's slowest serving path (blockify + crossJoin GEMMs + queue
    merge across a shuffle, SURVEY.md §3.3) collapsed into chunked MXU
    GEMM + lax.top_k scans (ops/topk.py; Pallas fused variant when its
    probe passes).  Factors are synthetic at the production shape —
    serving cost does not depend on their values."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpu_als.io.movielens import ML25M_SHAPE
    from tpu_als.ops import pallas_topk
    from tpu_als.ops.topk import topk_scores
    from tpu_als.utils.platform import fence, on_tpu

    nU, nI, _ = ML25M_SHAPE
    if args.small:
        nU, nI = nU // 25, nI // 25
    k, block = 10, 4096
    devs = jax.devices()
    log(f"devices: {devs}")
    rng = np.random.default_rng(0)
    U32 = jnp.asarray(rng.normal(size=(nU, args.rank)).astype(np.float32))
    V32 = jnp.asarray(rng.normal(size=(nI, args.rank)).astype(np.float32))
    cdt = jnp.dtype(args.compute_dtype)
    U, V = U32.astype(cdt), V32.astype(cdt)
    valid = jnp.ones(nI, dtype=bool)
    pallas_ok = bool(on_tpu() and k <= 128 and cdt == jnp.float32
                     and pallas_topk.available(args.rank, k))
    log(f"catalog {nI:,} items, {nU:,} users, rank {args.rank}, "
        f"dtype {args.compute_dtype}, pallas_topk={pallas_ok}")

    nblocks = nU // block  # whole blocks only: one compiled shape
    backend = "pallas" if pallas_ok else "xla"  # report what is measured

    def serve_all():
        last = None
        for s in range(0, nblocks * block, block):
            last = topk_scores(jax.lax.dynamic_slice_in_dim(U, s, block),
                               V, valid, k=k, item_chunk=block,
                               backend=backend)
        return last

    t0 = time.time()
    sc, ix = serve_all()
    fence(sc)
    log(f"warmup (compile + full pass): {time.time()-t0:.1f}s")
    t0 = time.time()
    sc, ix = serve_all()
    fence(sc)
    dt = time.time() - t0
    checksum = float(jnp.sum(jnp.abs(sc)))
    users = nblocks * block
    ups = users / dt
    log(f"{users:,} users served in {dt:.2f}s -> {ups:,.0f} users/sec "
        f"(checksum {checksum:.4g})")
    overlap = None
    if cdt != jnp.float32:
        # the variant carries its own quality evidence: top-k overlap
        # vs the exact f32 ranking on the first user block
        _, ix32 = topk_scores(U32[:block], V32, valid, k=k,
                              item_chunk=block, backend="xla")
        _, ixv = topk_scores(U[:block], V, valid, k=k, item_chunk=block,
                             backend=backend)
        a, b = np.asarray(ixv), np.asarray(ix32)
        overlap = float(np.mean([len(set(a[r]) & set(b[r])) / k
                                 for r in range(block)]))
        log(f"top-{k} overlap vs f32: {overlap:.4f}")
    return {
        "value": round(ups, 1),
        "unit": "users/sec",
        "vs_baseline": None,
        "baseline_note": "no assumed Spark serving proxy — the reference "
                         "publishes no recommendForAllUsers numbers; the "
                         "measured artifact stands alone",
        "config": {
            "users_served": users, "items": nI, "rank": args.rank,
            "k": k, "block": block, "device": str(jax.devices()[0]),
            "seconds_full_pass": round(dt, 3),
            "topk_backend": backend,
            "compute_dtype": args.compute_dtype,
            "topk_overlap_vs_f32": (None if overlap is None
                                    else round(overlap, 4)),
            "gemm_tflops": round(
                2.0 * users * nI * args.rank / dt / 1e12, 3),
        },
    }


def run_multichip(args):
    """Pod-scale recipe measurement (ROADMAP item 2; BASELINE config 3
    on-ramp): ingest -> shard -> fused-comm ring
    (solve_backend='gather_fused_ring') over EVERY visible device, the
    whole iteration in ONE kernel per half-step with the inter-chip
    factor rotation riding the kernel's own remote-DMA ring.

    Two platforms, one schedule: on a TPU slice the kernel compiles with
    the hardware race-control arms and the result banks to
    ``--multichip-json`` (MULTICHIP_*.json, banked_at provenance); on CPU
    (``--platform cpu``) the identical grid/ring schedule runs
    interpret-mode on the 8 forced host devices at a reduced
    schedule-validation scale — the tier-1-testable path
    scripts/pod_recipe.sh --dry-run and scripts/multichip_smoke.sh drive.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpu_als.core.als import AlsConfig, resolve_solve_path
    from tpu_als.io.movielens import ML25M_SHAPE
    from tpu_als.utils.platform import fence

    nU, nI, nnz = ML25M_SHAPE
    if args.small:
        # interpret-mode emulation prices the SCHEDULE, not the chip:
        # small multichip is a schedule-validation scale (every device
        # gets multiple row tiles and several buckets), not 1/25 ML-25M
        nU, nI, nnz = 1200, 900, 40000

    devs = jax.devices()
    D = len(devs)
    log(f"devices: {D} x {devs[0].device_kind}")
    if D < 2:
        raise RuntimeError(
            "multichip mode needs a multi-device backend; on CPU start "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=8")

    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_als.parallel.comm import shard_csr_grid
    from tpu_als.parallel.data import partition_balanced
    from tpu_als.parallel.mesh import AXIS, make_mesh
    from tpu_als.parallel.trainer import (
        _slot_init,
        comm_bytes_per_iter,
        make_ring_step,
        stacked_counts,
    )

    # -- ingest: synthesize + shard + stage (timed as one phase) --------
    t0 = time.time()
    u, i, r = synthetic_cached(nU, nI, nnz, seed=0)
    mesh = make_mesh(D)
    leading = NamedSharding(mesh, P(AXIS))
    upart = partition_balanced(np.bincount(u, minlength=nU), D)
    ipart = partition_balanced(np.bincount(i, minlength=nI), D)
    ush = shard_csr_grid(upart, ipart, u, i, r)
    ish = shard_csr_grid(ipart, upart, i, u, r)
    ub = jax.device_put(ush.device_buckets(), leading)
    ib = jax.device_put(ish.device_buckets(), leading)
    counts = (
        jax.device_put(stacked_counts(upart, u, r, positive_only=True),
                       leading),
        jax.device_put(stacked_counts(ipart, i, r, positive_only=True),
                       leading))
    ingest_s = time.time() - t0
    log(f"ingest (synthesize+shard+stage): {ingest_s:.1f}s "
        f"({nnz:,} ratings over {D} devices)")

    # -- ring: the fused-comm step at the production rank ---------------
    cfg = AlsConfig(rank=args.rank, max_iter=1, reg_param=0.01,
                    implicit_prefs=True, alpha=40.0, seed=0,
                    solve_backend="gather_fused_ring",
                    compute_dtype=args.compute_dtype)
    step = make_ring_step(mesh, ush, ish, cfg)
    backends = resolve_solve_path(cfg, cfg.rank, matfree_capable=False)
    log(f"resolved backends: {backends}")
    key = jax.random.PRNGKey(0)
    ku, kv = jax.random.split(key)
    U = jax.device_put(_slot_init(ku, upart, cfg.rank), leading)
    V = jax.device_put(_slot_init(kv, ipart, cfg.rank), leading)

    t0 = time.time()
    U, V = step(U, V, ub, ib, *counts)
    fence(U)
    log(f"warmup (compile + 1 iter): {time.time()-t0:.1f}s")

    t0 = time.time()
    for _ in range(args.iters):
        U, V = step(U, V, ub, ib, *counts)
    fence(U)
    dt = time.time() - t0
    checksum = float(jnp.sum(jnp.abs(U)))
    iters_per_sec = args.iters / dt
    log(f"{args.iters} iters in {dt:.2f}s -> {iters_per_sec:.3f} "
        f"iters/sec (checksum {checksum:.4g})")

    flops = analytic_flops_per_iter(nnz, nU, nI, cfg.rank, implicit=True)
    achieved = flops * iters_per_sec
    ring_bytes = comm_bytes_per_iter(
        "gather_fused_ring", upart, ipart, cfg.rank,
        user_container=ush, item_container=ish, implicit=True,
        compute_dtype=cfg.compute_dtype)
    result = {
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        "vs_baseline": None,
        "baseline_note": "no Spark pod proxy — whole-mesh iters/sec; the "
                         "per-device roofline is docs/roofline.md's "
                         "multi-chip section",
        "config": {
            "users": nU, "items": nI, "ratings": nnz, "rank": args.rank,
            "implicit": True, "alpha": 40.0,
            "device": str(devs[0]), "devices": D,
            "platform": devs[0].platform,
            "seconds_per_iter": round(dt / args.iters, 3),
            "ingest_seconds": round(ingest_s, 1),
            "compute_dtype": str(cfg.compute_dtype),
            "gather_strategy": "ring",
            "solve_backend": "gather_fused_ring",
            "comm_bytes_per_iter": ring_bytes,
            "tflops_per_iter_analytic": round(flops / 1e12, 3),
            "achieved_tflops": round(achieved / 1e12, 3),
            "mfu_pct_vs_bf16_peak": mfu_pct_vs_bf16_peak(achieved, D),
            **backends,
        },
    }
    _bank_multichip(result, args)
    return result


def _bank_multichip(result, args):
    """MULTICHIP_*.json banking: one file per (device count, platform),
    overwritten by the freshest measurement, ``banked_at`` stamped at
    bank time — same provenance rule as the sweep's banked lines
    (_bank_variant): later rounds transport the record verbatim, so the
    timestamp must be absolute and written HERE, not derived from file
    mtime downstream."""
    import os

    path = args.multichip_json
    if not path:
        cfgd = result["config"]
        path = (f"MULTICHIP_{cfgd['devices']}dev_"
                f"{cfgd['platform']}.json")
    doc = dict(result)
    doc["metric"] = "als_iters_per_sec_multichip"
    doc["banked_at"] = _dt.datetime.now(
        _dt.timezone.utc).isoformat(timespec="seconds")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    log(f"banked multichip evidence -> {path}")


def _resolve(cfg):
    from tpu_als.core.als import resolve_solve_path

    return resolve_solve_path(cfg, cfg.rank)


def run_rmse(args):
    """Held-out RMSE at ML-25M scale (BASELINE.json metric 2): explicit ALS
    on the planted-low-rank synthetic, 95/5 split.  The generator plants a
    rank-16 structure + noise, so a correct solver must recover most of it;
    the floor is the half-star quantization + noise (~0.36 stars).

    ``--mode ml100k`` reuses this path at BASELINE config 1's operating
    point instead: ML-100K shape (943 x 1,682, 100k ratings), rank 10,
    10 iterations, explicit, 80/20 split — the stock-PySpark starter
    config.  The reported value there is fit wall-clock (the row's
    comparison is against `local[*]` Spark, which this environment cannot
    run), with held-out RMSE carried in the config block."""
    import numpy as np

    import jax

    from tpu_als.core.als import AlsConfig, train, predict
    from tpu_als.core.ratings import build_csr_buckets
    from tpu_als.io.movielens import ML100K_SHAPE, ML25M_SHAPE

    if args.mode == "ml100k":
        nU, nI, nnz = ML100K_SHAPE
        rank, iters, reg, test_frac = 10, 10, 0.1, 0.2
    else:
        nU, nI, nnz = ML25M_SHAPE
        rank, iters, reg, test_frac = (args.rank, args.iters_rmse,
                                       args.reg, 0.05)
    if args.small:
        nU, nI, nnz = nU // 25, nI // 25, nnz // 25

    devs = jax.devices()
    log(f"devices: {devs}")
    u, i, r = synthetic_cached(nU, nI, nnz, seed=0)

    rng = np.random.default_rng(1)
    test = rng.random(nnz) < test_frac
    ut, it_, rt = u[test], i[test], r[test]
    u, i, r = u[~test], i[~test], r[~test]
    log(f"split: {len(r):,} train / {len(rt):,} test")

    t0 = time.time()
    ucsr = build_csr_buckets(u, i, r, nU, width_growth=args.width_growth)
    icsr = build_csr_buckets(i, u, r, nI, width_growth=args.width_growth)
    log(f"blocked ({time.time()-t0:.1f}s)")

    def measure(overrides):
        """Train + held-out score at args+overrides, reusing the split and
        blocked containers — an A/B variant costs its compile + train,
        not a whole process (synthesis and blocking dominate startup)."""
        import jax.numpy as jnp

        cfg = AlsConfig(rank=rank, max_iter=iters,
                        reg_param=reg, implicit_prefs=False, seed=0,
                        solve_backend=args.solve_backend,
                        compute_dtype=overrides.get("compute_dtype",
                                                    args.compute_dtype),
                        cg_iters=overrides.get("cg_iters", args.cg_iters),
                        cg_mode=overrides.get("cg_mode", args.cg_mode))
        # Per-iteration wall-clock via the train() callback, syncing each
        # iteration so iter 1 absorbs the jit compile and iters 2..N are
        # steady state — the same warmup/steady split headline mode uses.
        # Dividing compile-inclusive wall-clock by max_iter is what made
        # this mode report ~8-11 s/iter while headline measured 1.184.
        iter_marks = [time.time()]

        def _mark(_it, Ucb, _Vcb):
            Ucb.block_until_ready()
            iter_marks.append(time.time())

        t0 = iter_marks[0]
        U, V = train(ucsr, icsr, cfg, callback=_mark)
        U.block_until_ready()
        train_s = time.time() - t0
        iter_s = [b - a for a, b in zip(iter_marks, iter_marks[1:])]
        steady = iter_s[1:]
        steady_per_iter = (sum(steady) / len(steady)) if steady else None
        warmup_s = iter_s[0] if iter_s else train_s
        log(f"trained {cfg.max_iter} iters in {train_s:.1f}s "
            f"(warmup {warmup_s:.1f}s"
            + (f", steady {steady_per_iter:.3f}s/iter)"
               if steady_per_iter is not None else ")"))
        warm_s = None
        if args.mode == "ml100k":
            # the cold fit above is compile-dominated on accelerators at
            # this tiny shape; a second in-process fit (jit cache warm)
            # is what a user iterating on hyperparameters experiences,
            # and what CrossValidator cells pay after the first
            warm_marks = [time.time()]

            def _warm_mark(_it, Ucb, _Vcb):
                Ucb.block_until_ready()
                warm_marks.append(time.time())

            U2, _ = train(ucsr, icsr, cfg, callback=_warm_mark)
            U2.block_until_ready()
            warm_s = time.time() - warm_marks[0]
            warm_iter_s = [b - a for a, b in zip(warm_marks, warm_marks[1:])]
            log(f"warm re-fit (compile cached): {warm_s:.1f}s"
                + (f" ({warm_s / len(warm_iter_s):.3f}s/iter)"
                   if warm_iter_s else ""))

        # chunked held-out scoring (test set can be >1M pairs)
        se, cnt = 0.0, 0
        B = 1 << 20
        ones = None
        for s in range(0, len(rt), B):
            ub_, ib_, rb = ut[s:s + B], it_[s:s + B], rt[s:s + B]
            if ones is None or len(ub_) != len(ones):
                ones = jnp.ones(len(ub_), bool)
            pred = predict(U, V, jnp.asarray(ub_), jnp.asarray(ib_),
                           ones, ones)
            pred = np.asarray(pred)
            ok = np.isfinite(pred)
            se += float(((pred[ok] - rb[ok]) ** 2).sum())
            cnt += int(ok.sum())
        rmse = float(np.sqrt(se / max(cnt, 1)))
        base = float(np.sqrt(np.mean((rt - r.mean()) ** 2)))
        log(f"held-out RMSE {rmse:.4f} (global-mean predictor {base:.4f})")

        config = {
            "users": nU, "items": nI, "ratings": nnz, "rank": cfg.rank,
            "iters": cfg.max_iter, "reg_param": cfg.reg_param,
            "train_seconds": round(train_s, 1),
            # steady-state (compile excluded); the old value divided the
            # compile-inclusive wall-clock by max_iter
            "seconds_per_iter": (round(steady_per_iter, 3)
                                 if steady_per_iter is not None
                                 else round(train_s / max(cfg.max_iter, 1),
                                            3)),
            "warmup_seconds": round(warmup_s, 2),
            "seconds_per_iter_incl_compile":
                round(train_s / max(cfg.max_iter, 1), 3),
            "test_pairs_scored": cnt,
            "device": str(jax.devices()[0]),
            "cg_iters": cfg.cg_iters, "cg_mode": cfg.cg_mode,
            "compute_dtype": str(cfg.compute_dtype),
            **_resolve(cfg),
        }
        if args.mode == "ml100k":
            config["heldout_rmse"] = round(rmse, 4)
            config["global_mean_rmse"] = round(base, 4)
            if warm_s is not None:
                config["train_seconds_warm"] = round(warm_s, 2)
                config["seconds_per_iter_warm"] = round(
                    warm_s / max(cfg.max_iter, 1), 3)
            return {
                "value": round(train_s, 2),
                "unit": "seconds_fit_wallclock",
                "vs_baseline": None,
                "baseline_note": "BASELINE config 1: stock-PySpark "
                                 "`local[*]` baseline is unpublished and "
                                 "Spark cannot run in this environment; "
                                 "the measured artifact is our fit "
                                 "wall-clock + held-out RMSE",
                "config": config,
            }
        return {
            "value": round(rmse, 4),
            "unit": "rmse_stars",
            "vs_baseline": round(base / rmse, 3),
            "baseline_note": "vs_baseline = global-mean-predictor RMSE / "
                             "model RMSE (>1 is better); reference "
                             "publishes no RMSE",
            "config": config,
        }

    specs = (_ab_specs(args, allow_wg=False, allow_strategy=False)
             if args.mode == "rmse" else [])
    if not specs:
        return measure({})
    return _run_ab(specs, measure, "rmse",
                   "als_heldout_rmse_ml25m_explicit",
                   args, "train_seconds")


def run_foldin(args):
    """Fold-in p50 latency (BASELINE.json config 4): micro-batches of new
    ratings folded into a fitted model's user factors against fixed item
    factors.  Item catalog at ML-25M size so the jitted solve runs at the
    production shape; latency includes the host-side batch prep (that IS
    the serving path)."""
    import numpy as np

    import jax

    from tpu_als.api.estimator import ALS
    from tpu_als.io.movielens import ML25M_SHAPE, synthetic_movielens
    from tpu_als.stream.microbatch import FoldInServer
    from tpu_als.utils.frame import ColumnarFrame

    nU_cat, nI, _ = ML25M_SHAPE
    nU = 20000   # training-user count only affects fit time, not fold-in
    nnz = 2_000_000
    if args.small:
        nU, nI, nnz = nU // 10, nI // 10, nnz // 10
    devs = jax.devices()
    log(f"devices: {devs}")
    frame = synthetic_movielens(nU, nI, nnz, seed=0)
    model = ALS(rank=args.rank, maxIter=2, regParam=0.01, seed=0).fit(frame)
    log("model fitted; running fold-in batches")

    srv = FoldInServer(model)
    t0 = time.time()
    # startup prewarm: compile and run the padded shapes the batch size
    # implies (touched-user rows up to the batch, capped by the
    # 1000-hot-user pool), so latency quantiles measure serving, not jits
    srv.prewarm(rows=(min(args.foldin_batch, 1000),), widths=(128,))
    prewarm_s = time.time() - t0
    log(f"prewarm: {prewarm_s:.1f}s")
    rng = np.random.default_rng(1)
    base = int(model._user_map.ids.max()) + 1
    batches = 30
    for b in range(batches):
        n = args.foldin_batch
        srv.update(ColumnarFrame({
            "user": rng.integers(base, base + 1000, n),
            "item": rng.choice(model._item_map.ids, n),
            "rating": rng.uniform(0.5, 5.0, n).astype(np.float32),
        }))
    p50 = srv.latency(0.5, skip_warmup=True)
    p95 = srv.latency(0.95, skip_warmup=True)
    # the symmetric serving direction: NEW ITEMS folded against the
    # (much larger) user factor table — quantiles reported alongside
    n_user_stats = len(srv.stats)
    ibase = int(model._item_map.ids.max()) + 1
    for b in range(8):
        srv.update_items(ColumnarFrame({
            "user": rng.choice(model._user_map.ids, args.foldin_batch),
            "item": rng.integers(ibase, ibase + 200, args.foldin_batch),
            "rating": rng.uniform(0.5, 5.0,
                                  args.foldin_batch).astype(np.float32),
        }))
    item_lat = sorted(s[2] for s in srv.stats[n_user_stats + 1:])
    item_p50 = (item_lat[len(item_lat) // 2] if item_lat
                else float("nan"))
    return {
        "value": round(p50, 4),
        "unit": "seconds_p50",
        "vs_baseline": None,
        "baseline_note": "reference stack has no fold-in (full refit "
                         "required; SURVEY.md §3.5) — latency vs refit is "
                         "the comparison",
        "config": {
            "rank": args.rank, "items": nI, "batch_size": args.foldin_batch,
            "batches": batches, "p95_seconds": round(p95, 4),
            "prewarm_seconds": round(prewarm_s, 1),
            "item_foldin_p50_seconds": round(item_p50, 4),
            "device": str(jax.devices()[0]),
        },
    }


def _oracle_recall(Ustar, Vstar, item_counts, eval_u, eval_i,
                   train_u, train_i, k=10, noise=0.3):
    """Filtered recall@k of the Bayes ranker for this protocol — its
    ceiling.  A test positive is a popularity-weighted draw that cleared
    the rating threshold, so the optimal score is
    ``log q(item) + log P(rating >= 3.5 | planted preference)`` — NOT the
    raw preference (a pure-preference ranker ignores the draw
    distribution and scores far below trainable models here).  With the
    generator's star mapping, rating >= 3.5 iff raw >= -0.25/1.1."""
    import numpy as np

    from tpu_als.models.two_tower import ban_lists, log_popularity

    def erf(x):
        # Abramowitz & Stegun 7.1.26, |err| < 1.5e-7 — numpy-only so the
        # oracle metric doesn't make scipy a hard dependency of bench.py
        # (the rest of the repo treats scipy as optional)
        sign = np.sign(x)
        ax = np.abs(x)
        t = 1.0 / (1.0 + 0.3275911 * ax)
        poly = t * (0.254829592 + t * (-0.284496736 + t * (
            1.421413741 + t * (-1.453152027 + t * 1.061405429))))
        return sign * (1.0 - poly * np.exp(-ax * ax))

    q = log_popularity(item_counts)
    users, inv = np.unique(eval_u, return_inverse=True)
    topk = np.zeros((len(users), k), np.int32)
    B = 2048
    tp, tit, bounds = ban_lists(users, train_u, train_i, B)
    thresh = -0.25 / 1.1
    for bi, s in enumerate(range(0, len(users), B)):
        e = min(s + B, len(users))
        mu = Ustar[users[s:e]] @ Vstar.T
        z = (mu - thresh) / (noise * np.sqrt(2.0))
        with np.errstate(divide="ignore"):
            sc = q[None, :] + np.log(
                np.maximum(0.5 * (1.0 + erf(z)), 1e-300))
        lo, hi = bounds[bi], bounds[bi + 1]
        sc[tp[lo:hi] - s, tit[lo:hi]] = -np.inf
        topk[s:e] = np.argpartition(-sc, k, axis=1)[:, :k]
    hits = (topk[inv] == eval_i[:, None]).any(axis=1)
    return float(hits.mean())


def run_twotower(args):
    """Two-tower retrieval recall@10 (BASELINE.json config 5), ALS-warm
    vs cold start, on held-out positives."""
    import numpy as np

    import jax

    from tpu_als.core.als import AlsConfig, train
    from tpu_als.core.ratings import build_csr_buckets
    from tpu_als.io.movielens import synthetic_movielens
    from tpu_als.models.two_tower import (
        TwoTowerConfig, recall_at_k, train_two_tower)

    devs = jax.devices()
    log(f"devices: {devs}")
    nU, nI, nnz = 20000, 4000, 800_000
    if args.small:
        nU, nI, nnz = nU // 10, nI // 10, nnz // 10
    frame, Ustar, Vstar = synthetic_movielens(nU, nI, nnz, seed=0,
                                              return_factors=True)
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    r = np.asarray(frame["rating"])
    item_counts = np.bincount(i, minlength=nI).astype(np.float64)
    pos = r >= 3.5  # positives for retrieval
    u, i, r = u[pos], i[pos], r[pos]
    rng = np.random.default_rng(2)
    test = rng.random(len(u)) < 0.1
    ut, it_ = u[test], i[test]
    u2, i2, r2 = u[~test], i[~test], r[~test]
    # the synthetic draws (u, i) pairs with replacement, so an interaction
    # can land in both splits; under the filtered protocol a test pair
    # that is also a train pair is a guaranteed miss (its item is banned)
    # — drop those so the metric reflects ranking, not duplicate rate
    key = ut.astype(np.int64) * nI + it_
    train_key = np.unique(u2.astype(np.int64) * nI + i2)
    fresh = ~np.isin(key, train_key)
    ut, it_ = ut[fresh], it_[fresh]
    log(f"test pairs: {int(test.sum()):,} -> {len(ut):,} after dropping "
        "train-duplicated pairs")

    als_cfg = AlsConfig(rank=32, max_iter=8, reg_param=0.005,
                        implicit_prefs=True, alpha=20.0, seed=0)
    ucsr = build_csr_buckets(u2, i2, r2, nU)
    icsr = build_csr_buckets(i2, u2, r2, nI)
    U, V = train(ucsr, icsr, als_cfg)
    log("ALS warm-start factors trained")

    cfg = TwoTowerConfig(embed_dim=32, out_dim=32, epochs=args.tt_epochs,
                         seed=0)
    # filtered protocol: each user's TRAIN items are removed from their
    # candidate set (they occupy the unfiltered top-k by construction,
    # pinning held-out recall to the random floor — see recall_at_k).
    # Serving-time popularity prior: training removed popularity via the
    # logQ correction; the test draws are popularity-biased, so adding
    # temperature·log q back at serving (the Bayes-oracle form) is the
    # honest best-serving configuration.
    from tpu_als.models.two_tower import serving_bias

    excl = (u2, i2)
    bias = serving_bias(np.bincount(i2, minlength=nI), cfg.temperature)
    # warm-vs-cold over EPOCH BUDGETS (VERDICT r3 #6): the warm-start
    # advantage is a few-epoch phenomenon (it washes out as cold
    # training converges), so the defended operating point must come
    # from the curve, not a single endpoint
    milestones = sorted({e for e in (1, 3, 5, 10, 20)
                         if e <= cfg.epochs} | {cfg.epochs})
    curve = {"warm": {}, "cold": {}, "warm_prior": {}}
    eval_s = [0.0]  # callback recall evals, excluded from the train timer

    def make_cb(tag):
        def cb(epoch, loss, params):
            if epoch not in milestones:
                return
            t_eval = time.time()
            curve[tag][epoch] = round(
                recall_at_k(params, ut, it_, k=10, exclude=excl), 4)
            if tag == "warm":
                curve["warm_prior"][epoch] = round(
                    recall_at_k(params, ut, it_, k=10, exclude=excl,
                                item_bias=bias), 4)
            eval_s[0] += time.time() - t_eval
            log(f"epoch {epoch}: {tag} recall@10 {curve[tag][epoch]}")
        return cb

    t0 = time.time()
    warm = train_two_tower(u2, i2, nU, nI, cfg,
                           als_user_factors=np.asarray(U),
                           als_item_factors=np.asarray(V),
                           callback=make_cb("warm"))
    warm_s = time.time() - t0 - eval_s[0]
    train_two_tower(u2, i2, nU, nI, cfg, callback=make_cb("cold"))
    r_warm = curve["warm"][cfg.epochs]
    r_cold = curve["cold"][cfg.epochs]
    r_warm_prior = curve["warm_prior"][cfg.epochs]
    r_warm_unf = recall_at_k(warm, ut, it_, k=10)
    r_oracle = _oracle_recall(Ustar, Vstar, item_counts, ut, it_, u2, i2,
                              k=10)
    # the defended operating point: the epoch budget where the warm
    # start buys the most recall over cold (ties -> earliest = cheapest)
    gap_by_epoch = {e: round(curve["warm"][e] - curve["cold"][e], 4)
                    for e in milestones}
    best_epoch = max(milestones,
                     key=lambda e: (gap_by_epoch[e], -e))
    log(f"filtered recall@10 warm {r_warm:.4f} (with serving prior "
        f"{r_warm_prior:.4f}) vs cold {r_cold:.4f} (unfiltered warm "
        f"{r_warm_unf:.4f}, oracle ceiling {r_oracle:.4f}); "
        f"largest warm-cold gap {gap_by_epoch[best_epoch]} at "
        f"epoch {best_epoch}")
    return {
        "value": round(r_warm_prior, 4),
        "unit": "recall_at_10",
        "vs_baseline": round(r_warm / max(r_cold, 1e-9), 3),
        "baseline_note": "value = warm recall@10 WITH the serving-time "
                         "popularity prior (the deployed configuration); "
                         "vs_baseline = plain warm/cold recall at equal "
                         "epochs (>1 = ALS warm start helps); reference "
                         "stack has no neural retrieval",
        "config": {
            "users": nU, "items": nI, "train_pairs": int(len(u2)),
            "test_pairs": int(len(ut)), "epochs": cfg.epochs,
            "protocol": "filtered (train items excluded per user)",
            "warm_recall_at_10": round(r_warm, 4),
            "cold_recall_at_10": round(r_cold, 4),
            "prior_warm_recall_at_10": round(r_warm_prior, 4),
            "unfiltered_warm_recall_at_10": round(r_warm_unf, 4),
            "oracle_recall_at_10": round(r_oracle, 4),
            "pct_of_oracle": round(
                100.0 * r_warm_prior / max(r_oracle, 1e-9), 1),
            "recall_curve_by_epoch": curve,
            "warm_minus_cold_by_epoch": gap_by_epoch,
            "best_warm_gap_epoch": best_epoch,
            "train_seconds_warm": round(warm_s, 1),
            "device": str(jax.devices()[0]),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="headline",
                    choices=["headline", "rmse", "ml100k", "foldin",
                             "twotower", "serve", "multichip"])
    ap.add_argument("--small", action="store_true",
                    help="1/25 scale for quick checks")
    ap.add_argument("--iters", type=int, default=3,
                    help="timed iterations after warmup (headline mode)")
    ap.add_argument("--iters-rmse", type=int, default=10,
                    help="training iterations (rmse mode)")
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--reg", type=float, default=0.02,
                    help="regParam for rmse mode (weighted-λ scheme)")
    ap.add_argument("--solve-backend", default="auto",
                    choices=["auto", "unfused", "gather_fused",
                             "gather_fused_solve", "gather_fused_ring"],
                    help="half-step solve path (AlsConfig.solve_backend); "
                         "'auto' probes the Pallas kernels on TPU; "
                         "'gather_fused' forces the DMA-gather NE build, "
                         "'gather_fused_solve' the whole-iteration fused "
                         "kernel (ops/pallas_gather_ne), "
                         "'gather_fused_ring' the fused-COMM variant "
                         "(ring strategies only: the shard rotation runs "
                         "as in-kernel remote DMAs)")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="dtype for the gather/einsum stage")
    ap.add_argument("--cg-iters", type=int, default=0,
                    help="> 0: inexact ALS — replace the exact per-row "
                         "solve with this many warm-started CG steps "
                         "(batched MXU matvecs instead of r^3 "
                         "factorizations); 0 = exact Cholesky path")
    ap.add_argument("--cg-mode", default="matfree",
                    choices=["matfree", "dense"],
                    help="matfree: apply A through the gathered factors "
                         "(no [n,r,r] tensor, no NE einsum); dense: "
                         "build A once and run CG on it")
    ap.add_argument("--foldin-batch", type=int, default=512,
                    help="ratings per micro-batch (foldin mode)")
    ap.add_argument("--tt-epochs", type=int, default=20,
                    help="two-tower training epochs (twotower mode)")
    ap.add_argument("--width-growth", type=float, default=2.0,
                    choices=[2.0, 1.5],
                    help="bucket width ladder: 2.0 = powers of two, "
                         "1.5 = add 0.75*2^k rungs (~25%% less padding, "
                         "more jit specializations)")
    ap.add_argument("--ab", default="",
                    help="comma list of variant specs (exact, cg2, cg3, "
                         "cg2_dense, bf16, cg2_bf16, wg15, ...) measured "
                         "in ONE process sharing synthesis/blocking/"
                         "staging (headline and rmse modes)")
    ap.add_argument("--ab-dir", default="",
                    help="directory to append each finished variant's "
                         "JSON line into its canonical sweep log (e.g. "
                         "sweep_logs) so auto-selection sees the evidence "
                         "even if a later variant dies")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="cpu = force the CPU backend (smoke tests of the "
                         "wiring; the result is no device number).  "
                         "default = a TPU, or exit non-zero")
    ap.add_argument("--no-auto-config", action="store_true",
                    help="disable sweep-evidence auto-selection (the "
                         "sweep itself must pass this so its steps "
                         "measure the configs they claim to)")
    ap.add_argument("--multichip-json", default="",
                    help="multichip mode: bank the measurement (plus "
                         "banked_at) to this path; default "
                         "MULTICHIP_<devices>dev_<platform>.json")
    args = ap.parse_args()

    if (args.mode == "headline" and not args.no_auto_config
            and not args.small and args.platform == "default"
            and not args.ab          # an A/B run measures its own specs;
            and args.cg_iters == 0   # auto-config mutating the base flags
            and args.compute_dtype == "float32"   # would contaminate the
            and args.width_growth == 2.0          # banked evidence
            and args.cg_mode == "matfree"
            and args.solve_backend == "auto"):
        # `is not None`, not truthiness: {} is the legitimate "winner is
        # the default config, no overrides" outcome — behaviorally the
        # same (zero setattrs), but the condition now matches the
        # "auto-selected" log line best_measured_flags emits (advisor r3)
        picked = best_measured_flags()
        if picked is not None:
            for k, v in picked.items():
                setattr(args, k, v)

    if args.ab and args.ab_dir:
        # refuse un-bankable base configs before touching the device
        # (the _run_ab-time call stays as the backstop for direct callers)
        _check_ab_bankable(args, args.mode)

    metric, unit = {
        "headline": ("als_iters_per_sec_rank128_ml25m_implicit",
                     "iters/sec"),
        "rmse": ("als_heldout_rmse_ml25m_explicit", "rmse_stars"),
        "ml100k": ("als_ml100k_rank10_fit_seconds",
                   "seconds_fit_wallclock"),
        "foldin": ("foldin_p50_latency", "seconds_p50"),
        "twotower": ("two_tower_recall_at_10", "recall_at_10"),
        "serve": ("serve_topk_users_per_sec_ml25m_rank128", "users/sec"),
        "multichip": ("als_iters_per_sec_multichip", "iters/sec"),
    }[args.mode]
    if args.small:
        metric += "_small"

    import jax

    from tpu_als.utils.platform import enable_persistent_compile_cache

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    device = device_info()
    if args.platform != "cpu" and device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and JAX reports {device}; pass "
            "--platform cpu for a smoke run of the wiring (its numbers "
            "are not device numbers)")
    enable_persistent_compile_cache()

    run = {"headline": run_headline, "rmse": run_rmse,
           "ml100k": run_rmse,
           "foldin": run_foldin, "twotower": run_twotower,
           "serve": run_serve, "multichip": run_multichip}[args.mode]
    result = run(args)
    result["metric"] = metric
    result["device"] = device
    print(json.dumps(result))


if __name__ == "__main__":
    main()
