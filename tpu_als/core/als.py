"""The ALS training engine: jitted half-steps over bucketed padded CSR.

This is the TPU-native replacement for the reference stack's ``computeFactors``
loop (Spark MLlib ``ml/recommendation/ALS.scala`` — SURVEY.md §3.1): where
Spark runs, per iteration, two RDD shuffles moving factor messages between
user-blocks and item-blocks and then per-row scalar solves inside tasks, here
each half-step is one jitted function: gather the opposite factor rows per
degree-bucket, build all normal equations with one einsum per bucket, and
solve them with one batched Cholesky (or fixed-sweep NNLS) per chunk.

Single-device and sharded training share :func:`local_half_step`; the sharded
path (tpu_als.parallel.trainer) wraps it in ``shard_map`` with an
``all_gather`` of the opposite factor shard in place of the shuffle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as _dc_replace

import jax
import jax.numpy as jnp

from tpu_als.core.ratings import trainer_chunk

from tpu_als.ops.solve import (
    DEFAULT_JITTER,
    compute_yty,
    normal_eq_explicit,
    normal_eq_implicit,
    solve_cg,
    solve_cg_matfree,
    solve_nnls,
    solve_spd,
)


@dataclass(frozen=True)
class AlsConfig:
    """Algorithm knobs.  Names/defaults mirror the Estimator params (§2.D)."""

    rank: int = 10
    max_iter: int = 10
    reg_param: float = 0.1
    implicit_prefs: bool = False
    alpha: float = 1.0
    nonnegative: bool = False
    seed: int = 0
    nnls_sweeps: int = 32
    compute_dtype: str = "float32"  # or "bfloat16" for the A/b einsums
    # 'auto': normal equations + the fastest healthy Pallas solve —
    # batch-in-lanes (tpu_als.ops.pallas_lanes, rank <= 128, 2.2x the
    # blocked kernel on v5e) then blocked Cholesky (pallas_solve), else
    # the XLA cholesky lowering.  On the NE-build side, 'auto'
    # additionally upgrades the gather+einsum build to the DMA-gather
    # fused kernel (tpu_als.ops.pallas_gather_ne — factor rows stream
    # HBM→VMEM once, Vg never materialized), and beyond that to the
    # WHOLE-ITERATION fused kernel (gather → Gram → ridge/YtY tail →
    # in-VMEM Cholesky solve; A never exists in HBM), each step only
    # when BOTH its compile-and-validate probe AND its timing probe beat
    # the shallower path on this chip (available ≠ faster: the
    # pallas_fused lesson — its HBM-streamed Vg + per-column VPU solve
    # measured 34x slower than einsum+lanes on v5e, and it is retired).
    # 'gather_fused' forces the DMA-gather NE kernel,
    # 'gather_fused_solve' forces the whole-iteration kernel (both run
    # interpret-mode off-TPU, so CPU tests exercise them); 'unfused'
    # forces the plain einsum path (NNLS always uses unfused).
    # 'gather_fused_ring' forces the fused-COMM kernel under the ring
    # strategies: the inter-chip factor rotation runs as a
    # make_async_remote_copy ring INSIDE the whole-iteration kernel
    # (ops.pallas_gather_ne.gather_solve_ring) — explicit knob + an
    # availability probe on the live mesh, never a banked verdict (the
    # multi-host safety rule: banked outcomes must not steer
    # collectives).  On the local/all_gather paths it degrades to
    # 'gather_fused_solve' (an S=1 ring IS that kernel, bitwise)
    solve_backend: str = "auto"
    # > 0: replace the exact per-row factorization with that many
    # warm-started Jacobi-CG steps (ops.solve) — inexact ALS.
    # The solve cost drops from r³/3 serial-recurrence work to cg_iters
    # batched MXU matvecs; the warm start is the previous ALS iterate, so
    # the outer fixed-point loop converges to the same solution.
    # Precedence: nonnegative (NNLS) > forced fused backends > cg_iters.
    cg_iters: int = 0
    # 'matfree' (default): apply A through the gathered factor rows —
    # A·p = YtY·p + Vgᵀ((c−1) ⊙ (Vg·p)) + λn·p — so the [n, r, r]
    # normal-equation tensor is NEVER built (kills both the NE einsum and
    # A's HBM round-trips).  'dense': build A once, run CG on it (the
    # A/B partner; also what the ring strategy always uses — its A is
    # accumulated across streamed shards, which a matvec can't replay
    # without re-streaming the ring per CG step).
    cg_mode: str = "matfree"
    # THE solve pre-regularization floor: the absolute jitter added to
    # every per-row Gram matrix before factorization (ops.solve — one
    # knob for solve_spd / solve_cg / solve_cg_matfree / solve_nnls, and
    # the base rung of the adaptive escalation ladder).  Static: a
    # different jitter is a different compiled step.
    jitter: float = DEFAULT_JITTER
    # residual-checked jitter escalation + CG fallback inside solve_spd
    # (ops.solve ADAPTIVE_JITTER_RUNGS).  OFF by default — the plain
    # step's jaxpr must stay byte-identical; the guardrails 'recover'
    # mode (resilience.guardrails) flips it on for its own step build.
    adaptive_solve: bool = False


def resolve_solve_path(cfg: AlsConfig, rank, matfree_capable=True):
    """Which solve path the probes actually select for this config — the
    single source of truth for both the half-step dispatch and the
    benchmark's attribution fields.  When the execution planner is armed
    (TPU_ALS_PLAN_CACHE != 'off', the default) the resolve goes through
    tpu_als.plan: a warm cache entry for this (device, jax, rank, dtype)
    key seeds the probe registry so the walk below runs with ZERO probe
    executions; a cold resolve runs the walk and banks its verdicts.
    Either way the verdict is computed by :func:`_resolve_solve_path_walk`
    — the planner supplies probe outcomes, never a different answer — and
    with the planner off this is exactly the pre-planner behavior
    (tests/test_plan.py pins the training-step jaxpr byte-identical)."""
    from tpu_als import plan as _plan

    if _plan.armed():
        label = (f"solve={cfg.solve_backend},cg={cfg.cg_iters},"
                 f"mode={cfg.cg_mode},nonneg={int(cfg.nonnegative)},"
                 f"matfree={int(matfree_capable)}")
        resolved = _plan.resolve_training(
            rank=rank, compute_dtype=cfg.compute_dtype, label=label,
            walk=lambda: _resolve_solve_path_walk(cfg, rank,
                                                  matfree_capable))
        if resolved is not None:
            return resolved
    return _resolve_solve_path_walk(cfg, rank, matfree_capable)


def _tuned_kernel_kwargs(cfg: AlsConfig, rank):
    """``(kernel_kwargs, table_dtype)`` from the banked autotune config,
    or ``({}, None)`` — the untuned fallback.  STRICTLY gated on the
    planner being armed AND ``TPU_ALS_AUTOTUNE=1``: with the gate off
    nothing is consulted and the fused-solve call sites receive no
    extra kwargs, so the training-step jaxpr stays byte-identical to
    the pre-autotune tree (tests pin this the plan_cache_off way).
    ``table_dtype`` is the tuned factor-table residency dtype (the bf16
    knob); None means "keep cfg.compute_dtype"."""
    from tpu_als import plan as _plan

    if not (_plan.armed() and _plan.autotune_enabled()):
        return {}, None
    kcfg = _plan.resolve_kernel_config(rank=int(rank),
                                       compute_dtype=cfg.compute_dtype)
    if not kcfg:
        return {}, None
    kwargs = {"panel": int(kcfg["panel"]), "max_wc": int(kcfg["max_wc"]),
              "vmem_budget": int(kcfg["vmem_budget"]),
              "depth": int(kcfg["depth"])}
    tdt = str(kcfg.get("dtype") or cfg.compute_dtype)
    return kwargs, (None if tdt == str(cfg.compute_dtype) else tdt)


def _resolve_solve_path_walk(cfg: AlsConfig, rank, matfree_capable=True):
    """The probe walk behind :func:`resolve_solve_path` (VERDICT r1 weak
    #3: record *resolved* backends, not requested ones).

    Returns a dict with ``resolved_solve_path`` ∈ {'einsum+nnls',
    'gatherfused_solve' (the whole-iteration fused kernel — no '+'
    solver suffix because the solve happens in-kernel),
    'matfree_cg{n}_warmstart' (inexact ALS, no NE einsum;
    n = cfg.cg_iters), 'einsum+cg{n}_warmstart' (inexact ALS on the
    einsum-built A), 'einsum+pallas_lanes',
    'einsum+pallas_lanes_blocked' (out-of-core lanes, ranks > 128),
    'einsum+pallas_cholesky', 'einsum+xla_cholesky'} plus the raw probe
    outcomes.  The NE-build prefix flips from 'einsum' to 'gatherfused'
    (e.g. 'gatherfused+pallas_lanes') when solve_backend='gather_fused'
    forces the DMA-gather kernel, or — under 'auto' — when its
    compile-and-validate probe AND its beats-the-einsum timing probe
    both pass (tpu_als.ops.pallas_gather_ne); 'auto' further upgrades
    to 'gatherfused_solve' when the whole-iteration kernel's own
    validate + timing probes beat the best unfused composition.

    ``matfree_capable=False``: the caller's half-step cannot apply A
    matrix-free (the ring strategy — its A is accumulated across
    streamed shards) — cg_mode='matfree' then RESOLVES to the dense CG
    label, because that is what executes.
    """
    from tpu_als.ops import pallas_lanes, pallas_solve
    from tpu_als.ops.solve import SOLVE_PATH_NAMES, auto_solve_backend
    from tpu_als.utils.platform import on_tpu

    tpu = on_tpu()
    # probe lazily: only the branches that consume a probe outcome run it
    # (each probe compiles+executes a kernel on TPU); None = not probed
    solve_ok = lanes_ok = blocked_ok = gather_ok = gsolve_ok = None
    if cfg.nonnegative:
        path = "einsum+nnls"
    elif cfg.solve_backend == "gather_fused_solve":
        # forced whole-iteration fusion: no probe — dispatch would ignore
        # its outcome, and the probe costs a Mosaic compile+execute on
        # every resolve.  Off-TPU the kernel runs in interpret mode.
        path = "gatherfused_solve"
    elif cfg.solve_backend == "gather_fused_ring":
        # forced fused-comm ring: the ring strategies move the rotation
        # in-kernel (comm.ring_fused_half_step); the local/all_gather
        # paths treat this as gather_fused_solve (the S=1 degenerate
        # ring, bitwise the same kernel body).  The on-mesh availability
        # probe (pallas_gather_ne.ring_available) gates the SHARDED
        # dispatch at step-build time, not here — resolve runs per
        # process and must not execute collectives.
        path = "gatherfused_ring"
    elif cfg.solve_backend == "gather_fused":
        # forced DMA-gather NE build; the solve still walks the probe
        # order (the kernel writes A/b, the solve stays on lanes/xla).
        # Off-TPU the kernel runs in interpret mode, so no gate here.
        base = SOLVE_PATH_NAMES[auto_solve_backend(rank)]
        path = "gatherfused" + base[len("einsum"):]
    elif cfg.cg_iters > 0:
        # inexact ALS: no factorization, no Pallas kernel, no probe —
        # matfree applies A through the factor rows (no NE einsum at
        # all); dense runs the matvecs on the einsum-built A
        path = (f"matfree_cg{cfg.cg_iters}_warmstart"
                if cfg.cg_mode == "matfree" and matfree_capable
                else f"einsum+cg{cfg.cg_iters}_warmstart")
    else:
        # the same probe walk solve_spd's dispatch runs — prewarming here
        # IS the prewarm contract; the re-reads below are cache hits
        path = SOLVE_PATH_NAMES[auto_solve_backend(rank)]
        from tpu_als.ops import pallas_lanes_blocked

        lanes_ok = bool(tpu and pallas_lanes.available(rank))
        blocked_ok = (None if lanes_ok
                      else bool(tpu and pallas_lanes_blocked.available(rank)))
        solve_ok = (None if (lanes_ok or blocked_ok)
                    else bool(tpu and pallas_solve.available(rank)))
        if cfg.solve_backend == "auto":
            # NE-build upgrade: the DMA-gather kernel replaces the
            # gather+einsum build ONLY when it validates AND measures
            # faster than the einsum path on this chip (both probes
            # cached per process; off-TPU both return False, so CPU runs
            # keep the einsum path under 'auto')
            from tpu_als.ops import pallas_gather_ne

            gather_ok = bool(
                tpu and pallas_gather_ne.available(rank, cfg.compute_dtype)
                and pallas_gather_ne.faster_than_einsum(
                    rank, cfg.compute_dtype))
            if gather_ok:
                path = "gatherfused" + path[len("einsum"):]
            # deepest fusion last: the whole-iteration kernel replaces
            # NE build AND solve only when it validates AND measures
            # faster than the best unfused composition (which the speed
            # probe itself picks via faster_than_einsum)
            gsolve_ok = bool(
                tpu
                and pallas_gather_ne.solve_available(rank,
                                                     cfg.compute_dtype)
                and pallas_gather_ne.solve_faster_than_unfused(
                    rank, cfg.compute_dtype))
            if gsolve_ok:
                path = "gatherfused_solve"
    return {
        "solve_backend_requested": cfg.solve_backend,
        "gather_ne_probe": gather_ok,
        "gather_solve_probe": gsolve_ok,
        "pallas_lanes_probe": lanes_ok,
        "pallas_lanes_blocked_probe": blocked_ok,
        "pallas_solve_probe": solve_ok,
        "resolved_solve_path": path,
        "on_tpu": tpu,
    }


def init_factors(key, num_rows, rank, dtype=jnp.float32):
    """Seeded init: unit-norm gaussian rows, like the reference stack's
    XORShiftRandom + normalize init (SURVEY.md §3.1 ``initialize``)."""
    x = jax.random.normal(key, (num_rows, rank), dtype=jnp.float32)
    nrm = jnp.linalg.norm(x, axis=1, keepdims=True)
    return (x / jnp.maximum(nrm, 1e-12)).astype(dtype)


def local_half_step(V_full, buckets, num_rows, cfg: AlsConfig, YtY=None,
                    chunk_elems=1 << 19, prev=None, reg=None, alpha=None):
    """Solve all rows of one side given the full opposite factor matrix.

    V_full [N_opposite, r]; buckets: list[Bucket] (device arrays); returns
    new factors [num_rows, r].  Everything static-shaped; per bucket the rows
    are processed in scan chunks so the gathered [chunk, w, r] tensor stays
    within the HBM budget set by ``chunk_elems`` — pass the value the buckets
    were built with (``CsrBuckets.chunk_elems``) so row padding divides the
    chunk exactly.

    ``prev`` [num_rows, r]: the solved side's CURRENT factors — the warm
    start for the inexact-ALS CG path (``cfg.cg_iters > 0``); ignored by
    the exact solvers.

    ``reg``: overrides ``cfg.reg_param``, and may be a TRACED scalar —
    the single-device step passes it dynamically so configs differing
    only in regParam share one compiled executable (a CrossValidator
    regParam grid then compiles once per rank instead of once per cell).
    The whole-iteration fused branch ('gatherfused_solve') keeps the
    static ``cfg.reg_param``/``cfg.alpha`` (its Pallas tail bakes them
    into the kernel; make_step keeps them in the jit cache key there).
    """
    if reg is None:
        reg = cfg.reg_param
    if alpha is None:
        alpha = cfg.alpha
    r = V_full.shape[-1]
    cdt = jnp.dtype(cfg.compute_dtype)
    # cast ONCE before the gathers: the gather reads padded_nnz × r elements
    # (>> N × r), so under bfloat16 casting first halves the dominant HBM
    # stream; casting after the gather would move f32 bytes and only shrink
    # the einsum inputs
    V_comp = V_full.astype(cdt)
    out = jnp.zeros((num_rows, r), dtype=jnp.float32)

    if cfg.solve_backend not in ("auto", "unfused", "gather_fused",
                                 "gather_fused_solve",
                                 "gather_fused_ring"):
        raise ValueError(
            f"unknown solve_backend {cfg.solve_backend!r} (expected "
            "'auto', 'unfused', 'gather_fused', 'gather_fused_solve' or "
            "'gather_fused_ring')")
    resolved = resolve_solve_path(cfg, r)
    # DMA-gather fused NE build (ops.pallas_gather_ne): the factor rows
    # stream HBM→VMEM inside the kernel, so the Vg = V_comp[c] gather
    # below never runs and the [chunk, w, r] intermediate never exists —
    # trainer_chunk drops it from the memory model (fused_gather=True).
    # 'gatherfused_solve' goes further: the ridge/YtY tail and the
    # Cholesky solve also run in-kernel, so A/b never exist in HBM.
    # Off-TPU the kernels run in interpret mode (CPU tier-1 exercises
    # them).
    # 'gatherfused_ring' on this LOCAL path is the S=1 degenerate ring —
    # the same whole-iteration kernel body, bitwise — so it shares the
    # gsolve dispatch (the in-kernel rotation only exists under the ring
    # strategies' shard_map; comm.ring_fused_half_step owns that case)
    gsolve = resolved["resolved_solve_path"] in ("gatherfused_solve",
                                                 "gatherfused_ring")
    gather = resolved["resolved_solve_path"].startswith("gatherfused+")
    gather_interpret = not resolved["on_tpu"]
    # banked autotune knobs for the fused-solve kernel ({} unless armed
    # AND TPU_ALS_AUTOTUNE=1 — the byte-identical-jaxpr-off contract);
    # a tuned table dtype overrides the kernel's stream dtype only
    tuned_kw, tuned_dt = (_tuned_kernel_kwargs(cfg, r) if gsolve
                          else ({}, None))
    kdt = jnp.dtype(tuned_dt) if tuned_dt else cdt
    cg = (cfg.cg_iters > 0 and not cfg.nonnegative
          and not (gather or gsolve))
    if cfg.cg_mode not in ("matfree", "dense"):
        raise ValueError(f"unknown cg_mode {cfg.cg_mode!r} "
                         "(expected 'matfree' or 'dense')")
    matfree = cg and cfg.cg_mode == "matfree"

    for b in buckets:
        nb, w = b.cols.shape
        chunk = trainer_chunk(nb, w, r, chunk_elems,
                              fused_gather=gather or gsolve)
        nchunks = nb // chunk
        cols = b.cols.reshape(nchunks, chunk, w)
        vals = b.vals.reshape(nchunks, chunk, w)
        mask = b.mask.reshape(nchunks, chunk, w)
        rows = b.rows.reshape(nchunks, chunk)

        def solve_chunk(args):
            c, v, m, rw = args
            if gsolve:
                from tpu_als.ops.pallas_gather_ne import (
                    gather_fused_solve_explicit,
                    gather_fused_solve_implicit,
                )

                # whole-iteration fusion: gather, Gram, ridge/YtY tail
                # AND the blocked Cholesky solve in one kernel — only x
                # comes back; A/b/Vg never exist in HBM.  reg/alpha/
                # jitter are STATIC here (the Pallas tail bakes them in;
                # make_step keeps them in the cache key for this path).
                with jax.named_scope("gather_fused_solve"):
                    if cfg.implicit_prefs:
                        return gather_fused_solve_implicit(
                            V_comp.astype(kdt), c, v.astype(kdt),
                            m.astype(kdt),
                            cfg.reg_param, cfg.alpha,
                            YtY.astype(jnp.float32),
                            jitter=cfg.jitter, **tuned_kw,
                            interpret=gather_interpret)
                    return gather_fused_solve_explicit(
                        V_comp.astype(kdt), c, v.astype(kdt),
                        m.astype(kdt),
                        cfg.reg_param, jitter=cfg.jitter, **tuned_kw,
                        interpret=gather_interpret)
            if gather:
                from tpu_als.ops.pallas_gather_ne import (
                    gather_normal_eq_explicit,
                    gather_normal_eq_implicit,
                )

                # fused DMA-gather + Gram build: A/b come straight off
                # the HBM-resident V_comp; semantics are bitwise the
                # normal_eq_* path (same weights/ridge/YtY/count — the
                # empty-row guard stays in solve_spd, as always)
                with jax.named_scope("gather_fused_ne"):
                    if cfg.implicit_prefs:
                        A, rhs, count = gather_normal_eq_implicit(
                            V_comp, c, v.astype(cdt), m.astype(cdt),
                            reg, alpha, YtY.astype(jnp.float32),
                            interpret=gather_interpret)
                    else:
                        A, rhs, count = gather_normal_eq_explicit(
                            V_comp, c, v.astype(cdt), m.astype(cdt),
                            reg, interpret=gather_interpret)
                with jax.named_scope("solve"):
                    return solve_spd(A.astype(jnp.float32),
                                     rhs.astype(jnp.float32), count,
                                     jitter=cfg.jitter,
                                     adaptive=cfg.adaptive_solve)
            with jax.named_scope("gather_factors"):
                Vg = V_comp[c]
            # warm start for the inexact (CG) solvers: the solved side's
            # current rows.  Padding rows (index num_rows) clip to a real
            # row's stale value, but their count is 0 so CG drives them
            # to 0 and the scatter drops them anyway.  One site for both
            # CG modes so their trajectories cannot diverge.
            x0 = None
            if cg and prev is not None:
                x0 = prev.astype(jnp.float32)[jnp.clip(rw, 0, num_rows - 1)]
            if matfree:
                # matrix-free inexact solve (ops.solve.solve_cg_matfree):
                # A applied through Vg — neither the NE einsum nor the
                # [chunk, r, r] tensor ever exists
                with jax.named_scope("cg_matfree"):
                    return solve_cg_matfree(
                        Vg, v, m, reg,
                        implicit=cfg.implicit_prefs, alpha=alpha,
                        YtY=YtY, x0=x0, iters=cfg.cg_iters,
                        jitter=cfg.jitter)
            with jax.named_scope("normal_eq"):
                if cfg.implicit_prefs:
                    A, rhs, count = normal_eq_implicit(
                        Vg, v.astype(cdt), m.astype(cdt), reg,
                        alpha, YtY.astype(jnp.float32),
                    )
                else:
                    A, rhs, count = normal_eq_explicit(
                        Vg, v.astype(cdt), m.astype(cdt), reg
                    )
            A = A.astype(jnp.float32)
            rhs = rhs.astype(jnp.float32)
            with jax.named_scope("solve"):
                if cfg.nonnegative:
                    return solve_nnls(A, rhs, count, sweeps=cfg.nnls_sweeps,
                                      jitter=cfg.jitter)
                if cg:
                    return solve_cg(A, rhs, count, x0=x0,
                                    iters=cfg.cg_iters, jitter=cfg.jitter)
                return solve_spd(A, rhs, count, jitter=cfg.jitter,
                                 adaptive=cfg.adaptive_solve)

        if nchunks == 1:
            x = solve_chunk((cols[0], vals[0], mask[0], rows[0]))
            xs = x[None]
        else:
            xs = jax.lax.map(solve_chunk, (cols, vals, mask, rows))
        # padding rows carry index num_rows -> out of bounds -> dropped
        out = out.at[b.rows].set(
            xs.reshape(nb, r), mode="drop", unique_indices=True
        )
    return out


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "num_users", "num_items",
                     "user_chunk_elems", "item_chunk_elems"),
    donate_argnums=(0, 1))
def _step_jit(U, V, ub, ib, reg, alpha, *, cfg, num_users, num_items,
              user_chunk_elems, item_chunk_elems):
    """THE jitted full ALS iteration — module-level, so its jit cache is
    keyed on (static config, array shapes) and SHARED across fits.
    ``reg`` and ``alpha`` are traced scalars: estimators differing only
    in regParam/alpha reuse one compiled executable (see make_step)."""
    if cfg.implicit_prefs:
        YtY_u = compute_yty(U)
        V = local_half_step(U, ib, num_items, cfg, YtY_u,
                            item_chunk_elems, prev=V, reg=reg, alpha=alpha)
        YtY_v = compute_yty(V)
        U = local_half_step(V, ub, num_users, cfg, YtY_v,
                            user_chunk_elems, prev=U, reg=reg, alpha=alpha)
    else:
        V = local_half_step(U, ib, num_items, cfg,
                            chunk_elems=item_chunk_elems, prev=V, reg=reg)
        U = local_half_step(V, ub, num_users, cfg,
                            chunk_elems=user_chunk_elems, prev=U, reg=reg)
    return U, V


def make_step(user_buckets, item_buckets, num_users, num_items, cfg: AlsConfig,
              user_chunk_elems=1 << 19, item_chunk_elems=1 << 19):
    """Build the jitted full ALS iteration (item half-step then user
    half-step, the reference stack's order — SURVEY.md §3.1).

    The rating buckets are passed to the jitted function as *arguments*, not
    closure captures: a closed-over device array is baked into the HLO as a
    constant, which at ML-25M scale means shipping ~1 GB of rating data
    inside the compile payload (and re-compiling whenever the data changes).
    As arguments they stay on device and the compiled step is reusable.

    regParam AND alpha enter the compiled step as TRACED scalars and are
    stripped from the static cache key (along with max_iter/seed, which
    the step body never reads), so a tuning grid over regParam/alpha at
    fixed rank/data compiles ONCE instead of once per grid cell — the
    recompile tax on a CrossValidator was ~30s × cells on a v5e.  The
    whole-iteration fused config ('gatherfused_solve') keeps both static
    (its Pallas tail bakes them into the kernel).
    """
    # probe the solve kernels EAGERLY: a probe firing inside the jit trace
    # below cannot run (and the jit cache would pin the fallback path for
    # the step's lifetime) — see ops.solve.prewarm_solve
    resolved = resolve_solve_path(cfg, cfg.rank)
    if resolved["resolved_solve_path"] == "gatherfused_solve":
        # the whole-iteration kernel bakes reg/alpha into its Pallas tail
        # (static lowering) — keep them in the cache key so two regParams
        # compile two steps instead of sharing a wrong executable
        cfg_key = _dc_replace(cfg, max_iter=0, seed=0)
    else:
        cfg_key = _dc_replace(cfg, reg_param=0.0, alpha=0.0,
                              max_iter=0, seed=0)
    reg = jnp.float32(cfg.reg_param)
    alpha = jnp.float32(cfg.alpha)

    def step(U, V):
        return _step_jit(U, V, user_buckets, item_buckets, reg, alpha,
                         cfg=cfg_key, num_users=num_users,
                         num_items=num_items,
                         user_chunk_elems=user_chunk_elems,
                         item_chunk_elems=item_chunk_elems)

    return step


def train(user_csr, item_csr, cfg: AlsConfig, callback=None, init=None,
          start_iter=0):
    """Single-device ALS training loop.

    ``user_csr``: CsrBuckets keyed by user (cols = item idx) — solves U.
    ``item_csr``: CsrBuckets keyed by item (cols = user idx) — solves V.
    ``callback(iteration, U, V)`` runs between iterations (logging,
    checkpointing); the per-iteration compute itself is one jitted call with
    zero host round-trips inside.

    ``init``: optional ``(U0, V0)`` warm start — the failure-recovery path
    (SURVEY.md §5.3): ALS is a fixed-point iteration, so resuming from a
    checkpoint's factors at ``start_iter`` reproduces the uninterrupted run
    exactly.  Runs the remaining ``cfg.max_iter - start_iter`` iterations.
    """
    num_users = user_csr.num_rows
    num_items = item_csr.num_rows
    if init is not None:
        U = jnp.asarray(init[0], dtype=jnp.float32)
        V = jnp.asarray(init[1], dtype=jnp.float32)
    else:
        key = jax.random.PRNGKey(cfg.seed)
        ku, kv = jax.random.split(key)
        U = init_factors(ku, num_users, cfg.rank)
        V = init_factors(kv, num_items, cfg.rank)

    ub = jax.device_put(user_csr.device_buckets())
    ib = jax.device_put(item_csr.device_buckets())
    step = make_step(ub, ib, num_users, num_items, cfg,
                     user_csr.chunk_elems, item_csr.chunk_elems)
    # stage attribution (obs/trace.py): armed via TPU_ALS_STAGE_ATTRIBUTION
    # or obs.trace.enable_stage_attribution(), the fused step above is
    # replaced by its decomposed fence-timed twin and per-stage seconds
    # land in train.stage_seconds histograms.  Disarmed (the default),
    # this one boolean check per train() call is the entire cost — the
    # jitted step is untouched (pinned in tests/test_attribution.py).
    from tpu_als.obs.trace import stage_attribution_armed

    if stage_attribution_armed():
        from tpu_als.perf.attribution import make_attributed_step

        step = make_attributed_step(ub, ib, num_users, num_items, cfg,
                                    user_csr.chunk_elems,
                                    item_csr.chunk_elems)

    # numerical-health guardrails (resilience/guardrails.py): armed via
    # --guardrails warn|recover / TPU_ALS_GUARDRAILS.  Same discipline as
    # stage attribution above — disarmed, this one mode check is the
    # entire cost and the jitted step is byte-identical (pinned in
    # tests/test_guardrails.py).  Armed, sentinels are a SEPARATE small
    # jitted reduction read at the callback boundary; the production
    # step is never modified.  'recover' additionally builds its step
    # with the adaptive solve ladder so ill-conditioned Gram rows heal
    # in-device before a sentinel ever has to trip.
    from tpu_als.resilience import faults
    from tpu_als.resilience.guardrails import Monitor, guardrails_mode

    gmode = guardrails_mode()
    monitor = None
    if gmode != "off":
        monitor = Monitor(cfg, gmode)
        if gmode == "recover" and not stage_attribution_armed():
            step = make_step(ub, ib, num_users, num_items,
                             _dc_replace(cfg, adaptive_solve=True),
                             user_csr.chunk_elems, item_csr.chunk_elems)
    gram_fault = faults.armed("solve.gram")

    it = start_iter
    retry = False
    while it < cfg.max_iter:
        if monitor is not None:
            monitor.keep_last_good(U, V, retry=retry)
        U, V = step(U, V)
        if gram_fault and faults.check("solve.gram") == "corrupt":
            # chaos hook: poison one factor row post-step, host-level —
            # exactly what a blown Gram solve leaves behind
            U = U.at[0].set(jnp.nan)
        if monitor is not None:
            trip = monitor.judge(it + 1, U, V)
            if trip is not None and monitor.mode == "recover":
                U, V, reg_scale = monitor.rollback(it + 1, trip)
                # rebuild with bumped reg: reg_param is a TRACED scalar
                # stripped from the jit cache key (make_step docstring),
                # so this is a cache hit, not a recompile
                step = make_step(
                    ub, ib, num_users, num_items,
                    _dc_replace(cfg, adaptive_solve=True,
                                reg_param=cfg.reg_param * reg_scale),
                    user_csr.chunk_elems, item_csr.chunk_elems)
                retry = True
                continue
        if (monitor is not None and retry and monitor.mode == "recover"
                and monitor.reg_scale != 1.0):
            # the reg bump is TRANSIENT: the retried iteration cleared,
            # so drop back to the configured regularization — a
            # permanent bump would quietly change the model the user
            # asked for (also a jit cache hit, same as above)
            monitor.reg_scale = 1.0
            step = make_step(ub, ib, num_users, num_items,
                             _dc_replace(cfg, adaptive_solve=True),
                             user_csr.chunk_elems, item_csr.chunk_elems)
        retry = False
        it += 1
        if callback is not None:
            callback(it, U, V)
    return U, V


@jax.jit
def predict(U, V, u_idx, i_idx, u_valid, i_valid):
    """Gather-dot scoring: the TPU replacement for the reference stack's two
    distributed hash joins in ``ALSModel.transform`` (SURVEY.md §3.2).

    Out-of-range / cold ids (valid mask False) yield NaN — the
    ``coldStartStrategy='nan'`` semantic; 'drop' filters host-side.
    """
    u = jnp.clip(u_idx, 0, U.shape[0] - 1)
    i = jnp.clip(i_idx, 0, V.shape[0] - 1)
    scores = jnp.einsum("nr,nr->n", U[u], V[i])
    ok = (
        u_valid & i_valid
        & (u_idx >= 0) & (u_idx < U.shape[0])
        & (i_idx >= 0) & (i_idx < V.shape[0])
    )
    return jnp.where(ok, scores, jnp.nan)
