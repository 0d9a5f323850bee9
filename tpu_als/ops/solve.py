"""Batched normal-equation build + least-squares solves — the numerics core.

This replaces the reference stack's per-row scalar path (Spark MLlib's
``NormalEquation`` accumulating ``A += x xᵀ`` one rating at a time via BLAS
``dspr``, then one LAPACK ``dppsv`` packed-Cholesky call *per entity row* —
canonical upstream ``mllib/src/main/scala/org/apache/spark/ml/recommendation/
ALS.scala``, ``NormalEquation`` / ``CholeskySolver`` / ``NNLSSolver``;
SURVEY.md §2.B5) with one **batched** einsum + Cholesky over every row of a
shard at once, which is the shape the TPU MXU wants: a handful of large
contractions instead of millions of rank-2 BLAS calls.

The solver family, exact → inexact: batched Cholesky (:func:`solve_spd`,
kernel-accelerated via tpu_als.ops.pallas_*), fixed-sweep NNLS
(:func:`solve_nnls`), and warm-started Jacobi-CG for inexact ALS —
:func:`solve_cg` on the built tensor, :func:`solve_cg_matfree` applying
the operator straight through the gathered factor rows.

The *build* side has the same exact/fused split: the einsum builds here
consume a materialized ``Vg`` gathered by XLA, while
:mod:`tpu_als.ops.pallas_gather_ne` DMA-gathers factor rows from the
HBM-resident table directly into the Gram accumulation (``Vg`` never
touches HBM — ~59% fewer modeled NE-build bytes at the headline shape,
see docs/roofline.md). Its wrappers reuse this module's weighting
expressions verbatim (:func:`implicit_weights`, the ``reg·count`` ridge)
so the fused build is bitwise-equal to :func:`normal_eq_explicit` /
:func:`normal_eq_implicit` at f32 in the single-width-chunk regime.

Shapes use the padded-CSR convention from :mod:`tpu_als.core.ratings`:

  ``Vg``   [n, w, r]  gathered opposite-side factor rows per entity
  ``vals`` [n, w]     ratings (0 in padding slots)
  ``mask`` [n, w]     1.0 for real entries, 0.0 for padding
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The ONE default Tikhonov jitter, threaded everywhere a solve can be
# reached: every solver signature below, the Pallas probe matrices, the
# fused-kernel default, fold-in, and ``AlsConfig.jitter`` all reference
# this name.  A literal 1e-6 anywhere else is a lint finding
# (magic-jitter, tpu_als/analysis/lint.py): a drifted copy means the
# attribution twin or a probe solves a DIFFERENTLY-regularized system
# than the production step and the bitwise-equivalence pins lie.
DEFAULT_JITTER = 1e-6

# The adaptive-solve escalation ladder (resilience guardrails, docs/
# resilience.md): rungs are ABSOLUTE jitter levels tried above the
# configured base jitter, in order, before the CG fallback.  Residuals
# are judged against _ADAPTIVE_TOL relative to ||b|| — loose enough that
# a healthy f32 Cholesky always clears it on the first rung (the armed
# overhead is then one residual matvec), tight enough that a
# numerically-singular factorization (NaN/Inf backsubstitution, or a
# wildly wrong x from a near-zero pivot) fails it.
ADAPTIVE_JITTER_RUNGS = (1e-4, 1e-2)
_ADAPTIVE_TOL = 1e-2


class SolveUnstable(ArithmeticError):
    """Every rung of the adaptive solve ladder failed — the per-row
    system is beyond what jitter escalation and the CG fallback can
    stabilize (typed so callers distinguish 'the data is numerically
    hostile' from a programming error)."""

    def __init__(self, bad_rows, total_rows):
        super().__init__(
            f"adaptive SPD solve failed on {bad_rows} of {total_rows} "
            f"rows after jitter escalation {ADAPTIVE_JITTER_RUNGS} and "
            "the CG fallback — the Gram systems are numerically "
            "unsalvageable (see docs/resilience.md guardrails)")
        self.bad_rows = bad_rows
        self.total_rows = total_rows


def normal_eq_explicit(Vg, vals, mask, reg):
    """Normal equations for explicit-feedback ALS (ALS-WR weighting).

    For each entity u with rated factor rows ``v_k`` and ratings ``r_k``:

        A_u = Σ_k v_k v_kᵀ + λ·n_u·I        b_u = Σ_k r_k v_k

    λ is scaled by the per-entity rating count ``n_u`` — the "weighted-λ"
    scheme Spark ALS uses (``regParam * ne.k`` in the reference stack's solver,
    SURVEY.md §2.B5), which makes regParam roughly scale-free in dataset size.

    Returns ``(A [n,r,r], b [n,r], count [n])``.
    """
    Vm = Vg * mask[..., None]
    # Σ v vᵀ over the w axis. One MXU-friendly contraction for all n rows.
    A = jnp.einsum("nwr,nws->nrs", Vm, Vm, preferred_element_type=jnp.float32)
    b = jnp.einsum("nw,nwr->nr", vals * mask, Vg, preferred_element_type=jnp.float32)
    count = jnp.sum(mask, axis=-1)
    r = Vg.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    A = A + (reg * count)[:, None, None] * eye
    return A, b, count


def implicit_weights(vals, mask, alpha):
    """Hu–Koren–Volinsky weighting: ``(c − 1, preference)``.

    THE shared formula consumed by the dense normal-equation build
    (:func:`normal_eq_implicit`) and the matrix-free CG operator
    (:func:`solve_cg_matfree`) — one site, so the two solvers cannot
    drift on the confidence/preference semantics.
    """
    conf_m1 = alpha * jnp.abs(vals) * mask          # c − 1, 0 in padding
    pref = (vals > 0).astype(vals.dtype)
    return conf_m1, pref


def normal_eq_implicit(Vg, vals, mask, reg, alpha, YtY):
    """Normal equations for implicit-feedback ALS (Hu–Koren–Volinsky).

    Confidence ``c_k = 1 + α·|r_k|``, preference ``p_k = 1 if r_k > 0 else 0``.
    Using the YᵀY trick (SURVEY.md §3.1 — the reference stack computes YtY
    once per half-step via ``treeAggregate``; here it's one einsum + psum):

        A_u = YᵀY + Σ_k (c_k − 1) v_k v_kᵀ + λ·n_u·I
        b_u = Σ_k c_k p_k v_k

    Negative ratings contribute confidence but preference 0, and — matching
    the reference solver's ``numExplicits`` — only ratings > 0 count toward
    the λ·n regularization scaling.

    Returns ``(A [n,r,r], b [n,r], count [n])``.
    """
    conf_m1, pref = implicit_weights(vals, mask, alpha)
    A = jnp.einsum(
        "nw,nwr,nws->nrs", conf_m1, Vg, Vg, preferred_element_type=jnp.float32
    )
    b = jnp.einsum(
        "nw,nwr->nr", (1.0 + conf_m1) * pref * mask, Vg,
        preferred_element_type=jnp.float32,
    )
    count = jnp.sum(pref * mask, axis=-1)
    r = Vg.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    A = A + YtY[None] + (reg * count)[:, None, None] * eye
    return A, b, count


def compute_yty(V):
    """YᵀY over all (valid) factor rows; invalid rows must be zero.

    [N, r] -> [r, r].  Under ``shard_map`` callers ``psum`` the result over the
    mesh axis — the analog of the reference stack's ``treeAggregate``.
    """
    return jnp.einsum("nr,ns->rs", V, V, preferred_element_type=jnp.float32)


# what each backend of the SPD solve is called in a resolved solve path
# (``core.als.resolve_solve_path``, the ``foldin_solve_path`` event)
SOLVE_PATH_NAMES = {"lanes": "einsum+pallas_lanes",
                    "lanes_blocked": "einsum+pallas_lanes_blocked",
                    "pallas": "einsum+pallas_cholesky",
                    "xla": "einsum+xla_cholesky"}


def auto_solve_backend(rank):
    """THE preference-ordered probe walk for the SPD solve — the single
    source of truth shared by ``solve_spd``'s 'auto' branch,
    ``prewarm_solve``, and ``resolve_solve_path`` (core/als.py), so the
    prewarmed probes are exactly the ones the dispatch consults.

    Returns 'lanes' | 'lanes_blocked' | 'pallas' | 'xla'.  Each Pallas
    kernel engages only after its compile-and-validate probe passes on
    the local Mosaic (probes are cached per process).  'lanes' owns
    ranks <= 128 (whole working set VMEM-resident); 'lanes_blocked' owns
    ranks above (same layout, 128-blocks streamed out-of-core —
    tpu_als.ops.pallas_lanes_blocked; rank-256 config-3 path).
    """
    from tpu_als.ops import pallas_lanes, pallas_lanes_blocked, pallas_solve
    from tpu_als.utils.platform import on_tpu

    if not on_tpu():
        return "xla"
    if pallas_lanes.available(rank):
        return "lanes"
    if pallas_lanes_blocked.available(rank):
        return "lanes_blocked"
    if pallas_solve.available(rank):
        return "pallas"
    return "xla"


def prewarm_solve(rank):
    """Run the solve-kernel probes EAGERLY for this rank (cached per
    process).  Anything that jit-traces a path reaching
    ``solve_spd(backend='auto')`` must probe eagerly first: a probe cannot
    execute inside a trace (tpu_als.utils.platform.probe_kernel degrades
    that trace to the fallback path without caching), and the jit cache
    would then pin the slow path for the compiled step's lifetime.
    Callers: ``fold_in`` and ``scripts/ablate.py`` directly; the training
    step builders (``make_step`` and the tpu_als.parallel.trainer
    builders) get the same effect through their eager
    ``resolve_solve_path`` call — all of them walk the same
    :func:`auto_solve_backend` probe order.
    """
    auto_solve_backend(rank)


def _dispatch_spd(A, b, backend):
    """One batched Cholesky solve of the (already pre-regularized) A —
    the backend dispatch shared by the plain and adaptive solve_spd
    paths, so every escalation rung runs on the SAME kernel the plain
    solve would."""
    if backend == "lanes":
        from tpu_als.ops import pallas_lanes

        # forced-lanes path: validate the panel width on this Mosaic first
        # (cached per process; free after an eager prewarm).  Without this,
        # selected_panel(r) returns DEFAULT_PANEL when available() never
        # ran, and the panel=8 fused trailing update's extra [panel, r,
        # LANES] scratch could hit a VMEM/Mosaic failure the auto path's
        # probe-and-fallback would have avoided (ADVICE r2).  When the
        # probe could NOT validate a width (off-TPU, probe failure, or
        # probe-inside-trace degrade), run the rank-1 recurrence (panel=1)
        # — never an unvalidated fused update.
        r = A.shape[-1]
        ok = pallas_lanes.available(r)
        panel = pallas_lanes.selected_panel(r) if ok else 1
        mxu = pallas_lanes.selected_mxu(r) if ok else False
        return pallas_lanes.spd_solve_lanes(A, b, panel=panel, mxu=mxu)
    if backend == "lanes_blocked":
        from tpu_als.ops import pallas_lanes_blocked

        # same discipline as lanes: the MXU trailing update engages only
        # after the probe validated it on this Mosaic
        r = A.shape[-1]
        mxu = (pallas_lanes_blocked.selected_mxu(r)
               if pallas_lanes_blocked.available(r) else False)
        return pallas_lanes_blocked.spd_solve_lanes_blocked(A, b, mxu=mxu)
    if backend == "pallas":
        from tpu_als.ops.pallas_solve import spd_solve_pallas

        return spd_solve_pallas(A, b)
    L = jnp.linalg.cholesky(A)
    y = jax.scipy.linalg.solve_triangular(L, b[..., None], lower=True)
    x = jax.scipy.linalg.solve_triangular(
        L, y, lower=True, trans=1
    )[..., 0]
    return x


def solve_spd(A, b, count, jitter=DEFAULT_JITTER, backend="auto",
              adaptive=False):
    """Batched SPD solve via Cholesky: x = A⁻¹ b for each row.

    Rows with ``count == 0`` (entities with no ratings in this shard — padding
    rows or cold entities) get A replaced by I so the factorization stays
    finite; their b is 0 so the solution is exactly 0.  This is the batched
    equivalent of the reference solver's per-row ``dppsv`` (SURVEY.md §2.C1).

    backend: 'auto' routes, in preference order, to (1) the batch-in-lanes
    Pallas kernel (tpu_als.ops.pallas_lanes — the serial Cholesky
    recurrence vectorized across 128 matrices in the lane dimension;
    measured 2.2x the blocked kernel at rank 128 on v5e, rank <= 128
    only), (2) the out-of-core blocked lanes kernel for ranks above 128
    (tpu_als.ops.pallas_lanes_blocked — same layout, 128-blocks streamed
    through VMEM, substitutions on XLA), (3) the VMEM blocked-Cholesky
    kernel (tpu_als.ops.pallas_solve, any rank), (4) the XLA
    cholesky/triangular_solve lowering — whose column-sequential HBM
    passes are the training-loop bottleneck at six-figure batch sizes.
    Each kernel engages only when its compile-and-validate probe passes
    on the local Mosaic version.  'lanes' / 'lanes_blocked' / 'pallas' /
    'xla' force a specific path.

    ``adaptive=True`` (the guardrails recover path, docs/resilience.md):
    the empty-row identity guard and ``jitter`` pre-regularization apply
    as always, then the solution is RESIDUAL-CHECKED — rows whose
    relative residual fails escalate through ADAPTIVE_JITTER_RUNGS
    re-solves and finally a Jacobi-CG fallback, all under one
    ``lax.cond`` so the healthy common case pays only the residual
    matvec.  Escalation happens at THIS layer, above the backend
    dispatch, so the xla / pallas_lanes / gather_fused paths all inherit
    it.  A row the full ladder cannot save keeps its (non-finite or
    residual-failing) CG answer — the host-side verdict and the typed
    :class:`SolveUnstable` live in :func:`solve_spd_checked` and the
    training sentinels (raising is impossible inside a trace).
    """
    if A.dtype == jnp.bfloat16:
        # no bf16 Cholesky lowering (and an 8-bit mantissa is hopeless for
        # a factorization anyway): solve in f32, hand back bf16.  The
        # Python-level dtype gate leaves the f32 training trace untouched.
        return solve_spd(A.astype(jnp.float32), b.astype(jnp.float32),
                         count, jitter=jitter, backend=backend,
                         adaptive=adaptive).astype(jnp.bfloat16)
    r = A.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    empty = (count <= 0)[:, None, None]
    A0 = jnp.where(empty, eye, A)
    A = A0 + jitter * eye
    if backend == "auto":
        backend = auto_solve_backend(r)
    if backend not in ("lanes", "lanes_blocked", "pallas", "xla"):
        raise ValueError(f"unknown solve backend {backend!r} (expected "
                         "'auto', 'lanes', 'lanes_blocked', 'pallas' or "
                         "'xla')")
    if not adaptive:
        return _dispatch_spd(A, b, backend)

    def _row_ok(x, Areg):
        res = jnp.einsum("nrs,ns->nr", Areg, x,
                         preferred_element_type=jnp.float32) - b
        rnorm = jnp.linalg.norm(res, axis=-1)
        bnorm = jnp.linalg.norm(b, axis=-1)
        finite = jnp.all(jnp.isfinite(x), axis=-1)
        return finite & (rnorm <= _ADAPTIVE_TOL * (bnorm + 1.0))

    x0 = _dispatch_spd(A, b, backend)
    ok0 = _row_ok(x0, A)

    def _escalate(x_first):
        xs, oks = x_first, ok0
        for rung in ADAPTIVE_JITTER_RUNGS:
            Ar = A0 + rung * eye
            xr = _dispatch_spd(Ar, b, backend)
            xs = jnp.where(oks[:, None], xs, xr)
            oks = oks | _row_ok(xr, Ar)
        # final rung: fixed-iteration Jacobi-CG on the heaviest-jittered
        # system — factorization-free, so a Cholesky that breaks down on
        # every rung still gets a descent answer
        Ac = A0 + ADAPTIVE_JITTER_RUNGS[-1] * eye
        diag = jnp.diagonal(Ac, axis1=-2, axis2=-1)

        def matvec(p):
            return jnp.einsum("nrs,ns->nr", Ac, p,
                              preferred_element_type=jnp.float32)

        warm = jnp.where(jnp.isfinite(xs), xs, 0.0)
        xc = pcg(matvec, b, diag, x0=warm, iters=min(2 * r, 32))
        return jnp.where(oks[:, None], xs, xc)

    return jax.lax.cond(jnp.all(ok0), lambda x: x, _escalate, x0)


def solve_spd_checked(A, b, count, jitter=DEFAULT_JITTER, backend="auto"):
    """Eager adaptive solve with a host-side verdict: runs the full
    escalation ladder and raises the typed :class:`SolveUnstable` when
    rows remain non-finite or residual-failing after every rung — the
    'all rungs fail' contract a jitted caller cannot enforce itself."""
    x = solve_spd(A, b, count, jitter=jitter, backend=backend,
                  adaptive=True)
    r = A.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    empty = (count <= 0)[:, None, None]
    A0 = jnp.where(empty, eye, A)
    # a row is salvaged if its answer satisfies ANY rung's system: a row
    # solved cleanly at base jitter must not be judged against the
    # heaviest-rung regularization it never needed
    ok = jnp.zeros(x.shape[0], dtype=bool)
    bnorm = jnp.linalg.norm(b, axis=-1)
    for rung in (jitter,) + ADAPTIVE_JITTER_RUNGS:
        res = jnp.einsum("nrs,ns->nr", A0 + rung * eye, x,
                         preferred_element_type=jnp.float32) - b
        ok = ok | (jnp.linalg.norm(res, axis=-1)
                   <= _ADAPTIVE_TOL * (bnorm + 1.0))
    bad = ~(jnp.all(jnp.isfinite(x), axis=-1) & ok)
    nbad = int(jnp.sum(bad))
    if nbad:
        raise SolveUnstable(nbad, int(x.shape[0]))
    return x


def pcg(matvec, b, diag, x0=None, iters=3):
    """Generic batched Jacobi-preconditioned CG, fixed iterations.

    ``matvec``: callable [n, r] -> [n, r] applying the (batched) SPD
    operator; ``diag`` [n, r]: its diagonal (the Jacobi preconditioner).
    Shared engine of :func:`solve_cg` (dense A) and the matrix-free
    half-step path (tpu_als.core.als.local_half_step), which applies A
    through the gathered factor rows without ever materializing the
    [n, r, r] tensor.
    """
    x = jnp.zeros_like(b) if x0 is None else x0.astype(b.dtype)
    res = b - matvec(x)
    z = res / diag
    p = z
    rz = jnp.einsum("nr,nr->n", res, z)

    def body(_, carry):
        x, res, p, rz = carry
        Ap = matvec(p)
        denom = jnp.einsum("nr,nr->n", p, Ap)
        alpha = rz / jnp.maximum(denom, 1e-30)
        x = x + alpha[:, None] * p
        res = res - alpha[:, None] * Ap
        z = res / diag
        rz_new = jnp.einsum("nr,nr->n", res, z)
        beta = rz_new / jnp.maximum(rz, 1e-30)
        p = z + beta[:, None] * p
        return x, res, p, rz_new

    x, _, _, _ = jax.lax.fori_loop(0, iters, body, (x, res, p, rz))
    return x


def solve_cg(A, b, count, x0=None, iters=3, jitter=DEFAULT_JITTER):
    """Batched Jacobi-preconditioned conjugate gradient, fixed iterations.

    The Takács–Pilászy approach for ALS (Applications of the conjugate
    gradient method for implicit feedback collaborative filtering, 2011):
    instead of factorizing each A (r³/3 serial-recurrence work — the
    measured 80% of the on-chip iteration, VPU-bound at ~1% MFU), run a
    few CG steps whose cost is one batched matvec each
    (``einsum('nrs,ns->nr')`` — a [n, r, r] × [n, r] contraction the MXU
    executes at high utilization).  With ``x0`` warm-started from the
    previous ALS iterate the outer fixed-point iteration converges to the
    same solution (inexact ALS): each half-step only needs to reduce the
    residual below the progress the outer loop makes, which 2-3 steps do.

    Same contract as :func:`solve_spd`: rows with ``count <= 0`` get
    A := I, and since their b is 0 the first CG step lands exactly on
    x = 0 even from a nonzero warm start (α = 1, residual −x₀) — cold
    entities keep the zero-factor semantic.

    Fixed ``iters`` keeps the trip count static for XLA (same stance as
    the fixed-sweep NNLS, SURVEY.md §7 hard-part 4).
    """
    r = A.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    empty = (count <= 0)[:, None, None]
    A = jnp.where(empty, eye, A) + jitter * eye
    diag = jnp.diagonal(A, axis1=-2, axis2=-1)          # Jacobi precond

    def matvec(p):
        return jnp.einsum("nrs,ns->nr", A, p,
                          preferred_element_type=jnp.float32)

    return pcg(matvec, b, diag, x0=x0, iters=iters)


def solve_cg_matfree(Vg, vals, mask, reg, implicit=False, alpha=1.0,
                     YtY=None, x0=None, iters=3, jitter=DEFAULT_JITTER):
    """Matrix-free inexact solve: warm-started Jacobi-CG where A is
    applied THROUGH the gathered factor rows —

        A·p = YtY·p + Vgᵀ((c−1) ⊙ (Vg·p)) + (λn + jitter)·p

    — so the [n, r, r] normal-equation tensor is never materialized: the
    NE einsum and A's HBM round-trips both disappear; what remains per CG
    step is two nnz-proportional contractions the MXU runs well.

    ``Vg`` may be reduced precision (bfloat16): the big tensor stays
    narrow in HBM while every reduction and every Krylov intermediate
    accumulates in f32 (mixed-dtype einsums promote — the dense path
    builds A once with f32 accumulation, and this path must not add
    per-iteration bf16 rounding the dense path doesn't have).

    Same weighting formulas as the dense build (:func:`implicit_weights`,
    the ``numExplicits`` count rule) and same cold-row contract as
    :func:`solve_spd`: rows with count 0 act as A := I, b = 0, landing
    exactly on x = 0 from any warm start.
    """
    dt = Vg.dtype
    mA = mask.astype(dt)
    vA = vals.astype(dt)
    if implicit:
        w_conf, pref = implicit_weights(vA, mA, alpha)
        rhs = jnp.einsum("nw,nwr->nr", (1.0 + w_conf) * pref * mA, Vg,
                         preferred_element_type=jnp.float32)
        count = jnp.sum(pref.astype(jnp.float32) * mask, axis=-1)
    else:
        w_conf = mA
        rhs = jnp.einsum("nw,nwr->nr", vA * mA, Vg,
                         preferred_element_type=jnp.float32)
        count = jnp.sum(mask, axis=-1)
    rhs = rhs.astype(jnp.float32)
    w32 = w_conf.astype(jnp.float32)
    ridge = (reg * count + jitter)[:, None]
    empty = (count <= 0)[:, None]
    diag = jnp.einsum("nw,nwr->nr", w_conf, Vg * Vg,
                      preferred_element_type=jnp.float32) + ridge
    YtYf = YtY.astype(jnp.float32) if implicit else None
    if YtYf is not None:
        diag = diag + jnp.diagonal(YtYf)[None, :]
    diag = jnp.where(empty, 1.0, diag)

    def matvec(p):
        # mixed-dtype einsums: p/t stay f32, only Vg is (possibly) bf16
        t = jnp.einsum("nwr,nr->nw", Vg, p,
                       preferred_element_type=jnp.float32)
        mv = jnp.einsum("nw,nwr->nr", w32 * t, Vg,
                        preferred_element_type=jnp.float32)
        mv = mv + ridge * p
        if YtYf is not None:
            mv = mv + p @ YtYf
        # empty rows (chunk padding / cold entities): A := I so CG lands
        # exactly on x = 0 (their b is 0)
        return jnp.where(empty, p, mv)

    return pcg(matvec, rhs, diag, x0=x0, iters=iters)


@functools.partial(jax.jit, static_argnames=("sweeps", "jitter"))
def solve_nnls(A, b, count, sweeps=32, jitter=DEFAULT_JITTER):
    """Batched nonnegative least squares via cyclic coordinate descent.

    Replaces the reference stack's projected-CG ``NNLSSolver``
    (``mllib/.../optimization/NNLS.scala``, SURVEY.md §2.B5) with a
    fixed-iteration, jittable scheme: for SPD A, cyclic CD on
    ½xᵀAx − bᵀx subject to x ≥ 0 converges monotonically; a fixed number of
    sweeps keeps shapes/trip-counts static for XLA (SURVEY.md §7 hard-part 4).
    """
    r = A.shape[-1]
    eye = jnp.eye(r, dtype=A.dtype)
    empty = (count <= 0)[:, None, None]
    A = jnp.where(empty, eye, A) + jitter * eye
    diag = jnp.diagonal(A, axis1=-2, axis2=-1)  # [n, r]

    x0 = jnp.zeros_like(b)

    def sweep(x, _):
        def coord(j, x):
            # residual_j = (A x - b)_j ; x_j <- max(0, x_j - residual_j / A_jj)
            Ax_j = jnp.einsum("nr,nr->n", A[:, j, :], x)
            xj = jnp.maximum(0.0, x[:, j] - (Ax_j - b[:, j]) / diag[:, j])
            return x.at[:, j].set(xj)

        x = jax.lax.fori_loop(0, r, coord, x)
        return x, None

    x, _ = jax.lax.scan(sweep, x0, None, length=sweeps)
    return x
