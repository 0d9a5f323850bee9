#!/usr/bin/env python3
"""Proof that tpu-als still starts on the chip: BASELINE config 2 — the
MovieLens-25M shape (162,541 users x 59,047 items x 25,000,095 ratings,
synthetic from ``--seed``), rank 128, implicit feedback, alpha 40, f32 —
trained, served and folded into on one TPU through the entry points a
user calls, each answer checked against a plain numpy reference.

    python chip_smoke.py             # one chip: data, train, serve, foldin, kernels
    python chip_smoke.py --chips 4   # only the sharded fit / sharded top-k and
                                     # their one-device comparisons

There is no CPU mode: the ``device`` phase exits non-zero when the first
device is not a TPU, before any other phase.  Every phase prints one JSON
line; a failed check raises :class:`SmokeFailure` and the script exits 1
at once, without the final line.  The last line of a run that passed is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
on one chip it also carries the distance the default matmul precision
leaves between the "f32" fit and float64.

The phases are plain functions that take their sizes as arguments, so
``tests/test_chip_smoke.py`` rehearses them at tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ML25M = dict(num_users=162_541, num_items=59_047, num_ratings=25_000_095)
RANK = 128
ALPHA = 40.0
REG = 0.01
TOP_K = 10

# How far a solved factor row may sit from the float64 reference, as
# ||x - ref|| / ||ref||.  The code path — gather, normal equations, the
# Cholesky kernel — is held to SOLVE_RTOL with the matmuls at
# Precision.HIGHEST.  What users run is the backend's default matmul
# precision, which on a TPU is ONE bf16 pass for f32 operands: the "f32"
# configuration computes its Gram matrices from bf16-rounded factors.  The
# chip reads max 6.1e-2 / median 1.1e-2 there for the user half-step
# (2.9e-5 at HIGHEST) and 2.6e-3 for the fold-in, the same to the digit in
# every run, so the default-precision bounds are twice those readings: a
# drift in the arithmetic shows, a rounding does not.  Both distances are
# printed, and the median goes on the result line.
SOLVE_RTOL = 1e-3
DEFAULT_PRECISION_MAX = 0.12
DEFAULT_PRECISION_MEDIAN = 0.022
FOLDIN_DEFAULT_PRECISION_MAX = 6e-3
DEFAULT_MATMUL = "one bf16 pass for f32 operands (TPU default precision)"
# Sharded vs one-device factors after the same iterations from the same
# seed, per-row distance, BOTH FITS AT Precision.HIGHEST.  At the default
# precision the pair sits median 1.3e-2 / p99 5.9e-2 apart on the chip and
# proves nothing (bf16 rounding is a step function: it turns the f32-level
# differences of two differently-shaped programs into whole-ulp flips);
# those distances are printed only.  At HIGHEST what is left is f32
# accumulation order, which grows with the length of a row's sums.  The
# bounds were set before the chip run from a full-size CPU run in plain
# f32 — median 1.7e-4 / p99 5.5e-4, max 9.5e-4 over the 98.6% of users
# with < 1,000 ratings, 2.7e-2 at 98,172 ratings, 7.7e-2 on the heaviest
# user (1,050,964) — and the tiny rehearsal on the CPU has to pass them
# too: median and p99 over all rows, and every row within SHARDED_ROW *
# sqrt(max(1, ratings / HEAVY_DEGREE)), which shows a fault in a single
# ordinary row and says that a row further than SHARDED_ROW apart is one
# of the heaviest.  The chip at HIGHEST then read median 4.0e-5 / p99
# 8.5e-5 / max 4.1e-4 for users (the ten worst rows all with > 68,000
# ratings) and 4.6e-5 / 1.2e-4 / 2.1e-4 for items: 25 times inside.
SHARDED_MEDIAN = 1e-3
SHARDED_P99 = 2e-3
SHARDED_ROW = 1e-2
HEAVY_DEGREE = 1000
N_WORST = 10
# top-k scores vs float64 dot products, relative to the largest score
# (the chip's one-pass scores sat 1.9e-3 off; the CPU's 1e-7)
SCORE_RTOL = 5e-3


class SmokeFailure(Exception):
    """A check of one phase did not hold."""


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def require(cond, what):
    if not cond:
        raise SmokeFailure(what)


class PallasCallLog:
    """Records every ``pallas_call`` traced while installed: the phase it
    was first traced in, the kernel body's name and whether it was asked
    to run in interpret mode.  A jitted kernel is traced once per shape,
    so a later phase that reuses a shape adds no entry."""

    def __init__(self):
        self.calls = []
        self.phase = None

    def __enter__(self):
        from jax.experimental import pallas as pl

        self._pl, self._orig = pl, pl.pallas_call

        def logged(kernel, *args, **kwargs):
            body = getattr(kernel, "func", kernel)
            self.calls.append({
                "phase": self.phase, "kernel": body.__name__,
                "interpret": bool(kwargs.get("interpret", False))})
            return self._orig(kernel, *args, **kwargs)

        pl.pallas_call = logged
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig

    def counted(self):
        """The calls, one entry per (phase, kernel, interpret)."""
        counts = {}
        for c in self.calls:
            key = (c["phase"], c["kernel"], c["interpret"])
            counts[key] = counts.get(key, 0) + 1
        return [{"phase": p, "kernel": kn, "interpret": i, "traced": n}
                for (p, kn, i), n in counts.items()]


def device_phase(chips):
    """Look at the device first; anything but a TPU ends the run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    require(d.platform == "tpu",
            f"no TPU: jax.devices()[0] is {d.platform}:{d.device_kind}; "
            "chip_smoke.py has no CPU mode")
    require(len(devs) >= chips,
            f"--chips {chips} needs {chips} devices, JAX reports {len(devs)}")
    emit("device", jax=jax.__version__, **info)
    return info


def build_native_libraries():
    """Build the three native IO libraries from the tracked sources (the
    staleness test is a hash of the source, tpu_als/io/_native_build.py).
    With a compiler on the machine a failed build raises; without one
    the numpy paths run, and the caller prints which."""
    if shutil.which("g++") is None:
        return "numpy (no g++ on this machine)"
    from tpu_als.io import fastbucket, fastcsv, stream

    fastbucket.load()
    fastcsv._load()
    stream._load()
    return "native"


def data_phase(num_users, num_items, num_ratings, seed):
    """Ratings of the given shape from ``seed``; bucketize both sides."""
    from tpu_als.core.ratings import build_csr_buckets, remap_ids
    from tpu_als.io.movielens import synthetic_movielens

    t0 = time.perf_counter()
    bucketizer = build_native_libraries()
    frame = synthetic_movielens(num_users, num_items, num_ratings,
                                seed=seed)
    t_gen = time.perf_counter() - t0
    u_idx, user_map = remap_ids(frame["user"])
    i_idx, item_map = remap_ids(frame["item"])
    r = np.asarray(frame["rating"], dtype=np.float32)
    t0 = time.perf_counter()
    ucsr = build_csr_buckets(u_idx, i_idx, r, len(user_map))
    icsr = build_csr_buckets(i_idx, u_idx, r, len(item_map))
    t_bucket = time.perf_counter() - t0
    require(ucsr.nnz == icsr.nnz == num_ratings, "bucketizer lost ratings")
    emit("data", users=len(user_map), items=len(item_map),
         ratings=int(num_ratings), seed=seed, bucketizer=bucketizer,
         generate_s=round(t_gen, 2), bucketize_s=round(t_bucket, 2),
         user_padded_over_nnz=round(ucsr.padded_nnz / ucsr.nnz, 4),
         item_padded_over_nnz=round(icsr.padded_nnz / icsr.nnz, 4),
         user_buckets=len(ucsr.buckets), item_buckets=len(icsr.buckets))
    return frame


def probe_verdicts():
    """Every probe outcome in the process with its cause."""
    from tpu_als.utils.platform import probe_caches

    out = []
    for name, cache in sorted(probe_caches().items()):
        for key, ok in cache.items():
            meta = cache.meta.get(key, {})
            out.append({"probe": name, "key": repr(key), "ok": bool(ok),
                        "reason": meta.get("reason"),
                        "seconds": (None if meta.get("seconds") is None
                                    else round(meta["seconds"], 2))})
    return out


def implicit_reference(V64, cols, vals, reg, alpha, jitter):
    """Implicit-feedback normal equations solved in float64, one user per
    entry of ``cols``/``vals``:  A = VtV + sum_k alpha|r_k| v_k v_k^T +
    reg*n*I,  b = sum_k c_k p_k v_k  (Hu-Koren-Volinsky; n counts the
    positive ratings)."""
    VtV = V64.T @ V64
    eye = np.eye(V64.shape[1])
    out = []
    for c, v in zip(cols, vals):
        Vg = V64[c]
        v = np.asarray(v, dtype=np.float64)
        conf_m1 = alpha * np.abs(v)
        pref = (v > 0).astype(np.float64)
        A = VtV + (Vg * conf_m1[:, None]).T @ Vg \
            + (reg * pref.sum() + jitter) * eye
        out.append(np.linalg.solve(A, ((1.0 + conf_m1) * pref) @ Vg))
    return np.stack(out)


def row_errors(x, ref):
    """Per-row ||x - ref|| / ||ref|| (rows with ref = 0 use ||x||)."""
    num = np.linalg.norm(np.asarray(x, np.float64) - ref, axis=1)
    den = np.linalg.norm(ref, axis=1)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), num)


def dense_ids(frame):
    """The dense row index of every rating's user and item, as ``fit``
    numbers them."""
    from tpu_als.core.ratings import remap_ids

    return remap_ids(frame["user"])[0], remap_ids(frame["item"])[0]


def sampled_ratings(frame, u_idx, i_idx, users):
    """Every rating by ``users`` (sorted dense user indices) as one
    ``(cols, vals)`` pair of arrays per user."""
    sel = np.flatnonzero(np.isin(u_idx, users))
    local = np.searchsorted(users, u_idx[sel])
    order = np.argsort(local, kind="stable")
    sel, local = sel[order], local[order]
    cuts = np.searchsorted(local, np.arange(1, len(users)))
    vals = np.asarray(frame["rating"], dtype=np.float32)[sel]
    return np.split(i_idx[sel], cuts), np.split(vals, cuts)


def user_half_step(V, cols, vals, cfg):
    """One more user half-step for the users of ``cols``/``vals`` only,
    through the trainer's own ``local_half_step`` (gather, normal
    equations, solve) — traced anew on every call, so an ambient
    ``jax.default_matmul_precision`` takes effect."""
    import jax
    import jax.numpy as jnp

    from tpu_als.core.als import local_half_step
    from tpu_als.core.ratings import build_csr_buckets
    from tpu_als.ops.solve import compute_yty

    n_users = len(cols)
    local = np.repeat(np.arange(n_users), [len(c) for c in cols])
    csr = build_csr_buckets(local, np.concatenate(cols),
                            np.concatenate(vals), n_users)
    buckets = jax.device_put(csr.device_buckets())

    @jax.jit
    def run(V, buckets):
        return local_half_step(V, buckets, n_users, cfg, compute_yty(V),
                               csr.chunk_elems)

    return np.asarray(run(jnp.asarray(V), buckets))


def train_phase(frame, *, rank, max_iter, seed, n_check_users, clock,
                mesh=None, precision=None):
    """``tpu_als.ALS(...).fit(frame)`` — the Estimator — timed per
    iteration, then (``n_check_users`` > 0) checked against the float64
    reference.  ``precision`` puts the whole fit under
    ``jax.default_matmul_precision`` (the four-chip comparison); users
    run with none.  Returns the model, the resolved path and the
    reference distances (``None`` without the check)."""
    import contextlib

    import jax

    import tpu_als
    from tpu_als.core.als import resolve_solve_path
    from tpu_als.utils.platform import fence

    est_kwargs = dict(rank=rank, implicitPrefs=True, alpha=ALPHA,
                      regParam=REG, maxIter=max_iter, seed=seed)
    cfg = tpu_als.ALS(**est_kwargs)._config()
    t0 = time.perf_counter()
    resolved = resolve_solve_path(cfg, rank)
    probe_s = time.perf_counter() - t0

    marks = []

    def on_iteration(iteration, U, V):
        fence((U, V))
        marks.append(time.perf_counter())

    mark = clock.now()
    t_fit = time.perf_counter()
    with (contextlib.nullcontext() if precision is None
          else jax.default_matmul_precision(precision)):
        model = tpu_als.ALS(fitCallback=on_iteration, mesh=mesh,
                            **est_kwargs).fit(frame)
    fit_s = time.perf_counter() - t_fit
    compiled = {k: round(v, 2) for k, v in clock.since(mark).items()}
    iter_s = np.diff([t_fit] + marks)
    require(len(iter_s) == max_iter, "fitCallback did not fire per iteration")

    U, V = model._U, model._V
    require(U.shape[1] == rank and V.shape[1] == rank, "wrong factor rank")
    require(np.isfinite(U).all() and np.isfinite(V).all(),
            "non-finite factors")

    timing = dict(
        entry="tpu_als.ALS.fit", rank=rank, max_iter=max_iter,
        implicit=True, alpha=ALPHA, reg=REG,
        matmul_precision=precision or "default",
        mesh_devices=None if mesh is None else int(mesh.devices.size),
        resolved_solve_path=resolved["resolved_solve_path"],
        resolve_probes_s=round(probe_s, 2),
        fit_wall_s=round(fit_s, 2), fit_compile=compiled,
        first_iteration_s=round(float(iter_s[0]), 3),
        first_iteration_includes="id maps, bucketize, upload, compile",
        iteration_s=[round(float(t), 4) for t in iter_s[1:]],
        timed_region_ends_with="block_until_ready",
        device=jax.devices()[0].device_kind)
    if not n_check_users:
        emit("train", **timing)
        return model, resolved, None

    # reference check, outside the timed part: for a sample of users,
    # one more user half-step from the final item factors against the
    # same implicit normal equations solved in numpy float64 — once as
    # users run it, once with the matmuls at Precision.HIGHEST
    rng = np.random.default_rng(seed)
    users = np.sort(rng.choice(U.shape[0],
                               size=min(n_check_users, U.shape[0]),
                               replace=False))
    cols, vals = sampled_ratings(frame, *dense_ids(frame), users)
    x = user_half_step(V, cols, vals, cfg)
    with jax.default_matmul_precision("highest"):
        x_hi = user_half_step(V, cols, vals, cfg)
    ref = implicit_reference(np.asarray(V, np.float64), cols, vals,
                             REG, ALPHA, cfg.jitter)
    err, err_hi = row_errors(x, ref), row_errors(x_hi, ref)
    check = dict(
        highest_precision_max_row_err=float(err_hi.max()),
        default_precision_max_row_err=float(err.max()),
        default_precision_median_row_err=float(np.median(err)))
    emit("train", **timing,
         reference="numpy float64 implicit normal equations",
         reference_users=len(users), **check,
         highest_precision_rtol=SOLVE_RTOL,
         default_matmul=DEFAULT_MATMUL,
         default_precision_bounds=[DEFAULT_PRECISION_MAX,
                                   DEFAULT_PRECISION_MEDIAN])
    require(err_hi.max() <= SOLVE_RTOL,
            f"user half-step at Precision.HIGHEST is {err_hi.max():.3g} "
            f"from the float64 reference (allowed {SOLVE_RTOL})")
    require(err.max() <= DEFAULT_PRECISION_MAX
            and np.median(err) <= DEFAULT_PRECISION_MEDIAN,
            f"user half-step at default precision is max {err.max():.3g} "
            f"/ median {np.median(err):.3g} from the float64 reference "
            f"(allowed {DEFAULT_PRECISION_MAX} / "
            f"{DEFAULT_PRECISION_MEDIAN})")
    return model, resolved, check


def topk_reference(Q64, V64, k):
    """float64 scores, top-(k+1) per row, descending."""
    s = Q64 @ V64.T
    kk = min(k + 1, s.shape[1])
    idx = np.argsort(-s, axis=1, kind="stable")[:, :kk]
    return np.take_along_axis(s, idx, axis=1), idx


def compare_topk(name, scores, ids, Q, V, k, exact):
    """Against the float64 top-k: every returned score is the dot product
    of its returned id, sorted descending; no returned item scores more
    than the tolerance below the true k-th best (a valid top-k up to
    rounding, near-ties or not); ids equal the reference on rows whose
    top-(k+1) scores are separated by more than the tolerance.
    ``exact=False`` (the int8 shortlist) may miss a true top-k item:
    then recall is bounded instead of the last two."""
    scores, ids = np.asarray(scores), np.asarray(ids)
    Q64, V64 = np.asarray(Q, np.float64), np.asarray(V, np.float64)
    ref_s, ref_i = topk_reference(Q64, V64, k)
    tol = SCORE_RTOL * float(np.abs(ref_s).max())
    own = np.einsum("nr,nkr->nk", Q64, V64[ids])
    score_err = float(np.abs(scores - own).max())
    require(score_err <= tol,
            f"{name}: returned scores are {score_err:.3g} from the dot "
            f"products of the returned ids (allowed {tol:.3g})")
    require((np.diff(scores, axis=1) <= tol).all(),
            f"{name}: scores not sorted descending")
    shortfall = float((ref_s[:, k - 1:k] - own).max())
    clear = (-np.diff(ref_s, axis=1) > 2 * tol).all(axis=1)
    same = (ids == ref_i[:, :k]).all(axis=1)
    recall = float(np.mean([len(set(a) & set(b)) / k
                            for a, b in zip(ids, ref_i[:, :k])]))
    if exact:
        require(shortfall <= 2 * tol,
                f"{name}: a returned item scores {shortfall:.3g} below "
                f"the true k-th best (allowed {2 * tol:.3g})")
        require(same[clear].all(),
                f"{name}: ids differ from the reference on "
                f"{int((~same[clear]).sum())} rows without near-ties")
    else:
        require(recall >= 0.99, f"{name}: recall@{k} {recall:.4f} < 0.99")
    return {"rows": int(len(ids)), "recall": round(recall, 4),
            "rows_ids_equal": int(same.sum()),
            "rows_without_near_ties": int(clear.sum()),
            "max_score_err": score_err, "score_tol": tol,
            "max_shortfall_vs_kth_best": shortfall}


def serve_phase(model, *, k, n_id_requests, n_vector_requests, seed):
    """``ServingEngine(k).publish/warmup`` answering requests by user id
    and by fold-in vector, then the Estimator's own
    ``recommendForUserSubset``; both against a float64 top-k."""
    from tpu_als.ops.topk import auto_topk_backend
    from tpu_als.serving.engine import ServingEngine

    U, V = model._U, model._V
    k = min(k, V.shape[0])
    rng = np.random.default_rng(seed + 1)
    uids = rng.choice(U.shape[0], size=min(n_id_requests, U.shape[0]),
                      replace=False)
    vectors = U[rng.choice(U.shape[0], size=n_vector_requests)] \
        + 0.01 * rng.normal(size=(n_vector_requests, U.shape[1])
                            ).astype(np.float32)

    engine = ServingEngine(k=k)
    t0 = time.perf_counter()
    engine.publish(U, V)
    engine.warmup()
    warm_s = time.perf_counter() - t0
    lat = []
    answers = []
    with engine:
        for payload in [int(u) for u in uids] + list(vectors):
            t0 = time.perf_counter()
            answers.append(engine.recommend(payload, timeout=120))
            lat.append(time.perf_counter() - t0)
    scores = np.stack([a[0] for a in answers])
    ids = np.stack([a[1] for a in answers])
    Q = np.concatenate([U[uids], vectors])
    engine_cmp = compare_topk("ServingEngine", scores, ids, Q, V, k,
                              exact=False)

    # the Estimator's door to the top-k kernel
    sub = uids[:64]
    raw_users = model._user_map.ids[sub]
    t0 = time.perf_counter()
    recs = model.recommendForUserSubset({"user": raw_users}, k)
    subset_s = time.perf_counter() - t0
    dense = model._user_map.to_dense(recs["user"])
    item_col = recs["recommendations"].dtype.names[0]
    rec_ids = model._item_map.to_dense(
        recs["recommendations"][item_col].reshape(-1)).reshape(len(dense), k)
    subset_cmp = compare_topk(
        "recommendForUserSubset", recs["recommendations"]["rating"],
        rec_ids, U[dense], V, k, exact=True)
    backend = auto_topk_backend(U.shape[1], k)
    emit("serve", entry="ServingEngine.publish/warmup/recommend",
         k=k, publish_warmup_s=round(warm_s, 2),
         requests_by_id=len(uids), requests_by_vector=len(vectors),
         request_median_s=float(np.median(lat)),
         request_max_s=float(np.max(lat)),
         engine_vs_reference=engine_cmp,
         subset_entry="ALSModel.recommendForUserSubset",
         subset_topk_backend=backend, subset_s=round(subset_s, 3),
         subset_vs_reference=subset_cmp)
    return backend


def foldin_phase(model, *, n_new, width, seed):
    """``tpu_als.core.foldin.fold_in`` for new users (the last one with
    no rating at all) against the float64 ridge solve."""
    import jax
    import jax.numpy as jnp

    from tpu_als.core.foldin import fold_in
    from tpu_als.ops.solve import DEFAULT_JITTER, auto_solve_backend

    V = model._V
    rng = np.random.default_rng(seed + 2)
    cols = rng.integers(0, V.shape[0], size=(n_new, width)).astype(np.int32)
    vals = rng.choice(np.arange(0.5, 5.5, 0.5), size=(n_new, width)
                      ).astype(np.float32)
    degree = rng.integers(1, width + 1, size=n_new)
    degree[-1] = 0                                # a cold row
    mask = (np.arange(width)[None, :] < degree[:, None]).astype(np.float32)
    args = (jnp.asarray(V), jnp.asarray(cols), jnp.asarray(vals * mask),
            jnp.asarray(mask), REG)
    t0 = time.perf_counter()
    x = np.asarray(fold_in(*args, implicit_prefs=True, alpha=ALPHA))
    first_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        x_hi = np.asarray(fold_in(*args, implicit_prefs=True, alpha=ALPHA))
    ref = implicit_reference(
        np.asarray(V, np.float64),
        [cols[n, :degree[n]] for n in range(n_new - 1)],
        [vals[n, :degree[n]] for n in range(n_new - 1)],
        REG, ALPHA, DEFAULT_JITTER)
    err, err_hi = row_errors(x[:-1], ref), row_errors(x_hi[:-1], ref)
    backend = auto_solve_backend(V.shape[1])
    emit("foldin", entry="tpu_als.core.foldin.fold_in", new_users=n_new,
         width=width, solve_backend=backend,
         first_call_s=round(first_s, 2),
         reference="numpy float64 ridge solve",
         highest_precision_max_row_err=float(err_hi.max()),
         highest_precision_rtol=SOLVE_RTOL,
         default_precision_max_row_err=float(err.max()),
         default_precision_bound=FOLDIN_DEFAULT_PRECISION_MAX,
         cold_row_is_zero=bool((x[-1] == 0).all()))
    require(np.isfinite(x).all(), "fold-in produced non-finite factors")
    require(err_hi.max() <= SOLVE_RTOL,
            f"fold-in at Precision.HIGHEST is {err_hi.max():.3g} from the "
            f"float64 ridge solve (allowed {SOLVE_RTOL})")
    require(err.max() <= FOLDIN_DEFAULT_PRECISION_MAX,
            f"fold-in at default precision is {err.max():.3g} from the "
            f"float64 ridge solve (allowed {FOLDIN_DEFAULT_PRECISION_MAX})")
    require((x[-1] == 0).all(), "a user with no ratings must fold in to 0")
    return backend


# which Pallas kernel body each dispatch verdict puts on the path
_TRAIN_KERNELS = {
    "einsum+pallas_lanes": ("_chol_lanes_kernel",),
    "einsum+pallas_cholesky": ("_chol_solve_kernel",),
    "gatherfused+pallas_lanes": ("_gather_gram_kernel",
                                 "_chol_lanes_kernel"),
    "gatherfused+pallas_cholesky": ("_gather_gram_kernel", "_chol_solve_kernel"),
    "gatherfused_solve": ("_gather_solve_kernel",),
}
_SOLVE_KERNELS = {"lanes": ("_chol_lanes_kernel",),
                  "pallas": ("_chol_solve_kernel",)}
_TOPK_KERNELS = {"pallas": ("_topk_kernel",)}


def kernels_phase(log, *, train_path, topk_backend, foldin_backend):
    """No interpreted Pallas call; a compiled Pallas kernel on the
    training, serving and fold-in paths (the sandbox compile says each
    has one at rank 128); every probe verdict printed with its cause.
    A kernel off the selected path that the compiler refused is printed
    and does not fail the run.  (A probe that ends in any exception
    other than the compiler's refusal raises where it happens —
    tpu_als.utils.platform.probe_kernel — so it never gets here.)"""
    verdicts = probe_verdicts()
    interpreted = [c for c in log.calls if c["interpret"]]
    compiled = {c["kernel"] for c in log.calls if not c["interpret"]}
    paths = {"train": (train_path, _TRAIN_KERNELS),
             "serve": (topk_backend, _TOPK_KERNELS),
             "foldin": (foldin_backend, _SOLVE_KERNELS)}
    expected = {phase: table.get(chosen)
                for phase, (chosen, table) in paths.items()}
    refused = [v for v in verdicts
               if "compiler refused" in (v["reason"] or "")]
    emit("kernels", pallas_calls=log.counted(), interpreted=len(interpreted),
         expected_on_path=expected, probes=verdicts,
         refused=[f"{v['probe']}{v['key']}: {v['reason']}"
                  for v in refused])
    require(not interpreted,
            f"{len(interpreted)} Pallas calls ran in interpret mode: "
            f"{sorted({c['kernel'] for c in interpreted})}")
    for phase, kernels in expected.items():
        chosen = paths[phase][0]
        require(kernels is not None,
                f"{phase} path resolved to {chosen!r}: no compiled Pallas "
                "kernel where the sandbox compile says there is one")
        missing = [kn for kn in kernels if kn not in compiled]
        require(not missing,
                f"{phase} path {chosen!r} never traced {missing}")


def memory_by_device():
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def factors_apart(a, b, ratings):
    """Per-row distance of two fits' factors, against the bound that
    grows with the row's number of ratings."""
    d = row_errors(a, np.asarray(b, np.float64))
    allowed = SHARDED_ROW * np.sqrt(np.maximum(1.0, ratings / HEAVY_DEGREE))

    def rows(order):
        return [{"row": int(w), "apart": float(d[w]),
                 "ratings": int(ratings[w])} for w in order]

    return {"median": float(np.median(d)),
            "p99": float(np.quantile(d, 0.99)),
            "max": float(d.max()),
            "rows_over_row_bound": int((d > allowed).sum()),
            "nearest_its_row_bound": rows([np.argmax(d / allowed)])[0],
            "worst_rows": rows(np.argsort(-d)[:N_WORST])}


def sharded_phase(frame, *, chips, rank, max_iter, seed, k, n_queries,
                  clock):
    """The sharded fit on a ``chips``-device mesh (default gather
    strategy) against the one-device fit from the same seed — once as
    users run it, once with both at Precision.HIGHEST, which is the pair
    that is held to a bound — and ``topk_sharded`` against the one-device
    top-k."""
    import jax.numpy as jnp

    from tpu_als.ops.topk import topk_scores
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.parallel.serve import topk_sharded

    mesh = make_mesh(chips)
    fits = {}
    for precision in (None, "highest"):
        name = precision or "default"
        sharded, resolved, _ = train_phase(
            frame, rank=rank, max_iter=max_iter, seed=seed,
            n_check_users=0, clock=clock, mesh=mesh, precision=precision)
        emit("memory", after=f"sharded fit, {name} precision",
             devices=memory_by_device())
        single, _, _ = train_phase(
            frame, rank=rank, max_iter=max_iter, seed=seed,
            n_check_users=0, clock=clock, precision=precision)
        emit("memory", after=f"one-device fit, {name} precision",
             devices=memory_by_device())
        fits[name] = (sharded, single)

    u_idx, i_idx = dense_ids(frame)
    ratings = {"user": np.bincount(u_idx), "item": np.bincount(i_idx)}
    apart = {name: {"user": factors_apart(s._U, o._U, ratings["user"]),
                    "item": factors_apart(s._V, o._V, ratings["item"])}
             for name, (s, o) in fits.items()}
    rng = np.random.default_rng(seed + 3)
    U, V = fits["default"][1]._U, fits["default"][1]._V
    k = min(k, V.shape[0])
    Q = U[rng.choice(U.shape[0], size=min(n_queries, U.shape[0]),
                     replace=False)]
    s4, i4, info = topk_sharded(Q, V, k, mesh, return_info=True)
    s1, i1 = topk_scores(jnp.asarray(Q), jnp.asarray(V),
                         jnp.ones(V.shape[0], bool), k)
    s1, i1 = np.asarray(s1), np.asarray(i1)
    cmp4 = compare_topk("topk_sharded", s4, i4, Q, V, k, exact=True)
    tol = cmp4["score_tol"]
    emit("sharded", chips=chips, strategy="all_gather",
         resolved_solve_path=resolved["resolved_solve_path"],
         factors_vs_one_device_at_highest=apart["highest"],
         factors_allowed={
             "median": SHARDED_MEDIAN, "p99": SHARDED_P99,
             "row": f"{SHARDED_ROW} * sqrt(max(1, ratings / "
                    f"{HEAVY_DEGREE}))"},
         factors_vs_one_device_at_default_not_checked=apart["default"],
         topk_sharded_vs_reference=cmp4,
         topk_sharded_vs_one_device={
             "max_score_diff": float(np.abs(s4 - s1).max()),
             "rows_ids_equal": int((i4 == i1).all(axis=1).sum())},
         topk_degraded=info["degraded"])
    require(not info["degraded"],
            f"topk_sharded degraded to its fallback: {info['reason']}")
    for side, d in apart["highest"].items():
        require(d["median"] <= SHARDED_MEDIAN and d["p99"] <= SHARDED_P99,
                f"at Precision.HIGHEST the sharded {side} factors are "
                f"median {d['median']:.3g} / p99 {d['p99']:.3g} from the "
                f"one-device fit (allowed {SHARDED_MEDIAN} / {SHARDED_P99})")
        require(d["rows_over_row_bound"] == 0,
                f"at Precision.HIGHEST {d['rows_over_row_bound']} sharded "
                f"{side} rows are further from the one-device fit than "
                f"their number of ratings allows; worst "
                f"{d['nearest_its_row_bound']}")
    require(float(np.abs(s4 - s1).max()) <= tol,
            "topk_sharded scores differ from the one-device top-k")
    return resolved


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    # a banked planner verdict must not steer this run
    os.environ["TPU_ALS_PLAN_CACHE"] = "off"
    result = {"ok": True}
    try:
        result["device"] = device_phase(args.chips)
        from tpu_als.obs import compiles
        from tpu_als.utils.platform import enable_persistent_compile_cache

        emit("setup", plan_cache="disarmed (TPU_ALS_PLAN_CACHE=off)",
             compile_cache_dir=enable_persistent_compile_cache())
        clock = compiles.install()
        with PallasCallLog() as log:
            log.phase = "data"
            frame = data_phase(seed=args.seed, **ML25M)
            if args.chips > 1:
                log.phase = "sharded"
                sharded_phase(frame, chips=args.chips, rank=RANK,
                              max_iter=2, seed=args.seed, k=TOP_K,
                              n_queries=1024, clock=clock)
                interpreted = [c for c in log.calls if c["interpret"]]
                emit("kernels", pallas_calls=log.counted(),
                     probes=probe_verdicts())
                require(not interpreted, "Pallas calls in interpret mode")
            else:
                log.phase = "train"
                model, resolved, check = train_phase(
                    frame, rank=RANK, max_iter=3, seed=args.seed,
                    n_check_users=256, clock=clock)
                result.update(
                    default_matmul=DEFAULT_MATMUL,
                    default_precision_median_row_err=check[
                        "default_precision_median_row_err"])
                log.phase = "serve"
                topk_backend = serve_phase(
                    model, k=TOP_K, n_id_requests=96, n_vector_requests=32,
                    seed=args.seed)
                log.phase = "foldin"
                foldin_backend = foldin_phase(model, n_new=64, width=256,
                                              seed=args.seed)
                kernels_phase(log,
                              train_path=resolved["resolved_solve_path"],
                              topk_backend=topk_backend,
                              foldin_backend=foldin_backend)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
