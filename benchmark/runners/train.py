"""``kind: train`` — one ``tpu_als.ALS(...).fit(frame)`` whose ``fitCallback``
is the clock.  The callback fences ``(U, V)`` and stamps every iteration
boundary.  The first boundary ends set-up (data from the seed, probes, id
maps, bucketize, upload, trace, compile, the first iteration); the window
runs from there until ``--seconds`` have passed, and is closed by raising
:class:`WindowClosed` out of the callback, which stops the fit at an
iteration boundary the way ``resilience.preempt.Preempted`` does.
``train_iter_s`` is the whole window over all its iterations, so a stall
anywhere in it shows; the median, least and longest iteration are printed
on the ``fit`` line beside it.

``correct``: ALS's step solves V from U and then U from V, so the final U
is the exact solution of the user normal equations at the final V, and the
final V that of the item normal equations at the U of the boundary before
(a device copy of which the callback keeps: ``_step_jit`` donates U and V).
For rows sampled from the seed the reference builds those equations in
float64, and the number compared is how far the program's row is from
solving them: the residual ``|A x - b| / |b|``.  (The distance of ``x``
from the float64 solution is printed beside it but not held to a limit: it
is the residual magnified by the system's condition number, 1e5 for the
rank-128 item rows, and does not tell float8 operands from the program.)
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import datagen
from benchmark.harness import Outcome, at_least, at_most
from benchmark.reference import als_normal_eq as ref
from benchmark.trace import profiler_options

ALS_SEED_MOD = 2 ** 31 - 1      # the estimator's seed feeds a PRNGKey


class WindowClosed(Exception):
    """Raised from the fitCallback to stop the fit at a boundary."""


def drive_fit(frame, als_params, *, seed, seconds, on_window_open=None,
              trace_iterations=0, trace_dir=None):
    """Run the fit to the end of the window.  Returns the boundary stamps
    (``marks[0]`` opens the window), the final ``(U, V)``, the device copy
    of U from the boundary before, and the fit's start stamp."""
    import jax
    import jax.numpy as jnp

    import tpu_als

    marks, state = [], {}

    def callback(iteration, U, V):
        with jax.profiler.TraceAnnotation("bench.callback"):
            jax.block_until_ready((U, V))
            now = time.perf_counter()
            if not marks:
                # warm the copy's program before the window opens
                state["u_prev"] = jax.block_until_ready(
                    jnp.array(U, copy=True))
                if trace_iterations:
                    jax.profiler.start_trace(
                        trace_dir, profiler_options=profiler_options())
                now = time.perf_counter()
                marks.append(now)
                if on_window_open is not None:
                    on_window_open(now)
                return
            marks.append(now)
            done = (len(marks) - 1 >= trace_iterations if trace_iterations
                    else now - marks[0] >= seconds)
            if done:
                if trace_iterations:
                    jax.profiler.stop_trace()
                state["final"] = (U, V)
                raise WindowClosed
            state["u_prev"] = jnp.array(U, copy=True)

    t_fit = time.perf_counter()
    try:
        tpu_als.ALS(fitCallback=callback, maxIter=10 ** 6,
                    seed=int(seed) % ALS_SEED_MOD, **als_params).fit(frame)
    except WindowClosed:
        pass
    U, V = state["final"]
    return marks, np.asarray(U), np.asarray(V), np.asarray(state["u_prev"]), t_fit


def probe_seconds():
    """Sum of the seconds the program's kernel probes took in this
    process, and each verdict with its reason."""
    from tpu_als.utils.platform import probe_caches

    total, verdicts = 0.0, []
    for name, cache in sorted(probe_caches().items()):
        for key, ok in cache.items():
            meta = cache.meta.get(key, {})
            total += meta.get("seconds") or 0.0
            verdicts.append({"probe": name, "key": repr(key), "ok": bool(ok),
                             "reason": meta.get("reason"),
                             "seconds": meta.get("seconds")})
    return total, verdicts


def dense_index(raw_ids):
    """(sorted unique ids, function raw -> dense), as ``fit`` numbers rows."""
    uniq = np.unique(raw_ids)
    return uniq, lambda x: np.searchsorted(uniq, x)


def reference_residuals(data, config, U, V, U_prev, *, seed, n_rows,
                        operand_dtype=None):
    """``{side: {"residual", "distance"}}``, one value per sampled row: user
    rows of U against the float64 normal equations at V, item rows of V
    against those at U_prev.  With ``operand_dtype`` the CONTROL stands in
    the program's place: the reference's own solution from operands rounded
    to that type, held against the same float64 equations."""
    als = config["als"]
    kw = dict(reg=als["regParam"], implicit=als["implicitPrefs"],
              alpha=als.get("alpha", 1.0), jitter=config["solve_jitter"])
    users, u_of = dense_index(data["user"])
    items, i_of = dense_index(data["item"])
    rng = datagen.rng_for(seed, 1)
    out = {}
    for side, uniq, rows_raw, cols_raw, solved, at, cols_of in (
            ("user", users, data["user"], data["item"], U, V, i_of),
            ("item", items, data["item"], data["user"], V, U_prev, u_of)):
        rows = np.sort(rng.choice(len(uniq), size=min(n_rows, len(uniq)),
                                  replace=False))
        cols, vals = ref.ratings_of(rows_raw, cols_raw, data["rating"],
                                    uniq[rows])
        cols = [cols_of(c) for c in cols]
        A, b = ref.normal_equations(at, cols, vals, **kw)
        x = (solved[rows] if operand_dtype is None else
             ref.solve_rows(at, cols, vals, operand_dtype=operand_dtype,
                            **kw))
        out[side] = {"residual": ref.residuals(A, b, x),
                     "distance": ref.row_distances(
                         x, np.linalg.solve(A, b[..., None])[..., 0])}
    return out


def checks_from(found, U, V, config):
    lim = config["correct"]
    rank = config["als"]["rank"]
    checks = [at_most(f"{side}_residual_{stat}", value,
                      lim[f"{side}_residual_{stat}"])
              for side in ("user", "item")
              for stat, value in (
                  ("median", np.median(found[side]["residual"])),
                  ("max", found[side]["residual"].max()))]
    return checks + [
        at_least("factors_finite",
                 float(np.isfinite(U).all() and np.isfinite(V).all()), 1.0),
        at_most("rank_mismatch",
                float(U.shape[1] != rank or V.shape[1] != rank), 0.0),
    ]


def run(cell):
    cfg, mix = cell.config, cell.traffic
    t0 = time.perf_counter()
    data = datagen.synthetic_ratings(
        cfg["num_users"], cfg["num_items"], cfg["num_ratings"], cell.seed,
        **cfg.get("generator", {}))
    cell.say("data", generate_s=time.perf_counter() - t0,
             ratings=len(data["rating"]))

    opened = {}

    def on_window_open(now):
        opened["setup_s"] = now - cell.t_process
        opened["compiles"] = cell.clock.now()

    trace_dir = cell.scratch("trace") if cell.trace else None
    marks, U, V, U_prev, t_fit = drive_fit(
        data, cfg["als"], seed=cell.seed, seconds=cell.seconds,
        on_window_open=on_window_open,
        trace_iterations=mix["trace_iterations"] if cell.trace else 0,
        trace_dir=trace_dir)
    in_window = cell.clock.since(opened["compiles"])
    iter_s = np.diff(marks)
    probe_s, verdicts = probe_seconds()
    cell.say("probes", probe_s=probe_s, verdicts=verdicts)
    cell.say("fit", fit_first_iter_s=marks[0] - t_fit,
             compile_before_window=opened["compiles"],
             compile_in_window=in_window,
             iterations=len(iter_s), window_s=marks[-1] - marks[0],
             iter_s_median=float(np.median(iter_s)),
             iter_s_min=float(iter_s.min()), iter_s_max=float(iter_s.max()),
             iter_s_first=[float(t) for t in iter_s[:16]])

    t0 = time.perf_counter()
    found = reference_residuals(data, cfg, U, V, U_prev, seed=cell.seed,
                                n_rows=mix["check_rows"])
    checks = checks_from(found, U, V, cfg)
    checks.append(at_most("compilations_in_window",
                          in_window["compilations"], 0))
    cell.say("reference", seconds=time.perf_counter() - t0,
             rows_per_side=mix["check_rows"],
             row_distance={side: {"median": float(np.median(f["distance"])),
                                  "max": float(f["distance"].max())}
                           for side, f in found.items()})
    return Outcome(
        metrics={"setup_s": opened["setup_s"],
                 "train_iter_s": (marks[-1] - marks[0]) / len(iter_s)},
        attempted=len(iter_s), failed=0, checks=checks,
        counters={"fit_first_iter_s": marks[0] - t_fit, "probe_s": probe_s,
                  "iterations": len(iter_s)},
        trace_dir=trace_dir,
        artifacts={"data": data, "U": U, "V": V, "U_prev": U_prev,
                   "found": found})
