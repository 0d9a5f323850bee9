"""Perf ablation for the ALS half-step: where do the milliseconds go?

Builds the same bucketed step as tpu_als.core.als but with individual stages
ablatable, so stage cost = full - ablated (single jitted call per variant —
per-dispatch latency makes micro-timing of the stages useless).

Usage: python scripts/ablate.py [--scale 25] [--rank 128] [--variants ...]
"""

import argparse
import os
import sys
import time

# repo-root import without PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from tpu_als.core.ratings import build_csr_buckets, trainer_chunk
from tpu_als.io.movielens import ML25M_SHAPE, synthetic_movielens
from tpu_als.ops.solve import (
    compute_yty, normal_eq_explicit, normal_eq_implicit, solve_cg,
    solve_spd)


def half_step(V_full, buckets, num_rows, rank, chunk_elems, YtY, ab, cfgd):
    out = jnp.zeros((num_rows, rank), jnp.float32)
    for b in buckets:
        nb, w = b.cols.shape
        chunk = trainer_chunk(nb, w, rank, chunk_elems)
        nch = nb // chunk
        cols = b.cols.reshape(nch, chunk, w)
        vals = b.vals.reshape(nch, chunk, w)
        mask = b.mask.reshape(nch, chunk, w)

        cdt = jnp.dtype(cfgd["compute_dtype"])
        V_comp = V_full.astype(cdt)

        def f(args):
            c, v, m = args
            if cfgd["solve_backend"] == "gather_fused_solve" and ab not in (
                    "no-neq", "no-solve"):
                from tpu_als.ops.pallas_gather_ne import (
                    gather_fused_solve_explicit, gather_fused_solve_implicit)
                from tpu_als.utils.platform import on_tpu

                # whole-iteration fused kernel: the gather happens inside
                # (DMA ring), so no-gather ablates by pinning the indices
                interp = not on_tpu()
                c_ab = c * 0 if ab == "no-gather" else c
                if cfgd["implicit"]:
                    return gather_fused_solve_implicit(
                        V_comp, c_ab, v.astype(cdt), m.astype(cdt),
                        cfgd["reg"], cfgd["alpha"],
                        YtY.astype(jnp.float32), interpret=interp)
                return gather_fused_solve_explicit(
                    V_comp, c_ab, v.astype(cdt), m.astype(cdt),
                    cfgd["reg"], interpret=interp)
            if ab == "no-gather":
                # same gather op, all indices 0: measures the random-access
                # penalty (cache-resident source row) without changing the
                # program shape
                Vg = V_comp[c * 0]
            else:
                Vg = V_comp[c]
            if ab == "no-neq":
                A = jnp.broadcast_to(
                    jnp.eye(rank) * 2.0, (chunk, rank, rank))
                rhs = Vg[:, 0, :]
                cnt = jnp.sum(m, axis=-1)
            elif cfgd["implicit"]:
                A, rhs, cnt = normal_eq_implicit(
                    Vg, v.astype(cdt), m.astype(cdt), cfgd["reg"],
                    cfgd["alpha"], YtY)
            else:
                A, rhs, cnt = normal_eq_explicit(
                    Vg, v.astype(cdt), m.astype(cdt), cfgd["reg"])
            A = A.astype(jnp.float32)
            rhs = rhs.astype(jnp.float32)
            if ab == "no-solve":
                return rhs
            sb = cfgd["solve_backend"]
            if cfgd["cg_iters"] > 0 and sb != "gather_fused_solve":
                # inexact-ALS solve: timing is warm-start-invariant (same
                # fixed iteration count), so the ablation runs it cold
                return solve_cg(A, rhs, cnt, iters=cfgd["cg_iters"])
            # under --solve-backend gather_fused_solve the no-neq/no-solve
            # variants fall back to the unfused path; use the XLA solver
            # there so the stage delta isn't conflated with a solver swap
            return solve_spd(
                A, rhs, cnt,
                backend="xla" if sb == "gather_fused_solve" else sb)

        if nch == 1:
            xs = f((cols[0], vals[0], mask[0]))[None]
        else:
            xs = jax.lax.map(f, (cols, vals, mask))
        if ab != "no-scatter":
            out = out.at[b.rows].set(
                xs.reshape(nb, rank), mode="drop", unique_indices=True)
        else:
            out = out + jnp.sum(xs) * 0  # keep xs live
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=25, help="divide ML-25M by")
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--explicit", action="store_true")
    ap.add_argument("--variants", nargs="*", default=[
        "full", "no-solve", "no-gather", "no-neq", "no-scatter"])
    ap.add_argument("--solve-backend", default="auto",
                    choices=["auto", "xla", "pallas", "lanes",
                             "gather_fused_solve"])
    ap.add_argument("--subproc", action="store_true",
                    help="run each variant in its own subprocess with a "
                         "timeout so one pathological compile cannot hang "
                         "the whole sweep")
    ap.add_argument("--variant-timeout", type=int, default=420)
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="dtype for the gather/normal-equation stage")
    ap.add_argument("--cg-iters", type=int, default=0,
                    help="> 0: ablate with the inexact-ALS CG solve "
                         "instead of the factorization")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="cpu = force the CPU backend (smoke tests)")
    args = ap.parse_args()
    from tpu_als.utils.platform import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    if args.cg_iters > 0 and args.solve_backend == "gather_fused_solve":
        # the forced fusion takes precedence over cg (core/als.py doc) —
        # refusing the combination beats printing fused timings under a
        # CG label
        ap.error("--cg-iters cannot be combined with --solve-backend "
                 "gather_fused_solve (the fused kernel would run and the "
                 "output would be mislabeled as a CG ablation)")
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    if args.subproc:
        # one process for each chip: this parent has imported jax but
        # never initialises a backend (no jax.devices(), no array) before
        # it returns, so the children — run one after another — each get
        # the device to themselves
        import subprocess
        import sys as _sys

        for v in args.variants:
            cmd = [_sys.executable, os.path.abspath(__file__),
                   "--scale", str(args.scale), "--rank", str(args.rank),
                   "--iters", str(args.iters),
                   "--solve-backend", args.solve_backend,
                   "--compute-dtype", args.compute_dtype,
                   "--cg-iters", str(args.cg_iters),
                   "--platform", args.platform,
                   "--variants", v]
            if args.explicit:
                cmd.append("--explicit")
            try:
                subprocess.run(cmd, timeout=args.variant_timeout)
            except subprocess.TimeoutExpired:
                print(f"{v:12s} TIMEOUT after {args.variant_timeout}s",
                      flush=True)
        return

    nU, nI, nnz = (s // args.scale for s in ML25M_SHAPE)
    frame = synthetic_movielens(nU, nI, nnz, seed=0)
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    r = np.asarray(frame["rating"])
    ucsr = build_csr_buckets(u, i, r, nU)
    icsr = build_csr_buckets(i, u, r, nI)
    ub = jax.device_put(ucsr.device_buckets())
    ib = jax.device_put(icsr.device_buckets())
    cfgd = {"implicit": not args.explicit, "reg": 0.01, "alpha": 40.0,
            "solve_backend": args.solve_backend,
            "compute_dtype": args.compute_dtype,
            "cg_iters": args.cg_iters}
    rank = args.rank

    def step_impl(U, V, ub, ib, ab):
        YtY_u = compute_yty(U) if cfgd["implicit"] else None
        V = half_step(U, ib, nI, rank, icsr.chunk_elems, YtY_u, ab, cfgd)
        YtY_v = compute_yty(V) if cfgd["implicit"] else None
        U = half_step(V, ub, nU, rank, ucsr.chunk_elems, YtY_v, ab, cfgd)
        return U, V

    from tpu_als.utils.platform import fence

    if args.solve_backend in ("auto", "pallas", "lanes") and \
            args.cg_iters == 0:
        # probe the solve kernels EAGERLY: probes cannot run inside the
        # jit traces below (probe_kernel degrades that trace to the
        # fallback without caching), which would silently measure the XLA
        # path under an 'auto' label.  The CG path never touches the
        # Pallas solvers, so probing there would only burn compile time.
        from tpu_als.ops.solve import prewarm_solve

        prewarm_solve(rank)

    base = None
    for ab in args.variants:
        key = jax.random.PRNGKey(0)
        ku, kv = jax.random.split(key)
        U = jax.random.normal(ku, (nU, rank), jnp.float32)
        V = jax.random.normal(kv, (nI, rank), jnp.float32)
        # tal: disable=bare-jit -- one jit per ablation variant is the point:
        # each variant IS a different step function, compiled and timed once
        step = jax.jit(lambda U, V, ub, ib: step_impl(U, V, ub, ib, ab),
                       donate_argnums=(0, 1))
        t0 = time.time()
        U, V = step(U, V, ub, ib)
        fence(U)
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(args.iters):
            U, V = step(U, V, ub, ib)
        fence(U)
        dt = (time.time() - t0) / args.iters
        if ab == "full":
            base = dt
        delta = f"  (saves {base - dt:+.3f}s)" if base and ab != "full" else ""
        print(f"{ab:12s} {dt:7.3f} s/iter  [compile {compile_s:.1f}s]{delta}",
              flush=True)


if __name__ == "__main__":
    main()
