"""Kernel lab: A/B the lanes-Cholesky kernel variants on the local TPU.

Sweeps the production ``spd_solve_lanes`` trailing-update panel widths for
correctness (vs the XLA lowering) and speed at a headline-representative
shape; the winner sets ``pallas_lanes.DEFAULT_PANEL``.

``--ne`` switches to the DMA-gather NE-build lab instead: per bucket
width, the fused gather+Gram kernel (ops/pallas_gather_ne) vs the XLA
gather+einsum build it replaces — wall time, max error, and the modeled
HBM bytes of each path (perf.roofline closed forms, the same numbers the
roofline stage table and the jaxpr audit pin).

``--solve-fused`` A/Bs the whole-iteration fused kernel
(``gather_solve``: gather → Gram → Cholesky → x, nothing but x in HBM)
against the unfused gather-NE kernel + lanes-Cholesky pipeline it
collapses, per bucket width, with both paths' modeled HBM bytes.

Usage: python scripts/kernel_lab.py [--n 262144] [--rank 128] [--panel 8]
       python scripts/kernel_lab.py --ne [--widths 64 256 1024]
       python scripts/kernel_lab.py --solve-fused [--platform cpu]
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax.numpy as jnp

from tpu_als.ops.pallas_lanes import LANES, spd_solve_lanes


def ne_lab(args, interpret):
    """Per-width fused-vs-einsum NE build A/B (the --ne mode)."""
    import jax

    from tpu_als.ops.pallas_gather_ne import gather_normal_eq_explicit
    from tpu_als.ops.solve import normal_eq_explicit
    from tpu_als.perf.roofline import (einsum_ne_build_bytes,
                                       fused_ne_kernel_bytes)
    from tpu_als.utils.platform import fence

    r = args.rank
    rng = np.random.default_rng(0)
    N = 1 << 16 if not interpret else 512
    V = jnp.asarray(rng.normal(size=(N, r)).astype(np.float32)
                    / np.sqrt(r))
    for w in args.widths:
        n = max(8, min(args.n, (1 << 22) // w) if not interpret else 16)
        cols = jnp.asarray(rng.integers(0, N, (n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
        mask = jnp.asarray((rng.random((n, w)) < 0.9).astype(np.float32))

        @jax.jit
        def fused(V, c, v, m):
            return gather_normal_eq_explicit(V, c, v, m, 0.1,
                                             interpret=interpret)

        @jax.jit
        def einsum(V, c, v, m):
            return normal_eq_explicit(V[c], v, m, 0.1)

        def best(f):
            fence(f(V, cols, vals, mask)[0])
            ts = []
            for _ in range(args.reps):
                t0 = time.time()
                fence(f(V, cols, vals, mask)[0])
                ts.append(time.time() - t0)
            return min(ts)

        tf, te = best(fused), best(einsum)
        err = np.abs(np.asarray(fused(V, cols, vals, mask)[0])
                     - np.asarray(einsum(V, cols, vals, mask)[0])).max()
        P = n * w
        fb = fused_ne_kernel_bytes(P, n, max(128, r), 4)
        eb = einsum_ne_build_bytes(P, n, r, 4)
        print(f"w={w:6d} n={n:7d}: fused {tf*1e3:8.2f} ms "
              f"({fb/1e9/max(tf,1e-9):6.1f} GB/s model)  "
              f"einsum {te*1e3:8.2f} ms "
              f"({eb/1e9/max(te,1e-9):6.1f} GB/s model)  "
              f"speedup {te/max(tf,1e-9):5.2f}x  maxerr {err:.2e}",
              flush=True)


def solve_fused_lab(args, interpret):
    """Whole-iteration fused gather→Gram→solve vs the unfused gather-NE
    + lanes-Cholesky pipeline (the --solve-fused mode)."""
    import jax

    from tpu_als.ops.pallas_gather_ne import (
        gather_fused_solve_explicit,
        gather_normal_eq_explicit,
    )
    from tpu_als.ops.solve import DEFAULT_JITTER, solve_spd
    from tpu_als.perf.roofline import (fused_ne_kernel_bytes,
                                       fused_solve_kernel_bytes)
    from tpu_als.utils.platform import fence

    r = args.rank
    rng = np.random.default_rng(0)
    N = 1 << 16 if not interpret else 512
    V = jnp.asarray(rng.normal(size=(N, r)).astype(np.float32)
                    / np.sqrt(r))
    for w in args.widths:
        n = max(8, min(args.n, (1 << 22) // w) if not interpret else 16)
        cols = jnp.asarray(rng.integers(0, N, (n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
        mask = jnp.asarray((rng.random((n, w)) < 0.9).astype(np.float32))

        @jax.jit
        def fused(V, c, v, m):
            return gather_fused_solve_explicit(V, c, v, m, 0.1,
                                               interpret=interpret)

        @jax.jit
        def unfused(V, c, v, m):
            A, bb, cnt = gather_normal_eq_explicit(V, c, v, m, 0.1,
                                                   interpret=interpret)
            if r <= 128:
                # same Cholesky family the fused tail embeds, same
                # interpret setting — the delta is the fusion, not a
                # solver swap
                A = A + DEFAULT_JITTER * jnp.eye(r, dtype=A.dtype)
                return spd_solve_lanes(A, bb, interpret=interpret)
            return solve_spd(A, bb, cnt)

        def best(f):
            fence(f(V, cols, vals, mask))
            ts = []
            for _ in range(args.reps):
                t0 = time.time()
                fence(f(V, cols, vals, mask))
                ts.append(time.time() - t0)
            return min(ts)

        tf, tu = best(fused), best(unfused)
        err = np.abs(np.asarray(fused(V, cols, vals, mask))
                     - np.asarray(unfused(V, cols, vals, mask))).max()
        P = n * w
        r_pad = max(128, r)
        fb = fused_solve_kernel_bytes(P, n, r_pad, 4)
        # the unfused comparator's traffic: NE kernel + the A/b HBM
        # handoff the fusion deletes (write by NE, read by solver)
        ub = (fused_ne_kernel_bytes(P, n, r_pad, 4)
              + 2 * n * (r_pad * r_pad + r_pad) * 4)
        print(f"w={w:6d} n={n:7d}: fused_solve {tf*1e3:8.2f} ms "
              f"({fb/1e9/max(tf,1e-9):6.1f} GB/s model)  "
              f"ne+lanes {tu*1e3:8.2f} ms "
              f"({ub/1e9/max(tu,1e-9):6.1f} GB/s model)  "
              f"speedup {tu/max(tf,1e-9):5.2f}x  maxerr {err:.2e}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32768)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--panels", type=int, nargs="*", default=[4, 8, 16])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ne", action="store_true",
                    help="run the gather-fused NE-build lab instead of "
                         "the solver panel sweep")
    ap.add_argument("--solve-fused", action="store_true",
                    help="run the whole-iteration fused-solve lab "
                         "(gather_solve vs gather-NE + lanes Cholesky)")
    ap.add_argument("--widths", type=int, nargs="*",
                    default=[64, 256, 1024])
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="cpu = force the CPU backend + interpret-mode "
                         "kernels (a dry run of the script; its timings "
                         "are the interpreter's)")
    args = ap.parse_args()
    n, r = args.n, args.rank

    interpret = args.platform == "cpu"
    if interpret:
        import jax

        jax.config.update("jax_platforms", "cpu")
        # interpret mode serially emulates every lane group: a full-size
        # timing batch would take hours and its numbers are meaningless
        # anyway — the dry-run exists to prove the script + kernels run
        # end-to-end, so shrink the batch and keep the correctness check
        n = min(n, 2 * LANES)

    from tpu_als.utils.platform import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    if args.solve_fused:
        return solve_fused_lab(args, interpret)
    if args.ne:
        return ne_lab(args, interpret)

    rng = np.random.default_rng(0)
    # correctness batch (small), validated vs XLA
    nc = LANES + 8
    M = rng.normal(size=(nc, r, r)).astype(np.float32) / np.sqrt(r)
    Ac = jnp.asarray(M @ np.swapaxes(M, 1, 2)
                     + 0.5 * np.eye(r, dtype=np.float32)[None])
    bc = jnp.asarray(rng.normal(size=(nc, r)).astype(np.float32))
    from tpu_als.ops.solve import solve_spd
    ref = np.asarray(solve_spd(Ac, bc, jnp.ones(nc), backend="xla"))

    # timing batch: same SPD instance tiled ON DEVICE — only the small
    # correctness batch (~8 MB) crosses from the host
    reps = -(-n // nc)
    A = jnp.tile(Ac, (reps, 1, 1))[:n]
    b = jnp.tile(bc, (reps, 1))[:n]
    A.block_until_ready()
    print(f"data staged: {A.nbytes/1e9:.1f} GB on device", flush=True)

    def bench(f, label):
        x = f(A, b)
        x.block_until_ready()
        t0 = time.time()
        for _ in range(args.reps):
            x = f(A, b)
        x.block_until_ready()
        dt = (time.time() - t0) / args.reps
        print(f"{label:20s} {dt*1e3:8.1f} ms  "
              f"({n / dt / 1e6:.2f} M solves/s)", flush=True)
        return x

    if r <= 128:
        for p in [1] + list(args.panels):
            # panels wide enough to feed the MXU get both trailing-update
            # variants; rank-1 sweeps have nothing for the matrix unit
            for mx in ((False, True) if p >= 8 else (False,)):
                f = functools.partial(spd_solve_lanes, panel=p, mxu=mx,
                                      interpret=interpret)
                tag = f"lanes panel={p}" + (" mxu" if mx else "")
                bench(f, tag)
                err = np.abs(np.asarray(
                    spd_solve_lanes(Ac, bc, panel=p, mxu=mx,
                                    interpret=interpret))
                    - ref).max()
                print(f"  {tag} max err vs xla: {err:.2e}")
    else:
        # ranks past the flat layout: sweep the blocked out-of-core
        # kernel's panel width (stream/factor panels) the same way
        from tpu_als.ops.pallas_lanes_blocked import spd_solve_lanes_blocked

        for p in args.panels:
            for mx in ((False, True) if p >= 8 else (False,)):
                f = functools.partial(spd_solve_lanes_blocked, panel=p,
                                      mxu=mx, interpret=interpret)
                tag = f"lanes_blocked panel={p}" + (" mxu" if mx else "")
                bench(f, tag)
                err = np.abs(np.asarray(
                    spd_solve_lanes_blocked(Ac, bc, panel=p, mxu=mx,
                                            interpret=interpret))
                    - ref).max()
                print(f"  {tag} max err vs xla: {err:.2e}")


if __name__ == "__main__":
    main()
