"""Decompose the non-solve half-step cost: gather vs normal-equation
einsum vs scatter, per bucket width, at ML-25M shapes — and A/B the
DMA-gather fused NE kernel (ops/pallas_gather_ne) against the unfused
gather+einsum it replaces, per bucket, with its modeled HBM bytes.

The round-2 on-chip ablation pinned the solve at ~60%+ of the iteration;
this script breaks down the remaining ~0.78 s/iter so the next kernel
effort targets the right stage.  Each stage is timed as its own jitted
program over the real ML-25M/scale bucket layout (padding included), each
timed region ended by ``utils.platform.fence``.

Usage: python scripts/profile_ne.py [--scale 25] [--rank 128]
       [--platform cpu]   (interpret-mode dry run of the script)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from tpu_als.core.ratings import build_csr_buckets, trainer_chunk
from tpu_als.io.movielens import ML25M_SHAPE, synthetic_movielens
from tpu_als.utils.platform import fence


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=25)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="cpu = force the CPU backend + interpret-mode "
                         "fused kernel (dry run; timings meaningless)")
    args = ap.parse_args()

    interpret = args.platform == "cpu"
    if interpret:
        jax.config.update("jax_platforms", "cpu")
        args.scale = max(args.scale, 2500)   # interpret mode is serial

    nU, nI, nnz = (s // args.scale for s in ML25M_SHAPE)
    r = args.rank
    cdt = jnp.dtype(args.compute_dtype)
    frame = synthetic_movielens(nU, nI, nnz, seed=0)
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    rv = np.asarray(frame["rating"])

    for side, (ri, ci, n_rows, n_opp) in {
        "user": (u, i, nU, nI), "item": (i, u, nI, nU),
    }.items():
        csr = build_csr_buckets(ri, ci, rv, n_rows)
        V = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(0), (n_opp, r), jnp.float32))
        print(f"--- {side} side: {len(csr.buckets)} buckets, "
              f"padded {csr.padded_nnz / csr.nnz:.2f}x ---", flush=True)

        for b in csr.buckets:
            nb, w = b.cols.shape
            chunk = trainer_chunk(nb, w, r, csr.chunk_elems)
            nch = nb // chunk
            cols = jax.device_put(b.cols.reshape(nch, chunk, w))
            vals = jax.device_put(b.vals.reshape(nch, chunk, w))
            mask = jax.device_put(b.mask.reshape(nch, chunk, w))

            def run(stage):
                def gather_only(c, v, m):
                    return V[c].astype(cdt).sum(axis=(1, 2))

                def einsum_only(c, v, m):
                    # gather replaced by a broadcast of row 0: same einsum
                    # shapes, no random access
                    Vg = jnp.broadcast_to(
                        V[:1].astype(cdt)[None], (c.shape[0], w, r))
                    conf = (40.0 * jnp.abs(v) * m).astype(cdt)
                    A = jnp.einsum("nw,nwr,nws->nrs", conf, Vg, Vg,
                                   preferred_element_type=jnp.float32)
                    return A.sum(axis=(1, 2))

                def both(c, v, m):
                    Vg = V[c].astype(cdt)
                    conf = (40.0 * jnp.abs(v) * m).astype(cdt)
                    A = jnp.einsum("nw,nwr,nws->nrs", conf, Vg, Vg,
                                   preferred_element_type=jnp.float32)
                    return A.sum(axis=(1, 2))

                def fused(c, v, m):
                    # the DMA-gather kernel doing the same one-sided
                    # conf-weighted Gram — Vg never materialized
                    from tpu_als.ops.pallas_gather_ne import gather_gram

                    conf = (40.0 * jnp.abs(v) * m).astype(cdt)
                    S, _ = gather_gram(V.astype(cdt), c, conf,
                                       (v * m).astype(cdt),
                                       two_sided=False,
                                       interpret=interpret)
                    return S.sum(axis=(1, 2))

                f = {"gather": gather_only, "einsum": einsum_only,
                     "gather+einsum": both, "fused": fused}[stage]

                @jax.jit
                def prog(cols, vals, mask):
                    def body(args):
                        return f(*args)
                    return jax.lax.map(body, (cols, vals, mask)).sum()

                out = prog(cols, vals, mask)
                fence(out)
                t0 = time.time()
                for _ in range(args.iters):
                    out = prog(cols, vals, mask)
                fence(out)
                return (time.time() - t0) / args.iters

            tg = run("gather")
            te = run("einsum")
            tb = run("gather+einsum")
            tf = run("fused")
            gb = nb * w * r * 4 / 1e9
            fl = 2 * nb * w * r * r / 1e12
            # the fused kernel's modeled HBM bytes (the CostEstimate /
            # roofline single source of truth) at this bucket's shape
            from tpu_als.perf.roofline import fused_ne_kernel_bytes

            fgb = fused_ne_kernel_bytes(nb * w, nb, max(128, r),
                                        cdt.itemsize) / 1e9
            print(f"w={w:6d} rows={nb:8d} ({nch} chunks): "
                  f"gather {tg*1e3:7.2f} ms ({gb/max(tg,1e-9):5.1f} GB/s)  "
                  f"einsum {te*1e3:7.2f} ms ({fl/max(te,1e-9):5.2f} TF/s)  "
                  f"both {tb*1e3:7.2f} ms  "
                  f"fused {tf*1e3:7.2f} ms "
                  f"({fgb/max(tf,1e-9):5.1f} GB/s model, "
                  f"{tb/max(tf,1e-9):4.2f}x vs both)", flush=True)


if __name__ == "__main__":
    main()
