"""Single-chip rank-256 throughput proxy — BASELINE row 3 (config 3).

Config 3 is Amazon-2023 (~570M ratings, rank 256) on a v5e-32 mesh; the
mesh is not available here, so this measures the per-core slice: a
synthetic problem sized to ONE v5e core at the production rank (nnz and
entity counts scaled to 1/32 of the full set, rank kept at 256).  What it
establishes on real hardware:

- the rank-256 solve path (the flat lanes kernel caps at rank 128, so
  config 3 rides ``pallas_lanes_blocked`` — the out-of-core lanes
  factorization — with ``pallas_solve`` as the probe fallback): probe
  outcomes, the resolved dispatch, AND a direct solve-kernel A/B
  (xla vs pallas vs lanes_blocked) are printed;
- seconds/iteration for the full half-step pipeline at rank 256;
- peak HBM via ``device.memory_stats()`` — the model the CPU-mesh tests
  (tests/test_rank256.py) verify shape-by-shape, priced on chip.

Prints ONE JSON line (same contract as bench.py: without a TPU and
without ``--platform cpu`` it exits non-zero and prints none).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=1_700_000,
                    help="~54.5M Amazon-2023 users / 32 cores")
    ap.add_argument("--items", type=int, default=1_500_000,
                    help="~48M items / 32 cores")
    ap.add_argument("--nnz", type=int, default=18_000_000,
                    help="~570M ratings / 32 cores")
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink users/items/nnz together (quick checks)")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"])
    ap.add_argument("--solve-ab", type=int, default=8192,
                    help="SPD systems for the rank-256 solve-kernel A/B "
                         "(xla vs pallas vs lanes_blocked); 0 disables")
    args = ap.parse_args()

    metric = f"als_iters_per_sec_rank{args.rank}_single_core_proxy"
    import numpy as np

    import jax

    from bench import analytic_flops_per_iter, device_info, log

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif device_info()["platform"] != "tpu":
        raise SystemExit(f"rank256_proxy.py measures a TPU and JAX reports "
                         f"{device_info()}; pass --platform cpu for a dry run")
    from tpu_als.utils.platform import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    from tpu_als.core.als import (
        AlsConfig, init_factors, make_step, resolve_solve_path)
    from tpu_als.core.ratings import build_csr_buckets
    from tpu_als.io.movielens import synthetic_movielens
    from tpu_als.utils.platform import fence

    nU = max(64, int(args.users * args.scale))
    nI = max(64, int(args.items * args.scale))
    nnz = max(1024, int(args.nnz * args.scale))
    devs = jax.devices()
    log(f"devices: {devs}")

    t0 = time.time()
    frame = synthetic_movielens(nU, nI, nnz, seed=0)
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    r = np.asarray(frame["rating"])
    log(f"synthesized {nnz:,} ratings ({time.time()-t0:.1f}s)")
    ucsr = build_csr_buckets(u, i, r, nU)
    icsr = build_csr_buckets(i, u, r, nI)
    waste = (ucsr.padded_nnz + icsr.padded_nnz) / (2.0 * nnz)
    log(f"blocked (waste {waste:.2f}x)")

    cfg = AlsConfig(rank=args.rank, max_iter=1, reg_param=0.01,
                    implicit_prefs=True, alpha=40.0, seed=0)
    backends = resolve_solve_path(cfg, cfg.rank)
    log(f"resolved rank-{args.rank} backends: {backends}")

    # solve-kernel A/B at the production rank: xla vs pallas (blocked
    # first-gen) vs lanes_blocked (out-of-core lanes) on one batch of
    # SPD systems — records which kernel should own rank 256 on THIS
    # chip (the auto order is a projection until this measures it)
    solve_ab = {}
    if args.solve_ab > 0:
        import jax.numpy as jnp

        from tpu_als.ops.solve import solve_spd

        rng = np.random.default_rng(0)
        nsys = args.solve_ab
        M = rng.normal(size=(nsys, args.rank, args.rank)).astype(
            np.float32) / np.sqrt(args.rank)
        A = jnp.asarray(M @ np.swapaxes(M, 1, 2)
                        + 0.5 * np.eye(args.rank, dtype=np.float32)[None])
        bb = jnp.asarray(
            rng.normal(size=(nsys, args.rank)).astype(np.float32))
        cnt = jnp.ones((nsys,), jnp.float32)
        for be in ("xla", "pallas", "lanes_blocked"):
            try:
                x = solve_spd(A, bb, cnt, backend=be)
                x.block_until_ready()  # compile + 1 run
                t0 = time.time()
                for _ in range(3):
                    x = solve_spd(A, bb, cnt, backend=be)
                x.block_until_ready()
                solve_ab[be] = round((time.time() - t0) / 3, 4)
                log(f"solve A/B {be}: {solve_ab[be]}s for {nsys} systems")
            except Exception as e:
                solve_ab[be] = f"failed: {type(e).__name__}"
                log(f"solve A/B {be} failed: {e}")

    key = jax.random.PRNGKey(0)
    ku, kv = jax.random.split(key)
    U = init_factors(ku, nU, cfg.rank)
    V = init_factors(kv, nI, cfg.rank)
    ub = jax.device_put(ucsr.device_buckets())
    ib = jax.device_put(icsr.device_buckets())
    step = make_step(ub, ib, nU, nI, cfg, ucsr.chunk_elems, icsr.chunk_elems)

    t0 = time.time()
    U, V = step(U, V)
    U.block_until_ready()
    fence(U)
    log(f"warmup (compile + 1 iter): {time.time()-t0:.1f}s")

    t0 = time.time()
    for _ in range(args.iters):
        U, V = step(U, V)
    U.block_until_ready()
    fence(U)
    dt = time.time() - t0
    ips = args.iters / dt
    log(f"{args.iters} iters in {dt:.1f}s -> {ips:.4f} iters/sec")

    # peak HBM of the EXACT rank-256 pipeline — captured BEFORE the cg2
    # block so the figure prices the config-3 model, not the benchmark's
    # second factor set + executable (code-review r4)
    stats = {}
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        pass
    peak = stats.get("peak_bytes_in_use")
    flops = analytic_flops_per_iter(nnz, nU, nI, cfg.rank, implicit=True)
    payload = {
        "metric": metric,
        "value": round(ips, 4),
        "unit": "iters/sec",
        "vs_baseline": None,
        "baseline_note": "config-3 per-core slice (full set / 32); no "
                         "reference number exists for this config",
        "config": {
            "users": nU, "items": nI, "ratings": nnz, "rank": args.rank,
            "seconds_per_iter": round(dt / args.iters, 3),
            "padding_waste": round(waste, 3),
            "peak_hbm_gb": round(peak / 1e9, 3) if peak else None,
            "tflops_per_iter_analytic": round(flops / 1e12, 3),
            "achieved_tflops": round(flops * ips / 1e12, 3),
            "solve_ab_seconds": solve_ab,
            "cg2_matfree_iters_per_sec": None,
            "device": str(jax.devices()[0]),
            **backends,
        },
    }
    # bank the exact measurement NOW: if the step's timeout kills the cg2
    # attempt below, this JSON line already satisfies the sweep contract
    print(json.dumps(payload), flush=True)

    # config-3's inexact-ALS candidate at the same shapes: the r^3
    # factorization (the dominant stage at rank 256) becomes 2 batched
    # MXU matvecs
    try:
        from dataclasses import replace as _replace

        cfg_cg = _replace(cfg, cg_iters=2)
        step_cg = make_step(ub, ib, nU, nI, cfg_cg,
                            ucsr.chunk_elems, icsr.chunk_elems)
        Uc, Vc = init_factors(ku, nU, cfg.rank), init_factors(kv, nI,
                                                              cfg.rank)
        t0 = time.time()
        Uc, Vc = step_cg(Uc, Vc)
        fence(Uc)
        log(f"cg2 warmup (compile + 1 iter): {time.time()-t0:.1f}s")
        t0 = time.time()
        for _ in range(args.iters):
            Uc, Vc = step_cg(Uc, Vc)
        Uc.block_until_ready()
        fence(Uc)
        cg_ips = args.iters / (time.time() - t0)
        log(f"cg2 (matfree): {cg_ips:.4f} iters/sec "
            f"({cg_ips / ips:.2f}x exact)")
        payload["config"]["cg2_matfree_iters_per_sec"] = round(cg_ips, 4)
        # final line supersedes the banked one (readers take the LAST
        # JSON line)
        print(json.dumps(payload), flush=True)
    except Exception as e:
        log(f"cg2 timing failed: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
