#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the same engine, factors
and mix at a ladder of offered rates, one window each.  The knee is the
highest rate at which nothing is shed, at least 99 % of requests are
answered inside the window and ``gen_late_p99_ms`` does not grow through
the window; the cell's rate is 0.8 x the knee, rounded to two figures and
written into the traffic file by hand (PERF.md section 4 has the table).

    python3 benchmark/tests/serve_sweep.py --workload <name> --rates 1000,2000,4000 --seconds 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["TPU_ALS_PLAN_CACHE"] = "off"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import datagen, harness
    from benchmark.runners import serve

    enable_persistent_compile_cache()
    _, w, cfg, mix, _ = harness.cell_files(ROOT, args.workload)
    harness.device_info(w["chips"])
    engine, U, V, _ = serve.start_engine(cfg, mix, args.seed)
    rng = datagen.rng_for(args.seed, 2)
    try:
        for rate in [0.25 * float(args.rates.split(",")[0])] + [
                float(r) for r in args.rates.split(",")]:
            loop, _ = serve.open_stream(engine, U, dict(mix, rate_per_s=rate),
                                        rng, args.seconds, cfg["serving"]["k"])
            loop.run()
            lat, late = loop.latency_ms(), loop.late_ms()
            in_window = (loop.t_done[loop.answered()] - loop.t0
                         <= mix["warmup_seconds"] + args.seconds).sum()
            half = len(late) // 2
            print(json.dumps({
                "SWEEP": args.workload, "offered_per_s": rate,
                "requests": loop.n, "answered": len(lat),
                "answered_in_window_share": float(in_window) / loop.n,
                "shed_or_failed": loop.n - len(lat),
                "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
                "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
                "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
                "late_p99_first_half_ms": float(np.percentile(late[:half], 99)),
                "late_p99_second_half_ms": float(np.percentile(late[half:], 99)),
                "queue_p50_ms": float(np.median(loop.queue_ms())),
                "batches": loop.batches(), "batch_sizes": loop.batch_sizes(),
                "gc": loop.gc_clock.summary(),
                "slowest": loop.slowest(3),
                "drain_s": loop.t_end - loop.t_last_submit}), flush=True)
    finally:
        engine.stop()


if __name__ == "__main__":
    main()
