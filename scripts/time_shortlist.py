#!/usr/bin/env python3
"""The shortlist selection alone, on the chip: ``jax.lax.top_k`` against
``ops.topk.shortlist_topk`` on ``f32[B, 1505938]`` (the benchmark cell's
catalog), k = 64, B in {8, 32, 128}.

    chiprun -- python scripts/time_shortlist.py

Each call is fenced with ``block_until_ready``; 20 repeats after a warm
one, median ms, and the plan (stages, blocks, L) as chosen.  One JSON line
per B, then a table.  The input is a plain row-major matrix of N(0, 1)
scores, not the int8 score fusion's output: inside the serving program the
matrix is already whole blocks wide (the index pads its catalog once, at
build time), here the ragged 1,505,938 pays its pad, so the second pair of
columns times the function at the padded width too.  Exits 1 without a TPU:
a CPU's times are not the chip's.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_als.ops.topk import (  # noqa: E402
    shortlist_columns,
    shortlist_plan,
    shortlist_topk,
)

COLUMNS = 1_505_938
K = 64
BATCHES = (8, 32, 128)
REPEATS = 20


@jax.jit
def single(scores):
    return jax.lax.top_k(scores, K)


@jax.jit
def staged(scores):
    return shortlist_topk(scores, K)


def median_ms(fn, x):
    jax.block_until_ready(fn(x))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"time_shortlist: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    padded = shortlist_columns(COLUMNS, K)
    rows = []
    for b in BATCHES:
        row = {"B": b, "k": K, "device": dev.device_kind}
        for cols in (COLUMNS, padded):
            x = jax.random.normal(jax.random.PRNGKey(b), (b, cols),
                                  jnp.float32)
            want, got = single(x), staged(x)
            same = bool(jnp.array_equal(want[0], got[0])
                        and jnp.array_equal(want[1], got[1]))
            plan = shortlist_plan(cols, K)
            row[str(cols)] = {
                "top_k_ms": median_ms(single, x),
                "shortlist_topk_ms": median_ms(staged, x),
                "equal": same, **plan._asdict()}
            del x, want, got
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(f"\n| B | lax.top_k ms | shortlist_topk ms | at {padded} columns: "
          "lax.top_k ms | shortlist_topk ms | stages, blocks, L | equal |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for row in rows:
        a, p = row[str(COLUMNS)], row[str(padded)]
        print(f"| {row['B']} | {a['top_k_ms']:.3f} | "
              f"{a['shortlist_topk_ms']:.3f} | {p['top_k_ms']:.3f} | "
              f"{p['shortlist_topk_ms']:.3f} | {a['stages']}, {a['blocks']}, "
              f"{a['block_len']} | {a['equal'] and p['equal']} |")
    return 0 if all(r[str(c)]["equal"] for r in rows
                    for c in (COLUMNS, padded)) else 1


if __name__ == "__main__":
    sys.exit(main())
