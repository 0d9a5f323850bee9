#!/usr/bin/env python3
"""What a batch pays for the delta segment's slots, on the chip: the
scoring program with a segment (``serving.engine._serve_int8_packed(delta=)``)
at the live cells' size for several numbers of slots, beside the delta-free
one, bucket 8 and 128, median of 30 runs each after a warm-up.  The reading
``plan.DEFAULT_LIVE_CADENCE["compact_delta_frac"]`` was moved from (PERF.md
section 6, PR 34).  Exits 1 without a TPU.

    chiprun -- python3 scripts/time_delta_segment.py
"""

from __future__ import annotations

import json
import os
import statistics as st
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

USERS, ITEMS, RANK = 1_703_438, 1_505_938, 256


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("time_delta_segment: no TPU", file=sys.stderr)
        return 1
    from tpu_als.core.ratings import row_capacity
    from tpu_als.serving.engine import _serve_int8_packed
    from tpu_als.serving.index import build_index

    key = jax.random.PRNGKey(0)
    V = np.asarray(jax.random.normal(key, (ITEMS, RANK), jnp.float32)) / 16
    U = jax.random.normal(key, (row_capacity(USERS), RANK), jnp.float32)
    idx = build_index(V, shortlist_k=64).reserve(rows=row_capacity(ITEMS))
    del V

    def timed(fn, *args, **kw):
        fn(*args, **kw).block_until_ready()
        runs = []
        for _ in range(30):
            t0 = time.perf_counter()
            fn(*args, **kw).block_until_ready()
            runs.append(1e3 * (time.perf_counter() - t0))
        return st.median(runs)

    for bucket in (8, 128):
        packed = jnp.zeros((bucket, RANK + 2), jnp.int32)
        row = {"bucket": bucket, "no_segment_ms": timed(
            _serve_int8_packed, U, idx.Vq, idx.sv, idx.V, idx.valid, (), (),
            packed, k=10, shortlist_k=64)}
        for slots in (512, 4096, 32768, 262144):
            seg = idx.reserve(slots=slots)
            row[f"slots_{slots}_ms"] = timed(
                _serve_int8_packed, U, seg.Vq, seg.sv, seg.V, seg.valid,
                (*seg._seg, seg._last_id()), (), packed, k=10,
                shortlist_k=64)
            del seg
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
