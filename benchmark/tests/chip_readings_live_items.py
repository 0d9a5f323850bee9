#!/usr/bin/env python3
"""Readings that the limits of the moving-catalog cell's comparisons are set
from (PERF.md section 2): for each seed, one run of the cell through the
harness, the numbers the program was compared on, and beside them the
CONTROL's — what a replay with every fold's operands rounded one precision
step down would have published (bfloat16, the step below the configuration's
float32; and float8), held to the float64 folds as the program is, for the
same events in the same batches.  Also how far the rows the program published
drift from the replay that follows nobody (float64 from the seeded factors
alone), by the quarter of the run: the chain of folds amplifies, which is
why ``correct`` holds each fold to its own inputs.  One process for all
seeds, so compiles are paid once.  The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live_items.py --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["TPU_ALS_PLAN_CACHE"] = "off"

CELL = "amazon23-r256-share32-live-items.serve-foldin-items"


def drift_by_quarter(rep, free, quarters=4):
    """Largest relative distance between an item row the program published
    and the same fold's row in ``free``, the replay that follows nobody, by
    the quarter of the run the fold was in."""
    out = [0.0] * quarters
    n = max(len(rep.n_items), 1)
    theirs = {b: dict(zip(ids.tolist(), rows)) for b, ids, rows in
              free.item_log}
    for b, ids, rows in rep.item_log:
        for i, x in zip(ids.tolist(), rows):
            y = theirs.get(b, {}).get(i)
            if y is not None:
                q = min(quarters - 1, b * quarters // n)
                out[q] = max(out[q], float(np.linalg.norm(x - y)
                                           / np.linalg.norm(y)))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        _, _, runner, cell = harness.open_cell(
            ROOT, args.workload, seed, args.seconds, False)
        outcome = runner.run(cell)
        a = outcome.artifacts
        kept = (a["streams"], a["updater"], a["tap"], a["model"], a["U"],
                a["V"], cell.config)
        control = {}
        for dtype in ("bfloat16", "float8_e4m3fn"):
            held_to, _, _ = runner.replay_of(*kept, operand_dtype=dtype)
            control[dtype] = {c.name: c.value for c in runner.fold_checks(
                held_to, cell.config["correct"])}
        rep = a["replay"]
        users, items, stars = (np.concatenate(
            [getattr(ev, name)[ev.admitted] for _, ev in a["streams"]])
            for name in ("user", "item", "stars"))
        free = runner.ref_replay.replay(
            a["U"], a["V"], users, items, stars,
            [r["events"] for r in a["updater"].flight.records()
             if r.get("status") == "ok"], cell.config["als"]["regParam"])
        print(json.dumps({
            "READINGS": args.workload, "seed": seed,
            "correct": all(c.holds for c in outcome.checks),
            "metrics": outcome.metrics,
            "memory_peak_bytes": harness.memory_peak_bytes(),
            "program": {c.name: c.value for c in outcome.checks},
            "item_row_drift_by_quarter": drift_by_quarter(rep, free),
            "widest_fold": rep.widest,
            "control": control}), flush=True)
        # the tables leave the device before the next seed's come
        del outcome, a, kept, rep, free, runner, cell
        gc.collect()


if __name__ == "__main__":
    main()
