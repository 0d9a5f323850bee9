#!/usr/bin/env python3
"""Readings behind the live-unseen cell's ``correct`` (PERF.md section 2):
for each seed one run of the cell through its runner and the numbers the
program was compared on; beside them the three CONTROLS:

(i)   ``--appends-off``: a second run of the seed with the histories frozen
      at publish (the rows move, the ids do not join): must read ``correct:
      false`` by the in-window check (``seen_returned_all_answers``) AND the
      after-drain one (``rated_in_the_run_returned_after_drain``);
(ii)  ``--events-only``: a run whose server has no resident history (the
      ``-live`` sibling's rule): must fail the fold check;
(iii) on the program's own run, the REFERENCE one precision step down in the
      program's place: every ``--folds``-th fold with bfloat16 operands
      against the float64 fold (its smallest error and the program's largest
      bracket the fold limit), and the sampled answers from an int4
      shortlist rescored in float8 (``topk_unseen.lower_precision_topk``),
      the same ids excluded.

One process for all seeds.  The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live_unseen.py --seeds 1,2 --seconds 10 --appends-off --events-only
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["TPU_ALS_PLAN_CACHE"] = "off"

CELL = "amazon23-r256-share32-live-unseen.serve-foldin-unseen"


def lower_precision(a, cell, every):
    import numpy as np

    from benchmark.reference import live_unseen as ref
    from benchmark.reference import topk_unseen
    from benchmark.runners import serve_unseen

    rep, tap, model, V = a["rep"], a["tap"], a["model"], a["V"]
    reg = cell.config["als"]["regParam"]
    folds = [(int(model._user_map.to_original([row])[0]), seq)
             for seq in sorted(tap.log) for row in tap.log[seq][0].tolist()]
    low = [ref.row_rel_err(ref.fold(V, rep, u, reg, s,
                                    operand_dtype="bfloat16"),
                           ref.fold(V, rep, u, reg, s))
           for u, s in folds[::every]]
    k, lim = cell.config["serving"]["k"], cell.config["correct"]
    exact = ref.exact_topk_left(a["Q"], V, k, a["excluded"])
    s, i = topk_unseen.lower_precision_topk(
        a["Q"], V, k, a["excluded"], shortlist_k=64, shortlist_bits=4,
        rescore_dtype="float8_e4m3fn")
    served = {c.name: c.value for c in serve_unseen.compare(
        "", s, i, a["Q"], V, a["excluded"], k, lim, exact)}
    return {"bfloat16_fold_row_rel_err": {
                "folds": len(low), "min": float(np.min(low)),
                "median": float(np.median(low)), "max": float(np.max(low))},
            "int4+float8_e4m3fn": served}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--appends-off", action="store_true")
    ap.add_argument("--events-only", action="store_true")
    ap.add_argument("--folds", type=int, default=8,
                    help="every n-th fold goes through the bfloat16 control")
    args = ap.parse_args()

    import numpy as np

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    mixes = [("program", {})]
    if args.appends_off:
        mixes.append(("appends_off", {"appends": False}))
    if args.events_only:
        mixes.append(("events_only", {"fold_base": False}))
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, mix in mixes:
            _, _, runner, cell = harness.open_cell(
                ROOT, args.workload, seed, args.seconds, False)
            cell.traffic = dict(cell.traffic, **mix)
            outcome = runner.run(cell)
            a = outcome.artifacts
            errs = a.get("fold_errs") or [float("nan")]
            print(json.dumps({
                "READINGS": args.workload, "seed": seed, "run": name,
                "correct": all(c.holds for c in outcome.checks),
                "failed_checks": [c.name for c in outcome.checks
                                  if not c.holds],
                "metrics": outcome.metrics,
                "memory_peak_bytes": harness.memory_peak_bytes(),
                "rated_back_after_drain": a.get("rated_back"),
                "by_id_with_seen_share": a.get("by_id_with_seen_share"),
                "fold_row_rel_err": {
                    "folds": len(errs), "median": float(np.median(errs)),
                    "max": float(np.max(errs))},
                "program": {c.name: c.value for c in outcome.checks},
                "control": (lower_precision(a, cell, args.folds)
                            if name == "program" else None)}), flush=True)
            # the tables leave the device before the next run's come
            del outcome, a, runner, cell
            gc.collect()


if __name__ == "__main__":
    main()
