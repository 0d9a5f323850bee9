#!/usr/bin/env python3
"""Readings that the limits of the landing cell's comparisons are set from
(PERF.md section 2): for each seed, one run of the cell through the harness,
the numbers the program was compared on, and beside them the CONTROLS' —
what a program would have published, for the same events in the same
batches and landings, that

  ``no_catchup``   lands the refit's rows and nothing else (the events since
                   the snapshot lost until their entities are rated again);
  ``stale``        folds the catch-up over the tables as they stood BEFORE
                   the landing;
  ``bfloat16``     rounds every fold's operands one precision step down
                   (and ``float8_e4m3fn``, two);

each held to the float64 folds as the program is (numpy, at the cell's
size).  One process for all seeds, so compiles are paid once.  The
benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live_refit.py --seeds 1,2,3
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

CELL = "amazon23-r256-share32-live-refit.serve-foldin-refit"
CONTROLS = {"no_catchup": {"catchup": "none"}, "stale": {"catchup": "stale"},
            "bfloat16": {"operand_dtype": "bfloat16"},
            "float8_e4m3fn": {"operand_dtype": "float8_e4m3fn"}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--controls", default=",".join(CONTROLS))
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
                a["V"], a["refits"], cell.config)
        control = {}
        for name in (c for c in args.controls.split(",") if c):
            journal = runner.control_journal(*kept, **CONTROLS[name])
            held, _, _ = runner.replay_of(*kept, journal=journal)
            control[name] = {c.name: c.value for c in (
                runner.items.fold_checks(held, cell.config["correct"])
                + runner.catchup_checks(held, cell.config["correct"]))}
        print(json.dumps({
            "READINGS": args.workload, "seed": seed,
            "correct": all(c.holds for c in outcome.checks),
            "failed": outcome.failed, "metrics": outcome.metrics,
            "memory_peak_bytes": harness.memory_peak_bytes(),
            "program": {c.name: c.value for c in outcome.checks},
            "not_held": [c.name for c in outcome.checks if not c.holds],
            "catchup": a["replay"].catchup_sizes if a["replay"] else None,
            "control": control}), flush=True)
        # the tables leave the device before the next seed's come
        del outcome, a, kept, runner, cell
        gc.collect()


if __name__ == "__main__":
    main()
