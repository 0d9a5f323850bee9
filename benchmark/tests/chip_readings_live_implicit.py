#!/usr/bin/env python3
"""Readings that the limits of the implicit live cell's comparisons are set
from (PERF.md section 2), and its three CONTROLS, each of which must read
``correct: false``.  For each seed one run of the cell through the harness:
the numbers the program was compared on and, beside them, control (iii) —
what a replay would have published whose operands are rounded one precision
step down (bfloat16, the step below the configuration's float32): in every
fold AND in the Gram matrices (``all``), in the folds alone (``folds``), in
the whole-table Gram matrices and their updates alone (``grams``: can any
reading tell a bfloat16 Gram matrix from a float32 one at this size?), each
held to the float64 folds as the program is; and the Gram matrices of the
final tables from bfloat16 rows, held as the program's kept ones are.

``--frozen-gram``: control (i), one more run on the first seed with the
program's Gram matrices FROZEN at their start value (the Gram-carrying row
write patched to leave the matrix as it was).  ``--explicit-rule``: control
(ii), one more run with the PROGRAM given the explicit rule on this
configuration (``run(cell, program_als=...)``), the reference keeping the
configuration's.  One process for all runs, so compiles are paid once.  The
benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live_implicit.py --seeds 1,2,3 \\
        --frozen-gram --explicit-rule
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["TPU_ALS_PLAN_CACHE"] = "off"

CELL = "amazon23-r256-share32-live-implicit.serve-foldin-implicit"
LOWER = "bfloat16"


def freeze_gram():
    """Patch the program's Gram-carrying row write to leave the matrix as
    it was; returns the undo."""
    import jax

    from tpu_als.core import foldin

    real = foldin._scatter_rows_yty

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _scatter_rows_yty(table, yty, rows, vals):
        return table.at[rows].set(vals, mode="drop"), yty

    foldin._scatter_rows_yty = _scatter_rows_yty
    return lambda: setattr(foldin, "_scatter_rows_yty", real)


def precision_controls(runner, a, config):
    """Control (iii) by what is rounded, from one run's artifacts."""
    from benchmark.reference import foldin_implicit as ref_rule

    kept = (a["streams"], a["updater"], a["tap"], a["model"], a["U"],
            a["V"], config)
    out = {}
    for name, how in (("all", dict(operand_dtype=LOWER, gram_dtype=LOWER)),
                      ("folds", dict(operand_dtype=LOWER)),
                      ("grams", dict(gram_dtype=LOWER))):
        held_to, _, _ = runner.replay_of(*kept, **how)
        out[name] = {c.name: c.value for c in runner.fold_checks(
            held_to, config["correct"])}
    rep = a["replay"]
    low = [ref_rule.gram(rep.final_table(side), operand_dtype=LOWER)
           for side in (0, 1)]
    checks, _ = runner.gram_checks(None, rep, config["correct"], 0, kept=low)
    out["gram_of_lower_rows"] = {c.name: c.value for c in checks
                                 if c.name.endswith("_rel_err")}
    return out


def one_run(harness, workload, seed, seconds, **how):
    _, _, runner, cell = harness.open_cell(ROOT, workload, seed, seconds,
                                           False)
    outcome = runner.run(cell, **how)
    return runner, cell, outcome


def said(what, workload, seed, outcome, harness, **more):
    print(json.dumps({
        "READINGS": workload, "run": what, "seed": seed,
        "correct": all(c.holds for c in outcome.checks),
        "failed_checks": sorted(c.name for c in outcome.checks
                                if not c.holds),
        "metrics": outcome.metrics,
        "memory_peak_bytes": harness.memory_peak_bytes(),
        "program": {c.name: c.value for c in outcome.checks},
        "limits": {c.name: c.limit for c in outcome.checks}, **more}),
        flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--frozen-gram", action="store_true")
    ap.add_argument("--explicit-rule", action="store_true")
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        runner, cell, outcome = one_run(harness, args.workload, seed,
                                        args.seconds)
        a = outcome.artifacts
        said("program", args.workload, seed, outcome, harness,
             widest_fold=a["replay"].widest if a["replay"] else None,
             control=(precision_controls(runner, a, cell.config)
                      if a["replay"] else None))
        # the tables leave the device before the next run's come
        del outcome, a, runner, cell
        gc.collect()
    if args.frozen_gram:
        undo = freeze_gram()
        try:
            _, _, outcome = one_run(harness, args.workload, seeds[0],
                                    args.seconds)
        finally:
            undo()
        said("control_frozen_gram", args.workload, seeds[0], outcome,
             harness)
        del outcome
        gc.collect()
    if args.explicit_rule:
        config = harness.cell_files(ROOT, args.workload)[2]
        _, _, outcome = one_run(
            harness, args.workload, seeds[0], args.seconds,
            program_als=dict(config["als"], implicitPrefs=False))
        said("control_explicit_rule", args.workload, seeds[0], outcome,
             harness)


if __name__ == "__main__":
    main()
