#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md section 2):
for each seed, one short run of a cell through the harness, the numbers the
program was compared on, and beside them the CONTROL's — the reference put
in the program's place one precision step down (and at bfloat16, the step
the "f32" configuration already takes), at the cell's own size, on the same
inputs.  One process for all seeds, so probes and compiles are paid
once.  The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings.py --workload <name> --seeds 1,2,3 --seconds 3
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


def train_control(outcome, cell):
    from benchmark.runners import train

    a = outcome.artifacts
    out = {"program": _summary(a["found"])}
    for dtype in ("bfloat16", "float8_e4m3fn"):
        out["control_" + dtype] = _summary(train.reference_residuals(
            a["data"], cell.config, a["U"], a["V"], a["U_prev"],
            seed=cell.seed, n_rows=cell.traffic["check_rows"],
            operand_dtype=dtype))
    return out


def _summary(found):
    return {f"{side}_{what}_{stat}": float(fn(values))
            for side, f in found.items() for what, values in f.items()
            for stat, fn in (("median", np.median), ("max", np.max))}


def serve_control(outcome, cell, emulate=False):
    from benchmark.reference import topk
    from benchmark.runners import serve

    a = outcome.artifacts
    loop, U, V = a["loop"], a["U"], a["V"]
    k = cell.config["serving"]["k"]
    _, Q = serve.sampled_queries(loop, U, cell.traffic, cell.seed)
    out = {"window": {"gc": loop.gc_clock.summary(),
                      "slowest": loop.slowest(3),
                      "batch_sizes": loop.batch_sizes()}}
    exact = topk.exact_topk(Q, V, k)
    # the control one step below the program; with --emulate also the step
    # the program already takes (int8 shortlist, bf16 operands)
    steps = [("int4+float8_e4m3fn", 4, "float8_e4m3fn")]
    if emulate:
        steps.append(("int8+bfloat16", 8, "bfloat16"))
    for name, bits, dtype in steps:
        s, i = topk.lower_precision_topk(
            Q, V, k, shortlist_k=64, shortlist_bits=bits, rescore_dtype=dtype)
        out["control_" + name] = {
            c.name: c.value for c in serve.compare_answers(
                s, i, Q, V, k, cell.config["correct"], exact=exact)}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--emulate", action="store_true")
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        _, _, runner, cell = harness.open_cell(
            ROOT, args.workload, seed, args.seconds, False)
        outcome = runner.run(cell)
        control = (train_control(outcome, cell)
                   if cell.traffic["kind"] == "train"
                   else serve_control(outcome, cell, args.emulate))
        print(json.dumps({"READINGS": args.workload, "seed": seed,
                          "correct": all(c.holds for c in outcome.checks),
                          "metrics": outcome.metrics,
                          "memory_peak_bytes": harness.memory_peak_bytes(),
                          "program": {c.name: c.value
                                      for c in outcome.checks},
                          "control": control}), flush=True)
        # the engine's tables leave the device before the next seed's come
        del outcome, control, runner, cell
        gc.collect()


if __name__ == "__main__":
    main()
