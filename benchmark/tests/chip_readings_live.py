#!/usr/bin/env python3
"""Readings that the limits of the live cell's read-your-writes comparison
are set from (PERF.md section 2): for each seed, one run of the cell through
the harness, the numbers the program was compared on, and beside them the
CONTROL's — the reference fold in the program's place with its operands
rounded one precision step down (float8; and bfloat16, the step the
"float32" fold already takes on the chip), served exactly, for the same
users and events.  One process for all seeds, so compiles are paid once.
The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live.py --seeds 1,2,3 --seconds 10
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

CELL = "amazon23-r256-share32-live.serve-foldin"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        _, _, runner, cell = harness.open_cell(
            ROOT, args.workload, seed, args.seconds, False)
        outcome = runner.run(cell)
        a = outcome.artifacts
        control = {}
        for dtype in ("bfloat16", "float8_e4m3fn"):
            checks, _ = runner.read_your_writes(
                None, a["model"], a["by_user"], a["V"], cell.config,
                cell.traffic, cell.seed, operand_dtype=dtype,
                answers=a["read_your_writes"])
            control[dtype] = {c.name: c.value for c in checks}
        print(json.dumps({"READINGS": args.workload, "seed": seed,
                          "correct": all(c.holds for c in outcome.checks),
                          "metrics": outcome.metrics,
                          "memory_peak_bytes": harness.memory_peak_bytes(),
                          "program": {c.name: c.value
                                      for c in outcome.checks},
                          "control": control}), flush=True)
        # the tables leave the device before the next seed's come
        del outcome, a, runner, cell
        gc.collect()


if __name__ == "__main__":
    main()
