#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  Looks at the device first (anything but a
TPU with the cell's chips exits 1 before any other work), sets up from the
seed, warms up, measures for ``--seconds``, checks the outputs outside the
window and prints the result line last.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    # the plan cache's default directory is under ~, which no run outlives
    os.environ["TPU_ALS_PLAN_CACHE"] = "off"
    from benchmark import harness

    try:
        harness.find_cell(ROOT, args.workload)
        import tpu_als  # noqa: F401  (a checkout without the program fails here)
        from tpu_als.utils.platform import enable_persistent_compile_cache

        enable_persistent_compile_cache()
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_process=T_PROCESS)
    except (harness.BenchmarkError, ImportError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
