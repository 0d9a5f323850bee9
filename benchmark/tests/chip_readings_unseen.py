#!/usr/bin/env python3
"""Readings behind the unseen cell's ``correct`` (PERF.md section 2): for
each seed one run of the cell through the harness and the numbers the
program was compared on; beside them, on the same sampled queries and the
same excluded ids, the CONTROL one precision step down
(``reference/topk_unseen.lower_precision_topk``: int4 shortlist, float8
rescore; with ``--emulate`` also the step the program already takes, int8 +
bfloat16); and with ``--rule-off`` a second run of the same seed with
nothing published or sent to exclude (the parent's semantics), which must
read ``correct: false`` on guarantees (1) and (2), and whose share of by-id
requests that were served a rated item, with its recall against the masked
reference, goes into the configuration file's ``measured``.  One process
for all seeds.  The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_unseen.py --seeds 1,2 --seconds 10 --rule-off
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

CELL = "amazon23-r256-share32-unseen.serve-unseen"


def lower_precision(runner, a, cell, emulate):
    from benchmark.reference import topk_unseen as ref

    import numpy as np

    k, lim = cell.config["serving"]["k"], cell.config["correct"]
    n, V = len(a["Q"]), a["V"]
    Q = np.concatenate([a["Q"], a["longest_Q"]])
    excluded = a["excluded"] + a["longest_excluded"]
    exact = ref.exact_topk(Q, V, k, excluded)
    steps = [("int4+float8_e4m3fn", 4, "float8_e4m3fn")]
    if emulate:
        steps.append(("int8+bfloat16", 8, "bfloat16"))
    out = {}
    for name, bits, dtype in steps:
        s, i = ref.lower_precision_topk(
            Q, V, k, excluded, shortlist_k=64, shortlist_bits=bits,
            rescore_dtype=dtype)
        # the sampled clients, then the longest histories
        out[name] = {c.name: c.value for part, rows in (
            ("", slice(0, n)), ("_longest", slice(n, None)))
            for c in runner.compare(
                part, s[rows], i[rows], Q[rows], V, excluded[rows], k,
                dict(lim, recall_at_k=lim["recall_at_k" + part]),
                (exact[0][rows], exact[1][rows]))}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--emulate", action="store_true")
    ap.add_argument("--rule-off", action="store_true")
    args = ap.parse_args()

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for rule in (True, False) if args.rule_off else (True,):
            _, _, runner, cell = harness.open_cell(
                ROOT, args.workload, seed, args.seconds, False)
            cell.traffic = dict(cell.traffic, rule=rule)
            outcome = runner.run(cell)
            a = outcome.artifacts
            print(json.dumps({
                "READINGS": args.workload, "seed": seed, "rule": rule,
                "correct": all(c.holds for c in outcome.checks),
                "failed_checks": [c.name for c in outcome.checks
                                  if not c.holds],
                "metrics": outcome.metrics,
                "memory_peak_bytes": harness.memory_peak_bytes(),
                "by_id_with_seen_share": a.get("by_id_with_seen_share"),
                "program": {c.name: c.value for c in outcome.checks},
                "control": (lower_precision(runner, a, cell, args.emulate)
                            if rule else None)}), flush=True)
            # the tables leave the device before the next run's come
            del outcome, a, runner, cell
            gc.collect()


if __name__ == "__main__":
    main()
