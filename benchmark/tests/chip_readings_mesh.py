#!/usr/bin/env python3
"""``chip_readings.py`` for the four-chip cell: for each seed, one run of
the cell through the harness, the numbers the program was compared on, and
beside them the CONTROL's — the blocked reference put in the program's
place one precision step down (int4 shortlist, float8 rescore; with
``--emulate`` also the step the configuration already takes, int8 + bf16),
at the cell's own size (12,047,500 items), on the same sampled queries.
The limits of ``amazon23-r256-host4of16``'s ``correct`` are set from these
(PERF.md section 2).  One process for all seeds; the benchmark's own runs
never run this.

    chiprun --chips 4 -- python3 benchmark/tests/chip_readings_mesh.py \\
        --workload amazon23-r256-host4of16.serve-steady-mesh --seeds 1,2 --seconds 3

``--control-only`` leaves the program out: the seed's factors (drawn on the
host, as the runner draws them), ``check_requests`` queries of distinct
clients from the mix, and the control's numbers alone.  Numpy on the host
and nothing else, so it needs no chip and may run anywhere the catalog
fits.
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


def control_numbers(Q, V, cfg, emulate=False):
    """The four checks' numbers for the reference one precision step down
    (and, with ``emulate``, at the step the configuration already takes)
    in the program's place."""
    from benchmark.reference import topk_blocked as ref
    from benchmark.runners import serve_mesh

    k = cfg["serving"]["k"]
    exact = ref.exact_topk(Q, V, k)
    steps = [("int4+float8_e4m3fn", 4, "float8_e4m3fn")]
    if emulate:
        steps.append(("int8+bfloat16", 8, "bfloat16"))
    out = {}
    for name, bits, dtype in steps:
        s, i = ref.lower_precision_topk(
            Q, V, k, shortlist_k=64, shortlist_bits=bits, rescore_dtype=dtype)
        out["control_" + name] = {
            c.name: c.value for c in serve_mesh.compare_answers(
                s, i, Q, V, k, cfg["correct"], exact=exact)}
    return out


def control(outcome, cell, emulate=False):
    """The control on the queries the run itself was compared on."""
    from benchmark.runners import serve

    a = outcome.artifacts
    loop = a["loop"]
    _, Q = serve.sampled_queries(loop, a["U"], cell.traffic, cell.seed)
    return {"window": {"slowest": loop.slowest(3),
                       "batch_sizes": loop.batch_sizes()},
            **control_numbers(Q, a["V"], cell.config, emulate)}


def control_only(workload, seed, emulate):
    """The control's readings for one seed, without the program: queries
    of ``check_requests`` distinct clients drawn from the mix."""
    import numpy as np

    from benchmark import datagen, harness
    from benchmark.runners import serve, serve_mesh

    _, _, cfg, mix, _ = harness.cell_files(ROOT, workload)
    U, V = serve_mesh.host_factors(cfg["num_users"], cfg["num_items"],
                                   cfg["als"]["rank"], seed)
    payloads = serve.make_requests(datagen.rng_for(seed, 3), U, mix,
                                   8 * mix["check_requests"])
    queries = {}
    for j, p in enumerate(payloads):
        queries.setdefault(p if isinstance(p, int) else -1 - j,
                           U[p] if isinstance(p, int) else p)
    Q = np.stack(list(queries.values())[:mix["check_requests"]])
    return control_numbers(Q, V, cfg, emulate)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--emulate", action="store_true")
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args()
    if args.control_only:
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps({"READINGS": args.workload, "seed": seed,
                              "control": control_only(args.workload, seed,
                                                      args.emulate)}),
                  flush=True)
        return

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        _, _, runner, cell = harness.open_cell(
            ROOT, args.workload, seed, args.seconds, False)
        outcome = runner.run(cell)
        print(json.dumps({"READINGS": args.workload, "seed": seed,
                          "correct": all(c.holds for c in outcome.checks),
                          "metrics": outcome.metrics,
                          "memory_peak_bytes": harness.memory_peak_bytes(),
                          "program": {c.name: c.value
                                      for c in outcome.checks},
                          "control": control(outcome, cell, args.emulate)}),
              flush=True)
        # the engine's tables leave the chips before the next seed's come
        del outcome, runner, cell
        gc.collect()


if __name__ == "__main__":
    main()
