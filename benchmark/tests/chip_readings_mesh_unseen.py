#!/usr/bin/env python3
"""Readings behind the four-chip unseen cell's ``correct`` (PERF.md section
2), ``chip_readings_unseen.py`` for the mesh: for each seed one run of the
cell through the harness and the numbers the program was compared on;
beside them, on the same sampled queries and the same excluded ids, the
CONTROL one precision step down (``reference/topk_unseen.
lower_precision_topk``: int4 shortlist, float8 rescore) at the cell's own
size, 12,047,500 items; and with ``--rule-off`` a second run of the same
seed with nothing published or sent to exclude (the parent's semantics on
one chip; on a mesh the parent refuses), which must read ``correct: false``
on guarantees (1) and (2).  One process for all: the seed's histories and
factors are made once and shared by its runs.  The benchmark's own runs
never run this.

    chiprun --chips 4 -- python3 benchmark/tests/chip_readings_mesh_unseen.py \\
        --seeds 5252000001 --seconds 10 --rule-off

``--control-only`` leaves the program out: the seed's histories and item
factors (made on the host, as the runner makes them), the cell's
``check_requests`` distinct clients drawn as its requests are and the
``check_longest`` longest histories, each query's row planted from its own
history, and the control's numbers alone.  Numpy on the host and nothing
else, so it needs no chip and may run anywhere the catalog fits (14 GB).
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

CELL = "amazon23-r256-host4of16-unseen.serve-unseen-mesh"


def control_numbers(Q, V, excluded, n, cfg):
    """``serve_unseen.compare``'s numbers for the reference one precision
    step down in the program's place: the first ``n`` queries the sampled
    clients, the others the longest histories."""
    from benchmark.reference import topk_unseen as ref
    from benchmark.runners import serve_unseen

    k, lim = cfg["serving"]["k"], cfg["correct"]
    exact = ref.exact_topk(Q, V, k, excluded)
    s, i = ref.lower_precision_topk(
        Q, V, k, excluded, shortlist_k=64, shortlist_bits=4,
        rescore_dtype="float8_e4m3fn")
    return {"int4+float8_e4m3fn": {
        c.name: c.value for part, rows in (("", slice(0, n)),
                                           ("_longest", slice(n, None)))
        for c in serve_unseen.compare(
            part, s[rows], i[rows], Q[rows], V, excluded[rows], k,
            dict(lim, recall_at_k=lim["recall_at_k" + part]),
            (exact[0][rows], exact[1][rows]))}}


def control(outcome, cell):
    """The control on the queries the run itself was compared on."""
    import numpy as np

    a = outcome.artifacts
    return control_numbers(
        np.concatenate([a["Q"], a["longest_Q"]]), a["V"],
        a["excluded"] + a["longest_excluded"], len(a["Q"]), cell.config)


def control_only(workload, seed):
    import numpy as np

    from benchmark import datagen, harness, histories_by_shard
    from benchmark.runners import serve_mesh, serve_unseen

    _, _, cfg, mix, _ = harness.cell_files(ROOT, workload)
    indptr, indices, stars = histories_by_shard.seeded_histories(cfg, seed)
    _, V = serve_mesh.host_factors(1, cfg["num_items"], cfg["als"]["rank"],
                                   seed)
    hist = (indptr, indices)

    def planted(u):
        lo, hi = indptr[u], indptr[u + 1]
        return (stars[lo:hi, None] * V[indices[lo:hi]]).sum(
            0, dtype=np.float32)

    # the mix's requests want the whole user table for their vectors: a
    # request by vector is that user's row plus noise, made here
    rng = datagen.rng_for(seed, 3)
    n = 8 * mix["check_requests"]
    users = np.searchsorted(indptr, rng.integers(0, len(indices), n),
                            side="right") - 1
    by_vector = rng.random(n) < mix["vector_share"]
    noise = 0.01 * rng.standard_normal((n, V.shape[1]), dtype=np.float32)
    Q, excluded, seen = [], [], set()
    for j, u in enumerate(users):
        if int(u) in seen or len(Q) == mix["check_requests"]:
            continue
        seen.add(int(u))
        own = serve_unseen.excluded_of(int(u), u, hist)
        Q.append(planted(u) + (noise[j] if by_vector[j] else 0))
        excluded.append(own[:mix["exclude_ids"]] if by_vector[j] else own)
    longest = np.argsort(-np.diff(indptr),
                         kind="stable")[:mix["check_longest"]]
    Q += [planted(u) for u in longest]
    excluded += [serve_unseen.excluded_of(int(u), u, hist) for u in longest]
    return control_numbers(np.stack(Q), V, excluded,
                           len(Q) - len(longest), cfg)


def once_a_seed(fn):
    """``fn(config, seed)`` / ``fn(..., seed)``'s result kept for the
    process: the runs of one seed share its inputs."""
    kept = {}

    def call(*args, **kw):
        key = (fn.__name__, tuple(a for a in args if isinstance(a, int)),
               tuple(id(a) for a in args if hasattr(a, "shape")))
        if key not in kept:
            kept.clear()
            kept[key] = fn(*args, **kw)
        return kept[key]

    return call


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rule-off", action="store_true")
    ap.add_argument("--control-only", action="store_true")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.control_only:
        for seed in seeds:
            print(json.dumps({"READINGS": args.workload, "seed": seed,
                              "control": control_only(args.workload, seed)}),
                  flush=True)
        return

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness, histories_by_shard
    from benchmark.runners import serve_mesh

    enable_persistent_compile_cache()
    # (the harness loads the runner anew for every run, and the runner
    # takes these from their modules as it is loaded)
    histories_by_shard.seeded_histories = once_a_seed(
        histories_by_shard.seeded_histories)
    histories_by_shard.planted_user_factors = once_a_seed(
        histories_by_shard.planted_user_factors)
    serve_mesh.host_factors = once_a_seed(serve_mesh.host_factors)
    for seed in seeds:
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
                "control": (control(outcome, cell)
                            if rule and not args.no_control else None)}),
                flush=True)
            # the tables leave the chips before the next run's come
            del outcome, a, runner, cell
            gc.collect()


if __name__ == "__main__":
    main()
