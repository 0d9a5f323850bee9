#!/usr/bin/env python3
"""``serve_sweep.py`` pointed at the four-chip unseen cell: the same ladder
of offered rates, one window each, against ``runners/serve_mesh_unseen.py``'s
engine (a mesh of the cell's chips, the histories published and sharded with
the user table, the factors planted from them) and ``runners/
serve_unseen.py``'s requests (users asking in proportion to their histories,
by id or by vector with their own list).  Run by hand on the four chips; the
cell's rate holds if it is at most 0.8 x the highest rate that shed nothing
in a 30 s window (PERF.md section 4 has the table).

    chiprun --chips 4 -- python3 benchmark/tests/serve_sweep_mesh_unseen.py \\
        --workload amazon23-r256-host4of16-unseen.serve-unseen-mesh \\
        --rates 2000,2400,2800 --seconds 30
"""

from __future__ import annotations

import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    from benchmark import harness
    from benchmark.runners import serve, serve_mesh_unseen, serve_unseen

    workload = sys.argv[sys.argv.index("--workload") + 1]
    _, w, _, _, _ = harness.cell_files(ROOT, workload)
    found = {}

    def start_engine(config, mix, seed):
        cell = types.SimpleNamespace(
            config=config, traffic=mix, seed=seed, chips=w["chips"],
            say=lambda what, **fields: print(json.dumps(
                {"what": what, **fields}, default=str), flush=True))
        asker, U, V, found["hist"], phases = serve_mesh_unseen.start_engine(
            cell)
        cell.say("setup", **phases)
        return asker, U, V, phases

    def open_stream(asker, U, mix, rng, seconds, k, clock=None):
        loop, marks, _ = serve_unseen.open_stream(
            asker, U, found["hist"], mix, rng, seconds, k, clock=clock)
        return loop, marks

    serve.start_engine, serve.open_stream = start_engine, open_stream
    sweep = harness.load_module(os.path.join(HERE, "serve_sweep.py"),
                                "bench_serve_sweep")
    sweep.main()


if __name__ == "__main__":
    main()
