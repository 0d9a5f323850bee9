#!/usr/bin/env python3
"""``serve_sweep.py`` pointed at the mesh engine: the same ladder of offered
rates, one window each, against ``runners/serve_mesh.py``'s engine (a mesh of
the cell's chips) and not ``runners/serve.py``'s.  Run by hand on the four
chips; the cell's rate holds if it is at most 0.8 x the highest rate that
shed nothing in a 30 s window (PERF.md section 4 has the table).

    chiprun --chips 4 -- python3 benchmark/tests/serve_sweep_mesh.py --workload \\
        amazon23-r256-host4of16.serve-steady-mesh --rates 2000,3000 --seconds 30
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
    from benchmark.runners import serve, serve_mesh

    workload = sys.argv[sys.argv.index("--workload") + 1]
    _, w, _, _, _ = harness.cell_files(ROOT, workload)

    def start_engine(config, mix, seed):
        cell = types.SimpleNamespace(
            config=config, traffic=mix, seed=seed, chips=w["chips"],
            say=lambda what, **fields: print(json.dumps(
                {"what": what, **fields}, default=str), flush=True))
        return serve_mesh.start_engine(cell)

    serve.start_engine = start_engine
    sweep = harness.load_module(os.path.join(HERE, "serve_sweep.py"),
                                "bench_serve_sweep")
    sweep.main()


if __name__ == "__main__":
    main()
