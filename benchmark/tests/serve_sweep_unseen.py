#!/usr/bin/env python3
"""``serve_sweep.py`` pointed at the unseen cell: the same ladder of offered
rates, one window each, against ``runners/serve_unseen.py``'s engine (the
histories published, the factors planted from them) and its requests (users
asking in proportion to their histories, by id or by vector with their own
list).  Run by hand on the chip, ``serve-steady``'s sweep beside it (PERF.md
section 6 has the table).

    chiprun -- python3 benchmark/tests/serve_sweep_unseen.py --workload \\
        amazon23-r256-share32-unseen.serve-unseen --rates 1600,4000,8000 --seconds 30
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    from benchmark import harness
    from benchmark.runners import serve, serve_unseen

    found = {}

    def start_engine(config, mix, seed):
        asker, U, V, hist, phases = serve_unseen.start_engine(config, mix,
                                                              seed)
        found["hist"] = hist
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
