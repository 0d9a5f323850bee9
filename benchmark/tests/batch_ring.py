"""One run of ``benchmark/run.py`` as it is, and afterwards what the serving
engine's per-batch flight records say of it: by bucket, how many batches,
their cycle, and the median, mean and longest of every phase of the engine
thread's cycle (``ServingEngine.batch_flight``; milliseconds).  For runs
that no profiler traces — a slow-mode window beside a fast-mode one, a
window with a stall in it (PERF.md section 7):

    chiprun -- python3 benchmark/tests/batch_ring.py --workload \\
        amazon23-r256-share32.serve-steady --seed <n> --seconds 30 --trace 0

The engine is built with rings large enough to hold the whole run; what it
does per batch is the same.  A ``--trace 1`` run's traced stream follows the
window's after a pause of a quarter of a second, so it is reported apart.
"""

from __future__ import annotations

import json
import os
import runpy
import statistics as st
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RING = 1 << 14


def streams(records, pause_s=0.2):
    """The records cut where the engine thread sat idle for ``pause_s``."""
    out = [[]]
    for r in records:
        if out[-1] and r["spans"]["serve.idle"] > pause_s:
            out.append([])
        out[-1].append(r)
    return out


def summary(records, seconds):
    """One row per bucket over the last ``seconds`` of ``records``."""
    window = [r for r in records if r["t0"] > records[-1]["t0"] - seconds]
    rows = []
    for bucket in sorted({r["bucket"] for r in window}):
        own = [r for r in window if r["bucket"] == bucket]
        cycle = [1e3 * (b["t0"] - a["t0"]) for a, b in zip(window, window[1:])
                 if a["bucket"] == b["bucket"] == bucket]
        row = {"bucket": bucket, "batches": len(own),
               "rows_mean": st.mean(r["rows"] for r in own),
               "waiting_mean": st.mean(r["waiting"] for r in own),
               "cycle_ms": [st.median(cycle), st.mean(cycle),
                            max(cycle)] if cycle else None}
        for name in own[0]["spans"]:
            ms = [1e3 * r["spans"][name] for r in own]
            row[name] = [st.median(ms), st.mean(ms), max(ms)]
        rows.append(row)
    return rows


def main(argv):
    sys.path.insert(0, ROOT)
    import tpu_als.serving.engine as engine_module

    engines = []
    init = engine_module.ServingEngine.__init__

    def init_with_large_rings(self, *args, **kwargs):
        kwargs.setdefault("flight_capacity", RING)
        init(self, *args, **kwargs)
        engines.append(self)

    engine_module.ServingEngine.__init__ = init_with_large_rings
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + argv
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        if e.code:
            return e.code
    seconds = float(argv[argv.index("--seconds") + 1])
    records = list(engines[0].batch_flight._ring)
    for k, stream in enumerate(s for s in streams(records) if len(s) > 8):
        for row in summary(stream, seconds):
            print(json.dumps({"batch_ring": k, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
