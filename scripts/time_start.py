#!/usr/bin/env python3
"""Why did this start take as long as it did: one benchmark cell started,
and the program's own record of the start printed.

Runs ``benchmark/run.py`` as it is for ``--cell`` (one run: set-up, the
window, the checks; its ``setup`` / ``engine_ready`` lines carry the
runner's OUTSIDE laps — ``publish_s``, ``warmup_s``, ``foldin_server_s``
... — of the same start) and then prints what the program recorded from
inside (``tpu_als/obs/phases.py``, ``tpu_als/obs/compiles.py``):

- ``phase``: one row a phase path and side (``start.publish/start.publish.
  users`` ...; a path met several times — ``start.pin``, ``start.
  first_run`` — summed, ``n`` says how often): wall and CPU seconds, GB
  handed to the device inside it, device GB in use as it last closed,
  programs that reached the backend's compile call inside it, of which
  the persistent cache answered (``hits``), and the seconds traced,
  lowered and in that call, and what the phase says of its own input
  (``side``; the two passes over the histories: ``ids``, the check also
  ``parts``); ``top_level_s`` / ``unsplit_pct`` as the
  benchmark's ``start_program_s`` / ``start_unsplit_pct`` read them;
- ``placed_gb``: ``device.placed_bytes`` by table; ``pins``:
  ``serving.pins`` by source;
- ``program``: the compile ledger's ``--top`` dearest program NAMES
  (calls of the backend's compiler, hits, misses, seconds traced /
  lowered / in the call, every shape of the name together);
  ``compile_call``: the dearest single calls (the ``jax_program`` events,
  each with the phase it fell in); ``programs``: the process's totals
  beside the benchmark's own clock (``benchmark/clocks.py``: ``lower_s``,
  ``compile_s``, ``compilations``) and the ``jax_program`` events' sum;
- ``window``: what the ledger counted between the window's opening and
  its end — ``listener_calls`` 0 says JAX called neither listener while
  the traffic ran.

The ledger is installed BEFORE the run here (a plain run installs it with
its first engine), so the benchmark's own programs — the seeded factors,
the planted histories — are in the table too; ``tpu_als`` and ``jax`` are
then imported before ``run.py`` starts its clock, and this run's
``setup_s`` is some 4 s short of a plain run's: take ``setup_s`` from
``benchmark/run.py``.  A COLD start is this script's first run in a
checkout whose ``.bench_cache/xla_cache`` is empty.  ``--profile DIR``
records a profile from here to the window's opening (the phases are
``TraceAnnotation``s: they lie above the transfers and first executions
they caused); leave ``--trace`` 0 with it.  No CPU mode (``run.py`` has
none):

    chiprun -- python3 scripts/time_start.py --cell \\
        amazon23-r256-share32-live-items-unseen.serve-foldin-all --seed 7
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = ("seconds", "cpu_seconds", "placed_bytes", "programs",
          "cache_hits", "trace_s", "lower_s", "compile_s")
# what a phase says of its own input (known before it opens)
LABELS = ("side", "ids", "parts")


def phase_table(events):
    """``[row]`` by path (and ``side``, where a phase carries one), in
    the order the paths first opened."""
    rows = {}
    for e in events:
        if e["type"] != "span" or not e["name"].startswith("start."):
            continue
        path = "/".join(p for p in e["path"].split("/")
                        if p.startswith("start."))
        row = rows.setdefault((path, e.get("side")), dict.fromkeys(FIELDS, 0)
                              | {"path": path, "n": 0, "t0": e["t0"]})
        row["n"] += 1
        row["t0"] = min(row["t0"], e["t0"])
        for f in FIELDS:
            row[f] += e[f]
        row["device_bytes_in_use"] = e["device_bytes_in_use"]
        row.update({k: e[k] for k in LABELS if k in e})
    return sorted(rows.values(), key=lambda row: row["t0"])


def say(what, **fields):
    print(json.dumps({"time_start": what, **fields}), flush=True)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--profile", metavar="DIR")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import clocks, start_phases
    from tpu_als import obs
    from tpu_als.obs import compiles

    ledger, marks, bench_clocks = compiles.install(), [], []

    class Clock(clocks.CompileClock):
        """The benchmark's clock, which the runner reads as the window
        opens and after it: the ledger is read at the same instants."""

        def __init__(self):
            super().__init__()
            bench_clocks.append(self)

        def now(self):
            if not marks and args.profile:
                import jax

                jax.profiler.stop_trace()
            marks.append(ledger.now())
            return super().now()

    clocks.CompileClock = Clock
    if args.profile:
        import jax

        jax.profiler.start_trace(args.profile)
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py"),
                "--workload", args.cell, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace)]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        if e.code:
            return e.code

    events = list(obs.default_registry()._events)
    table = phase_table(events)
    for row in table:
        say("phase", path=row["path"], n=row["n"],
            seconds=round(row["seconds"], 3),
            cpu_seconds=round(row["cpu_seconds"], 3),
            placed_gb=round(1e-9 * row["placed_bytes"], 3),
            device_gb_in_use=round(1e-9 * row["device_bytes_in_use"], 3),
            programs=row["programs"], hits=row["cache_hits"],
            trace_s=round(row["trace_s"], 3),
            lower_s=round(row["lower_s"], 3),
            compile_s=round(row["compile_s"], 3),
            **{k: row[k] for k in LABELS if k in row})
    say("start_phases", top_level_s=start_phases.seconds(start_phases.top),
        unsplit_pct=start_phases.unsplit_pct())
    say("placed_gb", **{labels["table"]: round(1e-9 * v, 4) for labels, v
                        in obs.counter_series("device.placed_bytes")})
    say("pins", **{labels["source"]: v for labels, v
                   in obs.counter_series("serving.pins")})
    programs = ledger.programs()

    def cost(name):
        rec = programs[name]
        return rec["trace_s"] + rec["lower_s"] + rec["compile_s"]

    for name in sorted(programs, key=cost, reverse=True)[:args.top]:
        say("program", fun_name=name, seconds=round(cost(name), 3),
            **{k: round(v, 3) for k, v in programs[name].items()})
    compiled = [e for e in events if e["type"] == "jax_program"]
    for e in sorted(compiled, key=lambda e: -(
            e["trace_s"] + e["lower_s"] + e["compile_s"]))[:args.top]:
        say("compile_call", **{k: round(v, 3) if isinstance(v, float)
                               else v for k, v in e.items()
                               if k not in ("ts", "type")})
    say("programs", names=len(programs), ledger=ledger.now(),
        benchmark_clock=(dict(bench_clocks[0].totals,
                              compilations=bench_clocks[0].compilations)
                         if bench_clocks else None),
        jax_program_events=len(compiled),
        jax_program_s=sum(e["lower_s"] + e["compile_s"] for e in compiled),
        under_traffic=[e["fun_name"] for e in compiled
                       if e["phase"] == "traffic"])
    if len(marks) >= 2:
        say("window", **{k: marks[1][k] - marks[0][k] for k in marks[0]})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
