#!/usr/bin/env python3
"""What closed the serving micro-batches of one benchmark run.

Runs ``benchmark/tests/batch_ring.py`` as it is (one run of
``benchmark/run.py``, then the batch ring's phases by bucket) and adds,
for each stream of the run, the shares of ``closed_by`` over the last
``--seconds`` of its per-batch records — ``slot``: the engine thread
held a free slot of its pipeline and popped what was queued at once
(every batch of a started engine below saturation); ``full``;
``closed``; ``age`` and ``wait``, the timed rule of callers that drive
``next_batch`` themselves, which a started engine never counts — with
the requests' wait in the queue (``queue_wait_ms``: median, mean,
longest over the request ring, the quantity the benchmark's
``serve_queue_ms`` takes the median of), the head's age
on arrival and the engine thread's wait for the user table's lock
(``lock_wait``: median, mean, longest, in ms — a window that stood still
with none of it did not stand behind a row write), how many of the
window's batches were dispatched while the batch before was still being
read back (``in_flight``: the count and share of each value, as
``serving.batch_overlap`` labels them), the engine thread's wait for one
of two batches in flight to complete (``handoff_wait``: batches with
any, then median, mean, longest in ms), the batch's own wait for the
completion thread (``completion_wait``: its whole life less its four
phases) and that thread's wait for a batch (``completion_idle``), the
two halves of dispatch (``upload``, ``launch``: median, mean, longest in
ms) with how the staged batch reached the device (``upload_how_pct``: the
share of ``call`` — it rode the scoring call as its host argument, the
transfer is inside ``launch`` — of ``put_one``, a mesh engine's since PR
44: one transfer to the mesh's first device, inside ``upload``, and the
program spreads it — and of ``put``, a separate ``device_put``, which no
path makes since PR 41) and, for a stream a profiler watched, each
phase's CPU share
(``cpu_pct``: the thread's own CPU time over the phase's wall time, summed
over the window — what is missing the thread spent without a processor, or
in the two clock calls the span holds), the latency of the requests that rode
the stream's batches (``e2e_ms``: median and 90th percentile of submit →
answer over the request ring: a traced stream's beside the window's is
what the profiler costs), the
process's ``serving.batch_closed`` and ``serving.batch_overlap``
counters against the batches the engine served, and what its ``publish_update``s did to
the device's user table (``serving.user_table_writes``: a live cell
counts ``inplace`` alone) and to the catalog (``serving.catalog_writes``:
``carried`` alone where no item is folded; where items are, ``delta`` and
``compact`` with the bytes a publish sent of each table, the compactions'
rows, the items appended and the ratings still waiting for a side's
factor), the ``serving_shortlist`` events (one a scoring program
``warmup()`` / ``warmup_live()`` compiled: the selection's stages, blocks
and ``blocks_layout``, what stage two asks of the compiler for that
bucket; since PR 43 ``blockmax``, how stage one reduces a block —
``lanes`` on the mesh cell's blocks of 256, ``block`` elsewhere — and
``tail``, the delta segment's slots that join at stage three: 512 in the
live-items cell, 0 elsewhere), since PR 51 where its pins came from
(``serving.pins`` by ``source`` and the ``serving_pin`` events, one a pin
with its seconds and its file's bytes: a start with a warm compile cache
counts ``loaded`` alone), and for an engine given a mesh its
``serving_mesh_plan`` events (one a bucket ``warmup()`` pinned; since PR
44 with ``placements``, the transfers a staged batch takes, and
``spread_bytes``, what the program's first all-reduce moves for it) with
the process's ``serving.mesh_exchange_bytes``: the mesh path read
without a profiler.  No CPU mode (``run.py`` has none):

    chiprun -- python3 scripts/serve_batch_closed.py --workload \\
        amazon23-r256-share32.serve-steady --seed <n> --seconds 30 --trace 0
"""

from __future__ import annotations

import collections
import importlib.util
import json
import os
import statistics as st
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAYS = ("slot", "full", "closed", "age", "wait")
PHASES = ("serve.batch.stage", "serve.batch.dispatch",
          "serve.batch.readback", "serve.batch.complete")


def three(values):
    """Median, mean and longest."""
    values = list(values)
    return [st.median(values), st.mean(values), max(values)]


def shares(records, seconds, requests=()):
    """``closed_by`` over the last ``seconds`` of one stream's records;
    ``requests``: the request ring's records, for the latency of those
    that rode the window's batches."""
    window = [r for r in records if r["t0"] > records[-1]["t0"] - seconds]
    by = collections.Counter(r["closed_by"] for r in window)
    row = {"batches": len(window),
           "rows_mean": st.mean(r["rows"] for r in window),
           "head_wait_ms_median": 1e3 * st.median(
               r["head_wait"] for r in window)}
    for name in ("lock_wait", "handoff_wait", "completion_idle"):
        row[name + "_ms"] = three(1e3 * r[name] for r in window)
    row["handoff_waits"] = sum(r["handoff_wait"] > 0 for r in window)
    row["completion_wait_ms"] = three(
        1e3 * (r["spans"]["serve.batch"] - sum(r["spans"][p] for p in PHASES))
        for r in window)
    for name in ("upload", "launch"):
        row[name + "_ms"] = three(1e3 * r[name] for r in window)
    hows = collections.Counter(r["upload_how"] for r in window)
    row["upload_how_pct"] = {how: 100.0 * n / len(window)
                             for how, n in sorted(hows.items())}
    # the CPU clock is read only while a profiler records: a traced stream
    watched = [r for r in window if r["cpu"]["stage"] is not None]
    if watched:
        row["cpu_pct"] = {
            phase: 100.0 * sum(r["cpu"][phase] for r in watched)
            / sum(r["spans"]["serve.batch." + phase] for r in watched)
            for phase in watched[0]["cpu"]}
    flying = collections.Counter(r["in_flight"] for r in window)
    for n in sorted(flying):
        row[f"in_flight_{n}"] = flying[n]
        row[f"in_flight_{n}_pct"] = 100.0 * flying[n] / len(window)
    for way in WAYS:
        row[way] = by[way]
        row[way + "_pct"] = 100.0 * by[way] / len(window)
    rode = range(window[0]["batch"], window[-1]["batch"] + 1)
    e2e = sorted(1e3 * r["e2e_seconds"] for r in requests
                 if r.get("batch") in rode and r["e2e_seconds"] is not None)
    if e2e:
        row["e2e_ms"] = {"requests": len(e2e), "p50": e2e[len(e2e) // 2],
                         "p90": e2e[(9 * len(e2e)) // 10]}
    return row


def main(argv):
    sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "batch_ring", os.path.join(ROOT, "benchmark", "tests",
                                   "batch_ring.py"))
    batch_ring = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(batch_ring)
    import tpu_als.serving.engine as engine_module
    from tpu_als import obs

    engines = []
    init = engine_module.ServingEngine.__init__

    def init_and_keep(self, *args, **kwargs):
        init(self, *args, **kwargs)
        engines.append(self)

    engine_module.ServingEngine.__init__ = init_and_keep
    code = batch_ring.main(argv)
    if code:
        return code
    seconds = float(argv[argv.index("--seconds") + 1])
    records = engines[0].batch_flight.records()
    requests = engines[0].flight.records()
    for k, stream in enumerate(s for s in batch_ring.streams(records)
                               if len(s) > 8):
        print(json.dumps({"batch_closed": k,
                          **shares(stream, seconds, requests)}), flush=True)
    waits = [1e3 * r["spans"]["queue_wait"] for r in requests
             if r["spans"].get("queue_wait") is not None]
    print(json.dumps({"queue_wait_ms": three(waits),
                      "requests": len(waits)}), flush=True)
    counted = {way: obs.counter_value("serving.batch_closed", by=way)
               for way in WAYS}
    print(json.dumps({"serving.batch_closed": counted,
                      "sum": sum(counted.values()),
                      "batches_served": engines[0]._batch_seq}), flush=True)
    print(json.dumps({"serving.batch_overlap": {
        str(n): obs.counter_value("serving.batch_overlap", in_flight=n)
        for n in (0, 1)}}), flush=True)
    writes = {how: obs.counter_value("serving.user_table_writes", how=how)
              for how in ("inplace", "replaced", "carried")}
    print(json.dumps({"serving.user_table_writes": writes,
                      "publishes": obs.counter_value("serving.publishes")}),
          flush=True)
    catalog = {how: obs.counter_value("serving.catalog_writes", how=how)
               for how in ("carried", "delta", "compact", "replaced")}
    events = obs.default_registry()._events
    print(json.dumps({
        "serving.catalog_writes": catalog,
        "live.publish_h2d_bytes": obs.counter_value(
            "live.publish_h2d_bytes"),
        "live.catalog_h2d_bytes": obs.counter_value(
            "live.catalog_h2d_bytes"),
        "live.items_appended": obs.counter_value("live.items_appended"),
        "compaction_rows": [e["rows"] for e in events
                            if e["type"] == "serving_compaction"],
        "live.events_waiting_last": ([
            e["value"] for e in events if e["type"] == "metric"
            and e["name"] == "live.events_waiting"] or [None])[-1]}),
        flush=True)
    def bare(kind):
        return [{k: v for k, v in e.items() if k not in ("ts", "type")}
                for e in events if e["type"] == kind]

    print(json.dumps({"serving_shortlist": bare("serving_shortlist")}),
          flush=True)
    print(json.dumps({
        "serving.pins": {source: obs.counter_value("serving.pins",
                                                   source=source)
                         for source in ("loaded", "compiled", "unreadable")},
        "serving_pin": bare("serving_pin")}), flush=True)
    plans = bare("serving_mesh_plan")
    if plans:
        print(json.dumps({"serving_mesh_plan": plans,
                          "serving.mesh_exchange_bytes": obs.counter_value(
                              "serving.mesh_exchange_bytes"),
                          "batches_served": engines[0]._batch_seq}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
