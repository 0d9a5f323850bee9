"""One ``live.batch`` by phase, and every serving batch's life read against
the updater phase it overlapped.

Beside ``live_spans.py`` and ``pipeline_spans.py`` (imported, not changed).
Since ISSUE 54 the updater thread tiles its ``live.batch`` span with child
spans, nested where the work is (``live.batch.prepare``; inside
``live.batch.foldin`` — and, where an updater folds items too, inside its
``.foldin.users`` / ``.foldin.items`` — ``.group``, ``.history``, ``.map``,
``.pack``, ``.readback`` around ``.call``, ``.write_back``; inside
``live.batch.publish`` ``.users``, ``.history``, ``.catalog`` around a
``.ride`` and at times a ``.compact``, ``.ride``, ``.lock_wait``,
``.writes``, ``.after``; ``live.batch.record``), the stamped ones with the
thread's own CPU time (``cpu_us``) beside the wall time of the same interval
(``wall_us``); and ``serve.batch.stage`` carries ``lock_wait_us``, its wait
for the lock a publish writes under.  The names are data here, as the
``serve.`` spans are to ``program_spans.py``; nothing of the program is
imported.  A trace of a program that writes no such span (an older commit,
a cell without an updater) yields ``None`` from every reader.

Every ``live.`` span is written by ONE thread, so the spans nest and the
tree is read from the intervals alone.  **A phase's time is its OWN**: what
the program timed inside its span (``wall_us``; an unstamped span's
duration) less what its children cover.  The spans that only hold others
(``CONTAINERS``) hold UNSPLIT time, kept under ``<container>/own``: the
Python between two phases, which no span names; what a stamped span covers beyond its
``wall_us`` — its two readings of the CPU clock, which only a traced run
makes — goes to ``stamps``: the instrument's own cost, kept out of every
phase.  Phases, the containers' own and ``stamps`` add up to the batch
exactly.

**The join.**  A serving batch lives from its stage's start to its
complete's end (``pipeline_spans.batches``: the spans of one ``seq`` on two
threads).  It is classed by the phase whose own time covers most of that
interval — ``none`` where most of it lies outside every ``live.batch`` (the
updater idles or coalesces) — and the lives of the most-ridden bucket are
then summarised by class.

``pipeline_spans.read`` turns the file into plain tuples (read once a
process, for both modules); everything here is arithmetic on them.
"""

from __future__ import annotations

import bisect
import collections
import functools
import os
import statistics

import numpy as np

from benchmark import pipeline_spans
from benchmark import trace as tr

BATCH = "live.batch"
FOLDIN, PUBLISH = "live.batch.foldin", "live.batch.publish"
PREPARE, RECORD = "live.batch.prepare", "live.batch.record"
CALL = "live.batch.foldin.call"
LOCK_WAIT = "live.batch.publish.lock_wait"
WRITES = "live.batch.publish.writes"
# the fold's host work: everything of a fold but the program's call and
# the blocking read of its rows
FOLD_HOST = tuple(FOLDIN + leaf for leaf in (
    ".group", ".history", ".map", ".pack", ".write_back"))
# spans that only hold other spans: their own time is nobody's
CONTAINERS = (BATCH, FOLDIN, FOLDIN + ".users", FOLDIN + ".items", PUBLISH)
# a container's own time goes under its name with this behind it
OWN = "/own"
STAMPS, NONE = "stamps", "none"
STAGE = pipeline_spans.STAGE


def tree(spans):
    """``[(name, start_ns, end_ns, stats, parent index or None)]`` of one
    thread's spans ``[(name, start_ns, dur_ns, stats)]``, by start, a
    span before what it holds: the parent is the innermost span that was
    open when it started."""
    out, stack = [], []
    for name, start, dur, stats in sorted(spans,
                                          key=lambda s: (s[1], -s[2])):
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        out.append((name, start, start + dur, stats,
                    stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def own(nodes):
    """``([own wall ns], [stamp ns], [own CPU us or None], [(index,
    start_ns, end_ns)])`` of :func:`tree`'s nodes.  A stamped span's time
    is its ``wall_us``, the interval the program itself timed INSIDE the
    span, and what the span covers beyond it is the STAMP: the two
    readings of the CPU clock (7-30 us each on the chip's host) and the
    annotation's own edges — the instrument, not the phase.  Own wall:
    that time (an unstamped span's: its duration) less the children's
    durations; own CPU: ``cpu_us`` less that of the nearest stamped spans
    inside (``None`` without the stat).  Own walls and stamps add up to
    the roots' durations.  The segments cut the timeline, disjoint and by
    start, each under the INNERMOST span that covers it."""
    stamp = [end - start - 1000 * stats["wall_us"] if "wall_us" in stats
             else 0 for _, start, end, stats, _ in nodes]
    wall = [end - start - stamp[i]
            for i, (_, start, end, _, _) in enumerate(nodes)]
    cpu = [stats.get("cpu_us") for _, _, _, stats, _ in nodes]
    kids = collections.defaultdict(list)
    for i, (_, start, end, stats, parent) in enumerate(nodes):
        if parent is None:
            continue
        kids[parent].append(i)
        wall[parent] -= end - start
        if "cpu_us" in stats:
            up = parent
            while up is not None and cpu[up] is None:
                up = nodes[up][4]
            if up is not None:
                cpu[up] -= stats["cpu_us"]
    segments = []
    for i, (_, start, end, _, _) in enumerate(nodes):
        at = start
        for k in kids[i]:
            if nodes[k][1] > at:
                segments.append((i, at, nodes[k][1]))
            at = max(at, nodes[k][2])
        if end > at:
            segments.append((i, at, end))
    return wall, stamp, cpu, sorted(segments, key=lambda s: s[1])


def label(name):
    """The class a span's own time goes under: a container's is unsplit
    time, named after it (``live.batch.publish/own``)."""
    return name + OWN if name in CONTAINERS else name


def unsplit(by_label):
    """Summed over the containers' own: ``{label: number}`` -> number."""
    return sum(v for name, v in by_label.items() if name.endswith(OWN))


def phases(live):
    """The updater's batches by phase, from its ``live.`` spans, or
    ``None`` where they hold no ``live.batch`` or none of its phases (an
    older commit):

    ``batches``     ``live.batch`` spans
    ``batch_ns``    their summed duration
    ``wall_ns``     ``{label: [ns a batch]}``: own time by phase and batch
                    (0 for a batch without the phase), a container's
                    under ``<its name>/own``, ``stamps`` what the stamped spans
                    cover beyond the interval they timed (:func:`own`);
                    a batch's add up to its span
    ``cpu_us``      ``{label: us}``: own CPU time by phase, summed over the
                    batches (the stamped phases only)
    ``children_cpu_us``  summed ``cpu_us`` of the spans directly under a
                    ``live.batch``: the batch's CPU time less what lies
                    between them and what their own stamps burn
    ``segments``    ``[(label or none, start_ns, end_ns)]``: the thread's
                    timeline, disjoint and by start, ``none`` outside a
                    batch
    """
    nodes = tree(live)
    roots = [i for i, n in enumerate(nodes) if n[0] == BATCH]
    if not roots or not any(n[0] == PREPARE for n in nodes):
        return None
    wall, stamp, cpu, cut = own(nodes)
    root = list(range(len(nodes)))
    for i, n in enumerate(nodes):
        if n[4] is not None:
            root[i] = root[n[4]]
    at = {r: k for k, r in enumerate(roots)}
    wall_ns = collections.defaultdict(lambda: [0] * len(roots))
    cpu_us = collections.Counter()
    for i, n in enumerate(nodes):
        if root[i] not in at:
            continue
        wall_ns[label(n[0])][at[root[i]]] += wall[i]
        wall_ns[STAMPS][at[root[i]]] += stamp[i]
        if cpu[i] is not None:
            cpu_us[label(n[0])] += cpu[i]
    return {"batches": len(roots),
            "batch_ns": sum(nodes[r][2] - nodes[r][1] for r in roots),
            "wall_ns": dict(wall_ns), "cpu_us": dict(cpu_us),
            "children_cpu_us": sum(n[3].get("cpu_us", 0) for n in nodes
                                   if n[4] in at),
            "segments": [(label(nodes[i][0]) if root[i] in at else NONE,
                          s, e) for i, s, e in cut]}


def classed(interval, segments, starts):
    """The label whose segments cover most of ``interval = (t0, t1)``;
    ``none`` where most of it lies under no batch.  ``starts``: the
    segments' start times (they are disjoint and sorted)."""
    t0, t1 = interval
    cover = collections.Counter()
    k = max(bisect.bisect_right(starts, t0) - 1, 0)
    while k < len(segments) and segments[k][1] < t1:
        name, s, e = segments[k]
        cover[name] += max(0, min(e, t1) - max(s, t0))
        k += 1
    cover[NONE] += (t1 - t0) - sum(cover.values())
    return max(cover, key=lambda name: (cover[name], name == NONE))


def lives(serve, segments):
    """``{label: [life ns]}`` of the serving batches of the most-ridden
    bucket (``serve``: the ``serve.`` spans), each classed by
    :func:`classed` over its life, stage start to complete end; ``{}``
    where the spans hold no whole batch."""
    whole, _ = pipeline_spans.batches(serve)
    if not whole:
        return {}
    (bucket, _), = collections.Counter(
        b.bucket for b in whole.values()).most_common(1)
    starts = [s for _, s, _ in segments]
    out = collections.defaultdict(list)
    for b in whole.values():
        if b.bucket == bucket:
            out[classed((b.T0, b.T5), segments, starts)].append(b.T5 - b.T0)
    return dict(out)


def summary(serve, live):
    """The per-layer metrics' values (ms, or as named) and the two
    tables, from the ``serve.`` and ``live.`` spans of one trace;
    ``None`` where :func:`phases` finds nothing.

    ``phase_table``  ``{label: (median wall ms a batch, own CPU ms a
                     batch)}``, over ``phase_table_batches`` batches
    ``life_table``   ``{label: (batches, median life ms, 90th percentile
                     life ms)}`` of the ``life_table_batches`` serving
                     batches by class
    """
    found = phases(live)
    if found is None:
        return None
    n, wall = found["batches"], found["wall_ns"]

    def median_ms(*labels):
        return 1e-6 * statistics.median(
            sum(wall.get(name, [0] * n)[k] for name in labels)
            for k in range(n))

    by_class = lives(serve, found["segments"])
    beside = [ns for name, ls in by_class.items() if name != NONE
              for ns in ls]
    alone = by_class.get(NONE, [])
    waits = [s[3]["lock_wait_us"] for s in serve
             if s[0] == STAGE and "lock_wait_us" in s[3]]
    return {
        "live_prepare_ms": median_ms(PREPARE),
        "live_fold_host_ms": median_ms(*FOLD_HOST),
        "live_fold_call_ms": median_ms(CALL),
        "live_publish_lock_wait_ms": median_ms(LOCK_WAIT),
        "live_publish_locked_ms": median_ms(WRITES),
        "live_record_ms": median_ms(RECORD),
        "live_host_cpu_ms": 1e-3 * found["children_cpu_us"] / n,
        "live_batch_unsplit_pct": (
            100.0 * unsplit({name: sum(ns) for name, ns in wall.items()})
            / found["batch_ns"]),
        "serve_life_beside_live_ms": (
            1e-6 * (statistics.median(beside) - statistics.median(alone))
            if beside and alone else None),
        "serve_stage_lock_wait_p99_ms": (
            1e-3 * float(np.percentile(waits, 99)) if waits else None),
        "phase_table_batches": n,
        "life_table_batches": sum(map(len, by_class.values())),
        "phase_table": {
            name: (median_ms(name),
                   1e-3 * found["cpu_us"].get(name, 0) / n)
            for name in sorted(wall)},
        "life_table": {
            name: (len(ls), 1e-6 * statistics.median(ls),
                   1e-6 * float(np.percentile(ls, 90)))
            for name, ls in sorted(by_class.items())}}


@functools.lru_cache(maxsize=2)
def _summary_of(path, mtime_ns):
    """:func:`summary` of what ``pipeline_spans`` read of the file (once
    a process: the parse is shared); ``None`` as its readers say it, for a
    file with no device plane."""
    found = pipeline_spans._read_of(path, mtime_ns)
    return None if found is None else summary(found[0], found[1])


def traced(ctx):
    """:func:`summary` of the trace this run recorded (read once per
    process), or ``None`` for a run without one."""
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    return _summary_of(path, os.stat(path).st_mtime_ns)


def metric(ctx, name, table=None):
    """The value of one per-layer metric, or ``None``; with ``table``
    (``phase_table`` | ``life_table``) that table is printed beside it,
    a line of the run's own (``what`` = the table's name, ``batches`` =
    how many it is over)."""
    found = traced(ctx)
    if found is None:
        return None
    if table is not None:
        ctx.cell.say(table, batches=found[table + "_batches"], **{
            phase: [round(v, 4) for v in row]
            for phase, row in found[table].items()})
    return found[name]
