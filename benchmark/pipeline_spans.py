"""A batch's life on one timeline: the serving pipeline's spans joined by
their ``seq`` to the device's own run of that batch.

Beside ``program_spans.py`` (imported, not changed), which splits the
device's IDLE time by the engine thread's spans and assumes one thread
working through its batches in turn.  With two batches in flight a batch is
followed here instead: every span the engine writes for it carries its
``seq`` (``serve.batch.stage``, ``.dispatch`` and inside it
``.dispatch.upload`` / ``.dispatch.launch`` on the engine thread,
``.readback`` / ``.complete`` on the completion thread), the launch span
names the compiled program (``program``: ``jit__serve_int8_packed``, ...,
as the ``XLA Modules`` line of a device plane names each of its runs), and
``pipe.slot_wait`` is the engine thread's wait for one of its two slots.  The
names are data here; nothing of the program is imported.  A trace of a
program that writes no launch span (an older commit), or a file with no
device plane, yields ``None`` from every reader.

Per batch, on the host's clock (ns)::

    T0 stage start   TL launch start   T2 dispatch end
    T3 readback start   T4 readback end   T5 complete end

and on the device's, ``D0``/``D1``: its run of the batch's program, from the
earliest start to the latest end over the chips.  **The run is found by
order**: one engine thread launches the runs of a program and a device
executes them in launch order, so the k-th launch span of a program is the
k-th ``XLA Modules`` event of that name on each device.  The traced stream's
two ends may cut a launch from its run: up to ``ENDS`` unmatched at either
end are dropped, more yield ``None``.  **The pair is checked by the run's
identifier**: a module event carries a ``run_id``, and so does the host's
own event of that launch, ``DoEnqueueProgram`` (the runtime's, on the
calling thread or on one of its workers); where the file holds it, the
enqueue of the paired run must lie inside its batch's life, and its start is
then the instant the program cannot have started before.

**One clock, from the join itself.**  The profiler puts a device's clock
beside the host's only to within a millisecond or two.  The shift ``d`` to
add to the device's clock satisfies, for every batch, ``D0 + d >= TL`` (a
program cannot start before the call that launches it; ``>=`` the enqueue's
start where the file has it) and ``D1 + d <= T4`` (a readback cannot return
before the program ends), so ``d in [max(TL - D0), min(T4 - D1)]`` over the
pairs of every device (the chips of a host share the recording's clock).
The least is taken — the earliest the devices can have run, the convention
of ``program_spans.clock_shift_ns`` — and the interval's width reported
(``slack_ns``): how far ``launch_lag`` and ``ready_unread`` /
``readback_tail`` can be off.  No "no batch in flight" interval is needed,
so two batches in flight do not disturb it.

A batch's critical path, six segments that add up to ``T5 - T0`` exactly::

    prelaunch      TL - T0             stage + upload
    launch_lag     D0 - TL             the call, and the program waiting
                                       behind the batch before on the device
    device         D1 - D0
    ready_unread   max(0, T3 - D1)     the answer lay ready while the
                                       completion thread was busy
    readback_tail  T4 - max(D1, T3)    transfer + wake-up
    complete       T5 - T4

Two stages, as in ``trace.py``: :func:`read` turns the file into plain
tuples, the rest is arithmetic on them.
"""

from __future__ import annotations

import collections
import functools
import os
import statistics
from typing import NamedTuple

from benchmark import live_spans, program_spans
from benchmark import trace as tr

BATCH = "serve.batch"
STAGE, DISPATCH = "serve.batch.stage", "serve.batch.dispatch"
UPLOAD, LAUNCH = "serve.batch.dispatch.upload", "serve.batch.dispatch.launch"
READBACK, COMPLETE = "serve.batch.readback", "serve.batch.complete"
SLOT_WAIT = "pipe.slot_wait"
# the runtime's own host event of a launch: it carries the ``run_id`` that
# the device's ``XLA Modules`` event of that run carries
ENQUEUE = "DoEnqueueProgram"
LIVE_BATCH, LIVE_READBACK = "live.batch", "live.batch.foldin.readback"
SEGMENTS = ("prelaunch", "launch_lag", "device", "ready_unread",
            "readback_tail", "complete")
# launches without a run, or runs without a launch, that the traced
# stream's start and its end may each leave
ENDS = 2


class Batch(NamedTuple):
    """One batch's stamps on the host's clock, ns."""

    seq: int
    bucket: int
    program: str
    T0: int
    TL: int
    T2: int
    T3: int
    T4: int
    T5: int
    upload_ns: int
    launch_ns: int


def batches(spans):
    """``({seq: Batch}, {program: [(TL, seq)] by TL})`` of ``serve.`` spans
    ``[(name, start_ns, dur_ns, stats)]``: the batches that have all of
    their spans in the file, and every launch of each program, whole
    batch or not (a run on the device answers to each)."""
    by_seq, launches = {}, {}
    for name, start, dur, stats in spans:
        if "seq" in stats:
            by_seq.setdefault(stats["seq"], {})[name] = (start, start + dur,
                                                         stats)
    whole = {}
    for seq, own in by_seq.items():
        if LAUNCH not in own:
            continue
        tl, launched, stats = own[LAUNCH]
        launches.setdefault(stats["program"], []).append((tl, seq))
        if all(n in own for n in (BATCH, STAGE, DISPATCH, UPLOAD, READBACK,
                                  COMPLETE)):
            whole[seq] = Batch(
                seq, own[BATCH][2].get("bucket", 0), stats["program"],
                own[STAGE][0], tl, own[DISPATCH][1], own[READBACK][0],
                own[READBACK][1], own[COMPLETE][1],
                own[UPLOAD][1] - own[UPLOAD][0], launched - tl)
    return whole, {p: sorted(ls) for p, ls in launches.items()}


def shift_interval(pairs):
    """``(lo, hi, seq of the batch that sets lo, seq that sets hi)`` of
    ``[(seq, not before, not after or None, D0, D1)]``: the shifts of the
    device's clock under which no run starts before the host instant it
    cannot precede (``D0 + d >= not before``) and none ends after the one it
    cannot follow (``D1 + d <= not after``).  Empty (``hi < lo``) where
    the pairs cannot all be true."""
    lo, lo_seq = max((after - d0, seq) for seq, after, _, d0, _ in pairs)
    hi, hi_seq = min(((before - d1, seq) for seq, _, before, _, d1 in pairs
                      if before is not None), default=(lo, lo_seq))
    return lo, hi, lo_seq, hi_seq


def anchored(launches, runs, whole, enqueued=None):
    """``[(seq, not before, not after, D0, D1)]`` of launches
    ``[(TL, seq)]`` zipped with runs ``[(D0, D1, run_id)]``: a run cannot
    start before its launch span does, nor — where the host's own
    ``DoEnqueueProgram`` event of that ``run_id`` is in the file
    (``enqueued``: ``{run_id: start_ns}``) and lies inside the batch's
    life, as it must if the pair is right — before that; it cannot end
    after its batch's readback has returned (``None`` for a batch cut by
    the file's end)."""
    out, enqueued = [], enqueued or {}
    for (tl, seq), (d0, d1, run_id) in zip(launches, runs):
        t4 = whole[seq].T4 if seq in whole else None
        enq = enqueued.get(run_id, tl)
        out.append((seq, enq if tl <= enq <= (t4 or enq) else tl, t4,
                    d0, d1))
    return out


def pair_by_order(launches, runs, whole, enqueued=None):
    """The :func:`anchored` pairs of ONE program's launches
    ``[(TL, seq)]`` and its runs on ONE device ``[(D0, D1, run_id)]``, both
    by time, k-th to k-th.  Where the stream's ends cut some off, the head
    of either list is dropped by up to ``ENDS``: the alignment with the
    fewest dropped under which the two clocks can agree at all (a
    :func:`shift_interval` that is not empty; one batch off and thousands
    of unevenly spaced batches leave none) is taken, and the least
    contradictory where none can; ``None`` where more than ``ENDS`` stay
    unmatched at an end."""
    best = None
    for drop_l, drop_r in ([(0, 0)] + [(k, 0) for k in range(1, ENDS + 1)]
                           + [(0, k) for k in range(1, ENDS + 1)]):
        ls, rs = launches[drop_l:], runs[drop_r:]
        if not ls or not rs or abs(len(ls) - len(rs)) > ENDS:
            continue
        pairs = anchored(ls, rs, whole, enqueued)
        lo, hi, _, _ = shift_interval(pairs)
        if best is None or hi - lo > best[0]:
            best = (hi - lo, pairs)
        if hi >= lo:
            break
    return None if best is None else best[1]


def device_runs(launches, runs, whole, enqueued=None):
    """``({seq: (D0, D1)} on the DEVICE's clock, the shift's interval (lo,
    hi, lo seq, hi seq))`` over every program and device: ``runs`` is
    ``{program: {device: [(D0, D1, run_id)]}}``.  A batch's run is from the
    earliest start to the latest end over the devices that ran it, and ONE
    interval holds the pairs of all of them: the chips of a host are
    recorded on one clock (on a v5e host four chips' intervals ended within
    0.6 us of one another; chips that were not would leave the interval
    empty, ``hi < lo``).  ``None`` where any program's launches and runs
    cannot be paired on a device that ran it."""
    pairs, devices = [], set()
    for program, ls in launches.items():
        if not runs.get(program):
            return None
        for dev, rs in runs[program].items():
            paired = pair_by_order(ls, sorted(rs), whole, enqueued)
            if paired is None:
                return None
            pairs += paired
            devices.add(dev)
    found = {}
    for seq, _, _, d0, d1 in pairs:
        found.setdefault(seq, []).append((d0, d1))
    return ({seq: (min(r[0] for r in rs), max(r[1] for r in rs))
             for seq, rs in found.items() if len(rs) == len(devices)},
            shift_interval(pairs))


def segments(b, d0, d1):
    """The six segments of batch ``b``'s critical path, ns, ``(d0, d1)``
    its run on the HOST's clock; they add up to ``b.T5 - b.T0``."""
    return {"prelaunch": b.TL - b.T0, "launch_lag": d0 - b.TL,
            "device": d1 - d0, "ready_unread": max(0, b.T3 - d1),
            "readback_tail": b.T4 - max(d1, b.T3),
            "complete": b.T5 - b.T4}


def cpu_pct(spans, name):
    """100 x summed ``cpu_us`` / summed ``wall_us`` of the spans called
    ``name`` that carry the two stats (the thread's CPU time and the wall
    time of one interval inside the span, its two slow clock calls left
    out), or ``None`` where none does."""
    own = [(stats["wall_us"], stats["cpu_us"]) for n, _, _, stats in spans
           if n == name and "cpu_us" in stats and "wall_us" in stats]
    wall = sum(w for w, _ in own)
    return 100.0 * sum(cpu for _, cpu in own) / wall if wall else None


def life(spans, pipe_spans, runs, enqueued=None):
    """The traced batches' lives, from ``serve.`` spans, ``pipe.`` spans,
    ``runs = {program: {device: [(D0, D1, run_id)]}}`` and the host's
    enqueue events ``{run_id: start_ns}``; ``None`` where the spans name no
    launch, or the launches and runs cannot be paired:

    ``batches``    ``serve.batch`` spans
    ``joined``     whole batches with a run on every device that ran any
    ``shift_ns``, ``slack_ns``   what was added to the devices' clock, and
                   the width of the interval it was the least of
    ``bound_by``   the ``seq`` of the two batches that set that interval
    ``bucket``     the bucket most of the joined batches rode (8 in every
                   cell): the medians are over its batches
    ``median_ns``  ``life`` (``T5 - T0``), the six ``SEGMENTS``, ``upload``
                   and ``launch`` (the spans' durations)
    ``slot_wait_ns``   summed ``pipe.slot_wait``
    ``lives``      ``[(Batch, segments)]`` of the joined batches, by ``T0``
    """
    whole, launches = batches(spans)
    if not launches:
        return None
    found = device_runs(launches, runs, whole, enqueued)
    if found is None:
        return None
    on_device, (lo, hi, lo_seq, hi_seq) = found
    joined = sorted((whole[seq] for seq in on_device if seq in whole),
                    key=lambda b: b.T0)
    if not joined:
        return None
    lives = [(b, segments(b, on_device[b.seq][0] + lo,
                          on_device[b.seq][1] + lo)) for b in joined]
    (bucket, _), = collections.Counter(
        b.bucket for b in joined).most_common(1)
    own = [(b, seg) for b, seg in lives if b.bucket == bucket]
    median = {name: statistics.median(seg[name] for _, seg in own)
              for name in SEGMENTS}
    median.update(
        life=statistics.median(b.T5 - b.T0 for b, _ in own),
        upload=statistics.median(b.upload_ns for b, _ in own),
        launch=statistics.median(b.launch_ns for b, _ in own))
    return {"batches": sum(s[0] == BATCH for s in spans),
            "joined": len(joined), "shift_ns": lo,
            "slack_ns": hi - lo, "bound_by": (lo_seq, hi_seq),
            "bucket": bucket, "median_ns": median,
            "slot_wait_ns": sum(s[2] for s in pipe_spans
                                if s[0] == SLOT_WAIT),
            "lives": lives}


def read(path):
    """``(serve. spans, pipe. spans, live. spans, runs, enqueued)`` of the
    file: the host's spans as ``program_spans.read`` lists them,
    ``{program: {device: [(D0, D1, run_id)]}}`` of the ``XLA Modules``
    events of every program a launch span names, and ``{run_id:
    start_ns}`` of the host's ``DoEnqueueProgram`` events (the earliest
    of a run); ``None`` for a file with no device plane (the benchmark
    has no CPU mode)."""
    planes = program_spans._planes(path)
    if not any(tr.DEVICE_PLANE.match(plane.name) for plane in planes):
        return None
    host = program_spans.read(path, prefix=("serve.", "pipe.", "live."),
                              planes=planes)
    spans, pipe_spans, live = ([s for s in host if s[0].startswith(prefix)]
                               for prefix in ("serve.", "pipe.", "live."))
    programs = {s[3]["program"] for s in spans
                if s[0] == LAUNCH and "program" in s[3]}
    runs, enqueued = {}, {}
    for plane in planes:
        dev = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name != live_spans.MODULES_LINE:
                continue
            for ev in line.events:
                if dev:
                    program = ev.name.split("(")[0]
                    if program in programs:
                        runs.setdefault(program, {}).setdefault(
                            int(dev.group(1)), []).append(
                                (int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns),
                                 dict(ev.stats).get("run_id")))
                elif ev.name == ENQUEUE:
                    run_id = dict(ev.stats).get("run_id")
                    enqueued[run_id] = min(int(ev.start_ns),
                                           enqueued.get(run_id,
                                                        int(ev.start_ns)))
    return spans, pipe_spans, live, runs, enqueued


@functools.lru_cache(maxsize=2)
def _read_of(path, mtime_ns):
    found = read(path)
    if found is None:
        return None
    spans, pipe_spans, live, runs, enqueued = found
    return spans, live, life(spans, pipe_spans, runs, enqueued)


def traced(ctx):
    """``(serve. spans, live. spans, life)`` of the trace this run recorded
    (read once per process), or ``None`` for a run without one."""
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    return _read_of(path, os.stat(path).st_mtime_ns)


def traced_life(ctx):
    found = traced(ctx)
    return None if found is None else found[2]


def median_ms(ctx, key):
    """The median of ``key`` (``life``, a segment, ``upload``, ``launch``)
    over the traced batches of the most-ridden bucket, ms."""
    found = traced_life(ctx)
    return None if found is None else 1e-6 * found["median_ns"][key]


def serve_cpu_pct(ctx, name):
    found = traced(ctx)
    return None if found is None else cpu_pct(found[0], name)
