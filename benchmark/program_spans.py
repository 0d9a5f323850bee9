"""The program's own spans in the profiler trace, and the device's idle time
put down to them.

The serving engine writes its batch cycle onto the profiler's timeline as
``jax.profiler.TraceAnnotation`` spans whose names start with ``serve.``
(``serve.idle``, ``serve.batch.coalesce``, ``serve.batch`` with the stats
``seq``, ``bucket``, ``rows``, ``path``, and inside it ``serve.batch.stage``,
``.dispatch``, ``.readback``, ``.complete``).  The names are data here: the
benchmark keys on them as it keys on ``spd_solve_lanes``, and imports nothing
of the program for it.  The profiler names every host line alike, so a span
is found by its name on whatever line it sits.

Two stages, as in ``trace.py``: :func:`read` and :func:`device_busy` turn the
file into plain tuples, :func:`attribute` and :func:`clock_shift_ns` are
arithmetic on them.  The profiler puts the device's clock beside the host's
only to within a millisecond or two, and differently in every recording (a
v5e trace of this cell had every program start 0.9 ms BEFORE the span of the
call that launched it), which is as long as the phases to be told apart.  So
the device's timeline is first moved to where the spans allow it to be: no
operation can run between the end of one batch's ``readback`` and the start
of the next ``dispatch``.  A trace of
a program that writes no such span (an older commit), or a file with no
``/device:TPU:<n>`` plane, yields ``None`` from every reader: the benchmark
has no CPU mode, and a metric its program cannot report is left out.
"""

from __future__ import annotations

import bisect
import functools
import os

from benchmark import trace as tr

BATCH = "serve.batch"
DISPATCH, READBACK = "serve.batch.dispatch", "serve.batch.readback"
# the engine thread's phases that are not the wait for the device
HOST_PHASES = ("serve.batch.coalesce", "serve.batch.stage",
               "serve.batch.dispatch", "serve.batch.complete")
UNATTRIBUTED = "unattributed"


def _planes(path):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)


def read(path, prefix="serve.", planes=None):
    """``[(name, start_ns, dur_ns, stats)]`` of every event of a host plane
    whose name starts with ``prefix``, by start; ``stats`` holds the span's
    keyword fields.  (``planes``: the file's, where the caller has read it.)"""
    spans = []
    for plane in planes or _planes(path):
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.duration_ns), dict(ev.stats)))
    return sorted(spans, key=lambda s: s[1])


def device_busy(path, planes=None):
    """``{device: [(start_ns, end_ns)]}`` of the ``XLA Ops`` line of each
    ``/device:TPU:<n>`` plane, nested operations and all."""
    busy = {}
    for plane in planes or _planes(path):
        dev = tr.DEVICE_PLANE.match(plane.name)
        if not dev:
            continue
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                busy.setdefault(int(dev.group(1)), []).extend(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events)
    return {d: ops for d, ops in busy.items() if ops}


def overlap_ns(a, b):
    """Nanoseconds covered by both of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(busy, spans, window):
    """``{span name: ns, "unattributed": ns}``: every gap of one device's
    busy intervals (merged here, so nested operations count once) inside
    ``window = (t0, t1)``, split by its exact overlap with the spans of each
    name; what lies under no span is ``unattributed``.  Spans that do not
    overlap one another add up to the gaps exactly."""
    t0, t1 = window
    merged = [(max(s, t0), min(e, t1)) for s, e in tr.busy_union(busy)
              if s < t1 and e > t0]
    edges = [(t0, t0)] + merged + [(t1, t1)]
    gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    by_name = {}
    for name, start, dur, _ in spans:
        by_name.setdefault(name, []).append((start, start + dur))
    out = {name: overlap_ns(gaps, tr.busy_union(ivs))
           for name, ivs in by_name.items()}
    covered = tr.busy_union(iv for ivs in by_name.values() for iv in ivs)
    out[UNATTRIBUTED] = (sum(e - s for s, e in gaps)
                         - overlap_ns(gaps, covered))
    return out


def no_batch_in_flight(spans):
    """``[(start_ns, end_ns)]``: from the end of each ``readback`` to the
    start of the next ``dispatch`` — the engine thread (one, working through
    its batches in turn) has nothing on the device."""
    starts = sorted(s for name, s, _, _ in spans if name == DISPATCH)
    out = []
    for end in sorted(s + d for name, s, d, _ in spans if name == READBACK):
        i = bisect.bisect_left(starts, end)
        if i < len(starts):
            out.append((end, starts[i]))
    return out


def clock_shift_ns(busy, forbidden, limit_ns=10_000_000):
    """The nanoseconds to add to the device's clock so that the least of its
    busy time (``busy``: merged intervals) falls into ``forbidden`` (sorted,
    disjoint).  The shifts that achieve it are a range, as wide as the
    shortest launch latency and wake-up together; of the ranges within
    ``limit_ns`` the one nearest to no shift is taken, and of it the least
    shift — the earliest the device can have run: the batch that was
    launched fastest starts with its ``dispatch`` span.  Chosen so that two
    recordings of the same program read alike, whatever their clocks did."""
    if not busy or not forbidden:
        return 0
    # busy time inside ``forbidden`` as a function of the shift d is a sum
    # of trapezoids, one for every busy (s, e) and forbidden (a, c) that a
    # shift within the limit can bring together: it rises from d = a - e, is
    # level between a - s and c - e, and is gone at c - s.  Piecewise
    # linear, so its least value is taken at a corner
    f_ends = [c for _, c in forbidden]
    corners = [(-limit_ns, 0), (limit_ns, 0)]     # (shift, turn of slope)
    for s, e in busy:
        j = bisect.bisect_left(f_ends, s - limit_ns)
        while j < len(forbidden) and forbidden[j][0] < e + limit_ns:
            a, c = forbidden[j]
            corners += [(a - e, 1), (min(a - s, c - e), -1),
                        (max(a - s, c - e), -1), (c - s, 1)]
            j += 1
    corners.sort()
    points, value, slope, at = [], 0, 0, corners[0][0]
    for d, turn in corners:
        value += slope * (d - at)
        at, slope = d, slope + turn
        if -limit_ns <= d <= limit_ns:
            points.append((d, value))
    least = min(v for _, v in points)
    stretches = []        # [first, last] shift of each level run at ``least``
    for k, (d, v) in enumerate(points):
        if v != least:
            continue
        if k and points[k - 1][1] == least:
            stretches[-1][1] = d
        else:
            stretches.append([d, d])
    first, _ = min(stretches, key=lambda r: 0 if r[0] <= 0 <= r[1]
                   else min(abs(r[0]), abs(r[1])))
    return first


def window_of(busy):
    """First device operation to the end of the last, over all devices: the
    window ``trace.summarize`` uses."""
    return (min(s for ops in busy.values() for s, _ in ops),
            max(e for ops in busy.values() for _, e in ops))


def cycle(path):
    """The serving engine's batch cycle over the traced window, or ``None``
    where the file holds no ``serve.`` span or no device plane:

    ``batches``   ``serve.batch`` spans that touch the window
    ``gap_ns``    device idle by span name (mean over devices), the time
                  under ``serve.batch`` itself but under none of its phases
                  counted as unattributed
    ``host_ns``   summed duration, inside the window, of the phases in
                  which the engine thread works and does not wait for the
                  device, idle device or not
    ``rows``, ``bucket_rows``   summed over those batches
    ``clock_shift_ns``   what was added to each device's clock first
    """
    planes = _planes(path)
    busy = device_busy(path, planes)
    spans = read(path, planes=planes)
    if not busy or not spans:
        return None
    forbidden = no_batch_in_flight(spans)
    shift = {d: clock_shift_ns(tr.busy_union(ops), forbidden)
             for d, ops in busy.items()}
    busy = {d: [(s + shift[d], e + shift[d]) for s, e in ops]
            for d, ops in busy.items()}
    t0, t1 = window = window_of(busy)
    batches = [s for s in spans
               if s[0] == BATCH and s[1] < t1 and s[1] + s[2] > t0]
    if not batches:
        return None
    leaves = [s for s in spans if s[0] != BATCH]
    gap_ns = {}
    for ops in busy.values():
        for name, ns in attribute(ops, leaves, window).items():
            gap_ns[name] = gap_ns.get(name, 0.0) + ns / len(busy)
    host_ns = sum(max(0, min(s + dur, t1) - max(s, t0))
                  for name, s, dur, _ in leaves if name in HOST_PHASES)
    return {"batches": len(batches), "gap_ns": gap_ns, "host_ns": host_ns,
            "clock_shift_ns": shift,
            "rows": sum(s[3].get("rows", 0) for s in batches),
            "bucket_rows": sum(s[3].get("bucket", 0) for s in batches)}


@functools.lru_cache(maxsize=2)
def _cycle_of(path, mtime_ns):
    return cycle(path)


def traced_cycle(ctx):
    """:func:`cycle` of the trace this run recorded (read once per process),
    or ``None`` for a run without one.  The runner puts the trace under the
    checkout's ``.bench_cache/runs/trace``."""
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    return _cycle_of(path, os.stat(path).st_mtime_ns)


def gap_ms_per_batch(ctx, name):
    """Device idle under the spans called ``name``, ms per batch."""
    c = traced_cycle(ctx)
    if c is None:
        return None
    return 1e-6 * c["gap_ns"].get(name, 0.0) / c["batches"]
