"""The live updater's batch cycle in the profiler trace: the ``live.`` spans
of its thread (``live.idle``, ``live.batch.coalesce``, ``live.batch`` with
the stats ``seq``, ``events``, ``users``, ``new_users``, ``width``, ``mode``,
and inside it ``live.batch.foldin`` and ``live.batch.publish``) and the
device's runs of the fold-in program.  The v5e's profiler gives an operation
its HLO text and its times and no ``op_name``, so the program's scopes
(``live.foldin.gram`` / ``.solve``) are not in the file; its ``XLA Modules``
line names each run of a compiled program after the jitted function,
``jit__fold_in_jit(<fingerprint>)``, and that is what is read.  The names
are data here, as the ``serve.`` spans are to ``program_spans.py``; nothing of
the program is imported.  A trace of a program that writes no such span (an
older commit, a cell without an updater) yields ``None`` from every reader.
"""

from __future__ import annotations

import functools
import os

from benchmark import program_spans
from benchmark import trace as tr

BATCH, PUBLISH = "live.batch", "live.batch.publish"
MODULES_LINE = "XLA Modules"
FOLDIN_MODULE = "jit__fold_in_jit"


def module_runs(planes, name):
    """``{device: [(start_ns, end_ns)]}`` of the ``XLA Modules`` events
    whose name starts with ``name``: each run of that compiled program,
    from its first operation to the end of its last."""
    found = {}
    for plane in planes:
        dev = tr.DEVICE_PLANE.match(plane.name)
        if not dev:
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if ev.name.startswith(name):
                    found.setdefault(int(dev.group(1)), []).append(
                        (int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns)))
    return found


def cycle(path):
    """The updater's cycle over the traced seconds, or ``None`` where the
    file holds no ``live.batch`` span:

    ``batches``     ``live.batch`` spans
    ``batch_ns``    their summed duration (the thread works: fold-in and
                    publish with the Python around them)
    ``publish_ns``  summed duration of ``live.batch.publish``
    ``foldin_device_ns``  summed time of the fold-in program's runs
                    (mean over devices), or ``None`` where the trace
                    names none
    ``events``, ``users``, ``new_users``   summed over the batches
    """
    planes = program_spans._planes(path)
    spans = program_spans.read(path, prefix="live.", planes=planes)
    batches = [s for s in spans if s[0] == BATCH]
    if not batches:
        return None
    ops = module_runs(planes, FOLDIN_MODULE)
    busy = [sum(e - s for s, e in tr.busy_union(iv)) for iv in ops.values()]
    return {"batches": len(batches),
            "batch_ns": sum(s[2] for s in batches),
            "publish_ns": sum(s[2] for s in spans if s[0] == PUBLISH),
            "foldin_device_ns": sum(busy) / len(busy) if busy else None,
            **{k: sum(s[3].get(k, 0) for s in batches)
               for k in ("events", "users", "new_users")}}


@functools.lru_cache(maxsize=2)
def _cycle_of(path, mtime_ns):
    return cycle(path)


def traced_cycle(ctx):
    """:func:`cycle` of the trace this run recorded (read once per
    process), or ``None`` for a run without one."""
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    return _cycle_of(path, os.stat(path).st_mtime_ns)


def ms_per_batch(ctx, key):
    c = traced_cycle(ctx)
    if c is None or c[key] is None:
        return None
    return 1e-6 * c[key] / c["batches"]
