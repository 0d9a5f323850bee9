"""The item side of the live updater's cycle in the profiler trace, beside
``live_spans.py`` (imported, not changed): the ``live.batch.foldin.items``
spans of the updater thread and the device's runs of the catalog's write
programs, which the trace's ``XLA Modules`` line names after their jitted
functions.  The names are data here; nothing of the program is imported.  A
trace of a program that writes none of them (an older commit, a cell whose
updater folds no items) yields ``None`` from every reader."""

from __future__ import annotations

import os

from benchmark import live_spans, program_spans
from benchmark import trace as tr

ITEMS = "live.batch.foldin.items"
# the engine's own table in place; the touched rows quantized into the
# index's delta segment; a compaction (the segment scattered into the
# donated base arrays); the fold-in server's two fixed tables
WRITE_MODULES = ("jit__scatter_items", "jit__write_segment",
                 "jit__fold_segment", "jit__scatter_rows")


def _traced(ctx):
    """(the updater's cycle, the trace's planes) of this run, or ``None``."""
    cycle = live_spans.traced_cycle(ctx)
    if cycle is None:
        return None
    path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                       "runs", "trace"))
    return cycle, path, program_spans._planes(path)


def items_foldin_ms(ctx):
    """Summed ``live.batch.foldin.items`` spans per ``live.batch``, ms."""
    found = _traced(ctx)
    if found is None:
        return None
    cycle, path, planes = found
    spans = [s for s in program_spans.read(path, prefix="live.",
                                           planes=planes) if s[0] == ITEMS]
    if not spans:
        return None
    return 1e-6 * sum(s[2] for s in spans) / cycle["batches"]


def catalog_write_device_ms(ctx):
    """Device time of the catalog's write programs (mean over devices) per
    ``live.batch``, ms."""
    found = _traced(ctx)
    if found is None:
        return None
    cycle, _, planes = found
    by_device = {}
    for name in WRITE_MODULES:
        for dev, runs in live_spans.module_runs(planes, name).items():
            by_device.setdefault(dev, []).extend(runs)
    if not by_device:
        return None
    busy = [sum(e - s for s, e in tr.busy_union(iv))
            for iv in by_device.values()]
    return 1e-6 * sum(busy) / len(busy) / cycle["batches"]
