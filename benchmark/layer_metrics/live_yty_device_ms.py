"""Layer: live kernels.  Source: device trace — time in the traced seconds of
the Gram-carrying row write's runs on the device, per ``live.batch``: the
``XLA Modules`` events named ``jit__scatter_rows_yty`` (``core/foldin.py``:
the rows a fold moved gathered as they lay, ``G + new^T new - old^T old``
under the program's scope ``live.foldin.yty``, and the rows' set beside it —
the v5e's profiler gives an operation no ``op_name``, so the program that
holds the scope is what is read; its plain sibling ``jit__scatter_rows`` is
the set alone).  An implicit server that folds both sides runs it twice a
batch.  ``None`` where the trace names no such program (a commit that
recomputes the Gram matrix over the table, an explicit cell).  Moves
``serve_p90_ms``: the serving batches queue behind it on the one device."""

import os

from benchmark import live_spans, program_spans
from benchmark import trace as tr

MODULE = "jit__scatter_rows_yty"


def read(ctx):
    cycle = live_spans.traced_cycle(ctx)
    if cycle is None:
        return None
    path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache", "runs",
                                       "trace"))
    runs = live_spans.module_runs(program_spans._planes(path), MODULE)
    if not runs:
        return None
    busy = [sum(e - s for s, e in tr.busy_union(iv)) for iv in runs.values()]
    return 1e-6 * sum(busy) / len(busy) / cycle["batches"]
