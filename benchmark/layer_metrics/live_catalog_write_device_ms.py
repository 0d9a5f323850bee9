"""Layer: live kernels.  Source: device trace — time in the traced seconds of
the catalog's write programs on the device, per ``live.batch``: the ``XLA
Modules`` events named ``jit__scatter_items`` (the engine's own table, in
place), ``jit__write_segment`` (the touched rows quantized into the index's
delta segment), ``jit__fold_segment`` (a compaction: the segment scattered
into the donated base arrays) and ``jit__scatter_rows`` (the fold-in server's
two fixed tables), as ``live_foldin_device_ms`` reads ``jit__fold_in_jit``.
``None`` where the trace names none of them.  Moves ``serve_p50_ms``: the
serving batches queue behind them on the one device."""

from benchmark import live_item_spans


def read(ctx):
    return live_item_spans.catalog_write_device_ms(ctx)
