"""Layer: live kernels.  Source: device trace — the bytes the traced
seconds' folds HAD to move (``peaks_live_unseen.fold_bytes``: the REAL,
unpadded ratings' rank-256 float32 rows read once and the solved rows
written) over the device time of the fold-in program's runs in those
seconds (the ``XLA Modules`` events named ``jit__fold_in_jit``, as
``live_foldin_device_ms`` reads them) times the chip's published HBM
bandwidth.  Higher is better; a program that reads every row at least once
cannot read above 100.  ``None`` where the run folded over no history or
the trace names no such program.  Moves ``serve_p50_ms``."""

from benchmark import live_spans, peaks, peaks_live_unseen


def read(ctx):
    ratings, rows = (ctx.counters.get("fold_ratings_traced"),
                     ctx.counters.get("fold_rows_traced"))
    c = live_spans.traced_cycle(ctx)
    if not ratings or not rows or c is None or not c["foldin_device_ns"]:
        return None
    least = peaks_live_unseen.fold_bytes(ratings, rows, ctx.counters["rank"])
    return 100.0 * least / (1e-9 * c["foldin_device_ns"] * peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"])
