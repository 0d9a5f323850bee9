"""Layer: serving kernels.  Source: device trace — the least bytes a batch
of this query must read (``peaks_unseen.score_bytes``: the int8 catalog,
its scales and validity once, the batch's excluded ids once) over the
device's busy time a traced batch (``serve_score_device_ms``: the WHOLE
scoring program, mask and selection included) times the chip's published
HBM bandwidth.  Moves ``serve_p50_ms``.  A run that excluded nothing reads
nothing."""

from benchmark import peaks, peaks_unseen


def read(ctx):
    n = ctx.counters.get("batches")
    ids = ctx.counters.get("excluded_ids_per_batch")
    if ctx.trace is None or not n or ids is None or not ctx.trace.busy_s:
        return None
    least = peaks_unseen.score_bytes(ctx.counters["score_columns"],
                                     ctx.counters["rank"], ids)
    return 100.0 * least / (ctx.trace.busy_s / n * peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"])
