"""Layer: live kernels.  Source: device trace — time in the traced seconds of
the fold-in program's runs on the device (the ``XLA Modules`` events named
``jit__fold_in_jit``: Gram build and solve, first operation to last), per
batch.  Moves ``serve_p50_ms``: the serving batches queue behind them on the
one device."""

from benchmark import live_spans


def read(ctx):
    return live_spans.ms_per_batch(ctx, "foldin_device_ns")
