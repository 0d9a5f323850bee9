"""Layer: serving kernels.  Source: device trace — self time of the device
operations that move data between chips (all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter, by the name of their HLO instruction or
its opcode), mean over the chips, over the batches dispatched in the traced
seconds.  Moves ``serve_p50_ms``.  A trace with no such operation (one chip,
or a program that has none) reads nothing."""

COLLECTIVE = r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter"


def read(ctx):
    n = ctx.counters.get("batches")
    if ctx.trace is None or not n:
        return None
    seconds = ctx.trace.op_seconds(COLLECTIVE)
    return 1e3 * seconds / n if seconds else None
