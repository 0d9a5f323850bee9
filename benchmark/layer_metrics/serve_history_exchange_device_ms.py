"""Layer: serving kernels.  Source: device trace — self time of the
all-reduce whose operand is the batch's ``s32[bucket, history pad]`` lists
(by the instruction's own text on the ``XLA Ops`` line: its result shape and
opcode, the pads the cell's traffic names), mean over the chips, over the
batches dispatched in the traced seconds.  Moves ``serve_p50_ms``.  A trace
with no such operation (one chip, a program that shards no histories, a
batch that excluded nothing) reads nothing."""


def read(ctx):
    n, pads = ctx.counters.get("batches"), ctx.counters.get("history_pads")
    if ctx.trace is None or not n or not pads:
        return None
    widths = "|".join(str(int(p)) for p in pads)
    seconds = ctx.trace.op_seconds(
        rf" s32\[\d+,(?:{widths})\] all-reduce(?:-start|-done)?\b")
    return 1e3 * seconds / n if seconds else None
