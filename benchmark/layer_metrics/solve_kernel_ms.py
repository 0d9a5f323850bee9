"""Layer: kernels.  Source: device trace — summed self time of the solve
kernel's events over the traced iterations.  Moves ``train_iter_s``.

PATTERN names the events: read by hand from one trace of each training
configuration on the chip (PERF.md section 5).  It matches the event's name
or its ``tf_op``/``long_name`` stat."""

PATTERN = r"^%spd_solve|_chol_\w*kernel|^%cholesky|^%triangular-solve"


def seconds_per_iteration(ctx):
    n = ctx.counters.get("iterations")
    if ctx.trace is None or not n:
        return None
    s = ctx.trace.op_seconds(PATTERN)
    return s / n if s > 0 else None


def read(ctx):
    s = seconds_per_iteration(ctx)
    return None if s is None else 1e3 * s
