"""Layer: kernels.  Source: device trace + the benchmark's own count of the
solve's operations and bytes (``benchmark/peaks.py``; both half-steps:
users + items systems of order rank) and the chip's published peaks: the
least time the chip could take over the measured solve-kernel time.  Prints
which bound it is.  Moves ``train_iter_s``."""

import os

from benchmark import harness, peaks


def read(ctx):
    here = os.path.dirname(os.path.abspath(__file__))
    solve = harness.load_module(os.path.join(here, "solve_kernel_ms.py"),
                                "bench_layer_metric_solve_kernel_ms")
    measured = solve.seconds_per_iteration(ctx)
    if measured is None:
        return None
    cfg = ctx.cell.config
    ops, nbytes = peaks.solve_work(cfg["num_users"] + cfg["num_items"],
                                   cfg["als"]["rank"])
    least, bound = peaks.least_seconds(ops, nbytes,
                                       peaks.peaks_for(ctx.device_kind))
    ctx.cell.say("solve_roofline", least_s=least, bound=bound, ops=ops,
                 bytes=nbytes, measured_s=measured)
    return 100.0 * least / measured
