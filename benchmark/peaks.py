"""The chip's published peaks and the closed-form work of the solve stage.

One table, keyed by ``jax.devices()[0].device_kind``.  A device that is not
in it is an error, never a default.  Source: Google Cloud documentation,
"TPU v5e" system architecture page: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM2e at 819 GB/s per chip.  There is no published f32 figure; a
share of the bf16 peak is therefore a lower bound on a f32 kernel's share.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to benchmark/peaks.py with its source") from None


def solve_work(n_systems, rank):
    """Operations and HBM bytes that solving ``n_systems`` SPD systems of
    order ``rank`` by Cholesky needs (copied from the closed forms of
    ``tpu_als/perf/roofline.py``): r^3/3 for the factorisation plus 2 r^2
    for the two triangular solves; one read of A (r^2 f32), one read of b
    and one write of x (2 r f32)."""
    ops = n_systems * (rank ** 3 / 3.0 + 2.0 * rank ** 2)
    nbytes = n_systems * (rank ** 2 + 2.0 * rank) * 4.0
    return ops, nbytes


def least_seconds(ops, nbytes, peaks):
    """(seconds, which) — the larger of the compute and the memory bound."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
