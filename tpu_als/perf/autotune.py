"""Measured-timing autotuner for the fused-solve kernel family.

Closes the half of ROADMAP item 4 the planner left open: the plan cache
banks probe *verdicts* (faster/slower booleans) but every knob governing
the measured-vs-floor gap — ``panel``, the ``_tiles_solve`` VMEM budget,
``max_wc``, the DMA ``pump`` depth, the factor-table dtype — stayed a
hand-picked literal.  This module searches that small discrete space by
timing the REAL kernel (``ops.pallas_gather_ne.gather_solve``) min-of-k
at the plan key's shape class and returns the winner next to the
roofline model's closed-form prediction, so the planner
(``plan.planner.resolve_kernel_config``) can bank
``{config, measured_seconds, model_seconds, banked_at}`` into the
existing ``plan_*.json`` entries and thread the config through the
dispatch sites in place of the literals.

Search discipline: one-at-a-time from the hand-picked defaults — the
default config is timed FIRST, then each knob's alternatives with every
other knob held at its default, and the winner is the single measured
minimum with ties (and sub-noise wins) going to the EARLIER trial.
Because the default is trial 0, the tuned config is never slower than
the hand-picked constants on the very A/B that chose it, by
construction.  The enumeration order is deterministic (dict/tuple order
of ``SPACE``), so a deterministic timer makes the whole search
deterministic — the seed only feeds the instance generator.

Off-TPU the kernels run under ``interpret=True``: the timings still
rank configs by the work the interpreter simulates, but they are NOT
device measurements — the planner banks them with ``source:
"interpret"`` and never lets them override an on-chip verdict.

The re-plan loop: :func:`drifted` compares a banked measured/modeled
ratio against a fresh one; past the configurable band
(``TPU_ALS_TUNE_BAND``) the planner invalidates the entry so the next
armed resolve re-tunes instead of riding a stale config.  The
``floor_audit`` contract (analysis/contracts.py) pins the committed
bank's ratios to the same band so the roofline gap can never silently
reopen in CI.
"""

from __future__ import annotations

import os
import time

from tpu_als import obs

# the discrete search space; every value is a feasible kernel knob at
# rank <= 512 except where _tiles_solve raises TileBudgetError (the
# search skips infeasible combos instead of banking them)
SPACE = {
    "panel": (8, 16, 32),
    "vmem_budget": (1 << 16, 1 << 17, 1 << 18, 1 << 19),
    "max_wc": (128, 256, 512),
    "depth": (2, 4, 8),
    "dtype": ("float32", "bfloat16"),
}

# the hand-picked historical constants — the untuned/off fallback, and
# trial 0 of every search.  depth 8 IS the substrate default
# (ring_buffer.dma_slots == min(8, n_entries); every real tile has
# n_entries >= 64), and dtype float32 is the headline compute dtype.
DEFAULT_CONFIG = {
    "panel": 16,
    "vmem_budget": 1 << 17,
    "max_wc": 256,
    "depth": 8,
    "dtype": "float32",
}

TUNE_BAND_ENV = "TPU_ALS_TUNE_BAND"
DEFAULT_TUNE_BAND = 2.0


def tune_band(default=DEFAULT_TUNE_BAND):
    """The measured/modeled drift band (a multiplicative factor > 1);
    ``TPU_ALS_TUNE_BAND`` overrides."""
    raw = os.environ.get(TUNE_BAND_ENV, "")
    try:
        band = float(raw) if raw else float(default)
    except ValueError:
        band = float(default)
    return max(1.0 + 1e-9, band)


def drifted(banked_ratio, current_ratio, band=None):
    """True when a fresh measured/modeled ratio has left the banked
    ratio's band — the re-plan trigger (``observe regress --trend`` and
    the attribution gap table both reduce their evidence to this)."""
    band = tune_band() if band is None else float(band)
    if not banked_ratio or not current_ratio:
        return False
    rel = float(current_ratio) / float(banked_ratio)
    return rel > band or rel < 1.0 / band


def enumerate_configs(space=None):
    """Deterministic one-at-a-time trial list: the defaults first, then
    each knob's alternatives with the others held at default."""
    space = dict(SPACE if space is None else space)
    base = dict(DEFAULT_CONFIG)
    base.update({k: v[0] for k, v in space.items()
                 if k in base and base[k] not in v})
    trials = [dict(base)]
    for knob, values in space.items():
        if knob not in base:
            raise ValueError(f"unknown autotune knob {knob!r}; "
                             f"knobs: {sorted(DEFAULT_CONFIG)}")
        for v in values:
            if v == base[knob]:
                continue
            cfg = dict(base)
            cfg[knob] = v
            trials.append(cfg)
    return trials


def feasible(config, rank):
    """A config is feasible when the panel divides the padded rank and
    the VMEM budget keeps the row tile above the panel-efficiency knee
    (``_tiles_solve`` raising TileBudgetError is the infeasible case)."""
    from tpu_als.ops.pallas_gather_ne import TileBudgetError, _tiles_solve

    r_pad = max(128, -(-int(rank) // 128) * 128)
    if r_pad % int(config["panel"]):
        return False
    try:
        _tiles_solve(r_pad, 8, panel=int(config["panel"]),
                     max_wc=int(config["max_wc"]),
                     vmem_budget=int(config["vmem_budget"]))
    except TileBudgetError:
        return False
    return True


def model_seconds(config, rank, n, w):
    """The roofline closed-form prediction for one fused-solve call at
    this config's padded shapes — ``fused_solve_kernel_bytes`` over the
    v5e HBM stream, the same single source of truth the kernel's
    ``CostEstimate`` and the fused_solve_audit contract pin.  This is
    what the measured timing is banked NEXT TO, and what the
    ``floor_audit`` band is derived from."""
    import importlib

    rl = importlib.import_module("tpu_als.perf.roofline")
    from tpu_als.ops.pallas_gather_ne import _tiles_solve

    r_pad = max(128, -(-int(rank) // 128) * 128)
    w8 = -(-int(w) // 8) * 8
    tn, wc, w_pad = _tiles_solve(r_pad, w8, panel=int(config["panel"]),
                                 max_wc=int(config["max_wc"]),
                                 vmem_budget=int(config["vmem_budget"]))
    n_pad = -(-int(n) // tn) * tn
    db = 2 if "bfloat16" in str(config["dtype"]) else 4
    by = rl.fused_solve_kernel_bytes(n_pad * w_pad, n_pad, r_pad, db)
    hbm_gbps = rl.device_peaks(rl.MODELED_DEVICE_KIND)["hbm_gbps"]
    return by / (hbm_gbps * 1e9)


def make_timer(rank, compute_dtype, *, n=256, w=64, k=3, seed=0,
               interpret=None):
    """Build the real-kernel timer: ``timer(config) -> min-of-k
    seconds`` for one ``gather_solve`` call on a representative
    (n, w) explicit instance at ``rank``.  Warm call first (compile
    excluded), then min of ``k`` fenced wall-clock reps — the
    ``faster_than_einsum`` probe's ``best(f)`` idiom.  ``interpret``
    defaults to "not on a TPU"."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_als.ops.pallas_gather_ne import gather_fused_solve_explicit
    from tpu_als.utils import platform

    if interpret is None:
        interpret = not platform.on_tpu()
    rng = np.random.default_rng(int(seed))
    N = max(4 * n, 64)
    V32 = jnp.asarray(rng.normal(size=(N, rank)).astype(np.float32)
                      / np.sqrt(rank))
    cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
    vals32 = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
    mask32 = jnp.asarray((rng.random((n, w)) < 0.8).astype(np.float32))

    def timer(config):
        # the dtype knob IS the factor-table residency: the table and
        # the weight streams move in the config dtype end-to-end (the
        # kernel's reduce_precision ridge keeps the tail consistent)
        dt = jnp.dtype(str(config["dtype"]))
        V = V32.astype(dt)
        vals, mask = vals32.astype(dt), mask32.astype(dt)

        def run():
            return gather_fused_solve_explicit(
                V, cols, vals, mask, 0.1,
                panel=int(config["panel"]),
                max_wc=int(config["max_wc"]),
                vmem_budget=int(config["vmem_budget"]),
                depth=int(config["depth"]),
                interpret=interpret)

        platform.fence(run())  # compile + warm
        best = None
        for _ in range(max(1, int(k))):
            t0 = time.perf_counter()
            platform.fence(run())
            dt_s = time.perf_counter() - t0
            best = dt_s if best is None else min(best, dt_s)
        return best

    timer.interpret = bool(interpret)
    return timer


def tune(*, rank=128, compute_dtype="float32", space=None, budget_s=120.0,
         k=3, n=256, w=64, seed=0, timer=None, kernel="gather_solve"):
    """Run the one-at-a-time search and return the verdict dict the
    planner banks verbatim::

        {"config", "measured_seconds", "default_seconds",
         "model_seconds", "source", "trials", "tune_seconds"}

    ``timer(config) -> seconds`` is injectable (determinism tests, and
    the planner's interpret/device split rides ``timer.interpret``);
    the default is :func:`make_timer` on the real kernel.  The search
    stops early when ``budget_s`` is exhausted — the best config so far
    wins, and the defaults are always trial 0, so a tuned verdict is
    never slower than the hand-picked constants on its own A/B."""
    if timer is None:
        timer = make_timer(rank, compute_dtype, n=n, w=w, k=k, seed=seed)
    source = ("interpret" if getattr(timer, "interpret", True)
              else "device")
    trials = []
    best_cfg, best_s = None, None
    t_start = time.perf_counter()
    for config in enumerate_configs(space):
        if trials and budget_s is not None \
                and time.perf_counter() - t_start > float(budget_s):
            break
        if not feasible(config, rank):
            continue
        seconds = float(timer(config))
        obs.emit("tune_trial", kernel=kernel, config=dict(config),
                 seconds=seconds)
        trials.append({"config": dict(config), "seconds": seconds})
        if best_s is None or seconds < best_s:   # strict: ties keep the
            best_cfg, best_s = dict(config), seconds  # earlier trial
    if best_cfg is None:
        raise ValueError(f"no feasible config at rank {rank} in the "
                         f"given space")
    default_s = trials[0]["seconds"]
    return {
        "config": best_cfg,
        "measured_seconds": best_s,
        "default_seconds": default_s,
        "model_seconds": model_seconds(best_cfg, rank, n, w),
        "source": source,
        "trials": trials,
        "tune_seconds": time.perf_counter() - t_start,
        "shape": {"rank": int(rank), "n": int(n), "w": int(w),
                  "k": int(k), "seed": int(seed)},
    }
