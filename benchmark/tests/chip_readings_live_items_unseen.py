#!/usr/bin/env python3
"""Readings behind the ``serve-foldin-all`` cell's ``correct`` (PERF.md
section 2): for each seed one run of the cell through its runner and the
numbers the program was compared on; beside them the two CONTROLS that must
read ``correct: false``:

(i)   ``--slot-mask-off``: a second run of the seed in which the scoring
      program does not mask the segment's slots by the excluded ids (every
      id of a batch's lists that names a slot is taken out of the lists
      before ``serving.index.shortlist_rescore`` sees them; the base columns
      are masked as ever, and a slot's own base column is overridden
      anyway): a new item goes back to its rater — ``raters_given_a_new_
      item_back`` of ``raters_asked`` is the share that gives guarantee 4
      its teeth on planted factors;
(ii)  on the program's own run, the REFERENCE one precision step down in the
      program's place: every item fold of the run with bfloat16 operands,
      from the program's own inputs, held to the float64 fold as the
      program's row is (``ref.Replay.step(operand_dtype=)``): its smallest
      ``rel_err / (kappa * 2^-24)`` and the program's largest bracket
      ``correct.item_fold_c``.

(iii) on the same run, the sampled after-drain answers from an int4
      shortlist rescored in float8 over the FINAL catalog
      (``topk_unseen.lower_precision_topk``), the same ids excluded: the
      served path one precision step down, whose ``score_rel_err`` and
      recall bracket the serving limits from above.

One process for all seeds.  The benchmark's own runs never run this.

    python3 benchmark/tests/chip_readings_live_items_unseen.py --seeds 1,2 --seconds 10 --slot-mask-off
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["TPU_ALS_PLAN_CACHE"] = "off"

CELL = "amazon23-r256-share32-live-items-unseen.serve-foldin-all"


def without_the_slot_mask():
    """Put a ``shortlist_rescore`` in the engine's place that never sees an
    excluded id that names a slot of the segment; returns the undo."""
    import jax
    import jax.numpy as jnp

    from tpu_als.ops.topk import NOT_AN_ID
    from tpu_als.serving import engine

    scored = engine.shortlist_rescore

    def unmasked(U, Vq, sv, V, valid, *, delta=None, seen=None, **kw):
        if delta and seen is not None:
            drows = delta[0]
            seen = tuple(
                jnp.where((s[:, :, None] == drows[None, None, :]).any(-1),
                          NOT_AN_ID, s) for s in seen)
        return scored(U, Vq, sv, V, valid, delta=delta, seen=seen, **kw)

    engine.shortlist_rescore = unmasked
    jax.clear_caches()

    def undo():
        engine.shortlist_rescore = scored
        jax.clear_caches()

    return undo


def bfloat16_item_folds(a, runner, cell):
    import numpy as np

    low, _, _ = runner.replayed(a["streams"], a["updater"], a["tap"],
                                a["model"], a["U"], a["V"], a["hist"],
                                cell.config, operand_dtype="bfloat16")
    over = low.item_err_over_kappa()
    lim = cell.config["correct"]["item_fold_c"]
    return {"folds": len(over), "err_over_kappa_min": float(over.min()),
            "err_over_kappa_median": float(np.median(over)),
            "err_over_kappa_max": float(over.max()),
            "rel_err_min_median_max": [float(f(low.fold_err[1]))
                                       for f in (np.min, np.median, np.max)],
            "limit": lim, "correct": bool(over.max() <= lim)}


def int4_float8_answers(a, runner, cell):
    """The after-drain sample (every touched user asked after the last
    publish: one generation, the final catalog) answered one precision
    step down, held to the checks the program's answers are."""
    import numpy as np

    from benchmark.reference import topk_unseen

    n, rep = a["after_sample"], a["rep"]
    Q, excluded = a["after_Q"][:n], a["after_excluded"][:n]
    k, lim = cell.config["serving"]["k"], cell.config["correct"]
    s, i = topk_unseen.lower_precision_topk(
        Q, rep.final_catalog(), k, excluded, shortlist_k=64,
        shortlist_bits=4, rescore_dtype="float8_e4m3fn")
    gens = np.full(n, rep.batches)
    return {c.name: c.value for c in runner.compare(
        "", s, i, Q, gens, rep, excluded, k, lim)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--slot-mask-off", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from tpu_als.utils.platform import enable_persistent_compile_cache

    from benchmark import harness

    enable_persistent_compile_cache()
    runs = ["program"] + (["slot_mask_off"] if args.slot_mask_off else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in runs:
            undo = without_the_slot_mask() if name == "slot_mask_off" \
                else (lambda: None)
            try:
                _, _, runner, cell = harness.open_cell(
                    ROOT, args.workload, seed, args.seconds, False)
                outcome = runner.run(cell)
            finally:
                undo()
            a = outcome.artifacts
            rep = a["rep"]
            over = (rep.item_err_over_kappa() if rep is not None
                    else np.array([np.nan]))
            print(json.dumps({
                "READINGS": args.workload, "seed": seed, "run": name,
                "correct": all(c.holds for c in outcome.checks),
                "failed_checks": [c.name for c in outcome.checks
                                  if not c.holds],
                "metrics": outcome.metrics,
                "memory_peak_bytes": a.get("memory_peak_bytes"),
                "raters_asked": a.get("raters_asked"),
                "raters_given_a_new_item_back":
                    a.get("raters_given_a_new_item_back"),
                "new_items_back": a.get("new_items_back"),
                "requests_whose_history_named_a_slot":
                    a.get("requests_whose_history_named_a_slot"),
                "item_folds": None if rep is None else {
                    "folds": len(over),
                    "kappa_min_median_max": [
                        float(f(rep.item_kappa))
                        for f in (np.min, np.median, np.max)],
                    "err_over_kappa_median": float(np.median(over)),
                    "err_over_kappa_max": float(over.max()),
                    "rel_err_max": float(max(rep.fold_err[1]))},
                "program": {c.name: c.value for c in outcome.checks},
                "control_bfloat16_item_folds": (
                    bfloat16_item_folds(a, runner, cell)
                    if name == "program" and rep is not None else None),
                "control_int4_float8_answers": (
                    int4_float8_answers(a, runner, cell)
                    if name == "program" and rep is not None else None),
                "largest_score_err_at": a.get("largest_score_err_at"),
            }), flush=True)
            # the tables leave the device before the next run's come
            del outcome, a, runner, cell, rep
            gc.collect()


if __name__ == "__main__":
    main()
