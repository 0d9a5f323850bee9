"""A throwaway benchmark root at a size the CPU holds: the real runners,
readers and harness (symlinked), tiny configurations and mixes (new files).
Used by the tests in this directory; ``run.py`` itself refuses a CPU."""

from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_ALS_PLAN_CACHE", "off")

# degree ranges at which 12,000 ratings over 300 x 200 repeat no pair
TINY_GENERATOR = {"planted_rank": 4, "user_degree": [10, 80],
                  "item_degree": [20, 150]}
# the CPU multiplies f32 exactly: residuals read 1e-7, float8 operands 1e-2
TINY_RESIDUALS = {"user_residual_median": 1e-5, "user_residual_max": 1e-4,
                  "item_residual_median": 1e-5, "item_residual_max": 1e-4}
TINY_CONFIGS = {
    "tiny-r16-implicit": {
        "num_users": 300, "num_items": 200, "num_ratings": 12000,
        "als": {"rank": 16, "implicitPrefs": True, "alpha": 40.0,
                "regParam": 0.01},
        "solve_jitter": 1e-6, "generator": TINY_GENERATOR,
        "serving": {"k": 10},
        "correct": dict(TINY_RESIDUALS, score_rel_err=1e-4,
                        recall_at_k=0.9)},
    "tiny-r10-explicit": {
        "num_users": 300, "num_items": 200, "num_ratings": 12000,
        "als": {"rank": 10, "implicitPrefs": False, "regParam": 0.1},
        "solve_jitter": 1e-6, "generator": TINY_GENERATOR,
        "correct": TINY_RESIDUALS},
}
TINY_TRAFFIC = {
    "train-steady": {"kind": "train", "trace_iterations": 2,
                     "check_rows": 64},
    "serve-steady": {"kind": "serve", "rate_per_s": 200, "zipf_s": 1.1,
                     "vector_share": 0.1, "warmup_seconds": 0.2,
                     "head_burst": 16, "warm_batches": [8, 4],
                     "trace_seconds": 0.5, "answer_timeout_s": 5.0,
                     "check_requests": 64},
}
TINY_CELLS = [("tiny-r16-implicit", "train-steady"),
              ("tiny-r16-implicit", "serve-steady"),
              ("tiny-r10-explicit", "train-steady")]


def real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def full_manifest():
    """``BENCHMARK.json`` with the entries of ``held_back.json`` beside its
    own: the training cells' metrics wait there for a configuration large
    enough for the driver's memory floor, and their runner and readers are
    rehearsed here all the same."""
    manifest = real_manifest()
    with open(os.path.join(BENCH, "held_back.json")) as f:
        held = json.load(f)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[section] = manifest[section] + held[section]
    return manifest


def make_root(tmp, configs=None, traffic=None, cells=None, extra_files=None,
              extra_layer_metrics=()):
    """Write a benchmark root under ``tmp`` and return its path.  Metrics
    keep their names; each lists the tiny cells of the kind it had."""
    configs = configs or TINY_CONFIGS
    traffic = traffic or TINY_TRAFFIC
    cells = cells or TINY_CELLS
    root = str(tmp)
    bench = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "runners", "layer_metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("runners", "layer_metrics"):
        for name in os.listdir(os.path.join(BENCH, sub)):
            if name.endswith(".py"):
                os.symlink(os.path.join(BENCH, sub, name),
                           os.path.join(bench, sub, name))
    for name, body in configs.items():
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(body, f)
    for name, body in traffic.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(body, f)
    for rel, text in (extra_files or {}).items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)

    real = full_manifest()
    kind_of = {w["name"]: traffic_kind(w["traffic"]) for w in real["workloads"]}
    names = {kind: [f"{c}.{t}" for c, t in cells
                    if traffic[t]["kind"] == kind]
             for kind in {t["kind"] for t in traffic.values()}}

    def retarget(metric):
        m = copy.deepcopy(metric)
        if "workloads" in m:
            kinds = {kind_of[w] for w in m["workloads"]}
            m["workloads"] = [n for k in kinds for n in names.get(k, [])]
        return m

    manifest = dict(real)
    manifest["configs"] = [
        {"name": n, "source": "test", "reduced": [], "why": "test",
         "file": f"benchmark/configs/{n}.json"} for n in configs]
    manifest["workloads"] = [
        {"name": f"{c}.{t}", "config": c, "traffic": t, "chips": 1,
         "why": "test"} for c, t in cells]
    manifest["end_to_end"] = [retarget(m) for m in real["end_to_end"]]
    manifest["per_layer"] = ([retarget(m) for m in real["per_layer"]]
                             + list(extra_layer_metrics))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def traffic_kind(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)["kind"]
