"""One cell, one run.  Everything that belongs to one configuration, one
traffic mix, one kind of runner or one per-layer metric lives in a file of
its own, found by the names in ``BENCHMARK.json``:

    <config entry>.file                         the configuration's sizes
    <paths[0]>/traffic/<traffic>.json           the mix; names its runner ``kind``
    <paths[0]>/runners/<kind>.py                ``run(cell) -> Outcome``
    <paths[0]>/layer_metrics/<metric>.py        ``read(ctx) -> float | None``

so a later PR adds files and manifest entries and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field


class BenchmarkError(Exception):
    """The run cannot produce a result line (exit code 1, no line)."""


@dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float
    holds: bool

    def line(self):
        return {"check": self.name, "value": self.value,
                "limit": self.limit, "holds": bool(self.holds)}


def at_most(name, value, limit):
    return Check(name, float(value), float(limit), bool(value <= limit))


def at_least(name, value, limit):
    return Check(name, float(value), float(limit), bool(value >= limit))


@dataclass
class Outcome:
    metrics: dict                 # end-to-end values by name (setup_s too)
    attempted: int
    failed: int
    checks: list                  # [Check]
    counters: dict = field(default_factory=dict)   # for the layer readers
    trace_dir: str | None = None
    artifacts: dict = field(default_factory=dict)  # the run's inputs and
    #     outputs, for whoever calls the runner itself (the control of
    #     tests/chip_readings.py); the harness never looks at them


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    root: str
    bench_dir: str
    t_process: float              # perf_counter at process start, moved
    #     later by the seconds the accelerator runtime took to start: set-up
    #     is ``now - t_process``, the machine's own part left out
    clock: object                 # clocks.CompileClock

    def say(self, what, **fields):
        print(json.dumps({"cell": self.name, "what": what, **fields},
                         default=str), flush=True)

    def scratch(self, name):
        """An empty directory for this run's trace, inside the checkout."""
        path = os.path.join(self.root, ".bench_cache", "runs", name)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    if not os.path.exists(path):
        raise BenchmarkError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(root, workload):
    """(manifest, workload entry, config entry) or BenchmarkError."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    return manifest, w, configs[w["config"]]


def cell_files(root, workload):
    """(manifest, workload entry, configuration, traffic mix, the
    benchmark's directory) of one cell, each from its own file."""
    manifest, w, cfg_entry = find_cell(root, workload)
    bench_dir = os.path.join(root, manifest["paths"][0])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    return manifest, w, config, traffic, bench_dir


def metrics_of(manifest, section, workload):
    """The metrics of ``section`` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def device_info(chips, require_tpu=True):
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise BenchmarkError(f"no TPU: jax.devices()[0] is "
                             f"{info['platform']}:{info['kind']}; the "
                             "benchmark has no CPU mode")
    if len(devs) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, JAX reports "
                             f"{len(devs)}")
    return info


def memory_peak_bytes():
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@dataclass
class LayerContext:
    """What a per-layer reader may look at."""

    cell: Cell
    counters: dict
    trace: object | None          # trace.TraceSummary
    device_kind: str


def read_layer_metrics(cell, manifest, outcome, summary, device_kind):
    ctx = LayerContext(cell, outcome.counters, summary, device_kind)
    out = {}
    for m in metrics_of(manifest, "per_layer", cell.name):
        reader = load_module(
            os.path.join(cell.bench_dir, "layer_metrics", m["name"] + ".py"),
            "bench_layer_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def open_cell(root, workload, seed, seconds, trace, *, t_process=None,
              require_tpu=True):
    """(manifest, device, runner module, Cell) of one run, before any work
    on the device but the look at it."""
    from benchmark import clocks

    t_process = time.perf_counter() if t_process is None else t_process
    manifest, w, config, traffic, bench_dir = cell_files(root, workload)
    t_backend = time.perf_counter()
    device = device_info(w["chips"], require_tpu=require_tpu)
    backend_start_s = time.perf_counter() - t_backend
    runner = load_module(
        os.path.join(bench_dir, "runners", traffic["kind"] + ".py"),
        "bench_runner_" + traffic["kind"])
    cell = Cell(
        name=workload, config=config, traffic=traffic, chips=w["chips"],
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        root=root, bench_dir=bench_dir,
        t_process=t_process + backend_start_s, clock=clocks.CompileClock())
    cell.say("device", process_to_backend_s=t_backend - t_process,
             backend_start_s=backend_start_s, **device)
    return manifest, device, runner, cell


def run_cell(root, workload, seed, seconds, trace, *, t_process=None,
             require_tpu=True):
    """Run one cell and return the result line as a dict."""
    from benchmark import clocks

    manifest, device, runner, cell = open_cell(
        root, workload, seed, seconds, trace, t_process=t_process,
        require_tpu=require_tpu)
    with clocks.PallasCallLog() as pallas_log:
        outcome = runner.run(cell)
    device["memory_peak_bytes"] = memory_peak_bytes()

    interpreted = pallas_log.interpreted()
    cell.say("pallas", compiled=pallas_log.compiled(),
             interpreted=interpreted)
    checks = list(outcome.checks)
    if require_tpu:
        checks.append(at_most("pallas_calls_in_interpret_mode",
                              len(interpreted), 0))
    for c in checks:
        cell.say("compared", **c.line())

    line = {"correct": all(c.holds for c in checks),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed)}
    if not trace:
        units = {m["name"]: m["unit"]
                 for m in metrics_of(manifest, "end_to_end", workload)}
        missing = sorted(set(units) - set(outcome.metrics))
        if missing:
            raise BenchmarkError(f"runner reported no {missing}")
        line["metrics"] = {n: {"value": float(outcome.metrics[n]),
                               "unit": units[n]} for n in units}
    else:
        from benchmark import trace as tr

        summary = tr.summarize(tr.read_xplane(
            tr.find_xplane(outcome.trace_dir)))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["metrics"] = read_layer_metrics(cell, manifest, outcome,
                                             summary, device["kind"])
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]}
    line["device"] = device
    return line
