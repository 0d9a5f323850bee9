"""The runners at tiny size on the CPU (called as functions: ``run.py``
itself refuses a CPU), the comparison that decides ``correct`` shown to
fail (a broken timed path; the reference one precision step down), the
trace reduction, and the proof that a configuration, a traffic mix with its
runner and a per-layer metric are each added as new files plus one entry.
"""

from __future__ import annotations

import ast
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen, harness, peaks
from benchmark import trace as tr
from benchmark.reference import topk
from benchmark.tests import tiny

BIG_SEED = 2 ** 31 + 12345      # the driver's seeds pass 32 signed bits


SOLVE_OP = tr.short_name(
    "%spd_solve_lanes.24 = f32[32,128,128]{2,1,0:T(8,128)S(1)} custom-call("
    "f32[32,128,128,128]{3,2,1,0:T(8,128)} %bitcast.1899), "
    'custom_call_target="tpu_custom_call", frontend_attributes={}')


def fake_device_trace(monkeypatch):
    """The CPU's trace has no TPU plane: keep the real file read and the
    real host spans, and put two device operations beside them."""
    real = tr.read_xplane

    def read(path):
        raw = real(path)
        t0 = min([s[1] for s in raw.host_spans] or [0])
        raw.device_ops = {0: [(SOLVE_OP, t0, 1_000_000),
                              ("fusion.2", t0 + 3_000_000, 500_000)]}
        return raw

    monkeypatch.setattr(tr, "read_xplane", read)
    # a CPU has no published peaks; the table refuses an unknown device
    monkeypatch.setitem(peaks.DEVICE_PEAKS, "cpu",
                        peaks.DEVICE_PEAKS["TPU v5 lite"])


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


@pytest.mark.parametrize("cell", ["tiny-r16-implicit.train-steady",
                                  "tiny-r10-explicit.train-steady"])
def test_train_cell_runs_and_is_correct(root, cell):
    line = harness.run_cell(root, cell, BIG_SEED, 0.5, False,
                            require_tpu=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "train_iter_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["metrics"]["train_iter_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"


def test_serve_cell_runs_and_is_correct(root):
    line = harness.run_cell(root, "tiny-r16-implicit.serve-steady", BIG_SEED,
                            1.0, False, require_tpu=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    assert line["attempted"] == 200 and line["failed"] == 0


def test_same_seed_same_inputs():
    ranges = dict(user_degree=[2, 20], item_degree=[2, 30])
    a = datagen.synthetic_ratings(50, 40, 500, BIG_SEED, **ranges)
    b = datagen.synthetic_ratings(50, 40, 500, BIG_SEED, **ranges)
    c = datagen.synthetic_ratings(50, 40, 500, BIG_SEED + 1, **ranges)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["user"], c["user"])
    # every seed: the same degrees (so the same bucket shapes and the same
    # work), inside the configuration's range, and no pair twice
    for x in (a, c):
        assert len(set(zip(x["user"].tolist(), x["item"].tolist()))) == 500
        for side, (lo, hi) in (("user", (2, 20)), ("item", (2, 30))):
            deg = np.bincount(x[side])
            assert lo <= deg.min() and deg.max() == hi
    assert np.array_equal(np.sort(np.bincount(a["item"])),
                          np.sort(np.bincount(c["item"])))
    with pytest.raises(ValueError):      # a heaviest user alone on its levels
        datagen.synthetic_ratings(50, 40, 500, 1)
    rng = datagen.rng_for(BIG_SEED, 2)
    due = datagen.poisson_arrivals(rng, 100, 2.0)
    assert len(due) == 200 and (np.diff(due) >= 0).all() and due[-1] < 2.0


@pytest.mark.parametrize("cell,expected", [
    ("tiny-r16-implicit.train-steady",
     {"fit_first_iter_s", "probe_s", "step_device_ms", "solve_kernel_ms",
      "solve_roofline", "device_idle_pct.train"}),
    ("tiny-r16-implicit.serve-steady",
     {"serve_queue_ms", "serve_score_device_ms", "device_idle_pct.serve",
      "gen_late_p99_ms", "serve_p95_ms", "serve_p99_ms", "serve_max_ms"})])
def test_traced_run_reports_layer_metrics(root, monkeypatch, cell, expected):
    fake_device_trace(monkeypatch)
    line = harness.run_cell(root, cell, 7, 1.0, True, require_tpu=False)
    assert expected == set(line["metrics"])
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert line["breakdown"]["device_ops"][0][0] == SOLVE_OP
    assert SOLVE_OP == ("%spd_solve_lanes.24 f32[32,128,128] "
                        "custom-call:tpu_custom_call")
    assert line["breakdown"]["idle_gaps"]


# -- the comparison has been shown to fail ---------------------------------

def test_step_that_returns_its_state_unchanged_is_not_correct(root,
                                                              monkeypatch):
    import tpu_als.core.als as core_als

    monkeypatch.setattr(core_als, "make_step",
                        lambda *a, **k: (lambda U, V: (U + 0, V + 0)))
    line = harness.run_cell(root, "tiny-r16-implicit.train-steady", 5, 0.3,
                            False, require_tpu=False)
    assert line["correct"] is False


def test_a_stall_inside_the_window_moves_train_iter_s(root, monkeypatch):
    """``train_iter_s`` is the whole window over its iterations: one stalled
    iteration has to show, which a median of the gaps would hide."""
    import time

    import tpu_als.core.als as core_als

    real = core_als.make_step
    calls = {"n": 0}

    def make_step(*a, **k):
        step = real(*a, **k)

        def stalled(U, V):
            calls["n"] += 1
            if calls["n"] == 3:
                time.sleep(1.0)
            return step(U, V)
        return stalled

    monkeypatch.setattr(core_als, "make_step", make_step)
    line = harness.run_cell(root, "tiny-r16-implicit.train-steady", 5, 0.3,
                            False, require_tpu=False)
    assert calls["n"] >= 3 and line["correct"] is True
    whole = line["metrics"]["train_iter_s"]["value"] * line["attempted"]
    assert whole >= 1.0


def test_item_half_step_left_out_is_not_correct(root, monkeypatch):
    """V never updated, U solved exactly at that V: only the item rows'
    comparison (against the U of the boundary before) can see it."""
    import tpu_als.core.als as core_als

    real = core_als.make_step

    def make_step(*a, **k):
        step = real(*a, **k)
        return lambda U, V: (step(U, V + 0)[0], V + 0)

    monkeypatch.setattr(core_als, "make_step", make_step)
    line = harness.run_cell(root, "tiny-r16-implicit.train-steady", 5, 0.3,
                            False, require_tpu=False)
    assert line["correct"] is False


def test_answer_altered_where_it_is_produced_is_not_correct(root,
                                                            monkeypatch):
    from tpu_als.serving import batcher

    real = batcher.Ticket.complete

    def complete(self, result):
        scores, ids = result
        ids = np.array(ids)
        ids[0] = (ids[0] + 1) % 200       # serve another item's id
        real(self, (scores, ids))

    monkeypatch.setattr(batcher.Ticket, "complete", complete)
    line = harness.run_cell(root, "tiny-r16-implicit.serve-steady", 5, 0.5,
                            False, require_tpu=False)
    assert line["correct"] is False


def test_shed_requests_count_as_failed(root, monkeypatch):
    from tpu_als.serving import batcher, engine

    real = engine.ServingEngine.submit
    calls = {"n": 0}

    def submit(self, payload, **kw):
        calls["n"] += 1
        # the 12 requests of the warm batches pass: a refusal there is not
        # a shed of the window's but a failed set-up
        if calls["n"] > 12 and calls["n"] % 10 == 0:
            raise batcher.Overloaded("test shed")
        return real(self, payload, **kw)

    monkeypatch.setattr(engine.ServingEngine, "submit", submit)
    line = harness.run_cell(root, "tiny-r16-implicit.serve-steady", 5, 0.5,
                            False, require_tpu=False)
    assert line["failed"] >= 9


def test_control_train_reference_one_precision_down_fails_the_limits():
    """The control: the reference put in the program's place, computed from
    float8 operands (one step below the bf16 pass the configuration's f32
    already is).  It must fail the limits the real cells are held to."""
    from benchmark.runners import train

    cfg = harness.load_json(os.path.join(
        tiny.BENCH, "configs", "ml25m-r128-implicit.json"))
    small = dict(cfg, num_users=400, num_items=300, num_ratings=40_000)
    data = datagen.synthetic_ratings(400, 300, 40_000, BIG_SEED,
                                     user_degree=[20, 150],
                                     item_degree=[20, 300])
    rng = np.random.default_rng(1)
    U = rng.standard_normal((400, 128)).astype(np.float32) * 0.1
    V = rng.standard_normal((300, 128)).astype(np.float32) * 0.1
    found = train.reference_residuals(data, small, U, V, U, seed=BIG_SEED,
                                      n_rows=64,
                                      operand_dtype="float8_e4m3fn")
    checks = train.checks_from(found, U, V, small)
    assert not all(c.holds for c in checks)
    zero = {side: {"residual": np.zeros(64)} for side in ("user", "item")}
    assert all(c.holds for c in train.checks_from(zero, U, V, small))


@pytest.mark.parametrize("config, rank", [("amazon23-r256-share32", 256),
                                          ("ml25m-r128-implicit", 128)])
def test_control_serve_reference_one_precision_down_fails_the_limits(config,
                                                                     rank):
    from benchmark.runners import serve

    cfg = harness.load_json(os.path.join(
        tiny.BENCH, "configs", config + ".json"))
    rng = np.random.default_rng(2)
    U = rng.standard_normal((256, rank)).astype(np.float32)
    V = (rng.standard_normal((4000, rank)) / np.sqrt(rank)).astype(np.float32)
    scores, ids = topk.lower_precision_topk(
        U, V, 10, shortlist_k=64, shortlist_bits=4,
        rescore_dtype="float8_e4m3fn")
    checks = serve.compare_answers(scores, ids, U, V, 10, cfg["correct"])
    assert not all(c.holds for c in checks)
    exact_s, exact_i = topk.exact_topk(U, V, 10)
    assert all(c.holds for c in serve.compare_answers(
        exact_s, exact_i, U, V, 10, cfg["correct"]))


# -- the trace reduction ------------------------------------------------------

def test_trace_arithmetic_on_hand_written_events():
    raw = tr.RawTrace(
        device_ops={0: [("while", 0, 100), ("%spd_solve.1", 10, 30),
                        ("fusion.2", 50, 40), ("copy", 120, 10)]},
        host_spans=[("bench.callback", 98, 25)])
    s = tr.summarize(raw)
    assert s.window_s == pytest.approx(130e-9)
    assert s.busy_s == pytest.approx(110e-9)        # nested ops counted once
    assert s.idle_pct == pytest.approx(100 * 20 / 130)
    assert s.op_seconds("solve") == pytest.approx(30e-9)
    assert dict(s.top_ops())["while"] == pytest.approx(30e-9)   # self time
    assert s.idle_gaps == [("bench.callback", pytest.approx(20e-9))]
    with pytest.raises(ValueError):
        tr.summarize(tr.RawTrace())


def test_trace_reader_on_a_recorded_chip_trace():
    path = os.path.join(tiny.HERE, "data", "serve_v5e.xplane.pb")
    expect = harness.load_json(os.path.join(tiny.HERE, "data",
                                            "serve_v5e.expect.json"))
    s = tr.summarize(tr.read_xplane(path))
    assert s.n_devices == 1 and s.n_ops == expect["n_ops"]
    assert s.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    assert s.window_s == pytest.approx(expect["window_s"], rel=1e-9)
    assert s.top_ops(1)[0][0] == expect["top_op"]
    assert 0 < s.busy_s < s.window_s


# -- data-driven: new files plus one entry ------------------------------------

NEW_RUNNER = '''
from benchmark.harness import Outcome, at_most

def run(cell):
    return Outcome(metrics={"setup_s": 0.5}, attempted=cell.traffic["n"],
                   failed=0, checks=[at_most("nothing", 0, 0)],
                   counters={"n": cell.traffic["n"] * cell.config["scale"]},
                   trace_dir=cell.scratch("trace"))
'''
NEW_READER = '''
def read(ctx):
    return ctx.counters.get("n")
'''


def test_a_config_a_mix_a_runner_and_a_metric_are_new_files(tmp_path,
                                                            monkeypatch):
    configs = dict(tiny.TINY_CONFIGS, **{"new-config": {"scale": 3}})
    traffic = dict(tiny.TINY_TRAFFIC, **{"new-mix": {"kind": "newkind",
                                                     "n": 7}})
    cells = tiny.TINY_CELLS + [("new-config", "new-mix")]
    metric = {"name": "new_count", "unit": "1", "better": "higher",
              "source": "program_counter", "layer": "new layer",
              "moves": "setup_s", "workloads": ["new-config.new-mix"]}
    root = tiny.make_root(
        tmp_path, configs, traffic, cells,
        extra_files={"benchmark/runners/newkind.py": NEW_RUNNER,
                     "benchmark/layer_metrics/new_count.py": NEW_READER},
        extra_layer_metrics=[metric])
    line = harness.run_cell(root, "new-config.new-mix", 1, 1.0, False,
                            require_tpu=False)
    assert line["metrics"] == {"setup_s": {"value": 0.5, "unit": "s"}}
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "read_xplane", lambda p: tr.RawTrace(
        device_ops={0: [("op", 0, 10)]}))
    line = harness.run_cell(root, "new-config.new-mix", 1, 1.0, True,
                            require_tpu=False)
    assert line["metrics"] == {"new_count": {"value": 21.0, "unit": "1"}}
    # and the cells that were there still run, untouched
    assert harness.run_cell(root, "tiny-r10-explicit.train-steady", 1, 0.2,
                            False, require_tpu=False)["correct"]


# -- the command and the manifest ---------------------------------------------

def test_run_py_refuses_a_machine_without_a_tpu():
    first = tiny.real_manifest()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         first, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tiny.ROOT, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("manifest", [tiny.real_manifest,
                                      tiny.full_manifest])
def test_manifest_names_files_that_exist(manifest):
    """``BENCHMARK.json`` alone, and with the held-back entries beside it."""
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    bench = os.path.join(tiny.ROOT, m["paths"][0])
    for c in m["configs"]:
        assert os.path.exists(os.path.join(tiny.ROOT, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    names = {w["name"] for w in m["workloads"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for w in m["workloads"]:
        mix = harness.load_json(os.path.join(bench, "traffic",
                                             w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(bench, "runners",
                                           mix["kind"] + ".py"))
        assert len(w["why"]) <= 200
        assert len(harness.metrics_of(m, "end_to_end", w["name"])) >= 2
        assert harness.metrics_of(m, "per_layer", w["name"])
    for metric in m["per_layer"]:
        assert os.path.exists(os.path.join(bench, "layer_metrics",
                                           metric["name"] + ".py"))
        assert metric["moves"] in e2e
        assert set(metric["workloads"]) <= names
    for e in m["end_to_end"]:
        assert 0 < e["bound"] <= 0.1


ALLOWED_PROGRAM_IMPORTS = {
    "tpu_als", "tpu_als.serving.engine", "tpu_als.utils.platform"}


def test_the_benchmark_imports_the_program_through_the_listed_names_only():
    """PERF.md section 3 lists them; tests may reach further to break the
    timed path on purpose."""
    seen = set()
    for dirpath, _, files in os.walk(tiny.BENCH):
        if os.path.basename(dirpath) in ("tests", "__pycache__"):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(dirpath, name)).read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    seen |= {a.name for a in node.names
                             if a.name.startswith("tpu_als")}
                elif (isinstance(node, ast.ImportFrom) and node.module
                      and node.module.startswith("tpu_als")):
                    seen.add(node.module)
    assert seen <= ALLOWED_PROGRAM_IMPORTS, seen - ALLOWED_PROGRAM_IMPORTS
