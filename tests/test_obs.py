"""tpu_als.obs — registry semantics, exposition, run dirs, the observe CLI.

Covers the observability contracts end to end on the forced 8-device CPU
mesh (conftest): fixed-bucket histograms, schema validation at call time
AND statically (scripts/check_obs_schema.py), Prometheus text exposition,
finalize/run-dir lifecycle, the instrumented train/serve/ingest/checkpoint
paths, and the `tpu_als observe summarize|tail` surface.  The deeper
comm-model-vs-jaxpr cross-check lives in tests/test_comm_audit.py; here we
verify the emitted gauge matches the audited estimator value for every
strategy.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from tpu_als import ALS, obs
from tpu_als.cli import main as cli_main
from tpu_als.obs import report
from tpu_als.obs.metrics import BUCKET_BOUNDS, MetricsRegistry, _Hist
from tpu_als.parallel.mesh import make_mesh
from tpu_als.utils import observe
from tpu_als.utils.observe import IterationLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKER = os.path.join(REPO, "scripts", "check_obs_schema.py")


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test gets a clean default registry (the instrumented modules
    resolve it at call time through the tpu_als.obs delegators)."""
    obs.reset()
    yield
    obs.reset()


def _read_events(run_dir):
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _parse_prom(text):
    """name{labels} -> float for every sample line (comments skipped)."""
    samples = {}
    for line in text.strip().splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        samples[key] = float(val)
    return samples


# -- histogram buckets -----------------------------------------------------

def test_bucket_grid_is_fixed_log_scale():
    assert len(BUCKET_BOUNDS) == 49
    assert BUCKET_BOUNDS[0] == pytest.approx(1e-6)
    assert BUCKET_BOUNDS[-1] == pytest.approx(1e6)
    assert all(b < c for b, c in zip(BUCKET_BOUNDS, BUCKET_BOUNDS[1:]))
    # 4 buckets per decade, anchored at 1.0
    assert BUCKET_BOUNDS[24] == 1.0
    assert BUCKET_BOUNDS[28] / BUCKET_BOUNDS[24] == pytest.approx(10.0)


def test_hist_bucket_placement():
    h = _Hist()
    h.observe(1.0)          # exact bound: le semantics put it AT the bound
    assert h.counts[24] == 1
    h = _Hist()
    h.observe(2.0)          # (10^0.25, 10^0.5]
    assert h.counts[26] == 1
    h = _Hist()
    h.observe(5e7)          # beyond the last bound: overflow bucket
    assert h.counts[-1] == 1
    assert h.quantile(0.5) == 5e7   # overflow reports the observed max


def test_hist_state_and_quantiles():
    h = _Hist()
    for v in (0.01, 0.02, 0.04, 10.0):
        h.observe(v)
    st = h.state()
    assert st["count"] == 4
    assert st["sum"] == pytest.approx(10.07)
    assert st["min"] == 0.01 and st["max"] == 10.0
    # quantile returns the bucket's upper bound: an upper estimate
    assert st["p50"] >= 0.02
    assert st["p95"] == pytest.approx(10.0)   # 10.0 is a grid bound
    empty = _Hist()
    assert empty.state()["count"] == 0 and empty.state()["p50"] is None


def test_hist_quantile_bucket_edges():
    import math

    # empty: NaN for EVERY q — including q=0, where target is 0 and a
    # naive `acc >= target` would report the first grid bound
    empty = _Hist()
    for q in (0.0, 0.5, 1.0):
        assert math.isnan(empty.quantile(q))
    # single sample ON a grid bound (le semantics put it AT the bound):
    # every quantile reports exactly that bound, never the next one up
    h = _Hist()
    h.observe(1.0)                       # == BUCKET_BOUNDS[24]
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == 1.0
    # exact-boundary observation deeper in the grid behaves the same
    h = _Hist()
    h.observe(BUCKET_BOUNDS[30])
    assert h.quantile(0.5) == BUCKET_BOUNDS[30]
    # q=0 with only a LATE bucket populated: the empty prefix must not
    # satisfy the target — the answer is the min's bucket, not bound[0]
    h = _Hist()
    h.observe(10.0)
    assert h.quantile(0.0) == 10.0 != BUCKET_BOUNDS[0]
    # overflow-only series: the observed max at every quantile
    h = _Hist()
    h.observe(5e7)
    assert h.quantile(0.0) == h.quantile(1.0) == 5e7


def test_registry_quantile_accessors_edge_cases():
    import math

    reg = MetricsRegistry()
    # a never-observed series reads NaN / 0, never raises
    assert math.isnan(reg.histogram_quantile("serve.request_seconds", 0.5))
    assert reg.histogram_count("serve.request_seconds") == 0
    assert reg.counter_value("serve.requests") == 0
    # single observation at an exact bound round-trips through the
    # label-keyed accessor
    reg.histogram("serve.request_seconds", 1.0, strategy="ring")
    assert reg.histogram_quantile("serve.request_seconds", 0.5,
                                  strategy="ring") == 1.0
    assert reg.histogram_count("serve.request_seconds",
                               strategy="ring") == 1
    # label mismatch is a distinct (empty) series
    assert math.isnan(reg.histogram_quantile("serve.request_seconds", 0.5))


# -- schema validation at call time ----------------------------------------

def test_undeclared_or_miskinded_names_raise():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.counter("made.up.metric")
    with pytest.raises(TypeError):
        reg.counter("serve.request_seconds")   # declared as a histogram
    with pytest.raises(TypeError):
        reg.histogram("serve.requests", 1.0)   # declared as a counter
    with pytest.raises(KeyError):
        reg.emit("made_up_event", x=1)
    with pytest.raises(ValueError):
        reg.emit("warning", what="half")       # missing required `reason`


# -- spans -----------------------------------------------------------------

def test_span_paths_nest_and_carry_labels():
    reg = MetricsRegistry()
    with reg.span("outer"):
        with reg.span("inner", strategy="ring"):
            pass
    spans = [e for e in reg._events if e["type"] == "span"]
    assert [e["path"] for e in spans] == ["outer/inner", "outer"]
    assert spans[0]["name"] == "inner" and spans[0]["strategy"] == "ring"
    assert all(e["seconds"] >= 0 for e in spans)


# -- Prometheus exposition -------------------------------------------------

def test_prometheus_exposition_contract():
    reg = MetricsRegistry()
    reg.counter("serve.requests", 3)
    reg.gauge("train.comm_bytes_per_iter", 4096, strategy="ring")
    for v in (0.001, 0.002, 0.5, 2e7):
        reg.histogram("serve.request_seconds", v, strategy="all_gather")
    text = reg.prometheus_text()
    samples = _parse_prom(text)
    assert samples["tpu_als_serve_requests_total"] == 3
    assert samples[
        'tpu_als_train_comm_bytes_per_iter{strategy="ring"}'] == 4096
    assert "# TYPE tpu_als_serve_request_seconds histogram" in text
    buckets = [(k, v) for k, v in samples.items()
               if k.startswith("tpu_als_serve_request_seconds_bucket")]
    # cumulative over the fixed grid: 49 bounds + the +Inf bucket
    assert len(buckets) == 50
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    inf_key = [k for k, _ in buckets if 'le="+Inf"' in k]
    assert inf_key and samples[inf_key[0]] == 4   # overflow obs included
    assert samples[
        'tpu_als_serve_request_seconds_count{strategy="all_gather"}'] == 4
    assert samples[
        'tpu_als_serve_request_seconds_sum{strategy="all_gather"}'] == \
        pytest.approx(0.503 + 2e7)


# -- run-dir lifecycle -----------------------------------------------------

def test_finalize_roundtrip_and_idempotence(tmp_path):
    run = str(tmp_path / "obs")
    reg = MetricsRegistry()
    reg.configure(run, config={"cmd": "test"}, argv=["train", "--x"])
    assert reg.active()
    reg.counter("ingest.rows", 5)
    reg.gauge("train.comm_bytes_per_iter", 1234, strategy="ring")
    with reg.span("train.fit"):
        pass
    assert reg.finalize() == run
    events = _read_events(run)
    assert [e["type"] for e in events] == ["metric", "span", "snapshot"]
    snap = events[-1]
    assert snap["counters"]["ingest.rows"] == 5
    assert snap["gauges"][
        'train.comm_bytes_per_iter{strategy="ring"}'] == 1234
    with open(os.path.join(run, "run_manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["argv"] == ["train", "--x"]
    assert manifest["config"] == {"cmd": "test"}
    assert manifest["finished_at"] >= manifest["started_at"]
    assert manifest["device_count"] == 8
    samples = _parse_prom(
        open(os.path.join(run, "metrics.prom")).read())
    assert samples["tpu_als_ingest_rows_total"] == 5
    # idempotent: a second finalize appends only what happened since
    n1 = len(events)
    reg.counter("ingest.rows", 2)      # counters don't emit events
    reg.finalize()
    events = _read_events(run)
    assert len(events) == n1 + 1       # exactly the second snapshot
    assert events[-1]["counters"]["ingest.rows"] == 7
    reg.deconfigure()
    assert not reg.active()
    assert reg.finalize() is None      # detached: nothing written


# -- summarize -------------------------------------------------------------

def test_summarize_events_aggregates():
    reg = MetricsRegistry()
    with reg.span("train.fit"):
        with reg.span("train.iteration"):
            pass
        with reg.span("train.iteration"):
            pass
    reg.emit("iteration", iteration=1, seconds=0.5, total_seconds=0.5,
             probe_rmse=0.9)
    reg.gauge("train.comm_bytes_per_iter", 777, strategy="all_gather")
    reg.emit("warning", what="trace_skipped", reason="already active")
    s = report.summarize_events(reg._events)
    it_path = "train.fit/train.iteration"
    assert s["phases"][it_path]["count"] == 2
    assert s["phases"]["train.fit"]["count"] == 1
    assert s["phases"][it_path]["mean_seconds"] == pytest.approx(
        s["phases"][it_path]["total_seconds"] / 2)
    assert s["iterations"][0]["probe_rmse"] == 0.9
    assert s["gauges"][
        'train.comm_bytes_per_iter{strategy="all_gather"}'] == 777
    assert s["warnings"][0]["what"] == "trace_skipped"
    text = report.render_summary(s)
    assert "phases:" in text and it_path in text
    assert "probe_rmse" in text
    assert "warning: trace_skipped" in text


# -- instrumented paths ----------------------------------------------------

def test_checkpoint_events_and_metrics(tmp_path, rng):
    from tpu_als.io.checkpoint import load_factors, save_factors

    run = str(tmp_path / "obs")
    obs.configure(run)
    path = str(tmp_path / "ckpt")
    U = rng.normal(size=(6, 3)).astype(np.float32)
    V = rng.normal(size=(5, 3)).astype(np.float32)
    save_factors(path, np.arange(6), U, np.arange(5), V, iteration=2)
    load_factors(path)
    obs.finalize()
    events = _read_events(run)
    saves = [e for e in events if e["type"] == "checkpoint_save"]
    loads = [e for e in events if e["type"] == "checkpoint_load"]
    assert len(saves) == 1 and len(loads) == 1
    assert saves[0]["bytes"] > 0 and saves[0]["iteration"] == 2
    snap = events[-1]
    assert snap["counters"]["checkpoint.save_bytes"] == saves[0]["bytes"]
    assert snap["counters"]["checkpoint.load_bytes"] == loads[0]["bytes"]
    assert snap["histograms"]["checkpoint.save_seconds"]["count"] == 1
    assert snap["histograms"]["checkpoint.load_seconds"]["count"] == 1


def test_ingest_counters_match_file(tmp_path):
    from tpu_als.io.stream import stream_ingest

    p = tmp_path / "ratings.csv"
    p.write_text("u1,i1,3.0\nu2,i2,4.0\nu1,i2,5.0\n")
    u, i, r, ulab, ilab = stream_ingest(str(p))
    assert len(u) == 3
    snap = obs.snapshot()
    assert snap["counters"]["ingest.rows"] == 3
    assert snap["counters"]["ingest.bytes"] == os.path.getsize(p)
    evs = [e for e in obs.default_registry()._events
           if e["type"] == "ingest"]
    assert len(evs) == 1 and evs[0]["rows"] == 3


def test_estimator_gauge_matches_comm_model():
    """The train.comm_bytes_per_iter gauge must equal the estimator's
    audited comm model for every strategy (the model itself is checked
    against traced jaxprs in tests/test_comm_audit.py).  Sparse random
    layout so all_to_all does not degenerate into its fallback."""
    gen = np.random.default_rng(11)
    nU = nI = 256
    u = np.repeat(np.arange(nU), 4)
    i = np.concatenate([gen.choice(nI, 4, replace=False)
                        for _ in range(nU)])
    r = gen.normal(size=len(u)).astype(np.float32)
    frame = {"user": u, "item": i, "rating": r}
    mesh = make_mesh(8)
    for strategy in ("all_gather", "ring", "all_to_all"):
        obs.reset()
        als = ALS(rank=4, maxIter=1, regParam=0.05, seed=0, mesh=mesh,
                  gatherStrategy=strategy)
        als.fit(frame)
        assert als.lastFitStrategy == strategy, \
            "layout degenerated; the strategy under test never ran"
        key = f'train.comm_bytes_per_iter{{strategy="{strategy}"}}'
        gauges = obs.snapshot()["gauges"]
        assert key in gauges, gauges
        assert gauges[key] == als.lastFitCommBytes > 0


def test_serve_histogram_and_overhead():
    from tpu_als.parallel.serve import topk_sharded

    gen = np.random.default_rng(3)
    U = gen.normal(size=(64, 8)).astype(np.float32)
    V = gen.normal(size=(256, 8)).astype(np.float32)
    mesh = make_mesh(8)
    topk_sharded(U, V, 10, mesh)            # warmup / compile
    n, times = 5, []
    for _ in range(n):
        t0 = time.perf_counter()
        topk_sharded(U, V, 10, mesh)
        times.append(time.perf_counter() - t0)
    snap = obs.snapshot()
    h = snap["histograms"]['serve.request_seconds{strategy="all_gather"}']
    assert h["count"] == n + 1
    assert snap["counters"]["serve.requests"] == n + 1
    assert snap["counters"]["serve.rows"] == 64 * (n + 1)
    # the exposition of the live registry parses as Prometheus text
    samples = _parse_prom(obs.prometheus_text())
    assert samples[
        'tpu_als_serve_request_seconds_count{strategy="all_gather"}'] \
        == n + 1
    assert samples["tpu_als_serve_requests_total"] == n + 1
    # instrumentation overhead: the per-request registry writes (what
    # topk_sharded adds per call) must be <5% of the request itself
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        obs.histogram("serve.request_seconds", 1e-3, strategy="all_gather")
        obs.counter("serve.requests")
        obs.counter("serve.rows", 64)
    per_request_cost = (time.perf_counter() - t0) / reps
    assert per_request_cost < 0.05 * min(times), \
        (per_request_cost, min(times))


# -- IterationLogger / trace hardening -------------------------------------

def test_iteration_logger_context_manager(tmp_path):
    path = tmp_path / "log.jsonl"
    U = np.ones((4, 2), dtype=np.float32)
    V = np.ones((3, 2), dtype=np.float32)
    with IterationLogger(stream=None, path=str(path)) as logger:
        logger(1, U, V)
        logger(2, U, V)
        assert logger._file is not None
    assert logger._closed and logger._file is None
    recs = [json.loads(line) for line in open(path)]
    assert [r["iteration"] for r in recs] == [1, 2]
    # total_seconds is cumulative wall clock: monotone, >= the delta
    assert recs[1]["total_seconds"] >= recs[0]["total_seconds"]
    assert recs[1]["total_seconds"] >= recs[1]["seconds"]


def test_iteration_logger_lazy_open(tmp_path):
    path = tmp_path / "never.jsonl"
    with IterationLogger(stream=None, path=str(path)):
        pass                       # no records -> no file
    assert not path.exists()


def test_trace_degrades_to_noop_when_profiler_fails(tmp_path, monkeypatch):
    def boom(logdir):
        raise RuntimeError("profiler plugin missing")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    ran = []
    with observe.trace(str(tmp_path / "t")):
        ran.append(True)           # body still runs, nothing raises
    assert ran
    warns = [e for e in obs.default_registry()._events
             if e["type"] == "warning"]
    assert any(e["what"] == "trace_unavailable" for e in warns)
    assert observe._trace_active is False


def test_trace_nested_request_skipped(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with observe.trace(str(tmp_path / "outer")):
        with observe.trace(str(tmp_path / "inner")):
            pass
    warns = [e for e in obs.default_registry()._events
             if e["type"] == "warning"]
    assert any(e["what"] == "trace_skipped" for e in warns)
    assert observe._trace_active is False


# -- static schema checker -------------------------------------------------

def test_check_obs_schema_repo_is_clean():
    p = subprocess.run([sys.executable, CHECKER],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr + p.stdout
    assert "OK" in p.stdout


def test_check_obs_schema_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        'obs.counter("made.up.metric")\n'
        'obs.histogram("serve.requests", 1.0)\n'
        'obs.emit("made_up_event", x=1)\n'
        'obs.counter(variable_name)\n'
        'ev = {"ts": 0.0, "type": "rogue_inline_event"}\n')
    p = subprocess.run([sys.executable, CHECKER, "--paths", str(bad)],
                       capture_output=True, text=True)
    assert p.returncode == 1
    assert "made.up.metric" in p.stderr
    assert "declared as a counter" in p.stderr
    assert "made_up_event" in p.stderr
    assert "non-literal name" in p.stderr
    assert "rogue_inline_event" in p.stderr


def test_check_obs_schema_catches_accessor_and_assertion_drift(tmp_path):
    """The read-side extension: typo'd accessor names and undeclared
    Assertion(metric=/event=/den=) literals are violations; a dynamic
    accessor read (the scenario evaluator) is NOT."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        'q = reg.histogram_quantile("no.such.hist", 0.5)\n'
        'c = reg.counter_value("serve.request_seconds")\n'
        'ok = reg.histogram_quantile(a.metric, a.q)\n'
        'x = Assertion("n", "quantile", metric="not.declared", q=0.5)\n'
        'y = Assertion("n", "event", event="not_an_event")\n'
        'z = Assertion("n", "ratio", num="serving.shed",\n'
        '              den=("serving.requests", "bogus.counter"))\n'
        'w = Assertion("n", "fact", fact="anything_goes")\n')
    p = subprocess.run([sys.executable, CHECKER, "--paths", str(bad)],
                       capture_output=True, text=True)
    assert p.returncode == 1
    assert "no.such.hist" in p.stderr
    # kind mismatch through the accessor alias
    assert "used as a counter (counter_value)" in p.stderr
    assert "not.declared" in p.stderr
    assert "not_an_event" in p.stderr
    assert "bogus.counter" in p.stderr
    # the dynamic read and the fact-kind assertion are clean
    assert "a.metric" not in p.stderr
    assert "anything_goes" not in p.stderr
    assert p.stderr.count(str(bad.name)) == 5


def test_check_obs_schema_catches_fault_point_drift(tmp_path):
    """The fault-vocabulary extension: typo'd faults.check/armed/hits
    literals and unparseable scenario fault_spec strings are violations;
    a declared point and a well-formed spec are not."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        'mode = faults.check("solve.grim")\n'
        'ok = faults.armed("solve.gram")\n'
        'n = faults.hits(point_var)\n'
        'spec = ScenarioSpec(fault_spec="ingest.record=corrupt@every=5")\n'
        'bad = ScenarioSpec(fault_spec="no.such.point=raise")\n'
        'ugly = ScenarioSpec(fault_spec="solve.gram-corrupt")\n')
    p = subprocess.run([sys.executable, CHECKER, "--paths", str(bad)],
                       capture_output=True, text=True)
    assert p.returncode == 1
    assert "solve.grim" in p.stderr
    assert "non-literal point" in p.stderr
    assert "no.such.point" in p.stderr
    assert "solve.gram-corrupt" in p.stderr
    # the declared point (line 2) and well-formed spec (line 4) are clean
    assert "4 violation(s)" in p.stderr
    assert f"{bad.name}:2" not in p.stderr
    assert f"{bad.name}:4" not in p.stderr


# -- the observe CLI end to end (ISSUE acceptance) -------------------------

def test_cli_train_then_observe_summarize(tmp_path, capsys):
    out = str(tmp_path / "model")
    cli_main(["train", "--data", "synthetic:200x80x3000", "--rank", "4",
              "--max-iter", "2", "--devices", "4",
              "--gather-strategy", "ring", "--output", out])
    capsys.readouterr()                      # drop training chatter
    obs_dir = os.path.join(out, "obs")
    for name in ("events.jsonl", "metrics.prom", "run_manifest.json"):
        assert os.path.exists(os.path.join(obs_dir, name)), name

    cli_main(["observe", "summarize", out])
    text = capsys.readouterr().out
    assert "phases:" in text and "cli.train" in text
    assert "train.fit" in text and "data.load" in text
    assert "iterations:" in text and "probe_rmse" in text
    assert 'train.comm_bytes_per_iter{strategy="ring"}' in text
    assert "MB/device/iter" in text

    cli_main(["observe", "summarize", out, "--json"])
    j = json.loads(capsys.readouterr().out)
    assert j["phases"]["cli.train"]["count"] == 1
    assert len(j["iterations"]) == 2
    assert all(np.isfinite(ev["probe_rmse"]) for ev in j["iterations"])
    assert j["manifest"]["config"]["cmd"] == "train"

    cli_main(["observe", "tail", out, "-n", "5"])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    assert len(lines) == 5
    assert json.loads(lines[-1])["type"] == "snapshot"

    # the model save itself must be intact next to the run dir
    assert os.path.exists(os.path.join(out, "manifest.json"))
    # the exposition file parses
    samples = _parse_prom(
        open(os.path.join(obs_dir, "metrics.prom")).read())
    assert 'tpu_als_train_comm_bytes_per_iter{strategy="ring"}' in samples


def test_observe_tail_event_filter(tmp_path, capsys):
    run = str(tmp_path / "obs")
    reg = MetricsRegistry()
    reg.configure(run)
    for i in range(5):
        reg.emit("warning", what=f"w{i}", reason="x")
        with reg.span("noise"):
            pass
    reg.finalize()
    # filtered BEFORE the tail slice: the last 3 warnings, not whatever
    # warnings happen to sit in the last 3 raw lines
    lines = report.cmd_tail(run, n=3, event="warning").splitlines()
    assert [json.loads(ln)["what"] for ln in lines] == ["w2", "w3", "w4"]
    assert all(json.loads(ln)["type"] == "warning" for ln in lines)
    # the CLI surface
    cli_main(["observe", "tail", run, "-n", "2", "--event", "span"])
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(out) == 2
    assert all(json.loads(ln)["type"] == "span" for ln in out)
    # a type with no occurrences filters to empty output, not an error
    assert report.cmd_tail(run, n=5, event="flight_record") == ""


def test_observe_summarize_missing_dir_errors(tmp_path):
    with pytest.raises(SystemExit):
        cli_main(["observe", "summarize", str(tmp_path / "nope")])
