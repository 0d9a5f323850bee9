"""tpu_als/analysis (docs/analysis.md): the tracer-safety linter, the
jax-free obs-vocabulary engine behind scripts/check_obs_schema.py, and
the jaxpr contract registry.

The load-bearing pins, straight from the subsystem's contract:

- every rule in the catalog has a fixture (tests/fixtures_analysis/)
  that fires it and a negative that stays silent, and each bad fixture
  makes the CLI exit nonzero;
- the AST lint stage is jax-free — proven by poisoning ``jax`` the way
  test_regress.py poisons the bench gate — and finishes under 10 s on
  the full default roots;
- the merged tree lints clean against the checked-in baseline, and the
  baseline stays policy-EMPTY (findings get fixed or suppressed with a
  reason, never banked);
- the four jaxpr pins are resolvable by name from
  ``analysis.contracts`` and re-verify with unchanged verdicts;
- the defects this linter surfaced on the pre-PR tree stay fixed
  (DEFAULT_JITTER threading, the attribution twin mirror, the
  serve-bench pacing epoch, the check_obs_schema jax-free claim).
"""

import glob
import importlib.util
import inspect
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures_analysis")
LINT = os.path.join(REPO, "tpu_als", "analysis", "lint.py")
SHIM = os.path.join(REPO, "scripts", "check_obs_schema.py")


def _load_standalone(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# loaded by file path, never through the package: the same jax-free
# doorway the smoke scripts use
lint = _load_standalone("_tal_lint_under_test", LINT)


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _poisoned_env(tmp_path):
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        'raise ImportError("jax must not be imported by the lint '
        'stage")\n')
    return {**os.environ, "PYTHONPATH": str(poison)}


# -- the fixture corpus: one positive + one negative per rule --------------

RULE_CASES = [
    ("bad_parse_error.py", "parse-error"),
    ("bad_tracer_branch.py", "tracer-branch"),
    ("bad_host_side_effect.py", "host-side-effect"),
    ("bad_wallclock_rng.py", "wallclock-rng"),
    ("bad_use_after_donation.py", "use-after-donation"),
    ("bad_dtype_drift.py", "dtype-drift"),
    ("bad_numpy_on_traced.py", "numpy-on-traced"),
    ("bad_unregistered_name.py", "unregistered-name"),
    ("bad_bare_jit.py", "bare-jit"),
    ("bad_magic_jitter.py", "magic-jitter"),
    ("bad_jaxfree_import.py", "jaxfree-import"),
    ("bad_timer_brackets_span.py", "timer-brackets-span"),
    ("bad_suppression.py", "bad-suppression"),
]


def test_corpus_covers_the_whole_catalog():
    """Adding a rule without a fixture (or retiring one and leaving its
    fixture behind) fails here, keeping the corpus authoritative."""
    assert {rule for _, rule in RULE_CASES} == set(lint.RULES)
    on_disk = {os.path.basename(p)
               for p in glob.glob(_fixture("bad_*.py"))}
    assert on_disk == {fname for fname, _ in RULE_CASES}


@pytest.mark.parametrize("fname,rule", RULE_CASES)
def test_bad_fixture_fires_its_rule(fname, rule):
    findings, nfiles = lint.lint_paths([_fixture(fname)])
    assert nfiles == 1
    assert any(f.rule == rule for f in findings), \
        [(f.rule, f.msg) for f in findings]


@pytest.mark.parametrize("fname,rule", RULE_CASES)
def test_bad_fixture_exits_nonzero(fname, rule):
    p = subprocess.run(
        [sys.executable, LINT, "--paths", _fixture(fname),
         "--baseline", "none"],
        capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 1, p.stdout + p.stderr
    assert rule in p.stderr


@pytest.mark.parametrize("fname", sorted(
    os.path.basename(p) for p in glob.glob(
        os.path.join(FIXTURES, "ok_*.py"))))
def test_ok_fixture_is_finding_free(fname):
    findings, nfiles = lint.lint_paths([_fixture(fname)])
    assert nfiles == 1
    assert not findings, [(f.rule, f.line, f.msg) for f in findings]


def test_suppression_without_reason_does_not_suppress():
    """A reasonless 'tal: disable' is itself a finding AND the finding
    it aimed at survives — silence is never free."""
    findings, _ = lint.lint_paths([_fixture("bad_suppression.py")])
    rules = [f.rule for f in findings]
    assert "bad-suppression" in rules and "bare-jit" in rules


def test_suppression_with_reason_suppresses():
    findings, _ = lint.lint_paths([_fixture("ok_suppression.py")])
    assert not findings, [(f.rule, f.msg) for f in findings]


# -- baseline round-trip ---------------------------------------------------

def test_baseline_round_trip(tmp_path):
    """write-baseline -> exit 0 against it -> remove it -> exit 1; a
    fixed finding left in the baseline is reported stale, not fatal."""
    bad = _fixture("bad_magic_jitter.py")
    baseline = tmp_path / "baseline.txt"
    run = lambda *extra: subprocess.run(
        [sys.executable, LINT, "--paths", bad,
         "--baseline", str(baseline), *extra],
        capture_output=True, text=True, cwd=REPO)

    p = run("--write-baseline")
    assert p.returncode == 0 and baseline.exists(), p.stderr
    entries = [ln for ln in baseline.read_text().splitlines()
               if ln and not ln.startswith("#")]
    assert len(entries) == 1 and " :: magic-jitter :: " in entries[0]

    p = run()
    assert p.returncode == 0, p.stdout + p.stderr
    assert "1 baselined" in p.stdout

    # the baselined finding no longer exists -> stale note on stderr,
    # still exit 0 (notes nag, they don't block)
    p = subprocess.run(
        [sys.executable, LINT, "--paths", _fixture("ok_magic_jitter.py"),
         "--baseline", str(baseline)],
        capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 0
    assert "stale baseline entry" in p.stderr

    baseline.unlink()
    p = run()
    assert p.returncode == 1
    assert "magic-jitter" in p.stderr


def test_checked_in_baseline_is_empty():
    """The policy in the file's own header: findings get fixed or
    suppressed at the site with a reason, never banked."""
    with open(os.path.join(REPO, "lint_baseline.txt")) as f:
        entries = [ln for ln in f.read().splitlines()
                   if ln.strip() and not ln.startswith("#")]
    assert entries == []


# -- the repo tree: clean, fast, and jax-free ------------------------------

def test_repo_tree_lints_clean_under_10s():
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, LINT], capture_output=True,
                       text=True, cwd=REPO)
    dt = time.monotonic() - t0
    assert p.returncode == 0, p.stdout + p.stderr
    assert "tpu_als lint: OK" in p.stdout
    assert dt < 10.0, f"lint took {dt:.1f}s — the CI-gate budget is 10s"


def test_lint_stage_is_jax_free(tmp_path):
    """The AST stage must run on hosts with no accelerator stack at all
    (the test_regress.py poisoning discipline)."""
    p = subprocess.run([sys.executable, LINT], capture_output=True,
                       text=True, cwd=REPO, env=_poisoned_env(tmp_path))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "tpu_als lint: OK" in p.stdout


def test_obs_schema_shim_is_jax_free(tmp_path):
    """The pre-PR script claimed 'deliberately jax-free' while importing
    tpu_als.obs.schema through the package root (which imports jax) —
    the linter's jaxfree-import rule caught it; the shim now loads the
    engine standalone by file path.  This is fix #1 of the findings the
    linter surfaced on its own tree."""
    p = subprocess.run([sys.executable, SHIM], capture_output=True,
                       text=True, cwd=REPO, env=_poisoned_env(tmp_path))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "check_obs_schema: OK" in p.stdout


# -- the contract registry -------------------------------------------------

def test_contracts_resolvable_by_name():
    from tpu_als.analysis import contracts

    assert set(contracts.names()) == {
        "ne_audit", "fused_solve_audit", "guardrails_disarmed",
        "tracing_disarmed", "plan_cache_off", "comm_audit",
        "ring_substrate", "live_delta_index", "elastic_disarmed",
        "floor_audit"}
    for name in contracts.names():
        c = contracts.get(name)
        assert c.name == name
        assert "tests/" in c.provenance      # every pin names its owner
    with pytest.raises(KeyError, match="no contract named"):
        contracts.get("bogus")


def test_contracts_verify_with_unchanged_verdicts():
    """The acceptance pin: all four byte-level invariants still hold
    when re-verified through the registry (conftest supplies the
    8-device CPU backend comm_audit needs)."""
    from tpu_als.analysis import contracts

    results = contracts.verify_all()
    assert [r.name for r in results] == list(contracts.names())
    assert all(r.ok for r in results), \
        [(r.name, r.detail) for r in results if not r.ok]


def test_verify_all_only_subset():
    from tpu_als.analysis import contracts

    results = contracts.verify_all(only=["guardrails_disarmed"])
    assert [r.name for r in results] == ["guardrails_disarmed"]
    assert results[0].ok, results[0].detail


def test_cli_lint_contract_by_name(capsys):
    from tpu_als.cli import main as cli_main

    rc = cli_main(["lint", "--paths", _fixture("ok_magic_jitter.py"),
                   "--baseline", "none", "--contract", "ne_audit"])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "contract ne_audit: OK" in out.out
    assert "tpu_als lint --contracts: OK (1 verified)" in out.out

    rc = cli_main(["lint", "--paths", _fixture("ok_magic_jitter.py"),
                   "--baseline", "none", "--contract", "bogus"])
    out = capsys.readouterr()
    assert rc == 1
    assert "contract bogus: UNKNOWN" in out.err


def test_cli_module_doorway_propagates_exit_code():
    """`python -m tpu_als.cli` must exit with lint's return code — the
    smoke scripts' `|| fail=1` gating is dead weight otherwise.  (cli's
    __main__ shim used to drop main()'s return value on the floor.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = subprocess.run(
        [sys.executable, "-m", "tpu_als.cli", "lint", "--paths",
         _fixture("bad_bare_jit.py"), "--baseline", "none"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    ok = subprocess.run(
        [sys.executable, "-m", "tpu_als.cli", "lint", "--paths",
         _fixture("ok_bare_jit.py"), "--baseline", "none"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr


# -- the defects the linter surfaced stay fixed ----------------------------

def test_default_jitter_is_the_one_knob():
    """Fix #2 (magic-jitter, 14 sites): every solver entry point and
    AlsConfig share ops.solve.DEFAULT_JITTER — a retuned default
    propagates everywhere instead of stranding 1e-6 copies."""
    from tpu_als.core import foldin
    from tpu_als.core.als import AlsConfig
    from tpu_als.ops import solve
    from tpu_als.ops.pallas_gather_ne import gather_solve

    D = solve.DEFAULT_JITTER
    for fn in (solve.solve_spd, solve.solve_spd_checked, solve.solve_cg,
               solve.solve_cg_matfree, solve.solve_nnls,
               foldin.fold_in, foldin._fold_in_jit, gather_solve):
        assert inspect.signature(fn).parameters["jitter"].default == D, \
            getattr(fn, "__name__", fn)
    assert AlsConfig().jitter == D


def test_attribution_twin_mirrors_default_jitter():
    """Fix #2b: the attribution twin picks the prebuilt solver exactly
    when cfg.jitter matches the production default — by comparing
    against DEFAULT_JITTER, not a second 1e-6 literal that could drift
    from the real default silently."""
    from tpu_als.ops import solve
    from tpu_als.perf import attribution

    src = inspect.getsource(attribution)
    assert "DEFAULT_JITTER" in src
    assert "1e-6" not in src
    # and the linter agrees: no magic-jitter findings anywhere in the
    # subsystems the sweep fixed
    for rel in ("tpu_als/ops", "tpu_als/core", "tpu_als/perf"):
        findings, _ = lint.lint_paths([os.path.join(REPO, rel)])
        assert not [f for f in findings if f.rule == "magic-jitter"], rel
    assert solve.DEFAULT_JITTER == 1e-6


def test_serve_bench_pacing_epoch_inside_span():
    """Fix #3 (timer-brackets-span): the serve-bench drive loop's pacing
    epoch starts inside the obs.span, so the span-enter JSONL write can
    never make request 0 late against its own schedule."""
    findings, _ = lint.lint_paths([os.path.join(REPO, "tpu_als",
                                                "cli.py")])
    assert not [f for f in findings if f.rule == "timer-brackets-span"]


def test_stage_timer_suppression_is_reasoned():
    """The flip side of fix #3: obs/trace.py's stage() clock DOES
    bracket the span — deliberately, because the attribution coverage
    bound attributes all armed-path time to stages — and carries an
    in-source suppression with a reason rather than a baseline entry."""
    trace_py = os.path.join(REPO, "tpu_als", "obs", "trace.py")
    with open(trace_py) as f:
        src = f.read()
    assert "tal: disable=timer-brackets-span --" in src
    findings, _ = lint.lint_paths([trace_py])
    assert not findings, [(f.rule, f.line) for f in findings]
