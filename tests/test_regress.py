"""The bench regression gate (tpu_als/obs/regress.py + ``observe
regress`` + scripts/bench_gate.sh).

The gate is the reader the result banks never had: a round capture sat
in the repo carrying ``value: null`` for three PRs because nothing
consumed it.  These tests pin the typed exit codes on synthetic series
(regression -> 1, latest null -> 2, provenance -> 3) AND that the
committed artifacts at the repo root gate clean (exit 0) — the same
invariant scripts/bench_gate.sh enforces in the smoke gates.

Pure stdlib under test: no jax import in this module's code paths.
"""

import json
import os
import subprocess

import pytest

from tpu_als.cli import main as cli_main
from tpu_als.obs import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(d, name, doc):
    p = os.path.join(str(d), name)
    with open(p, "w") as f:
        json.dump(doc, f)
    return p


def _round(n, value, unit="iters/sec", **extra):
    return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
            "parsed": {"metric": "m", "value": value, "unit": unit,
                       **extra}}


# -- the committed artifacts (the acceptance bar) --------------------------

def test_committed_banks_gate_clean():
    result = regress.check(REPO)
    assert result["exit_code"] == regress.EXIT_OK
    # the gate actually read the committed banks, not an empty glob
    # (the round wrappers BENCH_r01-r05 / MULTICHIP_r01 went with the
    # capture pipeline that wrote them; MULTICHIP_r02-r05 are the round
    # series that remains)
    assert "BENCH_serve_cpu.json" in result["checked"]
    assert "BENCH_autotune_cpu.json" in result["checked"]
    assert "MULTICHIP_r05.json" in result["checked"]
    assert "MULTICHIP" in result["series"]
    assert all(f["severity"] != "error" for f in result["findings"])


def test_bench_gate_script_passes_exit_code_through(tmp_path):
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "bench_gate.sh")],
        capture_output=True, text=True)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "verdict: OK" in p.stdout
    # and a failing root propagates its typed code through the script
    _write(tmp_path, "BENCH_broken.json",
           {"metric": "m", "value": None, "unit": "ms",
            "banked_at": "2026-08-01T00:00:00+00:00"})
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "bench_gate.sh"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert p.returncode == regress.EXIT_NULL_BANK, p.stdout + p.stderr


# -- synthetic series: the typed failure modes -----------------------------

def test_regression_beyond_noise_band_exits_1(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _round(1, 1.00))
    _write(tmp_path, "BENCH_r02.json", _round(2, 0.98))   # within noise
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_OK
    _write(tmp_path, "BENCH_r03.json", _round(3, 0.80))   # -20% throughput
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_REGRESSION
    msg = [f for f in result["findings"] if f["severity"] == "error"]
    assert len(msg) == 1 and "noise band" in msg[0]["message"]
    # a wider band absorbs it
    assert regress.check(str(tmp_path), noise=0.30)["exit_code"] == 0


def test_unit_direction_lower_better(tmp_path):
    # ms series: the LARGER latest value is the regression
    _write(tmp_path, "BENCH_r01.json", _round(1, 30.0, unit="ms"))
    _write(tmp_path, "BENCH_r02.json", _round(2, 45.0, unit="ms"))
    assert regress.check(str(tmp_path))["exit_code"] == \
        regress.EXIT_REGRESSION
    # improving latency is not a regression
    _write(tmp_path, "BENCH_r02.json", _round(2, 20.0, unit="ms"))
    assert regress.check(str(tmp_path))["exit_code"] == regress.EXIT_OK


def test_latest_null_exits_2_historical_null_warns(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _round(1, 1.0))
    _write(tmp_path, "BENCH_r02.json", _round(2, None))
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_NULL_BANK
    # a later measured round demotes the null to a historical warning
    _write(tmp_path, "BENCH_r03.json", _round(3, 1.02))
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_OK
    assert any("[historical]" in f["message"] for f in result["findings"])
    # --strict upgrades the historical null back to an error
    assert regress.check(str(tmp_path), strict=True)["exit_code"] == \
        regress.EXIT_NULL_BANK


def test_null_round_with_sweep_fallback_counts_as_measured(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _round(1, 1.0))
    doc = _round(2, None)
    doc["parsed"]["last_builder_measured"] = {"value": 0.99,
                                              "unit": "iters/sec"}
    _write(tmp_path, "BENCH_r02.json", doc)
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_OK
    assert any("sweep fallback" in f["message"]
               for f in result["findings"])


def test_direct_bank_provenance_exits_3(tmp_path):
    bank = {"metric": "serve_e2e_p99_ms", "value": 31.6, "unit": "ms"}
    _write(tmp_path, "BENCH_serve.json", bank)        # no banked_at
    assert regress.check(str(tmp_path))["exit_code"] == \
        regress.EXIT_PROVENANCE
    bank["banked_at"] = "2026-08-05T11:14:02"         # tz-naive
    _write(tmp_path, "BENCH_serve.json", bank)
    assert regress.check(str(tmp_path))["exit_code"] == \
        regress.EXIT_PROVENANCE
    bank["banked_at"] = "2026-08-05T11:14:02+00:00"
    _write(tmp_path, "BENCH_serve.json", bank)
    assert regress.check(str(tmp_path))["exit_code"] == regress.EXIT_OK


def test_multichip_latest_failure_exits_1(tmp_path):
    _write(tmp_path, "MULTICHIP_r01.json",
           {"n_devices": 4, "rc": 0, "ok": True, "skipped": False})
    _write(tmp_path, "MULTICHIP_r02.json",
           {"n_devices": 4, "rc": 124, "ok": False, "skipped": False})
    assert regress.check(str(tmp_path))["exit_code"] == \
        regress.EXIT_REGRESSION
    # skipped rounds never judge the series
    _write(tmp_path, "MULTICHIP_r03.json",
           {"n_devices": 4, "rc": 0, "ok": False, "skipped": True})
    _write(tmp_path, "MULTICHIP_r02.json",
           {"n_devices": 4, "rc": 0, "ok": True, "skipped": False})
    assert regress.check(str(tmp_path))["exit_code"] == regress.EXIT_OK


def test_unreadable_and_unknown_shapes(tmp_path):
    with open(os.path.join(str(tmp_path), "BENCH_r01.json"), "w") as f:
        f.write("{not json")
    result = regress.check(str(tmp_path))
    assert result["exit_code"] == regress.EXIT_NULL_BANK
    assert "unreadable" in result["findings"][0]["message"]
    _write(tmp_path, "BENCH_weird.json", {"something": "else"})
    result = regress.check(str(tmp_path), files=[
        os.path.join(str(tmp_path), "BENCH_weird.json")])
    assert result["exit_code"] == regress.EXIT_OK
    assert "unrecognized" in result["findings"][0]["message"]


def test_render_carries_verdict(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _round(1, 1.0))
    _write(tmp_path, "BENCH_r02.json", _round(2, 0.5))
    text = regress.render(regress.check(str(tmp_path)))
    assert "verdict: REGRESSION (exit 1)" in text
    text = regress.render(regress.check(str(tmp_path), noise=2.0))
    assert "verdict: OK (exit 0)" in text


# -- the CLI surface -------------------------------------------------------

def test_cli_observe_regress_exit_codes(tmp_path, capsys):
    _write(tmp_path, "BENCH_r01.json", _round(1, 1.0))
    _write(tmp_path, "BENCH_r02.json", _round(2, 0.5))
    with pytest.raises(SystemExit) as e:
        cli_main(["observe", "regress", str(tmp_path)])
    assert e.value.code == regress.EXIT_REGRESSION
    capsys.readouterr()
    # clean root returns (no SystemExit) and prints the OK verdict
    cli_main(["observe", "regress", str(tmp_path), "--noise", "2.0"])
    assert "verdict: OK" in capsys.readouterr().out
    # --json emits the machine-readable result
    cli_main(["observe", "regress", str(tmp_path), "--noise", "2.0",
              "--json"])
    j = json.loads(capsys.readouterr().out)
    assert j["exit_code"] == 0 and j["noise"] == 2.0


def test_bench_gate_is_jax_free(tmp_path):
    """The gate must run on hosts with no accelerator stack at all —
    bench_gate.sh loads regress.py standalone (the full CLI surface,
    which imports the package and thus jax, is the convenience path)."""
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        'raise ImportError("jax must not be imported by the bench gate")\n')
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "bench_gate.sh")],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(poison)})
    assert p.returncode == 0, p.stdout + p.stderr
    assert "verdict: OK" in p.stdout


# -- the trend-aware gate (--trend) ----------------------------------------


_MASKED_SLIDE = [10.0, 9.2, 8.6, 8.0, 9.2]
# latest 9.2 vs best prior 10.0 is -8%: INSIDE the 10% band, so the
# plain latest-vs-best gate passes — but the least-squares fit over all
# five rounds loses ~11% of its starting value: the masking case the
# trend gate exists for


def _series(d, vals, unit="iters/sec", name="BENCH"):
    for n, v in enumerate(vals, 1):
        _write(d, f"{name}_r{n:02d}.json", _round(n, v, unit=unit))


def test_trend_catches_masked_regression(tmp_path):
    _series(tmp_path, _MASKED_SLIDE)
    assert regress.check(str(tmp_path))["exit_code"] == regress.EXIT_OK
    result = regress.check(str(tmp_path), trend=True)
    assert result["exit_code"] == regress.EXIT_REGRESSION
    assert any("trend" in f["message"] and "falling" in f["message"]
               for f in result["findings"])


def test_trend_clean_on_stable_series(tmp_path):
    _series(tmp_path, [10.0, 10.2, 9.9, 10.1, 10.0])
    assert regress.check(str(tmp_path), trend=True)["exit_code"] == \
        regress.EXIT_OK


def test_trend_direction_aware(tmp_path):
    # an IMPROVING series drifts steeply but in the better direction
    _series(tmp_path, [8.0, 8.6, 9.2, 10.0])
    assert regress.check(str(tmp_path), trend=True)["exit_code"] == \
        regress.EXIT_OK
    # lower-better unit: the same RISING values are now a regression
    _series(tmp_path, [8.0, 8.6, 9.2, 10.0], unit="ms",
            name="BENCH_lat")
    result = regress.check(str(tmp_path), trend=True)
    assert result["exit_code"] == regress.EXIT_REGRESSION
    assert any("rising" in f["message"] for f in result["findings"])


def test_trend_needs_three_points(tmp_path):
    # a 2-point slide is latest-vs-best territory; the trend fit stays
    # quiet (this also keeps the committed 2-point BENCH_r history
    # trend-clean at the repo root)
    _series(tmp_path, [10.0, 8.9])
    result = regress.check(str(tmp_path), trend=True)
    assert not any("trend" in f["message"] for f in result["findings"])


def test_trend_window_bounds_the_fit(tmp_path):
    # ancient history outside the window must not drag the fit: the
    # last 3 rounds are flat, the slide is 5 rounds old (the plain
    # latest-vs-best finding fires either way — judge the TREND
    # findings specifically)
    _series(tmp_path, [14.0, 12.0, 10.0, 10.0, 10.0, 10.0])

    def trend_findings(window):
        result = regress.check(str(tmp_path), trend=True,
                               trend_window=window)
        return [f for f in result["findings"] if "trend" in f["message"]]

    assert not trend_findings(3)
    assert trend_findings(6)


def test_committed_banks_gate_clean_with_trend():
    # scripts/bench_gate.sh now runs with trend ON by default — the
    # committed history must hold under the stronger gate
    result = regress.check(REPO, trend=True)
    assert result["exit_code"] == regress.EXIT_OK
    assert result["trend"] is True


def test_trend_cli_flag(tmp_path, capsys):
    _series(tmp_path, _MASKED_SLIDE)
    cli_main(["observe", "regress", str(tmp_path)])
    capsys.readouterr()
    with pytest.raises(SystemExit) as e:
        cli_main(["observe", "regress", str(tmp_path), "--trend"])
    assert e.value.code == regress.EXIT_REGRESSION
    out = capsys.readouterr().out
    assert "trend window 5" in out


def test_bench_gate_script_no_trend_flag(tmp_path):
    # the script gates with trend by default; --no-trend restores the
    # plain latest-vs-best behaviour
    _series(tmp_path, _MASKED_SLIDE)
    gate = os.path.join(REPO, "scripts", "bench_gate.sh")
    p = subprocess.run(["bash", gate, str(tmp_path)],
                       capture_output=True, text=True)
    assert p.returncode == regress.EXIT_REGRESSION, p.stdout + p.stderr
    p = subprocess.run(["bash", gate, str(tmp_path), "--no-trend"],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stdout + p.stderr
