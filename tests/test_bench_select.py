"""bench.py's sweep-evidence auto-selection: the driver's end-of-round
capture must pick the fastest VALIDATED configuration the opportunistic
sweep measured, and never an unvalidated one."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def _write(d, name, payload):
    with open(os.path.join(d, name + ".out"), "w") as f:
        f.write("some stderr-ish line\n")
        f.write(json.dumps(payload) + "\n")


def test_no_evidence_keeps_defaults(tmp_path):
    assert bench.best_measured_flags(str(tmp_path)) is None


def test_fastest_validated_wins(tmp_path):
    d = str(tmp_path)
    _write(d, "headline_f32", {"value": 0.75, "unit": "iters/sec"})
    _write(d, "headline_cg2", {"value": 2.4, "unit": "iters/sec"})
    _write(d, "headline_bf16", {"value": 0.9, "unit": "iters/sec"})
    _write(d, "rmse_cg2", {"value": 0.44, "unit": "rmse_stars"})
    assert bench.best_measured_flags(d) == {"cg_iters": 2}


def test_cg_winner_requires_quality_evidence(tmp_path):
    d = str(tmp_path)
    _write(d, "headline_f32", {"value": 0.75})
    _write(d, "headline_cg2", {"value": 2.4})
    # no rmse_cg2 at all -> keep defaults
    assert bench.best_measured_flags(d) is None
    # quality evidence exists but fails the gate -> keep defaults
    _write(d, "rmse_cg2", {"value": 0.9})
    assert bench.best_measured_flags(d) is None
    # passing quality unlocks the cg winner
    _write(d, "rmse_cg2", {"value": 0.43})
    assert bench.best_measured_flags(d) == {"cg_iters": 2}


def test_error_steps_are_ignored(tmp_path):
    d = str(tmp_path)
    _write(d, "headline_cg2", {"value": None, "error": "run died"})
    _write(d, "headline_f32", {"value": 0.7})
    assert bench.best_measured_flags(d) == {}


def test_quality_neutral_winner_needs_no_gate(tmp_path):
    # wg15 changes padding only (masked rows) — numerics-identical, so
    # it is selectable without extra quality evidence
    d = str(tmp_path)
    _write(d, "headline_wg15", {"value": 1.1})
    assert bench.best_measured_flags(d) == {"width_growth": 1.5}


def test_configs_without_quality_evidence_never_selected(tmp_path):
    # a speed win without its matching quality step must NOT auto-select;
    # cg3/cg2_dense have no step at all and are never eligible
    d = str(tmp_path)
    _write(d, "headline_bf16_wg15", {"value": 9.9})
    _write(d, "headline_cg2_bf16", {"value": 9.8})
    _write(d, "headline_cg3", {"value": 9.9})
    _write(d, "headline_f32", {"value": 0.7})
    # the fastest eligible config lacks its quality step -> defaults
    # (no silent demotion to a slower validated one)
    assert bench.best_measured_flags(d) is None


def test_per_config_quality_steps_unlock_their_winner(tmp_path):
    d = str(tmp_path)
    _write(d, "headline_cg2_bf16", {"value": 9.8})
    _write(d, "headline_cg2", {"value": 2.4})
    _write(d, "rmse_cg2", {"value": 0.43})
    # the faster cg2_bf16 lacks ITS quality step -> whole selection
    # falls back to defaults (the winner is unvalidated, and silently
    # demoting to a slower validated config would misattribute)
    assert bench.best_measured_flags(d) is None
    _write(d, "rmse_cg2_bf16", {"value": 0.45})
    assert bench.best_measured_flags(d) == {
        "cg_iters": 2, "compute_dtype": "bfloat16"}


def test_ml100k_mode_registered():
    # BASELINE config-1 row: the mode must exist in the CLI surface
    import subprocess

    p = subprocess.run(
        [sys.executable, "bench.py", "--mode", "nonsense"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "ml100k" in p.stderr  # argparse lists valid choices


def _args(**kw):
    import argparse

    d = dict(ab="", ab_dir="", small=False)
    d.update(kw)
    return argparse.Namespace(**d)


def test_ab_specs_parse_known_and_reject_unknown():
    assert bench._ab_specs(_args()) == []
    specs = bench._ab_specs(_args(ab="exact,cg2,cg2_bf16"))
    assert [s for s, _ in specs] == ["exact", "cg2", "cg2_bf16"]
    assert specs[0][1] == {}
    assert specs[1][1] == {"cg_iters": 2}
    assert specs[2][1] == {"cg_iters": 2, "compute_dtype": "bfloat16"}
    try:
        bench._ab_specs(_args(ab="warp9"))
    except SystemExit:
        pass
    else:
        raise AssertionError("unknown spec must be rejected")


def test_ab_banks_into_canonical_logs(tmp_path):
    # the file the combined A/B writes is EXACTLY the file auto-selection
    # reads for that config — a variant banked by --ab is equivalent
    # evidence to a dedicated sweep step run
    res = {"value": 0.9, "unit": "iters/sec", "config": {}}
    bench._bank_variant("headline", "cg2", str(tmp_path), res, "m")
    assert bench._last_json(
        str(tmp_path / "headline_cg2.out"))["value"] == 0.9
    bench._bank_variant("rmse", "cg2", str(tmp_path),
                        {"value": 0.44, "config": {}}, "m")
    assert bench._last_json(str(tmp_path / "rmse_cg2.out"))["value"] == 0.44
    # exact maps to the canonical step names
    bench._bank_variant("headline", "exact", str(tmp_path), res, "m")
    assert bench._last_json(str(tmp_path / "headline_f32.out"))
    bench._bank_variant("rmse", "exact", str(tmp_path),
                        {"value": 0.43, "config": {}}, "m")
    assert bench._last_json(str(tmp_path / "rmse.out"))


def test_ab_never_banks_small_or_error_runs(tmp_path):
    bench._bank_variant("headline", "cg2", str(tmp_path),
                        {"value": 0.9, "config": {}}, "m", small=True)
    bench._bank_variant("headline", "cg3", str(tmp_path),
                        {"value": None, "config": {}}, "m")
    assert not (tmp_path / "headline_cg2.out").exists()
    assert not (tmp_path / "headline_cg3.out").exists()


def test_ab_banked_evidence_drives_auto_selection(tmp_path):
    # end-to-end contract: one combined A/B run's banked files are enough
    # for best_measured_flags to pick the validated winner
    _write(tmp_path, "headline_f32", {"value": 0.85})
    _write(tmp_path, "headline_cg2", {"value": 2.1, "banked_by":
                                      "headline --ab"})
    _write(tmp_path, "rmse_cg2", {"value": 0.44, "banked_by": "rmse --ab"})
    assert bench.best_measured_flags(str(tmp_path)) == {"cg_iters": 2}


def test_ab_retry_skips_banked_and_flags_partial_failure(tmp_path):
    import argparse

    # prior evidence: cg2 banked by an earlier (partial) A/B run
    _write(tmp_path, "headline_cg2", {"value": 2.0, "metric": "m",
                                      "banked_by": "headline --ab",
                                      "config": {"seconds_per_iter": 0.5}})
    calls = []

    def measure(overrides):
        calls.append(dict(overrides))
        if overrides.get("cg_iters") == 3:
            raise RuntimeError("device lost")
        return {"value": 1.0, "unit": "u",
                "config": {"seconds_per_iter": 1.0}}

    args = argparse.Namespace(ab="", ab_dir=str(tmp_path), small=False)
    specs = [("cg2", {"cg_iters": 2}), ("exact", {}),
             ("cg3", {"cg_iters": 3})]
    res = bench._run_ab(specs, measure, "headline", "m", args,
                        "seconds_per_iter")
    # cg2 skipped (banked), exact measured, cg3 failed -> error surfaces
    assert calls == [{}, {"cg_iters": 3}]
    assert res["config"]["ab"]["cg2"]["banked"] == "prior run"
    assert "cg3" in res["error"]
    # a --small line in the canonical log is NOT prior evidence
    _write(tmp_path, "headline_bf16", {"value": 9.9, "metric": "m_small",
                                       "banked_by": "headline --ab"})
    assert bench._already_banked("headline", "bf16", str(tmp_path)) is None


def test_ab_banking_requires_canonical_base_flags():
    import argparse

    args = argparse.Namespace(ab="cg2", ab_dir="sweep_logs", small=False,
                              cg_iters=0, cg_mode="matfree",
                              compute_dtype="bfloat16", width_growth=2.0,
                              solve_backend="auto", rank=128, iters=5,
                              iters_rmse=12, reg=0.02)
    try:
        bench._check_ab_bankable(args, "headline")
    except SystemExit as e:
        assert "compute_dtype" in str(e)
    else:
        raise AssertionError("off-default base flag must refuse banking")
    args.compute_dtype = "float32"
    bench._check_ab_bankable(args, "headline")   # canonical flags pass
    args.ab_dir = ""
    args.cg_iters = 2
    bench._check_ab_bankable(args, "headline")   # no banking -> no check


def test_ab_banking_guards_model_and_scale_flags():
    """A rank-64 or short-iteration run banked under a canonical name
    would read downstream as full-scale rank-128 evidence (advisor r4,
    medium): every model/scale flag the name doesn't encode must sit at
    the sweep's canonical value."""
    import argparse

    def mk(**kw):
        base = dict(ab="cg2", ab_dir="d", small=False, cg_iters=0,
                    cg_mode="matfree", compute_dtype="float32",
                    width_growth=2.0, solve_backend="auto", rank=128,
                    iters=5, iters_rmse=12, reg=0.02)
        base.update(kw)
        return argparse.Namespace(**base)

    for mode, bad in [("headline", {"rank": 64}),
                      ("headline", {"iters": 3}),
                      ("rmse", {"rank": 64}),
                      ("rmse", {"iters_rmse": 8}),
                      ("rmse", {"reg": 0.1})]:
        try:
            bench._check_ab_bankable(mk(**bad), mode)
        except SystemExit as e:
            (key,) = bad
            assert key in str(e)
        else:
            raise AssertionError(f"{mode} {bad} must refuse banking")
    # iters is headline-only: an rmse run may carry any --iters value
    bench._check_ab_bankable(mk(iters=3), "rmse")


def test_bank_variant_stamps_absolute_banked_at(tmp_path):
    bench._bank_variant("headline", "cg2", str(tmp_path),
                        {"value": 0.9, "config": {}}, "m")
    line = bench._last_json(str(tmp_path / "headline_cg2.out"))
    banked_at = line["banked_at"]
    # absolute ISO-8601 UTC instant, never a relative phrase
    import datetime as dt

    parsed = dt.datetime.fromisoformat(banked_at)
    assert parsed.tzinfo is not None
    assert "round" not in banked_at and "sweep" not in banked_at


def test_already_banked_rejects_config_mismatch(tmp_path):
    """A stale or mislabeled banked line (wrong rank or non-ML-25M
    shape) must not short-circuit a real retry (advisor r4, low)."""
    full = {"rank": 128, "users": 162541, "items": 59047}
    _write(tmp_path, "headline_cg2",
           {"value": 2.0, "metric": "m", "config": {**full, "rank": 64}})
    assert bench._already_banked("headline", "cg2", str(tmp_path)) is None
    _write(tmp_path, "headline_cg2",
           {"value": 2.0, "metric": "m",
            "config": {**full, "users": 6501, "items": 2361}})
    assert bench._already_banked("headline", "cg2", str(tmp_path)) is None
    _write(tmp_path, "headline_cg2",
           {"value": 2.0, "metric": "m", "config": full})
    got = bench._already_banked("headline", "cg2", str(tmp_path))
    assert got is not None and got["value"] == 2.0
    # a legacy line with no config fields cannot contradict -> accepted
    _write(tmp_path, "headline_cg3", {"value": 3.0, "metric": "m"})
    assert bench._already_banked(
        "headline", "cg3", str(tmp_path))["value"] == 3.0
    # rmse mode additionally pins its iteration count and reg: a short
    # 8-iter (or off-reg) line must not stand in for the 12-iter gate
    rcfg = {"rank": 128, "users": 162541, "items": 59047,
            "iters": 12, "reg_param": 0.02}
    for bad in ({"iters": 8}, {"reg_param": 0.1}):
        _write(tmp_path, "rmse_cg2",
               {"value": 0.44, "metric": "m", "config": {**rcfg, **bad}})
        assert bench._already_banked("rmse", "cg2", str(tmp_path)) is None
    _write(tmp_path, "rmse_cg2",
           {"value": 0.44, "metric": "m", "config": rcfg})
    assert bench._already_banked(
        "rmse", "cg2", str(tmp_path))["value"] == 0.44


def test_without_a_tpu_bench_exits_nonzero_and_prints_no_value():
    """No chip and no ``--platform cpu``: a non-zero exit, nothing on
    stdout — above all no ``value`` copied from an earlier run."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "bench.py", "--small"], capture_output=True,
        text=True, cwd=root, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "measures a TPU" in p.stderr and "value" not in p.stdout


def test_mfu_is_rated_against_the_device_that_is_there(monkeypatch):
    """One peaks table keyed by device_kind; an unknown accelerator is an
    error, a CPU smoke run gets no device metric at all."""
    import jax
    import pytest

    from tpu_als.perf.roofline import device_peaks

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert bench.mfu_pct_vs_bf16_peak(19.7e12) == 10.0
    assert bench.mfu_pct_vs_bf16_peak(19.7e12, n_chips=4) == 2.5
    assert bench.device_info() == {"platform": "tpu",
                                   "device_kind": "TPU v5 lite", "count": 1}
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="no published peaks"):
        bench.mfu_pct_vs_bf16_peak(1e12)
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert bench.mfu_pct_vs_bf16_peak(1e12) is None
    assert "source" in device_peaks("TPU v5 lite")
