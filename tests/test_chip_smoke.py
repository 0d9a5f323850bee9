"""Rehearsal of chip_smoke.py without the chip: the script's phases are
plain functions that take their sizes as arguments, so they run here at
tiny size on the CPU and must pass their own reference checks.  Steering
happens in the test — the script has no CPU mode and no option that gives
it one, which the first test pins."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from tpu_als.obs import compiles  # noqa: E402

TINY = dict(num_users=400, num_items=150, num_ratings=30_000)
RANK = 16


def test_without_a_tpu_the_script_exits_nonzero_at_the_device_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    # no phase line, and above all no result line
    assert p.stdout.strip() == ""


def lines_of(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


@pytest.fixture(scope="module")
def clock():
    return compiles.install()


@pytest.fixture(scope="module")
def frame():
    return chip_smoke.data_phase(seed=0, **TINY)


@pytest.fixture(scope="module")
def trained(frame, clock):
    return chip_smoke.train_phase(frame, rank=RANK, max_iter=3, seed=0,
                                  n_check_users=32, clock=clock)


def test_data_phase_says_which_bucketizer_ran(capsys):
    chip_smoke.data_phase(seed=1, **TINY)
    (line,) = lines_of(capsys)
    assert line["phase"] == "data" and line["ratings"] == 30_000
    assert line["bucketizer"].split()[0] in ("native", "numpy")
    assert 1.0 <= line["user_padded_over_nnz"] < 2.5


def test_train_phase_fits_through_the_estimator_and_matches_float64(
        frame, clock, capsys):
    model, resolved, check = chip_smoke.train_phase(
        frame, rank=RANK, max_iter=3, seed=0, n_check_users=32,
        clock=clock)
    (line,) = lines_of(capsys)
    assert check["default_precision_median_row_err"] \
        == line["default_precision_median_row_err"]
    assert line["matmul_precision"] == "default"
    assert line["entry"] == "tpu_als.ALS.fit"
    assert line["resolved_solve_path"] == resolved["resolved_solve_path"]
    assert len(line["iteration_s"]) == 2          # maxIter - 1 steady ones
    assert line["highest_precision_max_row_err"] <= chip_smoke.SOLVE_RTOL
    # on the CPU the default matmul precision IS full f32
    assert line["default_precision_max_row_err"] <= chip_smoke.SOLVE_RTOL
    assert set(line["fit_compile"]) == set(compiles.TOTALS)
    assert line["fit_compile"]["programs"] > 0
    assert model._U.shape[1] == RANK and np.isfinite(model._U).all()


def test_a_check_that_does_not_hold_stops_the_phase(frame, clock,
                                                    monkeypatch):
    monkeypatch.setattr(chip_smoke, "SOLVE_RTOL", 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="float64 reference"):
        chip_smoke.train_phase(frame, rank=RANK, max_iter=1, seed=0,
                               n_check_users=8, clock=clock)


def test_serve_phase_answers_by_id_and_by_vector(trained, capsys):
    model, _, _ = trained
    capsys.readouterr()
    backend = chip_smoke.serve_phase(model, k=10, n_id_requests=24,
                                     n_vector_requests=8, seed=0)
    (line,) = lines_of(capsys)
    assert backend == "xla"                       # off-TPU verdict
    assert line["engine_vs_reference"]["rows"] == 32
    assert line["engine_vs_reference"]["recall"] >= 0.99
    assert line["subset_vs_reference"]["rows"] == 24


def test_foldin_phase_matches_the_ridge_solve_and_zeroes_a_cold_row(
        trained, capsys):
    model, _, _ = trained
    capsys.readouterr()
    chip_smoke.foldin_phase(model, n_new=8, width=32, seed=0)
    (line,) = lines_of(capsys)
    assert line["cold_row_is_zero"] is True
    assert line["highest_precision_max_row_err"] <= chip_smoke.SOLVE_RTOL


def test_kernels_phase_reports_interpreted_pallas_calls(rng):
    """The check exists to catch a path that ran the Pallas interpreter:
    make one such call under the log and see it named."""
    import jax.numpy as jnp

    from tpu_als.ops.pallas_lanes import spd_solve_lanes

    M = rng.normal(size=(3, 8, 8)).astype(np.float32)
    A = jnp.asarray(M @ M.transpose(0, 2, 1) + np.eye(8, dtype=np.float32))
    with chip_smoke.PallasCallLog() as log:
        log.phase = "train"
        spd_solve_lanes(A, jnp.ones((3, 8)), interpret=True)
    assert log.calls == [{"phase": "train", "kernel": "_chol_lanes_kernel",
                          "interpret": True}]
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="interpret mode.*_chol_lanes_kernel"):
        chip_smoke.kernels_phase(log, train_path="einsum+pallas_lanes",
                                 topk_backend="pallas",
                                 foldin_backend="lanes")


def test_kernels_phase_refuses_a_path_without_a_pallas_kernel():
    log = chip_smoke.PallasCallLog()
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="train path resolved to "
                             "'einsum\\+xla_cholesky'"):
        chip_smoke.kernels_phase(log, train_path="einsum+xla_cholesky",
                                 topk_backend="pallas",
                                 foldin_backend="lanes")
    # a path that names a kernel nobody traced is caught as well
    log.calls = [{"phase": "train", "kernel": "_chol_lanes_kernel",
                  "interpret": False}]
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="serve path 'pallas' never traced"):
        chip_smoke.kernels_phase(log, train_path="einsum+pallas_lanes",
                                 topk_backend="pallas",
                                 foldin_backend="lanes")


def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices "
                    "(--xla_force_host_platform_device_count)")
    return jax.devices()


def test_sharded_phase_on_four_virtual_devices(frame, clock, capsys):
    """The --chips 4 path on the CPU backend's virtual devices: the
    sharded fit vs the one-device fit from the same seed, as users run it
    and at Precision.HIGHEST (the pair that is held to the bounds),
    topk_sharded vs the one-device top-k, bytes per device printed."""
    devices = four_devices()
    capsys.readouterr()
    chip_smoke.sharded_phase(frame, chips=4, rank=RANK, max_iter=2, seed=0,
                             k=10, n_queries=64, clock=clock)
    lines = lines_of(capsys)
    assert [ln["phase"] for ln in lines] == ["train", "memory"] * 4 \
        + ["sharded"]
    assert [ln["mesh_devices"] for ln in lines[0:8:2]] == [4, None, 4, None]
    assert [ln["matmul_precision"] for ln in lines[0:8:2]] \
        == ["default", "default", "highest", "highest"]
    assert len(lines[1]["devices"]) == len(devices)
    last = lines[-1]
    assert last["topk_degraded"] is False
    held = last["factors_vs_one_device_at_highest"]
    assert held["user"]["p99"] <= chip_smoke.SHARDED_P99
    assert held["user"]["rows_over_row_bound"] == 0
    assert len(held["user"]["worst_rows"]) == chip_smoke.N_WORST
    assert "user" in last["factors_vs_one_device_at_default_not_checked"]
    assert "reference" not in lines[0]   # this path runs no other phase


def test_sharded_phase_catches_a_row_that_lost_ratings(frame, clock,
                                                       monkeypatch):
    """What the HIGHEST pair is there for: ONE ordinary user of the
    sharded fit solved from half of their ratings — far inside the old
    median/p99-only bounds — is a distance no accumulation order
    explains, and the phase stops on it."""
    import tpu_als

    four_devices()
    real = chip_smoke.train_phase
    u_idx, i_idx = chip_smoke.dense_ids(frame)
    degree = np.bincount(u_idx)
    victim = int(np.argsort(degree)[len(degree) // 2])
    (cols,), (vals,) = chip_smoke.sampled_ratings(
        frame, u_idx, i_idx, np.array([victim]))
    cfg = tpu_als.ALS(rank=RANK, implicitPrefs=True, alpha=chip_smoke.ALPHA,
                      regParam=chip_smoke.REG)._config()

    def faulty(frame, **kw):
        model, resolved, check = real(frame, **kw)
        if kw.get("mesh") is not None and kw.get("precision") == "highest":
            model._U[victim] = chip_smoke.user_half_step(
                model._V, [cols[::2]], [vals[::2]], cfg)[0]
        return model, resolved, check

    monkeypatch.setattr(chip_smoke, "train_phase", faulty)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="1 sharded user rows are further from the "
                             f"one-device fit.*'row': {victim},"):
        chip_smoke.sharded_phase(frame, chips=4, rank=RANK, max_iter=2,
                                 seed=0, k=10, n_queries=64, clock=clock)
