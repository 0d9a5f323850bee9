"""Batch-in-lanes Pallas SPD solver vs dense reference (interpret mode on
the CPU test mesh; tests/test_chip_compile.py compiles the same kernel for
a described v5e)."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from tpu_als.ops.pallas_lanes import (
    LANES,
    available,
    spd_solve_lanes,
    supported_rank,
)
from tpu_als.ops.solve import solve_spd


def _spd_problem(rng, N, r, scale=1.0):
    M = rng.normal(size=(N, r, r)).astype(np.float32) * scale
    A = M @ M.transpose(0, 2, 1) + 0.5 * np.eye(r, dtype=np.float32)
    b = rng.normal(size=(N, r)).astype(np.float32)
    return jnp.asarray(A), jnp.asarray(b)


@pytest.mark.parametrize("N,r", [
    (5, 4),           # tiny everything, heavy batch padding
    (37, 10),         # the ALS default rank
    (LANES, 32),      # exactly one lane group
    (LANES + 9, 64),  # two groups, second mostly padding
    (40, 128),        # the benchmark rank
])
def test_matches_dense_solve(rng, N, r):
    A, b = _spd_problem(rng, N, r)
    x = np.asarray(spd_solve_lanes(A, b, interpret=True))
    ref = np.stack([np.linalg.solve(np.asarray(A)[k], np.asarray(b)[k])
                    for k in range(N)])
    denom = max(1.0, np.abs(ref).max())
    assert np.abs(x - ref).max() / denom < 5e-3


@pytest.mark.parametrize("panel", [1, 4, 8, 16])
def test_panel_widths_agree(rng, panel):
    # the panelized trailing update must reproduce the rank-1 recurrence
    # (same math, different blocking) at the benchmark rank
    N, r = LANES + 8, 128
    A, b = _spd_problem(rng, N, r, scale=1.0 / np.sqrt(r))
    x = np.asarray(spd_solve_lanes(A, b, panel=panel, interpret=True))
    ref = solve_spd(A, b, jnp.ones(N), backend="xla")
    np.testing.assert_allclose(x, np.asarray(ref), atol=1e-3, rtol=1e-2)


@pytest.mark.parametrize("panel,r", [(8, 128), (16, 128), (32, 128),
                                     (8, 24)])
def test_mxu_trailing_update_agrees(rng, panel, r):
    # the MXU rank-k trailing update (dot_general over the panel dim)
    # must reproduce the VPU sweep's math — same factorization, the
    # contraction moved to the matrix unit.  VPU-vs-XLA agreement per
    # panel is test_panel_widths_agree's pin; here MXU goes against the
    # XLA reference at every panel and against the VPU sweep once, on
    # the cheap sub-128 case (each interpret-mode compile is ~10s of
    # tier-1 budget, and the heavy VPU reruns re-prove a pinned fact)
    N = LANES + 8
    A, b = _spd_problem(rng, N, r, scale=1.0 / np.sqrt(r))
    x_mxu = np.asarray(spd_solve_lanes(A, b, panel=panel, mxu=True,
                                       interpret=True))
    ref = solve_spd(A, b, jnp.ones(N), backend="xla")
    np.testing.assert_allclose(x_mxu, np.asarray(ref), atol=1e-3,
                               rtol=1e-2)
    if r < 128:
        x_vpu = np.asarray(spd_solve_lanes(A, b, panel=panel, mxu=False,
                                           interpret=True))
        np.testing.assert_allclose(x_mxu, x_vpu, atol=1e-3, rtol=1e-2)


def test_selected_mxu_defaults_conservative():
    # no probe has validated the MXU variant off-TPU: dispatch must get
    # False (the VPU sweep), never an unvalidated kernel
    from tpu_als.ops.pallas_lanes import selected_mxu

    assert selected_mxu(128) is False


def test_panel_rounds_to_divisor(rng):
    # rank 24 pads to 24; DEFAULT_PANEL=8 divides it, but panel=16 must
    # round down to a divisor instead of tracing a ragged loop
    N, r = 12, 24
    A, b = _spd_problem(rng, N, r)
    x = np.asarray(spd_solve_lanes(A, b, panel=16, interpret=True))
    ref = np.stack([np.linalg.solve(np.asarray(A)[k], np.asarray(b)[k])
                    for k in range(N)])
    assert np.abs(x - ref).max() / max(1.0, np.abs(ref).max()) < 5e-3


def test_matches_solve_spd_contract(rng):
    # same prep as solve_spd: empty rows (count=0) -> identity A, zero b
    N, r = 24, 16
    A, b = _spd_problem(rng, N, r)
    count = np.ones(N, np.float32)
    count[::5] = 0.0
    b = jnp.asarray(np.where(count[:, None] > 0, np.asarray(b), 0.0))
    x_ref = solve_spd(A, b, jnp.asarray(count), backend="xla")
    eye = jnp.eye(r)
    Ap = jnp.where((count <= 0)[:, None, None], eye, A) + 1e-6 * eye
    x_lan = spd_solve_lanes(Ap, b, interpret=True)
    np.testing.assert_allclose(np.asarray(x_lan), np.asarray(x_ref),
                               atol=2e-4, rtol=2e-3)
    assert (np.asarray(x_lan)[::5] == 0).all()


def test_rank_gate():
    # the [r, r, 128] scratch exceeds VMEM above rank 128: the blocked
    # kernel owns that regime and available() must refuse without probing
    assert supported_rank(128)
    assert not supported_rank(136)
    assert available(256) is False


def test_solve_spd_lanes_backend_dispatch(rng, monkeypatch):
    # backend='lanes' must route to spd_solve_lanes (a refactor dropping
    # 'lanes' from the dispatch would otherwise only surface on TPU, at
    # trace time); unknown backends must raise
    from tpu_als.ops import pallas_lanes

    N, r = 16, 8
    A, b = _spd_problem(rng, N, r)
    count = jnp.ones((N,), jnp.float32)
    hits = []

    def fake(Ax, bx, panel=None, mxu=False, interpret=False):
        hits.append((Ax.shape, panel))
        return jnp.linalg.solve(Ax, bx[..., None])[..., 0]

    monkeypatch.setattr(pallas_lanes, "spd_solve_lanes", fake)
    x = solve_spd(A, b, count, backend="lanes")
    assert hits and hits[0][0] == (N, r, r)
    ref = solve_spd(A, b, count, backend="xla")
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    with pytest.raises(ValueError, match="unknown solve backend"):
        solve_spd(A, b, count, backend="warp")


class TestAvailableProbe:
    """Same standard as pallas_solve.available: wrong-but-finite output
    fails, crashes fail, correct output passes."""

    def _probe(self, monkeypatch, fake_kernel, cache=None):
        from tpu_als.ops import pallas_lanes
        from tpu_als.utils import platform

        monkeypatch.setattr(platform, "on_tpu", lambda: True)
        monkeypatch.setattr(pallas_lanes, "_AVAILABLE",
                            {} if cache is None else cache)
        monkeypatch.setattr(pallas_lanes, "_PANEL", {})
        monkeypatch.setattr(pallas_lanes, "_MXU", {})
        monkeypatch.setattr(pallas_lanes, "spd_solve_lanes", fake_kernel)
        return pallas_lanes.available(32)

    def test_rejects_wrong_but_finite_kernel(self, monkeypatch):
        assert self._probe(
            monkeypatch,
            lambda A, b, panel=None, mxu=False, interpret=False: b,
        ) is False

    def test_rejects_crashing_kernel(self, monkeypatch):
        def boom(A, b, panel=None, mxu=False, interpret=False):
            raise jax.errors.JaxRuntimeError("mosaic compile failure")

        with pytest.warns(UserWarning, match="refused by the compiler"):
            assert self._probe(monkeypatch, boom) is False

    def test_accepts_correct_kernel(self, monkeypatch):
        from tpu_als.ops import pallas_lanes

        assert self._probe(
            monkeypatch,
            lambda A, b, panel=None, mxu=False, interpret=False:
            jnp.linalg.solve(A, b[..., None])[..., 0],
        ) is True
        # the probe ladder tries the MXU variant first; a kernel that
        # validates under it records the MXU selection for dispatch
        assert pallas_lanes.selected_mxu(32) is True

    def test_mxu_crash_falls_back_to_vpu(self, monkeypatch):
        # an MXU-only Mosaic failure must not disable the kernel: the
        # ladder degrades to the VPU sweep and records mxu=False
        from tpu_als.ops import pallas_lanes

        from tpu_als.utils.platform import ProbeCache

        def picky(A, b, panel=None, mxu=False, interpret=False):
            if mxu:
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: scoped vmem\nsecond line")
            return jnp.linalg.solve(A, b[..., None])[..., 0]

        cache = ProbeCache("t_lanes_ladder")
        with pytest.warns(UserWarning, match="refused by the compiler"):
            assert self._probe(monkeypatch, picky, cache) is True
        assert pallas_lanes.selected_mxu(32) is False
        # the verdict says which rung won and why the other lost
        assert cache.meta[32]["reason"] == (
            "pallas_lanes[r=32,panel=32,mxu=True]: compiler refused: "
            "JaxRuntimeError: RESOURCE_EXHAUSTED: scoped vmem; "
            "pallas_lanes[r=32,panel=8,mxu=False]: compiled and validated")

    def test_probe_bug_is_not_a_rung_loss(self, monkeypatch):
        # anything but the compiler's refusal propagates out of the ladder
        def buggy(A, b, panel=None, mxu=False, interpret=False):
            raise AttributeError("no such name")

        with pytest.raises(AttributeError):
            self._probe(monkeypatch, buggy)
