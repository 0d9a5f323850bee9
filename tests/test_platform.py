"""probe_kernel scaffolding: trace-safety, refusals that say why, the
device test and the compile cache's place."""

import os
import warnings

import jax
import jax.numpy as jnp
import pytest

from tpu_als.utils import platform


def test_probe_inside_jit_trace_degrades_without_caching(monkeypatch):
    """A probe firing while a training step is being TRACED (solve_spd's
    auto dispatch runs under jit) cannot execute — round-2 regression: its
    concrete arrays became tracers, block_until_ready raised, and False
    was CACHED, silently downgrading the whole process to the XLA path
    (the RMSE benchmark trained 40% slower than the headline run).  The
    contract now: degrade that one trace, cache nothing, warn — and every
    step builder prewarms probes eagerly so this never fires in the
    shipped call paths."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cache = {}
    calls = []

    def probe():
        calls.append(1)
        return True

    @jax.jit
    def traced(y):
        ok = platform.probe_kernel(cache, "k", probe)
        return y * (1.0 if ok else 0.0)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = traced(jnp.ones(3))
    assert any("inside a jit trace" in str(x.message) for x in w)
    assert cache == {}        # nothing cached from the in-trace request
    assert calls == []        # the probe body never ran under the trace
    assert float(out[0]) == 0.0  # that trace used the fallback path
    # a later EAGER call probes and caches normally
    assert platform.probe_kernel(cache, "k", probe) is True
    assert cache["k"] is True and calls == [1]


def test_compiler_refusal_cached_once_with_its_reason(monkeypatch):
    """The compiler's refusal of a kernel is a verdict: cached False, no
    retry, one warning, and the first line of the message reaches the
    registry (``probe_caches()`` meta) so nobody has to guess why a run
    took the next backend."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cache = platform.probe_cache("t_refused")
    platform.clear_probe_caches("t_refused")
    calls = []

    def broken():
        calls.append(1)
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering for "
            "KernelType.TC: reduce_precision.\nsecond line")

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert platform.probe_kernel(cache, "k", broken) is False
        assert platform.probe_kernel(cache, "k", broken) is False
    assert len(calls) == 1  # no retry, cached
    assert sum("refused by the compiler" in str(x.message) for x in w) == 1
    meta = platform.probe_caches()["t_refused"].meta["k"]
    assert meta["reason"] == (
        "compiler refused: NotImplementedError: Unimplemented primitive "
        "in Pallas TPU lowering for KernelType.TC: reduce_precision.")
    assert meta["seconds"] is not None


def test_probe_bug_propagates_instead_of_becoming_a_verdict(monkeypatch):
    """An AttributeError (a renamed JAX name), a wrong shape, a tracer —
    anything that is not the compiler speaking — is a bug in the probe.
    Filed as ``False`` it would send a run down a path nobody chose."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cache = {}

    def buggy():
        raise AttributeError("module 'pltpu' has no attribute "
                             "'TPUCompilerParams'")

    with pytest.raises(AttributeError):
        platform.probe_kernel(cache, "k", buggy)
    assert cache == {}


def test_ladder_records_which_rung_won_and_why_the_others_lost():
    notes = {}

    def refused():
        raise NotImplementedError("no lowering for this\nmore")

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert platform.try_rung(notes, "rung[a]", refused) is False
    assert platform.try_rung(notes, "rung[b]", lambda: False) is False
    assert platform.try_rung(notes, "rung[c]", lambda: True) is True
    assert len(w) == 1 and "rung[a]" in str(w[0].message)
    assert platform.ladder_reason(notes) == (
        "rung[a]: compiler refused: NotImplementedError: no lowering for "
        "this; rung[b]: wrong result; rung[c]: compiled and validated")
    with pytest.raises(ZeroDivisionError):   # a bug is not a rung's loss
        platform.try_rung(notes, "rung[d]", lambda: 1 / 0)


def test_probe_reason_from_the_probe_itself(monkeypatch):
    """A probe may say why: ``(ok, reason)`` — the timing probes report
    both timings, the ladders their rungs."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cache = platform.probe_cache("t_reason")
    platform.clear_probe_caches("t_reason")
    assert platform.probe_kernel(
        cache, "speed", lambda: (False, "lost the timing probe: 2 ms vs "
                                        "1 ms")) is False
    assert cache.meta["speed"]["reason"].startswith("lost the timing")
    assert platform.probe_kernel(cache, "plain", lambda: True) is True
    assert cache.meta["plain"]["reason"] == "compiled and validated"


def test_on_tpu_propagates_a_backend_init_error(monkeypatch):
    """A backend that fails to initialise must not read as "not a TPU":
    every ``interpret = not on_tpu()`` site would then run the Pallas
    interpreter on the CPU in silence."""
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        platform.on_tpu()


def test_on_tpu_reads_the_first_device_platform(monkeypatch):
    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert platform.on_tpu() is True
    Dev.platform = "cpu"            # a TPU-ish kind does not make it one
    assert platform.on_tpu() is False


def test_compile_cache_honours_the_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and the program
    sets no directory in code."""
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    assert platform.enable_persistent_compile_cache() == "/x"
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_is_one_absolute_directory_in_the_checkout(
        monkeypatch, tmp_path):
    """Without the variable: one fixed directory inside the checkout,
    the same from any working directory (the path is part of the cache
    key) — never a temp name, a pid or the time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    seen = []
    for cwd in (tmp_path, os.path.dirname(os.path.abspath(__file__))):
        monkeypatch.chdir(cwd)
        seen.append(platform.enable_persistent_compile_cache())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert seen[0] == seen[1] == os.path.join(repo, ".bench_cache",
                                              "xla_cache")
    assert updates["jax_compilation_cache_dir"] == seen[0]
