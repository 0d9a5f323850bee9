"""Unified jaxpr-contract registry: the repo's byte-level pins, by name.

Several subsystems carry the same load-bearing discipline — a claim about
the TRACED program, pinned byte-for-byte against the jaxpr rather than
against the claimant's own inputs:

- ``ne_audit``            — the einsum NE build materializes exactly one
  ``Vg = V[cols]`` gather; the gather-fused build traces NO HBM gather;
  the fused kernel's embedded CostEstimate equals the roofline's
  ``fused_ne_kernel_bytes`` at the kernel's padded shapes.
- ``fused_solve_audit``   — the whole-iteration fused kernel
  (``gather_solve``: gather → Gram → Cholesky → x) traces NO HBM gather,
  stamps a CostEstimate equal to the roofline's
  ``fused_solve_kernel_bytes``, and that stamp sits strictly below the
  gather-fused NE build plus the A/b HBM handoff it deletes.
- ``guardrails_disarmed`` — arming the divergence sentinels must not
  perturb the production step's traced graph (``str(jax.make_jaxpr)``
  byte-identity, armed vs disarmed).
- ``tracing_disarmed``    — arming causal tracing (``obs.tracing``)
  must not perturb the production step's traced graph either: trace
  context is host-side state on tickets/events, never a jit operand.
- ``plan_cache_off``      — ``TPU_ALS_PLAN_CACHE=off`` vs a warm cache
  dir resolves the byte-identical step jaxpr: the planner supplies probe
  verdicts, never a different program.
- ``comm_audit``          — the collective bytes the sharded step's
  jaxpr actually moves equal ``trainer.comm_bytes_per_iter``'s closed
  form exactly.
- ``live_delta_index``    — an incremental publish (delta segment of
  only the touched/appended rows, and its later compaction) returns
  top-k scores/indices BITWISE equal to a full ``build_index`` rebuild
  of the updated catalog, and compaction's arrays are byte-equal to
  the rebuild's (serving/index.py; not a jaxpr pin but the same
  discipline — an exactness claim re-verified by name).
- ``floor_audit``         — the committed autotune bank
  (``BENCH_autotune_cpu.json``): the tuned config is never slower than
  the hand-picked defaults, the banked ``model_seconds`` equals the
  ``fused_solve_kernel_bytes`` closed form re-derived at the banked
  config/shape, and the measured-vs-modeled ratio stays inside its
  band — so the roofline gap can never silently reopen in CI.

Before this registry the four pins lived in four test files with no
shared vocabulary; a kernel author adding a fifth had to rediscover the
idiom each time.  Here each pin is a ``Contract(name, build, pin)``:
``build()`` produces the traced artifact (jaxprs, byte counts),
``pin(artifact)`` asserts the invariant and returns a one-line verdict.
``tpu_als lint --contracts`` re-verifies all of them; ``--contract
<name>`` re-verifies one.  The authoritative (parameter-rich) versions
remain the provenance tests named on each contract — this registry is
the cheap, named, CI-gated re-verification at small shapes.

Import layering: this module imports only stdlib at module level; jax
and tpu_als subsystems load lazily inside each ``build``.  Contracts
assume a fresh process (the CLI / smoke-script invocation): process
state they must control (guardrails mode, the plan-cache env var, probe
caches) is saved and restored, but a caller that already armed a
subsystem mid-process may see spurious verdicts.  ``comm_audit`` needs
a multi-device backend — start Python with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU.
"""

from __future__ import annotations

import dataclasses
import os
import time

__all__ = [
    "Contract", "ContractViolation", "Result",
    "get", "names", "verify", "verify_all",
]


class ContractViolation(AssertionError):
    """A pinned jaxpr-level invariant no longer holds."""


@dataclasses.dataclass(frozen=True)
class Result:
    name: str
    ok: bool
    detail: str


@dataclasses.dataclass(frozen=True)
class Contract:
    """One named, re-verifiable jaxpr pin.

    ``build``: () -> artifact (traces the program(s), counts bytes).
    ``pin``: artifact -> str (asserts; the returned string is the
    human verdict).  ``provenance``: the authoritative test that owns
    the full-strength version of this pin.
    """

    name: str
    build: "callable"
    pin: "callable"
    provenance: str

    def verify(self):
        t0 = time.perf_counter()
        try:
            detail = self.pin(self.build())
        except Exception as e:  # noqa: BLE001 — verdicts, not crashes
            return Result(self.name, False,
                          f"{type(e).__name__}: {e} [{self.provenance}]")
        dt = time.perf_counter() - t0
        return Result(self.name, True,
                      f"{detail} [{dt:.1f}s; {self.provenance}]")


def _require(cond, msg):
    if not cond:
        raise ContractViolation(msg)


# -- shared tiny problem (the guardrails/plan pin shapes) -------------------

def _tiny_csr(nU=60, nI=40, nnz=800, seed=0):
    import numpy as np

    from tpu_als.core.ratings import build_csr_buckets

    gen = np.random.default_rng(seed)
    u = gen.integers(0, nU, nnz)
    i = gen.integers(0, nI, nnz)
    r = gen.uniform(0.5, 5.0, nnz).astype(np.float32)
    ucsr = build_csr_buckets(u, i, r, nU, min_width=4, chunk_elems=1 << 12)
    icsr = build_csr_buckets(i, u, r, nI, min_width=4, chunk_elems=1 << 12)
    return ucsr, icsr


def _tiny_step_and_factors(cfg):
    import jax

    from tpu_als.core.als import init_factors, make_step

    ucsr, icsr = _tiny_csr()
    nU, nI = ucsr.num_rows, icsr.num_rows
    ub = jax.device_put(ucsr.device_buckets())
    ib = jax.device_put(icsr.device_buckets())
    step = make_step(ub, ib, nU, nI, cfg,
                     ucsr.chunk_elems, icsr.chunk_elems)
    ku, kv = jax.random.split(jax.random.PRNGKey(cfg.seed))
    U0 = init_factors(ku, nU, cfg.rank)
    V0 = init_factors(kv, nI, cfg.rank)
    return step, U0, V0, ucsr, icsr


# -- ne_audit ---------------------------------------------------------------

def _build_ne_audit():
    import numpy as np

    import jax.numpy as jnp

    from tpu_als.ops.pallas_gather_ne import (
        _tiles,
        gather_normal_eq_explicit,
    )
    from tpu_als.ops.solve import normal_eq_explicit
    from tpu_als.perf.ne_audit import gather_out_bytes, pallas_cost_bytes
    from tpu_als.perf.roofline import fused_ne_kernel_bytes

    n, w, r, N = 48, 40, 24, 300           # the provenance test's shapes
    rng = np.random.default_rng(7)
    V = jnp.asarray(rng.normal(size=(N, r)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
    mask = jnp.asarray((rng.random((n, w)) < 0.8).astype(np.float32))

    einsum = lambda V, c, v, m: normal_eq_explicit(V[c], v, m, 0.1)
    fused = lambda V, c, v, m: gather_normal_eq_explicit(
        V, c, v, m, 0.1, interpret=True)

    r_pad = max(128, -(-r // 128) * 128)
    tn, wc, w_pad = _tiles(r_pad, -(-w // 8) * 8)
    n_pad = -(-n // tn) * tn
    return {
        "vg_bytes": n * w * r * 4,
        "einsum_gather": gather_out_bytes(einsum, V, cols, vals, mask),
        "fused_gather": gather_out_bytes(fused, V, cols, vals, mask),
        "fused_cost": pallas_cost_bytes(fused, V, cols, vals, mask),
        "model_bytes": fused_ne_kernel_bytes(n_pad * w_pad, n_pad,
                                             r_pad, 4),
    }


def _pin_ne_audit(a):
    total, count = a["einsum_gather"]
    _require(count == 1 and total == a["vg_bytes"],
             f"einsum path traced {count} gather(s) writing {total} B, "
             f"expected exactly one writing {a['vg_bytes']} B (Vg)")
    _require(a["fused_gather"] == (0, 0),
             f"gather-fused path traced an HBM gather: "
             f"{a['fused_gather']} — Vg is being materialized")
    ctotal, ccount = a["fused_cost"]
    _require(ccount == 1 and ctotal == a["model_bytes"],
             f"fused CostEstimate {ctotal} B != fused_ne_kernel_bytes "
             f"{a['model_bytes']} B at padded shapes")
    return (f"einsum gather == Vg ({a['vg_bytes']} B), fused gather-free, "
            f"CostEstimate == model ({a['model_bytes']} B)")


# -- fused_solve_audit ------------------------------------------------------

def _build_fused_solve_audit():
    import numpy as np

    import jax.numpy as jnp

    from tpu_als.ops.pallas_gather_ne import (
        _tiles,
        _tiles_solve,
        gather_fused_solve_explicit,
        gather_normal_eq_explicit,
    )
    from tpu_als.perf.ne_audit import gather_out_bytes, pallas_cost_bytes
    from tpu_als.perf.roofline import fused_solve_kernel_bytes

    n, w, r, N = 48, 40, 24, 300           # the ne_audit contract's shapes
    rng = np.random.default_rng(7)
    V = jnp.asarray(rng.normal(size=(N, r)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
    mask = jnp.asarray((rng.random((n, w)) < 0.8).astype(np.float32))

    fsolve = lambda V, c, v, m: gather_fused_solve_explicit(
        V, c, v, m, 0.1, interpret=True)
    ne = lambda V, c, v, m: gather_normal_eq_explicit(
        V, c, v, m, 0.1, interpret=True)

    r_pad = max(128, -(-r // 128) * 128)
    w8 = -(-w // 8) * 8
    tn, _, w_pad = _tiles_solve(r_pad, w8)
    n_pad = -(-n // tn) * tn
    tn_ne, _, _ = _tiles(r_pad, w8)
    n_pad_ne = -(-n // tn_ne) * tn_ne
    # what the unfused gather_fused path moves ON TOP of its NE kernel:
    # A [n, r, r] + b [n, r] written to HBM, then read back by the
    # solver (the x write appears in both paths, so it cancels out of
    # the comparison)
    handoff = 2 * n_pad_ne * (r_pad * r_pad + r_pad) * 4
    return {
        "solve_gather": gather_out_bytes(fsolve, V, cols, vals, mask),
        "solve_cost": pallas_cost_bytes(fsolve, V, cols, vals, mask),
        "model_bytes": fused_solve_kernel_bytes(
            n_pad * w_pad, n_pad, r_pad, 4),
        "ne_cost": pallas_cost_bytes(ne, V, cols, vals, mask),
        "handoff": handoff,
    }


def _pin_fused_solve_audit(a):
    _require(a["solve_gather"] == (0, 0),
             f"whole-iteration fused path traced an HBM gather: "
             f"{a['solve_gather']} — Vg is being materialized")
    ctotal, ccount = a["solve_cost"]
    _require(ccount == 1 and ctotal == a["model_bytes"],
             f"fused-solve CostEstimate {ctotal} B != "
             f"fused_solve_kernel_bytes {a['model_bytes']} B at padded "
             f"shapes")
    ntotal, ncount = a["ne_cost"]
    _require(ncount == 1,
             f"NE comparator traced {ncount} pallas_call(s), expected 1")
    unfused = ntotal + a["handoff"]
    _require(ctotal < unfused,
             f"fused-solve bytes {ctotal} B not below the NE-build + "
             f"A/b handoff total {unfused} B — the fusion stopped "
             f"deleting traffic")
    drop = 100.0 * (1.0 - ctotal / unfused)
    return (f"gather-free, CostEstimate == model ({ctotal} B), "
            f"{drop:.0f}% below NE build + A/b handoff ({unfused} B)")


# -- guardrails_disarmed ----------------------------------------------------

def _build_guardrails_disarmed():
    import jax

    from tpu_als.core.als import AlsConfig
    from tpu_als.resilience import guardrails

    step, U0, V0, _, _ = _tiny_step_and_factors(
        AlsConfig(rank=4, max_iter=2))
    disarmed = str(jax.make_jaxpr(step)(U0, V0))
    with guardrails.scoped("recover"):
        armed = str(jax.make_jaxpr(step)(U0, V0))
    return {"disarmed": disarmed, "armed": armed}


def _pin_guardrails_disarmed(a):
    _require(a["disarmed"] == a["armed"],
             "arming guardrails changed the production step's jaxpr "
             f"({len(a['disarmed'])} vs {len(a['armed'])} chars) — the "
             "sentinels leaked into the traced graph")
    return f"armed == disarmed step jaxpr ({len(a['disarmed'])} chars)"


# -- tracing_disarmed -------------------------------------------------------

def _build_tracing_disarmed():
    import jax

    from tpu_als.core.als import AlsConfig
    from tpu_als.obs import tracing

    step, U0, V0, _, _ = _tiny_step_and_factors(
        AlsConfig(rank=4, max_iter=2))
    disarmed = str(jax.make_jaxpr(step)(U0, V0))
    with tracing.traced():
        armed = str(jax.make_jaxpr(step)(U0, V0))
    return {"disarmed": disarmed, "armed": armed}


def _pin_tracing_disarmed(a):
    _require(a["disarmed"] == a["armed"],
             "arming causal tracing changed the production step's jaxpr "
             f"({len(a['disarmed'])} vs {len(a['armed'])} chars) — trace "
             "context leaked into the traced graph (it must stay "
             "host-side: ids on tickets/events, never in jit)")
    return f"armed == disarmed step jaxpr ({len(a['disarmed'])} chars)"


# -- plan_cache_off ---------------------------------------------------------

def _build_plan_cache_off():
    import tempfile

    from tpu_als.core.als import AlsConfig
    from tpu_als.plan.cache import ENV_VAR
    from tpu_als.utils import platform

    import jax

    cfg = AlsConfig(rank=4, max_iter=2)
    saved = os.environ.get(ENV_VAR)
    try:
        with tempfile.TemporaryDirectory() as td:
            os.environ[ENV_VAR] = "off"
            platform.clear_probe_caches()
            step, U0, V0, _, _ = _tiny_step_and_factors(cfg)
            off = str(jax.make_jaxpr(step)(U0, V0))

            os.environ[ENV_VAR] = os.path.join(td, "armed")
            platform.clear_probe_caches()
            step, U0, V0, _, _ = _tiny_step_and_factors(cfg)
            armed = str(jax.make_jaxpr(step)(U0, V0))
    finally:
        if saved is None:
            os.environ.pop(ENV_VAR, None)
        else:
            os.environ[ENV_VAR] = saved
        platform.clear_probe_caches()
    return {"off": off, "armed": armed}


def _pin_plan_cache_off(a):
    _require(a["off"] == a["armed"],
             "arming the plan cache changed the step's jaxpr "
             f"({len(a['off'])} vs {len(a['armed'])} chars) — the "
             "planner steered the traced program, not just the probes")
    return f"cache-off == cache-armed step jaxpr ({len(a['off'])} chars)"


# -- comm_audit -------------------------------------------------------------

def _build_comm_audit():
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_als.core.als import AlsConfig
    from tpu_als.parallel.comm import shard_csr_grid
    from tpu_als.parallel.comm_audit import (
        collective_bytes,
        remote_dma_bytes,
    )
    from tpu_als.parallel.data import partition_balanced, shard_csr
    from tpu_als.parallel.mesh import AXIS, make_mesh
    from tpu_als.parallel.trainer import (
        comm_bytes_per_iter,
        make_ring_step,
        make_sharded_step,
        stacked_counts,
    )

    D = len(jax.devices())
    if D < 2:
        raise ContractViolation(
            "comm_audit needs a multi-device backend; start Python with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU")
    rank = 8
    gen = np.random.default_rng(3)
    nU, nI, nnz = 60, 40, 900
    u = gen.integers(0, nU, nnz)
    i = gen.integers(0, nI, nnz)
    r = np.abs(gen.normal(size=nnz)).astype(np.float32) + 0.1
    upart = partition_balanced(np.bincount(u, minlength=nU), D)
    ipart = partition_balanced(np.bincount(i, minlength=nI), D)
    ush = shard_csr(upart, ipart, u, i, r, min_width=4)
    ish = shard_csr(ipart, upart, i, u, r, min_width=4)
    mesh = make_mesh(D)
    leading = NamedSharding(mesh, P(AXIS))
    U = jax.device_put(
        jnp.zeros((upart.padded_rows, rank), jnp.float32), leading)
    V = jax.device_put(
        jnp.zeros((ipart.padded_rows, rank), jnp.float32), leading)
    ub = jax.device_put(ush.device_buckets(), leading)
    ib = jax.device_put(ish.device_buckets(), leading)
    cfg = AlsConfig(rank=rank, max_iter=1, reg_param=0.1,
                    implicit_prefs=True, alpha=4.0, seed=0)
    step = make_sharded_step(mesh, ush, ish, cfg)
    traced, breakdown = collective_bytes(step, U, V, ub, ib, axis_size=D)
    model = comm_bytes_per_iter("all_gather", upart, ipart, rank,
                                user_container=ush, item_container=ish,
                                implicit=True)

    # fused-comm ring (solve_backend='gather_fused_ring'): the inter-chip
    # bytes move as in-kernel remote DMAs — invisible to
    # collective_bytes, counted by remote_dma_bytes — and must equal the
    # model's gather_fused_ring closed form (perf.roofline
    # ring_remote_bytes per half-step), with NO ppermute left in the
    # traced step (the rotation migrated into the kernel)
    rank_ring = 128  # real lane width: the payload model is r_pad-exact
    ug = shard_csr_grid(upart, ipart, u, i, r, min_width=4)
    ig = shard_csr_grid(ipart, upart, i, u, r, min_width=4)
    cfg_ring = AlsConfig(rank=rank_ring, max_iter=1, reg_param=0.1,
                         implicit_prefs=True, alpha=4.0, seed=0,
                         solve_backend="gather_fused_ring")
    ring_step = make_ring_step(mesh, ug, ig, cfg_ring)
    Ur = jax.device_put(
        jnp.zeros((upart.padded_rows, rank_ring), jnp.float32), leading)
    Vr = jax.device_put(
        jnp.zeros((ipart.padded_rows, rank_ring), jnp.float32), leading)
    ubg = jax.device_put(ug.device_buckets(), leading)
    ibg = jax.device_put(ig.device_buckets(), leading)
    uc = jax.device_put(stacked_counts(upart, u, r, positive_only=True),
                        leading)
    ic = jax.device_put(stacked_counts(ipart, i, r, positive_only=True),
                        leading)
    ring_args = (Ur, Vr, ubg, ibg, uc, ic)
    ring_traced, _ = remote_dma_bytes(ring_step, *ring_args)
    _, ring_breakdown = collective_bytes(ring_step, *ring_args,
                                         axis_size=D)
    # the implicit=False form is the pure ring term; the implicit=True
    # delta is the psum(YtY) adder — pinned separately because they are
    # counted by different auditors (remote_dma_bytes vs collective_bytes)
    ring_model = comm_bytes_per_iter(
        "gather_fused_ring", upart, ipart, rank_ring,
        user_container=ug, item_container=ig, implicit=False)
    ring_model_psum = comm_bytes_per_iter(
        "gather_fused_ring", upart, ipart, rank_ring,
        user_container=ug, item_container=ig, implicit=True) - ring_model
    return {"traced": traced, "model": model, "breakdown": breakdown,
            "devices": D, "ring_traced": ring_traced,
            "ring_model": ring_model,
            "ring_psum_traced": ring_breakdown.get("psum", 0),
            "ring_psum_model": ring_model_psum,
            "ring_breakdown": ring_breakdown}


def _pin_comm_audit(a):
    _require(a["breakdown"].get("all_gather")
             and a["breakdown"].get("psum"),
             f"expected all_gather+psum collectives, traced "
             f"{sorted(a['breakdown'])}")
    _require(a["traced"] == a["model"],
             f"traced collective bytes {a['traced']} != "
             f"comm_bytes_per_iter model {a['model']} "
             f"(breakdown {a['breakdown']})")
    _require(a["ring_traced"] == a["ring_model"],
             f"traced in-kernel remote-DMA bytes {a['ring_traced']} != "
             f"comm_bytes_per_iter('gather_fused_ring') ring term "
             f"{a['ring_model']}")
    _require("ppermute" not in a["ring_breakdown"]
             and "all_gather" not in a["ring_breakdown"],
             "fused-comm ring step still traces XLA gather collectives "
             f"({sorted(a['ring_breakdown'])}) — the rotation did not "
             "move in-kernel")
    _require(a["ring_psum_traced"] == a["ring_psum_model"],
             f"fused-ring psum(YtY) bytes {a['ring_psum_traced']} != "
             f"model {a['ring_psum_model']}")
    return (f"traced == modeled collective bytes ({a['traced']} B/device "
            f"across {a['devices']} devices; fused-ring remote-DMA "
            f"{a['ring_traced']} B/device == closed form, no XLA gather "
            "collectives)")


# -- ring_substrate ---------------------------------------------------------

def _build_ring_substrate():
    import re
    from pathlib import Path

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from tpu_als.ops import pallas_gather_ne as pg
    from tpu_als.ops import pallas_topk as pt
    from tpu_als.ops import ring_buffer as rb

    # frozen twins of the PRE-extraction hand-rolled schedules (PR 14's
    # in-kernel loop in pallas_gather_ne; pallas_topk's per-grid-step
    # variant).  These are deliberate verbatim copies: the substrate
    # extraction claimed "byte-identical jaxpr modulo source locations",
    # and this contract is where that claim is load-bearing.
    def _frozen_pump(n_entries, make_copy, depth=None):
        if depth is None:
            depth = min(8, n_entries)  # inlined DMA_SLOTS=8, pre-extraction
        for s in range(depth):
            make_copy(s, s).start()

        def _body(e, carry):
            make_copy(e, e % depth).wait()

            @pl.when(e + depth < n_entries)
            def _next():
                make_copy(e + depth, e % depth).start()

            return carry

        jax.lax.fori_loop(0, n_entries, _body, 0)

    def _frozen_grid_pump(step, n_steps, make_copy, depth=2):
        @pl.when(step == 0)
        def _prime():
            make_copy(0, 0).start()

        make_copy(step, jax.lax.rem(step, depth)).wait()

        @pl.when(step + 1 < n_steps)
        def _next():
            make_copy(step + 1, jax.lax.rem(step + 1, depth)).start()

    def _norm(jaxpr):
        # source locations are the ONE documented difference between the
        # twin (defined here) and the substrate (defined in ring_buffer)
        return re.sub(r" at /[^,\s)]*", "", str(jaxpr))

    rng = np.random.default_rng(0)
    n, w, r = 24, 12, 8
    V = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, n, size=(5, w)).astype(np.int32))
    aw = jnp.ones((5, w), jnp.float32)
    bw = jnp.asarray(rng.normal(size=(5, w)).astype(np.float32))
    cw = jnp.ones((5, w), jnp.float32)
    U = jnp.asarray(rng.normal(size=(256, 16)).astype(np.float32))
    Vt = jnp.asarray(rng.normal(size=(1024, 16)).astype(np.float32))
    valid = jnp.ones(1024, bool)

    # trace the UNJITTED entry points (__wrapped__): pjit caches inner
    # jaxprs across calls, so the monkeypatched twin would be invisible
    # through the jit wrapper
    def traces():
        out = {
            "gather_gram": jax.make_jaxpr(
                lambda: pg.gather_gram.__wrapped__(
                    V, cols, aw, bw, two_sided=True, interpret=True))(),
            "gather_solve": jax.make_jaxpr(
                lambda: pg.gather_solve.__wrapped__(
                    V, cols, aw, bw, cw, two_sided=True, reg=0.1,
                    interpret=True))(),
            "topk": jax.make_jaxpr(
                lambda: pt.topk_scores_pallas.__wrapped__(
                    U, Vt, valid, 10, interpret=True))(),
        }
        return {k: _norm(v) for k, v in out.items()}

    current = traces()
    orig = rb.pump, rb.grid_pump
    rb.pump, rb.grid_pump = _frozen_pump, _frozen_grid_pump
    try:
        frozen = traces()
    finally:
        rb.pump, rb.grid_pump = orig

    # source scan: the substrate owns EVERY async-DMA descriptor.  A
    # private make_async_copy / make_async_remote_copy call site outside
    # ops/ring_buffer.py is a fourth hand-rolled double-buffer waiting to
    # drift.  Call syntax only — prose mentions in docstrings are fine.
    root = Path(pg.__file__).resolve().parents[1]
    call = re.compile(r"make_async(?:_remote)?_copy\s*\(")
    offenders = sorted(
        str(p.relative_to(root))
        for p in root.rglob("*.py")
        if p.name != "ring_buffer.py" and call.search(p.read_text())
    )
    return {"current": current, "frozen": frozen, "offenders": offenders}


def _pin_ring_substrate(a):
    for k, cur in a["current"].items():
        froz = a["frozen"][k]
        _require(cur == froz,
                 f"{k}: substrate-routed jaxpr differs from the frozen "
                 f"pre-extraction twin ({len(cur)} vs {len(froz)} chars "
                 "after source-location normalization) — the extraction "
                 "changed the emitted schedule")
    _require(not a["offenders"],
             "private async-DMA call sites outside ops/ring_buffer.py: "
             f"{a['offenders']}")
    sizes = ", ".join(f"{k} {len(v)}c" for k, v in a["current"].items())
    return (f"substrate pump == frozen hand-rolled twin ({sizes}); no "
            "async-DMA call sites outside ops/ring_buffer.py")


# -- live_delta_index -------------------------------------------------------

def _build_live_delta():
    import numpy as np

    from tpu_als.serving.index import build_index

    rng = np.random.default_rng(17)
    Ni, r, n, k, sk = 220, 8, 13, 5, 48
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    valid = rng.random(Ni) > 0.15
    U = rng.normal(size=(n, r)).astype(np.float32)
    base = build_index(V, item_valid=valid, shortlist_k=sk, seq=1)

    touched = rng.choice(Ni, 9, replace=False)
    Vn = np.concatenate(
        [V, rng.normal(size=(5, r)).astype(np.float32)])
    Vn[touched] = rng.normal(size=(9, r)).astype(np.float32)
    validn = np.concatenate([valid, np.ones(5, bool)])
    rows = np.concatenate([touched, np.arange(Ni, Ni + 5)])
    delta = base.with_updates(rows, Vn[rows], valid_rows=validn[rows],
                              seq=2)
    # scored BEFORE the compaction: compact() donates the base arrays,
    # and an index whose successor was compacted is spent
    answers = {"delta": tuple(np.asarray(x) for x in delta.topk(U, k))}
    compacted = delta.compact(seq=3)
    answers["compacted"] = tuple(np.asarray(x)
                                 for x in compacted.topk(U, k))
    ref = build_index(Vn, item_valid=validn, shortlist_k=sk, seq=2)
    return {"U": U, "k": k, "answers": answers, "compacted": compacted,
            "ref": ref, "touched": len(rows)}


def _pin_live_delta(a):
    import numpy as np

    s_r, ix_r = (np.asarray(x) for x in a["ref"].topk(a["U"], a["k"]))
    for which in ("delta", "compacted"):
        s, ix = a["answers"][which]
        _require(np.array_equal(s, s_r),
                 f"{which} top-k SCORES differ from the full rebuild "
                 "(the O(touched) incremental publish is not bitwise)")
        _require(np.array_equal(ix, ix_r),
                 f"{which} top-k INDICES differ from the full rebuild")
    for arr in ("V", "Vq", "sv", "valid"):
        _require(np.array_equal(np.asarray(getattr(a["compacted"], arr)),
                                np.asarray(getattr(a["ref"], arr))),
                 f"compacted index array {arr!r} differs bytewise from "
                 "a full rebuild — compaction re-quantized or dropped "
                 "rows")
    return (f"delta({a['touched']} touched rows) and compacted top-k "
            "bitwise == full rebuild; compacted arrays byte-equal")


# -- registry ---------------------------------------------------------------

# -- elastic_disarmed -------------------------------------------------------

def _build_elastic_disarmed():
    import os

    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_als.core.als import AlsConfig
    from tpu_als.parallel.data import partition_balanced, shard_csr
    from tpu_als.parallel.mesh import AXIS, make_mesh
    from tpu_als.parallel.trainer import make_sharded_step
    from tpu_als.resilience import elastic, faults

    D = min(2, len(jax.devices()))
    mesh = make_mesh(D)
    gen = np.random.default_rng(0)
    nU, nI, nnz = 24, 16, 200
    u = gen.integers(0, nU, nnz)
    i = gen.integers(0, nI, nnz)
    r = gen.uniform(0.5, 5.0, nnz).astype(np.float32)
    upart = partition_balanced(np.bincount(u, minlength=nU), D)
    ipart = partition_balanced(np.bincount(i, minlength=nI), D)
    ush = shard_csr(upart, ipart, u, i, r)
    ish = shard_csr(ipart, upart, i, u, r)
    cfg = AlsConfig(rank=4, max_iter=2)
    leading = NamedSharding(mesh, P(AXIS))
    ub = jax.device_put(ush.device_buckets(), leading)
    ib = jax.device_put(ish.device_buckets(), leading)
    U0 = jax.device_put(
        np.zeros((upart.padded_rows, cfg.rank), np.float32), leading)
    V0 = jax.device_put(
        np.zeros((ipart.padded_rows, cfg.rank), np.float32), leading)

    step = make_sharded_step(mesh, ush, ish, cfg)
    disarmed = str(jax.make_jaxpr(step)(U0, V0, ub, ib))
    # arm the detector's fault point (a schedule that never fires, so
    # tracing completes) AND route tracing through the elastic wrapper —
    # exactly what train_sharded(elastic=True) installs
    spec_was = os.environ.get(faults.ENV_VAR)
    os.environ[faults.ENV_VAR] = "mesh.device_lost=raise@nth=999999"
    faults.install_from_env()
    try:
        wrapped = elastic.wrap_step(step, mesh)
        armed = str(jax.make_jaxpr(wrapped)(U0, V0, ub, ib))
    finally:
        if spec_was is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = spec_was
        faults.install_from_env()
    return {"disarmed": disarmed, "armed": armed}


def _pin_elastic_disarmed(a):
    _require(a["disarmed"] == a["armed"],
             "arming the elastic device-loss detector changed the "
             f"production step's jaxpr ({len(a['disarmed'])} vs "
             f"{len(a['armed'])} chars) — the detector must stay a "
             "host-level wrapper, never enter the traced graph")
    return ("elastic-armed wrapped step jaxpr == raw step jaxpr "
            f"({len(a['disarmed'])} chars)")


# -- floor_audit: the banked autotune A/B stays inside its roofline band ----

# the committed autotune bank this contract audits; an override root lets
# the red-path test (and a TPU re-bank rehearsal) point at a doctored copy
FLOOR_AUDIT_ROOT_ENV = "TPU_ALS_FLOOR_AUDIT_ROOT"
FLOOR_AUDIT_BANK = "BENCH_autotune_cpu.json"
# measured/modeled band for DEVICE-sourced banks: the headline sits ~24x
# off the revised roofline floor (ROADMAP), so 32x is the "gap silently
# reopened" tripwire; interpret-sourced banks only pin ratio > 1 (the
# CPU interpreter cannot beat the v5e closed-form floor)
FLOOR_BAND_ENV = "TPU_ALS_FLOOR_BAND"
DEFAULT_FLOOR_BAND = 32.0
# never-slower tolerance: one regress noise band (obs.regress default)
FLOOR_AUDIT_NOISE = 0.10


def _build_floor_audit():
    import json

    from tpu_als.perf import autotune

    root = os.environ.get(FLOOR_AUDIT_ROOT_ENV) or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..")
    path = os.path.join(os.path.abspath(root), FLOOR_AUDIT_BANK)
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    shape = doc["shape"]
    # re-derive the prediction from THE closed form at the banked config
    # and shapes — the bank's own model_seconds field is provenance, the
    # formula is authority (the ne_audit discipline applied to a bank)
    model_s = autotune.model_seconds(doc["config"], shape["rank"],
                                     shape["n"], shape["w"])
    try:
        band = float(os.environ.get(FLOOR_BAND_ENV, "")
                     or DEFAULT_FLOOR_BAND)
    except ValueError:
        band = DEFAULT_FLOOR_BAND
    return {"doc": doc, "model_s": model_s, "band": band,
            "path": os.path.basename(path)}


def _pin_floor_audit(a):
    doc, model_s, band = a["doc"], a["model_s"], a["band"]
    tuned_s = float(doc["tuned_seconds"])
    default_s = float(doc["default_seconds"])
    source = doc.get("source", "interpret")
    _require(tuned_s > 0 and default_s > 0 and model_s > 0,
             f"{a['path']}: non-positive timing "
             f"(tuned {tuned_s}, default {default_s}, model {model_s})")
    _require(tuned_s <= default_s * (1.0 + FLOOR_AUDIT_NOISE),
             f"{a['path']}: the banked tuned config is SLOWER than the "
             f"hand-picked defaults ({tuned_s:.6f}s vs {default_s:.6f}s, "
             f"tolerance {FLOOR_AUDIT_NOISE:.0%}) — the autotuner's "
             "never-slower acceptance rule is broken")
    banked_model = doc.get("model_seconds")
    if banked_model is not None:
        _require(abs(float(banked_model) - model_s)
                 <= 1e-6 * max(float(banked_model), model_s),
                 f"{a['path']}: banked model_seconds "
                 f"{float(banked_model):.3e} != fused_solve_kernel_bytes "
                 f"closed form {model_s:.3e} at the banked config/shape "
                 "— the bank drifted from the roofline model")
    ratio = tuned_s / model_s
    if source == "device":
        _require(0.9 <= ratio <= band,
                 f"{a['path']}: device measured/modeled ratio {ratio:.2f} "
                 f"outside [0.9, {band:g}] — the roofline gap silently "
                 "reopened (or the measurement beat physics); re-tune "
                 "and re-bank")
    else:
        _require(ratio > 1.0,
                 f"{a['path']}: interpret-mode measured/modeled ratio "
                 f"{ratio:.2f} <= 1 — the CPU interpreter cannot beat "
                 "the v5e HBM floor; the bank is doctored or mis-derived")
    speedup = default_s / tuned_s
    _require(abs(float(doc["value"]) - speedup)
             <= 1e-6 * max(float(doc["value"]), speedup),
             f"{a['path']}: banked speedup value {doc['value']} != "
             f"default_seconds/tuned_seconds {speedup:.6f}")
    return (f"banked {source} A/B: tuned {tuned_s:.4f}s <= default "
            f"{default_s:.4f}s (speedup {speedup:.2f}x), "
            f"measured/modeled {ratio:.1f} inside its band")


_REGISTRY = {
    c.name: c for c in (
        Contract("ne_audit", _build_ne_audit, _pin_ne_audit,
                 "tests/test_ne_audit.py, PR 6"),
        Contract("fused_solve_audit", _build_fused_solve_audit,
                 _pin_fused_solve_audit,
                 "tests/test_gather_solve.py, PR 14"),
        Contract("guardrails_disarmed", _build_guardrails_disarmed,
                 _pin_guardrails_disarmed,
                 "tests/test_guardrails.py::"
                 "test_disarmed_step_jaxpr_is_byte_identical, PR 8"),
        Contract("tracing_disarmed", _build_tracing_disarmed,
                 _pin_tracing_disarmed,
                 "tests/test_tracing.py::"
                 "test_tracing_disarmed_step_jaxpr_byte_identical, "
                 "PR 13"),
        Contract("plan_cache_off", _build_plan_cache_off,
                 _pin_plan_cache_off,
                 "tests/test_plan.py::"
                 "test_planner_off_training_step_jaxpr_byte_identical, "
                 "PR 9"),
        Contract("comm_audit", _build_comm_audit, _pin_comm_audit,
                 "tests/test_comm_audit.py, PR 6"),
        Contract("ring_substrate", _build_ring_substrate,
                 _pin_ring_substrate,
                 "tests/test_ring_substrate.py, PR 15"),
        Contract("live_delta_index", _build_live_delta, _pin_live_delta,
                 "tests/test_live.py, PR 11"),
        Contract("elastic_disarmed", _build_elastic_disarmed,
                 _pin_elastic_disarmed,
                 "tests/test_resilience.py, PR 18"),
        Contract("floor_audit", _build_floor_audit, _pin_floor_audit,
                 "tests/test_autotune.py, PR 20"),
    )
}


def names():
    return tuple(_REGISTRY)


def get(name):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no contract named {name!r}; registered: "
            f"{', '.join(_REGISTRY)}") from None


def verify(name):
    return get(name).verify()


def verify_all(only=None):
    """Verify every registered contract (or the named subset), in
    registration order.  Unknown names in ``only`` are skipped here —
    the CLI reports them — so the return covers exactly the contracts
    that ran."""
    picked = [c for n, c in _REGISTRY.items()
              if only is None or n in set(only)]
    return [c.verify() for c in picked]
