"""Pallas TPU kernel: batched SPD solve with the BATCH dimension in lanes.

Second-generation layout for the ALS solve (see tpu_als.ops.pallas_solve
for the first): instead of tiling matrices over the batch dimension and
running the Cholesky recurrence with masked lane reductions and one-hot
MXU extractions, this kernel lays the working set out as ``S[a, b, t] =
A_t[b, a]`` with ``t`` (the matrix index) in the 128-wide LANE dimension.
The serial column recurrence then vectorizes across 128 matrices at once
and every per-column step becomes a *static sublane slice*:

  * column ``j`` of all 128 matrices is ``S[j]`` — a [r, 128] slice, no
    masked reduction;
  * the pivot ``d = S[j, j]`` is a [128] vector — no lane extraction;
  * the rank-1 trailing update is one broadcast multiply-subtract over
    ``[r, r, 128]`` — no one-hot selector matmuls.

The trade: a plain MXU matmul cannot batch over lanes, so the original
trailing update ran on the VPU at r³ (vs the blocked scheme's r³/3 + MXU
panels).  What the layout buys is the removal of every cross-lane
reduction and selector dot from the serial chain — which is what actually
bounds the first-generation kernel (measured: its runtime is invariant to
the batch-tile size, so it is latency-, not throughput-, bound).

Third-generation refinement (``mxu=True``): the serial chain keeps the
lanes layout, but the rank-``panel`` trailing update — the only O(r²·P)
dense block, and the part that swept all of S per panel on the VPU — is
re-expressed as ONE lane-batched ``dot_general`` (batch dim = lanes,
contraction over the panel axis): per lane, an honest [r, P]·[P, r] GEMM
the MXU runs as a systolic pass.  The cost is two in-register layout
rotations around the GEMM (batch-leading in, lane-trailing out); whether
that trade wins on the local Mosaic is exactly what the ``available()``
probe ladder decides — the MXU panel is tried first and the VPU panel /
rank-1 recurrences remain the validated fallbacks, so a Mosaic that
rejects (or mis-lowers) minormost-batch contractions degrades instead of
crashing.

Substitution uses the same layout: y and x live as [r, 128] panels and
each forward/backward step is a [128]-wide vector operation.

Same contract as ``spd_solve_pallas``: caller pre-regularizes A (jitter +
empty-row identity guard); rows with b = 0 solve to x = 0.  Replaces the
reference stack's per-entity LAPACK ``dppsv`` (Spark MLlib
``CholeskySolver``, SURVEY.md §2.B5/C1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_als.ops.ring_buffer import local_copy

LANES = 128

# MXU contractions inside the factorization run at HIGHEST precision: the
# default f32 path is a single bf16 pass whose ~4e-3 relative error
# COMPOUNDS through the Cholesky recurrence (the pallas_solve round-1
# lesson) — HIGHEST restores ~1e-6 and the GEMM is a small fraction of
# kernel time next to the serial column chain.
_PREC = jax.lax.Precision.HIGHEST


def _chol_lanes_kernel(A_ref, b_ref, x_ref, S, Pn, sem, *, r, panel, mxu):
    """One lane-group: factorize 128 matrices and solve.

    A_ref [G, r, r, LANES] stays in HBM (``memory_space=ANY``) with
    A_ref[g, a, b, t] = A_t[b, a] (column-major per matrix so column j is
    a leading-axis slice); the kernel DMAs group ``g`` straight into the
    working scratch ``S`` [r, r, LANES] — at r=128 the group is 8 MB, so a
    pipelined (double-buffered) input block plus the scratch would blow
    the 16 MiB VMEM limit, and the copy (~10 µs at HBM bandwidth) is
    negligible against the factorization anyway.  b_ref / x_ref
    [1, r, LANES].  After the loop S[j] holds column j of L (entries above
    the diagonal zeroed).

    ``panel`` > 1 runs the recurrence in panels of that many columns:
    left-looking factorization of the panel against the scratch ``Pn``
    [panel, r, LANES], then ONE fused rank-``panel`` trailing update pass
    over S instead of ``panel`` rank-1 passes.  The update is what bounds
    this kernel (it sweeps all of S per column), so its VMEM traffic —
    and the kernel's runtime — drops by ~``panel``×.  panel=1 is the
    original rank-1 recurrence.

    ``mxu=True`` additionally moves that trailing update off the VPU: the
    rank-``panel`` correction ``upd[a, b, t] = Σ_k Pn[k, a, t]·Pn[k, b, t]``
    is one ``dot_general`` with the LANE axis as the batch dimension —
    per lane a [r, panel]·[panel, r] GEMM, i.e. 128 MXU passes per panel
    instead of an O(r²·panel·LANES) VPU broadcast sweep.  The serial
    panel factorization (the latency-bound part the lanes layout exists
    for) is unchanged.
    """
    g = pl.program_id(0)
    cp = local_copy(A_ref.at[g], S, sem)
    cp.start()
    cp.wait()
    sub = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 0)  # row index b

    def col(j, _):
        cj = S[j]                                   # [r, LANES]
        d = jnp.sum(jnp.where(sub == j, cj, 0.0), axis=0)     # pivot [LANES]
        inv = jax.lax.rsqrt(jnp.maximum(d, 1e-30))
        ncol = jnp.where(sub >= j, cj * inv[None, :], 0.0)    # L[:, j]
        # trailing rank-1 update, unmasked over the column axis: ncol is
        # zero above row j, so columns a < j receive no update, and
        # columns a <= j are never read again anyway — skipping the
        # where-mask pass is free
        S[:] = S[:] - ncol[:, None, :] * ncol[None, :, :]
        # column j itself was hit by the update (a == j); store the factor
        S[j] = ncol
        return 0

    def panel_step(ip, _):
        base = ip * panel
        # left-looking factorization of the panel columns: corrections
        # from columns inside the panel come from Pn (their trailing
        # update hasn't been applied to S yet)
        for jj in range(panel):
            j = base + jj
            cj = S[j]
            for kk in range(jj):
                Lk = Pn[kk]
                lkj = jnp.sum(jnp.where(sub == j, Lk, 0.0), axis=0)
                cj = cj - Lk * lkj[None, :]
            d = jnp.sum(jnp.where(sub == j, cj, 0.0), axis=0)
            inv = jax.lax.rsqrt(jnp.maximum(d, 1e-30))
            Pn[jj] = jnp.where(sub >= j, cj * inv[None, :], 0.0)
        # one fused rank-`panel` trailing update.  Columns a < base are
        # untouched (factor columns are zero above their pivot row); the
        # panel's own columns ARE hit...
        if mxu:
            # lane-batched GEMM: upd[t, a, b] = Σ_k Pn[k,a,t]·Pn[k,b,t]
            # — per lane an [r, panel]·[panel, r] MXU contraction; the
            # transpose back to the [a, b, t] working layout is the
            # price of admission the probe ladder adjudicates
            upd = jax.lax.dot_general(
                Pn[:], Pn[:],
                dimension_numbers=(((0,), (0,)), ((2,), (2,))),
                preferred_element_type=jnp.float32, precision=_PREC,
            )  # [LANES, r, r]
            S[:] = S[:] - jnp.transpose(upd, (1, 2, 0))
        else:
            upd = Pn[0][:, None, :] * Pn[0][None, :, :]
            for kk in range(1, panel):
                upd = upd + Pn[kk][:, None, :] * Pn[kk][None, :, :]
            S[:] = S[:] - upd
        # ...and restored, same trick as the rank-1 recurrence above
        for jj in range(panel):
            S[base + jj] = Pn[jj]
        return 0

    if panel > 1:
        jax.lax.fori_loop(0, r // panel, panel_step, 0, unroll=False)
    else:
        jax.lax.fori_loop(0, r, col, 0, unroll=False)

    # forward substitution L y = b: y_j = (b_j - Σ_{k<j} L[j,k] y_k)/L[j,j]
    def fwd(j, res):
        cj = S[j]                                   # column j of L [r, LANES]
        d = jnp.sum(jnp.where(sub == j, cj, 0.0), axis=0)
        yj = jnp.sum(jnp.where(sub == j, res, 0.0), axis=0) / d
        # subtract y_j * L[b, j] from all later rows b > j
        res = jnp.where(sub > j, res - yj[None, :] * cj, res)
        res = jnp.where(sub == j, yj[None, :], res)
        return res

    y = jax.lax.fori_loop(0, r, fwd, b_ref[0], unroll=False)

    # backward substitution Lᵀ x = y: x_j = (y_j - Σ_{k>j} L[k,j] x_k)/L[j,j]
    def bwd(t, res):
        j = r - 1 - t
        cj = S[j]
        d = jnp.sum(jnp.where(sub == j, cj, 0.0), axis=0)
        # Σ_{k>j} L[k, j] x_k: column j of L holds exactly those entries
        s = jnp.sum(jnp.where(sub > j, cj * res, 0.0), axis=0)
        xj = (jnp.sum(jnp.where(sub == j, res, 0.0), axis=0) - s) / d
        res = jnp.where(sub == j, xj[None, :], res)
        return res

    x_ref[0] = jax.lax.fori_loop(0, r, bwd, y, unroll=False)


# default trailing-update panel width for the VPU update; chosen on v5e
# (scripts/kernel_lab.py sweep at the headline shape) — see available()
# which validates the configured width on the local Mosaic before the
# kernel engages
DEFAULT_PANEL = 8
# default panel width for the MXU (lane-batched GEMM) trailing update:
# wider panels amortize the two layout rotations around the GEMM and keep
# the [r, panel] operand a full systolic pass; 32 balances that against
# the left-looking panel factorization's O(panel²) serial work
DEFAULT_MXU_PANEL = 32


def _vmem_limit_bytes(r_pad):
    """Scoped-VMEM limit handed to the compiler: room for four
    [r, r, LANES] f32 tensors (the working scratch, the trailing update
    and its two layout rotations) — 32 MiB at r_pad = 128, where the
    default 16 MiB refuses both panel rungs (see :func:`supported_rank`);
    never below the default.  A v5e core has 128 MiB of VMEM."""
    return max(16 << 20, 4 * r_pad * r_pad * LANES * 4)


@functools.partial(jax.jit, static_argnames=("panel", "mxu", "interpret"))
def spd_solve_lanes(A, b, panel=None, mxu=False, interpret=False):
    """Batched SPD solve x = A⁻¹ b.  A [N, r, r] f32, b [N, r] f32.

    Drop-in for ``spd_solve_pallas``; transposes to the lanes layout on
    device (one XLA transpose each way, fused into neighbours where
    possible).  ``panel``: trailing-update panel width (must divide the
    padded rank; None = the variant default, capped to the padded rank).
    ``mxu``: run the trailing update as a lane-batched MXU GEMM instead
    of the VPU broadcast sweep — pass ``selected_mxu(rank)`` so only a
    probe-validated variant engages (the auto dispatch in
    tpu_als.ops.solve does).
    """
    N, r = b.shape
    r_pad = -(-r // 8) * 8
    if panel is None:
        panel = DEFAULT_MXU_PANEL if mxu else DEFAULT_PANEL
    panel = min(panel, r_pad)
    while r_pad % panel:
        panel -= 1
    n_pad = -(-N // LANES) * LANES
    eye_tail = jnp.eye(r_pad, dtype=jnp.float32)[None, :, :]
    Ap = jnp.pad(A, ((0, n_pad - N), (0, r_pad - r), (0, r_pad - r)))
    diag_fix = jnp.where(
        (jax.lax.broadcasted_iota(jnp.int32, (1, r_pad, r_pad), 1) >= r)
        | (jnp.arange(n_pad)[:, None, None] >= N),
        eye_tail, 0.0,
    )
    Ap = Ap + diag_fix
    bp = jnp.pad(b, ((0, n_pad - N), (0, r_pad - r)))

    # [N, b, a] -> [G, a, b, t]: column-major per matrix, batch in lanes
    G = n_pad // LANES
    At = jnp.transpose(
        Ap.reshape(G, LANES, r_pad, r_pad), (0, 3, 2, 1))
    bt = jnp.transpose(bp.reshape(G, LANES, r_pad), (0, 2, 1))

    kernel = functools.partial(_chol_lanes_kernel, r=r_pad, panel=panel,
                               mxu=mxu)
    xt = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, r_pad, LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r_pad, LANES), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((G, r_pad, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r_pad, r_pad, LANES), jnp.float32),
                        pltpu.VMEM((max(panel, 1), r_pad, LANES),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA],
        cost_estimate=pl.CostEstimate(
            flops=int(n_pad * (r_pad ** 3 + 4 * r_pad ** 2)),
            bytes_accessed=(n_pad * r_pad * r_pad + 2 * n_pad * r_pad) * 4,
            transcendentals=n_pad * r_pad,
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(r_pad)),
        interpret=interpret,
    )(At, bt)
    x = jnp.transpose(xt, (0, 2, 1)).reshape(n_pad, r_pad)
    return x[:N, :r]


from tpu_als.utils.platform import probe_cache as _probe_cache

_AVAILABLE = _probe_cache("pallas_lanes")  # r_pad -> bool, once per process
_PANEL = {}      # r_pad -> panel width that validated on this Mosaic
_MXU = {}        # r_pad -> True when the MXU trailing update validated


def selected_panel(rank):
    """Panel width ``available()`` validated for this rank (DEFAULT_PANEL
    until a probe has run)."""
    r_pad = -(-rank // 8) * 8
    return _PANEL.get(r_pad, DEFAULT_PANEL)


def selected_mxu(rank):
    """True when ``available()`` validated the MXU (lane-batched GEMM)
    trailing update for this rank on the local Mosaic; False until a
    probe has run — an unvalidated MXU update never engages (the same
    discipline as selected_panel)."""
    r_pad = -(-rank // 8) * 8
    return _MXU.get(r_pad, False)


def supported_rank(rank):
    """VMEM feasibility: the whole [r, r, LANES] working set is resident.
    At r_pad = 128 that scratch is 8 MiB and the trailing update's
    temporaries bring the kernel's scoped allocation to 19.33 MiB (MXU
    panel 32) / 24.50 MiB (VPU panel 8) as the v5e compiler counts it —
    over the 16 MiB default, hence :func:`_vmem_limit_bytes`; the rank-1
    rung fits the default.  Ranks above 128 are owned by the out-of-core
    blocked variant of this layout (tpu_als.ops.pallas_lanes_blocked),
    with tpu_als.ops.pallas_solve as the probe fallback."""
    r_pad = -(-rank // 8) * 8
    return r_pad <= 128


def available(rank=128):
    """True when the kernel compiles AND produces correct results on the
    local TPU at this rank — validated against the XLA lowering on a
    random SPD batch (same standard as pallas_solve.available)."""
    from tpu_als.utils.platform import ladder_reason, probe_kernel, try_rung

    r_pad = -(-rank // 8) * 8
    if not supported_rank(rank):
        return False

    def probe():
        import numpy as np

        from tpu_als.ops.solve import DEFAULT_JITTER, solve_spd

        n, r = LANES + 8, r_pad  # force 2 lane groups + batch padding
        rng = np.random.default_rng(0)
        M = rng.normal(size=(n, r, r)).astype(np.float32) / np.sqrt(r)
        A = jnp.asarray(
            M @ np.swapaxes(M, 1, 2)
            + 0.5 * np.eye(r, dtype=np.float32)[None])
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        ref = np.asarray(
            solve_spd(A, b, jnp.ones((n,), jnp.float32), backend="xla"))

        def attempt(p, mx):
            x = spd_solve_lanes(A + DEFAULT_JITTER * jnp.eye(r), b,
                                panel=p, mxu=mx)
            return np.allclose(np.asarray(x), ref, atol=1e-3, rtol=1e-2)

        # MXU panel GEMM first (the rank-k trailing update on the
        # systolic array), then the VPU panel sweep, then rank-1 — each
        # rung a strictly simpler lowering, so what the compiler refuses
        # costs one rung, and the notes say which and why
        notes = {}
        for p, mx in ((DEFAULT_MXU_PANEL, True), (DEFAULT_PANEL, False),
                      (1, False)):
            label = f"pallas_lanes[r={r_pad},panel={min(p, r_pad)},mxu={mx}]"
            if try_rung(notes, label, lambda: attempt(p, mx)):
                _PANEL[r_pad] = min(p, r_pad)
                _MXU[r_pad] = mx
                return True, ladder_reason(notes)
        return False, ladder_reason(notes)

    return probe_kernel(_AVAILABLE, r_pad, probe)
