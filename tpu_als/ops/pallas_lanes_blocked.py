"""Pallas TPU kernel: batch-in-lanes Cholesky for ranks ABOVE 128.

The lanes kernel (tpu_als.ops.pallas_lanes) holds its whole ``[r, r, 128]``
working set in VMEM — 8 MiB at r=128, structurally capped there: r=256
would need 32 MiB against the 16 MiB limit (SURVEY.md §7 hard-part 2; the
rank-256 Amazon config, BASELINE.json configs[2], is exactly this shape).

This module extends the layout past 128 with an **out-of-core blocked
factorization** (VERDICT r3 #4): the matrix is tiled into 128×128 blocks;
one block at a time streams through the same ``[128, 128, LANES]``
lane-major VMEM working set; the factor is written back OVER the input in
HBM (``input_output_aliases`` — no second [N, r, r] allocation, which at
the rank-256 bench shape is gigabytes); and cross-block corrections
stream already-factored panels back from HBM in ``[panel, 128, LANES]``
slices.  The scratch is 8 MiB (block) + 2 × 0.5 MiB (stream buffers),
independent of rank; with the update temporaries the v5e compiler counts
24.56 MiB of scoped VMEM at the default 16 MiB limit and 40.49 MiB when
given room, hence ``_VMEM_LIMIT_BYTES``.

Right-looking block algorithm, all in the kernel's transposed layout
``S[col, row, lane]`` (column j of every lane's matrix is a leading-axis
slice, exactly as in pallas_lanes):

  for k in 0..nb:                      # nb = r_pad / 128 diagonal blocks
    W <- A[k,k];  W -= Σ_{m<k} L[k,m]·L[k,m]ᵀ   (streamed panels)
    factor W (panelized lanes recurrence);  L[k,k] <- W
    for i in k+1..nb:                  # blocks below the diagonal
      W <- A[i,k];  W -= Σ_{m<k} L[i,m]·L[k,m]ᵀ (two streams)
      W <- W · L[k,k]⁻ᵀ                (streamed right-looking tri-solve)
      L[i,k] <- W

The kernel factors ONLY (no substitution phases): the two triangular
substitutions are r² work that XLA's batched ``solve_triangular`` handles
well on the MXU — it is the r³ *factorization* whose XLA lowering is
column-sequential and slow (BASELINE.md round-2 ablation: the solve was
92% of the iteration before the first kernel).  Replaces the reference
stack's per-entity LAPACK ``dppsv`` at ranks the flat kernel cannot reach.

On-chip timing vs tpu_als.ops.pallas_solve at rank 256 is what
scripts/rank256_proxy.py measures (not measured yet); until a chip run
says otherwise the auto dispatch prefers this kernel above 128 because it
keeps the lanes layout's defining property — no cross-lane reductions or
selector matmuls in the serial chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_als.ops.ring_buffer import local_copy

LANES = 128
BLOCK = 128
PANEL = 8

# scoped-VMEM limit handed to the compiler (a v5e core has 128 MiB): at
# rank 256 the default 16 MiB refuses the kernel and 32 MiB still does
# (33.89 MiB MXU / 40.49 MiB VPU wanted); 64 MiB compiles both rungs
_VMEM_LIMIT_BYTES = 64 << 20

# see pallas_lanes._PREC — bf16 single-pass MXU error compounds through
# the Cholesky recurrence; HIGHEST keeps the GEMM rungs at f32 fidelity
_PREC = jax.lax.Precision.HIGHEST


def _chol_blocked_kernel(A_ref, out_ref, W, Bs, Cs, sem, *, nb, panel, mxu):
    """Factor one lane-group of ``nb·128``-rank matrices, blockwise.

    A_ref/out_ref [G, r_pad, r_pad, LANES] in HBM, ALIASED (the factor
    overwrites A).  Layout: [g, col, row, lane].  W [B, B, LANES] is the
    active block; Bs/Cs [panel, B, LANES] are streamed factor panels.
    After the kernel, blocks on/below the diagonal hold L (diag blocks
    with exact zeros above their diagonal); blocks ABOVE the diagonal
    still hold input values — callers take ``tril``.
    """
    g = pl.program_id(0)
    B = BLOCK
    sub = jax.lax.broadcasted_iota(jnp.int32, (B, LANES), 0)

    def dma(src, dst):
        cp = local_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def blk(ref, cb, rb):
        """[B, B, LANES] block view: column-block cb, row-block rb."""
        return ref.at[g, cb * B:(cb + 1) * B, rb * B:(rb + 1) * B]

    def fused_outer(S1, S2):
        """Σ_cc S1[cc] ⊗ S2[cc] over the panel axis -> [B, B, LANES].

        ``mxu=True`` runs it as ONE lane-batched rank-``panel`` GEMM
        (per lane a [B, panel]·[panel, B] MXU contraction — the Schur
        corrections are where the blocked algorithm's r³/3 FLOPs live,
        so this is the whole-kernel lever); False is the VPU broadcast
        sweep the probe ladder falls back to.
        """
        if mxu:
            upd = jax.lax.dot_general(
                S1[:], S2[:],
                dimension_numbers=(((0,), (0,)), ((2,), (2,))),
                preferred_element_type=jnp.float32, precision=_PREC,
            )  # [LANES, B, B]
            return jnp.transpose(upd, (1, 2, 0))
        upd = S1[0][:, None, :] * S2[0][None, :, :]
        for cc in range(1, panel):
            upd = upd + S1[cc][:, None, :] * S2[cc][None, :, :]
        return upd

    def factor_active():
        """Panelized lanes Cholesky of W in place (pallas_lanes
        panel_step, with Bs as the panel scratch)."""
        def panel_step(ip, _):
            base = ip * panel
            for jj in range(panel):
                j = base + jj
                cj = W[j]
                for kk in range(jj):
                    Lk = Bs[kk]
                    lkj = jnp.sum(jnp.where(sub == j, Lk, 0.0), axis=0)
                    cj = cj - Lk * lkj[None, :]
                d = jnp.sum(jnp.where(sub == j, cj, 0.0), axis=0)
                inv = jax.lax.rsqrt(jnp.maximum(d, 1e-30))
                Bs[jj] = jnp.where(sub >= j, cj * inv[None, :], 0.0)
            W[:] = W[:] - fused_outer(Bs, Bs)
            for jj in range(panel):
                W[base + jj] = Bs[jj]
            return 0

        jax.lax.fori_loop(0, B // panel, panel_step, 0, unroll=False)

    for k in range(nb):
        # ---- diagonal block: Schur corrections, then factorize ----
        dma(blk(A_ref, k, k), W)
        for m in range(k):
            for c0 in range(0, B, panel):
                dma(out_ref.at[g, m * B + c0:m * B + c0 + panel,
                               k * B:(k + 1) * B], Bs)
                W[:] = W[:] - fused_outer(Bs, Bs)
        factor_active()
        dma(W, blk(out_ref, k, k))

        # ---- blocks below: corrections, then L[i,k] = A[i,k]·L[k,k]⁻ᵀ ----
        for i in range(k + 1, nb):
            dma(blk(A_ref, k, i), W)
            for m in range(k):
                for c0 in range(0, B, panel):
                    sl = slice(m * B + c0, m * B + c0 + panel)
                    dma(out_ref.at[g, sl, k * B:(k + 1) * B], Bs)
                    dma(out_ref.at[g, sl, i * B:(i + 1) * B], Cs)
                    W[:] = W[:] - fused_outer(Bs, Cs)
            # right-looking triangular solve against streamed L[k,k]:
            # finalize the panel's columns left-looking (corrections from
            # columns inside the panel), then ONE fused update of all
            # later columns
            for c0 in range(0, B, panel):
                dma(out_ref.at[g, k * B + c0:k * B + c0 + panel,
                               k * B:(k + 1) * B], Bs)
                for jj in range(panel):
                    j = c0 + jj
                    cj = W[j]
                    for mm in range(jj):
                        # L_kk[j, c0+mm]: row j of the streamed column
                        lmj = jnp.sum(jnp.where(sub == j, Bs[mm], 0.0),
                                      axis=0)
                        cj = cj - W[c0 + mm] * lmj[None, :]
                    d = jnp.sum(jnp.where(sub == j, Bs[jj], 0.0), axis=0)
                    W[j] = cj / jnp.maximum(d, 1e-30)[None, :]
                # later columns a > c0+panel-1: W[a] -= Σ_jj
                # L_kk[a, c0+jj] · W[c0+jj]; panel rows ≤ c0+panel-1 are
                # zeroed so within-panel columns (already final) and
                # earlier columns receive nothing
                upd = None
                for jj in range(panel):
                    Bm = jnp.where(sub > c0 + panel - 1, Bs[jj], 0.0)
                    term = Bm[:, None, :] * W[c0 + jj][None, :, :]
                    upd = term if upd is None else upd + term
                W[:] = W[:] - upd
            dma(W, blk(out_ref, k, i))


@functools.partial(jax.jit, static_argnames=("panel", "mxu", "interpret"))
def chol_lanes_blocked(A, panel=None, mxu=False, interpret=False):
    """Batched lower-Cholesky factor L of SPD ``A`` [N, r, r] f32, via the
    blocked out-of-core lanes kernel.  Caller pre-regularizes A (jitter +
    identity for empty rows), same contract as the flat kernel.

    ``panel``: factor/stream panel width (must divide BLOCK=128; None =
    PANEL).  Exposed so scripts/kernel_lab.py can tune it on chip the
    same way the flat kernel's DEFAULT_PANEL was tuned.  ``mxu``: run the
    streamed Schur corrections as lane-batched MXU GEMMs (fused_outer) —
    pass ``selected_mxu(rank)`` so only a probe-validated variant
    engages."""
    if panel is None:
        panel = PANEL
    if BLOCK % panel:
        raise ValueError(f"panel {panel} must divide {BLOCK}")
    N, r = A.shape[0], A.shape[-1]
    nb = -(-r // BLOCK)
    r_pad = nb * BLOCK
    n_pad = -(-N // LANES) * LANES
    Ap = jnp.pad(A, ((0, n_pad - N), (0, r_pad - r), (0, r_pad - r)))
    # identity on padded rows/cols keeps the factorization finite there
    eye_tail = jnp.eye(r_pad, dtype=jnp.float32)[None]
    diag_fix = jnp.where(
        (jax.lax.broadcasted_iota(jnp.int32, (1, r_pad, r_pad), 1) >= r)
        | (jnp.arange(n_pad)[:, None, None] >= N),
        eye_tail, 0.0)
    Ap = Ap + diag_fix

    G = n_pad // LANES
    At = jnp.transpose(Ap.reshape(G, LANES, r_pad, r_pad), (0, 3, 2, 1))
    kernel = functools.partial(_chol_blocked_kernel, nb=nb, panel=panel,
                               mxu=mxu)
    Lt = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((G, r_pad, r_pad, LANES),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((BLOCK, BLOCK, LANES), jnp.float32),
            pltpu.VMEM((panel, BLOCK, LANES), jnp.float32),
            pltpu.VMEM((panel, BLOCK, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(n_pad * r_pad ** 3 / 3),
            bytes_accessed=int(n_pad * r_pad * r_pad * 4 * (nb + 2)),
            transcendentals=n_pad * r_pad,
        ),
        interpret=interpret,
    )(At)
    # [G, col, row, lane] -> [N, row, col]; blocks above the diagonal
    # still hold input values (never written) -> tril
    L = jnp.transpose(Lt, (0, 3, 2, 1)).reshape(n_pad, r_pad, r_pad)
    return jnp.tril(L[:N, :r, :r])


@functools.partial(jax.jit, static_argnames=("panel", "mxu", "interpret"))
def spd_solve_lanes_blocked(A, b, panel=None, mxu=False, interpret=False):
    """Batched SPD solve x = A⁻¹b for ranks > 128: blocked lanes
    factorization + XLA batched triangular substitutions (r² work the
    MXU handles; only the r³ factorization needed a kernel)."""
    L = chol_lanes_blocked(A, panel=panel, mxu=mxu, interpret=interpret)
    y = jax.scipy.linalg.solve_triangular(L, b[..., None], lower=True)
    return jax.scipy.linalg.solve_triangular(L, y, lower=True,
                                             trans=1)[..., 0]


from tpu_als.utils.platform import probe_cache as _probe_cache

_AVAILABLE = _probe_cache("pallas_lanes_blocked")  # r_pad -> bool
_MXU = {}  # r_pad -> bool: MXU fused_outer variant validated by probe


def selected_mxu(rank):
    """Whether the probe validated the MXU trailing-update variant at
    this rank (False until ``available`` has run; the VPU sweep is the
    conservative default)."""
    r_pad = -(-rank // BLOCK) * BLOCK
    return _MXU.get(r_pad, False)


def supported_rank(rank):
    """This kernel exists for ranks the flat lanes layout cannot hold;
    the streamed working set is rank-independent, so any rank above 128
    is structurally fine (padding rounds to 128-block multiples)."""
    return rank > 128


def available(rank=256):
    """True when the kernel compiles AND matches the XLA lowering on a
    random SPD batch at this rank on the local Mosaic (same standard as
    the other solve kernels)."""
    from tpu_als.utils.platform import ladder_reason, probe_kernel, try_rung

    if not supported_rank(rank):
        return False
    r_pad = -(-rank // BLOCK) * BLOCK

    def probe():
        import numpy as np

        from tpu_als.ops.solve import DEFAULT_JITTER, solve_spd

        n, r = LANES + 8, r_pad  # 2 lane groups + batch padding
        rng = np.random.default_rng(0)
        M = rng.normal(size=(n, r, r)).astype(np.float32) / np.sqrt(r)
        A = jnp.asarray(
            M @ np.swapaxes(M, 1, 2)
            + 0.5 * np.eye(r, dtype=np.float32)[None])
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        ref = np.asarray(
            solve_spd(A, b, jnp.ones((n,), jnp.float32), backend="xla"))

        def attempt(mx):
            x = spd_solve_lanes_blocked(
                A + DEFAULT_JITTER * jnp.eye(r), b, mxu=mx)
            return np.allclose(np.asarray(x), ref, atol=1e-3, rtol=1e-2)

        # Ladder: the MXU fused_outer first (lane-batched GEMM Schur
        # corrections), then the VPU sweep.
        notes = {}
        for mx in (True, False):
            label = f"pallas_lanes_blocked[r={r_pad},mxu={mx}]"
            if try_rung(notes, label, lambda: attempt(mx)):
                _MXU[r_pad] = mx
                return True, ladder_reason(notes)
        return False, ladder_reason(notes)

    return probe_kernel(_AVAILABLE, r_pad, probe)
