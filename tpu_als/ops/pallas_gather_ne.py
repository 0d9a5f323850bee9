"""Pallas TPU kernel: DMA-gather factor rows + fused Gram (normal-eq) build.

The unfused half-step (tpu_als.core.als.local_half_step) materializes the
gathered opposite factors ``Vg [n, w, r]`` in HBM: the XLA gather reads one
factor row per padded rating entry AND writes it into the gathered layout
(``2·P·r·db`` bytes), then the normal-equation einsum reads the whole thing
back (another ``P·r·db``).  At ML-25M/rank-128 that round-trip is the
co-dominant stage on the roofline floor (docs/roofline.md: gather_stream
95.76 ms + the einsum's re-read).  This kernel deletes it: the bucket's
``cols`` land in SMEM, each factor row is DMA-copied **directly from the
HBM-resident factor table** into a VMEM tile (double-buffered
``pltpu.make_async_copy``), and the Gram accumulation

    A = Σ_w  (aw·v) vᵀ        b = Σ_w  bw·v

runs on the VMEM tile as the rows stream through — ``Vg`` never exists in
HBM.  Each padded entry's factor row moves HBM→VMEM exactly once.

Two fusion depths share the DMA-gather front end:

* :func:`gather_gram` (``gather_normal_eq_*``) fuses ONLY gather + Gram
  build and writes ``A [n, r, r]`` / ``b [n, r]`` back to HBM; the
  ridge/YtY tail, the count, the empty-row guard and the SPD solve stay
  on the proven XLA / ``pallas_lanes`` paths (``tpu_als.ops.solve``).
* :func:`gather_solve` (``gather_fused_solve_*``) keeps going: the ridge/
  YtY/empty-guard tail and the blocked Cholesky + substitution from
  ``tpu_als.ops.pallas_solve`` run on the VMEM accumulator at the last
  width chunk, so ``A`` **never exists in HBM at all** — only ``x [n, r]``
  comes back.  This retires the old ``ops.pallas_fused`` attempt, which
  fused the same tail but still streamed an HBM-materialized ``Vg`` in
  (and whose per-column VPU recurrence made it 34× slower than
  einsum+lanes on v5e; the pallas_solve panel factorization used here
  does its trailing updates as batched MXU GEMMs).  Both depths are
  probe-gated independently — availability AND speed — so the planner
  picks the deepest fusion that actually wins on the local chip.

Numerics contract: :func:`gather_normal_eq_explicit` /
:func:`gather_normal_eq_implicit` are drop-in replacements for
``normal_eq_explicit(V[cols], …)`` / ``normal_eq_implicit(V[cols], …)``,
**bitwise at f32** for sublane-multiple widths that fit one width chunk
(every real bucket width — tpu_als.core.ratings.entity_widths only emits
%8==0 widths): the weights, the count, and the ridge/YtY tail are computed
by the *same* XLA expressions as the reference builders, and the in-kernel
contraction is the same ``dot_general`` the einsum lowers to, over the same
operands in the same dtypes (``compute_dtype=bfloat16`` flows through
unchanged — the table is gathered in the compute dtype, contractions
accumulate in f32 via ``preferred_element_type``).  Buckets whose padded
width spans several width chunks accumulate chunk-by-chunk, which matches
the einsum only to rounding (the property tests assert tight allclose
there, exact equality on single-chunk widths).

Grid: ``(row_tiles, width_chunks)``, width innermost; the ``[TN, r, r]``
accumulator persists across the width chunks of one row tile (the
standard Pallas revisiting pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_als.ops import ring_buffer as rb
from tpu_als.ops.solve import DEFAULT_JITTER, implicit_weights

# ring depth comes from the shared substrate (ops.ring_buffer) — kept as a
# module alias because the kernels' semaphore-ring scratch shapes and the
# ring_substrate contract both reference it
_DMA_SLOTS = rb.DMA_SLOTS


class TileBudgetError(ValueError):
    """The VMEM budget forces the fused-solve row tile below the
    panel-efficiency knee (TN < 8, a degenerate 1-row-tile grid whose
    factorization panels can no longer amortize their scoped-VMEM
    temporaries).  Raised instead of silently clamping — callers (the
    autotuner's search loop, or a hand-picked override) should treat the
    config as infeasible and widen ``vmem_budget`` or shrink ``panel``."""


def _weighted_row_sum(bw, Vg_t):
    """``Σ_k bw[t, k]·Vg_t[t, k, :]`` -> [TN, r] f32, the b-side
    accumulation shared by the three kernels below.  ``bw`` rides as
    [TN, 1, WC]: the chip's compiler refuses a ``dot_general`` whose left
    operand has only batch and contracting dimensions (no
    ``lhs_non_contracting_dims`` to parse), so it gets a unit free one."""
    return jax.lax.dot_general(
        bw[:, None, :], Vg_t,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :]


def _weighted_ridge(count, reg, wdt):
    """``reg · count`` as the reference builders compute it: in the weight
    dtype ``wdt``.  At f32 that is the plain product.  Below f32 the
    rounding is the explicit ``reduce_precision`` op, which the Pallas TPU
    lowering does not implement — a bf16 fused solve is refused by the
    chip's compiler with that message."""
    reg_w = jnp.asarray(reg, wdt).astype(jnp.float32)
    if jnp.dtype(wdt) == jnp.float32:
        return count * reg_w
    fi = jnp.finfo(wdt)
    return jax.lax.reduce_precision(
        jax.lax.reduce_precision(count, fi.nexp, fi.nmant) * reg_w,
        fi.nexp, fi.nmant)


def _gather_gram_kernel(cols_ref, aw_ref, bw_ref, V_hbm, A_ref, b_ref,
                        Vg, S, bacc, sem, *, n_wc, two_sided):
    """One (row-tile, width-chunk) grid step.

    cols_ref [TN, WC] (SMEM, scalar-readable DMA indices); aw/bw [TN, WC]
    (VMEM) — the A-side and b-side per-entry weights, precomputed by the
    wrappers with the reference builders' exact expressions; V_hbm [N, r]
    stays in HBM (``memory_space=ANY``).  Scratch: Vg [TN, WC, r] (the
    VMEM landing tile — the only place the gathered rows ever exist),
    S [TN, r, r] / bacc [TN, r] f32 accumulators, sem: DMA semaphore ring.

    two_sided=True applies ``aw`` to BOTH contraction operands (the
    explicit builder's ``Vm = Vg·mask`` on each side); False applies it to
    one side (the implicit builder's ``conf_m1·Vg`` against raw ``Vg``).
    """
    j = pl.program_id(1)
    tn, wc = cols_ref.shape
    n_e = tn * wc

    @pl.when(j == 0)
    def _init():
        S[:] = jnp.zeros_like(S)
        bacc[:] = jnp.zeros_like(bacc)

    def _copy(e, slot):
        t = e // wc
        k = e % wc
        return rb.local_copy(
            V_hbm.at[cols_ref[t, k]], Vg.at[t, k], sem.at[slot])

    # the substrate's multiple-buffering schedule: prime the ring, then
    # wait entry e / start entry e+depth into the slot e just vacated
    rb.pump(n_e, _copy)

    Vg_t = Vg[:]
    aw = aw_ref[:]
    Vw = Vg_t * aw[..., None]
    # same batched contraction the reference einsums lower to, accumulated
    # chunk-by-chunk in f32
    S[:] = S[:] + jax.lax.dot_general(
        Vw, Vw if two_sided else Vg_t,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    bacc[:] = bacc[:] + _weighted_row_sum(bw_ref[:], Vg_t)

    @pl.when(j == n_wc - 1)
    def _emit():
        A_ref[:] = S[:]
        b_ref[:] = bacc[:]


def _tiles(r_pad, w8, max_wc=256):
    """(TN, WC, W_PAD) for a bucket of (8-padded) width ``w8``.

    Mosaic constrains the LAST dim of a block to be a 128-multiple or the
    full array dim — the width is the last dim of the [TN, WC] cols/aw/bw
    blocks, so WC is the whole padded width or a 128-multiple dividing it
    (the pallas_fused lesson: 8-step shrinking passes interpret mode but
    fails the real lowering).  TN is bounded by the VMEM working set
    (S accumulator + the Vg landing tile + pipelined aw/bw blocks) and by
    the SMEM cols block (TN·WC int32 scalars).
    """
    if w8 <= max_wc:
        wc = w_pad = w8
    else:
        w_pad = -(-w8 // 128) * 128
        wc = max_wc - (max_wc % 128)
        while wc > 128 and w_pad % wc:
            wc -= 128
    tn = 256
    while tn > 8 and tn * (r_pad * r_pad + 3 * wc * r_pad) > (1 << 21):
        tn //= 2
    while tn > 8 and tn * wc > (1 << 13):
        tn //= 2
    return tn, wc, w_pad


@functools.partial(jax.jit, static_argnames=("two_sided", "interpret"))
def gather_gram(V, cols, aw, bw, *, two_sided, interpret=False):
    """Raw fused gather+Gram: ``S[i] = Σ_k aw[i,k]·v[i,k] v[i,k]ᵀ`` (both
    sides weighted when ``two_sided``), ``b[i] = Σ_k bw[i,k]·v[i,k]`` with
    ``v[i,k] = V[cols[i,k]]`` — the rows DMA'd straight from the
    HBM-resident ``V``, never materialized as an [n, w, r] intermediate.

    V [N, r] (any float dtype — bf16 halves the dominant HBM stream);
    cols [n, w] int32; aw/bw [n, w].  Returns (S [n, r, r] f32, b [n, r]
    f32).  The ridge/YtY/count tail lives in the gather_normal_eq_*
    wrappers so it stays bitwise-identical to ``normal_eq_*``.
    """
    N, r = V.shape
    n, w = cols.shape
    # rows are DMA'd as whole [r_pad] slices: pad the table's lane dim to
    # a 128 multiple once (a no-op at the rank-128 headline)
    r_pad = max(128, -(-r // 128) * 128)
    tn, wc, w_pad = _tiles(r_pad, -(-w // 8) * 8)
    assert wc == w_pad or (wc % 128 == 0 and w_pad % wc == 0), (wc, w_pad)
    n_pad = -(-n // tn) * tn
    V_p = jnp.pad(V, ((0, 0), (0, r_pad - r)))
    # padding slots index row 0 with zero weight — contributes nothing
    cols_p = jnp.pad(cols.astype(jnp.int32),
                     ((0, n_pad - n), (0, w_pad - w)))
    aw_p = jnp.pad(aw, ((0, n_pad - n), (0, w_pad - w)))
    bw_p = jnp.pad(bw, ((0, n_pad - n), (0, w_pad - w)))
    n_wc = w_pad // wc

    from tpu_als.perf.roofline import fused_ne_kernel_bytes

    db = jnp.dtype(V.dtype).itemsize
    kernel = functools.partial(
        _gather_gram_kernel, n_wc=n_wc, two_sided=two_sided)
    S, b = pl.pallas_call(
        kernel,
        grid=(n_pad // tn, n_wc),
        in_specs=[
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((tn, r_pad, r_pad), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn, r_pad), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, r_pad, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, r_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, wc, r_pad), V.dtype),
            pltpu.VMEM((tn, r_pad, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((rb.dma_slots(tn * wc),)),
        ],
        # bytes = THE roofline fused-stage model (perf.roofline) at the
        # kernel's padded shapes — tests/test_ne_audit.py extracts this
        # from the traced jaxpr and pins it to the model, the same way
        # test_comm_audit.py pins collective bytes
        cost_estimate=pl.CostEstimate(
            flops=int(2.0 * n_pad * w_pad * r_pad * (r_pad + 1)),
            bytes_accessed=fused_ne_kernel_bytes(
                n_pad * w_pad, n_pad, r_pad, db),
            transcendentals=0,
        ),
        interpret=interpret,
    )(cols_p, aw_p, bw_p, V_p)
    return S[:n, :r, :r], b[:n, :r]


def gather_normal_eq_explicit(V, cols, vals, mask, reg, *, interpret=False):
    """Fused-gather drop-in for ``normal_eq_explicit(V[cols], vals, mask,
    reg)`` — same returns ``(A, b, count)``, bitwise at f32 (module
    docstring), without ever materializing ``V[cols]`` in HBM.

    The weights and the ridge tail are the reference builder's exact
    expressions; only the gather+contraction runs in the kernel.
    """
    aw = mask
    bw = vals * mask
    S, b = gather_gram(V, cols, aw, bw, two_sided=True, interpret=interpret)
    count = jnp.sum(mask, axis=-1)
    r = V.shape[-1]
    eye = jnp.eye(r, dtype=S.dtype)
    A = S + (reg * count)[:, None, None] * eye
    return A, b, count


def gather_normal_eq_implicit(V, cols, vals, mask, reg, alpha, YtY, *,
                              interpret=False):
    """Fused-gather drop-in for ``normal_eq_implicit(V[cols], vals, mask,
    reg, alpha, YtY)`` — same returns ``(A, b, count)``, bitwise at f32.

    Confidence/preference come from the shared :func:`implicit_weights`
    (the one site normal_eq_implicit and solve_cg_matfree also use), the
    YtY + weighted-λ tail is the reference builder's exact expression.
    """
    conf_m1, pref = implicit_weights(vals, mask, alpha)
    aw = conf_m1
    bw = (1.0 + conf_m1) * pref * mask
    S, b = gather_gram(V, cols, aw, bw, two_sided=False,
                       interpret=interpret)
    count = jnp.sum(pref * mask, axis=-1)
    r = V.shape[-1]
    eye = jnp.eye(r, dtype=S.dtype)
    A = S + YtY[None] + (reg * count)[:, None, None] * eye
    return A, b, count


# --------------------------------------------------------------------------
# whole-iteration fusion: gather -> Gram -> ridge/YtY tail -> Cholesky solve
# --------------------------------------------------------------------------

def _gather_solve_kernel(cols_ref, aw_ref, bw_ref, cw_ref, YtY_ref, V_hbm,
                         x_ref, Vg, S, LT, bacc, cnt, sem, *, n_wc,
                         two_sided, panel, reg, jitter, depth=None):
    """One (row-tile, width-chunk) grid step of the fully fused half-step.

    Same DMA-gather + Gram front end as :func:`_gather_gram_kernel`, plus
    ``cw_ref [TN, WC]`` — the per-entry COUNT weights (explicit: the mask;
    implicit: ``pref·mask``), accumulated lane-uniform into ``cnt`` so the
    weighted ridge and the empty-row guard can apply in-kernel.  At the
    last width chunk the ridge/YtY/jitter tail (the ``solve_spd``
    pre-regularization, verbatim) is applied to the VMEM accumulator and
    the blocked Cholesky + substitution from ``tpu_als.ops.pallas_solve``
    produce ``x_ref [TN, r]`` — ``A`` is never written to HBM.
    """
    j = pl.program_id(1)
    tn, wc = cols_ref.shape
    r = S.shape[-1]
    n_e = tn * wc

    @pl.when(j == 0)
    def _init():
        S[:] = jnp.zeros_like(S)
        bacc[:] = jnp.zeros_like(bacc)
        cnt[:] = jnp.zeros_like(cnt)

    def _copy(e, slot):
        t = e // wc
        k = e % wc
        return rb.local_copy(
            V_hbm.at[cols_ref[t, k]], Vg.at[t, k], sem.at[slot])

    rb.pump(n_e, _copy, depth=depth)

    Vg_t = Vg[:]
    aw = aw_ref[:]
    Vw = Vg_t * aw[..., None]
    S[:] = S[:] + jax.lax.dot_general(
        Vw, Vw if two_sided else Vg_t,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    bacc[:] = bacc[:] + _weighted_row_sum(bw_ref[:], Vg_t)
    cnt[:] = cnt[:] + jnp.sum(
        cw_ref[:], axis=1).astype(jnp.float32)[:, None]  # lane-uniform

    @pl.when(j == n_wc - 1)
    def _solve():
        from tpu_als.ops.pallas_solve import factorize, substitute

        ii = jax.lax.broadcasted_iota(jnp.int32, (tn, r, r), 1)
        kk = jax.lax.broadcasted_iota(jnp.int32, (tn, r, r), 2)
        diag = ii == kk
        c3 = cnt[:][:, None, :]                       # [TN, 1, r] broadcast
        # a bf16 run's ridge is bf16-rounded; ``.astype`` pairs get
        # elided inside a jitted kernel (XLA excess precision), hence the
        # explicit op in _weighted_ridge.  Without it the fused diagonal
        # sits ~0.4% of λ·n off the unfused path's at bf16.
        ridge = _weighted_ridge(c3, reg, cw_ref.dtype)
        A = S[:] + YtY_ref[:][None].astype(jnp.float32)
        A = jnp.where(diag, A + ridge + jitter, A)
        # empty rows (count == 0): A := I so the factorization stays
        # finite; b is already 0 there so x = 0 — the solve_spd contract
        A = jnp.where(c3 <= 0.0, jnp.where(diag, 1.0 + jitter, 0.0), A)
        S[:] = A
        factorize(S, LT, tn=tn, r=r, panel=panel)
        x_ref[:] = substitute(LT, bacc[:], tn=tn, r=r, panel=panel)


def _tiles_solve(r_pad, w8, panel=16, max_wc=256, vmem_budget=1 << 17):
    """(TN, WC, W_PAD) for the fused-solve kernel: the gather kernel's
    tiling, shrunk further for the second [TN, r, r] scratch (LT) and
    capped so the factorization's scoped-VMEM stack (the ~20 live
    [TN, panel, r] temporaries at its deepest point — the pallas_fused
    round's measured overflow at rank 32 / TN 256) stays under the 16 MiB
    limit.  TN stays a sublane (8) multiple.

    ``vmem_budget`` is the factorization-stack element budget the cap is
    derived from (historically the hard-coded ``1 << 17``; now an
    autotuner knob).  A budget that forces the cap below the sublane
    minimum (TN < 8) is a degenerate grid, not a smaller tile — raise
    :class:`TileBudgetError` instead of silently clamping to 8 rows of a
    tile the factorization can't panel efficiently."""
    tn, wc, w_pad = _tiles(r_pad, w8, max_wc)
    while tn > 8 and tn * (2 * r_pad * r_pad + 3 * wc * r_pad) > (1 << 21):
        tn //= 2
    cap = int(vmem_budget) // (max(panel, 32) * r_pad)
    if cap < 8:
        raise TileBudgetError(
            f"vmem_budget {vmem_budget} caps the fused-solve row tile at "
            f"{cap} rows for r_pad={r_pad} panel={panel} — below the "
            f"8-row panel-efficiency knee; raise vmem_budget to at least "
            f"{8 * max(panel, 32) * r_pad} or shrink panel")
    tn = min(tn, cap)
    tn = max(8, (tn // 8) * 8)
    return tn, wc, w_pad


@functools.partial(jax.jit, static_argnames=("two_sided", "reg", "jitter",
                                             "panel", "max_wc",
                                             "vmem_budget", "depth",
                                             "interpret"))
def gather_solve(V, cols, aw, bw, cw, YtY=None, *, two_sided, reg,
                 jitter=DEFAULT_JITTER, panel=16, max_wc=256,
                 vmem_budget=1 << 17, depth=None, interpret=False):
    """Whole-iteration fused half-step core: DMA-gather ``V[cols]`` rows
    straight into VMEM, accumulate the weighted Gram, apply the ridge/YtY/
    empty-guard tail and solve — returns ``x [n, r]`` f32 only.  Neither
    the gathered rows nor the normal-equation matrices ever touch HBM.

    V [N, r] (any float dtype — bf16 halves the dominant HBM stream);
    cols [n, w] int32; aw/bw/cw [n, w] (A-side, b-side and count weights —
    the wrappers compute them with the reference builders' exact
    expressions).  ``reg``/``jitter`` are static floats baked into the
    kernel tail (the ``solve_spd`` pre-regularization, applied in VMEM).

    ``panel``/``max_wc``/``vmem_budget``/``depth`` are the autotuner's
    tiling knobs (perf.autotune); their defaults ARE the historical
    hand-picked constants, so an untuned call traces byte-identically to
    the pre-knob kernel.  ``depth=None`` keeps the substrate's own
    multiple-buffering depth (``ring_buffer.dma_slots``).
    """
    N, r = V.shape
    n, w = cols.shape
    r_pad = max(128, -(-r // 128) * 128)
    if r_pad % panel:
        raise ValueError(f"panel {panel} must divide padded rank {r_pad}")
    tn, wc, w_pad = _tiles_solve(r_pad, -(-w // 8) * 8, panel=panel,
                                 max_wc=max_wc, vmem_budget=vmem_budget)
    assert wc == w_pad or (wc % 128 == 0 and w_pad % wc == 0), (wc, w_pad)
    n_pad = -(-n // tn) * tn
    V_p = jnp.pad(V, ((0, 0), (0, r_pad - r)))
    # padding slots index row 0 with zero weight — contributes nothing;
    # padded batch rows have count 0 and hit the empty-row guard (x = 0)
    cols_p = jnp.pad(cols.astype(jnp.int32),
                     ((0, n_pad - n), (0, w_pad - w)))
    aw_p = jnp.pad(aw, ((0, n_pad - n), (0, w_pad - w)))
    bw_p = jnp.pad(bw, ((0, n_pad - n), (0, w_pad - w)))
    cw_p = jnp.pad(cw, ((0, n_pad - n), (0, w_pad - w)))
    YtY_p = (jnp.zeros((r_pad, r_pad), jnp.float32) if YtY is None
             else jnp.pad(YtY.astype(jnp.float32),
                          ((0, r_pad - r), (0, r_pad - r))))
    n_wc = w_pad // wc

    from tpu_als.perf.roofline import fused_solve_kernel_bytes

    db = jnp.dtype(V.dtype).itemsize
    eff_depth = (None if depth is None
                 else max(1, min(int(depth), rb.dma_slots(tn * wc))))
    kernel = functools.partial(
        _gather_solve_kernel, n_wc=n_wc, two_sided=two_sided, panel=panel,
        reg=float(reg), jitter=float(jitter), depth=eff_depth)
    x = pl.pallas_call(
        kernel,
        grid=(n_pad // tn, n_wc),
        in_specs=[
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn, wc), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r_pad, r_pad), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tn, r_pad), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, r_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tn, wc, r_pad), V.dtype),
            pltpu.VMEM((tn, r_pad, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((rb.dma_slots(tn * wc),)),
        ],
        # bytes = THE roofline fused-solve model (perf.roofline) at the
        # kernel's padded shapes — the fused_solve_audit contract
        # (analysis/contracts.py) extracts this from the traced jaxpr and
        # pins it to the closed form, the test_ne_audit.py pattern
        cost_estimate=pl.CostEstimate(
            flops=int(2.0 * n_pad * w_pad * r_pad * (r_pad + 1)
                      + n_pad * (r_pad ** 3 / 3 + 2 * r_pad ** 2)),
            bytes_accessed=fused_solve_kernel_bytes(
                n_pad * w_pad, n_pad, r_pad, db),
            transcendentals=n_pad * r_pad,
        ),
        interpret=interpret,
    )(cols_p, aw_p, bw_p, cw_p, YtY_p, V_p)
    return x[:n, :r]


def gather_fused_solve_explicit(V, cols, vals, mask, reg, *,
                                jitter=DEFAULT_JITTER, panel=16, max_wc=256,
                                vmem_budget=1 << 17, depth=None,
                                interpret=False):
    """Fused-gather drop-in for ``normal_eq_explicit(V[cols], …)`` +
    ``solve_spd`` — returns ``x`` only; A/b/Vg never exist in HBM.  The
    weights are the reference builder's exact expressions; the ridge/
    empty-guard tail runs in-kernel with the same arithmetic.  The tiling
    knobs default to the historical constants (see :func:`gather_solve`)."""
    aw = mask
    bw = vals * mask
    cw = mask
    return gather_solve(V, cols, aw, bw, cw, two_sided=True,
                        reg=float(reg), jitter=jitter, panel=panel,
                        max_wc=max_wc, vmem_budget=vmem_budget, depth=depth,
                        interpret=interpret)


def gather_fused_solve_implicit(V, cols, vals, mask, reg, alpha, YtY, *,
                                jitter=DEFAULT_JITTER, panel=16, max_wc=256,
                                vmem_budget=1 << 17, depth=None,
                                interpret=False):
    """Fused-gather drop-in for ``normal_eq_implicit(V[cols], …)`` +
    ``solve_spd`` — returns ``x`` only.  Confidence/preference come from
    the shared :func:`implicit_weights`; the YtY + weighted-λ tail applies
    in-kernel to the VMEM accumulator."""
    conf_m1, pref = implicit_weights(vals, mask, alpha)
    aw = conf_m1
    bw = (1.0 + conf_m1) * pref * mask
    cw = pref * mask
    return gather_solve(V, cols, aw, bw, cw, YtY, two_sided=False,
                        reg=float(reg), jitter=jitter, panel=panel,
                        max_wc=max_wc, vmem_budget=vmem_budget, depth=depth,
                        interpret=interpret)


# --------------------------------------------------------------------------
# Fused-comm ring: the whole-iteration kernel UNDER shard_map, with the
# inter-chip factor rotation moved INSIDE the kernel as a
# make_async_remote_copy ring (solve_backend="gather_fused_ring").
# --------------------------------------------------------------------------

# collective_id for the ring kernel's barrier semaphore (compiled path
# only); any process-unique small int works — it namespaces the barrier
# across distinct collective kernels, and this repo has exactly one
_RING_COLLECTIVE_ID = 7


def _gather_solve_ring_kernel(cols_ref, aw_ref, bw_ref, cw_ref, YtY_ref,
                              V_hbm, x_ref, buf0, buf1, Vg, S, LT, bacc,
                              cnt, sem, send_sem, recv_sem, ack_sem, *,
                              axis_name, n_shards, n_wc, two_sided, panel,
                              reg, jitter, sync, depth=None):
    """One (row-tile, ring-step, width-chunk) grid cell of the fused-comm
    half-step.  Grid dims ``(i, t, j)``: per row tile ``i``, ring step
    ``t`` streams source shard ``(me - t) % S`` — held in ``V_hbm`` at
    ``t == 0`` and in the substrate's two HBM landing buffers
    ``buf0``/``buf1`` (parity ``t % 2``) afterwards — while the remote
    copy forwarding the held shard to the RIGHT neighbor is in flight
    under the same gather/Gram front end as :func:`_gather_solve_kernel`.
    The weight blocks arrive pre-rotated by the wrapper (leading axis
    ``t`` indexes the shard held at step ``t``), so the accumulation is
    just the fused-solve kernel's, once per shard; the ridge/YtY/
    empty-guard tail and the blocked Cholesky solve run at the last
    ``(t, j)`` cell exactly as in the single-device kernel — at
    ``n_shards == 1`` the ring degenerates to :func:`_gather_solve_kernel`
    bitwise (no sends trace at all).

    ``sync`` (compiled path only — interpret mode emulates devices
    sequentially, so it validates the schedule and the numerics but NOT
    race-freedom, and remote ``semaphore_signal`` is not implemented by
    the interpreter): two extra arms close the two real-hardware races of
    a 2-buffer ring —

    * **ack backpressure**: my step-``t`` send lands in the right
      neighbor's ``buf[t % 2]``, which that neighbor reads as ``cur`` at
      step ``t - 1``; a sender running one step ahead would clobber it.
      After consuming ``cur(t)`` each receiver signals its LEFT
      neighbor's ``ack_sem`` (steps ``t <= S - 3`` — one ack per gated
      send), and every send at ``t >= 1`` waits one ack first.
    * **pass barrier**: row tile ``i + 1`` restarts the ring at ``t = 0``
      targeting ``buf0`` while a slower neighbor may still be reading its
      pass-``i`` buffers; each pass opens with a neighbor barrier on the
      ``collective_id``-scoped barrier semaphore.
    """
    t = pl.program_id(1)
    j = pl.program_id(2)
    _, tn, wc = cols_ref.shape
    r = S.shape[-1]
    n_e = tn * wc

    if n_shards > 1:
        me = jax.lax.axis_index(axis_name)
        right = jax.lax.rem(me + 1, n_shards)
        left = jax.lax.rem(me + n_shards - 1, n_shards)
        odd = jax.lax.rem(t, 2) == 1

        if sync:
            @pl.when((t == 0) & (j == 0))
            def _pass_barrier():
                bar = pltpu.get_barrier_semaphore()
                pltpu.semaphore_signal(
                    bar, 1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_signal(
                    bar, 1, device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_wait(bar, 2)

            @pl.when((t >= 1) & (t <= n_shards - 2) & (j == 0))
            def _ack_gate():
                pltpu.semaphore_wait(ack_sem, 1)

        # forward cur(t) to the right neighbor's landing buffer for step
        # t+1 (parity (t+1)%2 == destination buf[t%2]... the dst of step
        # t's send IS what the neighbor reads as cur(t+1)); three static
        # source variants because cur(t) is V_hbm / buf0 / buf1
        @pl.when((t == 0) & (j == 0))
        def _send_home():
            rb.remote_copy(V_hbm, buf0, send_sem, recv_sem, right).start()

        @pl.when((t >= 1) & (t <= n_shards - 2) & odd & (j == 0))
        def _send_odd():
            rb.remote_copy(buf0, buf1, send_sem, recv_sem, right).start()

        @pl.when((t >= 1) & (t <= n_shards - 2) & ~odd & (j == 0))
        def _send_even():
            rb.remote_copy(buf1, buf0, send_sem, recv_sem, right).start()

    @pl.when((t == 0) & (j == 0))
    def _init():
        S[:] = jnp.zeros_like(S)
        bacc[:] = jnp.zeros_like(bacc)
        cnt[:] = jnp.zeros_like(cnt)

    def _gather_from(src):
        def _copy(e, slot):
            tt = e // wc
            k = e % wc
            return rb.local_copy(
                src.at[cols_ref[0, tt, k]], Vg.at[tt, k], sem.at[slot])

        rb.pump(n_e, _copy, depth=depth)

    if n_shards == 1:
        _gather_from(V_hbm)
    else:
        @pl.when(t == 0)
        def _g_home():
            _gather_from(V_hbm)

        @pl.when((t >= 1) & odd)
        def _g_odd():
            _gather_from(buf0)

        @pl.when((t >= 1) & ~odd)
        def _g_even():
            _gather_from(buf1)

    Vg_t = Vg[:]
    aw = aw_ref[0]
    Vw = Vg_t * aw[..., None]
    S[:] = S[:] + jax.lax.dot_general(
        Vw, Vw if two_sided else Vg_t,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    bacc[:] = bacc[:] + _weighted_row_sum(bw_ref[0], Vg_t)
    cnt[:] = cnt[:] + jnp.sum(
        cw_ref[0], axis=1).astype(jnp.float32)[:, None]  # lane-uniform

    if n_shards > 1:
        @pl.when((t <= n_shards - 2) & (j == n_wc - 1))
        def _drain():
            # retire my send and the incoming shard (recv_sem is signaled
            # by the LEFT neighbor's symmetric send) before step t+1
            # reads the landing buffer; all variants share one shape, so
            # one canonical descriptor waits both semaphores
            d = rb.remote_copy(buf0, buf1, send_sem, recv_sem, right)
            d.wait_send()
            d.wait_recv()

        if sync:
            @pl.when((t <= n_shards - 3) & (j == n_wc - 1))
            def _ack_left():
                # cur(t) fully consumed (the last width chunk's pump has
                # retired) — free the left neighbor's next gated send
                pltpu.semaphore_signal(
                    ack_sem, 1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when((t == n_shards - 1) & (j == n_wc - 1))
    def _solve():
        from tpu_als.ops.pallas_solve import factorize, substitute

        ii = jax.lax.broadcasted_iota(jnp.int32, (tn, r, r), 1)
        kk = jax.lax.broadcasted_iota(jnp.int32, (tn, r, r), 2)
        diag = ii == kk
        c3 = cnt[:][:, None, :]                       # [TN, 1, r] broadcast
        ridge = _weighted_ridge(c3, reg, cw_ref.dtype)
        A = S[:] + YtY_ref[:][None].astype(jnp.float32)
        A = jnp.where(diag, A + ridge + jitter, A)
        A = jnp.where(c3 <= 0.0, jnp.where(diag, 1.0 + jitter, 0.0), A)
        S[:] = A
        factorize(S, LT, tn=tn, r=r, panel=panel)
        x_ref[:] = substitute(LT, bacc[:], tn=tn, r=r, panel=panel)


def gather_solve_ring(V_shard, cols, aw, bw, cw, YtY=None, *, two_sided,
                      reg, axis_name=None, jitter=DEFAULT_JITTER, panel=16,
                      max_wc=256, vmem_budget=1 << 17, depth=None,
                      interpret=False):
    """Fused-comm half-step core (inside ``shard_map``): one kernel call
    per bucket runs the WHOLE distributed iteration — the inter-chip ring
    rotation (``make_async_remote_copy``), the DMA row gather, the Gram
    accumulation across all ``S`` source shards, and the ridge/YtY/solve
    tail — overlapped on the substrate's shared double buffers.  Returns
    ``x [n, r]`` f32; neither the rotated shards (beyond the two ``[per,
    r]`` HBM landing buffers) nor A/b ever exist as XLA values.

    V_shard [per, r]: THIS device's shard of the opposite factors (compute
    dtype).  cols/aw/bw/cw [S, n, w]: the RingCsr bucket's shard-local
    column ids and weights, source-shard-major and UNROTATED — the wrapper
    rotates the leading axis by ``(me - t) % S`` so block ``t`` always
    weighs the shard held at ring step ``t``.  ``axis_name`` names the
    mesh axis (required when ``S > 1``).

    Off-TPU pass ``interpret=True`` (the forced-host-device CPU mesh):
    numerics and schedule are exercised, the hardware-race arms (ack
    backpressure + pass barrier, see the kernel docstring) compile only
    on real meshes.
    """
    per, r = V_shard.shape
    n_shards, n, w = cols.shape
    r_pad = max(128, -(-r // 128) * 128)
    if r_pad % panel:
        raise ValueError(f"panel {panel} must divide padded rank {r_pad}")
    tn, wc, w_pad = _tiles_solve(r_pad, -(-w // 8) * 8, panel=panel,
                                 max_wc=max_wc, vmem_budget=vmem_budget)
    assert wc == w_pad or (wc % 128 == 0 and w_pad % wc == 0), (wc, w_pad)
    n_pad = -(-n // tn) * tn
    V_p = jnp.pad(V_shard, ((0, 0), (0, r_pad - r)))

    if n_shards > 1:
        if axis_name is None:
            raise ValueError("axis_name is required when n_shards > 1")
        me = jax.lax.axis_index(axis_name)
        src_order = jnp.mod(
            me - jnp.arange(n_shards, dtype=jnp.int32), n_shards)

        def _rot(x):
            return jnp.take(x, src_order, axis=0)
    else:
        def _rot(x):
            return x

    def _prep(x):
        # padding slots index row 0 with zero weight; padded batch rows
        # have count 0 and hit the empty-row guard (x = 0) — the
        # gather_solve contract
        return jnp.pad(_rot(x), ((0, 0), (0, n_pad - n), (0, w_pad - w)))

    cols_p = _prep(cols.astype(jnp.int32))
    aw_p = _prep(aw)
    bw_p = _prep(bw)
    cw_p = _prep(cw)
    YtY_p = (jnp.zeros((r_pad, r_pad), jnp.float32) if YtY is None
             else jnp.pad(YtY.astype(jnp.float32),
                          ((0, r_pad - r), (0, r_pad - r))))
    n_wc = w_pad // wc
    n_rt = n_pad // tn

    from tpu_als.perf.roofline import fused_ring_kernel_bytes, \
        ring_remote_bytes

    db = jnp.dtype(V_shard.dtype).itemsize
    sync = not interpret and n_shards > 1
    eff_depth = (None if depth is None
                 else max(1, min(int(depth), rb.dma_slots(tn * wc))))
    kernel = functools.partial(
        _gather_solve_ring_kernel, axis_name=axis_name, n_shards=n_shards,
        n_wc=n_wc, two_sided=two_sided, panel=panel, reg=float(reg),
        jitter=float(jitter), sync=sync, depth=eff_depth)
    x = pl.pallas_call(
        kernel,
        grid=(n_rt, n_shards, n_wc),
        in_specs=[
            pl.BlockSpec((1, tn, wc), lambda i, t, j: (t, i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tn, wc), lambda i, t, j: (t, i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tn, wc), lambda i, t, j: (t, i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tn, wc), lambda i, t, j: (t, i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r_pad, r_pad), lambda i, t, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tn, r_pad), lambda i, t, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, r_pad), jnp.float32),
        scratch_shapes=[
            pl.ANY((per, r_pad), V_shard.dtype),   # buf0 (HBM landing)
            pl.ANY((per, r_pad), V_shard.dtype),   # buf1
            pltpu.VMEM((tn, wc, r_pad), V_shard.dtype),
            pltpu.VMEM((tn, r_pad, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad), jnp.float32),
            pltpu.VMEM((tn, r_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((rb.dma_slots(tn * wc),)),
            pltpu.SemaphoreType.DMA,      # send
            pltpu.SemaphoreType.DMA,      # recv
            pltpu.SemaphoreType.REGULAR,  # ack (sync arm only)
        ],
        # bytes = THE roofline fused-comm model (perf.roofline): the
        # fused-solve stream plus the in-kernel remote-DMA ring payload —
        # the extended comm_audit contract (analysis/contracts.py)
        # extracts both from the traced kernel and pins them to the
        # closed forms
        cost_estimate=pl.CostEstimate(
            flops=int(2.0 * n_pad * n_shards * w_pad * r_pad * (r_pad + 1)
                      + n_pad * (r_pad ** 3 / 3 + 2 * r_pad ** 2)),
            bytes_accessed=fused_ring_kernel_bytes(
                n_pad * n_shards * w_pad, n_pad, r_pad, db,
                ring_remote_bytes(n_rt, n_shards, per, r_pad, db)),
            transcendentals=n_pad * r_pad,
        ),
        compiler_params=(
            pltpu.CompilerParams(collective_id=_RING_COLLECTIVE_ID)
            if sync else None),
        interpret=interpret,
    )(cols_p, aw_p, bw_p, cw_p, YtY_p, V_p)
    return x[:n, :r]


def gather_fused_ring_explicit(V_shard, cols, vals, mask, reg, *,
                               axis_name=None, jitter=DEFAULT_JITTER,
                               panel=16, max_wc=256, vmem_budget=1 << 17,
                               depth=None, interpret=False):
    """Fused-comm drop-in for one explicit ring half-step: the reference
    builders' exact weight expressions over the UNROTATED [S, n, w] bucket
    arrays, then one :func:`gather_solve_ring` call.  At ``S == 1`` this
    is :func:`gather_fused_solve_explicit` bitwise (same kernel body, no
    sends)."""
    aw = mask
    bw = vals * mask
    cw = mask
    return gather_solve_ring(V_shard, cols, aw, bw, cw, two_sided=True,
                             reg=float(reg), axis_name=axis_name,
                             jitter=jitter, panel=panel, max_wc=max_wc,
                             vmem_budget=vmem_budget, depth=depth,
                             interpret=interpret)


def gather_fused_ring_implicit(V_shard, cols, vals, mask, reg, alpha, YtY,
                               *, axis_name=None, jitter=DEFAULT_JITTER,
                               panel=16, max_wc=256, vmem_budget=1 << 17,
                               depth=None, interpret=False):
    """Fused-comm drop-in for one implicit ring half-step — weights from
    the shared :func:`implicit_weights`, YtY + weighted-λ tail in-kernel."""
    conf_m1, pref = implicit_weights(vals, mask, alpha)
    aw = conf_m1
    bw = (1.0 + conf_m1) * pref * mask
    cw = pref * mask
    return gather_solve_ring(V_shard, cols, aw, bw, cw, YtY,
                             two_sided=False, reg=float(reg),
                             axis_name=axis_name, jitter=jitter,
                             panel=panel, max_wc=max_wc,
                             vmem_budget=vmem_budget, depth=depth,
                             interpret=interpret)


from tpu_als.utils.platform import probe_cache as _probe_cache

_AVAILABLE = _probe_cache("pallas_gather_ne")
_FASTER = _probe_cache("pallas_gather_ne_speed")


def available(rank=128, compute_dtype="float32"):
    """Compile-and-validate probe, cached per (padded rank, dtype) — the
    probe_kernel contract (off-TPU → False; a Mosaic rejection caches
    False so callers stay on the einsum path).  Validates BOTH kernel
    variants (explicit/two-sided and implicit/one-sided compile different
    bodies) against the unfused builders on a multi-row-tile,
    multi-width-chunk instance, so a miscompile producing finite-but-wrong
    values also fails."""
    from tpu_als.utils.platform import probe_kernel

    r_pad = max(128, -(-rank // 128) * 128)
    cdt = str(compute_dtype)

    def probe():
        import numpy as np

        from tpu_als.ops.solve import normal_eq_explicit, normal_eq_implicit

        dt = jnp.dtype(cdt)
        # >= 2 row tiles and >= 2 width chunks: exercise the accumulator
        # revisiting across the inner grid dim and the DMA ring reuse
        w = 256
        while True:
            tn, wc, w_pad = _tiles(r_pad, w)
            if w_pad // wc >= 2:
                break
            w *= 2
        n, N = 2 * tn, 3 * tn
        rng = np.random.default_rng(0)
        V = jnp.asarray(rng.normal(size=(N, rank)).astype(np.float32)
                        / np.sqrt(rank)).astype(dt)
        cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
        mask = jnp.asarray((rng.random((n, w)) < 0.8).astype(np.float32))
        tol = dict(atol=1e-3, rtol=1e-2)
        A, b, c = gather_normal_eq_explicit(
            V, cols, vals.astype(dt), mask.astype(dt), 0.1)
        Ar, br, cr = normal_eq_explicit(
            V[cols], vals.astype(dt), mask.astype(dt), 0.1)
        A.block_until_ready()
        if not (np.allclose(np.asarray(A), np.asarray(Ar), **tol)
                and np.allclose(np.asarray(b), np.asarray(br), **tol)):
            return False
        YtY = jnp.asarray(rng.normal(size=(rank, rank)).astype(np.float32))
        YtY = YtY @ YtY.T / rank
        Ai, bi, ci = gather_normal_eq_implicit(
            V, cols, vals.astype(dt), mask.astype(dt), 0.1, 4.0, YtY)
        Air, bir, cir = normal_eq_implicit(
            V[cols], vals.astype(dt), mask.astype(dt), 0.1, 4.0, YtY)
        Ai.block_until_ready()
        return bool(np.allclose(np.asarray(Ai), np.asarray(Air), **tol)
                    and np.allclose(np.asarray(bi), np.asarray(bir), **tol))

    return probe_kernel(_AVAILABLE, (r_pad, cdt), probe)


def faster_than_einsum(rank=128, compute_dtype="float32", n=2048, w=256,
                       reps=3):
    """Timing probe: True only when the fused kernel BEATS the XLA
    gather+einsum build on a representative bucket — the auto path
    selects the kernel on this outcome, never on availability alone
    (the fused_pallas lesson: available ≠ faster).  Cached per process
    via probe_kernel (off-TPU → False)."""
    from tpu_als.utils.platform import fence, probe_kernel

    r_pad = max(128, -(-rank // 128) * 128)
    cdt = str(compute_dtype)

    def probe():
        import time

        import numpy as np

        from tpu_als.ops.solve import normal_eq_explicit

        if not available(rank, cdt):
            return False, "kernel not available"
        dt = jnp.dtype(cdt)
        rng = np.random.default_rng(0)
        N = 4 * n
        V = jnp.asarray(rng.normal(size=(N, rank)).astype(np.float32)
                        / np.sqrt(rank)).astype(dt)
        cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(dt))
        mask = jnp.asarray((rng.random((n, w)) < 0.9).astype(dt))

        @jax.jit
        def fused(V, cols, vals, mask):
            return gather_normal_eq_explicit(V, cols, vals, mask, 0.1)

        @jax.jit
        def einsum(V, cols, vals, mask):
            return normal_eq_explicit(V[cols], vals, mask, 0.1)

        def best(f):
            fence(f(V, cols, vals, mask)[0])  # compile + warm
            t = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fence(f(V, cols, vals, mask)[0])
                t.append(time.perf_counter() - t0)
            return min(t)

        tf, te = best(fused), best(einsum)
        return tf < te, (
            f"{'won' if tf < te else 'lost'} the timing probe: fused "
            f"{tf * 1e3:.3f} ms vs einsum {te * 1e3:.3f} ms "
            f"(min of {reps}, n={n}, w={w})")

    return probe_kernel(_FASTER, ("speed", r_pad, cdt, n, w), probe)


_SOLVE_AVAILABLE = _probe_cache("pallas_gather_solve")
_SOLVE_FASTER = _probe_cache("pallas_gather_solve_speed")


def solve_available(rank=128, compute_dtype="float32"):
    """Compile-and-validate probe for the whole-iteration fused kernel,
    cached per (padded rank, dtype) — same contract as :func:`available`.
    Validates BOTH variants (explicit and implicit compile different
    bodies) against the unfused builders + ``solve_spd`` on a
    multi-row-tile, multi-width-chunk instance."""
    from tpu_als.utils.platform import probe_kernel

    r_pad = max(128, -(-rank // 128) * 128)
    cdt = str(compute_dtype)

    def probe():
        import numpy as np

        from tpu_als.ops.solve import (normal_eq_explicit,
                                       normal_eq_implicit, solve_spd)

        dt = jnp.dtype(cdt)
        w = 256
        while True:
            tn, wc, w_pad = _tiles_solve(r_pad, w)
            if w_pad // wc >= 2:
                break
            w *= 2
        n, N = 2 * tn, 3 * tn
        rng = np.random.default_rng(0)
        V = jnp.asarray(rng.normal(size=(N, rank)).astype(np.float32)
                        / np.sqrt(rank)).astype(dt)
        cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
        mask = jnp.asarray((rng.random((n, w)) < 0.8).astype(np.float32))
        tol = dict(atol=1e-3, rtol=1e-2)
        x = gather_fused_solve_explicit(
            V, cols, vals.astype(dt), mask.astype(dt), 0.1)
        A, b, c = normal_eq_explicit(
            V[cols], vals.astype(dt), mask.astype(dt), 0.1)
        ref = solve_spd(A, b, c, backend="xla")
        x.block_until_ready()
        if not np.allclose(np.asarray(x), np.asarray(ref), **tol):
            return False
        YtY = jnp.asarray(rng.normal(size=(rank, rank)).astype(np.float32))
        YtY = YtY @ YtY.T / rank
        xi = gather_fused_solve_implicit(
            V, cols, vals.astype(dt), mask.astype(dt), 0.1, 4.0, YtY)
        Ai, bi, ci = normal_eq_implicit(
            V[cols], vals.astype(dt), mask.astype(dt), 0.1, 4.0, YtY)
        refi = solve_spd(Ai, bi, ci, backend="xla")
        xi.block_until_ready()
        return bool(np.allclose(np.asarray(xi), np.asarray(refi), **tol))

    return probe_kernel(_SOLVE_AVAILABLE, (r_pad, cdt), probe)


def solve_faster_than_unfused(rank=128, compute_dtype="float32", n=2048,
                              w=256, reps=3):
    """Timing probe: True only when the whole-iteration fused kernel
    BEATS the current best unfused composition (the gather-Gram kernel
    when IT probes faster, else the XLA gather+einsum, followed by
    ``solve_spd(backend='auto')``) on a representative bucket — the
    fused_pallas lesson (available ≠ faster) applied to the deeper
    fusion.  Cached per process via probe_kernel (off-TPU → False)."""
    from tpu_als.utils.platform import fence, probe_kernel

    r_pad = max(128, -(-rank // 128) * 128)
    cdt = str(compute_dtype)

    def probe():
        import time

        import numpy as np

        from tpu_als.ops.solve import normal_eq_explicit, solve_spd

        if not solve_available(rank, cdt):
            return False, "kernel not available"
        dt = jnp.dtype(cdt)
        rng = np.random.default_rng(0)
        N = 4 * n
        V = jnp.asarray(rng.normal(size=(N, rank)).astype(np.float32)
                        / np.sqrt(rank)).astype(dt)
        cols = jnp.asarray(rng.integers(0, N, size=(n, w)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(n, w)).astype(dt))
        mask = jnp.asarray((rng.random((n, w)) < 0.9).astype(dt))
        use_gather_ne = faster_than_einsum(rank, cdt, n=n, w=w, reps=reps)

        @jax.jit
        def fused(V, cols, vals, mask):
            return gather_fused_solve_explicit(V, cols, vals, mask, 0.1)

        @jax.jit
        def unfused(V, cols, vals, mask):
            if use_gather_ne:
                A, b, c = gather_normal_eq_explicit(V, cols, vals, mask,
                                                    0.1)
            else:
                A, b, c = normal_eq_explicit(V[cols], vals, mask, 0.1)
            return solve_spd(A, b, c)

        def best(f):
            fence(f(V, cols, vals, mask))  # compile + warm
            t = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fence(f(V, cols, vals, mask))
                t.append(time.perf_counter() - t0)
            return min(t)

        tf, tu = best(fused), best(unfused)
        return tf < tu, (
            f"{'won' if tf < tu else 'lost'} the timing probe: fused "
            f"{tf * 1e3:.3f} ms vs unfused {tu * 1e3:.3f} ms "
            f"(min of {reps}, n={n}, w={w})")

    return probe_kernel(_SOLVE_FASTER, ("speed", r_pad, cdt, n, w), probe)


_RING_AVAILABLE = _probe_cache("pallas_gather_ring")


def ring_available(rank=128, compute_dtype="float32", n_shards=None):
    """Compile-and-validate probe for the fused-comm ring kernel ON THE
    LIVE MESH, cached per (padded rank, dtype, n_shards) — the gate
    ``trainer.make_ring_step`` consults before adopting
    ``solve_backend='gather_fused_ring'`` on hardware.

    Unlike the single-device probes this one executes a COLLECTIVE (the
    in-kernel remote-DMA ring under ``shard_map`` over the first
    ``n_shards`` local devices), so its verdict is only meaningful for
    the mesh it ran on — the cache key carries ``n_shards``, and the
    planner's persistence layer (utils.platform.snapshot_probes) may bank
    it like any other probe because the CONSUMER re-validates shape: a
    banked verdict for a different shard count is a cache miss, never a
    steer.  Validates explicit AND implicit variants against the
    single-device whole-iteration kernel on the concatenated global
    column space.  Off-TPU → False (the CPU path doesn't need it: the
    interpret-mode kernel is dispatched unconditionally there).
    """
    from tpu_als.utils.platform import probe_kernel

    if n_shards is None:
        n_shards = jax.device_count()
    r_pad = max(128, -(-rank // 128) * 128)
    cdt = str(compute_dtype)

    def probe():
        import functools as ft

        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from tpu_als.parallel.mesh import shard_map

        if jax.device_count() < n_shards:
            return False, f"{jax.device_count()} devices < {n_shards} shards"
        S = n_shards
        ax = "ring_probe"
        mesh = Mesh(np.array(jax.devices()[:S]), (ax,))
        dt = jnp.dtype(cdt)
        rng = np.random.default_rng(0)
        tn, _, _ = _tiles_solve(r_pad, 16)
        per, n, w = 64, tn + 8, 16  # ragged: one partial kernel row tile
        V = jnp.asarray(rng.normal(size=(S * per, rank))
                        .astype(np.float32) / np.sqrt(rank)).astype(dt)
        cols = rng.integers(0, per, size=(S, S, n, w)).astype(np.int32)
        vals = rng.normal(size=(S, S, n, w)).astype(np.float32)
        mask = (rng.random(size=(S, S, n, w)) < 0.8).astype(np.float32)
        YtY = np.asarray(V.astype(jnp.float32).T @ V.astype(jnp.float32))

        @jax.jit
        @ft.partial(shard_map, mesh=mesh,
                    in_specs=(P(ax), P(ax), P(ax), P(ax), P()),
                    out_specs=(P(ax), P(ax)), check_vma=False)
        def run(V_shard, c, v, m, yty):
            xe = gather_fused_ring_explicit(
                V_shard, c[0], v[0].astype(dt), m[0].astype(dt), 0.1,
                axis_name=ax)
            xi = gather_fused_ring_implicit(
                V_shard, c[0], v[0].astype(dt), m[0].astype(dt), 0.1,
                4.0, yty, axis_name=ax)
            return xe[None], xi[None]

        xe, xi = run(V, jnp.asarray(cols), jnp.asarray(vals),
                     jnp.asarray(mask), jnp.asarray(YtY))
        xe.block_until_ready()
        xe, xi = np.asarray(xe), np.asarray(xi)
        tol = dict(atol=1e-3, rtol=1e-2)
        for d in range(S):
            gc = np.concatenate([cols[d, s] + s * per for s in range(S)],
                                axis=1)
            gv = np.concatenate([vals[d, s] for s in range(S)], axis=1)
            gm = np.concatenate([mask[d, s] for s in range(S)], axis=1)
            re_ = gather_fused_solve_explicit(
                V, jnp.asarray(gc), jnp.asarray(gv).astype(dt),
                jnp.asarray(gm).astype(dt), 0.1)
            ri = gather_fused_solve_implicit(
                V, jnp.asarray(gc), jnp.asarray(gv).astype(dt),
                jnp.asarray(gm).astype(dt), 0.1, 4.0, jnp.asarray(YtY))
            if not (np.allclose(xe[d], np.asarray(re_), **tol)
                    and np.allclose(xi[d], np.asarray(ri), **tol)):
                return False
        return True

    return probe_kernel(_RING_AVAILABLE, (r_pad, cdt, n_shards), probe)
