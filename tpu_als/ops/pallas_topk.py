"""Pallas TPU kernel: fused GEMM + running top-k for recommendation serving.

The XLA path (tpu_als.ops.topk) streams item tiles through an einsum and
folds each tile into a running ``jax.lax.top_k`` — but XLA cannot fuse the
top-k into the matmul, so every [users, item_chunk] score tile makes a round
trip through HBM.  At ML-25M serving scale (160k users x 60k items) that is
~40 GB of score traffic for ~2.5 GFLOP of useful ranking work: purely
bandwidth-bound.

This kernel keeps the running (scores, ids) top-k block resident in VMEM
across the item-tile grid dimension (the output-revisiting pattern), computes
each [TU, TI] score tile on the MXU, and merges it in-register with k rounds
of vectorized argmax-extraction on the VPU.  Scores never touch HBM; HBM
traffic drops to the factor matrices themselves plus the [users, k] result.

The item factor table stays HBM-resident (``memory_space=ANY``) and its
tiles stream into a 2-slot VMEM ring via the shared double-buffer substrate
(:mod:`tpu_als.ops.ring_buffer`): :func:`ring_buffer.grid_pump` waits tile
``j`` and puts tile ``j+1``'s DMA in flight under tile ``j``'s GEMM+merge —
the same slot/semaphore discipline as ``pallas_gather_ne``'s row gather,
stated once.  (Under BlockSpec auto-pipelining the compiler ran an
equivalent schedule; owning the copy makes the kernel's HBM stream explicit
and substrate-audited — bytes and numerics are unchanged.)

Replaces the reference stack's ``recommendForAll`` (blockify + crossJoin +
per-block GEMM + BoundedPriorityQueue merge across a shuffle,
``mllib/.../recommendation/MatrixFactorizationModel.scala`` — SURVEY.md §3.3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_als.ops import ring_buffer as rb

NEG_INF = -3.4e38

# lane width: the merge buffer reserves one lane-tile for the carried best-k
LANES = 128


def _topk_kernel(U_ref, V_hbm, valid_ref, out_s_ref, out_i_ref, Vt, sem,
                 *, k, tile_i, n_ti):
    """One (user-tile, item-tile) grid cell.

    U_ref   [TU, r]      resident user factor tile
    V_hbm   [Ni, r]      the HBM-resident item factor table (``ANY``)
    valid_ref [1, TI]    1.0 = rankable item, 0.0 = padding/cold
    out_s/out_i [TU, LANES]  running best (revisited across the item grid
                         dim; only the first k lanes are meaningful)
    Vt [2, TI, r] / sem: the substrate's 2-slot item-tile ring — slot
    ``j%2`` holds this step's tile while ``j+1``'s DMA is in flight.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_s_ref[:] = jnp.full_like(out_s_ref, NEG_INF)
        out_i_ref[:] = jnp.zeros_like(out_i_ref)

    def _copy(e, slot):
        return rb.local_copy(
            V_hbm.at[pl.ds(e * tile_i, tile_i)], Vt.at[slot], sem.at[slot])

    rb.grid_pump(j, n_ti, _copy)

    tu = U_ref.shape[0]
    # [TU, TI] score tile on the MXU, streamed from the slot just waited
    scores = jax.lax.dot_general(
        U_ref[:], Vt[jax.lax.rem(j, 2)],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(valid_ref[0, :][None, :] > 0, scores, NEG_INF)
    ids = jax.lax.broadcasted_iota(jnp.int32, (tu, tile_i), 1) + j * tile_i

    # merge buffer: [TU, TI + LANES] = new tile ++ carried best
    merged_s = jnp.concatenate([scores, out_s_ref[:]], axis=1)
    merged_i = jnp.concatenate([ids, out_i_ref[:]], axis=1)

    # k rounds of argmax-extract (VPU): descending, first-index tie-break —
    # carried best sits at high columns so fresh (lower-id) entries win ties
    # the same way a single global top_k would only for distinct scores;
    # callers should not rely on tie order (the XLA path doesn't either).
    def extract(jj, carry):
        ms, mi, bs, bi = carry
        col = jnp.argmax(ms, axis=1)  # [TU]
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, ms.shape, 1)
            == col[:, None]
        )
        val = jnp.max(ms, axis=1)  # [TU]
        idx = jnp.sum(jnp.where(hit, mi, 0), axis=1)  # [TU]
        onecol = (
            jax.lax.broadcasted_iota(jnp.int32, bs.shape, 1) == jj
        )
        bs = jnp.where(onecol, val[:, None], bs)
        bi = jnp.where(onecol, idx[:, None], bi)
        ms = jnp.where(hit, NEG_INF, ms)
        return ms, mi, bs, bi

    best_s = jnp.full_like(out_s_ref, NEG_INF)
    best_i = jnp.zeros_like(out_i_ref)
    _, _, best_s, best_i = jax.lax.fori_loop(
        0, k, extract, (merged_s, merged_i, best_s, best_i)
    )
    out_s_ref[:] = best_s
    out_i_ref[:] = best_i


@functools.partial(
    jax.jit, static_argnames=("k", "tile_u", "tile_i", "interpret")
)
def topk_scores_pallas(U, V, item_valid, k, tile_u=256, tile_i=512,
                       interpret=False):
    """Top-k items per user row.  Same contract as
    :func:`tpu_als.ops.topk.chunked_topk_scores`: U [n, r], V [Ni, r],
    item_valid [Ni] bool; returns (scores [n, k], indices [n, k]) sorted
    descending.  ``k`` must be <= 128 (one lane tile carries the best list).
    """
    if k > LANES:
        raise ValueError(f"pallas top-k supports k <= {LANES}, got {k}")
    n, r = U.shape
    Ni = V.shape[0]

    n_pad = -(-n // tile_u) * tile_u
    i_pad = -(-Ni // tile_i) * tile_i
    r_pad = -(-r // LANES) * LANES
    Up = jnp.pad(U.astype(jnp.float32), ((0, n_pad - n), (0, r_pad - r)))
    Vp = jnp.pad(V.astype(jnp.float32), ((0, i_pad - Ni), (0, r_pad - r)))
    validp = jnp.pad(
        item_valid.astype(jnp.float32), (0, i_pad - Ni)
    ).reshape(1, i_pad)

    grid = (n_pad // tile_u, i_pad // tile_i)
    kernel = functools.partial(_topk_kernel, k=k, tile_i=tile_i,
                               n_ti=i_pad // tile_i)
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_u, r_pad), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, tile_i), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile_u, LANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_u, LANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_i, r_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * i_pad * r_pad,
            bytes_accessed=(n_pad * r_pad + i_pad * r_pad + 2 * n_pad * LANES)
            * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(Up, Vp, validp)
    return out_s[:n, :k], out_i[:n, :k]


from tpu_als.utils.platform import probe_cache as _probe_cache

_AVAILABLE = _probe_cache("pallas_topk")


def available(rank=128, k=10):
    """Compile-and-run probe, cached per (padded rank, k) — the kernel
    instantiation depends on both (k is a static loop bound; the rank sets
    the lane padding), so a verdict for one shape must not green-light
    another.  Validated against the XLA scan path, same contract as the
    solver kernels' ``available()``: a Mosaic regression (compile failure
    OR finite-but-wrong output) makes serving degrade to the XLA scan."""
    from tpu_als.utils.platform import probe_kernel

    r_pad = -(-max(1, rank) // LANES) * LANES
    k = min(k, LANES)

    def probe():
        import numpy as np

        from tpu_als.ops.topk import chunked_topk_scores

        rng = np.random.default_rng(0)
        # >= 2 user tiles and >= 2 item tiles so the output-revisiting
        # merge across the item grid dimension is exercised
        n, ni, r = 2 * 256, 2 * 512, r_pad
        U = (rng.normal(size=(n, r)) / np.sqrt(r)).astype(np.float32)
        V = (rng.normal(size=(ni, r)) / np.sqrt(r)).astype(np.float32)
        valid = jnp.asarray(np.ones(ni, bool))
        s, i = topk_scores_pallas(jnp.asarray(U), jnp.asarray(V), valid, k)
        rs, _ = chunked_topk_scores(jnp.asarray(U), jnp.asarray(V), valid, k)
        s.block_until_ready()
        s, i, rs = np.asarray(s), np.asarray(i), np.asarray(rs)
        # score VALUES must match the XLA scan; exact index equality is not
        # required (fp accumulation-order near-ties may rank-swap on a
        # healthy kernel) — instead the returned ids must reproduce the
        # returned scores under an independent host-side dot
        host = np.einsum("nr,nkr->nk", U, V[i])
        return (np.allclose(s, rs, atol=1e-4)
                and np.allclose(host, s, atol=1e-3))

    return probe_kernel(_AVAILABLE, (r_pad, k), probe)
