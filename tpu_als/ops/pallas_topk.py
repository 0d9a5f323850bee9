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


# collective_id for the serving merge ring — distinct from the training
# ring's _RING_COLLECTIVE_ID (pallas_gather_ne) so a pod running both
# kernels never aliases their barrier semaphores
_MERGE_COLLECTIVE_ID = 8


def _stable_extract(ms, mi, k, tu):
    """k rounds of argmax-extraction reproducing ``jax.lax.top_k``'s
    STABLE order bitwise: descending values, first-column tie-break.

    Unlike :func:`_topk_kernel`'s extract (which retires taken slots to
    ``NEG_INF`` and so re-picks sentinel columns arbitrarily), taken
    slots retire to ``-inf`` — strictly below the ``NEG_INF`` sentinel —
    so successive argmaxes select distinct earliest-untaken columns the
    same way a stable sort would, sentinels included.  This is what lets
    the cross-shard merge promise BITWISE equality (scores AND ids)
    with :func:`tpu_als.ops.topk.chunked_topk_scores`; callers place the
    carried best at LOW columns (earliest-seen wins ties, the chunked
    scan's ``[best_s, scores]`` order).
    """
    def extract(jj, carry):
        ms, mi, bs, bi = carry
        col = jnp.argmax(ms, axis=1)  # first max column per row
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, ms.shape, 1)
            == col[:, None]
        )
        val = jnp.max(ms, axis=1)
        idx = jnp.sum(jnp.where(hit, mi, 0), axis=1)
        onecol = (
            jax.lax.broadcasted_iota(jnp.int32, bs.shape, 1) == jj
        )
        bs = jnp.where(onecol, val[:, None], bs)
        bi = jnp.where(onecol, idx[:, None], bi)
        ms = jnp.where(hit, -jnp.inf, ms)
        return ms, mi, bs, bi

    bs = jnp.full((tu, LANES), NEG_INF, jnp.float32)
    bi = jnp.zeros((tu, LANES), jnp.int32)
    _, _, bs, bi = jax.lax.fori_loop(0, k, extract, (ms, mi, bs, bi))
    return bs, bi


def _topk_merge_ring_kernel(U_ref, V_hbm, valid_ref, out_s_ref, out_i_ref,
                            Vt, coll, sem, send_sem, recv_sem, *, k, tile_i,
                            n_ti, axis_name, n_shards, ni_loc, sync):
    """One (user-tile, phase) grid cell of the cross-shard serving merge.

    Grid dims ``(i, p)`` with ``p`` ranging over ``n_ti + S`` phases:

    * ``p < n_ti`` — score item tile ``p`` of THIS device's catalog shard
      against the replicated query tile (the :func:`_topk_kernel` GEMM +
      merge, streamed through the substrate's 2-slot VMEM ring) into the
      running best refs; ids are globalized as ``me * ni_loc + local``.
      At the last tile the finished local candidate set is packed into
      ``coll[me]`` — scores in lanes ``[0, LANES)``, ids bitcast to f32
      in lanes ``[LANES, 2·LANES)``.
    * ``n_ti <= p < n_ti + S - 1`` — ring hop ``h = p - n_ti + 1``: send
      the set SOURCED from shard ``(me - h + 1) % S`` (received last hop;
      own set at ``h = 1``) to the right neighbor's same ``coll`` slot as
      one ``remote_copy``, and retire this hop's send + the incoming set
      from the left.  Slot identity is keyed on the SOURCE shard, so
      sender and receiver agree and every slot is written exactly once
      per pass — no ack backpressure is needed (each hop's send reads the
      slot the previous hop's ``wait_recv`` retired, so no device can run
      ahead within a pass), only the pass barrier below.
    * ``p == n_ti + S - 1`` — merge ``coll[0..S-1]`` in shard order with
      :func:`_stable_extract` (carried-at-low-columns), which makes the
      result bitwise-equal to ``chunked_topk_scores`` over the
      concatenated global catalog, tie-break included.

    Per-shard candidate lists exist only in the ``coll`` VMEM scratch —
    never as an XLA value in HBM (the ``serve_comm_audit`` contract pins
    this, plus the remote-DMA byte count, against the roofline closed
    form).  ``sync`` (compiled path only): pass barrier at ``p == 0`` on
    the ``collective_id``-scoped barrier semaphore — tile ``i + 1``
    repacks ``coll[me]`` while a slower neighbor may still be merging
    pass ``i``.  At ``n_shards == 1`` the ring degenerates to the packed
    local set (no sends trace at all).
    """
    p = pl.program_id(1)
    tu = U_ref.shape[0]

    if n_shards > 1:
        me = jax.lax.axis_index(axis_name)
        right = jax.lax.rem(me + 1, n_shards)
        left = jax.lax.rem(me + n_shards - 1, n_shards)

        if sync:
            @pl.when(p == 0)
            def _pass_barrier():
                bar = pltpu.get_barrier_semaphore()
                pltpu.semaphore_signal(
                    bar, 1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_signal(
                    bar, 1, device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_wait(bar, 2)
    else:
        me = jnp.int32(0)

    @pl.when(p == 0)
    def _init():
        out_s_ref[:] = jnp.full_like(out_s_ref, NEG_INF)
        out_i_ref[:] = jnp.zeros_like(out_i_ref)

    @pl.when(p < n_ti)
    def _score():
        def _copy(e, slot):
            return rb.local_copy(
                V_hbm.at[pl.ds(e * tile_i, tile_i)], Vt.at[slot],
                sem.at[slot])

        rb.grid_pump(p, n_ti, _copy)

        scores = jax.lax.dot_general(
            U_ref[:], Vt[jax.lax.rem(p, 2)],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        scores = jnp.where(valid_ref[0, :][None, :] > 0, scores, NEG_INF)
        ids = (jax.lax.broadcasted_iota(jnp.int32, (tu, tile_i), 1)
               + me * ni_loc + p * tile_i)

        # carried best at LOW columns — the chunked scan's stable order
        bs, bi = _stable_extract(
            jnp.concatenate([out_s_ref[:], scores], axis=1),
            jnp.concatenate([out_i_ref[:], ids], axis=1), k, tu)
        out_s_ref[:] = bs
        out_i_ref[:] = bi

        @pl.when(p == n_ti - 1)
        def _pack():
            packed = jnp.concatenate(
                [bs, jax.lax.bitcast_convert_type(bi, jnp.float32)],
                axis=1)
            coll[pl.ds(me, 1)] = packed[None]

    if n_shards > 1:
        @pl.when((p >= n_ti) & (p < n_ti + n_shards - 1))
        def _hop():
            h = p - n_ti + 1
            sl = jax.lax.rem(me + n_shards - h + 1, n_shards)
            d = rb.remote_copy(coll.at[sl], coll.at[sl], send_sem,
                               recv_sem, right)
            d.start()
            # retire my send and the incoming set from the LEFT (lands in
            # slot (me - h) % S, which the next hop forwards) — all hops
            # share one payload shape, so the descriptor waits both
            d.wait_send()
            d.wait_recv()

    @pl.when(p == n_ti + n_shards - 1)
    def _merge():
        bs = jnp.full((tu, LANES), NEG_INF, jnp.float32)
        bi = jnp.zeros((tu, LANES), jnp.int32)
        for s in range(n_shards):  # static: shard order == ascending ids
            bs, bi = _stable_extract(
                jnp.concatenate([bs, coll[s, :, :LANES]], axis=1),
                jnp.concatenate(
                    [bi, jax.lax.bitcast_convert_type(
                        coll[s, :, LANES:], jnp.int32)], axis=1),
                k, tu)
        out_s_ref[:] = bs
        out_i_ref[:] = bi


def topk_merge_ring(U, V_loc, item_valid_loc, k, *, axis_name=None,
                    n_shards=1, ni_loc=None, tile_u=256, tile_i=512,
                    interpret=False):
    """Cross-shard top-k serving core (inside ``shard_map``): ONE kernel
    call per device scores the replicated query rows against this
    device's catalog shard and merges the per-shard candidate sets
    in-kernel over ``make_async_remote_copy`` hops on the ring substrate.
    Per-shard candidate lists never materialize in HBM — the only
    cross-device traffic is the packed ``[TU, 2·LANES]`` running set,
    ``S - 1`` hops per user tile (``perf.roofline.serve_merge_remote_bytes``
    is the closed form; the ``serve_comm_audit`` contract pins the traced
    kernel against it).

    U [n, r] REPLICATED queries; V_loc [ni_loc, r] / item_valid_loc
    [ni_loc] THIS device's shard (``ni_loc`` is the uniform shard stride;
    pass it explicitly if ``V_loc`` arrives pre-padded).  Returns
    (scores [n, k], ids [n, k]) replicated, bitwise-equal to
    ``chunked_topk_scores`` on the concatenated catalog — tie-break
    included (the stable-extract merge; see ``_stable_extract``) —
    whenever the score values themselves are reproducible across the two
    contraction shapes (exact at integer-valued factors; the contract's
    adversarial-tie corpus).  Off-TPU pass ``interpret=True``: numerics
    and schedule are exercised; the pass-barrier arm compiles only on
    real meshes.
    """
    if k > LANES:
        raise ValueError(f"pallas top-k supports k <= {LANES}, got {k}")
    if n_shards > 1 and axis_name is None:
        raise ValueError("axis_name is required when n_shards > 1")
    n, r = U.shape
    ni = V_loc.shape[0]
    if ni_loc is None:
        ni_loc = ni

    n_pad = -(-n // tile_u) * tile_u
    i_pad = -(-ni // tile_i) * tile_i
    r_pad = -(-r // LANES) * LANES
    Up = jnp.pad(U.astype(jnp.float32), ((0, n_pad - n), (0, r_pad - r)))
    Vp = jnp.pad(V_loc.astype(jnp.float32),
                 ((0, i_pad - ni), (0, r_pad - r)))
    validp = jnp.pad(
        item_valid_loc.astype(jnp.float32), (0, i_pad - ni)
    ).reshape(1, i_pad)

    n_ti = i_pad // tile_i
    n_ut = n_pad // tile_u
    grid = (n_ut, n_ti + n_shards)
    sync = not interpret and n_shards > 1
    kernel = functools.partial(
        _topk_merge_ring_kernel, k=k, tile_i=tile_i, n_ti=n_ti,
        axis_name=axis_name, n_shards=n_shards, ni_loc=ni_loc, sync=sync)

    from tpu_als.perf.roofline import serve_merge_remote_bytes

    out_s, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_u, r_pad), lambda i, p: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            # hop/merge phases revisit the last tile's block (clamped
            # index map) — only scoring phases read it
            pl.BlockSpec((1, tile_i),
                         lambda i, p: (0, jnp.minimum(p, n_ti - 1)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile_u, LANES), lambda i, p: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_u, LANES), lambda i, p: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_i, r_pad), jnp.float32),   # item-tile ring
            # per-source-shard packed candidate sets: scores ++ bitcast
            # ids; 2·LANES·TU·S·4 B (256 KiB at S=8, TU=128) — the VMEM
            # cost of never spilling the lists to HBM
            pltpu.VMEM((n_shards, tile_u, 2 * LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,      # send
            pltpu.SemaphoreType.DMA,      # recv
        ],
        # bytes = the single-device top-k stream plus THE roofline
        # serving-merge ring payload (perf.roofline) — serve_comm_audit
        # extracts the remote-DMA component from the traced kernel and
        # pins it to the closed form
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * i_pad * r_pad,
            bytes_accessed=(n_pad * r_pad + i_pad * r_pad
                            + 2 * n_pad * LANES) * 4
            + serve_merge_remote_bytes(n_ut, n_shards, tile_u),
            transcendentals=0,
        ),
        compiler_params=(
            pltpu.CompilerParams(collective_id=_MERGE_COLLECTIVE_ID)
            if sync else None),
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


_MERGE_AVAILABLE = _probe_cache("pallas_topk_merge_ring")


def merge_ring_available(rank=128, k=10, n_shards=None):
    """Compile-and-validate probe for the cross-shard merge kernel ON THE
    LIVE MESH, cached per (padded rank, k, n_shards) — the gate
    ``parallel.serve.topk_sharded`` / ``ServingEngine`` consult before
    adopting ``serve_backend='merge_ring'`` on hardware.

    Same discipline as ``pallas_gather_ne.ring_available``: the probe
    executes a COLLECTIVE (the in-kernel candidate-set ring under
    ``shard_map``), so its verdict is only meaningful for the mesh it ran
    on — the cache key carries ``n_shards`` and the CONSUMER re-validates
    shape, so a banked verdict for a different shard count is a cache
    miss, never a steer.  Validates against the single-device
    ``chunked_topk_scores`` on the concatenated catalog.  Off-TPU →
    False (the CPU path doesn't need it: the interpret-mode kernel is
    dispatched by tests/contracts explicitly, and CPU serving uses the
    compiled XLA sharded path).
    """
    from tpu_als.utils.platform import probe_kernel

    if n_shards is None:
        n_shards = jax.device_count()
    r_pad = -(-max(1, rank) // LANES) * LANES
    k = min(k, LANES)

    def probe():
        import functools as ft

        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from tpu_als.ops.topk import chunked_topk_scores
        from tpu_als.parallel.mesh import shard_map

        if jax.device_count() < n_shards:
            return False, f"{jax.device_count()} devices < {n_shards} shards"
        S = n_shards
        ax = "merge_probe"
        mesh = Mesh(np.array(jax.devices()[:S]), (ax,))
        rng = np.random.default_rng(0)
        # integer-valued factors: scores are exact in f32, so equality
        # with the XLA scan is bitwise — ties included (duplicated rows)
        per, n = 96, 40
        base = rng.integers(-3, 4, size=(7, r_pad)).astype(np.float32)
        V = base[rng.integers(0, 7, size=S * per)]
        U = rng.integers(-3, 4, size=(n, r_pad)).astype(np.float32)
        valid = rng.random(S * per) < 0.9

        @jax.jit
        @ft.partial(shard_map, mesh=mesh,
                    in_specs=(P(), P(ax), P(ax)), out_specs=(P(), P()),
                    check_vma=False)
        def run(Uq, V_shard, valid_shard):
            return topk_merge_ring(
                Uq, V_shard, valid_shard, k, axis_name=ax, n_shards=S,
                tile_u=8 * (-(-n // 8)), tile_i=128)

        from tpu_als.parallel.mesh import shard_leading

        spec = shard_leading(mesh)
        s, ix = run(jnp.asarray(U),
                    jax.device_put(V, spec),
                    jax.device_put(valid, spec))
        s.block_until_ready()
        rs, rix = chunked_topk_scores(
            jnp.asarray(U), jnp.asarray(V), jnp.asarray(valid),
            min(k, S * per))
        return (np.array_equal(np.asarray(s), np.asarray(rs))
                and np.array_equal(np.asarray(ix), np.asarray(rix)))

    return probe_kernel(_MERGE_AVAILABLE, (r_pad, k, n_shards), probe)
