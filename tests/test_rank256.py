"""Rank-256 evidence on the CPU mesh (BASELINE config 3, VERDICT r2 #3).

Config 3 (Amazon-2023, ~570M ratings, rank 256, v5e-32) cannot run here,
so this file pins what CAN be checked without the pod:

- the per-device buffer arithmetic of each gather strategy at rank-256
  parameters — the documented HBM model must be reproduced by the actual
  built containers (shapes are exact at any entity count, so a scale
  model on the 8-device mesh verifies the formulas);
- end-to-end strategy equivalence AT rank 256 (tiny entity counts, full
  rank): the solve path, tiling arithmetic, and collectives all run at
  the production rank.

The single-chip rank-256 throughput proxy is ``scripts/rank256_proxy.py``
(not run on a chip yet).
"""

import warnings

import numpy as np

from tpu_als.core.als import AlsConfig
from tpu_als.core.ratings import trainer_chunk
from tpu_als.parallel.a2a import build_a2a
from tpu_als.parallel.comm import shard_csr_grid
from tpu_als.parallel.data import partition_balanced, shard_csr
from tpu_als.parallel.mesh import make_mesh
from tpu_als.parallel.trainer import stacked_counts, train_sharded

RANK = 256
MEM_ELEMS = 1 << 28  # 1 GiB of f32 — trainer_chunk's per-intermediate cap


def test_trainer_tile_bounds_accumulator_at_rank256():
    """At config-3 shard sizes (~1-2M solved rows/device) the row-tiled
    trainer must cap the [tile, r, r] accumulator at 1 GiB f32; the naive
    full-shard accumulator it replaces would be ~275 GB/device."""
    for nb in (1 << 20, 1 << 21):
        for w in (8, 64, 256, 1024):
            tile = trainer_chunk(nb, w, RANK, 1 << 19)
            assert tile * RANK * max(w, RANK) <= MEM_ELEMS
            assert nb % tile == 0  # tiles cover the shard exactly
    naive_bytes = (1 << 20) * RANK * RANK * 4
    assert naive_bytes > 250e9  # the blowup the tiling exists to avoid


def _sparse_layout(rng, D=8, per_user=2, users_per_dev=64, items_per_dev=64):
    nU, nI = users_per_dev * D, items_per_dev * D
    nnz = per_user * nU
    u = rng.integers(0, nU, nnz)
    i = rng.integers(0, nI, nnz)
    r = np.abs(rng.normal(size=nnz)).astype(np.float32) + 0.1
    upart = partition_balanced(np.bincount(u, minlength=nU), D)
    ipart = partition_balanced(np.bincount(i, minlength=nI), D)
    return u, i, r, upart, ipart


def test_ring_rank256_bytes_match_documented_model(rng):
    """Every term of parallel/comm.py's peak-HBM model, recomputed from
    the containers a rank-256 build actually produces."""
    D = 8
    u, i, r, upart, ipart = _sparse_layout(np.random.default_rng(5),
                                           D=D, per_user=6)
    grid = shard_csr_grid(upart, ipart, u, i, r, min_width=8)

    # term 1: the resident opposite factor shard — O(N_opposite/D · r)
    resident_bytes = ipart.rows_per_shard * RANK * 4
    assert resident_bytes == ipart.padded_rows // D * RANK * 4

    # term 2: one tile's accumulator — O(tile · r²), capped at 1 GiB
    for b in grid.buckets:
        S, nb, w = b.cols.shape[1], b.cols.shape[2], b.cols.shape[3]
        assert S == D  # full source axis: each device holds D grid cells
        tile = trainer_chunk(nb, w, RANK, grid.chunk_elems)
        assert tile * RANK * max(w, RANK) <= MEM_ELEMS
        # the full opposite table is NEVER a term: the ring holds one
        # shard (resident) + one in-flight permute buffer of equal size
        assert 2 * resident_bytes < ipart.padded_rows * RANK * 4 or D <= 2


def test_a2a_rank256_recv_table_below_gather(rng):
    """The a2a recv table [D·R, r] must beat all_gather's full opposite
    table at rank-256 parameters on the sparse layout (and the plan must
    be non-degenerate, i.e. the win is real, not the fallback)."""
    D = 8
    u, i, r, upart, ipart = _sparse_layout(np.random.default_rng(7), D=D)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = build_a2a(upart, ipart, u, i, r, min_width=8)
    assert not plan.degenerate
    recv_bytes = D * plan.request_budget * RANK * 4
    gather_bytes = ipart.padded_rows * RANK * 4
    assert recv_bytes <= gather_bytes // 2


def test_all_strategies_agree_at_rank256(rng):
    """One full iteration of every gather strategy at rank 256 on the
    8-device mesh: the production rank exercises the real solve path
    (rank > 128 rides pallas_solve on chip, XLA here) and the tiling
    arithmetic; all three must agree."""
    D = 8
    local = np.random.default_rng(3)
    nU, nI, nnz = 48, 32, 500
    u = local.integers(0, nU, nnz)
    i = local.integers(0, nI, nnz)
    r = np.abs(local.normal(size=nnz)).astype(np.float32) + 0.1
    upart = partition_balanced(np.bincount(u, minlength=nU), D)
    ipart = partition_balanced(np.bincount(i, minlength=nI), D)
    cfg = AlsConfig(rank=RANK, max_iter=1, reg_param=0.1, seed=0)
    mesh = make_mesh(D)

    Ug, Vg = train_sharded(
        mesh, upart, ipart,
        shard_csr(upart, ipart, u, i, r, min_width=8),
        shard_csr(ipart, upart, i, u, r, min_width=8), cfg)

    rc = (stacked_counts(upart, u, r), stacked_counts(ipart, i, r))
    Ur, Vr = train_sharded(
        mesh, upart, ipart,
        shard_csr_grid(upart, ipart, u, i, r, min_width=8),
        shard_csr_grid(ipart, upart, i, u, r, min_width=8), cfg,
        strategy="ring", ring_counts=rc)
    np.testing.assert_allclose(np.asarray(Ur), np.asarray(Ug),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(Vr), np.asarray(Vg),
                               rtol=2e-3, atol=2e-3)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # dense at this scale: a2a may pad
        ua = build_a2a(upart, ipart, u, i, r, min_width=8)
        ia = build_a2a(ipart, upart, i, u, r, min_width=8)
    Ua, Va = train_sharded(mesh, upart, ipart, ua, ia, cfg,
                           strategy="all_to_all")
    np.testing.assert_allclose(np.asarray(Ua), np.asarray(Ug),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(Va), np.asarray(Vg),
                               rtol=2e-3, atol=2e-3)


def test_sharded_serving_at_rank256(rng):
    """Config-3 serving evidence (SURVEY.md §5.7): top-k at rank 256 over
    the 8-device mesh, ring (catalog never materialized) == all_gather ==
    single device."""
    from tpu_als.ops.topk import chunked_topk_scores
    from tpu_als.parallel.serve import topk_sharded
    import jax.numpy as jnp

    U = rng.normal(size=(40, 256)).astype(np.float32)
    V = rng.normal(size=(100, 256)).astype(np.float32)
    ref_s, ref_i = chunked_topk_scores(
        jnp.asarray(U), jnp.asarray(V), jnp.ones(100, bool), k=10)
    for strategy in ("all_gather", "ring"):
        s, ix = topk_sharded(U, V, 10, make_mesh(8), strategy=strategy)
        np.testing.assert_allclose(s, np.asarray(ref_s), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(ix, np.asarray(ref_i))
