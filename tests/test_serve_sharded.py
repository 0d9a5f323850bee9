"""Sharded serving (parallel/serve.py): both strategies must agree with
the single-device chunked top-k — the serving analog of the trainer's
sharded == single-device equivalence tests (SURVEY.md §4.4)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tests.conftest import assert_topk_within_contract
from tpu_als.ops.topk import chunked_topk_scores
from tpu_als.parallel.mesh import make_mesh
from tpu_als.parallel.serve import topk_sharded


def _factors(rng, nu, ni, r):
    # continuous values: score ties (which strategies may break
    # differently) have probability ~0
    U = rng.normal(size=(nu, r)).astype(np.float32)
    V = rng.normal(size=(ni, r)).astype(np.float32)
    return U, V


def _reference(U, V, valid, k):
    return chunked_topk_scores(jnp.asarray(U), jnp.asarray(V),
                               jnp.asarray(valid), k=k)


# 3 shards beside the full mesh width: the ring's rotation schedule
# must not assume a power-of-two neighborhood
@pytest.mark.parametrize("n_shards", [3, 8])
@pytest.mark.parametrize("strategy", ["all_gather", "ring"])
def test_matches_single_device(rng, strategy, n_shards):
    U, V = _factors(rng, 41, 97, 8)  # divisible by neither shard count
    valid = np.ones(97, bool)
    k = 10
    ref_s, ref_i = _reference(U, V, valid, k)
    s, ix = topk_sharded(U, V, k, make_mesh(n_shards), strategy=strategy)
    np.testing.assert_allclose(s, np.asarray(ref_s), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ix, np.asarray(ref_i))


@pytest.mark.parametrize("strategy", ["all_gather", "ring"])
def test_k_larger_than_shard(rng, strategy):
    # 8 devices x 2 items/shard: k=5 exceeds every shard's local k
    U, V = _factors(rng, 12, 16, 4)
    k = 5
    ref_s, ref_i = _reference(U, V, np.ones(16, bool), k)
    s, ix = topk_sharded(U, V, k, make_mesh(8), strategy=strategy)
    np.testing.assert_allclose(s, np.asarray(ref_s), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ix, np.asarray(ref_i))


@pytest.mark.parametrize("n_shards", [3, 8])
@pytest.mark.parametrize("strategy", ["all_gather", "ring"])
def test_item_valid_mask(rng, strategy, n_shards):
    U, V = _factors(rng, 9, 40, 4)
    valid = rng.random(40) < 0.5
    k = 3
    ref_s, ref_i = _reference(U, V, valid, k)
    s, ix = topk_sharded(U, V, k, make_mesh(n_shards), strategy=strategy,
                         item_valid=valid)
    np.testing.assert_allclose(s, np.asarray(ref_s), rtol=1e-5, atol=1e-6)
    # every selected index must be a valid item
    assert valid[ix].all()


@pytest.mark.parametrize("strategy", ["all_gather", "ring"])
def test_all_invalid_shard_never_answers(rng, strategy):
    # shard 2 of 8 contributes nothing: its local top-k is all sentinel
    # and must never displace a real candidate in the merge.  Integer
    # factors from a 7-row palette: every dot product is exact and rows
    # collide constantly, so ties are the common case and the scores
    # compare bitwise
    base = rng.integers(-3, 4, size=(7, 8)).astype(np.float32)
    V = base[rng.integers(0, 7, 64)]
    U = rng.integers(-3, 4, size=(11, 8)).astype(np.float32)
    valid = np.ones(64, bool)
    valid[16:24] = False
    k = 5
    ref_s, _ = _reference(U, V, valid, k)
    s, ix = topk_sharded(U, V, k, make_mesh(8), strategy=strategy,
                         item_valid=valid)
    np.testing.assert_array_equal(s, np.asarray(ref_s))
    assert not np.isin(ix, np.arange(16, 24)).any()
    # tied ids may differ from the single-device order: each must earn
    # its score
    np.testing.assert_array_equal(
        np.take_along_axis(U @ V.T, ix.astype(np.int64), axis=1), s)


def test_k_capped_at_catalog(rng):
    U, V = _factors(rng, 5, 6, 4)
    s, ix = topk_sharded(U, V, 50, make_mesh(8))
    assert s.shape == (5, 6) and ix.shape == (5, 6)
    # every real item appears exactly once per row
    assert np.array_equal(np.sort(ix, axis=1),
                          np.broadcast_to(np.arange(6), (5, 6)))


def test_strategies_agree_on_duplicate_scores(rng):
    """Adversarial ties: the module docstring promises SCORES agree
    across strategies to reduction-order rounding even though tied
    INDICES may differ (merge order is shard-rotation order).  Pin both
    halves: scores within the serving contract's SCORE_ULPS of the
    single-device kernel (hence of each other), and every returned
    index earns its claimed score."""
    base = rng.normal(size=(7, 6)).astype(np.float32)
    V = base[rng.integers(0, 7, 96)]     # whole catalog = repeated rows
    U = rng.normal(size=(11, 6)).astype(np.float32)
    k = 12                               # deep enough to span tie groups
    s_ag, i_ag = topk_sharded(U, V, k, make_mesh(8),
                              strategy="all_gather")
    s_ring, i_ring = topk_sharded(U, V, k, make_mesh(8), strategy="ring")
    valid = np.ones(len(V), bool)
    assert_topk_within_contract(s_ag, i_ag, U, V, valid, k)
    assert_topk_within_contract(s_ring, i_ring, U, V, valid, k)
    full = U.astype(np.float64) @ V.astype(np.float64).T
    for ix, s in ((i_ag, s_ag), (i_ring, s_ring)):
        np.testing.assert_allclose(
            np.take_along_axis(full, ix.astype(np.int64), axis=1), s,
            rtol=1e-5, atol=1e-5)


def test_unknown_strategy_rejected(rng):
    U, V = _factors(rng, 4, 4, 2)
    with pytest.raises(ValueError, match="unknown serving strategy"):
        topk_sharded(U, V, 2, make_mesh(8), strategy="broadcast")


def test_recommend_arrays_mesh_equivalence(rng):
    """ALSModel.recommend_arrays(mesh=...) == the single-device path."""
    from tests.conftest import make_ratings
    from tpu_als import ALS, ColumnarFrame

    u, i, r, _, _ = make_ratings(rng, 30, 20, 4, density=0.5)
    frame = ColumnarFrame({"user": u, "item": i, "rating": r})
    model = ALS(rank=4, maxIter=3, regParam=0.005, seed=0).fit(frame)
    ids0, rec0, sc0 = model.recommend_arrays(5)
    for strategy in ("all_gather", "ring"):
        ids1, rec1, sc1 = model.recommend_arrays(
            5, mesh=make_mesh(8), gatherStrategy=strategy)
        np.testing.assert_array_equal(ids0, ids1)
        np.testing.assert_allclose(sc0, sc1, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(rec0, rec1)
