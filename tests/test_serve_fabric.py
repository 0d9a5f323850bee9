"""Sharded serving fabric (PR 17): the mesh-resident int8 index, the
engine's choice of index from its mesh, and the traffic-derived bucket
ladder.

Equality discipline: corpora are built from INTEGER-valued factors drawn
from a tiny row pool, so every f32 dot product is exact regardless of
contraction order and rows collide constantly — score ties are the
common case, not the measure-zero one.  Bitwise equality (scores AND
ids) against the single-device ``chunked_topk_scores`` is then a real
statement about tie ORDER across shard counts and delta publishes.  All
on the 8-device forced-host CPU backend.
"""

import ast
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import assert_topk_within_contract
from tpu_als.ops.topk import chunked_topk_scores
from tpu_als.parallel.mesh import make_mesh
from tpu_als.resilience import faults
from tpu_als.serving.engine import ServingEngine
from tpu_als.serving.index import (
    Int8CandidateIndex,
    ShardedInt8Index,
    build_index,
    build_sharded_index,
)


def _tie_corpus(rng, nu, ni, r, pool=7):
    """Integer factors from a ``pool``-row palette: exact f32 arithmetic
    and duplicate catalog rows everywhere."""
    base = rng.integers(-3, 4, size=(pool, r)).astype(np.float32)
    V = base[rng.integers(0, pool, ni)]
    U = rng.integers(-3, 4, size=(nu, r)).astype(np.float32)
    return U, V


def _reference(U, V, valid, k):
    s, i = chunked_topk_scores(jnp.asarray(U), jnp.asarray(V),
                               jnp.asarray(valid), k=k)
    return np.asarray(s), np.asarray(i)


# ---------------------------------------------------------------------------
# 1. mesh-sharded int8 index


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


def test_sharded_index_bitwise_vs_single_device(rng, mesh8):
    # distinct-score corpus: ids must match the single-device index
    # exactly, not merely point at equal scores; the scores agree to
    # the index contract's SCORE_ULPS (per-shard rescore GEMMs have
    # other shapes than the single-device one — serving/index.py)
    Ni, r, k = 700, 32, 10
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    U = rng.normal(size=(33, r)).astype(np.float32)
    valid = rng.random(Ni) < 0.9
    ref = build_index(V, item_valid=valid, shortlist_k=Ni)
    sh = build_sharded_index(V, mesh8, item_valid=valid, shortlist_k=Ni)
    assert isinstance(sh, ShardedInt8Index)
    assert isinstance(ref, Int8CandidateIndex)
    s0, i0 = ref.topk(jnp.asarray(U), k)
    s1, i1 = sh.topk(jnp.asarray(U), k)
    n_clear = assert_topk_within_contract(s0, i0, U, V, valid, k)
    assert assert_topk_within_contract(s1, i1, U, V, valid, k) == n_clear
    assert n_clear >= 30          # the corpus IS distinct-score
    assert np.array_equal(np.asarray(i0), np.asarray(i1))


def test_sharded_index_tie_scores_and_ids_verifiable(rng, mesh8):
    # ragged Ni (700 over 8 shards): scores bitwise vs chunked; each
    # returned id re-verified independently (ties make id equality
    # against a different tiebreak order meaningless)
    Ni, k = 700, 10
    U, V = _tie_corpus(rng, 21, Ni, 32, pool=11)
    valid = rng.random(Ni) < 0.9
    ref_s, _ = _reference(U, V, valid, k)
    sh = build_sharded_index(V, mesh8, item_valid=valid, shortlist_k=Ni)
    s, i = sh.topk(jnp.asarray(U), k)
    s, i = np.asarray(s), np.asarray(i)
    assert np.array_equal(s, ref_s)
    sc = U @ V.T
    hit = s > -3.0e38
    assert valid[i[hit]].all()
    assert np.array_equal(sc[np.nonzero(hit)[0], i[hit]], s[hit])


def test_sharded_index_delta_then_compact_bitwise(rng, mesh8):
    Ni, r, k = 700, 32, 10
    U, V = _tie_corpus(rng, 17, Ni, r, pool=11)
    valid = rng.random(Ni) < 0.9
    sh = build_sharded_index(V, mesh8, item_valid=valid, shortlist_k=Ni)
    touch = rng.choice(Ni, size=29, replace=False)
    app = np.arange(Ni, Ni + 4)    # appends, under capacity
    rows = np.concatenate([touch, app])
    newV = _tie_corpus(rng, 1, rows.size, r, pool=11)[1]
    newvalid = rng.random(rows.size) < 0.8
    d = sh.with_updates(rows, newV, newvalid, seq=1)
    assert isinstance(d, ShardedInt8Index)
    assert d.delta_count == rows.size and d.n_items == Ni + 4
    V2 = np.concatenate([V, np.zeros((4, r), np.float32)])
    valid2 = np.concatenate([valid, np.zeros(4, bool)])
    V2[rows], valid2[rows] = newV, newvalid
    ref_s, _ = _reference(U, V2, valid2, k)
    ds, _ = d.topk(jnp.asarray(U), k, shortlist_k=Ni + 4)
    assert np.array_equal(np.asarray(ds), ref_s)
    c = d.compact(seq=2)
    assert isinstance(c, ShardedInt8Index) and c.delta_count == 0
    cs, _ = c.topk(jnp.asarray(U), k, shortlist_k=Ni + 4)
    assert np.array_equal(np.asarray(cs), ref_s)


def test_sharded_index_retag_shares_device_arrays(rng, mesh8):
    _, V = _tie_corpus(rng, 1, 96, 8)
    sh = build_sharded_index(V, mesh8)
    t = sh.retag(5)
    assert isinstance(t, ShardedInt8Index) and t.seq == 5
    assert t.V is sh.V and t.Vq is sh.Vq and t.ni_loc == sh.ni_loc


def test_sharded_index_growth_past_capacity_rebuilds(rng, mesh8):
    _, V = _tie_corpus(rng, 1, 100, 8)
    sh = build_sharded_index(V, mesh8)
    big = np.arange(sh.n_items, sh.capacity + 13)
    g = sh.with_updates(big, _tie_corpus(rng, 1, big.size, 8)[1], seq=3)
    assert isinstance(g, ShardedInt8Index)
    assert g.n_items == sh.capacity + 13 and g.delta_count == 0
    assert g.capacity >= g.n_items
    with pytest.raises(ValueError, match="append gap"):
        sh.with_updates(np.asarray([sh.capacity + 2]),
                        np.zeros((1, 8), np.float32))


def test_sharded_index_all_invalid_and_sparse_valid(rng, mesh8):
    U, V = _tie_corpus(rng, 9, 200, 8)
    none, _ = build_sharded_index(
        V, mesh8, item_valid=np.zeros(200, bool),
        shortlist_k=200).topk(jnp.asarray(U), 5)
    assert np.all(np.asarray(none) <= -3.0e38)
    few = np.zeros(200, bool)
    few[[3, 101, 199]] = True      # k > valid count
    fs, _ = build_sharded_index(
        V, mesh8, item_valid=few, shortlist_k=200).topk(jnp.asarray(U), 5)
    ref_s, _ = _reference(U, V, few, 5)
    assert np.array_equal(np.asarray(fs), ref_s)


def test_sharded_index_residency(rng, mesh8):
    # the catalog is never committed whole to one device: every base
    # array spans all 8 shards with an ni_loc-row slice on each
    _, V = _tie_corpus(rng, 1, 700, 16)
    sh = build_sharded_index(V, mesh8)
    for arr in (sh.V, sh.Vq, sh.sv, sh.valid):
        assert len(arr.sharding.device_set) == 8
        assert arr.addressable_shards[0].data.shape[0] == sh.ni_loc


# ---------------------------------------------------------------------------
# 2. the engine picks its index from the mesh


def _drain(eng, payloads, **kw):
    tickets = [eng.submit(p, **kw) for p in payloads]
    while True:
        b = eng.batcher.next_batch(timeout=0.01)
        if b is None:
            break
        eng.serve_batch(b)
    return [t.result(timeout=10) for t in tickets]


# 0 = no mesh; 3 pads the 700-row catalog to D·ceil(Ni/D) = 702
@pytest.mark.parametrize("n_shards", [0, 2, 3, 8])
def test_engine_backends_bitwise(rng, n_shards):
    Nu, Ni, r, k = 40, 700, 32, 10
    U, V = _tie_corpus(rng, Nu, Ni, r, pool=11)
    valid = rng.random(Ni) < 0.9
    ref_s, ref_i = _reference(U, V, valid, k)
    eng = ServingEngine(k=k, shortlist_k=Ni, buckets=(8, 32),
                        mesh=make_mesh(n_shards) if n_shards else None)
    eng.publish(U, V, item_valid=valid)
    assert isinstance(eng.published_index,
                      ShardedInt8Index if n_shards else Int8CandidateIndex)
    eng.warmup()
    for u, (s, ix) in zip(range(20), _drain(eng, list(range(20)))):
        assert np.array_equal(ix, ref_i[u])
        assert np.array_equal(s, ref_s[u])
    # fold-in payload equal to a published row answers identically
    (s, ix), = _drain(eng, [U[7].copy()])
    assert np.array_equal(ix, ref_i[7])
    # per-request k trim slices the shared response buffer
    (s, ix), = _drain(eng, [3], k=4)
    assert s.shape == (4,) and np.array_equal(ix, ref_i[3, :4])


def test_engine_imports_no_mesh_kernel_or_batch_topk():
    """The arrow serving -> parallel must not come back unnoticed: the
    engine reaches a mesh through ``ShardedInt8Index`` alone, never
    through the batch ``topk_sharded`` or a Pallas top-k."""
    import tpu_als.serving.engine as engine

    banned = ("tpu_als.parallel.serve", "tpu_als.ops.pallas_topk")
    tree = ast.parse(pathlib.Path(engine.__file__).read_text())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            seen.add(node.module)
            seen.update(f"{node.module}.{a.name}" for a in node.names)
    assert not [m for m in seen if m.startswith(banned)], seen


def test_engine_backend_event_mesh_only(rng, mesh8):
    """``serving_backend`` fires once per MESH-backed engine with
    ``sharded`` and the shard count; mesh-less engines emit nothing
    (docs/observability.md)."""
    from tpu_als import obs

    U, V = _tie_corpus(rng, 8, 96, 16)
    reg = obs.reset()
    try:
        for eng in (ServingEngine(k=5, shortlist_k=96, buckets=(8,)),
                    ServingEngine(k=5, shortlist_k=96, buckets=(8,),
                                  mesh=mesh8)):
            eng.publish(U, V)
            eng.publish(U, V)       # once per engine, not per publish
        ev = [e for e in reg._events if e["type"] == "serving_backend"]
        assert [(e["backend"], e["n_shards"]) for e in ev] == \
            [("sharded", 8)]
    finally:
        obs.reset()


@pytest.mark.parametrize("n_shards", [3, 8])
def test_engine_publish_update_modes_on_mesh(rng, n_shards):
    Nu, Ni, r, k = 30, 700, 32, 10
    U, V = _tie_corpus(rng, Nu, Ni, r, pool=11)
    valid = rng.random(Ni) < 0.9
    eng = ServingEngine(k=k, shortlist_k=Ni, buckets=(8,),
                        mesh=make_mesh(n_shards))
    eng.publish(U, V, item_valid=valid)
    _, mode = eng.publish_update(U, V, item_valid=valid)
    assert mode == "retag"
    V2 = V.copy()
    V2[[5, 600]] = _tie_corpus(rng, 1, 2, r, pool=11)[1]
    _, mode = eng.publish_update(U, V2, touched_items=[5, 600],
                                 item_valid=valid)
    assert mode == "delta"
    ref_s, ref_i = _reference(U, V2, valid, k)
    (s, ix), = _drain(eng, [11])
    assert np.array_equal(ix, ref_i[11]) and np.array_equal(s, ref_s[11])


@pytest.mark.parametrize("n_shards", [3, 8])
def test_engine_torn_publish_serves_fresh_catalog(rng, n_shards):
    # a corrupt publish must never leave a stale shard answering: the
    # fresh index is dropped and the exact path answers against the
    # FRESH host catalog
    Nu, Ni, r, k = 30, 700, 32, 10
    U, V = _tie_corpus(rng, Nu, Ni, r, pool=11)
    valid = rng.random(Ni) < 0.9
    V2 = V.copy()
    V2[[5, 600]] = _tie_corpus(rng, 1, 2, r, pool=11)[1]
    eng = ServingEngine(k=k, shortlist_k=Ni, buckets=(8,),
                        mesh=make_mesh(n_shards))
    eng.publish(U, V, item_valid=valid)
    faults.install("serving.publish=corrupt")
    try:
        eng.publish(U, V2, item_valid=valid)
    finally:
        faults.clear()
    ref_s, ref_i = _reference(U, V2, valid, k)
    (s, ix), = _drain(eng, [11])
    assert np.array_equal(ix, ref_i[11]) and np.array_equal(s, ref_s[11])


def test_engine_score_fault_falls_back_exact(rng, mesh8):
    Nu, Ni, r, k = 20, 700, 32, 10
    U, V = _tie_corpus(rng, Nu, Ni, r, pool=11)
    valid = rng.random(Ni) < 0.9
    ref_s, ref_i = _reference(U, V, valid, k)
    eng = ServingEngine(k=k, shortlist_k=Ni, buckets=(8,), mesh=mesh8)
    eng.publish(U, V, item_valid=valid)
    faults.install("serving.score=corrupt@every=1")
    try:
        (s, ix), = _drain(eng, [2])
    finally:
        faults.clear()
    assert np.array_equal(ix, ref_i[2]) and np.array_equal(s, ref_s[2])
    rec, = eng.batch_flight.records()
    assert rec["path"] == "exact"


def test_engine_pin_dropped_on_shape_changing_publish(rng):
    # distinct scores here: the truncated shortlist makes no tie-order
    # promise, and this test is about the pin lifecycle, not ties
    Nu, Ni, r, k = 20, 300, 16, 5
    U = rng.normal(size=(Nu, r)).astype(np.float32)
    V = rng.normal(size=(Ni, r)).astype(np.float32)
    eng = ServingEngine(k=k, shortlist_k=64, buckets=(8,))
    eng.publish(U, V)
    eng.warmup()
    assert (8, "int8") in eng._pinned and (8, "exact") in eng._pinned
    Vbig = np.concatenate(
        [V, rng.normal(size=(200, r)).astype(np.float32)])
    eng.publish(U, Vbig)               # shapes changed, pins now stale
    ref_s, ref_i = _reference(U, Vbig, np.ones(500, bool), k)
    (s, ix), = _drain(eng, [4])
    assert np.array_equal(ix, ref_i[4])
    assert (8, "int8") not in eng._pinned   # dropped, jit served


# ---------------------------------------------------------------------------
# 3. traffic-derived bucket ladder


def test_observed_ladder_is_pow2_quantiles():
    from tpu_als.plan import resolve_serving_buckets
    from tpu_als.plan.planner import _ladder_from_observed

    sizes = [3, 3, 4, 7, 9, 20, 20, 21, 40, 120]
    lad = resolve_serving_buckets(observed=sizes)
    assert lad == _ladder_from_observed(sizes)
    assert all(b & (b - 1) == 0 for b in lad)    # pow2 rungs
    assert lad[-1] == 128                        # covers the max
    assert lad == tuple(sorted(set(lad)))


def test_observed_ladder_empty_falls_back():
    from tpu_als.plan import resolve_serving_buckets
    from tpu_als.serving.batcher import DEFAULT_BUCKETS

    assert resolve_serving_buckets(observed=[]) == tuple(DEFAULT_BUCKETS)


def test_observed_ladder_banks_and_recalls(tmp_path, monkeypatch):
    from tpu_als import plan

    monkeypatch.setenv("TPU_ALS_PLAN_CACHE", str(tmp_path))
    plan.clear()
    try:
        lad = plan.resolve_serving_buckets(rank=16,
                                           observed=[3, 5, 60, 200])
        assert lad == (64, 256) or lad[-1] == 256
        # a later default resolution inherits the banked measured mix
        assert plan.resolve_serving_buckets(rank=16) == lad
    finally:
        plan.clear()
