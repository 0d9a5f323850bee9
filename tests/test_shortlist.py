"""The int8 shortlist's selection (``ops.topk.shortlist_topk``): what
``jax.lax.top_k`` returns, element for element, on both sides of the
one-stage / two-stage decision; and the three kernels that call it (base,
delta, per-shard) at a catalog large enough to engage two stages, under the
contracts they already had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import assert_topk_within_contract
from tpu_als import obs
from tpu_als.core.ratings import row_capacity
from tpu_als.ops.topk import (
    NEG_INF,
    ROW_MAJOR_BELOW,
    ShortlistPlan,
    block_maxima,
    shortlist_columns,
    shortlist_plan,
    shortlist_topk,
    topk_validity,
)
from tpu_als.parallel.mesh import make_mesh
from tpu_als.serving import ServingEngine, build_index
from tpu_als.serving.index import build_sharded_index

# large enough for two stages at shortlist 16 (the decision's threshold
# there is 8,460 columns), small enough for tier-1
BIG_ITEMS, RANK, SHORTLIST = 40_000, 16, 16


@pytest.fixture(autouse=True)
def _fresh():
    reg = obs.reset()
    yield reg


def _scores(rng, kind, n, N, k):
    if kind == "random":
        return rng.standard_normal((n, N)).astype(np.float32)
    if kind == "ties":          # seven distinct values: ties everywhere
        return rng.integers(-3, 4, (n, N)).astype(np.float32)
    if kind == "sparse":        # fewer than k finite entries a row
        x = np.full((n, N), NEG_INF, np.float32)
        for row in x:
            cols = rng.choice(N, size=int(rng.integers(0, k)), replace=False)
            row[cols] = rng.standard_normal(len(cols))
        return x
    assert kind == "tail"       # the winners sit in the ragged last block
    x = rng.integers(0, 2, (n, N)).astype(np.float32)
    x[:, -(N % 128 or 128):] += 5.0
    return x


# (n, N, k, stages expected): both sides of the decision, ragged and whole
# last blocks, row counts that are and are not whole sublane tiles
SHAPES = [
    (4, 1_000, 8, 1),
    (8, 4_231, 8, 1),            # one block short of the threshold
    (8, 4_232, 8, 2),            # the threshold itself: 4 * (1024 + 34)
    (3, 20_259, 16, 2),
    (16, 34_000, 64, 2),
    (5, 33_791, 64, 1),
    (1, 40_064, 16, 2),          # whole blocks: no pad
    (8, 70_001, 64, 2),
    (2, 300_000, 8, 2),          # block length 256
    (8, 40_960, 16, 2),          # 320 blocks: whole tiles of 8 (the other
    (32, 40_900, 16, 2),         # gather), whole and ragged last block,
    (3, 40_960, 16, 2),          # rows that are no whole tile
]


@pytest.mark.parametrize("kind", ["random", "ties", "sparse", "tail"])
@pytest.mark.parametrize("n,N,k,stages", SHAPES)
def test_shortlist_topk_is_lax_top_k(kind, n, N, k, stages):
    rng = np.random.default_rng(N + k)
    plan = shortlist_plan(N, k)
    assert plan.stages == stages and plan.columns == N
    x = jnp.asarray(_scores(rng, kind, n, N, k))
    want_s, want_i = jax.lax.top_k(x, k)
    got_s, got_i = jax.jit(shortlist_topk, static_argnums=1)(x, k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def _block_scores(rng, kind, n, N, k, L):
    """Scores built block by block, for stage two: ``tied_blocks`` — a
    few blocks with a maximum of their own, then the k-th largest block
    maximum shared by every second block, the winners' position in their
    block random; ``few_blocks`` — fewer than ``k`` blocks hold a valid
    score at all, yet more than ``k`` scores (the rest is the index's
    sentinel)."""
    blocks = -(-N // L)
    if kind == "tied_blocks":
        x = rng.integers(-3, 1, (n, N)).astype(np.float32)
        for row in x:
            for b in rng.choice(blocks, size=k // 4, replace=False):
                row[b * L + rng.integers(0, min(L, N - b * L))] = \
                    7.0 + rng.random()
            for b in range(int(rng.integers(0, 2)), blocks, 2):
                row[b * L + rng.integers(0, min(L, N - b * L))] = 5.0
        return x
    assert kind == "few_blocks"
    x = np.full((n, N), NEG_INF, np.float32)
    for row in x:
        for b in rng.choice(blocks, size=k // 2, replace=False):
            cols = b * L + rng.choice(min(L, N - b * L), size=3,
                                      replace=False)
            row[cols] = rng.integers(-2, 3, 3)
    return x


@pytest.mark.parametrize("kind", ["tied_blocks", "few_blocks"])
@pytest.mark.parametrize("N", [40_064, 40_000])     # whole and ragged
@pytest.mark.parametrize("n", [8, 32, 128])
def test_stage_two_is_lax_top_k_at_every_bucket(kind, n, N):
    """Both sides of the rule that decides stage two's layout (row-major
    under ``ROW_MAJOR_BELOW`` rows, the compiler's above), on the cases
    stage two decides: ties among the block maxima at the k-th place, and
    fewer than ``k`` blocks with anything valid in them."""
    k = SHORTLIST
    plan = shortlist_plan(N, k, rows=n)
    assert plan.stages == 2
    assert plan.blocks_layout == ("row_major" if n < ROW_MAJOR_BELOW
                                  else "compiler")
    rng = np.random.default_rng(n + N)
    x = jnp.asarray(_block_scores(rng, kind, n, N, k, plan.block_len))
    want_s, want_i = jax.lax.top_k(x, k)
    got_s, got_i = jax.jit(shortlist_topk, static_argnums=1)(x, k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def _with_a_tail(rng, kind, n, N, d, k):
    """``(scores [n, N], tail [n, d])`` for the cases a tail decides."""
    if kind == "ties_across":   # seven values: ties across the boundary
        x = rng.integers(-3, 4, (n, N + d)).astype(np.float32)
        x[:, N - 3:N + 3] = 3.0     # the largest, on both sides of it
    elif kind == "all_in_tail":
        x = rng.standard_normal((n, N + d)).astype(np.float32)
        x[:, N:N + k] += 100.0
    elif kind == "none_in_tail":
        x = rng.standard_normal((n, N + d)).astype(np.float32)
        x[:, N:] = NEG_INF          # an empty segment: every slot free
    else:
        assert kind == "sentinels"  # fewer than k finite entries a row
        x = np.full((n, N + d), NEG_INF, np.float32)
        x[:, ::3] = -np.inf
        for row in x:
            cols = rng.choice(N + d, size=int(rng.integers(0, k)),
                              replace=False)
            row[cols] = rng.integers(-2, 3, len(cols))
            row[N + int(rng.integers(0, d))] = 1.0
    return x[:, :N], x[:, N:]


# (n, N, d, k, stages): whole and ragged last blocks, a one-stage plan, a
# tail that is no whole block, blocks of 256, the buckets' row counts
TAIL_SHAPES = [
    (8, 40_064, 512, 16, 2),
    (32, 40_064, 512, 16, 2),
    (128, 40_064, 512, 16, 2),
    (8, 40_000, 512, 16, 2),     # ragged last block AND a tail
    (3, 20_259, 5, 16, 2),       # ragged, rows no whole tile, a short tail
    (8, 4_231, 100, 8, 1),       # one stage: concatenated here
    (4, 60, 8, 64, 1),           # fewer columns than k without the tail
    (2, 300_000, 256, 8, 2),     # block length 256
    (8, 40_960, 512, 16, 2),     # 320 blocks: whole tiles of 8
    (128, 40_900, 512, 16, 2),   # the same, ragged
]


@pytest.mark.parametrize("kind", ["ties_across", "all_in_tail",
                                  "none_in_tail", "sentinels"])
@pytest.mark.parametrize("n,N,d,k,stages", TAIL_SHAPES)
def test_a_tail_is_top_k_of_the_concatenation(kind, n, N, d, k, stages):
    """``shortlist_topk(scores, k, tail=)`` against ``lax.top_k`` of the
    two concatenated, values and indices: the tail's columns are
    positions ``N ..`` of the answer, and no ``-inf`` pad of a ragged last
    block is returned or shares one of them."""
    plan = shortlist_plan(N, k, rows=n, tail=d)
    assert (plan.stages, plan.columns, plan.tail) == (stages, N, d)
    rng = np.random.default_rng(n + N + d)
    x, t = (jnp.asarray(a) for a in _with_a_tail(rng, kind, n, N, d, k))
    want_s, want_i = jax.lax.top_k(jnp.concatenate([x, t], axis=1), k)
    got_s, got_i = jax.jit(shortlist_topk, static_argnums=1)(x, k, tail=t)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.asarray(got_i).max() < N + d
    if kind == "all_in_tail":       # as many winners as the tail can hold
        assert np.asarray(got_i)[:, :min(d, k)].min() >= N
    if kind == "none_in_tail" and N >= k:
        assert np.asarray(got_i).max() < N


@pytest.mark.parametrize("kind", ["random", "ties", "sentinels"])
@pytest.mark.parametrize("L", [256, 384])
@pytest.mark.parametrize("n", [8, 32, 128])
def test_stage_one_through_the_lane_maxima_is_the_block_maximum(kind, L, n):
    """Blocks longer than 128 lanes: the maximum over the maxima of a
    block's 128-lane groups is the block's maximum, bit for bit."""
    blocks = 37
    rng = np.random.default_rng(L + n)
    if kind == "random":
        x = rng.standard_normal((n, blocks * L)).astype(np.float32)
    elif kind == "ties":
        x = rng.integers(-3, 4, (n, blocks * L)).astype(np.float32)
    else:
        x = rng.choice(np.array([NEG_INF, -np.inf, 0.5], np.float32),
                       (n, blocks * L), p=[0.6, 0.399, 0.001])
        x[:, :L] = -np.inf          # a block of nothing else
    tiled = jnp.asarray(x).reshape(n // 8, 8, blocks, L)
    lanes = jax.jit(block_maxima, static_argnums=1)(tiled, "lanes")
    block = jax.jit(block_maxima, static_argnums=1)(tiled, "block")
    want = x.reshape(n // 8, 8, blocks, L).max(axis=-1)
    np.testing.assert_array_equal(np.asarray(lanes), want)
    np.testing.assert_array_equal(np.asarray(block), want)


@pytest.mark.parametrize("N,k,L", [(300_000, 8, 256), (589_824, 4, 384),
                                   (600_000, 4, 384)])
def test_long_blocks_plan_the_lane_maxima_and_select_as_top_k(N, k, L):
    plan = shortlist_plan(N, k, rows=8)
    assert (plan.stages, plan.block_len, plan.blockmax) == (2, L, "lanes")
    rng = np.random.default_rng(N)
    x = jnp.asarray(rng.integers(-3, 4, (8, N)).astype(np.float32))
    want_s, want_i = jax.lax.top_k(x, k)
    got_s, got_i = jax.jit(shortlist_topk, static_argnums=1)(x, k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_rows_decide_the_layout_and_nothing_else():
    two = shortlist_plan(1_506_048, 64)
    assert ROW_MAJOR_BELOW == 56
    for rows in (1, 8, 32, 55, 56, 128, 1024):
        plan = shortlist_plan(1_506_048, 64, rows=rows)
        assert plan[:4] == two[:4]
        assert plan.blocks_layout == ("row_major" if rows < 56
                                      else "compiler")
        # one stage is the compiler's single top_k whatever the rows
        assert shortlist_plan(2_000, 64, rows=rows) == ShortlistPlan(
            1, 1, 2_000, 2_000, "compiler")
    assert two.blocks_layout == "compiler"      # no rows: columns' plan


def test_the_constraint_is_in_the_jaxpr_only_where_the_plan_says():
    def primitives(n):
        jaxpr = jax.make_jaxpr(lambda s: shortlist_topk(s, SHORTLIST))(
            jax.ShapeDtypeStruct((n, BIG_ITEMS), jnp.float32))
        return [str(e.primitive) for e in jaxpr.eqns]

    assert primitives(8).count("layout_constraint") == 1
    assert primitives(32).count("layout_constraint") == 1
    assert primitives(128).count("layout_constraint") == 0
    assert primitives(8).count("top_k") == primitives(128).count("top_k") == 2


@pytest.mark.parametrize("N,k", [(1_505_938, 64), (40_000, 16),
                                 (10_000_000, 64), (300_000, 8)])
def test_plan_is_whole_blocks_near_the_square_root(N, k):
    plan = shortlist_plan(N, k)
    assert plan.stages == 2 and plan.block_len % 128 == 0
    assert abs(plan.block_len - max((N / k) ** 0.5, 128)) <= 64
    assert (plan.blocks - 1) * plan.block_len < N <= \
        plan.blocks * plan.block_len
    padded = shortlist_columns(N, k)
    again = shortlist_plan(padded, k)
    assert N <= padded < N + again.block_len
    assert padded == again.blocks * again.block_len
    assert shortlist_columns(padded, k) == padded


def test_the_benchmark_cells_plan():
    assert shortlist_plan(1_505_938, 64) == ShortlistPlan(
        stages=2, blocks=11_766, block_len=128, columns=1_505_938,
        blockmax="block")
    assert shortlist_columns(1_505_938, 64) == 1_506_048
    # the cells' programs, bucket by bucket: steady and fold-in; the live
    # catalog's base with the segment's 512 slots as its tail; one shard
    # of the mesh cell, whose blocks of 256 go through the lane maxima
    for columns, blocks, block_len, blockmax, tail in (
            (1_506_048, 11_766, 128, "block", 0),
            (1_529_856, 11_952, 128, "block", 512),
            (3_012_096, 11_766, 256, "lanes", 0)):
        for bucket, layout in ((8, "row_major"), (32, "row_major"),
                               (128, "compiler")):
            assert shortlist_plan(columns, 64, rows=bucket, tail=tail) \
                == ShortlistPlan(2, blocks, block_len, columns, layout,
                                 blockmax, tail)


@pytest.mark.parametrize("N,k", [(1, 1), (63, 64), (2_000, 64),
                                 (5_000, 16), (8_459, 16), (33_791, 64)])
def test_small_catalogs_keep_the_single_top_k(N, k):
    """One stage is the call it replaces: the same jaxpr, so the same
    program."""
    assert shortlist_plan(N, k) == ShortlistPlan(1, 1, N, N)
    assert shortlist_columns(N, k) == N
    kk = min(k, N)
    x = jax.ShapeDtypeStruct((4, N), jnp.float32)
    jax.clear_caches()
    ours = jax.make_jaxpr(lambda s: shortlist_topk(s, kk))(x)
    jax.clear_caches()
    theirs = jax.make_jaxpr(lambda s: jax.lax.top_k(s, kk))(x)
    assert str(ours) == str(theirs)


@pytest.fixture(scope="module")
def big():
    rng = np.random.default_rng(26)
    V = rng.normal(size=(BIG_ITEMS, RANK)).astype(np.float32)
    U = rng.normal(size=(24, RANK)).astype(np.float32)
    valid = rng.random(BIG_ITEMS) < 0.9
    return U, V, valid


def test_big_index_engages_two_stages_and_pads_once(big):
    _, V, valid = big
    idx = build_index(V, item_valid=valid, shortlist_k=SHORTLIST)
    plan = idx.shortlist_plan()
    assert plan.stages == 2
    assert plan.columns == plan.blocks * plan.block_len == 40_064
    assert idx.Vq.shape[0] == idx.sv.shape[0] == idx.valid.shape[0] == 40_064
    assert idx.V.shape[0] == idx.n_base == idx.n_items == BIG_ITEMS
    assert not np.asarray(idx.valid)[BIG_ITEMS:].any()


def test_big_index_topk_within_contract(big):
    U, V, valid = big
    idx = build_index(V, item_valid=valid, shortlist_k=SHORTLIST)
    s, ix = idx.topk(jnp.asarray(U), 5)
    assert np.asarray(ix).max() < BIG_ITEMS
    assert assert_topk_within_contract(s, ix, U, V, valid, 5) >= 20


def test_big_index_sparse_validity_keeps_sentinels(big):
    """Fewer valid items than the shortlist: the sentinels and the ids
    under them are the single top_k's, and none points at a padding
    column."""
    U, V, _ = big
    valid = np.zeros(BIG_ITEMS, bool)
    valid[[7, 20_000, 39_999]] = True
    idx = build_index(V, item_valid=valid, shortlist_k=SHORTLIST)
    s, ix = (np.asarray(a) for a in idx.topk(jnp.asarray(U), 5))
    assert_topk_within_contract(s, ix, U, V, valid, 5)
    assert (topk_validity(s).sum(axis=1) == 3).all()
    assert ix.max() < BIG_ITEMS


def _assert_bitwise(idx, ref, U, k):
    s, ix = (np.asarray(a) for a in idx.topk(U, k))
    rs, rix = (np.asarray(a) for a in ref.topk(U, k))
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(ix, rix)


def test_big_delta_and_compact_bitwise_against_rebuild(big):
    """The ``live_delta_index`` contract where two stages select: touched
    and appended rows through the delta segment, then compacted, against
    ``build_index`` of the updated catalog."""
    U, V, valid = big
    rng = np.random.default_rng(27)
    idx = build_index(V, item_valid=valid, shortlist_k=SHORTLIST, seq=1)
    touched = np.sort(rng.choice(BIG_ITEMS, size=37, replace=False))
    appended = np.arange(BIG_ITEMS, BIG_ITEMS + 100)    # past the padding
    rows = np.concatenate([touched, appended]).astype(np.int64)
    V2 = np.concatenate(
        [V, np.zeros((len(appended), RANK), np.float32)])
    V2[rows] = 3.0 * rng.normal(size=(len(rows), RANK)).astype(np.float32)
    valid2 = np.concatenate([valid, np.ones(len(appended), bool)])
    valid2[touched] = True
    upd = idx.with_updates(rows, V2[rows], seq=2)
    # the base's columns in two stages, the segment's slots their tail
    assert upd.shortlist_plan() == shortlist_plan(40_064, SHORTLIST,
                                                  tail=256)
    assert upd.shortlist_plan()[:4] == idx.shortlist_plan()[:4]
    ref = build_index(V2, item_valid=valid2, shortlist_k=SHORTLIST, seq=2)
    Uq = jnp.asarray(U)
    _assert_bitwise(upd, ref, Uq, 5)
    assert np.isin(np.asarray(upd.topk(Uq, 5)[1]), rows).any()
    comp = upd.compact(seq=3)
    assert comp.delta_count == 0 and comp.n_base == BIG_ITEMS + 100
    for name in ("Vq", "sv", "valid", "V"):
        np.testing.assert_array_equal(np.asarray(getattr(comp, name)),
                                      np.asarray(getattr(ref, name)))
    _assert_bitwise(comp, ref, Uq, 5)


def test_big_sharded_index_two_stages_per_shard(big):
    """The sharded ``body`` on the 8-device CPU mesh of the fabric tests:
    each shard's 9,000 columns, padded once to whole blocks (9,088) as the
    single-device index pads its own, select in two stages, and the answer
    is the single-device index's."""
    U, V, valid = big
    rng = np.random.default_rng(28)
    V = np.concatenate(
        [V, rng.normal(size=(32_000, RANK)).astype(np.float32)])
    valid = np.concatenate([valid, rng.random(32_000) < 0.9])
    sh = build_sharded_index(V, make_mesh(8), item_valid=valid,
                             shortlist_k=SHORTLIST)
    cols = shortlist_columns(9_000, SHORTLIST)
    assert cols == sh.ni_loc == 9_088 and cols % shortlist_plan(
        cols, SHORTLIST).block_len == 0
    assert sh.shortlist_plan() == shortlist_plan(cols, SHORTLIST)
    assert sh.shortlist_plan().stages == 2
    s, ix = sh.topk(jnp.asarray(U), 5)
    assert assert_topk_within_contract(s, ix, U, V, valid, 5) >= 20
    one = build_index(V, item_valid=valid, shortlist_k=SHORTLIST)
    np.testing.assert_array_equal(np.asarray(ix),
                                  np.asarray(one.topk(jnp.asarray(U), 5)[1]))
    rows = np.array([5, 9_001, 71_999], dtype=np.int64)
    upd = sh.with_updates(rows, 4.0 * V[rows], seq=2)
    assert upd.shortlist_plan() == shortlist_plan(cols, SHORTLIST, tail=4)
    V2 = V.copy()
    V2[rows] *= 4.0
    s2, ix2 = upd.topk(jnp.asarray(U), 5)
    assert_topk_within_contract(s2, ix2, U, V2, valid, 5)


def _shortlist_events(reg):
    return [e for e in reg._events if e["type"] == "serving_shortlist"]


def test_warmup_reports_the_plan_the_program_was_traced_with(_fresh, big):
    U, V, _ = big
    eng = ServingEngine(k=5, buckets=(8, 32), shortlist_k=SHORTLIST,
                        max_wait_s=0.0)
    eng.publish(U, V)
    eng.warmup()
    events = _shortlist_events(_fresh)
    assert [(e["bucket"], e["path"]) for e in events] == [(8, "int8"),
                                                          (32, "int8")]
    want = shortlist_plan(shortlist_columns(BIG_ITEMS, SHORTLIST), SHORTLIST)
    assert want == eng.published_index.shortlist_plan()
    for e in events:
        assert (e["stages"], e["blocks"], e["block_len"], e["columns"]) \
            == (2, 313, 128, 40_064) == tuple(want)[:4]
        # both buckets lie under ROW_MAJOR_BELOW rows: the event says what
        # stage two asked of the compiler for THIS program
        assert e["blocks_layout"] == "row_major" == \
            eng.published_index.shortlist_plan(rows=e["bucket"]).blocks_layout
        assert (e["blockmax"], e["tail"]) == ("block", 0)
    eng.warmup_live()
    live = _shortlist_events(_fresh)[2:]
    idx = eng.published_index
    # ONE program a bucket for "with a segment": the catalog's spare
    # rows and the segment's slots fix its shapes, the base in whole
    # blocks (no batch pays for a ragged last one) and the segment's
    # slots joined at stage three
    assert idx.n_base == row_capacity(BIG_ITEMS) and idx.delta_slots == 512
    columns = int(idx.Vq.shape[0])
    assert [(e["bucket"], e["delta_rows"], e["tail"], e["columns"])
            for e in live] == [(8, 512, 512, columns),
                               (32, 512, 512, columns)]
    assert all(e["stages"] == 2 and e["columns"] % e["block_len"] == 0
               and e["blockmax"] == "block" for e in live)
    assert all(tuple(shortlist_plan(e["columns"], SHORTLIST, e["bucket"],
                                    tail=512))
               == (e["stages"], e["blocks"], e["block_len"], e["columns"],
                   e["blocks_layout"], e["blockmax"], e["tail"])
               for e in live)


def test_warmup_reports_one_stage_on_a_small_catalog(_fresh):
    rng = np.random.default_rng(3)
    eng = ServingEngine(k=5, buckets=(8,), shortlist_k=32, max_wait_s=0.0)
    eng.publish(rng.normal(size=(10, 6)).astype(np.float32),
                rng.normal(size=(300, 6)).astype(np.float32))
    eng.warmup()
    (e,) = _shortlist_events(_fresh)
    assert (e["bucket"], e["path"], e["stages"], e["blocks"],
            e["block_len"], e["columns"], e["blocks_layout"],
            e["blockmax"], e["tail"]) == (
                8, "int8", 1, 1, 300, 300, "compiler", "none", 0)
