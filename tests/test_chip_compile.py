"""The main path's kernels, compiled at real widths for a v5e that is
described, not attached (the TPU compiler is installed with JAX).  Nothing
runs: these are the compiler's verdicts — what interpret mode cannot show
(scoped-VMEM limits, tiling, primitives the Pallas TPU lowering lacks).
A kernel the compiler still refuses is an ``xfail(strict=True)`` carrying
the compiler's first line, so the PR that repairs it is told.

All in ONE file, the topology described inside a module-scoped fixture:
only one process at a time may load the TPU library, pytest-xdist imports
every test file in every worker, and ``--dist loadfile`` gives this file
to one of them.  Compiles happen in the test's own process, with the
persistent compilation cache off around them (an entry written for a
described chip cannot be read back without one).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ML25M_ITEMS = 59_047


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; shapes are (shape, dtype)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("rank,panel,mxu", [
    (128, 32, True),     # the ladder's first rung: what `auto` selects
    (128, 8, False),
    (128, 1, False),
    (96, 32, True),
    (64, 32, True),
])
def test_spd_solve_lanes(one_chip, rank, panel, mxu):
    from tpu_als.ops.pallas_lanes import spd_solve_lanes

    compile_for(one_chip,
                functools.partial(spd_solve_lanes, panel=panel, mxu=mxu),
                ((136, rank, rank), F32), ((136, rank), F32))


def test_spd_solve_pallas_rank128(one_chip):
    from tpu_als.ops.pallas_solve import spd_solve_pallas

    compile_for(one_chip, spd_solve_pallas,
                ((2048, 128, 128), F32), ((2048, 128), F32))


def test_topk_scores_pallas_ml25m_catalog(one_chip):
    from tpu_als.ops.pallas_topk import topk_scores_pallas

    compile_for(one_chip, functools.partial(topk_scores_pallas, k=10),
                ((1024, 128), F32), ((ML25M_ITEMS, 128), F32),
                ((ML25M_ITEMS,), jnp.bool_))


def test_fold_in_solve_rank128(one_chip, monkeypatch):
    """fold_in's jitted body with the solve dispatch steered, in the test,
    to what `auto` resolves to on the chip (off-TPU the walk answers
    'xla' without asking the compiler)."""
    from tpu_als.core import foldin
    from tpu_als.ops import pallas_lanes, solve

    monkeypatch.setattr(solve, "auto_solve_backend", lambda rank: "lanes")
    monkeypatch.setattr(pallas_lanes, "available", lambda rank=128: True)
    monkeypatch.setitem(pallas_lanes._PANEL, 128, 32)
    monkeypatch.setitem(pallas_lanes._MXU, 128, True)
    body = functools.partial(foldin._fold_in_jit.__wrapped__,
                             reg_param=0.01, implicit_prefs=True,
                             alpha=40.0)
    compile_for(one_chip, body, ((ML25M_ITEMS, 128), F32),
                ((3, 64, 256), I32))


GATHER = [((60_000, 128), None), ((2048, 256), I32), ((2048, 256), None),
          ((2048, 256), None)]


def _gather_shapes(dt, extra=()):
    return [(s, dt if d is None else d) for s, d in GATHER] + list(extra)


def test_gather_gram_f32(one_chip):
    from tpu_als.ops.pallas_gather_ne import gather_gram

    compile_for(one_chip, functools.partial(gather_gram, two_sided=False),
                *_gather_shapes(F32))


def test_gather_solve_f32(one_chip):
    from tpu_als.ops.pallas_gather_ne import gather_solve

    compile_for(one_chip,
                functools.partial(gather_solve, two_sided=False, reg=0.1),
                *_gather_shapes(F32, [((2048, 256), F32),
                                      ((128, 128), F32)]))


@pytest.mark.xfail(strict=True, reason=(
    "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: "
    "infer-vector-layout: unsupported shape cast (vector<16x256xbf16> -> "
    "vector<16x256x1xbf16>); behind it, one-row DMAs of a bf16 table: "
    "'Slice shape along dimension 0 must be aligned to tiling (8)'"))
def test_gather_gram_bf16_still_refused(one_chip):
    from tpu_als.ops.pallas_gather_ne import gather_gram

    compile_for(one_chip, functools.partial(gather_gram, two_sided=False),
                *_gather_shapes(BF16))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=(
    "NotImplementedError: Unimplemented primitive in Pallas TPU lowering "
    "for KernelType.TC: reduce_precision"))
def test_gather_solve_bf16_still_refused(one_chip):
    from tpu_als.ops.pallas_gather_ne import gather_solve

    compile_for(one_chip,
                functools.partial(gather_solve, two_sided=False, reg=0.1),
                *_gather_shapes(BF16, [((2048, 256), BF16),
                                       ((128, 128), F32)]))


@pytest.mark.slow   # the unrolled rank-256 kernel takes minutes to compile
@pytest.mark.parametrize("mxu", [True, False])
def test_spd_solve_lanes_blocked_rank256(one_chip, mxu):
    from tpu_als.ops.pallas_lanes_blocked import spd_solve_lanes_blocked

    compile_for(one_chip,
                functools.partial(spd_solve_lanes_blocked, mxu=mxu),
                ((2048, 256, 256), F32), ((2048, 256), F32))


# -- the live cell's catalog programs at its size (PR 34) --------------------

LIVE_USERS, LIVE_ITEMS, LIVE_RANK, LIVE_SLOTS = 1_703_438, 1_505_938, 256, 512


def _compiled(sharding, jitted, *shapes, **statics):
    """``jitted`` (donations and all) compiled for the described chip."""
    return jitted.lower(*[jax.ShapeDtypeStruct(s, d, sharding=sharding)
                          for s, d in shapes], **statics).compile()


def _serve_int8(sharding, U, base, packed, delta=(), histories=(), pad=None):
    """The engine's one int8 program lowered for the described chip from
    ``(shape, dtype)`` pairs: ``delta`` the segment's five arrays and the
    last id, ``histories`` ``(runs, indices)`` (``runs`` a pair itself
    where the table is laid out to grow), or neither."""
    from tpu_als.serving.engine import _serve_int8_packed

    def of(sd):       # a ``(shape, dtype)`` pair, or a tuple of such
        if len(sd) == 2 and not isinstance(sd[1], tuple):
            return jax.ShapeDtypeStruct(*sd, sharding=sharding)
        return tuple(map(of, sd))

    return _serve_int8_packed.lower(
        of(U), *map(of, base), of(tuple(delta)), of(tuple(histories)),
        of(packed), k=10, shortlist_k=64, pad=pad)


def _live_catalog_shapes():
    from tpu_als.core.ratings import row_capacity
    from tpu_als.ops.topk import shortlist_columns

    cap = row_capacity(LIVE_ITEMS)
    cols = shortlist_columns(cap, 64)
    r, d = LIVE_RANK, LIVE_SLOTS
    base = [((cols, r), jnp.int8), ((cols,), jnp.float32),
            ((cap, r), jnp.float32), ((cols,), jnp.bool_)]
    seg = [((d,), jnp.int32), ((d, r), jnp.int8), ((d,), jnp.float32),
           ((d, r), jnp.float32), ((d,), jnp.bool_)]
    return cap, cols, base, seg


@pytest.mark.parametrize("bucket", [8, 128])
def test_serve_with_a_segment_at_the_live_cells_size(one_chip, bucket):
    """The one scoring program a bucket of an engine whose catalog moves:
    base + segment are whole shortlist blocks (no ragged last block to
    pad), and the program fits beside the tables."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.ops.topk import shortlist_plan
    cap, cols, base, seg = _live_catalog_shapes()
    plan = shortlist_plan(cols, 64, tail=LIVE_SLOTS)
    assert plan.stages == 2 and plan.columns % plan.block_len == 0
    c = _serve_int8(
        one_chip, ((row_capacity(LIVE_USERS), LIVE_RANK), jnp.float32),
        base, ((bucket, LIVE_RANK + 2), jnp.int32),
        delta=(*seg, ((), jnp.int32))).compile()
    assert c.memory_analysis().temp_size_in_bytes < 4 << 30


def test_the_catalog_writes_are_in_place_at_the_live_cells_size(one_chip):
    """The compaction, the engine's row write and the fold-in server's, as
    the chip's compiler builds them: the tables aliased to the results, no
    copy of one in the program."""
    from tpu_als.core.foldin import _scatter_rows
    from tpu_als.serving.engine import _scatter_items
    from tpu_als.serving.index import _fold_segment_inplace

    cap, cols, (Vq, sv, V, valid), seg = _live_catalog_shapes()
    rows, vals, ok = (((8,), jnp.int32), ((8, LIVE_RANK), jnp.float32),
                      ((8,), jnp.bool_))
    tables = (f"f32[{cap},{LIVE_RANK}]", f"s8[{cols},{LIVE_RANK}]")
    for fn, shapes in (
            (_fold_segment_inplace, (V, Vq, sv, valid, *seg)),
            (_scatter_items, (V, ((cap,), jnp.bool_), rows, vals, ok)),
            (_scatter_rows, (V, rows, vals))):
        text = _compiled(one_chip, fn, *shapes).as_text()
        assert "input_output_alias" in text
        assert not [ln for ln in text.splitlines()
                    if (" copy(" in ln or " copy-start(" in ln)
                    and any(t in ln for t in tables)], fn


def test_a_landings_programs_at_the_live_cells_size(one_chip):
    """What a refit's landing runs that no start does (``ServingEngine.
    warmup_landing``): a table copied on the device — a real second buffer,
    nothing aliased — and the whole catalog TABLE quantized, spare rows and
    block padding in one program that holds no copy of the table beside
    its result (a landing stands two engine generations side by side:
    13.4 of 16 GB)."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.serving.engine import _copy_table
    from tpu_als.serving.index import _quantize_rows

    cap, cols, (Vq, sv, V, valid), _ = _live_catalog_shapes()
    for table in (V, ((row_capacity(LIVE_USERS), LIVE_RANK), jnp.float32)):
        c = _compiled(one_chip, _copy_table, table)
        mem = c.memory_analysis()
        assert "input_output_alias" not in c.as_text()
        assert mem.output_size_in_bytes == mem.argument_size_in_bytes
        assert mem.temp_size_in_bytes == 0
    mem = _compiled(one_chip, _quantize_rows, V,
                    pad=cols - cap).memory_analysis()
    assert 0 <= mem.output_size_in_bytes - cols * (LIVE_RANK + 4) < 4096
    assert mem.temp_size_in_bytes < 64 << 20


# -- stage two of the shortlist: the layout its TopK is handed (PR 37) -------

MESH_ITEMS_PER_SHARD, MESH_USERS_PER_SHARD = 3_012_096, 3_460_224
BUCKETS = (8, 32, 128)


def _stage_two_topk(text):
    """``[(result layout, scoped bytes or None)]`` of the instructions
    that run ``serve.shortlist.blocks``' ``top_k`` (the ``TopK`` custom
    call, or the ``kCustom`` fusion around it): ``TopK`` keeps its
    operand's layout, so ``{1,0`` says the block maxima arrived with the
    blocks along the lanes, ``{0,1`` with the batch's rows there (8 of
    128 filled at bucket 8, 12.2 MB of scoped memory instead of 1.0)."""
    found = []
    for ln in text.splitlines():
        if (re.search(r'op_name="[^"]*serve\.shortlist\.blocks/top_k"', ln)
                and ('custom_call_target="TopK"' in ln
                     or "kind=kCustom" in ln)):
            layout = re.search(r"= \(f32\[[\d,]+\](\{[\d,]+)", ln).group(1)
            scoped = re.search(r'"size":"(\d+)"', ln)
            found.append((layout, int(scoped.group(1)) if scoped else None))
    return found


def _assert_stage_two_as_planned(text, columns, bucket):
    """Where the plan asks for row-major the compiler obeys and ``TopK``
    asks for about a megabyte; where it leaves the layout to the compiler
    (128 rows fill the lanes) the compiler still picks rows-along-lanes,
    which is what makes leaving it the cheaper choice there (0.31 against
    0.68 ms on the v5e, PERF.md section 6, PR 37)."""
    from tpu_als.ops.topk import shortlist_plan

    plan = shortlist_plan(columns, 64, rows=bucket)
    assert plan.blocks_layout == ("row_major" if bucket < 128
                                  else "compiler")
    found = _stage_two_topk(text)
    assert found, "no instruction carries serve.shortlist.blocks/top_k"
    if plan.blocks_layout == "compiler":
        assert all(layout == "{0,1" for layout, _ in found), found
        return
    assert all(layout == "{1,0" for layout, _ in found), found
    scoped = [s for _, s in found if s is not None]
    assert scoped and max(scoped) < 2 << 20, found


@pytest.mark.parametrize("bucket", BUCKETS)
def test_stage_two_topk_layout_in_the_steady_cells_program(
        one_chip, bucket):
    _, cols, base, _ = _live_catalog_shapes()
    Vq, sv, _, valid = base
    c = _serve_int8(
        one_chip, ((LIVE_USERS, LIVE_RANK), jnp.float32),
        (Vq, sv, ((LIVE_ITEMS, LIVE_RANK), jnp.float32), valid),
        ((bucket, LIVE_RANK + 2), jnp.int32)).compile()
    _assert_stage_two_as_planned(c.as_text(), cols, bucket)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_stage_two_topk_layout_with_a_segment(one_chip, bucket):
    from tpu_als.core.ratings import row_capacity

    _, cols, base, seg = _live_catalog_shapes()
    c = _serve_int8(
        one_chip, ((row_capacity(LIVE_USERS), LIVE_RANK), jnp.float32),
        base, ((bucket, LIVE_RANK + 2), jnp.int32),
        delta=(*seg, ((), jnp.int32))).compile()
    # the base's columns: the segment's scores join at stage three
    _assert_stage_two_as_planned(c.as_text(), cols, bucket)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_stage_two_topk_layout_on_every_shard_of_the_mesh_cell(
        topo, bucket):
    """``shortlist_rescore`` on a shard, inside the mesh engine's one
    program a bucket, at the mesh cell's 3,012,096 catalog rows a shard,
    for the four described chips."""
    c = _lowered("mesh", bucket, topo, None).compile()
    _assert_stage_two_as_planned(c.as_text(), MESH_ITEMS_PER_SHARD, bucket)


# -- per-request exclusion (PR 39) --------------------------------------------

UNSEEN_RATINGS = 17_860_625
HISTORY_PADS = (64, 512, 4096)
# sha256 of the lowered (StableHLO) text of the three programs the cells
# without histories run, at their cells' shapes.  Pinned again at PR 46,
# which made the three copies of the scoring pipeline one function
# (``serving.index.shortlist_rescore``) traced straight into the two
# engine programs: the nested ``jit(_int8_topk)`` / ``jit(_int8_topk_
# delta)`` calls, whose names stood in the text, went, and ``delta`` took
# the name ``_serve_int8_packed``.  What was compared before the hashes
# moved, here and for ``PARENT_LOWERED_SEEN`` below: the OPTIMISED HLO the
# chip's compiler (this file's described v5e) makes of parent (13dd465)
# and change for every program the six cells pin — int8 and exact, at
# buckets 8 / 32 / 128, steady, with the live cell's spare user rows,
# with a segment, on the four-chip mesh, and the programs that exclude at
# every history pad in both layouts of the histories: 51 programs.  With
# instruction, computation and parameter names, metadata and the tables
# of file and function names taken out, 39 read the same line for line;
# in 12 the entry's schedule is the same line for line and the fused
# computations are listed in another order (in 3 of them one fusion
# numbers its parameters otherwise); ``memory_analysis()`` is equal in
# all 51.  The text moved by names alone.  History of the hashes before:
# ``steady`` stood from 01a67e1 (before the engine knew of histories) to
# PR 45; ``delta`` and ``mesh`` were pinned at PR 43 (stage one reads the
# segment's scores as a tail; blocks of 256 through the lane maxima) and
# ``mesh`` again at PR 44 (the staged batch ``[S * B, rank + 2]`` by
# rows, one ``all_reduce`` of its block before the lookup)
PARENT_LOWERED = {
    ("steady", 8): "ce5ee2d9c92b0463", ("steady", 32): "cbc45aeebd13a265",
    ("steady", 128): "5a98f1e78003bc5a",
    ("delta", 8): "f60c536c4610f20d", ("delta", 32): "dc97ae197b569b91",
    ("delta", 128): "fd3a95afaeac44d6",
    ("mesh", 8): "12fa06f50388057e", ("mesh", 32): "1eba0b98255ed5b9",
    ("mesh", 128): "9bc54298d8f56bf5",
}


def _lowered(name, bucket, topo, one_chip):
    from tpu_als.core.ratings import row_capacity
    from tpu_als.ops.topk import shortlist_columns
    from tpu_als.serving.engine import _mesh_queries, _pack_response
    from tpu_als.serving.index import _build_sharded_int8

    cap, _, base, seg = _live_catalog_shapes()
    packed = ((bucket, LIVE_RANK + 2), jnp.int32)
    if name == "steady":
        cols = shortlist_columns(LIVE_ITEMS, 64)
        return _serve_int8(
            one_chip, ((LIVE_USERS, LIVE_RANK), jnp.float32),
            (((cols, LIVE_RANK), jnp.int8), ((cols,), jnp.float32),
             ((LIVE_ITEMS, LIVE_RANK), jnp.float32), ((cols,), jnp.bool_)),
            packed)
    if name == "delta":
        return _serve_int8(
            one_chip, ((row_capacity(LIVE_USERS), LIVE_RANK), jnp.float32),
            base, packed, delta=(*seg, ((), jnp.int32)))
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpu_als.parallel.mesh import AXIS, make_mesh

    mesh = make_mesh(devices=list(topo.devices))
    shards, ni, r = len(topo.devices), MESH_ITEMS_PER_SHARD, LIVE_RANK
    rows, whole = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())

    def shape(s, d, sharding):
        return jax.ShapeDtypeStruct(s, d, sharding=sharding)

    # as ``ServingEngine._int8_call`` builds it
    return _build_sharded_int8(
        mesh, 10, 10, 64, ni, False, _mesh_queries, _pack_response,
        "serve_mesh_int8").lower(
        shape((shards * MESH_USERS_PER_SHARD, r), jnp.float32, rows),
        shape((shards * bucket, r + 2), jnp.int32, rows),   # _place_one's
        shape((shards * ni, r), jnp.int8, rows),
        shape((shards * ni,), jnp.float32, rows),
        shape((shards * ni, r), jnp.float32, rows),
        shape((shards * ni,), jnp.bool_, rows),
        shape((), jnp.int32, whole))


@pytest.mark.parametrize("name,bucket", sorted(PARENT_LOWERED))
def test_programs_without_histories_lower_to_the_parents_text(
        topo, one_chip, name, bucket):
    import hashlib

    text = _lowered(name, bucket, topo, one_chip).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_LOWERED[name, bucket]


# -- stage one reads the score matrix once (PR 43) ------------------------------

SCORE_COLUMNS = {"steady": 1_506_048, "delta": 1_529_856,
                 "mesh": MESH_ITEMS_PER_SHARD}
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*?)\)(?:, |$)")


def _entry(text):
    """``[(name, result shapes, opcode, operand names, op_name)]`` of the
    compiled program's entry computation."""
    out = []
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = INSTRUCTION.match(ln)
        if m:
            scope = re.search(r'op_name="([^"]*)"', ln)
            out.append((m.group(1), m.group(2), m.group(3),
                        re.findall(r"%([\w.\-]+)", m.group(4)),
                        scope.group(1) if scope else ""))
    return out


def _over_the_matrix(text, elements):
    """The entry's instructions that write or read an f32 array of
    ``elements`` elements (the score matrix under any of its views), the
    ones that move no byte left out: ``{name: (opcode, op_name)}``."""
    def holds(shapes):
        return any(math.prod(map(int, dims.split(","))) == elements
                   for dims in re.findall(r"f32\[([\d,]+)\]", shapes))

    entry = _entry(text)
    matrix = {name for name, shapes, *_ in entry if holds(shapes)}
    return {name: (opcode, scope)
            for name, shapes, opcode, operands, scope in entry
            if opcode not in ("get-tuple-element", "bitcast", "tuple",
                              "parameter")
            and (name in matrix or matrix & set(operands))}


def test_the_mesh_programs_block_maxima_fold_before_they_reduce(
        topo, one_chip):
    """Bucket 8 of the mesh cell (97 % of its batches), blocks of 256: the
    compiler fuses no reduce over 256 lanes into the score fusion, so ONE
    instruction under ``serve.shortlist.blockmax`` reads the ``f32[8,
    3012096]`` matrix — a fusion that takes it as ``[.., 11766, 2, 128]``
    and folds the two 128-lane groups elementwise before it reduces
    (0.068 ms on the chip; the parent's plain ``reduce`` over ``[.., 11766,
    256]`` 0.165)."""
    text = _lowered("mesh", 8, topo, one_chip).compile().as_text()
    over = _over_the_matrix(text, 8 * MESH_ITEMS_PER_SHARD)
    (name,) = [name for name, (_, scope) in over.items()
               if "serve.shortlist.blockmax" in scope]
    assert over[name][0] == "fusion"
    called = re.search(rf"%{re.escape(name)} = .*calls=%([\w.\-]+)",
                       text).group(1)
    body = text[text.index(f"\n%{called} ("):]
    body = body[:body.index("\n}")]
    blocks = MESH_ITEMS_PER_SHARD // 256
    assert re.search(rf"f32\[(1,)?8,{blocks},2,128\]", body), body[:600]
    assert " maximum(" in body and " reduce(" in body


@pytest.mark.parametrize("bucket", BUCKETS)
def test_no_scoring_program_passes_over_the_matrix_more_than_steady(
        topo, one_chip, bucket):
    """With a segment the score matrix is written once and read as often
    as in the steady program of that bucket (at 8: by the gather alone;
    at 32 and 128, where the compiler writes it to HBM, by the block
    maxima's reduce and the gather), and the program holds no array of
    the concatenated width.  The mesh program, blocks of 256, pays the
    block maxima's pass at bucket 8 too (the test above), and nothing
    beyond it."""
    count = {}
    for name, columns in SCORE_COLUMNS.items():
        text = _lowered(name, bucket, topo, one_chip).compile().as_text()
        count[name] = _over_the_matrix(text, bucket * columns)
        if name == "delta":
            assert not re.search(r"\b(11956|1530368)\b", text)
    assert len(count["steady"]) == (2 if bucket == 8 else 3), count
    assert len(count["delta"]) <= len(count["steady"]), count
    assert len(count["mesh"]) <= 3, count


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_program_that_excludes_at_the_unseen_cells_size(one_chip, bucket):
    """``_serve_int8_packed`` with histories at every history pad of the
    cell's ladder: it compiles; beyond the program without histories it holds
    the one-byte mask and no four-byte matrix of the scores' size (the
    bit-packed words written out broadcast, or a second ``f32[B,
    columns]``); the only sort it adds is its own, unstable one (the
    compiler puts a stable sort, 9-15 s to compile, before a scatter it is
    not told is sorted), and no mask is copied row by row (the ``while``
    a scatter into ``bool[B, columns]`` ends in)."""
    from tpu_als.ops.topk import shortlist_columns
    from tpu_als.serving.engine import MAX_EXCLUDE

    cols = shortlist_columns(LIVE_ITEMS, 64)
    tables = [((LIVE_USERS, LIVE_RANK), jnp.float32),
              ((cols, LIVE_RANK), jnp.int8), ((cols,), jnp.float32),
              ((LIVE_ITEMS, LIVE_RANK), jnp.float32), ((cols,), jnp.bool_)]
    plain = _serve_int8(one_chip, tables[0], tables[1:],
                        ((bucket, LIVE_RANK + 2), jnp.int32)).compile()
    base = plain.memory_analysis().temp_size_in_bytes
    sorts = plain.as_text().count(" sort(")
    for pad in HISTORY_PADS:
        c = _serve_int8(
            one_chip, tables[0], tables[1:],
            ((bucket, LIVE_RANK + 2 + MAX_EXCLUDE), jnp.int32),
            histories=(((LIVE_USERS + 1,), jnp.int32),
                       ((UNSEEN_RATINGS + HISTORY_PADS[-1],), jnp.int32)),
            pad=pad).compile()
        text = c.as_text()
        entry = text[text.index("\nENTRY "):]
        extra = c.memory_analysis().temp_size_in_bytes - base
        assert extra < 1.05 * bucket * cols + (8 << 20), (pad, extra)
        assert text.count(" sort(") == sorts + 1, pad
        # the one loop is the histories' slices, a row each: none walks
        # a mask
        assert not [ln for ln in entry.splitlines()
                    if " while(" in ln and "pred[" in ln], pad
        assert not re.search(r"= u32\[368,32,\d+,128\]", entry), pad
        assert 'op_name="jit(_serve_int8_packed)/serve.exclude/' in text


# -- histories that grow (PR 42) -----------------------------------------------

# sha256 of the lowered text of the programs a generation WITH histories
# runs when nobody called ``warmup_live`` (the ``serve-unseen`` cell), at
# that cell's shapes: the histories as published keep their layout and
# their programs; only a table laid out to grow runs other programs
# (``runs`` a pair of arrays).  Taken at 906f260 (PR 42's parent), pinned
# again at PR 46 after the comparison above
PARENT_LOWERED_SEEN = {
    ("exact", 8, 4096): "acc254a58ae6577b",
    ("exact", 32, 4096): "f2439bc227f36498",
    ("exact", 128, 4096): "06dd0b878a1807c3",
    ("int8", 8, 64): "2e3edab155129c6c", ("int8", 8, 512): "0ab65b0db0163d0d",
    ("int8", 8, 4096): "9f0c49e4b5ef46e3",
    ("int8", 32, 64): "1eebc45e50694806",
    ("int8", 32, 512): "2a9faa5256dda76b",
    ("int8", 32, 4096): "c507bbfffef0cc1e",
    ("int8", 128, 64): "51b116703c9b54fb",
    ("int8", 128, 512): "eb6e54fb9b925f76",
    ("int8", 128, 4096): "b1866e399046b623",
}


def _seen_shapes(bucket, grown=False):
    """(catalog tables, where the runs lie, the ids, the staged batch) of
    the unseen cell; ``grown``: of the live-unseen cell after
    ``warmup_live`` (row capacity, room behind the runs, the 8,192 rung)."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.ops.topk import shortlist_columns
    from tpu_als.serving.engine import MAX_EXCLUDE

    cols = shortlist_columns(LIVE_ITEMS, 64)
    users = row_capacity(LIVE_USERS) if grown else LIVE_USERS
    tables = [((users, LIVE_RANK), jnp.float32),
              ((cols, LIVE_RANK), jnp.int8), ((cols,), jnp.float32),
              ((LIVE_ITEMS, LIVE_RANK), jnp.float32), ((cols,), jnp.bool_)]
    runs = (((users,), jnp.int32),) * 2 if grown \
        else ((LIVE_USERS + 1,), jnp.int32)
    ids = ((36_000_000 + 8192 if grown
            else UNSEEN_RATINGS + HISTORY_PADS[-1],), jnp.int32)
    packed = ((bucket, LIVE_RANK + 2 + MAX_EXCLUDE), jnp.int32)
    return tables, runs, ids, packed


@pytest.mark.parametrize("path,bucket,pad", sorted(PARENT_LOWERED_SEEN))
def test_histories_as_published_lower_to_the_parents_text(
        one_chip, path, bucket, pad):
    import hashlib

    from tpu_als.serving.engine import _serve_exact_packed

    tables, runs, ids, packed = _seen_shapes(bucket)
    if path == "int8":
        low = _serve_int8(one_chip, tables[0], tables[1:], packed,
                          histories=(runs, ids), pad=pad)
    else:
        one = lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip)
        low = _serve_exact_packed.lower(
            one(tables[0]), one(tables[3]), one(((LIVE_ITEMS,), jnp.bool_)),
            (one(runs), one(ids)), one(packed), k=10, item_chunk=8192,
            pad=pad)
    assert hashlib.sha256(low.as_text().encode()).hexdigest()[:16] \
        == PARENT_LOWERED_SEEN[path, bucket, pad]


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_program_that_excludes_from_histories_that_grow(one_chip, bucket):
    """``_serve_int8_packed`` over a table of histories laid out to grow
    (``(start, count)`` in place of ``indptr``) at the live-unseen cell's
    size and its top rung, 8,192: it compiles, with the one sort of its
    own, and holds no more than the program at 4,096 over the histories
    as published plus the wider lists."""
    def compiled(grown, pad):
        tables, runs, ids, packed = _seen_shapes(bucket, grown)
        return _serve_int8(one_chip, tables[0], tables[1:], packed,
                           histories=(runs, ids), pad=pad).compile()

    frozen, grown = compiled(False, 4096), compiled(True, 8192)
    assert grown.as_text().count(" sort(") \
        == frozen.as_text().count(" sort(")
    extra = (grown.memory_analysis().temp_size_in_bytes
             - frozen.memory_analysis().temp_size_in_bytes)
    # twice the keys to sort and scatter, the same mask
    assert extra < 16 * bucket * 8192 + (8 << 20), extra


@pytest.mark.parametrize("pad", (64, 512, 4096, 8192))
@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_program_that_excludes_and_scores_a_segment(one_chip, bucket,
                                                        pad):
    """``_serve_int8_packed`` given the segment AND histories that grow,
    at the ``serve-foldin-all`` cell's shapes (spare catalog rows, 512
    slots, the grown ladder's four pads): what ``warmup_live`` pins under
    ``(bucket, "int8_delta", pad)`` since PR 47.  It compiles; beyond the
    program with the segment alone it holds the one-byte mask and no
    ``[B, pad + 64, 512]`` compare of the lists against the slots (541 MB
    at bucket 128 and pad 8,192, were it written out); its one sort more
    is the mask's own.  Temporaries on the described v5e (PERF.md section
    5): 1.2-2.6 MB at bucket 8, 197 MB at 32, 983 MB at 128, whatever the
    pad."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.serving.engine import MAX_EXCLUDE

    cap, cols, base, seg = _live_catalog_shapes()
    users = ((row_capacity(LIVE_USERS), LIVE_RANK), jnp.float32)
    delta = (*seg, ((), jnp.int32))
    plain = _serve_int8(one_chip, users, base,
                        ((bucket, LIVE_RANK + 2), jnp.int32),
                        delta=delta).compile()
    _, runs, ids, packed = _seen_shapes(bucket, grown=True)
    assert packed == ((bucket, LIVE_RANK + 2 + MAX_EXCLUDE), jnp.int32)
    c = _serve_int8(one_chip, users, base, packed, delta=delta,
                    histories=(runs, ids), pad=pad).compile()
    text = c.as_text()
    extra = (c.memory_analysis().temp_size_in_bytes
             - plain.memory_analysis().temp_size_in_bytes)
    assert extra < 1.05 * bucket * cols + (8 << 20), extra
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    assert text.count(" sort(") == plain.as_text().count(" sort(") + 1
    assert not re.search(rf"pred\[{bucket},{pad + MAX_EXCLUDE},{LIVE_SLOTS}\]",
                         text[text.index("\nENTRY "):])
    assert 'op_name="jit(_serve_int8_packed)/serve.exclude/' in text


@pytest.mark.parametrize("rows,width", [(8, 4096), (8, 8192), (64, 8192)])
def test_fold_in_over_whole_histories_at_rank_256(one_chip, rows, width):
    """The fold-in program at the widths a resident base history brings, up
    to the most a call gathers (``FOLD_ELEMENTS``): it compiles, in true
    float32, within 1.5 GB of temporaries."""
    from tpu_als.core.foldin import _fold_in_jit
    from tpu_als.core.ratings import row_capacity
    from tpu_als.ops.solve import DEFAULT_JITTER
    from tpu_als.stream.microbatch import FOLD_ELEMENTS

    assert rows * width <= max(FOLD_ELEMENTS, 8 * width)
    c = _compiled(
        one_chip, _fold_in_jit,
        ((row_capacity(LIVE_ITEMS), LIVE_RANK), jnp.float32),
        ((3, rows, width), jnp.int32), ((), jnp.float32),
        implicit_prefs=False, alpha=1.0, nonnegative=False, nnls_sweeps=32,
        jitter=DEFAULT_JITTER, backend="xla")
    assert c.memory_analysis().temp_size_in_bytes < 1.5 * (1 << 30)
    assert 'op_name="jit(_fold_in_jit)/live.foldin.gram/' in c.as_text()


def test_the_history_writes_are_in_place_at_the_cells_size(one_chip):
    """``_append_runs`` and ``_move_run`` donate what they write into:
    aliased to the result in the compiled program, no copy of the 144 MB
    table; their operations carry the scope ``live.publish.history``."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.serving.engine import _append_runs, _move_run

    cap, size = row_capacity(LIVE_USERS), 36_000_000 + 8192
    tables = (f"s32[{cap}]", f"s32[{size}]")
    for fn, shapes, statics in (
            (_append_runs, (((cap,), jnp.int32), ((cap,), jnp.int32),
                            ((size,), jnp.int32), ((5, 8), jnp.int32)),
             {}),
            (_move_run, (((size,), jnp.int32), ((), jnp.int32),
                         ((), jnp.int32)), {"width": 8192})):
        c = _compiled(one_chip, fn, *shapes, **statics)
        text = c.as_text()
        assert "input_output_alias" in text
        assert not [ln for ln in text.splitlines()
                    if (" copy(" in ln or " copy-start(" in ln)
                    and any(t in ln for t in tables)], fn
        assert "live.publish.history" in text
        assert c.memory_analysis().temp_size_in_bytes < (1 << 20)
