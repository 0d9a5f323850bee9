"""Chunked GEMM + running top-k — the recommendation serving kernel.

Replaces the reference stack's ``recommendForAll`` path (blockify both factor
sets, crossJoin all block pairs, per-pair BLAS3 GEMM, per-row
``BoundedPriorityQueue`` merge across a shuffle — SURVEY.md §3.3) with a
single jitted scan: stream item-factor tiles through an MXU GEMM against the
resident user block and fold each tile's scores into a running
``jax.lax.top_k``.  No queues, no shuffle, no host round-trips.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from tpu_als.obs.schema import SERVE_EXCLUDE_SCOPE

# plain python float: creating a jnp scalar here would initialize the JAX
# backend as an import side effect
NEG_INF = -3.4e38


def topk_validity(scores):
    """Bool mask of the slots in a top-k result that hold a REAL score.

    When fewer than ``k`` items are valid (sparse ``item_valid``, a
    catalog smaller than ``k``, or all-False validity), the surplus
    slots carry the ``NEG_INF`` sentinel with arbitrary indices —
    callers must trim with this mask before surfacing results.  Works
    on the output of :func:`chunked_topk_scores`, the sharded
    ``parallel.serve.topk_sharded``, and the int8 index
    (``serving.index``): all three fill invalid slots with the same
    sentinel constant.
    """
    return scores > NEG_INF


# the id that pads a row's list of excluded ids: outside every catalog, so
# the scatter that reads it drops it (``serving.index.SLOT_FREE``'s value)
NOT_AN_ID = 2 ** 31 - 1


class ExclusionPlan(NamedTuple):
    """What :func:`excluded_mask` builds for ``rows`` rows of ``ids``
    excluded ids in all (its lists' widths added up) over ``columns``
    scores in blocks of ``block``: the words of its bit-packed mask (32
    blocks a word), the bytes of those words, and the keys it sorts."""

    rows: int
    ids: int
    columns: int
    block: int
    words: int
    mask_bytes: int
    keys: int


def exclusion_plan(rows, ids, columns, block):
    """The mask of ``rows`` rows of ``ids`` ids over ``columns`` scores —
    a function of those static numbers alone, so an event that reports
    it (``serving_exclusion``) cannot disagree with the program."""
    rows, ids, columns, block = int(rows), int(ids), int(columns), int(block)
    if columns % block:
        raise ValueError(f"{columns} columns are not whole blocks of "
                         f"{block}")
    words = -(-(columns // block) // 32) * rows * block
    if 32 * words >= 2 ** 31:
        raise ValueError(f"{rows} rows of {columns} columns: more (row, "
                         "column) pairs than one mask's int32 keys hold")
    return ExclusionPlan(rows, ids, columns, block, words, 4 * words,
                         rows * ids)


def excluded_mask(seen, columns, block):
    """``bool[columns // block, n, block]``: True at ``(id // block, row,
    id % block)`` for every id of the lists in ``seen`` — a sequence of
    ``int32[n, h]`` arrays, each row some of that row's excluded catalog
    ids in any order, padded with :data:`NOT_AN_ID` (an id outside ``[0,
    columns)`` is dropped; an id may stand twice).  Block-major, because
    that is how the scores lie on the TPU (8 rows by 128 lanes a tile,
    tile after tile along the columns): transposed to ``[n, columns //
    block, block]`` it is the row-major mask's own bytes, and a chunked
    scan takes its chunks as they come.

    THE RULE of per-request exclusion, which every scoring path applies
    through this one mask (``serving.index._int8_topk`` before its
    shortlist and again before the last ``top_k`` of its rescore,
    :func:`chunked_topk_scores` chunk by chunk): *the answer is what the
    same program would return if the excluded (row, column) pairs had
    ``valid = False`` for that row alone* — same sentinels (``NEG_INF``,
    :func:`topk_validity`), same tie rule, and where fewer than ``k``
    columns are left the surplus slots carry the sentinel.  Exact for any
    number of ids a row: nothing is "filtered after the shortlist".

    How (PERF.md section 6, PR 39, has the forms that were timed): a
    scatter into a ``bool[n, columns]`` matrix walks the whole matrix at
    60 GB/s on the v5e and is then copied row by row into the tiled
    layout (0.8 ms at 8 rows, 11 ms at 128).  So the mask is built
    bit-packed, 32 BLOCKS to a word — ``uint32[ceil(blocks / 32), n,
    block]``, 1.5 MB at 8 rows of 1.5 M columns — by one scatter-add of
    ``1 << (block % 32)``, and unpacked by a shift along the major
    dimension, which costs the tiled layout nothing.  The scatter is told
    its indices are sorted, and they are: the keys ``(word, bit)`` are
    sorted here first, UNSTABLE (the TPU compiler's own stable sort, which
    it puts before any scatter it is not told that of, compiles for 9-15
    s at 33,000 keys; the unstable one for 1.6 s), and a key that stands
    twice adds its bit once.  No ``f32[n, columns]`` is made here.
    """
    plan = exclusion_plan(seen[0].shape[0], sum(s.shape[1] for s in seen),
                          columns, block)
    n, blocks = plan.rows, columns // block
    with jax.named_scope(SERVE_EXCLUDE_SCOPE):
        ids = jnp.concatenate(seen, axis=1) if len(seen) > 1 else seen[0]
        row = jnp.arange(n, dtype=jnp.int32)[:, None]
        blk, lane = ids // block, ids % block
        key = (((blk >> 5) * n + row) * block + lane) * 32 + (blk & 31)
        key = jnp.where((ids >= 0) & (ids < columns), key, 32 * plan.words)
        key = jax.lax.sort(key.reshape(-1), is_stable=False)

        def scatter(key):
            once = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                    key[1:] != key[:-1]])
            return jnp.zeros((plan.words,), jnp.uint32).at[key >> 5].add(
                jnp.where(once,
                          jnp.uint32(1) << (key & 31).astype(jnp.uint32),
                          jnp.uint32(0)),
                mode="drop", indices_are_sorted=True)

        # the scatter pays by the key, padding included (8.7 ns each on
        # the v5e), and most batches hold far fewer ids than their pad
        # allows: the real keys sort first, so where they fit a quarter
        # of the list that quarter is all that is scattered
        few = plan.keys // 4
        if few >= 1024:
            # (the barrier: the compiler otherwise moves the broadcast
            # below into both branches, and they return four bytes a pair)
            words = jax.lax.optimization_barrier(jax.lax.cond(
                jnp.sum(key < 32 * plan.words) <= few,
                lambda key: scatter(key[:few]), scatter, key))
        else:
            words = scatter(key)
        bits = (words.reshape(-1, 1, n, block)
                >> jnp.arange(32, dtype=jnp.uint32)[None, :, None, None])
        # unpacked HERE, one byte a pair: without the barrier the compiler
        # moves the reshape below in front of the shift and then writes
        # the broadcast words out, four bytes a pair
        mask = jax.lax.optimization_barrier((bits & 1) != 0)
        return mask.reshape(-1, n, block)[:blocks]


# Stage two's ``TopK`` on the v5e (11,766 and 11,956 block maxima a row,
# k 64; PERF.md section 6, PR 37): handed its operand row-major it costs
# 5.4 us a row of the batch; handed it with the rows along the 128 lanes,
# which is how the block maxima leave the score fusion and what the
# compiler picks by itself, 0.30 ms whatever their number up to 128 (at 8
# rows 8 of 128 lanes hold data).  Below this many rows row-major is
# cheaper: 0.044 against 0.303 ms at 8, 0.173 against 0.299 at 32, 0.68
# against 0.31 at 128.
ROW_MAJOR_BELOW = 56


class ShortlistPlan(NamedTuple):
    """How :func:`shortlist_topk` selects ``k`` of ``columns`` scores a
    row (and of ``tail`` more behind them): in one ``lax.top_k``
    (``stages`` 1, one block of all columns) or over ``blocks``
    contiguous blocks of ``block_len`` columns; how stage one reduces a
    block (``blockmax``: ``"block"`` = each block of 128 lanes at once,
    ``"lanes"`` = a longer block folded to 128 lanes first, the
    elementwise maximum of its 128-lane groups, ``"none"`` with one
    stage); what stage two asks of the
    compiler for its operand (``blocks_layout``: ``"row_major"`` = the
    block maxima constrained to blocks-along-lanes before ``top_k``,
    ``"compiler"`` = no constraint, the layout is the compiler's); and
    how many columns stage three joins to its winners (``tail``)."""

    stages: int
    blocks: int
    block_len: int
    columns: int
    blocks_layout: str = "compiler"
    blockmax: str = "none"
    tail: int = 0


def shortlist_plan(columns, k, rows=None, tail=0):
    """The selection :func:`shortlist_topk` compiles for a ``[rows,
    columns]`` score matrix, ``k`` and a ``[rows, tail]`` tail — a
    function of those static numbers and nothing else (no argument,
    environment variable, planner entry or probe), so an event that
    reports it cannot disagree with the program.  ``rows`` decides
    ``blocks_layout`` alone (row-major below :data:`ROW_MAJOR_BELOW`
    rows); a plan asked for without it is the plan of the columns, its
    layout left at ``"compiler"``.  ``tail`` decides nothing: the stages
    and blocks are those of the ``columns`` alone.

    Two stages read ``columns`` scores once for the block maxima and
    then run ``TopK`` over ``blocks + k * block_len`` of them, which is
    least near ``block_len = sqrt(columns / k)``; the block length is
    the multiple of 128 (the TPU's lane width) nearest to that.  Where
    that is not at least four times fewer than ``columns`` (or there are
    fewer than ``k`` blocks to choose from) the single ``lax.top_k``
    stays: every small catalog.
    """
    columns, k, tail = int(columns), int(k), int(tail)
    if k >= 1:
        block_len = 128 * max(1, int(math.sqrt(columns / k) / 128 + 0.5))
        blocks = -(-columns // block_len)
        if blocks >= k and 4 * (k * block_len + blocks) <= columns:
            return ShortlistPlan(
                2, blocks, block_len, columns,
                "row_major" if rows is not None and rows < ROW_MAJOR_BELOW
                else "compiler",
                "lanes" if block_len > 128 else "block", tail)
    return ShortlistPlan(1, 1, columns, columns, tail=tail)


def shortlist_columns(columns, k):
    """``columns`` rounded up to whole blocks of its own
    :func:`shortlist_plan` (unchanged where one stage selects): the
    width an index pads its catalog to once, at build time, so that no
    batch pays for a ragged last block."""
    while True:
        plan = shortlist_plan(columns, k)
        if plan.stages == 1 or columns % plan.block_len == 0:
            return columns
        columns = plan.blocks * plan.block_len


def block_maxima(tiled, blockmax):
    """Stage one of :func:`shortlist_topk`: the maximum of every block
    of the ``[..., blocks, L]`` view, ``[..., blocks]`` — over the block
    at once (``blockmax`` = ``"block"``), or, for ``L`` a multiple of 128
    above it (``"lanes"``), with the block's 128-lane groups folded into
    one first, elementwise, and that reduced over its lanes: the same
    values bit for bit, a maximum of maxima.  What is dear in this pass on
    the TPU is the reduce ACROSS lanes, one for every 8 rows by 128 lanes;
    the fold halves their number for a block of 256 at the price of an
    elementwise maximum (PERF.md section 6, PR 43: 0.165 -> 0.068 ms for
    8 rows of 3.0 M columns; the forms that were timed are there)."""
    *lead, blocks, L = tiled.shape
    if blockmax == "lanes":
        groups = tiled.reshape(*lead, blocks, L // 128, 128)
        # slice by slice: a ``max`` over the groups' axis and one over the
        # lanes the compiler merges back into the reduce over the block
        folded = groups[..., 0, :]
        for g in range(1, L // 128):
            folded = jnp.maximum(folded, groups[..., g, :])
        return jnp.max(folded, axis=-1)
    return jnp.max(tiled, axis=-1)


def shortlist_topk(scores, k, tail=None):
    """``jax.lax.top_k(scores, k)`` for an f32 ``[n, N]`` matrix, element
    for element (values, indices, ties to the lower index, sentinels
    included), in two exact stages where :func:`shortlist_plan` says
    they pay: the maxima of contiguous blocks of ``L`` columns, ``top_k``
    of those for ``k`` blocks, then ``top_k`` over the ``k * L`` scores
    of the winning blocks.

    Exact because every member of a row's top ``k`` lies in one of the
    ``k`` blocks with the largest maximum: were ``x`` in the top ``k``
    and its block not chosen, the chosen blocks' maxima would be ``k``
    other elements that precede ``x`` (larger, or equal in a lower
    block, hence at a lower index).  The chosen block ids are sorted
    ascending before the gather, so position order in the gathered
    ``[n, k * L]`` is column order and the second ``top_k`` breaks ties
    as the single one would.

    ``tail`` (f32 ``[n, d]``): scores of ``d`` more columns that follow
    the matrix's own — positions ``N .. N + d - 1`` of the result — and
    the answer is ``lax.top_k(concatenate([scores, tail], 1), k)``'s.
    The matrix alone goes through stages one and two; the tail is joined
    to stage three's winners before its ``top_k``.  Exact for the reason
    above (a member of the top ``k`` of matrix and tail that lies in the
    matrix is in the matrix's own top ``k``, and every tail column is a
    candidate), and the joined ``[n, k * L + d]`` is in column order.  A
    caller that concatenated instead (a delta segment's 512 scores behind
    1.5 M) handed stage one a matrix whose block maximum the v5e compiler
    does not fuse into the fusion that computes the scores: it read the
    matrix a second time, 0.11 ms a batch of 8 rows (PERF.md section 6,
    PR 43).  With one stage the two are concatenated here.

    A ragged last block is padded with ``-inf`` — below every score
    and, being last, behind every real column in a tie (``k * L - L +
    1`` or more real columns precede the pad in stage three, so no pad
    is returned, tail or none).  An index that
    knows its shape ahead pads its catalog to
    :func:`shortlist_columns` instead and skips that copy.  The matrix
    is viewed as ``[n / 8, 8, blocks, L]``: on the TPU, whose f32 tile
    is 8 rows by 128 lanes, that view is the row-major matrix's own
    bytes, and both the block maximum and the gather read it in place
    (the flat ``[n, blocks, L]`` view cost two relayout copies of the
    whole matrix at ``n`` = 128: compiled HLO for the v5e, PR 26).

    Stage one (:func:`block_maxima`) reduces a block of 128 columns at
    once: the v5e compiler makes that reduce a second result of the
    fusion that computes the scores, for a batch whose matrix it keeps in
    fast memory (8 rows).  A reduce over a longer block it does not fuse:
    a pass of its own over the written matrix, 0.165 ms a batch of the
    mesh cell (blocks of 256).  There (``blockmax`` of the plan:
    ``"lanes"``) the block's 128-lane groups are folded into one first,
    elementwise, which leaves that pass half its reduces across lanes
    (0.068 ms).  Asked for the groups' maxima first, the compiler does
    fuse them into the score fusion — and that fusion, with 96 MB of
    matrix and the maxima's 12 MB (8 of 128 lanes filled) in fast memory,
    takes smaller windows and 0.09 ms longer: the slower form (PERF.md
    section 6, PR 43).

    Where the blocks come in whole tiles of 8 (``blocks % 8 == 0``: the
    live-items cell's 11,952) the v5e compiler gives the ``[n / 8, 8,
    blocks, L]`` view its default layout before a gather along
    ``blocks`` — legal there without padding — and COPIES the matrix into
    it: 0.043 ms a batch of 8 rows, 0.59 of 32, 2.36 of 128 (PR 43).
    There the winners are taken as rows of ``[n / 8 * blocks * 8, L]``,
    the view transposed to the order its bytes already have, which
    compiles to the row gather of the matrix in place that the other
    block counts get by themselves.

    The block maxima leave that reduce (on the chip: the score fusion)
    with the blocks along the sublanes and the batch's ``n`` rows along
    the 128 lanes, and ``TopK`` takes them as they come.  For a small
    batch (``blocks_layout`` of the plan) stage two therefore asks for
    them row-major first: a relayout of ``n * blocks`` floats, a few
    microseconds, for a ``TopK`` whose lanes are full.  A layout changes
    no value.
    """
    n, N = scores.shape
    plan = shortlist_plan(N, k, rows=n,
                          tail=0 if tail is None else tail.shape[1])
    if plan.stages == 1:
        if tail is not None:
            scores = jnp.concatenate([scores, tail], axis=1)
        return jax.lax.top_k(scores, k)
    L, blocks = plan.block_len, plan.blocks
    if blocks * L != N:
        scores = jnp.pad(scores, ((0, 0), (0, blocks * L - N)),
                         constant_values=-jnp.inf)
    sub = 8 if n % 8 == 0 else n
    tiled = scores.reshape(n // sub, sub, blocks, L)
    with jax.named_scope("serve.shortlist.blockmax"):
        block_max = block_maxima(tiled, plan.blockmax).reshape(n, blocks)
    with jax.named_scope("serve.shortlist.blocks"):
        if plan.blocks_layout == "row_major":
            block_max = with_layout_constraint(
                block_max, Layout(major_to_minor=(0, 1)))
        _, block_ids = jax.lax.top_k(block_max, k)
        block_ids = jnp.sort(block_ids, axis=1)
    with jax.named_scope("serve.shortlist.gather"):
        if blocks % 8:
            won = jnp.take_along_axis(
                tiled, block_ids.reshape(n // sub, sub, k, 1), axis=2)
        else:
            # the same rows, taken from the view's own bytes (docstring)
            rows = jnp.transpose(tiled, (0, 2, 1, 3)).reshape(-1, L)
            row = jnp.arange(n, dtype=jnp.int32)[:, None]
            at = ((row // sub) * blocks + block_ids) * sub + row % sub
            won = rows.at[at.reshape(-1)].get(mode="promise_in_bounds")
    with jax.named_scope("serve.shortlist.select"):
        flat = won.reshape(n, k * L)
        if tail is not None:
            flat = jnp.concatenate([flat, tail], axis=1)
        values, pos = jax.lax.top_k(flat, k)
        # a position past the winners is the tail's
        inb = pos if tail is None else jnp.minimum(pos, k * L - 1)
        cols = (jnp.take_along_axis(block_ids, inb // L, axis=1) * L
                + inb % L)
        if tail is not None:
            cols = jnp.where(pos < k * L, cols, N + pos - k * L)
    return values, cols


@functools.partial(jax.jit, static_argnames=("k", "item_chunk"))
def chunked_topk_scores(U, V, item_valid, k, item_chunk=8192, seen=None):
    """Top-k items per user row of ``U``.

    U [n, r]; V [Ni, r]; item_valid [Ni] bool (False rows never recommended —
    padding rows and cold items).  Returns (scores [n, k], indices [n, k]).
    ``seen`` (lists of ``int32[n, h]`` ids, as :func:`excluded_mask`
    takes them): ids each row is not to be answered with, by that
    function's rule.

    When a row has fewer than ``k`` valid items the remaining slots
    hold the ``NEG_INF`` sentinel score with MEANINGLESS indices (the
    running-merge init state) — apply :func:`topk_validity` to the
    scores to know which slots are real.
    """
    n, r = U.shape
    Ni = V.shape[0]
    nchunks = -(-Ni // item_chunk)
    pad = nchunks * item_chunk - Ni
    Vp = jnp.pad(V, ((0, pad), (0, 0)))
    validp = jnp.pad(item_valid, (0, pad)).astype(jnp.bool_)
    Vc = Vp.reshape(nchunks, item_chunk, r)
    validc = validp.reshape(nchunks, item_chunk)
    base = jnp.arange(nchunks, dtype=jnp.int32) * item_chunk
    xs = (Vc, validc, base)
    if seen is not None:
        # the rows' masks ride the scan chunk by chunk, beside ``valid``
        xs += (excluded_mask(seen, nchunks * item_chunk, item_chunk),)

    init_s = jnp.full((n, k), NEG_INF, dtype=jnp.float32)
    init_i = jnp.zeros((n, k), dtype=jnp.int32)

    def step(carry, chunk):
        best_s, best_i = carry
        Vt, valid, off, *excluded = chunk
        scores = jnp.einsum(
            "nr,cr->nc", U, Vt, preferred_element_type=jnp.float32
        )
        ok = valid[None, :]
        if excluded:
            ok = ok & ~excluded[0]
        scores = jnp.where(ok, scores, NEG_INF)
        ids = off + jnp.arange(Vt.shape[0], dtype=jnp.int32)
        cat_s = jnp.concatenate([best_s, scores], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (n, Vt.shape[0]))], axis=1)
        new_s, sel = jax.lax.top_k(cat_s, k)
        new_i = jnp.take_along_axis(cat_i, sel, axis=1)
        return (new_s, new_i), None

    (best_s, best_i), _ = jax.lax.scan(step, (init_s, init_i), xs)
    return best_s, best_i


def auto_topk_backend(rank, k):
    """The 'auto' probe walk: the fused Pallas kernel only on TPU, only
    for lane-sized k, and only after its compile-and-run probe passes —
    a Mosaic regression degrades to the scan instead of crashing
    serving.  Shared by :func:`topk_scores` and the execution planner
    (tpu_als.plan), so the warm-cache verdict and the cold walk cannot
    drift."""
    from tpu_als.ops import pallas_topk
    from tpu_als.utils.platform import on_tpu

    return ("pallas" if (on_tpu() and k <= 128
                         and pallas_topk.available(rank, k))
            else "xla")


def topk_scores(U, V, item_valid, k, item_chunk=8192, backend="auto"):
    """Top-k dispatch: the fused Pallas kernel on TPU (scores never touch
    HBM — tpu_als.ops.pallas_topk), the XLA scan elsewhere.

    backend: 'auto' (the :func:`auto_topk_backend` walk; when called
    EAGERLY with the planner armed the verdict goes through
    tpu_als.plan — a warm cache answers with zero probe executions —
    while a call under an ambient jit trace skips the planner's disk
    I/O and walks the in-process caches as before) | 'pallas' | 'xla'.
    """
    if backend == "auto":
        rank = U.shape[1]
        tracing = isinstance(U, jax.core.Tracer) \
            or isinstance(V, jax.core.Tracer)
        if not tracing:
            from tpu_als import plan as _plan

            if _plan.armed():
                backend = _plan.resolve_topk(
                    rank=rank, k=k,
                    walk=lambda: auto_topk_backend(rank, k))
        if backend == "auto":
            backend = auto_topk_backend(rank, k)
    if backend == "pallas":
        from tpu_als.ops.pallas_topk import topk_scores_pallas

        return topk_scores_pallas(U, V, item_valid, k)
    return chunked_topk_scores(U, V, item_valid, k, item_chunk=item_chunk)
