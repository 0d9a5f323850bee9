"""Int8 candidate index: quantized shortlist on the MXU, exact rescore.

At serving batch sizes the exact top-k pass (``ops/topk.py``) reads the
whole f32 item table per request batch — HBM bandwidth, not FLOPs, is
the wall.  Symmetric per-row int8 quantization cuts the scored bytes 4x
and runs the shortlist GEMM on the MXU's int8 path; the top
``shortlist_k`` candidates are then rescored EXACTLY in f32 so the
returned top-k is the exact kernel's up to f32 reduction-order rounding.

Contract (property-tested in tests/test_serving.py; written for the
JAX that is installed, 0.9.0): as long as the true top-k survives the
int8 shortlist, ``topk(U, k)`` returns

- scores within :data:`SCORE_ULPS` units in the last place — of the
  row's largest score — of ``chunked_topk_scores(U, V, valid, k)``, and
- the same indices on every row whose top-(k+1) exact scores are
  pairwise separated by more than twice that tolerance (closer scores
  may legitimately change places).

It is NOT bitwise.  The rescore contracts the ``[n, r]`` query batch
against ``n * shortlist_k`` gathered catalog columns, the chunked scan
against ``item_chunk`` columns; XLA is free to block the rank
contraction differently for the two GEMM shapes, so the last bits differ
(JAX 0.4.37's CPU backend happened not to; 0.9.0's does: 1.9e-6 on
scores of magnitude ~8 at rank 24, up to 10 ulp at rank 128 over 300
random shapes).  What does hold exactly:

- the rescore keeps the ``nr,cr->nc`` contraction of the chunked scan
  (a batched per-row gather, ``nr,nkr->nk``, drifts further);
- invalid slots carry the same ``NEG_INF`` sentinel constant the exact
  kernel uses, in the same places, so all-invalid rows and short
  catalogs degrade identically.

The shortlist itself is ``ops.topk.shortlist_topk``: what
``jax.lax.top_k`` over the approximate scores returns, element for
element, found in two exact stages (block maxima, then ``top_k`` over
the winning blocks) wherever the static shapes ``(columns,
shortlist_k)`` say that pays, and by the single ``top_k`` on every
small catalog.  The one pipeline here (:func:`shortlist_rescore`: base,
with a delta segment, per shard) calls it once.  A base index whose
shape engages two stages pads ``Vq`` / ``sv`` / ``valid`` — never
``V`` — with invalid columns to
whole blocks once, at build time (``ops.topk.shortlist_columns``):
they sort behind every real column, so no answer changes, and no batch
pays for a ragged last block.

The column-gather rescore prices at ``n * (n*shortlist_k) * r`` MACs —
an ``n``-fold overshoot versus the minimal per-row rescore — and still
beats the exact pass whenever ``n * shortlist_k < n_items``, i.e. for
any real catalog.  Shortlist soundness: per-row symmetric quantization
bounds the score error by ``~|u||v| r / 127``; a ``shortlist_k`` of a
few times ``k`` absorbs it on real factor distributions, and callers
that need certainty can set ``shortlist_k >= n_items`` (the shortlist
then covers the catalog and the contract is unconditional).

Incremental re-quantization (the live fold-in → publish loop): a
publish that changed 12 catalog rows must not re-quantize 50M.
:meth:`Int8CandidateIndex.with_updates` quantizes ONLY the
touched/appended rows into a small **delta segment** layered over the
untouched base arrays — O(touched) quantization work per publish —
and :meth:`compact` periodically folds the segment back into the base
(a memcpy-class scatter, no re-quantization at all).  The pinned
contract (``live_delta_index`` in analysis/contracts.py, property
matrix in tests/test_live.py): delta-segment and compacted ``topk``
are BITWISE equal to a full :func:`build_index` rebuild of the updated
catalog, under the same true-top-k-survives-the-shortlist condition as
the base contract.  Three ingredients make that exact rather than
approximate:

- per-row symmetric quantization has no cross-row state, so a touched
  row quantized alone is bit-identical to the same row quantized
  inside a full-catalog rebuild;
- the int8 shortlist GEMM accumulates in **int32** — exact integer
  arithmetic, order-independent — so scoring the base and the delta
  segment as two GEMMs yields approx scores elementwise bitwise equal
  to the rebuild's single GEMM, and the shortlist selects the same
  candidate value-set;
- the exact rescore keeps the base path's ``nr,cr->nc`` contraction at
  the same ``[n, n*shortlist_k]`` shapes, gathering candidate columns
  from base or delta by position.

Base rows overridden by the delta are masked to ``NEG_INF`` in the
base GEMM (their fresh values live in the segment), so a row is never
scored twice and never scored stale.

The segment lives on the DEVICE, in arrays of a fixed number of slots
(:attr:`Int8CandidateIndex.delta_slots`; free slots carry
:data:`SLOT_FREE` and ``valid=False``): ``with_updates`` uploads the
touched rows alone, quantizes them there and writes them into their
slots (a row already in the segment keeps its slot), so a row is
quantized and uploaded once and the scoring program's shapes do not
move while the segment has room.  The host keeps only which catalog id
sits in which slot.  :meth:`Int8CandidateIndex.reserve` gives the base
arrays spare rows and the segment its slots ahead of traffic;
:meth:`compact` scatters the segment into the base arrays IN PLACE (they
are donated: the index it is called on, and every index that shares its
base arrays, is spent afterwards — as a generation's user table after a
row write, ``serving/engine.py``).
"""

from __future__ import annotations

import copy
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from tpu_als.core.foldin import put
from tpu_als.core.ratings import _next_pow2, pad_for, pads_up_to
from tpu_als.obs.schema import SERVE_EXCLUDE_SCOPE, SERVE_MESH_SCOPES
from tpu_als.ops.topk import (
    NEG_INF,
    NOT_AN_ID,
    excluded_mask,
    shortlist_columns,
    shortlist_plan,
    shortlist_topk,
)
from tpu_als.parallel.mesh import AXIS, shard_leading, shard_map
from tpu_als.serving import pins

# how far a rescored score may sit from the chunked kernel's, in units in
# the last place of the row's largest score (module docstring)
SCORE_ULPS = 16

# the catalog id a free slot of the delta segment carries: outside every
# catalog, so the scatters that read it (the override mask, the
# compaction) drop it whatever the base arrays' size
SLOT_FREE = NOT_AN_ID


@functools.partial(jax.jit, static_argnames=("pad",))
def _quantize_rows(X, pad=0):
    """Symmetric per-row int8: scale = max|row| / 127 (zero rows get
    scale 1 so the division is safe and the row quantizes to zeros).
    ``pad`` appends that many zero rows, as quantized zero rows come out
    (zeros, scale 1), written with the rest: no second copy of a large
    catalog."""
    s = jnp.max(jnp.abs(X), axis=1) / 127.0
    s = jnp.where(s == 0.0, 1.0, s).astype(jnp.float32)
    q = jnp.clip(jnp.round(X / s[:, None]), -127, 127).astype(jnp.int8)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        s = jnp.pad(s, (0, pad), constant_values=1.0)
    return q, s


def mask_block(columns):
    """The block :func:`shortlist_rescore` asks ``ops.topk.excluded_mask`` for
    over ``columns`` scores: the TPU's 128 lanes where the columns are
    whole blocks of them (every catalog the shortlist takes in two
    stages, which an index pads to whole blocks), else one block of all
    columns (a small catalog)."""
    return 128 if columns % 128 == 0 else columns


def shard_lists(seen, first, columns):
    """Lists of LOGICAL excluded ids (``ops.topk.excluded_mask``'s
    ``seen``) as the lists of one shard's own columns: the shard holds
    catalog ids ``[first, first + columns)``, an id inside them becomes
    its column ``id - first``, every other ``NOT_AN_ID`` (another
    shard's to mask; the padding stays what it is)."""
    with jax.named_scope(SERVE_EXCLUDE_SCOPE):
        return tuple(
            jnp.where((ids >= first) & (ids - first < columns), ids - first,
                      NOT_AN_ID) for ids in seen)


def shortlist_rescore(U, Vq, sv, V, valid, *, k, shortlist_k, delta=None,
                      last_id=None, seen=None, shard=None):
    """THE scoring pipeline, traced into its caller's program: ``U``
    quantized per row, the int8 GEMM against ``Vq`` (int8 x int8 ->
    int32 on the MXU) rescaled to approximate f32 scores, what may not
    be answered masked to ``NEG_INF``, ``ops.topk.shortlist_topk``, the
    candidates' f32 rows gathered and rescored exactly with the chunked
    kernel's own contraction shape (full ``U`` batch x gathered catalog
    columns: module docstring), the last ``top_k``.  Returns the top
    ``k`` ``(scores [n, k], logical catalog ids [n, k])``.  What is
    passed beside the base arrays decides, at trace time, what else it
    does; ``None`` traces nothing for it:

    ``delta`` — the segment's five arrays ``(drows, dVq, dsv, dV,
    dvalid)``: a second int8 GEMM over its slots, whose scores join the
    shortlist as its ``tail`` (what a shortlist over the two
    concatenated returns, without a second pass over the matrix), base
    columns the segment overrides masked (whatever ``dvalid`` says: a
    slot may mark an item invalid), and the SAME-shaped rescore (why this
    stays bitwise: module docstring).  ``drows`` maps slots to logical
    ids; free slots carry :data:`SLOT_FREE` (out of every scatter's
    range, ``dvalid`` False).  An appended id may fall on a spare or
    block-padding column of ``Vq``: invalid already, so marking it
    overridden changes nothing.  ``last_id`` clamps the ids into the
    logical catalog (a sharded caller clamps after its merge instead).

    ``seen`` — lists of ``int32[n, h]`` ids padded with ``NOT_AN_ID``,
    the ids each row is not to be answered with
    (``ops.topk.excluded_mask`` takes them and states the rule): out of
    the approximate scores before the shortlist, and out of the rescored
    candidates before the last ``top_k`` (a candidate list of a row with
    fewer than ``shortlist_k`` columns left holds some).

    ``shard`` — ``(me, ni_loc)`` inside ``shard_map``: the base arrays
    are this shard's slice, catalog ids ``[me * ni_loc, (me + 1) *
    ni_loc)``, which the returned ids are offset by; the (replicated)
    segment is scored by every shard but masked to the slots it OWNS, so
    each is scored exactly once mesh-wide.  No shard sees another's
    rows.  ``seen`` holds LOGICAL ids, the same lists on every shard:
    each masks the ids it owns among its own columns
    (:func:`shard_lists`) — every excluded id is masked by exactly one
    shard — and compares the segment's slots with the lists as they
    are."""
    n, nb = U.shape[0], Vq.shape[0]
    # the catalog id of this shard's first row (without a shard nothing
    # is traced for it, no ``+ 0``)
    first = None if shard is None else shard[0] * shard[1]
    Uq, su = _quantize_rows(U)
    acc = jnp.einsum("nr,cr->nc", Uq, Vq,
                     preferred_element_type=jnp.int32)
    approx = acc.astype(jnp.float32) * su[:, None] * sv[None, :]
    base_ok, approx_d = valid, None
    if delta:
        drows, dVq, dsv, dV, dvalid = delta
        d = dVq.shape[0]
        at, dmask = drows, dvalid       # the slots' columns of the base
        if shard is not None:
            at = drows - first
            owned = (at >= 0) & (at < nb)
            at, dmask = jnp.where(owned, at, nb), dvalid & owned
        # a base row the segment overrides (or an id out of this base's
        # range, dropped) must never shortlist from its stale value
        over = jnp.zeros((nb,), jnp.bool_).at[at].set(True, mode="drop")
        base_ok = valid & ~over
    ok = base_ok[None, :]
    if seen is not None:
        # block-major: transposed it is the row-major mask's own bytes
        excluded = excluded_mask(
            seen if shard is None else shard_lists(seen, first, nb), nb,
            mask_block(nb)).transpose(1, 0, 2).reshape(n, nb)
        ok = ok & ~excluded
    approx = jnp.where(ok, approx, NEG_INF)
    if delta:
        acc_d = jnp.einsum("nr,cr->nc", Uq, dVq,
                           preferred_element_type=jnp.int32)
        approx_d = acc_d.astype(jnp.float32) * su[:, None] * dsv[None, :]
        ok_d = dmask[None, :]
        if seen is not None:
            # a slot's logical id against the lists themselves: it may
            # lie past the base's columns, where the mask ends
            excluded_d = (jnp.concatenate(seen, axis=1)[:, :, None]
                          == drows[None, None, :]).any(axis=1)
            ok_d = ok_d & ~excluded_d
        approx_d = jnp.where(ok_d, approx_d, NEG_INF)
    # with a segment: positions in nb + d
    _, cand = shortlist_topk(approx, shortlist_k, tail=approx_d)
    flat = cand.reshape(-1)
    if delta:
        in_base = flat < nb
        base_ix = jnp.minimum(flat, nb - 1)
        delta_ix = jnp.clip(flat - nb, 0, d - 1)
        Vc = jnp.where(in_base[:, None], jnp.take(V, base_ix, axis=0),
                       jnp.take(dV, delta_ix, axis=0))  # [n*sk, r]
    else:
        Vc = jnp.take(V, flat, axis=0)
    exact_all = jnp.einsum("nr,cr->nc", U, Vc,
                           preferred_element_type=jnp.float32)
    rows = (jnp.arange(n, dtype=jnp.int32)[:, None] * shortlist_k
            + jnp.arange(shortlist_k, dtype=jnp.int32)[None, :])
    exact = jnp.take_along_axis(exact_all, rows, axis=1)
    logical = flat if shard is None else flat + first
    if delta:
        cand_ok = jnp.where(in_base, jnp.take(base_ok, base_ix),
                            jnp.take(dmask, delta_ix))
        logical = jnp.where(in_base, logical, jnp.take(drows, delta_ix))
        cand_ok = cand_ok.reshape(n, shortlist_k)
    else:
        # (a shard gathers over the flat list, a whole index over ``[n,
        # shortlist_k]``: the forms the two compiled from before they
        # were one function, kept so that both compile to what the chip
        # was measured running)
        cand_ok = (jnp.take(base_ok, cand) if shard is None
                   else jnp.take(base_ok, flat).reshape(n, shortlist_k))
    if seen is not None:
        gone = jnp.take_along_axis(excluded, cand, axis=1)
        if delta:
            gone = jnp.where(cand < nb, gone, jnp.take_along_axis(
                excluded_d, jnp.clip(cand - nb, 0, d - 1), axis=1))
        cand_ok &= ~gone
    exact = jnp.where(cand_ok, exact, NEG_INF)
    s, sel = jax.lax.top_k(exact, k)
    if last_id is not None:
        logical = jnp.minimum(logical, last_id)
    return s, jnp.take_along_axis(logical.reshape(n, shortlist_k), sel,
                                  axis=1)


# what the indexes' own ``topk`` run: the pipeline as a program by itself
_topk_jit = jax.jit(shortlist_rescore, static_argnames=("k", "shortlist_k"))


# rows of the host array a segment write takes: each row's slot, its
# catalog id, its valid bit, and (all along the fourth) the catalog's last
# id afterwards
SEGMENT_SENT = 4


@functools.partial(jax.jit, static_argnames=("at",))
def _write_segment(drows, dVq, dsv, dV, dvalid, sent, rows, *, at=0):
    """``rows`` (f32 ``[n, rank]``, from the host or, a fold's own
    result, on the device already) into their slots of the delta segment,
    quantized here — per row, so bit for bit what a rebuild of the whole
    catalog would hold.  What the write needs from the host is ONE
    array, ``sent``: ``int32``, its rows ``at`` to ``at + SEGMENT_SENT``
    the slots, the catalog ids, the valid bits and the catalog's last id
    (``at``: whoever sends more in the same array says where these lie),
    of which the first ``n`` columns are read.  The update is padded up
    ``pad_for``'s ladder with slots outside the segment, which are
    dropped: few programs, and only the touched payload crosses
    host→device.  Nothing is donated: the generation before scores
    against its own segment, and a segment is a few MB.  Returns the
    segment and, for whoever writes the same rows elsewhere or clamps
    answers to the catalog, ``(ids, valid bits, last id)`` on the
    device."""
    slots, ids, ok = (sent[at + i, :rows.shape[0]] for i in range(3))
    valid = ok != 0
    with jax.named_scope("live.publish.scatter_items"):
        q, s = _quantize_rows(rows)
        return (drows.at[slots].set(ids, mode="drop"),
                dVq.at[slots].set(q, mode="drop"),
                dsv.at[slots].set(s, mode="drop"),
                dV.at[slots].set(rows, mode="drop"),
                dvalid.at[slots].set(valid, mode="drop"),
                ids, valid, sent[at + 3, 0])


class SegmentUpdate(typing.NamedTuple):
    """The host's side of one update of the delta segment
    (:meth:`Int8CandidateIndex.plan_update`), before anything is sent."""

    ids: np.ndarray     # the catalog ids, ascending, none twice
    slots: np.ndarray   # the slot each one takes (its own, or a free one)
    ok: np.ndarray      # the valid bit of each
    n_items: int        # the catalog's size afterwards
    d_rows: np.ndarray  # the segment's ids by slot afterwards

    def sent(self, pad, order=None):
        """:func:`_write_segment`'s ``sent`` for rows that lie in
        ``order`` (indices into ``ids``; as ``ids`` without), padded to
        ``pad`` with slots and ids outside every array."""
        o, n = slice(None) if order is None else order, len(self.ids)
        sent = np.full((SEGMENT_SENT, pad), SLOT_FREE, dtype=np.int32)
        sent[0, :n], sent[1, :n] = self.slots[o], self.ids[o]
        sent[2, :n], sent[2, n:] = self.ok[o], 0
        sent[3] = self.n_items - 1
        return sent


def _fold_segment(V, Vq, sv, valid, drows, dVq, dsv, dV, dvalid):
    """The segment's rows scattered into the base arrays at their
    catalog ids (free slots fall outside and are dropped), and the
    emptied slot map: a compaction.  Nothing is quantized again."""
    with jax.named_scope("live.publish.compact"):
        return (V.at[drows].set(dV, mode="drop"),
                Vq.at[drows].set(dVq, mode="drop"),
                sv.at[drows].set(dsv, mode="drop"),
                valid.at[drows].set(dvalid, mode="drop"),
                jnp.full_like(drows, SLOT_FREE), jnp.zeros_like(dvalid))


# the base arrays donated: the same buffers come back with the segment's
# rows written, no copy of the catalog on the device; the caller's
# handles are deleted (``Int8CandidateIndex.compact``)
_fold_segment_inplace = jax.jit(_fold_segment, donate_argnums=(0, 1, 2, 3))
# for base arrays sharded over a mesh, which are re-placed afterwards
_fold_segment_copied = jax.jit(_fold_segment)


def segment_write_bytes(n_rows, rank):
    """Bytes :meth:`Int8CandidateIndex.with_updates` uploads for
    ``n_rows`` touched rows: :func:`_write_segment`'s ``sent`` and the
    f32 rows, padded up the ladder (the engine's own table takes the
    same arrays on the device: no second upload)."""
    return pad_for(n_rows) * 4 * (SEGMENT_SENT + int(rank))


class Int8CandidateIndex:
    """Quantize-once-per-publish candidate index over the item factors.

    Built by :meth:`ServingEngine.publish` (or directly from ``V``);
    ``seq`` tags the model publish the index belongs to, so the engine
    can detect a stale index (catalog swapped, index not rebuilt) and
    fall back to the exact path instead of serving against the wrong
    catalog.
    """

    def __init__(self, V, item_valid=None, shortlist_k=64, seq=0):
        V = jnp.asarray(V, dtype=jnp.float32)
        Ni = int(V.shape[0])
        if Ni == 0:
            raise ValueError("cannot index an empty catalog")
        self.V = V
        self.n_items = Ni
        self.shortlist_k = min(int(shortlist_k), Ni)
        # whole shortlist blocks (module docstring); 0 on a small catalog
        pad = shortlist_columns(Ni, self.shortlist_k) - Ni
        valid = (jnp.ones(Ni, dtype=jnp.bool_) if item_valid is None
                 else jnp.asarray(item_valid, dtype=jnp.bool_))
        self.valid = jnp.pad(valid, (0, pad)) if pad else valid
        self.Vq, self.sv = _quantize_rows(V, pad=pad)
        self.seq = seq
        self._clear_delta()

    @classmethod
    def over(cls, V, item_valid, n_items, shortlist_k=64, seq=0, slots=0):
        """The index over a catalog TABLE as it lies on the device: ``V``
        ``[rows, rank]`` float32 with ``n_items`` live rows and spare
        (zero) rows after them, ``item_valid`` the host's ``bool[rows]``
        (false from ``n_items`` on), an empty segment of ``slots`` slots
        — array for array the shapes of ``build_index`` of the live rows
        + :meth:`reserve` ``(rows, slots)``, and the same bits (a zero
        row quantizes to zeros at scale 1, which is how ``reserve`` pads),
        with no smaller index in between: what replaces a live generation
        whose programs are compiled for those shapes.  ``V`` becomes the
        index's own (:meth:`compact` donates it): hand over a buffer
        nothing else writes."""
        new = cls.__new__(cls)
        rows = int(V.shape[0])
        new.V, new.n_items, new.seq = V, int(n_items), seq
        new.shortlist_k = min(int(shortlist_k), new.n_items)
        cols = shortlist_columns(rows, new.shortlist_k)
        valid = np.zeros(cols, dtype=bool)
        valid[:rows] = np.asarray(item_valid, dtype=bool)
        new.valid = jnp.asarray(valid)
        new.Vq, new.sv = _quantize_rows(V, pad=cols - rows)
        new._clear_delta()
        if slots:
            new._seg = new._with_slots(int(slots))
        return new

    def prewarm_over(self):
        """Run what :meth:`over` runs at this index's shapes — the whole
        base table quantized, block padding and all — and drop the
        result (0.4 GB at 1.5 M x 256, for the length of the call)."""
        rows = self.n_base
        jax.block_until_ready(_quantize_rows(
            self.V, pad=shortlist_columns(rows, self.shortlist_k) - rows))

    def shortlist_plan(self, rows=None):
        """The selection :meth:`topk` compiles for this index as it
        stands and a batch of ``rows`` queries: the
        ``ops.topk.shortlist_plan`` of its base's score matrix, the
        delta segment's slots its ``tail``."""
        return shortlist_plan(int(self.Vq.shape[0]), self.shortlist_k, rows,
                              tail=self.delta_slots)

    # -- delta segment (incremental re-quantization) -------------------

    def _clear_delta(self):
        # the host's part of the segment: which catalog id sits in which
        # slot, in slot order (O(delta rows)); the rows themselves are on
        # the device, once
        self.d_rows = np.empty(0, dtype=np.int64)
        # (drows, dVq, dsv, dV, dvalid), ``delta_slots`` slots each
        self._seg = None
        self._last = None
        self.written = None     # with_updates: what it uploaded

    @staticmethod
    def _put(arrays):
        """Host arrays onto the device(s) the segment lives on."""
        return put(arrays)

    @property
    def n_base(self):
        """Rows held by the base (pre-delta) arrays, spare rows counted,
        block padding of the quantized ones not."""
        return int(self.V.shape[0])

    @property
    def delta_count(self):
        """Rows currently carried by the delta segment."""
        return int(self.d_rows.size)

    @property
    def delta_slots(self):
        """Columns the delta segment adds to the score matrix: the slots
        it has, used or free (0 without a segment)."""
        return 0 if self._seg is None else int(self._seg[0].shape[0])

    def _copy_shell(self, seq):
        new = copy.copy(self)       # shallow: every array shared
        new.seq = self.seq if seq is None else int(seq)
        new.written = None
        return new

    def retag(self, seq):
        """A shallow copy sharing every array, tagged for a new publish.

        The zero-cost incremental publish: a USER fold-in changes no
        catalog row, so the index is carried FRESH (scored against)
        instead of rebuilt or marked stale.  Instances are treated as
        immutable — the engine never re-tags in place.
        """
        return self._copy_shell(seq)

    def _with_slots(self, slots):
        """The segment with ``slots`` slots, what it holds kept: a new
        set of (small) arrays, and new shapes for the scoring program."""
        r = int(self.V.shape[1])
        seg = self._put((np.full(slots, SLOT_FREE, np.int32),
                         np.zeros((slots, r), np.int8),
                         np.ones(slots, np.float32),
                         np.zeros((slots, r), np.float32),
                         np.zeros(slots, bool)))
        if self._seg is None:
            return seg
        old = self.delta_slots
        return tuple(a.at[:old].set(b) for a, b in zip(seg, self._seg))

    def reserve(self, rows=0, slots=0):
        """A new index over the same catalog whose base arrays hold
        ``rows`` catalog rows (spare ones zero and invalid; the
        quantized arrays in whole shortlist blocks of that many, as
        :func:`build_index` of ``rows`` items would pad them) and whose
        segment has ``slots`` slots: appended items and a filling segment
        then change no array's shape, so whoever serves from the index
        compiles its programs once.  One copy of each array it enlarges,
        here; arrays already that large are shared."""
        new = self._copy_shell(None)
        nb, r = self.n_base, int(self.V.shape[1])
        if rows > nb:
            cols = shortlist_columns(rows, self.shortlist_k)
            new.V = jnp.pad(self.V, ((0, rows - nb), (0, 0)))
            # from the catalog's own rows on: the block padding of the
            # smaller size may reach further than that of the larger
            new.Vq = jnp.pad(self.Vq[:nb], ((0, cols - nb), (0, 0)))
            new.sv = jnp.pad(self.sv[:nb], (0, cols - nb),
                             constant_values=1.0)
            new.valid = jnp.pad(self.valid[:nb], (0, cols - nb))
        if slots > self.delta_slots:
            new._seg = new._with_slots(int(slots))
        return new

    def _held(self, ids):
        """``(held, slot)`` for catalog ``ids``: which of them the
        segment holds, and in which slot (meaningless where not held)."""
        if not self.delta_count:
            return (np.zeros(len(ids), dtype=bool),
                    np.zeros(len(ids), dtype=np.int64))
        by_id = np.argsort(self.d_rows, kind="stable")
        at = np.minimum(np.searchsorted(self.d_rows[by_id], ids),
                        self.delta_count - 1)
        return self.d_rows[by_id[at]] == ids, by_id[at]

    def slots_needed(self, rows):
        """How many free slots ``with_updates(rows, ...)`` would take: a
        row already in the segment keeps its slot."""
        rows = np.unique(np.asarray(rows, dtype=np.int64).ravel())
        return int(rows.size - self._held(rows)[0].sum())

    def _checked_update(self, rows, valid_rows):
        """``(ids ascending, none twice; where in ``rows`` each one's LAST
        mention stands; their valid bits; the catalog's size afterwards)``
        of an update to ``rows`` (at least one), or ``ValueError``: a
        negative id, or appended ids that leave a hole above the
        catalog."""
        valid_rows = (np.ones(len(rows), dtype=bool) if valid_rows is None
                      else np.asarray(valid_rows, dtype=bool).ravel())
        if rows.min() < 0:
            raise ValueError("negative catalog row id in delta update")
        # newest-wins dedup inside the call: keep each id's LAST row
        uniq, first_rev = np.unique(rows[::-1], return_index=True)
        last = len(rows) - 1 - first_rev
        n_new = int(max(self.n_items, int(uniq[-1]) + 1))
        appended = uniq[uniq >= self.n_items]
        if len(appended) != n_new - self.n_items:
            gap = sorted(set(range(self.n_items, n_new))
                         - set(appended.tolist()))
            raise ValueError(
                f"append gap: ids {gap} missing — appended rows must "
                "be contiguous above the current catalog")
        return uniq, last, valid_rows[last], n_new

    def _host_rows(self, V_rows, n):
        """``V_rows`` as the ``n`` f32 rows of an update."""
        return np.asarray(V_rows, dtype=np.float32).reshape(
            n, int(self.V.shape[1]))

    def plan_update(self, rows, valid_rows=None):
        """``(SegmentUpdate, where in ``rows`` each of its ids' LAST
        mention stands)`` of an update to catalog ``rows`` (see
        :meth:`with_updates`; none: an update that writes nothing): the
        host's side alone, nothing sent, nothing written.  A row the
        segment holds keeps its slot, the others take the next free
        ones."""
        rows = np.asarray(rows, dtype=np.int64).ravel()
        if len(rows) == 0:
            none = np.empty(0, dtype=np.int64)
            return SegmentUpdate(none, none, none.astype(bool),
                                 self.n_items, self.d_rows), none
        ids, last, ok, n_new = self._checked_update(rows, valid_rows)
        held, slots = self._held(ids)
        slots[~held] = self.delta_count + np.arange(int((~held).sum()))
        return SegmentUpdate(ids, slots, ok, n_new, np.concatenate(
            [self.d_rows, ids[~held]])), last

    def write_update(self, update, sent, rows, at=0, seq=None):
        """A new index with ``update`` (:meth:`plan_update`) written:
        ``sent`` is its :meth:`SegmentUpdate.sent` on the device — rows
        ``at`` to ``at + SEGMENT_SENT`` of a larger array, where its
        sender had more to send — and ``rows`` the f32 rows in the same
        order, on the device, padded (uploaded with it, or a fold's own
        result: nothing of them crosses host→device then).  A segment
        without room for the new rows is enlarged to the next power of
        two."""
        new = self._copy_shell(seq)
        new.n_items, new.d_rows = update.n_items, update.d_rows
        seg = self._seg
        if len(new.d_rows) > self.delta_slots:
            seg = self._with_slots(_next_pow2(len(new.d_rows)))
        *new._seg, ids, ok, last = _write_segment(*seg, sent, rows, at=at)
        new._seg = tuple(new._seg)
        # the rows as they were written — ``(ids, rows, valid bits)``,
        # padded with ids outside any table — for whoever writes them
        # elsewhere too (the engine's own catalog): nothing is uploaded
        # twice; and the last catalog id, which came up with them
        new.written = (ids, rows, ok)
        new._last = (new.n_items, last)
        return new

    def with_updates(self, rows, V_rows, valid_rows=None, seq=None):
        """A new index with ``rows`` of the catalog re-quantized into
        the delta segment — O(len(rows)) upload and quantization work,
        the base arrays shared untouched.

        ``rows`` are logical catalog ids; ids ``>= n_items`` APPEND
        (catalog growth from an item fold-in) and must leave no hole
        above the current catalog size.  A row already in the segment
        is replaced in its slot (newest wins).  Quantizing only the
        touched rows is bitwise-identical to a full rebuild because
        quantization is strictly per-row (the ``live_delta_index``
        contract).  A segment without room for the new rows is enlarged
        to the next power of two (new shapes: whoever must not compile
        under traffic calls :meth:`reserve` ahead and :meth:`compact`
        before the segment overflows, as the engine does).  ONE
        placement: the rows with :func:`_write_segment`'s ``sent``
        (:meth:`plan_update` + :meth:`write_update`, which whoever holds
        the rows on the device calls itself).
        """
        rows = np.asarray(rows, dtype=np.int64).ravel()
        if len(rows) == 0:
            return self._copy_shell(seq)
        update, last = self.plan_update(rows, valid_rows)
        pad = pad_for(len(update.ids))
        vals = np.zeros((pad, int(self.V.shape[1])), dtype=np.float32)
        vals[:len(last)] = self._host_rows(V_rows, len(rows))[last]
        return self.write_update(
            update, *self._put((update.sent(pad), vals)), seq=seq)

    def compact(self, seq=None):
        """Fold the delta segment back into the base arrays, IN PLACE.

        A memcpy-class scatter — the segment's already-quantized rows
        are placed, nothing is re-quantized — yielding arrays bitwise
        equal to a full :func:`build_index` rebuild of the updated
        catalog.  The base arrays are DONATED to the scatter: the same
        buffers come back, no copy of the catalog on the device, and
        the index this is called on, with every index that shares its
        base arrays, is spent (its arrays read "deleted").  Programs run
        in dispatch order, so a batch dispatched before the call reads
        the old rows whole.  Where the catalog has outgrown the base
        arrays they are first enlarged to exactly ``n_items`` rows
        (:meth:`reserve`: a copy, new shapes).  The emptied segment
        keeps its slots.
        """
        if not self.d_rows.size:
            return self._copy_shell(seq)
        src = (self if self.n_items <= self.n_base
               else self.reserve(rows=self.n_items))
        new = src._copy_shell(seq)
        _, dVq, dsv, dV, _ = src._seg
        (new.V, new.Vq, new.sv, new.valid, drows,
         dvalid) = src._fold(src._seg[0])
        new.d_rows = np.empty(0, dtype=np.int64)
        new._seg = (drows, dVq, dsv, dV, dvalid)
        return new

    def _fold(self, drows):
        """The segment's rows at catalog ids ``drows`` into the base
        arrays, which are donated: ``(V, Vq, sv, valid, the emptied slot
        map, the emptied valid bits)``."""
        return _fold_segment_inplace(self.V, self.Vq, self.sv, self.valid,
                                     drows, *self._seg[1:])

    def prewarm(self, max_rows):
        """Run the segment's row write at every padded size up to
        ``max_rows`` rows and the compaction once, on this index's own
        arrays, writing nothing (every slot free): the index to use
        afterwards — the compaction donated the base arrays it was
        given, the same buffers with the same values come back."""
        new = self._copy_shell(None)
        r = int(self.V.shape[1])
        nothing = self.plan_update(())[0]
        for pad in pads_up_to(max_rows):
            new = new.write_update(nothing, *self._put((
                nothing.sent(pad), np.zeros((pad, r), np.float32))))
        new.V, new.Vq, new.sv, new.valid, _, _ = new._fold(
            jnp.full_like(new._seg[0], SLOT_FREE))
        return new

    def _last_id(self):
        """The last catalog id, on the device (answers are clamped to
        it): uploaded once per catalog size."""
        if self._last is None or self._last[0] != self.n_items:
            self._last = (self.n_items,
                          self._put(np.int32(self.n_items - 1)))
        return self._last[1]

    def rows(self, ids):
        """``(f32 rows [n, rank], valid bits [n])`` this index serves for
        catalog ``ids``, read back to the host: the segment's where it
        holds the id, else the base arrays' — what a rescore multiplies.
        For whoever checks a publish against what it meant to publish;
        O(len(ids)) off the device."""
        ids = np.asarray(ids, dtype=np.int64).ravel()
        held, slots = self._held(ids)
        base = np.minimum(ids, self.n_base - 1)
        rows = np.array(jnp.take(self.V, base, axis=0))
        ok = np.array(jnp.take(self.valid, base)) & (ids < self.n_base)
        if held.any():
            rows[held] = np.asarray(
                jnp.take(self._seg[3], slots[held], axis=0))
            ok[held] = np.asarray(jnp.take(self._seg[4], slots[held]))
        return rows, ok & (ids < self.n_items)

    def block_until_ready(self):
        """Fence every device array this index owns (bench timing)."""
        jax.block_until_ready([self.V, self.valid, self.Vq, self.sv,
                               *(self._seg or ())])
        return self

    def nbytes_quantized(self):
        """HBM the shortlist pass reads per batch (vs 4x for f32)."""
        base = int(np.prod(self.Vq.shape)) + 4 * int(self.sv.shape[0])
        r = int(self.V.shape[1])
        return base + self.delta_count * (r + 4)

    def topk(self, U, k, shortlist_k=None):
        """Top-k of ``U @ V.T`` via int8 shortlist + exact f32 rescore.

        Returns ``(scores [n, k], indices [n, k])`` matching
        ``chunked_topk_scores`` to ``SCORE_ULPS`` (see module docstring
        for the contract and its conditions).  ``k`` is capped by the shortlist, the shortlist by
        the catalog.  With a delta segment (used or emptied) the
        shortlist runs over base + segment, without one over the base
        alone; either way the
        selection is :meth:`shortlist_plan`'s (one ``top_k`` on a small
        catalog, two exact stages on a large one: same candidates).
        """
        sk = self.shortlist_k if shortlist_k is None else \
            min(int(shortlist_k), self.n_items)
        if k > sk:
            raise ValueError(
                f"k={k} exceeds shortlist_k={sk}; the shortlist must "
                "contain at least k candidates")
        return self._topk(jnp.asarray(U, dtype=jnp.float32), int(k), sk)

    def _topk(self, U, k, sk):
        return _topk_jit(
            U, self.Vq, self.sv, self.V, self.valid, k=k, shortlist_k=sk,
            delta=self._seg, last_id=self._last_id() if self._seg else None)


def build_index(V, item_valid=None, shortlist_k=64, seq=0):
    """Full-rebuild reference: quantize the ENTIRE catalog from scratch.

    O(catalog) — what every publish cost before the delta segment, and
    the bitwise reference the ``live_delta_index`` contract judges
    :meth:`Int8CandidateIndex.with_updates` / :meth:`compact` against.
    """
    return Int8CandidateIndex(V, item_valid=item_valid,
                              shortlist_k=shortlist_k, seq=seq)


def _shard_merge(s, gids, last_id, *, axis, k):
    """The shards' local top-``k_loc`` lists gathered onto every shard
    (two ``all_gather``s of ``[n, k_loc]``: ``S * k_loc`` values a
    query, never a per-shard candidate LIST in host memory),
    concatenated in shard order and reduced with one stable
    ``lax.top_k``: every shard ends with the same ``[n, k]`` answer."""
    n, k_loc = s.shape
    all_s = jax.lax.all_gather(s, axis)                # [S, n, k_loc]
    all_i = jax.lax.all_gather(gids, axis)
    cat_s = jnp.transpose(all_s, (1, 0, 2)).reshape(n, -1)
    cat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(n, -1)
    if cat_s.shape[1] < k:     # tiny shards: pad so top_k(k) is legal
        pad = k - cat_s.shape[1]
        cat_s = jnp.pad(cat_s, ((0, 0), (0, pad)),
                        constant_values=NEG_INF)
        cat_i = jnp.pad(cat_i, ((0, 0), (0, pad)))
    bs, sel = jax.lax.top_k(cat_s, k)
    bi = jnp.take_along_axis(cat_i, sel, axis=1)
    return bs, jnp.minimum(bi, last_id)


def mesh_exchange_bytes(n_shards, rows, rank, k_loc, wide=0):
    """Bytes one device moves for one batch of the mesh engine's scoring
    program, by ``parallel.comm_audit``'s conventions (a test pins this
    to the traced program's): the ``psum`` that spreads the staged
    ``[rows, rank + 2 + wide]`` int32 batch from the one shard it was
    placed on (``serving.engine._mesh_spread``: see
    :func:`mesh_spread_bytes`; ``wide``: the columns of the requests'
    own lists of excluded ids, where the batch excludes),
    the by-id lookup's ``psum`` of the ``[rows, rank]`` f32 queries, a
    bidirectional-ring all-reduce, ``2 (S-1)/S`` of them, and the
    merge's two ``all_gather``s of the local ``[rows, k_loc]`` f32
    scores and int32 ids, ``(S-1)/S`` of the gathered ``[S, rows,
    k_loc]`` each.  What the users' histories add to a batch that
    excludes is :func:`mesh_history_bytes`, counted beside this."""
    S = int(n_shards)
    return (mesh_spread_bytes(S, rows, rank, wide)
            + 2 * (S - 1) * rows * rank * 4 // S
            + 2 * (S - 1) * rows * k_loc * 4)


def mesh_spread_bytes(n_shards, rows, rank, wide=0):
    """Of :func:`mesh_exchange_bytes`, the staged batch's spread alone:
    what the host's one placement a batch costs the ICI."""
    S = int(n_shards)
    return 2 * (S - 1) * rows * (rank + 2 + wide) * 4 // S


def mesh_history_bytes(n_shards, rows, pad):
    """Bytes one device moves for the histories of one batch that
    excludes, at history pad ``pad``: the ``psum`` of the ``[rows, pad]``
    int32 lists (``serving.engine._mesh_history``: the owning shard's
    ids, zeros from the others), the same all-reduce convention as
    :func:`mesh_exchange_bytes` — all of the users' histories that ever
    crosses a link after their publish."""
    S = int(n_shards)
    return 2 * (S - 1) * rows * pad * 4 // S


@functools.lru_cache(maxsize=64)
def _build_sharded_int8(mesh, k, k_loc, sk_loc, ni_loc, has_delta,
                        lookup=None, pack=None, name="sharded_int8_topk",
                        pad=None):
    """THE sharded scoring program, ``shard_map``'d and jitted under
    ``name``: per shard :func:`shortlist_rescore` over its slice of the
    catalog, :func:`_shard_merge` on every shard.  As
    :meth:`ShardedInt8Index.topk` builds it, it takes a batch of query
    VECTORS, replicated, and returns ``(scores, ids)``.  A serving
    engine's whole request path is the same program with ``lookup(U,
    packed, me=, axis=, pad=)`` in front — ``(the queries, the lists of
    ids they are not to be answered with or None)`` from ITS first two
    arguments, a user table and a staged batch, both sharded by rows —
    and ``pack(scores, ids)`` behind, one replicated result.  ``pad``: a
    batch that excludes; the lookup then takes the users' histories too,
    ``lookup(U, packed, runs, indices, ...)``, both sharded by rows, and
    gives the lists at that history pad — one program a bucket and pad.
    The steps lie in the scopes ``obs.schema.SERVE_MESH_SCOPES`` (the
    lookup opens its own)."""
    P = jax.sharding.PartitionSpec
    head = ((P(),) if lookup is None
            else (P(AXIS),) * (2 if pad is None else 4))

    def program(*args):
        queries, (Vq, sv, V, valid, last_id, *delta) = (
            args[:len(head)], args[len(head):])
        me = jax.lax.axis_index(AXIS)
        U, seen = ((queries[0], None) if lookup is None
                   else lookup(*queries, me=me, axis=AXIS, pad=pad))
        with jax.named_scope(SERVE_MESH_SCOPES[1]):
            s, gids = shortlist_rescore(
                U, Vq, sv, V, valid, k=k_loc, shortlist_k=sk_loc,
                delta=delta, seen=seen, shard=(me, ni_loc))
        with jax.named_scope(SERVE_MESH_SCOPES[2]):
            out = _shard_merge(s, gids, last_id, axis=AXIS, k=k)
            return out if pack is None else pack(*out)

    program.__name__ = name     # the program's name on a device trace
    return pins.built(jax.jit(shard_map(
        program, mesh=mesh,
        in_specs=head + (P(AXIS), P(AXIS), P(AXIS), P(AXIS), P())
        + (P(),) * (5 if has_delta else 0),
        out_specs=(P(), P()) if pack is None else P(), check_vma=False)),
        _build_sharded_int8, mesh, k, k_loc, sk_loc, ni_loc, has_delta,
        lookup, pack, name, pad)


def place_catalog(V, item_valid, mesh, shortlist_k=64):
    """``(V, item_valid, n_items)`` with the host's catalog sharded by
    rows over ``mesh``: ``ceil(n_items / D)`` rows a shard, rounded up to
    whole shortlist blocks (``ops.topk.shortlist_columns``, as the
    one-device index pads its quantized rows: no batch pays for a ragged
    last block), the rows past the catalog zero and invalid.  Each
    shard's rows go up a chunk at a time into a zero table on its own
    device (``core.foldin.place_rows``), so the host never pads a copy
    of the catalog and no device holds more than its shard and one
    chunk."""
    from tpu_als.core.foldin import place_rows
    V = np.asarray(V, dtype=np.float32)
    Ni, D = int(V.shape[0]), int(mesh.devices.size)
    if Ni == 0:
        raise ValueError("cannot index an empty catalog")
    ni_loc = -(-Ni // D)
    cap = D * shortlist_columns(ni_loc, min(int(shortlist_k), ni_loc))
    valid = (np.ones(Ni, dtype=bool) if item_valid is None
             else np.asarray(item_valid, dtype=bool).ravel())
    return (place_rows(V, capacity=cap, mesh=mesh, table="catalog"),
            jax.device_put(np.pad(valid, (0, cap - Ni)),
                           shard_leading(mesh)), Ni)


class ShardedInt8Index(Int8CandidateIndex):
    """:class:`Int8CandidateIndex` with the catalog SHARDED over a mesh.

    Build/publish places each shard's slice device-resident
    (:func:`place_catalog`: ``n_shards * ni_loc`` rows, shard ``s``
    holding catalog ids ``[s * ni_loc, (s + 1) * ni_loc)``, ``ni_loc``
    in whole shortlist blocks, uploaded a chunk at a time); the full
    table is never committed to any single
    device and never copied on the host.  Quantization runs jitted on
    the already-sharded array — per-row, so it stays sharded and each
    device quantizes only its slice.

    The PR 11 live pipeline composes unchanged: :meth:`with_updates`
    inherits the base's slot bookkeeping and row write (O(touched) per
    publish, base arrays shared by reference; the segment's arrays are
    replicated over the mesh), the segment is
    routed to owning shards at SCORE time by ``row // ni_loc``, and
    :meth:`compact` scatters the segment into a COPY of the sharded base
    (:meth:`_fold`; not donated: this class's compaction has never run in a cell;
    capacity always covers
    ``n_items`` here — growth past the shard stride rebuilds, see
    :meth:`with_updates`).

    Contract: same as the base index — scores within ``SCORE_ULPS`` of
    the exact kernel when the true top-k survives the (now per-shard)
    shortlist, which is a strictly WEAKER condition: each shard
    shortlists ``min(sk, ni_loc + d_pad)`` of its own slice, so the
    mesh-wide candidate pool is a superset of the single-device one.
    No TIE-ORDER promise between equal scores — same caveat as the
    single-device int8 index.
    """

    def __init__(self, V, mesh, item_valid=None, shortlist_k=64, seq=0,
                 n_items=None):
        """``V``: the host's catalog, placed by :func:`place_catalog` —
        or, with ``n_items``, that function's result (``item_valid``
        with it), which the index then shares with whoever placed it
        (the engine: its exact fallback scores the same buffers)."""
        if n_items is None:
            V, item_valid, n_items = place_catalog(V, item_valid, mesh,
                                                   shortlist_k)
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        self.ni_loc = int(V.shape[0]) // self.n_shards
        self.V, self.valid = V, item_valid
        self.Vq, self.sv = _quantize_rows(V)
        self.n_items = int(n_items)
        self.shortlist_k = min(int(shortlist_k), self.n_items)
        self.seq = seq
        self._clear_delta()

    def _put(self, arrays):
        return put(arrays, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()))

    def shortlist_plan(self, rows=None):
        # what one shard scores: its rows, and the whole segment behind
        d = self.delta_slots
        return shortlist_plan(self.ni_loc,
                              min(self.shortlist_k, self.ni_loc + d), rows,
                              tail=d)

    @property
    def capacity(self):
        """Catalog ids the sharded base can hold without re-striding."""
        return self.n_base

    def reserve(self, rows=0, slots=0):
        """The segment's slots only: the shards' stride fixes the base
        arrays' capacity (growth past it rebuilds, :meth:`_regrown`)."""
        return super().reserve(slots=slots)

    def with_updates(self, rows, V_rows, valid_rows=None, seq=None):
        rows_a = np.asarray(rows, dtype=np.int64).ravel()
        if rows_a.size and int(rows_a.max()) >= self.capacity:
            return self._regrown(rows_a, V_rows, valid_rows, seq)
        return super().with_updates(rows, V_rows, valid_rows, seq)

    def _regrown(self, rows, V_rows, valid_rows, seq):
        """Growth past the shard stride: every id's owning shard moves,
        so there is no incremental path — rebuild the sharded base at
        the grown size (O(catalog), the rare capacity-crossing publish;
        within capacity :meth:`with_updates` stays O(touched))."""
        V_rows = self._host_rows(V_rows, len(rows))
        rows, last, valid_rows, n_new = self._checked_update(rows,
                                                             valid_rows)
        V_rows = V_rows[last]
        base = self.compact() if self.d_rows.size else self
        V_full = np.zeros((n_new, int(self.V.shape[1])), dtype=np.float32)
        V_full[:self.n_items] = np.asarray(base.V)[:self.n_items]
        valid_full = np.zeros(n_new, dtype=bool)
        valid_full[:self.n_items] = np.asarray(base.valid)[:self.n_items]
        V_full[rows] = V_rows
        valid_full[rows] = valid_rows
        return type(self)(V_full, self.mesh, item_valid=valid_full,
                          shortlist_k=self.shortlist_k,
                          seq=self.seq if seq is None else int(seq))

    def _fold(self, drows):
        """The base class's scatter into a COPY of the sharded base
        arrays (nothing donated: the index stays whole), re-placed
        shard-leading so residency survives the scatter."""
        spec = shard_leading(self.mesh)
        out = _fold_segment_copied(self.V, self.Vq, self.sv, self.valid,
                                   drows, *self._seg[1:])
        return (*(jax.device_put(a, spec) for a in out[:4]), *out[4:])

    def _topk(self, U, k, sk):
        """Scored shard-resident (see class docstring); per-query device
        traffic is ``S * k_loc`` merged candidates, never a per-shard
        list."""
        k_loc, sk_loc = self.shard_widths(k, sk)
        fn = _build_sharded_int8(self.mesh, k, k_loc, sk_loc, self.ni_loc,
                                 bool(self.delta_slots))
        return fn(U, *self.score_args())

    def shard_widths(self, k, shortlist_k=None):
        """``(k_loc, sk_loc)``: how many answers, of how long a
        shortlist, one shard gives for a query."""
        sk = self.shortlist_k if shortlist_k is None else shortlist_k
        sk_loc = min(sk, self.ni_loc + self.delta_slots)
        return min(int(k), sk_loc), sk_loc

    def score_args(self):
        """What a sharded scoring program takes after its queries: the
        four sharded base arrays, the last catalog id (answers are
        clamped to it), and the replicated delta segment if there is
        one."""
        return (self.Vq, self.sv, self.V, self.valid, self._last_id(),
                *(self._seg or ()))


def build_sharded_index(V, mesh, item_valid=None, shortlist_k=64, seq=0):
    """Full sharded rebuild: quantize the whole catalog, device-resident
    per shard.  The mesh-placed counterpart of :func:`build_index`."""
    return ShardedInt8Index(V, mesh, item_valid=item_valid,
                            shortlist_k=shortlist_k, seq=seq)
