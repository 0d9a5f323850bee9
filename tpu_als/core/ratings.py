"""Ratings containers: id remapping + bucketed, padded CSR shards.

This is the TPU-native replacement for the reference stack's blocking
machinery (Spark MLlib's ``RatingBlock``/``InBlock``/``OutBlock``/
``LocalIndexEncoder`` inside ``ml/recommendation/ALS.scala`` — SURVEY.md
§2.B4): where Spark compresses ratings into a ``numUserBlocks ×
numItemBlocks`` grid of CSC-like structures and shuffles factor messages
between them, we lay ratings out as **statically-shaped, degree-bucketed,
padded CSR** resident in HBM, so every ALS half-step is a fixed set of
gather→einsum→cholesky calls with no dynamic shapes (SURVEY.md §7 hard-part 1:
"raggedness on a static-shape machine").

Bucketing: entity rows are grouped by rating count into power-of-two width
buckets (width = next_pow2(count), floored at ``min_width``), each padded to
its width.  Power-law degree skew therefore costs at most 2× padding per row
instead of max-degree× padding for a single rectangle.

All structures here are host-side numpy; the trainer moves them to device
once (the "pulled … into device-sharded CSR blocks once" step of the
north-star in BASELINE.json).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

# the shared rating-sanity bound for poisoned-input quarantine
# (resilience/guardrails): any |rating| above this is treated as data
# corruption, not signal.  Real rating scales are O(1)-O(100); implicit
# confidence counts can be large but a value past 1e6 overwhelms the f32
# normal-equation accumulators (r^2 terms reach 1e12) and is always a
# poisoned record in practice.
RATING_ABS_MAX = 1e6


def invalid_rating_mask(r, max_abs=RATING_ABS_MAX):
    """Boolean mask of ratings that must be quarantined: non-finite or
    magnitude above ``max_abs``.  numpy-only — shared by the streaming
    ingest quarantine (io.stream) and the estimator's input scrub
    (api.estimator), so both sides of the guardrail agree on what
    'poisoned' means."""
    r = np.asarray(r)
    return ~np.isfinite(r) | (np.abs(r) > max_abs)


class Bucket(NamedTuple):
    """One fixed-width padded CSR bucket.  A pytree of arrays.

    rows [nb]      entity index per row; padding rows hold ``oob_row`` (one
                   past the last valid index) so factor scatters can use
                   ``mode='drop'`` instead of a mask.
    cols [nb, w]   opposite-entity indices (0 in padding slots)
    vals [nb, w]   ratings (0 in padding slots)
    mask [nb, w]   1.0 real / 0.0 padding
    """

    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    mask: np.ndarray

    @property
    def width(self):
        # last axis so the property also holds for stacked [..., nb, w]
        # bucket arrays (tpu_als.parallel.data / .comm)
        return self.cols.shape[-1]


@dataclass
class CsrBuckets:
    """All buckets for one side (users or items) of one shard."""

    buckets: list  # list[Bucket], ascending width
    num_rows: int  # entity count this shard (valid scatter targets)
    counts: np.ndarray  # [num_rows] rating count per entity
    nnz: int
    chunk_elems: int  # scan-chunk budget the padding was built for; the
    # trainer must chunk with this same value (rows are pre-padded to it)

    @property
    def padded_nnz(self):
        return sum(b.mask.size for b in self.buckets)

    def device_buckets(self):
        """Buckets as a plain list of NamedTuples (already a pytree)."""
        return list(self.buckets)


@dataclass
class IdMap:
    """Dense-index ↔ original-id mapping, persisted with the model.

    The reference stack requires ids to fit in int range and keeps them as-is
    (SURVEY.md §7 hard-part 5); we additionally densify to 0..N-1 so factor
    matrices are plain arrays.  ``ids[dense] == original``.
    """

    ids: np.ndarray  # [n] original ids, position = dense index

    def __post_init__(self):
        self._lookup = None   # (sorted ids, their dense rows) of ids[:k]
        self._tail = None     # the same of ids[k:], the ids appended since
        self._buf = None      # ``ids`` is a view of it once ids are appended

    def __len__(self):
        return len(self.ids)

    def to_dense(self, original, missing=-1):
        """Map original ids -> dense indices; unseen ids -> ``missing``."""
        original = np.asarray(original)
        if self._lookup is None:
            order = np.argsort(self.ids, kind="stable")
            self._lookup = (self.ids[order], order)
            self._tail = None
        out = np.full(original.shape, missing, dtype=np.int64)
        for tier in (self._lookup, self._tail):
            if tier is None or not len(tier[0]):
                continue
            sorted_ids, order = tier
            pos = np.searchsorted(sorted_ids, original)
            pos = np.clip(pos, 0, len(sorted_ids) - 1)
            hit = sorted_ids[pos] == original
            out = np.where(hit, order[pos], out)
        return out

    def to_original(self, dense):
        return self.ids[np.asarray(dense)]

    def reserve(self, capacity):
        """Room for ``capacity`` ids, so that :meth:`append` copies none of
        those already there."""
        if self._buf is None or len(self._buf) < capacity:
            buf = np.empty(int(capacity), dtype=self.ids.dtype)
            buf[:len(self.ids)] = self.ids
            self._buf, self.ids = buf, buf[:len(self.ids)]

    def append(self, new_ids):
        """Take ids the map does not hold yet (each once) at the next dense
        indices, which are returned.  Costs O(new + appended so far), not
        O(all): the lookup of the ids sorted at first use stays, the
        appended ones are kept sorted beside it and folded into it only
        once they are a sixteenth of it (then the next lookup sorts all)."""
        new_ids = np.asarray(new_ids, dtype=self.ids.dtype)
        n, k = len(self.ids), len(new_ids)
        if n + k > (len(self._buf) if self._buf is not None else 0):
            self.reserve(row_capacity(n + k))
        self._buf[n:n + k] = new_ids
        self.ids = self._buf[:n + k]
        dense = np.arange(n, n + k, dtype=np.int64)
        if self._lookup is not None:
            t_ids, t_dense = self._tail or (new_ids[:0], dense[:0])
            if len(t_ids) + k > max(4096, len(self._lookup[0]) >> 4):
                self._lookup = None
            else:
                by_id = np.argsort(new_ids, kind="stable")
                at = np.searchsorted(t_ids, new_ids[by_id])
                self._tail = (np.insert(t_ids, at, new_ids[by_id]),
                              np.insert(t_dense, at, dense[by_id]))
        return dense


def remap_ids(raw):
    """Densify one id column.  Returns (dense_idx [n], IdMap)."""
    raw = np.asarray(raw)
    uniq, inv = np.unique(raw, return_inverse=True)
    return inv.astype(np.int64), IdMap(ids=uniq)


def _next_pow2(x):
    return 1 << int(max(0, int(np.ceil(np.log2(max(1, x))))))


def row_capacity(n):
    """Rows to allocate for a table of ``n`` live rows that entities are
    appended to between refits: a 64th more (1,024 at least), in whole
    512s.  An array of this many rows keeps its shape, and every program
    compiled for it, until the spare rows are used up."""
    return -(-(int(n) + max(1024, int(n) >> 6)) // 512) * 512


def pads_up_to(n):
    """Every padded size a count of up to ``n`` can take in the live
    path's programs: 8, 64, 512, 4096, ... — few enough to compile and run
    them all before the stream starts."""
    pads = [8]
    while pads[-1] < n:
        pads.append(pads[-1] * 8)
    return tuple(pads)


def pad_for(n):
    """The least of those sizes that holds ``n``."""
    return pads_up_to(n)[-1]


def growth_room(n):
    """How many more a list of ``n`` has room for where lists GROW between
    refits (a resident rating history, the run of it on the device): an
    eighth of it, 8 at least (``n``: a count, or an array of them)."""
    return np.maximum(8, np.asarray(n) >> 3)


def growth_pads(longest):
    """:func:`pads_up_to` for lists that grow from at most ``longest``:
    where the longest with its :func:`growth_room` no longer fits the top
    rung, ONE rung more, the power of two that holds it (8,192 above a
    longest of 4,096 — the ladder's own next rung, 32,768, would have
    every program that rides it pay for four times the padding)."""
    pads = pads_up_to(longest)
    most = int(longest) + int(growth_room(int(longest)))
    return pads if most <= pads[-1] else pads + (_next_pow2(most),)


def rung_for(n, pads):
    """The least of ``pads`` that holds ``n``; past them all the plain
    ladder's (:func:`pad_for`: a size nothing was warmed for)."""
    return next((p for p in pads if p >= n), None) or pad_for(n)


# what the live path warms by default: up to 512 touched entities a
# micro-batch (the live updater's max_batch is 256), of up to 512 ratings
LIVE_PADS = pads_up_to(512)


def entity_widths(counts, min_width, growth=2.0):
    """Bucket width per entity, floored at ``min_width``.  The single
    source of truth for bucket assignment — the numpy and native blocking
    paths both call this.

    growth=2.0 (default): next power of two — worst-case 2× padding.
    growth=1.5: adds the 0.75·2^k rungs that are multiples of 8
    (…, 24, 48, 96, 192, …), cutting worst-case padding to ~1.5× at the
    cost of ~1.4× more bucket specializations.  The 8-multiple restriction
    keeps every width a TPU sublane multiple (the fused kernel and the
    sharded stackers rely on it).
    """
    counts = np.maximum(np.asarray(counts, dtype=np.int64), 1)
    w = np.maximum(
        min_width, 1 << np.ceil(np.log2(counts)).astype(np.int64)
    )
    if growth < 2.0:
        w34 = (3 * w) // 4
        ok = (w34 >= counts) & (w34 >= min_width) & (w34 % 8 == 0)
        w = np.where(ok, w34, w)
    return w


def scan_chunk(nb, width, chunk_elems):
    """Builder-side rows-per-scan-step for a bucket of ``nb`` rows of
    ``width``.  Always a power of two, so the trainer can halve it freely
    (any smaller power of two still divides the padded row count) when the
    rank makes the per-row normal-equation tensor, not the gathered factors,
    the dominant intermediate.  Builders pad row counts up to a multiple.

    The chunk is additionally capped at ~``nb``/16 (floored at 64 rows):
    pad-to-chunk costs up to ``chunk - 1`` fully-computed phantom rows, so
    a chunk near ``nb`` (the old single-chunk regime) could double a
    bucket's work at small scale, while ≥16 scan steps keep the padding
    under ~6-12% for the cost of amortized extra launches.  The trainer's
    re-derivation (:func:`trainer_chunk`) provably lands on the same chunk
    for the padded count — and its gcd fallback covers any drift.
    """
    cap = max(1, chunk_elems // width)
    cap = 1 << (cap.bit_length() - 1)  # floor to power of two
    full = 1 << max(0, nb - 1).bit_length()  # ceil to power of two
    tgt = max(64, 1 << max(0, -(-nb // 16) - 1).bit_length())
    return max(1, min(cap, full, tgt))


def padded_bucket_rows(nb, width, chunk_elems):
    """Bucket row count padded to its scan chunk — THE pairing every
    builder must use identically (numpy/native blocking, the sharded
    stacker, and the multi-host layout agreement all call this; a drifted
    copy would make hosts disagree on global bucket shapes)."""
    chunk = scan_chunk(nb, width, chunk_elems)
    return -(-nb // chunk) * chunk


def trainer_chunk(nb_padded, width, rank, chunk_elems, mem_elems=1 << 28,
                  fused_gather=False):
    """Trainer-side chunk: the builder chunk, halved until the largest
    per-chunk intermediate — max(Vg [chunk,w,r], A [chunk,r,r]) — fits in
    ``mem_elems`` elements (default 2^28 f32 elems = 1 GiB).

    ``fused_gather=True``: the DMA-gather NE kernel
    (tpu_als.ops.pallas_gather_ne) never materializes Vg in HBM — only
    the A tensor bounds the chunk, so wide buckets keep the builder
    chunk instead of halving it ``width/rank``-fold.

    The gcd fallback only defends against buckets built with a different
    ``chunk_elems`` (degrades throughput, never correctness).
    """
    c = scan_chunk(nb_padded, width, chunk_elems)
    big = rank if fused_gather else max(width, rank)
    while c > 1 and c * rank * big > mem_elems:
        c //= 2
    if nb_padded % c:
        c = math.gcd(nb_padded, c)
    return c


def build_csr_buckets(
    row_idx,
    col_idx,
    vals,
    num_rows,
    min_width=8,
    chunk_elems=1 << 19,
    dtype=np.float32,
    native=None,
    width_growth=2.0,
):
    """Build degree-bucketed padded CSR from COO triples.

    Duplicate (row, col) entries are kept as-is (they contribute twice, same
    as duplicate ratings fed to the reference stack's blocking).

    Rows per bucket are padded to a multiple of the bucket's scan chunk
    (:func:`scan_chunk` — a power of two bounded by ``chunk_elems // width``
    and by the bucket's row count) so the trainer can reshape to
    [nchunks, chunk, w] without tracing-time pads, halving the chunk if the
    rank demands it; padding rows carry ``rows == num_rows`` (out-of-bounds
    ⇒ scatter-dropped).

    ``native``: True forces the threaded C++ bucketizer
    (tpu_als.io.fastbucket — bit-identical output), False forces numpy,
    None (default) uses C++ when the library builds and f32 ratings are
    requested.
    """
    if native or native is None:
        from tpu_als.io import fastbucket

        ok = dtype == np.float32 and fastbucket.available()
        if native and not ok:
            raise RuntimeError(
                "native bucketizer requires float32 vals and a working g++")
        if ok:
            return _build_csr_buckets_native(
                row_idx, col_idx, vals, num_rows, min_width, chunk_elems,
                width_growth)
    row_idx = np.asarray(row_idx, dtype=np.int64)
    col_idx = np.asarray(col_idx, dtype=np.int64)
    vals = np.asarray(vals, dtype=dtype)
    nnz = len(row_idx)
    counts = np.bincount(row_idx, minlength=num_rows).astype(np.int64)

    order = np.argsort(row_idx, kind="stable")
    s_rows = row_idx[order]
    s_cols = col_idx[order]
    s_vals = vals[order]

    uniq, starts, ucounts = np.unique(s_rows, return_index=True, return_counts=True)
    # per-entry: rank of its row among unique rows, and offset within the row
    entry_rank = np.repeat(np.arange(len(uniq)), ucounts)
    entry_off = np.arange(nnz) - starts[entry_rank]

    widths = entity_widths(ucounts, min_width, width_growth)
    buckets = []
    for w in sorted(set(widths.tolist())):
        sel_rows = np.flatnonzero(widths == w)  # indices into uniq
        nb = len(sel_rows)
        nb_pad = padded_bucket_rows(nb, w, chunk_elems)
        rows = np.full(nb_pad, num_rows, dtype=np.int32)
        rows[:nb] = uniq[sel_rows]
        cols = np.zeros((nb_pad, w), dtype=np.int32)
        v = np.zeros((nb_pad, w), dtype=dtype)
        m = np.zeros((nb_pad, w), dtype=dtype)
        # local row position within this bucket for each selected unique row
        local = np.full(len(uniq), -1, dtype=np.int64)
        local[sel_rows] = np.arange(nb)
        emask = local[entry_rank] >= 0
        er = local[entry_rank[emask]]
        eo = entry_off[emask]
        cols[er, eo] = s_cols[emask]
        v[er, eo] = s_vals[emask]
        m[er, eo] = 1.0
        buckets.append(Bucket(rows=rows, cols=cols, vals=v, mask=m))

    return CsrBuckets(
        buckets=buckets,
        num_rows=num_rows,
        counts=counts,
        nnz=nnz,
        chunk_elems=chunk_elems,
    )


def _build_csr_buckets_native(row_idx, col_idx, vals, num_rows, min_width,
                              chunk_elems, width_growth=2.0):
    """Threaded C++ blocking path — same output as the numpy path above."""
    from tpu_als.io import fastbucket

    row_idx = np.asarray(row_idx, dtype=np.int64)
    counts = fastbucket.counts(row_idx, num_rows)
    w_all = entity_widths(counts, min_width, width_growth)
    rated = counts > 0
    layout = []
    bucket_widths = sorted(set(w_all[rated].tolist()))
    for w in bucket_widths:
        nb = int((rated & (w_all == w)).sum())
        layout.append((int(w), nb, padded_bucket_rows(nb, w, chunk_elems)))
    # per-entity bucket index (exact width match; -1 for unrated entities)
    ebucket = np.searchsorted(
        np.asarray(bucket_widths, dtype=np.int64), w_all
    ).astype(np.int32)
    ebucket[~rated] = -1
    raw = fastbucket.fill_buckets(
        row_idx, col_idx, vals, num_rows, counts, ebucket, layout)
    buckets = [Bucket(rows=r, cols=c, vals=v, mask=m) for r, c, v, m in raw]
    return CsrBuckets(
        buckets=buckets,
        num_rows=num_rows,
        counts=counts,
        nnz=len(row_idx),
        chunk_elems=chunk_elems,
    )
