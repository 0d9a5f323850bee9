"""Per-stage bytes/FLOPs roofline for one ALS iteration (ISSUE 2).

The question this module answers quantitatively: *how close is the
measured headline (1.184 s/iter on ML-25M rank-128 implicit, one v5e
core) to the memory-bound floor of the algorithm?*  Every prior perf
claim ended at "fastest variant tried"; the matfree-CG episode
(BASELINE.md round-5 resolution) showed why that is not enough — a
designed 10× lever lost on chip because nobody had priced its extra
passes over the gathered-factor HBM stream.

Model
-----
One full iteration = two half-steps (items solved against gathered user
factors, then vice versa).  Per half-step, with ``P`` padded ratings on
the solved side (``padding_waste × nnz``), ``n`` solved rows, ``N``
opposite rows, rank ``r`` and compute-dtype width ``db``:

- **gather_stream**: every padded entry reads one opposite factor row
  and writes it into the gathered layout (``2·P·r·db``), plus the
  cols/vals/mask rating stream (``12·P``).  This is THE co-dominant
  cost at rank 128 and the stream matfree CG fatally re-read.
- **normal_eq**: the einsum re-reads the gathered rows (``P·r·db``) and
  writes the ``[n, r, r]`` normal-equation tensor once (``n·r²·4``).
  FLOPs ``2·P·r² + 2·P·r`` (A then b).
- **solve**: reads A + b, writes x (``n·(r²+2·r)·4``).  FLOPs
  ``n·(2r³/3 + 4r²)`` — tiny on the MXU, but the batched Cholesky is a
  serial recurrence that runs on the VPU; the measured headline spends
  ~80% of the iteration here (BASELINE.md round-2 profile), far above
  this stage's floor.  The roofline makes that gap explicit instead of
  hiding it in a fudge factor.
- **scatter**: writes the solved rows back (``n·r·4``).
- **yty** (implicit feedback only): reads each factor table once and
  prices ``2·N·r²`` FLOPs per half-step.
- **collective** (sharded only): ICI bytes from
  :func:`tpu_als.parallel.trainer.comm_bytes_per_iter` — the SAME
  closed form the comm-audit tests pin to the traced jaxpr, so the
  roofline's comm stage is transitively traced-checked
  (tests/test_roofline.py cross-checks this equality directly).

Floor = Σ over stages of ``max(hbm_bytes/BW, flops/peak)`` (each stage
at its bandwidth: HBM for on-chip stages, ICI for the collective).  A
pure-HBM floor (Σ bytes / HBM BW) is reported alongside — that is the
"how fast could this possibly go without changing the algorithm"
number docs/roofline.md quotes next to the measured 1.184.
"""

from __future__ import annotations

from dataclasses import dataclass

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# THE one table: the model below, bench.py's advisory MFU and the
# autotuner's floor all read it.  A device that is not here is an error
# (:func:`device_peaks`), not a v5e.  f32 is taken as half the bf16 MXU
# rate; ICI 1,600 Gbit/s = 200 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "name": "v5e", "bf16_flops": 197e12, "f32_flops": 98.5e12,
        "hbm_gbps": 819.0, "ici_gbps": 200.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip",
    },
}
# the device the closed-form model describes when none is named
MODELED_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind):
    """The peaks row of ``device_kind``; raises for a device with no
    published row instead of rating it against another chip's peak."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add a row with its source to "
            "tpu_als.perf.roofline.DEVICE_PEAKS") from None


# THE headline config (BASELINE.md row 2): ML-25M, rank 128, implicit
# alpha=40, f32, single v5e core; padding_waste and the measured
# s/iter are the 2026-07-31 chip run's (an earlier installation, JAX
# 0.4.37; PERF.md holds what has been measured since).
HEADLINE = dict(n_users=162_541, n_items=59_047, nnz=25_000_095,
                rank=128, dtype="float32", implicit=True,
                padding_waste=1.514, devices=1)
HEADLINE_MEASURED_S_PER_ITER = 1.184


def fused_ne_kernel_bytes(P, n, r, db):
    """HBM bytes the gather-fused NE kernel
    (tpu_als.ops.pallas_gather_ne) moves for one half-step over ``P``
    padded entries / ``n`` solved rows: each entry's factor row read ONCE
    straight into VMEM (never written back as a gathered intermediate),
    the cols (int32) + aw/bw weight streams, and the A/b outputs.

    THE single source of truth shared by the roofline's fused stage
    below, the kernel's ``pl.CostEstimate``, and the traced-jaxpr audit
    (tests/test_ne_audit.py extracts the estimate from the trace and pins
    it to this formula — the test_comm_audit.py pattern).
    """
    return int(P * r * db + P * (4 + 2 * db) + n * r * r * 4 + n * r * 4)


def fused_solve_kernel_bytes(P, n, r, db):
    """HBM bytes the whole-iteration fused kernel
    (tpu_als.ops.pallas_gather_ne.gather_solve) moves for one half-step:
    each entry's factor row read ONCE straight into VMEM, the cols (int32)
    + aw/bw/cw weight streams, and the solved ``x [n, r]`` output — the
    ``[n, r, r]`` normal-equation tensor never touches HBM (neither
    written NOR read back by a solver), which is this model's whole
    difference from :func:`fused_ne_kernel_bytes` + the solve stage.

    THE single source of truth shared by the roofline's fused-solve stage,
    the kernel's ``pl.CostEstimate``, and the fused_solve_audit contract
    (analysis/contracts.py) that pins the traced estimate to this formula.
    """
    return int(P * r * db + P * (4 + 3 * db) + n * r * 4)


def ring_remote_bytes(n_row_tiles, n_shards, per, r, db):
    """In-kernel remote-DMA payload of ONE ``gather_solve_ring`` call
    (tpu_als.ops.pallas_gather_ne): every row tile runs its own full ring
    pass, and each pass forwards the held ``[per, r]`` factor shard
    ``S - 1`` times — there is NO homecoming rotation (the XLA ring's
    S-th permute exists only to restore the shard for the next tile; the
    kernel re-streams from its immutable HBM copy instead, which is why
    the in-kernel ring moves (S-1)/S of the XLA ring's bytes per pass).

    THE single source of truth shared by the kernel's ``pl.CostEstimate``
    ring term, ``trainer.comm_bytes_per_iter('gather_fused_ring', …)``,
    and the extended ``comm_audit`` contract (analysis/contracts.py) that
    pins the traced remote-DMA payload × fire count to this formula.
    """
    return int(n_row_tiles * max(0, n_shards - 1) * per * r * db)


def fused_ring_kernel_bytes(P, n, r, db, ring_bytes):
    """HBM bytes of the fused-comm ring kernel
    (tpu_als.ops.pallas_gather_ne.gather_solve_ring): the whole-iteration
    fused model (:func:`fused_solve_kernel_bytes` — rows read once, weight
    streams, x out) plus the inter-chip ring payload
    (:func:`ring_remote_bytes`, counted once per transfer: the send's HBM
    read on this chip; the matching write lands on the neighbor)."""
    return fused_solve_kernel_bytes(P, n, r, db) + int(ring_bytes)


def einsum_ne_build_bytes(P, n, r, db, restream=1.0):
    """Modeled NE-build bytes of the UNFUSED path (gather_stream +
    normal_eq stages below, summed): the gather reads one factor row per
    padded entry and writes the [n, w, r] intermediate, the cols/vals/
    mask stream rides along, and the einsum re-reads the gathered rows
    and writes A.  The fused-vs-einsum byte-reduction claim
    (docs/roofline.md; pinned ≥40% at the headline config in
    tests/test_ne_audit.py) is this minus :func:`fused_ne_kernel_bytes`.
    """
    return int(restream * (2.0 * P * r * db) + 12.0 * P
               + P * r * db + n * r * r * 4.0)


def modeled_padding_waste(counts, min_width=8, chunk_elems=1 << 19,
                          growth=2.0):
    """padded_nnz / nnz for a degree distribution, derived from the SAME
    width-assignment + row-padding helpers the builder uses
    (tpu_als.core.ratings.entity_widths / padded_bucket_rows) — no bucket
    arrays are built, so this prices ML-25M-scale layouts instantly.
    Cross-checked against an actual ``build_csr_buckets`` run in
    tests/test_roofline.py (replaces the hardcoded 1.514 caller constant;
    the constant survives as an explicit override).
    """
    import numpy as np

    from tpu_als.core.ratings import entity_widths, padded_bucket_rows

    counts = np.asarray(counts, dtype=np.int64)
    nnz = int(counts.sum())
    rated = counts[counts > 0]
    if not nnz or not len(rated):
        return 1.0
    w = entity_widths(rated, min_width, growth)
    padded = 0
    for wv in sorted(set(w.tolist())):
        nb = int((w == wv).sum())
        padded += padded_bucket_rows(nb, int(wv), chunk_elems) * int(wv)
    return padded / nnz


@dataclass
class Stage:
    name: str
    bytes: float          # bytes moved through `bw` per iteration
    flops: float          # MXU-priced FLOPs per iteration
    bw: float             # bytes/sec of the stage's channel
    peak: float           # FLOP/s peak for the stage's dtype
    note: str = ""

    @property
    def byte_seconds(self):
        return self.bytes / self.bw if self.bw else 0.0

    @property
    def flop_seconds(self):
        return self.flops / self.peak if self.peak else 0.0

    @property
    def floor_seconds(self):
        return max(self.byte_seconds, self.flop_seconds)

    @property
    def bound(self):
        if not self.bytes and not self.flops:
            return "-"
        return "bytes" if self.byte_seconds >= self.flop_seconds \
            else "flops"


def _dtype_bytes(dtype):
    return {"float32": 4, "bfloat16": 2, "float16": 2}[str(dtype)]


def roofline(n_users, n_items, nnz, rank, *, dtype="float32",
             implicit=True, padding_waste=None, devices=1,
             strategy=None, tiles_user=1, tiles_item=1,
             comm_bytes=None, user_part=None, item_part=None,
             user_container=None, item_container=None,
             user_counts=None, item_counts=None,
             min_width=8, chunk_elems=1 << 19, width_growth=2.0,
             ne_path="einsum",
             device_kind=MODELED_DEVICE_KIND, hbm_gbps=None, ici_gbps=None,
             measured_s_per_iter=None):
    """Analytical per-stage roofline for one full ALS iteration.

    Parameterized by problem shape, ``dtype`` (compute dtype of the
    gather/NE stream), ``strategy`` + chunking (``tiles_user`` /
    ``tiles_item`` row-tile counts — the ring and chunked-gather
    strategies re-stream the opposite factors once per tile).

    ``ne_path``: 'einsum' prices the unfused build (gather_stream +
    normal_eq stages); 'gather_fused' prices the DMA-gather kernel
    (tpu_als.ops.pallas_gather_ne) — one fused stage reading each factor
    row ONCE and writing A/b, the :func:`fused_ne_kernel_bytes` model;
    'gather_fused_solve' prices the whole-iteration fusion — gather, Gram,
    ridge/YtY tail AND the Cholesky solve in one kernel writing only x,
    the :func:`fused_solve_kernel_bytes` model (the standalone solve
    stage folds into it).

    ``padding_waste``: explicit override; when None it is DERIVED from
    the per-entity degree arrays ``user_counts``/``item_counts`` via
    :func:`modeled_padding_waste` (the builder's own width assignment at
    ``min_width``/``chunk_elems``/``width_growth``), falling back to 1.0
    when no counts are given.

    Collective bytes: pass ``comm_bytes`` directly, or the built
    partitions/containers (``user_part``/``item_part`` +
    ``user_container``/``item_container``) to price them with the exact
    :func:`~tpu_als.parallel.trainer.comm_bytes_per_iter` closed form
    — the one the comm-audit tests pin to the traced jaxpr.

    Returns a plain dict (JSON-ready): per-stage accounting, the
    byte-only HBM floor, the per-stage roofline floor, and (when
    ``measured_s_per_iter`` is given) the measured-over-floor ratios.
    """
    D = max(1, int(devices))
    r = int(rank)
    db = _dtype_bytes(dtype)
    peaks = device_peaks(device_kind)
    peak = peaks["f32_flops"] if db == 4 else peaks["bf16_flops"]
    hbm_gbps = peaks["hbm_gbps"] if hbm_gbps is None else hbm_gbps
    ici_gbps = peaks["ici_gbps"] if ici_gbps is None else ici_gbps
    hbm = hbm_gbps * 1e9
    ici = ici_gbps * 1e9
    if ne_path not in ("einsum", "gather_fused", "gather_fused_solve"):
        raise ValueError(f"unknown ne_path {ne_path!r} (expected "
                         "'einsum', 'gather_fused' or "
                         "'gather_fused_solve')")
    padding_waste_source = "explicit"
    if padding_waste is None:
        if user_counts is not None or item_counts is not None:
            sides = [c for c in (user_counts, item_counts) if c is not None]
            padding_waste = sum(
                modeled_padding_waste(c, min_width, chunk_elems,
                                      width_growth)
                for c in sides) / len(sides)
            padding_waste_source = "derived"
        else:
            padding_waste = 1.0
            padding_waste_source = "default"

    # per-device padded entries over BOTH half-steps; solved rows and
    # opposite-table rows per device
    P = 2.0 * float(padding_waste) * float(nnz) / D
    n = float(n_users + n_items) / D
    # the ring / chunked strategies re-stream the opposite factors once
    # per row tile; plain all_gather and a single-device run stream once
    restream = 1.0
    if strategy in ("ring", "ring_overlap", "all_gather_chunked"):
        restream = (float(tiles_user) + float(tiles_item)) / 2.0

    if ne_path == "gather_fused_solve":
        # the solve is fused INTO this stage (its flops ride along, its
        # A/b read-back bytes vanish) — no standalone solve stage below
        ne_stages = [Stage(
            "gather_fused_solve",
            bytes=(fused_solve_kernel_bytes(P, n, r, db)
                   + (restream - 1.0) * P * r * db),
            flops=(2.0 * P * r * r + 2.0 * P * r
                   + n * (2.0 * r ** 3 / 3.0 + 4.0 * r * r)),
            bw=hbm, peak=peak,
            note="whole-iteration fused kernel: factor rows read ONCE "
                 "into VMEM, Gram + ridge/YtY tail + Cholesky solve in "
                 "VMEM, only x written — A never in HBM "
                 "(ops/pallas_gather_ne.gather_solve)")]
    elif ne_path == "gather_fused":
        ne_stages = [Stage(
            "gather_fused_ne",
            bytes=(fused_ne_kernel_bytes(P, n, r, db)
                   + (restream - 1.0) * P * r * db),
            flops=2.0 * P * r * r + 2.0 * P * r,
            bw=hbm, peak=peak,
            note="DMA-gather kernel: factor rows read ONCE into VMEM, "
                 "A/b written — Vg never in HBM "
                 "(ops/pallas_gather_ne)")]
    else:
        ne_stages = [
            Stage("gather_stream",
                  bytes=restream * (2.0 * P * r * db) + 12.0 * P,
                  flops=0.0, bw=hbm, peak=peak,
                  note="opposite factor rows read+written per padded "
                       "entry + cols/vals/mask stream"),
            Stage("normal_eq",
                  bytes=P * r * db + n * r * r * 4.0,
                  flops=2.0 * P * r * r + 2.0 * P * r,
                  bw=hbm, peak=peak,
                  note="einsum re-reads gathered rows, writes [n,r,r] A"),
        ]
    stages = list(ne_stages)
    if ne_path != "gather_fused_solve":
        stages.append(Stage(
            "solve",
            bytes=n * (r * r + 2.0 * r) * 4.0,
            flops=n * (2.0 * r ** 3 / 3.0 + 4.0 * r * r),
            bw=hbm, peak=peak,
            note="reads A+b, writes x; VPU-serial Cholesky in "
                 "practice — see docs/roofline.md"))
    stages.append(Stage(
        "scatter",
        bytes=n * r * 4.0, flops=0.0, bw=hbm, peak=peak,
        note="solved rows written back"))
    if implicit:
        stages.append(Stage(
            "yty",
            bytes=2.0 * (float(n_users + n_items) / D) * r * 4.0,
            flops=2.0 * 2.0 * (float(n_users + n_items) / D) * r * r,
            bw=hbm, peak=peak,
            note="YtY precompute per half-step"))
    if comm_bytes is None and strategy is not None and D > 1:
        if user_part is not None and item_part is not None:
            from tpu_als.parallel.trainer import comm_bytes_per_iter

            comm_bytes = comm_bytes_per_iter(
                strategy, user_part, item_part, r,
                user_container=user_container,
                item_container=item_container, implicit=implicit)
        else:
            # closed-form estimate with balanced rows_per_shard =
            # ceil(n/D) — same formulas as trainer.comm_bytes_per_iter
            # (which is exact once containers exist; all_to_all needs
            # the built request budgets, so no estimate there)
            per_u = -(-int(n_users) // D)
            per_i = -(-int(n_items) // D)
            fb = 4 * r
            if strategy == "all_gather":
                comm_bytes = (D - 1) * (per_i + per_u) * fb
            elif strategy in ("ring", "ring_overlap"):
                comm_bytes = D * fb * (per_i * int(tiles_user)
                                       + per_u * int(tiles_item))
            elif strategy == "all_gather_chunked":
                comm_bytes = (D - 1) * fb * (per_i * int(tiles_user)
                                             + per_u * int(tiles_item))
            if comm_bytes is not None and implicit:
                comm_bytes += 2 * 2 * (D - 1) * r * r * 4 // D
    if comm_bytes:
        stages.append(Stage(
            "collective", bytes=float(comm_bytes), flops=0.0,
            bw=ici, peak=peak,
            note=f"{strategy} ICI traffic "
                 "(= trainer.comm_bytes_per_iter, traced-checked)"))

    hbm_bytes = sum(s.bytes for s in stages if s.bw == hbm)
    total_flops = sum(s.flops for s in stages)
    hbm_floor = hbm_bytes / hbm
    floor = sum(s.floor_seconds for s in stages)
    report = {
        "config": {
            "n_users": int(n_users), "n_items": int(n_items),
            "nnz": int(nnz), "rank": r, "dtype": str(dtype),
            "implicit": bool(implicit),
            "padding_waste": float(padding_waste),
            "padding_waste_source": padding_waste_source,
            "width_growth": float(width_growth),
            "ne_path": ne_path, "devices": D,
            "strategy": strategy,
            "tiles_user": int(tiles_user), "tiles_item": int(tiles_item),
            "hbm_gbps": float(hbm_gbps), "ici_gbps": float(ici_gbps),
            "device_kind": device_kind, "device_name": peaks["name"],
        },
        "stages": [
            {"name": s.name, "bytes": int(s.bytes), "flops": int(s.flops),
             "byte_seconds": s.byte_seconds,
             "flop_seconds": s.flop_seconds,
             "floor_seconds": s.floor_seconds,
             "bound": s.bound, "note": s.note}
            for s in stages
        ],
        "hbm_bytes_per_iter": int(hbm_bytes),
        "comm_bytes_per_iter": int(comm_bytes or 0),
        "flops_per_iter": int(total_flops),
        "hbm_floor_s_per_iter": hbm_floor,
        "roofline_floor_s_per_iter": floor,
    }
    if measured_s_per_iter:
        report["measured_s_per_iter"] = float(measured_s_per_iter)
        report["measured_over_hbm_floor"] = (
            float(measured_s_per_iter) / hbm_floor if hbm_floor else None)
        report["measured_over_roofline_floor"] = (
            float(measured_s_per_iter) / floor if floor else None)
    return report


def headline_roofline(**overrides):
    """The roofline of BASELINE.md row 2 with its measured point.

    ``headline_roofline(ne_path='gather_fused')`` prices the same config
    on the DMA-gather kernel — the revised floor docs/roofline.md quotes.
    """
    return roofline(**{**HEADLINE, **overrides},
                    measured_s_per_iter=HEADLINE_MEASURED_S_PER_ITER)


def render(report):
    """Human-readable table for ``tpu_als observe roofline``."""
    c = report["config"]
    lines = [
        ("ALS iteration roofline — "
         f"{c['n_users']}x{c['n_items']} nnz={c['nnz']} rank={c['rank']} "
         f"{c['dtype']} {'implicit' if c['implicit'] else 'explicit'} "
         f"waste={c['padding_waste']:.3f}"
         f" ({c.get('padding_waste_source', 'explicit')})"
         f" ne={c.get('ne_path', 'einsum')} D={c['devices']}"
         + (f" strategy={c['strategy']}" if c["strategy"] else "")),
        (f"(HBM {c['hbm_gbps']} GB/s, ICI {c['ici_gbps']} GB/s, "
         f"{c.get('device_name', 'v5e')})"),
        "",
        f"{'stage':<16}{'MB moved':>12}{'GFLOP':>10}"
        f"{'bytes ms':>10}{'flops ms':>10}{'bound':>7}",
    ]
    for s in report["stages"]:
        lines.append(
            f"{s['name']:<16}{s['bytes'] / 1e6:>12.1f}"
            f"{s['flops'] / 1e9:>10.1f}"
            f"{s['byte_seconds'] * 1e3:>10.2f}"
            f"{s['flop_seconds'] * 1e3:>10.2f}{s['bound']:>7}")
    lines += [
        "",
        f"HBM floor (all bytes / BW):    "
        f"{report['hbm_floor_s_per_iter']:.3f} s/iter",
        f"roofline floor (per-stage max): "
        f"{report['roofline_floor_s_per_iter']:.3f} s/iter",
    ]
    if "measured_s_per_iter" in report:
        lines += [
            f"measured:                       "
            f"{report['measured_s_per_iter']:.3f} s/iter  "
            f"({report['measured_over_hbm_floor']:.1f}x HBM floor, "
            f"{report['measured_over_roofline_floor']:.1f}x roofline)",
            "gap mechanism: the batched Cholesky runs on the VPU's "
            "serial recurrence, ~80% of the measured iteration "
            "(docs/roofline.md)",
        ]
    return "\n".join(lines)
