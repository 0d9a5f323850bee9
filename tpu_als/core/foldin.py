"""Jitted incremental fold-in: update touched user factors without a refit.

The reference stack has no streaming path — Spark MLlib requires a full refit
when new ratings arrive (SURVEY.md §3.5).  The north-star (BASELINE.json
configs[3]) replaces that with the standard ALS fold-in: for each touched
user u with rating rows against the *fixed* item factors V,

    u* = (VᵤᵀCᵤVᵤ + λ·n·I)⁻¹ VᵤᵀCᵤp(u)

— exactly one batched half-step restricted to the touched rows, served as a
single jitted kernel.  Shapes are padded to power-of-two (rows and width) by
the stream driver so repeated micro-batches hit the jit cache.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from tpu_als.core.ratings import pad_for
from tpu_als.obs.phases import count_placed
from tpu_als.obs.schema import LIVE_FOLDIN_YTY_SCOPE
from tpu_als.ops.solve import (
    DEFAULT_JITTER,
    SOLVE_PATH_NAMES,
    auto_solve_backend,
    compute_yty,
    normal_eq_explicit,
    normal_eq_implicit,
    solve_nnls,
    solve_spd,
)


# The lane kernels solve 128 systems side by side, one per lane, and are
# admitted by a probe that compiles them (minutes at rank 256): below four
# lane groups there is nothing to fill them with and nothing to win back
# the probe, so a micro-batch's handful of systems takes XLA's Cholesky
FEW_SYSTEMS = 512


def solve_path(rank, rows, nonnegative=False):
    """``(backend, path, why)`` of the solve for ``rows`` systems of this
    rank, from what the caller can see before it traces: up to
    ``FEW_SYSTEMS`` rows the XLA Cholesky, above them the probe walk the
    training step takes (:func:`tpu_als.ops.solve.auto_solve_backend`,
    eager: a probe cannot run inside a trace).  ``path`` is the name
    ``core.als.resolve_solve_path`` gives the same choice."""
    if nonnegative:
        return "xla", "einsum+nnls", "nonnegative"
    if rows <= FEW_SYSTEMS:
        backend, why = "xla", f"{rows} systems, at most {FEW_SYSTEMS}"
    else:
        backend, why = auto_solve_backend(rank), "probe walk"
    return backend, SOLVE_PATH_NAMES[backend], why


# rows a :func:`place_rows` upload carries: 32 MiB at rank 256
PLACE_CHUNK = 1 << 15

# host→device placement calls of the live write path, by the thread that
# made them (:func:`put`, :func:`placements`)
_placed = threading.local()


def put(arrays, device=None):
    """``jax.device_put``, counted for the calling thread: every placement
    the live write path makes beside a program's call goes through here
    (a placement is ≈ 0.35 ms of Python around 0.08 ms of transfer on
    the chip's host: PERF.md section 5, PR 41), so that
    ``live.host_placements`` counts them where they are made."""
    _placed.n = placements() + 1
    return jax.device_put(arrays, device)


def placements():
    """Placements the calling thread has made through :func:`put`."""
    return getattr(_placed, "n", 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(table, rows, at):
    """``rows`` written into ``table`` from row ``at`` on, in place (the
    table is donated; ``at`` is traced: one program for every offset)."""
    return jax.lax.dynamic_update_slice(table, rows, (at, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(table, rows, vals):
    """``vals`` written at ``rows`` of ``table``, in place (donated);
    rows outside it are dropped."""
    with jax.named_scope("live.foldin.scatter"):
        return table.at[rows].set(vals, mode="drop")


# rows one step of :func:`whole_yty` multiplies (8 MiB at rank 256).  On a
# v5e the sum inside ONE product loses with its length, all one way: over
# 1.73 M x 256 rows of N(0, 1) a step of 65,536 rows (and the one einsum
# over the table) read 1.1e-5 to 1.9e-5 under the float64 diagonal, every
# entry alike, a step of 8,192 rows 2e-7 with no lean, steps of 1,024 and
# fewer 6e-7 to 1.2e-6 again (more sums of sums) — 8.2 to 17 ms each
# (PERF.md section 6, PR 57)
YTY_CHUNK = 1 << 13


@jax.jit
def whole_yty(table):
    """``table^T table`` of a WHOLE factor table in true float32
    (``Precision.HIGHEST``: the default is one bfloat16 pass of float32
    operands on the chip), ``YTY_CHUNK`` rows a step: short sums, and no
    table-sized operand split beside the table.  O(table): what a
    live server pays where it places a table whole, never a batch
    (:func:`_scatter_rows_yty` moves the result from then on)."""
    n, r = table.shape
    step = min(n, YTY_CHUNK)
    with jax.named_scope(LIVE_FOLDIN_YTY_SCOPE), \
            jax.default_matmul_precision("highest"):
        G = jax.lax.fori_loop(
            0, n // step,
            lambda i, G: G + compute_yty(jax.lax.dynamic_slice_in_dim(
                table, i * step, step)),
            jnp.zeros((r, r), jnp.float32))
        if n % step:
            G = G + compute_yty(table[n - n % step:])
    return G


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_yty(table, yty, rows, vals):
    """:func:`_scatter_rows` that also moves ``yty``, the table's Gram
    matrix ``table^T table``, by the rows it writes: ``yty + new^T new -
    old^T old`` in true float32, ``old`` the rows as they lie at ``rows``
    BEFORE the set (``rows`` names none twice).  A row outside the table
    (the padding) is dropped by the set and counts as zero on both
    sides.  O(touched rows * rank^2), the table read at ``rows`` alone."""
    with jax.named_scope(LIVE_FOLDIN_YTY_SCOPE), \
            jax.default_matmul_precision("highest"):
        inside = (rows < table.shape[0])[:, None]
        old = table.at[rows].get(mode="fill", fill_value=0.0)
        new = jnp.where(inside, vals, 0.0)
        yty = yty + compute_yty(new) - compute_yty(old)
    with jax.named_scope("live.foldin.scatter"):
        return table.at[rows].set(vals, mode="drop"), yty


def write_rows(table, rows, vals, pad=None, yty=None):
    """``table`` (on the device, as :func:`place_rows` made it) with the
    host's ``vals`` at ``rows``, written IN PLACE: the table is donated,
    so the caller's handle is deleted and the result is the same buffer.
    Only the rows cross host→device, padded up ``pad_for``'s ladder (8,
    64, 512, ...; ``pad``: to that many, for whoever runs the programs
    ahead) with a row outside the table, which is dropped: few
    programs, O(touched rows) on the host, on the link and on the
    device.

    ``yty``: the table's Gram matrix on the device (:func:`whole_yty`),
    for whoever keeps one — the implicit rule reads it at every fold.
    The same write then also moves it by the rows written
    (:func:`_scatter_rows_yty`) and ``(table, yty)`` comes back."""
    n, pad = len(rows), pad or pad_for(len(rows))
    vp = np.zeros((pad, table.shape[1]), dtype=np.float32)
    vp[:n] = vals
    return _scatter(table, yty, *put((padded_rows(rows, pad, table), vp)))


def _scatter(table, yty, rows, vals):
    """The row write's program on its arguments: the plain one, or with
    ``yty`` (the table's Gram matrix) the one that also moves it."""
    write, more = ((_scatter_rows, ()) if yty is None
                   else (_scatter_rows_yty, (yty,)))
    return write(table, *more, rows, vals)


def padded_rows(rows, pad, table):
    """``rows`` as ``int32[pad]``, padded with a row outside ``table``
    (dropped by every row write)."""
    rp = np.full(pad, table.shape[0], dtype=np.int32)
    rp[:len(rows)] = rows
    return rp


def write_placed_rows(table, rows, vals, yty=None):
    """:func:`write_rows` for ``vals`` that lie on the device already —
    the padded ``[pad, rank]`` result of a fold, ``rows`` the table rows
    of its first ``len(rows)`` — the same program at the same shapes:
    only the row numbers come from the host, and they ride the call as
    its host argument (no placement)."""
    return _scatter(table, yty, padded_rows(rows, vals.shape[0], table),
                    vals)


def place_rows(F, *, capacity, mesh=None, table=None):
    """``F`` on the device with zero rows up to ``capacity``: the table a
    live path appends to without a change of shape (a gather or a lookup
    never addresses the spare rows; ``F^T F`` is unchanged by them).
    ``F`` comes from the host ``PLACE_CHUNK`` rows at a time, each chunk
    written in place into a zero table that is donated to the write, so
    the table never lies on the device twice (uploaded whole and then
    padded it does at its peak: 1.5 GB more at 1.5 M × 256).  The last
    chunk starts early enough to be whole — rows written twice, with the
    same values — so every chunk runs one program.

    With a ``mesh`` the table is sharded by rows over its devices: shard
    ``s`` of ``D`` holds rows ``[s * n_loc, (s + 1) * n_loc)``, ``n_loc =
    ceil(capacity / D)``, and the result has ``D * n_loc`` rows.  Each
    shard is placed as above on its own device, the shards' chunks in
    turn so that the devices' uploads overlap, and the shards are joined
    without a copy: no device ever holds another's rows, or its own
    twice.

    ``table`` names what goes up for ``device.placed_bytes`` (``users`` |
    ``catalog`` | ``fold_fixed``: ``obs.phases.count_placed``); ``None``
    counts nothing."""
    F = np.asarray(F, dtype=np.float32)
    if table is not None:
        count_placed(table, F.nbytes)
    if mesh is None:
        return _place_shards(F, [(None, 0, len(F))], capacity)[0]
    devices = list(mesh.devices.flat)
    n_loc = -(-int(capacity) // len(devices))
    shards = _place_shards(
        F, [(d, min(s * n_loc, len(F)), min((s + 1) * n_loc, len(F)))
            for s, d in enumerate(devices)], n_loc)
    return jax.make_array_from_single_device_arrays(
        (len(devices) * n_loc, F.shape[1]),
        jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0])),
        shards)


def _place_shards(F, parts, rows):
    """One zero table of ``rows`` rows per ``(device, lo, hi)`` of
    ``parts`` (``None``: the default device), ``F[lo:hi]`` written into
    its head a chunk at a time; see :func:`place_rows`."""
    width = F.shape[1]
    tables, sent = [], [None] * len(parts)
    for device, _, _ in parts:
        with jax.default_device(device):
            tables.append(jnp.zeros((rows, width), jnp.float32))
    steps = [min(PLACE_CHUNK, hi - lo) for _, lo, hi in parts]
    for i in range(max(-(-(hi - lo) // (step or 1))
                       for (_, lo, hi), step in zip(parts, steps))):
        for j, ((device, lo, hi), step) in enumerate(zip(parts, steps)):
            at = i * step
            if not step or at >= hi - lo:
                continue
            at = min(at, hi - lo - step)
            chunk = jax.device_put(F[lo + at:lo + at + step], device)
            tables[j] = _write_rows(tables[j], chunk, at)
            # one upload behind, no more: an upload takes its device
            # buffer when it is enqueued, and a loop that ran ahead would
            # hold the whole table a second time
            if sent[j] is not None:
                sent[j].block_until_ready()
            sent[j] = chunk
    return tables


def fold_in(
    V,
    cols,
    vals,
    mask,
    reg_param,
    implicit_prefs=False,
    alpha=1.0,
    nonnegative=False,
    nnls_sweeps=32,
    YtY=None,
    jitter=DEFAULT_JITTER,
):
    """Solve factors for a batch of touched entities against fixed ``V``.

    cols/vals/mask: [n, w] padded CSR rows (same convention as
    tpu_als.core.ratings).  Returns new factors [n, rank].

    The three arrays go to the program as ONE (:func:`pack_rows`: planes
    of one packed array cost nothing to pack, and host arrays ride the
    call).  Eager wrapper: settles the solve's backend before tracing
    (:func:`solve_path`; a probe inside the jit trace cannot run and
    would pin the fallback path into the jit cache), then dispatches to the
    jitted body, one program per (rows, width, backend).
    """
    backend = solve_path(V.shape[-1], cols.shape[0], nonnegative)[0]
    return _fold_in_jit(V, pack_rows(cols, vals, mask), reg_param,
                        implicit_prefs=implicit_prefs, alpha=alpha,
                        nonnegative=nonnegative, nnls_sweeps=nnls_sweeps,
                        YtY=YtY, jitter=jitter, backend=backend)


def planes(packed):
    """``(cols, vals, mask)`` of rows packed as :func:`pack_rows` packs
    them, as views of the host array ``packed`` (``int32[3, n, w]``):
    what a caller fills in place and hands to :func:`fold_in`, which
    then sends ``packed`` itself."""
    return packed[0], packed[1].view(np.float32), packed[2].view(np.float32)


def pack_rows(cols, vals, mask):
    """The padded rows of a fold as the ONE array its program takes,
    ``int32[3, n, w]``: the ids, the ratings' float32 bits and the mask's
    float32 bits (same-itemsize views: nothing is converted, and floats
    ride as integer bits, never the other way — the TPU flushes the
    subnormals that small ids read as).  The :func:`planes` of one host
    array ARE that array: no copy.  Other host arrays are stacked on the
    host — either way the array rides the program's call as its host
    argument, no placement of its own; arrays on the device are packed
    there."""
    if not all(isinstance(a, np.ndarray) for a in (cols, vals, mask)):
        return jnp.stack([jnp.asarray(cols, jnp.int32)] + [
            jax.lax.bitcast_convert_type(jnp.asarray(a, jnp.float32),
                                         jnp.int32) for a in (vals, mask)])
    whole = cols.base
    if (isinstance(whole, np.ndarray) and whole.dtype == np.int32
            and whole.shape == (3, *cols.shape)
            and all(a.base is whole and a.ctypes.data == whole[i].ctypes.data
                    for i, a in enumerate((cols, vals, mask)))):
        return whole
    return np.stack([cols.astype(np.int32, copy=False),
                     vals.astype(np.float32, copy=False).view(np.int32),
                     mask.astype(np.float32, copy=False).view(np.int32)])


@functools.partial(
    jax.jit,
    static_argnames=("implicit_prefs", "nonnegative", "nnls_sweeps", "jitter",
                     "backend"),
)
def _fold_in_jit(
    V,
    packed,
    reg_param,
    implicit_prefs=False,
    alpha=1.0,
    nonnegative=False,
    nnls_sweeps=32,
    YtY=None,
    jitter=DEFAULT_JITTER,
    backend="auto",
):
    # the rows as ``pack_rows`` packed them: one argument, one transfer
    # where it comes from the host
    cols = packed[0]
    vals, mask = (jax.lax.bitcast_convert_type(packed[i], jnp.float32)
                  for i in (1, 2))
    # A handful of systems builds its normal equations in true float32:
    # six bf16 passes of matrices this small cost nothing beside the
    # program's launch, and one pass costs the published rows 6e-3 to
    # 8e-3 of their length on a v5e (median; XLA's Cholesky multiplies in
    # float32 whatever the default) — more than serving's rescore adds
    few = cols.shape[0] <= FEW_SYSTEMS
    # the scopes name the program's two halves in every operation's
    # op_name, for whoever reads the compiled program
    with jax.named_scope("live.foldin.gram"), (
            jax.default_matmul_precision("highest") if few
            else contextlib.nullcontext()):
        Vg = V[cols]
        if implicit_prefs:
            if YtY is None:
                YtY = compute_yty(V)
            A, b, count = normal_eq_implicit(Vg, vals, mask, reg_param,
                                             alpha, YtY)
        else:
            A, b, count = normal_eq_explicit(Vg, vals, mask, reg_param)
    with jax.named_scope("live.foldin.solve"):
        if nonnegative:
            return solve_nnls(A, b, count, sweeps=nnls_sweeps,
                              jitter=jitter)
        return solve_spd(A, b, count, jitter=jitter, backend=backend)
