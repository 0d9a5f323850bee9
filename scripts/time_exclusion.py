#!/usr/bin/env python3
"""What per-request exclusion costs the device: the mask alone and inside
the scoring program, history pad {0, 64, 512, 4096} x bucket {8, 32, 128}
at the benchmark cell's 1,506,048 columns of rank 256.

    chiprun -- python scripts/time_exclusion.py [--forms a,b,...]

One JSON line a measurement, then a markdown table.  Forms of the mask
(``--forms``, default all), each given a ``[B, pad]`` list of histories
and a ``[B, 64]`` list of the requests' own ids, rows ascending, and
each returning ``bool[B, columns]``:

- ``tree``: ``ops.topk.excluded_mask`` as the tree has it — the (word,
  bit) keys sorted unstably, one scatter-add into a bit-packed
  ``uint32[blocks / 32, B, 128]``, unpacked by a shift along the major
  dimension;
- ``dense``: the straightforward form — ONE scatter of the two lists
  concatenated into ``bool[B, columns]``: the TPU compiler flattens the
  pairs, SORTS them (stably: ``compile_s``), runs its sorted scatter into
  a flat array and copies that row by row into the tiled matrix;
- ``dense_sorted``: the same as one scatter a list, each told its
  indices are sorted (no sort, on the device or in the compiler);
- ``flat``: the batch's ids as ``pad + 3 B`` (row, id) pairs in all — one
  long history and short ones — scattered sorted into ``bool[B *
  columns]`` and reshaped: what a layout by the batch's TOTAL would pay;
- ``compare``: no scatter — ``any(columns == id)`` over the ids, bucket
  8 and pad 64 alone (it costs ``B * columns * ids`` compares).

Then ``serving.index.shortlist_rescore`` whole: without ``seen`` (pad 0, the
program every other cell runs) and with it at each pad — every row's
lists full, and one row's full beside rows of 3 ids (what most batches
look like: the scatter then takes the quarter of the sorted keys that
holds them all) — the device's time a run and the three longest
operations.  A time is the device's own
(the run's duration on the trace's ``XLA Modules`` line, median of 20
after a warm one); ``compile_s`` is the host's clock around the first
call.  Exits 1 without a TPU: a CPU's times are not the chip's.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace import self_times, short_name  # noqa: E402
from tpu_als.ops.topk import (  # noqa: E402
    NOT_AN_ID,
    excluded_mask,
    shortlist_columns,
)

ITEMS = 1_505_938
K = 64
RANK = 256
PADS = (64, 512, 4096)
OWN = 64
BUCKETS = (8, 32, 128)
REPEATS = 20


def device_ms(fn, *args):
    """``(device ms a run, compile seconds, [(operation, self ms a
    run)] longest first)`` of the jitted ``fn``."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(REPEATS):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    lines = {line.name: list(line.events) for plane in data.planes
             if plane.name == "/device:TPU:0" for line in plane.lines}
    runs = [ev.duration_ns * 1e-6 for ev in lines["XLA Modules"]]
    ops = self_times([(short_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)) for ev in lines["XLA Ops"]])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:3]
    return (statistics.median(runs), compile_s,
            [(op, ns * 1e-6 / REPEATS) for op, ns in top])


def histories(rng, bucket, pad, columns, full_rows=None):
    """``int32[bucket, pad]``: each row ``pad`` distinct ids ascending
    (``full_rows`` of them; the others 3 ids and padding)."""
    seen = np.full((bucket, pad), NOT_AN_ID, np.int32)
    for b in range(bucket):
        n = pad if full_rows is None or b < full_rows else min(3, pad)
        seen[b, :n] = np.sort(rng.choice(columns, n, replace=False))
    return seen


@jax.jit
def mask_tree(history, own):
    n = history.shape[0]
    return excluded_mask((history, own), COLS, 128).transpose(
        1, 0, 2).reshape(n, COLS)


@jax.jit
def mask_dense(history, own):
    seen = jnp.concatenate([history, own], axis=1)
    n = seen.shape[0]
    return jnp.zeros((n, COLS), jnp.bool_).at[
        jnp.arange(n, dtype=jnp.int32)[:, None], seen].set(True, mode="drop")


@jax.jit
def mask_dense_sorted(history, own):
    n = history.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    mask = jnp.zeros((n, COLS), jnp.bool_)
    for ids in (history, own):
        mask = mask.at[rows, ids].set(True, mode="drop",
                                      indices_are_sorted=True)
    return mask


def mask_flat(n):
    @jax.jit
    def fn(rows, ids):
        at = jnp.where(ids < COLS, rows * COLS + ids, n * COLS)
        return jnp.zeros((n * COLS,), jnp.bool_).at[at].set(
            True, mode="drop", indices_are_sorted=True).reshape(n, COLS)
    return fn


@jax.jit
def mask_compare(history, own):
    seen = jnp.concatenate([history, own], axis=1)
    cols = jnp.arange(COLS, dtype=jnp.int32)
    return (cols[None, :, None] == seen[:, None, :]).any(-1)


COLS = shortlist_columns(ITEMS, K)


def masks_alone(forms, rng):
    rows = []
    for bucket in BUCKETS:
        own = jnp.asarray(histories(rng, bucket, OWN, ITEMS))
        for pad in PADS:
            history = jnp.asarray(histories(rng, bucket, pad, ITEMS))
            row = {"table": "mask", "B": bucket, "pad": pad}
            for form, fn in (("tree", mask_tree), ("dense", mask_dense),
                             ("dense_sorted", mask_dense_sorted)):
                if form in forms:
                    row[form] = device_ms(fn, history, own)
            if {"tree", "dense"} <= forms:      # the same mask, bit for bit
                assert bool(jnp.array_equal(mask_tree(history, own),
                                            mask_dense(history, own))), \
                    (bucket, pad)
            if "flat" in forms:
                one = histories(rng, bucket, pad, ITEMS, full_rows=1)
                r, c = np.nonzero(one < ITEMS)
                flat_r = np.full(pad + 3 * bucket, bucket - 1, np.int32)
                flat_c = np.full(pad + 3 * bucket, NOT_AN_ID, np.int32)
                flat_r[:len(r)], flat_c[:len(r)] = r, one[r, c]
                row["flat"] = device_ms(mask_flat(bucket),
                                        jnp.asarray(flat_r),
                                        jnp.asarray(flat_c))
            if "compare" in forms and pad == 64 and bucket == 8:
                row["compare"] = device_ms(mask_compare, history, own)
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def scoring_program(rng):
    from tpu_als.serving.index import _topk_jit

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    Vq = jax.random.randint(k1, (COLS, RANK), -127, 128, jnp.int8)
    sv = jnp.full((COLS,), 1.0 / 127 / 16, jnp.float32)
    V = jax.random.normal(k2, (ITEMS, RANK), jnp.float32) / 16
    valid = jnp.ones((COLS,), jnp.bool_).at[ITEMS:].set(False)
    rows = []
    for bucket in BUCKETS:
        U = jax.random.normal(jax.random.fold_in(k3, bucket),
                              (bucket, RANK), jnp.float32)
        for pad, full_rows in [(0, None)] + [(p, f) for p in PADS
                                             for f in (None, 1)]:
            kw = {}
            if pad:     # every row full, or one full and the others 3 ids
                kw["seen"] = (
                    jnp.asarray(histories(rng, bucket, pad, ITEMS,
                                          full_rows)),
                    jnp.asarray(histories(rng, bucket, OWN, ITEMS,
                                          full_rows)))
            ms, compile_s, top = device_ms(
                lambda *a, kw=kw: _topk_jit(*a, k=10, shortlist_k=K, **kw),
                U, Vq, sv, V, valid)
            if pad:     # the rule held: no excluded id among the answers
                _, ids = _topk_jit(U, Vq, sv, V, valid, k=10,
                                   shortlist_k=K, **kw)
                hit = (np.asarray(ids)[:, :, None] == np.concatenate(
                    kw["seen"], axis=1)[:, None, :]).any()
                assert not hit, (bucket, pad)
            row = {"table": "program", "B": bucket, "pad": pad,
                   "rows_full": "all" if full_rows is None else full_rows,
                   "device_ms": ms, "compile_s": compile_s, "top": top}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms",
                    default="tree,dense,dense_sorted,flat,compare")
    args = ap.parse_args(argv)
    forms = set(args.forms.split(","))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"time_exclusion: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    masks = masks_alone(forms, rng)
    program = scoring_program(rng)

    def cell(t):
        return (f"{t[0]:.4f} ({t[1]:.1f} s; " + ", ".join(
            f"`{op}` {ms:.4f}" for op, ms in t[2][:2]) + ")")

    print("\nThe mask alone: device ms a run (compile seconds; its two "
          "longest operations, self ms a run), medians of 20.\n")
    names = [f for f in ("tree", "dense", "dense_sorted", "flat", "compare")
             if f in forms]
    print("| B | pad | " + " | ".join(names) + " |")
    print("| --- | --- |" + " --- |" * len(names))
    for r in masks:
        print(f"| {r['B']} | {r['pad']} | " + " | ".join(
            cell(r[f]) if f in r else "-" for f in names) + " |")
    print("\n`shortlist_rescore` whole at the cell's shapes: pad 0 is the "
          "program without `seen`.\n")
    print("| B | pad | rows full | device ms | over pad 0 | compile s | "
          "longest operations |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    base = {r["B"]: r["device_ms"] for r in program if r["pad"] == 0}
    for r in program:
        print(f"| {r['B']} | {r['pad']} | {r['rows_full']} | "
              f"{r['device_ms']:.4f} | "
              f"{r['device_ms'] - base[r['B']]:+.4f} | "
              f"{r['compile_s']:.1f} | " + ", ".join(
                  f"`{op}` {ms:.4f}" for op, ms in r["top"]) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
