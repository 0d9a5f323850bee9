#!/usr/bin/env python3
"""The shortlist selection on the chip, whole and by stage, k = 64,
B in {8, 32, 128}.

    chiprun -- python scripts/time_shortlist.py

Three tables, one JSON line a row and then markdown:

1. **The stages alone** (PR 37), blocks in {11,766, 11,956} (the cells'
   catalogs, without and with the live segment): ``jax.lax.top_k`` on
   ``f32[B, blocks]`` handed over row-major (``{1,0}``) and column-major
   (``{0,1}``, which is how stage two's block maxima leave the score
   fusion: the batch's rows along the 128 lanes), each asked for with a
   layout constraint on the operand, and on ``f32[B, k * 128]`` (stage
   three's operand).  The layout ``TopK`` was in fact handed is read back
   from the compiled program, and the operation's own time stands beside
   the program's (which holds the relayout copies a constraint may cost).
2. **The whole function**: ``ops.topk.shortlist_topk`` on ``f32[B,
   blocks * 128]`` under three rules for stage two's layout constraint:
   the tree's (``ops.topk.ROW_MAJOR_BELOW`` rows), never (the function
   as it was before PR 37) and always.
3. **The scoring program** ``serving.index.shortlist_rescore`` at the benchmark
   cell's shapes (1,506,048 int8 rows of rank 256) under the same three:
   what a batch pays on the device.

Then, as since PR 26, ``jax.lax.top_k`` against ``shortlist_topk`` on
``f32[B, 1505938]``, ragged and at the padded width.

With ``--block-len 256`` and / or ``--tail 512`` (PR 43) it times STAGE
ONE instead, and nothing else (~2 min): the scoring program whose stage
one reads the written score matrix a second time, in the parent's form
beside the tree's, with the operations that take longest in each —

- ``--block-len L`` (a multiple of 128 above 128): ``shortlist_rescore`` over a
  catalog whose plan has blocks of ``L`` (256: one shard of the mesh
  cell, 3,012,096 rows), the block maximum taken over a block at once
  (parent) and as ``ops.topk.block_maxima`` takes a long block (tree:
  its 128-lane groups folded into one first);
- ``--tail d``: ``shortlist_rescore(delta=)`` at the live-items cell's shapes
  (1,529,856 base columns, ``d`` slots), the segment's scores
  concatenated to the matrix (parent) and joined at stage three (tree).

The parent's form is written out here (:func:`parents_shortlist`) and
put in the index module's place of ``shortlist_topk`` around its runs: a
script's device, not a switch of the program.

A time is the device's own: the median duration of the program's runs on
the trace's ``XLA Modules`` line over 20 runs after a warm one (a host
clock around a 0.05 ms program reads the launch, 0.2-0.5 ms on this
host), and its longest operation by self time on the ``XLA Ops`` line;
the host clock's median, each run fenced with ``block_until_ready``,
stands beside them.  Inputs are N(0, 1) scores.
Exits 1 without a TPU: a CPU's times are not the chip's.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace import self_times, short_name  # noqa: E402
from tpu_als.ops import topk as topk_mod  # noqa: E402
from tpu_als.ops.topk import (  # noqa: E402
    shortlist_columns,
    shortlist_plan,
    shortlist_topk,
)

COLUMNS = 1_505_938
K = 64
BLOCK_LEN = 128
BLOCKS = (11_766, 11_956)
BATCHES = (8, 32, 128)
REPEATS = 20
RANK = 256
ROW_MAJOR = Layout(major_to_minor=(0, 1))
COLUMN_MAJOR = Layout(major_to_minor=(1, 0))


def profiled(fn, *args):
    """``(device ms of each run, host ms of each run, {operation: self
    ns over all runs})`` of ``REPEATS`` fenced calls of the jitted ``fn``
    after a warm one, from a profiler trace of them."""
    jax.block_until_ready(fn(*args))
    host = []
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            host.append((time.perf_counter() - t0) * 1e3)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    lines = {line.name: list(line.events) for plane in data.planes
             if plane.name == "/device:TPU:0" for line in plane.lines}
    runs = [ev.duration_ns * 1e-6 for ev in lines["XLA Modules"]]
    assert len(runs) == REPEATS, (len(runs), REPEATS)
    ops = self_times([(short_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)) for ev in lines["XLA Ops"]])
    return runs, host, ops


def times_ms(fn, *args):
    """``(device ms, host ms, longest operation, its ms)`` of one call of
    the jitted ``fn``: medians over ``REPEATS`` runs, the device's from
    its own record of them; the operation's is its self time a run."""
    runs, host, ops = profiled(fn, *args)
    op, ns = max(ops.items(), key=lambda kv: kv[1])
    return (statistics.median(runs), statistics.median(host), op,
            ns * 1e-6 / REPEATS)


def longest_ops(fn, *args, top=6):
    """``(device ms, [(operation, self ms a run)])`` of the jitted
    ``fn``: the median of its runs and its ``top`` operations by self
    time, as :func:`times_ms` reads them."""
    runs, _, ops = profiled(fn, *args)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return (statistics.median(runs),
            [(op, ns * 1e-6 / REPEATS) for op, ns in ranked])


def topk_operands(fn, *args, **kw):
    """What each ``TopK`` of the compiled ``fn`` is handed, by the last
    part of its ``op_name``: ``{"blocks/top_k": "f32[8,11766]{1,0}"}``."""
    text = fn.lower(*args, **kw).compile().as_text()
    shapes = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\]\{[\d,]*)", text))
    found = {}
    for ln in text.splitlines():
        m = re.search(r"= \(.*\) (?:custom-call|fusion)\((%[\w.\-]+)\).*"
                      r"(?:custom_call_target=\"TopK\"|kind=kCustom).*"
                      r"op_name=\"[^\"]*?(\w+/top_k|top_k)\"", ln)
        if m and m.group(1) in shapes:
            found[m.group(2)] = shapes[m.group(1)] + "}"
    return found


@jax.jit
def single(scores):
    return jax.lax.top_k(scores, K)


@functools.partial(jax.jit, static_argnames="layout")
def single_in(scores, layout):
    return jax.lax.top_k(with_layout_constraint(scores, layout), K)


@jax.jit
def staged(scores):
    return shortlist_topk(scores, K)


def timed_by_rule(fn, *args, **kw):
    """Device / host ms of the jitted ``fn`` under three rules for stage
    two's layout constraint: ``tree`` (``ROW_MAJOR_BELOW`` as it stands),
    ``never`` (the program as it was before PR 37) and ``always``.  A
    script's device, not a switch of the program: the constant is put
    back, and every trace dropped, around each."""
    out = {}
    kept = topk_mod.ROW_MAJOR_BELOW
    for rule, below in (("tree", kept), ("never", 0), ("always", 1 << 30)):
        topk_mod.ROW_MAJOR_BELOW = below
        jax.clear_caches()
        try:
            out[rule] = times_ms(lambda *a: fn(*a, **kw), *args)
            if rule == "tree":
                out["operands"] = topk_operands(fn, *args, **kw)
        finally:
            topk_mod.ROW_MAJOR_BELOW = kept
    jax.clear_caches()
    return out


def stages_alone(dev):
    rows = []
    for b in BATCHES:
        for blocks in BLOCKS:
            x = jax.random.normal(jax.random.PRNGKey(b), (b, blocks),
                                  jnp.float32)
            won = jax.random.normal(jax.random.PRNGKey(b + 1),
                                    (b, K * BLOCK_LEN), jnp.float32)
            want = single(x)
            row = {"table": "stages", "B": b, "blocks": blocks,
                   "device": dev.device_kind, "equal": True}
            for name, layout in (("row_major", ROW_MAJOR),
                                 ("column_major", COLUMN_MAJOR)):
                got = single_in(x, layout)
                row["equal"] &= bool(jnp.array_equal(want[0], got[0])
                                     and jnp.array_equal(want[1], got[1]))
                row[name] = times_ms(single_in, x, layout)
                row[name + "_operand"] = topk_operands(single_in, x, layout)
            row["select"] = times_ms(single_in, won, ROW_MAJOR)
            rows.append(row)
            print(json.dumps(row), flush=True)
            del x, won
    return rows


def whole_function():
    rows = []
    for b in BATCHES:
        for blocks in BLOCKS:
            x = jax.random.normal(jax.random.PRNGKey(b),
                                  (b, blocks * BLOCK_LEN), jnp.float32)
            row = {"table": "whole", "B": b, "blocks": blocks,
                   **timed_by_rule(staged, x),
                   **shortlist_plan(blocks * BLOCK_LEN, K, b)._asdict()}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del x
    return rows


def scoring_program():
    from tpu_als.serving.index import _topk_jit

    cols = shortlist_columns(COLUMNS, K)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    Vq = jax.random.randint(k1, (cols, RANK), -127, 128, jnp.int8)
    sv = jnp.full((cols,), 1.0 / 127 / 16, jnp.float32)
    V = jax.random.normal(k2, (COLUMNS, RANK), jnp.float32) / 16
    valid = jnp.ones((cols,), jnp.bool_).at[COLUMNS:].set(False)
    rows = []
    for b in BATCHES:
        U = jax.random.normal(jax.random.fold_in(k3, b), (b, RANK),
                              jnp.float32)
        row = {"table": "program", "B": b, "columns": cols,
               "blocks": shortlist_plan(cols, K).blocks,
               **timed_by_rule(_topk_jit, U, Vq, sv, V, valid,
                               k=10, shortlist_k=K)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def against_single_top_k(dev):
    padded = shortlist_columns(COLUMNS, K)
    rows = []
    for b in BATCHES:
        row = {"table": "single", "B": b, "k": K, "device": dev.device_kind}
        for cols in (COLUMNS, padded):
            x = jax.random.normal(jax.random.PRNGKey(b), (b, cols),
                                  jnp.float32)
            want, got = single(x), staged(x)
            same = bool(jnp.array_equal(want[0], got[0])
                        and jnp.array_equal(want[1], got[1]))
            row[str(cols)] = {
                "top_k": times_ms(single, x),
                "shortlist_topk": times_ms(staged, x),
                "equal": same, **shortlist_plan(cols, K, b)._asdict()}
            del x, want, got
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows, padded


def parents_shortlist(scores, k, tail=None):
    """``shortlist_topk`` as the parent of PR 43 had it: a tail
    concatenated to the matrix before stage one, and a block's maximum
    taken over the whole block whatever its length."""
    if tail is not None:
        scores = jnp.concatenate([scores, tail], axis=1)
    kept = topk_mod.shortlist_plan
    topk_mod.shortlist_plan = lambda *a, **kw: kept(*a, **kw)._replace(
        blockmax="block")
    try:
        return shortlist_topk(scores, k)
    finally:
        topk_mod.shortlist_plan = kept


def in_both_forms(fn, *args, **kw):
    """``{"parent": ..., "tree": ...}``: :func:`longest_ops` of the
    jitted scoring program ``fn`` with the index module's
    ``shortlist_topk`` replaced by :func:`parents_shortlist` and as it
    stands, and whether the two answered alike."""
    from tpu_als.serving import index

    out, answers = {}, {}
    for form, topk in (("parent", parents_shortlist),
                       ("tree", shortlist_topk)):
        index.shortlist_topk = topk
        jax.clear_caches()
        try:
            call = lambda *a: fn(*a, **kw)      # noqa: E731
            answers[form] = jax.block_until_ready(call(*args))
            out[form] = longest_ops(call, *args)
        finally:
            index.shortlist_topk = shortlist_topk
    jax.clear_caches()
    out["equal"] = all(bool(jnp.array_equal(a, b)) for a, b in
                       zip(answers["parent"], answers["tree"]))
    return out


def stage_one(block_len, tail):
    """The scoring programs whose stage one PR 43 rewrote, in both forms
    (module docstring): one JSON line a bucket, then markdown."""
    from tpu_als.core.ratings import row_capacity
    from tpu_als.serving.index import SLOT_FREE, _topk_jit

    def catalog(cols, rows):
        k1, k2 = jax.random.split(jax.random.PRNGKey(cols))
        return (jax.random.randint(k1, (cols, RANK), -127, 128, jnp.int8),
                jnp.full((cols,), 1.0 / 127 / 16, jnp.float32),
                jax.random.normal(k2, (rows, RANK), jnp.float32) / 16,
                jnp.ones((cols,), jnp.bool_).at[rows:].set(False))

    def queries(b):
        return jax.random.normal(jax.random.PRNGKey(b), (b, RANK),
                                 jnp.float32)

    rows = []
    if block_len:
        # one shard of the mesh cell where that gives the asked length,
        # else the catalog whose plan's blocks are that long
        cols = 3_012_096 if block_len == 256 else K * block_len ** 2
        cols = shortlist_columns(cols, K)
        assert shortlist_plan(cols, K).block_len == block_len, cols
        tables = catalog(cols, cols)
        for b in BATCHES:
            rows.append({"table": "stage_one", "program": "base",
                         "B": b, **shortlist_plan(cols, K, b)._asdict(),
                         **in_both_forms(_topk_jit, queries(b), *tables,
                                         k=10, shortlist_k=K)})
            print(json.dumps(rows[-1]), flush=True)
        del tables
    if tail:
        cap = row_capacity(COLUMNS)
        cols = shortlist_columns(cap, K)
        tables = catalog(cols, cap)
        k1, k2 = jax.random.split(jax.random.PRNGKey(tail))
        used = tail // 2        # half the slots hold rows, some overridden
        seg = (jnp.full((tail,), SLOT_FREE, jnp.int32).at[:used].set(
                   jax.random.choice(k1, cap, (used,), replace=False)
                   .astype(jnp.int32)),
               jax.random.randint(k2, (tail, RANK), -127, 128, jnp.int8),
               jnp.full((tail,), 1.0 / 127 / 16, jnp.float32),
               jax.random.normal(k1, (tail, RANK), jnp.float32) / 16,
               jnp.zeros((tail,), jnp.bool_).at[:used].set(True))
        for b in BATCHES:
            rows.append({"table": "stage_one",
                         "program": "with a segment", "B": b,
                         **shortlist_plan(cols, K, b, tail)._asdict(),
                         **in_both_forms(_topk_jit, queries(b), *tables,
                                         k=10, shortlist_k=K, delta=seg,
                                         last_id=jnp.int32(cap - 1))})
            print(json.dumps(rows[-1]), flush=True)
    print("\nDevice ms a run (median of 20) of the scoring program, stage "
          "one in the parent's form and in the tree's; below each, its "
          "longest operations (self ms a run).\n")
    print("| program | B | columns, L, tail | parent | tree | parent - tree "
          "| equal |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['program']} | {r['B']} | {r['columns']}, "
              f"{r['block_len']}, {r['tail']} | {r['parent'][0]:.4f} | "
              f"{r['tree'][0]:.4f} | {r['parent'][0] - r['tree'][0]:.4f} | "
              f"{r['equal']} |")
    for r in rows:
        for form in ("parent", "tree"):
            print(f"\n{r['program']} B={r['B']} {form}: " + "; ".join(
                f"`{op}` {ms:.4f}" for op, ms in r[form][1]))
    return 0 if all(r["equal"] for r in rows) else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--block-len", type=int, default=0,
                    help="time stage one at blocks of this many columns "
                    "(a multiple of 128 above 128), parent's form and "
                    "tree's")
    ap.add_argument("--tail", type=int, default=0,
                    help="time stage one with a delta segment of this "
                    "many slots, concatenated (parent) and joined at "
                    "stage three (tree)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"time_shortlist: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    if args.block_len or args.tail:
        return stage_one(args.block_len, args.tail)
    stages = stages_alone(dev)
    whole = whole_function()
    program = scoring_program()
    single_rows, padded = against_single_top_k(dev)

    def ms(t):
        return f"{t[0]:.4f} ({t[1]:.3f})"

    def op(t):
        return f"{t[3]:.4f} `{t[2]}`"

    print("\nDevice ms of the program (host clock ms), medians of 20; "
          "an operation's: self ms a run.\n")
    print("| B | blocks | top_k asked row-major {1,0} | its longest "
          "operation | top_k asked column-major {0,1} | its longest "
          "operation | ratio of the two operations | top_k f32[B, 8192] "
          "row-major (stage three) | TopK was handed | equal |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in stages:
        print(f"| {r['B']} | {r['blocks']} | {ms(r['row_major'])} | "
              f"{op(r['row_major'])} | {ms(r['column_major'])} | "
              f"{op(r['column_major'])} | "
              f"{r['column_major'][3] / r['row_major'][3]:.2f} | "
              f"{ms(r['select'])} | {r['row_major_operand']} / "
              f"{r['column_major_operand']} | {r['equal']} |")
    print(f"\nStage two constrained to row-major by the tree's rule "
          f"(under {topk_mod.ROW_MAJOR_BELOW} rows), never (the program "
          "before PR 37), always.\n")
    for title, table in (("shortlist_topk on f32[B, blocks * 128]", whole),
                         ("shortlist_rescore at the cell's shapes", program)):
        print(f"| B | blocks | {title}: tree | its longest operation | "
              "never | its longest operation | always | its longest "
              "operation | never - tree | TopK was handed (tree) |")
        print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
        for r in table:
            print(f"| {r['B']} | {r['blocks']} | " + " | ".join(
                f"{ms(r[rule])} | {op(r[rule])}"
                for rule in ("tree", "never", "always"))
                + f" | {r['never'][0] - r['tree'][0]:.4f} | "
                f"{r['operands']} |")
        print()
    print(f"\n| B | lax.top_k | shortlist_topk | at {padded} columns: "
          "lax.top_k | shortlist_topk | stages, blocks, L | equal |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for row in single_rows:
        a, p = row[str(COLUMNS)], row[str(padded)]
        print(f"| {row['B']} | {ms(a['top_k'])} | "
              f"{ms(a['shortlist_topk'])} | {ms(p['top_k'])} | "
              f"{ms(p['shortlist_topk'])} | {a['stages']}, {a['blocks']}, "
              f"{a['block_len']} | {a['equal'] and p['equal']} |")
    ok = (all(r["equal"] for r in stages)
          and all(r[str(c)]["equal"] for r in single_rows
                  for c in (COLUMNS, padded)))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
