#!/usr/bin/env python3
"""What the engine thread pays to hand a staged batch to the device, on
the chip's host: the forms of one batch's dispatch at the shapes of
``amazon23-r256-share32.serve-steady`` (``--mesh 4``: of
``amazon23-r256-host4of16.serve-steady-mesh``), bucket 8, 32 and 128,
against the AOT executable ``ServingEngine.warmup()`` pinned.

    chiprun -- python3 scripts/time_dispatch.py
    chiprun --chips 4 -- python3 scripts/time_dispatch.py --mesh 4

On one chip (PR 41):

(a) ``put_call``: ``jax.device_put`` of the staged array, then the call
    on its result — ``_dispatch`` until PR 41; its two halves are timed
    apart as well (``put_call.put``, ``put_call.call``);
(b) ``host_call``: the staged numpy array handed to the call, whose own
    argument handling places it — ``_dispatch`` since PR 41;
(c) ``put``: the ``device_put`` alone;
(d) ``placed_call``: the call on an argument that is on the device
    already (what a call costs with nothing to upload);
(i) ``loaded_call``: (b) on the same executable LOADED from its
    serialized bytes (``serving.pins.dumps`` / ``loads``), as a warm
    start pins it since PR 51 — it should cost what (b) costs (with
    ``--mesh``: ``loaded_one_call``, beside (e)).

(a) − (b) is what a batch saves; (c) against (b) − (d) says how much of
a ``device_put`` is its Python path and how much the transfer.

With ``--mesh`` (PR 44) the same four against the program that takes the
batch REPLICATED (PR 41's: a host argument of a sharded program goes
back to Python's ``shard_args``, a placement a shard on the calling
thread), built here from the engine's own pieces, beside the entries
that make fewer placements:

(e) ``one_call``: the engine's since PR 44 — ``ServingEngine._place_one``
    (one transfer to the mesh's first device; the other shards' blocks
    are zeros that lie there; one call of the runtime's batched
    placement), then the pinned program, whose first operation sums the
    blocks; halves ``one_call.place`` / ``one_call.call``;
(f) ``one_public_call``: the same array assembled by the public API
    (``jax.device_put`` to the first device, then
    ``jax.make_array_from_single_device_arrays``), the same program;
(g) ``rows_call``: the host array handed to a program that takes it
    SHARDED by rows (``B / S`` a shard) and all-gathers it first;
(h) ``batched_call``: the replicated program on an array placed by ONE
    call of the runtime's batched placement with the host array given
    ``S`` times (four transfers, none of Python's layers between them).

After the forms, one line a program: ``stream_us``, a call on a placed
argument made back to back 400 times and waited for once — where the
device is the longer leg (the mesh cell's shapes) that is the device's
time a batch, and the difference between programs what the entry's
collective costs it.

The engine thread's pattern: a NEW zeroed array every call (ids written
into it), the result handed to a second thread that reads it back
(``np.asarray``) and gives the slot back, two slots — so two batches are
in flight, and the interpreter is shared with a reader, as in a started
engine.  A time is the host's clock around the form alone (the wait for
a slot is outside it), microseconds, over ``--calls`` calls a form and
bucket (2,000), the forms taken in turn in four rounds, forward and
backward.  One JSON line a form and bucket (median, mean, 10th and 90th
percentile, and the quartiles' distance as a share of the median), then
the differences.

Exits 1 without a TPU: a CPU's times are not the chip's (``--rehearse``
runs a small catalog wherever it is, for the wiring; every line names
its device).
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import statistics as st
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BUCKETS = (8, 32, 128)
ROUNDS = 4
STREAM = 400


def timed(form, calls, staged):
    """``calls`` dispatches of ``form`` — ``x -> (what to read back, when
    its first half had ended or None)`` — on the engine thread's pattern;
    seconds a call, and the seconds of each call's first half."""
    slots = threading.Semaphore(2)
    handed = queue.SimpleQueue()

    def read_back():
        while (r := handed.get()) is not None:
            np.asarray(r)
            slots.release()

    reader = threading.Thread(target=read_back, name="readback")
    reader.start()
    whole, first = [], []
    try:
        for i in range(calls):
            slots.acquire()
            x = staged(i)
            t0 = time.perf_counter()
            r, t1 = form(x)
            whole.append(time.perf_counter() - t0)
            if t1 is not None:
                first.append(t1 - t0)
            handed.put(r)
    finally:
        handed.put(None)
        reader.join(60.0)
    return whole, first


def row(values):
    """Median, mean, 10th and 90th percentile, in microseconds, and the
    quartiles' distance over the median."""
    us = sorted(1e6 * v for v in values)
    q1, med, q3 = st.quantiles(us, n=4)
    return {"median_us": med, "mean_us": st.mean(us),
            "p10_us": us[len(us) // 10], "p90_us": us[(9 * len(us)) // 10],
            "spread": (q3 - q1) / med, "calls": len(us)}


def build_entry_variant(eng, idx, entry):
    """The mesh engine's int8 program with another entry for the staged
    batch, from the engine's own pieces: ``replicated`` — every shard is
    given the ``[B, rank + 2]`` batch whole, the parent's — or ``rows`` —
    ``B / S`` rows a shard, gathered first."""
    import jax

    from tpu_als.parallel.mesh import AXIS, shard_map
    from tpu_als.serving import engine as E
    from tpu_als.serving.index import _shard_merge, shortlist_rescore

    P = jax.sharding.PartitionSpec
    k_loc, sk_loc = idx.shard_widths(eng.k)

    def serve(U, packed, Vq, sv, V, valid, last_id):
        me = jax.lax.axis_index(AXIS)
        if entry == "rows":
            packed = jax.lax.all_gather(packed, AXIS, tiled=True)
        Ub = E._mesh_lookup(U, packed, me=me, axis=AXIS)
        s, gids = shortlist_rescore(Ub, Vq, sv, V, valid, k=k_loc,
                                    shortlist_k=sk_loc,
                                    shard=(me, idx.ni_loc))
        return E._pack_response(
            *_shard_merge(s, gids, last_id, axis=AXIS, k=eng.k))

    serve.__name__ = "serve_mesh_int8_" + entry
    return jax.jit(shard_map(
        serve, mesh=eng.mesh,
        in_specs=(P(AXIS), P() if entry == "replicated" else P(AXIS),
                  P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(), check_vma=False))


def halves(place, call):
    """A form in two runtime calls, its first half's end noted."""
    def form(x):
        placed = place(x)
        t1 = time.perf_counter()
        return call(placed), t1
    return form


def whole(call):
    return lambda x: (call(x), None)


def one_chip_forms(eng, m, idx, B, rank):
    """``{form: callable}``, ``{program: (executable, placed argument)}``
    and whether the forms agree bit for bit, for bucket ``B`` of a
    mesh-less engine."""
    import jax

    from tpu_als.serving import pins

    proto = eng._proto(B, rank)
    fn, call_args, _ = eng._int8_call(m, idx, proto)
    c = eng._pinned[(B, eng._int8_pin(idx))]
    loaded = pins.loads(pins.dumps(c), call_args)
    at = next(i for i, a in enumerate(call_args) if a is proto)
    head, tail = call_args[:at], call_args[at + 1:]

    def call(packed):
        return c(*head, packed, *tail)

    def loaded_call(packed):
        return loaded(*head, packed, *tail)

    x = np.zeros((B, rank + 2), np.int32)
    x[:, rank] = np.arange(B)
    want = np.asarray(call(x))
    same = all(np.array_equal(want, np.asarray(got)) for got in (
        call(jax.device_put(x)), loaded_call(x)))
    forms = {"put_call": halves(jax.device_put, call),
             "host_call": whole(call),
             "put": whole(jax.device_put),
             "placed_call": whole(lambda _, p=jax.device_put(x): call(p)),
             "loaded_call": whole(loaded_call)}
    return forms, {"jit_" + fn.__name__: (call, jax.device_put(x))}, same


def mesh_forms(eng, m, idx, B, rank):
    """The same for a mesh engine: the engine's entry beside the
    variants'."""
    import jax
    from jax._src.interpreters.pxla import batched_device_put

    from tpu_als.serving import pins

    devices = list(eng.mesh.devices.flat)
    S = len(devices)
    proto = eng._proto(B, rank)
    fn, call_args, _ = eng._int8_call(m, idx, proto)
    c = eng._pinned[(B, eng._int8_pin(idx))]
    loaded = pins.loads(pins.dumps(c), call_args)
    at = next(i for i, a in enumerate(call_args) if a is proto)
    head, tail = call_args[:at], call_args[at + 1:]
    rep_proto = jax.device_put(np.zeros((B, rank + 2), np.int32),
                               eng._replicated)
    rows_proto = jax.device_put(np.zeros((B, rank + 2), np.int32),
                                eng._by_rows)
    replicated = build_entry_variant(eng, idx, "replicated").lower(
        *head, rep_proto, *tail).compile()
    by_rows = build_entry_variant(eng, idx, "rows").lower(
        *head, rows_proto, *tail).compile()

    def one(packed):
        return c(*head, packed, *tail)

    def loaded_one(packed):
        return loaded(*head, packed, *tail)

    def rep(packed):
        return replicated(*head, packed, *tail)

    def rows(packed):
        return by_rows(*head, packed, *tail)

    zeros = [jax.device_put(np.zeros((B, rank + 2), np.int32), d)
             for d in devices[1:]]

    def place_public(x):
        return jax.make_array_from_single_device_arrays(
            (S * B, rank + 2), eng._by_rows,
            [jax.device_put(x, devices[0]), *zeros])

    aval = jax.core.ShapedArray((B, rank + 2), np.int32)

    def place_batched(x):
        return batched_device_put(aval, eng._replicated, [x] * S, devices)

    def put(x):
        return jax.device_put(x, eng._replicated)

    x = np.zeros((B, rank + 2), np.int32)
    x[:, rank] = np.arange(B) * (m.U.shape[0] // B)     # every shard's rows
    want = np.asarray(rep(x))
    same = all(np.array_equal(want, np.asarray(got)) for got in (
        one(eng._place_one(x)), one(place_public(x)), rows(x),
        rep(place_batched(x)), rep(put(x)), loaded_one(eng._place_one(x))))
    forms = {"put_call": halves(put, rep),
             "host_call": whole(rep),
             "put": whole(put),
             "placed_call": whole(lambda _, p=put(x): rep(p)),
             "one_call": halves(eng._place_one, one),
             "one_public_call": halves(place_public, one),
             "rows_call": whole(rows),
             "batched_call": halves(place_batched, rep),
             "loaded_one_call": halves(eng._place_one, loaded_one)}
    programs = {"jit_serve_mesh_int8_replicated": (rep, put(x)),
                "jit_" + fn.__name__: (one, eng._place_one(x)),
                "jit_serve_mesh_int8_rows": (
                    rows, jax.device_put(x, eng._by_rows))}
    return forms, programs, same


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0,
                    help="chips of the mesh engine (0: one chip, no mesh)")
    ap.add_argument("--calls", type=int, default=2000)
    ap.add_argument("--rehearse", action="store_true",
                    help="a small catalog, on whatever device there is")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "devices": jax.device_count()}
    if dev.platform != "tpu" and not args.rehearse:
        print("time_dispatch: no TPU", file=sys.stderr)
        return 1
    import tpu_als
    from benchmark.runners.serve import seeded_factors
    from benchmark.runners.serve_mesh import host_factors
    from tpu_als.serving.engine import ServingEngine

    name = ("amazon23-r256-host4of16" if args.mesh
            else "amazon23-r256-share32")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    users, items = config["num_users"], config["num_items"]
    rank, k = config["als"]["rank"], config["serving"]["k"]
    if args.rehearse:
        users, items = 4096, 8192
    t0 = time.perf_counter()
    U, V = (host_factors if args.mesh else seeded_factors)(
        users, items, rank, 41)
    eng = ServingEngine(k=k, buckets=BUCKETS,
                        mesh=(tpu_als.make_mesh(args.mesh)
                              if args.mesh else None))
    eng.publish(U, V)
    del U, V
    eng.warmup()
    print(json.dumps({"device": device, "config": name, "users": users,
                      "items": items, "rank": rank, "mesh": args.mesh,
                      "setup_s": time.perf_counter() - t0}), flush=True)

    m, idx = eng._model, eng._model.index
    rng = np.random.default_rng(41)
    for B in BUCKETS:
        forms, programs, same = (mesh_forms if args.mesh else
                                 one_chip_forms)(eng, m, idx, B, rank)
        ids = rng.integers(0, users, size=(args.calls, B), dtype=np.int32)

        def staged(i, B=B, ids=ids):
            x = np.zeros((B, rank + 2), dtype=np.int32)
            x[:, rank] = ids[i]
            return x

        for form in forms.values():     # every form once, outside the timing
            timed(form, 16, staged)
        per = args.calls // ROUNDS
        took = {name: ([], []) for name in forms}
        for r in range(ROUNDS):
            for name in (list(forms) if r % 2 == 0 else list(forms)[::-1]):
                w, f = timed(forms[name], per, staged)
                took[name][0].extend(w)
                took[name][1].extend(f)
        med = {}
        for name, (w, f) in took.items():
            med[name] = st.median(w) * 1e6
            print(json.dumps({"bucket": B, "form": name,
                              "bytes": 4 * B * (rank + 2), **row(w)}),
                  flush=True)
            if f:
                first = "put" if name == "put_call" else "place"
                print(json.dumps({"bucket": B, "form": f"{name}.{first}",
                                  **row(f)}), flush=True)
                print(json.dumps({
                    "bucket": B, "form": f"{name}.call",
                    **row([a - b for a, b in zip(w, f)])}), flush=True)
        for program, (call, placed) in programs.items():
            np.asarray(call(placed))
            t1 = time.perf_counter()
            for _ in range(STREAM):
                r = call(placed)
            np.asarray(r)
            print(json.dumps({
                "bucket": B, "program": program,
                "stream_us": 1e6 * (time.perf_counter() - t1) / STREAM}),
                flush=True)
        line = {"bucket": B, "same_answer": same,
                "device": device["platform"],
                "saving_us (a)-(b)": med["put_call"] - med["host_call"],
                "put_alone_us (c)": med["put"],
                "upload_inside_call_us (b)-(d)":
                    med["host_call"] - med["placed_call"]}
        ours, as_loaded = (("one_call", "loaded_one_call") if args.mesh
                           else ("host_call", "loaded_call"))
        line["loaded_minus_compiled_us"] = med[as_loaded] - med[ours]
        line["compiled_quartiles_us"] = (
            row(took[ours][0])["spread"] * med[ours])
        if args.mesh:
            line.update({
                f"saving_us (b)-{name}": med["host_call"] - med[name]
                for name in ("one_call", "one_public_call", "rows_call",
                             "batched_call")})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
