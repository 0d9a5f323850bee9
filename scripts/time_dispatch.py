#!/usr/bin/env python3
"""What the engine thread pays to hand a staged batch to the device, on
the chip's host: four forms of one batch's dispatch at the shapes of
``amazon23-r256-share32.serve-steady`` (``--mesh 4``: of
``amazon23-r256-host4of16.serve-steady-mesh``), bucket 8 and 32, against
the AOT executable ``ServingEngine.warmup()`` pinned.

    chiprun -- python3 scripts/time_dispatch.py
    chiprun --chips 4 -- python3 scripts/time_dispatch.py --mesh 4

(a) ``put_call``: ``jax.device_put`` of the staged array, then the call
    on its result — ``_dispatch`` until PR 41; its two halves are timed
    apart as well (``put_call.put``, ``put_call.call``);
(b) ``host_call``: the staged numpy array handed to the call, whose own
    argument handling places it — ``_dispatch`` since PR 41;
(c) ``put``: the ``device_put`` alone;
(d) ``placed_call``: the call on an argument that is on the device
    already (what a call costs with nothing to upload).

(a) − (b) is what a batch saves; (c) against (b) − (d) says how much of
a ``device_put`` is its Python path and how much the transfer (PERF.md
section 7, Unexplained (f)).

The engine thread's pattern: a NEW zeroed array every call (ids written
into it), the result handed to a second thread that reads it back
(``np.asarray``) and gives the slot back, two slots — so two batches are
in flight, and the interpreter is shared with a reader, as in a started
engine.  A time is the host's clock around the form alone (the wait for
a slot is outside it), microseconds, over ``--calls`` calls a form and
bucket (2,000), the forms taken in turn in four rounds, forward and
backward.  One JSON line a form and bucket, then the differences.

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

FORMS = ("put_call", "host_call", "put", "placed_call")
BUCKETS = (8, 32)
ROUNDS = 4


def timed(form, calls, c, head, tail, staged, placed, put):
    """``calls`` dispatches of ``form`` on the engine thread's pattern;
    seconds a call, and for ``put_call`` the seconds of its upload."""
    slots = threading.Semaphore(2)
    handed = queue.SimpleQueue()

    def read_back():
        while (r := handed.get()) is not None:
            np.asarray(r)
            slots.release()

    reader = threading.Thread(target=read_back, name="readback")
    reader.start()
    whole, upload = [], []
    try:
        for i in range(calls):
            slots.acquire()
            x = staged(i)
            t0 = time.perf_counter()
            if form == "put_call":
                packed = put(x)
                t1 = time.perf_counter()
                r = c(*head, packed, *tail)
                upload.append(t1 - t0)
            elif form == "host_call":
                r = c(*head, x, *tail)
            elif form == "put":
                r = put(x)
            else:
                r = c(*head, placed, *tail)
            whole.append(time.perf_counter() - t0)
            handed.put(r)
    finally:
        handed.put(None)
        reader.join(60.0)
    return whole, upload


def row(values):
    """Median, mean, 10th and 90th percentile, in microseconds."""
    us = sorted(1e6 * v for v in values)
    return {"median_us": st.median(us), "mean_us": st.mean(us),
            "p10_us": us[len(us) // 10], "p90_us": us[(9 * len(us)) // 10],
            "calls": len(us)}


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
    eng = ServingEngine(k=k, mesh=(tpu_als.make_mesh(args.mesh)
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
        proto = eng._proto(B, rank)
        fn, call_args, _ = eng._int8_call(m, idx, proto)
        c = eng._pinned[(B, eng._int8_pin(idx))]
        at = next(i for i, a in enumerate(call_args) if a is proto)
        head, tail = call_args[:at], call_args[at + 1:]
        ids = rng.integers(0, users, size=(args.calls, B), dtype=np.int32)

        def staged(i, B=B, ids=ids):
            x = np.zeros((B, rank + 2), dtype=np.int32)
            x[:, rank] = ids[i]
            return x

        def put(x):
            return jax.device_put(x, eng._replicated)

        # the two ways to the device give the same answer, bit for bit
        x = staged(0)
        same = bool(np.array_equal(np.asarray(c(*head, put(x), *tail)),
                                   np.asarray(c(*head, x, *tail))))
        placed = put(staged(1))
        for form in FORMS:       # every form once, outside the timing
            timed(form, 16, c, head, tail, staged, placed, put)
        per = args.calls // ROUNDS
        whole = {form: [] for form in FORMS}
        upload = []
        for r in range(ROUNDS):
            for form in (FORMS if r % 2 == 0 else FORMS[::-1]):
                w, u = timed(form, per, c, head, tail, staged, placed, put)
                whole[form] += w
                upload += u
        med = {}
        for form in FORMS:
            med[form] = st.median(whole[form]) * 1e6
            print(json.dumps({"bucket": B, "form": form,
                              "program": "jit_" + fn.__name__,
                              "bytes": 4 * B * (rank + 2),
                              **row(whole[form])}), flush=True)
        call = [w - u for w, u in zip(whole["put_call"], upload)]
        print(json.dumps({"bucket": B, "form": "put_call.put",
                          **row(upload)}), flush=True)
        print(json.dumps({"bucket": B, "form": "put_call.call",
                          **row(call)}), flush=True)
        print(json.dumps({
            "bucket": B, "same_answer": same, "device": device["platform"],
            "saving_us (a)-(b)": med["put_call"] - med["host_call"],
            "put_alone_us (c)": med["put"],
            "upload_inside_call_us (b)-(d)":
                med["host_call"] - med["placed_call"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
