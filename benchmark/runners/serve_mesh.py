"""``kind: serve_mesh`` — ``serve``'s open loop against a ``ServingEngine``
that is given a mesh of the cell's chips: catalog, int8 rows and user table
sharded by rows over them, every batch scored by all of them.

The request side is ``runners/serve.py``'s own (imported, nothing of it
changed): ``make_requests``, ``open_stream``, ``OpenLoop``, one Poisson
stream of a head and a window, a traced second stream in a ``--trace 1``
run.  What is this runner's own: the factors are drawn on the host, part
by part from the seed (26 GB: no chip holds a factor before ``publish``
gives it its shard), and the engine is built with
``mesh=make_mesh(chips)`` and driven through the entry points every user of
the package has (``publish``, ``warmup``, ``start``, ``submit``).

``correct``, once the window has closed, on a seeded sample of the answered
requests, no client twice (``serve.sampled_queries``), against
``reference/topk_blocked.py``: every returned score is the float64 dot
product of its returned id (relative to the largest score), scores descend,
recall@k against the float64 exact top-k over ALL the shards' items is at
or above the configuration's floor, ids lie in the catalog, and nothing
compiled in the window.  (``serve.compare_answers`` is not used: it turns
the whole catalog into float64, 24.7 GB here, for the rows it was served.)

Every chip's ``peak_bytes_in_use`` is printed after ``publish`` and after
the window, and the window's share of the counter
``serving.mesh_exchange_bytes`` goes to the layer readers.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import datagen
from benchmark.harness import Outcome, at_least, at_most
from benchmark.reference import topk_blocked as ref
from benchmark.runners.serve import make_requests, open_stream, sampled_queries


FACTOR_PARTS = 32     # fixed: the seed's values do not depend on the host


def host_factors(n_users, n_items, rank, seed):
    """U ~ N(0, 1), V ~ N(0, 1/rank), float32, drawn ON THE HOST, part by
    part: each of ``FACTOR_PARTS`` row ranges of a table from its own
    child of the seed's ``SeedSequence``, one thread a part (numpy draws
    without the interpreter's lock), straight into the table.  No chip
    ever holds a factor it was not given by ``publish``: drawn on the
    chips, 26 GB would come back over four links and then be copied once
    more on the host, a page fault at a time (3.3 s a quarter table)."""
    import threading

    def table(which, n, scale):
        out = np.empty((n, rank), np.float32)
        bounds = np.linspace(0, n, FACTOR_PARTS + 1).astype(np.int64)
        seeds = np.random.SeedSequence([int(seed), which]).spawn(
            FACTOR_PARTS)

        def draw(i):
            part = out[bounds[i]:bounds[i + 1]]
            np.random.default_rng(seeds[i]).standard_normal(
                out=part, dtype=np.float32)
            if scale != 1.0:
                part *= np.float32(scale)

        threads = [threading.Thread(target=draw, args=(i,))
                   for i in range(FACTOR_PARTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    return table(0, n_users, 1.0), table(1, n_items, 1.0 / np.sqrt(rank))


def device_peaks():
    """``peak_bytes_in_use`` and ``bytes_in_use`` of every chip, in the
    order of ``jax.local_devices()``."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {"peak_bytes_in_use": [int(s.get("peak_bytes_in_use", 0))
                                  for s in stats],
            "bytes_in_use": [int(s.get("bytes_in_use", 0)) for s in stats]}


def start_engine(cell):
    """(engine started and warm, U, V, seconds by phase) for the seed's
    factors, on a mesh of the cell's chips."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    import jax

    from tpu_als import make_mesh
    from tpu_als.serving.engine import ServingEngine

    cfg, mix = cell.config, cell.traffic
    phases = {"import_s": lap()}
    U, V = host_factors(cfg["num_users"], cfg["num_items"],
                        cfg["als"]["rank"], cell.seed)
    phases["factors_s"] = lap()
    engine = ServingEngine(k=cfg["serving"]["k"],
                           mesh=make_mesh(cell.chips))
    engine.publish(U, V)
    jax.block_until_ready(engine.published_index.Vq)
    phases["publish_s"] = lap()
    cell.say("memory", after="publish", **device_peaks())
    engine.warmup()
    engine.start()
    phases["warmup_s"] = lap()
    # each program's first execution, before the stream (runners/serve.py)
    rng = datagen.rng_for(cell.seed, 4)
    for n in mix["warm_batches"]:
        tickets = [engine.submit(p) for p in make_requests(rng, U, mix, n)]
        for t in tickets:
            t.result(timeout=120.0)
    phases["warm_batches_s"] = lap()
    return engine, U, V, phases


def compare_answers(scores, ids, Q, V, k, lim, exact=None):
    """``serve.compare_answers``'s four checks, from the blocked
    reference; ``exact`` takes the float64 top-k of ``Q`` where the caller
    has it already."""
    ref_s, ref_i = exact if exact is not None else ref.exact_topk(Q, V, k)
    largest = float(np.abs(ref_s).max())
    in_catalog = (ids >= 0) & (ids < len(V))
    own = ref.own_scores(Q, V, np.where(in_catalog, ids, 0))
    score_err = float(np.abs(scores - own).max()) / largest
    unsorted = float(np.maximum(np.diff(scores, axis=1), 0).max()) / largest
    return [
        at_most("score_rel_err", score_err, lim["score_rel_err"]),
        at_most("scores_ascending_by", unsorted, lim["score_rel_err"]),
        at_least("recall_at_k", ref.recall(ids, ref_i), lim["recall_at_k"]),
        at_least("ids_in_catalog", float(in_catalog.all()), 1.0),
    ]


def answer_checks(loop, U, V, config, mix, seed):
    if not len(loop.answered()):
        return [at_least("answered_requests", 0, 1)]
    sample, Q = sampled_queries(loop, U, mix, seed)
    return compare_answers(loop.scores[sample].astype(np.float64),
                           loop.ids[sample], Q, V, config["serving"]["k"],
                           config["correct"])


def run(cell):
    import jax

    from tpu_als import obs

    cfg, mix = cell.config, cell.traffic
    t_start = time.perf_counter()
    engine, U, V, phases = start_engine(cell)
    cell.say("setup", process_to_runner_s=t_start - cell.t_process, **phases)
    rng = datagen.rng_for(cell.seed, 2)
    exchanged = {}

    def moved():
        return obs.counter_value("serving.mesh_exchange_bytes") or 0

    try:
        loop, marks = open_stream(engine, U, mix, rng, cell.seconds,
                                  cfg["serving"]["k"], clock=cell.clock)
        at_head = loop.at_head

        def window_opens():
            at_head()
            exchanged["head"] = moved()

        loop.at_head = window_opens
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(phases.values()), head=loop.head)
        loop.run()
        exchanged["end"] = moved()
        in_window = cell.clock.since(marks["compile"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        cell.say("memory", after="window", **device_peaks())
        trace_dir, traced = None, None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _ = open_stream(engine, U, mix, rng,
                                    mix["trace_seconds"], cfg["serving"]["k"])
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                traced.run()
            finally:
                jax.profiler.stop_trace()
    finally:
        engine.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    failed = loop.n - len(loop.answered())
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat), failed=failed, errors=sorted(
                 collections.Counter(e for j, e in loop.errors.items()
                                     if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window,
             mesh_exchange_bytes=exchanged["end"] - exchanged["head"],
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    t0 = time.perf_counter()
    checks = answer_checks(loop, U, V, cfg, mix, cell.seed)
    cell.say("reference", seconds=time.perf_counter() - t0,
             requests=mix["check_requests"])
    checks.append(at_most("compilations_in_window",
                          in_window["compilations"], 0))
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    if traced is not None:      # nothing of the traced window is timed,
        failed += traced.n - len(traced.answered())   # but a failure counts
    return Outcome(
        metrics=metrics, attempted=loop.n + (traced.n if traced else 0),
        failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  # the trace holds the traced stream's head too
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "mesh_exchange_bytes": exchanged["end"] - exchanged["head"],
                  "window_batches": loop.batches()},
        trace_dir=trace_dir, artifacts={"loop": loop, "U": U, "V": V})
