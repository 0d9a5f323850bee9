"""``kind: serve_mesh_unseen`` — ``serve_unseen``'s open loop (users asking
in proportion to what they have rated; 90 % by id, the resident history
excluded; 10 % by vector with a list of 64) against a ``ServingEngine``
that is given a mesh of the cell's chips AND its users' rating histories:
catalog, int8 rows and user table sharded by rows over the chips as in
``serve_mesh``, the histories sharded with the user table, every batch
scored by all the chips, each masking the ids it owns.

Nothing of the request side is this runner's own: ``make_requests``,
``open_stream``, ``Asker``, ``ask_longest`` and the whole of ``correct``
(``answer_checks``: guarantee (1) over EVERY answer, recall and scores on
a seeded sample and on the longest histories, against
``reference/topk_unseen.py`` — float64, blocked over catalog rows, logical
ids, nothing of shards) are ``runners/serve_unseen.py``'s, the item
factors and the chips' memory lines ``runners/serve_mesh.py``'s.  What is:

- the set-up.  142.9 M ratings are eight times the one-chip share's, and
  the 12.3 GB catalog fits no chip: histories and planted user factors
  are made by parts of the user table, in parallel, on the host
  (``benchmark/histories_by_shard.py``), and the ``setup`` line carries
  the phases (``histories_s``, ``item_factors_s``, ``planted_s``,
  ``publish_s``, ``warmup_s``, ``warm_batches_s``);
- the counters the mesh adds to a batch that excludes:
  ``serving.mesh_exchange_bytes`` and ``serving.mesh_history_bytes`` over
  the window, and ONE chip's score columns for ``serve_score_hbm_pct``.

On a program whose mesh engine takes no histories (every tree before PR
52) the run ends at once with the engine's own ``NotImplementedError``,
before any factor is drawn (:func:`refused_at_once`).
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import datagen, histories_by_shard
from benchmark.harness import Outcome, at_most
from benchmark.runners import serve_unseen as su
from benchmark.runners.serve_mesh import device_peaks, host_factors

COUNTERS = ("serving.exclusion_upload_bytes", "serving.mesh_exchange_bytes",
            "serving.mesh_history_bytes")


def refused_at_once(engine, rank, k):
    """Where the program declares no counter for what the histories move
    between chips (``serving.mesh_history_bytes`` in its metric
    vocabulary), its mesh engine has no histories either: ask it, with
    tables of one user and ``k`` items, and let its refusal end the run
    (``publish`` raises before it reads an argument there).  A program
    that declares it is asked nothing."""
    from tpu_als import obs

    if COUNTERS[2] not in obs.schema.METRICS:
        engine.publish(np.zeros((1, rank), np.float32),
                       np.ones((k, rank), np.float32),
                       user_seen=(np.zeros(2, np.int64),
                                  np.empty(0, np.int32)))


def start_engine(cell):
    """(asker over the engine started and warm, U, V, the histories,
    seconds by phase)."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    import jax

    from tpu_als import make_mesh
    from tpu_als.serving.engine import ServingEngine

    cfg, mix = cell.config, cell.traffic
    rank, k = cfg["als"]["rank"], cfg["serving"]["k"]
    engine = ServingEngine(k=k, mesh=make_mesh(cell.chips))
    refused_at_once(engine, rank, k)
    phases = {"import_s": lap()}
    indptr, indices, stars = histories_by_shard.seeded_histories(
        cfg, cell.seed)
    phases["histories_s"] = lap()
    _, V = host_factors(1, cfg["num_items"], rank, cell.seed)
    phases["item_factors_s"] = lap()
    U = histories_by_shard.planted_user_factors(indptr, indices, stars, V)
    del stars
    phases["planted_s"] = lap()
    rule = mix.get("rule", True)
    engine.publish(U, V, user_seen=(indptr, indices) if rule else None)
    jax.block_until_ready(engine.published_index.Vq)
    phases["publish_s"] = lap()
    cell.say("memory", after="publish", **device_peaks())
    engine.warmup()
    engine.start()
    phases["warmup_s"] = lap()
    hist = (indptr, indices)
    asker = su.Asker(engine, rule)
    # each program's first execution under the engine's own threads, before
    # the stream (runners/serve.py)
    rng = datagen.rng_for(cell.seed, 4)
    for n in mix["warm_batches"]:
        tickets = [asker.submit(p)
                   for p in su.make_requests(rng, U, hist, mix, n)[0]]
        for t in tickets:
            t.result(timeout=120.0)
    phases["warm_batches_s"] = lap()
    return asker, U, V, hist, phases


def run(cell):
    import jax

    from tpu_als import obs

    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    asker, U, V, hist, phases = start_engine(cell)
    cell.say("setup", process_to_runner_s=t_start - cell.t_process, **phases)
    rng = datagen.rng_for(cell.seed, 2)
    read = {}

    def counters():
        return {name: obs.counter_value(name) or 0 for name in COUNTERS}

    try:
        loop, marks, users = su.open_stream(asker, U, hist, mix, rng,
                                            cell.seconds, k,
                                            clock=cell.clock)
        at_head = loop.at_head

        def window_opens():
            at_head()
            read["head"] = counters()

        loop.at_head = window_opens
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(phases.values()), head=loop.head)
        loop.run()
        end = counters()
        moved = {name: end[name] - read["head"][name] for name in COUNTERS}
        in_window = cell.clock.since(marks["compile"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        cell.say("memory", after="window", **device_peaks())
        longest = su.ask_longest(asker, hist, mix, k)
        trace_dir, traced = None, None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _, t_users = su.open_stream(asker, U, hist, mix, rng,
                                                mix["trace_seconds"], k)
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                traced.run()
            finally:
                jax.profiler.stop_trace()
        index = asker.engine.published_index
        shard_columns = None if index is None else int(index.ni_loc)
        pads = mix["history_pads"] if asker.engine.holds_histories else []
    finally:
        asker.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    failed = loop.n - len(loop.answered())
    lengths = np.diff(hist[0])[users[loop.head:]]
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat), failed=failed, errors=sorted(
                 collections.Counter(e for j, e in loop.errors.items()
                                     if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window,
             exclusion_upload_bytes=moved[COUNTERS[0]],
             mesh_exchange_bytes=moved[COUNTERS[1]],
             mesh_history_bytes=moved[COUNTERS[2]],
             history_ids={q: float(np.percentile(lengths, q))
                          for q in (10, 50, 90, 99, 100)},
             history_pads=pads, shard_columns=shard_columns,
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    t0 = time.perf_counter()
    checks, found = su.answer_checks(loop, users, longest, U, V, hist, cfg,
                                     mix, cell.seed)
    cell.say("reference", seconds=time.perf_counter() - t0,
             requests=mix["check_requests"], longest=mix["check_longest"],
             by_id_with_seen_share=found.get("by_id_with_seen_share"))
    checks.append(at_most("compilations_in_window",
                          in_window["compilations"], 0))
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    if traced is not None:      # nothing of the traced window is timed,
        failed += traced.n - len(traced.answered())   # but a failure counts
    p90 = obs.histogram_quantile("serving.excluded_ids", 0.9,
                                 source="history")
    return Outcome(
        metrics=metrics, attempted=loop.n + (traced.n if traced else 0),
        failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  # the trace holds the traced stream's head too
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "excluded_ids_p90": None if np.isnan(p90) else p90,
                  "exclusion_upload_bytes": moved[COUNTERS[0]],
                  "mesh_exchange_bytes": moved[COUNTERS[1]],
                  "mesh_history_bytes": moved[COUNTERS[2]],
                  "window_batches": loop.batches(),
                  # ONE chip's columns: every chip scores its own slice
                  # for every batch, and the busy time is a chip's mean
                  "score_columns": shard_columns,
                  "rank": cfg["als"]["rank"],
                  "history_pads": pads,
                  # what the traced stream's batches were to exclude
                  "excluded_ids_per_batch": None if not traced else sum(
                      len(su.excluded_of(p, u, hist))
                      for p, u in zip(traced.payloads, t_users))
                  / max(traced.batches(head_too=True), 1)},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "users": users, "U": U, "V": V,
                   "hist": hist, "longest": longest, **found})
