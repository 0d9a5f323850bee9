"""``kind: serve`` — an open loop against ``ServingEngine``.

Factors come from the seed (no fit: serving time does not depend on how
the factors were made).  ``publish``, ``warmup``, ``start``, one batch of
each size in the mix's ``warm_batches`` (each program's first execution),
then ONE stream of Poisson arrivals at the rate fixed in the traffic file: a warm-up
head of ``warmup_seconds`` and, without a gap, the window.  The head is
set-up and nothing of it is timed; it is there so that the window opens on
an engine in its steady state (queue, batch sizes) and not on an empty one
(``head_burst`` of its requests may come due at once, at its start).
Head and window each hold a FIXED number of requests (the seed moves their
order, ids and gaps, not the amount of work).  One thread
(the caller's) hands each request to ``submit`` when it is due; one waiter
thread takes the answers in order and stamps them.  A request's latency
runs from the instant it was DUE to its answer in hand; the end-to-end
metrics are percentiles of ALL requests of the window (which, and why not
the 95th or the 99th: PERF.md section 2).  A shed
(``Overloaded``), failed or unanswered request counts in ``failed``.
A ``--trace 1`` run measures the same whole window with the profiler off —
its host-clock layer metrics, the 99th percentile and the longest latency
among them, are the whole window's — and then traces a second, short
window for the device's numbers.

``correct``, once the window has closed, on a seeded sample of the answered
requests, no client twice: every returned score is the float64 dot product
of its returned id (relative to the largest score), scores descend, and
recall@k against the float64 exact top-k is at or above the
configuration's floor.
"""

from __future__ import annotations

import collections
import gc
import threading
import time

import numpy as np

from benchmark import datagen
from benchmark.harness import Outcome, at_least, at_most
from benchmark.reference import topk as ref


def seeded_factors(n_users, n_items, rank, seed):
    """U ~ N(0, 1), V ~ N(0, 1/rank), float32, made on the device in one
    jitted call and read back once (``publish`` wants V on the host)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        ku, kv = jax.random.split(key)
        U = jax.random.normal(ku, (n_users, rank), jnp.float32)
        V = jax.random.normal(kv, (n_items, rank), jnp.float32)
        return U, V / jnp.sqrt(jnp.float32(rank))

    U, V = make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return np.asarray(U), np.asarray(V)


def make_requests(rng, U, mix, n):
    """``n`` payloads: a share by user id (zipf: a few heavy clients), the
    rest by rank-length vector (a user folded in since the last publish)."""
    n_users = U.shape[0]
    by_vector = rng.random(n) < mix["vector_share"]
    relabel = rng.permutation(n_users)
    ids = relabel[rng.choice(n_users, size=n,
                             p=datagen.zipf_weights(n_users, mix["zipf_s"]))]
    noise = 0.01 * rng.standard_normal((n, U.shape[1]), dtype=np.float32)
    payloads = []
    for j in range(n):
        if by_vector[j]:
            payloads.append(U[ids[j]] + noise[j])
        else:
            payloads.append(int(ids[j]))
    return payloads


class GcClock:
    """Seconds the cyclic collector held the interpreter, by generation
    (``gc.callbacks``): a stall of the generator or the engine thread that
    is the collector's shows here."""

    def __init__(self):
        self.pauses = []          # (generation, seconds)
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self):
        return {"collections": len(self.pauses),
                "total_ms": 1e3 * sum(s for _, s in self.pauses),
                "max_ms": 1e3 * max([s for _, s in self.pauses] or [0.0])}


class OpenLoop:
    """Submits ``payloads`` at ``due`` (seconds from start).  One waiter
    thread takes the answers in order, stamps them, copies scores and ids
    into preallocated arrays and lets the ticket go: nothing per request
    outlives its answer, so the collector's work does not grow with the
    window.  The first ``head`` requests are the warm-up: they are offered
    like the rest and left out of everything the loop reports
    (``at_head`` is called as the first request after them comes due)."""

    def __init__(self, engine, payloads, due, answer_timeout_s, k, head=0,
                 at_head=None):
        self.engine, self.payloads, self.due = engine, payloads, due
        self.answer_timeout_s = answer_timeout_s
        self.n_all = n = len(payloads)
        self.head, self.at_head = head, at_head
        self.n = n - head                      # requests of the window
        self.measured = np.arange(n) >= head
        self.t_submit = np.full(n, np.nan)     # handed to submit()
        self.t_queued = np.full(n, np.nan)     # Ticket.t_submit
        self.t_dequeue = np.full(n, np.nan)    # Ticket.t_dequeue
        self.t_done = np.full(n, np.nan)       # answer in hand
        self.scores = np.zeros((n, k), np.float32)
        self.ids = np.full((n, k), -1, np.int64)
        self.errors = {}
        self._handed = collections.deque()
        self._more = threading.Semaphore(0)

    def _wait(self):
        import jax

        for _ in range(self.n_all):
            self._more.acquire()
            j, ticket = self._handed.popleft()
            if ticket is None:
                continue
            try:
                with jax.profiler.TraceAnnotation("bench.wait_answer"):
                    scores, ids = ticket.result(timeout=self.answer_timeout_s)
                self.t_done[j] = time.perf_counter()
                self.scores[j, :len(scores)] = scores
                self.ids[j, :len(ids)] = ids
                self.t_queued[j] = ticket.t_submit
                self.t_dequeue[j] = ticket.t_dequeue
            except Exception as e:   # noqa: BLE001 — every failure is counted
                self.errors[j] = type(e).__name__

    def run(self):
        # the generator's own payloads are long-lived and, to the engine,
        # another machine's: keep the cyclic collector from walking them
        # inside the window (PERF.md section 2)
        gc.collect()
        gc.freeze()
        try:
            self._drive()
        finally:
            gc.unfreeze()
        return self

    def _drive(self):
        import jax

        waiter = threading.Thread(target=self._wait, name="bench-waiter")
        with GcClock() as self.gc_clock:
            waiter.start()
            self.t0 = t0 = time.perf_counter()
            for j in range(self.n_all):
                if j == self.head and self.at_head is not None:
                    self.at_head()
                wait = t0 + self.due[j] - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.wait_due"):
                        time.sleep(wait)
                ticket = None
                try:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        self.t_submit[j] = time.perf_counter()
                        ticket = self.engine.submit(self.payloads[j])
                except Exception as e:   # noqa: BLE001 — Overloaded is a shed
                    self.errors[j] = type(e).__name__
                self._handed.append((j, ticket))
                self._more.release()
            self.t_last_submit = time.perf_counter()
            waiter.join()
            self.t_end = time.perf_counter()

    # -- what the window showed ------------------------------------------
    def answered(self, head_too=False):
        return np.flatnonzero(~np.isnan(self.t_done)
                              & (self.measured | head_too))

    def latency_ms(self):
        a = self.answered()
        return (self.t_done[a] - (self.t0 + self.due[a])) * 1e3

    def late_ms(self):
        ok = ~np.isnan(self.t_submit) & self.measured
        return (self.t_submit[ok] - (self.t0 + self.due[ok])) * 1e3

    def queue_ms(self):
        a = self.answered()
        return (self.t_dequeue[a] - self.t_queued[a]) * 1e3

    def batches(self, head_too=False):
        return len(np.unique(self.t_dequeue[self.answered(head_too)]))

    def batch_sizes(self, buckets=(8, 32, 128)):
        """How many of the window's batches held up to 8, 32, 128 of its
        requests (requests dequeued at the same instant are one batch)."""
        _, sizes = np.unique(self.t_dequeue[self.answered()],
                             return_counts=True)
        edges = np.searchsorted(buckets, sizes)
        return {str(b): int((edges == i).sum())
                for i, b in enumerate(buckets)}

    def slowest(self, n=5):
        """The slowest answers: (seconds into the window when due, ms)."""
        a = self.answered()
        lat = self.latency_ms()
        worst = np.argsort(-lat)[:n]
        return [[float(self.due[a[w]]), float(lat[w])] for w in worst]


def start_engine(config, mix, seed):
    """(engine started and warm, U, V, seconds by phase) for the seed's
    factors."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    from tpu_als.serving.engine import ServingEngine

    phases = {"import_s": lap()}
    U, V = seeded_factors(config["num_users"], config["num_items"],
                          config["als"]["rank"], seed)
    phases["factors_s"] = lap()
    engine = ServingEngine(k=config["serving"]["k"])
    engine.publish(U, V)
    phases["publish_s"] = lap()
    engine.warmup()
    engine.start()
    phases["warmup_s"] = lap()
    # ``warmup`` compiles the engine's programs and runs none: each one's
    # first execution then took up to 2 s under traffic, in the head or the
    # window (PERF.md section 6).  So every batch size the mix names is
    # submitted at once and answered before the stream starts
    rng = datagen.rng_for(seed, 4)
    for n in mix["warm_batches"]:
        tickets = [engine.submit(p) for p in make_requests(rng, U, mix, n)]
        for t in tickets:
            t.result(timeout=120.0)
    phases["warm_batches_s"] = lap()
    return engine, U, V, phases


def open_stream(engine, U, mix, rng, seconds, k, clock=None):
    """(loop, marks) of one arrival stream: ``warmup_seconds`` of head, then
    ``seconds`` of window, which opens ``warmup_seconds`` after the loop's
    ``t0``.  Given a compile clock, ``marks`` takes its reading as the
    head's last request has been handed over."""
    rate, warm_s = mix["rate_per_s"], mix["warmup_seconds"]
    head_due = datagen.poisson_arrivals(rng, rate, warm_s)
    # a burst opens the head where the mix asks for one: that many of the
    # head's requests come due at once, as after a client's or a host's
    # stall, so that an engine whose steady state depends on its queue's
    # history (PERF.md section 5) is in the lasting one when the window opens
    head_due[:mix["head_burst"]] = 0.0
    due = np.concatenate(
        [head_due, warm_s + datagen.poisson_arrivals(rng, rate, seconds)])
    marks = {}

    def at_head():
        if clock is not None:
            marks["compile"] = clock.now()

    return OpenLoop(engine, make_requests(rng, U, mix, len(due)), due,
                    mix["answer_timeout_s"], k, head=len(head_due),
                    at_head=at_head), marks


def sampled_queries(loop, U, mix, seed):
    """(request numbers, query vectors) of a seeded sample of the answered
    requests, no client twice: the zipf mix sends 40 % of a plain sample
    from a few heavy clients, whose few misses then swing the recall from
    seed to seed (0.981-0.997 where distinct queries read 0.994-0.995)."""
    order = datagen.rng_for(seed, 3).permutation(loop.answered())
    client = np.array([p if isinstance(p, int) else -1 - j
                       for j, p in ((j, loop.payloads[j]) for j in order)])
    _, first = np.unique(client, return_index=True)
    sample = order[np.sort(first)[:mix["check_requests"]]]
    Q = np.stack([U[p] if isinstance(p, int) else p
                  for p in (loop.payloads[j] for j in sample)])
    return sample, Q


def answer_checks(loop, U, V, config, mix, seed):
    """Compare a seeded sample of the answered requests with the float64
    reference."""
    if not len(loop.answered()):
        return [at_least("answered_requests", 0, 1)]
    sample, Q = sampled_queries(loop, U, mix, seed)
    return compare_answers(loop.scores[sample].astype(np.float64),
                           loop.ids[sample], Q, V, config["serving"]["k"],
                           config["correct"])


def compare_answers(scores, ids, Q, V, k, lim, exact=None):
    """The checks of one set of answers; ``exact`` takes the float64 top-k
    of ``Q`` where the caller has it already."""
    ref_s, ref_i = exact if exact is not None else ref.exact_topk(Q, V, k)
    largest = float(np.abs(ref_s).max())
    own = ref.own_scores(Q, V, ids)
    score_err = float(np.abs(scores - own).max()) / largest
    unsorted = float(np.maximum(np.diff(scores, axis=1), 0).max()) / largest
    return [
        at_most("score_rel_err", score_err, lim["score_rel_err"]),
        at_most("scores_ascending_by", unsorted, lim["score_rel_err"]),
        at_least("recall_at_k", ref.recall(ids, ref_i), lim["recall_at_k"]),
        at_least("ids_in_catalog", float(((ids >= 0) & (ids < len(V))).all()),
                 1.0),
    ]


def run(cell):
    import jax

    cfg, mix = cell.config, cell.traffic
    t_start = time.perf_counter()
    engine, U, V, phases = start_engine(cfg, mix, cell.seed)
    cell.say("setup", process_to_runner_s=t_start - cell.t_process, **phases)
    rng = datagen.rng_for(cell.seed, 2)
    try:
        loop, marks = open_stream(engine, U, mix, rng, cell.seconds,
                                  cfg["serving"]["k"], clock=cell.clock)
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(phases.values()), head=loop.head)
        loop.run()
        in_window = cell.clock.since(marks["compile"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
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
        # every percentile a manifest may name as an end-to-end metric; the
        # harness prints the ones the cell's manifest declares
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
                  else None},
        trace_dir=trace_dir, artifacts={"loop": loop, "U": U, "V": V})
