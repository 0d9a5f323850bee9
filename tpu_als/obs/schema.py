"""The declared observability vocabulary — every metric and event type.

The registry (tpu_als.obs.metrics) validates names against these tables at
call time, and ``scripts/check_obs_schema.py`` validates every *call site*
statically, so an undeclared name fails a tier-1 test instead of silently
minting a new time series nothing downstream knows how to read (the
Codahale-metrics discipline the reference stack gets from its fixed
MetricsSystem source names — SURVEY.md §5.5).

Adding a metric or event type = add a row here + a row in the matching
table of docs/observability.md.

Labels are vocabulary too: ``LABELS`` declares which label keys each
metric's writers may attach, and the registry rejects any other key at
call time — an ad-hoc label would mint a series dimension nothing
downstream (the Prometheus exposition, `observe summarize`, the bench
judges) knows how to aggregate.  The ``tenant`` label is the multi-
tenant attribution contract: every ``serving.*``/``live.*`` series
carries it (``TENANT_LABELED`` is derived, so adding a serving metric
without deciding its tenant story is impossible — the static check in
``analysis/vocab.py`` pins exactly that).
"""

from __future__ import annotations

# metric name -> (kind, unit, help text).  kind in {counter, gauge,
# histogram}; a name used with a different kind than declared raises.
METRICS = {
    "train.comm_bytes_per_iter": (
        "gauge", "bytes",
        "modeled per-device collective traffic of one ALS iteration "
        "(trainer.comm_bytes_per_iter, labeled by effective strategy)"),
    "train.gather_block_rows": (
        "gauge", "rows",
        "rows per column block of the chunked all_gather schedule "
        "(comm.gather_block_plan; bounds the resident gathered slice)"),
    "serve.request_seconds": (
        "histogram", "seconds",
        "wall-clock latency of one sharded top-k request "
        "(parallel.serve.topk_sharded), labeled by strategy"),
    "serve.requests": (
        "counter", "requests", "sharded top-k requests served"),
    "serve.rows": (
        "counter", "rows", "query rows scored by sharded top-k"),
    "ingest.rows": (
        "counter", "rows", "rating rows parsed by stream_ingest"),
    "ingest.bytes": (
        "counter", "bytes", "file bytes read by stream_ingest"),
    "ingest.stall_seconds": (
        "counter", "seconds",
        "time stream_ingest spent blocked in file reads (I/O stall, "
        "as opposed to parse/intern time)"),
    "foldin.ratings": (
        "counter", "rows",
        "ratings that entered a FoldInServer fold for the first time, one "
        "per rating and side folded: a rating folded into its user AND "
        "into its item (update and update_items) counts twice; a rating "
        "held for a side without a factor counts when it enters"),
    "foldin.ids_mapped": (
        "counter", "ids",
        "ids of a fold's fixed side sent through IdMap.to_dense, by side "
        "folded (user | item): the events' own, once, and what a touched "
        "entity's history still held WITHOUT a table row where the map "
        "has grown since it last looked — never a history's known ids "
        "(stream.microbatch._Ratings keeps those as rows); the "
        "live.batch.foldin.map span's ``mapped`` beside its ``ratings``"),
    "foldin.yty_rows": (
        "counter", "rows",
        "table rows that entered an update of the Gram matrix an implicit "
        "FoldInServer keeps of a fixed table (G + new^T new - old^T old "
        "inside the row write, core.foldin._scatter_rows_yty), by the "
        "table's side (user: U^T U, moved by a user fold's write-back | "
        "item: V^T V, by an item fold's): O(touched rows) a batch"),
    "foldin.yty_full": (
        "counter", "programs",
        "whole-table Gram programs (core.foldin.whole_yty, O(table)) an "
        "implicit FoldInServer ran, by the table's side (user | item) and "
        "when: start (the table's first placement, at construction or "
        "when the item side is first asked for; a prewarm's grown table) "
        "| placed (the table re-placed whole after its spare rows ran "
        "out) — never a batch that only writes rows"),
    "checkpoint.save_seconds": (
        "histogram", "seconds", "save_factors wall-clock duration"),
    "checkpoint.save_bytes": (
        "counter", "bytes", "bytes written by save_factors"),
    "checkpoint.load_seconds": (
        "histogram", "seconds", "load_factors wall-clock duration"),
    "checkpoint.load_bytes": (
        "counter", "bytes", "bytes read by load_factors"),
    "serve.degraded": (
        "counter", "requests",
        "top-k requests answered from last-good factors because the "
        "sharded gather failed (parallel.serve degraded mode)"),
    "serving.enqueue_seconds": (
        "histogram", "seconds",
        "time a request waited in the admission queue "
        "(serving.batcher: enqueue -> dequeue)"),
    "serving.score_seconds": (
        "histogram", "seconds",
        "device scoring time per serving micro-batch, labeled "
        "path=int8|exact"),
    "serving.e2e_seconds": (
        "histogram", "seconds",
        "end-to-end serving request latency (submit -> completion)"),
    "serving.batch_rows": (
        "histogram", "rows",
        "real (unpadded) requests per dequeued serving micro-batch — "
        "shows bucket fill under the offered load"),
    "serving.queue_depth": (
        "gauge", "requests",
        "admission-queue backlog sampled after each batch dequeue"),
    "serving.requests": (
        "counter", "requests", "requests admitted by the serving engine"),
    "serving.shed": (
        "counter", "requests",
        "requests refused at admission (queue at capacity; the typed "
        "Overloaded the caller sees)"),
    "serving.batch_closed": (
        "counter", "batches",
        "micro-batches dequeued, labeled by what closed them: "
        "by=slot (the engine's own loop held a free slot of its "
        "pipeline: popped at once with whatever was queued, nothing "
        "waited for) | full (the largest bucket filled) | closed (the "
        "batcher was closing) | and, for callers that drive next_batch "
        "themselves, the timed rule: age (the head had already waited "
        "max_wait_s when the consumer arrived: popped without a wait) | "
        "wait (the rest of the head's max_wait_s ran out)"),
    "serving.batch_overlap": (
        "counter", "batches",
        "one per batch the engine THREAD dispatched (a synchronous "
        "serve_batch counts in_flight=0), labeled by the batches it had "
        "already handed to the completion thread that were not yet "
        "completed as this dispatch began: in_flight=0 (the device was "
        "given nothing meanwhile: the serial loop) | 1 (this batch was "
        "dispatched while the one before was being read back); never "
        "more, the engine thread waits first (the batch record's "
        "handoff_wait)"),
    "serving.expired": (
        "counter", "requests",
        "requests whose deadline passed while queued (failed with "
        "DeadlineExceeded instead of being scored)"),
    "serving.fallback_exact": (
        "counter", "requests",
        "requests scored on the exact path because the int8 index was "
        "stale (publish without requantize, or injected staleness)"),
    "serving.publishes": (
        "counter", "publishes",
        "model generations atomically swapped into the serving engine"),
    "serving.user_table_writes": (
        "counter", "publishes",
        "one per ServingEngine.publish_update, labeled by what it did to "
        "the device's user table: how=inplace (the touched and appended "
        "rows written into the live table, which was donated to the "
        "write: O(touched) on the device) | replaced (a new table "
        "uploaded whole: no row list, or rows the live table cannot "
        "take) | carried (no row to write: the live table as it is)"),
    "serving.catalog_writes": (
        "counter", "publishes",
        "one per ServingEngine.publish_update, labeled by what it did to "
        "the catalog: how=carried (nothing of it changed: the index "
        "re-tagged) | delta (the touched and appended rows written into "
        "the index's delta segment and into the engine's own table, in "
        "place) | compact (the same, and the segment folded into the base "
        "arrays, in place, before or after) | replaced (uploaded whole: "
        "no live index to take the rows)"),
    "serving.excluded_ids": (
        "histogram", "ids",
        "catalog ids taken out of one request's ranking by a batch that "
        "excludes (ops.topk.excluded_mask's rule), one sample a request "
        "and source: source=history (the published history of a by-id "
        "request's user, publish(user_seen=...); 0 for a request by "
        "vector; written only by an engine that published histories) | "
        "request (the request's own list, submit(exclude=...))"),
    "serving.exclusion_upload_bytes": (
        "counter", "bytes",
        "what the requests' own lists of excluded ids add to a batch's "
        "one host->device upload, one add a batch that excludes: bucket "
        "x MAX_EXCLUDE int32 columns of the staging layout (the users' "
        "histories never cross again after their publish)"),
    "serving.mesh_exchange_bytes": (
        "counter", "bytes",
        "a mesh engine only: what one device moves between the chips "
        "for the batches it scored, one add a batch — the all-reduce "
        "that spreads the staged [bucket, rank + 2] batch (64 columns "
        "wider where the batch excludes: the requests' own lists) from "
        "the one shard the host placed it on (since PR 44), the by-id "
        "lookup's all-reduce of the [bucket, rank] queries and the "
        "merge's two all-gathers of the shards' local top-k lists, by "
        "the closed form serving.index.mesh_exchange_bytes (a test pins "
        "it to the traced program's collectives)"),
    "serving.mesh_history_bytes": (
        "counter", "bytes",
        "a mesh engine that excludes only: what one device moves between "
        "the chips for the users' histories of the batches it scored, one "
        "add a batch that excludes — the all-reduce of the [bucket, "
        "history pad] int32 lists, the owning shard's ids and zeros from "
        "the others (serving.engine._mesh_history), by the closed form "
        "serving.index.mesh_history_bytes (a test pins it to the traced "
        "program's collectives); nothing else of a history crosses a "
        "link after its publish, and serving.mesh_exchange_bytes counts "
        "the requests' own lists, which ride the staged batch's spread"),
    "serving.pins": (
        "counter", "programs",
        "scoring programs a ServingEngine warm-up pinned (warmup, "
        "warmup_live, warmup_histories; one a bucket, path and history "
        "pad), labeled by where the executable came from: source=loaded "
        "(deserialized from the pin store in the compile cache's "
        "directory, serving.pins.pin: nothing traced or lowered) | "
        "compiled (not there, or no compile cache configured: lowered "
        "and compiled, and written there) | unreadable (its file did "
        "not load: compiled, and the file written over); a start with "
        "a warm cache counts loaded only"),
    "scenario.freshness_seconds": (
        "histogram", "seconds",
        "cold-start scenario: rating-arrival -> servable latency (fold-"
        "in + republish + first successful recommend for a NEW user)"),
    "train.rollbacks": (
        "counter", "rollbacks",
        "guardrail rollbacks: iterations retried from the last-good "
        "factor snapshot after a sentinel trip (resilience.guardrails, "
        "recover mode)"),
    "ingest.quarantined_rows": (
        "counter", "rows",
        "rating records routed to the quarantine sink by stream_ingest "
        "or the estimator's input scrub (malformed, non-finite, or "
        "out-of-range) instead of aborting the ingest"),
    "serving.publish_seconds": (
        "histogram", "seconds",
        "wall-clock cost of one model publish, labeled "
        "mode=full|retag|delta|compact|none — the O(touched)-vs-"
        "O(catalog) incremental-publish claim is measured here"),
    "live.freshness_seconds": (
        "histogram", "seconds",
        "rating-arrival -> servable: from the event entering the live "
        "updater's admission queue to its fold-in's publish seq being "
        "visible to the score path (tpu_als.live.updater)"),
    "live.shed": (
        "counter", "events",
        "rating events refused at the live updater's admission queue "
        "(queue at capacity; the typed Overloaded the producer sees)"),
    "live.queue_depth": (
        "gauge", "events",
        "live-updater admission backlog sampled after each micro-batch "
        "dequeue"),
    "live.publish_h2d_bytes": (
        "counter", "bytes",
        "bytes ServingEngine.publish_update sent host -> device of the "
        "USER table: the touched user rows and their indices on the "
        "incremental path (the indices alone — and what is unread of "
        "the publish's one int32[10, pad] — where the caller held the "
        "rows on the device, device_rows), the whole table where it had "
        "to re-place it (the catalog's: live.catalog_h2d_bytes)"),
    "live.catalog_h2d_bytes": (
        "counter", "bytes",
        "bytes ServingEngine.publish_update sent host -> device of the "
        "CATALOG: the touched and appended item rows with their ids, slots "
        "and valid bits (padded to 8 / 64 / 512 rows; once, for the "
        "index's segment and the engine's own table; ids, slots and "
        "valid bits alone where the caller held the rows on the device, "
        "device_rows), the whole catalog where it had to re-place it"),
    "live.host_placements": (
        "counter", "placements",
        "host -> device placement calls (core.foldin.put: jax.device_put "
        "beside a program's call, ~0.35 ms of Python each on the chip's "
        "host) the live updater's thread made for its micro-batches, one "
        "add a batch: folds, the fold-in server's row writes, the "
        "publish's rows, segment and history plan; 1 a batch where every "
        "table is written from the fold's rows on the device (the "
        "publish's one int32 array), 0 for a batch that moved no row; "
        "the same number as the live.batch span's ``placements``"),
    "live.items_appended": (
        "counter", "items",
        "catalog items the live updater's item fold appended (unknown "
        "before their first foldable rating, servable by id after its "
        "publish)"),
    "live.items_folded": (
        "counter", "items",
        "item folds the live updater published, labeled kind=first (the "
        "item had no factor: appended to the catalog) | again (a row "
        "re-folded over all the item's ratings so far)"),
    "live.items_left_to_refit": (
        "counter", "events",
        "rating events whose ITEM the fold-in server left its factor: a "
        "server with a resident base history folds an item only where "
        "the run's events are ALL its ratings (none resident), so an "
        "item a resident rating names waits for the refit; the events "
        "still enter their users' folds and histories "
        "(stream/microbatch.py)"),
    "live.history_segment_ids": (
        "counter", "ids",
        "of the ids ServingEngine.publish_update appended to its users' "
        "histories (live.history_appended_ids), those that name an item "
        "the published index holds in its delta segment at that publish: "
        "the scoring program masks them by slot, not by base column"),
    "live.events_waiting": (
        "gauge", "events",
        "ratings the fold-in server holds in a history for a side whose "
        "other entity has no factor yet (one per rating and side), "
        "sampled after each micro-batch's folds"),
    "foldin.history_width": (
        "histogram", "ratings",
        "padded width of each run of the fold-in program (the rung that "
        "holds the longest rating history among the entities it solves: "
        "8 / 64 / 512 / 4096 ...; a server with a resident base history "
        "folds over ALL of a user's ratings, so its widths follow the "
        "histories, not the batch), labeled side=user|item"),
    "live.history_appended_ids": (
        "counter", "ids",
        "catalog ids ServingEngine.publish_update appended to its users' "
        "resident histories (seen_appended: one per folded rating of an "
        "item its user had not rated), swapped in with the rows of the "
        "same publish"),
    "live.history_h2d_bytes": (
        "counter", "bytes",
        "bytes ServingEngine.publish_update sent host -> device for the "
        "HISTORIES: the appended ids, their positions and their users' "
        "starts and counts (padded to 8 / 64 / 512 entries) and two "
        "scalars a relocated run — O(ids appended), never a history; the "
        "8 bytes an id of a table laid out anew are not in it (a warning "
        "says when that happens under traffic)"),
    "live.landings": (
        "counter", "landings",
        "whole model generations (a refit's factors with the catch-up "
        "folded onto them) a running live updater installed "
        "(LiveUpdater.land)"),
    "live.landing.catchup_events": (
        "counter", "events",
        "rating events admitted after a landed refit's snapshot and "
        "folded by then: their users and items are what the landing's "
        "catch-up folded again, over all their kept ratings, against "
        "the refit's tables"),
    "live.landing.bytes_placed": (
        "counter", "bytes",
        "bytes of whole tables a landing handed host -> device (the "
        "growth of device.placed_bytes across it: the fold-in server's "
        "two tables; the engine's generation is copied from them on the "
        "device)"),
    "live.landing.peak_bytes": (
        "gauge", "bytes",
        "peak_bytes_in_use of the fullest local device, read as a "
        "landing ends: the most the device has held since the process "
        "began (a landing holds two engine generations until its swap)"),
    "live.history_relocations": (
        "counter", "runs",
        "histories whose run on the device was full when a publish "
        "appended to it and was moved to the table's free room first (a "
        "copy of the run on the device, O(history): the slow path of "
        "the grown layout, serving.engine._Seen)"),
    "train.stage_seconds": (
        "histogram", "seconds",
        "fence-timed seconds of one attributed ALS stage (obs.trace."
        "stage), labeled stage=<perf.roofline stage name> so "
        "`observe attribution` can join measured time against the "
        "modeled floor"),
    "tenancy.tenants": (
        "gauge", "tenants",
        "models currently registered with the multi-tenant control "
        "plane (tpu_als.tenancy.registry)"),
    "tenancy.served_rows": (
        "counter", "rows",
        "requests completed per tenant by the fair-share scheduler "
        "(labeled tenant=<name>; the goodput series the fairness "
        "ratio is computed from)"),
    "tenancy.batch_errors": (
        "counter", "batches",
        "micro-batches whose scoring raised, failed in isolation "
        "(labeled tenant=<name>: the failing tenant's tickets erred, "
        "every other tenant kept serving)"),
    "train.reformations": (
        "counter", "reformations",
        "elastic mesh reformations: a mid-fit device loss was detected, "
        "the ring re-formed on the surviving mesh and training resumed "
        "from the last atomic checkpoint (resilience.elastic)"),
    "soak.windows": (
        "counter", "windows",
        "soak windows completed by the production-week orchestrator "
        "(tpu_als.soak.orchestrator)"),
    "soak.injections": (
        "counter", "injections",
        "chaos injections whose fault observably fired during a soak "
        "(the soak_injection event carries the evidence)"),
    "soak.recoveries": (
        "counter", "recoveries",
        "chaos injections that fired AND left recovery evidence in the "
        "trail before their window closed"),
    "soak.window_seconds": (
        "histogram", "seconds",
        "wall-clock duration of one soak window (traffic replay + "
        "chaos actions + joins; the schedule's window_s is the floor)"),
    "jax.programs": (
        "counter", "programs",
        "compile-path events of this process by JAX's own listeners "
        "(obs.compiles, installed by the first ServingEngine or "
        "FoldInServer): stage=trace|lower|compile, and for a backend-"
        "compile call cache=hit (the persistent compilation cache held "
        "the executable) | miss (it did not: compiled) | off (the call "
        "went by no persistent cache: none configured, or an entry "
        "under its thresholds); when=traffic (no start phase open on "
        "the compiling thread and an engine started) | before.  No "
        "program name in a label: the jax_program event names each"),
    "jax.program_seconds": (
        "counter", "seconds",
        "seconds of the same events by stage=trace|lower|compile and "
        "when=traffic|before (a function traced inside another counted "
        "once; compile: the backend's compile call, a cache's fetch "
        "included)"),
    "device.placed_bytes": (
        "counter", "bytes",
        "bytes of whole tables handed host -> device, where they go up "
        "(core.foldin.place_rows and the engine's placements), at a "
        "start AND under traffic: table=users (ServingEngine's user "
        "table) | catalog (its catalog) | index (the candidate index's "
        "own copy of the catalog: one chip, no mesh) | histories (the "
        "users' histories and, where they are laid out to grow, the "
        "moves' sources and targets) | fold_fixed (a FoldInServer's "
        "fixed side: the catalog, and the user table where items fold). "
        "A table re-placed whole under traffic (spare rows used up) "
        "shows here and in no per-batch counter"),
    "start.seconds": (
        "counter", "seconds",
        "wall seconds of the start phases (obs.phases.phase; "
        "schema.START_PHASES), exact sums by path = the '/'-joined "
        "start.* phases open on the thread: a path with no '/' is a "
        "start's top level, one that prefixes no other a leaf"),
    "start.placed_bytes": (
        "counter", "bytes",
        "device.placed_bytes' growth inside each start phase, by the "
        "same path"),
}

# metric name -> label keys its writers may attach.  Any key outside
# this row raises at call time (metrics.MetricsRegistry) and fails the
# static check (analysis/vocab.py) — labels are declared vocabulary,
# not free-form tags.  Metrics absent from this table take no labels.
LABELS = {
    "train.comm_bytes_per_iter": ("strategy",),
    "train.gather_block_rows": ("n_blocks", "side"),
    "train.stage_seconds": ("stage",),
    "serve.request_seconds": ("strategy",),
    "foldin.history_width": ("side",),
    "foldin.ids_mapped": ("side",),
    "foldin.yty_rows": ("side",),
    "foldin.yty_full": ("side", "when"),
    "serving.enqueue_seconds": ("tenant",),
    "serving.score_seconds": ("path", "tenant"),
    "serving.e2e_seconds": ("tenant",),
    "serving.batch_rows": ("tenant",),
    "serving.queue_depth": ("tenant",),
    "serving.requests": ("tenant",),
    "serving.shed": ("tenant",),
    "serving.batch_closed": ("by", "tenant"),
    "serving.batch_overlap": ("in_flight", "tenant"),
    "serving.expired": ("tenant",),
    "serving.fallback_exact": ("tenant",),
    "serving.publishes": ("tenant",),
    "serving.user_table_writes": ("how", "tenant"),
    "serving.mesh_exchange_bytes": ("tenant",),
    "serving.mesh_history_bytes": ("tenant",),
    "serving.pins": ("source", "tenant"),
    "serving.excluded_ids": ("source", "tenant"),
    "serving.exclusion_upload_bytes": ("tenant",),
    "serving.publish_seconds": ("mode", "tenant"),
    "live.freshness_seconds": ("tenant",),
    "live.shed": ("tenant",),
    "live.queue_depth": ("tenant",),
    "live.publish_h2d_bytes": ("tenant",),
    "live.host_placements": ("tenant",),
    "live.catalog_h2d_bytes": ("tenant",),
    "live.items_appended": ("tenant",),
    "live.events_waiting": ("tenant",),
    "live.items_folded": ("kind", "tenant"),
    "live.items_left_to_refit": ("tenant",),
    "live.history_segment_ids": ("tenant",),
    "live.history_appended_ids": ("tenant",),
    "live.history_h2d_bytes": ("tenant",),
    "live.history_relocations": ("tenant",),
    "live.landings": ("tenant",),
    "live.landing.catchup_events": ("tenant",),
    "live.landing.bytes_placed": ("tenant",),
    "live.landing.peak_bytes": ("tenant",),
    "serving.catalog_writes": ("how", "tenant"),
    "tenancy.served_rows": ("tenant",),
    "tenancy.batch_errors": ("tenant",),
    "jax.programs": ("stage", "cache", "when"),
    "jax.program_seconds": ("stage", "when"),
    "device.placed_bytes": ("table",),
    "start.seconds": ("path",),
    "start.placed_bytes": ("path",),
}

# every metric allowed to carry the multi-tenant attribution label —
# derived from LABELS so it can never drift from the table above; the
# analysis gate additionally pins that every serving.*/live.* metric
# appears here (a new serving series without a tenant story is a lint
# failure, the same way serving.publish_seconds' mode label is pinned)
TENANT_LABELED = tuple(sorted(
    n for n, keys in LABELS.items() if "tenant" in keys))

# -- causal-trace vocabulary (tpu_als/obs/tracing.py) ------------------------
#
# Every hop a request or rating event takes is one named span; the name
# is vocabulary exactly like a metric name — ``tracing.record_span`` and
# ``tracing.start_trace`` validate against this table at call time, and
# ``analysis/vocab.py`` validates every call-site literal statically.
# ``tpu_als observe explain`` renders the tree these spans encode.
TRACE_SPANS = (
    "serve.admit",        # request admitted at the serving front door
    "serve.queue",        # waited in the MicroBatcher admission queue
    "tenancy.round",      # drained by one fair-share scheduler round
    "serve.score",        # scored on device (path=int8|exact)
    "serve.expired",      # deadline passed while queued
    "live.admit",         # rating event admitted by the live updater
    "live.queue",         # waited in the live admission queue
    "live.quarantine",    # poisoned event dropped before the factors
    "live.foldin",        # folded into the touched factor rows
    "live.publish",       # rode an incremental publish_update
    "live.visible",       # its publish seq became score-path visible
    "elastic.detect",     # a failed step was classified (probe verdict)
    "elastic.reform",     # the mesh was rebuilt on the survivors
    "elastic.resume",     # training re-entered from the checkpoint
)

# per-span outcome vocabulary; "ok" is the happy path, everything else
# names the typed refusal/failure the span ended in (sheds and breaches
# are traced, never dropped)
TRACE_STATUSES = ("ok", "shed", "expired", "failed", "quarantined")

# the flight recorder's per-record span-key breakdowns (source of truth
# here, stdlib-only, so analysis/vocab.py can assert — jax-free — that
# they never collide with the record's structural fields or labels)
SERVE_SPAN_KEYS = ("admission", "queue_wait", "score", "respond")
# a batch's cycle: the names of its profiler spans
# (``jax.profiler.TraceAnnotation`` in serving/batcher.py and
# serving/engine.py, one set per batch, on the device trace's clock) and
# the keys of the engine's per-batch flight record, which keeps the same
# durations for whoever runs no profiler.  Trace readers key on these
# names (benchmark/program_spans.py), never on thread names.  A started
# engine writes them from TWO threads (E: the engine thread, ``_run``;
# C: the completion thread, ``_run_completions``), so a batch's readback
# may lie under the next batch's stage and dispatch; a synchronous
# ``serve_batch`` writes all of them on its caller's thread, the four
# phases inside ``serve.batch``
SERVE_BATCH_SPAN_KEYS = (
    "serve.idle",             # E: blocked on an empty queue
    "serve.batch.coalesce",   # E: first request seen -> batch popped
    #                           (waiting, closed_by, head_wait)
    "serve.batch",            # E: stage + dispatch (seq, bucket, rows,
    #                           path); synchronous: all of serve_batch.
    #                           The RECORD's is the batch's whole life,
    #                           stage to its last ticket's bookkeeping
    "serve.batch.stage",      # E: the wait for the user table's lock, then
    #                           expiry check + staging into the upload
    #                           array (lock_wait_us: that wait alone, the
    #                           record's ``lock_wait`` — what a publish's
    #                           ``live.batch.publish.writes`` kept it out)
    "serve.batch.dispatch",   # E: upload + the scoring call, until it
    #                           returns
    "serve.batch.readback",   # C: the one bulk device->host transfer (seq)
    "serve.batch.complete",   # C: completing the tickets + their
    #                           bookkeeping (seq)
)
# every span of a batch carries its ``seq``, and the four phases
# (stage, dispatch, readback, complete) ``cpu_us`` beside ``wall_us``:
# the thread's own CPU time inside the span (``time.thread_time_ns``) and
# the wall time of the same interval, so wall - CPU is time the thread
# held no processor — waiting for the interpreter, or blocked in a
# transfer.  The two are stamped only while a profiler session records
# (serving/engine.py ``cpu_mark``: the CPU clock is a system call, 7-30 us
# on the chip's sandboxed host), so a span has both or neither.  Dispatch
# is split where its work happens, two child spans INSIDE
# ``serve.batch.dispatch`` (a reader that adds the leaves above covers no
# more time for them); not keys of the batch record, which carries their
# seconds as ``upload`` and ``launch``
SERVE_DISPATCH_SPAN_KEYS = (
    "serve.batch.dispatch.upload",  # E: what the host does for the
    #                                 staged batch's upload apart from
    #                                 the scoring call (seq, bytes: the
    #                                 staged array's; how = call: the
    #                                 array rides the program's call as
    #                                 its host argument, the transfer is
    #                                 inside launch, and this span holds
    #                                 the scorer chosen and its arguments
    #                                 built | put_one: a mesh engine's,
    #                                 since PR 44 — one transfer to the
    #                                 mesh's first device inside this
    #                                 span, the program spreads it and
    #                                 launch holds no transfer | put: a
    #                                 separate device_put, which no path
    #                                 makes since PR 41)
    "serve.batch.dispatch.launch",  # E: the scorer called, until the call
    #                                 returns (seq, program: the jitted
    #                                 function as the trace's XLA Modules
    #                                 line names it,
    #                                 ``jit__serve_int8_packed``,
    #                                 ``jit__serve_exact_packed``,
    #                                 ``jit_serve_mesh_int8``,
    #                                 ``jit_serve_mesh_exact``;
    #                                 pin: the path name of the key it
    #                                 was pinned under, ``int8`` |
    #                                 ``int8_delta`` (given a segment) |
    #                                 ``exact`` — with the stage span's
    #                                 ``excluded`` (the history pad) the
    #                                 whole key; pinned 0|1: the AOT
    #                                 executable took it)
)
# the engine thread's blocking wait for one of MAX_IN_FLIGHT slots,
# written only when it blocks (seq: the batch that will take the slot;
# the record's ``handoff_wait``).  Named OUTSIDE the ``serve.`` prefix on
# purpose: benchmark/program_spans.py::read takes every ``serve.`` event
# for a phase of a batch, and a span BETWEEN two batches would move its
# unattributed share and break "the gaps by span add up to the idle
# time"; the ``benchmark`` PR that retires that reader may rename it
PIPE_SPAN_KEYS = (
    "pipe.slot_wait",
)
# inside a mesh engine's ONE scoring program a bucket (serving/engine.py
# ``_build_mesh_exact``; serving/index.py ``_build_sharded_int8``, which
# the engine gives its lookup and pack) the three steps that
# exist only across chips are ``jax.named_scope``s, in every operation's
# ``op_name`` beside ``serve.shortlist.*`` (ops/topk.py); the engine
# thread's spans above lie around that program unchanged,
# ``path="int8_sharded"``
SERVE_MESH_SCOPES = (
    "serve.mesh.lookup",      # each shard takes the user rows it owns for
    #                           the batch's ids; one all-reduce sums them
    "serve.mesh.score",       # the shard's int8 shortlist + f32 rescore
    "serve.mesh.merge",       # two all-gathers of the local top-k lists,
    #                           one top_k, the packed response
)
# per-request exclusion inside a scoring program: one ``jax.named_scope``
# around the rule's operations (serving/engine.py ``_select_seen``: the
# batch's histories gathered from the published CSR; ops/topk.py
# ``excluded_mask``: the sort, the scatter-add into the bit-packed mask
# and its unpacking), in
# every such operation's ``op_name``; and on the host the stat
# ``excluded`` (the batch's history pad) on ``serve.batch.stage`` of a
# batch that excludes
SERVE_EXCLUDE_SCOPE = "serve.exclude"
# on a mesh, beside ``serve.mesh.lookup``: the shard that owns a by-id
# slot's user takes that history's first ids from ITS part of the table
# (``_select_seen`` on the shard's own runs, so ``serve.exclude`` lies
# inside it) and one all-reduce of the ``int32[bucket, history pad]``
# lists hands them to every shard (serving/engine.py ``_mesh_history``);
# each shard's mask over its own columns is ``serve.exclude`` inside
# ``serve.mesh.score`` (serving/index.py ``shard_lists``, ops/topk.py)
SERVE_MESH_HISTORY_SCOPE = "serve.mesh.history"
LIVE_SPAN_KEYS = ("queue_wait", "quarantine", "foldin", "publish")
# the updater thread's batch cycle as profiler spans, the write path's
# counterpart of SERVE_BATCH_SPAN_KEYS (``TraceAnnotation`` in
# live/updater.py, always on, disjoint but for ``live.batch`` around its
# two phases).  Inside the device programs the same vocabulary goes on as
# ``jax.named_scope``: ``live.foldin.gram`` / ``live.foldin.solve``
# (core/foldin.py) and ``live.publish.scatter`` (serving/engine.py)
LIVE_BATCH_SPAN_KEYS = (
    "live.idle",              # blocked on an empty admission queue
    "live.batch.coalesce",    # first event seen -> batch popped
    "live.batch",             # all of _process (seq, events, users,
    #                           new_users, width, mode, placements: the
    #                           host -> device placements the thread
    #                           made for the batch, counter
    #                           live.host_placements; with fold_items
    #                           also items, new_items, segment_rows)
    "live.batch.foldin",      # FoldInServer.update (+ update_items)
    "live.batch.publish",     # ServingEngine.publish_update (with
    #                           fold_items also ``items``: the catalog
    #                           rows it names; on an engine that holds
    #                           histories ``history_ids``: the ids it
    #                           appends — one publish's three parts)
)
# what an updater with ``fold_items`` writes besides, inside the two
# phases above (none of it without: a user-only updater's timeline is
# LIVE_BATCH_SPAN_KEYS and nothing else).  In the device programs:
# ``live.publish.scatter_items`` (the catalog row write: the engine's
# own table, serving/engine.py, and the index's segment,
# serving/index.py) and ``live.publish.compact`` (serving/index.py)
LIVE_ITEM_SPAN_KEYS = (
    "live.batch.foldin.users",    # FoldInServer.update
    "live.batch.foldin.items",    # FoldInServer.update_items
    "live.batch.publish.compact",  # the segment folded into the base
)
# what a publish on a generation that holds users' histories writes
# besides, inside ``live.batch.publish`` (serving/engine.py
# ``_append_history``; none of it on an engine without histories): the
# ids the publish adds to its users' histories planned and uploaded — the
# write itself is dispatched with the row write, under the table lock —
# with the stats ``ids``, ``users``, ``relocated`` and, under a profiler,
# ``cpu_us`` / ``wall_us``.  In the device programs that write them
# (``_append_runs``, ``_move_run``) the scope LIVE_HISTORY_SCOPE
LIVE_HISTORY_SPAN_KEYS = (
    "live.batch.publish.history",
)
LIVE_HISTORY_SCOPE = "live.publish.history"
# the Gram matrix an implicit fold-in server keeps of each fixed table
# (core/foldin.py): the update by the rows a write moved, inside the row
# write's program beside ``live.foldin.scatter``
# (``_scatter_rows_yty``), and the whole-table pass where a table is
# placed whole (``whole_yty``).  (``live.foldin.gram`` is the normal
# equations' build inside the fold program.)
LIVE_FOLDIN_YTY_SCOPE = "live.foldin.yty"
# what every fold writes, items or none, inside ``live.batch.foldin``
# (with ``fold_items`` inside ``.foldin.users`` / ``.foldin.items``):
# stream/microbatch.py, which the updater drives.  ``live.batch``,
# ``.foldin`` and ``.publish`` also carry ``cpu_us`` and ``wall_us`` (as
# the engine's phases above, under a profiler)
LIVE_FOLDIN_SPAN_KEYS = (
    "live.batch.foldin.readback",  # the fold-in program called — its
    #                                ids, stars and mask ride the call
    #                                as ONE host array: no upload of
    #                                their own since PR 49 (three
    #                                before) — and its rows read back:
    #                                blocks on the device (side: users
    #                                | items)
)
# one ``live.batch`` tiled by phase (ISSUE 54): every millisecond of the
# updater thread's batch lies under one of these or under one of the
# spans above (``live.batch.foldin.readback``, ``.publish.history``,
# ``.publish.compact``), each written a BATCH of events and never an
# event, each with ``cpu_us`` / ``wall_us`` under a profiler
# (serving/engine.py ``Stamped``).  The tree of one batch, a child
# indented under its parent (docs/observability.md has it whole):
#
#   live.batch
#     .prepare
#     .foldin [.users | .items with fold_items]
#       .group .history .map .pack .readback{.call} .write_back
#     .publish
#       .join .users .history .catalog{.ride .compact} .send{.ride}
#       .lock_wait .writes .after
#     .record
#
# benchmark/live_phase_spans.py keys on the names: what a container
# (``live.batch``, ``.foldin``, ``.foldin.users`` / ``.items``,
# ``.publish``) holds outside its children is the batch's UNSPLIT time
LIVE_PHASE_SPAN_KEYS = (
    "live.batch.prepare",            # updater: _process up to the fold —
    #                                  the events' arrays, their queue
    #                                  hops, the quarantine mask
    "live.batch.foldin.group",       # microbatch (side on each of the
    #                                  fold's): the frame's columns, the
    #                                  items left to the refit, events
    #                                  grouped by entity
    "live.batch.foldin.history",     # the EVENTS' ids mapped to table
    #                                  rows, once, and put behind each
    #                                  entity's history, which is kept as
    #                                  rows (_Ratings: a first touch copies
    #                                  the resident run as it lies;
    #                                  ``rate``: one rating an id; ratings
    #                                  held for a side without a factor)
    "live.batch.foldin.map",         # what the touched histories hold
    #                                  WITHOUT a row looked up again, where
    #                                  the map has grown (mapped: ids sent
    #                                  through to_dense this fold, the
    #                                  events' included; ratings: ids in
    #                                  the folds), the usable counted, who
    #                                  is folded
    "live.batch.foldin.pack",        # one call's ids, stars and mask
    #                                  into the ONE host array it rides:
    #                                  slice copies, an entity a row
    "live.batch.foldin.call",        # the fold-in program called, until
    #                                  the call returns (rows, width: the
    #                                  padded shape; calls: which of the
    #                                  fold's calls) — INSIDE
    #                                  ``.foldin.readback``, whose rest is
    #                                  the blocking read of the rows
    "live.batch.foldin.write_back",  # the rows into the host's table and
    #                                  the other direction's fixed table
    "live.batch.publish.join",       # updater: what the publish is
    #                                  handed — the ids that join their
    #                                  users' histories (_joining), the
    #                                  folds' rows on the device, the
    #                                  touched users' table rows
    "live.batch.publish.users",      # engine: _update_users — which rows
    #                                  the user table takes, and how
    "live.batch.publish.catalog",    # engine: what becomes of the catalog
    #                                  — carried and the index re-tagged,
    #                                  or (_write_catalog) the segment's
    #                                  update planned and written, around
    #                                  a ``.ride`` and, where the segment
    #                                  is full, a ``.compact``
    "live.batch.publish.send",       # engine: the last before the lock —
    #                                  the ids that name a segment's item
    #                                  counted, the ``.ride`` where the
    #                                  catalog sent none, the history's
    #                                  plan placed
    "live.batch.publish.ride",       # engine: _send — the publish's ONE
    #                                  int32[PUBLISH_SENT, pad] built and
    #                                  placed (bytes); inside ``.catalog``
    #                                  or ``.send``
    "live.batch.publish.lock_wait",  # engine: _swap until _table_lock is
    #                                  held
    "live.batch.publish.writes",     # engine: _swap under the lock — the
    #                                  donating row writes dispatched
    #                                  (programs: how many), the
    #                                  generation swapped; how long a
    #                                  batch's stage can be kept out
    "live.batch.publish.after",      # engine: both locks given back —
    #                                  the counters, the serving_publish
    #                                  event
    "live.batch.record",             # updater: counters, the batch
    #                                  span's stats, the freshness
    #                                  samples, the flight record
)

# a refit LANDS on a running updater (live/updater.py ``LiveUpdater.land``,
# on its caller's thread): one ``live.landing`` (stats ``seq``, ``users``,
# ``items``, ``catchup_events``, ``catchup_users``, ``catchup_items``)
# around its steps, each a ``Stamped`` span with ``cpu_us`` / ``wall_us``
# under a profiler.  In the device program that copies a table
# (serving/engine.py ``_copy_table``) the scope ``live.landing.copy``.
# benchmark/layer_metrics/live_landing_*.py read the same seconds from the
# updater's ``landings`` records; serve_life_beside_landing_ms joins the
# serving batches' lives with the ``live.landing`` spans
LIVE_LANDING_SPAN_KEYS = (
    "live.landing",               # updater: a landing, from the moment the
    #                               loop stands between two batches (no
    #                               ``live.`` span overlaps a live.batch)
    "live.landing.pause",         # updater: the wait for that moment, as
    #                               the stat ``waited_us`` of an empty span
    "live.landing.tables",        # microbatch: the refit's rows into the
    #                               host's two tables, who is folded again
    "live.landing.place",         # microbatch: one of the server's tables
    #                               released and placed anew (side, bytes)
    "live.landing.catchup",       # microbatch: the catch-up's folds and
    #                               row writes (users, items, rounds,
    #                               calls)
    "live.landing.catchup.call",  # microbatch: one call of the fold-in
    #                               program and its rows read back (side,
    #                               rows, width)
    "live.landing.users",         # engine: the generation's user table
    #                               (a copy on the device, or placed)
    "live.landing.catalog",       # engine: its catalog, likewise
    "live.landing.index",         # engine: the index's own catalog and
    #                               the whole table quantized, an empty
    #                               segment
    "live.landing.lock_wait",     # engine: _swap until _table_lock is
    #                               held
    "live.landing.swap",          # engine: the generation installed under
    #                               the lock: how long a batch's stage can
    #                               be kept out
    "live.landing.release",       # engine: the generation before deleted
    "live.landing.record",        # updater: the record, counters, event
)

# the phases of a serving start (obs/phases.py::phase: one ``span`` event
# each with seconds, CPU seconds, bytes placed, device bytes in use and
# the compile ledger's difference; a TraceAnnotation, never a named
# scope; exact sums in start.seconds / start.placed_bytes by path).
# Children tile their parent; ``start.pin`` and ``start.first_run`` are
# children of whichever warm-up pins and runs.  benchmark/start_phases.py
# keys on the names and on the path's shape
START_PHASES = (
    "start.publish",                  # ServingEngine.publish, whole
    "start.publish.users",            # _place_users: the user table up,
    #                                   in chunks, waited for
    "start.publish.histories",        # _place_seen
    "start.publish.histories.check",  #   the CSR validated on the host
    #                                   (labels ids, parts)
    "start.publish.histories.place",  #   runs and ids up (on a mesh a
    #                                   shard at a time: _shard_seen)
    "start.publish.catalog",          # _place_catalog: V (and valid) up
    "start.publish.index",            # _build_index
    "start.publish.index.place",      #   one chip: V up a SECOND time,
    #                                   the index's own copy
    "start.publish.index.quantize",   #   the int8 rows and scales made
    #                                   on the device (_quantize_rows)
    "start.warmup",                   # ServingEngine.warmup
    "start.warmup_publish",           # .warmup_publish: the user-row
    #                                   writes run (start.first_run)
    "start.warmup_landing",           # .warmup_landing: the programs a
    #                                   landing runs (a table copied on
    #                                   the device, the whole catalog
    #                                   table quantized), run once
    "start.warmup_live",              # .warmup_live
    "start.warmup_live.reserve",      #   spare rows for the catalog's
    #                                   arrays, the segment made
    "start.warmup_histories",         # _warm_histories (alone, or inside
    #                                   start.warmup_live)
    "start.warmup_histories.plan",    #   _lay_out on the host: where
    #                                   every id moves to (label ids)
    "start.warmup_histories.place",   #   sources and targets up (8 bytes
    #                                   an id, once)
    "start.pin",                      # _pin: one pinned program loaded,
    #                                   or lowered + compiled + written
    "start.first_run",                # a program RUN for the first time
    #                                   by a warm-up, waited for
    "start.foldin_server",            # FoldInServer.__init__
    "start.foldin_server.reserve",    #   the host's table copied into a
    #                                   buffer with spare rows
    "start.foldin_server.place",      #   _place("_V"): the catalog up a
    #                                   THIRD time, the folds' fixed side
    "start.foldin_server.history",    #   the resident ratings' widths
    "start.foldin_server.yty",        #   implicit: the Gram of a fixed
    #                                   table, whole (``side``: item at
    #                                   construction, user inside
    #                                   start.prewarm where the item side
    #                                   is first asked for)
    "start.prewarm",                  # FoldInServer.prewarm (sides=)
    "start.prewarm.reserve",          #   host work before a side folds:
    #                                   the id map sorted; on the item
    #                                   side (_fixed) the catalog copied
    #                                   into a buffer with spare rows,
    #                                   which items a resident rating
    #                                   names
    "start.prewarm.place",            #   _fixed, the item side: the user
    #                                   table up a SECOND time
    "start.prewarm.programs",         #   the ladder: every fold-in
    #                                   program compiled or fetched, run
    "start.prewarm.writes",           #   the four row-write forms a pad
    "start.updater",                  # LiveUpdater.start, whole
)

# field names every flight record (and its flight_record event) claims
# structurally — span keys and label keys must stay disjoint from these
FLIGHT_RESERVED = ("seq", "status", "spans", "e2e_seconds", "path",
                   "trigger", "ts", "type")

# event type -> (required fields beyond ts/type, help text).  Extra
# fields are allowed (events are self-describing JSON); missing required
# fields raise at emit time.
EVENTS = {
    "command": (
        ("cmd", "argv"),
        "one per CLI invocation: the subcommand and its argv"),
    "span": (
        ("name", "path", "t0", "seconds"),
        "one per closed span(): its start (t0, perf_counter seconds) and "
        "wall-clock duration; path is the '/'-joined stack of enclosing "
        "span names (the tree structure).  The same name is a "
        "TraceAnnotation on the profiler's timeline while one records.  "
        "A start phase's span (obs.phases.phase; name in START_PHASES) "
        "also carries cpu_seconds (the calling thread's), placed_bytes "
        "(device.placed_bytes' growth inside it), device_bytes_in_use "
        "(as it closes, the fullest local device) and the compile "
        "ledger's difference over it: programs (backend-compile calls), "
        "cache_hits, cache_misses, trace_s, lower_s, compile_s; the two "
        "passes over the histories also say what they walked: ids, and "
        "the check its parts"),
    "jax_program": (
        ("fun_name", "trace_s", "lower_s", "compile_s", "cache", "phase"),
        "one per backend-compile call of this process (obs.compiles): "
        "the program's name as JAX gives it (jit(...) stripped), the "
        "seconds it was traced and lowered on that thread before the "
        "call and the call's own (a cache's fetch included), cache = "
        "hit | miss | off, and phase = the innermost start.* phase open "
        "on the compiling thread, 'traffic' where none is and an engine "
        "is started, null before"),
    "metric": (
        ("kind", "name", "value"),
        "a gauge set (gauges are point-in-time, so each set is an "
        "event; counters/histograms appear only in the final snapshot)"),
    "iteration": (
        ("iteration", "seconds", "total_seconds"),
        "one per training iteration observed by the CLI's "
        "IterationLogger (factor norms, optional probe_rmse)"),
    "ingest": (
        ("path", "rows", "bytes", "seconds", "stall_seconds"),
        "one per stream_ingest call: this host's parsed totals"),
    "checkpoint_save": (
        ("path", "seconds", "bytes"),
        "one per save_factors call"),
    "checkpoint_load": (
        ("path", "seconds", "bytes"),
        "one per load_factors call"),
    "retry_attempt": (
        ("what", "attempt", "attempts", "elapsed_seconds", "reason"),
        "one per failed attempt inside resilience.retry.retry_call "
        "(the call will be retried)"),
    "retry_exhausted": (
        ("what", "attempts", "reason"),
        "retry_call gave up: every attempt in the budget failed"),
    "fault_injected": (
        ("point", "mode", "hit"),
        "a resilience.faults fault point fired (chaos testing only; "
        "never emitted when TPU_ALS_FAULT_SPEC is unset)"),
    "serving_publish": (
        ("seq", "items", "quantized"),
        "one per ServingEngine.publish: the generation sequence number, "
        "catalog size, and whether an int8 index was built for it; a "
        "publish_update adds users = inplace|replaced|carried, what it "
        "did to the device's user table (serving.user_table_writes' how), "
        "and catalog = carried|delta|compact|replaced "
        "(serving.catalog_writes' how)"),
    "serving_compaction": (
        ("seq", "rows"),
        "one per compaction of the live index's delta segment "
        "(ServingEngine._compact_live): the generation it was folded "
        "under (compaction changes no answer, so seq stays) and the rows "
        "the segment held"),
    "serving_backend": (
        ("backend", "n_shards"),
        "one per ServingEngine that was given a mesh, at first publish: "
        "backend is sharded (the int8 index sharded over n_shards "
        "devices); an engine without a mesh emits none"),
    "serving_shortlist": (
        ("bucket", "path", "stages", "blocks", "block_len", "columns",
         "blocks_layout", "blockmax", "tail"),
        "one per int8 scoring program ServingEngine.warmup / warmup_live "
        "compiles (per bucket and path; warmup_live adds delta_rows, the "
        "segment's slots): how "
        "its shortlist selects, from ops.topk.shortlist_plan — stages 1 is "
        "one lax.top_k over all columns, 2 is block maxima then top_k "
        "over the winning blocks of block_len columns; blocks_layout is "
        "what stage two asks of the compiler for its operand: row_major "
        "(the block maxima constrained to blocks-along-lanes, for a "
        "bucket under ops.topk.ROW_MAJOR_BELOW rows: on the TPU they "
        "leave the score fusion with the batch's rows along the 128 "
        "lanes, 8 of 128 filled at bucket 8) or compiler (no constraint); "
        "blockmax is how stage one reduces a block: block (its 128 lanes "
        "at once), lanes (a block longer than 128 folded to 128 lanes "
        "first, the elementwise maximum of its 128-lane groups: the v5e "
        "compiler does not fuse a reduce over 256 lanes into the fusion "
        "that computes the scores, and the fold halves what that pass of "
        "its own costs) or none (one stage); tail is the columns joined "
        "to stage three's winners instead of to the score matrix (a "
        "delta segment's slots; 0 without one; columns counts the "
        "matrix's own)"),
    "serving_exclusion": (
        ("bucket", "path", "history_pad", "request_pad", "rows", "ids",
         "columns", "block", "words", "mask_bytes", "keys"),
        "one per scoring program that excludes, as ServingEngine.warmup "
        "compiles and pins it for a generation that holds users' "
        "histories (per bucket: path int8 at every history pad of the "
        "ladder 64, 512, ... up to the longest history, path exact at the "
        "longest alone): the ids a row may exclude (ids = history_pad + "
        "request_pad, the request's own MAX_EXCLUDE) and what the mask "
        "costs, from ops.topk.exclusion_plan, the helper the program's "
        "mask was traced with — the uint32 words of the bit-packed mask "
        "(32 blocks of block columns to a word), their mask_bytes, and the "
        "keys it sorts before its one scatter-add (rows x ids)"),
    "serving_mesh_plan": (
        ("bucket", "shards", "items_per_shard", "users_per_shard", "k_loc",
         "placements", "spread_bytes", "exchange_bytes", "history_pad",
         "history_bytes"),
        "one per sharded int8 scoring program a mesh engine's "
        "ServingEngine.warmup compiles and pins (per bucket, and per "
        "history pad where the generation holds histories: history_pad, "
        "null otherwise, and history_bytes, what one device moves for "
        "the batch's [bucket, history pad] lists, "
        "serving.index.mesh_history_bytes; spread_bytes and "
        "exchange_bytes then count the 64 wider columns): the mesh "
        "size, the catalog and user-table rows one shard holds, the "
        "answers one shard gives a query, the host->device transfers the "
        "staged batch takes (placements: 1 since PR 44, to the mesh's "
        "first device; one a shard before), the bytes the program's "
        "first all-reduce moves to spread it (spread_bytes: "
        "serving.index.mesh_spread_bytes), and the bytes one device moves "
        "between the chips for one batch, the spread among them (the "
        "lookup's all-reduce, the merge's all-gathers: "
        "serving.index.mesh_exchange_bytes), which is what "
        "serving.mesh_exchange_bytes adds per batch"),
    "serving_pin": (
        ("bucket", "path", "pad", "source", "seconds", "bytes"),
        "one per scoring program a ServingEngine warm-up pinned: its "
        "bucket, path (int8 | int8_delta | exact) and history pad (null "
        "for a program that takes no histories), where the executable "
        "came from (source, as serving.pins labels it: loaded | compiled "
        "| unreadable), the seconds the pin took (the load, or lower + "
        "compile + the store's write; not the program's first run) and "
        "the bytes of its file in the store, read or written (0 where "
        "no compile cache is configured); split = those seconds by step "
        "as serving.pins.pin took them: key_s and load_s (the file read "
        "and deserialized), or key_s, lower_s, compile_s and write_s "
        "(serialized, deflated and written)"),
    "foldin_solve_path": (
        ("side", "rank", "rows", "width", "path", "reason"),
        "one per fold-in program FoldInServer.prewarm compiled and ran "
        "(side user|item, padded rows x width): the solve it takes, under "
        "core.als.resolve_solve_path's names, and why — from "
        "core.foldin.solve_path, the function fold_in dispatches on"),
    "serve_degraded": (
        ("strategy", "reason"),
        "a sharded top-k request fell back to last-good gathered "
        "factors after a gather failure"),
    "preempted": (
        ("iteration", "signum"),
        "training stopped at an iteration boundary after SIGTERM/"
        "SIGINT; a resumable checkpoint was written if a checkpoint "
        "dir is configured"),
    "checkpoint_quarantined": (
        ("path", "reason"),
        "load_factors moved a corrupt checkpoint generation aside to "
        ".corrupt/ (and fell back to .old when present)"),
    "guardrail_tripped": (
        ("iteration", "sentinel", "mode"),
        "a numerical-health sentinel fired at a training iteration "
        "boundary (resilience.guardrails; sentinel is one of "
        "nonfinite|norm_band|trend)"),
    "train_rollback": (
        ("iteration", "attempt", "sentinel", "reg_param"),
        "recover-mode guardrails restored the last-good factor "
        "snapshot (seeded perturbation + regularization bump) and are "
        "retrying the iteration"),
    "ingest_quarantined": (
        ("path", "rows", "reasons"),
        "one per ingest call that quarantined records: total rows "
        "routed to the sink and the per-reason breakdown "
        "(malformed/nonfinite/out_of_range); mirrors checkpoint's "
        ".corrupt/ convention"),
    "warning": (
        ("what", "reason"),
        "a degraded-but-continuing condition (e.g. profiler trace "
        "skipped because one is already active; what='jax.compile': a "
        "program reached the backend's compile call under traffic — "
        "fun_name, seconds, cache and phase='traffic' name it)"),
    "snapshot": (
        ("counters", "gauges", "histograms"),
        "final registry state, appended once by finalize() so the JSONL "
        "alone reconstructs every counter/gauge/histogram"),
    "scenario_start": (
        ("scenario", "phases"),
        "a scenario run began: its name, phase list, and effective "
        "config (tpu_als.scenario.runner)"),
    "scenario_phase": (
        ("scenario", "phase", "seconds"),
        "one scenario phase completed, with its wall-clock seconds"),
    "scenario_assert": (
        ("scenario", "check", "ok", "observed", "expected"),
        "one scenario assertion judged: observed value vs bound (the "
        "verdict is re-derivable from these events alone)"),
    "scenario_end": (
        ("scenario", "passed", "seconds"),
        "a scenario run finished (or aborted on a phase failure, with "
        "an extra 'error' field): the verdict and total seconds"),
    "flight_record": (
        ("seq", "trigger", "status", "spans"),
        "one per-request trace dumped by the serving flight recorder "
        "on an SLO breach, shed, or degraded-mode answer: spans is the "
        "admission/queue_wait/score/respond breakdown in seconds and "
        "batch the engine's batch counter; the same triggers dump the "
        "engine's per-batch records (spans keyed by "
        "SERVE_BATCH_SPAN_KEYS, with batch, t0, bucket, rows, waiting, "
        "closed_by = slot|full|closed|age|wait as serving.batch_closed's "
        "by, head_wait = seconds the batch's oldest request had waited "
        "as the consumer arrived, lock_wait = seconds the engine thread "
        "waited for the user table's lock, inside serve.batch.stage, "
        "in_flight = 0|1 batches handed over and not yet completed as "
        "this one was dispatched, as serving.batch_overlap's label, "
        "handoff_wait = seconds the engine thread waited, before it "
        "dequeued this batch, for one of two batches in flight to "
        "complete, completion_idle = seconds the completion thread had "
        "waited for a batch when this one was handed over, upload / "
        "launch = seconds of the two child spans of dispatch "
        "(SERVE_DISPATCH_SPAN_KEYS; upload + launch <= dispatch), "
        "upload_how = call|put_one|put as the upload span's how (call: "
        "the staged batch rode the scoring call as its host argument, its "
        "transfer is inside launch; put_one: a mesh engine placed it on "
        "the mesh's first device, inside upload, and the program spread "
        "it), cpu = "
        "{stage, dispatch, readback, complete}: seconds of the thread's "
        "own CPU time in each phase, beside the phase's wall seconds in "
        "spans — wall - CPU is time the thread held no processor; each "
        "None for a batch that no profiler session watched: the CPU "
        "clock is read only where a trace holds it); the "
        "live updater's per-batch records carry foldin_cpu and "
        "publish_cpu the same way "
        "(obs.trace.FlightRecorder)"),
    "attribution": (
        ("stages", "wall_s_per_iter", "coverage"),
        "one per `observe attribution` run: measured per-stage seconds "
        "joined against the roofline floor (the planner's measured-"
        "probe input format)"),
    "plan_resolved": (
        ("key", "component", "source", "resolved"),
        "the execution planner settled a plan component (solve path / "
        "top-k backend / gather strategy / serving buckets): the plan "
        "key, whether the verdict came from 'cache' or a fresh 'probe' "
        "walk, and the resolved value (tpu_als.plan.planner)"),
    "plan_probe": (
        ("kernel", "outcome", "seconds"),
        "one probe consultation spent by a COLD plan resolve (the "
        "per-kernel verdicts newly cached during the walk, plus one "
        "'walk:<component>' record for the walk itself); a warm-cache "
        "resolve emits none — the warm-start tests pin exactly that"),
    "plan_cache_hit": (
        ("key", "component", "path", "seeded"),
        "a plan component resolved from the persistent autotune cache: "
        "entry path and how many banked probe verdicts were seeded "
        "into the in-process registry (zero probe executions)"),
    "live_update": (
        ("seq", "events", "touched", "mode"),
        "one per live-updater micro-batch published: the resulting "
        "publish seq, rating events folded, catalog rows touched, and "
        "the publish mode (retag|delta|compact|full|none) "
        "(tpu_als.live.updater)"),
    "live_landing": (
        ("seq", "snapshot", "users", "items", "catchup_events",
         "catchup_users", "catchup_items", "programs", "placed_bytes",
         "peak_bytes", "seconds"),
        "one per refit landed on a running live updater "
        "(LiveUpdater.land): the publish seq of the landed generation, "
        "the snapshot (admission count) the refit's data ended at, the "
        "tables' live rows, the events admitted since the snapshot and "
        "the users and items the catch-up folded again, the programs "
        "compiled meanwhile (0 after a start with refits=True), the "
        "bytes of whole tables handed to the device, the device's peak "
        "bytes so far and the landing's wall seconds"),
    "live_freshness_breach": (
        ("seq", "freshness_seconds", "slo_s"),
        "a live update's arrival->servable freshness exceeded the SLO; "
        "the updater's flight-recorder tail (queue_wait/quarantine/"
        "foldin/publish spans) is dumped alongside with "
        "trigger='freshness_breach'"),
    "tenant_registered": (
        ("tenant", "users", "items", "shape_class"),
        "one per TenantRegistry.register: the tenant's published table "
        "sizes and its planner shape-class (tenants sharing a "
        "shape-class share the plan-cache entry and, with equal "
        "rank/buckets, the compiled scoring executables)"),
    "tenant_removed": (
        ("tenant",),
        "a tenant was deregistered from the control plane; its engine "
        "was stopped and its device buffers released"),
    "trace_span": (
        ("trace_id", "span_id", "parent_id", "name", "status",
         "seconds"),
        "one causal-trace hop (tpu_als.obs.tracing): deterministic "
        "trace/span/parent ids link admission -> queue -> scheduler "
        "round -> score -> publish -> visible across serve/live/"
        "tenancy; `tpu_als observe explain` rebuilds the tree from "
        "these events alone (name in TRACE_SPANS, status in "
        "TRACE_STATUSES; seconds may be null for instantaneous hops)"),
    "device_lost": (
        ("iteration", "lost", "surviving"),
        "the elastic detector classified a failed collective/ring step "
        "as device loss: the health probe (bounded retry backoff) "
        "exhausted on the named logical device ids; 'surviving' is how "
        "many devices stay in the mesh (resilience.elastic)"),
    "mesh_reformed": (
        ("old_devices", "new_devices", "lost"),
        "the mesh was rebuilt from the surviving logical device ids and "
        "the shard plan / bucket schedule re-derived through the "
        "planner for the new device count (api.fitting elastic "
        "recovery)"),
    "elastic_resume": (
        ("iteration", "source", "devices"),
        "training re-entered the (shrunk) ring at an iteration "
        "boundary: from the last atomic checkpoint ('checkpoint', with "
        "its path in an extra field) or from the seed-deterministic "
        "init ('scratch' — the quarantined epoch is re-run in full)"),
    "plan_cache_miss": (
        ("key", "component", "reason"),
        "a plan component was not servable from the cache (reason: "
        "absent|component_absent|corrupt) — a probe walk follows and "
        "its verdict is banked; 'corrupt' means the entry file was "
        "quarantined to .corrupt/ first"),
    "plan_tuned": (
        ("key", "component", "source", "config", "measured_seconds",
         "model_seconds"),
        "the measured-timing autotuner banked a kernel config into the "
        "plan entry: the winning knobs (panel/vmem_budget/max_wc/depth/"
        "dtype), the min-of-k measured seconds next to the roofline "
        "closed-form prediction, and whether the timings came from the "
        "'device' or the CPU 'interpret' path — interpret verdicts "
        "never override an on-chip one (tpu_als.plan.planner)"),
    "tune_trial": (
        ("kernel", "config", "seconds"),
        "one autotune search trial: the kernel timed, the candidate "
        "config, and its min-of-k seconds (tpu_als.perf.autotune); a "
        "warm kernel-config resolve emits none — autotune_smoke pins "
        "exactly that"),
    "soak_start": (
        ("windows", "window_s", "tenants", "seed"),
        "a production-week soak began: the compressed timeline "
        "(windows x window_s seconds), the tenant mix, and the traffic "
        "seed; 'scheduled_injections' (extra field) is the chaos "
        "schedule's size — the verdict's injections_observed check "
        "compares against it (tpu_als.soak.orchestrator)"),
    "soak_window": (
        ("window", "offered", "answered", "shed", "errors"),
        "one soak window's serve outcome totals plus a 'tenants' extra "
        "field mapping tenant -> {offered, answered, shed, errors, "
        "p99_ms} — the verdict judges victim-free tenants from these "
        "per-window records alone"),
    "soak_injection": (
        ("window", "action", "fired", "recovered"),
        "one scheduled chaos injection's outcome: the window it landed "
        "in, the action performed, whether the fault observably fired, "
        "and whether its recovery evidence made it into the trail "
        "before the window closed; 'victim' and 'spec' ride as extra "
        "fields"),
    "soak_verdict": (
        ("passed", "survived_minutes", "checks"),
        "the soak's SLO verdict as judged from the trail (tpu_als/soak/"
        "verdict.py — stdlib-only, so the same verdict re-derives "
        "offline from events.jsonl alone)"),
}


def check_metric(name, kind):
    """Raise if ``name`` is undeclared or declared with another kind."""
    decl = METRICS.get(name)
    if decl is None:
        raise KeyError(
            f"metric {name!r} is not declared in tpu_als.obs.schema."
            "METRICS — declare it there (and in docs/observability.md) "
            "before emitting it")
    if decl[0] != kind:
        raise TypeError(
            f"metric {name!r} is declared as a {decl[0]}, used as a "
            f"{kind}")


def check_labels(name, labels):
    """Raise if a write attaches a label key ``name``'s LABELS row does
    not declare (no row = no labels).  Values are free; KEYS are the
    vocabulary — each declared key is one series dimension downstream
    readers aggregate over."""
    if not labels:
        return
    allowed = LABELS.get(name, ())
    unknown = sorted(k for k in labels if k not in allowed)
    if unknown:
        raise ValueError(
            f"metric {name!r} does not declare label key(s) {unknown} "
            f"(declared: {list(allowed)}) — add them to "
            "tpu_als.obs.schema.LABELS before writing the series")


def check_start_phase(name):
    """Raise if ``name`` is no declared phase of a start."""
    if name not in START_PHASES:
        raise KeyError(
            f"start phase {name!r} is not declared in tpu_als.obs."
            "schema.START_PHASES — declare it there (and in "
            "docs/observability.md) before opening it")


def check_trace_span(name, status="ok"):
    """Raise if a causal-trace span names an undeclared hop or ends in
    an undeclared status — span names are vocabulary exactly like
    metric names (``observe explain`` renders only declared hops)."""
    if name not in TRACE_SPANS:
        raise KeyError(
            f"trace span {name!r} is not declared in tpu_als.obs."
            "schema.TRACE_SPANS — declare it there (and in "
            "docs/observability.md) before recording it")
    if status not in TRACE_STATUSES:
        raise ValueError(
            f"trace span {name!r} carries undeclared status {status!r} "
            f"(declared: {list(TRACE_STATUSES)})")


def check_event(etype, fields):
    """Raise if ``etype`` is undeclared or missing a required field."""
    decl = EVENTS.get(etype)
    if decl is None:
        raise KeyError(
            f"event type {etype!r} is not declared in tpu_als.obs."
            "schema.EVENTS — declare it there (and in "
            "docs/observability.md) before emitting it")
    missing = [f for f in decl[0] if f not in fields]
    if missing:
        raise ValueError(
            f"event {etype!r} is missing required field(s) {missing} "
            f"(declared: {list(decl[0])})")
