"""Well-known metric families for the runtime's hot subsystems.

Declared HERE (not in the subsystems) so that importing
``paddle_tpu.observe`` alone materializes every family with zeroed
default children: a snapshot dumped by a process that died before
reaching the executor still carries the full executor/RPC schema — the
diagnosis is "0 cache misses, 0 RPC calls", not an absent file.
Subsystems import their families from here and only ever
increment/observe.
"""

from __future__ import annotations

from .metrics import Registry

__all__ = ["REGISTRY"]

REGISTRY = Registry()

# ------------------------------------------------------------- executor
EXECUTOR_CACHE_HITS = REGISTRY.counter(
    "paddle_executor_cache_hits_total",
    "Plan-cache hits in Executor._gather (program+feed-signature key)")
EXECUTOR_CACHE_MISSES = REGISTRY.counter(
    "paddle_executor_cache_misses_total",
    "Plan-cache misses of Executor and ParallelEngine (each one costs "
    "an analyze_block + jit wrap: the executor.prepare span)")
EXECUTOR_STEPS = REGISTRY.counter(
    "paddle_executor_steps_total",
    "Train/eval steps executed (run_repeated counts all K scanned steps)")
EXECUTOR_PREPARE_SECONDS = REGISTRY.histogram(
    "paddle_executor_prepare_seconds",
    "Wall time of Executor._prepare / ParallelEngine._prepare "
    "(verification, pass pipeline, block analysis, jit wrap): the "
    "executor.prepare span's duration")
EXECUTOR_COMPILE_SECONDS = REGISTRY.histogram(
    "paddle_executor_compile_seconds",
    "Wall time of a dispatch inside which JAX traced, lowered, compiled "
    "or loaded a program (the listener of observe/trace.py saw a stage "
    "end in it), whichever time round: a plan's first dispatch, and "
    "every later one that loaded its program again. Every other "
    "dispatch lands in paddle_executor_run_seconds. With "
    "PADDLE_TPU_TRACE=0 nothing listens and the first dispatch of each "
    "signature is taken for the loading one")
PROGRAM_LOADS = REGISTRY.counter(
    "paddle_executor_program_loads_total",
    "Backend stages JAX ran (one a program it made executable), by "
    "where the executable came from: cache='hit' loaded from the "
    "persistent compilation cache, 'miss' compiled by XLA and written "
    "to it, 'off' compiled with no cache entry written (cache not "
    "enabled, or the program under its thresholds); again='1' = this "
    "(plan, function) had a backend stage before in this process. "
    "again='1' rising in a serving process is a recompilation in "
    "production (docs/OBSERVABILITY.md)", labels=("cache", "again"))
for _c in ("hit", "miss", "off"):
    for _a in ("0", "1"):
        PROGRAM_LOADS.labels(cache=_c, again=_a)
PROGRAM_LOAD_SECONDS = REGISTRY.counter(
    "paddle_executor_program_load_seconds_total",
    "Seconds JAX spent making programs executable, by stage: 'trace' "
    "(function to jaxpr; a trace nested in another's is counted once), "
    "'lower' (jaxpr to MLIR module), 'backend' (XLA compile or "
    "persistent-cache load). A restarted replica's cold start by stage; "
    "the ring's executor.load.* spans say which program",
    labels=("stage",))
for _s in ("trace", "lower", "backend"):
    PROGRAM_LOAD_SECONDS.labels(stage=_s)
EXECUTOR_RUN_SECONDS = REGISTRY.histogram(
    "paddle_executor_run_seconds",
    "Steady-state step latency, split by phase: 'dispatch' is the async "
    "hand-off (host time until the XLA launch returns), 'complete' is "
    "dispatch-to-results-ready (only observed when the host actually "
    "blocks, e.g. return_numpy or an explicit wait). For "
    "site=run_pipelined, 'complete' measures dispatch to FIRST host "
    "block on the step's FetchHandle — by design ~max_in_flight steps "
    "late, so it reads higher than site=run without the step being "
    "slower; compare 'dispatch' across sites, not 'complete'",
    labels=("site", "phase"))
for _site in ("run", "run_repeated", "run_pipelined"):
    for _phase in ("dispatch", "complete"):
        # pre-materialize the per-site/phase series (schema-is-the-signal,
        # same as the RPC methods below)
        EXECUTOR_RUN_SECONDS.labels(site=_site, phase=_phase)
EXECUTOR_CACHE_EVICTIONS = REGISTRY.counter(
    "paddle_executor_plan_cache_evictions_total",
    "Plans evicted from the size-capped executor LRU "
    "(PADDLE_TPU_EXECUTOR_CACHE_SIZE); sustained growth = shape churn")
FEED_TO_RUN_GAP_SECONDS = REGISTRY.histogram(
    "paddle_feed_to_run_gap_seconds",
    "Gap between the input pipeline handing over a batch and the next "
    "executor dispatch starting — input-bound vs compute-bound signal. "
    "Unpipelined runs stamp at host-batch production, so the gap "
    "includes the blocking H2D convert; DevicePrefetcher stamps at "
    "device-resident hand-off, so a working pipeline shows ~µs gaps")

# ------------------------------------------------------------- pipeline
PIPELINE_PREFETCH_DEPTH = REGISTRY.gauge(
    "paddle_pipeline_prefetch_queue_depth",
    "Device-resident batches currently queued in DevicePrefetcher "
    "(0 while compute-bound consumers drain faster than the reader). "
    "Process-global, last-writer-wins: meaningful with ONE live "
    "pipeline; concurrent prefetchers overwrite each other and close() "
    "zeroes it")
PIPELINE_IN_FLIGHT = REGISTRY.gauge(
    "paddle_pipeline_in_flight_steps",
    "Dispatched-but-unresolved DISPATCH UNITS in run_pipelined's "
    "in-flight window: steps in the classic loop, K-step scanned "
    "windows under whole-loop compilation (a reading of 2 at "
    "steps_per_call=25 means 50 training steps in flight)")
PIPELINE_H2D_BYTES = REGISTRY.counter(
    "paddle_pipeline_h2d_bytes_total",
    "Feed bytes transferred host->device by DevicePrefetcher")
PIPELINE_H2D_SECONDS = REGISTRY.histogram(
    "paddle_pipeline_h2d_seconds",
    "Per-hand-off DevicePrefetcher convert + device_put + ready wall "
    "time (off the step loop's critical path): one observation per "
    "batch in the classic loop, one per K-batch stacked WINDOW under "
    "whole-loop compilation (the single device_put that amortizes "
    "per-batch H2D call overhead)")
PIPELINE_WAIT_SECONDS = REGISTRY.histogram(
    "paddle_pipeline_wait_seconds",
    "Time run_pipelined blocked on the OLDEST in-flight step — at the "
    "window cap before dispatching the next one, or draining the last "
    "max_in_flight steps after the reader ran dry")
PIPELINE_OVERLAP_RATIO = REGISTRY.gauge(
    "paddle_pipeline_overlap_ratio",
    "1 - fetch-blocked/wall for the last run_pipelined loop: ~1.0 = the "
    "in-flight window never stalled dispatch, ~0 = the loop serialized "
    "on waits for the oldest step's results. Measures WINDOW waits only "
    "— an input-starved loop also reads ~1.0; diagnose starvation via "
    "prefetch_queue_depth ~0 (the feed->run gap is stamped at queue "
    "hand-off, so it stays ~µs even while the consumer starves)")
PIPELINE_CONST_HITS = REGISTRY.counter(
    "paddle_pipeline_const_feed_hits_total",
    "Feeds served from the const-feed dedup cache (H2D skipped)")
PIPELINE_CONST_BYTES_SAVED = REGISTRY.counter(
    "paddle_pipeline_const_feed_bytes_saved_total",
    "H2D bytes avoided by const-feed dedup hits")

# ------------------------------------------- pipeline: windowed dispatch
# (whole-loop compilation: run_pipelined/train_loop with steps_per_call
# K > 1 scan K batches per device dispatch — see docs/PERFORMANCE.md
# "Whole-loop compilation". `stats_dump --grep paddle_pipeline_window`
# is the one-liner that shows whether the amortization engaged.)
PIPELINE_WINDOW_SIZE = REGISTRY.gauge(
    "paddle_pipeline_window_size",
    "Resolved steps_per_call K of the last windowed run_pipelined loop "
    "(the explicit argument, else PADDLE_TPU_STEPS_PER_CALL, else 1 = "
    "the classic one-dispatch-per-step loop)")
PIPELINE_WINDOW_STEPS = REGISTRY.histogram(
    "paddle_pipeline_window_steps_per_dispatch",
    "Steps carried by each windowed scan dispatch — full windows "
    "observe K; the ragged tail's per-step fallback dispatches land in "
    "ragged_steps_total instead of here")
PIPELINE_WINDOW_SECONDS = REGISTRY.histogram(
    "paddle_pipeline_window_seconds",
    "Windowed-dispatch latency by phase: 'dispatch' is the async "
    "hand-off of one K-step scan (host time until the XLA launch "
    "returns — the cost amortized over K steps), 'complete' is "
    "dispatch-to-results-ready, observed when the window's FetchHandle "
    "first blocks (like executor_run_seconds, ~max_in_flight windows "
    "late by design)", labels=("phase",))
for _phase in ("dispatch", "complete"):
    PIPELINE_WINDOW_SECONDS.labels(phase=_phase)
PIPELINE_WINDOW_RAGGED = REGISTRY.counter(
    "paddle_pipeline_window_ragged_steps_total",
    "Steps dispatched through the per-step fallback because the window "
    "could not fill (reader ran dry mid-window, or a batch's shapes "
    "differed from the window in progress) — a ragged tail never "
    "compiles a second scan length")

# ------------------------------------------------------------------ rpc
RPC_CALLS = REGISTRY.counter(
    "paddle_rpc_client_calls_total",
    "RPCClient calls by method", labels=("method",))
RPC_ERRORS = REGISTRY.counter(
    "paddle_rpc_client_errors_total",
    "RPCClient calls that raised RPCError", labels=("method",))
RPC_RETRIES = REGISTRY.counter(
    "paddle_rpc_client_retries_total",
    "Extra attempts beyond the first (get_var init-race polling)",
    labels=("method",))
RPC_DEADLINE_EXPIRATIONS = REGISTRY.counter(
    "paddle_rpc_client_deadline_expirations_total",
    "Calls that exhausted PADDLE_TPU_RPC_DEADLINE_MS", labels=("method",))
RPC_BYTES_SENT = REGISTRY.counter(
    "paddle_rpc_client_bytes_sent_total",
    "Payload bytes pushed through ps_client_send_var")
RPC_BYTES_RECV = REGISTRY.counter(
    "paddle_rpc_client_bytes_recv_total",
    "Payload bytes decoded from get_var/prefetch responses")
RPC_SECONDS = REGISTRY.histogram(
    "paddle_rpc_client_seconds",
    "RPCClient call latency by method", labels=("method",))
RPC_SERVER_REQUESTS = REGISTRY.counter(
    "paddle_rpc_server_requests_total",
    "RPCServer-side operations", labels=("method",))
RPC_COMPRESS_BYTES_SAVED = REGISTRY.counter(
    "paddle_rpc_client_compress_bytes_saved_total",
    "Wire bytes avoided by the gradient-compression hook "
    "(PADDLE_TPU_RPC_COMPRESS=bf16: fp32 grads travel as bf16 and are "
    "decoded back on receipt); 0 while compression is off (default)")
RPC_COMPRESSED_VARS = REGISTRY.counter(
    "paddle_rpc_client_compressed_vars_total",
    "send_var payloads that traveled bf16-encoded")

_RPC_METHODS = ("connect", "send_var", "get_var", "prefetch",
                "send_barrier", "fetch_barrier", "send_complete")
for _m in _RPC_METHODS:
    # pre-materialize the per-method series: a snapshot taken before any
    # RPC ran still shows every method at 0 (the schema IS the signal)
    RPC_CALLS.labels(method=_m)
    RPC_SECONDS.labels(method=_m)
    RPC_ERRORS.labels(method=_m)

# --------------------------------------------------------------- engine
ENGINE_DISPATCHES = REGISTRY.counter(
    "paddle_engine_dispatches_total",
    "ParallelEngine compiled-step dispatches", labels=("site",))
ENGINE_RUN_SECONDS = REGISTRY.histogram(
    "paddle_engine_run_seconds",
    "ParallelEngine dispatch wall time (placement + compiled step)",
    labels=("site",))
ENGINE_COLLECTIVES = REGISTRY.counter(
    "paddle_engine_collectives_total",
    "Explicit collectives EMITTED AT TRACE TIME by op lowerings "
    "(ppermute/all_to_all/...); per compile, not per step",
    labels=("kind",))
ENGINE_DEVICES = REGISTRY.gauge(
    "paddle_engine_device_count", "Mesh size of the last-built engine")

# ----------------------------------------------------------------- data
DATA_BATCHES = REGISTRY.counter(
    "paddle_data_batches_total",
    "Batches produced by the input pipelines", labels=("source",))
for _s in ("reader.batch", "datafeed", "device_prefetcher"):
    DATA_BATCHES.labels(source=_s)

# -------------------------------------------------------- serving
# (serving/queue.py, serving/batcher.py, serving/engine.py and the
# Predictor bucket router — see docs/SERVING.md)
SERVING_QUEUE_DEPTH = REGISTRY.gauge(
    "paddle_serving_queue_depth",
    "Requests currently waiting in the admission queue (RequestQueue); "
    "pinned at capacity = sustained overload, submits are being rejected")
SERVING_QUEUE_WAIT_SECONDS = REGISTRY.histogram(
    "paddle_serving_queue_wait_seconds",
    "Time a request spent queued before admission (submit to the "
    "scheduler popping it); the queue-side half of request latency")
SERVING_QUEUE_REJECTED = REGISTRY.counter(
    "paddle_serving_queue_rejected_total",
    "Submits rejected because the bounded queue was full (backpressure: "
    "the caller gets QueueFull, never a silent drop)")
SERVING_DEADLINE_EXPIRATIONS = REGISTRY.counter(
    "paddle_serving_deadline_expirations_total",
    "Requests whose deadline passed while still queued — they are "
    "failed with DeadlineExpired at pop time, never dispatched")
SERVING_REQUESTS = REGISTRY.counter(
    "paddle_serving_requests_total",
    "Serving requests by terminal outcome and tenant. Cardinality is "
    "bounded by contract: tenant ids are deployment configuration "
    "(router quota keys; 'default' when unset), never caller free text",
    labels=("outcome", "tenant"))
for _o in ("ok", "rejected", "expired", "cancelled", "error"):
    # pre-materialize the schema (same pattern as the RPC methods);
    # only the default tenant — real tenants appear as they submit
    SERVING_REQUESTS.labels(outcome=_o, tenant="default")
SERVING_REQUEST_SECONDS = REGISTRY.histogram(
    "paddle_serving_request_seconds",
    "End-to-end request latency (submit to completion), observed for "
    "requests that completed ok")
SERVING_BATCHES = REGISTRY.counter(
    "paddle_serving_batches_total",
    "Micro-batches dispatched by the dynamic batcher (one Predictor "
    "run each)")
SERVING_BATCH_ROWS = REGISTRY.histogram(
    "paddle_serving_batch_rows",
    "Rows coalesced per micro-batch BEFORE bucket padding — low values "
    "with a deep queue mean the max-wait window is too short")
SERVING_BUCKET_HITS = REGISTRY.counter(
    "paddle_serving_bucket_hits_total",
    "Predictor runs served by a warmup_batch_sizes bucket executable "
    "(exact size or padded up) — steady state should be all hits")
SERVING_BUCKET_MISSES = REGISTRY.counter(
    "paddle_serving_bucket_miss_total",
    "Predictor runs whose batch exceeded every warmup bucket and fell "
    "back to an exact-shape compile — sustained growth = the bucket "
    "list needs a bigger entry")
SERVING_PADDED_ROWS = REGISTRY.counter(
    "paddle_serving_padded_rows_total",
    "Zero rows added by bucket padding (wasted compute rides these)")
SERVING_ROWS = REGISTRY.counter(
    "paddle_serving_rows_total",
    "Real (caller) rows through the Predictor bucket router; "
    "padding waste = padded_rows / (rows + padded_rows)")
SERVING_PADDING_WASTE = REGISTRY.gauge(
    "paddle_serving_padding_waste_ratio",
    "Padding fraction of the LAST routed batch (pad rows / bucket "
    "size); the counters above give the lifetime ratio")
SERVING_SLOTS_ACTIVE = REGISTRY.gauge(
    "paddle_serving_slots_active",
    "Decode slots currently holding a live sequence in the continuous-"
    "batching engine (of engine b_max)")
SERVING_OCCUPANCY = REGISTRY.histogram(
    "paddle_serving_slot_occupancy_ratio",
    "active_slots / b_max observed at every decode step — the engine's "
    "effective batch efficiency; admissions raise it mid-run, "
    "retirements lower it (a lockstep batcher would hold the initial "
    "ratio until the LONGEST request finished)")
SERVING_TTFT_SECONDS = REGISTRY.histogram(
    "paddle_serving_ttft_seconds",
    "Time to first token, one observation a request: submit to the end "
    "of its admission (queue wait + prefill + splice + first sample), "
    "when the first token exists on the host. The duration of the "
    "request's serving.request.first_token span")
SERVING_TOKEN_GAP_SECONDS = REGISTRY.histogram(
    "paddle_serving_token_gap_seconds",
    "Gap between tokens, one observation a decode step: this step's end "
    "minus the previous emission's end on this engine (the previous "
    "step, or an admission in between) — the gap every rider of both "
    "saw, admissions that held the batch up included")
SERVING_ADMITTED = REGISTRY.counter(
    "paddle_serving_slots_admitted_total",
    "Sequences admitted into a free decode slot (prefill-then-insert)")
SERVING_RETIRED = REGISTRY.counter(
    "paddle_serving_slots_retired_total",
    "Sequences retired from their slot (EOS or token budget) — the "
    "slot frees immediately instead of idling until the batch drains")
SERVING_DECODE_STEPS = REGISTRY.counter(
    "paddle_serving_decode_steps_total",
    "Continuous-batching PLAIN decode dispatches (each advances every "
    "active slot by one token); speculative iterations count into "
    "paddle_serving_spec_verify_steps_total instead")
SERVING_STEP_DISPATCHES = REGISTRY.counter(
    "paddle_serving_step_dispatches_total",
    "Plain decode steps by how the loop dispatched them, counted where "
    "it picks: 'ahead' went out before the step before it was read, its "
    "tokens the ids that step leaves on the device (one step in flight: "
    "every rider greedy, no draft lane); 'sync' went out with nothing "
    "in flight, its tokens from the host (the first step after an idle "
    "engine, and every step a sampled rider or a draft lane rides)",
    labels=("dispatch",))
SERVING_OVERRUN_ROWS = REGISTRY.counter(
    "paddle_serving_overrun_rows_total",
    "Rows a step dispatched ahead computed for a rider that the read of "
    "the step before it found finished (its eos_id): written at pos + 1 "
    "of a slot then free, id dropped. A rider that ends by length is "
    "known at dispatch and never counts here")
SERVING_FETCHES = REGISTRY.counter(
    "paddle_serving_fetches_total",
    "What the engine brought to the host to choose tokens from, counted "
    "where it picks the fetch: a plain decode step (site='step') "
    "fetches 'tokens' (b_max ids the program's own argmax chose) when "
    "every rider is at temperature 0 and 'logits' ([b_max, 1, vocab]) "
    "when one samples; an admission (site='admit') fetches 'tokens' "
    "(one id) for a greedy request and 'logits' (the last prompt "
    "position's row; a prefix hit's suffix dispatch: every suffix "
    "position's) for a sampled one. Speculative iterations are not "
    "counted",
    labels=("site", "fetch"))
SERVING_TOKENS = REGISTRY.counter(
    "paddle_serving_tokens_total",
    "Tokens generated by the continuous-batching engine (prefill-"
    "sampled first tokens included)")
SERVING_PREFILL_PROGRAMS = REGISTRY.counter(
    "paddle_serving_prefill_programs_total",
    "Distinct prompt lengths the engine compiled a prefill executable "
    "for — sustained growth = prompt-length churn; bucket prompts")

# ------------------------------------------- serving: fleet tier
# (serving/prefix.py, serving/router.py and the engine's speculative
# decode — see docs/SERVING.md "The fleet tier")
SERVING_PREFIX_HITS = REGISTRY.counter(
    "paddle_serving_prefix_hits_total",
    "Admissions whose prompt matched a stored prefix: the cached K/V "
    "rows were spliced and only the suffix prefilled")
SERVING_PREFIX_MISSES = REGISTRY.counter(
    "paddle_serving_prefix_misses_total",
    "Prefix-store lookups finding no usable stored prefix (full "
    "prefill taken); only counted while a store is attached")
SERVING_PREFIX_TOKENS_SAVED = REGISTRY.counter(
    "paddle_serving_prefix_tokens_saved_total",
    "Prompt tokens NOT prefilled because a stored prefix covered them "
    "(sum of hit lengths) — the cache's work-avoidance in tokens")
SERVING_PREFIX_INSERTS = REGISTRY.counter(
    "paddle_serving_prefix_inserts_total",
    "Prefixes stored (first sighting of a registered prefix boundary)")
SERVING_PREFIX_EVICTIONS = REGISTRY.counter(
    "paddle_serving_prefix_evictions_total",
    "LRU evictions from the byte-capped prefix store — sustained "
    "growth = the cap is smaller than the live shared-prefix set")
SERVING_PREFIX_ENTRIES = REGISTRY.gauge(
    "paddle_serving_prefix_entries",
    "Prefixes currently resident in the store")
SERVING_PREFIX_BYTES = REGISTRY.gauge(
    "paddle_serving_prefix_bytes",
    "Host bytes held by stored prefix K/V rows (capped by the store's "
    "max_bytes)")
SERVING_SPEC_PROPOSED = REGISTRY.counter(
    "paddle_serving_spec_proposed_tokens_total",
    "Draft tokens proposed by the speculative decoder (k per "
    "speculative slot per verify step)")
SERVING_SPEC_ACCEPTED = REGISTRY.counter(
    "paddle_serving_spec_accepted_tokens_total",
    "Draft tokens the target model's greedy verification accepted; "
    "accepted/proposed is THE speculative win rate — at 0 the engine "
    "pays draft cost for nothing, switch the draft model off")
SERVING_SPEC_VERIFY_STEPS = REGISTRY.counter(
    "paddle_serving_spec_verify_steps_total",
    "Target-model verify dispatches (each scores k+1 positions per "
    "slot in ONE dispatch; plain slots ride the same dispatch)")
SERVING_SPEC_DRAFT_STEPS = REGISTRY.counter(
    "paddle_serving_spec_draft_steps_total",
    "Draft-model decode dispatches (k per verify step, plus the "
    "mirror-advance step a plain iteration takes while speculative "
    "slots are in the batch)")
SERVING_ROUTER_ROUTED = REGISTRY.counter(
    "paddle_serving_router_routed_total",
    "Requests the router dispatched, by replica slot index (stable "
    "across restarts — bounded by the replica count); re-admissions "
    "count again at their new replica", labels=("replica",))
SERVING_ROUTER_REJECTED = REGISTRY.counter(
    "paddle_serving_router_rejected_total",
    "Router admission rejections: 'quota' = the tenant's in-flight "
    "cap, 'slo' = projected queue wait exceeded the request deadline "
    "(reject-early: the caller hears no at submit, not after the "
    "deadline burned in a queue), 'backpressure' = every healthy "
    "replica's queue was full, 'memory' = every candidate replica's "
    "predicted-bytes admission guard refused the prefill "
    "(analysis/memory.py)", labels=("reason",))
for _r in ("quota", "slo", "backpressure", "memory"):
    SERVING_ROUTER_REJECTED.labels(reason=_r)
SERVING_MEMORY_HEADROOM = REGISTRY.gauge(
    "paddle_serving_memory_headroom_bytes",
    "Device-budget headroom at the engine's last predicted-bytes "
    "admission check: budget minus the prompt's predicted peak "
    "(negative = that admission was denied). Process-global, "
    "last-writer-wins like prefetch_queue_depth; 0 until an engine "
    "with a configured budget admits — the autoscaler-facing "
    "headroom signal tools/fleet_top.py columns")
SERVING_MEMORY_DENIED = REGISTRY.counter(
    "paddle_serving_memory_admissions_denied_total",
    "Engine submits refused by the predicted-bytes admission guard: "
    "resident bytes (weights + 2L decode-cache slabs) plus the prompt's "
    "predicted prefill peak exceeded the engine's device budget — the "
    "caller hears MemoryBudgetExceeded at submit instead of the "
    "replica OOMing mid-prefill; 0 while no budget is configured")
SERVING_ROUTER_READMITTED = REGISTRY.counter(
    "paddle_serving_router_readmitted_total",
    "In-flight requests re-admitted to a surviving replica after "
    "their replica was drained (wedge/death) — generation restarts "
    "from the prompt; outputs are unaffected (seeded sampling)")
SERVING_ROUTER_RESTARTS = REGISTRY.counter(
    "paddle_serving_router_replica_restarts_total",
    "Replica engine rebuilds by replica slot index (drain + fresh "
    "engine via the factory)", labels=("replica",))
SERVING_ROUTER_HEALTHY = REGISTRY.gauge(
    "paddle_serving_router_replicas_healthy",
    "Replicas currently accepting work (started, scheduler alive, "
    "not draining)")
SERVING_ROUTER_PROJECTED_WAIT = REGISTRY.histogram(
    "paddle_serving_router_projected_wait_seconds",
    "The router's projected queue wait at admission (outstanding "
    "tokens on the chosen replica / estimated token rate) — the "
    "quantity the SLO reject-early check compares to the deadline")

# ----------------------------------------------------------- resilience
# (paddle_tpu/resilience/: fault injection, wedge watchdog, checkpoint-
# resume supervisor — see docs/RESILIENCE.md)
RESILIENCE_FAULTS_INJECTED = REGISTRY.counter(
    "paddle_resilience_faults_injected_total",
    "Faults injected by the armed FaultPlan (resilience/faults.py), by "
    "site and mode — chaos tests assert on these instead of trusting "
    "the injection happened", labels=("site", "mode"))
FAULT_SITES = ("executor.dispatch", "device_put", "rpc.send",
               "reader.next", "checkpoint.write",
               "trainer.heartbeat", "membership.join")
for _site in FAULT_SITES:
    for _mode in ("raise", "delay", "wedge", "crash"):
        # pre-materialize the full site x mode schema (schema-is-the-
        # signal: a sidecar from a crashed chaos run still shows every
        # site at 0 except the one that fired)
        RESILIENCE_FAULTS_INJECTED.labels(site=_site, mode=_mode)
RESILIENCE_FAULT_SITES_ARMED = REGISTRY.gauge(
    "paddle_resilience_fault_sites_armed",
    "Fault specs armed in the currently installed FaultPlan "
    "(0 = injection plane inactive)")
RESILIENCE_WEDGES = REGISTRY.counter(
    "paddle_resilience_wedges_detected_total",
    "Watchdog wedge detections: a heartbeat-stamped operation ran past "
    "its deadline with no progress stamp (one count per stalled "
    "operation, not per poll)", labels=("site",))
for _site in ("executor.dispatch", "executor.wait"):
    RESILIENCE_WEDGES.labels(site=_site)
RESILIENCE_HEARTBEAT_AGE = REGISTRY.gauge(
    "paddle_resilience_heartbeat_age_seconds",
    "Age of the OLDEST still-open heartbeat operation at the "
    "watchdog's last poll; 0 while the process is idle (only written "
    "while paddle_resilience_watchdog_armed is 1)")
RESILIENCE_WATCHDOG_ARMED = REGISTRY.gauge(
    "paddle_resilience_watchdog_armed",
    "1 while a Watchdog thread is polling heartbeats")
RESILIENCE_RECOVERIES = REGISTRY.counter(
    "paddle_resilience_recoveries_total",
    "resilient_train_loop recoveries by kind: 'resume' reloaded the "
    "latest manifest checkpoint and fast-forwarded the reader, "
    "'restart' re-ran the startup program (no durable checkpoint yet)",
    labels=("kind",))
for _k in ("resume", "restart"):
    RESILIENCE_RECOVERIES.labels(kind=_k)
RESILIENCE_CHECKPOINTS = REGISTRY.counter(
    "paddle_resilience_checkpoints_total",
    "Supervisor checkpoints by status: 'written' = durable + manifest "
    "updated, 'pruned' = retired by retain-last-K, 'failed' = the "
    "async write raised (previous checkpoint stays latest)",
    labels=("status",))
for _s in ("written", "pruned", "failed"):
    RESILIENCE_CHECKPOINTS.labels(status=_s)
RESILIENCE_CHECKPOINT_SECONDS = REGISTRY.histogram(
    "paddle_resilience_checkpoint_seconds",
    "Train-loop wall time spent launching one periodic checkpoint "
    "(device->host snapshot + finalizing the previous write; the disk "
    "write itself runs on the background thread)")
RESILIENCE_BACKOFF_SECONDS = REGISTRY.histogram(
    "paddle_resilience_retry_backoff_seconds",
    "Full-jitter backoff sleeps taken before a supervisor recovery "
    "attempt")
RESILIENCE_FF_BATCHES = REGISTRY.counter(
    "paddle_resilience_fast_forward_batches_total",
    "Reader batches consumed and discarded while fast-forwarding to "
    "the resumed step after a checkpoint reload")
RESILIENCE_ORPHANS_CLEANED = REGISTRY.counter(
    "paddle_resilience_checkpoint_orphans_cleaned_total",
    "Stale checkpoint staging (.tmp) files left by DEAD writer "
    "processes, removed by a later save to the same path")
RESILIENCE_RESTARTS = REGISTRY.counter(
    "paddle_resilience_restarts_total",
    "resilient_train_loop retry-loop restarts by the exception class "
    "being retried ('other' folds anything outside the pre-declared "
    "set) — the flight recorder has the traceback, this has the rate",
    labels=("cause",))
RESTART_CAUSES = ("InjectedFault", "RPCError", "PeerGoneError", "other")
for _c in RESTART_CAUSES:
    RESILIENCE_RESTARTS.labels(cause=_c)

# -------------------------------------------------------------- elastic
# (resilience/elastic.py + distributed/membership.py: elastic multi-host
# training — membership, lease eviction, deterministic reshard-from-
# manifest. See docs/RESILIENCE.md "Elastic jobs".)
ELASTIC_EVENTS = REGISTRY.counter(
    "paddle_elastic_membership_events_total",
    "Trainer membership transitions seen by the registry: 'join' = "
    "first heartbeat of an unknown trainer, 'rejoin' = heartbeat from "
    "a previously evicted/left trainer, 'leave' = graceful goodbye, "
    "'evict' = lease expired or the worker process died",
    labels=("event",))
for _e in ("join", "rejoin", "leave", "evict"):
    ELASTIC_EVENTS.labels(event=_e)
ELASTIC_TRAINERS_ACTIVE = REGISTRY.gauge(
    "paddle_elastic_trainers_active",
    "Trainers currently holding a live (unexpired) membership lease")
ELASTIC_GENERATION = REGISTRY.gauge(
    "paddle_elastic_generation",
    "The elastic job's current generation (bumps on every reshard; a "
    "long-running job sitting at 0 never lost or gained a trainer)")
ELASTIC_HEARTBEATS = REGISTRY.counter(
    "paddle_elastic_heartbeats_total",
    "Trainer heartbeats drained by the membership registry")
ELASTIC_RESHARDS = REGISTRY.counter(
    "paddle_elastic_reshards_total",
    "Deterministic reshard-from-manifest executions, by the membership "
    "change that forced them", labels=("cause",))
for _c in ("evict", "join", "leave"):
    ELASTIC_RESHARDS.labels(cause=_c)
ELASTIC_RESHARD_SECONDS = REGISTRY.histogram(
    "paddle_elastic_reshard_seconds",
    "Wall time of one reshard's teardown phase: stopping the old "
    "generation's workers + archiving the checkpoint state it resumes "
    "from. The next generation's spawn/compile cost shows up as the "
    "gap to its first heartbeat in the job timeline, not here")
ELASTIC_JOINS_DROPPED = REGISTRY.counter(
    "paddle_elastic_joins_dropped_total",
    "Join/rejoin announcements dropped by an armed membership.join "
    "fault (partition simulation) — the trainer's next heartbeat "
    "retries the join")
ELASTIC_WORLD_FALLBACKS = REGISTRY.counter(
    "paddle_elastic_manifest_world_fallbacks_total",
    "Manifests whose 'world' section could not be used: 'missing' = "
    "pre-elastic manifest loaded as a single-trainer world, "
    "'malformed' = unusable section degraded to a fresh-start world "
    "(counted, never a crash)", labels=("kind",))
for _k in ("missing", "malformed"):
    ELASTIC_WORLD_FALLBACKS.labels(kind=_k)

# ------------------------------------------------------------- analysis
# (paddle_tpu/analysis/: static program verifier — see docs/ANALYSIS.md)
ANALYSIS_PROGRAMS = REGISTRY.counter(
    "paddle_analysis_programs_verified_total",
    "Programs run through analysis.verify_program, by trigger: "
    "'validate' = explicit Program.validate(), 'prepare' = executor "
    "prepare-time checking (PADDLE_TPU_VALIDATE=1), 'cli' = "
    "tools/lint_program.py", labels=("site",))
for _s in ("validate", "prepare", "cli", "capture"):
    ANALYSIS_PROGRAMS.labels(site=_s)
ANALYSIS_FINDINGS = REGISTRY.counter(
    "paddle_analysis_findings_total",
    "Verifier findings by rule (severity folded into the rule's "
    "contract — see the catalog in docs/ANALYSIS.md); errors also "
    "raise ProgramVerifyError at validate/prepare", labels=("rule",))
# pre-materialize the rule schema (import placed at the bottom of this
# module would cycle; the analysis package declares its rule list as a
# plain tuple precisely so this stays a data dependency)
_ANALYSIS_RULES = (
    "shape-infer", "shape-annotation", "dtype-annotation",
    "unregistered-op", "def-before-use", "undefined-input",
    "fetch-undefined", "dead-var", "dead-op", "double-write",
    "int64-feed", "int64-narrowing", "grad-pairing", "sub-block",
    # dataflow-engine-powered rules (analysis/dataflow.py)
    "dead-store", "write-after-write", "use-before-init",
    # range-engine-powered numerics rules (analysis/ranges.py)
    "bf16-overflow", "domain-violation", "int-narrowing-loss",
    # memory-engine-powered rules (analysis/memory.py)
    "memory-over-budget", "max-safe-batch", "dead-persistable")
for _r in _ANALYSIS_RULES:
    ANALYSIS_FINDINGS.labels(rule=_r)
ANALYSIS_VERIFY_SECONDS = REGISTRY.histogram(
    "paddle_analysis_verify_seconds",
    "Wall time of one verify_program pass (shape inference + lint "
    "suite) — scales with op count, not with tensor sizes")

# value-range abstract interpretation (analysis/ranges.py — see
# docs/ANALYSIS.md "The range engine")
ANALYSIS_RANGES_PROGRAMS = REGISTRY.counter(
    "paddle_analysis_ranges_programs_total",
    "Programs run through the value-range abstract interpreter "
    "(RangeAnalysis construction): once per lint run that activates a "
    "range-powered rule, per quantize-pass application, per "
    "lint_program.py --ranges invocation")
ANALYSIS_RANGES_VARS = REGISTRY.counter(
    "paddle_analysis_ranges_vars_total",
    "Variables classified per analysis, by final interval kind: "
    "'const' = exact compile-time literal, 'bounded' = finite "
    "[lo, hi], 'finite' = provably no inf/nan but unbounded, 'top' = "
    "nothing provable (incl. the declared WIDEN_TO_TOP widenings)",
    labels=("kind",))
for _k in ("const", "bounded", "finite", "top"):
    ANALYSIS_RANGES_VARS.labels(kind=_k)
ANALYSIS_RANGES_WIDENED = REGISTRY.counter(
    "paddle_analysis_ranges_widened_total",
    "Explicit widenings to T, by reason: 'declared' = the op is in "
    "range_rules.WIDEN_TO_TOP (or a *_grad), 'unknown-op' = no rule "
    "and no declaration (repo_lint rule 7 keeps this 0 for shape-ruled "
    "ops), 'loop' = a loop body's write did not stabilize in the "
    "bounded fixpoint, 'rule-error' = a transfer function crashed "
    "(widen, never sink the analysis)", labels=("reason",))
for _r in ("declared", "unknown-op", "loop", "rule-error"):
    ANALYSIS_RANGES_WIDENED.labels(reason=_r)
ANALYSIS_RANGES_SECONDS = REGISTRY.histogram(
    "paddle_analysis_ranges_seconds",
    "Wall time of one whole-program range analysis (scales with op "
    "count; scope-value reads are opt-in and excluded by default)")
ANALYSIS_RANGES_CALIBRATION_BATCHES = REGISTRY.counter(
    "paddle_analysis_ranges_calibration_batches_total",
    "Feed batches observed by an attached ranges.Calibration (the "
    "executor feed-observer hook): N batches = N increments")

# static peak-HBM estimation (analysis/memory.py — see docs/ANALYSIS.md
# "The memory engine")
ANALYSIS_MEMORY_PROGRAMS = REGISTRY.counter(
    "paddle_analysis_memory_programs_total",
    "Programs run through the liveness-based peak-HBM estimator "
    "(MemoryAnalysis construction), by trigger: 'lint' = the memory "
    "lint rules, 'cli' = tools/memory_report.py, 'serving' = the engine "
    "admission "
    "guard, 'dist' = the "
    "distributed verifier's per-pserver shard-fit proof, 'api' = "
    "direct callers (contrib.memory_usage_calc and user code)",
    labels=("site",))
for _s in ("api", "lint", "cli", "serving", "capture", "dist"):
    ANALYSIS_MEMORY_PROGRAMS.labels(site=_s)
ANALYSIS_MEMORY_SECONDS = REGISTRY.histogram(
    "paddle_analysis_memory_seconds",
    "Wall time of one whole-program memory analysis (scales with op "
    "count, never with tensor sizes — bytes ride shape algebra)")

# ------------------------------------------------------------ cost engine
# (paddle_tpu/analysis/cost.py: the roofline cost model — per-op
# FLOPs/bytes rules composed into predicted step seconds)
ANALYSIS_COST_PROGRAMS = REGISTRY.counter(
    "paddle_cost_programs_total",
    "Programs run through the roofline cost engine (CostAnalysis "
    "construction), by trigger: 'cli' = tools/cost_report.py, "
    "'api' = direct callers",
    labels=("site",))
for _s in ("api", "cli"):
    ANALYSIS_COST_PROGRAMS.labels(site=_s)
ANALYSIS_COST_SECONDS = REGISTRY.histogram(
    "paddle_cost_seconds",
    "Wall time of one whole-program cost analysis (scales with op "
    "count — FLOPs/bytes ride shape algebra, never tensor payloads)")
ANALYSIS_COST_UNRULED = REGISTRY.counter(
    "paddle_cost_unruled_ops_total",
    "Ops priced WITHOUT a registered cost rule (bytes-only, zero "
    "FLOPs): the engine's coverage debt. The shape-ruled vocabulary "
    "can never land here — tools/repo_lint.py rule 10 proves every "
    "shape-ruled op carries a cost rule or a ZERO_COST declaration")

# ------------------------------------------------ distributed verifier
# (paddle_tpu/analysis/distributed.py: the cross-program wire/shard/
# deadlock verifier over transpiler output — see docs/ANALYSIS.md
# "Distributed verification")
ANALYSIS_DIST_JOBS = REGISTRY.counter(
    "paddle_analysis_dist_jobs_verified_total",
    "Distributed jobs (trainer + pserver program sets) run through "
    "analysis.validate_distributed, by trigger: 'api' = direct "
    "callers, 'cli' = tools/lint_distributed.py, 'elastic' = the "
    "elastic tier verifying a reshard generation's world pre-launch "
    "(PADDLE_TPU_VALIDATE=1)", labels=("site",))
for _s in ("api", "cli", "elastic"):
    ANALYSIS_DIST_JOBS.labels(site=_s)
ANALYSIS_DIST_FINDINGS = REGISTRY.counter(
    "paddle_analysis_dist_findings_total",
    "Distributed-verifier findings by rule (catalog in docs/ANALYSIS.md "
    "'Distributed verification'); errors raise ProgramVerifyError "
    "before any job process launches", labels=("rule",))
# pre-materialized mirror of analysis.infer.DIST_RULES (same data-
# dependency contract as _ANALYSIS_RULES above; set equality is pinned
# by tests/test_dist_verifier.py and repo_lint rule 12 proves every
# family referenced from analysis/distributed.py is declared here)
_DIST_RULES = (
    "dist-wire-unresolved", "dist-wire-shape", "dist-wire-compress",
    "dist-sparse-wire", "dist-shard-gap", "dist-shard-overlap",
    "dist-shard-assignment", "dist-opt-pairing", "dist-table-coverage",
    "dist-barrier", "dist-ordering", "dist-fanin", "dist-tv",
    "dist-pserver-memory",
)
for _r in _DIST_RULES:
    ANALYSIS_DIST_FINDINGS.labels(rule=_r)
ANALYSIS_DIST_SECONDS = REGISTRY.histogram(
    "paddle_analysis_dist_verify_seconds",
    "Wall time of one whole-job distributed verification (all four "
    "rule groups + the per-pserver memory proof) — scales with total "
    "op count across the program set, never with tensor payloads")

# ----------------------------------------------------- dygraph capture
# (paddle_tpu/imperative/jit.py + capture.py: eager functions traced
# into Programs and replayed through the Executor — see
# docs/IMPERATIVE.md)
IMPERATIVE_CAPTURES = REGISTRY.counter(
    "paddle_imperative_captures_total",
    "Eager functions traced into a Program (first call per input "
    "signature/branch/bucket); each capture pays eager execution + "
    "verification once, replays ride the plan cache")
IMPERATIVE_CAPTURE_SECONDS = REGISTRY.histogram(
    "paddle_imperative_capture_seconds",
    "Wall time of ONE capture: the eager trace, Program construction "
    "and capture-time verification (excludes the replay-side trace "
    "and XLA compile: the replay's loading dispatch lands in "
    "paddle_executor_compile_seconds)")
IMPERATIVE_CAPTURED_OPS = REGISTRY.histogram(
    "paddle_imperative_captured_ops",
    "Ops per captured Program block (forward + captured backward + "
    "optimizer update) — the size of what each replay fuses into one "
    "XLA dispatch")
IMPERATIVE_CACHE_HITS = REGISTRY.counter(
    "paddle_imperative_cache_hits_total",
    "Captured-function calls served by an existing entry (signature + "
    "branch guards matched) — the steady state; a low hit ratio means "
    "shape/branch churn is defeating the capture cache")
IMPERATIVE_RETRACES = REGISTRY.counter(
    "paddle_imperative_retraces_total",
    "Re-captures AFTER a function's first trace, by trigger: 'shape' = "
    "new input signature (bucketing off), 'bucket' = new lead-dim "
    "bucket (PADDLE_TPU_CAPTURE_BUCKETS), 'branch' = Python control "
    "flow took a path no cached entry's guards match, 'config' = "
    "pass/kernel config_key changed under an already-seen signature",
    labels=("reason",))
for _r in ("shape", "bucket", "branch", "config"):
    IMPERATIVE_RETRACES.labels(reason=_r)
IMPERATIVE_CACHE_EVICTIONS = REGISTRY.counter(
    "paddle_imperative_cache_evictions_total",
    "Entries evicted from the size-capped capture LRU "
    "(PADDLE_TPU_CAPTURE_CACHE_SIZE); sustained growth = signature "
    "churn re-tracing in a loop")

# ------------------------------------------------------------- optimizer
# (paddle_tpu/core/passes/: graph-optimizing pass pipeline — see
# docs/OPTIMIZER.md. PADDLE_TPU_OPTIMIZE=0 bypasses the pipeline; tests
# pin that NONE of these families move then.)
OPTIMIZER_PROGRAMS = REGISTRY.counter(
    "paddle_optimizer_programs_optimized_total",
    "Programs run through the optimizing pass pipeline at executor "
    "prepare time (once per plan-cache miss), by effective "
    "PADDLE_TPU_OPTIMIZE level", labels=("level",))
for _lv in ("1", "2"):
    OPTIMIZER_PROGRAMS.labels(level=_lv)
OPTIMIZER_OPS_IN = REGISTRY.counter(
    "paddle_optimizer_ops_in_total",
    "Global-block ops entering the pipeline (sum over optimized "
    "programs); with ops_out_total this is the lifetime op-count "
    "reduction ratio")
OPTIMIZER_OPS_OUT = REGISTRY.counter(
    "paddle_optimizer_ops_out_total",
    "Global-block ops surviving the pipeline (sum over optimized "
    "programs)")
OPTIMIZER_OPS_REMOVED = REGISTRY.counter(
    "paddle_optimizer_ops_removed_total",
    "Net ops removed from the program, by pass (copy-prop/CSE/DCE "
    "removals, folding net of materialized constants, fusion net of "
    "inserted fused ops)", labels=("pass",))
OPTIMIZER_OPS_FOLDED = REGISTRY.counter(
    "paddle_optimizer_ops_folded_total",
    "Const-subgraph ops evaluated at optimize time by "
    "constant_folding_pass (before netting out the assign_value ops "
    "that materialize still-consumed results)")
OPTIMIZER_OPS_FUSED = REGISTRY.counter(
    "paddle_optimizer_ops_fused_total",
    "Elementwise-chain ops swallowed into fused_elementwise ops "
    "(constituents counted, one fused op re-inserted per chain)")
OPTIMIZER_PASS_SECONDS = REGISTRY.histogram(
    "paddle_optimizer_pass_seconds",
    "Wall time of one pass application (graph build + apply + "
    "materialize; the per-pass verify is not included — it rides "
    "optimize_seconds)", labels=("pass",))
OPTIMIZER_SECONDS = REGISTRY.histogram(
    "paddle_optimizer_optimize_seconds",
    "Wall time of one whole pipeline run over a program, including "
    "the verify-after-every-pass checks")
# pre-materialize the per-pass schema from the pipeline's pass list —
# kept as a plain tuple HERE (not imported from core.passes, which
# would cycle); tests pin it equal to core.passes.PIPELINE's names
_OPTIMIZER_PASSES = (
    "constant_folding_pass",
    "copy_propagation_pass",
    "common_subexpression_elimination_pass",
    "dead_op_elimination_pass",
    "post_training_quantize_pass",
    "amp_bf16_pass",
    "fuse_elementwise_pass",
)
OPTIMIZER_TV_CHECKS = REGISTRY.counter(
    "paddle_optimizer_tv_checks_total",
    "Per-pass translation validations run (analysis/tv.py: the pass's "
    "declared rewrite log machine-checked against before/after "
    "reaching-definition facts); one per structural pass application "
    "that changed the program, once per plan-cache miss. 0 under "
    "PADDLE_TPU_OPTIMIZE_TV=0", labels=("pass",))
OPTIMIZER_TV_VIOLATIONS = REGISTRY.counter(
    "paddle_optimizer_tv_violations_total",
    "Translation-validation violations found, by pass — every count "
    "here also raised an OptimizerPassError (the run FAILED loudly; "
    "this is the rate, the exception text has the def-chains). A "
    "nonzero steady-state value means a pass is rewriting programs it "
    "cannot prove equivalent: report it as a pass bug", labels=("pass",))
OPTIMIZER_TV_SECONDS = REGISTRY.histogram(
    "paddle_optimizer_tv_seconds",
    "Wall time of one per-pass translation validation (snapshot "
    "excluded — it rides the pass row; scales with op count x reads "
    "per op, never with tensor sizes)")
for _p in _OPTIMIZER_PASSES:
    OPTIMIZER_OPS_REMOVED.labels(**{"pass": _p})
    OPTIMIZER_PASS_SECONDS.labels(**{"pass": _p})
    OPTIMIZER_TV_CHECKS.labels(**{"pass": _p})
    OPTIMIZER_TV_VIOLATIONS.labels(**{"pass": _p})

# ------------------------------------------------------------ quantization
# (core/passes/quantize_pass.py + the range-aware amp upgrade — see
# docs/OPTIMIZER.md "Post-training int8 quantization".
# PADDLE_TPU_OPTIMIZE_QUANT=0 (the default) bypasses the pass; tests pin
# that NONE of these families move then.)
QUANT_WEIGHTS = REGISTRY.counter(
    "paddle_quant_weights_quantized_total",
    "Weights rewritten to int8 storage + per-channel dequantize by the "
    "quantize_pass, by consuming op type; once per pass application "
    "(= once per plan-cache miss)", labels=("op",))
for _op in ("mul", "matmul", "matmul_v2", "conv2d"):
    QUANT_WEIGHTS.labels(op=_op)
QUANT_OPS_INSERTED = REGISTRY.counter(
    "paddle_quant_ops_inserted_total",
    "quantize/dequantize/scale-literal ops the quantize_pass spliced "
    "into optimized programs (3 per quantized weight)")
QUANT_SKIPPED = REGISTRY.counter(
    "paddle_quant_skipped_total",
    "Weight-consuming ops the quantize_pass examined and refused, by "
    "reason: 'written' = the program writes the weight (training), "
    "'grad' = a gradient for it exists, 'dtype' = not float32, "
    "'shape' = rank unsupported for per-channel scales, 'scope' = no "
    "concrete value in the run scope, 'unproven' = the range engine "
    "could not prove the weight finite, 'small' = below the size "
    "floor", labels=("reason",))
for _r in ("written", "grad", "dtype", "shape", "scope", "unproven",
           "small"):
    QUANT_SKIPPED.labels(reason=_r)
QUANT_AMP_KEPT_F32 = REGISTRY.counter(
    "paddle_quant_amp_kept_f32_total",
    "Ops the range-aware amp_bf16_pass stamped f32 instead of the "
    "table's bf16 because their output interval provably exceeds the "
    "bf16 finite range — each count is a would-have-been inf")

# --------------------------------------------------------------- kernels
# (paddle_tpu/kernels/ and ops/attention.py: each kernel's form comes
# from its operands — see docs/KERNELS.md; the per-kernel plan families
# follow)
KERNEL_DISPATCHES = REGISTRY.counter(
    "paddle_kernel_dispatches_total",
    "fused_attention lowerings by the form taken: impl='pallas' the flash "
    "kernel, 'composed' the XLA math under the sequence threshold. "
    "Counted at LOWERING time (once per plan-cache miss), not per step; "
    "nothing moves under PADDLE_TPU_KERNELS=0",
    labels=("op", "impl"))
for _c in ("pallas", "composed"):
    KERNEL_DISPATCHES.labels(op="attention", impl=_c)

FLASH_BLOCK_PLANS = REGISTRY.counter(
    "paddle_flash_block_plans_total",
    "Flash-attention Pallas calls lowered, by kernel name (flash_fwd, "
    "flash_refwd, flash_bwd_dkv, flash_bwd_dq), the [bq]x[bk] block one "
    "grid step computes, and whether a single block covered the kernel's "
    "reduction axis so the carry was dropped ('1'). Counted at LOWERING "
    "time like paddle_kernel_dispatches_total: it says which plan a "
    "compiled step holds (ops/attention.py _block_plan). layout is how "
    "the operands came: 'heads' [B,H,S,D] (the serving prefills, a "
    "rotated or grouped training layer) or 'lanes' [B,S,H*D] (the "
    "projections as they are, the head a block index along the last "
    "axis: no transpose round the call)",
    labels=("kernel", "block", "single_pass", "layout"))

FLASH_STEP_HEADS = REGISTRY.counter(
    "paddle_flash_step_heads_total",
    "Flash-attention Pallas calls lowered, by kernel name, whether a "
    "single block covered the kernel's reduction axis ('1') and how many "
    "(batch, head) rows ONE grid step takes, unrolled in the step so one "
    "head's MXU passes run beside another's softmax. Counted at LOWERING "
    "time beside paddle_flash_block_plans_total. The forward's count is "
    "ops/attention.py _forward_heads: from the widths, the dtype, the "
    "group and the plan, held to the VMEM a kernel is compiled under",
    labels=("kernel", "single_pass", "heads"))

DROPOUT_MASK_PLANS = REGISTRY.counter(
    "paddle_dropout_mask_plans_total",
    "Dropout keep masks lowered, by the op that draws (site 'dropout' or "
    "'fused_attention') and where the bits come from ('rbg_u32': one "
    "32-bit RngBitGenerator draw an element, compared as integers, the "
    "mask saved for the grad op). Counted at LOWERING time like "
    "paddle_flash_block_plans_total: a compiled bert-base train step "
    "holds 37 (ops/random_mask.py keep_mask)",
    labels=("site", "bits"))

MOE_GMM_PLANS = REGISTRY.counter(
    "paddle_moe_gmm_plans_total",
    "Grouped-matmul calls of the expert layer lowered, by kernel name "
    "(moe_gmm_up, moe_gmm_down), the [tm]x[tk]x[tn] tile one grid step "
    "computes ('-' for the composed form) and the form the step holds "
    "('pallas' or 'composed'). Counted at LOWERING time like "
    "paddle_flash_block_plans_total (kernels/moe_gmm.py gmm_plan)",
    labels=("kernel", "tile", "form"))

KV_CACHE_WRITE_PLANS = REGISTRY.counter(
    "paddle_kv_cache_write_plans_total",
    "KV-cache writes lowered, by the form the step holds ('pallas': one "
    "in-place kernel call a cache tensor; 'composed': "
    "lax.dynamic_update_slice, vmapped for per-slot positions) and the "
    "rows a slot writes (1 in a decode step, the prompt's length in a "
    "prefill). Counted at LOWERING time like paddle_moe_gmm_plans_total "
    "(kernels/kv_cache_write.py kv_cache_write)",
    labels=("form", "rows"))

MOE_ROUTED_PAIRS = REGISTRY.gauge(
    "paddle_moe_routed_pairs",
    "Token-expert pairs the decode step has routed to each expert of "
    "each layer since the engine started: a copy of the device-side "
    "[n_layer, n_expert] int32 the step adds to, refreshed when "
    "DecodeEngine.routed_pairs() is called (no fetch a step)",
    labels=("layer", "expert"))

MOE_EXPERTS_TOUCHED = REGISTRY.gauge(
    "paddle_moe_experts_touched",
    "Decode steps in which each HELD expert of each layer was given at "
    "least one token-expert pair since the engine started (a cfg with "
    "n_expert_local: one chip's share of the experts): a copy of the "
    "device-side [n_layer, n_expert_local] int32 beside the routed-pairs "
    "tally, refreshed with it by DecodeEngine.routed_pairs(). The grouped "
    "matmul fetches no weights for an empty group, so the expert bytes a "
    "step streams follow this count",
    labels=("layer", "expert"))

MOE_COMPACT_CALLS = REGISTRY.gauge(
    "paddle_moe_compact_calls",
    "Expert calls of the prefills of a cfg with n_expert_local that were "
    "long enough to carry a bound on the pairs this chip's experts hold "
    "(twice the share's even part of the call's token-expert pairs, "
    "ops/moe_ops.py compact_rows), by layer and by the branch they took: "
    "'compact' cut the sorted pair rows at the bound before the gather, "
    "'full' found more held pairs than the bound and ran every row, as a "
    "call without a bound does. A copy of the device-side [n_layer, 2] "
    "int32 the prefill programs add to, refreshed by "
    "DecodeEngine.routed_pairs() (no fetch an admission). 'full' rising "
    "= the router sends this share more than twice its even part",
    labels=("layer", "path"))

MOE_ZERO_PAIRS = REGISTRY.gauge(
    "paddle_moe_zero_pairs",
    "Token-expert pairs of the serving decode step that chose an IDENTITY "
    "(zero-compute) expert of a cfg with n_zero_expert, by expert branch "
    "(a layer; a pair of sub-layers under shortcut_moe): they are given "
    "to no group, cost no row of a grouped matmul and add w x. With the "
    "layer's paddle_moe_routed_pairs they add up to b_max x expert_top_k "
    "a step. A copy of column 0 of the device-side [branches, 2] int32, "
    "refreshed by DecodeEngine.routed_pairs() / zero_pairs() (no fetch a "
    "step). The even share is n_zero_expert / (n_expert + n_zero_expert)",
    labels=("layer",))

MOE_REAL_EXPERTS_MAX = REGISTRY.gauge(
    "paddle_moe_real_experts_max",
    "The most experts WITH weights one token of a serving decode step "
    "chose since the engine was built (free slots' rows too), by expert "
    "branch, for a cfg with n_zero_expert: between 0 and expert_top_k, "
    "the straggler's width — work a token varies with identity experts. "
    "A copy of column 1 of the same device-side tally (a running maximum)",
    labels=("layer",))

SERVING_CACHE_BYTES = REGISTRY.gauge(
    "paddle_serving_cache_bytes",
    "Bytes of the decode caches a serving lane built, by kind: 'ring' "
    "(a sliding layer's [b_max, n_kv, window, Dh] tensors, position p in "
    "row p mod window), 'full' ([b_max, n_kv, max_len, Dh] slabs) and "
    "'latent' (a latent-attention layer's ONE [b_max, 1, max_len, "
    "kv_lora_rank + d_rope] tensor: keys and values are read out of the "
    "same row) and 'state' (a state-space layer's [b_max, G, N, (H / G) P] "
    "recurrent state and its [b_max, K - 1, C] convolution rows, a gated "
    "convolution layer's [b_max, K - 1, d_model] carried rows: no "
    "position axis, the same bytes whatever a sequence's length). Kinds "
    "are told apart by the tensor's name and layer (gpt.cache_kind). Set "
    "where the lane builds its caches; last lane wins",
    labels=("kind",))

SERVING_POSITIONS = REGISTRY.counter(
    "paddle_serving_positions_total",
    "Cache positions a plain decode step stood over, summed over the "
    "steps: 'live' adds the lengths its riders had reached (the rows "
    "their attention may see), 'held' b_max x max_len (the rows a full "
    "slab holds for every slot, which a step that reads slabs whole "
    "streams whether a slot is live or not). live / held is the share of "
    "a slab read that was of use; host integers, counted at dispatch",
    labels=("kind",))

SERVING_WEIGHT_BYTES = REGISTRY.gauge(
    "paddle_serving_weight_bytes",
    "Bytes of the parameters a serving lane's decode program declares, by "
    "the dtype each is STORED in (cfg['weight_dtype'] stores the matrices "
    "of a gpt cfg in bfloat16; norm scales and every other vector stay "
    "float32). Set where the lane builds its programs; last lane wins",
    labels=("dtype",))

MLA_ATTENTION_PLANS = REGISTRY.counter(
    "paddle_mla_attention_plans_total",
    "Which latent-attention (cfg['attn'] = 'mla') form a program holds: "
    "'expanded' (per-head keys and values rebuilt from the latent) counts "
    "a layer where models/gpt.py BUILDS one, kernel 'fused_attention' "
    "(the prefill; the flash kernel and block it lowers to are "
    "paddle_flash_block_plans_total's) or 'composed' (the training "
    "build), block '-'; 'absorbed' (the decode step's, scores and values "
    "read out of the latent row itself) counts a call of mla_decode at "
    "LOWERING, kernel 'pallas' with the rows one grid step takes and the "
    "walk ('512 live': only the (slot, block) pairs that hold a visible "
    "row are grid steps) or 'composed' for jax.numpy. widths is [q/k]x[v] "
    "of the call ('192x128' expanded, '576x512' absorbed at the published "
    "sizes)",
    labels=("form", "kernel", "block", "widths"))

MLA_DECODE_BLOCKS = REGISTRY.counter(
    "paddle_mla_decode_blocks_total",
    "Grid steps of the absorbed latent-attention kernel "
    "(kernels/mla_decode.py), summed over the plain decode steps of a lane "
    "whose cache is latent and over its latent layers: 'live' adds the "
    "(slot, block) pairs that hold a visible row, pos // block + 1 a slot "
    "over all b_max slots (a free slot keeps its one block): the steps the "
    "kernel's work list walks; 'grid' b_max x max_len / block, the steps "
    "of a grid over whole slabs. live / grid is the share of that grid "
    "that held a block; host integers, counted at dispatch from the "
    "block decode_plan gives (on the CPU too, where the composed form "
    "runs)",
    labels=("kind",))

RESIDUAL_PLANS = REGISTRY.counter(
    "paddle_residual_plans_total",
    "Which form of a changed residual path a program holds (gpt "
    "cfg['residual'] = 'mhc': n streams a token, kernels/mhc.py): one "
    "count a call of mhc_pre (op 'pre': what a sub-block reads of the "
    "streams, and the row's mappings) or mhc_post (op 'post': what it "
    "writes back, in place) at LOWERING, kernel 'pallas' (one pass over "
    "the stream) or 'composed' (jax.numpy: every CPU run, and "
    "PADDLE_TPU_KERNELS=0). A prefill or a decode step of L layers lowers "
    "2 L of each",
    labels=("form", "op", "kernel", "streams"))

SSM_PLANS = REGISTRY.counter(
    "paddle_ssm_plans_total",
    "Which form of the selective state-space recurrence a program holds "
    "(gpt cfg['mixers'] with 'ssm' layers, kernels/ssm.py): one count a "
    "call of ssm_scan (op 'scan': a whole prompt, chunked) or ssm_update "
    "(op 'update': one token a slot into the state, in place) at "
    "LOWERING, kernel 'pallas' or 'composed' (jax.numpy: every CPU run, "
    "and PADDLE_TPU_KERNELS=0), with the chunk the scan blocks the "
    "recurrence in (1 for the update). A prefill of a model with L "
    "state-space layers lowers L scans, its decode step L updates",
    labels=("op", "kernel", "chunk"))

CONV_PLANS = REGISTRY.counter(
    "paddle_conv_plans_total",
    "Which form of the causal depth-wise convolution of a whole prompt a "
    "program holds (kernels/ssm.py conv_prefill, the program's op "
    "causal_conv in front of an ssm, mamba, delta or gated-convolution "
    "mixer): one count a call at LOWERING. kernel 'pallas' reads and "
    "writes the prompt once, in blocks of chunk positions; 'composed' "
    "(chunk 0) is K shifted jax.numpy passes: every CPU run, "
    "PADDLE_TPU_KERNELS=0, an x not read in place (no attr columns), a "
    "prompt under one block, a width that is no whole number of lane "
    "tiles. A family of its own and not op='conv' of "
    "paddle_ssm_plans_total, whose samples the state-space cell's facts "
    "list whole. A prefill lowers one a layer with such a mixer",
    labels=("kernel", "chunk"))

POWER_PLANS = REGISTRY.counter(
    "paddle_power_plans_total",
    "Which form of a power-retention layer's core a program holds (gpt "
    "cfg['layer_types'] with 'retention' layers, kernels/power.py): one "
    "count a call of power_scan (a whole prompt, chunked) or power_update "
    "(one token a slot into the state, in place, the query heads read out "
    "of it in the same pass) at LOWERING, form 'pallas' or 'composed' "
    "(jax.numpy: every CPU run, and PADDLE_TPU_KERNELS=0), with the chunk "
    "the scan takes the attention form in (1 for the update). A prefill "
    "of a model with L retention layers lowers L scans, its decode step L "
    "updates",
    labels=("kernel", "form", "chunk"))

POWER_STATE_BYTES = REGISTRY.gauge(
    "paddle_power_state_bytes",
    "Bytes of power-retention state the lane built last holds: the state "
    "[b_max, G, R, D] and normaliser [b_max, G, D, D] every power_update "
    "of its decode step reads and writes (the rows R as kept, "
    "kernels/power.py::phi_plan, not the exact count of pairs). Part of "
    "paddle_serving_cache_bytes{kind='state'}; 0 for a model without "
    "such layers")

POWER_CHUNKS = REGISTRY.counter(
    "paddle_power_chunks_total",
    "Chunks the admissions' prefills scanned in power-retention layers: "
    "layers x ceil(prompt / kernels.power.scan_chunk(prompt)) a "
    "full-prompt prefill (serving/engine.py); the first chunk of a layer "
    "reads no state")

DELTA_PLANS = REGISTRY.counter(
    "paddle_delta_plans_total",
    "Which form of a gated delta-rule layer's core a program holds (gpt "
    "cfg['layer_types'] with 'delta' layers, kernels/delta.py): one count "
    "a call of delta_scan (a whole prompt, chunked: a unit lower "
    "triangular solve a chunk and head) or delta_update (one token a slot "
    "into the state, in place: read at the key, corrected, read at the "
    "query in the same pass) at LOWERING, form 'pallas' or 'composed' "
    "(jax.numpy: every CPU run, and PADDLE_TPU_KERNELS=0), with the chunk "
    "of the scan (1 for the update). A prefill of a model with L delta "
    "layers lowers L scans, its decode step L updates",
    labels=("kernel", "form", "chunk"))

DELTA_STATE_BYTES = REGISTRY.gauge(
    "paddle_delta_state_bytes",
    "Bytes of delta-rule state the lane built last holds: the state "
    "[b_max, Hv, Dk, Dv] every delta_update of its decode step reads and "
    "writes, and the rows [b_max, taps - 1, C] of the convolution in "
    "front of it that the layer's causal_conv_step shifts. Part of "
    "paddle_serving_cache_bytes{kind='state'}; 0 for a model without "
    "such layers")

DELTA_CHUNKS = REGISTRY.counter(
    "paddle_delta_chunks_total",
    "Chunks the admissions' prefills scanned in delta-rule layers: "
    "layers x ceil(prompt / kernels.delta.scan_chunk(prompt)) a "
    "full-prompt prefill (serving/engine.py): one triangular solve a "
    "chunk and value head")

MAMBA_PLANS = REGISTRY.counter(
    "paddle_mamba_plans_total",
    "Which form of a Mamba-1 layer's recurrence a program holds (gpt "
    "cfg['layer_types'] with 'mamba' layers, kernels/mamba.py): one count "
    "a call of mamba_scan (a whole prompt, walked position by position "
    "inside the kernel: the decay is one number a channel AND state, so "
    "no chunk of it is a matrix product) or mamba_update (one token a "
    "slot into the state, in place) at LOWERING, form 'pallas' or "
    "'composed' (jax.numpy: every CPU run, and PADDLE_TPU_KERNELS=0), "
    "with the block of positions a grid step of the scan holds (1 for "
    "the update). A prefill of a model with L mamba layers lowers L "
    "scans, its decode step L updates",
    labels=("kernel", "form", "block"))

MAMBA_STATE_BYTES = REGISTRY.gauge(
    "paddle_mamba_state_bytes",
    "Bytes of Mamba-1 state the lane built last holds: the state [b_max, "
    "1, N, C] every mamba_update of its decode step reads and writes, and "
    "the rows [b_max, taps - 1, C] of the convolution in front of it that "
    "the layer's causal_conv_step shifts. Part of "
    "paddle_serving_cache_bytes{kind='state'}; 0 for a model without "
    "such layers")

MAMBA_CHUNKS = REGISTRY.counter(
    "paddle_mamba_chunks_total",
    "Blocks of positions the admissions' prefills scanned in mamba "
    "layers: layers x ceil(prompt / kernels.mamba.scan_block(prompt)) a "
    "full-prompt prefill (serving/engine.py); inside a block the kernel "
    "walks the positions one by one")

MHC_RES_DEVIATION = REGISTRY.gauge(
    "paddle_mhc_res_deviation",
    "The largest |row sum - 1| or |column sum - 1| any residual mapping "
    "H_res (doubly stochastic up to its Sinkhorn rounds' error) has shown "
    "in a decode step of the engine since it was built, over the step's "
    "rows (free slots too) and sub-blocks: the step keeps the running "
    "maximum on the device (gpt.MHC_RES_DEV_VAR) and "
    "DecodeEngine.mhc_res_deviation() is the one transfer that refreshes "
    "this gauge. A kernel of fewer rounds, or a narrower mapping "
    "arithmetic, moves it")

# ---------------------------------------------------------------- tracing
# (observe/trace.py: trace contexts + the crash flight recorder — see
# docs/OBSERVABILITY.md "Trace propagation")
TRACE_DUMPS = REGISTRY.counter(
    "paddle_trace_flight_dumps_total",
    "Flight-recorder dumps written, by trigger ('signal' = the "
    "graceful-shutdown SIGTERM/SIGINT handlers, observe/shutdown.py)",
    labels=("reason",))
for _r in ("wedge", "crash", "atexit", "manual", "signal"):
    TRACE_DUMPS.labels(reason=_r)

# Every span/trace-event SITE name used in code must appear here — the
# same centralize-the-schema contract as the metric families above,
# enforced by tools/repo_lint.py (trace-site rule): a typo'd site would
# otherwise fragment a trace across names tools/trace_view.py can't
# group. Grammar: <subsystem>.<noun-or-phase>, dotted lowercase.
TRACE_SITES = (
    # executor (core/executor.py, parallel/engine.py): one
    # executor.call per run()/run_repeated()/ParallelEngine._execute,
    # parent of the host's phases in it: gather (plan + state lookup,
    # feed conversion; h2d nests in it), place (the mesh engine's
    # device_put onto shardings), dispatch (tagged with the plan-cache
    # signature), complete (the host block on results), write_back (new
    # state into the scope). The call's time less its children's is what
    # is still unnamed
    "executor.call", "executor.gather", "executor.h2d", "executor.place",
    "executor.dispatch", "executor.complete", "executor.write_back",
    # set-up (observe/trace.py "Program loads"): executor.prepare is the
    # plan-cache miss path (verification, the pass pipeline, block
    # analysis; parent of the optimizer.* spans), in executor.gather or
    # under seed_plan. executor.load.* are retroactive spans of JAX's
    # own stages, children of the executor.dispatch that caused them
    # (attrs fun, plan; on .backend also cache, nth, retrieval_s). A
    # steady dispatch has none of the four
    "executor.prepare", "executor.load.trace", "executor.load.lower",
    "executor.load.backend",
    # pipelined input (core/pipeline.py): fill-thread spans under the
    # loop context handed off explicitly by run_pipelined
    "pipeline.prefetch", "pipeline.const_lookup",
    # serving (serving/queue.py, batcher.py, engine.py, router.py):
    # one trace per request from submit to its single terminal done
    # event; the router propagates the SAME trace across the replica
    # hop, so a drained-and-readmitted request's story stays one trace
    "serving.request.submit", "serving.request.done",
    "serving.queue.wait", "serving.batch.dispatch",
    "serving.engine.admit", "serving.engine.prefill",
    "serving.engine.suffix_prefill", "serving.engine.splice",
    "serving.engine.step", "serving.engine.spec",
    "serving.engine.feeds", "serving.engine.sample",
    "serving.engine.retire", "serving.request.first_token",
    # the engine's own set-up: one build span a program constructed
    # (attrs program, P, ops), one load_params a lane's given weights
    "serving.engine.build", "serving.engine.load_params",
    "serving.router.route", "serving.router.drain",
    "serving.router.readmit",
    # rpc (distributed/rpc.py): client call spans; server events linked
    # to the calling trainer's trace via wire metadata
    "rpc.client", "rpc.server.recv", "rpc.server.get_var",
    # resilience (resilience/faults.py, watchdog.py): the events that
    # explain a flight-recorder dump's final moments
    "resilience.fault", "resilience.wedge",
    # elastic jobs (resilience/elastic.py, distributed/membership.py):
    # membership transitions, per-generation spans and the reshard span
    # — the story of who left/joined and what the job did about it
    "elastic.membership", "elastic.generation", "elastic.reshard",
    # optimizer (core/passes): one pipeline span per optimized program,
    # one child span per applied pass, one per-pass translation-
    # validation span — optimization cost shows up in the flight
    # recorder next to the compile it feeds
    "optimizer.pipeline", "optimizer.pass", "optimizer.tv",
    # dygraph capture (imperative/jit.py): one span per trace capture
    # (tagged with the retrace reason) and one per cached replay
    "imperative.capture", "imperative.replay",
    # frozen deployable artifacts (export/): one span per artifact
    # build and one per load; the router's rolling upgrade drains ride
    # the existing serving.router.drain span with reason="roll"
    "export.save", "export.load",
)

# ------------------------------------------------------ fleet telemetry
# (observe/export.py, fleet.py, slo.py, shutdown.py — the live metrics
# plane; docs/OBSERVABILITY.md "Fleet telemetry plane". Every family
# below moves ONLY when the plane is explicitly enabled: with
# PADDLE_TPU_METRICS_PORT unset and no collector/monitor constructed,
# tests pin zero movement across all of them, like PADDLE_TPU_TRACE=0.)
EXPORT_HTTP_REQUESTS = REGISTRY.counter(
    "paddle_export_http_requests_total",
    "Requests the /metrics exporter answered, by endpoint ('metrics', "
    "'snapshot' = /snapshot.json, 'healthz'; 'other' = 404s)",
    labels=("endpoint",))
for _e in ("metrics", "snapshot", "healthz", "other"):
    EXPORT_HTTP_REQUESTS.labels(endpoint=_e)
EXPORT_LISTENING = REGISTRY.gauge(
    "paddle_export_listening",
    "1 while the MetricsExporter HTTP thread is serving, 0 otherwise "
    "— scrape-side liveness for the process itself")
FLEET_INGESTS = REGISTRY.counter(
    "paddle_fleet_ingests_total",
    "Per-instance snapshots a FleetCollector absorbed, by transport: "
    "'scrape' = HTTP pull of an exporter, 'push' = @TELEMETRY@ frames "
    "over the RPC stack, 'ingest' = direct in-process hand-off",
    labels=("source",))
for _s in ("scrape", "push", "ingest"):
    FLEET_INGESTS.labels(source=_s)
FLEET_INSTANCES = REGISTRY.gauge(
    "paddle_fleet_instances",
    "Instances the FleetCollector currently tracks, by lease state "
    "('live' = reported within the expiry window, 'stale' = lease "
    "lapsed but series retained for post-mortem)", labels=("state",))
for _s in ("live", "stale"):
    FLEET_INSTANCES.labels(state=_s)
FLEET_EXPIRED = REGISTRY.counter(
    "paddle_fleet_instances_expired_total",
    "Lease expiries: instances that stopped reporting and were marked "
    "stale — a FaultPlan-killed trainer shows up here, not as a "
    "forever-frozen 'live' row")
SLO_EVALUATIONS = REGISTRY.counter(
    "paddle_slo_evaluations_total",
    "SloMonitor evaluation passes (each pass checks every declared "
    "objective once over its window)")
SLO_BREACHES = REGISTRY.counter(
    "paddle_slo_breaches_total",
    "Objective breaches, labelled by the declared objective name; at "
    "most one increment per objective per evaluation window — a "
    "sustained burn reads as breaches-per-window, not per-sample",
    labels=("objective",))
SHUTDOWN_SIGNALS = REGISTRY.counter(
    "paddle_shutdown_signals_total",
    "Graceful-shutdown signals handled (flight ring dumped, telemetry "
    "sidecar flushed, exporter stopped) before re-raising the default "
    "disposition", labels=("signal",))
for _s in ("SIGTERM", "SIGINT"):
    SHUTDOWN_SIGNALS.labels(signal=_s)

# ------------------------------------------------- deployable artifacts
# (paddle_tpu/export/: frozen single-file deployment artifacts — see
# docs/DEPLOYMENT.md. Loading an artifact must move NONE of the
# paddle_optimizer_*/plan-cache-miss families for the signatures
# it covers; the cold-start acceptance test pins exactly that.)
ARTIFACT_SAVES = REGISTRY.counter(
    "paddle_export_artifact_saves_total",
    "Artifacts built by save_artifact (verify + optimize + freeze + "
    "atomic single-file write)")
ARTIFACT_SAVE_SECONDS = REGISTRY.histogram(
    "paddle_export_artifact_save_seconds",
    "Wall time of one save_artifact: program verify + optimizer "
    "pipeline (TV forced on) + param checksums + AOT export + the "
    "atomic zip write")
ARTIFACT_LOADS = REGISTRY.counter(
    "paddle_export_artifact_loads_total",
    "load_artifact calls by outcome: 'ok' rehydrated a servable "
    "bundle (possibly with counted per-section degradations), 'skew' "
    "refused with ArtifactSkewError, 'corrupt' refused an unreadable/"
    "truncated file — a refused artifact is NEVER silently served",
    labels=("outcome",))
for _o in ("ok", "skew", "corrupt"):
    ARTIFACT_LOADS.labels(outcome=_o)
ARTIFACT_LOAD_SECONDS = REGISTRY.histogram(
    "paddle_export_artifact_load_seconds",
    "Wall time of one successful load_artifact: manifest + checksum "
    "validation, program/param rehydration — the cold-start cost the "
    "artifact reduces trace/optimize to")
# every refusal reason the validation ladder can produce, schema-first
ARTIFACT_SKEW_REASONS = ("corrupt", "future_version", "section_checksum",
                         "config_key", "param_checksum", "tv_digest")
ARTIFACT_SKEW = REGISTRY.counter(
    "paddle_export_artifact_skew_total",
    "Artifacts refused at load, by validation-ladder reason: 'corrupt' "
    "= unreadable zip/manifest or truncated file, 'future_version' = "
    "format newer than this runtime, 'section_checksum' = a section "
    "blob fails its manifest sha256, 'config_key' = the recorded "
    "passes/kernels/quant/AMP config differs from the running process, "
    "'param_checksum' = a parameter fails its per-var sha256, "
    "'tv_digest' = the rewrite-log digest does not match",
    labels=("reason",))
for _r in ARTIFACT_SKEW_REASONS:
    ARTIFACT_SKEW.labels(reason=_r)
ARTIFACT_DEGRADED = REGISTRY.counter(
    "paddle_export_artifact_degraded_total",
    "OPTIONAL artifact sections dropped at load with the rest of the "
    "artifact still served, by (section, reason): 'absent' = the save "
    "side could not produce it, 'version' = the section's own format "
    "version is unknown to this runtime, 'jax' = jax.export missing or "
    "deserialization failed. Each count is one recompute the artifact "
    "was supposed to avoid — mandatory validation failures land in "
    "paddle_export_artifact_skew_total instead, never here",
    labels=("section", "reason"))
for _sec, _r in (("aot", "absent"), ("aot", "version"), ("aot", "jax"),
                 ("memory", "absent"), ("rewrite_log", "absent"),
                 ("serving", "absent")):
    ARTIFACT_DEGRADED.labels(section=_sec, reason=_r)
ARTIFACT_AOT_CALLS = REGISTRY.counter(
    "paddle_export_artifact_aot_calls_total",
    "Predictor runs served by a frozen jax.export executable from the "
    "artifact's AOT section (zero trace, zero optimize, zero XLA "
    "re-lowering) instead of the executor plan path")
ARTIFACT_PLANS_SEEDED = REGISTRY.counter(
    "paddle_export_plans_seeded_total",
    "Executor plan-cache entries seeded from a loaded artifact's "
    "frozen program — each seeded signature's first run is a cache "
    "HIT (the cold-start contract: zero plan-cache misses for "
    "covered signatures)")
ARTIFACT_ROLLS = REGISTRY.counter(
    "paddle_export_rolls_total",
    "ReplicaRouter.roll fleet upgrades by outcome: 'ok' = every "
    "replica replaced, 'partial' = the roll stopped early (router "
    "closing mid-roll); a replica crash during the roll recovers "
    "through the ordinary monitor path and does not fail the roll",
    labels=("outcome",))
for _o in ("ok", "partial"):
    ARTIFACT_ROLLS.labels(outcome=_o)
ARTIFACT_ROLL_REPLICAS = REGISTRY.counter(
    "paddle_export_roll_replicas_total",
    "Replicas drained and rebuilt by ReplicaRouter.roll (one count "
    "per replaced replica, incremented after the replacement engine "
    "is serving)")
