"""Registry of every metric family the framework exposes.

The exposition surface has grown PR over PR (gateway request counters,
phase histograms, SLO gauges, health scores, event counters; server-side
``tpu:*`` contract families) and nothing kept it discoverable: an operator
had to curl ``/metrics`` and guess semantics.  This module is the single
declarative list — name, type, labels, help, surface — that:

- generates ``docs/METRICS.md`` (``make metrics-docs``;
  ``tests/test_metrics_docs.py`` asserts the file is current), and
- is cross-checked against the REAL rendered expositions by the contract
  suite, so a family added to a render path without a registry entry (or
  vice versa) fails tier-1 instead of silently drifting.

Keep entries in render order per surface; the doc generator preserves it.
"""

from __future__ import annotations

from dataclasses import dataclass

GATEWAY_SURFACE = "gateway /metrics (proxy)"
SERVER_SURFACE = "model server /metrics (api_http)"


@dataclass(frozen=True)
class Family:
    name: str
    kind: str                 # counter | gauge | histogram
    labels: tuple             # label names ("" entries not allowed)
    help: str
    surface: str


# The closed label set of tpu:engine_phase_seconds_total: every phase the
# engine thread can be in (server/profiler.py's phase stack, the
# ``engine.<phase>`` annotations of a profiler trace) and whether the thread
# is then blocked on the chip ("device") or doing the host's work ("host").
ENGINE_PHASES = (
    ("admit", "host"),
    ("prefill.stage", "host"),
    ("prefill.wait", "device"),
    ("prefill.emit", "host"),
    ("decode.plan", "host"),
    ("decode.stage", "host"),
    ("decode.wait", "device"),
    ("decode.readback", "host"),
    ("decode.emit", "host"),
    ("decode.account", "host"),
    ("idle", "host"),
    ("other", "host"),
)

# The ``path`` label set of ``tpu:sample_steps_total``, cheapest first: what
# the sampler (server/sampling.py) ran for one decode step, chosen on the
# device from the live rows' parameters.  ``sample_routed`` returns an index
# into this tuple.
SAMPLE_PATHS = ("argmax", "draw", "filtered")
# The ``lanes`` label set of ``tpu:kv_positions_read_total``: the two kinds
# of cache lane a model with window layers keeps (models/transformer.py).
KV_LANES = ("full", "window")
# The ``program`` label set of the ``tpu:prompt_*`` families: the jitted
# programs that compute a prompt (server/engine.py: jit_prefill,
# jit_prefill_many, jit_prefill_chunk and the sequence-parallel ring).
PROMPT_PROGRAMS = ("prefill", "prefill_many", "chunk", "ring")

GATEWAY_FAMILIES = (
    Family("gateway_requests_total", "counter", ("model",),
           "Requests admitted past body parsing, by model.",
           GATEWAY_SURFACE),
    Family("gateway_scheduled_total", "counter", ("pod",),
           "Scheduler picks, by target pod.", GATEWAY_SURFACE),
    Family("gateway_shed_total", "counter", ("model",),
           "Load-shed drops (429); unlabeled line = pre-admission fallback "
           "(model unknown).", GATEWAY_SURFACE),
    Family("gateway_errors_total", "counter", ("model",),
           "Request failures (admission errors, upstream failures, broken "
           "streams); unlabeled line = pre-admission fallback.",
           GATEWAY_SURFACE),
    Family("gateway_lora_affinity_hits_total", "counter", (),
           "Picks that landed on a pod already serving the requested "
           "adapter.", GATEWAY_SURFACE),
    Family("gateway_retries_total", "counter", ("reason",),
           "Budgeted data-path retries performed, by failure reason "
           "(connect | ttft_timeout | upstream_503 | read | read_timeout; "
           "gateway/resilience.py).", GATEWAY_SURFACE),
    Family("gateway_hedges_total", "counter", ("outcome",),
           "TTFT hedges, by outcome (fired | won | lost | no_candidate | "
           "failed); enabled via --hedge-ttft-s.", GATEWAY_SURFACE),
    Family("gateway_client_disconnects_total", "counter", ("model",),
           "Client-side disconnects of live SSE relays; the partial "
           "request is still observed into the e2e histograms.",
           GATEWAY_SURFACE),
    Family("gateway_upstream_connections_total", "counter", ("pod", "state"),
           "Upstream keepalive-pool connections by pod and state "
           "(created = fresh TCP handshake, reused = served off a pooled "
           "connection).", GATEWAY_SURFACE),
    Family("gateway_upstream_connection_reuse_ratio", "gauge", (),
           "Pool-wide connection reuse: reused / (created + reused); near "
           "0 means every request pays a handshake.", GATEWAY_SURFACE),
    Family("gateway_pick_latency_seconds", "histogram", (),
           "Scheduler pick latency.", GATEWAY_SURFACE),
    Family("gateway_prompt_tokens_total", "counter", ("model",),
           "Prompt tokens accounted from upstream usage, by model.",
           GATEWAY_SURFACE),
    Family("gateway_completion_tokens_total", "counter", ("model",),
           "Completion tokens accounted from upstream usage, by model.",
           GATEWAY_SURFACE),
    Family("gateway_ttft_seconds", "histogram", ("model", "path"),
           "Client-observed time to first token (path = collocated | "
           "disaggregated).", GATEWAY_SURFACE),
    Family("gateway_tpot_seconds", "histogram", ("model", "path"),
           "Client-observed time per output token after the first.",
           GATEWAY_SURFACE),
    Family("gateway_e2e_seconds", "histogram", ("model", "path"),
           "Client-observed end-to-end request latency.", GATEWAY_SURFACE),
    Family("gateway_pool_prefix_reused_tokens_total", "counter", ("pod",),
           "Per-replica tpu:prefix_reused_tokens re-exported at the "
           "gateway (KV-affinity observable).", GATEWAY_SURFACE),
    Family("gateway_slo_compliance_ratio", "gauge", ("model", "objective"),
           "Cumulative fraction of requests meeting the objective "
           "(gateway/slo.py; objectives: ttft, tpot, e2e, error_rate).",
           GATEWAY_SURFACE),
    Family("gateway_slo_burn_rate", "gauge",
           ("model", "objective", "window"),
           "Windowed error-budget burn rate (1.0 = budget consumed exactly "
           "at the sustainable rate; fast-burn pages at 14.4 by default).",
           GATEWAY_SURFACE),
    Family("gateway_pod_health_score", "gauge", ("pod",),
           "Fused 0-1 replica health score (gateway/health.py; freshness, "
           "errors, queue, KV, latency components).", GATEWAY_SURFACE),
    Family("gateway_pod_health_state", "gauge", ("pod", "state"),
           "Hysteresis health state (healthy | degraded | unhealthy); the "
           "labeled series is 1.", GATEWAY_SURFACE),
    Family("gateway_upstream_errors_total", "counter", ("pod",),
           "Upstream connection/stream/5xx failures, by pod.",
           GATEWAY_SURFACE),
    Family("gateway_upstream_timeouts_total", "counter", ("pod",),
           "Upstream timeouts (subset of errors), by pod.", GATEWAY_SURFACE),
    Family("gateway_handoff_failures_total", "counter", ("pod",),
           "Disaggregation hop failures attributed to the refusing/failing "
           "pod.", GATEWAY_SURFACE),
    Family("tpu:health_would_avoid_total", "counter", ("pod",),
           "Picks that landed on a non-healthy replica (always counted; "
           "with health_policy=log_only routing is otherwise unchanged).",
           GATEWAY_SURFACE),
    Family("gateway_circuit_state", "gauge", ("pod",),
           "Per-pod circuit-breaker state (0 closed / 1 open / 2 "
           "half-open; gateway/resilience.py).", GATEWAY_SURFACE),
    Family("gateway_usage_share", "gauge", ("model", "adapter", "resource"),
           "Pool-wide consumption share per {model, adapter} (EMA of "
           "scrape-tick deltas; resource: step_seconds | tokens | "
           "kv_block_seconds; gateway/usage.py).", GATEWAY_SURFACE),
    Family("gateway_noisy_neighbor_score", "gauge", ("model", "adapter"),
           "Step-seconds consumption share over admitted-traffic share "
           "(1.0 = proportional; flags noisy past the configured ratio "
           "with hysteresis).", GATEWAY_SURFACE),
    Family("gateway_usage_would_deprioritize_total", "counter",
           ("model", "adapter"),
           "Picks that served a currently-flagged noisy key, attributed "
           "to the flagged {model, adapter} (with fairness mode log_only "
           "routing is otherwise unchanged).", GATEWAY_SURFACE),
    Family("gateway_quota_throttles_total", "counter", ("model", "adapter"),
           "Admissions that found the tenant's fairness quota bucket "
           "empty (gateway/fairness.py, mode=enforce).", GATEWAY_SURFACE),
    Family("gateway_fairness_demotions_total", "counter",
           ("model", "adapter"),
           "Over-quota requests demoted one criticality tier (Critical -> "
           "Default -> Sheddable; graceful degradation instead of a hard "
           "shed).", GATEWAY_SURFACE),
    Family("gateway_tenant_quota_remaining", "gauge", ("model", "adapter"),
           "Remaining fairness-quota bucket tokens per throttled tenant "
           "(refill --fairness-quota-rps/s, cost scaled by LoRA rank).",
           GATEWAY_SURFACE),
    Family("gateway_adapter_residency", "gauge",
           ("model", "adapter", "pod", "tier"),
           "Adapter residency as the placement planner sees it: one "
           "series per (pod, adapter) with its tier (slot | host); disk-"
           "tier adapters have no series (gateway/placement.py).",
           GATEWAY_SURFACE),
    Family("gateway_placement_decisions_total", "counter", ("action",),
           "Placement-planner decisions emitted, by action (prefetch | "
           "migrate | demote | evict); executed by lora_sidecar "
           "--planner-url over the adapter wire.", GATEWAY_SURFACE),
    Family("gateway_placement_would_steer_total", "counter", (),
           "Picks that landed on a pod without the adapter RAM-resident "
           "while a resident replica existed (placement_mode=log_only "
           "observable; routing unchanged).", GATEWAY_SURFACE),
    Family("gateway_placement_wrong_tier_picks_total", "counter", (),
           "Same condition under placement_mode=prefer_resident — zero "
           "modulo counted escapes (the cold_start_storm chaos bar).",
           GATEWAY_SURFACE),
    Family("gateway_placement_escapes_total", "counter", (),
           "prefer_resident last-resort escapes: the adapter was resident "
           "in the pool but on no candidate, so the full set served.",
           GATEWAY_SURFACE),
    Family("gateway_statebus_peers", "gauge", (),
           "Gateway replicas with a FRESH statebus snapshot (self "
           "excluded); 0 with peers configured means local-only "
           "enforcement fallback (gateway/statebus.py).", GATEWAY_SURFACE),
    Family("gateway_statebus_snapshot_age_seconds", "gauge", ("replica",),
           "Age of each known replica's newest statebus snapshot (local "
           "receive clock; own replica included at ~0).", GATEWAY_SURFACE),
    Family("gateway_statebus_merge_seconds", "histogram", (),
           "Statebus merge latency per received doc batch (gossip fold, "
           "not the network round trip).", GATEWAY_SURFACE),
    Family("gateway_statebus_stale_fallbacks_total", "counter", (),
           "Transitions into local-only enforcement because every peer "
           "snapshot aged past the staleness bound (journaled as "
           "statebus_stale; recovery journals statebus_rejoin).",
           GATEWAY_SURFACE),
    Family("gateway_statebus_exchanges_total", "counter", ("outcome",),
           "Peer push-pull exchange attempts by outcome (ok | error).",
           GATEWAY_SURFACE),
    Family("gateway_fleet_sources", "gauge", ("kind",),
           "Sources the fleet collector reached on its last /debug/fleet "
           "pull, by kind (gateway = statebus peers + self, pod = pool "
           "replicas; gateway/fleetobs.py).", GATEWAY_SURFACE),
    Family("gateway_fleet_stitched_traces", "gauge", (),
           "Cross-replica traces stitched on the last fleet pull.",
           GATEWAY_SURFACE),
    Family("gateway_fleet_collect_errors_total", "counter", ("source",),
           "Fleet-collector pull failures by source (also journaled as "
           "fleet_peer_error); the source's cached view keeps serving.",
           GATEWAY_SURFACE),
    Family("gateway_fleet_collect_seconds", "histogram", (),
           "Wall time of one full fleet pull (all sources concurrent).",
           GATEWAY_SURFACE),
    Family("gateway_kv_reuse_efficiency", "gauge", ("pod",),
           "Per-pod prefix-cache reuse efficiency: reused prompt tokens / "
           "(reused + prefilled) cumulative (gateway/kvobs.py over the "
           "replicas' tpu:kv_* ledger families).", GATEWAY_SURFACE),
    Family("gateway_kv_parked_share", "gauge", ("pod",),
           "Fraction of the pod's KV block budget held by parked "
           "(prefilled-but-unslotted) handoff KV.", GATEWAY_SURFACE),
    Family("gateway_kv_saved_tokens_per_s", "gauge", ("pod",),
           "EMA rate of prefill tokens the pod's prefix cache absorbed "
           "(scrape-tick deltas of tpu:prefix_reused_tokens).",
           GATEWAY_SURFACE),
    Family("gateway_kv_duplicated_prefixes", "gauge", (),
           "Prefixes resident on >= min_replicas pods at the last rollup "
           "— the fleet duplication index's row count.", GATEWAY_SURFACE),
    Family("gateway_kv_duplicated_blocks", "gauge", (),
           "KV blocks caching a prefix some other replica also holds "
           "(sum(holders) - max(holders) per duplicated prefix): HBM "
           "spent caching the same tokens twice.", GATEWAY_SURFACE),
    Family("gateway_kv_dedup_tokens_saved_per_s", "gauge", (),
           "Reuse traffic (tokens/s) currently served by duplicate copies "
           "— what a KV-affinity router or shared KV store could serve "
           "from one copy.", GATEWAY_SURFACE),
    Family("gateway_kv_prefix_replicas", "gauge", ("prefix",),
           "Replica count holding each duplicated prefix (top rows by "
           "duplicated blocks; prefix = content-addressed 16-hex id).",
           GATEWAY_SURFACE),
    Family("gateway_capacity_saturation", "gauge", ("resource",),
           "Pool saturation index per resource, 0..1 (gateway/capacity.py; "
           "max over pods — saturation is a weakest-link property): kv "
           "(1 - free/capacity), decode_slots (batch occupancy window "
           "mean), queue (waiting over waiting+running), prefill_compute "
           "(prefill wall seconds per wall second).", GATEWAY_SURFACE),
    Family("gateway_capacity_pod_saturation", "gauge", ("pod", "resource"),
           "Per-pod per-resource saturation index, 0..1 (the rows behind "
           "gateway_capacity_saturation).", GATEWAY_SURFACE),
    Family("gateway_capacity_offered_rps", "gauge", (),
           "EMA'd offered arrival rate (prefill completions/s summed over "
           "the pool's pods, scrape-tick deltas).", GATEWAY_SURFACE),
    Family("gateway_capacity_knee_rps", "gauge", (),
           "The calibrated twin's knee: offered load where simulated TTFT "
           "p95 crosses the SLO (bisected DES probes at the observed "
           "prompt/output mix, times the pod count).", GATEWAY_SURFACE),
    Family("gateway_capacity_headroom_ratio", "gauge", (),
           "Headroom-at-SLO: (knee - offered) / knee, clamped to 0 "
           "(0 = at or past the knee; 1 = idle).", GATEWAY_SURFACE),
    Family("gateway_capacity_time_to_breach_seconds", "gauge", (),
           "Forecast seconds until the offered-rate trend (least-squares "
           "slope over recent windows) crosses the knee; -1 = no breach "
           "on the current trend; 0 = already past the knee.  Entering "
           "the breach horizon journals a capacity_forecast event.",
           GATEWAY_SURFACE),
    Family("gateway_twin_drift", "gauge", ("observable",),
           "EMA'd relative divergence |predicted - observed| / observed "
           "between the calibrated twin and the live pool, per observable "
           "(prefill_s, decode_step_s, occupancy via Little's law).  "
           "Breaching --twin-drift-threshold journals twin_drift and "
           "untrusts forecasts.", GATEWAY_SURFACE),
    Family("gateway_twin_trusted", "gauge", (),
           "1 while the twin's forecasts are trusted (a model is loaded "
           "or fitted AND drift is below threshold); 0 = capacity "
           "surfaces still export but must not be believed.",
           GATEWAY_SURFACE),
    Family("gateway_pick_sample_total", "counter", (),
           "Picks recorded by the routing decision ledger "
           "(gateway/pickledger.py; deterministic every-Nth sampling — "
           "multiply by the configured sample_every to estimate pick "
           "volume).", GATEWAY_SURFACE),
    Family("gateway_pick_narrowing", "gauge", ("stage",),
           "Mean surviving candidates after each pick stage across "
           "sampled picks (pool -> role_partition -> filter_tree -> "
           "health/circuit -> fairness -> placement -> prefix_affinity "
           "-> rng): the funnel /debug/picks itemizes per record.",
           GATEWAY_SURFACE),
    Family("gateway_pick_steered_total", "counter", ("seam",),
           "Sampled picks whose final survivor set the counterfactual "
           "replay shows this advisor seam changed (disabling the seam "
           "yields a different set) — the 'why pod X' attribution.",
           GATEWAY_SURFACE),
    Family("gateway_events_total", "counter", ("kind",),
           "Flight-recorder events by kind (events.py; the journal itself "
           "is served by /debug/events).", GATEWAY_SURFACE),
    Family("gateway_loop_lag_seconds_total", "counter", (),
           "The proxy's stall clock (tracing.LoopClock): a task sleeps 50 ms "
           "over and over; this sums how late each sleep ended. Over "
           "gateway_loop_ticks_total, the mean wait of a coroutine that was "
           "ready for its turn on the event loop.", GATEWAY_SURFACE),
    Family("gateway_loop_ticks_total", "counter", (),
           "Sleeps the proxy's stall clock has finished.", GATEWAY_SURFACE),
    Family("gateway_loop_stall_seconds_total", "counter", (),
           "Sum of the stall clock's overshoots of 250 ms and more: 0 in a "
           "sound run; the pause's length where the process, or the whole "
           "machine (then every process reads the same), stopped.",
           GATEWAY_SURFACE),
)

SERVER_FAMILIES = (
    Family("tpu:prefill_queue_size", "gauge", (),
           "Requests awaiting prefill.", SERVER_SURFACE),
    Family("tpu:decode_queue_size", "gauge", (),
           "Prefilled requests awaiting a decode slot.", SERVER_SURFACE),
    Family("tpu:num_requests_running", "gauge", (),
           "In-flight requests.", SERVER_SURFACE),
    Family("tpu:num_requests_waiting", "gauge", (),
           "Total queued (prefill + decode).", SERVER_SURFACE),
    Family("tpu:kv_cache_usage_perc", "gauge", (),
           "Paged-KV utilization 0..1 (parked KV included).",
           SERVER_SURFACE),
    Family("tpu:kv_tokens_capacity", "gauge", (),
           "Total KV token capacity.", SERVER_SURFACE),
    Family("tpu:kv_tokens_free", "gauge", (),
           "Free KV token headroom.", SERVER_SURFACE),
    Family("tpu:kv_parked_tokens", "gauge", (),
           "Prefilled-but-unslotted KV tokens held outside the cache.",
           SERVER_SURFACE),
    Family("tpu:decode_tokens_per_sec", "gauge", (),
           "Recent decode throughput (EMA).", SERVER_SURFACE),
    Family("tpu:lora_requests_info", "gauge",
           ("running_lora_adapters", "waiting_lora_adapters", "max_lora",
            "adapter_ranks", "resident_tiers"),
           "Adapter-activity info gauge (vLLM semantics: running = "
           "actively decoding, waiting = parked in decode_wait / queued); "
           "adapter_ranks is a name:rank CSV (rank-aware fairness "
           "weighting); resident_tiers is a name:tier CSV over the "
           "slot/host residency ladder; value is a unix timestamp "
           "(latest series wins).", SERVER_SURFACE),
    Family("tpu:adapter_residency_info", "gauge", ("tier", "adapters"),
           "Residency ladder info gauge: one line per tier (slot = "
           "device buffers, host = host-RAM cache) with an adapters CSV; "
           "every adapter appears in exactly one tier per replica "
           "(server/lora_manager.py); value is a unix timestamp.",
           SERVER_SURFACE),
    Family("tpu:adapter_tier_transitions_total", "counter", ("from", "to"),
           "Residency-ladder transitions (load, promote, demote, "
           "prefetch, evict, host-LRU overflow) by from/to tier.",
           SERVER_SURFACE),
    Family("tpu:adapter_load_seconds_total", "counter", ("tier",),
           "Cumulative adapter-load wall seconds by source tier (host = "
           "device put of a cached copy, disk = full Orbax restore); "
           "mean = _total / tpu:adapter_loads_total.", SERVER_SURFACE),
    Family("tpu:adapter_loads_total", "counter", ("tier",),
           "Adapter loads performed, by source tier.", SERVER_SURFACE),
    Family("tpu:pool_role", "gauge", ("role",),
           "Disaggregation role info gauge (collocated | prefill | "
           "decode).", SERVER_SURFACE),
    Family("tpu:prefix_reused_tokens", "counter", (),
           "Cumulative prompt tokens served from the prefix cache.",
           SERVER_SURFACE),
    Family("tpu:spec_cycles", "counter", (),
           "Speculative-decoding verify cycles.", SERVER_SURFACE),
    Family("tpu:spec_tokens_per_cycle", "gauge", (),
           "Accepted tokens per speculative cycle (draft-quality signal).",
           SERVER_SURFACE),
    Family("tpu:stream_lanes", "gauge", (),
           "Configured concurrent chunk-stream lanes (long prompts "
           "streaming into reserved cache lanes at once; "
           "EngineConfig.stream_lanes).", SERVER_SURFACE),
    Family("tpu:stream_lanes_active", "gauge", (),
           "Chunk-stream lanes currently mid-prompt; at the configured "
           "lane count a further long prompt head-of-line waits.",
           SERVER_SURFACE),
    Family("tpu:dispatch_steps", "histogram", (),
           "Fused decode steps per dispatch — the adaptive multi-step "
           "planner's decision record (buckets land on its power-of-two "
           "choices; EngineConfig.adaptive_steps).", SERVER_SURFACE),
    Family("tpu:moe_layer_steps_total", "counter", (),
           "Sparse layers run, one per layer per decode step or prefill "
           "program (0 for a dense model).", SERVER_SURFACE),
    Family("tpu:moe_assignments_total", "counter", (),
           "Token-to-expert assignments of live rows that the expert "
           "matmuls computed, summed over layer-steps: under a share of the "
           "experts (n_experts_local) those whose expert is held here.",
           SERVER_SURFACE),
    Family("tpu:moe_experts_touched_total", "counter", (),
           "Experts with at least one live assignment, summed over "
           "layer-steps: over tpu:moe_layer_steps_total, the experts a "
           "layer reads (under a share: of those held here).",
           SERVER_SURFACE),
    Family("tpu:moe_tiles_used_total", "counter", (),
           "Row tiles of the expert dispatch that hold a group, summed over "
           "layer-steps: over tpu:moe_experts_touched_total, the tiles a "
           "touched expert's group takes (1.0: every group in one tile).",
           SERVER_SURFACE),
    Family("tpu:moe_assignments_routed_total", "counter", (),
           "Every token-to-expert assignment the router made for a live "
           "row, held here or not, summed over layer-steps: "
           "tpu:moe_assignments_total over it is the share of the routing "
           "this program's experts took (1 without a share).",
           SERVER_SURFACE),
    Family("tpu:moe_tiles_laid_out_total", "counter", (),
           "Row tiles of the expert dispatch's layout, sized for the worst "
           "routing (dropless), summed over layer-steps: "
           "tpu:moe_tiles_used_total over it is the share of the layout "
           "the expert matmul's grid walks.", SERVER_SURFACE),
    Family("tpu:sample_steps_total", "counter", ("path",),
           "Decode steps by the sampler's path, as the device took it: "
           "argmax (no live row samples: no sort, filter or draw) | draw "
           "(temperature only) | filtered (some live row asks for top-k or "
           "top-p: the full-vocabulary sort); metrics_registry.SAMPLE_PATHS.",
           SERVER_SURFACE),
    Family("tpu:decode_stage_ops_total", "counter", (),
           "Host-to-device transfers and helper programs the engine issued "
           "to stage its plain decode dispatches, the decode program's own "
           "call not counted: over tpu:dispatch_steps_count, the trips "
           "through JAX's dispatch a decode block costs before its call.",
           SERVER_SURFACE),
    Family("tpu:latent_kv_positions_total", "counter", (),
           "Cache positions a latent (MLA) model's live rows held, summed "
           "over the steps of the plain decode dispatches: over "
           "tpu:dispatch_steps_sum, the latent rows one decode step's "
           "attention kernel reads per layer. 0 for a model with per-head "
           "K/V lanes.",
           SERVER_SURFACE),
    Family("tpu:ssm_state_rows_total", "counter", (),
           "Rows whose recurrent (state-space) state a decode step rewrote, "
           "summed over the steps of the plain decode dispatches: over "
           "tpu:dispatch_steps_sum, the states one decode step's update "
           "kernel reads and writes per layer. 0 for a model without a "
           "mixer.",
           SERVER_SURFACE),
    Family("tpu:conv_state_rows_total", "counter", (),
           "Rows whose conv state (the last inputs of a gated short "
           "convolution, models/shortconv.py) a decode step shifted and "
           "rewrote, summed over the steps of the plain decode dispatches: "
           "over tpu:dispatch_steps_sum, the rows one decode step's conv "
           "layers update, a layer. 0 for a model without conv layers.",
           SERVER_SURFACE),
    Family("tpu:kda_state_rows_total", "counter", (),
           "Rows whose delta-rule matrix state (models/kda.py) a decode "
           "step rewrote, summed over the steps of the plain decode "
           "dispatches: over tpu:dispatch_steps_sum, the rows whose states "
           "one decode step's update kernel reads and writes, a layer. 0 "
           "for a model without KDA layers.",
           SERVER_SURFACE),
    Family("tpu:kv_positions_read_total", "counter", ("lanes",),
           "Cache positions the attention of the plain decode dispatches' "
           "steps read of the live rows' lanes, a layer of the kind: "
           "lanes=full the rows' whole lengths (a full-attention layer), "
           "lanes=window at most the window of each (a sliding-window "
           "layer's ring); over tpu:dispatch_steps_sum, the positions one "
           "decode step's kernel reads per layer of the kind. 0 for a model "
           "all of whose layers hold full lanes (it counts for a stack with "
           "a window, lanes=full and lanes=window, and for one with conv "
           "layers, lanes=full of its attention layers); "
           "metrics_registry.KV_LANES.",
           SERVER_SURFACE),
    Family("tpu:decode_attn_grid_steps_total", "counter", (),
           "Grid steps the decode-attention kernel walks a layer's call, "
           "summed over the steps of the plain decode dispatches: the live "
           "rows' tiles (ceil(length / tile) each; a row that does not "
           "decode has none), reckoned on the host from the staged lengths "
           "by the schedule's own rule; of a stack with two kinds of lane, "
           "the full lanes'. Over tpu:dispatch_steps_sum, the steps one "
           "decode step's kernel call walks, where slots x tiles was walked "
           "before. 0 where no kernel takes the cache's shape.",
           SERVER_SURFACE),
    Family("tpu:lora_rows_total", "counter", (),
           "Live rows whose LoRA slot is >= 0, summed over the steps of the "
           "plain decode dispatches: over tpu:dispatch_steps_sum, the rows "
           "of a step that use what it reads of the adapters (a step that "
           "is handed adapter buffers reads every slot's matrices of the "
           "targets handed, whichever rows use them).",
           SERVER_SURFACE),
    Family("tpu:lora_free_steps_total", "counter", (),
           "Steps of the plain decode dispatches that ran the decode program "
           "without the LoRA delta, because no row of the block named an "
           "adapter: over tpu:dispatch_steps_sum, the share of decode steps "
           "that read no adapter matrix. 0 on a server without adapter "
           "buffers (--max-loras 0), whose one program never has the delta.",
           SERVER_SURFACE),
    Family("tpu:lora_target_reads_total", "counter", (),
           "LoRA targets (of q, k, v, o, gate, up, down) whose buffers the "
           "plain decode dispatches that ran WITH the delta were handed, "
           "summed over their steps: a block with an adapter row is handed "
           "only the targets some resident adapter carries. Over "
           "(tpu:dispatch_steps_sum - tpu:lora_free_steps_total), the "
           "targets a delta step reads: 2 where every resident adapter is "
           "on q and v, 7 where one carries them all.",
           SERVER_SURFACE),
    Family("tpu:logprob_steps_total", "counter", (),
           "Steps of the plain decode dispatches staged while a held slot's "
           "request asked for logprobs: the steps whose program may take "
           "the log-softmax and top-5 over [slots, vocabulary] (it does "
           "where such a row is still live, for the whole batch; a step "
           "nobody asked takes neither). Over tpu:dispatch_steps_sum, the "
           "share of decode steps that pay for logprobs: 0 under traffic "
           "that asks for none.",
           SERVER_SURFACE),
    Family("tpu:decode_blocks_overlapped_total", "counter", (),
           "Decode blocks dispatched from the device carry while an earlier "
           "block was still unread: over the decode dispatches "
           "(tpu:dispatch_wall_seconds_count, phases decode and spec), the "
           "share of blocks for which the device had its next step queued "
           "before the host read the last. 0: every block was staged with "
           "none in flight (the device had run dry).",
           SERVER_SURFACE),
    Family("tpu:prompt_programs_total", "counter", ("program",),
           "Prompt programs enqueued, by the jitted program: prefill (one "
           "prompt at its bucket) | prefill_many (same-bucket prompts in one "
           "call) | chunk (a piece of a streamed prompt, or the suffix behind "
           "a cached prefix) | ring (sequence-parallel); "
           "metrics_registry.PROMPT_PROGRAMS.", SERVER_SURFACE),
    Family("tpu:chunk_attn_grid_steps_total", "counter", (),
           "Grid steps the chunk-attention kernel walks, summed over the "
           "attention layers of the chunk programs enqueued: a layer's call "
           "is kv heads x query tiles x key tiles of its lane (one step "
           "serves every query head of its kv head; a window layer's lane "
           "is its ring with the chunk behind it), reckoned on the host from "
           "the shapes by the dispatcher's own rule. Over "
           "tpu:prompt_programs_total{program=\"chunk\"}, the steps a chunk "
           "program. 0 where no kernel takes the shapes (int8 lanes, a "
           "chunk or a lane the tiles do not divide).", SERVER_SURFACE),
    Family("tpu:prompt_positions_total", "counter", ("program", "kind"),
           "Positions the prompt programs computed, counted where each is "
           "enqueued: kind=real the prompt's tokens, kind=pad the padding up "
           "to the program's shape (a grouped program: rows x bucket). pad "
           "over the sum is the share of the prompt programs' work that is "
           "thrown away.", SERVER_SURFACE),
    Family("tpu:prompt_program_seconds_total", "counter", ("program",),
           "Device-queue time of the prompt programs: each one's interval on "
           "the loop's completion chain, from the later of its enqueue and "
           "the last completion the loop saw to its own completion (programs "
           "seen complete together share theirs by positions). With the "
           "decode blocks' intervals (tpu:dispatch_wall_seconds_sum, phases "
           "decode and spec) it tiles the time the device's queue was busy; "
           "over tpu:prompt_programs_total, seconds a program.",
           SERVER_SURFACE),
    Family("tpu:prefill_seconds", "histogram", ("model", "role"),
           "Prefill compute latency.", SERVER_SURFACE),
    Family("tpu:handoff_seconds", "histogram", ("model", "role"),
           "KV-handoff serialize / deserialize+attach latency.",
           SERVER_SURFACE),
    Family("tpu:decode_step_seconds", "histogram", ("model", "role"),
           "Per-step decode cadence.", SERVER_SURFACE),
    Family("tpu:adapter_step_seconds_total", "counter",
           ("model", "adapter", "phase"),
           "TPU step wall-seconds charged to each adapter (decode "
           "dispatches split evenly across active slots; prefills charged "
           "whole to their owner; adapter=base = no-LoRA rows; "
           "server/usage.py).", SERVER_SURFACE),
    Family("tpu:adapter_tokens_total", "counter",
           ("model", "adapter", "phase"),
           "Tokens attributed per adapter (prompt tokens at prefill, "
           "emitted tokens at decode).", SERVER_SURFACE),
    Family("tpu:adapter_kv_block_seconds_total", "counter",
           ("model", "adapter"),
           "Time-integral of KV blocks held per adapter (parked "
           "decode_wait KV included; token-seconds when the cache is not "
           "paged).", SERVER_SURFACE),
    Family("tpu:step_seconds_total", "counter", ("phase",),
           "Engine wall step-seconds per phase — the conservation "
           "denominator: per-adapter step-seconds sum to this within "
           "epsilon (tests/test_usage.py).", SERVER_SURFACE),
    Family("tpu:idle_slot_seconds_total", "counter", (),
           "Slot-seconds decode dispatches ran with empty rows (pool "
           "waste).", SERVER_SURFACE),
    Family("tpu:prefill_padding_tokens_total", "counter", (),
           "Prompt positions computed as padding and thrown away (pool "
           "waste): a bucket's, a group's, a ring's and, since PR 57, the "
           "chunk stream's (every piece of a streamed prompt goes out at "
           "the largest bucket). By construction the sum over programs of "
           "tpu:prompt_positions_total{kind=\"pad\"}.", SERVER_SURFACE),
    Family("tpu:decode_batch_occupancy", "histogram", (),
           "Active-slots / total-slots fraction per decode dispatch.",
           SERVER_SURFACE),
    Family("tpu:dispatch_wall_seconds", "histogram", ("phase",),
           "Per-dispatch device program + host-sync wall by phase "
           "(prefill | decode | spec); the step-timeline profiler's "
           "dispatch bucket (server/profiler.py, /debug/profile).",
           SERVER_SURFACE),
    Family("tpu:dispatch_gap_seconds", "histogram", ("kind",),
           "Engine-thread gap between consecutive dispatches (kind=host "
           "= step-loop overhead the ROADMAP item-2 levers amortize; "
           "kind=idle = the gap contained a no-work wait).",
           SERVER_SURFACE),
    Family("tpu:engine_phase_seconds_total", "counter", ("phase", "on"),
           "Engine-thread seconds by phase (self time; the phases tile the "
           "thread's wall): admit | prefill.stage/.wait/.emit | "
           "decode.plan/.stage/.wait/.readback/.emit/.account | idle | "
           "other; on=device where the thread is blocked on the chip, "
           "on=host elsewhere (metrics_registry.ENGINE_PHASES; the same "
           "names are engine.<phase> annotations in a profiler trace).",
           SERVER_SURFACE),
    Family("tpu:kv_blocks_total", "gauge", (),
           "KV block budget the ledger accounts: pool blocks + parked "
           "block-equivalents (server/kv_ledger.py; paged mode with "
           "EngineConfig.kv_ledger).", SERVER_SURFACE),
    Family("tpu:kv_block_tokens", "gauge", (),
           "Tokens per KV block (the ledger's block size).",
           SERVER_SURFACE),
    Family("tpu:kv_blocks", "gauge", ("state",),
           "Block budget by state (free | active | prefix_resident | "
           "parked); states tile the budget, so the sum equals "
           "tpu:kv_blocks_total — the conservation invariant "
           "tests/test_kv_ledger.py pins.", SERVER_SURFACE),
    Family("tpu:kv_block_events_total", "counter", ("kind",),
           "Block lifecycle events (alloc | evict | reuse_hit | "
           "reuse_unwind | register | release | cache_park | park | "
           "unpark | sweep).", SERVER_SURFACE),
    Family("tpu:kv_prefix_hits_total", "counter", ("prefix",),
           "Prefix-cache hits per content-addressed prefix id (16-hex of "
           "the deepest chained block hash; identical across replicas for "
           "the same prompt prefix — the fleet duplication join key).",
           SERVER_SURFACE),
    Family("tpu:kv_prefix_tokens_saved_total", "counter", ("prefix",),
           "Prompt tokens served from cache per prefix (reuse unwinds "
           "subtracted, so the sum tracks tpu:prefix_reused_tokens).",
           SERVER_SURFACE),
    Family("tpu:kv_prefix_resident_blocks", "gauge", ("prefix",),
           "Cached chain depth (blocks) currently resident per prefix; "
           "decays as LRU eviction consumes the chain.", SERVER_SURFACE),
    Family("tpu:kv_free_run_blocks", "histogram", (),
           "Lengths of maximal runs of consecutive free physical block "
           "ids at the last sync — the fragmentation view (a pool can be "
           "40% free and still lack contiguous headroom).", SERVER_SURFACE),
    Family("tpu:kv_parked_share", "histogram", (),
           "Parked share of the block budget sampled at each ledger sync.",
           SERVER_SURFACE),
    Family("tpu:events_total", "counter", ("kind",),
           "Replica-side flight-recorder events by kind (served by the "
           "replica's /debug/events).", SERVER_SURFACE),
    Family("tpu:stream_write_lag_seconds_total", "counter", (),
           "Seconds from the engine thread publishing a token to a stream "
           "(Request.t_emit) to the SSE chunk's write returning on the "
           "event loop, summed over the data chunks written: over "
           "tpu:stream_chunks_total, what a token waits for the consumer's "
           "pool thread, the loop and the socket.", SERVER_SURFACE),
    Family("tpu:stream_chunks_total", "counter", (),
           "SSE data chunks written whose publish time was known (the "
           "denominator of tpu:stream_write_lag_seconds_total).",
           SERVER_SURFACE),
    Family("tpu:loop_lag_seconds_total", "counter", (),
           "The model server's stall clock (tracing.LoopClock): a task "
           "sleeps 50 ms over and over; this sums how late each sleep "
           "ended. Over tpu:loop_ticks_total, the event loop's mean lag: "
           "what the engine thread and the streams' pool threads cost the "
           "loop in waits for the interpreter lock.", SERVER_SURFACE),
    Family("tpu:loop_ticks_total", "counter", (),
           "Sleeps the model server's stall clock has finished.",
           SERVER_SURFACE),
    Family("tpu:loop_stall_seconds_total", "counter", (),
           "Sum of the stall clock's overshoots of 250 ms and more: 0 in a "
           "sound run; the pause's length where the process, or the whole "
           "machine, stopped.", SERVER_SURFACE),
)


def all_families() -> tuple[Family, ...]:
    return GATEWAY_FAMILIES + SERVER_FAMILIES


def registered_names() -> set[str]:
    return {f.name for f in all_families()}


def render_markdown() -> str:
    """The full ``docs/METRICS.md`` content (generated; do not hand-edit)."""
    out = [
        "# Metrics reference",
        "",
        "<!-- GENERATED by `make metrics-docs` from "
        "llm_instance_gateway_tpu/metrics_registry.py — do not edit. -->",
        "",
        "Every Prometheus family the framework exposes, by surface.  "
        "Histogram families expose the usual `_bucket`/`_sum`/`_count` "
        "series.  Counter families keyed by an attribution label render an "
        "unlabeled fallback line when no labeled sample exists yet.",
        "",
    ]
    for surface in (GATEWAY_SURFACE, SERVER_SURFACE):
        out += [f"## {surface}", "",
                "| family | type | labels | help |",
                "|---|---|---|---|"]
        for f in all_families():
            if f.surface != surface:
                continue
            labels = ", ".join(f.labels) if f.labels else "—"
            help_cell = f.help.replace("|", "\\|")  # literal pipes in cells
            out.append(
                f"| `{f.name}` | {f.kind} | {labels} | {help_cell} |")
        out.append("")
    return "\n".join(out)
