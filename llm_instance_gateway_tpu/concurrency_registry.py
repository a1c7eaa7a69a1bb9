"""Registry of every cross-thread shared field the framework mutates.

PRs 10-11 multiplied the cross-thread surface: the statebus gossip path
calls ``set_remote_noisy`` / ``set_remote_avoid`` / ``set_remote_resident``
into advisor state that concurrent data-path picks read lock-free, per-pool
tick stacks run on the observability thread, the fleet collector and the
step profiler each added their own locks — ~40 ``threading.Lock`` sites
across the tree, each with a hand-maintained discipline that lived in
comments.  This module is the single declarative list (the
``metrics_registry.py`` shape): every class owning cross-thread state
declares its **owning domain**, its **lock attributes**, and — for every
field rebound after construction — the field's **publication discipline**
and the methods allowed to write it.

The concurrency lint (``lint/concurrency.py``; ``make lint``) cross-checks
this against the AST:

- ``ownership``: a class that constructs a lock but is not registered
  fails; a registered class assigning an undeclared field outside
  ``__init__`` fails; a write from a method not in the field's ``writers``
  allowlist fails.  Overlay seams (``set_remote_*``) are the declared
  gossip-domain exceptions, not folklore.
- ``publish-by-swap``: a field declared SWAP_PUBLISHED is read lock-free
  on the pick hot path, so writers must REPLACE the whole object —
  any in-place mutation (``.append``/``.update``/``[k] =``/``+=``) of it
  fails lint.
- ``lock-order``: the interprocedural acquisition graph over the declared
  lock attributes (plus call edges resolved through ``BINDINGS``) must be
  acyclic; the runtime ``lockwitness`` cross-checks the graph's
  completeness from real acquisitions in the interleave harness.

Keep entries grouped by module; ``tests/test_lint.py`` and the clean-tree
lint run are the currency tests — an undeclared shared field fails CI, a
dead entry fails CI.
"""

from __future__ import annotations

from dataclasses import dataclass

# -- owning domains (who mutates the state in steady operation) -------------
DATA_PATH = "data-path"            # request/pick threads (HTTP + gRPC pool)
OBS_TICK = "observability-tick"    # the proxy's control_tick thread
GOSSIP = "gossip"                  # statebus exchange / merged-view apply
ENGINE_STEP = "engine-step"        # the model server's engine loop thread
COLLECTOR = "collector"            # fleet-collector pulls (event loop)
CONTROL = "control"                # config reload / lifecycle (rare writes)

DOMAINS = (DATA_PATH, OBS_TICK, GOSSIP, ENGINE_STEP, COLLECTOR, CONTROL)

# -- publication disciplines ------------------------------------------------
# Reads and writes both happen inside the owning class's lock; lock-free
# readers are bugs (the lint can't see reads, but the witness harness and
# the discipline docs make the contract explicit).
LOCK_GUARDED = "lock-guarded"
# Lock-free reads on the hot path; writers REPLACE the field with a whole
# (effectively immutable) object — the ``_noisy_pods_cache`` tuple-swap
# idiom.  In-place mutation anywhere fails the publish-by-swap rule.
SWAP_PUBLISHED = "publish-by-swap"
# Increment-only numeric state owned by one domain (or bumped under the
# lock); readers tolerate a stale value, never a torn one.
MONOTONIC = "monotonic-counter"
# Touched only from the owning domain's single thread (the engine step
# loop's scratch state); in-place mutation is legal because there are NO
# cross-thread readers — crossing a thread boundary means re-declaring
# under one of the disciplines above.
OWNER_PRIVATE = "owner-private"

DISCIPLINES = (LOCK_GUARDED, SWAP_PUBLISHED, MONOTONIC, OWNER_PRIVATE)


@dataclass(frozen=True)
class SharedField:
    name: str
    discipline: str
    writers: tuple = ()    # methods allowed to rebind it (besides __init__)
    domain: str = ""       # override of the class domain (overlay seams)
    note: str = ""


@dataclass(frozen=True)
class SharedClass:
    module: str            # repo-relative path
    name: str
    domain: str            # dominant owning domain
    lock_attrs: tuple = ("_lock",)
    rlock_attrs: tuple = ()   # reentrant members of lock_attrs
    fields: tuple = ()
    note: str = ""


PKG = "llm_instance_gateway_tpu"

CLASSES = (
    # -- shared infrastructure ---------------------------------------------
    SharedClass(
        f"{PKG}/events.py", "EventJournal", DATA_PATH,
        lock_attrs=("_lock",),
        fields=(
            SharedField("_seq", MONOTONIC, writers=("emit",)),
        ),
        note="ring appends are GIL-atomic; seq/counters bump under the "
             "lock"),
    SharedClass(
        f"{PKG}/gateway/telemetry.py", "GatewayMetrics", DATA_PATH,
        fields=(
            SharedField("lora_affinity_hits", MONOTONIC,
                        writers=("record_pick",)),
        ),
        note="all counter tables mutate in place under the lock"),
    SharedClass(
        f"{PKG}/gateway/provider.py", "Provider", OBS_TICK,
        lock_attrs=("_lock",), rlock_attrs=("_lock",),
        fields=(
            SharedField("version", MONOTONIC,
                        writers=("refresh_metrics_once",
                                 "refresh_pods_once",
                                 "update_pod_metrics")),
        ),
        note="snapshot() hands out (version, pods) pairs; pods lists are "
             "swapped whole per refresh"),
    SharedClass(
        f"{PKG}/gateway/datastore.py", "Datastore", CONTROL,
        lock_attrs=("_lock",), rlock_attrs=("_lock",),
        fields=(
            SharedField("_pool", SWAP_PUBLISHED, writers=("set_pool",)),
        )),

    # -- gateway advisor stack (per pool) ----------------------------------
    SharedClass(
        f"{PKG}/gateway/health.py", "HealthScorer", OBS_TICK,
        fields=(
            SharedField("_non_healthy", SWAP_PUBLISHED,
                        writers=("update",),
                        note="the pick seam's lock-free avoid mark set"),
            SharedField("last_update", MONOTONIC, writers=("update",)),
            SharedField("would_avoid_total", MONOTONIC,
                        writers=("note_pick",), domain=DATA_PATH),
        )),
    SharedClass(
        f"{PKG}/gateway/resilience.py", "CircuitBreaker", DATA_PATH,
        fields=(
            SharedField("_blocked_cache", SWAP_PUBLISHED,
                        writers=("blocked_set",),
                        note="lock-free fast-path read; rebuilt under the "
                             "lock when dirty"),
            SharedField("_cache_expiry", SWAP_PUBLISHED,
                        writers=("blocked_set",)),
            SharedField("_cache_dirty", LOCK_GUARDED,
                        writers=("blocked_set", "note_pick", "prune",
                                 "_transition", "_maybe_half_open")),
        )),
    SharedClass(
        f"{PKG}/gateway/resilience.py", "RetryBudget", DATA_PATH,
        fields=(
            SharedField("_tokens", LOCK_GUARDED,
                        writers=("note_request", "try_spend")),
            SharedField("spent_total", MONOTONIC, writers=("try_spend",)),
            SharedField("denied_total", MONOTONIC, writers=("try_spend",)),
        )),
    SharedClass(
        f"{PKG}/gateway/resilience.py", "ResiliencePlane", DATA_PATH,
        fields=(
            SharedField("escape_hatch_total", MONOTONIC,
                        writers=("note_escape_hatch",)),
            SharedField("_remote_avoid", SWAP_PUBLISHED,
                        writers=("set_remote_avoid",), domain=GOSSIP,
                        note="statebus overlay; avoid_set() unions it "
                             "lock-free per pick"),
        )),
    SharedClass(
        f"{PKG}/gateway/usage.py", "UsageRollup", OBS_TICK,
        fields=(
            SharedField("_noisy_models", SWAP_PUBLISHED,
                        writers=("tick", "seed_noisy",
                                 "set_remote_noisy"),
                        note="noisy() serves it lock-free per pick"),
            SharedField("_noisy_key_of", SWAP_PUBLISHED,
                        writers=("tick", "seed_noisy"),
                        note="note_pick reads it lock-free; every writer "
                             "(seed_noisy included) rebuilds and swaps "
                             "the dict whole"),
            SharedField("_remote_noisy", SWAP_PUBLISHED,
                        writers=("set_remote_noisy",), domain=GOSSIP,
                        note="statebus overlay; note_pick falls back to "
                             "it lock-free"),
            SharedField("_totals", LOCK_GUARDED, writers=("tick",)),
            SharedField("_pool_waste", LOCK_GUARDED, writers=("tick",)),
            SharedField("_prev_requests", LOCK_GUARDED, writers=("tick",)),
            SharedField("last_tick", MONOTONIC,
                        writers=("tick",),
                        note="maybe_tick reads it lock-free (float "
                             "rebind)"),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
            SharedField("would_deprioritize_total", MONOTONIC,
                        writers=("note_pick",), domain=DATA_PATH),
        )),
    SharedClass(
        f"{PKG}/gateway/kvobs.py", "KvObsRollup", OBS_TICK,
        fields=(
            SharedField("_remote_tables", SWAP_PUBLISHED,
                        writers=("set_remote_tables",), domain=GOSSIP,
                        note="peer-gateway residency overlay; tick() reads "
                             "it lock-free before joining, so writers swap "
                             "the whole dict"),
            SharedField("_pods", LOCK_GUARDED, writers=("tick",)),
            SharedField("_dup_rows", LOCK_GUARDED, writers=("tick",)),
            SharedField("_dup_totals", LOCK_GUARDED, writers=("tick",)),
            SharedField("_dup_prefixes", LOCK_GUARDED, writers=("tick",)),
            SharedField("last_tick", MONOTONIC, writers=("tick",),
                        note="maybe_tick reads it lock-free (float "
                             "rebind)"),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
        ),
        note="EMA/delta tables (_prev_*, _*_rate) mutate in place under "
             "the lock; the kv_duplication journal emit runs after "
             "release (no nested acquisition)"),
    SharedClass(
        f"{PKG}/gateway/capacity.py", "CapacityPlanner", OBS_TICK,
        fields=(
            SharedField("_pods", LOCK_GUARDED,
                        writers=("_derive_saturation",),
                        note="derived lazily at render/debug time from "
                             "the stashed rows, under the same lock"),
            SharedField("_pool_saturation", LOCK_GUARDED,
                        writers=("_derive_saturation",)),
            SharedField("_prev", LOCK_GUARDED,
                        writers=("_fold_windows",),
                        note="rebuilt and swapped whole each fold (pod "
                             "membership churn prunes via the swap)"),
            SharedField("_rows_old", LOCK_GUARDED,
                        writers=("_fold_windows",)),
            SharedField("_sat_dt", LOCK_GUARDED,
                        writers=("_fold_windows",)),
            SharedField("_sat_ticks", LOCK_GUARDED,
                        writers=("_fold_windows", "_derive_saturation"),
                        note="fold invalidates, derive stamps — both "
                             "under the tick lock"),
            SharedField("_model", LOCK_GUARDED,
                        writers=("_load_artifact", "_refit"),
                        note="_load_artifact runs at construction; _refit "
                             "under the tick lock"),
            SharedField("_model_info", LOCK_GUARDED,
                        writers=("_load_artifact", "_refit")),
            SharedField("_forecast", LOCK_GUARDED,
                        writers=("_update_forecast",)),
            SharedField("_drift_state", LOCK_GUARDED,
                        writers=("_update_drift",)),
            SharedField("_drift_over", LOCK_GUARDED,
                        writers=("_update_drift",)),
            SharedField("_drift_under", LOCK_GUARDED,
                        writers=("_update_drift",)),
            SharedField("last_tick", MONOTONIC, writers=("tick",),
                        note="maybe_tick reads it lock-free (float "
                             "rebind)"),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
        ),
        note="EMA tables (_windows, _mix, _rate_hist, _drift) mutate in "
             "place under the lock; the _fold/_refit/_update helpers all "
             "run from tick() inside it; journal emits run after release "
             "(kvobs discipline)"),
    SharedClass(
        f"{PKG}/gateway/pickledger.py", "PickLedger", OBS_TICK,
        fields=(
            SharedField("_rollup", SWAP_PUBLISHED, writers=("tick",),
                        note="seam_rollup() serves statebus/fleet/loadgen "
                             "readers lock-free; tick() rebuilds and "
                             "swaps the dict whole"),
            SharedField("_ring", LOCK_GUARDED, writers=("charge",)),
            SharedField("_seq", LOCK_GUARDED, writers=("charge",)),
            SharedField("_samples", LOCK_GUARDED, writers=("charge",)),
            SharedField("_stage_survivors", LOCK_GUARDED,
                        writers=("charge",)),
            SharedField("_stage_removed", LOCK_GUARDED,
                        writers=("charge",)),
            SharedField("_steered", LOCK_GUARDED, writers=("charge",)),
            SharedField("_decisive", LOCK_GUARDED, writers=("charge",)),
            SharedField("_escapes", LOCK_GUARDED, writers=("charge",)),
            SharedField("_steered_away", LOCK_GUARDED,
                        writers=("charge",)),
            SharedField("_shadow_mismatch", LOCK_GUARDED,
                        writers=("charge",)),
            SharedField("_picks_seen", MONOTONIC, writers=("sampled",),
                        domain=DATA_PATH,
                        note="per-pick int rebind next to the GIL-atomic "
                             "itertools.count bump; readers tolerate "
                             "one-pick staleness"),
            SharedField("last_tick", MONOTONIC, writers=("tick",),
                        note="maybe_tick reads it lock-free (float "
                             "rebind)"),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
        ),
        note="charge() runs the counterfactual replays and builds the "
             "record BEFORE taking the lock; journal emits run after "
             "release (kvobs discipline — no nested acquisition)"),
    SharedClass(
        f"{PKG}/gateway/fairness.py", "FairnessPolicy", OBS_TICK,
        fields=(
            SharedField("_noisy_pods_cache", SWAP_PUBLISHED,
                        writers=("noisy_pods",), domain=DATA_PATH,
                        note="the checked tuple-swap idiom: (noisy-set "
                             "identity, frozenset) swapped whole; a "
                             "mid-pick swap can never tear "
                             "(tests/test_concurrency.py)"),
            SharedField("_fair_shares", LOCK_GUARDED, writers=("tick",)),
            SharedField("_shares", LOCK_GUARDED, writers=("tick",)),
            SharedField("_costs", LOCK_GUARDED, writers=("tick",)),
            SharedField("_throttled", LOCK_GUARDED, writers=("tick",)),
            SharedField("cfg", SWAP_PUBLISHED,
                        writers=("update_config",), domain=CONTROL,
                        note="whole FairnessConfig dataclass swapped on "
                             "hot reload"),
            SharedField("quota_scale", SWAP_PUBLISHED,
                        writers=("set_quota_scale",), domain=GOSSIP),
            SharedField("escape_total", MONOTONIC,
                        writers=("note_fairness_escape",),
                        domain=DATA_PATH),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
        )),
    SharedClass(
        f"{PKG}/gateway/placement.py", "PlacementPlanner", OBS_TICK,
        fields=(
            SharedField("_resident_pods", SWAP_PUBLISHED,
                        writers=("_rebuild_merged_locked",),
                        note="note_pick/resident_pods read it lock-free"),
            SharedField("_tier_pods", SWAP_PUBLISHED,
                        writers=("_rebuild_merged_locked",),
                        note="identity doubles as the native marshal's "
                             "staleness signal"),
            SharedField("_have_residency", SWAP_PUBLISHED,
                        writers=("_rebuild_merged_locked",)),
            SharedField("_have_local_residency", SWAP_PUBLISHED,
                        writers=("tick",)),
            SharedField("_local_tier_pods", SWAP_PUBLISHED,
                        writers=("tick",),
                        note="statebus publishes it; swapped whole per "
                             "tick"),
            SharedField("_remote_tier_pods", SWAP_PUBLISHED,
                        writers=("set_remote_resident",), domain=GOSSIP),
            SharedField("_decisions", SWAP_PUBLISHED, writers=("tick",)),
            SharedField("_residency", LOCK_GUARDED, writers=("tick",)),
            SharedField("_idle", LOCK_GUARDED, writers=("tick",)),
            SharedField("_model_of", LOCK_GUARDED, writers=("tick",)),
            SharedField("cfg", SWAP_PUBLISHED, writers=("update_config",),
                        domain=CONTROL),
            SharedField("would_steer_total", MONOTONIC,
                        writers=("note_pick",), domain=DATA_PATH),
            SharedField("wrong_tier_total", MONOTONIC,
                        writers=("note_pick",), domain=DATA_PATH),
            SharedField("escape_total", MONOTONIC,
                        writers=("note_placement_escape",),
                        domain=DATA_PATH),
            SharedField("last_tick", MONOTONIC, writers=("tick",)),
            SharedField("ticks", MONOTONIC, writers=("tick",)),
        )),
    SharedClass(
        f"{PKG}/gateway/slo.py", "SLOEngine", OBS_TICK,
        fields=(
            SharedField("last_tick", MONOTONIC, writers=("tick",)),
        )),
    SharedClass(
        f"{PKG}/gateway/statebus.py", "StateBus", GOSSIP,
        fields=(
            SharedField("_seq", MONOTONIC, writers=("snapshot",),
                        domain=OBS_TICK),
            SharedField("_ever_saw_peer", SWAP_PUBLISHED,
                        writers=("merge",),
                        note="latching bool; set-once rebind"),
            SharedField("_stale", SWAP_PUBLISHED, writers=("apply",),
                        domain=OBS_TICK),
            SharedField("last_apply_scale", SWAP_PUBLISHED,
                        writers=("apply",), domain=OBS_TICK),
            SharedField("stale_fallbacks_total", MONOTONIC,
                        writers=("apply",), domain=OBS_TICK),
            SharedField("exchanges", MONOTONIC,
                        note="per-outcome counters mutated in place by "
                             "the exchange event loop only; render() "
                             "copies under the lock"),
        )),
    SharedClass(
        f"{PKG}/gateway/fleetobs.py", "FleetCollector", COLLECTOR,
        fields=(
            SharedField("last_sources", SWAP_PUBLISHED,
                        writers=("_collect_locked",)),
            SharedField("last_stitched", SWAP_PUBLISHED,
                        writers=("_collect_locked",)),
        )),

    # -- scheduling ----------------------------------------------------------
    SharedClass(
        f"{PKG}/gateway/scheduling/native.py", "NativeScheduler",
        DATA_PATH, lock_attrs=("_call_lock",),
        fields=(
            SharedField("_role_cache", SWAP_PUBLISHED,
                        writers=("_routable_pods",),
                        note="(version, pods, eff-version) tuple swapped "
                             "whole; racing writers compute identical "
                             "values for one snapshot version"),
            SharedField("cfg", SWAP_PUBLISHED, writers=("update_config",),
                        domain=CONTROL),
            SharedField("_decode_tree", SWAP_PUBLISHED,
                        writers=("update_config",), domain=CONTROL),
            SharedField("_oracle_tree", SWAP_PUBLISHED,
                        writers=("update_config",), domain=CONTROL,
                        note="the pick ledger's shadow-replay filter tree; "
                             "rebuilt and swapped whole on hot reload like "
                             "_decode_tree"),
            SharedField("_cfg_gen", MONOTONIC,
                        writers=("update_config",), domain=CONTROL),
        ),
        note="the native State handle + persistent buffers live entirely "
             "under _call_lock; the finish seams (prefix hash, RNG, "
             "note_*) run outside it by PR-6 contract (lock-discipline "
             "rule)"),
    SharedClass(
        f"{PKG}/gateway/scheduling/admission.py", "AdmissionController",
        DATA_PATH,
        fields=(
            SharedField("_cfg", SWAP_PUBLISHED, writers=("update_config",),
                        domain=CONTROL),
            SharedField("_queues", SWAP_PUBLISHED,
                        writers=("update_config",), domain=CONTROL),
            SharedField("_park_budget", SWAP_PUBLISHED,
                        writers=("set_park_budget",), domain=CONTROL),
            SharedField("_drain_scheduler", SWAP_PUBLISHED,
                        writers=("_arm",), domain=CONTROL),
            SharedField("_running", SWAP_PUBLISHED,
                        writers=("_arm", "stop"), domain=CONTROL),
            SharedField("_thread", SWAP_PUBLISHED, writers=("_arm",),
                        domain=CONTROL),
        )),
    SharedClass(
        f"{PKG}/gateway/scheduling/prefix_affinity.py", "PrefixIndex",
        DATA_PATH,
        note="holder map mutates in place under the lock; no post-init "
             "rebinds"),

    # -- controllers / transports -------------------------------------------
    SharedClass(
        f"{PKG}/gateway/controllers/filewatch.py", "MembershipAggregator",
        CONTROL),
    SharedClass(
        f"{PKG}/gateway/controllers/k8swatch.py", "KubeSource", CONTROL,
        lock_attrs=("_slices_lock",)),
    SharedClass(
        f"{PKG}/gateway/extproc/service.py", "HealthService", CONTROL,
        lock_attrs=("_watchers_lock",),
        fields=(
            SharedField("_watchers", LOCK_GUARDED, writers=("watch",),
                        note="admission counter inc/dec under the lock"),
        )),

    # -- model server --------------------------------------------------------
    SharedClass(
        f"{PKG}/server/usage.py", "UsageTracker", ENGINE_STEP,
        fields=(
            SharedField("_kv_holdings", LOCK_GUARDED,
                        writers=("sync_kv",)),
            SharedField("_kv_t", LOCK_GUARDED, writers=("sync_kv",)),
            SharedField("idle_slot_seconds", MONOTONIC,
                        writers=("charge_decode",)),
            SharedField("padding_tokens", MONOTONIC,
                        writers=("charge_padding",)),
        )),
    SharedClass(
        f"{PKG}/server/profiler.py", "StepProfiler", ENGINE_STEP,
        fields=(
            SharedField("_seq", MONOTONIC, writers=("note_dispatch",)),
            SharedField("moe", LOCK_GUARDED, writers=("note_moe",),
                        note="a sparse model's routing counts: the engine "
                             "thread adds at the decode readback, the "
                             "scrape reads a copy under the lock"),
            SharedField("sample_steps", LOCK_GUARDED,
                        writers=("note_sample_paths",),
                        note="decode steps by the sampler's path: the "
                             "engine thread adds at the decode readback, "
                             "the scrape reads a copy under the lock"),
            SharedField("stage_ops", LOCK_GUARDED,
                        writers=("note_stage_ops",),
                        note="transfers and helper programs of decode "
                             "staging: the engine thread adds at each "
                             "dispatch, the scrape reads under the lock"),
            SharedField("lora_rows", LOCK_GUARDED,
                        writers=("note_lora_rows",),
                        note="adapter rows of the decode steps: the engine "
                             "thread adds at each dispatch, the scrape "
                             "reads under the lock"),
            SharedField("lora_free_steps", LOCK_GUARDED,
                        writers=("note_lora_free_steps",),
                        note="decode steps run without the adapter delta: "
                             "the engine thread adds at each such dispatch, "
                             "the scrape reads under the lock"),
            SharedField("lora_target_reads", LOCK_GUARDED,
                        writers=("note_lora_target_reads",),
                        note="adapter targets handed to the decode steps "
                             "run with the delta: the engine thread adds at "
                             "each such dispatch, the scrape reads under "
                             "the lock"),
            SharedField("logprob_steps", LOCK_GUARDED,
                        writers=("note_logprob_steps",),
                        note="decode steps staged with a row that asked "
                             "for logprobs: the engine thread adds at each "
                             "such dispatch, the scrape reads under the "
                             "lock"),
            SharedField("blocks_overlapped", LOCK_GUARDED,
                        writers=("note_overlapped_block",),
                        note="decode blocks dispatched over an unread one: "
                             "the engine thread adds at each such dispatch, "
                             "the scrape reads under the lock"),
            SharedField("latent_positions", LOCK_GUARDED,
                        writers=("note_latent_positions",),
                        note="latent cache rows the decode steps read: the "
                             "engine thread adds at each dispatch, the "
                             "scrape reads under the lock"),
            SharedField("ssm_rows", LOCK_GUARDED,
                        writers=("note_ssm_rows",),
                        note="recurrent states the decode steps rewrote: "
                             "the engine thread adds at each dispatch, the "
                             "scrape reads under the lock"),
            SharedField("conv_rows", LOCK_GUARDED,
                        writers=("note_conv_rows",),
                        note="conv states the decode steps rewrote: the "
                             "engine thread adds at each dispatch, the "
                             "scrape reads under the lock"),
            SharedField("kda_rows", LOCK_GUARDED,
                        writers=("note_kda_rows",),
                        note="delta-rule states the decode steps rewrote: "
                             "the engine thread adds at each dispatch, the "
                             "scrape reads under the lock"),
            SharedField("kv_positions", LOCK_GUARDED,
                        writers=("note_kv_positions",),
                        note="cache positions the decode steps read by kind "
                             "of lane: the engine thread adds at each "
                             "dispatch, the scrape reads under the lock"),
            SharedField("attn_grid_steps", LOCK_GUARDED,
                        writers=("note_attn_grid_steps",),
                        note="grid steps the decode kernel's schedule held: "
                             "the engine thread adds at each dispatch, the "
                             "scrape reads under the lock"),
            SharedField("chunk_attn_grid_steps", LOCK_GUARDED,
                        writers=("note_prompt_program",),
                        note="grid steps the chunk programs' attends walk: "
                             "the engine thread adds where one is enqueued, "
                             "the scrape reads under the lock"),
            SharedField("prompt", LOCK_GUARDED,
                        writers=("note_prompt_program",
                                 "note_prompt_done"),
                        note="the prompt programs' counts, positions and "
                             "seconds by program: the engine thread adds "
                             "where one is enqueued and where it is seen "
                             "complete, the scrape reads under the lock"),
            SharedField("_last_end", OWNER_PRIVATE,
                        writers=("_chain",)),
            SharedField("_idle_pending", OWNER_PRIVATE,
                        writers=("_chain", "note_idle")),
            SharedField("_prev_active", OWNER_PRIVATE,
                        writers=("note_dispatch",)),
            SharedField("_split_mark", OWNER_PRIVATE,
                        writers=("note_dispatch",)),
            SharedField("_prefill_mark", OWNER_PRIVATE,
                        writers=("take_prefill_split",)),
            SharedField("_open", SWAP_PUBLISHED,
                        writers=("_push", "_switch", "_pop"),
                        note="(innermost open phase, last charge time), "
                             "one tuple swapped per phase transition; "
                             "phase_seconds() copies the per-phase dict "
                             "between two reads of it, lock-free"),
        ),
        note="the phase stack (_stack, _anns, _phase_s) is mutated in "
             "place by the engine thread alone; _phase_s never gains a "
             "key after construction"),
    SharedClass(
        f"{PKG}/server/kv_ledger.py", "KvLedger", ENGINE_STEP,
        fields=(
            SharedField("_states", LOCK_GUARDED, writers=("sync_states",),
                        note="recounted whole from allocator ground truth "
                             "per sync; snapshot() copies under the lock"),
            SharedField("_parked_tokens", LOCK_GUARDED,
                        writers=("sync_states",)),
            SharedField("_free_view", SWAP_PUBLISHED,
                        writers=("sync_states",),
                        note="immutable tuple of the free list, swapped "
                             "whole; the scrape-rate fragmentation "
                             "histogram reads it without re-walking the "
                             "allocator"),
            SharedField("_syncs", MONOTONIC, writers=("sync_states",)),
            SharedField("prefix_table_evictions", MONOTONIC,
                        writers=("_touch",)),
        ),
        note="event counters / prefix LRU / ring mutate in place under "
             "the lock (the GatewayMetrics shape); every note_* is "
             "engine-thread, snapshot() is the scrape thread"),
    SharedClass(
        f"{PKG}/server/lora_manager.py", "LoRAManager", ENGINE_STEP,
        lock_attrs=("_lock", "_mutate_lock"),
        fields=(
            SharedField("buffers", SWAP_PUBLISHED,
                        writers=("load", "demote", "unload"),
                        note="device buffer pytree swapped whole per "
                             "residency verb"),
            SharedField("_targets", LOCK_GUARDED, writers=("_retarget",),
                        note="union of the slot tier's LoRA targets (and "
                             "a load's under way): a frozenset swapped "
                             "whole under _lock by the residency verbs"),
            SharedField("_targets_hook", SWAP_PUBLISHED,
                        writers=("watch_targets",), domain=CONTROL,
                        note="the engine's listener, set once at its "
                             "construction; called under _mutate_lock, "
                             "never under _lock"),
        )),
    SharedClass(
        f"{PKG}/server/engine.py", "Engine", ENGINE_STEP,
        lock_attrs=("_lock", "_trace_lock"),
        fields=(
            SharedField("_lora_targets", SWAP_PUBLISHED,
                        writers=("_retarget",), domain=CONTROL,
                        note="the LoRA targets a decode block with an "
                             "adapter row is handed: a tuple swapped whole "
                             "under _trace_lock by the thread of a load "
                             "that widens it or a helper thread that "
                             "narrows it; the engine thread reads it "
                             "lock-free at each dispatch"),
            SharedField("_running", SWAP_PUBLISHED,
                        writers=("start", "stop"), domain=CONTROL),
            SharedField("_thread", SWAP_PUBLISHED, writers=("start",),
                        domain=CONTROL),
            SharedField("_draining", SWAP_PUBLISHED, writers=("drain",),
                        domain=CONTROL),
            SharedField("_admitting", LOCK_GUARDED,
                        writers=("_admit_and_insert",
                                 "_drain_decode_wait")),
            SharedField("_pending", LOCK_GUARDED,
                        writers=("_admit_and_insert", "_collect_followers",
                                 "_start_stream", "stop")),
            SharedField("_streams", LOCK_GUARDED,
                        note="chunk-stream lane list (engine-thread "
                             "append/remove in place; the scrape thread "
                             "iterates a list() copy like decode_wait)"),
            SharedField("_stream_rr", OWNER_PRIVATE,
                        writers=("_stream_step",),
                        note="fair-interleave round-robin cursor"),
            SharedField("_stops_active", OWNER_PRIVATE,
                        writers=("_clear_slot", "_program_stop_lanes"),
                        note="rows with programmed device stop lanes; "
                             "excludes speculative dispatch"),
            SharedField("decode_wait", LOCK_GUARDED,
                        writers=("_sweep_decode_wait",)),
            SharedField("_parked_kv_tokens", LOCK_GUARDED,
                        writers=("_do_attach", "_drain_decode_wait",
                                 "_park_waiting", "_read_first_tokens",
                                 "_sweep_decode_wait", "stop")),
            SharedField("cache", SWAP_PUBLISHED,
                        writers=("_insert_prompt_kv", "_sync_tables"),
                        note="KV pytree swapped whole by the engine "
                             "thread"),
            SharedField("draft_cache", OWNER_PRIVATE,
                        writers=("_draft_admit",)),
            SharedField("_tables_dirty", OWNER_PRIVATE,
                        writers=("_paged_ensure", "_paged_free_row",
                                 "_prefix_match_and_map", "_sync_tables")),
            SharedField("_kv_evicts_pending", OWNER_PRIVATE,
                        writers=("_paged_alloc_block", "_kv_ledger_sync"),
                        note="eviction tally drained into ONE aggregated "
                             "kv_evict journal event per ledger sync"),
            SharedField("_moe_pending", OWNER_PRIVATE,
                        writers=("_moe_keep", "_moe_drain"),
                        note="device arrays of prefill programs' routing "
                             "counts, drained into the next decode "
                             "readback"),
            SharedField("_dev_counts", OWNER_PRIVATE,
                        writers=("_count_first_token", "_counts",
                                 "_enqueue_decode", "_register_slot")),
            SharedField("_counts_dummy", OWNER_PRIVATE,
                        writers=("_enqueue_decode",),
                        note="the penalty-free [B, 1] counts argument: "
                             "donated to each decode block and handed "
                             "back by it"),
            SharedField("_dev_tokens", OWNER_PRIVATE,
                        writers=("_activate_slot",
                                 "_dispatch_block", "_dispatch_spec_block")),
            SharedField("_dev_positions", OWNER_PRIVATE,
                        writers=("_dispatch_block", "_dispatch_spec_block")),
            SharedField("_dev_remaining", OWNER_PRIVATE,
                        writers=("_dispatch_block", "_dispatch_spec_block")),
            SharedField("_dev_stop_hist", OWNER_PRIVATE,
                        writers=("_dispatch_block", "_dispatch_spec_block"),
                        note="stop-automaton history carry (device-"
                             "resident twin of _slot_stop_hist)"),
            SharedField("_dev_has_extra", OWNER_PRIVATE,
                        writers=("_activate_slot",
                                 "_dispatch_spec_block")),
            SharedField("_dev_extra_pos", OWNER_PRIVATE,
                        writers=("_dispatch_spec_block",)),
            SharedField("_dev_extra_tok", OWNER_PRIVATE,
                        writers=("_dispatch_spec_block",)),
            SharedField("_first_unread", OWNER_PRIVATE,
                        writers=("_queue_first_token",
                                 "_read_first_tokens"),
                        note="first tokens still on the device, in the "
                             "order their prefills were enqueued"),
            SharedField("_inflight", OWNER_PRIVATE,
                        writers=("_loop",),
                        note="the decode block dispatched and not read"),
            SharedField("_last_done_pc", OWNER_PRIVATE,
                        writers=("_process_block", "_read_first_tokens",
                                 "_prompt_programs_done", "_see_inflight"),
                        note="when the loop last saw the device complete "
                             "a block or a prompt program: the completion "
                             "chain's anchor"),
            SharedField("_prompt_pending", OWNER_PRIVATE,
                        writers=("_note_prompt_program",
                                 "_prompt_programs_done"),
                        note="prompt programs enqueued and not seen "
                             "complete yet, in the device queue's order"),
            SharedField("_prompt_enqueued", OWNER_PRIVATE,
                        writers=("_note_prompt_program",)),
            SharedField("_prev_dispatch_steps", OWNER_PRIVATE,
                        writers=("_loop", "_paged_ensure_decode")),
            SharedField("decode_tps_ema", SWAP_PUBLISHED,
                        writers=("_account_dispatch",),
                        note="float rebind; the scrape thread reads it "
                             "lock-free"),
            SharedField("prefix_reused_tokens", MONOTONIC,
                        writers=("_prefix_bucket_prefill",
                                 "_prefix_match_and_map")),
            SharedField("spec_cycles", MONOTONIC,
                        writers=("_dispatch_spec_block",)),
            SharedField("spec_emitted", MONOTONIC,
                        writers=("_process_block",)),
            SharedField("total_generated", MONOTONIC,
                        writers=("_account_dispatch",
                                 "_emit_first_token")),
            SharedField("total_requests", MONOTONIC,
                        writers=("attach_prefilled", "submit"),
                        domain=DATA_PATH),
        ),
        note="device-array fields are engine-thread-owned and rebound "
             "whole; queue/park accounting shares the lock with the HTTP "
             "submit path"),
)

# Attribute-name -> registered class, for the lock-order rule's
# interprocedural call resolution (``self.usage.note_pick()`` resolves to
# ``UsageRollup.note_pick`` through this map).  One name, one class,
# repo-wide — keep attribute naming unambiguous or the analyzer (and the
# reader) loses the thread.
BINDINGS = {
    "journal": "EventJournal",
    "metrics": "GatewayMetrics",
    "provider": "Provider",
    "datastore": "Datastore",
    "health": "HealthScorer",
    "breaker": "CircuitBreaker",
    "retry_budget": "RetryBudget",
    "resilience": "ResiliencePlane",
    "health_advisor": "ResiliencePlane",
    "usage": "UsageRollup",
    "kvobs": "KvObsRollup",
    "capacity": "CapacityPlanner",
    "pickledger": "PickLedger",
    "pick_ledger": "PickLedger",
    "fairness": "FairnessPolicy",
    "usage_advisor": "FairnessPolicy",
    "placement": "PlacementPlanner",
    "placement_advisor": "PlacementPlanner",
    "slo": "SLOEngine",
    "statebus": "StateBus",
    "bus": "StateBus",
    "fleet": "FleetCollector",
    "prefix_index": "PrefixIndex",
    "admission": "AdmissionController",
    "tracker": "UsageTracker",
    "kv_ledger": "KvLedger",
    "profiler": "StepProfiler",
    "lora": "LoRAManager",
    "engine": "Engine",
}


def all_classes() -> tuple[SharedClass, ...]:
    return CLASSES


def by_name() -> dict[str, SharedClass]:
    return {c.name: c for c in CLASSES}


def render_markdown() -> str:
    """Domain/discipline catalogue for ARCHITECTURE.md §3m (generated on
    demand by docs tooling; the source of truth stays here)."""
    out = ["| class | module | domain | lock(s) | shared fields "
           "(discipline) |", "|---|---|---|---|---|"]
    for c in CLASSES:
        fields = ", ".join(
            f"`{f.name}` ({f.discipline}"
            + (f", {f.domain}" if f.domain and f.domain != c.domain else "")
            + ")"
            for f in c.fields) or "—"
        locks = ", ".join(f"`{a}`" for a in c.lock_attrs) or "—"
        out.append(f"| `{c.name}` | `{c.module.split('/', 1)[1]}` "
                   f"| {c.domain} | {locks} | {fields} |")
    return "\n".join(out)
