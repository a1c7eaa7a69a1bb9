"""Model-server metrics adapter: scrape + map TPU serving metrics.

Parity: reference ``pkg/ext-proc/backend/vllm/metrics.go`` — scrape
``http://<pod>/metrics``, parse Prometheus text, map the server's counters
into ``gateway.types.Metrics``, and derive the active-LoRA set from a labeled
info gauge, selecting the *latest* series when multiple are exposed
(metrics.go:135-150).

Where vLLM exports CUDA-side counters (``vllm:gpu_cache_usage_perc``,
``vllm:num_requests_waiting``), our TPU server (``server/metrics.py``) exports
the contract below.  The names are the seam between the gateway and any
TPU model server (JetStream-style) that wants to join a pool:

=====================================  =======================================
``tpu:prefill_queue_size``             requests awaiting prefill (gauge)
``tpu:decode_queue_size``              requests awaiting a decode slot (gauge)
``tpu:num_requests_running``           in-flight requests (gauge)
``tpu:num_requests_waiting``           total queued (prefill+decode) (gauge)
``tpu:kv_cache_usage_perc``            paged-KV utilization 0..1 (gauge)
``tpu:kv_tokens_capacity``             total KV token capacity (gauge)
``tpu:kv_tokens_free``                 free KV token headroom (gauge)
``tpu:decode_tokens_per_sec``          recent decode throughput (gauge)
``tpu:prefix_reused_tokens``           cumulative prompt tokens served from
                                       the prefix cache (counter, optional)
``tpu:prefill_seconds``                prefill compute latency (histogram,
                                       optional; mean = _sum/_count feeds
                                       Metrics.prefill_seconds_mean)
``tpu:handoff_seconds``                handoff serialize / deserialize+attach
                                       latency (histogram, optional)
``tpu:decode_step_seconds``            per-step decode cadence (histogram,
                                       optional; mean feeds
                                       Metrics.decode_step_seconds_mean)
``tpu:lora_requests_info``             labels ``running_lora_adapters`` (CSV),
                                       ``max_lora``; gauge value = unix ts of
                                       the snapshot (latest series wins)
=====================================  =======================================
"""

from __future__ import annotations

import concurrent.futures as futures
import threading
import urllib.error
import urllib.request

from llm_instance_gateway_tpu.gateway.types import Metrics, Pod, PodMetrics
from llm_instance_gateway_tpu.utils import prom_parse

# Metric-name contract (metrics.go:19-32 equivalent).
LORA_INFO_METRIC = "tpu:lora_requests_info"
LORA_ADAPTERS_LABEL = "running_lora_adapters"
LORA_WAITING_LABEL = "waiting_lora_adapters"
LORA_MAX_LABEL = "max_lora"
LORA_RANKS_LABEL = "adapter_ranks"  # optional name:rank CSV (rank-aware fairness)
LORA_TIERS_LABEL = "resident_tiers"  # optional name:tier CSV (residency summary)
# Residency ladder (server/lora_manager.py): one info line per tier with an
# ``adapters`` CSV; value is a unix timestamp (latest series wins per tier).
RESIDENCY_INFO_METRIC = "tpu:adapter_residency_info"
RESIDENCY_TIER_LABEL = "tier"
RESIDENCY_ADAPTERS_LABEL = "adapters"
PREFILL_QUEUE_METRIC = "tpu:prefill_queue_size"
DECODE_QUEUE_METRIC = "tpu:decode_queue_size"
RUNNING_METRIC = "tpu:num_requests_running"
WAITING_METRIC = "tpu:num_requests_waiting"
KV_USAGE_METRIC = "tpu:kv_cache_usage_perc"
KV_CAPACITY_METRIC = "tpu:kv_tokens_capacity"
KV_FREE_METRIC = "tpu:kv_tokens_free"
KV_PARKED_METRIC = "tpu:kv_parked_tokens"
DECODE_TPS_METRIC = "tpu:decode_tokens_per_sec"
PREFIX_REUSED_METRIC = "tpu:prefix_reused_tokens"
PREFILL_SECONDS_METRIC = "tpu:prefill_seconds"
DECODE_STEP_SECONDS_METRIC = "tpu:decode_step_seconds"
DECODE_BATCH_OCCUPANCY_METRIC = "tpu:decode_batch_occupancy"
# Capacity-attribution families (server/usage.py; all optional).
ADAPTER_STEP_SECONDS_METRIC = "tpu:adapter_step_seconds_total"
ADAPTER_TOKENS_METRIC = "tpu:adapter_tokens_total"
ADAPTER_KV_SECONDS_METRIC = "tpu:adapter_kv_block_seconds_total"
IDLE_SLOT_SECONDS_METRIC = "tpu:idle_slot_seconds_total"
PREFILL_PADDING_METRIC = "tpu:prefill_padding_tokens_total"
# KV economy ledger families (server/kv_ledger.py; all optional).
KV_BLOCKS_METRIC = "tpu:kv_blocks"
KV_BLOCKS_TOTAL_METRIC = "tpu:kv_blocks_total"
KV_BLOCK_TOKENS_METRIC = "tpu:kv_block_tokens"
KV_BLOCK_EVENTS_METRIC = "tpu:kv_block_events_total"
KV_PREFIX_HITS_METRIC = "tpu:kv_prefix_hits_total"
KV_PREFIX_TOKENS_SAVED_METRIC = "tpu:kv_prefix_tokens_saved_total"
KV_PREFIX_RESIDENT_METRIC = "tpu:kv_prefix_resident_blocks"


class FetchError(Exception):
    pass


def families_to_metrics(
    families: dict[str, list[prom_parse.Sample]], existing: Metrics
) -> tuple[Metrics, list[str]]:
    """Map parsed families onto a cloned Metrics (promToPodMetrics, :73-129).

    Missing families leave the existing (stale) values in place and are
    reported in the returned error list — the reference aggregates per-metric
    errors with multierr and keeps going (metrics.go:78-128).
    """
    updated = existing.clone()
    errs: list[str] = []

    def latest_value(name: str) -> float | None:
        s = prom_parse.latest_sample(families.get(name, []))
        if s is None:
            errs.append(f"metric family {name!r} not found")
            return None
        return s.value

    v = latest_value(RUNNING_METRIC)
    if v is not None:
        updated.running_queue_size = int(v)
    v = latest_value(WAITING_METRIC)
    if v is not None:
        updated.waiting_queue_size = int(v)
    v = latest_value(KV_USAGE_METRIC)
    if v is not None:
        updated.kv_cache_usage_percent = float(v)

    # TPU-specific signals are optional for foreign servers: absence is not an
    # error if the total-queue contract is satisfied.
    for name, setter in (
        (PREFILL_QUEUE_METRIC, lambda m, x: setattr(m, "prefill_queue_size", int(x))),
        (DECODE_QUEUE_METRIC, lambda m, x: setattr(m, "decode_queue_size", int(x))),
        (KV_CAPACITY_METRIC, lambda m, x: setattr(m, "kv_tokens_capacity", int(x))),
        (KV_FREE_METRIC, lambda m, x: setattr(m, "kv_tokens_free", int(x))),
        (KV_PARKED_METRIC, lambda m, x: setattr(m, "kv_parked_tokens", int(x))),
        (DECODE_TPS_METRIC, lambda m, x: setattr(m, "decode_tokens_per_sec", float(x))),
        (PREFIX_REUSED_METRIC, lambda m, x: setattr(m, "prefix_reused_tokens", int(x))),
    ):
        s = prom_parse.latest_sample(families.get(name, []))
        if s is not None:
            setter(updated, s.value)

    # Phase-latency histograms (optional): the parser sees a histogram as
    # its component families, so mean = <fam>_sum / <fam>_count.  The labels
    # (model/role) are single-valued per replica — latest sample suffices.
    for fam, attr in (
        (PREFILL_SECONDS_METRIC, "prefill_seconds_mean"),
        (DECODE_STEP_SECONDS_METRIC, "decode_step_seconds_mean"),
    ):
        s_sum = prom_parse.latest_sample(families.get(fam + "_sum", []))
        s_count = prom_parse.latest_sample(families.get(fam + "_count", []))
        if s_sum is not None and s_count is not None and s_count.value > 0:
            setattr(updated, attr, s_sum.value / s_count.value)

    # CUMULATIVE histogram sums/counts (optional), summed ACROSS label
    # series: the capacity plane (gateway/capacity.py) differences these
    # between scrape ticks into per-window means — the observation windows
    # the twin's self-calibration fits.  Means alone can't give windows
    # (they average over all time); the raw accumulators can.
    for fam, sum_attr, count_attr in (
        (PREFILL_SECONDS_METRIC,
         "prefill_seconds_sum", "prefill_seconds_count"),
        (DECODE_STEP_SECONDS_METRIC,
         "decode_step_seconds_sum", "decode_step_seconds_count"),
        (DECODE_BATCH_OCCUPANCY_METRIC,
         "decode_batch_occupancy_sum", "decode_batch_occupancy_count"),
    ):
        sums = families.get(fam + "_sum", [])
        counts = families.get(fam + "_count", [])
        if sums and counts:
            setattr(updated, sum_attr, sum(s.value for s in sums))
            setattr(updated, count_attr, sum(s.value for s in counts))

    # Capacity attribution (optional): every labeled sample folds in, keyed
    # by its (model, adapter[, phase]) labels — replicas expose one model,
    # so "latest sample" selection does not apply; rebuild the dicts whole
    # each scrape (cumulative counters, never merged with stale keys).
    for fam, attr, with_phase in (
        (ADAPTER_STEP_SECONDS_METRIC, "adapter_step_seconds", True),
        (ADAPTER_TOKENS_METRIC, "adapter_tokens", True),
        (ADAPTER_KV_SECONDS_METRIC, "adapter_kv_block_seconds", False),
    ):
        samples = families.get(fam, [])
        if samples:
            table = {}
            for s in samples:
                adapter = s.labels.get("adapter", "")
                if not adapter:
                    continue
                model = s.labels.get("model", "")
                key = ((model, adapter, s.labels.get("phase", ""))
                       if with_phase else (model, adapter))
                table[key] = s.value
            setattr(updated, attr, table)
    for fam, setter in (
        (IDLE_SLOT_SECONDS_METRIC,
         lambda m, x: setattr(m, "idle_slot_seconds", float(x))),
        (PREFILL_PADDING_METRIC,
         lambda m, x: setattr(m, "prefill_padding_tokens", int(x))),
    ):
        s = prom_parse.latest_sample(families.get(fam, []))
        if s is not None:
            setter(updated, s.value)

    # KV economy ledger (optional): state-labeled block gauges and the
    # prefix-keyed reuse tables, rebuilt whole each scrape (a prefix
    # evicted from the replica's bounded table must drop here too — the
    # duplication index would otherwise count ghosts).
    kv_blocks = {}
    for s in families.get(KV_BLOCKS_METRIC, []):
        state = s.labels.get("state", "")
        if state:
            kv_blocks[state] = int(s.value)
    if kv_blocks:
        updated.kv_blocks = kv_blocks
    for name, setter in (
        (KV_BLOCKS_TOTAL_METRIC,
         lambda m, x: setattr(m, "kv_blocks_total", int(x))),
        (KV_BLOCK_TOKENS_METRIC,
         lambda m, x: setattr(m, "kv_block_tokens", int(x))),
    ):
        s = prom_parse.latest_sample(families.get(name, []))
        if s is not None:
            setter(updated, s.value)
    events = {}
    for s in families.get(KV_BLOCK_EVENTS_METRIC, []):
        kind = s.labels.get("kind", "")
        if kind:
            events[kind] = s.value
    if events:
        updated.kv_block_events = events
    for fam, attr in (
        (KV_PREFIX_HITS_METRIC, "kv_prefix_hits"),
        (KV_PREFIX_TOKENS_SAVED_METRIC, "kv_prefix_tokens_saved"),
        (KV_PREFIX_RESIDENT_METRIC, "kv_prefix_resident_blocks"),
    ):
        samples = families.get(fam, [])
        if samples:
            table = {}
            for s in samples:
                prefix = s.labels.get("prefix", "")
                if prefix:
                    table[prefix] = s.value
            setattr(updated, attr, table)

    # LoRA info: latest series by gauge-value timestamp (metrics.go:135-150 —
    # the reference compares the *gauge value*, which vLLM sets to a unix ts).
    # Running AND waiting adapters union into the affinity set (the
    # reference unions both CSVs into ActiveModels).
    lora_samples = families.get(LORA_INFO_METRIC, [])
    if lora_samples:
        best = max(lora_samples, key=lambda s: s.value)
        adapters: dict[str, int] = {}
        csv = best.labels.get(LORA_ADAPTERS_LABEL, "")
        waiting_csv = best.labels.get(LORA_WAITING_LABEL, "")
        for name in (csv + "," + waiting_csv).split(","):
            name = name.strip()
            if name:
                adapters[name] = 0
        updated.active_adapters = adapters
        # Running/waiting split kept ALONGSIDE the union: the placement
        # planner reads waiting as its prefetch-urgency signal.
        updated.running_adapters = frozenset(
            n.strip() for n in csv.split(",") if n.strip())
        updated.waiting_adapters = frozenset(
            n.strip() for n in waiting_csv.split(",") if n.strip())
        # Optional name:rank CSV (our server exports it; foreign vLLM-style
        # servers simply lack the label and ranks stay unknown).
        ranks: dict[str, int] = {}
        for entry in best.labels.get(LORA_RANKS_LABEL, "").split(","):
            name, sep, raw_rank = entry.strip().rpartition(":")
            if not sep or not name:
                continue
            try:
                ranks[name] = int(float(raw_rank))
            except (ValueError, OverflowError):  # "inf" overflows int()
                errs.append(
                    f"invalid {LORA_RANKS_LABEL} entry: {entry!r}")
        updated.adapter_ranks = ranks
        # Optional name:tier residency summary CSV — the fallback source
        # for adapter_tiers when the dedicated residency family is absent
        # (the family below overrides when present).
        tiers: dict[str, str] = {}
        for entry in best.labels.get(LORA_TIERS_LABEL, "").split(","):
            name, sep, tier = entry.strip().rpartition(":")
            if sep and name and tier:
                tiers[name] = tier
        updated.adapter_tiers = tiers
        raw_max = best.labels.get(LORA_MAX_LABEL)
        if raw_max is None:
            # Without max_lora the slot-room predicates are permanently false
            # for this pod — surface the misconfiguration instead of silently
            # degrading LoRA placement.
            errs.append(f"{LORA_INFO_METRIC} missing {LORA_MAX_LABEL} label")
        else:
            try:
                updated.max_active_adapters = int(float(raw_max))
            except ValueError:
                errs.append(f"invalid {LORA_MAX_LABEL} label: {best.labels}")

    # Residency ladder (optional): per-tier info lines; latest sample per
    # tier wins (value = unix ts, like the LoRA info gauge).  Rebuilt whole
    # each scrape so demoted/evicted adapters drop their tier immediately.
    res_samples = families.get(RESIDENCY_INFO_METRIC, [])
    if res_samples:
        by_tier: dict[str, prom_parse.Sample] = {}
        for s in res_samples:
            tier = s.labels.get(RESIDENCY_TIER_LABEL, "")
            if tier and (tier not in by_tier or s.value > by_tier[tier].value):
                by_tier[tier] = s
        tiers = {}
        for tier, s in by_tier.items():
            for name in s.labels.get(RESIDENCY_ADAPTERS_LABEL, "").split(","):
                name = name.strip()
                if name:
                    tiers[name] = tier
        updated.adapter_tiers = tiers
    return updated, errs


class PodMetricsClient:
    """HTTP scraper (FetchMetrics, metrics.go:38-68)."""

    def __init__(self, timeout_s: float = 5.0,
                 scheme: str = "http") -> None:
        self.timeout_s = timeout_s
        self.scheme = scheme
        # Build/load the native scanner NOW (seconds of g++ on first build):
        # lazily it would fire on the first production-sized scrape and
        # stall the 50ms loop with the loader lock held, going stale on
        # every pod exactly at startup.
        prom_parse._load_native()

    def fetch_metrics(self, pod: Pod, existing: Metrics) -> Metrics:
        url = f"{self.scheme}://{pod.address}/metrics"
        try:
            with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
                if resp.status != 200:
                    raise FetchError(
                        f"unexpected status code from {pod}: {resp.status}"
                    )
                body = resp.read().decode("utf-8", errors="replace")
        except (urllib.error.URLError, OSError, TimeoutError) as e:
            raise FetchError(f"failed to fetch metrics from {pod}: {e}") from e
        # C scanner on the 50ms hot loop (pure-Python fallback inside).
        families = prom_parse.parse_text_fast(body)
        updated, _errs = families_to_metrics(families, existing)
        return updated


class FakePodMetricsClient:
    """Test fake (backend/fake.go:10-21): per-pod canned results or errors."""

    def __init__(
        self,
        res: dict[str, Metrics] | None = None,
        err: dict[str, Exception] | None = None,
    ) -> None:
        self.res = res or {}
        self.err = err or {}

    def fetch_metrics(self, pod: Pod, existing: Metrics) -> Metrics:
        if pod.name in self.err:
            raise self.err[pod.name]
        if pod.name in self.res:
            return self.res[pod.name].clone()
        return existing.clone()


def fetch_all(
    client,
    pods: list[PodMetrics],
    timeout_s: float = 5.0,
    executor: futures.ThreadPoolExecutor | None = None,
) -> tuple[dict[str, Metrics], list[str]]:
    """Parallel per-pod fetch fan-out (provider.go:145-162).

    Pass a persistent ``executor`` (Provider owns and passes its own) —
    creating and context-managing a pool per call would both churn threads at
    the 50 ms refresh cadence and, worse, block past ``timeout_s`` in
    ``shutdown(wait=True)`` while a slow endpoint drips bytes.  With a shared
    pool, stragglers keep a worker busy past the deadline but never block the
    refresh loop; the bounded pool size caps the damage from a wedged pod.
    The module-level fallback pool exists only for executor-less callers
    (tests, one-shot scripts).
    """
    results: dict[str, Metrics] = {}
    errs: list[str] = []
    if not pods:
        return results, errs
    ex = executor or _default_executor()
    futs = {ex.submit(client.fetch_metrics, pm.pod, pm.metrics): pm.pod for pm in pods}
    done, not_done = futures.wait(futs, timeout=timeout_s)
    for fut in done:
        pod = futs[fut]
        try:
            results[pod.name] = fut.result()
        except Exception as e:  # non-fatal: stale metrics persist
            errs.append(str(e))
    for fut in not_done:
        fut.cancel()  # cancels queued fetches; running ones finish in background
        errs.append(f"timeout fetching metrics from {futs[fut]}")
    return results, errs


_SHARED_EXECUTOR: futures.ThreadPoolExecutor | None = None
_SHARED_EXECUTOR_LOCK = threading.Lock()


def _default_executor() -> futures.ThreadPoolExecutor:
    global _SHARED_EXECUTOR
    with _SHARED_EXECUTOR_LOCK:
        if _SHARED_EXECUTOR is None:
            _SHARED_EXECUTOR = futures.ThreadPoolExecutor(
                max_workers=32, thread_name_prefix="metrics-fetch"
            )
        return _SHARED_EXECUTOR
