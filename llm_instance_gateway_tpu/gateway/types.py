"""Core gateway data types: pods (TPU slice replicas) and their live metrics.

Parity: reference ``pkg/ext-proc/backend/types.go:8-53`` defines
``Pod{Name,Address}`` and ``Metrics{ActiveModels, RunningQueueSize,
WaitingQueueSize, KVCacheUsagePercent, ...}``.  The TPU-native schema differs
deliberately:

- The unit of placement is a **slice-backed replica** (a JetStream-style server
  owning one TPU slice), not a single-GPU pod (SURVEY.md §2.5).
- Queue depth is split into **prefill** and **decode** queues because TPU
  continuous batching disaggregates the two phases; the scheduler must route on
  the right one (SURVEY.md §7 "hard parts").
- KV headroom is token-denominated (``kv_tokens_free`` /
  ``kv_tokens_capacity``) in addition to the percent signal, enabling
  token-aware long-context routing (reference stubs this at
  ``backend/types.go:25`` but never uses it).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

# Pool roles under cross-engine prefill/decode disaggregation
# (server/kv_transfer.py).  A pool mixing "prefill" and "decode" replicas
# gets two-stage routing (scheduler.schedule_disaggregated); "collocated"
# replicas serve whole requests single-hop (the reference topology).
ROLE_COLLOCATED = "collocated"
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
POOL_ROLES = (ROLE_COLLOCATED, ROLE_PREFILL, ROLE_DECODE)


def pod_role(pod) -> str:
    """A pod's disaggregation role, defaulting legacy objects to collocated."""
    return getattr(pod, "role", ROLE_COLLOCATED) or ROLE_COLLOCATED


@dataclass(frozen=True)
class Pod:
    """A routable model-server replica (one TPU-slice-backed server process).

    ``address`` is ``host:port`` of the replica's serving endpoint.  For a
    multi-host slice this is the slice leader (SURVEY.md §7: "the pod is
    actually the slice's leader host").  ``role`` marks prefill/decode
    specialization for disaggregated pools (collocated = serves both
    phases, the default and the reference behavior).
    """

    name: str
    address: str
    role: str = ROLE_COLLOCATED

    def __str__(self) -> str:  # parity: types.go Pod.String()
        return f"{self.name}({self.address})"


@dataclass
class Metrics:
    """Live scheduling signals scraped from one replica.

    Parity with ``backend/types.go:17-31`` plus the TPU prefill/decode split.
    ``active_adapters`` maps adapter id -> number of in-flight requests using
    it (reference: ``ActiveModels map[string]int``).
    """

    active_adapters: dict[str, int] = field(default_factory=dict)
    max_active_adapters: int = 0
    # Resident adapter -> LoRA rank (tpu:lora_requests_info adapter_ranks
    # label): the heterogeneity signal rank-aware fair-share weighting
    # consumes (gateway/fairness.py).  Empty for foreign servers.
    adapter_ranks: dict[str, int] = field(default_factory=dict)
    # Residency ladder (tpu:adapter_residency_info): adapter -> tier
    # ("slot" | "host").  Adapters absent are disk-tier (cold).  The
    # placement planner and the prefer_resident routing seam consume this;
    # empty for servers without the residency families.
    adapter_tiers: dict[str, str] = field(default_factory=dict)
    # The running/waiting split behind active_adapters (which stays the
    # UNION for the affinity filter): waiting adapters are the planner's
    # urgency signal — requests parked on an adapter not yet decodable.
    running_adapters: frozenset = frozenset()
    waiting_adapters: frozenset = frozenset()
    # Queue depths.  ``waiting_queue_size`` mirrors the reference's vLLM
    # num_requests_waiting; on TPU it is prefill_queue + decode_waiting.
    running_queue_size: int = 0
    waiting_queue_size: int = 0
    prefill_queue_size: int = 0
    decode_queue_size: int = 0
    # KV / HBM headroom.  ``kv_tokens_free`` already accounts for parked
    # (prefilled-but-unslotted) KV on the server side; ``kv_parked_tokens``
    # is exported separately for observability.
    kv_cache_usage_percent: float = 0.0
    kv_tokens_capacity: int = 0
    kv_tokens_free: int = 0
    kv_parked_tokens: int = 0
    # Serving rates (optional, for latency-aware policies and the simulator).
    decode_tokens_per_sec: float = 0.0
    # Cumulative prompt tokens served from the replica's prefix cache
    # (``tpu:prefix_reused_tokens``): the observable a future KV-affinity
    # routing policy needs — a replica already holding a shared prefix is
    # cheaper to prefill on (SURVEY §5 observability note).
    prefix_reused_tokens: int = 0
    # Phase-latency means derived from the replica's tpu:prefill_seconds /
    # tpu:decode_step_seconds histograms (_sum / _count): the per-replica
    # observables an SLO-aware routing policy ranks on.  0.0 = no samples
    # yet (or a foreign server without the families).
    prefill_seconds_mean: float = 0.0
    decode_step_seconds_mean: float = 0.0
    # CUMULATIVE phase-histogram sums/counts behind the means above, plus
    # the decode-batch occupancy histogram: the capacity plane
    # (gateway/capacity.py) differences these between scrape ticks to get
    # per-WINDOW means — the observation windows
    # sim/calibrate.calibrate_from_observables fits the twin from.
    prefill_seconds_sum: float = 0.0
    prefill_seconds_count: float = 0.0
    decode_step_seconds_sum: float = 0.0
    decode_step_seconds_count: float = 0.0
    decode_batch_occupancy_sum: float = 0.0
    decode_batch_occupancy_count: float = 0.0
    # Per-adapter capacity attribution scraped from the replica's
    # tpu:adapter_*_total families (server/usage.py).  Keys:
    # (model, adapter, phase) for step-seconds/tokens, (model, adapter)
    # for KV block-seconds; values are the replica's CUMULATIVE counters.
    # The gateway-wide rollup (gateway/usage.py) sums these across pods
    # and differences between scrape ticks.
    adapter_step_seconds: dict = field(default_factory=dict)
    adapter_tokens: dict = field(default_factory=dict)
    adapter_kv_block_seconds: dict = field(default_factory=dict)
    # Pool-waste counters (cumulative): slot-seconds decode dispatches ran
    # with empty rows, and prompt tokens prefilled as bucket/ring padding.
    idle_slot_seconds: float = 0.0
    prefill_padding_tokens: int = 0
    # KV economy ledger families (server/kv_ledger.py; all optional —
    # absent on foreign servers and with the ledger off).  kv_blocks maps
    # state -> blocks ("free"/"active"/"prefix_resident"/"parked", tiling
    # kv_blocks_total); kv_block_events maps lifecycle kind -> cumulative
    # count; the kv_prefix_* tables key on the content-addressed prefix
    # id, the join key of the fleet duplication index (gateway/kvobs.py).
    kv_blocks: dict = field(default_factory=dict)
    kv_blocks_total: int = 0
    kv_block_tokens: int = 0
    kv_block_events: dict = field(default_factory=dict)
    kv_prefix_hits: dict = field(default_factory=dict)
    kv_prefix_tokens_saved: dict = field(default_factory=dict)
    kv_prefix_resident_blocks: dict = field(default_factory=dict)

    def clone(self) -> "Metrics":
        m = dataclasses.replace(self)
        m.active_adapters = dict(self.active_adapters)
        m.adapter_ranks = dict(self.adapter_ranks)
        m.adapter_tiers = dict(self.adapter_tiers)
        m.adapter_step_seconds = dict(self.adapter_step_seconds)
        m.adapter_tokens = dict(self.adapter_tokens)
        m.adapter_kv_block_seconds = dict(self.adapter_kv_block_seconds)
        m.kv_blocks = dict(self.kv_blocks)
        m.kv_block_events = dict(self.kv_block_events)
        m.kv_prefix_hits = dict(self.kv_prefix_hits)
        m.kv_prefix_tokens_saved = dict(self.kv_prefix_tokens_saved)
        m.kv_prefix_resident_blocks = dict(self.kv_prefix_resident_blocks)
        return m

    @property
    def total_queue_size(self) -> int:
        """Combined pending work; used where the reference used WaitingQueueSize."""
        if self.waiting_queue_size:
            return self.waiting_queue_size
        return self.prefill_queue_size + self.decode_queue_size


@dataclass
class PodMetrics:
    """A pod together with its latest metrics snapshot (types.go:33-53)."""

    pod: Pod
    metrics: Metrics

    def clone(self) -> "PodMetrics":
        return PodMetrics(pod=self.pod, metrics=self.metrics.clone())

    def __str__(self) -> str:
        return f"Pod: {self.pod}; Metrics: {self.metrics}"
