"""Simulation core: TPU continuous-batching replica + event loop.

Server model (the TPU analog of the reference's ``llmactor.py`` +
``continous_batching.py``): each replica is a prefill/decode disaggregated
engine with ``decode_slots`` concurrent sequences and a token-denominated KV
budget.  Per iteration it either prefills one queued request (bucketed) or
advances every active slot one token — the same policy as
``server/engine.py``'s loop, so simulated queues/latencies have the same
shape as the real engine's.

Latency model (BASELINE.md form, TPU-recalibrated):
    T_prefill = max(c_min, c0 + c1 * prompt_tokens)
    T_decode  = c3 + c4 * total_kv_tokens_in_batch + c_batch * batch_size
Defaults are ``V5E_DEFAULT`` below (recorded by an early round, not
measured on today's engine); the reference's A100 constants
(``constants.py:1-8``) remain available as ``A100_VLLM`` for comparison.
"""

from __future__ import annotations

import heapq
from collections import OrderedDict
from dataclasses import dataclass

from llm_instance_gateway_tpu.gateway.types import Metrics, Pod, PodMetrics


@dataclass(frozen=True)
class LatencyModel:
    prefill_min_s: float
    prefill_base_s: float
    prefill_per_token_s: float
    decode_base_s: float
    decode_per_kv_token_s: float
    decode_per_seq_s: float
    adapter_load_s: float = 0.5     # Orbax restore of one adapter (disk tier)
    # Host-RAM promotion (residency ladder, server/lora_manager.py): the
    # adapter's weights are already in host memory, so a "load" is one
    # device put of a few-MB delta — tens of milliseconds, versus the
    # DISK tier's full Orbax restore (adapter_load_s).
    host_promote_s: float = 0.02
    # Decode fast-path knobs (engine PR 15 — the cost model item-3's
    # autoscaler loop reuses):
    # Fused decode steps per dispatch (EngineConfig.adaptive_steps /
    # decode_steps_per_sync): the dispatch base cost ``decode_base_s`` is
    # paid ONCE per dispatch while the per-kv/per-seq terms scale with the
    # fused step count — exactly the amortization the adaptive planner
    # buys on the real engine.
    steps_per_dispatch: int = 1
    # Concurrent chunk-stream lanes (EngineConfig.stream_lanes): how many
    # long prompts a SimServer advances chunk-by-chunk at once.
    stream_lanes: int = 1

    def prefill_s(self, prompt_tokens: int) -> float:
        return max(
            self.prefill_min_s,
            self.prefill_base_s + self.prefill_per_token_s * prompt_tokens,
        )

    def decode_s(self, total_kv_tokens: int, batch: int) -> float:
        return (
            self.decode_base_s
            + self.decode_per_kv_token_s * total_kv_tokens
            + self.decode_per_seq_s * batch
        )

    def decode_block_s(self, total_kv_tokens: int, batch: int) -> float:
        """One fused dispatch advancing every sequence
        ``steps_per_dispatch`` tokens: base paid once, marginal terms per
        step (kv integral approximated at the block's starting size)."""
        k = max(1, self.steps_per_dispatch)
        return (
            self.decode_base_s
            + k * (self.decode_per_kv_token_s * total_kv_tokens
                   + self.decode_per_seq_s * batch)
        )


# Reference calibration: A100-40GB, llama-3 arch on vLLM (constants.py:1-8).
A100_VLLM = LatencyModel(
    prefill_min_s=0.04,
    prefill_base_s=0.01969,
    prefill_per_token_s=6.769375513e-5,
    decode_base_s=0.014,
    decode_per_kv_token_s=5.353485087e-7,
    decode_per_seq_s=1.026494433e-4,
)

# v5e-1 constants as an early round recorded them for a since-rewritten
# engine (bench-llama-1b, 16 decode slots, K=8 fused steps, pipelined
# dispatch).  No ledger row backs them: on today's engine and host they
# are NOT MEASURED (ROADMAP Design 8 recalibrates from chip cells):
#   prefill  = 0.0205 + 1.52e-6 * prompt_tokens      (weight-stream bound
#              at batch 1: the base is HBM weights + dispatch, the
#              per-token slope is small until prompts reach thousands)
#   decode   = 0.0045 + 4.5e-8 * kv_tokens + 2.8e-4 * batch   per step
V5E_DEFAULT = LatencyModel(
    prefill_min_s=0.0176,
    prefill_base_s=0.0205,
    prefill_per_token_s=1.52e-6,
    decode_base_s=0.0045,
    decode_per_kv_token_s=4.5e-8,
    decode_per_seq_s=2.81e-4,
)


@dataclass
class SimRequest:
    rid: int
    arrival_s: float
    prompt_tokens: int
    output_tokens: int
    model: str
    adapter: str | None = None
    critical: bool = False
    tier: str = "Default"  # Critical / Default / Sheddable
    slo_s_per_token: float = 0.025
    # Shared-prefix modeling (session templates / multi-turn context): the
    # leading ``prefix_tokens`` of the prompt are identical across every
    # request with the same ``prefix_id`` — a replica holding it in its
    # prefix cache prefills only the suffix (models/paged.py semantics).
    prefix_id: int | None = None
    prefix_tokens: int = 0
    # lifecycle
    t_first_token: float = -1.0
    t_done: float = -1.0
    generated: int = 0
    shed: bool = False

    @property
    def ttft_s(self) -> float:
        return self.t_first_token - self.arrival_s if self.t_first_token >= 0 else -1

    @property
    def latency_per_output_token_s(self) -> float:
        if self.t_done < 0 or self.output_tokens == 0:
            return -1
        return (self.t_done - self.arrival_s) / self.output_tokens


@dataclass
class _ActiveSeq:
    request: SimRequest
    kv_tokens: int


class SimServer:
    """One TPU replica: prefill queue + decode slots + KV budget + adapters."""

    def __init__(
        self,
        name: str,
        latency: LatencyModel,
        decode_slots: int = 16,
        kv_capacity_tokens: int = 44_448,
        max_adapters: int = 4,
        prefix_cache_size: int = 32,
        host_cache_slots: int = 0,
        preload: "list[str] | None" = None,
        chunk_tokens: int = 0,
        kv_block_tokens: int = 16,
    ):
        self.name = name
        self.pod = Pod(name=name, address=f"{name}:8000")
        self.latency = latency
        self.decode_slots = decode_slots
        self.kv_capacity_tokens = kv_capacity_tokens
        self.max_adapters = max_adapters
        self.prefill_queue: list[SimRequest] = []
        self.active: list[_ActiveSeq] = []
        # Slot tier: adapter -> in-flight refcount (the engine's device
        # slot buffers).  ``preload`` models the all-resident baseline —
        # adapters resident at t=0 with no load charge.
        self.resident_adapters: dict[str, int] = {
            a: 0 for a in (preload or [])}
        # Host-RAM tier (residency ladder): adapters whose weights are in
        # host memory — promotion costs host_promote_s instead of the
        # full adapter_load_s disk restore.  LRU, bounded.
        self.host_cache_slots = host_cache_slots
        self.host_cache: "OrderedDict[str, None]" = OrderedDict()
        # Per-tier load counters (the sim twin of tpu:adapter_loads_total).
        self.disk_loads = 0
        self.host_promotes = 0
        self.demotions = 0
        # In-flight adapter loads: adapter -> sim time the weights become
        # slot-resident.  Loads run OFF the step path (the engine restores
        # in an executor thread; only the waiting request pays the
        # latency) — a cold adapter must not freeze every active slot.
        self.loading: dict[str, float] = {}
        # Last admission time per slot-resident adapter: slot pressure
        # demotes the least-recently-USED idle adapter, so a hot adapter
        # that is momentarily idle between requests is not the one the
        # cold tail displaces.
        self.last_used: dict[str, float] = {}
        self.busy_until = 0.0
        self.tokens_generated = 0
        # Prefix cache: retained prefix_ids, LRU-capped (the engine's
        # zero-ref cached blocks, abstracted to whole prefixes; their pool
        # occupancy is evict-on-demand and not charged against kv_free).
        self.prefix_cache_size = prefix_cache_size
        self.cached_prefixes: "OrderedDict[int, int]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_reused_tokens = 0
        # KV economy twin (server/kv_ledger.py): the sim's token-denominated
        # budget quantized to block-equivalents so ``kv_snapshot()`` emits
        # the SAME snapshot shape the engine ledger exports — per-prefix
        # heatmap rows keyed by the workload's shared prefix_id rendered as
        # a 16-hex id (identical across replicas, the duplication join key).
        self.kv_block_tokens = max(1, kv_block_tokens)
        self.prefix_stats: dict[int, dict] = {}   # pid -> hits/saved/touch
        self.prefix_registers = 0
        self.prefix_evictions = 0
        self.prefilled_tokens = 0  # cumulative prompt tokens computed
        self._kv_syncs = 0
        # Chunk-stream lanes (engine PR 15): prompts beyond chunk_tokens
        # stream one chunk per iteration into up to latency.stream_lanes
        # concurrent lanes (fair round-robin), interleaved with decode —
        # 0 disables (monolithic prefill, the pre-lever model).
        self.chunk_tokens = chunk_tokens
        self.streaming: list[dict] = []   # {"req": SimRequest, "done": int}
        self._lane_rr = 0

    # -- KV economy twin ---------------------------------------------------
    @staticmethod
    def _prefix_label(pid: int) -> str:
        """The workload's integer prefix_id as a 16-hex id — the same
        width as the engine's content-addressed ids, and identical across
        every sim replica serving the prefix (the duplication join key)."""
        return "%016x" % (pid % (1 << 64))

    def kv_snapshot(self) -> dict:
        """The engine ledger's ``snapshot()`` shape from sim state
        (server/kv_ledger.py contract; tests/test_sim.py pins key parity).
        Token-denominated sim KV quantizes to ``kv_block_tokens``-sized
        block-equivalents; cached prefixes sit outside the charged budget
        exactly like the engine's zero-ref evictable blocks."""
        from llm_instance_gateway_tpu.server.kv_ledger import (
            FREE_RUN_BUCKETS, PARKED_SHARE_BUCKETS)
        from llm_instance_gateway_tpu.tracing import Histogram

        self._kv_syncs += 1
        block = self.kv_block_tokens
        pool = max(1, self.kv_capacity_tokens // block)
        active = sum(-(-a.kv_tokens // block) for a in self.active
                     if a.kv_tokens > 0)
        resident = {pid: -(-tok // block)
                    for pid, tok in self.cached_prefixes.items() if tok > 0}
        free = max(0, pool - active - sum(resident.values()))
        prefixes = []
        for pid, blocks in resident.items():
            stats = self.prefix_stats.get(pid) or {
                "hits": 0, "tokens_saved": 0, "last_touch": 0.0}
            prefixes.append({
                "prefix": self._prefix_label(pid),
                "hits": stats["hits"],
                "tokens_saved": stats["tokens_saved"],
                "blocks": blocks,
                "age_s": 0.0,
            })
        prefixes.sort(key=lambda e: (-e["hits"], -e["tokens_saved"],
                                     e["prefix"]))
        free_runs = Histogram(FREE_RUN_BUCKETS)
        if free:
            # The sim has no physical block ids: its free space is one
            # contiguous run (an upper bound on contiguity, stated here).
            free_runs.observe(float(free))
        parked_share = Histogram(PARKED_SHARE_BUCKETS)
        parked_share.observe(0.0)
        return {
            "blocks_total": pool,
            "pool_blocks": pool,
            "block_tokens": block,
            "states": {"free": free, "active": active,
                       "prefix_resident": sum(resident.values()),
                       "parked": 0},
            "parked_tokens": 0,
            "events": {"reuse_hit": self.prefix_hits,
                       "register": self.prefix_registers,
                       "evict": self.prefix_evictions},
            "prefixes": prefixes,
            "prefix_table_size": len(resident),
            "prefix_table_evictions": self.prefix_evictions,
            "free_runs": free_runs.state(),
            "parked_share": parked_share.state(),
            "ring": [],
            "syncs": self._kv_syncs,
        }

    # -- metrics the production scheduler consumes -------------------------
    def metrics(self) -> PodMetrics:
        used = sum(a.kv_tokens for a in self.active)
        tiers = {name: "slot" for name in self.resident_adapters}
        for name in self.host_cache:
            tiers.setdefault(name, "host")
        kv = self.kv_snapshot()
        return PodMetrics(
            pod=self.pod,
            metrics=Metrics(
                active_adapters=dict(self.resident_adapters),
                max_active_adapters=self.max_adapters,
                adapter_tiers=tiers,
                running_adapters=frozenset(
                    s.request.adapter for s in self.active
                    if s.request.adapter),
                waiting_adapters=frozenset(
                    r.adapter for r in self.prefill_queue if r.adapter),
                running_queue_size=len(self.active),
                waiting_queue_size=len(self.prefill_queue),
                prefill_queue_size=len(self.prefill_queue),
                decode_queue_size=0,
                kv_cache_usage_percent=used / self.kv_capacity_tokens,
                kv_tokens_capacity=self.kv_capacity_tokens,
                kv_tokens_free=self.kv_capacity_tokens - used,
                # KV economy twin: the same fields metrics_client parses
                # from a real pod's tpu:kv_* families, so the gateway's
                # kvobs rollup (and KV_BASELINE generation) runs over sim
                # fleets unchanged.
                prefix_reused_tokens=self.prefix_reused_tokens,
                adapter_tokens={("sim", "base", "prefill"):
                                float(self.prefilled_tokens)},
                kv_blocks=dict(kv["states"]),
                kv_blocks_total=kv["blocks_total"],
                kv_block_tokens=kv["block_tokens"],
                kv_block_events=dict(kv["events"]),
                kv_prefix_hits={e["prefix"]: e["hits"]
                                for e in kv["prefixes"]},
                kv_prefix_tokens_saved={e["prefix"]: e["tokens_saved"]
                                        for e in kv["prefixes"]},
                kv_prefix_resident_blocks={e["prefix"]: e["blocks"]
                                           for e in kv["prefixes"]},
            ),
        )

    # -- residency ladder (planner-drivable verbs) -------------------------
    def _host_put(self, adapter: str) -> None:
        if self.host_cache_slots <= 0:
            return
        self.host_cache[adapter] = None
        self.host_cache.move_to_end(adapter)
        while len(self.host_cache) > self.host_cache_slots:
            self.host_cache.popitem(last=False)  # LRU falls to disk

    def _start_load(self, adapter: str, now: float) -> None:
        """Kick an async slot load: host_promote_s off the host tier, the
        full Orbax adapter_load_s off disk.  The requesting sequence stays
        queued until the load lands; other traffic keeps flowing."""
        if adapter in self.loading:
            return
        if adapter in self.host_cache:
            del self.host_cache[adapter]
            cost = self.latency.host_promote_s
            self.host_promotes += 1
        else:
            cost = self.latency.adapter_load_s
            self.disk_loads += 1
        self.loading[adapter] = now + cost

    def _finish_loads(self, now: float) -> None:
        """Land finished restores into slots, displacing least-recently-
        used IDLE adapters under pressure (vLLM-style slot LRU; the
        pre-ladder sim's self-eviction, now demoting into the host tier).
        When every resident adapter is mid-decode there is nothing safe
        to displace, so the set transiently exceeds ``max_adapters`` —
        and is squeezed back down as decodes finish and later landings
        re-apply pressure."""
        for adapter, ready_at in list(self.loading.items()):
            if ready_at > now:
                continue
            del self.loading[adapter]
            self.resident_adapters.setdefault(adapter, 0)
            while len(self.resident_adapters) > self.max_adapters:
                before = len(self.resident_adapters)
                self._slot_pressure(adapter)
                if len(self.resident_adapters) == before:
                    break  # everything else is busy: transient overflow

    def _slot_pressure(self, keep: str) -> None:
        """Engine-side LRU displacement: demote the least-recently-used
        idle adapter to host RAM to make room (vLLM-style slot LRU; the
        planner's demote/evict decisions ride on top of this backstop)."""
        idle = [name for name, refs in self.resident_adapters.items()
                if refs == 0 and name != keep]
        if not idle:
            return
        victim = min(idle, key=lambda n: (self.last_used.get(n, -1.0), n))
        del self.resident_adapters[victim]
        self._host_put(victim)
        self.demotions += 1

    def host_prefetch(self, adapter: str) -> None:
        """Planner 'prefetch'/'migrate' verb: disk -> host RAM.  Free of
        TPU step time — the Orbax restore runs host-side off the decode
        loop (the engine loads in an executor thread); only the later
        promotion's device put charges the step path."""
        if adapter in self.resident_adapters or adapter in self.host_cache:
            return
        self._host_put(adapter)

    def demote(self, adapter: str) -> None:
        """Planner 'demote' verb: slot -> host RAM; refused (no-op) while
        in-flight requests pin the slot — AdapterBusyError semantics."""
        if self.resident_adapters.get(adapter) == 0:
            del self.resident_adapters[adapter]
            self._host_put(adapter)
            self.demotions += 1

    def evict_host(self, adapter: str) -> None:
        """Planner 'evict' verb: host RAM -> disk."""
        self.host_cache.pop(adapter, None)

    # -- engine iteration (mirrors server/engine.py:_loop) ------------------
    def kv_free(self) -> int:
        return self.kv_capacity_tokens - sum(a.kv_tokens for a in self.active)

    def _admit_would_fit(self, req: SimRequest) -> bool:
        return req.prompt_tokens + req.output_tokens <= self.kv_free()

    def step(self, now: float) -> float:
        """Run one engine iteration starting at ``now``; return its duration.

        Returns 0.0 when idle (nothing to do).
        """
        self._finish_loads(now)
        # Admission: prefill one queued request if a slot is free and the
        # full sequence fits in KV (the engine's slot admission gate).
        # Requests whose adapter is still loading are SKIPPED, not head-
        # blocking: the engine's executor-thread restore lets other
        # traffic keep flowing while the waiting request pays the latency.
        req = None
        lanes = max(1, self.latency.stream_lanes)
        active_streams = len(self.streaming) + len(self.active)
        if self.prefill_queue and active_streams < self.decode_slots:
            for i, queued in enumerate(self.prefill_queue):
                if (queued.adapter is not None
                        and queued.adapter not in self.resident_adapters):
                    self._start_load(queued.adapter, now)
                    continue  # waiting on its load; later traffic flows
                if not self._admit_would_fit(queued):
                    break  # KV capacity head-block at the first admissible
                if (self.chunk_tokens
                        and queued.prompt_tokens > self.chunk_tokens):
                    # Long prompt: takes a chunk-stream lane (no compute
                    # this iteration; chunks advance below).  No lane free
                    # = head-of-line wait, the engine's FIFO contract.
                    if len(self.streaming) >= lanes:
                        break
                    self.streaming.append(
                        {"req": self.prefill_queue.pop(i), "done": 0})
                    break
                req = self.prefill_queue.pop(i)
                break
        if req is not None:
            prefill_tokens = req.prompt_tokens
            if req.prefix_id is not None:
                stats = self.prefix_stats.setdefault(
                    req.prefix_id,
                    {"hits": 0, "tokens_saved": 0, "last_touch": now})
                stats["last_touch"] = now
                if req.prefix_id in self.cached_prefixes:
                    # Cache hit: only the suffix prefills (the prefix's KV
                    # blocks map into the row's table, zero compute).
                    prefill_tokens = max(
                        0, req.prompt_tokens - req.prefix_tokens)
                    self.prefix_hits += 1
                    self.prefix_reused_tokens += req.prefix_tokens
                    stats["hits"] += 1
                    stats["tokens_saved"] += req.prefix_tokens
                else:
                    self.prefix_misses += 1
                    self.prefix_registers += 1
                self.cached_prefixes[req.prefix_id] = req.prefix_tokens
                self.cached_prefixes.move_to_end(req.prefix_id)
                while len(self.cached_prefixes) > self.prefix_cache_size:
                    evicted, _tok = self.cached_prefixes.popitem(last=False)
                    self.prefix_stats.pop(evicted, None)
                    self.prefix_evictions += 1
            self.prefilled_tokens += prefill_tokens
            duration = self.latency.prefill_s(prefill_tokens)
            if req.adapter:
                self.resident_adapters[req.adapter] = (
                    self.resident_adapters.get(req.adapter, 0) + 1
                )
                self.last_used[req.adapter] = now
            req.t_first_token = now + duration
            req.generated = 1
            self.tokens_generated += 1
            if req.generated >= req.output_tokens:
                req.t_done = now + duration  # single-token request: done
                if req.adapter:  # release the refcount taken above
                    refs = self.resident_adapters.get(req.adapter, 1)
                    self.resident_adapters[req.adapter] = max(0, refs - 1)
            else:
                self.active.append(_ActiveSeq(req, req.prompt_tokens + 1))
            return duration

        duration = 0.0
        if self.streaming:
            # One chunk of ONE lane per iteration (fair round-robin),
            # interleaved with the decode block below — the engine loop's
            # cycle shape, so N long prompts advance concurrently instead
            # of head-of-line serializing.
            self._lane_rr %= len(self.streaming)
            lane = self.streaming[self._lane_rr]
            self._lane_rr += 1
            r = lane["req"]
            chunk = min(self.chunk_tokens, r.prompt_tokens - lane["done"])
            duration += self.latency.prefill_s(chunk)
            lane["done"] += chunk
            self.prefilled_tokens += chunk
            if lane["done"] >= r.prompt_tokens:
                # Final chunk: the lane activates as a live decode slot
                # and the first token is emitted (engine _stream_step).
                self.streaming.remove(lane)
                r.t_first_token = now + duration
                r.generated = 1
                self.tokens_generated += 1
                if r.adapter:
                    self.resident_adapters[r.adapter] = (
                        self.resident_adapters.get(r.adapter, 0) + 1)
                    self.last_used[r.adapter] = now
                if r.generated >= r.output_tokens:
                    r.t_done = now + duration
                    if r.adapter:
                        refs = self.resident_adapters.get(r.adapter, 1)
                        self.resident_adapters[r.adapter] = max(0, refs - 1)
                else:
                    self.active.append(_ActiveSeq(r, r.prompt_tokens + 1))
        if self.active:
            total_kv = sum(a.kv_tokens for a in self.active)
            steps = max(1, self.latency.steps_per_dispatch)
            duration += self.latency.decode_block_s(total_kv,
                                                    len(self.active))
            finished = []
            for seq in self.active:
                adv = min(steps,
                          seq.request.output_tokens - seq.request.generated)
                seq.request.generated += adv
                seq.kv_tokens += adv
                self.tokens_generated += adv
                if seq.request.generated >= seq.request.output_tokens:
                    seq.request.t_done = now + duration
                    finished.append(seq)
            for seq in finished:
                self.active.remove(seq)
                if seq.request.adapter:
                    refs = self.resident_adapters.get(seq.request.adapter, 1)
                    self.resident_adapters[seq.request.adapter] = max(0, refs - 1)
            return duration
        if duration > 0:
            return duration
        if self.loading:
            # Idle except for in-flight adapter loads: stay scheduled
            # until the earliest one lands (the event loop only re-kicks
            # idle servers on arrivals).  A restore that is ready but
            # waiting for the planner to free a slot polls at a coarse
            # cadence instead of busy-spinning the event loop.
            return max(0.01, min(self.loading.values()) - now)
        return 0.0


class EventLoop:
    """Minimal DES driver: servers advance via their own iteration events."""

    def __init__(self, servers: list[SimServer]):
        self.servers = servers
        self.now = 0.0
        self._events: list[tuple[float, int, object]] = []
        self._seq = 0

    def schedule(self, t: float, item) -> None:
        heapq.heappush(self._events, (t, self._seq, item))
        self._seq += 1

    def kick(self, server: SimServer) -> None:
        """Ensure a server has a pending iteration event."""
        if server.busy_until <= self.now:
            self.schedule(self.now, server)

    def run(self, until: float) -> None:
        while self._events:
            t, _, item = heapq.heappop(self._events)
            if t > until:
                break
            self.now = t
            if isinstance(item, SimServer):
                duration = item.step(self.now)
                if duration > 0:
                    item.busy_until = self.now + duration
                    self.schedule(item.busy_until, item)
                # idle servers get re-kicked on arrival
            elif callable(item):
                item(self)
