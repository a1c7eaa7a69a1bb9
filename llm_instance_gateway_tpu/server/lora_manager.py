"""LoRA adapter lifecycle on the serving process: registry + Orbax hot-swap.

This replaces the reference's vLLM-side adapter machinery: where the sidecar
POSTs ``/v1/load_lora_adapter`` and vLLM pulls safetensors into CUDA slots
(``tools/dynamic-lora-sidecar/sidecar/sidecar.py:177-213``), our server
restores an **Orbax checkpoint** directly into the pre-allocated JAX slot
buffers (``models.lora``) — no recompilation, no process restart, and the
swap is one device-buffer write (BASELINE.json north star: "hot-swaps
adapters into a JAX/XLA serving process via Orbax restore").

Checkpoint layout (written by ``save_adapter`` / the training pipeline):
a pytree ``{"meta": {"alpha": f, "rank": r}, "weights": {target: {"a": ...,
"b": ...}}}`` saved with ``orbax.checkpoint.PyTreeCheckpointer``.
"""

from __future__ import annotations

import logging
import time
from collections import OrderedDict
from collections.abc import Callable, Iterable
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.models import lora as lora_lib

logger = logging.getLogger(__name__)

# Residency ladder tiers (MinT/InfiniLoRA-style disaggregated placement):
# ``slot`` = device buffers, decodable this instant; ``host`` = weights
# parked in host RAM (promotion is one device put, no checkpoint restore);
# ``disk`` = Orbax checkpoint only (cold: restore + device put).  An
# adapter is in EXACTLY ONE tier per replica at any time — the
# conservation invariant tests/test_placement.py lints through the
# rendered exposition.
TIER_SLOT = "slot"
TIER_HOST = "host"
TIER_DISK = "disk"
RESIDENCY_TIERS = (TIER_SLOT, TIER_HOST, TIER_DISK)


class AdapterError(Exception):
    pass


class AdapterBusyError(AdapterError):
    """Adapter has in-flight requests pinned to its slot (HTTP 409).

    Unloading a slot that live decodes still read would silently degrade
    those requests to base-model output — and a subsequent load() into the
    recycled slot would hand them a *different tenant's* weights.  The
    sidecar reconciler simply retries on its next pass once traffic drains.
    """


@dataclass
class AdapterInfo:
    name: str
    slot: int
    rank: int
    alpha: float
    source: str  # checkpoint path or "inline"
    # Checkpoint-shaped host copy of the weights (numpy pytree).  This is
    # what demotion moves into the host tier: extracting weights back out
    # of the padded device buffers would be a device read plus an un-pad;
    # keeping the (few-MB) host reference makes slot->host a pointer move
    # and host->slot one device put.  Excluded from repr (huge).
    weights: dict | None = None

    @property
    def targets(self) -> frozenset[str]:
        """The LoRA targets this adapter carries: every other target's slot
        holds exact zeros (``models.lora.load_adapter``)."""
        if self.weights is None:  # legacy load path: nothing is known
            return frozenset(lora_lib.TARGETS)
        return frozenset(lora_lib.TARGETS).intersection(self.weights)

    def __repr__(self):  # keep logs/debug payloads weight-free
        return (f"AdapterInfo(name={self.name!r}, slot={self.slot}, "
                f"rank={self.rank}, alpha={self.alpha}, "
                f"source={self.source!r})")


def save_adapter(path: str, weights: dict, alpha: float, rank: int) -> None:
    """Write an adapter checkpoint (numpy pytree) via Orbax."""
    import orbax.checkpoint as ocp

    tree = {
        "meta": {"alpha": np.float32(alpha), "rank": np.int32(rank)},
        "weights": {
            t: {k: np.asarray(v, np.float32) for k, v in tv.items()}
            for t, tv in weights.items()
        },
    }
    ocp.PyTreeCheckpointer().save(path, tree)


def load_adapter_checkpoint(path: str) -> tuple[dict, float, int]:
    import orbax.checkpoint as ocp

    tree = ocp.PyTreeCheckpointer().restore(path)
    meta = tree["meta"]
    return tree["weights"], float(meta["alpha"]), int(meta["rank"])


class LoRAManager:
    """Thread-safe adapter registry bound to the engine's slot buffers.

    Mirrors the metric semantics of ``vllm:lora_requests_info``
    (``backend/vllm/metrics.go:19-32``): ``running_adapters`` is the set the
    gateway's affinity filter matches against; ``max_slots`` is max_lora.
    """

    def __init__(self, cfg, dtype=jnp.bfloat16, mesh=None,
                 host_cache_slots: int = 8, clock=time.perf_counter):
        self.cfg = cfg
        self._lock = witness_lock("LoRAManager._lock")
        # Serializes whole load/unload operations: the buffer update is a
        # read-modify-write of self.buffers, and concurrent HTTP admin calls
        # run in separate executor threads — without this, the second writer
        # would silently drop the first one's weights.
        self._mutate_lock = witness_lock("LoRAManager._mutate_lock")
        self._adapters: dict[str, AdapterInfo] = {}
        self._active: dict[str, int] = {}  # name -> in-flight request count
        self._free_slots = list(range(cfg.max_lora_slots))
        # The union of the targets the slot-tier adapters carry, and of a
        # load under way from the moment it holds its slot: what a decode
        # block with an adapter row has to read (resident_targets).  The
        # hook hears of every change of it (watch_targets).
        self._targets: frozenset[str] = frozenset()
        self._targets_hook: Callable[[frozenset[str]], None] | None = None
        # Host-RAM tier: name -> (weights numpy pytree, alpha, rank,
        # source), LRU-bounded.  Promotion (host -> slot) skips the Orbax
        # restore entirely — one device put; demotion (slot -> host) copies
        # the checkpoint-shaped weights back to host numpy so a later
        # promote restores bit-identical deltas.
        self.host_cache_slots = max(0, host_cache_slots)
        self._host: "OrderedDict[str, tuple]" = OrderedDict()
        self._clock = clock
        # Residency-plane accounting (rendered by server/metrics.py):
        # tier transitions and per-tier load latency (sum, count).
        self.tier_transitions: dict[tuple[str, str], int] = {}
        self.load_seconds: dict[str, list] = {
            t: [0.0, 0] for t in (TIER_HOST, TIER_DISK)}
        self.buffers = lora_lib.init_lora_buffers(cfg, dtype=dtype)
        # Sharded serving: pin slot buffers to the engine's mesh so the delta
        # matmuls compose with the column-sharded base projections without
        # resharding (parallel/sharding.py lora_specs).
        self._mesh = mesh
        if mesh is not None:
            from llm_instance_gateway_tpu.parallel import sharding as sharding_lib

            self._lora_specs = sharding_lib.lora_specs(cfg)
            self.buffers = sharding_lib.shard_pytree(
                self.buffers, self._lora_specs, mesh)

    def _pin(self, buffers):
        """Re-pin buffers to the mesh after an eager .at[].set mutation."""
        if self._mesh is None:
            return buffers
        from llm_instance_gateway_tpu.parallel import sharding as sharding_lib

        return sharding_lib.shard_pytree(buffers, self._lora_specs, self._mesh)

    # -- queries -----------------------------------------------------------
    def running_adapters(self) -> list[str]:
        with self._lock:
            return sorted(self._adapters)

    def adapter_ranks(self) -> dict[str, int]:
        """Resident adapter name -> LoRA rank — the heterogeneity signal
        the gateway's rank-aware fair-share weighting consumes (exported
        as the ``adapter_ranks`` label of ``tpu:lora_requests_info``).
        Host-tier adapters are included: the planner prices a promotion's
        rank cost before it happens."""
        with self._lock:
            ranks = {name: info.rank for name, info in self._adapters.items()}
            for name, (_w, _a, rank, _src) in self._host.items():
                ranks.setdefault(name, rank)
            return ranks

    def adapter_tiers(self) -> dict[str, str]:
        """Adapter name -> residency tier for every adapter this replica
        holds in RAM (slot or host).  Disk-tier adapters are unknowable
        here (the checkpoint store is unbounded); the placement planner
        treats absence as disk.  Each name maps to exactly one tier — the
        conservation invariant the exposition lint pins."""
        with self._lock:
            tiers = {name: TIER_SLOT for name in self._adapters}
            for name in self._host:
                tiers[name] = TIER_HOST
            return tiers

    def residency_snapshot(self) -> dict[str, list[str]]:
        """Tier -> sorted adapter names (tpu:adapter_residency_info)."""
        with self._lock:
            return {TIER_SLOT: sorted(self._adapters),
                    TIER_HOST: sorted(self._host)}

    def residency_counters(self) -> tuple[dict, dict]:
        """(tier transitions {(from, to): n}, per-tier load latency
        {tier: [sum_s, count]}) — copies for the metrics snapshot."""
        with self._lock:
            return (dict(self.tier_transitions),
                    {t: list(sc) for t, sc in self.load_seconds.items()})

    def resident_targets(self) -> frozenset[str]:
        """The LoRA targets some slot-tier adapter carries (the keys of
        ``AdapterInfo.weights``), and those of a load that has its slot and
        is not published yet.  Every other target's buffers hold exact zeros
        in every slot, so a program that serves adapter rows need not be
        handed them (``Engine._block_lora_buffers``)."""
        with self._lock:
            return self._targets

    def targets_of(self, names: Iterable[str]) -> frozenset[str]:
        """The targets the slot-tier adapters among ``names`` carry."""
        with self._lock:
            return frozenset().union(*(
                self._adapters[n].targets for n in names
                if n in self._adapters))

    def watch_targets(
            self, hook: Callable[[frozenset[str]], None] | None) -> None:
        """``hook(resident_targets)`` is called, on the thread of the
        residency verb and outside ``_lock``, whenever that set changes:
        by ``load`` BEFORE the adapter is published (no request can name it
        until the hook returns, so a hook may take its time to get ready
        for a wider set), by ``unload`` and ``demote`` after the slot is
        zeroed."""
        self._targets_hook = hook

    def _retarget(self, loading: frozenset[str] = frozenset()) -> bool:
        """Recount ``_targets`` from the slot tier and a load under way;
        True if it changed.  Caller holds self._lock."""
        before = self._targets
        self._targets = loading.union(*(
            info.targets for info in self._adapters.values()))
        return self._targets != before

    def _targets_changed(self) -> None:
        """Tell the hook.  Caller holds ``_mutate_lock`` (the set cannot
        move under the hook) and not ``_lock``."""
        if self._targets_hook is not None:
            self._targets_hook(self.resident_targets())

    def _note_transition(self, frm: str, to: str) -> None:
        """Caller holds self._lock."""
        key = (frm, to)
        self.tier_transitions[key] = self.tier_transitions.get(key, 0) + 1

    def _note_load(self, tier: str, seconds: float) -> None:
        with self._lock:
            sc = self.load_seconds.setdefault(tier, [0.0, 0])
            sc[0] += seconds
            sc[1] += 1

    def _host_put(self, name: str, weights: dict, alpha: float, rank: int,
                  source: str) -> None:
        """Insert into the bounded host tier (caller holds self._lock);
        LRU overflow falls off to disk (the checkpoint is the backstop)."""
        self._host[name] = (weights, alpha, rank, source)
        self._host.move_to_end(name)
        while len(self._host) > self.host_cache_slots:
            evicted, _ = self._host.popitem(last=False)
            self._note_transition(TIER_HOST, TIER_DISK)
            logger.info("host cache full: adapter %s fell to disk", evicted)

    @property
    def max_slots(self) -> int:
        return self.cfg.max_lora_slots

    def slot_for(self, adapter_name: str | None) -> int:
        """Slot id for a request (-1 = base model). Raises if not resident."""
        if adapter_name is None:
            return -1
        with self._lock:
            info = self._adapters.get(adapter_name)
        if info is None:
            raise AdapterError(f"adapter {adapter_name!r} is not loaded")
        return info.slot

    def acquire(self, adapter_name: str | None) -> int:
        """Resolve AND pin: the slot cannot be unloaded/recycled until the
        matching ``release``.  The engine acquires at admission and releases
        at finish, so live decodes never read a repurposed slot buffer."""
        if adapter_name is None:
            return -1
        with self._lock:
            info = self._adapters.get(adapter_name)
            if info is None:
                raise AdapterError(f"adapter {adapter_name!r} is not loaded")
            self._active[adapter_name] = self._active.get(adapter_name, 0) + 1
            return info.slot

    def release(self, adapter_name: str | None) -> None:
        if adapter_name is None:
            return
        with self._lock:
            n = self._active.get(adapter_name, 0)
            if n <= 1:
                self._active.pop(adapter_name, None)
            else:
                self._active[adapter_name] = n - 1

    def active_requests(self, adapter_name: str) -> int:
        with self._lock:
            return self._active.get(adapter_name, 0)

    # -- mutations ---------------------------------------------------------
    def load(
        self,
        name: str,
        weights: dict | None = None,
        alpha: float = 16.0,
        rank: int = 8,
        checkpoint_path: str | None = None,
    ) -> AdapterInfo:
        """Load an adapter into a free slot (idempotent per name)."""
        if not name or not all(c.isalnum() or c in "._-" for c in name):
            raise AdapterError(
                f"invalid adapter name {name!r}: use [A-Za-z0-9._-] "
                "(names flow into Prometheus labels and routing configs)"
            )
        with self._mutate_lock:
            with self._lock:
                if name in self._adapters:
                    return self._adapters[name]  # resident (sidecar.py:185-188)
                if not self._free_slots:
                    raise AdapterError(
                        f"no free adapter slots (max {self.cfg.max_lora_slots})"
                    )
                slot = self._free_slots.pop(0)
                # Promotion path: a host-tier copy skips the Orbax restore
                # — the whole point of the residency ladder.  The entry is
                # popped (not copied) so the name is never in two tiers.
                # A caller supplying NEW weights, or a DIFFERENT checkpoint
                # path than the cached copy came from, is publishing a new
                # version: the stale host copy must not shadow it — it is
                # discarded (the caller's source is authoritative).
                cached = self._host.pop(name, None)
                if cached is not None and (
                        weights is not None
                        or (checkpoint_path is not None
                            and checkpoint_path != cached[3])):
                    self._note_transition(TIER_HOST, TIER_DISK)
                    logger.info(
                        "discarding stale host copy of %s (source %s; "
                        "caller supplied a new source)", name, cached[3])
                    cached = None
            source = checkpoint_path or "inline"
            from_tier, timed_tier = TIER_DISK, None
            t0 = self._clock()
            try:
                if cached is not None:
                    weights, alpha, rank, source = cached
                    from_tier = timed_tier = TIER_HOST
                elif checkpoint_path is not None:
                    weights, alpha, rank = load_adapter_checkpoint(
                        checkpoint_path)
                    timed_tier = TIER_DISK
                if weights is None:
                    raise AdapterError("either weights or checkpoint_path required")
                self.buffers = self._pin(lora_lib.load_adapter(
                    self.buffers, self.cfg, slot, weights, alpha, rank
                ))
                info = AdapterInfo(
                    name=name, slot=slot, rank=rank, alpha=alpha,
                    source=source, weights=weights,
                )
                # A load that widens the resident targets waits here, on
                # its own thread, for whoever serves them to be ready
                # (the engine compiles the wider decode programs), and
                # only then becomes visible to acquire() and the gateway.
                with self._lock:
                    widened = self._retarget(loading=info.targets)
                if widened:
                    self._targets_changed()
            except Exception:
                with self._lock:
                    self._free_slots.insert(0, slot)
                    if cached is not None:  # promotion failed: keep the copy
                        self._host[name] = cached
                    self._retarget()
                raise
            if timed_tier is not None:
                self._note_load(timed_tier, self._clock() - t0)
            with self._lock:
                self._adapters[name] = info
                self._note_transition(from_tier, TIER_SLOT)
        logger.info("loaded adapter %s into slot %d (rank %d, from %s)",
                    name, slot, rank, from_tier)
        return info

    def unload(self, name: str) -> bool:
        with self._mutate_lock:
            with self._lock:
                # Busy-check and pop atomically: a concurrent acquire() holds
                # the same lock and increments only while the name is still
                # registered, so no request can slip in after the check.
                active = self._active.get(name, 0)
                if active:
                    raise AdapterBusyError(
                        f"adapter {name!r} has {active} in-flight request(s); "
                        "retry after they drain"
                    )
                info = self._adapters.pop(name, None)
                if info is None:
                    # Host-tier unload needs no buffer work — drop the copy.
                    if self._host.pop(name, None) is not None:
                        self._note_transition(TIER_HOST, TIER_DISK)
                        logger.info("unloaded host-cached adapter %s", name)
                        return True
                    return False
            self.buffers = self._pin(
                lora_lib.unload_adapter(self.buffers, self.cfg, info.slot))
            with self._lock:
                self._free_slots.append(info.slot)
                self._note_transition(TIER_SLOT, TIER_DISK)
                narrowed = self._retarget()
            if narrowed:
                self._targets_changed()
        logger.info("unloaded adapter %s from slot %d", name, info.slot)
        return True

    def demote(self, name: str) -> bool:
        """Slot -> host RAM: free the device slot, keep the weights hot so
        a later ``load`` is one device put instead of an Orbax restore.
        Refuses (AdapterBusyError -> HTTP 409) while the adapter has
        in-flight or decode_wait-parked requests — the engine acquires at
        admission and releases at finish, so a demoted slot can never be
        recycled under a live decode (the same pin ``unload`` honors).
        Refuses outright when the host tier is disabled: "demoting" into
        a zero-slot cache would silently discard the weights (fatal for
        inline-loaded adapters with no checkpoint backstop) while
        claiming tier=host."""
        if self.host_cache_slots <= 0:
            raise AdapterError(
                "cannot demote: host cache disabled (host_cache_slots=0); "
                "use unload if the checkpoint store is the backstop")
        with self._mutate_lock:
            with self._lock:
                active = self._active.get(name, 0)
                if active:
                    raise AdapterBusyError(
                        f"adapter {name!r} has {active} in-flight request(s); "
                        "retry after they drain"
                    )
                info = self._adapters.pop(name, None)
                if info is None:
                    return False
                if info.weights is None:
                    # No host copy to park (legacy load path): a demote
                    # would lose the weights entirely — refuse.
                    self._adapters[name] = info
                    raise AdapterError(
                        f"adapter {name!r} has no host-side weights to "
                        "demote (reload it from a checkpoint first)")
            self.buffers = self._pin(
                lora_lib.unload_adapter(self.buffers, self.cfg, info.slot))
            with self._lock:
                self._free_slots.append(info.slot)
                self._host_put(name, info.weights, info.alpha, info.rank,
                               info.source)
                self._note_transition(TIER_SLOT, TIER_HOST)
                narrowed = self._retarget()
            if narrowed:
                self._targets_changed()
        logger.info("demoted adapter %s: slot %d -> host RAM", name,
                    info.slot)
        return True

    def prefetch(self, name: str, checkpoint_path: str) -> bool:
        """Disk -> host RAM: Orbax-restore into the host tier WITHOUT
        consuming a device slot, so a later promotion is cheap.  Idempotent
        for already-RAM-resident names (slot or host)."""
        if not name or not all(c.isalnum() or c in "._-" for c in name):
            raise AdapterError(
                f"invalid adapter name {name!r}: use [A-Za-z0-9._-]")
        if self.host_cache_slots <= 0:
            raise AdapterError("host cache disabled (host_cache_slots=0)")
        with self._mutate_lock:
            with self._lock:
                if name in self._adapters or name in self._host:
                    return False  # already RAM-resident
            t0 = self._clock()
            weights, alpha, rank = load_adapter_checkpoint(checkpoint_path)
            self._note_load(TIER_DISK, self._clock() - t0)
            with self._lock:
                self._host_put(name, weights, alpha, rank, checkpoint_path)
                self._note_transition(TIER_DISK, TIER_HOST)
        logger.info("prefetched adapter %s into host RAM (rank %d)",
                    name, rank)
        return True

    def evict_host(self, name: str) -> bool:
        """Host RAM -> disk: drop the host copy (the checkpoint remains
        the backstop).  Slot-resident adapters are untouched — demote
        first."""
        with self._mutate_lock, self._lock:
            if self._host.pop(name, None) is None:
                return False
            self._note_transition(TIER_HOST, TIER_DISK)
        logger.info("evicted adapter %s from host RAM", name)
        return True
