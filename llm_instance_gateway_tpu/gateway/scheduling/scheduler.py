"""Scheduler: builds the decision trees and picks a target replica.

Parity: reference ``pkg/ext-proc/scheduling/scheduler.go:26-122``:

- ``default`` tree: critical? -> low-latency path, else sheddable path which
  drops with RESOURCE_EXHAUSTED when no replica has capacity
  (scheduler.go:74-90 -> 429 at the transport layer).
- low-latency path: queue < threshold -> LoRA affinity -> can-accept-new-LoRA,
  falling back to least-queuing -> low-LoRA-cost -> least-KV-cache
  (scheduler.go:34-72).
- Final choice: uniform random among survivors (scheduler.go:120) to spread
  near-ties.

TPU-native extensions (both ON by default — this framework routes TPU
disaggregated-continuous-batching replicas; pass ``False`` for strict
reference parity, as the parity tests do):

- ``token_aware=True`` inserts the KV-token-headroom predicate ahead of the
  queue filters so long-context requests only land where the prompt fits.
- ``prefill_aware=True`` routes on the prefill queue (TTFT-gating signal under
  prefill/decode disaggregation) before total queue depth.

"""

from __future__ import annotations

import random
import time
from typing import (
    TYPE_CHECKING,
    Any,
    Callable,
    Iterable,
    Protocol,
    Sequence,
    TypeVar,
)

from llm_instance_gateway_tpu.gateway.scheduling.config import (
    DEFAULT_CONFIG,
    SchedulerConfig,
)
from llm_instance_gateway_tpu.gateway.scheduling.filter import (
    Filter,
    FilterError,
    least_kv_cache_filter,
    least_prefill_queue_filter,
    least_queuing_filter,
    make_predicates,
    to_filter_func,
)
from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
from llm_instance_gateway_tpu.gateway.types import (
    ROLE_COLLOCATED,
    ROLE_DECODE,
    ROLE_PREFILL,
    Pod,
    PodMetrics,
    pod_role,
)


if TYPE_CHECKING:
    from llm_instance_gateway_tpu.gateway.scheduling.prefix_affinity import (
        PrefixIndex,
    )

# Candidate element type: the Python scheduler filters PodMetrics, the
# native scheduler filters survivor INDICES with a name_of mapper — the
# advisor filters below are generic over both.
C = TypeVar("C")


class SchedulingError(Exception):
    """Raised when no pod can serve the request.

    ``shed`` marks the load-shedding drop (reference maps it to gRPC
    RESOURCE_EXHAUSTED -> HTTP 429, server.go:95-113).
    """

    def __init__(self, msg: str, shed: bool = False):
        super().__init__(msg)
        self.shed = shed


class PodMetricsProvider(Protocol):
    """scheduler.go:108-110."""

    def all_pod_metrics(self) -> list[PodMetrics]: ...


def filter_by_policy(advisor: Any, candidates: list[C],
                     name_of: Callable[[C], str] | None = None) -> list[C]:
    """Apply the advisor's health policy over a candidate set.

    The advisor seam (``gateway/resilience.py:ResiliencePlane``) exposes
    ``policy`` + ``should_avoid``; schedulers call this AFTER the filter
    tree, BEFORE the prefix-affinity tie-break and the RNG draw.

    - ``log_only`` (or no advisor / a bare HealthScorer without a policy):
      returns ``candidates`` UNCHANGED — the byte-identical guarantee the
      same-RNG diff tests pin.
    - ``avoid``: the subset the advisor would not avoid; when EVERY
      candidate is avoidable, the full set comes back (last-resort escape
      hatch — a fully-unhealthy pool still serves) and the advisor's
      ``note_escape_hatch`` counter/journal fires.
    - ``strict``: like ``avoid`` but an all-avoidable set sheds
      (``SchedulingError(shed=True)`` -> 429) instead of escaping.

    ``name_of`` maps a candidate to its pod name (defaults to the
    ``PodMetrics`` shape; the native scheduler passes an index mapper).
    """
    if advisor is None or not candidates:
        return candidates
    policy = getattr(advisor, "policy", "log_only")
    if policy == "log_only":
        return candidates
    if name_of is None:
        name_of = lambda pm: pm.pod.name  # noqa: E731
    batch = getattr(advisor, "avoid_set", None)
    if batch is not None:
        bad = batch()  # two lock acquisitions total, not two per pod
        if not bad:
            return candidates
        preferred = [c for c in candidates if name_of(c) not in bad]
    else:
        preferred = [c for c in candidates
                     if not advisor.should_avoid(name_of(c))]
    if preferred:
        return preferred
    if policy == "strict":
        raise SchedulingError(
            "all candidate replicas are unhealthy or circuit-open "
            "(health_policy=strict)", shed=True)
    note = getattr(advisor, "note_escape_hatch", None)
    if note is not None:
        note()
    return candidates


def filter_by_fairness(
    advisor: Any, req: "LLMRequest", candidates: list[C],
    active_of: Callable[[C], Iterable[str]] | None = None,
) -> list[C]:
    """Apply the fairness advisor's pick deprioritization over a candidate
    set (``gateway/fairness.py:FairnessPolicy``); schedulers call this
    AFTER ``filter_by_policy``, BEFORE the prefix tie-break and RNG draw.

    - ``log_only`` (or no advisor / a bare UsageRollup without a mode):
      returns ``candidates`` UNCHANGED — the byte-identical guarantee the
      same-RNG diff tests pin.
    - ``deprioritize`` / ``enforce``: pods hosting a currently-flagged
      noisy adapter are *marked*.  A quiet request narrows to unmarked
      survivors (isolation: the flood can't degrade cotenants on its
      replicas); when EVERY candidate is marked the full set comes back
      and ``note_fairness_escape`` fires — the same counted last-resort
      shape as ``filter_by_policy``.  A request whose OWN key is flagged
      narrows to the marked pods instead (containment: the flood keeps
      its existing replicas but can't claim fresh ones); no marked
      candidate is not an escape — there is nothing to avoid.

    ``active_of`` maps a candidate to its resident-adapter names (defaults
    to the ``PodMetrics`` shape; the native scheduler's candidate indices
    are resolved before this runs, so both paths share this function).
    An advisor exposing ``noisy_pods`` (FairnessPolicy) serves the mark
    set from a per-tick cache instead — one frozenset membership test per
    candidate on the hot path (the <5% ``pick_fairness_ratio`` bound).
    """
    if advisor is None or not candidates:
        return candidates
    if getattr(advisor, "mode", "log_only") == "log_only":
        return candidates
    flagged = advisor.noisy()
    if not flagged:
        return candidates
    get_marked = getattr(advisor, "noisy_pods", None)
    marked = get_marked() if get_marked is not None else None
    if marked is not None:
        hosts = [c.pod.name in marked for c in candidates]
    else:
        if active_of is None:
            active_of = lambda pm: pm.metrics.active_adapters  # noqa: E731
        hosts = [any(a in flagged for a in active_of(c))
                 for c in candidates]
    if req.model in flagged:
        preferred = [c for c, h in zip(candidates, hosts) if h]
        return preferred or candidates
    preferred = [c for c, h in zip(candidates, hosts) if not h]
    if preferred:
        return preferred
    note = getattr(advisor, "note_fairness_escape", None)
    if note is not None:
        note()
    return candidates


def filter_by_placement(
    advisor: Any, req: "LLMRequest", candidates: list[C],
    name_of: Callable[[C], str] | None = None,
) -> list[C]:
    """Apply the placement plane's residency steering over a candidate
    set (``gateway/placement.py:PlacementPlanner``); schedulers call this
    AFTER ``filter_by_fairness``, BEFORE the prefix tie-break and RNG
    draw.

    - ``log_only`` (or no advisor): returns ``candidates`` UNCHANGED —
      the byte-identical guarantee the same-RNG diff tests pin (the
      advisor's ``note_pick`` still counts would-steer picks).
    - ``prefer_resident``: narrows to pods where the request's adapter is
      RAM-resident, slot tier winning ties over host tier (a slot pick
      decodes immediately, a host pick pays the promote's device put, a
      cold pick pays the full Orbax restore); when the adapter IS
      resident somewhere but on NO candidate, the full set comes back and
      ``note_placement_escape`` fires — the same counted last-resort
      shape as the health/fairness filters.  An adapter resident NOWHERE
      (cold tail, base-model traffic) is not an escape: there is nothing
      to steer toward, and the planner's prefetch rule — not the pick
      seam — owns it.  A pool exporting no residency data at all
      (``resident_pods`` returns None) likewise leaves the set untouched.
    """
    if advisor is None or not candidates:
        return candidates
    if getattr(advisor, "mode", "log_only") != "prefer_resident":
        return candidates
    get_tiers = getattr(advisor, "resident_tiers", None)
    if get_tiers is not None:
        tiers = get_tiers(req.resolved_target_model)
        slot_set, host_set = tiers if tiers is not None \
            else (frozenset(), frozenset())
    else:  # flat advisor (tests/fakes): one tier, no slot preference
        slot_set = advisor.resident_pods(req.resolved_target_model) \
            or frozenset()
        host_set = frozenset()
    if not slot_set and not host_set:
        return candidates
    # One pass, both tiers (this filter rides the pick hot path).
    slot_pref: list = []
    host_pref: list = []
    if name_of is None:
        for c in candidates:
            name = c.pod.name
            if name in slot_set:
                slot_pref.append(c)
            elif name in host_set:
                host_pref.append(c)
    else:
        for c in candidates:
            name = name_of(c)
            if name in slot_set:
                slot_pref.append(c)
            elif name in host_set:
                host_pref.append(c)
    preferred = slot_pref or host_pref
    if preferred:
        return preferred
    note = getattr(advisor, "note_placement_escape", None)
    if note is not None:
        note()
    return candidates


def _drop_filter() -> Filter:
    def drop(req: LLMRequest, pods: Sequence[PodMetrics]) -> list[PodMetrics]:
        raise FilterError(
            "dropping request due to limited backend resources", shed=True
        )

    return Filter(name="drop request", func=drop)


def build_default_tree(
    cfg: SchedulerConfig = DEFAULT_CONFIG,
    token_aware: bool = False,
    prefill_aware: bool = False,
) -> Filter:
    """Construct the reference decision tree (scheduler.go:26-91)."""
    preds = make_predicates(cfg)

    def queue_filter(tail: Filter | None) -> Filter:
        """Queue-depth stage ending in ``tail``.

        With ``prefill_aware`` the stage is prefill-queue bucketing followed by
        total-queue bucketing; the tail is attached to the *last* node so later
        wiring can't clobber the inner chain.
        """
        least_queue = Filter(
            name="least queuing",
            func=least_queuing_filter,
            next_on_success_or_failure=tail,
        )
        if prefill_aware:
            return Filter(
                name="least prefill queuing",
                func=least_prefill_queue_filter,
                next_on_success_or_failure=least_queue,
            )
        return least_queue

    def with_token_headroom(inner: Filter) -> Filter:
        if not token_aware:
            return inner
        return Filter(
            name="token headroom",
            func=to_filter_func(preds["token_headroom"], "token_headroom"),
            next_on_success=inner,
            next_on_failure=inner,  # headroom is advisory: fall back, don't fail
        )

    # queueLoRAAndKVCacheFilter (scheduler.go:35-46)
    queue_lora_kv = queue_filter(
        Filter(
            name="low cost LoRA",
            func=to_filter_func(preds["low_lora_cost"], "low_lora_cost"),
            next_on_success_or_failure=Filter(
                name="least KV cache percent", func=least_kv_cache_filter
            ),
        )
    )

    # queueAndKVCacheFilter (scheduler.go:49-56)
    queue_kv = queue_filter(
        Filter(name="least KV cache percent", func=least_kv_cache_filter)
    )

    # lowLatencyFilter (scheduler.go:58-72)
    low_latency = Filter(
        name="low queueing filter",
        func=to_filter_func(preds["low_queueing"], "low_queueing"),
        next_on_success=Filter(
            name="affinity LoRA",
            func=to_filter_func(preds["lora_affinity"], "lora_affinity"),
            next_on_success=queue_kv,
            next_on_failure=Filter(
                name="can accept LoRA Adapter",
                func=to_filter_func(preds["can_accept_new_lora"], "can_accept_new_lora"),
                next_on_success_or_failure=queue_kv,
            ),
        ),
        next_on_failure=queue_lora_kv,
    )

    # sheddableRequestFilter (scheduler.go:74-90)
    sheddable = Filter(
        name="has capacity for sheddable requests",
        func=to_filter_func(preds["sheddable_admission"], "sheddable_admission"),
        next_on_success=queue_lora_kv,
        next_on_failure=_drop_filter(),
    )

    # defaultFilter (scheduler.go:27-32)
    return Filter(
        name="critical request",
        func=to_filter_func(preds["critical_request"], "critical_request"),
        next_on_success=with_token_headroom(low_latency),
        next_on_failure=with_token_headroom(sheddable),
    )


def build_decode_tree(
    cfg: SchedulerConfig = DEFAULT_CONFIG,
    token_aware: bool = True,
) -> Filter:
    """Decode-hop stage for disaggregated pools: KV headroom first (the
    decode replica holds this request's KV for its WHOLE lifetime — the
    signal that gates TPOT stability), then total queue depth.  Prefill
    signals are irrelevant here: a decode-role replica admits handoffs
    straight into decode slots and its prefill queue stays empty."""
    preds = make_predicates(cfg)
    kv_then_queue = Filter(
        name="least KV cache percent",
        func=least_kv_cache_filter,
        next_on_success_or_failure=Filter(
            name="least queuing", func=least_queuing_filter),
    )
    if not token_aware:
        return kv_then_queue
    return Filter(
        name="token headroom",
        func=to_filter_func(preds["token_headroom"], "token_headroom"),
        next_on_success=kv_then_queue,
        next_on_failure=kv_then_queue,  # advisory: fall back, don't fail
    )


def split_pool_roles(
    pods: Sequence[PodMetrics],
) -> tuple[list[PodMetrics], list[PodMetrics]]:
    """(prefill-role, decode-role) partitions; collocated pods are in
    neither (they serve single-hop traffic)."""
    prefills = [pm for pm in pods if pod_role(pm.pod) == ROLE_PREFILL]
    decodes = [pm for pm in pods if pod_role(pm.pod) == ROLE_DECODE]
    return prefills, decodes


class Scheduler:
    """scheduler.go:93-122, with configurable thresholds and TPU options."""

    def __init__(
        self,
        pod_metrics_provider: PodMetricsProvider,
        cfg: SchedulerConfig = DEFAULT_CONFIG,
        token_aware: bool = True,
        prefill_aware: bool = True,
        prefix_aware: bool = True,
        prefix_index: "PrefixIndex | None" = None,
        rng: random.Random | None = None,
        tree: Filter | None = None,
    ) -> None:
        self._provider = pod_metrics_provider
        self.cfg = cfg
        self._token_aware = token_aware
        self._prefill_aware = prefill_aware
        # Prefix-cache-aware tie-break (scheduling/prefix_affinity.py),
        # applied AFTER the tree over its survivor set — identical seam in
        # the native scheduler, so the two implementations stay
        # parity-comparable.  Inert until requests carry prefix_hashes AND
        # a prefix repeats.  ``prefix_index`` injects a SHARED index when
        # several scheduler instances route one pool (e.g. the admission
        # controller's drain scheduler) — split indexes would learn
        # conflicting holders and flap.  prefix_aware=False disables the
        # tie-break even with an injected index (the flag is the OFF
        # switch; the index argument only chooses whose state to share).
        self.prefix_index = prefix_index if prefix_aware else None
        if prefix_aware and self.prefix_index is None:
            from llm_instance_gateway_tpu.gateway.scheduling.prefix_affinity import (
                PrefixIndex,
            )

            self.prefix_index = PrefixIndex()
        self._custom_tree = tree is not None
        self._tree = tree or build_default_tree(
            cfg, token_aware=token_aware, prefill_aware=prefill_aware
        )
        # Decode-hop stage for disaggregated pools (role-split replicas);
        # inert while every pod is collocated.
        self._decode_tree = build_decode_tree(cfg, token_aware=token_aware)
        self._rng = rng or random.Random()
        # Health/resilience hook (set by the proxy).  With the default
        # ``log_only`` policy ``note_pick`` only counts would-be avoidance
        # decisions into tpu:health_would_avoid_total — no RNG draws, no
        # filtering, routing byte-identical to a scheduler without the
        # hook (pinned by the same-RNG diff tests).  With ``avoid`` /
        # ``strict`` (gateway/resilience.py) the survivor set additionally
        # passes through ``filter_by_policy`` before the tie-break/draw.
        self.health_advisor: Any = None
        # Usage/fairness seam (gateway/usage.py + gateway/fairness.py, set
        # by the proxy).  A bare UsageRollup (or a FairnessPolicy in
        # ``log_only``) only counts flagged picks into
        # gateway_usage_would_deprioritize_total — no RNG, no filtering,
        # routing byte-identical (pinned by same-RNG diff tests).  A
        # FairnessPolicy in ``deprioritize``/``enforce`` additionally runs
        # the survivor set through ``filter_by_fairness`` after the health
        # policy filter and before the tie-break/draw.
        self.usage_advisor: Any = None
        # Placement seam (gateway/placement.py, set by the proxy).  A
        # PlacementPlanner in ``log_only`` only counts picks that missed
        # a resident replica (gateway_placement_would_steer_total) —
        # routing byte-identical, pinned by same-RNG diff tests.  In
        # ``prefer_resident`` the survivor set additionally passes through
        # ``filter_by_placement`` after the fairness filter.
        self.placement_advisor: Any = None
        # Decision-ledger seam (gateway/pickledger.py, set by the proxy).
        # Sampling is a counter modulus — no RNG draws, no filtering —
        # so routing stays byte-identical with the ledger attached
        # (pinned by same-RNG diff tests); all record/counterfactual
        # work rides sampled picks only.
        self.pick_ledger: Any = None

    def update_config(self, cfg: SchedulerConfig) -> None:
        """Swap thresholds at runtime (pool hot-reload); rebuilds the tree.

        A caller-injected custom tree is left untouched — thresholds belong
        to the default tree; silently replacing a custom policy on reload
        would be a worse surprise than ignoring the new numbers.
        """
        self.cfg = cfg
        if self._custom_tree:
            import logging

            logging.getLogger(__name__).warning(
                "scheduler has a custom filter tree; ignoring threshold reload"
            )
            return
        self._tree = build_default_tree(
            cfg, token_aware=self._token_aware,
            prefill_aware=self._prefill_aware,
        )
        self._decode_tree = build_decode_tree(
            cfg, token_aware=self._token_aware)

    def _survivors(self, req: LLMRequest,
                   pods: Sequence[PodMetrics]) -> list[PodMetrics]:
        try:
            survivors = self._tree.filter(req, pods)
        except FilterError as e:
            raise SchedulingError(
                f"failed to apply filter, resulted 0 pods: {e}", shed=e.shed
            ) from e
        if not survivors:
            raise SchedulingError("failed to apply filter, resulted 0 pods")
        return survivors

    def _pick(self, req: LLMRequest, survivors: Sequence[PodMetrics],
              hop: str = "single", pool_n: int = 0, role_n: int = 0) -> Pod:
        # Enforcing health policy narrows the candidate set FIRST, so the
        # prefix-affinity tie-break can't pin a request to an avoided
        # holder (log_only returns the set unchanged); fairness
        # deprioritization runs over whatever survives it.
        ledger = self.pick_ledger
        sampled = ledger is not None and ledger.sampled()
        base = survivors
        if sampled:
            escape_base = ledger.escape_counters(
                self.health_advisor, self.usage_advisor,
                self.placement_advisor)
            base = list(survivors)  # pin the funnel head for the record
        post_health = filter_by_policy(self.health_advisor, base)
        post_fairness = filter_by_fairness(self.usage_advisor, req,
                                           post_health)
        final = filter_by_placement(self.placement_advisor, req,
                                    post_fairness)
        pick = None
        tie_break = False
        if self.prefix_index is not None and req.prefix_hashes:
            held = self.prefix_index.prefer(req, final)
            if held is not None:
                pick = held.pod
                tie_break = True
        if pick is None:
            pick = final[self._rng.randrange(len(final))].pod
        if self.prefix_index is not None and req.prefix_hashes:
            # The pick is about to prefill (and, with the engine's prefix
            # cache on, retain) this prefix: future lookups route here.
            self.prefix_index.record(req.prefix_hashes, pick.name)
        if self.health_advisor is not None:
            self.health_advisor.note_pick(pick.name)
        if self.usage_advisor is not None:
            self.usage_advisor.note_pick(pick.name, req.model)
        if self.placement_advisor is not None:
            self.placement_advisor.note_pick(
                pick.name, req.resolved_target_model)
        if sampled:
            ledger.charge(
                req, winner=pick.name, base=base, post_health=post_health,
                post_fairness=post_fairness, post_placement=final,
                hop=hop, path="python", pool_n=pool_n, role_n=role_n,
                tie_break=tie_break,
                advisors=(self.health_advisor, self.usage_advisor,
                          self.placement_advisor),
                escape_base=escape_base, trace_id=req.trace_id)
        return pick

    def schedule(self, req: LLMRequest) -> Pod:
        pods = self._provider.all_pod_metrics()
        # Role-split pools: single-hop traffic stays off the specialized
        # replicas when collocated ones exist (a decode replica serving a
        # full request would prefill on its decode-critical MXU); in a
        # FULLY split pool single-hop is the degraded fallback and any
        # replica can take it (roles are advisory, engines are complete).
        collocated = [pm for pm in pods
                      if pod_role(pm.pod) == ROLE_COLLOCATED]
        role_set = collocated or list(pods)
        return self._pick(req, self._survivors(req, role_set),
                          pool_n=len(pods), role_n=len(role_set))

    def schedule_disaggregated(
        self, req: LLMRequest
    ) -> tuple[Pod, Pod | None]:
        """Two-stage routing for disaggregated pools.

        Returns ``(prefill_pod, decode_pod)``: the prefill replica is
        picked by the FULL decision tree over the prefill-role set (its
        prefill-queue/TTFT stages are exactly the signals that matter for
        hop 1, and prefix affinity applies here — that is where prefill
        reuse lives), then the decode replica by KV-headroom/queue signals
        over the decode-role set (``build_decode_tree``).  Pools without
        both roles fall back to single-hop: ``(schedule(req), None)``.
        """
        pods = self._provider.all_pod_metrics()
        prefills, decodes = split_pool_roles(pods)
        if not prefills or not decodes:
            return self.schedule(req), None
        t0 = time.perf_counter()
        prefill_pod = self._pick(req, self._survivors(req, prefills),
                                 hop="prefill", pool_n=len(pods),
                                 role_n=len(prefills))
        t1 = time.perf_counter()
        ledger = self.pick_ledger
        sampled = ledger is not None and ledger.sampled()
        if sampled:
            escape_base = ledger.escape_counters(
                self.health_advisor, self.usage_advisor,
                self.placement_advisor)
        try:
            decode_base = self._decode_tree.filter(req, decodes)
        except FilterError as e:
            raise SchedulingError(
                f"no decode replica for disaggregated request: {e}",
                shed=e.shed) from e
        decode_health = filter_by_policy(self.health_advisor, decode_base)
        decode_fairness = filter_by_fairness(
            self.usage_advisor, req, decode_health)
        decode_survivors = filter_by_placement(
            self.placement_advisor, req, decode_fairness)
        decode_pod = decode_survivors[
            self._rng.randrange(len(decode_survivors))].pod
        if self.health_advisor is not None:
            self.health_advisor.note_pick(decode_pod.name)
        if self.usage_advisor is not None:
            self.usage_advisor.note_pick(decode_pod.name, req.model)
        if self.placement_advisor is not None:
            self.placement_advisor.note_pick(
                decode_pod.name, req.resolved_target_model)
        if sampled:
            ledger.charge(
                req, winner=decode_pod.name, base=decode_base,
                post_health=decode_health, post_fairness=decode_fairness,
                post_placement=decode_survivors, hop="decode",
                path="python", pool_n=len(pods), role_n=len(decodes),
                advisors=(self.health_advisor, self.usage_advisor,
                          self.placement_advisor),
                escape_base=escape_base, trace_id=req.trace_id)
        # Per-hop pick split for the tracing layer (the admission span's
        # attribution of "pick" into prefill-hop vs decode-hop cost).
        req.pick_hops_s = (t1 - t0, time.perf_counter() - t1)
        return prefill_pod, decode_pod
