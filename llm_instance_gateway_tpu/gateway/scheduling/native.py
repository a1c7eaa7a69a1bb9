"""ctypes binding for the C++ scheduler hot path (native/scheduler.cc).

``NativeScheduler`` is a drop-in for ``Scheduler`` — identical decision-tree
semantics (fuzz-verified against the Python tree), with candidate-set
computation in C++ and the final random pick kept in Python so RNG behavior
matches.  Falls back transparently when the shared library can't be built
(``available()`` is False); callers should construct via ``make_scheduler``.

Snapshot-resident fast path (the data-plane tentpole): the whole routable
world — pod metric arrays, the health/circuit avoid-set, the adapter
residency table, usage-deprioritization marks, and the threshold config —
is marshalled into a native ``State`` handle ONCE per provider snapshot
version (i.e. at scrape cadence), not per pick.  The per-pick FFI crossing
then carries only request scalars (interned adapter id, critical,
prompt_tokens) and reads the candidate set out of a persistent buffer; the
RNG draw stays in Python, so picks are byte-identical to the Python
``Scheduler`` parity oracle (same-RNG diff tests).  ``pick_many`` batches N
requests into one crossing for the bench/load rigs.

Fallback-to-Python rules: no library -> ``make_scheduler`` returns the
Python ``Scheduler``; a provider without ``snapshot()`` (or a role-filtered
subset) has no version to key the resident state on, so the state is
re-marshalled per pick — semantics identical, amortization lost.

The library auto-builds on first use via the Makefile next to the source —
the image ships g++/make, and the build is one translation unit (<1 s).
"""

from __future__ import annotations

import ctypes
import logging
import os
import random
import threading
import weakref

import numpy as np

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.gateway.scheduling.config import (
    DEFAULT_CONFIG,
    SchedulerConfig,
)
from llm_instance_gateway_tpu.gateway.scheduling.filter import FilterError
from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
    PodMetricsProvider,
    Scheduler,
    SchedulingError,
    build_decode_tree,
    build_default_tree,
    filter_by_fairness,
    filter_by_placement,
    filter_by_policy,
    split_pool_roles,
)
from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
from llm_instance_gateway_tpu.gateway.types import (
    ROLE_COLLOCATED,
    Pod,
    PodMetrics,
    pod_role,
)

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libligsched.so")
# Must match scheduler.cc's lig_abi_version() — bumped on any exported-
# signature change so a stale prebuilt .so is refused, not miscalled.
# `make lint` (abi-drift rule) cross-checks the argtypes below against the
# C signatures and the checked-in lint/abi_baseline.json fingerprint.
_ABI_VERSION = 4
# Override the library path (e.g. the ASan/UBSan-instrumented build from
# `make native-asan`); the builder/staleness dance is skipped for overrides
# — the caller owns the file.
_LIB_ENV = "LIG_NATIVE_LIB"

LIG_SHED = -1
LIG_ERROR = -2
LIG_SHED_STRICT = -3

# filter_by_policy parity: the policy string marshals to a native mode code
# at snapshot-update time (log_only never filters natively either).
_POLICY_CODE = {"log_only": 0, "avoid": 1, "strict": 2}
# filter_by_fairness parity: deprioritize and enforce share the pick-seam
# narrowing; enforce's extra semantics (admission quotas) live entirely in
# Python (gateway/fairness.py), so the native code is binary.
_FAIRNESS_CODE = {"log_only": 0, "deprioritize": 1, "enforce": 1}
# filter_by_placement parity: log_only marshals no marks (note_pick stays
# in Python over the planner's own map — routing byte-identical).
_PLACEMENT_CODE = {"log_only": 0, "prefer_resident": 1}

_SHED_MSG = ("failed to apply filter, resulted 0 pods: dropping request due "
             "to limited backend resources")
_STRICT_MSG = ("all candidate replicas are unhealthy or circuit-open "
               "(health_policy=strict)")

_lib = None
_lib_lock = threading.Lock()

_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_f64p = ctypes.POINTER(ctypes.c_double)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def _load_library():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib_path = os.environ.get(_LIB_ENV) or _LIB_PATH
        if not os.environ.get(_LIB_ENV):
            from llm_instance_gateway_tpu.utils.native_build import (
                ensure_native_lib,
            )

            if ensure_native_lib(_NATIVE_DIR, "libligsched.so",
                                 "scheduler.cc") is None:
                return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            logger.warning("native scheduler load failed: %s", e)
            return None
        try:
            # Version handshake BEFORE any argtype wiring: a prebuilt .so
            # from an older tree can pass the mtime staleness check, and
            # the AttributeError guard below only catches MISSING symbols
            # — an arity change on an existing one would scramble
            # arguments in the routing hot path.  Mismatch (or a pre-
            # handshake library without the symbol) falls back to Python.
            lib.lig_abi_version.restype = ctypes.c_int32
            lib.lig_abi_version.argtypes = []
            abi = lib.lig_abi_version()
            if abi != _ABI_VERSION:
                logger.warning(
                    "native scheduler ABI %d != expected %d; "
                    "falling back to Python", abi, _ABI_VERSION)
                return None
            lib.lig_state_new.restype = ctypes.c_void_p
            lib.lig_state_new.argtypes = []
            lib.lig_state_free.restype = None
            lib.lig_state_free.argtypes = [ctypes.c_void_p]
            lib.lig_state_update.restype = ctypes.c_int32
            lib.lig_state_update.argtypes = [
                ctypes.c_void_p, ctypes.c_int32,
                _i32p, _i32p, _f64p, _i64p, _i64p,  # waiting..kv_capacity
                _i32p, _i32p,                       # n_active, max_active
                _u8p,                               # avoid marks
                ctypes.c_int32, _i32p, _i32p,       # adapters CSR
                ctypes.c_int32,                     # res_ids length (v4)
                _u8p,                               # adapter noisy marks
                _i32p, _i32p,                       # placement CSR: offsets,
                ctypes.c_int32,                     # ids + length (v4),
                _u8p, _u8p,                         # tier codes, any bits
                ctypes.c_double, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_double, ctypes.c_int32,
                ctypes.c_uint8, ctypes.c_uint8,     # token/prefill aware
                ctypes.c_uint8, ctypes.c_uint8,     # policy/fairness modes
                ctypes.c_uint8,                     # placement mode
            ]
            lib.lig_pick.restype = ctypes.c_int32
            lib.lig_pick.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint8,
                ctypes.c_uint8, ctypes.c_int64, _i32p, _u8p,
            ]
            lib.lig_pick_many.restype = ctypes.c_int32
            lib.lig_pick_many.argtypes = [
                ctypes.c_void_p, ctypes.c_int32,
                _i32p, _u8p, _u8p,    # adapter_ids, criticals, req_noisies
                _i64p,                # prompt_tokens
                _i32p, _i32p, _u8p,   # out_counts, out_cands, out_flags
            ]
        except AttributeError as e:
            # A stale .so predating the snapshot API: rebuildable hosts get
            # a fresh build on the next ensure; meanwhile fall back.
            logger.warning("native scheduler ABI mismatch: %s", e)
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load_library() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class _NativeState:
    """One native snapshot handle + the Python-side cache keys guarding it."""

    __slots__ = ("handle", "key", "avoid", "noisy", "placed", "out",
                 "intern", "_finalizer", "__weakref__")

    def __init__(self, lib):
        self.handle = lib.lig_state_new()
        if not self.handle:
            raise RuntimeError("lig_state_new failed")
        self.key = None          # (version, n_pods, policy, fairness,
        #                           placement, cfg_gen)
        self.avoid = None        # frozenset marshalled into the avoid marks
        self.noisy = frozenset()  # noisy names marshalled into the marks
        self.placed = None       # resident map marshalled into the
        #                           placement marks (identity-compared: the
        #                           planner swaps the dict whole per tick)
        self.out = np.empty(0, np.int32)  # persistent candidate buffer
        # Adapter interning for THIS state's residency CSR: name -> dense
        # id, rebuilt from scratch at every marshal so the table (and the
        # native bitmap sized from it) stays bounded by the adapters
        # actually resident in the snapshot — never by historical churn.
        # A request adapter absent from the table was not resident on any
        # pod at snapshot time (id -1: no affinity anywhere) — exactly the
        # Python tree's view of the same snapshot.
        self.intern: dict[str, int] = {}
        self._finalizer = weakref.finalize(
            self, lib.lig_state_free, self.handle)


class NativeScheduler:
    """Same interface as Scheduler.schedule; C++ candidate computation over
    a snapshot-resident native state."""

    def __init__(
        self,
        pod_metrics_provider: PodMetricsProvider,
        cfg: SchedulerConfig = DEFAULT_CONFIG,
        token_aware: bool = True,
        prefill_aware: bool = True,
        prefix_aware: bool = True,
        prefix_index=None,
        rng: random.Random | None = None,
    ):
        lib = _load_library()
        if lib is None:
            raise RuntimeError("native scheduler library unavailable")
        self._lib = lib
        self._provider = pod_metrics_provider
        self.cfg = cfg
        self.token_aware = token_aware
        self.prefill_aware = prefill_aware
        # Same post-tree prefix-affinity tie-break as the Python Scheduler
        # (scheduling/prefix_affinity.py): applied over the C++ candidate
        # set, so the fuzz-pinned candidate parity is untouched.
        # ``prefix_index`` shares one index across scheduler instances
        # routing the same pool; prefix_aware=False disables the tie-break
        # even with an injected index (see Scheduler.__init__).
        self.prefix_index = prefix_index if prefix_aware else None
        if prefix_aware and self.prefix_index is None:
            from llm_instance_gateway_tpu.gateway.scheduling.prefix_affinity import (
                PrefixIndex,
            )

            self.prefix_index = PrefixIndex()
        self._rng = rng or random.Random()
        # Decode-hop stage for disaggregated pools: the tiny Python tree
        # (2-3 filters over the decode-role subset) — not worth an FFI
        # seam, and it keeps the fuzz-pinned C++ candidate parity for the
        # main tree untouched.
        self._decode_tree = build_decode_tree(cfg, token_aware=token_aware)
        # Python-oracle tree for the pick ledger's shadow replay: sampled
        # native picks are EXPLAINED by re-running this tree + the silent
        # advisor chain in Python (gateway/pickledger.py) — the FFI hot
        # path never grows a crossing for observability.  Inert until a
        # ledger is attached.
        self._oracle_tree = build_default_tree(
            cfg, token_aware=token_aware, prefill_aware=prefill_aware)
        # Snapshot-resident native state: ``_state`` is keyed on the
        # provider's monotonic snapshot version (plus policy/config
        # generations) and re-marshalled only when one of them moves;
        # ``_scratch`` serves version-less calls (role subsets, the legacy
        # candidates() API) where there is nothing to key a cache on.
        self._state = _NativeState(lib)
        self._scratch = _NativeState(lib)
        self._cfg_gen = 0
        # (version, pods-after-role-policy, effective version) — see
        # _routable_pods.
        self._role_cache: tuple | None = None
        # The gRPC transport calls schedule() from a thread pool; the
        # native state handles and persistent buffers are shared state.
        self._call_lock = witness_lock("NativeScheduler._call_lock")
        # Health/resilience hook (gateway/resilience.py) — same seam as
        # the Python Scheduler: log_only counts would-be avoidance picks
        # and never alters the pick (candidate parity with C++ stays
        # exact); avoid/strict marshal the advisor's avoid_set into the
        # native snapshot so policy filtering costs zero extra crossings.
        self.health_advisor = None
        # Usage/fairness seam (gateway/usage.py + gateway/fairness.py) —
        # same contract as the Python Scheduler's usage_advisor.  The
        # noisy marks ride the native snapshot (per-adapter bits + per-pod
        # hog bits, refreshed whenever the advisor's noisy set moves); a
        # FairnessPolicy in deprioritize/enforce narrows candidates
        # NATIVELY (filter_by_fairness parity, fairness escape on flag
        # bit 2), while log_only keeps byte-exact parity with the Python
        # path and only counts flagged picks.
        self.usage_advisor = None
        # Placement seam (gateway/placement.py) — same contract as the
        # Python Scheduler's placement_advisor.  prefer_resident marshals
        # the planner's resident map into the snapshot (per-adapter pod
        # bits + pool-wide "resident anywhere" bits, so the escape-hatch
        # condition matches the Python filter exactly); log_only marshals
        # nothing and keeps byte-exact parity, note_pick counting in
        # Python over the planner's own map.
        self.placement_advisor = None
        # Decision-ledger seam (gateway/pickledger.py) — same contract as
        # the Python Scheduler's pick_ledger: counter-modulus sampling
        # (no RNG, no filtering, routing byte-identical), with sampled
        # picks explained via the Python-oracle shadow replay above.
        self.pick_ledger = None

    # -- marshalling --------------------------------------------------------
    def _policy_and_avoid(self) -> tuple[str, frozenset]:
        """The advisor's current policy + avoid-set (both cheap cached
        reads on the ResiliencePlane).  log_only marshals no marks."""
        advisor = self.health_advisor
        if advisor is None:
            return "log_only", frozenset()
        policy = getattr(advisor, "policy", "log_only")
        if policy == "log_only":
            return policy, frozenset()
        batch = getattr(advisor, "avoid_set", None)
        if batch is not None:
            return policy, frozenset(batch())
        return policy, None  # per-pod should_avoid: no cheap change signal

    def _fairness_and_noisy(self) -> tuple[str, frozenset]:
        """The usage advisor's fairness mode + live noisy-name set (both
        cheap cached reads on the FairnessPolicy/UsageRollup).  A bare
        rollup has no mode — log_only, marks still marshalled for the
        flag-bit observable."""
        usage = self.usage_advisor
        if usage is None:
            return "log_only", frozenset()
        mode = getattr(usage, "mode", "log_only")
        if mode not in _FAIRNESS_CODE:
            mode = "log_only"
        get_noisy = getattr(usage, "noisy", None)
        noisy = frozenset(get_noisy()) if get_noisy is not None \
            else frozenset()
        return mode, noisy

    def _placement_and_map(self) -> tuple[str, dict | None]:
        """The placement advisor's mode + resident map (adapter ->
        frozenset of pod names; swapped whole per planner tick, so object
        identity is the staleness signal).  log_only — or a pool with no
        residency data — marshals no marks."""
        advisor = self.placement_advisor
        if advisor is None:
            return "log_only", None
        mode = getattr(advisor, "mode", "log_only")
        if mode not in _PLACEMENT_CODE or _PLACEMENT_CODE[mode] == 0:
            return "log_only", None
        get_map = getattr(advisor, "resident_map", None)
        rmap = get_map() if get_map is not None else None
        if rmap is None:
            return "log_only", None
        return mode, rmap

    def _marshal(self, state: _NativeState, pods: list[PodMetrics],
                 policy: str, bad: frozenset | None, fairness: str,
                 noisy_names: frozenset, placement: str = "log_only",
                 resident_map: dict | None = None) -> None:
        """Push the full routable world into ``state`` (tick-time cost)."""
        n = len(pods)
        waiting = np.fromiter(
            (pm.metrics.total_queue_size for pm in pods), np.int32, n)
        prefill = np.fromiter(
            (pm.metrics.prefill_queue_size for pm in pods), np.int32, n)
        kv_usage = np.fromiter(
            (pm.metrics.kv_cache_usage_percent for pm in pods), np.float64, n)
        kv_free = np.fromiter(
            (pm.metrics.kv_tokens_free for pm in pods), np.int64, n)
        kv_capacity = np.fromiter(
            (pm.metrics.kv_tokens_capacity for pm in pods), np.int64, n)
        n_active = np.fromiter(
            (len(pm.metrics.active_adapters) for pm in pods), np.int32, n)
        max_active = np.fromiter(
            (pm.metrics.max_active_adapters for pm in pods), np.int32, n)
        if bad is None:
            advisor = self.health_advisor
            avoid = np.fromiter(
                (advisor.should_avoid(pm.pod.name) for pm in pods),
                np.uint8, n)
        elif bad:
            avoid = np.fromiter(
                (pm.pod.name in bad for pm in pods), np.uint8, n)
        else:
            avoid = np.zeros(n, np.uint8)
        # Adapter residency as CSR, interning names to dense ids.  The
        # table is rebuilt per marshal (see _NativeState.intern): only the
        # adapters resident in THIS snapshot get ids, so the native bitmap
        # never grows with historical adapter churn.
        table: dict[str, int] = {}
        offsets = np.empty(n + 1, np.int32)
        ids: list[int] = []
        for i, pm in enumerate(pods):
            offsets[i] = len(ids)
            for name in pm.metrics.active_adapters:
                aid = table.get(name)
                if aid is None:
                    aid = table[name] = len(table)
                ids.append(aid)
        offsets[n] = len(ids)
        res_ids = np.asarray(ids, dtype=np.int32)
        # Placement marks (prefer_resident only): the planner's resident
        # map becomes a second CSR over the SAME intern table — names
        # resident somewhere but active nowhere still intern, so a request
        # for a demotable-but-idle adapter resolves an id.  placed_any
        # carries the POOL-wide resident bit: an adapter whose only homes
        # are outside this pods list still escapes (Python filter parity).
        placed_lists: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        placement_code = _PLACEMENT_CODE.get(placement, 0)
        if placement_code and resident_map:
            pod_index = {pm.pod.name: i for i, pm in enumerate(pods)}
            for adapter_name, (slot_pods, host_pods) in resident_map.items():
                aid = table.get(adapter_name)
                if aid is None:
                    aid = table[adapter_name] = len(table)
                for tier_code, pod_names in ((2, slot_pods), (1, host_pods)):
                    for pod_name in pod_names:
                        i = pod_index.get(pod_name)
                        if i is not None:
                            placed_lists[i].append((aid, tier_code))
        n_adapters = len(table)
        placed_offsets = np.empty(n + 1, np.int32)
        placed_flat: list[int] = []
        placed_tier_flat: list[int] = []
        for i in range(n):
            placed_offsets[i] = len(placed_flat)
            for aid, tier_code in placed_lists[i]:
                placed_flat.append(aid)
                placed_tier_flat.append(tier_code)
        placed_offsets[n] = len(placed_flat)
        placed_ids = np.asarray(placed_flat, dtype=np.int32)
        placed_tiers = np.asarray(placed_tier_flat, dtype=np.uint8)
        placed_any = np.zeros(max(1, n_adapters), np.uint8)
        if placement_code and resident_map:
            for adapter_name, (slot_pods, host_pods) in resident_map.items():
                if slot_pods or host_pods:
                    placed_any[table[adapter_name]] = 1
        noisy = np.zeros(max(1, n_adapters), np.uint8)
        for name in noisy_names:
            aid = table.get(name)
            if aid is not None:
                noisy[aid] = 1
        rc = self._lib.lig_state_update(
            self._void(state), n,
            _ptr(waiting, ctypes.c_int32), _ptr(prefill, ctypes.c_int32),
            _ptr(kv_usage, ctypes.c_double), _ptr(kv_free, ctypes.c_int64),
            _ptr(kv_capacity, ctypes.c_int64),
            _ptr(n_active, ctypes.c_int32), _ptr(max_active, ctypes.c_int32),
            _ptr(avoid, ctypes.c_uint8),
            n_adapters, _ptr(offsets, ctypes.c_int32),
            _ptr(res_ids, ctypes.c_int32), len(res_ids),
            _ptr(noisy, ctypes.c_uint8),
            _ptr(placed_offsets, ctypes.c_int32),
            _ptr(placed_ids, ctypes.c_int32), len(placed_ids),
            _ptr(placed_tiers, ctypes.c_uint8),
            _ptr(placed_any, ctypes.c_uint8),
            self.cfg.kv_cache_threshold,
            self.cfg.queue_threshold_critical,
            self.cfg.queueing_threshold_lora,
            self.cfg.token_headroom_factor,
            self.cfg.prefill_queue_threshold,
            1 if self.token_aware else 0,
            1 if self.prefill_aware else 0,
            _POLICY_CODE.get(policy, 0),
            _FAIRNESS_CODE.get(fairness, 0),
            placement_code,
        )
        if rc != 0:
            raise SchedulingError(f"native state update failed ({rc})")
        if state.out.shape[0] < n:
            state.out = np.empty(n, np.int32)
        state.avoid = bad
        state.noisy = noisy_names
        state.placed = resident_map if placement_code else None
        state.intern = table

    @staticmethod
    def _void(state: _NativeState):
        return ctypes.c_void_p(state.handle)

    def _ensure_state(self, version, pods: list[PodMetrics],
                      policy_mode: bool = True) -> _NativeState:
        """Return a marshalled state for ``pods``.

        With a real snapshot ``version`` the resident state is reused until
        the provider version, the scheduler config, or the advisor's
        avoid-set moves — the tick-time handshake that makes the per-pick
        call carry request scalars only.  Version-less calls (role subsets,
        ad-hoc pods lists) marshal the scratch handle every time.
        """
        if policy_mode:
            policy, bad = self._policy_and_avoid()
            fairness, noisy = self._fairness_and_noisy()
            placement, rmap = self._placement_and_map()
        else:
            policy, bad = "log_only", frozenset()
            fairness, noisy = "log_only", frozenset()
            placement, rmap = "log_only", None
        if version is None:
            self._marshal(self._scratch, pods, policy, bad, fairness, noisy,
                          placement, rmap)
            self._scratch.key = None
            return self._scratch
        state = self._state
        key = (version, len(pods), policy, fairness, placement,
               self._cfg_gen)
        # ``bad is None`` = an advisor with per-pod should_avoid only (no
        # batch set to compare): no cheap change signal, so re-marshal.
        # The noisy-name set is compared like the avoid set — a rollup
        # flag transition between provider versions must reach the
        # resident marks.  The planner's resident map is identity-compared
        # (swapped whole per tick), so a planner tick between provider
        # versions reaches the placement marks the same way.
        if (state.key != key or bad is None or state.avoid != bad
                or state.noisy != noisy or state.placed is not rmap):
            self._marshal(state, pods, policy, bad, fairness, noisy,
                          placement, rmap)
            state.key = key
        return state

    # -- candidate computation ---------------------------------------------
    def candidates(self, req: LLMRequest, pods: list[PodMetrics],
                   version: int | None = None) -> list[int]:
        """Tree survivors WITHOUT policy filtering (legacy API — the parity
        fuzz drives it; policy belongs to the pick seam)."""
        if not pods:
            # Parity: the Python tree's failure branches land in the drop
            # filter on an empty pool, i.e. shed -> 429.
            raise SchedulingError(
                "failed to apply filter, resulted 0 pods: no pods", shed=True
            )
        with self._call_lock:
            state = self._ensure_state(None, pods, policy_mode=False)
            count, _ = self._pick_candidates_locked(state, req)
            return state.out[:count].tolist()

    def _pick_candidates_locked(self, state: _NativeState,
                                req: LLMRequest) -> tuple[int, int]:
        """One FFI crossing: request scalars in, candidate count + flags
        out (candidates land in ``state.out``)."""
        adapter_id = state.intern.get(req.resolved_target_model, -1)
        flags = ctypes.c_uint8(0)
        count = self._lib.lig_pick(
            self._void(state), adapter_id,
            1 if req.critical else 0,
            # Request-noisy matched against the MARSHALLED name set (the
            # same set the per-pod hog bits were computed from), mirroring
            # note_pick's req.model matching.
            1 if req.model in state.noisy else 0,
            req.prompt_tokens,
            _ptr(state.out, ctypes.c_int32), ctypes.byref(flags))
        if count == LIG_SHED:
            raise SchedulingError(_SHED_MSG, shed=True)
        if count == LIG_SHED_STRICT:
            raise SchedulingError(_STRICT_MSG, shed=True)
        if count < 0:
            raise SchedulingError(f"native scheduler error {count}")
        return count, flags.value

    def update_config(self, cfg: SchedulerConfig) -> None:
        """Swap thresholds at runtime — re-marshalled on the next pick via
        the config generation in the snapshot cache key."""
        self.cfg = cfg
        self._cfg_gen += 1
        self._decode_tree = build_decode_tree(
            cfg, token_aware=self.token_aware)
        self._oracle_tree = build_default_tree(
            cfg, token_aware=self.token_aware,
            prefill_aware=self.prefill_aware)

    def _snapshot_pods(self):
        snapshot = getattr(self._provider, "snapshot", None)
        if snapshot is not None:
            return snapshot()  # atomic (version, pods) pair
        return None, self._provider.all_pod_metrics()

    def _routable_pods(self):
        """(pods, version, pool_total) after the single-hop role policy,
        with the O(pods) role partition cached per snapshot version — the
        per-pick path must not re-walk 200 pods to rediscover an
        unchanged split.  ``pool_total`` is the pre-partition pool size
        (the pick ledger's funnel head)."""
        version, pods = self._snapshot_pods()
        cache = self._role_cache
        if version is not None and cache is not None and cache[0] == version:
            return cache[1], cache[2], cache[3]
        total = len(pods)
        collocated = [pm for pm in pods
                      if pod_role(pm.pod) == ROLE_COLLOCATED]
        if collocated and len(collocated) != len(pods):
            use, use_version = collocated, None
        else:
            use, use_version = pods, version
        if version is not None:
            self._role_cache = (version, use, use_version, total)
        return use, use_version, total

    # -- pick ---------------------------------------------------------------
    def _finish_pick(self, req: LLMRequest, pods: list[PodMetrics],
                     cand: list[int], flags: int, hop: str = "single",
                     pool_n: int = 0) -> Pod:
        """Post-candidate seams, identical to Scheduler._pick ordering:
        escape-hatch note, prefix tie-break, RNG draw, note_pick hooks.

        Runs OUTSIDE ``_call_lock`` (``cand`` is the caller's copy of the
        candidate indices): the lazy prefix-hash resolution and the
        prefix-index bookkeeping here can cost more than the pick itself,
        and serializing them would collapse the threaded gRPC transport to
        single-thread hash speed — the Python Scheduler runs the same
        seams unlocked."""
        advisor = self.health_advisor
        if flags & 1 and advisor is not None:
            note = getattr(advisor, "note_escape_hatch", None)
            if note is not None:
                note()
        if flags & 4 and self.usage_advisor is not None:
            # Fairness escape hatch: every candidate hosted a flagged
            # adapter (scheduler.py filter_by_fairness parity).
            note = getattr(self.usage_advisor, "note_fairness_escape", None)
            if note is not None:
                note()
        if flags & 8 and self.placement_advisor is not None:
            # Placement escape hatch: the adapter is resident in the pool
            # but on no candidate (filter_by_placement parity).
            note = getattr(self.placement_advisor,
                           "note_placement_escape", None)
            if note is not None:
                note()
        pick = None
        tie_break = False
        if self.prefix_index is not None and req.prefix_hashes:
            held = self.prefix_index.prefer(req, [pods[i] for i in cand])
            if held is not None:
                pick = held.pod
                tie_break = True
        if pick is None:
            pick = pods[cand[self._rng.randrange(len(cand))]].pod
        if self.prefix_index is not None and req.prefix_hashes:
            self.prefix_index.record(req.prefix_hashes, pick.name)
        if advisor is not None:
            advisor.note_pick(pick.name)
        if self.usage_advisor is not None:
            self.usage_advisor.note_pick(pick.name, req.model)
        if self.placement_advisor is not None:
            self.placement_advisor.note_pick(
                pick.name, req.resolved_target_model)
        ledger = self.pick_ledger
        if ledger is not None and ledger.sampled():
            self._charge_shadow(ledger, req, pods, cand, flags, hop,
                                pool_n, tie_break, pick)
        return pick

    def _charge_shadow(self, ledger, req: LLMRequest,
                       pods: list[PodMetrics], cand: list[int], flags: int,
                       hop: str, pool_n: int, tie_break: bool,
                       pick: Pod) -> None:
        """Explain a sampled native pick via Python-oracle shadow replay:
        the oracle tree + silent advisor chain over the SAME pods list
        the native pick saw.  ``shadow_match`` records whether the replay
        reproduced the native candidate set — a truthfulness observable
        (the same-RNG diff tests pin the paths byte-identical), never an
        assert.  Off the FFI path entirely; sampled picks only."""
        advisors = (self.health_advisor, self.usage_advisor,
                    self.placement_advisor)
        try:
            base = self._oracle_tree.filter(req, list(pods))
        except FilterError:
            # The oracle sheds where the native path served (snapshot
            # skew): fall back to the native candidates as the funnel
            # head — still a truthful record of what survived.
            base = [pods[i] for i in cand]
        post_health, post_fairness, final = ledger.replay(
            req, base, advisors)
        actual = {pods[i].pod.name for i in cand}
        shadow_match = {pm.pod.name for pm in final} == actual
        escapes = [seam for bit, seam in
                   ((1, "health/circuit"), (4, "fairness"),
                    (8, "placement")) if flags & bit]
        ledger.charge(
            req, winner=pick.name, base=base, post_health=post_health,
            post_fairness=post_fairness, post_placement=final, hop=hop,
            path="native-shadow", pool_n=pool_n or len(pods),
            role_n=len(pods), tie_break=tie_break, advisors=advisors,
            escapes=escapes, trace_id=req.trace_id,
            shadow_match=shadow_match)

    def schedule(self, req: LLMRequest) -> Pod:
        # Same role policy as the Python Scheduler: single-hop traffic
        # prefers collocated replicas; a role-filtered SUBSET bypasses the
        # snapshot-version resident state (it keys on (version, n) and a
        # subset would poison it).
        pods, version, pool_total = self._routable_pods()
        if not pods:
            raise SchedulingError(
                "failed to apply filter, resulted 0 pods: no pods", shed=True)
        with self._call_lock:
            state = self._ensure_state(version, pods)
            count, flags = self._pick_candidates_locked(state, req)
            cand = state.out[:count].tolist()
        return self._finish_pick(req, pods, cand, flags, pool_n=pool_total)

    def pick_many(self, reqs: list[LLMRequest]) -> list[Pod]:
        """Batched scheduling: ONE FFI crossing for the whole batch (the
        bench/load-rig amortization entry).  Semantics are pick-for-pick
        identical to calling ``schedule`` in a loop — same candidate sets,
        same RNG consumption, same advisor seams — including raising the
        shed ``SchedulingError`` at the first request that sheds."""
        if not reqs:
            return []
        pods, version, pool_total = self._routable_pods()
        if not pods:
            raise SchedulingError(
                "failed to apply filter, resulted 0 pods: no pods", shed=True)
        n, n_reqs = len(pods), len(reqs)
        with self._call_lock:
            state = self._ensure_state(version, pods)
            intern = state.intern
            noisy = state.noisy
            adapter_ids = np.fromiter(
                (intern.get(r.resolved_target_model, -1) for r in reqs),
                np.int32, n_reqs)
            criticals = np.fromiter(
                (1 if r.critical else 0 for r in reqs), np.uint8, n_reqs)
            req_noisies = np.fromiter(
                (1 if r.model in noisy else 0 for r in reqs),
                np.uint8, n_reqs)
            prompt_tokens = np.fromiter(
                (r.prompt_tokens for r in reqs), np.int64, n_reqs)
            counts = np.empty(n_reqs, np.int32)
            cands = np.empty(n_reqs * n, np.int32)
            flags = np.empty(n_reqs, np.uint8)
            rc = self._lib.lig_pick_many(
                self._void(state), n_reqs,
                _ptr(adapter_ids, ctypes.c_int32),
                _ptr(criticals, ctypes.c_uint8),
                _ptr(req_noisies, ctypes.c_uint8),
                _ptr(prompt_tokens, ctypes.c_int64),
                _ptr(counts, ctypes.c_int32), _ptr(cands, ctypes.c_int32),
                _ptr(flags, ctypes.c_uint8))
            if rc != 0:
                raise SchedulingError(f"native pick_many failed ({rc})")
        # counts/cands/flags are call-local: the finish seams (prefix
        # hashing, RNG, advisors) run unlocked, same as schedule().
        picks: list[Pod] = []
        for r_idx in range(n_reqs):
            count = int(counts[r_idx])
            if count == LIG_SHED:
                raise SchedulingError(_SHED_MSG, shed=True)
            if count == LIG_SHED_STRICT:
                raise SchedulingError(_STRICT_MSG, shed=True)
            if count < 0:
                raise SchedulingError(f"native scheduler error {count}")
            cand = cands[r_idx * n:r_idx * n + count].tolist()
            picks.append(self._finish_pick(
                reqs[r_idx], pods, cand, int(flags[r_idx]),
                pool_n=pool_total))
        return picks

    def schedule_disaggregated(
        self, req: LLMRequest
    ) -> tuple[Pod, Pod | None]:
        """Two-stage routing (see ``Scheduler.schedule_disaggregated``):
        native candidates over the prefill-role subset (scratch state —
        subsets have no snapshot version), then the Python decode tree
        over the decode-role subset."""
        version, pods = self._snapshot_pods()
        prefills, decodes = split_pool_roles(pods)
        if not prefills or not decodes:
            return self.schedule(req), None
        with self._call_lock:
            state = self._ensure_state(None, prefills)
            count, flags = self._pick_candidates_locked(state, req)
            cand = state.out[:count].tolist()
        prefill_pod = self._finish_pick(req, prefills, cand, flags,
                                        hop="prefill", pool_n=len(pods))
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
            # The decode hop IS the Python path here (tree + filters run
            # in Python above) — charged directly, no shadow needed.
            ledger.charge(
                req, winner=decode_pod.name, base=decode_base,
                post_health=decode_health, post_fairness=decode_fairness,
                post_placement=decode_survivors, hop="decode",
                path="python", pool_n=len(pods), role_n=len(decodes),
                advisors=(self.health_advisor, self.usage_advisor,
                          self.placement_advisor),
                escape_base=escape_base, trace_id=req.trace_id)
        return prefill_pod, decode_pod


def make_scheduler(provider, cfg: SchedulerConfig = DEFAULT_CONFIG,
                   prefer_native: bool = True, **kwargs):
    """Native scheduler when buildable, Python tree otherwise — and says
    which (``chip_smoke.py`` prints the line)."""
    if prefer_native and available():
        try:
            scheduler = NativeScheduler(provider, cfg, **kwargs)
            logger.info("scheduler: native (%s, ABI %d)",
                        os.path.basename(_LIB_PATH), _ABI_VERSION)
            return scheduler
        except RuntimeError:
            pass
    logger.info("scheduler: python (native library not built or refused)")
    return Scheduler(provider, cfg, **kwargs)
