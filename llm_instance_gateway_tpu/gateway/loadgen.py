"""Gateway load benchmark: the ghz-style ext-proc stress rig.

Parity: reference ``pkg/ext-proc/test/benchmark/benchmark.go:20-110`` — spin a
local ext-proc server with ``numFakePods`` fake pods × ``numModelsPerPod``
adapters (default 200×5 = 1000 models), fire N Process requests
round-robining model names, and report throughput + latency summary.

Two transports (the data-plane fast-path A/B; every emission carries which
one ran as ``relay_mode``):

- **fast** (default): drives the handler ``Server.process`` in-process —
  no gRPC stream, no proto (de)serialization — i.e. the pick →
  header-mutate hot path alone, the loop the ≥10k routed picks/s/core
  target is about.
- **slow** (``--no-fast-path``): the pre-existing gRPC ext-proc stream,
  paying the full proto marshalling tax per request — the baseline the
  fast/slow ratio in every artifact compares against.

Run:  python -m llm_instance_gateway_tpu.gateway.loadgen --requests 10000
"""

from __future__ import annotations

import argparse
import bisect
import hashlib
import json
import math
import random
import time

from llm_instance_gateway_tpu.api.v1alpha1 import Criticality
from llm_instance_gateway_tpu.gateway.handlers.messages import RequestBody
from llm_instance_gateway_tpu.gateway.handlers.server import (
    DEFAULT_DECODE_POD_HEADER,
    DEFAULT_TARGET_POD_HEADER,
    RequestContext,
)
from llm_instance_gateway_tpu.gateway.scheduling.prefix_affinity import (
    PREFIX_BLOCK_CHARS,
)
from llm_instance_gateway_tpu.gateway.testing import (
    build_handler_server,
    fake_metrics,
    fake_pod,
    generate_request,
    make_model,
    start_ext_proc,
)
from llm_instance_gateway_tpu.gateway.types import Pod
from llm_instance_gateway_tpu.tracing import TRACE_HEADER


def model_name(i: int) -> str:  # benchmark.go:71-73
    return f"adapter-{i}"


def attach_pick_ledger(outer_scheduler, sample_every: int = 8):
    """Wire a standalone decision ledger into a rig scheduler's
    ``pick_ledger`` seam (the AdvisorStack does this in production; bare
    loadgen rigs have no stack).  Returns the ledger, or None when the
    scheduler predates the seam."""
    from llm_instance_gateway_tpu.gateway import pickledger

    sched = getattr(outer_scheduler, "_scheduler", outer_scheduler)
    if not hasattr(sched, "pick_ledger"):
        return None
    ledger = pickledger.PickLedger(
        cfg=pickledger.PickLedgerConfig(sample_every=sample_every))
    sched.pick_ledger = ledger
    return ledger


def pick_funnel_block(ledger) -> dict | None:
    """The artifact's ``pick_funnel`` section: per-stage mean narrowing
    + per-seam steering counts from one ledger's rollup."""
    if ledger is None:
        return None
    ledger.tick()
    roll = ledger.seam_rollup()
    return {
        "samples": roll["samples"],
        "mean_survivors": roll["mean_survivors"],
        "steered": roll["steered"],
        "decisive": roll["decisive"],
    }


CRITICALITY_TIERS = {"critical": Criticality.CRITICAL,
                     "default": Criticality.DEFAULT,
                     "sheddable": Criticality.SHEDDABLE}


def parse_criticality_mix(spec: str) -> dict[str, float]:
    """``"critical=0.1,default=0.6,sheddable=0.3"`` -> normalized weight
    dict keyed by tier name (``Critical``/``Default``/``Sheddable``).
    Weights normalize; unknown tiers raise — a typo'd tier would silently
    skew the traffic shape the chaos scenario and sim calibration share."""
    mix: dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, raw = part.partition("=")
        tier = CRITICALITY_TIERS.get(name.strip().lower())
        if tier is None:
            raise ValueError(
                f"criticality-mix entry {part!r}: tier must be one of "
                f"{sorted(CRITICALITY_TIERS)}")
        try:
            w = float(raw)
        except ValueError:
            raise ValueError(f"criticality-mix entry {part!r}: weight must "
                             "be a number") from None
        if w <= 0:
            raise ValueError(f"criticality-mix entry {part!r}: weight must "
                             "be > 0")
        mix[tier.value] = mix.get(tier.value, 0.0) + w
    if not mix:
        raise ValueError("empty criticality mix")
    total = sum(mix.values())
    return {k: v / total for k, v in mix.items()}


def assign_tiers(model_names: list[str], mix: dict[str, float],
                 seed: int = 0) -> dict[str, str]:
    """Seeded weighted tier assignment per model name: uniform round-robin
    traffic over the models then matches the mix in expectation, and the
    same seed reproduces the same shape run over run."""
    rng = random.Random(seed)
    tiers = sorted(mix)
    weights = [mix[t] for t in tiers]
    return {name: rng.choices(tiers, weights=weights)[0]
            for name in model_names}


def parse_adapter_mix(spec: str, normalize: bool = True) -> dict[str, float]:
    """``"a=0.7,b=0.2,base=0.1"`` -> normalized weight dict.  ``base``
    routes to the shared base model (no adapter); weights need not sum to
    1 (they normalize), but must be positive.  ``normalize=False`` keeps
    the raw weights — the --adapter-universe overlay path, where
    ``base=0.1`` must mean a 0.1 ABSOLUTE share carved out of the Zipf
    mass, not "100% of a one-entry mix"."""
    mix: dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, raw = part.partition("=")
        try:
            w = float(raw)
        except ValueError:
            raise ValueError(f"adapter-mix entry {part!r}: weight must be "
                             "a number") from None
        if not name or w <= 0:
            raise ValueError(f"adapter-mix entry {part!r}: need name=weight "
                             "with weight > 0")
        mix[name.strip()] = mix.get(name.strip(), 0.0) + w
    if not mix:
        raise ValueError("empty adapter mix")
    if not normalize:
        return mix
    total = sum(mix.values())
    return {k: v / total for k, v in mix.items()}


def build_universe_mix(universe: int, zipf_s: float,
                       extra_mix: dict[str, float] | None = None
                       ) -> dict[str, float]:
    """Zipf adapter mix over a synthetic universe — THE SAME weights and
    ``zipf-0000..`` naming as ``sim/run.py`` (one shared helper, so a
    loadgen per-residency-tier report and a sim ``ttft_by_adapter``
    report cross-correlate by adapter name and can never silently
    diverge).  ``extra_mix`` entries (an explicit ``--adapter-mix``,
    e.g. ``base=0.1``) merge on top and the whole thing renormalizes, so
    the universe composes with the existing mix machinery instead of
    replacing it."""
    from llm_instance_gateway_tpu.sim.run import universe_name, zipf_weights

    if universe <= 0:
        raise ValueError("adapter universe must be > 0")
    mix = {universe_name(k): w
           for k, w in enumerate(zipf_weights(universe, zipf_s))}
    if extra_mix:
        extra_total = sum(extra_mix.values())
        scale = max(0.0, 1.0 - extra_total)
        mix = {name: w * scale for name, w in mix.items()}
        mix.update(extra_mix)
        total = sum(mix.values())
        mix = {name: w / total for name, w in mix.items()}
    return mix


def assign_residency_tiers(mix: dict[str, float], slot_per_pod: int = 16,
                           host_per_pod: int = 128) -> dict[str, str]:
    """Adapter -> residency tier for the universe fixture: the hottest
    ``slot_per_pod`` adapters are slot-resident, the next
    ``host_per_pod`` host-RAM-resident, the long tail disk-only — the
    <10%-resident shape of the tentpole's target scenario."""
    ranked = sorted((n for n in mix if n != "base"),
                    key=lambda n: (-mix[n], n))
    tiers: dict[str, str] = {}
    for i, name in enumerate(ranked):
        if i < slot_per_pod:
            tiers[name] = "slot"
        elif i < slot_per_pod + host_per_pod:
            tiers[name] = "host"
    return tiers


def build_mix_fixture(num_fake_pods: int, mix: dict[str, float],
                      tiers: dict[str, str] | None = None):
    """Weighted-adapter rig: every pod serves ALL mix adapters (affinity
    is trivially satisfiable — the variable under test is the traffic
    skew, the reproducible noisy-neighbor input), plus the shared base
    model for the ``base`` key."""
    adapters = sorted(n for n in mix if n != "base")
    pods = {}
    for i in range(num_fake_pods):
        if tiers is None:
            active = {name: 0 for name in adapters}
            max_adapters = len(adapters) + 1
        else:
            # Universe rig: only slot-resident adapters are ACTIVE (the
            # engine's lora_requests_info semantics); the host tier rides
            # adapter_tiers, the long tail is absent (disk).
            active = {n for n, t in tiers.items() if t == "slot"}
            active = {name: 0 for name in active}
            max_adapters = max(1, len(active))
        pods[fake_pod(i)] = fake_metrics(
            queue=i % 5, kv=(i % 10) / 10.0,
            adapters=active,
            max_adapters=max_adapters,
            adapter_tiers=tiers or {},
        )
    models = [make_model(name, Criticality.CRITICAL) for name in adapters]
    models.append(make_model("shared-base", Criticality.CRITICAL))
    return pods, models


def build_fixture(num_fake_pods: int, num_models_per_pod: int,
                  with_base_model: bool = False, role_split: bool = False):
    """benchmark.go:75-106: pod i serves adapters i*M..i*M+M-1.

    ``role_split`` alternates prefill/decode roles across the fleet
    (disaggregated-pool rig): the scheduler then runs TWO-stage picks and
    every response must carry both target headers."""
    pods = {}
    models = []
    total = num_fake_pods * num_models_per_pod
    for i in range(num_fake_pods):
        adapters = {
            model_name(i * num_models_per_pod + j): 0
            for j in range(num_models_per_pod)
        }
        role = ("prefill" if i % 2 == 0 else "decode") if role_split \
            else "collocated"
        pods[fake_pod(i, role=role)] = fake_metrics(
            queue=i % 5, kv=(i % 10) / 10.0, adapters=adapters,
            max_adapters=num_models_per_pod + 1,
        )
    for i in range(total):
        models.append(make_model(model_name(i), Criticality.CRITICAL))
    if with_base_model:
        # A shared base model with NO adapter: session-prefix traffic
        # routes through it so the prefix tie-break is the only stickiness
        # source (adapter traffic is already pod-pinned by LoRA affinity).
        # Session mode only — the recorded baseline fixture stays 1000.
        models.append(make_model("shared-base", Criticality.CRITICAL))
    return pods, models


class ConsistentRing:
    """Consistent-hash ring spraying request keys across N gateway
    replicas (``--gateways``).  Virtual nodes smooth the load split;
    blake2b keeps the mapping stable across processes and runs, so the
    SAME key (model name, session id) always lands on the SAME replica —
    the property that keeps prefix/session affinity coherent when a
    fleet of gateways fronts one pool (each replica's prefix index only
    ever learns the keys hashed to it)."""

    def __init__(self, n_replicas: int, vnodes: int = 64):
        if n_replicas < 1:
            raise ValueError("need at least one replica")
        points: list[tuple[int, int]] = []
        for r in range(n_replicas):
            for v in range(vnodes):
                h = int.from_bytes(
                    hashlib.blake2b(f"{r}:{v}".encode(),
                                    digest_size=8).digest(), "big")
                points.append((h, r))
        points.sort()
        self._points = points
        self.vnodes = vnodes

    def replica_of(self, key: str) -> int:
        h = int.from_bytes(
            hashlib.blake2b(key.encode(), digest_size=8).digest(), "big")
        i = bisect.bisect_left(self._points, (h, -1))
        if i == len(self._points):
            i = 0
        return self._points[i][1]


def build_pool_fixture(tag: str, pool_index: int, num_fake_pods: int,
                       num_models_per_pod: int):
    """One pool's pods+models with a ``tag`` namespace (multi-pool rig):
    pod ``{tag}-pod-i`` serves adapters ``i*M..i*M+M-1`` — the same
    shape as ``build_fixture``, disjoint across pools."""
    pods = {}
    total = num_fake_pods * num_models_per_pod
    for i in range(num_fake_pods):
        adapters = {f"{tag}-adapter-{i * num_models_per_pod + j}": 0
                    for j in range(num_models_per_pod)}
        pods[Pod(name=f"{tag}-pod-{i}",
                 address=f"10.{pool_index}.{i // 250}.{i % 250}:8000")] = \
            fake_metrics(queue=i % 5, kv=(i % 10) / 10.0,
                         adapters=adapters,
                         max_adapters=num_models_per_pod + 1)
    models = [make_model(f"{tag}-adapter-{k}", Criticality.CRITICAL)
              for k in range(total)]
    return pods, models


def _build_gateway_replica(pool_fixtures: list, seed: int, replica: int,
                           fairness_cfg=None):
    """One in-process gateway replica fronting every pool: a real handler
    ``Server`` + seeded ``Scheduler`` per pool, a real ``AdvisorStack``
    wired into each pool's seams, a ``MultiPoolServer`` front (when >1
    pool), and a ``StateBus`` over the stacks — the full control-plane
    shape the proxy runs, minus HTTP."""
    from llm_instance_gateway_tpu import events as events_mod
    from llm_instance_gateway_tpu.gateway.advisors import AdvisorStack
    from llm_instance_gateway_tpu.gateway.multipool import MultiPoolServer
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
        Scheduler,
    )
    from llm_instance_gateway_tpu.gateway.statebus import (
        StateBus,
        StateBusConfig,
    )

    journal = events_mod.EventJournal(capacity=64)
    servers: dict[str, object] = {}
    datastores: dict[str, object] = {}
    stacks: dict[str, object] = {}
    for k, (pool, pods, models) in enumerate(pool_fixtures):
        # Deterministic per-(replica, pool) RNG: the parity harness
        # rebuilds an identical replica (same seeds, same request
        # stream) with the hog flagged LOCALLY and diffs picks 1:1.
        server = build_handler_server(
            pods, models,
            scheduler_factory=lambda provider, _k=k: Scheduler(
                provider, rng=random.Random(seed * 7919 + replica * 97 + _k)))
        provider = server.scheduler._provider
        stacks[pool] = AdvisorStack(pool, provider,
                                    scheduler=server.scheduler,
                                    server=server, journal=journal,
                                    fairness_cfg=fairness_cfg)
        servers[pool] = server
        datastores[pool] = server.datastore
    if len(pool_fixtures) > 1:
        front = MultiPoolServer(servers, datastores,
                                default=pool_fixtures[0][0])
    else:
        front = servers[pool_fixtures[0][0]]
    bus = StateBus(stacks, cfg=StateBusConfig(replica_id=f"gw-{replica}"))
    return front, stacks, bus


def run_multi_gateway(requests: int = 20000, gateways: int = 4,
                      pools: int = 2, num_fake_pods: int = 50,
                      num_models_per_pod: int = 5, seed: int = 0,
                      parity_requests: int = 400) -> dict:
    """The N-gateway × M-pool rig behind ``--gateways``.

    Two phases:

    - **Throughput**: ``requests`` bodies spray across ``gateways``
      in-process replicas by consistent hash of the model name; each
      replica's batch runs in its own timed loop (replicas are separate
      processes in production — the GIL forbids honest in-process
      parallel timing), interleaved with a single-replica baseline over
      the same fixture, three passes each, best wall kept.
      ``aggregate_rps`` is the MAKESPAN view: total requests over the
      slowest replica's wall — what an N-process fleet would serve,
      conservative under consistent-hash load imbalance.

    - **Enforcement parity** (the pick-for-pick diff harness): fresh
      replicas with ``fairness_mode=deprioritize``; a hog adapter is
      noisy-flagged on replica 0 ONLY, one statebus gossip round runs
      (= one observability tick), then every replica processes its
      stream and an identically-seeded ORACLE twin — the hog flagged
      locally, i.e. the single-gateway brain — processes the same
      stream.  Picks must match 1:1: enforcement decisions reach every
      replica within one tick of single-gateway parity.
    """
    from llm_instance_gateway_tpu.gateway.fairness import FairnessConfig

    fixtures = [(f"p{p}",) + build_pool_fixture(f"p{p}", p, num_fake_pods,
                                                num_models_per_pod)
                for p in range(pools)]
    all_models = [m.spec.model_name
                  for _, _, models in fixtures for m in models]
    ring = ConsistentRing(gateways)
    # Assign the request stream up front: round-robin over every pool's
    # models, replica by consistent hash of the model (the affinity key).
    streams: list[list[bytes]] = [[] for _ in range(gateways)]
    for i in range(requests):
        target = all_models[i % len(all_models)]
        streams[ring.replica_of(target)].append(generate_request(target))

    def timed_run(front, bodies: list[bytes]) -> tuple[float, list[float]]:
        lats = []
        t0 = time.perf_counter()
        for body in bodies:
            t1 = time.perf_counter()
            res = front.process(RequestContext(), RequestBody(body=body))
            lats.append(time.perf_counter() - t1)
            assert res.immediate_status is None, res.immediate_status
        return time.perf_counter() - t0, lats

    def pct(lats: list[float], p: float) -> float:
        if not lats:
            return 0.0
        lats = sorted(lats)
        return lats[min(len(lats) - 1, int(p * len(lats)))]

    # Phase 1: throughput.  Baseline and replicas run INTERLEAVED, three
    # passes each, best wall kept: CPU-noise drift across the run
    # must not masquerade as (or hide) a scaling regression on either
    # side of the ratio.
    base_front, _, _ = _build_gateway_replica(fixtures, seed, replica=999)
    replicas = [_build_gateway_replica(fixtures, seed, replica=r)
                for r in range(gateways)]
    fronts = [front for front, _, _ in replicas]
    base_wall = float("inf")
    best: dict[int, tuple[float, list[float]]] = {}
    for _ in range(3):
        wall, _ = timed_run(base_front, [b for s in streams for b in s])
        base_wall = min(base_wall, wall)
        for r in range(gateways):
            wall, lats = timed_run(fronts[r], streams[r])
            if r not in best or wall < best[r][0]:
                best[r] = (wall, lats)
    single_rps = requests / base_wall
    per_replica: dict[str, dict] = {}
    for r in range(gateways):
        wall, lats = best[r]
        per_replica[f"gw-{r}"] = {
            "requests": len(streams[r]),
            "rps": round(len(streams[r]) / wall, 1) if wall > 0 else 0.0,
            "p50_us": round(pct(lats, 0.5) * 1e6, 1),
            "p99_us": round(pct(lats, 0.99) * 1e6, 1),
        }
    # Makespan aggregate: N replicas run in parallel in production, so
    # the fleet serves the whole stream in the SLOWEST replica's wall —
    # conservative (sum-of-rates overshoots N x when min-over-runs gets
    # lucky on the smaller per-replica batches) and naturally capped at
    # ~N x modulo the consistent-hash load imbalance.
    aggregate_rps = requests / max(w for w, _ in best.values())

    # Phase 2: enforcement parity within one statebus tick.
    hog = "p0-adapter-0"  # active on p0-pod-0 only (fixture shape)
    fcfg = FairnessConfig(mode="deprioritize")
    merged = [_build_gateway_replica(fixtures, seed + 1, r,
                                     fairness_cfg=fcfg)
              for r in range(gateways)]
    oracle = [_build_gateway_replica(fixtures, seed + 1, r,
                                     fairness_cfg=fcfg)
              for r in range(gateways)]
    # The flood is detected on replica 0 ONLY; oracles (the one-brain
    # reference) all know it locally.
    merged[0][1]["p0"].usage.seed_noisy(hog, hog)
    for _, stacks, _ in oracle:
        stacks["p0"].usage.seed_noisy(hog, hog)
        for stack in stacks.values():
            stack.fairness.set_quota_scale(1.0 / gateways)
    pre_visible = all(hog in stacks["p0"].fairness.noisy()
                      for _, stacks, _ in merged[1:])
    # One gossip round = one tick: full-mesh push-pull + apply.
    for _, _, bus in merged:
        bus.snapshot()
    for a in range(gateways):
        for b in range(a + 1, gateways):
            merged[a][2].exchange_with(merged[b][2])
    for _, _, bus in merged:
        bus.apply()
    post_visible = all(hog in stacks["p0"].fairness.noisy()
                       for _, stacks, _ in merged)
    # Identical per-replica parity streams: quiet + hog traffic mixed
    # (seeded), spread over both pools.
    prng = random.Random(seed + 2)
    parity_targets = [
        hog if prng.random() < 0.2
        else all_models[prng.randrange(len(all_models))]
        for _ in range(parity_requests)]
    checked = mismatches = 0
    for r in range(gateways):
        bodies = [generate_request(t) for t in parity_targets
                  if ring.replica_of(t) == r]
        for body in bodies:
            ctx_m, ctx_o = RequestContext(), RequestContext()
            res_m = merged[r][0].process(ctx_m, RequestBody(body=body))
            res_o = oracle[r][0].process(ctx_o, RequestBody(body=body))
            checked += 1
            if (res_m.set_headers.get(DEFAULT_TARGET_POD_HEADER)
                    != res_o.set_headers.get(DEFAULT_TARGET_POD_HEADER)):
                mismatches += 1
    bus0 = merged[0][2]
    # Fleet pick funnel: weighted per-stage mean narrowing + per-seam
    # steering summed across every throughput replica's per-pool ledger
    # (the AdvisorStack wires one into each scheduler).
    funnel_samples = 0
    funnel_means: dict[str, float] = {}
    funnel_steered: dict[str, int] = {}
    for _, stacks, _ in replicas:
        for stack in stacks.values():
            block = pick_funnel_block(stack.pickledger)
            if not block or not block["samples"]:
                continue
            n = block["samples"]
            funnel_samples += n
            for stage, mean in block["mean_survivors"].items():
                funnel_means[stage] = funnel_means.get(stage, 0.0) + mean * n
            for seam, count in block["steered"].items():
                funnel_steered[seam] = funnel_steered.get(seam, 0) + count
    pick_funnel = {
        "samples": funnel_samples,
        "mean_survivors": {
            stage: round(total / funnel_samples, 2)
            for stage, total in funnel_means.items()
        } if funnel_samples else {},
        "steered": funnel_steered,
    }
    return {
        "mode": "multi_gateway",
        "gateways": gateways,
        "pools": pools,
        "requests": requests,
        "num_fake_pods_per_pool": num_fake_pods,
        "num_models": len(all_models),
        "spray": {"mode": "consistent_hash", "vnodes": ring.vnodes},
        "per_replica": per_replica,
        "single_replica_rps": round(single_rps, 1),
        "aggregate_rps": round(aggregate_rps, 1),
        "scaling_x": round(aggregate_rps / single_rps, 2),
        "scaling_note": ("aggregate = requests / slowest replica wall "
                         "(makespan; replicas are separate processes in "
                         "production), best-of-3 interleaved passes; "
                         "mild superlinearity is real cache locality — "
                         "each replica touches only its consistent-hash "
                         "bucket's model subset"),
        "parity": {
            "hog": hog,
            "fairness_mode": fcfg.mode,
            "noisy_visible_on_peers_pre_exchange": pre_visible,
            "noisy_visible_on_peers_post_exchange": post_visible,
            "converged_after_exchanges": 1,
            "checked_picks": checked,
            "pick_mismatches_vs_single_brain": mismatches,
        },
        "statebus": {
            "live_replicas": bus0.live_replicas(),
            "quota_scale": bus0.last_apply_scale,
        },
        "pick_funnel": pick_funnel,
        "relay_mode": "fast",
        "scheduler": "python",
    }


def session_prompt(sid: int, k: int, prefix_chars: int) -> str:
    """A prompt whose leading ``prefix_chars`` are identical for every
    request of session ``sid`` (multi-turn / per-tenant template traffic),
    followed by a per-request suffix."""
    return (f"{sid:04d}" * (prefix_chars // 4 + 1))[:prefix_chars] + f" q{k}"


ARRIVAL_SHAPES = ("poisson", "burst", "diurnal")


def build_arrival_timeline(shape: str, n: int, rate_rps: float = 100.0,
                           seed: int = 0, burst_factor: float = 8.0,
                           duty: float = 0.2,
                           period_s: float = 10.0) -> list[float]:
    """Seeded VIRTUAL arrival timestamps for ``n`` requests.

    The rig's dispatch loop is a synchronous tight loop (it measures
    gateway processing cost, not wall-clock pacing), so arrival shapes
    are virtual: a seeded timeline stamped onto the run and recorded in
    the emission (``arrival_summary``) — the reproducible offered-load
    shape the sim's calibration scenarios and the capacity plane's
    forecast tests consume.

    - ``poisson``: memoryless exponential inter-arrivals at ``rate_rps``.
    - ``burst``: on/off square wave — ``duty`` of each ``period_s`` runs
      at ``burst_factor`` x the off rate, normalized so the MEAN rate
      stays ``rate_rps``.
    - ``diurnal``: sinusoidal modulation with period ``period_s`` (a
      compressed day): the instantaneous rate swings 0.25x..1.75x the
      mean.
    """
    if shape not in ARRIVAL_SHAPES:
        raise ValueError(f"unknown arrival shape {shape!r} "
                         f"(choices: {ARRIVAL_SHAPES})")
    rng = random.Random(seed)
    out: list[float] = []
    t = 0.0
    for _ in range(n):
        if shape == "poisson":
            rate = rate_rps
        elif shape == "burst":
            base = rate_rps / (duty * burst_factor + (1.0 - duty))
            in_burst = (t % period_s) < duty * period_s
            rate = base * (burst_factor if in_burst else 1.0)
        else:  # diurnal
            rate = rate_rps * (1.0
                               + 0.75 * math.sin(2.0 * math.pi * t / period_s))
        t += rng.expovariate(max(rate, 1e-6))
        out.append(t)
    return out


def arrival_summary(shape: str, timeline: list[float], rate_rps: float,
                    seed: int) -> dict:
    """The emission block describing a virtual arrival timeline: the
    shape + seed (enough to regenerate it exactly), the offered-rate
    series in 1s windows (capped), and the burstiness observables a
    reader compares across shapes (peak-to-mean, inter-arrival CV —
    ~1 for poisson, >1 for bursty)."""
    n = len(timeline)
    duration = timeline[-1] if timeline else 0.0
    counts: dict[int, int] = {}
    for ts in timeline:
        counts[int(ts)] = counts.get(int(ts), 0) + 1
    series = [counts.get(s, 0) for s in range(int(duration) + 1)]
    mean = n / max(duration, 1e-9)
    inter = [b - a for a, b in zip(timeline, timeline[1:])]
    cv = 0.0
    if inter:
        mi = sum(inter) / len(inter)
        var = sum((x - mi) ** 2 for x in inter) / len(inter)
        cv = (var ** 0.5) / max(mi, 1e-12)
    return {
        "shape": shape, "seed": seed, "rate_rps": rate_rps,
        "requests": n,
        "virtual_duration_s": round(duration, 1),
        "mean_rps": round(mean, 1),
        "peak_1s_rps": max(series) if series else 0,
        "peak_to_mean": round((max(series) if series else 0)
                              / max(mean, 1e-9), 2),
        "interarrival_cv": round(cv, 3),
        # The head of the 1s offered-rate series (bounded: a long run's
        # full series belongs in --trace-out territory, not the summary).
        "offered_rps_windows": series[:64],
    }


def run_load(
    requests: int = 10000,
    num_fake_pods: int = 200,
    num_models_per_pod: int = 5,
    port: int = 19102,
    streams: int = 8,
    use_native: bool = False,
    session_prefix_chars: int = 0,
    session_count: int = 64,
    role_split: bool = False,
    trace_out: str | None = None,
    adapter_mix: dict[str, float] | None = None,
    mix_seed: int = 0,
    criticality_mix: dict[str, float] | None = None,
    adapter_universe: int = 0,
    adapter_zipf: float = 1.1,
    fast_path: bool = True,
    arrival: str | None = None,
    arrival_rate_rps: float = 100.0,
    arrival_seed: int = 0,
) -> dict:
    """Fire ``requests`` Process calls; return a ghz-style summary dict.

    ``use_native`` swaps the Python filter tree for the C++ scheduler hot
    path (``scheduling/native.py``) — the A/B the recorded results compare.
    ``fast_path`` picks the transport: in-process ``Server.process``
    dispatch (fast; no gRPC stream, no proto marshalling) vs the
    pre-existing gRPC ext-proc stream (slow) — the summary's
    ``relay_mode`` field records which one ran.
    ``session_prefix_chars`` > 0 switches to session traffic: every request
    carries one of ``session_count`` shared prompt prefixes, measuring the
    prefix-affinity path's hot-loop cost (hashing rides the pick) and its
    stickiness (distinct pods per session; 1.0 = every repeat landed on
    the session's replica).  ``role_split`` makes the fleet half
    prefill-role / half decode-role: every pick becomes TWO-stage
    (prefill replica by the full tree, decode replica by KV headroom) and
    the summary reports the two-stage rate + per-hop header coverage.
    ``adapter_mix`` (``parse_adapter_mix`` output) switches to WEIGHTED
    adapter traffic drawn from a seeded RNG — the reproducible
    noisy-neighbor input — and the summary gains a per-adapter latency
    breakdown."""
    if session_prefix_chars and session_prefix_chars < PREFIX_BLOCK_CHARS:
        raise ValueError(
            f"session_prefix_chars must be >= {PREFIX_BLOCK_CHARS} (the "
            "affinity hash covers whole blocks only; a shorter prefix "
            "would measure a no-op)")
    if (adapter_mix or adapter_universe) and session_prefix_chars:
        raise ValueError("adapter-mix and session modes are exclusive "
                         "(each defines its own traffic shape)")
    if (adapter_mix or adapter_universe) and role_split:
        raise ValueError("adapter-mix builds an all-collocated fleet; "
                         "combining it with --role-split would report a "
                         "meaningless two_stage_rate")
    residency_tiers: dict[str, str] | None = None
    if adapter_universe:
        # Seeded Zipf draw over a synthetic universe, composing with an
        # explicit --adapter-mix (its entries overlay, e.g. base=0.1) and
        # with --criticality-mix (tier assignment over the same models).
        adapter_mix = build_universe_mix(adapter_universe, adapter_zipf,
                                         extra_mix=adapter_mix)
        residency_tiers = assign_residency_tiers(adapter_mix)
        pods, models = build_mix_fixture(num_fake_pods, adapter_mix,
                                         tiers=residency_tiers)
    elif adapter_mix:
        pods, models = build_mix_fixture(num_fake_pods, adapter_mix)
    else:
        pods, models = build_fixture(
            num_fake_pods, num_models_per_pod,
            with_base_model=bool(session_prefix_chars),
            role_split=role_split)
    tier_of: dict[str, str] = {}
    if criticality_mix:
        # Re-register the fixture's models with seeded weighted tiers so
        # uniform traffic over them reproduces the requested criticality
        # shape — the traffic mold the adapter_flood chaos scenario and
        # future sim calibration share.
        tier_of = assign_tiers(
            sorted(m.spec.model_name for m in models), criticality_mix,
            seed=mix_seed)
        models = [make_model(m.spec.model_name,
                             Criticality(tier_of[m.spec.model_name]))
                  for m in models]
    factory = None
    if use_native:
        from llm_instance_gateway_tpu.gateway.scheduling.native import (
            available, make_scheduler)

        if not available():
            raise RuntimeError("native scheduler library unavailable")
        factory = make_scheduler
    total_models = num_fake_pods * num_models_per_pod
    latencies: list[float] = []
    session_pods: dict[int, set[str]] = {}
    session_requests: dict[int, int] = {}
    two_stage_hits = 0
    trace_hits = 0  # responses carrying the echoed x-lig-trace-id
    # Weighted adapter draw: seeded, so a mix scenario replays exactly.
    mix_rng = random.Random(mix_seed)
    mix_names = sorted(adapter_mix) if adapter_mix else []
    mix_weights = [adapter_mix[n] for n in mix_names] if adapter_mix \
        else []
    per_adapter_lat: dict[str, list[float]] = {}
    per_tier_lat: dict[str, list[float]] = {}
    per_tier_shed: dict[str, int] = {}
    # Residency-tier breakdown (universe mode): latency of requests whose
    # adapter is slot- / host- / disk-tier in the fixture — the TTFT-
    # by-tier shape the placement scenario's acceptance bar reads.
    per_res_tier_lat: dict[str, list[float]] = {}

    def res_tier_account(adapter: str | None, latency_s: float) -> None:
        if residency_tiers is None or adapter is None:
            return
        tier = ("base" if adapter == "base"
                else residency_tiers.get(adapter, "disk"))
        per_res_tier_lat.setdefault(tier, []).append(latency_s)
    sheds = 0  # only nonzero under --criticality-mix (asserted otherwise)

    def body_for(i: int) -> tuple[bytes, int | None, str | None, str]:
        if adapter_mix:
            name = mix_rng.choices(mix_names, weights=mix_weights)[0]
            target = "shared-base" if name == "base" else name
            return generate_request(target), None, name, target
        if session_prefix_chars:
            sid = i % session_count
            return generate_request(
                "shared-base",
                prompt=session_prompt(sid, i, session_prefix_chars)), \
                sid, None, "shared-base"
        target = model_name(i % total_models)
        return generate_request(target), None, None, target

    def tier_account(target: str, latency_s: float, shed: bool) -> None:
        """Per-criticality-tier latency/shed tally (criticality-mix mode)."""
        tier = tier_of.get(target)
        if tier is None:
            return
        if shed:
            per_tier_shed[tier] = per_tier_shed.get(tier, 0) + 1
        else:
            per_tier_lat.setdefault(tier, []).append(latency_s)

    def account(keys: dict, sid: int | None) -> None:
        """Per-response bookkeeping shared by both transports; ``keys``
        maps set-header name -> value."""
        nonlocal trace_hits, two_stage_hits
        if TRACE_HEADER in keys:
            trace_hits += 1
        if role_split and (DEFAULT_TARGET_POD_HEADER in keys
                           and DEFAULT_DECODE_POD_HEADER in keys):
            two_stage_hits += 1
        if sid is not None:
            session_requests[sid] = session_requests.get(sid, 0) + 1
            target = keys.get(DEFAULT_TARGET_POD_HEADER)
            if target:
                session_pods.setdefault(sid, set()).add(target)

    if fast_path:
        # In-process dispatch: the handler core alone — request parse,
        # admission, pick, header mutation — with ZERO transport framing.
        server = build_handler_server(pods, models, scheduler_factory=factory)
        ledger = attach_pick_ledger(server.scheduler)
        t_start = time.perf_counter()
        for i in range(requests):
            body, sid, adapter, target = body_for(i)
            msg = RequestBody(body=body)
            # Body construction stays OUTSIDE the sample, matching the
            # slow path (which builds every body before its timer): the
            # latency A/B measures the gateway's processing, not the rig's
            # request generator.
            t0 = time.perf_counter()
            res = server.process(RequestContext(), msg)
            t1 = time.perf_counter()
            shed = res.immediate_status is not None
            if criticality_mix:
                # Sheddable-tier traffic MAY shed under a saturated
                # fixture — that is the per-tier breakdown's whole point.
                tier_account(target, t1 - t0, shed)
            else:
                assert not shed, f"request {i} shed ({res.immediate_status})"
            if shed:
                # Sheds stay OUT of the headline latency/trace tallies
                # (a near-instant 429 would deflate p50/p99 and make
                # mix artifacts incomparable to non-mix ones); the
                # per-tier rows above carry them.
                sheds += 1
                continue
            latencies.append(t1 - t0)
            if adapter is not None:
                per_adapter_lat.setdefault(adapter, []).append(t1 - t0)
            res_tier_account(adapter, t1 - t0)
            account(res.set_headers, sid)
        wall = time.perf_counter() - t_start
    else:
        import grpc

        from llm_instance_gateway_tpu.gateway.extproc import (
            ext_proc_v3_pb2 as pb,
        )
        from llm_instance_gateway_tpu.gateway.extproc.service import (
            make_process_stub,
        )

        server = start_ext_proc(pods, models, port=port,
                                scheduler_factory=factory)
        ledger = attach_pick_ledger(server.handler_server.scheduler)
        try:
            channel = grpc.insecure_channel(f"localhost:{port}")
            stub = make_process_stub(channel)
            t_start = time.perf_counter()
            # Round-robin model names (benchmark.go:64-69), batched into
            # streams.
            sent = 0
            while sent < requests:
                batch = min(requests - sent, max(1, requests // streams))
                bodies = [body_for(sent + k) for k in range(batch)]
                msgs = [
                    pb.ProcessingRequest(request_body=pb.HttpBody(body=body))
                    for body, _, _, _ in bodies
                ]
                t0 = time.perf_counter()
                # One stream per batch: measures per-message processing
                # inline.
                for k, resp in enumerate(stub(iter(msgs))):
                    t1 = time.perf_counter()
                    lat = t1 - t0
                    t0 = t1
                    shed = resp.WhichOneof("response") != "request_body"
                    if criticality_mix:
                        tier_account(bodies[k][3], lat, shed)
                    else:
                        assert not shed
                    if shed:
                        sheds += 1  # headline tallies exclude sheds
                        continue
                    latencies.append(lat)
                    adapter = bodies[k][2]
                    if adapter is not None:
                        per_adapter_lat.setdefault(adapter, []).append(lat)
                    res_tier_account(adapter, lat)
                    keys = {
                        h.header.key: (h.header.raw_value.decode("utf-8",
                                                                 "replace")
                                       if h.header.raw_value
                                       else h.header.value)
                        for h in (resp.request_body.response
                                  .header_mutation.set_headers)
                    }
                    account(keys, bodies[k][1])
                sent += batch
            wall = time.perf_counter() - t_start
            channel.close()
        finally:
            server.stop(None)

    latencies.sort()

    def pct(p: float) -> float:
        if not latencies:
            return 0.0  # every request shed (saturated mix fixture)
        return latencies[min(len(latencies) - 1, int(p * len(latencies)))]

    out = {
        "requests": requests,
        "num_fake_pods": num_fake_pods,
        "num_models": len(models),
        "wall_s": round(wall, 3),
        "rps": round(requests / wall, 1),
        "p50_us": round(pct(0.5) * 1e6, 1),
        "p99_us": round(pct(0.99) * 1e6, 1),
        # 1.0 = every SERVED response echoed a trace id in its header
        # mutation (the client-side correlation contract; sheds never
        # reach the trace-echo path and are excluded).
        "trace_id_rate": round(trace_hits / max(1, requests - sheds), 4),
        # Which data-plane transport ran: "fast" = in-process dispatch,
        # "slow" = gRPC ext-proc stream — so every future artifact carries
        # the fast/slow axis alongside the scheduler one.
        "relay_mode": "fast" if fast_path else "slow",
    }
    funnel = pick_funnel_block(ledger)
    if funnel is not None:
        # Per-stage mean narrowing + per-seam steering over the sampled
        # picks of THIS run (gateway/pickledger.py; no advisors attached
        # on the bare rig, so steering is the filter tree's alone).
        out["pick_funnel"] = funnel
    if trace_out:
        # Raw per-request samples in the shape tools/trace_report.py reads
        # ({"phases": {name: [seconds...]}}): the ext-proc Process round
        # trip IS the gateway decision phase under this rig.
        with open(trace_out, "w") as f:
            json.dump({"phases": {"extproc.process": latencies}}, f)
    if adapter_universe:
        # Universe mode: the flat per-adapter dump would be 1000+ rows —
        # the per-RESIDENCY-tier breakdown is the shape that matters (the
        # slot/host/disk latency split the placement plane acts on).
        out["adapter_universe"] = adapter_universe
        out["adapter_zipf"] = adapter_zipf
        tiers_summary = {}
        for tier in sorted(per_res_tier_lat):
            vals = sorted(per_res_tier_lat[tier])
            tiers_summary[tier] = {
                "requests": len(vals),
                "p50_us": round(vals[len(vals) // 2] * 1e6, 1),
                "p99_us": round(
                    vals[min(len(vals) - 1, int(0.99 * len(vals)))] * 1e6, 1),
            }
        out["per_residency_tier"] = tiers_summary
    elif adapter_mix:
        # Per-adapter latency breakdown: the observable a noisy-neighbor
        # scenario compares against the gateway's usage attribution.
        out["adapter_mix"] = {k: round(v, 4)
                              for k, v in sorted(adapter_mix.items())}
        breakdown = {}
        for name in sorted(per_adapter_lat):
            vals = sorted(per_adapter_lat[name])
            breakdown[name] = {
                "requests": len(vals),
                "p50_us": round(vals[len(vals) // 2] * 1e6, 1),
                "p99_us": round(
                    vals[min(len(vals) - 1, int(0.99 * len(vals)))] * 1e6, 1),
            }
        out["per_adapter"] = breakdown
    if criticality_mix:
        # Per-tier latency/shed breakdown: the traffic shape + observable
        # the adapter_flood chaos scenario and sim calibration share
        # (zero critical sheds is an acceptance invariant there).
        out["criticality_mix"] = {k: round(v, 4)
                                  for k, v in sorted(criticality_mix.items())}
        # Headline latencies cover served traffic only; the shed count
        # keeps rps (= requests/wall) interpretable next to them.
        out["sheds"] = sheds
        tiers = {}
        for tier in sorted(set(per_tier_lat) | set(per_tier_shed)):
            vals = sorted(per_tier_lat.get(tier, []))
            row = {"requests": len(vals) + per_tier_shed.get(tier, 0),
                   "shed": per_tier_shed.get(tier, 0)}
            if vals:
                row["p50_us"] = round(vals[len(vals) // 2] * 1e6, 1)
                row["p99_us"] = round(
                    vals[min(len(vals) - 1, int(0.99 * len(vals)))] * 1e6, 1)
            tiers[tier] = row
        out["per_tier"] = tiers
    if role_split:
        # 1.0 = every response carried BOTH hop headers (prefill target +
        # x-decode-pod) — the two-stage pick ran on every request.
        out["two_stage_rate"] = round(two_stage_hits / requests, 4)
    if session_prefix_chars:
        if not session_pods:
            raise RuntimeError(
                "session mode matched no target-pod headers — the "
                "measurement is broken, not perfectly sticky")
        per = [len(p) for p in session_pods.values()]
        out["sessions"] = len(per)
        out["session_prefix_chars"] = session_prefix_chars
        # 1.0 = perfect stickiness; N = the session sprayed over N pods.
        out["distinct_pods_per_session_avg"] = round(sum(per) / len(per), 2)
        # Estimated prefix-cache reuse from stickiness alone: a request can
        # hit a pod-local prefix cache iff its pod already served this
        # session once, so each distinct pod a session touched charges one
        # compulsory miss.  This is the upper bound the routing achieves —
        # the ledger's measured reuse_efficiency (/debug/kv) reads at or
        # below it when engines evict.
        total = sum(session_requests.values())
        hits = sum(max(0, session_requests[sid] - len(pods))
                   for sid, pods in session_pods.items())
        out["est_prefix_reuse_rate"] = round(hits / max(1, total), 4)
        # Token-weighted: only the shared prefix chars of each hitting
        # prompt are actually reusable.
        prompt_chars = session_prefix_chars + len(" q0")
        out["est_reuse_efficiency"] = round(
            (hits / max(1, total))
            * (session_prefix_chars / prompt_chars), 4)
    if arrival:
        # Virtual offered-load shape (--arrival): seeded, reproducible,
        # recorded so the artifact carries the load SHAPE alongside the
        # latency numbers — the input the capacity twin's trend
        # forecasts and sim calibration replay.
        timeline = build_arrival_timeline(arrival, requests,
                                          rate_rps=arrival_rate_rps,
                                          seed=arrival_seed)
        out["arrival"] = arrival_summary(arrival, timeline,
                                         arrival_rate_rps, arrival_seed)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=10000)
    parser.add_argument("--fake-pods", type=int, default=200)
    parser.add_argument("--models-per-pod", type=int, default=5)
    parser.add_argument("--native", action="store_true",
                        help="C++ scheduler hot path instead of the Python "
                             "filter tree")
    parser.add_argument("--session-prefix-chars", type=int, default=0,
                        help="session traffic: shared prompt prefixes of "
                             "this many chars (measures prefix-affinity "
                             "cost + stickiness)")
    parser.add_argument("--sessions", type=int, default=64)
    parser.add_argument("--role-split", action="store_true",
                        help="disaggregated-pool rig: half the fake fleet "
                             "prefill-role, half decode-role; measures the "
                             "two-stage pick rate and cost")
    parser.add_argument("--trace-out", default=None, metavar="PATH",
                        help="write per-request phase samples as JSON for "
                             "tools/trace_report.py")
    parser.add_argument("--adapter-mix", default=None, metavar="SPEC",
                        help='weighted adapter traffic, e.g. '
                             '"a=0.7,b=0.2,base=0.1" ("base" = the shared '
                             'base model); seeded draw for reproducible '
                             'noisy-neighbor scenarios, per-adapter '
                             'latency breakdown in the report')
    parser.add_argument("--mix-seed", type=int, default=0,
                        help="seed for the weighted adapter draw")
    parser.add_argument("--adapter-universe", type=int, default=0,
                        metavar="N",
                        help="long-tail traffic: N synthetic adapters with "
                             "seeded Zipf-weighted traffic (composes with "
                             "--adapter-mix overlays and --criticality-mix); "
                             "the fixture tiers the hottest adapters "
                             "slot/host-resident and the report gains a "
                             "per-residency-tier latency breakdown")
    parser.add_argument("--adapter-zipf", type=float, default=1.1,
                        metavar="S",
                        help="Zipf exponent for --adapter-universe traffic")
    parser.add_argument("--criticality-mix", default=None, metavar="SPEC",
                        help='weighted criticality tiers, e.g. '
                             '"critical=0.1,default=0.6,sheddable=0.3": '
                             "the fixture's models get seeded tier "
                             "assignments and the report gains a per-tier "
                             "latency/shed breakdown")
    parser.add_argument("--arrival", default=None, choices=ARRIVAL_SHAPES,
                        help="stamp a seeded VIRTUAL arrival timeline on "
                             "the run (poisson | burst | diurnal) and "
                             "record its offered-rate shape in the "
                             "emission — the reproducible load shape sim "
                             "calibration and capacity-forecast tests "
                             "replay; the dispatch loop itself stays a "
                             "tight loop")
    parser.add_argument("--arrival-rate", type=float, default=100.0,
                        metavar="RPS",
                        help="mean rate of the virtual arrival timeline")
    parser.add_argument("--arrival-seed", type=int, default=0,
                        help="seed for the virtual arrival timeline")
    parser.add_argument("--no-fast-path", action="store_true",
                        help="drive the gRPC ext-proc stream (proto "
                             "marshalling per request) instead of the "
                             "in-process fast path — the slow side of the "
                             "relay_mode A/B")
    parser.add_argument("--gateways", type=int, default=1, metavar="N",
                        help="spray requests across N in-process gateway "
                             "replicas by consistent hash (per-replica "
                             "rps/p99 breakdown + single-replica scaling "
                             "ratio + pick-for-pick statebus enforcement "
                             "parity in the report)")
    parser.add_argument("--pools", type=int, default=1, metavar="M",
                        help="with --gateways: each replica fronts M "
                             "independent pools (MultiPoolServer routing; "
                             "disjoint pod/model namespaces per pool)")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed for the multi-gateway rig's scheduler "
                             "RNGs and parity traffic draw")
    args = parser.parse_args(argv)
    if args.gateways > 1:
        if (args.adapter_mix or args.adapter_universe
                or args.session_prefix_chars or args.role_split
                or args.criticality_mix or args.no_fast_path
                or args.native):
            parser.error("--gateways composes with --fake-pods/"
                         "--models-per-pod/--pools only (each replica "
                         "runs the plain fast-path PYTHON-scheduler "
                         "fixture; --native has no multi-gateway path "
                         "yet and would silently measure the wrong "
                         "scheduler)")
        print(json.dumps(run_multi_gateway(
            requests=args.requests, gateways=args.gateways,
            pools=max(1, args.pools), num_fake_pods=args.fake_pods,
            num_models_per_pod=args.models_per_pod, seed=args.seed)))
        return
    summary = run_load(args.requests, args.fake_pods, args.models_per_pod,
                       use_native=args.native,
                       session_prefix_chars=args.session_prefix_chars,
                       session_count=args.sessions,
                       role_split=args.role_split,
                       trace_out=args.trace_out,
                       adapter_mix=(parse_adapter_mix(
                                        args.adapter_mix,
                                        normalize=not args.adapter_universe)
                                    if args.adapter_mix else None),
                       mix_seed=args.mix_seed,
                       criticality_mix=(
                           parse_criticality_mix(args.criticality_mix)
                           if args.criticality_mix else None),
                       adapter_universe=args.adapter_universe,
                       adapter_zipf=args.adapter_zipf,
                       fast_path=not args.no_fast_path,
                       arrival=args.arrival,
                       arrival_rate_rps=args.arrival_rate,
                       arrival_seed=args.arrival_seed)
    summary["scheduler"] = "native" if args.native else "python"
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
