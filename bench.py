"""Benchmark: multiplexed-LoRA serving throughput vs single-tenant baseline.

The BASELINE.json north star: route multiplexed LoRA'd InferenceModels at
>= 90% of single-tenant tokens/sec.  This bench measures exactly that ratio
on the chip, through the real engine:

- Phase A (baseline): N greedy requests against the base model.
- Phase B (multiplexed): same workload round-robined across 4 resident LoRA
  adapters (rank 8) — per-row adapter deltas in every decode batch.

It needs a TPU and FAILS without one: a CPU run is not a device number.
Prints ONE JSON line naming the device it ran on:
  {"metric": "multiplexed_lora_tokens_per_sec", "value": <tok/s>,
   "unit": "tok/s", "vs_baseline": <multiplexed / single-tenant>,
   "device": {"platform": ..., "kind": ..., "count": ...}}

``--handoff-microbench`` runs the device-independent CPU microbenches
instead (also reachable one by one through ``tools/bench_check.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp
import numpy as np


def bench_model_cfg():
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B

    # ~1.1B-param Llama-3-shaped model: fits v5e-1 HBM in bf16 with a
    # 16-slot x 512-token KV cache and 4 adapter slots.
    return dataclasses.replace(
        LLAMA3_8B, name="bench-llama-1b", vocab_size=32_000, d_model=2048,
        n_layers=16, n_heads=16, n_kv_heads=8, d_ff=8192, head_dim=128,
        max_seq_len=512, max_lora_slots=4, max_lora_rank=8,
        use_flash_attention=True,
    )


def make_adapter_weights(cfg, rank, seed):
    from llm_instance_gateway_tpu.models.lora import target_dims

    dims = target_dims(cfg)
    rng = np.random.RandomState(seed)
    return {
        t: {
            "a": (rng.randn(cfg.n_layers, dims[t][0], rank) * 0.01).astype(np.float32),
            "b": (rng.randn(cfg.n_layers, rank, dims[t][1]) * 0.01).astype(np.float32),
        }
        for t in ("q", "k", "v", "o")
    }


def run_phase(engine, n_requests, prompt_len, max_new, adapters, seed=0):
    from llm_instance_gateway_tpu.server.engine import Request, SamplingParams

    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n_requests):
        adapter = adapters[i % len(adapters)] if adapters else None
        reqs.append(
            Request(
                prompt_tokens=list(rng.randint(1, 250, size=prompt_len)),
                max_new_tokens=max_new,
                sampling=SamplingParams(temperature=0.0),
                adapter=adapter,
            )
        )
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    for r in reqs:
        if not r.done.wait(1800):
            raise RuntimeError("bench request timed out")
    wall = time.perf_counter() - t0
    tokens = sum(len(r.output_tokens) for r in reqs)
    ttfts = sorted(r.ttft_s for r in reqs)
    return {
        "tokens": tokens,
        "wall_s": wall,
        "tok_per_s": tokens / wall,
        "ttft_p50_ms": ttfts[len(ttfts) // 2] * 1e3,
        "ttft_p99_ms": ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))] * 1e3,
    }


def run_handoff_microbench() -> dict:
    """Disaggregation phase: device-independent (CPU backend, tiny model).

    Two measurements:

    - **Handoff plane throughput**: N requests through the full
      cross-engine path — ``prefill_only`` on a prefill-role engine,
      serialize, deserialize, ``attach_prefilled`` on a decode-role engine
      (paged pool) — reported as KV blocks/s exported+attached and wire
      MB/s.

    - **Decode interference A/B (TTFT/TPOT split)**: short decode-heavy
      requests measured once on a COLLOCATED engine that is concurrently
      admitting long prefills (the interference disaggregation removes),
      and once on a decode-role engine fed attaches while the long
      prefills run on the SEPARATE prefill engine.  TPOT = per-request
      (t_done - t_first_token)/(tokens-1).
    """
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from llm_instance_gateway_tpu.server.kv_transfer import PrefillHandoff

    cfg = dataclasses.replace(
        LLAMA3_8B, name="handoff-cpu", vocab_size=512, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, head_dim=32,
        max_seq_len=256,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    block = 16
    ecfg = dict(decode_slots=4, max_seq_len=256,
                prefill_buckets=(32, 64, 128))

    def engine(**kw):
        e = Engine(cfg, params, EngineConfig(**ecfg, **kw), eos_id=None,
                   dtype=jnp.float32)
        e.start()
        return e

    rng = np.random.RandomState(0)

    def req(prompt_len, max_new):
        return Request(
            prompt_tokens=list(rng.randint(1, 500, size=prompt_len)),
            max_new_tokens=max_new, sampling=SamplingParams(temperature=0.0))

    pre = engine(role="prefill")
    dec = engine(role="decode", paged_kv_block=block)
    coll = engine(paged_kv_block=block)
    out: dict = {}
    try:
        # Warm the compiled-shape set out of the measurement.
        warm = dec.attach_prefilled(PrefillHandoff.from_bytes(
            pre.prefill_only(req(64, 2), timeout_s=300).to_bytes()))
        warm.done.wait(300)
        coll.generate(req(64, 2), timeout_s=300)
        coll.generate(req(120, 2), timeout_s=300)

        # --- handoff plane throughput (+ per-phase trace collection) ---
        n_req, prompt_len = 8, 64
        wire_bytes = 0
        traces = []  # the /debug/traces shape tools/trace_report.py reads
        t0 = time.perf_counter()
        for i in range(n_req):
            pr = req(prompt_len, 2)
            h = pre.prefill_only(pr, timeout_s=300)
            t_s0 = time.time()
            wire = h.to_bytes()
            t_s1 = time.time()
            wire_bytes += len(wire)
            t_d0 = time.time()
            handoff2 = PrefillHandoff.from_bytes(wire)
            t_d1 = time.time()
            ar = dec.attach_prefilled(handoff2)
            t_att = time.time()
            if not ar.done.wait(300):
                raise RuntimeError("attach timed out")
            spans = [
                {"name": "engine.queue_wait", "start": pr.t_submit,
                 "end": pr.t_prefill_start},
                {"name": "engine.prefill", "start": pr.t_prefill_start,
                 "end": pr.t_first_token},
                {"name": "handoff.serialize", "start": t_s0, "end": t_s1},
                {"name": "handoff.deserialize", "start": t_d0, "end": t_d1},
                {"name": "handoff.attach", "start": t_d1, "end": t_att},
                {"name": "engine.decode", "start": t_att, "end": ar.t_done},
            ]
            traces.append({"trace_id": f"bench-{i}", "spans": spans})
        wall = time.perf_counter() - t0
        blocks = n_req * (-(-prompt_len // block))
        out["handoff_blocks_per_s"] = round(blocks / wall, 1)
        out["handoff_wire_mb_s"] = round(wire_bytes / wall / 1e6, 2)

        # Per-phase latency table (tools/trace_report.py smoke invocation):
        # the same code path the CLI uses, so the BENCH trajectory carries
        # the phase breakdown the tracing subsystem exists to answer.
        try:
            from tools import trace_report

            rows = trace_report.phase_table(
                trace_report.phase_samples({"traces": traces}))
            out["phase_latency_ms"] = {
                r["phase"]: {"p50": r["p50_ms"], "p95": r["p95_ms"],
                             "p99": r["p99_ms"]}
                for r in rows}
        except Exception as e:  # additive: never block the throughput metric
            out["phase_latency_error"] = str(e)[:200]

        # --- decode interference A/B ---
        def tpot_ms(r):
            steps = max(1, len(r.output_tokens) - 1)
            return (r.t_done - r.t_first_token) * 1e3 / steps

        # Collocated: decode-heavy requests share the engine with long
        # prefill admissions — each prefill program stalls every active
        # decode slot for its duration (the interference under test).
        decoders = [req(16, 24) for _ in range(4)]
        for r in decoders:
            coll.submit(r)
        longs = [coll.submit(req(120, 2)) for _ in range(4)]
        for r in decoders + longs:
            if not r.done.wait(300):
                raise RuntimeError("collocated request timed out")
        vals = sorted(tpot_ms(r) for r in decoders)
        out["colloc_decode_tpot_p50_ms"] = round(vals[len(vals) // 2], 2)
        out["colloc_decode_tpot_max_ms"] = round(vals[-1], 2)

        # Disaggregated: decoders attach on dec; long prefills hand off on
        # pre (their KV never enters dec's decode loop as prefill work).
        decoders = []
        for _ in range(4):
            decoders.append(dec.attach_prefilled(PrefillHandoff.from_bytes(
                pre.prefill_only(req(16, 24), timeout_s=300).to_bytes())))
        longs = [pre.submit(Request(
            prompt_tokens=list(rng.randint(1, 500, size=120)),
            max_new_tokens=2, sampling=SamplingParams(temperature=0.0)))
            for _ in range(4)]
        for r in decoders:
            if not r.done.wait(300):
                raise RuntimeError("disagg decode request timed out")
        for r in longs:
            r.done.wait(300)
        vals = sorted(tpot_ms(r) for r in decoders)
        out["disagg_decode_tpot_p50_ms"] = round(vals[len(vals) // 2], 2)
        out["disagg_decode_tpot_max_ms"] = round(vals[-1], 2)
        out["disagg_decode_ttft_p50_ms"] = round(sorted(
            r.ttft_s for r in decoders)[len(decoders) // 2] * 1e3, 2)

        # --- usage-attribution overhead A/B ---
        # Same engine/workload with the capacity-attribution tracker ON
        # (the default) vs OFF: decode-heavy requests so the per-dispatch
        # charge path dominates the delta.  Acceptance bar (observability
        # PR): usage_attribution_ratio <= 1.05 — attribution costs < 5%
        # of decode-step cost.  Interleaved rounds, MIN per side (the
        # PR-2/PR-4 microbench precedent: contended cores swing single
        # runs 2x).
        off_engine = engine(paged_kv_block=block, usage_attribution=False)
        try:
            def decode_wall(e) -> float:
                rs = [req(16, 24) for _ in range(4)]
                t0 = time.perf_counter()
                for r in rs:
                    e.submit(r)
                for r in rs:
                    if not r.done.wait(300):
                        raise RuntimeError("usage A/B request timed out")
                return time.perf_counter() - t0

            decode_wall(coll), decode_wall(off_engine)  # warmup pair
            on_best = off_best = float("inf")
            for _ in range(3):
                off_best = min(off_best, decode_wall(off_engine))
                on_best = min(on_best, decode_wall(coll))
            out["usage_attribution_on_s"] = round(on_best, 4)
            out["usage_attribution_off_s"] = round(off_best, 4)
            out["usage_attribution_ratio"] = round(on_best / off_best, 4)
        finally:
            off_engine.stop()
        if jax.default_backend() == "cpu":
            # Both engines share this host's cores, so cross-engine CPU
            # contention inflates the disagg numbers; on separate TPU
            # replicas the interference signal is the COLLOCATED max/p50
            # spread (decode stalls during co-resident prefill programs).
            out["handoff_note"] = "cpu-backend: engines share host cores"
    finally:
        pre.stop()
        dec.stop()
        coll.stop()
    return out


def run_pick_microbench(n: int = 4000, n_pods: int = 64,
                        n_models: int = 128) -> dict:
    """Scheduler pick microbench with a tracing-overhead A/B.

    Device-independent: a real Python filter-tree scheduler over a static
    fake fleet, run through the SAME per-pick instrumentation the proxy
    executes per request (trace-id mint for the echo contract, admission
    span record, pick-latency histogram observe) — measured once with the
    tracer DISABLED (LIG_TRACE=0 equivalent: record() short-circuits) and
    once ENABLED at default sampling.  The acceptance bar is
    ``pick_traced_ratio`` <= 1.05: turning tracing on costs < 5% of a pick.
    Each side reports its MIN over interleaved runs — this container's
    cores are contended and single-run ratios swing 2x from noise alone.
    """
    from llm_instance_gateway_tpu import tracing
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.telemetry import GatewayMetrics
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod, static_provider,
    )

    pods = {
        fake_pod(i): fake_metrics(
            queue=i % 5, kv=(i % 10) / 10.0,
            adapters={f"adapter-{i * 2 + j}": 0 for j in range(2)},
            max_adapters=4)
        for i in range(n_pods)
    }
    scheduler = Scheduler(static_provider(pods))
    reqs = [
        LLMRequest(model=f"adapter-{i % n_models}",
                   resolved_target_model=f"adapter-{i % n_models}",
                   critical=True, prompt_tokens=25, criticality="Critical")
        for i in range(64)
    ]

    def loop(tracer) -> float:
        gm = GatewayMetrics()
        t0 = time.perf_counter()
        for i in range(n):
            trace_id = tracing.new_trace_id()  # echo contract: always minted
            t_req = time.time()
            tp0 = time.perf_counter()
            pod = scheduler.schedule(reqs[i % len(reqs)])
            pick_s = time.perf_counter() - tp0
            gm.record_pick(pod.name, pick_s, False)
            tracer.record(trace_id, "gateway.admission", t_req, time.time(),
                          pod=pod.name, pick_s=round(pick_s, 6))
        return time.perf_counter() - t0

    # Interleaved A/B pairs (warm-up pair discarded), MIN per side: this
    # container's cores are contended and single-pair ratios swing 2x from
    # scheduler-side noise alone — each side's minimum is its uncontended
    # cost, which is the quantity the <5% bound is about.
    off, on = tracing.Tracer(enabled=False), tracing.Tracer()
    loop(off), loop(on)
    base_best = traced_best = float("inf")
    for _ in range(12):
        base_best = min(base_best, loop(off))
        traced_best = min(traced_best, loop(on))
    return {
        "pick_us": round(base_best / n * 1e6, 2),
        "pick_traced_us": round(traced_best / n * 1e6, 2),
        "pick_traced_ratio": round(traced_best / base_best, 4),
    }


def run_policy_microbench(n: int = 4000, n_pods: int = 64) -> dict:
    """Health-policy enforcement cost A/B (robustness PR acceptance bar:
    ``pick_policy_ratio`` <= 1.05 — enforcing ``health_policy=avoid``
    costs < 5% of a pick vs ``log_only``).

    Same harness shape as ``run_pick_microbench``: a real Python
    filter-tree scheduler over a static fleet, with a REAL ResiliencePlane
    advisor attached on both sides — log_only pays only the note_pick
    count, avoid additionally runs ``filter_by_policy`` over the survivor
    set (one degraded pod in the fleet so the filter actually filters).
    Interleaved runs, MIN per side (contended cores swing single runs 2x).
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway import health, resilience
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod,
    )
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    provider = StaticProvider([
        PodMetrics(pod=fake_pod(i),
                   metrics=fake_metrics(queue=i % 5, kv=(i % 10) / 10.0))
        for i in range(n_pods)
    ])
    req = LLMRequest(model="m", resolved_target_model="m", critical=True,
                     prompt_tokens=25, criticality="Critical")

    def make_side(policy: str):
        plane = resilience.ResiliencePlane(
            health.HealthScorer(provider=provider),
            cfg=resilience.ResilienceConfig(health_policy=policy))
        plane.health.update()
        # Degrade ONE pod so avoid-mode filtering does real work.
        for _ in range(8):
            plane.health.record_upstream("pod-0", ok=False)
        plane.health.update()
        plane.health.update()
        sched = Scheduler(provider, prefix_aware=False,
                          rng=random_mod.Random(0))
        sched.health_advisor = plane
        return sched

    log_only, avoid = make_side("log_only"), make_side("avoid")

    def loop(sched) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            sched.schedule(req)
        return time.perf_counter() - t0

    loop(log_only), loop(avoid)  # warmup pair
    base_best = avoid_best = float("inf")
    for _ in range(12):
        base_best = min(base_best, loop(log_only))
        avoid_best = min(avoid_best, loop(avoid))
    return {
        "pick_policy_log_only_us": round(base_best / n * 1e6, 2),
        "pick_policy_avoid_us": round(avoid_best / n * 1e6, 2),
        "pick_policy_ratio": round(avoid_best / base_best, 4),
    }


def run_pick_ledger_microbench(n: int = 4000, n_pods: int = 64) -> dict:
    """Decision-ledger overhead A/B (explainability PR acceptance bar:
    ``pick_ledger_ratio`` <= 1.05 — sampled decision records + the
    counterfactual replays, amortized at the default sample_every=8,
    cost < 5% of a pick vs no ledger).

    Same harness shape as ``run_policy_microbench``: a real Python
    filter-tree scheduler over a static fleet with ALL THREE advisor
    planes attached on both sides (the ledger's counterfactual replays
    exercise every seam); the ON side additionally wires a real
    ``PickLedger``.  Interleaved runs, MIN per side.
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway import fairness as fairness_mod
    from llm_instance_gateway_tpu.gateway import health, resilience
    from llm_instance_gateway_tpu.gateway import pickledger
    from llm_instance_gateway_tpu.gateway import placement as placement_mod
    from llm_instance_gateway_tpu.gateway import usage as usage_mod
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod,
    )
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    provider = StaticProvider([
        PodMetrics(pod=fake_pod(i),
                   metrics=fake_metrics(queue=i % 5, kv=(i % 10) / 10.0))
        for i in range(n_pods)
    ])
    req = LLMRequest(model="m", resolved_target_model="m", critical=True,
                     prompt_tokens=25, criticality="Critical")

    def make_side(with_ledger: bool):
        plane = resilience.ResiliencePlane(
            health.HealthScorer(provider=provider))
        plane.health.update()
        rollup = usage_mod.UsageRollup(provider)
        fair = fairness_mod.FairnessPolicy(rollup, provider=provider)
        planner = placement_mod.PlacementPlanner(provider, usage=rollup)
        sched = Scheduler(provider, prefix_aware=False,
                          rng=random_mod.Random(0))
        sched.health_advisor = plane
        sched.usage_advisor = fair
        sched.placement_advisor = planner
        if with_ledger:
            sched.pick_ledger = pickledger.PickLedger(
                cfg=pickledger.PickLedgerConfig(sample_every=8))
        return sched

    off, on = make_side(False), make_side(True)

    def loop(sched) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            sched.schedule(req)
        return time.perf_counter() - t0

    loop(off), loop(on)  # warmup pair
    # Median of PAIRED per-round ratios, not MIN per side: each round
    # times off then on back-to-back so CPU-frequency drift cancels
    # within the pair — a MIN-per-side comparison can attribute a
    # machine-wide slow phase entirely to whichever side it landed on.
    offs, ons, ratios = [], [], []
    for _ in range(12):
        o, w = loop(off), loop(on)
        offs.append(o)
        ons.append(w)
        ratios.append(w / o)
    ratios.sort()
    mid = len(ratios) // 2
    ratio = (ratios[mid] if len(ratios) % 2
             else (ratios[mid - 1] + ratios[mid]) / 2)
    return {
        "pick_ledger_off_us": round(min(offs) / n * 1e6, 2),
        "pick_ledger_on_us": round(min(ons) / n * 1e6, 2),
        "pick_ledger_ratio": round(ratio, 4),
    }


def run_fairness_microbench(n: int = 4000, n_pods: int = 64) -> dict:
    """Fairness pick-deprioritization cost A/B (fairness PR acceptance
    bar: ``pick_fairness_ratio`` <= 1.05 — ``mode=enforce`` costs < 5% of
    a pick vs the policy OFF).

    Same harness shape as ``run_policy_microbench``: a real Python
    filter-tree scheduler over a static fleet, with a REAL FairnessPolicy
    (over a rollup carrying one flagged-noisy adapter resident on part of
    the fleet, so ``filter_by_fairness`` does real narrowing work on every
    pick) vs no advisor at all.  Interleaved runs, MIN per side.
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway import fairness as fairness_mod
    from llm_instance_gateway_tpu.gateway import usage as usage_mod
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod,
    )
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    # A quarter of the fleet hosts the flagged adapter: quiet picks narrow
    # past it every time (the enforcing path's real work).
    provider = StaticProvider([
        PodMetrics(pod=fake_pod(i),
                   metrics=fake_metrics(
                       queue=i % 5, kv=(i % 10) / 10.0,
                       adapters={"hog": 0} if i % 4 == 0 else {},
                       max_adapters=2))
        for i in range(n_pods)
    ])
    req = LLMRequest(model="m", resolved_target_model="m", critical=True,
                     prompt_tokens=25, criticality="Critical")

    rollup = usage_mod.UsageRollup(provider)
    # Flag "hog" directly (the microbench measures pick cost, not
    # detection); seed_noisy keeps the coupled flag tables consistent.
    rollup.seed_noisy("base", "hog")
    plane = fairness_mod.FairnessPolicy(
        rollup, cfg=fairness_mod.FairnessConfig(mode="enforce"),
        provider=provider)

    off = Scheduler(provider, prefix_aware=False, rng=random_mod.Random(0))
    enforce = Scheduler(provider, prefix_aware=False,
                        rng=random_mod.Random(0))
    enforce.usage_advisor = plane

    def loop(sched) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            sched.schedule(req)
        return time.perf_counter() - t0

    loop(off), loop(enforce)  # warmup pair
    base_best = enforce_best = float("inf")
    for _ in range(12):
        base_best = min(base_best, loop(off))
        enforce_best = min(enforce_best, loop(enforce))
    return {
        "pick_fairness_off_us": round(base_best / n * 1e6, 2),
        "pick_fairness_enforce_us": round(enforce_best / n * 1e6, 2),
        "pick_fairness_ratio": round(enforce_best / base_best, 4),
    }


def run_placement_microbench(n: int = 4000, n_pods: int = 64) -> dict:
    """Placement pick-steering cost A/B (placement PR acceptance bar:
    ``pick_placement_ratio`` <= 1.05 — ``prefer_resident`` costs < 5% of
    a pick vs no placement advisor).

    Same harness shape as ``run_fairness_microbench``: a real Python
    filter-tree scheduler over a static fleet whose pods export residency
    tiers (a quarter slot-resident, a quarter host-resident for the
    request's adapter, so ``filter_by_placement`` does real two-level
    narrowing on every pick) with a REAL ticked PlacementPlanner, vs no
    advisor at all.  Interleaved runs, MIN per side.
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway import placement as placement_mod
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod,
    )
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    provider = StaticProvider([
        PodMetrics(pod=fake_pod(i),
                   metrics=fake_metrics(
                       queue=i % 5, kv=(i % 10) / 10.0,
                       adapters={"hot": 0} if i % 4 == 0 else {},
                       max_adapters=2,
                       adapter_tiers=({"hot": "slot"} if i % 4 == 0
                                      else {"hot": "host"} if i % 4 == 1
                                      else {})))
        for i in range(n_pods)
    ])
    req = LLMRequest(model="hot", resolved_target_model="hot",
                     critical=True, prompt_tokens=25,
                     criticality="Critical")
    planner = placement_mod.PlacementPlanner(
        provider, cfg=placement_mod.PlacementConfig(mode="prefer_resident"))
    planner.tick()

    off = Scheduler(provider, prefix_aware=False, rng=random_mod.Random(0))
    steered = Scheduler(provider, prefix_aware=False,
                        rng=random_mod.Random(0))
    steered.placement_advisor = planner

    def loop(sched) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            sched.schedule(req)
        return time.perf_counter() - t0

    loop(off), loop(steered)  # warmup pair
    base_best = steer_best = float("inf")
    for _ in range(12):
        base_best = min(base_best, loop(off))
        steer_best = min(steer_best, loop(steered))
    return {
        "pick_placement_off_us": round(base_best / n * 1e6, 2),
        "pick_placement_resident_us": round(steer_best / n * 1e6, 2),
        "pick_placement_ratio": round(steer_best / base_best, 4),
    }


def run_witness_microbench(n: int = 4000, n_pods: int = 64) -> dict:
    """Lock-witness overhead A/B (concurrency-contract PR acceptance bar:
    ``pick_witness_ratio`` <= 1.05 — running with LIG_LOCK_WITNESS armed
    costs < 5% of a pick vs plain locks, so the whole test suite can stay
    witnessed without taxing anything).

    Same harness shape as ``run_policy_microbench``: a real Python
    filter-tree scheduler + ResiliencePlane advisor + GatewayMetrics
    recording, so each pick crosses the three hot-path locks the witness
    wraps (health note_pick, breaker note_pick, pick-latency record).  The
    witness arms at LOCK CONSTRUCTION time, so each side builds its whole
    stack under its own env setting.  Interleaved runs, MIN per side.
    """
    import os as os_mod
    import random as random_mod

    from llm_instance_gateway_tpu import lockwitness
    from llm_instance_gateway_tpu.gateway import health, resilience
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.telemetry import GatewayMetrics
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, fake_pod,
    )
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    req = LLMRequest(model="m", resolved_target_model="m", critical=True,
                     prompt_tokens=25, criticality="Critical")

    def make_side(armed: bool):
        prev = os_mod.environ.get(lockwitness.ENV)
        os_mod.environ[lockwitness.ENV] = "1" if armed else "0"
        try:
            provider = StaticProvider([
                PodMetrics(pod=fake_pod(i),
                           metrics=fake_metrics(queue=i % 5,
                                                kv=(i % 10) / 10.0))
                for i in range(n_pods)
            ])
            plane = resilience.ResiliencePlane(
                health.HealthScorer(provider=provider))
            plane.health.update()
            gm = GatewayMetrics()
            sched = Scheduler(provider, prefix_aware=False,
                              rng=random_mod.Random(0))
            sched.health_advisor = plane
        finally:
            if prev is None:
                os_mod.environ.pop(lockwitness.ENV, None)
            else:
                os_mod.environ[lockwitness.ENV] = prev
        return sched, gm

    plain, armed = make_side(False), make_side(True)

    def loop(side) -> float:
        sched, gm = side
        t0 = time.perf_counter()
        for _ in range(n):
            pod = sched.schedule(req)
            gm.record_pick(pod.name, 0.0, False)
        return time.perf_counter() - t0

    loop(plain), loop(armed)  # warmup pair
    off_best = on_best = float("inf")
    for _ in range(12):
        off_best = min(off_best, loop(plain))
        on_best = min(on_best, loop(armed))
    return {
        "pick_witness_off_us": round(off_best / n * 1e6, 2),
        "pick_witness_on_us": round(on_best / n * 1e6, 2),
        "pick_witness_ratio": round(on_best / off_best, 4),
    }


def run_decode_lever_microbench(emit_lanes: bool = False) -> dict:
    """Decode fast-path lever family (CPU-deterministic; ROADMAP item 2).

    Three A/Bs over one micro model (so per-dispatch host overhead, the
    thing multi-step fusion amortizes, is a visible share of the wall):

    - **adaptive multi-step dispatch**: decode tok/s at the seed settings
      (steps=1, host stops) vs the fast path (``adaptive_steps=8`` +
      device-side stops).  ``decode_adaptive_speedup`` is the PR's pinned
      >= 2x acceptance bar, gated absolutely by tools/bench_check.py.
    - **device-side stop strings**: wall with stop sequences riding the
      device automaton vs the host oracle (stops present, never matching)
      — bounds the automaton's overhead (``device_stops_ratio``).
    - **concurrent chunk-stream lanes**: a long prompt ahead of a shorter
      long prompt plus short decode traffic, 1 lane vs 2: the second
      prompt's TTFT no longer serializes behind the first
      (``stream_second_ttft_ratio``), with the lane-occupancy histogram
      (``emit_lanes=True``) as the committed evidence artifact.

    MIN over interleaved rounds per side, the suite convention.
    """
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )

    # Micro model: small enough that the per-dispatch host tax dominates a
    # single step.
    cfg = dataclasses.replace(
        LLAMA3_8B, name="lever-cpu", vocab_size=128, d_model=64,
        n_layers=1, n_heads=2, n_kv_heads=1, d_ff=128, head_dim=32,
        max_seq_len=512,
        # XLA paths: the Pallas kernels run interpreted off-TPU and would
        # time the interpreter, not the engine.
        use_flash_attention=False, use_pallas_decode=False,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    base = dict(decode_slots=4, max_seq_len=512, prefill_buckets=(16,))
    rng = np.random.RandomState(0)

    def engine(**kw):
        e = Engine(cfg, params, EngineConfig(**base, **kw), eos_id=None,
                   dtype=jnp.float32)
        e.start()
        return e

    def reqs(n, prompt_len, max_new, stops=()):
        return [
            Request(prompt_tokens=list(rng.randint(1, 120, size=prompt_len)),
                    max_new_tokens=max_new,
                    sampling=SamplingParams(temperature=0.0),
                    stop_sequences=tuple(tuple(s) for s in stops))
            for _ in range(n)
        ]

    def decode_wall(e, stops=()) -> tuple[float, int]:
        rs = reqs(4, 16, 64, stops=stops)
        t0 = time.perf_counter()
        for r in rs:
            e.submit(r)
        for r in rs:
            if not r.done.wait(300):
                raise RuntimeError("decode-lever request timed out")
        wall = time.perf_counter() - t0
        return wall, sum(len(r.output_tokens) for r in rs)

    out: dict = {}
    seed_e = engine(decode_steps_per_sync=1, device_stops=False)
    fast_e = engine(adaptive_steps=8, device_stops=True)
    try:
        decode_wall(seed_e), decode_wall(fast_e)  # warmup/compile pair
        seed_best = fast_best = float("inf")
        toks = 0
        for _ in range(3):
            w, toks = decode_wall(seed_e)
            seed_best = min(seed_best, w)
            w, _ = decode_wall(fast_e)
            fast_best = min(fast_best, w)
        out["decode_step1_tok_s"] = round(toks / seed_best, 1)
        out["decode_adaptive_tok_s"] = round(toks / fast_best, 1)
        out["decode_adaptive_speedup"] = round(seed_best / fast_best, 4)

        # Device automaton overhead: stops present, never matching (token
        # 127 is excluded from the random prompts and unlikely greedy; a
        # match would only shorten both sides identically anyway).
        stops = [(127, 126, 125), (124, 123)]
        host_e = engine(adaptive_steps=8, device_stops=False)
        try:
            decode_wall(fast_e, stops), decode_wall(host_e, stops)
            on_best = off_best = float("inf")
            for _ in range(3):
                off_best = min(off_best, decode_wall(host_e, stops)[0])
                on_best = min(on_best, decode_wall(fast_e, stops)[0])
            out["device_stops_on_s"] = round(on_best, 4)
            out["device_stops_off_s"] = round(off_best, 4)
            out["device_stops_ratio"] = round(on_best / off_best, 4)
        finally:
            host_e.stop()
    finally:
        seed_e.stop()
        fast_e.stop()

    # -- chunk-stream lanes: head-of-line A/B ------------------------------
    long_a = list(rng.randint(1, 120, size=160))   # 10 chunks of 16
    long_b = list(rng.randint(1, 120, size=48))    # 3 chunks: the victim
    shorts = [list(rng.randint(1, 120, size=8)) for _ in range(2)]

    def lane_run(e):
        occupancy: dict[int, int] = {}
        ra = Request(prompt_tokens=long_a, max_new_tokens=8,
                     sampling=SamplingParams(temperature=0.0))
        rb = Request(prompt_tokens=long_b, max_new_tokens=8,
                     sampling=SamplingParams(temperature=0.0))
        rs = [Request(prompt_tokens=p, max_new_tokens=8,
                      sampling=SamplingParams(temperature=0.0))
              for p in shorts]
        t0 = time.perf_counter()
        for r in (ra, rb, *rs):
            e.submit(r)
        while not all(r.done.is_set() for r in (ra, rb, *rs)):
            n = len(e._streams)
            occupancy[n] = occupancy.get(n, 0) + 1
            time.sleep(0.0002)
        wall = time.perf_counter() - t0
        for r in (ra, rb, *rs):
            if r.error:
                raise RuntimeError(f"lane bench request failed: {r.error}")
        return wall, rb.ttft_s, occupancy

    # One engine per side, warmed with a throwaway pass so the chunk /
    # decode programs compile OUTSIDE the measured window (each Engine
    # owns fresh jit objects), then MIN TTFT over rounds.
    one_e = engine(stream_lanes=1)
    two_e = engine(stream_lanes=2)
    # Occupancy accumulates across EVERY round (warmup included): the
    # per-round samples come from a polling thread, so any single round
    # can miss the overlap window — but the stream_lanes_max_active gate
    # (== 2) only needs the overlap observed ONCE across the whole run.
    occ_all_1: dict[int, int] = {}
    occ_all_2: dict[int, int] = {}

    def merge(dst: dict, src: dict) -> None:
        for k, v in src.items():
            dst[k] = dst.get(k, 0) + v
    try:
        merge(occ_all_1, lane_run(one_e)[2])  # warmup/compile pair
        merge(occ_all_2, lane_run(two_e)[2])
        wall_1 = ttft_b_1 = wall_2 = ttft_b_2 = float("inf")
        for _ in range(3):
            w, t, o = lane_run(one_e)
            merge(occ_all_1, o)
            if t < ttft_b_1:
                wall_1, ttft_b_1 = w, t
            w, t, o = lane_run(two_e)
            merge(occ_all_2, o)
            if t < ttft_b_2:
                wall_2, ttft_b_2 = w, t
    finally:
        one_e.stop()
        two_e.stop()
    out["stream_serialized_wall_s"] = round(wall_1, 4)
    out["stream_dual_wall_s"] = round(wall_2, 4)
    out["stream_second_ttft_1lane_ms"] = round(ttft_b_1 * 1e3, 2)
    out["stream_second_ttft_2lane_ms"] = round(ttft_b_2 * 1e3, 2)
    out["stream_second_ttft_ratio"] = round(
        ttft_b_1 / ttft_b_2, 4) if ttft_b_2 > 0 else 0.0
    out["stream_lanes_max_active"] = max(occ_all_2) if occ_all_2 else 0
    if emit_lanes:
        out["lane_occupancy"] = {
            "one_lane_samples": {str(k): v
                                 for k, v in sorted(occ_all_1.items())},
            "two_lane_samples": {str(k): v
                                 for k, v in sorted(occ_all_2.items())},
        }
    return out


def run_profiler_microbench(emit_profile: bool = False,
                            fast_path: bool = False) -> dict:
    """Step-timeline-profiler overhead A/B (fleet-observability PR
    acceptance bar: ``step_profile_ratio`` <= 1.05 — profiling every
    dispatch costs < 5% of step-loop wall).

    Two tiny CPU engines run the same decode-heavy workload, profiler ON
    (the default) vs ``step_profile=False``; interleaved rounds, MIN per
    side (the usage-attribution A/B precedent — contended cores swing
    single runs 2x).  ``emit_profile=True`` additionally returns the ON
    engine's profiler snapshot — the deterministic run committed as
    ``PROFILE_BASELINE.json`` (the dispatch/host-sync/idle attribution
    baseline every ROADMAP item-2 lever is measured against).
    ``fast_path=True`` runs both engines with the decode levers on
    (adaptive fused dispatch + device-side stops) — the post-lever
    attribution the refreshed baseline commits, whose host-sync share
    must sit strictly below the pre-lever baseline's.
    """
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )

    cfg = dataclasses.replace(
        LLAMA3_8B, name="profiler-cpu", vocab_size=512, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, head_dim=32,
        max_seq_len=256,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    ecfg = dict(decode_slots=4, max_seq_len=256,
                prefill_buckets=(32, 64, 128))
    if fast_path:
        ecfg["adaptive_steps"] = 8
    rng = np.random.RandomState(0)

    def engine(**kw):
        e = Engine(cfg, params, EngineConfig(**ecfg, **kw), eos_id=None,
                   dtype=jnp.float32)
        e.start()
        return e

    def req(prompt_len, max_new):
        return Request(
            prompt_tokens=list(rng.randint(1, 500, size=prompt_len)),
            max_new_tokens=max_new,
            sampling=SamplingParams(temperature=0.0))

    def decode_wall(e) -> float:
        rs = [req(16, 24) for _ in range(4)]
        t0 = time.perf_counter()
        for r in rs:
            e.submit(r)
        for r in rs:
            if not r.done.wait(300):
                raise RuntimeError("profiler A/B request timed out")
        return time.perf_counter() - t0

    on_engine = engine()
    off_engine = engine(step_profile=False)
    try:
        decode_wall(on_engine), decode_wall(off_engine)  # warmup pair
        on_best = off_best = float("inf")
        for _ in range(3):
            off_best = min(off_best, decode_wall(off_engine))
            on_best = min(on_best, decode_wall(on_engine))
        out = {
            "step_profile_on_s": round(on_best, 4),
            "step_profile_off_s": round(off_best, 4),
            "step_profile_ratio": round(on_best / off_best, 4),
        }
        if emit_profile:
            out["profile"] = on_engine.profiler.snapshot()
    finally:
        on_engine.stop()
        off_engine.stop()
    return out


def run_kv_ledger_microbench() -> dict:
    """KV block-lifecycle ledger overhead A/B (KV-economy PR acceptance
    bar: ``kv_ledger_ratio`` < 1.05 — charging every alloc/reuse/release
    plus the per-scrape state recount costs < 5% of paged-engine wall).

    Two tiny paged-KV CPU engines run the same shared-prefix workload
    (the reuse path is the hottest ledger charge site), ledger ON (the
    default) vs ``kv_ledger=False``; interleaved rounds, MIN per side
    (the step-profiler A/B precedent).  Each round also scrapes
    ``metrics_snapshot()`` once per request batch, so the ledger's
    snapshot/render cost is inside the measured wall, as in production.
    """
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )

    cfg = dataclasses.replace(
        LLAMA3_8B, name="kvledger-cpu", vocab_size=512, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, head_dim=32,
        max_seq_len=256,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    ecfg = dict(decode_slots=4, max_seq_len=256,
                prefill_buckets=(32, 64), paged_kv_block=8,
                prefix_cache=True)
    rng = np.random.RandomState(0)
    shared = list(rng.randint(1, 500, size=16))  # two full shared blocks

    def engine(**kw):
        e = Engine(cfg, params, EngineConfig(**ecfg, **kw), eos_id=None,
                   dtype=jnp.float32)
        e.start()
        return e

    def wall(e) -> float:
        rs = [Request(
            prompt_tokens=shared + list(rng.randint(1, 500, size=8)),
            max_new_tokens=16,
            sampling=SamplingParams(temperature=0.0)) for _ in range(4)]
        t0 = time.perf_counter()
        for r in rs:
            e.submit(r)
        for r in rs:
            if not r.done.wait(300):
                raise RuntimeError("kv ledger A/B request timed out")
        e.metrics_snapshot()  # the scrape rides the measured wall
        return time.perf_counter() - t0

    on_engine = engine()
    off_engine = engine(kv_ledger=False)
    try:
        wall(on_engine), wall(off_engine)  # warmup pair
        on_best = off_best = float("inf")
        for _ in range(3):
            off_best = min(off_best, wall(off_engine))
            on_best = min(on_best, wall(on_engine))
        return {
            "kv_ledger_on_s": round(on_best, 4),
            "kv_ledger_off_s": round(off_best, 4),
            "kv_ledger_ratio": round(on_best / off_best, 4),
        }
    finally:
        on_engine.stop()
        off_engine.stop()


def run_capacity_microbench(n_pods: int = 16, n_ticks: int = 192) -> dict:
    """Capacity-plane tick overhead A/B (capacity-twin PR acceptance bar:
    ``capacity_tick_ratio`` < 1.05 — enabling ``CapacityPlanner`` on the
    observability cadence costs < 5% of the control-tick composite the
    proxy already runs every period).

    Both sides drive the REAL composite — the full advisor stack
    (health/breaker, usage, kvobs, fairness, placement, pickledger), the
    SLO engine, and the statebus snapshot/apply, exactly
    ``GatewayProxy.control_tick``'s synchronous pass — over an identical
    deterministic schedule of advancing pod accumulators; the ON side
    flips ``CapacityConfig.enabled``.  The planner's clock is pinned to
    a virtual 5s-per-tick clock (the default obs cadence) so the
    ``min_window_s`` window floor folds on its production duty cycle —
    one fold per 6 ticks, clock-compare early-returns between — instead
    of collapsing to a single fold at bench speed.  192 ticks per round
    = 32 folds = exactly one ``refit_every_ticks`` self-calibration, so
    the refit spike lands once per round instead of jittering the
    per-round ratios.  Self-calibration
    refits ride the measured wall (they amortize at
    ``refit_every_ticks``, as in production) but the DES knee probes are
    excluded: their cadence is a config knob whose cost ``make
    sim-check`` pins, not a per-tick tax.  The workload advance runs
    OUTSIDE the timed region (it is load synthesis, not observability
    work — leaving it in would pad both sides and flatter the ratio).
    The two sides interleave per tick (off-tick then on-tick, same
    virtual instant) and each tick index is timed separately; the
    reported ratio compares per-side sums of PER-TICK-INDEX medians
    across rounds.  An OS or GC hiccup lands in one tick of one round
    and that tick's cross-round median rejects it, while structural
    cost — including the refit tick — survives because it recurs at
    the same tick index every round.
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway.advisors import AdvisorStack
    from llm_instance_gateway_tpu.gateway.capacity import CapacityConfig
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.slo import SLOEngine
    from llm_instance_gateway_tpu.gateway.statebus import StateBus
    from llm_instance_gateway_tpu.gateway.telemetry import GatewayMetrics
    from llm_instance_gateway_tpu.gateway.testing import fake_metrics, fake_pod
    from llm_instance_gateway_tpu.gateway.types import PodMetrics

    rng = random_mod.Random(0)
    # Per-pod per-tick accumulator increments, precomputed once so both
    # sides (and every round) replay identical scrape content.
    plan = [[(0.02 * (1 + rng.random()), 20.0,
              1.5 * (1 + rng.random()), 3000.0,
              5 * 0.25 * (1 + rng.random()), 5.0,
              20.0 * rng.randint(120, 260), 20.0 * rng.randint(130, 170),
              # KV free varies independently of batch so calibration
              # windows stay full-rank: the twin actually FITS and the
              # ON side pays the real steady-state path (drift
              # predictions + amortized refits), not the degenerate
              # fit-rejected one.
              200000 - rng.randint(20000, 160000))
             for _ in range(n_pods)]
            for _ in range(n_ticks)]

    def make_side(enabled: bool):
        pods = [PodMetrics(pod=fake_pod(i),
                           metrics=fake_metrics(
                               queue=i % 5, kv=(i % 10) / 10.0,
                               adapters={f"adapter-{i}-{j}": 0
                                         for j in range(4)}))
                for i in range(n_pods)]
        for pm in pods:
            pm.metrics.kv_tokens_capacity = 200000
            pm.metrics.kv_tokens_free = 180000
            pm.metrics.running_queue_size = 4
        gw_metrics = GatewayMetrics()
        stack = AdvisorStack(
            "pool", StaticProvider(pods), metrics=gw_metrics,
            capacity_cfg=CapacityConfig(enabled=enabled,
                                        forecast_every_ticks=10 ** 9))
        slo = SLOEngine(gw_metrics)
        bus = StateBus({"pool": stack})
        clock = [1000.0]
        stack.capacity._clock = lambda: clock[0]

        def advance(tick_i: int) -> None:
            # Production-shaped load: 4 models on the SLO engine, one
            # token-attribution entry per {adapter, phase} per pod — the
            # multi-tenant tables the usage plane exists to roll up, not
            # a single-model toy that would understate the base.
            clock[0] += 5.0
            for j in range(4):
                gw_metrics.record_request("m%d" % j)
                gw_metrics.record_phase("m%d" % j, "/v1/completions",
                                        ttft_s=0.05, tpot_s=0.02,
                                        e2e_s=3.0)
            for i, (pm, inc) in enumerate(zip(pods,
                                              plan[tick_i % n_ticks])):
                m = pm.metrics
                m.prefill_seconds_sum += inc[0]
                m.prefill_seconds_count += inc[1]
                m.decode_step_seconds_sum += inc[2]
                m.decode_step_seconds_count += inc[3]
                m.decode_batch_occupancy_sum += inc[4]
                m.decode_batch_occupancy_count += inc[5]
                m.kv_tokens_free = inc[8]
                at = m.adapter_tokens
                for j in range(4):
                    for value, phase in ((inc[6] / 4.0, "prefill"),
                                         (inc[7] / 4.0, "decode")):
                        k = ("m%d" % j, "adapter-%d-%d" % (i, j), phase)
                        at[k] = at.get(k, 0.0) + value
        return stack, slo, bus, advance

    off_side, on_side = make_side(False), make_side(True)
    perf = time.perf_counter

    def timed_tick(side, i: int) -> float:
        stack, slo, bus, advance = side
        advance(i)
        t0 = perf()
        stack.tick()
        slo.tick()
        bus.tick()
        return perf() - t0

    n_rounds = 16
    # off_t[r][i] / on_t[r][i]: wall of tick i in round r.
    off_t = [[0.0] * n_ticks for _ in range(n_rounds)]
    on_t = [[0.0] * n_ticks for _ in range(n_rounds)]
    for i in range(n_ticks):  # warmup round (untimed)
        timed_tick(off_side, i), timed_tick(on_side, i)
    for r in range(n_rounds):
        for i in range(n_ticks):
            off_t[r][i] = timed_tick(off_side, i)
            on_t[r][i] = timed_tick(on_side, i)

    def col(rows: list, i: int) -> list:
        return sorted(rows[r][i] for r in range(n_rounds))

    total_off = total_on = min_off = min_on = 0.0
    mid = n_rounds // 2
    for i in range(n_ticks):
        o, w = col(off_t, i), col(on_t, i)
        total_off += (o[mid - 1] + o[mid]) / 2
        total_on += (w[mid - 1] + w[mid]) / 2
        min_off += o[0]
        min_on += w[0]
    return {
        "capacity_tick_off_us": round(min_off / n_ticks * 1e6, 2),
        "capacity_tick_on_us": round(min_on / n_ticks * 1e6, 2),
        "capacity_tick_ratio": round(total_on / total_off, 4),
    }


def run_native_pick_microbench(n: int = 4000, n_pods: int = 200,
                               n_models: int = 1000,
                               batch: int = 64) -> dict:
    """Snapshot-resident native pick cost (the data-plane fast path).

    200 pods x 1000 adapters — the LOADGEN fixture scale — over a REAL
    versioned ``Provider`` so the resident state marshals once and every
    pick crosses the FFI with request scalars only.  Three measurements,
    MIN over interleaved runs (contended-core precedent from the other
    microbenches):

    - ``pick_native_us``: one ``schedule()`` = one ``lig_pick`` crossing.
    - ``pick_many_us``: per-pick cost with ``batch`` requests amortized
      into ONE ``lig_pick_many`` crossing.
    - ``pick_python_us``: the Python oracle on the SAME fixture, and
      ``pick_native_speedup`` = python/native — the compute-only gap the
      e2e loadgen ratio is chasing.
    """
    import random as random_mod

    from llm_instance_gateway_tpu.gateway.scheduling import native
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest
    from llm_instance_gateway_tpu.gateway.testing import (
        build_handler_server, fake_metrics, fake_pod, make_model,
    )

    if not native.available():
        return {"native_pick_error": "libligsched.so unavailable"}
    per_pod = max(1, n_models // n_pods)
    pods = {
        fake_pod(i): fake_metrics(
            queue=i % 5, kv=(i % 10) / 10.0,
            adapters={f"adapter-{i * per_pod + j}": 0
                      for j in range(per_pod)},
            max_adapters=per_pod + 1)
        for i in range(n_pods)
    }
    models = [make_model(f"adapter-{i}") for i in range(n_models)]
    # build_handler_server gives a versioned Provider (snapshot cache key).
    provider = build_handler_server(pods, models).scheduler._provider
    nat = native.NativeScheduler(provider, rng=random_mod.Random(0))
    py = Scheduler(provider, rng=random_mod.Random(0), prefix_aware=False)
    reqs = [
        LLMRequest(model=f"adapter-{i % n_models}",
                   resolved_target_model=f"adapter-{i % n_models}",
                   critical=True, prompt_tokens=25, criticality="Critical")
        for i in range(256)
    ]

    def loop_single(sched) -> float:
        t0 = time.perf_counter()
        for i in range(n):
            sched.schedule(reqs[i % len(reqs)])
        return time.perf_counter() - t0

    def loop_many() -> float:
        t0 = time.perf_counter()
        done = 0
        while done < n:
            take = min(batch, n - done)
            nat.pick_many([reqs[(done + k) % len(reqs)]
                           for k in range(take)])
            done += take
        return time.perf_counter() - t0

    loop_single(nat), loop_many(), loop_single(py)  # warmup
    nat_best = many_best = py_best = float("inf")
    for _ in range(8):
        nat_best = min(nat_best, loop_single(nat))
        many_best = min(many_best, loop_many())
        py_best = min(py_best, loop_single(py))
    return {
        "pick_native_us": round(nat_best / n * 1e6, 2),
        "pick_many_us": round(many_best / n * 1e6, 2),
        "pick_python_us": round(py_best / n * 1e6, 2),
        "pick_native_speedup": round(py_best / nat_best, 2),
        "native_picks_per_s": round(n / nat_best, 1),
    }


def run_relay_microbench(n_chunks: int = 256, chunk_bytes: int = 160,
                         rounds: int = 6) -> dict:
    """Zero-copy relay A/B: chunks/s through the REAL proxy relay loop,
    fast (verbatim write + tail references) vs slow (per-chunk line
    re-framing) — same upstream script, same sockets, interleaved rounds
    with MAX throughput per side (the µbench the regression gate rides).
    """
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from llm_instance_gateway_tpu.api.v1alpha1 import InferencePool
    from llm_instance_gateway_tpu.gateway import resilience
    from llm_instance_gateway_tpu.gateway.datastore import Datastore
    from llm_instance_gateway_tpu.gateway.handlers.server import Server
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.proxy import GatewayProxy
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.testing import (
        fake_metrics, make_model,
    )
    from llm_instance_gateway_tpu.gateway.types import Pod, PodMetrics

    filler = b'data: {"choices": [{"index": 0, "text": "' + \
        b"x" * max(1, chunk_bytes - 60) + b'"}]}\n\n'
    final = (b'data: {"choices": [{"index": 0, "text": "."}], '
             b'"usage": {"prompt_tokens": 7, "completion_tokens": 3}}\n\n')

    async def measure() -> dict:
        async def completions(request: web.Request) -> web.StreamResponse:
            resp = web.StreamResponse(
                status=200, headers={"Content-Type": "text/event-stream"})
            await resp.prepare(request)
            for _ in range(n_chunks - 2):
                await resp.write(filler)
            await resp.write(final)
            await resp.write(b"data: [DONE]\n\n")
            return resp

        app = web.Application()
        app.router.add_post("/v1/completions", completions)
        up = TestServer(app)
        await up.start_server()

        async def one_side(fast: bool):
            pods = {Pod("p", f"127.0.0.1:{up.port}"): fake_metrics()}
            ds = Datastore(pods=list(pods))
            ds.set_pool(InferencePool(name="pool"))
            ds.store_model(make_model("m"))
            provider = StaticProvider(
                [PodMetrics(pod=p, metrics=m) for p, m in pods.items()])
            proxy = GatewayProxy(
                Server(Scheduler(provider, token_aware=False,
                                 prefill_aware=False, prefix_aware=False),
                       ds),
                provider, ds,
                resilience_cfg=resilience.ResilienceConfig(),
                fast_relay=fast)
            client = TestClient(TestServer(proxy.build_app()))
            await client.start_server()

            async def one_round() -> float:
                t0 = time.perf_counter()
                resp = await client.post(
                    "/v1/completions",
                    json={"model": "m", "prompt": "x", "stream": True})
                raw = await resp.read()
                wall = time.perf_counter() - t0
                assert resp.status == 200 and raw.endswith(
                    b"data: [DONE]\n\n")
                return wall

            return client, one_round

        fast_client, fast_round = await one_side(True)
        slow_client, slow_round = await one_side(False)
        try:
            await fast_round(), await slow_round()  # warmup pair
            fast_best = slow_best = float("inf")
            for _ in range(rounds):
                fast_best = min(fast_best, await fast_round())
                slow_best = min(slow_best, await slow_round())
        finally:
            await fast_client.close()
            await slow_client.close()
            await up.close()
        return {
            "relay_fast_chunks_per_s": round(n_chunks / fast_best, 1),
            "relay_slow_chunks_per_s": round(n_chunks / slow_best, 1),
            # >= 1.0: verbatim relay at least matches the line scanner.
            "relay_fast_ratio": round(slow_best / fast_best, 4),
        }

    return asyncio.run(measure())


# Per-chip peaks, keyed by ``jax.devices()[0].device_kind``.  A device that
# is not here is an error, not a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s HBM bandwidth,
    # 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py: no published peaks for device_kind {device_kind!r}; "
            f"add it to DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})") from None


def _param_bytes(params) -> int:
    """Total bytes the decode step streams from HBM for weights (int8
    weight-only quant counts 1 byte/param + f32 scales)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total


def _roofline_probes(engine, cfg, params, b_slots: int, peaks: dict) -> dict:
    """Measure decode HBM-roofline fraction and prefill MFU against
    ``peaks`` (``device_peaks`` of the device the bench runs on).

    - Decode probe: exactly ``b_slots`` short-prompt/long-output requests so
      every decode step runs full-batch; achieved HBM bytes/s = (weight
      bytes + mean KV-read bytes per step) x steps/s vs the v5e peak.
    - Prefill probe: bucket-sized prompts, 1 new token each; MFU = dense
      forward FLOPs (2 x params x tokens) / wall vs bf16 peak.

    Both are conservative: they ignore activation traffic (decode) and
    attention FLOPs (prefill), so the reported fractions are lower bounds
    on hardware utilization.
    """
    hd = cfg.resolved_head_dim
    # Counts EVERY leaf (embeddings and quant scales included): 2*N*T is an
    # approximation of dense forward FLOPs and the extra leaves overstate it
    # by a few percent at these shapes — acceptable for a roofline FRACTION.
    n_params = sum(l.size for l in jax.tree.leaves(params))
    w_bytes = _param_bytes(params)
    kv_itemsize = jax.tree.leaves(engine.cache)[0].dtype.itemsize

    # --- decode probe ---
    prompt, new = 16, 96
    r = run_phase(engine, b_slots, prompt, new, adapters=[])
    steps_per_s = r["tok_per_s"] / b_slots
    mean_len = prompt + new / 2
    kv_bytes_per_step = (
        b_slots * cfg.n_layers * 2 * mean_len * cfg.n_kv_heads * hd
        * kv_itemsize)
    decode_hbm_frac = (
        (w_bytes + kv_bytes_per_step) * steps_per_s / peaks["hbm_bytes_per_s"])

    # --- prefill probe ---
    pf_prompt = 256
    n_pf = 16
    t0 = time.perf_counter()
    rp = run_phase(engine, n_pf, pf_prompt, 1, adapters=[])
    pf_wall = time.perf_counter() - t0
    pf_flops = 2.0 * n_params * n_pf * pf_prompt
    prefill_mfu = pf_flops / pf_wall / peaks["bf16_flops"]

    return {
        "decode_tok_per_s_fullbatch": round(r["tok_per_s"], 1),
        "decode_hbm_frac": round(decode_hbm_frac, 4),
        "prefill_mfu": round(prefill_mfu, 4),
        "ttft_p50_ms": round(rp["ttft_p50_ms"], 1),
        "ttft_p99_ms": round(rp["ttft_p99_ms"], 1),
    }


def main() -> None:
    from llm_instance_gateway_tpu import runtime
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig
    from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

    device = runtime.require_accelerator("bench.py")
    peaks = device_peaks(device.device_kind)
    runtime.configure_compile_cache()
    cfg = bench_model_cfg()
    dtype = jnp.bfloat16
    n_requests, prompt_len, max_new = 48, 100, 64

    # Weight-only int8: halves the HBM weight traffic decode is bound by.
    # Applied to BOTH phases, so the north-star ratio stays apples-to-apples.
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype,
                                     quantize=True)
    engine_cfg = EngineConfig(
        decode_slots=16,
        max_seq_len=cfg.max_seq_len,
        prefill_buckets=(128, 256),
        # Step knobs as the earlier rounds left them; on this host: not
        # measured (ROADMAP Speed 4a/6 re-derive them).
        decode_steps_per_sync=32,
        pipeline_decode=True,
        # Burst admission: all 48 requests arrive at once and share buckets,
        # so grouped prefill collapses the admission phase from ~48
        # dispatches to ~12 (applies identically to both phases — the
        # north-star ratio stays apples-to-apples).
        prefill_batch=4,
    )

    # Two engines over SHARED params: the TRUE single-tenant baseline
    # (lora_manager=None compiles a delta-free program — the honest
    # denominator) and the multiplexed engine with 4 resident adapters.
    # Phases are INTERLEAVED (A B A B ...) with the pair order alternating,
    # so phase-order bias and a slow window can't skew the ratio.
    baseline_engine = Engine(cfg, params, engine_cfg, lora_manager=None,
                             eos_id=None, dtype=dtype)
    lora = LoRAManager(cfg, dtype=dtype)
    multi_engine = Engine(cfg, params, engine_cfg, lora_manager=lora,
                          eos_id=None, dtype=dtype)
    baseline_engine.start()
    multi_engine.start()
    try:
        adapter_names = []
        for i in range(cfg.max_lora_slots):
            name = f"bench-adapter-{i}"
            lora.load(name, weights=make_adapter_weights(cfg, rank=8, seed=i),
                      alpha=16.0, rank=8)
            adapter_names.append(name)
        run_phase(baseline_engine, 2, prompt_len, 4, adapters=[])  # warm-up A
        run_phase(multi_engine, 2, prompt_len, 4, adapters=adapter_names)  # warm-up B
        multis, ratios = [], []
        best_multi_stats = None
        for s in range(3):
            def sample_single():
                return run_phase(baseline_engine, n_requests, prompt_len,
                                 max_new, adapters=[])["tok_per_s"]

            def sample_multi():
                return run_phase(multi_engine, n_requests, prompt_len,
                                 max_new, adapters=adapter_names)

            if s % 2 == 0:
                a, bs = sample_single(), sample_multi()
            else:
                bs, a = sample_multi(), sample_single()
            multis.append(bs["tok_per_s"])
            if bs["tok_per_s"] == max(multis):
                best_multi_stats = bs
            ratios.append(bs["tok_per_s"] / a)

        # Efficiency, not just a ratio: where the measured throughput sits
        # against the device's published HBM/MXU peaks.
        roofline = _roofline_probes(
            baseline_engine, cfg, params, engine_cfg.decode_slots, peaks)
    finally:
        baseline_engine.stop()
        multi_engine.stop()

    ratios.sort()
    print(json.dumps({
        "metric": "multiplexed_lora_tokens_per_sec",
        "value": round(max(multis), 2),
        "unit": "tok/s",
        "vs_baseline": round(ratios[len(ratios) // 2], 4),
        "multiplexed_ttft_p50_ms": round(best_multi_stats["ttft_p50_ms"], 1),
        "multiplexed_ttft_p99_ms": round(best_multi_stats["ttft_p99_ms"], 1),
        **roofline,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": device.count},
    }), flush=True)


if __name__ == "__main__":
    if "--handoff-microbench" in sys.argv:
        # The device-independent CPU microbenches (each also reachable
        # through tools/bench_check.py).  A crash in any is a crash.
        results = run_handoff_microbench()
        for phase in (run_pick_microbench, run_policy_microbench,
                      run_fairness_microbench, run_placement_microbench,
                      run_native_pick_microbench, run_relay_microbench,
                      run_profiler_microbench, run_witness_microbench,
                      run_kv_ledger_microbench, run_pick_ledger_microbench,
                      run_capacity_microbench):
            results.update(phase())
        print(json.dumps(results), flush=True)
    else:
        main()
