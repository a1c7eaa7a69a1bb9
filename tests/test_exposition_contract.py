"""Exposition contract: every /metrics surface round-trips the parser.

Malformed Prometheus lines historically failed only at SCRAPE time (an
operator's Prometheus silently dropping the page); this suite makes them
fail tier-1 instead.  Both render paths — the gateway's
``GatewayMetrics.render`` (proxy /metrics) and the server's
``server.metrics.render`` (api_http /metrics) — are exercised through real
aiohttp endpoints, parsed with ``utils/prom_parse.py``, and linted for
histogram invariants (cumulative ``le`` buckets, ``+Inf`` == ``_count``)
and TYPE coverage.
"""

import asyncio
import math

from aiohttp.test_utils import TestClient, TestServer

from llm_instance_gateway_tpu import tracing
from llm_instance_gateway_tpu.gateway.telemetry import GatewayMetrics
from llm_instance_gateway_tpu.server import metrics as server_metrics
from llm_instance_gateway_tpu.utils import prom_parse

HOSTILE = 'evil"model\nname\\tenant'


def lint_exposition(text: str) -> dict:
    """Parse + validate one exposition page; returns the parsed families.

    Checks:
    - every non-comment line parsed into a sample (no silent drops);
    - every family has a ``# TYPE`` comment (base name for histogram
      component series);
    - histogram families: ``le`` values are parseable floats ending in
      ``+Inf``, bucket counts are cumulative, and the ``+Inf`` bucket
      equals ``_count``.
    """
    families = prom_parse.parse_text(text)
    types: dict[str, str] = {}
    n_samples = 0
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
        elif line and not line.startswith("#"):
            n_samples += 1
    assert n_samples == sum(len(v) for v in families.values()), (
        "some exposition lines failed to parse")

    def base_name(fam: str) -> str:
        for suffix in ("_bucket", "_sum", "_count", "_total"):
            if fam.endswith(suffix) and fam[: -len(suffix)] in types:
                return fam[: -len(suffix)]
        return fam

    for fam in families:
        assert base_name(fam) in types, f"family {fam} has no TYPE line"

    for name, kind in types.items():
        if kind != "histogram":
            continue
        buckets = families.get(name + "_bucket", [])
        counts = families.get(name + "_count", [])
        assert buckets and counts, f"histogram {name} missing series"
        # Group bucket series by their non-le labels.
        series: dict[tuple, list] = {}
        for s in buckets:
            key = tuple(sorted(
                (k, v) for k, v in s.labels.items() if k != "le"))
            series.setdefault(key, []).append(s)
        for key, ss in series.items():
            les = [math.inf if s.labels["le"] == "+Inf"
                   else float(s.labels["le"]) for s in ss]
            assert les == sorted(les), f"{name}{key}: le not ascending"
            assert les[-1] == math.inf, f"{name}{key}: no +Inf bucket"
            values = [s.value for s in ss]
            assert values == sorted(values), f"{name}{key}: not cumulative"
            count = next(
                (c.value for c in counts if tuple(sorted(
                    c.labels.items())) == key), None)
            assert count == values[-1], (
                f"{name}{key}: +Inf bucket {values[-1]} != _count {count}")
    return families


def loaded_gateway_metrics() -> GatewayMetrics:
    gm = GatewayMetrics()
    for model in ("sql-assist", HOSTILE):
        gm.record_request(model)
        gm.record_usage(model, 10, 20)
        gm.record_phase(model, "collocated", ttft_s=0.05, tpot_s=0.002,
                        e2e_s=0.4)
        gm.record_phase(model, "disaggregated", ttft_s=0.03, tpot_s=0.001,
                        e2e_s=0.2)
    gm.record_pick("pod-a", 0.0002, affinity_hit=True)
    gm.record_shed()            # pre-admission: unlabeled fallback
    gm.record_shed("sql-assist")
    gm.record_error(HOSTILE)
    # Upstream keepalive pool (fast-relay PR): created + reused per pod,
    # hostile pod name included.
    gm.record_upstream_conn("pod-a", reused=False)
    gm.record_upstream_conn("pod-a", reused=True)
    gm.record_upstream_conn(HOSTILE, reused=True)
    return gm


def _steps_hist() -> dict:
    from llm_instance_gateway_tpu.server.engine import STEP_BUCKETS

    h = tracing.Histogram(STEP_BUCKETS)
    h.observe(1)
    h.observe(8)
    return h.state()


def _kv_ledger_state() -> dict:
    """A charged KV ledger (server/kv_ledger.py) with a hostile prefix id
    so every tpu:kv_* family renders and round-trips."""
    from llm_instance_gateway_tpu.server.kv_ledger import KvLedger

    led = KvLedger(n_blocks=16, block_tokens=8)
    led.note_alloc(n=4)
    led.note_register(HOSTILE, blocks=2)
    led.note_reuse_hit(HOSTILE, blocks=2, tokens=16)
    led.note_release(freed=1, cached=2)
    led.note_park(24, source="handoff")
    led.sync_states([0, 1, 2, 7], active_blocks=8, prefix_resident=4,
                    parked_tokens=24)
    return led.snapshot()


def server_snapshot() -> dict:
    from llm_instance_gateway_tpu.server import profiler as profiler_mod
    from llm_instance_gateway_tpu.server import usage as usage_mod

    hist = tracing.Histogram(tracing.LATENCY_BUCKETS)
    for v in (0.002, 0.01, 7.0):
        hist.observe(v)
    occupancy = tracing.Histogram(usage_mod.OCCUPANCY_BUCKETS)
    occupancy.observe(0.5)
    occupancy.observe(1.0)
    # Step-timeline profiler (server/profiler.py): one dispatch per
    # phase plus a host gap and an idle gap, so every label value of the
    # tpu:dispatch_* families renders.
    prof = profiler_mod.StepProfiler()
    prof.note_dispatch("prefill", -0.3, 0.3, active=1, total_slots=4)
    prof.note_dispatch("decode", 0.0, 0.1, active=2, total_slots=4)
    prof.note_dispatch("decode", 0.15, 0.1, active=2, total_slots=4)
    prof.note_idle()
    prof.note_dispatch("spec", 0.5, 0.1, active=2, total_slots=4)
    # ... and one phase of each kind, so tpu:engine_phase_seconds_total
    # renders its whole closed label set (zero-valued series included).
    with prof.phase("decode.stage") as ph:
        ph.to("decode.wait")
    prof.note_lora_rows(3)  # tpu:lora_rows_total
    prof.note_lora_free_steps(5)  # tpu:lora_free_steps_total
    prof.note_lora_target_reads(14)  # tpu:lora_target_reads_total
    prof.note_logprob_steps(9)  # tpu:logprob_steps_total
    prof.note_overlapped_block()  # tpu:decode_blocks_overlapped_total
    prof.note_latent_positions(41)  # tpu:latent_kv_positions_total
    prof.note_attn_grid_steps(17)  # tpu:decode_attn_grid_steps_total
    prof.note_conv_rows(23)  # tpu:conv_state_rows_total
    prof.note_kda_rows(31)  # tpu:kda_state_rows_total
    prof.note_kv_positions(29, 0)  # tpu:kv_positions_read_total{lanes}
    # tpu:prompt_programs_total / tpu:prompt_positions_total /
    # tpu:prompt_program_seconds_total, every program of the label set
    # ... and tpu:chunk_attn_grid_steps_total, a chunk program's
    prof.note_prompt_program("chunk", 1000, 24, 1488)
    prof.note_prompt_program("prefill_many", 300, 212)
    prof.note_prompt_done(0.5, 0.625, [("chunk", 0.125)])
    return {
        "profile": prof.hist_state(),
        "model_name": HOSTILE,
        "pool_role": "prefill",
        "prefill_queue_size": 2,
        "decode_queue_size": 1,
        "num_requests_running": 3,
        "num_requests_waiting": 3,
        "kv_cache_usage_perc": 0.25,
        "kv_tokens_capacity": 8192,
        "kv_tokens_free": 6144,
        "decode_tokens_per_sec": 123.4,
        "running_lora_adapters": ["a1", HOSTILE],
        "waiting_lora_adapters": [HOSTILE],
        "max_lora": 4,
        "adapter_ranks": {"a1": 8, HOSTILE: 64},
        # Residency ladder (placement plane) with a hostile adapter name
        # in the tier CSVs: each name in exactly ONE tier (the
        # conservation lint in tests/test_placement.py reads the same
        # surface).
        "residency": {"slot": ["a1"], "host": [HOSTILE]},
        "tier_transitions": {("disk", "slot"): 2, ("slot", "host"): 1},
        "adapter_load_seconds": {"host": [0.05, 1], "disk": [1.2, 2]},
        "prefix_reused_tokens": 77,
        # KV economy ledger (server/kv_ledger.py): the tpu:kv_* block-
        # lifecycle families with a hostile prefix label.
        "kv_ledger": _kv_ledger_state(),
        # Decode fast-path observables (adaptive dispatch + stream lanes).
        "stream_lanes": 2,
        "stream_lanes_active": 1,
        "dispatch_steps_hist": _steps_hist(),
        "phase_hist": {
            "prefill": hist.state(),
            "handoff": tracing.Histogram(tracing.LATENCY_BUCKETS).state(),
            "decode_step": hist.state(),
        },
        # Capacity attribution (server/usage.py) with a hostile adapter
        # name on every labeled dimension.
        "usage": {
            "step_seconds": {(HOSTILE, "decode"): 1.25,
                             ("base", "prefill"): 0.5},
            "tokens": {(HOSTILE, "decode"): 40, ("base", "prefill"): 16},
            "kv_block_seconds": {HOSTILE: 9.5, "base": 3.25},
            "engine_step_seconds": {"decode": 1.25, "prefill": 0.5},
            "idle_slot_seconds": 2.75,
            "padding_tokens": 12,
            "occupancy": occupancy.state(),
            "kv_block_tokens": 16,
        },
    }


class FakeEngine:
    def metrics_snapshot(self):
        return server_snapshot()


def test_gateway_render_contract():
    families = lint_exposition(loaded_gateway_metrics().render())
    # Labeled + unlabeled shed coexist (pre-admission fallback).
    shed = {tuple(s.labels.items()): s.value
            for s in families["gateway_shed_total"]}
    assert shed[()] == 1 and shed[(("model", "sql-assist"),)] == 1
    # The hostile model name round-trips through escaping.
    assert any(s.labels.get("model") == HOSTILE
               for s in families["gateway_errors_total"])
    # Pick latency is a true histogram now (satellite): bucket series exist.
    assert "gateway_pick_latency_seconds_bucket" in families
    # Tentpole families, labeled by model AND path.
    for fam in ("gateway_ttft_seconds", "gateway_tpot_seconds",
                "gateway_e2e_seconds"):
        paths = {s.labels["path"] for s in families[fam + "_bucket"]}
        assert paths == {"collocated", "disaggregated"}
    # Upstream keepalive pool (fast-relay PR): two-label counter with a
    # hostile pod name round-tripping, plus the pool-wide reuse gauge.
    conns = {(s.labels["pod"], s.labels["state"]): s.value
             for s in families["gateway_upstream_connections_total"]}
    assert conns[("pod-a", "created")] == 1
    assert conns[("pod-a", "reused")] == 1
    assert conns[(HOSTILE, "reused")] == 1
    ratio = families["gateway_upstream_connection_reuse_ratio"][0].value
    assert abs(ratio - 2 / 3) < 1e-3


def test_server_render_contract():
    families = lint_exposition(server_metrics.render(server_snapshot()))
    for fam in ("tpu:prefill_seconds", "tpu:handoff_seconds",
                "tpu:decode_step_seconds"):
        assert fam + "_bucket" in families
        labels = families[fam + "_bucket"][0].labels
        assert labels["model"] == HOSTILE and labels["role"] == "prefill"
    assert families["tpu:prefill_seconds_count"][0].value == 3
    # Capacity-attribution families (this PR): hostile adapter labels
    # round-trip, counters are cumulative, occupancy is a true histogram.
    step = {(s.labels["adapter"], s.labels["phase"]): s.value
            for s in families["tpu:adapter_step_seconds_total"]}
    assert step == {(HOSTILE, "decode"): 1.25, ("base", "prefill"): 0.5}
    assert all(s.labels["model"] == HOSTILE
               for s in families["tpu:adapter_step_seconds_total"])
    kv = {s.labels["adapter"]: s.value
          for s in families["tpu:adapter_kv_block_seconds_total"]}
    assert kv == {HOSTILE: 9.5, "base": 3.25}
    engine_total = {s.labels["phase"]: s.value
                    for s in families["tpu:step_seconds_total"]}
    assert engine_total == {"decode": 1.25, "prefill": 0.5}
    assert families["tpu:idle_slot_seconds_total"][0].value == 2.75
    assert families["tpu:prefill_padding_tokens_total"][0].value == 12
    assert "tpu:decode_batch_occupancy_bucket" in families
    assert families["tpu:decode_batch_occupancy_count"][0].value == 2
    # Running vs waiting adapters are distinct labels on the info gauge.
    info = families["tpu:lora_requests_info"][0].labels
    assert info["running_lora_adapters"] == f"a1,{HOSTILE}"
    assert info["waiting_lora_adapters"] == HOSTILE
    # Step-timeline profiler families (server/profiler.py): per-phase
    # dispatch walls, host vs idle gap kinds, true histogram series.
    wall_phases = {s.labels["phase"]
                   for s in families["tpu:dispatch_wall_seconds_bucket"]}
    assert wall_phases == {"prefill", "decode", "spec"}
    gap_kinds = {s.labels["kind"]: s.value
                 for s in families["tpu:dispatch_gap_seconds_count"]}
    assert gap_kinds == {"host": 1, "idle": 1}
    from llm_instance_gateway_tpu.metrics_registry import ENGINE_PHASES

    phase_on = {(s.labels["phase"], s.labels["on"])
                for s in families["tpu:engine_phase_seconds_total"]}
    assert phase_on == set(ENGINE_PHASES)
    # Adapter rows of the decode steps, beside the staging counter.
    assert families["tpu:lora_rows_total"][0].value == 3
    assert families["tpu:lora_free_steps_total"][0].value == 5
    assert families["tpu:lora_target_reads_total"][0].value == 14
    assert families["tpu:logprob_steps_total"][0].value == 9
    assert families["tpu:decode_blocks_overlapped_total"][0].value == 1
    assert families["tpu:latent_kv_positions_total"][0].value == 41
    assert families["tpu:decode_attn_grid_steps_total"][0].value == 17
    assert families["tpu:conv_state_rows_total"][0].value == 23
    assert families["tpu:kda_state_rows_total"][0].value == 31
    assert families["tpu:chunk_attn_grid_steps_total"][0].value == 1488
    assert {s.labels["lanes"]: s.value
            for s in families["tpu:kv_positions_read_total"]} == {
                "full": 29, "window": 0}
    # The prompt programs: the closed label set, zero-valued series included.
    from llm_instance_gateway_tpu.metrics_registry import PROMPT_PROGRAMS

    assert {s.labels["program"]: s.value
            for s in families["tpu:prompt_programs_total"]} == {
                **dict.fromkeys(PROMPT_PROGRAMS, 0),
                "chunk": 1, "prefill_many": 1}
    positions = {(s.labels["program"], s.labels["kind"]): s.value
                 for s in families["tpu:prompt_positions_total"]}
    assert set(positions) == {(p, k) for p in PROMPT_PROGRAMS
                              for k in ("real", "pad")}
    assert positions["chunk", "real"] == 1000
    assert positions["prefill_many", "pad"] == 212
    assert {s.labels["program"]: s.value
            for s in families["tpu:prompt_program_seconds_total"]} == {
                **dict.fromkeys(PROMPT_PROGRAMS, 0.0), "chunk": 0.125}
    assert families["tpu:decode_stage_ops_total"][0].value == 0
    # Decode fast-path families (adaptive dispatch + stream lanes).
    assert families["tpu:stream_lanes"][0].value == 2
    assert families["tpu:stream_lanes_active"][0].value == 1
    assert families["tpu:dispatch_steps_count"][0].value == 2
    assert families["tpu:dispatch_steps_sum"][0].value == 9
    # KV economy ledger (server/kv_ledger.py): per-state blocks tile the
    # budget and the hostile prefix id survives the label round-trip.
    states = {s.labels["state"]: s.value for s in families["tpu:kv_blocks"]}
    assert set(states) == {"free", "active", "prefix_resident", "parked"}
    assert sum(states.values()) == families["tpu:kv_blocks_total"][0].value
    assert families["tpu:kv_block_tokens"][0].value == 8
    hit_prefixes = {s.labels["prefix"]
                    for s in families["tpu:kv_prefix_hits_total"]}
    assert HOSTILE in hit_prefixes
    assert "tpu:kv_free_run_blocks_bucket" in families
    assert "tpu:kv_parked_share_bucket" in families


def test_proxy_metrics_endpoint_round_trips():
    """The REAL aiohttp /metrics endpoint on the proxy serves lint-clean
    text (same render path, plus the pool-signal re-export)."""
    from llm_instance_gateway_tpu.api.v1alpha1 import InferencePool
    from llm_instance_gateway_tpu.gateway.datastore import Datastore
    from llm_instance_gateway_tpu.gateway.handlers.server import Server
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.proxy import GatewayProxy
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    async def run():
        pod = Pod(HOSTILE, "127.0.0.1:1")
        ds = Datastore(pods=[pod])
        ds.set_pool(InferencePool(name="pool"))
        provider = StaticProvider(
            [PodMetrics(pod=pod,
                        metrics=Metrics(prefix_reused_tokens=9))])
        proxy = GatewayProxy(
            Server(Scheduler(provider, token_aware=False,
                             prefill_aware=False), ds), provider, ds)
        proxy.metrics = loaded_gateway_metrics()
        proxy.metrics.pool_signals_fn = provider.all_pod_metrics
        client = TestClient(TestServer(proxy.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            text = await resp.text()
        finally:
            await client.close()
        families = lint_exposition(text)
        assert any(
            s.labels["pod"] == HOSTILE
            for s in families["gateway_pool_prefix_reused_tokens_total"])

    asyncio.run(run())


def test_api_http_metrics_endpoint_round_trips():
    """The REAL aiohttp /metrics endpoint on the model server serves
    lint-clean text, including the new histogram families."""
    from llm_instance_gateway_tpu.server.api_http import ModelServer

    async def run():
        server = ModelServer(FakeEngine(), tokenizer=None,
                             model_name="llama3-tiny")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            text = await resp.text()
        finally:
            await client.close()
        families = lint_exposition(text)
        assert "tpu:decode_step_seconds_bucket" in families
        # ModelServer injects its served name when the snapshot lacks one.
        assert (families["tpu:prefill_seconds_bucket"][0]
                .labels["model"] == HOSTILE)

    asyncio.run(run())


def loaded_observability():
    """A proxy-shaped observability stack (SLO engine + health scorer +
    journal) with hostile labels exercised on every new family."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway import health, slo
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    gm = loaded_gateway_metrics()
    journal = events.EventJournal(capacity=64)
    journal.emit(events.PICK, trace_id="t1", pod=HOSTILE)
    journal.emit(events.SHED, model=HOSTILE)
    engine = slo.SLOEngine(gm, cfg=slo.SLOConfig(min_window_total=1),
                           journal=journal)
    engine.tick(now=1000.0)
    # Traffic BETWEEN ticks so even the 1m window has a delta to judge.
    gm.record_phase("sql-assist", "collocated", ttft_s=0.05, tpot_s=0.002,
                    e2e_s=0.4)
    engine.tick(now=1070.0)
    provider = StaticProvider(
        [PodMetrics(pod=Pod(HOSTILE, "127.0.0.1:1"), metrics=Metrics())])
    scorer = health.HealthScorer(provider=provider, journal=journal)
    for _ in range(5):
        scorer.record_upstream(HOSTILE, ok=False, timeout=True)
    scorer.record_handoff(HOSTILE, ok=False)
    scorer.update(now=100.0)
    scorer.update(now=105.0)
    scorer.update(now=110.0)
    scorer.note_pick(HOSTILE)  # degraded pod: counts as would-avoid
    return gm, engine, scorer, journal


def test_slo_health_events_exposition_contract():
    """Satellite: the new gateway_slo_*, gateway_pod_health_*, upstream/
    handoff counters, would-avoid counter, and event-counter families lint
    clean on the composed gateway page — TYPE coverage, label escaping,
    and gauge-vs-counter semantics."""
    gm, engine, scorer, journal = loaded_observability()
    text = gm.render() + "\n".join(
        engine.render() + scorer.render()
        + journal.render_prom("gateway_events_total")) + "\n"
    families = lint_exposition(text)
    types = {line.split(" ")[2]: line.split(" ")[3]
             for line in text.splitlines() if line.startswith("# TYPE ")}
    # Gauge families (point-in-time, may go down).
    for fam in ("gateway_slo_compliance_ratio", "gateway_slo_burn_rate",
                "gateway_pod_health_score", "gateway_pod_health_state"):
        assert types[fam] == "gauge", fam
        assert families[fam], fam
    # Counter families (cumulative only).
    for fam in ("gateway_upstream_errors_total",
                "gateway_upstream_timeouts_total",
                "gateway_handoff_failures_total",
                "tpu:health_would_avoid_total", "gateway_events_total"):
        assert types[fam] == "counter", fam
    # Hostile labels round-trip on every new dimension.
    assert {s.labels["model"] for s in
            families["gateway_slo_compliance_ratio"]} == {"sql-assist",
                                                          HOSTILE}
    assert any(s.labels["window"] == "1m"
               for s in families["gateway_slo_burn_rate"])
    assert {s.labels["objective"] for s in
            families["gateway_slo_compliance_ratio"]} >= {
        "ttft", "tpot", "e2e", "error_rate"}
    assert [s.labels["pod"] for s in
            families["gateway_pod_health_score"]] == [HOSTILE]
    assert families["gateway_pod_health_state"][0].labels["state"] in (
        "healthy", "degraded", "unhealthy")
    assert [s.labels["pod"] for s in
            families["tpu:health_would_avoid_total"]] == [HOSTILE]
    # Direct emits plus the transitions the scorer itself journaled.
    assert {s.labels["kind"] for s in
            families["gateway_events_total"]} >= {"pick", "shed",
                                                  "health_transition"}


def test_resilience_families_exposition_contract():
    """Robustness-PR satellite: gateway_circuit_state{pod},
    gateway_retries_total{reason}, gateway_hedges_total{outcome}, and
    gateway_client_disconnects_total{model} lint clean on the composed
    page — TYPE coverage, hostile-label escaping, gauge-vs-counter
    semantics, and the documented 0/1/2 circuit-state encoding."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway import health, resilience
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    gm = loaded_gateway_metrics()
    gm.record_retry("connect")
    gm.record_retry("ttft_timeout")
    gm.record_hedge("fired")
    gm.record_hedge("won")
    gm.record_client_disconnect(HOSTILE)
    journal = events.EventJournal(capacity=64)
    provider = StaticProvider(
        [PodMetrics(pod=Pod(HOSTILE, "127.0.0.1:1"), metrics=Metrics())])
    plane = resilience.ResiliencePlane(
        health.HealthScorer(provider=provider, journal=journal),
        cfg=resilience.ResilienceConfig(trip_consecutive=2),
        journal=journal)
    for _ in range(2):
        plane.record_upstream(HOSTILE, ok=False)
    text = gm.render() + "\n".join(
        plane.render() + journal.render_prom("gateway_events_total")) + "\n"
    families = lint_exposition(text)
    types = {line.split(" ")[2]: line.split(" ")[3]
             for line in text.splitlines() if line.startswith("# TYPE ")}
    assert types["gateway_circuit_state"] == "gauge"
    for fam in ("gateway_retries_total", "gateway_hedges_total",
                "gateway_client_disconnects_total"):
        assert types[fam] == "counter", fam
    assert {s.labels["reason"] for s in families["gateway_retries_total"]} \
        == {"connect", "ttft_timeout"}
    assert {s.labels["outcome"] for s in families["gateway_hedges_total"]} \
        == {"fired", "won"}
    # Hostile labels round-trip on the new pod/model dimensions.
    (circuit,) = families["gateway_circuit_state"]
    assert circuit.labels["pod"] == HOSTILE and circuit.value == 1.0  # open
    assert any(s.labels.get("model") == HOSTILE
               for s in families["gateway_client_disconnects_total"])
    # The breaker transition landed in the event-counter family.
    assert any(s.labels["kind"] == "circuit_transition"
               for s in families["gateway_events_total"])


def loaded_usage_rollup():
    """A REAL UsageRollup over a provider whose pod exposes hostile-labeled
    attribution counters, ticked twice so deltas/shares/scores exist."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway import usage as gusage
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    gm = loaded_gateway_metrics()
    m = Metrics(
        adapter_step_seconds={(HOSTILE, HOSTILE, "decode"): 1.0,
                              (HOSTILE, "base", "decode"): 1.0},
        adapter_tokens={(HOSTILE, HOSTILE, "decode"): 10},
        adapter_kv_block_seconds={(HOSTILE, HOSTILE): 5.0},
        idle_slot_seconds=1.5, prefill_padding_tokens=7)
    provider = StaticProvider(
        [PodMetrics(pod=Pod("pod-u", "127.0.0.1:1"), metrics=m)])
    journal = events.EventJournal(capacity=64)
    rollup = gusage.UsageRollup(provider, metrics=gm, journal=journal)
    rollup.tick(now=100.0)
    m.adapter_step_seconds = {(HOSTILE, HOSTILE, "decode"): 9.0,
                              (HOSTILE, "base", "decode"): 2.0}
    rollup.tick(now=105.0)
    rollup.note_pick("pod-u", None)  # model-less pick: never counted
    return gm, rollup, journal


def test_usage_rollup_exposition_contract():
    """Capacity-attribution satellite: gateway_usage_share{model,adapter,
    resource}, gateway_noisy_neighbor_score{model,adapter}, and the
    would-deprioritize counter lint clean on the composed gateway page
    with hostile labels."""
    gm, rollup, journal = loaded_usage_rollup()
    text = gm.render() + "\n".join(
        rollup.render()
        + journal.render_prom("gateway_events_total")) + "\n"
    families = lint_exposition(text)
    types = {line.split(" ")[2]: line.split(" ")[3]
             for line in text.splitlines() if line.startswith("# TYPE ")}
    assert types["gateway_usage_share"] == "gauge"
    assert types["gateway_noisy_neighbor_score"] == "gauge"
    assert types["gateway_usage_would_deprioritize_total"] == "counter"
    shares = {(s.labels["adapter"], s.labels["resource"]): s.value
              for s in families["gateway_usage_share"]}
    # Step-second shares over the tick delta: 8/10 vs 2/10 (EMA-weighted).
    assert shares[(HOSTILE, "step_seconds")] > shares[("base",
                                                       "step_seconds")]
    assert all(s.labels["model"] == HOSTILE
               for s in families["gateway_usage_share"])
    assert {s.labels["adapter"]
            for s in families["gateway_noisy_neighbor_score"]} == {
        HOSTILE, "base"}
    # Unlabeled fallback keeps the counter family present at zero.
    assert families["gateway_usage_would_deprioritize_total"][0].value == 0


def loaded_placement_planner():
    """A ticked PlacementPlanner over a hostile-named residency fixture
    (shared with the docs-coverage test)."""
    from llm_instance_gateway_tpu.gateway.placement import (
        PlacementConfig,
        PlacementPlanner,
    )
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics,
        Pod,
        PodMetrics,
    )

    provider = StaticProvider([
        PodMetrics(pod=Pod("pod-0", "1.1.1.1:1"),
                   metrics=Metrics(adapter_tiers={HOSTILE: "slot"},
                                   active_adapters={HOSTILE: 0},
                                   max_active_adapters=4)),
        PodMetrics(pod=Pod(HOSTILE, "1.1.1.1:2"),
                   metrics=Metrics(adapter_tiers={"a1": "host"},
                                   max_active_adapters=4)),
    ])

    class FakeUsage:
        def shares_snapshot(self):
            return {(HOSTILE, HOSTILE): 0.6, ("m", "a1"): 0.1}

    planner = PlacementPlanner(provider, usage=FakeUsage(),
                               cfg=PlacementConfig(mode="prefer_resident"))
    planner.tick()
    planner.note_pick(HOSTILE, HOSTILE)  # wrong-tier observable
    planner.note_placement_escape()
    return planner


def test_placement_exposition_contract():
    """The placement families lint clean and round-trip hostile labels
    on the gateway surface."""
    planner = loaded_placement_planner()
    text = "\n".join(planner.render()) + "\n"
    fams = lint_exposition(text)
    assert len(fams) >= 5, sorted(fams)
    residency = fams["gateway_adapter_residency"]
    assert any(s.labels.get("pod") == HOSTILE for s in residency)
    assert any(s.labels.get("adapter") == HOSTILE for s in residency)
    assert fams["gateway_placement_wrong_tier_picks_total"][0].value == 1
    assert fams["gateway_placement_escapes_total"][0].value == 1


def loaded_fairness_policy():
    """A REAL FairnessPolicy with a hostile-labeled tenant throttled and
    demoted, so every fairness family renders labeled samples."""
    from llm_instance_gateway_tpu.gateway import fairness as fairness_mod
    from llm_instance_gateway_tpu.gateway.scheduling.types import LLMRequest

    class FakeRollup:
        def shares_snapshot(self):
            return {(HOSTILE, HOSTILE): 0.9, (HOSTILE, "base"): 0.1}

        def noisy(self):
            return frozenset()

        def note_pick(self, pod, model):
            pass

    policy = fairness_mod.FairnessPolicy(
        FakeRollup(),
        cfg=fairness_mod.FairnessConfig(mode="enforce", quota_rps=1.0,
                                        quota_burst=1.0),
        clock=lambda: 100.0)
    policy.tick(now=100.0)
    for _ in range(2):  # second admission exhausts the 1-token burst
        policy.admit(LLMRequest(model=HOSTILE, critical=True,
                                criticality="Critical"))
    return policy


def test_fairness_exposition_contract():
    """Fairness-plane families: quota throttles/demotions counters and the
    quota-remaining gauge lint clean with hostile labels; the relabeled
    would-deprioritize counter carries BOTH model and adapter labels."""
    gm, rollup, journal = loaded_usage_rollup()
    rollup.seed_noisy(HOSTILE, HOSTILE)
    rollup.note_pick("pod-u", HOSTILE)
    policy = loaded_fairness_policy()
    text = gm.render() + "\n".join(
        rollup.render() + policy.render()) + "\n"
    families = lint_exposition(text)
    (wd,) = [s for s in families["gateway_usage_would_deprioritize_total"]
             if s.labels]
    assert wd.labels == {"model": HOSTILE, "adapter": HOSTILE}
    assert wd.value == 1
    (thr,) = families["gateway_quota_throttles_total"][-1:]
    assert thr.labels == {"model": HOSTILE, "adapter": HOSTILE}
    (dem,) = families["gateway_fairness_demotions_total"][-1:]
    assert dem.labels == {"model": HOSTILE, "adapter": HOSTILE}
    assert families["gateway_tenant_quota_remaining"]


def test_fairness_empty_state_still_lints():
    from llm_instance_gateway_tpu.gateway import fairness as fairness_mod

    class FakeRollup:
        def shares_snapshot(self):
            return {}

        def noisy(self):
            return frozenset()

    policy = fairness_mod.FairnessPolicy(FakeRollup())
    families = lint_exposition("\n".join(policy.render()) + "\n")
    assert families["gateway_quota_throttles_total"][0].value == 0
    assert families["gateway_fairness_demotions_total"][0].value == 0
    # Gauges render no unlabeled fallback: absent until a bucket exists.
    assert "gateway_tenant_quota_remaining" not in families


def loaded_statebus():
    """A REAL StateBus over one advisor stack, with a hostile replica id
    on the wire, a merged peer doc, and a stale fallback counted."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway.advisors import AdvisorStack
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.statebus import (
        StateBus,
        StateBusConfig,
    )
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    provider = StaticProvider(
        [PodMetrics(pod=Pod("pod-0", "127.0.0.1:1"), metrics=Metrics())])
    stack = AdvisorStack("pool", provider, journal=events.EventJournal())
    clock = [100.0]
    bus = StateBus({"pool": stack},
                   cfg=StateBusConfig(replica_id=HOSTILE,
                                      peers=("http://peer:1",),
                                      staleness_s=5.0),
                   journal=stack.journal, clock=lambda: clock[0])
    bus.tick()
    bus.merge([{"replica": HOSTILE + "-peer", "seq": 3, "ts": 100.0,
                "pools": {"pool": {"noisy": {"hog": ["m", "hog"]},
                                   "avoid": ["pod-9"], "resident": {},
                                   "buckets": [], "shares": []}}}])
    bus.apply()
    clock[0] = 120.0  # every peer ages out: stale fallback counted
    bus.apply()
    return bus


def test_statebus_exposition_contract():
    """Statebus satellite: gateway_statebus_peers / snapshot-age /
    merge-latency histogram / stale-fallback + exchange counters lint
    clean with a hostile replica id round-tripping."""
    bus = loaded_statebus()
    bus.exchanges["ok"] = 2
    bus.exchanges["error"] = 1
    text = "\n".join(bus.render()) + "\n"
    families = lint_exposition(text)
    types = {line.split(" ")[2]: line.split(" ")[3]
             for line in text.splitlines() if line.startswith("# TYPE ")}
    assert types["gateway_statebus_peers"] == "gauge"
    assert types["gateway_statebus_snapshot_age_seconds"] == "gauge"
    assert types["gateway_statebus_merge_seconds"] == "histogram"
    assert types["gateway_statebus_stale_fallbacks_total"] == "counter"
    assert types["gateway_statebus_exchanges_total"] == "counter"
    # Hostile replica ids round-trip on the age gauge (own + peer).
    replicas = {s.labels["replica"]
                for s in families["gateway_statebus_snapshot_age_seconds"]}
    assert replicas == {HOSTILE, HOSTILE + "-peer"}
    # The aged-out peer left the fresh count at zero and the fallback
    # counter at one.
    assert families["gateway_statebus_peers"][0].value == 0
    assert families["gateway_statebus_stale_fallbacks_total"][0].value == 1
    assert {s.labels["outcome"] for s in
            families["gateway_statebus_exchanges_total"]} == {"ok", "error"}
    assert "gateway_statebus_merge_seconds_bucket" in families


def loaded_fleet_collector():
    """A REAL FleetCollector with a hostile source name in its error
    counter and one collect's worth of gauge state (shared with the
    docs-coverage test)."""
    from llm_instance_gateway_tpu.gateway.fleetobs import FleetCollector

    collector = FleetCollector("gw-self", peer_urls=("http://peer:1",))
    collector.errors_total[HOSTILE] = 2
    collector.last_sources = {"gateway": 1, "pod": 3}
    collector.last_stitched = 7
    collector.collect_hist.observe(0.02)
    return collector


def test_fleet_collector_exposition_contract():
    """Fleet satellite: gateway_fleet_sources / stitched-traces gauges,
    the per-source error counter (hostile source name round-tripping),
    and the collect-latency histogram lint clean."""
    collector = loaded_fleet_collector()
    text = "\n".join(collector.render()) + "\n"
    families = lint_exposition(text)
    types = {line.split(" ")[2]: line.split(" ")[3]
             for line in text.splitlines() if line.startswith("# TYPE ")}
    assert types["gateway_fleet_sources"] == "gauge"
    assert types["gateway_fleet_stitched_traces"] == "gauge"
    assert types["gateway_fleet_collect_errors_total"] == "counter"
    assert types["gateway_fleet_collect_seconds"] == "histogram"
    kinds = {s.labels["kind"]: s.value
             for s in families["gateway_fleet_sources"]}
    assert kinds == {"gateway": 1, "pod": 3}
    assert families["gateway_fleet_stitched_traces"][0].value == 7
    errs = {s.labels["source"]: s.value
            for s in families["gateway_fleet_collect_errors_total"]}
    assert errs == {HOSTILE: 2}
    assert "gateway_fleet_collect_seconds_bucket" in families


def test_multipool_merged_exposition_round_trips():
    """Two pools' advisor stacks merged through merge_exposition_blocks:
    one # TYPE line per family, per-stack unlabeled counters summed, and
    the whole page still parses."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway.advisors import (
        AdvisorStack,
        merge_exposition_blocks,
    )
    from llm_instance_gateway_tpu.gateway.provider import StaticProvider
    from llm_instance_gateway_tpu.gateway.types import (
        Metrics, Pod, PodMetrics)

    journal = events.EventJournal()
    stacks = []
    for tag in ("a", HOSTILE):
        provider = StaticProvider([PodMetrics(
            pod=Pod(f"{tag}-pod", "127.0.0.1:1"),
            metrics=Metrics(adapter_tiers={f"{tag}-ad": "slot"},
                            max_active_adapters=4))])
        stack = AdvisorStack(f"pool-{tag}", provider, journal=journal)
        stack.tick()
        stack.placement.note_placement_escape()  # unlabeled counter += 1
        stacks.append(stack)
    text = "\n".join(
        merge_exposition_blocks([s.render() for s in stacks])) + "\n"
    families = lint_exposition(text)
    type_lines = [line for line in text.splitlines()
                  if line.startswith("# TYPE ")]
    assert len(type_lines) == len(set(type_lines)), type_lines
    # Per-stack unlabeled counters SUMMED (1 escape per stack).
    assert families["gateway_placement_escapes_total"][0].value == 2
    # Labeled samples from BOTH pools coexist (hostile pod included).
    pods = {s.labels["pod"]
            for s in families["gateway_adapter_residency"]}
    assert pods == {"a-pod", f"{HOSTILE}-pod"}


def test_empty_observability_state_still_lints():
    """Fresh proxy, zero traffic: the composed page must still parse (the
    would-avoid/upstream counters render unlabeled 0 fallbacks; SLO and
    health families are simply absent)."""
    from llm_instance_gateway_tpu import events
    from llm_instance_gateway_tpu.gateway import health, slo

    gm = GatewayMetrics()
    engine = slo.SLOEngine(gm)
    scorer = health.HealthScorer()
    journal = events.EventJournal()
    text = gm.render() + "\n".join(
        engine.render() + scorer.render()
        + journal.render_prom("gateway_events_total")) + "\n"
    families = lint_exposition(text)
    assert families["gateway_events_total"][0].value == 0
    assert families["tpu:health_would_avoid_total"][0].value == 0


def test_server_events_family_round_trips():
    """Satellite: tpu:events_total on the model-server surface — rendered
    through the REAL aiohttp endpoint, with hostile event kinds escaped."""
    import asyncio as asyncio_mod

    from llm_instance_gateway_tpu.server.api_http import ModelServer

    async def run():
        server = ModelServer(FakeEngine(), tokenizer=None,
                             model_name="llama3-tiny")
        server.events.emit("admission_reject", status=429,
                           reason="queue_full")
        server.events.emit(HOSTILE)
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            text = await resp.text()
        finally:
            await client.close()
        families = lint_exposition(text)
        kinds = {s.labels["kind"]: s.value
                 for s in families["tpu:events_total"]}
        assert kinds == {"admission_reject": 1.0, HOSTILE: 1.0}

    asyncio_mod.run(run())


def test_pick_latency_histogram_math():
    """The summary -> histogram satellite: counts land in the right le
    buckets and quantile() still answers from the same state."""
    gm = GatewayMetrics()
    for v in (0.0002, 0.0002, 0.04):
        gm.record_pick("p", v, False)
    families = lint_exposition(gm.render())
    by_le = {s.labels["le"]: s.value
             for s in families["gateway_pick_latency_seconds_bucket"]}
    assert by_le["0.00025"] == 2.0
    assert by_le["0.05"] == 3.0
    assert by_le["+Inf"] == 3.0
    assert families["gateway_pick_latency_seconds_count"][0].value == 3
