"""Capacity-attribution plane tests (server/usage.py + gateway/usage.py).

The acceptance-critical invariants:

- **Conservation**: Σ per-adapter ``tpu:adapter_step_seconds_total`` equals
  the engine's wall step-seconds (``tpu:step_seconds_total``) within 1%,
  per phase, through the REAL engine code paths.
- **Routing unchanged**: attaching the usage advisor to a scheduler leaves
  the pick sequence byte-identical (same RNG) — only the
  would-deprioritize counter moves.
- **Noisy-neighbor detection**: a consumption/traffic skew flags the right
  adapter with hysteresis, quiet adapters never flag, transitions land in
  the flight recorder (the chaos scenario drives the same math end-to-end).
- **Parked adapters are waiting, not running** (the lora_requests_info
  satellite): a prefilled request without a decode slot reports under
  ``waiting_lora_adapters``.
"""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu.gateway import usage as gusage
from llm_instance_gateway_tpu.gateway.provider import StaticProvider
from llm_instance_gateway_tpu.gateway.types import Metrics, Pod, PodMetrics
from llm_instance_gateway_tpu.server.usage import (
    BASE,
    UsageTracker,
    owner_key,
)

# ---------------------------------------------------------------------------
# UsageTracker units
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class TestUsageTracker:
    def test_even_split_conserves_wall(self):
        tr = UsageTracker(decode_slots=4)
        tr.charge_decode(0.3, ["a", "b", None], {"a": 1, "b": 2, BASE: 3})
        tr.charge_decode(0.1, ["a"], {"a": 1})
        snap = tr.snapshot()
        per_adapter = sum(v for (_, p), v in snap["step_seconds"].items()
                          if p == "decode")
        assert per_adapter == pytest.approx(
            snap["engine_step_seconds"]["decode"])
        assert snap["step_seconds"][("a", "decode")] == pytest.approx(0.2)
        assert snap["step_seconds"][(BASE, "decode")] == pytest.approx(0.1)
        assert snap["tokens"][("a", "decode")] == 2

    def test_empty_owner_dispatch_charges_nothing(self):
        tr = UsageTracker(decode_slots=4)
        tr.charge_step("decode", 1.0, [])
        snap = tr.snapshot()
        assert snap["step_seconds"] == {}
        assert snap["engine_step_seconds"] == {}

    def test_occupancy_and_idle_slot_seconds(self):
        tr = UsageTracker(decode_slots=4)
        tr.charge_decode(0.5, ["a"])        # 1/4 full: 3 idle slots
        tr.charge_decode(0.5, ["a", "b", "c", None])  # full
        snap = tr.snapshot()
        assert snap["idle_slot_seconds"] == pytest.approx(1.5)
        assert snap["occupancy"]["count"] == 2
        assert snap["occupancy"]["sum"] == pytest.approx(0.25 + 1.0)

    def test_kv_integral_includes_parked(self):
        clock = FakeClock()
        tr = UsageTracker(decode_slots=4, kv_block=16, clock=clock)
        # adapter a holds 32 tokens (2 blocks), parked b holds 20 (2 blocks)
        tr.sync_kv([("a", 32), ("b", 20)])
        clock.t += 2.0
        snap = tr.snapshot()
        assert snap["kv_block_seconds"]["a"] == pytest.approx(4.0)
        assert snap["kv_block_seconds"]["b"] == pytest.approx(4.0)
        # Holdings replaced: only `a` accrues over the next interval.
        tr.sync_kv([("a", 32)])
        clock.t += 1.0
        snap = tr.snapshot()
        assert snap["kv_block_seconds"]["a"] == pytest.approx(6.0)
        assert snap["kv_block_seconds"]["b"] == pytest.approx(4.0)

    def test_padding_counter(self):
        tr = UsageTracker(decode_slots=2)
        tr.charge_padding(5)
        tr.charge_padding(0)
        tr.charge_padding(7)
        assert tr.snapshot()["padding_tokens"] == 12

    def test_owner_key(self):
        assert owner_key(None) == BASE
        assert owner_key("") == BASE
        assert owner_key("x") == "x"


# ---------------------------------------------------------------------------
# Engine conservation (the acceptance criterion, through REAL code paths)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def attribution_engine():
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig
    from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

    params = transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    lora = LoRAManager(TINY_TEST, dtype=jnp.float32)
    rng = np.random.RandomState(7)

    def weights(seed):
        r = np.random.RandomState(seed)
        return {t: {"a": (r.randn(TINY_TEST.d_model, 2) * 0.01
                          ).astype(np.float32),
                    "b": (r.randn(2, TINY_TEST.d_model) * 0.01
                          ).astype(np.float32)}
                for t in ("wq", "wv")}

    lora.load("tenant-a", weights=weights(1), alpha=8.0, rank=2)
    lora.load("tenant-b", weights=weights(2), alpha=8.0, rank=2)
    engine = Engine(
        TINY_TEST, params,
        EngineConfig(decode_slots=4, max_seq_len=64,
                     prefill_buckets=(8, 16, 32)),
        lora_manager=lora, eos_id=None, dtype=jnp.float32)
    engine.start()
    yield engine, rng
    engine.stop()


def _mk_req(prompt, max_new, adapter=None):
    from llm_instance_gateway_tpu.server.engine import (
        Request,
        SamplingParams,
    )

    return Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                   sampling=SamplingParams(temperature=0.0), adapter=adapter)


class TestEngineConservation:
    def test_step_seconds_conserved_and_exposed(self, attribution_engine):
        """Mixed base + two-adapter traffic: per-adapter step-seconds sum
        to the engine wall total within 1% PER PHASE, verified on the
        rendered exposition (the same text the gateway scrapes)."""
        from llm_instance_gateway_tpu.server import metrics as server_metrics
        from llm_instance_gateway_tpu.utils import prom_parse

        engine, rng = attribution_engine
        reqs = [
            _mk_req(rng.randint(1, 200, size=5), 6, None),
            _mk_req(rng.randint(1, 200, size=9), 6, "tenant-a"),
            _mk_req(rng.randint(1, 200, size=3), 6, "tenant-b"),
            _mk_req(rng.randint(1, 200, size=12), 6, "tenant-a"),
        ]
        for r in reqs:
            engine.submit(r)
        for r in reqs:
            assert r.done.wait(120)
            assert r.error is None
        snap = engine.metrics_snapshot()
        snap["model_name"] = "tiny"
        text = server_metrics.render(snap)
        fams = prom_parse.parse_text(text)
        per_adapter: dict[str, float] = {}
        for s in fams["tpu:adapter_step_seconds_total"]:
            ph = s.labels["phase"]
            per_adapter[ph] = per_adapter.get(ph, 0.0) + s.value
        engine_total = {s.labels["phase"]: s.value
                        for s in fams["tpu:step_seconds_total"]}
        assert set(per_adapter) == set(engine_total) >= {"prefill", "decode"}
        for phase, total in engine_total.items():
            assert total > 0.0
            assert per_adapter[phase] == pytest.approx(total, rel=0.01), (
                phase, per_adapter[phase], total)
        # Every tenant that sent traffic is attributed.
        adapters = {s.labels["adapter"]
                    for s in fams["tpu:adapter_step_seconds_total"]}
        assert adapters >= {"base", "tenant-a", "tenant-b"}
        # Decode tokens: attribution matches what the requests received
        # (first token is a prefill product, charged there).
        decode_toks = sum(
            s.value for s in fams["tpu:adapter_tokens_total"]
            if s.labels["phase"] == "decode")
        assert decode_toks == sum(len(r.output_tokens) - 1 for r in reqs)
        prefill_toks = sum(
            s.value for s in fams["tpu:adapter_tokens_total"]
            if s.labels["phase"] == "prefill")
        assert prefill_toks == sum(len(r.prompt_tokens) for r in reqs)
        # KV block-seconds accrued for every owner.
        kv = {s.labels["adapter"]: s.value
              for s in fams["tpu:adapter_kv_block_seconds_total"]}
        assert all(v > 0.0 for v in kv.values())
        # Pool-waste observables exist (padding from bucket rounding).
        assert fams["tpu:prefill_padding_tokens_total"][0].value > 0
        assert fams["tpu:decode_batch_occupancy_count"][0].value > 0

    @pytest.mark.parametrize("layout", [{}, {"paged_kv_block": 8}],
                             ids=["lanes", "paged"])
    @pytest.mark.parametrize("extra", [{}, {"speculative_k": 2}],
                             ids=["plain", "spec"])
    def test_dispatch_accounting_agrees_across_its_sinks(self, extra, layout):
        """Every decode dispatch, plain or speculative, ends in the one
        ``Engine._account_dispatch``; its four sinks (usage tracker,
        profiler, generated total + throughput EMA, step histograms) must
        tell one story of the same requests."""
        import dataclasses

        from llm_instance_gateway_tpu.models import transformer
        from llm_instance_gateway_tpu.models.configs import TINY_TEST
        from llm_instance_gateway_tpu.server.engine import (
            Engine,
            EngineConfig,
        )

        params = transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        draft = {}
        if extra.get("speculative_k"):
            dcfg = dataclasses.replace(
                TINY_TEST, name="tiny-draft", d_model=32, n_layers=1,
                n_heads=2, n_kv_heads=1, d_ff=64, head_dim=16)
            draft = {"draft_cfg": dcfg, "draft_params": transformer.init_params(
                dcfg, jax.random.PRNGKey(7), dtype=jnp.float32)}
        engine = Engine(TINY_TEST, params,
                        EngineConfig(decode_slots=2, max_seq_len=64,
                                     prefill_buckets=(8, 16), **extra,
                                     **layout),
                        eos_id=None, dtype=jnp.float32, **draft)
        engine.start()
        try:
            reqs = [_mk_req((5, 6, 7), 6), _mk_req((9, 8, 7, 6, 5), 9),
                    _mk_req((3, 4), 4)]
            for r in reqs:
                engine.submit(r)
            for r in reqs:
                assert r.done.wait(120)
                assert r.error is None
        finally:
            engine.stop()
        emitted = sum(len(r.output_tokens) for r in reqs)
        assert emitted == 6 + 9 + 4
        # generated total: every token the requests received
        assert engine.total_generated == emitted
        assert engine.decode_tps_ema > 0.0
        # usage tracker: decode tokens are all but each request's first
        # (a prefill product), and the decode wall it charged is the wall
        # the profiler recorded for the same dispatches
        usage = engine.usage.snapshot()
        assert sum(n for (_, ph), n in usage["tokens"].items()
                   if ph == "decode") == emitted - len(reqs)
        prof = engine.profiler
        decode_kinds = {k: n for k, n in prof.dispatches.items()
                        if k != "prefill"}
        assert set(decode_kinds) <= {"decode", "spec"}
        if extra.get("speculative_k"):
            assert decode_kinds.get("spec", 0) > 0
        # (a block whose rows all finished before it was read has no
        # owner: the tracker books its occupancy and charges it to nobody)
        owned = [r for r in prof.snapshot()["records"]
                 if r["phase"] != "prefill" and r["active"]]
        assert usage["engine_step_seconds"]["decode"] == pytest.approx(
            sum(r["wall_s"] for r in owned), rel=1e-5)
        # step histograms: one cadence observation a decode dispatch of
        # either kind, one planner record a plain one
        n_dispatches = sum(decode_kinds.values())
        assert n_dispatches > 0
        assert engine.phase_hist["decode_step"].n == n_dispatches
        assert engine.dispatch_steps_hist.n == decode_kinds.get("decode", 0)
        assert usage["occupancy"]["count"] == n_dispatches


class TestParkedAdapterIsWaiting:
    def test_parked_decode_wait_adapter_reports_waiting(
            self, attribution_engine):
        """Regression (lora_requests_info satellite): with every decode
        slot busy, a prefilled-but-parked adapter request counts under
        waiting_lora_adapters, NOT running — the vLLM semantics the
        gateway's affinity scorer assumes."""
        engine, rng = attribution_engine
        # Fill all 4 slots with long base-model decodes.
        hogs = [_mk_req(rng.randint(1, 200, size=5), 48) for _ in range(4)]
        for r in hogs:
            engine.submit(r)
        # Wait until every slot is occupied.
        deadline = time.time() + 60
        while time.time() < deadline:
            if sum(1 for s in engine.slots if s is not None) == 4:
                break
            time.sleep(0.01)
        parked = _mk_req(rng.randint(1, 200, size=5), 4, "tenant-a")
        engine.submit(parked)
        seen_waiting = False
        while time.time() < deadline and not parked.done.is_set():
            snap = engine.metrics_snapshot()
            if "tenant-a" in snap["waiting_lora_adapters"]:
                seen_waiting = True
                assert "tenant-a" not in snap["running_lora_adapters"]
                break
            time.sleep(0.005)
        for r in hogs + [parked]:
            assert r.done.wait(120)
        assert seen_waiting, (
            "parked adapter request never surfaced in "
            "waiting_lora_adapters")


# ---------------------------------------------------------------------------
# metrics_client: new families + running/waiting union
# ---------------------------------------------------------------------------


EXPO = """\
# TYPE tpu:num_requests_running gauge
tpu:num_requests_running 1
# TYPE tpu:num_requests_waiting gauge
tpu:num_requests_waiting 2
# TYPE tpu:kv_cache_usage_perc gauge
tpu:kv_cache_usage_perc 0.5
# TYPE tpu:adapter_step_seconds_total counter
tpu:adapter_step_seconds_total{model="m",adapter="a",phase="decode"} 1.5
tpu:adapter_step_seconds_total{model="m",adapter="base",phase="prefill"} 0.5
# TYPE tpu:adapter_tokens_total counter
tpu:adapter_tokens_total{model="m",adapter="a",phase="decode"} 40
# TYPE tpu:adapter_kv_block_seconds_total counter
tpu:adapter_kv_block_seconds_total{model="m",adapter="a"} 9.25
# TYPE tpu:idle_slot_seconds_total counter
tpu:idle_slot_seconds_total 3.5
# TYPE tpu:prefill_padding_tokens_total counter
tpu:prefill_padding_tokens_total 11
# TYPE tpu:lora_requests_info gauge
tpu:lora_requests_info{running_lora_adapters="a",waiting_lora_adapters="b,c",max_lora="4"} 100.0
"""


def test_metrics_client_parses_attribution_families():
    from llm_instance_gateway_tpu.gateway.metrics_client import (
        families_to_metrics,
    )
    from llm_instance_gateway_tpu.utils import prom_parse

    metrics, _errs = families_to_metrics(prom_parse.parse_text(EXPO),
                                         Metrics())
    assert metrics.adapter_step_seconds == {
        ("m", "a", "decode"): 1.5, ("m", "base", "prefill"): 0.5}
    assert metrics.adapter_tokens == {("m", "a", "decode"): 40}
    assert metrics.adapter_kv_block_seconds == {("m", "a"): 9.25}
    assert metrics.idle_slot_seconds == 3.5
    assert metrics.prefill_padding_tokens == 11
    # Running AND waiting union into the affinity set (reference
    # semantics) — the parked adapters stay routable-by-affinity.
    assert set(metrics.active_adapters) == {"a", "b", "c"}
    assert metrics.max_active_adapters == 4


# ---------------------------------------------------------------------------
# Gateway rollup: shares, scores, hysteresis, journal
# ---------------------------------------------------------------------------


def _rollup_fixture(cfg=None):
    gm_requests = {}

    class FakeGM:
        requests_total = gm_requests

    m = Metrics()
    provider = StaticProvider(
        [PodMetrics(pod=Pod("p0", "127.0.0.1:1"), metrics=m)])
    journal = events_mod.EventJournal(capacity=128)
    rollup = gusage.UsageRollup(provider, metrics=FakeGM(), cfg=cfg,
                                journal=journal)
    return rollup, m, gm_requests, journal


class TestUsageRollup:
    def test_shares_and_traffic(self):
        rollup, m, req, _ = _rollup_fixture(
            gusage.UsageConfig(ema_alpha=1.0))
        m.adapter_step_seconds = {("m", "a", "decode"): 0.0,
                                  ("m", "base", "decode"): 0.0}
        rollup.tick(now=0.0)
        m.adapter_step_seconds = {("m", "a", "decode"): 3.0,
                                  ("m", "base", "decode"): 1.0}
        req.update({"a": 30, "other-model": 10})
        rollup.tick(now=5.0)
        payload = rollup.debug_payload()
        rows = {r["adapter"]: r for r in payload["adapters"]}
        assert rows["a"]["share"]["step_seconds"] == pytest.approx(0.75)
        assert rows["base"]["share"]["step_seconds"] == pytest.approx(0.25)
        # `a` consumed 75% on ~75% of traffic -> score ~1 (not noisy);
        # base traffic (the model no adapter claims) covers the base key.
        assert rows["a"]["score"] == pytest.approx(1.0, rel=0.1)
        assert payload["noisy"] == []

    def test_noisy_flag_hysteresis_and_journal(self):
        cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                                 enter_ticks=2, exit_ticks=2,
                                 ema_alpha=1.0)
        rollup, m, req, journal = _rollup_fixture(cfg)
        step = {("m", "hog", "decode"): 0.0, ("m", "quiet", "decode"): 0.0}
        m.adapter_step_seconds = dict(step)
        rollup.tick(now=0.0)

        def advance(hog_s, quiet_s, hog_req, quiet_req, now):
            step[("m", "hog", "decode")] += hog_s
            step[("m", "quiet", "decode")] += quiet_s
            m.adapter_step_seconds = dict(step)
            req["hog"] = req.get("hog", 0) + hog_req
            req["quiet"] = req.get("quiet", 0) + quiet_req
            rollup.tick(now=now)

        # Tick 1 over threshold: candidate only (dwell 2) — not flagged.
        advance(9.0, 1.0, 1, 9, now=5.0)
        assert rollup.noisy() == frozenset()
        # Tick 2 over threshold: flags, journals the transition.
        advance(9.0, 1.0, 1, 9, now=10.0)
        assert rollup.noisy() == frozenset({"hog"})
        flags = journal.events(kind=events_mod.NOISY_NEIGHBOR, limit=16)
        assert len(flags) == 1 and flags[0]["attrs"]["adapter"] == "hog"
        assert flags[0]["attrs"]["to"] == gusage.NOISY
        # Two quiet ticks clear it (exit dwell), journaling the clear.
        advance(1.0, 9.0, 5, 5, now=15.0)
        assert rollup.noisy() == frozenset({"hog"})
        advance(1.0, 9.0, 5, 5, now=20.0)
        assert rollup.noisy() == frozenset()
        flags = journal.events(kind=events_mod.NOISY_NEIGHBOR, limit=16)
        assert len(flags) == 2 and flags[1]["attrs"]["to"] == gusage.QUIET

    def test_min_share_floor_suppresses_tiny_adapters(self):
        cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                                 enter_ticks=1, ema_alpha=1.0)
        rollup, m, req, _ = _rollup_fixture(cfg)
        m.adapter_step_seconds = {("m", "tiny", "decode"): 0.0,
                                  ("m", "big", "decode"): 0.0}
        rollup.tick(now=0.0)
        # `tiny` consumes 10x its traffic share but only 5% of the pool.
        m.adapter_step_seconds = {("m", "tiny", "decode"): 0.5,
                                  ("m", "big", "decode"): 9.5}
        req.update({"tiny": 1, "big": 199})
        rollup.tick(now=5.0)
        assert rollup.noisy() == frozenset()

    def test_vanished_keys_drop_state(self):
        rollup, m, req, _ = _rollup_fixture(
            gusage.UsageConfig(ema_alpha=1.0))
        m.adapter_step_seconds = {("m", "gone", "decode"): 0.0}
        rollup.tick(now=0.0)
        m.adapter_step_seconds = {("m", "gone", "decode"): 1.0}
        rollup.tick(now=5.0)
        assert any(r["adapter"] == "gone"
                   for r in rollup.debug_payload()["adapters"])
        m.adapter_step_seconds = {("m", "new", "decode"): 1.0}
        rollup.tick(now=10.0)
        rollup.tick(now=15.0)
        assert not any(r["adapter"] == "gone"
                       for r in rollup.debug_payload()["adapters"])

    def test_multi_model_base_traffic_not_double_counted(self):
        """Two served models, each with a base tenant: every request name
        is counted toward at most ONE key — model B's flooding base tenant
        must flag even though model A's base traffic dominates the pool
        (the old global-unclaimed-sum denominator hid it)."""
        cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                                 enter_ticks=1, ema_alpha=1.0)
        rollup, m, req, _ = _rollup_fixture(cfg)
        step = {("model-a", "base", "decode"): 0.0,
                ("model-b", "base", "decode"): 0.0}
        m.adapter_step_seconds = dict(step)
        rollup.tick(now=0.0)
        # B's base tenant: 55% of pool step-seconds on 10% of traffic.
        step[("model-a", "base", "decode")] += 4.5
        step[("model-b", "base", "decode")] += 5.5
        m.adapter_step_seconds = dict(step)
        req.update({"model-a": 90, "model-b": 10})
        rollup.tick(now=5.0)
        rows = {(r["model"], r["adapter"]): r
                for r in rollup.debug_payload()["adapters"]}
        # Traffic shares per key reflect each model's OWN requests.
        assert rows[("model-b", "base")]["traffic_share"] < 0.2
        assert rows[("model-b", "base")]["score"] >= cfg.noisy_ratio
        assert rows[("model-a", "base")]["state"] == gusage.QUIET
        assert "model-b" in rollup.noisy()

    def test_note_pick_matches_flagged_base_tenant(self):
        """A flagged base tenant is keyed by its SERVED model name (that
        is what note_pick receives); the would-deprioritize counter must
        move for it."""
        cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                                 enter_ticks=1, ema_alpha=1.0)
        rollup, m, req, _ = _rollup_fixture(cfg)
        m.adapter_step_seconds = {("served", "base", "decode"): 0.0,
                                  ("served", "quiet", "decode"): 0.0}
        rollup.tick(now=0.0)
        m.adapter_step_seconds = {("served", "base", "decode"): 9.0,
                                  ("served", "quiet", "decode"): 1.0}
        req.update({"served": 1, "quiet": 9})
        rollup.tick(now=5.0)
        assert rollup.noisy() == frozenset({"served"})
        rollup.note_pick("pod-0", "served")
        rollup.note_pick("pod-0", "quiet")
        # Counted under the flagged (model, adapter) KEY, not just the
        # matched request name — the offender attribution the log_only
        # fairness runs need.
        assert rollup.would_deprioritize == {("served", "base"): 1}

    def test_gc_of_flagged_key_journals_exit(self):
        """A noisy key whose adapter leaves every pod's exposition must
        journal the exit transition — no unmatched 'enter' events in the
        flight recorder."""
        cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                                 enter_ticks=1, ema_alpha=1.0)
        rollup, m, req, journal = _rollup_fixture(cfg)
        m.adapter_step_seconds = {("m", "hog", "decode"): 0.0,
                                  ("m", "quiet", "decode"): 0.0}
        rollup.tick(now=0.0)
        m.adapter_step_seconds = {("m", "hog", "decode"): 9.0,
                                  ("m", "quiet", "decode"): 1.0}
        req.update({"hog": 1, "quiet": 9})
        rollup.tick(now=5.0)
        assert rollup.noisy() == frozenset({"hog"})
        # The hog's adapter vanishes (unloaded / pod churn).
        m.adapter_step_seconds = {("m", "quiet", "decode"): 2.0}
        rollup.tick(now=10.0)
        rollup.tick(now=15.0)
        assert rollup.noisy() == frozenset()
        flags = journal.events(kind=events_mod.NOISY_NEIGHBOR, limit=16)
        assert [e["attrs"]["to"] for e in flags] == [gusage.NOISY,
                                                     gusage.QUIET]

    def test_pool_waste_aggregates(self):
        rollup, m, _req, _ = _rollup_fixture()
        m.idle_slot_seconds = 4.5
        m.prefill_padding_tokens = 20
        rollup.tick(now=0.0)
        waste = rollup.debug_payload()["pool_waste"]
        assert waste["idle_slot_seconds"] == 4.5
        assert waste["prefill_padding_tokens"] == 20


# ---------------------------------------------------------------------------
# The log-only scheduler seam: routing byte-identical (same-RNG diff test)
# ---------------------------------------------------------------------------


def _flagged_rollup(model="m"):
    cfg = gusage.UsageConfig(noisy_ratio=2.0, min_share=0.2,
                             enter_ticks=1, ema_alpha=1.0)
    rollup, metrics, req, _ = _rollup_fixture(cfg)
    metrics.adapter_step_seconds = {("base-model", model, "decode"): 0.0,
                                    ("base-model", "other", "decode"): 0.0}
    rollup.tick(now=0.0)
    metrics.adapter_step_seconds = {("base-model", model, "decode"): 9.0,
                                    ("base-model", "other", "decode"): 1.0}
    req.update({model: 1, "other": 9})
    rollup.tick(now=5.0)
    assert model in rollup.noisy()
    return rollup


class TestRoutingUnchanged:
    """Acceptance: the usage seam is LOG-ONLY — identical RNG, identical
    pick sequence with the advisor attached; only the would-deprioritize
    counter moves."""

    def _provider(self):
        return StaticProvider([
            PodMetrics(pod=Pod(f"pod-{i}", f"127.0.0.1:{i}"),
                       metrics=Metrics(waiting_queue_size=i % 3))
            for i in range(4)
        ])

    def test_picks_byte_identical_with_usage_advisor(self):
        from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
            Scheduler,
        )
        from llm_instance_gateway_tpu.gateway.scheduling.types import (
            LLMRequest,
        )

        provider = self._provider()
        mk = lambda: Scheduler(provider, token_aware=False,  # noqa: E731
                               prefill_aware=False, prefix_aware=False,
                               rng=random.Random(11))
        plain, advised = mk(), mk()
        rollup = _flagged_rollup("m")
        advised.usage_advisor = rollup

        req = LLMRequest(model="m", resolved_target_model="m",
                         critical=True)
        quiet = LLMRequest(model="other", resolved_target_model="other",
                           critical=True)
        picks_plain, picks_advised = [], []
        for i in range(64):
            r = req if i % 2 == 0 else quiet
            picks_plain.append(plain.schedule(r).name)
            picks_advised.append(advised.schedule(r).name)
        assert picks_plain == picks_advised  # routing byte-identical
        # Only flagged-model picks counted; the quiet model never.
        assert rollup.would_deprioritize_total == 32
        assert rollup.would_deprioritize == {("base-model", "m"): 32}

    def test_native_scheduler_has_the_same_seam(self):
        from llm_instance_gateway_tpu.gateway.scheduling import native

        if not native.available():
            pytest.skip("native scheduler library not built")
        from llm_instance_gateway_tpu.gateway.scheduling.types import (
            LLMRequest,
        )

        provider = self._provider()
        mk = lambda: native.NativeScheduler(  # noqa: E731
            provider, token_aware=False, prefill_aware=False,
            prefix_aware=False, rng=random.Random(11))
        plain, advised = mk(), mk()
        rollup = _flagged_rollup("m")
        advised.usage_advisor = rollup
        req = LLMRequest(model="m", resolved_target_model="m",
                         critical=True)
        picks_plain = [plain.schedule(req).name for _ in range(48)]
        picks_advised = [advised.schedule(req).name for _ in range(48)]
        assert picks_plain == picks_advised
        assert rollup.would_deprioritize_total == 48


# ---------------------------------------------------------------------------
# Debug surfaces + lig-top render
# ---------------------------------------------------------------------------


def test_api_http_debug_usage_endpoint():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_instance_gateway_tpu.server.api_http import ModelServer
    from test_exposition_contract import FakeEngine

    async def run():
        server = ModelServer(FakeEngine(), tokenizer=None,
                             model_name="tiny")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/debug/usage")
            assert resp.status == 200
            payload = await resp.json()
        finally:
            await client.close()
        assert payload["model"] == "tiny"
        # Tuple keys flatten to "adapter|phase" for JSON.
        assert any(k.endswith("|decode")
                   for k in payload["usage"]["step_seconds"])
        assert payload["usage"]["idle_slot_seconds"] == 2.75
        assert payload["waiting_lora_adapters"]

    asyncio.run(run())


def test_proxy_debug_usage_endpoint():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_instance_gateway_tpu.api.v1alpha1 import InferencePool
    from llm_instance_gateway_tpu.gateway.datastore import Datastore
    from llm_instance_gateway_tpu.gateway.handlers.server import Server
    from llm_instance_gateway_tpu.gateway.proxy import GatewayProxy
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
        Scheduler,
    )

    async def run():
        pod = Pod("pod-a", "127.0.0.1:1")
        ds = Datastore(pods=[pod])
        ds.set_pool(InferencePool(name="pool"))
        provider = StaticProvider([PodMetrics(
            pod=pod,
            metrics=Metrics(adapter_step_seconds={
                ("m", "a", "decode"): 2.0}))])
        proxy = GatewayProxy(
            Server(Scheduler(provider, token_aware=False,
                             prefill_aware=False), ds), provider, ds)
        # The pick seam is wired at construction: the FairnessPolicy wraps
        # the rollup (log_only keeps it byte-identical to the bare seam).
        outer = proxy.server.scheduler
        sched = getattr(outer, "_scheduler", outer)
        assert sched.usage_advisor is proxy.fairness
        assert proxy.fairness.usage is proxy.usage
        assert proxy.server.fairness is proxy.fairness
        client = TestClient(TestServer(proxy.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/debug/usage")
            assert resp.status == 200
            payload = await resp.json()
        finally:
            await client.close()
        assert "adapters" in payload and "pool_waste" in payload
        assert payload["ticks"] >= 1

    asyncio.run(run())


def test_lig_top_render():
    from tools.lig_top import render_table

    payload = {
        "ticks": 5,
        "pool_waste": {"idle_slot_seconds": 12.5,
                       "prefill_padding_tokens": 340},
        "noisy": ["hog"],
        "adapters": [
            {"model": "m", "adapter": "hog",
             "share": {"step_seconds": 0.81, "tokens": 0.7,
                       "kv_block_seconds": 0.6},
             "traffic_share": 0.2, "score": 4.05, "state": "noisy"},
            {"model": "m", "adapter": "quiet",
             "share": {"step_seconds": 0.19, "tokens": 0.3,
                       "kv_block_seconds": 0.4},
             "traffic_share": 0.8, "score": 0.24, "state": "quiet"},
        ],
    }
    out = render_table(payload)
    lines = out.splitlines()
    assert "noisy: hog" in out
    assert "idle_slot_seconds=12.5" in out
    hog_line = next(ln for ln in lines if ln.startswith("m"))
    assert "hog" in hog_line and "81.0" in hog_line and "noisy" in hog_line
    # Rows stay in payload order (pre-sorted by step share, descending).
    assert lines.index(hog_line) < lines.index(
        next(ln for ln in lines if "quiet" in ln))


def test_lig_top_render_empty_payload():
    from tools.lig_top import render_table

    out = render_table({"adapters": [], "pool_waste": {}, "noisy": []})
    assert "no attribution samples" in out


# ---------------------------------------------------------------------------
# Blackbox dump carries the usage payload
# ---------------------------------------------------------------------------


def test_blackbox_includes_usage(tmp_path):
    import json

    from llm_instance_gateway_tpu.gateway import slo as slo_mod

    path = slo_mod.write_blackbox(
        str(tmp_path), {"trigger": "fast_burn", "model": "m",
                        "objective": "ttft"},
        usage_payload={"adapters": [{"adapter": "hog"}], "noisy": ["hog"]})
    with open(path) as f:
        dump = json.load(f)
    assert dump["usage"]["noisy"] == ["hog"]
