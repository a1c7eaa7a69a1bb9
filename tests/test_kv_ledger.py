"""KV economy ledger (server/kv_ledger.py): the block-lifecycle books.

The acceptance bar: the ledger's per-state block accounting TILES the
budget — free + active + prefix_resident + parked == blocks_total within
one block — verified through the RENDERED exposition (the same text the
gateway scrapes), under a randomized workload that exercises every
lifecycle path at once: prefix-cache reuse hits, LRU eviction, release
parking, handoff imports parked in decode_wait, and chunk-stream lanes.
Plus the unit layer (charge methods, bounded prefix LRU, fragmentation
runs, hostile-label rendering) and the ``/debug/kv`` surface.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server import metrics as server_metrics
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.kv_ledger import (
    EVENT_KINDS,
    STATES,
    KvLedger,
    free_run_lengths,
    render_kv,
)
from llm_instance_gateway_tpu.server.kv_transfer import PrefillHandoff
from llm_instance_gateway_tpu.utils import prom_parse

CFG = TINY_TEST
HOSTILE_PREFIX = 'ab"12\\cd\n34'


# ---------------------------------------------------------------------------
# Unit layer (no engine)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestLedgerUnits:
    def test_free_run_lengths(self):
        assert free_run_lengths([]) == []
        assert free_run_lengths([5]) == [1]
        # LIFO allocator order must not matter: {1,2,3} and {8,9} are the
        # maximal consecutive runs regardless of free-list order.
        assert sorted(free_run_lengths([9, 3, 1, 2, 8])) == [2, 3]
        assert free_run_lengths(range(10)) == [10]

    def test_states_tile_budget_and_parked_ceil(self):
        led = KvLedger(n_blocks=16, block_tokens=8, clock=FakeClock())
        # 9 parked tokens -> ceil(9/8) = 2 block-equivalents.
        led.sync_states(free_blocks=[0, 1, 2], active_blocks=10,
                        prefix_resident=3, parked_tokens=9)
        snap = led.snapshot()
        states = snap["states"]
        assert states == {"free": 3, "active": 10, "prefix_resident": 3,
                          "parked": 2}
        assert snap["blocks_total"] == 16 + 2
        assert sum(states.values()) == snap["blocks_total"]
        assert snap["parked_tokens"] == 9
        # The parked-share histogram sampled the sync.
        assert snap["parked_share"]["count"] == 1

    def test_charges_round_trip_snapshot(self):
        clock = FakeClock()
        led = KvLedger(n_blocks=8, block_tokens=8, clock=clock)
        led.note_alloc(n=3)
        led.note_register("aa00", blocks=2)
        clock.t += 5.0
        led.note_reuse_hit("aa00", blocks=2, tokens=16)
        led.note_release(freed=1, cached=2)
        led.note_park(24, source="handoff")
        led.note_unpark(24)
        led.note_sweep(24, reason="ttl")
        snap = led.snapshot()
        assert snap["events"]["alloc"] == 3
        assert snap["events"]["register"] == 1
        assert snap["events"]["reuse_hit"] == 1
        assert snap["events"]["release"] == 1
        assert snap["events"]["cache_park"] == 2
        assert snap["events"]["park"] == 1
        assert snap["events"]["unpark"] == 1
        assert snap["events"]["sweep"] == 1
        assert set(snap["events"]) <= set(EVENT_KINDS)
        (entry,) = snap["prefixes"]
        assert entry["prefix"] == "aa00"
        assert entry["hits"] == 1
        assert entry["tokens_saved"] == 16
        assert entry["blocks"] == 2
        assert entry["age_s"] == 0.0  # hit re-touched it at t+5
        # Ring holds the lifecycle narrative, newest last.
        assert [e["kind"] for e in snap["ring"]] == [
            "alloc", "register", "reuse_hit", "release", "park", "unpark",
            "sweep"]

    def test_eviction_decays_chain_and_unwind_cancels_hit(self):
        led = KvLedger(n_blocks=8, block_tokens=8, clock=FakeClock())
        led.note_register("aa00", blocks=3)
        led.note_reuse_hit("aa00", blocks=3, tokens=24)
        led.note_evict("aa00")
        led.note_reuse_unwind("aa00", blocks=3, tokens=24)
        (entry,) = led.snapshot()["prefixes"]
        assert entry["blocks"] == 2      # chain terminus evicted
        assert entry["hits"] == 0        # unwind cancelled the hit
        assert entry["tokens_saved"] == 0

    def test_prefix_table_lru_bounded(self):
        led = KvLedger(n_blocks=8, block_tokens=8, prefix_table_cap=4,
                       clock=FakeClock())
        for i in range(7):
            led.note_register("p%02d" % i, blocks=1)
        led.note_reuse_hit("p03", blocks=1, tokens=8)  # keep p03 hot
        snap = led.snapshot()
        assert snap["prefix_table_size"] == 4
        assert snap["prefix_table_evictions"] == 3
        assert {e["prefix"] for e in snap["prefixes"]} == {
            "p03", "p04", "p05", "p06"}

    def test_render_kv_escapes_hostile_prefix(self):
        led = KvLedger(n_blocks=8, block_tokens=8, clock=FakeClock())
        led.note_register(HOSTILE_PREFIX, blocks=1)
        led.note_reuse_hit(HOSTILE_PREFIX, blocks=1, tokens=8)
        led.sync_states([0, 1], 4, 2, 0)
        text = "\n".join(render_kv(led.snapshot())) + "\n"
        fams = prom_parse.parse_text(text)
        # Parse succeeded and the hostile id round-tripped unmangled.
        assert fams["tpu:kv_prefix_hits_total"][0].labels["prefix"] \
            == HOSTILE_PREFIX
        states = {s.labels["state"]: s.value for s in fams["tpu:kv_blocks"]}
        assert set(states) == set(STATES)
        assert sum(states.values()) == fams["tpu:kv_blocks_total"][0].value
        assert "tpu:kv_free_run_blocks_bucket" in fams
        assert "tpu:kv_parked_share_bucket" in fams

    def test_ledger_thread_safety_smoke(self):
        """Concurrent chargers + snapshotters: no exception, counters
        conserve (the witness harness covers ordering; this is the
        drop-in sanity net)."""
        # free(3) + active(4) + prefix_resident(5) tile the 12-block pool;
        # parked rides on top, so every snapshot must conserve exactly.
        led = KvLedger(n_blocks=12, block_tokens=8)
        stop = threading.Event()

        def charge():
            i = 0
            while not stop.is_set():
                led.note_register("p%d" % (i % 9), blocks=1)
                led.note_reuse_hit("p%d" % (i % 9), blocks=1, tokens=8)
                led.sync_states([1, 2, 3], 4, 5, i % 17)
                i += 1

        threads = [threading.Thread(target=charge) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 0.3
            while time.monotonic() < deadline:
                snap = led.snapshot()
                assert sum(snap["states"].values()) == snap["blocks_total"]
        finally:
            stop.set()
            for t in threads:
                t.join()
        snap = led.snapshot()
        assert snap["events"]["register"] >= snap["prefix_table_size"]


# ---------------------------------------------------------------------------
# Engine integration: conservation through the rendered exposition
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def make_engine(params, **overrides):
    base = dict(decode_slots=4, max_seq_len=64, prefill_buckets=(8, 16),
                paged_kv_block=8, prefix_cache=True, stream_lanes=2)
    base.update(overrides)
    eng = Engine(CFG, params, EngineConfig(**base), lora_manager=None,
                 eos_id=None, dtype=jnp.float32)
    eng.start()
    return eng


# make_engine overrides for the contiguous-lane cache (no block economy).
LANES = dict(paged_kv_block=None, prefix_cache=False)


def mk_req(prompt, max_new=4):
    return Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                   sampling=SamplingParams(temperature=0.0))


def rendered_kv_families(engine):
    snap = engine.metrics_snapshot()
    snap["model_name"] = "tiny"
    return prom_parse.parse_text(server_metrics.render(snap))


def assert_conserved(fams, where=""):
    states = {s.labels["state"]: s.value for s in fams["tpu:kv_blocks"]}
    total = fams["tpu:kv_blocks_total"][0].value
    assert set(states) == set(STATES), where
    assert abs(sum(states.values()) - total) <= 1, (
        where, states, total)
    return states, total


class TestEngineConservation:
    def test_randomized_workload_conserves_blocks(self, params):
        """Three waves of randomized traffic — shared-prefix reuse, long
        prompts through the chunk-stream lanes, short fills — with the
        conservation sum checked on the rendered exposition between
        waves and at the end."""
        rng = np.random.RandomState(7)
        engine = make_engine(params)
        shared = list(rng.randint(1, 200, size=16))  # 2 full 8-tok blocks
        try:
            for wave in range(3):
                reqs = []
                for _ in range(3):  # shared-prefix traffic (reuse hits)
                    suffix = list(rng.randint(
                        1, 200, size=int(rng.randint(2, 7))))
                    reqs.append(mk_req(shared + suffix))
                # One long prompt past the largest bucket: the chunk-
                # stream lane path.
                reqs.append(mk_req(list(rng.randint(1, 200, size=24))))
                for _ in range(2):  # short random fills
                    reqs.append(mk_req(list(rng.randint(
                        1, 200, size=int(rng.randint(3, 9))))))
                for r in reqs:
                    engine.submit(r)
                for r in reqs:
                    assert r.done.wait(120)
                    assert r.error is None, r.error
                fams = rendered_kv_families(engine)
                assert_conserved(fams, where="wave %d" % wave)
            fams = rendered_kv_families(engine)
            states, total = assert_conserved(fams, where="final")
            # The workload exercised the economy: reuse hits landed on
            # the shared prefix, blocks allocated and released.
            events = {s.labels["kind"]: s.value
                      for s in fams["tpu:kv_block_events_total"]}
            assert events.get("alloc", 0) > 0
            assert events.get("release", 0) > 0
            assert events.get("reuse_hit", 0) >= 2, events
            assert events.get("register", 0) > 0
            # The heatmap has the shared prefix as its hottest row, and
            # its tokens-saved tracks the engine's own reuse counter.
            hits = {s.labels["prefix"]: s.value
                    for s in fams["tpu:kv_prefix_hits_total"]}
            assert max(hits.values()) >= 2
            saved = sum(s.value for s in
                        fams["tpu:kv_prefix_tokens_saved_total"])
            assert saved == fams["tpu:prefix_reused_tokens"][0].value
            # Quiesced: nothing active, nothing parked; the budget is
            # split between the free list and the prefix cache.
            assert states["active"] == 0 and states["parked"] == 0
            assert states["prefix_resident"] > 0
            # Fragmentation histogram observed the free runs.
            assert fams["tpu:kv_free_run_blocks_count"][0].value > 0
        finally:
            engine.stop()

    def test_handoff_import_parks_and_conserves(self, params):
        """Conservation holds WHILE handoff-imported KV sits parked in
        decode_wait (the parked state counts block-equivalents held
        outside the pool, growing the budget)."""
        engine = make_engine(params, decode_slots=2, max_seq_len=2048)
        pre = make_engine(params, role="prefill", stream_lanes=1)
        try:
            # Occupy both decode slots with decodes that outlast the
            # prefill engine's compile however fast a tiny step is.
            occupiers = [mk_req(list(range(3, 11)), max_new=2000)
                         for _ in range(2)]
            for r in occupiers:
                engine.submit(r)
            deadline = time.monotonic() + 60
            while any(not r.output_tokens for r in occupiers):
                assert time.monotonic() < deadline, "occupiers never ran"
                time.sleep(0.01)
            # Import a prefill handoff (prompt within the largest bucket —
            # prefill_only refuses chunked prompts): both slots busy ->
            # the imported KV must park in decode_wait.
            handoff = pre.prefill_only(mk_req(list(range(30, 44)),
                                              max_new=4), timeout_s=120)
            imported = engine.attach_prefilled(
                PrefillHandoff.from_bytes(handoff.to_bytes()))
            parked_seen = False
            deadline = time.monotonic() + 60
            while not imported.done.is_set() and not parked_seen:
                fams = rendered_kv_families(engine)
                states, _total = assert_conserved(fams, where="parked")
                parked_seen = states["parked"] > 0
                assert time.monotonic() < deadline
            assert parked_seen, "handoff import never observed parked"
            for r in occupiers + [imported]:
                assert r.done.wait(120)
                assert r.error is None, r.error
            fams = rendered_kv_families(engine)
            states, _ = assert_conserved(fams, where="drained")
            assert states["parked"] == 0
            events = {s.labels["kind"]: s.value
                      for s in fams["tpu:kv_block_events_total"]}
            assert events.get("park", 0) >= 1
            assert events.get("unpark", 0) >= 1
        finally:
            engine.stop()
            pre.stop()

    def test_lane_cache_has_no_ledger_and_no_families(self, params):
        """The ledger exists exactly when the cache is paged: a lane
        engine has none, and no tpu:kv_blocks* families (the token-level
        tpu:kv_tokens_* gauges are a separate, older surface)."""
        engine = make_engine(params, **LANES)
        try:
            r = engine.generate(mk_req((5, 6, 7)), timeout_s=120)
            assert r.error is None
            assert engine.kv_ledger is None
            snap = engine.metrics_snapshot()
            assert "kv_ledger" not in snap
            text = server_metrics.render({**snap, "model_name": "t"})
            assert "tpu:kv_blocks_total" not in text
            assert "tpu:kv_block_events_total" not in text
        finally:
            engine.stop()


# ---------------------------------------------------------------------------
# /debug/kv surface (api_http)
# ---------------------------------------------------------------------------


def test_api_http_debug_kv_endpoint(params):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_instance_gateway_tpu.server.api_http import ModelServer

    engine = make_engine(params)

    async def run():
        server = ModelServer(engine, tokenizer=None, model_name="tiny")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/debug/kv")
            assert resp.status == 200
            payload = await resp.json()
        finally:
            await client.close()
        return payload

    try:
        engine.generate(mk_req(tuple(range(3, 20))), timeout_s=120)
        payload = asyncio.run(run())
    finally:
        engine.stop()
    assert payload["model"] == "tiny"
    assert set(payload["states"]) == set(STATES)
    assert sum(payload["states"].values()) == payload["blocks_total"]
    assert payload["block_tokens"] == 8
    assert payload["syncs"] > 0
    assert isinstance(payload["ring"], list)


def test_api_http_debug_kv_404_on_a_lane_cache(params):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_instance_gateway_tpu.server.api_http import ModelServer

    engine = make_engine(params, **LANES)

    async def run():
        server = ModelServer(engine, tokenizer=None, model_name="tiny")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.get("/debug/kv")
            assert resp.status == 404
            body = await resp.json()
        finally:
            await client.close()
        return body

    try:
        body = asyncio.run(run())
    finally:
        engine.stop()
    assert "not paged" in body["error"]["message"]
