"""Engine decode fast-path levers (ISSUE 15 tentpole).

Three levers, one parity contract: adaptive multi-step dispatch with
device-side stop-string automata and N concurrent chunk-stream lanes must
produce BYTE-IDENTICAL outputs to the steps=1 host-stop oracle — on both
engine loops — while actually exercising the fast paths (fused dispatches,
mid-block device freezes, concurrently-advancing streams).
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.sampling import (
    STOP_LEN,
    encode_stop_rows,
    stop_hist_update,
    stop_suffix_hit,
)

CFG = TINY_TEST


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def make_engine(params, *, adaptive=0, device_stops=True, steps=1, lanes=1,
                burst=1, slots=2, paged=False, blocks=None, max_seq=64,
                buckets=(8, 16)):
    return Engine(
        CFG, params,
        EngineConfig(
            decode_slots=slots, max_seq_len=max_seq,
            prefill_buckets=buckets,
            decode_steps_per_sync=steps, adaptive_steps=adaptive,
            device_stops=device_stops, stream_lanes=lanes,
            stream_burst=burst,
            paged_kv_block=8 if paged else None, paged_kv_blocks=blocks,
        ),
        lora_manager=None, eos_id=None, dtype=jnp.float32,
    )


def gen(engine, prompt, max_new=8, stop_sequences=(), stop_token_ids=(),
        temp=0.0, seed=None):
    req = Request(
        prompt_tokens=list(prompt), max_new_tokens=max_new,
        sampling=SamplingParams(temperature=temp, seed=seed),
        stop_sequences=tuple(tuple(s) for s in stop_sequences),
        stop_token_ids=tuple(stop_token_ids),
    )
    engine.generate(req, timeout_s=120)
    assert req.error is None, req.error
    return req


class TestStopAutomatonUnits:
    def test_encode_right_aligned_and_bounds(self):
        ids, lens = encode_stop_rows([(5, 6), (7,)])
        assert lens[0] == 2 and lens[1] == 1
        assert ids[0][-2:] == [5, 6] and ids[0][:-2] == [-1] * (STOP_LEN - 2)
        assert ids[1][-1] == 7
        assert encode_stop_rows([()]) is None            # empty entry
        assert encode_stop_rows([(1,)] * 5) is None      # too many
        assert encode_stop_rows([tuple(range(STOP_LEN + 1))]) is None

    def test_suffix_hit_and_short_history(self):
        ids, lens = encode_stop_rows([(5, 6)])
        stop_ids = jnp.asarray([ids], jnp.int32)         # [1, S, L]
        stop_lens = jnp.asarray([lens], jnp.int32)
        hist = jnp.full((1, STOP_LEN), -1, jnp.int32)
        # One token generated (6): a 2-token stop must NOT match yet.
        hist = stop_hist_update(hist, jnp.asarray([6]), jnp.asarray([True]))
        assert not bool(stop_suffix_hit(hist, stop_ids, stop_lens)[0])
        hist = stop_hist_update(hist, jnp.asarray([5]), jnp.asarray([True]))
        hist = stop_hist_update(hist, jnp.asarray([6]), jnp.asarray([True]))
        assert bool(stop_suffix_hit(hist, stop_ids, stop_lens)[0])
        # Frozen rows keep their history (no false advance).
        frozen = stop_hist_update(hist, jnp.asarray([9]),
                                  jnp.asarray([False]))
        assert (np.asarray(frozen) == np.asarray(hist)).all()

    def test_no_stops_never_match(self):
        stop_ids = jnp.full((2, 4, STOP_LEN), -1, jnp.int32)
        stop_lens = jnp.zeros((2, 4), jnp.int32)
        hist = jnp.full((2, STOP_LEN), -1, jnp.int32)
        assert not bool(stop_suffix_hit(hist, stop_ids, stop_lens).any())


class TestDeviceStopParity:
    """Fused device-side stop strings == steps=1 host oracle, byte for
    byte (the PR's pinned acceptance bar)."""

    def test_multi_token_stop_parity(self, params):
        oracle = make_engine(params, steps=1, device_stops=False)
        oracle.start()
        try:
            free = gen(oracle, (5, 6, 7), max_new=16).output_tokens
            # Stops chosen FROM the greedy continuation so they really hit:
            # one inside the first fused block, one spanning the 8-step
            # dispatch boundary of the adaptive ceiling.
            # An in-vocab pair that never appears consecutively in the
            # greedy continuation: the "stop never fires" case.
            miss = next(
                [a, b]
                for a in range(CFG.vocab_size)
                for b in (a + 1,)
                if [a, b] not in [free[i:i + 2] for i in range(len(free))])
            cases = [
                ([free[2:4]], ()),                 # len-2, hits mid-block
                ([free[6:9]], ()),                 # len-3, spans step-8 edge
                ([free[2:4], free[6:9]], ()),      # first match wins
                ([miss], ()),                      # never matches: length
                ([], (free[3],)),                  # custom id via automaton
            ]
            wants = [
                gen(oracle, (5, 6, 7), max_new=16, stop_sequences=ss,
                    stop_token_ids=ids)
                for ss, ids in cases
            ]
        finally:
            oracle.stop()
        fused = make_engine(params, adaptive=8, device_stops=True)
        fused.start()
        try:
            for (ss, ids), want in zip(cases, wants):
                got = gen(fused, (5, 6, 7), max_new=16, stop_sequences=ss,
                          stop_token_ids=ids)
                assert got.output_tokens == want.output_tokens, (ss, ids)
                assert got.finish_reason == want.finish_reason, (ss, ids)
        finally:
            fused.stop()

    def test_stop_spanning_dispatch_boundary_static_steps(self, params):
        """History must carry ACROSS dispatches: with static 4-step fusion
        a stop whose tokens straddle the block edge still matches."""
        oracle = make_engine(params, steps=1, device_stops=False)
        oracle.start()
        try:
            free = gen(oracle, (9, 9), max_new=12).output_tokens
            stop = free[2:5]  # tokens 3..5 emit across the 4-step boundary
            want = gen(oracle, (9, 9), max_new=12, stop_sequences=[stop])
        finally:
            oracle.stop()
        fused = make_engine(params, steps=4, device_stops=True)
        fused.start()
        try:
            got = gen(fused, (9, 9), max_new=12, stop_sequences=[stop])
        finally:
            fused.stop()
        assert got.output_tokens == want.output_tokens
        assert got.finish_reason == "stop" == want.finish_reason
        assert got.output_tokens[-len(stop):] == list(stop)

    def test_device_freeze_really_happens_mid_block(self, params):
        """The device automaton (not just the host trim) freezes the row:
        after the stop lands mid-block the remaining fused steps come back
        invalid, so the output stops exactly at the match even though the
        dispatch ran 8 steps."""
        probe = make_engine(params, steps=1, device_stops=False)
        probe.start()
        try:
            free = gen(probe, (5, 6, 7), max_new=16).output_tokens
        finally:
            probe.stop()
        eng = make_engine(params, steps=8, device_stops=True)
        eng.start()
        try:
            got = gen(eng, (5, 6, 7), max_new=16,
                      stop_sequences=[free[1:3]])
        finally:
            eng.stop()
        assert got.output_tokens == free[:3]
        assert got.finish_reason == "stop"

    def test_paged_and_prefix_compose(self, params):
        """Device stops on the paged pool with prefix caching: parity vs
        the host oracle on the same cache layout."""
        oracle = make_engine(params, steps=1, device_stops=False,
                             paged=True, blocks=24)
        prefix = list(np.random.RandomState(3).randint(1, 250, size=8))
        p = prefix + [41, 42]
        oracle.start()
        try:
            free = gen(oracle, p, max_new=10).output_tokens
            want = gen(oracle, p, max_new=10, stop_sequences=[free[2:4]])
        finally:
            oracle.stop()
        fused = make_engine(params, adaptive=8, device_stops=True,
                            paged=True, blocks=24)
        fused.start()
        try:
            got = gen(fused, p, max_new=10, stop_sequences=[free[2:4]])
        finally:
            fused.stop()
        assert got.output_tokens == want.output_tokens
        assert got.finish_reason == want.finish_reason == "stop"

    def test_validation_rejects_bad_sequences(self, params):
        eng = make_engine(params)
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit(Request(prompt_tokens=[1, 2],
                               stop_sequences=((),)))
        with pytest.raises(ValueError, match="vocabulary"):
            eng.submit(Request(prompt_tokens=[1, 2],
                               stop_sequences=((CFG.vocab_size + 7,),)))


class TestAdaptivePlanner:
    def test_same_seed_parity_across_fusion(self, params):
        """Seeded sampling depends only on (seed, position): adaptive
        fused dispatch must reproduce the steps=1 oracle token-for-token
        even at temperature > 0."""
        oracle = make_engine(params, steps=1)
        oracle.start()
        try:
            want = gen(oracle, (3, 1, 4), max_new=12, temp=0.9,
                       seed=42).output_tokens
        finally:
            oracle.stop()
        fused = make_engine(params, adaptive=8)
        fused.start()
        try:
            got = gen(fused, (3, 1, 4), max_new=12, temp=0.9,
                      seed=42).output_tokens
        finally:
            fused.stop()
        assert got == want

    def test_planner_fuses_and_records_histogram(self, params):
        eng = make_engine(params, adaptive=8)
        eng.start()
        try:
            gen(eng, (5, 6, 7), max_new=17)
        finally:
            eng.stop()
        st = eng.dispatch_steps_hist.state()
        # Some dispatch fused more than one step...
        assert st["sum"] > st["count"]
        # ...and the planner clamped to the remaining budget instead of
        # overshooting: 16 decode steps (1 token came from prefill), and
        # the one block that is dispatched before the host has read the
        # row's last token is a single step, never a fused one.
        assert 16 <= st["sum"] <= 17

    def test_streaming_rows_cap_fusion(self, params):
        """The SSE-cadence planner input: a streaming consumer pins every
        dispatch to adaptive_stream_cap (default 1) — the regression test
        for fused bursts wrecking perceived TPOT."""
        eng = make_engine(params, adaptive=8)
        eng.start()
        try:
            req = Request(prompt_tokens=[5, 6, 7], max_new_tokens=10,
                          sampling=SamplingParams(temperature=0.0),
                          streaming=True)
            eng.generate(req, timeout_s=120)
            assert req.error is None
        finally:
            eng.stop()
        st = eng.dispatch_steps_hist.state()
        assert st["count"] >= 9          # one dispatch per decode token
        assert st["sum"] == st["count"]  # every dispatch ran exactly 1 step

    def test_inter_token_arrival_in_streaming_path(self, params):
        """Per-step emission: a consumer thread waiting on stream_event
        observes the fused block's tokens incrementally (many distinct
        wakes), not as one end-of-dispatch burst."""
        eng = make_engine(params, adaptive=8)
        eng.start()
        req = Request(prompt_tokens=[5, 6, 7], max_new_tokens=12,
                      sampling=SamplingParams(temperature=0.0),
                      streaming=True)
        observations = []

        def consume():
            while not req.done.is_set():
                req.stream_event.wait(1.0)
                req.stream_event.clear()
                observations.append(len(req.output_tokens))
            observations.append(len(req.output_tokens))

        t = threading.Thread(target=consume)
        t.start()
        try:
            eng.generate(req, timeout_s=120)
        finally:
            t.join(timeout=10)
            eng.stop()
        assert req.error is None
        distinct = sorted(set(observations))
        # Streaming cap = 1 step per dispatch, one wake per token: the
        # consumer must see a real progression, not 0 -> 12 in one hop.
        assert len(distinct) >= len(req.output_tokens) // 2, distinct


class TestStreamLanes:
    LONG = 40  # > largest bucket (16): takes the chunk-stream path

    def _mixed(self, engine, rng_seed=0):
        rng = np.random.RandomState(rng_seed)
        long_a = list(rng.randint(1, 250, size=self.LONG))
        long_b = list(rng.randint(1, 250, size=self.LONG))
        short = [(5, 6, 7), (9, 9)]
        reqs = [Request(prompt_tokens=p, max_new_tokens=6,
                        sampling=SamplingParams(temperature=0.0))
                for p in (long_a, long_b, *short)]
        max_active = 0
        for r in reqs:
            engine.submit(r)
        while not all(r.done.is_set() for r in reqs):
            max_active = max(max_active, len(engine._streams))
            time.sleep(0.0005)
        for r in reqs:
            assert r.error is None, r.error
        return [r.output_tokens for r in reqs], max_active

    def test_two_lanes_token_parity_and_overlap(self, params):
        serial = make_engine(params, lanes=1, slots=4)
        serial.start()
        try:
            want, max_active_1 = self._mixed(serial)
        finally:
            serial.stop()
        assert max_active_1 <= 1  # the old head-of-line behavior
        dual = make_engine(params, lanes=2, slots=4)
        dual.start()
        try:
            got, max_active_2 = self._mixed(dual)
        finally:
            dual.stop()
        assert got == want
        # The second long prompt streamed CONCURRENTLY with the first.
        assert max_active_2 == 2

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_a_burst_gives_the_tokens_of_one_chunk_a_turn(self, params,
                                                          lanes):
        serial = make_engine(params, lanes=lanes, slots=4)
        serial.start()
        try:
            want, _ = self._mixed(serial)
        finally:
            serial.stop()
        burst = make_engine(params, lanes=lanes, burst=4, slots=4)
        burst.start()
        try:
            got, _ = self._mixed(burst)
        finally:
            burst.stop()
        assert got == want

    @pytest.mark.parametrize("burst,first,second", [
        (1, "one chunk in", "waits"),
        (2, "two chunks in", "waits"),
        (3, "done", "waits"),
        (4, "done", "one chunk in"),
        (8, "done", "done"),
    ])
    def test_the_chunks_of_one_turn(self, params, burst, first, second):
        """Two prompts of three chunks each, one lane, the loop not
        running: what one turn's chunk programs leave of them."""
        eng = make_engine(params, burst=burst, slots=4)
        rng = np.random.RandomState(3)
        reqs = [Request(prompt_tokens=list(rng.randint(1, 250,
                                                       size=self.LONG)),
                        max_new_tokens=4,
                        sampling=SamplingParams(temperature=0.0))
                for _ in range(2)]
        for r in reqs:
            eng.submit(r)
        eng._admit_and_insert()
        eng._stream_turn()

        def state(req):
            if any(s is not None and s.request is req for s in eng.slots):
                return "done"
            for st in eng._streams:
                if st.request is req:
                    return {16: "one chunk in", 32: "two chunks in",
                            0: "no chunk in"}[st.next_start]
            return "waits"

        assert [state(r) for r in reqs] == [first, second]
        # A lane is one stream's at a time, whatever the burst.
        assert len(eng._streams) <= 1

    def test_lane_pressure_gate_under_tiny_pool(self, params):
        """KV-pressure-aware admission: a pool too small for two whole
        prompts + decode growth keeps the second stream parked — and the
        run still completes with serialized-identical tokens."""
        serial = make_engine(params, lanes=1, slots=3, paged=True,
                             blocks=20)
        serial.start()
        try:
            want, _ = self._mixed(serial, rng_seed=1)
        finally:
            serial.stop()
        tight = make_engine(params, lanes=2, slots=3, paged=True,
                            blocks=20)
        tight.start()
        try:
            got, _ = self._mixed(tight, rng_seed=1)
        finally:
            tight.stop()
        assert got == want

    def test_lane_gauges_exported(self, params):
        eng = make_engine(params, lanes=3)
        snap = eng.metrics_snapshot()
        assert snap["stream_lanes"] == 3
        assert snap["stream_lanes_active"] == 0
        from llm_instance_gateway_tpu.server import metrics as server_metrics

        text = server_metrics.render(snap)
        assert "tpu:stream_lanes 3" in text
        assert "tpu:stream_lanes_active 0" in text
        assert "tpu:dispatch_steps_bucket" in text


class TestHTTPStopWiring:
    def test_openai_stop_strings_reach_the_engine_automaton(self, params):
        """The production surface feeds tokenized `stop` strings into
        Request.stop_sequences (early-freeze accelerator; the text-level
        scan stays the oracle) — only round-trippable encodings qualify."""
        from llm_instance_gateway_tpu.server.api_http import ModelServer
        from llm_instance_gateway_tpu.server.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        eng = make_engine(params)
        server = ModelServer(eng, tok, "llama3-tiny")
        req = server._make_request({"stop": ["ab"], "max_tokens": 4},
                                   [1, 2], None)
        assert len(req.stop_sequences) == 1
        assert tok.decode(list(req.stop_sequences[0])) == "ab"
        # Non-list/empty shapes degrade to no sequences, never an error.
        assert server._make_request({"stop": ""}, [1], None).stop_sequences == ()
        assert server._make_request({}, [1], None).stop_sequences == ()


class TestSSEPerTokenChunks:
    def test_sse_emits_one_chunk_per_token(self, params):
        """HTTP-level regression: with fused dispatch the SSE stream still
        delivers (roughly) one delta chunk per token — the per-token
        chunking in _stream_sse_loop, fed by per-step emission."""
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_instance_gateway_tpu.server.api_http import ModelServer
        from llm_instance_gateway_tpu.server.tokenizer import ByteTokenizer

        eng = make_engine(params, adaptive=8, slots=2, max_seq=64,
                          buckets=(8, 16, 32))
        eng.start()
        server = ModelServer(eng, ByteTokenizer(), "llama3-tiny")

        async def run():
            client = TestClient(TestServer(server.build_app()))
            await client.start_server()
            try:
                resp = await client.post("/v1/completions", json={
                    "model": "llama3-tiny", "prompt": "hi",
                    "max_tokens": 12, "stream": True,
                })
                assert resp.status == 200
                raw = await resp.read()
            finally:
                await client.close()
            return raw

        try:
            raw = asyncio.new_event_loop().run_until_complete(run())
        finally:
            eng.stop()
        deltas = []
        for line in raw.split(b"\n"):
            if line.startswith(b"data: ") and line[6:] != b"[DONE]":
                payload = json.loads(line[6:])
                if "choices" in payload:
                    deltas.append(payload)
        # 12 tokens; ByteTokenizer may hold back multi-byte tails, so
        # allow some grouping — but a burst regression (1-2 fat chunks)
        # must fail.
        assert len(deltas) >= 8, len(deltas)
