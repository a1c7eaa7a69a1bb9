"""Cross-engine prefill/decode disaggregation: the KV handoff plane.

The correctness contract: a prefill-role engine's ``prefill_only`` handoff,
attached on a SECOND engine via ``attach_prefilled``, produces tokens
IDENTICAL to collocated serving — for both cache layouts (lane and paged),
both KV-quant configs (bf16/f32 and int8), both wire lanes (raw and
int8-quantized), with a LoRA adapter set, across a real serialization
round-trip.  Plus: attach is idempotent, registers imported blocks in the
decode engine's prefix-cache chain (so local traffic reuses them), and the
parked-KV accounting the gateway routes on stays truthful.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.kv_transfer import (
    PrefillHandoff,
    export_handoff,
    make_request,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

CFG = TINY_TEST
PROMPT = tuple(range(3, 20))  # 17 tokens -> 2 full 8-token blocks


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def adapter_weights(seed=7, rank=2):
    from llm_instance_gateway_tpu.models.lora import target_dims

    dims = target_dims(CFG)
    rng = np.random.RandomState(seed)
    return {
        t: {"a": rng.randn(CFG.n_layers, dims[t][0], rank) * 0.5,
            "b": rng.randn(CFG.n_layers, rank, dims[t][1]) * 0.5}
        for t in ("q", "v")
    }


def make_engine(start=True, lora=False, **overrides):
    base = dict(decode_slots=2, max_seq_len=64, prefill_buckets=(8, 16, 32))
    base.update(overrides)
    manager = None
    if lora:
        manager = LoRAManager(CFG, dtype=jnp.float32)
        manager.load("handoff-adapter", weights=adapter_weights(),
                     alpha=8.0, rank=2)
    eng = Engine(CFG, jax.tree.map(lambda x: x, make_engine.params),
                 EngineConfig(**base), lora_manager=manager,
                 eos_id=None, dtype=jnp.float32)
    if start:
        eng.start()
    return eng


def make_req(prompt=PROMPT, max_new=8, adapter=None, temp=0.0, **kw):
    return Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                   sampling=SamplingParams(temperature=temp), adapter=adapter,
                   **kw)


@pytest.fixture(scope="module", autouse=True)
def _bind_params(params):
    make_engine.params = params
    yield


class TestWireFormat:
    def _req(self):
        return make_req(max_new=5, adapter="a1")

    def _kv(self, seed=0):
        rng = np.random.RandomState(seed)
        # [L, 1, bucket, Kh, hd] like a bucketed prefill's output.
        shape = (CFG.n_layers, 1, 32, CFG.n_kv_heads, CFG.resolved_head_dim)
        return (rng.randn(*shape).astype(np.float32),
                rng.randn(*shape).astype(np.float32))

    def test_raw_roundtrip_exact(self):
        k, v = self._kv()
        req = self._req()
        req.logprobs = 2
        h = export_handoff(req, k, v, n=17, first_token=42,
                           lp_info=(np.float32(-1.5),
                                    np.zeros(5, np.float32),
                                    np.arange(5, dtype=np.int32)))
        h2 = PrefillHandoff.from_bytes(h.to_bytes())
        assert h2.kv_format == "raw"
        np.testing.assert_array_equal(h2.k, k[:, 0, :17])
        np.testing.assert_array_equal(h2.v, v[:, 0, :17])
        assert h2.prompt_tokens == list(PROMPT)
        assert h2.first_token == 42
        assert h2.adapter == "a1"
        assert h2.logprobs == 2
        lp, top_v, top_i = h2.first_lp_info()
        assert float(lp) == -1.5 and list(top_i) == [0, 1, 2, 3, 4]
        # The rebuilt Request carries the sampling params verbatim.
        r2 = make_request(h2)
        assert r2.prompt_tokens == list(PROMPT)
        assert r2.max_new_tokens == 5
        assert r2.request_id == req.request_id

    def test_int8_roundtrip_and_stability(self):
        """int8 wire: close to the source values, and quantization-STABLE —
        dequantize -> re-quantize reproduces the identical int8 payload
        (the property that keeps quant-engine parity exact)."""
        k, v = self._kv(1)
        h = export_handoff(self._req(), k, v, n=17, first_token=1,
                           quantize="int8")
        h2 = PrefillHandoff.from_bytes(h.to_bytes())
        assert h2.kv_format == "int8"
        assert h2.k.dtype == np.int8 and h2.k_scale.dtype == np.float32
        kd, vd = h2.kv_arrays()
        np.testing.assert_allclose(kd, k[:, 0, :17], atol=0.02)
        np.testing.assert_allclose(vd, v[:, 0, :17], atol=0.02)
        h3 = export_handoff(self._req(), kd[:, None], vd[:, None], n=17,
                            first_token=1, quantize="int8")
        np.testing.assert_array_equal(h3.k, h2.k)
        np.testing.assert_array_equal(h3.k_scale, h2.k_scale)
        # And the int8 lane is actually smaller on the wire.
        raw = export_handoff(self._req(), k, v, n=17, first_token=1)
        assert len(h.to_bytes()) < len(raw.to_bytes()) * 0.6

    def test_sampling_params_survive_json(self):
        req = make_req(max_new=4)
        req.sampling = SamplingParams(temperature=0.7, top_k=5, top_p=0.9,
                                      seed=123, presence_penalty=0.5,
                                      logit_bias={7: -2.0, 9: 1.5})
        k, v = self._kv(2)
        h = PrefillHandoff.from_bytes(
            export_handoff(req, k, v, n=17, first_token=3).to_bytes())
        sp = make_request(h).sampling
        assert sp.temperature == pytest.approx(0.7)
        assert sp.seed == 123
        assert sp.logit_bias == {7: -2.0, 9: 1.5}  # int keys restored

    def test_malformed_payloads_rejected(self):
        import json as json_mod
        import struct

        with pytest.raises(ValueError, match="magic"):
            PrefillHandoff.from_bytes(b"not a handoff at all")
        k, v = self._kv(3)
        wire = export_handoff(self._req(), k, v, n=17,
                              first_token=1).to_bytes()
        with pytest.raises(ValueError, match="truncated"):
            PrefillHandoff.from_bytes(wire[: len(wire) // 2])
        # Tampered header with a negative dim: must fail at the parse
        # boundary, not walk the payload cursor backwards.
        magic_len = 8
        (head_len,) = struct.unpack_from("<I", wire, magic_len)
        head = json_mod.loads(wire[magic_len + 4:magic_len + 4 + head_len])
        head["arrays"][0]["shape"][0] = -1
        new_head = json_mod.dumps(head).encode()
        tampered = (wire[:magic_len] + struct.pack("<I", len(new_head))
                    + new_head + wire[magic_len + 4 + head_len:])
        with pytest.raises(ValueError, match="negative dimension"):
            PrefillHandoff.from_bytes(tampered)
        # Non-whitelisted dtype strings must not reach np.dtype().
        head["arrays"][0]["shape"][0] = 2
        head["arrays"][0]["dtype"] = "object"
        new_head = json_mod.dumps(head).encode()
        tampered = (wire[:magic_len] + struct.pack("<I", len(new_head))
                    + new_head + wire[magic_len + 4 + head_len:])
        with pytest.raises(ValueError, match="unsupported handoff dtype"):
            PrefillHandoff.from_bytes(tampered)


class TestTwoEngineParity:
    """The acceptance bar: disaggregated == collocated, token for token."""

    @pytest.mark.parametrize("kv_quant", [None, "int8"],
                             ids=["bf16-cache", "int8-cache"])
    @pytest.mark.parametrize("adapter", [None, "handoff-adapter"],
                             ids=["base", "lora"])
    def test_disagg_matches_collocated(self, kv_quant, adapter):
        coll = make_engine(lora=adapter is not None, kv_cache_quant=kv_quant,
                           paged_kv_block=8, prefix_cache=True)
        pre = make_engine(lora=adapter is not None, kv_cache_quant=kv_quant,
                          role="prefill")
        dec = make_engine(lora=adapter is not None, kv_cache_quant=kv_quant,
                          role="decode", paged_kv_block=8, prefix_cache=True)
        try:
            want = coll.generate(make_req(adapter=adapter),
                                 timeout_s=180).output_tokens
            handoff = pre.prefill_only(make_req(adapter=adapter),
                                       timeout_s=180)
            # Quant engines default to the int8 wire lane.
            assert handoff.kv_format == ("int8" if kv_quant else "raw")
            wire = handoff.to_bytes()
            req = dec.attach_prefilled(PrefillHandoff.from_bytes(wire))
            assert req.done.wait(180)
            assert req.error is None
            assert req.finish_reason == "length"
            assert req.output_tokens == want
            assert req.ttft_s > 0  # TTFT stamped on the decode engine
        finally:
            coll.stop(), pre.stop(), dec.stop()

    def test_lane_cache_decode_engine(self):
        """attach composes with the contiguous-lane cache too (no paging)."""
        coll = make_engine()
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode")
        try:
            want = coll.generate(make_req(), timeout_s=180).output_tokens
            h = pre.prefill_only(make_req(), timeout_s=180)
            req = dec.attach_prefilled(
                PrefillHandoff.from_bytes(h.to_bytes()))
            assert req.done.wait(180) and req.error is None
            assert req.output_tokens == want
        finally:
            coll.stop(), pre.stop(), dec.stop()

    def test_fused_decode_engine_parity(self):
        """The decode hop fuses four steps a dispatch from the attached
        first token; the collocated engine steps one at a time."""
        coll = make_engine()
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode", decode_steps_per_sync=4)
        try:
            want = coll.generate(make_req(), timeout_s=180).output_tokens
            h = pre.prefill_only(make_req(), timeout_s=180)
            req = dec.attach_prefilled(
                PrefillHandoff.from_bytes(h.to_bytes()))
            assert req.done.wait(180) and req.error is None
            assert req.output_tokens == want
        finally:
            coll.stop(), pre.stop(), dec.stop()


class TestAttachSemantics:
    def test_idempotent_attach_and_prefix_composition(self):
        """Attaching the same handoff twice is safe (content-identical
        rewrite + registration skip), the imported blocks land in the
        prefix-cache chain, and a LOCAL same-prefix request reuses them."""
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode", paged_kv_block=8, prefix_cache=True)
        try:
            wire = pre.prefill_only(make_req(), timeout_s=180).to_bytes()
            r1 = dec.attach_prefilled(PrefillHandoff.from_bytes(wire))
            assert r1.done.wait(180) and r1.error is None
            assert len(dec._prefix_table) == 2  # 2 full blocks registered
            r2 = dec.attach_prefilled(PrefillHandoff.from_bytes(wire))
            assert r2.done.wait(180) and r2.error is None
            assert r2.output_tokens == r1.output_tokens
            assert len(dec._prefix_table) == 2  # no duplicate registration
            # Local traffic sharing the prefix prefills only the suffix.
            loc = dec.generate(make_req(), timeout_s=180)
            assert loc.output_tokens == r1.output_tokens
            assert dec.prefix_reused_tokens >= 16
            # Nothing leaked: all rows freed, cached blocks evictable.
            snap = dec.metrics_snapshot()
            assert snap["num_requests_running"] == 0
            assert snap["kv_parked_tokens"] == 0
        finally:
            pre.stop(), dec.stop()

    def test_first_token_only_request_never_takes_a_slot(self):
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode")
        try:
            h = pre.prefill_only(make_req(max_new=1), timeout_s=180)
            req = dec.attach_prefilled(PrefillHandoff.from_bytes(
                h.to_bytes()))
            assert req.done.wait(180)
            assert req.output_tokens == [h.first_token]
            assert req.finish_reason == "length"
        finally:
            pre.stop(), dec.stop()

    def test_prefill_only_rejects_beyond_bucket(self):
        pre = make_engine(role="prefill")
        try:
            with pytest.raises(ValueError, match="largest bucket"):
                pre.prefill_only(make_req(prompt=tuple(range(40))))
        finally:
            pre.stop()

    def test_prefill_only_needs_no_free_slot(self):
        """A prefill-role engine keeps serving handoffs while every decode
        slot is busy — the whole point of the disaggregation."""
        pre = make_engine(role="prefill", decode_slots=1)
        try:
            blocker = make_req(prompt=(1, 2, 3), max_new=40)
            pre.submit(blocker)  # occupies the only slot
            h = pre.prefill_only(make_req(max_new=4), timeout_s=180)
            assert h is not None and h.n == len(PROMPT)
            blocker.cancelled.set()
            assert blocker.done.wait(60)
        finally:
            pre.stop()

    def test_attach_validations(self):
        dec = make_engine(role="decode", start=False)
        dec.start()
        try:
            h = export_handoff(
                make_req(prompt=tuple(range(70)), max_new=2),
                np.zeros((CFG.n_layers, 1, 72, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                np.zeros((CFG.n_layers, 1, 72, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                n=70, first_token=1)
            with pytest.raises(ValueError, match="max_seq_len"):
                dec.attach_prefilled(h)  # 70 >= max_seq_len 64
        finally:
            dec.stop()

    def test_attach_validates_sampling_carry(self):
        """The handoff's sampling carry crosses a trust boundary: an
        out-of-vocab logit_bias id must be refused at attach, exactly as
        submit() refuses it (clipping would mis-bias a real token)."""
        dec = make_engine(role="decode")
        try:
            req = make_req(max_new=4)
            req.sampling = SamplingParams(
                logit_bias={CFG.vocab_size + 7: 1.0})
            bad = export_handoff(
                req,
                np.zeros((CFG.n_layers, 1, 32, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                np.zeros((CFG.n_layers, 1, 32, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                n=17, first_token=1)
            with pytest.raises(ValueError, match="outside the vocabulary"):
                dec.attach_prefilled(bad)
        finally:
            dec.stop()

    def test_attach_unknown_adapter_fails_fast(self):
        dec = make_engine(role="decode", lora=True)
        try:
            bad = export_handoff(
                make_req(adapter="no-such-adapter"),
                np.zeros((CFG.n_layers, 1, 32, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                np.zeros((CFG.n_layers, 1, 32, CFG.n_kv_heads,
                          CFG.resolved_head_dim), np.float32),
                n=17, first_token=1)
            with pytest.raises(Exception, match="no-such-adapter"):
                dec.attach_prefilled(bad)
        finally:
            dec.stop()

    def test_draining_decode_engine_refuses_attach(self):
        from llm_instance_gateway_tpu.server.engine import EngineDraining

        pre = make_engine(role="prefill")
        dec = make_engine(role="decode")
        try:
            h = pre.prefill_only(make_req(), timeout_s=180)
            dec.drain(timeout_s=0.1)
            with pytest.raises(EngineDraining):
                dec.attach_prefilled(h)
        finally:
            pre.stop(), dec.stop()


class TestAbandonedHandoffRelease:
    """Regression (robustness PR): a decode-hop failure after a successful
    prefill hop abandons imported KV on the decode replica — the gateway's
    best-effort ``release_request`` (and the engine's ``handoff_ttl_s``
    sweep as the backstop) must free it instead of decoding tokens nobody
    will read."""

    # Context of the decode engines whose slots blockers have to hold.
    DEC_SEQ = 2048

    def _parked_attach(self, dec, pre):
        """Fill every decode slot, then attach a handoff so it PARKS in
        decode_wait (the abandoned-work position).  Returns (attached
        request, blockers)."""
        # Long enough to outlast the prefill engine's compile and a TTL
        # sweep however fast a tiny decode step is (``DEC_SEQ``).
        blockers = [make_req(prompt=(1, 2, 3 + i), max_new=self.DEC_SEQ)
                    for i in range(2)]
        for b in blockers:
            dec.submit(b)
        wire = pre.prefill_only(make_req(max_new=8), timeout_s=180).to_bytes()
        req = dec.attach_prefilled(PrefillHandoff.from_bytes(wire))
        deadline = 60.0
        import time as time_mod

        t0 = time_mod.monotonic()
        while dec.metrics_snapshot()["kv_parked_tokens"] == 0:
            assert time_mod.monotonic() - t0 < deadline, "never parked"
            time_mod.sleep(0.02)
        return req, blockers

    def _finish_blockers(self, dec, blockers):
        for b in blockers:
            b.cancelled.set()
        for b in blockers:
            assert b.done.wait(60)

    def test_release_request_frees_parked_attach(self):
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode", max_seq_len=self.DEC_SEQ)
        try:
            req, blockers = self._parked_attach(dec, pre)
            assert dec.release_request(req.request_id) is True
            assert req.done.wait(60)
            assert req.finish_reason == "cancelled"
            import time as time_mod

            t0 = time_mod.monotonic()
            while dec.metrics_snapshot()["kv_parked_tokens"] != 0:
                assert time_mod.monotonic() - t0 < 60
                time_mod.sleep(0.02)
            # Idempotent: the request is no longer live.
            assert dec.release_request(req.request_id) is False
            # Unknown ids are a clean no-op.
            assert dec.release_request("no-such-id") is False
            self._finish_blockers(dec, blockers)
        finally:
            pre.stop(), dec.stop()

    def test_handoff_ttl_sweep_is_the_backstop(self):
        """With the release message lost, the TTL sweep frees a parked
        import on its own; a NON-handoff parked prefill is never TTL-swept
        (its caller is still waiting on done)."""
        pre = make_engine(role="prefill")
        dec = make_engine(role="decode", handoff_ttl_s=0.3,
                          max_seq_len=self.DEC_SEQ)
        try:
            req, blockers = self._parked_attach(dec, pre)
            assert req.done.wait(60)  # swept without any release call
            assert req.finish_reason == "cancelled"
            assert dec.metrics_snapshot()["kv_parked_tokens"] == 0
            self._finish_blockers(dec, blockers)
        finally:
            pre.stop(), dec.stop()

    def test_release_endpoint_over_http(self):
        """The ``POST /v1/prefill/release`` surface end-to-end against a
        real engine: parked attach -> released true; repeat -> false."""
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_instance_gateway_tpu.server.api_http import ModelServer

        pre = make_engine(role="prefill")
        dec = make_engine(role="decode", max_seq_len=self.DEC_SEQ)
        try:
            req, blockers = self._parked_attach(dec, pre)
            server = ModelServer(dec, tokenizer=None, model_name="m")

            async def run():
                client = TestClient(TestServer(server.build_app()))
                await client.start_server()
                try:
                    r1 = await client.post(
                        "/v1/prefill/release",
                        json={"request_id": req.request_id})
                    assert r1.status == 200
                    assert (await r1.json())["released"] is True
                    assert req.done.wait(60)
                    r2 = await client.post(
                        "/v1/prefill/release",
                        json={"request_id": req.request_id})
                    assert (await r2.json())["released"] is False
                    r3 = await client.post("/v1/prefill/release",
                                           json={"nope": 1})
                    assert r3.status == 400
                finally:
                    await client.close()

            asyncio.run(run())
            self._finish_blockers(dec, blockers)
        finally:
            pre.stop(), dec.stop()
