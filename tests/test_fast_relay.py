"""Zero-copy relay byte-parity: the fast SSE path vs the slow oracle.

The fast relay (proxy.py ``fast_relay=True``, the default) writes upstream
chunks to the client verbatim — no per-chunk decode/split/re-encode — and
parses the final usage chunk + ``[DONE]`` exclusion ONCE at stream end from
raw tail bytes.  The pre-existing line-scanning relay is kept as the parity
oracle (``fast_relay=False``).  These tests pin chunk-for-chunk equality of
everything the client and the metrics plane can observe: status, headers,
trace-id echo, the relayed byte stream, error terminations, usage
accounting, and the PR-4 retry interaction.
"""

import asyncio
import json
import random

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from llm_instance_gateway_tpu.api.v1alpha1 import InferencePool
from llm_instance_gateway_tpu.gateway import resilience
from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.handlers.server import Server
from llm_instance_gateway_tpu.gateway.provider import StaticProvider
from llm_instance_gateway_tpu.gateway.proxy import (
    RELAY_TAIL_BYTES,
    GatewayProxy,
    final_data_line,
)
from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
from llm_instance_gateway_tpu.gateway.testing import fake_metrics, make_model
from llm_instance_gateway_tpu.gateway.types import Pod, PodMetrics
from llm_instance_gateway_tpu.tracing import TRACE_HEADER

USAGE_LINE = (b'data: {"choices": [{"index": 0, "text": "."}], '
              b'"usage": {"prompt_tokens": 7, "completion_tokens": 3, '
              b'"total_tokens": 10}}')


# ---------------------------------------------------------------------------
# final_data_line: the raw-bytes end-of-stream parse
# ---------------------------------------------------------------------------


class TestFinalDataLine:
    def test_picks_last_data_line(self):
        tail = b"data: {\"a\": 1}\n\ndata: {\"b\": 2}\n\ndata: [DONE]\n\n"
        assert final_data_line(tail) == b'data: {"b": 2}'

    def test_skips_done_terminator(self):
        assert final_data_line(b"data: [DONE]\n\n") == b""

    def test_ignores_unterminated_trailing_line(self):
        # Only \n-terminated lines count — same contract as the slow
        # path's incremental scan (a partial line never parses).
        tail = b'data: {"a": 1}\n\ndata: {"partial": '
        assert final_data_line(tail) == b'data: {"a": 1}'

    def test_empty(self):
        assert final_data_line(b"") == b""
        assert final_data_line(b"\n\n") == b""


# ---------------------------------------------------------------------------
# Scripted upstream + A/B proxy harness
# ---------------------------------------------------------------------------


async def start_scripted_upstream(chunks, abort_after: int | None = None,
                                  fail_first: int = 0):
    """An upstream that writes ``chunks`` one write at a time (yielding
    between writes so the relay sees them as separate reads), optionally
    ABORTING the transport after ``abort_after`` writes (mid-stream
    upstream death, no [DONE]) or 503-ing the first ``fail_first``
    requests (the pre-first-byte failure the retry loop may re-attempt)."""
    failures = {"left": fail_first}

    async def completions(request: web.Request) -> web.StreamResponse:
        if failures["left"] > 0:
            failures["left"] -= 1
            return web.Response(status=503, text="draining")
        resp = web.StreamResponse(
            status=200, headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        for i, chunk in enumerate(chunks):
            if abort_after is not None and i >= abort_after:
                request.transport.close()  # abrupt upstream death
                return resp
            await resp.write(chunk)
            await asyncio.sleep(0.01)
        return resp

    app = web.Application()
    app.router.add_post("/v1/completions", completions)
    server = TestServer(app)
    await server.start_server()
    return server


def build_proxy(pods: dict, fast_relay: bool,
                rcfg: resilience.ResilienceConfig | None = None,
                seed: int = 7) -> GatewayProxy:
    ds = Datastore(pods=list(pods))
    ds.set_pool(InferencePool(name="pool"))
    ds.store_model(make_model("m"))
    provider = StaticProvider(
        [PodMetrics(pod=p, metrics=m) for p, m in pods.items()])
    scheduler = Scheduler(provider, token_aware=False, prefill_aware=False,
                          prefix_aware=False, rng=random.Random(seed))
    return GatewayProxy(Server(scheduler, ds), provider, ds,
                        resilience_cfg=rcfg, fast_relay=fast_relay)


async def stream_once(proxy, body=None):
    """One streaming request; returns (status, headers, raw body bytes)."""
    client = TestClient(TestServer(proxy.build_app()))
    await client.start_server()
    try:
        resp = await client.post(
            "/v1/completions",
            json=body or {"model": "m", "prompt": "x", "stream": True})
        raw = await resp.read()
        return resp.status, dict(resp.headers), raw
    finally:
        await client.close()


async def ab_streams(chunks, rcfg=None, pods_for=None, abort_after=None):
    """Run the SAME scripted stream through a fast-relay proxy and a
    slow-relay proxy; returns the two (status, headers, raw) triples."""
    out = []
    for fast in (True, False):
        up = await start_scripted_upstream(chunks, abort_after=abort_after)
        pods = (pods_for(up) if pods_for
                else {Pod("p", f"127.0.0.1:{up.port}"): fake_metrics()})
        proxy = build_proxy(pods, fast_relay=fast, rcfg=rcfg)
        try:
            out.append((await stream_once(proxy), proxy))
        finally:
            await up.close()
    return out


def assert_relay_parity(fast_result, slow_result):
    (f_status, f_headers, f_raw), _ = fast_result
    (s_status, s_headers, s_raw), _ = slow_result
    assert f_status == s_status
    assert f_raw == s_raw  # chunk-for-chunk: the byte stream is identical
    for key in ("Content-Type", "Cache-Control", "x-served-by"):
        assert f_headers.get(key) == s_headers.get(key)
    assert TRACE_HEADER in f_headers and TRACE_HEADER in s_headers


# ---------------------------------------------------------------------------
# Byte parity
# ---------------------------------------------------------------------------


class TestRelayByteParity:
    def test_stream_with_usage_and_done(self):
        chunks = [
            b'data: {"choices": [{"index": 0, "text": "a"}]}\n\n',
            b'data: {"choices": [{"index": 0, "text": "b"}]}\n\n',
            USAGE_LINE + b"\n\n",
            b"data: [DONE]\n\n",
        ]

        async def run():
            fast, slow = await ab_streams(chunks)
            assert_relay_parity(fast, slow)
            (_, _, raw), _ = fast
            assert raw == b"".join(chunks)
            # BOTH modes parsed the final usage chunk (fast: raw tail at
            # stream end; slow: incremental line scan) — [DONE] excluded.
            for _, proxy in (fast, slow):
                text = proxy.metrics.render()
                assert 'gateway_prompt_tokens_total{model="m"} 7' in text
                assert ('gateway_completion_tokens_total{model="m"} 3'
                        in text)

        asyncio.run(run())

    def test_usage_line_split_across_chunks(self):
        # The final usage data line arrives SPLIT across transport chunks:
        # the slow path re-frames through its buffer, the fast path joins
        # the tail references — identical accounting either way.
        head, tail = USAGE_LINE[:30], USAGE_LINE[30:]
        chunks = [
            b'data: {"choices": [{"index": 0, "text": "a"}]}\n\n',
            head, tail + b"\n\n",
            b"data: [DONE]\n\n",
        ]

        async def run():
            fast, slow = await ab_streams(chunks)
            assert_relay_parity(fast, slow)
            for _, proxy in (fast, slow):
                assert ('gateway_prompt_tokens_total{model="m"} 7'
                        in proxy.metrics.render())

        asyncio.run(run())

    def test_long_stream_tail_trim_still_parses_usage(self):
        # Enough pre-usage volume to overflow the fast relay's bounded
        # tail several times over: trimming whole chunks off the front
        # must never lose the final usage line.
        filler = b'data: {"choices": [{"index": 0, "text": "' + \
            b"x" * 512 + b'"}]}\n\n'
        n_filler = (RELAY_TAIL_BYTES // len(filler)) * 3
        chunks = [filler] * 8 + [USAGE_LINE + b"\n\n", b"data: [DONE]\n\n"]

        async def run():
            # Volume via repeated writes of the filler chunk (8 scripted
            # writes is plenty to exercise trimming given coalescing, and
            # n_filler repeats would make the test slow); then verify the
            # trim math directly on a synthetic tail.
            fast, slow = await ab_streams(chunks)
            assert_relay_parity(fast, slow)
            for _, proxy in (fast, slow):
                assert ('gateway_prompt_tokens_total{model="m"} 7'
                        in proxy.metrics.render())

        asyncio.run(run())
        # Direct trim-math check at full overflow volume (no sockets).
        joined = b"".join([filler] * n_filler + [USAGE_LINE + b"\n\n",
                          b"data: [DONE]\n\n"])
        assert final_data_line(joined[-RELAY_TAIL_BYTES:]) == USAGE_LINE

    def test_no_usage_stream_records_nothing(self):
        chunks = [
            b'data: {"choices": [{"index": 0, "text": "a"}]}\n\n',
            b"data: [DONE]\n\n",
        ]

        async def run():
            fast, slow = await ab_streams(chunks)
            assert_relay_parity(fast, slow)
            for _, proxy in (fast, slow):
                # The last non-DONE line has no usage object: zero tokens
                # accounted (the family exists, the count stays 0).
                assert ('gateway_prompt_tokens_total{model="m"} 0'
                        in proxy.metrics.render())

        asyncio.run(run())

    def test_midstream_upstream_death_terminates_identically(self):
        chunks = [
            b'data: {"choices": [{"index": 0, "text": "a"}]}\n\n',
            b'data: {"choices": [{"index": 0, "text": "b"}]}\n\n',
            b"never sent",
        ]

        async def run():
            rcfg = resilience.ResilienceConfig(
                ttft_timeout_s=2.0, stream_idle_timeout_s=0.5)
            fast, slow = await ab_streams(chunks, rcfg=rcfg, abort_after=2)
            assert_relay_parity(fast, slow)
            (_, _, raw), proxy = fast
            # Both committed streams end in the error event + [DONE].
            assert raw.endswith(
                b'data: {"error": {"message": "upstream stream '
                b'interrupted"}}\n\ndata: [DONE]\n\n')
            assert proxy.metrics.errors_total  # counted as an error

        asyncio.run(run())


# ---------------------------------------------------------------------------
# Retry interaction (PR 4) + error bodies
# ---------------------------------------------------------------------------


class TestRelayResilienceParity:
    @pytest.mark.parametrize("fast", [True, False])
    def test_retry_repicks_then_streams(self, fast):
        """Pre-first-byte failure (503 on attempt one): the budgeted retry
        loop re-attempts and the stream then relays normally — on BOTH
        relay modes, with the retry counted and the relayed bytes intact."""
        chunks = [USAGE_LINE + b"\n\n", b"data: [DONE]\n\n"]

        async def run():
            up = await start_scripted_upstream(chunks, fail_first=1)
            pods = {Pod("live", f"127.0.0.1:{up.port}"): fake_metrics()}
            rcfg = resilience.ResilienceConfig(
                retry_budget_ratio=1.0, max_retries=3,
                connect_timeout_s=0.5, ttft_timeout_s=2.0)
            proxy = build_proxy(pods, fast_relay=fast, rcfg=rcfg)
            client = TestClient(TestServer(proxy.build_app()))
            await client.start_server()
            try:
                resp = await client.post(
                    "/v1/completions",
                    json={"model": "m", "prompt": "x", "stream": True})
                raw = await resp.read()
                assert resp.status == 200
                assert raw == b"".join(chunks)
                assert resp.headers["x-served-by"] == "live"
            finally:
                await client.close()
                await up.close()
            text = proxy.metrics.render()
            assert 'gateway_retries_total{reason="upstream_503"} 1' in text
            # The saved stream still accounted its final usage chunk.
            assert 'gateway_prompt_tokens_total{model="m"} 7' in text

        asyncio.run(run())

    @pytest.mark.parametrize("fast", [True, False])
    def test_error_body_carries_trace_id(self, fast):
        """Non-stream error path is relay-mode independent: a 502 error
        body still carries the trace id on both builds."""

        async def run():
            pods = {Pod("p", "127.0.0.1:1"): fake_metrics()}
            proxy = build_proxy(pods, fast_relay=fast)
            client = TestClient(TestServer(proxy.build_app()))
            await client.start_server()
            try:
                resp = await client.post(
                    "/v1/completions", json={"model": "m", "prompt": "x"})
                assert resp.status == 502
                body = json.loads(await resp.read())
                assert (body["error"]["trace_id"]
                        == resp.headers[TRACE_HEADER])
            finally:
                await client.close()

        asyncio.run(run())


# ---------------------------------------------------------------------------
# Keepalive pool: connection reuse stats
# ---------------------------------------------------------------------------


class TestConnectionReuse:
    def test_sequential_requests_reuse_the_pooled_connection(self):
        async def run():
            async def completions(request: web.Request) -> web.Response:
                body = await request.json()
                return web.json_response({
                    "id": "c", "model": body["model"],
                    "choices": [{"index": 0, "text": "hi",
                                 "finish_reason": "stop"}],
                    "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                              "total_tokens": 2},
                })

            app = web.Application()
            app.router.add_post("/v1/completions", completions)
            up = TestServer(app)
            await up.start_server()
            pods = {Pod("p", f"127.0.0.1:{up.port}"): fake_metrics()}
            proxy = build_proxy(pods, fast_relay=True)
            client = TestClient(TestServer(proxy.build_app()))
            await client.start_server()
            try:
                for _ in range(4):
                    resp = await client.post(
                        "/v1/completions",
                        json={"model": "m", "prompt": "x"})
                    assert resp.status == 200
                    await resp.read()
            finally:
                await client.close()
                await up.close()
            conns = proxy.metrics.upstream_connections_total
            created = conns.get(("p", "created"), 0)
            reused = conns.get(("p", "reused"), 0)
            assert created >= 1
            assert reused >= 1  # keepalive pool did its job
            assert proxy.metrics.connection_reuse_ratio() > 0.0
            text = proxy.metrics.render()
            assert ('gateway_upstream_connections_total{pod="p",'
                    'state="created"}') in text
            assert ('gateway_upstream_connections_total{pod="p",'
                    'state="reused"}') in text
            assert "gateway_upstream_connection_reuse_ratio" in text

        asyncio.run(run())
