"""The request path outside the engine loop, measured where the work happens
(PR 37): over a tiny CPU engine behind a real ``api_http`` and a real
``proxy``, one streamed request's first-token time is tiled by spans and
attributes, every written chunk books its emit-to-write lag, and each
asyncio process runs a stall clock."""

import asyncio
import json
import random

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_instance_gateway_tpu import metrics_registry, tracing
from llm_instance_gateway_tpu.api.v1alpha1 import InferencePool
from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.handlers.server import Server
from llm_instance_gateway_tpu.gateway.provider import StaticProvider
from llm_instance_gateway_tpu.gateway.proxy import GatewayProxy
from llm_instance_gateway_tpu.gateway.scheduling.scheduler import Scheduler
from llm_instance_gateway_tpu.gateway.testing import fake_metrics, make_model
from llm_instance_gateway_tpu.gateway.types import Pod, PodMetrics
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server import engine as engine_mod
from llm_instance_gateway_tpu.server.api_http import ModelServer
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from llm_instance_gateway_tpu.server.tokenizer import ByteTokenizer
from tools import trace_report

# Every sampled token is one of four printable bytes, so each decodes to a
# character and becomes its own SSE chunk (the benchmark's device).
BIAS = {str(b): 100.0 for b in b"abcd"}
PREFILL_PHASES = ("prefill.stage", "prefill.wait", "prefill.emit")
SERVER_ORDER = ["server.accept", "engine.queue_wait", "engine.prefill",
                "server.first_write"]


@pytest.fixture(scope="module")
def model_server():
    params = transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    engine = Engine(
        TINY_TEST, params,
        EngineConfig(decode_slots=2, max_seq_len=64,
                     prefill_buckets=(8, 16, 32)),
        eos_id=None, dtype=jnp.float32)
    engine.start()
    yield ModelServer(engine, ByteTokenizer(), "llama3-tiny")
    engine.stop()


def build_proxy(address: str) -> GatewayProxy:
    pod = Pod("pod-a", address)
    ds = Datastore(pods=[pod])
    ds.set_pool(InferencePool(name="pool"))
    ds.store_model(make_model("llama3-tiny"))
    provider = StaticProvider([PodMetrics(pod=pod, metrics=fake_metrics())])
    scheduler = Scheduler(provider, token_aware=False, prefill_aware=False,
                          prefix_aware=False, rng=random.Random(7))
    return GatewayProxy(Server(scheduler, ds), provider, ds)


async def through_both(model_server, trace_ids, max_tokens=6):
    """Streamed requests through proxy -> api_http -> engine, one after
    another; returns (gateway doc, server doc, gateway /metrics, server
    /metrics) read after the last."""
    upstream = TestServer(model_server.build_app())
    await upstream.start_server()
    gw = TestClient(TestServer(
        build_proxy(f"127.0.0.1:{upstream.port}").build_app()))
    await gw.start_server()
    direct = TestClient(upstream)
    try:
        for tid in trace_ids:
            resp = await gw.post(
                "/v1/completions",
                headers={tracing.TRACE_HEADER: tid},
                json={"model": "llama3-tiny", "prompt": "hello there",
                      "max_tokens": max_tokens, "stream": True,
                      "logit_bias": BIAS})
            assert resp.status == 200
            raw = await resp.read()
            assert raw.rstrip().endswith(b"data: [DONE]")
        await asyncio.sleep(0.12)  # two ticks of the stall clocks
        last = trace_ids[-1]
        docs = []
        for client in (gw, direct):
            r = await client.get(f"/debug/traces?trace_id={last}")
            docs.append(await r.json())
        pages = [await (await client.get("/metrics")).text()
                 for client in (gw, direct)]
        profile = await (await direct.get("/debug/profile")).json()
        return docs[0], docs[1], pages[0], pages[1], profile
    finally:
        await gw.close()
        await upstream.close()


@pytest.fixture(scope="module")
def one_request(model_server):
    """The second of two requests: the first compiles the programs."""
    return asyncio.run(through_both(model_server, ["warm0000", "tile0001"]))


def spans_of(doc):
    (trace,) = doc["traces"]
    return {s["name"]: s for s in trace["spans"]}


def test_server_spans_tile_first_token_time(one_request):
    _, srv_doc, *_ = one_request
    spans = spans_of(srv_doc)
    parts = [spans[name] for name in SERVER_ORDER]
    for before, after in zip(parts, parts[1:]):
        # contiguous and ordered: each begins where the one before ends
        assert after["start"] == pytest.approx(before["end"], abs=0.002)
        assert after["start"] >= before["start"]
    whole = parts[-1]["end"] - parts[0]["start"]
    assert sum(p["end"] - p["start"] for p in parts) == pytest.approx(
        whole, abs=0.002)
    assert whole > 0


def test_gateway_stream_record_carries_the_hops_parts(one_request):
    gw_doc, srv_doc, *_ = one_request
    attrs = spans_of(gw_doc)["gateway.stream"]["attrs"]
    assert attrs["pre_s"] > 0 and attrs["first_chunk_s"] > 0
    assert attrs["pre_s"] + attrs["first_chunk_s"] <= attrs["ttft_s"]
    # six tokens, six data chunks and the final one (writes may coalesce)
    assert 1 <= attrs["chunks"] <= 8
    assert 0 < attrs["relay_mean_s"] <= attrs["relay_max_s"]
    assert attrs["stall_s"] == 0.0 and attrs["loop_lag_s"] >= 0.0
    # the server's four parts fit inside what the gateway waited for
    server = spans_of(srv_doc)
    inside = sum(server[n]["end"] - server[n]["start"] for n in SERVER_ORDER)
    assert inside <= attrs["first_chunk_s"] + 0.002


def test_engine_spans_carry_the_admissions_facts(one_request):
    _, srv_doc, *_ = one_request
    spans = spans_of(srv_doc)
    prefill = spans["engine.prefill"]["attrs"]
    assert prefill["prompt_tokens"] == len("hello there") + 1  # BOS
    assert prefill["bucket"] == 16 and prefill["rows"] == 0
    parts = [prefill[k] for k in ("stage_s", "wait_s", "emit_s")]
    assert all(p >= 0 for p in parts) and sum(parts) > 0
    decode = spans["engine.decode"]["attrs"]
    assert decode["chunks"] >= 6 and decode["write_lag_max_s"] > 0


def test_both_processes_answer_one_clock_pair(one_request):
    gw_doc, srv_doc, _, _, profile = one_request
    for doc in (gw_doc, srv_doc, profile):
        assert set(doc["clock"]) == {"time", "perf_counter"}
        assert doc["clock"]["time"] > 1e9


def test_counters_are_rendered_and_registered(one_request):
    *_, gw_page, srv_page, _ = one_request
    registered = metrics_registry.registered_names()

    def value(page, family):
        assert family in registered
        (line,) = [ln for ln in page.splitlines()
                   if ln.startswith(family + " ")]
        return float(line.split()[1])

    assert value(srv_page, "tpu:stream_chunks_total") >= 12  # two requests
    assert value(srv_page, "tpu:stream_write_lag_seconds_total") > 0
    for prefix in ("tpu:", "gateway_"):
        page = srv_page if prefix == "tpu:" else gw_page
        assert value(page, prefix + "loop_ticks_total") >= 2
        assert value(page, prefix + "loop_lag_seconds_total") >= 0
        assert value(page, prefix + "loop_stall_seconds_total") == 0


def test_trace_report_prints_the_tiling(one_request, tmp_path, capsys):
    gw_doc, srv_doc, *_ = one_request
    paths = []
    for name, doc in (("gw.json", gw_doc), ("srv.json", srv_doc)):
        path = tmp_path / name
        path.write_text(json.dumps(doc))
        paths += ["--url", str(path)]
    assert trace_report.main(paths) == 0
    out = capsys.readouterr().out
    rest = out.split("first-token time by part")[1]
    table, cost = rest.split("\n\nwhat a prompt cost the device:\n")
    table = table.splitlines()[3:]
    # PR 57: under the table, what the prompt cost in programs and positions
    assert [ln.split("  ")[0] for ln in cost.splitlines()[2:]] == [
        label for label, _ in trace_report.PROMPT_COST]
    rows = [next(p for p in trace_report.PARTS if ln.startswith(p))
            for ln in table]
    assert rows == [p for p in trace_report.PARTS  # all, in order
                    if not p.startswith("POST -> first chunk")]
    merged = trace_report.multi_replica_traces(
        [("gw", gw_doc), ("srv", srv_doc)])["traces"]
    (parts,) = [trace_report.first_token_parts(t) for t in merged]
    tiled = sum(v for k, v in parts.items()
                if not k.startswith(("=", " ", "per token")))
    assert tiled == pytest.approx(parts["= ttft_s of gateway.stream"],
                                  abs=1e-5)
    # one host, one clock: the residual splits into the way in and out
    assert (parts["  way in: POST -> handler entry"]
            + parts["  way out: first write -> gateway has it"]) == (
        pytest.approx(parts["network and HTTP (first_chunk_s - the four)"],
                      abs=0.002))
    merged[0]["skew"] = {"gateway.stream": 0.5}  # shifted clocks: no split
    assert not any(k.startswith("  way") for k in
                   trace_report.first_token_parts(merged[0]))
    # the gateway's document alone: no residual, the hop's own numbers stay
    alone = trace_report.first_token_parts(gw_doc["traces"][0])
    assert "POST -> first chunk (first_chunk_s)" in alone
    assert "server.accept" not in alone


def test_prefill_parts_equal_the_phase_counters_growth(model_server,
                                                       one_request):
    """One admission on a warm, idle engine: what its record says the phase
    stack charged to prefill.* is what the counters grew by."""
    engine = model_server.engine
    before = engine.profiler.phase_seconds()
    req = engine.generate(Request(prompt_tokens=[1, 2, 3, 4, 5],
                                  max_new_tokens=4), timeout_s=60)
    assert req.error is None and len(req.output_tokens) == 4
    after = engine.profiler.phase_seconds()
    growth = sum(after[p] - before[p] for p in PREFILL_PHASES)
    attrs = req.prefill_attrs
    assert attrs["prompt_tokens"] == 5 and attrs["bucket"] == 8
    assert attrs["stage_s"] + attrs["wait_s"] + attrs["emit_s"] == (
        pytest.approx(growth, abs=1e-6))
    assert growth > 0


def test_publish_keeps_the_oldest_stamp_until_the_consumer_takes_it(
        monkeypatch):
    now = iter([10.0, 11.0, 12.0])
    monkeypatch.setattr(engine_mod.time, "time", lambda: next(now))
    req = Request(prompt_tokens=[1])
    engine_mod._publish(req)
    engine_mod._publish(req)  # a second token before the consumer woke
    assert req.t_emit == 10.0 and req.stream_event.is_set()
    req.t_emit = 0.0  # the consumer took it
    engine_mod._publish(req)
    assert req.t_emit == 11.0


def test_prefill_enqueue_annotation_names_its_requests():
    """The trace-only span round the prefill program's call carries what
    matches an idle gap of a device trace to its engine.prefill span."""
    seen = []

    class Note:
        def __init__(self, name, **metadata):
            seen.append((name, metadata))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    from llm_instance_gateway_tpu.server.profiler import StepProfiler

    a, b = Request(prompt_tokens=[1] * 9), Request(prompt_tokens=[1] * 5)
    a.prefill_attrs.update(prompt_tokens=9, bucket=16, rows=3)
    stub = type("E", (), {"profiler": StepProfiler(annotate=Note)})()
    with Engine._enqueue(stub, "engine.prefill.enqueue", a, b):
        pass
    with Engine._enqueue(stub, "engine.decode.enqueue"):
        pass
    assert seen == [
        ("engine.prefill.enqueue",
         {"request_id": f"{a.request_id}+{b.request_id}",
          "prompt_tokens": 9, "bucket": 16}),
        ("engine.decode.enqueue", {})]


class FakeTime:
    """A clock and a sleep for ``LoopClock``: each sleep takes what the
    script says, and the script's end cancels the task."""

    def __init__(self, sleeps):
        self.now = 50.0
        self.sleeps = list(sleeps)

    def clock(self):
        return self.now

    async def sleep(self, period):
        if not self.sleeps:
            raise asyncio.CancelledError
        self.now += self.sleeps.pop(0)


def run_clock(sleeps) -> tracing.LoopClock:
    fake = FakeTime(sleeps)
    clock = tracing.LoopClock(clock=fake.clock, sleep=fake.sleep)
    with pytest.raises(asyncio.CancelledError):
        asyncio.run(clock.run())
    return clock


def test_stall_clock_books_a_pause_as_stall_and_a_late_tick_as_lag():
    paused = run_clock([0.05, 1.05])  # on time, then a 1 s overshoot
    assert paused.ticks == 2
    assert paused.lag_s == pytest.approx(1.0)
    assert paused.stall_s == pytest.approx(1.0)
    late = run_clock([0.06, 0.05, 0.04])  # 60 ms: 10 ms of lag, no stall
    assert late.ticks == 3
    assert late.lag_s == pytest.approx(0.01)
    assert late.stall_s == 0.0
    # on either side of the stall's threshold of 250 ms
    edge = run_clock([0.29, 0.31])
    assert edge.stall_s == pytest.approx(0.26)
    assert edge.lag_s == pytest.approx(0.5)
    assert edge.marks() == (edge.lag_s, 2, edge.stall_s)
    assert edge.render("a_total", "b_total", "c_total") == [
        "# TYPE a_total counter", "a_total 0.500000",
        "# TYPE b_total counter", "b_total 2",
        "# TYPE c_total counter", "c_total 0.260000"]
