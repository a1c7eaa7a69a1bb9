"""Local end-to-end suite: real processes, real sockets, full stack.

The reference's e2e suite validates deployability on a kind cluster
(``test/e2e/e2e_test.go:32-122``); without a cluster here, this is the
equivalent: model server + gateway + sidecar launched as SUBPROCESSES (the
same binaries the manifests run), driven over HTTP:

  client -> gateway (schedule on live scraped metrics, traffic split)
         -> model server (engine) -> tokens back, usage accounted,
  sidecar reconciles an adapter onto the live server -> affinity routing.

Marked ``e2e``: slower than unit tests but still CPU-hermetic.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVER_PORT = 18801
GATEWAY_PORT = 18810


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _wait_http(url: str, timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                if resp.status == 200:
                    return
        except OSError:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"{url} not up within {timeout_s}s")


def _post(url: str, payload: dict, timeout_s: float = 120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _launch_module(args, log_path, cwd=None):
    """Start `python -m <args>` with the repo env; returns (proc, log)."""
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m"] + args, env=_env(),
        stdout=log, stderr=subprocess.STDOUT, cwd=cwd,
    )
    return proc, log


def _teardown_procs(procs):
    for proc, log in procs:
        proc.send_signal(signal.SIGTERM)
    for proc, log in procs:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("e2e")
    pool = tmp / "pool.yaml"
    pool.write_text(f"""\
kind: InferencePool
metadata: {{name: e2e-pool, resourceVersion: "1"}}
spec: {{selector: {{app: e2e}}, targetPortNumber: {SERVER_PORT}}}
---
kind: InferenceModel
metadata: {{name: llama3-tiny}}
spec: {{modelName: llama3-tiny, criticality: Default, poolRef: {{name: e2e-pool}}}}
---
kind: InferenceModel
metadata: {{name: sql-assist}}
spec:
  modelName: sql-assist
  criticality: Critical
  poolRef: {{name: e2e-pool}}
  targetModels: [{{name: e2e-adapter, weight: 100}}]
""")
    procs = []

    def launch(args, log_name):
        entry = _launch_module(args, tmp / log_name, cwd=str(tmp))
        procs.append(entry)
        return entry[0]

    def teardown():
        _teardown_procs(procs)

    try:
        server = launch(
            ["llm_instance_gateway_tpu.server.api_http", "--model", "llama3-tiny",
             "--platform", "cpu", "--port", str(SERVER_PORT), "--decode-slots", "2",
             "--max-seq-len", "128", "--dtype", "float32"],
            "server.log",
        )
        _wait_http(f"http://127.0.0.1:{SERVER_PORT}/health")
        launch(
            ["llm_instance_gateway_tpu.gateway.proxy", "--config", str(pool),
             "--port", str(GATEWAY_PORT),
             "--pod", f"r1=127.0.0.1:{SERVER_PORT}",
             "--probe-endpoints", "--watch-config"],
            "gateway.log",
        )
        _wait_http(f"http://127.0.0.1:{GATEWAY_PORT}/healthz")
        # The provider needs one pod-refresh cycle before the scheduler sees r1.
        time.sleep(2.0)
    except Exception:
        teardown()  # startup failure must not orphan the launched processes
        raise
    yield {"tmp": tmp, "pool": pool, "server": server}
    teardown()


def test_routed_completion(stack):
    status, body = _post(
        f"http://127.0.0.1:{GATEWAY_PORT}/v1/completions",
        {"model": "llama3-tiny", "prompt": "e2e", "max_tokens": 4},
    )
    assert status == 200
    assert body["usage"]["completion_tokens"] == 4


def test_adapter_rollout_and_affinity_routing(stack):
    """Sidecar --once loads an Orbax adapter; the traffic-split model then
    routes through the gateway to the adapter."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
    from llm_instance_gateway_tpu.models.lora import target_dims
    from llm_instance_gateway_tpu.server.lora_manager import save_adapter

    cfg = LLAMA3_8B.tiny()
    dims = target_dims(cfg)
    rng = np.random.RandomState(0)
    weights = {
        t: {"a": rng.randn(cfg.n_layers, dims[t][0], 2) * 0.3,
            "b": rng.randn(cfg.n_layers, 2, dims[t][1]) * 0.3}
        for t in ("q", "v")
    }
    ckpt = stack["tmp"] / "e2e-adapter-ckpt"
    save_adapter(str(ckpt), weights, alpha=8.0, rank=2)

    rollout = stack["tmp"] / "rollout.yaml"
    rollout.write_text(f"""\
tpuLoRAConfig:
  host: 127.0.0.1
  port: {SERVER_PORT}
  ensureExist:
    models:
      - id: e2e-adapter
        source: {ckpt}
""")
    result = subprocess.run(
        [sys.executable, "-m", "llm_instance_gateway_tpu.tools.lora_sidecar",
         "--config", str(rollout), "--once"],
        env=_env(), capture_output=True, timeout=120,
    )
    assert result.returncode == 0, result.stderr.decode()

    # Logical model sql-assist -> target e2e-adapter via the gateway.
    status, body = _post(
        f"http://127.0.0.1:{GATEWAY_PORT}/v1/completions",
        {"model": "sql-assist", "prompt": "SELECT", "max_tokens": 4},
    )
    assert status == 200
    assert body["model"] == "e2e-adapter"  # body rewritten by the gateway


def test_saturation_backpressure(stack):
    """Unknown models 400 at the gateway; direct unknown adapters 404 at the
    server — the two admission layers stay distinguishable."""
    status, body = _post(
        f"http://127.0.0.1:{GATEWAY_PORT}/v1/completions",
        {"model": "ghost", "prompt": "x"},
    )
    assert status == 400
    status, _ = _post(
        f"http://127.0.0.1:{SERVER_PORT}/v1/completions",
        {"model": "ghost", "prompt": "x"},
    )
    assert status == 404


def test_extproc_binary_serves_grpc(stack):
    """The gRPC EPP binary (Envoy deployment mode) routes over a real socket."""
    import grpc

    sys.path.insert(0, REPO)
    from llm_instance_gateway_tpu.gateway.extproc import ext_proc_v3_pb2 as pb
    from llm_instance_gateway_tpu.gateway.extproc import health_v1_pb2 as healthpb
    from llm_instance_gateway_tpu.gateway.extproc.service import (
        make_health_stub,
        make_process_stub,
    )

    port = 18820
    entry = _launch_module(
        ["llm_instance_gateway_tpu.gateway.extproc",
         "--config", str(stack["pool"]), "--port", str(port),
         "--pod", f"r1=127.0.0.1:{SERVER_PORT}", "--probe-endpoints"],
        stack["tmp"] / "extproc.log",
    )
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        health = make_health_stub(channel)
        deadline = time.monotonic() + 30
        status = None
        while time.monotonic() < deadline:
            try:
                status = health(healthpb.HealthCheckRequest(), timeout=2).status
                if status == healthpb.HealthCheckResponse.SERVING:
                    break
            except grpc.RpcError:
                pass
            time.sleep(0.5)
        assert status == healthpb.HealthCheckResponse.SERVING
        # Provider needs a pod-refresh cycle before the scheduler sees r1.
        stub = make_process_stub(channel)
        body = json.dumps({"model": "llama3-tiny", "prompt": "x",
                           "max_tokens": 2}).encode()
        deadline = time.monotonic() + 30
        headers = {}
        while time.monotonic() < deadline:
            try:
                resp = next(stub(iter([pb.ProcessingRequest(
                    request_body=pb.HttpBody(body=body))])))
            except grpc.RpcError:
                time.sleep(1.0)  # warm-up window: retry like the health loop
                continue
            if resp.WhichOneof("response") == "request_body":
                headers = {o.header.key: o.header.raw_value.decode() for o in
                           resp.request_body.response.header_mutation.set_headers}
                if headers.get("target-pod"):
                    break
            time.sleep(1.0)
        assert headers.get("target-pod") == f"127.0.0.1:{SERVER_PORT}"
        channel.close()
    finally:
        _teardown_procs([entry])


def test_a_paused_replica_reads_as_its_stall_and_not_the_gateways(stack):
    """The stall clocks (tracing.LoopClock) through the benchmark's own
    metric files: stop the replica's process for a second under streamed
    load; ``server.stalled_ms`` reads about the pause, the gateway's clock
    none of it, and the requests that lived through it say so."""
    import threading

    sys.path.insert(0, REPO)
    from benchmark import manifest, readers

    def read(name, ctx):
        spec = manifest.load_metric(name)
        return readers.READERS[spec["reader"]](spec["args"], ctx)

    def get(url):
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.read().decode()

    gw = f"http://127.0.0.1:{GATEWAY_PORT}"
    srv = f"http://127.0.0.1:{SERVER_PORT}"
    body = {"model": "llama3-tiny", "prompt": "pause", "max_tokens": 48,
            "stream": True, "logit_bias": {str(b): 100.0 for b in b"abcd"}}
    _post_stream = urllib.request.Request(
        gw + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(_post_stream, timeout=120) as resp:
        assert resp.read().rstrip().endswith(b"data: [DONE]")  # warm
    before = get(srv + "/metrics")
    cursor = json.loads(get(gw + "/debug/traces?limit=1"))["seq"]
    halt = threading.Event()
    answered = []

    def offer():
        while not halt.is_set():
            with urllib.request.urlopen(_post_stream, timeout=120) as resp:
                answered.append(resp.read().rstrip().endswith(b"[DONE]"))

    clients = [threading.Thread(target=offer, daemon=True) for _ in range(2)]
    for c in clients:
        c.start()
    time.sleep(0.6)
    stack["server"].send_signal(signal.SIGSTOP)
    try:
        time.sleep(1.0)
    finally:
        stack["server"].send_signal(signal.SIGCONT)
    time.sleep(0.6)
    halt.set()
    for c in clients:
        c.join(timeout=60)
        assert not c.is_alive()
    assert answered and all(answered)
    time.sleep(0.2)  # the clocks' next tick
    traces = json.loads(
        get(f"{gw}/debug/traces?since={cursor}&limit=1024"))["traces"]
    ctx = {"window_s": 3.0, "prom_before": [before],
           "prom_after": [get(srv + "/metrics")], "gateway_traces": traces}
    stalled = read("server.stalled_ms", ctx)
    at_gateway = read("gateway.stalled_ms", ctx)
    # a second's pause less what was left of the 50 ms sleep it fell into,
    # plus the machine's own lateness in waking the process
    assert 900.0 <= stalled <= 2000.0, stalled
    assert at_gateway is not None and at_gateway <= stalled - 700.0
    assert read("server.loop_lag_ms", ctx) > 0
    assert read("gateway.loop_lag_ms", ctx) is not None
    page = get(gw + "/metrics")
    assert "gateway_loop_ticks_total" in page
