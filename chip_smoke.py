#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives the system's main path once, through the entry points a user runs:

    client -> python -m llm_instance_gateway_tpu.gateway.proxy
           -> python -m llm_instance_gateway_tpu.server.api_http  (holds the chip)

at the full published widths of Qwen2.5-7B (d_model 3584, 28 q / 4 kv heads
of 128, d_ff 18944, vocab 152064, QKV bias, all 28 layers), int8 weights made
from a seed.  It answers a handful of `/v1/completions` sent to the GATEWAY
(base model, a LoRA adapter addressed by its InferenceModel, a stream, a
request with logprobs, a small burst) and checks what came back.

This process never imports JAX: a parent that touched JAX would hold the
chip and the server child would hang.  The one child allowed near JAX before
the server (the adapter writer) runs with JAX_PLATFORMS=cpu.

Exit code 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
only when every check held on a TPU.  No accelerator, a child dying, a failed
check, a raised phase: non-zero exit and no result line.

    python3 chip_smoke.py                 # one chip (the driver's run)
    python3 chip_smoke.py --chips 4       # four-chip host: --mesh tensor=4,
                                          # then four one-chip replicas
    python3 chip_smoke.py --rehearse-cpu  # tiny preset on the CPU: rehearses
                                          # the script, is NEVER a pass
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke_work")  # listed in .gitignore
NATIVE = os.path.join(HERE, "llm_instance_gateway_tpu", "native")

SERVER_PORT = 18941
GATEWAY_PORT = 18940
REHEARSAL_EXIT = 10  # a rehearsal that held every check: still not a pass

# Qwen2.5-7B as published (Qwen/Qwen2.5-7B config.json) — the smoke's own
# copy, so the server's report is checked against something it did not say.
PUBLISHED = {
    "d_model": 3584, "n_layers": 28, "n_heads": 28, "n_kv_heads": 4,
    "head_dim": 128, "d_ff": 18944, "vocab_size": 152064,
    "attention_bias": True,
}

ADAPTER = "smoke-adapter"
TUNED_MODEL = "smoke-tuned"  # the InferenceModel that targets the adapter

# Byte-level tokenizer: one token per character (+BOS).  ~200 characters
# lands in the 256 prefill bucket (>= BLOCK_Q: the flash kernel's side of
# the dispatch); the short prompt lands in a bucket below it (XLA's side).
LONG_PROMPT = (
    "The gateway picks a replica from live metrics: KV-cache headroom, the "
    "prefill and decode queues, adapter residency. The replica holds one "
    "chip and serves every adapter from one batch. Explain the trade:")
SHORT_PROMPT = "Hello, chip."
MAX_TOKENS = 32
# Random weights spread the argmax over all 152,064 ids, of which the
# byte-level tokenizer can print 256: unbiased, every answer decodes to "".
# A +100 logit_bias on 32 printable bytes (the API's maximum) makes greedy
# decoding the argmax of the MODEL's logits among those 32 — still the
# model's computation, now visible as text the checks can compare.
VISIBLE = {str(b): 100 for b in b"abcdefghijklmnopqrstuvwxyz ,.;-\n"}


class SmokeFailure(Exception):
    pass


# --------------------------------------------------------------------------
# plumbing
# --------------------------------------------------------------------------

class Proc:
    """A child started by the smoke; ``main`` stops every one on the way
    out, whatever happened."""

    def __init__(self, name: str, argv: list[str], env: dict, log_path: str):
        self.name, self.log_path = name, log_path
        self._log = open(log_path, "w")
        self.popen = subprocess.Popen(
            argv, env=env, cwd=HERE, stdout=self._log,
            stderr=subprocess.STDOUT)

    def require_alive(self) -> None:
        rc = self.popen.poll()
        if rc is not None:
            raise SmokeFailure(
                f"{self.name} exited with code {rc}; its log ends:\n"
                + self.tail())

    def tail(self, n_bytes: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n_bytes))
            return f.read().decode(errors="replace")

    def log_lines(self) -> list[str]:
        with open(self.log_path, errors="replace") as f:
            return f.read().splitlines()

    def stop(self) -> None:
        if self.popen.poll() is None:
            self.popen.send_signal(signal.SIGTERM)
            try:
                self.popen.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.popen.kill()
                self.popen.wait(timeout=10)
        self._log.close()


def child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def http(method: str, url: str, payload: dict | None = None,
         timeout_s: float = 30.0):
    """(status, headers, body bytes).  HTTP error statuses are returned, not
    raised; a refused connection (dead child) raises."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def wait_http(url: str, proc: Proc, timeout_s: float) -> float:
    """Poll until ``url`` answers 200; fails at once if the child died."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        proc.require_alive()
        try:
            if http("GET", url, timeout_s=2)[0] == 200:
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(0.5)
    raise SmokeFailure(f"{url} not up within {timeout_s:.0f}s; "
                       f"{proc.name} log ends:\n{proc.tail()}")


def metric(text: str, name: str) -> float:
    """Sum of a family's samples in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise SmokeFailure(f"metric {name} not in the server's /metrics")
    return total


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'PASS' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def build_native() -> None:
    """Built from what git would commit: `*.so` is ignored, so whatever
    library lies here is stale by definition.  Remove both and rebuild from
    scheduler.cc / prom_parse.cc; with a compiler present a build failure
    fails the smoke."""
    for so in ("libligsched.so", "libligprom.so"):
        try:
            os.remove(os.path.join(NATIVE, so))
        except FileNotFoundError:
            pass
    if not (shutil.which("g++") and shutil.which("make")):
        print("native: no g++/make here — the gateway will use the Python "
              "scheduler", flush=True)
        return
    subprocess.run(["make", "-C", NATIVE, "-s", "-B", "all"], check=True,
                   timeout=300)
    print("native: rebuilt libligsched.so and libligprom.so from source",
          flush=True)


def write_pool(path: str, model: str, ports: list[int]) -> None:
    with open(path, "w") as f:
        f.write(f"""\
kind: InferencePool
metadata: {{name: smoke-pool, resourceVersion: "1"}}
spec: {{selector: {{app: smoke}}, targetPortNumber: {ports[0]}}}
---
kind: InferenceModel
metadata: {{name: {model}}}
spec: {{modelName: {model}, criticality: Default, poolRef: {{name: smoke-pool}}}}
---
kind: InferenceModel
metadata: {{name: {TUNED_MODEL}}}
spec:
  modelName: {TUNED_MODEL}
  criticality: Critical
  poolRef: {{name: smoke-pool}}
  targetModels: [{{name: {ADAPTER}, weight: 100}}]
""")


ADAPTER_WRITER = """
import sys
import numpy as np
from llm_instance_gateway_tpu.models import llama, qwen
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.server.lora_manager import save_adapter

cfg = {**llama.CONFIGS, **qwen.CONFIGS}[sys.argv[1]]
dims = target_dims(cfg)
rng = np.random.RandomState(7)
rank = 4
weights = {
    t: {"a": rng.randn(cfg.n_layers, dims[t][0], rank) * 0.05,
        "b": rng.randn(cfg.n_layers, rank, dims[t][1]) * 0.05}
    for t in ("q", "v")
}
save_adapter(sys.argv[2], weights, alpha=8.0, rank=rank)
"""


def write_adapter(model: str, path: str) -> None:
    """Seeded Orbax adapter, written by a short child that is kept OFF the
    chip by name (it imports jax through lora_manager)."""
    shutil.rmtree(path, ignore_errors=True)
    subprocess.run([sys.executable, "-c", ADAPTER_WRITER, model, path],
                   env=child_env(JAX_PLATFORMS="cpu"), cwd=HERE, check=True,
                   timeout=300)


def completion(base: str, payload: dict, timeout_s: float):
    t0 = time.monotonic()
    status, headers, body = http("POST", base + "/v1/completions", payload,
                                 timeout_s)
    wall = time.monotonic() - t0
    if status != 200:
        raise SmokeFailure(f"POST {base}/v1/completions {payload.get('model')}"
                           f" -> HTTP {status}: {body[:400]!r}")
    return json.loads(body), {k.lower(): v for k, v in headers.items()}, wall


def stream_completion(base: str, payload: dict, timeout_s: float):
    """(text, data-chunk count, saw [DONE], headers) of an SSE completion."""
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(payload).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    text, chunks, done = "", 0, False
    try:
        resp = urllib.request.urlopen(req, timeout=timeout_s)
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"stream POST -> HTTP {e.code}: "
                           f"{e.read()[:400]!r}") from None
    with resp:
        headers = {k.lower(): v for k, v in resp.headers.items()}
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            chunks += 1
            text += json.loads(data)["choices"][0].get("text", "")
    return text, chunks, done, headers


class KvWatcher(threading.Thread):
    """Polls the server's /metrics while requests run: tpu:kv_cache_usage_perc
    is a gauge, so it has to be caught in the act."""

    def __init__(self, url: str):
        super().__init__(daemon=True)
        self.url, self.peak, self._halt = url, 0.0, threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            try:
                text = http("GET", self.url, timeout_s=5)[2].decode()
                self.peak = max(self.peak,
                                metric(text, "tpu:kv_cache_usage_perc"))
            except (OSError, SmokeFailure):
                pass  # the main thread notices a dead server on its own
            self._halt.wait(0.05)

    def finish(self) -> float:
        self._halt.set()
        self.join(timeout=10)
        return self.peak


def drive_requests(check: Checks, gw: str, servers: dict[str, str],
                   model: str, cold_timeout_s: float) -> dict:
    """The handful of requests, all through the gateway, and their checks.
    ``servers``: pod name -> base URL (one entry except in replica mode,
    where the burst grows so that every replica is all but sure of a pick:
    32 uniform picks miss one of four pods 4 x 0.75^32 = 0.04% of runs)."""
    pods = set(servers)
    n_burst = 8 if len(servers) == 1 else 32
    first_server = next(iter(servers.values()))
    timings = {}
    base_req = {"model": model, "prompt": LONG_PROMPT,
                "max_tokens": MAX_TOKENS, "temperature": 0,
                "logit_bias": VISIBLE, "logprobs": 1}

    def answer(body):
        """(text, per-token model logprobs): the text is what a user sees,
        the logprobs are the finer fingerprint of the same computation."""
        choice = body["choices"][0]
        return choice["text"], choice["logprobs"]["token_logprobs"]

    def usage_ok(body):
        u = body["usage"]
        return (0 < u["completion_tokens"] <= MAX_TOKENS
                and u["prompt_tokens"] >= 128)

    def metrics_now():
        texts = [http("GET", s + "/metrics", timeout_s=10)[2].decode()
                 for s in servers.values()]
        return (sum(metric(t, "tpu:prefill_seconds_count") for t in texts),
                sum(metric(t, "tpu:decode_step_seconds_count") for t in texts))

    prefills0, decodes0 = metrics_now()
    watcher = KvWatcher(first_server + "/metrics")
    watcher.start()

    print("request 1: base model, long prompt, greedy (cold: compiles the "
          "prefill bucket and every decode variant it meets)", flush=True)
    cold, h, timings["cold_first_request_s"] = completion(
        gw, base_req, cold_timeout_s)
    check("base completion: 200 via gateway, x-served-by names a pod",
          h.get("x-served-by") in pods, f"x-served-by={h.get('x-served-by')}")
    check("base completion: token counts within max_tokens, prompt in a "
          ">=128 bucket", usage_ok(cold), json.dumps(cold["usage"]))
    text, lps = answer(cold)
    print(f"  base answer: {text!r}", flush=True)

    print("request 2: the same greedy prompt again (warm)", flush=True)
    warm, _, timings["warm_request_s"] = completion(gw, base_req,
                                                    cold_timeout_s)
    check("same greedy prompt twice gives the same text (and logprobs)",
          answer(warm) == (text, lps) and len(text) > 0,
          f"{len(text)} chars")

    if len(servers) == 1:
        print("request 3: the same prompt DIRECT to the server", flush=True)
        direct, _, _ = completion(first_server, base_req, cold_timeout_s)
        check("gateway and direct-to-server answers agree",
              answer(direct) == (text, lps))

    print(f"adapter: POST /v1/load_lora_adapter {ADAPTER}", flush=True)
    for name, server in servers.items():
        status, _, body = http(
            "POST", server + "/v1/load_lora_adapter",
            {"lora_name": ADAPTER,
             "lora_path": os.path.join(WORK, "adapter")}, timeout_s=300)
        if status != 200:
            raise SmokeFailure(f"load_lora_adapter on {name} -> HTTP "
                               f"{status}: {body[:400]!r}")
    # The gateway learns adapter residency from its next metrics scrape.
    time.sleep(1.0)
    print(f"request 4: InferenceModel {TUNED_MODEL} -> adapter {ADAPTER}",
          flush=True)
    tuned, h, _ = completion(gw, dict(base_req, model=TUNED_MODEL),
                             cold_timeout_s)
    check("adapter completion: gateway rewrote the model to the adapter",
          tuned["model"] == ADAPTER and h.get("x-served-by") in pods,
          f"model={tuned['model']}")
    check("adapter completion: token counts within max_tokens",
          usage_ok(tuned), json.dumps(tuned["usage"]))
    t_text, t_lps = answer(tuned)
    print(f"  adapter answer: {t_text!r}", flush=True)
    shift = max((abs(a - b) for a, b in zip(t_lps, lps)), default=0.0)
    check("adapter moves the model's distribution (the delta is applied)",
          t_text != text or shift > 1e-3,
          f"text differs: {t_text != text}; max logprob shift {shift:.4f}")

    print("request 5: stream: true", flush=True)
    stream_req = {k: v for k, v in base_req.items() if k != "logprobs"}
    s_text, chunks, done, h = stream_completion(
        gw, dict(stream_req, stream=True), cold_timeout_s)
    check("stream: SSE chunks of text then [DONE], x-served-by present",
          done and chunks > 1 and len(s_text) > 0
          and h.get("x-served-by") in pods,
          f"{chunks} data chunks, {len(s_text)} chars")
    # Not a check: the stream decodes one step per dispatch, the unary
    # request in fused blocks — two compiled programs, whose bf16 rounding
    # may part ways on a near-tie between two of the 32 visible tokens.
    print(f"  [info] stream text equals the unary answer: {s_text == text}",
          flush=True)

    print("request 6: logprobs=3, short prompt (a bucket below BLOCK_Q)",
          flush=True)
    lp, _, _ = completion(
        gw, {"model": model, "prompt": SHORT_PROMPT, "max_tokens": 16,
             "temperature": 0, "logprobs": 3, "logit_bias": VISIBLE},
        cold_timeout_s)
    lps = lp["choices"][0]["logprobs"]
    flat = list(lps["token_logprobs"]) + [
        v for top in lps["top_logprobs"] for v in top.values()]
    check("logprobs: one per generated token, finite everywhere (no NaN "
          "from the int8 x bf16 path)",
          len(lps["token_logprobs"]) == lp["usage"]["completion_tokens"] > 0
          and all(isinstance(v, (int, float)) and math.isfinite(v) and v <= 0
                  for v in flat),
          f"{len(flat)} values, min {min(flat, default=0):.3f}")

    print(f"a burst of {n_burst} (base and adapter mixed in one decode "
          "batch)", flush=True)
    results: list = [None] * n_burst

    def one(i: int) -> None:
        try:
            results[i] = completion(
                gw, dict(base_req, model=TUNED_MODEL if i % 2 else model,
                         prompt=f"{LONG_PROMPT} ({i})"), cold_timeout_s)
        except Exception as e:  # noqa: BLE001 — re-raised below, not hidden
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(n_burst)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=cold_timeout_s + 60)
    timings["burst_wall_s"] = time.monotonic() - t0
    timings["n_burst"] = n_burst
    for r in results:
        if isinstance(r, Exception):
            raise r
        if r is None:
            raise SmokeFailure("a burst request never returned")
    check(f"burst: {n_burst}/{n_burst} answered 200 within max_tokens",
          all(usage_ok(r[0]) for r in results))
    served_by = sorted({r[1].get("x-served-by") for r in results})
    check("burst: every answer names a pod of the pool",
          set(served_by) <= pods, f"x-served-by={served_by}")
    timings["served_by"] = served_by

    kv_peak = watcher.finish()
    prefills1, decodes1 = metrics_now()
    check("server /metrics: prefill and decode step counts moved",
          prefills1 > prefills0 and decodes1 > decodes0,
          f"prefill {prefills0:.0f}->{prefills1:.0f}, "
          f"decode steps {decodes0:.0f}->{decodes1:.0f}")
    check("server /metrics: tpu:kv_cache_usage_perc non-zero during the run",
          kv_peak > 0, f"peak {kv_peak:.4f}")
    return timings


def device_report(check: Checks, server: str, rehearsal: bool,
                  want_devices: int) -> dict:
    status, _, body = http("GET", server + "/debug/device", timeout_s=30)
    if status != 200:
        raise SmokeFailure(f"/debug/device -> HTTP {status}")
    dev = json.loads(body)
    mc = dev["model_config"]
    print(f"server reports: platform={dev['platform']} "
          f"device_kind={dev['device_kind']!r} "
          f"device_count={dev['device_count']} mesh={dev['mesh']}",
          flush=True)
    print(f"server reports: model {mc['name']} d_model {mc['d_model']} / "
          f"d_ff {mc['d_ff']} / {mc['n_heads']}x{mc['n_kv_heads']} heads of "
          f"{mc['head_dim']} / vocab {mc['vocab_size']} / "
          f"{mc['n_layers']} layers served; weight bytes by dtype "
          f"{dev['weight_bytes_by_dtype']}", flush=True)
    for d in dev["devices"]:
        ms = d["memory_stats"] or {}
        print(f"  device {d['id']}: weight_bytes={d['weight_bytes']} "
              f"bytes_in_use={ms.get('bytes_in_use')} "
              f"peak_bytes_in_use={ms.get('peak_bytes_in_use')} "
              f"bytes_limit={ms.get('bytes_limit')}", flush=True)
    if rehearsal:
        check("REHEARSAL: server on the cpu by name", dev["platform"] == "cpu")
        return dev
    check("server reports platform=tpu", dev["platform"] == "tpu",
          dev["platform"])
    check(f"server reports {want_devices} device(s)",
          dev["device_count"] == want_devices, str(dev["device_count"]))
    check("server serves Qwen2.5-7B at its published widths, all 28 layers",
          all(mc.get(k) == v for k, v in PUBLISHED.items()),
          json.dumps({k: mc.get(k) for k in PUBLISHED}))
    int8 = dev["weight_bytes_by_dtype"].get("int8", 0)
    check("weights are int8 (projections + lm_head ~7.1 GB)",
          6.5e9 < int8 < 7.5e9, f"{int8 / 1e9:.2f} GB int8")
    peaks = [(d["memory_stats"] or {}).get("peak_bytes_in_use")
             for d in dev["devices"]]
    check("memory_stats()['peak_bytes_in_use'] reported for every device",
          all(isinstance(p, int) and p > 0 for p in peaks), str(peaks))
    return dev


def dispatch_report(check: Checks, server: Proc, rehearsal: bool) -> None:
    """Echo which attention implementation each traced program compiled in
    (ops/attention.log_choice) and hold the server to it."""
    lines = [ln[ln.index("attention dispatch:"):]
             for ln in server.log_lines() if "attention dispatch:" in ln]
    seen: dict[str, int] = {}
    for ln in lines:
        seen[ln] = seen.get(ln, 0) + 1
    print("attention implementation chosen by each traced program:",
          flush=True)
    for ln, n in seen.items():
        print(f"  {ln}  (x{n})", flush=True)
    ops = {ln.split("op=")[1].split()[0] for ln in seen}
    check("dispatch: decode and prefill programs reported their choice",
          {"decode", "flash_prefill"} <= ops, str(sorted(ops)))
    if rehearsal:
        return
    check("dispatch: no kernel in interpret mode, no non-TPU backend",
          not any("pallas-interpret" in ln or "reason=backend=" in ln
                  for ln in seen))
    decode = [ln for ln in seen if "op=decode " in ln]
    check("dispatch: decode took the Pallas kernel at the smoke's shapes",
          bool(decode) and all("impl=pallas " in ln for ln in decode))
    big = [ln for ln in seen if "op=flash_prefill " in ln
           and int(ln.split("shape=q(")[1].split(",")[1]) >= 128]
    check("dispatch: prefill buckets >= 128 took the Pallas kernel",
          bool(big) and all("impl=pallas " in ln for ln in big))
    small_xla = [ln for ln in seen if "op=flash_prefill " in ln
                 and "impl=xla" in ln]
    check("dispatch: every XLA choice carries its shape reason",
          all("!= 0" in ln for ln in seen if "impl=xla" in ln),
          f"{len(small_xla)} prefill bucket(s) below BLOCK_Q")


def scheduler_report(gateway: Proc) -> None:
    line = next((ln for ln in gateway.log_lines() if "scheduler:" in ln), None)
    if line is None:
        raise SmokeFailure("the gateway never said which scheduler it built")
    print("gateway " + line[line.index("scheduler:"):], flush=True)
    if shutil.which("g++") and shutil.which("make") and "native" not in line:
        raise SmokeFailure("g++ is present but the gateway fell back to the "
                           "Python scheduler")


# --------------------------------------------------------------------------
# topologies
# --------------------------------------------------------------------------

def server_argv(model: str, port: int, rehearsal: bool,
                mesh: str | None) -> list[str]:
    argv = [sys.executable, "-m", "llm_instance_gateway_tpu.server.api_http",
            "--model", model, "--port", str(port),
            "--platform", "cpu" if rehearsal else "tpu"]
    if rehearsal:
        # Tiny preset: max_seq_len 512 so the long prompt still meets a
        # >=128 bucket; float32 as every CPU test runs it.
        argv += ["--decode-slots", "4", "--max-seq-len", "512",
                 "--dtype", "float32"]
    else:
        # Steady state at these settings: int8 weights 7.1 GB + bf16
        # embedding 1.1 GB + bf16 KV 16 slots x 2048 x 57,344 B = 1.9 GB.
        # Step knobs are the server's defaults (--adaptive-steps 8):
        # recorded, not tuned, here.
        argv += ["--quantize", "int8", "--decode-slots", "16",
                 "--max-seq-len", "2048"]
    if mesh:
        argv += ["--mesh", mesh]
    return argv


def run_topology(label: str, check: Checks, model: str, rehearsal: bool,
                 mesh: str | None, replicas: int, procs: list[Proc]) -> dict:
    """Start server(s) + gateway, drive the requests, report, stop."""
    print(f"\n=== {label} ===", flush=True)
    started = len(procs)
    n_dev = 4 if mesh else 1
    ports = [SERVER_PORT + i for i in range(replicas)]
    write_pool(os.path.join(WORK, "pool.yaml"), model, ports)
    t0 = time.monotonic()
    servers: dict[str, str] = {}
    server_procs = []
    for i, port in enumerate(ports):
        env = {}
        if rehearsal and mesh:
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        if replicas > 1 and not rehearsal:
            # One process per chip, told which chip is its own by libtpu's
            # process-bounds environment; the parent stays off JAX.
            env.update(TPU_VISIBLE_CHIPS=str(i),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        p = Proc(f"server-{i}", server_argv(model, port, rehearsal, mesh),
                 child_env(**env), os.path.join(WORK, f"server-{i}.log"))
        procs.append(p)
        server_procs.append(p)
        servers[f"r{i}"] = f"http://127.0.0.1:{port}"
    for p, url in zip(server_procs, servers.values()):
        up = wait_http(url + "/health", p, timeout_s=900)
        print(f"{p.name}: /health ok after {up:.1f}s", flush=True)
    load_s = time.monotonic() - t0

    gw_argv = [sys.executable, "-m", "llm_instance_gateway_tpu.gateway.proxy",
               "--config", os.path.join(WORK, "pool.yaml"),
               "--port", str(GATEWAY_PORT)]
    for name, url in servers.items():
        gw_argv += ["--pod", f"{name}={url[len('http://'):]}"]
    gateway = Proc("gateway", gw_argv, child_env(JAX_PLATFORMS="cpu"),
                   os.path.join(WORK, "gateway.log"))
    procs.append(gateway)
    wait_http(f"http://127.0.0.1:{GATEWAY_PORT}/healthz", gateway, 120)
    time.sleep(2.0)  # one pod-refresh cycle before the scheduler sees pods

    # Bytes per device AFTER LOAD, before any request compiled anything.
    loaded = [json.loads(http("GET", s + "/debug/device", timeout_s=30)[2])
              for s in servers.values()]
    gw = f"http://127.0.0.1:{GATEWAY_PORT}"
    timings = drive_requests(check, gw, servers, model, cold_timeout_s=900)
    timings["load_s"] = load_s
    for p in procs[started:]:
        p.require_alive()

    devs = [device_report(check, s, rehearsal, n_dev)
            for s in servers.values()]
    for p in server_procs:
        dispatch_report(check, p, rehearsal)
    scheduler_report(gateway)
    if mesh:
        total = sum(loaded[0]["weight_bytes_by_dtype"].values())
        per_dev = [d["weight_bytes"] for d in loaded[0]["devices"]]
        in_use = [(d["memory_stats"] or {}).get("bytes_in_use")
                  for d in loaded[0]["devices"]]
        print(f"after load: weight bytes per device {per_dev} of {total} "
              f"total; bytes_in_use per device {in_use}", flush=True)
        check("mesh: no device holds more than 1/3 of the weight bytes",
              max(per_dev) <= total / 3,
              f"max {max(per_dev) / 1e9:.2f} GB of {total / 1e9:.2f} GB")
        sm = [ln for ln in server_procs[0].log_lines()
              if "Pallas kernels via shard_map" in ln]
        if not rehearsal:
            check("mesh: the shard_map kernels were the chosen "
                  "implementation",
                  bool(sm) and "flash_prefill=True" in sm[0]
                  and "cached_decode=True" in sm[0], sm[0][-90:] if sm else "")
    if replicas > 1:
        check(f"replicas: picks spread over all {replicas} x-served-by names",
              len(timings["served_by"]) == replicas, str(timings["served_by"]))
        if not rehearsal:
            kinds = {(d["platform"], d["device_count"]) for d in devs}
            check("replicas: every process holds exactly one tpu chip",
                  kinds == {("tpu", 1)}, str(kinds))
    print(f"times [{label}]: load+health {load_s:.1f}s (cold set-up), first "
          f"request {timings['cold_first_request_s']:.1f}s (cold: compiles "
          f"in the request), same request warm "
          f"{timings['warm_request_s']:.2f}s, burst of "
          f"{timings['n_burst']} {timings['burst_wall_s']:.2f}s", flush=True)
    for p in reversed(procs[started:]):
        p.stop()
    del procs[started:]
    return devs[0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny preset on the CPU; exits %d at best, never 0"
                         % REHEARSAL_EXIT)
    args = ap.parse_args()
    rehearsal = args.rehearse_cpu
    model = "qwen-tiny" if rehearsal else "qwen2.5-7b"

    if not os.path.isdir(os.path.join(HERE, "llm_instance_gateway_tpu")):
        print("chip_smoke.py: the program is not here — no "
              "llm_instance_gateway_tpu/ beside this script", file=sys.stderr)
        return 1
    if rehearsal:
        print("*** REHEARSAL on the CPU at a tiny preset: exercises this "
              "script's control flow only. It is NOT a pass and says "
              "nothing about the chip. ***", flush=True)

    def on_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)
    os.makedirs(WORK, exist_ok=True)
    procs: list[Proc] = []
    check = Checks()
    t_start = time.monotonic()
    try:
        build_native()
        write_adapter(model, os.path.join(WORK, "adapter"))
        if args.chips == 1:
            dev = run_topology("one chip", check, model, rehearsal,
                               mesh=None, replicas=1, procs=procs)
        else:
            dev = run_topology("four chips: --mesh tensor=4", check, model,
                               rehearsal, mesh="tensor=4", replicas=1,
                               procs=procs)
            run_topology("four chips: four one-chip replicas", check, model,
                         rehearsal, mesh=None, replicas=4, procs=procs)
    except SmokeFailure as e:
        print(f"chip_smoke.py FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for p in reversed(procs):
            p.stop()
    print(f"total wall {time.monotonic() - t_start:.1f}s", flush=True)
    if check.failed:
        print("chip_smoke.py FAILED checks: " + "; ".join(check.failed),
              file=sys.stderr, flush=True)
        return 1
    if rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "note": "CPU rehearsal held every check; "
                                  "not a chip pass"}), flush=True)
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
