#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It never imports JAX (a parent that touched JAX
would hold the chip): it starts the cell's model server(s) through the
benchmark's thin wrapper round ``server.api_http.main`` and the gateway
through ``gateway.proxy``, warms every shape the cell's traffic uses by a
fixed script sent to each replica directly, checks a fixed probe set, offers
seeded traffic TO THE GATEWAY for ``--seconds``, prints the phases of set-up
and then, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``; every
child is stopped before it exits.  No chip: non-zero exit and no result line.

``--rehearse-cpu`` runs the same script at the tiny presets on the CPU.  It
proves the control flow, exits 10 and prints no result line: a CPU rehearsal
is never a result.  ``--rate`` overrides an open-loop mix's rate for the one
sweep that finds the knee; the driver never passes it.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()          # set-up is counted from here
T_PROCESS_MONO = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import client, manifest, readers  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402

WORK = os.path.join(ROOT, ".smoke_work", "bench")   # .gitignore covers it
CACHE = os.path.join(ROOT, ".jax_cache")            # fixed: part of the key
NATIVE = os.path.join(ROOT, "llm_instance_gateway_tpu", "native")
SERVER_PORT, GATEWAY_PORT = 18961, 18960
REHEARSAL_EXIT = 10
ADAPTER, TUNED = "bench-adapter-%d", "bench-tuned-%d"
# The server's rule for its default prefill buckets (api_http.main), copied:
# the warm-up must touch every one the mix can meet.
DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)
PIN_EXEC = ("import os,sys\n"
            "os.sched_setaffinity(0,{int(c) for c in sys.argv[1].split(',')})\n"
            "os.execv(sys.argv[2],sys.argv[2:])\n")


class Failure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[{time.time() - T_PROCESS:7.2f}s] {msg}", flush=True)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

class Proc:
    """A child of the run, pinned to its cores before exec; ``main`` stops
    every one on the way out, whatever happened."""

    def __init__(self, name: str, argv: list[str], env: dict, log_path: str,
                 cores: list[int]):
        self.name, self.log_path = name, log_path
        self.t_spawn = time.time()
        self._log = open(log_path, "w")
        argv = [sys.executable, "-c", PIN_EXEC,
                ",".join(map(str, cores))] + argv
        self.popen = subprocess.Popen(argv, env=env, cwd=ROOT,
                                      stdout=self._log,
                                      stderr=subprocess.STDOUT)

    def require_alive(self) -> None:
        rc = self.popen.poll()
        if rc is not None:
            raise Failure(f"{self.name} exited with code {rc}; its log "
                          f"ends:\n{self.tail()}")

    def tail(self, n_bytes: int = 3000) -> str:
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
                self.popen.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.popen.kill()
                self.popen.wait(timeout=10)
        self._log.close()


def child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    # Every compiled program from the cache, also those that compile in
    # under a second (JAX's default leaves them out, so they would compile
    # again in every run, and under contention fall on either side of it).
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # The span and dispatch rings hold a whole window.
    env["LIG_TRACE_CAPACITY"] = "8192"
    env["LIG_PROFILE_CAPACITY"] = "8192"
    env.update(extra)
    return env


def http(method: str, url: str, payload: dict | None = None,
         timeout_s: float = 30.0):
    """(status, headers, body).  Error statuses are returned, not raised; a
    refused connection raises OSError."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def wait_http(url: str, procs: list[Proc], timeout_s: float) -> None:
    """Poll until ``url`` answers 200; fails at once if a child died."""
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        for p in procs:
            p.require_alive()
        try:
            if http("GET", url, timeout_s=2)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.1)
    raise Failure(f"{url} not up within {timeout_s:.0f}s; {procs[0].name} "
                  f"log ends:\n{procs[0].tail()}")


def plan_cores(per_replica: int, replicas: int) -> tuple[list[list[int]],
                                                          list[int]]:
    """Disjoint shares of the cores this process may use: one per replica,
    and the rest for the gateway, the load generator and this process — as
    separate pods get separate CPU requests."""
    allowed = sorted(os.sched_getaffinity(0))
    per = max(1, min(per_replica, (len(allowed) - 1) // replicas))
    shares = [allowed[i * per:(i + 1) * per] for i in range(replicas)]
    front = allowed[replicas * per:] or allowed[-1:]
    return shares, front


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def build_native() -> None:
    """The gateway's C++ scheduler, built from source where it is not there
    yet (``*.so`` is never committed).  An up-to-date build is a no-op, so
    only a checkout's first run pays."""
    if not (shutil.which("g++") and shutil.which("make")):
        log("native: no g++/make — the gateway uses the Python scheduler")
        return
    subprocess.run(["make", "-C", NATIVE, "-s", "all"], check=True,
                   timeout=300, stdout=subprocess.DEVNULL)


def write_pool(path: str, served: str, n_adapters: int, port: int) -> None:
    docs = [f"""\
kind: InferencePool
metadata: {{name: bench-pool, resourceVersion: "1"}}
spec: {{selector: {{app: bench}}, targetPortNumber: {port}}}
""", f"""\
kind: InferenceModel
metadata: {{name: {served}}}
spec: {{modelName: {served}, criticality: Default, poolRef: {{name: bench-pool}}}}
"""]
    for i in range(n_adapters):
        docs.append(f"""\
kind: InferenceModel
metadata: {{name: {TUNED % i}}}
spec:
  modelName: {TUNED % i}
  criticality: Default
  poolRef: {{name: bench-pool}}
  targetModels: [{{name: {ADAPTER % i}, weight: 100}}]
""")
    with open(path, "w") as f:
        f.write("---\n".join(docs))


def fixed_prompt(n_tokens: int, salt: int) -> str:
    """Warm-up and probe prompts: the same in every run, whatever the seed."""
    import random

    rng = random.Random(1000 * n_tokens + salt)
    return "".join(rng.choices(traffic_mod.PROMPT_ALPHABET,
                               k=max(1, n_tokens - 1)))


def warm_up(base: str, served: str, shapes: list[int], n_adapters: int,
            stream: bool) -> int:
    """The fixed script, sent to one replica directly: every prefill shape
    of the mix, without and with an adapter row, each followed by a few
    decode steps; every adapter slot once; where the mix does not stream,
    one longer answer that walks the fused decode variants (8, 4, 2, 1)."""
    todo = [(n, served, 3) for n in shapes]
    if n_adapters:
        todo += [(n, ADAPTER % 0, 3) for n in shapes]
        todo += [(shapes[0], ADAPTER % i, 3) for i in range(1, n_adapters)]
    if not stream:
        todo.append((shapes[0], served, 16))
    for n, model, want in todo:
        body = {"model": model, "prompt": fixed_prompt(n, 1),
                "max_tokens": want, "temperature": 0,
                "logit_bias": traffic_mod.LOGIT_BIAS}
        if stream:
            body["stream"] = True
        res = client.send(*_host_port(base), body, client.Result(index=-1),
                          time.monotonic() + 1100)
        if not res.ok or res.tokens != want:
            raise Failure(f"warm-up of {n} tokens on {base} ({model}): "
                          f"{res.error or res.tokens}")
    return len(todo)


def _host_port(base: str) -> tuple[str, int]:
    host, port = base[len("http://"):].split(":")
    return host, int(port)


def probe_set(served: str, shapes: list[int],
              n_adapters: int) -> list[tuple[dict, str]]:
    """Base and adapter, shortest and longest prefill shape of the mix: each
    a request body for the gateway and the name a replica knows the model
    by (the gateway rewrites an InferenceModel to its adapter)."""
    models = [(served, served)] + ([(TUNED % 0, ADAPTER % 0)]
                                   if n_adapters else [])
    lengths = sorted({shapes[0], shapes[-1]})
    return [({"model": m, "prompt": fixed_prompt(n, 3), "max_tokens": 2,
              "temperature": 0, "logprobs": 1,
              "logit_bias": traffic_mod.LOGIT_BIAS}, direct)
            for m, direct in models for n in lengths]


def fingerprint(body: bytes):
    choice = json.loads(body)["choices"][0]
    return choice["text"], tuple(choice["logprobs"]["token_logprobs"])


def run_probes(gw: str, direct: str, probes: list[tuple[dict, str]],
               notes: list[str], procs: list[Proc]) -> bool:
    """Each probe twice through the gateway and once to a replica directly:
    identical tokens and logprob fingerprints all three times, finite
    logprobs.  The first pass also waits (by polling, not sleeping) until
    the gateway routes: it answers 429/503 until it has seen the pods and,
    for an adapter, its residency."""
    ok = True
    for body, direct_model in probes:
        direct_body = dict(body, model=direct_model)
        prints = []
        for base, payload in ((gw, body), (gw, body), (direct, direct_body)):
            t_end = time.monotonic() + 60
            while True:
                for p in procs:
                    p.require_alive()
                status, _, out = http("POST", base + "/v1/completions",
                                      payload, timeout_s=1100)
                if status == 200 or time.monotonic() > t_end:
                    break
                time.sleep(0.05)
            if status != 200:
                raise Failure(f"probe {body['model']} via {base}: HTTP "
                              f"{status} {out[:300]!r}")
            prints.append(fingerprint(out))
        same = prints[0] == prints[1] == prints[2]
        finite = all(math.isfinite(v) and v <= 0 for v in prints[0][1])
        if not (same and finite and len(prints[0][1]) == 2):
            ok = False
            notes.append(f"probe {body['model']}/{len(body['prompt'])}: "
                         f"same={same} finite={finite} {prints}")
    return ok


# --------------------------------------------------------------------------
# collection
# --------------------------------------------------------------------------

def scrape(servers: list[str]) -> list[str]:
    return [http("GET", s + "/metrics", timeout_s=10)[2].decode()
            for s in servers]


def trace_seq(base: str) -> int:
    return json.loads(http("GET", base + "/debug/traces?limit=1")[2])["seq"]


def traces_since(base: str, since: int) -> list[dict]:
    """Every trace record newer than ``since``, paged by the endpoint's own
    cursor; spans of one trace that arrive on two pages are joined."""
    by_id: dict[str, dict] = {}
    for _ in range(64):
        doc = json.loads(http(
            "GET", f"{base}/debug/traces?since={since}&limit=1024",
            timeout_s=30)[2])
        for t in doc["traces"]:
            have = by_id.setdefault(t["trace_id"], dict(t, spans=[]))
            known = {(s["name"], s["start"]) for s in have["spans"]}
            have["spans"] += [s for s in t["spans"]
                              if (s["name"], s["start"]) not in known]
        if doc["next_since"] >= doc["seq"] or doc["next_since"] <= since:
            break
        since = doc["next_since"]
    return list(by_id.values())


class Poller(threading.Thread):
    """Traced runs only: gauges have to be caught in the act, and the
    dispatch records ride a bounded ring.  Polls each replica's
    ``tpu:kv_cache_usage_perc`` four times a second and its
    ``/debug/profile`` records every two seconds, merged by ``seq``."""

    def __init__(self, servers: list[str]):
        super().__init__(daemon=True)
        self.servers = servers
        self.kv = [None] * len(servers)
        self.records: list[dict[int, dict]] = [{} for _ in servers]
        self._halt = threading.Event()

    def run(self) -> None:
        n = 0
        while not self._halt.is_set():
            for i, s in enumerate(self.servers):
                try:
                    text = http("GET", s + "/metrics", timeout_s=5)[2].decode()
                    v = readers.prom_value(text, "tpu:kv_cache_usage_perc")
                    if v is not None:
                        self.kv[i] = max(self.kv[i] or 0.0, v)
                    if n % 8 == 0:
                        self.pull_profile(i)
                except (OSError, ValueError):
                    pass
            n += 1
            self._halt.wait(0.25)

    def pull_profile(self, i: int) -> None:
        doc = json.loads(http("GET", self.servers[i] + "/debug/profile",
                              timeout_s=5)[2])
        for rec in doc.get("records", ()):
            self.records[i][rec["seq"]] = rec

    def finish(self, first_seq: list[int]) -> list[list[dict]]:
        self._halt.set()
        self.join(timeout=10)
        out = []
        for i in range(len(self.servers)):
            try:
                self.pull_profile(i)
            except (OSError, ValueError):
                pass
            out.append([r for s, r in sorted(self.records[i].items())
                        if s > first_seq[i]])
        return out


def profile_seq(base: str) -> int:
    return json.loads(http("GET", base + "/debug/profile")[2])["seq"]


def traced_programs(p: Proc) -> int:
    return sum(1 for ln in p.log_lines() if "attention dispatch:" in ln)


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(CACHE) if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def server_phases(p: Proc) -> dict:
    """Wall-clock stamps of a replica's start, from its own log: the
    wrapper's BENCH_PHASE lines and the program's timestamped log."""
    out = {}
    for ln in p.log_lines():
        if ln.startswith("BENCH_PHASE "):
            try:
                doc = json.loads(ln[len("BENCH_PHASE "):])
            except ValueError:
                continue  # two writers on one line: a phase stamp is lost
            out.setdefault(doc["phase"], doc["t"])
        elif "serving RANDOM weights" in ln:
            out.setdefault("weights_start", _stamp(ln))
        elif "compile cache:" in ln:
            out.setdefault("main", _stamp(ln))
    return {k: round(v - p.t_spawn, 3) for k, v in out.items()
            if v is not None}


def _stamp(line: str):
    try:
        return float(line.split(" ", 1)[0])
    except ValueError:
        return None


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def rehearsal_traffic(traffic: dict) -> dict:
    """The tiny presets hold 512 positions and 4 slots: the mix keeps its
    shape and loses its size.  Only ever used by ``--rehearse-cpu``."""
    t = json.loads(json.dumps(traffic))
    p, o = t["prompt_tokens"], t["output_tokens"]
    p.update(median=min(p["median"], 48), min=min(p["min"], 20),
             max=min(p["max"], 200))
    o.update(median=min(o["median"], 6), min=min(o["min"], 3),
             max=min(o["max"], 10))
    t["ramp_s"] = min(t.get("ramp_s", 0), 2)
    t["tail_s"] = min(t.get("tail_s", 0), 2)
    t["drain_s"] = 30
    if t["loop"] == "closed":
        t["clients"] = min(t["clients"], 4)
    else:
        t["rate_rps"] = min(t["rate_rps"], 3.0)
    return t


def run_cell(args, procs: list[Proc]) -> tuple[dict | None, bool]:
    man = manifest.load_manifest()
    cell = manifest.cell(man, args.workload)
    config = manifest.load_config(cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    rehearse = args.rehearse_cpu
    section = manifest.section(config, rehearse)
    if rehearse:
        traffic = rehearsal_traffic(traffic)
    if args.rate is not None:
        traffic["rate_rps"] = args.rate
    replicas, served = section["replicas"], section["served_model"]
    n_adapters = int(traffic.get("adapters", {}).get("count", 0))
    stream = bool(traffic.get("stream", True))
    notes: list[str] = []
    phases: dict = {}

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(CACHE, exist_ok=True)
    shares, front = plan_cores(section["cores_per_replica"], replicas)
    os.sched_setaffinity(0, front)
    log(f"cell {cell['name']}: config {cell['config']} x traffic "
        f"{cell['traffic']}, seed {args.seed}, {args.seconds}s, trace "
        f"{args.trace}; os.cpu_count()={os.cpu_count()}, replica cores "
        f"{shares}, gateway+generator cores {front}")
    cache_at_start = cache_entries()

    # -- replicas first: they take longest ---------------------------------
    max_seq = int(section["server_args"][
        section["server_args"].index("--max-seq-len") + 1])
    servers, server_procs = [], []
    for i in range(replicas):
        env = {"BENCH_TRACE_SECONDS": str(args.trace_seconds)}
        if replicas > 1 and not rehearse:
            # One process per chip, told which by libtpu's process bounds.
            env.update(TPU_VISIBLE_CHIPS=str(i),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        ctl = os.path.join(WORK, f"replica-{i}")
        os.makedirs(ctl)
        port = SERVER_PORT + i
        argv = [sys.executable, "-m", "benchmark.server_wrapper",
                manifest.config_file(cell["config"]),
                "1" if rehearse else "0", ctl, "--",
                "--port", str(port),
                "--platform", "cpu" if rehearse else "tpu"
                ] + section["server_args"]
        p = Proc(f"replica-{i}", argv, child_env(**env),
                 os.path.join(WORK, f"replica-{i}.log"), shares[i])
        procs.append(p)
        server_procs.append(p)
        servers.append(f"http://127.0.0.1:{port}")
    pod_names = [f"r{i}" for i in range(replicas)]

    # -- while they load: schedule, native library, adapters, gateway ------
    requests = traffic_mod.build_requests(traffic, args.seed, args.seconds)
    tuned = [TUNED % i for i in range(n_adapters)]
    bodies = [traffic_mod.payload(r, served, tuned, stream) for r in requests]
    buckets = [b for b in DEFAULT_BUCKETS if b <= max_seq]
    shapes = traffic_mod.prefill_shapes(traffic, buckets)
    build_native()
    adapter_dir = os.path.join(WORK, "adapters")
    if n_adapters:
        subprocess.run(
            [sys.executable, "-m", "benchmark.adapter_writer",
             manifest.config_file(cell["config"]), "1" if rehearse else "0",
             adapter_dir, str(n_adapters),
             str(traffic["adapters"]["rank"])],
            env=child_env(JAX_PLATFORMS="cpu"), cwd=ROOT, check=True,
            timeout=300)
    pool = os.path.join(WORK, "pool.yaml")
    write_pool(pool, served, n_adapters, SERVER_PORT)
    gw_argv = [sys.executable, "-m", "llm_instance_gateway_tpu.gateway.proxy",
               "--config", pool, "--port", str(GATEWAY_PORT)]
    for name, url in zip(pod_names, servers):
        gw_argv += ["--pod", f"{name}={url[len('http://'):]}"]
    gateway = Proc("gateway", gw_argv, child_env(JAX_PLATFORMS="cpu"),
                   os.path.join(WORK, "gateway.log"), front)
    procs.append(gateway)
    gw = f"http://127.0.0.1:{GATEWAY_PORT}"
    phases["front_ready_s"] = round(time.time() - T_PROCESS, 3)

    t_health = []
    for p, url in zip(server_procs, servers):
        wait_http(url + "/health", [p], timeout_s=1100)
        t_health.append(round(time.time() - p.t_spawn, 3))
    phases["load_s"] = round(time.time() - server_procs[0].t_spawn, 3)
    log(f"replicas healthy after {t_health} s from their spawn")

    # -- adapters and warm-up: each replica directly, all at once ----------
    t_warm = time.time()
    errors: list[BaseException] = []
    warm_s = [0.0] * replicas

    def prepare(i: int) -> None:
        try:
            for a in range(n_adapters):
                status, _, body = http(
                    "POST", servers[i] + "/v1/load_lora_adapter",
                    {"lora_name": ADAPTER % a,
                     "lora_path": os.path.join(adapter_dir, ADAPTER % a)},
                    timeout_s=600)
                if status != 200:
                    raise Failure(f"load_lora_adapter on replica {i}: HTTP "
                                  f"{status} {body[:300]!r}")
            warm_up(servers[i], served, shapes, n_adapters, stream)
            warm_s[i] = round(time.time() - t_warm, 3)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=prepare, args=(i,))
               for i in range(replicas)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        for p in procs:
            p.require_alive()
        time.sleep(0.1)
    if errors:
        raise errors[0]
    log(f"warm-up of shapes {shapes} done per replica after {warm_s} s")

    wait_http(gw + "/healthz", [gateway], timeout_s=120)
    t_probe = time.time()
    probes_ok = run_probes(gw, servers[0], probe_set(served, shapes,
                                                     n_adapters),
                           notes, procs)
    # "The gateway sees all pods": a short fixed request through it until
    # every replica has answered one (round the picker, no sleep).
    seen: set = set()
    t_end = time.monotonic() + 60
    while len(seen) < replicas and time.monotonic() < t_end:
        res = client.send("127.0.0.1", GATEWAY_PORT, {
            "model": served, "prompt": fixed_prompt(shapes[0], 4),
            "max_tokens": 2, "temperature": 0, "stream": stream,
            "logit_bias": traffic_mod.LOGIT_BIAS}, client.Result(index=-1),
            time.monotonic() + 60)
        if res.ok:
            seen.add(res.served_by)
    phases["probe_s"] = round(time.time() - t_probe, 3)
    phases["warmup_s"] = round(time.time() - t_warm, 3)
    if len(seen) < replicas:
        notes.append(f"the gateway reached only {sorted(seen)}")

    for p in procs:
        p.require_alive()

    # -- the window ----------------------------------------------------------
    programs_before = [traced_programs(p) for p in server_procs]
    ramp = float(traffic.get("ramp_s", 0.0))
    t0 = time.monotonic() + 0.25 + ramp
    setup_s = (t0 - T_PROCESS_MONO)
    phases["setup_s"] = setup_s
    log(f"first measured request due at {setup_s:.2f}s (ramp {ramp}s); "
        f"{len(requests)} requests in the schedule")
    holder: dict = {}

    def offer() -> None:
        if traffic["loop"] == "open":
            holder["results"] = client.run_open(
                requests, bodies, "127.0.0.1", GATEWAY_PORT, t0,
                args.seconds, float(traffic.get("drain_s", 30)))
        else:
            holder["results"] = client.run_closed(
                requests, bodies, "127.0.0.1", GATEWAY_PORT, t0,
                args.seconds, int(traffic["clients"]))

    runner = threading.Thread(target=offer, daemon=True)
    runner.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    prom_before = scrape(servers)
    cursors = {"gw": trace_seq(gw), "srv": [trace_seq(s) for s in servers]}
    first_seq = [profile_seq(s) for s in servers]
    cache_before = cache_entries()
    poller = None
    if args.trace:
        poller = Poller(servers)
        poller.start()
        # The window's last seconds: stopping a trace keeps the server's
        # cores busy for a while (20 s on the v5e host), and that falls into
        # the drain and not into what the counters of the window see.
        at = t0 + max(0.5, args.seconds - args.trace_seconds - 0.5)
        time.sleep(max(0.0, at - time.monotonic()))
        server_procs[0].popen.send_signal(signal.SIGUSR1)
    time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
    prom_after = scrape(servers)
    profile_records = poller.finish(first_seq) if poller else []
    gauge_peaks = {"kv": poller.kv if poller else []}
    while runner.is_alive():
        for p in procs:
            p.require_alive()
        runner.join(timeout=0.2)
    results = holder["results"]
    log("window and drain over")

    gateway_traces = traces_since(gw, cursors["gw"])
    server_traces = [traces_since(s, c)
                     for s, c in zip(servers, cursors["srv"])]
    programs_after = [traced_programs(p) for p in server_procs]
    cache_after = cache_entries()
    devices = [json.loads(http("GET", s + "/debug/device")[2])
               for s in servers]
    dev0 = devices[0]
    trace = None
    if args.trace:
        server_procs[0].popen.send_signal(signal.SIGUSR2)
        path = os.path.join(WORK, "replica-0", "trace_summary.json")
        t_end = time.monotonic() + 240
        while not os.path.exists(path) and time.monotonic() < t_end:
            server_procs[0].require_alive()
            time.sleep(0.2)
        if os.path.exists(path):
            with open(path) as f:
                trace = json.load(f)
        if not trace or "error" in trace:
            notes.append(f"device trace: {trace and trace['error']}")
            trace = None
    for p in procs:
        p.require_alive()
    for i, p in enumerate(server_procs):
        ph = server_phases(p)
        ph.update(health=t_health[i], warmup_done=warm_s[i])
        log(f"phases replica-{i} (s from its spawn at "
            f"{p.t_spawn - T_PROCESS:.2f}): {json.dumps(ph)}")
    log("phases run: " + json.dumps(phases))

    # -- metrics and verdict -------------------------------------------------
    ctx = {
        "window_s": float(args.seconds), "t0": t0, "results": results,
        "traffic": traffic, "config": section,
        "prom_before": prom_before, "prom_after": prom_after,
        "gateway_traces": gateway_traces, "server_traces": server_traces,
        "profile_records": profile_records, "gauge_peaks": gauge_peaks,
        "phases": phases, "device": devices, "trace": trace,
        "device_kind": dev0["device_kind"], "pod_names": pod_names,
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of(man, cell["name"], kind):
        spec = manifest.load_metric(m["name"])
        value = readers.READERS[spec["reader"]](spec.get("args", {}), ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            notes.append(f"metric {m['name']}: nothing to read")

    window = [r for r in results if r.in_window]
    failed = [r for r in window if r.error is not None]
    answered = [r for r in window if r.ok]
    short = [r for r in answered if r.tokens != r.want_tokens]
    new_programs = [a - b for a, b in zip(programs_after, programs_before)]
    served_by = {r.served_by for r in answered}
    model_ok = all(dev0["model_config"].get(k) == v
                   for k, v in ctx["config"]["model"].items()) or rehearse
    device_ok = rehearse or all(
        d["platform"] == "tpu" and d["device_count"] == 1
        for d in devices) and len(devices) == cell["chips"]
    checks = {
        "probes identical via gateway twice and direct": probes_ok,
        "every answered request returned exactly its max_tokens": not short,
        "no request failed": not failed,
        "some request was answered": bool(answered),
        "no new program traced inside the window": not any(new_programs),
        "no new compile-cache entry inside the window":
            cache_after == cache_before,
        "every replica served": served_by >= set(pod_names),
        "model sizes as the configuration file states": model_ok,
        "a TPU with the cell's chips": device_ok,
    }
    for name, ok in checks.items():
        log(f"  [{'PASS' if ok else 'FAIL'}] {name}")
    for r in failed[:5]:
        notes.append(f"request {r.index}: {r.error}")
    for n in notes:
        log("  note: " + n)
    log(f"compile cache entries: {cache_at_start} at start, {cache_before} "
        f"before the window, {cache_after} after; new traced programs in "
        f"the window {new_programs}")

    peak = max([(d.get("memory_stats") or {}).get("peak_bytes_in_use") or 0
                for dev in devices for d in dev["devices"]] or [0])
    device = {"platform": dev0["platform"], "kind": dev0["device_kind"],
              "count": sum(d["device_count"] for d in devices),
              "memory_peak_bytes": peak}
    result = {"correct": all(checks.values()), "attempted": len(window),
              "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["cell"] = {"workload": cell["name"], "seed": args.seed,
                      "seconds": args.seconds,
                      "rate_rps": traffic.get("rate_rps"),
                      "answered": len(answered),
                      "cut": sum(1 for r in results if r.cut)}
    if args.rate is not None:
        result["sweep"] = sweep_info(ctx, window)
    if args.keep:
        keep = os.path.join(ROOT, "chiprun_out", args.keep)
        os.makedirs(keep, exist_ok=True)
        for name in os.listdir(WORK):
            if name.endswith(".log"):
                shutil.copy(os.path.join(WORK, name), keep)
        if trace:
            with open(os.path.join(keep, "trace_summary.json"), "w") as f:
                json.dump(trace, f)
    return result, rehearse


def sweep_info(ctx: dict, window: list) -> dict:
    """What the knee is judged by: answered over sent, and whether queue
    wait grew from the window's first half to its second."""
    spans = sorted(
        (s["start"], s["end"] - s["start"])
        for per in ctx["server_traces"] for t in per for s in t["spans"]
        if s["name"] == "engine.queue_wait")
    half = len(spans) // 2
    q = readers.quantile
    return {
        "sent": len(window),
        "answered": sum(1 for r in window if r.ok),
        "queue_wait_p50_ms_halves": [
            1000 * (q([d for _, d in part], 0.5) or 0.0)
            for part in (spans[:half], spans[half:])],
        "ttft_p50_ms": readers.client_quantile(
            {"field": "ttft", "q": 0.5}, ctx),
        "ttft_p90_ms": readers.client_quantile(
            {"field": "ttft", "q": 0.9}, ctx),
        "tpot_p50_ms": readers.client_quantile(
            {"field": "tpot", "q": 0.5}, ctx),
        "output_tok_s": readers.client_tokens_per_s({}, ctx),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=4.0,
                    help="length of the device trace inside the window")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny presets on the CPU; exits %d at best and "
                         "prints no result line" % REHEARSAL_EXIT)
    ap.add_argument("--rate", type=float, default=None,
                    help="sweep only: override an open-loop mix's rate")
    ap.add_argument("--dev-cache-env", action="store_true",
                    help="builder's chip calls only: keep the compile cache "
                         "where JAX_COMPILATION_CACHE_DIR says, so that it "
                         "outlives the call; the driver's runs keep it in "
                         "the checkout")
    ap.add_argument("--keep", default=None, metavar="NAME",
                    help="copy logs and the trace summary to "
                         "chiprun_out/NAME")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "llm_instance_gateway_tpu")):
        print("benchmark/run.py: the program is not here — no "
              "llm_instance_gateway_tpu/ beside benchmark/", file=sys.stderr)
        return 1
    if args.seconds is None:
        args.seconds = float(manifest.load_manifest()["run_seconds"])
    if args.rehearse_cpu:
        print("*** REHEARSAL on the CPU at the tiny presets: exercises this "
              "harness's control flow only and prints no result. ***",
              flush=True)

    def on_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)
    global CACHE
    if args.dev_cache_env and os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE = os.environ["JAX_COMPILATION_CACHE_DIR"]
    procs: list[Proc] = []
    try:
        result, rehearsal = run_cell(args, procs)
    except (Failure, subprocess.CalledProcessError, OSError, KeyError) as e:
        print(f"benchmark/run.py FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        for p in reversed(procs):
            p.stop()
    log(f"total wall {time.time() - T_PROCESS:.1f}s")
    if rehearsal:
        print("REHEARSAL held: " + json.dumps(
            {"correct": result["correct"], "attempted": result["attempted"],
             "failed": result["failed"],
             "metrics_read": sorted(result["metrics"])}), flush=True)
        return REHEARSAL_EXIT if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
