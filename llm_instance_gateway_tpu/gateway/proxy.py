"""Standalone gateway: an L7 reverse proxy embedding the ext-proc handler core.

The reference runs as an Envoy ext-proc sidecar: Envoy terminates HTTP, calls
the EPP over gRPC, then routes to the ORIGINAL_DST cluster using the
``target-pod`` header (``pkg/manifests/patch_policy.yaml:14-42``).  On GKE
that wiring is reproduced by the manifests under ``deploy/``; for
environments without Envoy (and for the TPU pools' leaner data path) this
module IS the proxy: it terminates OpenAI-style HTTP, runs the identical
four-phase handler core inline (request headers -> body -> schedule ->
forward -> response phases), and streams the model server's reply back.

Endpoints:
- ``POST /v1/completions`` and ``/v1/chat/completions`` — routed inference.
- ``GET  /metrics``  — gateway self-telemetry (scheduler decisions, shed rate,
  pick latency, TTFT/TPOT/e2e histograms; resolves reference TODO
  provider.go:140).
- ``GET  /debug/traces`` — recent request traces (``?trace_id=`` filters,
  ``?since=<seq>`` serves incremental deltas — the same cursor contract
  as ``/debug/events``, what the fleet collector polls); each trace
  merges the proxy's own spans with the model servers' spans returned in
  their ``x-lig-spans`` response headers, so one JSON document answers
  "where did this request spend its time?" across up to three processes.
- ``GET  /debug/slo`` — per-model SLO compliance + multi-window burn rates
  + burn state (gateway/slo.py), evaluated on demand.
- ``GET  /debug/health`` — per-replica 0-1 health scores with components
  and hysteresis states (gateway/health.py), plus the resilience plane:
  health policy, per-pod circuit-breaker states, retry-budget level
  (gateway/resilience.py).
- ``GET  /debug/usage`` — pool-wide capacity attribution: per-{model,
  adapter} consumption shares, noisy-neighbor scores/flags, pool-waste
  aggregates (gateway/usage.py; live console: ``tools/lig_top.py``).
- ``GET  /debug/kv`` — the fleet KV economy view (gateway/kvobs.py):
  per-pod reuse efficiency / parked-KV share over the replicas'
  ``tpu:kv_*`` ledger families and the cross-replica prefix duplication
  index ("prefix P resident on k replicas, N blocks duplicated");
  rendered by ``tools/kv_report.py``.
- ``GET  /debug/capacity`` — the capacity & saturation plane
  (gateway/capacity.py): per-pod per-resource saturation indices, the
  sim-calibrated twin's headroom-at-SLO and time-to-breach forecasts, and
  the twin-drift trust state; rendered by ``tools/capacity_report.py``.
- ``GET  /debug/events`` — the flight recorder (events.py): admission
  rejections, pick outcomes, disagg fallbacks, scrape failures, SLO/health
  transitions, noisy-neighbor flags; ``?since=<seq>`` for incremental
  polling.
- ``GET  /debug/fleet`` — the fleet observability view (gateway/fleetobs.py):
  every peer gateway's and pool pod's traces/events/slo/health pulled
  through the incremental cursors, cross-replica traces stitched into
  causally-ordered timelines with clock-skew normalization, event journals
  merged by (replica, seq), fleet-wide SLO rollup; rendered by
  ``tools/fleet_report.py``.
- ``GET  /healthz``  — 200 once the InferencePool is synced (main.go:43-52).
- ``GET  /v1/models`` — logical models from the datastore.

On an SLO fast burn the proxy snapshots events + traces + metrics + SLO and
health payloads into a black-box dump file (``LIG_BLACKBOX_DIR``, cooldown
``LIG_BLACKBOX_COOLDOWN_S``); ``tools/blackbox_report.py`` renders the
post-mortem timeline.

Every response — success or error — carries the request's ``x-lig-trace-id``
(error bodies embed it too) so clients and the loadgen can correlate.

Failure policy (gateway/resilience.py): idempotent upstream failures
(connect errors, 503s, TTFT timeouts — anything before the first relayed
byte) retry with decorrelated-jitter backoff under a global retry budget,
re-running admission + pick each attempt so ``health_policy=avoid`` steers
the re-pick off the failed replica; non-streaming requests can hedge on a
slow TTFT; per-phase timeouts (connect / TTFT / stream-idle) replace the
old single 3600 s client timeout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import tempfile
import time
import uuid

import aiohttp
from aiohttp import web

from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu.gateway import capacity as capacity_mod
from llm_instance_gateway_tpu.gateway import fleetobs
from llm_instance_gateway_tpu.gateway import pickledger as pickledger_mod
from llm_instance_gateway_tpu.gateway import slo as slo_mod
from llm_instance_gateway_tpu.gateway import statebus as statebus_mod
from llm_instance_gateway_tpu.gateway.advisors import (
    AdvisorStack,
    merge_exposition_blocks,
)
from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.handlers.messages import (
    RequestBody,
    RequestHeaders,
    ResponseBody,
    ResponseHeaders,
)
from llm_instance_gateway_tpu.gateway.handlers.server import (
    ProcessingError,
    RequestContext,
    Server,
)
from llm_instance_gateway_tpu.gateway.resilience import retry_backoff
from llm_instance_gateway_tpu.gateway.telemetry import GatewayMetrics, Timer
from llm_instance_gateway_tpu import tracing

logger = logging.getLogger(__name__)

# Fast-relay final-usage window: the zero-copy path keeps only the trailing
# bytes of the stream (as whole chunk references, never per-chunk copies) to
# parse the final usage chunk from; SSE usage envelopes are a few hundred
# bytes, so 16 KB of tail is orders of magnitude of margin.
RELAY_TAIL_BYTES = 16384
# Upstream keepalive pool: how long an idle per-pod connection survives and
# how many concurrent connections one pod may hold.  Reuse is the point —
# a fresh TCP handshake per request is pure data-plane tax.
UPSTREAM_KEEPALIVE_S = float(os.environ.get("LIG_UPSTREAM_KEEPALIVE_S", "30"))
# A replica streams one connection a request it decodes: the cap has to lie
# above the largest ``--decode-slots`` a pool serves, or the gateway fills
# half a replica and times the rest out waiting for a connection (a 64-slot
# replica behind the old 32: chip run, PR 43).
UPSTREAM_CONNS_PER_POD = int(os.environ.get("LIG_UPSTREAM_CONNS_PER_POD",
                                            "256"))


def final_data_line(tail: bytes) -> bytes:
    """Last complete ``data: `` line of an SSE stream that is not the
    ``[DONE]`` terminator, from the stream's trailing bytes — the fast
    relay's end-of-stream usage parse (raw bytes; the per-chunk loop never
    re-frames lines).  Matches the slow path's incremental scan: only
    ``\\n``-terminated lines count."""
    lines = tail.split(b"\n")
    for line in reversed(lines[:-1]):
        if line.startswith(b"data: ") and line != b"data: [DONE]":
            return line
    return b""


class GatewayProxy:
    def __init__(
        self,
        handler_server: Server,
        provider,
        datastore: Datastore,
        resilience_cfg=None,
        slo_cfg: "slo_mod.SLOConfig | None" = None,
        health_cfg=None,
        usage_cfg=None,
        fairness_cfg=None,
        placement_cfg=None,
        blackbox_dir: str | None = None,
        fast_relay: bool = True,
        pools: dict | None = None,
        statebus_cfg: "statebus_mod.StateBusConfig | None" = None,
        pickledger_cfg: "pickledger_mod.PickLedgerConfig | None" = None,
        capacity_cfg: "capacity_mod.CapacityConfig | None" = None,
    ):
        self.server = handler_server
        self.provider = provider
        self.datastore = datastore
        self.metrics = GatewayMetrics()
        # Re-export per-replica prefix-cache reuse at the gateway /metrics
        # (the KV-affinity observable; see GatewayMetrics.pool_signals_fn).
        self.metrics.pool_signals_fn = provider.all_pod_metrics
        # Request tracing (tracing.py): bounded span ring served by
        # /debug/traces; sampling/capacity via LIG_TRACE_* env.
        self.tracer = tracing.Tracer()
        # This process's stall clock (gateway_loop_*), run from _on_startup.
        self.loop_clock = tracing.LoopClock()
        self._loop_clock_task: asyncio.Task | None = None
        # ONE flight recorder per gateway process; every pool's advisor
        # stack journals into it (events carry pod/model attributes).
        self.journal = events_mod.EventJournal()
        # Per-pool advisor stacks (gateway/advisors.py).  A single-pool
        # gateway gets exactly one stack over its own provider/scheduler
        # — identical wiring to the historical inline construction.  A
        # multi-pool front (``pools`` = MultiPoolComponents.pools) gets a
        # FULL stack per pool: each pool's scheduler carries its own
        # advisor seams (Python AND native paths) and each pool's handler
        # core its own fairness admit() gate — the PR-7 "enforcement
        # INACTIVE" carve-out is gone.
        self.stacks: dict[str, AdvisorStack] = {}
        if pools:
            for name, comps in pools.items():
                ds = comps.datastore
                self.stacks[name] = AdvisorStack(
                    name, comps.provider,
                    scheduler=comps.scheduler,
                    server=comps.handler_server,
                    metrics=self.metrics, journal=self.journal,
                    resilience_cfg=resilience_cfg, health_cfg=health_cfg,
                    usage_cfg=usage_cfg, fairness_cfg=fairness_cfg,
                    placement_cfg=placement_cfg,
                    pickledger_cfg=pickledger_cfg,
                    capacity_cfg=capacity_cfg,
                    # Scope this pool's admitted-traffic shares to its own
                    # models (the shared GatewayMetrics counts everything).
                    request_filter=(
                        lambda m, _ds=ds: _ds.fetch_model(m) is not None))
                if hasattr(comps.provider, "journal"):
                    comps.provider.journal = self.journal
            self._default_pool = next(iter(pools))
            default = getattr(handler_server, "_default", None)
            if default in self.stacks:
                self._default_pool = default
        else:
            pool_name = "default"
            get_pool = getattr(datastore, "get_pool", None)
            if get_pool is not None:
                try:
                    pool_name = get_pool().name or pool_name
                except Exception:
                    pass
            self.stacks[pool_name] = AdvisorStack(
                pool_name, provider,
                scheduler=getattr(handler_server, "scheduler", None),
                server=handler_server,
                metrics=self.metrics, journal=self.journal,
                resilience_cfg=resilience_cfg, health_cfg=health_cfg,
                usage_cfg=usage_cfg, fairness_cfg=fairness_cfg,
                placement_cfg=placement_cfg,
                pickledger_cfg=pickledger_cfg,
                capacity_cfg=capacity_cfg)
            self._default_pool = pool_name
            # Scrape failures land in the flight recorder (Provider
            # emits, throttled); StaticProvider lacks the attribute.
            if hasattr(provider, "journal"):
                provider.journal = self.journal
        # Back-compat aliases: the default pool's planes under the
        # historical names.  Single-pool deployments (and every existing
        # caller/test) see exactly the old object graph; the data path
        # routes per-pod through ``_stack_for_pod`` so multi-pool fronts
        # feed the RIGHT pool's health scorer and breaker.
        stack = self.stacks[self._default_pool]
        self.health = stack.health
        self.resilience = stack.resilience
        self.usage = stack.usage
        self.kvobs = stack.kvobs
        self.capacity = stack.capacity
        self.fairness = stack.fairness
        self.placement = stack.placement
        self.pickledger = stack.pickledger
        self._pod_stack_cache: dict[str, AdvisorStack] = {}
        # SLO engine stays gateway-wide: it reads the shared
        # GatewayMetrics histograms, which span every pool this process
        # fronts.
        self.slo = slo_mod.SLOEngine(
            self.metrics, cfg=slo_cfg, journal=self.journal,
            on_fast_burn=self._on_fast_burn)
        # Replicated control-plane state bus (gateway/statebus.py): the
        # tick's derived state becomes versioned per-pool snapshots
        # gossiped between gateway replicas; the merged view overlays the
        # stacks' advisors so N gateways share one brain.  Peer-less
        # (the default) it is inert beyond serving /debug/statebus.
        self.statebus = statebus_mod.StateBus(
            self.stacks, cfg=statebus_cfg, journal=self.journal)
        # Fleet observability collector (gateway/fleetobs.py): pulls the
        # peer gateways' (the statebus peer list — the fleet topology is
        # already wired) and every pool pod's debug surfaces through the
        # incremental cursors, stitches cross-replica traces, and serves
        # /debug/fleet.  Peer-less single-pool gateways still get the
        # local+pods view (streaming decode spans live only on pods).
        self.fleet = fleetobs.FleetCollector(
            self.statebus.replica_id,
            peer_urls=self.statebus.cfg.peers,
            pods_fn=self._fleet_pods,
            local_fn=self._fleet_local_payloads,
            journal=self.journal)
        # Black-box dump directory + dump-storm cooldown; both env-tunable.
        self.blackbox_dir = (
            blackbox_dir or os.environ.get("LIG_BLACKBOX_DIR")
            or os.path.join(tempfile.gettempdir(), "lig-blackbox"))
        self._blackbox_cooldown_s = float(
            os.environ.get("LIG_BLACKBOX_COOLDOWN_S", "60"))
        self._last_dump_t = 0.0  # of the last SUCCESSFUL dump
        self._dump_inflight = False
        # Evaluation cadence for the background tick (0 disables the task;
        # /debug/slo and /debug/health still evaluate on demand).
        self.obs_tick_s = float(os.environ.get("LIG_SLO_TICK_S", "5"))
        self._obs_task: asyncio.Task | None = None
        # Strong refs to in-flight KV-release tasks (the event loop only
        # keeps weak ones; see _spawn_release).
        self._release_tasks: set = set()
        self._session: aiohttp.ClientSession | None = None
        # Data-plane fast path: the zero-copy SSE relay.  ``False`` is the
        # line-scanning relay, the byte-parity reference of
        # tests/test_fast_relay.py: a constructor argument with no CLI flag.
        self.fast_relay = fast_relay
        # Preallocated header templates: the per-request mutation copies a
        # template and stamps the request-scoped values instead of
        # rebuilding the static keys on every hop.
        self._sse_headers_tpl = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        }
        self._upstream_headers_tpl = {"Content-Type": "application/json"}

    # -- app wiring --------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/v1/completions", self.handle_completion)
        app.router.add_post("/v1/chat/completions", self.handle_completion)
        app.router.add_get("/metrics", self.handle_metrics)
        app.router.add_get("/debug/traces", self.handle_debug_traces)
        app.router.add_get("/debug/slo", self.handle_debug_slo)
        app.router.add_get("/debug/health", self.handle_debug_health)
        app.router.add_get("/debug/usage", self.handle_debug_usage)
        app.router.add_get("/debug/kv", self.handle_debug_kv)
        app.router.add_get("/debug/capacity", self.handle_debug_capacity)
        app.router.add_get("/debug/picks", self.handle_debug_picks)
        app.router.add_get("/debug/placement", self.handle_debug_placement)
        app.router.add_get("/debug/statebus", self.handle_debug_statebus)
        app.router.add_get("/debug/fleet", self.handle_debug_fleet)
        app.router.add_post("/statebus/exchange",
                            self.handle_statebus_exchange)
        app.router.add_get("/debug/events", self.handle_debug_events)
        app.router.add_get("/healthz", self.handle_health)
        app.router.add_get("/v1/models", self.handle_models)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    async def _on_startup(self, app) -> None:
        # Per-phase timeouts (gateway/resilience.py) replace the old single
        # total timeout: connect is bounded here; TTFT and idle-between-
        # chunks are enforced per request on the data path, so a dead
        # replica fails in seconds while a long healthy stream runs
        # indefinitely.
        rcfg = self.resilience.cfg
        # Per-pod keepalive connection pool: upstream connections are
        # reused across requests (a handshake per request is data-plane
        # tax), with creation/reuse counted per pod through aiohttp's
        # trace hooks — the ``gateway_upstream_connections_total`` family
        # and the reuse-ratio gauge come straight from these two events.
        connector = aiohttp.TCPConnector(
            limit=0, limit_per_host=UPSTREAM_CONNS_PER_POD,
            keepalive_timeout=UPSTREAM_KEEPALIVE_S)
        trace_cfg = aiohttp.TraceConfig()

        async def _conn_created(session, ctx, params) -> None:
            pod = (getattr(ctx, "trace_request_ctx", None) or {}).get("pod")
            self.metrics.record_upstream_conn(pod or "?", reused=False)

        async def _conn_reused(session, ctx, params) -> None:
            pod = (getattr(ctx, "trace_request_ctx", None) or {}).get("pod")
            self.metrics.record_upstream_conn(pod or "?", reused=True)

        trace_cfg.on_connection_create_end.append(_conn_created)
        trace_cfg.on_connection_reuseconn.append(_conn_reused)
        self._session = aiohttp.ClientSession(
            connector=connector,
            timeout=aiohttp.ClientTimeout(
                total=None, connect=rcfg.connect_timeout_s or None),
            trace_configs=[trace_cfg],
        )
        self._loop_clock_task = asyncio.get_running_loop().create_task(
            self.loop_clock.run())
        if self.obs_tick_s > 0:
            self._obs_task = asyncio.get_running_loop().create_task(
                self._observability_loop())

    async def _on_cleanup(self, app) -> None:
        for task in (self._loop_clock_task, self._obs_task):
            if task is not None:
                task.cancel()
        self._loop_clock_task = self._obs_task = None
        if self._session is not None:
            await self._session.close()

    def control_tick(self) -> None:
        """One full control-plane pass: every pool's advisor stack
        (health/breaker, usage shares, fairness quotas, placement), the
        gateway-wide SLO engine, then the statebus snapshot+apply — the
        tick-derived state becomes this replica's published snapshot and
        the freshest peer state overlays the advisors.  Synchronous (no
        I/O): chaos and tests drive it explicitly; peer exchange is the
        async half in ``_observability_loop``."""
        for stack in self.stacks.values():
            stack.tick()
        self.slo.tick()
        self.statebus.tick()
        # Prune the pod->stack route cache against live membership (the
        # breaker.prune pattern): pod names are never reused, so without
        # this the cache grows monotonically under membership churn.
        if len(self.stacks) > 1 and self._pod_stack_cache:
            live = set()
            for stack in self.stacks.values():
                live |= stack.pod_names()
            for name in [n for n in self._pod_stack_cache
                         if n not in live]:
                del self._pod_stack_cache[name]

    async def _observability_loop(self) -> None:
        """Background evaluation tick: per-pool advisor stacks first
        (cheap, feed the journal), the SLO engine (may fire the black-box
        dump), the statebus snapshot/merge, then the peer push-pull
        exchange."""
        while True:
            await asyncio.sleep(self.obs_tick_s)
            try:
                self.control_tick()
            except Exception:
                logger.exception("observability tick failed")
            try:
                if self.statebus.cfg.peers and self._session is not None:
                    await self.statebus.exchange(self._session)
                    self.statebus.apply()  # fold what the exchange brought
            except Exception:
                logger.exception("statebus exchange failed")

    def _on_fast_burn(self, model: str, objective: str, burns: dict) -> None:
        """SLO fast-burn hook: snapshot everything into a black-box dump
        (rate-limited — a breach across N models must not write N dumps a
        second) and journal where it went.

        The file write runs OFF the event loop when one is running: a
        fast burn is exactly when the gateway is already degraded, and a
        multi-MB synchronous dump to slow disk would stall every in-flight
        request.  The cooldown stamps only on SUCCESS — a failed write
        (disk full, unwritable dir) retries on the next breach tick before
        the pre-incident journal rotates out."""
        now = time.time()
        if (self._dump_inflight
                or now - self._last_dump_t < self._blackbox_cooldown_s):
            return
        self._dump_inflight = True
        reason = {"trigger": "fast_burn", "model": model,
                  "objective": objective,
                  "burns": {k: (round(v, 3) if v is not None else None)
                            for k, v in burns.items()}}

        def write() -> None:
            try:
                # Pod profiler snapshots: best-effort bounded fetches off
                # the event loop (this runs in the executor) — a wedged
                # pod costs one timeout, never the dump.
                pods = self._fleet_pods()
                profiles = fleetobs.collect_pod_payloads(
                    pods, "/debug/profile", thread_name="blackbox-profile")
                # KV economy at dump time: the gateway rollup (refreshed —
                # the breach may predate the last observability tick) plus
                # each pod's raw ledger snapshot; unreachable pods degrade
                # to error markers, never a lost dump.
                self.kvobs.maybe_tick(max(1.0, self.obs_tick_s))
                kv_payload = {
                    "gateway": self.kvobs.debug_payload(),
                    "pods": fleetobs.collect_pod_payloads(
                        pods, "/debug/kv", thread_name="blackbox-kv"),
                }
                # Twin state at dump time: saturation, forecasts and the
                # drift trust flag — was capacity exhaustion forecast, and
                # was the forecast trusted, when the burn hit?
                capacity_payload = None
                if self.capacity.cfg.enabled:
                    self.capacity.maybe_tick(max(1.0, self.obs_tick_s))
                    capacity_payload = {
                        name: stack.capacity.debug_payload()
                        for name, stack in self.stacks.items()}
                # Decision records at dump time: the last sampled picks
                # per pool — "why were requests landing where they were in
                # the 30s before the breach" (tools/blackbox_report.py
                # renders the funnel + decisive seams).
                picks_payload = {
                    name: pickledger_mod.debug_picks_payload(
                        stack.pickledger, {"limit": "64"})
                    for name, stack in self.stacks.items()}
                path = slo_mod.write_blackbox(
                    self.blackbox_dir, reason, journal=self.journal,
                    tracer=self.tracer, metrics_text=self._render_metrics(),
                    slo_payload=self.slo.debug_payload(),
                    health_payload=self.health.debug_payload(),
                    usage_payload=self.usage.debug_payload(),
                    statebus_payload=self.statebus.debug_payload(),
                    profile_payload=profiles,
                    kv_payload=kv_payload,
                    picks_payload=picks_payload,
                    capacity_payload=capacity_payload)
                self._last_dump_t = time.time()
                self.journal.emit(events_mod.BREACH_DUMP, model=model,
                                  objective=objective, path=path)
                logger.warning(
                    "SLO fast burn (%s/%s): black-box dump written to %s",
                    model, objective, path)
            except OSError:
                logger.exception("black-box dump failed")
            finally:
                self._dump_inflight = False

        try:
            asyncio.get_running_loop().run_in_executor(None, write)
        except RuntimeError:
            write()  # synchronous contexts (tests, CLI tools)

    # -- fleet observability seams -----------------------------------------
    def _fleet_pods(self) -> list:
        """Live ``(pod_name, address)`` membership across every pool this
        gateway fronts — the fleet collector's pod source list."""
        out = []
        for stack in self.stacks.values():
            for pm in stack.provider.all_pod_metrics():
                out.append((pm.pod.name, pm.pod.address))
        return out

    def _fleet_local_payloads(self) -> dict:
        """This replica's own debug payloads, handed to the fleet
        collector without an HTTP round trip to ourselves."""
        # The journal pages OLDEST-first from a cursor: anchor the cursor
        # 512 rows behind the head so the fleet view carries the NEWEST
        # local events (the pre-breach window), not the ring's stale tail.
        events_since = max(0, self.journal.seq - 512)
        return {
            "traces": tracing.debug_traces_payload(
                self.tracer, {"limit": "256"}),
            "events": events_mod.debug_events_payload(
                self.journal, {"since": str(events_since), "limit": "512"}),
            "slo": self.slo.debug_payload(),
            "health": self.health.debug_payload(),
        }

    # -- per-pool routing of data-path signals -----------------------------
    def _stack_for_pod(self, pod_name: str) -> AdvisorStack:
        """The advisor stack owning ``pod_name``.  Single-pool fronts
        short-circuit to the only stack; multi-pool lookups are cached
        (pods never migrate between pools — membership churn only adds
        names)."""
        if len(self.stacks) == 1:
            return self.stacks[self._default_pool]
        stack = self._pod_stack_cache.get(pod_name)
        if stack is not None:
            return stack
        for stack in self.stacks.values():
            if pod_name in stack.pod_names():
                self._pod_stack_cache[pod_name] = stack
                return stack
        return self.stacks[self._default_pool]

    def _record_upstream(self, pod_name: str, ok: bool,
                         timeout: bool = False) -> None:
        """Route an upstream outcome to the owning pool's resilience plane
        (health scorer + circuit breaker)."""
        self._stack_for_pod(pod_name).resilience.record_upstream(
            pod_name, ok, timeout=timeout)

    def _record_handoff(self, pod_name: str, ok: bool) -> None:
        self._stack_for_pod(pod_name).resilience.record_handoff(
            pod_name, ok)

    # -- request path ------------------------------------------------------
    def _error_response(self, status: int, message: str, kind: str,
                        trace_id: str,
                        headers: dict | None = None) -> web.Response:
        """Error envelope with the trace id in BOTH the body and the header
        — failed requests are the ones most worth correlating.  429s get a
        ``Retry-After`` hint (graceful-degradation contract: shed clients
        back off instead of hammering a saturated pool)."""
        all_headers = {tracing.TRACE_HEADER: trace_id, **(headers or {})}
        if status == 429 and "Retry-After" not in all_headers:
            all_headers["Retry-After"] = str(
                max(1, int(self.fairness.cfg.retry_after_s)))
        return web.json_response(
            {"error": {"message": message, "type": kind,
                       "trace_id": trace_id}},
            status=status,
            headers=all_headers,
        )

    @staticmethod
    def _body_ttft_s(resp_body: bytes) -> float | None:
        """Server-reported first-token latency from a completions envelope
        (``ttft_ms``), as seconds — None when the envelope doesn't carry it
        (chat)."""
        try:
            v = json.loads(resp_body).get("ttft_ms")
            return float(v) / 1e3 if v is not None else None
        except (json.JSONDecodeError, ValueError, AttributeError, TypeError):
            return None

    def _finish_phase(self, req_ctx, trace_id: str, path: str, t_req: float,
                      t_first: float | None, t_last: float,
                      status: str = "ok") -> None:
        """Observe a finished request into the gateway TTFT/TPOT/e2e
        histograms and stamp the trace's summary fields.

        ``t_first`` is the wall clock at which the FIRST generated token
        existed (stream: first data chunk; JSON: server-reported ttft or
        prefill-hop completion); TPOT spreads the remaining wall over the
        remaining tokens.  ``status`` rides the trace summary (e.g.
        ``client_disconnect`` for a partially-delivered stream — the
        observation still lands in the histograms, so e2e percentiles see
        the aborted request).
        """
        model = req_ctx.model or "?"
        completion = req_ctx.usage.completion_tokens
        ttft_s = (t_first - t_req) if t_first else None
        tpot_s = None
        if t_first and completion > 1:
            tpot_s = max(0.0, t_last - t_first) / (completion - 1)
        self.metrics.record_phase(model, path, ttft_s, tpot_s,
                                  e2e_s=t_last - t_req)
        self.tracer.annotate(trace_id, model=model, path=path, status=status)

    async def handle_completion(self, request: web.Request) -> web.Response:
        # Taken before the body is read: the read is part of what the hop
        # adds to first-token time (``pre_s`` of ``gateway.stream``).
        t_req = time.time()
        body = await request.read()
        req_ctx = RequestContext(loop_marks=self.loop_clock.marks())
        # Request-scoped tracing: honor an inbound id or mint one; it rides
        # to the replica and back so one id follows the request across the
        # gateway, the scheduler decision, and the model server (SURVEY.md
        # §5: the reference's only decision-path observability was verbose
        # logs; this is the structured equivalent).
        request_id = request.headers.get("x-request-id") or uuid.uuid4().hex[:16]
        trace_id = (request.headers.get(tracing.TRACE_HEADER)
                    or tracing.new_trace_id())
        req_ctx.trace_id = trace_id
        loop = asyncio.get_running_loop()
        rcfg = self.resilience.cfg
        # Hedging is for non-streaming requests only (two live SSE relays
        # for one client are unmergeable); the flag lives in the body, so
        # parse it only when hedging is enabled at all.
        hedge_ok = False
        if rcfg.hedge_ttft_s > 0:
            try:
                hedge_ok = not json.loads(body).get("stream", False)
            except (json.JSONDecodeError, AttributeError, UnicodeDecodeError):
                hedge_ok = False

        # Phase 1: headers through the same core the gRPC transport uses.
        self.server.process(req_ctx, RequestHeaders(headers=dict(request.headers)))

        # Phase 2 + forward, as a bounded retry loop: each attempt re-runs
        # admission + pick (so a failure recorded on the previous attempt
        # steers the re-pick under health_policy=avoid) and one upstream
        # forward.  Only failures where NO byte has reached the client are
        # retried, every retry spends the global retry budget, and backoff
        # is decorrelated jitter — retries cannot amplify an outage.
        attempt = 0
        backoff_s = 0.0
        while True:
            # Scheduling is CPU-only (no I/O) but can walk a large pool;
            # run in executor to keep the event loop responsive.
            try:
                with Timer() as t:
                    result = await loop.run_in_executor(
                        None, self.server.process, req_ctx,
                        RequestBody(body=body)
                    )
            except ProcessingError as e:
                self.metrics.record_error(req_ctx.model or None,
                                          pre_admission=True)
                self.journal.emit(events_mod.ADMISSION_REJECT, trace_id,
                                  model=req_ctx.model or "", status=e.status,
                                  error=str(e)[:200])
                self.tracer.record(trace_id, "gateway.admission", t_req,
                                   time.time(), error=str(e))
                self.tracer.annotate(trace_id, model=req_ctx.model or "",
                                     status="error")
                kind = ("invalid_request_error" if e.status == 400
                        else "api_error")
                return self._error_response(e.status, str(e), kind, trace_id)
            if attempt == 0:
                self.metrics.record_request(req_ctx.model or "?")
                self.resilience.retry_budget.note_request()
            if result.immediate_status is not None:
                self.metrics.record_shed(req_ctx.model or None)
                self.journal.emit(events_mod.SHED, trace_id,
                                  model=req_ctx.model or "",
                                  status=result.immediate_status)
                self.tracer.record(trace_id, "gateway.admission", t_req,
                                   time.time(), shed=True)
                self.tracer.annotate(trace_id, model=req_ctx.model or "",
                                     status="shed")
                return self._error_response(
                    result.immediate_status,
                    "dropping request due to limited backend resources",
                    "rate_limit_exceeded", trace_id)

            pod = req_ctx.target_pod
            affinity_hit = False
            pm = (self.provider.get_pod_metrics(pod.name)
                  if hasattr(self.provider, "get_pod_metrics") else None)
            if pm is not None:
                affinity_hit = (req_ctx.resolved_target_model
                                in pm.metrics.active_adapters)
            self.metrics.record_pick(pod.name, t.seconds, affinity_hit)
            # One span covers admission + scheduler pick (the pick's own
            # cost rides as an attribute — it is also a full histogram
            # family).  Queue-wait and per-hop pick splits attribute a slow
            # admission to admission-queue parking vs prefill-hop vs
            # decode-hop pick cost.
            attribution = {}
            if req_ctx.admission_wait_s:
                attribution["queue_wait_s"] = round(req_ctx.admission_wait_s, 6)
            if req_ctx.pick_hops_s is not None:
                attribution["pick_prefill_s"] = round(req_ctx.pick_hops_s[0], 6)
                attribution["pick_decode_s"] = round(req_ctx.pick_hops_s[1], 6)
            if attempt:
                attribution["attempt"] = attempt
            self.tracer.record(trace_id, "gateway.admission", t_req,
                               time.time(), pod=pod.name,
                               pick_s=round(t.seconds, 6), **attribution)

            # Forward to the picked replica (Envoy's ORIGINAL_DST role).
            out_body = result.body if result.body is not None else body
            decode_pod = getattr(req_ctx, "decode_pod", None)
            self.journal.emit(
                events_mod.PICK, trace_id, model=req_ctx.model or "",
                pod=pod.name,
                **({"decode_pod": decode_pod.name} if decode_pod else {}),
                **({"attempt": attempt} if attempt else {}))
            if decode_pod is not None:
                # Disaggregated pick: relay prefill -> handoff -> decode.
                resp = await self._disagg_forward(
                    request, pod, decode_pod, out_body, request_id, req_ctx,
                    trace_id, t_req)
                if resp is not None:
                    return resp
                # Either hop refused (draining, long prompt, unsupported
                # params): serve single-hop on the prefill replica — every
                # engine is complete regardless of role.
                self.journal.emit(events_mod.DISAGG_FALLBACK, trace_id,
                                  model=req_ctx.model or "",
                                  prefill_pod=pod.name,
                                  decode_pod=decode_pod.name)
                logger.info("request=%s disaggregated path unavailable; "
                            "single-hop on %s", request_id, pod.name)

            resp, failure = await self._forward_collocated(
                request, pod, body, out_body, request_id, req_ctx, trace_id,
                t_req, hedge_ok=hedge_ok and decode_pod is None)
            if resp is not None:
                return resp

            # Retry-eligible failure: nothing reached the client yet.
            if (attempt >= rcfg.max_retries
                    or not self.resilience.retry_budget.try_spend()):
                self.metrics.record_error(req_ctx.model or None)
                self.tracer.annotate(trace_id, status="upstream_error")
                status = 504 if "timeout" in failure else 502
                return self._error_response(
                    status,
                    f"upstream {failure} after {attempt + 1} attempt(s)",
                    "api_error", trace_id)
            attempt += 1
            self.metrics.record_retry(failure)
            self.journal.emit(events_mod.RETRY, trace_id, pod=pod.name,
                              reason=failure, attempt=attempt)
            backoff_s = retry_backoff(
                self.resilience.rng, backoff_s or rcfg.backoff_base_s,
                rcfg.backoff_base_s, rcfg.backoff_cap_s)
            await asyncio.sleep(backoff_s)

    @staticmethod
    async def _bounded(awaitable, timeout_s: float):
        """Await with an optional bound (0 disables) — every upstream
        await on the data path goes through a per-phase limit; an
        unbounded hop would resurrect the hung-request failure mode the
        per-phase timeouts exist to kill."""
        if timeout_s and timeout_s > 0:
            return await asyncio.wait_for(awaitable, timeout_s)
        return await awaitable

    async def _post_upstream(self, path: str, pod, out_body: bytes,
                             request_id: str, trace_id: str):
        """POST to one replica, bounded by the TTFT timeout: the await
        resolves when response HEADERS are up (SSE: immediately; JSON: when
        generation finished server-side).  Raises asyncio.TimeoutError /
        aiohttp.ClientError for the caller to classify."""
        ttft = self.resilience.cfg.ttft_timeout_s
        headers = dict(self._upstream_headers_tpl)
        headers["x-request-id"] = request_id
        headers[tracing.TRACE_HEADER] = trace_id
        headers[self.server.target_pod_header] = pod.address
        coro = self._session.post(
            f"http://{pod.address}{path}",
            data=out_body,
            headers=headers,
            trace_request_ctx={"pod": pod.name},
        )
        return await (asyncio.wait_for(coro, ttft) if ttft > 0 else coro)

    def _repick_pod(self, body: bytes, exclude: str,
                    demoted_to: str | None = None):
        """Scheduler re-pick for a hedge, on a throwaway context (runs in
        the executor).  None when admission fails or the pick lands on the
        pod already being hedged against."""
        ctx = RequestContext()
        # A hedge probe must not spend the tenant's quota bucket again —
        # the primary attempt already charged this client request — and
        # must keep the primary's demotion: hedges fire under exactly the
        # saturation quotas target, so an undemoted probe would restore
        # the priority the quota removed.
        ctx.fairness_charged = True
        ctx.fairness_demoted_to = demoted_to
        try:
            result = self.server.process(ctx, RequestBody(body=body))
        except ProcessingError:
            return None
        if result.immediate_status is not None or ctx.target_pod is None:
            return None
        return None if ctx.target_pod.name == exclude else ctx.target_pod

    async def _post_with_hedge(self, request, pod, raw_body: bytes,
                               out_body: bytes, request_id: str,
                               trace_id: str,
                               demoted_to: str | None = None):
        """TTFT-based hedge: when the primary hasn't produced response
        headers within ``hedge_ttft_s``, re-pick a different replica and
        race a second identical request; first success wins, the loser is
        cancelled.  Returns (upstream, winning_pod, outcome)."""
        primary = asyncio.ensure_future(
            self._post_upstream(request.path, pod, out_body, request_id,
                                trace_id))
        done, _ = await asyncio.wait(
            {primary}, timeout=self.resilience.cfg.hedge_ttft_s)
        if done:
            return primary.result(), pod, None  # may raise; caller classifies
        loop = asyncio.get_running_loop()
        hedge_pod = await loop.run_in_executor(
            None, self._repick_pod, raw_body, pod.name, demoted_to)
        if hedge_pod is None:
            self.metrics.record_hedge("no_candidate")
            return (await primary), pod, None
        self.metrics.record_hedge("fired")
        self.journal.emit(events_mod.HEDGE, trace_id, pod=pod.name,
                          hedge_pod=hedge_pod.name)
        hedge = asyncio.ensure_future(
            self._post_upstream(request.path, hedge_pod, out_body,
                                request_id, trace_id))
        owner = {primary: pod, hedge: hedge_pod}
        pending = set(owner)
        while pending:
            done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED)
            winners = [tk for tk in done
                       if not tk.cancelled() and tk.exception() is None]
            if not winners:
                continue  # this round only produced failures; wait the rest
            winner = primary if primary in winners else winners[0]
            for tk in set(owner) - {winner}:
                if tk.done() and not tk.cancelled():
                    if tk.exception() is None:
                        # The loser also answered: its success still counts
                        # (clears streaks / half-open probe accounting).
                        self._record_upstream(owner[tk].name,
                                                        ok=True)
                        tk.result().close()
                    else:
                        # The loser's failure still reaches the breaker.
                        self._record_upstream(
                            owner[tk].name, ok=False,
                            timeout=isinstance(tk.exception(),
                                               asyncio.TimeoutError))
                else:
                    tk.cancel()
            outcome = "won" if winner is hedge else "lost"
            self.metrics.record_hedge(outcome)
            return winner.result(), owner[winner], outcome
        # Both attempts failed: surface the primary's error (the caller's
        # pod attribution matches), after recording the hedge-side failure.
        self.metrics.record_hedge("failed")
        self._record_upstream(
            hedge_pod.name, ok=False,
            timeout=isinstance(hedge.exception(), asyncio.TimeoutError))
        raise primary.exception()

    async def _forward_collocated(self, request, pod, raw_body: bytes,
                                  out_body: bytes, request_id: str, req_ctx,
                                  trace_id: str, t_req: float,
                                  hedge_ok: bool = False):
        """One single-hop forward attempt.

        Returns ``(response, None)`` when a client-ready response exists
        (success, streamed, or a passthrough non-503 upstream status), or
        ``(None, reason)`` for a retry-eligible failure — exactly the set
        where no byte has reached the client: connect errors, TTFT
        timeouts, 503s, and failed non-streaming body reads.
        """
        rcfg = self.resilience.cfg
        t_up0 = time.time()
        hedge_outcome = None

        def _failed(reason: str, err, timeout: bool = False):
            self._record_upstream(pod.name, ok=False,
                                            timeout=timeout)
            self.journal.emit(events_mod.UPSTREAM_ERROR, trace_id,
                              pod=pod.name, reason=reason,
                              error=str(err)[:200])
            self.tracer.record(trace_id, "gateway.upstream", t_up0,
                               time.time(), pod=pod.name, error=str(err))
            logger.warning("upstream %s failed (%s): %s",
                           pod.address, reason, err)
            return None, reason

        try:
            if hedge_ok:
                upstream, pod, hedge_outcome = await self._post_with_hedge(
                    request, pod, raw_body, out_body, request_id, trace_id,
                    demoted_to=req_ctx.fairness_demoted_to)
            else:
                upstream = await self._post_upstream(
                    request.path, pod, out_body, request_id, trace_id)
        except asyncio.TimeoutError as e:
            return _failed("ttft_timeout", str(e) or "ttft timeout",
                           timeout=True)
        except (aiohttp.ClientError, ConnectionResetError, OSError) as e:
            return _failed("connect", e)
        status = upstream.status
        try:
            if status == 503:
                # Draining / queue-full replica: the canonical idempotent
                # retry case (no generation happened).
                upstream.release()
                return _failed("upstream_503", "upstream answered 503")
            if "text/event-stream" in upstream.headers.get("Content-Type", ""):
                # Streamed generation: relay SSE chunks as they arrive —
                # buffering would defeat streaming, and usage accounting
                # happens from the stream's final chunk if present.  A
                # stream that dies BEFORE its first chunk comes back as a
                # retry-eligible failure (already recorded by the relay).
                return await self._relay_stream(
                    request, upstream, pod, req_ctx,
                    trace=(trace_id, t_req, "collocated", t_up0))
            idle = rcfg.stream_idle_timeout_s
            resp_body = await (asyncio.wait_for(upstream.read(), idle)
                               if idle > 0 else upstream.read())
            self.tracer.record_wire(
                trace_id, upstream.headers.get(tracing.SPANS_HEADER))
        except asyncio.TimeoutError as e:
            upstream.close()
            return _failed("read_timeout", str(e) or "body read timeout",
                           timeout=True)
        except (aiohttp.ClientError, ConnectionResetError, OSError) as e:
            upstream.close()
            return _failed("read", e)
        t_up1 = time.time()
        # 5xx from the replica counts against its health (the server
        # answered, but wrongly); 2xx-4xx reset the error streak.
        self._record_upstream(pod.name, ok=status < 500)
        self.tracer.record(trace_id, "gateway.upstream", t_up0, t_up1,
                           pod=pod.name, status=status,
                           **({"hedge": hedge_outcome} if hedge_outcome
                              else {}))

        # Phases 3+4: response headers + usage accounting.
        hdr_result = self.server.process(req_ctx, ResponseHeaders())
        try:
            self.server.process(req_ctx, ResponseBody(body=resp_body))
            self.metrics.record_usage(
                req_ctx.model,
                req_ctx.usage.prompt_tokens,
                req_ctx.usage.completion_tokens,
            )
        except ProcessingError:
            pass  # non-JSON upstream bodies skip accounting

        server_ttft = self._body_ttft_s(resp_body)
        self._finish_phase(
            req_ctx, trace_id, "collocated", t_req,
            t_first=(t_up0 + server_ttft) if server_ttft is not None else None,
            t_last=t_up1)
        logger.info(
            "request=%s trace=%s model=%s target=%s pod=%s status=%d "
            "prompt_tokens=%d completion_tokens=%d total_ms=%.1f",
            request_id, trace_id, req_ctx.model, req_ctx.resolved_target_model,
            pod.name, status, req_ctx.usage.prompt_tokens,
            req_ctx.usage.completion_tokens, (time.time() - t_req) * 1e3,
        )
        headers = {"x-served-by": pod.name, "x-request-id": request_id,
                   tracing.TRACE_HEADER: trace_id, **hdr_result.set_headers}
        return web.Response(body=resp_body, status=status, headers=headers,
                            content_type="application/json"), None

    async def _disagg_forward(self, request: web.Request, prefill_pod,
                              decode_pod, out_body: bytes, request_id: str,
                              req_ctx, trace_id: str,
                              t_req: float) -> web.StreamResponse | None:
        """Two-hop data path for a disaggregated pick.

        Hop 1 posts the (possibly rewritten) body to the prefill replica's
        ``/v1/prefill`` and receives the serialized ``PrefillHandoff``;
        hop 2 posts it to the decode replica's ``/v1/attach``, which decodes
        to completion and answers in the normal OpenAI envelope (SSE
        included).  Returns None to signal single-hop fallback — any 4xx/5xx
        from either hop (draining replica, prompt beyond the prefill bucket,
        params the handoff path doesn't carry) degrades gracefully rather
        than failing the request.

        Tracing: both hops get their own gateway-side spans, and each hop's
        ``x-lig-spans`` response header (engine queue/prefill, handoff
        serialize/deserialize/attach, decode) merges into the SAME trace —
        the proxy's /debug/traces shows the full three-process timeline.
        """
        t_pre0 = time.time()
        hop_pod = prefill_pod  # which hop an exception below attributes to
        engine_req_id = None  # the prefill engine's id, for abandon-release
        rcfg = self.resilience.cfg
        resp_obj = None  # in-flight hop response, closed on failure
        try:
            # Both hops ride the per-phase bounds: response headers within
            # the TTFT budget, body within the idle budget — a blackholed
            # replica must degrade this request to single-hop in bounded
            # time, not hang it (the single total timeout is gone).
            pre = resp_obj = await self._bounded(
                self._session.post(
                    f"http://{prefill_pod.address}/v1/prefill",
                    data=out_body,
                    headers={"Content-Type": "application/json",
                             "x-request-id": request_id,
                             tracing.TRACE_HEADER: trace_id},
                    trace_request_ctx={"pod": prefill_pod.name},
                ), rcfg.ttft_timeout_s)
            if pre.status != 200:
                logger.warning(
                    "prefill hop %s returned %d; falling back",
                    prefill_pod.address, pre.status)
                pre.release()
                self._record_handoff(prefill_pod.name, ok=False)
                self.tracer.record(
                    trace_id, "gateway.prefill_hop", t_pre0, time.time(),
                    pod=prefill_pod.name, status=pre.status,
                    fallback=True)
                return None
            handoff = await self._bounded(pre.read(),
                                          rcfg.stream_idle_timeout_s)
            engine_req_id = pre.headers.get("x-request-id")
            self.tracer.record_wire(
                trace_id, pre.headers.get(tracing.SPANS_HEADER))
            t_pre1 = time.time()
            self.tracer.record(trace_id, "gateway.prefill_hop", t_pre0,
                               t_pre1, pod=prefill_pod.name,
                               wire_bytes=len(handoff))
            t_att0 = time.time()
            hop_pod = decode_pod
            upstream = resp_obj = await self._bounded(
                self._session.post(
                    f"http://{decode_pod.address}/v1/attach",
                    data=handoff,
                    headers={"Content-Type": "application/octet-stream",
                             "x-request-id": request_id,
                             tracing.TRACE_HEADER: trace_id},
                    trace_request_ctx={"pod": decode_pod.name},
                ), rcfg.ttft_timeout_s)
            status = upstream.status
            if status != 200:
                logger.warning(
                    "attach hop %s returned %d; falling back",
                    decode_pod.address, status)
                upstream.release()
                self._record_handoff(decode_pod.name, ok=False)
                self.tracer.record(
                    trace_id, "gateway.attach_hop", t_att0, time.time(),
                    pod=decode_pod.name, status=status, fallback=True)
                return None
            if "text/event-stream" in upstream.headers.get(
                    "Content-Type", ""):
                resp, fail = await self._relay_stream(
                    request, upstream, decode_pod, req_ctx,
                    trace=(trace_id, t_req, "disaggregated", t_att0),
                    served_by=f"{prefill_pod.name}+{decode_pod.name}")
                if resp is not None:
                    return resp
                # The attach stream died before its first chunk: the
                # decode engine holds abandoned work — release it and
                # fall back single-hop (nothing reached the client).
                self._record_handoff(decode_pod.name, ok=False)
                if engine_req_id:
                    self._spawn_release(decode_pod, engine_req_id, trace_id)
                self.tracer.record(
                    trace_id, "gateway.attach_hop", t_att0, time.time(),
                    pod=decode_pod.name, fallback=True, error=fail)
                return None
            resp_body = await self._bounded(upstream.read(),
                                            rcfg.stream_idle_timeout_s)
            self.tracer.record_wire(
                trace_id, upstream.headers.get(tracing.SPANS_HEADER))
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            if resp_obj is not None:
                resp_obj.close()
            # No record_error here: the caller serves the request single-hop
            # next, and THAT path records the request's actual outcome — a
            # recovered hop must not inflate the error rate (non-200 hop
            # statuses above are treated identically).  The health scorer
            # and breaker DO see it: hop failures are a per-replica
            # degradation signal regardless of the request's final outcome.
            self._record_handoff(hop_pod.name, ok=False)
            if hop_pod is decode_pod and engine_req_id:
                # The decode hop died AFTER the handoff bytes were posted:
                # the decode engine may have parked (or be decoding) KV
                # nobody will ever read — the caller reroutes single-hop
                # next.  Best-effort release of the abandoned work; the
                # engine-side TTL sweep (--handoff-ttl-s) is the backstop
                # when this message is lost too.
                self._spawn_release(decode_pod, engine_req_id, trace_id)
            logger.warning("disaggregated path %s->%s failed: %s",
                           prefill_pod.address, decode_pod.address, e)
            return None
        t_att1 = time.time()
        self._record_handoff(prefill_pod.name, ok=True)
        self._record_handoff(decode_pod.name, ok=True)
        self.tracer.record(trace_id, "gateway.attach_hop", t_att0, t_att1,
                           pod=decode_pod.name, status=status)
        hdr_result = self.server.process(req_ctx, ResponseHeaders())
        try:
            self.server.process(req_ctx, ResponseBody(body=resp_body))
            self.metrics.record_usage(
                req_ctx.model,
                req_ctx.usage.prompt_tokens,
                req_ctx.usage.completion_tokens,
            )
        except ProcessingError:
            pass
        # TTFT on the two-hop path: the first token exists the moment the
        # prefill hop returns (it rides the handoff's sampling carry).
        self._finish_phase(req_ctx, trace_id, "disaggregated", t_req,
                           t_first=t_pre1, t_last=t_att1)
        logger.info(
            "request=%s trace=%s model=%s disaggregated prefill=%s decode=%s "
            "status=%d prompt_tokens=%d completion_tokens=%d",
            request_id, trace_id, req_ctx.model, prefill_pod.name,
            decode_pod.name, status, req_ctx.usage.prompt_tokens,
            req_ctx.usage.completion_tokens,
        )
        headers = {
            "x-served-by": f"{prefill_pod.name}+{decode_pod.name}",
            "x-request-id": request_id,
            tracing.TRACE_HEADER: trace_id,
            **hdr_result.set_headers,
        }
        return web.Response(body=resp_body, status=status, headers=headers,
                            content_type="application/json")

    def _spawn_release(self, pod, engine_req_id: str,
                       trace_id: str) -> None:
        """Fire-and-forget ``POST /v1/prefill/release`` at ``pod``: cancel
        work abandoned by a failed hop (queued / parked / decoding KV whose
        response path is gone).  Journaled either way — the release is
        best-effort, the flight recorder is the audit trail."""

        async def release() -> None:
            ok = False
            try:
                # Bounded: the pod being released is the one that just
                # failed — an unbounded POST at it would pin this task for
                # the life of the process.
                async with await asyncio.wait_for(
                    self._session.post(
                        f"http://{pod.address}/v1/prefill/release",
                        json={"request_id": engine_req_id},
                        headers={tracing.TRACE_HEADER: trace_id},
                        trace_request_ctx={"pod": pod.name},
                    ), timeout=5.0,
                ) as r:
                    ok = (r.status == 200
                          and bool((await r.json()).get("released")))
            except Exception:  # best-effort: a failed release must never
                pass           # surface as an unhandled task exception
            self.journal.emit(events_mod.KV_RELEASE, trace_id, pod=pod.name,
                              request_id=engine_req_id, released=ok)

        # The loop holds only a weak ref to tasks: keep a strong one until
        # completion or the release can be garbage-collected mid-flight.
        task = asyncio.get_running_loop().create_task(release())
        self._release_tasks.add(task)
        task.add_done_callback(self._release_tasks.discard)

    def _client_disconnected(self, req_ctx, pod, trace_id, t_req, path,
                             t_up0, t_first) -> None:
        """Mid-stream client disconnect accounting: journal the event,
        count it, and observe the PARTIAL request into the e2e histograms
        with the trace summary stamped ``client_disconnect`` — previously
        these requests vanished from every aggregate."""
        now = time.time()
        self.metrics.record_client_disconnect(req_ctx.model or None)
        self.journal.emit(events_mod.CLIENT_DISCONNECT, trace_id or "",
                          pod=pod.name, model=req_ctx.model or "")
        logger.info("client disconnected mid-stream (pod=%s)", pod.name)
        if trace_id:
            self.tracer.record(trace_id, "gateway.stream", t_up0, now,
                               pod=pod.name, client_disconnect=True)
            self._finish_phase(req_ctx, trace_id, path, t_req,
                               t_first=t_first, t_last=now,
                               status="client_disconnect")

    async def _relay_stream(self, request: web.Request, upstream, pod,
                            req_ctx, trace=None,
                            served_by: str | None = None):
        """Relay an SSE stream.  Returns ``(response, None)`` once any byte
        has been committed to the client, or ``(None, reason)`` when the
        stream died BEFORE its first chunk — that failure is still
        retry-eligible, so the 200 headers must not be sent yet (a
        committed stream that later breaks is terminated with the error
        event + [DONE] instead; bubbling up would make the handler try to
        send a second response).

        Two relay modes, byte-parity pinned by tests/test_fast_relay.py:

        - **fast** (default, ``self.fast_relay``): zero-copy — every
          upstream chunk is written to the client verbatim with NO
          per-chunk decode/split/re-encode; the only per-chunk work is
          appending a chunk *reference* to a bounded tail deque.  The
          final usage chunk and ``[DONE]`` exclusion are parsed ONCE at
          stream end from the raw tail bytes (``final_data_line``).
        - **slow** (the parity oracle, reachable from tests only):
          SSE lines are re-framed through a byte buffer per chunk so a
          data line split across transport chunks still parses.

        Per-phase timeouts: the FIRST chunk is bounded by ``ttft_timeout_s``
        and every later inter-chunk gap by ``stream_idle_timeout_s`` — a
        braking replica fails or terminates in bounded time instead of
        hanging the client for the old 3600 s total.

        A ``ConnectionResetError`` (or handler-task cancellation) from the
        client side is journaled as ``client_disconnect``, counted, and
        the partial request still lands in the e2e histograms.

        ``trace`` = (trace_id, t_req, path, t_up0): streaming is where real
        client-observed TTFT/TPOT live — the first relayed data chunk stamps
        TTFT (when its write to the client returned), the final chunk closes
        the stream span and TPOT spreads over the final usage count.  The
        ``gateway.stream`` record of a stream that ran to its end carries
        the hop's parts: ``pre_s`` (``t_req`` -> the POST was issued),
        ``first_chunk_s`` (POST -> first chunk received), ``ttft_s``
        (``t_req`` -> first chunk written: what ``gateway_ttft_seconds``
        observes), ``relay_mean_s`` / ``relay_max_s`` (per chunk: received
        -> written), ``chunks``, and from the stall clock over the
        request's life ``loop_lag_s`` (mean lag a tick) and ``stall_s``.
        """
        trace_id, t_req, path, t_up0 = trace or (None, 0.0, "collocated", 0.0)
        rcfg = self.resilience.cfg
        chunks = upstream.content.iter_any()
        # First chunk BEFORE prepare(): until a byte is relayed, a dead
        # stream is an idempotent failure the caller may retry/reroute —
        # committing 200 headers here would forfeit that.
        pending = None
        try:
            pending = await self._bounded(chunks.__anext__(),
                                          rcfg.ttft_timeout_s)
            t_recv = t_recv0 = time.time()
        except StopAsyncIteration:
            pending = None  # legitimate empty stream: relay it as-is
        except asyncio.TimeoutError:
            upstream.close()
            self._record_upstream(pod.name, ok=False, timeout=True)
            self.journal.emit(events_mod.UPSTREAM_ERROR, trace_id or "",
                              pod=pod.name, stream=True,
                              error="no first chunk within TTFT budget")
            if trace_id:
                self.tracer.record(trace_id, "gateway.stream", t_up0,
                                   time.time(), pod=pod.name,
                                   error="ttft timeout")
            logger.warning("stream from %s produced no first chunk in time",
                           pod.address)
            return None, "ttft_timeout"
        except (aiohttp.ClientError, ConnectionResetError, OSError) as e:
            upstream.close()
            self._record_upstream(pod.name, ok=False)
            self.journal.emit(events_mod.UPSTREAM_ERROR, trace_id or "",
                              pod=pod.name, stream=True,
                              error=str(e)[:200] or "stream broke pre-first-"
                                                    "chunk")
            if trace_id:
                self.tracer.record(trace_id, "gateway.stream", t_up0,
                                   time.time(), pod=pod.name, error=str(e))
            logger.warning("stream from %s broke before first chunk: %s",
                           pod.address, e)
            return None, "read"
        headers = dict(self._sse_headers_tpl)
        headers["x-served-by"] = served_by or pod.name
        if trace_id:
            headers[tracing.TRACE_HEADER] = trace_id
        resp = web.StreamResponse(status=upstream.status, headers=headers)
        await resp.prepare(request)
        fast = self.fast_relay
        last_data_line = b""
        buf = b""
        # Fast relay: chunk REFERENCES only — the deque keeps enough tail
        # bytes for the end-of-stream usage parse, trimmed by whole chunks.
        tail: list[bytes] = []
        tail_len = 0
        t_first = None  # the first chunk's write to the client returned
        n_chunks = 0
        relay_sum = relay_max = 0.0  # per chunk: received -> written
        try:
            while pending is not None:
                chunk = pending
                if fast:
                    tail.append(chunk)
                    tail_len += len(chunk)
                    while (len(tail) > 1
                           and tail_len - len(tail[0]) >= RELAY_TAIL_BYTES):
                        tail_len -= len(tail.pop(0))
                else:
                    buf += chunk
                    *lines, buf = buf.split(b"\n")
                    for line in lines:
                        if (line.startswith(b"data: ")
                                and line != b"data: [DONE]"):
                            last_data_line = line
                try:
                    await resp.write(chunk)
                except (ConnectionResetError, ConnectionError):
                    # The UPSTREAM was serving fine — its streaks/probe
                    # accounting must not dangle on the client's exit.
                    self._record_upstream(pod.name, ok=True)
                    upstream.close()
                    self._client_disconnected(req_ctx, pod, trace_id, t_req,
                                              path, t_up0, t_first)
                    return resp, None
                now = time.time()
                if t_first is None:
                    t_first = now
                n_chunks += 1
                relay_sum += now - t_recv
                relay_max = max(relay_max, now - t_recv)
                try:
                    pending = await self._bounded(
                        chunks.__anext__(), rcfg.stream_idle_timeout_s)
                    t_recv = time.time()
                except StopAsyncIteration:
                    pending = None
        except asyncio.CancelledError:
            # aiohttp cancels the handler task when the CLIENT's connection
            # drops mid-stream — account for the partial request, then let
            # the cancellation propagate (swallowing it would break the
            # server's teardown contract).
            self._record_upstream(pod.name, ok=True)
            upstream.close()
            self._client_disconnected(req_ctx, pod, trace_id, t_req,
                                      path, t_up0, t_first)
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            timed_out = isinstance(e, asyncio.TimeoutError)
            if timed_out:
                upstream.close()  # the hung read owns the connection
            self.metrics.record_error(req_ctx.model or None)
            self._record_upstream(pod.name, ok=False,
                                            timeout=timed_out)
            self.journal.emit(events_mod.UPSTREAM_ERROR, trace_id or "",
                              pod=pod.name, stream=True,
                              error=str(e)[:200] or "stream idle timeout")
            if trace_id:
                self.tracer.record(trace_id, "gateway.stream", t_up0,
                                   time.time(), pod=pod.name, error=str(e))
                self.tracer.annotate(trace_id, status="stream_error")
            logger.warning("upstream stream from %s broke: %s", pod.address, e)
            try:
                await resp.write(
                    b'data: {"error": {"message": "upstream stream interrupted"}}\n\n'
                    b"data: [DONE]\n\n"
                )
            except (ConnectionResetError, ConnectionError):
                # The client is ALSO gone: account for it instead of
                # silently dropping the request from every aggregate.
                self._client_disconnected(req_ctx, pod, trace_id, t_req,
                                          path, t_up0, t_first)
            except asyncio.CancelledError:
                self._client_disconnected(req_ctx, pod, trace_id, t_req,
                                          path, t_up0, t_first)
                raise
            return resp, None
        t_end = time.time()
        self._record_upstream(pod.name, ok=True)
        if fast:
            last_data_line = final_data_line(b"".join(tail))
        try:
            final = json.loads(last_data_line[len(b"data: "):])
            usage = final.get("usage") or {}
            self.metrics.record_usage(
                req_ctx.model,
                int(usage.get("prompt_tokens", 0) or 0),
                int(usage.get("completion_tokens", 0) or 0),
            )
            req_ctx.usage.prompt_tokens = int(usage.get("prompt_tokens", 0) or 0)
            req_ctx.usage.completion_tokens = int(
                usage.get("completion_tokens", 0) or 0)
        except (json.JSONDecodeError, ValueError):
            pass
        if trace_id:
            # The hop's share of first-token time and of every token's way
            # out, all on this process's clock (an empty stream has none).
            parts = {}
            if n_chunks:
                lag0, ticks0, stall0 = req_ctx.loop_marks or (0.0, 0, 0.0)
                lag1, ticks1, stall1 = self.loop_clock.marks()
                parts = {
                    "pre_s": round(t_up0 - t_req, 6),
                    "first_chunk_s": round(t_recv0 - t_up0, 6),
                    "ttft_s": round(t_first - t_req, 6),
                    "relay_mean_s": round(relay_sum / n_chunks, 6),
                    "relay_max_s": round(relay_max, 6),
                    "chunks": n_chunks,
                    "loop_lag_s": round((lag1 - lag0)
                                        / max(1, ticks1 - ticks0), 6),
                    "stall_s": round(stall1 - stall0, 6)}
            self.tracer.record(trace_id, "gateway.stream", t_up0, t_end,
                               pod=pod.name, **parts)
            self._finish_phase(req_ctx, trace_id, path, t_req,
                               t_first=t_first, t_last=t_end)
        return resp, None

    # -- ops endpoints -----------------------------------------------------
    def _render_metrics(self) -> str:
        """The full gateway exposition page: request-path counters and
        histograms (GatewayMetrics) plus the observability control plane's
        families — SLO gauges, per-pool advisor stacks (health, circuits,
        usage, fairness, placement — merged so shared families keep one
        ``# TYPE`` line and per-stack scalar counters sum), the statebus,
        and the event counters."""
        text = self.metrics.render()
        if len(self.stacks) == 1:
            stack_lines = self.stacks[self._default_pool].render()
        else:
            stack_lines = merge_exposition_blocks(
                [stack.render() for stack in self.stacks.values()])
        extra = (self.slo.render() + stack_lines
                 + self.statebus.render()
                 + self.fleet.render()
                 + self.journal.render_prom("gateway_events_total")
                 + self.loop_clock.render("gateway_loop_lag_seconds_total",
                                          "gateway_loop_ticks_total",
                                          "gateway_loop_stall_seconds_total"))
        if extra:
            text += "\n".join(extra) + "\n"
        return text

    async def handle_metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=self._render_metrics(),
                            content_type="text/plain")

    async def handle_debug_traces(self, request: web.Request) -> web.Response:
        """Recent request traces as JSON (``?trace_id=`` exact filter,
        ``?limit=`` count cap) — the merged cross-process timeline."""
        return web.json_response(
            tracing.debug_traces_payload(self.tracer, request.query))

    async def handle_debug_slo(self, request: web.Request) -> web.Response:
        """Per-model SLO compliance, windowed burn rates, and burn state.
        Evaluates on demand (floored at the configured cadence — ring
        growth AND the tick-denominated hysteresis must track
        LIG_SLO_TICK_S, not an aggressive poller) so a curl sees the
        current state even when the background task is disabled."""
        self.slo.maybe_tick(max(1.0, self.obs_tick_s))
        return web.json_response(self.slo.debug_payload())

    async def handle_debug_health(self, request: web.Request) -> web.Response:
        """Per-replica health scores, components, states, would-avoid
        counters, plus the resilience plane (policy, per-pod circuit
        states, retry budget).  Floored at the configured cadence: the
        dwell-tick hysteresis counts update PASSES, so a fast poller must
        not drive transitions.  Multi-pool fronts add a ``pools`` section
        (one health+resilience payload per pool) next to the default
        pool's top-level fields."""
        for stack in self.stacks.values():
            stack.health.maybe_update(max(1.0, self.obs_tick_s))
        payload = self.health.debug_payload()
        payload["resilience"] = self.resilience.debug_payload()
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: dict(stack.health.debug_payload(),
                           resilience=stack.resilience.debug_payload())
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_usage(self, request: web.Request) -> web.Response:
        """Pool-wide capacity attribution: per-{model, adapter} consumption
        shares, admitted-traffic shares, noisy-neighbor scores/flags, and
        pool-waste aggregates (gateway/usage.py; rendered live by
        ``tools/lig_top.py``) — plus the fairness plane's throttle and
        demotion state (gateway/fairness.py).  Floored at the configured
        cadence — the enter/exit hysteresis counts rollup passes.
        Multi-pool fronts add a ``pools`` section (one usage+fairness+
        residency payload per pool) next to the default pool's top-level
        fields."""
        for stack in self.stacks.values():
            stack.usage.maybe_tick(max(1.0, self.obs_tick_s))
        payload = self.usage.debug_payload()
        payload["fairness"] = self.fairness.debug_payload()
        # Residency alongside the usage shares (pod -> adapter -> tier):
        # lig-top renders WHERE each tenant's weights live next to what
        # they consume.
        payload["residency"] = self.placement.debug_payload()["residency"]
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: dict(
                    stack.usage.debug_payload(),
                    fairness=stack.fairness.debug_payload(),
                    residency=stack.placement.debug_payload()["residency"])
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_kv(self, request: web.Request) -> web.Response:
        """The fleet KV economy view (gateway/kvobs.py): per-pod reuse
        efficiency, parked-KV share, and the cross-replica prefix
        duplication index joined over the pods' ``tpu:kv_prefix_*``
        tables.  Floored at the configured cadence — the savings-rate
        EMAs difference cumulative counters per rollup pass.  Multi-pool
        fronts add a ``pools`` section next to the default pool's
        top-level fields.  Rendered by ``tools/kv_report.py``; the
        fast-burn black-box dump embeds the same payload."""
        for stack in self.stacks.values():
            stack.kvobs.maybe_tick(max(1.0, self.obs_tick_s))
        payload = self.kvobs.debug_payload()
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: stack.kvobs.debug_payload()
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_capacity(self,
                                    request: web.Request) -> web.Response:
        """The capacity & saturation plane (gateway/capacity.py):
        per-pod per-resource saturation indices, the calibrated twin's
        knee/headroom/time-to-breach forecasts, drift divergences and the
        trust state.  Floored at the configured cadence — the calibration
        windows difference cumulative counters per rollup pass.
        Multi-pool fronts add a ``pools`` section.  Rendered by
        ``tools/capacity_report.py``; the fast-burn black-box dump embeds
        the same payload."""
        for stack in self.stacks.values():
            if stack.capacity.cfg.enabled:
                stack.capacity.maybe_tick(max(1.0, self.obs_tick_s))
        payload = self.capacity.debug_payload()
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: stack.capacity.debug_payload()
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_picks(self, request: web.Request) -> web.Response:
        """The routing decision ledger (gateway/pickledger.py): sampled
        per-pick explanation records — stage-by-stage candidate
        narrowing, removed-pod attribution, escape-hatch fires, and the
        counterfactual "decisive seam" tag.  ``?since=<seq>`` incremental
        cursor + ``?limit=`` cap, mirroring /debug/events; records join
        traces via their ``trace_id`` (the ``x-lig-trace-id`` the proxy
        mints).  Multi-pool fronts add a ``pools`` section.  Rendered by
        ``tools/pick_report.py``; the fast-burn black-box dump embeds the
        same payload."""
        payload = pickledger_mod.debug_picks_payload(
            self.pickledger, request.query)
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: pickledger_mod.debug_picks_payload(
                    stack.pickledger, request.query)
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_placement(self, request: web.Request) -> web.Response:
        """The placement plane's state + this tick's decisions — the wire
        ``tools/lora_sidecar.py --planner-url`` polls.  Floored at the
        configured cadence like the other debug surfaces (idle dwell
        counts planner passes).  Multi-pool fronts add a ``pools``
        section (one planner payload per pool) — a sidecar polls with
        ``?pool=<name>`` to read exactly its pool's slice."""
        for stack in self.stacks.values():
            stack.usage.maybe_tick(max(1.0, self.obs_tick_s))
            if (stack.placement.ticks == 0
                    or time.time() - stack.placement.last_tick
                    >= max(1.0, self.obs_tick_s)):
                stack.placement.tick()
        pool = request.query.get("pool")
        if pool:
            stack = self.stacks.get(pool)
            if stack is None:
                return web.json_response(
                    {"error": f"unknown pool {pool!r}",
                     "pools": sorted(self.stacks)}, status=404)
            return web.json_response(stack.placement.debug_payload())
        payload = self.placement.debug_payload()
        if len(self.stacks) > 1:
            payload["pools"] = {
                name: stack.placement.debug_payload()
                for name, stack in self.stacks.items()}
        return web.json_response(payload)

    async def handle_debug_statebus(self,
                                    request: web.Request) -> web.Response:
        """The replicated state plane's view: this replica's local
        snapshot, every known replica's versions/ages, and the merged
        per-pool overlay the advisors currently apply —
        ``tools/statebus_report.py`` renders the divergence table."""
        return web.json_response(self.statebus.debug_payload())

    async def handle_debug_fleet(self, request: web.Request) -> web.Response:
        """The fleet observability view (gateway/fleetobs.py): one pull of
        every peer gateway's and pool pod's debug surfaces (incremental
        cursors — deltas only), stitched cross-replica traces, the merged
        fleet journal, fleet-wide SLO rollup, and per-gateway health.
        ``?limit=`` caps stitched traces (1..256, default 64).  Rendered
        by ``tools/fleet_report.py``; dead sources degrade to their
        cached view with an error marker, never a failed page."""
        try:
            limit = max(1, min(int(request.query.get("limit", "64")), 256))
        except ValueError:
            limit = 64
        session = self._session
        if session is None:
            # Called before startup (tests, one-shot tools): a throwaway
            # session is fine at debug-endpoint cadence.
            async with aiohttp.ClientSession() as tmp:
                payload = await self.fleet.collect(tmp, limit=limit)
        else:
            payload = await self.fleet.collect(session, limit=limit)
        # The fleet KV economy rollup rides along so a peer (or
        # tools/fleet_report.py) reads duplication context without a
        # second pull; per-pod joins live at /debug/kv.
        self.kvobs.maybe_tick(max(1.0, self.obs_tick_s))
        payload["kv"] = self.kvobs.debug_payload()
        # Capacity rollup rides along too: headroom/forecast/trust per
        # pool, so a fleet console answers "which pool runs out first"
        # without a second pull; full detail lives at /debug/capacity.
        if self.capacity.cfg.enabled:
            self.capacity.maybe_tick(max(1.0, self.obs_tick_s))
            payload["capacity"] = {
                name: {"saturation": cap["saturation"],
                       "forecast": cap["forecast"]}
                for name, stack in self.stacks.items()
                for cap in [stack.capacity.debug_payload()]}
        # Fleet pick-steering rollup: which replicas/pools are steering
        # picks and why, joined from the statebus docs already gossiped
        # (no extra pull) — per-pick joins live at /debug/picks.
        payload["picks"] = fleetobs.pick_steering_rollup(
            self.statebus.all_docs())
        return web.json_response(payload)

    async def handle_statebus_exchange(
            self, request: web.Request) -> web.Response:
        """Push-pull gossip endpoint: a peer POSTs the snapshot docs it
        knows (its own + transitively learned ones); we merge them and
        answer with OUR full doc set, so one round trip equalizes both
        sides even across replicas that never talk directly.

        A gateway with NO peers configured refuses the exchange: the
        statebus's peer-less contract is "inert beyond /debug/statebus",
        and merged docs steer enforcement — an open endpoint would let
        any client that can reach the port flag tenants noisy or mark
        every pod avoided.  (With peers configured, restrict reachability
        of this port to the gateway fleet — the gossip wire carries no
        authentication, like the rest of the gateway's surfaces.)"""
        if not self.statebus.cfg.peers:
            return web.json_response(
                {"error": "statebus has no peers configured "
                          "(--statebus-peer); exchange refused"},
                status=403)
        try:
            docs = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            return web.json_response({"error": "malformed docs"},
                                     status=400)
        if not isinstance(docs, list):
            return web.json_response({"error": "expected a doc list"},
                                     status=400)
        self.statebus.merge(docs)
        self.statebus.apply()
        return web.json_response(self.statebus.all_docs())

    async def handle_debug_events(self, request: web.Request) -> web.Response:
        """The flight recorder: ``?since=<seq>`` incremental cursor,
        ``?kind=`` filter, ``?limit=`` cap."""
        return web.json_response(
            events_mod.debug_events_payload(self.journal, request.query))

    async def handle_health(self, request: web.Request) -> web.Response:
        if self.datastore.has_synced_pool():
            return web.Response(text="ok")
        return web.Response(status=503, text="InferencePool not synced")

    async def handle_models(self, request: web.Request) -> web.Response:
        models = [
            {"id": m.spec.model_name, "object": "model",
             "criticality": m.spec.criticality.value}
            for m in self.datastore.all_models()
        ]
        return web.json_response({"object": "list", "data": models})


def main(argv: list[str] | None = None) -> None:
    from llm_instance_gateway_tpu.gateway import bootstrap

    parser = argparse.ArgumentParser(description="TPU-native inference gateway")
    parser.add_argument("--port", type=int, default=8081)
    parser.add_argument("--no-pick-ledger", action="store_true",
                        help="disable the routing decision ledger "
                             "(/debug/picks goes empty; routing itself is "
                             "unchanged either way — the ledger is log-only)")
    parser.add_argument("--pick-sample-every", type=int, default=8,
                        help="sample every Nth pick into the decision "
                             "ledger (1 = every pick; default 8)")
    bootstrap.add_common_args(parser)
    bootstrap.add_resilience_args(parser)
    bootstrap.add_statebus_args(parser)
    args = parser.parse_args(argv)

    comps = bootstrap.components_from_args(args)
    proxy = GatewayProxy(comps.handler_server, comps.provider, comps.datastore,
                         resilience_cfg=bootstrap.resilience_from_args(args),
                         fairness_cfg=bootstrap.fairness_from_args(args),
                         placement_cfg=bootstrap.placement_from_args(args),
                         capacity_cfg=bootstrap.capacity_from_args(args),
                         pickledger_cfg=pickledger_mod.PickLedgerConfig(
                             enabled=not args.no_pick_ledger,
                             sample_every=max(1, args.pick_sample_every)),
                         pools=getattr(comps, "pools", None),
                         statebus_cfg=bootstrap.statebus_from_args(
                             args, port=args.port))
    try:
        web.run_app(proxy.build_app(), port=args.port)
    finally:
        comps.stop()


if __name__ == "__main__":
    main()
