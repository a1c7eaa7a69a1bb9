"""Fleet observability plane: cross-replica trace stitching + aggregation.

PR 10 made the control plane horizontally scalable (N gateway replicas x
M pools) but every observability surface stayed per-process: a request's
trace lives only on the replica that served it, event journals have no
fleet view, and SLO burn is computed per gateway.  This module is the
fleet layer the per-process surfaces report through:

- **Stitcher** (pure functions, the testable core): ``stitch_traces``
  merges ``/debug/traces`` payloads from any number of gateway replicas
  and model-server pods into per-trace-id timelines — every span tagged
  with its source, duplicates (a server span the gateway already merged
  from ``x-lig-spans``) folded, clock skew normalized PER HOP against
  the serving gateway's hop spans (clock domains follow span names, not
  shipping sources — the gateway's wire copies carry the pods' clocks),
  spans causally ordered.
  ``merge_events`` merges flight-recorder journals by ``(replica, seq)``;
  ``fleet_slo`` folds per-replica SLO payloads into fleet-wide
  compliance + worst burn per objective.
- **Collector** (``FleetCollector``): pulls ``/debug/traces?since=`` /
  ``/debug/events?since=`` (the incremental cursors — deltas, never the
  whole ring), ``/debug/slo`` and ``/debug/health`` from every peer
  gateway (the ``--statebus-peer`` list — the fleet topology is already
  wired) and every pool pod, folds them into bounded per-source caches,
  and serves the stitched fleet view as ``/debug/fleet`` on EVERY
  replica.  A dead source degrades to its cached data + an error marker
  (journaled ``fleet_peer_error``), never a failed page.

``tools/fleet_report.py`` renders the fleet view (per-phase fleet-wide
percentiles, slowest-trace exemplars, per-replica divergence);
``tools/trace_report.py --url a --url b`` runs multi-replica payloads
through the same stitcher.
"""

from __future__ import annotations

import asyncio
import collections
import time

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu.tracing import (
    Histogram,
    escape_label,
    render_counter,
    render_histogram,
)

# Collect wall per source fetch is network-bound; second-scale buckets.
COLLECT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0)

# Which gateway hop span "covers" which downstream span names — the
# anchor pairs skew normalization aligns on.  A child source's earliest
# matching span must start inside its parent hop's window; when it
# doesn't, the whole source shifts by one offset (clocks skew per
# process, not per span).
HOP_CHILDREN = (
    ("gateway.prefill_hop", ("engine.queue_wait", "engine.prefill",
                             "handoff.serialize")),
    ("gateway.attach_hop", ("handoff.deserialize", "handoff.attach",
                            "engine.decode")),
    ("gateway.upstream", ("engine.queue_wait", "engine.prefill",
                          "engine.decode", "handoff.serialize")),
    ("gateway.stream", ("server.accept", "engine.queue_wait",
                        "engine.prefill", "server.first_write",
                        "engine.decode")),
)

# The span name that identifies the gateway that SERVED a trace — the
# reference clock skew normalization aligns everything else against.
REFERENCE_SPAN = "gateway.admission"


# ---------------------------------------------------------------------------
# Stitcher (pure)
# ---------------------------------------------------------------------------


def _span_key(span: dict) -> tuple:
    """Identity of a span independent of which replica shipped it: the
    gateway's merged copy of a server span (``x-lig-spans``) carries the
    same name and µs-rounded boundaries as the server's own record."""
    try:
        return (str(span.get("name", "")), round(float(span["start"]), 6),
                round(float(span["end"]), 6))
    except (KeyError, TypeError, ValueError):
        return (str(span.get("name", "")), None, None)


def _normalize_skew(spans: list[dict]) -> dict[str, float]:
    """Shift downstream spans onto the serving gateway's clock, IN PLACE;
    returns the applied offsets keyed by the anchoring hop span.

    The clock domain of a span is decided by its NAME, never by which
    replica shipped it: the gateway's ``/debug/traces`` already carries
    the pods' spans merged off ``x-lig-spans`` at the PODS' timestamps,
    so a source-keyed shift would leave exactly the skewed copies
    unshifted.  ``gateway.*`` spans are the reference clock; each hop's
    child span group (HOP_CHILDREN, claimed in order so e.g. a disagg
    trace's decode spans anchor on the attach hop, not the absent
    upstream span) shifts as ONE unit — clocks skew per process, and a
    hop's children all come from one process.  A group whose earliest
    span already starts inside its hop window stays put (synced clocks —
    the common case); groups with no matching hop stay unshifted (a
    partial trace is rendered honestly, not invented)."""
    ref_by_name: dict[str, dict] = {}
    for s in spans:
        if not s["name"].startswith("gateway."):
            continue
        # Earliest hop span of each name anchors (retries re-record hops).
        cur = ref_by_name.get(s["name"])
        if cur is None or s["start"] < cur["start"]:
            ref_by_name[s["name"]] = s
    skew: dict[str, float] = {}
    claimed: set[int] = set()
    for hop_name, child_names in HOP_CHILDREN:
        parent = ref_by_name.get(hop_name)
        if parent is None:
            continue
        children = [s for s in spans
                    if id(s) not in claimed and s["name"] in child_names]
        if not children:
            continue
        claimed.update(id(s) for s in children)
        child_start = min(s["start"] for s in children)
        if parent["start"] <= child_start <= parent["end"]:
            continue
        offset = parent["start"] - child_start
        skew[hop_name] = round(offset, 6)
        for s in children:
            s["start"] = round(s["start"] + offset, 6)
            s["end"] = round(s["end"] + offset, 6)
    return skew


def stitch_traces(sources: list[tuple[str, dict]],
                  limit: int = 256) -> list[dict]:
    """Merge ``/debug/traces`` payloads from many replicas into per-trace
    stitched timelines.

    ``sources`` is ``[(replica_name, payload), ...]`` where payload is
    the ``{"traces": [...]}`` shape both debug surfaces serve.  Returns
    stitched trace dicts, most recent first (by last span end), capped at
    ``limit``: trace_id, merged model/path/status, the sources that
    contributed, the per-hop skew offsets applied (``_normalize_skew``),
    and spans sorted causally (each span carries its ``source``).
    Hostile inputs degrade per-item: malformed spans are skipped,
    duplicate span names across replicas stay distinguishable by source,
    missing hops leave skew at zero.
    """
    traces: dict[str, dict] = {}
    for name, payload in sources:
        if not isinstance(payload, dict):
            continue
        for trace in payload.get("traces") or []:
            if not isinstance(trace, dict):
                continue
            tid = str(trace.get("trace_id") or "")
            if not tid:
                continue
            t = traces.setdefault(tid, {
                "trace_id": tid, "model": "", "path": "", "status": "",
                "sources": [], "_spans": {}})
            if name not in t["sources"]:
                t["sources"].append(name)
            for field in ("model", "path", "status"):
                v = trace.get(field)
                if v and not t[field]:
                    t[field] = str(v)
            for span in trace.get("spans") or []:
                if not isinstance(span, dict):
                    continue
                try:
                    clean = {"name": str(span.get("name", "?")),
                             "start": float(span["start"]),
                             "end": float(span["end"])}
                except (KeyError, TypeError, ValueError):
                    continue  # partial x-lig-spans rows degrade per-span
                if clean["end"] < clean["start"]:
                    clean["start"], clean["end"] = (clean["end"],
                                                    clean["start"])
                attrs = span.get("attrs")
                if isinstance(attrs, dict) and attrs:
                    clean["attrs"] = attrs
                key = _span_key(clean)
                if key in t["_spans"]:
                    continue  # the gateway's merged copy of this span
                clean["source"] = name
                t["_spans"][key] = clean

    out = []
    for t in traces.values():
        spans = list(t.pop("_spans").values())
        # Skew normalization needs the serving gateway's hop spans as the
        # reference clock; a pod-only view (no admission span) renders
        # unshifted.
        skew: dict[str, float] = {}
        if any(s["name"] == REFERENCE_SPAN for s in spans):
            skew = _normalize_skew(spans)
        spans.sort(key=lambda s: (s["start"], s["end"], s["name"]))
        t["skew"] = skew
        t["spans"] = spans
        t["t_created"] = spans[0]["start"] if spans else 0.0
        # Max end, not the last-sorted span's end: an enclosing span
        # (gateway.upstream around its engine children) ends last but
        # sorts by START — recency ordering must see the true last
        # activity or the limit cut drops the freshest trace.
        t["t_last"] = max((s["end"] for s in spans), default=0.0)
        out.append(t)
    out.sort(key=lambda t: -t["t_last"])
    return out[:max(0, limit)]


def merge_events(sources: list[tuple[str, dict]],
                 limit: int = 512) -> list[dict]:
    """Merge flight-recorder payloads by ``(replica, seq)``: each row
    gains a ``replica`` field, duplicates (re-polled pages) fold, and the
    result is one chronological fleet journal, newest ``limit`` rows.
    Rows without an int-able ``seq`` are skipped and non-numeric ``ts``
    sorts as 0 — a foreign/older peer's journal shape degrades per-row,
    never the merged page."""
    seen: set[tuple[str, int]] = set()
    rows: list[tuple[float, str, int, dict]] = []
    for name, payload in sources:
        if not isinstance(payload, dict):
            continue
        for event in payload.get("events") or []:
            if not isinstance(event, dict):
                continue
            try:
                seq = int(event.get("seq", 0))
            except (TypeError, ValueError):
                continue
            if (name, seq) in seen:
                continue
            seen.add((name, seq))
            try:
                ts = float(event.get("ts", 0.0))
            except (TypeError, ValueError):
                ts = 0.0
            rows.append((ts, name, seq, {**event, "replica": name}))
    rows.sort(key=lambda r: r[:3])
    return [r[3] for r in rows[-max(0, limit):]]


def fleet_slo(payloads: dict[str, dict]) -> dict:
    """Fold per-replica ``/debug/slo`` payloads into the fleet view:
    good/total SUM per (model, objective) — fleet compliance is the
    traffic-weighted truth, not an average of ratios — plus the worst
    burn rate and the per-replica burn states."""
    models: dict[str, dict] = {}
    for replica, payload in sorted(payloads.items()):
        if not isinstance(payload, dict):
            continue
        models_doc = payload.get("models")
        if not isinstance(models_doc, dict):
            continue
        for model, objectives in models_doc.items():
            if not isinstance(objectives, dict):
                continue
            for objective, o in objectives.items():
                if not isinstance(o, dict):
                    continue
                agg = models.setdefault(model, {}).setdefault(objective, {
                    "good": 0, "total": 0, "compliance": None,
                    "worst_burn": None, "worst_burn_replica": None,
                    "states": {}})
                try:
                    agg["good"] += int(o.get("good") or 0)
                    agg["total"] += int(o.get("total") or 0)
                except (TypeError, ValueError):
                    pass
                agg["states"][replica] = o.get("state")
                burns = [v for v in (o.get("burn_rates") or {}).values()
                         if isinstance(v, (int, float))]
                if burns:
                    worst = max(burns)
                    if agg["worst_burn"] is None or worst > agg["worst_burn"]:
                        agg["worst_burn"] = round(worst, 4)
                        agg["worst_burn_replica"] = replica
    for objectives in models.values():
        for agg in objectives.values():
            if agg["total"]:
                agg["compliance"] = round(agg["good"] / agg["total"], 6)
    return {"models": models, "replicas": sorted(payloads)}


def pick_steering_rollup(docs: list[dict]) -> dict:
    """Fold statebus docs' per-pool pick-ledger rollups
    (``gateway/pickledger.py`` via ``StateBus.snapshot``) into the fleet
    steering view — "which seam is steering traffic on which replica":
    per replica/pool the seam steering counts and decisive-seam
    histogram, plus fleet-wide seam totals.  Pure over ``all_docs()``;
    docs from pre-ledger peers (no ``picks`` key) are skipped."""
    replicas: dict[str, dict] = {}
    totals_steered: dict[str, int] = {}
    totals_decisive: dict[str, int] = {}
    for doc in docs or ():
        if not isinstance(doc, dict):
            continue
        replica = doc.get("replica")
        pools = doc.get("pools")
        if not isinstance(replica, str) or not isinstance(pools, dict):
            continue
        for pool, pool_doc in sorted(pools.items()):
            if not isinstance(pool_doc, dict):
                continue
            picks = pool_doc.get("picks")
            if not isinstance(picks, dict) or not picks.get("samples"):
                continue
            steered = {str(k): int(v) for k, v in
                       (picks.get("steered") or {}).items()
                       if isinstance(v, (int, float))}
            decisive = {str(k): int(v) for k, v in
                        (picks.get("decisive") or {}).items()
                        if isinstance(v, (int, float))}
            replicas.setdefault(replica, {})[pool] = {
                "samples": int(picks.get("samples") or 0),
                "picks": int(picks.get("picks") or 0),
                "steered": steered,
                "decisive": decisive,
                "escapes": dict(picks.get("escapes") or {}),
                "steered_away": dict(picks.get("steered_away") or {}),
            }
            for seam, n in steered.items():
                totals_steered[seam] = totals_steered.get(seam, 0) + n
            for tag, n in decisive.items():
                totals_decisive[tag] = totals_decisive.get(tag, 0) + n
    return {"replicas": replicas,
            "steered_total": totals_steered,
            "decisive_total": totals_decisive}


def collect_pod_payloads(pods: list[tuple[str, str]],
                         path: str = "/debug/profile",
                         timeout_s: float = 2.0,
                         thread_name: str = "blackbox-fetch") -> dict:
    """Best-effort JSON fetch of one debug ``path`` from every pool pod —
    the black-box dump's profiler and KV-economy sections (runs in the
    dump's executor thread, never on the event loop).  Fetches run
    CONCURRENTLY so a breach dump on a pool full of black-holed pods
    (exactly when dumps fire) is delayed by ~one timeout, not one per
    wedged pod; failures become error markers."""
    import concurrent.futures as futures
    import json as json_mod
    import urllib.request

    def fetch(address: str) -> dict:
        with urllib.request.urlopen(f"http://{address}{path}",
                                    timeout=timeout_s) as resp:
            return json_mod.loads(resp.read().decode())

    out: dict[str, dict] = {}
    if not pods:
        return out
    # No context manager: its exit is shutdown(wait=True), which would
    # block past the deadline on stragglers and discard what completed
    # meanwhile — the dump must pay at most the deadline, never a
    # per-wedged-pod wait.
    ex = futures.ThreadPoolExecutor(max_workers=min(16, len(pods)),
                                    thread_name_prefix=thread_name)
    futs = {ex.submit(fetch, address): name for name, address in pods}
    try:
        for fut in futures.as_completed(futs, timeout=timeout_s * 4):
            try:
                out[futs[fut]] = fut.result()
            except Exception as e:  # noqa: BLE001 — a failed pod is
                out[futs[fut]] = {"error": str(e)[:200]}  # a marker
    except futures.TimeoutError:
        # Sweep anything that finished between the deadline and here;
        # genuine stragglers get the fallback marker below.
        for fut, name in futs.items():
            if name not in out and fut.done():
                try:
                    out[name] = fut.result()
                except Exception as e:  # noqa: BLE001
                    out[name] = {"error": str(e)[:200]}
    ex.shutdown(wait=False, cancel_futures=True)
    for name, _address in pods:
        out.setdefault(name, {"error": "fetch did not complete"})
    return out


def collect_pod_profiles(pods: list[tuple[str, str]],
                         timeout_s: float = 2.0) -> dict:
    """Back-compat alias: the profiler-section fetch predates the
    path-parameterized ``collect_pod_payloads``."""
    return collect_pod_payloads(pods, "/debug/profile", timeout_s,
                                thread_name="blackbox-profile")


# ---------------------------------------------------------------------------
# Collector
# ---------------------------------------------------------------------------


class _SourceState:
    """Per-source incremental-poll state: cursors + bounded caches."""

    __slots__ = ("trace_since", "event_since", "traces", "events",
                 "last_ok", "last_error")

    def __init__(self):
        self.trace_since = 0
        self.event_since = 0
        # trace_id -> folded partial trace (bounded, LRU by activity).
        self.traces: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict())
        self.events: collections.deque = collections.deque(maxlen=2048)
        self.last_ok = False
        self.last_error = ""


class FleetCollector:
    """Pulls every replica's debug surfaces into one stitched fleet view.

    ``peer_urls`` are gateway base URLs (the ``--statebus-peer`` list);
    ``pods_fn`` returns the live ``[(pod_name, address), ...]`` pool
    membership; ``local_fn`` returns this replica's own payloads without
    HTTP (``{"traces": ..., "events": ..., "slo": ..., "health": ...}``).
    Thread-safe enough for its use: collect() runs on the event loop,
    render() on the scrape path — counters are guarded by a lock, caches
    are only touched from collect().
    """

    def __init__(self, replica: str, peer_urls: tuple = (),
                 pods_fn=None, local_fn=None,
                 journal: "events_mod.EventJournal | None" = None,
                 timeout_s: float = 2.0, trace_capacity: int = 256,
                 clock=time.time):
        self.replica = replica
        self.peer_urls = tuple(peer_urls)
        self.pods_fn = pods_fn or (lambda: [])
        self.local_fn = local_fn
        self.journal = journal
        self.timeout_s = timeout_s
        self.trace_capacity = max(1, trace_capacity)
        self._clock = clock
        self._sources: dict[str, _SourceState] = {}
        self._lock = witness_lock("FleetCollector._lock")
        # collect() is single-flight: two overlapping /debug/fleet pulls
        # would both read the same cursors and double-append events into
        # the bounded deques (evicting real history with duplicates).
        self._collect_lock = asyncio.Lock()
        self.collect_hist = Histogram(COLLECT_BUCKETS)
        self.errors_total: dict[str, int] = {}
        self.last_sources: dict[str, int] = {}  # kind -> fresh count
        self.last_stitched = 0

    # -- folding -------------------------------------------------------------
    def _state(self, name: str) -> _SourceState:
        st = self._sources.get(name)
        if st is None:
            st = self._sources[name] = _SourceState()
        return st

    def _fold_traces(self, st: _SourceState, payload: dict) -> None:
        for trace in payload.get("traces") or []:
            if not isinstance(trace, dict) or not trace.get("trace_id"):
                continue
            tid = str(trace["trace_id"])
            cur = st.traces.get(tid)
            if cur is None:
                cur = st.traces[tid] = {
                    "trace_id": tid, "model": "", "path": "", "status": "",
                    "spans": [], "_keys": set()}
                while len(st.traces) > self.trace_capacity:
                    st.traces.popitem(last=False)
            else:
                st.traces.move_to_end(tid)
            for field in ("model", "path", "status"):
                v = trace.get(field)
                if v:
                    cur[field] = str(v)
            for span in trace.get("spans") or []:
                if not isinstance(span, dict):
                    continue
                key = _span_key(span)
                if key in cur["_keys"]:
                    continue  # re-shipped row from a retreated cursor
                cur["_keys"].add(key)
                cur["spans"].append(span)
        if isinstance(payload.get("next_since"), int):
            st.trace_since = payload["next_since"]

    def _fold_events(self, st: _SourceState, payload: dict) -> None:
        for event in payload.get("events") or []:
            if isinstance(event, dict):
                st.events.append(event)
        if isinstance(payload.get("next_since"), int):
            st.event_since = payload["next_since"]

    def _trace_payload(self, st: _SourceState) -> dict:
        return {"traces": [
            {k: v for k, v in t.items() if k != "_keys"}
            for t in st.traces.values()]}

    # -- collection ----------------------------------------------------------
    async def _fetch_json(self, session, url: str):
        import aiohttp

        timeout = aiohttp.ClientTimeout(total=self.timeout_s)
        async with session.get(url, timeout=timeout) as resp:
            if resp.status != 200:
                raise RuntimeError(f"{url} -> {resp.status}")
            return await resp.json()

    async def _collect_source(self, session, name: str, base: str,
                              kind: str) -> dict | None:
        """One source's pull: traces+events deltas always; slo+health for
        gateway peers.  Returns the fetched slo/health payloads (or None
        on failure — the cached traces/events still contribute)."""
        st = self._state(name)
        try:
            traces = await self._fetch_json(
                session, f"{base}/debug/traces?since={st.trace_since}"
                         f"&limit=1024")
            events = await self._fetch_json(
                session, f"{base}/debug/events?since={st.event_since}"
                         f"&limit=2048")
            if not isinstance(traces, dict) or not isinstance(events, dict):
                # Valid JSON of the wrong shape (foreign peer, wrong URL)
                # is a source failure, not a page failure.
                raise RuntimeError(f"{base}: non-dict debug payload")
            extra = {}
            if kind == "gateway":
                extra["slo"] = await self._fetch_json(
                    session, f"{base}/debug/slo")
                extra["health"] = await self._fetch_json(
                    session, f"{base}/debug/health")
                if any(not isinstance(v, dict) for v in extra.values()):
                    raise RuntimeError(f"{base}: non-dict slo/health "
                                       f"payload")
        except Exception as e:  # noqa: BLE001 — every failure is a marker
            st.last_ok = False
            st.last_error = str(e)[:200]
            with self._lock:
                self.errors_total[name] = self.errors_total.get(name, 0) + 1
            if self.journal is not None:
                # ``kind`` is the journal's own positional — the source's
                # flavor rides as source_kind.
                self.journal.emit(events_mod.FLEET_PEER_ERROR, source=name,
                                  source_kind=kind, error=st.last_error)
            return None
        self._fold_traces(st, traces)
        self._fold_events(st, events)
        st.last_ok = True
        st.last_error = ""
        return extra

    async def collect(self, session, limit: int = 64) -> dict:
        """One fleet pull: every source concurrently, then stitch.
        Single-flight (overlapping callers queue on the lock — each
        still gets a complete, current payload)."""
        async with self._collect_lock:
            return await self._collect_locked(session, limit)

    async def _collect_locked(self, session, limit: int) -> dict:
        t0 = time.perf_counter()
        now = self._clock()
        gateways = [(f"gw:{u}", u, "gateway") for u in self.peer_urls]
        pods = [(f"pod:{name}", f"http://{addr}", "pod")
                for name, addr in self.pods_fn()]
        results = await asyncio.gather(*(
            self._collect_source(session, name, base, kind)
            for name, base, kind in gateways + pods))

        slo_payloads: dict[str, dict] = {}
        health_payloads: dict[str, dict] = {}
        trace_sources: list[tuple[str, dict]] = []
        event_sources: list[tuple[str, dict]] = []
        # This replica's own view rides along without HTTP.
        if self.local_fn is not None:
            local = self.local_fn()
            trace_sources.append((self.replica, local.get("traces") or {}))
            event_sources.append((self.replica, local.get("events") or {}))
            if local.get("slo") is not None:
                slo_payloads[self.replica] = local["slo"]
            if local.get("health") is not None:
                health_payloads[self.replica] = local["health"]
        for (name, _base, kind), extra in zip(gateways + pods, results):
            st = self._state(name)
            trace_sources.append((name, self._trace_payload(st)))
            event_sources.append((name, {"events": list(st.events)}))
            if extra:
                if "slo" in extra:
                    slo_payloads[name] = extra["slo"]
                if "health" in extra:
                    health_payloads[name] = extra["health"]

        stitched = stitch_traces(trace_sources, limit=limit)
        merged_events = merge_events(event_sources)
        ok_by_kind: dict[str, int] = {"gateway": 0, "pod": 0}
        source_rows = []
        if self.local_fn is not None:
            source_rows.append({"name": self.replica, "kind": "gateway",
                                "url": "", "ok": True, "error": ""})
            ok_by_kind["gateway"] += 1
        for name, base, kind in gateways + pods:
            st = self._state(name)
            if st.last_ok:
                ok_by_kind[kind] += 1
            source_rows.append({"name": name, "kind": kind, "url": base,
                               "ok": st.last_ok, "error": st.last_error})
        # Prune state for sources that left the fleet (pod churn mints
        # new names forever): a departed pod's cached deques/traces and
        # its errors_total series must not grow memory and Prometheus
        # cardinality monotonically (the statebus eviction precedent).
        live = {name for name, _base, _kind in gateways + pods}
        for name in [n for n in self._sources if n not in live]:
            del self._sources[name]
        with self._lock:
            for name in [n for n in self.errors_total if n not in live]:
                del self.errors_total[name]
            self.last_sources = ok_by_kind
            self.last_stitched = len(stitched)
        self.collect_hist.observe(time.perf_counter() - t0)
        return {
            "replica": self.replica,
            "collected_at": round(now, 6),
            "sources": source_rows,
            "traces": stitched,
            "events": merged_events,
            "slo": fleet_slo(slo_payloads),
            "health": health_payloads,
        }

    # -- export --------------------------------------------------------------
    def render(self) -> list[str]:
        """The ``gateway_fleet_*`` families."""
        with self._lock:
            sources = dict(self.last_sources)
            errors = dict(self.errors_total)
            stitched = self.last_stitched
        lines = ["# TYPE gateway_fleet_sources gauge"]
        for kind in sorted(sources):
            lines.append('gateway_fleet_sources{kind="%s"} %d'
                         % (escape_label(kind), sources[kind]))
        lines += ["# TYPE gateway_fleet_stitched_traces gauge",
                  f"gateway_fleet_stitched_traces {stitched}"]
        lines += render_counter("gateway_fleet_collect_errors_total",
                                errors, "source")
        lines += render_histogram("gateway_fleet_collect_seconds",
                                  self.collect_hist)
        return lines
