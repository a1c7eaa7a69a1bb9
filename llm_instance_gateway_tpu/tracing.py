"""End-to-end request tracing: span recorder + shared histogram exposition.

PR 1 split a request's life across up to three processes (gateway proxy ->
prefill engine -> KV handoff -> decode engine); the only exported signals were
aggregate counters, so "where did this slow request spend its time?" had no
answer (the reference EPP acknowledges the same export gap,
``backend/provider.go:140``; SURVEY.md §5).  This module is the shared,
dependency-free substrate both halves use:

- **Trace propagation**: every request gets a trace id at the proxy, carried
  in the ``x-lig-trace-id`` header through ``/v1/completions``, the two-hop
  ``/v1/prefill`` -> ``/v1/attach`` relay, and the ext-proc handlers, and
  echoed in every response (success headers AND error bodies) so clients and
  the loadgen can correlate.
- **Span recorder** (``Tracer``): named wall-clock spans buffered in a
  bounded per-process ring, exported as JSON by the ``/debug/traces``
  endpoints on the proxy and ``api_http``.  Model servers additionally
  return their spans in a compact ``x-lig-spans`` response header so the
  proxy can merge a request's cross-process timeline into ONE trace.
- **Stall clock** (``LoopClock``): one task per asyncio process (the
  proxy, ``api_http``) that sleeps 50 ms and books how late it woke.  A
  machine pause reads as the same stall in every process, a loop starved
  of the interpreter lock as lag in one.
- **Histogram + exposition helper**: the one Prometheus histogram
  implementation (``_bucket``/``le`` lines, cumulative counts, ``+Inf``)
  shared by the gateway families (``gateway_ttft_seconds``,
  ``gateway_tpot_seconds``, ``gateway_e2e_seconds``,
  ``gateway_pick_latency_seconds``) and the server families
  (``tpu:prefill_seconds``, ``tpu:handoff_seconds``,
  ``tpu:decode_step_seconds``).

Sampling is deterministic on the trace id (one blake2b over 16 hex chars),
so a trace is either recorded by EVERY process on its path or by none —
there are no half-assembled timelines.  The default records everything; the
ring bounds memory either way.
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import json
import os
import random
import time

# Header names (lowercase; transports do case-insensitive lookups).
TRACE_HEADER = "x-lig-trace-id"
SPANS_HEADER = "x-lig-spans"

# Second-scale phase latencies (TTFT, prefill, e2e): wider than the
# microsecond-scale pick-latency buckets below.
LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
# Scheduler-pick-scale buckets (the gateway's historical default).
PICK_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                0.05, 0.1, 0.25, 0.5, 1.0)


# Trace ids need uniqueness, not cryptographic strength: uuid4 costs ~25µs
# on kernels with slow urandom (measured in the bench image — os.urandom is
# a real syscall there, and so is os.getpid), which would alone bust the
# <5% pick-overhead budget.  A urandom-seeded PRNG mints in ~1µs;
# register_at_fork reseeds children so they can't replay the parent's
# sequence without paying a per-call getpid syscall.
_rng = random.Random(int.from_bytes(os.urandom(8), "big"))


def _reseed() -> None:
    global _rng
    _rng = random.Random(int.from_bytes(os.urandom(8), "big") ^ os.getpid())


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reseed)


def new_trace_id() -> str:
    return f"{_rng.getrandbits(64):016x}"


def header_trace_id(headers) -> str | None:
    """Case-insensitive ``x-lig-trace-id`` lookup over any mapping."""
    get = getattr(headers, "get", None)
    if get is not None:
        v = get(TRACE_HEADER) or get(TRACE_HEADER.title())
        if v:
            return str(v)
    for k in headers:
        if str(k).lower() == TRACE_HEADER:
            return str(headers[k])
    return None


def escape_label(value: str) -> str:
    """Prometheus label-value escaping (backslash, quote, newline).

    One hostile label value must not poison a whole exposition page; every
    render path label goes through here.
    """
    return str(value).replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n")


# ---------------------------------------------------------------------------
# Histogram (+ Prometheus histogram exposition)
# ---------------------------------------------------------------------------


class Histogram:
    """Fixed-bucket latency histogram: observe() is a few list ops, cheap
    enough for the request path.  Exposed either as quantile estimates
    (``quantile``) or true Prometheus histogram series
    (``render_histogram``)."""

    __slots__ = ("buckets", "counts", "total", "n")

    def __init__(self, buckets: tuple[float, ...] = PICK_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)
        self.total = 0.0
        self.n = 0

    def observe(self, v: float) -> None:
        i = 0
        for i, b in enumerate(self.buckets):
            if v <= b:
                break
        else:
            i = len(self.buckets)
        self.counts[i] += 1
        self.total += v
        self.n += 1

    def quantile(self, q: float) -> float:
        if self.n == 0:
            return 0.0
        target = q * self.n
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                return self.buckets[i] if i < len(self.buckets) else float("inf")
        return float("inf")

    def state(self) -> dict:
        """Copy-out snapshot (cross-thread export: metrics_snapshot)."""
        return {"buckets": list(self.buckets), "counts": list(self.counts),
                "sum": self.total, "count": self.n}


def _fmt(v: float) -> str:
    # %g gives "0.001" / "2.5" / "5e-05": all parse back as the same float.
    return format(v, "g")


def render_keyed_family(name: str, table: dict, labels: tuple,
                        kind: str = "counter", fmt: str = "%s") -> list[str]:
    """One multi-label family over tuple keys: ``# TYPE`` line, an
    unlabeled 0 fallback when the table is empty (counters only — a gauge
    family with no series simply renders nothing past its TYPE line), keys
    sorted and every label value escaped.  The tuple-key sibling of
    ``render_counter`` — the fairness/usage planes key everything by
    ``(model, adapter)``."""
    lines = [f"# TYPE {name} {kind}"]
    if not table:
        if kind == "counter":
            lines.append(f"{name} 0")
        return lines
    for key in sorted(table):
        label_str = ",".join(
            f'{label}="{escape_label(str(part))}"'
            for label, part in zip(labels, key))
        lines.append(f"{name}{{{label_str}}} {fmt % (table[key],)}")
    return lines


def render_counter(name: str, table: dict, label: str) -> list[str]:
    """One labeled counter family: ``# TYPE`` line, a ``None`` key (or an
    empty table) rendered as the unlabeled fallback line, remaining keys
    sorted and escaped.  The ONE implementation behind every counter
    family the gateway, the event journal, and the health scorer expose —
    exposition-format fixes land here once."""
    lines = [f"# TYPE {name} counter"]
    if not table:
        lines.append(f"{name} 0")
    # None sorts first: stable output, fallback line leads.
    for key in sorted(table, key=lambda k: (k is not None, k or "")):
        if key is None:
            lines.append(f"{name} {table[key]}")
        else:
            lines.append(
                f'{name}{{{label}="{escape_label(key)}"}} {table[key]}')
    return lines


def render_histogram(name: str, hist, labels: dict[str, str] | None = None,
                     type_line: bool = True) -> list[str]:
    """Prometheus histogram exposition lines for one series.

    ``hist`` is a ``Histogram`` or its ``state()`` dict.  ``labels`` are
    escaped here.  ``type_line=False`` lets a caller emitting several label
    sets of the same family write the ``# TYPE`` comment once.
    """
    if isinstance(hist, Histogram):
        hist = hist.state()
    base = "".join(
        f'{k}="{escape_label(v)}",' for k, v in (labels or {}).items())
    plain = "{" + base.rstrip(",") + "}" if base else ""
    lines = [f"# TYPE {name} histogram"] if type_line else []
    cum = 0
    for b, c in zip(hist["buckets"], hist["counts"]):
        cum += c
        lines.append(f'{name}_bucket{{{base}le="{_fmt(b)}"}} {cum}')
    cum += hist["counts"][len(hist["buckets"])]
    lines.append(f'{name}_bucket{{{base}le="+Inf"}} {cum}')
    lines.append(f"{name}_sum{plain} {hist['sum']}")
    lines.append(f"{name}_count{plain} {hist['count']}")
    return lines


# ---------------------------------------------------------------------------
# Stall clock
# ---------------------------------------------------------------------------


class LoopClock:
    """How late an asyncio loop runs what is due: ``run()`` sleeps
    ``PERIOD_S`` over and over and books each sleep's overshoot.

    ``lag_s`` / ``ticks`` is the loop's mean lag (every coroutine that was
    ready waited about that long for its turn); ``stall_s`` sums only the
    overshoots of ``STALL_S`` and more, so it reads 0 in a sound run and
    the pause's length where the process, or the whole machine, stopped.
    Plain attributes written by the one task on the loop's thread; a
    render reads them from that same thread.
    """

    PERIOD_S = 0.05
    STALL_S = 0.25

    def __init__(self, clock=time.perf_counter, sleep=asyncio.sleep):
        self._clock = clock
        self._sleep = sleep
        self.lag_s = 0.0
        self.ticks = 0
        self.stall_s = 0.0

    async def run(self) -> None:
        while True:
            t0 = self._clock()
            await self._sleep(self.PERIOD_S)
            over = max(0.0, self._clock() - t0 - self.PERIOD_S)
            self.ticks += 1
            self.lag_s += over
            if over >= self.STALL_S:
                self.stall_s += over

    def marks(self) -> tuple[float, int, float]:
        """(lag_s, ticks, stall_s) now: two of these bracket a stretch."""
        return self.lag_s, self.ticks, self.stall_s

    def render(self, lag: str, ticks: str, stall: str) -> list[str]:
        """Exposition lines under the caller's three family names."""
        return [f"# TYPE {lag} counter", f"{lag} {self.lag_s:.6f}",
                f"# TYPE {ticks} counter", f"{ticks} {self.ticks}",
                f"# TYPE {stall} counter", f"{stall} {self.stall_s:.6f}"]


def clock_pair() -> dict:
    """``time.time()`` and ``time.perf_counter()`` read back to back: what
    lays request spans (wall clock) and the step profiler's records and
    annotations (perf_counter) of one process on one axis."""
    return {"time": time.time(), "perf_counter": time.perf_counter()}


# ---------------------------------------------------------------------------
# Span recorder
# ---------------------------------------------------------------------------


def wire_spans(spans) -> str:
    """Compact JSON for the ``x-lig-spans`` response header:
    ``[[name, start, end], ...]`` (epoch seconds, µs precision)."""
    return json.dumps(
        [[n, round(float(s), 6), round(float(e), 6)] for n, s, e in spans],
        separators=(",", ":"))


def parse_wire(value: str) -> list[tuple[str, float, float]]:
    """Tolerant inverse of ``wire_spans`` — foreign headers must never
    break a response relay."""
    try:
        rows = json.loads(value)
        return [(str(r[0]), float(r[1]), float(r[2]))
                for r in rows if len(r) >= 3]
    except (ValueError, TypeError, KeyError, IndexError):
        return []


# Annotation marker inside the flat ring: record[1] is a span name for
# spans, or this sentinel for (model, path, status) trace metadata.
_META = None


class Tracer:
    """Bounded per-process trace recorder.

    The HOT PATH is a single ``deque.append`` of a tuple onto a flat,
    maxlen-bounded ring (GIL-atomic — no lock, no per-trace dict, no
    eviction bookkeeping): record() sits on the proxy's per-request path.
    Grouping spans into per-trace JSON happens at EXPORT (/debug/traces),
    which is a debug endpoint and can afford the O(ring) walk.

    ``capacity`` counts traces; the span ring holds ``capacity * 16``
    records, so old traces age out naturally.  Sampling is decided per
    TRACE (deterministic hash of the id), so multi-process traces are
    complete or absent, never partial.

    Every record carries a monotonic sequence number (the events.py
    journal convention), so ``/debug/traces?since=<seq>`` serves
    incremental reads — the fleet collector and ``--watch`` tooling poll
    deltas instead of re-shipping the whole ring.  The bump is a plain
    attribute increment, not a lock: recorders run under the GIL and a
    rare duplicate seq under thread races costs a poller one re-shipped
    span, never a lost one.
    """

    def __init__(self, capacity: int | None = None,
                 sample: float | None = None, enabled: bool | None = None):
        if capacity is None:
            capacity = int(os.environ.get("LIG_TRACE_CAPACITY", "256"))
        if sample is None:
            sample = float(os.environ.get("LIG_TRACE_SAMPLE", "1.0"))
        if enabled is None:
            enabled = os.environ.get("LIG_TRACE", "1") not in ("0", "false")
        self.capacity = max(1, capacity)
        self.sample = sample
        self.enabled = enabled
        self._seq = 0
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity * 16)

    @property
    def seq(self) -> int:
        """Head sequence number (the newest record's seq)."""
        return self._seq

    def sampled(self, trace_id: str) -> bool:
        if not self.enabled:
            return False
        if self.sample >= 1.0:
            return True  # default: no hash on the hot path
        if self.sample <= 0.0:
            return False
        h = hashlib.blake2b(trace_id.encode(), digest_size=8).digest()
        return int.from_bytes(h, "big") / 2.0 ** 64 < self.sample

    def record(self, trace_id: str, name: str, start: float, end: float,
               **attrs) -> None:
        if not trace_id or not self.sampled(trace_id):
            return
        self._seq = seq = self._seq + 1
        self._ring.append(
            (seq, trace_id, name, float(start), float(end), attrs or None))

    def record_wire(self, trace_id: str, value: str | None) -> None:
        """Merge spans from a downstream ``x-lig-spans`` header."""
        if not value or not trace_id or not self.sampled(trace_id):
            return
        for n, s, e in parse_wire(value):
            self._seq = seq = self._seq + 1
            self._ring.append((seq, trace_id, n, s, e, None))

    def annotate(self, trace_id: str, model: str | None = None,
                 path: str | None = None, status: str | None = None) -> None:
        if not trace_id or not self.sampled(trace_id):
            return
        self._seq = seq = self._seq + 1
        self._ring.append((seq, trace_id, _META, model, path, status))

    # -- export (the /debug/traces JSON shape) ------------------------------

    def _collect(self, since: int = 0) -> "collections.OrderedDict[str, dict]":
        """Group the flat ring into trace dicts, ordered by last activity.
        ``since`` skips records with seq <= since (the incremental-cursor
        read); each trace carries ``seq`` = its newest included record."""
        traces: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
        for rec in list(self._ring):  # snapshot: appends may race the walk
            seq, tid = rec[0], rec[1]
            if seq <= since:
                continue
            t = traces.get(tid)
            if t is None:
                t = traces[tid] = {"trace_id": tid, "model": "", "path": "",
                                   "status": "", "seq": seq, "_min_seq": seq,
                                   "spans": []}
            else:
                traces.move_to_end(tid)
            t["seq"] = max(t["seq"], seq)
            t["_min_seq"] = min(t["_min_seq"], seq)
            if rec[2] is _META:
                _, _, _, model, path, status = rec
                if model is not None:
                    t["model"] = model
                if path is not None:
                    t["path"] = path
                if status is not None:
                    t["status"] = str(status)
            else:
                _, _, name, s, e, attrs = rec
                t["spans"].append(
                    {"name": name, "start": round(s, 6), "end": round(e, 6),
                     **({"attrs": attrs} if attrs else {})})
        return traces

    @staticmethod
    def _export(t: dict) -> dict:
        spans = sorted(t["spans"], key=lambda x: (x["start"], x["end"]))
        t_created = spans[0]["start"] if spans else 0.0
        t = {k: v for k, v in t.items() if k != "_min_seq"}
        return {**t, "t_created": t_created, "spans": spans}

    def get(self, trace_id: str) -> dict | None:
        t = self._collect().get(trace_id)
        return self._export(t) if t is not None else None

    def recent(self, limit: int = 64) -> list[dict]:
        """Most-recently-active-first trace dicts."""
        traces = self._collect()
        out = [self._export(t) for t in
               list(traces.values())[-max(0, limit):]]
        out.reverse()
        return out

    def since(self, since: int, limit: int = 1024) -> tuple[list[dict], int]:
        """(partial trace dicts holding only records with seq > ``since``,
        next_since cursor).  The page is the ``limit`` traces whose OLDEST
        new record is earliest, and on truncation the cursor retreats to
        just before the first excluded trace's oldest record — resuming
        from it can re-ship a few records of an included trace (the
        stitcher dedups spans) but can never skip one, the events.py
        lossless-paging contract lifted to trace granularity."""
        traces = sorted(self._collect(since).values(),
                        key=lambda t: t["_min_seq"])
        limit = max(0, limit)
        page, excluded = traces[:limit], traces[limit:]
        if excluded:
            next_since = min(t["_min_seq"] for t in excluded) - 1
        else:
            next_since = max((t["seq"] for t in page), default=self._seq)
        return [self._export(t) for t in page], next_since


def debug_traces_payload(tracer: Tracer, query) -> dict:
    """The shared ``/debug/traces`` response body: ``?trace_id=`` exact
    filter, ``?limit=`` count cap (1..1024, default 64), and the
    incremental cursor ``?since=<seq>`` (the /debug/events contract:
    poll with ``since=next_since`` until ``next_since == seq``) returning
    only records newer than the cursor, grouped per trace.  ``clock`` is
    this process's ``clock_pair``.  One contract for the proxy and api_http
    endpoints."""
    clock = clock_pair()
    trace_id = query.get("trace_id")
    if trace_id:
        t = tracer.get(trace_id)
        return {"traces": [t] if t else [], "seq": tracer.seq,
                "clock": clock}
    try:
        limit = max(1, min(int(query.get("limit", "64")), 1024))
    except ValueError:
        limit = 64
    raw_since = query.get("since")
    if raw_since is not None:
        try:
            since = max(0, int(raw_since))
        except ValueError:
            since = 0
        rows, next_since = tracer.since(since, limit)
        return {"traces": rows, "seq": tracer.seq,
                "next_since": next_since, "clock": clock}
    return {"traces": tracer.recent(limit), "seq": tracer.seq,
            "clock": clock}
