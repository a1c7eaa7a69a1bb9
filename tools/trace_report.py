"""Per-phase latency report from tracing output.

Reads either shape and prints a per-phase p50/p95/p99 table:

- a ``/debug/traces`` JSON document (proxy or api_http; file path, URL, or
  ``-`` for stdin): ``{"traces": [{"spans": [{"name", "start", "end"}]}]}``
  — each span's duration is one sample of its phase;
- a loadgen ``--trace-out`` file: ``{"phases": {name: [seconds, ...]}}``.

Over ``/debug/traces`` documents it then prints **first-token time by
part**, in the order a streamed request lives them (p50 and p90 over the
requests that have the part): the gateway before it posts (``pre_s`` of
``gateway.stream``), the server's ``server.accept`` -> ``engine.queue_wait``
-> ``engine.prefill`` (and of it what the engine thread's phase stack
charged: ``stage_s`` / ``wait_s`` / ``emit_s``, a streamed prompt's summed
over its chunks, and ``device_s``, what its prompt programs held of the
device's queue) -> ``server.first_write``,
the residual between the gateway's ``first_chunk_s`` and those four
(network and HTTP; where both processes share a clock, split into the way in
and the way out), the gateway's way out, and their sum
``ttft_s``; then the per-token lag (``relay_mean_s``, ``write_lag_max_s``);
and under the table what a prompt cost in programs and computed positions
(``programs`` / ``positions`` of ``engine.prefill``, beside its tokens).
The server's spans of a streamed request live on the replica: give both
``/debug/traces`` (``--url`` twice, or ``--replicas``) to see the whole
table.

Usage:
  python tools/trace_report.py http://localhost:8081/debug/traces
  python tools/trace_report.py --url http://localhost:8081/debug/traces \
      --url http://localhost:8000/debug/traces
  python tools/trace_report.py traces.json --json
  python -m llm_instance_gateway_tpu.gateway.loadgen --requests 2000 \
      --trace-out /tmp/phases.json && python tools/trace_report.py /tmp/phases.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Runnable as `python tools/trace_report.py` from anywhere: the fleet
# stitcher import (multi-replica mode) needs the repo root on the path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(source: str) -> dict:
    """Load a traces/phases JSON document from a path, URL, or stdin."""
    if source == "-":
        return json.load(sys.stdin)
    if source.startswith(("http://", "https://")):
        import urllib.request

        with urllib.request.urlopen(source, timeout=10) as resp:
            return json.loads(resp.read().decode())
    with open(source) as f:
        return json.load(f)


def phase_samples(doc: dict) -> dict[str, list[float]]:
    """Phase name -> duration samples (seconds), from either input shape."""
    if "phases" in doc:
        return {str(k): [float(x) for x in v]
                for k, v in doc["phases"].items()}
    samples: dict[str, list[float]] = {}
    for trace in doc.get("traces", []):
        for span in trace.get("spans", []):
            try:
                d = float(span["end"]) - float(span["start"])
            except (KeyError, TypeError, ValueError):
                continue
            samples.setdefault(str(span.get("name", "?")), []).append(
                max(0.0, d))
    return samples


def percentile(xs: list[float], p: float) -> float:
    """Nearest-rank percentile over a SORTED sample list."""
    return xs[min(len(xs) - 1, int(p * len(xs)))]


def phase_table(samples: dict[str, list[float]]) -> list[dict]:
    """One row per phase: n, p50/p95/p99 and mean in milliseconds, sorted
    by p50 descending (the biggest time sinks lead)."""
    rows = []
    for name, xs in samples.items():
        if not xs:
            continue
        xs = sorted(xs)
        rows.append({
            "phase": name,
            "n": len(xs),
            "p50_ms": round(percentile(xs, 0.50) * 1e3, 3),
            "p95_ms": round(percentile(xs, 0.95) * 1e3, 3),
            "p99_ms": round(percentile(xs, 0.99) * 1e3, 3),
            "mean_ms": round(sum(xs) / len(xs) * 1e3, 3),
        })
    rows.sort(key=lambda r: (-r["p50_ms"], r["phase"]))
    return rows


def _span_index(trace: dict) -> dict[str, dict]:
    """Span name -> the trace's first span of that name."""
    out: dict[str, dict] = {}
    for span in trace.get("spans", []):
        out.setdefault(str(span.get("name")), span)
    return out


def _length(span: dict | None) -> float | None:
    return None if span is None else max(
        0.0, float(span["end"]) - float(span["start"]))


SERVER_SPANS = ("server.accept", "engine.queue_wait", "engine.prefill",
                "server.first_write")
# The rows of the first-token table, in the order a streamed request lives
# them; an indented row is a share of the one above it.
PARTS = (
    "gateway: entry -> POST (pre_s)",
    "server.accept",
    "engine.queue_wait",
    "engine.prefill",
    "  prefill.stage (stage_s)",
    "  prefill.wait (wait_s)",
    "  prefill.emit (emit_s)",
    "  on the device's queue (device_s)",
    "server.first_write",
    "network and HTTP (first_chunk_s - the four)",
    "  way in: POST -> handler entry",
    "  way out: first write -> gateway has it",
    "POST -> first chunk (first_chunk_s)",
    "gateway: first chunk -> client (rest of ttft_s)",
    "= ttft_s of gateway.stream",
    "per token: gateway received -> written (relay_mean_s)",
    "per token: engine emit -> written, largest (write_lag_max_s)",
)


def first_token_parts(trace: dict) -> dict[str, float]:
    """One request's first-token time by part (seconds, keys of ``PARTS``),
    as far as the trace holds the spans and attributes."""
    spans = _span_index(trace)
    stream = (spans.get("gateway.stream") or {}).get("attrs") or {}
    prefill = (spans.get("engine.prefill") or {}).get("attrs") or {}
    decode = (spans.get("engine.decode") or {}).get("attrs") or {}
    server = {name: _length(spans.get(name)) for name in SERVER_SPANS}
    parts: dict[str, float | None] = {
        "gateway: entry -> POST (pre_s)": stream.get("pre_s"),
        **server,
        "  prefill.stage (stage_s)": prefill.get("stage_s"),
        "  prefill.wait (wait_s)": prefill.get("wait_s"),
        "  prefill.emit (emit_s)": prefill.get("emit_s"),
        "  on the device's queue (device_s)": prefill.get("device_s"),
        "per token: gateway received -> written (relay_mean_s)":
            stream.get("relay_mean_s"),
        "per token: engine emit -> written, largest (write_lag_max_s)":
            decode.get("write_lag_max_s"),
    }
    if "ttft_s" in stream:
        first_chunk = stream["first_chunk_s"]
        if None in server.values():  # the replica's spans are not here
            parts["POST -> first chunk (first_chunk_s)"] = first_chunk
        else:
            parts["network and HTTP (first_chunk_s - the four)"] = (
                first_chunk - sum(server.values()))
            if not trace.get("skew"):
                # Absolute stamps of two processes: only where they share
                # a clock (one host; the stitcher found no skew to shift).
                posted = float(spans["gateway.stream"]["start"])
                parts["  way in: POST -> handler entry"] = (
                    float(spans["server.accept"]["start"]) - posted)
                parts["  way out: first write -> gateway has it"] = (
                    posted + first_chunk
                    - float(spans["server.first_write"]["end"]))
        parts["gateway: first chunk -> client (rest of ttft_s)"] = (
            stream["ttft_s"] - stream["pre_s"] - first_chunk)
        parts["= ttft_s of gateway.stream"] = stream["ttft_s"]
    return {k: float(v) for k, v in parts.items() if v is not None}


def first_token_table(traces: list[dict]) -> list[dict]:
    """One row per part in the order of ``PARTS``: n, p50 and p90 in
    milliseconds over the traces that have it."""
    samples: dict[str, list[float]] = {}
    for trace in traces:
        for name, v in first_token_parts(trace).items():
            samples.setdefault(name, []).append(v)
    rows = []
    for name in PARTS:
        xs = sorted(samples.get(name, ()))
        if xs:
            rows.append({"part": name, "n": len(xs),
                         "p50_ms": round(percentile(xs, 0.50) * 1e3, 3),
                         "p90_ms": round(percentile(xs, 0.90) * 1e3, 3)})
    return rows


# What a prompt cost, by attribute of ``engine.prefill``: not times, so not
# rows of the first-token table.
PROMPT_COST = (("prompt tokens", "prompt_tokens"),
               ("prompt programs", "programs"),
               ("positions computed (padding included)", "positions"))


def prompt_cost_rows(traces: list[dict]) -> list[dict]:
    """n, p50 and p90 of each ``PROMPT_COST`` attribute over the requests
    whose ``engine.prefill`` span says what its prompt cost (none from a
    replica older than the attributes)."""
    spans = [(_span_index(t).get("engine.prefill") or {}).get("attrs") or {}
             for t in traces]
    spans = [a for a in spans if "programs" in a]
    rows = []
    for label, key in PROMPT_COST if spans else ():
        xs = sorted(a[key] for a in spans)
        rows.append({"a request's": label, "n": len(xs),
                     "p50": percentile(xs, 0.50), "p90": percentile(xs, 0.90)})
    return rows


def format_table(rows: list[dict], headers: tuple | None = None) -> str:
    if not rows:
        return "(no phase samples)"
    headers = tuple(headers or rows[0].keys())
    widths = [max(len(h), *(len(str(r[h])) for r in rows)) for h in headers]
    def fmt(vals):
        return "  ".join(str(v).rjust(w) if i else str(v).ljust(w)
                         for i, (v, w) in enumerate(zip(vals, widths)))
    lines = [fmt(headers), fmt(["-" * w for w in widths])]
    lines += [fmt([r[h] for h in headers]) for r in rows]
    return "\n".join(lines)


def multi_replica_traces(sources: list[tuple[str, dict]]) -> dict:
    """SEVERAL replicas' /debug/traces payloads as one document, merged
    through the fleet stitcher (gateway/fleetobs.py) — duplicate spans (the
    gateway's ``x-lig-spans`` copy of a server span) fold and per-source
    clock skew normalizes, so the tables are the fleet truth instead of
    one replica's view reported as the whole story."""
    from llm_instance_gateway_tpu.gateway import fleetobs

    return {"traces": fleetobs.stitch_traces(sources, limit=1024)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="per-phase latency table from /debug/traces JSON or "
                    "loadgen --trace-out output")
    parser.add_argument("source", nargs="?",
                        help="file path, http(s) URL, or - for stdin")
    parser.add_argument("--url", action="append", default=[],
                        help="a replica's /debug/traces URL (repeatable: "
                             "multiple replicas merge through the fleet "
                             "stitcher instead of one view posing as the "
                             "whole story)")
    parser.add_argument("--replicas",
                        help="CSV of replica base URLs; each fetches "
                             "<base>/debug/traces and merges like --url")
    parser.add_argument("--json", action="store_true",
                        help="emit the rows as one JSON line instead of a "
                             "table")
    args = parser.parse_args(argv)
    urls = list(args.url)
    if args.replicas:
        urls += [u.strip().rstrip("/") + "/debug/traces"
                 for u in args.replicas.split(",") if u.strip()]
    if urls:
        sources = [(u, load(u)) for u in urls]
        if args.source:
            sources.append((args.source, load(args.source)))
        doc = multi_replica_traces(sources)
    elif args.source:
        doc = load(args.source)
    else:
        parser.error("need a source, --url, or --replicas")
    rows = phase_table(phase_samples(doc))
    if args.json:
        print(json.dumps(rows))
    else:
        print(format_table(rows))
        parts = first_token_table(doc.get("traces", []))
        if parts:
            print("\nfirst-token time by part, and the per-token lag:")
            print(format_table(parts))
        cost = prompt_cost_rows(doc.get("traces", []))
        if cost:
            print("\nwhat a prompt cost the device:")
            print(format_table(cost))
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
