"""Per-phase latency report from tracing output.

Reads either shape and prints a per-phase p50/p95/p99 table:

- a ``/debug/traces`` JSON document (proxy or api_http; file path, URL, or
  ``-`` for stdin): ``{"traces": [{"spans": [{"name", "start", "end"}]}]}``
  — each span's duration is one sample of its phase;
- a loadgen ``--trace-out`` file: ``{"phases": {name: [seconds, ...]}}``.

Usage:
  python tools/trace_report.py http://localhost:8081/debug/traces
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


def multi_replica_samples(sources: list[tuple[str, dict]]) -> dict:
    """Phase samples over SEVERAL replicas' /debug/traces payloads,
    merged through the fleet stitcher (gateway/fleetobs.py) — duplicate
    spans (the gateway's ``x-lig-spans`` copy of a server span) fold and
    per-source clock skew normalizes, so the table is the fleet truth
    instead of one replica's view reported as the whole story."""
    from llm_instance_gateway_tpu.gateway import fleetobs

    return phase_samples(
        {"traces": fleetobs.stitch_traces(sources, limit=1024)})


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
        samples = multi_replica_samples(sources)
    elif args.source:
        samples = phase_samples(load(args.source))
    else:
        parser.error("need a source, --url, or --replicas")
    rows = phase_table(samples)
    if args.json:
        print(json.dumps(rows))
    else:
        print(format_table(rows))
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
