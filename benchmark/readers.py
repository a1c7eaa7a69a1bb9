"""The readers: the small fixed set of ways a metric is taken from what a run
collected.  A metric is a data file ``metrics/<name>.json`` naming one reader
and its arguments, so most new metrics are data.

The set grows only by a ``benchmark`` PR.  What a program's PR can add
without one is a metric file and, for a kernel's roofline share, a file of
shapes beside it: ``kernel_roofline`` finds its bytes function by name.

A reader gets its ``args`` and the run's ``ctx`` (see ``run.collect``) and
returns a number, or None where it finds nothing to read — the harness then
leaves that metric out of the line.

Stdlib only.
"""

from __future__ import annotations

import importlib
import re
import statistics

from benchmark import peaks, shapes, trace_reduce


# -- helpers ---------------------------------------------------------------

def quantile(values: list[float], q: float) -> float | None:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; None for no values."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def prom_samples(text: str, family: str, labels: dict | None = None):
    """Values of the samples of ``family`` whose labels include ``labels``."""
    want = [f'{k}="{v}"' for k, v in (labels or {}).items()]
    out = []
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        m = _SAMPLE.match(line)
        if not m or m.group(1) != family:
            continue
        lab = m.group(2) or ""
        if all(w in lab for w in want):
            try:
                out.append((lab, float(m.group(3))))
            except ValueError:
                pass
    return out


def prom_value(text: str, family: str, labels: dict | None = None):
    samples = prom_samples(text, family, labels)
    return sum(v for _, v in samples) if samples else None


def _delta(ctx: dict, spec: dict) -> float | None:
    """Sum over replicas of after - before of one family."""
    total, seen = 0.0, False
    for before, after in zip(ctx["prom_before"], ctx["prom_after"]):
        a = prom_value(after, spec["family"], spec.get("labels"))
        if a is None:
            continue
        b = prom_value(before, spec["family"], spec.get("labels")) or 0.0
        total += a - b
        seen = True
    return total if seen else None


def _window_results(ctx: dict) -> list:
    return [r for r in ctx["results"] if r.in_window]


# -- readers ---------------------------------------------------------------

def prom_delta(args: dict, ctx: dict):
    """scale x (sum over replicas of the growth of ``num``) over the growth
    of ``den``: another family, ``"window_s"`` (once per replica, so a share
    of time comes out as the replicas' mean), ``"client_prompt_tokens"``
    (prompt tokens of the window's answered requests) or nothing."""
    num = _delta(ctx, args["num"])
    if num is None:
        return None
    den = args.get("den")
    if den is None:
        d = 1.0
    elif den == "window_s":
        d = ctx["window_s"] * len(ctx["prom_after"])
    elif den == "client_prompt_tokens":
        d = float(sum(r.prompt_tokens for r in _window_results(ctx)
                      if r.t_first is not None))
    else:
        d = _delta(ctx, den)
    if not d:
        return None
    return args.get("scale", 1.0) * num / d


def prom_hist_quantile(args: dict, ctx: dict):
    """A quantile of a Prometheus histogram's growth over the window,
    interpolated inside its bucket (replicas pooled)."""
    fam = args["family"] + "_bucket"
    growth: dict[float, float] = {}
    for before, after in zip(ctx["prom_before"], ctx["prom_after"]):
        b = dict(prom_samples(before, fam, args.get("labels")))
        for lab, v in prom_samples(after, fam, args.get("labels")):
            le = re.search(r'le="([^"]+)"', lab).group(1)
            edge = float("inf") if le == "+Inf" else float(le)
            growth[edge] = growth.get(edge, 0.0) + v - b.get(lab, 0.0)
    edges = sorted(growth)
    if not edges or growth[edges[-1]] <= 0:
        return None
    target = args["q"] * growth[edges[-1]]
    prev_edge, prev_cum = 0.0, 0.0
    for e in edges:
        if growth[e] >= target:
            if e == float("inf"):
                return args.get("scale", 1.0) * prev_edge
            span = growth[e] - prev_cum
            frac = (target - prev_cum) / span if span > 0 else 1.0
            return args.get("scale", 1.0) * (
                prev_edge + (e - prev_edge) * frac)
        prev_edge, prev_cum = e, growth[e]
    return None


def span_quantile(args: dict, ctx: dict):
    """A quantile over the window's spans named ``span`` — of an attribute,
    or of the span's own length — from the gateway's or the servers'
    ``/debug/traces``."""
    if args["source"] == "gateway":
        traces = ctx["gateway_traces"]
    else:
        traces = [t for per in ctx["server_traces"] for t in per]
    values = []
    for t in traces:
        for s in t.get("spans", ()):
            if s["name"] != args["span"]:
                continue
            if args.get("attr"):
                v = (s.get("attrs") or {}).get(args["attr"])
                if v is not None:
                    values.append(float(v))
            else:
                values.append(s["end"] - s["start"])
    v = quantile(values, args["q"])
    return None if v is None else args.get("scale", 1.0) * v


def profile_field(args: dict, ctx: dict):
    """The mean of one field of the ``/debug/profile`` per-dispatch records
    gathered through the window (polled, merged by ``seq``), over the
    records of one phase."""
    values = [rec[args["field"]] for per in ctx["profile_records"]
              for rec in per
              if rec.get("phase") == args.get("phase", rec.get("phase"))]
    if not values:
        return None
    return args.get("scale", 1.0) * statistics.fmean(values)


def poll_peak(args: dict, ctx: dict):
    """The peak of a polled gauge over the window, the fullest replica's."""
    peaks_ = [p for p in ctx["gauge_peaks"].get(args["gauge"], [])
              if p is not None]
    return args.get("scale", 1.0) * max(peaks_) if peaks_ else None


def phase(args: dict, ctx: dict):
    return ctx["phases"].get(args["name"])


def device_field(args: dict, ctx: dict):
    """A field of ``memory_stats()`` from ``/debug/device``, the largest
    over replicas and devices."""
    vals = [(d.get("memory_stats") or {}).get(args["field"])
            for dev in ctx["device"] for d in dev["devices"]]
    vals = [v for v in vals if isinstance(v, (int, float))]
    return args.get("scale", 1.0) * max(vals) if vals else None


def trace_idle(args: dict, ctx: dict):
    """100 x (1 - busy / window) of the traced device."""
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def trace_op_time(args: dict, ctx: dict):
    """Device time of the operations whose name matches ``regex``: as a
    share of the traced window (``per`` = "window", in %) or in seconds."""
    tr = ctx.get("trace")
    if not tr or "op_totals" not in tr:
        return None
    pat = re.compile(args["regex"])
    total = sum(s for n, s in tr["op_totals"] if pat.search(n))
    if not total:
        return None
    if args.get("per") == "window":
        return 100.0 * total / tr["window_s"]
    return args.get("scale", 1.0) * total


def roofline(args: dict, ctx: dict):
    """Share of the HBM roofline reached by the decode program: the bytes
    one step must read (``shapes.decode_step_bytes`` at the window's mean
    live rows and mean context) over the chip's published bandwidth, over
    the program's median device time in the trace.  A sparse model's expert
    bytes are those of the experts uniform routing of the configuration's
    own ``n_experts_per_token`` would touch, or, under ``"experts":
    "counted"``, of the experts the window's counters say a layer-step read
    (``tpu:moe_experts_touched_total`` over ``tpu:moe_layer_steps_total``,
    which the prefill and chunk programs' layer-steps ride too)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("modules"):
        return None
    mod = trace_reduce.decode_module(tr, args.get("min_module_s", 0.005))
    rows = profile_field({"field": "active", "phase": "decode"}, ctx)
    if mod is None or not rows:
        return None
    done = [r for r in _window_results(ctx) if r.tokens > 0]
    weight = sum(r.tokens for r in done)
    if not weight:
        return None
    context = sum(r.tokens * (r.prompt_tokens + r.tokens / 2.0)
                  for r in done) / weight
    model = ctx["config"]["model"]
    experts_read = None
    if model.get("n_experts") and args.get("experts") == "counted":
        experts_read = prom_delta(
            {"num": {"family": "tpu:moe_experts_touched_total"},
             "den": {"family": "tpu:moe_layer_steps_total"}}, ctx)
        if experts_read is None:
            return None
    nbytes = shapes.decode_step_bytes(
        model, rows, context, weights=args.get("weights", "int8"),
        kv=args.get("kv", "bfloat16"),
        experts_per_token=model.get("n_experts_per_token", 2),
        experts_read=experts_read)
    bw = peaks.device_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / mod["median_s"]


def kernel_roofline(args: dict, ctx: dict):
    """Share of the HBM roofline reached by the operations whose name
    matches ``regex``: the bytes they must move over the chip's published
    bandwidth, over their device time.  ``bytes_fn`` names a function under
    ``benchmark/`` as ``"<module>:<function>"``; it gets the configuration's
    ``model`` group and ``inputs``, each the window's growth of a counter
    (``{"family": ...}``) or the mean of a dispatch-record field
    (``{"profile_field": ..., "phase": ...}``), and returns the bytes of the
    whole window.  The trace holds the window's last seconds only, so the
    kernels' traced time is taken up to the window by WORK and not by the
    clock: by the decode programs the window dispatched
    (``tpu:dispatch_steps_count``, all replicas) over those the trace holds
    (replica 0's) — an open loop's traced seconds are not as busy as its
    window.  Totals against totals, never a mean times a count of steps."""
    traced_s = trace_op_time({"regex": args["regex"]}, ctx)
    if not traced_s:
        return None
    inputs = {}
    for key, spec in args["inputs"].items():
        if "family" in spec:
            value = _delta(ctx, spec)
        else:
            value = profile_field({"field": spec["profile_field"],
                                   "phase": spec.get("phase", "decode")}, ctx)
        if not value:
            return None
        inputs[key] = value
    mod = trace_reduce.decode_module(ctx["trace"],
                                     args.get("min_module_s", 0.001))
    dispatched = _delta(ctx, {"family": "tpu:dispatch_steps_count"})
    if mod is None or not dispatched:
        return None
    module, _, function = args["bytes_fn"].partition(":")
    fn = getattr(importlib.import_module("benchmark." + module), function)
    nbytes = fn(ctx["config"]["model"], inputs)
    kernel_s = traced_s * dispatched / mod["count"]
    bw = peaks.device_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / kernel_s


def client_quantile(args: dict, ctx: dict):
    """A quantile over the window's requests of ``ttft`` (from when each was
    due), ``tpot`` (mean gap between its output tokens) or ``late`` (how
    late it left), in ms.  A request that failed has no time and is counted
    in ``failed``."""
    field = {"ttft": "ttft_s", "tpot": "tpot_s", "late": "late_s"}[
        args["field"]]
    values = [getattr(r, field) for r in _window_results(ctx)
              if r.error is None]
    v = quantile([x for x in values if x is not None], args["q"])
    return None if v is None else 1000.0 * v


def client_tokens_per_s(args: dict, ctx: dict):
    """Output tokens that arrived inside the window, per second of it."""
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["window_s"]
    n = sum(k for r in ctx["results"] for t, k in r.chunks if t0 <= t < t1)
    return n / ctx["window_s"] if n else None


def client_slo_good(args: dict, ctx: dict):
    """Share of the window's requests that met both limits of the traffic
    file; a failed request met neither."""
    rs = _window_results(ctx)
    rs = [r for r in rs if not r.cut]
    if not rs:
        return None
    slo = ctx["traffic"]["slo"]
    good = sum(1 for r in rs if r.ok and r.ttft_s is not None
               and 1000.0 * r.ttft_s <= slo["ttft_ms"]
               and (r.tpot_s is None or 1000.0 * r.tpot_s <= slo["tpot_ms"]))
    return 100.0 * good / len(rs)


def client_imbalance(args: dict, ctx: dict):
    """(max - mean) / mean of the window's requests by ``x-served-by``."""
    counts = {name: 0 for name in ctx["pod_names"]}
    for r in _window_results(ctx):
        if r.served_by in counts:
            counts[r.served_by] += 1
    if len(counts) < 2 or not sum(counts.values()):
        return None
    mean = sum(counts.values()) / len(counts)
    return 100.0 * (max(counts.values()) - mean) / mean


READERS = {f.__name__: f for f in (
    prom_delta, prom_hist_quantile, span_quantile, profile_field, poll_peak,
    phase, device_field, trace_idle, trace_op_time, roofline,
    client_quantile, client_tokens_per_s, client_slo_good, client_imbalance,
    kernel_roofline)}
