"""From a profiler trace to numbers: the reduction every PR shares.

``summarise`` is pure (lists of events in, a dict out) and is tested on a
small recorded trace; ``summarise_xplane`` reads JAX's ``.xplane.pb`` in the
process that took it (the server wrapper), so the benchmark's parent stays
off JAX.

An event is ``[name, start_ns, duration_ns]``.  ``ops`` are the device's
operations (the TPU plane's "XLA Ops" line), ``modules`` the executions of
whole compiled programs (its "XLA Modules" line).

- busy: the union of the intervals in which an operation ran;
- window: from the first operation's start to the last one's end;
- idle gaps: the holes of that union, each named by the programs that ran
  before and after it (the device trace knows no host phase; the engine
  thread's own split is ``engine.host_gap_pct``);
- per program: count, total, median — the decode program is the one run
  most often among those that take longer than a floor.
"""

from __future__ import annotations

import bisect
import re
import statistics

TOP = 10
_SAFE = re.compile(r"[^A-Za-z0-9_.:\-]+")


def clean(name: str, limit: int = 64) -> str:
    return _SAFE.sub("_", name).strip("_")[:limit] or "unnamed"


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def summarise(ops: list, modules: list, top: int = TOP) -> dict:
    ops = [(n, float(s), float(d)) for n, s, d in ops if d > 0]
    if not ops:
        return {"error": "no device operation in the trace"}
    busy = _union([(s, s + d) for _, s, d in ops])
    t_first, t_last = busy[0][0], busy[-1][1]
    busy_ns = sum(e - s for s, e in busy)

    by_op: dict[str, float] = {}
    for n, _, d in ops:
        by_op[n] = by_op.get(n, 0.0) + d
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    device_ops = ranked[:top]

    mods = sorted((float(s), float(s) + float(d), n) for n, s, d in modules)
    starts = [m[0] for m in mods]

    def module_at_or_before(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return clean(mods[i][2], 40) if i >= 0 else "start"

    def module_after(t: float) -> str:
        i = bisect.bisect_left(starts, t)
        return clean(mods[i][2], 40) if i < len(mods) else "end"

    gaps = []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    idle_gaps = [
        [f"after_{module_at_or_before(e0)}_before_{module_after(s1 - 1)}",
         g / 1e9] for g, e0, s1 in gaps[:top]]

    per_module: dict[str, list[float]] = {}
    for s, e, n in mods:
        per_module.setdefault(n, []).append(e - s)
    module_stats = {
        clean(n, 80): {"count": len(ds), "total_s": sum(ds) / 1e9,
                       "median_s": statistics.median(ds) / 1e9}
        for n, ds in per_module.items()}

    return {
        "window_s": (t_last - t_first) / 1e9,
        "busy_s": busy_ns / 1e9,
        "n_ops": len(ops),
        "device_ops": [[clean(n), d / 1e9] for n, d in device_ops],
        "idle_gaps": idle_gaps,
        "op_totals": [[clean(n, 120), d / 1e9] for n, d in ranked[:300]],
        "modules": module_stats,
    }


def decode_module(summary: dict, min_s: float = 0.005) -> dict | None:
    """The decode program of a traced window: of the programs whose median
    run takes at least ``min_s`` (which leaves out the small samplers and
    cache inserts), the one that ran most often."""
    best = None
    for name, st in (summary.get("modules") or {}).items():
        if st["median_s"] >= min_s and (best is None
                                        or st["count"] > best["count"]):
            best = dict(st, name=name)
    return best


def summarise_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` and reduce its first device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    device = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    ops: list = []
    modules: list = []
    if device:
        plane_name = device[0].name
        for ln in device[0].lines:
            evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
            if ln.name == "XLA Ops":
                ops = evs
            elif ln.name == "XLA Modules":
                modules = evs
    else:
        # No TPU plane: a CPU rehearsal.  The XLA client's threads stand in,
        # so that the path is rehearsed; the parent prints no result there.
        plane_name = "/host:CPU (rehearsal)"
        for p in planes:
            if p.name != "/host:CPU":
                continue
            for ln in p.lines:
                if ln.name.startswith("tf_XLAPjRtCpuClient"):
                    ops += [[e.name, e.start_ns, e.duration_ns]
                            for e in ln.events]
    out = summarise(ops, modules)
    out["plane"] = plane_name
    return out
