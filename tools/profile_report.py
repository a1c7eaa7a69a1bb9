"""Dispatch-gap attribution table from the engine step-timeline profiler.

Reads a ``/debug/profile`` payload (URL, file path, or ``-`` for stdin —
including the ``profile`` section of a black-box dump) and renders where
the engine thread's wall went:

- the attribution table — dispatch / host-sync / idle shares (they tile
  the tracked engine-thread timeline, so they sum to 100%);
- per-phase dispatch walls (prefill vs decode vs spec) with counts and
  mean wall per dispatch;
- the engine thread's whole wall by phase (admit, prefill.stage / .wait /
  .emit, decode.plan / .stage / .wait / .readback / .emit / .account, idle,
  other; ``tpu:engine_phase_seconds_total``), and per decode dispatch its
  stage / wait / readback / emit parts;
- the prompt programs by the jitted program (``tpu:prompt_programs_total``,
  ``tpu:prompt_positions_total``, ``tpu:prompt_program_seconds_total``): how
  many, real and padded positions, milliseconds of the device's queue a
  program, share of the tracked time;
- a recent-dispatch summary from the record ring (mean batch occupancy,
  mean steps per dispatch, slot churn).

With ``--xplane FILE.xplane.pb`` (a ``jax.profiler`` trace of a live
replica) it reads the trace instead: the holes in the device's timeline (the
union of the TPU plane's "XLA Ops") against the ``engine.<phase>``
annotations the engine thread wrote into the same trace — for the ten
longest holes and for all of them, which phase the thread was in, and for
a hole that holds an admission the request's id, prompt length and bucket
(the ``engine.prefill.enqueue`` annotation's metadata: the ``request_id``
is the ``cmpl-`` id's tail, ``prompt_tokens`` and ``bucket`` are on the
``engine.prefill`` span of ``/debug/traces``) — and the device time of
the largest operations with the ``jax.named_scope`` each belongs to.

This is the evidence layer for the ROADMAP item-2 decode levers: every
"amortize the step loop" change must move the host-sync share DOWN on
this table versus a saved earlier payload (``--baseline``), not just a
throughput ratio.

Usage:
  python tools/profile_report.py http://localhost:8000/debug/profile
  python tools/profile_report.py saved_profile.json --baseline earlier.json
  python tools/profile_report.py dump.json --json
  python tools/profile_report.py --xplane trace/plugins/profile/*/*.xplane.pb
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.trace_report import load  # noqa: E402 — one loader, no drift


def extract_profile(doc: dict, pod: str | None = None) -> dict:
    """Accept a raw /debug/profile payload, a document carrying it under
    ``profile``, or a black-box dump whose ``profile`` section maps pod
    name -> snapshot (slo.write_blackbox's shape; unreachable pods carry
    error markers).  ``pod`` selects one replica from a dump; without it
    the first pod (sorted) with a valid snapshot is used, with a note on
    stderr when several were available."""
    if "attribution" in doc:
        return doc
    inner = doc.get("profile")
    if isinstance(inner, dict):
        if "attribution" in inner:
            return inner
        # Black-box dump shape: pod name -> snapshot-or-error-marker.
        valid = {name: snap for name, snap in sorted(inner.items())
                 if isinstance(snap, dict) and "attribution" in snap}
        if pod is not None:
            if pod in valid:
                return valid[pod]
            raise ValueError(
                f"pod {pod!r} has no profiler snapshot in this dump "
                f"(pods with one: {sorted(valid) or 'none'})")
        if valid:
            name, snap = next(iter(valid.items()))
            if len(valid) > 1:
                print(f"note: dump holds {len(valid)} pod snapshots; "
                      f"showing {name!r} (pick one with --pod)",
                      file=sys.stderr)
            return snap
    raise ValueError("no profiler payload found (expected an 'attribution' "
                     "key or a 'profile' section)")


def attribution_rows(profile: dict) -> list[dict]:
    """One row per bucket: seconds + share of the tracked total."""
    att = profile.get("attribution") or {}
    shares = att.get("shares") or {}
    rows = []
    for bucket, key in (("dispatch", "dispatch_seconds"),
                        ("host_sync", "host_sync_seconds"),
                        ("idle", "idle_seconds")):
        rows.append({
            "bucket": bucket,
            "seconds": round(float(att.get(key, 0.0)), 6),
            "share_pct": round(100.0 * float(shares.get(bucket, 0.0)), 3),
        })
    return rows


def phase_rows(profile: dict) -> list[dict]:
    """Per-phase dispatch wall: total seconds, dispatch count, mean wall
    per dispatch (from the wall histogram's _sum/_count)."""
    rows = []
    for phase, state in sorted((profile.get("hist") or {}).get(
            "wall", {}).items()):
        n = int(state.get("count", 0))
        total = float(state.get("sum", 0.0))
        rows.append({
            "phase": phase,
            "dispatches": n,
            "wall_s": round(total, 6),
            "mean_ms": round(total / n * 1e3, 3) if n else 0.0,
        })
    return rows


def record_summary(profile: dict) -> dict:
    """Aggregate view of the recent per-dispatch record ring."""
    records = [r for r in profile.get("records") or []
               if r.get("phase") != "prefill"]
    if not records:
        return {}
    occ = [r["active"] / r["slots"] for r in records if r.get("slots")]
    gaps = [r.get("gap_s", 0.0) for r in records]
    return {
        "recent_dispatches": len(records),
        "mean_occupancy": round(sum(occ) / len(occ), 4) if occ else None,
        "mean_steps_per_dispatch": round(
            sum(r.get("n_steps", 1) for r in records) / len(records), 2),
        "mean_gap_ms": round(sum(gaps) / len(gaps) * 1e3, 4),
        "slot_churn_events": sum(1 for r in records if r.get("slot_churn")),
    }


def thread_phase_rows(profile: dict) -> list[dict]:
    """The engine thread's wall by phase, largest first (empty for a
    payload from before the phase stack)."""
    phases = (profile.get("attribution") or {}).get("phases") or {}
    rows = [{"phase": name, "on": rec.get("on", ""),
             "seconds": round(float(rec.get("seconds", 0.0)), 6),
             "share_pct": round(100.0 * float(rec.get("share", 0.0)), 3)}
            for name, rec in phases.items()]
    return sorted(rows, key=lambda r: -r["seconds"])


def decode_split(profile: dict) -> dict:
    """Mean parts of the recent decode dispatches, in ms."""
    records = [r for r in profile.get("records") or []
               if r.get("phase") == "decode" and "stage_s" in r]
    if not records:
        return {}
    out = {"dispatches": len(records)}
    for key in ("wall_s", "stage_s", "wait_s", "readback_s", "emit_s"):
        out[key[:-2] + "_ms"] = round(
            1e3 * sum(r[key] for r in records) / len(records), 4)
    return out


def _per_decode_dispatch(profile: dict, key: str, mean_key: str) -> dict:
    """One counter of ``hist`` and its mean per decode dispatch; empty for
    a payload from before the counter."""
    hist = profile.get("hist") or {}
    if key not in hist:
        return {}
    n = int(((hist.get("wall") or {}).get("decode") or {}).get("count", 0))
    total = int(hist[key])
    return {key: total, "decode_dispatches": n,
            mean_key: round(total / n, 3) if n else 0.0}


def stage_ops_row(profile: dict) -> dict:
    """Host-to-device transfers and helper programs the engine issued to
    stage its decode dispatches (``tpu:decode_stage_ops_total``)."""
    return _per_decode_dispatch(profile, "stage_ops", "ops_per_dispatch")


def lora_rows_row(profile: dict) -> dict:
    """Live rows with a LoRA slot, summed over the decode steps
    (``tpu:lora_rows_total``), the decode steps that ran without the
    adapter delta (``tpu:lora_free_steps_total``) and the adapter targets
    the other steps were handed (``tpu:lora_target_reads_total``; each left
    out for a payload from before that counter); per dispatch is per step,
    and the second a share of the steps, where dispatches are one step
    long."""
    row = _per_decode_dispatch(profile, "lora_rows", "rows_per_dispatch")
    if row:
        row.update(_per_decode_dispatch(profile, "lora_free_steps",
                                        "free_steps_per_dispatch"))
        row.update(_per_decode_dispatch(profile, "lora_target_reads",
                                        "target_reads_per_dispatch"))
    return row


def logprob_steps_row(profile: dict) -> dict:
    """Decode steps staged with a row whose request asked for logprobs
    (``tpu:logprob_steps_total``): per dispatch is a share of the steps
    where dispatches are one step long; empty for a payload from before the
    counter."""
    return _per_decode_dispatch(profile, "logprob_steps",
                                "logprob_steps_per_dispatch")


def latent_positions_row(profile: dict) -> dict:
    """Cache positions a latent (MLA) model's live rows held, summed over
    the decode steps (``tpu:latent_kv_positions_total``); empty for a model
    with per-head K/V lanes."""
    if not (profile.get("hist") or {}).get("latent_positions"):
        return {}
    return _per_decode_dispatch(profile, "latent_positions",
                                "positions_per_dispatch")


def ssm_rows_row(profile: dict) -> dict:
    """Rows whose recurrent (state-space) state the decode steps rewrote
    (``tpu:ssm_state_rows_total``); empty for a model without a mixer."""
    if not (profile.get("hist") or {}).get("ssm_rows"):
        return {}
    return _per_decode_dispatch(profile, "ssm_rows", "rows_per_dispatch")


def conv_rows_row(profile: dict) -> dict:
    """Rows whose conv state (a gated short convolution's last inputs) the
    decode steps rewrote (``tpu:conv_state_rows_total``); empty for a model
    without conv layers."""
    if not (profile.get("hist") or {}).get("conv_rows"):
        return {}
    return _per_decode_dispatch(profile, "conv_rows", "rows_per_dispatch")


def kda_rows_row(profile: dict) -> dict:
    """Rows whose delta-rule matrix state the decode steps rewrote
    (``tpu:kda_state_rows_total``); empty for a model without KDA layers."""
    if not (profile.get("hist") or {}).get("kda_rows"):
        return {}
    return _per_decode_dispatch(profile, "kda_rows", "rows_per_dispatch")


def kv_positions_rows(profile: dict) -> list[dict]:
    """Cache positions the decode steps read of the live rows' lanes, by
    the kind of lane (``tpu:kv_positions_read_total``); empty for a model
    without a window."""
    hist = profile.get("hist") or {}
    read = hist.get("kv_positions") or {}
    if not any(read.values()):
        return []
    n = int(((hist.get("wall") or {}).get("decode") or {}).get("count", 0))
    return [{"lanes": lanes, "positions": int(total),
             "decode_dispatches": n,
             "positions_per_dispatch": round(total / n, 3) if n else 0.0}
            for lanes, total in read.items()]


def attn_grid_steps_row(profile: dict) -> dict:
    """Grid steps the decode-attention kernel's schedule held a layer's call,
    summed over the decode steps (``tpu:decode_attn_grid_steps_total``);
    empty where no kernel takes the cache's shape."""
    if not (profile.get("hist") or {}).get("attn_grid_steps"):
        return {}
    return _per_decode_dispatch(profile, "attn_grid_steps",
                                "steps_per_dispatch")


def overlap_row(profile: dict) -> dict:
    """Decode blocks dispatched while an earlier block was still unread
    (``tpu:decode_blocks_overlapped_total``) and their share of the decode
    blocks (plain and speculative): ~100% in a loaded window, 0 where
    every block was staged with none in flight (the device had run dry);
    empty for a payload from before the counter."""
    hist = profile.get("hist") or {}
    if "blocks_overlapped" not in hist:
        return {}
    wall = hist.get("wall") or {}
    n = sum(int((wall.get(kind) or {}).get("count", 0))
            for kind in ("decode", "spec"))
    over = int(hist["blocks_overlapped"])
    return {"blocks_overlapped": over, "decode_blocks": n,
            "overlapped_pct": round(100.0 * over / n, 2) if n else 0.0}


def prompt_program_rows(profile: dict) -> list[dict]:
    """The prompt programs by the jitted program (``tpu:prompt_programs_total``,
    ``tpu:prompt_positions_total``, ``tpu:prompt_program_seconds_total``): how
    many, the prompt tokens and the padding they computed, the milliseconds
    of the device's queue one held, their share of the tracked time, and
    the grid steps a chunk program's attends walk
    (``tpu:chunk_attn_grid_steps_total``; 0 of the other programs, and where
    no kernel takes the shapes); the programs that ran only; empty for a
    payload from before the families."""
    hist = profile.get("hist") or {}
    prompt = hist.get("prompt") or {}
    tracked = float((profile.get("attribution") or {}).get(
        "tracked_seconds", 0.0))
    rows = []
    for program, row in prompt.items():
        n, computed = int(row["programs"]), row["real"] + row["pad"]
        if not n:
            continue
        rows.append({
            "program": program, "programs": n,
            "real": int(row["real"]), "pad": int(row["pad"]),
            "pad_pct": round(100.0 * row["pad"] / computed, 2),
            "ms_per_program": round(1e3 * row["seconds"] / n, 3),
            "share_pct": (round(100.0 * row["seconds"] / tracked, 2)
                          if tracked else 0.0),
            "attn_grid_steps": (
                round(hist.get("chunk_attn_grid_steps", 0) / n, 1)
                if program == "chunk" else 0)})
    return rows


# -- a device trace against the engine thread's annotations -----------------

ANNOTATION_PREFIX = "engine."
NO_ANNOTATION = "other"  # the bottom of the phase stack is not annotated
# The jax.named_scope names the model code uses (models/transformer.py,
# models/mla.py, models/ssm.py, models/shortconv.py, models/kda.py,
# models/paged.py,
# models/lora.py, server/sampling.py, server/engine.py).
SCOPES = frozenset((
    "embed", "attn.qkv", "attn.qk_norm", "attn.rope", "attn.kv_update",
    "attn.core", "conv.in_proj", "conv.mix", "conv.out_proj",
    "kda.in_proj", "kda.conv", "kda.gate", "kda.scan", "kda.update",
    "kda.gate_norm", "kda.out_proj", "attn.head_gate",
    "attn.core.window", "attn.out", "attn.q_latent", "attn.kv_latent", "attn.absorb",
    "attn.expand", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.update",
    "ssm.gate_norm", "ssm.out_proj", "mlp", "moe.route", "moe.dispatch",
    "moe.experts", "moe.shared", "lora", "lm_head", "sample",
    "sample.topk_sort", "logprobs", "stops", "kv.insert"))


def union(intervals: list) -> list:
    """Merged [start, end] pairs of possibly overlapping intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_time_segments(events: list) -> list:
    """Properly nested ``(name, start, duration)`` spans of one thread ->
    non-overlapping ``(start, end, name)`` pieces of each span's SELF time
    (a child's time is taken out of its parent), sorted by start."""
    out: list[tuple[float, float, str]] = []
    stack: list[list] = []  # [name, end, covered up to]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if cursor < end:
                out.append((cursor, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and stack[-1][2] < start:
            out.append((stack[-1][2], start, stack[-1][0]))
            stack[-1][2] = start
        stack.append([name, start + dur, start])
    close(float("inf"))
    return sorted(out)


def gaps_by_phase(ops: list, annotations: list, top: int = 10,
                  notes: list = ()) -> dict:
    """Which phase the engine thread was in while the device sat idle.

    ``ops`` are the device's operations as ``(start_ns, duration_ns)``,
    ``annotations`` the engine thread's ``engine.*`` spans as ``(name,
    start_ns, duration_ns)`` on the same clock.  A gap is a hole in the
    union of ``ops``; each instant of it goes to the innermost annotation
    open then (``other`` where none is).  ``notes`` are ``(start_ns,
    {key: value})`` of annotations with metadata (a prefill's enqueue names
    its request): a longest gap lists those that began inside it.  Pure:
    lists in, a dict out."""
    busy = union([(s, s + d) for s, d in ops if d > 0])
    if not busy:
        return {"error": "no device operation in the trace"}
    segs = self_time_segments(
        [(n[len(ANNOTATION_PREFIX):] if n.startswith(ANNOTATION_PREFIX)
          else n, s, d) for n, s, d in annotations])
    starts = [s for s, _, _ in segs]

    def split(g0: float, g1: float) -> dict[str, float]:
        parts: dict[str, float] = {}
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segs) and segs[i][0] < g1:
            s, e, name = segs[i]
            o = min(e, g1) - max(s, g0)
            if o > 0:
                parts[name] = parts.get(name, 0.0) + o
                covered += o
            i += 1
        if g1 - g0 - covered > 0:
            parts[NO_ANNOTATION] = (parts.get(NO_ANNOTATION, 0.0)
                                    + g1 - g0 - covered)
        return parts

    gaps = [(s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    total: dict[str, float] = {}
    for _, g0, g1 in gaps:
        for name, ns in split(g0, g1).items():
            total[name] = total.get(name, 0.0) + ns
    window = busy[-1][1] - busy[0][0]
    idle = sum(g for g, _, _ in gaps)
    longest = sorted(gaps, reverse=True)[:top]
    return {
        "window_s": window / 1e9,
        "idle_s": idle / 1e9,
        "idle_pct": 100.0 * idle / window if window else 0.0,
        "n_gaps": len(gaps),
        "total_ms": {n: v / 1e6 for n, v in sorted(
            total.items(), key=lambda kv: -kv[1])},
        "longest": [
            {"at_s": (g0 - busy[0][0]) / 1e9, "gap_ms": g / 1e6,
             "phases_ms": {n: v / 1e6 for n, v in sorted(
                 split(g0, g1).items(), key=lambda kv: -kv[1])},
             "notes": [meta for at, meta in notes if g0 <= at < g1]}
            for g, g0, g1 in longest],
    }


def op_scope(stat_values: list) -> tuple[str, str, str]:
    """(program, scope, where) of one device operation from its string
    stats: XLA keeps ``jit(<program>)/.../<scope>/.../<primitive>`` as the
    operation's name in the source program.  ``scope`` is the chain of
    ``jax.named_scope`` names on that path, outermost first
    (``attn.qkv/lora``), ``where`` the path's
    last three components
    (what to go by where no scope is: the layer scan's own slicing has
    none).  All empty where no stat has such a path (a copy the compiler
    put in)."""
    for v in stat_values:
        if not isinstance(v, str) or not v.startswith("jit("):
            continue
        parts = v.rstrip(":").split("/")
        found = [part for part in parts if part in SCOPES]
        return (parts[0][4:].split(")", 1)[0], "/".join(found),
                "/".join(parts[1:][-3:]))
    return "", "", ""


def ops_by_scope(op_events: list, top: int = 25) -> dict:
    """Device time by operation and by scope.  ``op_events`` are ``(name,
    duration_ns, [stat values])`` of the "XLA Ops" line.  Operations nest
    (a ``while`` contains its body), so sums pass the busy time; read
    rows, not the total."""
    by_op: dict[tuple[str, str, str, str], float] = {}
    by_scope: dict[str, float] = {}
    for name, dur, stats in op_events:
        key = (name, *op_scope(stats))
        by_op[key] = by_op.get(key, 0.0) + dur
        scope = key[2] or "(none)"
        by_scope[scope] = by_scope.get(scope, 0.0) + dur
    return {
        "ops": [{"op": n, "program": p, "scope": sc, "where": w,
                 "device_ms": d / 1e6}
                for (n, p, sc, w), d in sorted(
                    by_op.items(), key=lambda kv: -kv[1])[:top]],
        "scopes_ms": {sc: d / 1e6 for sc, d in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
    }


# -- reading an .xplane.pb ---------------------------------------------------
# The file is a serialized XSpace (tsl/profiler/protobuf/xplane.proto).
# jax.profiler.ProfileData reads events but not the per-operation metadata
# where XLA keeps an operation's source name (the "tf_op" stat, e.g.
# jit(decode_block)/while/body/attn.qkv/dot_general), so the few fields
# needed are read from the wire format here.  Field numbers, from the .proto:
# XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5;
# XLine.name=2 .timestamp_ns=3 .events=4; XEvent.metadata_id=1 .offset_ps=2
# .duration_ps=3; XEventMetadata.name=2 .stats=5; XStat.metadata_id=1
# .str_value=5 .ref_value=7; XStatMetadata.name=2; a map entry is key=1,
# value=2.


def _varint(buf, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        byte = buf[i]
        i += 1
        val |= (byte & 0x7F) << shift
        if byte < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, value) pairs of one protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield field, int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for field, val in _fields(view):
        if field == 1:
            key = val
        elif field == 2:
            value = val
    return key, value


def _stat_value(stat: dict, stat_names: dict):
    """One XStat's value: a string (own or by reference) or a number."""
    if 5 in stat:
        return _text(stat[5])
    if 7 in stat:
        return stat_names.get(stat[7], "")
    if 2 in stat:  # a double, read as fixed64
        return struct.unpack("<d", stat[2].to_bytes(8, "little"))[0]
    return stat.get(3, stat.get(4))


def _plane(view) -> dict:
    """One XPlane: its name, its lines as ``(name, [(metadata id, start_ns,
    duration_ns)])``, per event-metadata id the operation's name and its
    "tf_op" (source name) stat, and for the host plane ``notes``: per line
    ``(start_ns, name, {stat: value})`` of the events that carry stats of
    their own (a ``TraceAnnotation``'s keyword arguments)."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for field, val in _fields(view):
        if field == 2:
            name = _text(val)
        elif field == 3:
            lines.append(val)
        elif field == 4:
            key, em = _map_entry(val)
            ev_meta[key] = em
        elif field == 5:
            key, sm = _map_entry(val)
            stat_names[key] = next(
                (_text(v) for f, v in _fields(sm) if f == 2), "")
    tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
    meta: dict[int, tuple[str, str]] = {}
    for key, em in ev_meta.items():
        op_name = source = ""
        for field, val in _fields(em):
            if field == 2:
                op_name = _text(val)
            elif field == 5:
                stat = dict(_fields(val))
                if stat.get(1) in tf_op:
                    ref = stat.get(7)  # a string shared through the stats
                    source = (_text(stat[5]) if 5 in stat
                              else stat_names.get(ref, ""))
        meta[key] = (op_name, source)
    out_lines = []
    notes: dict[str, list] = {}
    for ln in lines:
        lname, t0_ns, events = "", 0, []
        for field, val in _fields(ln):
            if field == 2:
                lname = _text(val)
            elif field == 3:
                t0_ns = val
            elif field == 4:
                ev = dict(_fields(val))
                start = t0_ns + ev.get(2, 0) / 1e3
                events.append((ev.get(1, 0), start, ev.get(3, 0) / 1e3))
                if 4 in ev and name == "/host:CPU":
                    stats = [dict(_fields(v)) for f, v in _fields(val)
                             if f == 4]
                    notes.setdefault(lname, []).append((
                        start, meta.get(ev.get(1, 0), ("", ""))[0], {
                            stat_names.get(st.get(1), ""):
                                _stat_value(st, stat_names) for st in stats}))
        out_lines.append((lname, events))
    return {"name": name, "lines": out_lines, "meta": meta, "notes": notes}


def read_xplane(path: str) -> dict:
    """The lists ``gaps_by_phase`` and ``ops_by_scope`` take, from a
    ``jax.profiler`` trace: the first TPU plane's operations and programs,
    and the host thread that wrote the most ``engine.*`` annotations."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [_plane(v) for field, v in _fields(space) if field == 1]
    device = sorted((p for p in planes
                     if p["name"].startswith("/device:TPU:")),
                    key=lambda p: p["name"])
    ops: list = []
    op_events: list = []
    modules: list = []
    if device:
        meta = device[0]["meta"]
        for lname, events in device[0]["lines"]:
            if lname == "XLA Ops":
                for mid, start, dur in events:
                    op_name, source = meta.get(mid, ("", ""))
                    ops.append((start, dur))
                    op_events.append((op_name.split(" = ")[0].lstrip("%"),
                                      dur, [source]))
            elif lname == "XLA Modules":
                modules = sorted({meta.get(mid, ("", ""))[0]
                                  for mid, _, _ in events})
    annotations: list = []
    notes: list = []
    thread = ""
    for p in planes:
        if p["name"] != "/host:CPU":
            continue
        for lname, events in p["lines"]:
            evs = [(p["meta"].get(mid, ("", ""))[0], start, dur)
                   for mid, start, dur in events]
            evs = [e for e in evs if e[0].startswith(ANNOTATION_PREFIX)]
            if len(evs) > len(annotations):
                annotations, thread = evs, lname
                notes = [(at, stats) for at, n, stats
                         in p["notes"].get(lname, [])
                         if n.startswith(ANNOTATION_PREFIX)]
    return {"plane": device[0]["name"] if device else "", "ops": ops,
            "op_events": op_events, "modules": modules,
            "annotations": annotations, "thread": thread, "notes": notes}


def render_xplane(trace: dict, top: int = 10) -> str:
    table = gaps_by_phase(trace["ops"], trace["annotations"], top,
                          trace.get("notes", ()))
    if "error" in table:
        return "error: " + table["error"]
    counts: dict[str, int] = {}
    for name, _, _ in trace["annotations"]:
        counts[name] = counts.get(name, 0) + 1
    out = [
        f"DEVICE IDLE GAP -> ENGINE PHASE ({trace['plane']}; engine thread "
        f"{trace['thread']!r}, {len(trace['annotations'])} annotations)",
        f"window {table['window_s']:.3f}s, idle {table['idle_s']:.3f}s "
        f"({table['idle_pct']:.1f}%) in {table['n_gaps']} gaps",
        "",
        "All gaps, by the phase the engine thread was in:",
        _table([{"phase": n, "idle_ms": round(v, 3),
                 "share_pct": round(100.0 * v / (1e3 * table["idle_s"]), 1)
                 if table["idle_s"] else 0.0}
                for n, v in table["total_ms"].items()],
               ("phase", "idle_ms", "share_pct")),
        "",
        f"The {len(table['longest'])} longest gaps:",
        _table([{"at_s": round(g["at_s"], 4), "gap_ms": round(g["gap_ms"], 3),
                 "phases": ", ".join(f"{n} {v:.2f}"
                                     for n, v in g["phases_ms"].items()),
                 "admits": "; ".join(
                     " ".join(f"{k}={v}" for k, v in meta.items())
                     for meta in g["notes"]) or "-"}
                for g in table["longest"]],
               ("at_s", "gap_ms", "phases", "admits")),
        "",
        "Annotations on the engine thread: " + ", ".join(
            f"{n} x{c}" for n, c in sorted(counts.items())),
        "Programs on the device: " + ", ".join(trace["modules"]),
    ]
    scoped = ops_by_scope(trace["op_events"])
    out += ["", "Device time by operation (operations nest):",
            _table([{"op": r["op"], "program": r["program"] or "-",
                     "scope": r["scope"] or "-", "where": r["where"] or "-",
                     "device_ms": round(r["device_ms"], 3)}
                    for r in scoped["ops"]],
                   ("op", "program", "scope", "where", "device_ms")),
            "", "Device time by scope (operations nest): " + ", ".join(
                f"{sc} {ms:.1f}" for sc, ms in scoped["scopes_ms"].items())]
    return "\n".join(out)


def _table(rows: list[dict], headers: tuple) -> str:
    if not rows:
        return "(no samples)"
    widths = [max(len(h), *(len(str(r[h])) for r in rows)) for h in headers]

    def fmt(vals):
        return "  ".join(str(v).rjust(w) if i else str(v).ljust(w)
                         for i, (v, w) in enumerate(zip(vals, widths)))

    lines = [fmt(headers), fmt(["-" * w for w in widths])]
    lines += [fmt([r[h] for h in headers]) for r in rows]
    return "\n".join(lines)


def host_sync_delta(profile: dict, previous: dict | None) -> dict | None:
    """Host-sync-share movement vs a previous baseline's shares — the
    number every ROADMAP item-2 lever is judged by.  ``previous`` is
    either a ``{"shares": {...}}`` block or a full profiler payload
    (--baseline FILE)."""
    if not previous:
        return None
    prev_shares = previous.get("shares")
    if prev_shares is None and "attribution" in previous:
        prev_shares = (previous.get("attribution") or {}).get("shares")
    if not prev_shares:
        return None
    cur = float(((profile.get("attribution") or {}).get("shares")
                 or {}).get("host_sync", 0.0))
    prev = float(prev_shares.get("host_sync", 0.0))
    return {
        "previous_pct": round(100.0 * prev, 4),
        "current_pct": round(100.0 * cur, 4),
        "delta_pp": round(100.0 * (cur - prev), 4),
        "improved": cur < prev,
    }


def render_report(profile: dict, previous: dict | None = None) -> str:
    att = attribution_rows(profile)
    out = [
        "ENGINE STEP-TIMELINE ATTRIBUTION "
        f"(tracked {profile.get('attribution', {}).get('tracked_seconds', 0)}s "
        f"over {profile.get('attribution', {}).get('dispatches', 0)} dispatches)",
        "",
        _table(att, ("bucket", "seconds", "share_pct")),
        "",
        "Per-phase dispatch wall:",
        _table(phase_rows(profile), ("phase", "dispatches", "wall_s",
                                     "mean_ms")),
    ]
    thread = thread_phase_rows(profile)
    if thread:
        out += ["", "Engine thread by phase (self time; tiles "
                f"{profile['attribution'].get('thread_seconds', 0)}s of "
                "the thread's wall):",
                _table(thread, ("phase", "on", "seconds", "share_pct"))]
    split = decode_split(profile)
    if split:
        out += ["", "Recent decode dispatch, mean parts: " + ", ".join(
            f"{k}={v}" for k, v in split.items())]
    staged = stage_ops_row(profile)
    if staged:
        out += ["", "Decode staging:",
                _table([staged], ("stage_ops", "decode_dispatches",
                                  "ops_per_dispatch"))]
    overlap = overlap_row(profile)
    if overlap:
        out += ["", "Decode overlap (blocks dispatched over an unread one):",
                _table([overlap], ("blocks_overlapped", "decode_blocks",
                                   "overlapped_pct"))]
    prompts = prompt_program_rows(profile)
    if prompts:
        out += ["", "Prompt programs (positions computed, and the time of "
                "the device's queue they held):",
                _table(prompts, ("program", "programs", "real", "pad",
                                 "pad_pct", "ms_per_program", "share_pct",
                                 "attn_grid_steps"))]
    adapter_rows = lora_rows_row(profile)
    if adapter_rows:
        out += ["", "Adapter rows in the decode steps, the steps run "
                "without the adapter delta, and the targets the others "
                "were handed:",
                _table([adapter_rows], tuple(
                    k for k in ("lora_rows", "decode_dispatches",
                                "rows_per_dispatch", "lora_free_steps",
                                "free_steps_per_dispatch",
                                "lora_target_reads",
                                "target_reads_per_dispatch")
                    if k in adapter_rows))]
    asked = logprob_steps_row(profile)
    if asked:
        out += ["", "Decode steps staged with a row that asked for "
                "logprobs:",
                _table([asked], ("logprob_steps", "decode_dispatches",
                                 "logprob_steps_per_dispatch"))]
    latent = latent_positions_row(profile)
    if latent:
        out += ["", "Latent cache rows read by the decode steps:",
                _table([latent], ("latent_positions", "decode_dispatches",
                                  "positions_per_dispatch"))]
    recurrent = ssm_rows_row(profile)
    if recurrent:
        out += ["", "Recurrent states rewritten by the decode steps:",
                _table([recurrent], ("ssm_rows", "decode_dispatches",
                                     "rows_per_dispatch"))]
    conv = conv_rows_row(profile)
    if conv:
        out += ["", "Conv states rewritten by the decode steps:",
                _table([conv], ("conv_rows", "decode_dispatches",
                                "rows_per_dispatch"))]
    delta = kda_rows_row(profile)
    if delta:
        out += ["", "Delta-rule states rewritten by the decode steps:",
                _table([delta], ("kda_rows", "decode_dispatches",
                                 "rows_per_dispatch"))]
    lanes = kv_positions_rows(profile)
    if lanes:
        out += ["", "Cache positions read by the decode steps, a layer of "
                "the kind:",
                _table(lanes, ("lanes", "positions", "decode_dispatches",
                               "positions_per_dispatch"))]
    grid = attn_grid_steps_row(profile)
    if grid:
        out += ["", "Grid steps of the decode-attention kernel, a layer's "
                "call:",
                _table([grid], ("attn_grid_steps", "decode_dispatches",
                                "steps_per_dispatch"))]
    delta = host_sync_delta(profile, previous)
    if delta:
        out += ["", "Host-sync share vs previous baseline: "
                f"{delta['previous_pct']}% -> {delta['current_pct']}% "
                f"(delta {delta['delta_pp']:+}pp"
                f"{', improved' if delta['improved'] else ''})"]
    summary = record_summary(profile)
    if summary:
        out += ["", "Recent decode dispatches: " + ", ".join(
            f"{k}={v}" for k, v in summary.items())]
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="dispatch / host-sync / idle attribution table from a "
                    "/debug/profile payload")
    parser.add_argument("source", nargs="?",
                        help="file path, http(s) URL, or - for stdin")
    parser.add_argument("--xplane", metavar="FILE.xplane.pb",
                        help="read a jax.profiler trace instead: device "
                             "idle gaps against the engine thread's "
                             "engine.<phase> annotations, and device time "
                             "by named scope")
    parser.add_argument("--pod",
                        help="which pod's snapshot to render when the "
                             "source is a black-box dump holding several")
    parser.add_argument("--baseline",
                        help="a previous profiler payload to diff the "
                             "host-sync share against")
    parser.add_argument("--json", action="store_true",
                        help="emit the attribution + phase rows as JSON")
    args = parser.parse_args(argv)
    if args.xplane:
        trace = read_xplane(args.xplane)
        if args.json:
            print(json.dumps({
                "gaps": gaps_by_phase(trace["ops"], trace["annotations"]),
                "ops": ops_by_scope(trace["op_events"]),
                "modules": trace["modules"], "thread": trace["thread"]}))
        else:
            print(render_xplane(trace))
        return 0
    if not args.source:
        parser.error("a /debug/profile source or --xplane is needed")
    try:
        doc = load(args.source)
        profile = extract_profile(doc, pod=args.pod)
        previous = (extract_profile(load(args.baseline))
                    if args.baseline else None)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({
            "attribution": attribution_rows(profile),
            "phases": phase_rows(profile),
            "thread_phases": thread_phase_rows(profile),
            "decode_split": decode_split(profile),
            "summary": record_summary(profile),
            **({"host_sync_delta": host_sync_delta(profile, previous)}
               if previous else {}),
        }))
    else:
        print(render_report(profile, previous=previous))
    return 0


if __name__ == "__main__":
    sys.exit(main())
