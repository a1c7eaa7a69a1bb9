"""Engine step-timeline profiler: where does a decode step's wall go?

ROADMAP item 2 names the engine "dispatch-bound" — device-side stop
detection, multi-step scheduling, and chunked prefill all exist to shave
the Python step loop and the host<->device round-trip — but until now
nothing MEASURED that loop: ``tpu:decode_step_seconds`` sees only the
dispatch+readback wall, and the time the engine thread spends BETWEEN
dispatches (token materialization, admission, stream writes, paged-table
sync) was invisible.  This module is the evidence layer: a bounded ring
recorder charged at the same call sites as the usage tracker
(``server/usage.py``), splitting the engine thread's timeline into three
disjoint buckets:

- **dispatch**: the jitted program call plus its host sync (the
  ``step_s`` every decode/spec/pipelined block already measures, and the
  prefill compute wall) — ``tpu:dispatch_wall_seconds{phase}``;
- **host-sync**: the gap between one dispatch's end and the next
  dispatch's start while the engine had work — the Python step-loop tax
  multi-step scheduling amortizes — ``tpu:dispatch_gap_seconds{kind=
  "host"}``;
- **idle**: gaps that contain a ``_work.wait`` (no admissible work), so
  loop overhead is never blamed on an empty queue —
  ``tpu:dispatch_gap_seconds{kind="idle"}``.

``tools/profile_report.py`` renders the attribution table (shares of the
three buckets summing to 100%); the committed ``PROFILE_BASELINE.json``
run is the baseline every ROADMAP item-2 lever gets measured against.
Per-dispatch records (wall, gap, batch occupancy, step count, net slot
churn) ride ``/debug/profile`` for timeline views.

The recorder sits on the engine thread's hottest path, so it follows the
usage tracker's budget discipline: ``note_dispatch`` is a few float ops
+ two histogram observes + a bounded-deque append per DISPATCH (not per
token), behind the ``EngineConfig.step_profile`` off-switch that exists
for the bench A/B (``step_profile_ratio`` <= 1.05), not for production
use.
"""

from __future__ import annotations

import collections
import os
import time

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.tracing import Histogram

# Dispatch walls run from ~100µs (tiny CPU models) to hundreds of ms (a
# fused multi-step block of a large model); gaps run µs to ms.  One shared
# edge set keeps the two families comparable on a dashboard.
DISPATCH_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                    5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 1.0)

GAP_HOST = "host"
GAP_IDLE = "idle"

# How many per-dispatch records /debug/profile ships (the ring may hold
# more; JSON payloads stay bounded).
SNAPSHOT_RECORDS = 256


class StepProfiler:
    """Bounded per-dispatch timeline recorder for one engine.

    All mutators run on the engine thread; ``snapshot()``/``hist_state()``
    copy out under the lock for the scrape thread (the UsageTracker
    locking pattern).
    """

    def __init__(self, capacity: int | None = None, clock=time.perf_counter):
        if capacity is None:
            capacity = int(os.environ.get("LIG_PROFILE_CAPACITY", "2048"))
        self.capacity = max(1, capacity)
        self._clock = clock
        self._lock = witness_lock("StepProfiler._lock")
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        self._seq = 0
        # End of the previous dispatch on the engine-thread clock; None
        # until the first dispatch (no gap to attribute yet).
        self._last_end: float | None = None
        # The engine loop found no work since the last dispatch: the next
        # gap contains a wait and is attributed idle, not host-sync.
        self._idle_pending = False
        # Dispatch wall that happened OFF the engine-thread gap clock
        # (prefill walls are stamped with time.time in _record_ttft, so
        # they cannot anchor the perf_counter gap chain; their wall is
        # subtracted from the next gap instead of double-counting as
        # host-sync).
        self._foreign_wall = 0.0
        self._prev_active = 0
        # Cumulative buckets (the attribution table's numerators).
        self.dispatch_seconds: dict[str, float] = {}
        self.dispatches: dict[str, int] = {}
        self.gap_seconds: dict[str, float] = {GAP_HOST: 0.0, GAP_IDLE: 0.0}
        self.padding_tokens = 0
        self.wall_hist: dict[str, Histogram] = {}
        self.gap_hist: dict[str, Histogram] = {
            GAP_HOST: Histogram(DISPATCH_BUCKETS),
            GAP_IDLE: Histogram(DISPATCH_BUCKETS),
        }

    # -- engine-thread mutators ---------------------------------------------
    def note_idle(self) -> None:
        """The loop is about to wait for work: the next inter-dispatch gap
        is queue idleness, not step-loop overhead."""
        self._idle_pending = True

    def note_padding(self, pad_tokens: int) -> None:
        if pad_tokens > 0:
            with self._lock:
                self.padding_tokens += pad_tokens

    def note_dispatch(self, phase: str, t0: float | None, wall_s: float,
                      active: int = 0, total_slots: int = 0,
                      n_steps: int = 1) -> None:
        """Record one dispatch.

        ``t0`` is the dispatch start on the engine thread's perf_counter
        clock — it anchors the host-sync gap chain.  ``None`` means the
        wall was measured on a different clock (prefill): the wall is
        recorded but excluded from gap math, and subtracted from the next
        gap so prefill compute is never misattributed as host-sync.
        """
        if wall_s < 0.0:
            wall_s = 0.0
        gap = 0.0
        gap_kind = ""
        with self._lock:
            self.dispatch_seconds[phase] = (
                self.dispatch_seconds.get(phase, 0.0) + wall_s)
            self.dispatches[phase] = self.dispatches.get(phase, 0) + 1
            hist = self.wall_hist.get(phase)
            if hist is None:
                hist = self.wall_hist[phase] = Histogram(DISPATCH_BUCKETS)
            hist.observe(wall_s)
            if t0 is None:
                self._foreign_wall += wall_s
            else:
                if self._last_end is not None and t0 > self._last_end:
                    gap = max(0.0, t0 - self._last_end - self._foreign_wall)
                    gap_kind = GAP_IDLE if self._idle_pending else GAP_HOST
                    self.gap_seconds[gap_kind] += gap
                    self.gap_hist[gap_kind].observe(gap)
                self._foreign_wall = 0.0
                self._idle_pending = False
                self._last_end = t0 + wall_s
            self._seq += 1
            churn = active - self._prev_active
            self._prev_active = active
            self._ring.append((self._seq, phase, round(wall_s, 9),
                               round(gap, 9), gap_kind, active, total_slots,
                               n_steps, churn))

    # -- export (any thread) -------------------------------------------------
    def attribution(self) -> dict:
        """The gap-attribution summary: absolute seconds per bucket and
        shares of the tracked total — dispatch + host + idle tile the
        tracked timeline, so the shares sum to 100% by construction."""
        with self._lock:
            dispatch = sum(self.dispatch_seconds.values())
            host = self.gap_seconds[GAP_HOST]
            idle = self.gap_seconds[GAP_IDLE]
            by_phase = dict(self.dispatch_seconds)
            n = sum(self.dispatches.values())
        total = dispatch + host + idle
        if total > 0:
            # The largest bucket absorbs the rounding remainder so the
            # three rounded shares sum to exactly 1.0 — consumers (and
            # the committed-baseline test) rely on "100% by construction".
            shares = {"dispatch": round(dispatch / total, 6),
                      "host_sync": round(host / total, 6),
                      "idle": round(idle / total, 6)}
            largest = max(shares, key=lambda k: shares[k])
            shares[largest] = round(
                1.0 - sum(v for k, v in shares.items() if k != largest), 6)
        else:
            shares = {"dispatch": 0.0, "host_sync": 0.0, "idle": 0.0}
        return {
            "dispatches": n,
            "dispatch_seconds": round(dispatch, 6),
            "host_sync_seconds": round(host, 6),
            "idle_seconds": round(idle, 6),
            "tracked_seconds": round(total, 6),
            "dispatch_seconds_by_phase": {
                k: round(v, 6) for k, v in sorted(by_phase.items())},
            "shares": shares,
        }

    def hist_state(self) -> dict:
        """The small copy-out ``Engine.metrics_snapshot()`` embeds — the
        ``tpu:dispatch_wall_seconds`` / ``tpu:dispatch_gap_seconds``
        exposition source (server/metrics.py)."""
        with self._lock:
            return {
                "wall": {p: h.state()
                         for p, h in sorted(self.wall_hist.items())},
                "gap": {k: h.state()
                        for k, h in sorted(self.gap_hist.items())},
            }

    def snapshot(self) -> dict:
        """The full ``/debug/profile`` payload: attribution summary,
        histogram states, and the newest per-dispatch records."""
        with self._lock:
            records = list(self._ring)[-SNAPSHOT_RECORDS:]
            padding = self.padding_tokens
        return {
            "capacity": self.capacity,
            "seq": self._seq,
            "padding_tokens": padding,
            "attribution": self.attribution(),
            "hist": self.hist_state(),
            "records": [
                {"seq": seq, "phase": phase, "wall_s": wall, "gap_s": gap,
                 **({"gap_kind": kind} if kind else {}),
                 "active": active, "slots": slots, "n_steps": n_steps,
                 "slot_churn": churn}
                for (seq, phase, wall, gap, kind, active, slots, n_steps,
                     churn) in records],
        }


def render_profile(hist: dict) -> list[str]:
    """Exposition lines for one ``StepProfiler.hist_state()`` payload
    (the server/metrics.py render seam)."""
    from llm_instance_gateway_tpu.tracing import render_histogram

    lines: list[str] = []
    first = True
    for phase, state in (hist.get("wall") or {}).items():
        lines += render_histogram("tpu:dispatch_wall_seconds", state,
                                  {"phase": phase}, type_line=first)
        first = False
    first = True
    for kind, state in (hist.get("gap") or {}).items():
        lines += render_histogram("tpu:dispatch_gap_seconds", state,
                                  {"kind": kind}, type_line=first)
        first = False
    return lines
