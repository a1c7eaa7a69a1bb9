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
  ``step_s`` every decode/spec block already measures, and the
  prefill compute wall) — ``tpu:dispatch_wall_seconds{phase}``;
- **host-sync**: the gap between one dispatch's end and the next
  dispatch's start while the engine had work — the Python step-loop tax
  multi-step scheduling amortizes — ``tpu:dispatch_gap_seconds{kind=
  "host"}``;
- **idle**: gaps that contain a ``_work.wait`` (no admissible work), so
  loop overhead is never blamed on an empty queue —
  ``tpu:dispatch_gap_seconds{kind="idle"}``.

``tools/profile_report.py`` renders the attribution table (shares of the
three buckets summing to 100%).
Per-dispatch records (wall, gap, batch occupancy, step count, net slot
churn) ride ``/debug/profile`` for timeline views.

The three buckets say nothing about what happens INSIDE a dispatch, where
the device trace shows the chip idle for a fifth of the time.  So the same
recorder keeps a second, finer account: a **phase stack** driven from the
engine thread (``with profiler.phase("decode.stage") as ph: ...
ph.to("decode.wait")``).  Every transition charges the time since the
last one to the innermost open phase (self time: a child's time is not
its parent's), so the phases tile the thread's wall by construction —
``tpu:engine_phase_seconds_total{phase,on}``, the label set of
``metrics_registry.ENGINE_PHASES``.  Each open phase is also a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` on the engine
thread's line of the profiler's host plane, on the clock the device
trace is taken on: ``tools/profile_report.py --xplane`` reads which phase
the thread was in during each hole of the device's timeline.

The recorder sits on the engine thread's hottest path, so it follows the
usage tracker's budget discipline: ``note_dispatch`` is a few float ops
+ two histogram observes + a bounded-deque append per DISPATCH (not per
token).  A phase transition is one clock read, one dict add and one tuple,
plus the annotation (which tests an atomic flag when no trace is being
taken); no lock.
"""

from __future__ import annotations

import collections
import os
import time

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.metrics_registry import (
    ENGINE_PHASES,
    KV_LANES,
    PROMPT_PROGRAMS,
    SAMPLE_PATHS,
)
from llm_instance_gateway_tpu.tracing import Histogram

# Dispatch walls run from ~100µs (tiny CPU models) to hundreds of ms (a
# fused multi-step block of a large model); gaps run µs to ms.  One shared
# edge set keeps the two families comparable on a dashboard.
DISPATCH_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                    5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 1.0)

# transformer.MOE_TALLY, restated so that this module imports no JAX.
MOE_COUNTERS = ("layer_steps", "assignments", "experts_touched", "tiles_used",
                "assignments_routed", "tiles_laid_out")
GAP_HOST = "host"
GAP_IDLE = "idle"

# How many per-dispatch records /debug/profile ships (the ring may hold
# more; JSON payloads stay bounded).
SNAPSHOT_RECORDS = 256

# phase -> "device" where the thread is blocked on the chip, else "host".
PHASE_ON = dict(ENGINE_PHASES)
PHASE_OTHER = "other"  # the bottom of the stack: what no phase covers
_ANNOTATION = {name: "engine." + name for name in PHASE_ON}
# The parts of a decode dispatch a /debug/profile record carries.
_SPLIT = (("stage_s", "decode.stage"), ("wait_s", "decode.wait"),
          ("readback_s", "decode.readback"), ("emit_s", "decode.emit"))
# The parts of an admission an ``engine.prefill`` request span carries.
_PREFILL_SPLIT = (("stage_s", "prefill.stage"), ("wait_s", "prefill.wait"),
                  ("emit_s", "prefill.emit"))


class _NoScope:
    """What ``phase()`` stands for where there is no profiler: one shared
    object, nothing measured."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def to(self, name: str) -> None:
        pass


NO_PHASE = _NoScope()


class _Scope:
    """One open phase of the engine thread; ``to`` renames it in place, so
    that a run of phases one after another is one ``with``."""

    __slots__ = ("_prof", "_name")

    def __init__(self, prof: "StepProfiler", name: str):
        self._prof, self._name = prof, name

    def __enter__(self):
        self._prof._push(self._name)
        return self

    def __exit__(self, *exc):
        self._prof._pop()
        return False

    def to(self, name: str) -> None:
        self._prof._switch(name)


class StepProfiler:
    """Bounded per-dispatch timeline recorder for one engine.

    All mutators run on the engine thread; ``snapshot()``/``hist_state()``
    copy out under the lock for the scrape thread (the UsageTracker
    locking pattern).
    """

    def __init__(self, capacity: int | None = None, clock=time.perf_counter,
                 annotate=None):
        """``annotate(name)`` makes the context manager that puts an open
        phase into the profiler's trace (the engine passes
        ``jax.profiler.TraceAnnotation``); None keeps this module off
        JAX."""
        if capacity is None:
            capacity = int(os.environ.get("LIG_PROFILE_CAPACITY", "2048"))
        self.capacity = max(1, capacity)
        self._clock = clock
        self._lock = witness_lock("StepProfiler._lock")
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        self._seq = 0
        # Routing counts of a sparse model's layer-steps (decode and
        # prefill programs; transformer.MOE_TALLY), zero for a dense one.
        self.moe = [0] * len(MOE_COUNTERS)
        # Decode steps by the path the sampler took on the device
        # (metrics_registry.SAMPLE_PATHS, in that order).
        self.sample_steps = [0] * len(SAMPLE_PATHS)
        # Host-to-device transfers and helper programs the engine issued
        # to stage its plain decode dispatches (the decode program's own
        # call not counted).
        self.stage_ops = 0
        # Live rows whose LoRA slot is >= 0, summed over the steps of the
        # plain decode dispatches: the rows a step's adapter reads serve.
        self.lora_rows = 0
        # Steps of the plain decode dispatches that ran the program without
        # the LoRA delta: no row of the block named an adapter on an engine
        # that holds adapter buffers (0 on one that holds none).
        self.lora_free_steps = 0
        # LoRA targets whose buffers the plain decode dispatches with the
        # delta were handed, summed over their steps: over those steps, the
        # targets a delta step reads (all seven where a resident adapter
        # carries them all).
        self.lora_target_reads = 0
        # Steps of the plain decode dispatches staged while a held slot's
        # request asked for logprobs: the steps whose program may take the
        # log-softmax and top-K over [B, V] (0 where nobody asks).
        self.logprob_steps = 0
        # Cache rows the latent (MLA) decode kernel had to read: the live
        # rows' cache lengths, summed over the steps of the plain decode
        # dispatches.  0 for a model with per-head K/V lanes.
        self.latent_positions = 0
        # Rows whose recurrent (state-space) state a decode step rewrote,
        # summed over the steps of the plain decode dispatches.  0 for a
        # model without a mixer.
        self.ssm_rows = 0
        # Rows whose conv state (a gated short convolution's last inputs)
        # a decode step shifted and rewrote, summed over the steps of the
        # plain decode dispatches.  0 for a model without conv layers.
        self.conv_rows = 0
        # Rows whose delta-rule matrix state (models/kda.py) a decode step
        # rewrote, summed over the steps of the plain decode dispatches.  0
        # for a model without KDA layers.
        self.kda_rows = 0
        # Cache positions the decode steps' attention read of the live
        # rows' lanes, a layer of the kind, by the kind of lane
        # (metrics_registry.KV_LANES): the full lanes' grow with a row, a
        # window layer's ring stops at the window.  0 for a model without
        # a window.
        self.kv_positions = [0] * len(KV_LANES)
        # Grid steps the decode-attention kernel walks a layer's call (the
        # live rows' tiles: ``pallas_decode_attention.decode_schedule``),
        # summed over the steps of the plain decode dispatches; of a stack
        # with two kinds of lane, the full lanes'.  0 where no kernel takes
        # the cache's shape.
        self.attn_grid_steps = 0
        # Decode blocks dispatched while an earlier block was still unread:
        # the device then had its next step queued before the host read
        # the last.  Over the decode and spec dispatches: the share of
        # blocks the overlapped loop kept the device ahead for.
        self.blocks_overlapped = 0
        # The prompt programs, by the jitted program
        # (metrics_registry.PROMPT_PROGRAMS): how many were enqueued, the
        # prompt tokens and the padding each computed, and the seconds of
        # the loop's completion chain they held (``Engine._note_prompt_program``
        # counts, ``Engine._prompt_programs_done`` times).
        self.prompt = {p: {"programs": 0, "real": 0, "pad": 0, "seconds": 0.0}
                       for p in PROMPT_PROGRAMS}
        # Grid steps the chunk-attention kernel walks over the attention
        # layers of the chunk programs enqueued, from the shapes
        # (``Engine._chunk_attn_steps``).  0 where no kernel takes them.
        self.chunk_attn_grid_steps = 0
        # End of the previous dispatch on the engine-thread clock; None
        # until the first dispatch (no gap to attribute yet).
        self._last_end: float | None = None
        # The engine loop found no work since the last dispatch: the next
        # gap contains a wait and is attributed idle, not host-sync.
        self._idle_pending = False
        self._prev_active = 0
        # Cumulative buckets (the attribution table's numerators).
        self.dispatch_seconds: dict[str, float] = {}
        self.dispatches: dict[str, int] = {}
        self.gap_seconds: dict[str, float] = {GAP_HOST: 0.0, GAP_IDLE: 0.0}
        self.wall_hist: dict[str, Histogram] = {}
        self.gap_hist: dict[str, Histogram] = {
            GAP_HOST: Histogram(DISPATCH_BUCKETS),
            GAP_IDLE: Histogram(DISPATCH_BUCKETS),
        }
        # The phase stack.  Seconds per phase, every key there from the
        # start so that the scrape thread's copy never sees the dict grow.
        self._annotate = annotate
        self._phase_s: dict[str, float] = dict.fromkeys(PHASE_ON, 0.0)
        self._stack: list[str] = []
        self._anns: list = []
        # (innermost open phase, when it was last charged), swapped whole
        # on every transition: the scrape thread adds the open stretch to
        # its copy and sees a torn pair never.  None until the first phase.
        self._open: tuple[str, float] | None = None
        # _SPLIT's phase totals at the last decode record, and
        # _PREFILL_SPLIT's at the last ``take_prefill_split``.
        self._split_mark = (0.0,) * len(_SPLIT)
        self._prefill_mark = (0.0,) * len(_PREFILL_SPLIT)

    # -- the phase stack (engine thread) ------------------------------------
    def phase(self, name: str) -> _Scope:
        """``with profiler.phase(name) as ph:`` — ``name`` is the thread's
        phase until the block ends or ``ph.to(other)`` renames it."""
        return _Scope(self, name)

    def annotation(self, name: str, **metadata):
        """A span in the trace alone, no counter (the jitted call inside
        a ``*.stage`` phase: an enqueue that blocks shows there).
        ``metadata`` rides the event (``TraceAnnotation`` formats it only
        while a trace is being taken)."""
        if self._annotate is None:
            return NO_PHASE
        return self._annotate(name, **metadata)

    def _charge(self) -> float:
        now = self._clock()
        if self._open is None:
            self._stack.append(PHASE_OTHER)
        else:
            name, since = self._open
            self._phase_s[name] += now - since
        return now

    def _trace(self, name: str | None) -> None:
        """Leave the innermost annotation (``name`` None) or enter one."""
        if self._annotate is None:
            return
        if name is None:
            self._anns.pop().__exit__(None, None, None)
        else:
            ann = self._annotate(name)
            ann.__enter__()
            self._anns.append(ann)

    def _push(self, name: str) -> None:
        label = _ANNOTATION[name]  # KeyError: not one of ENGINE_PHASES
        now = self._charge()
        self._stack.append(name)
        self._open = (name, now)
        self._trace(label)

    def _switch(self, name: str) -> None:
        label = _ANNOTATION[name]
        now = self._charge()
        self._stack[-1] = name
        self._open = (name, now)
        self._trace(None)
        self._trace(label)

    def _pop(self) -> None:
        now = self._charge()
        self._stack.pop()
        self._open = (self._stack[-1], now)
        self._trace(None)

    def phase_seconds(self) -> dict[str, float]:
        """Seconds per phase up to now, the open stretch included (any
        thread).  The engine thread swaps ``_open`` after it has charged:
        a copy taken between two reads of the same ``_open`` is whole."""
        for _ in range(8):
            mark = self._open
            out = dict(self._phase_s)
            if mark is self._open:
                break
        if mark is not None:
            out[mark[0]] += max(0.0, self._clock() - mark[1])
        return out

    # -- engine-thread mutators ---------------------------------------------
    def note_idle(self) -> None:
        """The loop is about to wait for work: the next inter-dispatch gap
        is queue idleness, not step-loop overhead."""
        self._idle_pending = True

    def take_prefill_split(self) -> dict[str, float]:
        """What the phase stack charged to ``prefill.stage`` / ``.wait`` /
        ``.emit`` since the last call (engine thread, between phases of an
        admission: an open prefill phase's stretch is not in it yet)."""
        totals = tuple(self._phase_s[p] for _, p in _PREFILL_SPLIT)
        out = {key: round(t - m, 9) for (key, _), t, m in
               zip(_PREFILL_SPLIT, totals, self._prefill_mark)}
        self._prefill_mark = totals
        return out

    def note_dispatch(self, phase: str, t0: float, wall_s: float,
                      active: int = 0, total_slots: int = 0,
                      n_steps: int = 1) -> None:
        """Record one dispatch.

        ``t0`` is the dispatch start on the engine thread's perf_counter
        clock — it anchors the host-sync gap chain: the gap before a
        dispatch is its ``t0`` less the end of the one before (a prefill's
        wall is never host-sync, and the host's time between a prefill and
        the next decode block is).  A dispatch that began before the last
        one ended (an overlapped block, a prompt streamed in chunks between
        decode blocks) has no gap.

        A decode or spec record also carries what the phase stack charged
        to ``decode.stage`` / ``.wait`` / ``.readback`` / ``.emit`` since
        the last such record: the wait, the readback and the emit are
        this block's, the stage is the next block's, staged before this
        one was awaited.
        """
        if wall_s < 0.0:
            wall_s = 0.0
        gap = 0.0
        gap_kind = ""
        split = None
        if phase != "prefill":
            totals = tuple(self._phase_s[p] for _, p in _SPLIT)
            split = tuple(round(t - m, 9)
                          for t, m in zip(totals, self._split_mark))
            self._split_mark = totals
        with self._lock:
            self.dispatch_seconds[phase] = (
                self.dispatch_seconds.get(phase, 0.0) + wall_s)
            self.dispatches[phase] = self.dispatches.get(phase, 0) + 1
            hist = self.wall_hist.get(phase)
            if hist is None:
                hist = self.wall_hist[phase] = Histogram(DISPATCH_BUCKETS)
            hist.observe(wall_s)
            gap, gap_kind = self._chain(t0, t0 + wall_s)
            self._seq += 1
            churn = active - self._prev_active
            self._prev_active = active
            self._ring.append((self._seq, phase, round(wall_s, 9),
                               round(gap, 9), gap_kind, active, total_slots,
                               n_steps, churn, split))

    def _chain(self, t0: float, end: float) -> tuple[float, str]:
        """Put the interval ``t0``..``end`` into the gap chain (under the
        lock): the gap before it, booked by its kind, and the chain's new
        end."""
        gap, gap_kind = 0.0, ""
        if self._last_end is not None and t0 > self._last_end:
            gap = t0 - self._last_end
            gap_kind = GAP_IDLE if self._idle_pending else GAP_HOST
            self.gap_seconds[gap_kind] += gap
            self.gap_hist[gap_kind].observe(gap)
        self._idle_pending = False
        if self._last_end is None or end > self._last_end:
            self._last_end = end
        return gap, gap_kind

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
        phases = self.phase_seconds()
        thread_s = sum(phases.values())
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
            # The finer account: the engine thread's whole wall by phase.
            "thread_seconds": round(thread_s, 6),
            "phases": {
                name: {"on": PHASE_ON[name], "seconds": round(sec, 6),
                       "share": round(sec / thread_s, 6) if thread_s else 0.0}
                for name, sec in phases.items()},
        }

    def note_moe(self, tally) -> None:
        """Add the routing counts one readback brought back."""
        with self._lock:
            self.moe = [a + int(b) for a, b in zip(self.moe, tally)]

    def moe_state(self) -> dict:
        with self._lock:
            return dict(zip(MOE_COUNTERS, self.moe))

    def note_sample_paths(self, paths) -> None:
        """Count the steps of one decode block by the sampler's path:
        ``paths`` holds one index into ``SAMPLE_PATHS`` per step."""
        with self._lock:
            for i in paths:
                self.sample_steps[int(i)] += 1

    def sample_state(self) -> dict:
        with self._lock:
            return dict(zip(SAMPLE_PATHS, self.sample_steps))

    def note_stage_ops(self, n: int) -> None:
        """Count ``n`` transfers or helper programs issued to stage a
        plain decode dispatch."""
        with self._lock:
            self.stage_ops += n

    def note_lora_rows(self, n: int) -> None:
        """Count ``n`` adapter rows (LoRA slot >= 0) over the steps of one
        plain decode dispatch."""
        with self._lock:
            self.lora_rows += n

    def note_lora_free_steps(self, n: int) -> None:
        """Count the ``n`` steps of one plain decode dispatch that was
        handed no adapter buffers because none of its rows named an
        adapter."""
        with self._lock:
            self.lora_free_steps += n

    def note_lora_target_reads(self, n: int) -> None:
        """Count ``n`` LoRA targets handed over the steps of one plain
        decode dispatch that ran with the delta (targets x steps)."""
        with self._lock:
            self.lora_target_reads += n

    def note_logprob_steps(self, n: int) -> None:
        """Count the ``n`` steps of one plain decode dispatch staged with a
        row whose request asked for logprobs."""
        with self._lock:
            self.logprob_steps += n

    def note_latent_positions(self, n: int) -> None:
        """Count ``n`` cache positions a latent model's live rows held over
        the steps of one plain decode dispatch."""
        with self._lock:
            self.latent_positions += n

    def note_ssm_rows(self, n: int) -> None:
        """Count ``n`` rows whose recurrent state the steps of one plain
        decode dispatch rewrote (live rows x the block's steps)."""
        with self._lock:
            self.ssm_rows += n

    def note_conv_rows(self, n: int) -> None:
        """Count ``n`` rows whose conv state the steps of one plain decode
        dispatch rewrote (live rows x the block's steps)."""
        with self._lock:
            self.conv_rows += n

    def note_kda_rows(self, n: int) -> None:
        """Count ``n`` rows whose delta-rule state the steps of one plain
        decode dispatch rewrote (live rows x the block's steps)."""
        with self._lock:
            self.kda_rows += n

    def note_kv_positions(self, full: int, window: int) -> None:
        """Count the positions the steps of one plain decode dispatch read
        of the live rows' full lanes and of their rings, a layer of each
        kind."""
        with self._lock:
            self.kv_positions[0] += full
            self.kv_positions[1] += window

    def note_attn_grid_steps(self, n: int) -> None:
        """Count ``n`` grid steps the decode-attention kernel's schedule
        held a layer's call over the steps of one plain decode dispatch."""
        with self._lock:
            self.attn_grid_steps += n

    def note_overlapped_block(self) -> None:
        """Count one decode block dispatched while an earlier block was
        still unread."""
        with self._lock:
            self.blocks_overlapped += 1

    def note_prompt_program(self, program: str, real: int, pad: int,
                            attn_steps: int = 0) -> None:
        """Count one prompt program enqueued: ``real`` prompt tokens and
        ``pad`` positions of padding up to the program's shape, and the
        ``attn_steps`` grid steps its layers' chunk attends walk."""
        with self._lock:
            row = self.prompt[program]  # KeyError: not of PROMPT_PROGRAMS
            row["programs"] += 1
            row["real"] += real
            row["pad"] += pad
            self.chunk_attn_grid_steps += attn_steps

    def note_prompt_done(self, t0: float, done: float, shares) -> None:
        """Prompt programs seen complete at ``done`` held the completion
        chain from ``t0``: ``shares`` is ``(program, seconds)`` of each
        (its own interval, or its share of a burst's).  The interval sits
        in the gap chain like a dispatch's: the queue was not empty while
        a chunk ran that no decode record covers."""
        with self._lock:
            for program, seconds in shares:
                self.prompt[program]["seconds"] += seconds
            self._chain(t0, done)

    def prompt_state(self) -> dict:
        with self._lock:
            return {p: dict(row) for p, row in self.prompt.items()}

    def hist_state(self) -> dict:
        """The small copy-out ``Engine.metrics_snapshot()`` embeds — the
        ``tpu:dispatch_wall_seconds`` / ``tpu:dispatch_gap_seconds``
        / ``tpu:engine_phase_seconds_total`` exposition source
        (server/metrics.py)."""
        with self._lock:
            out = {
                "wall": {p: h.state()
                         for p, h in sorted(self.wall_hist.items())},
                "gap": {k: h.state()
                        for k, h in sorted(self.gap_hist.items())},
                "stage_ops": self.stage_ops,
                "lora_rows": self.lora_rows,
                "lora_free_steps": self.lora_free_steps,
                "lora_target_reads": self.lora_target_reads,
                "logprob_steps": self.logprob_steps,
                "latent_positions": self.latent_positions,
                "ssm_rows": self.ssm_rows,
                "conv_rows": self.conv_rows,
                "kda_rows": self.kda_rows,
                "kv_positions": dict(zip(KV_LANES, self.kv_positions)),
                "attn_grid_steps": self.attn_grid_steps,
                "chunk_attn_grid_steps": self.chunk_attn_grid_steps,
                "blocks_overlapped": self.blocks_overlapped,
            }
        out["phases"] = self.phase_seconds()
        out["moe"] = self.moe_state()
        out["sample_steps"] = self.sample_state()
        out["prompt"] = self.prompt_state()
        return out

    def snapshot(self) -> dict:
        """The full ``/debug/profile`` payload: attribution summary,
        histogram states, and the newest per-dispatch records."""
        with self._lock:
            records = list(self._ring)[-SNAPSHOT_RECORDS:]
        return {
            "capacity": self.capacity,
            "seq": self._seq,
            "attribution": self.attribution(),
            "hist": self.hist_state(),
            "records": [
                {"seq": seq, "phase": phase, "wall_s": wall, "gap_s": gap,
                 **({"gap_kind": kind} if kind else {}),
                 "active": active, "slots": slots, "n_steps": n_steps,
                 "slot_churn": churn,
                 **(dict(zip((k for k, _ in _SPLIT), split))
                    if split else {})}
                for (seq, phase, wall, gap, kind, active, slots, n_steps,
                     churn, split) in records],
        }


def render_profile(hist: dict) -> list[str]:
    """Exposition lines for one ``StepProfiler.hist_state()`` payload
    (the server/metrics.py render seam)."""
    from llm_instance_gateway_tpu.tracing import (
        escape_label,
        render_histogram,
    )

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
    phases = hist.get("phases")
    if phases:
        lines.append("# TYPE tpu:engine_phase_seconds_total counter")
        lines += [
            f'tpu:engine_phase_seconds_total{{phase="{escape_label(name)}",'
            f'on="{escape_label(on)}"}} {phases.get(name, 0.0):.6f}'
            for name, on in PHASE_ON.items()]
    moe = hist.get("moe")
    if moe:
        # The families by their literal names: the metric-currency lint
        # rule looks each registered family up in the code.
        for family, name in (
                ("tpu:moe_layer_steps_total", "layer_steps"),
                ("tpu:moe_assignments_total", "assignments"),
                ("tpu:moe_experts_touched_total", "experts_touched"),
                ("tpu:moe_tiles_used_total", "tiles_used"),
                ("tpu:moe_assignments_routed_total", "assignments_routed"),
                ("tpu:moe_tiles_laid_out_total", "tiles_laid_out")):
            if name in moe:
                lines += [f"# TYPE {family} counter",
                          f"{family} {moe[name]}"]
    sample_steps = hist.get("sample_steps")
    if sample_steps:
        lines.append("# TYPE tpu:sample_steps_total counter")
        lines += [
            f'tpu:sample_steps_total{{path="{escape_label(path)}"}} {n}'
            for path, n in sample_steps.items()]
    if "stage_ops" in hist:
        lines += ["# TYPE tpu:decode_stage_ops_total counter",
                  f"tpu:decode_stage_ops_total {hist['stage_ops']}"]
    if "lora_rows" in hist:
        lines += ["# TYPE tpu:lora_rows_total counter",
                  f"tpu:lora_rows_total {hist['lora_rows']}"]
    if "lora_free_steps" in hist:
        lines += ["# TYPE tpu:lora_free_steps_total counter",
                  f"tpu:lora_free_steps_total {hist['lora_free_steps']}"]
    if "lora_target_reads" in hist:
        lines += ["# TYPE tpu:lora_target_reads_total counter",
                  "tpu:lora_target_reads_total "
                  f"{hist['lora_target_reads']}"]
    if "logprob_steps" in hist:
        lines += ["# TYPE tpu:logprob_steps_total counter",
                  f"tpu:logprob_steps_total {hist['logprob_steps']}"]
    if "latent_positions" in hist:
        lines += ["# TYPE tpu:latent_kv_positions_total counter",
                  "tpu:latent_kv_positions_total "
                  f"{hist['latent_positions']}"]
    if "ssm_rows" in hist:
        lines += ["# TYPE tpu:ssm_state_rows_total counter",
                  f"tpu:ssm_state_rows_total {hist['ssm_rows']}"]
    if "conv_rows" in hist:
        lines += ["# TYPE tpu:conv_state_rows_total counter",
                  f"tpu:conv_state_rows_total {hist['conv_rows']}"]
    if "kda_rows" in hist:
        lines += ["# TYPE tpu:kda_state_rows_total counter",
                  f"tpu:kda_state_rows_total {hist['kda_rows']}"]
    kv_positions = hist.get("kv_positions")
    if kv_positions:
        lines.append("# TYPE tpu:kv_positions_read_total counter")
        lines += [
            f'tpu:kv_positions_read_total{{lanes="{escape_label(lanes)}"}} '
            f'{n}' for lanes, n in kv_positions.items()]
    if "attn_grid_steps" in hist:
        lines += ["# TYPE tpu:decode_attn_grid_steps_total counter",
                  "tpu:decode_attn_grid_steps_total "
                  f"{hist['attn_grid_steps']}"]
    if "chunk_attn_grid_steps" in hist:
        lines += ["# TYPE tpu:chunk_attn_grid_steps_total counter",
                  "tpu:chunk_attn_grid_steps_total "
                  f"{hist['chunk_attn_grid_steps']}"]
    if "blocks_overlapped" in hist:
        lines += ["# TYPE tpu:decode_blocks_overlapped_total counter",
                  "tpu:decode_blocks_overlapped_total "
                  f"{hist['blocks_overlapped']}"]
    prompt = hist.get("prompt")
    if prompt:
        lines.append("# TYPE tpu:prompt_programs_total counter")
        lines += [
            f'tpu:prompt_programs_total{{program="{escape_label(p)}"}} '
            f'{row["programs"]}' for p, row in prompt.items()]
        lines.append("# TYPE tpu:prompt_positions_total counter")
        lines += [
            f'tpu:prompt_positions_total{{program="{escape_label(p)}",'
            f'kind="{escape_label(kind)}"}} {row[kind]}'
            for p, row in prompt.items() for kind in ("real", "pad")]
        lines.append("# TYPE tpu:prompt_program_seconds_total counter")
        lines += [
            "tpu:prompt_program_seconds_total"
            f'{{program="{escape_label(p)}"}} {row["seconds"]:.6f}'
            for p, row in prompt.items()]
    return lines
