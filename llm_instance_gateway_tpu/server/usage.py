"""Per-adapter capacity attribution on the serving engine.

One decode batch multiplexes many LoRA adapters and the base model per-row
(the engine's premise), so pool-level gauges can say how BUSY a replica is
but not WHO is consuming it.  This tracker charges every unit of engine
capacity to the {adapter} that used it:

- **Step seconds** (``tpu:adapter_step_seconds_total{adapter,phase}``):
  each decode dispatch's wall time is split evenly across the slots active
  in that batch (every active row advances one token per step regardless of
  adapter, so even attribution is exact for the fused program); each
  prefill's wall time is charged whole to its owner.  The engine-side
  ``tpu:step_seconds_total{phase}`` accumulates the same wall at the same
  call sites, so Σ-per-adapter == engine total is an INVARIANT the
  conservation test (tests/test_usage.py) pins within 1%.
- **Tokens** (``tpu:adapter_tokens_total{adapter,phase}``): prompt tokens
  at prefill, emitted tokens at decode.
- **KV block seconds** (``tpu:adapter_kv_block_seconds_total{adapter}``):
  the time-integral of KV blocks held, including requests PARKED in
  ``decode_wait`` (prefilled KV pinned off-cache is real HBM nobody else
  can use).  Unit: paged-block-seconds under the paged cache, token-seconds
  (block=1) on the contiguous-lane cache.
- **Pool waste** nobody previously saw: the decode batch occupancy
  histogram (``tpu:decode_batch_occupancy``, active/total slots per
  dispatch), ``tpu:idle_slot_seconds_total`` (slot-seconds spent empty
  while the batch stepped), and ``tpu:prefill_padding_tokens_total``
  (bucket/ring padding tokens prefilled and thrown away).

The tracker is engine-thread-hot: ``charge_decode`` is a handful of dict
ops per DISPATCH (not per token).  All methods take the tracker's own lock only — safe to call
from the engine loop and snapshot from the scrape thread.
"""

from __future__ import annotations

import time

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.tracing import Histogram

# Attribution key for requests with no LoRA adapter (base-model rows).
BASE = "base"

# Decode-batch occupancy fractions (active/total slots).  Eight even bins:
# the signal is "how full do dispatches run", not a latency tail.
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

PHASE_PREFILL = "prefill"
PHASE_DECODE = "decode"


def owner_key(adapter: str | None) -> str:
    return adapter if adapter else BASE


class UsageTracker:
    """Accumulates per-adapter consumption; snapshot() is the export seam."""

    def __init__(self, decode_slots: int, kv_block: int = 1,
                 clock=time.monotonic):
        self.decode_slots = max(1, decode_slots)
        self.kv_block = max(1, kv_block)
        self._clock = clock
        self._lock = witness_lock("UsageTracker._lock")
        self.step_seconds: dict[tuple[str, str], float] = {}
        self.tokens: dict[tuple[str, str], int] = {}
        self.kv_block_seconds: dict[str, float] = {}
        # Engine wall per phase, accumulated at the SAME call sites as the
        # per-adapter charges — the conservation denominator.
        self.engine_step_seconds: dict[str, float] = {}
        self.idle_slot_seconds = 0.0
        self.padding_tokens = 0
        self.occupancy = Histogram(OCCUPANCY_BUCKETS)
        # KV holdings integral: the holdings recorded at the LAST sync are
        # charged for the elapsed interval on the next sync/snapshot.
        self._kv_holdings: tuple[tuple[str, float], ...] = ()
        self._kv_t: float | None = None

    # -- charging (engine thread) -----------------------------------------
    def charge_step(self, phase: str, wall_s: float,
                    owners: list[str | None],
                    tokens: dict[str, int] | None = None) -> None:
        """Split ``wall_s`` evenly across ``owners`` (adapter names; None =
        base).  No-op with an empty owner list — an unowned dispatch must
        not skew the conservation invariant."""
        if not owners or wall_s <= 0.0:
            return
        share = wall_s / len(owners)
        with self._lock:
            self.engine_step_seconds[phase] = (
                self.engine_step_seconds.get(phase, 0.0) + wall_s)
            for owner in owners:
                key = (owner_key(owner), phase)
                self.step_seconds[key] = self.step_seconds.get(key, 0.0) + share
            for owner, n in (tokens or {}).items():
                if n:
                    key = (owner, phase)
                    self.tokens[key] = self.tokens.get(key, 0) + n

    def charge_decode(self, wall_s: float, owners: list[str | None],
                      tokens: dict[str, int] | None = None) -> None:
        """One decode dispatch: step-second attribution plus the pool-waste
        observables (occupancy + idle-slot-seconds)."""
        active = len(owners)
        with self._lock:
            self.occupancy.observe(active / self.decode_slots)
            if wall_s > 0.0:
                self.idle_slot_seconds += (
                    wall_s * (self.decode_slots - active))
        self.charge_step(PHASE_DECODE, wall_s, owners, tokens)

    def charge_padding(self, pad_tokens: int) -> None:
        if pad_tokens > 0:
            with self._lock:
                self.padding_tokens += pad_tokens

    def sync_kv(self, holdings: list[tuple[str | None, int]] | None,
                now: float | None = None) -> None:
        """Charge the PREVIOUS holdings for the elapsed interval, then (if
        ``holdings`` is not None) replace them.  ``holdings`` is
        [(adapter, kv_tokens_held), ...]; tokens convert to blocks here."""
        now = self._clock() if now is None else now
        with self._lock:
            if self._kv_t is not None:
                dt = now - self._kv_t
                if dt > 0.0:
                    for owner, blocks in self._kv_holdings:
                        self.kv_block_seconds[owner] = (
                            self.kv_block_seconds.get(owner, 0.0)
                            + blocks * dt)
            self._kv_t = now
            if holdings is not None:
                self._kv_holdings = tuple(
                    (owner_key(a), -(-t // self.kv_block))
                    for a, t in holdings if t > 0)

    # -- export (any thread) ----------------------------------------------
    def snapshot(self) -> dict:
        """Copy-out for ``Engine.metrics_snapshot()`` — flushes the pending
        KV interval first so a scrape between engine syncs still sees
        up-to-date block-seconds."""
        self.sync_kv(None)
        with self._lock:
            return {
                "step_seconds": dict(self.step_seconds),
                "tokens": dict(self.tokens),
                "kv_block_seconds": dict(self.kv_block_seconds),
                "engine_step_seconds": dict(self.engine_step_seconds),
                "idle_slot_seconds": self.idle_slot_seconds,
                "padding_tokens": self.padding_tokens,
                "occupancy": self.occupancy.state(),
                "kv_block_tokens": self.kv_block,
            }


def render_usage(usage: dict, model: str) -> list[str]:
    """Exposition lines for one ``UsageTracker.snapshot()`` payload (the
    ``server/metrics.py`` render seam; labels escaped there via the shared
    helpers)."""
    from llm_instance_gateway_tpu.tracing import escape_label, render_histogram

    lines = []
    m = escape_label(model)
    step = usage.get("step_seconds") or {}
    if step:
        lines.append("# TYPE tpu:adapter_step_seconds_total counter")
        for (adapter, phase) in sorted(step):
            lines.append(
                'tpu:adapter_step_seconds_total{model="%s",adapter="%s",'
                'phase="%s"} %.6f'
                % (m, escape_label(adapter), escape_label(phase),
                   step[(adapter, phase)]))
    toks = usage.get("tokens") or {}
    if toks:
        lines.append("# TYPE tpu:adapter_tokens_total counter")
        for (adapter, phase) in sorted(toks):
            lines.append(
                'tpu:adapter_tokens_total{model="%s",adapter="%s",'
                'phase="%s"} %d'
                % (m, escape_label(adapter), escape_label(phase),
                   toks[(adapter, phase)]))
    kv = usage.get("kv_block_seconds") or {}
    if kv:
        lines.append("# TYPE tpu:adapter_kv_block_seconds_total counter")
        for adapter in sorted(kv):
            lines.append(
                'tpu:adapter_kv_block_seconds_total{model="%s",'
                'adapter="%s"} %.6f' % (m, escape_label(adapter), kv[adapter]))
    engine_s = usage.get("engine_step_seconds") or {}
    if engine_s:
        lines.append("# TYPE tpu:step_seconds_total counter")
        for phase in sorted(engine_s):
            lines.append('tpu:step_seconds_total{phase="%s"} %.6f'
                         % (escape_label(phase), engine_s[phase]))
    lines.append("# TYPE tpu:idle_slot_seconds_total counter")
    lines.append("tpu:idle_slot_seconds_total %.6f"
                 % usage.get("idle_slot_seconds", 0.0))
    lines.append("# TYPE tpu:prefill_padding_tokens_total counter")
    lines.append("tpu:prefill_padding_tokens_total %d"
                 % usage.get("padding_tokens", 0))
    occ = usage.get("occupancy")
    if occ:
        lines += render_histogram("tpu:decode_batch_occupancy", occ)
    return lines
