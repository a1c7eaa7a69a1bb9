"""Routing decision ledger: per-pick explainability with counterfactual
seam attribution.

Traces say *where* a request went; the advisor planes say *what they
flagged*; this module records *why a pick landed where it did*.  For a
deterministically-sampled subset of picks it keeps a bounded ring of
decision records, each capturing the stage-by-stage narrowing pipeline —
role partition -> filter tree -> health/circuit (``filter_by_policy``) ->
fairness -> placement -> prefix tie-break -> RNG draw — with surviving-
candidate counts and removed-pod attribution per stage, escape-hatch
fires, the disagg hop identity (single/prefill/decode), and the winning
pod, joined to the request's trace by ``x-lig-trace-id``.

**Counterfactual lane**: for every sampled pick the pure advisor filter
chain is re-run with each seam individually disabled (the other advisors
wrapped in a note-suppressing proxy so no counter double-fires; the
prefix index and the RNG are never touched).  A seam whose absence
changes the final survivor set *steered* this pick
(``gateway_pick_steered_total{seam}``); the changed seam with the largest
survivor-set delta is tagged *decisive* (ties break in chain order; when
no seam changed the outcome, the tag falls through to ``prefix_affinity``
if the tie-break fired, ``rng`` if the draw chose among >1 survivors,
else ``none``).

**Charging paths**: the Python ``Scheduler`` charges directly from
``_pick`` (and the disagg decode hop); the ``NativeScheduler`` must not
grow its FFI hot path, so sampled native picks are explained by a
Python-oracle *shadow replay* — the same filter tree + silent advisor
chain re-run over the same pods list, with ``shadow_match`` recording
whether the replay reproduced the native candidate set (the paths are
pinned byte-identical by the same-RNG diff tests, so a mismatch is a
drift observable, not an assert).

**Cost discipline**: sampling is a counter modulus (never an RNG draw —
the log-only invariant requires routing byte-identical with the ledger
ON), the unsampled path is one ``enabled`` check + one GIL-atomic
``itertools.count`` bump, and every record/counterfactual cost rides only
sampled picks.

Surfaces: ``GET /debug/picks?since=`` (cursor contract of
``events.debug_events_payload``), the ``gateway_pick_*`` exposition
families, the fast-burn black-box dump (rendered by
``tools/blackbox_report.py``), ``tools/pick_report.py``, and the statebus
-> ``fleetobs.pick_steering_rollup`` fleet view on ``/debug/fleet``.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import asdict, dataclass
from typing import Sequence

from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.tracing import escape_label

# Canonical stage order of one pick (the funnel rows every record and the
# gateway_pick_narrowing family carry, in pipeline order).
STAGES = ("pool", "role_partition", "filter_tree", "health/circuit",
          "fairness", "placement", "prefix_affinity", "rng")
# The advisor seams the counterfactual lane can disable, in chain order
# (= the decisive-seam tie-break order).
SEAMS = ("health/circuit", "fairness", "placement")
# Decisive tags beyond the seams (always rendered so dashboards see a
# stable label set).
_DECISIVE_EXTRA = ("prefix_affinity", "rng", "none")
# Removed-pod attribution cap per stage row (records are ring-resident;
# a 200-pod narrowing event must not hold 200 names forever).
_REMOVED_CAP = 16

# Shared read-only counterfactual rows for the common (seam-did-nothing)
# case: (seam, changed, delta, would_add, would_remove, replayed).
# Reused across records so sampled picks on a healthy fleet allocate no
# per-seam containers at all.
_CF_NOOP = {seam: (seam, False, 0, (), (), False) for seam in SEAMS}
_NO_REMOVED: tuple = ()


@dataclass(frozen=True)
class PickLedgerConfig:
    # OFF switch: disabled() short-circuits sampled() before the counter.
    enabled: bool = True
    # Deterministic sampling: every Nth pick is recorded (counter
    # modulus, NOT an RNG draw — the scheduler RNG must see an identical
    # call sequence with the ledger on or off).  1 = every pick.
    sample_every: int = 8
    # Bounded decision-record ring (the /debug/picks cursor pages it).
    capacity: int = 512


class _SilentAdvisor:
    """Delegation proxy that suppresses an advisor's ``note_*`` hooks.

    The scheduler filter functions fire escape counters via
    ``getattr(advisor, "note_...", None)``; raising AttributeError for
    those names makes a counterfactual replay side-effect-free while
    every read (``policy``, ``avoid_set``, ``noisy``, ``resident_tiers``,
    ...) still reaches the real advisor.
    """

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name.startswith("note_"):
            raise AttributeError(name)
        return getattr(self._inner, name)


def _silent(advisor):
    return None if advisor is None else _SilentAdvisor(advisor)


def _names(candidates) -> list[str]:
    return [c.pod.name for c in candidates]


def replay_filter_chain(req, candidates, health=None, usage=None,
                        placement=None):
    """Re-run the pure advisor filter chain over ``candidates`` with all
    note hooks suppressed — no escape counters, no prefix index, no RNG.
    Returns the (post-health, post-fairness, post-placement) survivor
    lists.  A strict-policy shed in the replay (possible only when the
    live pick also shed) degrades to an empty final set."""
    from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
        SchedulingError,
        filter_by_fairness,
        filter_by_placement,
        filter_by_policy,
    )

    base = list(candidates)
    try:
        s1 = filter_by_policy(_silent(health), base)
    except SchedulingError:
        return [], [], []
    s2 = filter_by_fairness(_silent(usage), req, s1)
    s3 = filter_by_placement(_silent(placement), req, s2)
    return s1, s2, s3


class PickLedger:
    """Bounded, thread-safe decision-record ring + steering aggregates.

    One instance per pool (built by ``AdvisorStack``); the scheduler
    reaches it through its ``pick_ledger`` seam attribute exactly like
    the advisor seams — ``None`` (or ``enabled=False``) means every pick
    pays one attribute read and nothing else.
    """

    def __init__(self, cfg: PickLedgerConfig | None = None,
                 journal: "events_mod.EventJournal | None" = None,
                 clock=time.time):
        self.cfg = cfg or PickLedgerConfig()
        self.journal = journal
        self._clock = clock
        self._lock = witness_lock("PickLedger._lock")
        # Pick counter for the deterministic sampling modulus.  Bumped
        # lock-free on EVERY pick (``next`` on itertools.count is
        # GIL-atomic); everything else in this class only moves on
        # sampled picks, under the lock.
        self._counter = itertools.count()
        self._picks_seen = 0            # last counter value observed
        # Decision-record ring + monotonic cursor (events.py contract).
        # Entries are flat tuples of scalars/strings/tuples, NOT live
        # dicts: a ring of 512 nested record dicts is ~13k long-lived
        # GC-tracked containers that every collection re-scans, and that
        # churn — not the charge() compute — dominated the measured pick
        # overhead.  Tuples whose leaves are atomic get untracked by the
        # collector, so the ring is invisible to it; _materialize()
        # rebuilds the documented dict shape on the (rare) read path.
        self._ring: list[tuple] = []
        self._seq = 0
        # Aggregates across sampled picks (render/rollup inputs).
        self._samples = 0
        self._stage_survivors: dict[str, int] = {}   # stage -> sum
        self._stage_removed: dict[str, int] = {}     # stage -> sum
        self._steered: dict[str, int] = {}           # seam -> picks changed
        self._decisive: dict[str, int] = {}          # tag -> picks
        self._escapes: dict[str, int] = {}           # seam -> hatch fires
        self._steered_away: dict[str, int] = {}      # pod -> removals
        self._shadow_mismatch = 0
        # Swap-published rollup cache: recomputed by tick(), read without
        # the lock by statebus/fleet/loadgen consumers (seam_rollup).
        self._rollup: dict = self._empty_rollup()
        self.last_tick = 0.0
        self.ticks = 0

    # -- sampling gate (pick hot path) ---------------------------------------
    def sampled(self) -> bool:
        """One call per pick: True when THIS pick should be recorded.
        Deterministic (pick ordinal modulus; the first pick is always
        sampled) and RNG-free, so routing stays byte-identical."""
        if not self.cfg.enabled:
            return False
        n = next(self._counter)
        self._picks_seen = n + 1
        return n % self.cfg.sample_every == 0

    # -- scheduler-facing helpers -------------------------------------------
    @staticmethod
    def escape_counters(health, usage, placement) -> tuple[int, int, int]:
        """The advisors' cumulative escape counters, read before the
        filter chain on a sampled pick; ``charge(escape_base=...)`` diffs
        them afterwards to attribute which hatch fired for THIS pick."""
        return (getattr(health, "escape_hatch_total", 0) or 0,
                getattr(usage, "escape_total", 0) or 0,
                getattr(placement, "escape_total", 0) or 0)

    def replay(self, req, candidates, advisors):
        """Shadow-replay seam for the native scheduler: the silent filter
        chain over the oracle tree's survivor set."""
        health, usage, placement = advisors
        return replay_filter_chain(req, candidates, health=health,
                                   usage=usage, placement=placement)

    # -- charge --------------------------------------------------------------
    def charge(self, req, *, winner: str, base, post_health, post_fairness,
               post_placement, hop: str = "single", path: str = "python",
               pool_n: int = 0, role_n: int = 0, tie_break: bool = False,
               advisors=(None, None, None), escapes=None, escape_base=None,
               trace_id: str = "", shadow_match=None) -> None:
        """Record one sampled pick.

        ``base``..``post_placement`` are the actual survivor lists the
        pick narrowed through (PodMetrics on both paths); ``escapes`` is
        the explicit fired-hatch list (native flag bits) or derived from
        ``escape_base`` (Python path: counter deltas).  The counterfactual
        replays run here, outside the ledger lock, advisors untouched.
        """
        health, usage, placement = advisors
        if escapes is None and escape_base is not None:
            after = self.escape_counters(health, usage, placement)
            escapes = tuple(seam for seam, b, a in
                            zip(SEAMS, escape_base, after) if a > b)
        escapes = tuple(escapes) if escapes else ()

        # Filters only ever REMOVE pods, so an unchanged survivor count
        # means an unchanged survivor set — the O(1) length checks here
        # (and the identity checks below, gating the counterfactual
        # replays) stand in for set comparisons, and unchanged stages
        # REUSE the previous name list instead of re-materializing it.
        base_names = _names(base)
        n_health = (base_names if len(post_health) == len(base_names)
                    else _names(post_health))
        n_fair = (n_health if len(post_fairness) == len(n_health)
                  else _names(post_fairness))
        n_place = (n_fair if len(post_placement) == len(n_fair)
                   else _names(post_placement))
        stage_inputs = (base_names, n_health, n_fair)
        stage_outputs = (n_health, n_fair, n_place)
        actual_final = None

        # Counterfactual lane: each seam individually disabled, the other
        # advisors silenced.  A seam whose absence changes the final set
        # steered this pick; largest delta wins the decisive tag.  A seam
        # whose live filter passed its input through unchanged is skipped
        # without a replay — disabling a no-op filter reproduces the live
        # chain exactly, so the replay cost rides only picks a seam
        # actually narrowed.
        cf_rows = []
        steered: list[str] = []
        decisive = ""
        best_delta = -1
        for i, seam in enumerate(SEAMS):
            alt_advisors = [health, usage, placement]
            if (alt_advisors[i] is None
                    or stage_outputs[i] is stage_inputs[i]):
                cf_rows.append(_CF_NOOP[seam])
                continue
            if actual_final is None:
                actual_final = frozenset(n_place)
            alt_advisors[i] = None
            _, _, alt_final = replay_filter_chain(
                req, base, health=alt_advisors[0], usage=alt_advisors[1],
                placement=alt_advisors[2])
            alt_set = frozenset(_names(alt_final))
            delta = alt_set ^ actual_final
            changed = bool(delta)
            if changed:
                steered.append(seam)
                if len(delta) > best_delta:
                    best_delta, decisive = len(delta), seam
            cf_rows.append((
                seam, changed, len(delta),
                tuple(sorted(alt_set - actual_final)[:_REMOVED_CAP]),
                tuple(sorted(actual_final - alt_set)[:_REMOVED_CAP]),
                True))
        if not steered:
            if tie_break:
                decisive = "prefix_affinity"
            elif len(post_placement) > 1:
                decisive = "rng"
            else:
                decisive = "none"

        # Stage funnel with removed-pod attribution (advisor stages; the
        # earlier stages carry counts only — their inputs never reach the
        # pick seam).  Everything lands in one flat tuple of scalars and
        # tuples: the ring must stay GC-UNTRACKED (see __init__), so the
        # document shape is only materialized on the read path.
        removed3 = []
        removed_total: list[str] = []
        prev = base_names
        for cur in stage_outputs:
            if cur is prev:
                removed: Sequence[str] = _NO_REMOVED
            else:
                cur_set = set(cur)
                removed = tuple(sorted(
                    name for name in prev if name not in cur_set
                )[:_REMOVED_CAP])
                removed_total.extend(removed)
            removed3.append(removed)
            prev = cur
        survivors8 = (int(pool_n), int(role_n), len(base_names),
                      len(n_health), len(n_fair), len(n_place),
                      1 if tie_break else len(n_place), 1)
        steered_t = tuple(steered)
        ts = round(self._clock(), 6)
        with self._lock:
            self._seq += 1
            self._ring.append((
                self._seq, ts, trace_id, req.model,
                req.resolved_target_model, hop, path, survivors8,
                tuple(removed3), escapes, bool(tie_break), winner,
                steered_t, decisive, tuple(cf_rows),
                None if shadow_match is None else bool(shadow_match)))
            if len(self._ring) > self.cfg.capacity:
                del self._ring[:len(self._ring) - self.cfg.capacity]
            self._samples += 1
            for stage, surv in zip(STAGES, survivors8):
                self._stage_survivors[stage] = (
                    self._stage_survivors.get(stage, 0) + surv)
            for seam, removed in zip(SEAMS, removed3):
                if removed:
                    self._stage_removed[seam] = (
                        self._stage_removed.get(seam, 0) + len(removed))
            for seam in steered:
                self._steered[seam] = self._steered.get(seam, 0) + 1
            self._decisive[decisive] = self._decisive.get(decisive, 0) + 1
            for seam in escapes:
                self._escapes[seam] = self._escapes.get(seam, 0) + 1
            for name in removed_total:
                self._steered_away[name] = (
                    self._steered_away.get(name, 0) + 1)
            if shadow_match is False:
                self._shadow_mismatch += 1
        # Journal emits AFTER the lock release (kvobs discipline).
        if self.journal is not None:
            self.journal.emit(events_mod.PICK_SAMPLE, trace_id=trace_id,
                              hop=hop, path=path, winner=winner,
                              decisive=decisive,
                              steered=",".join(steered) or "none")
            if escapes:
                self.journal.emit(events_mod.PICK_ESCAPE_EXPLAINED,
                                  trace_id=trace_id, winner=winner,
                                  seams=",".join(escapes))

    # -- rollup --------------------------------------------------------------
    def _empty_rollup(self) -> dict:
        return {"picks": 0, "samples": 0, "steered": {}, "decisive": {},
                "escapes": {}, "mean_survivors": {}, "steered_away": {},
                "shadow_mismatch": 0}

    def maybe_tick(self, min_interval_s: float = 1.0) -> None:
        if self._clock() - self.last_tick >= min_interval_s:
            self.tick()

    def tick(self, now: float | None = None) -> None:
        """Recompute and swap-publish the steering rollup (the statebus /
        fleet / loadgen read surface)."""
        now = self._clock() if now is None else now
        with self._lock:
            samples = self._samples
            rollup = {
                "picks": self._picks_seen,
                "samples": samples,
                "steered": dict(self._steered),
                "decisive": dict(self._decisive),
                "escapes": dict(self._escapes),
                "mean_survivors": {
                    stage: round(total / samples, 2)
                    for stage, total in self._stage_survivors.items()
                } if samples else {},
                "steered_away": dict(sorted(
                    self._steered_away.items(),
                    key=lambda kv: (-kv[1], kv[0]))[:8]),
                "shadow_mismatch": self._shadow_mismatch,
            }
            self.last_tick = now
            self.ticks += 1
        self._rollup = rollup  # swap-published: readers never lock

    def seam_rollup(self) -> dict:
        """The last tick's steering rollup (swap-published — safe from
        any thread without the lock)."""
        return self._rollup

    # -- export --------------------------------------------------------------
    @staticmethod
    def _materialize(entry: tuple) -> dict:
        """Rebuild the documented record dict from a flat ring entry."""
        (seq, ts, trace_id, model, adapter, hop, path, survivors8,
         removed3, escapes, tie_break, winner, steered, decisive,
         cf_rows, shadow_match) = entry
        stage_rows = []
        for i, stage in enumerate(STAGES):
            removed = removed3[i - 3] if 3 <= i < 6 else ()
            stage_rows.append({"stage": stage, "survivors": survivors8[i],
                               "removed": list(removed)})
        counterfactual = {}
        for seam, changed, delta, would_add, would_remove, replayed \
                in cf_rows:
            if replayed:
                counterfactual[seam] = {
                    "changed": changed, "delta": delta,
                    "would_add": list(would_add),
                    "would_remove": list(would_remove)}
            else:
                counterfactual[seam] = {"changed": False, "delta": 0}
        record = {
            "seq": seq,
            "ts": ts,
            "trace_id": trace_id,
            "model": model,
            "adapter": adapter,
            "hop": hop,
            "path": path,
            "stages": stage_rows,
            "escapes": list(escapes),
            "tie_break": tie_break,
            "winner": winner,
            "steered": list(steered),
            "decisive": decisive,
            "counterfactual": counterfactual,
        }
        if shadow_match is not None:
            record["shadow_match"] = shadow_match
        return record

    def records(self, since: int = 0, limit: int = 256) -> list[dict]:
        """Oldest ``limit`` records with seq > ``since`` (events.py
        cursor semantics: page with since=next_since, never skip)."""
        with self._lock:
            entries = [e for e in self._ring if e[0] > since]
        return [self._materialize(e) for e in entries[:max(0, limit)]]

    @property
    def seq(self) -> int:
        return self._seq

    def render(self) -> list[str]:
        """The ``gateway_pick_*`` families.  Canonical stage/seam labels
        always render (dashboards see a stable set); any extra keys that
        reached the aggregates render escaped."""
        with self._lock:
            samples = self._samples
            survivors = dict(self._stage_survivors)
            steered = dict(self._steered)
        lines = ["# TYPE gateway_pick_sample_total counter",
                 "gateway_pick_sample_total %d" % samples,
                 "# TYPE gateway_pick_narrowing gauge"]
        for stage in (*STAGES, *sorted(set(survivors) - set(STAGES))):
            mean = survivors.get(stage, 0) / samples if samples else 0.0
            lines.append('gateway_pick_narrowing{stage="%s"} %.2f'
                         % (escape_label(stage), mean))
        lines.append("# TYPE gateway_pick_steered_total counter")
        for seam in (*SEAMS, *sorted(set(steered) - set(SEAMS))):
            lines.append('gateway_pick_steered_total{seam="%s"} %d'
                         % (escape_label(seam), steered.get(seam, 0)))
        return lines

    def debug_payload(self) -> dict:
        """The ledger block of ``/debug/picks`` (records ride next to it
        via ``debug_picks_payload``)."""
        with self._lock:
            decisive = dict(self._decisive)
            escapes = dict(self._escapes)
            samples = self._samples
            picks = self._picks_seen
        self.maybe_tick()
        return {
            "picks": picks,
            "samples": samples,
            "decisive": decisive,
            "escapes": escapes,
            "rollup": self.seam_rollup(),
            "ticks": self.ticks,
            "last_tick": self.last_tick,
            "config": asdict(self.cfg),
        }


def debug_picks_payload(ledger: PickLedger, query) -> dict:
    """The ``/debug/picks`` response body: ``?since=<seq>`` incremental
    cursor + ``?limit=`` page size, same contract as
    ``events.debug_events_payload`` (poll with since=next_since until
    next_since == seq to drain)."""
    try:
        since = max(0, int(query.get("since", "0")))
    except ValueError:
        since = 0
    try:
        limit = max(1, min(int(query.get("limit", "256")), 2048))
    except ValueError:
        limit = 256
    rows = ledger.records(since=since, limit=limit)
    payload = ledger.debug_payload()
    payload.update({
        "seq": ledger.seq,
        "next_since": rows[-1]["seq"] if rows else ledger.seq,
        "records": rows,
    })
    return payload
