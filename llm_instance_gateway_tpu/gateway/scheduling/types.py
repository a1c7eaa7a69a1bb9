"""Scheduling request type.

Parity: reference ``pkg/ext-proc/scheduling/types.go:4-11`` (``LLMRequest``)
plus a token-count hint used by TPU-side token-aware routing (long-context
requests must land on replicas with enough KV-token headroom, SURVEY.md §5
"long-context").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence


class LazyPrefixHashes(Sequence):
    """Sequence facade that defers the prefix-hash chain until a consumer
    actually touches it.

    The chain (up to 32 chained blake2b digests over 8 KB of prompt,
    prefix_affinity.py) used to run on EVERY request body in the ext-proc
    hot path; threading this thunk instead means the digests only compute
    when a prefix-aware scheduler evaluates ``req.prefix_hashes`` — for a
    prefix-unaware build (or a custom drop-in that never reads the field)
    the cost is one object allocation.  Computes once, then serves the
    cached tuple; truthiness, iteration, indexing, and equality all match
    the eager tuple the field used to hold.
    """

    __slots__ = ("_fn", "_value")

    def __init__(self, fn: Callable[[], tuple]):
        self._fn = fn
        self._value: tuple | None = None

    def _resolve(self) -> tuple:
        if self._value is None:
            self._value = tuple(self._fn())
            self._fn = None  # drop the closure (it pins the prompt text)
        return self._value

    def __bool__(self) -> bool:
        return bool(self._resolve())

    def __len__(self) -> int:
        return len(self._resolve())

    def __iter__(self):
        return iter(self._resolve())

    def __getitem__(self, i):
        return self._resolve()[i]

    def __eq__(self, other) -> bool:
        if isinstance(other, LazyPrefixHashes):
            other = other._resolve()
        return self._resolve() == tuple(other) if isinstance(
            other, (tuple, list)) else self._resolve() == other

    def __hash__(self):
        return hash(self._resolve())

    def __repr__(self) -> str:
        if self._value is None:
            return "LazyPrefixHashes(<unevaluated>)"
        return f"LazyPrefixHashes({self._value!r})"


@dataclass
class LLMRequest:
    model: str
    target_models: dict[str, int] = field(default_factory=dict)
    resolved_target_model: str = ""
    critical: bool = False
    # TPU addition: estimated prompt tokens (0 = unknown).  Enables the
    # kv-token-headroom predicate; requests without the hint fall back to the
    # reference's percent-based signal.
    prompt_tokens: int = 0
    # Full criticality tier ("Critical"/"Default"/"Sheddable"): the
    # admission queue drains tiers at different weights; ``critical`` stays
    # the filter tree's binary signal (reference types.go parity).
    criticality: str = "Default"
    # TPU addition: chained block hashes of the prompt's leading text
    # (scheduling/prefix_affinity.py) — lets the scheduler prefer the
    # replica already holding this prefix's KV blocks.  Empty = no hint.
    # May hold a ``LazyPrefixHashes`` (the request handler threads one so
    # the digest chain never runs unless a scheduler consumes it).
    prefix_hashes: "tuple | LazyPrefixHashes" = ()
    # Tracing attribution (filled by the scheduling layer, read by the
    # request handler): how long this request waited in the admission
    # queue before a pod admitted it, and the (prefill_hop, decode_hop)
    # pick-time split of a disaggregated two-stage pick.
    admission_wait_s: float = 0.0
    pick_hops_s: tuple | None = None
    # The request's x-lig-trace-id (minted by the transport before
    # scheduling): lets the pick ledger's decision records join the
    # request's trace/span timeline.  Empty for callers without tracing
    # (sim, load rigs) — the ledger records it verbatim.
    trace_id: str = ""
