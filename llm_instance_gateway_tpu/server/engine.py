"""Continuous-batching serving engine: prefill/decode split over static slots.

JetStream-style architecture (the TPU answer to vLLM's continuous batching the
reference routes to, SURVEY.md §2.5/§7):

- **Prefill** runs one prompt at a time, padded to a small set of bucket
  lengths (a handful of compiled shapes, never per-request recompiles), and
  produces the prompt KV + the first sampled token.
- **Insert** writes the prompt KV into a free row of the static decode cache
  (``[n_layers, decode_slots, max_seq_len, n_kv, hd]``).
- **Generate** advances ALL active slots one token per step with a single
  fixed-shape jitted function (decode + sampling fused into one program,
  cache donated so XLA updates it in place).

The engine thread interleaves: one prefill admission, then decode steps.
Queues are explicit and exported: ``prefill_queue`` (admission backlog) and
``decode_wait`` (prefilled but waiting for a free slot) — the two signals the
gateway's prefill-aware scheduler routes on (``gateway/scheduling``).

Requests carry an optional LoRA adapter name; the slot id from
``LoRAManager`` rides into the decode batch per-row, so one batch multiplexes
adapters and the base model (the premise of the gateway's affinity routing).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import logging
import math
import queue as queue_mod
import threading
import time
import uuid
from collections.abc import Callable
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models import paged as paged_lib
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.ops import pallas_attention
from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda
from llm_instance_gateway_tpu.server.sampling import (
    STOP_LEN,
    STOP_SEQS,
    encode_stop_rows,
    sample,
    sample_routed,
    stop_hist_update,
    stop_suffix_hit,
)
from llm_instance_gateway_tpu.server.kv_ledger import KvLedger
from llm_instance_gateway_tpu.server.profiler import StepProfiler
from llm_instance_gateway_tpu.server.usage import UsageTracker, owner_key
from llm_instance_gateway_tpu.tracing import LATENCY_BUCKETS, Histogram

logger = logging.getLogger(__name__)

# Top-K alternatives computed device-side per step (the OpenAI completions
# API maximum): one compiled program for the whole batch, which computes
# them in the steps where a live row asked (``_logprobs_if_asked``); the
# host stores values only for requests that asked.
LOGPROB_TOPK = 5
MAX_LOGIT_BIAS = 32  # per-request logit_bias entries (static lanes)

# Dispatch-size histogram edges for tpu:dispatch_steps: the planner only
# emits powers of two, so the buckets land exactly on its choices.
STEP_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
# Tokens/sec EMA smoothing for the exported throughput gauge.
TPS_EMA_ALPHA = 0.2

# The per-slot inputs of a decode step as (name, shape of one row, value of
# an empty row), by dtype.  Each group is ONE flat host buffer: the engine's
# ``_slot_<name>`` mirrors are views into it, so the ~40 sites that write a
# row need no dirty mark, and a dispatch uploads two buffers, not fifteen
# arrays.  ``_slot_views`` is the layout on both sides of the transfer.
_SLOT_I32 = (
    ("positions", (), 0), ("lora", (), -1),
    ("topk", (), 0), ("remaining", (), 0), ("seed", (), -1),
    ("bias_ids", (MAX_LOGIT_BIAS,), -1),
    ("stop_ids", (STOP_SEQS, STOP_LEN), -1), ("stop_lens", (STOP_SEQS,), 0),
    ("stop_hist", (STOP_LEN,), -1),
    # 1 from a row's activation to the next dispatch: that block takes the
    # row's position, budget and stop history from this buffer and not from
    # the device carry (``_stage_carry``).
    ("fresh", (), 0),
    # 1 while the row's request asked for logprobs (``_logprobs_if_asked``).
    ("logprobs", (), 0),
)
_SLOT_F32 = (
    ("temp", (), 0.0), ("topp", (), 1.0), ("presence", (), 0.0),
    ("frequency", (), 0.0), ("bias_vals", (MAX_LOGIT_BIAS,), 0.0),
)
# Host-to-device transfers one staged decode dispatch issues: the two
# buffers above (tpu:decode_stage_ops_total).
STAGE_UPLOADS = 2


def _slot_spans(fields, b: int) -> dict:
    """``{name: (slice of the flat buffer, (b, *shape))}`` for ``b`` rows
    laid out as ``fields``."""
    out, at = {}, 0
    for name, shape, _ in fields:
        n = b * math.prod(shape)
        out[name] = slice(at, at + n), (b, *shape)
        at += n
    return out


def _slot_views(buf, fields, b: int) -> dict:
    """``{name: buf's [b, *shape] part}`` for a flat buffer laid out as
    ``fields``: views of a numpy buffer on the host, static slices of the
    uploaded copy inside the decode program."""
    return {name: buf[span].reshape(shape)
            for name, (span, shape) in _slot_spans(fields, b).items()}


def _stage_carry(carry, i32: dict):
    """The device carry as the next block takes it, decided from what the
    step stages anyway (``i32``: ``_slot_views`` of the uploaded int32
    buffer), so that one fixed-shape program serves whatever the host freed
    or admitted since the last block:

    - a row the host does not hold (staged budget 0: never registered, or
      cleared for a reason the device cannot see: a custom stop id, the
      length cap, a cancellation, an exhausted pool) has its budget zeroed;
    - a row activated since the last block (``fresh``) takes its position,
      budget and stop history from the staged values; its first token is
      the carry's (the prefill left it on the device), and where the host
      record holds no emitted token yet it also seeds the history.
    """
    tokens, positions, remaining, stop_hist = carry
    fresh = i32["fresh"] > 0
    positions = jnp.where(fresh, i32["positions"], positions)
    remaining = jnp.where(
        fresh, i32["remaining"],
        jnp.where(i32["remaining"] > 0, remaining, 0))
    staged_hist = i32["stop_hist"]
    seeded = staged_hist.at[:, -1].set(
        jnp.where(staged_hist[:, -1] < 0, tokens, staged_hist[:, -1]))
    stop_hist = jnp.where(fresh[:, None], seeded, stop_hist)
    return tokens, positions, remaining, stop_hist


def _slot_buffer(fields, b: int, dtype) -> tuple[np.ndarray, dict]:
    """A host buffer for ``b`` empty rows of ``fields``, and its views."""
    buf = np.empty((b * sum(math.prod(s) for _, s, _ in fields),), dtype)
    views = _slot_views(buf, fields, b)
    for name, _, empty in fields:
        views[name][...] = empty
    return buf, views


class EngineDraining(RuntimeError):
    """submit() refused because the engine is in graceful termination.

    Distinct type so the HTTP layer maps exactly this condition to a 503
    the gateway retries elsewhere; any other RuntimeError stays a 500
    (reference parity: only a draining replica reports itself unroutable;
    pkg/ext-proc/handlers/server.go's ResourceExhausted mapping is the
    analogous single-condition translation)."""


def _named(name: str, fn, *bound):
    """``functools.partial(fn, *bound)`` under a stable ``__name__``: jit
    names the compiled program after it (``jit_<name>`` in a device trace;
    a bare partial is ``jit__unknown``), and a partial keeps the signature
    that ``donate_argnames`` / ``static_argnames`` are resolved against."""
    part = functools.partial(fn, *bound)
    part.__name__ = part.__qualname__ = name
    return part


def _abstract(x):
    """Shape, dtype and, of a committed array, sharding: what a jitted
    program's trace is keyed by."""
    committed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=x.sharding if committed else None)


def _in_order(targets) -> tuple[str, ...]:
    """A set of LoRA targets in ``models.lora.TARGETS``' order."""
    return tuple(t for t in lora_lib.TARGETS if t in targets)


def _in_phase(name: str, hand_over: bool = False):
    """The decorated engine-thread method runs as phase ``name`` of the
    step profiler's stack (server/profiler.py).  With ``hand_over`` it gets
    the open phase as its ``ph`` argument and renames it as it goes
    (``ph.to("decode.stage")``): a run of phases in one method."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *args, **kwargs):
            with self._phase(name) as ph:
                if hand_over:
                    kwargs["ph"] = ph
                return fn(self, *args, **kwargs)
        return wrapped
    return deco


def _is_ready(array) -> bool:
    """Whether the device has produced ``array``; never waits."""
    return array.is_ready()


def _publish(req: "Request") -> None:
    """Wake the consumer of ``req``'s stream (engine thread: a token was
    appended, or the request finished).  ``t_emit`` keeps the oldest stamp
    the consumer has not taken yet, so a consumer that falls a step behind
    reads its whole lag."""
    if not req.t_emit:
        req.t_emit = time.time()
    req.stream_event.set()


def _logprob_values(logits, sampled, valid_vocab: int):
    """(sampled-token logprob, top-K logprobs, top-K ids) from raw logits.

    Model logprobs (pre-temperature), padded-vocab positions masked out —
    consistent with what the sampler can actually emit.
    """
    masked = jnp.where(
        jnp.arange(logits.shape[-1]) < valid_vocab, logits, -jnp.inf
    )
    logp = jax.nn.log_softmax(masked, axis=-1)
    sampled_lp = jnp.take_along_axis(logp, sampled[..., None], axis=-1)[..., 0]
    top_v, top_i = jax.lax.top_k(logp, LOGPROB_TOPK)
    return sampled_lp, top_v, top_i


_logprob_info = jax.named_scope("logprobs")(_logprob_values)


@jax.named_scope("logprobs")
def _logprobs_if_asked(asked, logits, sampled, valid_vocab: int):
    """``_logprob_info`` where ``asked`` (a scalar on the device: some live
    row of the step wants logprobs), zeros of its shapes where not.  One
    ``lax.cond`` inside the caller's program, as ``sample_routed`` chooses
    its path: a step nobody asked pays no pass over ``[B, V]``, a step one
    row asked pays for the whole batch, and a row's values never depend on
    what its neighbours asked."""
    def values():
        return _logprob_values(logits, sampled, valid_vocab)

    return jax.lax.cond(
        asked, values,
        lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                             jax.eval_shape(values)))


@dataclass
class EngineConfig:
    decode_slots: int = 8
    max_seq_len: int = 1024
    prefill_buckets: tuple[int, ...] = (16, 32, 64, 128, 256, 512, 1024)
    max_queue: int = 256
    # Decode steps fused into one jitted program per host sync.  Each host
    # round-trip costs dispatch + readback latency (on the v5e host: not
    # measured); K>1 amortizes it.  Stop detection is device-side (rows
    # freeze at EOS/budget and emit invalid steps), so large K costs only
    # K-step admission latency, not wasted tokens.
    decode_steps_per_sync: int = 1
    # Adaptive multi-step dispatch (ROADMAP item 2): > 0 makes this the
    # CEILING of a per-dispatch planner that picks n_steps from the live
    # batch instead of the static decode_steps_per_sync — the minimum
    # remaining token budget across active rows (never fuse past the point
    # every row is frozen), pending admissions / parked inserts / chunk
    # streams (a waiting prefill or stream chunk must not stall behind a
    # fused block: those dispatches drop to 1 step), and SSE cadence
    # (dispatches serving a streaming consumer cap at
    # ``adaptive_stream_cap`` so fusion can't regress perceived TPOT).
    # Choices quantize to powers of two, so the compiled-variant set stays
    # log2(ceiling) deep.  0 = off (static decode_steps_per_sync).
    adaptive_steps: int = 0
    # Fused-step cap while any active row streams to an SSE consumer (the
    # planner input that keeps adaptive fusion from batching a stream's
    # tokens into bursts).  1 = per-token cadence (default).
    adaptive_stream_cap: int = 1
    # Device-side stop sequences: compile each row's stop strings (token
    # suffixes) into per-row automata evaluated INSIDE the fused decode
    # program, so a row whose stop hits mid-block freezes there with zero
    # host round-trips (the host oracle still trims once per dispatch —
    # token parity is structural, not probabilistic).  False = host-only
    # stop checks: the reference tests/test_decode_levers.py holds the
    # device automata to, a constructor argument with no CLI flag.
    device_stops: bool = True
    # Concurrent chunk-stream lanes: how many long prompts may stream
    # chunk-by-chunk into reserved cache lanes AT ONCE (fair round-robin,
    # one chunk per engine cycle across lanes).  1 = the old single-lane
    # behavior where a second long prompt head-of-line blocks behind the
    # first.  Lanes beyond the first admit only with KV headroom left for
    # active decode growth (paged pools).
    stream_lanes: int = 1
    # Chunk programs one loop turn may enqueue before its decode block.
    # 1 = one chunk a turn (a decode step between any two chunks).  N > 1
    # drains a backlog of long prompts faster: up to N chunks go out back
    # to back, a stream that ends in the turn hands its lane to the next
    # waiting prompt in the same turn, and the live rows wait N chunk
    # programs for their next token.  The device's work is the same; what
    # moves is how long a prompt stands in ``num_requests_waiting``, which
    # a gateway sheds on (queueThresholdCritical).
    stream_burst: int = 1
    # Prefill-ahead depth: prompts prefilled while all decode slots are busy
    # wait here (KV held off-cache) and insert the instant a slot frees —
    # the decode batch never idles a slot waiting for a prefill, and the
    # queue length is the true ``tpu:decode_queue_size`` the gateway's
    # prefill-aware scheduler routes on.  None = decode_slots.
    decode_wait_cap: int | None = None
    # Grouped prefill admission: when several queued prompts land in the
    # SAME bucket and free slots exist, up to this many prefill as ONE
    # [P, bucket] program instead of P dispatches — small-batch prefill
    # underfills the MXU and each dispatch pays the host round-trip, so
    # bursts admit near-P-times faster.  Compiled-shape set stays bounded:
    # buckets x group sizes (2..prefill_batch).  1 = off (existing path).
    # Applies to the direct-admission path of the contiguous-lane cache;
    # paged admissions stay per-request (block allocation is per-row
    # backpressure).
    prefill_batch: int = 1
    # Abandoned-handoff TTL: an attach-imported request still PARKED in
    # decode_wait this many seconds after admission is presumed abandoned
    # (its gateway gave up on the hop and rerouted) and is failed/freed by
    # the engine loop's sweep instead of eventually decoding tokens nobody
    # will read.  0 disables; the gateway's best-effort
    # ``POST /v1/prefill/release`` is the fast path, this is the backstop.
    handoff_ttl_s: float = 0.0
    # Paged KV cache (models/paged.py): block size in tokens; None = the
    # default contiguous-lane cache.  With paging, the kv metrics report
    # allocated/total blocks — vLLM's gpu_cache_usage_perc semantics, which
    # the reference's 0.8 routing threshold was tuned against — and
    # ``paged_kv_blocks`` may be set below slots*ceil(max_seq/block) to
    # oversubscribe HBM for short-sequence traffic (a request that outgrows
    # an exhausted pool fails with "kv pool exhausted"; keep the gateway's
    # KV threshold at/below 0.8 to stay clear of it).
    paged_kv_block: int | None = None
    paged_kv_blocks: int | None = None
    # Speculative decoding: a small DRAFT model proposes this many tokens
    # per cycle; the target model verifies them in ONE multi-token forward
    # (extend_step) — decode is HBM-weight-bound, so scoring K+1 tokens
    # costs barely more than one step, and accepted prefixes multiply
    # tokens/step.  Greedy rows (temperature 0) accept the longest matching
    # prefix — EXACT greedy parity with non-speculative decoding; sampled
    # rows fall back to one verified token per cycle.  Requires
    # ``draft_params``/``draft_cfg`` at Engine construction.  Composes with
    # ``decode_steps_per_sync`` (cycles are fused into one device-side
    # scan of ceil(steps/(K+1)) cycles per dispatch), the paged cache
    # (extend_step_paged verify), and GSPMD serve meshes (draft replicated)
    # — including all three together on a tensor/expert mesh
    # (parity-tested).  paged + a data mesh is excluded by the engine's
    # own paged/mesh rule, independent of speculation; paged + a sequence
    # mesh (ring prefill) constructs, with speculation verifying through
    # extend_step_paged as usual.
    speculative_k: int = 0
    # KV-cache quantization ("int8" or None): K/V stored int8 with
    # per-(position, kv-head) f32 scales, dequantized inside the fused
    # attention reads — long-context decode is KV-bandwidth-bound and int8
    # halves that HBM traffic (the JetStream serving trade; scale overhead
    # 1/(2*head_dim)).  Composes with BOTH cache layouts: contiguous lanes
    # and the paged pool (vLLM's quantized-paged-KV composition — scale
    # pools index by physical block, so prefix-cache reuse carries them
    # for free), and with prefix caching, grouped admission, chunked
    # prefill, speculative decoding, and GSPMD meshes.  Decode attention
    # runs the int8-aware Pallas kernel
    # (ops/pallas_decode_attention.decode_attention_quant — dequantizes in
    # VMEM at the MXU feed), so the bandwidth win and the kernel win stack
    # on the lane path AND on the paged gathered view.
    kv_cache_quant: str | None = None
    # Pool role under cross-engine prefill/decode disaggregation
    # (server/kv_transfer.py): "prefill" replicas serve prefill_only()
    # handoffs, "decode" replicas serve attach_prefilled() imports,
    # "collocated" runs both phases locally (the default).  The role is
    # ADVISORY — every engine keeps the full API in every role, so a
    # gateway can always fall back to single-hop serving — but it is
    # exported via /metrics and drives the gateway's two-stage routing.
    role: str = "collocated"
    # Prefix caching (paged mode only): full prompt blocks are
    # content-addressed (chained hashes, vLLM-style) and retained with
    # refcounts after a request finishes; a later prompt sharing the prefix
    # maps the cached blocks into its table and prefills only the suffix.
    # Reuse applies on BOTH admission paths: bucketed prompts (the shared
    # system prompt below the largest bucket — the common case) run one
    # suffix-bucket chunk program over the mapped prefix, and chunk-stream
    # prompts skip whole leading chunks.  Grouped/parked admissions
    # register their blocks for later consumers.  Zero-ref cached blocks
    # are evicted LRU when the pool needs space, so enabling this costs
    # nothing but the hashing.
    prefix_cache: bool = False


@dataclass
class SamplingParams:
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # Reproducible sampling (OpenAI `seed`): tokens depend only on
    # (seed, position, distribution) — batch composition, restarts, and
    # the engine RNG stop mattering.  None = engine RNG.
    seed: int | None = None
    # OpenAI penalties over GENERATED tokens (vLLM semantics — the prompt
    # does not count): presence subtracts a flat amount from every
    # already-emitted token's logit, frequency subtracts per occurrence.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # OpenAI logit_bias: {token_id: bias} added to the logits before every
    # pick (greedy included).  At most MAX_LOGIT_BIAS entries (static
    # device lanes).
    logit_bias: dict[int, float] | None = None


def _bias_arrays(sp: "SamplingParams"):
    """(ids [MAX_LOGIT_BIAS] int32, vals f32) device lanes for a request's
    logit_bias dict (-1 = unused entry)."""
    ids = np.full((MAX_LOGIT_BIAS,), -1, np.int32)
    vals = np.zeros((MAX_LOGIT_BIAS,), np.float32)
    if sp.logit_bias:
        for j, (tid, bv) in enumerate(sorted(sp.logit_bias.items())):
            ids[j] = tid
            vals[j] = bv
    return ids, vals


def _seed_i32(seed: int | None) -> int:
    """Map a user seed onto the device-side int32 lane: None -> -1 (engine
    RNG); any int masks to non-negative 31-bit (PRNGKey folds it, so only
    equality of masked values matters for reproducibility)."""
    return -1 if seed is None else (int(seed) & 0x7FFFFFFF)


@dataclass
class Request:
    prompt_tokens: list[int]
    max_new_tokens: int = 64
    sampling: SamplingParams = field(default_factory=SamplingParams)
    adapter: str | None = None
    stop_token_ids: tuple[int, ...] = ()
    # Multi-token stop sequences (tokenized stop strings): generation ends
    # the moment the output's token tail equals one of these; the matching
    # tokens are emitted and finish_reason is "stop" (same inclusion
    # semantics as stop_token_ids).  Evaluated device-side inside the
    # fused decode block when they fit the static automaton lanes
    # (sampling.STOP_SEQS x STOP_LEN) and ``EngineConfig.device_stops``;
    # the host oracle in the result walk is authoritative either way.
    stop_sequences: tuple[tuple[int, ...], ...] = ()
    # Set by the transport for SSE responses: the adaptive dispatch
    # planner caps fusion at ``adaptive_stream_cap`` while this request is
    # active, so a live stream keeps per-token cadence.
    streaming: bool = False
    request_id: str = field(default_factory=lambda: uuid.uuid4().hex[:16])
    # Record per-token logprobs: None = off; 0 = sampled token only (e.g.
    # best_of ranking); 1..LOGPROB_TOPK = also that many top alternatives.
    logprobs: int | None = None
    # Lifecycle (filled by the engine).
    # Cross-engine disaggregation: prefill_only() deposits the request's
    # PrefillHandoff here (finish_reason "handoff").
    handoff: object = None
    output_tokens: list[int] = field(default_factory=list)
    output_logprobs: list[float] = field(default_factory=list)
    output_top_logprobs: list[dict[int, float]] = field(default_factory=list)
    finish_reason: str | None = None
    error: str | None = None
    t_submit: float = 0.0
    # Wall clock of the request's first prefill compute (queue wait ends
    # here; the tracing layer derives the engine.queue_wait/engine.prefill
    # span boundary from it).  0.0 = never prefilled on THIS engine (e.g.
    # an attached handoff, whose prefill ran on the prefill-role replica).
    t_prefill_start: float = 0.0
    # The same instant on the step profiler's clock (perf_counter): where
    # the prefill's wall sits in the engine thread's gap chain.
    t_prefill_start_pc: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    # What the engine knows of this request's admission, for the transport's
    # ``engine.prefill`` span: ``prompt_tokens``, ``bucket``, ``rows`` (rows
    # decoding when it was admitted: the rows it stalls); what the prompt
    # cost the device: ``programs`` enqueued for it, the ``positions`` they
    # computed (its padding included; of a grouped program its own row) and
    # ``device_s``, the sum of their intervals on the completion chain; and,
    # once the admission is settled, ``stage_s`` / ``wait_s`` / ``emit_s``
    # (a prompt streamed in chunks: summed over its chunks).
    prefill_attrs: dict = field(default_factory=dict)
    done: threading.Event = field(default_factory=threading.Event)
    # Incremental consumption point for streaming responses.
    stream_event: threading.Event = field(default_factory=threading.Event)
    # Wall clock of the oldest token the stream's consumer has not looked at
    # yet (``Engine._publish`` stamps it, the consumer zeroes it at each
    # wake): emit-to-write lag is the consumer's ``now - t_emit``.
    t_emit: float = 0.0
    # Set by the transport when the client went away: the engine frees the
    # slot at the next block boundary instead of decoding to completion.
    cancelled: threading.Event = field(default_factory=threading.Event)

    @property
    def ttft_s(self) -> float:
        return (self.t_first_token - self.t_submit) if self.t_first_token else 0.0


class PagedPoolExhausted(Exception):
    """The paged KV pool has no free blocks (oversubscribed pool)."""


@dataclass
class _Slot:
    request: Request
    lora_slot: int
    position: int  # position of the NEXT token to generate


class _HostBatch:
    """One device->host transfer for a grouped-prefill output set.

    P separate 0-d device scalars, each async-copied and later transferred
    alone, would re-pay the per-row round-trips the batched prefill is
    meant to amortize.  This starts ONE async copy per array and
    materializes all rows with one ``np.asarray`` per array on first
    access.
    """

    __slots__ = ("arrays", "_host")

    def __init__(self, *arrays):
        self.arrays = arrays
        for a in arrays:
            try:
                a.copy_to_host_async()
            except AttributeError:
                pass
        self._host = None

    def host(self):
        if self._host is None:
            self._host = tuple(np.asarray(a) for a in self.arrays)
        return self._host


class _Row:
    """Row ``i`` of array ``a`` in a ``_HostBatch``: numpy-protocol view
    whose first host access materializes the whole batch.  ``dev`` carries
    the device slice for carry scatters that must stay device-resident
    (the loop's no-host-round-trip contract)."""

    __slots__ = ("_batch", "_a", "_i", "dev")

    def __init__(self, batch: _HostBatch, a: int, i: int, dev=None):
        self._batch, self._a, self._i, self.dev = batch, a, i, dev

    def _value(self):
        return self._batch.host()[self._a][self._i]

    def __array__(self, dtype=None, copy=None):
        v = np.asarray(self._value())
        return v.astype(dtype) if dtype is not None else v

    def __int__(self):
        return int(self._value())


@dataclass
class _WaitingPrefill:
    """A prefilled request parked in ``decode_wait``: prompt KV held
    off-cache until a decode slot frees (JetStream's prefill/decode
    disaggregation inside one engine)."""

    request: Request
    first_token: object  # device scalar
    k: object            # [L, 1, bucket, Kh, hd]
    v: object
    n: int
    lora_slot: int
    # First-token (lp, top_v, top_i) device tuple; None once recorded.
    lp_info: object = None
    # Cross-engine attach: the first token was already emitted (on THIS
    # engine, at attach admission) — the insert must not schedule a second
    # pending-first materialization.
    first_emitted: bool = False
    # Imported via attach_prefilled: the insert may map already-cached
    # prefix blocks instead of re-writing identical content.
    from_handoff: bool = False
    # Wall clock at park time; with ``handoff_ttl_s`` set, an imported
    # handoff that sat parked past the TTL is abandoned work (the gateway
    # that posted it gave up and rerouted) and is swept instead of slotted.
    t_parked: float = 0.0


@dataclass
class _PromptProgram:
    """A prompt program on the device's queue that the loop has not seen
    complete yet (``Engine._note_prompt_program`` / ``_prompt_programs_done``)."""

    program: str  # metrics_registry.PROMPT_PROGRAMS
    positions: int  # computed, padding included
    t0: float  # its enqueue, on perf_counter
    out: object  # a result of it: ready once the program is done
    reqs: tuple  # the requests whose prompt it computes


@dataclass
class _ChunkStream:
    """A long prompt streaming into a RESERVED cache lane one chunk per
    engine cycle, so active decode slots keep stepping between chunks
    (round-1 admitted the whole prompt in one blocking burst, coupling
    queued TTFT to running TPOT)."""

    request: Request
    slot_idx: int
    lora_slot: int
    next_start: int = 0
    last_logits: object = None


def _refuse_what_lanes_alone_serve(model_cfg, cfg: "EngineConfig",
                                   lora_manager, mesh) -> None:
    """A latent (MLA) cache, a recurrent state beside the K/V lanes (a
    state-space mixer), a stack of two kinds of layer with ring lanes
    for its window layers, a stack some of whose layers are no
    attention and hold a conv state (``models/shortconv.py``), and a stack
    of delta-rule layers with a matrix state beside the latent rows of its
    latent layers, its experts held as a chip's share (``models/kda.py``),
    are served from contiguous lanes on one device, base model only.  Every other way to hold or move a row's state assumes
    that it is per-head K and V by position and nothing else, in one stack
    a layer: what a rebuilt, shared, shipped or rolled-back row would need
    of a state that is no function of positions, or of a ring that has
    already overwritten them, is not there.  Each is refused here by name
    rather than run wrong."""
    if model_cfg.kda_n_heads:
        kind = ("keeps a delta-rule matrix state (layers without attention, "
                "whose state is no function of positions) beside the latent "
                "rows of its latent layers"
                + (", and holds a share of each layer's experts"
                   if model_cfg.n_experts_local else ""))
        loras = ("--max-loras above 0 (models/lora.py's buffers are scanned "
                 "a layer a step, not a period, and name no delta-rule or "
                 "latent target)")
        extra = {"--prefill-batch above 1 (a grouped prefill's rows are "
                 "cut out of one stack of K and V)": cfg.prefill_batch > 1}
    elif model_cfg.conv_kernel:
        kind = ("keeps a conv state (layers without attention, whose state "
                "is no function of positions) beside the K/V lanes of its "
                "attention layers")
        loras = ("--max-loras above 0 (models/lora.py's buffers are scanned "
                 "a layer a step, not a period, and name no conv target)")
        extra = {"--prefill-batch above 1 (a grouped prefill's rows are "
                 "cut out of one stack of K and V)": cfg.prefill_batch > 1}
    elif model_cfg.layer_pattern:
        kind = ("scans a period of layer kinds over ring lanes beside its "
                "full lanes")
        loras = ("--max-loras above 0 (models/lora.py's buffers are scanned "
                 "a layer a step, not a period)")
        extra = {"--prefill-batch above 1 (a grouped prefill's rows are "
                 "cut out of one stack of K and V)": cfg.prefill_batch > 1}
    elif model_cfg.latent_width:
        kind = "keeps a latent (MLA) KV cache"
        loras = ("--max-loras above 0 (models/lora.py sizes its targets "
                 "from per-head q, k, v)")
        extra = {}
    else:
        kind = "keeps a recurrent (state-space) state beside its KV lanes"
        loras = ("--max-loras above 0 (an adapter's delta beside the "
                 "model's fixed multipliers is undefined here)")
        extra = {"--prefill-batch above 1 (a grouped prefill's rows are "
                 "cut out of K and V alone)": cfg.prefill_batch > 1}
    asked = {
        "--paged-kv-block (models/paged.py; the prefix cache needs it)":
            cfg.paged_kv_block is not None or cfg.prefix_cache,
        "--kv-quantize int8": cfg.kv_cache_quant is not None,
        "--speculative (extend_step)": cfg.speculative_k > 0,
        "--role prefill/decode (server/kv_transfer.py)":
            cfg.role != "collocated",
        "--mesh": mesh is not None and mesh.size > 1,
        loras: lora_manager is not None,
        **extra,
    }
    for what, on in asked.items():
        if on:
            raise ValueError(
                f"{model_cfg.name} {kind}, which {what} does not serve yet")


class Engine:
    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        engine_cfg: EngineConfig | None = None,
        lora_manager=None,
        eos_id: int | None = None,
        dtype=jnp.bfloat16,
        seed: int = 0,
        mesh=None,
        draft_params=None,
        draft_cfg: ModelConfig | None = None,
    ):
        self.model_cfg = model_cfg
        self.cfg = engine_cfg or EngineConfig()
        self.params = params
        self.lora = lora_manager
        self.eos_id = eos_id
        self._rng = jax.random.PRNGKey(seed)

        self._spec = self.cfg.speculative_k > 0
        if self._spec:
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "speculative_k > 0 requires draft_params and draft_cfg")
            if draft_cfg.vocab_size != model_cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share the token space "
                    f"({draft_cfg.vocab_size} != {model_cfg.vocab_size})")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg

        b = self.cfg.decode_slots
        self.paged = self.cfg.paged_kv_block is not None
        self._kv_quant = self.cfg.kv_cache_quant is not None
        self._latent = bool(model_cfg.latent_width)
        self._recurrent = bool(model_cfg.ssm_d_inner)
        # A stack of two kinds of layer: full lanes and, for its window
        # layers, ring lanes (``transformer.init_decode_cache``).
        self._ringed = bool(model_cfg.layer_pattern)
        # Layers without attention: a conv state a slot beside the lanes.
        self._conv = bool(model_cfg.conv_kernel)
        # Delta-rule layers: a matrix state a slot and a head.
        self._kda = bool(model_cfg.kda_n_heads)
        # Positions a window layer's ring holds of a row (0: no window).
        self._window = min(model_cfg.sliding_window, self.cfg.max_seq_len)
        if self._latent or self._recurrent or self._ringed:
            _refuse_what_lanes_alone_serve(
                model_cfg, self.cfg, lora_manager, mesh)
        if self.cfg.kv_cache_quant not in (None, "int8"):
            raise ValueError(
                f"kv_cache_quant={self.cfg.kv_cache_quant!r}: only 'int8' "
                "(or None) is supported")
        if self.paged:
            self._block = self.cfg.paged_kv_block
            self._max_blocks_per_seq = -(-self.cfg.max_seq_len // self._block)
            self._n_blocks = (
                self.cfg.paged_kv_blocks
                if self.cfg.paged_kv_blocks is not None
                else b * self._max_blocks_per_seq
            )
            self.cache = paged_lib.init_paged_cache(
                model_cfg, b, self.cfg.max_seq_len,
                self._n_blocks, self._block, dtype=dtype,
                quantized=self._kv_quant,
            )
            # Host-side allocator: physical block 1..n are allocatable;
            # block 0 is the trash block (paged_lib.TRASH_BLOCK).
            self._free_blocks: list[int] = list(range(1, self._n_blocks + 1))
            self._row_blocks: list[list[int]] = [[] for _ in range(b)]
            self._tables_host = np.zeros(
                (b, self._max_blocks_per_seq), np.int32)
            self._tables_dirty = False
            # Prefix cache state: chain-hash -> block, block -> (hash, refs),
            # plus an LRU of zero-ref cached blocks (evicted on demand).
            self._prefix_enabled = self.cfg.prefix_cache
            self._prefix_table: dict[int, int] = {}
            self._block_hash: dict[int, int] = {}
            self._block_refs: dict[int, int] = {}
            self._evictable: "collections.OrderedDict[int, int]" = (
                collections.OrderedDict())
            self.prefix_reused_tokens = 0
        else:
            self._prefix_enabled = False
            self.cache = transformer.init_decode_cache(
                model_cfg, b, self.cfg.max_seq_len, dtype=dtype,
                quantized=self._kv_quant,
            )
        # (positions a tile, tiles a lane) of the decode-attention kernel's
        # walk over this cache, for tpu:decode_attn_grid_steps_total: a
        # page of the pool, else the kernel's own tile; (0, 0) where no
        # kernel takes the cache's shape.
        self._attn_tiles = (
            (self._block, self._max_blocks_per_seq) if self.paged
            else pda.mla_tiles(self.cache["k"]) if self._latent
            else pda.lane_tiles(self.cache["k"]))
        # Sharded serving (SURVEY §2.5/§7 ICI domain): pin params and the
        # decode cache to the mesh via GSPMD specs; every jitted step then
        # partitions from its committed inputs — XLA inserts the ICI
        # collectives (one psum per layer on attn/MLP outputs for Megatron
        # tensor parallelism), nothing in the loop code changes.
        self.mesh = mesh
        # Pallas kernels under the mesh: GSPMD can't partition an opaque
        # pallas_call, so under a >1 mesh the in-model auto-dispatch is
        # always turned OFF (it would replicate full arrays to every
        # device) — and the kernels come back as shard_map wrappers that
        # run them shard-local over the data/tensor axes
        # (ops/sharded_attention.py).  Head layouts the wrappers can't
        # split group-aligned keep the XLA fallback.
        self._prefill_attn_fn = None
        self._decode_attn_fn = None
        if mesh is not None and mesh.size > 1 and (
            model_cfg.use_flash_attention or model_cfg.use_pallas_decode
        ):
            from llm_instance_gateway_tpu.ops import sharded_attention

            wants_flash = model_cfg.use_flash_attention
            wants_decode = model_cfg.use_pallas_decode
            model_cfg = dataclasses.replace(
                model_cfg, use_flash_attention=False, use_pallas_decode=False)
            self.model_cfg = model_cfg
            if sharded_attention.mesh_supports(model_cfg, mesh):
                if wants_flash and mesh.shape.get("sequence", 1) == 1:
                    # sequence>1 prefill belongs to the ring path; bucketed
                    # prefill there stays XLA rather than paying redundant
                    # per-shard compute.
                    self._prefill_attn_fn = (
                        sharded_attention.make_flash_prefill(model_cfg, mesh))
                if (wants_decode and not self.paged
                        and b % mesh.shape.get("data", 1) == 0):
                    # The batch gate is load-bearing: a non-divisible B
                    # would force shard_map to replicate the data-sharded
                    # KV cache (a full-cache all-gather per layer per
                    # step) — worse than the XLA fallback.  Quantized
                    # lanes get the QUANT-AWARE wrapper (raw int8 + scales
                    # shard-local into the int8 kernel, dequant in VMEM):
                    # the bandwidth win and the kernel win stack under the
                    # mesh too — a plain wrapper here would materialize a
                    # full bf16 cache per layer per step.
                    self._decode_attn_fn = (
                        sharded_attention.make_cached_decode_quant(
                            model_cfg, mesh) if self._kv_quant else
                        sharded_attention.make_cached_decode(model_cfg, mesh))
                logger.info(
                    "mesh size %d: Pallas kernels via shard_map "
                    "(flash_prefill=%s, cached_decode=%s)", mesh.size,
                    self._prefill_attn_fn is not None,
                    self._decode_attn_fn is not None)
            else:
                logger.info(
                    "mesh size %d: head layout (%d q heads, %d kv heads) "
                    "does not split group-aligned over tensor=%d; using "
                    "XLA attention", mesh.size, model_cfg.n_heads,
                    model_cfg.n_kv_heads, mesh.shape.get("tensor", 1))
        if mesh is not None:
            from llm_instance_gateway_tpu.parallel import sharding as sharding_lib

            if mesh.shape.get("pipe", 1) > 1:
                # Serving decodes layer-by-layer through one cache; a pipe
                # axis would only replicate (parallel.pipeline covers the
                # training/prefill side).  Refuse rather than silently waste
                # 1/pipe of the pool.
                raise ValueError(
                    "serving meshes must have pipe=1; fold those devices "
                    "into tensor/data instead")
            if self.paged and mesh.shape.get("data", 1) > 1:
                # The block pool belongs to no mesh axis (rows serve
                # whichever requests the host allocator assigns), so the
                # batch can't shard over data.  Tensor/expert-parallel
                # paged serving — the big-model case — IS supported
                # (paged_cache_specs), and a sequence axis serves RING
                # PREFILL (the pool replicates over it; decode ignores it).
                raise ValueError(
                    "paged KV on a mesh requires data=1 (the pool "
                    "replicates over fsdp/sequence and shards kv-heads "
                    "over tensor): scale data-parallel replicas as "
                    "separate engine processes behind the gateway")
            self.params = sharding_lib.shard_pytree(
                self.params, sharding_lib.param_specs(model_cfg), mesh)
            self.cache = sharding_lib.shard_pytree(
                self.cache,
                (sharding_lib.paged_cache_specs(model_cfg, mesh,
                                                quantized=self._kv_quant)
                 if self.paged else
                 sharding_lib.cache_specs(model_cfg, mesh,
                                          quantized=self._kv_quant)),
                mesh)
        # Ring-attention prefill (parallel/long_context.py): with a
        # sequence axis in the mesh, prompts beyond the largest bucket run
        # as ONE sequence-parallel program over the ring instead of
        # chunk-streaming through the cache lane — each device holds S/n of
        # the activations, so the prompt budget scales with the mesh.
        self._ring = None
        if mesh is not None and mesh.shape.get("sequence", 1) > 1:
            # Lane AND paged engines: the ring computes sequence-sharded
            # prompt KV; the insert (lane dynamic-slice or paged block
            # scatter) reshards it to the cache's own spec under GSPMD.
            from llm_instance_gateway_tpu.parallel import long_context

            self._ring = long_context.make_sharded_prefill(model_cfg, mesh)
            # Pad ring prompts to this multiple: big enough to bound the
            # number of compiled shapes (like prefill buckets), aligned to
            # 8*seq_shards so every device's block is sublane-aligned.
            self._ring_pad = max(8 * mesh.shape["sequence"],
                                 max(self.cfg.prefill_buckets))
        self.slots: list[_Slot | None] = [None] * b
        # Host mirrors of the decode program's per-slot inputs: views into
        # two flat buffers (_SLOT_I32 / _SLOT_F32), written row by row where
        # they always were and uploaded whole by ``_enqueue_decode``.
        self._slots_i32, i32 = _slot_buffer(_SLOT_I32, b, np.int32)
        self._slots_f32, f32 = _slot_buffer(_SLOT_F32, b, np.float32)
        self._slot_positions = i32["positions"]
        self._slot_lora = i32["lora"]
        # Where the rows' adapter slots lie in a staged copy of the buffer
        # (_block_lora_buffers).
        self._lora_span = _slot_spans(_SLOT_I32, b)["lora"][0]
        self._slot_temp = f32["temp"]
        self._slot_topk = i32["topk"]
        self._slot_topp = f32["topp"]
        self._slot_seed = i32["seed"]
        self._slot_presence = f32["presence"]
        self._slot_frequency = f32["frequency"]
        self._slot_bias_ids = i32["bias_ids"]
        self._slot_bias_vals = f32["bias_vals"]
        # Generated-token occurrence counts, device-resident (a [B, V]
        # transfer per dispatch would swamp the step): rows zero at
        # registration, the decode scan updates them in its carry.
        self._dev_counts = None  # lazy: [B, V_padded] int32 on first use
        # What a penalty-free dispatch passes instead (``penalized`` is the
        # program's static flag): donated, and handed back by the program
        # as the next dispatch's, so no step makes one.
        self._counts_dummy = jnp.zeros((b, 1), jnp.int32)
        # Per-row token budget for device-side stop (0 = frozen row).
        self._slot_remaining = i32["remaining"]
        self._eos_for_device = jnp.int32(-1 if eos_id is None else eos_id)
        # Device stop-string automata (server/sampling.py): per-row stop
        # suffix lanes (right-aligned, -1 padded) programmed at slot
        # registration, plus the history a fresh row is staged with (the
        # tokens the host has emitted for it; from then on the history
        # rides the device carry, ``_stage_carry``).
        self._slot_stop_ids = i32["stop_ids"]
        self._slot_stop_lens = i32["stop_lens"]
        self._slot_stop_hist = i32["stop_hist"]
        self._slot_fresh = i32["fresh"]
        self._slot_logprobs = i32["logprobs"]
        # Count of rows with programmed device stop lanes: excludes
        # speculative dispatch (the spec block does not evaluate the
        # automaton, so its history carry would go stale mid-generation).
        self._stops_active = 0

        self.prefill_queue: queue_mod.Queue[Request] = queue_mod.Queue(
            maxsize=self.cfg.max_queue
        )
        # Prefilled-but-unslotted requests (the engine docstring's
        # ``decode_wait``); plus the head-of-line request pulled off the
        # queue but not yet admissible (e.g. a chunked prompt with no lane).
        self.decode_wait: "collections.deque[_WaitingPrefill]" = collections.deque()
        # Padded KV tokens pinned by decode_wait entries (engine-thread
        # mutated; read by the scrape thread — int updates are atomic).
        self._parked_kv_tokens = 0
        self._pending: Request | None = None
        # Long prompts stream chunk-by-chunk into RESERVED cache lanes,
        # interleaved with decode blocks (_stream_step): up to
        # ``stream_lanes`` at once, advanced fair round-robin (one chunk
        # per engine cycle across lanes) so a second 32k prompt no longer
        # head-of-line blocks behind the first.
        self._streams: list[_ChunkStream] = []
        self._stream_rr = 0  # round-robin cursor over self._streams
        self._reserved_slots: set[int] = set()
        self._work = threading.Condition()
        self._running = False
        self._draining = False
        # Flight-recorder seam (llm_instance_gateway_tpu/events.py): the
        # HTTP layer installs EventJournal.emit here so engine lifecycle
        # changes (drain start) land in /debug/events without the engine
        # importing any server machinery.  Signature: (kind, **attrs).
        self.event_sink = None
        # Requests mid-admission (popped from the queue, slot not yet
        # registered): counted into num_requests_waiting so drain() and the
        # routing signal never see a phantom-quiescent engine.
        self._admitting = 0
        # Live requests by id (inserted at submit/attach, removed in
        # _finish) — the handle ``release_request`` cancels through.
        self._live: dict[str, Request] = {}
        self._thread: threading.Thread | None = None

        # Telemetry (exported by server.metrics in the gateway contract).
        self._lock = witness_lock("Engine._lock")
        self.total_generated = 0
        self.total_requests = 0
        self.decode_tps_ema = 0.0
        self.ttft_history: list[float] = []
        # Phase-latency histograms, rendered as tpu:prefill_seconds /
        # tpu:handoff_seconds / tpu:decode_step_seconds (server/metrics.py).
        # Mutated under self._lock; exported copy-out via metrics_snapshot.
        self.phase_hist: dict[str, Histogram] = {
            "prefill": Histogram(LATENCY_BUCKETS),
            "handoff": Histogram(LATENCY_BUCKETS),
            "decode_step": Histogram(LATENCY_BUCKETS),
        }
        # Fused steps per PLAIN decode dispatch — the adaptive planner's
        # decision record, rendered as tpu:dispatch_steps (spec blocks'
        # token-row counts are not planner choices and stay out).
        # Mutated under self._lock like phase_hist.
        self.dispatch_steps_hist = Histogram(STEP_BUCKETS)
        # Capacity attribution (server/usage.py): who is consuming this
        # replica.  Own lock; charged from the engine thread, snapshotted
        # by the scrape thread.
        self.usage = UsageTracker(
            b, kv_block=self._block if self.paged else 1)
        # Step-timeline profiler (server/profiler.py): charged at the
        # same dispatch call sites as the usage tracker, plus idle marks
        # from the engine loop, so the dispatch/host-sync/idle attribution
        # tiles the engine thread's wall.
        self.profiler = StepProfiler(annotate=jax.profiler.TraceAnnotation)
        # Requests with prompt work since the phase parts were last handed
        # out (_settle_admissions): a first token emitted, a chunk staged.
        self._unsettled: list[Request] = []
        # The loop's state.  First tokens still on the device, in the
        # order their prefills were enqueued, as (request, token,
        # logprob triple) (_read_first_tokens); the block in flight, if
        # any, and its write span (the paged reservation, the planner's
        # lag); and when the device was last seen to complete something
        # (a block, a prefill), on the profiler's clock: the step clock's
        # anchor (_process_block).
        self._first_unread: list[tuple] = []
        self._inflight: dict | None = None
        # Prompt programs enqueued and not seen complete yet, in the
        # device queue's order, and how many were ever enqueued (the number
        # a decode block or a queued first token carries: everything up to
        # it is ahead of that on the queue).
        self._prompt_pending: list[_PromptProgram] = []
        self._prompt_enqueued = 0
        # The traces of the decode programs, where the engine holds
        # adapter buffers (_traced, _retarget).  _lora_targets: the LoRA
        # targets whose buffers a block with an adapter row is handed, in
        # TARGETS' order: the manager's resident targets, or an older,
        # wider set while the narrower one's traces are compiled.
        # _decode_variants: each decode program the loop has met under each
        # of its static arguments -> how to lower it for another set of
        # targets.  _decode_traces: (variant, targets or None) -> set once
        # that trace is compiled.  Written under _trace_lock, which is never
        # held over a compile; the loop reads all three without it.
        self._trace_lock = witness_lock("Engine._trace_lock")
        self._lora_targets: tuple[str, ...] = ()
        self._decode_variants: dict[tuple, Callable] = {}
        self._decode_traces: dict[tuple, threading.Event] = {}
        if lora_manager is not None:
            lora_manager.watch_targets(self._on_resident_targets)
            self._lora_targets = _in_order(lora_manager.resident_targets())
        self._prev_dispatch_steps = 0
        self._last_done_pc = 0.0
        # The device carry from one block to the next: each row's last
        # token, position, budget and stop-automaton history never make a
        # host round-trip; rows re-seed at activation (_stage_carry).
        self._dev_tokens = jnp.zeros((b,), jnp.int32)
        self._dev_positions = jnp.zeros((b,), jnp.int32)
        self._dev_remaining = jnp.zeros((b,), jnp.int32)
        self._dev_stop_hist = jnp.full((b, STOP_LEN), -1, jnp.int32)
        # KV economy ledger (server/kv_ledger.py): block lifecycle,
        # per-prefix reuse, fragmentation.  Own lock; charged at the
        # allocator/prefix/park sites, state-recounted on the KV sync,
        # snapshotted by the scrape thread.  Paged pool only — the
        # contiguous-lane cache has no block economy to account.
        self.kv_ledger: KvLedger | None = (
            KvLedger(self._n_blocks, self._block) if self.paged else None)
        # LRU evictions since the last KV sync (journaled as ONE
        # aggregated kv_evict event per sync — eviction storms must not
        # flood the flight recorder's bounded ring).
        self._kv_evicts_pending = 0

        if self.paged:
            step_fn = paged_lib.decode_step_paged
        elif self._decode_attn_fn is not None:
            step_fn = functools.partial(
                transformer.decode_step, attention_fn=self._decode_attn_fn)
        else:
            step_fn = transformer.decode_step
        # Every program is jitted under a stable name (_named): a device
        # trace then says jit_decode_block, jit_prefill, ... and not
        # jit__unknown_<fingerprint>.
        self._jit_prefill = jax.jit(_named(
            "prefill", self._prefill_impl, model_cfg, self._prefill_attn_fn))
        self._jit_prefill_many = jax.jit(_named(
            "prefill_many", self._prefill_many_impl, model_cfg,
            self._prefill_attn_fn))
        self._jit_decode = jax.jit(
            _named("decode_block", self._decode_impl, model_cfg, step_fn),
            donate_argnames=("cache", "counts"),
            static_argnames=("n_steps", "penalized"),
        )
        # Insert donates the cache too: without donation every admission would
        # copy the full multi-GB decode cache.
        self._jit_insert = jax.jit(
            _named("insert_prefill",
                   paged_lib.insert_prefill_paged if self.paged
                   # a window model's prompt parts by kind of layer
                   else functools.partial(transformer.insert_prefill,
                                          cfg=model_cfg) if self._ringed
                   else transformer.insert_prefill),
            donate_argnames=("cache",),
        )
        # Chunked prefill for prompts beyond the largest bucket: one
        # chunk-sized program streams the prompt into the cache lane.
        self._jit_chunk = jax.jit(
            _named(
                "prefill_chunk", self._chunk_impl, model_cfg,
                paged_lib.prefill_with_cache_paged if self.paged
                else transformer.prefill_with_cache,
            ),
            donate_argnames=("cache",),
        )
        # Routing counts of a sparse model's prefill programs, still on the
        # device: the next decode readback brings them back with its own.
        self._moe_pending: list = []

        def sample_one(logits, key, t, k, p, seed, pos, bias_ids,
                       bias_vals):
            tok = sample(
                logits[None], key, jnp.full((1,), t, jnp.float32),
                jnp.full((1,), k, jnp.int32), jnp.full((1,), p, jnp.float32),
                valid_vocab=model_cfg.vocab_size,
                seeds=jnp.full((1,), seed, jnp.int32),
                positions=jnp.full((1,), pos, jnp.int32),
                bias_ids=bias_ids[None], bias_vals=bias_vals[None],
            )
            lp, top_v, top_i = _logprob_info(
                logits[None], tok, model_cfg.vocab_size)
            return tok[0], (lp[0], top_v[0], top_i[0])

        self._jit_sample_one = jax.jit(sample_one)
        self._jit_next_key = jax.jit(_named(
            "next_key", lambda key: tuple(jax.random.split(key))))

        if self._spec:
            if mesh is not None and mesh.size > 1 and (
                draft_cfg.use_flash_attention or draft_cfg.use_pallas_decode
            ):
                # Same invariant as the target: GSPMD can't partition an
                # opaque pallas_call, and the draft's ops run inside the
                # sharded spec block — its in-model auto-dispatch must be
                # off too (XLA attention; the draft is small).
                draft_cfg = dataclasses.replace(
                    draft_cfg, use_flash_attention=False,
                    use_pallas_decode=False)
                self.draft_cfg = draft_cfg
            self.draft_cache = transformer.init_decode_cache(
                draft_cfg, b, self.cfg.max_seq_len, dtype=dtype)
            if mesh is not None:
                # The draft is small: replicate it on the mesh (its whole
                # point is being cheap) — the target keeps its GSPMD
                # shardings and XLA partitions the fused verify normally.
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(mesh, PartitionSpec())
                self.draft_params = jax.device_put(draft_params, rep)
                self.draft_cache = jax.device_put(self.draft_cache, rep)
            self._spec_ok = np.zeros((b,), bool)
            # The (token, position) the draft hasn't ingested yet — only set
            # after a FULLY-accepted cycle (d_K's kv is missing then): spec
            # blocks update the triple in their carry, no host round-trip.
            self._dev_extra_tok = jnp.zeros((b,), jnp.int32)
            self._dev_extra_pos = jnp.zeros((b,), jnp.int32)
            self._dev_has_extra = jnp.zeros((b,), bool)
            self.spec_cycles = 0
            self.spec_emitted = 0

            def draft_prefill(params, tokens, positions):
                _, k, v = transformer.prefill(draft_cfg, params, tokens,
                                              positions)
                return k, v

            self._jit_draft_prefill = jax.jit(draft_prefill)
            self._jit_draft_insert = jax.jit(
                _named("draft_insert", transformer.insert_prefill),
                donate_argnames=("cache",))
            # The speculative dispatch lays the staged rows over the carry
            # ahead of the call (a plain block does it inside its program).
            self._jit_stage_carry = jax.jit(_named(
                "stage_carry", lambda carry, buf: _stage_carry(
                    carry, _slot_views(buf, _SLOT_I32, b))))
            self._jit_spec_block = jax.jit(
                _named("spec_block", self._spec_block_impl, model_cfg,
                       draft_cfg),
                donate_argnames=("cache", "draft_cache"),
                static_argnames=("n_cycles", "k_steps"))

    # ------------------------------------------------------------------
    # jitted compute
    # ------------------------------------------------------------------

    @staticmethod
    def _prefill_impl(
        model_cfg, attn_fn, params, lora_bufs, tokens, positions, true_len,
        lora_slot, temp, topk, topp, key, seed, bias_ids, bias_vals,
    ):
        """Prefill one padded prompt; sample the first new token."""
        slot_ids = jnp.full((1,), lora_slot, jnp.int32)
        logits, k, v, *moe = transformer.prefill(
            model_cfg, params, tokens, positions, lora_bufs=lora_bufs,
            slot_ids=slot_ids, attention_fn=attn_fn,
            lengths=true_len, moe_tally=bool(model_cfg.n_experts),
        )
        last = logits[:, true_len - 1]  # [1, V]
        first_token = sample(
            last, key,
            temperature=jnp.full((1,), temp, jnp.float32),
            top_k=jnp.full((1,), topk, jnp.int32),
            top_p=jnp.full((1,), topp, jnp.float32),
            valid_vocab=model_cfg.vocab_size,
            seeds=jnp.full((1,), seed, jnp.int32),
            positions=jnp.full((1,), true_len - 1, jnp.int32),
            bias_ids=bias_ids[None], bias_vals=bias_vals[None],
        )
        lp, top_v, top_i = _logprob_info(last, first_token, model_cfg.vocab_size)
        return (first_token[0], k, v, (lp[0], top_v[0], top_i[0]),
                moe[0] if moe else None)

    @staticmethod
    def _prefill_many_impl(
        model_cfg, attn_fn, params, lora_bufs, tokens, positions, true_lens,
        lora_slots, temps, topks, topps, key, seeds, bias_ids, bias_vals,
    ):
        """Prefill P padded same-bucket prompts as one program; sample each
        row's first token (the [P, bucket] generalization of
        ``_prefill_impl`` — per-row lengths, adapters, sampling params)."""
        logits, k, v, *moe = transformer.prefill(
            model_cfg, params, tokens, positions, lora_bufs=lora_bufs,
            slot_ids=lora_slots, attention_fn=attn_fn,
            lengths=true_lens, moe_tally=bool(model_cfg.n_experts),
        )
        last = jnp.take_along_axis(
            logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]  # [P, V]
        first_tokens = sample(
            last, key, temps, topks, topps, valid_vocab=model_cfg.vocab_size,
            seeds=seeds, positions=true_lens - 1,
            bias_ids=bias_ids, bias_vals=bias_vals)
        lp, top_v, top_i = _logprob_info(last, first_tokens, model_cfg.vocab_size)
        return first_tokens, k, v, (lp, top_v, top_i), moe[0] if moe else None

    @staticmethod
    def _chunk_impl(model_cfg, chunk_fn, params, cache, *args, **kw):
        """The chunk program; a sparse model's routing counts leave it
        beside the cache (``transformer._tallied``)."""
        cache = transformer.with_moe_tally(model_cfg, cache)
        last_logits, cache = chunk_fn(model_cfg, params, cache, *args, **kw)
        return last_logits, cache, cache.pop("moe", None)

    @staticmethod
    def _decode_impl(
        model_cfg, step_fn, params, lora_bufs, cache, slots_i32, slots_f32,
        carry, key, eos_id, counts,
        n_steps: int, penalized: bool = False,
    ):
        """``n_steps`` fused decode+sample steps with DEVICE-SIDE stop.

        The per-slot inputs arrive as the engine's two flat buffers
        (``_SLOT_I32`` / ``_SLOT_F32``) and are taken apart here by static
        slices.  ``carry`` is the previous block's ``(tokens, positions,
        remaining, stop_hist)`` outputs (the host record lags the device;
        ``_stage_carry`` lays what the host freed or admitted since over
        them).  ``key`` is the ENGINE's key: the program splits it as
        ``Engine._next_key`` does and returns the engine's next one, so the
        decode step and the prefills still draw from one stream.

        Each row carries an activity state: ``remaining`` token budget and an
        implicit frozen flag (remaining <= 0 or EOS emitted).  Frozen rows
        stop advancing their position (their cache cell is overwritten in
        place — harmless, the lane is re-inserted on reuse) and emit
        ``valid=False`` steps, so a row that stops mid-block wastes no host
        tokens and large K blocks stay cheap at sequence tails.

        Stop-STRING automata extend the same freeze mechanism: each row
        carries its last ``STOP_LEN`` emitted tokens (``stop_hist``; the
        prefill's first token included, -1 = not yet generated) and a table
        of right-aligned stop suffixes (``stop_ids``/``stop_lens``,
        server/sampling.py).  A step whose sampled token completes a suffix
        emits it as a valid token and zeroes the budget — the row freezes
        mid-block with zero host round-trips, and the host result walk
        merely confirms the match once per dispatch.

        Returns (toks [K,B], valid [K,B], logprob triplet, paths [K],
        next carry, next key, counts, cache, moe): ``paths`` the sampler's
        path of each step (an index into ``metrics_registry.SAMPLE_PATHS``,
        chosen on the device by the live rows' parameters), ``counts`` the
        donated input handed back (updated when ``penalized``), ``moe`` the
        block's routing counts of a sparse model
        (``transformer.MOE_TALLY``; they ride the cache through the steps),
        None for a dense one.
        Positions are clamped below max_seq_len so capped slots never write
        out of bounds.
        """
        if "tables" in cache:  # paged: logical length = table span * block
            max_len = cache["tables"].shape[1] * cache["k"].shape[2]
        else:
            max_len = cache["k"].shape[2]

        c0 = counts.shape[0]
        i32 = _slot_views(slots_i32, _SLOT_I32, c0)
        f32 = _slot_views(slots_f32, _SLOT_F32, c0)
        slot_ids, topk, seeds = i32["lora"], i32["topk"], i32["seed"]
        bias_ids, stop_ids = i32["bias_ids"], i32["stop_ids"]
        stop_lens, want_lp = i32["stop_lens"], i32["logprobs"] > 0
        temp, topp, bias_vals = f32["temp"], f32["topp"], f32["bias_vals"]
        presence, frequency = f32["presence"], f32["frequency"]
        tokens, positions, remaining, stop_hist = _stage_carry(carry, i32)
        next_key, key = jax.random.split(key)
        cache = transformer.with_moe_tally(model_cfg, cache)

        def one_step(carry, step_key):
            cache, tokens, positions, remaining, hist, counts = carry
            active = remaining > 0
            safe_pos = jnp.minimum(positions, max_len - 1)
            # active gates the KV WRITE too: frozen/empty rows scatter
            # nothing (trash block / OOB-dropped) — their lane may already
            # belong to a mid-stream chunk prompt on a reserved slot.
            logits, cache = step_fn(
                model_cfg, params, cache, tokens, safe_pos,
                lora_bufs=lora_bufs, slot_ids=slot_ids, active=active,
            )
            if penalized:
                # OpenAI penalties over generated tokens: subtract BEFORE
                # both the greedy argmax and the draw.  ``penalized`` is a
                # STATIC flag — penalty-free dispatches compile without the
                # [B, V] pass (and take a [B, 1] dummy counts arg).
                logits = logits - (presence[:, None] * (counts > 0)
                                   + frequency[:, None] * counts)
            # live=active: a freed slot still carries its last request's
            # sampling parameters, and must not choose the batch's path.
            sampled, path = sample_routed(
                logits, step_key, temp, topk, topp,
                valid_vocab=model_cfg.vocab_size,
                seeds=seeds, positions=safe_pos,
                bias_ids=bias_ids, bias_vals=bias_vals, live=active)
            lp, top_v, top_i = _logprobs_if_asked(
                jnp.any(want_lp & active), logits, sampled,
                model_cfg.vocab_size)
            valid = active
            # EOS emitted now is a valid token but deactivates the row.
            hit_eos = valid & (sampled == eos_id)
            # Stop-string automaton: the emitted token enters the history
            # ring; a completed suffix deactivates the row exactly like
            # EOS (the stop's tail tokens are emitted, later steps are
            # invalid).  Frozen rows keep their history untouched.
            with jax.named_scope("stops"):
                hist = stop_hist_update(hist, sampled, valid)
                hit_stop = valid & stop_suffix_hit(hist, stop_ids, stop_lens)
            remaining = jnp.where(valid, remaining - 1, remaining)
            remaining = jnp.where(hit_eos | hit_stop, 0, remaining)
            next_tokens = jnp.where(active, sampled, tokens)
            next_positions = positions + active.astype(positions.dtype)
            if penalized:
                counts = counts.at[jnp.arange(c0), sampled].add(
                    valid.astype(jnp.int32))
            return (cache, next_tokens, next_positions, remaining, hist,
                    counts), (sampled, valid, lp, top_v, top_i, path)

        keys = jax.random.split(key, n_steps)
        carry, (toks, valid, lps, top_v, top_i, paths) = (
            jax.lax.scan(one_step,
                         (cache, tokens, positions, remaining, stop_hist,
                          counts), keys)
        )
        cache, *carry, counts = carry
        # The token/position/budget/history carries live on device for
        # the dispatch of the following block (no host round-trip).
        moe = cache.pop("moe", None)
        return (toks, valid, lps, top_v, top_i, paths, tuple(carry),
                next_key, counts, cache, moe)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def start(self) -> None:
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        with self._work:
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # The loop thread is stuck (a device call that has not
                # returned, e.g. a long compile).  It still owns
                # _pending/decode_wait/slots — sweeping them here would
                # race a live mutator and risk double-finish.  Leave the
                # state to that thread; handlers hit their own timeouts.
                logger.error("engine loop thread still alive after 10s join;"
                             " skipping straggler sweep (device call in flight?)")
                return
        # Anything still queued/parked/active when the loop exits would
        # leave its done Event unset forever (handlers block until their
        # own timeout).  Fail stragglers explicitly — after a drain this
        # set is empty; after an abrupt stop it is the honest outcome.
        stragglers: list[Request] = []
        if self._pending is not None:
            stragglers.append(self._pending)
            self._pending = None
        while True:
            try:
                stragglers.append(self.prefill_queue.get_nowait())
            except queue_mod.Empty:
                break
        while self.decode_wait:
            w = self.decode_wait.popleft()
            self._parked_kv_tokens -= w.k.shape[2]
            stragglers.append(w.request)
        while self._streams:
            stragglers.append(self._streams.pop().request)
        stragglers += [s.request for s in self.slots if s is not None]
        for req in stragglers:
            if not req.done.is_set():
                req.error = req.error or "engine stopped"
                self._finish(req, "error")

    def _plan_steps(self) -> int:
        """Fused decode steps for the next dispatch (the adaptive planner).

        Static mode (``adaptive_steps`` <= 0): the decode_steps_per_sync
        CLI value, unchanged.  Adaptive mode picks from the live batch:

        - **admission pressure** — queued prompts, a parked insert, or an
          in-flight chunk stream all run BETWEEN dispatches, so a fused
          block would stall their TTFT/chunk cadence: those dispatches
          drop to 1 step;
        - **remaining budget** — never fuse past the minimum remaining
          token budget across active rows (the block would spend its tail
          decoding frozen rows), less the in-flight block's steps, since
          the host record lags it;
        - **SSE cadence** — any active streaming consumer caps fusion at
          ``adaptive_stream_cap`` so perceived TPOT cannot regress.

        The choice quantizes DOWN to a power of two: ``n_steps`` is a
        static jit argument, so this bounds the compiled-variant set to
        log2(ceiling) programs per (penalized, adapter buffers or none)
        combination.
        """
        ceiling = self.cfg.adaptive_steps
        if ceiling <= 0:
            return max(1, self.cfg.decode_steps_per_sync)
        # Parked decode_wait entries only stall on a FREE slot (their
        # prefill already ran); a saturated pool with parked work keeps
        # fusing — the min-remaining bound below still lands the block
        # edge on the next budget-driven slot free.
        if (self._streams or self._pending is not None
                or not self.prefill_queue.empty()
                or (self.decode_wait
                    and self._free_slot_index() is not None)):
            return 1
        n = max(1, ceiling)
        for s in self.slots:
            if s is None:
                continue
            req = s.request
            if req.streaming:
                n = min(n, max(1, self.cfg.adaptive_stream_cap))
            # A slotted row has its first token, read or not (the loop
            # reads it after this dispatch is planned).
            rem = (req.max_new_tokens - max(1, len(req.output_tokens))
                   - self._prev_dispatch_steps)
            n = min(n, max(1, rem))
        p = 1
        while p * 2 <= n:
            p *= 2
        return p

    def _enqueue_decode(self, n_steps: int, carry):
        """Stage and enqueue one plain decode block: the slot mirrors go up
        as their two buffers and nothing else is made for the call — the
        engine's key, the penalty-free counts dummy and the cache are each
        the previous block's output.  ``carry`` as in
        ``_decode_impl``.  Returns the block's (toks, valid, lps, top_v,
        top_i, paths), its next carry, and its routing counts with those
        the prefills left (``_moe_drain``).

        The buffers go as private host copies, never the mirrors
        themselves: on the CPU backend an upload can alias its numpy
        source (``_sync_tables``), and the loop rewrites rows while the
        block is in flight.  ``counts`` is the real buffer only
        when some row carries a penalty (static flag -> two compiled
        variants), so penalty-free serving never allocates or streams
        [B, V] counts.  The adapter buffers likewise go only when some row
        of the staged copy names an adapter, and then only those of the
        targets a resident adapter carries (``_block_lora_buffers``:
        ``None`` or a dict of fewer targets is another pytree -> another
        trace of each variant, ``None`` the program of a server without
        adapters), so a block of base rows reads no adapter matrix and a
        block with an adapter row none that holds only zeros."""
        penalized = bool(self._slot_presence.any()
                         or self._slot_frequency.any())
        counts = self._counts() if penalized else self._counts_dummy
        self.profiler.note_stage_ops(STAGE_UPLOADS)
        if self._recurrent or self._conv or self._kda:
            # Every step of the block rewrites the state of every row the
            # host holds (a row that stops mid-block counts on to its end).
            (self.profiler.note_conv_rows if self._conv
             else self.profiler.note_kda_rows if self._kda
             else self.profiler.note_ssm_rows)(
                n_steps * sum(s is not None for s in self.slots))
        # Step j of the block reads position + 1 + j rows of a live row's
        # lane.  With a block still unread the host record is that block's
        # steps behind the device, for every row but
        # one activated since; a row that stops mid-block counts on to the
        # block's end.
        lag = self._inflight["n_steps"] if self._inflight else 0
        at = [s.position + (0 if self._slot_fresh[i] else lag)
              for i, s in enumerate(self.slots) if s is not None]
        if self._attn_tiles[1]:
            # ... in the tiles of the kernel's schedule, a layer's call.
            self.profiler.note_attn_grid_steps(sum(
                pda.schedule_steps([p + j for p in at], *self._attn_tiles)
                for j in range(1, n_steps + 1)))
        if self._latent or self._window or self._conv:
            held = sum(at) * n_steps + len(at) * n_steps * (n_steps + 1) // 2
            if self._latent:
                self.profiler.note_latent_positions(held)
            else:
                # ... and a window layer's ring at most the window of them.
                self.profiler.note_kv_positions(held, sum(
                    min(p + j, self._window)
                    for p in at for j in range(1, n_steps + 1)))
        i32, f32 = self._slots_i32.copy(), self._slots_f32.copy()
        # The copy carries the activations since the last block; the block
        # after this one takes those rows from the carry like any other.
        self._slot_fresh[:] = 0
        lora_bufs, targets, adapter_rows = self._block_lora_buffers(i32)
        self.profiler.note_lora_rows(n_steps * adapter_rows)
        if targets is not None:
            self.profiler.note_lora_target_reads(n_steps * len(targets))
        elif self.lora is not None:
            self.profiler.note_lora_free_steps(n_steps)
        if self._slot_logprobs.any():
            self.profiler.note_logprob_steps(n_steps)
        with self._enqueue("engine.decode.enqueue"):
            (*outs, carry, self._rng, counts, self.cache, moe) = (
                self._traced(
                    self._jit_decode, 1, targets,
                    self.params, lora_bufs, self.cache,
                    i32, f32, carry,
                    self._rng, self._eos_for_device, counts,
                    n_steps=n_steps, penalized=penalized))
        if penalized:
            self._dev_counts = counts
        else:
            self._counts_dummy = counts
        return outs, carry, self._moe_drain(moe)

    def _traced(self, fn, at: int, targets, *args, **statics):
        """``fn(*args, **statics)``, a jitted decode program handed what
        ``_block_lora_buffers`` decided: ``targets``, and as ``args[at]``
        the buffers themselves.  An engine that holds adapter buffers runs
        each variant of such a program (its static arguments) on several
        traces: without the delta, and with the delta of each set of
        targets it has handed.  The loop itself compiles only the first
        trace it meets of a variant, as an engine without adapters does;
        every other one is lowered and compiled off the loop from that
        first call's shapes (``lower().compile()`` fills the caches the
        jitted call then finds), before a block can need it:

        - the variant's other trace (without the delta, or with the
          targets now handed) by a helper thread as soon as the first is
          met, so that an adapter row joining base rows, or the last one
          leaving, does not hold every row up for a compile;
        - a wider set's by the thread of the load that widens it, before
          a request can name the adapter; a narrower set's by a helper
          thread after an unload, the wider set in use meanwhile
          (``_on_resident_targets``).

        Which trace a block runs never depends on any of this."""
        if self.lora is None:
            return fn(*args, **statics)
        variant = tuple(sorted(statics.items()))
        met = self._decode_traces.get((variant, targets))
        if met is not None:
            # Compiled ahead; or being compiled: that is waited for, not
            # made a second time beside it.
            met.wait()
            return fn(*args, **statics)
        lower = None
        if variant not in self._decode_variants:
            # Now, while the donated arguments still are: shapes and
            # shardings, with the buffers' place left open.
            shapes = jax.tree_util.tree_map(_abstract, args)

            def lower(bufs):
                fn.lower(*shapes[:at], bufs, *shapes[at + 1:],
                         **statics).compile()
        out = fn(*args, **statics)
        with self._trace_lock:
            if lower is not None:
                self._decode_variants[variant] = lower
            self._decode_traces.setdefault(
                (variant, targets), threading.Event()).set()
        other = self._lora_targets if targets is None else None
        if other != ():
            threading.Thread(target=self._retarget, args=(other,),
                             daemon=True, name="decode-trace-prepare").start()
        return out

    def _retarget(self, targets: tuple[str, ...] | None,
                  adopt: bool = False) -> None:
        """Compile, on the calling thread (never the loop's), the trace
        with ``targets`` (``None``: without the delta) of every decode
        variant the loop has met, and wait for those another thread is
        compiling; with ``adopt``, then make ``targets`` what a block with
        an adapter row is handed, if they still are the manager's resident
        targets (a later change of those has made its own call)."""
        bufs = None if targets is None else jax.tree_util.tree_map(
            _abstract, lora_lib.select_targets(self.lora.buffers, targets))
        while True:
            mine, theirs = [], []
            with self._trace_lock:
                # A program handed no target's buffers is none to compile.
                for variant, lower in (self._decode_variants.items()
                                       if targets != () else ()):
                    done = self._decode_traces.get((variant, targets))
                    if done is None:
                        done = threading.Event()
                        self._decode_traces[(variant, targets)] = done
                        mine.append((lower, done))
                    elif not done.is_set():
                        theirs.append(done)
                if not mine and not theirs:
                    if adopt and (self.lora.resident_targets()
                                  == frozenset(targets)):
                        self._lora_targets = targets
                    return
            for lower, done in mine:
                try:
                    lower(bufs)
                except Exception:  # the call itself compiles when it comes
                    logger.exception(
                        "preparing a decode program with the adapter "
                        "targets %s failed", targets)
                finally:
                    done.set()
            for done in theirs:
                done.wait()

    def _on_resident_targets(self, resident: frozenset[str]) -> None:
        """``LoRAManager``'s hook (``watch_targets``): the targets the
        resident adapters carry are now ``resident``.  What a block with an
        adapter row is handed always holds them all.  A load that brings a
        target not handed yet waits here, on its own thread and before any
        request can name its adapter, for the wider set's traces; an unload
        that takes one away returns at once and a helper thread adopts the
        narrower set when its traces are compiled."""
        targets = _in_order(resident)
        with self._trace_lock:  # not between a helper's check and its word
            handed = self._lora_targets
        if resident <= set(handed):
            if targets != handed:
                threading.Thread(
                    target=self._retarget, args=(targets, True), daemon=True,
                    name="decode-trace-prepare").start()
            return
        self._retarget(targets, adopt=True)

    def _count_first_token(self, slot_idx: int, tok) -> None:
        """Penalty rows count their prefill-sampled first token too (vLLM
        generated-token semantics: penalties apply from the first decode
        step).  ``tok`` may be a host int or a device scalar."""
        if self._slot_presence[slot_idx] or self._slot_frequency[slot_idx]:
            self._dev_counts = self._counts().at[slot_idx, tok].add(1)

    def _counts(self):
        """[B, V_padded] generated-token counts, created on first need
        (penalty-free serving never pays the HBM)."""
        if self._dev_counts is None:
            if self.model_cfg.tie_embeddings:
                v = self.params["embed"].shape[0]
            else:
                head = self.params["lm_head"]
                if isinstance(head, dict):  # weight-only int8 leaf
                    head = head["q"]
                v = head.shape[-1]
            self._dev_counts = jnp.zeros(
                (self.cfg.decode_slots, int(v)), jnp.int32)
        return self._dev_counts

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful-termination half of the pod lifecycle: stop ADMITTING
        (submit raises; the /health flip pulls the replica out of the
        EPP's routable set) while the loop keeps decoding until every
        queued/parked/running request reaches a terminal state.  Returns
        True when fully drained, False on timeout — either way the caller
        then calls ``stop()`` (stragglers fail as the loop exits; k8s
        would be at the end of terminationGracePeriod anyway)."""
        self._draining = True
        if self.event_sink is not None:
            try:
                # "role_change" in the flight recorder's shared kind
                # namespace: the replica is leaving the routable set.
                self.event_sink("role_change", role=self.cfg.role,
                                draining=True)
            except Exception:
                logger.exception("event sink failed on drain")
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            snap = self.metrics_snapshot()
            if (snap["num_requests_running"] == 0
                    and snap["num_requests_waiting"] == 0):
                return True
            time.sleep(0.02)
        return False

    @property
    def draining(self) -> bool:
        return self._draining

    def _validate_sampling(self, request: Request) -> None:
        """Sampling-parameter gates shared by submit() and
        attach_prefilled() — a handoff's sampling carry crosses a trust
        boundary and must clear the same bars as a direct submission."""
        sp = request.sampling
        if self._spec and (sp.presence_penalty or sp.frequency_penalty
                           or sp.logit_bias):
            raise ValueError(
                "presence/frequency penalties and logit_bias are not "
                "supported on a speculative engine (the verify block's "
                "greedy pick bypasses the sampling seam); disable "
                "speculative_k or the parameter")
        if sp.logit_bias:
            if len(sp.logit_bias) > MAX_LOGIT_BIAS:
                raise ValueError(
                    f"logit_bias supports at most {MAX_LOGIT_BIAS} entries")
            for tid in sp.logit_bias:
                if not 0 <= tid < self.model_cfg.vocab_size:
                    # Out-of-vocab ids would clip onto token V-1 in the
                    # device scatter and mis-bias a real token.
                    raise ValueError(
                        f"logit_bias token id {tid} is outside the "
                        f"vocabulary [0, {self.model_cfg.vocab_size})")
        for seq in request.stop_sequences:
            if not seq:
                raise ValueError("stop_sequences entries must be non-empty")
            for tid in seq:
                if not 0 <= int(tid) < self.model_cfg.vocab_size:
                    # A negative id would alias the device automaton's -1
                    # padding lane and make a short history false-match.
                    raise ValueError(
                        f"stop sequence token id {tid} is outside the "
                        f"vocabulary [0, {self.model_cfg.vocab_size})")

    def submit(self, request: Request) -> Request:
        """Enqueue; raises queue.Full when saturated (gateway sees the depth)."""
        if self._draining:
            raise EngineDraining("engine is draining (graceful termination)")
        self._validate_sampling(request)
        if len(request.prompt_tokens) >= self.cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(request.prompt_tokens)} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}"
            )
        if self.paged and self._paged_needed(
                len(request.prompt_tokens) + 1) > self._n_blocks:
            # Larger than the ENTIRE pool: admission could never succeed and
            # the request would head-of-line block the engine forever.
            raise ValueError(
                f"prompt needs {self._paged_needed(len(request.prompt_tokens) + 1)} "
                f"KV blocks but the pool has {self._n_blocks}"
            )
        # Prompts beyond the largest bucket stream through chunked prefill;
        # within a bucket, validate the bucket fit here rather than mid-batch.
        if self._max_bucket() <= 0:
            raise ValueError(
                f"no usable prefill bucket <= max_seq_len "
                f"{self.cfg.max_seq_len}: {self.cfg.prefill_buckets}"
            )
        if len(request.prompt_tokens) <= self._max_bucket():
            self._bucket(len(request.prompt_tokens))
        request.t_submit = time.time()
        if request.adapter is not None and self.lora is not None:
            # Resolve eagerly so unknown adapters fail fast (404, not
            # mid-batch) — and PIN the slot so unload/reload can't swap the
            # buffers out from under this request mid-generation.  Released
            # in _finish (or right here if admission is refused).
            self.lora.acquire(request.adapter)
        try:
            self.prefill_queue.put_nowait(request)
        except queue_mod.Full:
            if request.adapter is not None and self.lora is not None:
                self.lora.release(request.adapter)
            raise
        with self._lock:
            self.total_requests += 1
            self._live[request.request_id] = request
        with self._work:
            self._work.notify()
        return request

    def generate(self, request: Request, timeout_s: float = 600.0) -> Request:
        """Submit and block until completion (HTTP layer calls this)."""
        self.submit(request)
        if not request.done.wait(timeout_s):
            request.error = "generation timed out"
            request.cancelled.set()  # release the slot; nobody is waiting
        return request

    # ------------------------------------------------------------------
    # cross-engine prefill/decode disaggregation (server/kv_transfer.py)
    # ------------------------------------------------------------------

    def prefill_only(self, request: Request, timeout_s: float = 600.0,
                     quantize: str | None = None):
        """Run ONLY the prefill and return a ``PrefillHandoff`` (hop 1 of
        disaggregated serving).  No decode slot, no cache lane, no pool
        block is touched — a prefill-role replica serves these regardless
        of decode occupancy.

        The wire lane defaults to int8 on an int8-KV engine (the decode
        side re-quantizes to the identical values — see kv_transfer's
        parity note) and the raw compute dtype otherwise.  Prompts beyond
        the largest bucket are refused: the chunk-stream and ring paths
        write into THIS engine's cache, which is exactly what a handoff
        exists to avoid.
        """
        self._refuse_handoff()
        n = len(request.prompt_tokens)
        if self._max_bucket() <= 0 or n > self._max_bucket():
            raise ValueError(
                f"prefill_only supports prompts within the largest bucket "
                f"({self._max_bucket()}); got {n} tokens")
        request._handoff_only = True
        request._handoff_quantize = (
            quantize if quantize is not None
            else ("int8" if self._kv_quant else None))
        self.submit(request)
        if not request.done.wait(timeout_s):
            request.error = "prefill timed out"
            request.cancelled.set()
        if request.error:
            raise RuntimeError(request.error)
        return request.handoff

    def _refuse_handoff(self) -> None:
        """Every engine keeps the handoff API in every role; one whose rows
        hold more than per-head K and V by position cannot ship them."""
        if self._latent or self._recurrent or self._ringed:
            raise ValueError(
                f"{self.model_cfg.name}: the handoff wire "
                "(server/kv_transfer.py) ships one stack of per-head K and "
                "V only, not a latent cache, a recurrent state, a conv "
                "state or ring lanes")

    def attach_prefilled(self, handoff) -> Request:
        """Admit a ``PrefillHandoff`` straight into decode (hop 2): the KV
        imports into this engine's cache and the request decodes from its
        carried first token — prefill is skipped entirely.  Returns the
        live ``Request`` (already submitted); callers wait on ``done`` /
        ``stream_event`` exactly like after ``submit``.
        """
        from llm_instance_gateway_tpu.server import kv_transfer

        self._refuse_handoff()
        request = kv_transfer.make_request(handoff)
        if self._draining:
            raise EngineDraining("engine is draining (graceful termination)")
        if handoff.n != len(request.prompt_tokens) or handoff.n <= 0:
            raise ValueError("handoff length/prompt mismatch")
        self._validate_sampling(request)
        expect = (self.model_cfg.n_layers, handoff.n,
                  self.model_cfg.n_kv_heads,
                  self.model_cfg.resolved_head_dim)
        if (tuple(handoff.k.shape) != expect
                or tuple(handoff.v.shape) != expect):
            # Fail at admission (caller-visible), not as a 500 inside the
            # engine loop: the handoff was produced for a DIFFERENT model.
            raise ValueError(
                f"handoff KV shape {tuple(handoff.k.shape)} does not match "
                f"this engine's cache layout {expect}")
        if len(request.prompt_tokens) >= self.cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(request.prompt_tokens)} exceeds "
                f"max_seq_len {self.cfg.max_seq_len}")
        if self.paged and self._paged_needed(
                len(request.prompt_tokens) + 1) > self._n_blocks:
            raise ValueError(
                f"prompt needs "
                f"{self._paged_needed(len(request.prompt_tokens) + 1)} KV "
                f"blocks but the pool has {self._n_blocks}")
        request.t_submit = time.time()
        if request.adapter is not None and self.lora is not None:
            # Same pin discipline as submit(): unknown adapters fail fast,
            # resident ones can't swap out mid-generation.
            self.lora.acquire(request.adapter)
        request._attach_handoff = handoff
        try:
            self.prefill_queue.put_nowait(request)
        except queue_mod.Full:
            if request.adapter is not None and self.lora is not None:
                self.lora.release(request.adapter)
            raise
        with self._lock:
            self.total_requests += 1
            self._live[request.request_id] = request
        with self._work:
            self._work.notify()
        return request

    def release_request(self, request_id: str) -> bool:
        """Best-effort cancel of a live request by id (the gateway's
        abandon path: a decode hop whose response was lost after the
        handoff was posted — ``POST /v1/prefill/release``).

        Marks the request cancelled and wakes the loop; the existing
        cancel seams finish it wherever it sits (queued, parked in
        ``decode_wait`` — freeing the parked KV accounting — or active in
        a slot, where the decode block sweep clears it).  Returns whether
        a live request with that id existed.  Idempotent; unknown ids are
        a no-op (the request may have finished, or never arrived).
        """
        with self._lock:
            req = self._live.get(request_id)
        if req is None or req.done.is_set():
            return False
        req.cancelled.set()
        if self.event_sink is not None:
            self.event_sink("kv_release", request_id=request_id)
        with self._work:
            self._work.notify()
        return True

    # ------------------------------------------------------------------
    # metrics snapshot (the scrape contract, gateway/metrics_client.py)
    # ------------------------------------------------------------------

    def _adapter_activity(self) -> tuple[list[str], list[str]]:
        """(running, waiting) LoRA adapter name lists for the
        ``tpu:lora_requests_info`` gauge — vLLM reference semantics:
        *running* = adapters with a slotted (actively decoding) request or
        the in-flight chunk stream; *waiting* = adapters whose requests are
        prefilled-but-parked in ``decode_wait`` (or pulled-but-unadmitted).
        A request parked without a slot is NOT running — counting it as
        such made the gateway's affinity scorer steer traffic toward the
        replica least able to take it.  Scrape-thread safe: a deque walk
        raced by the engine thread degrades to an empty waiting set for
        one scrape rather than corrupting anything."""
        running: set[str] = set()
        waiting: set[str] = set()
        for s in self.slots:
            if s is not None and s.request.adapter:
                running.add(s.request.adapter)
        for stream in list(self._streams):
            if stream.request.adapter:
                running.add(stream.request.adapter)
        try:
            for w in list(self.decode_wait):
                if w.request.adapter:
                    waiting.add(w.request.adapter)
        except RuntimeError:  # deque mutated during the scrape-side walk
            pass
        pending = self._pending
        if pending is not None and pending.adapter:
            waiting.add(pending.adapter)
        return sorted(running), sorted(waiting)

    def _kv_usage(self, held, parked: int, used_tokens: int,
                  capacity: int) -> float:
        """``tpu:kv_cache_usage_perc``, the number the gateway's threshold
        routes on: held bytes over allocated bytes.  For a cache of one
        kind that is tokens over token capacity (``held`` is not looked
        at).  A window model's row of ``p`` positions holds ``p`` in each
        full layer and min(p, ring) in each window layer, of the
        ``max_seq_len`` and ``ring`` allocated: position-layers over
        position-layers, every one the same bytes."""
        if not self._window:
            return used_tokens / capacity if capacity else 0.0
        n_win = self.model_cfg.n_window_layers
        n_full = self.model_cfg.n_layers - n_win
        used = sum(n_full * p + n_win * min(p, self._window) for p in held)
        used += (n_full + n_win) * parked
        return used / (self.cfg.decode_slots * (
            n_full * self.cfg.max_seq_len + n_win * self._window))

    def metrics_snapshot(self) -> dict:
        active = sum(1 for s in self.slots if s is not None)
        held = ()  # the lanes' rows by their lengths; a paged pool has none
        if self.paged:
            # vLLM gpu_cache_usage_perc semantics: allocated / total blocks.
            # Zero-ref cached prefix blocks are reclaimable on demand, so
            # they count as free for routing pressure.
            capacity = self._n_blocks * self._block
            used_tokens = (self._n_blocks - len(self._free_blocks)
                           - len(self._evictable)) * self._block
        else:
            held = [s.position for s in self.slots if s is not None] + [
                st.next_start for st in list(self._streams)]
            used_tokens = sum(held)
            capacity = self.cfg.decode_slots * self.cfg.max_seq_len
        # decode_wait KV is real allocated HBM held OUTSIDE the cache/pool;
        # vLLM's counter (the semantics the 0.8 threshold was tuned against,
        # backend/vllm/metrics.go:30) reflects ALL allocated blocks, so fold
        # the parked rows into usage/headroom.  The percent can transiently
        # exceed 1.0 when every slot is full AND prefill-ahead is parked —
        # that is honest extra pressure, and the scheduler's `<= threshold`
        # comparisons only get more conservative.
        parked = self._parked_kv_tokens
        used_tokens += parked
        with self._lock:
            tps = self.decode_tps_ema
            phase_hist = {k: h.state() for k, h in self.phase_hist.items()}
            steps_hist = self.dispatch_steps_hist.state()
        running_adapters, waiting_adapters = self._adapter_activity()
        max_lora = self.lora.max_slots if self.lora else 0
        # In-flight chunk streams count as prefilling: invisible, the
        # gateway would route MORE traffic to the replica busiest streaming.
        prefill_depth = self.prefill_queue.qsize() + (
            1 if self._pending is not None else 0) + (
            len(self._streams)) + self._admitting
        decode_depth = len(self.decode_wait)
        return {
            "pool_role": self.cfg.role,
            "prefill_queue_size": prefill_depth,
            "decode_queue_size": decode_depth,  # prefilled, awaiting a slot
            "num_requests_running": active,
            "num_requests_waiting": prefill_depth + decode_depth,
            "kv_cache_usage_perc": self._kv_usage(
                held, parked, used_tokens, capacity),
            "kv_tokens_capacity": capacity,
            "kv_tokens_free": max(0, capacity - used_tokens),
            "kv_parked_tokens": parked,
            "decode_tokens_per_sec": tps,
            "running_lora_adapters": running_adapters,
            "waiting_lora_adapters": waiting_adapters,
            "max_lora": max_lora,
            # Resident adapter -> LoRA rank: the heterogeneity signal the
            # gateway's rank-aware fair-share weighting (fairness plane)
            # consumes — a rank-64 flood must not starve rank-8 tenants.
            "adapter_ranks": (self.lora.adapter_ranks() if self.lora
                              else {}),
            # Residency ladder (placement plane): tier -> adapter names,
            # slot<->host<->disk transition counters, per-tier load
            # latency — rendered as tpu:adapter_residency_info /
            # tpu:adapter_tier_transitions_total / tpu:adapter_load_*
            # plus the resident_tiers label on tpu:lora_requests_info.
            **(self._residency_keys() if self.lora else {}),
            # Fused steps per dispatch (adaptive planner decision record)
            # + chunk-stream lane occupancy — the decode fast-path
            # observables (tpu:dispatch_steps / tpu:stream_lanes*).
            "dispatch_steps_hist": steps_hist,
            "stream_lanes": max(1, self.cfg.stream_lanes),
            "stream_lanes_active": len(self._streams),
            # Phase-latency histogram states (server/metrics.py renders
            # these as the tpu:*_seconds histogram families).
            "phase_hist": phase_hist,
            # Per-adapter capacity attribution (server/usage.py) — the
            # tpu:adapter_*_total / pool-waste families.
            "usage": self.usage.snapshot(),
            # Step-timeline profiler histogram states (server/profiler.py)
            # — the tpu:dispatch_wall_seconds / tpu:dispatch_gap_seconds
            # families; the full per-dispatch ring rides /debug/profile.
            "profile": self.profiler.hist_state(),
            # KV economy ledger (server/kv_ledger.py) — the tpu:kv_*
            # block-lifecycle families; the full payload (event ring,
            # prefix heatmap) rides /debug/kv.  Re-synced here so a
            # scrape between dispatches still sees current block states.
            **(self._kv_ledger_snapshot_key()),
            **({"prefix_reused_tokens": self.prefix_reused_tokens}
               if self._prefix_enabled else {}),
            **({
                "spec_cycles": self.spec_cycles,
                # Accepted tokens per verify cycle vs the K+1 ceiling: THE
                # health signal for draft quality.
                "spec_tokens_per_cycle": round(
                    self.spec_emitted / self.spec_cycles, 3)
                if self.spec_cycles else 0.0,
            } if self._spec else {}),
        }

    def _kv_ledger_snapshot_key(self) -> dict:
        """``{"kv_ledger": snapshot}`` for ``metrics_snapshot`` (empty
        on the lane cache, which has no ledger).  The recount reads the
        allocator's host-side structures lock-free like the paged math
        above — the same single-writer tolerance, and the ledger's own
        lock makes the stored counts internally consistent."""
        if self.kv_ledger is None:
            return {}
        self._kv_ledger_sync()
        return {"kv_ledger": self.kv_ledger.snapshot()}

    def _residency_keys(self) -> dict:
        transitions, load_seconds = self.lora.residency_counters()
        return {
            "residency": self.lora.residency_snapshot(),
            "tier_transitions": transitions,
            "adapter_load_seconds": load_seconds,
        }

    # ------------------------------------------------------------------
    # engine loop
    # ------------------------------------------------------------------

    def _free_slot_index(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None and i not in self._reserved_slots:
                return i
        return None

    def _clear_slot(self, i: int) -> None:
        """Release a decode slot row (and, when paged, its pool blocks)."""
        self.slots[i] = None
        if self._spec:
            self._spec_ok[i] = False
        self._slot_lora[i] = -1
        # A staged budget of 0 is also how the next block learns that the
        # host let the row go (_stage_carry).
        self._slot_remaining[i] = 0
        self._slot_fresh[i] = 0
        self._slot_logprobs[i] = 0
        if self._slot_stop_lens[i].any():
            self._slot_stop_ids[i] = -1
            self._slot_stop_lens[i] = 0
            self._slot_stop_hist[i] = -1
            self._stops_active = int(
                (self._slot_stop_lens.sum(axis=1) > 0).sum())
        self._slot_seed[i] = -1
        self._slot_presence[i] = 0.0
        self._slot_frequency[i] = 0.0
        self._slot_bias_ids[i] = -1
        self._slot_bias_vals[i] = 0.0
        if self.paged:
            self._paged_free_row(i)
        # NO usage KV sync here: _clear_slot runs inside the decode result
        # loops (k finished slots would rebuild the holdings list k times
        # per dispatch); every dispatch syncs once at its end, and the
        # rarer non-dispatch clears are off by at most one dispatch
        # interval.

    # -- paged-pool allocator (host side; device sees only table contents) --

    def _paged_needed(self, upto_len: int) -> int:
        return min(-(-upto_len // self._block), self._max_blocks_per_seq)

    def _paged_can_admit(self, n_prompt: int,
                         prompt: list[int] | None = None,
                         adapter: str | None = None,
                         hashes: list[bytes] | None = None) -> bool:
        """Capacity gate.  With ``prompt`` given, cached-prefix blocks that
        would map at zero cost are subtracted from the need — otherwise a
        shared system prompt held by a live request would spuriously
        backpressure the very workload prefix caching targets."""
        if not self.paged:
            return True
        avail = len(self._free_blocks) + (
            len(self._evictable) if self._prefix_enabled else 0)
        needed = self._paged_needed(n_prompt + 1)
        if needed <= avail:
            return True  # plain path fits (evicting zero-ref blocks if need be)
        if prompt is None:
            return False
        # Reuse feasibility: matched blocks come free — but a matched block
        # currently sitting zero-ref in the evictable LRU gets PINNED by the
        # map (it stops being reclaimable), so it can't count toward avail
        # too.  Without this, the admit passed on the double-count, then
        # _paged_ensure found the pool dry and errored the request instead
        # of backpressuring it.  Live-held matched blocks (refs > 0, not in
        # either pool) are the zero-cost case this clause exists for.
        matched = self._prefix_match_blocks(prompt, adapter, hashes)
        reuse_needed = needed - min(len(matched), needed)
        reuse_avail = avail - sum(1 for b in matched if b in self._evictable)
        return reuse_needed <= reuse_avail

    def _paged_alloc_block(self) -> int:
        """One free physical block, evicting the LRU zero-ref cached block
        if the free list is dry.  Raises ``PagedPoolExhausted``."""
        if self._free_blocks:
            blk = self._free_blocks.pop()
            if self.kv_ledger is not None:
                self.kv_ledger.note_alloc()
            return blk
        if self._prefix_enabled and self._evictable:
            blk, h = self._evictable.popitem(last=False)  # LRU
            self._prefix_table.pop(h, None)
            self._block_hash.pop(blk, None)
            self._block_refs.pop(blk, None)
            if self.kv_ledger is not None:
                self.kv_ledger.note_evict(h.hex()[:16])
                self.kv_ledger.note_alloc()
                self._kv_evicts_pending += 1
            return blk
        raise PagedPoolExhausted(
            f"kv pool exhausted: {self._n_blocks} blocks of "
            f"{self._block} tokens all allocated"
        )

    def _paged_ensure(self, row: int, upto_len: int) -> None:
        """Grow ``row``'s table to cover positions < upto_len.

        Raises ``PagedPoolExhausted`` (leaving the row's existing blocks
        intact — the caller decides between backpressure and failing the
        request)."""
        blocks = self._row_blocks[row]
        needed = self._paged_needed(upto_len)
        while len(blocks) < needed:
            blk = self._paged_alloc_block()
            blocks.append(blk)
            self._tables_host[row, len(blocks) - 1] = blk
            self._tables_dirty = True

    def _paged_free_row(self, row: int) -> None:
        blocks = self._row_blocks[row]
        if blocks:
            freed = cached = 0
            for blk in blocks:
                h = self._block_hash.get(blk)
                if h is None:
                    self._free_blocks.append(blk)
                    freed += 1
                else:
                    # Cached prefix block: drop this row's reference; at
                    # zero it parks in the evictable LRU (content kept).
                    self._block_refs[blk] -= 1
                    if self._block_refs[blk] == 0:
                        self._evictable[blk] = h  # fresh key -> MRU end
                        cached += 1
            if self.kv_ledger is not None:
                self.kv_ledger.note_release(freed, cached)
            self._row_blocks[row] = []
            self._tables_host[row, :] = paged_lib.TRASH_BLOCK
            self._tables_dirty = True

    # -- prefix cache (content-addressed full prompt blocks, vLLM-style) --

    def _prefix_hashes(self, prompt: list[int], max_blocks: int,
                       adapter: str | None) -> list[bytes]:
        """Chained SHA-256 digests of the first ``max_blocks`` full prompt
        blocks.  The chain is seeded with the LoRA adapter identity — KV
        depends on the adapter's wk/wv deltas, so the same tokens under a
        different adapter are DIFFERENT content.  Cryptographic hashing,
        not ``hash()``: Python's tuple hash is adversarially collidable,
        and a collision here maps another prompt's KV into this request
        (the vLLM CVE-2025-25183 failure mode)."""
        bs = self._block
        h = hashlib.sha256(repr(adapter).encode()).digest()
        out = []
        for i in range(max_blocks):
            h = hashlib.sha256(
                h + np.asarray(prompt[i * bs:(i + 1) * bs],
                               np.int64).tobytes()
            ).digest()
            out.append(h)
        return out

    def _prefix_hashes_for(self, req: Request) -> list[bytes]:
        """Memoized hash chain for a request's prompt: the digests depend
        only on (prompt, adapter), but backpressured admission re-checks
        the SAME pending request every engine cycle (~20Hz) — without the
        memo a long prompt recomputes hundreds of SHA-256 calls per spin
        on the thread that also drives decode dispatch."""
        memo = getattr(req, "_prefix_hash_memo", None)
        if memo is None:
            memo = self._prefix_hashes(
                req.prompt_tokens,
                (len(req.prompt_tokens) - 1) // self._block, req.adapter)
            req._prefix_hash_memo = memo
        return memo

    def _prefix_match_blocks(self, prompt: list[int], adapter: str | None,
                             hashes: list[bytes] | None = None) -> list[int]:
        """Dry-run of the hash walk: the physical blocks that would map
        (no incref)."""
        if not self._prefix_enabled:
            return []
        if hashes is None:
            hashes = self._prefix_hashes(
                prompt, (len(prompt) - 1) // self._block, adapter)
        out = []
        for h in hashes:
            blk = self._prefix_table.get(h)
            if blk is None:
                break
            out.append(blk)
        return out

    def _prefix_match_and_map(self, row: int, prompt: list[int],
                              adapter: str | None,
                              hashes: list[bytes] | None = None) -> int:
        """Map the longest cached prefix into ``row``'s table (increfs).
        Returns the number of reused TOKENS (multiple of the block size).
        At least the prompt's last token always recomputes, so the request
        still produces fresh logits."""
        if not self._prefix_enabled:
            return 0
        max_blocks = (len(prompt) - 1) // self._block
        blocks = self._row_blocks[row]
        assert not blocks, "prefix map must precede suffix allocation"
        if hashes is None:
            hashes = self._prefix_hashes(prompt, max_blocks, adapter)
        for h in hashes:
            blk = self._prefix_table.get(h)
            if blk is None:
                break
            self._block_refs[blk] += 1
            self._evictable.pop(blk, None)  # in use again
            blocks.append(blk)
            self._tables_host[row, len(blocks) - 1] = blk
            self._tables_dirty = True
        reused = len(blocks) * self._block
        self.prefix_reused_tokens += reused
        if reused and self.kv_ledger is not None:
            # Prefix identity = hex of the DEEPEST matched chain hash:
            # content-addressed and adapter-seeded, so the same shared
            # prompt yields the same id on every replica — the join key
            # for the gateway's fleet duplication index.
            self.kv_ledger.note_reuse_hit(
                hashes[len(blocks) - 1].hex()[:16], len(blocks), reused)
        return reused

    def _prefix_register_row(self, row: int, prompt: list[int],
                             adapter: str | None,
                             hashes: list[bytes] | None = None) -> None:
        """After a prompt is fully in the row's blocks, publish its full
        blocks to the prefix table so later prompts can share them."""
        if not self._prefix_enabled:
            return
        max_blocks = (len(prompt) - 1) // self._block
        blocks = self._row_blocks[row]
        if hashes is None:
            hashes = self._prefix_hashes(prompt, max_blocks, adapter)
        for i, h in enumerate(hashes):
            blk = blocks[i]
            if self._block_hash.get(blk) is not None:
                continue  # already a cached block (mapped via reuse)
            if h in self._prefix_table:
                continue  # another live block already serves this content
            self._block_hash[blk] = h
            self._prefix_table[h] = blk
            self._block_refs[blk] = 1
        if hashes and self.kv_ledger is not None:
            self.kv_ledger.note_register(hashes[-1].hex()[:16], len(hashes))

    def _kv_note_reuse_unwind(self, req: Request, reused: int) -> None:
        """Ledger mirror of a reuse unwind — called exactly where
        ``prefix_reused_tokens`` is decremented, so the ledger's
        tokens-saved attribution tracks the engine counter."""
        if self.kv_ledger is None or not reused:
            return
        blocks = reused // self._block
        hashes = self._prefix_hashes_for(req)
        self.kv_ledger.note_reuse_unwind(
            hashes[blocks - 1].hex()[:16], blocks, reused)

    def _sync_tables(self) -> None:
        """Push host-side table changes to the device copy in the cache.

        COPY, never alias: the cache is DONATED to every decode/insert/
        chunk program, and on the CPU backend ``jnp.asarray`` of a numpy
        array can share its buffer — donation would then let XLA write a
        program OUTPUT over ``_tables_host`` behind the allocator's back
        (observed: the [B, STOP_LEN] stop-history output landing in the
        same-shaped donated tables buffer, trashing every row's mapping).
        """
        if self.paged and self._tables_dirty:
            self.cache = dict(self.cache,
                              tables=jnp.array(self._tables_host, copy=True))
            self._tables_dirty = False

    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b and b <= self.cfg.max_seq_len:
                return b
        raise ValueError(f"prompt length {n} exceeds largest prefill bucket")

    def _max_bucket(self) -> int:
        return max(
            (b for b in self.cfg.prefill_buckets if b <= self.cfg.max_seq_len),
            default=0,
        )

    def _next_key(self):
        """A subkey for a program that is not the decode block (which
        splits the engine's key itself): the split and its unpacking as
        ONE small program, ``jit_next_key``."""
        self._rng, sub = self._jit_next_key(self._rng)
        return sub

    def _lora_buffers(self):
        return self.lora.buffers if self.lora is not None else None

    def _block_lora_buffers(self, staged_i32: np.ndarray):
        """What a decode block reads of the adapters, decided from
        ``staged_i32``, the private copy of the int32 slot buffer that goes
        up with it (a freed row holds -1): ``(buffers, their targets, rows
        naming an adapter)``, buffers and targets ``None`` when no row
        names one.  A slot -1 row's delta is an exact 0 (``models/lora.py``),
        so a block of base rows runs the program traced without the delta
        and says what the other would.  One adapter row among base rows
        brings the block ``scale`` and the ``{t}_a`` / ``{t}_b`` of
        ``_lora_targets``: the manager's own arrays, nothing copied, and
        only of the targets a resident adapter carries, since every other
        target's hold exact zeros in every slot.  The rule of every decode
        dispatch, plain and speculative; the prompt programs always take
        all the buffers."""
        if self.lora is None:
            return None, None, 0
        rows = int(np.count_nonzero(staged_i32[self._lora_span] >= 0))
        if not rows:
            return None, None, 0
        targets = self._lora_targets
        assert self.lora.targets_of(
            s.request.adapter for s in self.slots
            if s is not None and s.lora_slot >= 0) <= set(targets), (
                "a row names an adapter with a target the block is not "
                f"handed: {targets}")
        return (lora_lib.select_targets(self.lora.buffers, targets),
                targets, rows)

    def _phase(self, name: str):
        """The engine thread's phase from here to the end of the ``with``
        (server/profiler.py)."""
        return self.profiler.phase(name)

    def _enqueue(self, name: str, *reqs: "Request"):
        """Trace-only span round a jitted call inside a ``*.stage`` phase.
        Round a prefill program it names the program's requests (their ids,
        the first's prompt length and bucket), so that an idle gap of the
        device trace can be matched to the ``engine.prefill`` request
        span."""
        if not reqs:
            return self.profiler.annotation(name)
        attrs = reqs[0].prefill_attrs
        return self.profiler.annotation(
            name, request_id="+".join(r.request_id for r in reqs),
            prompt_tokens=attrs.get("prompt_tokens", 0),
            bucket=attrs.get("bucket", 0))

    def _note_prompt_program(self, program: str, positions: int, real: int,
                             t0: float, out, *reqs: "Request") -> None:
        """One prompt program is on the device's queue: ``program`` (of
        ``metrics_registry.PROMPT_PROGRAMS``), enqueued at ``t0``, computes
        ``positions`` positions of which ``real`` are prompt tokens of
        ``reqs``; ``out`` is a result of it.  Counted here
        (``tpu:prompt_programs_total``, ``tpu:prompt_positions_total``, and
        the padding in the operator's ``tpu:prefill_padding_tokens_total``),
        timed where the loop sees it complete (``_prompt_programs_done``)."""
        pad = positions - real
        self.profiler.note_prompt_program(
            program, real, pad,
            self._chunk_attn_steps(positions) if program == "chunk" else 0)
        self.usage.charge_padding(pad)
        for r in reqs:
            attrs = r.prefill_attrs
            attrs["programs"] = attrs.get("programs", 0) + 1
            attrs["positions"] = (attrs.get("positions", 0)
                                  + positions // len(reqs))
        self._prompt_pending.append(
            _PromptProgram(program, positions, t0, out, reqs))
        self._prompt_enqueued += 1
        self._see_inflight()

    def _chunk_attn_steps(self, chunk: int) -> int:
        """Grid steps the chunk attend's kernel walks over the attention
        layers of one chunk program of ``chunk`` positions
        (``tpu:chunk_attn_grid_steps_total``), from the cache's shapes by
        the dispatcher's own rule (``pallas_attention.chunk_grid_steps``): a
        full layer's lane is the slot's, a window layer's its ring with the
        chunk behind it (``transformer._ring_chunk``), a paged row's its
        table's blocks, a latent model's the rows expanded to a key a head.
        0 where the chunk attend takes the XLA form on every backend (int8
        lanes, the kernels off or under a mesh) or the shapes."""
        cfg = self.model_cfg
        if self._kv_quant or not cfg.use_flash_attention:
            return 0
        k = self.cache["k"]
        item = k.dtype.itemsize
        if self._latent:
            return k.shape[0] * pallas_attention.chunk_grid_steps(
                chunk, k.shape[2], cfg.n_heads, cfg.n_heads,
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, item)
        lane = (self._block * self._max_blocks_per_seq if self.paged
                else k.shape[2])
        steps = k.shape[0] * pallas_attention.chunk_grid_steps(
            chunk, lane, cfg.n_heads, *k.shape[-2:], item)
        ring = self.cache.get("k_win")
        if ring is not None:
            steps += ring.shape[0] * pallas_attention.chunk_grid_steps(
                chunk, ring.shape[2] + chunk, cfg.n_heads, *ring.shape[-2:],
                item)
        return steps

    def _see_inflight(self) -> None:
        """Staging a prompt program takes the host milliseconds, a burst of
        them longer than a decode step: a block in flight that is done by
        now is seen complete HERE (one readiness test, no wait) and not
        when the thread gets round to reading it, or its step would be
        booked the host's lateness and the programs behind it as much too
        little.  ``_process_block`` books what is noted."""
        blk = self._inflight
        if blk is None or "done" in blk or not _is_ready(blk["toks"]):
            return
        self._prompt_programs_done(blk["prompts"])  # ahead of it: done too
        blk["start"] = max(blk["t0"], self._last_done_pc)
        blk["done"] = self._last_done_pc = time.perf_counter()

    def _prompt_programs_done(self, upto: int) -> None:
        """See the first ``upto`` prompt programs ever enqueued complete:
        wait for the last of them that is not booked yet (``prefill.wait``;
        the queue runs in order, so the others are done too) and stamp the
        completion chain there.  Their interval, from the later of the
        first one's enqueue and the last completion the loop saw to now, is
        booked to ``tpu:prompt_program_seconds_total`` and to their
        requests' ``device_s``, shared by positions where several end
        together.  Nothing to do where all of them are booked."""
        pending = self._prompt_pending
        n = upto - (self._prompt_enqueued - len(pending))
        if n <= 0:
            return
        burst = pending[:n]
        del pending[:n]
        with self._phase("prefill.wait"):
            jax.block_until_ready(burst[-1].out)
        done = time.perf_counter()
        t0 = max(burst[0].t0, self._last_done_pc)
        self._last_done_pc = done
        positions = sum(p.positions for p in burst)
        shares = [(p.program, (done - t0) * p.positions / positions)
                  for p in burst]
        self.profiler.note_prompt_done(t0, done, shares)
        for p, (_, share) in zip(burst, shares):
            for r in p.reqs:
                r.prefill_attrs["device_s"] = round(
                    r.prefill_attrs.get("device_s", 0.0) + share, 9)

    def _wait_for_work(self) -> None:
        if self._prompt_pending:
            # Programs nothing waits for any more (a stream aborted, a
            # first token nobody reads): booked before the loop sleeps, or
            # the next burst's interval would run from their enqueue.
            try:
                self._prompt_programs_done(self._prompt_enqueued)
            except Exception:
                logger.exception("an abandoned prompt program failed")
        self._settle_admissions()
        self.profiler.note_idle()
        with self._phase("idle"), self._work:
            self._work.wait(timeout=0.05)

    @_in_phase("admit")
    def _admit_and_insert(self) -> bool:
        """Admission: drain decode_wait into freed slots (fill EVERY free
        slot before decoding: one left empty idles for a whole block),
        direct-prefill into free slots, prefill AHEAD when slots are full.

        FIFO holds: decode_wait drains before the raw queue, and a direct
        prefill only happens when nothing is parked — so it can never jump
        an older waiting request, whether the head waits on a slot or on
        paged-pool blocks.  Chunked prompts (beyond the largest bucket)
        stream straight into a cache lane, so with no lane free they
        head-of-line block as ``_pending``.
        """
        did = self._drain_decode_wait()
        cap = (self.cfg.decode_wait_cap if self.cfg.decode_wait_cap is not None
               else self.cfg.decode_slots)
        while True:
            if self._pending is None:
                try:
                    self._pending = self.prefill_queue.get_nowait()
                except queue_mod.Empty:
                    break
            req = self._pending
            if req.cancelled.is_set():
                self._pending = None
                self._finish(req, "cancelled")
                did = True
                continue
            if getattr(req, "_handoff_only", False):
                # Disaggregation hop 1: prefill with NO slot and NO cache
                # write — the KV leaves as a handoff, so this admits even
                # when every slot is busy and the pool is dry.
                self._pending = None
                self._admitting += 1
                try:
                    self._do_prefill_handoff(req)
                finally:
                    self._admitting -= 1
                did = True
                continue
            if getattr(req, "_attach_handoff", None) is not None:
                # Disaggregation hop 2: the KV is already computed; park it
                # in decode_wait (the same seam prefill-ahead uses) and let
                # the normal drain admit it into a slot.  The cap bounds
                # imported-but-unslotted KV exactly like prefill-ahead KV.
                if len(self.decode_wait) >= cap:
                    break
                self._pending = None
                self._admitting += 1
                try:
                    self._do_attach(req)
                finally:
                    self._admitting -= 1
                self._drain_decode_wait()
                did = True
                continue
            if self._free_slot_index() is not None:
                if self.decode_wait:
                    # The parked head couldn't take this slot (pool
                    # backpressure): strict FIFO — don't let a newer request
                    # steal the blocks it is waiting for.
                    break
                n_req = len(req.prompt_tokens)
                # Ring-path prompts (sequence-parallel prefill) never map
                # cached prefix blocks — the ring computes the whole prompt
                # and inserts into FRESH blocks — so their admission gate
                # must not assume hash-based reuse, or _paged_ensure would
                # exhaust the pool the gate said was sufficient.
                takes_ring = (n_req > self._max_bucket()
                              and self._ring_usable(n_req))
                if not self._paged_can_admit(
                        n_req, req.prompt_tokens,
                        req.adapter,
                        hashes=(self._prefix_hashes_for(req)
                                if self._prefix_enabled and not takes_ring
                                else None)):
                    break  # pool backpressure: wait for block frees
                if (len(req.prompt_tokens) > self._max_bucket()
                        and not self._ring_usable(len(req.prompt_tokens))):
                    if not self._lane_available(n_req):
                        break  # no lane (count or KV pressure); FIFO head waits
                    self._pending = None
                    self._admitting += 1
                    try:
                        if not self._start_stream(req):
                            break  # reparked for backpressure; stop cycle
                    finally:
                        self._admitting -= 1
                    did = True
                    continue
                self._pending = None
                self._admitting += 1
                try:
                    if (self.cfg.prefill_batch > 1
                            and len(req.prompt_tokens) <= self._max_bucket()
                            and not (self.paged and self._prefix_enabled)):
                        # Prefix-cache engines stay per-request: the grouped
                        # program computes full-prompt KV, so a cached-prefix
                        # row would pay the compute reuse exists to skip.
                        self._do_prefill_group(
                            self._collect_prefill_group(req))
                    else:
                        self._do_prefill(req)
                finally:
                    self._admitting -= 1
                did = True
                continue
            if (len(req.prompt_tokens) <= self._max_bucket()
                    and len(self.decode_wait) < cap):
                self._pending = None
                self._admitting += 1
                try:
                    if self.cfg.prefill_batch > 1:
                        # Paged included: prefill-ahead KV parks OFF-cache,
                        # so no pool blocks are touched until the drain,
                        # which gates per row on _paged_can_admit.
                        self._do_prefill_ahead_group(
                            self._collect_ahead_group(req, cap))
                    else:
                        self._do_prefill_ahead(req)
                finally:
                    self._admitting -= 1
                did = True
                continue
            break
        return did

    def _sweep_decode_wait(self) -> bool:
        """Drop cancelled entries ANYWHERE in decode_wait (not just the
        head: a released attach must free its parked KV even while older
        work blocks the front), plus handoff imports parked past the TTL —
        abandoned work whose gateway already rerouted."""
        now = time.time()
        ttl = self.cfg.handoff_ttl_s
        keep: list[_WaitingPrefill] = []
        swept = False
        for w in self.decode_wait:
            expired = (ttl > 0 and w.from_handoff and w.t_parked
                       and now - w.t_parked > ttl)
            if w.request.cancelled.is_set() or expired:
                self._parked_kv_tokens -= w.k.shape[2]
                if self.kv_ledger is not None:
                    self.kv_ledger.note_sweep(
                        int(w.k.shape[2]),
                        "ttl" if expired
                        and not w.request.cancelled.is_set()
                        else "cancelled")
                if expired and not w.request.cancelled.is_set():
                    logger.warning(
                        "handoff %s parked %.1fs > ttl %.1fs; releasing",
                        w.request.request_id, now - w.t_parked, ttl)
                    if self.event_sink is not None:
                        self.event_sink("kv_release",
                                        request_id=w.request.request_id,
                                        reason="ttl")
                self._finish(w.request, "cancelled")
                swept = True
            else:
                keep.append(w)
        if swept:
            self.decode_wait = collections.deque(keep)
            self._usage_sync_kv()  # parked holdings changed
        return swept

    def _drain_decode_wait(self) -> bool:
        did = self._sweep_decode_wait()
        while self.decode_wait:
            w = self.decode_wait[0]
            if w.request.cancelled.is_set():
                self.decode_wait.popleft()
                self._parked_kv_tokens -= w.k.shape[2]
                if self.kv_ledger is not None:
                    self.kv_ledger.note_sweep(int(w.k.shape[2]), "cancelled")
                self._usage_sync_kv()
                self._finish(w.request, "cancelled")
                did = True
                continue
            slot_idx = self._free_slot_index()
            if slot_idx is None:
                break
            if not self._paged_can_admit(w.n):
                break  # pool backpressure: KV stays parked off-cache
            self.decode_wait.popleft()
            self._parked_kv_tokens -= w.k.shape[2]
            if self.kv_ledger is not None:
                self.kv_ledger.note_unpark(int(w.k.shape[2]))
            # Mid-admission guard: between the pop (decode_queue -> 0) and
            # _register_slot (running -> 1) the insert runs a device op —
            # without this count a drain()/scrape polling that window sees
            # a phantom-quiescent engine and declares victory with the
            # request still in flight.
            self._admitting += 1
            try:
                self._insert_waiting(slot_idx, w)
            finally:
                self._admitting -= 1
            did = True
        return did

    def _do_prefill_ahead(self, req: Request) -> None:
        """Prefill with NO slot: prompt KV parks in decode_wait.

        TTFT is prefill-bound, not slot-bound, which is the point of the
        disaggregated design: the first token stays on the device
        (async-copied) and is read once the prefill is done
        (``_read_first_tokens``).
        """
        try:
            self._stamp_prefill_start(req)
            n = len(req.prompt_tokens)
            lora_slot = (self.lora.slot_for(req.adapter)
                         if self.lora is not None else -1)
            first_token, k, v, lp_info = self._bucket_prefill(
                req, n, lora_slot)
            self._park_waiting(req, first_token, lp_info, k, v, n, lora_slot)
        except Exception as e:  # engine must survive a poison request
            logger.exception("prefill-ahead failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")

    def _do_prefill_handoff(self, req: Request) -> None:
        """Prefill with NO slot and NO cache write: the prompt KV leaves
        the engine as a serializable ``PrefillHandoff`` (hop 1 of
        cross-engine disaggregation).  TTFT here measures pure prefill
        latency — the token itself is emitted by the decode engine."""
        from llm_instance_gateway_tpu.server import kv_transfer

        if req.cancelled.is_set():
            self._finish(req, "cancelled")
            return
        try:
            self._stamp_prefill_start(req)
            n = len(req.prompt_tokens)
            lora_slot = (self.lora.slot_for(req.adapter)
                         if self.lora is not None else -1)
            first_token, k, v, lp_info = self._bucket_prefill(
                req, n, lora_slot)
            self._prompt_programs_done(self._prompt_enqueued)
            with self._phase("prefill.wait"):
                # The token, its logprobs and the prompt's KV come to the
                # host here, once the prefill program is done.
                req.handoff = kv_transfer.export_handoff(
                    req, k, v, n, int(first_token),
                    lp_info=tuple(np.asarray(a) for a in lp_info),
                    quantize=getattr(req, "_handoff_quantize", None))
            with self._phase("prefill.emit"):
                req.t_first_token = time.time()
                self._record_ttft(req)
                self._finish(req, "handoff")
        except Exception as e:  # engine must survive a poison request
            logger.exception("handoff prefill failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")

    @_in_phase("prefill.stage")
    def _handoff_device_kv(self, handoff):
        """Handoff KV -> device arrays shaped like a bucketed prefill's
        output (``[L, 1, pad_to, Kh, hd]``), so the existing insert seams
        (lane dynamic-slice / paged block scatter, quantizing variants
        included) consume it unchanged.  Padding to the engine's own bucket
        set keeps the insert's compiled-shape set bounded."""
        k_np, v_np = handoff.kv_arrays()
        n = handoff.n
        if n <= self._max_bucket():
            pad_to = self._bucket(n)
        else:
            step = max(self._max_bucket(), 1)
            pad_to = min(-(-n // step) * step, self.cfg.max_seq_len)
        lyr, _, kh, hd = k_np.shape
        kp = np.zeros((lyr, 1, pad_to, kh, hd), k_np.dtype)
        vp = np.zeros((lyr, 1, pad_to, kh, hd), v_np.dtype)
        kp[:, 0, :n] = k_np
        vp[:, 0, :n] = v_np
        return jnp.asarray(kp), jnp.asarray(vp)

    def _do_attach(self, req: Request) -> None:
        """Import a handoff's KV and park it in ``decode_wait`` — from
        there the normal drain inserts it into a freed slot (allocating
        pool blocks, registering the prefix-cache chain) and decode starts
        at the carried position.  The first token is emitted HERE (it
        came over the wire, on the host): TTFT on this engine is attach
        latency, and a one-token request finishes without ever taking a
        slot."""
        handoff = req._attach_handoff
        if req.cancelled.is_set():
            self._finish(req, "cancelled")
            return
        try:
            lora_slot = (self.lora.slot_for(req.adapter)
                         if self.lora is not None else -1)
            k, v = self._handoff_device_kv(handoff)
            if self._emit_first_token(req, handoff.first_token,
                                      handoff.first_lp_info()):
                return  # finished at attach; never needed a slot
            w = _WaitingPrefill(
                request=req,
                first_token=jnp.asarray(handoff.first_token, jnp.int32),
                k=k, v=v, n=handoff.n, lora_slot=lora_slot,
                lp_info=None, first_emitted=True, from_handoff=True,
                t_parked=time.time())
            self.decode_wait.append(w)
            self._parked_kv_tokens += w.k.shape[2]
            if self.kv_ledger is not None:
                self.kv_ledger.note_park(int(w.k.shape[2]), "handoff")
            self._usage_sync_kv()
        except Exception as e:  # engine must survive a poison handoff
            logger.exception("attach failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")

    @_in_phase("prefill.stage")
    def _activate_slot(self, slot_idx: int, req: Request, lora_slot: int,
                       n: int, first_token, lp_info,
                       emitted: bool = False) -> None:
        """Slot activation, shared by direct prefills, decode_wait inserts
        and chunk-stream activation.  The first token
        stays on the device: one scatter puts it into the token carry, and
        ``_read_first_tokens`` reads it as soon as the loop holds the
        thread after the prefill (``emitted``: it already reached the
        request).  Position, budget and stop history are STAGED, not
        scattered: the next block takes them from the slot buffer under
        the row's ``fresh`` mark (``_stage_carry``), so an admission costs
        one fixed-shape helper program whatever else the block frees or
        admits."""
        # Grouped-prefill rows carry their device slice so this scatter
        # never forces a host sync.
        tok_dev = (first_token.dev if isinstance(first_token, _Row)
                   and first_token.dev is not None else first_token)
        self._dev_tokens = self._dev_tokens.at[slot_idx].set(tok_dev)
        self._register_slot(slot_idx, _Slot(
            request=req, lora_slot=lora_slot, position=n))
        self._slot_positions[slot_idx] = n
        self._slot_fresh[slot_idx] = 1
        # The row's stop history: emitted tokens from the host record
        # (attach / already-read admissions); with none yet, the program
        # seeds it with the device-resident first token.
        tail = req.output_tokens[-STOP_LEN:]
        if tail:
            self._slot_stop_hist[slot_idx, STOP_LEN - len(tail):] = tail
        if not emitted:
            self._queue_first_token(req, first_token, lp_info)
        self._count_first_token(slot_idx, tok_dev)
        if self._spec:
            # _register_slot set the row's sampling params _draft_admit
            # gates on; the device extra flag resets for the new occupant.
            self._dev_has_extra = self._dev_has_extra.at[slot_idx].set(False)
            self._draft_admit(slot_idx, req.prompt_tokens)

    def _queue_first_token(self, req: Request, first_token, lp_info) -> None:
        """Start the first token's copy to the host (a grouped row's left
        with its batch, ``_HostBatch``) and queue it for
        ``_read_first_tokens``."""
        try:
            first_token.copy_to_host_async()
        except AttributeError:
            pass
        self._first_unread.append(
            (req, first_token, lp_info, self._prompt_enqueued))

    def _insert_waiting(self, slot_idx: int, w: _WaitingPrefill) -> None:
        """Insert a parked prefill's KV into a freed cache lane."""
        req = w.request
        try:
            skip_blocks = 0
            if w.from_handoff and self._prefix_enabled:
                # Handoff composing with prefix reuse: whole blocks this
                # engine already caches for the prompt's prefix MAP into
                # the row (refcounted table repoint, zero writes) and the
                # insert scatter routes their positions to the trash block
                # — a repeated attach re-writes only the suffix, and a
                # shared live block is never re-scattered (identical
                # content in theory, but another row may be mid-read).
                reused = self._prefix_match_and_map(
                    slot_idx, req.prompt_tokens, req.adapter)
                skip_blocks = reused // self._block
            self._insert_prompt_kv(w.k, w.v, slot_idx, w.n,
                                   skip_leading_blocks=skip_blocks)
            self._prefix_register_row(slot_idx, req.prompt_tokens,
                                      req.adapter)
            # ``emitted``: the first token reached the request at attach
            # admission, or was queued for reading when it parked
            # (_park_waiting); queuing it again would emit it twice.  The
            # carry scatter still uses it (decode continues from it).
            self._activate_slot(
                slot_idx, req, w.lora_slot, w.n, w.first_token,
                w.lp_info, emitted=True)
        except Exception as e:
            logger.exception("decode-wait insert failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")
            if self.paged:
                # A failure after _insert_prompt_kv would otherwise strand
                # the row's blocks (and pin any freshly registered ones at
                # refs=1 forever): no slot was registered, so no
                # _clear_slot will ever free them.
                self._paged_free_row(slot_idx)

    # ------------------------------------------------------------------
    # speculative decoding (draft proposes, target verifies in one pass)
    # ------------------------------------------------------------------

    @staticmethod
    def _spec_block_impl(model_cfg, draft_cfg, params, draft_params,
                         lora_bufs, cache, draft_cache, tokens, positions,
                         remaining, extra_tok, extra_pos, has_extra, spec_ok,
                         temp, topk, topp, key, slot_ids, eos_id, seeds,
                         n_cycles: int, k_steps: int):
        """``n_cycles`` fused speculative cycles, entirely device-side.

        Each cycle: the draft ingests the <=2 context tokens it hasn't seen,
        proposes ``k_steps`` greedy tokens autoregressively, and the target
        scores [cur, d_1..d_K] in ONE multi-token forward (extend_step).
        Greedy rows accept the longest matching prefix plus the target's
        bonus token — EXACT greedy parity; sampled / non-speculating rows
        emit one token from the first position's logits.  Acceptance,
        budget, and EOS truncation are all mask arithmetic, so the whole
        block is one jitted program: the same dispatch/readback shape as
        ``_decode_impl``, which is what lets speculation compose with the
        overlapped order and ``decode_steps_per_sync > 1``.

        Stale-KV safety: cycle writes at positions p..p+K may leave garbage
        beyond the accepted prefix, but the NEXT cycle's K+1 writes start at
        the corrected position and always cover the stale range — the same
        invariant the single-cycle version relied on.  It holds for the
        paged target too: the physical address of a logical position is
        stable within a block's lifetime, and the engine pre-allocates the
        whole block span a dispatch can write (``_paged_ensure_decode``).

        Returns flattened [T=n_cycles*(K+1), B] token/valid/logprob arrays —
        the exact layout ``_decode_impl`` produces — plus the device carries
        (next token/position/budget, draft-extra triple) and both caches.
        """
        b = tokens.shape[0]
        paged = "tables" in cache
        if paged:
            s_max = cache["tables"].shape[1] * cache["k"].shape[2]
            target_extend = functools.partial(paged_lib.extend_step_paged,
                                              model_cfg, params)
        else:
            s_max = cache["k"].shape[2]
            target_extend = functools.partial(transformer.extend_step,
                                              model_cfg, params)
        kp1 = k_steps + 1

        def greedy_pick(lg, vocab):
            # Mask the zero-logit vocab-PADDING columns (lm_head pads to a
            # multiple of 128) or argmax can emit ids the tokenizer lacks.
            masked = jnp.where(jnp.arange(lg.shape[-1]) < vocab, lg, -jnp.inf)
            return jnp.argmax(masked, axis=-1).astype(jnp.int32)

        def one_cycle(carry, cycle_key):
            (cache, draft_cache, tokens, positions, remaining,
             extra_tok, extra_pos, has_extra) = carry
            active = remaining > 0
            safe_pos = jnp.minimum(positions, s_max - 1)
            # --- draft catch-up + propose ---
            ctx_tokens = jnp.stack(
                [jnp.where(has_extra, extra_tok, tokens), tokens], axis=1)
            ctx_positions = jnp.stack(
                [jnp.where(has_extra, jnp.minimum(extra_pos, s_max - 1),
                           safe_pos),
                 jnp.where(has_extra, safe_pos,
                           jnp.minimum(positions + 1, s_max - 1))], axis=1)
            idx = jnp.where(has_extra, 1, 0)  # last REAL ctx index per row
            logits2, draft_cache = transformer.extend_step(
                draft_cfg, draft_params, draft_cache, ctx_tokens,
                ctx_positions)
            last = logits2[jnp.arange(b), idx]  # [B, V]
            cur_pos = ctx_positions[jnp.arange(b), idx]
            d1 = greedy_pick(last, draft_cfg.vocab_size)

            def body(c, _):
                tok, pos, dcache = c
                lg, dcache = transformer.decode_step(
                    draft_cfg, draft_params, dcache, tok, pos)
                nxt = greedy_pick(lg, draft_cfg.vocab_size)
                return (nxt, jnp.minimum(pos + 1, s_max - 1), dcache), nxt

            if k_steps > 1:
                (_, _, draft_cache), rest = jax.lax.scan(
                    body,
                    (d1, jnp.minimum(cur_pos + 1, s_max - 1), draft_cache),
                    None, length=k_steps - 1)
                draft = jnp.concatenate([d1[None], rest], axis=0).T  # [B, K]
            else:
                draft = d1[:, None]

            # --- target verify: [cur, d_1..d_K] in one forward ---
            vtokens = jnp.concatenate([tokens[:, None], draft], axis=1)
            # Clamp like decode: overflow rows finish on the host's max_seq
            # check; the clamped scatter writes garbage the mask hides.
            vpos = jnp.minimum(
                positions[:, None] + jnp.arange(kp1)[None], s_max - 1)
            # active gates the verify WRITES: frozen/empty rows must not
            # stomp a reserved lane mid-chunk-stream (same contract as
            # _decode_impl's step_fn call).
            logits, cache = target_extend(
                cache, vtokens, vpos, lora_bufs=lora_bufs, slot_ids=slot_ids,
                active=active)
            greedy = greedy_pick(logits, model_cfg.vocab_size)  # [B, K+1]
            first_sampled = sample(
                logits[:, 0], cycle_key, temp, topk, topp,
                valid_vocab=model_cfg.vocab_size,
                seeds=seeds, positions=safe_pos, live=active)
            greedy_row = spec_ok & (temp <= 0.0)
            e0 = jnp.where(greedy_row, greedy[:, 0], first_sampled)
            # d_{i+1} must equal the target's greedy continuation g_i.
            match = (draft == greedy[:, :-1]) & greedy_row[:, None]
            m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            count = jnp.where(greedy_row, m + 1, 1)
            emitted = greedy.at[:, 0].set(e0)
            lp, top_v, top_i = _logprob_info(
                logits, emitted, model_cfg.vocab_size)

            # --- device-side truncation: frozen rows, budget, EOS ---
            count = jnp.where(active, jnp.minimum(count, remaining), 0)
            in_count = jnp.arange(kp1)[None] < count[:, None]
            iseos = emitted == eos_id
            ex_eos = (jnp.cumsum(iseos.astype(jnp.int32), axis=1)
                      - iseos.astype(jnp.int32))  # EOS strictly before j
            valid = in_count & (ex_eos == 0)
            eff = jnp.sum(valid.astype(jnp.int32), axis=1)
            hit_eos = jnp.any(valid & iseos, axis=1)

            # --- carry updates ---
            new_tok = jnp.where(
                eff > 0, emitted[jnp.arange(b), jnp.maximum(eff - 1, 0)],
                tokens)
            new_positions = positions + eff
            new_remaining = jnp.where(hit_eos, 0, remaining - eff)
            # Fully-accepted cycle: d_K's kv is missing from the draft lane;
            # hand it to the next cycle's catch-up.
            full = greedy_row & active & (eff == kp1) & ~hit_eos
            new_extra_tok = jnp.where(full, draft[:, k_steps - 1], extra_tok)
            new_extra_pos = jnp.where(full, positions + k_steps, extra_pos)
            return ((cache, draft_cache, new_tok, new_positions,
                     new_remaining, new_extra_tok, new_extra_pos, full),
                    (emitted, valid, lp, top_v, top_i))

        keys = jax.random.split(key, n_cycles)
        carry, (emitted, valid, lps, top_v, top_i) = jax.lax.scan(
            one_cycle,
            (cache, draft_cache, tokens, positions, remaining,
             extra_tok, extra_pos, has_extra), keys)
        (cache, draft_cache, next_tokens, next_positions, next_remaining,
         next_extra_tok, next_extra_pos, next_has_extra) = carry
        # Flatten [C, B, K+1] -> [C*(K+1), B]: cycle-major, within-cycle
        # order preserved — the host walks it exactly like decode steps.
        t = n_cycles * kp1
        flat = lambda a: jnp.swapaxes(a, 1, 2).reshape((t,) + a.shape[1:2])
        top_flat = lambda a: jnp.swapaxes(a, 1, 2).reshape(
            (t, b) + a.shape[3:])
        return (flat(emitted), flat(valid), flat(lps), top_flat(top_v),
                top_flat(top_i), next_tokens, next_positions, next_remaining,
                next_extra_tok, next_extra_pos, next_has_extra,
                cache, draft_cache)

    @_in_phase("prefill.stage")
    def _draft_admit(self, slot_idx: int, prompt_tokens: list[int]) -> None:
        """Mirror a freshly admitted prompt into the draft model's lane so
        the slot can speculate.  Rows admitted through paths the draft
        can't mirror (chunk stream, ring) simply don't speculate."""
        if not self._spec:
            return
        n = len(prompt_tokens)
        req = self.slots[slot_idx].request if self.slots[slot_idx] else None
        if (n > self._max_bucket() or self._slot_temp[slot_idx] > 0.0
                or (req is not None and req.stop_sequences)):
            # Sampled rows never accept proposals — mirroring their prompt
            # into the draft would be a wasted prefill per admission.
            # Stop-sequence rows are excluded too: their automata only run
            # in plain blocks, so they never speculate.
            self._spec_ok[slot_idx] = False
            return
        try:
            bucket = self._bucket(n)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = prompt_tokens
            positions = np.zeros((1, bucket), np.int32)
            positions[0, :n] = np.arange(n)
            k, v = self._jit_draft_prefill(
                self.draft_params, jnp.asarray(tokens), jnp.asarray(positions))
            self.draft_cache = self._jit_draft_insert(
                self.draft_cache, k, v, jnp.int32(slot_idx), jnp.int32(n))
            self._spec_ok[slot_idx] = True
        except Exception:
            logger.exception("draft admit failed; slot %d decodes "
                             "non-speculatively", slot_idx)
            self._spec_ok[slot_idx] = False

    def _spec_cycles_per_sync(self) -> int:
        """Speculative cycles per dispatch.

        All-greedy batches: ceil(steps/(K+1)) cycles keeps the per-dispatch
        token cadence comparable to ``decode_steps_per_sync`` plain steps
        (each cycle emits up to K+1 tokens).  Mixed batches: sampled rows
        advance only ONE token per cycle, so the short schedule would
        throttle them (K+1)x per dispatch — run a full ``steps`` cycles
        instead, which restores sampled-row cadence and lets greedy rows
        run ahead (budget masks cap them).  Two schedules = two compiled
        block variants, both cached after first use.  Under the adaptive
        planner, ``steps`` is the planned per-dispatch budget (admission
        pressure and SSE cadence throttle speculative fusion exactly like
        plain fusion)."""
        steps = self._plan_steps()
        mixed = any(
            s is not None
            and not (self._spec_ok[i] and self._slot_temp[i] <= 0.0)
            for i, s in enumerate(self.slots))
        if mixed:
            return steps
        k1 = self.cfg.speculative_k + 1
        return max(1, -(-steps // k1))

    def _spec_row_steps(self, n_cycles: int, k: int) -> list[int]:
        """Per-row paged write-frontier for one spec block: a speculating
        row can advance K+1 per cycle; a sampled/non-spec row advances one
        per cycle but each verify still writes a K-token rejected tail."""
        return [
            n_cycles * (k + 1)
            if self._spec_ok[i] and self._slot_temp[i] <= 0.0
            else n_cycles + k + 1
            for i in range(self.cfg.decode_slots)
        ]

    def _prefill_common(self, req: Request):
        """Shared admission path: bucketed (or ring sequence-parallel)
        prefill + insert.  Long prompts only reach here when ``_ring_usable``
        — otherwise ``_admit_and_insert`` diverts them to the interleaved
        chunk stream (``_start_stream``/``_stream_step``).
        Returns (slot_idx, first_token_device, n, lora_slot, lp_info)."""
        self._stamp_prefill_start(req)
        slot_idx = self._free_slot_index()
        n = len(req.prompt_tokens)
        lora_slot = self.lora.slot_for(req.adapter) if self.lora is not None else -1
        if self._prefix_enabled and n <= self._max_bucket():
            # Cached shared prefix: skip the full-prompt program entirely
            # and prefill only the suffix (VERDICT r2 #6 — the shared
            # system prompt below the largest bucket is the common case).
            # None = nothing cached matched; fall through (one hash walk).
            res = self._prefix_bucket_prefill(req, slot_idx, n, lora_slot)
            if res is not None:
                return res
        if n > self._max_bucket():
            first_token, k, v, lp_info = self._ring_prefill(req, n, lora_slot)
        else:
            first_token, k, v, lp_info = self._bucket_prefill(req, n, lora_slot)
        # Insert prompt KV (trim to bucket; cache rows are max_seq_len).
        self._insert_prompt_kv(k, v, slot_idx, n)
        if self._prefix_enabled and n <= self._max_bucket():
            self._prefix_register_row(slot_idx, req.prompt_tokens,
                                      req.adapter)
        return slot_idx, first_token, n, lora_slot, lp_info

    def _prefix_bucket_prefill(self, req: Request, slot_idx: int, n: int,
                               lora_slot: int):
        """Bucketed admission over a cached prefix: map the cached blocks
        into the row's table (zero compute), then run ONE chunk program
        over the suffix — padded to the suffix's own bucket, attending to
        the mapped prefix KV through the page table.  A 256-token shared
        system prompt with a 32-token question prefills 32 tokens, not 288.
        Returns the ``_prefill_common`` tuple, or None when nothing cached
        matched (caller falls through to the plain bucketed program)."""
        reused = self._prefix_match_and_map(
            slot_idx, req.prompt_tokens, req.adapter,
            hashes=self._prefix_hashes_for(req))
        if reused == 0:
            return None
        try:
            self._paged_ensure(slot_idx, n)
        except PagedPoolExhausted:
            # The gate may have admitted on PLAIN-path feasibility (reuse
            # pinned the matched evictables and came up short).  Unwind the
            # map — the blocks return to the evictable LRU — and fall back
            # to the full-prompt program, which can evict them.
            self._paged_free_row(slot_idx)
            self.prefix_reused_tokens -= reused  # nothing was reused
            self._kv_note_reuse_unwind(req, reused)
            return None
        try:
            self._sync_tables()
            c = n - reused
            last_logits = self._chunk_dispatch(
                req, req.prompt_tokens[reused:], reused, self._bucket(c),
                slot_idx, n, lora_slot)
            self._prefix_register_row(slot_idx, req.prompt_tokens,
                                      req.adapter,
                                      hashes=self._prefix_hashes_for(req))
            first_token, lp_info = self._sample_first(req, last_logits, n)
        except BaseException:
            # Defensive: _paged_can_admit gated this admission (matched
            # blocks excluded from avail when pinned out of the evictable
            # LRU), so exhaustion here should not happen — but any failure
            # must not strand the mapped prefix refs or fresh suffix blocks
            # (the caller's cleanup only fires once it knows slot_idx).
            self._paged_free_row(slot_idx)
            self.prefix_reused_tokens -= reused  # nothing was reused
            self._kv_note_reuse_unwind(req, reused)
            raise
        return slot_idx, first_token, n, lora_slot, lp_info

    def _ring_usable(self, n: int) -> bool:
        """True when the sequence-parallel prefill path can take this prompt."""
        if self._ring is None:
            return False
        padded = -(-n // self._ring_pad) * self._ring_pad
        return padded <= self.cfg.max_seq_len

    @_in_phase("prefill.stage")
    def _ring_prefill(self, req: Request, n: int, lora_slot: int):
        """One sequence-parallel prefill program over the mesh ring.

        The prompt pads right to a multiple of ``_ring_pad`` (a bounded set
        of compiled shapes, like buckets); pad rows sit after the real
        tokens, so causal ring attention keeps real positions exact and the
        garbage tail is trimmed by the length-``n`` insert.
        """
        from llm_instance_gateway_tpu.parallel import long_context

        sp = req.sampling
        padded = -(-n // self._ring_pad) * self._ring_pad
        tokens = np.zeros((1, padded), np.int32)
        tokens[0, :n] = req.prompt_tokens
        positions = np.broadcast_to(
            np.arange(padded, dtype=np.int32), (1, padded))
        tok_d, pos_d = long_context.shard_inputs(
            self.mesh, jnp.asarray(tokens), jnp.asarray(positions))
        t0 = time.perf_counter()
        logits, k, v = self._ring(
            self.params, tok_d, pos_d,
            lora_bufs=self._lora_buffers(),
            slot_ids=jnp.full((1,), lora_slot, jnp.int32),
        )
        first_token, lp_info = self._sample_first(req, logits[0, n - 1], n)
        # The token stands for the program: it keeps [padded, V] logits
        # no longer than the sampler does.
        self._note_prompt_program("ring", padded, n, t0, first_token, req)
        return first_token, k, v, lp_info

    @_in_phase("prefill.stage")
    def _chunk_dispatch(self, req: Request, piece, start: int, chunk: int,
                        slot_idx: int, lane_end: int, lora_slot: int):
        """Stage one prompt piece, padded to ``chunk`` positions from
        ``start``, and enqueue the chunk program on lane ``slot_idx``;
        returns the logits after the piece's last real token."""
        c = len(piece)
        tokens = np.zeros((chunk,), np.int32)
        tokens[:c] = piece
        positions = start + np.arange(chunk, dtype=np.int32)
        args = (self.params, self.cache,
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.int32(slot_idx), jnp.int32(lane_end), jnp.int32(c - 1))
        t0 = time.perf_counter()
        with self._enqueue("engine.prefill.enqueue", req):
            last_logits, self.cache, moe = self._jit_chunk(
                *args, lora_bufs=self._lora_buffers(),
                lora_slot=jnp.int32(lora_slot))
        self._note_prompt_program("chunk", chunk, c, t0, last_logits, req)
        self._moe_keep(moe)
        return last_logits

    @_in_phase("prefill.stage")
    def _sample_first(self, req: Request, last_logits, n: int):
        """Enqueue the one-row sampler over a prompt's last logits."""
        sp = req.sampling
        return self._jit_sample_one(
            last_logits, self._next_key(), jnp.float32(sp.temperature),
            jnp.int32(sp.top_k), jnp.float32(sp.top_p),
            jnp.int32(_seed_i32(sp.seed)),
            jnp.int32(n - 1), *map(jnp.asarray, _bias_arrays(sp)))

    @_in_phase("prefill.stage")
    def _bucket_prefill(self, req: Request, n: int, lora_slot: int):
        """Pad a bucketable prompt and run the jitted prefill.
        Returns (first_token device scalar, k, v, lp_info)."""
        sp = req.sampling
        bucket = self._bucket(n)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = req.prompt_tokens
        positions = np.zeros((1, bucket), np.int32)
        positions[0, :n] = np.arange(n)
        args = (
            self.params, self._lora_buffers(),
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.int32(n), jnp.int32(lora_slot),
            jnp.float32(sp.temperature), jnp.int32(sp.top_k),
            jnp.float32(sp.top_p), self._next_key(),
            jnp.int32(_seed_i32(sp.seed)),
            *map(jnp.asarray, _bias_arrays(sp)),
        )
        t0 = time.perf_counter()
        with self._enqueue("engine.prefill.enqueue", req):
            *out, moe = self._jit_prefill(*args)
        self._note_prompt_program("prefill", bucket, n, t0, out[0], req)
        self._moe_keep(moe)
        return out

    @_in_phase("prefill.stage")
    def _bucket_prefill_many(self, reqs, ns, lora_slots):
        """One [P, bucket] prefill over same-bucket prompts.
        Returns (first_tokens [P] device, k [L,P,S,...], v, lp_infos)."""
        bucket = self._bucket(max(ns))
        p = len(reqs)
        tokens = np.zeros((p, bucket), np.int32)
        positions = np.zeros((p, bucket), np.int32)
        for i, (req, n) in enumerate(zip(reqs, ns)):
            tokens[i, :n] = req.prompt_tokens
            positions[i, :n] = np.arange(n)
        sps = [r.sampling for r in reqs]
        args = (
            self.params, self._lora_buffers(),
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(ns, jnp.int32), jnp.asarray(lora_slots, jnp.int32),
            jnp.asarray([sp.temperature for sp in sps], jnp.float32),
            jnp.asarray([sp.top_k for sp in sps], jnp.int32),
            jnp.asarray([sp.top_p for sp in sps], jnp.float32),
            self._next_key(),
            jnp.asarray([_seed_i32(sp.seed) for sp in sps],
                        jnp.int32),
            *(jnp.asarray(np.stack(arrs))
              for arrs in zip(*(_bias_arrays(sp) for sp in sps))),
        )
        t0 = time.perf_counter()
        with self._enqueue("engine.prefill.enqueue", *reqs):
            *out, moe = self._jit_prefill_many(*args)
        self._note_prompt_program(
            "prefill_many", p * bucket, sum(ns), t0, out[0], *reqs)
        self._moe_keep(moe)
        return out

    def _moe_keep(self, tally) -> None:
        """Park a program's routing counts (None: a dense model)."""
        if tally is not None:
            self._moe_pending.append(tally)

    def _moe_drain(self, tally) -> list:
        """The routing counts a decode dispatch takes to its readback: its
        own and those parked since the last one."""
        self._moe_keep(tally)
        drained, self._moe_pending = self._moe_pending, []
        return drained

    def _moe_account(self, fetched: list) -> None:
        """Book the routing counts a decode readback brought back."""
        if fetched:
            self.profiler.note_moe(np.sum(fetched, axis=0))

    def _collect_followers(self, first_req, limit: int) -> list:
        """Pull same-bucket followers of ``first_req`` for one batched
        prefill, up to ``limit`` total.  The first non-groupable pull parks
        as ``_pending`` — FIFO order holds."""
        group = [first_req]
        bucket = self._bucket(len(first_req.prompt_tokens))
        while len(group) < limit and self._pending is None:
            try:
                nxt = self.prefill_queue.get_nowait()
            except queue_mod.Empty:
                break
            if nxt.cancelled.is_set():
                self._finish(nxt, "cancelled")
                continue
            n = len(nxt.prompt_tokens)
            if n <= self._max_bucket() and self._bucket(n) == bucket:
                group.append(nxt)
            else:
                self._pending = nxt  # different bucket/long: next cycle
        return group

    def _collect_prefill_group(self, first_req) -> list:
        """Direct-admission grouping: bounded by free unreserved slots.

        Only the direct branch calls this (decode_wait empty, a free slot
        for the head), so every grouped request admits under exactly the
        checks the one-at-a-time path applied."""
        return self._collect_followers(first_req, min(
            self.cfg.prefill_batch,
            sum(1 for i, s in enumerate(self.slots)
                if s is None and i not in self._reserved_slots)))

    def _collect_ahead_group(self, first_req, cap: int) -> list:
        """Prefill-ahead grouping: bounded by decode_wait headroom."""
        return self._collect_followers(first_req, min(
            self.cfg.prefill_batch, max(1, cap - len(self.decode_wait))))

    def _park_waiting(self, req, first_token, lp_info, k, v, n: int,
                      lora_slot: int) -> None:
        """Park one prefilled row in decode_wait (the prefill-ahead
        contract: TTFT is prefill-bound, not slot-bound.  The first token
        is read with the others once the prefill is done,
        ``_read_first_tokens``)."""
        w = _WaitingPrefill(request=req, first_token=first_token,
                            lp_info=lp_info, k=k, v=v, n=n,
                            lora_slot=lora_slot)
        self._queue_first_token(req, first_token, lp_info)
        self.decode_wait.append(w)
        # Parked prompt KV pins real HBM ([L, 1, bucket, Kh, hd] per entry)
        # outside the decode cache — count the padded rows so the routing
        # signal sees the pressure (metrics_snapshot).
        self._parked_kv_tokens += w.k.shape[2]
        if self.kv_ledger is not None:
            self.kv_ledger.note_park(int(w.k.shape[2]), "prefill_ahead")
        self._usage_sync_kv()

    def _do_prefill_ahead_group(self, reqs) -> None:
        """Batched prefill-ahead: one program, every row parks in
        decode_wait (mirrors ``_do_prefill_ahead`` per row)."""
        batch = self._grouped_batch(reqs, self._do_prefill_ahead)
        if batch is None:
            return
        live, ns, lora_slots, k, v, tok_rows, lp_rows = batch
        for i, req in enumerate(live):
            try:
                self._park_waiting(
                    req, tok_rows[i], lp_rows[i],
                    k[:, i:i + 1], v[:, i:i + 1], ns[i], lora_slots[i])
            except Exception as e:
                logger.exception("grouped parking failed for %s",
                                 req.request_id)
                req.error = str(e)
                self._finish(req, "error")

    def _grouped_batch(self, reqs, single_fn):
        """Shared grouped-prefill preamble: filter cancelled/bad-adapter
        rows (each fails alone), fall back to ``single_fn`` for a group of
        one, run ONE batched prefill (a failure there fails the whole
        group — the engine-survives posture of the single path).

        Returns None when the caller has nothing left to do, else
        ``(live, ns, lora_slots, k, v, tok_rows, lp_rows)`` where the
        per-row token/logprob views hold device slices, with one async
        copy an array issued for the lot (``_HostBatch``).
        """
        live, ns, lora_slots = [], [], []
        for req in reqs:
            if req.cancelled.is_set():
                self._finish(req, "cancelled")
                continue
            try:
                lora_slots.append(
                    self.lora.slot_for(req.adapter)
                    if self.lora is not None else -1)
            except Exception as e:  # unknown adapter fails only this row
                req.error = str(e)
                self._finish(req, "error")
                continue
            live.append(req)
            ns.append(len(req.prompt_tokens))
        if not live:
            return None
        if len(live) == 1:
            single_fn(live[0])
            return None
        self._stamp_prefill_start(*live)
        try:
            first_tokens, k, v, (lps, top_vs, top_is) = (
                self._bucket_prefill_many(live, ns, lora_slots))
            # One async DMA per ARRAY (not per row); rows keep their
            # device slice for the carry scatter and materialize
            # host-side from the shared bulk transfer.
            hb = _HostBatch(first_tokens, lps, top_vs, top_is)
            tok_rows = [_Row(hb, 0, i, dev=first_tokens[i])
                        for i in range(len(live))]
            lp_rows = [(_Row(hb, 1, i), _Row(hb, 2, i), _Row(hb, 3, i))
                       for i in range(len(live))]
        except Exception as e:
            logger.exception("grouped prefill failed (%d reqs)", len(live))
            for req in live:
                req.error = str(e)
                self._finish(req, "error")
            return None
        return live, ns, lora_slots, k, v, tok_rows, lp_rows

    def _do_prefill_group(self, reqs) -> None:
        """Batched admission: one prefill program fills len(reqs) slots.
        Per-row post-processing mirrors ``_do_prefill``; a row that fails
        after the batched call fails alone."""
        batch = self._grouped_batch(reqs, self._do_prefill)
        if batch is None:
            return
        live, ns, lora_slots, k, v, tok_rows, lp_rows = batch
        pool_starved = False  # once a row parks on exhaustion, FIFO holds
        for i, req in enumerate(live):
            try:
                slot_idx = self._free_slot_index()
                if pool_starved:
                    slot_idx = None  # later rows must not overtake the parked one
                if slot_idx is None:
                    # Defensive: the free-slot count is taken at collection
                    # and the engine loop is single-threaded, so this should
                    # not happen — but a computed prefill must never be
                    # dropped.  Park it exactly like a prefill-ahead.
                    self._park_waiting(
                        req, tok_rows[i], lp_rows[i],
                        k[:, i:i + 1], v[:, i:i + 1], ns[i], lora_slots[i])
                    continue
                try:
                    self._insert_prompt_kv(
                        k[:, i:i + 1], v[:, i:i + 1], slot_idx, ns[i])
                except PagedPoolExhausted:
                    # The group outran the pool: this row (and, for FIFO,
                    # every later row) parks off-cache like a prefill-ahead
                    # and inserts when blocks free (_drain_decode_wait
                    # gates on _paged_can_admit).  _insert_prompt_kv
                    # already freed the partial row.
                    pool_starved = True
                    self._park_waiting(
                        req, tok_rows[i], lp_rows[i],
                        k[:, i:i + 1], v[:, i:i + 1], ns[i], lora_slots[i])
                    continue
                self._activate_slot(
                    slot_idx, req, lora_slots[i], ns[i],
                    tok_rows[i], lp_rows[i])
            except Exception as e:
                logger.exception("grouped admission failed for %s",
                                 req.request_id)
                req.error = str(e)
                self._finish(req, "error")

    @_in_phase("prefill.stage")
    def _insert_prompt_kv(self, k, v, slot_idx: int, n: int,
                          skip_leading_blocks: int = 0) -> None:
        """Write a bucketed prefill's KV into the cache (lane or paged).

        ``skip_leading_blocks`` (paged only) diverts that many leading
        blocks' positions to the trash block — the attach path's
        prefix-reuse composition, where those blocks are already mapped
        from the cache and must not be re-scattered."""
        if not self.paged:
            with self._enqueue("engine.prefill.enqueue"):
                self.cache = self._jit_insert(
                    self.cache, k, v, jnp.int32(slot_idx), jnp.int32(n)
                )
            return
        try:
            self._paged_ensure(slot_idx, n)
            bucket = k.shape[2]
            nb_bucket = -(-bucket // self._block)
            row_bl = self._row_blocks[slot_idx]
            # Wholly-padding bucket blocks scatter into the trash block.
            phys = row_bl + [paged_lib.TRASH_BLOCK] * (
                nb_bucket - len(row_bl))
            if skip_leading_blocks:
                phys = ([paged_lib.TRASH_BLOCK] * skip_leading_blocks
                        + phys[skip_leading_blocks:])
            self._sync_tables()
            self.cache = self._jit_insert(
                self.cache, k, v, jnp.int32(slot_idx),
                jnp.asarray(phys, jnp.int32),
                jnp.asarray(self._tables_host[slot_idx]),
                jnp.int32(n),
            )
        except Exception:
            # No slot is registered yet, so no _clear_slot will ever free
            # the row: an exhausted pool (the caller parks the row) or a
            # failed scatter (the caller fails the request) would strand
            # its blocks.
            self._paged_free_row(slot_idx)
            raise

    # ------------------------------------------------------------------
    # interleaved long-prompt streaming (one chunk per engine cycle)
    # ------------------------------------------------------------------

    def _lane_available(self, n_prompt: int) -> bool:
        """KV-pressure-aware lane admission: may a new chunk stream take a
        reserved lane now?  Bounded by ``stream_lanes``; lanes beyond the
        first additionally require the paged pool to keep a growth block
        per active decode row AFTER the stream's atomic whole-prompt
        allocation — a second 32k stream must unblock head-of-line waits,
        not starve running decode of its next block."""
        if len(self._streams) >= max(1, self.cfg.stream_lanes):
            return False
        if not self._streams or not self.paged:
            return True
        active = sum(1 for s in self.slots if s is not None)
        avail = len(self._free_blocks) + (
            len(self._evictable) if self._prefix_enabled else 0)
        return avail - self._paged_needed(n_prompt + 1) >= active

    def _start_stream(self, req: Request) -> bool:
        """Reserve a free lane and begin streaming a long prompt into it.

        The lane is held out of ``_free_slot_index`` (not a live slot, so
        decode steps skip it) and receives one chunk per ``_stream_step``
        pick (fair round-robin across up to ``stream_lanes`` concurrent
        streams).  Returns False only when the request was reparked for
        backpressure (caller must stop admitting this cycle).
        """
        if req.cancelled.is_set():
            self._finish(req, "cancelled")
            return True
        self._stamp_prefill_start(req)
        try:
            slot_idx = self._free_slot_index()
            lora_slot = (self.lora.slot_for(req.adapter)
                         if self.lora is not None else -1)
        except Exception as e:
            logger.exception("stream admission failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")
            return True
        self._reserved_slots.add(slot_idx)
        reused = 0
        if self.paged:
            # Allocate the WHOLE prompt's blocks now, atomically with the
            # _paged_can_admit gate the caller just passed (same engine
            # cycle, single thread): interleaved short-request admissions
            # and decode growth between chunks can no longer drain the pool
            # out from under a stream mid-flight.
            try:
                # Cached-prefix blocks map in first (refcounted, zero
                # compute); only the suffix gets fresh blocks and chunks.
                reused = self._prefix_match_and_map(
                    slot_idx, req.prompt_tokens, req.adapter)
                self._paged_ensure(slot_idx, len(req.prompt_tokens))
                self._sync_tables()
            except PagedPoolExhausted:
                # Defensive (the gate should prevent this): repark as the
                # head-of-line pending request and retry when blocks free.
                self._paged_free_row(slot_idx)
                self._reserved_slots.discard(slot_idx)
                self._pending = req
                return False
        self._streams.append(_ChunkStream(request=req, slot_idx=slot_idx,
                                          lora_slot=lora_slot,
                                          next_start=reused))
        return True

    def _abort_stream(self, st: _ChunkStream, reason: str) -> None:
        if st in self._streams:
            self._streams.remove(st)
        self._reserved_slots.discard(st.slot_idx)
        if self.paged:
            self._paged_free_row(st.slot_idx)
        self._finish(st.request, reason)

    def _stream_turn(self) -> None:
        """The chunk programs of one loop turn: one, or up to
        ``stream_burst`` of them back to back.  A stream that ends inside a
        burst frees its lane, so admission runs again and the next waiting
        prompt's first chunk can go out in the same turn."""
        for left in range(max(1, self.cfg.stream_burst), 0, -1):
            if not self._streams:
                return
            lanes = len(self._streams)
            self._stream_step()
            if left > 1 and len(self._streams) < lanes:
                self._admit_and_insert()

    @_in_phase("admit")
    def _stream_step(self) -> None:
        """Dispatch ONE chunk of ONE in-flight stream — the round-robin
        cursor rotates across lanes, so N concurrent long prompts advance
        fairly interleaved (one chunk per engine cycle total keeps the
        decode cadence unchanged versus a single lane), and decode blocks
        run between chunks, so streaming a 32k prompt does not freeze
        every active slot's TPOT.  On a stream's
        final chunk, sample the first token and activate its lane as a
        live decode slot."""
        if not self._streams:
            return
        self._stream_rr %= len(self._streams)
        st = self._streams[self._stream_rr]
        self._stream_rr += 1
        req = st.request
        if req.cancelled.is_set():
            self._abort_stream(st, "cancelled")
            return
        chunk = self._max_bucket()
        prompt = req.prompt_tokens
        n = len(prompt)
        start = st.next_start
        piece = prompt[start:start + chunk]
        c = len(piece)
        try:
            if self.paged:
                self._paged_ensure(st.slot_idx, start + c)
                self._sync_tables()
            st.last_logits = self._chunk_dispatch(
                req, piece, start, chunk, st.slot_idx, start + c,
                st.lora_slot)
        except Exception as e:  # engine must survive a poison request
            logger.exception("chunk stream failed for %s", req.request_id)
            req.error = str(e)
            self._abort_stream(st, "error")
            return
        st.next_start = start + c
        self._owes_parts(req)  # this chunk's
        if st.next_start < n:
            return  # more chunks; the loop decodes before the next one
        # Final chunk: publish the prompt's full blocks for prefix reuse,
        # sample the first token, then activate the lane as a live slot.
        if self.paged:
            self._prefix_register_row(st.slot_idx, prompt, req.adapter)
        self._streams.remove(st)
        self._reserved_slots.discard(st.slot_idx)
        slot_idx = st.slot_idx
        try:
            first_token, lp_info = self._sample_first(req, st.last_logits, n)
            self._activate_slot(
                slot_idx, req, st.lora_slot, n, first_token, lp_info)
        except Exception as e:
            logger.exception("stream activation failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")
            if self.paged and self.slots[slot_idx] is None:
                self._paged_free_row(slot_idx)

    @_in_phase("prefill.emit")
    def _register_slot(self, slot_idx: int, slot: _Slot) -> None:
        sp = slot.request.sampling
        self.slots[slot_idx] = slot
        self._slot_lora[slot_idx] = slot.lora_slot
        self._slot_temp[slot_idx] = sp.temperature
        self._slot_topk[slot_idx] = sp.top_k
        self._slot_topp[slot_idx] = sp.top_p
        self._slot_seed[slot_idx] = _seed_i32(sp.seed)
        self._slot_logprobs[slot_idx] = slot.request.logprobs is not None
        self._slot_presence[slot_idx] = sp.presence_penalty
        self._slot_frequency[slot_idx] = sp.frequency_penalty
        (self._slot_bias_ids[slot_idx],
         self._slot_bias_vals[slot_idx]) = _bias_arrays(sp)
        if sp.presence_penalty or sp.frequency_penalty:
            # Materialize + zero the row; the first-token count follows via
            # _count_first_token once the prefill's token is known.
            self._dev_counts = self._counts().at[slot_idx].set(0)
        # Budget for device-side stop: the prefill already produced token 1.
        self._slot_remaining[slot_idx] = max(0, slot.request.max_new_tokens - 1)
        self._program_stop_lanes(slot_idx, slot.request)
        self._usage_sync_kv()

    def _program_stop_lanes(self, slot_idx: int, req: Request) -> None:
        """Compile the request's stop suffixes into the row's device
        automaton lanes.  Custom single-token stop ids fold in as length-1
        sequences; anything that does not fit the static lanes — or
        ``device_stops`` off — leaves the lanes empty, and the host oracle
        in the result walk stays authoritative either way."""
        self._slot_stop_ids[slot_idx] = -1
        self._slot_stop_lens[slot_idx] = 0
        self._slot_stop_hist[slot_idx] = -1
        # Speculative engines: custom single-token stop ids keep the old
        # host-side seam (programming them would pause spec dispatch via
        # _stops_active for no freeze win — spec blocks host-trim anyway);
        # multi-token sequences DO program and exclude speculation.
        fold_ids = not self._spec
        if self.cfg.device_stops and (req.stop_sequences
                                      or (fold_ids and req.stop_token_ids)):
            enc = encode_stop_rows(
                [tuple(s) for s in req.stop_sequences]
                + ([(int(t),) for t in req.stop_token_ids]
                   if fold_ids else []))
            if enc is not None:
                ids, lens = enc
                self._slot_stop_ids[slot_idx] = ids
                self._slot_stop_lens[slot_idx] = lens
        self._stops_active = int(
            (self._slot_stop_lens.sum(axis=1) > 0).sum())

    def _record_ttft(self, req: Request) -> None:
        with self._lock:
            self.ttft_history.append(req.ttft_s)
            if len(self.ttft_history) > 1000:
                del self.ttft_history[:500]
            if req.t_prefill_start and req.t_first_token:
                # Pure prefill compute (queue wait excluded) — the
                # tpu:prefill_seconds exposition family.
                self.phase_hist["prefill"].observe(
                    max(0.0, req.t_first_token - req.t_prefill_start))
        if req.t_prefill_start and req.t_first_token:
            # Attribution: the prefill's wall charged whole to its owner
            # (grouped prefills charge each rider the shared program wall
            # — per-request compute-seconds, the same accounting the
            # engine-total conservation denominator accumulates), prompt
            # tokens counted as phase=prefill.
            self.usage.charge_step(
                "prefill",
                max(0.0, req.t_first_token - req.t_prefill_start),
                [req.adapter],
                tokens={owner_key(req.adapter): len(req.prompt_tokens)})
            self.profiler.note_dispatch(
                "prefill", req.t_prefill_start_pc,
                max(0.0, req.t_first_token - req.t_prefill_start),
                active=1, total_slots=self.cfg.decode_slots,
                n_steps=len(req.prompt_tokens))
            self._owes_parts(req)

    def _kv_ledger_sync(self) -> None:
        """Recount the KV ledger's block states from allocator ground
        truth (free list, DISTINCT blocks across row tables — a shared
        prefix block mapped into several rows is one block — and the
        evictable LRU).  Recounting rather than deriving is what makes
        the ledger's conservation sum a leak detector.  Rides the same
        sites as ``_usage_sync_kv``; ``metrics_snapshot`` also calls it
        so a scrape is never staler than the last dispatch."""
        led = self.kv_ledger
        if led is None:
            return
        distinct: set[int] = set()
        for blocks in self._row_blocks:
            distinct.update(blocks)
        led.sync_states(self._free_blocks, len(distinct),
                        len(self._evictable), self._parked_kv_tokens)
        if self._kv_evicts_pending:
            n, self._kv_evicts_pending = self._kv_evicts_pending, 0
            if self.event_sink is not None:
                self.event_sink("kv_evict", n=n)

    def _usage_sync_kv(self) -> None:
        """Refresh the attribution tracker's KV-holdings integral (engine
        thread): active slot rows at their current position, parked
        ``decode_wait`` KV at its padded size (the same HBM the
        ``kv_parked_tokens`` gauge counts), and the in-flight chunk
        stream's filled prefix.  The KV ledger's state recount rides the
        same call sites."""
        self._kv_ledger_sync()
        holdings: list[tuple[str | None, int]] = [
            (s.request.adapter, s.position)
            for s in self.slots if s is not None]
        holdings += [(w.request.adapter, w.k.shape[2])
                     for w in self.decode_wait]
        holdings += [(st.request.adapter, st.next_start)
                     for st in self._streams if st.next_start > 0]
        self.usage.sync_kv(holdings)

    def _account_dispatch(self, kind: str, t0: float, step_s: float,
                          owners: list, tok_by_owner: dict[str, int],
                          n_tokens: int, n_steps: int) -> None:
        """End-of-dispatch bookkeeping of every decode dispatch (plain or
        speculative), under the caller's ``decode.account`` phase.
        ``tpu:decode_step_seconds`` observes ``step_s / n_steps``.
        ``tpu:dispatch_steps`` records
        the PLANNER's power-of-two choices, so only ``kind == "decode"``
        observes it: a speculative block's token-row count is not one."""
        self.usage.charge_decode(step_s, owners, tok_by_owner)
        self._usage_sync_kv()
        self._settle_admissions()
        self.profiler.note_dispatch(
            kind, t0, step_s, active=len(owners),
            total_slots=self.cfg.decode_slots, n_steps=n_steps)
        with self._lock:
            self.total_generated += n_tokens
            inst = n_tokens / step_s if step_s > 0 else 0.0
            self.decode_tps_ema = ((1 - TPS_EMA_ALPHA) * self.decode_tps_ema
                                   + TPS_EMA_ALPHA * inst)
            self.phase_hist["decode_step"].observe(
                step_s / max(1, n_steps))
            if kind == "decode":
                self.dispatch_steps_hist.observe(n_steps)

    def observe_handoff(self, seconds: float) -> None:
        """Record one handoff-plane operation (serialize on the prefill
        side; deserialize+attach admission on the decode side) into
        tpu:handoff_seconds.  Called from the HTTP layer."""
        with self._lock:
            self.phase_hist["handoff"].observe(max(0.0, seconds))

    def _stamp_prefill_start(self, *reqs: Request) -> None:
        """Queue wait ends / prefill compute begins (first stamp wins)."""
        self._settle_admissions()
        now, now_pc = time.time(), time.perf_counter()
        rows = None
        for r in reqs:
            if not r.t_prefill_start:
                r.t_prefill_start, r.t_prefill_start_pc = now, now_pc
                if rows is None:
                    rows = sum(s is not None for s in self.slots)
                n = len(r.prompt_tokens)
                r.prefill_attrs.update(
                    prompt_tokens=n, rows=rows,
                    bucket=self._bucket(min(n, self._max_bucket())))

    def _owes_parts(self, req: Request) -> None:
        """``req`` had prompt work since the last settle: the phase parts
        handed out next are (also) its."""
        if req not in self._unsettled:
            self._unsettled.append(req)

    def _settle_admissions(self) -> None:
        """Hand the requests whose first token came since the last call
        what the phase stack charged to ``prefill.*`` meanwhile (engine
        thread, outside any prefill phase: before the next admission, in a
        decode dispatch's accounting, before the loop waits).  Requests
        admitted by one program share its parts; a prompt streamed in
        chunks is handed each chunk's as it goes (``_stream_step``) and
        carries their sum."""
        if self._first_unread:
            # Between an admission's staging and the reading of its first
            # token: its parts are not whole yet.
            return
        parts = self.profiler.take_prefill_split()
        for req in self._unsettled:
            attrs = req.prefill_attrs
            for key, seconds in parts.items():
                attrs[key] = round(attrs.get(key, 0.0) + seconds, 9)
        self._unsettled.clear()

    def _store_logprobs(self, req: Request, lp, top_v, top_i) -> None:
        """Record a token's logprob info iff the request asked for it."""
        if req.logprobs is None:
            return
        req.output_logprobs.append(float(lp))
        if req.logprobs > 0:
            kk = min(req.logprobs, len(top_i))
            req.output_top_logprobs.append(
                {int(top_i[j]): float(top_v[j]) for j in range(kk)})

    @_in_phase("prefill.emit")
    def _emit_first_token(self, req: Request, tok: int,
                          lp_info=None) -> bool:
        """Record the prefill's first sampled token (TTFT, stream, counters);
        True if that token already finishes the request."""
        req.t_first_token = time.time()
        req.output_tokens.append(tok)
        if lp_info is not None:
            lp, top_v, top_i = lp_info
            self._store_logprobs(req, np.asarray(lp),
                                 np.asarray(top_v), np.asarray(top_i))
        _publish(req)
        with self._lock:
            self.total_generated += 1
        self._record_ttft(req)
        if self._is_finished(req, tok):
            self._finish(req, "stop" if self._is_stop(req, tok) else "length")
            return True
        return False

    def _paged_ensure_decode(self, n_steps: int,
                             per_row_steps: list[int] | None = None) -> None:
        """Pre-dispatch block growth for every active row.

        The host position lags the device by the IN-FLIGHT dispatch, so
        the reservation is previous-dispatch-steps + this
        dispatch's steps — dispatch sizes vary when speculative blocks
        (cycles x (K+1) writes, including rejected tails) interleave with
        plain blocks, so a flat 2*n_steps would under-reserve after a
        larger block and route in-flight KV writes to the trash block.
        ``per_row_steps`` narrows the reservation per row (a sampled row
        in a speculative block advances one token per cycle, so its write
        frontier is cycles+K, not cycles*(K+1) — reserving the worst case
        for every row would make tight pools fail requests speculation-off
        would serve).  Over-reservation is returned at free.  A row the
        exhausted pool cannot grow fails with "kv pool exhausted" (the
        documented oversubscription tradeoff) without touching the batch.
        """
        # Recorded for BOTH cache layouts: the paged reservation below
        # needs it, and _plan_steps subtracts it from the host-lagged
        # remaining budgets on lanes too.
        prev, self._prev_dispatch_steps = self._prev_dispatch_steps, n_steps
        if not self.paged:
            return
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            row_steps = (per_row_steps[i] if per_row_steps is not None
                         else n_steps)
            target = min(slot.position + row_steps + prev + 1,
                         self.cfg.max_seq_len)
            try:
                self._paged_ensure(i, target)
            except PagedPoolExhausted as e:
                req = slot.request
                logger.warning("kv pool exhausted; failing %s", req.request_id)
                req.error = str(e)
                self._finish(req, "error")
                self._clear_slot(i)
        self._sync_tables()

    # ------------------------------------------------------------------
    # the loop: host readback overlaps the next device block
    # ------------------------------------------------------------------

    def _loop(self) -> None:
        """The overlapped order: block N+1 is dispatched from the
        device-resident token/position/budget carry BEFORE block N's
        tokens are read, so the device never waits for the host between
        two decode steps: readback, emit, accounting, admission, planning
        and staging of a step all run while it computes the next one.

        One turn of the loop, with block N in flight: admit (a prefill is
        enqueued behind N), dispatch N+1 (behind the prefill), read N
        (wait, readback, emit, account), read the admitted requests' first
        tokens (the prefill is done about when N's walk is; N+1 is queued
        behind it, so the device does not idle while the host reads).

        Consequences handled here:
        - stop detection is device-side (budget/EOS freeze rows and emit
          invalid steps), so a finishing slot wastes no trimmed tokens; a
          row freed for a reason only the host sees (custom stop ids, the
          length cap, a cancellation) is staged with budget 0 and the next
          block zeroes it in the carry (``_stage_carry``);
        - slot FREEING lags one block (the frozen row just decodes invalid
          steps until the host sees the stop);
        - a prefill's first token stays on the device until the prefill is
          done, and leaves then, not with its slot's first block
          (``_read_first_tokens``).
        """
        while self._running:
            did_work = self._admit_and_insert()
            if self._streams:
                self._stream_turn()
                did_work = True
            block = None
            if any(s is not None for s in self.slots):
                try:
                    block = self._dispatch_block()
                except Exception as e:
                    logger.exception("decode dispatch failed")
                    self._fail_all_slots(e)
                did_work = True
            if self._inflight is not None:
                try:
                    self._process_block(self._inflight, current=block)
                except Exception as e:
                    # Async JAX errors surface at materialization, not at
                    # dispatch: the engine must survive; fail the batch.
                    logger.exception("block materialization failed")
                    self._fail_all_slots(e)
                    block = None
                did_work = True
            if self._first_unread:
                self._read_first_tokens(current=block)
                did_work = True
            self._inflight = block
            if block is None:
                self._prev_dispatch_steps = 0
            if not did_work:
                self._wait_for_work()
        if self._inflight is not None:
            try:
                self._process_block(self._inflight, current=None)
            except Exception as e:
                logger.exception("final block materialization failed")
                self._fail_all_slots(e)
            self._inflight = None

    def _read_first_tokens(self, current: dict | None) -> None:
        """Read the first tokens still on the device, in the order their
        prefills were enqueued, and hand each to its request: the point at
        which a streamed request's first chunk leaves.  The thread waits
        here (``prefill.wait``) for a prefill that is not done; block
        ``current`` is queued behind it meanwhile.  A request that finishes
        with this token (``max_tokens`` 1, a stop) gives its slot, or its
        place in decode_wait, back at once; its lane in ``current`` is
        garbage."""
        unread, self._first_unread = self._first_unread, []
        for req, first_token, lp_info, prompts in unread:
            if req.done.is_set():
                continue  # cancelled or failed since
            try:
                # Its prompt programs first: their interval ends where the
                # last of them does, not where the token's copy lands.
                self._prompt_programs_done(prompts)
                with self._phase("prefill.wait"):
                    tok = int(np.asarray(first_token))
                    if lp_info is not None:
                        lp_info = tuple(np.asarray(a) for a in lp_info)
                self._last_done_pc = time.perf_counter()
                finished = self._emit_first_token(req, tok, lp_info)
            except Exception as e:  # a prefill's async error surfaces here
                logger.exception("first token of %s failed", req.request_id)
                req.error = str(e)
                self._finish(req, "error")
                finished = True
            waiting = next(
                (w for w in self.decode_wait if w.request is req), None)
            if waiting is not None:
                waiting.first_emitted = True
                waiting.lp_info = None
                if finished:  # done at its first token: it needs no slot
                    self.decode_wait.remove(waiting)
                    self._parked_kv_tokens -= waiting.k.shape[2]
                    if self.kv_ledger is not None:
                        self.kv_ledger.note_unpark(int(waiting.k.shape[2]))
                    self._usage_sync_kv()
                continue
            if not finished:
                continue
            for i, slot in enumerate(self.slots):
                if slot is not None and slot.request is req:
                    self._clear_slot(i)
                    if current is not None and current["rows"][i] is slot:
                        current["rows"][i] = None
        self._settle_admissions()

    def _fail_all_slots(self, e: Exception) -> None:
        for i, slot in enumerate(self.slots):
            if slot is not None:
                slot.request.error = str(e)
                self._finish(slot.request, "error")
                self._clear_slot(i)

    def _do_prefill(self, req: Request) -> None:
        """Prefill + insert with NO synchronous readback: the first token is
        scattered into the device carry and async-copied; the loop reads it
        once the prefill is done (``_read_first_tokens``)."""
        if req.cancelled.is_set():  # died while queued: skip the prefill
            self._finish(req, "cancelled")
            return
        slot_idx = None
        registered = False
        try:
            slot_idx, first_token, n, lora_slot, lp_info = (
                self._prefill_common(req))
            # t_first_token is stamped when the token MATERIALIZES in
            # _read_first_tokens: stamping here would understate TTFT by
            # the prefill program and the block it is queued behind.
            self._activate_slot(
                slot_idx, req, lora_slot, n, first_token, lp_info)
            registered = True
        except Exception as e:  # engine must survive a poison request
            logger.exception("prefill failed for %s", req.request_id)
            req.error = str(e)
            self._finish(req, "error")
        finally:
            if self.paged and slot_idx is not None and not registered:
                self._paged_free_row(slot_idx)  # don't strand a slot-less row

    @_in_phase("decode.plan", hand_over=True)
    def _dispatch_block(self, ph) -> dict:
        """Stage and enqueue the next block from the device carry, whether
        or not the block before it has been read (``_inflight``: the
        mechanism of the overlapped order, counted in
        ``tpu:decode_blocks_overlapped_total``)."""
        if self._inflight is not None:
            self.profiler.note_overlapped_block()
        # Stop-automaton rows exclude speculative dispatch: the spec
        # block does not evaluate the suffix automata, so its history
        # carry would go stale; plain fused blocks serve the batch until
        # those rows finish.  Nor is it worth its draft and verify where
        # no row can accept proposals (all sampled or stream-admitted).
        if self._spec and not self._stops_active and any(
            s is not None and self._spec_ok[i] and self._slot_temp[i] <= 0.0
            for i, s in enumerate(self.slots)
        ):
            return self._dispatch_spec_block(ph)
        n_steps = self._plan_steps()
        self._paged_ensure_decode(n_steps)
        ph.to("decode.stage")
        t0 = time.perf_counter()
        # A block staged with no row that asked for logprobs computed none
        # (``_logprobs_if_asked``): its three arrays of zeros stay on the
        # device, neither fetched nor walked.
        asked = self._slot_logprobs.any()
        (toks, valid, *lp, paths), carry, moe = (
            self._enqueue_decode(
                n_steps, (self._dev_tokens, self._dev_positions,
                          self._dev_remaining, self._dev_stop_hist)))
        (self._dev_tokens, self._dev_positions, self._dev_remaining,
         self._dev_stop_hist) = carry
        # The sampler's paths ride with the routing counts: small arrays
        # the block's one readback brings back beside the tokens.
        tail = [paths, *moe]
        lp = tuple(lp) if asked else ()
        for arr in (toks, valid, *lp, *tail):
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass
        return {
            "tail": tail,
            "toks": toks,
            "valid": valid,
            "lp": lp,  # (lps, top_v, top_i), or () where no row asked
            "rows": list(self.slots),  # request refs valid at dispatch time
            "n_steps": n_steps,
            "t0": t0,
            "prompts": self._prompt_enqueued,
        }

    def _dispatch_spec_block(self, ph) -> dict:
        """Speculative dispatch: same block contract as the plain
        path — flattened [T, B] outputs plus device carries — so
        ``_process_block`` consumes it unchanged.  The draft-extra triple
        rides the device carry; between spec and plain blocks (e.g. the
        only greedy row finished) extras may go stale, which degrades
        proposal quality for a cycle but never correctness: the target's
        verify is exact regardless of what the draft proposes."""
        k = self.cfg.speculative_k
        n_cycles = self._spec_cycles_per_sync()
        # Paged: every position a cycle can write (accepted or rejected)
        # must have a real block before dispatch.
        self._paged_ensure_decode(
            n_cycles * (k + 1),
            per_row_steps=self._spec_row_steps(n_cycles, k))
        ph.to("decode.stage")
        t0 = time.perf_counter()
        # What the plain block does inside its program: freed rows' budgets
        # zeroed, activated rows' positions and budgets taken as staged.
        staged = self._slots_i32.copy()
        (self._dev_tokens, self._dev_positions, self._dev_remaining,
         self._dev_stop_hist) = self._jit_stage_carry(
            (self._dev_tokens, self._dev_positions, self._dev_remaining,
             self._dev_stop_hist), staged)
        self._slot_fresh[:] = 0
        # The verify reads the adapters by the plain block's rule, from
        # the slots that go up with it.
        lora_bufs, targets, _ = self._block_lora_buffers(staged)
        args = (
            self.params, self.draft_params, lora_bufs,
            self.cache, self.draft_cache,
            self._dev_tokens, self._dev_positions, self._dev_remaining,
            self._dev_extra_tok, self._dev_extra_pos, self._dev_has_extra,
            jnp.asarray(self._spec_ok),
            jnp.asarray(self._slot_temp), jnp.asarray(self._slot_topk),
            jnp.asarray(self._slot_topp), self._next_key(),
            jnp.asarray(staged[self._lora_span]), self._eos_for_device,
            jnp.asarray(self._slot_seed),
        )
        with self._enqueue("engine.decode.enqueue"):
            (toks, valid, lps, top_v, top_i, next_tokens, next_positions,
             next_remaining, next_etok, next_epos, next_has,
             self.cache, self.draft_cache) = self._traced(
                self._jit_spec_block, 2, targets, *args,
                n_cycles=n_cycles, k_steps=k)
        self._dev_tokens = next_tokens
        self._dev_positions = next_positions
        self._dev_remaining = next_remaining
        self._dev_extra_tok = next_etok
        self._dev_extra_pos = next_epos
        self._dev_has_extra = next_has
        self.spec_cycles += n_cycles
        for arr in (toks, valid, lps, top_v, top_i):
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass
        return {
            "toks": toks,
            "valid": valid,
            "lp": (lps, top_v, top_i),
            "rows": list(self.slots),
            "n_steps": n_cycles * (k + 1),
            "t0": t0,
            "prompts": self._prompt_enqueued,
            "spec": True,
        }

    @_in_phase("decode.wait", hand_over=True)
    def _process_block(self, blk: dict, current: dict | None, ph) -> None:
        """Materialise and walk block ``blk`` while ``current`` computes:
        wait (the time the thread really blocks on the device), readback,
        emit, account.

        The step the block books (``tpu:decode_step_seconds``, the
        profiler's wall) is the interval between two completions on the
        device's queue: from the later of this block's staging and the
        last thing the loop saw complete (the block before, or a prompt
        program: a prefill read since, a chunk ahead of this block) to this
        block's completion.  With blocks overlapped
        that is the cadence at which a row's tokens become available, one
        device step; for a block staged on an idle device it is stage +
        wait."""
        # The prompt programs enqueued before the block are ahead of it on
        # the device's queue (a chunk that was not its prompt's last is
        # awaited nowhere else): the thread waits for them first and in all
        # no longer, and the block's step starts where they end.
        self._prompt_programs_done(blk["prompts"])
        outs = jax.block_until_ready(
            (blk["toks"], blk["valid"], *blk["lp"], *blk.get("tail", ())))
        if "done" in blk:  # seen complete while the host staged prompts
            t0, done = blk["start"], blk["done"]
        else:
            done = time.perf_counter()
            t0 = max(blk["t0"], self._last_done_pc)
            self._last_done_pc = done
        step_s = done - t0
        ph.to("decode.readback")
        # [n_steps, B] each.  One device_get for the lot: the copies start
        # together and the thread waits once, where one np.asarray per
        # array waited in turn (2.3-2.4 ms a step for five on the v5e
        # host; ledger, PR 25).  The logprob triplet comes where a row of
        # the block asked.  A plain block's tail is its sampler paths, then
        # routing counts; a speculative block has none.
        toks_np, valid_np, *rest = jax.device_get(outs)
        lp_np, tail = rest[:len(blk["lp"])], rest[len(blk["lp"]):]
        if tail:
            self.profiler.note_sample_paths(tail[0])
            self._moe_account(tail[1:])
        ph.to("decode.emit")
        n_tokens = 0
        # Attribution owners = every row resident at DISPATCH time (they
        # all shared this block's wall); tokens counted per owner below.
        owners = [s.request.adapter for s in blk["rows"] if s is not None]
        tok_by_owner: dict[str, int] = {}
        for i, slot in enumerate(blk["rows"]):
            if slot is None:
                continue
            req = slot.request
            if req.done.is_set():
                continue
            if req.cancelled.is_set():
                self._finish(req, "cancelled")
                if self.slots[i] is slot:
                    self._clear_slot(i)
                if current is not None and current["rows"][i] is slot:
                    current["rows"][i] = None
                continue
            finished = False
            row_tokens = 0
            for k in range(blk["n_steps"]):
                if not valid_np[k, i]:
                    continue  # device froze this row (budget/EOS)
                tok = int(toks_np[k, i])
                req.output_tokens.append(tok)
                if lp_np:
                    self._store_logprobs(req, *(a[k, i] for a in lp_np))
                # Per-step emission: each token of the fused block is
                # published to the stream consumer as it lands in the
                # trim walk, not once per dispatch — an SSE reader wakes
                # per token instead of per burst.
                _publish(req)
                n_tokens += 1
                row_tokens += 1
                slot.position += 1
                if (
                    self._is_finished(req, tok)
                    or slot.position >= self.cfg.max_seq_len - 1
                ):
                    finished = True
                    break
            if row_tokens:
                key = owner_key(req.adapter)
                tok_by_owner[key] = tok_by_owner.get(key, 0) + row_tokens
            _publish(req)
            if finished:
                self._finish(req, "stop" if self._is_stop(req, req.output_tokens[-1])
                             else "length")
                if self.slots[i] is slot:
                    # Host-only stop reasons (custom ids, length cap) leave
                    # a positive device budget: the cleared row's staged
                    # budget of 0 zeroes it in the next block.
                    self._clear_slot(i)
                if current is not None and current["rows"][i] is slot:
                    current["rows"][i] = None  # its lane in-flight is garbage
        ph.to("decode.account")
        if blk.get("spec"):
            self.spec_emitted += n_tokens
        self._account_dispatch("spec" if blk.get("spec") else "decode",
                               t0, step_s, owners, tok_by_owner,
                               n_tokens, blk["n_steps"])

    def _is_stop(self, req: Request, tok: int) -> bool:
        """Host stop oracle, evaluated once per emitted token in the
        post-dispatch walk: EOS / custom stop ids / multi-token stop
        sequences against the output tail.  The device automaton freezes
        rows by the SAME rule mid-block; this check is what actually
        finishes the request, so device/host agreement is structural."""
        if tok == self.eos_id or tok in req.stop_token_ids:
            return True
        if req.stop_sequences:
            out = req.output_tokens
            for seq in req.stop_sequences:
                n = len(seq)
                if n and len(out) >= n and tuple(out[-n:]) == tuple(seq):
                    return True
        return False

    def _is_finished(self, req: Request, tok: int) -> bool:
        return self._is_stop(req, tok) or len(req.output_tokens) >= req.max_new_tokens

    def _finish(self, req: Request, reason: str) -> None:
        if req.done.is_set():
            return  # idempotent: a request finishes (and releases) once
        req.finish_reason = reason
        req.t_done = time.time()
        with self._lock:
            self._live.pop(req.request_id, None)
        # Release BEFORE signalling done: a caller that wakes on done and
        # immediately unloads the adapter must not see a stale pin.
        if req.adapter is not None and self.lora is not None:
            self.lora.release(req.adapter)
        _publish(req)
        req.done.set()
