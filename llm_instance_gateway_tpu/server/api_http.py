"""Model-server HTTP API: OpenAI-style inference + admin + metrics.

The surface the gateway (and the LoRA sidecar) expects from a pool replica —
the union of what vLLM exposed to the reference:

- ``POST /v1/completions``        OpenAI completions (prompt string or token ids)
- ``POST /v1/chat/completions``   chat (checkpoint tokenizer's own chat
                                  template when it ships one, else a
                                  role-prefix transcript)
- ``GET  /v1/models``             base model + resident adapters (sidecar diff
                                  source, ``sidecar.py:140-155``)
- ``POST /v1/load_lora_adapter``  ``{"lora_name": ..., "lora_path": ...}``
                                  (vLLM-compatible field names, sidecar.py:177-195)
- ``POST /v1/unload_lora_adapter`` ``{"lora_name": ...}``
- ``GET  /metrics``               tpu:* exposition (gateway scrape contract,
                                  including the tpu:prefill_seconds /
                                  tpu:handoff_seconds /
                                  tpu:decode_step_seconds histograms)
- ``GET  /debug/traces``          recent request traces (span JSON,
                                  ``?trace_id=`` filter, ``?since=<seq>``
                                  incremental cursor — the fleet
                                  collector's delta poll)
- ``GET  /debug/events``          replica-side flight recorder (admission
                                  rejections, handoff refusals, drain
                                  transitions; ``?since=`` cursor)
- ``GET  /debug/usage``           per-adapter capacity attribution snapshot
                                  (step-seconds / tokens / KV block-seconds
                                  per {adapter, phase} + pool waste;
                                  server/usage.py)
- ``GET  /debug/profile``         step-timeline profiler snapshot (per-
                                  dispatch wall / host-sync gap / idle
                                  attribution + recent dispatch records;
                                  server/profiler.py, rendered by
                                  tools/profile_report.py)
- ``GET  /debug/device``          where this replica runs, as JAX reports it
                                  (platform, device_kind, device count,
                                  per-device ``memory_stats``) and what it
                                  serves (model widths, weight bytes by
                                  dtype) — what ``chip_smoke.py`` checks
- ``GET  /health``                200 once the engine loop is up

Tracing: every inference request adopts the ``x-lig-trace-id`` header (or
mints one), records engine-phase spans (queue wait, prefill, decode, handoff
serialize/deserialize/attach) into a bounded ring, echoes the id on every
response, and returns its spans in a compact ``x-lig-spans`` header so the
gateway proxy can merge the cross-process timeline into one trace.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import queue as queue_mod
import time

from aiohttp import web

from llm_instance_gateway_tpu.server import metrics as metrics_mod
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineDraining,
    MAX_LOGIT_BIAS,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.lora_manager import (
    AdapterBusyError,
    AdapterError,
    LoRAManager,
)
from llm_instance_gateway_tpu.server.tokenizer import load_tokenizer
from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu import tracing

logger = logging.getLogger(__name__)

MAX_N = 8          # n / best_of cap (each candidate occupies engine capacity)
MAX_LOGPROBS = 5   # engine.LOGPROB_TOPK — the OpenAI completions maximum
# OpenAI chat accepts top_logprobs up to 20; the engine computes a top-5
# device-side (LOGPROB_TOPK), so requests above MAX_LOGPROBS are accepted
# and truncated, with the cap noted in the response's logprobs object
# (README "OpenAI surface divergences").
OPENAI_MAX_TOP_LOGPROBS = 20

_FFFD = "�"
# A partial UTF-8 character pending completion by a later byte-fallback
# token is at most 3 bytes (a 4-byte sequence missing its last byte); its
# decode renders at most that many replacement chars, so a longer trailing
# U+FFFD run is genuine model output, never an artifact.
_MAX_PARTIAL_FFFD = 3


class ModelServer:
    def __init__(self, engine: Engine, tokenizer, model_name: str,
                 lora_manager: LoRAManager | None = None,
                 aliases: set[str] | None = None):
        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        # Extra names the base model answers to (e.g. the CLI preset alias
        # when a checkpoint brought its own name) — existing clients keep
        # working across a checkpoint swap.
        self.aliases = {model_name} | (aliases or set())
        self.lora = lora_manager
        # Per-process span ring served by /debug/traces (tracing.py).
        self.tracer = tracing.Tracer()
        # This process's stall clock (tpu:loop_*), run by build_app's app.
        self.loop_clock = tracing.LoopClock()
        # Emit-to-write lag of the streamed chunks: seconds from the engine
        # publishing a token (Request.t_emit) to resp.write returning, and
        # the chunks counted (tpu:stream_write_lag_seconds_total /
        # tpu:stream_chunks_total).  Written on the loop's thread only.
        self.stream_write_lag_s = 0.0
        self.stream_chunks = 0
        # Server-side flight recorder (events.py): admission rejections,
        # handoff failures, drain/role changes — served by /debug/events
        # and counted in the tpu:events_total family on /metrics.
        self.events = events_mod.EventJournal()
        if engine is not None and hasattr(engine, "event_sink"):
            # The engine reports lifecycle events (drain start) through
            # this seam without importing any HTTP-layer machinery.
            engine.event_sink = self.events.emit

    def build_app(self) -> web.Application:
        # Deterministic fault injection (gateway/faultinject.py): the
        # LIG_FAULTS env var names a JSON schedule; the middleware applies
        # blackhole/brownout/error/disconnect faults to /v1/* handlers so
        # the 3-process e2e chaos stack exercises the REAL server binary.
        middlewares = []
        faults_path = os.environ.get("LIG_FAULTS")
        if faults_path:
            from llm_instance_gateway_tpu.gateway import faultinject

            schedule = faultinject.FaultSchedule.from_file(faults_path)
            middlewares.append(
                faultinject.aiohttp_middleware(schedule,
                                               journal=self.events))
            logger.warning("fault injection armed from %s: %s",
                           faults_path, schedule.describe())
        app = web.Application(middlewares=middlewares)
        app.cleanup_ctx.append(self._run_loop_clock)
        app.router.add_post("/v1/completions", self.handle_completions)
        app.router.add_post("/v1/chat/completions", self.handle_chat)
        # Cross-engine disaggregation hops (gateway/proxy.py two-hop relay).
        app.router.add_post("/v1/prefill", self.handle_prefill)
        app.router.add_post("/v1/attach", self.handle_attach)
        app.router.add_post("/v1/prefill/release", self.handle_release)
        app.router.add_get("/v1/models", self.handle_models)
        app.router.add_post("/v1/load_lora_adapter", self.handle_load_adapter)
        app.router.add_post("/v1/unload_lora_adapter", self.handle_unload_adapter)
        # Residency-ladder verbs (placement plane, server/lora_manager.py):
        # demote = slot -> host RAM, prefetch = disk -> host RAM (no slot),
        # evict = host RAM -> disk.  The lora_sidecar's planner mode drives
        # these from the gateway's /debug/placement decisions.
        app.router.add_post("/v1/demote_lora_adapter", self.handle_demote_adapter)
        app.router.add_post("/v1/prefetch_lora_adapter", self.handle_prefetch_adapter)
        app.router.add_post("/v1/evict_lora_adapter", self.handle_evict_adapter)
        app.router.add_get("/metrics", self.handle_metrics)
        app.router.add_get("/debug/traces", self.handle_debug_traces)
        app.router.add_get("/debug/events", self.handle_debug_events)
        app.router.add_get("/debug/usage", self.handle_debug_usage)
        app.router.add_get("/debug/profile", self.handle_debug_profile)
        app.router.add_get("/debug/kv", self.handle_debug_kv)
        app.router.add_get("/debug/device", self.handle_debug_device)
        app.router.add_get("/health", self.handle_health)
        return app

    async def _run_loop_clock(self, app):
        task = asyncio.get_running_loop().create_task(self.loop_clock.run())
        yield
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)

    # -- tracing helpers ----------------------------------------------------
    @staticmethod
    def _trace_id_for(request: web.Request) -> str:
        return (tracing.header_trace_id(request.headers)
                or tracing.new_trace_id())

    @staticmethod
    def _engine_spans(req, decode_start: float | None = None,
                      with_decode: bool = True) -> list:
        """Span triples derived from a finished engine Request's wall-clock
        stamps: queue wait (submit -> prefill start), prefill compute
        (prefill start -> first token), decode (first token -> done).
        ``decode_start`` overrides the decode span's start (attach path:
        the first token predates THIS engine; decode begins at attach)."""
        spans = []
        if req.t_submit and req.t_prefill_start:
            spans.append(("engine.queue_wait", req.t_submit,
                          max(req.t_submit, req.t_prefill_start)))
        if req.t_prefill_start and req.t_first_token:
            spans.append(("engine.prefill", req.t_prefill_start,
                          max(req.t_prefill_start, req.t_first_token)))
        if with_decode:
            start = decode_start or req.t_first_token
            end = req.t_done or time.time()
            if start and end >= start:
                spans.append(("engine.decode", start, end))
        return spans

    def _record_spans(self, trace_id: str, spans, status: str = "ok",
                      attrs: dict | None = None) -> dict:
        """Record spans locally and build the response headers that echo the
        trace id and carry the spans back to the gateway.  ``attrs`` maps a
        span's name to its attributes."""
        for name, s, e in spans:
            self.tracer.record(trace_id, name, s, e,
                               **(attrs or {}).get(name, {}))
        self.tracer.annotate(trace_id, model=self.model_name, status=status)
        headers = {tracing.TRACE_HEADER: trace_id}
        if spans and self.tracer.sampled(trace_id):
            headers[tracing.SPANS_HEADER] = tracing.wire_spans(spans)
        return headers

    def _reject(self, status: int, message: str, trace_id: str | None,
                reason: str) -> web.Response:
        """Capacity/lifecycle rejection: journal it (the flight recorder
        correlates replica-side 429/503/422 with gateway-side picks via the
        trace id) and answer the usual error envelope.  Plain 400 client
        errors do NOT come through here — they are request defects, not
        system events."""
        self.events.emit(events_mod.ADMISSION_REJECT, trace_id or "",
                         status=status, reason=reason)
        return _err(status, message, trace_id)

    # -- helpers -----------------------------------------------------------
    def _resolve_model(self, requested: str) -> str | None:
        """Adapter name if the request targets a resident adapter, else None
        (base model).  Unknown names raise AdapterError -> 404, matching
        vLLM's behavior the sidecar relies on."""
        if requested in ("", None) or requested in self.aliases:
            return None
        if self.lora is not None and requested in self.lora.running_adapters():
            return requested
        raise AdapterError(f"model {requested!r} is not served by this replica")

    def _encode_prompt(self, body: dict) -> list[int]:
        prompt = body.get("prompt", "")
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            return list(prompt)  # pre-tokenized
        if isinstance(prompt, list):
            prompt = " ".join(str(p) for p in prompt)
        return self.tokenizer.encode(str(prompt))

    def _make_request(self, body: dict, prompt_tokens: list[int], adapter,
                      logprobs: int | None = None,
                      candidate: int = 0) -> Request:
        seed = body.get("seed")
        if seed is not None:
            # n/best_of fan-out with one seed would produce identical
            # candidates (the draw depends only on seed+position); folding
            # the candidate index keeps each choice distinct yet the whole
            # response reproducible (vLLM does the same).
            seed = int(seed) + candidate
        presence = float(body.get("presence_penalty") or 0.0)
        frequency = float(body.get("frequency_penalty") or 0.0)
        raw_bias = body.get("logit_bias") or None
        logit_bias = ({int(k): float(v) for k, v in raw_bias.items()}
                      if raw_bias else None)
        return Request(
            prompt_tokens=prompt_tokens,
            max_new_tokens=int(body.get("max_tokens", 64)),
            sampling=SamplingParams(
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                top_p=float(body.get("top_p", 1.0)),
                seed=seed,
                presence_penalty=presence,
                frequency_penalty=frequency,
                logit_bias=logit_bias,
            ),
            adapter=adapter,
            stop_sequences=self._encode_stops(body),
            logprobs=logprobs,
        )

    def _encode_stops(self, body: dict) -> tuple[tuple[int, ...], ...]:
        """Tokenized OpenAI ``stop`` strings for the engine's device-side
        suffix automata — an EARLY-FREEZE accelerator, not the oracle.
        The text-level scan (_wait_with_stops / _truncate_at_stop) stays
        authoritative: an automaton hit only ends generation at a token
        tail whose decode CONTAINS the stop string (encode/decode
        round-trip), which the text truncation then cuts identically,
        while a stop spelled by a different token split simply misses the
        automaton and is caught by the text scan as before.  So this can
        only stop generation earlier, never change the response."""
        stop = body.get("stop")
        stops = ([stop] if isinstance(stop, str)
                 else [s for s in stop if isinstance(s, str)]
                 if isinstance(stop, list) else [])
        out = []
        for s in stops:
            if not s:
                continue
            try:
                ids = self.tokenizer.encode(s)
                if ids and self.tokenizer.decode(list(ids)) == s:
                    out.append(tuple(int(t) for t in ids))
            except Exception:  # pragma: no cover - defensive: odd tokenizer
                continue
        return tuple(out)

    @staticmethod
    def _parse_choice_params(body: dict) -> tuple[int, int, int | None, list[str]]:
        """(n, best_of, logprobs, stops) with OpenAI validation rules."""
        n = int(body.get("n", 1))
        best_of = int(body.get("best_of", max(n, 1)))
        if not 1 <= n <= MAX_N:
            raise ValueError(f"n must be in [1, {MAX_N}]")
        if not n <= best_of <= MAX_N:
            raise ValueError(f"best_of must be in [n, {MAX_N}]")
        logprobs = body.get("logprobs")
        if logprobs is not None:
            logprobs = int(logprobs)
            if not 0 <= logprobs <= MAX_LOGPROBS:
                raise ValueError(f"logprobs must be in [0, {MAX_LOGPROBS}]")
        stop = body.get("stop")
        if stop is None:
            stops: list[str] = []
        elif isinstance(stop, str):
            stops = [stop]
        elif (isinstance(stop, list)
              and all(isinstance(s, str) for s in stop)):
            stops = list(stop)
        else:
            raise ValueError("stop must be a string or a list of strings")
        if len(stops) > 4:
            raise ValueError("at most 4 stop sequences are supported")
        for name in ("presence_penalty", "frequency_penalty"):
            val = float(body.get(name) or 0.0)  # null == unset
            if not -2.0 <= val <= 2.0:
                raise ValueError(f"{name} must be in [-2, 2]")
        bias = body.get("logit_bias")
        if bias is not None:
            if not isinstance(bias, dict):
                raise ValueError("logit_bias must be an object")
            if len(bias) > MAX_LOGIT_BIAS:
                raise ValueError("logit_bias supports at most "
                                 f"{MAX_LOGIT_BIAS} entries")
            for k, v in bias.items():
                if not -100.0 <= float(v) <= 100.0:
                    raise ValueError("logit_bias values must be in "
                                     "[-100, 100]")
                int(k)  # token ids must be integral (ValueError otherwise)
        return n, best_of, logprobs, [s for s in stops if s]

    def _wait_with_stops(self, req: Request, stops: list[str],
                         timeout_s: float = 600.0,
                         submit: bool = True) -> Request:
        """generate(), plus early cancellation the moment a stop string
        appears in the decoded text (the exact cut happens afterwards in
        _truncate_at_stop — generation must not keep burning the slot).

        Decoding is incremental (only unconsumed tokens) and the stop search
        only rescans a window the new piece could have completed, so a long
        generation stays O(n), not O(n^2), on the executor thread.
        ``submit=False`` waits on an ALREADY-submitted request (the attach
        hop admits through ``engine.attach_prefilled``)."""
        if submit:
            self.engine.submit(req)
        deadline = time.monotonic() + timeout_s
        max_stop = max((len(s) for s in stops), default=0)
        text = ""
        consumed = 0
        while True:
            req.stream_event.wait(0.25)
            req.stream_event.clear()
            done = req.done.is_set()
            n = len(req.output_tokens)
            if stops and n > consumed:
                piece = self.tokenizer.decode(req.output_tokens[consumed:n])
                if piece.endswith("�") and not done:
                    pass  # incomplete UTF-8 tail: re-decode next wake
                else:
                    window_start = max(0, len(text) - max_stop + 1)
                    text += piece
                    consumed = n
                    if any(s in text[window_start:] for s in stops):
                        req.cancelled.set()
                        req.done.wait(30)
                        return req
            if done:
                return req
            if time.monotonic() > deadline:
                req.error = "generation timed out"
                req.cancelled.set()
                return req

    def _truncate_at_stop(self, req: Request, stops: list[str]) -> tuple[str, bool]:
        """Cut text AND the per-token records at the earliest stop match.
        Returns (final text, whether a stop hit)."""
        full = self.tokenizer.decode(req.output_tokens)
        if not stops:
            return full, False
        hits = [(full.index(s), s) for s in stops if s in full]
        if not hits:
            return full, False
        idx, _ = min(hits)
        # Smallest token count whose decoded prefix already contains a stop
        # ("contains a stop" is monotone in the prefix length, so binary
        # search): everything from that token on is post-stop and dropped.
        lo, hi = 1, len(req.output_tokens)
        while lo < hi:
            mid = (lo + hi) // 2
            if any(s in self.tokenizer.decode(req.output_tokens[:mid])
                   for s in stops):
                hi = mid
            else:
                lo = mid + 1
        keep = lo
        del req.output_tokens[keep:]
        del req.output_logprobs[keep:]
        del req.output_top_logprobs[keep:]
        req.finish_reason = "stop"
        return full[:idx], True

    @staticmethod
    def _held_back(cur: str, full: str) -> int:
        """How many trailing replacement chars of ``cur`` (a prefix decode)
        are partial-multi-byte artifacts rather than genuine U+FFFD output.

        A char the model actually emitted survives verbatim into the FULL
        decode at the same index; an artifact resolves into a different
        character once the completing bytes arrive.  So: hold back the
        smallest trailing-U+FFFD suffix whose removal makes ``cur`` agree
        with the full decode, bounded by one UTF-8 char's worth of pending
        bytes (``_MAX_PARTIAL_FFFD``) — beyond that the run is genuine."""
        run = len(cur) - len(cur.rstrip(_FFFD))
        cap = min(run, _MAX_PARTIAL_FFFD)
        for hold in range(cap + 1):
            keep = len(cur) - hold
            if full[:keep] == cur[:keep]:
                return hold
        return cap

    def _per_token_records(self, req: Request, k: int,
                           text_limit: int | None = None):
        """Per-generated-token ``(piece, logprob, deduped_tops)`` rows — the
        ONE walk both logprobs envelopes (completions and chat) build from.

        Piece attribution holds back trailing replacement chars while more
        tokens remain *and the full decode resolves them*: a UTF-8
        character split across byte-fallback tokens is attributed whole to
        its COMPLETING token (predecessors emit ""), while a token that
        GENUINELY decodes to U+FFFD keeps its char in place (``_held_back``
        distinguishes the two; held-back chars are bounded by one UTF-8
        char's max pending bytes).  Either way the pieces' concatenation
        equals the full decode exactly.  ``deduped_tops`` keeps the most
        probable id per surface string (byte-fallback ids can collide).

        ``text_limit`` clips the walk to the RETURNED text (stop-sequence
        truncation is character-granular while the token records are
        token-granular: the kept token completing a stop would otherwise
        leak the stop's tail into the envelope, OpenAI trims it)."""
        rows = []
        committed = ""
        n = len(req.output_tokens)
        full = None  # full decode, computed lazily on the first FFFD tail
        for i in range(n):
            if text_limit is not None and len(committed) >= text_limit:
                break
            cur = self.tokenizer.decode(req.output_tokens[: i + 1])
            if i + 1 < n and cur.endswith(_FFFD):
                if full is None:
                    full = self.tokenizer.decode(req.output_tokens)
                hold = self._held_back(cur, full)
                if hold:
                    cur = cur[:-hold]
            piece = cur[len(committed):]
            if text_limit is not None:
                piece = piece[: max(0, text_limit - len(committed))]
            committed += piece
            lp = (req.output_logprobs[i]
                  if i < len(req.output_logprobs) else None)
            tops: dict[str, float] = {}
            if k > 0 and i < len(req.output_top_logprobs):
                for tok, v in req.output_top_logprobs[i].items():
                    key = self.tokenizer.decode([tok])
                    v = max(v, -1e9)
                    if key not in tops or v > tops[key]:
                        tops[key] = v
            rows.append((piece, None if lp is None else max(lp, -1e9), tops))
        return rows

    def _logprobs_json(self, req: Request, k: int,
                       text_limit: int | None = None) -> dict:
        """OpenAI completions ``logprobs`` object (tokens / token_logprobs /
        top_logprobs / text_offset)."""
        tokens, token_lps, tops, offsets = [], [], [], []
        offset = 0
        for piece, lp, top in self._per_token_records(req, k, text_limit):
            offsets.append(offset)
            offset += len(piece)
            tokens.append(piece)
            token_lps.append(lp)
            if k > 0:
                tops.append(top)
        return {
            "tokens": tokens,
            "token_logprobs": token_lps,
            "top_logprobs": tops if k > 0 else None,
            "text_offset": offsets,
        }

    def _chat_logprobs_json(self, req: Request, top_n: int,
                            text_limit: int | None = None) -> dict:
        """OpenAI CHAT ``logprobs`` object — ``choices[].logprobs.content[]``
        entries with token / logprob / bytes / top_logprobs (the chat form:
        per-token objects with UTF-8 byte arrays, no text_offset — distinct
        envelope over the same ``_per_token_records`` walk the completions
        form uses).  ``bytes`` carries the attributed piece's UTF-8, so the
        concatenation of all bytes arrays equals the content's encoding."""
        content = []
        for piece, lp, top in self._per_token_records(req, top_n, text_limit):
            content.append({
                "token": piece,
                "logprob": lp,
                "bytes": list(piece.encode("utf-8", "surrogatepass")),
                "top_logprobs": [
                    {"token": k, "logprob": v,
                     "bytes": list(k.encode("utf-8", "surrogatepass"))}
                    for k, v in sorted(top.items(), key=lambda kv: -kv[1])
                ][:top_n],
            })
        return {"content": content}

    def _chat_prompt(self, messages: list) -> tuple[str, bool]:
        """Chat messages -> (prompt text, add_bos).  A checkpoint
        tokenizer's own chat template wins (HFTokenizer.apply_chat_template
        — the format the model was TRAINED on); tokenizers without one get
        the plain role-prefix transcript.  Templated prompts encode with
        add_bos=False: most real templates render the BOS token as text,
        and prepending another would feed [BOS, BOS, ...] — a stream the
        model never trained on.  Template failures (role restrictions,
        strict alternation, non-string content) raise ValueError so the
        handler returns a 400, not a 500 — the request was valid OpenAI but
        invalid for THIS model's template, a client-fixable condition."""
        apply = getattr(self.tokenizer, "apply_chat_template", None)
        if apply is not None:
            try:
                templated = apply(messages)
            except Exception as e:  # jinja TemplateError, TypeError, ...
                raise ValueError(
                    f"messages are not renderable by this model's chat "
                    f"template: {e}") from e
            if templated is not None:
                return templated, False
        return "\n".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}"
            for m in messages
        ) + "\nassistant:", True

    @staticmethod
    def _parse_chat_logprobs(body: dict) -> tuple[bool, int, int]:
        """(logprobs flag, EFFECTIVE top-N, REQUESTED top-N) with OpenAI
        chat validation.  The OpenAI range [0, 20] is accepted in full;
        the engine records a device-side top-5 (LOGPROB_TOPK), so the
        effective N truncates there and responses note the cap when it
        bit (``top_logprobs_truncated_to``)."""
        lp_flag = bool(body.get("logprobs"))
        top_n = body.get("top_logprobs")
        if top_n is None:
            return lp_flag, 0, 0
        if not lp_flag:
            raise ValueError("top_logprobs requires logprobs: true")
        top_n = int(top_n)
        if not 0 <= top_n <= OPENAI_MAX_TOP_LOGPROBS:
            raise ValueError(
                f"top_logprobs must be in [0, {OPENAI_MAX_TOP_LOGPROBS}]")
        return lp_flag, min(top_n, MAX_LOGPROBS), top_n

    async def _run(self, req: Request, stops: list[str] | None = None) -> Request:
        loop = asyncio.get_running_loop()
        try:
            if stops:
                return await loop.run_in_executor(
                    None, self._wait_with_stops, req, stops)
            return await loop.run_in_executor(None, self.engine.generate, req)
        except asyncio.CancelledError:
            # Non-streaming client disconnected: free the slot too.
            req.cancelled.set()
            raise

    async def _run_many(self, reqs: list[Request],
                        stops: list[str]) -> list[Request]:
        """Run candidates concurrently; if ANY submit/run fails, cancel the
        siblings (gather alone would leave them decoding for nobody — load
        amplification exactly when capacity is scarce) and re-raise the
        first failure."""
        results = await asyncio.gather(
            *(self._run(r, stops=stops) for r in reqs),
            return_exceptions=True)
        failure = next(
            (r for r in results if isinstance(r, BaseException)), None)
        if failure is not None:
            for r in reqs:
                r.cancelled.set()
            raise failure
        return list(results)

    # -- streaming ---------------------------------------------------------
    async def _stream_sse(self, http_request: web.Request, req, model: str,
                          object_name: str, make_delta,
                          timeout_s: float = 600.0,
                          stops: list[str] | None = None,
                          echo_prefix: str | None = None,
                          submit: bool = True,
                          trace_id: str | None = None,
                          decode_start: float | None = None,
                          t_accept: float | None = None):
        """Server-sent-events generation stream (OpenAI stream=true shape).

        Tokens appear in ``req.output_tokens`` as the engine decodes (in
        K-step blocks); each wake decodes only the unconsumed suffix and
        emits it as one chunk.  A suffix ending in a replacement char is held
        back whole — likely a multi-byte UTF-8 sequence the next block
        completes.  Submission happens BEFORE headers so saturation is a real
        429 (the gateway's backpressure contract), and the done flag is read
        BEFORE the token count so the final re-diff can't drop a tail.

        Spans of the way in and out (``trace_id`` given): ``server.accept``
        from ``t_accept`` (the handler's entry) to ``engine.submit``
        returning, ``server.first_write`` from the engine's first token to
        the first data chunk's write returning; every data chunk's
        emit-to-write lag goes to the two stream counters, the request's
        largest onto its ``engine.decode`` span.
        """
        # Mark the request as SSE-consumed BEFORE submission: the engine's
        # adaptive dispatch planner caps fused steps for streaming rows
        # (EngineConfig.adaptive_stream_cap) so a live stream keeps
        # per-token cadence instead of n_steps-sized bursts.
        req.streaming = True
        if submit:
            try:
                self.engine.submit(req)
            except EngineDraining as e:
                return self._reject(503, str(e), trace_id, "draining")  # replica leaving the set
            except ValueError as e:
                return _err(400, str(e), trace_id)
            except queue_mod.Full:
                return self._reject(429, "prefill queue is full",
                                    trace_id, "queue_full")
            if trace_id and t_accept:
                self.tracer.record(trace_id, "server.accept", t_accept,
                                   time.time())

        # From here the request occupies engine capacity: ANY exit before
        # completion (disconnect during prepare, write failure, handler
        # cancel, unexpected exception) must release the slot — enforced by
        # the finally below, not by enumerating exception types.
        chunks = 0  # data chunks written
        lag_max = 0.0  # the largest emit-to-write lag among them
        try:
            stream_headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "x-accel-buffering": "no",
            }
            if trace_id:
                stream_headers[tracing.TRACE_HEADER] = trace_id
            resp = web.StreamResponse(headers=stream_headers)
            await resp.prepare(http_request)
            loop = asyncio.get_running_loop()
            consumed = 0  # tokens already emitted as text
            deadline = time.monotonic() + timeout_s

            async def emit(payload: dict, t_emit: float | None = None) -> None:
                """Write one SSE chunk; ``t_emit`` marks a data chunk: the
                ``Request.t_emit`` its tokens were published at (0.0: not
                known, counted in no lag)."""
                nonlocal chunks, lag_max
                await resp.write(f"data: {json.dumps(payload)}\n\n".encode())
                if t_emit is None:
                    return
                now = time.time()
                chunks += 1
                if chunks == 1 and trace_id and req.t_first_token:
                    self.tracer.record(trace_id, "server.first_write",
                                       req.t_first_token, now)
                if t_emit:
                    lag = now - t_emit
                    self.stream_write_lag_s += lag
                    self.stream_chunks += 1
                    lag_max = max(lag_max, lag)

            if echo_prefix:
                # OpenAI echo under streaming: the prompt text leads the
                # stream as its own chunk.
                await emit({
                    "id": f"cmpl-{req.request_id}",
                    "object": object_name,
                    "model": model,
                    "choices": [make_delta(echo_prefix, None)],
                })
            if stops:
                return await self._stream_sse_loop_stops(
                    req, model, object_name, make_delta, resp, loop,
                    deadline, emit, stops,
                )
            return await self._stream_sse_loop(
                req, model, object_name, make_delta, resp, loop, consumed,
                deadline, emit,
            )
        finally:
            if not req.done.is_set():
                # Stream ended without the request completing (disconnect,
                # deadline, any exception): release the decode slot instead
                # of generating to completion for nobody.
                req.cancelled.set()
            elif trace_id:
                # Completed stream: the engine stamps are final — record the
                # phase spans (streams can't carry x-lig-spans post-hoc, so
                # they live on THIS server's /debug/traces, same trace id).
                self._record_spans(
                    trace_id,
                    self._engine_spans(req, decode_start=decode_start),
                    status=req.finish_reason or "ok",
                    attrs={"engine.prefill": req.prefill_attrs,
                           "engine.decode": {
                               "chunks": chunks,
                               "write_lag_max_s": round(lag_max, 6)}})

    async def _stream_sse_loop(self, req, model, object_name, make_delta,
                               resp, loop, consumed, deadline, emit):
        while True:
            await loop.run_in_executor(None, req.stream_event.wait, 0.25)
            req.stream_event.clear()
            t_emit, req.t_emit = req.t_emit, 0.0
            done = req.done.is_set()  # read BEFORE the token count
            n = len(req.output_tokens)
            if n > consumed:
                # PER-TOKEN chunking: the engine publishes each fused-block
                # token individually (per-step emission from the trim
                # walk), and each token's text DELTA becomes its own SSE
                # chunk — a K-step fused dispatch no longer arrives as one
                # concatenated burst.  Deltas come from prefix-diffing
                # growing decodes of the unconsumed window (the
                # concatenation-safe pattern the non-stream logprob walk
                # uses): per-token decode() is NOT concatenative for
                # SentencePiece-style tokenizers (leading-space stripping),
                # so decoding each token span independently would eat
                # inter-word spaces.  A prefix still ending in U+FFFD is
                # held back (likely a multi-byte sequence the next token
                # completes); the window is tiny (per-step wakes), so the
                # quadratic prefix decode stays O(burst) per dispatch.
                window = req.output_tokens[consumed:n]
                prev = ""
                clean = 0  # tokens of the window emitted cleanly
                for i in range(1, len(window) + 1):
                    text = self.tokenizer.decode(window[:i])
                    if text.endswith("�"):
                        if i < len(window):
                            continue  # next token may complete the bytes
                        if not done:
                            break     # hold the incomplete tail back
                    delta = text[len(prev):]
                    prev = text
                    clean = i
                    if delta:
                        await emit({
                            "id": f"cmpl-{req.request_id}",
                            "object": object_name,
                            "model": model,
                            "choices": [make_delta(delta, None)],
                        }, t_emit)
                consumed += clean
            if done:
                # Final re-diff: anything appended since the last emit (or a
                # held-back tail) rides the final chunk.
                tail = (
                    self.tokenizer.decode(req.output_tokens[consumed:])
                    if len(req.output_tokens) > consumed else ""
                )
                await emit({
                    "id": f"cmpl-{req.request_id}",
                    "object": object_name,
                    "model": model,
                    "choices": [make_delta(tail, req.finish_reason or "stop")],
                    "usage": {
                        "prompt_tokens": len(req.prompt_tokens),
                        "completion_tokens": len(req.output_tokens),
                        "total_tokens": len(req.prompt_tokens) + len(req.output_tokens),
                    },
                }, t_emit)
                await resp.write(b"data: [DONE]\n\n")
                return resp
            if time.monotonic() > deadline:
                req.cancelled.set()  # stop burning the slot for a dead stream
                await emit({"error": {"message": "generation timed out"}})
                await resp.write(b"data: [DONE]\n\n")
                return resp

    async def _stream_sse_loop_stops(self, req, model, object_name,
                                     make_delta, resp, loop, deadline, emit,
                                     stops):
        """Character-based streaming with stop-sequence scanning.

        Emitted text always lags the decoded text by ``holdback`` characters
        (longest stop minus one) while generating, so no prefix of a stop
        sequence ever reaches the client before the match is decided."""
        holdback = max(len(s) for s in stops) - 1
        max_stop = holdback + 1
        emitted = 0
        consumed = 0  # tokens folded into ``text`` so far
        text = ""
        hits: list[tuple[int, str]] = []

        t_emit = 0.0  # Request.t_emit as taken at the last wake

        async def send(delta: str, fin: str | None, usage: bool = False):
            payload = {
                "id": f"cmpl-{req.request_id}",
                "object": object_name,
                "model": model,
                "choices": [make_delta(delta, fin)],
            }
            if usage:
                payload["usage"] = {
                    "prompt_tokens": len(req.prompt_tokens),
                    "completion_tokens": len(req.output_tokens),
                    "total_tokens": (len(req.prompt_tokens)
                                     + len(req.output_tokens)),
                }
            await emit(payload, t_emit)

        while True:
            await loop.run_in_executor(None, req.stream_event.wait, 0.25)
            req.stream_event.clear()
            t_emit, req.t_emit = req.t_emit, 0.0
            done = req.done.is_set()  # read BEFORE decoding
            n = len(req.output_tokens)
            if n > consumed:
                # Incremental decode + windowed search: O(total) over the
                # generation, not O(n^2).
                piece = self.tokenizer.decode(req.output_tokens[consumed:n])
                if piece.endswith("�") and not done:
                    pass  # incomplete UTF-8 tail: re-decode next wake
                else:
                    window = max(0, len(text) - max_stop + 1)
                    text += piece
                    consumed = n
                    hits = [(text.index(s, window), s)
                            for s in stops if s in text[window:]]
            if hits:
                idx, _ = min(hits)
                req.cancelled.set()  # free the slot; text is final
                await loop.run_in_executor(None, req.done.wait, 30)
                self._truncate_at_stop(req, stops)  # usage matches the cut
                if idx > emitted:
                    await send(text[emitted:idx], None)
                await send("", "stop", usage=True)
                await resp.write(b"data: [DONE]\n\n")
                return resp
            limit = len(text) if done else max(emitted, len(text) - holdback)
            if limit > emitted:
                await send(text[emitted:limit], None)
                emitted = limit
            if done:
                await send("", req.finish_reason or "stop", usage=True)
                await resp.write(b"data: [DONE]\n\n")
                return resp
            if time.monotonic() > deadline:
                req.cancelled.set()
                await emit({"error": {"message": "generation timed out"}})
                await resp.write(b"data: [DONE]\n\n")
                return resp

    # -- inference ---------------------------------------------------------
    async def handle_completions(self, request: web.Request) -> web.Response:
        t_accept = time.time()
        trace_id = self._trace_id_for(request)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body", trace_id)
        try:
            adapter = self._resolve_model(body.get("model", self.model_name))
        except AdapterError as e:
            return _err(404, str(e), trace_id)
        try:
            n, best_of, logprobs, stops = self._parse_choice_params(body)
        except (ValueError, TypeError) as e:
            return _err(400, str(e), trace_id)
        prompt_tokens = self._encode_prompt(body)
        echo = bool(body.get("echo"))

        def echo_text() -> str:
            # The client's own string round-trips exactly; only
            # pre-tokenized (list-of-int) prompts need a decode, where
            # tokenizer normalization is inherent to the request form.
            prompt = body.get("prompt", "")
            if isinstance(prompt, str):
                return prompt
            return self.tokenizer.decode(prompt_tokens)

        if echo and logprobs is not None:
            # OpenAI echo+logprobs returns PROMPT logprobs, which the
            # engine does not record; reject rather than mislabel.
            return _err(400, "echo is not supported together with logprobs",
                        trace_id)
        if body.get("stream"):
            if n > 1 or best_of > 1:
                return _err(400, "streaming supports n=1 / best_of=1",
                            trace_id)
            if logprobs is not None:
                # Explicit rejection beats a silently-null field: chunks
                # carry no logprobs object.
                return _err(400, "logprobs is not supported with streaming",
                            trace_id)
            req = self._make_request(body, prompt_tokens, adapter)
            prefix = echo_text() if echo else None
            return await self._stream_sse(
                request, req, body.get("model", self.model_name),
                "text_completion",
                lambda delta, fin: {"index": 0, "text": delta, "finish_reason": fin},
                stops=stops, echo_prefix=prefix, trace_id=trace_id,
                t_accept=t_accept,
            )
        # best_of candidates decode concurrently (the engine batches them);
        # ranking needs per-token logprobs, so candidates record at least the
        # sampled-token values even when the client didn't ask.
        record = logprobs if logprobs is not None else (
            0 if best_of > n else None)
        reqs = [
            self._make_request(body, list(prompt_tokens), adapter,
                               logprobs=record, candidate=i)
            for i in range(best_of)
        ]
        try:
            reqs = await self._run_many(reqs, stops)
        except EngineDraining as e:
            return self._reject(503, str(e), trace_id, "draining")
        except ValueError as e:
            return _err(400, str(e), trace_id)
        except queue_mod.Full:
            # Backpressure the gateway cleanly; its scheduler already sees the
            # queue depth via /metrics and will shed/redirect.
            return self._reject(429, "prefill queue is full", trace_id,
                                "queue_full")
        for r in reqs:
            if r.error:
                return _err(500, r.error, trace_id)
        texts = {id(r): self._truncate_at_stop(r, stops)[0] for r in reqs}
        # OpenAI usage semantics: completion_tokens counts ALL generated
        # candidates, including best_of ones not returned.
        completion_tokens = sum(len(r.output_tokens) for r in reqs)
        if best_of > n:
            # OpenAI best_of: keep the n candidates with the highest mean
            # token logprob.
            def mean_lp(r: Request) -> float:
                return (sum(r.output_logprobs) / len(r.output_logprobs)
                        if r.output_logprobs else float("-inf"))

            reqs.sort(key=mean_lp, reverse=True)
            reqs = reqs[:n]
        echo_prefix = echo_text() if echo else ""
        choices = []
        for i, r in enumerate(reqs):
            choice = {
                "index": i,
                "text": echo_prefix + texts[id(r)],
                "finish_reason": r.finish_reason,
            }
            if logprobs is not None:
                choice["logprobs"] = self._logprobs_json(
                    r, logprobs, text_limit=len(texts[id(r)]))
            choices.append(choice)
        headers = self._record_spans(
            trace_id, self._engine_spans(reqs[0]),
            status=reqs[0].finish_reason or "ok",
            attrs={"engine.prefill": reqs[0].prefill_attrs})
        return web.json_response({
            "id": f"cmpl-{reqs[0].request_id}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": body.get("model", self.model_name),
            "choices": choices,
            "usage": {
                "prompt_tokens": len(prompt_tokens),
                "completion_tokens": completion_tokens,
                "total_tokens": len(prompt_tokens) + completion_tokens,
            },
            "ttft_ms": round(reqs[0].ttft_s * 1000, 2),
        }, headers=headers)

    async def handle_chat(self, request: web.Request) -> web.Response:
        t_accept = time.time()
        trace_id = self._trace_id_for(request)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body", trace_id)
        messages = body.get("messages", [])
        try:
            adapter = self._resolve_model(body.get("model", self.model_name))
        except AdapterError as e:
            return _err(404, str(e), trace_id)
        try:
            prompt, add_bos = self._chat_prompt(messages)
            n, best_of, _, stops = self._parse_choice_params(body)
            lp_flag, top_n, top_req = self._parse_chat_logprobs(body)
        except (ValueError, TypeError) as e:
            return _err(400, str(e), trace_id)
        prompt_tokens = self.tokenizer.encode(prompt, add_bos=add_bos)
        if body.get("stream"):
            if n > 1 or best_of > 1:
                return _err(400, "streaming supports n=1 / best_of=1",
                            trace_id)
            if lp_flag:
                return _err(400, "logprobs are not supported with "
                                 "streaming chat completions", trace_id)
            req = self._make_request(body, prompt_tokens, adapter)
            return await self._stream_sse(
                request, req, body.get("model", self.model_name),
                "chat.completion.chunk",
                lambda delta, fin: {
                    "index": 0,
                    "delta": ({"content": delta} if delta else {}),
                    "finish_reason": fin,
                },
                stops=stops, trace_id=trace_id, t_accept=t_accept,
            )
        reqs = [self._make_request(body, list(prompt_tokens), adapter,
                                   logprobs=top_n if lp_flag else None,
                                   candidate=i)
                for i in range(n)]
        try:
            reqs = await self._run_many(reqs, stops)
        except EngineDraining as e:
            return self._reject(503, str(e), trace_id, "draining")
        except ValueError as e:
            return _err(400, str(e), trace_id)
        except queue_mod.Full:
            return self._reject(429, "prefill queue is full", trace_id,
                                "queue_full")
        for r in reqs:
            if r.error:
                return _err(500, r.error, trace_id)
        choices = []
        for i, r in enumerate(reqs):
            text, _ = self._truncate_at_stop(r, stops)
            choice = {
                "index": i,
                "message": {"role": "assistant", "content": text},
                "finish_reason": r.finish_reason,
            }
            if lp_flag:
                choice["logprobs"] = self._chat_logprobs_json(
                    r, top_n, text_limit=len(text))
                if top_req > top_n:
                    # Divergence note: the client asked for more than the
                    # engine's device-side top-k records.
                    choice["logprobs"]["top_logprobs_truncated_to"] = top_n
            choices.append(choice)
        completion_tokens = sum(len(r.output_tokens) for r in reqs)
        headers = self._record_spans(
            trace_id, self._engine_spans(reqs[0]),
            status=reqs[0].finish_reason or "ok",
            attrs={"engine.prefill": reqs[0].prefill_attrs})
        return web.json_response({
            "id": f"chatcmpl-{reqs[0].request_id}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": body.get("model", self.model_name),
            "choices": choices,
            "usage": {
                "prompt_tokens": len(prompt_tokens),
                "completion_tokens": completion_tokens,
                "total_tokens": len(prompt_tokens) + completion_tokens,
            },
        }, headers=headers)

    # -- disaggregation hops (server/kv_transfer.py) -------------------------
    async def handle_prefill(self, request: web.Request) -> web.Response:
        """Hop 1: prefill only, return the serialized ``PrefillHandoff``.

        Accepts the standard completions/chat body.  Shapes the handoff
        path can't carry (candidate fan-out, echo) and prompts beyond the
        prefill bucket answer 422 — the gateway treats any non-200 as
        "serve this single-hop instead", so unsupported requests degrade,
        never fail.
        """
        trace_id = self._trace_id_for(request)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body", trace_id)
        try:
            adapter = self._resolve_model(body.get("model", self.model_name))
        except AdapterError as e:
            return _err(404, str(e), trace_id)
        try:
            n, best_of, logprobs, _stops = self._parse_choice_params(body)
            if isinstance(body.get("messages"), list):
                prompt, add_bos = self._chat_prompt(body["messages"])
                prompt_tokens = self.tokenizer.encode(prompt, add_bos=add_bos)
                lp_flag, top_n, _ = self._parse_chat_logprobs(body)
                logprobs = top_n if lp_flag else None
            else:
                prompt_tokens = self._encode_prompt(body)
        except (ValueError, TypeError) as e:
            return _err(400, str(e), trace_id)
        if n > 1 or best_of > 1 or body.get("echo"):
            return self._reject(422, "prefill hop supports single-candidate, "
                                     "non-echo requests", trace_id,
                                "prefill_unsupported")
        req = self._make_request(body, prompt_tokens, adapter,
                                 logprobs=logprobs)
        loop = asyncio.get_running_loop()
        try:
            handoff = await loop.run_in_executor(
                None, lambda: self.engine.prefill_only(req))
        except EngineDraining as e:
            return self._reject(503, str(e), trace_id, "draining")
        except queue_mod.Full:
            return self._reject(429, "prefill queue is full", trace_id,
                                "queue_full")
        except ValueError as e:
            return self._reject(422, str(e), trace_id,
                                "prefill_refused")  # e.g. beyond the bucket set
        except RuntimeError as e:
            return _err(500, str(e), trace_id)
        handoff.body = body  # envelope params ride to the decode hop
        handoff.trace_id = trace_id  # one id across both hops
        t_ser0 = time.time()
        wire = handoff.to_bytes()
        t_ser1 = time.time()
        self.engine.observe_handoff(t_ser1 - t_ser0)
        headers = self._record_spans(
            trace_id,
            self._engine_spans(req, with_decode=False)
            + [("handoff.serialize", t_ser0, t_ser1)],
            status="handoff", attrs={"engine.prefill": req.prefill_attrs})
        headers.update({"x-request-id": req.request_id,
                        "x-prefill-ttft-ms": f"{req.ttft_s * 1000:.2f}"})
        return web.Response(
            body=wire,
            content_type="application/octet-stream",
            headers=headers,
        )

    async def handle_attach(self, request: web.Request) -> web.Response:
        """Hop 2: admit a ``PrefillHandoff`` straight into decode and answer
        in the normal OpenAI envelope (streaming included) — the client
        response is indistinguishable from collocated serving."""
        from llm_instance_gateway_tpu.server.kv_transfer import PrefillHandoff

        raw = await request.read()
        t_des0 = time.time()
        try:
            handoff = PrefillHandoff.from_bytes(raw)
        except Exception as e:
            return _err(400, f"malformed handoff: {e}",
                        self._trace_id_for(request))
        t_des1 = time.time()
        # The trace id prefers the header but survives header-stripping
        # transports via the handoff's own field.
        trace_id = (tracing.header_trace_id(request.headers)
                    or handoff.trace_id or tracing.new_trace_id())
        body = handoff.body or {}
        chat = isinstance(body.get("messages"), list)
        try:
            _, _, _, stops = self._parse_choice_params(body)
        except (ValueError, TypeError) as e:
            return _err(400, str(e), trace_id)
        try:
            req = self.engine.attach_prefilled(handoff)
        except EngineDraining as e:
            return self._reject(503, str(e), trace_id, "draining")
        except queue_mod.Full:
            return self._reject(429, "attach admission queue is full",
                                trace_id, "queue_full")
        except AdapterError as e:
            return _err(404, str(e), trace_id)
        except ValueError as e:
            return self._reject(422, str(e), trace_id, "attach_refused")
        t_att = time.time()
        self.engine.observe_handoff(t_att - t_des0)
        attach_spans = [("handoff.deserialize", t_des0, t_des1),
                        ("handoff.attach", t_des1, t_att)]
        model = body.get("model", self.model_name)
        if body.get("stream"):
            for name, s, e in attach_spans:
                self.tracer.record(trace_id, name, s, e)
            if chat:
                return await self._stream_sse(
                    request, req, model, "chat.completion.chunk",
                    lambda delta, fin: {
                        "index": 0,
                        "delta": ({"content": delta} if delta else {}),
                        "finish_reason": fin,
                    },
                    stops=stops, submit=False, trace_id=trace_id,
                    decode_start=t_att)
            return await self._stream_sse(
                request, req, model, "text_completion",
                lambda delta, fin: {"index": 0, "text": delta,
                                    "finish_reason": fin},
                stops=stops, submit=False, trace_id=trace_id,
                decode_start=t_att)
        loop = asyncio.get_running_loop()
        try:
            if stops:
                await loop.run_in_executor(
                    None, lambda: self._wait_with_stops(
                        req, stops, submit=False))
            else:
                await loop.run_in_executor(None, req.done.wait, 600.0)
        except asyncio.CancelledError:
            req.cancelled.set()
            raise
        if not req.done.is_set():
            req.error = "generation timed out"
            req.cancelled.set()
        if req.error:
            return _err(500, req.error, trace_id)
        trace_headers = self._record_spans(
            trace_id,
            attach_spans + [("engine.decode", t_att,
                             req.t_done or time.time())],
            status=req.finish_reason or "ok")
        text, _ = self._truncate_at_stop(req, stops)
        completion_tokens = len(req.output_tokens)
        usage = {
            "prompt_tokens": len(req.prompt_tokens),
            "completion_tokens": completion_tokens,
            "total_tokens": len(req.prompt_tokens) + completion_tokens,
        }
        if chat:
            choice = {
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": req.finish_reason,
            }
            if req.logprobs is not None:
                choice["logprobs"] = self._chat_logprobs_json(
                    req, req.logprobs, text_limit=len(text))
                try:
                    top_req = int(body.get("top_logprobs") or 0)
                except (TypeError, ValueError):
                    top_req = 0
                if top_req > req.logprobs:
                    # The prefill hop already capped the recorded top-k;
                    # keep the truncation note on the attach path too.
                    choice["logprobs"]["top_logprobs_truncated_to"] = (
                        req.logprobs)
            return web.json_response({
                "id": f"chatcmpl-{req.request_id}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": model,
                "choices": [choice],
                "usage": usage,
            }, headers=trace_headers)
        choice = {
            "index": 0,
            "text": text,
            "finish_reason": req.finish_reason,
        }
        if req.logprobs is not None:
            choice["logprobs"] = self._logprobs_json(
                req, req.logprobs, text_limit=len(text))
        return web.json_response({
            "id": f"cmpl-{req.request_id}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": model,
            "choices": [choice],
            "usage": usage,
            "ttft_ms": round(req.ttft_s * 1000, 2),
        }, headers=trace_headers)

    async def handle_release(self, request: web.Request) -> web.Response:
        """Best-effort release of abandoned disaggregation work: the
        gateway posts ``{"request_id": ...}`` when a decode hop failed
        AFTER the handoff bytes were delivered — the engine may hold the
        imported KV parked (or decoding) with nobody left to read the
        response.  Idempotent; unknown ids answer ``released: false``
        (the request finished, was never admitted, or already swept by
        the engine's ``--handoff-ttl-s`` backstop)."""
        trace_id = self._trace_id_for(request)
        try:
            body = await request.json()
            request_id = body["request_id"]
        except (json.JSONDecodeError, KeyError, TypeError):
            return _err(400, "body must be JSON with request_id", trace_id)
        released = bool(self.engine.release_request(str(request_id)))
        if released:
            logger.info("released abandoned request %s", request_id)
        return web.json_response(
            {"request_id": request_id, "released": released},
            headers={tracing.TRACE_HEADER: trace_id})

    # -- admin -------------------------------------------------------------
    async def handle_models(self, request: web.Request) -> web.Response:
        data = [{"id": self.model_name, "object": "model", "root": self.model_name}]
        if self.lora is not None:
            data += [
                {"id": name, "object": "model", "root": self.model_name,
                 "parent": self.model_name}
                for name in self.lora.running_adapters()
            ]
        return web.json_response({"object": "list", "data": data})

    async def handle_load_adapter(self, request: web.Request) -> web.Response:
        if self.lora is None:
            return _err(400, "LoRA serving is not enabled")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body")
        name = body.get("lora_name")
        path = body.get("lora_path")
        if not name or not path:
            return _err(400, "lora_name and lora_path are required")
        if name in self.aliases:
            # A base-model alias would shadow the adapter in _resolve_model:
            # requests naming it would silently get un-adapted output.
            return _err(409, f"adapter name {name!r} collides with the base "
                             "model's served names")
        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(
                None, lambda: self.lora.load(name, checkpoint_path=path)
            )
        except AdapterError as e:
            return _err(409, str(e))
        except Exception as e:
            logger.exception("adapter load failed")
            return _err(500, f"failed to load adapter: {e}")
        return web.json_response({"status": "ok", "loaded": name})

    async def handle_unload_adapter(self, request: web.Request) -> web.Response:
        if self.lora is None:
            return _err(400, "LoRA serving is not enabled")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body")
        name = body.get("lora_name")
        if not name:
            return _err(400, "lora_name is required")
        try:
            removed = self.lora.unload(name)
        except AdapterBusyError as e:
            return _err(409, str(e))
        if not removed:
            return _err(404, f"adapter {name!r} not loaded")
        return web.json_response({"status": "ok", "unloaded": name})

    async def handle_demote_adapter(self, request: web.Request) -> web.Response:
        """Slot -> host RAM (409 while in-flight/parked requests pin the
        slot; the planner/sidecar just retries next pass)."""
        if self.lora is None:
            return _err(400, "LoRA serving is not enabled")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body")
        name = body.get("lora_name")
        if not name:
            return _err(400, "lora_name is required")
        try:
            demoted = self.lora.demote(name)
        except AdapterBusyError as e:
            return _err(409, str(e))
        except AdapterError as e:
            return _err(409, str(e))
        if not demoted:
            return _err(404, f"adapter {name!r} not slot-resident")
        return web.json_response({"status": "ok", "demoted": name,
                                  "tier": "host"})

    async def handle_prefetch_adapter(self, request: web.Request) -> web.Response:
        """Disk -> host RAM without consuming a device slot (the Orbax
        restore runs off the event loop like a load); idempotent for
        RAM-resident names."""
        if self.lora is None:
            return _err(400, "LoRA serving is not enabled")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body")
        name = body.get("lora_name")
        path = body.get("lora_path")
        if not name or not path:
            return _err(400, "lora_name and lora_path are required")
        if name in self.aliases:
            return _err(409, f"adapter name {name!r} collides with the base "
                             "model's served names")
        loop = asyncio.get_running_loop()
        try:
            fetched = await loop.run_in_executor(
                None, lambda: self.lora.prefetch(name, path))
        except AdapterError as e:
            return _err(409, str(e))
        except Exception as e:
            logger.exception("adapter prefetch failed")
            return _err(500, f"failed to prefetch adapter: {e}")
        return web.json_response({"status": "ok", "prefetched": name,
                                  "already_resident": not fetched})

    async def handle_evict_adapter(self, request: web.Request) -> web.Response:
        """Host RAM -> disk (slot-resident adapters are untouched: demote
        first — eviction must never race a live decode)."""
        if self.lora is None:
            return _err(400, "LoRA serving is not enabled")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _err(400, "invalid JSON body")
        name = body.get("lora_name")
        if not name:
            return _err(400, "lora_name is required")
        if not self.lora.evict_host(name):
            return _err(404, f"adapter {name!r} not host-resident")
        return web.json_response({"status": "ok", "evicted": name})

    # -- ops ---------------------------------------------------------------
    async def handle_metrics(self, request: web.Request) -> web.Response:
        snap = self.engine.metrics_snapshot()
        # The engine doesn't know its served name; the phase-latency
        # histogram families are labeled by model + role at render time.
        snap.setdefault("model_name", self.model_name)
        text = metrics_mod.render(snap)
        # Flight-recorder counters (server-side twin of the gateway's
        # gateway_events_total family).
        text += "\n".join(self.events.render_prom("tpu:events_total")) + "\n"
        text += "\n".join(self._render_transport()) + "\n"
        return web.Response(text=text, content_type="text/plain")

    def _render_transport(self) -> list[str]:
        """What this process measures outside the engine thread: the
        streams' emit-to-write lag and the event loop's stall clock."""
        return [
            "# TYPE tpu:stream_write_lag_seconds_total counter",
            f"tpu:stream_write_lag_seconds_total {self.stream_write_lag_s:.6f}",
            "# TYPE tpu:stream_chunks_total counter",
            f"tpu:stream_chunks_total {self.stream_chunks}",
            *self.loop_clock.render("tpu:loop_lag_seconds_total",
                                    "tpu:loop_ticks_total",
                                    "tpu:loop_stall_seconds_total")]

    async def handle_debug_traces(self, request: web.Request) -> web.Response:
        """Recent traces recorded by THIS replica (``?trace_id=`` filter).
        The gateway's /debug/traces shows the merged cross-process view;
        this endpoint is the replica-local ground truth (streaming decode
        spans live only here)."""
        return web.json_response(
            tracing.debug_traces_payload(self.tracer, request.query))

    async def handle_debug_events(self, request: web.Request) -> web.Response:
        """This replica's flight recorder (admission rejections, handoff
        refusals, drain transitions); same query contract as the gateway's
        ``/debug/events`` (``?since=``/``?kind=``/``?limit=``)."""
        return web.json_response(
            events_mod.debug_events_payload(self.events, request.query))

    async def handle_debug_usage(self, request: web.Request) -> web.Response:
        """This replica's per-adapter capacity attribution (server/usage.py):
        step-seconds / tokens / KV block-seconds per {adapter, phase} plus
        the pool-waste observables — the raw payload the gateway's
        ``gateway/usage.py`` rollup (and ``tools/lig_top.py``) aggregates.
        Tuple keys flatten to ``"adapter|phase"`` strings for JSON."""
        snap = self.engine.metrics_snapshot()
        usage = snap.get("usage") or {}
        flat = dict(usage)
        for key in ("step_seconds", "tokens"):
            flat[key] = {f"{a}|{p}": v
                         for (a, p), v in (usage.get(key) or {}).items()}
        return web.json_response({
            "model": self.model_name,
            "role": snap.get("pool_role", "collocated"),
            "running_lora_adapters": snap.get("running_lora_adapters", []),
            "waiting_lora_adapters": snap.get("waiting_lora_adapters", []),
            # Residency ladder alongside the usage shares (placement
            # plane): tier -> adapter names, so lig-top and operators see
            # WHERE each tenant's weights live, not just what they cost.
            "residency": snap.get("residency", {}),
            "usage": flat,
        })

    async def handle_debug_profile(self, request: web.Request) -> web.Response:
        """The step-timeline profiler's full payload (server/profiler.py):
        dispatch/host-sync/idle attribution summary, wall+gap histogram
        states, the newest per-dispatch records and this process's
        ``clock`` pair (``tracing.clock_pair``) — what
        ``tools/profile_report.py`` renders and the fleet collector's
        black-box dumps embed.  404 from an engine stand-in that has no
        profiler (every ``Engine`` has one)."""
        profiler = getattr(self.engine, "profiler", None)
        if profiler is None:
            return _err(404, "this engine has no step profiler")
        return web.json_response({"model": self.model_name,
                                  "role": self.engine.cfg.role,
                                  "clock": tracing.clock_pair(),
                                  **profiler.snapshot()})

    async def handle_debug_kv(self, request: web.Request) -> web.Response:
        """The KV economy ledger's full payload (server/kv_ledger.py):
        block-state accounting, per-prefix reuse heatmap, fragmentation
        histograms, and the lifecycle event ring — what
        ``tools/kv_report.py`` renders, the gateway's ``gateway/kvobs.py``
        duplication index joins, and black-box dumps embed.  404 when the
        engine runs the contiguous-lane cache, which has no block economy
        and so no ledger."""
        ledger = getattr(self.engine, "kv_ledger", None)
        if ledger is None:
            return _err(404, "no kv ledger: the cache is not paged "
                             "(--paged-kv-block)")
        self.engine._kv_ledger_sync()
        return web.json_response({"model": self.model_name,
                                  "role": self.engine.cfg.role,
                                  **ledger.snapshot()})

    async def handle_debug_device(self, request: web.Request) -> web.Response:
        """The device this process landed on and what it holds there.
        ``memory_stats`` is null where the backend reports none (the CPU)."""
        import dataclasses

        import jax

        devices = jax.devices()
        by_dtype: dict[str, int] = {}
        by_device = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves(self.engine.params):
            name = str(leaf.dtype)
            by_dtype[name] = by_dtype.get(name, 0) + leaf.nbytes
            for shard in leaf.addressable_shards:
                by_device[shard.device.id] += shard.data.nbytes
        cfg = self.engine.model_cfg
        keep = ("name", "vocab_size", "d_model", "n_layers", "n_heads",
                "n_kv_heads", "d_ff", "attention_bias", "n_experts",
                "n_experts_per_token", "norm_topk_prob", "qk_norm",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "moe_d_ff",
                "n_shared_experts", "first_k_dense", "router_sigmoid",
                "routed_scaling_factor", "ssm_d_inner", "ssm_n_heads",
                "ssm_head_dim", "ssm_d_state", "ssm_n_groups", "ssm_d_conv",
                "ssm_chunk", "layer_pattern", "sliding_window",
                "router_pre_attention", "mlp_activation", "qk_norm_head",
                "conv_kernel", "tie_embeddings",
                "router_gate_eps", "n_experts_local", "expert_first",
                "n_group", "topk_group", "kda_n_heads", "kda_head_dim",
                "kda_conv", "kda_lower_bound", "mla_head_gate")
        return web.json_response({
            "model": self.model_name,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "devices": [{"id": d.id, "weight_bytes": by_device[d.id],
                         "memory_stats": d.memory_stats()}
                        for d in devices],
            "model_config": {
                **{k: v for k, v in dataclasses.asdict(cfg).items()
                   if k in keep},
                "head_dim": cfg.resolved_head_dim},
            "weight_bytes_by_dtype": by_dtype,
            "mesh": (None if self.engine.mesh is None
                     else dict(self.engine.mesh.shape)),
        })

    async def handle_health(self, request: web.Request) -> web.Response:
        if self.engine.draining:
            # Readiness flip: the EPP's health-probed membership (and a k8s
            # readinessProbe) drops a draining replica from the routable
            # set while in-flight requests finish.  (On the SIGTERM path
            # aiohttp closes the listener before on_shutdown, so probes see
            # connection-refused instead — the same unready outcome; this
            # branch serves keep-alive connections and any future admin-
            # initiated drain.)
            return web.Response(status=503, text="draining")
        return web.Response(text="ok")


def _err(status: int, message: str,
         trace_id: str | None = None) -> web.Response:
    """Error envelope; when a trace id is known it rides both the body and
    the header so failed requests stay correlatable."""
    error: dict = {"message": message, "type": "invalid_request_error"}
    if trace_id:
        error["trace_id"] = trace_id
    return web.json_response(
        {"error": error},
        status=status,
        headers={tracing.TRACE_HEADER: trace_id} if trace_id else None,
    )


def main(argv=None) -> None:
    import jax.numpy as jnp
    import jax

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import ModelConfig
    from llm_instance_gateway_tpu.models import llama, gemma, mixtral, qwen
    from llm_instance_gateway_tpu.server.engine import EngineConfig

    all_configs: dict[str, ModelConfig] = {}
    for mod in (llama, gemma, mixtral, qwen):
        all_configs.update(mod.CONFIGS)

    parser = argparse.ArgumentParser(description="TPU model server")
    parser.add_argument("--model", default="llama3-tiny", choices=sorted(all_configs))
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--decode-slots", type=int, default=8)
    parser.add_argument("--max-seq-len", type=int, default=1024)
    parser.add_argument("--max-loras", type=int, default=4)
    parser.add_argument(
        "--host-cache-slots", type=int, default=8,
        help="host-RAM adapter cache size (the middle tier of the "
             "slot -> host -> disk residency ladder): demoted/prefetched "
             "adapters park here so promotion is one device put instead "
             "of an Orbax restore; 0 disables the tier")
    parser.add_argument(
        "--prefill-buckets", type=int, nargs="+", default=None,
        metavar="N",
        help="prefill bucket sizes (compiled-shape set). Default: powers "
             "of two up to min(--max-seq-len, 1024) — the 1024 cap keeps "
             "the long-prompt window open on big-context servers, where "
             "prompts above the largest bucket chunk-stream interleaved "
             "with decode (one monolithic prefill would freeze every "
             "active slot's TPOT) or run ONE ring-attention program when "
             "--mesh has a sequence axis")
    parser.add_argument("--decode-steps", type=int, default=8,
                        help="fused decode steps per host sync (K); "
                             "superseded when --adaptive-steps is set")
    parser.add_argument("--adaptive-steps", type=int, default=8,
                        metavar="CEILING",
                        help="adaptive multi-step dispatch: a per-dispatch "
                             "planner picks the fused step count (power of "
                             "two <= CEILING) from remaining budgets, "
                             "pending admissions/chunk streams, and SSE "
                             "cadence; 0 = static --decode-steps")
    parser.add_argument("--stream-lanes", type=int, default=1,
                        help="concurrent chunk-stream lanes: how many "
                             "long prompts may stream into reserved cache "
                             "lanes at once (fair round-robin); 1 = a "
                             "second long prompt head-of-line waits")
    parser.add_argument("--stream-burst", type=int, default=1,
                        help="chunk programs one engine turn may enqueue "
                             "before its decode block; above 1 a backlog "
                             "of long prompts leaves the waiting queue "
                             "sooner and the live rows wait that many "
                             "chunks for their next token")
    parser.add_argument("--prefill-batch", type=int, default=1,
                        help="group up to P same-bucket queued prompts into "
                             "one prefill program (contiguous-lane cache)")
    parser.add_argument("--quantize", choices=["none", "int8"], default="none",
                        help="weight-only quantization of the big projections")
    parser.add_argument("--tokenizer", default=None, help="local HF tokenizer dir")
    parser.add_argument("--checkpoint", default=None, help="Orbax params dir")
    parser.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    parser.add_argument(
        "--platform", default=None, choices=["cpu", "tpu"],
        help="pin the JAX platform. Default: whatever JAX finds — and the "
             "server EXITS if that is the CPU, unless the CPU was asked "
             "for by name (here, or in JAX_PLATFORMS)",
    )
    parser.add_argument(
        "--paged-kv-block", type=int, default=None, metavar="TOKENS",
        help="enable the paged KV cache with this block size (e.g. 64); "
             "kv metrics then report allocated/total blocks (vLLM "
             "gpu_cache_usage_perc semantics)",
    )
    parser.add_argument(
        "--drain-grace", type=float, default=30.0, metavar="S",
        help="graceful termination: on SIGTERM, flip /health to 503 (the "
             "EPP drops the replica), refuse new requests, and give "
             "in-flight ones this many seconds to finish",
    )
    parser.add_argument(
        "--kv-quantize", choices=("none", "int8"), default="none",
        help="store the KV cache int8 with per-position/head scales — "
             "halves the HBM traffic long-context decode is bound by "
             "(composes with both the contiguous-lane and paged caches, "
             "prefix caching included)",
    )
    parser.add_argument(
        "--paged-kv-blocks", type=int, default=None, metavar="N",
        help="paged pool size in blocks; below slots*ceil(max_seq/block) "
             "oversubscribes HBM for short-sequence traffic",
    )
    parser.add_argument(
        "--speculative", type=int, default=0, metavar="K",
        help="speculative decoding: a draft model proposes K tokens per "
             "cycle, the target verifies them in one multi-token forward; "
             "greedy requests keep exact parity.  Requires --draft-model",
    )
    parser.add_argument(
        "--draft-model", default=None, choices=sorted(all_configs),
        help="model preset for the speculative draft (must share the "
             "target's tokenizer/vocab), e.g. gemma-2b under gemma-7b",
    )
    parser.add_argument(
        "--draft-checkpoint", default=None,
        help="Orbax params dir for the draft model (random weights "
             "otherwise — dev mode)",
    )
    parser.add_argument(
        "--role", choices=("collocated", "prefill", "decode"),
        default="collocated",
        help="disaggregation role: 'prefill' replicas serve /v1/prefill "
             "handoffs, 'decode' replicas admit them via /v1/attach, "
             "'collocated' (default) serves whole requests; the role is "
             "advisory — every server keeps the full API",
    )
    parser.add_argument(
        "--prefix-cache", action="store_true",
        help="retain finished prompts' full KV blocks (content-addressed, "
             "refcounted) so prompts sharing a prefix skip recomputing it; "
             "requires --paged-kv-block",
    )
    parser.add_argument(
        "--handoff-ttl-s", type=float, default=60.0,
        help="abandoned-handoff backstop: an attach-imported request still "
             "parked in decode_wait this many seconds after admission is "
             "presumed abandoned (its gateway rerouted) and is released; "
             "0 disables. The gateway's POST /v1/prefill/release is the "
             "fast path, this TTL the safety net.",
    )
    parser.add_argument(
        "--mesh", default=None, metavar="AXIS=N[,AXIS=N...]",
        help="serve sharded over a device mesh, e.g. 'tensor=8' on a v5e-8 "
             "pool or 'data=2,tensor=4'; axes: data,fsdp,tensor,expert,"
             "sequence (parallel/mesh.py). Default: single device.",
    )
    parser.add_argument("-v", "--verbose", action="count", default=0)
    args = parser.parse_args(argv)

    if args.paged_kv_blocks is not None and args.paged_kv_block is None:
        parser.error("--paged-kv-blocks requires --paged-kv-block")
    if args.prefill_buckets and max(args.prefill_buckets) > args.max_seq_len:
        # A silently-ignored oversized bucket would also inflate _ring_pad
        # and close the ring window with no diagnostic.
        parser.error(
            f"--prefill-buckets {max(args.prefill_buckets)} exceeds "
            f"--max-seq-len {args.max_seq_len}")
    if args.prefix_cache and args.paged_kv_block is None:
        parser.error("--prefix-cache requires --paged-kv-block")
    if args.speculative > 0 and args.draft_model is None:
        parser.error("--speculative requires --draft-model")
    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.INFO)
    from llm_instance_gateway_tpu import runtime

    runtime.resolve_platform(args.platform)
    logger.info("compile cache: %s", runtime.configure_compile_cache())
    import dataclasses
    cfg = dataclasses.replace(all_configs[args.model], max_lora_slots=args.max_loras)
    if cfg.latent_width and args.max_loras > 0:
        raise SystemExit(
            f"{args.model} keeps a latent (MLA) KV cache: adapters are not "
            "served over latent projections (models/lora.py sizes its "
            "targets from per-head q, k, v); start it with --max-loras 0")
    if cfg.ssm_d_inner and (args.max_loras > 0 or args.mesh):
        raise SystemExit(
            f"{args.model} keeps a recurrent (state-space) state beside its "
            "KV lanes: it is served on one device, base model only; start "
            "it with --max-loras 0 and without --mesh")
    if cfg.layer_pattern and (args.max_loras > 0 or args.mesh):
        raise SystemExit(
            f"{args.model} scans a period of layer kinds over "
            + ("a conv state beside the K/V lanes of its attention layers"
               if cfg.conv_kernel
               else "a delta-rule state beside the latent rows of its "
               "latent layers" if cfg.kda_n_heads
               else "ring lanes beside its full lanes")
            + ": it is served on one device, base model only; start it "
            "with --max-loras 0 and without --mesh")
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    tokenizer = load_tokenizer(args.tokenizer)
    served_name = args.model  # CLI preset alias (cfg.name can differ from it)
    host_params = None
    if args.checkpoint:
        from llm_instance_gateway_tpu.models.convert import load_serving_checkpoint

        ckpt_cfg, host_params = load_serving_checkpoint(args.checkpoint)
        if ckpt_cfg is not None:
            # Converted checkpoints carry their architecture; the preset
            # --model only contributes serving knobs like max_lora_slots.
            cfg = dataclasses.replace(
                ckpt_cfg, max_lora_slots=args.max_loras,
                max_lora_rank=cfg.max_lora_rank,
            )
            served_name = cfg.name  # checkpoint architectures bring their name
            logger.info("model config restored from checkpoint: %s", cfg.name)
        logger.info("restored params from %s", args.checkpoint)
    # Validate AFTER the checkpoint may have replaced the architecture.
    if tokenizer.vocab_size > cfg.vocab_size:
        raise SystemExit(
            f"tokenizer vocab {tokenizer.vocab_size} exceeds model vocab {cfg.vocab_size}"
        )

    mesh = shardings = None
    quantize = args.quantize == "int8"
    if args.mesh:
        from llm_instance_gateway_tpu.parallel.mesh import (
            MeshConfig, initialize_distributed, make_mesh,
        )
        from llm_instance_gateway_tpu.parallel.sharding import param_shardings

        initialize_distributed()  # no-op single-host; DCN wiring on pods
        axes = {}
        for part in args.mesh.split(","):
            k, _, v = part.partition("=")
            axes[k.strip()] = int(v)
        mesh = make_mesh(MeshConfig(**axes))
        shardings = param_shardings(cfg, mesh, quantized=quantize)
        logger.info("serving sharded over mesh %s", dict(mesh.shape))

    # Weights reach the device one leaf at a time, already quantized and
    # (on a mesh) already sharded: neither the f32 draw nor the dense bf16
    # tree of a 7B model fits a 16 GB chip beside the int8 one.
    from llm_instance_gateway_tpu.ops.quant import quantize_params

    if host_params is None:
        logger.warning("no --checkpoint: serving RANDOM weights (dev mode)")
        params = transformer.init_params(
            cfg, jax.random.PRNGKey(0), dtype=dtype, quantize=quantize,
            shardings=shardings)
    elif quantize:
        params = quantize_params(host_params, shardings=shardings)
    else:
        params = host_params
    if mesh is None:
        params = jax.device_put(params)  # host leaves -> the one device
    del host_params
    if quantize:
        logger.info("weights quantized to int8 (per-output-channel)")

    draft_params = draft_cfg = None
    if args.speculative > 0:
        draft_cfg = all_configs[args.draft_model]
        if args.draft_checkpoint:
            from llm_instance_gateway_tpu.models.convert import (
                load_serving_checkpoint,
            )

            dc, draft_params = load_serving_checkpoint(args.draft_checkpoint)
            if dc is not None:
                draft_cfg = dc
        else:
            logger.warning("no --draft-checkpoint: draft uses RANDOM "
                           "weights (dev mode — proposals rarely accepted)")
            draft_params = transformer.init_params(
                draft_cfg, jax.random.PRNGKey(1), dtype=dtype)
        if quantize:
            draft_params = quantize_params(draft_params)
        if mesh is None:  # on a mesh the engine replicates the draft
            draft_params = jax.device_put(draft_params)

    # --max-loras 0: no adapter buffers at all, so no program computes a
    # delta over zero slots.
    lora_manager = None if args.max_loras == 0 else LoRAManager(
        cfg, dtype=dtype, mesh=mesh, host_cache_slots=args.host_cache_slots)
    engine = Engine(
        cfg, params,
        EngineConfig(
            decode_slots=args.decode_slots, max_seq_len=args.max_seq_len,
            prefill_buckets=(
                tuple(sorted(args.prefill_buckets))
                if args.prefill_buckets else
                tuple(b for b in (16, 32, 64, 128, 256, 512, 1024)
                      if b <= args.max_seq_len)
                or (min(args.max_seq_len, 1024),)),
            decode_steps_per_sync=args.decode_steps,
            adaptive_steps=args.adaptive_steps,
            stream_lanes=args.stream_lanes,
            stream_burst=args.stream_burst,
            prefill_batch=args.prefill_batch,
            paged_kv_block=args.paged_kv_block,
            paged_kv_blocks=args.paged_kv_blocks,
            prefix_cache=args.prefix_cache,
            role=args.role,
            handoff_ttl_s=args.handoff_ttl_s,
            speculative_k=args.speculative,
            kv_cache_quant=(None if args.kv_quantize == "none"
                            else args.kv_quantize),
        ),
        lora_manager=lora_manager,
        eos_id=tokenizer.eos_id,
        dtype=dtype,
        mesh=mesh,
        draft_params=draft_params,
        draft_cfg=draft_cfg,
    )
    engine.start()
    server = ModelServer(engine, tokenizer, served_name, lora_manager,
                         aliases={args.model})
    app = server.build_app()

    async def _graceful_drain(app_):
        # SIGTERM path (aiohttp stops accepting, then runs on_shutdown while
        # in-flight handlers get shutdown_timeout to finish): flip /health
        # to 503, refuse new submits, and let the engine decode the
        # in-flight work to completion before the loop stops.
        logger.info("draining engine (grace %.0fs)", args.drain_grace)
        loop = asyncio.get_running_loop()
        drained = await loop.run_in_executor(
            None, lambda: engine.drain(args.drain_grace))
        logger.info("drain %s", "complete" if drained else "timed out")

    app.on_shutdown.append(_graceful_drain)
    try:
        web.run_app(app, port=args.port,
                    shutdown_timeout=args.drain_grace + 30)
    finally:
        engine.stop()


if __name__ == "__main__":
    main()
