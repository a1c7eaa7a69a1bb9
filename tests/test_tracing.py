"""Tracing substrate unit tests: span ring, sampling, wire format,
histogram exposition (llm_instance_gateway_tpu/tracing.py)."""

import functools
import json

import pytest

from llm_instance_gateway_tpu import tracing
from llm_instance_gateway_tpu.utils import prom_parse


class TestTraceIds:
    def test_mint_shape_and_uniqueness(self):
        ids = {tracing.new_trace_id() for _ in range(1000)}
        assert len(ids) == 1000
        assert all(len(t) == 16 and int(t, 16) >= 0 for t in ids)

    def test_header_lookup_case_insensitive(self):
        assert tracing.header_trace_id({"X-Lig-Trace-Id": "abc"}) == "abc"
        assert tracing.header_trace_id({"x-lig-trace-id": "abc"}) == "abc"
        assert tracing.header_trace_id({"other": "x"}) is None


class TestTracer:
    def test_record_and_export(self):
        tr = tracing.Tracer(capacity=8)
        tr.record("t1", "b", 2.0, 3.0)
        tr.record("t1", "a", 1.0, 2.0, pod="p0")
        tr.annotate("t1", model="m", path="collocated", status="ok")
        t = tr.get("t1")
        assert t["model"] == "m" and t["path"] == "collocated"
        assert t["status"] == "ok"
        # Spans export sorted by start time regardless of record order.
        assert [s["name"] for s in t["spans"]] == ["a", "b"]
        assert t["spans"][0]["attrs"] == {"pod": "p0"}
        assert t["t_created"] == 1.0

    def test_ring_bounds_memory(self):
        tr = tracing.Tracer(capacity=4)
        for i in range(200):
            tr.record(f"t{i}", "s", float(i), float(i + 1))
        recent = tr.recent(1000)
        # The flat ring holds capacity*16 span records; old traces age out.
        assert 0 < len(recent) <= 4 * 16
        assert tr.get("t0") is None  # evicted
        assert tr.get("t199") is not None

    def test_recent_most_recent_first(self):
        tr = tracing.Tracer(capacity=16)
        for i in range(5):
            tr.record(f"t{i}", "s", float(i), float(i + 1))
        assert [t["trace_id"] for t in tr.recent(3)] == ["t4", "t3", "t2"]

    def test_disabled_and_zero_sample_record_nothing(self):
        for tr in (tracing.Tracer(enabled=False),
                   tracing.Tracer(sample=0.0)):
            tr.record("t", "s", 1.0, 2.0)
            assert tr.recent(10) == []
            assert not tr.sampled("t")

    def test_sampling_is_deterministic_per_trace(self):
        a = tracing.Tracer(sample=0.5)
        b = tracing.Tracer(sample=0.5)
        ids = [tracing.new_trace_id() for _ in range(256)]
        decisions = [a.sampled(t) for t in ids]
        # Deterministic hash: a second tracer (= another process) agrees on
        # every trace, so cross-process traces are complete or absent.
        assert decisions == [b.sampled(t) for t in ids]
        assert any(decisions) and not all(decisions)

    def test_wire_round_trip(self):
        spans = [("engine.prefill", 10.0, 10.5), ("engine.decode", 10.5, 12.0)]
        header = tracing.wire_spans(spans)
        assert json.loads(header)  # valid compact JSON
        tr = tracing.Tracer()
        tr.record_wire("t", header)
        assert [s["name"] for s in tr.get("t")["spans"]] == [
            "engine.prefill", "engine.decode"]

    def test_wire_parse_tolerates_junk(self):
        assert tracing.parse_wire("not json") == []
        assert tracing.parse_wire('[["only-name"]]') == []
        assert tracing.parse_wire('[["n", 1, 2], ["bad"], ["m", 3, 4]]') == [
            ("n", 1.0, 2.0), ("m", 3.0, 4.0)]


class TestTraceCursor:
    """``/debug/traces?since=`` incremental cursor (ISSUE 12 satellite):
    the /debug/events paging contract lifted to trace granularity, so
    the fleet collector and --watch tooling poll deltas instead of
    re-shipping the whole ring."""

    def test_seq_is_monotonic_across_record_kinds(self):
        t = tracing.Tracer()
        t.record("t1", "a", 1.0, 2.0)
        t.record_wire("t1", tracing.wire_spans([("b", 2.0, 3.0)]))
        t.annotate("t1", model="m")
        assert t.seq == 3

    def test_since_returns_only_new_records(self):
        t = tracing.Tracer()
        t.record("t1", "a", 1.0, 2.0)
        payload = tracing.debug_traces_payload(t, {"since": "0"})
        assert payload["next_since"] == 1
        assert [s["name"] for s in payload["traces"][0]["spans"]] == ["a"]
        t.record("t1", "b", 2.0, 3.0)
        t.record("t2", "c", 3.0, 4.0)
        payload = tracing.debug_traces_payload(
            t, {"since": str(payload["next_since"])})
        assert payload["seq"] == 3 and payload["next_since"] == 3
        by_id = {tr["trace_id"]: tr for tr in payload["traces"]}
        # Only the DELTA ships: t1's already-polled span "a" stays home.
        assert [s["name"] for s in by_id["t1"]["spans"]] == ["b"]
        assert [s["name"] for s in by_id["t2"]["spans"]] == ["c"]

    def test_caught_up_poll_returns_nothing(self):
        t = tracing.Tracer()
        t.record("t1", "a", 1.0, 2.0)
        payload = tracing.debug_traces_payload(t, {"since": "1"})
        assert payload["traces"] == []
        assert payload["next_since"] == payload["seq"] == 1

    def test_truncated_page_never_skips_a_record(self):
        """Lossless paging: when ``limit`` truncates, the cursor retreats
        to just before the first excluded trace's oldest record — a
        poller may re-receive a span (the stitcher dedups) but can never
        lose one, even with interleaved traces."""
        t = tracing.Tracer()
        t.record("tA", "a1", 1.0, 2.0)   # seq 1
        t.record("tB", "b1", 2.0, 3.0)   # seq 2
        t.record("tA", "a2", 3.0, 4.0)   # seq 3
        page1 = tracing.debug_traces_payload(
            t, {"since": "0", "limit": "1"})
        assert [tr["trace_id"] for tr in page1["traces"]] == ["tA"]
        # tB (oldest record seq 2) was excluded: cursor retreats to 1.
        assert page1["next_since"] == 1
        page2 = tracing.debug_traces_payload(
            t, {"since": str(page1["next_since"])})
        by_id = {tr["trace_id"]: tr for tr in page2["traces"]}
        assert [s["name"] for s in by_id["tB"]["spans"]] == ["b1"]
        assert [s["name"] for s in by_id["tA"]["spans"]] == ["a2"]

    def test_hostile_since_falls_back(self):
        t = tracing.Tracer()
        t.record("t1", "a", 1.0, 2.0)
        payload = tracing.debug_traces_payload(t, {"since": "zzz"})
        assert len(payload["traces"]) == 1

    def test_plain_payload_shape_unchanged(self):
        """Without ?since= the historical contract holds (most recent
        first, no next_since key) — plus the new head seq."""
        t = tracing.Tracer()
        t.record("t1", "a", 1.0, 2.0)
        payload = tracing.debug_traces_payload(t, {})
        assert "next_since" not in payload
        assert payload["seq"] == 1
        assert payload["traces"][0]["trace_id"] == "t1"


class TestHistogramRender:
    def test_custom_buckets_size_counts(self):
        h = tracing.Histogram(tracing.LATENCY_BUCKETS)
        assert len(h.counts) == len(tracing.LATENCY_BUCKETS) + 1
        h.observe(0.003)
        h.observe(100.0)  # overflow bucket
        assert h.n == 2 and h.counts[-1] == 1

    def test_exposition_shape(self):
        h = tracing.Histogram((0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        lines = tracing.render_histogram("f_seconds", h, {"model": "m"})
        text = "\n".join(lines) + "\n"
        fams = prom_parse.parse_text(text)
        buckets = fams["f_seconds_bucket"]
        # Cumulative counts: 1 (<=0.1), 2 (<=1.0), 3 (+Inf).
        assert [s.value for s in buckets] == [1.0, 2.0, 3.0]
        assert [s.labels["le"] for s in buckets] == ["0.1", "1", "+Inf"]
        assert all(s.labels["model"] == "m" for s in buckets)
        assert fams["f_seconds_count"][0].value == 3
        assert abs(fams["f_seconds_sum"][0].value - 5.55) < 1e-9

    def test_label_escaping(self):
        h = tracing.Histogram((1.0,))
        h.observe(0.5)
        hostile = 'bad"model\nname\\x'
        text = "\n".join(
            tracing.render_histogram("f_seconds", h, {"model": hostile})) + "\n"
        fams = prom_parse.parse_text(text)
        # The parser unescapes back to the original hostile value — the
        # exposition stayed well-formed.
        assert fams["f_seconds_bucket"][0].labels["model"] == hostile


# ---------------------------------------------------------------------------
# Names the device trace can show: every model block sits in a
# jax.named_scope, so a compiled operation's name says which block it is.
# ---------------------------------------------------------------------------

DENSE_SCOPES = ("embed", "attn.qkv", "attn.rope", "attn.core", "attn.out",
                "mlp", "lora", "lm_head")
MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts")
STEP_SCOPES = ("sample", "sample.topk_sort", "logprobs", "stops")


@functools.lru_cache(maxsize=None)
def lowered_text(which: str) -> str:
    """The lowered program text, with locations, of one model function at
    a tiny preset (traced once per function for the whole module)."""
    import jax
    import jax.numpy as jnp

    from llm_instance_gateway_tpu.models import lora as lora_lib
    from llm_instance_gateway_tpu.models import paged, transformer
    from llm_instance_gateway_tpu.models.configs import (
        TINY_MOE_TEST,
        TINY_TEST,
    )

    moe = which.endswith("_moe")
    cfg = TINY_MOE_TEST if moe else TINY_TEST
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0),
                                        dtype=jnp.float32))
    bufs = None if moe else jax.eval_shape(
        lambda: lora_lib.init_lora_buffers(cfg, dtype=jnp.float32))
    b, s = 16, 8
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    if which.startswith("prefill"):
        fn = functools.partial(transformer.prefill, cfg)
        args = (params, i32((b, s)), i32((b, s)), bufs, i32((b,)))
    elif which.startswith("decode_step_paged"):
        cache = jax.eval_shape(lambda: paged.init_paged_cache(
            cfg, b, max_len=32, n_blocks=80, block=8, dtype=jnp.float32))
        fn = functools.partial(paged.decode_step_paged, cfg)
        args = (params, cache, i32((b,)), i32((b,)), bufs, i32((b,)))
    elif which.startswith("decode_step"):
        cache = jax.eval_shape(lambda: transformer.init_decode_cache(
            cfg, b, 32, dtype=jnp.float32))
        fn = functools.partial(transformer.decode_step, cfg)
        args = (params, cache, i32((b,)), i32((b,)), bufs, i32((b,)))
    else:
        raise KeyError(which)
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def has_scope(text: str, scope: str) -> bool:
    """``scope`` is a whole component of some operation's name (inside a
    scan body the name starts at the body: ``"attn.qkv/dot_general"``)."""
    return f"/{scope}/" in text or f'"{scope}/' in text


class TestNamedScopes:
    @pytest.mark.parametrize("scope", DENSE_SCOPES + ("attn.kv_update",))
    def test_decode_step_names_its_blocks(self, scope):
        assert has_scope(lowered_text("decode_step"), scope)

    @pytest.mark.parametrize("scope", DENSE_SCOPES)
    def test_prefill_names_its_blocks(self, scope):
        assert has_scope(lowered_text("prefill"), scope)

    @pytest.mark.parametrize("scope", MOE_SCOPES)
    @pytest.mark.parametrize("which", ["decode_step_moe", "prefill_moe"])
    def test_moe_names_its_blocks(self, which, scope):
        text = lowered_text(which)
        assert has_scope(text, scope)
        assert not has_scope(text, "mlp")  # the dense block is not traced

    @pytest.mark.parametrize("scope", ("attn.kv_update", "attn.core",
                                       "attn.out", "embed", "lm_head"))
    def test_paged_decode_step_names_its_blocks(self, scope):
        assert has_scope(lowered_text("decode_step_paged"), scope)

    @pytest.mark.parametrize("scope", STEP_SCOPES)
    def test_decode_block_names_sampling_and_stops(self, scope):
        """The engine's own program: the sampler, the full-vocabulary
        sort inside it, the logprobs and the stop automata."""
        import jax
        import jax.numpy as jnp

        from llm_instance_gateway_tpu.server.engine import Engine

        text = _decode_block_text(jax, jnp, Engine)
        assert has_scope(text, scope)
        assert "jit(decode_block)" in text


    def test_decode_block_sorts_and_draws_only_inside_a_branch(self):
        """The sampler's conditional in the decode step's body: the argmax
        branch hands back a value computed outside it and nothing else;
        every sort, cumulative sum and random draw of the body sits in
        one of the other two branches, the sort in the last alone."""
        import re

        import jax
        import jax.numpy as jnp

        from llm_instance_gateway_tpu.server.engine import Engine

        text = _decode_block_text(jax, jnp, Engine)
        lines = text.splitlines()
        heavy = re.compile(
            r"stablehlo\.(sort|rng)|call @(sort|cumsum|_gumbel|_uniform"
            r"|_threefry_fold_in|threefry2x32|random_bits)")
        # The sampler's switch: the outermost conditional of the function
        # that calls the sort (a step's body; its helpers are private
        # functions of their own).
        sort_call = next(i for i, l in enumerate(lines) if "call @sort" in l)
        start = max(i for i in range(sort_call)
                    if lines[i].lstrip().startswith("func.func"))
        end = next(i for i in range(sort_call, len(lines))
                   if lines[i].startswith("  }"))
        case = next(i for i in range(start, sort_call)
                    if '"stablehlo.case"' in lines[i])
        pad = " " * (len(lines[case]) - len(lines[case].lstrip()))
        cuts = [case]
        for i in range(case + 1, end):
            if lines[i] == pad + "}, {":
                cuts.append(i)
            elif lines[i].startswith(pad + "}) :"):
                cuts.append(i)
                break
        branches = [lines[a + 1:b] for a, b in zip(cuts, cuts[1:])]
        assert len(branches) == 3  # metrics_registry.SAMPLE_PATHS
        argmax, draw, filtered = branches
        assert len(argmax) == 1 and "stablehlo.return" in argmax[0]
        assert any("call @_gumbel" in l for l in draw)
        assert not any(re.search(r"call @(sort|cumsum)", l) for l in draw)
        assert any("call @sort" in l for l in filtered)
        assert any("call @cumsum" in l for l in filtered)
        assert any("call @_gumbel" in l for l in filtered)
        outside = lines[start:case] + lines[cuts[-1]:end]
        assert not [l for l in outside if heavy.search(l)]
        # ...and the scope that names the sort is in that branch alone.
        scoped = re.findall(r'loc\("([^"]*sample\.topk_sort[^"]*)"', text)
        assert scoped and all("branch_2_fun/sample.topk_sort" in n
                              for n in scoped)


@functools.lru_cache(maxsize=None)
def _decode_block_text(jax, jnp, Engine) -> str:
    import math

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server.engine import (
        _SLOT_F32,
        _SLOT_I32,
        _named,
    )
    from llm_instance_gateway_tpu.server.sampling import STOP_LEN

    cfg, b = TINY_TEST, 2
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0),
                                        dtype=jnp.float32))
    cache = jax.eval_shape(lambda: transformer.init_decode_cache(
        cfg, b, 32, dtype=jnp.float32))
    fn = jax.jit(
        _named("decode_block", Engine._decode_impl, cfg,
               transformer.decode_step),
        static_argnames=("n_steps", "penalized"))

    def flat(fields, dtype):  # the engine's buffer of b rows of fields
        return jax.ShapeDtypeStruct(
            (b * sum(math.prod(shape) for _, shape, _ in fields),), dtype)

    row = jax.ShapeDtypeStruct((b,), jnp.int32)
    carry = (row, row, row, jax.ShapeDtypeStruct((b, STOP_LEN), jnp.int32))
    return fn.lower(
        params, None, cache, flat(_SLOT_I32, jnp.int32),
        flat(_SLOT_F32, jnp.float32), carry, jax.random.PRNGKey(0),
        jnp.int32(-1), jax.ShapeDtypeStruct((b, 1), jnp.int32),
        n_steps=1, penalized=False,
    ).as_text(debug_info=True)
