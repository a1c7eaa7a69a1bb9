"""A decode block none of whose rows names an adapter runs the decode program
without the LoRA delta (``Engine._block_lora_buffers``).

The engine holds adapter buffers (a ``lora_manager`` with a non-zero adapter
resident) and every decode dispatch is recorded at the jitted call: whether
it was handed the buffers, how many rows of the int32 buffer that went up
with it named an adapter, whether a block was in flight.  What the requests
got is held against ``tests/_reference.py`` (no engine; the adapter merged
into the weights, ``W + alpha / r * a @ b``, so no line of ``models/lora.py``
is shared) and against each request alone through the same engine.
"""

import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    _SLOT_I32,
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
    _slot_views,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager
from tests._reference import reference_tokens

CFG = TINY_TEST
SLOTS = 4
ADAPTER = "tuned"
ALPHA, RANK = 4.0, 2
# The layer weight each LoRA target corrects (models/transformer.py).
WEIGHT_OF = {"q": "wq", "k": "wk", "v": "wv", "o": "wo", "gate": "w_gate",
             "up": "w_up", "down": "w_down"}
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def wait_for(cond, what: str, timeout_s: float = 180.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


@pytest.fixture(scope="module")
def model():
    """Float32 weights, one adapter over every target, and the weights with
    that adapter merged in (the reference's model for an adapter row)."""
    params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    rng = np.random.RandomState(3)
    dims = target_dims(CFG)
    weights = {
        t: {"a": rng.randn(CFG.n_layers, dims[t][0], RANK) * 0.3,
            "b": rng.randn(CFG.n_layers, RANK, dims[t][1]) * 0.3}
        for t in WEIGHT_OF}
    layers = dict(params["layers"])
    for t, name in WEIGHT_OF.items():
        layers[name] = layers[name] + jnp.asarray(
            ALPHA / RANK * np.einsum("lir,lro->lio", weights[t]["a"],
                                     weights[t]["b"]), jnp.float32)
    return types.SimpleNamespace(params=params, weights=weights,
                                 merged={**params, "layers": layers})


def make_engine(model, adapters: bool = True, **extra) -> Engine:
    lora = None
    if adapters:
        lora = LoRAManager(CFG, dtype=jnp.float32)
        lora.load(ADAPTER, weights=model.weights, alpha=ALPHA, rank=RANK)
    base = dict(decode_slots=SLOTS, max_seq_len=96, prefill_buckets=(8, 16))
    base.update(extra)
    return Engine(CFG, model.params, EngineConfig(**base), lora_manager=lora,
                  eos_id=None, dtype=jnp.float32)


class Dispatches:
    """Every decode dispatch of ``engine`` as ``(kind, handed the adapter
    buffers, rows of the staged slots naming an adapter, steps, a block was
    in flight)``, recorded at the jitted call itself; beside each, in
    ``bufs``, the adapter buffers it was handed (the dict itself)."""

    def __init__(self, engine: Engine):
        self.seen: list[tuple] = []
        self.bufs: list[dict | None] = []
        self.plain = engine._jit_decode

        def decode(params, lora_bufs, cache, i32, *rest, n_steps, **kw):
            slots = _slot_views(np.asarray(i32), _SLOT_I32, SLOTS)["lora"]
            self.seen.append(("decode", lora_bufs is not None,
                              int((slots >= 0).sum()), n_steps,
                              engine._inflight is not None))
            self.bufs.append(lora_bufs)
            return self.plain(params, lora_bufs, cache, i32, *rest,
                              n_steps=n_steps, **kw)

        decode.lower = self.plain.lower  # Engine._traced prepares by it
        engine._jit_decode = decode
        if engine._spec:
            spec = engine._jit_spec_block

            def spec_block(*args, **kw):
                slots = np.asarray(args[16])  # slot_ids, as uploaded
                self.seen.append(("spec", args[2] is not None,
                                  int((slots >= 0).sum()),
                                  kw["n_cycles"] * (kw["k_steps"] + 1),
                                  engine._inflight is not None))
                self.bufs.append(args[2])
                return spec(*args, **kw)

            spec_block.lower = spec.lower
            engine._jit_spec_block = spec_block


def request(prompt, n, adapter=None, sampling=None) -> Request:
    return Request(prompt_tokens=list(prompt), max_new_tokens=n, logprobs=0,
                   sampling=sampling or SamplingParams(temperature=0.0),
                   adapter=adapter)


def finish(reqs) -> None:
    for r in reqs:
        assert r.done.wait(300), "request never finished"
        assert r.error is None, r.error


def alone(engine, req: Request) -> Request:
    """``req`` again, with nothing beside it."""
    again = request(req.prompt_tokens, req.max_new_tokens, req.adapter,
                    req.sampling)
    engine.submit(again)
    finish([again])
    return again


BASE_ROWS = [([3, 5, 7], 10, None),
             ([9, 8, 7, 6], 12, SamplingParams(temperature=0.9, seed=42)),
             ([5, 6, 7, 2, 4], 8, None)]
LONG, JOINER = ([2, 4, 6], 56), ([4, 4, 4], 6)
AMONG = [([11, 12], 9, None), ([4, 4, 4], 9, ADAPTER), ([8, 1, 5], 9, None),
         ([7, 7], 9, None)]


@pytest.fixture(scope="module")
def served(model):
    """Three stretches of traffic through ONE engine whose adapter is
    resident: (a) base rows only; (b) a long base answer joined mid-stream
    by an adapter row and left by it again; (c) one adapter row among base
    rows.  Then every request of (b) and (c) alone.  The decode dispatches
    of each stretch, and the counters at its end."""
    engine = make_engine(model)
    seen = Dispatches(engine)
    out = {}

    def stretch(name, reqs):
        finish(reqs)
        wait_for(lambda: engine._inflight is None
                 and not any(engine.slots), "the engine to go quiet")
        hist = engine.profiler.hist_state()
        out[name] = types.SimpleNamespace(
            reqs=reqs, dispatches=seen.seen[:],
            rows=hist["lora_rows"], free=hist["lora_free_steps"],
            overlapped=hist["blocks_overlapped"])
        seen.seen.clear()

    engine.start()
    try:
        stretch("base", [engine.submit(request(p, n, sampling=s))
                         for p, n, s in BASE_ROWS])
        # Both variants compiled before (b), so that the joiner meets the
        # long answer mid-stream and not at the end of a compile.
        stretch("warm", [engine.submit(request([1, 2], 3, ADAPTER))])
        long_one = engine.submit(request(*LONG))
        wait_for(lambda: len(long_one.output_tokens) >= 4, "a few tokens")
        joiner = engine.submit(request(*JOINER, ADAPTER))
        stretch("joined", [long_one, joiner])
        stretch("among", [engine.submit(request(p, n, a))
                          for p, n, a in AMONG])
        out["alone"] = {id(r): alone(engine, r)
                        for r in out["joined"].reqs + out["among"].reqs}
        out["metrics"] = metrics.render(engine.metrics_snapshot())
        out["profile"] = engine.profiler.snapshot()
    finally:
        engine.stop()
    return out


def reference(model, req: Request, logprobs=None) -> list[int]:
    return reference_tokens(
        CFG, model.merged if req.adapter else model.params,
        req.prompt_tokens, req.max_new_tokens, logprobs=logprobs,
        sampling=req.sampling)


class TestBaseRowsOnly:
    """(a)"""

    def test_every_block_went_without_the_buffers(self, served):
        blocks = served["base"].dispatches
        assert blocks and all(
            (handed, rows) == (False, 0) for _, handed, rows, *_ in blocks)

    @pytest.mark.parametrize("row", range(len(BASE_ROWS)))
    def test_tokens_and_logprobs_are_the_references(self, served, model,
                                                    row):
        req, want_lps = served["base"].reqs[row], []
        assert req.output_tokens == reference(model, req, want_lps)
        np.testing.assert_allclose(req.output_logprobs, want_lps,
                                   rtol=0, atol=2e-4)


class TestABaseRowJoinedAndLeftByAnAdapterRow:
    """(b)"""

    def test_consecutive_blocks_ran_different_variants_in_flight(self,
                                                                 served):
        blocks = served["joined"].dispatches
        handed = [b[1] for b in blocks]
        first = handed.index(True)
        last = len(handed) - 1 - handed[::-1].index(True)
        # ... base alone, then beside the adapter row, then alone again:
        assert 0 < first <= last < len(handed) - 1
        assert all(handed[first:last + 1])
        assert not any(handed[:first]) and not any(handed[last + 1:])
        # both switches with the block before still unread.
        assert blocks[first][4] and blocks[last + 1][4]
        assert served["joined"].overlapped > served["warm"].overlapped

    def test_the_buffers_went_exactly_with_the_adapter_rows(self, served):
        for name in ("base", "warm", "joined", "among"):
            for _, handed, rows, *_ in served[name].dispatches:
                assert handed == (rows > 0)

    @pytest.mark.parametrize("row", (0, 1), ids=("base", "adapter"))
    def test_each_row_is_the_request_alone(self, served, model, row):
        req = served["joined"].reqs[row]
        assert len(req.output_tokens) == req.max_new_tokens
        assert req.output_tokens == served["alone"][id(req)].output_tokens
        np.testing.assert_allclose(
            req.output_logprobs, served["alone"][id(req)].output_logprobs,
            rtol=0, atol=2e-4)
        assert req.output_tokens == reference(model, req)


class TestOneAdapterRowAmongBaseRows:
    """(c)"""

    def test_the_blocks_of_the_adapter_row_took_the_buffers(self, served):
        blocks = served["among"].dispatches
        with_row = [b for b in blocks if b[2]]
        assert with_row and all(b[1] for b in with_row)
        # ... beside base rows: the first block staged all four.
        assert with_row[0][2] == 1

    def test_the_adapter_moves_its_row(self, served, model):
        req = served["among"].reqs[1]
        assert req.adapter == ADAPTER
        base = reference_tokens(CFG, model.params, req.prompt_tokens,
                                req.max_new_tokens)
        assert req.output_tokens != base
        assert req.output_tokens == reference(model, req)

    @pytest.mark.parametrize("row", range(len(AMONG)))
    def test_each_row_is_the_request_alone_and_the_references(
            self, served, model, row):
        req = served["among"].reqs[row]
        assert req.output_tokens == served["alone"][id(req)].output_tokens
        assert req.output_tokens == reference(model, req)


class TestTheCounters:
    """(d)"""

    @pytest.mark.parametrize("stretch", ("base", "warm", "joined", "among"))
    def test_they_count_what_was_dispatched(self, served, stretch):
        names = ("base", "warm", "joined", "among")
        before = names[:names.index(stretch)]
        free = sum(steps for name in (*before, stretch)
                   for _, handed, _, steps, _ in served[name].dispatches
                   if not handed)
        rows = sum(rows * steps for name in (*before, stretch)
                   for _, _, rows, steps, _ in served[name].dispatches)
        assert served[stretch].free == free
        assert served[stretch].rows == rows
        assert (served[stretch].free > 0) and (
            (served[stretch].rows > 0) == (stretch != "base"))

    def test_they_are_exposed(self, served):
        hist = served["profile"]["hist"]
        assert hist["lora_free_steps"] > 0 and hist["lora_rows"] > 0
        lines = served["metrics"].splitlines()
        assert "# TYPE tpu:lora_free_steps_total counter" in lines
        assert f"tpu:lora_free_steps_total {hist['lora_free_steps']}" in lines
        assert f"tpu:lora_rows_total {hist['lora_rows']}" in lines
        # ... a share of the decode steps: every step is one or the other.
        steps = float(next(ln for ln in lines if ln.startswith(
            "tpu:dispatch_steps_sum")).split()[-1])
        with_rows = sum(
            steps for name in ("base", "warm", "joined", "among")
            for _, handed, _, steps, _ in served[name].dispatches if handed)
        assert 0 < hist["lora_free_steps"] < steps
        assert hist["lora_free_steps"] + with_rows <= steps

    def test_an_engine_without_adapters_counts_neither(self, model):
        engine = make_engine(model, adapters=False)
        seen = Dispatches(engine)
        engine.start()
        try:
            finish([engine.submit(request(p, n)) for p, n, _ in BASE_ROWS])
        finally:
            engine.stop()
        assert seen.seen and not any(handed for _, handed, *_ in seen.seen)
        hist = engine.profiler.hist_state()
        assert hist["lora_free_steps"] == 0 and hist["lora_rows"] == 0


class TestTwoProgramsAndNoMore:
    """(e)"""

    def test_mixed_traffic_traces_two_decode_programs_once(self, model):
        engine = make_engine(model)
        seen = Dispatches(engine)
        traces: list[str] = []

        def listener(name, _secs, **_kw):
            if name == TRACE_EVENT:
                traces.append(threading.current_thread().name)

        def traffic():
            finish([engine.submit(request([3, 5, 7], 6))])            # base
            finish([engine.submit(request([4, 4, 4], 6, ADAPTER))])   # tuned
            finish([engine.submit(request(p, n, a)) for p, n, a in AMONG])

        engine.start()
        monitoring.register_event_duration_secs_listener(listener)
        try:
            traffic()
            assert {steps for *_, steps, _ in seen.seen} == {1}
            assert {handed for _, handed, *_ in seen.seen} == {False, True}
            assert seen.plain._cache_size() == 2
            first_pass = len(traces)
            assert first_pass > 0
            traffic()
            assert len(traces) == first_pass, "the second pass traced"
            assert seen.plain._cache_size() == 2
        finally:
            monitoring.unregister_event_duration_listener(listener)
            engine.stop()


class TestTheSpeculativeBlock:
    """(f)"""

    @pytest.fixture(scope="class")
    def spec(self, model):
        """Base rows, then a base row beside an adapter row, through an
        engine that speculates (the draft is the target: every proposal
        accepted, greedy parity exact)."""
        lora = LoRAManager(CFG, dtype=jnp.float32)
        lora.load(ADAPTER, weights=model.weights, alpha=ALPHA, rank=RANK)
        engine = Engine(
            CFG, model.params,
            EngineConfig(decode_slots=SLOTS, max_seq_len=96,
                         prefill_buckets=(8, 16), speculative_k=2),
            lora_manager=lora, eos_id=None, dtype=jnp.float32,
            draft_params=model.params, draft_cfg=CFG)
        seen = Dispatches(engine)
        out = {}
        engine.start()
        try:
            for name, rows in (("base", [([3, 5, 7], 10, None),
                                         ([9, 8, 7, 6], 12, None)]),
                               ("beside", [([11, 12], 12, None),
                                           ([4, 4, 4], 12, ADAPTER)])):
                reqs = [engine.submit(request(p, n, a)) for p, n, a in rows]
                finish(reqs)
                wait_for(lambda: engine._inflight is None
                         and not any(engine.slots), "the engine to go quiet")
                out[name] = (reqs, seen.seen[:])
                seen.seen.clear()
        finally:
            engine.stop()
        assert engine.spec_cycles > 0
        return out

    @pytest.mark.parametrize("stretch", ("base", "beside"))
    def test_it_follows_the_same_rule(self, spec, stretch):
        _, blocks = spec[stretch]
        assert any(kind == "spec" for kind, *_ in blocks)
        for _, handed, rows, *_ in blocks:
            assert handed == (rows > 0)
        assert any(handed for _, handed, *_ in blocks) == (stretch == "beside")

    @pytest.mark.parametrize("stretch,row", [("base", 0), ("base", 1),
                                             ("beside", 0), ("beside", 1)])
    def test_every_row_gets_the_references_tokens(self, spec, model,
                                                  stretch, row):
        req = spec[stretch][0][row]
        assert req.output_tokens == reference(model, req)


class TestTheOtherTraceIsPreparedOffTheLoop:
    """The first block of a decode variant has the variant's other trace
    lowered and compiled on a helper thread, so the block that first needs
    it finds it."""

    LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def lowerings(self, model, adapters: bool, resident: bool = True):
        """Threads that lowered ``jit_decode_block`` while a base request
        and then an adapter request (or a second base request) ran."""
        engine = make_engine(model, adapters=adapters)
        if adapters and not resident:
            assert engine.lora.unload(ADAPTER)
        lowered: list[str] = []

        def listener(name, _secs, fun_name="", **_kw):
            if name == self.LOWER_EVENT and "decode_block" in fun_name:
                lowered.append(threading.current_thread().name)

        monitoring.register_event_duration_secs_listener(listener)
        engine.start()
        try:
            finish([engine.submit(request([3, 5, 7], 6))])
            wait_for(lambda: not any(
                t.name == "decode-trace-prepare"
                for t in threading.enumerate()), "the helper to end")
            first = lowered[:]
            finish([engine.submit(request(
                [4, 4, 4], 6, ADAPTER if adapters and resident else None))])
        finally:
            monitoring.unregister_event_duration_listener(listener)
            engine.stop()
        return first, lowered[len(first):], engine

    def test_an_adapter_row_finds_its_program_compiled(self, model):
        first, later, engine = self.lowerings(model, adapters=True)
        assert sorted(first) == sorted(
            ["decode-trace-prepare", engine._thread.name])
        assert later == [], "the adapter row's block lowered a program"

    @pytest.mark.parametrize("adapters,resident", [(False, True),
                                                   (True, False)],
                             ids=("no_buffers", "no_adapter_resident"))
    def test_nothing_is_prepared_that_no_row_can_ask_for(
            self, model, adapters, resident):
        first, later, engine = self.lowerings(model, adapters, resident)
        assert first == [engine._thread.name] and later == []
