"""A decode block with an adapter row is handed the buffers of only those
LoRA targets that a RESIDENT adapter carries (``LoRAManager.resident_targets``,
``Engine._block_lora_buffers``), and no block ever waits for a compile that a
load or an unload caused (``Engine._traced``, ``_retarget``,
``_on_resident_targets``).

Beside ``tests/test_lora_free_decode.py`` and on its rig: every decode
dispatch recorded at the jitted call with the dict it was handed
(``Dispatches``), every request held against itself alone through the same
engine and against ``tests/_reference.py`` (no engine; the adapter merged
into the weights, so no line of ``models/lora.py`` is shared).
"""

import random
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st
from jax._src import monitoring

from llm_instance_gateway_tpu import metrics_registry
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.lora import TARGETS, target_dims
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    _in_order,
    _Slot,
)
from llm_instance_gateway_tpu.server.lora_manager import (
    AdapterError,
    LoRAManager,
)
from tests._reference import reference_tokens
from tests.test_lora_free_decode import (
    ALPHA,
    CFG,
    RANK,
    SLOTS,
    WEIGHT_OF,
    Dispatches,
    alone,
    finish,
    request,
    wait_for,
)

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# Adapter name -> the targets it carries.
CARRIES = {"qv": ("q", "v"), "qv2": ("q", "v"), "mlp": ("gate", "up", "down"),
           "ko": ("k", "o"), "all": TARGETS}


def keys_of(targets) -> set[str]:
    return {"scale"} | {f"{t}_{side}" for t in targets for side in "ab"}


def targets_of(bufs) -> tuple[str, ...] | None:
    return None if bufs is None else _in_order(
        {k[:-2] for k in bufs if k != "scale"})


@pytest.fixture(scope="module")
def model():
    """Float32 weights, one adapter of each kind of ``CARRIES``, and for
    each the weights with it merged in (the reference's model for its
    rows)."""
    params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    dims = target_dims(CFG)
    adapters, merged = {}, {None: params}
    for seed, (name, targets) in enumerate(CARRIES.items()):
        rng = np.random.RandomState(11 + seed)
        adapters[name] = {
            t: {"a": rng.randn(CFG.n_layers, dims[t][0], RANK) * 0.3,
                "b": rng.randn(CFG.n_layers, RANK, dims[t][1]) * 0.3}
            for t in targets}
        layers = dict(params["layers"])
        for t in targets:
            layers[WEIGHT_OF[t]] = layers[WEIGHT_OF[t]] + jnp.asarray(
                ALPHA / RANK * np.einsum(
                    "lir,lro->lio", adapters[name][t]["a"],
                    adapters[name][t]["b"]), jnp.float32)
        merged[name] = {**params, "layers": layers}
    return types.SimpleNamespace(params=params, adapters=adapters,
                                 merged=merged)


def load(lora: LoRAManager, model, name: str):
    return lora.load(name, weights=model.adapters[name], alpha=ALPHA,
                     rank=RANK)


def make_engine(model, resident, **extra) -> Engine:
    lora = LoRAManager(CFG, dtype=jnp.float32)
    for name in resident:
        load(lora, model, name)
    base = dict(decode_slots=SLOTS, max_seq_len=96, prefill_buckets=(8, 16))
    base.update(extra)
    if base.get("speculative_k"):
        extra = dict(draft_params=model.params, draft_cfg=CFG)
    else:
        extra = {}
    return Engine(CFG, model.params, EngineConfig(**base), lora_manager=lora,
                  eos_id=None, dtype=jnp.float32, **extra)


def quiet(engine) -> None:
    wait_for(lambda: engine._inflight is None and not any(engine.slots),
             "the engine to go quiet")


def helpers_ended() -> None:
    wait_for(lambda: not any(t.name == "decode-trace-prepare"
                             for t in threading.enumerate()),
             "the helper threads to end")


def reference(model, req, logprobs=None) -> list[int]:
    return reference_tokens(CFG, model.merged[req.adapter],
                            req.prompt_tokens, req.max_new_tokens,
                            logprobs=logprobs, sampling=req.sampling)


class Lowerings:
    """The threads that lowered ``jit_decode_block`` or ``jit_spec_block``
    while it listens: a program compiled anywhere, by the jitted call or
    ahead of it."""

    def __init__(self):
        self.threads: list[str] = []

    def _hear(self, name, _secs, fun_name="", **_kw):
        if name == LOWER_EVENT and ("decode_block" in fun_name
                                    or "spec_block" in fun_name):
            self.threads.append(threading.current_thread().name)

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._hear)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._hear)


# What is resident -> the rows that decode together, (prompt, tokens,
# adapter); the prompts fill both prefill buckets.
MIXES = {
    ("qv",): [([3, 5, 7], 10, None), ([4, 4, 4], 12, "qv"),
              ([9, 8, 7, 6, 5, 4, 3, 2, 1, 2], 9, "qv")],
    ("all",): [([3, 5, 7], 10, None), ([4, 4, 4], 12, "all")],
    ("qv", "all"): [([3, 5, 7], 10, None), ([4, 4, 4], 12, "qv"),
                    ([8, 1, 5], 11, "all")],
    ("qv", "mlp"): [([4, 4, 4], 12, "qv"), ([8, 1, 5], 11, "mlp"),
                    ([11, 12], 9, None)],
}
request_of = request  # inside a fixture ``request`` is pytest's
KINDS = [(resident, 0) for resident in MIXES] + [(("qv",), 2)]
IDS = ["+".join(r) + ("-spec" if k else "") for r, k in KINDS]


@pytest.fixture(scope="module", params=KINDS, ids=IDS)
def served(request, model):
    """The mix of what is resident through ONE engine (plain, or
    speculating with the target as its own draft), a stretch of base rows
    before it, every request of it alone after it: the decode dispatches
    with what each was handed, the prompt programs' too, the counters."""
    resident, spec_k = request.param
    engine = make_engine(model, resident, speculative_k=spec_k)
    seen = Dispatches(engine)
    prefill, prompts = engine._jit_prefill, []

    def spy_prefill(params, lora_bufs, *rest):
        prompts.append(lora_bufs)
        return prefill(params, lora_bufs, *rest)

    engine._jit_prefill = spy_prefill
    engine.start()
    try:
        with Lowerings() as lowered:
            finish([engine.submit(request_of([3, 5, 7], 6, None))])
            reqs = [engine.submit(request_of(p, n, a))
                    for p, n, a in MIXES[resident]]
            finish(reqs)
            quiet(engine)
            together = list(zip(seen.seen, seen.bufs))
            hist = engine.profiler.hist_state()
            apart = [alone(engine, r) for r in reqs]
            helpers_ended()
        out = types.SimpleNamespace(
            resident=resident, spec=bool(spec_k), engine=engine, reqs=reqs,
            apart=apart, blocks=together, all_blocks=list(seen.bufs),
            hist=hist, prompts=prompts, prefill=prefill,
            decode_traces=seen.plain._cache_size(),
            lowered=lowered.threads,
            metrics=metrics.render(engine.metrics_snapshot()))
    finally:
        engine.stop()
    return out


class TestABlockIsHandedTheResidentTargets:

    def test_exactly_the_resident_sets_buffers_or_none(self, served):
        union = _in_order({t for name in served.resident
                           for t in CARRIES[name]})
        with_rows = [bufs for (_, _, rows, *_), bufs in served.blocks
                     if rows]
        assert with_rows
        for bufs in with_rows:
            assert set(bufs) == keys_of(union)
        for (_, handed, rows, *_), bufs in served.blocks:
            assert handed == (rows > 0) == (bufs is not None)
        kinds = {kind for (kind, *_), _ in served.blocks}
        assert ("spec" in kinds) == served.spec

    def test_they_are_the_managers_own_arrays(self, served):
        own = served.engine.lora.buffers
        for bufs in served.all_blocks:
            for key, array in (bufs or {}).items():
                assert array is own[key], key

    def test_every_row_is_the_request_alone(self, served):
        for req, again in zip(served.reqs, served.apart):
            assert len(req.output_tokens) == req.max_new_tokens
            assert req.output_tokens == again.output_tokens
            np.testing.assert_allclose(
                req.output_logprobs, again.output_logprobs, rtol=0,
                atol=2e-4)

    def test_every_row_is_the_references(self, served, model):
        for req in served.reqs:
            want_lps = []
            assert req.output_tokens == reference(model, req, want_lps), (
                req.adapter)
            np.testing.assert_allclose(req.output_logprobs, want_lps,
                                       rtol=0, atol=2e-4)

    def test_each_adapter_moves_its_rows(self, served, model):
        for req in served.reqs:
            base = reference_tokens(CFG, model.params, req.prompt_tokens,
                                    req.max_new_tokens)
            assert (req.output_tokens != base) == (req.adapter is not None)

    def test_two_decode_programs_and_never_a_wider_one(self, served):
        """Mixed traffic traces each decode variant twice: without the
        delta, and with the resident targets' (all seven only where an
        adapter carries them all)."""
        # ... of the plain program; one that speculates runs it now and then.
        assert served.decode_traces == 2 or served.spec
        union = _in_order({t for name in served.resident
                           for t in CARRIES[name]})
        assert {targets_of(b) for b in served.all_blocks} == {None, union}

    def test_the_loop_compiled_one_trace_and_a_helper_the_other(self, served):
        """... of each program it ran: the first it met (an engine without
        adapters compiles that one too)."""
        loop = served.engine._thread.name
        programs = 2 if served.spec else 1
        assert served.lowered.count(loop) <= programs
        assert set(served.lowered) <= {loop, "decode-trace-prepare"}

    def test_the_prompt_programs_take_every_buffer_once_a_bucket(self,
                                                                 served):
        own = served.engine.lora.buffers
        assert served.prompts
        for bufs in served.prompts:
            assert set(bufs) == keys_of(TARGETS)
            assert all(bufs[k] is own[k] for k in bufs)
        buckets = {8 if len(r.prompt_tokens) <= 8 else 16
                   for r in served.reqs}
        assert served.prefill._cache_size() == len(buckets)

    def test_the_counter_counts_the_targets_handed(self, served):
        want = sum(steps * len(targets_of(bufs))  # plain blocks only
                   for (kind, _, _, steps, _), bufs in served.blocks
                   if kind == "decode" and bufs is not None)
        assert served.hist["lora_target_reads"] == want
        assert served.spec or want > 0

    def test_the_counter_is_registered_and_rendered(self, served):
        assert "tpu:lora_target_reads_total" in (
            metrics_registry.registered_names())
        lines = served.metrics.splitlines()
        assert "# TYPE tpu:lora_target_reads_total counter" in lines
        total = served.engine.profiler.hist_state()["lora_target_reads"]
        assert f"tpu:lora_target_reads_total {total}" in lines


class TestAnEngineWithoutAdaptersCountsNothing:

    def test_no_targets_are_read(self, model):
        engine = Engine(CFG, model.params,
                        EngineConfig(decode_slots=SLOTS, max_seq_len=96,
                                     prefill_buckets=(8, 16)),
                        eos_id=None, dtype=jnp.float32)
        engine.start()
        try:
            finish([engine.submit(request([3, 5, 7], 6))])
        finally:
            engine.stop()
        assert engine.profiler.hist_state()["lora_target_reads"] == 0
        assert engine._decode_traces == {} and engine._decode_variants == {}


def through_it_all(out, model) -> None:
    """The row that decoded while the targets changed is the request alone
    under the last set, and as far as the reference's row reaches the
    reference's."""
    long_one = out.long_one
    assert len(long_one.output_tokens) == long_one.max_new_tokens == 400
    assert long_one.output_tokens == out.long_again.output_tokens
    np.testing.assert_allclose(long_one.output_logprobs,
                               out.long_again.output_logprobs, rtol=0,
                               atol=2e-4)
    head = request(long_one.prompt_tokens, 60, long_one.adapter)
    assert long_one.output_tokens[:60] == reference(model, head)


class Gate:
    """``jit_decode_block.lower`` of ``engine`` held shut for the dicts that
    ``holds`` says: who came to lower what, and nobody through until
    ``open``."""

    def __init__(self, seen: Dispatches, engine: Engine, holds):
        self.open = threading.Event()
        self.came: list[tuple] = []
        real = seen.plain.lower

        def lower(params, bufs, *rest, **kw):
            targets = targets_of(bufs)
            self.came.append((threading.current_thread().name, targets))
            if holds(targets):
                assert self.open.wait(300), "the gate never opened"
            return real(params, bufs, *rest, **kw)

        engine._jit_decode.lower = lower


class TestALoadThatWidensTheTargets:
    """A gate/up/down adapter loaded while rows decode."""

    @pytest.fixture(scope="class")
    def loaded(self, model):
        engine = make_engine(model, ("qv",), max_seq_len=512)
        seen = Dispatches(engine)
        gate = Gate(seen, engine, lambda t: t is not None and "gate" in t)
        out = types.SimpleNamespace(engine=engine, seen=seen)
        engine.start()
        try:
            # Both traces of the variant, before anything is loaded.
            finish([engine.submit(request([3, 5, 7], 6)),
                    engine.submit(request([4, 4, 4], 6, "qv"))])
            quiet(engine)
            helpers_ended()
            with Lowerings() as lowered:
                long_one = engine.submit(request([2, 4, 6], 400, "qv"))
                wait_for(lambda: len(long_one.output_tokens) >= 4,
                         "a few tokens")
                loader = threading.Thread(
                    target=load, args=(engine.lora, model, "mlp"),
                    name="the-load")
                loader.start()
                wait_for(lambda: any(t and "gate" in t
                                     for _, t in gate.came),
                         "the load to come to the compile")
                # While the wider trace is not compiled:
                out.running_meanwhile = engine.lora.running_adapters()
                out.handed_meanwhile = engine._lora_targets
                out.resident_meanwhile = engine.lora.resident_targets()
                try:
                    engine.lora.acquire("mlp")
                    out.acquired_meanwhile = True
                except AdapterError:
                    out.acquired_meanwhile = False
                at = len(seen.bufs)
                beside = engine.submit(request([4, 4, 4], 8, "qv"))
                finish([beside])
                out.beside = beside
                out.blocks_meanwhile = seen.bufs[at:]
                out.loader_alive_meanwhile = loader.is_alive()
                gate.open.set()
                loader.join(300)
                assert not loader.is_alive()
                out.running_after = engine.lora.running_adapters()
                out.handed_after = engine._lora_targets
                at = len(seen.bufs)
                other = engine.submit(request([4, 4, 4], 80, "qv"))
                wait_for(lambda: len(other.output_tokens) >= 2, "a row")
                first = engine.submit(request([8, 1, 5], 9, "mlp"))
                finish([first, other])
                out.first = first
                out.blocks_after = seen.bufs[at:]
                out.rows_after = [rows for _, _, rows, *_ in seen.seen[at:]]
                # A load inside the set, then.
                at = len(lowered.threads)
                traces = seen.plain._cache_size()
                load(engine.lora, model, "qv2")
                helpers_ended()
                out.inside = types.SimpleNamespace(
                    lowered=lowered.threads[at:],
                    new_traces=seen.plain._cache_size() - traces,
                    handed=engine._lora_targets,
                    running=engine.lora.running_adapters())
                second = engine.submit(request([4, 4, 4], 8, "qv2"))
                finish([second, long_one])
                out.second, out.long_one = second, long_one
                out.long_again = alone(engine, long_one)
            out.lowered = lowered.threads
            out.came = gate.came
        finally:
            gate.open.set()
            engine.stop()
        return out

    def test_it_is_not_running_until_the_wider_trace_is_compiled(self,
                                                                 loaded):
        assert loaded.loader_alive_meanwhile
        assert loaded.running_meanwhile == ["qv"]
        assert not loaded.acquired_meanwhile
        # ... though it holds its slot and counts as resident already.
        assert loaded.resident_meanwhile == {"q", "v", "gate", "up", "down"}
        assert loaded.running_after == ["mlp", "qv"]

    def test_rows_decode_on_under_the_narrower_set_meanwhile(self, loaded,
                                                             model):
        assert loaded.handed_meanwhile == ("q", "v")
        assert loaded.blocks_meanwhile
        assert {targets_of(b) for b in loaded.blocks_meanwhile} == {
            ("q", "v")}
        assert loaded.beside.output_tokens == reference(model, loaded.beside)

    def test_its_first_block_finds_its_program(self, loaded, model):
        wide = ("q", "v", "gate", "up", "down")
        assert loaded.handed_after == wide
        assert max(loaded.rows_after) >= 2, "never beside another row"
        assert {targets_of(b) for b in loaded.blocks_after} == {wide}
        assert loaded.first.output_tokens == reference(model, loaded.first)

    def test_no_block_compiled_on_the_engine_thread(self, loaded):
        assert loaded.lowered == ["the-load"]
        assert [t for name, t in loaded.came if name == "the-load"] == [
            ("q", "v", "gate", "up", "down")]

    def test_a_load_inside_the_set_compiles_nothing(self, loaded, model):
        assert loaded.inside.lowered == []
        assert loaded.inside.new_traces == 0
        assert loaded.inside.handed == ("q", "v", "gate", "up", "down")
        assert loaded.inside.running == ["mlp", "qv", "qv2"]
        assert loaded.second.output_tokens == reference(model, loaded.second)

    def test_the_row_that_decoded_through_it_all_is_the_references(
            self, loaded, model):
        """... under the two-target program, then the five-target one."""
        through_it_all(loaded, model)


class TestAnUnloadThatNarrowsTheTargets:

    @pytest.fixture(scope="class")
    def unloaded(self, model):
        engine = make_engine(model, ("qv", "mlp"), max_seq_len=512)
        seen = Dispatches(engine)
        gate = Gate(seen, engine, lambda t: t == ("q", "v"))
        wide = ("q", "v", "gate", "up", "down")
        out = types.SimpleNamespace(wide=wide)
        engine.start()
        try:
            finish([engine.submit(request([3, 5, 7], 6)),
                    engine.submit(request([8, 1, 5], 6, "mlp"))])
            quiet(engine)
            helpers_ended()
            with Lowerings() as lowered:
                long_one = engine.submit(request([2, 4, 6], 400, "qv"))
                wait_for(lambda: len(long_one.output_tokens) >= 4,
                         "a few tokens")
                came = len(gate.came)
                assert engine.lora.unload("mlp")
                wait_for(lambda: any(t == ("q", "v") for _, t in gate.came),
                         "the helper to come to the compile")
                out.resident_meanwhile = engine.lora.resident_targets()
                out.handed_meanwhile = engine._lora_targets
                at = len(seen.bufs)
                beside = engine.submit(request([4, 4, 4], 8, "qv"))
                finish([beside])
                out.beside = beside
                out.blocks_meanwhile = seen.bufs[at:]
                gate.open.set()
                wait_for(lambda: engine._lora_targets == ("q", "v"),
                         "the narrower set to be adopted")
                helpers_ended()
                at = len(seen.bufs)
                after = engine.submit(request([4, 4, 4], 8, "qv"))
                finish([after])
                out.after = after
                out.blocks_after = seen.bufs[at:]
                finish([long_one])
                out.long_one = long_one
                out.long_again = alone(engine, long_one)
            out.lowered, out.came = lowered.threads, gate.came[came:]
        finally:
            gate.open.set()
            engine.stop()
        return out

    def test_the_wider_set_stays_until_the_narrower_trace_is_ready(
            self, unloaded, model):
        assert unloaded.resident_meanwhile == {"q", "v"}
        assert unloaded.handed_meanwhile == unloaded.wide
        assert unloaded.blocks_meanwhile and {
            targets_of(b) for b in unloaded.blocks_meanwhile} == {
                unloaded.wide}
        assert unloaded.beside.output_tokens == reference(model,
                                                          unloaded.beside)

    def test_then_the_narrower_one_is_handed(self, unloaded, model):
        assert {targets_of(b) for b in unloaded.blocks_after} == {("q", "v")}
        assert unloaded.after.output_tokens == reference(model,
                                                         unloaded.after)
        through_it_all(unloaded, model)

    def test_a_helper_thread_compiled_it_and_not_the_loop(self, unloaded):
        assert unloaded.lowered == ["decode-trace-prepare"]
        assert unloaded.came == [("decode-trace-prepare", ("q", "v"))]


class TestUnloadedToNothingAndLoadedAgain:
    """The first adapter of a server that has served base rows: the load
    compiles the trace with the delta, where the first adapter row did."""

    def test_the_load_compiles_what_the_first_adapter_row_needs(self, model):
        engine = make_engine(model, ())
        seen = Dispatches(engine)
        engine.start()
        try:
            with Lowerings() as lowered:
                finish([engine.submit(request([3, 5, 7], 6))])
                helpers_ended()
                assert lowered.threads == [engine._thread.name]
                assert engine._lora_targets == ()
                load(engine.lora, model, "qv")
                assert lowered.threads[1:] == ["MainThread"]
                assert engine._lora_targets == ("q", "v")
                req = engine.submit(request([4, 4, 4], 6, "qv"))
                finish([req])
                quiet(engine)
                assert lowered.threads[2:] == []
                assert req.output_tokens == reference(model, req)
                assert engine.lora.unload("qv")
                helpers_ended()
                assert engine._lora_targets == ()
                load(engine.lora, model, "qv2")
                assert engine._lora_targets == ("q", "v")
                assert lowered.threads[2:] == []
            assert seen.plain._cache_size() == 2
        finally:
            engine.stop()


# -- the invariant, over random residency verbs ------------------------------

VERBS = st.lists(
    st.tuples(st.sampled_from(("load", "unload", "demote")),
              st.sampled_from(sorted(CARRIES))),
    min_size=1, max_size=24)


@pytest.fixture(scope="module")
def rig(model):
    """An engine that is never started: this thread plays the loop.  Two
    decode variants "met", whose lowering is a short sleep that records
    what it was asked for."""
    engine = make_engine(model, ())
    compiled: list[tuple] = []

    def variant(name):
        def lower(bufs):
            threading.Event().wait(0.002)
            compiled.append((name, targets_of(bufs),
                             threading.current_thread().name))
        return lower

    return types.SimpleNamespace(engine=engine, compiled=compiled,
                                 variants={(("n_steps", n),): variant(n)
                                           for n in (1, 4)})


def check_a_block(engine: Engine) -> None:
    """Stage a block whose rows name every running adapter (pinned, as an
    admitted request pins its own) and hold what it is handed against what
    they carry; and every trace it could run is compiled."""
    lora = engine.lora
    names = []
    for name in lora.running_adapters()[:SLOTS]:
        try:
            lora.acquire(name)
            names.append(name)
        except AdapterError:  # unloaded since
            pass
    handed = engine._lora_targets  # one read, as the loop makes
    for variant in engine._decode_variants:
        if handed:
            done = engine._decode_traces.get((variant, handed))
            assert done is not None and done.is_set(), (variant, handed)
    try:
        for i, name in enumerate(names):
            slot = lora.slot_for(name)
            engine.slots[i] = _Slot(request=request([1], 1, name),
                                    lora_slot=slot, position=1)
            engine._slot_lora[i] = slot
        bufs, targets, rows = engine._block_lora_buffers(
            engine._slots_i32.copy())
        assert rows == len(names)
        if not names:
            assert bufs is None and targets is None
            return
        for name in names:
            assert set(CARRIES[name]) <= set(targets), (name, targets)
        assert set(bufs) == keys_of(targets)
    finally:
        for i, name in enumerate(names):
            engine.slots[i] = None
            engine._slot_lora[i] = -1
            lora.release(name)


def reset(rig) -> None:
    """Nothing resident, every variant met without the delta, nothing
    else compiled."""
    engine, lora = rig.engine, rig.engine.lora
    for name in lora.running_adapters():
        lora.unload(name)
    helpers_ended()
    with engine._trace_lock:
        engine._decode_variants.update(rig.variants)
        engine._decode_traces.clear()
        for variant in rig.variants:
            engine._decode_traces[(variant, None)] = done = threading.Event()
            done.set()
    assert engine._lora_targets == ()
    del rig.compiled[:]


@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(verbs=VERBS)
def test_the_handed_set_holds_every_named_adapters_targets(rig, model, verbs):
    engine, lora = rig.engine, rig.engine.lora
    reset(rig)
    for verb, name in verbs:
        try:
            if verb == "load":
                load(lora, model, name)
            else:
                getattr(lora, verb)(name)
        except AdapterError:  # no free slot
            pass
        check_a_block(engine)
    helpers_ended()
    check_a_block(engine)
    # Settled: exactly the resident targets, each trace compiled once, a
    # widening one on the thread of its load.
    assert engine._lora_targets == _in_order(lora.resident_targets())
    traces = [(v, t) for v, t, _ in rig.compiled]
    assert len(traces) == len(set(traces))
    assert {who for *_, who in rig.compiled} <= {"MainThread",
                                                 "decode-trace-prepare"}


def test_it_holds_while_loads_and_unloads_race_the_loop(rig, model):
    """Three threads of residency verbs against this thread staging blocks
    as fast as it can, the interpreter switching threads every 10 us."""
    engine, lora = rig.engine, rig.engine.lora
    reset(rig)
    stop, failures = threading.Event(), []

    def verbs(seed: int) -> None:
        rng = random.Random(seed)
        try:
            while not stop.is_set():
                verb = rng.choice(("load", "load", "unload", "demote"))
                name = rng.choice(sorted(CARRIES))
                try:
                    if verb == "load":
                        load(lora, model, name)
                    else:
                        getattr(lora, verb)(name)
                except AdapterError:  # no free slot, or pinned by a block
                    pass
        except Exception as e:  # the thread's own report
            failures.append(e)

    threads = [threading.Thread(target=verbs, args=(seed,))
               for seed in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline, blocks = time.monotonic() + 3.0, 0
        while time.monotonic() < deadline:
            check_a_block(engine)
            blocks += 1
    finally:
        stop.set()
        for t in threads:
            t.join(60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not failures, failures
    assert blocks > 100
    helpers_ended()
    check_a_block(engine)
    assert engine._lora_targets == _in_order(lora.resident_targets())
    assert len(rig.compiled) == len({(v, t) for v, t, _ in rig.compiled})
