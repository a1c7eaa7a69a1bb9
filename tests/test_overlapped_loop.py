"""The engine's one loop, the overlapped order of PR 40: block N+1 is
dispatched from the device carry before block N is read.

Five things are held here; the first four the tree's earlier pipelined loop
got wrong or left unsaid:

(a) nothing is traced after the first block of each shape, however many
    rows a block frees and whatever is admitted between (the fault of PR
    30's batch run: a budget-zero scatter whose shape was the count of rows
    freed);
(b) a first token leaves when its prefill is done, not with its slot's first
    decode block;
(c) one seeded mix gives every request the tokens and logprobs it gets
    alone, on every kind of cache the engine serves;
(d) the step a block books is one step, not two, and the twelve phases tile
    the engine thread's wall;
(e) the loop survives what a device call raises: it fails the rows that
    were hit and goes on serving.
"""

import dataclasses
import inspect
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import (
    TINY_FALCON_H1_TEST,
    TINY_MOE_TEST,
    TINY_SMALLTHINKER_TEST,
    TINY_TEST,
)
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.models.mixtral import CONFIGS
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    _SLOT_I32,
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def wait_for(cond, what: str, timeout_s: float = 180.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def tiny_params(cfg=TINY_TEST):
    return transformer.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def tiny_engine(params=None, cfg=TINY_TEST, lora=None, **extra) -> Engine:
    base = dict(decode_slots=4, max_seq_len=96, prefill_buckets=(8, 16))
    base.update(extra)
    return Engine(cfg, params if params is not None else tiny_params(cfg),
                  EngineConfig(**base), lora_manager=lora, eos_id=None,
                  dtype=jnp.float32)


def greedy(prompt, n, **kw) -> Request:
    return Request(prompt_tokens=list(prompt), max_new_tokens=n,
                   sampling=SamplingParams(temperature=0.0), **kw)


def test_no_field_of_the_configuration_selects_an_order():
    assert "pipeline_decode" not in {
        f.name for f in dataclasses.fields(EngineConfig)}
    with pytest.raises(TypeError):
        EngineConfig(pipeline_decode=True)


def test_the_servers_parser_refuses_the_flag_that_selected_one(capsys):
    """A deployment that still passes it gets argparse's error, not a
    server that ignores what it was told."""
    from llm_instance_gateway_tpu.server import api_http

    for flag in ("--no-pipeline-decode", "--pipeline-decode"):
        with pytest.raises(SystemExit) as exit_:
            api_http.main(["--model", "llama3-tiny", "--platform", "cpu",
                           flag])
        assert exit_.value.code == 2
        assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


def test_an_engine_has_one_loop_and_one_way_to_dispatch():
    for gone in ("_loop_pipelined", "_do_decode_step", "_do_spec_step",
                 "_sync_stop_hist", "_activate_slot_pipelined",
                 "_do_prefill_pipelined"):
        assert not hasattr(Engine, gone), gone
    engine = tiny_engine()
    engine.start()
    try:
        assert engine._thread._target == engine._loop
    finally:
        engine.stop()
    for method in ("_admit_and_insert", "_drain_decode_wait", "_do_attach",
                   "_do_prefill_ahead", "_insert_waiting", "_park_waiting",
                   "_do_prefill_ahead_group", "_grouped_batch",
                   "_do_prefill_group", "_stream_step",
                   "_paged_ensure_decode"):
        assert "pipelined" not in inspect.signature(
            getattr(Engine, method)).parameters, method


def test_a_rows_last_token_is_the_device_carrys_alone():
    """No host mirror of it is staged: the decode program takes the carry
    it is given, and the int32 buffer is a value a slot shorter."""
    assert "tokens" not in {name for name, _, _ in _SLOT_I32}
    engine = tiny_engine()
    assert not hasattr(engine, "_slot_tokens")
    assert inspect.signature(Engine._enqueue_decode).parameters[
        "carry"].default is inspect.Parameter.empty


# -- (a) nothing traced after the first block of each shape -----------------

class TestNothingIsTracedAfterTheFirstBlockOfEachShape:
    """Waves of k requests with one budget, admitted in one turn of the
    loop beside a long answer that keeps a block in flight, finish in one
    block: that block frees k rows at once."""

    @pytest.fixture(scope="class")
    def run(self):
        engine = tiny_engine(decode_slots=8)
        hold = threading.Event()
        admit = engine._admit_and_insert

        def gated():
            return False if hold.is_set() else admit()

        engine._admit_and_insert = gated
        freed_in_a_block: list[int] = []
        process = engine._process_block

        def counting(blk, current):
            before = sum(s is not None for s in engine.slots)
            process(blk, current=current)
            freed_in_a_block.append(
                before - sum(s is not None for s in engine.slots))

        engine._process_block = counting
        traces: list[str] = []

        def listener(name, _secs, **_kw):
            if name == TRACE_EVENT:
                traces.append(threading.current_thread().name)

        def wave(k: int, budget: int = 6) -> list[Request]:
            hold.set()
            reqs = [engine.submit(greedy([3 + i, 5, 7, 9], budget))
                    for i in range(k)]
            hold.clear()
            for r in reqs:
                assert r.done.wait(180) and r.error is None, r.error
            return reqs

        engine.start()
        monitoring.register_event_duration_secs_listener(listener)
        try:
            long_one = engine.submit(greedy([2, 4, 6], 90))
            wait_for(lambda: len(long_one.output_tokens) >= 2, "long answer")
            wave(1)  # the first block of each shape: everything compiles
            warm = len(traces)
            marks = {}
            for k in (2, 3, 5, 1, 5):
                at = len(freed_in_a_block)
                wave(k)
                marks[k] = max(freed_in_a_block[at:], default=0)
            assert not long_one.done.is_set(), "a block was always in flight"
            after = len(traces)
            long_one.cancelled.set()
            assert long_one.done.wait(60)
        finally:
            monitoring.unregister_event_duration_listener(listener)
            engine.stop()
        return {"warm": warm, "after": after, "freed": marks,
                "all_freed": freed_in_a_block, "engine": engine}

    def test_blocks_freed_none_one_two_three_and_five_rows(self, run):
        assert run["freed"] == {2: 2, 3: 3, 5: 5, 1: 1}
        assert 0 in run["all_freed"]

    def test_the_count_of_traced_programs_did_not_grow(self, run):
        assert run["warm"] > 0  # the listener heard the warm-up
        assert run["after"] == run["warm"], (
            f"{run['after'] - run['warm']} programs traced after warm-up")

    def test_a_staged_decode_dispatch_is_still_two_uploads(self, run):
        prof = run["engine"].profiler
        assert prof.hist_state()["stage_ops"] == 2 * prof.dispatches["decode"]


# -- (b) the first token does not wait for the slot's first block -----------

def test_first_token_is_out_before_the_slots_first_block_is_processed():
    """The device stub: ``_process_block`` of the NEW request's first block
    is held back until the request's first chunk has been published.  With
    the first token read only in that block (the tree's earlier loop) the
    hold would run into its timeout."""
    engine = tiny_engine()
    process = engine._process_block
    seen: dict = {}
    probe_box: list[Request] = []

    def held(blk, current):
        probe = probe_box[0] if probe_box else None
        if probe is not None and "first_block" not in seen and any(
                s is not None and s.request is probe for s in blk["rows"]):
            # hold the block back: the first chunk must not need it
            seen["published_in_time"] = probe.stream_event.wait(20)
            seen["first_block"] = {
                "t_first_token": probe.t_first_token,
                "tokens_out": len(probe.output_tokens),
                "held_at": time.time()}
        process(blk, current=current)

    engine._process_block = held
    engine.start()
    try:
        other = engine.submit(greedy([2, 4, 6], 60))
        wait_for(lambda: len(other.output_tokens) >= 3, "a block in flight")
        probe = greedy([5, 6, 7, 8], 8, logprobs=2)
        probe.stream_event.clear()
        probe_box.append(probe)
        engine.submit(probe)
        assert probe.done.wait(120) and probe.error is None, probe.error
        assert other.done.wait(120)
    finally:
        engine.stop()
    first = seen["first_block"]
    assert seen["published_in_time"], "first chunk waited for the block"
    assert first["t_first_token"] > 0, "stamped before its first block"
    assert first["t_first_token"] <= first["held_at"]
    assert first["tokens_out"] == 1  # the prefill's token, and only it
    assert len(probe.output_tokens) == 8
    assert len(probe.output_logprobs) == 8  # the first token's came along
    span = probe.prefill_attrs
    assert span["stage_s"] > 0 and span["emit_s"] > 0


# -- (c) a mix gives each request the answer it gets alone ---------------------

GLM = CONFIGS["glm-tiny"]


def _adapters(cfg) -> LoRAManager:
    lora = LoRAManager(cfg, dtype=jnp.float32)
    dims = target_dims(cfg)
    rng = np.random.RandomState(0)
    for name in ("ad-a", "ad-b"):
        lora.load(name, weights={
            t: {"a": rng.randn(cfg.n_layers, dims[t][0], 2) * 0.3,
                "b": rng.randn(cfg.n_layers, 2, dims[t][1]) * 0.3}
            for t in ("q", "v")}, alpha=4.0, rank=2)
    return lora


KINDS = {
    # name -> (model config, adapters served, extra EngineConfig fields);
    # a kind that refuses adapters (``_refuse_what_lanes_alone_serve``)
    # runs the mix on the base model.
    "lanes": (TINY_TEST, True, {}),
    "latent": (GLM, False, {}),
    "paged": (TINY_TEST, True, {"paged_kv_block": 8}),
    "recurrent": (TINY_FALCON_H1_TEST, False, {}),
    "window": (TINY_SMALLTHINKER_TEST, False, {}),
    "sparse": (TINY_MOE_TEST, True, {}),
}


def _mix(adapters: bool, probe: list[int]) -> list[dict]:
    """One seeded mix: greedy and seeded-sampled rows, adapters, a custom
    stop id, a stop sequence, ``max_tokens`` 1 and 2, a chunk-streamed
    prompt (over the largest bucket, 16) and a cancellation mid-block.
    ``probe`` is the greedy answer to the stop rows' prompt."""
    ad = (lambda name: name) if adapters else (lambda name: None)
    seeded = lambda t, seed: SamplingParams(  # noqa: E731
        temperature=t, seed=seed)
    return [
        dict(prompt=[3, 5, 7], n=10, adapter=ad("ad-a")),
        dict(prompt=[3, 5, 7], n=12, sampling=seeded(0.9, 42),
             adapter=ad("ad-b")),
        dict(prompt=[9, 8, 7, 6], n=1),
        dict(prompt=[9, 8, 7, 6, 5], n=2, sampling=seeded(1.1, 7)),
        dict(prompt=[5, 6, 7], n=12, stop_token_ids=[probe[4]]),
        dict(prompt=[5, 6, 7], n=12, stop_sequences=[probe[5:7]]),
        dict(prompt=list(range(3, 43)), n=8, adapter=ad("ad-a")),  # chunked
        dict(prompt=[2, 4, 6], n=70, cancel_after=4),
        dict(prompt=[11, 12], n=9, sampling=seeded(0.7, 3)),
        dict(prompt=list(range(50, 75)), n=6, sampling=seeded(0.8, 11)),
        dict(prompt=[4, 4, 4], n=7, adapter=ad("ad-b")),
        dict(prompt=[8, 1], n=2),
    ]


def _run_mix(kind: str, together: bool) -> list[dict]:
    """The mix through one engine: ``together``, every request submitted
    as the script goes; else each request alone, the next one submitted
    when the one before it is done.  Same programs, same batch width."""
    cfg, adapters, extra = KINDS[kind]
    params = tiny_params(cfg)
    engine = tiny_engine(params, cfg, lora=_adapters(cfg) if adapters
                         else None, decode_slots=3, **extra)
    engine.start()
    try:
        probe = engine.generate(greedy([5, 6, 7], 12), timeout_s=180)
        assert probe.error is None, probe.error
        reqs = []
        for spec in _mix(adapters, list(probe.output_tokens)):
            req = Request(
                prompt_tokens=list(spec["prompt"]),
                max_new_tokens=spec["n"], logprobs=2,
                sampling=spec.get("sampling",
                                  SamplingParams(temperature=0.0)),
                adapter=spec.get("adapter"),
                stop_token_ids=tuple(spec.get("stop_token_ids", ())),
                stop_sequences=tuple(
                    tuple(s) for s in spec.get("stop_sequences", ())))
            engine.submit(req)
            reqs.append((req, spec))
            if "cancel_after" in spec:
                wait_for(lambda r=req, s=spec: len(r.output_tokens)
                         >= s["cancel_after"] or r.done.is_set(),
                         "tokens before the cancellation")
                req.cancelled.set()
            if not together:
                assert req.done.wait(300), "request never finished"
        for req, _ in reqs:
            assert req.done.wait(300), "request never finished"
    finally:
        engine.stop()
    assert engine.profiler.hist_state()["blocks_overlapped"] > 0
    return [{"tokens": list(r.output_tokens),
             "logprobs": list(r.output_logprobs),
             "top": list(r.output_top_logprobs),
             "finish": r.finish_reason, "error": r.error,
             "t_first": r.t_first_token} for r, _ in reqs]


@pytest.fixture(scope="module", params=sorted(KINDS))
def alone_and_together(request):
    return (request.param, _run_mix(request.param, together=False),
            _run_mix(request.param, together=True))


class TestAMixGivesEachRequestTheAnswerItGetsAlone:
    def test_the_mix_ran_what_it_scripts(self, alone_and_together):
        _, alone, _ = alone_and_together
        assert all(r["error"] is None for r in alone)
        finishes = [r["finish"] for r in alone]
        assert finishes.count("cancelled") == 1
        assert finishes.count("stop") == 2
        assert len(alone[2]["tokens"]) == 1 and len(alone[3]["tokens"]) == 2
        # the custom stop id and the stop sequence, the fifth token and the
        # sixth and seventh of the greedy answer (sooner where a kind's
        # answer repeats itself)
        assert alone[4]["finish"] == alone[5]["finish"] == "stop"
        assert len(alone[4]["tokens"]) <= 5 and len(alone[5]["tokens"]) <= 7
        assert len(alone[6]["tokens"]) == 8   # the chunk-streamed prompt

    def test_token_for_token(self, alone_and_together):
        kind, alone, over = alone_and_together
        for i, (s, o) in enumerate(zip(alone, over, strict=True)):
            assert o["error"] is None, (kind, i, o["error"])
            assert o["finish"] == s["finish"], (kind, i)
            if s["finish"] == "cancelled":
                n = min(len(s["tokens"]), len(o["tokens"]))
                assert n >= 4 and o["tokens"][:n] == s["tokens"][:n]
            else:
                assert o["tokens"] == s["tokens"], (kind, i)
            assert o["t_first"] > 0

    def test_logprob_for_logprob(self, alone_and_together):
        kind, alone, over = alone_and_together
        for i, (s, o) in enumerate(zip(alone, over, strict=True)):
            n = min(len(s["logprobs"]), len(o["logprobs"]))
            assert n == len(o["tokens"]) or o["finish"] == "cancelled"
            assert o["logprobs"][:n] == s["logprobs"][:n], (kind, i)
            assert o["top"][:n] == s["top"][:n], (kind, i)


# -- (d) the step clock ------------------------------------------------------

STEP_S = 0.03


class TestTheStepClock:
    """A device stub of fixed step length: a block's outputs are ready
    ``STEP_S`` after the later of its enqueue and the readiness of the
    block before it, as on a device that runs one program at a time."""

    @pytest.fixture(scope="class")
    def run(self):
        engine = tiny_engine(decode_slots=2)
        program = engine._jit_decode
        ready_at: dict[int, float] = {}
        last_ready = [0.0]

        def slow_program(*args, **kwargs):
            outs = program(*args, **kwargs)
            last_ready[0] = max(time.perf_counter(), last_ready[0]) + STEP_S
            ready_at[id(outs[0])] = last_ready[0]
            return outs

        real_block = jax.block_until_ready

        def block_until_ready(tree):
            leaves = jax.tree_util.tree_leaves(tree)
            due = ready_at.pop(id(leaves[0]), None) if leaves else None
            if due is not None:
                time.sleep(max(0.0, due - time.perf_counter()))
            return real_block(tree)

        engine._jit_decode = slow_program
        mp = pytest.MonkeyPatch()
        mp.setattr(jax, "block_until_ready", block_until_ready)
        engine.start()
        try:
            engine.generate(greedy([5, 6, 7], 4), timeout_s=120)  # compiles
            steps0 = engine.phase_hist["decode_step"].state()
            gaps0 = dict(engine.profiler.gap_seconds)
            over0 = engine.profiler.hist_state()["blocks_overlapped"]
            n0 = engine.profiler.dispatches["decode"]
            t0, a = time.perf_counter(), engine.profiler.phase_seconds()
            req = engine.generate(greedy([5, 6, 7], 30), timeout_s=120)
            b, t1 = engine.profiler.phase_seconds(), time.perf_counter()
            assert req.error is None and len(req.output_tokens) == 30
            steps1 = engine.phase_hist["decode_step"].state()
            snap = engine.profiler.snapshot()
        finally:
            engine.stop()
            mp.undo()
        return {
            "wall": t1 - t0,
            "phases": {k: b[k] - a[k] for k in b},
            "step_sum": steps1["sum"] - steps0["sum"],
            "step_count": steps1["count"] - steps0["count"],
            "host_gap": engine.profiler.gap_seconds["host"] - gaps0["host"],
            "overlapped": snap["hist"]["blocks_overlapped"] - over0,
            "blocks": snap["attribution"]["dispatches"] and (
                engine.profiler.dispatches["decode"] - n0),
            "records": [r for r in snap["records"]
                        if r["phase"] == "decode"][-20:],
            "snapshot": snap, "engine": engine}

    def test_the_booked_step_is_one_step_not_two(self, run):
        """29 decode tokens behind a 30 ms device step: the steps sum to
        the wall they took, and each is one device step (the tree's
        earlier loop booked dispatch-to-processed, two steps, once blocks
        overlap)."""
        assert run["step_count"] >= 29
        mean = run["step_sum"] / run["step_count"]
        assert 0.8 * STEP_S < mean < 1.35 * STEP_S, mean
        assert run["step_sum"] <= run["wall"] + 1e-3
        steady = [r["wall_s"] for r in run["records"][2:-2]]
        assert steady and max(steady) < 1.6 * STEP_S, steady

    def test_the_twelve_phases_tile_the_wall(self, run):
        assert len(run["phases"]) == 12
        assert sum(run["phases"].values()) == pytest.approx(
            run["wall"], abs=0.01)
        # ... and the thread really blocked on the device for most of it:
        # the host's work a step came off the wait, not on top of it.
        assert run["phases"]["decode.wait"] > 0.6 * run["wall"]
        assert run["phases"]["decode.wait"] < run["step_sum"]

    def test_blocks_overlapped_and_the_device_never_ran_dry(self, run):
        assert run["overlapped"] >= run["blocks"] - 2
        # the host-sync gap books only the time nothing was queued
        assert run["host_gap"] < 0.25 * STEP_S * run["blocks"]

    def test_the_counter_is_exported_and_reported(self, run):
        import tools.profile_report as profile_report

        engine, snap = run["engine"], run["snapshot"]
        over = snap["hist"]["blocks_overlapped"]
        text = metrics.render(engine.metrics_snapshot())
        assert f"tpu:decode_blocks_overlapped_total {over}\n" in text + "\n"
        row = profile_report.overlap_row(snap)
        assert row["blocks_overlapped"] == over
        assert row["decode_blocks"] == snap["hist"]["wall"]["decode"]["count"]
        assert row["overlapped_pct"] > 80.0
        assert "Decode overlap" in profile_report.render_report(snap)


def test_the_planner_counts_the_unread_first_token():
    """A two-token answer that does not stream, under the adaptive planner:
    the one token left after the prefill's is one step.  The loop plans
    that block before it has read the first token; taking the host record
    for the row's progress it fused two steps, and a benchmark run's probes
    compiled a decode variant of their own inside `setup_s` (my chip runs,
    PR 40)."""
    engine = tiny_engine(adaptive_steps=8)
    engine.start()
    try:
        for _ in range(3):
            req = engine.generate(greedy([5, 6, 7], 2), timeout_s=120)
            assert req.error is None and len(req.output_tokens) == 2
    finally:
        engine.stop()
    steps = engine.dispatch_steps_hist.state()
    assert steps["count"] >= 3 and steps["sum"] == steps["count"], steps
    assert engine._jit_decode._cache_size() == 1


# -- (e) the loop survives ----------------------------------------------------

class Injected(RuntimeError):
    pass


def _raise_once(armed: threading.Event, fn):
    """``fn``, but for the one call after ``armed`` is set."""
    def stub(*args, **kwargs):
        if armed.is_set():
            armed.clear()
            raise Injected("injected fault")
        return fn(*args, **kwargs)
    return stub


# what raises -> (the engine's attribute that is stubbed, whom it hits:
# "all" the rows in flight, "one" the request being admitted)
FAULTS = {
    "decode-dispatch": ("_jit_decode", "all"),
    "block-materialisation": (None, "all"),  # jax.block_until_ready
    "first-token-read": ("_emit_first_token", "one"),
    "direct-prefill": ("_jit_insert", "one"),
    "stream-chunk": ("_jit_chunk", "one"),
}


class TestTheLoopSurvives:
    """"The engine must survive; fail the batch": whatever a device call
    raises, the rows it hit finish with ``error`` set and ``finish_reason``
    "error", the rows it did not hit get their tokens, a request submitted
    afterwards gets the tokens an untouched engine gives, and the paged
    pool has all its blocks back."""

    @pytest.mark.parametrize("layout", [{}, {"paged_kv_block": 8}],
                             ids=["lanes", "paged"])
    @pytest.mark.parametrize("fault", sorted(FAULTS))
    def test_it_fails_the_rows_that_were_hit_and_goes_on(
            self, fault, layout, monkeypatch):
        attr, hits = FAULTS[fault]
        engine = tiny_engine(decode_slots=3, **layout)
        armed = threading.Event()
        if attr is None:
            real = jax.block_until_ready
            faulty = _raise_once(armed, real)
            monkeypatch.setattr(
                jax, "block_until_ready", lambda tree: (
                    faulty if threading.current_thread() is engine._thread
                    else real)(tree))
        else:
            setattr(engine, attr, _raise_once(armed, getattr(engine, attr)))
        # over the largest bucket (16) where the fault is a chunk's
        victim_prompt = (list(range(3, 43)) if fault == "stream-chunk"
                         else [9, 8, 7, 6])
        engine.start()
        try:
            free = len(engine._free_blocks) if engine.paged else None
            want = engine.generate(greedy([5, 6, 7], 8), timeout_s=120)
            bystander_alone = engine.generate(greedy([2, 4, 6], 80),
                                              timeout_s=120)
            assert want.error is None and bystander_alone.error is None
            bystander = engine.submit(greedy([2, 4, 6], 80))
            wait_for(lambda: len(bystander.output_tokens) >= 3,
                     "a block in flight")
            if hits == "one":  # the next admission is the victim's
                armed.set()
            victim = engine.submit(greedy(victim_prompt, 40))
            if hits == "all":  # both rows are in the next block
                wait_for(lambda: len(victim.output_tokens) >= 2,
                         "the victim's row in flight")
                armed.set()
            assert victim.done.wait(120) and bystander.done.wait(120)
            assert not armed.is_set(), "the fault was never reached"
            assert victim.finish_reason == "error"
            assert "injected fault" in victim.error
            if hits == "all":
                assert bystander.finish_reason == "error"
                assert "injected fault" in bystander.error
            else:
                assert bystander.error is None
                assert (bystander.output_tokens
                        == bystander_alone.output_tokens)
            after = engine.generate(greedy([5, 6, 7], 8), timeout_s=120)
            assert after.error is None
            assert after.output_tokens == want.output_tokens
            if engine.paged:  # done is set before the slot is cleared
                wait_for(lambda: len(engine._free_blocks) == free,
                         "blocks never came back", timeout_s=10)
        finally:
            engine.stop()
