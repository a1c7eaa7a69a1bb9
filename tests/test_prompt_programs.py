"""A prompt program's own record (PR 57): counted where it is enqueued,
padded positions and all, and timed where the loop sees it complete.

Three things are held here:

(a) the count: every program that computes a prompt is counted by the
    jitted program, its positions split into prompt tokens and padding; the
    operator's ``tpu:prefill_padding_tokens_total`` is the same padding, the
    chunk stream's for the first time;
(b) the completion chain: a chunk that is not its prompt's last is awaited
    before the decode block queued behind it, so a block's step is the decode
    program's own and the chunk's time is the chunk's; decode steps, prompt
    programs and the stretches the queue was empty tile the chain from the
    first staging to the last completion; a block with nothing ahead of it
    books what it booked before, to the digit;
(c) the request's ``engine.prefill`` attrs and the two reports say what the
    prompt cost.
"""

import math
import time

import jax
import jax.numpy as jnp
import pytest

from llm_instance_gateway_tpu.metrics_registry import PROMPT_PROGRAMS
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server import engine as engine_mod
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.profiler import (
    StepProfiler,
    render_profile,
)

BUCKETS = (8, 16)
CHUNK = max(BUCKETS)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def tiny_engine(params, **extra) -> Engine:
    base = dict(decode_slots=4, max_seq_len=96, prefill_buckets=BUCKETS)
    base.update(extra)
    return Engine(TINY_TEST, params, EngineConfig(**base), eos_id=None,
                  dtype=jnp.float32)


def greedy(prompt, n) -> Request:
    return Request(prompt_tokens=list(prompt), max_new_tokens=n,
                   sampling=SamplingParams(temperature=0.0))


def families(text: str) -> dict:
    """``family{labels}`` -> value of a rendered exposition."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


# -- (a) the count -----------------------------------------------------------

# kind -> (EngineConfig fields, the prompts that go in together, the program
# that computes them, programs, real positions, computed positions).
LONG = list(range(3, 43))  # 40 tokens: 16 + 16 + 8 of the third chunk
COUNTS = {
    "bucket": ({}, [[5, 6, 7, 8, 9]], "prefill", 1, 5, 8),
    "bucket-exact": ({}, [list(range(3, 19))], "prefill", 1, 16, 16),
    "grouped": ({"prefill_batch": 4},
                [[5, 6, 7], [9, 8, 7, 6, 5], [4, 4]], "prefill_many",
                1, 10, 24),
    "stream": ({}, [LONG], "chunk", math.ceil(len(LONG) / CHUNK),
               len(LONG), math.ceil(len(LONG) / CHUNK) * CHUNK),
    "stream-paged": ({"paged_kv_block": 8}, [LONG], "chunk", 3, 40, 48),
    # three tokens behind a cached block of eight (the warm-up's): the
    # suffix alone is computed, at its own bucket
    "prefix-suffix": ({"paged_kv_block": 8, "prefix_cache": True},
                      [list(range(3, 11)) + [60, 61, 62]], "chunk",
                      1, 3, 8),
}


@pytest.fixture(scope="module", params=sorted(COUNTS))
def counted(request, params):
    """One engine a kind; a row decodes all the while; the kind's prompts
    go in together, and what the counters grew by is returned."""
    extra, prompts, program, *_ = COUNTS[request.param]
    engine = tiny_engine(params, **extra)
    engine.start()
    try:
        # compiles, and (prefix-suffix) caches the prefix's blocks
        warm = engine.generate(greedy(list(range(3, 19)), 2), timeout_s=180)
        assert warm.error is None, warm.error
        decoding = engine.submit(greedy([2, 4, 6], 60))
        deadline = time.monotonic() + 180
        while len(decoding.output_tokens) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        before = metrics.render(engine.metrics_snapshot())
        state0 = engine.profiler.prompt_state()
        reqs = [engine.submit(greedy(p, 3)) for p in prompts]
        for r in reqs + [decoding]:
            assert r.done.wait(180) and r.error is None, r.error
        # the last program's seconds are booked where its first token is
        # read, before the request is done
        after = metrics.render(engine.metrics_snapshot())
        state1 = engine.profiler.prompt_state()
    finally:
        engine.stop()
    grew = families(after)
    for k, v in families(before).items():
        grew[k] -= v
    return {"kind": request.param, "program": program, "reqs": reqs,
            "grew": grew, "scrapes": (before, after),
            "state": {p: {k: state1[p][k] - state0[p][k] for k in state1[p]}
                      for p in state1}}


class TestEveryPromptProgramIsCountedWhereItIsEnqueued:
    def test_programs_by_the_jitted_program(self, counted):
        _, _, program, n, _, _ = COUNTS[counted["kind"]]
        for p in PROMPT_PROGRAMS:
            grew = counted["grew"][f'tpu:prompt_programs_total{{program="{p}"}}']
            assert grew == (n if p == program else 0), (p, grew)

    def test_positions_are_prompt_tokens_and_padding(self, counted):
        _, _, program, n, real, computed = COUNTS[counted["kind"]]
        key = 'tpu:prompt_positions_total{{program="{}",kind="{}"}}'
        got_real = counted["grew"][key.format(program, "real")]
        got_pad = counted["grew"][key.format(program, "pad")]
        assert got_real == real
        assert got_real + got_pad == computed
        if counted["kind"].startswith("stream"):
            assert n == math.ceil(real / CHUNK) and computed == n * CHUNK

    def test_the_operators_family_counts_the_same_padding(self, counted):
        """``tpu:prefill_padding_tokens_total``: fed by the same call, so
        the chunk stream's padding is in it (it was never before PR 57)."""
        pad = sum(v for k, v in counted["grew"].items()
                  if k.startswith("tpu:prompt_positions_total")
                  and 'kind="pad"' in k)
        assert counted["grew"]["tpu:prefill_padding_tokens_total"] == pad
        _, _, _, _, real, computed = COUNTS[counted["kind"]]
        assert pad == computed - real

    def test_no_grid_steps_where_no_kernel_takes_the_shapes(self, counted):
        # 16-wide heads: the chunk attend is XLA's on every backend
        assert counted["grew"]["tpu:chunk_attn_grid_steps_total"] == 0

    def test_every_program_was_seen_complete(self, counted):
        state = counted["state"][counted["program"]]
        assert state["seconds"] > 0.0
        others = [p for p in PROMPT_PROGRAMS if p != counted["program"]]
        assert all(counted["state"][p]["seconds"] == 0.0 for p in others)

    def test_the_request_says_what_its_prompt_cost(self, counted):
        _, prompts, _, n, _, computed = COUNTS[counted["kind"]]
        attrs = [r.prefill_attrs for r in counted["reqs"]]
        assert all(a["programs"] == n for a in attrs)
        # a grouped program's positions are rows x bucket: each its row
        assert sum(a["positions"] for a in attrs) == computed
        assert all(a["device_s"] > 0.0 for a in attrs)
        booked = counted["state"][counted["program"]]["seconds"]
        # riders of one program each carry its interval
        assert attrs[0]["device_s"] == pytest.approx(booked, abs=1e-6)
        for a in attrs:
            assert a["stage_s"] > 0.0 and a["wait_s"] >= 0.0


    def test_the_benchmarks_metric_files_read_the_exposition(self, counted):
        """The six metric files of ``benchmark/metrics`` over the engine's
        own ``/metrics`` text, before and after: what the harness's reader
        is handed on the chip."""
        from benchmark import manifest, readers

        before, after = counted["scrapes"]
        ctx = {"window_s": 2.0, "prom_before": [before],
               "prom_after": [after]}
        read = {name: readers.prom_delta(
            manifest.load_metric(name)["args"], ctx)
            for name in ("model.chunk_program_ms", "model.prompt_pad_pct",
                         "model.prompt_programs_pct")}
        _, _, program, n, real, computed = COUNTS[counted["kind"]]
        seconds = counted["state"][program]["seconds"]
        assert read["model.prompt_pad_pct"] == pytest.approx(
            100.0 * (computed - real) / computed)
        assert read["model.prompt_programs_pct"] == pytest.approx(
            100.0 * seconds / 2.0, abs=1e-3)
        if program == "chunk":
            assert read["model.chunk_program_ms"] == pytest.approx(
                1000.0 * seconds / n, abs=1e-2)
        else:
            assert read["model.chunk_program_ms"] is None


# -- the chunk attend's grid steps (PR 59) ------------------------------------

# The cells that stream chunk programs: (n_heads, the cache's leaves as
# ``init_decode_cache`` lays them, latent (nope, rope) widths, the steps a
# 1,024-token chunk program): layers x kv heads as the lane has them x query
# tiles x key tiles, one step for ALL the query heads of a kv head.
CELL_LAYOUTS = {
    # 3 full layers x (4 x 4 x 16) + 9 window layers x (4 x 4 x 5); the
    # per-query-head grid at [256, 512] tiles walked 20,832
    "smallthinker": (28, {"k": (3, 32, 16384, 4, 128),
                          "k_win": (9, 32, 4096, 4, 128)}, None, 1488),
    "qwen7b-doc": (28, {"k": (28, 32, 2048, 4, 128)}, None, 28 * 32),
    # two 64-wide kv heads a 128-lane row: 4 rows under 32 query heads
    "lfm2-packed": (32, {"k": (3, 64, 8192, 4, 128)}, None, 3 * 4 * 4 * 8),
    # a latent row expanded to a 256-wide key a head: a group of one
    "glm-latent": (20, {"k": (13, 32, 4096, 640)}, (192, 64),
                   13 * 20 * 4 * 4),
}


@pytest.mark.parametrize("cell", sorted(CELL_LAYOUTS))
def test_a_chunk_programs_grid_steps_from_the_cells_shapes(cell):
    """``Engine._chunk_attn_steps`` over the cache's shapes alone (no
    array is made): what ``tpu:chunk_attn_grid_steps_total`` adds a chunk
    program in each cell that streams its prompts."""
    import types

    n_heads, leaves, latent, want = CELL_LAYOUTS[cell]
    nope, rope = latent or (0, 0)
    eng = types.SimpleNamespace(
        model_cfg=types.SimpleNamespace(
            use_flash_attention=True, n_heads=n_heads,
            qk_nope_head_dim=nope, qk_rope_head_dim=rope),
        cache={k: jax.ShapeDtypeStruct(v, jnp.bfloat16)
               for k, v in leaves.items()},
        _kv_quant=False, _latent=bool(latent), paged=False)
    assert Engine._chunk_attn_steps(eng, 1024) == want
    eng._kv_quant = True  # int8 lanes: the dequant fuses into XLA's reads
    assert Engine._chunk_attn_steps(eng, 1024) == 0


def test_a_streamed_prompt_adds_its_chunk_programs_grid_steps():
    """128-wide heads, two layers, lanes of 2,048: each 256-token chunk
    program adds layers x n_kv x (c // block_q) x (s_max // block_k) =
    2 x 2 x 1 x 2 to ``tpu:chunk_attn_grid_steps_total``, whatever the
    group (4 query heads over 2 kv heads here); a bucket prefill adds none."""
    import dataclasses

    cfg = dataclasses.replace(TINY_TEST, n_layers=2, n_heads=4, n_kv_heads=2,
                              head_dim=128, max_seq_len=2048)
    engine = Engine(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(1),
                                     dtype=jnp.float32),
        EngineConfig(decode_slots=2, max_seq_len=2048,
                     prefill_buckets=(128, 256)),
        eos_id=None, dtype=jnp.float32)
    assert engine._chunk_attn_steps(256) == (
        2 * 2 * (256 // 256) * (2048 // 1024))
    engine.start()
    try:
        short = engine.generate(greedy(list(range(3, 90)), 2), timeout_s=180)
        assert short.error is None, short.error
        assert engine.profiler.hist_state()["chunk_attn_grid_steps"] == 0
        out = engine.generate(greedy([3 + i % 50 for i in range(600)], 2),
                              timeout_s=180)
        assert out.error is None, out.error
        text = metrics.render(engine.metrics_snapshot())
    finally:
        engine.stop()
    grew = families(text)
    assert grew['tpu:prompt_programs_total{program="chunk"}'] == 3
    assert grew["tpu:chunk_attn_grid_steps_total"] == 3 * 8


# -- (b) the completion chain ------------------------------------------------

TICK = 1e-4  # what one reading of the clock costs
STEP_S, CHUNK_S, PREFILL_S = 0.010, 0.050, 0.020


class SteppedClock:
    """``time``, as the engine module sees it: a clock that moves by one
    tick a reading and jumps where the thread waits for the device."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self) -> float:
        self.now += TICK
        return self.now

    def time(self) -> float:
        return 1.7e9 + self.perf_counter()

    def __getattr__(self, name):
        return getattr(time, name)


class Rig:
    """An engine whose loop runs on the test's thread over a device stub:
    the decode block, the chunk program and the bucket prefill take
    ``STEP_S`` / ``CHUNK_S`` / ``PREFILL_S`` of the stepped clock, one after
    another in the order they were enqueued, and ``block_until_ready`` on
    a result moves the clock to where the program ends."""

    def __init__(self, params, mp, stage_s: float = 0.0, **extra):
        """``stage_s``: what staging a chunk costs the host before its
        program is enqueued."""
        self.clock = SteppedClock()
        self.stage_s = {"chunk": stage_s}
        mp.setattr(engine_mod, "time", self.clock)
        self.engine = tiny_engine(params, **{"decode_slots": 2, **extra})
        self.due: dict[int, float] = {}
        self.log: list[tuple] = []  # (program, start, end, its outputs)
        self.free_at = 0.0
        for attr, name, length in (("_jit_decode", "decode", STEP_S),
                                   ("_jit_chunk", "chunk", CHUNK_S),
                                   ("_jit_prefill", "prefill", PREFILL_S)):
            setattr(self.engine, attr,
                    self._timed(getattr(self.engine, attr), name, length))
        real_block = jax.block_until_ready

        def block_until_ready(tree):
            leaves = jax.tree_util.tree_leaves(tree)
            due = self.due.get(id(leaves[0])) if leaves else None
            if due is not None:
                self.clock.now = max(self.clock.now, due)
            return real_block(tree)

        mp.setattr(jax, "block_until_ready", block_until_ready)
        mp.setattr(engine_mod, "_is_ready", lambda array: self.due.get(
            id(array), 0.0) <= self.clock.now)
        # every block, as the loop's accounting saw it
        self.blocks: list[dict] = []
        process = self.engine._process_block

        def spy(blk, current):
            eng = self.engine
            seen = {"t0": blk["t0"], "last_done": eng._last_done_pc,
                    "ahead": blk["prompts"] - (eng._prompt_enqueued
                                               - len(eng._prompt_pending)),
                    "prompt_s": self.prompt_seconds()}
            process(blk, current=current)
            seen.update(done=eng._last_done_pc,
                        wall=eng.profiler.snapshot()["records"][-1]["wall_s"],
                        prompt_s=self.prompt_seconds() - seen["prompt_s"])
            self.blocks.append(seen)

        self.engine._process_block = spy

    def _timed(self, program, name, length):
        def run(*args, **kwargs):
            self.clock.now += self.stage_s.get(name, 0.0)
            outs = program(*args, **kwargs)
            start = max(self.clock.now, self.free_at)
            self.free_at = start + length
            self.due[id(outs[0])] = self.free_at
            self.log.append((name, start, self.free_at, outs))
            return outs
        return run

    def prompt_seconds(self) -> float:
        return sum(row["seconds"]
                   for row in self.engine.profiler.prompt_state().values())

    def run(self) -> None:
        """The loop, until it finds no work."""
        eng = self.engine
        wait = eng._wait_for_work

        def once():
            wait()
            eng._running = False

        eng._wait_for_work = once
        eng._running = True
        eng._loop()
        eng._wait_for_work = wait


@pytest.fixture()
def rig(params):
    mp = pytest.MonkeyPatch()
    try:
        yield lambda **extra: Rig(params, mp, **extra)
    finally:
        mp.undo()


def _warm(rig: Rig) -> None:
    """Compile every program the scripts meet, then forget what it cost."""
    eng = rig.engine
    a, b = eng.submit(greedy([5, 6, 7], 3)), eng.submit(greedy(LONG, 3))
    rig.run()
    assert a.done.is_set() and b.done.is_set() and not (a.error or b.error)
    rig.log.clear()
    rig.blocks.clear()


class TestTheCompletionChain:
    def test_a_chunk_is_booked_as_a_chunk_and_the_step_as_a_step(self, rig):
        """A row decodes while a 40-token prompt streams in three chunks
        behind it: each block's step is the decode program's, each chunk's
        interval the chunk program's, whoever the thread waited for."""
        rig = rig()
        _warm(rig)
        eng = rig.engine
        state0 = eng.profiler.prompt_state()
        decode0 = eng.profiler.dispatch_seconds["decode"]
        gap0 = eng.profiler.gap_seconds["host"]
        row = eng.submit(greedy([5, 6, 7], 30))
        long = eng.submit(greedy(LONG, 4))
        rig.run()
        assert row.error is None and len(row.output_tokens) == 30
        assert long.error is None and len(long.output_tokens) == 4
        state = eng.profiler.prompt_state()
        chunks = state["chunk"]["programs"] - state0["chunk"]["programs"]
        chunk_s = state["chunk"]["seconds"] - state0["chunk"]["seconds"]
        assert chunks == 3
        slack = 12 * TICK  # the stamps sit a few readings off the device's
        assert chunk_s / chunks == pytest.approx(CHUNK_S, abs=slack)
        behind = [b for b in rig.blocks if b["ahead"]]
        assert len(behind) == 2  # the third chunk's first token is read
        for b in rig.blocks:
            assert b["wall"] == pytest.approx(STEP_S, abs=slack), b
        # ... where the parent's rule booked the chunk into the step
        for b in behind:
            old_rule = b["done"] - max(b["t0"], b["last_done"])
            assert old_rule == pytest.approx(STEP_S + CHUNK_S, abs=slack)
            assert b["prompt_s"] == pytest.approx(CHUNK_S, abs=slack)
        # the request's span says the same
        attrs = long.prefill_attrs
        assert (attrs["programs"], attrs["positions"]) == (3, 48)
        assert attrs["device_s"] == pytest.approx(3 * CHUNK_S, abs=3 * slack)
        assert row.prefill_attrs["device_s"] == pytest.approx(
            PREFILL_S, abs=slack)
        # the chunks' parts are summed, not the last chunk's alone: the
        # thread waited for each of the three
        assert attrs["wait_s"] >= 0.0 and attrs["stage_s"] > 0.0
        decode_s = eng.profiler.dispatch_seconds["decode"] - decode0
        assert decode_s == pytest.approx(len(rig.blocks) * STEP_S,
                                         abs=len(rig.blocks) * slack)
        # a chunk's interval is in the profiler's gap chain too: the queue
        # was never empty, so no host gap opens where the chunks ran
        assert eng.profiler.gap_seconds["host"] - gap0 < 0.2 * CHUNK_S

    def test_steps_programs_and_gaps_tile_the_chain(self, rig):
        """From the first staging to the last completion the loop saw: the
        decode steps' seconds, the prompt programs' seconds and the
        stretches the device's queue was empty add up to the chain."""
        rig = rig()
        _warm(rig)
        eng = rig.engine
        prompt0, decode0 = rig.prompt_seconds(), (
            eng.profiler.dispatch_seconds["decode"])
        first_staging = rig.clock.now
        reqs = [eng.submit(greedy([5, 6, 7], 20)), eng.submit(greedy(LONG, 9)),
                eng.submit(greedy([9, 8, 7, 6, 5], 5))]
        rig.run()
        assert all(r.error is None and r.done.is_set() for r in reqs)
        last_completion = eng._last_done_pc
        programs = [(s, e) for _, s, e, _ in rig.log]
        # the stub's own account: when nothing was running
        empty = programs[0][0] - first_staging + sum(
            max(0.0, s - prev_e)
            for (s, _), (_, prev_e) in zip(programs[1:], programs))
        booked = (rig.prompt_seconds() - prompt0
                  + eng.profiler.dispatch_seconds["decode"] - decode0)
        chain = last_completion - first_staging
        assert booked + empty == pytest.approx(
            chain, abs=6 * TICK * len(programs))
        # and every program of the stub's is in the books once
        assert booked == pytest.approx(
            sum(e - s for s, e in programs), abs=6 * TICK * len(programs))

    def test_a_block_with_nothing_ahead_books_as_before(self, rig):
        """Decode alone: the step is the parent's ``done - max(t0, last
        completion)``, to the digit."""
        rig = rig()
        _warm(rig)
        eng = rig.engine
        req = eng.submit(greedy([5, 6, 7], 25))
        rig.run()
        assert req.error is None and len(req.output_tokens) == 25
        assert len(rig.blocks) >= 24
        for b in rig.blocks:
            assert b["ahead"] == 0 and b["prompt_s"] == 0.0
            assert b["wall"] == round(
                b["done"] - max(b["t0"], b["last_done"]), 9)

    def test_a_block_done_while_the_host_stages_a_burst_is_seen_there(
            self, rig):
        """Staging a chunk costs the host 8 ms and a turn stages up to four:
        the block in flight (10 ms) is done long before the thread reads
        it.  It is seen complete between two stagings, so its step is at
        most one staging late and the burst behind it as much short; read
        where the thread gets round to it, the step would hold the whole
        burst's staging."""
        stage_s = 0.008
        rig = rig(stage_s=stage_s, stream_burst=4, decode_slots=3)
        _warm(rig)
        eng = rig.engine
        state0 = eng.profiler.prompt_state()["chunk"]
        reqs = [eng.submit(greedy([5, 6, 7], 40))]
        admit, turns = eng._admit_and_insert, []

        def admit_and_insert():
            turns.append(None)
            if len(turns) == 6:  # the row is decoding, a block in flight
                reqs.extend(eng.submit(greedy(LONG, 6)) for _ in range(2))
            return admit()

        eng._admit_and_insert = admit_and_insert
        rig.run()
        eng._admit_and_insert = admit
        assert len(reqs) == 3
        assert all(r.error is None and r.done.is_set() for r in reqs)
        state = eng.profiler.prompt_state()["chunk"]
        n = state["programs"] - state0["programs"]
        chunk_s = state["seconds"] - state0["seconds"]
        assert n == 6
        slack = 12 * TICK
        for b in rig.blocks:
            assert b["wall"] <= STEP_S + stage_s + slack, b
        late = [b for b in rig.blocks if b["wall"] > STEP_S + slack]
        assert late  # the host WAS late; by one staging, not by three
        # ... and the chunks' seconds are short by what the steps are long
        long_by = sum(b["wall"] - STEP_S for b in rig.blocks)
        assert chunk_s == pytest.approx(n * CHUNK_S - long_by,
                                        abs=len(rig.blocks) * slack)
        assert chunk_s / n > 0.95 * CHUNK_S

    def test_a_stream_with_no_row_decoding_is_booked_at_its_first_token(
            self, rig):
        """No block carries the chunks: the read of the first token implies
        all three, and they share the interval by positions."""
        rig = rig()
        _warm(rig)
        eng = rig.engine
        state0 = eng.profiler.prompt_state()["chunk"]
        req = eng.submit(greedy(LONG, 1))
        rig.run()
        assert req.error is None and len(req.output_tokens) == 1
        # (the one block staged behind the last chunk finds them booked)
        assert all(b["ahead"] == 0 and b["prompt_s"] == 0.0
                   for b in rig.blocks)
        state = eng.profiler.prompt_state()["chunk"]
        assert state["programs"] - state0["programs"] == 3
        assert state["seconds"] - state0["seconds"] == pytest.approx(
            3 * CHUNK_S, abs=12 * TICK)
        assert req.prefill_attrs["device_s"] == pytest.approx(
            3 * CHUNK_S, abs=12 * TICK)

    def test_an_abandoned_program_is_booked_before_the_loop_sleeps(
            self, rig):
        """A stream cancelled after its first chunk: nothing waits for the
        chunk any more, and the next burst must not run from its enqueue."""
        rig = rig()
        _warm(rig)
        eng = rig.engine
        req = eng.submit(greedy(LONG, 4))
        step = eng._stream_step

        def cancel_after_one():
            step()
            req.cancelled.set()

        eng._stream_step = cancel_after_one
        rig.run()
        eng._stream_step = step
        assert req.finish_reason == "cancelled"
        assert not eng._prompt_pending


# -- the profiler's side and the reports --------------------------------------

def test_the_three_families_render_their_whole_label_set():
    prof = StepProfiler()
    prof.note_prompt_program("chunk", 1000, 24)
    prof.note_prompt_program("chunk", 1024, 0)
    prof.note_prompt_program("prefill_many", 300, 212)
    prof.note_prompt_done(1.0, 1.25, [("chunk", 0.25)])
    lines = render_profile(prof.hist_state())
    for family in ("tpu:prompt_programs_total", "tpu:prompt_positions_total",
                   "tpu:prompt_program_seconds_total"):
        assert f"# TYPE {family} counter" in lines
    assert 'tpu:prompt_programs_total{program="chunk"} 2' in lines
    assert 'tpu:prompt_programs_total{program="ring"} 0' in lines
    assert ('tpu:prompt_positions_total{program="chunk",kind="real"} 2024'
            in lines)
    assert ('tpu:prompt_positions_total{program="prefill_many",kind="pad"} '
            '212' in lines)
    assert ('tpu:prompt_program_seconds_total{program="chunk"} 0.250000'
            in lines)
    with pytest.raises(KeyError):
        prof.note_prompt_program("decode", 1, 0)


def test_a_prompt_interval_sits_in_the_gap_chain_like_a_dispatch():
    """Block, chunk, block back to back: no gap; a chunk enqueued on an
    empty queue after the loop slept: an idle gap before it, none after."""
    prof = StepProfiler()
    prof.note_dispatch("decode", 0.0, 0.01)
    prof.note_prompt_done(0.01, 0.06, [("chunk", 0.05)])
    prof.note_dispatch("decode", 0.06, 0.01)
    assert prof.gap_seconds == {"host": 0.0, "idle": 0.0}
    prof.note_idle()
    prof.note_prompt_done(1.07, 1.12, [("chunk", 0.05)])
    prof.note_dispatch("decode", 1.12, 0.01)
    assert prof.gap_seconds["idle"] == pytest.approx(1.0)
    assert prof.gap_seconds["host"] == 0.0
    assert prof.prompt_state()["chunk"]["seconds"] == pytest.approx(0.1)


def test_profile_report_prints_the_prompt_programs():
    import tools.profile_report as profile_report

    prof = StepProfiler()
    prof.note_dispatch("decode", 0.0, 0.6, active=2, total_slots=4)
    for _ in range(4):
        prof.note_prompt_program("chunk", 900, 124, 1488)
        prof.note_prompt_done(0.0, 0.1, [("chunk", 0.1)])
    prof.note_prompt_program("prefill", 100, 28)
    rows = profile_report.prompt_program_rows(prof.snapshot())
    assert [r["attn_grid_steps"] for r in rows] == [0, 1488.0]
    assert [r["program"] for r in rows] == ["prefill", "chunk"]
    chunk = rows[1]
    assert (chunk["programs"], chunk["real"], chunk["pad"]) == (4, 3600, 496)
    assert chunk["pad_pct"] == pytest.approx(100 * 496 / 4096, abs=0.01)
    assert chunk["ms_per_program"] == pytest.approx(100.0)
    assert chunk["share_pct"] == pytest.approx(100 * 0.4 / 0.6, abs=0.01)
    text = profile_report.render_report(prof.snapshot())
    assert "Prompt programs" in text
    # a payload from before the families: no table, no error
    old = prof.snapshot()
    del old["hist"]["prompt"]
    assert profile_report.prompt_program_rows(old) == []
    assert "Prompt programs" not in profile_report.render_report(old)


def test_trace_report_prints_what_the_prompt_cost():
    import tools.trace_report as trace_report

    def trace(i, **attrs):
        return {"trace_id": f"t{i}", "spans": [
            {"name": "engine.prefill", "start": 10.0, "end": 10.4,
             "attrs": {"prompt_tokens": 2000, "bucket": 1024, "rows": 3,
                       "stage_s": 0.004, "wait_s": 0.25, "emit_s": 0.001,
                       **attrs}}]}

    traces = [trace(0, programs=2, positions=2048, device_s=0.26),
              trace(1, programs=1, positions=512, device_s=0.06),
              trace(2)]  # a replica from before PR 57
    rows = {r["part"]: r for r in trace_report.first_token_table(traces)}
    assert rows["  on the device's queue (device_s)"]["n"] == 2
    assert rows["  on the device's queue (device_s)"]["p50_ms"] == 260.0
    cost = {r["a request's"]: r
            for r in trace_report.prompt_cost_rows(traces)}
    assert cost["prompt programs"] == {
        "a request's": "prompt programs", "n": 2, "p50": 2, "p90": 2}
    assert cost["positions computed (padding included)"]["p50"] == 2048
    assert cost["prompt tokens"]["p50"] == 2000
    assert trace_report.prompt_cost_rows(traces[2:]) == []
