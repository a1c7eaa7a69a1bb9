"""Decode staging (PR 30): the slot state goes up as two buffers, the key
and the penalty-free counts dummy stay on the device.

The reference is the parent's behaviour, kept here and not in the package:
``RestagingEngine`` uploads every per-slot field from its host mirror on
every dispatch (by copy: on the CPU backend ``jnp.asarray`` can alias its
numpy source, which would hide a stale device value), splits the key on the
host and makes a fresh counts dummy, and runs the parent's decode program.
A schedule in which consecutive occupants of a slot differ in everything a
slot carries has to give the same tokens and logprobs through the engine as
through it, dense and sparse.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_MOE_TEST, TINY_TEST
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    _SLOT_F32,
    _SLOT_I32,
    MAX_LOGIT_BIAS,
    STAGE_UPLOADS,
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
    _logprob_info,
    _named,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager
from llm_instance_gateway_tpu.server.sampling import (
    STOP_LEN,
    STOP_SEQS,
    sample_routed,
    stop_hist_update,
    stop_suffix_hit,
)

SLOTS = 2
MODELS = {"dense": TINY_TEST, "moe": TINY_MOE_TEST}
# The parent's mirrors, but for ``tokens`` (PR 48: a row's last token never
# leaves the device carry): name -> (dtype, shape of a row, empty row).
PARENT_MIRRORS = {
    "positions": (np.int32, (), 0),
    "lora": (np.int32, (), -1), "temp": (np.float32, (), 0.0),
    "topk": (np.int32, (), 0), "topp": (np.float32, (), 1.0),
    "seed": (np.int32, (), -1), "presence": (np.float32, (), 0.0),
    "frequency": (np.float32, (), 0.0),
    "bias_ids": (np.int32, (MAX_LOGIT_BIAS,), -1),
    "bias_vals": (np.float32, (MAX_LOGIT_BIAS,), 0.0),
    "remaining": (np.int32, (), 0),
    "stop_ids": (np.int32, (STOP_SEQS, STOP_LEN), -1),
    "stop_lens": (np.int32, (STOP_SEQS,), 0),
    "stop_hist": (np.int32, (STOP_LEN,), -1),
}


def _parent_decode_impl(
    model_cfg, step_fn, params, lora_bufs, cache, tokens, positions,
    slot_ids, temp, topk, topp, key, remaining, eos_id, seeds,
    presence, frequency, counts, bias_ids, bias_vals,
    stop_ids, stop_lens, stop_hist,
    n_steps: int, penalized: bool = False,
):
    """The decode program as PR 29 had it, verbatim but for this
    docstring: every per-slot field its own argument, ``key`` already split
    off the engine's by the host."""
    if "tables" in cache:  # paged: logical length = table span * block
        max_len = cache["tables"].shape[1] * cache["k"].shape[2]
    else:
        max_len = cache["k"].shape[2]

    c0 = tokens.shape[0]
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
        lp, top_v, top_i = _logprob_info(
            logits, sampled, model_cfg.vocab_size)
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
    (cache, next_tokens, next_positions, next_remaining, next_hist,
     counts) = carry
    # The token/position/budget/history carries live on device for
    # pipelined dispatch of the following block (no host round-trip).
    moe = cache.pop("moe", None)
    return (toks, valid, lps, top_v, top_i, paths,
            next_tokens, next_positions, next_remaining, next_hist,
            counts, cache, moe)


class RestagingEngine(Engine):
    """The parent's staging: an upload a field, an eager key split (and its
    unpacking) on the host and a fresh dummy before every block of the
    parent's program; the same eager split before every prefill."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        model_cfg, step_fn = self._jit_decode.__wrapped__.args
        self._jit_parent = jax.jit(
            _named("decode_block", _parent_decode_impl, model_cfg, step_fn),
            donate_argnames=("cache", "counts"),
            static_argnames=("n_steps", "penalized"))

    def _next_key(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _enqueue_decode(self, n_steps, carry):
        def up(name):
            return jnp.array(getattr(self, "_slot_" + name), copy=True)

        tokens, positions, remaining, hist = self._scatter_into(carry)
        penalized = bool(self._slot_presence.any()
                         or self._slot_frequency.any())
        counts = (self._counts() if penalized
                  else jnp.zeros((self.cfg.decode_slots, 1), jnp.int32))
        (*outs, next_tokens, next_positions, next_remaining, next_hist,
         counts, self.cache, moe) = self._jit_parent(
            self.params, self._lora_buffers(), self.cache, tokens, positions,
            up("lora"), up("temp"), up("topk"), up("topp"), self._next_key(),
            remaining, self._eos_for_device, up("seed"), up("presence"),
            up("frequency"), counts, up("bias_ids"), up("bias_vals"),
            up("stop_ids"), up("stop_lens"), hist,
            n_steps=n_steps, penalized=penalized)
        if penalized:
            self._dev_counts = counts
        return (outs, (next_tokens, next_positions, next_remaining,
                       next_hist), self._moe_drain(moe))


def _scatter_into(self, carry):
    """The parent's way with the device carry, eager and row by
    row: an activated row's position, budget and stop history scattered
    in, a freed row's budget zeroed (``_stage_carry`` does both inside the
    program, from the staged buffer)."""
    tokens, positions, remaining, hist = carry
    for i in np.flatnonzero(self._slot_fresh):
        positions = positions.at[i].set(int(self._slot_positions[i]))
        remaining = remaining.at[i].set(int(self._slot_remaining[i]))
        row = self._slot_stop_hist[i].copy()
        hist = hist.at[i].set(jnp.array(row))
        if row[-1] < 0:
            hist = hist.at[i, -1].set(tokens[i])
    for i in np.flatnonzero(self._slot_remaining <= 0):
        remaining = remaining.at[i].set(0)
    self._slot_fresh[:] = 0
    return tokens, positions, remaining, hist


RestagingEngine._scatter_into = _scatter_into


def build(engine_cls, model: str, slots: int = SLOTS):
    """An engine of ``engine_cls`` over seeded tiny weights and two
    adapters; same arguments, same weights."""
    cfg = MODELS[model]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    lora = LoRAManager(cfg, dtype=jnp.float32)
    dims = target_dims(cfg)
    rng = np.random.RandomState(0)
    for name in ("ad-a", "ad-b"):
        lora.load(name, weights={
            t: {"a": rng.randn(cfg.n_layers, dims[t][0], 2) * 0.3,
                "b": rng.randn(cfg.n_layers, 2, dims[t][1]) * 0.3}
            for t in ("q", "v")}, alpha=4.0, rank=2)
    return engine_cls(
        cfg, params,
        EngineConfig(decode_slots=slots, max_seq_len=256,
                     prefill_buckets=(8,)),
        lora_manager=lora, eos_id=None, dtype=jnp.float32)


def record(req: Request) -> dict:
    return {"tokens": list(req.output_tokens),
            "logprobs": list(req.output_logprobs),
            "top": list(req.output_top_logprobs),
            "finish": req.finish_reason, "error": req.error}


def wait_for(cond, what: str) -> None:
    deadline = time.monotonic() + 180
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def occupants(stop_seq, stop_id) -> list[Request]:
    """Ten requests no two neighbours of which agree in adapter, sampling
    parameters, seed, bias, stops or penalties.  Every draw is seeded or
    greedy, so a request's answer depends on nothing but itself: not on
    the slot it gets, nor on what the other slot holds, nor on when the
    engine thread admits it."""
    S = SamplingParams
    return [
        Request([3, 5, 7], 6, adapter="ad-a", logprobs=5),
        Request([2, 4, 6, 8], 7, logprobs=1, sampling=S(
            temperature=0.9, top_k=5, seed=11,
            logit_bias={7: 5.0, 9: -3.0})),
        Request([9, 8, 7], 8, adapter="ad-b", logprobs=2, sampling=S(
            presence_penalty=0.8, frequency_penalty=0.4)),
        Request([1, 2], 5, adapter="ad-a", logprobs=0, sampling=S(
            temperature=0.7, top_p=0.8, seed=5)),
        Request([3, 5, 7], 12, logprobs=1, stop_sequences=(stop_seq,)),
        Request([3, 5, 7], 12, logprobs=1, stop_token_ids=(stop_id,)),
        Request([4, 4, 4, 4], 6, adapter="ad-b", logprobs=3, sampling=S(
            logit_bias={11: 4.0, 12: 4.0})),
        Request([6, 1], 9, logprobs=1, sampling=S(
            temperature=1.0, seed=3, frequency_penalty=0.6)),
        Request([5, 5, 5], 4, logprobs=5),
        Request([7, 3], 6, adapter="ad-a", logprobs=1, sampling=S(
            temperature=0.8, top_k=3, top_p=0.9, seed=2**31 + 5)),
    ]


def run_schedule(engine: Engine) -> list[dict]:
    """Admissions, finishes, a cancel and slot reuse through ``SLOTS``
    slots; the record of every request, in the order of the script."""
    done: list[Request] = []
    engine.start()
    try:
        probe = engine.generate(Request([3, 5, 7], 12, logprobs=1),
                                timeout_s=180)
        assert probe.error is None, probe.error
        done.append(probe)
        # Stops that the probe's greedy answer does hit, mid-answer.
        stop_seq = tuple(probe.output_tokens[3:5])
        stop_id = probe.output_tokens[6]
        # A long answer holds one slot while ten occupants pass through
        # the other; it is cancelled once they are through.
        long = engine.submit(Request([8, 6, 4], 240, logprobs=1))
        first = occupants(stop_seq, stop_id)
        for req in first:
            engine.submit(req)
        for req in first:
            assert req.done.wait(180), "occupant never finished"
        wait_for(lambda: len(long.output_tokens) >= 2, "long never decoded")
        long.cancelled.set()
        assert long.done.wait(180)
        # Both slots free: the same ten in another order, two at a time, so
        # that each slot's next occupant differs from its last again.
        second = occupants(stop_seq, stop_id)
        second = second[5:] + second[:5]
        for req in second:
            engine.submit(req)
        for req in second:
            assert req.done.wait(180), "occupant never finished"
        done += [*first, long, *second]
    finally:
        engine.stop()
    return [record(r) for r in done]


@pytest.fixture(scope="module", params=list(MODELS))
def schedules(request):
    """(engine's records, reference's records, the staged buffers the
    engine's dispatches passed) of one model."""
    want = run_schedule(build(RestagingEngine, request.param))
    engine = build(Engine, request.param)
    program, aliased = engine._jit_decode, []

    def spy(*args, **kwargs):
        for buf, mirror in ((args[3], engine._slots_i32),
                            (args[4], engine._slots_f32)):
            if (not isinstance(buf, np.ndarray)
                    or np.shares_memory(buf, mirror)):
                aliased.append(type(buf))
        return program(*args, **kwargs)

    engine._jit_decode = spy
    got = run_schedule(engine)
    return got, want, aliased, engine


class TestParity:
    def test_the_schedule_ran_and_used_what_it_scripts(self, schedules):
        got, _, _, _ = schedules
        assert len(got) == 22
        assert all(r["error"] is None for r in got)
        finishes = [r["finish"] for r in got]
        assert finishes.count("stop") == 4       # two stops, two waves
        assert finishes.count("cancelled") == 1  # the long answer
        assert finishes.count("length") == 17
        # The stops cut their answers short of the probe's twelve.
        assert len(got[5]["tokens"]) == 5 and len(got[6]["tokens"]) == 7

    def test_tokens_match_the_restaging_engine(self, schedules):
        got, want, _, _ = schedules
        for i, (g, w) in enumerate(zip(got, want, strict=True)):
            if g["finish"] == "cancelled":
                n = min(len(g["tokens"]), len(w["tokens"]))
                assert n >= 2 and g["tokens"][:n] == w["tokens"][:n]
            else:
                assert g["tokens"] == w["tokens"], i
            assert g["finish"] == w["finish"], i

    def test_logprobs_match_the_restaging_engine(self, schedules):
        got, want, _, _ = schedules
        for i, (g, w) in enumerate(zip(got, want, strict=True)):
            n = min(len(g["logprobs"]), len(w["logprobs"]))
            assert n == len(g["tokens"]) or g["finish"] == "cancelled"
            assert g["logprobs"][:n] == w["logprobs"][:n], i
            assert g["top"][:n] == w["top"][:n], i

    def test_every_dispatch_uploaded_private_copies(self, schedules):
        """The buffers a dispatch hands to the program are numpy copies
        that share no memory with the mirrors: what the host writes into a
        row while the block is in flight cannot reach it."""
        _, _, aliased, engine = schedules
        assert engine.profiler.dispatches["decode"] > 40
        assert aliased == []

    def test_staging_ops_stay_under_three_a_dispatch(self, schedules):
        _, _, _, engine = schedules
        ops = engine.profiler.hist_state()["stage_ops"]
        n = engine.profiler.dispatches["decode"]
        # The budget-zero scatters went into the program (PR 40).
        assert ops == STAGE_UPLOADS * n


def test_steady_dispatch_books_two_ops_and_draws_the_parents_stream():
    """One request at a time, sampled WITHOUT a seed: its draws come from
    the engine's key, which the decode program now splits on the device
    and prefill's ``_next_key`` on the host.  The answers equal the
    restaging engine's, so the key sequence is the parent's; and a steady
    decode dispatch books exactly the two uploads."""
    def answers(engine):
        out = []
        engine.start()
        try:
            for i, (temp, top_k) in enumerate(
                    [(0.8, 0), (1.2, 4), (0.0, 0), (0.6, 0)]):
                req = engine.generate(Request(
                    [3 + i, 5, 7], 24, logprobs=0, sampling=SamplingParams(
                        temperature=temp, top_k=top_k)), timeout_s=180)
                assert req.error is None, req.error
                out.append((req.output_tokens, req.output_logprobs))
        finally:
            engine.stop()
        return out

    engine = build(Engine, "dense")
    got = answers(engine)
    assert got == answers(build(RestagingEngine, "dense"))
    assert len({tuple(t) for t, _ in got}) == 4

    ops = engine.profiler.hist_state()["stage_ops"]
    n = engine.profiler.dispatches["decode"]
    assert n >= 4 * 23
    assert ops == STAGE_UPLOADS * n
    text = metrics.render(engine.metrics_snapshot())
    assert f"tpu:decode_stage_ops_total {ops}\n" in text + "\n"
    assert engine.profiler.snapshot()["hist"]["stage_ops"] == ops


def test_adapter_rows_are_booked_from_the_staged_buffer():
    """``tpu:lora_rows_total``: two adapter rows and one base row live
    book 2 a step, read off the int32 buffer that goes up anyway, so a
    dispatch still stages with its two uploads and nothing else."""
    engine = build(Engine, "dense", slots=3)
    booked = []
    note = engine.profiler.note_lora_rows

    def spy(n):
        live = [s for s in engine.slots if s is not None]
        booked.append((n, sum(s.lora_slot >= 0 for s in live), len(live)))
        note(n)

    engine.profiler.note_lora_rows = spy
    engine.start()
    try:
        reqs = [engine.submit(Request([3, 5, 7], 40, adapter=adapter))
                for adapter in ("ad-a", None, "ad-b")]
        for req in reqs:
            assert req.done.wait(180) and req.error is None, req.error
    finally:
        engine.stop()
    n = engine.profiler.dispatches["decode"]
    assert len(booked) == n
    assert all(rows == adapters for rows, adapters, _ in booked)
    assert sum(1 for b in booked if b == (2, 2, 3)) >= 20
    hist = engine.profiler.snapshot()["hist"]
    assert hist["lora_rows"] == sum(rows for rows, _, _ in booked) > 0
    assert hist["stage_ops"] == STAGE_UPLOADS * n  # the count cost none
    text = metrics.render(engine.metrics_snapshot())
    assert f"tpu:lora_rows_total {hist['lora_rows']}\n" in text + "\n"


def test_base_rows_book_no_adapter_row():
    engine = build(Engine, "dense")
    engine.start()
    try:
        req = engine.generate(Request([3, 5, 7], 8), timeout_s=180)
        assert req.error is None, req.error
    finally:
        engine.stop()
    assert engine.profiler.dispatches["decode"] >= 7
    assert engine.profiler.hist_state()["lora_rows"] == 0
    assert "tpu:lora_rows_total 0\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


@pytest.mark.parametrize("max_seq_len,n_prompt,tiles", [
    (384, 126, (128, 3)), (256, 3, (256, 1)), (64, 3, (0, 0))],
    ids=["crosses-a-tile", "one-tile", "no-kernel-for-the-shape"])
def test_grid_steps_are_booked_by_the_schedules_rule(max_seq_len, n_prompt,
                                                     tiles):
    """``tpu:decode_attn_grid_steps_total``: a decode step books the tiles
    the kernel's schedule holds of its live rows' lanes (the first new
    token comes from the prefill, so the steps read n_prompt + 1, + 2, ...
    positions), by ``decode_schedule``'s rule on the host; nothing where no
    kernel takes the cache's shape."""
    from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda

    cfg = MODELS["dense"]
    engine = Engine(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32),
        EngineConfig(decode_slots=2, max_seq_len=max_seq_len,
                     prefill_buckets=(8, 128)),
        eos_id=None, dtype=jnp.float32)
    assert engine._attn_tiles == pda.lane_tiles(engine.cache["k"]) == tiles
    engine.start()
    try:
        req = engine.generate(
            Request([3 + i % 50 for i in range(n_prompt)], 6), timeout_s=180)
        assert req.error is None, req.error
    finally:
        engine.stop()
    steps = engine.profiler.dispatches["decode"]
    want = sum(-(-(n_prompt + 1 + j) // tiles[0]) for j in range(steps)
               ) if tiles[1] else 0
    assert steps >= 5 and (want > steps or tiles[1] < 2)
    hist = engine.profiler.hist_state()
    assert hist["attn_grid_steps"] == want
    assert f"tpu:decode_attn_grid_steps_total {want}\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


@pytest.fixture(scope="module")
def idle_engine():
    return build(Engine, "dense")


@pytest.mark.parametrize("name", sorted(PARENT_MIRRORS))
def test_mirror_is_a_view_of_its_buffer(idle_engine, name):
    """Each ``_slot_<name>`` keeps the parent's dtype, shape and empty
    value, and a write to it lands in the buffer that is uploaded."""
    dtype, shape, empty = PARENT_MIRRORS[name]
    mirror = getattr(idle_engine, "_slot_" + name)
    buf = (idle_engine._slots_i32 if dtype is np.int32
           else idle_engine._slots_f32)
    assert mirror.dtype == dtype and mirror.shape == (SLOTS, *shape)
    assert (mirror == empty).all()
    assert np.shares_memory(mirror, buf)
    before = buf.copy()
    mirror[1] = 7
    assert (buf != before).sum() == int(np.prod(shape, dtype=int))
    mirror[1] = empty
    assert (buf == before).all()


def test_buffers_hold_the_mirrors_fields_and_nothing_else(idle_engine):
    # ... but the mark of a row activated since the last block (PR 40) and
    # the mark of a row that asked for logprobs (PR 56), which no mirror of
    # the parent's stood for.
    assert sorted(n for n, _, _ in _SLOT_I32 + _SLOT_F32) == sorted(
        [*PARENT_MIRRORS, "fresh", "logprobs"])
    for fields, buf in ((_SLOT_I32, idle_engine._slots_i32),
                        (_SLOT_F32, idle_engine._slots_f32)):
        assert buf.ndim == 1 and buf.size == SLOTS * sum(
            int(np.prod(shape, dtype=int)) for _, shape, _ in fields)
