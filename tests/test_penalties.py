"""Presence/frequency penalties over generated tokens (vLLM semantics:
the prompt does not count).  Device-resident occurrence counts ride the
decode carry; penalty-free batches skip the [B, V] pass via lax.cond."""

import jax
import jax.numpy as jnp
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)

CFG = TINY_TEST


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def _engine(params, **extra):
    cfg = dict(decode_slots=3, max_seq_len=96, prefill_buckets=(8, 16))
    cfg.update(extra)
    return Engine(CFG, params, EngineConfig(**cfg),
                  eos_id=None, dtype=jnp.float32)


def _gen(engine, presence=0.0, frequency=0.0, max_new=24, temp=0.0,
         prompt=(5, 6, 7)):
    req = Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                  sampling=SamplingParams(temperature=temp,
                                          presence_penalty=presence,
                                          frequency_penalty=frequency))
    engine.generate(req, timeout_s=120)
    assert req.error is None, req.error
    return req.output_tokens


class TestPenalties:
    def test_large_presence_penalty_forbids_repeats(self, params):
        """Greedy + presence=2 (the OpenAI max) on a random tiny model:
        without the penalty the output loops; with it, once a token is
        emitted its logit drops enough that the tail stops repeating the
        dominant token (generated-token semantics)."""
        engine = _engine(params)
        engine.start()
        try:
            plain = _gen(engine)
            pen = _gen(engine, presence=2.0)
        finally:
            engine.stop()
        def max_run(toks):
            best = run = 1
            for a, b in zip(toks, toks[1:]):
                run = run + 1 if a == b else 1
                best = max(best, run)
            return best
        assert pen != plain
        assert max_run(pen) < max(max_run(plain), 2) or \
            len(set(pen)) > len(set(plain))

    def test_frequency_accumulates_per_occurrence(self, params):
        """Frequency penalty grows with count, so diversity increases
        monotonically-ish with the coefficient on a greedy loop."""
        engine = _engine(params)
        engine.start()
        try:
            none = _gen(engine, max_new=32)
            some = _gen(engine, frequency=1.5, max_new=32)
        finally:
            engine.stop()
        assert len(set(some)) > len(set(none))

    def test_zero_penalties_bitwise_unchanged(self, params):
        """The penalty-free path must match an engine that never saw the
        feature (the lax.cond skips the counts pass)."""
        e = _engine(params)
        e.start()
        try:
            a = _gen(e, temp=0.0)
            b = _gen(e, temp=0.0)
        finally:
            e.stop()
        assert a == b

    def test_fused_blocks_match_single_steps(self, params):
        """The counts ride the fused block's carry: four steps a dispatch
        penalise what one step a dispatch does."""
        single = _engine(params)
        fused = _engine(params, decode_steps_per_sync=4)
        single.start(), fused.start()
        try:
            assert (_gen(fused, presence=1.2, frequency=0.6) ==
                    _gen(single, presence=1.2, frequency=0.6))
        finally:
            single.stop(), fused.stop()

    def test_counts_reset_on_slot_reuse(self, params):
        """A later request must not inherit the previous occupant's
        occurrence counts."""
        engine = _engine(params, decode_slots=1)
        engine.start()
        try:
            first = _gen(engine, presence=2.0)
            second = _gen(engine, presence=2.0)
        finally:
            engine.stop()
        assert second == first  # fresh counts -> identical greedy walk

    def test_spec_engine_rejects_penalties(self, params):
        import dataclasses

        dcfg = dataclasses.replace(
            CFG, name="pen-draft", d_model=32, n_layers=1, n_heads=2,
            n_kv_heads=1, d_ff=64, head_dim=16)
        spec = Engine(
            CFG, params,
            EngineConfig(decode_slots=2, max_seq_len=64,
                         prefill_buckets=(8,), speculative_k=2),
            eos_id=None, dtype=jnp.float32,
            draft_params=transformer.init_params(
                dcfg, jax.random.PRNGKey(7), dtype=jnp.float32),
            draft_cfg=dcfg)
        with pytest.raises(ValueError, match="penalties"):
            spec.submit(Request(
                prompt_tokens=[5, 6], max_new_tokens=4,
                sampling=SamplingParams(presence_penalty=1.0)))


class TestLogitBias:
    def test_forced_and_banned_tokens(self, params):
        """A +100 bias forces a token at every pick (greedy included, first
        token included); banning the natural greedy choice changes the
        walk."""
        engine = _engine(params)
        engine.start()
        try:
            forced = Request(prompt_tokens=[5, 6, 7], max_new_tokens=6,
                             sampling=SamplingParams(
                                 temperature=0.0, logit_bias={99: 100.0}))
            engine.generate(forced, timeout_s=120)
            assert forced.error is None
            assert forced.output_tokens == [99] * 6

            plain = Request(prompt_tokens=[5, 6, 7], max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.0))
            engine.generate(plain, timeout_s=120)
            banned_id = plain.output_tokens[0]
            banned = Request(prompt_tokens=[5, 6, 7], max_new_tokens=6,
                             sampling=SamplingParams(
                                 temperature=0.0,
                                 logit_bias={banned_id: -100.0}))
            engine.generate(banned, timeout_s=120)
            assert banned.error is None
            assert banned.output_tokens[0] != banned_id
        finally:
            engine.stop()

    def test_bias_cap_rejected_at_submit(self, params):
        from llm_instance_gateway_tpu.server.engine import MAX_LOGIT_BIAS

        engine = _engine(params)
        with pytest.raises(ValueError, match="at most"):
            engine.submit(Request(
                prompt_tokens=[5], max_new_tokens=2,
                sampling=SamplingParams(
                    logit_bias={i: 1.0
                                for i in range(MAX_LOGIT_BIAS + 1)})))
