"""Per-request reproducible sampling (the OpenAI ``seed`` param).

Contract: a seeded row's tokens depend only on (seed, position,
distribution) — identical across engine restarts and across whatever else
shares its batch; unseeded rows keep the engine-RNG draw bit-for-bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.sampling import sample
from tests._reference import reference_tokens

CFG = TINY_TEST


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


class TestSampleLevel:
    def test_seeded_rows_ignore_engine_key(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (4, 64)) * 3
        logits = logits.at[2].set(logits[0])  # rows 0/2: same distribution
        args = (jnp.ones((4,), jnp.float32),          # temperature 1
                jnp.zeros((4,), jnp.int32),           # top_k off
                jnp.ones((4,), jnp.float32))          # top_p off
        seeds = jnp.asarray([7, -1, 7, 9], jnp.int32)
        pos = jnp.asarray([3, 3, 3, 3], jnp.int32)
        a = sample(logits, jax.random.PRNGKey(100), *args,
                   seeds=seeds, positions=pos)
        b = sample(logits, jax.random.PRNGKey(999), *args,
                   seeds=seeds, positions=pos)
        # Seeded rows identical under different engine keys; rows 0 and 2
        # (same seed, same position, same logits) agree with each other.
        assert int(a[0]) == int(b[0]) == int(a[2])
        assert int(a[3]) == int(b[3])

    def test_unseeded_rows_bitwise_match_legacy_path(self):
        logits = jax.random.normal(jax.random.PRNGKey(2), (3, 64)) * 3
        args = (jnp.ones((3,), jnp.float32), jnp.zeros((3,), jnp.int32),
                jnp.ones((3,), jnp.float32))
        key = jax.random.PRNGKey(5)
        legacy = sample(logits, key, *args)
        with_arg = sample(logits, key, *args,
                          seeds=jnp.full((3,), -1, jnp.int32),
                          positions=jnp.zeros((3,), jnp.int32))
        assert np.array_equal(np.asarray(legacy), np.asarray(with_arg))

    def test_position_varies_the_draw(self):
        logits = jax.random.normal(jax.random.PRNGKey(3), (1, 512))
        args = (jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.float32))
        toks = {int(sample(logits, jax.random.PRNGKey(0), *args,
                           seeds=jnp.asarray([4], jnp.int32),
                           positions=jnp.asarray([p], jnp.int32))[0])
                for p in range(16)}
        assert len(toks) > 1  # fold_in(position) actually varies draws


def _engine(params, **extra):
    return Engine(
        CFG, params,
        EngineConfig(decode_slots=3, max_seq_len=64, prefill_buckets=(8, 16),
                     **extra),
        eos_id=None, dtype=jnp.float32)


def _gen(engine, seed, prompt=(5, 6, 7), max_new=12):
    req = Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                  sampling=SamplingParams(temperature=0.9, seed=seed))
    engine.generate(req, timeout_s=120)
    assert req.error is None, req.error
    return req.output_tokens


class TestEngineLevel:
    def test_reproducible_across_engines_and_batchmates(self, params):
        e1 = _engine(params)
        e1.start()
        try:
            alone = _gen(e1, seed=42)
            again = _gen(e1, seed=42)
            other = _gen(e1, seed=43)
            # Same seed reproduces; different seed diverges.
            assert again == alone
            assert other != alone
            # Alongside unrelated batchmates: still identical.
            mates = [Request(prompt_tokens=[9, 9], max_new_tokens=12,
                             sampling=SamplingParams(temperature=0.8))
                     for _ in range(2)]
            seeded = Request(prompt_tokens=[5, 6, 7], max_new_tokens=12,
                             sampling=SamplingParams(temperature=0.9,
                                                     seed=42))
            for r in mates + [seeded]:
                e1.submit(r)
            for r in mates + [seeded]:
                assert r.done.wait(120) and r.error is None
            assert seeded.output_tokens == alone
        finally:
            e1.stop()
        # A fresh engine (different internal RNG stream) reproduces too.
        e2 = _engine(params)
        e2.start()
        try:
            assert _gen(e2, seed=42) == alone
        finally:
            e2.stop()

    def test_fused_blocks_draw_what_the_plain_reference_draws(self, params):
        """Four steps a dispatch against no engine at all: ``prefill`` and
        ``decode_step`` on one row, each token drawn by ``sample`` with the
        request's seed at its position."""
        fused = _engine(params, decode_steps_per_sync=4)
        fused.start()
        try:
            got = _gen(fused, seed=11)
        finally:
            fused.stop()
        assert got == reference_tokens(
            CFG, params, [5, 6, 7], 12,
            sampling=SamplingParams(temperature=0.9, seed=11))


class TestSeedFanout:
    def test_candidate_index_decorrelates_n(self, params):
        """seed + n>1: candidates must differ (candidate index folds into
        the seed) while the whole response stays reproducible."""
        from llm_instance_gateway_tpu.server.api_http import ModelServer

        class _Tok:  # minimal tokenizer stand-in
            eos_id = None
            def encode(self, s): return [5, 6, 7]
            def decode(self, ids): return "x" * len(ids)

        engine = _engine(params)
        srv = ModelServer(engine, _Tok(), "tiny")
        body = {"model": "tiny", "seed": 42, "temperature": 0.9,
                "max_tokens": 10, "n": 3}
        reqs1 = [srv._make_request(body, [5, 6, 7], None, candidate=i)
                 for i in range(3)]
        reqs2 = [srv._make_request(body, [5, 6, 7], None, candidate=i)
                 for i in range(3)]
        assert [r.sampling.seed for r in reqs1] == [42, 43, 44]
        engine.start()
        try:
            for r in reqs1 + reqs2:
                engine.submit(r)
            for r in reqs1 + reqs2:
                assert r.done.wait(120) and r.error is None
        finally:
            engine.stop()
        outs1 = [r.output_tokens for r in reqs1]
        outs2 = [r.output_tokens for r in reqs2]
        assert outs1 == outs2              # reproducible as a set
        assert len({tuple(o) for o in outs1}) == 3  # and distinct
