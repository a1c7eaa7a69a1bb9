"""Chunked prefill parity: N chunks must reproduce a monolithic prefill."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request

CFG = TINY_TEST


@pytest.mark.parametrize("chunk", [4, 8, 32], ids=lambda c: f"chunk{c}")
def test_prefill_with_cache_matches_monolithic(chunk):
    """N chunks (six of 4, three of 8 with a padded last one, one padded
    chunk of 32) equal one prefill: last logits, the lane's K/V in every
    layer of the stacked cache, its length; other lanes untouched."""
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = list(np.random.RandomState(0).randint(1, 250, size=23))
    n = len(prompt)
    # Monolithic reference.
    tokens = jnp.asarray([prompt], jnp.int32)
    positions = jnp.arange(n)[None]
    ref_logits, ref_k, ref_v = transformer.prefill(CFG, params, tokens, positions)

    # Chunked (last chunk padded), slot 1 of a 2-lane cache.
    cache = transformer.init_decode_cache(CFG, 2, 64, dtype=jnp.float32)
    for start in range(0, n, chunk):
        piece = prompt[start:start + chunk]
        c = len(piece)
        toks = np.zeros((chunk,), np.int32)
        toks[:c] = piece
        pos = start + np.arange(chunk, dtype=np.int32)
        last_logits, cache = transformer.prefill_with_cache(
            CFG, params, cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.int32(1), jnp.int32(start + c), jnp.int32(c - 1),
        )
    # Final-position logits match the monolithic prefill's.
    np.testing.assert_allclose(
        np.asarray(last_logits), np.asarray(ref_logits[0, n - 1]),
        rtol=2e-4, atol=2e-4,
    )
    # The lane's cached K/V for real positions match too.
    for name, ref in (("k", ref_k), ("v", ref_v)):
        np.testing.assert_allclose(
            np.asarray(cache[name][:, 1, :n]), np.asarray(ref[:, 0]),
            rtol=2e-4, atol=2e-4,
        )
    assert int(cache["length"][1]) == n
    # Other lanes untouched.
    assert float(jnp.abs(cache["k"][:, 0]).sum()) == 0.0


def test_engine_long_prompt_matches_bucketed():
    """A prompt beyond the largest bucket (chunked path) must produce the
    same greedy continuation as an engine whose bucket covers it whole."""
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = list(np.random.RandomState(1).randint(1, 250, size=40))

    big = Engine(
        CFG, params,
        EngineConfig(decode_slots=2, max_seq_len=96, prefill_buckets=(64,)),
        eos_id=None, dtype=jnp.float32,
    )
    big.start()
    try:
        want = big.generate(Request(prompt_tokens=prompt, max_new_tokens=6),
                            timeout_s=120).output_tokens
    finally:
        big.stop()

    chunked = Engine(
        CFG, params,
        EngineConfig(decode_slots=2, max_seq_len=96, prefill_buckets=(16,),
                     decode_steps_per_sync=2),
        eos_id=None, dtype=jnp.float32,
    )
    chunked.start()
    try:
        got = chunked.generate(Request(prompt_tokens=prompt, max_new_tokens=6),
                               timeout_s=120)
    finally:
        chunked.stop()
    assert got.error is None
    assert got.output_tokens == want


def test_unusable_bucket_config_rejected_at_submit():
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = Engine(
        CFG, params,
        EngineConfig(decode_slots=1, max_seq_len=32, prefill_buckets=(64,)),
        eos_id=None, dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="no usable prefill bucket"):
        engine.submit(Request(prompt_tokens=[1, 2], max_new_tokens=2))


def test_cancel_during_chunked_prefill_stops_chunks():
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = Engine(
        CFG, params,
        EngineConfig(decode_slots=1, max_seq_len=96, prefill_buckets=(8,)),
        eos_id=None, dtype=jnp.float32,
    )
    req = Request(prompt_tokens=list(range(1, 81)), max_new_tokens=10)
    req.cancelled.set()  # dead before admission: no chunks should run
    engine.start()
    try:
        engine.submit(req)
        assert req.done.wait(30)
        assert req.finish_reason == "cancelled"
        assert req.output_tokens == []
    finally:
        engine.stop()


def test_stream_interleaves_with_decode():
    """While a long prompt streams in chunk-by-chunk, an already-active
    request must keep producing tokens (round 1 ran the whole chunked
    prefill inside one admission, stalling every active slot)."""
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = Engine(
        CFG, params,
        EngineConfig(decode_slots=2, max_seq_len=256, prefill_buckets=(8,),
                     decode_steps_per_sync=1),
        eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        a = Request(prompt_tokens=[1, 2, 3], max_new_tokens=200)
        engine.submit(a)
        # Wait until A is actively decoding.
        for _ in range(600):
            if len(a.output_tokens) >= 2:
                break
            a.stream_event.wait(0.1)
            a.stream_event.clear()
        assert len(a.output_tokens) >= 2

        a_before = len(a.output_tokens)
        b = Request(prompt_tokens=list(range(1, 161)), max_new_tokens=4)
        engine.submit(b)  # 160 tokens / 8-token chunks = 20 stream steps
        assert b.done.wait(120) and b.error is None
        a_during = len(a.output_tokens) - a_before
        # One decode block runs between consecutive chunks: A must have
        # advanced roughly one token per chunk (>= 10 allows scheduling
        # slack); the blocking design yielded ~0.
        assert a_during >= 10, f"A advanced only {a_during} during stream"
        a.cancelled.set()
        assert a.done.wait(60)
    finally:
        engine.stop()
