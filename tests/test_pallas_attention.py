"""Flash-attention kernel parity (interpret mode on CPU; on the chip: tools/onchip_pallas_check.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.ops.attention import prefill_attention
from llm_instance_gateway_tpu.ops import pallas_attention


def make_qkv(b=2, s=256, h=4, kv=2, hd=128, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    return q, k, v


class TestFlashAttention:
    def test_matches_reference_causal(self):
        q, k, v = make_qkv()
        ref = prefill_attention(q, k, v)
        got = pallas_attention.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_head_mapping(self):
        # 8 query heads sharing 2 KV heads: head h must use kv head h//4.
        q, k, v = make_qkv(b=1, s=128, h=8, kv=2, seed=3)
        ref = prefill_attention(q, k, v)
        got = pallas_attention.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_unsupported_shapes_fall_back(self):
        # hd=16 violates the lane constraint -> XLA path, still correct.
        q, k, v = make_qkv(s=64, hd=16)
        assert not pallas_attention.supports(64, 16)
        ref = prefill_attention(q, k, v)
        got = pallas_attention.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-6)

    def test_right_padding_real_positions_exact(self):
        # Pad tail must not perturb real positions (the engine contract).
        q, k, v = make_qkv(b=1, s=256, seed=5)
        true_len = 100
        ref = prefill_attention(q[:, :true_len], k[:, :true_len], v[:, :true_len])
        got = pallas_attention.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(got[:, :true_len]), rtol=2e-5, atol=2e-5
        )


class TestChunkAttention:
    """Flash-style chunk attend (chunk-stream prefill hot op): parity with
    the XLA reference at every chunk offset, incl. the dynamic-diagonal
    masking and the garbage tail past the chunk's reach."""

    def _inputs(self, b=1, c=128, s_max=512, h=4, kv=2, hd=128, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, c, h, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (b, s_max, kv, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (b, s_max, kv, hd), jnp.float32)
        return q, kc, vc

    @pytest.mark.parametrize("start", [0, 64, 128, 200, 384])
    def test_matches_reference_at_offsets(self, start):
        # 64/200: UNALIGNED starts (the prefix-reuse admission path passes
        # block-granular offsets) — dynamic diagonal with partially-masked
        # rows and a mid-tile DMA clamp.
        from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention
        from llm_instance_gateway_tpu.ops.pallas_attention import (
            chunk_attention_pallas,
        )

        q, kc, vc = self._inputs(seed=start)
        ref = xla_chunk_attention(q, kc, vc, start)
        got = chunk_attention_pallas(q, kc, vc, jnp.int32(start),
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_garbage_past_reach_ignored(self):
        # Cache positions beyond start+i must not perturb outputs (they're
        # previous tenants' garbage the causal mask excludes).
        from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention
        from llm_instance_gateway_tpu.ops.pallas_attention import (
            chunk_attention_pallas,
        )

        start = 128
        q, kc, vc = self._inputs(seed=7)
        kc_p = kc.at[:, start + 128:].set(1e3)
        vc_p = vc.at[:, start + 128:].set(-1e3)
        ref = xla_chunk_attention(q, kc, vc, start)
        got = chunk_attention_pallas(q, kc_p, vc_p, jnp.int32(start),
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_dispatch_falls_back(self):
        # c=24 misses the 128 tile: the entry must take the XLA reference.
        from llm_instance_gateway_tpu.ops import pallas_attention as pa

        q, kc, vc = self._inputs(c=24, seed=3)
        assert not pa.supports_chunk(24, 512, 128)
        out = pa.chunk_attention(q, kc, vc, 16)
        assert out.shape == q.shape
