"""Flash-attention kernel parity (interpret mode on CPU; on the chip: tools/onchip_pallas_check.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.ops.attention import (
    pack_heads,
    prefill_attention,
)
from llm_instance_gateway_tpu.ops import pallas_attention


def make_qkv(b=2, s=256, h=4, kv=2, hd=128, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    return q, k, v


# LFM2's layout: 64-wide heads, 8 kv heads, a group of 4, two kv heads to a
# 128-lane row as the model hands them to the dispatchers.
NARROW = dict(h=32, kv=8, hd=64)


class TestFlashAttention:
    @pytest.mark.parametrize("layout", [{}, dict(b=1, **NARROW)],
                             ids=["hd128", "hd64-packed"])
    def test_matches_reference_causal(self, layout):
        q, k, v = make_qkv(**layout)
        ref = prefill_attention(q, k, v)
        pack = 128 // q.shape[-1]
        got = pallas_attention.flash_attention(
            q, pack_heads(k, pack), pack_heads(v, pack), interpret=True,
            pack=pack)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("layout", [dict(h=8, kv=2), NARROW],
                             ids=["hd128", "hd64-packed"])
    def test_gqa_head_mapping(self, layout):
        # 8 query heads sharing 2 KV heads: head h must use kv head h//4
        # (of packed rows: its own half of row h // 8).
        q, k, v = make_qkv(b=1, s=128, seed=3, **layout)
        ref = prefill_attention(q, k, v)
        pack = 128 // q.shape[-1]
        got = pallas_attention.flash_attention(
            q, pack_heads(k, pack), pack_heads(v, pack), interpret=True,
            pack=pack)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_packed_rows_fall_back_unpacked(self):
        # off the TPU the dispatcher hands the XLA path the heads unpacked
        q, k, v = make_qkv(b=1, s=128, seed=4, **NARROW)
        got = pallas_attention.flash_attention(
            q, pack_heads(k, 2), pack_heads(v, 2), pack=2)
        np.testing.assert_allclose(np.asarray(prefill_attention(q, k, v)),
                                   np.asarray(got), rtol=1e-6)

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

    @pytest.mark.parametrize("start", [0, 200, 384])
    def test_packed_narrow_heads_at_offsets(self, start):
        """The dispatcher over a lane of packed rows (LFM2: two 64-wide kv
        heads a row): the kernel (interpret) and the XLA fallback."""
        from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention

        q, kc, vc = self._inputs(seed=start, **NARROW)
        ref = xla_chunk_attention(q, kc, vc, start)
        for interpret in (True, False):
            got = pallas_attention.chunk_attention(
                q, pack_heads(kc, 2), pack_heads(vc, 2), jnp.int32(start),
                interpret=interpret, pack=2)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                       rtol=2e-5, atol=2e-5)

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
