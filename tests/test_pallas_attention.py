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

    # (q heads, kv heads, head dim, pack): the group g of query heads that
    # share a K/V tile as the KERNEL sees it; the packed lane is LFM2's (two
    # 64-wide kv heads a row: 16 padded query heads over 2 rows).
    GROUPS = {"g1": (2, 2, 128, 1), "g4": (8, 2, 128, 1),
              "g7": (14, 2, 128, 1), "g8-packed": (16, 4, 64, 2)}

    @pytest.mark.parametrize("block_q", [128, 256])
    @pytest.mark.parametrize("start", [0, 384, 768],
                             ids=["first", "mid-lane", "last-chunk"])
    @pytest.mark.parametrize("window", [0, 320], ids=["full", "window"])
    @pytest.mark.parametrize("layout", list(GROUPS))
    def test_group_shares_its_tile(self, layout, window, start, block_q):
        """One grid step a (kv head, query tile, key tile): every query
        head of the group against the XLA form, with the window's far edge
        and the diagonal cutting through [block_q, 512] tiles."""
        from llm_instance_gateway_tpu.ops.attention import (
            own_values,
            pad_queries,
            xla_chunk_attention,
        )

        h, kv, hd, pack = self.GROUPS[layout]
        q, kc, vc = self._inputs(c=256, s_max=1024, h=h, kv=kv, hd=hd,
                                 seed=start + h)
        ref = xla_chunk_attention(q, kc, vc, start, window)
        got = own_values(pallas_attention.chunk_attention_pallas(
            pad_queries(q, kv, pack), pack_heads(kc, pack),
            pack_heads(vc, pack), jnp.int32(start), block_q=block_q,
            block_k=512, interpret=True, window=window,
            scale=1.0 / hd ** 0.5), kv, pack)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("start", [0, 3072, 4096],
                             ids=["first", "mid-ring", "ring-full"])
    def test_a_ring_with_the_chunk_behind_it(self, start):
        """A window layer's lane is its ring in position order with the
        chunk behind it, 5,120 positions and no power of two: the dispatcher
        takes the tiles ``chunk_blocks`` gives it, [256, 1024] over a group
        of seven heads, and the window's far edge cuts through them."""
        from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention

        window, c = 4096, 1024
        assert pallas_attention.chunk_blocks(c, window + c, 7, 128, 4) == (
            256, 1024)
        q, kc, vc = self._inputs(c=c, s_max=window + c, h=7, kv=1, seed=start)
        got = pallas_attention.chunk_attention(
            q, kc, vc, jnp.int32(start), interpret=True, window=window)
        np.testing.assert_allclose(
            np.asarray(xla_chunk_attention(q, kc, vc, start, window)),
            np.asarray(got), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [0, 320], ids=["full", "window"])
    def test_grouped_grid_equals_the_per_head_grid_bit_for_bit(self, window):
        """The grid this kernel had before its head axis ran over the kv
        heads (one step a QUERY head, ``hi // g`` its kv head: the index
        maps are kept here) gives the same float32 numbers: a head's
        recurrence sees the same tiles in the same order either way."""
        import functools

        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        h, kv, hd, c, s_max, block_q, block_k = 14, 2, 128, 256, 1024, 128, 512
        g = h // kv
        q, kc, vc = self._inputs(c=c, s_max=s_max, h=h, kv=kv, seed=11)
        start = jnp.int32(384)

        def q_index(bi, hi, qi, kb, off):
            return (bi, 0, qi, hi)

        def kv_index(bi, hi, qi, kb, off):
            q_first = off[0] + qi * block_q
            tile = jnp.minimum(kb, (q_first + block_q - 1) // block_k)
            if window:
                tile = jnp.maximum(
                    tile, jnp.maximum(q_first - window + 1, 0) // block_k)
            return (bi, 0, tile, hi // g)

        per_head = pl.pallas_call(
            functools.partial(pallas_attention._chunk_kernel,
                              scale=1.0 / hd ** 0.5, window=window),
            out_shape=jax.ShapeDtypeStruct((1, 1, c, h * hd), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(1, h, c // block_q, s_max // block_k),
                in_specs=[pl.BlockSpec((1, 1, block_q, hd), q_index),
                          pl.BlockSpec((1, 1, block_k, hd), kv_index),
                          pl.BlockSpec((1, 1, block_k, hd), kv_index)],
                out_specs=pl.BlockSpec((1, 1, block_q, hd), q_index),
                scratch_shapes=[pltpu.VMEM((1, block_q, 128), jnp.float32),
                                pltpu.VMEM((1, block_q, 128), jnp.float32),
                                pltpu.VMEM((1, block_q, hd), jnp.float32)]),
            interpret=True,
        )(start.reshape(1), q.reshape(1, 1, c, h * hd),
          kc.reshape(1, 1, s_max, kv * hd), vc.reshape(1, 1, s_max, kv * hd))
        grouped = pallas_attention.chunk_attention_pallas(
            q, kc, vc, start, block_q=block_q, block_k=block_k,
            interpret=True, window=window)
        np.testing.assert_array_equal(
            np.asarray(per_head.reshape(1, c, h, hd)), np.asarray(grouped))

    @pytest.mark.parametrize("g,hd,want", [
        (1, 128, 256), (7, 128, 256), (8, 128, 256), (1, 256, 256),
        (16, 128, 128)])
    def test_query_tile_follows_the_group(self, g, hd, want):
        """256 query rows a head where the group's tiles and softmax state
        fit a step's VMEM, else 128; from the shapes alone."""
        blocks = pallas_attention.chunk_blocks
        assert blocks(1024, 16384, g, hd) == (want, 1024)
        # the widest key tile that divides the lane: a ring of 4,096 with a
        # 512-token chunk behind it, a lane a tile and a bit long
        assert blocks(1024, 4096 + 512, g, hd)[1] == 512
        assert blocks(1024, 16384 + 128, g, hd)[1] == 128
        assert blocks(384, 2048, g, hd)[0] == 128
        assert pallas_attention.chunk_shape_reasons(1024, 16384, hd, g) == []

    def test_a_group_over_a_steps_vmem_takes_the_xla_form(self):
        # 71 heads over one kv head (Falcon-7B's): no tile holds the group.
        reasons = pallas_attention.chunk_shape_reasons(1024, 2048, 128, g=71)
        assert reasons and "71 heads" in reasons[0]
        q, kc, vc = self._inputs(c=128, s_max=256, h=71, kv=1, seed=5)
        from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention
        got = pallas_attention.chunk_attention(q, kc, vc, 128, interpret=True)
        np.testing.assert_allclose(
            np.asarray(xla_chunk_attention(q, kc, vc, 128)), np.asarray(got),
            rtol=1e-6)

    def test_grid_walks_the_kv_heads(self):
        # SmallThinker's full lane and its ring with the chunk behind it
        assert pallas_attention.chunk_grid(1, 1024, 16384, 4, 256, 1024) == (
            1, 4, 4, 16)
        assert pallas_attention.chunk_grid(1, 1024, 5120, 4, 256, 1024) == (
            1, 4, 4, 5)

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
