"""Cached-decode attention kernel parity (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.ops.attention import decode_attention as xla_decode
from llm_instance_gateway_tpu.ops.attention import (
    own_values,
    pack_heads,
    pad_queries,
)
from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda


def make_inputs(b=4, h=8, kv=2, hd=128, s=256, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    return q, k, v, lengths


# (query heads, kv heads, head width): qwen2.5-7b's K = 4 at G = 7, mixtral's
# K = 8, the small layout the file has always used, and LFM2's 64-wide heads
# (8 kv heads, a group of 4), two to a 128-lane cache row.
HEAD_LAYOUTS = [(8, 2, 128), (28, 4, 128), (32, 8, 128), (32, 8, 64)]


def pack_of(hd):
    return 128 // hd


def stack_at(x, layer, n_layers=3):
    """``x`` as layer ``layer`` of a stacked cache whose other layers are
    poison: reading the wrong layer is an O(1000) error."""
    return jnp.stack([x if l == layer else jnp.full_like(x, 1e3)
                      for l in range(n_layers)])


class TestDecodeKernel:
    @pytest.mark.parametrize("h,kv,hd", HEAD_LAYOUTS)
    @pytest.mark.parametrize("layer", [None, 0, 2])
    def test_matches_reference(self, h, kv, hd, layer):
        """One layer's [B, S, K, hd] cache, and layer ``layer`` of a stacked
        [L, B, S, K, hd] cache read where it lies, equal the XLA reference
        on that layer's slice.  64-wide heads go in as the model hands them
        over: two to a row, the queries padded into their head's columns,
        the softmax's scale the narrow head's."""
        q, k, v, lengths = make_inputs(h=h, kv=kv, hd=hd)
        ref = xla_decode(q, k, v, lengths)
        pack = pack_of(hd)
        k, v = pack_heads(k, pack), pack_heads(v, pack)
        if layer is not None:
            k, v = stack_at(k, layer), stack_at(v, layer)
        got = own_values(pda.decode_attention_pallas(
            pad_queries(q, kv, pack), k, v, lengths,
            layer=None if layer is None else jnp.int32(layer),
            interpret=True, scale=hd ** -0.5), kv, pack)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("h,kv,hd", HEAD_LAYOUTS)
    def test_dispatcher_reads_a_layer_of_the_stack(self, h, kv, hd):
        """``decode_attention(layer=)``: the kernel (interpret) and the XLA
        fallback (this backend) both read layer 1 of the stack, of packed
        rows too."""
        q, k, v, lengths = make_inputs(h=h, kv=kv, hd=hd, seed=11)
        ref = xla_decode(q, k, v, lengths)
        pack = pack_of(hd)
        ks = stack_at(pack_heads(k, pack), 1)
        vs = stack_at(pack_heads(v, pack), 1)
        assert pda.lane_tiles(ks)[1] > 0  # the kernel takes the packed rows
        for interpret in (True, False):
            got = jax.jit(lambda q, ks, vs, lay: pda.decode_attention(
                q, ks, vs, lengths, layer=lay, interpret=interpret,
                pack=pack))(q, ks, vs, jnp.int32(1))
            np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("layer", [None, 1])
    def test_length_masking_exact(self, layer):
        # Garbage beyond each row's length must not perturb the output.
        q, k, v, lengths = make_inputs(seed=3)
        k_poisoned = k.at[:, -32:].set(1e3)
        v_poisoned = v.at[:, -32:].set(-1e3)
        short = jnp.minimum(lengths, k.shape[1] - 32)
        ref = xla_decode(q, k, v, short)
        if layer is not None:
            k_poisoned = stack_at(k_poisoned, layer)
            v_poisoned = stack_at(v_poisoned, layer)
        got = pda.decode_attention_pallas(q, k_poisoned, v_poisoned, short,
                                          layer=layer, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("h,kv", [(28, 4), (32, 8)])
    def test_short_rows_in_a_long_cache(self, h, kv):
        """Rows of 1..40 tokens in a 1024-position cache, four S-blocks of
        256: the dead blocks (no step of the schedule) hold poison."""
        q, k, v, _ = make_inputs(b=3, h=h, kv=kv, s=1024, seed=9)
        lengths = jnp.asarray([1, 17, 40], jnp.int32)
        ref = xla_decode(q, k, v, lengths)
        k = k.at[:, 256:].set(1e3)
        v = v.at[:, 256:].set(-1e3)
        got = pda.decode_attention_pallas(
            q, stack_at(k, 1, 2), stack_at(v, 1, 2), lengths,
            layer=jnp.int32(1), block_s=256, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_mqa_single_kv_head(self):
        q, k, v, lengths = make_inputs(h=8, kv=1, seed=5)
        ref = xla_decode(q, k, v, lengths)
        got = pda.decode_attention_pallas(q, k, v, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_multi_block_recurrence(self):
        # Force several tiles a row so the cross-block online-softmax carry
        # (scratch m/l/acc, corr rescaling) actually runs; the default
        # _pick_block(256) would cover s=256 in a single step.
        q, k, v, lengths = make_inputs(s=256, seed=7)
        ref = xla_decode(q, k, v, lengths)
        got = pda.decode_attention_pallas(q, k, v, lengths, block_s=64,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)
        # Short rows end their sweep before the lane does.
        short = jnp.minimum(lengths, 70)
        ref_s = xla_decode(q, k, v, short)
        got_s = pda.decode_attention_pallas(q, k, v, short, block_s=64,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(ref_s), np.asarray(got_s),
                                   rtol=2e-5, atol=2e-5)

    def test_unsupported_shapes_fall_back(self):
        q, k, v, lengths = make_inputs(hd=16, s=64)
        assert not pda.supports(64, 16)
        ref = xla_decode(q, k, v, lengths)
        got = pda.decode_attention(q, k, v, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-6)


    def test_wide_kv_rows_take_a_shorter_block(self):
        """On v5e [512, K*hd=4096] bf16 K+V tiles, double-buffered, exhaust
        VMEM (llama2-7b / gemma-7b, chip run of PR 21); int8 rows of the
        same width, and every GQA layout, keep 512."""
        assert pda._pick_block(2048, 4096 * 2) == 256   # MHA 32x128 bf16
        assert pda._pick_block(2048, 4096 * 1) == 512   # same, int8
        assert pda._pick_block(2048, 4 * 128 * 2) == 512  # qwen2.5-7b
        assert pda._pick_block(2048) == 512              # divisibility only
        assert pda._pick_block(2048, 1 << 20) == 0
        assert pda.shape_reasons(2048, 128, 1 << 20) == [
            "kv row of 1048576 B: no S-block fits VMEM"]

    def test_dispatch_says_what_it_chose_and_why(self, caplog):
        import logging

        q, k, v, lengths = make_inputs(hd=16, s=64)
        with caplog.at_level(logging.INFO,
                             logger="llm_instance_gateway_tpu.ops.attention"):
            pda.decode_attention(q, k, v, lengths)
            pda.decode_attention(q, k, v, lengths, interpret=True)
            q2, k2, v2, l2 = make_inputs(hd=128, s=128)
            pda.decode_attention(q2, k2, v2, l2)
            pda.decode_attention(q2, k2, v2, l2, interpret=True)
        lines = [r.getMessage() for r in caplog.records]
        assert "impl=xla" in lines[0] and "hd=16 % 128 != 0" in lines[0]
        assert "impl=xla" in lines[1]  # a shape gate beats interpret mode
        assert "impl=xla" in lines[2] and "reason=backend=cpu" in lines[2]
        assert "impl=pallas-interpret" in lines[3]


# Which rows decode in a step of six slots: interleaved with dead ones, a
# dead row first, a dead row last, and no live row at all.
LIVE_PATTERNS = {
    "interleaved": [True, False, True, False, False, True],
    "leading": [False, False, True, True, False, True],
    "trailing": [True, True, False, True, False, False],
    "none": [False] * 6,
}
# Every slot holds a position, as a freed slot keeps its last request's:
# one tile, a tile's edge, a straddle, three tiles, the whole lane.
STALE_LENGTHS = [5, 64, 130, 192, 256, 33]


def masked_lengths(pattern):
    live = np.asarray(LIVE_PATTERNS[pattern])
    return live, jnp.asarray(np.where(live, STALE_LENGTHS, 0), jnp.int32)


class TestRowsThatDoNotDecode:
    """A row handed over at length 0 (``decode_step``: ``active`` off) costs
    the kernel no tile and no matmul, and moves no live row's result."""

    @pytest.mark.parametrize("pattern", LIVE_PATTERNS)
    @pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("h,kv", [(28, 4), (16, 16)],
                             ids=["K4-G7", "K16-G1"])
    def test_live_rows_are_bit_for_bit_the_all_live_kernels(
            self, h, kv, quant, pattern):
        from llm_instance_gateway_tpu.models.transformer import (
            _kv_dequantize, _kv_quantize)

        q, k, v, _ = make_inputs(b=6, h=h, kv=kv, s=256, seed=21)
        live, lengths = masked_lengths(pattern)
        stale = jnp.asarray(STALE_LENGTHS, jnp.int32)
        if quant:
            (k8, ks), (v8, vs) = _kv_quantize(k), _kv_quantize(v)
            run = lambda lens: pda.decode_attention_quant_pallas(
                q, stack_at(k8, 1), stack_at(v8, 1), stack_at(ks, 1),
                stack_at(vs, 1), lens, layer=jnp.int32(1), block_s=64,
                interpret=True)
            k, v = (_kv_dequantize(k8, ks, jnp.float32),
                    _kv_dequantize(v8, vs, jnp.float32))
        else:
            run = lambda lens: pda.decode_attention_pallas(
                q, stack_at(k, 1), stack_at(v, 1), lens, layer=jnp.int32(1),
                block_s=64, interpret=True)
        got, all_live = np.asarray(run(lengths)), np.asarray(run(stale))
        np.testing.assert_array_equal(got[live], all_live[live])
        ref = np.asarray(xla_decode(q, k, v, stale))
        np.testing.assert_allclose(ref[live], got[live], rtol=2e-5, atol=2e-5)
        assert np.all(np.isfinite(got))
        np.testing.assert_array_equal(got[~live], 0.0)
        # the XLA path's dead rows are another garbage, and finite too
        assert np.all(np.isfinite(np.asarray(xla_decode(q, k, v, lengths))))

    @pytest.mark.parametrize("pattern", LIVE_PATTERNS)
    def test_paged_and_latent_kernels_share_the_rule(self, pattern):
        """The paged kernel (the table routes the schedule's row and tile)
        and the latent kernel: live rows as with every slot live, dead rows
        zeros."""
        from llm_instance_gateway_tpu.ops.attention import (
            gather_pool_rows, latent_decode_attention)

        live, lengths = masked_lengths(pattern)
        stale = jnp.asarray(STALE_LENGTHS, jnp.int32)
        q, k_pool, v_pool, tables, _ = TestPagedDecodeKernel().make_paged(
            b=6, seed=5)
        run = lambda lens: np.asarray(pda.paged_decode_attention_pallas(
            q, k_pool, v_pool, tables, lens, interpret=True))
        got = run(lengths)
        np.testing.assert_array_equal(got[live], run(stale)[live])
        np.testing.assert_array_equal(got[~live], 0.0)
        ref = np.asarray(xla_decode(q, gather_pool_rows(k_pool, tables),
                                    gather_pool_rows(v_pool, tables), stale))
        np.testing.assert_allclose(ref[live], got[live], rtol=2e-5, atol=2e-5)

        kq, kr = jax.random.split(jax.random.PRNGKey(8))
        ql = jax.random.normal(kq, (6, 4, 256), jnp.float32)
        rows = jax.random.normal(kr, (6, 256, 256), jnp.float32)
        run = lambda lens: np.asarray(pda.mla_decode_attention_pallas(
            ql, stack_at(rows, 1), lens, 128, 0.1, layer=jnp.int32(1),
            block_s=64, interpret=True))
        got = run(lengths)
        np.testing.assert_array_equal(got[live], run(stale)[live])
        np.testing.assert_array_equal(got[~live], 0.0)
        ref = np.asarray(latent_decode_attention(ql, rows, stale, 128, 0.1))
        np.testing.assert_allclose(ref[live], got[live], rtol=2e-5, atol=2e-5)


# (tile length, tiles a lane): the lane kernel's at Qwen's lanes and at
# SmallThinker's full lanes, a page of a paged pool, the latent kernel's.
SCHEDULE_SHAPES = {"lane-512x4": (512, 4), "lane-512x32": (512, 32),
                   "paged-64x4": (64, 4), "paged-16x128": (16, 128),
                   "latent-1024x4": (1024, 4)}
# Six slots' lengths as shares of a lane: mixed with dead rows first, between
# and last; rows that end on a tile's edge; one live row; none; all full.
SCHEDULE_ROWS = {
    "mixed": [0, 0.3, 0, 0.01, 1.0, 0],
    "edges": [0.25, 0.5, 0, 0.75, 1.0, 0.25],
    "one": [0, 0, 0, 0.6, 0, 0],
    "none": [0] * 6,
    "full": [1.0] * 6,
}


class TestDecodeSchedule:
    """``decode_schedule`` as a pure function of the lengths."""

    @pytest.mark.parametrize("rows", SCHEDULE_ROWS)
    @pytest.mark.parametrize("shape", SCHEDULE_SHAPES)
    def test_every_live_tile_once_in_slot_then_tile_order(self, shape, rows):
        block_s, n_tiles = SCHEDULE_SHAPES[shape]
        s_max = block_s * n_tiles
        lens = [int(np.ceil(share * s_max)) for share in SCHEDULE_ROWS[rows]]
        row, tile, n_steps = pda.decode_schedule(
            jnp.asarray(lens, jnp.int32), block_s, n_tiles)
        assert row.shape == tile.shape == (6 * n_tiles,)
        assert row.dtype == tile.dtype == n_steps.dtype == jnp.int32
        n = int(n_steps[0])
        want = [(b, t) for b, held in enumerate(lens)
                for t in range(-(-held // block_s))]
        assert n == len(want) == sum(-(-held // block_s) for held in lens)
        assert n == pda.schedule_steps(lens, block_s, n_tiles)
        steps = list(zip(np.asarray(row).tolist(), np.asarray(tile).tolist()))
        assert steps[:n] == want  # slot order, tiles ascending, no dead row
        # past its end the schedule repeats its last step: a walk under a
        # static bound copies nothing more
        assert set(steps[n:]) <= {want[-1] if want else (5, 0)}
        if rows == "full":
            assert n == 6 * n_tiles  # the rectangle
        if rows == "none":
            assert n == 0

    def test_a_length_past_the_lane_is_held_to_the_lane(self):
        row, tile, n_steps = pda.decode_schedule(
            jnp.asarray([300, 0, 1000], jnp.int32), 64, 4)
        assert int(n_steps[0]) == 8 == pda.schedule_steps([300, 0, 1000], 64, 4)
        assert int(tile.max()) == 3

    def test_a_schedule_built_for_another_tile_is_refused(self):
        q, k, v, lengths = make_inputs(s=256)
        with pytest.raises(ValueError, match="another tile"):
            pda.decode_attention_pallas(
                q, k, v, lengths, block_s=64, interpret=True,
                schedule=pda.decode_schedule(lengths, 128, 2))

    @pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
    def test_the_kernels_tiles_are_the_schedules(self, quant):
        """``lane_tiles`` / ``mla_tiles`` name the tile the kernels pick by
        themselves (a schedule built over them is the kernel's own), and
        ``decode_step`` builds one where there is a tile and none where
        there is not."""
        from llm_instance_gateway_tpu.models import transformer
        from llm_instance_gateway_tpu.models.configs import TINY_TEST

        lens = jnp.asarray([5, 0, 700], jnp.int32)
        k = jnp.zeros((2, 3, 2048, 4, 128), jnp.int8 if quant else jnp.bfloat16)
        assert pda.lane_tiles(k) == (512, 4)
        held = transformer._held(TINY_TEST, None, lens, k)
        assert held[0] is lens
        for got, want in zip(held[1], pda.decode_schedule(lens, 512, 4)):
            np.testing.assert_array_equal(got, want)
        wide = jnp.zeros((3, 2048, 32, 128), jnp.bfloat16)  # MHA: 256 a tile
        assert pda.lane_tiles(wide) == (256, 8)
        assert pda.mla_tiles(jnp.zeros((2, 3, 4096, 640), jnp.bfloat16)) == (
            1024, 4)
        odd = jnp.zeros((3, 200, 4, 128), jnp.bfloat16)
        assert pda.lane_tiles(odd) == pda.mla_tiles(odd[..., 0, :]) == (0, 0)
        assert transformer._held(TINY_TEST, None, lens, odd) == (lens, None)
        # an attention override (--mesh) builds its own, shard by shard
        assert transformer._held(TINY_TEST, lambda *a: None, lens, k) == (
            lens, None)


# Six slots of a 256-position lane in tiles of 64: dead rows first, dead rows
# between live ones, a row that ends on a tile's edge, one live row, none.
MIXED_BATCHES = {
    "leading-dead": [0, 0, 130, 5, 256, 33],
    "dead-between": [70, 0, 0, 200, 0, 1],
    "tile-edges": [64, 128, 0, 192, 256, 0],
    "one-live": [0, 0, 0, 0, 97, 0],
    "none": [0] * 6,
}


class TestWalkingTheSchedule:
    """The three kernels under the interpreter (the schedule under its
    static bound) against the XLA references on mixed batches: live rows
    right, dead rows exact zeros."""

    @staticmethod
    def check(got, ref, lengths):
        live = np.asarray(lengths) > 0
        got = np.asarray(got)
        np.testing.assert_allclose(np.asarray(ref)[live], got[live],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(got[~live], 0.0)

    @pytest.mark.parametrize("batch", MIXED_BATCHES)
    @pytest.mark.parametrize("variant", ["bf16", "int8", "ring", "prebuilt"])
    def test_lane_kernel(self, variant, batch):
        from llm_instance_gateway_tpu.models.transformer import (
            _kv_dequantize, _kv_quantize)

        q, k, v, _ = make_inputs(b=6, h=28, kv=4, s=256, seed=31)
        lengths = jnp.asarray(MIXED_BATCHES[batch], jnp.int32)
        if variant == "int8":
            (k8, ks), (v8, vs) = _kv_quantize(k), _kv_quantize(v)
            got = pda.decode_attention_quant_pallas(
                q, stack_at(k8, 1), stack_at(v8, 1), stack_at(ks, 1),
                stack_at(vs, 1), lengths, layer=jnp.int32(1), block_s=64,
                interpret=True)
            k, v = (_kv_dequantize(k8, ks, jnp.float32),
                    _kv_dequantize(v8, vs, jnp.float32))
        elif variant == "ring":
            # the dispatcher's own tile (one of 256) and the ring's name
            got = jax.jit(lambda q, k, v, lay: pda.decode_attention(
                q, k, v, lengths, layer=lay, interpret=True, ring=True))(
                    q, stack_at(k, 1), stack_at(v, 1), jnp.int32(1))
        else:
            schedule = (pda.decode_schedule(lengths, 64, 4)
                        if variant == "prebuilt" else None)
            got = pda.decode_attention_pallas(
                q, stack_at(k, 1), stack_at(v, 1), lengths,
                layer=jnp.int32(1), block_s=64, interpret=True,
                schedule=schedule)
        self.check(got, xla_decode(q, k, v, lengths), lengths)

    @pytest.mark.parametrize("batch", MIXED_BATCHES)
    @pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
    def test_paged_kernel(self, quant, batch):
        from llm_instance_gateway_tpu.models.transformer import (
            _kv_dequantize, _kv_quantize)
        from llm_instance_gateway_tpu.ops.attention import gather_pool_rows

        q, k_pool, v_pool, tables, _ = TestPagedDecodeKernel().make_paged(
            b=6, h=28, kv=4, seed=13)
        lengths = jnp.asarray(MIXED_BATCHES[batch], jnp.int32)
        scales = ()
        if quant:
            (k_pool, ks), (v_pool, vs) = _kv_quantize(k_pool), _kv_quantize(v_pool)
            scales = (ks, vs)
        got = pda.paged_decode_attention_pallas(
            q, k_pool, v_pool, tables, lengths, *scales, interpret=True)
        if quant:
            k_pool = _kv_dequantize(k_pool, ks, jnp.float32)
            v_pool = _kv_dequantize(v_pool, vs, jnp.float32)
        self.check(got, xla_decode(q, gather_pool_rows(k_pool, tables),
                                   gather_pool_rows(v_pool, tables), lengths),
                   lengths)

    @pytest.mark.parametrize("batch", MIXED_BATCHES)
    @pytest.mark.parametrize("prebuilt", [False, True], ids=["own", "prebuilt"])
    def test_latent_kernel(self, prebuilt, batch):
        from llm_instance_gateway_tpu.ops.attention import (
            latent_decode_attention)

        kq, kr = jax.random.split(jax.random.PRNGKey(17))
        q = jax.random.normal(kq, (6, 20, 256), jnp.float32)
        rows = jax.random.normal(kr, (6, 256, 256), jnp.float32)
        lengths = jnp.asarray(MIXED_BATCHES[batch], jnp.int32)
        schedule = pda.decode_schedule(lengths, 64, 4) if prebuilt else None
        got = pda.mla_decode_attention_pallas(
            q, stack_at(rows, 1), lengths, 128, 0.1, layer=jnp.int32(1),
            block_s=64, interpret=True, schedule=schedule)
        self.check(got, latent_decode_attention(q, rows, lengths, 128, 0.1),
                   lengths)


class TestPagedDecodeKernel:
    """Direct paged kernel: the block table rides the scalar prefetch and
    tiles DMA straight from the pool — parity against gather-then-attend
    with a SHUFFLED physical layout (logical order != physical order)."""

    def make_paged(self, b=4, h=8, kv=2, hd=128, block=64, m=4, seed=0):
        s_max = block * m
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
        n_blocks = b * m  # excludes trash block 0
        k_pool = jax.random.normal(ks[1], (n_blocks + 1, block, kv, hd),
                                   jnp.float32)
        v_pool = jax.random.normal(ks[2], (n_blocks + 1, block, kv, hd),
                                   jnp.float32)
        # Shuffled physical assignment: row i's logical blocks land in
        # arbitrary pool slots — the indirection under test.
        rng = np.random.RandomState(seed + 7)
        perm = rng.permutation(n_blocks) + 1  # physical blocks 1..n
        tables = jnp.asarray(perm.reshape(b, m), jnp.int32)
        lengths = jax.random.randint(ks[3], (b,), 1, s_max + 1)
        return q, k_pool, v_pool, tables, lengths

    def gathered(self, pool, tables):
        from llm_instance_gateway_tpu.ops.attention import gather_pool_rows

        return gather_pool_rows(pool, tables)

    def test_matches_gathered_reference(self):
        q, k_pool, v_pool, tables, lengths = self.make_paged()
        ref = xla_decode(q, self.gathered(k_pool, tables),
                         self.gathered(v_pool, tables), lengths)
        got = pda.paged_decode_attention_pallas(
            q, k_pool, v_pool, tables, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_int8_pool_matches_dequant_reference(self):
        from llm_instance_gateway_tpu.models.transformer import (
            _kv_dequantize, _kv_quantize)

        q, k_pool, v_pool, tables, lengths = self.make_paged(seed=2)
        kq, ks_ = _kv_quantize(k_pool)
        vq, vs_ = _kv_quantize(v_pool)
        ref = xla_decode(
            q,
            self.gathered(_kv_dequantize(kq, ks_, jnp.float32), tables),
            self.gathered(_kv_dequantize(vq, vs_, jnp.float32), tables),
            lengths)
        got = pda.paged_decode_attention_pallas(
            q, kq, vq, tables, lengths, ks_, vs_, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_trash_rows_and_dead_blocks(self):
        # length-0 rows (table all TRASH) emit zeros; rows shorter than one
        # block never read their dead blocks' garbage.
        q, k_pool, v_pool, tables, lengths = self.make_paged(seed=3)
        k_pool = k_pool.at[int(tables[1, 2])].set(1e3)  # dead for len<=2*64
        v_pool = v_pool.at[int(tables[1, 2])].set(-1e3)
        lengths = lengths.at[0].set(0).at[1].set(5)
        tables = tables.at[0].set(0)  # trash block everywhere
        ref = xla_decode(q, self.gathered(k_pool, tables),
                         self.gathered(v_pool, tables), lengths)
        got = pda.paged_decode_attention_pallas(
            q, k_pool, v_pool, tables, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(got[0]), 0.0)
        np.testing.assert_allclose(np.asarray(ref[1:]), np.asarray(got[1:]),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_dispatch_gathers_on_unsupported(self):
        # block=8 is below the int8 sublane floor (32): the entry must
        # fall back to gather + lane dispatchers, not crash.
        from llm_instance_gateway_tpu.models.transformer import _kv_quantize

        q, k_pool, v_pool, tables, lengths = self.make_paged(block=8, m=8)
        kq, ks_ = _kv_quantize(k_pool)
        vq, vs_ = _kv_quantize(v_pool)
        assert not pda.supports_paged(8, 128, jnp.int8)
        assert not pda.supports_paged(8, 128, jnp.bfloat16)  # bf16 floor 16
        assert pda.supports_paged(16, 128, jnp.bfloat16)
        assert pda.supports_paged(8, 128, jnp.float32)
        got = pda.paged_decode_attention(
            q, kq, vq, tables, lengths, ks_, vs_, interpret=False)
        assert got.shape == q.shape
