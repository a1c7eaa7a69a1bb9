"""Pallas TPU kernel for cached decode attention.

Decode attention reads the whole static KV cache every step — the HBM-bound
inner loop of serving.  The XLA reference (``ops.attention.decode_attention``)
materializes [B, K, G, S] logits between two einsums; this kernel streams the
cache in blocks with the online-softmax recurrence, keeping per-program state
in VMEM: one grid cell per (batch row, S-block) computes every head's
contribution for the row's single query token.  The cache is the STACKED
[L, B, S, K, hd] array of the model's layer loop, read in place: the layer
index rides the scalar prefetch, and the tiles are taken from the cache's
rows view (``_rows``), which costs no copy.

Length masking is exact (positions >= length contribute nothing), matching
the engine's garbage-tail cache contract.  A row of length 0 (a slot that
does not decode in this step: ``transformer.decode_step`` zeroes the length
of every row whose ``active`` bit is off) costs no tile and no matmul: its
grid steps point at the tile the step before them holds (``held_tile``), so
nothing is copied, nothing is computed, and the row emits zeros.  What it
still costs is its grid steps themselves.  ``decode_attention`` is the
dispatching entry: the kernel on a TPU backend for every shape ``supports``
accepts, the XLA reference otherwise — and it SAYS which, with the reason,
each time a program is traced (``ops.attention.log_choice``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_instance_gateway_tpu.ops.attention import (
    decode_attention as xla_decode,
    gather_pool_rows,
    kernel_reason,
    latent_decode_attention,
    log_choice,
)

NEG_INF = -1e30


def live_source(lengths: jax.Array) -> jax.Array:
    """[B] int32 for ``held_tile``: each row's nearest live row (length > 0)
    at or before it; for the dead rows that lead, the first live row; 0
    with no live row.  A live row is its own source.  Built from the
    lengths alone, so in a layer loop it is loop-invariant."""
    idx = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    live = lengths > 0
    # A running maximum over the live rows' indices; row 0, when dead,
    # stands in with the first live row's, which then leads the maximum up
    # to that row.  (The cumulative maximum comes last so that XLA lifts
    # all of this out of a layer loop: compiled for the v5e, a trailing
    # select stayed inside it.)
    first = jnp.argmax(live).astype(jnp.int32)
    return jax.lax.cummax(
        jnp.where(live, idx, jnp.where(idx == 0, first, -1)))


def held_tile(bi, sb, lens, src, block_s: int):
    """The index rule of every decode kernel here: (row, S-tile) of the
    cache that grid step (row ``bi``, S-block ``sb``) holds.  A live row
    sweeps its own tiles and clamps the blocks past its length to its last
    live tile; a dead row (length 0) holds its source's last live tile, or,
    leading, the first live row's first tile.  Either way a step that has
    nothing to read names the tile of the step before it, and Pallas
    copies a block only when its index changes: short rows cost bandwidth
    by their length, not by S_max, and dead rows none."""
    row = src[bi]
    last = jnp.maximum(lens[row] - 1, 0) // block_s
    tile = jnp.where(row == bi, jnp.minimum(sb, last),
                     jnp.where(row < bi, last, 0))
    return row, tile


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *refs,
                   block_s: int, n_kv: int, scale: float, quant: bool):
    # q_ref: [1, H, hd]; k_ref/v_ref: [1, block_s*K, hd] — one S-tile of the
    # cache in its ROWS view: row s*K + kh is position s of kv head kh, which
    # is how the [.., S, K, hd] cache lies in HBM (``_rows``), so the tile
    # arrives by one straight DMA and is read as whole vregs.  All heads go
    # through the MXU together (what a per-head grid would cost on the
    # chip: not measured): the [H, block_s*K] logits hold every query head
    # against every row, and the mask keeps the columns of its own kv head.
    # len_ref: [B] (SMEM, scalar-prefetched).  The S-block axis is the
    # innermost grid dim with "arbitrary" semantics: online-softmax state
    # rides f32 VMEM scratch across the sweep, like the prefill flash kernel.
    # ``quant``: K/V tiles arrive int8 with per-row f32 scales as lane
    # vectors (ks_ref/vs_ref: [1, 1, block_s*K]); int8 is exact in the
    # compute dtype, so the tiles feed the MXU as they are and the scales
    # multiply the logits' and the probabilities' columns — HBM streams
    # half the bytes of the bf16 variant, decode's actual bound.
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    n_heads = q_ref.shape[1]
    g = n_heads // n_kv
    rows = block_s * n_kv
    bi = pl.program_id(0)
    sb = pl.program_id(1)
    n_sb = pl.num_programs(1)
    length = len_ref[bi]
    start = sb * block_s

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Blocks entirely past `length`, and every block of a row of length 0,
    # do nothing (their DMA is elided too: ``held_tile`` names the tile the
    # step before already holds); the straddling block masks.
    @pl.when(start < length)
    def _compute():
        q = q_ref[0]  # [H, hd]
        k = k_ref[0]  # [rows, hd]
        v = v_ref[0]
        if quant:
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        # else: K/V stay in their storage dtype — the MXU consumes bf16
        # directly with f32 accumulation; an explicit astype of every
        # tile would be VPU work for nothing.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, rows] f32
        if quant:
            s = s * ks_ref[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (n_heads, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (n_heads, rows), 0)
        own = (row % n_kv == head // g) & (start + row // n_kv < length)
        s = jnp.where(own, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # Every head owns the block's first position (start < length), so
        # m_new is finite and the columns masked out come to exactly 0.
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * corr + p.sum(axis=-1, keepdims=True), l_scr.shape)
        if quant:
            p = p * vs_ref[0]
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(sb == n_sb - 1)
    def _finalize():
        # Rows with length == 0 (slots that do not decode in this step) never
        # accumulate (l stays 0) and emit zeros, not an unwritten buffer;
        # the engine masks such rows either way.
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def _indexed_kernel(len_ref, src_ref, index_ref, *rest, **kw):
    # The second and third scalar-prefetch operands (``live_source``; the
    # lane kernel's layer index, the paged kernel's block table) are
    # consumed by the index maps, not the body: only the DMA source moves.
    del src_ref, index_ref
    _decode_kernel(len_ref, *rest, **kw)


def _layer_view(x: jax.Array, layer) -> jax.Array:
    """One layer of a stacked array, for the XLA references (a slice XLA
    may materialise: the fallbacks must be right, not fast)."""
    if layer is None:
        return x
    return jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)


def _rows(x: jax.Array) -> jax.Array:
    """[.., S, K, hd] -> [.., S*K, hd]: the cache as rows of one head's
    vector.  XLA tiles the two minor dims, (K, hd) before and (rows, hd)
    after, and with hd a multiple of 128 both tilings put row s*K + kh at
    the same address: the reshape is a bitcast, no byte moves (compiled
    for the v5e: bf16 K = 4 ``T(4,128)(2,1)`` -> ``T(8,128)(2,1)``).  The
    flat [.., S, K*hd] view this kernel used before is a relayout there:
    a copy of the layer's cache per call (device trace, PR 24)."""
    return x.reshape(*x.shape[:-3], x.shape[-3] * x.shape[-2], x.shape[-1])


def _scale_rows(x: jax.Array) -> jax.Array:
    """[.., S, K] scales -> [.., 1, S*K]: a lane vector in the order of
    ``_rows``.  A relayout (XLA stores such an array S-minor on the v5e),
    of an array 1/hd the size of the cache."""
    return x.reshape(*x.shape[:-2], 1, x.shape[-2] * x.shape[-1])


def _scratch(n_heads: int, hd: int) -> list:
    return [
        pltpu.VMEM((n_heads, 128), jnp.float32),  # m (lane-padded)
        pltpu.VMEM((n_heads, 128), jnp.float32),  # l
        pltpu.VMEM((n_heads, hd), jnp.float32),   # o accumulator
    ]


# The pipeline double-buffers the K and the V tile: 2 operands x 2 buffers
# x block_s x (K*hd*itemsize) has to sit in scoped VMEM (16 MiB on v5e)
# beside the scratch, the [H, block_s*K] f32 logits and the int8 path's
# converted tiles.  On v5e [512, 4096] int8 tiles (8 MiB) lower and
# [512, 4096] bf16 tiles (16 MiB) exhaust VMEM (tools/onchip_pallas_check.py,
# chip run of PR 21: llama2-7b and gemma-7b, the MHA layouts with
# K*hd = 4096) — so wide rows take a shorter S-block.
_KV_TILES_VMEM_BUDGET = 8 << 20


def _pick_block(s_max: int, row_bytes: int = 0) -> int:
    """Largest S-block that divides ``s_max`` and whose double-buffered K+V
    tiles fit the VMEM budget; 0 when none does."""
    for bs in (512, 256, 128):
        if s_max % bs == 0 and 4 * bs * row_bytes <= _KV_TILES_VMEM_BUDGET:
            return bs
    return 0


def _pallas_decode_call(q, k_all, v_all, scales, lengths, layer,
                        block_s: int | None, interpret: bool,
                        name: str = "decode_attention") -> jax.Array:
    """Shared pallas_call builder for the bf16 and int8 variants, over the
    STACKED cache [L, B, S, K, hd] and a layer index: the index rides the
    scalar prefetch into the tiles' index map, so the kernel reads the
    layer where it lies and no slice of the cache is materialised.  With
    ``layer`` None the arrays are one layer's, a stack of one (a leading 1
    is free).  ``scales`` is None (bf16) or (k_scale, v_scale) f32, stacked
    like the cache: only that layer's reach the kernel, as lane vectors — a
    relayout kept to one layer of an array 1/hd the size of the cache.
    ``name`` is the call's name in a device trace: the same body over a
    window layer's ring lanes runs as ``decode_attention_window``."""
    if scales is not None:
        scales = [_layer_view(s, layer) for s in scales]
    if layer is None:
        k_all, v_all, layer = k_all[None], v_all[None], 0
    b, n_heads, hd = q.shape
    s_max, n_kv = k_all.shape[2], k_all.shape[3]
    if block_s is None:
        block_s = _pick_block(s_max,
                              n_kv * hd * jnp.dtype(k_all.dtype).itemsize)
    rows = block_s * n_kv
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(bi, sb, lens, src, lay):
        return (bi, 0, 0)

    def kv_index(bi, sb, lens, src, lay):
        row, tile = held_tile(bi, sb, lens, src, block_s)
        return (lay[0], row, tile, 0)

    def scale_index(bi, sb, lens, src, lay):
        _, row, tile, _ = kv_index(bi, sb, lens, src, lay)
        return (row, 0, tile)

    quant = scales is not None
    in_specs = [
        pl.BlockSpec((1, n_heads, hd), q_index),
        pl.BlockSpec((None, 1, rows, hd), kv_index),
        pl.BlockSpec((None, 1, rows, hd), kv_index),
    ]
    operands = [lengths, live_source(lengths), layer, q,
                _rows(k_all), _rows(v_all)]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, rows), scale_index)] * 2
        operands += [_scale_rows(s) for s in scales]
    kernel = functools.partial(_indexed_kernel, block_s=block_s, n_kv=n_kv,
                               scale=float(1.0 / (hd ** 0.5)), quant=quant)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # lengths: masking + DMA clamping; their live_source: what a
            # dead row's steps hold; layer: which of the stack
            num_scalar_prefetch=3,
            grid=(b, s_max // block_s),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_heads, hd), q_index),
            scratch_shapes=_scratch(n_heads, hd),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_int8" if quant else name,
    )(*operands)


def decode_attention_pallas(
    q: jax.Array,        # [B, n_heads, hd]
    k_cache: jax.Array,  # [B, S, n_kv, hd], or [L, B, S, n_kv, hd] + layer
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] int32
    layer=None,          # scalar int32: which layer of a stacked cache
    block_s: int | None = None,
    interpret: bool = False,
    name: str = "decode_attention",
) -> jax.Array:
    return _pallas_decode_call(q, k_cache, v_cache, None, lengths, layer,
                               block_s, interpret, name)


def decode_attention_quant_pallas(
    q: jax.Array,        # [B, n_heads, hd]
    k_cache: jax.Array,  # [B, S, n_kv, hd] int8, or stacked + layer
    v_cache: jax.Array,
    k_scale: jax.Array,  # [B, S, n_kv] f32, or stacked
    v_scale: jax.Array,
    lengths: jax.Array,  # [B] int32
    layer=None,
    block_s: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _pallas_decode_call(q, k_cache, v_cache, (k_scale, v_scale),
                               lengths, layer, block_s, interpret)


def shape_reasons(s_max: int, hd: int, row_bytes: int = 0) -> list[str]:
    """Shape gates of the lane kernel that this call misses (empty = ok).
    ``row_bytes``: one cache position's K (or V) bytes over all kv heads."""
    reasons = []
    if hd % 128:
        reasons.append(f"hd={hd} % 128 != 0")
    if s_max % 128:
        reasons.append(f"s_max={s_max} % 128 != 0")
    elif _pick_block(s_max, row_bytes) == 0:
        reasons.append(f"kv row of {row_bytes} B: no S-block fits VMEM")
    return reasons


def supports(s_max: int, hd: int, row_bytes: int = 0) -> bool:
    return not shape_reasons(s_max, hd, row_bytes)


def _row_bytes(k_cache: jax.Array) -> int:
    return k_cache.shape[-2] * k_cache.shape[-1] * k_cache.dtype.itemsize


# ---------------------------------------------------------------------------
# Direct paged variant: block-table indirection in the index map
# ---------------------------------------------------------------------------


def paged_shape_reasons(block: int, hd: int, dtype) -> list[str]:
    """The pool tile is one physical block in its rows view: [1, block*K,
    hd].  The gate stays on ``block``, the tile's sublane dim at K = 1: it
    must divide the dtype's packed tiling, (8, 128) f32, (16, 128) bf16,
    (32, 128) int8."""
    sublane = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 32)
    reasons = []
    if hd % 128:
        reasons.append(f"hd={hd} % 128 != 0")
    if block % sublane:
        reasons.append(
            f"paged block={block} % {sublane} != 0 (sublane tiling of "
            f"{jnp.dtype(dtype).name})")
    return reasons


def supports_paged(block: int, hd: int, dtype) -> bool:
    return not paged_shape_reasons(block, hd, dtype)


def paged_decode_attention_pallas(
    q: jax.Array,        # [B, n_heads, hd]
    k_pool: jax.Array,   # [n_blocks+1, P, n_kv, hd] (bf16 or int8)
    v_pool: jax.Array,
    tables: jax.Array,   # [B, M] int32 — physical block per logical block
    lengths: jax.Array,  # [B] int32
    k_scale: jax.Array | None = None,  # [n_blocks+1, P, n_kv] f32 (int8 mode)
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention DIRECTLY over the paged pool.

    The engine's original paged read gathered each row's blocks into a
    contiguous [B, S_max, K, hd] array first — materializing a second copy
    of the live cache in HBM every step (gather write + kernel read: ~2x
    the bytes decode is bound by).  Here the BLOCK TABLE rides the scalar
    prefetch (vLLM-PagedAttention's indirection, Pallas-style): the index
    map of each (row, logical-block) grid cell looks up the physical block
    and the DMA streams it straight from the pool, once.  Dead blocks
    (start >= length) clamp to the row's last live LOGICAL block, and a
    row of length 0 holds its source row's (``held_tile``) — whose
    physical index the revisited map returns again, so Mosaic elides their
    copies exactly like the lane kernel.  Composes with int8 pools: scale
    columns ride the same indirection.
    """
    b, n_heads, hd = q.shape
    n_kv = k_pool.shape[2]
    block = k_pool.shape[1]
    m = tables.shape[1]
    rows = block * n_kv

    def q_index(bi, sb, lens, src, tabs):
        return (bi, 0, 0)

    def kv_index(bi, sb, lens, src, tabs):
        return (tabs[held_tile(bi, sb, lens, src, block)], 0, 0)

    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, n_heads, hd), q_index),
        pl.BlockSpec((1, rows, hd), kv_index),
        pl.BlockSpec((1, rows, hd), kv_index),
    ]
    operands = [lengths, live_source(lengths), tables, q,
                _rows(k_pool), _rows(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, rows), kv_index)] * 2
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]
    # Same body as the lane kernel — the logical S-block index (grid dim 1)
    # drives masking exactly as there; the table routes the DMA.
    kernel = functools.partial(_indexed_kernel, block_s=block, n_kv=n_kv,
                               scale=float(1.0 / (hd ** 0.5)), quant=quant)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # lengths (masking), live_source + tables (DMA routing)
            num_scalar_prefetch=3,
            grid=(b, m),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_heads, hd), q_index),
            scratch_shapes=_scratch(n_heads, hd),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=("paged_decode_attention_int8" if quant
              else "paged_decode_attention"),
    )(*operands)


def paged_decode_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    tables: jax.Array, lengths: jax.Array,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch for the direct paged kernel: unsupported block/head
    shapes and non-TPU backends gather the row view (the pre-existing
    read) and take the lane-path dispatchers."""
    block, hd = k_pool.shape[1], k_pool.shape[3]
    quant = k_scale is not None
    reason = kernel_reason(
        paged_shape_reasons(block, hd, k_pool.dtype), interpret)
    log_choice(
        "paged_decode_int8" if quant else "paged_decode",
        f"q{tuple(q.shape)} pool{tuple(k_pool.shape)} "
        f"tables{tuple(tables.shape)}",
        reason and reason + "; gathering rows for the lane dispatcher",
        interpret)
    if reason is None:
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, tables, lengths, k_scale, v_scale,
            interpret=interpret)
    rows = functools.partial(gather_pool_rows, tables=tables)
    if quant:
        return decode_attention_quant(q, rows(k_pool), rows(v_pool),
                                      rows(k_scale), rows(v_scale), lengths,
                                      interpret=interpret)
    return decode_attention(q, rows(k_pool), rows(v_pool), lengths,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# Latent (MLA) variant: one row a position, keys and values in one tile
# ---------------------------------------------------------------------------


def _mla_kernel(len_ref, src_ref, layer_ref, q_ref, c_ref, o_ref, m_scr,
                l_scr, acc_scr, *, block_s: int, n_values: int, scale: float):
    # q_ref: [1, H, lanes], the absorbed queries; c_ref: [1, block_s, lanes],
    # one S-tile of the layer's latent rows [c | k_rope | 0].  The tile is
    # read from HBM once and used twice: all its columns are the keys of
    # EVERY head (one [H, lanes] x [lanes, block_s] matmul, no head of it
    # masked away), its first ``n_values`` columns the values.  The same
    # online-softmax sweep as ``_decode_kernel``.
    del src_ref, layer_ref  # consumed by the index maps
    bi = pl.program_id(0)
    sb = pl.program_id(1)
    length = len_ref[bi]
    start = sb * block_s

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < length)
    def _compute():
        q = q_ref[0]
        s = jax.lax.dot_general(
            q, c_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, block_s]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(q.dtype), c_ref[0, :, :n_values],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(sb == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def _mla_block(s_max: int) -> int:
    """Positions a tile: 1024 where it divides the lane (a 1.3 MB tile:
    half the grid steps of 512, of which a decode step of 32 rows x 13
    layers over 4,096 positions makes 3,328; chosen by that count, other
    sizes not timed), else the largest of 512/256/128 that does."""
    for bs in (1024, 512, 256, 128):
        if s_max % bs == 0:
            return bs
    return 0


def mla_shape_reasons(s_max: int, lanes: int, n_values: int) -> list[str]:
    reasons = []
    if lanes % 128 or n_values % 128:
        reasons.append(f"row of {lanes} lanes, {n_values} values: not whole "
                       "128-lane vregs")
    if not _mla_block(s_max):
        reasons.append(f"s_max={s_max} % 128 != 0")
    return reasons


def mla_decode_attention_pallas(
    q: jax.Array,       # [B, H, lanes] absorbed queries
    rows: jax.Array,    # [L, B, S, lanes] latent cache (or [B, S, lanes])
    lengths: jax.Array,  # [B] int32
    n_values: int,      # leading columns of a row that are its value
    scale: float,
    layer=None,
    block_s: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    if layer is None:
        rows, layer = rows[None], 0
    b, n_heads, lanes = q.shape
    s_max = rows.shape[2]
    block_s = block_s or _mla_block(s_max)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(bi, sb, lens, src, lay):
        return (bi, 0, 0)

    def row_index(bi, sb, lens, src, lay):
        # Dead S-blocks and dead rows revisit a held tile: no DMA.
        row, tile = held_tile(bi, sb, lens, src, block_s)
        return (lay[0], row, tile, 0)

    kernel = functools.partial(_mla_kernel, block_s=block_s,
                               n_values=n_values, scale=float(scale))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, n_values), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # lengths, their live_source, layer
            grid=(b, s_max // block_s),
            in_specs=[pl.BlockSpec((1, n_heads, lanes), q_index),
                      pl.BlockSpec((None, 1, block_s, lanes), row_index)],
            out_specs=pl.BlockSpec((1, n_heads, n_values), q_index),
            scratch_shapes=_scratch(n_heads, n_values),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mla_decode_attention",
    )(lengths, live_source(lengths), layer, q, rows)


def mla_decode_attention(
    q: jax.Array, rows: jax.Array, lengths: jax.Array, n_values: int,
    scale: float, layer=None, use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch for the latent cache: the kernel over the stacked rows and
    a layer index, the XLA form (``latent_decode_attention``) otherwise."""
    reason = ("pallas kernels off in the config" if not use_kernel
              else kernel_reason(
                  mla_shape_reasons(rows.shape[-2], rows.shape[-1], n_values),
                  interpret))
    log_choice("mla_decode", f"q{tuple(q.shape)} cache{tuple(rows.shape)}",
               reason, interpret)
    if reason is not None:
        return latent_decode_attention(q, _layer_view(rows, layer), lengths,
                                       n_values, scale)
    return mla_decode_attention_pallas(q, rows, lengths, n_values, scale,
                                       layer, interpret=interpret)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array,
    layer=None, interpret: bool = False, ring: bool = False,
) -> jax.Array:
    """Dispatch: Pallas kernel when shapes allow, XLA reference otherwise.
    With ``layer`` the caches are the stacked [L, B, S, K, hd] arrays and
    the kernel reads that layer in place.  ``ring``: the lanes are a window
    layer's rings, of which ``lengths`` says how many positions are held
    (the order of a softmax's keys does not matter, and each key's rotary
    encoding went on when it was written); the same kernel, named
    ``decode_attention_window`` so that a trace tells the two apart."""
    s_max, hd = k_cache.shape[-3], k_cache.shape[-1]
    reason = kernel_reason(
        shape_reasons(s_max, hd, _row_bytes(k_cache)), interpret)
    log_choice("decode_window" if ring else "decode",
               f"q{tuple(q.shape)} cache{tuple(k_cache.shape)}",
               reason, interpret)
    if reason is not None:
        return xla_decode(q, _layer_view(k_cache, layer),
                          _layer_view(v_cache, layer), lengths)
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, layer, interpret=interpret,
        name="decode_attention_window" if ring else "decode_attention")


def decode_attention_quant(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    k_scale: jax.Array, v_scale: jax.Array, lengths: jax.Array,
    layer=None, interpret: bool = False,
) -> jax.Array:
    """int8-KV dispatch: the quantized kernel streams half the HBM
    bytes AND skips the logits materialization; unsupported shapes / CPU
    dequantize and take the XLA reference."""
    s_max, hd = k_cache.shape[-3], k_cache.shape[-1]
    reason = kernel_reason(
        shape_reasons(s_max, hd, _row_bytes(k_cache)), interpret)
    log_choice("decode_int8",
               f"q{tuple(q.shape)} cache{tuple(k_cache.shape)}",
               reason, interpret)
    if reason is not None:
        k_cache, v_cache, k_scale, v_scale = (
            _layer_view(x, layer) for x in (k_cache, v_cache, k_scale, v_scale))
        deq = k_cache.astype(q.dtype) * k_scale[..., None].astype(q.dtype)
        dev = v_cache.astype(q.dtype) * v_scale[..., None].astype(q.dtype)
        return xla_decode(q, deq, dev, lengths)
    return decode_attention_quant_pallas(
        q, k_cache, v_cache, k_scale, v_scale, lengths, layer,
        interpret=interpret)
