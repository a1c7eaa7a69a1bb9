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
the engine's garbage-tail cache contract.  The grid is ONE dimension over a
schedule of the live steps (``decode_schedule``): for every row that reads
something, its tiles up to its length, and nothing else.  A row of length 0
(a slot that does not decode in this step: ``transformer.decode_step``
zeroes the length of every row whose ``active`` bit is off) and the tiles
past a short row's end are not in it, so they cost no copy, no matmul and
no grid step; such a row emits zeros.  On the chip the grid's bound is the
schedule's length, a dynamic bound; the interpreter takes none, so there
the same kernel walks the same schedule under the static bound slots x
tiles and the steps past its end do nothing.  ``decode_attention`` is the
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
    own_values,
    pad_queries,
    unpack_heads,
)

NEG_INF = -1e30


def decode_schedule(lengths: jax.Array, block_s: int, n_tiles: int):
    """The steps every decode kernel here walks, from the lengths alone (so
    in a layer loop it is loop-invariant): for every row of length > 0 its
    tiles 0 .. ceil(length / block_s) - 1, rows in slot order, tiles
    ascending.  Returns (row [B * n_tiles], tile [B * n_tiles], n_steps
    [1]), all int32: step ``i`` < ``n_steps`` works on tile ``tile[i]`` of
    row ``row[i]``; the entries past ``n_steps`` repeat the last live
    step's (of row 0's first tile when nothing is live), so that a walk
    under a static bound copies nothing for them."""
    b = lengths.shape[0]
    tiles_of = jnp.minimum((lengths + block_s - 1) // block_s,
                           n_tiles).astype(jnp.int32)
    ends = jnp.cumsum(tiles_of)
    n_steps = ends[-1:]
    i = jnp.minimum(jnp.arange(b * n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_steps - 1, 0))
    # the rows whose steps all lie before step i: their count is i's row
    # (the dead rows on the way count themselves in), their tiles its start
    done = ends[None, :] <= i[:, None]
    row = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), b - 1)
    tile = i - jnp.sum(jnp.where(done, tiles_of, 0), axis=1)
    return row, tile, n_steps


def _walk(lengths: jax.Array, schedule, block_s: int, n_tiles: int,
          interpret: bool):
    """(the scalar-prefetch operands of a walk over ``schedule``, its grid).
    ``schedule`` None: built here from the lengths (a caller with a layer
    loop builds it once before the loop, over ``lane_tiles`` or
    ``mla_tiles``).  The bound is
    the schedule's length where the backend lowers a dynamic one (Mosaic
    does, the interpreter does not), else its capacity."""
    capacity = lengths.shape[0] * n_tiles
    if schedule is None:
        schedule = decode_schedule(lengths, block_s, n_tiles)
    row, tile, n_steps = schedule
    if row.shape != (capacity,) or tile.shape != (capacity,):
        raise ValueError(f"a schedule of {row.shape[0]} steps for a walk "
                         f"of {capacity}: built for another tile")
    grid = (capacity,) if interpret else (n_steps[0],)
    return (lengths, row, tile, n_steps), grid


def schedule_steps(lengths, block_s: int, n_tiles: int) -> int:
    """``decode_schedule``'s ``n_steps`` by the same rule on the host, from
    lengths the host holds (``tpu:decode_attn_grid_steps_total``)."""
    return sum(min(-(-int(n) // block_s), n_tiles) for n in lengths)


def lane_tiles(k_cache) -> tuple[int, int]:
    """(positions a tile, tiles a lane) of the lane kernel's walk over
    ``k_cache`` ([.., B, S, K, hd], bf16 or int8); (0, 0) where the kernel
    takes no such cache (``shape_reasons``)."""
    s_max = k_cache.shape[-3]
    block_s = _pick_block(s_max, _row_bytes(k_cache))
    return block_s, block_s and s_max // block_s


def mla_tiles(rows) -> tuple[int, int]:
    """``lane_tiles`` of the latent kernel over ``rows`` ([.., B, S, lanes])."""
    s_max = rows.shape[-2]
    block_s = _mla_block(s_max)
    return block_s, block_s and s_max // block_s


def _step(len_ref, row_ref, tile_ref, n_ref, block_s: int, s_max: int):
    """Where this grid step stands: (it is one of the schedule's, its tile's
    first position, its row's length).  It is its row's first step when the
    tile starts at 0 and its last when the tile reaches the length."""
    i = pl.program_id(0)
    length = jnp.minimum(len_ref[row_ref[i]], s_max)
    return i < n_ref[0], tile_ref[i] * block_s, length


def _emit(out: jax.Array, lengths: jax.Array) -> jax.Array:
    """A row the schedule never visits has no output block written: it
    emits zeros, not an unwritten buffer (the engine masks such rows either
    way)."""
    return jnp.where((lengths > 0)[:, None, None], out, 0)


_WALK = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _decode_kernel(len_ref, row_ref, tile_ref, n_ref, index_ref, q_ref,
                   k_ref, v_ref, *refs, block_s: int, s_max: int, n_kv: int,
                   scale: float, quant: bool):
    # q_ref: [1, H, hd]; k_ref/v_ref: [1, block_s*K, hd] — one S-tile of the
    # cache in its ROWS view: row s*K + kh is position s of kv head kh, which
    # is how the [.., S, K, hd] cache lies in HBM (``_rows``), so the tile
    # arrives by one straight DMA and is read as whole vregs.  All heads go
    # through the MXU together (what a per-head grid would cost on the
    # chip: not measured): the [H, block_s*K] logits hold every query head
    # against every row, and the mask keeps the columns of its own kv head.
    # len_ref [B], row_ref / tile_ref / n_ref (``decode_schedule``): SMEM,
    # scalar-prefetched; index_ref (the lane kernel's layer index, the paged
    # kernel's block table) is consumed by the index maps, not the body:
    # only the DMA source moves.  The grid is sequential ("arbitrary"): a
    # row's tiles follow one another, and the online-softmax state rides
    # f32 VMEM scratch across them, like the prefill flash kernel.
    # ``quant``: K/V tiles arrive int8 with per-row f32 scales as lane
    # vectors (ks_ref/vs_ref: [1, 1, block_s*K]); int8 is exact in the
    # compute dtype, so the tiles feed the MXU as they are and the scales
    # multiply the logits' and the probabilities' columns — HBM streams
    # half the bytes of the bf16 variant, decode's actual bound.
    del index_ref
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    n_heads = q_ref.shape[1]
    g = n_heads // n_kv
    rows = block_s * n_kv
    live, start, length = _step(len_ref, row_ref, tile_ref, n_ref, block_s,
                                s_max)

    @pl.when(live & (start == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Every step of the schedule holds a tile that starts inside its row's
    # length; the tile that straddles the length masks.
    @pl.when(live)
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

    @pl.when(live & (start + block_s >= length))
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


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
                        name: str = "decode_attention",
                        schedule=None, scale: float | None = None,
                        ) -> jax.Array:
    """Shared pallas_call builder for the bf16 and int8 variants, over the
    STACKED cache [L, B, S, K, hd] and a layer index: the index rides the
    scalar prefetch into the tiles' index map, so the kernel reads the
    layer where it lies and no slice of the cache is materialised.  With
    ``layer`` None the arrays are one layer's, a stack of one (a leading 1
    is free).  ``scales`` is None (bf16) or (k_scale, v_scale) f32, stacked
    like the cache: only that layer's reach the kernel, as lane vectors — a
    relayout kept to one layer of an array 1/hd the size of the cache.
    ``name`` is the call's name in a device trace: the same body over a
    window layer's ring lanes runs as ``decode_attention_window``.
    ``scale``: the softmax's, where it is not 1 / sqrt(hd) of the rows as
    they lie (packed narrow heads, ``ops.attention.pack_heads``)."""
    if scales is not None:
        scales = [_layer_view(s, layer) for s in scales]
    if layer is None:
        k_all, v_all, layer = k_all[None], v_all[None], 0
    b, n_heads, hd = q.shape
    s_max, n_kv = k_all.shape[2], k_all.shape[3]
    if block_s is None:
        block_s = lane_tiles(k_all)[0]
    rows = block_s * n_kv
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(i, lens, row, tile, n, lay):
        return (row[i], 0, 0)

    def kv_index(i, lens, row, tile, n, lay):
        return (lay[0], row[i], tile[i], 0)

    def scale_index(i, lens, row, tile, n, lay):
        return (row[i], 0, tile[i])

    quant = scales is not None
    in_specs = [
        pl.BlockSpec((1, n_heads, hd), q_index),
        pl.BlockSpec((None, 1, rows, hd), kv_index),
        pl.BlockSpec((None, 1, rows, hd), kv_index),
    ]
    walk, grid = _walk(lengths, schedule, block_s, s_max // block_s,
                       interpret)
    operands = [*walk, layer, q, _rows(k_all), _rows(v_all)]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, rows), scale_index)] * 2
        operands += [_scale_rows(s) for s in scales]
    kernel = functools.partial(_decode_kernel, block_s=block_s, s_max=s_max,
                               n_kv=n_kv,
                               scale=float(scale or 1.0 / (hd ** 0.5)),
                               quant=quant)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # lengths and their schedule: masking + which tile a step
            # holds; layer: which of the stack
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_heads, hd), q_index),
            scratch_shapes=_scratch(n_heads, hd),
        ),
        compiler_params=_WALK,
        interpret=interpret,
        name="decode_attention_int8" if quant else name,
    )(*operands)
    return _emit(out, lengths)


def decode_attention_pallas(
    q: jax.Array,        # [B, n_heads, hd]
    k_cache: jax.Array,  # [B, S, n_kv, hd], or [L, B, S, n_kv, hd] + layer
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] int32
    layer=None,          # scalar int32: which layer of a stacked cache
    block_s: int | None = None,
    interpret: bool = False,
    name: str = "decode_attention",
    schedule=None,       # ``decode_schedule`` over ``lane_tiles``, built ahead
    scale: float | None = None,  # the softmax's, if not 1 / sqrt(hd)
) -> jax.Array:
    return _pallas_decode_call(q, k_cache, v_cache, None, lengths, layer,
                               block_s, interpret, name, schedule, scale)


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
    schedule=None,
) -> jax.Array:
    return _pallas_decode_call(q, k_cache, v_cache, (k_scale, v_scale),
                               lengths, layer, block_s, interpret,
                               schedule=schedule)


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
    and the DMA streams it straight from the pool, once.  The grid walks
    the lane kernel's schedule (``decode_schedule``, a tile being one
    logical block): the blocks past a row's length and the rows of length 0
    are no step of it.  Composes with int8 pools: scale columns ride the
    same indirection.
    """
    b, n_heads, hd = q.shape
    n_kv = k_pool.shape[2]
    block = k_pool.shape[1]
    m = tables.shape[1]
    rows = block * n_kv

    def q_index(i, lens, row, tile, n, tabs):
        return (row[i], 0, 0)

    def kv_index(i, lens, row, tile, n, tabs):
        return (tabs[row[i], tile[i]], 0, 0)

    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, n_heads, hd), q_index),
        pl.BlockSpec((1, rows, hd), kv_index),
        pl.BlockSpec((1, rows, hd), kv_index),
    ]
    walk, grid = _walk(lengths, None, block, m, interpret)
    operands = [*walk, tables, q, _rows(k_pool), _rows(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, rows), kv_index)] * 2
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]
    # Same body as the lane kernel — the step's logical block drives the
    # masking exactly as there; the table routes the DMA.
    kernel = functools.partial(_decode_kernel, block_s=block, s_max=block * m,
                               n_kv=n_kv, scale=float(1.0 / (hd ** 0.5)),
                               quant=quant)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # lengths and their schedule (masking, which block a step
            # holds), tables (DMA routing)
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_heads, hd), q_index),
            scratch_shapes=_scratch(n_heads, hd),
        ),
        compiler_params=_WALK,
        interpret=interpret,
        name=("paged_decode_attention_int8" if quant
              else "paged_decode_attention"),
    )(*operands)
    return _emit(out, lengths)


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


def _mla_kernel(len_ref, row_ref, tile_ref, n_ref, layer_ref, q_ref, c_ref,
                o_ref, m_scr, l_scr, acc_scr, *, block_s: int, s_max: int,
                n_values: int, scale: float):
    # q_ref: [1, H, lanes], the absorbed queries; c_ref: [1, block_s, lanes],
    # one S-tile of the layer's latent rows [c | k_rope | 0].  The tile is
    # read from HBM once and used twice: all its columns are the keys of
    # EVERY head (one [H, lanes] x [lanes, block_s] matmul, no head of it
    # masked away), its first ``n_values`` columns the values.  The same
    # walk of the schedule and online-softmax sweep as ``_decode_kernel``.
    del layer_ref  # consumed by the index maps
    live, start, length = _step(len_ref, row_ref, tile_ref, n_ref, block_s,
                                s_max)

    @pl.when(live & (start == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
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

    @pl.when(live & (start + block_s >= length))
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
    schedule=None,      # ``decode_schedule`` over ``mla_tiles``, built ahead
) -> jax.Array:
    if layer is None:
        rows, layer = rows[None], 0
    b, n_heads, lanes = q.shape
    s_max = rows.shape[2]
    block_s = block_s or _mla_block(s_max)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(i, lens, row, tile, n, lay):
        return (row[i], 0, 0)

    def row_index(i, lens, row, tile, n, lay):
        return (lay[0], row[i], tile[i], 0)

    walk, grid = _walk(lengths, schedule, block_s, s_max // block_s,
                       interpret)
    kernel = functools.partial(_mla_kernel, block_s=block_s, s_max=s_max,
                               n_values=n_values, scale=float(scale))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, n_values), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # lengths, their schedule, layer
            grid=grid,
            in_specs=[pl.BlockSpec((1, n_heads, lanes), q_index),
                      pl.BlockSpec((None, 1, block_s, lanes), row_index)],
            out_specs=pl.BlockSpec((1, n_heads, n_values), q_index),
            scratch_shapes=_scratch(n_heads, n_values),
        ),
        compiler_params=_WALK,
        interpret=interpret,
        name="mla_decode_attention",
    )(*walk, layer, q, rows)
    return _emit(out, lengths)


def mla_decode_attention(
    q: jax.Array, rows: jax.Array, lengths: jax.Array, n_values: int,
    scale: float, layer=None, use_kernel: bool = True,
    interpret: bool = False, schedule=None,
) -> jax.Array:
    """Dispatch for the latent cache: the kernel over the stacked rows and
    a layer index, the XLA form (``latent_decode_attention``) otherwise.
    ``schedule``: the kernel's (``decode_schedule`` over ``mla_tiles``),
    where the caller built it ahead of its layer loop."""
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
                                       layer, interpret=interpret,
                                       schedule=schedule)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array,
    layer=None, interpret: bool = False, ring: bool = False, schedule=None,
    pack: int = 1,
) -> jax.Array:
    """Dispatch: Pallas kernel when shapes allow, XLA reference otherwise.
    With ``layer`` the caches are the stacked [L, B, S, K, hd] arrays and
    the kernel reads that layer in place.  ``ring``: the lanes are a window
    layer's rings, of which ``lengths`` says how many positions are held
    (the order of a softmax's keys does not matter, and each key's rotary
    encoding went on when it was written); the same kernel, named
    ``decode_attention_window`` so that a trace tells the two apart.
    ``schedule``: the kernel's (``decode_schedule`` over ``lane_tiles``),
    where the caller built it ahead of its layer loop; built here
    otherwise.  ``pack`` > 1: the caches hold that many narrow kv heads a
    row ([.., K / pack, pack * hd], ``ops.attention.pack_heads``) and ``q``
    comes as the model has it, [B, H, hd]: the kernel takes the rows as
    they lie and the queries padded into their heads' columns."""
    s_max, hd = k_cache.shape[-3], k_cache.shape[-1]
    reason = kernel_reason(
        shape_reasons(s_max, hd, _row_bytes(k_cache)), interpret)
    log_choice("decode_window" if ring else "decode",
               f"q{tuple(q.shape)} cache{tuple(k_cache.shape)}",
               reason, interpret)
    if reason is not None:
        return xla_decode(q, unpack_heads(_layer_view(k_cache, layer), pack),
                          unpack_heads(_layer_view(v_cache, layer), pack),
                          lengths)
    n_kv = k_cache.shape[-2] * pack
    out = decode_attention_pallas(
        pad_queries(q, n_kv, pack), k_cache, v_cache, lengths, layer,
        interpret=interpret,
        name="decode_attention_window" if ring else "decode_attention",
        schedule=schedule, scale=1.0 / (q.shape[-1] ** 0.5))
    return own_values(out, n_kv, pack)


def decode_attention_quant(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    k_scale: jax.Array, v_scale: jax.Array, lengths: jax.Array,
    layer=None, interpret: bool = False, schedule=None,
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
        interpret=interpret, schedule=schedule)
