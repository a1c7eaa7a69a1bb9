"""Pallas TPU flash attention for prefill.

Why a hand kernel here and nowhere else (yet): prefill attention is the one
op where XLA's default schedule materializes the [S, S] score matrix in HBM
for long sequences — flash tiling keeps scores in VMEM and streams K/V
blocks, turning an O(S^2) HBM traffic pattern into O(S).  Everything
elementwise (norms, RoPE, activations) stays XLA-fused, per the guide's
"don't hand-schedule what the compiler already does".

Kernel shape: grid (B, H, S/BLOCK_Q, S/BLOCK_K) with the K-block axis
innermost and SEQUENTIAL ("arbitrary" semantics): VMEM holds ONE
[BLOCK_K, hd] K/V tile at a time — long-context ready, VMEM use is O(block)
regardless of S — while the online-softmax state (m, l, o-accumulator)
persists in f32 scratch across the K sweep and the output writes on the
last K block.  GQA is native: the K/V BlockSpec index-maps query head h to
KV head h // (H/K), so grouped heads share the same streamed K/V tile
without materialized repetition.  Causal K blocks strictly above the
diagonal skip their FLOPs via @pl.when.

The chunk attend (``chunk_attention_pallas``: a streamed prompt's chunk
of queries at a dynamic offset against its cache lane) has the same core
and another grid: (B, K, C/BLOCK_Q, S/BLOCK_K) over the KV heads.  A step
holds the query tiles of ALL H/K query heads of its kv head and the one K/V
tile they share, and runs the heads through the recurrence one after the
other, so a K/V tile leaves HBM once for the group and not once a query
head, and a layer's call has K and not H times the steps (those past the
chunk's reach or behind its window are skipped, but each is still a step).

Use ``flash_attention`` for the dispatching entry: it takes the XLA
reference (``ops.attention.prefill_attention``) when shapes don't meet the
tiling constraints (tiny test models, buckets below ``BLOCK_Q``) or off-TPU,
and logs which it took per traced program
(``ops.attention.log_choice``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_instance_gateway_tpu.ops.attention import (
    kernel_reason,
    log_choice,
    own_values,
    pad_queries,
    prefill_attention,
    unpack_heads,
)

NEG_INF = -1e30

BLOCK_Q = 128
BLOCK_K = 128
# The chunk attend's tiles where the shapes allow them.  A [256, 512] tile
# of scores is 16 of the [128, 128] ones, and a grid step costs ~0.2-0.35 us
# whether it computes or is skipped; at [128, 128] a 1,024-token chunk
# against a 16,384-position lane was 28,672 steps a layer (device trace,
# PR 45: 5.7 ms a full layer, 3.7 a window layer, half of a 91 ms chunk
# program), at [256, 512] 3,584 on the per-query-head grid and 512 on the
# kv heads' (PR 59).  The key tile is 1,024 wide since the grid walks the
# kv heads: what a head pays a tile whatever its width (the row maxima and
# sums across lanes, the accumulator's rescale, the state's stores) is
# ~0.4 us of the ~1.0 a [256, 512] head-tile costs, against 0.34 of MXU
# work, and a 1,024-wide tile pays it half as often: SmallThinker's full
# layer at offset 3,072 took 1.48 ms on the per-head grid, 0.95 grouped at
# [256, 512] and 0.64 at [256, 1024] (tools/onchip_pallas_check.py
# "chunk-attend time", chip runs of PR 59; the v5e's compiler refuses
# [256, 2048] and [512, 1024] at 7 heads a group: out of VMEM).
CHUNK_BLOCK_Q = 256
CHUNK_BLOCK_K = 1024


def _softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                   q_start, k_start, masked: bool, scale: float,
                   window: int = 0):
    """One K/V tile of the online-softmax recurrence — the numerically
    sensitive core shared by the self-attention flash kernel (static
    q_start) and the chunk-attend kernel (dynamic, offset q_start).  A
    ``masked`` tile keeps the keys at or before each query and, with
    ``window``, fewer than ``window`` positions behind it."""
    def go():
        bq = q_ref.shape[2]
        block_k = k_ref.shape[2]
        # The tiles feed the MXU in their storage dtype with float32
        # accumulation, as the decode kernel's do: a product of two bf16
        # numbers is exact in float32, so the scores are those of float32
        # operands, at the MXU's bf16 rate (float32 operands took 3.7 ms a
        # window layer a 1,024-token chunk at SmallThinker's layout: device
        # trace, PR 45).  Float32 tiles (the CPU's tests) stay float32.
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            keep = q_pos >= k_pos
            if window:
                keep &= q_pos - k_pos < window
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * corr + p.sum(axis=-1, keepdims=True), l_scr.shape)
        # The weights go to the values' dtype, as the XLA forms' do.
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    return go


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, scale: float):
    # Blocks keep their leading (batch, head) unit dims:
    # q_ref: [1, 1, BLOCK_Q, hd]; k_ref/v_ref: [1, 1, BLOCK_K, hd] — one K/V
    # tile per grid step, carried state in scratch (lane-padded to 128).
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    n_kblocks = pl.num_programs(3)
    bq = q_ref.shape[2]
    block_k = k_ref.shape[2]
    q_start = qi * bq
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        return _softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                              q_start, k_start, masked, scale)

    if causal:
        # Exactly one branch runs per step: the diagonal-straddling block
        # pays for the iota mask, interior blocks skip it, and blocks
        # strictly above the diagonal do nothing (their K/V DMA is also
        # elided — the index map revisits the previous tile).
        on_diagonal = (k_start + block_k > q_start) & (k_start < q_start + bq)
        pl.when(on_diagonal)(_compute(masked=True))
        pl.when(k_start + block_k <= q_start)(_compute(masked=False))
    else:
        _compute(masked=False)()

    @pl.when(kb == n_kblocks - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # [B, H, S, hd]
    k: jax.Array,  # [B, K, S, hd]
    v: jax.Array,
    causal: bool = True,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
    scale: float | None = None,  # the softmax's, if not 1 / sqrt(hd)
) -> jax.Array:
    b, h, s, hd = q.shape
    n_kv = k.shape[1]
    g = h // n_kv
    scale = float(scale or 1.0 / (hd ** 0.5))
    # K-block axis innermost and sequential: scratch carries the online
    # softmax state across it; the three outer axes parallelize freely.
    grid = (b, h, s // block_q, s // block_k)
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale)

    if causal:
        # Blocks strictly above the diagonal never contribute: clamp their
        # K/V index to the last contributing tile, so Pallas sees the same
        # block as the previous step and elides the dead HBM->VMEM copy
        # (the kernel's @pl.when skips their compute anyway).
        def kv_index(bi, hi, qi, kb, g=g):
            last = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // g, jnp.minimum(kb, last), 0)
    else:
        def kv_index(bi, hi, qi, kb, g=g):
            return (bi, hi // g, kb, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, kb: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, hd), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, hd), kv_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, kb: (bi, hi, qi, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # m (lane-padded)
            pltpu.VMEM((block_q, 128), jnp.float32),  # l
            pltpu.VMEM((block_q, hd), jnp.float32),   # o accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def shape_reasons(s: int, hd: int, block_q: int = BLOCK_Q,
                  block_k: int = BLOCK_K) -> list[str]:
    """Shape gates of the flash kernel that this call misses (empty = ok)."""
    reasons = []
    if hd % 128:
        reasons.append(f"hd={hd} % 128 != 0")
    if s % block_q or s % block_k:
        reasons.append(
            f"s={s} % BLOCK_Q/K={block_q}/{block_k} != 0"
            + (" (bucket < BLOCK_Q)" if s < block_q else ""))
    return reasons


def supports(s: int, hd: int, block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    """Shape gate for the kernel path (pad upstream or fall back)."""
    return not shape_reasons(s, hd, block_q, block_k)


# ---------------------------------------------------------------------------
# Chunk attend: a query chunk at a dynamic position offset vs the KV cache
# ---------------------------------------------------------------------------


def _chunk_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, window: int = 0):
    # Same online-softmax core as _flash_kernel (shared _softmax_block)
    # with TWO differences.  Query positions are offset by the chunk's
    # dynamic start (off_ref, SMEM) — chunk token i sits at global
    # position off + q_start + i and attends cache positions <= it.  And
    # the grid's head axis runs over the KV heads: q_ref / o_ref hold the
    # tile of ALL g query heads of the step's kv head ([1, 1, BQ, g * hd],
    # adjacent column blocks of the flat view), the scratch carries one
    # (m, l, accumulator) a head (leading g), and the one resident K/V tile
    # serves the g heads in turn, each through the recurrence it had when
    # it owned a grid step: the same numbers, in the same tile order.
    # K blocks wholly above the chunk's last position skip compute
    # (their DMA is elided by the index-map clamp).  With ``window`` a query
    # attends only the last ``window`` positions, its own included: K blocks
    # wholly behind every query's window skip compute and DMA the same way,
    # and a block the window's far edge cuts through takes the mask.  What
    # a block is (dead, edge, diagonal, interior) hangs on positions alone,
    # so the group shares the predicates.
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    n_kblocks = pl.num_programs(3)
    bq = q_ref.shape[2]
    block_k, hd = k_ref.shape[2:]
    g = q_ref.shape[3] // hd
    q_start = off_ref[0] + qi * bq
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        def go():
            for j in range(g):  # head j: its hd-wide columns of the tile
                _softmax_block(q_ref.at[:, :, :, pl.ds(j * hd, hd)], k_ref,
                               v_ref, m_scr.at[j], l_scr.at[j], acc_scr.at[j],
                               q_start, k_start, masked, scale, window)()
        return go

    # Dynamic diagonal (off is a runtime value): at most one branch fires.
    on_diagonal = (k_start + block_k > q_start) & (k_start < q_start + bq)
    below = k_start + block_k <= q_start
    if window:
        # The block's newest key lies ``window`` or more behind the tile's
        # oldest query: nothing of it is attended.
        dead = k_start + block_k - 1 + window <= q_start
        # Its oldest key lies that far behind the tile's newest query.
        edge = q_start + bq - 1 - k_start >= window
        pl.when(on_diagonal | (below & edge & ~dead))(_compute(masked=True))
        pl.when(below & ~edge)(_compute(masked=False))
    else:
        pl.when(on_diagonal)(_compute(masked=True))
        pl.when(below)(_compute(masked=False))

    @pl.when(kb == n_kblocks - 1)
    def _finalize():
        for j in range(g):
            o_ref[0, 0, :, pl.ds(j * hd, hd)] = (
                acc_scr[j] / jnp.maximum(l_scr[j, :, :1], 1e-30)
            ).astype(o_ref.dtype)


def chunk_attention_pallas(
    q: jax.Array,        # [B, C, H, hd] — chunk queries (contiguous positions)
    k_cache: jax.Array,  # [B, S, K, hd] — lane view incl. the chunk's KV
    v_cache: jax.Array,
    start: jax.Array,    # scalar int32: global position of chunk token 0
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
    window: int = 0,
    scale: float | None = None,  # the softmax's, if not 1 / sqrt(hd)
) -> jax.Array:
    """Flash-style chunk attend: chunk token i (global position start+i)
    attends cache positions <= start+i (with ``window``: the last
    ``window`` of them, its own included).  Replaces the XLA einsum's [C, S]
    logits materialization on the chunk-stream path — the long-context
    TTFT hot loop — with O(block) VMEM tiles; K blocks past each query
    tile's reach are clamped to the last contributing tile so their HBM
    copies are elided (bandwidth tracks the chunk's position, not S_max).
    One grid step is a (kv head, query tile, key tile): the H / K query
    heads of a kv head take its K/V tile from VMEM, fetched once for all of
    them (``chunk_grid``)."""
    b, c, h, hd = q.shape
    s_max = k_cache.shape[1]
    n_kv = k_cache.shape[2]
    g = h // n_kv
    scale = float(scale or 1.0 / (hd ** 0.5))
    # Heads as lane columns: in the flat [B, 1, S, K*hd] view a head is the
    # hd-wide column block its index map names (and a kv head's g query
    # heads the g*hd-wide one), so neither the queries nor the lane are
    # transposed to a heads-major copy.  The lane is flattened
    # HERE, next to the pallas_call that pins its layout: a transpose of a
    # slice of the layer loop's carry makes XLA lay the whole stacked cache
    # out heads-major, and convert it on the way in and out (compiled for
    # the v5e, PR 25).
    qf = q.reshape(b, 1, c, h * hd)
    kf = k_cache.reshape(b, 1, s_max, n_kv * hd)
    vf = v_cache.reshape(b, 1, s_max, n_kv * hd)
    off = jnp.asarray(start, jnp.int32).reshape(1)

    def q_index(bi, ki, qi, kb, off):
        return (bi, 0, qi, ki)

    def kv_index(bi, ki, qi, kb, off):
        q_first = off[0] + qi * block_q
        last = (q_first + block_q - 1) // block_k
        tile = jnp.minimum(kb, last)
        if window:  # blocks behind the window hold the first one inside it
            tile = jnp.maximum(
                tile, jnp.maximum(q_first - window + 1, 0) // block_k)
        return (bi, 0, tile, ki)

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # chunk start: masking + DMA clamping
            grid=chunk_grid(b, c, s_max, n_kv, block_q, block_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, g * hd), q_index),
                pl.BlockSpec((1, 1, block_k, hd), kv_index),
                pl.BlockSpec((1, 1, block_k, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, g * hd), q_index),
            scratch_shapes=[
                pltpu.VMEM((g, block_q, 128), jnp.float32),  # m (lane-padded)
                pltpu.VMEM((g, block_q, 128), jnp.float32),  # l
                pltpu.VMEM((g, block_q, hd), jnp.float32),   # o accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="chunk_attention",
    )(off, qf, kf, vf)
    return out.reshape(b, c, h, hd)


def chunk_grid(b: int, c: int, s_max: int, n_kv: int, block_q: int,
               block_k: int) -> tuple[int, int, int, int]:
    """The chunk attend's grid: (lanes, KV heads as the lane has them,
    query tiles, key tiles)."""
    return (b, n_kv, c // block_q, s_max // block_k)


# What a grid step of the chunk attend may hold of the v5e's 16 MiB of
# scoped VMEM: the rest is the compiler's (a masked tile's iotas, spills).
_CHUNK_VMEM_BYTES = 12 << 20


def _chunk_step_bytes(block_q: int, block_k: int, g: int, hd: int,
                      itemsize: int) -> int:
    """VMEM of one grid step of the chunk attend over a group of ``g``
    heads: the group's query and output tiles and the K and V tiles, each
    double-buffered, the group's (m, l, accumulator) and the float32 score
    tile of the one head in flight (scores, weights, mask)."""
    tiles = 2 * 2 * (block_q * g + block_k) * hd * itemsize
    state = g * block_q * (2 * 128 + hd) * 4
    return tiles + state + 3 * block_q * block_k * 4


def chunk_blocks(c: int, s_max: int, g: int, hd: int,
                 itemsize: int = 2) -> tuple[int, int]:
    """(block_q, block_k) of the chunk attend, from the shapes alone: the
    widest key tile from ``CHUNK_BLOCK_K`` down that divides the lane (a
    ring with a chunk behind it need not be a power of two), the query tile
    at ``CHUNK_BLOCK_Q`` where it divides the chunk, back at ``BLOCK_Q``
    where ``g`` heads of ``CHUNK_BLOCK_Q`` rows are more than a step may
    hold, and 0 where ``g`` heads of ``BLOCK_Q`` are too."""
    block_k = CHUNK_BLOCK_K
    while s_max % block_k and block_k > BLOCK_K:
        block_k //= 2
    for block_q in (CHUNK_BLOCK_Q, BLOCK_Q):
        if not c % block_q and _chunk_step_bytes(
                block_q, block_k, g, hd, itemsize) <= _CHUNK_VMEM_BYTES:
            return block_q, block_k
    return 0, block_k


def chunk_shape_reasons(c: int, s_max: int, hd: int, g: int = 1,
                        itemsize: int = 2) -> list[str]:
    reasons = []
    if hd % 128:
        reasons.append(f"hd={hd} % 128 != 0")
    if c % BLOCK_Q:
        reasons.append(f"chunk={c} % BLOCK_Q={BLOCK_Q} != 0")
    if s_max % BLOCK_K:
        reasons.append(f"s_max={s_max} % BLOCK_K={BLOCK_K} != 0")
    if not reasons and not chunk_blocks(c, s_max, g, hd, itemsize)[0]:
        reasons.append(f"a group of {g} heads of {hd} is over a step's VMEM")
    return reasons


def chunk_grid_steps(c: int, s_max: int, h: int, n_kv: int, hd: int,
                     itemsize: int = 2) -> int:
    """Steps of the chunk attend's grid for a ``c``-token chunk of ``h``
    query heads against one ``s_max`` lane of ``n_kv`` heads of ``hd`` AS
    THE LANE HAS THEM (packed rows: the rows and their width), by the
    dispatcher's own rule on the host (``tpu:chunk_attn_grid_steps_total``);
    0 where the kernel takes no such shape."""
    g = h // n_kv
    if chunk_shape_reasons(c, s_max, hd, g, itemsize):
        return 0
    return math.prod(chunk_grid(
        1, c, s_max, n_kv, *chunk_blocks(c, s_max, g, hd, itemsize)))


def supports_chunk(c: int, s_max: int, hd: int) -> bool:
    return not chunk_shape_reasons(c, s_max, hd)


def chunk_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, start,
    interpret: bool = False, window: int = 0, pack: int = 1,
) -> jax.Array:
    """Dispatch for the chunk attend; XLA reference otherwise.  ``pack`` >
    1: the lane holds that many narrow kv heads a row ([B, S, K / pack,
    pack * hd], ``ops.attention.pack_heads``) and ``q`` comes as the model
    has it: the kernel takes the rows as they lie and the queries padded
    into their heads' columns."""
    from llm_instance_gateway_tpu.ops.attention import xla_chunk_attention

    b, c, h, hd = q.shape
    s_max, rows = k_cache.shape[1:3]
    g, itemsize = h // rows, q.dtype.itemsize
    reason = kernel_reason(
        chunk_shape_reasons(c, s_max, hd * pack, g, itemsize), interpret)
    log_choice(
        "chunk_attend", f"q{tuple(q.shape)} cache{tuple(k_cache.shape)}",
        reason, interpret)
    if reason is not None:
        return xla_chunk_attention(q, unpack_heads(k_cache, pack),
                                   unpack_heads(v_cache, pack), start, window)
    n_kv = rows * pack
    block_q, block_k = chunk_blocks(c, s_max, g, hd * pack, itemsize)
    out = chunk_attention_pallas(
        pad_queries(q, n_kv, pack), k_cache, v_cache, start,
        block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, scale=1.0 / (hd ** 0.5))
    return own_values(out, n_kv, pack)


def flash_attention(
    q: jax.Array,  # [B, S, H, hd] (model layout)
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,
    causal: bool = True,
    interpret: bool = False,
    pack: int = 1,
) -> jax.Array:
    """Dispatch: Pallas kernel when shapes allow, XLA reference otherwise.
    ``pack`` > 1: ``k`` and ``v`` hold that many narrow kv heads a row
    ([B, S, K / pack, pack * hd], ``ops.attention.pack_heads``), ``q`` is
    as the model has it.

    NOTE: the kernel path is purely causal — use it for right-padded batches
    (pad tokens trail real ones, so causality alone keeps real positions
    exact; pad rows are garbage the caller ignores).  Packed batches with
    position-based masks must use the XLA path.
    """
    b, s, h, hd = q.shape
    reason = kernel_reason(
        shape_reasons(s, hd * pack), interpret)
    log_choice(
        "flash_prefill", f"q{tuple(q.shape)} kv{tuple(k.shape)}", reason,
        interpret)
    if reason is not None:
        return prefill_attention(q, unpack_heads(k, pack),
                                 unpack_heads(v, pack))
    n_kv = k.shape[2] * pack
    qt = pad_queries(q, n_kv, pack).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, interpret=interpret,
                               scale=1.0 / (hd ** 0.5))
    return own_values(out.transpose(0, 2, 1, 3), n_kv, pack)
