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

Use ``flash_attention`` for the dispatching entry: it takes the XLA
reference (``ops.attention.prefill_attention``) when shapes don't meet the
tiling constraints (tiny test models, buckets below ``BLOCK_Q``) or off-TPU,
and logs which it took per traced program
(``ops.attention.log_choice``).
"""

from __future__ import annotations

import functools

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
# The chunk attend's tiles where the shapes allow them: a [256, 512] tile of
# scores is 16 of the [128, 128] ones, and a grid step costs ~0.35 us whether
# it computes or is skipped; at [128, 128] a 1,024-token chunk against a
# 16,384-position lane is 28,672 steps a layer (device trace, PR 45: 5.7 ms
# a full layer, 3.7 a window layer, half of a 91 ms chunk program).
CHUNK_BLOCK_Q = 256
CHUNK_BLOCK_K = 512


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
    # with ONE difference: query positions are offset by the chunk's
    # dynamic start (off_ref, SMEM) — chunk token i sits at global
    # position off + q_start + i and attends cache positions <= it.
    # K blocks wholly above the chunk's last position skip compute
    # (their DMA is elided by the index-map clamp).  With ``window`` a query
    # attends only the last ``window`` positions, its own included: K blocks
    # wholly behind every query's window skip compute and DMA the same way,
    # and a block the window's far edge cuts through takes the mask.
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    n_kblocks = pl.num_programs(3)
    bq = q_ref.shape[2]
    block_k = k_ref.shape[2]
    q_start = off_ref[0] + qi * bq
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        return _softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                              q_start, k_start, masked, scale, window)

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
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
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
    copies are elided (bandwidth tracks the chunk's position, not S_max)."""
    b, c, h, hd = q.shape
    s_max = k_cache.shape[1]
    n_kv = k_cache.shape[2]
    g = h // n_kv
    scale = float(scale or 1.0 / (hd ** 0.5))
    # Heads as lane columns: in the flat [B, 1, S, K*hd] view a head is the
    # hd-wide column block its index map names, so neither the queries nor
    # the lane are transposed to a heads-major copy.  The lane is flattened
    # HERE, next to the pallas_call that pins its layout: a transpose of a
    # slice of the layer loop's carry makes XLA lay the whole stacked cache
    # out heads-major, and convert it on the way in and out (compiled for
    # the v5e, PR 25).
    qf = q.reshape(b, 1, c, h * hd)
    kf = k_cache.reshape(b, 1, s_max, n_kv * hd)
    vf = v_cache.reshape(b, 1, s_max, n_kv * hd)
    off = jnp.asarray(start, jnp.int32).reshape(1)

    def q_index(bi, hi, qi, kb, off):
        return (bi, 0, qi, hi)

    def kv_index(bi, hi, qi, kb, off, g=g):
        q_first = off[0] + qi * block_q
        last = (q_first + block_q - 1) // block_k
        tile = jnp.minimum(kb, last)
        if window:  # blocks behind the window hold the first one inside it
            tile = jnp.maximum(
                tile, jnp.maximum(q_first - window + 1, 0) // block_k)
        return (bi, 0, tile, hi // g)

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # chunk start: masking + DMA clamping
            grid=(b, h, c // block_q, s_max // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, hd), q_index),
                pl.BlockSpec((1, 1, block_k, hd), kv_index),
                pl.BlockSpec((1, 1, block_k, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, hd), q_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),  # m (lane-padded)
                pltpu.VMEM((block_q, 128), jnp.float32),  # l
                pltpu.VMEM((block_q, hd), jnp.float32),   # o accumulator
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


def chunk_shape_reasons(c: int, s_max: int, hd: int) -> list[str]:
    reasons = []
    if hd % 128:
        reasons.append(f"hd={hd} % 128 != 0")
    if c % BLOCK_Q:
        reasons.append(f"chunk={c} % BLOCK_Q={BLOCK_Q} != 0")
    if s_max % BLOCK_K:
        reasons.append(f"s_max={s_max} % BLOCK_K={BLOCK_K} != 0")
    return reasons


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
    reason = kernel_reason(
        chunk_shape_reasons(c, k_cache.shape[1], hd * pack), interpret)
    log_choice(
        "chunk_attend", f"q{tuple(q.shape)} cache{tuple(k_cache.shape)}",
        reason, interpret)
    if reason is not None:
        return xla_chunk_attention(q, unpack_heads(k_cache, pack),
                                   unpack_heads(v_cache, pack), start, window)
    s_max = k_cache.shape[1]
    n_kv = k_cache.shape[2] * pack
    out = chunk_attention_pallas(
        pad_queries(q, n_kv, pack), k_cache, v_cache, start,
        block_q=BLOCK_Q if c % CHUNK_BLOCK_Q else CHUNK_BLOCK_Q,
        block_k=BLOCK_K if s_max % CHUNK_BLOCK_K else CHUNK_BLOCK_K,
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
