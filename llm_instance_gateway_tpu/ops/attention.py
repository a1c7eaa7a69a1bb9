"""Attention ops: GQA prefill (causal) and cached decode, XLA reference path.

Shapes follow the grouped-query layout throughout: queries [.., n_kv, q_per_kv,
head_dim] so the KV heads never need materialized repetition (a bf16
``jnp.repeat`` of KV to 32 heads would burn HBM bandwidth for nothing — the
einsum contracts directly against the grouped axis and XLA tiles it onto the
MXU).

Softmax runs in f32 with max-subtraction.  The Pallas flash/paged kernels in
``ops.pallas`` are drop-in replacements for long context on real TPU; these
XLA versions are the correctness reference and the CPU test path.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free

# The one backend whose lowering is Mosaic TPU.  On any other the
# dispatchers take the XLA reference (and say so).
TPU_BACKEND = "tpu"


def kernel_reason(shape_reasons: list[str], interpret: bool) -> str | None:
    """Why a dispatcher does NOT take its kernel, or None when it does:
    the first failed shape gate, else a non-TPU backend (interpret mode —
    reachable only from tests — stands in for the backend)."""
    if shape_reasons:
        return shape_reasons[0]
    backend = jax.default_backend()
    if not interpret and backend != TPU_BACKEND:
        return f"backend={backend}"
    return None


def log_choice(op: str, shape: str, reason: str | None,
               interpret: bool = False) -> None:
    """One line per traced program saying which attention implementation
    it compiled in (dispatchers run at trace time, so a jitted program
    logs once per compiled shape).  chip_smoke.py echoes these lines."""
    impl = ("xla" if reason is not None
            else "pallas-interpret" if interpret else "pallas")
    logger.info("attention dispatch: op=%s impl=%s shape=%s reason=%s",
                op, impl, shape, reason or "supported shape on tpu")


def xla_chunk_attention(
    q: jax.Array,        # [B, C, n_heads, hd] — chunk at contiguous positions
    k_cache: jax.Array,  # [B, S_max, n_kv, hd] — incl. the chunk's own KV
    v_cache: jax.Array,
    start,               # scalar int32: global position of chunk token 0
    window: int = 0,     # > 0: and only positions > start+i - window
) -> jax.Array:
    """Chunk-vs-cache attention reference: chunk token i (global position
    start+i) attends cache positions <= start+i, with ``window`` the last
    ``window`` of them, its own included.  The chunk-stream prefill hot op;
    ``pallas_attention.chunk_attention`` auto-dispatches between this and
    the flash-style kernel.  Returns [B, C, n_heads, hd]."""
    b, c, n_heads, hd = q.shape
    s_max, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = n_heads // n_kv
    qg = q.reshape(b, c, n_kv, g, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bikgh,bjkh->bkgij", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    q_pos = jnp.asarray(start, jnp.int32) + jnp.arange(c)
    behind = q_pos[:, None] - jnp.arange(s_max)[None, :]  # [C, S]
    mask = behind >= 0
    if window:
        mask &= behind < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgij,bjkh->bikgh", probs, v_cache)
    return out.reshape(b, c, n_heads, hd)


# Heads narrower than a vreg's 128 lanes (LFM2: 64).  ``pack`` kv heads share
# one cache row [.., K / pack, pack * hd]: the cache is allocated, written
# and streamed in whole lanes (an [.., 8, 64] bf16 array is tiled with its
# minor dim padded to 128 on the TPU: twice the bytes).  A kernel sees a model
# of K / pack kv heads of pack * hd: query head h, whose kv head h // g lies
# in columns ((h // g) % pack) * hd of its row, goes in with zeros in the
# other heads' columns, so its product with the row is its own head's alone
# and its output's own columns are its own head's values.  The softmax scale
# stays 1 / sqrt(hd): the kernels take it.


def pack_heads(x: jax.Array, pack: int) -> jax.Array:
    """[.., K, hd] -> [.., K / pack, pack * hd] (``pack`` 1: as it is)."""
    if pack == 1:
        return x
    *lead, k, hd = x.shape
    return x.reshape(*lead, k // pack, pack * hd)


def unpack_heads(x: jax.Array, pack: int) -> jax.Array:
    """``pack_heads``'s inverse."""
    if pack == 1:
        return x
    *lead, k, w = x.shape
    return x.reshape(*lead, k * pack, w // pack)


def _own_columns(n_heads: int, n_kv: int, pack: int, dtype) -> jax.Array:
    """[H, pack] one-hot: which head of its packed row a query head reads."""
    sub = (jnp.arange(n_heads) // (n_heads // n_kv)) % pack
    return (sub[:, None] == jnp.arange(pack)[None]).astype(dtype)


def pad_queries(q: jax.Array, n_kv: int, pack: int) -> jax.Array:
    """[.., H, hd] -> [.., H, pack * hd]: each head in its kv head's
    columns of the packed row, zeros elsewhere.  ``n_kv``: the unpacked
    count."""
    if pack == 1:
        return q
    *lead, h, hd = q.shape
    own = _own_columns(h, n_kv, pack, q.dtype)
    return (q[..., None, :] * own[:, :, None]).reshape(*lead, h, pack * hd)


def own_values(out: jax.Array, n_kv: int, pack: int) -> jax.Array:
    """[.., H, pack * hd] -> [.., H, hd]: a padded query's output, its own
    kv head's columns."""
    if pack == 1:
        return out
    *lead, h, w = out.shape
    own = _own_columns(h, n_kv, pack, out.dtype)
    return jnp.sum(out.reshape(*lead, h, pack, w // pack) * own[:, :, None],
                   axis=-2)


def gather_pool_rows(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Paged-pool gather: ``[n_blocks+1, P, ...] x [B, M] -> [B, M*P, ...]``
    — each table row's physical blocks concatenated into the contiguous
    lane view.  Rank-generic (scale pools ``[n_blocks+1, P, K]`` included).
    The ONE definition of the pool->lane read; models/paged.py, the paged
    kernel's fallback, the on-chip check, and the tests all share it."""
    g = pool[tables]  # [B, M, P, ...]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def _grouped(q: jax.Array, n_kv_heads: int) -> jax.Array:
    """[.., n_heads, hd] -> [.., n_kv, q_per_kv, hd]."""
    *lead, n_heads, hd = q.shape
    return q.reshape(*lead, n_kv_heads, n_heads // n_kv_heads, hd)


def prefill_attention(
    q: jax.Array,  # [B, S, n_heads, hd]
    k: jax.Array,  # [B, S, n_kv, hd]
    v: jax.Array,  # [B, S, n_kv, hd]
    positions: jax.Array | None = None,  # [B, S] for packed/padded masking
    window: int = 0,
) -> jax.Array:
    """Causal self-attention over a full prompt.  Returns [B, S, n_heads, hd].

    With ``positions`` given, token i attends to j iff positions[j] <=
    positions[i] AND j <= i — correct for right-padded and left-packed
    batches alike.  ``window`` > 0 (a sliding-window layer): and only iff
    i - j < window, over the indices of a prompt that starts at position 0.
    """
    b, s, n_heads, hd = q.shape
    n_kv = k.shape[2]
    qg = _grouped(q, n_kv)  # [B,S,K,G,hd]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    # [B,K,G,S,S]
    logits = jnp.einsum("bikgh,bjkh->bkgij", qg, k, preferred_element_type=jnp.float32)
    logits *= scale
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    if window:
        causal &= ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
    mask = causal[None, None, None]
    if positions is not None:
        valid = positions[:, None, :] <= positions[:, :, None]  # [B,S_i,S_j]
        mask = mask & valid[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgij,bjkh->bikgh", probs, v)
    return out.reshape(b, s, n_heads, hd)


def latent_decode_attention(
    q: jax.Array,        # [B, n_heads, lanes] — queries absorbed into latents
    rows: jax.Array,     # [B, S_max, lanes] — one layer's latent cache rows
    lengths: jax.Array,  # [B]
    n_values: int,       # leading columns of a row that are its value
    scale: float,
) -> jax.Array:
    """Single-step attention over a latent (MLA) cache, the XLA form: every
    head scores against the SAME row of a position (all its columns) and
    sums the rows' first ``n_values`` columns.  Returns [B, n_heads,
    n_values].  ``pallas_decode_attention.mla_decode_attention`` is the
    kernel."""
    logits = jnp.einsum("bhc,bsc->bhs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(rows.shape[1])[None] < lengths[:, None]
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsc->bhc", probs, rows[..., :n_values])


def decode_attention(
    q: jax.Array,        # [B, n_heads, hd] — one new token per sequence
    k_cache: jax.Array,  # [B, S_max, n_kv, hd]
    v_cache: jax.Array,  # [B, S_max, n_kv, hd]
    lengths: jax.Array,  # [B] valid tokens per sequence (including current)
) -> jax.Array:
    """Single-step cached attention.  Returns [B, n_heads, hd].

    Reads the whole static-shaped cache and masks positions >= lengths —
    no dynamic shapes, so one compilation serves every step.  This read is
    the HBM-bound hot loop of decode; the Pallas paged kernel replaces it
    on TPU for large S_max.
    """
    b, s_max, n_kv, hd = k_cache.shape
    qg = _grouped(q, n_kv)  # [B,K,G,hd]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    logits *= scale
    valid = jnp.arange(s_max)[None] < lengths[:, None]  # [B,S]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache)
    return out.reshape(b, n_kv * (q.shape[1] // n_kv), hd)
