"""Paged KV cache: a shared block pool with per-slot block tables.

vLLM's PagedAttention memory model (the signal model the reference's
KV-threshold routing was tuned against — ``backend/vllm/metrics.go:30``
``gpu_cache_usage_perc`` = allocated blocks / total blocks), restated for
TPU/XLA constraints:

- The pool is ONE static array ``[L, n_blocks, block, Kh, hd]`` — shapes
  never depend on allocation state, so the decode step compiles once.
- Per-slot block tables ``[B, max_blocks_per_seq]`` map logical sequence
  blocks to pool blocks.  Allocation/free is host-side (the engine owns a
  free list); the device only ever sees the table contents change.
- Physical block 0 is reserved as the TRASH block: unallocated table
  entries and inactive rows point at it, so scatters stay in-bounds and
  masked-out garbage has a place to land (no dynamic shapes, no dropped
  scatter semantics to reason about).
- The decode read gathers each row's blocks back into a contiguous
  ``[B, S_max, Kh, hd]`` view (one XLA gather per layer) and reuses the
  exact same masked attention as the contiguous-lane path — so lane/paged
  parity is testable token-for-token.

Contiguous lanes (``transformer.init_decode_cache``) remain the default
fast path: they read the same bytes without the gather.  Paging buys
admission by ACTUAL usage — a pool smaller than ``slots x max_seq`` serves
more concurrent short sequences in the same HBM, with usage_perc telling
the gateway the truth about remaining headroom.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.models.transformer import (
    _attn_out,
    _attn_proj,
    _chunk_attend,
    _embed,
    _kv_dequantize,
    _kv_quantize,
    _layer_lp,
    _layer_xs,
    _lm_head,
    _mlp,
    _n_layers,
    _tallied,
)
from llm_instance_gateway_tpu.ops.attention import (
    decode_attention,
    gather_pool_rows,
)
from llm_instance_gateway_tpu.ops.layers import apply_rope, rms_norm

Params = dict[str, Any]

TRASH_BLOCK = 0  # physical block 0: scatter target for inactive/unallocated


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    n_blocks: int,
    block: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
) -> Params:
    """Block pool + tables.  ``n_blocks`` EXCLUDES the trash block.

    ``quantized`` stores the pools int8 with per-(position, kv-head) f32
    scale pools (vLLM's quantized-paged-KV composition: the HBM halving
    and the admission-by-actual-usage win stack).  Scales index by the
    same physical block as the data, so prefix-cache block reuse — a table
    repoint, never a copy — carries them for free."""
    hd = cfg.resolved_head_dim
    max_blocks_per_seq = -(-max_len // block)
    shape = (cfg.n_layers, n_blocks + 1, block, cfg.n_kv_heads, hd)
    cache = {
        "k": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "v": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "tables": jnp.full((batch, max_blocks_per_seq), TRASH_BLOCK, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if quantized:
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


_gather_rows = gather_pool_rows  # canonical def: ops.attention (shared with
                                 # the paged kernel's fallback and tooling)


@jax.named_scope("attn.kv_update")
def _pool_update(pools: tuple, k: jax.Array, v: jax.Array,
                 phys_block: jax.Array, offset: jax.Array) -> tuple:
    """Scatter freshly-computed bf16 K/V into the layer's pool tuple at
    (phys_block, offset) — the ONE write seam every paged step shares.
    A 4-tuple (k, v, k_scale, v_scale) is a quantized pool: values are
    int8-quantized per (position, kv-head) on the way in."""
    if len(pools) == 4:
        k_pool, v_pool, ks_pool, vs_pool = pools
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        return (k_pool.at[phys_block, offset].set(kq),
                v_pool.at[phys_block, offset].set(vq),
                ks_pool.at[phys_block, offset].set(ks),
                vs_pool.at[phys_block, offset].set(vs))
    k_pool, v_pool = pools
    return (k_pool.at[phys_block, offset].set(k),
            v_pool.at[phys_block, offset].set(v))


def _pool_rows(pools: tuple, tables: jax.Array, dtype=None) -> tuple:
    """Gather each table row's blocks into the contiguous lane view — the
    ONE read seam.  Quantized pools return (k, v) dequantized when ``dtype``
    is given (XLA fuses the multiply into the attention reads, so HBM still
    streams int8), or raw (k, v, k_scale, v_scale) for the int8-aware
    kernel when it is None."""
    if len(pools) == 4:
        rows = tuple(_gather_rows(p, tables) for p in pools)
        if dtype is None:
            return rows
        return (_kv_dequantize(rows[0], rows[2], dtype),
                _kv_dequantize(rows[1], rows[3], dtype))
    return _gather_rows(pools[0], tables), _gather_rows(pools[1], tables)


def decode_step_paged(
    cfg: ModelConfig,
    params: Params,
    cache: Params,           # init_paged_cache layout
    tokens: jax.Array,       # [B] int32
    positions: jax.Array,    # [B] int32
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,
    active: jax.Array | None = None,  # [B] bool — rows allowed to WRITE
):
    """One decode step over the paged pool.

    Semantics identical to ``transformer.decode_step`` (parity-tested); the
    only differences are the scatter address (table-mapped block/offset) and
    the gather-then-attend read.  With ``active`` given, inactive rows
    write the trash block instead of their table-mapped cell — a reserved
    row's table may already hold a mid-stream chunk prompt's real blocks.
    """
    b = tokens.shape[0]
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    block = cache["k"].shape[2]
    tables = cache["tables"]

    h = _embed(cfg, params, tokens)

    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, _ = lora_lib.stack_for_scan(lora_bufs)

    lengths = positions + 1
    batch_idx = jnp.arange(b)
    # Physical write address of each row's current position.  Rows whose
    # table entry is unallocated — and rows the engine marked inactive —
    # write the trash block.
    phys_block = tables[batch_idx, positions // block]  # [B]
    if active is not None:
        phys_block = jnp.where(active, phys_block, TRASH_BLOCK)
    offset = positions % block
    quant = "k_scale" in cache

    scanned, stacks = _layer_xs(params["layers"])

    def layer_fn(h, xs):
        lp, ll, layer, *pools = xs
        lp = _layer_lp(lp, stacks, layer)
        layer_lora = None if ll is None else {**ll, "scale": lora_bufs["scale"]}
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        hd = cfg.resolved_head_dim
        q = _attn_proj(cfg, lp, "q", hn, layer_lora, slot_ids).reshape(b, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", hn, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", hn, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
        k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
        pools = _pool_update(tuple(pools), k, v, phys_block, offset)
        with jax.named_scope("attn.core"):
            if cfg.use_pallas_decode:
                from llm_instance_gateway_tpu.ops.pallas_decode_attention import (
                    paged_decode_attention,
                )

                # DIRECT paged kernel: the block table rides the scalar
                # prefetch and each tile DMAs straight from the pool — no
                # gathered copy of the live cache materializes in HBM (the
                # old read paid gather write + kernel read).  int8 pools
                # stream half the bytes again, scales on the same
                # indirection; its auto-dispatch gathers + falls back
                # off-TPU.
                attn = paged_decode_attention(
                    q, pools[0], pools[1], tables, lengths, *pools[2:])
            else:
                attn = decode_attention(
                    q, *_pool_rows(pools, tables, h.dtype), lengths)
        h = h + _attn_out(lp, attn.reshape(b, -1), layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=active)
        return h + y, (pools, tally)

    xs = (scanned, per_layer_lora, jnp.arange(_n_layers(params)),
          cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    h, (carry, tally) = jax.lax.scan(layer_fn, h, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    new_cache = {"k": carry[0], "v": carry[1], "tables": tables,
                 "length": lengths}
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = carry[2], carry[3]
    return logits, _tallied(cache, new_cache, tally)


def extend_step_paged(
    cfg: ModelConfig,
    params: Params,
    cache: Params,           # init_paged_cache layout
    tokens: jax.Array,       # [B, C] int32 — C new tokens per slot
    positions: jax.Array,    # [B, C] int32 — absolute positions of each
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,
    active: jax.Array | None = None,  # [B] bool — rows allowed to WRITE
):
    """Multi-token cached decode over the paged pool — the speculative
    verify/catch-up primitive (parity contract: ``transformer.extend_step``,
    tested token-for-token).  Each row's C tokens scatter through its block
    table and attend to the row's gathered view, causal within the new
    tokens and over the lane's history.  Positions past the table span —
    and every position of a row the engine marked inactive — route to the
    trash block (same rule as ``prefill_with_cache_paged``).
    Returns (logits [B, C, V] f32, new cache).
    """
    b, c = tokens.shape
    hd = cfg.resolved_head_dim
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    block = cache["k"].shape[2]
    tables = cache["tables"]
    max_blocks = tables.shape[1]
    s_max = max_blocks * block
    batch_idx = jnp.arange(b)[:, None]  # [B, 1] broadcast over C

    writable = positions < s_max
    if active is not None:
        writable = writable & active[:, None]
    phys_block = jnp.where(
        writable,
        tables[batch_idx, jnp.clip(positions // block, 0, max_blocks - 1)],
        TRASH_BLOCK,
    )  # [B, C]
    offset = positions % block

    h = _embed(cfg, params, tokens)  # [B, C, D]
    live = None if active is None else jnp.broadcast_to(active[:, None], (b, c))

    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, _ = lora_lib.stack_for_scan(lora_bufs)

    quant = "k_scale" in cache

    scanned, stacks = _layer_xs(params["layers"])

    def layer_fn(h, xs):
        lp, ll, layer, *pools = xs
        lp = _layer_lp(lp, stacks, layer)
        layer_lora = None if ll is None else {**ll, "scale": lora_bufs["scale"]}
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(cfg, lp, "q", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        pools = _pool_update(tuple(pools), k, v, phys_block, offset)
        with jax.named_scope("attn.core"):
            # Quantized pools dequant at the gathered view: XLA fuses the
            # multiply into the attention reads, so HBM still streams int8.
            k_rows, v_rows = _pool_rows(pools, tables, h.dtype)
            qg = q.reshape(b, c, cfg.n_kv_heads, cfg.q_per_kv, hd)
            logits = jnp.einsum(
                "bikgh,bjkh->bkgij", qg, k_rows,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(hd).astype(jnp.float32)
            mask = jnp.arange(s_max)[None, None, :] <= positions[:, :, None]
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
            attn = jnp.einsum(
                "bkgij,bjkh->bikgh", probs, v_rows).reshape(b, c, -1)
        h = h + _attn_out(lp, attn, layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live)
        return h + y, (pools, tally)

    xs = (scanned, per_layer_lora, jnp.arange(_n_layers(params)),
          cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    h, (carry, tally) = jax.lax.scan(layer_fn, h, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    new_cache = {"k": carry[0], "v": carry[1], "tables": tables,
                 "length": positions[:, -1] + 1}
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = carry[2], carry[3]
    return logits, _tallied(cache, new_cache, tally)


@jax.named_scope("kv.insert")
def insert_prefill_paged(
    cache: Params,
    k_prompt: jax.Array,   # [L, 1, S_bucket, Kh, hd] from prefill
    v_prompt: jax.Array,
    row: jax.Array | int,          # decode-slot row owning the table entries
    phys_blocks: jax.Array,        # [ceil(S_bucket/block)] int32 — pool
                                   # blocks for this prompt (trash-padded)
    table_row: jax.Array,          # [max_blocks_per_seq] int32 — the row's
                                   # FULL new table (allocated + trash tail)
    length: jax.Array | int,
) -> Params:
    """Insert a prefilled prompt's KV into allocated pool blocks.

    The prompt KV is reshaped to whole blocks and written with one scatter
    per pool array; the trailing partial block carries bucket padding into
    a real block (masked by ``length``), and any wholly-padding blocks are
    directed at the trash block by the engine.
    """
    lyr, _, s, kh, hd = k_prompt.shape
    block = cache["k"].shape[2]
    n_b = phys_blocks.shape[0]
    pad = n_b * block - s
    if pad:
        padding = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
        k_prompt = jnp.pad(k_prompt, padding)
        v_prompt = jnp.pad(v_prompt, padding)
    if "k_scale" in cache:
        # Quantize at the insert seam (the prefill computes bf16 KV): one
        # scale per (layer, position, kv-head), scattered into the scale
        # pools at the same physical blocks as the data.
        kq, ks = _kv_quantize(k_prompt)  # [L,1,S',Kh,hd] -> [L,1,S',Kh]
        vq, vs = _kv_quantize(v_prompt)
        kb = kq.reshape(lyr, n_b, block, kh, hd)
        vb = vq.reshape(lyr, n_b, block, kh, hd)
        k = cache["k"].at[:, phys_blocks].set(kb)
        v = cache["v"].at[:, phys_blocks].set(vb)
        k_scale = cache["k_scale"].at[:, phys_blocks].set(
            ks.reshape(lyr, n_b, block, kh))
        v_scale = cache["v_scale"].at[:, phys_blocks].set(
            vs.reshape(lyr, n_b, block, kh))
        tables = cache["tables"].at[row].set(table_row)
        length_vec = cache["length"].at[row].set(length)
        return {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale,
                "tables": tables, "length": length_vec}
    kb = k_prompt.reshape(lyr, n_b, block, kh, hd)
    vb = v_prompt.reshape(lyr, n_b, block, kh, hd)
    k = cache["k"].at[:, phys_blocks].set(kb.astype(cache["k"].dtype))
    v = cache["v"].at[:, phys_blocks].set(vb.astype(cache["v"].dtype))
    tables = cache["tables"].at[row].set(table_row)
    length_vec = cache["length"].at[row].set(length)
    return {"k": k, "v": v, "tables": tables, "length": length_vec}


def prefill_with_cache_paged(
    cfg: ModelConfig,
    params: Params,
    cache: Params,
    tokens: jax.Array,      # [C] int32 — one chunk for ONE row
    positions: jax.Array,   # [C] int32 — absolute positions
    row: jax.Array,         # scalar int32 — decode-slot row
    lane_end: jax.Array,    # scalar int32 — valid tokens after this chunk
    last_index: jax.Array,  # scalar int32 — chunk index of last REAL token
    lora_bufs: Params | None = None,
    lora_slot: jax.Array | int = -1,
):
    """Chunked prefill against the paged pool (parity with
    ``transformer.prefill_with_cache``): chunk K/V scatter through the row's
    block table; chunk queries attend to the row's gathered view."""
    c = tokens.shape[0]
    hd = cfg.resolved_head_dim
    block = cache["k"].shape[2]
    tables = cache["tables"]
    max_blocks = tables.shape[1]
    s_max = max_blocks * block
    slot_ids = jnp.full((1,), lora_slot, jnp.int32)

    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, _ = lora_lib.stack_for_scan(lora_bufs)

    table_row = jax.lax.dynamic_index_in_dim(tables, row, 0, keepdims=False)
    # Final-chunk pads can run past s_max: the lane path's scatter drops
    # them (OOB), but a clipped table lookup would alias the row's LAST
    # real block — route them to the trash block instead.
    in_bounds = positions < s_max
    phys_block = jnp.where(
        in_bounds,
        table_row[jnp.clip(positions // block, 0, max_blocks - 1)],
        TRASH_BLOCK,
    )  # [C]
    offset = positions % block

    h = _embed(cfg, params, tokens)[None]
    pos2d = positions[None]
    live = (jnp.arange(c) <= last_index)[None]  # the final chunk's padding

    quant = "k_scale" in cache

    scanned, stacks = _layer_xs(params["layers"])

    def layer_fn(h, xs):
        lp, ll, layer, *pools = xs
        lp = _layer_lp(lp, stacks, layer)
        layer_lora = None if ll is None else {**ll, "scale": lora_bufs["scale"]}
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(cfg, lp, "q", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        q = apply_rope(q, pos2d, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, pos2d, cfg.rope_theta, cfg.rope_scaling)
        pools = _pool_update(tuple(pools), k[0], v[0], phys_block, offset)
        lane_k, lane_v = (r[0] for r in
                          _pool_rows(pools, table_row[None], h.dtype))
        # Flash-style chunk attend over the gathered lane view (shared
        # dispatch incl. the quant gate: transformer._chunk_attend).
        attn = _chunk_attend(cfg, quant, q, lane_k, lane_v, positions[0])
        h = h + _attn_out(lp, attn, layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live)
        return h + y, (pools, tally)

    xs = (scanned, per_layer_lora, jnp.arange(_n_layers(params)),
          cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    h, (carry, tally) = jax.lax.scan(layer_fn, h, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    last_h = jax.lax.dynamic_index_in_dim(h[0], last_index, 0, keepdims=False)
    last_logits = _lm_head(cfg, params, last_h)
    length_vec = cache["length"].at[row].set(lane_end)
    new_cache = {"k": carry[0], "v": carry[1], "tables": tables,
                 "length": length_vec}
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = carry[2], carry[3]
    return last_logits, _tallied(cache, new_cache, tally)
