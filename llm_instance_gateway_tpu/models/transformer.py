"""Core decoder-only transformer: one implementation, three families.

Covers Llama-3 (RoPE+GQA+SwiGLU), Gemma (tied embeddings, sqrt(d) embedding
scale, GeLU gate, (1+w) RMSNorm, shared KV head) and Mixtral (top-k MoE MLP)
via ``ModelConfig`` flags — the families the pool configs in BASELINE.json
serve.

TPU-first structure:
- Parameters are stacked over layers (``[n_layers, ...]`` leaves) and the
  forward runs ``lax.scan`` over them: one layer gets traced/compiled once,
  not n_layers times, and pjit shards every layer identically.
- The decode path is a fixed-shape step function: batch = the engine's decode
  slots, cache = ``[n_layers, B, S_max, n_kv, hd]``; one compilation serves
  the entire serving lifetime (XLA recompile storms are the TPU-serving
  failure mode the design avoids, SURVEY.md §7).
- bfloat16 params/activations, f32 softmax/norms, f32 logits.
- Multi-LoRA deltas (``models.lora``) apply to every projection with per-row
  slot ids, so one decode batch multiplexes adapters + base model.
- Every block sits in a ``jax.named_scope`` (embed, attn.qkv, attn.rope,
  attn.kv_update, attn.core, attn.out, mlp, moe.route / .dispatch /
  .experts / .fallback, lora, lm_head, kv.insert): the scope is in each
  compiled operation's name, so a device trace says which line of this file
  an operation belongs to.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.ops.attention import (
    decode_attention,
    log_choice,
    prefill_attention,
    xla_chunk_attention,
)
from llm_instance_gateway_tpu.ops.layers import apply_rope, rms_norm, swiglu
from llm_instance_gateway_tpu.ops.quant import (
    QUANT_TARGETS,
    constrain,
    expert_matmul,
    expert_mix,
    expert_mix_down,
    matmul as q_matmul,
    quantize_weight,
    static_sharding,
)

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("shape", "fan_in", "dtype", "quant", "sharding"))
def _draw_leaf(key, *, shape, fan_in, dtype, quant, sharding):
    """One random weight leaf, ``normal / sqrt(fan_in)`` (``fan_in`` None:
    the embedding's ``normal * 0.02``).  A stacked ``[L, ...]`` leaf
    (rank >= 3) is drawn one layer at a time, so the f32 draw and the
    int8 quantization's f32 view are one layer's, never the stack's.
    Module-level with static arguments so repeated inits (the test suite)
    hit jit's cache."""
    def draw(k, shp):
        w = jax.random.normal(k, shp, jnp.float32)
        w = (w * 0.02 if fan_in is None else w / jnp.sqrt(fan_in))
        w = w.astype(dtype)
        return quantize_weight(w) if quant else w

    if len(shape) >= 3:
        out = jax.lax.map(lambda k: draw(k, shape[1:]),
                          jax.random.split(key, shape[0]))
    else:
        out = draw(key, shape)
    return constrain(out, sharding)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                quantize: bool = False, shardings: Params | None = None,
                ) -> Params:
    """Seeded random weights, built so that start-up fits where steady
    state fits.

    Each leaf is its own program that draws ONE LAYER at a time
    (``_draw_leaf``): at Qwen2.5-7B widths ``w_gate`` alone is 7.6 GB as an
    f32 stack.  ``quantize`` int8-quantizes the ``ops.quant`` targets (and
    ``lm_head``) inside the same per-layer program, so the bf16 tree (15 GB
    at those widths) never exists either.  ``shardings``
    (``parallel.sharding.param_shardings``, built with the same
    ``quantize``) constrains every program's output: on a mesh no leaf is
    ever whole on one device.
    """
    hd = cfg.resolved_head_dim
    d, f, v = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    n_l = cfg.n_layers
    keys = iter(jax.random.split(key, 16))
    dtype = jnp.dtype(dtype)
    layer_sh = None if shardings is None else shardings["layers"]

    def rand(tree_sh, name, shape, fan_in):
        quant = quantize and (name in QUANT_TARGETS or name == "lm_head")
        sh = None if tree_sh is None else tree_sh[name]
        return _draw_leaf(next(keys), shape=shape, fan_in=fan_in,
                          dtype=dtype, quant=quant,
                          sharding=static_sharding(sh))

    def const(tree_sh, name, fill, shape):
        x = jnp.full(shape, fill, dtype)
        return x if tree_sh is None else jax.device_put(x, tree_sh[name])

    layers: Params = {
        "attn_norm": const(layer_sh, "attn_norm", 1, (n_l, d)),
        "mlp_norm": const(layer_sh, "mlp_norm", 1, (n_l, d)),
        "wq": rand(layer_sh, "wq", (n_l, d, cfg.n_heads * hd), d),
        "wk": rand(layer_sh, "wk", (n_l, d, cfg.n_kv_heads * hd), d),
        "wv": rand(layer_sh, "wv", (n_l, d, cfg.n_kv_heads * hd), d),
        "wo": rand(layer_sh, "wo", (n_l, cfg.n_heads * hd, d),
                   cfg.n_heads * hd),
    }
    if cfg.attention_bias:
        # Qwen2-family Q/K/V biases (zero init; checkpoints overwrite).
        layers["wq_b"] = const(layer_sh, "wq_b", 0, (n_l, cfg.n_heads * hd))
        layers["wk_b"] = const(layer_sh, "wk_b", 0,
                               (n_l, cfg.n_kv_heads * hd))
        layers["wv_b"] = const(layer_sh, "wv_b", 0,
                               (n_l, cfg.n_kv_heads * hd))
    if cfg.n_experts:
        e = cfg.n_experts
        layers["router"] = rand(layer_sh, "router", (n_l, d, e), d)
        layers["w_gate"] = rand(layer_sh, "w_gate", (n_l, e, d, f), d)
        layers["w_up"] = rand(layer_sh, "w_up", (n_l, e, d, f), d)
        layers["w_down"] = rand(layer_sh, "w_down", (n_l, e, f, d), f)
    else:
        layers["w_gate"] = rand(layer_sh, "w_gate", (n_l, d, f), d)
        layers["w_up"] = rand(layer_sh, "w_up", (n_l, d, f), d)
        layers["w_down"] = rand(layer_sh, "w_down", (n_l, f, d), f)

    params: Params = {
        "embed": rand(shardings, "embed", (v, d), None),
        "layers": layers,
        "final_norm": const(shardings, "final_norm", 1, (d,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(shardings, "lm_head", (d, v), d)
    return params


def init_decode_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
    quantized: bool = False,
) -> Params:
    """Contiguous-lane decode cache.  ``quantized`` stores K/V as int8 with
    per-(position, kv-head) f32 scales — decode at long context is bound by
    streaming the KV from HBM, and int8 halves that traffic vs bf16 (the
    JetStream serving trade); the dequantize multiply fuses into the
    attention reads, so HBM sees int8 while the MXU computes in ``dtype``.
    Scale overhead is 1/(2*head_dim) of the bf16 cache."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    cache = {
        "k": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "v": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if quantized:
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


def _kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., hd] -> (int8 [..., hd], f32 scale [...]): symmetric per-vector
    max-abs quantization (one scale per position per kv-head)."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def _kv_dequantize(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    return q.astype(dtype) * s[..., None].astype(dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _project(x, w, layer_lora, target, slot_ids):
    """x @ w plus the per-row LoRA delta for ``target`` (w may be int8)."""
    out = q_matmul(x, w)
    if layer_lora is not None:
        out = out + lora_lib.lora_delta(
            x,
            layer_lora[f"{target}_a"],
            layer_lora[f"{target}_b"],
            layer_lora["scale"],
            slot_ids,
        )
    return out


@jax.named_scope("attn.qkv")
def _attn_proj(lp, target, x, layer_lora, slot_ids):
    """Q/K/V projection with the optional attention bias (Qwen2-family:
    ``attention_bias`` adds learned biases to q/k/v only).  The bias keys
    exist in the layer params iff the config declares them, so bias-free
    models trace exactly the code they always did."""
    out = _project(x, lp[f"w{target}"], layer_lora, target, slot_ids)
    b = lp.get(f"w{target}_b")
    return out if b is None else out + b


@jax.named_scope("attn.out")
def _attn_out(lp, attn, layer_lora, slot_ids):
    """The attention block's output projection."""
    return _project(attn, lp["wo"], layer_lora, "o", slot_ids)


@jax.named_scope("embed")
def _embed(cfg: ModelConfig, params: Params, tokens):
    """Token embeddings; the activation dtype follows the param dtype."""
    h = params["embed"][tokens]
    if cfg.embedding_scale:
        h = h * jnp.sqrt(cfg.d_model).astype(h.dtype)
    return h


@jax.named_scope("lm_head")
def _lm_head(cfg: ModelConfig, params: Params, h):
    """Output head matmul (tied or separate) to f32 logits."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return q_matmul(h, head).astype(jnp.float32)


@jax.named_scope("attn.core")
def _chunk_attend(cfg: ModelConfig, quant: bool, q, lane_k, lane_v, start):
    """Chunk-vs-lane attention dispatch, shared by the lane and paged
    chunk-stream paths.  Flash-style kernel (XLA off-TPU/odd shapes, logged
    by the dispatcher) unless the lane was dequantized from an int8 cache —
    an opaque kernel can't fuse the dequant into its reads and would
    materialize a bf16 copy, so quantized lanes keep the fused XLA path
    (same reasoning as the decode-path quant gate).  Returns [1, C, H*hd]."""
    from llm_instance_gateway_tpu.ops import pallas_attention

    c = q.shape[1]
    if cfg.use_flash_attention and not quant:
        return pallas_attention.chunk_attention(
            q, lane_k[None], lane_v[None], start).reshape(1, c, -1)
    log_choice(
        "chunk_attend", f"q{tuple(q.shape)} lane{tuple(lane_k.shape)}",
        "int8 lane: the dequant fuses into the XLA reads" if quant
        else "use_flash_attention=False")
    return xla_chunk_attention(q, lane_k[None], lane_v[None],
                               start).reshape(1, c, -1)


def _mlp(cfg: ModelConfig, lp: Params, x, layer_lora, slot_ids):
    if cfg.n_experts:
        return _moe_mlp(cfg, lp, x)
    with jax.named_scope("mlp"):
        gate = _project(x, lp["w_gate"], layer_lora, "gate", slot_ids)
        up = _project(x, lp["w_up"], layer_lora, "up", slot_ids)
        return _project(swiglu(gate, up, cfg.gelu_mlp), lp["w_down"],
                        layer_lora, "down", slot_ids)


def _moe_mlp(cfg: ModelConfig, lp: Params, x):
    """Top-k mixture-of-experts MLP (Mixtral style).

    Two shape-static strategies, chosen at TRACE time by token count:

    - a batch too small for the capacity tile to beat dense (cap >= T —
      single-token decode): dense all-experts mix, where the dispatch
      bookkeeping would be pure overhead and weights (not FLOPs) bound
      the step anyway;
    - everything else — batched decode included: GShard-style grouped
      capacity dispatch (``_moe_grouped``) — per-token FLOPs drop from E
      to ~k*capacity_factor expert-MLPs, with a dense lax.cond fallback
      keeping results bit-exact when routing overflows capacity.

    LoRA is not applied to expert weights (matching vLLM, which targets
    attention + dense MLP only).
    """
    t = 1
    for dim in x.shape[:-1]:
        t *= dim
    cap = _moe_capacity(cfg, t)
    if cap >= t or (cfg.moe_exact_fallback and cap < 8):
        # Dense all-experts costs t*E expert-rows; grouped costs E*cap.
        # cap >= t means no FLOP win — and at these token counts decode is
        # weight-bound anyway (each expert's weights stream from HBM once
        # either way), so the dispatch bookkeeping would be pure overhead.
        # Exact mode additionally floors at cap >= 8: a 1-4 row tile is
        # discreteness-dominated (routine routing collisions overflow it —
        # the 2x headroom's overflow-rarity argument needs a few rows of
        # mean load), and every exact-mode overflow pays grouped PLUS
        # dense, costlier than just staying dense.  Dropping mode keeps
        # grouped at any tile (overflow drops, the standard serving trade).
        return _moe_dense(cfg, lp, x)
    return _moe_grouped(cfg, lp, x)


def _moe_capacity(cfg: ModelConfig, t: int) -> int:
    """Per-expert capacity tile for ``t`` tokens.

    cap ≈ t*k/E * factor.  Dropping mode uses the configured factor as-is
    (1.25 default — the standard GShard serving trade: a 16-slot Mixtral
    decode computes ~1.25x the dropless-ideal t*k expert-rows); EXACT mode
    takes at least 2.0x at every size, because its overflow fallback pays
    grouped PLUS dense for the batch and a tight tile overflows on routine
    router imbalance (still ~2x better than the dense path it falls back
    to).  Small tiles keep the exact ceiling — rounding 5 up to 8 would
    re-inflate the small-batch win; large tiles round up to a multiple of
    8 (MXU sublane alignment).
    """
    e, k = cfg.n_experts, cfg.n_experts_per_token
    f = cfg.moe_capacity_factor
    if cfg.moe_exact_fallback:
        # The overflow fallback pays grouped PLUS dense (expert weights
        # streamed twice), so it must stay rare at EVERY tile size — a
        # 16-slot decode tile at 1.25x mean load would overflow on most
        # batches.  2.0x puts overflow ~2.7 sigma out under uniform
        # routing; dropping mode uses the configured factor as-is.
        f = max(f, 2.0)
    cap = int(-(-t * k * f // e))
    if cap >= 16:
        cap = (cap + 7) // 8 * 8
    return min(t, cap)


def _moe_dense(cfg: ModelConfig, lp: Params, x):
    """Compute every expert; mix by renormalized top-k gates."""
    with jax.named_scope("moe.route"):
        router_logits = (x @ lp["router"]).astype(jnp.float32)  # [..., E]
        e = cfg.n_experts
        topv, topi = jax.lax.top_k(router_logits, cfg.n_experts_per_token)
        # Renormalize over the selected experts.
        gates = jax.nn.softmax(topv, axis=-1)
        # Scatter gate weights back to a dense [..., E] mix vector.
        dense_gates = jnp.sum(
            jax.nn.one_hot(topi, e, dtype=jnp.float32) * gates[..., None],
            axis=-2,
        )  # [..., E]
    with jax.named_scope("moe.experts"):
        hidden = expert_mix(x, lp["w_gate"])
        up = expert_mix(x, lp["w_up"])
        act = swiglu(hidden, up, cfg.gelu_mlp)
        per_expert = expert_mix_down(act, lp["w_down"])
        return jnp.einsum("...ed,...e->...d", per_expert,
                          dense_gates.astype(x.dtype))


def _moe_grouped(cfg: ModelConfig, lp: Params, x):
    """Grouped capacity dispatch: route tokens TO experts instead of running
    every expert over every token.

    Each token's k assignments scatter-add into per-expert capacity tiles
    ([E, C, D], O(T*k*D) data movement — NOT a [T,k,E,C] one-hot einsum,
    whose T*k*E*C*D cost would swamp the savings); three batched einsums
    run each expert's MLP over its C-row tile (MXU-shaped, shardable over
    the ``expert`` mesh axis); a gather + gate-weighted sum combines
    results.  Expert capacity C ≈ T*k/E * capacity_factor (``_moe_capacity``
    — exact ceiling for small tiles, multiple of 8 with exact-mode headroom
    for large ones): expert FLOPs scale with assignments made, not
    experts*tokens —
    the E/k inflation of the dense path is gone.  If any expert overflows
    C, ``moe_exact_fallback`` recomputes the batch densely inside lax.cond
    (exactness over speed for that batch).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    e, k = cfg.n_experts, cfg.n_experts_per_token

    with jax.named_scope("moe.route"):
        router_logits = (xf @ lp["router"]).astype(jnp.float32)  # [T, E]
        topv, topi = jax.lax.top_k(router_logits, k)
        gates = jax.nn.softmax(topv, axis=-1)  # [T, k]

    cap = _moe_capacity(cfg, t)

    with jax.named_scope("moe.dispatch"):
        flat_expert = topi.reshape(-1)  # [T*k]
        flat_assign = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)
        # Position of each assignment within its expert's capacity tile.
        pos = jnp.sum(
            (jnp.cumsum(flat_assign, axis=0) - 1) * flat_assign, axis=-1)
        kept = pos < cap  # [T*k]
        # Overflowed assignments clip onto the last tile row with a zeroed
        # contribution — collisions there add 0, and the combine gather
        # masks them out the same way.
        flat_idx = flat_expert * cap + jnp.clip(pos, 0, cap - 1)  # [T*k]
        keep_col = kept[:, None].astype(xf.dtype)

        xk = jnp.repeat(xf, k, axis=0)  # [T*k, D] (token order: topi's)
        x_e = (
            jnp.zeros((e * cap, d), xf.dtype)
            .at[flat_idx].add(xk * keep_col)
            .reshape(e, cap, d)
        )
    with jax.named_scope("moe.experts"):
        hidden = expert_matmul(x_e, lp["w_gate"])
        up = expert_matmul(x_e, lp["w_up"])
        act = swiglu(hidden, up, cfg.gelu_mlp)
        out_e = expert_matmul(act, lp["w_down"])
    with jax.named_scope("moe.dispatch"):  # the way back: gather + mix
        gathered = out_e.reshape(e * cap, d)[flat_idx] * keep_col  # [T*k, D]
        y = jnp.sum(
            gathered.reshape(t, k, d) * gates.astype(xf.dtype)[..., None],
            axis=1,
        )

    if cfg.moe_exact_fallback:
        with jax.named_scope("moe.fallback"):
            overflow = jnp.any(~kept)
            y = jax.lax.cond(
                overflow, lambda op: _moe_dense(cfg, lp, op), lambda _: y, xf
            )
    return y.reshape(orig_shape)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill_layer(
    cfg: ModelConfig,
    lp: Params,              # one layer's params (leaves without the L dim)
    h: jax.Array,            # [B, S, D]
    positions: jax.Array,    # [B, S] int32
    layer_lora: Params | None = None,
    slot_ids: jax.Array | None = None,  # [B] int32, -1 = base model
    attention_fn=None,
):
    """One decoder block over a full sequence.  Returns (h, (k, v)).

    The single source of truth for the prefill block: ``prefill`` scans it
    over the stacked layer params, and ``parallel.pipeline`` scans each
    stage's slice of the stack inside the pipelined schedule.
    """
    b, s, _ = h.shape
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    hd = cfg.resolved_head_dim
    q = _attn_proj(lp, "q", hn, layer_lora, slot_ids).reshape(b, s, cfg.n_heads, hd)
    k = _attn_proj(lp, "k", hn, layer_lora, slot_ids).reshape(b, s, cfg.n_kv_heads, hd)
    v = _attn_proj(lp, "v", hn, layer_lora, slot_ids).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    with jax.named_scope("attn.core"):
        if attention_fn is not None:
            attn = attention_fn(q, k, v, positions)
        elif cfg.use_flash_attention:
            # Right-padded batches: causal tiling alone keeps real positions
            # exact (pallas_attention.flash_attention docstring).
            from llm_instance_gateway_tpu.ops.pallas_attention import (
                flash_attention,
            )

            attn = flash_attention(q, k, v)
        else:
            attn = prefill_attention(q, k, v, positions)
    h = h + _attn_out(lp, attn.reshape(b, s, -1), layer_lora, slot_ids)
    hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    h = h + _mlp(cfg, lp, hn2, layer_lora, slot_ids)
    return h, (k, v)


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,       # [B, S] int32
    positions: jax.Array,    # [B, S] int32 (right-padded prompts: 0..len-1)
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,  # [B] int32, -1 = base model
    attention_fn=None,       # override: (q, k, v, positions) -> attn output
):
    """Full-prompt forward.  Returns (logits [B,S,V] f32, k [L,B,S,K,hd], v).

    ``attention_fn`` swaps the attention implementation — used by
    ``parallel.long_context`` to run ring attention over a sequence-sharded
    mesh for prompts that exceed one device's budget.
    """
    b, s = tokens.shape
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)

    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, bcast = lora_lib.stack_for_scan(lora_bufs)

    def layer_fn(h, xs):
        lp, ll = xs
        layer_lora = None if ll is None else {**ll, "scale": lora_bufs["scale"]}
        return prefill_layer(
            cfg, lp, h, positions, layer_lora=layer_lora, slot_ids=slot_ids,
            attention_fn=attention_fn,
        )

    xs = (params["layers"], per_layer_lora)
    h, (k_all, v_all) = jax.lax.scan(layer_fn, h, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    return logits, k_all, v_all


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


# The cached programs (decode_step, extend_step, prefill_with_cache) keep
# the stacked cache in ONE buffer for the whole step: it is the layer
# loop's CARRY, each layer writes its new rows into it with one scatter at
# [layer, row, position], and attention reads that layer out of it.  A
# cache scanned as xs/ys cannot be the donated buffer: XLA then copies the
# whole cache twice a step and every layer's slice out and back in (device
# trace, PR 24: 25-31 ms of a 61 ms step; the structure is held by
# tests/test_models.py::test_layer_scan_carries_the_cache).


def _kv_carry(cache: Params) -> tuple:
    """The stacked cache arrays that ride the layer loop: (k, v)
    [L, B, S, K, hd], plus (k_scale, v_scale) [L, B, S, K] of an int8
    cache."""
    kv = (cache["k"], cache["v"])
    if "k_scale" in cache:
        kv += (cache["k_scale"], cache["v_scale"])
    return kv


def _cache_of(kv: tuple, length: jax.Array) -> Params:
    return dict(zip(("k", "v", "k_scale", "v_scale"), kv), length=length)


@jax.named_scope("attn.kv_update")
def _write_kv(kv: tuple, at: tuple, k: jax.Array, v: jax.Array) -> tuple:
    """Write one layer's new rows into the stacked cache where it lies.
    ``at`` = (layer, rows, positions), the scatter address of ``k``/``v``'s
    leading dims; an address out of bounds drops its update (how an
    inactive row writes nothing).  An int8 cache quantizes here."""
    new = (k, v)
    if len(kv) == 4:
        (kq, ks), (vq, vs) = _kv_quantize(k), _kv_quantize(v)
        new = (kq, vq, ks, vs)
    return tuple(x.at[at].set(n) for x, n in zip(kv, new))


def _layer_arrays(kv: tuple, layer) -> tuple:
    """One layer of every array of the carry: slices XLA may materialise,
    for the attention paths that must be right and need not be fast.  The
    decode kernel takes the carry itself (``_decode_attend``)."""
    return tuple(
        jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False) for x in kv)


def _layer_view(kv: tuple, layer, dtype) -> tuple[jax.Array, jax.Array]:
    """One layer's (k, v) [B, S, K, hd] out of the carry, dequantized."""
    k, v, *scales = _layer_arrays(kv, layer)
    if scales:
        return (_kv_dequantize(k, scales[0], dtype),
                _kv_dequantize(v, scales[1], dtype))
    return k, v


def _scan_cached_layers(params: Params, cache: Params,
                        lora_bufs: Params | None, h: jax.Array, layer_fn):
    """The cached programs' layer loop.  xs: the stacked layer params, the
    LoRA stack and the layer index; carry: the activations and the stacked
    cache.  ``layer_fn(h, kv, layer, lp, layer_lora) -> (h, kv)``."""
    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, _ = lora_lib.stack_for_scan(lora_bufs)

    def body(carry, xs):
        lp, ll, layer = xs
        layer_lora = None if ll is None else {**ll, "scale": lora_bufs["scale"]}
        return layer_fn(*carry, layer, lp, layer_lora), None

    xs = (params["layers"], per_layer_lora, jnp.arange(cache["k"].shape[0]))
    (h, kv), _ = jax.lax.scan(body, (h, _kv_carry(cache)), xs)
    return h, kv


@jax.named_scope("attn.core")
def _decode_attend(cfg: ModelConfig, attention_fn, q, kv, layer, lengths):
    """One layer's cached attention over the lanes just written: which
    implementation reads them.  The kernel reads ``layer`` of the stacked
    carry in place; everything else gets that layer's view."""
    quant = len(kv) == 4
    if attention_fn is None and cfg.use_pallas_decode:
        from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda

        # The int8-aware kernel dequantizes in VMEM at the MXU feed, so HBM
        # streams half the bytes of the bf16 kernel.  (Both fall back to
        # XLA by themselves off-TPU and on unsupported shapes.)
        attend = pda.decode_attention_quant if quant else pda.decode_attention
        return attend(q, *kv, lengths, layer=layer)
    if quant and getattr(attention_fn, "quant_aware", False):
        # Quant-aware override (sharded_attention.make_cached_decode_quant):
        # raw int8 + scales go in; each shard's kernel dequantizes in VMEM,
        # so HBM streams int8 even under the mesh — kernel win and
        # bandwidth win together.
        return attention_fn(q, *_layer_arrays(kv, layer), lengths)
    # XLA attention, or an override without quant awareness: the (dequantized)
    # view.  NOTE — an opaque override cannot fuse the dequant into its reads
    # and materializes a full bf16 cache; the engine only installs
    # quant_aware wrappers on quantized lanes for exactly that reason.
    k_cache, v_cache = _layer_view(kv, layer, q.dtype)
    return (attention_fn or decode_attention)(q, k_cache, v_cache, lengths)


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Params,           # init_decode_cache layout
    tokens: jax.Array,       # [B] int32 — current token per slot
    positions: jax.Array,    # [B] int32 — position of ``tokens``
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,
    attention_fn=None,       # override: (q, k_cache, v_cache, lengths) -> attn
    active: jax.Array | None = None,  # [B] bool — rows allowed to WRITE
):
    """One decode step for every slot.  Returns (logits [B,V] f32, new cache).

    Inactive slots decode garbage LOGITS (masked out by the engine) but —
    with ``active`` given — write NOTHING: their scatter index is pushed
    out of bounds, where XLA drops the update.  Without the mask a frozen
    or empty row keeps stomping its lane at a stale position, which is
    fatal once a lane can be mid-chunk-stream for a DIFFERENT request
    while decode dispatches run (the concurrent-lane engine); lockstep
    batching keeps the step shape-static either way.

    ``attention_fn`` swaps the cached-attention implementation — used by
    ``ops.sharded_attention`` to run the Pallas decode kernel shard-local
    under a GSPMD mesh.
    """
    b = tokens.shape[0]
    hd = cfg.resolved_head_dim
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)  # [B, D]

    lengths = positions + 1
    batch_idx = jnp.arange(b)
    s_max = cache["k"].shape[2]
    # Scatter address only — rope/masks keep the true positions.  s_max is
    # out of bounds, so inactive rows' updates are dropped whole.
    write_pos = (positions if active is None
                 else jnp.where(active, positions, s_max))

    def layer_fn(h, kv, layer, lp, layer_lora):
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(lp, "q", hn, layer_lora, slot_ids).reshape(b, cfg.n_heads, hd)
        k = _attn_proj(lp, "k", hn, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        v = _attn_proj(lp, "v", hn, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
        k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
        kv = _write_kv(kv, (layer, batch_idx, write_pos), k, v)
        attn = _decode_attend(cfg, attention_fn, q, kv, layer, lengths)
        h = h + _attn_out(lp, attn.reshape(b, -1), layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        h = h + _mlp(cfg, lp, hn2, layer_lora, slot_ids)
        return h, kv

    h, kv = _scan_cached_layers(params, cache, lora_bufs, h, layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    return logits, _cache_of(kv, lengths)


def extend_step(
    cfg: ModelConfig,
    params: Params,
    cache: Params,           # init_decode_cache layout
    tokens: jax.Array,       # [B, C] int32 — C new tokens per slot
    positions: jax.Array,    # [B, C] int32 — absolute positions of each
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,
    active: jax.Array | None = None,  # [B] bool — rows allowed to WRITE
):
    """Multi-token cached decode: process C new tokens per slot in ONE
    forward (the speculative-decoding verify/catch-up primitive — decode is
    HBM-weight-bound, so scoring C tokens costs barely more than one).

    Each row's tokens scatter into its own cache lane at ``positions`` and
    attend to every cached position <= their own — causal within the new
    tokens and over the lane's history.  Rows are independent; garbage rows
    (frozen slots) decode garbage logits exactly like ``decode_step`` —
    and, with ``active`` given, write nothing (out-of-bounds scatter
    address, update dropped): a frozen row's lane may already belong to a
    mid-stream chunk prompt.  Returns (logits [B, C, V] f32, new cache) —
    logits[i] is the next-token distribution AFTER tokens[:, i].
    """
    b, c = tokens.shape
    hd = cfg.resolved_head_dim
    s_max = cache["k"].shape[2]
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)  # [B, C, D]

    batch_idx = jnp.arange(b)[:, None]  # [B, 1] broadcast over C
    write_pos = (positions if active is None
                 else jnp.where(active[:, None], positions, s_max))

    def layer_fn(h, kv, layer, lp, layer_lora):
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(lp, "q", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_heads, hd)
        k = _attn_proj(lp, "k", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        v = _attn_proj(lp, "v", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        kv = _write_kv(kv, (layer, batch_idx, write_pos), k, v)
        with jax.named_scope("attn.core"):
            k_read, v_read = _layer_view(kv, layer, h.dtype)
            # [B,C,K,G,hd] x [B,S,K,hd] -> [B,K,G,C,S]; mask j <= position_i.
            qg = q.reshape(b, c, cfg.n_kv_heads, cfg.q_per_kv, hd)
            logits = jnp.einsum(
                "bikgh,bjkh->bkgij", qg, k_read,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(hd).astype(jnp.float32)
            mask = jnp.arange(s_max)[None, None, :] <= positions[:, :, None]
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
            attn = jnp.einsum(
                "bkgij,bjkh->bikgh", probs, v_read).reshape(b, c, -1)
        h = h + _attn_out(lp, attn, layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        h = h + _mlp(cfg, lp, hn2, layer_lora, slot_ids)
        return h, kv

    h, kv = _scan_cached_layers(params, cache, lora_bufs, h, layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    return logits, _cache_of(kv, positions[:, -1] + 1)


def prefill_with_cache(
    cfg: ModelConfig,
    params: Params,
    cache: Params,
    tokens: jax.Array,      # [C] int32 — one chunk for ONE slot
    positions: jax.Array,   # [C] int32 — absolute positions of the chunk
    slot: jax.Array,        # scalar int32 — cache lane
    lane_end: jax.Array,    # scalar int32 — valid tokens in the lane AFTER
                            # this chunk (true prompt progress, excl. padding)
    last_index: jax.Array,  # scalar int32 — chunk index of the last REAL token
    lora_bufs: Params | None = None,
    lora_slot: jax.Array | int = -1,
):
    """Chunked prefill: run one prompt chunk against an existing cache lane.

    Long prompts stream through in fixed-size chunks: each chunk's K/V are
    scattered into the slot's cache rows at their absolute positions, and the
    chunk's queries attend to EVERYTHING cached so far (previous chunks) plus
    causally within the chunk — so N chunks reproduce a monolithic prefill
    exactly (parity-tested) while compiling only one chunk-sized program.

    A padded final chunk passes pad positions CONTINUING past the prompt
    (start+i): pads scatter into unused cells beyond ``lane_end`` (masked by
    the cache length) instead of overwriting real tokens, and ``last_index``
    selects the true final token's logits.

    Returns (last_logits [V] f32, new cache).
    """
    c = tokens.shape[0]
    hd = cfg.resolved_head_dim
    slot_ids = jnp.full((1,), lora_slot, jnp.int32)
    h = _embed(cfg, params, tokens)[None]  # [1, C, D]
    pos2d = positions[None]  # [1, C]
    quant = "k_scale" in cache

    def layer_fn(h, kv, layer, lp, layer_lora):
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(lp, "q", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_heads, hd)
        k = _attn_proj(lp, "k", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        v = _attn_proj(lp, "v", hn, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        q = apply_rope(q, pos2d, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, pos2d, cfg.rope_theta, cfg.rope_scaling)
        # Scatter the chunk's K/V into the slot's lane at absolute positions.
        kv = _write_kv(kv, (layer, slot, positions), k[0], v[0])
        # Chunk queries vs the whole lane, masked to index <= q position:
        # the one lane [S, K, hd] of this layer is sliced out of the carry.
        lane_k, lane_v = _layer_view(
            tuple(jax.lax.dynamic_index_in_dim(x, slot, 1, keepdims=False)
                  for x in kv), layer, h.dtype)
        # Flash-style chunk attend: no [C, S_max] logits materialize, and
        # K blocks past the chunk's reach elide their DMAs — bandwidth
        # tracks the prompt's progress, not S_max (_chunk_attend).
        attn = _chunk_attend(cfg, quant, q, lane_k, lane_v, positions[0])
        h = h + _attn_out(lp, attn, layer_lora, slot_ids)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        h = h + _mlp(cfg, lp, hn2, layer_lora, slot_ids)
        return h, kv

    h, kv = _scan_cached_layers(params, cache, lora_bufs, h, layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    last_h = jax.lax.dynamic_index_in_dim(h[0], last_index, 0, keepdims=False)
    last_logits = _lm_head(cfg, params, last_h)
    return last_logits, _cache_of(kv, cache["length"].at[slot].set(lane_end))


@jax.named_scope("kv.insert")
def insert_prefill(
    cache: Params,
    k_prompt: jax.Array,  # [L, 1, S, K, hd] from prefill
    v_prompt: jax.Array,
    slot: jax.Array | int,
    length: jax.Array | int,
) -> Params:
    """Insert a prefilled sequence's KV into a decode slot (JetStream-style
    prefill->insert->generate).  ``length`` is the true prompt length; the
    padded tail beyond it is garbage but masked by ``cache['length']``.
    """
    k = cache["k"]
    v = cache["v"]
    if "k_scale" in cache:
        kq, ks = _kv_quantize(k_prompt)  # [L,1,S,K,hd] -> scales [L,1,S,K]
        vq, vs = _kv_quantize(v_prompt)
        k = jax.lax.dynamic_update_slice(k, kq, (0, slot, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vq, (0, slot, 0, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(
            cache["k_scale"], ks, (0, slot, 0, 0))
        v_scale = jax.lax.dynamic_update_slice(
            cache["v_scale"], vs, (0, slot, 0, 0))
        length_vec = cache["length"].at[slot].set(length)
        return {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale,
                "length": length_vec}
    k = jax.lax.dynamic_update_slice(k, k_prompt.astype(k.dtype), (0, slot, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(v, v_prompt.astype(v.dtype), (0, slot, 0, 0, 0))
    length_vec = cache["length"].at[slot].set(length)
    return {"k": k, "v": v, "length": length_vec}
