"""Core decoder-only transformer: one implementation, ten families.

Covers Llama-3 (RoPE+GQA+SwiGLU), Gemma (tied embeddings, sqrt(d) embedding
scale, GeLU gate, (1+w) RMSNorm, shared KV head), Qwen2 (QKV bias), Mixtral
(top-k MoE MLP), OLMoE (QK-norm, 64 experts top-8, gates not renormalised)
and GLM-4.7-Flash (latent attention and a latent cache, ``models/mla.py``;
a leading dense layer before the sparse ones; a sigmoid router with a
selection bias and a shared expert) and Falcon-H1 (a parallel block: a
state-space mixer beside the attention of every layer, ``models/ssm.py``,
whose recurrent state rides the cache and the layer loop's carry next to
the K/V lanes; fixed muP multipliers) and SmallThinker (a PERIOD of layer
kinds, full attention without a position encoding and RoPE layers with a
sliding window, scanned a period a step over a cache of two kinds: full
lanes and ring lanes; a router that reads the block's input; ReLU gating)
and LFM2 (layers WITHOUT attention: a gated short convolution in its place in
three layers of four, ``models/shortconv.py``, whose per-slot state rides the
cache and the carry beside the K/V lanes of the attention layers alone; the
leaves of a period one stack a kind; 64-wide heads, two to a cache row, with
a per-head QK-norm; leading dense layers before a rotated period) and
Ling-3.0-flash (delta-rule linear attention in five layers of six,
``models/kda.py``, whose float32 matrix state a head rides the cache and the
carry beside the latent rows of the ONE latent layer that closes each period;
a group-limited router over experts of which the program holds a chip's share)
via ``ModelConfig`` flags.

TPU-first structure:
- Parameters are stacked over layers (``[n_layers, ...]`` leaves) and the
  forward runs ``lax.scan`` over them: one layer gets traced/compiled once,
  not n_layers times, and pjit shards every layer identically.
- The decode path is a fixed-shape step function: batch = the engine's decode
  slots, cache = ``[n_layers, B, S_max, n_kv, hd]``; one compilation serves
  the entire serving lifetime (XLA recompile storms are the TPU-serving
  failure mode the design avoids, SURVEY.md §7).
- bfloat16 params/activations, f32 softmax/norms, f32 logits.
- Multi-LoRA deltas (``models.lora``) apply to every projection whose
  target's buffers the program was handed: computed by slot over the whole
  batch, kept per row by its slot id, so one decode batch multiplexes
  adapters + base model.
- Every block sits in a ``jax.named_scope`` (embed, attn.qkv, attn.rope,
  attn.kv_update, attn.core (attn.core.window over a window layer's ring
  lanes), attn.out, mlp, moe.route / .dispatch /
  .experts / .shared, lora, lm_head, kv.insert; a latent model's attn.q_latent,
  attn.kv_latent, attn.absorb, attn.expand; a mixer's ssm.in_proj, ssm.conv,
  ssm.scan, ssm.update, ssm.gate_norm, ssm.out_proj; a conv layer's
  conv.in_proj, conv.mix, conv.out_proj; attn.qk_norm of a per-head
  QK-norm; a KDA layer's kda.in_proj, kda.conv, kda.gate, kda.scan,
  kda.update, kda.gate_norm, kda.out_proj; attn.head_gate): the scope is in each
  compiled operation's name, so a device trace says which line of this file
  an operation belongs to.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import kda
from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models import mla
from llm_instance_gateway_tpu.models import shortconv
from llm_instance_gateway_tpu.models import ssm
from llm_instance_gateway_tpu.models.configs import LayerKind, ModelConfig
from llm_instance_gateway_tpu.ops.attention import (
    decode_attention,
    log_choice,
    pack_heads,
    prefill_attention,
    unpack_heads,
    xla_chunk_attention,
)
from llm_instance_gateway_tpu.ops.layers import (
    apply_rope,
    gated,
    rms_norm,
    scaled,
)
from llm_instance_gateway_tpu.ops import pallas_moe
from llm_instance_gateway_tpu.ops.quant import (
    QUANT_TARGETS,
    constrain,
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
    d, v = cfg.d_model, cfg.padded_vocab
    keys = iter(jax.random.split(
        key, 32 if cfg.latent_width or cfg.ssm_d_inner or cfg.conv_kernel
        else 16))
    dtype = jnp.dtype(dtype)

    def rand(tree_sh, name, shape, fan_in):
        quant = quantize and (name in QUANT_TARGETS or name == "lm_head")
        sh = None if tree_sh is None else tree_sh[name]
        return _draw_leaf(next(keys), shape=shape, fan_in=fan_in,
                          dtype=dtype, quant=quant,
                          sharding=static_sharding(sh))

    def const(tree_sh, name, fill, shape):
        x = jnp.full(shape, fill, dtype)
        return x if tree_sh is None else jax.device_put(x, tree_sh[name])

    def group(layer_sh, n_l: int, sparse: bool, first: int = 0) -> Params:
        """``n_l`` stacked layers with one kind of MLP, from layer ``first``
        of the model on.  A model with conv or KDA layers stacks the
        attention's leaves over the group's ``n_a`` attention layers alone
        and the other operator's over its own layers (``_LEAF_OWNER``); a
        group without one of the two has no such leaf."""
        ops = [cfg.kind_of(first + j).operator for j in range(n_l)]
        n_a, n_kda = ops.count("attn"), ops.count("kda")
        layers: Params = {
            "attn_norm": const(layer_sh, "attn_norm", 1, (n_l, d)),
            "mlp_norm": const(layer_sh, "mlp_norm", 1, (n_l, d)),
        }
        def drawn(shapes, n: int = n_l) -> Params:
            """A module's leaves: name -> (shape, fan_in; 0: ones)."""
            return {name: (rand(layer_sh, name, (n, *shape), fan_in)
                           if fan_in
                           else const(layer_sh, name, 1, (n, *shape)))
                    for name, (shape, fan_in) in shapes.items()}

        if "conv" in ops:
            layers.update(drawn(shortconv.leaf_shapes(cfg),
                                ops.count("conv")))
        if n_kda:
            layers.update(drawn(kda.leaf_shapes(cfg), n_kda))
            layers.update(kda.init_vectors(cfg, next(keys), n_kda))
        if cfg.latent_width:
            if n_a:
                layers.update(drawn(mla.leaf_shapes(cfg), n_a))
        elif n_a:
            layers["wq"] = rand(layer_sh, "wq", (n_a, d, cfg.n_heads * hd), d)
            layers["wk"] = rand(layer_sh, "wk",
                                (n_a, d, cfg.n_kv_heads * hd), d)
            layers["wv"] = rand(layer_sh, "wv",
                                (n_a, d, cfg.n_kv_heads * hd), d)
            layers["wo"] = rand(layer_sh, "wo", (n_a, cfg.n_heads * hd, d),
                                cfg.n_heads * hd)
        if cfg.ssm_d_inner:
            layers.update(drawn(ssm.leaf_shapes(cfg)))
            layers.update(ssm.init_vectors(cfg, next(keys), n_l))
        if cfg.attention_bias:
            # Qwen2-family Q/K/V biases (zero init; checkpoints overwrite).
            layers["wq_b"] = const(layer_sh, "wq_b", 0,
                                   (n_l, cfg.n_heads * hd))
            layers["wk_b"] = const(layer_sh, "wk_b", 0,
                                   (n_l, cfg.n_kv_heads * hd))
            layers["wv_b"] = const(layer_sh, "wv_b", 0,
                                   (n_l, cfg.n_kv_heads * hd))
        if cfg.qk_norm:
            layers["q_norm"] = const(layer_sh, "q_norm", 1,
                                     (n_l, cfg.n_heads * hd))
            layers["k_norm"] = const(layer_sh, "k_norm", 1,
                                     (n_l, cfg.n_kv_heads * hd))
        if cfg.qk_norm_head and n_a:
            # One vector of ``hd`` for all heads, drawn about 2 (std 0.1):
            # normed q and k of weight 1 are what random projections give
            # anyway (a logit's std ~1, attention near uniform: with the
            # norm left out the logits moved by 0.03 of the largest at the
            # published widths, inside bf16's rounding; chip run, PR 54).
            # About 2 a logit's std is ~4, attention as peaked as a trained
            # model's, and a norm left out or without its weight is another
            # function by any limit.
            for name in ("q_norm", "k_norm"):
                layers[name] = 2 + rand(layer_sh, name, (n_a, hd), 100)
        if sparse:
            # The router scores every expert; the stacks hold this
            # program's share of them (``n_experts_local``; all without).
            e, f = cfg.experts_held, cfg.expert_d_ff
            layers["router"] = rand(layer_sh, "router",
                                    (n_l, d, cfg.n_experts), d)
            if cfg.router_sigmoid:
                # Drawn non-zero (std 0.1 beside scores in (0, 1)): a router
                # that ignores the selection bias must not pass a test.
                layers["router_bias"] = rand(layer_sh, "router_bias",
                                             (n_l, cfg.n_experts), 100)
            layers["w_gate"] = rand(layer_sh, "w_gate", (n_l, e, d, f), d)
            layers["w_up"] = rand(layer_sh, "w_up", (n_l, e, d, f), d)
            layers["w_down"] = rand(layer_sh, "w_down", (n_l, e, f, d), f)
            if cfg.n_shared_experts:
                fs = cfg.n_shared_experts * f
                layers["ws_gate"] = rand(layer_sh, "ws_gate", (n_l, d, fs), d)
                layers["ws_up"] = rand(layer_sh, "ws_up", (n_l, d, fs), d)
                layers["ws_down"] = rand(layer_sh, "ws_down", (n_l, fs, d),
                                         fs)
        else:
            f = cfg.d_ff
            layers["w_gate"] = rand(layer_sh, "w_gate", (n_l, d, f), d)
            layers["w_up"] = rand(layer_sh, "w_up", (n_l, d, f), d)
            layers["w_down"] = rand(layer_sh, "w_down", (n_l, f, d), f)
        return layers

    def group_sh(name):
        return None if shardings is None else shardings[name]

    n_dense = cfg.n_dense_layers
    dense_layers = (group(group_sh("dense_layers"), n_dense, False)
                    if n_dense else None)
    layers = group(group_sh("layers"), cfg.n_layers - n_dense,
                   bool(cfg.n_experts), n_dense)

    params: Params = {
        "embed": rand(shardings, "embed", (v, d), None),
        "layers": layers,
        "final_norm": const(shardings, "final_norm", 1, (d,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(shardings, "lm_head", (d, v), d)
    if dense_layers is not None:
        params["dense_layers"] = dense_layers
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
    Scale overhead is 1/(2*head_dim) of the bf16 cache.  A latent model's
    cache is ``mla.init_cache``: one row a position under ``k``, no ``v``.
    A model with a state-space mixer also gets ``ssm`` and ``conv``
    (``ssm.init_state``: the recurrent state of every slot, float32, and the
    conv's history); every other model's cache has no such key.  A model
    with window layers keeps them apart: ``k``/``v`` hold its FULL layers
    alone and ``k_win``/``v_win`` [n_window_layers, B, ring, K, hd] its
    window layers' rings, ``ring`` = min(``sliding_window``, ``max_len``)
    positions of which position p lies at p mod ring, so that a row's ring
    holds exactly its last ``ring`` positions.  A model with conv layers
    holds K and V for its ATTENTION layers alone and for each conv layer a
    slot's last inputs, ``conv`` (``shortconv.init_state``).  ``kv_pack``
    narrow kv heads share a row: [.., K / pack, pack * hd].  A model with KDA
    layers (``models/kda.py``) holds latent rows ``k`` for its latent layers
    alone and for each KDA layer a slot's matrix states ``kda`` (float32)
    and conv history ``conv`` (``kda.init_state``)."""
    if cfg.latent_width:
        if quantized:
            raise ValueError("a latent (MLA) cache has no int8 form")
        cache = mla.init_cache(cfg, batch, max_len, dtype)
        if cfg.kda_n_heads:
            cache.update(kda.init_state(cfg, batch, dtype))
        return cache
    hd = cfg.resolved_head_dim
    n_win = cfg.n_window_layers
    if n_win and quantized:
        raise ValueError("ring lanes have no int8 form")
    if cfg.conv_kernel and quantized:
        raise ValueError("an int8 KV cache beside a conv state is not "
                         "served (untested)")
    shape = (cfg.n_layers_of("full"), batch, max_len,
             cfg.n_kv_heads // cfg.kv_pack, hd * cfg.kv_pack)
    cache = {
        "k": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "v": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if quantized:
        if cfg.ssm_d_inner:
            raise ValueError("an int8 KV cache beside a recurrent state is "
                             "not served (untested)")
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    if cfg.ssm_d_inner:
        cache.update(ssm.init_state(cfg, batch, dtype))
    if cfg.conv_kernel:
        cache.update(shortconv.init_state(cfg, batch, dtype))
    if n_win:
        ring = (n_win, batch, min(cfg.sliding_window, max_len),
                cfg.n_kv_heads, hd)
        cache.update(k_win=jnp.zeros(ring, dtype), v_win=jnp.zeros(ring, dtype))
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
    """x @ w plus each row's LoRA delta for ``target`` (w may be int8), where
    the layer was handed that target's buffers."""
    out = q_matmul(x, w)
    if layer_lora is not None and f"{target}_a" in layer_lora:
        out = out + lora_lib.lora_delta(
            x,
            layer_lora[f"{target}_a"],
            layer_lora[f"{target}_b"],
            layer_lora["scale"],
            slot_ids,
        )
    return out


@jax.named_scope("attn.qkv")
def _attn_proj(cfg: ModelConfig, lp, target, x, layer_lora, slot_ids):
    """Q/K/V projection: the LoRA delta, the optional attention bias
    (Qwen2-family, q/k/v only) and the optional QK-norm (OLMoE: RMSNorm
    over the WHOLE projected q or k vector, before the split into heads).
    The bias and norm leaves exist in the layer params iff the config
    declares them, so models without them trace exactly the code they
    always did."""
    out = _project(x, lp[f"w{target}"], layer_lora, target, slot_ids)
    b = lp.get(f"w{target}_b")
    if b is not None:
        out = out + b
    norm = lp.get(f"{target}_norm")
    if norm is not None and not cfg.qk_norm_head:
        out = rms_norm(out, norm, cfg.norm_eps)
    return scaled(out, cfg.key_multiplier) if target == "k" else out


@jax.named_scope("attn.qk_norm")
def _head_norm(cfg: ModelConfig, lp, target, x):
    """LFM2's QK-norm on ``x`` [.., heads, hd], before RoPE: RMSNorm over
    the ``hd`` numbers of EACH head, one weight vector for all heads.  Every
    other model's q and k pass as they are."""
    if not cfg.qk_norm_head:
        return x
    return rms_norm(x, lp[f"{target}_norm"], cfg.norm_eps)


def _attn_in(cfg: ModelConfig, hn):
    """The normed input as the attention branch takes it."""
    return scaled(hn, cfg.attention_in_multiplier)


def _branches(cfg: ModelConfig, attn_out, ssm_out=None):
    """What a block adds to the residual before its MLP: the attention
    branch (times its multiplier) and, of a parallel block, the mixer's."""
    attn_out = scaled(attn_out, cfg.attention_out_multiplier)
    return attn_out if ssm_out is None else attn_out + ssm_out


def _split_carry(kv: tuple, n_rec: int) -> tuple[tuple, tuple]:
    """The layer loop's carry as (the attention's arrays, what is no K or V:
    a mixer's (ssm, conv), the conv layers' (conv,)); ``_n_rec``."""
    return kv[:len(kv) - n_rec], kv[len(kv) - n_rec:]


def _n_rec(cache: Params) -> int:
    """How many of the carry's last arrays hold a state that is no K or V:
    a mixer's (ssm, conv), the KDA layers' (kda, conv), the conv layers'
    (conv,), else none."""
    return (2 if "ssm" in cache or "kda" in cache
            else 1 if "conv" in cache else 0)


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
    return scaled(h, cfg.embedding_multiplier)


@jax.named_scope("lm_head")
def _lm_head(cfg: ModelConfig, params: Params, h):
    """Output head matmul (tied or separate) to f32 logits."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return scaled(q_matmul(h, head).astype(jnp.float32),
                  cfg.lm_head_multiplier)


def _core_scope(kind: LayerKind):
    """The scope of a layer's attention proper: a window layer's apart, so
    that a device trace tells the two kinds of lane from each other."""
    if kind.window:
        return jax.named_scope("attn.core.window")
    return jax.named_scope("attn.core")


def _chunk_attend(cfg: ModelConfig, quant: bool, q, lane_k, lane_v, start,
                  kind: LayerKind = LayerKind()):
    """Chunk-vs-lane attention dispatch, shared by the lane and paged
    chunk-stream paths.  Flash-style kernel (XLA off-TPU/odd shapes, logged
    by the dispatcher) unless the lane was dequantized from an int8 cache —
    an opaque kernel can't fuse the dequant into its reads and would
    materialize a bf16 copy, so quantized lanes keep the fused XLA path
    (same reasoning as the decode-path quant gate).  A window layer's
    queries see the last ``kind.window`` positions only.  Returns
    [1, C, H*hd]."""
    from llm_instance_gateway_tpu.ops import pallas_attention

    c = q.shape[1]
    with _core_scope(kind):
        if cfg.use_flash_attention and not quant:
            return pallas_attention.chunk_attention(
                q, lane_k[None], lane_v[None], start,
                window=kind.window, pack=cfg.kv_pack).reshape(1, c, -1)
        log_choice(
            "chunk_attend", f"q{tuple(q.shape)} lane{tuple(lane_k.shape)}",
            "int8 lane: the dequant fuses into the XLA reads" if quant
            else "use_flash_attention=False")
        return xla_chunk_attention(
            q, unpack_heads(lane_k[None], cfg.kv_pack),
            unpack_heads(lane_v[None], cfg.kv_pack), start,
            kind.window).reshape(1, c, -1)


def _mlp(cfg: ModelConfig, lp: Params, x, layer_lora, slot_ids, live=None,
         plan=None):
    """The block's MLP.  Returns ``(y, tally)``: ``tally`` is None for a
    dense model and the sparse layer's routing counts (``_moe_mlp``) for a
    sparse one.  ``live`` (bool, ``x``'s leading dims) marks the rows that
    hold a request; a dense MLP has no use for it.  ``plan``: the routing
    a block made before its attention (``_route_early``); without one the
    sparse layer routes on ``x``."""
    if cfg.n_experts and "router" in lp:  # not a leading dense layer
        return _moe_experts(cfg, lp, x,
                            plan or _moe_route(cfg, lp, x, live))
    with jax.named_scope("mlp"):
        gate = _project(x, lp["w_gate"], layer_lora, "gate", slot_ids)
        up = _project(x, lp["w_up"], layer_lora, "up", slot_ids)
        m_gate, m_down = cfg.mlp_multipliers
        y = _project(gated(scaled(gate, m_gate), up, cfg.mlp_activation),
                     lp["w_down"], layer_lora, "down", slot_ids)
        return scaled(y, m_down), None


def _route_early(cfg: ModelConfig, lp: Params, h, live):
    """The routing plan of a block whose router reads the block's INPUT
    (``router_pre_attention``): logits, the chosen experts, their gates and
    the tile plan need nothing of the attention's output, so they are made
    before it.  None for every other model, whose ``_mlp`` routes."""
    if cfg.router_pre_attention and "router" in lp:
        return _moe_route(cfg, lp, h, live)
    return None


# The sparse layer's routing counts, one int32 vector a layer-step:
# (layer-steps, live assignments, experts with at least one, row tiles that
# hold a group: over the experts touched, the tiles a group takes; every
# assignment the router made; the row tiles of the layout, a constant of the
# program: ``pallas_moe.n_tiles``).  The middle three count what the expert
# matmuls did: under a share (``n_experts_local``) the assignments KEPT and
# the experts touched OF THOSE HELD; the fifth counts held here or not, and
# equals ``assignments`` without a share; ``tiles_used`` over the sixth is
# the share of the layout's rectangle the expert matmul's grid walks.  They
# sum over layers and steps; the engine reads them back with the step.
MOE_TALLY = ("layer_steps", "assignments", "experts_touched", "tiles_used",
             "assignments_routed", "tiles_laid_out")
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _moe_mlp(cfg: ModelConfig, lp: Params, x, live=None):
    """The sparse layer routed on its own input: ``_moe_route`` then
    ``_moe_experts``.  Returns ``(y, tally)``."""
    return _moe_experts(cfg, lp, x, _moe_route(cfg, lp, x, live))


def _moe_route(cfg: ModelConfig, lp: Params, x, live=None) -> dict:
    """Top-k mixture-of-experts MLP by ONE dropless dispatch, the same code
    at every E, k and token count (Mixtral 8 top-2, OLMoE 64 top-8; decode
    batches and 1024-token prefills).

    route: f32 softmax of the router; the k largest are the experts, their
    weights renormalised over the chosen (``norm_topk_prob``) or taken as
    the full softmax gives them.  ``router_sigmoid``: f32 sigmoid scores;
    the experts are the k largest of score + ``router_bias``, their weights
    the scores WITHOUT the bias, renormalised over the chosen
    (``norm_topk_prob``) and scaled by ``routed_scaling_factor``; under
    ``n_group`` > 1 only the experts of the ``topk_group`` best groups can be
    chosen (``_group_limited``).  A share (``n_experts_local``): the router
    scores and chooses among ALL ``n_experts`` and the gates are normalised
    over all k chosen, absent ones included; an assignment whose expert
    another chip holds is then dropped like a dead row's, so the layer
    returns this chip's part of the mix.  dispatch: each of the ``T*k`` assignments
    gets a row in a layout grouped by expert, every group padded to whole
    tiles of ``tm`` rows (``pallas_moe.tile_rows``: from the mean group
    size, so static); its ``row`` there comes from a one-hot cumsum: no
    capacity, nothing dropped, nothing recomputed.  The token rows get
    there by one of two forms chosen from the static ``T*k``
    (``_gathers_in``): a decode batch's few by a row scatter into zeros, a
    prompt's many by a row GATHER in layout order, whose source map ``src``
    is one sort of the layout's keys (``_layout_source``).  experts: three
    grouped matmuls, each tile against its own expert's weights
    (``ops.pallas_moe``); an expert no row chose has no tile and is not
    read.  ``live`` rows only: a row that holds no request routes nowhere
    and gets zeros.

    ``lp`` carries either this layer's expert leaves ``[E, in, out]`` or,
    from the layer loops below, the STACKED leaves ``[L, E, in, out]`` and
    ``lp["layer"]``: the kernel reads the layer where it lies.

    LoRA is not applied to expert weights (matching vLLM, which targets
    attention + dense MLP only).  A model with shared experts adds their
    gated MLP of every token to the mix, once.

    This half is the route and the plan of the dispatch, which read ``x``
    (the MLP's normed input, or the block's input under
    ``router_pre_attention``) through the router alone: the gates [T, k],
    each assignment's ``row`` in the grouped layout, each layout row's
    source token ``src`` (None where the scatter lays the rows out), the
    tiles' experts and the ``tally`` (``MOE_TALLY``).  ``_moe_experts`` does
    the rest.
    """
    xf = x.reshape(-1, x.shape[-1])
    t = xf.shape[0]
    # ``e`` experts are held here, of the router's ``n_experts``: the tile
    # from the mean group over ALL experts (what a share's groups see), the
    # bound on the tiles from those held (all k of a row can land here).
    e, k = cfg.experts_held, cfg.n_experts_per_token
    tm = pallas_moe.tile_rows(t * k, cfg.n_experts)
    n_tiles = pallas_moe.n_tiles(t * k, e, tm)
    n_rows = n_tiles * tm

    with jax.named_scope("moe.route"):
        router_logits = jnp.dot(xf, lp["router"],
                                preferred_element_type=jnp.float32)  # [T, E]
        if cfg.router_sigmoid:
            scores = jax.nn.sigmoid(router_logits)
            _, topi = jax.lax.top_k(_group_limited(
                cfg, scores + lp["router_bias"].astype(jnp.float32)), k)
            gates = jnp.take_along_axis(scores, topi, axis=-1)  # [T, k]
            if cfg.norm_topk_prob:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + cfg.router_gate_eps)
            gates = gates * cfg.routed_scaling_factor
        else:
            topv, topi = jax.lax.top_k(router_logits, k)
            if cfg.norm_topk_prob:
                gates = jax.nn.softmax(topv, axis=-1)  # [T, k]
            else:
                gates = jnp.exp(topv - jax.nn.logsumexp(
                    router_logits, axis=-1, keepdims=True))

    with jax.named_scope("moe.dispatch"):
        expert = topi.reshape(-1)  # [T*k], token-major
        routed = jnp.asarray(t * k, jnp.int32)
        if cfg.n_experts_local:  # its place among those held; e: not here
            expert = expert - cfg.expert_first
            expert = jnp.where((expert >= 0) & (expert < e), expert, e)
        if live is not None:
            alive = jnp.repeat(live.reshape(-1), k)
            expert = jnp.where(alive, expert, e)
            routed = jnp.sum(alive, dtype=jnp.int32)
        chose = (expert[:, None] == jnp.arange(e)).astype(jnp.int32)  # [T*k, E]
        sizes = jnp.sum(chose, axis=0)  # [E] rows of each group
        # Place of an assignment in its group: assignments before it there.
        rank = jnp.sum((jnp.cumsum(chose, axis=0) - 1) * chose, axis=-1)
        first_row, tile_expert, n_used = pallas_moe.tile_plan(
            sizes, tm, n_tiles)
        # A dead row's address is out of bounds: no layout row is its own
        # (its scatter is dropped, the sorted layout keeps it past the last
        # group) and the way back's gather reads zeros for it.
        row = jnp.where(expert < e,
                        first_row[jnp.minimum(expert, e - 1)] + rank, n_rows)
        src = (_layout_source(expert, sizes, k, tm, n_rows)
               if _gathers_in(t * k, e) else None)
        tally = jnp.stack([jnp.ones((), jnp.int32), jnp.sum(sizes),
                           jnp.sum(sizes > 0), n_used, routed,
                           jnp.asarray(n_tiles, jnp.int32)])
    return {"gates": gates, "row": row, "src": src,
            "tile_expert": tile_expert, "n_used": n_used, "tally": tally,
            "tm": tm, "n_rows": n_rows}


def _group_limited(cfg: ModelConfig, biased):
    """Group-limited selection (``n_group`` > 1): ``biased`` [T, E], the
    scores plus the selection bias, with -inf wherever an expert's group is
    not among the ``topk_group`` best; a group scores the sum of its two
    largest entries.  As they are for every other model."""
    if cfg.n_group <= 1:
        return biased
    t, e = biased.shape
    grouped = biased.reshape(t, cfg.n_group, e // cfg.n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, G]
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    kept = jnp.any(best[..., None] == jnp.arange(cfg.n_group), axis=1)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, e)


# The most assignments (``T*k``, static) whose rows the SCATTER lays out.
# The way in alone on the v5e, us a call, scatter | gather
# (``tools/onchip_pallas_check.py "moe-dispatch"``; my chip runs, PR 46):
# decode, 32 rows: mixtral 64 -> [176, 4096] 13 | 17, glm 128 -> [1088, 2048]
# 14 | 31, smallthinker 192 -> [1152, 2560] 29 | 35, olmoe 256 ->
# [1216, 2048] 15 | 31; prompts: 256 -> [704, 4096] 48 | 35, 384 ->
# [1344, 2560] 120 | 39, 512 -> [1472, 2048] 53 | 35, 2,048 -> [2944, 4096]
# 272 | 59, 4,096 -> [12160, 2048] 321 | 182, 6,144 -> [14208, 2560]
# 1,452 | 251.  The gather's sort costs ~20 us whatever it sorts, more than a
# decode batch's whole scatter; from 384 assignments on the scatter loses.
_SCATTER_MAX_ASSIGN = 256


def _gathers_in(n_assign: int, n_experts: int) -> bool:
    """Whether ``n_assign`` assignments' rows reach the layout by gather
    (``_layout_source``) and not by scatter: above the crossing the chip
    shows, and where a layout key fits an int32."""
    return (n_assign > _SCATTER_MAX_ASSIGN
            and (n_experts + 1) << n_assign.bit_length() <= 1 << 31)


def _layout_source(expert, sizes, k: int, tm: int, n_rows: int):
    """The token whose row each of the layout's ``n_rows`` rows holds, T for
    a row that holds none, from the assignments' experts [T*k] (E: dead)
    and the groups' sizes [E], by ONE sort and no scatter.  The layout IS
    the sorted order of its rows' keys (group, place): an assignment's
    place is its index (a group keeps token order), a padding row's is past
    every index; the ``n_rows - T*k`` padding rows are dealt to the groups
    by what each lacks to whole tiles, the rest and the dead past the last
    group."""
    n, e = expert.shape[0], sizes.shape[0]
    bits = n.bit_length()
    low = (1 << bits) - 1  # over every assignment's index
    pad_end = jnp.cumsum(-(-sizes // tm) * tm - sizes)
    pad_group = jnp.sum(
        jnp.arange(n_rows - n, dtype=jnp.int32)[:, None] >= pad_end,
        axis=1, dtype=jnp.int32)
    keys = jax.lax.sort(jnp.concatenate([
        (expert << bits) | jnp.arange(n, dtype=jnp.int32),
        (pad_group << bits) | low]))
    index = keys & low
    return jnp.where((index < n) & ((keys >> bits) < e), index // k, n // k)


def _lay_out(xf, plan: dict, k: int):
    """The token rows ``xf`` [T, D] in the expert-grouped layout
    [n_rows, D], zeros where a row holds no assignment: gathered in layout
    order where the plan has the source map, else scattered."""
    if plan["src"] is None:
        return jnp.zeros((plan["n_rows"], xf.shape[-1]), xf.dtype).at[
            plan["row"]].set(jnp.repeat(xf, k, axis=0), mode="drop")
    # a zero row at index T: a gather that promises its indices in bounds
    # is one pass, ``mode="fill"`` a second one over the whole layout
    with_zero = jnp.concatenate([xf, jnp.zeros_like(xf[:1])])
    return with_zero.at[plan["src"]].get(mode="promise_in_bounds")


def _moe_experts(cfg: ModelConfig, lp: Params, x, plan: dict):
    """The sparse layer from its plan on (``_moe_route``): the rows of
    ``x`` laid out by expert (``_lay_out``), the three grouped matmuls, the
    way back (a gather by ``row``) and the gates' mix, the shared experts.
    Returns ``(y, tally)``."""
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    t, k = xf.shape[0], cfg.n_experts_per_token
    gates, row = plan["gates"], plan["row"]

    stacks = {name: lp[name] for name in _EXPERT_STACKS}
    layer = lp.get("layer")
    if layer is None:  # one layer's leaves: a stack of one
        stacks, layer = jax.tree.map(lambda a: a[None], stacks), 0

    with jax.named_scope("moe.dispatch"):
        x_e = _lay_out(xf, plan, k)

    with jax.named_scope("moe.experts"):
        gmm = functools.partial(
            pallas_moe.grouped_matmul, tile_expert=plan["tile_expert"],
            n_used=plan["n_used"], layer=layer, tm=plan["tm"],
            use_kernel=cfg.use_pallas_decode)
        # The kernel writes the tiles that hold a group and no other
        # (``pallas_moe``): the rows past them are read by ``gated`` alone,
        # row by row, into rows ``down`` does not read and ``row`` never
        # names.
        act = gated(gmm(x_e, stacks["w_gate"]), gmm(x_e, stacks["w_up"]),
                    cfg.mlp_activation)
        out_e = gmm(act, stacks["w_down"])  # [n_rows, D]

    with jax.named_scope("moe.dispatch"):  # the way back: gather + mix
        got = out_e.at[row].get(mode="fill", fill_value=0)  # [T*k, D]
        y = jnp.sum(got.reshape(t, k, d).astype(jnp.float32)
                    * gates[..., None], axis=1).astype(xf.dtype)
    if "ws_gate" in lp:
        with jax.named_scope("moe.shared"):
            y = y + q_matmul(
                gated(q_matmul(xf, lp["ws_gate"]), q_matmul(xf, lp["ws_up"]),
                      cfg.mlp_activation), lp["ws_down"])
    return y.reshape(orig_shape), plan["tally"]


def _layer_xs(layers: Params) -> tuple[Params, Params | None]:
    """A layer loop's share of the stacked layer params: (the leaves the
    loop scans, the expert stacks it does not).  A sparse model's expert
    stacks stay whole and ride into the loop's body beside the layer index
    (``_layer_lp``): scanned, each layer's 3 x [E, in, out] slice is copied
    out for the kernel on every step (device trace, PR 25)."""
    if "router" not in layers:
        return layers, None
    return ({n: w for n, w in layers.items() if n not in _EXPERT_STACKS},
            {n: layers[n] for n in _EXPERT_STACKS})


def _layer_lp(lp: Params, stacks: Params | None, layer) -> Params:
    return lp if stacks is None else {**lp, **stacks, "layer": layer}


def _n_layers(params: Params) -> int:
    return params["layers"]["attn_norm"].shape[0]


def _layer_groups(params: Params) -> list[Params]:
    """The model's stacks of layers in forward order: one for a homogeneous
    model; the leading dense layers (``first_k_dense``) and then the sparse
    ones for a model that has both."""
    if "dense_layers" in params:
        return [params["dense_layers"], params["layers"]]
    return [params["layers"]]


# Which operator's layers a leaf is stacked over in a model some of whose
# layers run no attention (``ModelConfig.kinds_own_leaves``,
# ``LayerKind.operator``); a leaf not named here is every layer's.
_LEAF_OWNER = {**dict.fromkeys(shortconv.CONV_LEAVES, "conv"),
               **dict.fromkeys(kda.LEAVES, "kda"),
               **dict.fromkeys(shortconv.ATTN_LEAVES + mla.LEAVES, "attn")}


def _period_layer(cfg: ModelConfig, lps: Params, kinds, j: int) -> Params:
    """Layer ``j`` of a period out of the period's leaves ``lps`` (each
    [layers of the period that have it, ...]).  Every leaf is every
    layer's, but in a model with conv or KDA layers: there each operator's
    leaves are stacked over the period's layers of that operator alone
    (``_LEAF_OWNER``), and a layer gets its own operator's."""
    if not cfg.kinds_own_leaves:
        return jax.tree.map(lambda a: a[j], lps)
    op = kinds[j].operator
    own = sum(k.operator == op for k in kinds[:j])  # its place in its kind
    return {name: jax.tree.map(
                lambda a, i=own if name in _LEAF_OWNER else j: a[i], leaf)
            for name, leaf in lps.items()
            if _LEAF_OWNER.get(name, op) == op}


def _stack_layers(ys: list):
    """One ``y`` a layer of a period -> each leaf stacked over the layers
    that HAVE it (a conv layer has no K and V, an attention layer no conv
    tail: None there); a leaf no layer has stays None."""
    first = next((y for y in ys if y is not None), None)
    if isinstance(first, tuple):
        return tuple(_stack_layers([y[i] for y in ys])
                     for i in range(len(first)))
    if isinstance(first, dict):
        return {name: _stack_layers([y[name] for y in ys]) for name in first}
    present = [y for y in ys if y is not None]
    return jnp.stack(present) if present else None


def _span_leaves(cfg: ModelConfig, scanned: Params, first: int, start: int,
                 n: int) -> Params:
    """The scanned leaves of the ``n`` layers from layer ``start`` on, out of
    those of a group with conv or KDA layers that starts at layer ``first``:
    each leaf's rows of those layers, counted among the group's layers that
    HAVE the leaf (``_period_layer``); a leaf none of them has is left out,
    as in a group of one kind."""
    def rows(op):  # None: a leaf every layer has
        def upto(end):
            return sum(op is None or cfg.kind_of(l).operator == op
                       for l in range(first, end))
        return slice(upto(start), upto(start + n))
    spans = {name: rows(_LEAF_OWNER.get(name)) for name in scanned}
    return {name: jax.tree.map(lambda a, at=at: a[at], scanned[name])
            for name, at in spans.items() if at.start < at.stop}


def _layer_spans(cfg: ModelConfig, params: Params):
    """The model's scans of layers in forward order (``cfg.group_spans`` of
    each of ``_layer_groups``): (the leaves the scan scans, the group's
    expert stacks, the span's first layer, its place in those stacks, its
    layers, the period of kinds it repeats).  A group is one span, its
    leaves as they are, but where a depth leaves part of a period over."""
    first = 0
    for layers in _layer_groups(params):
        scanned, stacks = _layer_xs(layers)
        n_group = layers["attn_norm"].shape[0]
        for start, n, kinds in cfg.group_spans(first, n_group):
            yield (scanned if n == n_group
                   else _span_leaves(cfg, scanned, first, start, n),
                   stacks, start, start - first, n, kinds)
        first += n_group


def _scan_groups(cfg: ModelConfig, params: Params, lora_bufs: Params | None,
                 carry, body):
    """``lax.scan`` over each group of layers in turn, one carry through
    all.  ``body(carry, layer, lp, layer_lora, kind, lane) -> (carry, ys)``:
    ``layer`` counts through the whole model, ``lp`` is the layer's params
    with a sparse group's expert stacks beside their index (``_layer_lp``),
    ``kind`` its ``LayerKind`` and ``lane`` its index among the layers that
    share its kind of cache (``layer`` itself for a model of one kind).
    Returns (carry, [each scan's stacked ys]).

    A model whose stack repeats a PERIOD of kinds (``cfg.layer_kinds``)
    scans periods: the scanned leaves are viewed [periods, period, ...],
    one step runs the period's layers one after another, each traced as its
    own kind, and the ys come back one a layer as from a scan of layers
    (of a model with conv layers: each leaf one a layer that has it).  The
    period is counted from layer 0 of the MODEL, so a group that starts at
    layer f runs it rotated by f, a group all of one kind scans a layer a
    step, and the layers a depth leaves over of a period are a scan of
    their own (``ModelConfig.group_spans``, ``_layer_spans``).  (Such a
    span's leaves are static slices of its group's, which XLA copies out on
    every run, ~6% of a traced window at Ling's widths; reading them where
    they lie behind an ``optimization_barrier`` took the copies away and
    cost more than they do, 13.4 against 10.1 ms a decode step: my chip
    runs, PR 60, ``PERF.md`` section 7.)"""
    per_layer_lora = None
    if lora_bufs is not None:
        per_layer_lora, _ = lora_lib.stack_for_scan(lora_bufs)
    groups = _layer_groups(params)
    if len(groups) > 1 and lora_bufs is not None:
        raise NotImplementedError(
            "LoRA buffers are one stack: not over two kinds of layers")
    if len(cfg.layer_kinds) > 1 and lora_bufs is not None:
        raise NotImplementedError(
            "LoRA buffers are scanned a layer a step: not over a period of "
            "layer kinds")
    ys = []
    seen: dict[str, int] = {}  # layers of each kind of cache so far
    # (``first``: the span's first layer; ``off``: its place in the stacks)
    for scanned, stacks, first, off, n, kinds in _layer_spans(cfg, params):
        period = len(kinds)
        # A layer's place among the layers that share its kind of cache
        # (full lanes, ring lanes, conv state): how many of them a period
        # holds, how many come before it there, how many before the span.
        caches = [k.cache for k in kinds]
        per_period = [caches.count(c) for c in caches]
        before = [caches[:j].count(c) + seen.get(c, 0)
                  for j, c in enumerate(caches)]

        def step(carry, xs, stacks=stacks, first=first, off=off, kinds=kinds,
                 before=before):
            lp, ll, i = xs
            layer_lora = (None if ll is None
                          else {**ll, "scale": lora_bufs["scale"]})
            layer = first + i if first else i
            lane = layer if before[0] == first else before[0] + i
            return body(carry, layer,
                        _layer_lp(lp, stacks, off + i if off else i),
                        layer_lora, kinds[0], lane)

        def period_step(carry, xs, stacks=stacks, first=first, off=off,
                        kinds=kinds, period=period, per_period=per_period,
                        before=before):
            lps, _, i = xs
            ys = []
            for j, kind in enumerate(kinds):
                at = i * period + j  # the layer's place in its span
                lane = i * per_period[j] + before[j]
                carry, y = body(
                    carry, first + at if first else at,
                    _layer_lp(_period_layer(cfg, lps, kinds, j), stacks,
                              off + at if off else at),
                    None, kind, lane)
                ys.append(y)
            return carry, _stack_layers(ys)

        if period > 1:
            periods = n // period
            scanned = jax.tree.map(
                lambda a: a.reshape(periods, a.shape[0] // periods,
                                    *a.shape[1:]), scanned)
            carry, y = jax.lax.scan(
                period_step, carry, (scanned, None, jnp.arange(periods)))
            y = jax.tree.map(
                lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), y)
        else:
            carry, y = jax.lax.scan(
                step, carry, (scanned, per_layer_lora, jnp.arange(n)))
        ys.append(y)
        for c in set(caches):
            seen[c] = seen.get(c, 0) + n // period * caches.count(c)
    return carry, ys


def _stacked(parts: list):
    """The groups' stacked outputs as one stack over the model's layers
    (None where no group has any, as a dense model's tallies; a dict, as a
    prefill's ``v`` of a model with a state beside its lanes, key by key)."""
    parts = [p for p in parts if p is not None]
    if parts and isinstance(parts[0], dict):
        return {name: _stacked([p[name] for p in parts]) for name in parts[0]}
    if len(parts) <= 1:
        return parts[0] if parts else None
    return jnp.concatenate(parts, axis=0)


def with_moe_tally(cfg: ModelConfig, cache: Params) -> Params:
    """``cache`` asking for a sparse model's routing counts (a zeroed
    ``"moe"`` leaf the cached programs add to, ``_tallied``); a dense
    model's cache as it is."""
    if not cfg.n_experts:
        return cache
    return {**cache, "moe": jnp.zeros((len(MOE_TALLY),), jnp.int32)}


def _tallied(cache: Params, new_cache: Params, tally) -> Params:
    """A cache that carries a ``"moe"`` leaf (int32, one per ``MOE_TALLY``)
    gets the program's routing counts added to it; without the leaf nobody
    asked, and XLA drops the counting."""
    if "moe" in cache:
        new_cache["moe"] = cache["moe"] + (
            0 if tally is None
            else jnp.sum(tally.reshape(-1, len(MOE_TALLY)), axis=0))
    return new_cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def _finish_block(cfg: ModelConfig, lp: Params, h, attn, layer_lora,
                  slot_ids, live, kv):
    """A latent layer from its attention output on: the output projection,
    the residual, the MLP.  Returns ``(h, (*kv, tally))``."""
    h = h + _attn_out(lp, attn, layer_lora, slot_ids)
    hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live)
    return h + y, (*kv, tally)


def _conv_block(cfg: ModelConfig, lp: Params, h, mix, layer_lora, slot_ids,
                live):
    """A layer without attention (``LayerKind.conv``, ``LayerKind.kda``):
    the gated short convolution, or the delta-rule operator, where another
    layer has its attention, then the MLP.  ``mix(hn) -> (its output, the
    operator's new state)`` is the form the program runs (``shortconv`` /
    ``kda`` ``.prompt_mix`` / ``decode_mix`` / ``chunk_mix``; the state a
    conv layer's array, a KDA layer's pair).  Returns (h, the state,
    tally)."""
    hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    mixed, state = mix(hn)
    h = h + mixed
    hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live)
    return h + y, state, tally


def prefill_layer(
    cfg: ModelConfig,
    lp: Params,              # one layer's params (leaves without the L dim)
    h: jax.Array,            # [B, S, D]
    positions: jax.Array,    # [B, S] int32
    layer_lora: Params | None = None,
    slot_ids: jax.Array | None = None,  # [B] int32, -1 = base model
    attention_fn=None,
    live: jax.Array | None = None,  # [B, S] bool — positions of the prompt
    kind: LayerKind = LayerKind(),
):
    """One decoder block over a full sequence.  Returns (h, (k, v, tally)),
    ``tally`` the sparse layer's routing counts (None for a dense model).
    A parallel block (``cfg.ssm_d_inner``) hands back, in ``v``'s place,
    ``{"v", "ssm", "conv"}``: the values and what the mixer's recurrence
    leaves after each row's last true position (``live``; all of them
    without it), which ``insert_prefill`` installs together.  A model with
    conv layers hands back ``{"v", "conv"}`` there: an attention layer its
    values and no conv state (None), a conv layer no ``k``, no values and
    the operator's state after each row's last true position; a model with
    KDA layers ``{"v": None, "kda", "conv"}``, a KDA layer its matrix states
    and conv history there and a latent layer its rows as ``k``.  ``kind`` is
    the layer's place in a period of kinds: a "nope" layer rotates nothing,
    a window layer of a prompt longer than its window masks by it (the XLA
    form: the flash kernel is causal only, and the serving buckets are
    shorter than a served window).

    The single source of truth for the prefill block: ``prefill`` scans it
    over the stacked layer params, and ``parallel.pipeline`` scans each
    stage's slice of the stack inside the pipelined schedule.
    """
    b, s, _ = h.shape
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    if kind.conv:
        h, tail, tally = _conv_block(
            cfg, lp, h, lambda hn: shortconv.prompt_mix(cfg, lp, hn, live),
            layer_lora, slot_ids, live)
        return h, (None, {"v": None, "conv": tail}, tally)
    if kind.kda:
        h, (state, tail), tally = _conv_block(
            cfg, lp, h, lambda hn: kda.prompt_mix(cfg, lp, hn, live),
            layer_lora, slot_ids, live)
        return h, (None, {"v": None, "kda": state, "conv": tail}, tally)
    plan = _route_early(cfg, lp, h, live)
    hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    if cfg.latent_width:
        # "k" is the layer's latent rows, keys and values both; "v" is
        # empty (beside KDA layers: their dict, with nothing of this layer).
        attn, k = mla.prefill_attend(cfg, lp, hn, positions, attention_fn)
        v = ({"v": None, "kda": None, "conv": None} if cfg.kda_n_heads
             else k[..., :0])
        return _finish_block(cfg, lp, h, attn, layer_lora, slot_ids, live,
                             (k, v))
    hd = cfg.resolved_head_dim
    ha = _attn_in(cfg, hn)
    q = _attn_proj(cfg, lp, "q", ha, layer_lora, slot_ids).reshape(b, s, cfg.n_heads, hd)
    k = _attn_proj(cfg, lp, "k", ha, layer_lora, slot_ids).reshape(b, s, cfg.n_kv_heads, hd)
    v = _attn_proj(cfg, lp, "v", ha, layer_lora, slot_ids).reshape(b, s, cfg.n_kv_heads, hd)
    q, k = _head_norm(cfg, lp, "q", q), _head_norm(cfg, lp, "k", k)
    if kind.rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    pack = cfg.kv_pack
    mixed = None
    if cfg.ssm_d_inner:
        mixed, state, tail = ssm.prompt_mix(cfg, lp, hn, live)
    windowed = 0 < kind.window < s
    if windowed and attention_fn is not None:
        raise NotImplementedError(
            "an attention override (--mesh) knows no sliding window")
    with _core_scope(kind):
        if attention_fn is not None:
            attn = attention_fn(q, k, v, positions)
        elif windowed:
            log_choice("flash_prefill", f"q{tuple(q.shape)}",
                       f"window {kind.window} < s: the kernel is causal only")
            attn = prefill_attention(q, k, v, positions, kind.window)
        elif cfg.use_flash_attention:
            # Right-padded batches: causal tiling alone keeps real positions
            # exact (pallas_attention.flash_attention docstring).
            from llm_instance_gateway_tpu.ops.pallas_attention import (
                flash_attention,
            )

            attn = flash_attention(q, pack_heads(k, pack),
                                   pack_heads(v, pack), pack=pack)
        else:
            attn = prefill_attention(q, k, v, positions)
    h = h + _branches(
        cfg, _attn_out(lp, attn.reshape(b, s, -1), layer_lora, slot_ids),
        mixed)
    hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live, plan=plan)
    k, v = pack_heads(k, pack), pack_heads(v, pack)  # as a cache holds them
    if cfg.ssm_d_inner:
        v = {"v": v, "ssm": state, "conv": tail}
    elif cfg.conv_kernel:
        v = {"v": v, "conv": None}
    return h + y, (k, v, tally)


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,       # [B, S] int32
    positions: jax.Array,    # [B, S] int32 (right-padded prompts: 0..len-1)
    lora_bufs: Params | None = None,
    slot_ids: jax.Array | None = None,  # [B] int32, -1 = base model
    attention_fn=None,       # override: (q, k, v, positions) -> attn output
    lengths: jax.Array | None = None,  # [B] int32 — true prompt lengths
    moe_tally: bool = False,
):
    """Full-prompt forward.  Returns (logits [B,S,V] f32, k [L,B,S,K,hd], v),
    and with ``moe_tally`` a sparse model's routing counts (``MOE_TALLY``,
    summed over layers) as a fourth.  A latent model's ``k`` is its latent
    rows [L,B,S,lanes] and its ``v`` empty [L,B,S,0] (``mla``); a model
    with a mixer returns ``{"v", "ssm" [L,B,H,N,P], "conv" [L,B,K-1,C]}``
    (the prompt's rows; a cache lays ``conv`` [L,K-1,B,C])
    in ``v``'s place (``prefill_layer``); a model with conv layers ``k``
    and ``{"v"}`` of its ATTENTION layers alone and ``"conv"``
    [L_conv,B,K-1,D] of its conv layers.  With ``lengths`` the padding past
    a prompt's end routes to no expert (its outputs are garbage either way)
    and leaves a mixer's state alone.

    ``attention_fn`` swaps the attention implementation — used by
    ``parallel.long_context`` to run ring attention over a sequence-sharded
    mesh for prompts that exceed one device's budget.
    """
    b, s = tokens.shape
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)

    live = (None if lengths is None
            else jnp.arange(s)[None] < jnp.reshape(lengths, (-1, 1)))

    def layer_fn(h, layer, lp, layer_lora, kind, lane):
        return prefill_layer(
            cfg, lp, h, positions,
            layer_lora=layer_lora, slot_ids=slot_ids,
            attention_fn=attention_fn, live=live, kind=kind,
        )

    h, ys = _scan_groups(cfg, params, lora_bufs, h, layer_fn)
    k_all, v_all, tally = (_stacked(list(part)) for part in zip(*ys))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    if moe_tally:
        return logits, k_all, v_all, jnp.sum(tally, axis=0)
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


def _carry_names(cache: Params) -> tuple[str, ...]:
    """The cache's stacked arrays that ride the layer loop, in the carry's
    order: (k, v) [L, B, S, K, hd], then (k_scale, v_scale) [L, B, S, K]
    of an int8 cache, or a mixer's recurrent (ssm, conv), or a window
    model's ring lanes (k_win, v_win) [L_window, B, ring, K, hd], k and v
    then being its full layers' alone; a latent cache's rows alone (they
    are keys and values)."""
    if "v" not in cache:  # latent rows; beside them the KDA layers' state
        return ("k", "kda", "conv") if "kda" in cache else ("k",)
    if "ssm" in cache:
        return ("k", "v", "ssm", "conv")
    if "conv" in cache:  # the conv layers' state; k and v the others' alone
        return ("k", "v", "conv")
    if "k_win" in cache:
        return ("k", "v", "k_win", "v_win")
    return ("k", "v") + (("k_scale", "v_scale") if "k_scale" in cache else ())


def _kv_carry(cache: Params) -> tuple:
    return tuple(cache[name] for name in _carry_names(cache))


def _cache_of(cache: Params, kv: tuple, length: jax.Array, tally) -> Params:
    """The cached programs' new cache: the carry's arrays, the new lengths,
    and the routing counts if the old cache asked for them."""
    new = dict(zip(_carry_names(cache), kv), length=length)
    return _tallied(cache, new, tally)


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


def _scan_cached_layers(cfg: ModelConfig, params: Params, cache: Params,
                        lora_bufs: Params | None, h: jax.Array, layer_fn):
    """The cached programs' layer loop.  xs: the stacked layer params, the
    LoRA stack and the layer index; carry: the activations and the stacked
    cache.  ``layer_fn(h, kv, layer, lp, layer_lora, kind, lane) -> (h, kv,
    tally)`` (``_scan_groups``); returns (h, kv, the layers' tallies stacked
    or None)."""
    def body(carry, *layer_args):
        h, kv, tally = layer_fn(*carry, *layer_args)
        return (h, kv), tally

    (h, kv), tallies = _scan_groups(
        cfg, params, lora_bufs, (h, _kv_carry(cache)), body)
    return h, kv, _stacked(tallies)


def _own_lanes(cfg: ModelConfig, kv: tuple, kind: LayerKind):
    """The carry's attention arrays as (this layer's own stacks, a function
    that lays the written stacks back into the carry's order).  A model of
    one kind owns them all; a window model's carry is (k, v, k_win, v_win),
    of which a window layer owns the rings and a full layer the rest."""
    if not cfg.sliding_window:
        return kv, lambda own: own
    if kind.window:
        return kv[2:], lambda own: kv[:2] + own
    return kv[:2], lambda own: own + kv[2:]


def _decode_attend(cfg: ModelConfig, attention_fn, q, kv, layer, held,
                   kind: LayerKind = LayerKind()):
    """One layer's cached attention over the lanes just written: which
    implementation reads them.  The kernel reads ``layer`` of the stacked
    carry in place; everything else gets that layer's view.  ``held``:
    (the positions each row's lane holds, the kernel's schedule over them:
    ``_held``); over a window layer's ring lanes all of them lie inside the
    window."""
    with _core_scope(kind):
        return _attend_cached(cfg, attention_fn, q, kv, layer, *held,
                              bool(kind.window))


def _held(cfg: ModelConfig, attention_fn, lengths, stack):
    """(``lengths``, the decode kernel's schedule of live steps over them)
    for the lanes ``stack``.  The schedule hangs on the lengths alone, and
    XLA does not lift it out of a layer loop (compiled for the v5e, PR 47:
    its cumulative sum and compares stayed in the loop's body), so
    ``decode_step`` builds it here, once a step, before the loop; None
    where no kernel takes it, which then builds its own or is XLA's."""
    if attention_fn is not None or not cfg.use_pallas_decode:
        return lengths, None
    from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda

    block_s, n_tiles = (pda.mla_tiles if cfg.latent_width
                        else pda.lane_tiles)(stack)
    if not n_tiles:
        return lengths, None
    return lengths, pda.decode_schedule(lengths, block_s, n_tiles)


def _attend_cached(cfg, attention_fn, q, kv, layer, lengths, schedule,
                   ring: bool):
    quant = len(kv) == 4
    if attention_fn is None and cfg.use_pallas_decode:
        from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda

        # The int8-aware kernel dequantizes in VMEM at the MXU feed, so HBM
        # streams half the bytes of the bf16 kernel.  (Both fall back to
        # XLA by themselves off-TPU and on unsupported shapes.)
        if quant:
            return pda.decode_attention_quant(q, *kv, lengths, layer=layer,
                                              schedule=schedule)
        return pda.decode_attention(q, *kv, lengths, layer=layer, ring=ring,
                                    schedule=schedule, pack=cfg.kv_pack)
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
    return (attention_fn or decode_attention)(
        q, unpack_heads(k_cache, cfg.kv_pack),
        unpack_heads(v_cache, cfg.kv_pack), lengths)


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
    with ``active`` given — write NOTHING and read nothing of their lanes:
    their scatter index is pushed out of bounds, where XLA drops the
    update, and the attention takes them at length 0 (a free slot keeps its
    last request's position, so ``positions + 1`` is never 0 by itself),
    which the decode kernels' schedule leaves out: no tile copied, no
    matmul, no grid step (``ops.pallas_decode_attention.decode_schedule``,
    built here once for the layer loop).  Without the mask a frozen
    or empty row keeps stomping its lane at a stale position, which is
    fatal once a lane can be mid-chunk-stream for a DIFFERENT request
    while decode dispatches run (the concurrent-lane engine); lockstep
    batching keeps the step shape-static either way.

    ``attention_fn`` swaps the cached-attention implementation — used by
    ``ops.sharded_attention`` to run the Pallas decode kernel shard-local
    under a GSPMD mesh.
    """
    if cfg.latent_width and attention_fn is not None:
        raise NotImplementedError(
            "a latent (MLA) cache takes no attention override (--mesh)")
    b = tokens.shape[0]
    hd = cfg.resolved_head_dim
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)  # [B, D]

    lengths = positions + 1
    # What the attention reads of each lane: none of a row that sits out.
    read_lengths = (lengths if active is None
                    else jnp.where(active, lengths, 0))
    held_full = _held(cfg, attention_fn, read_lengths, cache["k"])
    batch_idx = jnp.arange(b)
    s_max = cache["k"].shape[2]
    # a mixer's (ssm, conv), the KDA layers' (kda, conv), the conv layers'
    n_rec = _n_rec(cache)
    # Scatter address only — rope/masks keep the true positions.  s_max is
    # out of bounds, so inactive rows' updates are dropped whole.
    write_pos = (positions if active is None
                 else jnp.where(active, positions, s_max))
    if "k_win" in cache:
        # A window layer's ring: position p lies at p mod ring, so after
        # this step's write a row's ring holds its last min(p + 1, ring)
        # positions, each inside the window, and the attention reads that
        # many of them in the order they lie.
        ring = cache["k_win"].shape[2]
        ring_pos = jnp.where(write_pos < s_max, positions % ring, ring)
        held_ring = _held(cfg, attention_fn,
                          jnp.minimum(read_lengths, ring), cache["k_win"])

    def latent_layer_fn(h, kv, layer, lp, layer_lora, kind, lane):
        kv, rec = _split_carry(kv, n_rec)
        if kind.kda:
            h, rec, tally = _conv_block(
                cfg, lp, h, lambda hn: kda.decode_mix(
                    cfg, lp, hn, rec, lane, active),
                layer_lora, slot_ids, active)
            return h, kv + rec, tally
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        # (``lane``: the layer's place among the latent layers, which is
        # the layer itself where every layer is one)
        attn, kv = mla.decode_attend(
            cfg, lp, hn, positions, kv, (lane, batch_idx, write_pos),
            held_full, lane)
        h, (kv, tally) = _finish_block(cfg, lp, h, attn, layer_lora,
                                       slot_ids, active, (kv,))
        return h, kv + rec, tally

    def layer_fn(h, kv, layer, lp, layer_lora, kind, lane):
        kv, rec = _split_carry(kv, n_rec)
        if kind.conv:
            h, conv, tally = _conv_block(
                cfg, lp, h, lambda hn: shortconv.decode_mix(
                    cfg, lp, hn, rec[0], lane, active),
                layer_lora, slot_ids, active)
            return h, kv + (conv,), tally
        own, put_back = _own_lanes(cfg, kv, kind)
        plan = _route_early(cfg, lp, h, active)
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        ha = _attn_in(cfg, hn)
        q = _attn_proj(cfg, lp, "q", ha, layer_lora, slot_ids).reshape(b, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", ha, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", ha, layer_lora, slot_ids).reshape(b, cfg.n_kv_heads, hd)
        q, k = _head_norm(cfg, lp, "q", q), _head_norm(cfg, lp, "k", k)
        if kind.rope:
            q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
            k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta, cfg.rope_scaling)[:, 0]
        at, held = ((ring_pos, held_ring) if kind.window
                    else (write_pos, held_full))
        own = _write_kv(own, (lane, batch_idx, at),
                        pack_heads(k, cfg.kv_pack), pack_heads(v, cfg.kv_pack))
        attn = _decode_attend(cfg, attention_fn, q, own, lane, held, kind)
        kv = put_back(own)
        mixed = None
        if n_rec == 2:
            mixed, rec = ssm.decode_mix(cfg, lp, hn, rec, layer, active)
        h = h + _branches(
            cfg, _attn_out(lp, attn.reshape(b, -1), layer_lora, slot_ids),
            mixed)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=active,
                        plan=plan)
        return h + y, kv + rec, tally

    h, kv, tally = _scan_cached_layers(
        cfg, params, cache, lora_bufs, h,
        latent_layer_fn if cfg.latent_width else layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    return logits, _cache_of(cache, kv, lengths, tally)


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
    if cfg.latent_width:
        raise NotImplementedError(
            "extend_step (speculative verify) has no latent (MLA) form")
    if cfg.ssm_d_inner:
        raise NotImplementedError(
            "extend_step (speculative verify) is not served over a "
            "recurrent state: a rejected draft would need it rolled back")
    if cfg.conv_kernel:
        raise NotImplementedError(
            "extend_step (speculative verify) is not served over a conv "
            "state (a rejected draft would need it rolled back) or packed "
            "heads")
    if cfg.sliding_window:
        raise NotImplementedError(
            "extend_step (speculative verify) is not served over ring "
            "lanes: a rejected draft has overwritten positions the window "
            "still needs")
    b, c = tokens.shape
    hd = cfg.resolved_head_dim
    s_max = cache["k"].shape[2]
    if slot_ids is None:
        slot_ids = jnp.full((b,), -1, jnp.int32)
    h = _embed(cfg, params, tokens)  # [B, C, D]

    batch_idx = jnp.arange(b)[:, None]  # [B, 1] broadcast over C
    write_pos = (positions if active is None
                 else jnp.where(active[:, None], positions, s_max))
    live = None if active is None else jnp.broadcast_to(active[:, None], (b, c))

    def layer_fn(h, kv, layer, lp, layer_lora, kind, lane):
        plan = _route_early(cfg, lp, h, live)
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        q = _attn_proj(cfg, lp, "q", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", hn, layer_lora, slot_ids).reshape(
            b, c, cfg.n_kv_heads, hd)
        if kind.rope:
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
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live,
                        plan=plan)
        return h + y, kv, tally

    h, kv, tally = _scan_cached_layers(cfg, params, cache, lora_bufs, h,
                                       layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_head(cfg, params, h)
    return logits, _cache_of(cache, kv, positions[:, -1] + 1, tally)


def _ring_chunk(cfg: ModelConfig, q, k, v, own: tuple, lane, slot,
                positions, live, kind: LayerKind):
    """One chunk of a streamed prompt through a WINDOW layer: the chunk's
    queries ``q`` [1, C, H, hd] against the slot's ring as it stood and the
    chunk's own keys ``k``/``v`` [C, K, hd], which go into the ring only
    afterwards.  Returns (attention output [1, C, H*hd], the written rings).

    The ring (position p at p mod ring) is laid out in position order in
    front of the chunk's keys: before it has wrapped, slot s holds position
    s and the chunk goes in at index ``start``, over cells this prompt has
    not reached; once it has, the roll brings the oldest held position
    (``start`` - ring, at ``start`` mod ring) to index 0 and the chunk
    follows the ring's end.  What of the ring lies behind a query's window
    the mask drops, and a slot's last request is never read: every index
    before the chunk's is a position this prompt wrote.  The padding of a
    final chunk (``live`` false) is written nowhere: in a ring it would land
    on positions the window still holds."""
    ring = own[0].shape[2]
    c = k.shape[0]
    start = positions[0]
    held = jnp.minimum(start, ring)  # the chunk's index in position order
    shift = jnp.where(start > ring, start % ring, 0)

    def in_order(x, new):
        lane_x = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(x, lane, 0, keepdims=False),
            slot, 0, keepdims=False)  # [ring, K, hd]
        buf = jnp.concatenate(
            [jnp.roll(lane_x, -shift, axis=0),
             jnp.zeros((c, *lane_x.shape[1:]), lane_x.dtype)])
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (held, 0, 0))

    attn = _chunk_attend(cfg, False, q, in_order(own[0], k),
                         in_order(own[1], v), held, kind)
    at = jnp.where(live, positions % ring, ring)  # out of bounds: dropped
    return attn, _write_kv(own, (lane, slot, at), k, v)


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
    A mixer's recurrent state and conv history ride the slot's lane from
    chunk to chunk the same way (``ssm.chunk_mix``; a chunk at position 0
    starts from zeros, whatever the lane held).  A window layer's chunk
    attends over its ring AS IT STOOD plus its own keys and is written
    afterwards (``_ring_chunk``): written first, a chunk would overwrite
    positions its own first queries still need.

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
    live = (jnp.arange(c) <= last_index)[None]  # the final chunk's padding

    # a mixer's (ssm, conv), the KDA layers' (kda, conv), the conv layers'
    n_rec = _n_rec(cache)

    def latent_layer_fn(h, kv, layer, lp, layer_lora, kind, lane):
        kv, rec = _split_carry(kv, n_rec)
        if kind.kda:
            # The slot's lane holds what the chunks before this one left.
            h, rec, tally = _conv_block(
                cfg, lp, h, lambda hn: kda.chunk_mix(
                    cfg, lp, hn, rec, lane, slot, positions[0] == 0, live),
                layer_lora, slot_ids, live)
            return h, kv + rec, tally
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, kv = mla.chunk_attend(
            cfg, lp, hn, positions, kv, lane, slot,
            functools.partial(_chunk_attend, cfg, False))
        h, (kv, tally) = _finish_block(cfg, lp, h, attn, layer_lora,
                                       slot_ids, live, (kv,))
        return h, kv + rec, tally

    def layer_fn(h, kv, layer, lp, layer_lora, kind, lane):
        kv, rec = _split_carry(kv, n_rec)
        if kind.conv:
            # The slot's lane holds what the chunks before this one left.
            h, conv, tally = _conv_block(
                cfg, lp, h, lambda hn: shortconv.chunk_mix(
                    cfg, lp, hn, rec[0], lane, slot, positions[0] == 0,
                    live),
                layer_lora, slot_ids, live)
            return h, kv + (conv,), tally
        own, put_back = _own_lanes(cfg, kv, kind)
        plan = _route_early(cfg, lp, h, live)
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        ha = _attn_in(cfg, hn)
        q = _attn_proj(cfg, lp, "q", ha, layer_lora, slot_ids).reshape(1, c, cfg.n_heads, hd)
        k = _attn_proj(cfg, lp, "k", ha, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        v = _attn_proj(cfg, lp, "v", ha, layer_lora, slot_ids).reshape(1, c, cfg.n_kv_heads, hd)
        q, k = _head_norm(cfg, lp, "q", q), _head_norm(cfg, lp, "k", k)
        if kind.rope:
            q = apply_rope(q, pos2d, cfg.rope_theta, cfg.rope_scaling)
            k = apply_rope(k, pos2d, cfg.rope_theta, cfg.rope_scaling)
        k, v = pack_heads(k, cfg.kv_pack), pack_heads(v, cfg.kv_pack)
        if kind.window:
            attn, own = _ring_chunk(cfg, q, k[0], v[0], own, lane, slot,
                                    positions, live[0], kind)
        else:
            # Scatter the chunk's K/V into the slot's lane at absolute
            # positions.
            own = _write_kv(own, (lane, slot, positions), k[0], v[0])
            # Chunk queries vs the whole lane, masked to index <= q
            # position: the one lane [S, K, hd] of this layer is sliced out
            # of the carry.
            lane_k, lane_v = _layer_view(
                tuple(jax.lax.dynamic_index_in_dim(x, slot, 1, keepdims=False)
                      for x in own), lane, h.dtype)
            # Flash-style chunk attend: no [C, S_max] logits materialize,
            # and K blocks past the chunk's reach elide their DMAs —
            # bandwidth tracks the prompt's progress, not S_max
            # (_chunk_attend).
            attn = _chunk_attend(cfg, quant, q, lane_k, lane_v, positions[0],
                                 kind)
        kv = put_back(own)
        mixed = None
        if n_rec == 2:
            # The slot's lane holds what the chunks before this one left.
            mixed, rec = ssm.chunk_mix(cfg, lp, hn, rec, layer, slot,
                                       positions[0] == 0, live)
        h = h + _branches(cfg, _attn_out(lp, attn, layer_lora, slot_ids),
                          mixed)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        y, tally = _mlp(cfg, lp, hn2, layer_lora, slot_ids, live=live,
                        plan=plan)
        return h + y, kv + rec, tally

    h, kv, tally = _scan_cached_layers(
        cfg, params, cache, lora_bufs, h,
        latent_layer_fn if cfg.latent_width else layer_fn)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    last_h = jax.lax.dynamic_index_in_dim(h[0], last_index, 0, keepdims=False)
    last_logits = _lm_head(cfg, params, last_h)
    return last_logits, _cache_of(
        cache, kv, cache["length"].at[slot].set(lane_end), tally)


@jax.named_scope("kv.insert")
def insert_prefill(
    cache: Params,
    k_prompt: jax.Array,  # [L, 1, S, K, hd] from prefill
    v_prompt: jax.Array,
    slot: jax.Array | int,
    length: jax.Array | int,
    cfg: ModelConfig | None = None,
) -> Params:
    """Insert a prefilled sequence's KV into a decode slot (JetStream-style
    prefill->insert->generate).  ``length`` is the true prompt length; the
    padded tail beyond it is garbage but masked by ``cache['length']``.
    ``cfg`` is needed by a window model alone, whose prompt's layers part
    by kind: the full layers' into ``k``/``v`` as ever, the window layers'
    into the rings, ring slot s taking the newest position p < ``length``
    with p mod ring = s (for a prompt shorter than the ring, position s).
    A model with conv layers brings ``k_prompt`` and ``v_prompt["v"]`` of
    its attention layers and ``v_prompt["conv"]`` [L_conv, 1, K - 1, D],
    each conv layer's state at the TRUE length (``prefill``); a model with
    KDA layers its latent layers' rows as ``k_prompt`` and ``v_prompt["kda"]``
    / ``["conv"]`` of its KDA layers.
    """
    if "k_win" in cache:
        kinds = cfg.layer_kinds
        windowed = [bool(kinds[l % len(kinds)].window)
                    for l in range(k_prompt.shape[0])]
        full = jnp.asarray([l for l, w in enumerate(windowed) if not w])
        win = jnp.asarray([l for l, w in enumerate(windowed) if w])
        lanes = insert_prefill(
            {"k": cache["k"], "v": cache["v"], "length": cache["length"]},
            k_prompt[full], v_prompt[full], slot, length)
        ring = cache["k_win"].shape[2]
        cell = jnp.arange(ring)
        newest = length - 1 - (length - 1 - cell) % ring
        newest = jnp.clip(newest, 0, k_prompt.shape[2] - 1)
        rings = {
            name: jax.lax.dynamic_update_slice(
                cache[name], prompt[win][:, :, newest].astype(
                    cache[name].dtype), (0, slot, 0, 0, 0))
            for name, prompt in (("k_win", k_prompt), ("v_win", v_prompt))}
        return {**lanes, **rings}
    k = cache["k"]
    if "conv" in cache:  # v_prompt: prefill's {"v", "conv"}, a mixer's "ssm"
        # One insert installs lanes, state, conv history and length
        # together, so a freed slot needs no clearing.
        rec = {
            # the prompt's [L, 1, K - 1, C] into the cache's [L, K - 1, B, C]
            "conv": jax.lax.dynamic_update_slice(
                cache["conv"], jnp.swapaxes(v_prompt["conv"], 1, 2).astype(
                    cache["conv"].dtype), (0, 0, slot, 0)),
        }
        for name in ("ssm", "kda"):  # a float32 state [L, B, H, ., .]
            if name in cache:
                rec[name] = jax.lax.dynamic_update_slice(
                    cache[name], v_prompt[name].astype(cache[name].dtype),
                    (0, slot, 0, 0, 0))
        lanes = insert_prefill(
            {name: cache[name] for name in ("k", "v", "length")
             if name in cache}, k_prompt, v_prompt["v"], slot, length)
        return {**lanes, **rec}
    if "v" not in cache:  # a latent cache: k_prompt [L, 1, S, lanes]
        k = jax.lax.dynamic_update_slice(
            k, k_prompt.astype(k.dtype), (0, slot, 0, 0))
        return {"k": k, "length": cache["length"].at[slot].set(length)}
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
