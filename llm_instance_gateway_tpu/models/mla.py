"""Latent attention (MLA) for ``transformer.py``: the attention half of a
block whose cache holds one latent row a position instead of per-head K/V.

With h = norm(x), per layer (``cfg`` names the widths; GLM-4.7-Flash's in
brackets):

    c_q = norm_q(h W_qa) [768];  q = c_q W_qb, per head q_nope [192] | q_rope [64]
    [c_kv | k_r] = h W_kva [512 + 64];  c = norm_kv(c_kv);  k_rope = RoPE(k_r)
    q_rope = RoPE(q_rope) per head; ONE k_rope serves every head.

The cache row of a position is ``[c | k_rope | 0...]``, ``cfg.latent_lanes``
wide (576 numbers padded to 640 = five 128-lane vregs, so a row is whole
vregs and a tile of rows one straight DMA), stacked ``[L, B, S, lanes]`` as
the layer loop's carry like the K/V lanes (``transformer._scan_cached_layers``).

Two forms of the same attention (``tests/test_mla.py`` holds them equal):

- expanded (prefill): ``[k_nope_h | v_h] = c W_kvb,h``; key_h = [k_nope_h |
  k_rope], query_h = [q_nope_h | q_rope_h]; causal softmax attention at
  scale 1/sqrt(nope + rope), through the flash kernel (the value head is as
  wide as the key head: ``leaf_shapes`` refuses a model where it is not).
  The chunk stream expands the slot's whole lane
  of latents a layer at a time and takes the chunk kernel.
- absorbed (decode): ``q~_h = W_kvb,h^K q_nope_h`` [512]; scores
  ``[q~_h | q_rope_h] . [c | k_rope]``; ``o~_h = sum p c``; ``o_h = o~_h
  W_kvb,h^V``.  No per-head key or value ever exists; the kernel
  (``ops.pallas_decode_attention.mla_decode_attention``) reads each latent
  tile once for scores and values.

The pairing of rope columns is ``ops.layers.apply_rope``'s (split halves),
for program and reference alike: ``config.json`` does not state it.

Ling-3.0-flash's latent layers (one closing each period of six,
``layer_pattern``'s "mla"; the cache holds rows for THOSE layers alone) differ
in three things.  No query bottleneck (``q_lora_rank`` 0): ``q = h W_q``
directly, one leaf ``wq``.  Heads of 128 + 64 with values of 128: the prefill
kernels take one head size that is whole 128-lane vregs, so the expanded
form pads queries, keys and values with zero columns to 256 (``_kernel_heads``:
scores and values unchanged, the softmax scale kept at 1/sqrt(192) by a
factor on the queries) and cuts the output back.  A head-wise output gate
(``mla_head_gate``): ``o_h <- sigmoid(h w_gate,h) * o_h`` before ``W_o``, one
number a head from the layer's normed input (``gate_heads``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models.configs import ModelConfig, pad_to
from llm_instance_gateway_tpu.ops.attention import prefill_attention
from llm_instance_gateway_tpu.ops.layers import apply_rope, rms_norm
from llm_instance_gateway_tpu.ops.quant import is_quantized, matmul as q_matmul


# The latent layers' own leaves beside ``wq`` and ``wo``: in a period of
# kinds they are stacked over the latent layers alone
# (``transformer._LEAF_OWNER``).
LEAVES = ("wq_down", "q_latent_norm", "wq_up", "wkv_down", "kv_latent_norm",
          "wkv_up", "w_head_gate")


def leaf_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The attention leaves of one latent layer: name -> (shape, fan_in);
    fan_in 0 marks a norm weight (ones)."""
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.v_head_dim > qk:
        raise NotImplementedError(
            f"{cfg.name}: the prefill kernels take one head size, the "
            f"query/key head's ({qk} = qk_nope_head_dim + qk_rope_head_dim) "
            f"padded to whole vregs: a value head of {cfg.v_head_dim} does "
            "not fit it")
    query = ({"wq_down": ((d, cfg.q_lora_rank), d),
              "q_latent_norm": ((cfg.q_lora_rank,), 0),
              "wq_up": ((cfg.q_lora_rank, h * qk), cfg.q_lora_rank)}
             if cfg.q_lora_rank else {"wq": ((d, h * qk), d)})
    gate = {"w_head_gate": ((d, h), d)} if cfg.mla_head_gate else {}
    return {
        **query,
        **gate,
        "wkv_down": ((d, cfg.latent_width), d),
        "kv_latent_norm": ((cfg.kv_lora_rank,), 0),
        "wkv_up": ((cfg.kv_lora_rank,
                    h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                   cfg.kv_lora_rank),
        "wo": ((h * cfg.v_head_dim, d), h * cfg.v_head_dim),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """The latent decode cache: ``k`` holds the rows (keys AND values) of
    the layers that are latent attention (all, but in a period of kinds),
    and there is no ``v``."""
    return {
        "k": jnp.zeros((cfg.n_layers_of("full"), batch, max_len,
                        cfg.latent_lanes), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _scale(cfg: ModelConfig) -> float:
    return float(1.0 / (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)


def project(cfg: ModelConfig, lp, hn, positions):
    """``hn`` [..., S, D] at ``positions`` [..., S] -> (q_nope [..., S, H,
    nope], q_rope [..., S, H, rope] roped, latent rows [..., S, lanes])."""
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("attn.q_latent"):
        if "wq" in lp:  # no bottleneck (``q_lora_rank`` 0)
            q = q_matmul(hn, lp["wq"])
        else:
            c_q = rms_norm(q_matmul(hn, lp["wq_down"]), lp["q_latent_norm"],
                           cfg.norm_eps)
            q = q_matmul(c_q, lp["wq_up"])
        q = q.reshape(*hn.shape[:-1], h, nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
    with jax.named_scope("attn.kv_latent"):
        ckv = q_matmul(hn, lp["wkv_down"])
        c = rms_norm(ckv[..., :cfg.kv_lora_rank], lp["kv_latent_norm"],
                     cfg.norm_eps)
        k_r = ckv[..., None, cfg.kv_lora_rank:]  # one shared "head"
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    k_rope = apply_rope(k_r, positions, cfg.rope_theta,
                        cfg.rope_scaling)[..., 0, :]
    pad = jnp.zeros((*c.shape[:-1], cfg.latent_lanes - cfg.latent_width),
                    c.dtype)
    return q_nope, q_rope, jnp.concatenate([c, k_rope, pad], axis=-1)


def _kv_up(cfg: ModelConfig, lp):
    """``W_kvb`` as (k part [C, H, nope], v part [C, H, vd], k scales [H,
    nope] or None, v scales [H, vd] or None): an int8 leaf stays int8 and
    its per-output-channel scales are handed back beside it."""
    h, nope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = lp["wkv_up"]
    quant = is_quantized(w)
    m = (w["q"] if quant else w).reshape(cfg.kv_lora_rank, h, nope + vd)
    if not quant:
        return m[..., :nope], m[..., nope:], None, None
    s = w["s"].reshape(h, nope + vd)
    return m[..., :nope], m[..., nope:], s[:, :nope], s[:, nope:]


@jax.named_scope("attn.expand")
def expand(cfg: ModelConfig, lp, latent):
    """Latent rows [..., S, lanes] -> (k [..., S, H, nope + rope], v [...,
    S, H, vd]): per-head keys and values, for prefill."""
    h, nope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    c = latent[..., :cfg.kv_lora_rank]
    k_rope = latent[..., cfg.kv_lora_rank:cfg.latent_width]
    kv = q_matmul(c, lp["wkv_up"]).reshape(*c.shape[:-1], h, nope + vd)
    k_rope = jnp.broadcast_to(k_rope[..., None, :],
                              (*c.shape[:-1], h, k_rope.shape[-1]))
    return (jnp.concatenate([kv[..., :nope], k_rope], axis=-1),
            kv[..., nope:])


def _kernel_heads(q, k, v):
    """The expanded form's q, k [..., H, nope + rope] and v [..., H, vd] as
    the prefill kernels take them: ONE head size of whole 128-lane vregs.
    Where the model's are that already (GLM's 256 / 256) they pass as they
    are; else all three get zero columns up to it and q the factor that
    keeps the softmax's scale the true head's (the kernels scale by the size
    they see).  Returns (q, k, v, what to cut the output's heads back to:
    None or vd)."""
    qk, vd = q.shape[-1], v.shape[-1]
    wide = pad_to(qk, 128)
    if (qk, vd) == (wide, wide):
        return q, k, v, None

    def widen(x):
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, wide - x.shape[-1]),))

    q = q * jnp.asarray((wide / qk) ** 0.5, q.dtype)
    return widen(q), widen(k), widen(v), vd


def gate_heads(cfg: ModelConfig, lp, hn, attn):
    """The head-wise output gate (``mla_head_gate``): ``attn`` [..., H * vd]
    times sigmoid(hn w_gate) [..., H], a number a head.  Every other model's
    output passes as it is."""
    if "w_head_gate" not in lp:
        return attn
    with jax.named_scope("attn.head_gate"):
        gate = jax.nn.sigmoid(jnp.dot(hn, lp["w_head_gate"],
                                      preferred_element_type=jnp.float32))
        heads = attn.reshape(*attn.shape[:-1], cfg.n_heads, -1)
        return (heads * gate[..., None].astype(attn.dtype)).reshape(
            attn.shape)


def prefill_attend(cfg: ModelConfig, lp, hn, positions, attention_fn=None):
    """Bucketed prefill, expanded form.  ``hn`` [B, S, D] -> (attention
    output [B, S, H * vd], latent rows [B, S, lanes])."""
    b, s, _ = hn.shape
    q_nope, q_rope, latent = project(cfg, lp, hn, positions)
    k, v = expand(cfg, lp, latent)
    q, k, v, cut = _kernel_heads(
        jnp.concatenate([q_nope, q_rope], axis=-1), k, v)
    with jax.named_scope("attn.core"):
        if attention_fn is not None:
            attn = attention_fn(q, k, v, positions)
        elif cfg.use_flash_attention:
            from llm_instance_gateway_tpu.ops.pallas_attention import (
                flash_attention,
            )

            attn = flash_attention(q, k, v)
        else:
            attn = prefill_attention(q, k, v, positions)
    attn = attn[..., :cut].reshape(b, s, -1)
    return gate_heads(cfg, lp, hn, attn), latent


@jax.named_scope("attn.absorb")
def absorb_query(cfg: ModelConfig, lp, q_nope, q_rope):
    """(q_nope [B, H, nope], q_rope [B, H, rope]) -> the query against
    latent rows [B, H, lanes]: ``[W^K_h q_nope_h | q_rope_h | 0...]``."""
    wk, _, sk, _ = _kv_up(cfg, lp)
    if sk is not None:
        q_nope = q_nope * sk.astype(q_nope.dtype)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, wk.astype(q_nope.dtype))
    pad = jnp.zeros((*q_lat.shape[:-1], cfg.latent_lanes - cfg.latent_width),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


@jax.named_scope("attn.absorb")
def absorb_output(cfg: ModelConfig, lp, o_lat):
    """Attention over latents [B, H, C] -> per-head values [B, H * vd]."""
    _, wv, _, sv = _kv_up(cfg, lp)
    o = jnp.einsum("bhc,chv->bhv", o_lat, wv.astype(o_lat.dtype))
    if sv is not None:
        o = o * sv.astype(o.dtype)
    return o.reshape(o.shape[0], -1)


def decode_attend(cfg: ModelConfig, lp, hn, positions, kv, at, held,
                  layer):
    """One decode step's attention, absorbed form.  ``hn`` [B, D]; ``kv``
    the carry ``(rows [L, B, S, lanes],)``; ``at`` the scatter address of
    the new rows; ``held`` (the rows each lane holds, the kernel's schedule
    over them or None: ``transformer._held``).  Returns (attention output
    [B, H * vd], the carry)."""
    lengths, schedule = held
    from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda

    q_nope, q_rope, latent = project(cfg, lp, hn[:, None],
                                     positions[:, None])
    with jax.named_scope("attn.kv_update"):
        rows = kv[0].at[at].set(latent[:, 0].astype(kv[0].dtype))
    q_lat = absorb_query(cfg, lp, q_nope[:, 0], q_rope[:, 0])
    with jax.named_scope("attn.core"):
        o_lat = pda.mla_decode_attention(
            q_lat, rows, lengths, cfg.kv_lora_rank, _scale(cfg), layer=layer,
            use_kernel=cfg.use_pallas_decode, schedule=schedule)
    return gate_heads(cfg, lp, hn, absorb_output(cfg, lp, o_lat)), (rows,)


def chunk_attend(cfg: ModelConfig, lp, hn, positions, kv, layer, slot,
                 chunk_fn):
    """One chunk of a streamed prompt: its rows go into the slot's lane,
    then the chunk's queries attend to the WHOLE lane expanded to per-head
    keys and values (what lies past the chunk is masked by position).
    ``hn`` [1, C, D]; ``chunk_fn(q, lane_k, lane_v, start)`` is
    ``transformer._chunk_attend``'s dispatch.  Returns (attention output
    [1, C, H * vd], the carry)."""
    q_nope, q_rope, latent = project(cfg, lp, hn, positions[None])
    with jax.named_scope("attn.kv_update"):
        rows = kv[0].at[layer, slot, positions].set(
            latent[0].astype(kv[0].dtype))
    lane = jax.lax.dynamic_slice(
        rows, (layer, slot, 0, 0), (1, 1, *rows.shape[2:]))[0, 0]
    lane_k, lane_v = expand(cfg, lp, lane.astype(hn.dtype))
    q, lane_k, lane_v, cut = _kernel_heads(
        jnp.concatenate([q_nope, q_rope], axis=-1), lane_k, lane_v)
    attn = chunk_fn(q, lane_k, lane_v, positions[0])  # [1, C, H * wide]
    if cut is not None:
        attn = attn.reshape(*attn.shape[:-1], cfg.n_heads, -1)[
            ..., :cut].reshape(*attn.shape[:-1], -1)
    return gate_heads(cfg, lp, hn, attn), (rows,)
