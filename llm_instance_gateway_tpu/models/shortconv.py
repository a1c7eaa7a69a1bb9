"""The gated short convolution that LFM2 runs IN PLACE of attention in three
layers of four, for ``transformer.py``: a "conv" layer of
``ModelConfig.layer_pattern`` holds no K and V.

With h = norm(x), per conv layer (LFM2-24B-A2B's sizes in brackets):

    [B | C | u] = h W_in                     W_in: D -> 3 D  [2048 -> 6144]
    z_t = B_t * u_t                          elementwise
    c_t = sum_j w_j * z_{t - (K - 1) + j}    depthwise, causal, K taps [3],
                                             z = 0 before position 0, no bias,
                                             no activation
    s = (C_t * c_t) W_out                    W_out: D -> D

State.  Per sequence and layer the operator keeps z of the last K - 1
positions.  The decode cache holds it beside ``k`` and ``v`` as ``conv``
[L_conv, K - 1, B, D] in the activation dtype (the positions BEFORE the batch,
as Falcon-H1's conv history lies and for its reason, ``models/ssm.py``), and
``k``/``v`` hold the attention layers alone.  A decode step shifts and writes
one z a live row a layer; a bucketed prompt leaves the z of its last K - 1
TRUE positions (``ssm.conv_tail``, the mixer's, shared); the chunk stream
reads the state at a chunk's start and writes it at the chunk's true end; a
prompt's first chunk starts from zeros whatever the slot held.

The products and the conv's sum run in float32 and z is kept in the
activation dtype: three terms, so the sum's own rounding is not the state's.

Scopes: conv.in_proj, conv.mix, conv.out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import ssm
from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.ops.quant import matmul as q_matmul

F32 = jnp.float32

# The conv layers' own leaves (stacked over the CONV layers of a group) and
# the attention layers' (stacked over its attention layers); every other leaf
# of a layer is stacked over all of them.
CONV_LEAVES = ("conv_in", "conv_w", "conv_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


def leaf_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The operator's drawn leaves of one layer: name -> (shape, fan_in)."""
    d = cfg.d_model
    return {
        "conv_in": ((d, 3 * d), d),
        "conv_w": ((cfg.conv_kernel, d), cfg.conv_kernel),
        "conv_out": ((d, d), d),
    }


def init_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    """The conv layers' part of a decode cache."""
    return {"conv": jnp.zeros((cfg.n_layers_of("conv"), cfg.conv_kernel - 1,
                               batch, cfg.d_model), dtype)}


@jax.named_scope("conv.in_proj")
def in_proj(lp, hn):
    """``hn`` [..., D] -> (z = B * u, C), each [..., D]."""
    b, c, u = jnp.split(q_matmul(hn, lp["conv_in"]), 3, axis=-1)
    return (b.astype(F32) * u.astype(F32)).astype(hn.dtype), c


@jax.named_scope("conv.mix")
def mix(lp, padded, c):
    """C * conv(z) of ``padded`` [..., S + K - 1, D] (its first K - 1
    positions the history) and ``c`` [..., S, D] -> [..., S, D]."""
    w = lp["conv_w"].astype(F32)
    s = c.shape[-2]
    acc = 0.0
    for j in range(w.shape[0]):  # tap j weighs the input K - 1 - j back
        acc = acc + w[j] * padded[..., j:j + s, :].astype(F32)
    return (c.astype(F32) * acc).astype(c.dtype)


@jax.named_scope("conv.out_proj")
def out_proj(lp, y):
    return q_matmul(y, lp["conv_out"])


def prompt_mix(cfg: ModelConfig, lp, hn, live=None, history=None):
    """The operator over a (padded) prompt or one chunk of it.  ``hn``
    [B, S, D]; ``live`` [B, S] bool marks the true positions, which lead
    (None: all S); ``history`` [B, K - 1, D] is what came before (None: a
    prompt's start, zeros).  Returns (s [B, S, D], the state [B, K - 1, D]
    after the last TRUE position)."""
    b, s, d = hn.shape
    z, c = in_proj(lp, hn)
    if history is None:
        history = jnp.zeros((b, cfg.conv_kernel - 1, d), z.dtype)
    padded = jnp.concatenate([history.astype(z.dtype), z], axis=1)
    tail = ssm.conv_tail(padded, ssm.true_lengths(live, b, s),
                         cfg.conv_kernel)
    return out_proj(lp, mix(lp, padded, c)), tail


def decode_mix(cfg: ModelConfig, lp, hn, conv, lane, active=None):
    """One decode step's operator.  ``hn`` [B, D]; ``conv`` the carry's
    [L_conv, K - 1, B, D], of which this layer's is ``lane``; rows whose
    ``active`` bit is off leave it untouched.  Returns (s [B, D], conv)."""
    z, c = in_proj(lp, hn)
    history = jax.lax.dynamic_index_in_dim(conv, lane, 0, keepdims=False)
    padded = jnp.concatenate([history, z[None].astype(conv.dtype)], axis=0)
    with jax.named_scope("conv.mix"):
        moved = padded[1:] if active is None else jnp.where(
            active[None, :, None], padded[1:], history)
        conv = jax.lax.dynamic_update_index_in_dim(conv, moved, lane, 0)
    y = mix(lp, jnp.moveaxis(padded, 0, 1), c[:, None])[:, 0]
    return out_proj(lp, y), conv


def chunk_mix(cfg: ModelConfig, lp, hn, conv, lane, slot, first, live):
    """One chunk of a streamed prompt for ONE slot: the state the slot's
    lane holds (zeros where this is the prompt's ``first`` chunk) goes in,
    what the chunk leaves at its true end goes back.  ``hn`` [1, C, D],
    ``live`` [1, C].  Returns (s [1, C, D], conv)."""
    history = jnp.where(first, jnp.zeros((), conv.dtype),
                        conv[lane, :, slot])[None]
    s, tail = prompt_mix(cfg, lp, hn, live, history)
    return s, conv.at[lane, :, slot].set(tail[0])
