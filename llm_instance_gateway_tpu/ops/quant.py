"""Weight-only int8 quantization for serving.

Decode throughput on a single chip is bounded by reading the weights from
HBM every step; storing the big projection matrices as int8 with per-output-
channel scales halves that traffic versus bf16.  The matmul runs as
``(x @ w_int8.astype(bf16)) * scale`` — XLA fuses the widening into the MXU
feed, so HBM sees int8 while the MXU still computes in bf16, and the
per-column scale is algebraically exact to apply after the contraction.

Quantized leaves are dicts ``{"q": int8 [..., in, out], "s": f32 [..., out]}``
in place of the dense array; ``models.transformer`` dispatches through
``matmul`` below so dense and quantized checkpoints share one forward.
Symmetric per-channel quantization of ~normal weights keeps relative error
around 0.4% per matmul (validated in tests/test_quant.py).

Scope: the seven per-layer projections (dense [L, in, out] AND MoE expert
stacks [L, E, in, out]) + lm_head.  Embeddings (gather, not matmul), norms,
the MoE router (tiny, drives f32 top-k), and LoRA buffers stay bf16.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 # a latent (MLA) layer's projections, a shared expert's
                 "wq_down", "wq_up", "wkv_down", "wkv_up",
                 "ws_gate", "ws_up", "ws_down",
                 # a state-space mixer's two projections (models/ssm.py)
                 "ssm_in", "ssm_out",
                 # a gated short convolution's two (models/shortconv.py)
                 "conv_in", "conv_out",
                 # a delta-rule layer's two (models/kda.py)
                 "kda_in", "kda_out")


def quantize_weight(w: jax.Array) -> dict[str, jax.Array]:
    """Symmetric per-output-channel int8 quantization (last axis = out)."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0  # [..., 1, out]
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale.squeeze(-2).astype(jnp.float32)}


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def matmul(x: jax.Array, w: Any) -> jax.Array:
    """x @ w for dense arrays or quantized {"q","s"} leaves."""
    if is_quantized(w):
        y = x @ w["q"].astype(x.dtype)
        return y * w["s"].astype(x.dtype)
    return x @ w


def static_sharding(sharding: Any) -> Any:
    """A leaf's sharding (``parallel.sharding.param_shardings``) as a
    HASHABLE jit static argument: a quantized leaf's ``{"q", "s"}`` pair
    becomes a tuple; ``constrain`` undoes it inside the program."""
    return ((sharding["q"], sharding["s"]) if is_quantized(sharding)
            else sharding)


def constrain(out: Any, sharding: Any) -> Any:
    """Pin a leaf program's output to ``static_sharding(...)`` (None: no
    mesh) — the leaf is then born sharded, never whole on one device."""
    if sharding is None:
        return out
    if isinstance(sharding, tuple):
        sharding = dict(zip(("q", "s"), sharding))
    return jax.lax.with_sharding_constraint(out, sharding)


@functools.partial(jax.jit, static_argnames=("sharding",))
def _quantize_leaf(w, sharding=None):
    """``quantize_weight`` over a (possibly host-resident) leaf, one LAYER
    at a time for stacked ``[L, ..., in, out]`` leaves: the f32 view the
    quantizer needs is one layer's, not the stack's (7.6 GB for Qwen2.5-7B's
    ``w_gate``)."""
    out = jax.lax.map(quantize_weight, w) if w.ndim >= 3 else quantize_weight(w)
    return constrain(out, sharding)


def quantize_params(params: dict, quantize_lm_head: bool = True,
                    shardings: dict | None = None) -> dict:
    """Return a params tree with the big projections int8-quantized.

    Leaves may be host numpy arrays (``convert.load_serving_checkpoint``):
    each target moves to the device, is quantized and — with ``shardings``
    (``parallel.sharding.param_shardings(..., quantized=True)``) — lands
    sharded, one leaf at a time, so the dense tree never has to fit."""
    out = dict(params)

    def quantized(w, sh):
        return _quantize_leaf(w, sharding=static_sharding(sh))

    # "dense_layers": the leading dense stack of a model that has one.
    for group in ("layers", "dense_layers"):
        if group not in params:
            continue
        layers = dict(params[group])
        layer_sh = None if shardings is None else shardings[group]
        for name in QUANT_TARGETS:
            w = layers.get(name)
            if w is None or is_quantized(w):
                continue
            # Dense projections [L, in, out] AND MoE expert stacks
            # [L, E, in, out] quantize the same way (per-output-channel
            # over the last axis) — expert weights are exactly where
            # Mixtral's HBM-bound decode spends its weight bandwidth.  The
            # router stays dense (a tiny [d, E] matmul whose f32 logits
            # drive top-k).
            layers[name] = quantized(w, layer_sh and layer_sh[name])
        out[group] = layers
    if quantize_lm_head and "lm_head" in params and not is_quantized(params["lm_head"]):
        out["lm_head"] = quantized(params["lm_head"],
                                   shardings and shardings["lm_head"])
    return out


def quantized_bytes(params: dict) -> tuple[int, int]:
    """(bytes_now, bytes_if_dense_bf16) for the weight tree — memory audit."""
    now = 0
    dense = 0
    for leaf in jax.tree.leaves(params):
        now += leaf.size * leaf.dtype.itemsize
        dense += leaf.size * (2 if leaf.dtype == jnp.int8 else leaf.dtype.itemsize)
    return now, dense
