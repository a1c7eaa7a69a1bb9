"""Elementwise/normalization building blocks (XLA-fused on TPU).

These are deliberately *not* Pallas: RMSNorm and RoPE are elementwise chains
that XLA fuses into the surrounding matmuls for free; a hand kernel would only
forfeit fusion.  Accumulations run in float32 and cast back to the activation
dtype (bfloat16 on TPU), the standard mixed-precision discipline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5,
             plus_one: bool = False) -> jax.Array:
    """RMSNorm in f32.  ``plus_one`` selects the Gemma (1+w) convention."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    return (normed * w).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float,
                     scaling: tuple | None = None) -> jax.Array:
    """Inverse frequencies for rotary embedding, shape [head_dim//2], f32.

    ``scaling`` = (factor, low_freq_factor, high_freq_factor, original_max)
    applies the Llama-3.1 long-context frequency remapping: wavelengths
    beyond ``original_max/low_freq_factor`` are stretched by ``factor``,
    short wavelengths pass through, and the band between interpolates —
    parity-tested against transformers' llama3 rope_type.
    """
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponents)
    if scaling is None:
        return freqs
    factor, low_ff, high_ff, original_max = scaling
    wavelen = 2.0 * jnp.pi / freqs
    low_freq_wavelen = original_max / low_ff
    high_freq_wavelen = original_max / high_ff
    smooth = (original_max / wavelen - low_ff) / (high_ff - low_ff)
    interpolated = (1.0 - smooth) * freqs / factor + smooth * freqs
    scaled = jnp.where(wavelen > low_freq_wavelen, freqs / factor,
                       jnp.where(wavelen < high_freq_wavelen, freqs, interpolated))
    return scaled


@jax.named_scope("attn.rope")
def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: tuple | None = None) -> jax.Array:
    """Rotary position embedding.

    x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq].
    Uses the split-halves convention (Llama/NeoX style): pairs (x_i, x_{i+d/2}).
    Computed in f32, cast back — sin/cos precision matters at long context.
    """
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta, scaling)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``x`` times a config's fixed scalar, in ``x``'s dtype; at 1 (every
    model but the family that publishes multipliers) nothing is traced."""
    if multiplier == 1.0:
        return x
    return x * jnp.asarray(multiplier, x.dtype)


def gated(gate: jax.Array, up: jax.Array,
          activation: str = "silu") -> jax.Array:
    """Gated MLP activation ``act(gate) * up``: SiLU (Llama/Mixtral),
    tanh-GeLU (Gemma) or ReLU (SmallThinker's ReGLU), by
    ``ModelConfig.mlp_activation``."""
    if activation == "gelu":
        act = jax.nn.gelu(gate, approximate=True)
    elif activation == "relu":
        act = jax.nn.relu(gate)
    else:
        act = jax.nn.silu(gate)
    return act * up
