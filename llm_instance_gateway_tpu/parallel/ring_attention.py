"""Ring attention: sequence/context parallelism over the ``sequence`` mesh axis.

Long-context prefill can exceed one device's HBM and FLOP budget; ring
attention shards the sequence across devices and rotates K/V blocks around
the ring with ``ppermute`` (ICI neighbor exchanges — the cheapest collective
pattern on a TPU torus), accumulating attention with the online-softmax
recurrence so no device ever materializes the full [S, S] score matrix.

Causality is enforced with *global* positions reconstructed from
``axis_index``: block b of the ring holds tokens [b*S_loc, (b+1)*S_loc), so
a device can mask exactly which rotated keys its queries may attend to —
no wasted compute is skipped (each step still runs; skipping would need
data-dependent control flow that XLA can't pipeline), but masked blocks
contribute zeros through the softmax correction.

Reference pattern: Liu et al., "Ring Attention with Blockwise Transformers"
(PAPERS.md retrieval); implementation is shard_map + lax.fori_loop +
ppermute, fully jittable and differentiable.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_attention_local(
    q: jax.Array,  # [B, S_loc, H, hd] (this device's query block)
    k: jax.Array,  # [B, S_loc, K, hd]
    v: jax.Array,  # [B, S_loc, K, hd]
    *,
    axis_name: str,
    axis_size: int,
    causal: bool,
    varying_axes: tuple[str, ...] = (),
) -> jax.Array:
    b, s_loc, n_heads, hd = q.shape
    n_kv = k.shape[2]
    g = n_heads // n_kv
    qg = q.reshape(b, s_loc, n_kv, g, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    my_idx = jax.lax.axis_index(axis_name)
    local_pos = jnp.arange(s_loc)
    q_pos = my_idx * s_loc + local_pos  # global positions of my queries

    # Online-softmax accumulators (f32).  They start as constants but the
    # loop body mixes in device-varying data, so mark them varying over the
    # manual axes up front or the fori_loop carry types won't match (JAX
    # varying-axes typing for shard_map).
    m = jnp.full((b, n_kv, g, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, n_kv, g, s_loc), jnp.float32)
    o = jnp.zeros((b, n_kv, g, s_loc, hd), jnp.float32)
    if varying_axes:
        m, l, o = (jax.lax.pcast(x, varying_axes, to="varying")
                   for x in (m, l, o))

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(step, carry):
        m, l, o, k_cur, v_cur = carry
        # After `step` rotations I hold the block originally on (my_idx - step).
        src = (my_idx - step) % axis_size
        k_pos = src * s_loc + local_pos
        s = jnp.einsum(
            "bikgh,bjkh->bkgij", qg, k_cur, preferred_element_type=jnp.float32
        ) * scale  # [B,K,G,Sq,Sk]
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk] global causality
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bkgij,bjkh->bkgih", p, v_cur.astype(jnp.float32)
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_new, l, o, k_nxt, v_nxt

    m, l, o, _, _ = jax.lax.fori_loop(0, axis_size, body, (m, l, o, k, v))
    out = o / jnp.maximum(l[..., None], 1e-30)
    # [B,K,G,S,hd] -> [B,S,H,hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s_loc, n_heads, hd)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, S, H, hd] globally, S sharded over "sequence"
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "sequence",
    batch_axes: Sequence[str] = ("data",),
) -> jax.Array:
    """Sequence-parallel attention over a named mesh axis (jit-compatible)."""
    axis_size = mesh.shape[axis_name]
    spec = P(tuple(batch_axes), axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local,
            axis_name=axis_name,
            axis_size=axis_size,
            causal=causal,
            varying_axes=tuple(batch_axes) + (axis_name,),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
