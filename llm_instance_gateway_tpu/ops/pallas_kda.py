"""The delta-rule state's one-step update for decode (``models/kda.py``), as a
Pallas TPU kernel that rewrites the matrix state where it lies.

A decode step of a model with KDA layers must, a layer, a live row and a
head, read the head's state ``S`` [d_k, d_v] (float32: 64 KiB at 128 x 128,
2 MiB a row over Ling-3.0-flash's 32 heads) and write it back changed:

    Sd = exp(g)[:, None] * S                 decay the rows, channel by channel
    u  = beta * (v - k^T Sd)                 the delta: what the state lacks of v
    S' = Sd + k[:, None] * u[None, :]        the rank-one write
    o  = q^T S'

The matrix-vector product with the state BEFORE the write is what the
mixer's elementwise update (``ops/pallas_ssm.py``) has not.  The state's
traffic is the step's largest item after the experts', so the kernel
(``pl.pallas_call(name="kda_decode_update")``, one call a layer a step)
moves each state once each way and nothing else of size:

- the state is the STACKED ``[L, B, heads, d_k, d_v]`` array of the layer
  loop's carry, aliased from input to output: the kernel rewrites the blocks
  of the live rows of ``layer`` and XLA copies nothing;
- a grid step holds one (row, block of heads): 16 heads, 1 MiB;
- the state lies with d_k on the sublanes and d_v on the lanes, so both
  products with it are sums over sublanes (vreg adds) and ``u`` and ``o``
  land as rows, d_v on the lanes, as the next matmul wants them.  exp(g), k
  and q weigh the state's ROWS and so are needed as columns: a row's 3 x
  heads vectors of d_k come as the rows of one [128, 128] tile, which the
  kernel transposes once a step, and a head's column is a static lane slice
  of it;
- rows that do not decode (``live`` false) cost no state traffic, as in
  ``ops/pallas_ssm.py``: the live rows are visited first (``order``) and every
  step after the last of them names the block the step before it holds.
  Their states stay as they were and their ``o`` is zero.

All arithmetic is float32 on the VPU.  ``kda_decode_update`` is the
dispatching entry: the kernel on a TPU backend for the shapes
``shape_reasons`` accepts, the same update in ``jax.numpy``
(``kda_update_xla``) otherwise, and it says which (``log_choice``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_instance_gateway_tpu.ops.attention import kernel_reason, log_choice

LANES = 128
HEAD_BLOCK = 16    # heads of one grid step: 16 x 128 x 128 x 4 B = 1 MiB


def kda_update_xla(state, q, k, v, g, beta, live=None):
    """The update in ``jax.numpy``, float32.  ``state`` [B, H, dk, dv];
    ``q``, ``k``, ``g`` [B, H, dk] (q and k normed, g the log decay <= 0);
    ``v`` [B, H, dv]; ``beta`` [B, H]; ``live`` [B] bool or None.  Returns
    (o [B, H, dv] float32, new state).  A row that is not live keeps its
    state and gets o = 0.  Sums over d_k, no matmul: a TPU computes it in
    float32 as the kernel does."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - jnp.sum(k[..., None] * decayed, axis=-2))
    new = decayed + k[..., None] * u[..., None, :]
    o = jnp.sum(q[..., None] * new, axis=-2)
    if live is not None:
        keep = live[:, None, None]
        new = jnp.where(keep[..., None], new, state)
        o = jnp.where(keep, o, 0.0)
    return o, new


def _kernel(order_ref, n_ref, layer_ref, cols_ref, w_ref, s_ref, o_ref,
            so_ref, *, n_heads: int, block: int):
    # cols_ref [128, 128]: rows h, H + h, 2 H + h hold k, q and exp(g) of
    # head h of the row (d_k on the lanes); w_ref [2, hb, dv]: beta * v and
    # beta (the same number along dv) of the block's heads; s_ref / so_ref
    # [hb, dk, dv]: the block's state in and out (one buffer in HBM).
    del order_ref, layer_ref  # consumed by the index maps
    i, n_live = pl.program_id(0), n_ref[0]
    hb = pl.program_id(1)

    @pl.when(i < n_live)
    def _update():
        cols = cols_ref[...].T    # [dk, 128]: a vector a lane
        for first in range(0, n_heads, block):  # the block's place: static
            @pl.when(hb == first // block)
            def _(first=first):
                for j in range(block):
                    h = first + j
                    k_col = cols[:, h:h + 1]
                    q_col = cols[:, n_heads + h:n_heads + h + 1]
                    decay = cols[:, 2 * n_heads + h:2 * n_heads + h + 1]
                    decayed = decay * s_ref[j]
                    u = w_ref[0, j:j + 1, :] - w_ref[1, j:j + 1, :] * jnp.sum(
                        k_col * decayed, axis=0, keepdims=True)
                    new = decayed + k_col * u
                    so_ref[j] = new
                    o_ref[j:j + 1, :] = jnp.sum(q_col * new, axis=0,
                                                keepdims=True)

    # No live row at all: every step names one block, which the end of the
    # call writes back, so it has to hold what was there.
    @pl.when((n_live == 0) & (i == 0) & (hb == 0))
    def _keep():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def shape_reasons(n_heads: int, dk: int, dv: int) -> list[str]:
    reasons = []
    block = min(n_heads, HEAD_BLOCK)
    if dk != LANES or dv % LANES:
        reasons.append(f"d_k={dk}, d_v={dv}: the state's tile is 128 keys by "
                       "whole 128-lane vregs of values")
    if n_heads % block or block % 8:
        reasons.append(f"{n_heads} heads: not whole blocks of 8-sublane "
                       "tiles of heads")
    if 3 * n_heads > LANES:
        reasons.append(f"{n_heads} heads: k, q and the decay of a row do "
                       "not fit one 128-row tile")
    return reasons


def kda_decode_update_pallas(state_all, q, k, v, g, beta, live, layer,
                             interpret: bool = False):
    """The kernel over the stacked state [L, B, H, dk, dv]; arguments as
    ``kda_decode_update``.  Returns (o [B, H, dv] float32, the stacked state,
    rewritten in place for the live rows of ``layer``)."""
    f32 = jnp.float32
    _, b, n_heads, dk, dv = state_all.shape
    block = min(n_heads, HEAD_BLOCK)
    n_blocks = n_heads // block
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    cols = jnp.concatenate(
        [k, q, jnp.exp(g), jnp.zeros((b, LANES - 3 * n_heads, dk), f32)],
        axis=1)                                       # [B, 128, dk]
    w = jnp.stack([beta[..., None] * v,
                   jnp.broadcast_to(beta[..., None], v.shape)], axis=1)
    # Live rows first; a step past the last of them holds that row's last
    # block, so that nothing moves for it.
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def held(i, hb, order, n):
        row = order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]
        return row, jnp.where(i < n[0], hb, n_blocks - 1)

    def cols_index(i, hb, order, n, lay):
        return (held(i, hb, order, n)[0], 0, 0)

    def w_index(i, hb, order, n, lay):
        row, blk = held(i, hb, order, n)
        return (row, 0, blk, 0)

    def state_index(i, hb, order, n, lay):
        row, blk = held(i, hb, order, n)
        return (lay[0], row, blk, 0, 0)

    def o_index(i, hb, order, n, lay):
        row, blk = held(i, hb, order, n)
        return (row, blk, 0)

    state_spec = pl.BlockSpec((None, None, block, dk, dv), state_index)
    o, state_all = pl.pallas_call(
        functools.partial(_kernel, n_heads=n_heads, block=block),
        out_shape=(jax.ShapeDtypeStruct((b, n_heads, dv), f32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # order, live rows, layer
            grid=(b, n_blocks),
            in_specs=[pl.BlockSpec((None, LANES, dk), cols_index),
                      pl.BlockSpec((None, 2, block, dv), w_index),
                      state_spec],
            out_specs=(pl.BlockSpec((None, block, dv), o_index),
                       state_spec),
        ),
        # Operand 5 (after the three prefetched scalars, cols and w) is the
        # state: output 1 is the same buffer.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        interpret=interpret,
        name="kda_decode_update",
    )(order, n_live, layer, cols, w, state_all)
    # A row the kernel did not visit has whatever its block of o held.
    return jnp.where(live[:, None, None], o, 0.0), state_all


def kda_decode_update(state_all, q, k, v, g, beta, live, layer,
                      use_kernel: bool = True, interpret: bool = False):
    """One decode step of one layer's recurrence for every row.

    ``state_all``: the stacked state [L, B, H, dk, dv] float32, ``layer`` an
    index into it.  ``q``, ``k``, ``g`` [B, H, dk], ``v`` [B, H, dv],
    ``beta`` [B, H], ``live`` [B] bool (None: every row).  Returns (o [B, H,
    dv] float32, the state array with the live rows of ``layer`` updated)."""
    if live is None:
        live = jnp.ones((q.shape[0],), bool)
    n_heads, dk, dv = state_all.shape[2:]
    reason = ("pallas kernels off in the config" if not use_kernel
              else kernel_reason(shape_reasons(n_heads, dk, dv), interpret))
    log_choice("kda_update", f"state{tuple(state_all.shape)}", reason,
               interpret)
    if reason is None:
        return kda_decode_update_pallas(
            state_all, q, k, v, g, beta, live, layer, interpret=interpret)
    o, new = kda_update_xla(
        jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False),
        q, k, v, g, beta, live)
    return o, jax.lax.dynamic_update_index_in_dim(state_all, new, layer, 0)
