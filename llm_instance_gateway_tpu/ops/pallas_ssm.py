"""The state-space mixer's one-step update for decode, as a Pallas TPU kernel
that rewrites the recurrent state where it lies.

A decode step of a model with a mixer (``models/ssm.py``) must, a layer and a
live row, read the row's state ``H`` [heads, d_state, head_dim] (float32:
4 MiB at Falcon-H1-34B's 32 x 256 x 128), and write it back changed:

    H'[h, n, p] = exp(dt[h] A[h]) * H[h, n, p] + B[g(h), n] * dt[h] * x[h, p]
    y[h, p]     = sum_n C[g(h), n] * H'[h, n, p] + D[h] * x[h, p]

That traffic is the step's largest single item at a full batch, so the
kernel (``pl.pallas_call(name="ssm_decode_update")``, one call a layer a
step) moves each state once each way and nothing else of size:

- the state is the STACKED ``[L, B, heads, d_state, head_dim]`` array of the
  layer loop's carry, aliased from input to output: the kernel rewrites the
  blocks of the live rows of ``layer`` and XLA copies nothing;
- a grid step holds one (row, group) block: the heads of one B/C group
  (16 x 256 x 128 x 4 B = 2 MiB), so B and C are one vector each a block;
- the state is held with ``d_state`` on the sublanes and ``head_dim`` on the
  lanes, so that ``y`` is a sum over sublanes (vreg adds) and lands with
  ``head_dim`` on the lanes as the next matmul wants it; B and C come as
  rows and are turned into columns by a diagonal mask and a lane sum;
- rows that do not decode (``live`` false: free, frozen, a lane reserved
  for a chunk stream) cost no state traffic: the live rows are visited
  first (``order``), and every step after the last of them names the block
  the step before it holds, so Pallas copies nothing in and nothing out.
  Their states stay as they were and their ``y`` is zero.

All arithmetic is float32 on the VPU: no matmul, so no bf16 pass.
``ssm_decode_update`` is the dispatching entry: the kernel on a TPU backend
for the shapes ``shape_reasons`` accepts, the same update in ``jax.numpy``
(``ssm_update_xla``) otherwise, and it says which (``log_choice``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_instance_gateway_tpu.ops.attention import kernel_reason, log_choice

LANES = 128
BC_ROWS = 8            # sublanes of the block that carries B's and C's tiles
MAX_BLOCK_BYTES = 4 << 20   # one (row, group) state block; x4 with buffers


def ssm_update_xla(state, x, dt, a, bm, cm, d, live=None):
    """The update in ``jax.numpy``, float32, elementwise (no matmul, so a
    TPU computes it exactly as the kernel does).  ``state`` [B, H, N, P];
    ``x`` [B, H, P]; ``dt`` [B, H] (after softplus); ``a`` = -exp(A_log)
    [H]; ``bm``, ``cm`` [B, G, N]; ``d`` [H]; ``live`` [B] bool or None.
    Returns (y [B, H, P] float32, new state).  A row that is not live
    keeps its state and gets y = 0."""
    f32 = jnp.float32
    per_group = state.shape[1] // bm.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    bh = jnp.repeat(bm.astype(f32), per_group, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm.astype(f32), per_group, axis=1)
    decay = jnp.exp(dt * a.astype(f32))[..., None, None]
    new = decay * state + bh[..., :, None] * (dt[..., None] * x)[..., None, :]
    y = jnp.sum(new * ch[..., :, None], axis=2) + d.astype(f32)[:, None] * x
    if live is not None:
        keep = live[:, None, None]
        new = jnp.where(keep[..., None], new, state)
        y = jnp.where(keep, y, 0.0)
    return y, new


def _kernel(order_ref, n_ref, layer_ref, u_ref, bc_ref, s_ref, y_ref, o_ref,
            *, per_group: int, n_tiles: int):
    # u_ref [3, Hg, P]: rows of exp(dt A) (the same number along P), dt * x
    # and D * x for the block's heads; bc_ref [8, 128]: B's then C's tiles
    # of 128 states as rows; s_ref / o_ref [Hg, N, P]: the block's state in
    # and out (one buffer in HBM).
    del order_ref, layer_ref  # consumed by the index maps
    i, n_live = pl.program_id(0), n_ref[0]

    @pl.when(i < n_live)
    def _update():
        bc = bc_ref[...]
        diag = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))

        def column(r):
            # Row r of ``bc`` as a column [128, 1]: states on the sublanes.
            return jnp.sum(jnp.where(diag, bc[r:r + 1, :], 0.0), axis=1,
                           keepdims=True)

        b_cols = [column(j) for j in range(n_tiles)]
        c_cols = [column(n_tiles + j) for j in range(n_tiles)]
        for h in range(per_group):
            decay = u_ref[0, h:h + 1, :]
            dtx = u_ref[1, h:h + 1, :]
            acc = u_ref[2, h:h + 1, :]
            for j in range(n_tiles):
                tile = pl.ds(j * LANES, LANES)
                new = decay * s_ref[h, tile, :] + b_cols[j] * dtx
                o_ref[h, tile, :] = new
                acc = acc + jnp.sum(new * c_cols[j], axis=0, keepdims=True)
            y_ref[h:h + 1, :] = acc

    # No live row at all: every step names one block, which the end of the
    # call writes back, so it has to hold what was there.
    @pl.when((n_live == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _keep():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def shape_reasons(n_heads: int, n_groups: int, d_state: int,
                  head_dim: int) -> list[str]:
    reasons = []
    per_group = n_heads // max(n_groups, 1)
    if d_state % LANES or head_dim % LANES:
        reasons.append(f"d_state={d_state}, head_dim={head_dim}: not whole "
                       "128-lane tiles")
    if 2 * (d_state // LANES) > BC_ROWS:
        reasons.append(f"d_state={d_state}: B and C do not fit "
                       f"{BC_ROWS} rows of 128")
    if n_heads % max(n_groups, 1) or per_group % 8:
        reasons.append(f"{n_heads} heads in {n_groups} groups: a group is "
                       "not whole 8-sublane tiles of heads")
    if per_group * d_state * head_dim * 4 > MAX_BLOCK_BYTES:
        reasons.append("a group's state is over the block budget")
    return reasons


def ssm_decode_update_pallas(state_all, x, dt, a, bm, cm, d, live, layer,
                             interpret: bool = False):
    """The kernel over the stacked state [L, B, H, N, P]; arguments as
    ``ssm_decode_update``.  Returns (y [B, H, P] float32, the stacked state,
    rewritten in place for the live rows of ``layer``)."""
    f32 = jnp.float32
    _, b, n_heads, d_state, head_dim = state_all.shape
    n_groups = bm.shape[1]
    per_group, n_tiles = n_heads // n_groups, d_state // LANES
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))
    u = jnp.stack([jnp.broadcast_to(decay[..., None], x.shape),
                   dt[..., None] * x, d.astype(f32)[:, None] * x], axis=1)
    bc = jnp.concatenate(
        [bm.astype(f32).reshape(b, n_groups, n_tiles, LANES),
         cm.astype(f32).reshape(b, n_groups, n_tiles, LANES),
         jnp.zeros((b, n_groups, BC_ROWS - 2 * n_tiles, LANES), f32)], axis=2)
    # Live rows first; a step past the last of them holds that row's last
    # block, so that nothing moves for it.
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def held(i, g, order, n, lay):
        row = order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]
        return row, jnp.where(i < n[0], g, n_groups - 1)

    def u_index(i, g, order, n, lay):
        row, grp = held(i, g, order, n, lay)
        return (row, 0, grp, 0)

    def bc_index(i, g, order, n, lay):
        row, grp = held(i, g, order, n, lay)
        return (row, grp, 0, 0)

    def state_index(i, g, order, n, lay):
        row, grp = held(i, g, order, n, lay)
        return (lay[0], row, grp, 0, 0)

    def y_index(i, g, order, n, lay):
        row, grp = held(i, g, order, n, lay)
        return (row, grp, 0)

    state_spec = pl.BlockSpec((None, None, per_group, d_state, head_dim),
                              state_index)
    y, state_all = pl.pallas_call(
        functools.partial(_kernel, per_group=per_group, n_tiles=n_tiles),
        out_shape=(jax.ShapeDtypeStruct((b, n_heads, head_dim), f32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # order, live rows, layer
            grid=(b, n_groups),
            in_specs=[pl.BlockSpec((None, 3, per_group, head_dim), u_index),
                      pl.BlockSpec((None, None, BC_ROWS, LANES), bc_index),
                      state_spec],
            out_specs=(pl.BlockSpec((None, per_group, head_dim), y_index),
                       state_spec),
        ),
        # Operand 5 (after the three prefetched scalars, u and bc) is the
        # state: output 1 is the same buffer.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        interpret=interpret,
        name="ssm_decode_update",
    )(order, n_live, layer, u, bc, state_all)
    # A row the kernel did not visit has whatever its block of y held.
    return jnp.where(live[:, None, None], y, 0.0), state_all


def ssm_decode_update(state_all, x, dt, a, bm, cm, d, live, layer,
                      use_kernel: bool = True, interpret: bool = False):
    """One decode step of one layer's recurrence for every row.

    ``state_all``: the stacked state [L, B, H, N, P] float32, ``layer`` an
    index into it.  ``x`` [B, H, P], ``dt`` [B, H] (after softplus), ``a``
    [H], ``bm`` / ``cm`` [B, G, N], ``d`` [H], ``live`` [B] bool (None:
    every row).  Returns (y [B, H, P] float32, the state array with the
    live rows of ``layer`` updated)."""
    if live is None:
        live = jnp.ones((x.shape[0],), bool)
    n_heads, d_state, head_dim = state_all.shape[2:]
    reason = ("pallas kernels off in the config" if not use_kernel
              else kernel_reason(
                  shape_reasons(n_heads, bm.shape[1], d_state, head_dim),
                  interpret))
    log_choice("ssm_update", f"state{tuple(state_all.shape)}", reason,
               interpret)
    if reason is None:
        y, state_all = ssm_decode_update_pallas(
            state_all, x, dt, a, bm, cm, d, live, layer, interpret=interpret)
    else:
        y, new = ssm_update_xla(
            jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False),
            x, dt, a, bm, cm, d, live)
        state_all = jax.lax.dynamic_update_index_in_dim(
            state_all, new, layer, 0)
    return y, state_all
