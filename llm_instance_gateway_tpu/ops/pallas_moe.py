"""Grouped matmul for the dropless expert dispatch: row tiles against the
weights of each tile's own expert.

``models.transformer._moe_mlp`` lays the ``T*k`` assignments out by expert,
each expert's group padded to whole tiles of ``tm`` rows, so a tile belongs
to ONE expert and the matmul over ragged groups becomes: for each row tile,
``x[tile] @ w[expert_of(tile)]``.  No capacity, nothing dropped; an expert
with no assignment has no tile and its weights are never read.

The kernel takes the STACKED leaf ``[L, E, K, N]`` with the layer index as
a scalar-prefetch operand (as the decode-attention kernel takes the stacked
cache): a per-layer slice handed to an opaque call is a copy of the layer's
expert stacks per layer per step (device trace, PR 25: 25 ms of Mixtral's
51 ms step).  The tile's expert rides the scalar prefetch too and moves
only the weight block's DMA source.  int8 stacks are widened one
``[tk, tn]`` block at a time in VMEM and the per-output-channel scale is
applied to the f32 accumulator, so the stack never exists in bf16.

``grouped_matmul`` dispatches: the kernel on a TPU backend for shapes
``shape_reasons`` accepts, else the XLA form of the same tiles (gather each
tile's int8 block, one batched einsum), and says which when traced.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_instance_gateway_tpu.ops.attention import kernel_reason, log_choice
from llm_instance_gateway_tpu.ops.quant import is_quantized

# One weight block in its stored dtype.  The pipeline double-buffers it and
# the int8 path holds its widened copy beside it: 2 + 2 + 4 MiB at the
# budget, beside the x, out and accumulator tiles (under 3 MiB at tm = 128).
_W_BLOCK_BYTES = 2 << 20
_VMEM_LIMIT = 48 << 20


def tile_rows(n_assign: int, n_experts: int) -> int:
    """Rows of one tile for ``n_assign`` assignments over ``n_experts``:
    the power of two at or above twice the mean group, from 16 (a bf16
    vreg's sublanes) to 128 (the MXU's rows).  Twice the mean keeps most
    groups in one tile at decode sizes, so an expert's weights are read
    once."""
    want = max(1, -(-2 * n_assign // n_experts))
    return min(128, max(16, 1 << (want - 1).bit_length()))


def n_tiles(n_assign: int, n_experts: int, tm: int) -> int:
    """Static bound on sum_e ceil(group_e / tm): every non-empty group
    wastes under one tile, and no more groups than assignments exist."""
    return max(1, min((n_assign + n_experts * (tm - 1)) // tm,
                      min(n_experts, n_assign) + n_assign // tm))


def tile_plan(sizes, tm: int, tiles: int):
    """From the groups' sizes [E] (traced): the first row of each group
    [E], the expert of each of the ``tiles`` tiles (int32), and how many
    tiles hold a group.  Tiles past the last group repeat its expert: no
    weights move for them."""
    per_group = -(-sizes // tm)
    tile_end = jnp.cumsum(per_group)
    n_used = tile_end[-1]
    tile_expert = jnp.clip(jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(tiles), n_used - 1), side="right"),
        0, sizes.shape[0] - 1).astype(jnp.int32)
    return (tile_end - per_group) * tm, tile_expert, n_used


def _blocks(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn): the widest column block up to 1024 that divides N, and the
    longest row block that divides K and keeps the block in budget.  With
    tk == K consecutive tiles of one expert reuse the block in VMEM."""
    tn = next((c for c in (1024, 512, 256, 128) if n % c == 0), n)
    tk = k
    while tk * tn * itemsize > _W_BLOCK_BYTES and tk % 256 == 0:
        tk //= 2
    return tk, tn


def shape_reasons(k: int, n: int) -> list[str]:
    return [f"{name}={v} % 128 != 0" for name, v in (("K", k), ("N", n))
            if v % 128]


def _gmm_kernel(te_ref, meta_ref, x_ref, w_ref, *refs, quant: bool):
    # x_ref [tm, tk]; w_ref [tk, tn] of this tile's expert; s_ref [1, tn]
    # f32 (int8 only); o_ref [tm, tn]; acc [tm, tn] f32 across the K sweep.
    del te_ref  # consumed by the index maps
    if quant:
        s_ref, o_ref, acc = refs
    else:
        o_ref, acc = refs
    t, kk = pl.program_id(1), pl.program_id(2)
    last = kk == pl.num_programs(2) - 1
    used = t < meta_ref[0]

    @pl.when(used & (kk == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _accumulate():
        x = x_ref[...]
        acc[...] += jax.lax.dot_general(
            x, w_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(used & last)
    def _store():
        y = acc[...] * s_ref[...] if quant else acc[...]
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(used) & last)
    def _blank():  # a tile past the last group: defined, never gathered
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_matmul_pallas(x, w: Any, tile_expert, n_used, layer, *, tm: int,
                          interpret: bool = False) -> jax.Array:
    """x [n_tiles*tm, K]; w the stacked leaf [L, E, K, N] (array or int8
    ``{"q", "s"}``); tile_expert [n_tiles] int32 (tiles >= n_used repeat
    the last used tile's expert, so they move no weights); -> [rows, N]."""
    quant = is_quantized(w)
    wq = w["q"] if quant else w
    rows, k = x.shape
    n = wq.shape[-1]
    tk, tn = _blocks(k, n, wq.dtype.itemsize)
    nk = k // tk
    meta = jnp.stack([jnp.asarray(n_used, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    def kk_of(t, kk, meta):
        # A tile past the last group parks on the block the sweep ended on:
        # unchanged block index, no DMA.
        return jnp.where(t < meta[0], kk, nk - 1)

    def x_index(j, t, kk, te, meta):
        return (t, kk_of(t, kk, meta))

    def w_index(j, t, kk, te, meta):
        return (meta[1], te[t], kk_of(t, kk, meta), j)

    in_specs = [pl.BlockSpec((tm, tk), x_index),
                pl.BlockSpec((None, None, tk, tn), w_index)]
    operands = [tile_expert, meta, x, wq]
    if quant:
        # One layer's scales as [E, 1, N] rows (a relayout of E*N floats).
        scales = jax.lax.dynamic_index_in_dim(w["s"], layer, 0, keepdims=False)
        in_specs.append(pl.BlockSpec(
            (None, 1, tn), lambda j, t, kk, te, meta: (te[t], 0, j)))
        operands.append(scales[:, None, :])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, quant=quant),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # Column blocks outermost: within one, consecutive tiles of an
            # expert find its [K, tn] block already in VMEM.
            grid=(n // tn, rows // tm, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, kk, te, meta: (t, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_gmm_int8" if quant else "moe_gmm",
    )(*operands)


def grouped_matmul_xla(x, w: Any, tile_expert, layer, *, tm: int) -> jax.Array:
    """The same tiles in XLA: each tile's weight block gathered in its
    stored dtype (int8 stays int8), one batched einsum.  Right on every
    backend and under a mesh; reads a block per tile, not per expert."""
    quant = is_quantized(w)
    wq = jax.lax.dynamic_index_in_dim(w["q"] if quant else w, layer, 0,
                                      keepdims=False)
    xt = x.reshape(-1, tm, x.shape[-1])
    y = jnp.einsum("tmk,tkn->tmn", xt, wq[tile_expert].astype(x.dtype))
    if quant:
        s = jax.lax.dynamic_index_in_dim(w["s"], layer, 0, keepdims=False)
        y = y * s[tile_expert][:, None, :].astype(x.dtype)
    return y.reshape(x.shape[0], -1)


def grouped_matmul(x, w: Any, tile_expert, n_used, layer, *, tm: int,
                   use_kernel: bool = True,
                   interpret: bool = False) -> jax.Array:
    wq = w["q"] if is_quantized(w) else w
    k, n = wq.shape[-2:]
    reason = ("pallas kernels off in the config" if not use_kernel
              else kernel_reason(shape_reasons(k, n), interpret))
    log_choice("moe_gmm", f"x{tuple(x.shape)} w{tuple(wq.shape)} tm={tm}",
               reason, interpret)
    if reason is None:
        return grouped_matmul_pallas(x, w, tile_expert, n_used, layer, tm=tm,
                                     interpret=interpret)
    return grouped_matmul_xla(x, w, tile_expert, layer, tm=tm)
