"""Grouped matmul for the dropless expert dispatch: row tiles against the
weights of each tile's own expert.

``models.transformer._moe_mlp`` lays the ``T*k`` assignments out by expert,
each expert's group padded to whole tiles of ``tm`` rows, so a tile belongs
to ONE expert and the matmul over ragged groups becomes: for each row tile,
``x[tile] @ w[expert_of(tile)]``.  No capacity, nothing dropped; an expert
with no assignment has no tile and its weights are never read.  The rows
get there by a scatter into zeros from a decode batch (up to 256
assignments) and by a gather in layout order from a prompt
(``transformer._lay_out``: the layout is one sort of its rows' keys); a row
that holds no assignment is zeros either way, and what the kernel writes
there is never gathered back.

The kernel takes the STACKED leaf ``[L, E, K, N]`` with the layer index as
a scalar-prefetch operand (as the decode-attention kernel takes the stacked
cache): a per-layer slice handed to an opaque call is a copy of the layer's
expert stacks per layer per step (device trace, PR 25: 25 ms of Mixtral's
51 ms step).  The step's expert rides the scalar prefetch too and moves
only the weight block's DMA source.  int8 stacks are widened one
``[tk, tn]`` block at a time in VMEM and the per-output-channel scale is
applied to the f32 accumulator, so the stack never exists in bf16.

Two orders of ONE kernel, chosen from the shapes (``_by_group``).  By group,
where the experts are wide beside the rows and all rows fit VMEM at once
(Mixtral's decode batches and prompts of a few hundred tokens): a grid step
is a touched expert, every row tile of its group is
multiplied against the block while the pipeline fetches the next one's,
so a touched expert's block is fetched ONCE a call however many tiles its
group takes and however many K blocks its column is cut into (by tile,
Mixtral's nk = 2 and 8 re-fetched it for every tile: PERF.md, PR 44), and
x is fetched once.  By tile, for long prompts and for many narrow experts
(OLMoE, GLM-4.7-Flash): a grid step is a row tile, as before.

The layout is sized for the worst routing (``n_tiles``: dropless), and a
decode step fills a quarter to two thirds of it.  The grid ends where the
groups end: on the chip its row dimension is bound by ``n_used`` tiles (by
group: by the touched experts), a traced scalar, as the decode-attention
kernels walk their schedule (``pallas_decode_attention._walk``), so the
tiles past the last group cost no grid step and the layout's rows past
``n_used * tm`` are NOT WRITTEN: nothing may read them but elementwise, row
by row (``transformer._moe_experts`` reads the output through ``row``
alone, which lies in a used tile or out of bounds).  A call with nothing
routed here is a grid of no steps.  The interpreter takes no dynamic bound:
there, and only there, the grid keeps the layout's static bound, the steps
past the last group do nothing but write exact zeros, and the whole output
is defined.

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
# the int8 path may hold its widened copy beside it: 4 + 4 + 8 MiB at the
# budget.  OLMoE's and GLM's blocks are a whole column (tk == K) of 1-2 MiB
# under it; Mixtral's gate/up column [4096, 1024] is one block, down's
# [14336, 1024] four (half the grid steps of a 2 MiB budget: 2-3% of a call,
# my chip runs, PR 44).
_W_BLOCK_BYTES = 4 << 20
# What the group order (``_by_group``) may hold in VMEM for all rows at once,
# beside the weight blocks: Mixtral's decode takes 3-6 MiB of it, its gate/up
# at a 512-token prompt 31.
_ROWS_BYTES = 32 << 20
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
    # By comparing every tile with every group's end, not by bisection: a
    # loop of log2(E) rounds is ~40 device operations a layer where this
    # is one, and a device trace's cost follows their count (PR 47: the
    # profiler took 232 s to stop over olmoe_chat's 4 s, of the 240 the
    # benchmark waits).
    tile_expert = jnp.clip(jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(tiles), n_used - 1), side="right",
        method="compare_all"),
        0, sizes.shape[0] - 1).astype(jnp.int32)
    return (tile_end - per_group) * tm, tile_expert, n_used


def _blocks(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn): the widest column block up to 1024 that divides N, and the
    longest row block that divides K and keeps the block in budget."""
    tn = next((c for c in (1024, 512, 256, 128) if n % c == 0), n)
    tk = k
    while tk * tn * itemsize > _W_BLOCK_BYTES and tk % 256 == 0:
        tk //= 2
    return tk, tn


def shape_reasons(k: int, n: int) -> list[str]:
    return [f"{name}={v} % 128 != 0" for name, v in (("K", k), ("N", n))
            if v % 128]


def _by_group(rows: int, k: int, n: int, tn: int, itemsize: int) -> bool:
    """Whether the grid walks the experts' GROUPS and not the row tiles.
    By group x is fetched whole before the first step, padding rows and all,
    and nothing hides that fetch: so only where it is at most half of ONE
    expert's [K, N] matrix, the least a call reads of the weights (wide
    experts: Mixtral's decode and its prompts to ~512 tokens; not OLMoE's or
    GLM's 64 narrow ones, whose 1,088-1,216 padded rows of x outweigh an
    expert: by group they lost 1-2% end to end, PERF.md, PR 44), and where
    what then stays in VMEM for all rows at once fits: x (one buffer), the
    output column block (two) and its f32 accumulators."""
    x_bytes = rows * k * 2  # bf16 rows
    return (2 * x_bytes <= k * n * itemsize
            and x_bytes + rows * tn * 8 <= _ROWS_BYTES)


def _steps(tile_expert, n_used, n_experts: int, by_group: bool):
    """The grid's steps over the rows as ((first, count, fetch), n_live),
    int32 each: a step multiplies the run of ``count`` row tiles from tile
    ``first`` of its x block on with the weights of expert ``fetch``, and
    the first ``n_live`` steps are those that do something.  By tile: a step
    a tile, the block IS the tile.  By group: step s is the s-th TOUCHED
    expert's group (groups lie in expert order: ``tile_plan``), the block all
    rows.  Either way the steps that do something come first, so that each
    one's weights are fetched while the one before multiplies; the grid
    ends with them on the chip, and where it cannot (the interpreter) the
    rest name the last one's expert: they move no weights."""
    tiles = tile_expert.shape[0]
    used = jnp.arange(tiles) < n_used
    if not by_group:
        return (jnp.zeros((tiles,), jnp.int32), used.astype(jnp.int32),
                tile_expert), n_used
    experts = jnp.arange(n_experts, dtype=jnp.int32)
    size = jnp.sum((tile_expert[:, None] == experts) & used[:, None], axis=0,
                   dtype=jnp.int32)  # tiles of each expert's group
    start = jnp.cumsum(size) - size
    touched = size > 0
    n_groups = jnp.sum(touched)
    # pick[s, e]: expert e is the s-th touched one
    steps = jnp.arange(min(n_experts, tiles))
    pick = touched & (jnp.cumsum(touched) - 1 == jnp.minimum(
        steps, n_groups - 1)[:, None])
    first, fetch = (jnp.sum(jnp.where(pick, v, 0), axis=1, dtype=jnp.int32)
                    for v in (start, experts))
    count = jnp.where(steps < n_groups,
                      jnp.sum(jnp.where(pick, size, 0), axis=1), 0)
    return (first, count.astype(jnp.int32), fetch), n_groups


def _grid(n: int, tn: int, nk: int, steps, by_group: bool) -> tuple:
    # Column blocks outermost.  By group the weight block's index changes at
    # every step that holds a group: each touched expert's block once a
    # call, the next fetched while all tiles of this group are multiplied.
    # By tile it changes with the tile's expert and, with several K blocks,
    # at every step.  ``steps`` bounds the walk over the rows: ``_steps``'
    # ``n_live`` (traced: Mosaic lowers a dynamic bound and still fetches
    # the next step's blocks under this step's matmul) or, for the
    # interpreter, the length of its vectors.
    return (n // tn, nk, steps) if by_group else (n // tn, steps, nk)


def _index_maps(nk: int, by_group: bool) -> dict:
    """Block index maps over ``_grid``; each takes the grid indices and then
    the scalar-prefetch operands (``_steps``' three and the layer)."""
    if by_group:  # (j, kk, s); x whole, the output a column block of all rows
        return {
            "x": lambda j, kk, s, first, count, fetch, layer: (0, 0),
            "w": lambda j, kk, s, first, count, fetch, layer: (
                layer[0], fetch[s], kk, j),
            "s": lambda j, kk, s, first, count, fetch, layer: (fetch[s], 0, j),
            "o": lambda j, kk, s, first, count, fetch, layer: (0, j)}

    def kk_of(t, kk, count):
        # A tile past the last group (the interpreter's walk alone reaches
        # one) parks on the block the sweep ended on: unchanged block index,
        # no DMA.
        return jnp.where(count[t] > 0, kk, nk - 1)

    return {  # (j, t, kk)
        "x": lambda j, t, kk, first, count, fetch, layer: (
            t, kk_of(t, kk, count)),
        "w": lambda j, t, kk, first, count, fetch, layer: (
            layer[0], fetch[t], kk_of(t, kk, count), j),
        "s": lambda j, t, kk, first, count, fetch, layer: (fetch[t], 0, j),
        "o": lambda j, t, kk, first, count, fetch, layer: (t, j)}


def _gmm_kernel(first_ref, count_ref, fetch_ref, layer_ref, x_ref, w_ref,
                *refs, quant: bool, tm: int, tk: int, by_group: bool):
    # One step: count[s] row tiles from tile first[s] of the x block on,
    # against w_ref [tk, tn], the block of the step's expert.  By tile x_ref
    # [tm, tk], o_ref and acc (f32, across the K sweep) [tm, tn]; by group
    # x_ref [rows, K] and o_ref, acc [rows, tn]; s_ref [1, tn] f32 (int8).
    del fetch_ref, layer_ref  # consumed by the index maps
    if quant:
        s_ref, o_ref, acc = refs
    else:
        o_ref, acc = refs
    s_axis, k_axis = (2, 1) if by_group else (1, 2)
    s, kk = pl.program_id(s_axis), pl.program_id(k_axis)
    last = kk == pl.num_programs(k_axis) - 1
    count = count_ref[s]

    # Rows no group holds, never gathered.  By group the whole column block,
    # before the first group stores into it (a call that touches no expert
    # has no first step and writes nothing).  By tile a step past the last
    # group: the interpreter's static walk has them and leaves exact zeros,
    # the chip's grid ends before them and leaves those rows unwritten.
    @pl.when((kk == 0) & (s == 0) if by_group else (count == 0) & last)
    def _blank():
        o_ref[...] = jnp.zeros_like(o_ref)

    def tile(i, carry):
        r = pl.ds(pl.multiple_of((first_ref[s] + i) * tm, tm), tm)

        @pl.when(kk == 0)
        def _init():
            acc[r, :] = jnp.zeros((tm, acc.shape[1]), acc.dtype)

        x = (x_ref[r, pl.ds(pl.multiple_of(kk * tk, tk), tk)] if by_group
             else x_ref[r, :])
        # widened here, tile by tile, on its way into the MXU: a widened
        # copy kept in VMEM costs more than it saves (PERF.md, PR 44)
        acc[r, :] += jax.lax.dot_general(
            x, w_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            y = acc[r, :] * s_ref[...] if quant else acc[r, :]
            o_ref[r, :] = y.astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, count, tile, None)


def grouped_matmul_pallas(x, w: Any, tile_expert, n_used, layer, *, tm: int,
                          interpret: bool = False) -> jax.Array:
    """x [n_tiles*tm, K]; w the stacked leaf [L, E, K, N] (array or int8
    ``{"q", "s"}``); tile_expert [n_tiles] int32; n_used (traced) the tiles
    that hold a group, the grid's bound on the chip; -> [rows, N], of which
    the rows past ``n_used * tm`` are unwritten on the chip (exact zeros
    from the interpreter, whose walk is the static one: tiles >= n_used
    repeat the last used tile's expert, so they move no weights there)."""
    quant = is_quantized(w)
    wq = w["q"] if quant else w
    rows, k = x.shape
    n_experts, _, n = wq.shape[1:]
    tk, tn = _blocks(k, n, wq.dtype.itemsize)
    nk = k // tk
    by_group = _by_group(rows, k, n, tn, wq.dtype.itemsize)
    steps, n_live = _steps(tile_expert, n_used, n_experts, by_group)
    index = _index_maps(nk, by_group)
    held = rows if by_group else tm  # rows of one x, out and acc block

    in_specs = [(pl.BlockSpec((rows, k), index["x"],
                              pipeline_mode=pl.Buffered(1)) if by_group
                 else pl.BlockSpec((tm, tk), index["x"])),
                pl.BlockSpec((None, None, tk, tn), index["w"])]
    operands = [*steps, jnp.asarray(layer, jnp.int32).reshape(1), x, wq]
    if quant:
        # One layer's scales as [E, 1, N] rows (a relayout of E*N floats).
        scales = jax.lax.dynamic_index_in_dim(w["s"], layer, 0, keepdims=False)
        in_specs.append(pl.BlockSpec((None, 1, tn), index["s"]))
        operands.append(scales[:, None, :])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, quant=quant, tm=tm, tk=tk,
                          by_group=by_group),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=_grid(n, tn, nk,
                       steps[0].shape[0] if interpret else n_live, by_group),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((held, tn), index["o"]),
            scratch_shapes=[pltpu.VMEM((held, tn), jnp.float32)],
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
