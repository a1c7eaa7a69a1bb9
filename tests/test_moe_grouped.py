"""The dropless expert dispatch (``transformer._moe_mlp``) against the
reference's all-experts mix (``models.reference._mlp``): every assignment
is computed, whatever the routing.

Contracts:
- a router forced to send every token to ONE expert (the case a capacity
  tile overflows on) still matches; so do uniform routing, a batch with
  empty slots, and a single token;
- both gate rules (Mixtral renormalises over the chosen, OLMoE does not);
- int8 stacks; per-layer leaves and the stacked leaves + layer index;
- the Pallas grouped matmul (interpret mode) equals the XLA tiles, by group
  and by tile, with a column in one K block and in several;
- by group a touched expert's weight block is fetched once a call;
- the routing tally counts what was routed, and the tiles it took;
- the two ways into the layout (a decode batch's row scatter, a prompt's
  row gather from the sorted layout) give the same layout, ``y`` and tally
  bit for bit, and a prompt-sized program holds no scatter of token rows;
- compiled FLOPs track the assignments made, not experts x tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import reference, transformer
from llm_instance_gateway_tpu.models.configs import (
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
)
from llm_instance_gateway_tpu.ops import pallas_moe
from llm_instance_gateway_tpu.ops.quant import quantize_params

CFGS = {"mixtral": TINY_MOE_TEST, "olmoe": TINY_OLMOE_TEST}
# float32 on both sides; what differs is the order of summation (tiles of
# one expert against a loop over all) and the default matmul precision.
TOL = 2e-5


@pytest.fixture(scope="module", params=sorted(CFGS))
def model(request):
    cfg = CFGS[request.param]
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    return cfg, params


def layer0(params):
    return jax.tree.map(lambda a: a[0], {
        k: params["layers"][k]
        for k in ("router", "w_gate", "w_up", "w_down")})


def ref_mix(cfg, params, x):
    """The reference's sparse MLP over layer 0 of the stacked tree."""
    return reference._mlp(cfg, params["layers"], 0, x, None)


def rows(cfg, n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, cfg.d_model),
                             jnp.float32)


def close(got, want, tol=TOL):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(scale, 1.0)


class TestDroplessMatchesReference:
    @pytest.mark.parametrize("t", [1, 7, 64, 200])
    def test_uniform_routing(self, model, t):
        cfg, params = model
        x = rows(cfg, t)
        y, tally = transformer._moe_mlp(cfg, layer0(params), x)
        close(y, ref_mix(cfg, params, x))
        assert int(tally[1]) == t * cfg.n_experts_per_token

    def test_every_token_to_one_expert(self, model):
        """The router's column 0 dominates: every token's first choice is
        expert 0, whose group is T rows against a mean of T*k/E."""
        cfg, params = model
        x = jnp.abs(rows(cfg, 48)) + 0.1
        # x > 0, so logit_0 = 5 * sum(x) towers over every other expert's
        router = params["layers"]["router"].at[:, :, 0].set(5.0)
        forced = dict(params, layers=dict(params["layers"], router=router))
        y, tally = transformer._moe_mlp(cfg, layer0(forced), x)
        close(y, ref_mix(cfg, forced, x))
        _, topi = jax.lax.top_k(x @ router[0], cfg.n_experts_per_token)
        assert int(jnp.sum(topi == 0)) == 48  # expert 0's group: every token

    def test_empty_slots_route_nowhere(self, model):
        cfg, params = model
        x = rows(cfg, 8)
        live = jnp.array([True, False, True, False, False, True, False, False])
        y, tally = transformer._moe_mlp(cfg, layer0(params), x, live)
        want = ref_mix(cfg, params, x)
        close(y[live], want[live])
        assert float(jnp.max(jnp.abs(y[~live]))) == 0.0
        assert int(tally[1]) == 3 * cfg.n_experts_per_token
        none, tally0 = transformer._moe_mlp(
            cfg, layer0(params), x, jnp.zeros((8,), bool))
        assert float(jnp.max(jnp.abs(none))) == 0.0
        tiles = pallas_moe.n_tiles(
            8 * cfg.n_experts_per_token, cfg.n_experts, pallas_moe.tile_rows(
                8 * cfg.n_experts_per_token, cfg.n_experts))
        assert [int(v) for v in tally0] == [1, 0, 0, 0, 0, tiles]

    def test_stacked_leaves_and_layer_index(self, model):
        cfg, params = model
        x = rows(cfg, 16)
        lp = {k: params["layers"][k] for k in ("w_gate", "w_up", "w_down")}
        lp.update(router=params["layers"]["router"][1], layer=jnp.int32(1))
        y, _ = transformer._moe_mlp(cfg, lp, x)
        close(y, reference._mlp(cfg, params["layers"], 1, x, None))

    def test_int8_stacks(self, model):
        cfg, params = model
        qp = quantize_params(params)
        x = rows(cfg, 32)
        y, _ = transformer._moe_mlp(cfg, layer0(qp), x)
        # The reference dequantises the same leaves: same weights.
        close(y, ref_mix(cfg, qp, x))


def test_gate_rules_differ_and_each_matches():
    """OLMoE's weights sum to less than 1, Mixtral's to 1: swapping the
    rule on either model moves the output well past the tolerance."""
    for cfg in CFGS.values():
        params = transformer.init_params(cfg, jax.random.PRNGKey(5),
                                         dtype=jnp.float32)
        x = rows(cfg, 12)
        other = dataclasses.replace(cfg, norm_topk_prob=not cfg.norm_topk_prob)
        y, _ = transformer._moe_mlp(cfg, layer0(params), x)
        wrong, _ = transformer._moe_mlp(other, layer0(params), x)
        want = ref_mix(cfg, params, x)
        close(y, want)
        assert float(jnp.max(jnp.abs(wrong - want))) > 1e3 * TOL


def test_tally_counts_assignments_and_touched_experts():
    cfg = TINY_OLMOE_TEST
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    x = rows(cfg, 20)
    lp = layer0(params)
    _, tally = transformer._moe_mlp(cfg, lp, x)
    _, topi = jax.lax.top_k(x @ lp["router"], cfg.n_experts_per_token)
    counts = np.bincount(np.asarray(topi).ravel(), minlength=cfg.n_experts)
    tm = pallas_moe.tile_rows(20 * 8, cfg.n_experts)
    assert [int(v) for v in tally] == [
        1, 20 * 8, int((counts > 0).sum()), int((-(-counts // tm)).sum()),
        20 * 8,  # without a share every routed assignment is computed
        pallas_moe.n_tiles(20 * 8, cfg.n_experts, tm)]  # the layout's bound


def test_tally_counts_the_tiles_of_a_group_that_outgrows_one():
    """Every token's first choice forced onto expert 0: its group of 48
    rows takes more tiles than one, and the fourth count says how many."""
    cfg = TINY_OLMOE_TEST
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    x = jnp.abs(rows(cfg, 48)) + 0.1
    lp = layer0(params)
    lp["router"] = lp["router"].at[:, 0].set(5.0)
    _, tally = transformer._moe_mlp(cfg, lp, x)
    _, topi = jax.lax.top_k(x @ lp["router"], cfg.n_experts_per_token)
    counts = np.bincount(np.asarray(topi).ravel(), minlength=cfg.n_experts)
    tm = pallas_moe.tile_rows(48 * 8, cfg.n_experts)
    assert counts[0] == 48 > tm
    touched, tiles = int(tally[2]), int(tally[3])
    assert tiles == int((-(-counts // tm)).sum()) > touched


# Groups of 33, 0, 1, 17 and 5 rows in tiles of 16: three consecutive tiles
# of expert 0, an expert nobody chose, a group of one row, two tiles of
# expert 3, the expert changing between tiles, and two tiles past the last
# group.
SKEWED_SIZES, SKEWED_TM, SKEWED_TILES = (33, 0, 1, 17, 5), 16, 9


def skewed_plan(k, seed=1):
    """(x laid out by group with zeroed padding rows, tile_expert, n_used)."""
    sizes = jnp.asarray(SKEWED_SIZES, jnp.int32)
    first_row, te, n_used = pallas_moe.tile_plan(sizes, SKEWED_TM,
                                                 SKEWED_TILES)
    assert [int(v) for v in te] == [0, 0, 0, 2, 3, 3, 4, 4, 4]
    assert int(n_used) == 7
    row = np.arange(SKEWED_TILES * SKEWED_TM)
    held = np.zeros(row.shape, bool)
    for start, size in zip(np.asarray(first_row), SKEWED_SIZES):
        held |= (row >= start) & (row < start + size)
    x = jax.random.normal(jax.random.PRNGKey(seed), (row.size, k))
    return x * held[:, None], te, n_used


def cut_k_in(monkeypatch, k, n, itemsize, nk, by_group):
    """A block budget under which a column of K rows is cut into ``nk``
    blocks, and the order the kernel then walks the rows in."""
    tn = next(c for c in (1024, 512, 256, 128) if n % c == 0)
    monkeypatch.setattr(pallas_moe, "_W_BLOCK_BYTES", k // nk * tn * itemsize)
    assert pallas_moe._blocks(k, n, itemsize) == (k // nk, tn)
    monkeypatch.setattr(pallas_moe, "_by_group", lambda *shape: by_group)


def stack_of_two(k, n, quant, e=len(SKEWED_SIZES)):
    w = jax.random.normal(jax.random.PRNGKey(0), (2, e, k, n),
                          jnp.float32) / np.sqrt(k)
    if quant:
        from llm_instance_gateway_tpu.ops.quant import quantize_weight
        w = quantize_weight(w)
    return w


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("k,n,nk,by_group", [
    (128, 256, 1, True), (256, 128, 1, True), (512, 256, 1, False),
    (512, 256, 2, True), (512, 256, 4, True),
    (512, 256, 2, False), (512, 256, 4, False)])
def test_pallas_grouped_matmul_matches_xla_tiles(k, n, nk, by_group, quant,
                                                 monkeypatch):
    """The kernel in interpret mode against the XLA form of the same tiles,
    over layer 1 of a stack of two, a column in one K block and cut in two
    and in four, by group and by tile, with the skewed plan above: tiles
    past the last group are exact zeros."""
    cut_k_in(monkeypatch, k, n, 1 if quant else 4, nk, by_group)
    w = stack_of_two(k, n, quant)
    x, te, n_used = skewed_plan(k)
    got = pallas_moe.grouped_matmul_pallas(x, w, te, n_used, 1,
                                           tm=SKEWED_TM, interpret=True)
    want = pallas_moe.grouped_matmul_xla(x, w, te, 1, tm=SKEWED_TM)
    used = int(n_used) * SKEWED_TM
    np.testing.assert_allclose(got[:used], want[:used], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[used:]))) == 0.0
    other = pallas_moe.grouped_matmul_pallas(x, w, te, n_used, 0,
                                             tm=SKEWED_TM, interpret=True)
    assert float(jnp.max(jnp.abs(other[:used] - got[:used]))) > 0.1


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("nk", [1, 2, 4])
def test_both_orders_add_over_k_alike(nk, quant, monkeypatch):
    """By group and by tile accumulate the same K blocks in the same order
    into f32: the same bits, whatever the plan; and nothing used is nothing
    written."""
    k, n = 512, 256
    w = stack_of_two(k, n, quant)
    x, te, n_used = skewed_plan(k, seed=2)
    outs = []
    for by_group in (True, False):
        with monkeypatch.context() as m:
            cut_k_in(m, k, n, 1 if quant else 4, nk, by_group)
            outs.append([pallas_moe.grouped_matmul_pallas(
                x, w, te, used, 1, tm=SKEWED_TM, interpret=True)
                for used in (n_used, 0)])
    assert bool(jnp.array_equal(outs[0][0], outs[1][0]))
    assert float(jnp.max(jnp.abs(outs[0][0]))) > 0.1
    for out in (outs[0][1], outs[1][1]):
        assert float(jnp.max(jnp.abs(out))) == 0.0


def walk(k, n, itemsize, te, n_used, n_experts, tm, interpret=False):
    """Walk the kernel's grid in its order, under the bound it has on the
    chip (``_steps``' live steps) or under the interpreter's static one,
    through the index maps: ([(column block, row step, K block) of every
    step that multiplies something], the steps whose weight block index
    differs from the step before's: each is one DMA of a [tk, tn] block)."""
    tk, tn = pallas_moe._blocks(k, n, itemsize)
    by_group = pallas_moe._by_group(te.shape[0] * tm, k, n, tn, itemsize)
    steps, n_live = pallas_moe._steps(te, n_used, n_experts, by_group)
    w_index = pallas_moe._index_maps(k // tk, by_group)["w"]
    bound = steps[0].shape[0] if interpret else int(n_live)
    grid = pallas_moe._grid(n, tn, k // tk, bound, by_group)
    layer = jnp.ones((1,), jnp.int32)
    count = np.asarray(steps[1])
    visits, fetches, before = [], 0, None
    for j, a, b in np.ndindex(*grid):
        s, kk = (b, a) if by_group else (a, b)
        if count[s]:
            visits.append((j, s, kk))
        block = tuple(int(v) for v in w_index(j, a, b, *steps, layer))
        fetches += block != before
        before = block
    return visits, fetches


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("nk,by_group", [(1, True), (2, True), (4, True),
                                         (1, False), (2, False), (4, False)])
def test_a_touched_experts_block_is_fetched_once_a_call(nk, by_group,
                                                        interpret,
                                                        monkeypatch):
    """The invariant of PR 44, without a chip: by group the weight
    operand's block index changes touched experts x N/tn x nk times a call
    whatever the tiles a group takes; by tile, once K is cut, used tiles x
    N/tn x nk times: every tile of a group fetches the column again.  Under
    the chip's bound (the live steps) as under the interpreter's (the whole
    layout, whose steps past the last group park)."""
    k, n = 512, 768  # three column blocks of 256
    cut_k_in(monkeypatch, k, n, 1, nk, by_group)
    _, te, n_used = skewed_plan(k)
    touched = sum(size > 0 for size in SKEWED_SIZES)
    assert (touched, int(n_used)) == (4, 7)
    _, got = walk(k, n, 1, te, n_used, len(SKEWED_SIZES), SKEWED_TM,
                  interpret)
    tiles_fetch = int(n_used) if nk > 1 and not by_group else touched
    assert got == tiles_fetch * 3 * nk


@pytest.mark.parametrize("nk,by_group", [(1, True), (2, True), (4, True),
                                         (1, False), (2, False), (4, False)])
def test_the_grid_ends_at_the_last_step_that_holds_a_group(nk, by_group,
                                                           monkeypatch):
    """Under its bound on the chip the grid IS the live steps: every
    (column block, tile or touched group, K block) is visited once, in the
    order the static walk visits them, and no step lies past ``n_used``
    tiles / the touched groups; the static walk (the interpreter's) has the
    same live steps among the layout's."""
    k, n = 512, 768
    cut_k_in(monkeypatch, k, n, 1, nk, by_group)
    _, te, n_used = skewed_plan(k)
    live = 4 if by_group else 7  # touched experts | tiles that hold a group
    args = (k, n, 1, te, n_used, len(SKEWED_SIZES), SKEWED_TM)
    tk, tn = pallas_moe._blocks(k, n, 1)
    steps, n_live = pallas_moe._steps(te, n_used, len(SKEWED_SIZES),
                                      by_group)
    assert int(n_live) == live
    grid = pallas_moe._grid(n, tn, nk, int(n_live), by_group)
    assert grid == ((3, nk, live) if by_group else (3, live, nk))
    visits, _ = walk(*args)
    assert len(visits) == int(np.prod(grid)) == 3 * live * nk
    assert sorted(visits) == [(j, s, kk) for j in range(3)
                              for s in range(live) for kk in range(nk)]
    static, _ = walk(*args, interpret=True)
    assert static == visits
    layout = pallas_moe._grid(n, tn, nk, steps[0].shape[0], by_group)
    assert int(np.prod(layout)) == 3 * nk * (5 if by_group else SKEWED_TILES)
    first, count = np.asarray(steps[0]), np.asarray(steps[1])
    assert count[:live].all() and not count[live:].any()
    if by_group:  # every tile of the plan is in exactly one step's run
        runs = [t for s in range(live)
                for t in range(first[s], first[s] + count[s])]
        assert runs == list(range(int(n_used)))


@pytest.mark.parametrize("by_group", [True, False])
def test_nothing_used_is_a_grid_of_no_steps(by_group, monkeypatch):
    """A block whose rows are all frozen, a step none of whose rows routes
    to the held experts: ``n_used`` 0 bounds a grid of zero steps, nothing
    is visited and no weight block moves; the interpreter's static walk
    multiplies nothing either."""
    k, n = 512, 768
    cut_k_in(monkeypatch, k, n, 1, 2, by_group)
    _, te, n_used = pallas_moe.tile_plan(
        jnp.zeros((len(SKEWED_SIZES),), jnp.int32), SKEWED_TM, SKEWED_TILES)
    assert int(n_used) == 0
    steps, n_live = pallas_moe._steps(te, n_used, len(SKEWED_SIZES),
                                      by_group)
    assert int(n_live) == 0 and not np.asarray(steps[1]).any()
    grid = pallas_moe._grid(n, 256, 2, int(n_live), by_group)
    assert int(np.prod(grid)) == 0
    args = (k, n, 1, te, n_used, len(SKEWED_SIZES), SKEWED_TM)
    assert walk(*args) == ([], 0)
    assert walk(*args, interpret=True)[0] == []


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("by_group", [True, False])
def test_the_interpreter_alone_keeps_the_layouts_bound(by_group, monkeypatch):
    """The program for the chip bounds ONE grid dimension by a traced scalar
    (the rows': tiles by tile, touched groups by group); the interpreter,
    which takes no dynamic bound, walks the layout's static one, and its
    steps past the last group still leave exact zeros in rows that held
    something else before."""
    k, n = 512, 256
    cut_k_in(monkeypatch, k, n, 4, 2, by_group)
    w = stack_of_two(k, n, False)
    x, te, n_used = skewed_plan(k)
    grids = {}
    for interpret in (True, False):
        jaxpr = jax.make_jaxpr(
            lambda x, w, te, used: pallas_moe.grouped_matmul_pallas(
                x, w, te, used, 1, tm=SKEWED_TM, interpret=interpret))(
                    x, w, te, n_used)
        (call,) = _pallas_calls(jaxpr.jaxpr)
        grids[interpret] = call.params["grid_mapping"]
    rows_axis = 2 if by_group else 1
    static = [1, 2, 5] if by_group else [1, SKEWED_TILES, 2]
    assert list(grids[True].grid) == static
    assert grids[True].num_dynamic_grid_bounds == 0
    assert grids[False].num_dynamic_grid_bounds == 1
    assert [d for i, d in enumerate(grids[False].grid) if i != rows_axis] == [
        d for i, d in enumerate(static) if i != rows_axis]
    assert not isinstance(grids[False].grid[rows_axis], int)
    # every row of x non-zero: what the tiles past the last group would give
    # if they were multiplied is not zeros
    full = jnp.where(x == 0, 1.0, x)
    got = pallas_moe.grouped_matmul_pallas(full, w, te, n_used, 1,
                                           tm=SKEWED_TM, interpret=True)
    used = int(n_used) * SKEWED_TM
    assert float(jnp.min(jnp.max(jnp.abs(got[:used]), axis=1))) > 0.0
    assert float(jnp.max(jnp.abs(got[used:]))) == 0.0


@pytest.mark.parametrize("e,k,n,assignments,by_group", [
    (8, 4096, 14336, 64, True),      # Mixtral gate/up, a decode step
    (8, 14336, 4096, 64, True),      # ... down
    (8, 4096, 14336, 1024, True),    # ... gate/up, a 512-token prompt
    (8, 14336, 4096, 512, False),    # ... down, 256 tokens: x is 40 MB
    (8, 4096, 14336, 2048, False),   # ... 1,024 tokens: 2,944 rows
    (64, 2048, 1024, 256, False),    # OLMoE gate/up, a decode step
    (64, 1024, 2048, 256, False),    # ... down
    (64, 2048, 1536, 128, False),    # GLM-4.7-Flash gate/up
    (64, 1536, 2048, 128, False),    # ... down
])
def test_the_grid_walks_groups_where_experts_are_wide(e, k, n, assignments,
                                                      by_group):
    """The order comes from the shapes: by group where x whole is at most
    half of one expert's int8 matrix and all rows fit VMEM; the many narrow
    experts of OLMoE and GLM keep the order by tile, block sizes and all."""
    tm = pallas_moe.tile_rows(assignments, e)
    rows = pallas_moe.n_tiles(assignments, e, tm) * tm
    tk, tn = pallas_moe._blocks(k, n, 1)
    assert pallas_moe._by_group(rows, k, n, tn, 1) == by_group
    if e == 64:  # a whole column a block, as before the budget grew
        assert tk == k and tk * tn <= 2 << 20


def test_tile_plan_adapts_to_experts_and_rows():
    """One rule from E, k and T: decode-sized batches get 16-row tiles and
    prefill 128, and the static tile count bounds any routing."""
    assert pallas_moe.tile_rows(32 * 8, 64) == 16     # OLMoE decode
    assert pallas_moe.tile_rows(32 * 2, 8) == 16      # Mixtral decode
    assert pallas_moe.tile_rows(1024 * 8, 64) == 128  # OLMoE prefill
    for m, e in ((8, 64), (256, 64), (64, 8), (8192, 64), (2048, 8)):
        tm = pallas_moe.tile_rows(m, e)
        worst = min(e, m) - 1 + -(-(m - (min(e, m) - 1)) // tm)
        assert pallas_moe.n_tiles(m, e, tm) >= worst


def test_flops_track_assignments_not_experts():
    """Compiled FLOPs of the sparse layer stay near the rows it lays out
    (assignments + tile padding), far below computing all E experts."""
    cfg = TINY_OLMOE_TEST
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    lp, x = layer0(params), rows(cfg, 256)

    def flops(fn):
        return jax.jit(fn).lower(x).compile().cost_analysis()["flops"]

    sparse = flops(lambda v: transformer._moe_mlp(cfg, lp, v)[0])
    dense = flops(lambda v: reference._mlp(cfg, params["layers"], 0, v, None))
    assert sparse < dense / 2


# The two ways into the layout (``transformer._lay_out``), each forced at
# every size by moving the crossing: the widths of the three sparse models
# the benchmark serves, as routing arithmetic over tiny rows.
DISPATCH_CFGS = {
    "mixtral-8-top2": TINY_MOE_TEST,
    "olmoe-64-top8": TINY_OLMOE_TEST,
    "smallthinker-64-top6": dataclasses.replace(
        TINY_OLMOE_TEST, n_experts_per_token=6, norm_topk_prob=True),
}
ROUTINGS = ("drawn", "dead-rows", "one-expert", "expert-unchosen")


def routed(cfg, t, routing):
    """(lp, x, live) of one sparse layer: as drawn; with about a fifth of
    the rows dead; with every token's first choice forced onto expert 0 (a
    group of ``t`` rows: many tiles); with expert 3 chosen by no row."""
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    lp, x, live = layer0(params), jnp.abs(rows(cfg, t)) + 0.1, None
    if routing == "dead-rows":
        live = jax.random.uniform(jax.random.PRNGKey(7), (t,)) >= 0.2
        live = live.at[0].set(t == 1)  # a dead and a live row at least
    elif routing == "one-expert":
        lp["router"] = lp["router"].at[:, 0].set(5.0)
    elif routing == "expert-unchosen":
        lp["router"] = lp["router"].at[:, 3].set(-5.0)
    return lp, x, live


def in_both_forms(monkeypatch, cfg, lp, x, live):
    """{form: (plan, layout, y, tally)} with the crossing moved so that the
    scatter, then the gather, lays these rows out."""
    out = {}
    for form, most in (("scatter", 1 << 30), ("gather", 0)):
        monkeypatch.setattr(transformer, "_SCATTER_MAX_ASSIGN", most)
        plan = transformer._moe_route(cfg, lp, x, live)
        assert (plan["src"] is None) == (form == "scatter")
        out[form] = (plan, transformer._lay_out(
            x, plan, cfg.n_experts_per_token),
            *transformer._moe_experts(cfg, lp, x, plan))
    return out


ROUTED_SIZES = {"drawn": (1, 7, 32, 200, 1024),
                "dead-rows": (1, 7, 32, 200, 1024),
                "one-expert": (32, 200), "expert-unchosen": (7, 200)}


@pytest.mark.parametrize("routing,t", [
    (routing, t) for routing in ROUTINGS for t in ROUTED_SIZES[routing]])
@pytest.mark.parametrize("name", sorted(DISPATCH_CFGS))
def test_gathered_layout_is_the_scattered_one(name, routing, t, monkeypatch):
    cfg = DISPATCH_CFGS[name]
    e, k = cfg.n_experts, cfg.n_experts_per_token
    lp, x, live = routed(cfg, t, routing)
    forms = in_both_forms(monkeypatch, cfg, lp, x, live)
    plan, want_x_e, want_y, want_tally = forms["scatter"]
    new_plan, x_e, y, tally = forms["gather"]
    row = np.asarray(plan["row"])
    np.testing.assert_array_equal(new_plan["row"], row)
    held = row[row < plan["n_rows"]]
    # the premise: rows are assigned (as many as are live), the case is the
    # one its name says, and the layout holds each assignment's token row
    n_live = t if live is None else int(live.sum())
    assert held.size == n_live * k == int(want_tally[1])
    assert len(set(held.tolist())) == held.size
    sizes = np.bincount(np.asarray(plan["tile_expert"])[held // plan["tm"]],
                        minlength=e)
    if routing == "dead-rows":
        assert 0 < n_live < t or t == 1
    elif routing == "one-expert":
        assert sizes[0] == t > plan["tm"]
    elif routing == "expert-unchosen":
        assert sizes[3] == 0 < sizes.sum()
    np.testing.assert_array_equal(
        np.asarray(want_x_e)[row[row < plan["n_rows"]]],
        np.repeat(np.asarray(x), k, axis=0)[row < plan["n_rows"]])
    # the assigned rows bit for bit, and zeros wherever no row is assigned
    np.testing.assert_array_equal(x_e, want_x_e)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(tally, want_tally)
    if live is not None:
        assert not np.asarray(y)[~np.asarray(live)].any()


def test_the_form_is_chosen_from_the_static_assignment_count():
    """Decode batches of the benchmark's cells (64-256 assignments) scatter,
    every prefill bucket and chunk gathers; a key that would not fit an
    int32 is never built."""
    assert not any(transformer._gathers_in(n, e) for n, e in (
        (1, 8), (32 * 2, 8), (32 * 4, 64), (32 * 6, 64), (32 * 8, 64)))
    assert all(transformer._gathers_in(n, e) for n, e in (
        (257, 64), (64 * 6, 64), (64 * 8, 64), (1024 * 2, 8), (1024 * 8, 64),
        (8192 * 8, 256)))
    assert not transformer._gathers_in(1 << 24, 64)
    cfg, x = TINY_OLMOE_TEST, rows(TINY_OLMOE_TEST, 33)
    lp = routed(cfg, 33, "drawn")[0]
    assert transformer._moe_route(cfg, lp, x[:32])["src"] is None
    assert transformer._moe_route(cfg, lp, x)["src"].shape == (
        transformer._moe_route(cfg, lp, x)["n_rows"],)


def _row_scatters(jaxpr, d):
    """Every scatter equation of a jaxpr, sub-jaxprs included, that writes
    ``[*, d]`` rows."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter") and any(
                v.aval.ndim == 2 and v.aval.shape[-1] == d
                for v in eqn.outvars):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _row_scatters(sub, d)


@pytest.mark.parametrize("name", sorted(DISPATCH_CFGS))
def test_a_prompt_sized_program_scatters_no_rows(name):
    """Structure, not timing: the sparse layer over a prompt's tokens holds
    no scatter of ``[*, d_model]`` rows (a decode batch's does: the test
    can see one)."""
    cfg = DISPATCH_CFGS[name]
    lp = routed(cfg, 1, "drawn")[0]

    def scatters(t):
        jaxpr = jax.make_jaxpr(
            lambda x: transformer._moe_mlp(cfg, lp, x))(rows(cfg, t))
        return list(_row_scatters(jaxpr.jaxpr, cfg.d_model))

    assert len(scatters(32)) == 1
    assert scatters(200) == [] and scatters(1024) == []
