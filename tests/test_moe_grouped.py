"""The dropless expert dispatch (``transformer._moe_mlp``) against the
reference's all-experts mix (``models.reference._mlp``): every assignment
is computed, whatever the routing.

Contracts:
- a router forced to send every token to ONE expert (the case a capacity
  tile overflows on) still matches; so do uniform routing, a batch with
  empty slots, and a single token;
- both gate rules (Mixtral renormalises over the chosen, OLMoE does not);
- int8 stacks; per-layer leaves and the stacked leaves + layer index;
- the Pallas grouped matmul (interpret mode) equals the XLA tiles;
- the routing tally counts what was routed;
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
        assert [int(v) for v in tally0] == [1, 0, 0]

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
    assert [int(v) for v in tally] == [1, 20 * 8, int((counts > 0).sum())]


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("k,n", [(128, 256), (256, 128)])
def test_pallas_grouped_matmul_matches_xla_tiles(k, n, quant):
    """The kernel in interpret mode against the XLA form of the same tiles,
    over a stack of two layers, with tiles past the last group."""
    e, tm, n_tiles, n_used = 5, 16, 9, 6
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (2, e, k, n), jnp.float32) / np.sqrt(k)
    if quant:
        from llm_instance_gateway_tpu.ops.quant import quantize_weight
        w = quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (n_tiles * tm, k))
    te = jnp.array([0, 0, 1, 3, 4, 4, 4, 4, 4], jnp.int32)
    got = pallas_moe.grouped_matmul_pallas(x, w, te, n_used, 1, tm=tm,
                                           interpret=True)
    want = pallas_moe.grouped_matmul_xla(x, w, te, 1, tm=tm)
    np.testing.assert_allclose(got[: n_used * tm], want[: n_used * tm],
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[n_used * tm:]))) == 0.0


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
