"""Ling-3.0-flash's mechanisms at the tiny preset on the CPU: delta-rule
linear attention (KDA) in five layers of six with a float32 matrix state a
head and a conv tail (through the bucket's insert at a true length, the chunk
stream's edges, a reused slot, a row that sits out; the chunked form against
the sequential recurrence; the decode kernel against its XLA form), a latent
layer closing every period (no query bottleneck, a value head narrower than
the query head, a head-wise output gate, latent rows for those layers alone),
group-limited routing, the experts held as one chip's share, the engine, the
counters and every refusal.

One limit, float32 against ``models/reference.py``: logits and the states a
cache holds within 1e-4 of the largest reference value (seen: 2e-5 on logits
of about 5, 3e-6 on states).  Two float32 programs that sum in different
orders differ by rounding alone, and the chunked form sums 16 positions at a
time through a triangular solve where the reference goes position by
position; the least visible wrong function here (a share that renormalises
over the experts it holds) moves the logits by over 1e-2
(``test_each_wrong_function_misses_the_reference``).

The tiny preset: 2 dense layers (kda, kda) and one rotated period (kda, kda,
kda, mla, kda, kda); at a depth of 12, the cell's, the four layers left over
(kda, kda, kda, mla) are a span of their own; 32 experts in 4 groups of which
group 0's 8 are held.  Every test that can shares ONE sequence of 24 tokens
(a second of 43), the bucket of 32 and the chunk of 8: the eager reference
compiles its operations anew for every length (~7 s) and a program of this
stack takes ~5 s.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import kda, mla, reference, transformer
from llm_instance_gateway_tpu.models.configs import (
    LING_3_FLASH,
    TINY_GLM_TEST,
    TINY_LFM2_TEST,
    TINY_LING_TEST,
    LayerKind,
)
from llm_instance_gateway_tpu.ops import pallas_kda
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from tests.test_window import (
    SLOTS,
    fresh_cache,
    programs,
    rel_err,
    served_logits,
)

CFG = TINY_LING_TEST
TOL = 1e-4
KDA, MLA = LayerKind(kda=True), LayerKind()


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(3),
                                   dtype=jnp.float32)


def sequence(n, seed=5):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


SEQ, LONG = sequence(24), sequence(43)


_WANTED: dict = {}


def wanted(params, seq, n, cfg=CFG):
    """(the reference's logits from position n - 1 on, each KDA layer's
    matrix states and conv history after the whole of ``seq``); one forward
    a (parameters, sequence)."""
    key = (id(params), cfg, bytes(np.asarray(seq)))
    if key not in _WANTED:
        states = []
        logits = reference.forward(cfg, params, jnp.asarray(seq),
                                   states=states)
        _WANTED[key] = (np.asarray(logits),
                        np.stack([s for s, _ in states]),
                        np.stack([c for _, c in states]))
    logits, want_s, want_c = _WANTED[key]
    return logits[n - 1:], want_s, want_c


def state_errs(cache, slot, want_s, want_c):
    """The slot's matrix states and conv history against the reference's."""
    return (rel_err(cache["kda"][:, slot], want_s),
            rel_err(cache["conv"][:, :, slot], want_c))


# -- the configuration --------------------------------------------------------

def test_the_published_preset_is_the_sources():
    c = LING_3_FLASH
    assert (c.d_model, c.n_layers, c.n_heads, c.d_ff) == (2560, 42, 32, 6144)
    assert (c.n_experts, c.n_experts_per_token, c.moe_d_ff) == (512, 8, 768)
    assert (c.n_group, c.topk_group, c.routed_scaling_factor) == (8, 4, 2.5)
    assert (c.kv_lora_rank, c.q_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (512, 0, 128, 64, 128)
    assert (c.kda_n_heads, c.kda_head_dim, c.kda_conv,
            c.kda_lower_bound) == (32, 128, 4, -5.0)
    assert (c.first_k_dense, c.n_shared_experts, c.vocab_size,
            c.rope_theta, c.norm_eps) == (2, 1, 157_184, 6e6, 1e-6)
    assert c.n_experts_local == 0 and c.experts_held == 512
    assert c.latent_lanes == 640 and c.kda_conv_dim == 12_288


@pytest.mark.parametrize("cfg", [LING_3_FLASH, CFG], ids=lambda c: c.name)
def test_a_latent_layer_closes_every_period_of_six(cfg):
    for l in range(cfg.n_layers):
        assert cfg.kind_of(l) == (MLA if (l + 1) % 6 == 0 else KDA)
    assert cfg.n_layers_of("kda") == cfg.n_layers - cfg.n_layers // 6
    assert cfg.n_layers_of("full") == cfg.n_layers // 6


def test_the_published_depth_builds_and_traces():
    """42 = 2 dense + 6 rotated periods + 4 left over; 512 experts all held;
    the whole vocabulary: the decode program traces with abstract shapes."""
    cfg = LING_3_FLASH
    assert cfg.group_spans(0, 2) == [(0, 2, (KDA,))]
    assert cfg.group_spans(2, 40) == [
        (2, 36, (KDA, KDA, KDA, MLA, KDA, KDA)),
        (38, 4, (KDA, KDA, KDA, MLA))]
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), quantize=True))
    assert p["layers"]["wq"]["q"].shape == (7, 2560, 32 * 192)
    assert p["layers"]["kda_in"]["q"].shape == (33, 2560, 5 * 4096)
    assert p["dense_layers"]["kda_in"]["q"].shape[0] == 2
    assert "wq" not in p["dense_layers"]
    assert p["layers"]["w_gate"]["q"].shape == (40, 512, 2560, 768)
    assert p["layers"]["router"].shape == (40, 2560, 512)
    cache = jax.eval_shape(
        lambda: transformer.init_decode_cache(cfg, 2, 256))
    _, out = jax.eval_shape(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)), p, cache)
    assert out["k"].shape == (7, 2, 256, 640)
    assert out["kda"].shape == (35, 2, 32, 128, 128)
    assert out["kda"].dtype == jnp.float32
    assert out["conv"].shape == (35, 3, 2, 12_288)
    assert "v" not in out


def test_a_share_holds_its_experts_and_the_routers_whole_width():
    cut = dataclasses.replace(LING_3_FLASH, n_layers=12, n_experts_local=128,
                              vocab_size=39_296)
    p = jax.eval_shape(lambda: transformer.init_params(
        cut, jax.random.PRNGKey(0), quantize=True))
    assert p["layers"]["w_gate"]["q"].shape == (10, 128, 2560, 768)
    assert p["layers"]["router"].shape == (10, 2560, 512)
    assert p["layers"]["router_bias"].shape == (10, 512)
    assert p["lm_head"]["q"].shape == (2560, 39_296)


@pytest.mark.parametrize("change, error", [
    (dict(layer_pattern=("kda", "full")), "mla layer"),
    (dict(kda_n_heads=0), "kda layer"),
    (dict(n_experts_local=64, expert_first=480), "n_experts_local"),
    (dict(n_group=7), "group-limited"),
    (dict(topk_group=9), "group-limited"),
    (dict(kv_lora_rank=0), "mla layer"),
])
def test_a_config_that_is_no_stack_is_refused(change, error):
    with pytest.raises(ValueError, match=error):
        dataclasses.replace(LING_3_FLASH, **change)


def test_a_block_over_the_bounds_reach_is_refused():
    with pytest.raises(ValueError, match="overflows float32"):
        kda.leaf_shapes(dataclasses.replace(CFG, kda_lower_bound=-6.0))


def test_the_leaves_of_a_period_are_one_stack_a_kind(params):
    layers = params["layers"]
    assert layers["kda_in"].shape[0] == 5 and layers["wq"].shape[0] == 1
    assert layers["w_head_gate"].shape == (1, 64, 4)
    assert layers["attn_norm"].shape[0] == 6
    assert layers["w_gate"].shape == (6, 8, 64, 32)   # the share's experts
    assert layers["router"].shape == (6, 64, 32)      # the router's width
    assert "wq_down" not in layers and "wk" not in layers
    assert params["dense_layers"]["kda_in"].shape[0] == 2
    # layer 5 of the model is the sparse group's 4th layer, its first latent
    kinds = CFG.group_spans(2, 6)[0][2]
    lps = {n: layers[n] for n in ("kda_in", "wq", "attn_norm")}
    third = transformer._period_layer(CFG, lps, kinds, 3)
    assert set(third) == {"wq", "attn_norm"}
    assert np.array_equal(third["wq"], layers["wq"][0])
    assert np.array_equal(third["attn_norm"], layers["attn_norm"][3])
    fifth = transformer._period_layer(CFG, lps, kinds, 4)
    assert set(fifth) == {"kda_in", "attn_norm"}
    assert np.array_equal(fifth["kda_in"], layers["kda_in"][3])


def test_the_cache_holds_rows_for_latent_layers_and_a_state_for_the_rest():
    cache = transformer.init_decode_cache(CFG, 3, 128, jnp.bfloat16)
    assert set(cache) == {"k", "kda", "conv", "length"}
    assert cache["k"].shape == (1, 3, 128, CFG.latent_lanes)
    assert cache["kda"].shape == (7, 3, 2, 16, 16)
    assert cache["kda"].dtype == jnp.float32
    assert cache["conv"].shape == (7, 3, 3, 96)
    assert cache["conv"].dtype == jnp.bfloat16
    assert transformer._carry_names(cache) == ("k", "kda", "conv")
    with pytest.raises(ValueError, match="int8"):
        transformer.init_decode_cache(CFG, 3, 128, quantized=True)


@pytest.mark.parametrize("cfg", [TINY_GLM_TEST, TINY_LFM2_TEST],
                         ids=lambda c: c.name)
def test_an_older_configuration_has_no_new_array(cfg):
    cache = transformer.init_decode_cache(cfg, 2, 64, jnp.float32)
    assert "kda" not in cache
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert not [n for n in p["layers"] if n.startswith("kda")
                or n in ("w_head_gate",)]
    assert p["layers"]["w_gate"].shape[1] == cfg.n_experts


# -- the recurrence's three forms ---------------------------------------------

def recurrence_inputs(s, decay, seed=0, b=2, h=2, dk=16):
    """q, k normed, v, g per channel in (-5, 0), beta, a state to enter
    with.  ``decay``: where the log decays lie."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = kda._l2(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dk))
    raw = jax.random.normal(ks[3], (b, s, h, dk))
    g = -5.0 * jax.nn.sigmoid({"strong": 6.0 + raw, "weak": -9.0 + raw,
                               "both ends": 12.0 * raw}[decay])
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dk))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("decay", ["strong", "weak", "both ends"])
@pytest.mark.parametrize("s", [1, 15, 16, 17, 32, 45])
def test_the_chunked_form_is_the_sequential_recurrence(s, decay):
    """Across block edges (16) and with decays at both ends of (-5, 0): at
    the strong end a block's running sum reaches -80, which a split about
    the block's start would send through e^80."""
    q, k, v, g, beta, s0 = recurrence_inputs(s, decay)
    if decay == "strong":
        assert float(jnp.min(g)) < -4.95
    if decay == "weak":
        assert float(jnp.max(g)) > -0.01
    want_o, want_s = kda.scan_sequential(q, k, v, g, beta, s0)
    got_o, got_s = kda.scan_chunked(q, k, v, g, beta, s0)
    assert bool(jnp.all(jnp.isfinite(got_o)))
    assert rel_err(got_o, want_o) < 1e-5
    assert rel_err(got_s, want_s) < 1e-5


def test_a_padded_position_leaves_the_state_alone():
    q, k, v, g, beta, s0 = recurrence_inputs(24, "both ends")
    live = jnp.arange(24) < 13
    g = jnp.where(live[None, :, None, None], g, 0.0)
    beta = jnp.where(live[None, :, None], beta, 0.0)
    _, want = kda.scan_sequential(q[:, :13], k[:, :13], v[:, :13], g[:, :13],
                                  beta[:, :13], s0)
    for scan in (kda.scan_sequential, kda.scan_chunked):
        _, got = scan(q, k, v, g, beta, s0)
        assert rel_err(got, want) < 1e-5


def test_the_delta_rule_is_its_definition():
    """One step against the matrix form: S' = (I - beta k k^T) Diag(e^g) S +
    beta k v^T, o = S'^T q."""
    *steps, s0 = recurrence_inputs(1, "both ends")
    q, k, v, g, beta = (t[:, 0] for t in steps)  # the one position
    o, new = pallas_kda.kda_update_xla(s0, q, k, v, g, beta)
    eye = jnp.eye(16)
    kk = k[..., :, None] * k[..., None, :]
    want = (jnp.einsum("bhij,bhjv->bhiv", eye - beta[..., None, None] * kk,
                       jnp.exp(g)[..., None] * s0)
            + beta[..., None, None] * k[..., :, None] * v[..., None, :])
    assert rel_err(new, want) < 1e-6
    assert rel_err(o, jnp.einsum("bhd,bhdv->bhv", q, want)) < 1e-6


@pytest.mark.parametrize("live", [(True, False, True), (False,) * 3,
                                  (True,) * 3, (False, False, True)],
                         ids=lambda l: "".join("x" if i else "-" for i in l))
def test_the_decode_kernel_is_its_xla_form(live):
    """Interpret mode at the kernel's own tile (128 x 128 a head, 16 heads a
    block, two blocks): the live rows of the one layer rewritten, everything
    else bit for bit what it was."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    n_l, b, h, d = 2, 3, 32, 128
    state = jax.random.normal(ks[0], (n_l, b, h, d, d))
    q = kda._l2(jax.random.normal(ks[1], (b, h, d))) * d ** -0.5
    k = kda._l2(jax.random.normal(ks[2], (b, h, d)))
    v = jax.random.normal(ks[3], (b, h, d))
    g = -5.0 * jax.nn.sigmoid(8.0 * jax.random.normal(ks[4], (b, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h)))
    live = jnp.asarray(live)
    assert not pallas_kda.shape_reasons(h, d, d)
    want_o, want_s = pallas_kda.kda_decode_update(
        state, q, k, v, g, beta, live, 1, use_kernel=False)
    got_o, got_s = pallas_kda.kda_decode_update(
        state, q, k, v, g, beta, live, 1, interpret=True)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < 1e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-5
    assert np.array_equal(got_s[0], state[0])
    dead = np.flatnonzero(~np.asarray(live))
    assert np.array_equal(np.asarray(got_s[1])[dead],
                          np.asarray(state[1])[dead])
    assert np.array_equal(np.asarray(got_o)[dead], np.zeros((len(dead), h, d)))


@pytest.mark.parametrize("shape, why", [((2, 16, 16), "128 keys"),
                                        ((12, 128, 128), "blocks of 8"),
                                        ((48, 128, 128), "one 128-row tile")])
def test_the_kernel_says_which_shapes_it_leaves_to_xla(shape, why):
    assert why in " ".join(pallas_kda.shape_reasons(*shape))


# -- the served programs against the reference --------------------------------

CASES = {
    "bucket": (SEQ, 21, None),
    "bucket at a block's edge": (LONG, 32, None),
    "stream, a whole last chunk": (LONG, 40, 8),
    "stream, a padded last chunk": (SEQ, 21, 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_is_the_references_forward(params, case):
    seq, n, chunk = CASES[case]
    want, want_s, want_c = wanted(params, seq, n)
    got, cache = served_logits(CFG, params, seq, n, chunk=chunk)
    assert rel_err(got, want) < TOL
    err_s, err_c = state_errs(cache, 1, want_s, want_c)
    assert err_s < TOL and err_c < TOL


def test_a_depth_that_splits_a_period_is_the_references_forward():
    """The cell's depth: 2 dense layers, a rotated period, and (kda, kda,
    kda, mla) left over as a span of their own over their rows of the sparse
    group's leaves."""
    cfg = dataclasses.replace(CFG, n_layers=12)
    assert [(f, n, len(k)) for f, n, k in cfg.group_spans(2, 10)] == [
        (2, 6, 6), (8, 4, 4)]
    p = transformer.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    assert p["layers"]["kda_in"].shape[0] == 8
    assert p["layers"]["wq"].shape[0] == 2
    want, want_s, want_c = wanted(p, SEQ, 21, cfg)
    got, cache = served_logits(cfg, p, SEQ, 21, chunk=8)
    assert rel_err(got, want) < TOL
    assert cache["k"].shape[0] == 2 and cache["kda"].shape[0] == 10
    err_s, err_c = state_errs(cache, 1, want_s, want_c)
    assert err_s < TOL and err_c < TOL


@pytest.mark.parametrize("n", [17, 18, 19, 24])
def test_insert_writes_the_state_at_the_true_length(params, n):
    """A bucket-padded prompt (all in the bucket of 32) leaves its last
    TRUE token's states and the conv history of its last three true
    positions: the steps that follow, and what they leave, are the
    reference's."""
    want, want_s, want_c = wanted(params, SEQ, n)
    got, cache = served_logits(CFG, params, SEQ, n)
    assert rel_err(got, want) < TOL
    err_s, err_c = state_errs(cache, 1, want_s, want_c)
    assert err_s < TOL and err_c < TOL
    assert int(cache["length"][1]) == 24
    assert float(jnp.max(jnp.abs(cache["kda"][:, 0]))) == 0.0


@pytest.mark.parametrize("n", [3, 8, 9, 24])
def test_the_stream_carries_the_state_over_its_edges(params, n):
    """Chunks of 8: a prompt shorter than the conv's history, one that ends
    on a chunk's edge, one a position past it, and one of three whole
    chunks; then steps to the sequence's end."""
    want, want_s, want_c = wanted(params, SEQ, n)
    got, cache = served_logits(CFG, params, SEQ, n, slot=2, chunk=8)
    assert rel_err(got, want) < TOL
    err_s, err_c = state_errs(cache, 2, want_s, want_c)
    assert err_s < TOL and err_c < TOL


@pytest.mark.parametrize("second", ["bucket", "stream"])
def test_a_reused_slot_starts_from_zeros(params, second):
    _, cache = served_logits(CFG, params, sequence(34, seed=1), 30)
    want, _, _ = wanted(params, SEQ, 20)
    got, _ = served_logits(CFG, params, SEQ, 20, cache=cache,
                           chunk=8 if second == "stream" else None)
    assert rel_err(got, want) < TOL


def test_a_row_that_sits_out_keeps_its_state(params):
    _, cache = served_logits(CFG, params, SEQ, 18, slot=0)
    before = jax.tree.map(np.asarray, cache)
    toks = jnp.zeros((SLOTS,), jnp.int32)
    _, after = programs(CFG).step(params, cache, toks, toks,
                                  jnp.zeros((SLOTS,), bool))
    for name in ("k", "kda", "conv"):
        assert np.array_equal(before[name], np.asarray(after[name])), name


# -- wrong functions miss ------------------------------------------------------

def _gate_without_the_bound(cfg, lp, f, live=None):
    """Mamba's gate: -exp(A_log) * softplus(f + dt_bias), unbounded."""
    h, dk = cfg.kda_n_heads, cfg.kda_head_dim
    raw = (f.astype(jnp.float32) + lp["kda_dt_bias"]).reshape(
        *f.shape[:-1], h, dk)
    g = -jnp.exp(lp["kda_a_log"])[:, None] * jax.nn.softplus(raw)
    return g if live is None else jnp.where(live[..., None, None], g, 0.0)


def _no_delta(state, q, k, v, g, beta, live=None):
    """Gated linear attention: the write without what the state holds of v
    (every row of these tests is live)."""
    new = (jnp.exp(g)[..., None] * state
           + (beta[..., None] * k)[..., None] * v[..., None, :])
    return jnp.sum(q[..., None] * new, axis=-2), new


def _renormalised_over_the_held(cfg, lp, x, live=None):
    plan = _REAL_ROUTE(cfg, lp, x, live)
    xf = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(jnp.dot(xf, lp["router"]))
    _, topi = jax.lax.top_k(transformer._group_limited(
        cfg, scores + lp["router_bias"]), cfg.n_experts_per_token)
    g = jnp.take_along_axis(scores, topi, axis=-1)
    g = jnp.where(topi < cfg.n_experts_local, g, 0.0)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return {**plan, "gates": g * cfg.routed_scaling_factor}


_REAL_ROUTE = transformer._moe_route

WRONG = {
    "a gate without the bound": (kda, "log_decay", _gate_without_the_bound),
    "the delta term dropped (decode)": (
        pallas_kda, "kda_update_xla", _no_delta),
    "a share that renormalises over the held experts": (
        transformer, "_moe_route", _renormalised_over_the_held),
    "no head-wise gate": (mla, "gate_heads",
                          lambda cfg, lp, hn, attn: attn),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_function_misses_the_reference(params, name, monkeypatch):
    module, attr, wrong = WRONG[name]
    seq, n = SEQ, 21
    want, _, _ = wanted(params, seq, n)
    monkeypatch.setattr(module, attr, wrong)
    programs.cache_clear()
    try:
        got, _ = served_logits(CFG, params, seq, n, chunk=8)
    finally:
        monkeypatch.undo()
        programs.cache_clear()
    assert rel_err(got, want) > 30 * TOL


FLIPS = {
    "ungrouped routing": dict(n_group=1, topk_group=1),
    "gates not renormalised": dict(norm_topk_prob=False),
    "the next chip's share": dict(expert_first=8),
    "a bound of -1": dict(kda_lower_bound=-1.0),
}


@pytest.mark.parametrize("name", sorted(FLIPS))
def test_each_flipped_rule_misses_the_reference(params, name):
    seq, n = SEQ, 21
    want, _, _ = wanted(params, seq, n)
    got, _ = served_logits(dataclasses.replace(CFG, **FLIPS[name]), params,
                           seq, n)
    assert rel_err(got, want) > 30 * TOL


# -- the share and the groups --------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Four chips share a layer: each holds 8 of the 32 experts and returns
    its part of the routed mix plus the shared expert's.  Their routed parts
    and the shared expert ONCE add up to the uncut reference's layer, gates
    normalised over all 4 chosen wherever they lie."""
    whole = dataclasses.replace(CFG, n_experts_local=0)
    p = transformer.init_params(whole, jax.random.PRNGKey(7),
                                dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[3], p["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (19, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference._mlp(whole, p["layers"], 3, x, None)
        shared = reference._gated(x, lp["ws_gate"], lp["ws_up"],
                                  lp["ws_down"])
    total, kept = 0.0, 0
    for chip in range(4):
        cfg = dataclasses.replace(CFG, expert_first=8 * chip)
        mine = {**lp, **{n: lp[n][8 * chip:8 * chip + 8]
                         for n in ("w_gate", "w_up", "w_down")}}
        y, tally = transformer._moe_mlp(cfg, mine, x)
        total = total + (y - shared)
        kept += int(tally[1])
        assert int(tally[4]) == 19 * 4 and int(tally[2]) <= 8
    assert kept == 19 * 4          # every assignment lies on one chip
    assert rel_err(total + shared, want) < 1e-5
    y_whole, tally = transformer._moe_mlp(whole, lp, x)
    assert rel_err(y_whole, want) < 1e-5
    assert int(tally[1]) == int(tally[4]) == 19 * 4


def test_group_limited_selection_differs_from_the_ungrouped_top_k():
    """Hand-built: 4 groups of 4, top-2 groups, top-4 experts.  Group 0
    holds the single best expert and nothing else; groups 1 and 2 hold two
    good ones each.  Ungrouped, the best four are 0.9 and three of the
    0.8s/0.7s; grouped, group 0 (0.9 + 0.0) loses to groups 1 and 2."""
    cfg = dataclasses.replace(CFG, n_experts=16, n_experts_local=0,
                              n_group=4, topk_group=2, n_experts_per_token=4)
    biased = jnp.asarray([[0.9, 0.0, 0.0, 0.0,   0.8, 0.7, 0.1, 0.0,
                           0.8, 0.6, 0.2, 0.0,   0.5, 0.3, 0.0, 0.0]])
    assert set(np.asarray(jax.lax.top_k(biased, 4)[1])[0]) == {0, 4, 8, 5}
    limited = transformer._group_limited(cfg, biased)
    assert set(np.asarray(jax.lax.top_k(limited, 4)[1])[0]) == {4, 5, 8, 9}
    assert np.all(np.isneginf(np.asarray(limited)[0, [0, 1, 2, 3, 12, 13]]))
    one = dataclasses.replace(cfg, n_group=1, topk_group=1)
    assert transformer._group_limited(one, biased) is biased


def test_the_bias_picks_and_never_weighs(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (5, CFG.d_model))
    plan = transformer._moe_route(CFG, lp, x)
    scores = jax.nn.sigmoid(x @ lp["router"])
    _, topi = jax.lax.top_k(transformer._group_limited(
        CFG, scores + lp["router_bias"]), 4)
    chosen = jnp.take_along_axis(scores, topi, axis=-1)
    want = 2.5 * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    assert rel_err(plan["gates"], want) < 1e-6


# -- the latent layer ----------------------------------------------------------

def test_heads_are_padded_to_the_kernels_one_size_and_cut_back():
    """24 + 8 wide queries and keys, 16 wide values -> 128, 128, 128 with
    the softmax's scale kept the true head's; GLM's heads pass as they
    are."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 4, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 4, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 4, 16))
    qp, kp, vp, cut = mla._kernel_heads(q, k, v)
    assert qp.shape[-1] == kp.shape[-1] == vp.shape[-1] == 128 and cut == 16
    got = jnp.einsum("bihd,bjhd->bhij", qp, kp) / 128 ** 0.5
    want = jnp.einsum("bihd,bjhd->bhij", q, k) / 32 ** 0.5
    assert rel_err(got, want) < 1e-6
    same = jnp.zeros((1, 5, 4, 256))
    assert mla._kernel_heads(same, same, same)[3] is None


def test_the_gate_is_a_number_a_head(params):
    lp = jax.tree.map(lambda a: a[0], {n: params["layers"][n]
                                       for n in ("w_head_gate",)})
    hn = jax.random.normal(jax.random.PRNGKey(0), (3, CFG.d_model))
    attn = jnp.ones((3, CFG.n_heads * CFG.v_head_dim))
    got = mla.gate_heads(CFG, lp, hn, attn).reshape(3, CFG.n_heads, -1)
    want = jax.nn.sigmoid(hn @ lp["w_head_gate"])
    assert rel_err(got[..., 0], want) < 1e-6
    assert rel_err(got[..., -1], want) < 1e-6
    assert mla.gate_heads(CFG, {}, hn, attn) is attn


def test_a_kda_layer_traces_its_scopes_and_no_attention(params):
    text = jax.jit(lambda p, c, t: transformer.decode_step(
        CFG, p, c, t, t, active=jnp.ones((SLOTS,), bool))).lower(
            params, fresh_cache(CFG), jnp.zeros((SLOTS,), jnp.int32)
        ).as_text(debug_info=True)
    for scope in ("kda.in_proj", "kda.conv", "kda.gate", "kda.update",
                  "kda.gate_norm", "kda.out_proj", "attn.head_gate",
                  "attn.absorb", "moe.route"):
        assert scope in text, scope


# -- the engine ---------------------------------------------------------------

def make_engine(params, cfg=CFG, **kw):
    kw = {"decode_slots": 2, "max_seq_len": 64, "prefill_buckets": (16,),
          **kw}
    return Engine(cfg, params, EngineConfig(**kw), eos_id=None,
                  dtype=jnp.float32)


def is_the_references_greedy(params, prompt, answer) -> bool:
    seq = np.asarray(list(prompt) + list(answer), np.int32)
    logits = wanted(params, seq, len(prompt))[0][:-1, :CFG.vocab_size]
    return list(np.argmax(logits, axis=-1)) == list(answer)


def test_engine_gives_the_references_tokens_with_slot_reuse(params):
    """Four requests over two slots, bucketed and chunk-streamed prompts
    mixed: greedy tokens equal the plain reference's, so no slot reads its
    last request's state and no step writes a row it should not."""
    engine = make_engine(params, stream_burst=4)
    # (16 + 8 and 35 + 8 tokens: the two lengths the reference has compiled)
    prompts = [list(range(3, 19)), list(range(3, 38)), list(range(50, 66)),
               list(range(40, 75))]
    engine.start()
    try:
        reqs = [engine.submit(Request(prompt_tokens=p, max_new_tokens=8))
                for p in prompts]
        for req in reqs:
            assert req.done.wait(300) and req.error is None, req.error
    finally:
        engine.stop()
    for prompt, req in zip(prompts, reqs):
        assert len(req.output_tokens) == 8
        assert is_the_references_greedy(params, prompt, req.output_tokens)
    hist = engine.profiler.hist_state()
    assert hist["kda_rows"] > 0 and hist["ssm_rows"] == 0
    assert hist["conv_rows"] == 0 and hist["latent_positions"] > 0
    moe = hist["moe"]
    assert 0 < moe["assignments"] < moe["assignments_routed"]
    assert moe["experts_touched"] <= 8 * moe["layer_steps"]
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert f"tpu:kda_state_rows_total {hist['kda_rows']}\n" in text
    assert f"tpu:moe_assignments_routed_total {moe['assignments_routed']}\n" in text
    assert f"tpu:moe_assignments_total {moe['assignments']}\n" in text


def test_counters_are_slots_times_steps_and_every_assignment(params):
    """One request of 12 prompt tokens and 9 new ones: the decode steps one
    row each; the router makes 4 assignments a live row a sparse layer, in
    the prompt program (12 positions) and in every step."""
    engine = make_engine(params)
    engine.start()
    try:
        req = engine.generate(Request(prompt_tokens=list(range(3, 15)),
                                      max_new_tokens=9), timeout_s=300)
        assert req.error is None
    finally:
        engine.stop()
    hist = engine.profiler.hist_state()
    steps = engine.profiler.dispatches["decode"]
    assert 8 <= steps <= 9
    assert hist["kda_rows"] == steps
    assert hist["latent_positions"] == sum(12 + j for j in range(1, steps + 1))
    # (the ninth dispatch, if the loop made one, held no live row)
    assert hist["moe"]["assignments_routed"] == 6 * 4 * (12 + 8)
    assert hist["moe"]["layer_steps"] == 6 * (1 + steps)


def test_a_model_without_a_share_routes_what_it_keeps():
    from llm_instance_gateway_tpu.models.configs import TINY_MOE_TEST

    cfg = TINY_MOE_TEST
    engine = make_engine(transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32), cfg,
        prefill_buckets=(8,))
    engine.start()
    try:
        engine.generate(Request(prompt_tokens=[3, 5, 7], max_new_tokens=4),
                        timeout_s=300)
    finally:
        engine.stop()
    hist = engine.profiler.hist_state()
    assert hist["kda_rows"] == 0
    assert hist["moe"]["assignments_routed"] == hist["moe"]["assignments"] > 0
    assert "tpu:kda_state_rows_total 0\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


def test_int8_weights_serve_the_kda_projections(params):
    from llm_instance_gateway_tpu.ops import quant

    q = quant.quantize_params(params)
    for group in ("dense_layers", "layers"):
        assert quant.is_quantized(q[group]["kda_in"])
        assert quant.is_quantized(q[group]["kda_out"])
        assert not quant.is_quantized(q[group]["kda_beta"])
    assert quant.is_quantized(q["layers"]["wq"])
    assert not quant.is_quantized(q["layers"]["w_head_gate"])
    # the reference reads the served weights
    lp = q["layers"]
    assert reference._weight(lp["kda_in"], 0).shape == (64, 160)


REFUSED = {
    "paged": (dict(paged_kv_block=16), {}, "paged-kv-block"),
    "prefix_cache": (dict(paged_kv_block=16, prefix_cache=True), {},
                     "prefix cache"),
    "kv_int8": (dict(kv_cache_quant="int8"), {}, "kv-quantize"),
    "role_prefill": (dict(role="prefill"), {}, "kv_transfer"),
    "role_decode": (dict(role="decode"), {}, "kv_transfer"),
    "speculative": (dict(speculative_k=2), dict(draft_cfg=CFG),
                    "--speculative"),
    "mesh": ({}, dict(mesh=types.SimpleNamespace(size=4)), "--mesh"),
    "adapters": ({}, dict(lora_manager=object()), "max-loras"),
    "prefill_batch": (dict(prefill_batch=4), {}, "--prefill-batch"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_engine_refuses_what_a_matrix_state_and_a_share_do_not_serve(
        params, case):
    engine_kw, ctor_kw, names = REFUSED[case]
    if "draft_cfg" in ctor_kw:
        ctor_kw = dict(ctor_kw, draft_params=params)
    with pytest.raises(ValueError, match="delta-rule matrix state.*share of "
                       "each layer's experts") as err:
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, **engine_kw),
               eos_id=None, dtype=jnp.float32, **ctor_kw)
    assert names in str(err.value) and CFG.name in str(err.value)


def test_what_the_layer_loop_does_not_scan_is_refused(params):
    with pytest.raises(NotImplementedError, match="latent"):
        transformer.extend_step(CFG, params, {}, jnp.zeros((1, 2), jnp.int32),
                                jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(NotImplementedError, match="LoRA"):
        transformer.prefill(CFG, params, jnp.zeros((1, 4), jnp.int32),
                            jnp.arange(4)[None], lora_bufs={"scale": None})
    with pytest.raises(NotImplementedError, match="adapter"):
        reference.forward(CFG, params, jnp.zeros((4,), jnp.int32), (None, 0))


@pytest.mark.parametrize("flags", [["--max-loras", "4"],
                                   ["--max-loras", "0", "--mesh", "tensor=2"]],
                         ids=["adapters", "mesh"])
def test_server_refuses_adapters_and_a_mesh_by_name(flags):
    from llm_instance_gateway_tpu.server import api_http

    with pytest.raises(SystemExit, match="ling-tiny.*--max-loras 0"):
        api_http.main(["--model", "ling-tiny", "--platform", "cpu", *flags])


def test_debug_device_reports_the_new_fields():
    import inspect

    from llm_instance_gateway_tpu.server import api_http

    src = inspect.getsource(api_http.ModelServer)
    for field in ("n_experts_local", "n_group", "topk_group", "kda_n_heads",
                  "kda_head_dim", "kda_conv", "kda_lower_bound",
                  "mla_head_gate"):
        assert f'"{field}"' in src, field


def test_the_presets_are_where_the_server_wrapper_looks():
    from llm_instance_gateway_tpu.models import mixtral

    assert mixtral.CONFIGS["ling-3.0-flash"] is LING_3_FLASH
    assert mixtral.CONFIGS["ling-tiny"] is CFG
