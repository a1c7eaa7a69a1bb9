"""LFM2's mechanisms at the tiny preset on the CPU: layers WITHOUT attention
(a gated short convolution in its place in three layers of four), K and V in
the attention layers alone, a conv state a slot beside the lanes (through the
bucket's insert at a true length, the chunk stream's edges, a reused slot, a
row that sits out), a period rotated by two leading dense layers, the leaves
of a period one stack a kind, 64-wide heads two to a cache row, the per-head
QK-norm, the tied head, the engine, the counter and every refusal of what a
conv state does not serve.

One limit, float32 against ``models/reference.py``: logits, and the conv
state a cache holds, within 1e-5 of the largest reference value (seen: 3e-6
and 1e-5 of a state about 1).  Two float32 programs that sum in different
orders differ by rounding alone; the least visible thing that can be got
wrong here, a chunk that forgets the ONE older of its two carried inputs,
moves the logits by 1e-3 (``test_each_wrong_function_misses_the_reference``).

The tiny preset: 2 dense layers (conv, conv) and 2 periods of (full, conv,
conv, conv); 4 queries over 2 kv heads of 16, one packed cache row of 32.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import reference, shortconv, transformer
from llm_instance_gateway_tpu.models.configs import (
    LFM2_24B_A2B,
    TINY_FALCON_H1_TEST,
    TINY_GLM_TEST,
    TINY_LFM2_TEST,
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
    TINY_QWEN_TEST,
    TINY_SMALLTHINKER_TEST,
    LayerKind,
)
from llm_instance_gateway_tpu.ops import attention
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from tests.test_window import (
    SLOTS,
    bucket_prefill,
    fresh_cache,
    programs,
    rel_err,
    served_logits,
    stream_prefill,
)

CFG = TINY_LFM2_TEST
TOL = 1e-5
# layer_types of the source's config.json (the catalog row), all 40
LAYER_TYPES = ["conv", "conv", "full_attention", "conv"] * 10
CONV_LAYERS = [l for l in range(CFG.n_layers) if l % 4 != 2]
CONV, FULL = LayerKind(conv=True), LayerKind()


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(3),
                                   dtype=jnp.float32)


def sequence(n, seed=5):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


def wanted(params, seq, n, cfg=CFG):
    """(the reference's logits from position n - 1 on, each conv layer's
    last two z after the whole of ``seq``)."""
    states = []
    logits = reference.forward(cfg, params, jnp.asarray(seq), states=states)
    return np.asarray(logits)[n - 1:], np.stack(states)


def state_err(cache, slot, want):
    """The slot's conv state [L_conv, 2, D] against the reference's."""
    return float(np.max(np.abs(np.asarray(cache["conv"][:, :, slot]) - want))
                 / np.max(np.abs(want)))


# -- the configuration --------------------------------------------------------

def test_the_published_preset_is_the_sources():
    c = LFM2_24B_A2B
    assert (c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.head_dim) == (
        2048, 40, 32, 8, 64)
    assert c.n_heads * c.head_dim == c.d_model
    assert (c.d_ff, c.expert_d_ff, c.first_k_dense) == (11776, 1536, 2)
    assert (c.n_experts, c.n_experts_per_token, c.n_shared_experts) == (
        64, 4, 0)
    assert (c.vocab_size, c.max_seq_len, c.conv_kernel) == (65536, 128000, 3)
    assert c.rope_theta == 1e6 and c.norm_eps == 1e-5
    assert c.router_sigmoid and c.norm_topk_prob
    assert c.routed_scaling_factor == 1.0 and c.router_gate_eps == 1e-6
    assert c.qk_norm_head and not c.qk_norm and not c.attention_bias
    assert c.tie_embeddings and not c.embedding_scale
    assert c.kv_pack * c.head_dim == 128
    assert c.n_layers_of("conv") == 30 and c.n_layers_of("full") == 10
    d14 = dataclasses.replace(c, n_layers=14)
    assert d14.n_layers_of("conv") == 11 and d14.n_layers_of("full") == 3


@pytest.mark.parametrize("cfg", [LFM2_24B_A2B, CFG], ids=lambda c: c.name)
def test_layer_kinds_by_index_are_the_sources_layer_types(cfg):
    """The pattern is counted from layer 0 of the model, so the group that
    starts after two dense layers runs it rotated by two."""
    for l in range(cfg.n_layers):
        assert cfg.kind_of(l).conv == (LAYER_TYPES[l] == "conv"), l
    assert cfg.group_spans(0, 2) == [(0, 2, (CONV,))]
    assert cfg.group_spans(2, 8) == [(2, 8, (FULL, CONV, CONV, CONV))]


def test_the_published_depth_ends_half_a_period_in_and_builds():
    """40 = 2 dense + 9 periods + (full, conv): the two layers left over
    are a span of their own over their rows of the sparse group's leaves."""
    cfg = LFM2_24B_A2B
    assert cfg.group_spans(2, 38) == [(2, 36, (FULL, CONV, CONV, CONV)),
                                      (38, 2, (FULL, CONV))]
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), quantize=True))
    assert p["layers"]["wq"]["q"].shape[0] == 10
    assert p["layers"]["conv_in"]["q"].shape[0] == 28
    assert p["layers"]["w_gate"]["q"].shape[0] == 38
    cache = jax.eval_shape(
        lambda: transformer.init_decode_cache(cfg, 2, 256))
    _, out = jax.eval_shape(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)), p, cache)
    assert out["k"].shape == (10, 2, 256, 4, 128)
    assert out["conv"].shape == (30, 2, 2, 2048)


@pytest.mark.parametrize("n_layers, spans", [
    (4, [(2, 2, (FULL, CONV))]),
    (9, [(2, 4, (FULL, CONV, CONV, CONV)), (6, 3, (FULL, CONV, CONV))]),
    (11, [(2, 8, (FULL, CONV, CONV, CONV)), (10, 1, (FULL,))]),
    (12, [(2, 8, (FULL, CONV, CONV, CONV)), (10, 2, (FULL, CONV))]),
    (13, [(2, 8, (FULL, CONV, CONV, CONV)), (10, 3, (FULL, CONV, CONV))]),
])
@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "stream"])
def test_a_depth_that_splits_a_period_is_the_references_forward(
        n_layers, spans, chunk):
    """What a depth leaves over of a period runs as a scan of its own: the
    logits through the cache, the lanes and the conv state are those of
    the reference's plain loop over the layers."""
    cfg = dataclasses.replace(CFG, n_layers=n_layers)
    assert cfg.group_spans(2, n_layers - 2) == spans
    params = transformer.init_params(cfg, jax.random.PRNGKey(n_layers),
                                     dtype=jnp.float32)
    n, n_decode = (10, 5) if chunk is None else (21, 5)
    seq = sequence(n + n_decode, seed=n_layers)
    got, cache = served_logits(cfg, params, seq, n, chunk=chunk)
    want, state = wanted(params, seq, n, cfg)
    assert cache["k"].shape[0] == cfg.n_layers_of("full")
    assert rel_err(got, want) < TOL
    assert state_err(cache, 1, state) < TOL


@pytest.mark.parametrize("change, error", [
    (dict(conv_kernel=0), "conv_kernel"),
    (dict(layer_pattern=("full", "short_conv")), "layer_pattern"),
    (dict(layer_pattern=("full",)), "conv_kernel a conv layer"),
])
def test_a_config_that_is_no_stack_is_refused(change, error):
    with pytest.raises(ValueError, match=error):
        dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("change, pack", [
    ({}, 2),                                   # 2 kv heads of 16: a row of 32
    (dict(n_heads=8, n_kv_heads=8), 8),        # 8 of 16: 128 lanes
    (dict(n_heads=16, n_kv_heads=16), 8),      # two rows of 128
    (dict(head_dim=64), 2),                    # the published head
    (dict(head_dim=128), 1),
    (dict(head_dim=48), 1),                    # no whole heads fill 128
    (dict(n_heads=3, n_kv_heads=3), 1),
])
def test_the_pack_follows_the_heads(change, pack):
    """No field says how many kv heads share a cache row: the head's width
    and the kv heads' count do, so it cannot contradict them."""
    cfg = dataclasses.replace(CFG, **change)
    assert cfg.kv_pack == pack and cfg.n_kv_heads % pack == 0
    assert pack * cfg.resolved_head_dim <= 128
    with pytest.raises(TypeError):
        dataclasses.replace(CFG, kv_pack=4)
    assert LFM2_24B_A2B.kv_pack == 2


def test_the_leaves_of_a_period_are_one_stack_a_kind(params):
    """Attention leaves over the 2 attention layers, the conv operator's
    over the 6 conv layers, the rest over all 8; the dense group, two conv
    layers, has no attention leaf at all; the head is the embedding."""
    dense, sparse = params["dense_layers"], params["layers"]
    assert not set(dense) & set(shortconv.ATTN_LEAVES)
    for name in shortconv.CONV_LEAVES:
        assert dense[name].shape[0] == 2 and sparse[name].shape[0] == 6
    for name in shortconv.ATTN_LEAVES:
        assert sparse[name].shape[0] == 2
    for name in ("attn_norm", "mlp_norm", "router", "router_bias", "w_gate"):
        assert sparse[name].shape[0] == 8
    assert sparse["q_norm"].shape == (2, CFG.head_dim)  # one vector, all heads
    assert sparse["conv_in"].shape == (6, 64, 192)
    assert sparse["conv_w"].shape == (6, 3, 64)
    assert "lm_head" not in params
    big = jax.eval_shape(lambda: transformer.init_params(
        dataclasses.replace(LFM2_24B_A2B, n_layers=14), jax.random.PRNGKey(0),
        quantize=True))
    assert big["layers"]["wq"]["q"].shape == (3, 2048, 2048)
    assert big["layers"]["conv_in"]["q"].shape == (9, 2048, 6144)
    assert big["dense_layers"]["conv_in"]["q"].shape == (2, 2048, 6144)
    assert big["layers"]["w_gate"]["q"].shape == (12, 64, 2048, 1536)
    assert big["dense_layers"]["w_gate"]["q"].shape == (2, 2048, 11776)
    # 7.88 GB: every expert and the whole vocabulary held
    nbytes = sum(np.prod(l.shape) * l.dtype.itemsize
                 for l in jax.tree.leaves(big))
    assert round(nbytes / 1e9, 1) == 7.9


def test_the_cache_holds_lanes_for_attention_layers_and_a_conv_state():
    cache = fresh_cache(CFG)
    assert set(cache) == {"k", "v", "conv", "length"}
    # two 16-wide kv heads a row
    assert cache["k"].shape == (2, SLOTS, 128, 1, 32)
    assert cache["conv"].shape == (8, 2, SLOTS, 64)
    assert transformer._carry_names(cache) == ("k", "v", "conv")
    big = jax.eval_shape(lambda: transformer.init_decode_cache(
        dataclasses.replace(LFM2_24B_A2B, n_layers=14), 64, 8192))
    assert big["k"].shape == (3, 64, 8192, 4, 128)
    assert big["conv"].shape == (11, 2, 64, 2048)
    lanes = sum(2 * np.prod(big[n].shape) for n in ("k", "v"))
    assert round(lanes / 1e9, 2) == 3.22  # 15.0 GB with K and V in all 14
    assert 2 * np.prod(big["conv"].shape) == 5_767_168
    with pytest.raises(ValueError, match="int8"):
        transformer.init_decode_cache(CFG, 2, 32, quantized=True)


# -- the six older configurations: no new array, the same scan ---------------

@pytest.mark.parametrize("cfg", [TINY_QWEN_TEST, TINY_MOE_TEST,
                                 TINY_OLMOE_TEST, TINY_GLM_TEST,
                                 TINY_FALCON_H1_TEST,
                                 TINY_SMALLTHINKER_TEST], ids=lambda c: c.name)
def test_an_older_configuration_has_no_new_array(cfg):
    """No conv state in the cache or the layer loop's carry, no packed row,
    no per-kind stack, the scans as long as they were, in the decode and
    the prefill programs."""
    cache = transformer.init_decode_cache(cfg, 2, 32, jnp.float32)
    names = {"k", "length"} if cfg.latent_width else {"k", "v", "length"}
    if cfg.ssm_d_inner:
        names |= {"ssm", "conv"}
    if cfg.sliding_window:
        names |= {"k_win", "v_win"}
    assert set(cache) == names
    assert len(transformer._kv_carry(cache)) == len(names) - 1
    assert not (cfg.conv_kernel or cfg.qk_norm_head) and cfg.kv_pack == 1
    assert cfg.router_gate_eps == 1e-20
    if not cfg.latent_width:
        assert cache["k"].shape[-2:] == (cfg.n_kv_heads,
                                         cfg.resolved_head_dim)
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    n_sparse = cfg.n_layers - cfg.n_dense_layers
    for group in ("layers", "dense_layers"):
        for name, leaf in p.get(group, {}).items():
            assert leaf.shape[0] == (n_sparse if group == "layers"
                                     else cfg.n_dense_layers), name
            assert name not in shortconv.CONV_LEAVES
    period = len(cfg.layer_kinds)
    step = str(jax.make_jaxpr(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)))(p, cache))
    pre = str(jax.make_jaxpr(lambda p: transformer.prefill(
        cfg, p, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
        lengths=jnp.asarray([5])))(p))
    for text in (step, pre):
        assert f"length={n_sparse // period}" in text
        assert "conv.mix" not in text and "attn.qk_norm" not in text
    out = jax.eval_shape(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)), p, cache)[1]
    assert set(out) == names
    _, k, v = jax.eval_shape(lambda p: transformer.prefill(
        cfg, p, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None]), p)
    assert k.shape[0] == cfg.n_layers
    if not cfg.ssm_d_inner:
        assert v.shape[0] == cfg.n_layers


def test_the_groups_scan_a_layer_and_a_period_a_step(params):
    """Two dense conv layers are two steps of one scan; eight sparse layers
    two steps of another, each a period of four."""
    text = str(jax.make_jaxpr(lambda p, c: transformer.decode_step(
        CFG, p, c, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32)))(params, fresh_cache(CFG)))
    assert text.count("length=2") >= 2 and "length=8" not in text


# -- parity with the reference through the cache ------------------------------

CASES = {
    "bucket": (10, 12, None),
    "bucket, a whole one": (16, 6, None),
    "bucket, one token": (1, 8, None),
    "stream over three edges": (29, 10, 8),
    "stream of whole chunks": (32, 8, 8),
    "stream, one token past an edge": (17, 8, 8),
    "stream, two tokens a chunk": (9, 6, 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_is_the_references_forward(params, case):
    """Logits, not tokens, and the conv state itself: after the prompt and
    after the decode steps that follow it."""
    n, n_decode, chunk = CASES[case]
    seq = sequence(n + n_decode, seed=n)
    got, cache = served_logits(CFG, params, seq, n, chunk=chunk)
    want, state = wanted(params, seq, n)
    assert rel_err(got[:1], want[:1]) < TOL      # the prefill's logits
    assert rel_err(got[1:], want[1:]) < TOL      # the decode steps'
    # (the last fed token is seq[-1]: the state is that of the whole of seq)
    assert state_err(cache, 1, state) < TOL


@pytest.mark.parametrize("n", [1, 2, 5, 11, 16])
def test_insert_writes_the_state_at_the_true_length(params, n):
    """A bucketed prompt's padding does not enter the conv state: the slot
    holds z of positions n - 2 and n - 1, zeros where the prompt is shorter
    than that, whatever the bucket."""
    seq = sequence(n, seed=n)
    _, state = wanted(params, seq, n)
    _, cache = bucket_prefill(CFG, params, fresh_cache(CFG), seq, n, 2)
    assert state_err(cache, 2, state) < TOL
    if n == 1:
        assert not np.any(np.asarray(cache["conv"][:, 0, 2]))
    assert int(cache["length"][2]) == n
    for other in (0, 1):
        assert not np.any(np.asarray(cache["conv"][:, :, other]))
        assert not np.any(np.asarray(cache["k"][:, other]))
    # K and V of the attention layers alone, positions 0..n-1
    _, k_all, v_all = transformer.prefill(
        CFG, params, jnp.asarray(seq)[None], jnp.arange(n)[None])
    assert k_all.shape[0] == 2 and v_all["conv"].shape[0] == 8
    np.testing.assert_allclose(cache["k"][:, 2, :n], k_all[:, 0],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 8])
def test_the_stream_carries_the_state_over_its_edges(params, chunk):
    """After every chunk the slot's state is the reference's at the
    chunk's TRUE end."""
    n = 5 * chunk - 1
    seq = sequence(n, seed=chunk)
    cache = fresh_cache(CFG)
    for start in range(0, n, chunk):
        end = min(n, start + chunk)
        toks = np.zeros((chunk,), np.int32)
        toks[:end - start] = seq[start:end]
        _, cache = programs(CFG).chunk(
            params, cache, jnp.asarray(toks),
            jnp.asarray(start + np.arange(chunk, dtype=np.int32)),
            jnp.int32(1), jnp.int32(end), jnp.int32(end - start - 1))
        assert state_err(cache, 1, wanted(params, seq[:end], end)[1]) < TOL


@pytest.mark.parametrize("second", ["bucket", "stream"])
def test_a_reused_slot_starts_from_zeros(params, second):
    """A long request leaves its state and its lanes in the slot; the
    next, shorter one decodes as if the slot had been empty."""
    first = sequence(60, seed=1)
    _, cache = served_logits(CFG, params, first, 50, chunk=16)
    assert np.all(np.any(np.asarray(cache["conv"][:, :, 1]) != 0, axis=-1))
    seq, n = sequence(15, seed=2), 1 if second == "bucket" else 9
    got, cache = served_logits(CFG, params, seq, n, cache=cache,
                               chunk=8 if second == "stream" else None)
    want, state = wanted(params, seq, n)
    assert rel_err(got, want) < TOL
    assert state_err(cache, 1, state) < TOL


def test_a_row_that_sits_out_keeps_its_state(params):
    """Three rows of different ages decode together, one of them frozen:
    each gives what it gives alone, the frozen row's lanes and state stay
    as they were, and when it joins again it goes on from there."""
    seqs = [sequence(40, seed=s) for s in (11, 12, 13)]
    ns = (30, 5, 18)
    cache = fresh_cache(CFG)
    for slot, (seq, n) in enumerate(zip(seqs, ns)):
        _, cache = stream_prefill(CFG, params, cache, seq, n, slot, chunk=8)
    before = cache
    out = [[], [], []]

    def step(cache, j, active, fed):
        toks = jnp.asarray([seqs[s][ns[s] + fed[s]] for s in range(3)])
        pos = jnp.asarray([ns[s] + fed[s] for s in range(3)])
        logits, cache = programs(CFG).step(params, cache, toks, pos,
                                           jnp.asarray(active))
        for s in range(3):
            if active[s]:
                out[s].append(logits[s])
        return cache

    for j in range(4):
        cache = step(cache, j, [True, True, False], [j, j, 0])
    for name in ("k", "v"):
        np.testing.assert_array_equal(cache[name][:, 2], before[name][:, 2])
    np.testing.assert_array_equal(cache["conv"][:, :, 2],
                                  before["conv"][:, :, 2])
    for j in range(4):
        cache = step(cache, j, [True, False, True], [4 + j, 4, j])
    for slot, steps in ((0, 8), (1, 4), (2, 4)):
        want, state = wanted(params, seqs[slot][:ns[slot] + steps], ns[slot])
        assert rel_err(np.stack(out[slot]), want[1:]) < TOL
        assert state_err(cache, slot, state) < TOL


# -- wrong functions miss it ---------------------------------------------------

def _drop_state_at_edges(cfg, lp, hn, conv, lane, slot, first, live):
    return _REAL_CHUNK_MIX(cfg, lp, hn, conv, lane, slot, True, live)


def _forget_the_older_input(cfg, lp, hn, conv, lane, slot, first, live):
    return _REAL_CHUNK_MIX(cfg, lp, hn, conv.at[lane, 0, slot].set(0), lane,
                           slot, first, live)


def _state_at_the_buckets_end(padded, n_true, taps):
    return _REAL_TAIL(padded, jnp.full_like(n_true, padded.shape[1] - taps + 1),
                      taps)


_REAL_CHUNK_MIX = shortconv.chunk_mix
_REAL_TAIL = shortconv.ssm.conv_tail

WRONG = {
    "the per-head norm replaced by none": (
        transformer, "_head_norm", lambda cfg, lp, target, x: x, None),
    "the state dropped at a chunk's edge": (
        shortconv, "chunk_mix", _drop_state_at_edges, 8),
    "the older of the two carried inputs forgotten": (
        shortconv, "chunk_mix", _forget_the_older_input, 8),
    "the state cut at the bucket's end": (
        shortconv.ssm, "conv_tail", _state_at_the_buckets_end, None),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_function_misses_the_reference(params, name, monkeypatch):
    module, attr, wrong, chunk = WRONG[name]
    seq, n = sequence(30, seed=9), 21
    want, _ = wanted(params, seq, n)
    good, _ = served_logits(CFG, params, seq, n, chunk=chunk)
    assert rel_err(good, want) < TOL
    monkeypatch.setattr(module, attr, wrong)
    programs.cache_clear()
    try:
        got, _ = served_logits(CFG, params, seq, n, chunk=chunk)
    finally:
        monkeypatch.undo()
        programs.cache_clear()
    assert rel_err(got, want) > 30 * TOL


FLIPS = {
    "gates not renormalised": dict(norm_topk_prob=False),
    "GLM's 1e-20 in the gates' sum": dict(router_gate_eps=1e-20),
}


@pytest.mark.parametrize("name", sorted(FLIPS))
def test_each_flipped_rule_misses_the_reference(params, name):
    seq, n = sequence(30, seed=9), 21
    want, _ = wanted(params, seq, n)
    flipped = dataclasses.replace(CFG, **FLIPS[name])
    got, _ = served_logits(flipped, params, seq, n)
    floor = 1e-7 if "1e-20" in name else 30 * TOL
    assert rel_err(got, want) > floor


def test_the_per_head_norm_is_not_the_whole_vector_norm(params):
    """q normed head by head with ONE 16-vector differs from OLMoE's norm
    over the whole projected vector, and equals the plain formula."""
    lp = jax.tree.map(lambda a: a[0], {
        n: params["layers"][n] for n in ("q_norm", "k_norm")})
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 4, 16)),
                    jnp.float32)
    got = transformer._head_norm(CFG, lp, "q", x)
    xf = np.asarray(x)
    want = (xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + CFG.norm_eps)
            * np.asarray(lp["q_norm"]))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    whole = (xf / np.sqrt((xf.reshape(3, -1) ** 2).mean(-1)[:, None, None]
                          + CFG.norm_eps) * np.asarray(lp["q_norm"]))
    assert np.max(np.abs(np.asarray(got) - whole)) > 1e-2
    # every other model's q and k pass as they are
    assert transformer._head_norm(TINY_QWEN_TEST, {}, "q", x) is x
    text = jax.jit(lambda h: transformer.prefill_layer(
        CFG, jax.tree.map(lambda a: a[0], {
            k: v for k, v in params["layers"].items()
            if k not in shortconv.CONV_LEAVES}), h,
        jnp.arange(8)[None])[0]).lower(
            jnp.zeros((1, 8, 64), jnp.float32)).as_text(debug_info=True)
    assert "attn.qk_norm" in text and "attn.qkv" in text
    assert "conv.mix" not in text


def test_a_conv_layer_traces_its_scopes_and_no_attention(params):
    lp = jax.tree.map(lambda a: a[0], {
        k: v for k, v in params["layers"].items()
        if k not in shortconv.ATTN_LEAVES})
    text = jax.jit(lambda h: transformer.prefill_layer(
        CFG, lp, h, jnp.arange(8)[None], kind=LayerKind(conv=True))[0]).lower(
            jnp.zeros((1, 8, 64), jnp.float32)).as_text(debug_info=True)
    for scope in ("conv.in_proj", "conv.mix", "conv.out_proj", "moe.route"):
        assert scope in text, scope
    for scope in ("attn.qkv", "attn.core", "attn.rope", "attn.qk_norm"):
        assert scope not in text, scope


# -- packed heads ---------------------------------------------------------------

@pytest.mark.parametrize("n_kv, group, pack", [(8, 4, 2), (2, 2, 2),
                                               (4, 1, 4)])
def test_padded_queries_against_packed_rows_are_the_heads_own(n_kv, group,
                                                              pack):
    """A query padded into its kv head's columns of the packed row scores
    against the row exactly as against its own head, and its output's own
    columns are its own head's values."""
    rng = np.random.default_rng(pack)
    hd, s = 8, 5
    q = jnp.asarray(rng.normal(size=(3, n_kv * group, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, s, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, s, n_kv, hd)), jnp.float32)
    lengths = jnp.asarray([5, 3, 1])
    want = attention.decode_attention(q, k, v, lengths)
    kp, vp = attention.pack_heads(k, pack), attention.pack_heads(v, pack)
    assert kp.shape == (3, s, n_kv // pack, pack * hd)
    np.testing.assert_array_equal(attention.unpack_heads(kp, pack), k)
    qp = attention.pad_queries(q, n_kv, pack)
    # a kernel's scale is 1 / sqrt(its rows' width): hand it the model's
    out = attention.decode_attention(qp * np.sqrt(pack), kp, vp, lengths)
    got = attention.own_values(out, n_kv, pack)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert attention.pack_heads(k, 1) is k
    assert attention.pad_queries(q, n_kv, 1) is q


# -- the engine ---------------------------------------------------------------

def make_engine(params, cfg=CFG, **kw):
    kw = {"decode_slots": 2, "max_seq_len": 64, "prefill_buckets": (8, 16),
          **kw}
    return Engine(cfg, params, EngineConfig(**kw), eos_id=None,
                  dtype=jnp.float32)


def is_the_references_greedy(params, prompt, answer) -> bool:
    """Whether ``answer`` is the plain reference's own greedy continuation
    of ``prompt``: one full forward over both, no cache; each token has to
    be the argmax after everything before it."""
    seq = list(prompt) + list(answer)
    logits = np.asarray(reference.forward(
        CFG, params, jnp.asarray(seq, jnp.int32)))[:, :CFG.vocab_size]
    return list(np.argmax(logits[len(prompt) - 1:-1], axis=-1)) == list(answer)


@pytest.mark.parametrize("burst", [1, 4])
def test_engine_gives_the_references_tokens_with_slot_reuse(params, burst):
    """Five requests over two slots, bucketed and chunk-streamed prompts
    mixed: greedy tokens equal the plain reference's, so no slot reads its
    last request's state and no step writes a row it should not; the same
    with a prompt's chunks enqueued back to back (the cell's
    ``--stream-burst``), the state handed over with no decode between."""
    engine = make_engine(params, stream_burst=burst)
    prompts = [[3, 5, 7], list(range(3, 40)), [9, 8, 7, 6, 5, 4, 3, 2, 1, 11],
               list(range(40, 75)), [100]]
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
    assert hist["conv_rows"] > 0 and hist["ssm_rows"] == 0
    assert hist["kv_positions"]["full"] > 0
    assert hist["kv_positions"]["window"] == 0
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert f"tpu:conv_state_rows_total {hist['conv_rows']}\n" in text
    assert ('tpu:kv_positions_read_total{lanes="full"} '
            f'{hist["kv_positions"]["full"]}\n') in text
    assert engine.profiler.snapshot()["hist"]["conv_rows"] == hist["conv_rows"]


def test_counter_is_occupied_slots_times_steps(params):
    """One request of 12 prompt tokens and 9 new ones: eight decode steps
    (the first new token comes from the prefill) and the one dispatched
    before the eighth was read, one row each; step j reads 12 + j positions
    of each attention layer's lane."""
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
    assert hist["conv_rows"] == steps
    assert hist["kv_positions"]["full"] == sum(
        12 + j for j in range(1, steps + 1))


def test_a_model_without_conv_layers_counts_no_row():
    cfg = TINY_QWEN_TEST
    engine = make_engine(transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32), cfg,
        prefill_buckets=(8,))
    engine.start()
    try:
        engine.generate(Request(prompt_tokens=[3, 5, 7], max_new_tokens=4),
                        timeout_s=300)
    finally:
        engine.stop()
    assert engine.profiler.hist_state()["conv_rows"] == 0
    assert "tpu:conv_state_rows_total 0\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


def test_kv_cache_usage_is_over_the_lanes(params):
    """The conv state is constant a slot: usage stays tokens over the
    lanes' token capacity."""
    engine = make_engine(params)
    nobody = types.SimpleNamespace(adapter=None)
    engine.slots = [types.SimpleNamespace(position=p, request=nobody)
                    for p in (10, 40)]
    assert engine.metrics_snapshot()["kv_cache_usage_perc"] == 50 / 128


def test_int8_weights_serve_the_conv_projections(params):
    """``--quantize int8`` quantizes conv_in and conv_out like the other
    projections, and the reference reads the served weights."""
    from llm_instance_gateway_tpu.ops import quant

    q = quant.quantize_params(params)
    for group in ("dense_layers", "layers"):
        assert quant.is_quantized(q[group]["conv_in"])
        assert quant.is_quantized(q[group]["conv_out"])
        assert not quant.is_quantized(q[group]["conv_w"])
    assert quant.is_quantized(q["layers"]["wq"])
    assert not quant.is_quantized(q["embed"])
    seq, n = sequence(20, seed=4), 12
    got, _ = served_logits(CFG, q, seq, n)
    assert rel_err(got, wanted(q, seq, n)[0]) < TOL


def test_profile_report_has_a_section_for_the_counter():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import profile_report

    profile = {"hist": {"conv_rows": 640,
                        "wall": {"decode": {"count": 10}}}}
    assert profile_report.conv_rows_row(profile) == {
        "conv_rows": 640, "decode_dispatches": 10, "rows_per_dispatch": 64.0}
    assert profile_report.conv_rows_row({"hist": {"conv_rows": 0}}) == {}
    assert profile_report.conv_rows_row({}) == {}
    for scope in ("conv.in_proj", "conv.mix", "conv.out_proj",
                  "attn.qk_norm"):
        assert scope in profile_report.SCOPES, scope
    assert "Conv states rewritten" in profile_report.render_report(profile)


# -- what a conv state does not serve: refused at start-up, by name -----------

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
def test_engine_refuses_what_a_conv_state_does_not_serve(params, case):
    engine_kw, ctor_kw, names = REFUSED[case]
    if "draft_cfg" in ctor_kw:
        ctor_kw = dict(ctor_kw, draft_params=params)
    with pytest.raises(ValueError, match="conv state") as err:
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, **engine_kw),
               eos_id=None, dtype=jnp.float32, **ctor_kw)
    assert names in str(err.value) and CFG.name in str(err.value)


def test_the_handoff_api_is_refused_in_every_role(params):
    engine = make_engine(params)
    with pytest.raises(ValueError, match="kv_transfer.*conv state"):
        engine.prefill_only(Request(prompt_tokens=[3, 5, 7]))
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.attach_prefilled(object())


def test_what_the_layer_loop_does_not_scan_is_refused(params):
    with pytest.raises(NotImplementedError, match="conv"):
        transformer.extend_step(CFG, params, {}, jnp.zeros((1, 2), jnp.int32),
                                jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(NotImplementedError, match="LoRA"):
        transformer.prefill(CFG, params, jnp.zeros((1, 4), jnp.int32),
                            jnp.arange(4)[None], lora_bufs={"scale": None})
    with pytest.raises(NotImplementedError, match="conv layers"):
        reference.forward(CFG, params, jnp.zeros((4,), jnp.int32), (None, 0))


@pytest.mark.parametrize("flags", [["--max-loras", "4"],
                                   ["--max-loras", "0", "--mesh", "tensor=2"]],
                         ids=["adapters", "mesh"])
def test_server_refuses_adapters_and_a_mesh_by_name(flags):
    from llm_instance_gateway_tpu.server import api_http

    with pytest.raises(SystemExit, match="lfm2-tiny.*conv state.*"
                       "--max-loras 0"):
        api_http.main(["--model", "lfm2-tiny", "--platform", "cpu", *flags])


def test_debug_device_reports_the_new_fields():
    import inspect

    from llm_instance_gateway_tpu.server import api_http

    src = inspect.getsource(api_http.ModelServer)
    for field in ("qk_norm_head", "conv_kernel", "tie_embeddings",
                  "router_gate_eps", "layer_pattern"):
        assert f'"{field}"' in src, field


def test_the_presets_are_where_the_server_wrapper_looks():
    from llm_instance_gateway_tpu.models import mixtral

    assert mixtral.CONFIGS["lfm2-24b-a2b"] is LFM2_24B_A2B
    assert mixtral.CONFIGS["lfm2-tiny"] is CFG
