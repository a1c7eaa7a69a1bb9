"""SmallThinker's mechanisms at the tiny preset on the CPU: a period of layer
kinds scanned a period a step, full layers without a position encoding,
window layers over ring lanes, the chunk stream through a ring, slot reuse,
the router on the block's input, ReLU gating, the engine in both loops, the
counter, ``kv_cache_usage_perc`` over two kinds of lane and every refusal of
what ring lanes do not serve.

One limit, float32 against ``models/reference.py``: logits within 1e-5 of the
largest reference logit (seen: 1e-6).  Two float32 programs that sum in
different orders differ by rounding alone; the least visible thing that can
be got wrong here, one window layer's window off by one position, moves them
by 1e-3 (``test_each_flipped_rule_misses_the_reference``).

The tiny preset: two periods of (nope, window, window, window), a window of
16, so that a prompt of a few chunks of 16 and a dozen decode steps cross the
window and wrap the ring several times.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import reference, transformer
from llm_instance_gateway_tpu.models.configs import (
    SMALLTHINKER_21B_A3B,
    TINY_FALCON_H1_TEST,
    TINY_GLM_TEST,
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
    TINY_QWEN_TEST,
    TINY_SMALLTHINKER_TEST,
    LayerKind,
)
from llm_instance_gateway_tpu.ops import attention, pallas_attention
from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda
from llm_instance_gateway_tpu.ops.layers import gated
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from tests._reference import reference_tokens

CFG = TINY_SMALLTHINKER_TEST
W = CFG.sliding_window
TOL = 1e-5
SLOTS, S_MAX = 3, 128


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(3),
                                   dtype=jnp.float32)


def rel_err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(ref)))
                 / np.max(np.abs(np.asarray(ref))))


def sequence(n, seed=5):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


def fresh_cache(cfg=CFG):
    return transformer.init_decode_cache(cfg, SLOTS, S_MAX, jnp.float32)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The four cached programs of ``cfg``, jitted once a configuration as
    the engine jits them (eager, every call of a layer loop compiles anew:
    minutes of compiling and a process that grows until it is killed)."""
    return types.SimpleNamespace(
        prefill=jax.jit(lambda p, toks, pos, n: transformer.prefill(
            cfg, p, toks, pos, lengths=n)),
        insert=jax.jit(lambda cache, k, v, slot, n: transformer.insert_prefill(
            cache, k, v, slot, n, cfg=cfg)),
        chunk=jax.jit(lambda p, cache, toks, pos, slot, end, last:
                      transformer.prefill_with_cache(
                          cfg, p, cache, toks, pos, slot, end, last)),
        step=jax.jit(lambda p, cache, toks, pos, active:
                     transformer.decode_step(cfg, p, cache, toks, pos,
                                             active=active)))


def bucket_prefill(cfg, params, cache, seq, n, slot):
    """The engine's bucketed admission: a right-padded prompt through
    ``prefill`` and ``insert_prefill``.  Returns (last logits, cache)."""
    bucket = 1 << (n - 1).bit_length()
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = seq[:n]
    pos = np.zeros((1, bucket), np.int32)
    pos[0, :n] = np.arange(n)
    run = programs(cfg)
    logits, k, v = run.prefill(params, jnp.asarray(toks), jnp.asarray(pos),
                               jnp.asarray([n]))
    return logits[0, n - 1], run.insert(cache, k, v, jnp.int32(slot),
                                        jnp.int32(n))


def stream_prefill(cfg, params, cache, seq, n, slot, chunk=16):
    """The engine's chunk stream: ``chunk`` tokens at a time into the lane,
    the last chunk padded with positions that run on."""
    for start in range(0, n, chunk):
        piece = seq[start:min(n, start + chunk)]
        toks = np.zeros((chunk,), np.int32)
        toks[:len(piece)] = piece
        last, cache = programs(cfg).chunk(
            params, cache, jnp.asarray(toks),
            jnp.asarray(start + np.arange(chunk, dtype=np.int32)),
            jnp.int32(slot), jnp.int32(start + len(piece)),
            jnp.int32(len(piece) - 1))
    return last, cache


def served_logits(cfg, params, seq, n, slot=1, chunk=None, cache=None):
    """Logits at the last prompt position and at every fed position after
    it, through the cache.  Returns (logits [len(seq) - n + 1, V], cache)."""
    cache = fresh_cache(cfg) if cache is None else cache
    if chunk is None:
        last, cache = bucket_prefill(cfg, params, cache, seq, n, slot)
    else:
        last, cache = stream_prefill(cfg, params, cache, seq, n, slot, chunk)
    out = [np.asarray(last)]
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    for j in range(n, len(seq)):
        toks = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        toks[slot], pos[slot] = seq[j], j
        logits, cache = programs(cfg).step(
            params, cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(active))
        out.append(np.asarray(logits[slot]))
    return np.stack(out), cache


def wanted(params, seq, n, cfg=CFG):
    return np.asarray(reference.forward(cfg, params, jnp.asarray(seq)))[n - 1:]


# -- the configuration --------------------------------------------------------

def test_the_published_preset_is_the_sources():
    c = SMALLTHINKER_21B_A3B
    assert (c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.head_dim) == (
        2560, 52, 28, 4, 128)
    assert (c.n_experts, c.n_experts_per_token, c.expert_d_ff) == (64, 6, 768)
    assert (c.vocab_size, c.max_seq_len, c.sliding_window) == (
        151_936, 16_384, 4096)
    assert c.rope_theta == 1.5e6 and c.norm_eps == 1e-6
    assert c.norm_topk_prob and c.router_pre_attention
    assert c.mlp_activation == "relu" and not c.gelu_mlp
    kinds = c.layer_kinds
    assert kinds == (LayerKind(0, False),) + (LayerKind(4096, True),) * 3
    # rope_layout and sliding_window_layout of the source: 0 where l % 4 == 0
    assert [int(kinds[l % 4].rope) for l in range(8)] == [0, 1, 1, 1] * 2
    assert c.n_window_layers == 39
    assert dataclasses.replace(c, n_layers=12).n_window_layers == 9


@pytest.mark.parametrize("change, error", [
    (dict(n_layers=6), "whole periods"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(layer_pattern=("full", "sliding")), "layer_pattern"),
    (dict(mlp_activation="swish"), "mlp_activation"),
])
def test_a_config_that_is_no_stack_is_refused(change, error):
    with pytest.raises(ValueError, match=error):
        dataclasses.replace(CFG, **change)


def test_the_cache_is_of_two_kinds():
    cache = fresh_cache()
    assert set(cache) == {"k", "v", "k_win", "v_win", "length"}
    hd = CFG.resolved_head_dim
    assert cache["k"].shape == (2, SLOTS, S_MAX, CFG.n_kv_heads, hd)
    assert cache["k_win"].shape == (6, SLOTS, W, CFG.n_kv_heads, hd)
    assert transformer._carry_names(cache) == ("k", "v", "k_win", "v_win")
    big = jax.eval_shape(lambda: transformer.init_decode_cache(
        dataclasses.replace(SMALLTHINKER_21B_A3B, n_layers=12), 32, 16384))
    assert big["k"].shape == (3, 32, 16384, 4, 128)
    assert big["k_win"].shape == (9, 32, 4096, 4, 128)
    # 5.64 GB of bf16, against 12.9 GB for twelve layers of full lanes
    nbytes = sum(2 * np.prod(big[n].shape) for n in ("k", "v", "k_win", "v_win"))
    assert round(nbytes / 1e9, 2) == 5.64
    # a lane shorter than the window is its own ring
    short = transformer.init_decode_cache(CFG, 2, 8, jnp.float32)
    assert short["k_win"].shape[2] == 8
    with pytest.raises(ValueError, match="int8"):
        transformer.init_decode_cache(CFG, 2, 32, quantized=True)


@pytest.mark.parametrize("cfg", [TINY_QWEN_TEST, TINY_MOE_TEST,
                                 TINY_OLMOE_TEST, TINY_GLM_TEST,
                                 TINY_FALCON_H1_TEST], ids=lambda c: c.name)
def test_a_model_of_one_kind_has_no_new_array(cfg):
    """The five older configurations: no ring in the cache or the layer
    loop's carry, one kind a layer, the router where it was, the programs'
    inputs and outputs as the parent's."""
    cache = transformer.init_decode_cache(cfg, 2, 32, jnp.float32)
    names = {"k", "length"} if cfg.latent_width else {"k", "v", "length"}
    if cfg.ssm_d_inner:
        names |= {"ssm", "conv"}
    assert set(cache) == names
    assert len(transformer._kv_carry(cache)) == len(names) - 1
    assert cfg.layer_kinds == (LayerKind(0, True),)
    assert not (cfg.layer_pattern or cfg.sliding_window
                or cfg.router_pre_attention)
    assert cfg.mlp_activation == "silu" and cfg.n_window_layers == 0
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    step = jax.make_jaxpr(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)))(p, cache)
    text = str(step)
    assert "k_win" not in text
    # a layer a scan step, as ever: no scan over periods
    assert f"length={cfg.n_layers - cfg.first_k_dense}" in text
    out = jax.eval_shape(lambda p, c: transformer.decode_step(
        cfg, p, c, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        active=jnp.ones((2,), bool)), p, cache)[1]
    assert set(out) == names


def test_the_period_is_one_scan_step(params):
    """Eight layers are two steps of one scan, each four layers long."""
    cache = fresh_cache()
    text = str(jax.make_jaxpr(lambda p, c: transformer.decode_step(
        CFG, p, c, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32)))(params, cache))
    assert "length=2" in text and "length=8" not in text


# -- parity with the reference through the cache ------------------------------

CASES = {
    "bucket under the window": (10, 4, None),
    "bucket, decode crosses the window": (10, 14, None),
    "bucket over the window": (24, 12, None),
    "bucket over two windows, decode wraps again": (40, 20, None),
    "stream under the window, decode crosses and wraps": (10, 30, 16),
    "stream over window + chunk": (37, 20, 16),
    "stream of whole chunks": (48, 20, 16),
    "stream, a padded last chunk over the wrap": (50, 20, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_is_the_references_forward(params, case):
    n, n_decode, chunk = CASES[case]
    seq = sequence(n + n_decode, seed=n)
    got, _ = served_logits(CFG, params, seq, n, chunk=chunk)
    want = wanted(params, seq, n)
    assert rel_err(got[:1], want[:1]) < TOL      # the prefill's logits
    assert rel_err(got[1:], want[1:]) < TOL      # the decode steps'


def ring_wanted(k_all, n):
    """What a ring of ``W`` cells holds of positions 0..n-1: cell s the
    newest position p < n with p % W == s (None: never written)."""
    return {s: max((p for p in range(n) if p % W == s), default=None)
            for s in range(W)}


@pytest.mark.parametrize("n, chunk", [(9, None), (24, None), (40, None),
                                      (37, 16), (50, 16)])
def test_the_rings_hold_the_newest_window_of_the_prompt(params, n, chunk):
    """After ``insert_prefill`` and after a chunked prompt: ring cell s of
    every window layer holds the prompt's newest position congruent to s,
    and the full layers' lanes positions 0..n-1 in order."""
    seq = sequence(n, seed=n)
    toks, pos = jnp.asarray(seq)[None], jnp.arange(n)[None]
    _, k_all, v_all = transformer.prefill(CFG, params, toks, pos)
    if chunk is None:
        _, cache = bucket_prefill(CFG, params, fresh_cache(), seq, n, 1)
    else:
        _, cache = stream_prefill(CFG, params, fresh_cache(), seq, n, 1, chunk)
    full = [l for l in range(CFG.n_layers) if l % 4 == 0]
    win = [l for l in range(CFG.n_layers) if l % 4]
    # (a padded bucket and the bare prompt sum in different orders)
    np.testing.assert_allclose(cache["k"][:, 1, :n], k_all[jnp.asarray(full), 0],
                               rtol=1e-4, atol=1e-5)
    for cell, p in ring_wanted(k_all, n).items():
        if p is None:
            continue
        for name, src in (("k_win", k_all), ("v_win", v_all)):
            np.testing.assert_allclose(
                cache[name][:, 1, cell], src[jnp.asarray(win), 0, p],
                rtol=1e-4, atol=1e-5, err_msg=f"{name} cell {cell}")
    assert int(cache["length"][1]) == n
    # the neighbours' lanes and rings were not touched
    for name in ("k", "v", "k_win", "v_win"):
        assert not np.any(np.asarray(cache[name][:, 0]))
        assert not np.any(np.asarray(cache[name][:, 2]))


@pytest.mark.parametrize("second", ["bucket", "stream"])
def test_a_reused_slot_does_not_read_the_last_requests_ring(params, second):
    """A long request fills the slot's ring; the next, shorter one decodes
    as if the ring had been empty."""
    first = sequence(60, seed=1)
    _, cache = served_logits(CFG, params, first, 50, chunk=16)
    assert np.all(np.any(np.asarray(cache["k_win"][:, 1]) != 0, axis=(0, 2, 3)))
    seq, n = sequence(22, seed=2), 9
    got, _ = served_logits(CFG, params, seq, n, cache=cache,
                           chunk=16 if second == "stream" else None)
    assert rel_err(got, wanted(params, seq, n)) < TOL


def test_rows_do_not_see_each_other(params):
    """Three rows of different ages decode together, one of them frozen:
    each gives what it gives alone, and the frozen row's lanes and rings
    stay as they were."""
    seqs = [sequence(40, seed=s) for s in (11, 12, 13)]
    ns = (30, 5, 18)
    cache = fresh_cache()
    for slot, (seq, n) in enumerate(zip(seqs, ns)):
        _, cache = stream_prefill(CFG, params, cache, seq, n, slot)
    before = cache
    active = jnp.asarray([True, True, False])
    out = [[], []]
    for j in range(8):
        toks = jnp.asarray([seqs[0][ns[0] + j], seqs[1][ns[1] + j], 7])
        pos = jnp.asarray([ns[0] + j, ns[1] + j, ns[2]])
        logits, cache = programs(CFG).step(params, cache, toks, pos, active)
        out[0].append(logits[0])
        out[1].append(logits[1])
    for name in ("k", "v", "k_win", "v_win"):
        np.testing.assert_array_equal(cache[name][:, 2], before[name][:, 2])
    for slot in (0, 1):
        want = wanted(params, seqs[slot][:ns[slot] + 8], ns[slot])[1:]
        assert rel_err(np.stack(out[slot]), want) < TOL


FLIPS = {
    "rope on the full layers": dict(
        layer_pattern=("full", "window", "window", "window")),
    "no rope on a window layer": dict(
        layer_pattern=("nope", "nope", "window", "window"),),
    "the window left off": dict(layer_pattern=("nope", "full", "full", "full"),
                                sliding_window=0),
    "a window one position short": dict(sliding_window=W - 1),
    "a window one position long": dict(sliding_window=W + 1),
    "the router after the norm": dict(router_pre_attention=False),
    "silu for relu": dict(mlp_activation="silu"),
    "gates not renormalised": dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("name", sorted(FLIPS))
def test_each_flipped_rule_misses_the_reference(params, name):
    """The serving path with one rule of the layer changed is another
    function: it misses the float32 limit by orders of magnitude, through
    the bucket and the stream, in prefill and in decode."""
    seq, n = sequence(44, seed=9), 34
    want = wanted(params, seq, n)
    flipped = dataclasses.replace(CFG, **FLIPS[name])
    for chunk in (None, 16):
        got, _ = served_logits(flipped, params, seq, n, chunk=chunk)
        assert rel_err(got[:1], want[:1]) > 30 * TOL, chunk
        assert rel_err(got[1:], want[1:]) > 30 * TOL, chunk


def test_nope_layers_trace_no_rope_and_window_layers_their_own_scope(params):
    """``attn.rope`` is absent from a full layer, a window layer's attention
    runs as ``attn.core.window``, and the route comes before ``attn.qkv``."""
    h = jnp.zeros((1, 8, CFG.d_model), jnp.float32)
    pos = jnp.arange(8)[None]
    lp = jax.tree.map(lambda a: a[0], params["layers"])

    def scopes(kind):
        text = jax.jit(lambda h: transformer.prefill_layer(
            CFG, lp, h, pos, kind=kind)[0]).lower(h).as_text(debug_info=True)
        return text

    full, window = scopes(CFG.layer_kinds[0]), scopes(CFG.layer_kinds[1])
    assert "attn.rope" not in full and "attn.rope" in window
    assert "attn.core.window" in window and "attn.core.window" not in full
    assert "attn.core" in full
    assert 0 < window.index("moe.route") < window.index("attn.qkv")


# -- the pieces ---------------------------------------------------------------

@pytest.mark.parametrize("act", ["silu", "gelu", "relu"])
def test_gated_activations(act):
    g = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
    u = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    want = {"silu": jax.nn.silu(g), "relu": jnp.maximum(g, 0),
            "gelu": jax.nn.gelu(g, approximate=True)}[act] * u
    np.testing.assert_allclose(gated(g, u, act), want)
    if act == "relu":
        assert np.asarray(gated(g, u, act))[:3].tolist() == [0, 0, 0]


def test_reglu_experts_against_the_reference(params):
    """One sparse layer alone: the dropless dispatch with the router's
    input apart from the experts', against the reference's dense mix."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(11, CFG.d_model)), jnp.float32)
    r_in = jnp.asarray(rng.normal(size=(11, CFG.d_model)), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    plan = transformer._moe_route(CFG, lp, r_in)
    got, tally = transformer._moe_experts(CFG, lp, x, plan)
    want = reference._mlp(CFG, params["layers"], 1, x, None, r_in)
    assert rel_err(got, want) < TOL
    assert int(tally[1]) == 11 * CFG.n_experts_per_token
    # routed on its own input it is the plain sparse MLP
    own, _ = transformer._moe_mlp(CFG, lp, x)
    assert rel_err(own, reference._mlp(CFG, params["layers"], 1, x, None, x)
                   ) < TOL
    assert rel_err(own, want) > 1e-2


def test_route_reads_its_input_through_the_router_alone(params):
    """What the on-chip check pins the choice with
    (``reference_check_smallthinker.pin_routing``): a column of ones on the
    input and a bias as one more row of the router's matrix pick by logit +
    bias, and the gates stay the softmax of the chosen logits."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, CFG.d_model)),
                    jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    bias = np.zeros((1, CFG.n_experts), np.float32)
    bias[0, [1, 4, 6]] = 100.0
    plan = transformer._moe_route(
        CFG, dict(lp, router=jnp.concatenate([lp["router"], bias])),
        jnp.concatenate([x, jnp.ones((5, 1))], axis=-1))
    logits = np.asarray(x @ lp["router"])[:, [1, 4, 6]]
    want = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.sort(plan["gates"], -1), np.sort(want, -1),
                               rtol=1e-4)


@pytest.mark.parametrize("window", [0, 5, 16])
def test_xla_window_masks(window):
    """``prefill_attention`` and ``xla_chunk_attention`` with a window
    against a plain softmax over the positions the rule names."""
    rng = np.random.default_rng(window)
    s, h, k_heads, hd = 24, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(1, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, k_heads, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, k_heads, hd)), jnp.float32)
    scores = np.einsum("ihd,jhd->hij", np.asarray(q[0]),
                       np.repeat(np.asarray(k[0]), 2, axis=1)) / np.sqrt(hd)
    behind = np.arange(s)[:, None] - np.arange(s)[None]
    seen = (behind >= 0) & ((behind < window) if window else True)
    p = np.exp(np.where(seen, scores, -np.inf))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hij,jhd->ihd", p, np.repeat(np.asarray(v[0]), 2, axis=1))
    got = attention.prefill_attention(q, k, v, None, window)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    got = attention.xla_chunk_attention(q[:, 16:], k, v, 16, window)[0]
    np.testing.assert_allclose(got, want[16:], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("start, window", [(0, 256), (384, 256), (1024, 256),
                                           (640, 200), (512, 0), (896, 128)])
def test_chunk_kernel_masks_by_the_window(start, window):
    """The chunk kernel in interpret mode against the XLA form: tiles
    wholly behind the window skipped, the tile its far edge cuts masked."""
    rng = np.random.default_rng(start + window)
    c, s_max, h, k_heads, hd = 256, 1536, 2, 1, 128
    q = jnp.asarray(rng.normal(size=(1, c, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s_max, k_heads, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s_max, k_heads, hd)), jnp.float32)
    got = pallas_attention.chunk_attention(q, k, v, start, interpret=True,
                                           window=window)
    want = attention.xla_chunk_attention(q, k, v, start, window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_decode_kernel_over_ring_lanes_has_its_own_name():
    """One body, two names: a trace tells the lanes apart and
    ``^decode_attention`` finds both."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 2, 256, 2, 128)), jnp.float32)
    lengths = jnp.asarray([256, 70], jnp.int32)
    text = {ring: str(jax.make_jaxpr(lambda q, k: pda.decode_attention(
        q, k, k, lengths, layer=1, interpret=True, ring=ring))(q, k))
        for ring in (False, True)}
    assert "decode_attention_window" in text[True]
    assert "decode_attention_window" not in text[False]
    assert "decode_attention" in text[False]
    got = pda.decode_attention(q, k, k, lengths, layer=1, interpret=True,
                               ring=True)
    want = attention.decode_attention(q, k[1], k[1], lengths)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- the engine ---------------------------------------------------------------

def make_engine(params, cfg=CFG, **kw):
    kw = {"decode_slots": 2, "max_seq_len": 64, "prefill_buckets": (8, 16),
          **kw}
    return Engine(cfg, params, EngineConfig(**kw), eos_id=None,
                  dtype=jnp.float32)


def test_engine_gives_the_references_tokens_with_slot_reuse(params):
    """Five requests over two slots, bucketed and chunk-streamed prompts
    mixed, prompts under and over the window, answers that wrap the ring:
    greedy tokens equal the reference's, so no slot reads its last
    request's ring and no step writes a row it should not."""
    engine = make_engine(params)
    prompts = [[3, 5, 7], list(range(3, 40)), [9, 8, 7, 6, 5, 4, 3, 2, 1, 11],
               list(range(40, 75)), [100, 200]]
    engine.start()
    try:
        reqs = [engine.submit(Request(prompt_tokens=p, max_new_tokens=14))
                for p in prompts]
        for req in reqs:
            assert req.done.wait(300) and req.error is None, req.error
    finally:
        engine.stop()
    for prompt, req in zip(prompts, reqs):
        assert req.output_tokens == reference_tokens(CFG, params, prompt, 14)
    read = engine.profiler.hist_state()["kv_positions"]
    assert read["full"] > read["window"] > 0
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    for lanes in ("full", "window"):
        assert (f'tpu:kv_positions_read_total{{lanes="{lanes}"}} '
                f'{read[lanes]}\n') in text
    assert engine.profiler.snapshot()["hist"]["kv_positions"] == read


def test_counter_is_positions_by_kind_of_lane(params):
    """One request of 12 prompt tokens and 9 new ones: eight decode steps
    (the first new token comes from the prefill) and the one dispatched
    before the eighth was read, step j reading 12 + j positions of a full
    lane and at most 16 of a ring."""
    engine = make_engine(params)
    engine.start()
    try:
        req = engine.generate(Request(prompt_tokens=list(range(3, 15)),
                                      max_new_tokens=9), timeout_s=300)
        assert req.error is None
    finally:
        engine.stop()
    read = engine.profiler.hist_state()["kv_positions"]
    steps = engine.profiler.dispatches["decode"]
    assert 8 <= steps <= 9
    assert read["full"] == sum(12 + j for j in range(1, steps + 1))
    assert read["window"] == sum(min(12 + j, W) for j in range(1, steps + 1))


def test_a_model_without_a_window_counts_no_position():
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
    assert set(engine.cache) == {"k", "v", "length"}
    assert engine.profiler.hist_state()["kv_positions"] == {
        "full": 0, "window": 0}
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert 'tpu:kv_positions_read_total{lanes="full"} 0\n' in text
    assert 'tpu:kv_positions_read_total{lanes="window"} 0\n' in text


def test_kv_cache_usage_is_held_bytes_over_allocated_bytes(params):
    """By hand for two kinds of lane: a row of p positions holds p in each
    of the 2 full layers and min(p, 16) in each of the 6 window layers, of
    the 64 and 16 allocated a slot; and tokens over token capacity, as ever,
    for a model of one kind."""
    engine = make_engine(params)
    rows = (10, 40)
    nobody = types.SimpleNamespace(adapter=None)
    engine.slots = [types.SimpleNamespace(position=p, request=nobody)
                    for p in rows]
    snap = engine.metrics_snapshot()
    held = sum(2 * p + 6 * min(p, W) for p in rows)
    assert snap["kv_cache_usage_perc"] == pytest.approx(
        held / (2 * (2 * 64 + 6 * W)))
    assert snap["kv_tokens_capacity"] == 2 * 64
    assert snap["kv_tokens_free"] == 2 * 64 - sum(rows)
    # a stream half way through its prompt holds what it has written
    engine.slots = [None, None]
    engine._streams = [types.SimpleNamespace(next_start=32, request=nobody)]
    assert engine.metrics_snapshot()["kv_cache_usage_perc"] == pytest.approx(
        (2 * 32 + 6 * W) / (2 * (2 * 64 + 6 * W)))
    engine._streams = []
    assert engine.metrics_snapshot()["kv_cache_usage_perc"] == 0.0

    one = make_engine(transformer.init_params(
        TINY_QWEN_TEST, jax.random.PRNGKey(0), dtype=jnp.float32),
        TINY_QWEN_TEST)
    one.slots = [types.SimpleNamespace(position=p, request=nobody)
                 for p in rows]
    assert one.metrics_snapshot()["kv_cache_usage_perc"] == sum(rows) / 128


def test_profile_report_has_a_section_for_the_counter():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import profile_report

    profile = {"hist": {"kv_positions": {"full": 6400, "window": 4096},
                        "wall": {"decode": {"count": 10}}}}
    assert profile_report.kv_positions_rows(profile) == [
        {"lanes": "full", "positions": 6400, "decode_dispatches": 10,
         "positions_per_dispatch": 640.0},
        {"lanes": "window", "positions": 4096, "decode_dispatches": 10,
         "positions_per_dispatch": 409.6}]
    assert profile_report.kv_positions_rows(
        {"hist": {"kv_positions": {"full": 0, "window": 0}}}) == []
    assert profile_report.kv_positions_rows({}) == []
    assert "attn.core.window" in profile_report.SCOPES
    assert "Cache positions read" in profile_report.render_report(profile)


# -- what ring lanes do not serve: refused at start-up, by name ---------------

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
def test_engine_refuses_what_ring_lanes_do_not_serve(params, case):
    engine_kw, ctor_kw, names = REFUSED[case]
    if "draft_cfg" in ctor_kw:
        ctor_kw = dict(ctor_kw, draft_params=params)
    with pytest.raises(ValueError, match="ring lanes") as err:
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, **engine_kw),
               eos_id=None, dtype=jnp.float32, **ctor_kw)
    assert names in str(err.value) and CFG.name in str(err.value)


def test_the_handoff_api_is_refused_in_every_role(params):
    engine = make_engine(params)
    with pytest.raises(ValueError, match="kv_transfer.*ring"):
        engine.prefill_only(Request(prompt_tokens=[3, 5, 7]))
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.attach_prefilled(object())


def test_what_the_layer_loop_does_not_scan_is_refused(params):
    with pytest.raises(NotImplementedError, match="ring"):
        transformer.extend_step(CFG, params, {}, jnp.zeros((1, 2), jnp.int32),
                                jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(NotImplementedError, match="period"):
        transformer.prefill(CFG, params, jnp.zeros((1, 4), jnp.int32),
                            jnp.arange(4)[None], lora_bufs={"scale": None})


@pytest.mark.parametrize("flags", [["--max-loras", "4"],
                                   ["--max-loras", "0", "--mesh", "tensor=2"]],
                         ids=["adapters", "mesh"])
def test_server_refuses_adapters_and_a_mesh_by_name(flags):
    from llm_instance_gateway_tpu.server import api_http

    with pytest.raises(SystemExit, match="smallthinker-tiny.*--max-loras 0"):
        api_http.main(["--model", "smallthinker-tiny", "--platform", "cpu",
                       *flags])


def test_debug_device_reports_the_stacks_fields():
    import inspect

    from llm_instance_gateway_tpu.server import api_http

    src = inspect.getsource(api_http.ModelServer)
    for field in ("layer_pattern", "sliding_window", "router_pre_attention",
                  "mlp_activation"):
        assert f'"{field}"' in src, field


# -- the converter ------------------------------------------------------------

def hf_smallthinker(**changes):
    """The source's config.json as a transformers config object would carry
    it (the catalog's keys)."""
    base = dict(
        model_type="smallthinker", head_dim=128, hidden_size=2560,
        max_position_embeddings=16384, moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6, moe_num_primary_experts=64,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
        rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1] * 13, rope_scaling=None,
        rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1] * 13,
        sliding_window_size=4096, tie_word_embeddings=False,
        vocab_size=151936)
    return types.SimpleNamespace(**{**base, **changes})


def test_converter_turns_the_layouts_into_the_period():
    from llm_instance_gateway_tpu.models.convert import config_from_hf

    got = config_from_hf(hf_smallthinker())
    assert got == dataclasses.replace(SMALLTHINKER_21B_A3B,
                                      name="hf-smallthinker")
    # another period, and a stack with no window at all
    six = config_from_hf(hf_smallthinker(
        num_hidden_layers=6, rope_layout=[1, 1, 0] * 2,
        sliding_window_layout=[1, 0, 0] * 2))
    assert six.layer_pattern == ("window", "full", "nope")
    plain = config_from_hf(hf_smallthinker(
        rope_layout=[1] * 52, sliding_window_layout=[0] * 52))
    assert plain.layer_pattern == ("full",) and plain.sliding_window == 0


@pytest.mark.parametrize("change, error", [
    (dict(rope_layout=[0, 0, 1, 1] * 13), "without a position encoding"),
    (dict(sliding_window_size=0), "no sliding_window_size"),
    (dict(rope_layout=[0, 1] * 13), "do not name every layer"),
    (dict(moe_primary_router_apply_softmax=False), "sigmoid"),
])
def test_converter_refuses_what_the_stack_does_not_compute(change, error):
    from llm_instance_gateway_tpu.models.convert import config_from_hf

    with pytest.raises(NotImplementedError, match=error):
        config_from_hf(hf_smallthinker(**change))


def test_a_window_on_a_family_without_one_stays_refused():
    from llm_instance_gateway_tpu.models import convert

    mistral_like = types.SimpleNamespace(
        model_type="llama", sliding_window=4096, max_position_embeddings=32768,
        rope_scaling=None)
    with pytest.raises(NotImplementedError, match="sliding_window=4096"):
        convert.config_from_hf(mistral_like)


def test_converter_maps_the_familys_state_dict(params):
    """The tiny preset's own weights under the source's names (HF Linear
    weights are [out, in]) come back as the tree they were."""
    from llm_instance_gateway_tpu.models import convert

    layers = params["layers"]
    state = {"model.embed_tokens.weight": params["embed"][:CFG.vocab_size],
             "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"][:, :CFG.vocab_size].T}
    for i in range(CFG.n_layers):
        at = f"model.layers.{i}."
        state[at + "input_layernorm.weight"] = layers["attn_norm"][i]
        state[at + "post_attention_layernorm.weight"] = layers["mlp_norm"][i]
        for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                             ("wo", "o")):
            state[at + f"self_attn.{theirs}_proj.weight"] = layers[ours][i].T
        moe = at + "block_sparse_moe."
        state[moe + "primary_router.weight"] = layers["router"][i].T
        for e in range(CFG.n_experts):
            for ours in ("gate", "up", "down"):
                state[moe + f"experts.{e}.{ours}.weight"] = (
                    layers["w_" + ours][i, e].T)
    got = convert.params_from_hf_state_dict(CFG, state, dtype=jnp.float32)
    # the converter pads the vocabulary with zeros where ``init_params`` draws
    want = dict(params,
                embed=params["embed"].at[CFG.vocab_size:].set(0),
                lm_head=params["lm_head"].at[:, CFG.vocab_size:].set(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_a_config_restored_from_json_is_the_config():
    import json

    restored = type(CFG)(**json.loads(json.dumps(dataclasses.asdict(CFG))))
    assert restored.layer_pattern == CFG.layer_pattern
    assert restored.layer_kinds == CFG.layer_kinds
