"""Falcon-H1's mechanisms at the tiny preset on the CPU: the parallel block
(a state-space mixer beside attention), the recurrent state in the cache and
the layer loop's carry, the chunked scan, the decode update and its kernel,
padding, the chunk stream, slot reuse, the engine in both loops, the counter
and every refusal of what a recurrent state does not serve.

Two limits, both float32 against ``models/reference.py``:

- logits within 1e-5 of the largest reference logit (seen: 3e-7, and 6e-7
  after 300 decode steps).  Two float32 programs that sum in different
  orders differ by rounding alone; the least visible thing that can be left
  out, the multiplier on dt, moves the logits by 5e-5, every other one by
  1e-3 to 100 (``test_each_multiplier_changes_the_logits``).
- the recurrent state a cache holds within 1e-5 of the reference's largest
  state entry (seen: 2e-7).  With seeded random weights and the family's
  multipliers the skip ``D x`` is most of the mixer's output and the state's
  reading a few percent, so a bf16 state moves the logits by under 1e-5 and
  only this limit sees it, at 5e-3 (``test_a_bf16_state_misses_...``).
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import reference, ssm, transformer
from llm_instance_gateway_tpu.models.configs import (
    FALCON_H1_34B,
    TINY_FALCON_H1_TEST,
    TINY_GLM_TEST,
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
    TINY_QWEN_TEST,
)
from llm_instance_gateway_tpu.ops import pallas_ssm
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from tests._reference import reference_tokens

CFG = TINY_FALCON_H1_TEST
TOL = 1e-5
STATE_TOL = 1e-5


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


def bucket_prefill(cfg, params, prompt, bucket):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    return transformer.prefill(
        cfg, params, jnp.asarray(toks), jnp.arange(bucket)[None],
        lengths=jnp.asarray([len(prompt)]))


def stream_prefill(cfg, params, cache, prompt, chunk, slot=1):
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        toks = np.zeros((chunk,), np.int32)
        toks[:len(piece)] = piece
        last, cache = transformer.prefill_with_cache(
            cfg, params, cache, jnp.asarray(toks), start + jnp.arange(chunk),
            slot, start + len(piece), len(piece) - 1)
    return last, cache


def reference_state(cfg, params, seq):
    """The reference's state after ``seq`` as the cache lays it:
    [L, heads, d_state, head_dim]."""
    states = []
    reference.forward(cfg, params, jnp.asarray(seq), states=states)
    return np.stack([np.asarray(h).transpose(0, 2, 1) for h in states])


def served_logits(cfg, params, seq, n, chunk=None, s_max=64, spoil=None,
                  state=None):
    """The prompt ``seq[:n]`` by bucket (``chunk`` None) or through the
    chunk stream, then the rest fed through the decode step on lane 1 of
    two.  Logits at position n - 1 and after every fed token.  ``spoil``
    rewrites the cache between steps (a planted fault); ``state``, a list,
    gets lane 1's recurrent state after the last step."""
    cache = transformer.init_decode_cache(cfg, 2, s_max, jnp.float32)
    if chunk is None:
        bucket = 1 << (n - 1).bit_length()
        logits, k, v = bucket_prefill(cfg, params, seq[:n], bucket)
        cache = transformer.insert_prefill(cache, k, v, 1, n)
        out = [logits[0, n - 1]]
    else:
        last, cache = stream_prefill(cfg, params, cache, seq[:n], chunk)
        out = [last]
    step = jax.jit(lambda c, t, p: transformer.decode_step(
        cfg, params, c, t, p, active=jnp.asarray([False, True])))
    for j in range(n, len(seq)):
        if spoil is not None:
            cache = spoil(cache)
        logits, cache = step(cache, jnp.asarray([0, int(seq[j])]),
                             jnp.asarray([0, j]))
        out.append(logits[1])
    if state is not None:
        state.append(np.asarray(cache["ssm"][:, 1]))
    return np.stack([np.asarray(x, np.float32) for x in out])


# -- the configuration --------------------------------------------------------

def test_presets_are_in_the_registry_the_benchmark_reads():
    from llm_instance_gateway_tpu.models import mixtral

    big = mixtral.CONFIGS["falcon-h1-34b"]
    tiny = mixtral.CONFIGS["falcon-h1-tiny"]
    assert big is FALCON_H1_34B and tiny is CFG
    assert (big.d_model, big.n_layers, big.n_heads, big.n_kv_heads,
            big.resolved_head_dim, big.d_ff, big.vocab_size
            ) == (5120, 72, 20, 4, 128, 21504, 261120)
    assert (big.ssm_d_inner, big.ssm_n_heads, big.ssm_head_dim,
            big.ssm_d_state, big.ssm_n_groups, big.ssm_d_conv, big.ssm_chunk
            ) == (4096, 32, 128, 256, 2, 4, 128)
    assert (big.ssm_conv_dim, big.ssm_in_dim) == (5120, 9248)
    assert big.rope_theta == 1e11 and big.max_lora_slots == 0
    # the tiny preset keeps the ratios: 5 queries a kv head, 2 groups
    assert tiny.q_per_kv == big.q_per_kv == 5
    assert tiny.ssm_n_groups == 2 and tiny.ssm_d_conv == 4
    assert tiny.ssm_n_heads * tiny.ssm_head_dim == tiny.ssm_d_inner
    mults = [tiny.embedding_multiplier, tiny.attention_in_multiplier,
             tiny.attention_out_multiplier, tiny.key_multiplier,
             tiny.ssm_in_multiplier, tiny.ssm_out_multiplier,
             tiny.lm_head_multiplier, *tiny.mlp_multipliers]
    assert all(m != 1.0 for m in mults + list(tiny.ssm_multipliers))


def test_a_layer_is_430_million_parameters_at_the_published_widths():
    shapes = jax.eval_shape(
        lambda: transformer.init_params(
            dataclasses.replace(FALCON_H1_34B, n_layers=1),
            jax.random.PRNGKey(0)))
    per_layer = sum(int(np.prod(x.shape))
                    for x in jax.tree.leaves(shapes["layers"]))
    assert abs(per_layer / 1e6 - 430.1) < 0.1
    assert shapes["layers"]["ssm_in"].shape == (1, 5120, 9248)
    assert shapes["layers"]["ssm_out"].shape == (1, 4096, 5120)


def test_int8_covers_the_mixers_projections_and_the_vectors_stay_float32():
    p = transformer.init_params(CFG, jax.random.PRNGKey(0), quantize=True)
    layers = p["layers"]
    for name in ("ssm_in", "ssm_out", "wq", "w_gate"):
        assert layers[name]["q"].dtype == jnp.int8, name
    assert layers["ssm_conv_w"].dtype == jnp.bfloat16
    for name in ("ssm_a_log", "ssm_dt_bias", "ssm_d"):
        assert layers[name].dtype == jnp.float32, name
        assert layers[name].shape == (CFG.n_layers, CFG.ssm_n_heads)


def test_the_vectors_are_drawn_as_the_mamba2_reference_draws_them():
    v = ssm.init_vectors(FALCON_H1_34B, jax.random.PRNGKey(1), 8)
    minus_a = np.exp(np.asarray(v["ssm_a_log"]))
    dt = np.asarray(jax.nn.softplus(v["ssm_dt_bias"]))
    assert 1.0 <= minus_a.min() and minus_a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.ptp(np.log(dt)) > 3.0  # spread over the decades, not one value
    assert np.all(np.asarray(v["ssm_d"]) == 1.0)


def test_cache_holds_the_state_beside_the_lanes():
    cache = transformer.init_decode_cache(CFG, 3, 32, jnp.bfloat16)
    assert set(cache) == {"k", "v", "length", "ssm", "conv"}
    assert cache["ssm"].shape == (CFG.n_layers, 3, CFG.ssm_n_heads,
                                  CFG.ssm_d_state, CFG.ssm_head_dim)
    assert cache["ssm"].dtype == jnp.float32  # whatever the activations are
    assert cache["conv"].shape == (CFG.n_layers, CFG.ssm_d_conv - 1, 3,
                                   CFG.ssm_conv_dim)
    assert cache["conv"].dtype == jnp.bfloat16
    big = jax.eval_shape(lambda: transformer.init_decode_cache(
        dataclasses.replace(FALCON_H1_34B, n_layers=8), 64, 2048))
    assert big["ssm"].shape == (8, 64, 32, 256, 128)
    assert big["conv"].shape == (8, 3, 64, 5120)
    with pytest.raises(ValueError, match="int8"):
        transformer.init_decode_cache(CFG, 2, 32, quantized=True)


@pytest.mark.parametrize("cfg", [TINY_QWEN_TEST, TINY_MOE_TEST,
                                 TINY_OLMOE_TEST, TINY_GLM_TEST],
                         ids=lambda c: c.name)
def test_a_model_without_a_mixer_has_no_new_array(cfg):
    """Neither in its cache, nor in the layer loop's carry, nor among its
    weights; and none of the multipliers is traced for it."""
    cache = transformer.init_decode_cache(cfg, 2, 32, jnp.float32)
    latent = bool(cfg.latent_width)
    assert set(cache) == ({"k", "length"} if latent
                          else {"k", "v", "length"})
    assert len(transformer._kv_carry(cache)) == (1 if latent else 2)
    p = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    assert not [n for n in p["layers"] if n.startswith("ssm_")]
    mults = ("embedding_multiplier", "attention_in_multiplier",
             "attention_out_multiplier", "key_multiplier",
             "ssm_in_multiplier", "ssm_out_multiplier", "lm_head_multiplier")
    assert all(getattr(cfg, m) == 1.0 for m in mults)
    assert cfg.mlp_multipliers == (1.0, 1.0) and cfg.ssm_d_inner == 0


# -- the recurrence: three forms of it ----------------------------------------

def scan_inputs(s, seed=0, b=2):
    rng = np.random.default_rng(seed)
    h, p, n, g = CFG.ssm_n_heads, CFG.ssm_head_dim, CFG.ssm_d_state, 2
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        size=(b, s, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    return f(b, s, h, p), dt, a, f(b, s, g, n), f(b, s, g, n), f(h)


@pytest.mark.parametrize("s", [1, 5, 127, 128, 129, 300])
def test_chunked_scan_equals_the_sequential_one(s):
    """In chunks of 128, at lengths that are no multiple of it; a state
    handed in is carried on."""
    cfg = dataclasses.replace(CFG, ssm_chunk=128)
    args = scan_inputs(s, seed=s)
    h0 = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, CFG.ssm_n_heads, CFG.ssm_d_state, CFG.ssm_head_dim)),
        jnp.float32)
    for start in (None, h0):
        y_seq, h_seq = ssm.scan_sequential(cfg, *args, h0=start)
        y_chk, h_chk = ssm.scan_chunked(cfg, *args, h0=start)
        assert y_chk.shape == y_seq.shape == (2, s, CFG.ssm_n_heads,
                                              CFG.ssm_head_dim)
        assert rel_err(y_chk, y_seq) < 1e-5
        assert rel_err(h_chk, h_seq) < 1e-5


def test_a_position_with_no_step_leaves_the_state_alone():
    """dt = 0 is decay 1 and increment 0: what padding relies on."""
    x, dt, a, bm, cm, d = scan_inputs(20)
    dt = dt.at[:, 12:].set(0.0)
    _, h_all = ssm.scan_chunked(CFG, x, dt, a, bm, cm, d)
    _, h_cut = ssm.scan_chunked(CFG, x[:, :12], dt[:, :12], a, bm[:, :12],
                                cm[:, :12], d)
    np.testing.assert_allclose(h_all, h_cut, rtol=1e-6, atol=1e-7)


def update_inputs(b, h, g, n, p, seed=0, layers=2):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(-6, -1, size=(b, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    return (f(layers, b, h, n, p), f(b, h, p), dt, a, f(b, g, n), f(b, g, n),
            f(h))


LIVE_ROWS = {"all": [1, 1, 1, 1, 1], "some": [0, 1, 0, 1, 0],
             "last": [0, 0, 0, 0, 1], "first": [1, 0, 0, 0, 0],
             "none": [0, 0, 0, 0, 0]}


@pytest.mark.parametrize("case", sorted(LIVE_ROWS))
def test_kernel_matches_the_jnp_update_in_interpret_mode(case):
    """``ssm_decode_update`` (interpreted) over a stacked state and a layer
    index at the published tile shapes (a group of 8 heads x 256 x 128)
    against ``ssm_update_xla``: the live rows' states rewritten in place,
    every other row's and every other layer's left bit for bit, y zero for
    a row that sits out."""
    live = jnp.asarray(LIVE_ROWS[case], bool)
    state, *rest = update_inputs(5, 16, 2, 256, 128, seed=len(case))
    for layer in (0, 1):
        y, new = pallas_ssm.ssm_decode_update(
            state, *rest, live=live, layer=layer, interpret=True)
        want_y, want = pallas_ssm.ssm_update_xla(state[layer], *rest, live)
        np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(new[layer], want, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(new[1 - layer], state[1 - layer])
        dead = ~np.asarray(live)
        np.testing.assert_array_equal(np.asarray(new[layer])[dead],
                                      np.asarray(state[layer])[dead])
        assert not np.asarray(y)[dead].any()


def test_kernel_takes_only_whole_tiles():
    assert pallas_ssm.shape_reasons(32, 2, 256, 128) == []
    assert pallas_ssm.shape_reasons(4, 2, 16, 32)     # the tiny preset
    assert pallas_ssm.shape_reasons(32, 2, 1024, 128)  # B, C over 8 rows
    assert pallas_ssm.shape_reasons(64, 1, 256, 128)   # an 8 MiB block
    # off the chip the dispatcher takes the jnp update on the layer named
    state, *rest = update_inputs(3, 4, 2, 16, 32)
    y0, new0 = pallas_ssm.ssm_update_xla(state[1], *rest)
    y1, new1 = pallas_ssm.ssm_decode_update(state, *rest, None, 1)
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(new0, new1[1])
    np.testing.assert_array_equal(new1[0], state[0])


def test_gate_comes_before_the_norm_and_the_norm_is_per_group():
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(3, CFG.ssm_d_inner)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(3, CFG.ssm_d_inner)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(CFG.ssm_d_inner,)), jnp.float32)
    got = ssm.gate_norm(CFG, {"ssm_norm": w}, y, z)
    yz = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 2, -1)
    want = (yz / np.sqrt((yz ** 2).mean(-1, keepdims=True) + CFG.norm_eps)
            ).reshape(3, -1) * np.asarray(w)
    assert rel_err(got, want) < 1e-5
    one_group = ssm.gate_norm(dataclasses.replace(CFG, ssm_n_groups=1),
                              {"ssm_norm": w}, y, z)
    assert rel_err(one_group, want) > 1e-2


# -- the serving path against the reference ------------------------------------

@pytest.mark.parametrize("n", [3, 21, 64])
def test_prefill_logits_match_the_reference(params, n):
    seq = sequence(n)
    want = reference.forward(CFG, params, jnp.asarray(seq))
    bucket = 1 << (n - 1).bit_length()
    logits, k, v = bucket_prefill(CFG, params, seq, bucket)
    assert rel_err(logits[0, :n], want) < TOL
    assert set(v) == {"v", "ssm", "conv"}
    assert v["ssm"].shape == (CFG.n_layers, 1, CFG.ssm_n_heads,
                              CFG.ssm_d_state, CFG.ssm_head_dim)
    assert v["conv"].shape == (CFG.n_layers, 1, 3, CFG.ssm_conv_dim)


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunks"])
def test_float32_serving_path_matches_the_reference(params, chunk):
    """A prompt by bucket or through the chunk stream (21 tokens in chunks
    of 8: the last one padded), then decode through the cache."""
    seq, n = sequence(27), 21
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    got = served_logits(CFG, params, seq, n, chunk)[:len(want)]
    assert rel_err(got, want) < TOL


def test_three_hundred_decode_steps_stay_on_the_reference(params):
    """Prefill of 24 tokens, then 310 steps through the cache: at every
    step's position the logits are the reference's full forward's."""
    seq, n = sequence(335, seed=9), 24
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    state = []
    got = served_logits(CFG, params, seq, n, s_max=512, state=state)
    assert len(want) >= 300
    per_step = (np.max(np.abs(got[:len(want)] - want), axis=-1)
                / np.max(np.abs(want)))
    assert per_step.max() < TOL
    assert per_step[-50:].max() < TOL  # and it does not drift
    # the state after the last fed token (the reference's after seq[:-1]:
    # the last token of ``seq`` is fed, its successor never sampled)
    assert rel_err(state[0], reference_state(CFG, params, seq)) < STATE_TOL


def test_a_bf16_state_misses_the_reference(params):
    """The same path with the state rounded to bf16 after every step: the
    state's limit catches it by two orders of magnitude (the configuration's
    float32 state is not a matter of taste), the logits' limit does not
    (module docstring)."""
    seq, n = sequence(120, seed=9), 24

    def rounded(cache):
        return dict(cache, ssm=cache["ssm"].astype(jnp.bfloat16).astype(
            jnp.float32))

    state, sound = [], []
    served_logits(CFG, params, seq, n, s_max=128, spoil=rounded, state=state)
    served_logits(CFG, params, seq, n, s_max=128, state=sound)
    want = reference_state(CFG, params, seq)
    assert rel_err(sound[0], want) < STATE_TOL
    assert rel_err(state[0], want) > 50 * STATE_TOL


BUCKETS = (8, 16, 32)


@pytest.mark.parametrize("n", [7, 8, 9, 15, 16, 17, 31, 32, 33])
def test_padding_leaves_the_state_of_the_last_true_token(params, n):
    """A prompt at every bucket boundary - 1, 0, + 1, padded to its bucket
    (past the largest: through the chunk stream, its last chunk padded),
    installs the state, the conv history and the next-token logits of the
    prompt alone."""
    seq = sequence(n, seed=n)
    logits, _, bare = transformer.prefill(
        CFG, params, jnp.asarray(seq)[None], jnp.arange(n)[None])
    cache = transformer.init_decode_cache(CFG, 2, 64, jnp.float32)
    bucket = next((b for b in BUCKETS if b >= n), None)
    if bucket is None:
        last, cache = stream_prefill(CFG, params, cache, seq, BUCKETS[-1])
    else:
        padded_logits, k, v = bucket_prefill(CFG, params, seq, bucket)
        last = padded_logits[0, n - 1]
        cache = transformer.insert_prefill(cache, k, v, 1, n)
    assert rel_err(last, logits[0, n - 1]) < 1e-5
    assert rel_err(cache["ssm"][:, 1], bare["ssm"][:, 0]) < 1e-5
    assert rel_err(cache["conv"][:, :, 1], bare["conv"][:, 0]) < 1e-5
    assert int(cache["length"][1]) == n
    assert not np.asarray(cache["ssm"][:, 0]).any()  # the other lane


def test_a_prompt_shorter_than_the_conv_keeps_zeros_before_position_zero(
        params):
    seq = sequence(2)
    _, _, v = bucket_prefill(CFG, params, seq, 8)
    assert not np.asarray(v["conv"][:, 0, 0]).any()   # before position 0
    assert np.asarray(v["conv"][:, 0, 1:]).all(axis=-1).all()


def test_a_reused_slot_holds_no_trace_of_the_request_before(params):
    """A second request inserted over a first, long, one (by bucket and by
    the chunk stream): its logits are those of a fresh cache, bit for bit."""
    first, second = sequence(30, seed=1), sequence(11, seed=2)
    tail = sequence(6, seed=3)

    def run(cache, chunk):
        if chunk:
            last, cache = stream_prefill(CFG, params, cache, second, chunk)
        else:
            logits, k, v = bucket_prefill(CFG, params, second, 16)
            last = logits[0, len(second) - 1]
            cache = transformer.insert_prefill(cache, k, v, 1, len(second))
        out = [last]
        for j, tok in enumerate(tail):
            logits, cache = transformer.decode_step(
                CFG, params, cache, jnp.asarray([0, int(tok)]),
                jnp.asarray([0, len(second) + j]),
                active=jnp.asarray([False, True]))
            out.append(logits[1])
        return np.stack(out)

    for chunk in (None, 8):
        fresh = transformer.init_decode_cache(CFG, 2, 64, jnp.float32)
        used = transformer.init_decode_cache(CFG, 2, 64, jnp.float32)
        _, k, v = bucket_prefill(CFG, params, first, 32)
        used = transformer.insert_prefill(used, k, v, 1, len(first))
        for j in range(5):  # and it decoded for a while
            _, used = transformer.decode_step(
                CFG, params, used, jnp.asarray([0, 7]),
                jnp.asarray([0, len(first) + j]),
                active=jnp.asarray([False, True]))
        assert np.asarray(used["ssm"][:, 1]).any()
        np.testing.assert_array_equal(run(used, chunk), run(fresh, chunk))


def test_a_live_rows_logits_do_not_depend_on_the_other_rows(params):
    """Row 1 of three decodes the same tokens beside rows that are free,
    frozen mid-request or busy with other prompts; a row that sits out
    keeps its state, its conv history and its lanes."""
    seq = sequence(20, seed=4)
    other = sequence(9, seed=6)

    def run(neighbours: str):
        cache = transformer.init_decode_cache(CFG, 3, 64, jnp.float32)
        _, k, v = bucket_prefill(CFG, params, seq[:12], 16)
        cache = transformer.insert_prefill(cache, k, v, 1, 12)
        if neighbours != "free":
            _, k, v = bucket_prefill(CFG, params, other, 16)
            cache = transformer.insert_prefill(cache, k, v, 0, len(other))
            cache = transformer.insert_prefill(cache, k, v, 2, len(other))
        active = jnp.asarray([neighbours == "busy", True,
                              neighbours == "busy"])
        before = cache
        out = []
        for j in range(12, 20):
            logits, cache = transformer.decode_step(
                CFG, params, cache,
                jnp.asarray([5 + j, int(seq[j]), 200 - j]),
                jnp.asarray([len(other) + j - 12, j, len(other) + j - 12]),
                active=active)
            out.append(logits[1])
        if neighbours == "frozen":
            for name in ("ssm", "k", "v"):
                np.testing.assert_array_equal(cache[name][:, 0],
                                              before[name][:, 0])
            np.testing.assert_array_equal(cache["conv"][:, :, 0],
                                          before["conv"][:, :, 0])
        return np.stack(out)

    free = run("free")
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[12:]
    assert rel_err(free[:-1], want[:len(free) - 1]) < TOL
    np.testing.assert_allclose(run("frozen"), free, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(run("busy"), free, rtol=1e-5, atol=1e-8)


MULTIPLIERS = {
    "embedding_multiplier": {"embedding_multiplier": 1.0},
    "attention_in_multiplier": {"attention_in_multiplier": 1.0},
    "attention_out_multiplier": {"attention_out_multiplier": 1.0},
    "key_multiplier": {"key_multiplier": 1.0},
    "ssm_in_multiplier": {"ssm_in_multiplier": 1.0},
    "ssm_out_multiplier": {"ssm_out_multiplier": 1.0},
    "lm_head_multiplier": {"lm_head_multiplier": 1.0},
    "mlp_gate": {"mlp_multipliers": (1.0, CFG.mlp_multipliers[1])},
    "mlp_down": {"mlp_multipliers": (CFG.mlp_multipliers[0], 1.0)},
    **{f"ssm_multipliers_{part}": {"ssm_multipliers": tuple(
        1.0 if j == i else m for j, m in enumerate(CFG.ssm_multipliers))}
       for i, part in enumerate(("z", "x", "B", "C", "dt"))},
}


@pytest.mark.parametrize("name", sorted(MULTIPLIERS))
def test_each_multiplier_changes_the_logits(params, name):
    """The serving path with one multiplier left out is not the model: it
    misses the float32 limit by orders of magnitude, in prefill and in
    decode."""
    seq, n = sequence(20), 16
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    without = dataclasses.replace(CFG, **MULTIPLIERS[name])
    got = served_logits(without, params, seq, n)[:len(want)]
    assert rel_err(got[:1], want[:1]) > 3 * TOL   # the prefill's logits
    assert rel_err(got[1:], want[1:]) > 3 * TOL   # the decode steps'


def test_planted_faults_in_the_mixer_miss_the_reference(params):
    """No conv bias, no skip (D), no dt bias, another decay, one group of
    B and C for all heads: each is another function."""
    seq, n = sequence(20), 16
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    layers = params["layers"]
    faults = {
        "conv bias": dict(layers, ssm_conv_b=jnp.zeros_like(
            layers["ssm_conv_b"])),
        "skip": dict(layers, ssm_d=jnp.zeros_like(layers["ssm_d"])),
        "dt bias": dict(layers, ssm_dt_bias=jnp.zeros_like(
            layers["ssm_dt_bias"])),
        "decay": dict(layers, ssm_a_log=layers["ssm_a_log"] + 1.0),
    }
    for what, spoiled in faults.items():
        got = served_logits(CFG, dict(params, layers=spoiled), seq, n)
        assert rel_err(got[:len(want)], want) > 30 * TOL, what
    one_group = dataclasses.replace(CFG, ssm_n_groups=1, ssm_d_state=32)
    got = served_logits(one_group, params, seq, n)
    assert rel_err(got[:len(want)], want) > 30 * TOL


# -- the engine ---------------------------------------------------------------

def test_engine_gives_the_references_tokens_with_slot_reuse(params):
    """Five requests over two slots, bucketed and chunk-streamed prompts
    mixed, no adapter buffers (``lora_manager`` None, as ``--max-loras 0``
    serves): greedy tokens equal the reference's, so no slot carries a
    state over and no step moves the state of a row it should not."""
    engine = Engine(
        CFG, params,
        EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(8, 16)),
        eos_id=None, dtype=jnp.float32)
    prompts = [[3, 5, 7], list(range(3, 28)), [9, 8, 7, 6, 5, 4, 3, 2, 1, 11],
               list(range(40, 60)), [100, 200]]
    engine.start()
    try:
        reqs = [engine.submit(Request(prompt_tokens=p, max_new_tokens=5))
                for p in prompts]
        for req in reqs:
            assert req.done.wait(300) and req.error is None, req.error
    finally:
        engine.stop()
    for prompt, req in zip(prompts, reqs):
        assert req.output_tokens == reference_tokens(CFG, params, prompt, 5)
    hist = engine.profiler.hist_state()
    assert hist["ssm_rows"] >= 5 * 4  # every decode step of every request
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert f"tpu:ssm_state_rows_total {hist['ssm_rows']}\n" in text
    assert engine.profiler.snapshot()["hist"]["ssm_rows"] == hist["ssm_rows"]


def test_counter_is_the_live_rows_times_the_steps(params):
    """One request of 6 new tokens: five decode steps (the first new token
    comes from the prefill) and the one that was dispatched before the
    fifth was read, one row each (a row the host still holds counts)."""
    engine = Engine(CFG, params,
                    EngineConfig(decode_slots=2, max_seq_len=64,
                                 prefill_buckets=(8,)),
                    eos_id=None, dtype=jnp.float32)
    engine.start()
    try:
        req = engine.generate(Request(prompt_tokens=[3, 5, 7],
                                      max_new_tokens=6), timeout_s=300)
        assert req.error is None
    finally:
        engine.stop()
    steps = engine.profiler.dispatches["decode"]
    assert 5 <= steps <= 6
    assert engine.profiler.hist_state()["ssm_rows"] == steps


def test_a_model_without_a_mixer_counts_no_state_row():
    cfg = TINY_QWEN_TEST
    engine = Engine(cfg, transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(8,)),
        eos_id=None, dtype=jnp.float32)
    engine.start()
    try:
        engine.generate(Request(prompt_tokens=[3, 5, 7], max_new_tokens=4),
                        timeout_s=300)
    finally:
        engine.stop()
    assert set(engine.cache) == {"k", "v", "length"}
    assert engine.profiler.hist_state()["ssm_rows"] == 0
    assert "tpu:ssm_state_rows_total 0\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


def test_profile_report_has_a_section_for_the_counter():
    import sys

    sys.path.insert(0, "tools")
    import profile_report

    profile = {"hist": {"ssm_rows": 640, "wall": {"decode": {"count": 10}}}}
    assert profile_report.ssm_rows_row(profile) == {
        "ssm_rows": 640, "decode_dispatches": 10, "rows_per_dispatch": 64.0}
    assert profile_report.ssm_rows_row({"hist": {"ssm_rows": 0}}) == {}
    assert profile_report.ssm_rows_row({}) == {}


# -- what a recurrent state does not serve: refused at start-up, by name ------

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
def test_engine_refuses_what_a_recurrent_state_does_not_serve(params, case):
    engine_kw, ctor_kw, names = REFUSED[case]
    if "draft_cfg" in ctor_kw:
        ctor_kw = dict(ctor_kw, draft_params=params)
    with pytest.raises(ValueError, match="recurrent") as err:
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, **engine_kw),
               eos_id=None, dtype=jnp.float32, **ctor_kw)
    assert names in str(err.value) and CFG.name in str(err.value)


def test_the_handoff_api_is_refused_in_every_role(params):
    """A collocated engine keeps ``prefill_only`` / ``attach_prefilled``;
    for a recurrent state (and a latent cache) they say why they cannot
    serve, before anything is queued."""
    engine = Engine(CFG, params,
                    EngineConfig(decode_slots=2, max_seq_len=64),
                    eos_id=None, dtype=jnp.float32)
    with pytest.raises(ValueError, match="kv_transfer.*recurrent state"):
        engine.prefill_only(Request(prompt_tokens=[3, 5, 7]))
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.attach_prefilled(object())
    glm = Engine(TINY_GLM_TEST, transformer.init_params(
        TINY_GLM_TEST, jax.random.PRNGKey(0), dtype=jnp.float32),
        EngineConfig(decode_slots=2, max_seq_len=64), eos_id=None,
        dtype=jnp.float32)
    with pytest.raises(ValueError, match="kv_transfer.*latent cache"):
        glm.prefill_only(Request(prompt_tokens=[3, 5, 7]))


def test_extend_step_is_refused_for_a_recurrent_state(params):
    with pytest.raises(NotImplementedError, match="rolled back"):
        transformer.extend_step(CFG, params, {}, jnp.zeros((1, 2), jnp.int32),
                                jnp.zeros((1, 2), jnp.int32))


@pytest.mark.parametrize("flags", [["--max-loras", "4"],
                                   ["--max-loras", "0", "--mesh", "tensor=2"]],
                         ids=["adapters", "mesh"])
def test_server_refuses_adapters_and_a_mesh_by_name(flags):
    from llm_instance_gateway_tpu.server import api_http

    with pytest.raises(SystemExit, match="falcon-h1-tiny.*--max-loras 0"):
        api_http.main(["--model", "falcon-h1-tiny", "--platform", "cpu",
                       *flags])


def test_debug_device_reports_the_mixers_sizes():
    import inspect

    from llm_instance_gateway_tpu.server import api_http

    src = inspect.getsource(api_http.ModelServer)
    for field in ("ssm_d_inner", "ssm_n_heads", "ssm_head_dim",
                  "ssm_d_state", "ssm_n_groups", "ssm_d_conv", "ssm_chunk"):
        assert f'"{field}"' in src, field
