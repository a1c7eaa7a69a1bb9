"""Model correctness tests (CPU, tiny configs).

The key invariant is prefill/decode parity: running the prompt through
``prefill`` and then decoding token-by-token from an inserted cache must
produce the same logits as prefill produced at those positions — this is the
correctness contract the serving engine relies on (JetStream-style
prefill -> insert -> generate).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import (
    GEMMA_2B,
    MIXTRAL_8X7B,
    TINY_TEST,
)

TINY_GEMMA = GEMMA_2B.tiny()
TINY_MOE = MIXTRAL_8X7B.tiny()


def make_model(cfg, seed=0, dtype=jnp.float32):
    # float32 on CPU: bf16 emulation is slow and loosens parity tolerances.
    return transformer.init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)


def random_tokens(cfg, b, s, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab_size)


@pytest.mark.parametrize("cfg", [TINY_TEST, TINY_GEMMA, TINY_MOE], ids=lambda c: c.name)
def test_prefill_shapes_and_finiteness(cfg):
    params = make_model(cfg)
    b, s = 2, 8
    tokens = random_tokens(cfg, b, s)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    logits, k, v = transformer.prefill(cfg, params, tokens, positions)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert k.shape == (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.resolved_head_dim)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("cfg", [TINY_TEST, TINY_GEMMA], ids=lambda c: c.name)
def test_prefill_decode_parity(cfg):
    """Decode from an inserted prefill cache must match prefill logits."""
    params = make_model(cfg)
    s = 6
    tokens = random_tokens(cfg, 1, s)
    positions = jnp.arange(s)[None]
    ref_logits, k, v = transformer.prefill(cfg, params, tokens, positions)

    # Insert prompt[:3] into a decode cache, then decode tokens 3..5.
    split = 3
    cache = transformer.init_decode_cache(cfg, batch=2, max_len=16, dtype=jnp.float32)
    cache = transformer.insert_prefill(
        cache, k[:, :, :split], v[:, :, :split], slot=0, length=split
    )
    for i in range(split, s):
        step_tokens = jnp.array([tokens[0, i], 0], jnp.int32)
        step_positions = jnp.array([i, 0], jnp.int32)
        logits, cache = transformer.decode_step(
            cfg, params, cache, step_tokens, step_positions
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(ref_logits[0, i]), rtol=2e-4, atol=2e-4
        )


def test_causality():
    """Changing a later token must not affect earlier logits."""
    cfg = TINY_TEST
    params = make_model(cfg)
    tokens = random_tokens(cfg, 1, 8)
    positions = jnp.arange(8)[None]
    logits_a, *_ = transformer.prefill(cfg, params, tokens, positions)
    tokens_b = tokens.at[0, 5].set((tokens[0, 5] + 1) % cfg.vocab_size)
    logits_b, *_ = transformer.prefill(cfg, params, tokens_b, positions)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :5]), np.asarray(logits_b[0, :5]), rtol=1e-5
    )
    assert not np.allclose(np.asarray(logits_a[0, 5]), np.asarray(logits_b[0, 5]))


def test_padding_invariance():
    """Right-padding a prompt must not change its logits (position masking)."""
    cfg = TINY_TEST
    params = make_model(cfg)
    tokens = random_tokens(cfg, 1, 4)
    positions = jnp.arange(4)[None]
    logits_short, *_ = transformer.prefill(cfg, params, tokens, positions)
    padded = jnp.concatenate([tokens, jnp.zeros((1, 4), tokens.dtype)], axis=1)
    padded_pos = jnp.concatenate([positions, jnp.zeros((1, 4), jnp.int32)], axis=1)
    logits_padded, *_ = transformer.prefill(cfg, params, padded, padded_pos)
    np.testing.assert_allclose(
        np.asarray(logits_short[0]), np.asarray(logits_padded[0, :4]), rtol=2e-4, atol=2e-4
    )


class TestLoRA:
    def make_adapter(self, cfg, rank, seed=3, targets=("q", "v")):
        dims = lora_lib.target_dims(cfg)
        rng = np.random.RandomState(seed)
        return {
            t: {
                "a": rng.randn(cfg.n_layers, dims[t][0], rank) * 0.1,
                "b": rng.randn(cfg.n_layers, rank, dims[t][1]) * 0.1,
            }
            for t in targets
        }

    def test_empty_slots_match_base(self):
        cfg = TINY_TEST
        params = make_model(cfg)
        bufs = lora_lib.init_lora_buffers(cfg, dtype=jnp.float32)
        tokens = random_tokens(cfg, 2, 4)
        positions = jnp.broadcast_to(jnp.arange(4), (2, 4))
        base, *_ = transformer.prefill(cfg, params, tokens, positions)
        slot_ids = jnp.array([0, -1], jnp.int32)  # zeroed slot == no adapter
        with_lora, *_ = transformer.prefill(
            cfg, params, tokens, positions, lora_bufs=bufs, slot_ids=slot_ids
        )
        np.testing.assert_allclose(np.asarray(base), np.asarray(with_lora), rtol=1e-5)

    def test_adapter_changes_only_its_rows(self):
        cfg = TINY_TEST
        params = make_model(cfg)
        bufs = lora_lib.init_lora_buffers(cfg, dtype=jnp.float32)
        bufs = lora_lib.load_adapter(bufs, cfg, slot=1, adapter=self.make_adapter(cfg, 2),
                                     alpha=8.0, rank=2)
        tokens = random_tokens(cfg, 2, 4)
        positions = jnp.broadcast_to(jnp.arange(4), (2, 4))
        base, *_ = transformer.prefill(cfg, params, tokens, positions)
        slot_ids = jnp.array([1, -1], jnp.int32)
        mixed, *_ = transformer.prefill(
            cfg, params, tokens, positions, lora_bufs=bufs, slot_ids=slot_ids
        )
        # Row 0 (adapter) differs; row 1 (base) identical.
        assert not np.allclose(np.asarray(base[0]), np.asarray(mixed[0]))
        np.testing.assert_allclose(np.asarray(base[1]), np.asarray(mixed[1]), rtol=1e-5)

    def test_rank_padding_equivalence(self):
        """A rank-r adapter must behave identically under any max_lora_rank >= r."""
        cfg_small = TINY_TEST  # max_lora_rank=4
        import dataclasses
        cfg_big = dataclasses.replace(cfg_small, max_lora_rank=8)
        params = make_model(cfg_small)
        adapter = self.make_adapter(cfg_small, rank=2)
        tokens = random_tokens(cfg_small, 1, 4)
        positions = jnp.arange(4)[None]
        outs = []
        for cfg in (cfg_small, cfg_big):
            bufs = lora_lib.init_lora_buffers(cfg, dtype=jnp.float32)
            bufs = lora_lib.load_adapter(bufs, cfg, 0, adapter, alpha=4.0, rank=2)
            logits, *_ = transformer.prefill(
                cfg, params, tokens, positions, lora_bufs=bufs,
                slot_ids=jnp.array([0], jnp.int32),
            )
            outs.append(np.asarray(logits))
        # The padded lanes add exact zeros; what may differ is the order in
        # which a matmul of another contraction length (slots x r_max) sums
        # its float32 products: parts in 1e5 of the largest logit, which a
        # purely relative bound would refuse at logits near 0.
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5,
                                   atol=1e-5 * np.abs(outs[0]).max())

    def test_unload_restores_base(self):
        cfg = TINY_TEST
        bufs = lora_lib.init_lora_buffers(cfg, dtype=jnp.float32)
        loaded = lora_lib.load_adapter(bufs, cfg, 0, self.make_adapter(cfg, 2), 8.0, 2)
        unloaded = lora_lib.unload_adapter(loaded, cfg, 0)
        for k in bufs:
            np.testing.assert_array_equal(np.asarray(bufs[k]), np.asarray(unloaded[k]))

    def test_slot_and_rank_validation(self):
        cfg = TINY_TEST
        bufs = lora_lib.init_lora_buffers(cfg)
        with pytest.raises(ValueError, match="slot"):
            lora_lib.load_adapter(bufs, cfg, 99, {}, 8.0, 2)
        with pytest.raises(ValueError, match="rank"):
            lora_lib.load_adapter(bufs, cfg, 0, {}, 8.0, 999)


class TestSampling:
    def test_greedy_and_temperature(self):
        from llm_instance_gateway_tpu.server.sampling import sample
        logits = jnp.array([[0.0, 5.0, 1.0], [10.0, 0.0, 0.0]], jnp.float32)
        toks = sample(
            logits, jax.random.PRNGKey(0),
            temperature=jnp.array([0.0, 0.0]),
            top_k=jnp.array([0, 0]), top_p=jnp.array([1.0, 1.0]),
        )
        assert toks.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        from llm_instance_gateway_tpu.server.sampling import sample
        logits = jnp.array([[1.0, 2.0, 3.0, 4.0]], jnp.float32)
        seen = set()
        for i in range(50):
            t = sample(logits, jax.random.PRNGKey(i),
                       temperature=jnp.array([5.0]),
                       top_k=jnp.array([2]), top_p=jnp.array([1.0]))
            seen.add(int(t[0]))
        assert seen <= {2, 3}

    def test_top_p_restricts_support(self):
        from llm_instance_gateway_tpu.server.sampling import sample
        # ~[0.64, 0.23, 0.09, 0.03]: top_p=0.5 keeps only token 0.
        logits = jnp.array([[4.0, 3.0, 2.0, 1.0]], jnp.float32)
        for i in range(30):
            t = sample(logits, jax.random.PRNGKey(i),
                       temperature=jnp.array([1.0]),
                       top_k=jnp.array([0]), top_p=jnp.array([0.5]))
            assert int(t[0]) == 0


class TestVocabPadding:
    def test_sampling_never_emits_padded_ids(self):
        """Zero-logit padding columns must be unsampleable at any temperature."""
        from llm_instance_gateway_tpu.server.sampling import sample
        valid = 5
        # Real ids have strongly NEGATIVE logits; padding columns sit at 0.0
        # (the padded lm_head case) and would dominate without the mask.
        logits = jnp.concatenate(
            [jnp.full((1, valid), -10.0), jnp.zeros((1, 123))], axis=1
        )
        for i in range(40):
            tok = sample(logits, jax.random.PRNGKey(i),
                         jnp.array([2.0]), jnp.array([0]), jnp.array([1.0]),
                         valid_vocab=valid)
            assert int(tok[0]) < valid


# ---------------------------------------------------------------------------
# The cached programs against a plain reference (PR 25)
# ---------------------------------------------------------------------------


def _ref_cached_forward(cfg, params, layers, tokens, positions, lanes, writes):
    """Plain reference for decode_step / extend_step / prefill_with_cache.

    The cache is a per-layer LIST of dicts of [B, S, K, hd] arrays (int8
    with [B, S, K] scales when quantized), each updated with ``.at[].set``;
    attention is a masked f32 softmax over the row's whole lane.  ``tokens``
    and ``positions`` are [R, C] (C new tokens for each of R rows),
    ``lanes`` [R] the cache lane of each row, ``writes`` [R] whether the
    row may write.  Returns (logits [R, C, V], the new list)."""
    from llm_instance_gateway_tpu.ops.layers import apply_rope, rms_norm

    r_n, c = tokens.shape
    hd = cfg.resolved_head_dim
    none = jnp.full((r_n,), -1, jnp.int32)
    h = transformer._embed(cfg, params, tokens)
    out_layers = []
    for l, lc in enumerate(layers):
        lp = jax.tree.map(lambda x: x[l], params["layers"])
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps,
                      plus_one=cfg.norm_plus_one)
        q, k, v = (transformer._attn_proj(cfg, lp, t, hn, None, none).reshape(
            r_n, c, n, hd) for t, n in (("q", cfg.n_heads),
                                        ("k", cfg.n_kv_heads),
                                        ("v", cfg.n_kv_heads)))
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        lc = dict(lc)
        for r in range(r_n):
            if not writes[r]:
                continue
            at = (lanes[r], positions[r])
            if "k_scale" in lc:
                (kq, ks), (vq, vs) = (transformer._kv_quantize(k[r]),
                                      transformer._kv_quantize(v[r]))
                lc["k"] = lc["k"].at[at].set(kq)
                lc["v"] = lc["v"].at[at].set(vq)
                lc["k_scale"] = lc["k_scale"].at[at].set(ks)
                lc["v_scale"] = lc["v_scale"].at[at].set(vs)
            else:
                lc["k"] = lc["k"].at[at].set(k[r])
                lc["v"] = lc["v"].at[at].set(v[r])
        out_layers.append(lc)
        attn = []
        for r in range(r_n):
            lane_k, lane_v = lc["k"][lanes[r]], lc["v"][lanes[r]]
            if "k_scale" in lc:
                lane_k = lane_k.astype(jnp.float32) * lc["k_scale"][lanes[r]][..., None]
                lane_v = lane_v.astype(jnp.float32) * lc["v_scale"][lanes[r]][..., None]
            qg = q[r].reshape(c, cfg.n_kv_heads, cfg.q_per_kv, hd)
            s = jnp.einsum("ikgh,jkh->kgij", qg, lane_k) / np.sqrt(hd)
            seen = jnp.arange(lane_k.shape[0])[None] <= positions[r][:, None]
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
            attn.append(jnp.einsum("kgij,jkh->ikgh", p, lane_v).reshape(c, -1))
        h = h + transformer._attn_out(lp, jnp.stack(attn), None, none)
        hn2 = rms_norm(h, lp["mlp_norm"], cfg.norm_eps,
                       plus_one=cfg.norm_plus_one)
        h = h + transformer._mlp(cfg, lp, hn2, None, none)[0]
    h = rms_norm(h, params["final_norm"], cfg.norm_eps,
                 plus_one=cfg.norm_plus_one)
    return transformer._lm_head(cfg, params, h), out_layers


def _random_cache(cfg, b, s, quant, seed=7):
    """A stacked cache with every cell filled (an arbitrary history)."""
    cache = transformer.init_decode_cache(cfg, b, s, dtype=jnp.float32,
                                          quantized=quant)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(keys[0], cache["k"].shape, jnp.float32)
    v = jax.random.normal(keys[1], cache["v"].shape, jnp.float32)
    if quant:
        (cache["k"], cache["k_scale"]), (cache["v"], cache["v_scale"]) = (
            transformer._kv_quantize(k), transformer._kv_quantize(v))
    else:
        cache["k"], cache["v"] = k, v
    return cache


def _assert_cache_equals_list(cache, layers):
    for name in ("k", "v", "k_scale", "v_scale"):
        if name not in cache:
            assert name not in layers[0]
            continue
        want = np.stack([np.asarray(lc[name]) for lc in layers])
        got = np.asarray(cache[name])
        if got.dtype == np.int8:
            # The same quantizer in another fusion: at most one step apart.
            assert np.max(np.abs(got.astype(np.int32) - want)) <= 1, name
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=name)


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "extend", "chunk"])
def test_cached_programs_match_plain_reference(program, quant):
    """decode_step, extend_step and prefill_with_cache (the stacked cache as
    the layer loop's carry, written in place) give the logits and the cache
    contents of the per-layer-list reference; an inactive row writes
    nothing."""
    cfg = TINY_TEST
    params = make_model(cfg)
    b, s = 3, 32
    cache = _random_cache(cfg, b, s, quant)
    before = jax.tree.map(np.asarray, cache)
    layers = [{n: cache[n][l] for n in cache if n != "length"}
              for l in range(cfg.n_layers)]
    active = np.array([True, True, False])
    if program == "chunk":
        c = 4
        tokens = random_tokens(cfg, 1, c, seed=3)
        positions = 9 + jnp.arange(c)[None]
        got, new = jax.jit(transformer.prefill_with_cache, static_argnums=0)(
            cfg, params, cache, tokens[0], positions[0], jnp.int32(1),
            jnp.int32(13), jnp.int32(c - 1))
        want, want_layers = _ref_cached_forward(
            cfg, params, layers, tokens, positions, [1], [True])
        want, rows = want[0, c - 1], slice(None)
        assert new["length"].tolist() == [0, 13, 0]
        untouched = [0, 2]
    else:
        c = 1 if program == "decode" else 3
        tokens = random_tokens(cfg, b, c, seed=3)
        positions = jnp.asarray([5, 9, 3])[:, None] + jnp.arange(c)[None]
        if program == "decode":
            got, new = jax.jit(transformer.decode_step, static_argnums=0)(
                cfg, params, cache, tokens[:, 0], positions[:, 0],
                active=jnp.asarray(active))
            got = got[:, None]
        else:
            got, new = jax.jit(transformer.extend_step, static_argnums=0)(
                cfg, params, cache, tokens, positions,
                active=jnp.asarray(active))
        want, want_layers = _ref_cached_forward(
            cfg, params, layers, tokens, positions, range(b), active)
        rows = active
        untouched = [2]
    scale = float(np.max(np.abs(np.asarray(want))))
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               rtol=2e-4, atol=2e-4 * scale)
    _assert_cache_equals_list(new, want_layers)
    for name in ("k", "v", "k_scale", "v_scale"):
        if name in new:  # a lane that may not write is bit for bit as it was
            np.testing.assert_array_equal(
                np.asarray(new[name])[:, untouched], before[name][:, untouched])


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("cfg", [TINY_TEST, TINY_MOE], ids=lambda c: c.name)
def test_decode_step_reads_nothing_of_a_slot_that_sits_out(cfg, quant):
    """The live rows' logits and the cache written are the same whether the
    other slots are empty (position 0) or hold a finished request's stale
    position: the attention takes every inactive row at length 0."""
    params = make_model(cfg)
    b, s = 5, 32
    cache = _random_cache(cfg, b, s, quant)
    tokens = random_tokens(cfg, b, 1, seed=3)[:, 0]
    active = jnp.asarray([False, True, False, True, False])
    step = jax.jit(transformer.decode_step, static_argnums=0)
    live = np.asarray(active)
    results = []
    for others in (0, jnp.asarray([17, 0, 30, 0, 4])):
        positions = jnp.where(active, jnp.asarray([0, 5, 0, 9, 0]), others)
        logits, new = step(cfg, params, cache, tokens, positions,
                           active=active)
        assert bool(jnp.all(jnp.isfinite(logits)))  # dead rows: only finite
        results.append((np.asarray(logits)[live],
                        {n: np.asarray(x) for n, x in new.items()
                         if n != "length"}))
        # the engine's view of the lanes is untouched by the mask
        assert new["length"].tolist() == (positions + 1).tolist()
    (empty_logits, empty_cache), (stale_logits, stale_cache) = results
    np.testing.assert_array_equal(empty_logits, stale_logits)
    for name in empty_cache:
        np.testing.assert_array_equal(empty_cache[name], stale_cache[name])


@pytest.mark.parametrize("masked", [False, True], ids=["active-None", "active"])
def test_decode_step_hands_the_attention_length_zero_only_by_active(masked):
    """Callers that pass no ``active`` get ``positions + 1`` for every row,
    as ever; with it, the rows that sit out arrive at length 0."""
    from llm_instance_gateway_tpu.ops.attention import decode_attention

    cfg = TINY_TEST
    params = make_model(cfg)
    cache = _random_cache(cfg, 3, 16, False)
    positions = jnp.asarray([5, 9, 3])
    active = jnp.asarray([True, False, True]) if masked else None
    seen = []

    def spy(q, k, v, lengths):
        seen.append(np.asarray(lengths).tolist())
        return decode_attention(q, k, v, lengths)

    with jax.disable_jit():
        _, new = transformer.decode_step(
            cfg, params, cache, jnp.asarray([1, 2, 3]), positions,
            attention_fn=spy, active=active)
    assert seen and all(x == ([6, 0, 4] if masked else [6, 10, 4])
                        for x in seen)
    assert new["length"].tolist() == [6, 10, 4]


def _scans(jaxpr):
    """Every scan equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "extend", "chunk"])
def test_layer_scan_carries_the_cache(program, quant):
    """Structure, not timing: the layer scan takes no array of the cache's
    shape as ``xs`` (a scanned input is sliced per layer, a scanned output
    stacked: with a donated cache that cost two whole-cache copies and a
    slice and a write-back per layer, PERF.md §6 PR 25) and carries every
    one of them."""
    cfg = TINY_TEST
    params = make_model(cfg)
    b, s = 3, 32
    cache = transformer.init_decode_cache(cfg, b, s, dtype=jnp.float32,
                                          quantized=quant)
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    if program == "decode":
        fn, args = transformer.decode_step, (i32((b,)), i32((b,)))
    elif program == "extend":
        fn, args = transformer.extend_step, (i32((b, 2)), i32((b, 2)))
    else:
        fn = transformer.prefill_with_cache
        args = (i32((4,)), i32((4,)), i32(()), i32(()), i32(()))
    jaxpr = jax.make_jaxpr(functools.partial(fn, cfg))(params, cache, *args)
    cache_shapes = sorted(
        (v.shape, v.dtype) for n, v in cache.items() if n != "length")
    (scan,) = [e for e in _scans(jaxpr.jaxpr)
               if e.params["length"] == cfg.n_layers]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    avals = [(v.aval.shape, v.aval.dtype) for v in scan.invars]
    carry, xs = avals[n_consts:n_consts + n_carry], avals[n_consts + n_carry:]
    ys = [(v.aval.shape, v.aval.dtype) for v in scan.outvars[n_carry:]]
    assert sorted(a for a in carry if a in cache_shapes) == cache_shapes
    assert not [a for a in xs + avals[:n_consts] if a in cache_shapes]
    assert not [a for a in xs + ys if a[0][1:] == cache["k"].shape[1:]]
