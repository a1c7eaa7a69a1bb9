"""The serving programs against the plain float32 reference
(``models/reference.py``) on seeded random weights: prefill, then decode
steps through the cache, must give the LOGITS of the reference's full
forward over prompt + fed tokens (fed by teacher forcing, so both sides
see the same sequence; with random weights an argmax flips on rounding).

Both sides run in float32 here, so the tolerance is float32's: the two
differ in the order of summation (a cache and a dispatch against one full
forward and a loop over all experts) and by XLA's CPU matmul against
``precision=highest``: 1e-4 of the largest logit; the largest error seen
is 7e-7.  The three planted faults of ``TestPlantedFaults`` miss it by an
order of magnitude or more: a bf16 router softmax reads 1.1e-3, a
renormalised gate 0.35, no QK-norm 0.30.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import (
    lora as lora_lib,
    paged,
    reference,
    transformer,
)
from llm_instance_gateway_tpu.models.configs import (
    TINY_GLM_TEST,
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
    TINY_QWEN_TEST,
)

TOL = 1e-4          # of the largest reference logit of the sequence
S_MAX, N_DECODE = 32, 8
PROMPTS = (9, 5)    # two lanes of different lengths, decoded together
CFGS = {"olmoe-tiny": TINY_OLMOE_TEST, "qwen-tiny": TINY_QWEN_TEST,
        "mixtral-tiny": TINY_MOE_TEST, "glm-tiny": TINY_GLM_TEST}
# every norm weight, bias and selection bias a model can have
_OFF_INIT = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "wq_b", "wk_b",
             "wv_b", "q_latent_norm", "kv_latent_norm")


def make_model(cfg, seed=0):
    """Seeded weights with every leaf off its init value: norm weights,
    biases and QK-norm weights random, so none of them is a silent 1 or 0."""
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=jnp.float32)
    key = jax.random.PRNGKey(seed + 100)
    out = dict(params)
    for group in ("dense_layers", "layers"):  # GLM: a dense stack first
        if group not in params:
            continue
        layers = dict(params[group])
        for name in _OFF_INIT:
            if name in layers:
                key, k = jax.random.split(key)
                base = 0.0 if name.endswith("_b") else 1.0
                layers[name] = base + 0.3 * jax.random.normal(
                    k, layers[name].shape, jnp.float32)
        out[group] = layers
    return out


def make_lora(cfg, slot=1, seed=7):
    """One adapter on q and v (what the benchmark's adapters target)."""
    bufs = lora_lib.init_lora_buffers(cfg, dtype=jnp.float32)
    dims, r = lora_lib.target_dims(cfg), cfg.max_lora_rank
    key = jax.random.PRNGKey(seed)
    adapter = {}
    for t in ("q", "v"):
        key, ka, kb = jax.random.split(key, 3)
        adapter[t] = {
            "a": jax.random.normal(ka, (cfg.n_layers, dims[t][0], r)) * 0.3,
            "b": jax.random.normal(kb, (cfg.n_layers, r, dims[t][1])) * 0.3}
    return lora_lib.load_adapter(bufs, cfg, slot, adapter, alpha=2.0 * r,
                                 rank=r)


def sequences(cfg, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n + N_DECODE).astype(np.int32)
            for n in PROMPTS]


def reference_logits(cfg, params, seqs, lora):
    return [np.asarray(reference.forward(cfg, params, jnp.asarray(s), lora))
            for s in seqs]


def lane_logits(cfg, params, seqs, bufs, slot_ids):
    """Bucket prefill -> insert -> N_DECODE decode steps over both lanes.
    Returns per sequence the logits at its last prompt position and at
    every decoded position."""
    b = len(seqs)
    cache = transformer.init_decode_cache(cfg, b, S_MAX, dtype=jnp.float32)
    out = [[] for _ in seqs]
    for i, (seq, n) in enumerate(zip(seqs, PROMPTS)):
        bucket = 16
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq[:n]
        pos = jnp.arange(bucket)[None]
        logits, k, v = transformer.prefill(
            cfg, params, jnp.asarray(toks), pos, lora_bufs=bufs,
            slot_ids=slot_ids[i:i + 1])
        cache = transformer.insert_prefill(cache, k, v, i, n)
        out[i].append(np.asarray(logits[0, n - 1]))
    step = jax.jit(lambda c, t, p: transformer.decode_step(
        cfg, params, c, t, p, lora_bufs=bufs, slot_ids=slot_ids,
        active=jnp.ones((b,), bool)))
    for j in range(N_DECODE):
        toks = jnp.asarray([s[n + j] for s, n in zip(seqs, PROMPTS)])
        pos = jnp.asarray([n + j for n in PROMPTS])
        logits, cache = step(cache, toks, pos)
        for i in range(b):
            out[i].append(np.asarray(logits[i]))
    return out


def paged_logits(cfg, params, seqs, bufs, slot_ids):
    """Chunk prefill into each row's blocks -> N_DECODE paged steps."""
    b, block = len(seqs), 8
    per_row = S_MAX // block
    cache = paged.init_paged_cache(cfg, b, S_MAX, b * per_row, block,
                                   dtype=jnp.float32)
    tables = 1 + jnp.arange(b * per_row, dtype=jnp.int32).reshape(b, per_row)
    cache["tables"] = tables
    out = [[] for _ in seqs]
    for i, (seq, n) in enumerate(zip(seqs, PROMPTS)):
        chunk = 16
        toks = np.zeros((chunk,), np.int32)
        toks[:n] = seq[:n]
        last, cache = paged.prefill_with_cache_paged(
            cfg, params, cache, jnp.asarray(toks), jnp.arange(chunk), i, n,
            n - 1, lora_bufs=bufs, lora_slot=slot_ids[i])
        out[i].append(np.asarray(last))
    step = jax.jit(lambda c, t, p: paged.decode_step_paged(
        cfg, params, c, t, p, lora_bufs=bufs, slot_ids=slot_ids,
        active=jnp.ones((b,), bool)))
    for j in range(N_DECODE):
        toks = jnp.asarray([s[n + j] for s, n in zip(seqs, PROMPTS)])
        pos = jnp.asarray([n + j for n in PROMPTS])
        logits, cache = step(cache, toks, pos)
        for i in range(b):
            out[i].append(np.asarray(logits[i]))
    return out


def worst_error(got, want):
    """Largest |difference| over the compared positions of every sequence,
    as a share of that sequence's largest reference logit."""
    worst = 0.0
    for (g, w, n) in zip(got, want, PROMPTS):
        ref = w[n - 1: n + N_DECODE]
        worst = max(worst, float(np.max(np.abs(np.stack(g) - ref))
                                 / np.max(np.abs(ref))))
    return worst


def parity_error(cfg, params, system, adapter, system_cfg=None,
                 system_params=None):
    """Reference on (cfg, params); the system on the same unless a planted
    fault hands it something else."""
    seqs = sequences(cfg)
    # a latent model serves no adapter: no buffers at all
    bufs = None if cfg.latent_width else make_lora(cfg)
    # lane 0 on the adapter in slot 1, lane 1 on the base model
    slot_ids = jnp.asarray([1 if adapter else -1, -1], jnp.int32)
    want = reference_logits(cfg, params, seqs, None)
    if adapter:
        want[0] = np.asarray(reference.forward(
            cfg, params, jnp.asarray(seqs[0]), (bufs, 1)))
    got = system(system_cfg or cfg, system_params or params, seqs, bufs,
                 slot_ids)
    return worst_error(got, want)


@pytest.fixture(scope="module", params=sorted(CFGS))
def model(request):
    cfg = CFGS[request.param]
    return cfg, make_model(cfg)


@pytest.mark.parametrize("adapter", [False, True], ids=["base", "adapter"])
def test_lanes_match_reference(model, adapter):
    cfg, params = model
    if adapter and cfg.latent_width:
        with pytest.raises(NotImplementedError):
            reference.forward(cfg, params, jnp.zeros((4,), jnp.int32),
                              (None, 0))
        return
    assert parity_error(cfg, params, lane_logits, adapter) < TOL


@pytest.mark.parametrize("adapter", [False, True], ids=["base", "adapter"])
def test_paged_olmoe_matches_reference(adapter):
    cfg = TINY_OLMOE_TEST
    assert parity_error(cfg, make_model(cfg), paged_logits, adapter) < TOL


def test_adapter_moves_the_logits():
    """The adapter case is not the base case again: the reference with and
    without the adapter differ by far more than the tolerance."""
    cfg = TINY_OLMOE_TEST
    params, seq = make_model(cfg), jnp.asarray(sequences(cfg)[0])
    base = reference.forward(cfg, params, seq)
    tuned = reference.forward(cfg, params, seq, (make_lora(cfg), 1))
    assert float(jnp.max(jnp.abs(base - tuned))) > 100 * TOL * float(
        jnp.max(jnp.abs(base)))


class TestPlantedFaults:
    """Each fault is planted in what the SYSTEM runs; the reference keeps
    the architecture.  Every one must miss the tolerance."""

    cfg = TINY_OLMOE_TEST

    def test_renormalised_gate_fails(self):
        wrong = dataclasses.replace(self.cfg, norm_topk_prob=True)
        err = parity_error(self.cfg, make_model(self.cfg), lane_logits,
                           False, system_cfg=wrong)
        assert err > 10 * TOL

    def test_missing_qk_norm_fails(self):
        params = make_model(self.cfg)
        bare = dict(params, layers={k: v for k, v in params["layers"].items()
                                    if k not in ("q_norm", "k_norm")})
        err = parity_error(self.cfg, params, lane_logits, False,
                           system_params=bare)
        assert err > 10 * TOL

    def test_bf16_router_softmax_fails(self, monkeypatch):
        real = jax.nn.logsumexp

        def bf16_lse(x, **kw):
            return real(x.astype(jnp.bfloat16), **kw).astype(jnp.float32)

        monkeypatch.setattr(jax.nn, "logsumexp", bf16_lse)
        err = parity_error(self.cfg, make_model(self.cfg), lane_logits, False)
        assert err > 10 * TOL
