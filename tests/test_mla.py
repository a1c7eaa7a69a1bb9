"""GLM-4.7-Flash's mechanisms at the tiny preset on the CPU: the latent (MLA)
cache and its two forms of attention, the stack of two kinds of layer, the
sigmoid router with a selection bias and a shared expert, the engine in both
loops, the counter, and every refusal of what a latent cache does not serve.

float32 against ``models/reference.py`` is held to 1e-4 of the largest
reference logit (the largest seen is 5e-6); bf16 to the repo's limits for a
bf16 program against the float32 reference (``benchmark/reference_check``:
0.04 largest at published widths; here 0.15, because at 64 wide one flipped
expert choice moves a logit by several percent).
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import mla, reference, transformer
from llm_instance_gateway_tpu.models.configs import (
    GLM_4_7_FLASH,
    TINY_GLM_TEST,
    TINY_OLMOE_TEST,
)
from llm_instance_gateway_tpu.ops import pallas_decode_attention as pda
from llm_instance_gateway_tpu.ops.attention import latent_decode_attention
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from tests._reference import reference_tokens

CFG = TINY_GLM_TEST
TOL = 1e-4


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


# -- the configuration --------------------------------------------------------

def test_presets_are_in_the_registry_the_benchmark_reads():
    from llm_instance_gateway_tpu.models import mixtral

    big, tiny = mixtral.CONFIGS["glm-4.7-flash"], mixtral.CONFIGS["glm-tiny"]
    assert (big.d_model, big.n_layers, big.n_heads, big.d_ff, big.vocab_size
            ) == (2048, 47, 20, 10240, 154880)
    assert (big.q_lora_rank, big.kv_lora_rank, big.qk_nope_head_dim,
            big.qk_rope_head_dim, big.v_head_dim) == (768, 512, 192, 64, 256)
    assert big.resolved_head_dim == 192 + 64
    assert (big.n_experts, big.n_experts_per_token, big.moe_d_ff,
            big.n_shared_experts, big.first_k_dense) == (64, 4, 1536, 1, 1)
    assert big.router_sigmoid and big.norm_topk_prob
    assert big.routed_scaling_factor == 1.8
    assert (big.latent_width, big.latent_lanes) == (576, 640)
    # tiny: 1 dense + 2 sparse, 64 experts top-4 kept, five distinct sizes
    assert (tiny.n_layers, tiny.first_k_dense, tiny.n_experts,
            tiny.n_experts_per_token) == (3, 1, 64, 4)
    sizes = (tiny.q_lora_rank, tiny.kv_lora_rank, tiny.qk_nope_head_dim,
             tiny.qk_rope_head_dim, tiny.v_head_dim)
    assert len(set(sizes)) == 5
    assert tiny.resolved_head_dim == tiny.qk_nope_head_dim + tiny.qk_rope_head_dim
    # a model without the fields is what it was
    assert (TINY_OLMOE_TEST.latent_width, TINY_OLMOE_TEST.expert_d_ff) == (
        0, TINY_OLMOE_TEST.d_ff)


def test_the_stack_is_one_dense_layer_then_sparse_ones(params):
    dense, sparse = params["dense_layers"], params["layers"]
    assert dense["attn_norm"].shape[0] == 1 and "router" not in dense
    assert dense["w_gate"].shape == (1, CFG.d_model, CFG.d_ff)
    assert sparse["attn_norm"].shape[0] == CFG.n_layers - 1
    assert sparse["w_gate"].shape == (2, 64, CFG.d_model, CFG.moe_d_ff)
    assert sparse["ws_gate"].shape == (2, CFG.d_model, CFG.moe_d_ff)
    assert sparse["router_bias"].shape == (2, 64)
    # drawn non-zero: a router that ignores it must not pass
    assert float(jnp.std(sparse["router_bias"])) > 0.05
    for name in ("wq", "wk", "wv"):
        assert name not in dense and name not in sparse
    assert sparse["wkv_down"].shape == (2, CFG.d_model, CFG.latent_width)


def test_int8_covers_the_latent_projections_and_the_shared_expert():
    q = transformer.init_params(CFG, jax.random.PRNGKey(0), quantize=True)
    for name in ("wq_down", "wq_up", "wkv_down", "wkv_up", "wo", "ws_gate",
                 "ws_up", "ws_down", "w_gate"):
        assert q["layers"][name]["q"].dtype == jnp.int8, name
    assert q["dense_layers"]["w_down"]["q"].dtype == jnp.int8
    assert q["layers"]["router"].dtype == jnp.bfloat16
    assert q["layers"]["router_bias"].dtype == jnp.bfloat16


# -- the cache ----------------------------------------------------------------

def test_cache_holds_576_numbers_a_position_a_layer():
    big = dataclasses.replace(GLM_4_7_FLASH, n_layers=13)
    shape = jax.eval_shape(
        lambda: transformer.init_decode_cache(big, 32, 4096))
    assert set(shape) == {"k", "length"}  # no per-head K or V anywhere
    assert shape["k"].shape == (13, 32, 4096, 640)
    assert big.latent_width == 576
    nbytes = int(np.prod(shape["k"].shape)) * 2
    assert nbytes == 32 * 4096 * 13 * 1280  # 2.18 GB; per-head K+V: 35 GB
    with pytest.raises(ValueError, match="no int8 form"):
        transformer.init_decode_cache(big, 2, 128, quantized=True)


def test_prefill_writes_the_row_and_leaves_the_padding_zero(params):
    seq = sequence(12)
    _, k, v = transformer.prefill(CFG, params, jnp.asarray(seq)[None],
                                  jnp.arange(12)[None])
    assert k.shape == (CFG.n_layers, 1, 12, CFG.latent_lanes)
    assert v.shape == (CFG.n_layers, 1, 12, 0)
    assert float(jnp.min(jnp.max(jnp.abs(k[..., :CFG.latent_width]),
                                 axis=-1))) > 0
    assert float(jnp.max(jnp.abs(k[..., CFG.latent_width:]))) == 0.0
    cache = transformer.init_decode_cache(CFG, 2, 32, jnp.float32)
    cache = transformer.insert_prefill(cache, k, v, 1, 12)
    assert set(cache) == {"k", "length"}
    assert cache["length"].tolist() == [0, 12]
    np.testing.assert_array_equal(cache["k"][:, 1, :12], k[:, 0])
    assert float(jnp.max(jnp.abs(cache["k"][:, 0]))) == 0.0


# -- attention: bucket, chunk and decode against the reference -----------------

def served_logits(cfg, params, seq, n, chunk=None, dtype=jnp.float32,
                  s_max=64):
    """The prompt ``seq[:n]`` by bucket (``chunk`` None) or through the
    chunk stream, then the rest fed through the decode step on lane 1 of
    two.  Logits at position n - 1 and after every fed token."""
    cache = transformer.init_decode_cache(cfg, 2, s_max, dtype)
    if chunk is None:
        bucket = 32
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq[:n]
        logits, k, v = transformer.prefill(
            cfg, params, jnp.asarray(toks), jnp.arange(bucket)[None],
            lengths=jnp.asarray([n]))
        cache = transformer.insert_prefill(cache, k, v, 1, n)
        out = [logits[0, n - 1]]
    else:
        for start in range(0, n, chunk):
            piece = seq[start:min(n, start + chunk)]
            toks = np.zeros((chunk,), np.int32)
            toks[:len(piece)] = piece
            last, cache = transformer.prefill_with_cache(
                cfg, params, cache, jnp.asarray(toks),
                start + jnp.arange(chunk), 1, start + len(piece),
                len(piece) - 1)
        out = [last]
    step = jax.jit(lambda c, t, p: transformer.decode_step(
        cfg, params, c, t, p, active=jnp.asarray([False, True])))
    for j in range(n, len(seq)):
        logits, cache = step(cache, jnp.asarray([0, int(seq[j])]),
                             jnp.asarray([0, j]))
        out.append(logits[1])
    return np.stack([np.asarray(x, np.float32) for x in out])


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunks"])
def test_float32_serving_path_matches_the_reference(params, chunk):
    seq, n = sequence(27), 21
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    got = served_logits(CFG, params, seq, n, chunk)[:len(want)]
    assert rel_err(got, want) < TOL


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunks"])
def test_bf16_serving_path_stays_within_the_repos_limit(chunk):
    bf16 = transformer.init_params(CFG, jax.random.PRNGKey(3),
                                   dtype=jnp.bfloat16)
    seq, n = sequence(27), 21
    want = np.asarray(reference.forward(CFG, bf16, jnp.asarray(seq)))[n - 1:-1]
    got = served_logits(CFG, bf16, seq, n, chunk, jnp.bfloat16)[:len(want)]
    assert rel_err(got, want) < 0.15
    assert float(np.mean(np.abs(got - want)) / np.mean(np.abs(want))) < 0.05


def test_absorbed_equals_expanded(params):
    """One layer's attention both ways on the same latents: per-head keys
    and values expanded from them, and the absorbed query against the rows
    themselves."""
    lp = {k: v[0] for k, v in params["layers"].items()}
    rng = np.random.default_rng(0)
    b, s = 3, 16
    hn = jnp.asarray(rng.normal(size=(b, s, CFG.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_nope, q_rope, rows = mla.project(CFG, lp, hn, pos)
    k, v = mla.expand(CFG, lp, rows)
    assert k.shape == (b, s, CFG.n_heads, CFG.resolved_head_dim)
    assert v.shape == (b, s, CFG.n_heads, CFG.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)
    logits = jnp.einsum("bhd,bshd->bhs", q[:, -1], k) / np.sqrt(q.shape[-1])
    want = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(logits, -1), v)
    q_lat = mla.absorb_query(CFG, lp, q_nope[:, -1], q_rope[:, -1])
    assert q_lat.shape == (b, CFG.n_heads, CFG.latent_lanes)
    o_lat = latent_decode_attention(q_lat, rows, jnp.full((b,), s), CFG.kv_lora_rank,
                           1 / np.sqrt(q.shape[-1]))
    got = mla.absorb_output(CFG, lp, o_lat)
    assert rel_err(got, want.reshape(b, -1)) < 1e-5


def test_absorbed_form_reads_int8_weights_as_the_expanded_one(params):
    from llm_instance_gateway_tpu.ops.quant import quantize_weight

    lp = {k: v[0] for k, v in params["layers"].items()}
    lp["wkv_up"] = quantize_weight(lp["wkv_up"])
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.normal(size=(2, 8, CFG.latent_lanes)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(
        size=(2, CFG.n_heads, CFG.qk_nope_head_dim)), jnp.float32)
    k, v = mla.expand(CFG, lp, rows)
    q_lat = mla.absorb_query(CFG, lp, q_nope, jnp.zeros(
        (2, CFG.n_heads, CFG.qk_rope_head_dim)))
    want = jnp.einsum("bhn,bshn->bhs", q_nope, k[..., :CFG.qk_nope_head_dim])
    got = jnp.einsum("bhc,bsc->bhs", q_lat, rows)
    assert rel_err(got, want) < 1e-5
    # every position at weight 1: the latents' sum through W^V_h against the
    # expanded values' sum
    o_lat = jnp.broadcast_to(
        jnp.sum(rows[..., :CFG.kv_lora_rank], axis=1)[:, None],
        (2, CFG.n_heads, CFG.kv_lora_rank))
    assert rel_err(mla.absorb_output(CFG, lp, o_lat),
                   jnp.sum(v, axis=1).reshape(2, -1)) < 1e-5


@pytest.mark.parametrize("lengths", [(1, 130, 256), (200, 77, 5)])
def test_kernel_matches_the_xla_form_in_interpret_mode(lengths):
    """The Pallas kernel (interpreted) over a stacked cache and a layer
    index against ``latent_decode_attention``: rows past a length contribute
    nothing, one tile serves scores and values."""
    rng = np.random.default_rng(2)
    b, h, lanes, n_values, s_max = 3, 4, 256, 128, 256
    rows = jnp.asarray(rng.normal(size=(2, b, s_max, lanes)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, h, lanes)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 1):
        got = pda.mla_decode_attention_pallas(
            q, rows, lens, n_values, 0.125, layer=layer, block_s=128,
            interpret=True)
        want = latent_decode_attention(q, rows[layer], lens, n_values, 0.125)
        assert got.shape == (b, h, n_values)
        assert rel_err(got, want) < 1e-5
    assert pda.mla_shape_reasons(4096, 640, 512) == []
    assert pda.mla_shape_reasons(4096, 576, 512)  # rows must be whole vregs
    assert pda._mla_block(4096) == 1024 and pda._mla_block(384) == 128


# -- the router and the shared expert -----------------------------------------

def manual_moe(cfg, lp, x):
    """The sparse layer by its equations, in numpy float64."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    x = f(x)
    s = 1 / (1 + np.exp(-(x @ f(lp["router"]))))
    pick = s + f(lp["router_bias"])
    silu = lambda z: z / (1 + np.exp(-z))  # noqa: E731
    mlp = lambda z, g, u, d: (silu(z @ f(g)) * (z @ f(u))) @ f(d)  # noqa: E731
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-pick[t])[:cfg.n_experts_per_token]
        gates = cfg.routed_scaling_factor * s[t, chosen] / (
            s[t, chosen].sum() + 1e-20)
        for e, g in zip(chosen, gates):
            y[t] += g * mlp(x[t], lp["w_gate"][e], lp["w_up"][e],
                            lp["w_down"][e])
        y[t] += mlp(x[t], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, pick


@pytest.fixture(scope="module")
def sparse_layer(params):
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(6, CFG.d_model)),
                    jnp.float32)
    return lp, x


def test_sparse_layer_is_its_equations(sparse_layer):
    lp, x = sparse_layer
    y, tally = transformer._moe_mlp(CFG, lp, x)
    want, _ = manual_moe(CFG, lp, x)
    assert rel_err(y, want) < 1e-5
    assert tally.tolist()[:2] == [1, 6 * CFG.n_experts_per_token]


def test_bias_changes_the_choice_and_not_the_gates(sparse_layer):
    lp, x = sparse_layer
    base, _ = transformer._moe_mlp(CFG, lp, x)
    # the same bias on every expert: the same choice, and no gate moves
    shifted = dict(lp, router_bias=lp["router_bias"] + 5.0)
    same, _ = transformer._moe_mlp(CFG, shifted, x)
    assert rel_err(same, base) < 1e-6
    # a bias that forces expert 9 into every token's four
    _, pick = manual_moe(CFG, lp, x)
    assert not all(9 in np.argsort(-pick[t])[:4] for t in range(len(pick)))
    forced = dict(lp, router_bias=lp["router_bias"].at[9].add(10.0))
    moved, _ = transformer._moe_mlp(CFG, forced, x)
    assert rel_err(moved, base) > 1e-2
    # ... chosen by the bias, weighed without it
    assert rel_err(moved, manual_moe(CFG, forced, x)[0]) < 1e-5
    # a router that ignores the bias is another function
    ignored, _ = transformer._moe_mlp(
        CFG, dict(lp, router_bias=jnp.zeros_like(lp["router_bias"])), x)
    assert rel_err(ignored, base) > 1e-2


def test_scale_renormalisation_and_shared_expert_are_each_applied(sparse_layer):
    lp, x = sparse_layer
    base, _ = transformer._moe_mlp(CFG, lp, x)
    bare = {k: v for k, v in lp.items() if not k.startswith("ws_")}
    routed, _ = transformer._moe_mlp(CFG, bare, x)
    shared = (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])) @ lp["ws_down"]
    assert rel_err(base - routed, shared) < 1e-5  # added once, unweighted
    unscaled, _ = transformer._moe_mlp(
        dataclasses.replace(CFG, routed_scaling_factor=1.0), bare, x)
    assert rel_err(unscaled * 1.8, routed) < 1e-5
    raw, _ = transformer._moe_mlp(
        dataclasses.replace(CFG, norm_topk_prob=False), bare, x)
    assert rel_err(raw, routed) > 1e-2
    # renormalised: with 64 copies of one expert the gates' sum shows, 1.8
    one = {k: (jnp.broadcast_to(v[:1], v.shape) if k.startswith("w_") else v)
           for k, v in bare.items()}
    e0 = (jax.nn.silu(x @ bare["w_gate"][0]) * (x @ bare["w_up"][0])
          ) @ bare["w_down"][0]
    assert rel_err(transformer._moe_mlp(CFG, one, x)[0], 1.8 * e0) < 1e-5


def test_planted_faults_miss_the_reference(params):
    """The system with a softmax router, or without the selection bias, is
    not the model: each misses the float32 limit by orders of magnitude."""
    seq, n = sequence(20), 16
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq)))[n - 1:-1]
    softmax = dataclasses.replace(CFG, router_sigmoid=False)
    got = served_logits(softmax, params, seq, n)[:len(want)]
    assert rel_err(got, want) > 100 * TOL
    no_bias = dict(params, layers=dict(
        params["layers"],
        router_bias=jnp.zeros_like(params["layers"]["router_bias"])))
    got = served_logits(CFG, no_bias, seq, n)[:len(want)]
    assert rel_err(got, want) > 100 * TOL


# -- the engine ---------------------------------------------------------------

def test_engine_gives_the_references_tokens_with_slot_reuse(params):
    """Five requests over two slots, bucketed and chunk-streamed prompts
    mixed, no adapter buffers at all (``lora_manager`` None, as
    ``--max-loras 0`` serves): greedy tokens equal the reference's."""
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
    # every decode step read its live rows' whole lanes
    assert hist["latent_positions"] > sum(map(len, prompts))
    assert hist["moe"]["layer_steps"] > 0
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert f"tpu:latent_kv_positions_total {hist['latent_positions']}\n" in text
    assert "tpu:lora_rows_total 0\n" in text


def test_counter_sums_the_live_rows_cache_lengths(params):
    """One request of 3 prompt tokens and 6 new ones: the decode steps
    read 4, 5, ... positions (the first new token comes from the
    prefill)."""
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
    assert engine.profiler.hist_state()["latent_positions"] == sum(
        4 + j for j in range(steps))


def test_a_model_with_head_lanes_counts_no_latent_position():
    cfg = TINY_OLMOE_TEST
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
    assert engine.profiler.hist_state()["latent_positions"] == 0
    assert "tpu:latent_kv_positions_total 0\n" in metrics.render(
        engine.metrics_snapshot()) + "\n"


# -- what a latent cache does not serve: refused at start-up, by name ---------

REFUSED = {
    "paged": (dict(paged_kv_block=16), {}, "paged-kv-block"),
    "prefix_cache": (dict(paged_kv_block=16, prefix_cache=True), {},
                     "prefix cache"),
    "kv_int8": (dict(kv_cache_quant="int8"), {}, "kv-quantize"),
    "role_prefill": (dict(role="prefill"), {}, "kv_transfer"),
    "role_decode": (dict(role="decode"), {}, "kv_transfer"),
    "mesh": ({}, dict(mesh=types.SimpleNamespace(size=4)), "--mesh"),
    "adapters": ({}, dict(lora_manager=object()), "max-loras"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_engine_refuses_what_a_latent_cache_does_not_serve(params, case):
    engine_kw, ctor_kw, names = REFUSED[case]
    with pytest.raises(ValueError, match="latent") as err:
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, **engine_kw),
               eos_id=None, dtype=jnp.float32, **ctor_kw)
    assert names in str(err.value) and CFG.name in str(err.value)


def test_engine_refuses_speculative_decoding_for_a_latent_model(params):
    with pytest.raises(ValueError, match="speculative"):
        Engine(CFG, params,
               EngineConfig(decode_slots=2, max_seq_len=64, speculative_k=2),
               eos_id=None, dtype=jnp.float32, draft_params=params,
               draft_cfg=CFG)
    with pytest.raises(NotImplementedError, match="latent"):
        transformer.extend_step(CFG, params, {}, jnp.zeros((1, 2), jnp.int32),
                                jnp.zeros((1, 2), jnp.int32))


def test_server_refuses_adapter_slots_for_a_latent_model_by_name():
    from llm_instance_gateway_tpu.server import api_http

    with pytest.raises(SystemExit, match="glm-tiny.*--max-loras 0"):
        api_http.main(["--model", "glm-tiny", "--platform", "cpu",
                       "--max-loras", "4"])


def test_debug_device_reports_the_new_fields():
    import inspect

    from llm_instance_gateway_tpu.server import api_http

    src = inspect.getsource(api_http.ModelServer)
    for field in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "moe_d_ff",
                  "n_shared_experts", "first_k_dense", "router_sigmoid",
                  "routed_scaling_factor"):
        assert f'"{field}"' in src, field


# -- the kernel, compiled for the chip at the published widths -----------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_kernel_compiles_for_the_v5e_at_published_widths(one_chip):
    """32 rows x 4,096 positions of 640-lane rows over 13 layers, 20 heads:
    the chip's compiler takes the kernel as the cell runs it."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    from jax.experimental.compilation_cache import compilation_cache

    fn = jax.jit(lambda q, rows, lens, layer: pda.mla_decode_attention_pallas(
        q, rows, lens, 512, 1 / 16, layer=layer))
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(
            sd((32, 20, 640), jnp.bfloat16),
            sd((13, 32, 4096, 640), jnp.bfloat16),
            sd((32,), jnp.int32), sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "mla_decode_attention" in text and "tpu_custom_call" in text


def test_lane_kernel_compiles_for_the_v5e_with_its_dynamic_grid(one_chip):
    """Qwen2.5-7B's lanes, 32 slots x 2,048 positions over 28 layers (here,
    beside the other compiles for the chip: one process may describe it):
    the chip's compiler takes the lane kernel with its grid's bound a
    runtime value, the schedule's length, which the interpreter the other
    tests run under cannot take."""
    from jax.experimental.compilation_cache import compilation_cache

    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(lambda q, k, v, lens, layer: pda.decode_attention_pallas(
        q, k, v, lens, layer=layer))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lanes = sd((28, 32, 2048, 4, 128), jnp.bfloat16)
        compiled = fn.lower(sd((32, 28, 128), jnp.bfloat16), lanes, lanes,
                            sd((32,), jnp.int32), sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "decode_attention" in text and "tpu_custom_call" in text


def test_delta_rule_kernel_compiles_for_the_v5e_at_lings_widths(one_chip):
    """Ling-3.0-flash's delta-rule state (here, beside the other compiles
    for the chip: one process may describe it): 64 slots x 32 heads x 128 x
    128 float32 over 10 layers, rewritten in place: the chip's compiler takes
    the kernel with its 128 x 128 transpose and its blocks of 16 heads."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_instance_gateway_tpu.ops import pallas_kda

    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(pallas_kda.kda_decode_update_pallas, donate_argnums=(0,))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        vec = sd((64, 32, 128))
        compiled = fn.lower(sd((10, 64, 32, 128, 128)), vec, vec, vec, vec,
                            sd((64, 32)), sd((64,), jnp.bool_),
                            sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "kda_decode_update" in text and "tpu_custom_call" in text
    # in place: the state's 1.34 GB is aliased, not copied
    assert compiled.memory_analysis().alias_size_in_bytes >= 10 * 64 * 2 ** 21


@pytest.mark.parametrize("op", ["decode", "flash", "chunk"])
def test_packed_head_kernels_compile_for_the_v5e_at_lfm2s_widths(one_chip, op):
    """LFM2's 64-wide heads (here, beside the other compiles for the chip:
    one process may describe it): two kv heads to a 128-lane row, the
    queries padded into their head's columns, the softmax's scale the
    narrow head's; 64 slots x 8,192 positions over 3 layers, a 1,024-token
    bucket and a 1,024-token chunk, as the cell runs them."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_instance_gateway_tpu.ops import attention, pallas_attention

    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    bf16, scale = jnp.bfloat16, 64 ** -0.5
    pad = lambda q: attention.pad_queries(q, 8, 2)  # noqa: E731
    own = lambda o: attention.own_values(o, 8, 2)  # noqa: E731
    if op == "decode":
        lanes = sd((3, 64, 8192, 4, 128), bf16)
        fn = jax.jit(lambda q, k, v, lens, layer: own(
            pda.decode_attention_pallas(pad(q), k, v, lens, layer=layer,
                                        scale=scale)))
        shapes = (sd((64, 32, 64), bf16), lanes, lanes, sd((64,), jnp.int32),
                  sd((), jnp.int32))
        name = "decode_attention"
    elif op == "flash":
        fn = jax.jit(lambda q, k, v: own(pallas_attention.flash_attention_bhsd(
            pad(q).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale).transpose(0, 2, 1, 3)))
        kv = sd((1, 1024, 4, 128), bf16)
        shapes = (sd((1, 1024, 32, 64), bf16), kv, kv)
        name = "flash_attention"
    else:
        fn = jax.jit(lambda q, k, v, start: own(
            pallas_attention.chunk_attention_pallas(
                pad(q), k, v, start,
                block_q=pallas_attention.CHUNK_BLOCK_Q,
                block_k=pallas_attention.CHUNK_BLOCK_K, scale=scale)))
        lane = sd((1, 8192, 4, 128), bf16)
        shapes = (sd((1, 1024, 32, 64), bf16), lane, lane, sd((), jnp.int32))
        name = "chunk_attention"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert name in text and "tpu_custom_call" in text


# (query heads, the lane as the kernel sees it [S, K, hd], window, the tiles
# ``chunk_blocks`` picks): the chunk attend's layouts in the cells that
# stream chunk programs, then every other group the presets reach.
CHUNK_LAYOUTS = {
    "smallthinker-full": (28, (16384, 4, 128), 0, (256, 1024)),
    "smallthinker-ring": (28, (4096 + 1024, 4, 128), 4096, (256, 1024)),
    "qwen7b-doc": (28, (2048, 4, 128), 0, (256, 1024)),
    "glm-expanded": (20, (4096, 20, 256), 0, (256, 1024)),
    # two 64-wide kv heads a row: 32 padded query heads over 4 rows, g 8
    "lfm2-packed": (32, (8192, 4, 128), 0, (256, 1024)),
    "mixtral-g4": (32, (4096, 8, 128), 0, (256, 1024)),
    # 8 heads of 256 over one kv head: 256 query rows of them are over a
    # step's VMEM budget, 128 are not
    "gemma-2b-g8-hd256": (8, (8192, 1, 256), 0, (128, 1024)),
}


@pytest.mark.parametrize("layout", sorted(CHUNK_LAYOUTS))
def test_chunk_attend_compiles_for_the_v5e_at_the_cells_layouts(one_chip,
                                                                layout):
    """One grid step holds the tiles and the softmax state of ALL the query
    heads of a kv head: the tiles ``chunk_blocks`` picks from the shapes
    have to fit a step's VMEM on the chip (here, beside the other compiles
    for the chip: one process may describe it)."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_instance_gateway_tpu.ops import pallas_attention

    h, (s_max, n_kv, hd), window, want = CHUNK_LAYOUTS[layout]
    block_q, block_k = pallas_attention.chunk_blocks(1024, s_max, h // n_kv,
                                                     hd)
    assert (block_q, block_k) == want
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    lane = sd((1, s_max, n_kv, hd), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, start: pallas_attention.chunk_attention_pallas(
        q, k, v, start, block_q=block_q, block_k=block_k, window=window))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(sd((1, 1024, h, hd), jnp.bfloat16), lane, lane,
                            sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "chunk_attention" in text and "tpu_custom_call" in text


def test_ssm_update_kernel_compiles_for_the_v5e_at_published_widths(one_chip):
    """Falcon-H1-34B's state, 64 slots x 8 layers of 32 x 256 x 128 float32
    (2 GiB), through ``ssm_decode_update`` as the cell runs it (here, beside
    the other compile for the chip, because one process may describe the
    chip and this file is the one that does): the chip's compiler takes the
    kernel, and the state goes in and comes out as ONE buffer."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_instance_gateway_tpu.ops import pallas_ssm

    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(
        lambda state, x, dt, a, bm, cm, d, live, layer:
        pallas_ssm.ssm_decode_update_pallas(state, x, dt, a, bm, cm, d, live,
                                            layer),
        donate_argnums=(0,))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(
            sd((8, 64, 32, 256, 128)), sd((64, 32, 128)), sd((64, 32)),
            sd((32,)), sd((64, 2, 256)), sd((64, 2, 256)), sd((32,)),
            sd((64,), jnp.bool_), sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "ssm_decode_update" in text and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    state_bytes = 8 * 64 * 32 * 256 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes  # aliased, not copied
    assert mem.temp_size_in_bytes < state_bytes // 100


@pytest.mark.parametrize("e,k,n,assignments,by_group", [
    (8, 4096, 14336, 64, True),     # Mixtral gate/up of a decode step: 32
                                    # rows x top-2
    (8, 14336, 4096, 64, True),     # down of the same
    (8, 4096, 14336, 1024, True),   # gate/up of a 512-token prompt: the most
                                    # rows the group order holds in VMEM
    (8, 14336, 4096, 2048, False),  # down of a 1,024-token prompt: by tile
    (128, 2560, 768, 512, False),   # Ling-3.0-flash gate/up, 64 rows x top-8
    (128, 768, 2560, 512, False),   # ... down
    (64, 2048, 1536, 128, False),   # GLM-4.7-Flash gate/up, 32 rows x top-4
    (64, 2560, 768, 6144, False),   # SmallThinker gate/up, a 1,024-token chunk
])
def test_expert_matmul_compiles_for_the_v5e_at_the_cells_widths(
        one_chip, e, k, n, assignments, by_group):
    """``moe_gmm_int8`` over a stack of 4 layers of Mixtral-8x7B's 8 wide
    experts and of the narrow ones of Ling, GLM and SmallThinker (here for the
    reason above): the chip's compiler takes the kernel in both of its orders,
    the rows' grid dimension bound by a traced scalar (the last one by group,
    the MIDDLE one by tile), with all rows' x, output block and accumulators
    in VMEM beside the double-buffered weight block."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_instance_gateway_tpu.ops import pallas_moe

    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    tm = pallas_moe.tile_rows(assignments, e)
    tiles = pallas_moe.n_tiles(assignments, e, tm)
    tk, tn = pallas_moe._blocks(k, n, 1)
    assert pallas_moe._by_group(tiles * tm, k, n, tn, 1) == by_group
    fn = jax.jit(lambda x, w, te, used, layer: pallas_moe.grouped_matmul_pallas(
        x, w, te, used, layer, tm=tm))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(
            sd((tiles * tm, k), jnp.bfloat16),
            {"q": sd((4, e, k, n), jnp.int8), "s": sd((4, e, n), jnp.float32)},
            sd((tiles,), jnp.int32), sd((), jnp.int32),
            sd((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "moe_gmm_int8" in text and "tpu_custom_call" in text
    # the stack is read where it lies: no layer's experts are copied out
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
