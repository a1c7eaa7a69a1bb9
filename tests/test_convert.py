"""HF numerics parity: our decoder must match transformers' Llama exactly.

Builds a tiny randomly-initialized ``LlamaForCausalLM`` in memory (no
downloads), converts its weights, and compares logits — this pins our RoPE
convention, GQA layout, norm placement, and head transposes to the canonical
implementation.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.convert import from_hf_llama


def build_hf_llama(vocab=128, hidden=64, layers=2, heads=4, kv_heads=2, ff=128):
    cfg = transformers.LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        intermediate_size=ff, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10_000.0, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def hf_and_ours():
    model = build_hf_llama()
    cfg, params = from_hf_llama(model, dtype=jnp.float32)
    return model, cfg, params


def test_logits_match_hf(hf_and_ours):
    model, cfg, params = hf_and_ours
    ids = np.array([[3, 17, 54, 9, 88, 120, 7, 42]], np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()  # [1, S, V]
    tokens = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[1])[None]
    ours, *_ = transformer.prefill(cfg, params, tokens, positions)
    ours = np.asarray(ours)[:, :, : model.config.vocab_size]
    np.testing.assert_allclose(hf_logits, ours, rtol=2e-4, atol=2e-4)


def test_gqa_shapes_converted(hf_and_ours):
    model, cfg, params = hf_and_ours
    assert cfg.n_kv_heads == 2 and cfg.n_heads == 4
    assert params["layers"]["wk"].shape == (2, 64, 2 * 16)
    assert params["layers"]["wq"].shape == (2, 64, 4 * 16)


def test_greedy_continuation_matches_hf(hf_and_ours):
    """End-to-end: greedy decode agrees with HF's generate()."""
    model, cfg, params = hf_and_ours
    prompt = np.array([[5, 9, 23, 77]], np.int64)
    with torch.no_grad():
        hf_out = model.generate(
            torch.from_numpy(prompt), max_new_tokens=6, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, prompt.shape[1]:]

    tokens = jnp.asarray(prompt, jnp.int32)
    positions = jnp.arange(prompt.shape[1])[None]
    logits, k, v = transformer.prefill(cfg, params, tokens, positions)
    cache = transformer.init_decode_cache(cfg, 1, 32, dtype=jnp.float32)
    cache = transformer.insert_prefill(cache, k, v, 0, prompt.shape[1])
    out = [int(jnp.argmax(logits[0, prompt.shape[1] - 1, : model.config.vocab_size]))]
    pos = prompt.shape[1]
    for _ in range(5):
        lg, cache = transformer.decode_step(
            cfg, params, cache,
            jnp.asarray([out[-1]], jnp.int32), jnp.asarray([pos], jnp.int32),
        )
        out.append(int(jnp.argmax(lg[0, : model.config.vocab_size])))
        pos += 1
    assert out == hf_out.tolist()


class TestGemmaParity:
    @pytest.fixture(scope="class")
    def gemma_and_ours(self):
        cfg = transformers.GemmaConfig(
            vocab_size=160, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=1,
            intermediate_size=128, head_dim=16, max_position_embeddings=256,
            rms_norm_eps=1e-6, rope_theta=10_000.0,
        )
        torch.manual_seed(1)
        model = transformers.GemmaForCausalLM(cfg)
        model.eval()
        our_cfg, params = from_hf_llama(model, dtype=jnp.float32)
        return model, our_cfg, params

    def test_flags_mapped(self, gemma_and_ours):
        _, cfg, _ = gemma_and_ours
        assert cfg.embedding_scale and cfg.norm_plus_one and cfg.gelu_mlp
        assert cfg.tie_embeddings
        assert cfg.n_kv_heads == 1  # MQA

    def test_logits_match_hf(self, gemma_and_ours):
        model, cfg, params = gemma_and_ours
        ids = np.array([[2, 45, 101, 7, 88, 131]], np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(ids)).logits.numpy()
        tokens = jnp.asarray(ids, jnp.int32)
        positions = jnp.arange(ids.shape[1])[None]
        ours, *_ = transformer.prefill(cfg, params, tokens, positions)
        ours = np.asarray(ours)[:, :, : model.config.vocab_size]
        np.testing.assert_allclose(hf_logits, ours, rtol=3e-4, atol=3e-4)


def test_unsupported_model_type_rejected():
    cfg = transformers.MistralConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
    )
    from llm_instance_gateway_tpu.models.convert import config_from_hf
    with pytest.raises(NotImplementedError, match="model_type"):
        config_from_hf(cfg)


class TestMixtralParity:
    @pytest.fixture(scope="class")
    def mixtral_and_ours(self):
        cfg = transformers.MixtralConfig(
            vocab_size=144, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=128, rms_norm_eps=1e-5,
            rope_theta=10_000.0, tie_word_embeddings=False,
        )
        torch.manual_seed(4)
        model = transformers.MixtralForCausalLM(cfg)
        model.eval()
        our_cfg, params = from_hf_llama(model, dtype=jnp.float32)
        return model, our_cfg, params

    def test_moe_config_mapped(self, mixtral_and_ours):
        _, cfg, params = mixtral_and_ours
        assert cfg.n_experts == 4 and cfg.n_experts_per_token == 2
        assert params["layers"]["w_gate"].shape == (2, 4, 64, 96)
        assert params["layers"]["router"].shape == (2, 64, 4)

    def test_logits_match_hf(self, mixtral_and_ours):
        model, cfg, params = mixtral_and_ours
        ids = np.array([[3, 17, 54, 9, 88, 120, 7, 42]], np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(ids)).logits.numpy()
        tokens = jnp.asarray(ids, jnp.int32)
        positions = jnp.arange(ids.shape[1])[None]
        ours, *_ = transformer.prefill(cfg, params, tokens, positions)
        ours = np.asarray(ours)[:, :, : model.config.vocab_size]
        np.testing.assert_allclose(hf_logits, ours, rtol=3e-4, atol=3e-4)


class TestOlmoeParity:
    """OLMoE's checkpoint names (``self_attn.q_norm`` / ``k_norm``,
    ``mlp.gate``, ``mlp.experts.N.{gate,up,down}_proj``) onto the stacked
    leaves, and the two things its config has no key or an unusual value
    for — the unconditional QK-norm over the whole projected vector and
    gates that are NOT renormalised — held against ``OlmoeForCausalLM``:
    by the serving path and by the plain reference."""

    @pytest.fixture(scope="class")
    def olmoe_and_ours(self):
        cfg = transformers.OlmoeConfig(
            vocab_size=144, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=32, num_experts=8, num_experts_per_tok=3,
            norm_topk_prob=False, max_position_embeddings=128,
            rms_norm_eps=1e-5, rope_theta=10_000.0,
            tie_word_embeddings=False,
        )
        torch.manual_seed(6)
        model = transformers.OlmoeForCausalLM(cfg)
        with torch.no_grad():  # off their init of 1: a silent no-op else
            for layer in model.model.layers:
                for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm):
                    norm.weight.add_(0.3 * torch.randn_like(norm.weight))
        model.eval()
        our_cfg, params = from_hf_llama(model, dtype=jnp.float32)
        return model, our_cfg, params

    def test_config_and_names_mapped(self, olmoe_and_ours):
        _, cfg, params = olmoe_and_ours
        assert (cfg.n_experts, cfg.n_experts_per_token) == (8, 3)
        assert cfg.qk_norm and not cfg.norm_topk_prob
        layers = params["layers"]
        assert layers["q_norm"].shape == layers["k_norm"].shape == (2, 64)
        assert layers["router"].shape == (2, 64, 8)
        assert layers["w_gate"].shape == layers["w_up"].shape == (2, 8, 64, 32)
        assert layers["w_down"].shape == (2, 8, 32, 64)

    @pytest.mark.parametrize("which", ["serving", "reference"])
    def test_logits_match_hf(self, olmoe_and_ours, which):
        from llm_instance_gateway_tpu.models import reference

        model, cfg, params = olmoe_and_ours
        ids = np.array([[3, 17, 54, 9, 88, 120, 7, 42, 99, 5]], np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(ids)).logits.numpy()
        if which == "serving":
            ours, *_ = transformer.prefill(
                cfg, params, jnp.asarray(ids, jnp.int32),
                jnp.arange(ids.shape[1])[None])
        else:
            ours = reference.forward(cfg, params, jnp.asarray(ids[0]))[None]
        ours = np.asarray(ours)[:, :, : model.config.vocab_size]
        np.testing.assert_allclose(hf_logits, ours, rtol=3e-4, atol=3e-4)


def test_llama3_rope_scaling_mapped():
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8192},
    )
    from llm_instance_gateway_tpu.models.convert import config_from_hf
    ours = config_from_hf(cfg)
    assert ours.rope_scaling == (8.0, 1.0, 4.0, 8192)


def test_unknown_rope_scaling_type_rejected():
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
        rope_scaling={"rope_type": "yarn", "factor": 4.0},
    )
    from llm_instance_gateway_tpu.models.convert import config_from_hf
    with pytest.raises(NotImplementedError, match="rope_scaling type"):
        config_from_hf(cfg)


class TestRopeScaling:
    def test_llama3_scaling_matches_hf(self):
        """Our llama3 rope remapping must reproduce transformers' logits."""
        from llm_instance_gateway_tpu.models.convert import (
            config_from_hf, params_from_hf_state_dict,
        )

        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, max_position_embeddings=64,
            rms_norm_eps=1e-5, rope_theta=10_000.0, tie_word_embeddings=False,
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                          "original_max_position_embeddings": 32},
        )
        torch.manual_seed(2)
        model = transformers.LlamaForCausalLM(hf_cfg)
        model.eval()
        cfg = config_from_hf(hf_cfg)  # scaling mapped by the converter
        assert cfg.rope_scaling == (8.0, 1.0, 4.0, 32)
        state = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
        params = params_from_hf_state_dict(cfg, state, dtype=jnp.float32)
        ids = np.array([[3, 17, 54, 9, 88, 120, 7, 42, 11, 99]], np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(ids)).logits.numpy()
        ours, *_ = transformer.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32),
            jnp.arange(ids.shape[1])[None],
        )
        ours = np.asarray(ours)[:, :, :128]
        np.testing.assert_allclose(hf_logits, ours, rtol=3e-4, atol=3e-4)


def test_sliding_window_rejected():
    cfg = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
        num_local_experts=2, num_experts_per_tok=1,
        sliding_window=1024, max_position_embeddings=32768,
    )
    from llm_instance_gateway_tpu.models.convert import config_from_hf
    with pytest.raises(NotImplementedError, match="sliding_window"):
        config_from_hf(cfg)


def test_preset_alias_still_served_with_checkpoint_name(tmp_path):
    """Both the checkpoint's own name and the CLI preset alias resolve."""
    from llm_instance_gateway_tpu.server.api_http import ModelServer
    server = ModelServer.__new__(ModelServer)
    server.model_name = "hf-llama"
    server.aliases = {"hf-llama", "llama3-tiny"}
    server.lora = None
    assert server._resolve_model("hf-llama") is None
    assert server._resolve_model("llama3-tiny") is None
    with pytest.raises(Exception):
        server._resolve_model("ghost")


def test_adapter_name_colliding_with_alias_rejected():
    """An adapter named like a base-model alias must 409, not shadow."""
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    import jax
    from llm_instance_gateway_tpu.models import transformer as tf
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server.api_http import ModelServer
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig
    from llm_instance_gateway_tpu.server.lora_manager import LoRAManager
    from llm_instance_gateway_tpu.server.tokenizer import ByteTokenizer

    params = tf.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    lora = LoRAManager(TINY_TEST, dtype=jnp.float32)
    engine = Engine(TINY_TEST, params,
                    EngineConfig(decode_slots=1, max_seq_len=32,
                                 prefill_buckets=(8,)),
                    lora_manager=lora, dtype=jnp.float32)
    server = ModelServer(engine, ByteTokenizer(), "hf-llama", lora,
                         aliases={"llama3-tiny"})

    async def run():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.post("/v1/load_lora_adapter", json={
                "lora_name": "llama3-tiny", "lora_path": "/nope"})
            assert resp.status == 409
        finally:
            await client.close()

    asyncio.run(run())


def test_llama2_mha_logits_match_hf():
    """Llama-2 shape (MHA: kv_heads == heads, theta 1e4) — the reference
    PoC's model family (vllm-lora-deployment.yaml:33-39) certified like the
    GQA case."""
    model = build_hf_llama(heads=4, kv_heads=4)
    cfg, params = from_hf_llama(model, dtype=jnp.float32)
    assert cfg.n_kv_heads == cfg.n_heads == 4
    ids = np.array([[5, 9, 101, 33, 64, 2, 77, 18]], np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()
    tokens = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[1])[None]
    ours, *_ = transformer.prefill(cfg, params, tokens, positions)
    ours = np.asarray(ours)[:, :, : model.config.vocab_size]
    np.testing.assert_allclose(hf_logits, ours, rtol=2e-4, atol=2e-4)


class TestQwen2Parity:
    """Qwen2-family: the one architectural delta is learned Q/K/V biases
    (attention_bias) — numerics certified against Qwen2ForCausalLM."""

    @pytest.fixture(scope="class")
    def qwen_and_ours(self):
        cfg = transformers.Qwen2Config(
            vocab_size=144, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, max_position_embeddings=128,
            rms_norm_eps=1e-6, rope_theta=1_000_000.0,
            tie_word_embeddings=False,
        )
        torch.manual_seed(6)
        model = transformers.Qwen2ForCausalLM(cfg)
        # transformers zero-inits Linear biases: randomize q/k/v biases so
        # the parity tests actually EXERCISE the bias path (zero biases
        # would pass even if _attn_proj dropped or sign-flipped them).
        with torch.no_grad():
            for layer in model.model.layers:
                for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                             layer.self_attn.v_proj):
                    torch.nn.init.normal_(proj.bias, std=0.5)
        model.eval()
        our_cfg, params = from_hf_llama(model, dtype=jnp.float32)
        return model, our_cfg, params

    def test_bias_config_and_shapes(self, qwen_and_ours):
        model, cfg, params = qwen_and_ours
        assert cfg.attention_bias is True
        assert params["layers"]["wq_b"].shape == (2, 4 * 16)
        assert params["layers"]["wk_b"].shape == (2, 2 * 16)
        # The randomized biases actually came through the conversion.
        assert float(np.abs(np.asarray(params["layers"]["wq_b"])).max()) > 0.01

    def test_logits_match_hf(self, qwen_and_ours):
        model, cfg, params = qwen_and_ours
        ids = np.array([[3, 17, 54, 9, 88, 120, 7, 42]], np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(ids)).logits.numpy()
        tokens = jnp.asarray(ids, jnp.int32)
        positions = jnp.arange(ids.shape[1])[None]
        ours, *_ = transformer.prefill(cfg, params, tokens, positions)
        ours = np.asarray(ours)[:, :, : model.config.vocab_size]
        np.testing.assert_allclose(hf_logits, ours, rtol=3e-4, atol=3e-4)

    def test_greedy_continuation_matches_hf(self, qwen_and_ours):
        model, cfg, params = qwen_and_ours
        ids = [5, 9, 31]
        with torch.no_grad():
            hf_out = model.generate(
                torch.tensor([ids]), max_new_tokens=6, do_sample=False,
            )[0, len(ids):].tolist()
        cache = transformer.init_decode_cache(cfg, 1, 32, dtype=jnp.float32)
        tokens = jnp.asarray([ids], jnp.int32)
        positions = jnp.arange(len(ids))[None]
        logits, k, v = transformer.prefill(cfg, params, tokens, positions)
        cache = transformer.insert_prefill(cache, k, v, 0, len(ids))
        cur = int(np.argmax(np.asarray(
            logits[0, len(ids) - 1, : model.config.vocab_size])))
        ours = [cur]
        pos = len(ids)
        for _ in range(5):
            lg, cache = transformer.decode_step(
                cfg, params, cache, jnp.asarray([cur]), jnp.asarray([pos]))
            cur = int(np.argmax(np.asarray(
                lg[0, : model.config.vocab_size])))
            ours.append(cur)
            pos += 1
        assert ours == hf_out


def test_qwen2_default_config_converts_despite_inactive_sliding_window():
    """Qwen2Config ships sliding_window=4096 < max_position_embeddings but
    use_sliding_window=False (full causal attention): must convert."""
    from llm_instance_gateway_tpu.models.convert import config_from_hf

    cfg = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
        max_position_embeddings=32_768, sliding_window=4096,
        use_sliding_window=False,
    )
    ours = config_from_hf(cfg)
    assert ours.attention_bias is True


def test_llama_attention_bias_rejected():
    """HF llama attention_bias adds an o_proj bias our layout lacks:
    loud rejection, not silently-dropped bias math."""
    from llm_instance_gateway_tpu.models.convert import config_from_hf

    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=64,
        attention_bias=True,
    )
    with pytest.raises(NotImplementedError, match="attention_bias"):
        config_from_hf(cfg)
