"""Every model family serves through the engine (not just the Llama tiny).

Completeness check for BASELINE.json's pool configs: Gemma (tied embeddings,
MQA) and Mixtral (MoE) must run the full prefill->insert->decode lifecycle,
including multiplexed LoRA on the dense families.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import (
    GEMMA_2B,
    LLAMA2_7B,
    MIXTRAL_8X7B,
    OLMOE_1B_7B,
    QWEN2_5_7B,
)
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

FAMILIES = {
    "llama2-tiny": LLAMA2_7B.tiny(),  # the reference PoC's model family
    "gemma-tiny": GEMMA_2B.tiny(),
    "mixtral-tiny": MIXTRAL_8X7B.tiny(),
    "qwen-tiny": QWEN2_5_7B.tiny(),   # attention_bias (Q/K/V biases)
    "olmoe-tiny": OLMOE_1B_7B.tiny(),  # 64 experts top-8, QK-norm
}


@pytest.mark.parametrize("name", list(FAMILIES), ids=list(FAMILIES))
def test_family_serves_end_to_end(name):
    cfg = FAMILIES[name]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = Engine(
        cfg, params,
        EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
                     decode_steps_per_sync=2),
        eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        req = engine.generate(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=6), timeout_s=120
        )
    finally:
        engine.stop()
    assert req.error is None
    assert len(req.output_tokens) == 6
    assert req.finish_reason == "length"


@pytest.mark.parametrize("name", ["olmoe-tiny", "mixtral-tiny", "qwen-tiny"])
def test_routing_counters_come_back_with_the_readback(name):
    """A sparse model's layer-steps are counted in its decode, bucket
    prefill and chunk programs and booked at the decode readback; a dense
    model's programs count nothing and its counters read 0."""
    from llm_instance_gateway_tpu.server import metrics, profiler

    assert profiler.MOE_COUNTERS == transformer.MOE_TALLY
    cfg = FAMILIES[name]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = Engine(
        cfg, params,
        EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(8,)),
        eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        short = engine.generate(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=4), timeout_s=120)
        # longer than the largest bucket: the chunk program
        long = engine.generate(
            Request(prompt_tokens=list(range(3, 23)), max_new_tokens=4),
            timeout_s=120)
    finally:
        engine.stop()
    assert short.error is None and long.error is None
    moe = engine.profiler.moe_state()
    text = metrics.render(engine.metrics_snapshot())
    for counter, value in moe.items():
        assert f"tpu:moe_{counter}_total {value}" in text
    if not cfg.n_experts:
        assert set(moe.values()) == {0}
        return
    k, layers = cfg.n_experts_per_token, cfg.n_layers
    assert moe["layer_steps"] % layers == 0
    # 23 prompt tokens in all (padding routes nowhere) and at least the 6
    # decoded tokens whose steps were read back; one live row a step.
    steps = moe["layer_steps"] // layers
    assert moe["assignments"] >= (23 + 6) * k * layers
    assert moe["assignments"] <= (23 + steps) * k * layers
    assert k * moe["layer_steps"] <= moe["experts_touched"] <= min(
        cfg.n_experts * moe["layer_steps"], moe["assignments"])
    # every touched expert's group holds a tile or more, an assignment at most
    # one of its own
    assert moe["experts_touched"] <= moe["tiles_used"] <= moe["assignments"]


def test_gemma_with_lora_multiplexing():
    cfg = FAMILIES["gemma-tiny"]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    lora = LoRAManager(cfg, dtype=jnp.float32)
    from llm_instance_gateway_tpu.models.lora import target_dims

    dims = target_dims(cfg)
    rng = np.random.RandomState(0)
    lora.load("gemma-adapter", weights={
        t: {"a": rng.randn(cfg.n_layers, dims[t][0], 2) * 0.3,
            "b": rng.randn(cfg.n_layers, 2, dims[t][1]) * 0.3}
        for t in ("q", "v")
    }, alpha=8.0, rank=2)
    engine = Engine(
        cfg, params,
        EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(8, 16)),
        lora_manager=lora, eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        base = engine.generate(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=5), timeout_s=120
        )
        adapted = engine.generate(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=5,
                    adapter="gemma-adapter"), timeout_s=120
        )
    finally:
        engine.stop()
    assert base.error is None and adapted.error is None
    assert base.output_tokens != adapted.output_tokens
