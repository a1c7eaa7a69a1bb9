"""Engine chaos test: a randomized storm must terminate cleanly.

Mixed prompt lengths (bucketed + chunked), adapters + base, random
cancellations mid-flight — every request must reach a
terminal state (done set, a finish_reason, no engine-thread death), bounded
outputs, and the engine must still serve a clean request afterwards.
"""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

CFG = TINY_TEST


@pytest.mark.parametrize("prefill_batch,spec_k,paged,quant,prefix", [
    (1, 0, False, False, False), (3, 0, False, False, False),
    (1, 2, False, False, False), (3, 0, True, False, False),
    # Round-5 production shape: paged + int8 KV + prefix cache
    # (grouped stays off with prefix, per the engine's own reuse gate).
    (1, 0, True, True, True),
    (1, 0, True, False, False), (1, 2, True, False, False),
    (1, 0, False, True, False),
], ids=["plain", "grouped", "spec", "grouped-paged", "paged-int8-prefix",
        "paged", "spec-paged", "int8"])
def test_request_storm_terminates(prefill_batch, spec_k, paged, quant,
                                  prefix):
    import dataclasses

    rng = random.Random(0)
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    draft_kw = {}
    if spec_k:
        dcfg = dataclasses.replace(
            CFG, name="chaos-draft", d_model=32, n_layers=1, n_heads=2,
            n_kv_heads=1, d_ff=64, head_dim=16)
        draft_kw = dict(
            draft_cfg=dcfg,
            draft_params=transformer.init_params(
                dcfg, jax.random.PRNGKey(5), dtype=jnp.float32))
    lora = LoRAManager(CFG, dtype=jnp.float32)
    dims = target_dims(CFG)
    np_rng = np.random.RandomState(0)
    for i in range(2):
        lora.load(f"chaos-{i}", weights={
            t: {"a": np_rng.randn(CFG.n_layers, dims[t][0], 2) * 0.2,
                "b": np_rng.randn(CFG.n_layers, 2, dims[t][1]) * 0.2}
            for t in ("q", "v")
        }, alpha=4.0, rank=2)
    engine = Engine(
        CFG, params,
        EngineConfig(decode_slots=3, max_seq_len=96, prefill_buckets=(8, 16),
                     decode_steps_per_sync=3,
                     prefill_batch=prefill_batch, speculative_k=spec_k,
                     paged_kv_block=8 if paged else None,
                     # Undersized pool: the storm must survive grouped
                     # admission hitting exhaustion-parking backpressure.
                     paged_kv_blocks=24 if paged else None,
                     kv_cache_quant="int8" if quant else None,
                     prefix_cache=prefix),
        lora_manager=lora, eos_id=7, dtype=jnp.float32, **draft_kw,
    )
    engine.start()
    try:
        requests = []
        for i in range(24):
            n_prompt = rng.choice([3, 7, 14, 40])  # 40 -> chunked path
            req = Request(
                prompt_tokens=[rng.randrange(1, 250) for _ in range(n_prompt)],
                max_new_tokens=rng.choice([1, 4, 9, 30]),
                sampling=SamplingParams(
                    temperature=rng.choice([0.0, 0.8]),
                    top_k=rng.choice([0, 5]),
                ),
                adapter=rng.choice([None, "chaos-0", "chaos-1"]),
            )
            requests.append(req)
            engine.submit(req)
            if rng.random() < 0.25:  # random client disconnects
                threading.Timer(rng.random() * 0.5, req.cancelled.set).start()
            time.sleep(rng.random() * 0.05)

        deadline = time.monotonic() + 240
        for req in requests:
            remaining = max(1.0, deadline - time.monotonic())
            assert req.done.wait(remaining), f"request {req.request_id} hung"
        reasons = {r.finish_reason for r in requests}
        assert reasons <= {"stop", "length", "cancelled"}, reasons
        for r in requests:
            assert len(r.output_tokens) <= r.max_new_tokens
            if r.finish_reason == "stop":
                assert r.output_tokens[-1] == 7
        # Engine is still healthy: a clean follow-up completes correctly.
        follow = engine.generate(
            Request(prompt_tokens=[9, 9, 9], max_new_tokens=5), timeout_s=120
        )
        assert follow.error is None and len(follow.output_tokens) <= 5
        # done is set BEFORE the slot clears; poll briefly for the release.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            snap = engine.metrics_snapshot()
            if snap["num_requests_running"] == 0:
                break
            time.sleep(0.05)
        assert snap["prefill_queue_size"] == 0
        assert snap["num_requests_running"] == 0
    finally:
        engine.stop()
