"""On-chip A/B of the bandwidth composition: lane-bf16 vs paged-bf16 vs
paged-int8 (+prefix) serving throughput at long context.

Decode at long context is bound by streaming the KV cache from HBM; this
tool measures, on the chip, what the two bandwidth features buy on the same
~1.1B bench model `bench.py` uses:

- ``lane_bf16``      — the default contiguous-lane engine (baseline)
- ``paged_bf16``     — paged pool + direct paged kernel (no gathered copy)
- ``paged_int8``     — quantized pool + prefix cache (the production
                       long-context shape: paged + int8 + prefix)

One JSON line per engine config on stdout, then a summary naming the
device.  Reuses bench.py's model config and phase runner.  Needs a TPU;
fails without one.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (repo-root bench.py: shared machinery)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def run_variant(name: str, cfg, ecfg_kwargs: dict, prompt_len: int,
                max_new: int, n_requests: int) -> dict:
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.bfloat16)
    engine = Engine(cfg, params, EngineConfig(**ecfg_kwargs), eos_id=None,
                    dtype=jnp.bfloat16)
    engine.start()
    try:
        # Disjoint seeds: with the same stream, the prefix_cache variant
        # would serve measured prompts 0-1 straight from the warm phase's
        # cached blocks — a reuse win real traffic wouldn't grant — and the
        # A/B would conflate it with the bandwidth effect under test.
        warm = bench.run_phase(engine, n_requests=2, prompt_len=prompt_len,
                               max_new=8, adapters=[], seed=1)  # compile
        del warm
        stats = bench.run_phase(engine, n_requests=n_requests,
                                prompt_len=prompt_len, max_new=max_new,
                                adapters=[], seed=0)
    finally:
        engine.stop()
    row = {"variant": name, **{k: round(v, 2) for k, v in stats.items()}}
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    from llm_instance_gateway_tpu import runtime

    device = runtime.require_accelerator("tools/bench_paged_int8.py")
    runtime.configure_compile_cache()
    cfg = bench.bench_model_cfg()
    # Long-context shape: prompts near the cache limit so decode streams a
    # deep KV.
    prompt_len, max_new, n_requests = 384, 96, 16
    common = dict(decode_slots=16, max_seq_len=512,
                  prefill_buckets=(128, 256, 512),
                  decode_steps_per_sync=8, pipeline_decode=True)
    block = 64

    rows = [
        run_variant("lane_bf16", cfg, dict(common), prompt_len, max_new,
                    n_requests),
        run_variant("paged_bf16", cfg, dict(common, paged_kv_block=block),
                    prompt_len, max_new, n_requests),
        run_variant("paged_int8", cfg,
                    dict(common, paged_kv_block=block, kv_cache_quant="int8",
                         prefix_cache=True),
                    prompt_len, max_new, n_requests),
    ]
    base = rows[0]["tok_per_s"]
    print(json.dumps({
        "summary": "paged_int8_ab",
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": device.count},
        "model": cfg.name,
        "paged_vs_lane": round(rows[1]["tok_per_s"] / base, 3),
        "paged_int8_vs_lane": round(rows[2]["tok_per_s"] / base, 3),
    }), flush=True)


if __name__ == "__main__":
    main()
