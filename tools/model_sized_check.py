"""Model-sized virtual-mesh serving check (VERDICT r4 #9).

Every other multi-device certification in this repo runs on toy shapes
(d_model 64-128) — right for correctness, silent on the question "does the
sharding/memory plumbing hold up at model scale?".  This check serves a
~1.14B-parameter config with the REAL Llama-3 head layout (GQA, 8 KV
heads, head_dim 128 — the layout `models/configs.LLAMA3_8B` declares,
scaled to 1B the way the Llama-3.2-1B family is) over a tensor=8 virtual
CPU mesh: params shard Megatron-style, the decode cache shards its KV
heads, and a few greedy tokens decode end to end through the full engine
(bucketed prefill -> insert -> fused decode).  `--int8` additionally runs
the quantized cache + quant-aware shard_map wrapper at the same scale.

This exercises, at real-model tensor sizes, exactly what first contact
with a v5e-8 would: GSPMD spec/shape agreement on multi-GB params, scale
pools, LoRA-free fast paths, and the engine's committed-input sharding.
It does NOT measure speed (1-host CPU emulates 8 devices) and is gated
behind an env var because init+compile+prefill of a 1B model on one CPU
core takes minutes:

    LIG_MODEL_SIZED=1 python tools/model_sized_check.py [--int8]

or via the (slow, opt-in) test: LIG_MODEL_SIZED=1 pytest
tests/test_parallel.py -k model_sized.  A recorded run lives in
ARCHITECTURE.md §4.

Reference note: the reference gateway never touches model tensors (it
delegates serving to vLLM, SURVEY §2); this check belongs to the
model-server half this repo owns (SURVEY §2.5 "slice-backed replica").
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8


def _ensure_cpu_mesh() -> None:
    """Pin the CPU platform with 8 virtual devices.  Must be this process's
    first use of JAX (the count is an XLA flag read at backend start-up);
    no child process is involved."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={N_DEVICES}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.device_count() < N_DEVICES:
        raise SystemExit(
            f"model_sized_check needs {N_DEVICES} virtual CPU devices and "
            f"found {jax.device_count()}: run it in a fresh process (an "
            "XLA_FLAGS device count set too low wins over this one)")


def model_sized_config():
    """~1.14B params, Llama-3.2-1B-like: GQA 16q/8kv heads x 128."""
    from llm_instance_gateway_tpu.models.configs import LLAMA3_8B

    return dataclasses.replace(
        LLAMA3_8B,
        name="llama3-1b-meshcheck",
        vocab_size=32768,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        max_seq_len=512,
    )


def run(int8: bool = False, max_new: int = 4) -> dict:
    """Serve a few greedy tokens at 1B scale on a tensor=8 virtual mesh.
    Returns a result dict (also printed as the one-line summary)."""
    import jax
    import jax.numpy as jnp

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.parallel.mesh import MeshConfig, make_mesh
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )

    cfg = model_sized_config()
    t0 = time.monotonic()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.bfloat16)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    t_init = time.monotonic() - t0

    devices = jax.devices("cpu")[:N_DEVICES]
    mesh = make_mesh(MeshConfig(tensor=N_DEVICES), devices=devices)
    engine = Engine(
        cfg, params,
        EngineConfig(decode_slots=4, max_seq_len=256, prefill_buckets=(64,),
                     kv_cache_quant="int8" if int8 else None),
        eos_id=None, dtype=jnp.bfloat16, mesh=mesh,
    )
    quant_aware = bool(getattr(engine._decode_attn_fn, "quant_aware", False))
    t1 = time.monotonic()
    engine.start()
    try:
        reqs = [Request(prompt_tokens=[5 + i, 6, 7], max_new_tokens=max_new,
                        sampling=SamplingParams(temperature=0.0))
                for i in range(2)]
        for r in reqs:
            engine.submit(r)
        for r in reqs:
            if not r.done.wait(3000):
                raise RuntimeError("model-sized decode timed out")
            if r.error:
                raise RuntimeError(f"model-sized decode failed: {r.error}")
        served = [len(r.output_tokens) for r in reqs]
    finally:
        engine.stop()
    t_serve = time.monotonic() - t1

    result = {
        "params": n_params,
        "mesh": dict(mesh.shape),
        "int8": int8,
        "quant_kernel_wrapper": quant_aware,
        "served_tokens": served,
        "init_s": round(t_init, 1),
        "serve_s": round(t_serve, 1),
    }
    print(f"model_sized_check OK: params={n_params/1e9:.2f}B "
          f"mesh={dict(mesh.shape)} int8={int8} "
          f"quant_kernel_wrapper={quant_aware} served={served} "
          f"init={t_init:.0f}s serve(compile+decode)={t_serve:.0f}s")
    return result


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--int8", action="store_true",
                        help="quantized KV cache + quant-aware wrapper")
    parser.add_argument("--max-new", type=int, default=4)
    args = parser.parse_args(argv)
    if not os.environ.get("LIG_MODEL_SIZED"):
        print("set LIG_MODEL_SIZED=1 to run (minutes of CPU compile)")
        raise SystemExit(2)
    _ensure_cpu_mesh()
    run(int8=args.int8, max_new=args.max_new)


if __name__ == "__main__":
    main()
