"""Sweep decode (slots x K) on the chip; print tok/s per config.

Device-side stop removed the finish-lag waste that previously penalized
large K (a finished row freezes on-device instead of decoding garbage until
the next sync), so the K the bench uses deserves a sweep on the real host
(ROADMAP Speed 6; on this machine: not measured yet).

Method: the bench model + workload (bench.py) at each (decode_slots,
decode_steps_per_sync) over SHARED quantized params — engine construction
compiles per config, the measured phase excludes compile (warm-up first).
The grid runs in round-robin PASSES and each config reports its best pass,
so drift over the run is decorrelated from the config order.

Needs a TPU; fails without one.
Run:  python tools/decode_sweep.py [--passes 2] [--slots 16 32] [--k 8 16 32 64]
Emits one JSON line per config plus a "best" line at the end.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import bench


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--slots", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--k", type=int, nargs="+", default=[8, 16, 32, 64])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--prompt-len", type=int, default=100)
    args = ap.parse_args()

    from llm_instance_gateway_tpu import runtime
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

    device = runtime.require_accelerator("tools/decode_sweep.py")
    runtime.configure_compile_cache()
    print(json.dumps({"device": dataclasses.asdict(device)}), flush=True)
    cfg = bench.bench_model_cfg()
    dtype = jnp.bfloat16
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype,
                                     quantize=True)

    grid = [(s, k) for s in args.slots for k in args.k]
    results: dict[tuple[int, int], list[float]] = {g: [] for g in grid}
    engines: dict[tuple[int, int], Engine] = {}
    try:
        for slots, k in grid:
            engine = Engine(
                cfg, params,
                EngineConfig(
                    decode_slots=slots, max_seq_len=cfg.max_seq_len,
                    prefill_buckets=(128, 256),
                    decode_steps_per_sync=k, pipeline_decode=True,
                ),
                lora_manager=None, eos_id=None, dtype=dtype,
            )
            engine.start()
            engines[(slots, k)] = engine
            # Warm-up: compile prefill buckets + decode program.
            bench.run_phase(engine, 2, args.prompt_len, 4, adapters=[])

        for p in range(args.passes):
            for slots, k in grid:
                r = bench.run_phase(
                    engines[(slots, k)], args.requests, args.prompt_len,
                    args.max_new, adapters=[])
                results[(slots, k)].append(r["tok_per_s"])
                print(json.dumps({
                    "slots": slots, "k": k, "pass": p,
                    "tok_per_s": round(r["tok_per_s"], 1),
                    "ttft_p50_ms": round(r["ttft_p50_ms"], 1),
                }), flush=True)
    finally:
        for engine in engines.values():
            engine.stop()

    summary = sorted(
        ((max(v), s, k) for (s, k), v in results.items() if v), reverse=True)
    for tok_s, s, k in summary:
        print(json.dumps({"slots": s, "k": k, "best_tok_per_s": round(tok_s, 1)}),
              flush=True)
    best = summary[0]
    print(json.dumps({"best": {"slots": best[1], "k": best[2],
                               "tok_per_s": round(best[0], 1)}}), flush=True)


if __name__ == "__main__":
    main()
