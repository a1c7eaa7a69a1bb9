"""Writes the mix's LoRA adapters, seeded, in Orbax form.

    python -m benchmark.adapter_writer <config.json> <rehearse 0|1> <out dir> <count> <rank>

A short child of the run, kept OFF the chip by name (``JAX_PLATFORMS=cpu``:
it imports jax through ``lora_manager``).  The adapters are the same in every
run: their seed is their index.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np


def main() -> None:
    config_path, rehearse, out_dir, count, rank = sys.argv[1:6]
    from benchmark.server_wrapper import register
    from llm_instance_gateway_tpu.models import gemma, llama, mixtral, qwen
    from llm_instance_gateway_tpu.models.lora import target_dims
    from llm_instance_gateway_tpu.server.lora_manager import save_adapter

    with open(config_path) as f:
        served = register(json.load(f), rehearse == "1")
    cfg = {**llama.CONFIGS, **gemma.CONFIGS, **mixtral.CONFIGS,
           **qwen.CONFIGS}[served]
    rank = min(int(rank), cfg.max_lora_rank)
    dims = target_dims(cfg)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    for i in range(int(count)):
        rng = np.random.RandomState(7 + i)
        weights = {
            t: {"a": rng.randn(cfg.n_layers, dims[t][0], rank) * 0.05,
                "b": rng.randn(cfg.n_layers, rank, dims[t][1]) * 0.05}
            for t in ("q", "v")}
        save_adapter(os.path.join(out_dir, f"bench-adapter-{i}"), weights,
                     alpha=2.0 * rank, rank=rank)


if __name__ == "__main__":
    main()
