"""PartitionSpecs for model params, KV caches, LoRA buffers, and activations.

The GSPMD recipe (scaling-book style): annotate shardings on the jit
boundary, let XLA insert the collectives.  Megatron-style tensor parallelism
for the decoder: column-shard the up-projections (heads / ffn columns),
row-shard the down-projections, so each layer needs exactly one
reduce(-scatter) on the attention output and one on the MLP output — both
riding ICI.

Weights additionally shard over ``fsdp`` on their non-tensor dim (zero-cost
when fsdp=1).  KV caches shard heads over ``tensor`` and batch over ``data``.
LoRA buffers shard ``b`` (rank -> d_out) over ``tensor`` on d_out and keep
``a`` replicated (rank dims are tiny); the delta then composes with the
column-sharded base projection without extra collectives.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models.configs import ModelConfig


def param_specs(cfg: ModelConfig) -> dict[str, Any]:
    """PartitionSpec pytree matching ``transformer.init_params`` layout."""
    layers: dict[str, Any] = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        # [L, D, H*hd]: column-shard heads over tensor, D over fsdp.
        "wq": P(None, "fsdp", "tensor"),
        "wk": P(None, "fsdp", "tensor"),
        "wv": P(None, "fsdp", "tensor"),
        # [L, H*hd, D]: row-shard (same tensor axis contracts away).
        "wo": P(None, "tensor", "fsdp"),
    }
    if cfg.attention_bias:
        # [L, H*hd] biases shard with their projection's output columns.
        layers["wq_b"] = P(None, "tensor")
        layers["wk_b"] = P(None, "tensor")
        layers["wv_b"] = P(None, "tensor")
    if cfg.qk_norm:
        # [L, H*hd] norm weights: replicated (the norm reduces over the
        # whole projected vector, whichever way its columns are sharded).
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    if cfg.n_experts:
        layers.update(
            {
                "router": P(None, None, None),
                # [L, E, D, F]: experts over expert axis, ffn over tensor.
                "w_gate": P(None, "expert", "fsdp", "tensor"),
                "w_up": P(None, "expert", "fsdp", "tensor"),
                "w_down": P(None, "expert", "tensor", "fsdp"),
            }
        )
    else:
        layers.update(
            {
                "w_gate": P(None, "fsdp", "tensor"),
                "w_up": P(None, "fsdp", "tensor"),
                "w_down": P(None, "tensor", "fsdp"),
            }
        )
    specs: dict[str, Any] = {
        # [V, D]: vocab over tensor (embedding lookups all-gather a slice;
        # the final projection contracts D and psums over tensor).
        "embed": P("tensor", None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tensor")
    return specs


def cache_specs(cfg: ModelConfig | None = None, mesh: Mesh | None = None,
                quantized: bool = False) -> dict[str, Any]:
    """Decode cache [L, B, S, K, hd]: batch over data, KV heads over tensor.

    MQA/GQA caches whose kv-head count doesn't divide the tensor axis (e.g.
    Gemma-2B's single KV head on a tensor=4 mesh) replicate the head dim —
    the attention einsums then read the replicated cache and XLA partitions
    on the query heads instead.  ``quantized`` adds the int8 cache's
    per-(position, kv-head) scale arrays, sharded like K/V minus head_dim.
    """
    head_axis: str | None = "tensor"
    if cfg is not None and mesh is not None:
        if cfg.n_kv_heads % mesh.shape["tensor"] != 0:
            head_axis = None
    kv = P(None, "data", None, head_axis, None)
    specs = {"k": kv, "v": kv, "length": P("data")}
    if quantized:
        specs["k_scale"] = specs["v_scale"] = P(None, "data", None, head_axis)
    return specs


def paged_cache_specs(cfg: ModelConfig | None = None,
                      mesh: Mesh | None = None,
                      quantized: bool = False) -> dict[str, Any]:
    """Paged pool [L, n_blocks, block, Kh, hd]: KV heads over tensor (the
    Megatron split — attention reads stay shard-local, the psum lives in
    wo), everything else replicated.  The block-pool dim belongs to no mesh
    axis: rows of one pool serve whichever requests the host allocator
    assigns, so the batch/data axis must be 1 (tensor-parallel paged
    serving — the big-model case; data-parallel replicas are separate
    engine processes, which is how the gateway scales them anyway).
    ``quantized`` adds the int8 pool's scale arrays, sharded like K/V
    minus head_dim."""
    head_axis: str | None = "tensor"
    if cfg is not None and mesh is not None:
        if cfg.n_kv_heads % mesh.shape["tensor"] != 0:
            head_axis = None
    kv = P(None, None, None, head_axis, None)
    specs = {"k": kv, "v": kv, "tables": P(), "length": P()}
    if quantized:
        specs["k_scale"] = specs["v_scale"] = P(None, None, None, head_axis)
    return specs


def lora_specs(cfg: ModelConfig) -> dict[str, Any]:
    specs: dict[str, Any] = {"scale": P(None)}
    for t in lora_lib.TARGETS:
        # a: [L, S, d_in, r] replicated (tiny); b: [L, S, r, d_out] column-
        # sharded to match the base projection's output sharding.
        specs[f"{t}_a"] = P(None, None, None, None)
        specs[f"{t}_b"] = P(None, None, None, "tensor")
    # Row-sharded targets contract d_out == D over fsdp instead.
    specs["o_b"] = P(None, None, None, "fsdp")
    specs["down_b"] = P(None, None, None, "fsdp")
    return specs


def activation_specs() -> dict[str, Any]:
    return {
        "tokens_2d": P("data", "sequence"),   # [B, S]
        "tokens_1d": P("data"),               # [B]
        "logits_prefill": P("data", "sequence", "tensor"),
        "logits_decode": P("data", "tensor"),
    }


def _leaf_shardings(mesh: Mesh, spec: P, quantized: bool):
    """NamedSharding(s) for one param leaf.  A weight-only-int8 leaf
    (``ops.quant`` ``{"q", "s"}``) carries ONE spec for the original dense
    array: ``q`` takes it verbatim and the per-output-channel scale takes
    the spec minus its contracted (second-to-last) axis."""
    if not quantized:
        return NamedSharding(mesh, spec)
    axes = tuple(spec)
    scale_spec = P(*(axes[:-2] + axes[-1:])) if len(axes) >= 2 else spec
    return {"q": NamedSharding(mesh, spec),
            "s": NamedSharding(mesh, scale_spec)}


def param_shardings(cfg: ModelConfig, mesh: Mesh,
                    quantized: bool = False) -> dict[str, Any]:
    """``param_specs`` as a NamedSharding pytree — what
    ``transformer.init_params`` / ``quant.quantize_params`` take so every
    leaf is BORN sharded.  ``quantized`` gives the ``ops.quant`` targets
    (and ``lm_head``) their ``{"q", "s"}`` pair."""
    from llm_instance_gateway_tpu.ops.quant import QUANT_TARGETS

    specs = param_specs(cfg)
    out: dict[str, Any] = {
        k: _leaf_shardings(mesh, v, quantized and k == "lm_head")
        for k, v in specs.items() if k != "layers"}
    out["layers"] = {
        k: _leaf_shardings(mesh, v, quantized and k in QUANT_TARGETS)
        for k, v in specs["layers"].items()}
    return out


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """device_put a pytree with NamedShardings from a matching spec pytree
    (a no-op for leaves already placed that way; host numpy leaves go
    straight to their shards).  Weight-only-int8 leaves split their one
    spec as ``_leaf_shardings`` says — so ``--quantize int8`` composes with
    serve meshes for dense AND expert-stack weights."""
    from llm_instance_gateway_tpu.ops.quant import is_quantized

    def place(x, s):
        return jax.device_put(x, _leaf_shardings(mesh, s, is_quantized(x)))

    return jax.tree.map(place, tree, specs, is_leaf=is_quantized)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
