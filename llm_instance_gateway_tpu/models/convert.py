"""Convert HuggingFace checkpoints into this framework's param layout.

The serving path restores Orbax pytrees (``api_http --checkpoint``); real
deployments start from HF-format weights.  This module maps Llama-2/3
(incl. Llama-3.1 ``rope_scaling``), Gemma, and Mixtral state dicts onto
``transformer.init_params``'s stacked-layer layout — other model types are
rejected loudly until their mappings land — and numerics tests
(tests/test_convert.py) hold our decoder to the canonical implementations'
logits for every supported family.

Conventions verified by that test:
- RoPE: split-halves (rotate_half) convention, matching HF Llama.
- GQA: q [d, H*hd], k/v [d, K*hd] column layouts transpose from HF's
  [out, in] Linear weights.
- RMSNorm pre-norm placement, f32 accumulation.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.models.configs import (
    LLAMA3_8B,
    SMALLTHINKER_21B_A3B,
    ModelConfig,
)


def config_from_hf(hf_config) -> ModelConfig:
    """ModelConfig from a transformers Llama/Gemma/Mixtral/Qwen2/OLMoE
    config object.

    Mapped: Llama (incl. llama3-type rope_scaling), Gemma (embedding scale,
    (1+w) norm, tanh-GeLU), Mixtral (expert stacks + router), OLMoE (the
    same stacks under its own names, QK-norm, ``norm_topk_prob``).  Loud
    rejections instead of silent wrong math for everything else: unknown
    model types, non-llama3 rope_scaling types, and a sliding window on a
    family whose preset has none (its layers attend the full causal
    context).  SmallThinker (``smallthinker``), whose preset has a window,
    gets its per-layer layouts converted into the period of layer kinds the
    layer loop scans (``_smallthinker_config``).
    """
    model_type = getattr(hf_config, "model_type", "llama")
    if model_type == "smallthinker":
        return _smallthinker_config(hf_config)
    if model_type not in ("llama", "gemma", "mixtral", "qwen2", "olmoe"):
        raise NotImplementedError(
            f"HF model_type {model_type!r} not supported by the converter "
            "(llama, gemma, mixtral, qwen2, olmoe are)"
        )
    if model_type == "olmoe" and getattr(hf_config, "clip_qkv", None):
        raise NotImplementedError("OLMoE clip_qkv is not implemented")
    scaling_kwargs = {}
    rope_scaling = getattr(hf_config, "rope_scaling", None)
    if rope_scaling:
        rope_type = rope_scaling.get("rope_type", rope_scaling.get("type"))
        if rope_type != "llama3":
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} is not implemented "
                "(only 'llama3'); converting would silently change "
                "long-context frequencies"
            )
        scaling_kwargs = {
            "rope_scaling_factor": float(rope_scaling["factor"]),
            "rope_low_freq_factor": float(rope_scaling["low_freq_factor"]),
            "rope_high_freq_factor": float(rope_scaling["high_freq_factor"]),
            "rope_original_max_len": int(
                rope_scaling["original_max_position_embeddings"]),
        }
    sliding = getattr(hf_config, "sliding_window", None)
    max_pos = getattr(hf_config, "max_position_embeddings", 8192)
    # Qwen2-style configs carry sliding_window but gate it behind
    # use_sliding_window (default False = full causal attention, which our
    # decoder matches exactly); only an ACTIVE window is a real divergence.
    sliding_active = bool(getattr(hf_config, "use_sliding_window", True))
    if sliding and sliding_active and sliding < max_pos:
        raise NotImplementedError(
            f"sliding_window={sliding} < max_position_embeddings={max_pos}: "
            "our decoder attends the full causal context; converting would "
            "produce divergent long-context logits"
        )
    if model_type != "qwen2" and getattr(hf_config, "attention_bias", False):
        # HF llama-style attention_bias puts biases on q/k/v AND o_proj;
        # our bias support covers the Qwen2 layout (q/k/v only).  Loud
        # rejection beats silently dropping the o bias.
        raise NotImplementedError(
            f"attention_bias on model_type {model_type!r} is not supported "
            "(q/k/v/o biases; only the qwen2 q/k/v layout is implemented)"
        )
    gemma = model_type == "gemma"
    return dataclasses.replace(
        LLAMA3_8B,
        name=getattr(hf_config, "name_or_path", "") or f"hf-{model_type}",
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        d_ff=hf_config.intermediate_size,
        head_dim=getattr(hf_config, "head_dim", 0)
        or hf_config.hidden_size // hf_config.num_attention_heads,
        rope_theta=getattr(hf_config, "rope_theta", 10_000.0),
        norm_eps=hf_config.rms_norm_eps,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 8192),
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        # Gemma conventions (parity-tested against GemmaForCausalLM):
        # sqrt(d_model) embedding normalizer, (1+w) RMSNorm, tanh-GeLU gate.
        embedding_scale=gemma,
        norm_plus_one=gemma,
        mlp_activation="gelu" if gemma else "silu",
        # Mixtral MoE (parity-tested against MixtralForCausalLM; top-k
        # routing normalizations are algebraically identical).
        n_experts={"mixtral": getattr(hf_config, "num_local_experts", 0),
                   "olmoe": getattr(hf_config, "num_experts", 0),
                   }.get(model_type, 0),
        n_experts_per_token=getattr(hf_config, "num_experts_per_tok", 2),
        # OLMoE: the gates are the full softmax's unless norm_topk_prob
        # (Mixtral's rule is the renormalised one), and q and k are
        # RMS-normed whole, unconditionally (modeling_olmoe.py).
        norm_topk_prob=(model_type != "olmoe"
                        or bool(getattr(hf_config, "norm_topk_prob", False))),
        qk_norm=(model_type == "olmoe"),
        # Qwen2-family: learned Q/K/V biases (parity-tested against
        # Qwen2ForCausalLM; Qwen2 puts NO bias on o_proj).
        attention_bias=(model_type == "qwen2"),
        **scaling_kwargs,
    )


def _smallthinker_config(hf_config) -> ModelConfig:
    """The preset with a window, from the source's own keys: the two
    per-layer layouts become ONE period of kinds (``sliding_window_layout``
    1 with ``rope_layout`` 1: "window"; both 0: "nope"; window 0 with rope
    1: "full"), the shortest that the stack repeats; a window without a
    position encoding, or layouts with no period, are refused."""
    n = hf_config.num_hidden_layers
    windows = list(getattr(hf_config, "sliding_window_layout", None)
                   or [0] * n)
    ropes = list(getattr(hf_config, "rope_layout", None) or [1] * n)
    if len(windows) != n or len(ropes) != n:
        raise NotImplementedError(
            "sliding_window_layout / rope_layout do not name every layer")
    kinds = []
    for w, r in zip(windows, ropes):
        if w and not r:
            raise NotImplementedError(
                "a sliding-window layer without a position encoding")
        kinds.append("window" if w else "full" if r else "nope")
    period = next((p for p in range(1, n + 1)
                   if n % p == 0 and kinds == kinds[:p] * (n // p)), n)
    window = int(getattr(hf_config, "sliding_window_size", 0) or 0)
    if "window" in kinds and not window:
        raise NotImplementedError("window layers but no sliding_window_size")
    if getattr(hf_config, "rope_scaling", None):
        raise NotImplementedError("rope_scaling on a smallthinker model")
    if not getattr(hf_config, "moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "moe_primary_router_apply_softmax false (a sigmoid router)")
    return dataclasses.replace(
        SMALLTHINKER_21B_A3B,
        name=getattr(hf_config, "name_or_path", "") or "hf-smallthinker",
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=n,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.head_dim,
        d_ff=hf_config.moe_ffn_hidden_size,
        n_experts=hf_config.moe_num_primary_experts,
        n_experts_per_token=hf_config.moe_num_active_primary_experts,
        norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", True)),
        rope_theta=float(hf_config.rope_theta),
        norm_eps=hf_config.rms_norm_eps,
        max_seq_len=hf_config.max_position_embeddings,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        layer_pattern=tuple(kinds[:period]),
        sliding_window=window if "window" in kinds else 0,
    )


def params_from_hf_state_dict(cfg: ModelConfig, state_dict, dtype=jnp.bfloat16):
    """Map an HF Llama state dict onto our stacked-layer pytree.

    HF Linear weights are [out, in]; ours are [in, out] — transposed here.
    The embedding row space is padded to ``cfg.padded_vocab``.
    """
    # Per-tensor dtype cast at stack time: staging whole stacked layers in
    # f32 would triple peak host memory on an 8B conversion.
    def t(name):  # tensor -> [in, out] in the target dtype
        return jnp.asarray(np.asarray(state_dict[name]).T, dtype)

    def stack(fmt):
        return jnp.stack([t(fmt.format(i)) for i in range(cfg.n_layers)])

    def stack_raw(fmt):  # norms: 1-D, no transpose
        return jnp.stack(
            [jnp.asarray(np.asarray(state_dict[fmt.format(i)]), dtype)
             for i in range(cfg.n_layers)]
        )

    embed = np.asarray(state_dict["model.embed_tokens.weight"])
    padded = jnp.zeros((cfg.padded_vocab, cfg.d_model), dtype)
    padded = padded.at[: embed.shape[0]].set(jnp.asarray(embed, dtype))

    layers = {
        "attn_norm": stack_raw("model.layers.{}.input_layernorm.weight"),
        "mlp_norm": stack_raw("model.layers.{}.post_attention_layernorm.weight"),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
    }
    if cfg.attention_bias:
        # Qwen2-family Q/K/V biases (1-D, no transpose).
        layers["wq_b"] = stack_raw("model.layers.{}.self_attn.q_proj.bias")
        layers["wk_b"] = stack_raw("model.layers.{}.self_attn.k_proj.bias")
        layers["wv_b"] = stack_raw("model.layers.{}.self_attn.v_proj.bias")
    if cfg.qk_norm:
        layers["q_norm"] = stack_raw("model.layers.{}.self_attn.q_norm.weight")
        layers["k_norm"] = stack_raw("model.layers.{}.self_attn.k_norm.weight")
    if cfg.n_experts:
        # Mixtral: block_sparse_moe.experts.N.{w1=gate, w3=up, w2=down};
        # OLMoE: mlp.experts.N.{gate,up,down}_proj; SmallThinker:
        # block_sparse_moe.experts.N.{gate,up,down} under a router named
        # primary_router (each [f, d] or [d, f] in HF's [out, in]); stacked
        # here as [L, E, in, out].
        router = "gate"
        if "model.layers.0.mlp.gate.weight" in state_dict:
            moe, names = "mlp", ("gate_proj", "up_proj", "down_proj")
        elif ("model.layers.0.block_sparse_moe.primary_router.weight"
              in state_dict):
            moe, names = "block_sparse_moe", ("gate", "up", "down")
            router = "primary_router"
        else:
            moe, names = "block_sparse_moe", ("w1", "w3", "w2")

        def stack_experts(w_name):
            return jnp.stack([
                jnp.stack([
                    t(f"model.layers.{i}.{moe}.experts.{e}.{w_name}.weight")
                    for e in range(cfg.n_experts)
                ])
                for i in range(cfg.n_layers)
            ])

        layers["router"] = stack(
            "model.layers.{}." + moe + "." + router + ".weight")
        layers["w_gate"], layers["w_up"], layers["w_down"] = (
            stack_experts(n) for n in names)
    else:
        layers["w_gate"] = stack("model.layers.{}.mlp.gate_proj.weight")
        layers["w_up"] = stack("model.layers.{}.mlp.up_proj.weight")
        layers["w_down"] = stack("model.layers.{}.mlp.down_proj.weight")
    params = {
        "embed": padded,
        "layers": layers,
        "final_norm": jnp.asarray(
            np.asarray(state_dict["model.norm.weight"]), dtype),
    }
    if not cfg.tie_embeddings:
        head = jnp.asarray(np.asarray(state_dict["lm_head.weight"]).T, dtype)
        padded_head = jnp.zeros((cfg.d_model, cfg.padded_vocab), dtype)
        params["lm_head"] = padded_head.at[:, : head.shape[1]].set(head)
    return params


def from_hf_llama(model, dtype=jnp.bfloat16):
    """(cfg, params) from a loaded transformers LlamaForCausalLM."""
    cfg = config_from_hf(model.config)
    state = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    return cfg, params_from_hf_state_dict(cfg, state, dtype=dtype)


def save_hf_as_orbax(model, path: str, dtype=jnp.bfloat16) -> ModelConfig:
    """Convert + write the serving checkpoint (api_http --checkpoint).

    The params pytree goes to ``<path>/params`` and the architecture to
    ``<path>/model_config.json`` so the server reconstructs the exact
    ModelConfig without a preset (--model is ignored when present).
    """
    import json
    import os

    import orbax.checkpoint as ocp

    cfg, params = from_hf_llama(model, dtype=dtype)
    os.makedirs(path, exist_ok=True)
    ocp.PyTreeCheckpointer().save(os.path.join(path, "params"), params)
    with open(os.path.join(path, "model_config.json"), "w") as f:
        json.dump(dataclasses.asdict(cfg), f, indent=1)
    return cfg


def load_serving_checkpoint(path: str):
    """(cfg_or_None, params) from a checkpoint directory.

    Accepts both layouts: a bare Orbax params tree (preset-config servers)
    or the ``params`` + ``model_config.json`` pair save_hf_as_orbax writes.

    Params come back as HOST numpy arrays: the caller decides what reaches
    the device and in what form (``ops.quant.quantize_params`` moves one
    leaf at a time, ``parallel.sharding.shard_pytree`` places shards
    directly) — a 7B bf16 tree restored straight onto one 16 GB chip would
    not leave room to quantize it.
    """
    import json
    import os

    import jax
    import orbax.checkpoint as ocp

    cfg = None
    params_path = path
    cfg_file = os.path.join(path, "model_config.json")
    if os.path.exists(cfg_file):
        with open(cfg_file) as f:
            cfg = ModelConfig(**json.load(f))
        params_path = os.path.join(path, "params")
    ckptr = ocp.PyTreeCheckpointer()
    tree = ckptr.metadata(params_path).item_metadata.tree
    params = ckptr.restore(params_path, restore_args=jax.tree.map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree))
    return cfg, params
