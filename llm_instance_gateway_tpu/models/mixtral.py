"""Sparse-expert entry points: Mixtral-8x7B (8 experts, top-2, renormalised
gates) and OLMoE-1B-7B (64 experts, top-8, gates as the full softmax gives
them, QK-norm) and GLM-4.7-Flash (latent attention, a leading dense layer,
64 experts top-4 behind a sigmoid router with a selection bias, a shared
expert).  Falcon-H1-34B (dense; a state-space mixer beside attention in
every layer, ``models/ssm.py``) is entered here too: the benchmark's server
wrapper looks a preset up in this dict, ``llama``'s, ``gemma``'s and
``qwen``'s and nowhere else.  SmallThinker-21B-A3B (64 experts, top-6, ReLU
gating, a router on the block's input; a period of one full layer without a
position encoding and three window layers) is the stack of two kinds.
LFM2-24B-A2B (64 experts, top-4, GLM's router rule; three layers in four run
a gated short convolution in attention's place, ``models/shortconv.py``, and
hold no K and V; 64-wide heads with a per-head QK-norm) is the stack with
layers that are not attention.  Ling-3.0-flash (512 experts in 8 groups,
top-8 of the 4 best groups, held as a chip's share; delta-rule linear
attention, ``models/kda.py``, in five layers of six and a latent layer closing
the period) is the stack with a matrix state and a share of the experts.

BASELINE.json's criticality-tiered mixed pool pairs Mixtral-8x7B with
Gemma-7B on v5e-32.  The MoE MLP lives in ``transformer._moe_mlp``; expert
weights carry a leading expert axis that ``parallel.sharding`` maps onto the
mesh's expert/tensor axes.
"""

from __future__ import annotations

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import (
    FALCON_H1_34B,
    GLM_4_7_FLASH,
    LFM2_24B_A2B,
    LING_3_FLASH,
    MIXTRAL_8X7B,
    OLMOE_1B_7B,
    SMALLTHINKER_21B_A3B,
    TINY_FALCON_H1_TEST,
    TINY_GLM_TEST,
    TINY_LFM2_TEST,
    TINY_LING_TEST,
    TINY_MOE_TEST,
    TINY_OLMOE_TEST,
    TINY_SMALLTHINKER_TEST,
)

CONFIGS = {"mixtral-8x7b": MIXTRAL_8X7B, "mixtral-tiny": TINY_MOE_TEST,
           "olmoe-1b-7b": OLMOE_1B_7B, "olmoe-tiny": TINY_OLMOE_TEST,
           "glm-4.7-flash": GLM_4_7_FLASH, "glm-tiny": TINY_GLM_TEST,
           "falcon-h1-34b": FALCON_H1_34B,
           "falcon-h1-tiny": TINY_FALCON_H1_TEST,
           "smallthinker-21b-a3b": SMALLTHINKER_21B_A3B,
           "smallthinker-tiny": TINY_SMALLTHINKER_TEST,
           "lfm2-24b-a2b": LFM2_24B_A2B, "lfm2-tiny": TINY_LFM2_TEST,
           "ling-3.0-flash": LING_3_FLASH, "ling-tiny": TINY_LING_TEST}

init_params = transformer.init_params
init_decode_cache = transformer.init_decode_cache
insert_prefill = transformer.insert_prefill
prefill = transformer.prefill
decode_step = transformer.decode_step
