"""Model architecture configs for the families the pools serve.

The reference's PoC pools serve Llama-2-7b + LoRA on vLLM
(``examples/poc/manifests/vllm/vllm-lora-deployment.yaml:23-60``); the
BASELINE.json milestone configs call for Gemma-2B, Llama-3-8B and a
Mixtral-8x7B + Gemma-7B mixed pool.  These dataclasses cover all of them with
one decoder family (RoPE + GQA + RMSNorm + gated MLP, optionally MoE;
Qwen2's QKV bias and OLMoE's QK-norm and gate rule are flags; GLM's latent
attention, ``models/mla.py``; Falcon-H1's parallel block, a state-space
mixer beside the attention of every layer, ``models/ssm.py``, with the
family's fixed muP multipliers; SmallThinker's PERIOD of layer kinds,
``layer_pattern``: full attention without a position encoding and RoPE
layers with a sliding window in one stack, which the layer loop scans a
period a step and whose decode cache is of two kinds, full lanes of
``max_seq_len`` positions and ring lanes of ``sliding_window``).

All dims are chosen/padded TPU-first: head_dim and d_model multiples of 128
(MXU lane width), d_ff multiples of 128, vocab padded to 128 so the final
projection tiles cleanly onto the systolic array.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import NamedTuple


def pad_to(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


class LayerKind(NamedTuple):
    """What the operator of one layer of a stack is.  Attention: ``window``
    > 0 lets position i attend j iff 0 <= i - j < window (0: every j <= i),
    ``rope`` says whether q and k get the rotary position encoding at all.
    ``conv``: no attention at all but a gated short convolution
    (``models/shortconv.py``), whose layer holds no K and V.  ``kda``: no
    attention either but a delta-rule linear attention (``models/kda.py``),
    whose layer holds a matrix state a head and a conv tail."""
    window: int = 0
    rope: bool = True
    conv: bool = False
    kda: bool = False

    @property
    def operator(self) -> str:
        """Which operator's leaves the layer runs: "conv", "kda" or "attn"
        (per-head or latent attention)."""
        return "conv" if self.conv else "kda" if self.kda else "attn"

    @property
    def cache(self) -> str:
        """Which of a decode cache's stacks the layer's rows live in."""
        return ("conv" if self.conv else "kda" if self.kda
                else "ring" if self.window else "full")


MLP_ACTIVATIONS = ("silu", "gelu", "relu")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    rope_theta: float = 500_000.0
    # Llama-3.1 long-context rope scaling (factor 0 = disabled).
    rope_scaling_factor: float = 0.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    # Gemma-style differences.
    tie_embeddings: bool = False
    embedding_scale: bool = False  # Gemma multiplies embeddings by sqrt(d_model)
    norm_plus_one: bool = False  # Gemma RMSNorm uses (1 + w) weighting
    # The gated MLP's activation, dense and expert alike: ``act(gate) * up``
    # with SiLU (Llama), tanh-GeLU (Gemma) or ReLU (SmallThinker's ReGLU).
    mlp_activation: str = "silu"
    # Qwen2-family difference: learned biases on the Q/K/V projections
    # (attention only — o and the MLP stay bias-free).
    attention_bias: bool = False
    # MoE (Mixtral): 0 experts = dense.
    n_experts: int = 0
    n_experts_per_token: int = 2
    # The router's gate rule.  True (Mixtral): the k chosen experts' weights
    # are the softmax over the chosen logits, i.e. renormalised to sum to 1.
    # False (OLMoE, ``norm_topk_prob`` false): the weights are the softmax
    # over ALL experts, taken as they are.
    norm_topk_prob: bool = True
    # OLMoE: RMSNorm on the whole projected q and k vectors (all heads
    # together), after the projection and before the split into heads.
    qk_norm: bool = False
    # LFM2: RMSNorm over the ``head_dim`` numbers of EACH head of q and k,
    # one weight vector of ``head_dim`` for all heads, before RoPE.
    qk_norm_head: bool = False
    # Latent attention (MLA; GLM-4.7-Flash, ``glm4_moe_lite``):
    # ``kv_lora_rank`` > 0 makes every layer's keys and values come out of
    # ONE normed latent of that width plus ONE rope key of
    # ``qk_rope_head_dim`` shared by all heads, and that pair is all the
    # cache holds (``latent_width`` numbers a position a layer).  Queries go
    # through a normed bottleneck of ``q_lora_rank``; a head's query/key is
    # ``qk_nope_head_dim`` + ``qk_rope_head_dim`` wide (= ``head_dim``), its
    # value ``v_head_dim``.  ``models/mla.py`` holds the equations.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Sparse layers of that family.  ``first_k_dense`` leading layers keep
    # the dense MLP of width ``d_ff``; the others route over ``n_experts``
    # experts of width ``moe_d_ff`` (0: ``d_ff``) and add
    # ``n_shared_experts`` always-on experts of the same width.
    # ``router_sigmoid``: scores are sigmoid(logits); the experts are the
    # top-k of score + a learned selection bias, the gates the UNBIASED
    # scores (renormalised over the chosen under ``norm_topk_prob``) times
    # ``routed_scaling_factor``.
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    first_k_dense: int = 0
    router_sigmoid: bool = False
    routed_scaling_factor: float = 1.0
    # What a sigmoid router adds to the chosen scores' sum before it divides.
    router_gate_eps: float = 1e-20
    # A stack of more than one kind of attention layer (SmallThinker,
    # ``smallthinker``): ``layer_pattern`` is the PERIOD of kinds the stack
    # repeats, each "full" (causal, RoPE), "nope" (causal, no position
    # encoding at all) or "window" (RoPE; position i attends j iff
    # 0 <= i - j < ``sliding_window``).  Empty: every layer "full".  The
    # layer loop scans one period a step (``transformer._scan_groups``), and
    # a model with a window keeps TWO decode caches: ``k``/``v`` of
    # ``max_seq_len`` positions for its full layers and ``k_win``/``v_win``
    # rings of ``sliding_window`` positions (position p at p mod ring) for
    # its window layers.  ``router_pre_attention``: the router's logits come
    # from the block's INPUT (the residual stream before the attention
    # norm), not from the normed input of the MLP; the experts still read
    # the latter.
    layer_pattern: tuple[str, ...] = ()
    sliding_window: int = 0
    router_pre_attention: bool = False
    # Layers WITHOUT attention (LFM2, ``lfm2_moe``): a "conv" layer of the
    # pattern runs a gated short convolution in attention's place
    # (``models/shortconv.py``: in_proj to B | C | u, z = B * u, a causal
    # depthwise conv of ``conv_kernel`` taps over z, C * that, out_proj).
    # It holds no K and V: ``k``/``v`` are the ATTENTION layers' alone and
    # the cache keeps the conv's last ``conv_kernel - 1`` inputs a slot in
    # ``conv`` [L_conv, conv_kernel - 1, B, d_model].  The pattern is counted
    # from layer 0 of the MODEL: a group of layers that starts at layer f
    # (the sparse layers after ``first_k_dense`` dense ones) runs the
    # pattern rotated by f, and a depth that ends part of a period in (the
    # published 40 = 2 + 9 x 4 + 2) runs the layers left over as a span of
    # their own (``group_spans``).  The parameters of a group are one stack
    # a kind: the attention leaves stacked over the attention layers, the
    # conv leaves over the conv layers, the rest over all.  Its heads may be
    # narrower than a vreg's 128 lanes (``kv_pack``).
    conv_kernel: int = 0
    # Delta-rule linear attention IN PLACE of attention (Ling-3.0-flash,
    # ``bailing_hybrid``; Kimi Delta Attention, ``models/kda.py`` holds the
    # equations): a "kda" layer of the pattern keeps, a slot and a head, a
    # float32 matrix state [``kda_head_dim``, ``kda_head_dim``] (key x value)
    # and the last ``kda_conv`` - 1 inputs of its three short convolutions;
    # the cache holds them as ``kda`` [L_kda, B, heads, dk, dv] and ``conv``
    # [L_kda, kda_conv - 1, B, 3 x heads x dk].  The per-channel log decay
    # lies in (``kda_lower_bound``, 0).  A prompt's recurrence is computed in
    # blocks of ``kda.BLOCK`` positions (``kda.scan_chunked``).
    # The pattern's other word, "mla", is a latent-attention layer
    # (``kv_lora_rank`` > 0): only those layers hold latent rows.
    # ``mla_head_gate``: a latent layer's heads are gated, one sigmoid a
    # head from the layer's normed input, before the output projection.
    kda_n_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    mla_head_gate: bool = False
    # Experts held as ONE CHIP'S SHARE of a layer's (expert parallelism's
    # local part): the router scores all ``n_experts``, this program holds
    # and computes the ``n_experts_local`` from ``expert_first`` on (0: all)
    # and returns their part of the mix plus the shared expert's.
    # ``n_group`` > 1: group-limited routing, the experts lie in ``n_group``
    # equal groups, a group scores the sum of its two largest biased scores
    # and only the ``topk_group`` best groups' experts can be chosen.
    n_experts_local: int = 0
    expert_first: int = 0
    n_group: int = 1
    topk_group: int = 1
    # A state-space mixer beside attention in every layer (Falcon-H1,
    # ``falcon_h1``; the Mamba-2 form, ``models/ssm.py`` holds the
    # equations): ``ssm_d_inner`` > 0 makes the block parallel,
    # ``x + attn(x_n) + ssm(x_n)`` and then the MLP, and the decode cache
    # hold a recurrent state ``ssm`` [L, B, heads, d_state, head_dim]
    # (float32) and the conv's last ``ssm_d_conv - 1`` inputs ``conv``
    # [L, d_conv - 1, B, conv_dim] beside ``k`` and ``v``.  ``ssm_d_inner`` = ``ssm_n_heads`` x
    # ``ssm_head_dim``; B and C are shared by the heads of a group (head h
    # reads group ``h // (ssm_n_heads // ssm_n_groups)``); a prompt's
    # recurrence is computed in chunks of ``ssm_chunk`` positions.
    ssm_d_inner: int = 0
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_d_state: int = 0
    ssm_n_groups: int = 1
    ssm_d_conv: int = 4
    ssm_chunk: int = 128
    # That family's fixed scalar multipliers (muP), each 1 for every other
    # model, which then traces no multiply: on the embedding, on the input
    # of the attention and of the mixer, on the keys, on both branches'
    # outputs, on the mixer's projected z | x | B | C | dt, on the MLP's
    # gate and output, on the logits.
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # LoRA serving slots (compile-time constants: resizing reshapes buffers
    # and recompiles, so they mirror vLLM's --max-loras / max rank flags).
    max_lora_slots: int = 4
    max_lora_rank: int = 16
    # Pallas flash-attention for prefill (right-padded batches only).  On by
    # default: the dispatcher takes the XLA reference on CPU or when shapes
    # miss the tiling constraints, and logs which it took.  Lowers
    # non-interpreted on v5e ("TPU v5 lite") with parity inside 4 bf16 ulps
    # at every listed model's head layout (tools/onchip_pallas_check.py,
    # chip run of PR 21).  Speed against XLA: not measured.
    use_flash_attention: bool = True
    # Pallas cached-decode attention kernel (ops/pallas_decode_attention),
    # same dispatch; also switches a sparse model's grouped expert matmul
    # (ops/pallas_moe) between its kernel and its XLA tiles, so that a mesh,
    # which turns this off, partitions plain XLA.  Lowers on v5e with parity at the same layouts, bf16
    # and int8, lane and paged (same run); DMA-clamping skips cache blocks
    # past each row's length.  Speed against XLA: not measured.
    use_pallas_decode: bool = True

    def __post_init__(self):
        # (a config restored from JSON brings the period as a list)
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        if self.mlp_activation not in MLP_ACTIVATIONS:
            raise ValueError(f"mlp_activation {self.mlp_activation!r}: one "
                             f"of {MLP_ACTIVATIONS}")
        unknown = set(self.layer_pattern) - {"full", "nope", "window", "conv",
                                             "kda", "mla"}
        if unknown:
            raise ValueError(f"layer_pattern names {sorted(unknown)}")
        if ("kda" in self.layer_pattern) != bool(self.kda_n_heads):
            raise ValueError("a kda layer needs kda_n_heads > 0, and "
                             "kda_n_heads a kda layer")
        if ("mla" in self.layer_pattern) != bool(
                self.layer_pattern and self.latent_width):
            raise ValueError("an mla layer needs kv_lora_rank > 0, and a "
                             "latent model's pattern names its mla layers")
        if self.n_experts_local and not (
                0 < self.n_experts_local
                <= self.n_experts - self.expert_first):
            raise ValueError("n_experts_local from expert_first on is not "
                             "within n_experts")
        if self.n_group > 1 and (
                not self.router_sigmoid or self.n_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.n_experts // self.n_group < 2):
            raise ValueError("group-limited routing: a sigmoid router over "
                             "n_group equal groups of at least 2 experts, "
                             "topk_group of them kept")
        if ("window" in self.layer_pattern) != bool(self.sliding_window):
            raise ValueError("a window layer needs sliding_window > 0, and "
                             "sliding_window a window layer")
        if ("conv" in self.layer_pattern) != bool(self.conv_kernel):
            raise ValueError("a conv layer needs conv_kernel > 0, and "
                             "conv_kernel a conv layer")
        period = len(self.layer_kinds)
        # (A stack with conv layers runs what a depth leaves over of a
        # period as a span of its own, ``group_spans``: LFM2's published
        # depth ends half a period in.  Ring lanes are counted in whole
        # periods.)
        if self.n_layers % period and not self.kinds_own_leaves:
            raise ValueError(
                f"{self.n_layers} layers are not whole periods of {period}")
        if period > 1 and ((self.latent_width and not self.kda_n_heads)
                           or self.ssm_d_inner or (
                self.first_k_dense and not self.kinds_own_leaves)):
            raise NotImplementedError(
                "a period of layer kinds beside a latent cache or a mixer, "
                "or of attention kinds beside leading dense layers")

    @property
    def gelu_mlp(self) -> bool:
        return self.mlp_activation == "gelu"

    @property
    def layer_kinds(self) -> tuple[LayerKind, ...]:
        """The period of the stack, one ``LayerKind`` a layer of it, counted
        from layer 0 of the model."""
        return tuple(LayerKind(self.sliding_window if k == "window" else 0,
                               k != "nope", k == "conv", k == "kda")
                     for k in self.layer_pattern or ("full",))

    @property
    def kinds_own_leaves(self) -> bool:
        """A stack some of whose layers run no attention (conv or kda
        layers): each operator's leaves are stacked over ITS layers alone,
        and a depth may end part of a period in."""
        return bool(self.conv_kernel or self.kda_n_heads)

    @property
    def experts_held(self) -> int:
        """Experts whose weights this program holds (all, or its share)."""
        return self.n_experts_local or self.n_experts

    @property
    def kda_conv_dim(self) -> int:
        """Channels the KDA layers' three short convolutions run over:
        q | k | v."""
        return 3 * self.kda_n_heads * self.kda_head_dim

    @property
    def n_dense_layers(self) -> int:
        """Leading layers that keep a dense MLP in a sparse model."""
        return self.first_k_dense if self.n_experts else 0

    def group_spans(self, first: int, n: int
                    ) -> list[tuple[int, int, tuple[LayerKind, ...]]]:
        """The ``n`` layers from layer ``first`` on as spans (first layer,
        layers, the period of kinds the span repeats): whole periods of the
        model's pattern rotated by ``first``, or ONE kind where all ``n``
        are of it (LFM2's two leading dense layers, conv and conv); and,
        where the depth ends part of a period in, the layers left over as
        a last span, one run of what they are."""
        kinds = tuple(self.kind_of(first + j) for j in range(n))
        if len(set(kinds)) == 1:
            return [(first, n, kinds[:1])]
        period = len(self.layer_kinds)
        whole = n - n % period
        spans = [(first, whole, kinds[:period])] if whole else []
        if whole < n:
            rest = kinds[whole:]
            spans.append((first + whole, n - whole,
                          rest[:1] if len(set(rest)) == 1 else rest))
        return spans

    def kind_of(self, layer: int) -> LayerKind:
        kinds = self.layer_kinds
        return kinds[layer % len(kinds)]

    def n_layers_of(self, cache: str) -> int:
        """How many of the model's layers keep their rows in the decode
        cache's ``cache`` stack ("full", "ring", "conv" or "kda")."""
        return sum(self.kind_of(l).cache == cache
                   for l in range(self.n_layers))

    @property
    def n_window_layers(self) -> int:
        return self.n_layers_of("ring")

    @property
    def kv_pack(self) -> int:
        """How many kv heads lie side by side in one cache row [..,
        n_kv_heads / kv_pack, kv_pack * head_dim], which is how the cache is
        allocated, written and read (``ops.attention.pack_heads``): heads
        narrower than a vreg's 128 lanes as many as fill them (LFM2's 64:
        two), or as the kv heads allow; 1: a row a head.  Only a stack with
        conv layers packs: the engine serves it from contiguous bf16 lanes
        on one device and nothing else, and every other holder of K and V
        (the paged pool, int8 lanes, ``extend_step``, the handoff wire, a
        mesh) indexes a row by its head."""
        hd = self.resolved_head_dim
        if not self.conv_kernel or hd >= 128 or 128 % hd:
            return 1
        return math.gcd(128 // hd, self.n_kv_heads)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def latent_width(self) -> int:
        """Numbers a latent cache holds per position per layer (0: a
        per-head K/V cache)."""
        return self.kv_lora_rank and self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """``latent_width`` padded to whole 128-lane vregs: the cache row."""
        return pad_to(self.latent_width, 128)

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's causal conv runs over: x | B | C."""
        return self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_d_state

    @property
    def ssm_in_dim(self) -> int:
        """Columns of the mixer's input projection: z | x | B | C | dt."""
        return self.ssm_d_inner + self.ssm_conv_dim + self.ssm_n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab_size, 128)

    @property
    def rope_scaling(self) -> tuple | None:
        """(factor, low_ff, high_ff, original_max) or None when disabled."""
        if not self.rope_scaling_factor:
            return None
        return (
            self.rope_scaling_factor,
            self.rope_low_freq_factor,
            self.rope_high_freq_factor,
            self.rope_original_max_len,
        )

    def tiny(self) -> "ModelConfig":
        """Shrink to test size, keeping structure (ratios, GQA, MoE-ness)."""
        return replace(
            self,
            name=self.name + "-tiny",
            # Covers the byte-level tokenizer (259 ids) so tiny models serve
            # real text end-to-end.
            vocab_size=320,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=max(1, self.n_kv_heads * 4 // self.n_heads),
            d_ff=128,
            head_dim=16,
            max_seq_len=128,
            max_lora_rank=4,
        )


# The reference PoC's model (vllm-lora-deployment.yaml:33-39 serves
# meta-llama/Llama-2-7b-hf): MHA (no GQA), theta 1e4, 4k context.
LLAMA2_7B = ModelConfig(
    name="llama2-7b",
    vocab_size=32_000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=32,
    d_ff=11_008,
    rope_theta=10_000.0,
    max_seq_len=4096,
)

LLAMA3_8B = ModelConfig(
    name="llama3-8b",
    vocab_size=128_256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14_336,
    rope_theta=500_000.0,
    max_seq_len=8192,
)

GEMMA_2B = ModelConfig(
    name="gemma-2b",
    vocab_size=256_128,
    d_model=2048,
    n_layers=18,
    n_heads=8,
    n_kv_heads=1,
    d_ff=16_384,
    head_dim=256,
    rope_theta=10_000.0,
    tie_embeddings=True,
    embedding_scale=True,
    norm_plus_one=True,
    mlp_activation="gelu",
    max_seq_len=8192,
)

GEMMA_7B = ModelConfig(
    name="gemma-7b",
    vocab_size=256_128,
    d_model=3072,
    n_layers=28,
    n_heads=16,
    n_kv_heads=16,
    head_dim=256,
    d_ff=24_576,
    rope_theta=10_000.0,
    tie_embeddings=True,
    embedding_scale=True,
    norm_plus_one=True,
    mlp_activation="gelu",
    max_seq_len=8192,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32_000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14_336,
    rope_theta=1_000_000.0,
    n_experts=8,
    n_experts_per_token=2,
    max_seq_len=32_768,
)

QWEN2_5_7B = ModelConfig(
    name="qwen2.5-7b",
    vocab_size=152_064,
    d_model=3584,
    n_layers=28,
    n_heads=28,
    n_kv_heads=4,
    d_ff=18_944,
    head_dim=128,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    attention_bias=True,
    max_seq_len=32_768,
)

# allenai/OLMoE-1B-7B-0125-Instruct: MHA, 64 experts of width 1024, top-8,
# gates not renormalised, QK-norm (unconditional in modeling_olmoe.py).
OLMOE_1B_7B = ModelConfig(
    name="olmoe-1b-7b",
    vocab_size=50_304,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1024,
    head_dim=128,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    n_experts=64,
    n_experts_per_token=8,
    norm_topk_prob=False,
    qk_norm=True,
    max_seq_len=4096,
)

# zai-org/GLM-4.7-Flash (``glm4_moe_lite``): latent attention, one dense layer
# then sparse ones (64 experts of width 1536, top-4 by sigmoid score + bias,
# gates renormalised and scaled by 1.8, one shared expert).  The
# multi-token-prediction layer is a draft head and is not part of the forward.
GLM_4_7_FLASH = ModelConfig(
    name="glm-4.7-flash",
    vocab_size=154_880,
    d_model=2048,
    n_layers=47,
    n_heads=20,
    n_kv_heads=20,
    d_ff=10_240,
    head_dim=256,
    rope_theta=1_000_000.0,
    norm_eps=1e-5,
    n_experts=64,
    n_experts_per_token=4,
    norm_topk_prob=True,
    q_lora_rank=768,
    kv_lora_rank=512,
    qk_nope_head_dim=192,
    qk_rope_head_dim=64,
    v_head_dim=256,
    moe_d_ff=1536,
    n_shared_experts=1,
    first_k_dense=1,
    router_sigmoid=True,
    routed_scaling_factor=1.8,
    max_seq_len=202_752,
    max_lora_slots=0,  # adapters are not served over latent projections
)

# The CPU's GLM: 1 dense + 2 sparse layers, 64 experts top-4 kept, the five
# latent sizes distinct as the model's are (q/k head 24 + 8 = v head 32).
TINY_GLM_TEST = replace(
    GLM_4_7_FLASH.tiny(), name="glm-tiny", n_layers=3, n_kv_heads=4,
    head_dim=32, q_lora_rank=48, kv_lora_rank=40, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, moe_d_ff=96)

# tiiuae/Falcon-H1-34B-Instruct (``falcon_h1``): every layer runs attention
# (20 query / 4 kv heads of 128) and a Mamba-2 mixer (32 heads of 128, state
# 256, 2 groups, conv 4) side by side on one normed input, then a dense MLP;
# the muP multipliers are the published config's.
FALCON_H1_34B = ModelConfig(
    name="falcon-h1-34b",
    vocab_size=261_120,
    d_model=5120,
    n_layers=72,
    n_heads=20,
    n_kv_heads=4,
    d_ff=21_504,
    head_dim=128,
    rope_theta=1e11,
    norm_eps=1e-5,
    ssm_d_inner=4096,
    ssm_n_heads=32,
    ssm_head_dim=128,
    ssm_d_state=256,
    ssm_n_groups=2,
    ssm_d_conv=4,
    ssm_chunk=128,
    embedding_multiplier=5.656854249492381,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    lm_head_multiplier=0.0078125,
    max_seq_len=262_144,
    max_lora_slots=0,  # adapters are not served beside the multipliers
)

# The CPU's Falcon-H1: the ratios kept (5 queries a kv head, 2 groups of
# mixer heads, conv 4, a prompt of several scan chunks), every multiplier
# away from 1 and distinct.
TINY_FALCON_H1_TEST = replace(
    FALCON_H1_34B, name="falcon-h1-tiny", vocab_size=320, d_model=256,
    n_layers=3, n_heads=5, n_kv_heads=1, head_dim=32, d_ff=512,
    ssm_d_inner=128, ssm_n_heads=4, ssm_head_dim=32, ssm_d_state=16,
    ssm_chunk=8, attention_in_multiplier=0.8, max_seq_len=512,
    max_lora_rank=4)

# PowerInfer/SmallThinker-21BA3B-Instruct (``smallthinker``): layer l with
# l % 4 == 0 is full attention with NO position encoding, the other three of
# each period are RoPE layers with a 4,096-position window; 64 experts of
# width 768, top-6 by the softmax over the chosen, no shared expert and no
# dense MLP, ReLU gating; the router reads the block's input.
SMALLTHINKER_21B_A3B = ModelConfig(
    name="smallthinker-21b-a3b",
    vocab_size=151_936,
    d_model=2560,
    n_layers=52,
    n_heads=28,
    n_kv_heads=4,
    d_ff=768,
    head_dim=128,
    rope_theta=1_500_000.0,
    norm_eps=1e-6,
    mlp_activation="relu",
    n_experts=64,
    n_experts_per_token=6,
    norm_topk_prob=True,
    layer_pattern=("nope", "window", "window", "window"),
    sliding_window=4096,
    router_pre_attention=True,
    max_seq_len=16_384,
    max_lora_slots=0,  # adapters are not served over a period-scanned stack
)

# The CPU's SmallThinker: two periods, a window of 16 (a 16-token chunk
# stream and a few decode steps cross it and wrap the ring), 7 queries over
# 1 kv head kept as a ratio (here 4 over 2), 8 experts top-3.
TINY_SMALLTHINKER_TEST = replace(
    SMALLTHINKER_21B_A3B, name="smallthinker-tiny", vocab_size=320,
    d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32,
    n_experts=8, n_experts_per_token=3, sliding_window=16, max_seq_len=128,
    max_lora_rank=4)

# LiquidAI/LFM2-24B-A2B (``lfm2_moe``): layer l is attention iff l % 4 == 2
# (32 query / 8 kv heads of 64, a per-head QK-norm, RoPE), else a gated short
# convolution of 3 taps; layers 0-1 a dense MLP of 11,776, the others 64
# experts of 1,536, top-4 of sigmoid score + bias, renormalised; the head is
# tied to the embedding.  Two 64-wide kv heads share a cache row.
LFM2_24B_A2B = ModelConfig(
    name="lfm2-24b-a2b",
    vocab_size=65_536,
    d_model=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    d_ff=11_776,
    head_dim=64,
    rope_theta=1_000_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    n_experts=64,
    n_experts_per_token=4,
    norm_topk_prob=True,
    qk_norm_head=True,
    moe_d_ff=1536,
    first_k_dense=2,
    router_sigmoid=True,
    routed_scaling_factor=1.0,
    router_gate_eps=1e-6,
    layer_pattern=("conv", "conv", "full", "conv"),
    conv_kernel=3,
    max_seq_len=128_000,
    max_lora_slots=0,  # adapters are not served over a period-scanned stack
)

# The CPU's LFM2: 2 dense layers and 2 periods, 4 queries over 2 kv heads of
# 16 (a packed row of 32), 8 experts top-2.
TINY_LFM2_TEST = replace(
    LFM2_24B_A2B, name="lfm2-tiny", vocab_size=320, d_model=64, n_layers=10,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, moe_d_ff=32, n_experts=8,
    n_experts_per_token=2, max_seq_len=256, max_lora_rank=4)

# inclusionAI/Ling-3.0-flash (``bailing_hybrid``): layer l is latent attention
# (MLA: no query bottleneck, 32 heads of 128 + 64 / 128, a head-wise output
# gate) iff (l + 1) % 6 == 0 and Kimi Delta Attention otherwise (32 heads, a
# 128 x 128 float32 state a head, three short convolutions of 4 taps, a
# per-channel decay in (-5, 0)); layers 0-1 a dense MLP of 6,144, the others
# 512 experts of 768 in 8 groups (top-8 of the 4 best groups by sigmoid score
# + bias, gates renormalised and scaled by 2.5) and one shared expert.  The
# multi-token-prediction layer is a draft head and is not part of the forward.
LING_3_FLASH = ModelConfig(
    name="ling-3.0-flash",
    vocab_size=157_184,
    d_model=2560,
    n_layers=42,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6144,
    head_dim=128,
    rope_theta=6_000_000.0,
    norm_eps=1e-6,
    n_experts=512,
    n_experts_per_token=8,
    norm_topk_prob=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe_d_ff=768,
    n_shared_experts=1,
    first_k_dense=2,
    router_sigmoid=True,
    routed_scaling_factor=2.5,
    n_group=8,
    topk_group=4,
    layer_pattern=("kda", "kda", "kda", "kda", "kda", "mla"),
    kda_n_heads=32,
    kda_head_dim=128,
    kda_conv=4,
    kda_lower_bound=-5.0,
    mla_head_gate=True,
    max_seq_len=262_144,
    max_lora_slots=0,  # adapters are not served over a period-scanned stack
)

# The CPU's Ling: 2 dense layers and one rotated period (a depth of 12, the
# cell's, leaves four more layers over: the tests build it from this), 32
# experts in 4 groups of which the 8 of group 0 are held (one chip's share of
# four), top-4 of the 2 best groups, 2 KDA heads of 16, a latent of 40 + 8
# under 4 heads of 24 + 8 / 16.
TINY_LING_TEST = replace(
    LING_3_FLASH, name="ling-tiny", vocab_size=320, d_model=64, n_layers=8,
    n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, moe_d_ff=32,
    n_experts=32, n_experts_local=8, n_experts_per_token=4, n_group=4,
    topk_group=2, kv_lora_rank=40, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, kda_n_heads=2, kda_head_dim=16, max_seq_len=256,
    max_lora_rank=4)

TINY_TEST = LLAMA3_8B.tiny()
TINY_MOE_TEST = MIXTRAL_8X7B.tiny()
TINY_QWEN_TEST = QWEN2_5_7B.tiny()
TINY_OLMOE_TEST = OLMOE_1B_7B.tiny()  # keeps 64 experts, top-8
