"""The plain reference forward: the architecture's equations in ``jax.numpy``
and float32, nothing else.  What the serving path is checked against
(``tests/test_reference_parity.py`` at tiny size on the CPU;
``benchmark/reference_check.py`` at the published widths on the chip).

No cache, no kernels, no batching, no scan: one sequence, a Python loop over
layers, every expert computed for every token and mixed by the gate rule.
It imports nothing from ``transformer.py``, ``ops/`` or ``lora.py``, so a
fault there cannot hide in both.  It runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
computed in bf16 passes otherwise.

The equations, for a pre-norm decoder block on ``x`` [S, D] at positions
0..S-1 (``RMSNorm_w(z) = z / sqrt(mean(z^2) + eps) * w``):

    x_n = RMSNorm(x)
    q = x_n Wq (+ bq),  k = x_n Wk (+ bk),  v = x_n Wv (+ bv)      Qwen2: biases
    q = RMSNorm_q(q),   k = RMSNorm_k(k)    over the WHOLE vector  OLMoE: QK-norm
    q, k = RoPE(q), RoPE(k)     per head, rotate-half, full head width, theta
    a = softmax(q k^T / sqrt(hd) + causal mask) v     kv head = q head // group
    x = x + a Wo
    h_n = RMSNorm(x)
    dense:   x = x + (silu(h_n Wg) * (h_n Wu)) Wd
    sparse:  p = softmax(h_n Wr) over all E, in float32; the k largest p are
             the experts; weights p_i as they are (OLMoE, norm_topk_prob
             false) or p_i / sum of the chosen (Mixtral);
             x = x + sum_i w_i * (silu(h_n Wg_i) * (h_n Wu_i)) Wd_i
    logits = RMSNorm(x) W_head

Latent attention (GLM-4.7-Flash, ``kv_lora_rank`` > 0), the expanded form:

    c_q = RMSNorm_q(x_n Wqa);  q_h = c_q Wqb,h = [q_nope_h | q_rope_h]
    [c_kv | k_r] = x_n Wkva;  c = RMSNorm_kv(c_kv);  k_rope = RoPE(k_r), ONE
    key for all heads;  q_rope_h = RoPE(q_rope_h)
    [k_nope_h | v_h] = c Wkvb,h
    a_h = softmax(([q_nope_h | q_rope_h] [k_nope_h | k_rope]^T)
                  / sqrt(nope + rope) + causal mask) v_h;  x = x + concat(a) Wo

and its sparse layers (the first ``first_k_dense`` layers keep the dense MLP):

    s = sigmoid(h_n Wr) over all E, in float32; the experts are the k
    largest of s + b (the selection bias); g_i = scale * s_i / (sum of the
    chosen s + 1e-20): b picks and never weighs;
    x = x + sum_i g_i E_i(h_n) + S(h_n),  S the shared expert

Falcon-H1 (``falcon_h1``, ``ssm_d_inner`` > 0): a PARALLEL block, attention
and a state-space mixer (Mamba-2 form) both on x_n, their outputs added to
the residual together, and fixed scalar multipliers (1 for the models above):

    x0 = E[token] * embedding_multiplier
    q, k, v = (x_n * attention_in_multiplier) Wq, Wk, Wv;  k = k * key_multiplier
    a = attention as above;  a = (a Wo) * attention_out_multiplier
    [z | xBC | dt] = ((x_n * ssm_in_multiplier) W_in) * m
        m = ssm_multipliers over the parts z [d_inner], x [d_inner],
        B [groups * d_state], C [groups * d_state], dt [heads], in this order
    xBC_t = silu(sum_j w_conv[j] * xBC_{t-(K-1)+j} + b_conv)   causal,
        depthwise, K = ssm_d_conv taps, zeros before position 0
    x | B | C = xBC:  x [heads, head_dim];  B, C [groups, d_state];
        head h reads group h // (heads / groups)
    dt_t = softplus(dt_t + dt_bias) (no clamp);  A = -exp(A_log)
    H_t = exp(dt_t A) * H_{t-1} + dt_t * x_t (outer) B_t,   H_{-1} = 0,
        one position after another
    y_t = H_t C_t + D * x_t
    y = RMSNorm_w(y * silu(z)) with the mean of squares over each group's
        d_inner / groups columns (the gate first: norm_before_gate false)
    s = (y W_out) * ssm_out_multiplier;   x = x + a + s
    x = x + ((silu((h_n Wg) * mlp_multipliers[0]) * (h_n Wu)) Wd) * mlp_multipliers[1]
    logits = (RMSNorm(x) W_head) * lm_head_multiplier

SmallThinker (``smallthinker``, ``layer_pattern``): a stack that repeats a
period of layer kinds, a router that reads the block's INPUT, ReLU gating:

    r = x Wr                        router logits, from x BEFORE the norm
    layer kind "window":  q, k = RoPE(q), RoPE(k);  i attends j iff
                          0 <= i - j < sliding_window
    layer kind "nope":    no position encoding at all; i attends j iff j <= i
    p = softmax(r) over all E; the k largest are the experts, w_i = p_i /
        sum of the chosen (norm_topk_prob)
    x = x + sum_i w_i * (relu(h_n Wg_i) * (h_n Wu_i)) Wd_i

LFM2 (``lfm2_moe``, ``conv_kernel`` > 0): layer l of the MODEL is of the kind
``layer_pattern[l % period]``; a "conv" layer has no attention but a gated
short convolution; the attention layers' QK-norm is per head; the head is
the embedding transposed:

    kind "conv":  [B | C | u] = x_n W_in (split in this order);  z_t = B_t * u_t
                  c_t = sum_j w[j] * z_{t-(K-1)+j}    K = conv_kernel taps,
                      depthwise, causal, z = 0 before position 0, no bias, no
                      activation: an explicit sum over K shifted copies
                  x = x + (C_t * c_t) W_out
    kind "full":  q_h = RMSNorm_g_q(q_h), k_h = RMSNorm_g_k(k_h) over the hd
                  numbers of EACH head (one g of hd for all heads), before
                  RoPE; the rest as above
    sparse:       GLM's rule, g_i = scale * s_i / (sum of the chosen s +
                  router_gate_eps) (1e-6), no shared expert
    logits = RMSNorm(x) E^T
    The leaves of the attention lie stacked over the attention layers of
    their group alone, the conv operator's over its conv layers.

Ling-3.0-flash (``bailing_hybrid``, ``kda_n_heads`` > 0): layer l of the MODEL
is of the kind ``layer_pattern[l % period]``, "kda" or "mla":

    kind "kda":   [q^ | k^ | v^ | f | z] = x_n W_in (split in this order)
                  q-, k-, v- = silu(conv(.)), each a causal depthwise conv of
                      ``kda_conv`` taps, zeros before position 0, no bias
                  q = dk^(-1/2) q- / |q-|,  k = k- / |k-|   per head, eps 1e-6
                  g = bound * sigmoid(exp(A_log) * (f + dt_bias))  per channel
                  beta = sigmoid(x_n w_b)                     per head
                  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                        + beta_t k_t v_t^T;   o_t = S_t^T q_t,  S_{-1} = 0,
                      one position after another
                  x = x + [RMSNorm_w(o_t) per head * sigmoid(z_t)] W_out
    kind "mla":   the latent attention above with q = x_n W_q directly (no
                  bottleneck), a value head narrower than the query head, and
                  o_h <- sigmoid(x_n w_gate,h) * o_h before W_o
    sparse:       GLM's rule, but the experts lie in ``n_group`` groups, a
                  group scores the sum of its two largest s + b, and only the
                  ``topk_group`` best groups' experts can be chosen; under a
                  share (``n_experts_local``) the sum runs over the chosen
                  experts THIS program holds (``expert_first`` on), the gates
                  normalised over all chosen; the shared expert once
    The leaves of the latent attention lie stacked over the latent layers of
    their group alone, the KDA operator's over its KDA layers.

A LoRA adapter adds ``scale * (z A) B`` to a projection of ``z``.

Departures from the published descriptions, each on purpose:

- one layer's weights at a time: ``params`` is the program's stacked tree,
  and a layer is sliced (and an int8 leaf dequantised) inside the loop, so
  the float32 copy of a 7 B model never exists;
- an int8 leaf ``{"q", "s"}`` is read as the weight ``q * s`` (per output
  channel): the reference checks the program's arithmetic on the weights it
  serves, not the quantisation's distance from some bf16 original;
- every expert is computed for every token and the unchosen get weight 0,
  instead of a dispatch: the same sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _weight(leaf, layer: int | None = None):
    """A leaf of the program's tree as a float32 matrix: one layer of a
    stacked leaf, an int8 ``{"q", "s"}`` pair dequantised."""
    if isinstance(leaf, dict):
        q, s = leaf["q"], leaf["s"]
        if layer is not None:
            q, s = q[layer], s[layer]
        return q.astype(F32) * s.astype(F32)[..., None, :]
    return (leaf if layer is None else leaf[layer]).astype(F32)


def _rms_norm(z, w, eps):
    return z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps) * w


def _rope(z, theta):
    """z [S, heads, hd] at positions 0..S-1: rotate-half over the full head."""
    s, _, hd = z.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv  # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    z1, z2 = z[..., : hd // 2], z[..., hd // 2:]
    return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], axis=-1)


def _lora(z, lora, layer, target):
    if lora is None:
        return 0.0
    bufs, slot = lora
    a = bufs[f"{target}_a"][layer, slot].astype(F32)
    b = bufs[f"{target}_b"][layer, slot].astype(F32)
    return bufs["scale"][slot].astype(F32) * ((z @ a) @ b)


def _pattern(cfg, layer):
    """The kind of layer ``layer`` of the MODEL, by name."""
    pattern = cfg.layer_pattern or ("full",)
    return pattern[layer % len(pattern)]


def _kind(cfg, layer):
    """(window, rope) of layer ``layer``: 0 = every earlier position."""
    name = _pattern(cfg, layer)
    return (cfg.sliding_window if name == "window" else 0), name != "nope"


def _short_conv(cfg, lp, layer, x_n, states=None):
    """LFM2's gated short convolution; ``layer`` counts the group's conv
    layers.  ``states``, a list, gets the layer's z of the last K - 1
    positions [K - 1, D] appended (zeros before position 0)."""
    s, taps = x_n.shape[0], cfg.conv_kernel
    b, c, u = jnp.split(x_n @ _weight(lp["conv_in"], layer), 3, axis=-1)
    z = b * u
    w = lp["conv_w"][layer].astype(F32)  # [taps, D]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), F32), z])
    conv = sum(w[j] * padded[j:j + s] for j in range(taps))
    if states is not None:
        states.append(padded[s:])
    return (c * conv) @ _weight(lp["conv_out"], layer)


def _kda(cfg, lp, layer, x_n, states=None):
    """Kimi Delta Attention, position by position; ``layer`` counts the
    group's KDA layers.  ``states``, a list, gets the layer's (S [heads,
    dk, dv] after the last position, the convs' last K - 1 inputs
    [K - 1, 3 inner]) appended."""
    s, taps = x_n.shape[0], cfg.kda_conv
    h, dk = cfg.kda_n_heads, cfg.kda_head_dim
    inner = h * dk
    proj = x_n @ _weight(lp["kda_in"], layer)
    qkv, f, z = proj[:, :3 * inner], proj[:, 3 * inner:4 * inner], proj[:, 4 * inner:]
    w = lp["kda_conv_w"][layer].astype(F32)  # [taps, 3 inner]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * inner), F32), qkv])
    conv = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))
    q, k, v = (t.reshape(s, h, dk) for t in jnp.split(conv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    a = jnp.exp(lp["kda_a_log"][layer].astype(F32))[:, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        a * (f + lp["kda_dt_bias"][layer].astype(F32)).reshape(s, h, dk))
    beta = jax.nn.sigmoid(x_n @ lp["kda_beta"][layer].astype(F32))  # [S, h]
    state = jnp.zeros((h, dk, dk), F32)
    outs = []
    for t in range(s):
        state = jnp.exp(g[t])[:, :, None] * state
        delta = beta[t][:, None] * (
            v[t] - jnp.einsum("hd,hdv->hv", k[t], state))
        state = state + k[t][:, :, None] * delta[:, None, :]
        outs.append(jnp.einsum("hd,hdv->hv", q[t], state))
    if states is not None:
        states.append((state, padded[s:]))
    o = jnp.stack(outs)  # [S, h, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * lp["kda_norm"][layer].astype(F32)
    return (o.reshape(s, inner) * jax.nn.sigmoid(z)) @ _weight(
        lp["kda_out"], layer)


def _attention(cfg, lp, layer, x_n, lora, model_layer=None):
    """``layer`` counts the leaves' stack; ``model_layer`` (where they
    differ: a model whose attention leaves skip its conv layers) names the
    layer's kind."""
    s = x_n.shape[0]
    window, rope = _kind(cfg, layer if model_layer is None else model_layer)
    x_n = x_n * cfg.attention_in_multiplier
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    proj = {}
    for t in ("q", "k", "v"):
        z = x_n @ _weight(lp[f"w{t}"], layer) + _lora(x_n, lora, layer, t)
        if cfg.attention_bias:
            z = z + lp[f"w{t}_b"][layer].astype(F32)
        if cfg.qk_norm and t != "v":
            z = _rms_norm(z, lp[f"{t}_norm"][layer].astype(F32), cfg.norm_eps)
        proj[t] = z * cfg.key_multiplier if t == "k" else z
    q = proj["q"].reshape(s, cfg.n_heads, hd)
    k = proj["k"].reshape(s, cfg.n_kv_heads, hd)
    if cfg.qk_norm_head:
        q = _rms_norm(q, lp["q_norm"][layer].astype(F32), cfg.norm_eps)
        k = _rms_norm(k, lp["k_norm"][layer].astype(F32), cfg.norm_eps)
    if rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    v = proj["v"].reshape(s, cfg.n_kv_heads, hd)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(F32(hd))
    behind = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (behind >= 0) & (behind < window) if window else behind >= 0
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, v).reshape(s, -1)
    return ((a @ _weight(lp["wo"], layer) + _lora(a, lora, layer, "o"))
            * cfg.attention_out_multiplier)


def _mixer(cfg, lp, layer, x_n, states=None):
    """The state-space branch of a Falcon-H1 block, position by position.
    ``states``, a list, gets the layer's last ``H`` [heads, head_dim,
    d_state] appended."""
    s = x_n.shape[0]
    heads, hd, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state
    groups, taps, di = cfg.ssm_n_groups, cfg.ssm_d_conv, cfg.ssm_d_inner
    gn = groups * n
    m = jnp.concatenate([jnp.full((w,), mult, F32) for w, mult in zip(
        (di, di, gn, gn, heads), cfg.ssm_multipliers)])
    proj = ((x_n * cfg.ssm_in_multiplier) @ _weight(lp["ssm_in"], layer)) * m
    z, xbc, dt = proj[:, :di], proj[:, di:di + di + 2 * gn], proj[:, -heads:]
    w = lp["ssm_conv_w"][layer].astype(F32)  # [taps, channels]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps))
                      + lp["ssm_conv_b"][layer].astype(F32))
    x = xbc[:, :di].reshape(s, heads, hd)
    b = jnp.repeat(xbc[:, di:di + gn].reshape(s, groups, n),
                   heads // groups, axis=1)  # [S, heads, d_state]
    c = jnp.repeat(xbc[:, di + gn:].reshape(s, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"][layer].astype(F32))
    a = -jnp.exp(lp["ssm_a_log"][layer].astype(F32))
    state = jnp.zeros((heads, hd, n), F32)
    ys = []
    for t in range(s):
        state = (jnp.exp(dt[t] * a)[:, None, None] * state
                 + dt[t][:, None, None] * x[t][:, :, None] * b[t][:, None, :])
        ys.append(jnp.sum(state * c[t][:, None, :], axis=-1))
    if states is not None:
        states.append(state)
    y = jnp.stack(ys) + lp["ssm_d"][layer].astype(F32)[:, None] * x
    y = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, groups, di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = y.reshape(s, di) * lp["ssm_norm"][layer].astype(F32)
    return (y @ _weight(lp["ssm_out"], layer)) * cfg.ssm_out_multiplier


def _latent_attention(cfg, lp, layer, x_n):
    s = x_n.shape[0]
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    if cfg.q_lora_rank:
        c_q = _rms_norm(x_n @ _weight(lp["wq_down"], layer),
                        lp["q_latent_norm"][layer].astype(F32), cfg.norm_eps)
        q = c_q @ _weight(lp["wq_up"], layer)
    else:  # no bottleneck
        q = x_n @ _weight(lp["wq"], layer)
    q = q.reshape(s, h, nope + rope)
    ckv = x_n @ _weight(lp["wkv_down"], layer)
    c = _rms_norm(ckv[:, :rank], lp["kv_latent_norm"][layer].astype(F32),
                  cfg.norm_eps)
    k_rope = _rope(ckv[:, None, rank:], cfg.rope_theta)  # [S, 1, rope]
    q_rope = _rope(q[..., nope:], cfg.rope_theta)
    kv = (c @ _weight(lp["wkv_up"], layer)).reshape(s, h, nope + vd)
    scores = (jnp.einsum("ihd,jhd->hij", q[..., :nope], kv[..., :nope])
              + jnp.einsum("ihd,jd->hij", q_rope, k_rope[:, 0])
              ) / jnp.sqrt(F32(nope + rope))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, kv[..., nope:])
    if cfg.mla_head_gate:
        a = a * jax.nn.sigmoid(
            x_n @ lp["w_head_gate"][layer].astype(F32))[..., None]
    return a.reshape(s, -1) @ _weight(lp["wo"], layer)


_ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(z, wg, wu, wd, act="silu"):
    return (_ACT[act](z @ wg) * (z @ wu)) @ wd


def _mlp(cfg, lp, layer, h_n, lora, x=None):
    """``x``: the block's input, which the router of a
    ``router_pre_attention`` model reads in ``h_n``'s place."""
    if "router" not in lp:  # a dense model, or a leading dense layer
        gate = h_n @ _weight(lp["w_gate"], layer) + _lora(h_n, lora, layer, "gate")
        up = h_n @ _weight(lp["w_up"], layer) + _lora(h_n, lora, layer, "up")
        act = _ACT[cfg.mlp_activation](gate * cfg.mlp_multipliers[0]) * up
        return ((act @ _weight(lp["w_down"], layer)
                 + _lora(act, lora, layer, "down")) * cfg.mlp_multipliers[1])
    router = ((x if cfg.router_pre_attention else h_n)
              @ lp["router"][layer].astype(F32))  # [S, E]
    if cfg.router_sigmoid:
        p = jax.nn.sigmoid(router)
        pick = p + lp["router_bias"][layer].astype(F32)
    else:
        p = pick = jax.nn.softmax(router, axis=-1)
    if cfg.n_group > 1:  # only the best groups' experts can be chosen
        groups = pick.reshape(pick.shape[0], cfg.n_group, -1)
        score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
        worst_kept = jnp.sort(score, axis=-1)[:, -cfg.topk_group][:, None]
        pick = jnp.where((score >= worst_kept)[..., None], groups,
                         -jnp.inf).reshape(pick.shape)
    kth = jnp.sort(pick, axis=-1)[:, -cfg.n_experts_per_token][:, None]
    w = jnp.where(pick >= kth, p, 0.0)
    if cfg.router_sigmoid:
        if cfg.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True)
                     + cfg.router_gate_eps)
        w = w * cfg.routed_scaling_factor
    elif cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    wg, wu, wd = (_weight(lp[n], layer) for n in ("w_gate", "w_up", "w_down"))
    y = jnp.zeros_like(h_n)
    # (a share: the stacks hold the experts from ``expert_first`` on)
    for e in range(wg.shape[0]):
        at = cfg.expert_first + e
        y = y + w[:, at: at + 1] * _gated(h_n, wg[e], wu[e], wd[e],
                                          cfg.mlp_activation)
    if cfg.n_shared_experts:
        y = y + _gated(h_n, *(_weight(lp[n], layer)
                              for n in ("ws_gate", "ws_up", "ws_down")),
                       cfg.mlp_activation)
    return y


def forward(cfg, params, tokens, lora=None, states=None):
    """Logits [S, V] (float32) of one sequence ``tokens`` [S] at positions
    0..S-1.  ``params``: the program's tree (``transformer.init_params``
    layout; int8 leaves allowed).  ``lora``: None, or ``(buffers, slot)``,
    the serving LoRA buffers and the slot whose adapter this sequence uses.
    ``states``: None, or a list that gets each layer's recurrent state after
    the last position (a model with a mixer) or each CONV layer's last
    K - 1 inputs z (a model with conv layers) or each KDA layer's (matrix
    states, conv history), to hold a cache's against.
    """
    if (cfg.embedding_scale or cfg.norm_plus_one
            or cfg.gelu_mlp or cfg.rope_scaling_factor):
        raise NotImplementedError(
            "the reference covers the Llama/Qwen2/Mixtral/OLMoE/GLM/Falcon-H1/"
            "SmallThinker/LFM2/Ling block; the "
            f"Gemma conventions and rope scaling of {cfg.name} are not in it")
    if (cfg.kv_lora_rank or cfg.ssm_d_inner or cfg.conv_kernel
            or cfg.kda_n_heads) and (
            lora is not None):
        raise NotImplementedError(
            "no adapter over latent projections, beside a mixer or over "
            "conv layers")
    # (stack, index in it) of every layer: leading dense layers, if the
    # model has them, lie in a stack of their own.
    n_dense = (params["dense_layers"]["attn_norm"].shape[0]
               if "dense_layers" in params else 0)
    stack = ([(params["dense_layers"], i) for i in range(n_dense)]
             + [(params["layers"], i)
                for i in range(cfg.n_layers - n_dense)])
    # A layer's place among the layers of ITS kind (conv, kda or attention)
    # of its group: where a model with such layers keeps that kind's leaves.
    of_kind, seen = [], {}
    for l, (lp, _) in enumerate(stack):
        key = (id(lp), _pattern(cfg, l) in ("conv", "kda")
               and _pattern(cfg, l))
        of_kind.append(seen.get(key, 0))
        seen[key] = of_kind[-1] + 1
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32) * cfg.embedding_multiplier
        for l, (lp, layer) in enumerate(stack):
            x_n = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            if _pattern(cfg, l) == "conv":
                branches = _short_conv(cfg, lp, of_kind[l], x_n, states)
            elif _pattern(cfg, l) == "kda":
                branches = _kda(cfg, lp, of_kind[l], x_n, states)
            elif cfg.kda_n_heads:
                branches = _latent_attention(cfg, lp, of_kind[l], x_n)
            elif cfg.conv_kernel:
                branches = _attention(cfg, lp, of_kind[l], x_n, lora, l)
            else:
                branches = (_latent_attention(cfg, lp, layer, x_n)
                            if cfg.kv_lora_rank
                            else _attention(cfg, lp, layer, x_n, lora))
            if cfg.ssm_d_inner:
                branches = branches + _mixer(cfg, lp, layer, x_n, states)
            block_in, x = x, x + branches
            h_n = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + _mlp(cfg, lp, layer, h_n, lora, block_in)
        x = _rms_norm(x, params["final_norm"].astype(F32), cfg.norm_eps)
        head = (params["embed"].astype(F32).T if cfg.tie_embeddings
                else _weight(params["lm_head"]))
        return (x @ head) * cfg.lm_head_multiplier
