"""The benchmark's own copy of the plain float32 reference forward for
Ling-3.0-flash (``model_type`` ``bailing_hybrid``; the equations of
``llm_instance_gateway_tpu/models/reference.py`` as of PR 60), kept under
``benchmark/`` so that what decides ``benchmark/reference_check_ling.py`` is
part of the yardstick: a later PR that changes the program's reference does
not change this one.  ``tests/benchmark/test_bench_ling.py`` holds the two to
equal logits on ``ling-tiny``.

float32 under ``jax.default_matmul_precision("highest")``, one sequence, a
Python loop over layers, the delta rule one position after another (a
``lax.scan`` over positions: the sequential recurrence, no chunked form), every
held expert computed for every token and mixed by the gate rule; no cache, no
kernel, no batching.  It imports nothing from ``transformer.py``, ``kda.py``,
``mla.py`` or ``ops/``.

For x [S, 2560] entering layer l of the MODEL at positions 0..S-1, every norm
an RMSNorm (eps 1e-6), no bias anywhere; H = 32 heads, d_k = d_v = 128:

    h = norm_op(x)
    (l + 1) % 6 != 0 (``layer_pattern[l % 6] == "kda"``):
        [q^ | k^ | v^ | f | z] = h W_in       W_in [2560, 5 x 4096], this order
        q-, k-, v- = silu(conv4(q^)), silu(conv4(k^)), silu(conv4(v^))
            y_t = sum_{i=0..3} w_i x_{t-3+i}, zeros before position 0, no bias
        q_t = 128^(-1/2) q-_t / |q-_t|,  k_t = k-_t / |k-_t|   per head, eps 1e-6
        g_t = -5 sigmoid(exp(A_log) * (f_t + dt_bias))   a vector of 128 a head
        beta_t = sigmoid(h_t w_b)                         a number a head
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                                   S [128, 128], S_{-1} = 0
        x = x + [RMSNorm_128(o_t; w) * sigmoid(z_t)] W_out
    else ("mla"):
        q = h W_q  [32 x (128 + 64)];  [c_kv | k_r] = h W_kva [512 + 64]
        c = RMSNorm(c_kv);  k_rope = RoPE(k_r), ONE key for all heads;
        q_rope = RoPE(q_rope), theta 6e6 over the 64 columns, rotate-half
        [k_nope_h | v_h] = c W_kvb,h  [128 + 128]
        a_h = softmax(([q_nope_h | q_rope_h] [k_nope_h | k_rope]^T) / sqrt(192)
                      + causal mask) v_h
        a_h = sigmoid(h w_gate,h) * a_h;   x = x + concat(a) W_o
    m = norm_ffn(x)
    l < 2:  x = x + (silu(m W1) * (m W3)) W2                       width 6,144
    else:   s = sigmoid(m Wr) [512];  p = s + b
            a group of 64 scores the sum of its two largest p; the 4 best of
            the 8 groups stay; E8 = top-8 of p among their 256 experts
            g = 2.5 s[E8] / (sum s[E8] + 1e-20)
            x = x + sum_{e in E8 held here} g_e E_e(m) + S(m)      width 768
            (a share: this program holds ``n_experts_local`` experts from
            ``expert_first`` on; the gates are normalised over all of E8)
    logits = norm(x) W_head       (a slice of the vocabulary: its columns)

The program's tree keeps the KDA operator's leaves stacked over the KDA layers
of their group alone and the latent attention's over its latent layers; the
two leading dense layers are a group of their own (``dense_layers``).

Departures, each on purpose: one layer's weights at a time, and within a
sparse layer one expert's at a time, dequantised inside the loop; an int8
leaf ``{"q", "s"}`` is read as ``q * s``, so the reference checks the
program's arithmetic on the weights it serves; the attention is computed
``block`` queries at a time, which changes no number; ``logits_from`` cuts
the head to the positions that are compared.  ``round_to`` as in
``benchmark/reference/olmoe.py``: with a dtype, whatever enters a matmul is
first rounded to it and widened again (the delta rule's state stays float32:
the configuration states it so).  ``states``, a list, gets every KDA layer's
S after each of ``state_ends`` positions [ends, 32, 128, 128] and every latent
layer's rows [S, 576] (the normed latent | the roped key), in layer order.
``wrong`` computes another function on purpose, for the check's readings:
``"no_bound"`` (Mamba's gate, -exp(A_log) softplus(f + dt_bias), in place of
the bounded one), ``"no_delta"`` (the write beta k v^T without what the state
already holds of v: gated linear attention), ``"share_renormalised"`` (gates
normalised over the chosen experts this program HOLDS), ``"bf16_state"`` (the
state rounded to bfloat16 after every position: on the chip it moves the
logits less than bf16 activations do, so the check reads it and holds it to
nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_bound", "no_delta", "share_renormalised", "bf16_state")
L2_EPS = 1e-6


def _weight(leaf, *index):
    """``leaf[index]`` of the program's tree as a float32 matrix, an int8
    ``{"q", "s"}`` pair dequantised per output channel."""
    if isinstance(leaf, dict):
        q, s = leaf["q"][index], leaf["s"][index]
        return q.astype(F32) * s.astype(F32)[..., None, :]
    return leaf[index].astype(F32)


def _rounder(dtype):
    if dtype is None:
        return lambda z: z
    return lambda z: z.astype(dtype).astype(F32)


def _rms_norm(z, w, eps):
    return z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps) * w


def _rope(z, theta):
    """z [S, heads, hd] at positions 0..S-1: rotate-half over the full head."""
    s, _, hd = z.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    z1, z2 = z[..., : hd // 2], z[..., hd // 2:]
    return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("delta", "low"))
def _recur(state, q, k, v, g, beta, delta: bool = True, low: bool = False):
    """The delta rule over the positions of ``q`` .. ``beta`` from ``state``
    [H, dk, dv] on, one after another.  Returns (o [S, H, dv], the state
    after the last).  Sums over d_k, no matmul."""
    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[:, :, None] * s
        seen = jnp.sum(kt[:, :, None] * s, axis=1) if delta else 0.0
        s = s + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        if low:  # (reduce_precision: inside one program XLA may drop a
            # convert to bfloat16 and back as excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(qt[:, :, None] * s, axis=1)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _kda(cfg, lp, layer, h, _r, states, ends, wrong):
    s, taps = h.shape[0], cfg.kda_conv
    n, dk = cfg.kda_n_heads, cfg.kda_head_dim
    inner = n * dk
    proj = _r(h) @ _weight(lp["kda_in"], layer)
    qkv, f, z = (proj[:, :3 * inner], proj[:, 3 * inner:4 * inner],
                 proj[:, 4 * inner:])
    w = lp["kda_conv_w"][layer].astype(F32)  # [taps, 3 inner]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * inner), F32), qkv])
    conv = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))
    q, k, v = (t.reshape(s, n, dk) for t in jnp.split(conv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    a = jnp.exp(lp["kda_a_log"][layer].astype(F32))[:, None]
    raw = (f + lp["kda_dt_bias"][layer].astype(F32)).reshape(s, n, dk)
    if wrong == "no_bound":
        g = -a * jax.nn.softplus(raw)
    else:
        g = cfg.kda_lower_bound * jax.nn.sigmoid(a * raw)
    beta = jax.nn.sigmoid(_r(h) @ lp["kda_beta"][layer].astype(F32))  # [S, n]
    state = jnp.zeros((n, dk, dk), F32)
    outs, kept, at = [], [], 0
    for end in sorted({*(ends or ()), s}):  # in segments, the state kept
        if end > at:
            o, state = _recur(state, *(t[at:end] for t in (q, k, v, g, beta)),
                              delta=wrong != "no_delta",
                              low=wrong == "bf16_state")
            outs.append(o)
            at = end
        if end in (ends or (s,)):
            kept.append(state)
    if states is not None:
        states.append(jnp.stack(kept))
    o = jnp.concatenate(outs)  # [S, n, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * lp["kda_norm"][layer].astype(F32)
    return _r(o.reshape(s, inner) * jax.nn.sigmoid(z)) @ _weight(
        lp["kda_out"], layer)


def _latent_attention(cfg, lp, layer, h, _r, block, states):
    s = h.shape[0]
    n, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    h = _r(h)
    q = (h @ _weight(lp["wq"], layer)).reshape(s, n, nope + rope)
    ckv = h @ _weight(lp["wkv_down"], layer)
    c = _rms_norm(ckv[:, :rank], lp["kv_latent_norm"][layer].astype(F32),
                  cfg.norm_eps)
    k_rope = _rope(ckv[:, None, rank:], cfg.rope_theta)[:, 0]  # [S, rope]
    if states is not None:
        states.append(jnp.concatenate([c, k_rope], axis=-1))
    q_rope = _r(_rope(q[..., nope:], cfg.rope_theta))
    q_nope = _r(q[..., :nope])
    kv = (_r(c) @ _weight(lp["wkv_up"], layer)).reshape(s, n, nope + vd)
    k_nope, v, k_rope = _r(kv[..., :nope]), _r(kv[..., nope:]), _r(k_rope)
    out = []
    for start in range(0, s, block):
        i = jnp.arange(start, min(s, start + block))
        rows = slice(int(i[0]), int(i[-1]) + 1)
        scores = (jnp.einsum("ihd,jhd->hij", q_nope[rows], k_nope)
                  + jnp.einsum("ihd,jd->hij", q_rope[rows], k_rope)
                  ) / jnp.sqrt(F32(nope + rope))
        seen = i[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hij,jhd->ihd", _r(probs), v))
    a = jnp.concatenate(out)  # [S, n, vd]
    a = a * jax.nn.sigmoid(h @ lp["w_head_gate"][layer].astype(F32))[..., None]
    return _r(a).reshape(s, -1) @ _weight(lp["wo"], layer)


def _gated(m, wg, wu, wd, _r):
    return _r(jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def _dense(lp, layer, m, _r):
    return _gated(_r(m), *(_weight(lp[n], layer)
                           for n in ("w_gate", "w_up", "w_down")), _r)


def gates(cfg, scores, bias, wrong=None):
    """The router's weights [S, E] from its sigmoid ``scores`` and the
    selection ``bias``: zero for an expert not chosen."""
    pick = scores + bias
    groups = pick.reshape(pick.shape[0], cfg.n_group, -1)
    score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)  # [S, G]
    worst_kept = jnp.sort(score, axis=-1)[:, -cfg.topk_group][:, None]
    pick = jnp.where((score >= worst_kept)[..., None], groups,
                     -jnp.inf).reshape(pick.shape)
    kth = jnp.sort(pick, axis=-1)[:, -cfg.n_experts_per_token][:, None]
    w = jnp.where(pick >= kth, scores, 0.0)
    if wrong == "share_renormalised":
        held = jnp.arange(w.shape[1]) - cfg.expert_first
        w = jnp.where((held >= 0) & (held < (cfg.n_experts_local
                                             or cfg.n_experts)), w, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_gate_eps)
    return w * cfg.routed_scaling_factor


def _experts(cfg, lp, layer, m, _r, wrong):
    s = jax.nn.sigmoid(_r(m) @ lp["router"][layer].astype(F32))  # [S, 512]
    w = gates(cfg, s, lp["router_bias"][layer].astype(F32), wrong)
    m = _r(m)
    y = _gated(m, *(_weight(lp[n], layer)
                    for n in ("ws_gate", "ws_up", "ws_down")), _r)
    held = lp["w_gate"]["q"] if isinstance(lp["w_gate"], dict) else lp["w_gate"]
    for e in range(held.shape[1]):  # the experts this program holds
        at = cfg.expert_first + e
        y = y + w[:, at: at + 1] * _gated(
            m, *(_weight(lp[n], layer, e)
                 for n in ("w_gate", "w_up", "w_down")), _r)
    return y


def forward(cfg, params, tokens, round_to=None, logits_from: int = 0,
            wrong: str | None = None, block: int = 512, states=None,
            state_ends: tuple = ()):
    """Logits [S - logits_from, V] (float32) of one sequence ``tokens`` [S]
    at positions 0..S-1, from position ``logits_from`` on.  ``params``: the
    program's tree (``transformer.init_params`` layout; int8 leaves
    allowed)."""
    if not (cfg.kda_n_heads and cfg.kv_lora_rank and cfg.router_sigmoid
            and cfg.norm_topk_prob and cfg.mla_head_gate
            and not cfg.q_lora_rank and not cfg.tie_embeddings):
        raise NotImplementedError(f"{cfg.name} is not a bailing_hybrid model")
    if wrong not in (None, *WRONG):
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    _r = _rounder(round_to)
    n_dense = cfg.first_k_dense
    seen = {}  # (group, kind) -> layers of the kind met in the group
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for l in range(cfg.n_layers):
            dense = l < n_dense
            lp = params["dense_layers" if dense else "layers"]
            layer = l if dense else l - n_dense  # its place in its group
            kind = cfg.layer_pattern[l % len(cfg.layer_pattern)]
            own = seen.get((dense, kind), 0)     # ... among its own kind
            seen[dense, kind] = own + 1
            h = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            if kind == "kda":
                x = x + _kda(cfg, lp, own, h, _r, states, state_ends, wrong)
            else:
                x = x + _latent_attention(cfg, lp, own, h, _r, block, states)
            m = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + (_dense(lp, layer, m, _r) if dense
                     else _experts(cfg, lp, layer, m, _r, wrong))
        x = _r(_rms_norm(x[logits_from:], params["final_norm"].astype(F32),
                         cfg.norm_eps))
        return x @ _weight(params["lm_head"])
