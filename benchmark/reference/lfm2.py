"""The benchmark's own copy of the plain float32 reference forward for LFM2
(``model_type`` ``lfm2_moe``; the equations of
``llm_instance_gateway_tpu/models/reference.py`` as of PR 54), kept under
``benchmark/`` so that what decides ``benchmark/reference_check_lfm2.py`` is
part of the yardstick: a later PR that changes the program's reference does
not change this one.  ``tests/benchmark/test_bench_hybrid.py`` holds the two
to equal logits on ``lfm2-tiny``.

float32 under ``jax.default_matmul_precision("highest")``, one sequence, a
Python loop over layers, all 64 experts computed for every token and mixed by
the gate rule, the conv an explicit sum over three shifted copies; no cache,
no conv state, no kernel, no batching.  It imports nothing from
``transformer.py``, ``shortconv.py`` or ``ops/``.

For x [S, 2048] entering layer l of the MODEL at positions 0..S-1, every norm
an RMSNorm (eps 1e-5), no bias anywhere:

    h = norm_op(x)
    l % 4 == 2 (``layer_pattern[l % 4] == "full"``):
        q, k, v = h Wq, h Wk, h Wv        32 / 8 / 8 heads x 64
        q_h = RMSNorm_g_q(q_h), k_h = RMSNorm_g_k(k_h)   over the 64 numbers
                     of EACH head, one g of 64 for all heads, before RoPE
        q, k = RoPE(q), RoPE(k), theta 1e6 over all 64 columns, rotate-half
        a = softmax(q k^T / sqrt(64) + causal mask) v;   x = x + a Wo
    else ("conv", 3 taps, no bias, no activation):
        [B | C | u] = h W_in              W_in [2048, 6144], split in this order
        z_t = B_t * u_t
        c_t = w_0 z_(t-2) + w_1 z_(t-1) + w_2 z_t        z = 0 before position 0
        x = x + (C_t * c_t) W_out
    m = norm_ffn(x)
    l < 2:  x = x + (silu(m W1) * (m W3)) W2                     width 11,776
    else:   s = sigmoid(m Wr) [64];  E4 = top-4 of (s + b)
            g = s[E4] / (sum s[E4] + 1e-6) * routed_scaling_factor (1)
            x = x + sum_{e in E4} g_e (silu(m Wg_e) * (m Wu_e)) Wd_e    width 1,536
    logits = norm(x) E^T                  the head is the embedding (tied)

The program's tree keeps the attention's leaves stacked over the attention
layers of their group alone and the conv operator's over its conv layers;
the two leading dense layers are a group of their own (``dense_layers``).

Departures, each on purpose: one layer's weights at a time, and within a
sparse layer one expert's at a time, dequantised inside the loop; an int8
leaf ``{"q", "s"}`` is read as ``q * s``, so the reference checks the
program's arithmetic on the weights it serves; the attention is computed
``block`` queries at a time, which changes no number; ``logits_from`` cuts
the head to the positions that are compared.  ``round_to`` as in
``benchmark/reference/olmoe.py``: with a dtype, whatever enters a matmul is
first rounded to it and widened again.  ``states``, a list, gets every conv
layer's z of the two positions before each of ``state_ends`` [ends, 2, 2048]
in layer order (default: the sequence's end).  ``wrong``
computes another function on purpose, for the check's readings:
``"state_dropped"`` (z before every multiple of ``chunk`` positions read as
zeros: a chunk stream that forgets the state at its edges),
``"no_qk_norm"`` (q and k go into RoPE as projected), ``"bf16_conv"`` (the
conv's products and sums, and B * u and C * c, rounded to bfloat16 one by
one where the reference keeps float32).  ``conv_sum`` is the conv operator
alone, for the check's reading of the program's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("state_dropped", "no_qk_norm", "bf16_conv")


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


def _attention(cfg, lp, layer, h, _r, block, qk_norm=True):
    s, hd = h.shape[0], cfg.head_dim
    h = _r(h)
    q = (h @ _weight(lp["wq"], layer)).reshape(s, cfg.n_heads, hd)
    k = (h @ _weight(lp["wk"], layer)).reshape(s, cfg.n_kv_heads, hd)
    v = (h @ _weight(lp["wv"], layer)).reshape(s, cfg.n_kv_heads, hd)
    if qk_norm:
        q = _rms_norm(q, lp["q_norm"][layer].astype(F32), cfg.norm_eps)
        k = _rms_norm(k, lp["k_norm"][layer].astype(F32), cfg.norm_eps)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = _r(jnp.repeat(k, group, axis=1)), _r(jnp.repeat(v, group, axis=1))
    q = _r(q)
    out = []
    for start in range(0, s, block):
        i = jnp.arange(start, min(s, start + block))
        scores = jnp.einsum("ihd,jhd->hij", q[i[0]:i[-1] + 1], k
                            ) / jnp.sqrt(F32(hd))
        seen = i[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hij,jhd->ihd", _r(probs), v))
    a = _r(jnp.concatenate(out)).reshape(s, -1)
    return a @ _weight(lp["wo"], layer)


def conv_sum(w, padded, c, each=lambda z: z):
    """C * (the causal depthwise conv of z) from ``padded`` [S + K - 1, D]
    (its first K - 1 rows what came before) and ``c`` [S, D]: an explicit
    sum over K shifted copies.  ``each`` rounds every product and partial
    sum (the wrong function ``"bf16_conv"``; the reference rounds none)."""
    s = c.shape[0]
    acc = each(w[0] * padded[0:s])
    for j in range(1, w.shape[0]):
        acc = each(acc + each(w[j] * padded[j:j + s]))
    return each(c * acc)


def _short_conv(cfg, lp, layer, h, _r, states, ends, wrong, chunk):
    s, taps = h.shape[0], cfg.conv_kernel
    each = _rounder(jnp.bfloat16) if wrong == "bf16_conv" else (lambda z: z)
    b, c, u = jnp.split(_r(h) @ _weight(lp["conv_in"], layer), 3, axis=-1)
    z = each(b * u)
    w = lp["conv_w"][layer].astype(F32)  # [taps, D]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), F32), z])
    if states is not None:  # z of positions e - 2 and e - 1 lies at e, e + 1
        states.append(jnp.stack([padded[e:e + taps - 1]
                                 for e in ends or (s,)]))
    if wrong == "state_dropped":
        # position t reads z of t - j as zeros where t - j lies in the
        # chunk before t's: every chunk starts from an empty state
        t = jnp.arange(s)[:, None]
        y = 0.0
        for j in range(taps):
            back = taps - 1 - j
            kept = (t - back) // chunk == t // chunk
            y = y + jnp.where(kept, w[j] * padded[j:j + s], 0.0)
        y = c * y
    else:
        y = conv_sum(w, padded, c, each)
    return _r(y) @ _weight(lp["conv_out"], layer)


def _dense(lp, layer, m, _r):
    m = _r(m)
    wg, wu, wd = (_weight(lp[n], layer) for n in ("w_gate", "w_up", "w_down"))
    return _r(jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def _experts(cfg, lp, layer, m, _r):
    s = jax.nn.sigmoid(_r(m) @ lp["router"][layer].astype(F32))  # [S, 64]
    pick = s + lp["router_bias"][layer].astype(F32)
    kth = jnp.sort(pick, axis=-1)[:, -cfg.n_experts_per_token][:, None]
    w = jnp.where(pick >= kth, s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_gate_eps)
    w = w * cfg.routed_scaling_factor
    m = _r(m)
    y = jnp.zeros_like(m)
    for e in range(cfg.n_experts):
        wg, wu, wd = (_weight(lp[n], layer, e)
                      for n in ("w_gate", "w_up", "w_down"))
        y = y + w[:, e: e + 1] * (
            _r(jax.nn.silu(m @ wg) * (m @ wu)) @ wd)
    return y


def forward(cfg, params, tokens, round_to=None, logits_from: int = 0,
            wrong: str | None = None, block: int = 512, states=None,
            state_ends: tuple = (), chunk: int = 1024):
    """Logits [S - logits_from, V] (float32) of one sequence ``tokens`` [S]
    at positions 0..S-1, from position ``logits_from`` on.  ``params``: the
    program's tree (``transformer.init_params`` layout; int8 leaves
    allowed)."""
    if not (cfg.conv_kernel and cfg.qk_norm_head and cfg.router_sigmoid
            and cfg.tie_embeddings and cfg.norm_topk_prob):
        raise NotImplementedError(f"{cfg.name} is not an lfm2 model")
    if wrong not in (None, *WRONG):
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    _r = _rounder(round_to)
    n_dense = cfg.first_k_dense
    seen = {}  # (group, is conv) -> layers of the kind met in the group
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for l in range(cfg.n_layers):
            dense = l < n_dense
            lp = params["dense_layers" if dense else "layers"]
            layer = l if dense else l - n_dense  # its place in its group
            conv = cfg.layer_pattern[l % len(cfg.layer_pattern)] == "conv"
            own = seen.get((dense, conv), 0)     # ... among its own kind
            seen[dense, conv] = own + 1
            h = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            if conv:
                x = x + _short_conv(cfg, lp, own, h, _r, states, state_ends,
                                    wrong, chunk)
            else:
                x = x + _attention(cfg, lp, own, h, _r, block,
                                   qk_norm=wrong != "no_qk_norm")
            m = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + (_dense(lp, layer, m, _r) if dense
                     else _experts(cfg, lp, layer, m, _r))
        x = _r(_rms_norm(x[logits_from:], params["final_norm"].astype(F32),
                         cfg.norm_eps))
        return x @ _r(params["embed"].astype(F32)).T
