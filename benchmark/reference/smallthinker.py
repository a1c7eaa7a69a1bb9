"""The benchmark's own copy of the plain float32 reference forward for
SmallThinker (``model_name`` ``smallthinker_21b_instruct``; the equations of
``llm_instance_gateway_tpu/models/reference.py`` as of PR 45), kept under
``benchmark/`` so that what decides ``benchmark/reference_check_smallthinker.py``
is part of the yardstick: a later PR that changes the program's reference does
not change this one.  ``tests/benchmark/test_bench_window.py`` holds the two to
equal logits on ``smallthinker-tiny``.

float32 under ``jax.default_matmul_precision("highest")``, one sequence, a
Python loop over layers, all 64 experts computed for every token and mixed by
the gate rule, masks built from positions; no cache, no ring, no kernel, no
batching.  It imports nothing from ``transformer.py`` or ``ops/``.

For x [S, 2560] entering layer l at positions 0..S-1, every norm an RMSNorm
(eps 1e-6), no bias anywhere, no QK-norm:

    r = x Wr                          router logits [64], from x BEFORE the norm
    h = norm_attn(x)
    q, k, v = h Wq, h Wk, h Wv        28 / 4 / 4 heads x 128
    l % 4 != 0:  q, k = RoPE(q), RoPE(k), theta 1.5e6 over all 128 columns,
                 rotate-half pairing (not in config.json: as the program);
                 position i attends j iff 0 <= i - j < 4096
    l % 4 == 0:  no position encoding at all;  i attends j iff j <= i
    a = softmax(q k^T / sqrt(128) + mask) v;   x = x + a Wo
    m = norm_mlp(x)
    E6 = top-6 of r;  g = softmax(r[E6])      (softmax over all 64, the six
                 largest, renormalised over the chosen: the same numbers)
    x = x + sum_{e in E6} g_e (relu(m Wg_e) * (m Wu_e)) Wd_e      width 768
    logits = norm(x) W_head

The period of the stack is ``cfg.layer_pattern`` ("nope", "window", "window",
"window").  A ``router_bias`` leaf [L, 64], where the check pins the choice with one, picks
(top-6 of r + bias) and never weighs.

Departures, each on purpose: one layer's weights at a time, and within it one
expert's at a time, dequantised inside the loop; an int8 leaf ``{"q", "s"}``
is read as ``q * s``, so the reference checks the program's arithmetic on the
weights it serves; the attention is computed ``block`` queries at a time (the
[28, S, S] float32 scores of an 8,448-token sequence are 8 GB), which changes
no number; ``logits_from`` cuts the head to the positions that are compared.
``round_to`` as in ``benchmark/reference/olmoe.py``: with a dtype, whatever
enters a matmul is first rounded to it and widened again.  ``wrong`` computes
another function on purpose, for the check's readings: ``"no_window"`` (every
layer attends every earlier position), ``"rope_on_full"`` (the full layers
rotate too), ``"router_after_norm"`` (r from m, as in every other sparse
model here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_window", "rope_on_full", "router_after_norm")


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


def _attention(cfg, lp, layer, h, window, rope, _r, block):
    s = h.shape[0]
    hd = cfg.head_dim
    h = _r(h)
    q = (h @ _weight(lp["wq"], layer)).reshape(s, cfg.n_heads, hd)
    k = (h @ _weight(lp["wk"], layer)).reshape(s, cfg.n_kv_heads, hd)
    v = (h @ _weight(lp["wv"], layer)).reshape(s, cfg.n_kv_heads, hd)
    if rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = _r(jnp.repeat(k, group, axis=1)), _r(jnp.repeat(v, group, axis=1))
    q = _r(q)
    out = []
    for start in range(0, s, block):
        i = jnp.arange(start, min(s, start + block))
        scores = jnp.einsum("ihd,jhd->hij", q[i[0]:i[-1] + 1], k
                            ) / jnp.sqrt(F32(hd))
        behind = i[:, None] - jnp.arange(s)[None, :]
        seen = (behind >= 0) & (behind < window) if window else behind >= 0
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hij,jhd->ihd", _r(probs), v))
    a = _r(jnp.concatenate(out)).reshape(s, -1)
    return a @ _weight(lp["wo"], layer)


def _experts(cfg, lp, layer, m, r_in, _r):
    """The sparse MLP of ``m``, routed by ``r_in``."""
    logits = _r(r_in) @ lp["router"][layer].astype(F32)  # [S, 64]
    pick = logits
    if "router_bias" in lp:
        pick = logits + lp["router_bias"][layer].astype(F32)
    kth = jnp.sort(pick, axis=-1)[:, -cfg.n_experts_per_token][:, None]
    w = jnp.where(pick >= kth, jax.nn.softmax(logits, axis=-1), 0.0)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    m = _r(m)
    y = jnp.zeros_like(m)
    for e in range(cfg.n_experts):
        wg, wu, wd = (_weight(lp[n], layer, e)
                      for n in ("w_gate", "w_up", "w_down"))
        y = y + w[:, e: e + 1] * (
            _r(jax.nn.relu(m @ wg) * (m @ wu)) @ wd)
    return y


def forward(cfg, params, tokens, round_to=None, logits_from: int = 0,
            wrong: str | None = None, block: int = 512):
    """Logits [S - logits_from, V] (float32) of one sequence ``tokens`` [S]
    at positions 0..S-1, from position ``logits_from`` on.  ``params``: the
    program's tree (``transformer.init_params`` layout; int8 leaves
    allowed)."""
    if not (cfg.layer_pattern and cfg.router_pre_attention
            and cfg.mlp_activation == "relu" and cfg.norm_topk_prob):
        raise NotImplementedError(f"{cfg.name} is not a smallthinker model")
    if wrong not in (None, *WRONG):
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    _r = _rounder(round_to)
    lp = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for layer in range(cfg.n_layers):
            kind = cfg.layer_pattern[layer % len(cfg.layer_pattern)]
            window = cfg.sliding_window if kind == "window" else 0
            rope = kind != "nope"
            if wrong == "no_window":
                window = 0
            if wrong == "rope_on_full":
                rope = True
            h = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            block_in = x
            x = x + _attention(cfg, lp, layer, h, window, rope, _r, block)
            m = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            r_in = m if wrong == "router_after_norm" else block_in
            x = x + _experts(cfg, lp, layer, m, r_in, _r)
        x = _r(_rms_norm(x[logits_from:], params["final_norm"].astype(F32),
                         cfg.norm_eps))
        return x @ _weight(params["lm_head"])
