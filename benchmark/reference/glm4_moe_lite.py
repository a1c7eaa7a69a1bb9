"""The benchmark's own copy of the plain float32 reference forward for
GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``; the equations of
``llm_instance_gateway_tpu/models/reference.py`` as of PR 35), kept under
``benchmark/`` so that what decides ``benchmark/reference_check_glm.py`` is
part of the yardstick: a later PR that changes the program's reference does
not change this one.  ``tests/benchmark/test_bench_mla.py`` holds the two to
equal logits on ``glm-tiny``.

float32 under ``jax.default_matmul_precision("highest")``, one sequence, a
Python loop over layers, all 64 experts computed for every token and mixed by
the gate rule, the EXPANDED form of the attention only (per-head keys and
values from the latent; no cache, no absorbed form, no kernel).  It imports
nothing from ``transformer.py``, ``mla.py`` or ``ops/``.

For x [S, 2048] at positions 0..S-1, every norm an RMSNorm (eps 1e-5), no
bias anywhere:

    h = norm(x)
    c_q = norm_q(h Wqa) [768];  q_h = c_q Wqb,h = [q_nope_h 192 | q_rope_h 64]
    [c_kv 512 | k_r 64] = h Wkva;  c = norm_kv(c_kv);  k_rope = RoPE(k_r)
    q_rope_h = RoPE(q_rope_h);  ONE k_rope for all 20 heads; theta 1e6 over
    all 64 columns, rotate-half pairing (not in config.json: as the program)
    [k_nope_h 192 | v_h 256] = c Wkvb,h
    a_h = softmax((q_nope_h k_nope_h^T + q_rope_h k_rope^T) / sqrt(256)
                  + causal mask) v_h;   x = x + concat_h(a_h) Wo
    layer 0:    x = x + (silu(h' Wg) * (h' Wu)) Wd,  h' = norm(x), width 10240
    layers 1..: s = sigmoid(h' Wr) over 64 experts, float32; the experts are
                the top-4 of s + b; g_i = 1.8 s_i / (sum of the chosen s +
                1e-20);  x = x + sum_i g_i E_i(h') + S(h'),  width 1536
    logits = norm(x) W_head

Departures, each on purpose: one layer's weights at a time, and within a
sparse layer one expert's at a time, dequantised inside the loop (the float32
copy of one layer's experts is 2.4 GB); an int8 leaf ``{"q", "s"}`` is read
as ``q * s``, so the reference checks the program's arithmetic on the weights
it serves; ``logits_from`` cuts the head to the positions that are compared
(the whole [S, 154880] float32 logits of a 2,064-token sequence are 1.3 GB).
``round_to`` as in ``benchmark/reference/olmoe.py``: with a dtype, whatever
enters a matmul is first rounded to it and widened again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def _attention(cfg, lp, layer, x_n, _r):
    s = x_n.shape[0]
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    x_n = _r(x_n)
    c_q = _rms_norm(x_n @ _weight(lp["wq_down"], layer),
                    lp["q_latent_norm"][layer].astype(F32), cfg.norm_eps)
    q = (_r(c_q) @ _weight(lp["wq_up"], layer)).reshape(s, h, nope + rope)
    ckv = x_n @ _weight(lp["wkv_down"], layer)
    c = _rms_norm(ckv[:, :rank], lp["kv_latent_norm"][layer].astype(F32),
                  cfg.norm_eps)
    k_rope = _rope(ckv[:, None, rank:], cfg.rope_theta)[:, 0]  # [S, rope]
    q_rope = _rope(q[..., nope:], cfg.rope_theta)
    kv = (_r(c) @ _weight(lp["wkv_up"], layer)).reshape(s, h, nope + vd)
    scores = (jnp.einsum("ihd,jhd->hij", _r(q[..., :nope]), _r(kv[..., :nope]))
              + jnp.einsum("ihd,jd->hij", _r(q_rope), _r(k_rope))
              ) / jnp.sqrt(F32(nope + rope))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = _r(jnp.einsum("hij,jhd->ihd", _r(probs), _r(kv[..., nope:]))
           ).reshape(s, -1)
    return a @ _weight(lp["wo"], layer)


def _gated(z, wg, wu, wd, _r):
    return _r(jax.nn.silu(z @ wg) * (z @ wu)) @ wd


def _mlp(cfg, lp, layer, h_n, _r):
    names = ("w_gate", "w_up", "w_down")
    h_n = _r(h_n)
    if "router" not in lp:  # the leading dense layer
        return _gated(h_n, *(_weight(lp[n], layer) for n in names), _r)
    scores = jax.nn.sigmoid(h_n @ lp["router"][layer].astype(F32))  # [S, E]
    pick = scores + lp["router_bias"][layer].astype(F32)
    kth = jnp.sort(pick, axis=-1)[:, -cfg.n_experts_per_token][:, None]
    w = jnp.where(pick >= kth, scores, 0.0)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    y = _gated(h_n, *(_weight(lp["ws" + n[1:]], layer) for n in names), _r)
    for e in range(cfg.n_experts):
        y = y + w[:, e: e + 1] * _gated(
            h_n, *(_weight(lp[n], layer, e) for n in names), _r)
    return y


def forward(cfg, params, tokens, round_to=None, logits_from: int = 0):
    """Logits [S - logits_from, V] (float32) of one sequence ``tokens`` [S]
    at positions 0..S-1, from position ``logits_from`` on.  ``params``: the
    program's tree (``transformer.init_params`` layout; int8 leaves
    allowed): ``dense_layers`` the leading dense stack, ``layers`` the
    sparse one."""
    if not (cfg.kv_lora_rank and cfg.router_sigmoid and cfg.n_shared_experts):
        raise NotImplementedError(f"{cfg.name} is not a glm4_moe_lite model")
    _r = _rounder(round_to)
    n_dense = params["dense_layers"]["attn_norm"].shape[0]
    stack = ([(params["dense_layers"], i) for i in range(n_dense)]
             + [(params["layers"], i) for i in range(cfg.n_layers - n_dense)])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for lp, layer in stack:
            x_n = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + _attention(cfg, lp, layer, x_n, _r)
            h_n = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + _mlp(cfg, lp, layer, h_n, _r)
        x = _r(_rms_norm(x[logits_from:], params["final_norm"].astype(F32),
                         cfg.norm_eps))
        return x @ _weight(params["lm_head"])
