"""The benchmark's own copy of the plain float32 reference forward for
Falcon-H1 (``model_type`` ``falcon_h1``; the equations of
``llm_instance_gateway_tpu/models/reference.py`` as of PR 43), kept under
``benchmark/`` so that what decides ``benchmark/reference_check_falconh1.py``
is part of the yardstick: a later PR that changes the program's reference
does not change this one.  ``tests/benchmark/test_bench_ssm.py`` holds the two
to equal logits and states on ``falcon-h1-tiny``.

float32 under ``jax.default_matmul_precision("highest")``, one sequence, a
Python loop over layers, the recurrence one position after another (a
``lax.scan`` over positions: at the published widths a state is 4 MiB and a
prompt has hundreds of positions).  No cache, no chunks, no kernel.  It
imports nothing from ``transformer.py``, ``ssm.py`` or ``ops/``.

For x [S, D] at positions 0..S-1, every norm an RMSNorm (eps 1e-5), no bias
but the conv's (Falcon-H1-34B's sizes in brackets; the scalars are the
published config's multipliers, read from ``cfg``):

    x0 = E[token] * embedding_multiplier
    x_n = norm_in(x)
    q, k, v = (x_n * attention_in_multiplier) Wq, Wk, Wv;  k = k * key_multiplier
    q, k = RoPE(q), RoPE(k)    per head over all 128 columns, rotate-half
                               pairing (not in config.json: as the program)
    a = concat_h softmax(q_h k_g^T / sqrt(128) + causal) v_g,  g = h // 5
    a = (a Wo) * attention_out_multiplier
    [z | xBC | dt] = ((x_n * ssm_in_multiplier) W_in) * m      [4096 | 5120 | 32]
        m = ssm_multipliers over z [4096], x [4096], B [512], C [512], dt [32]
    xBC_t = silu(sum_j w[j] * xBC_{t-3+j} + b)    4 taps, zeros before position 0
    x | B | C = xBC: x [32, 128]; B, C [2, 256]; head h reads group h // 16
    dt_t = softplus(dt_t + dt_bias), no clamp;  A = -exp(A_log)
    H_t = exp(dt_t A) * H_{t-1} + dt_t * x_t (outer) B_t;   H_{-1} = 0
    y_t = H_t C_t + D * x_t
    y = norm_w(y * silu(z)), the mean of squares per group of 2048
    s = (y W_out) * ssm_out_multiplier;     x = x + a + s
    x = x + ((silu((h_n Wg) * mlp_multipliers[0]) * (h_n Wu)) Wd) * mlp_multipliers[1]
    logits = (norm_f(x) W_head) * lm_head_multiplier

Departures, each on purpose: one layer's weights at a time, and the head a
block of columns at a time, dequantised inside the loop (a float32 layer is
1.7 GB, the float32 head 5.3 GB); an int8 leaf ``{"q", "s"}`` is read as
``q * s``, so the reference checks the program's arithmetic on the weights it
serves; ``logits_from`` cuts the head to the positions that are compared.
``round_to`` as in ``benchmark/reference/olmoe.py``: with a dtype, whatever
enters a matmul is first rounded to it and widened again; ``state_dtype``
rounds the recurrent state to that dtype after every position (what a cache
that held it so would do).  ``states``, a list, gets each layer's state after
the last position, [heads, head_dim, d_state].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 32768  # columns of the output head dequantised at a time


def _weight(leaf, *index):
    """``leaf[index]`` of the program's tree as a float32 matrix, an int8
    ``{"q", "s"}`` pair dequantised per output channel."""
    if isinstance(leaf, dict):
        q, s = leaf["q"][index], leaf["s"][index]
        return q.astype(F32) * s.astype(F32)[..., None, :]
    return leaf[index].astype(F32)


def _rounder(dtype):
    """Round to ``dtype`` and widen again.  ``reduce_precision`` and not a
    pair of casts: inside a compiled loop (the state's scan) the TPU's
    compiler drops a cast down and up as excess precision (chip run, PR 43:
    a "bf16" state read 0.0 off the float32 one)."""
    if dtype is None:
        return lambda z: z
    info = jnp.finfo(dtype)
    return lambda z: jax.lax.reduce_precision(z, info.nexp, info.nmant)


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
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    x_n = _r(x_n * cfg.attention_in_multiplier)
    q = _rope((x_n @ _weight(lp["wq"], layer)).reshape(s, cfg.n_heads, hd),
              cfg.rope_theta)
    k = (x_n @ _weight(lp["wk"], layer)) * cfg.key_multiplier
    k = _rope(k.reshape(s, cfg.n_kv_heads, hd), cfg.rope_theta)
    v = (x_n @ _weight(lp["wv"], layer)).reshape(s, cfg.n_kv_heads, hd)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", _r(q), _r(k)) / jnp.sqrt(F32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = _r(jnp.einsum("hij,jhd->ihd", _r(probs), _r(v))).reshape(s, -1)
    return (a @ _weight(lp["wo"], layer)) * cfg.attention_out_multiplier


def _mixer(cfg, lp, layer, x_n, _r, _rs, states):
    s = x_n.shape[0]
    heads, hd, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state
    groups, taps, di = cfg.ssm_n_groups, cfg.ssm_d_conv, cfg.ssm_d_inner
    gn = groups * n
    m = jnp.concatenate([jnp.full((w,), mult, F32) for w, mult in zip(
        (di, di, gn, gn, heads), cfg.ssm_multipliers)])
    proj = (_r(x_n * cfg.ssm_in_multiplier) @ _weight(lp["ssm_in"], layer)) * m
    z, xbc, dt = proj[:, :di], proj[:, di:di + di + 2 * gn], proj[:, -heads:]
    w = lp["ssm_conv_w"][layer].astype(F32)  # [taps, channels]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32),
                              _r(xbc)])
    xbc = _r(jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps))
                         + lp["ssm_conv_b"][layer].astype(F32)))
    x = xbc[:, :di].reshape(s, heads, hd)
    b = jnp.repeat(xbc[:, di:di + gn].reshape(s, groups, n),
                   heads // groups, axis=1)  # [S, heads, d_state]
    c = jnp.repeat(xbc[:, di + gn:].reshape(s, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"][layer].astype(F32))
    a = -jnp.exp(lp["ssm_a_log"][layer].astype(F32))

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = _rs(jnp.exp(dt_t * a)[:, None, None] * state
                    + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    state, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), F32),
                            (x, b, c, dt))
    if states is not None:
        states.append(state)
    y = y + lp["ssm_d"][layer].astype(F32)[:, None] * x
    y = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, groups, di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = _r(y.reshape(s, di) * lp["ssm_norm"][layer].astype(F32))
    return (y @ _weight(lp["ssm_out"], layer)) * cfg.ssm_out_multiplier


def _mlp(cfg, lp, layer, h_n, _r):
    h_n = _r(h_n)
    gate = (h_n @ _weight(lp["w_gate"], layer)) * cfg.mlp_multipliers[0]
    act = _r(jax.nn.silu(gate) * (h_n @ _weight(lp["w_up"], layer)))
    return (act @ _weight(lp["w_down"], layer)) * cfg.mlp_multipliers[1]


def _head(params, x):
    """x W_head, a block of columns at a time."""
    head = params["lm_head"]
    quant = isinstance(head, dict)
    q = head["q"] if quant else head
    out = []
    for at in range(0, q.shape[-1], HEAD_BLOCK):
        w = q[:, at:at + HEAD_BLOCK].astype(F32)
        if quant:
            w = w * head["s"][at:at + HEAD_BLOCK].astype(F32)
        out.append(x @ w)
    return jnp.concatenate(out, axis=-1)


def forward(cfg, params, tokens, round_to=None, logits_from: int = 0,
            state_dtype=None, states=None):
    """Logits [S - logits_from, V] (float32) of one sequence ``tokens`` [S]
    at positions 0..S-1, from position ``logits_from`` on.  ``params``: the
    program's tree (``transformer.init_params`` layout; int8 leaves
    allowed)."""
    if not cfg.ssm_d_inner or cfg.n_experts or cfg.kv_lora_rank:
        raise NotImplementedError(f"{cfg.name} is not a falcon_h1 model")
    _r, _rs = _rounder(round_to), _rounder(state_dtype)
    lp = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32) * cfg.embedding_multiplier
        for layer in range(cfg.n_layers):
            x_n = _rms_norm(x, lp["attn_norm"][layer].astype(F32), cfg.norm_eps)
            x = (x + _attention(cfg, lp, layer, x_n, _r)
                 + _mixer(cfg, lp, layer, x_n, _r, _rs, states))
            h_n = _rms_norm(x, lp["mlp_norm"][layer].astype(F32), cfg.norm_eps)
            x = x + _mlp(cfg, lp, layer, h_n, _r)
        x = _r(_rms_norm(x[logits_from:], params["final_norm"].astype(F32),
                         cfg.norm_eps))
        return _head(params, x) * cfg.lm_head_multiplier
