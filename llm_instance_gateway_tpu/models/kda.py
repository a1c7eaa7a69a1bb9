"""Kimi Delta Attention (KDA), the delta-rule linear attention that
Ling-3.0-flash runs IN PLACE of attention in five layers of six, for
``transformer.py``: a "kda" layer of ``ModelConfig.layer_pattern`` holds no K
and V but, a sequence and a head, a matrix state.

With h = norm(x), per KDA layer, token t, head n (Ling-3.0-flash: 32 heads,
d_k = d_v = 128; arXiv:2510.26692 and ``fla``'s ``KimiDeltaAttention`` with
``safe_gate``):

    [q^ | k^ | v^ | f | z] = h W_in          W_in: D -> 5 x 4096, in this order
    q-, k-, v- = silu(conv(q^)), silu(conv(k^)), silu(conv(v^))
        each its own causal depthwise conv of 4 taps, zeros before position
        0, no bias: y_t = sum_i w_i x_{t-3+i}
    q_t = d_k^(-1/2) q-_t / |q-_t|,  k_t = k-_t / |k-_t|   L2 over a head, eps 1e-6
    g_t = bound * sigmoid(exp(A_log) * (f_t + dt_bias))   per CHANNEL, in (bound, 0)
    beta_t = sigmoid(h_t w_b)                               one number a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                         S [d_k, d_v], S_{-1} = 0
    y_t = [RMSNorm_w(o_t) * sigmoid(z_t)] W_out             the norm per head, one w of d_v

State.  Per sequence and layer the operator keeps ``S`` and the convs' last
3 inputs.  The decode cache holds them as ``kda`` [L_kda, B, heads, d_k, d_v]
in FLOAT32 (a running sum over the whole sequence, as the mixer's,
``models/ssm.py``) and ``conv`` [L_kda, 3, B, 3 x heads x d_k] in the
activation dtype, the positions BEFORE the batch as the other conv histories
lie.

Three forms of one recurrence, held equal by ``tests/test_ling.py``:

- ``scan_sequential``: position by position; the definition.
- ``scan_chunked`` (prompts): in blocks of ``BLOCK`` positions.  With
  G_i the block's running sum of g and S_0 the state it is entered with:
  ``(I + A) U = beta * (V - (K * exp(G)) S_0)``, ``A_ij = beta_i sum_d k_id
  k_jd exp(G_id - G_jd)`` for j < i (unit lower triangular, solved by forward
  substitution); ``o_i = S_0^T (exp(G_i) * q_i) + sum_{j<=i} [(q_i *
  exp(G_i - G_j)) . k_j] u_j``; ``S_C = Diag(exp(G_C)) S_0 + sum_j (exp(G_C -
  G_j) * k_j) u_j^T``.  ``exp(G_i - G_j)`` is at most 1, but split into
  ``exp(G_i) * exp(-G_j)`` so that it becomes a matmul the second factor
  reaches e^(5 C): inside float32 for C <= 17, which is what the bound of
  -5 is for, so a block is at most 16 positions and the state is what goes
  from block to block (no decay is ever split across blocks).  The split
  is taken about the block's middle, ``exp(G_i - G_m) * exp(G_m - G_j)``,
  so that the factors stay within e^(+-40) and a TPU's float32 matmul, which
  is bf16 passes, loses none of them to a flushed denormal.
- ``ops.pallas_kda.kda_decode_update`` (decode): one step, in place.

Padding.  A position at or past a row's true length has g = 0 and beta = 0:
decay 1, no delta, no write, so the state a bucket-padded prompt leaves is
its last TRUE token's; the conv tail is cut at the true length too.

Scopes: kda.in_proj, kda.conv, kda.gate, kda.scan (prompts), kda.update
(decode), kda.gate_norm, kda.out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.models import ssm
from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.ops import pallas_kda
from llm_instance_gateway_tpu.ops.quant import matmul as q_matmul

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
# Positions of one block of the chunked form: inside a block the decay is
# split into two factors about the block's middle, each within e^(+-bound *
# BLOCK / 2), which has to stay inside float32 (``leaf_shapes`` checks).
BLOCK = 16

# The operator's leaves, stacked over the KDA layers of a group alone.
LEAVES = ("kda_in", "kda_conv_w", "kda_beta", "kda_a_log", "kda_dt_bias",
          "kda_norm", "kda_out")


def leaf_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The operator's drawn leaves of one layer: name -> (shape, fan_in);
    fan_in 0 marks a norm weight (ones)."""
    d, inner = cfg.d_model, cfg.kda_n_heads * cfg.kda_head_dim
    if BLOCK * -cfg.kda_lower_bound > 85:
        raise ValueError(f"{cfg.name}: a block of {BLOCK} positions at a "
                         f"bound of {cfg.kda_lower_bound} overflows float32 "
                         "(models/kda.py)")
    return {
        "kda_in": ((d, 5 * inner), d),
        "kda_conv_w": ((cfg.kda_conv, 3 * inner), cfg.kda_conv),
        "kda_beta": ((d, cfg.kda_n_heads), d),
        "kda_norm": ((cfg.kda_head_dim,), 0),
        "kda_out": ((inner, d), inner),
    }


def init_vectors(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    """The gate's vectors, float32: ``exp(A_log)`` uniform in [1, 16] a head
    (log U(1, 16), as the mixer's), and ``dt_bias`` such that at f = 0 a
    channel's decay g / bound is log-uniform in [1e-3, 0.9]: with f ~ N(0, 1)
    times exp(A_log) the decays then lie all over (bound, 0), both ends
    included."""
    k_a, k_dt = jax.random.split(key)
    h, dk = cfg.kda_n_heads, cfg.kda_head_dim
    a = jax.random.uniform(k_a, (n_layers, h), F32, 1.0, 16.0)
    share = jnp.exp(jax.random.uniform(
        k_dt, (n_layers, h, dk), F32, minval=np.log(1e-3),
        maxval=np.log(0.9)))
    logit = jnp.log(share) - jnp.log1p(-share)
    return {"kda_a_log": jnp.log(a),
            "kda_dt_bias": (logit / a[..., None]).reshape(n_layers, h * dk)}


def init_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    """The KDA layers' part of a decode cache."""
    n = cfg.n_layers_of("kda")
    return {
        "kda": jnp.zeros((n, batch, cfg.kda_n_heads, cfg.kda_head_dim,
                          cfg.kda_head_dim), F32),
        "conv": jnp.zeros((n, cfg.kda_conv - 1, batch, cfg.kda_conv_dim),
                          dtype),
    }


@jax.named_scope("kda.in_proj")
def in_proj(cfg: ModelConfig, lp, hn):
    """``hn`` [..., D] -> (qkv [..., 3 inner] before the convs, f [...,
    inner], z [..., inner], beta [..., H] float32 after the sigmoid)."""
    inner = cfg.kda_n_heads * cfg.kda_head_dim
    out = q_matmul(hn, lp["kda_in"])
    beta = jax.nn.sigmoid(jnp.dot(hn, lp["kda_beta"],
                                  preferred_element_type=F32))
    return (out[..., :3 * inner], out[..., 3 * inner:4 * inner],
            out[..., 4 * inner:], beta)


@jax.named_scope("kda.conv")
def conv_window(cfg: ModelConfig, lp, padded):
    """silu(causal depthwise conv) of ``padded`` [..., S + K - 1, C] (its
    first K - 1 positions the history) -> [..., S, C], float32."""
    k = cfg.kda_conv
    s = padded.shape[-2] - (k - 1)
    w = lp["kda_conv_w"].astype(F32)
    acc = 0.0
    for j in range(k):  # tap j weighs the input K - 1 - j positions back
        acc = acc + w[j] * padded[..., j:j + s, :].astype(F32)
    return jax.nn.silu(acc)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def split_qkv(cfg: ModelConfig, qkv):
    """Convolved qkv [..., 3 inner] float32 -> (q, k, v), each [..., H, dk]:
    q and k L2-normed over the head, q scaled by dk^(-1/2)."""
    h, dk = cfg.kda_n_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(*t.shape[:-1], h, dk)
               for t in jnp.split(qkv, 3, axis=-1))
    return _l2(q) * dk ** -0.5, _l2(k), v


@jax.named_scope("kda.gate")
def log_decay(cfg: ModelConfig, lp, f, live=None):
    """g [..., H, dk] float32 in (bound, 0); 0 where ``live`` is false."""
    h, dk = cfg.kda_n_heads, cfg.kda_head_dim
    raw = (f.astype(F32) + lp["kda_dt_bias"].astype(F32)).reshape(
        *f.shape[:-1], h, dk)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["kda_a_log"].astype(F32))[:, None] * raw)
    return g if live is None else jnp.where(live[..., None, None], g, 0.0)


def scan_sequential(q, k, v, g, beta, s0=None):
    """The recurrence position by position.  ``q``, ``k``, ``g`` [B, S, H,
    dk], ``v`` [B, S, H, dv], ``beta`` [B, S, H], ``s0`` [B, H, dk, dv] or
    None.  Returns (o [B, S, H, dv] float32, the last state)."""
    b, _, h, dk = q.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, v.shape[-1]), F32)

    def step(s, xs):
        o, s = pallas_kda.kda_update_xla(s, *xs)
        return s, o

    s, o = jax.lax.scan(step, s0.astype(F32), tuple(
        jnp.moveaxis(t.astype(F32), 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


@jax.named_scope("kda.scan")
def scan_chunked(q, k, v, g, beta, s0=None):
    """The same numbers in blocks of ``BLOCK`` positions (the
    module's docstring has the algebra).  Any length: the tail is padded
    with g = 0 and beta = 0, which leaves the state alone."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(BLOCK, s)
    pad = -s % c
    q, k, v, g, beta = (jnp.pad(t.astype(F32), ((0, 0), (0, pad))
                                + ((0, 0),) * (t.ndim - 2))
                        for t in (q, k, v, g, beta))
    n_c = (s + pad) // c
    blocks = tuple(jnp.moveaxis(t.reshape(b, n_c, c, *t.shape[2:]), 1, 0)
                   for t in (q, k, v, g, beta))
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, dv), F32)
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    upto = jnp.tril(jnp.ones((c, c), bool))

    def block(st, xs):
        qc, kc, vc, gc, bc = xs       # [B, C, H, dk] ...; bc [B, C, H]
        cs = jnp.cumsum(gc, axis=1)   # G_i, in [bound * C, 0]
        # e^{G_i - G_j} as two factors about the block's middle, so that
        # neither leaves e^(+-bound * C / 2)
        mid = cs[:, c // 2:c // 2 + 1]
        up, rel, inv = jnp.exp(cs), jnp.exp(cs - mid), jnp.exp(mid - cs)
        k_out = kc * inv
        # A_ij = beta_i (k_i e^{G_i}) . (k_j e^{-G_j}), j < i
        a = jnp.einsum("bihd,bjhd->bhij", kc * rel, k_out, precision=HIGHEST)
        a = jnp.where(below, a * jnp.moveaxis(bc, 1, 2)[..., None], 0.0)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bihd,bhdv->bihv", kc * up, st, precision=HIGHEST))
        u = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=F32), jnp.moveaxis(rhs, 1, 2), lower=True,
            unit_diagonal=True)       # [B, H, C, dv]
        qk = jnp.where(upto, jnp.einsum("bihd,bjhd->bhij", qc * rel, k_out,
                                        precision=HIGHEST), 0.0)
        o = (jnp.einsum("bihd,bhdv->bihv", qc * up, st, precision=HIGHEST)
             + jnp.einsum("bhij,bhjv->bihv", qk, u, precision=HIGHEST))
        to_end = kc * jnp.exp(cs[:, -1:] - cs)        # e^{G_C - G_j} k_j
        st = (up[:, -1][..., None] * st
              + jnp.einsum("bjhd,bhjv->bhdv", to_end, u, precision=HIGHEST))
        return st, o

    st, o = jax.lax.scan(block, s0.astype(F32), blocks)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s + pad, h, dv)
    return o[:, :s], st


@jax.named_scope("kda.gate_norm")
def gate_norm(cfg: ModelConfig, lp, o, z):
    """RMSNorm over each head's ``o`` [..., H, dv] (one weight vector of dv
    for all heads), times sigmoid(z) [..., H * dv].  ``z``'s dtype."""
    o = o.astype(F32)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    normed = o * jax.lax.rsqrt(var + cfg.norm_eps) * lp["kda_norm"].astype(F32)
    return (normed.reshape(z.shape)
            * jax.nn.sigmoid(z.astype(F32))).astype(z.dtype)


@jax.named_scope("kda.out_proj")
def out_proj(lp, y):
    return q_matmul(y, lp["kda_out"])


def prompt_mix(cfg: ModelConfig, lp, hn, live=None, history=None, s0=None):
    """The operator over a (padded) prompt or one chunk of it.  ``hn``
    [B, S, D]; ``live`` [B, S] bool marks the true positions, which lead
    (None: all S); ``history`` [B, K - 1, C] and ``s0`` [B, H, dk, dv] are
    what came before (None: a prompt's start, zeros).  Returns (y [B, S, D],
    (the state [B, H, dk, dv] float32, the conv history [B, K - 1, C]) after
    the last TRUE position)."""
    b, s, _ = hn.shape
    qkv, f, z, beta = in_proj(cfg, lp, hn)
    if history is None:
        history = jnp.zeros((b, cfg.kda_conv - 1, qkv.shape[-1]), qkv.dtype)
    padded = jnp.concatenate([history.astype(qkv.dtype), qkv], axis=1)
    tail = ssm.conv_tail(padded, ssm.true_lengths(live, b, s), cfg.kda_conv)
    q, k, v = split_qkv(cfg, conv_window(cfg, lp, padded))
    if live is not None:
        beta = jnp.where(live[..., None], beta, 0.0)
    o, state = scan_chunked(q, k, v, log_decay(cfg, lp, f, live), beta, s0)
    return out_proj(lp, gate_norm(cfg, lp, o, z)), (state, tail)


def decode_mix(cfg: ModelConfig, lp, hn, rec, lane, active=None):
    """One decode step's operator.  ``hn`` [B, D]; ``rec`` the carry's
    ``(kda [L_kda, B, H, dk, dv], conv [L_kda, K - 1, B, C])``, of which
    this layer's is ``lane``; rows whose ``active`` bit is off leave both
    untouched.  Returns (y [B, D], the carry)."""
    state, conv = rec
    qkv, f, z, beta = in_proj(cfg, lp, hn)
    history = jax.lax.dynamic_index_in_dim(conv, lane, 0, keepdims=False)
    padded = jnp.concatenate([history, qkv[None].astype(conv.dtype)], axis=0)
    with jax.named_scope("kda.conv"):
        moved = padded[1:] if active is None else jnp.where(
            active[None, :, None], padded[1:], history)
        conv = jax.lax.dynamic_update_index_in_dim(conv, moved, lane, 0)
    q, k, v = split_qkv(
        cfg, conv_window(cfg, lp, jnp.moveaxis(padded, 0, 1))[:, 0])
    with jax.named_scope("kda.update"):
        o, state = pallas_kda.kda_decode_update(
            state, q, k, v, log_decay(cfg, lp, f), beta, live=active,
            layer=lane, use_kernel=cfg.use_pallas_decode)
    return out_proj(lp, gate_norm(cfg, lp, o, z)), (state, conv)


def chunk_mix(cfg: ModelConfig, lp, hn, rec, lane, slot, first, live):
    """One chunk of a streamed prompt for ONE slot: the state and the conv
    history the slot's lane holds (zeros where this is the prompt's
    ``first`` chunk) go in, what the chunk leaves at its true end goes back.
    ``hn`` [1, C, D], ``live`` [1, C].  Returns (y [1, C, D], the carry)."""
    state, conv = rec
    s0 = jnp.where(first, 0.0, state[lane, slot])[None]
    history = jnp.where(first, jnp.zeros((), conv.dtype),
                        conv[lane, :, slot])[None]
    y, (s1, tail) = prompt_mix(cfg, lp, hn, live, history, s0)
    return y, (state.at[lane, slot].set(s1[0]),
               conv.at[lane, :, slot].set(tail[0]))
