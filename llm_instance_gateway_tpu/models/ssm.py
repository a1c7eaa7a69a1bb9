"""The state-space mixer (Mamba-2 form) that Falcon-H1 runs BESIDE attention in
every layer, for ``transformer.py``: both branches read one normed input and
their outputs are added to the residual together.

With x_n = norm(x), per layer (``cfg`` names the sizes; Falcon-H1-34B's in
brackets; the scalars are the config's multipliers):

    [z | xBC | dt] = ((x_n * ssm_in_multiplier) W_in) * m     W_in: D -> 4096 + 5120 + 32
        m = ssm_multipliers laid over the five parts z, x, B, C, dt
    xBC = silu(conv(xBC) + b_conv)   causal, depthwise, 4 taps, zeros before position 0
    x | B | C = xBC:  x [32 heads, 128];  B, C [2 groups, 256];  head h reads group h // 16
    dt_t = softplus(dt_t + dt_bias) [32];  A = -exp(A_log) [32]
    H_t = exp(dt_t A) * H_{t-1} + dt_t * (B_t outer x_t)      H_{-1} = 0
    y_t = C_t H_t + D * x_t                                   [32, 128] -> [4096]
    y = RMSNorm_w,groups(y * silu(z))     gate first; mean of squares per group of 2048
    s = (y W_out) * ssm_out_multiplier                        W_out: 4096 -> D

State.  Per sequence and layer the recurrence keeps ``H`` and the conv's
last ``d_conv - 1`` inputs.  The decode cache holds them beside ``k`` and
``v`` as ``ssm`` [L, B, heads, d_state, head_dim] in FLOAT32 (a running sum
over the whole sequence: in bf16 an increment ``dt x B`` is lost beside a
state a few hundred steps old) and ``conv`` [L, d_conv - 1, B, conv_dim] in
the activation dtype.  ``H`` lies with ``d_state`` BEFORE ``head_dim``
(``H[h, n, p]``, the transpose of the equations' x-outer-B), which is what
the decode kernel's tiles want (``ops/pallas_ssm.py``); the conv history
lies with its three positions BEFORE the batch, so that a tile is (rows,
channels) and not (3 positions padded to a tile, channels): laid
[L, B, 3, C] the decode program relaid all of it twice a step (device
trace, PR 43).

Three forms of one recurrence, held equal by ``tests/test_ssm.py``:

- ``scan_sequential``: position by position; what the tests call the truth.
- ``scan_chunked`` (prompts): in chunks of ``cfg.ssm_chunk`` positions,
  inside a chunk as masked matmuls (float32, precision "highest": they are
  4% of a layer's prefill flops and they produce the state a whole answer
  decodes from), the state passed from chunk to chunk.
- ``ops.pallas_ssm.ssm_decode_update`` (decode): one step, in place.

Padding.  A position at or past a row's true length has dt = 0: decay 1,
increment 0, so the state a bucket-padded prompt leaves is its last TRUE
token's; the conv tail is cut at the true length too (``conv_tail``).

Scopes: ssm.in_proj, ssm.conv, ssm.scan (prompts), ssm.update (decode),
ssm.gate_norm, ssm.out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.models.configs import ModelConfig
from llm_instance_gateway_tpu.ops import pallas_ssm
from llm_instance_gateway_tpu.ops.layers import scaled
from llm_instance_gateway_tpu.ops.quant import matmul as q_matmul

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def leaf_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The mixer's drawn leaves of one layer: name -> (shape, fan_in);
    fan_in 0 marks a norm weight (ones).  The conv bias is drawn non-zero
    (std 0.1) so that a conv that drops it fails a test."""
    d = cfg.d_model
    if cfg.ssm_n_heads * cfg.ssm_head_dim != cfg.ssm_d_inner:
        raise ValueError(f"{cfg.name}: ssm_n_heads x ssm_head_dim is not "
                         "ssm_d_inner")
    return {
        "ssm_in": ((d, cfg.ssm_in_dim), d),
        "ssm_conv_w": ((cfg.ssm_d_conv, cfg.ssm_conv_dim), cfg.ssm_d_conv),
        "ssm_conv_b": ((cfg.ssm_conv_dim,), 100),
        "ssm_norm": ((cfg.ssm_d_inner,), 0),
        "ssm_out": ((cfg.ssm_d_inner, d), cfg.ssm_d_inner),
    }


def init_vectors(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    """The per-head vectors as the Mamba-2 reference initialises them, in
    float32: ``-A = exp(A_log)`` uniform in [1, 16], ``softplus(dt_bias)``
    log-uniform in [1e-3, 1e-1], ``D`` = 1.  A state then forgets over tens
    to thousands of positions, as a trained one does."""
    k_a, k_dt = jax.random.split(key)
    shape = (n_layers, cfg.ssm_n_heads)
    dt = jnp.exp(jax.random.uniform(
        k_dt, shape, F32, minval=np.log(1e-3), maxval=np.log(1e-1)))
    return {
        "ssm_a_log": jnp.log(jax.random.uniform(k_a, shape, F32, 1.0, 16.0)),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus's inverse
        "ssm_d": jnp.ones(shape, F32),
    }


def init_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    """The recurrent part of a decode cache."""
    return {
        "ssm": jnp.zeros((cfg.n_layers, batch, cfg.ssm_n_heads,
                          cfg.ssm_d_state, cfg.ssm_head_dim), F32),
        "conv": jnp.zeros((cfg.n_layers, cfg.ssm_d_conv - 1, batch,
                           cfg.ssm_conv_dim), dtype),
    }


def mup_vector(cfg: ModelConfig) -> np.ndarray:
    """``ssm_multipliers`` laid over the projection's columns, in the
    order of its five parts: z, x, B, C, dt."""
    gn = cfg.ssm_n_groups * cfg.ssm_d_state
    widths = (cfg.ssm_d_inner, cfg.ssm_d_inner, gn, gn, cfg.ssm_n_heads)
    return np.concatenate([np.full((w,), m, np.float32)
                           for w, m in zip(widths, cfg.ssm_multipliers)])


@jax.named_scope("ssm.in_proj")
def in_proj(cfg: ModelConfig, lp, hn):
    """``hn`` [..., D] -> (z [..., d_inner], xBC [..., conv_dim] before the
    conv, dt [..., heads] before the bias)."""
    out = q_matmul(scaled(hn, cfg.ssm_in_multiplier), lp["ssm_in"])
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        out = out * jnp.asarray(mup_vector(cfg), out.dtype)
    di, dc = cfg.ssm_d_inner, cfg.ssm_conv_dim
    return out[..., :di], out[..., di:di + dc], out[..., di + dc:]


@jax.named_scope("ssm.conv")
def conv_window(cfg: ModelConfig, lp, padded):
    """silu(causal depthwise conv + bias) of ``padded`` [..., S + K - 1, C]
    (its first K - 1 positions the history) -> [..., S, C]."""
    k = cfg.ssm_d_conv
    s = padded.shape[-2] - (k - 1)
    w = lp["ssm_conv_w"].astype(F32)
    acc = lp["ssm_conv_b"].astype(F32)
    for j in range(k):  # tap j weighs the input K - 1 - j positions back
        acc = acc + w[j] * padded[..., j:j + s, :].astype(F32)
    return jax.nn.silu(acc).astype(padded.dtype)


def conv_tail(padded, n_true, taps: int):
    """The history of a causal conv of ``taps`` taps after ``n_true`` [B]
    true positions of ``padded`` [B, S + K - 1, C]: the last K - 1 TRUE
    inputs (``padded``'s own history where the row is shorter than that).
    The mixer's conv and LFM2's short convolution (``models/shortconv.py``)
    both cut their tails here."""
    idx = n_true[:, None] + jnp.arange(taps - 1)[None]  # [B, K-1]
    return jnp.take_along_axis(padded, idx[..., None], axis=1)


def true_lengths(live, b: int, s: int):
    """[B] true positions of each row of a (padded) prompt: ``live``
    [B, S] bool marks them, and they lead (None: all ``s``)."""
    return (jnp.full((b,), s, jnp.int32) if live is None
            else jnp.sum(live, axis=1, dtype=jnp.int32))


def split_xbc(cfg: ModelConfig, xbc):
    """xBC [..., conv_dim] -> (x [..., H, P], B [..., G, N], C [..., G, N])."""
    di, gn = cfg.ssm_d_inner, cfg.ssm_n_groups * cfg.ssm_d_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(*lead, cfg.ssm_n_heads, cfg.ssm_head_dim),
            xbc[..., di:di + gn].reshape(*lead, cfg.ssm_n_groups,
                                         cfg.ssm_d_state),
            xbc[..., di + gn:].reshape(*lead, cfg.ssm_n_groups,
                                       cfg.ssm_d_state))


def step_size(lp, dt_raw, live=None):
    """dt = softplus(dt_raw + dt_bias) in float32; 0 where ``live`` is
    false (a padded position: decay 1, increment 0).  No clamp."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + lp["ssm_dt_bias"].astype(F32))
    return dt if live is None else jnp.where(live[..., None], dt, 0.0)


def scan_sequential(cfg: ModelConfig, x, dt, a, bm, cm, d, h0=None):
    """The recurrence position by position.  ``x`` [B, S, H, P], ``dt``
    [B, S, H], ``a``, ``d`` [H], ``bm`` / ``cm`` [B, S, G, N], ``h0``
    [B, H, N, P] or None.  Returns (y [B, S, H, P] float32, the last
    state)."""
    b = x.shape[0]
    if h0 is None:
        h0 = jnp.zeros((b, cfg.ssm_n_heads, cfg.ssm_d_state,
                        cfg.ssm_head_dim), F32)

    def step(h, xs):
        y, h = pallas_ssm.ssm_update_xla(h, *xs[:2], a, *xs[2:], d)
        return h, y

    h, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), h


@jax.named_scope("ssm.scan")
def scan_chunked(cfg: ModelConfig, x, dt, a, bm, cm, d, h0=None):
    """The same numbers in chunks of ``cfg.ssm_chunk`` positions: inside a
    chunk the contributions of positions s <= t to position t are one
    masked matmul, the state before the chunk adds its decayed reading, and
    the state moves on by the chunk's decayed sum.  Any length: the tail is
    padded with dt = 0."""
    b, s, n_h, p = x.shape
    g, n = bm.shape[-2:]
    q = min(cfg.ssm_chunk, s)
    pad = -s % q
    x, dt, bm, cm = (jnp.pad(t.astype(F32), ((0, 0), (0, pad))
                             + ((0, 0),) * (t.ndim - 2))
                     for t in (x, dt, bm, cm))
    n_c, per = (s + pad) // q, n_h // g
    # Chunks lead, heads are (group, head of the group).
    xc = jnp.moveaxis(x.reshape(b, n_c, q, g, per, p), 1, 0)
    dtc = jnp.moveaxis(dt.reshape(b, n_c, q, g, per), 1, 0)
    bc = jnp.moveaxis(bm.reshape(b, n_c, q, g, n), 1, 0)
    cc = jnp.moveaxis(cm.reshape(b, n_c, q, g, n), 1, 0)
    a = a.astype(F32).reshape(g, per)
    if h0 is None:
        h0 = jnp.zeros((b, n_h, n, p), F32)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def chunk(h, xs):
        xq, dtq, bq, cq = xs          # [B, Q, G, per, P], [B, Q, G, per], ...
        cs = jnp.cumsum(dtq * a, axis=1)              # log decay up to t
        # decay from s to t (s <= t), times dt_s and C_t . B_s
        diff = cs[:, :, None] - cs[:, None, :]        # [B, t, s, G, per]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                                  -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", cq, bq, precision=HIGHEST)
        w = decay * cb[..., None] * dtq[:, None]
        y = jnp.einsum("btsgj,bsgjp->btgjp", w, xq, precision=HIGHEST)
        hg = h.reshape(b, g, per, n, p)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "btgn,bgjnp->btgjp", cq, hg, precision=HIGHEST)
        to_end = jnp.exp(cs[:, -1:] - cs) * dtq       # [B, Q, G, per]
        hg = (jnp.exp(cs[:, -1])[..., None, None] * hg
              + jnp.einsum("bsgj,bsgn,bsgjp->bgjnp", to_end, bq, xq,
                           precision=HIGHEST))
        return hg.reshape(b, n_h, n, p), y

    h, y = jax.lax.scan(chunk, h0.astype(F32), (xc, dtc, bc, cc))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, n_h, p)
    y = y + d.astype(F32)[:, None] * x
    return y[:, :s], h


@jax.named_scope("ssm.gate_norm")
def gate_norm(cfg: ModelConfig, lp, y, z):
    """RMSNorm over each group of ``y * silu(z)`` (the gate comes first:
    ``mamba_norm_before_gate`` false).  [..., d_inner], ``z``'s dtype."""
    yz = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = yz.reshape(*yz.shape[:-1], cfg.ssm_n_groups, -1)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(yz.shape)
    return (normed * lp["ssm_norm"].astype(F32)).astype(z.dtype)


@jax.named_scope("ssm.out_proj")
def out_proj(cfg: ModelConfig, lp, y):
    return scaled(q_matmul(y, lp["ssm_out"]), cfg.ssm_out_multiplier)


def _a(lp):
    return -jnp.exp(lp["ssm_a_log"].astype(F32))


def prompt_mix(cfg: ModelConfig, lp, hn, live=None, history=None, h0=None):
    """The mixer over a (padded) prompt or one chunk of it.  ``hn``
    [B, S, D]; ``live`` [B, S] bool marks the true positions, which lead
    (None: all S); ``history`` [B, K - 1, C] and ``h0`` [B, H, N, P] are
    what came before (None: a prompt's start, zeros).
    Returns (s [B, S, D], last state [B, H, N, P] float32, conv history
    [B, K - 1, C]) after the last TRUE position."""
    b, s, _ = hn.shape
    z, xbc, dt_raw = in_proj(cfg, lp, hn)
    if history is None:
        history = jnp.zeros((b, cfg.ssm_d_conv - 1, xbc.shape[-1]),
                            xbc.dtype)
    padded = jnp.concatenate([history.astype(xbc.dtype), xbc], axis=1)
    tail = conv_tail(padded, true_lengths(live, b, s), cfg.ssm_d_conv)
    x, bm, cm = split_xbc(cfg, conv_window(cfg, lp, padded))
    y, h = scan_chunked(cfg, x, step_size(lp, dt_raw, live), _a(lp), bm, cm,
                        lp["ssm_d"], h0)
    y = gate_norm(cfg, lp, y.reshape(b, s, -1), z)
    return out_proj(cfg, lp, y), h, tail


def decode_mix(cfg: ModelConfig, lp, hn, rec, layer, active=None):
    """One decode step's mixer.  ``hn`` [B, D]; ``rec`` the carry's
    ``(ssm [L, B, H, N, P], conv [L, K - 1, B, C])``; rows whose ``active``
    bit is off leave both untouched.  Returns (s [B, D], the carry)."""
    ssm, conv = rec
    b = hn.shape[0]
    z, xbc, dt_raw = in_proj(cfg, lp, hn)
    history = jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    padded = jnp.concatenate([history, xbc[None].astype(conv.dtype)],
                             axis=0)  # [K, B, C]
    with jax.named_scope("ssm.conv"):
        moved = padded[1:] if active is None else jnp.where(
            active[None, :, None], padded[1:], history)
        conv = jax.lax.dynamic_update_index_in_dim(conv, moved, layer, 0)
    x, bm, cm = split_xbc(
        cfg, conv_window(cfg, lp, jnp.moveaxis(padded, 0, 1))[:, 0])
    with jax.named_scope("ssm.update"):
        y, ssm = pallas_ssm.ssm_decode_update(
            ssm, x, step_size(lp, dt_raw), _a(lp), bm, cm, lp["ssm_d"],
            live=active, layer=layer, use_kernel=cfg.use_pallas_decode)
    y = gate_norm(cfg, lp, y.reshape(b, -1), z)
    return out_proj(cfg, lp, y), (ssm, conv)


def chunk_mix(cfg: ModelConfig, lp, hn, rec, layer, slot, first, live):
    """One chunk of a streamed prompt for ONE slot: the state and the conv
    history the slot's lane holds (zeros where this is the prompt's
    ``first`` chunk) go in, what the chunk leaves goes back.  ``hn``
    [1, C, D], ``live`` [1, C]: the chunk's true positions.  Returns
    (s [1, C, D], the carry)."""
    ssm, conv = rec
    h0 = jnp.where(first, 0.0, ssm[layer, slot])[None]
    history = jnp.where(first, jnp.zeros((), conv.dtype),
                        conv[layer, :, slot])[None]
    s, h, tail = prompt_mix(cfg, lp, hn, live, history, h0)
    return s, (ssm.at[layer, slot].set(h[0]),
               conv.at[layer, :, slot].set(tail[0]))
