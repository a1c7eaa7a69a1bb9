"""On-chip check that every Pallas attention kernel LOWERS and is RIGHT.

Calls the ``*_pallas`` functions directly with ``interpret=False`` — never
the dispatchers, which off a TPU backend would compare XLA with XLA — at the
head layouts of every model ``models/configs.py`` lists (and the shard-local
layouts a ``tensor=4`` mesh hands each kernel), and checks each result
against the XLA reference.  Shapes a ``supports*`` gate rejects are listed
as GATED with the gate's reason and not run.

Every case runs; the exit code is non-zero if any failed to lower or
missed parity.  Needs a TPU (fails on any other backend).  Timing is not
this tool's job, speed comes from the benchmark's device trace, with one
exception: the ``live-rows`` cases also print what a decode kernel costs a
layer, beside the grid steps it walks (the schedule's: the live rows'
tiles): the lane kernel at 32 slots when 5 or all 32 of them decode at 150
positions, at Qwen's and OLMoE's layouts, and at SmallThinker's full lanes
(rows of 4-10k in lanes of 16,384) and Falcon-H1's (64 slots, rows of
~450), and the latent kernel at GLM's rows (~1,400 of 4,096); host clock
round one program of calls, as a model's layer loop makes them, and a
digest of the live rows' output, which two trees' kernels must share; and
the ``ssm-update`` cases what the state-space decode update costs a layer at
64 slots of Falcon-H1-34B's state (32 x 256 x 128 float32) with 64, 8 and 1
of them live, beside the time its bytes would take at the chip's bandwidth;
and the ``moe-reuse`` cases what the int8 expert matmul costs a call at
Mixtral's, OLMoE's, GLM's, Ling's, SmallThinker's and LFM2's decode shapes
with every touched group in one row tile and with two groups in two and
three, beside the steps its grid walks of the layout's, and with nothing
touched, and a digest of the used rows' output; and the ``moe-dispatch``
cases what the expert dispatch's way in costs alone (the token rows into the
expert-grouped layout) by the row scatter and by the gather from the sorted
layout, at the cells' chunk, prompt and decode shapes: where
``transformer._SCATTER_MAX_ASSIGN`` comes from; and the ``decode-tail``
cases what a decode step's tail costs alone (the head's logits -> sampler
-> logprobs) at Falcon-H1's, Qwen's and LFM2's slots x vocabulary, asked for
and not asked for, and a digest of the asked branch's three outputs beside
the unconditional call's (the program's before PR 56); and the
``chunk-attend time`` cases what the chunk attend costs a call through its
dispatcher at SmallThinker's full lane and ring-with-chunk and at LFM2's
packed rows, a 1,024-token chunk at the start, the middle and the end of
the mix's prompts, and a digest of the output.

    python tools/onchip_pallas_check.py            # on the chip
"""

from __future__ import annotations

import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu import runtime
from llm_instance_gateway_tpu.models.transformer import (
    _gathers_in,
    _kv_dequantize,
    _kv_quantize,
    _lay_out,
    _layout_source,
)
from llm_instance_gateway_tpu.ops import attention as xla_att
from llm_instance_gateway_tpu.ops import pallas_attention as flash
from llm_instance_gateway_tpu.ops import pallas_decode_attention as pdec
from llm_instance_gateway_tpu.ops import pallas_moe as pmoe
from llm_instance_gateway_tpu.ops import pallas_ssm as pssm
from llm_instance_gateway_tpu.server import engine as engine_lib
from llm_instance_gateway_tpu.server.sampling import sample_routed

# Parity bound, per element: |kernel - reference| <= TOL * max(1, |reference|).
# Both sides accumulate in f32 and round the output once to bf16 (half an
# ulp = 2^-9 relative each), and both round the softmax weights to bf16
# before the PV matmul — the kernel rounds the UN-normalised exp(s - m), the
# reference the normalised probability, so their per-term 2^-9 errors differ.
# Inputs are unit normals, so outputs are O(1).  4 bf16 ulps (2^-7 each)
# covers that; a wrong mask, head mapping or block index is an O(1) error
# and fails by two orders of magnitude.  The int8 cases compare against
# dequantise-then-XLA on the SAME int8 data; the reference rounds the scale
# to bf16 where the kernel keeps it f32 (2^-9 relative on every K and V
# element), so they get twice the bound.
TOL_BF16 = 4 * 2.0 ** -7
TOL_INT8 = 8 * 2.0 ** -7

# (label, q heads, kv heads, head dim): each listed model, then what one
# shard of a tensor=4 mesh sees (sharded_attention splits whole kv groups).
LAYOUTS = (
    ("llama2-7b g=1", 32, 32, 128),
    ("llama3-8b/mixtral-8x7b g=4", 32, 8, 128),
    ("gemma-2b g=8 hd256", 8, 1, 256),
    ("gemma-7b g=1 hd256", 16, 16, 256),
    ("qwen2.5-7b g=7", 28, 4, 128),
    ("qwen2.5-7b/tensor=4 shard g=7 kv=1", 7, 1, 128),
    ("llama3-8b/tensor=4 shard g=4 kv=2", 8, 2, 128),
    ("olmoe-1b-7b g=1 kv=16", 16, 16, 128),
)

# The grouped expert matmul (ops/pallas_moe): (label, E, K, N, assignments),
# decode- and prefill-sized, at the two sparse models' expert shapes.
MOE_SHAPES = (
    ("olmoe gate/up decode 32x8", 64, 2048, 1024, 256),
    ("olmoe down decode 32x8", 64, 1024, 2048, 256),
    ("olmoe gate/up prefill 1024x8", 64, 2048, 1024, 8192),
    ("mixtral gate/up decode 32x2", 8, 4096, 14336, 64),
    ("mixtral down decode 32x2", 8, 14336, 4096, 64),
    ("mixtral down prefill 1024x2", 8, 14336, 4096, 2048),
)

# What a second and third row tile of one expert cost the grouped matmul,
# and what the tiles past the last group cost it: (label, E, K, N,
# assignments the layout is sized for, experts touched, rows held: fewer than
# the assignments where the chip holds a share of the experts), decode-sized,
# at the shapes whose column is cut into several K blocks (Mixtral: nk 2 and
# 8), at those whose column is one block (OLMoE, GLM-4.7-Flash, LFM2) and at
# the narrow experts of Ling (a quarter of the routing lands on the 128 held
# here) and SmallThinker, whose layouts are mostly empty; and a step none of
# whose rows routes here.
MOE_REUSE_SHAPES = (
    ("mixtral gate/up decode 32x2", 8, 4096, 14336, 64, 7, 64),
    ("mixtral down decode 32x2", 8, 14336, 4096, 64, 7, 64),
    ("olmoe gate/up decode 32x8", 64, 2048, 1024, 256, 36, 256),
    ("glm-4.7-flash gate/up decode 32x4", 64, 2048, 1536, 128, 27, 128),
    ("glm-4.7-flash down decode 32x4", 64, 1536, 2048, 128, 27, 128),
    ("ling-3.0-flash gate/up decode 64x8", 128, 2560, 768, 512, 30, 128),
    ("ling-3.0-flash down decode 64x8", 128, 768, 2560, 512, 30, 128),
    ("smallthinker gate/up decode 32x6", 64, 2560, 768, 192, 14, 192),
    ("smallthinker down decode 32x6", 64, 768, 2560, 192, 14, 192),
    ("lfm2 gate/up decode 64x4", 64, 2048, 1536, 256, 48, 256),
    ("ling-3.0-flash gate/up decode, nothing held", 128, 2560, 768, 512, 0,
     0),
)

# The expert dispatch's way in (``transformer._lay_out``): (label, tokens,
# k, E, d_model) of the sparse cells' chunk and prompt programs, of the
# shortest prompts that gather, and of their decode steps at 32 slots.
MOE_DISPATCH_SHAPES = (
    ("smallthinker chunk 1024x6", 1024, 6, 64, 2560),
    ("glm-4.7-flash chunk 1024x4", 1024, 4, 64, 2048),
    ("mixtral prompt 1024x2", 1024, 2, 8, 4096),
    ("olmoe prompt 64x8", 64, 8, 64, 2048),
    ("smallthinker prompt 64x6", 64, 6, 64, 2560),
    ("mixtral prompt 128x2", 128, 2, 8, 4096),
    ("olmoe decode 32x8", 32, 8, 64, 2048),
    ("mixtral decode 32x2", 32, 2, 8, 4096),
    ("glm-4.7-flash decode 32x4", 32, 4, 64, 2048),
    ("smallthinker decode 32x6", 32, 6, 64, 2560),
)

DTYPE = jnp.bfloat16


def _keys(seed, n):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _lengths(b, s_max):
    """Mixed row lengths: 1, a block edge, a straddle, full."""
    base = [1, 128, s_max // 2 + 17, s_max, 129, s_max - 1, 77, 512]
    return jnp.asarray([min(s_max, base[i % len(base)]) for i in range(b)],
                       jnp.int32)


def _scaled_err(out, ref):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not out.size:  # a call that used no row: nothing to compare
        return 0.0
    if not np.all(np.isfinite(out)):
        return float("inf")
    return float(np.max(np.abs(out - ref) / np.maximum(1.0, np.abs(ref))))


def _us_a_call(run, calls):
    """(least, median) of five timings of ``run()``, which blocks until its
    program of ``calls`` calls is done, after one run to compile it."""
    run()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        run()
        times.append((time.perf_counter() - t0) / calls * 1e6)
    return min(times), sorted(times)[2]


def case_flash(h, n_kv, hd, s, b=1):
    kq, kk, kv = _keys(0, 3)
    q = jax.random.normal(kq, (b, s, h, hd), DTYPE)
    k = jax.random.normal(kk, (b, s, n_kv, hd), DTYPE)
    v = jax.random.normal(kv, (b, s, n_kv, hd), DTYPE)

    def kernel(q, k, v):
        out = flash.flash_attention_bhsd(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), interpret=False)
        return out.transpose(0, 2, 1, 3)

    return (jax.jit(kernel)(q, k, v),
            jax.jit(xla_att.prefill_attention)(q, k, v), TOL_BF16)


def case_decode(h, n_kv, hd, s_max, quant, b=16):
    kq, kk, kv = _keys(1, 3)
    q = jax.random.normal(kq, (b, h, hd), DTYPE)
    kf = jax.random.normal(kk, (b, s_max, n_kv, hd), jnp.float32)
    vf = jax.random.normal(kv, (b, s_max, n_kv, hd), jnp.float32)
    lengths = _lengths(b, s_max)

    def stacked(x):
        # The kernel reads layer 1 of a stack of two (layer 0 is poison):
        # the layer index rides the index map, as in the model's layer loop.
        return jnp.stack([jnp.full_like(x, 100), x])

    if quant:
        k8, ks = _kv_quantize(kf)
        v8, vs = _kv_quantize(vf)
        out = jax.jit(lambda q, *a: pdec.decode_attention_quant_pallas(
            q, *map(stacked, a), lengths, layer=jnp.int32(1),
            interpret=False))(q, k8, v8, ks, vs)
        ref = jax.jit(lambda q, k8, v8, ks, vs, l: xla_att.decode_attention(
            q, _kv_dequantize(k8, ks, q.dtype),
            _kv_dequantize(v8, vs, q.dtype), l))(q, k8, v8, ks, vs, lengths)
        return out, ref, TOL_INT8
    kc, vc = kf.astype(DTYPE), vf.astype(DTYPE)
    out = jax.jit(lambda q, kc, vc: pdec.decode_attention_pallas(
        q, stacked(kc), stacked(vc), lengths, layer=jnp.int32(1),
        interpret=False))(q, kc, vc)
    ref = jax.jit(xla_att.decode_attention)(q, kc, vc, lengths)
    return out, ref, TOL_BF16


def _spread(b, n_live, held):
    """``n_live`` rows of ``held`` positions spread over ``b`` slots, the
    others at length 0 (``transformer.decode_step``: ``active`` off)."""
    live = np.zeros(b, bool)
    live[np.linspace(0, b - 1, n_live).astype(int)] = True
    return np.where(live, held, 0)


def _ragged(b, low, high, seed):
    """``b`` live rows of ``low`` .. ``high`` positions, as a closed loop's
    slots hold them."""
    return np.random.RandomState(seed).randint(low, high + 1, b)


def case_live_rows(h, n_kv, hd, s_max, lengths, n_layers=8, calls=400,
                   latent=0):
    """A decode kernel as a decode step runs it over rows of ``lengths``
    positions (0: a slot that does not decode): the lane kernel over
    [L, B, S, K, hd] lanes, or with ``latent`` (a row's value columns) the
    latent kernel over [L, B, S, hd] rows.  Parity on every row (a dead
    row's is zeros on both sides), the grid steps the schedule holds of the
    slots x tiles rectangle, the time a layer of one program that walks the
    stacked cache ``calls`` times, and a digest of the live rows' output."""
    import hashlib

    b = len(lengths)
    lens = jnp.asarray(lengths, jnp.int32)
    live = np.asarray(lengths) > 0
    kq, kk, kv = _keys(6, 3)
    q = jax.random.normal(kq, (b, h, hd), DTYPE)
    if latent:
        block = pdec._mla_block(s_max)
        cache = (jax.random.normal(kk, (n_layers, b, s_max, hd), DTYPE),)
        kernel = lambda q, layer, rows: pdec.mla_decode_attention_pallas(
            q, rows, lens, latent, 1 / 16, layer=layer, interpret=False)
        ref = lambda q, rows: xla_att.latent_decode_attention(
            q, rows[1], lens, latent, 1 / 16)
    else:
        block = pdec._pick_block(s_max, n_kv * hd * 2)
        cache = tuple(jax.random.normal(key, (n_layers, b, s_max, n_kv, hd),
                                        DTYPE) for key in (kk, kv))
        kernel = lambda q, layer, kc, vc: pdec.decode_attention_pallas(
            q, kc, vc, lens, layer=layer, interpret=False)
        ref = lambda q, kc, vc: xla_att.decode_attention(
            q, kc[1], vc[1], lens)
    steps = int(np.sum(-(-np.asarray(lengths) // block)))

    @jax.jit
    def loop(q, *cache):
        def body(acc, layer):
            out = kernel(q + acc.astype(q.dtype), layer, *cache)
            return out[..., :1].astype(jnp.float32) * 1e-6, None
        layers = jnp.arange(calls, dtype=jnp.int32) % n_layers
        return jax.lax.scan(body, jnp.zeros((b, h, 1), jnp.float32),
                            layers)[0]

    least, median = _us_a_call(
        lambda: loop(q, *cache).block_until_ready(), calls)
    out = jax.jit(lambda q, *cache: kernel(q, jnp.int32(1), *cache))(
        q, *cache)
    digest = hashlib.sha256(
        np.asarray(out.astype(jnp.float32))[live].tobytes()).hexdigest()[:12]
    print(f"TIME   {'latent' if latent else 'lane'}-decode "
          f"{int(live.sum())}/{b} live rows x{int(np.sum(lengths)) // max(1, live.sum())} "
          f"h={h} kv={n_kv} s_max={s_max}: {least:.1f} us a layer (median "
          f"{median:.1f}, {calls} calls a program); {steps} live tiles of "
          f"the {b * (s_max // block)} of slots x tiles; live rows' output "
          f"sha256 {digest}", flush=True)
    return out, jax.jit(ref)(q, *cache) * live[:, None, None], TOL_BF16


def case_paged(h, n_kv, hd, block, quant, b=16, s_max=2048):
    m = s_max // block
    kq, kk, kv = _keys(3, 3)
    q = jax.random.normal(kq, (b, h, hd), DTYPE)
    n_blocks = b * m
    kf = jax.random.normal(kk, (n_blocks + 1, block, n_kv, hd), jnp.float32)
    vf = jax.random.normal(kv, (n_blocks + 1, block, n_kv, hd), jnp.float32)
    rng = np.random.RandomState(11)
    tables = jnp.asarray(
        (rng.permutation(n_blocks) + 1).reshape(b, m), jnp.int32)
    lengths = _lengths(b, s_max)

    def rows(pool, tabs):
        return xla_att.gather_pool_rows(pool, tabs)

    if quant:
        k8, ks = _kv_quantize(kf)
        v8, vs = _kv_quantize(vf)
        out = jax.jit(lambda *a: pdec.paged_decode_attention_pallas(
            *a, interpret=False))(q, k8, v8, tables, lengths, ks, vs)
        ref = jax.jit(lambda q, k8, v8, t, l, ks, vs: xla_att.decode_attention(
            q, _kv_dequantize(rows(k8, t), rows(ks, t), q.dtype),
            _kv_dequantize(rows(v8, t), rows(vs, t), q.dtype), l))(
                q, k8, v8, tables, lengths, ks, vs)
        return out, ref, TOL_INT8
    kp, vp = kf.astype(DTYPE), vf.astype(DTYPE)
    out = jax.jit(lambda *a: pdec.paged_decode_attention_pallas(
        *a, interpret=False))(q, kp, vp, tables, lengths)
    ref = jax.jit(lambda q, kp, vp, t, l: xla_att.decode_attention(
        q, rows(kp, t), rows(vp, t), l))(q, kp, vp, tables, lengths)
    return out, ref, TOL_BF16


def case_chunk(h, n_kv, hd, c, s_max, start):
    kq, kk, kv = _keys(4, 3)
    q = jax.random.normal(kq, (1, c, h, hd), DTYPE)
    kc = jax.random.normal(kk, (1, s_max, n_kv, hd), DTYPE)
    vc = jax.random.normal(kv, (1, s_max, n_kv, hd), DTYPE)
    off = jnp.int32(start)
    block_q, block_k = flash.chunk_blocks(c, s_max, h // n_kv, hd)
    out = jax.jit(lambda *a: flash.chunk_attention_pallas(
        *a, block_q=block_q, block_k=block_k, interpret=False))(
            q, kc, vc, off)
    ref = jax.jit(xla_att.xla_chunk_attention)(q, kc, vc, off)
    return out, ref, TOL_BF16


def case_chunk_timed(h, n_kv, hd, s_max, starts, window=0, pack=1, c=1024,
                     calls=60):
    """The chunk attend as a chunk program's layers call it, through the
    dispatcher (``pack`` > 1: narrow kv heads packed to a 128-lane row, the
    queries as the model has them): a ``c``-token chunk at each of
    ``starts`` of an ``s_max`` lane (a window layer's: the ring in position
    order with the chunk behind it, ``start`` at most the ring).  Prints us a
    call (one program of ``calls`` calls, each hanging on the one before)
    and a digest of the output, which two trees' kernels share where their
    tiles are equal (a head's recurrence sees the same tiles in the same
    order whichever grid walks them); parity at the last of ``starts``."""
    import hashlib

    kq, kk, kv = _keys(9, 3)
    q = jax.random.normal(kq, (1, c, h, hd), DTYPE)
    kc = jax.random.normal(kk, (1, s_max, n_kv, hd), DTYPE)
    vc = jax.random.normal(kv, (1, s_max, n_kv, hd), DTYPE)
    kp, vp = xla_att.pack_heads(kc, pack), xla_att.pack_heads(vc, pack)

    def attend(q, off):
        return flash.chunk_attention(q, kp, vp, off, window=window, pack=pack)

    @jax.jit
    def loop(q, off):
        def body(acc, _):
            out = attend(q + acc.astype(q.dtype), off)
            return out[..., :1].astype(jnp.float32) * 1e-6, None
        return jax.lax.scan(body, jnp.zeros((1, c, h, 1), jnp.float32),
                            None, length=calls)[0]

    once = jax.jit(attend)
    for start in starts:
        off = jnp.int32(start)
        least, median = _us_a_call(
            lambda: loop(q, off).block_until_ready(), calls)
        out = once(q, off)
        digest = hashlib.sha256(
            np.asarray(out.astype(jnp.float32)).tobytes()).hexdigest()[:12]
        print(f"TIME   chunk-attend c={c} start={start} h={h} kv={n_kv} "
              f"hd={hd} pack={pack} s_max={s_max} window={window}: "
              f"{least:.1f} us a call (median {median:.1f}, {calls} calls a "
              f"program); output sha256 {digest}", flush=True)
    ref = jax.jit(lambda q, kc, vc, off: xla_att.xla_chunk_attention(
        q, kc, vc, off, window))(q, kc, vc, off)
    return out, ref, TOL_BF16


def case_packed(op, h=32, n_kv=8, hd=64, pack=2, s_max=8192, start=3072):
    """LFM2's 64-wide heads through the DISPATCHERS as the model calls
    them: two kv heads to a 128-lane row (``xla_att.pack_heads``), the
    queries as the model has them; against the XLA form on the heads
    unpacked.  ``op``: "decode" (64 rows over layer 1 of [2, 64, S, 4, 128]
    lanes), "flash" (a 1,024-token bucket) or "chunk" (a 1,024-token chunk
    at ``start`` of an ``s_max`` lane)."""
    kq, kk, kv = _keys(7, 3)
    if op == "decode":
        b = 64
        q = jax.random.normal(kq, (b, h, hd), DTYPE)
        kc = jax.random.normal(kk, (b, s_max, n_kv, hd), DTYPE)
        vc = jax.random.normal(kv, (b, s_max, n_kv, hd), DTYPE)
        lengths = jnp.asarray(_ragged(b, 768, 5632, 4), jnp.int32)
        stacked = lambda x: jnp.stack([jnp.full_like(x, 100), x])
        out = jax.jit(lambda q, kc, vc: pdec.decode_attention(
            q, stacked(xla_att.pack_heads(kc, pack)),
            stacked(xla_att.pack_heads(vc, pack)), lengths,
            layer=jnp.int32(1), pack=pack))(q, kc, vc)
        return out, jax.jit(xla_att.decode_attention)(q, kc, vc, lengths), TOL_BF16
    s = 1024
    q = jax.random.normal(kq, (1, s, h, hd), DTYPE)
    if op == "flash":
        k = jax.random.normal(kk, (1, s, n_kv, hd), DTYPE)
        v = jax.random.normal(kv, (1, s, n_kv, hd), DTYPE)
        out = jax.jit(lambda q, k, v: flash.flash_attention(
            q, xla_att.pack_heads(k, pack), xla_att.pack_heads(v, pack),
            pack=pack))(q, k, v)
        return out, jax.jit(xla_att.prefill_attention)(q, k, v), TOL_BF16
    kc = jax.random.normal(kk, (1, s_max, n_kv, hd), DTYPE)
    vc = jax.random.normal(kv, (1, s_max, n_kv, hd), DTYPE)
    off = jnp.int32(start)
    out = jax.jit(lambda q, kc, vc, off: flash.chunk_attention(
        q, xla_att.pack_heads(kc, pack), xla_att.pack_heads(vc, pack), off,
        pack=pack))(q, kc, vc, off)
    return out, jax.jit(xla_att.xla_chunk_attention)(q, kc, vc, off), TOL_BF16


def case_moe(e, k, n, m, quant):
    """Skewed groups (expert 0 holds a quarter of the rows, one expert
    none) over layer 1 of a stack of two, against the XLA tiles on the same
    (int8) weights: both round x to bf16 and accumulate in f32."""
    from llm_instance_gateway_tpu.ops.quant import quantize_weight

    kw, kx, kg = _keys(5, 3)
    w = jax.random.normal(kw, (2, e, k, n), DTYPE) / jnp.sqrt(k).astype(DTYPE)
    w = quantize_weight(w) if quant else w
    tm = pmoe.tile_rows(m, e)
    n_tiles = pmoe.n_tiles(m, e, tm)
    rest = jax.random.randint(kg, (m - m // 4,), 1, max(2, e - 1))
    sizes = jnp.zeros((e,), jnp.int32).at[0].add(m // 4).at[rest].add(1)
    _, te, n_used = pmoe.tile_plan(sizes, tm, n_tiles)
    x = jax.random.normal(kx, (n_tiles * tm, k), DTYPE)
    out = jax.jit(lambda x, w, te, nu: pmoe.grouped_matmul_pallas(
        x, w, te, nu, 1, tm=tm))(x, w, te, n_used)
    ref = jax.jit(lambda x, w, te: pmoe.grouped_matmul_xla(
        x, w, te, 1, tm=tm))(x, w, te)
    used = int(n_used) * tm  # the rest the kernel does not write
    return out[:used], ref[:used], TOL_BF16


def _group_sizes(m, e, touched, tm, skewed):
    """``m`` rows over ``touched`` of ``e`` experts, spread over the experts'
    range as a router spreads them (the untouched lie between the touched):
    evenly, every group inside one tile; or the same rows skewed so that
    the first group takes three tiles and the second two, the others still
    one each."""
    if not touched:
        return jnp.zeros((e,), jnp.int32)
    heads = [2 * tm + 1, tm + 1] if skewed else []
    rest, left = touched - len(heads), m - sum(heads)
    sizes = heads + [left // rest + (i < left % rest) for i in range(rest)]
    assert 1 <= min(sizes) and max(sizes[len(heads):]) <= tm, sizes
    where = np.linspace(0, e - 1, touched).astype(int)
    return jnp.zeros((e,), jnp.int32).at[where].set(jnp.asarray(sizes))


def _moe_grid_steps(k, n, e, tm, tiles, n_used, touched):
    """(the steps ``moe_gmm_int8``'s grid walks on the chip for a plan that
    holds ``touched`` experts' groups in ``n_used`` of ``tiles`` tiles, the
    steps of the whole layout: what it walked before PR 61 and what the
    interpreter walks).  Reckoned here and not asked of the kernel's
    ``_steps`` and ``_grid``, so that this file still runs when copied into
    an older tree to time it (``tests/test_moe_tiles_counter.py`` holds the
    two equal)."""
    tk, tn = pmoe._blocks(k, n, 1)
    by_group = pmoe._by_group(tiles * tm, k, n, tn, 1)
    a_step = (n // tn) * (k // tk)
    return (a_step * (touched if by_group else n_used),
            a_step * (min(e, tiles) if by_group else tiles))


def case_moe_reuse(e, k, n, m, touched, held, n_layers=4, calls=100):
    """The int8 grouped matmul as a decode step's layer loop calls it, in a
    layout sized for ``m`` assignments: ``held`` rows over ``touched``
    experts, once with every group in one tile and once skewed
    (``_group_sizes``), through a stack of ``n_layers`` layers, ``calls``
    calls a program.  Prints us a call (host clock, least of five), the
    grid's steps beside the whole layout's, and the bytes/s that each
    TOUCHED expert's [K, N] int8 matrix read ONCE a call implies: what a
    tile more of the same expert costs is the difference of the two lines.
    Parity of the skewed plan against the XLA tiles on the same weights,
    over the rows of the used tiles (the rest the kernel does not write),
    and a digest of those rows, which two trees' kernels must share."""
    import hashlib

    kw, ks, kx = _keys(8, 3)
    # one [K, N] matrix a draw: a draw of the whole stack would take four
    # times its bytes in 32-bit words
    draw = jax.jit(lambda key: jax.lax.bitcast_convert_type(
        jax.random.bits(key, (k, n), jnp.uint8), jnp.int8))
    q = jnp.stack([jnp.stack([draw(key) for key in jax.random.split(kl, e)])
                   for kl in jax.random.split(kw, n_layers)])
    scale = (1 + 0.1 * jax.random.uniform(ks, (n_layers, e, n), jnp.float32)
             ) / (74.0 * np.sqrt(k))
    w = {"q": q, "s": scale}
    tm = pmoe.tile_rows(m, e)
    n_tiles = pmoe.n_tiles(m, e, tm)
    x = jax.random.normal(kx, (n_tiles * tm, k), DTYPE)

    @jax.jit
    def loop(x, w, te, n_used):
        def body(nu, layer):
            out = pmoe.grouped_matmul_pallas(x, w, te, nu, layer, tm=tm)
            # never true; ties each call to the one before (row 0 is
            # unwritten, and may hold anything, only where nothing is used)
            return nu + ((out[0, 0] > 1e30) & (nu > 0)).astype(jnp.int32), None
        layers = jnp.arange(calls, dtype=jnp.int32) % n_layers
        return jax.lax.scan(body, n_used, layers)[0]

    for skewed in (False, True) if touched else (False,):
        _, te, n_used = pmoe.tile_plan(
            _group_sizes(held, e, touched, tm, skewed), tm, n_tiles)
        least, median = _us_a_call(
            lambda: loop(x, w, te, n_used).block_until_ready(), calls)
        walked, laid_out = _moe_grid_steps(k, n, e, tm, n_tiles, int(n_used),
                                           touched)
        print(f"TIME   moe-reuse K={k} N={n} blocks={pmoe._blocks(k, n, 1)} "
              f"{held} rows, {touched} experts in {int(n_used)} tiles of {tm} "
              f"of the layout's {n_tiles}: {least:.1f} us a call (median "
              f"{median:.1f}, {calls} calls a program), {walked} grid steps "
              f"of the layout's {laid_out}: "
              f"{touched * k * n / least / 1e3:.0f} GB/s of the touched "
              f"experts' bytes read once", flush=True)
    out = jax.jit(lambda x, w, te, nu: pmoe.grouped_matmul_pallas(
        x, w, te, nu, 1, tm=tm))(x, w, te, n_used)
    ref = jax.jit(lambda x, w, te: pmoe.grouped_matmul_xla(
        x, w, te, 1, tm=tm))(x, w, te)
    used = int(n_used) * tm
    digest = hashlib.sha256(np.asarray(
        out[:used].astype(jnp.float32)).tobytes()).hexdigest()[:12]
    print(f"       moe-reuse K={k} N={n}: the {used} used rows' output "
          f"sha256 {digest}", flush=True)
    return out[:used], ref[:used], TOL_BF16


def case_moe_dispatch(t, k, e, d, calls=100, runs=100):
    """The way in alone: ``t`` token rows of ``d`` into the layout of their
    ``t * k`` assignments over ``e`` experts (a skewed draw; a fifth of a
    decode batch's rows dead), by the scatter and by the gather with its
    source map, each a program of ``calls`` calls whose inputs hang on the
    call before (nothing is hoisted, the layout is written whole).  Prints us
    a call (host clock, median of ``runs`` programs) beside the time the
    layout's bytes take at 819 GB/s; parity: the two layouts are equal."""
    kx, kl, kb, kd = _keys(11, 4)
    n = t * k
    tm = pmoe.tile_rows(n, e)
    n_rows = pmoe.n_tiles(n, e, tm) * tm
    xf = jax.random.normal(kx, (t, d), DTYPE)
    _, topi = jax.lax.top_k(jax.random.normal(kl, (t, e))
                            + 0.7 * jax.random.normal(kb, (e,)), k)
    dead = jax.random.uniform(kd, (t,)) < (0.2 if t <= 32 else 0.0)
    expert = jnp.where(jnp.repeat(dead, k), e, topi.reshape(-1)).astype(
        jnp.int32)
    chose = (expert[:, None] == jnp.arange(e)).astype(jnp.int32)
    sizes = jnp.sum(chose, axis=0)
    first_row, _, _ = pmoe.tile_plan(sizes, tm, n_rows // tm)
    rank = jnp.sum((jnp.cumsum(chose, axis=0) - 1) * chose, axis=-1)
    row = jnp.where(expert < e, first_row[jnp.minimum(expert, e - 1)] + rank,
                    n_rows)
    args = (xf, expert, sizes, row)

    def scatter(xf, expert, sizes, row):
        return _lay_out(xf, {"src": None, "row": row, "n_rows": n_rows}, k)

    def gather(xf, expert, sizes, row):
        return _lay_out(
            xf, {"src": _layout_source(expert, sizes, k, tm, n_rows)}, k)

    def us_a_call(form):
        @jax.jit
        def loop(*args):
            def body(c, _):  # c stays 0, which the compiler cannot know
                out = jax.lax.optimization_barrier(form(
                    *(a + c.astype(a.dtype) for a in args)))
                return c + (out[0, 0] > 3e4).astype(jnp.int32), None
            return jax.lax.scan(body, jnp.int32(0), None, length=calls)[0]

        assert int(loop(*args)) == 0
        times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            loop(*args).block_until_ready()
            times.append((time.perf_counter() - t0) / calls * 1e6)
        return sorted(times)[runs // 2]

    moved = (n_rows + n) * d * 2  # the layout written, the rows read
    print(f"TIME   moe-dispatch {n} assignments -> [{n_rows}, {d}] "
          f"(tiles of {tm}): scatter {us_a_call(scatter):.1f} us, gather "
          f"{us_a_call(gather):.1f} us a call (median of {runs} programs of "
          f"{calls}); the bytes at 819 GB/s {moved / 819e3:.1f} us; the "
          f"model takes the {'gather' if _gathers_in(n, e) else 'scatter'}",
          flush=True)
    return jax.jit(gather)(*args), jax.jit(scatter)(*args), 0.0


def case_decode_tail(b, v, calls=50):
    """A decode step's tail alone, as ``Engine._decode_impl`` makes it after
    ``lm_head``: float32 logits ``[b, v]`` of greedy rows, each with a
    ``logit_bias`` -> ``sample_routed`` -> the logprobs under
    ``_logprobs_if_asked``, a program of ``calls`` steps whose logits hang on
    the step before (materialised once a step behind a barrier, as the head
    writes them), run with the predicate true and false (one program, the
    predicate its argument).  Prints us a step, least of five, and the
    sha256 of the three outputs of one asked call beside the unconditional
    call's (``_logprob_info``, the program before PR 56): the tokens and the
    top-K ids have to agree, the log-probabilities to float32 rounding (on
    the CPU the digests are equal; on the v5e the two programs sum a row's
    exponentials in another order and differ by ~2e-6 in a value of ~15).
    The times order the two branches and are no measure of either inside
    ``jit_decode_block``: asked less not asked read 1.66 ms here at
    ``[64, 261120]`` where the traced program's ``logprobs`` scope reads
    0.54 ms a step with every step asked (``PERF.md`` section 6, PR 56),
    and the unconditional form in this loop 22 ms, so it is not timed.  A
    tree without ``_logprobs_if_asked`` prints the unconditional call's
    digest alone."""
    import hashlib

    kl, kb = _keys(13, 2)
    f32 = jnp.float32
    logits = 4.0 * jax.random.normal(kl, (b, v), f32)
    bias_ids = jax.random.randint(kb, (b, engine_lib.MAX_LOGIT_BIAS), 32, 127)
    bias_vals = jnp.full(bias_ids.shape, 8.0, f32)
    rows = jnp.zeros((b,), jnp.int32)
    gate = getattr(engine_lib, "_logprobs_if_asked", None)
    forms = {"every step": lambda asked, lg, tok: engine_lib._logprob_info(
        lg, tok, v)}
    if gate is not None:
        forms["gated"] = lambda asked, lg, tok: gate(asked, lg, tok, v)

    def tail(form, asked, lg):
        tok, _ = sample_routed(
            lg, jax.random.PRNGKey(0), rows.astype(f32), rows,
            jnp.ones((b,), f32), valid_vocab=v, seeds=rows - 1,
            positions=rows, bias_ids=bias_ids, bias_vals=bias_vals,
            live=rows == 0)
        return (tok, *form(asked, lg, tok))

    def us_a_step(form, asked):
        @jax.jit
        def loop(asked, logits):
            def body(c, _):  # c stays 0, which the compiler cannot know
                lg = jax.lax.optimization_barrier(logits + c)
                tok, lp, top_v, top_i = tail(form, asked, lg)
                return c + (lp[0] + top_v[0, 0] + tok[0] + top_i[0, 0]
                            > 3e9).astype(f32), None
            return jax.lax.scan(body, f32(0), None, length=calls)[0]

        def run():
            loop(asked, logits).block_until_ready()
        return _us_a_call(run, calls)[0]

    def digest(form):
        outs = jax.jit(lambda asked, lg: tail(form, asked, lg))(True, logits)
        return outs, hashlib.sha256(b"".join(
            np.asarray(a).tobytes() for a in outs[1:])).hexdigest()[:16]

    want, want_digest = digest(forms["every step"])
    line = f"TIME   decode-tail [{b}, {v}]:"
    got = want
    if gate is not None:
        got, got_digest = digest(forms["gated"])
        apart = max(float(jnp.max(jnp.abs(g - w)))
                    for g, w in zip(got[1:3], want[1:3]))
        line += (f" asked {us_a_step(forms['gated'], True):.1f} us a step, "
                 f"not asked {us_a_step(forms['gated'], False):.1f} (least "
                 f"of five programs of {calls}); sha256 of the asked outputs "
                 f"{got_digest}, the largest difference of a "
                 f"log-probability {apart:.3g};")
        if not (bool(jnp.all(got[0] == want[0]))
                and bool(jnp.all(got[3] == want[3]))):
            raise AssertionError("the asked branch's tokens or top-K ids "
                                 "are not the unconditional call's")
    print(f"{line} sha256 of the unconditional call's {want_digest}; one "
          f"pass over the float32 logits at 819 GB/s "
          f"{b * v * 4 / 819e3:.1f} us", flush=True)
    # Two compiled programs sum a row's 65-261k exponentials in another
    # order: float32 rounding of a log-probability of size ~15.
    return (jnp.concatenate([got[1][:, None], got[2]], axis=1),
            jnp.concatenate([want[1][:, None], want[2]], axis=1), 1e-5)


def case_ssm_update(n_live, b=64, h=32, g=2, n=256, p=128, n_layers=8,
                    calls=160):
    """``ssm_decode_update`` as a decode step of ``b`` slots runs it at the
    published shape: ``n_live`` rows spread over the slots, the others
    sitting out.  Parity with the ``jax.numpy`` update in float32 on the
    live rows' y and on the whole state array (rows that sit out and the
    other layers unchanged bit for bit), and the time a layer of one program
    that walks the stacked, donated state ``calls`` times."""
    ks = _keys(7, 7)
    f32 = jnp.float32
    state = jax.random.normal(ks[0], (n_layers, b, h, n, p), f32)
    x = jax.random.normal(ks[1], (b, h, p), f32)
    dt = jnp.exp(jax.random.uniform(ks[2], (b, h), f32, -7.0, -2.0))
    a = -jax.random.uniform(ks[3], (h,), f32, 1.0, 16.0)
    bm = jax.random.normal(ks[4], (b, g, n), f32)
    cm = jax.random.normal(ks[5], (b, g, n), f32)
    d = jax.random.normal(ks[6], (h,), f32)
    mask = np.zeros(b, bool)
    mask[np.linspace(0, b - 1, n_live).astype(int)] = True
    live = jnp.asarray(mask)

    want_y, want = jax.jit(pssm.ssm_update_xla)(state[1], x, dt, a, bm, cm,
                                                d, live)
    y, new = jax.jit(lambda s: pssm.ssm_decode_update_pallas(
        s, x, dt, a, bm, cm, d, live, jnp.int32(1)))(state)
    untouched = bool(jnp.all(new[0] == state[0])) and bool(jnp.all(
        jnp.where(live[:, None, None, None], True, new[1] == state[1])))
    state_err = _scaled_err(new[1], want)
    del new, want

    def loop(state, x):
        def body(carry, layer):
            state, acc = carry
            y, state = pssm.ssm_decode_update_pallas(
                state, x + acc, dt, a, bm, cm, d, live, layer)
            return (state, y * 1e-6), None
        layers = jnp.arange(calls, dtype=jnp.int32) % n_layers
        (state, acc), _ = jax.lax.scan(body, (state, jnp.zeros_like(x)),
                                       layers)
        return state, acc

    loop = jax.jit(loop, donate_argnums=(0,))
    state, acc = loop(state, x)
    acc.block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, acc = loop(state, x)
        acc.block_until_ready()
        times.append((time.perf_counter() - t0) / calls * 1e6)
    at_bandwidth = n_live * 2 * h * n * p * 4 / 819e9 * 1e6
    print(f"TIME   ssm-update {n_live}/{b} live rows h={h} n={n} p={p}: "
          f"{min(times):.1f} us a layer (median {sorted(times)[2]:.1f}, "
          f"{calls} calls a program; the live states both ways at 819 GB/s: "
          f"{at_bandwidth:.1f} us); other rows and layers untouched: "
          f"{untouched}", flush=True)
    if not untouched:
        raise AssertionError("a row that sits out, or another layer, moved")
    if state_err > 1e-5:
        raise AssertionError(f"new state off by {state_err}")
    # float32 both sides; the kernel sums a head's 256 products in another
    # order than the reference
    return y, want_y, 1e-4


def cases():
    """(name, gate reasons, thunk) for every kernel x layout x shape."""
    for label, b, v in (("falcon-h1-34b", 64, 261120),
                        ("qwen2.5-7b", 32, 152064),
                        ("lfm2-24b-a2b", 64, 65536)):
        yield (f"decode-tail [{label} {b}x{v}]", [],
               lambda b=b, v=v: case_decode_tail(b, v))
    for n_live in (64, 8, 1):
        yield (f"ssm-update {n_live}/64 live [falcon-h1-34b 32x256x128]",
               pssm.shape_reasons(32, 2, 256, 128),
               lambda n_live=n_live: case_ssm_update(n_live))
    for label, e, k, n, m in MOE_SHAPES:
        for quant in (False, True):
            yield (f"moe-gmm-{'int8' if quant else 'bf16'} [{label}]",
                   pmoe.shape_reasons(k, n),
                   lambda e=e, k=k, n=n, m=m, quant=quant: case_moe(
                       e, k, n, m, quant))
    for label, t, k, e, d in MOE_DISPATCH_SHAPES:
        yield (f"moe-dispatch [{label}]", [],
               lambda t=t, k=k, e=e, d=d: case_moe_dispatch(t, k, e, d))
    for label, e, k, n, m, touched, held in MOE_REUSE_SHAPES:
        yield (f"moe-reuse [{label}]", pmoe.shape_reasons(k, n),
               lambda e=e, k=k, n=n, m=m, touched=touched, held=held:
               case_moe_reuse(e, k, n, m, touched, held))
    # Speed 2 of ROADMAP.md: what the steps of the decode kernels' grid
    # cost, at the open-loop cells' layouts with few rows live and with all,
    # and at the closed-loop cells' ragged rows.
    for label, h, n_kv, hd, s_max in (("qwen2.5-7b g=7", 28, 4, 128, 2048),
                                      ("olmoe-1b-7b g=1 kv=16", 16, 16, 128,
                                       1024)):
        for n_live in (0, 5, 32):
            yield (f"live-rows {n_live}/32 x150 [{label}]",
                   pdec.shape_reasons(s_max, hd, n_kv * hd * 2),
                   lambda h=h, n_kv=n_kv, hd=hd, s_max=s_max, n_live=n_live:
                   case_live_rows(h, n_kv, hd, s_max,
                                  _spread(32, n_live, 150)))
    yield ("live-rows 32/32 x4-10k [smallthinker-21b-a3b full lanes]",
           pdec.shape_reasons(16384, 128, 4 * 128 * 2),
           lambda: case_live_rows(28, 4, 128, 16384,
                                  _ragged(32, 4096, 10240, 1), n_layers=3,
                                  calls=120))
    yield ("live-rows 64/64 x~450 [falcon-h1-34b]",
           pdec.shape_reasons(2048, 128, 4 * 128 * 2),
           lambda: case_live_rows(20, 4, 128, 2048, _ragged(64, 200, 700, 2)))
    yield ("live-rows 32/32 x~1400 [glm-4.7-flash latent rows]",
           pdec.mla_shape_reasons(4096, 640, 512),
           lambda: case_live_rows(20, 1, 640, 4096,
                                  _ragged(32, 300, 2500, 3), latent=512))
    # LFM2: 64-wide heads, two kv heads to a cache row, by the dispatchers.
    yield ("live-rows 64/64 x~2100 [lfm2-24b-a2b packed rows 4x128]",
           pdec.shape_reasons(8192, 128, 4 * 128 * 2),
           lambda: case_live_rows(32, 4, 128, 8192,
                                  _ragged(64, 768, 5632, 4), n_layers=3,
                                  calls=120))
    for op, gate in (("decode", pdec.shape_reasons(8192, 128, 4 * 128 * 2)),
                     ("flash", flash.shape_reasons(1024, 128)),
                     ("chunk", flash.chunk_shape_reasons(1024, 8192, 128))):
        yield (f"packed-heads {op} [lfm2-24b-a2b g=4 kv=8 hd64]", gate,
               lambda op=op: case_packed(op))
    # Speed 1 of ROADMAP.md: what the chunk attend costs a call at the
    # layouts whose cells stream their prompts as chunk programs.
    for label, args, kw in (
            ("smallthinker-21b-a3b full g=7 s_max=16384",
             (28, 4, 128, 16384, (0, 3072, 5120)), {}),
            ("smallthinker-21b-a3b ring+chunk g=7 s_max=5120 window=4096",
             (28, 4, 128, 5120, (0, 3072, 4096)), {"window": 4096}),
            ("lfm2-24b-a2b packed rows g=8 s_max=8192",
             (32, 8, 64, 8192, (0, 3072)), {"pack": 2})):
        yield (f"chunk-attend time [{label}]",
               flash.chunk_shape_reasons(1024, args[3], 128),
               lambda args=args, kw=kw: case_chunk_timed(*args, **kw))
    for label, h, n_kv, hd in LAYOUTS:
        for s in (128, 1024):
            yield (f"flash s={s} [{label}]", flash.shape_reasons(s, hd),
                   lambda h=h, n_kv=n_kv, hd=hd, s=s: case_flash(
                       h, n_kv, hd, s))
        for quant in (False, True):
            tag = "int8" if quant else "bf16"
            yield (f"lane-decode-{tag} s_max=2048 [{label}]",
                   pdec.shape_reasons(2048, hd,
                                      n_kv * hd * (1 if quant else 2)),
                   lambda h=h, n_kv=n_kv, hd=hd, quant=quant: case_decode(
                       h, n_kv, hd, 2048, quant))
            for block in (16, 64):
                yield (f"paged-decode-{tag} block={block} [{label}]",
                       pdec.paged_shape_reasons(
                           block, hd, jnp.int8 if quant else DTYPE),
                       lambda h=h, n_kv=n_kv, hd=hd, block=block,
                       quant=quant: case_paged(h, n_kv, hd, block, quant))
        for start in (0, 1024):
            yield (f"chunk-attend c=1024 s_max=4096 start={start} [{label}]",
                   flash.chunk_shape_reasons(1024, 4096, hd),
                   lambda h=h, n_kv=n_kv, hd=hd, start=start: case_chunk(
                       h, n_kv, hd, 1024, 4096, start))


def main() -> int:
    info = runtime.require_accelerator("tools/onchip_pallas_check.py")
    runtime.configure_compile_cache()
    print(f"device: platform={info.platform} device_kind={info.device_kind} "
          f"count={info.count}", flush=True)
    failed = []
    n_pass = n_gated = 0
    only = re.compile(sys.argv[1]) if len(sys.argv) > 1 else None
    for name, gate, thunk in cases():
        if only is not None and not only.search(name):
            continue
        if gate:
            n_gated += 1
            print(f"GATED  {name}: {gate[0]} (dispatcher takes XLA)",
                  flush=True)
            continue
        # Every case must be attempted so that one run lists ALL kernels
        # that fail to lower; any failure still fails the run below.
        try:
            out, ref, tol = thunk()
            err = _scaled_err(out, ref)
        except Exception as e:  # noqa: BLE001 — reported and counted
            first = str(e).strip().splitlines()[0][:300] if str(e) else ""
            print(f"FAIL   {name}: did not lower/run: "
                  f"{type(e).__name__}: {first}", flush=True)
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
            continue
        if err <= tol:
            n_pass += 1
            print(f"PASS   {name}: scaled_err={err:.5f} <= {tol:.5f}",
                  flush=True)
        else:
            print(f"FAIL   {name}: scaled_err={err:.5f} > {tol:.5f}",
                  flush=True)
            failed.append(name)
    print(f"onchip_pallas_check: {n_pass} passed, {n_gated} gated, "
          f"{len(failed)} failed on {info.device_kind}", flush=True)
    for name in failed:
        print(f"  failed: {name}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
