"""Token sampling: greedy, temperature, top-k, top-p — batched and jittable.

Per-row parameters arrive as arrays, letting one batch mix sampling configs —
required for multiplexed serving where every slot is a different request —
and everything is shape-static, so the decode step compiles once whatever
the requests ask.  What a call COSTS follows its live rows all the same:
one ``lax.switch`` inside the compiled program takes the argmax alone when
no live row samples, temperature scaling and one draw when none of the
sampling rows filters, and only otherwise the full-vocabulary sort that
top-k and top-p mask (``sample_routed``; no flag, no second program).

Also home to the DEVICE-SIDE stop-sequence automaton the fused decode block
evaluates per step (``stop_hist_update``/``stop_suffix_hit``): each row
carries a ring of its last ``STOP_LEN`` emitted tokens, and a per-row table
of right-aligned stop suffixes (-1 padded) matches against it with one
masked compare — no host round-trip per emitted token, which is what lets
``decode_steps_per_sync``/adaptive fusion stay safe for requests carrying
stop strings (the host only trims the overshoot once per dispatch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_instance_gateway_tpu.metrics_registry import SAMPLE_PATHS

NEG_INF = -1e30

# Device stop-automaton lanes: at most STOP_SEQS token-suffix sequences per
# row (the OpenAI surface caps `stop` at 4 strings), each at most STOP_LEN
# tokens.  Rows whose stops exceed either bound fall back to the host
# oracle (the engine leaves their lanes empty) — correctness never depends
# on fitting the lanes, only the fused-dispatch freeze does.
STOP_SEQS = 4
STOP_LEN = 8


def encode_stop_rows(
    sequences,                 # iterable of token-id tuples for ONE row
) -> "tuple[list[list[int]], list[int]] | None":
    """(ids [STOP_SEQS][STOP_LEN] right-aligned -1-padded, lens [STOP_SEQS])
    device lanes for one row's stop sequences, or ``None`` when they do not
    fit the static lanes (too many, too long, or empty entries)."""
    seqs = [tuple(int(t) for t in s) for s in sequences]
    if len(seqs) > STOP_SEQS or any(
            not s or len(s) > STOP_LEN for s in seqs):
        return None
    ids = [[-1] * STOP_LEN for _ in range(STOP_SEQS)]
    lens = [0] * STOP_SEQS
    for j, s in enumerate(seqs):
        ids[j][STOP_LEN - len(s):] = list(s)
        lens[j] = len(s)
    return ids, lens


def stop_hist_update(hist: jax.Array, sampled: jax.Array,
                     advance: jax.Array) -> jax.Array:
    """Shift each advancing row's token history left and append the newly
    sampled token (``hist`` [B, STOP_LEN] int32, -1 = not yet generated).
    Frozen rows (``advance`` False) keep their history unchanged."""
    shifted = jnp.concatenate(
        [hist[:, 1:], sampled[:, None].astype(hist.dtype)], axis=1)
    return jnp.where(advance[:, None], shifted, hist)


def stop_suffix_hit(hist: jax.Array, stop_ids: jax.Array,
                    stop_lens: jax.Array) -> jax.Array:
    """[B] bool: some stop sequence matches the row's history suffix.

    ``stop_ids`` [B, STOP_SEQS, STOP_LEN] is right-aligned with -1 padding,
    so an element-wise masked compare against the history tail IS the
    suffix match; -1 history entries (fewer tokens generated than the stop
    is long) can never equal a validated stop id, so short histories never
    false-match.  All-pad lanes are excluded via ``stop_lens`` > 0."""
    pad = stop_ids < 0
    eq = stop_ids == hist[:, None, :]
    matched = jnp.all(pad | eq, axis=-1)          # [B, STOP_SEQS]
    return jnp.any(matched & (stop_lens > 0), axis=-1)


def _draw(key, row_logits, seeds, positions):
    """One categorical draw per row of ``row_logits`` [B, V]: from the
    shared ``key``, or for rows with seed >= 0 from
    fold_in(PRNGKey(seed), position)."""
    sampled = jax.random.categorical(key, row_logits, axis=-1)
    if seeds is None:
        return sampled

    def seeded_draws(_):
        def row_draw(seed, pos, logits_1d):
            k = jax.random.fold_in(
                jax.random.PRNGKey(jnp.maximum(seed, 0)), pos)
            return jax.random.categorical(k, logits_1d)

        seeded = jax.vmap(row_draw)(
            seeds, positions.astype(jnp.int32), row_logits)
        return jnp.where(seeds >= 0, seeded, sampled)

    # lax.cond: the common all-unseeded batch skips the B key setups
    # and the second full-vocab draw at runtime.
    return jax.lax.cond(
        jnp.any(seeds >= 0), seeded_draws, lambda _: sampled, None)


def _top_k_top_p(scaled, top_k, top_p):
    """``scaled`` [B, V] with everything outside each row's top-k / top-p
    set at NEG_INF (top_k = 0 and top_p = 1.0 keep the row whole)."""
    v = scaled.shape[-1]
    # Top-k: mask everything below the k-th largest.  Fixed-shape sort.
    with jax.named_scope("sample.topk_sort"):
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V]
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, v) - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)  # [B,1]
    masked = jnp.where(scaled >= kth, scaled, NEG_INF)

    # Top-p over the already-top-k-masked distribution.  Top-k masking cannot
    # reorder a descending sort, so the sorted masked values are derivable
    # from the first sort — no second O(V log V) sort in the decode hot loop.
    ranks = jnp.arange(v)[None, :]
    sorted_masked = jnp.where(ranks <= k_idx[:, None], sorted_desc, NEG_INF)
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    # Keep tokens while exclusive-cumulative < top_p; the top-1 token is kept
    # unconditionally so top_p=0 degrades to argmax instead of a full mask.
    cutoff_mask = ((cumulative - probs_sorted) < top_p[:, None]) | (ranks == 0)
    threshold = jnp.where(cutoff_mask, sorted_masked, jnp.inf).min(axis=-1)  # [B]
    return jnp.where(masked >= threshold[:, None], masked, NEG_INF)


@jax.named_scope("sample")
def sample_routed(
    logits: jax.Array,        # [B, V] f32
    key: jax.Array,
    temperature: jax.Array,   # [B] f32; 0 = greedy
    top_k: jax.Array,         # [B] int32; 0 = disabled
    top_p: jax.Array,         # [B] f32; 1.0 = disabled
    valid_vocab: int | None = None,  # static: ids >= this are MXU padding
    seeds: jax.Array | None = None,      # [B] int32; -1 = engine RNG
    positions: jax.Array | None = None,  # [B] int32 — current input position
    bias_ids: jax.Array | None = None,   # [B, K] int32; -1 = unused entry
    bias_vals: jax.Array | None = None,  # [B, K] f32 — OpenAI logit_bias
    live: jax.Array | None = None,       # [B] bool; None = every row counts
) -> tuple[jax.Array, jax.Array]:
    """Returns (sampled token ids [B], index into ``SAMPLE_PATHS``).

    The call pays only for what its live rows ask.  One ``lax.switch`` on
    the device, inside the caller's program, takes the cheapest path that
    gives every live row its token:

    - ``argmax``: no live row has ``temperature > 0`` — the argmax alone;
      no scaling, sort, softmax, cumulative sum or draw;
    - ``draw``: some live row samples, none of those asks for top-k or
      top-p — temperature scaling and the categorical draw, no sort;
    - ``filtered``: some live sampling row has ``top_k > 0`` or
      ``top_p < 1`` — the full-vocabulary sort and the top-k / top-p mask
      for the whole batch, then the draw.

    A row's token depends on that row alone, never on the path its batch
    took: under one ``key`` the three paths agree wherever they overlap
    (the draw's noise is drawn per (row, id) from the key, whatever the
    logits), so a batch may mix greedy, temperature-only and top-p rows.
    One place they can part: with ``top_p = 1.0`` the filter's exclusive
    cumulative sum may round past 1.0 in float32 over a very large
    vocabulary and mask a tail of total mass ~1e-6; ``draw`` keeps that
    tail, as "disabled" says it should.

    ``live`` marks the rows whose token will be used (the decode block's
    ``remaining > 0``).  A dead row — a freed slot still carrying its last
    request's parameters — neither chooses the path nor samples: it gets
    its argmax.

    ``valid_vocab`` masks the vocab-padding columns (the lm_head is padded to
    a multiple of 128 for MXU tiling with zero — hence logit 0.0 — columns);
    without the mask, temperature sampling could emit ids the tokenizer has
    never heard of.

    ``seeds``/``positions``: per-request reproducible sampling (the OpenAI
    ``seed`` param).  A row with seed >= 0 draws from
    fold_in(PRNGKey(seed), position) instead of the shared engine key, so
    its tokens depend only on (seed, position, distribution) — identical
    across runs, restarts, and whatever else shares its batch.  Rows at -1
    keep the engine-RNG draw bit-for-bit.
    """
    b, v = logits.shape
    if valid_vocab is not None and valid_vocab < v:
        pad_mask = jnp.arange(v) < valid_vocab
        logits = jnp.where(pad_mask[None, :], logits, NEG_INF)
    if bias_ids is not None:
        # OpenAI logit_bias: applied before EVERYTHING (greedy argmax
        # included).  Pad entries (-1) scatter zero onto a clipped index.
        rows = jnp.arange(b)[:, None]
        logits = logits.at[rows, jnp.clip(bias_ids, 0, v - 1)].add(
            jnp.where(bias_ids >= 0, bias_vals, 0.0))
    greedy = jnp.argmax(logits, axis=-1)

    sampling = temperature > 0
    if live is not None:
        sampling &= live
    filtering = sampling & ((top_k > 0) | (top_p < 1.0))
    path = (jnp.any(sampling).astype(jnp.int32)
            + jnp.any(filtering).astype(jnp.int32))

    def scaled():
        # Temperature scaling (guard zero; greedy rows are selected at
        # the end).
        return logits / jnp.where(temperature > 0, temperature, 1.0)[:, None]

    branches = {
        "argmax": lambda: greedy,
        "draw": lambda: _draw(key, scaled(), seeds, positions),
        "filtered": lambda: _draw(
            key, _top_k_top_p(scaled(), top_k, top_p), seeds, positions),
    }
    sampled = jax.lax.switch(path, [branches[p] for p in SAMPLE_PATHS])
    return jnp.where(sampling, sampled, greedy).astype(jnp.int32), path


def sample(*args, **kwargs) -> jax.Array:
    """``sample_routed`` without the path index: token ids [B]."""
    return sample_routed(*args, **kwargs)[0]
