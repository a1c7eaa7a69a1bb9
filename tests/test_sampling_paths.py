"""The sampler's three paths (``server/sampling.py:sample_routed``).

``argmax`` / ``draw`` / ``filtered`` are chosen on the device from the live
rows' parameters.  Whatever the path, every live row gets the token the
one-path sampler gave it under the same key (``reference_sample`` below: a
frozen copy of ``sample()`` as it stood before the paths), and a dead row
gets its argmax.  The engine books the path the device took, step by step.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.metrics_registry import SAMPLE_PATHS
from llm_instance_gateway_tpu.server.sampling import NEG_INF, sample_routed

B, V, VALID, N_KEYS = 8, 1000, 937, 16


def reference_sample(logits, key, temperature, top_k, top_p, valid_vocab=None,
                     seeds=None, positions=None, bias_ids=None,
                     bias_vals=None):
    """``sample()`` as of PR 27, verbatim: sort, filter and draw for every
    row of every call, the greedy rows selected in the last line."""
    b, v = logits.shape
    if valid_vocab is not None and valid_vocab < v:
        pad_mask = jnp.arange(v) < valid_vocab
        logits = jnp.where(pad_mask[None, :], logits, NEG_INF)
    if bias_ids is not None:
        rows = jnp.arange(b)[:, None]
        logits = logits.at[rows, jnp.clip(bias_ids, 0, v - 1)].add(
            jnp.where(bias_ids >= 0, bias_vals, 0.0))
    greedy = jnp.argmax(logits, axis=-1)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / safe_t
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, v) - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    masked = jnp.where(scaled >= kth, scaled, NEG_INF)
    ranks = jnp.arange(v)[None, :]
    sorted_masked = jnp.where(ranks <= k_idx[:, None], sorted_desc, NEG_INF)
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_mask = ((cumulative - probs_sorted) < top_p[:, None]) | (ranks == 0)
    threshold = jnp.where(cutoff_mask, sorted_masked, jnp.inf).min(axis=-1)
    masked = jnp.where(masked >= threshold[:, None], masked, NEG_INF)
    sampled = jax.random.categorical(key, masked, axis=-1)
    if seeds is not None:
        def seeded_draws(_):
            def row_draw(seed, pos, row_logits):
                k = jax.random.fold_in(
                    jax.random.PRNGKey(jnp.maximum(seed, 0)), pos)
                return jax.random.categorical(k, row_logits)

            seeded = jax.vmap(row_draw)(
                seeds, positions.astype(jnp.int32), masked)
            return jnp.where(seeds >= 0, seeded, sampled)

        sampled = jax.lax.cond(
            jnp.any(seeds >= 0), seeded_draws, lambda _: sampled, None)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


# Per-row (temperature, top_k, top_p) of the LIVE rows, by the path they
# must bring about.  Greedy rows carry filters too: a filter on a row that
# does not sample costs nothing and changes nothing.
LIVE_ROWS = {
    "argmax": [(0.0, 0, 1.0), (0.0, 5, 1.0), (0.0, 0, 0.9), (0.0, 3, 0.5)],
    "draw": [(0.8, 0, 1.0), (0.0, 5, 0.9), (1.3, 0, 1.0), (0.0, 0, 1.0)],
    "filtered": [(0.7, 5, 1.0), (0.9, 0, 0.9), (0.8, 0, 1.0), (0.0, 0, 1.0),
                 (1.1, 40, 0.6)],
}
# What a freed slot may still carry: the rows past the live ones.
DEAD_ROWS = {
    "all_live": None,
    "some_dead": (0.0, 0, 1.0),
    "dead_stale": (0.8, 7, 0.5),
}


def batch_params(path: str, liveness: str):
    """(temperature, top_k, top_p, live) for B rows."""
    rows = LIVE_ROWS[path]
    dead = DEAD_ROWS[liveness]
    n_live = B if dead is None else 5
    params = [rows[i % len(rows)] for i in range(n_live)]
    params += [dead] * (B - n_live)
    t, k, p = zip(*params)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32), jnp.arange(B) < n_live)


def extras(seeded: bool, dressed: bool) -> dict:
    """Seeds and positions; ``dressed``: a vocabulary padded past
    ``VALID`` and a ``logit_bias`` strong enough to move the argmax."""
    kw = {}
    if seeded:
        kw["seeds"] = jnp.asarray([-1, 11, -1, 12, 13, -1, 14, -1], jnp.int32)
        kw["positions"] = jnp.arange(B, dtype=jnp.int32) + 3
    if dressed:
        ids = np.full((B, 4), -1, np.int32)
        vals = np.zeros((B, 4), np.float32)
        ids[:, 0] = np.arange(B) * 7 + 1
        vals[:, 0] = 9.0
        ids[::2, 1] = 5
        vals[::2, 1] = -4.0
        kw.update(valid_vocab=VALID, bias_ids=jnp.asarray(ids),
                  bias_vals=jnp.asarray(vals))
    return kw


def logits_for(i: int):
    # std 2: peaked enough that top-p cuts inside the row, flat enough
    # that the tail's mass stays far above float32's rounding at V = 1000
    # (where "top_p = 1.0" and "no filter" are the same thing).
    return 2.0 * jax.random.normal(jax.random.PRNGKey(1000 + i), (B, V))


def run_both(t, k, p, live, kw):
    """Tokens and path of ``sample_routed``, the reference's tokens and
    the argmax a dead row must get, over N_KEYS keys and logits."""
    static = {"valid_vocab": kw.pop("valid_vocab", None)}
    new = jax.jit(lambda lg, key: sample_routed(
        lg, key, t, k, p, live=live, **static, **kw))
    ref = jax.jit(lambda lg, key: reference_sample(
        lg, key, t, k, p, **static, **kw))
    argmax = jax.jit(lambda lg, key: reference_sample(
        lg, key, jnp.zeros_like(t), k, p, **static, **kw))
    for i in range(N_KEYS):
        key = jax.random.PRNGKey(i)
        lg = logits_for(i)
        toks, path = new(lg, key)
        yield (np.asarray(toks), int(path), np.asarray(ref(lg, key)),
               np.asarray(argmax(lg, key)))


@pytest.mark.parametrize("dressed", [False, True], ids=["plain", "bias+pad"])
@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
@pytest.mark.parametrize("liveness", list(DEAD_ROWS))
@pytest.mark.parametrize("path", SAMPLE_PATHS)
def test_every_path_gives_the_reference_tokens(path, liveness, seeded,
                                               dressed):
    t, k, p, live = batch_params(path, liveness)
    live_np = np.asarray(live)
    sampled_rows = 0
    for toks, took, want, greedy in run_both(
            t, k, p, None if liveness == "all_live" else live,
            extras(seeded, dressed)):
        assert SAMPLE_PATHS[took] == path
        np.testing.assert_array_equal(toks[live_np], want[live_np])
        # A dead row gets its argmax, whatever it still carries and
        # whatever path the live rows chose.
        np.testing.assert_array_equal(toks[~live_np], greedy[~live_np])
        if dressed:
            assert toks.max() < VALID
        sampled_rows += int(np.sum(want[live_np] != greedy[live_np]))
    # The comparison has teeth: sampling rows did leave their argmax.
    assert (sampled_rows > 0) == (path != "argmax")


@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
def test_mixed_batch_equals_the_reference_row_for_row(seeded):
    """Greedy, temperature-only, top-k and top-p rows in one batch, every
    row counted (``live=None``, as the one-row callers pass it)."""
    t = jnp.asarray([0.0, 0.8, 0.9, 0.0, 1.2, 0.7, 0.0, 1.0], jnp.float32)
    k = jnp.asarray([0, 0, 0, 4, 0, 12, 0, 0], jnp.int32)
    p = jnp.asarray([1.0, 1.0, 0.9, 1.0, 1.0, 0.8, 0.5, 1.0], jnp.float32)
    for toks, took, want, _ in run_both(t, k, p, None, extras(seeded, True)):
        assert SAMPLE_PATHS[took] == "filtered"
        np.testing.assert_array_equal(toks, want)


@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
def test_a_rows_token_does_not_depend_on_its_batchs_path(seeded):
    """Row 0 samples by temperature alone.  Its neighbours take the batch
    onto ``draw`` or onto ``filtered``: row 0's token is the same."""
    kw = extras(seeded, False)
    tokens = {}
    for path in ("draw", "filtered"):
        t, k, p, _ = batch_params(path, "all_live")
        t, k, p = t.at[0].set(0.8), k.at[0].set(0), p.at[0].set(1.0)
        fn = jax.jit(lambda lg, key, t=t, k=k, p=p: sample_routed(
            lg, key, t, k, p, **kw))
        outs = [fn(logits_for(i), jax.random.PRNGKey(i))
                for i in range(N_KEYS)]
        assert {SAMPLE_PATHS[int(took)] for _, took in outs} == {path}
        tokens[path] = [int(toks[0]) for toks, _ in outs]
    assert tokens["draw"] == tokens["filtered"]


# ---------------------------------------------------------------------------
# The engine books the path the DEVICE took
# ---------------------------------------------------------------------------

def test_engine_books_the_devices_path_step_by_step():
    """Greedy traffic books ``argmax`` alone; a temperature request books
    ``draw`` while it lives, and the count goes back to ``argmax`` on the
    step after it finishes although its freed slot still holds 0.8 in the
    host's mirror; a top-p request books ``filtered``."""
    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server import metrics
    from llm_instance_gateway_tpu.server.engine import (
        Engine,
        EngineConfig,
        Request,
        SamplingParams,
    )

    cfg = TINY_TEST
    params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    engine = Engine(
        cfg, params,
        EngineConfig(decode_slots=2, max_seq_len=512, prefill_buckets=(8,)),
        eos_id=None, dtype=jnp.float32,
    )
    state = engine.profiler.sample_state

    def wait_for(cond, what):
        deadline = time.monotonic() + 120
        while not cond():
            assert time.monotonic() < deadline, what
            time.sleep(0.002)

    engine.start()
    try:
        # Compile the programs first, so that the long request below is
        # still running when the short ones come and go.
        warm = engine.generate(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=3), timeout_s=120)
        assert warm.error is None
        long = engine.submit(
            Request(prompt_tokens=[3, 5, 7], max_new_tokens=400))
        wait_for(lambda: len(long.output_tokens) >= 2, "no decode step")
        greedy_only = state()
        assert greedy_only["argmax"] >= 3
        assert greedy_only["draw"] == greedy_only["filtered"] == 0

        warm_t = engine.generate(Request(
            prompt_tokens=[2, 4, 6], max_new_tokens=4,
            sampling=SamplingParams(temperature=0.8)), timeout_s=120)
        after_draw = state()
        assert warm_t.error is None and not long.done.is_set()
        # 3 decode steps follow the prefill's first token (the block in
        # flight may add the one after the row froze).
        assert 1 <= after_draw["draw"] <= 4
        assert after_draw["filtered"] == 0
        # The freed slot keeps its last request's temperature...
        assert engine.slots[1] is None
        assert engine._slot_temp[1] == np.float32(0.8)
        # ...and the steps after it are argmax steps all the same.
        wait_for(lambda: state()["argmax"] >= after_draw["argmax"] + 5,
                 "argmax steps did not resume")
        assert state()["draw"] == after_draw["draw"]

        top_p = engine.generate(Request(
            prompt_tokens=[2, 4, 6], max_new_tokens=4,
            sampling=SamplingParams(temperature=0.8, top_p=0.9)),
            timeout_s=120)
        after_filtered = state()
        assert top_p.error is None and not long.done.is_set()
        assert 1 <= after_filtered["filtered"] <= 4
        assert after_filtered["draw"] == after_draw["draw"]
        assert long.done.wait(120) and long.error is None
    finally:
        engine.stop()
    final = state()
    assert final["draw"] == after_draw["draw"]
    assert final["filtered"] == after_filtered["filtered"]
    # 399 decode steps for the long request, nearly all of them alone.
    assert final["argmax"] >= 399 - 8
    text = metrics.render(engine.metrics_snapshot())
    for path, n in final.items():
        assert f'tpu:sample_steps_total{{path="{path}"}} {n}' in text
    assert engine.profiler.snapshot()["hist"]["sample_steps"] == final
