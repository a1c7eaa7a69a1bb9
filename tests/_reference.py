"""The plain reference for "the engine gives these tokens": no engine, no
thread, no batch.  The prompt goes through ``transformer.prefill`` into a
cache of one row, then ``decode_step`` feeds one token at a time; each new
token is the argmax over the true vocabulary, or, for a seeded request,
the server's own ``sample`` with that seed (which depends on the seed, the
position and the distribution alone)."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.server.engine import _seed_i32
from llm_instance_gateway_tpu.server.sampling import sample


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """Jitted once a configuration (eager, every call of a layer loop
    compiles anew)."""
    return types.SimpleNamespace(
        prefill=jax.jit(lambda p, toks, pos, n: transformer.prefill(
            cfg, p, toks, pos, lengths=n)),
        insert=jax.jit(lambda cache, k, v, n: transformer.insert_prefill(
            cache, k, v, 0, n, cfg=cfg)),
        step=jax.jit(lambda p, cache, tok, pos: transformer.decode_step(
            cfg, p, cache, tok, pos)))


S_MAX = 64  # the row's length; prompt and answer fit in every caller


def reference_tokens(cfg, params, prompt, n, *, sampling=None,
                     logprobs: list | None = None) -> list[int]:
    """The ``n`` tokens that follow ``prompt``, on float32 weights.
    ``sampling``: a ``SamplingParams`` with a seed, or None for greedy.
    ``logprobs``: a list that takes each token's log-probability under the
    model (before temperature, over the true vocabulary).
    One compiled shape a configuration: the prompt is padded to
    ``S_MAX``."""
    run, p_len = _programs(cfg), len(prompt)

    def pick(logits, position):
        tok = draw(logits, position)
        if logprobs is not None:
            logprobs.append(float(
                jax.nn.log_softmax(logits[:cfg.vocab_size])[tok]))
        return tok

    def draw(logits, position):
        if sampling is None or sampling.temperature <= 0.0:
            return int(jnp.argmax(logits[:cfg.vocab_size]))
        one = lambda x, t: jnp.full((1,), x, t)
        return int(sample(
            logits[None], jax.random.PRNGKey(0),
            one(sampling.temperature, jnp.float32),
            one(sampling.top_k, jnp.int32), one(sampling.top_p, jnp.float32),
            valid_vocab=cfg.vocab_size,
            seeds=one(_seed_i32(sampling.seed), jnp.int32),
            positions=one(position, jnp.int32))[0])

    assert p_len + n <= S_MAX
    toks = np.zeros((1, S_MAX), np.int32)
    toks[0, :p_len] = prompt
    pos = np.zeros((1, S_MAX), np.int32)
    pos[0, :p_len] = np.arange(p_len)
    logits, k, v = run.prefill(params, jnp.asarray(toks), jnp.asarray(pos),
                               jnp.asarray([p_len]))
    cache = run.insert(
        transformer.init_decode_cache(cfg, 1, S_MAX, dtype=jnp.float32),
        k, v, jnp.int32(p_len))
    out = [pick(logits[0, p_len - 1], p_len - 1)]
    for at in range(p_len, p_len + n - 1):
        logits, cache = run.step(params, cache, jnp.asarray([out[-1]]),
                                 jnp.asarray([at]))
        out.append(pick(logits[0], at))
    return out
