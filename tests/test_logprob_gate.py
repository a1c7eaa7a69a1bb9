"""A decode step computes logprobs only where a live row of the block asked
for them (PR 56): one ``lax.cond`` inside the one decode program
(``engine._logprobs_if_asked``), decided from a mark a slot carries in the
int32 buffer that is uploaded anyway, and a host that neither fetches nor
walks the triplet of a block staged with no asking row.

The reference is the parent's program: the same ``Engine._decode_impl``
traced while ``engine._logprobs_if_asked`` is the unconditional
``_logprob_info`` call the parent made (``parent_program``).
"""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server import engine as engine_mod
from llm_instance_gateway_tpu.server import metrics
from llm_instance_gateway_tpu.server.engine import (
    _SLOT_F32,
    _SLOT_I32,
    LOGPROB_TOPK,
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
    _logprob_info,
    _logprobs_if_asked,
    _named,
    _slot_buffer,
    _slot_views,
)
from llm_instance_gateway_tpu.server.sampling import STOP_LEN

CFG = TINY_TEST
SLOTS = 4
STEPS = 4


@contextlib.contextmanager
def parent_program():
    """While open, a decode program that is traced computes its logprobs as
    the parent did: ``_logprob_info`` on every step, whoever asked."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            engine_mod, "_logprobs_if_asked",
            lambda asked, logits, sampled, vocab: _logprob_info(
                logits, sampled, vocab))
        yield


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)


def make_engine(params, slots: int = SLOTS) -> Engine:
    return Engine(
        CFG, params,
        EngineConfig(decode_slots=slots, max_seq_len=96,
                     prefill_buckets=(8,)),
        eos_id=None, dtype=jnp.float32)


# -- the program alone -------------------------------------------------------

@pytest.fixture(scope="module")
def programs(params):
    """``run(asking rows) -> (toks, valid, lps, top_v, top_i)`` of one
    ``STEPS``-step block over four rows, through the engine's program
    (``gated``) and through the parent's (``parent``): row 0 greedy, row 1
    sampling from a seed, row 2 greedy with two steps of budget left, row 3
    held by the host and frozen on the device (a budget of 0 in the carry)."""
    engine = make_engine(params)
    model_cfg, step_fn = engine._jit_decode.__wrapped__.args
    with parent_program():
        parent = jax.jit(
            _named("decode_block", Engine._decode_impl, model_cfg, step_fn),
            static_argnames=("n_steps", "penalized"))
        jax.eval_shape(  # trace it while the parent's call is in place
            lambda *a: parent(*a, n_steps=STEPS),
            *_block_args(engine, ()))

    def run(program, asking):
        outs = program(*_block_args(engine, asking), n_steps=STEPS)
        return [np.asarray(a) for a in outs[:5]]

    return (lambda asking: run(engine._jit_decode, asking),
            lambda asking: run(parent, asking))


def _block_args(engine: Engine, asking) -> tuple:
    """The decode program's arguments for the block ``programs`` describes,
    rows ``asking`` marked as asking for logprobs."""
    i32_buf, i32 = _slot_buffer(_SLOT_I32, SLOTS, np.int32)
    f32_buf, f32 = _slot_buffer(_SLOT_F32, SLOTS, np.float32)
    i32["positions"][:] = (3, 5, 2, 4)
    i32["remaining"][:] = (9, 9, 2, 5)
    i32["fresh"][:] = (1, 1, 1, 0)
    i32["seed"][1] = 7
    f32["temp"][1] = 0.9
    for row in asking:
        i32["logprobs"][row] = 1
    carry = (jnp.asarray([11, 12, 13, 14], jnp.int32),
             jnp.asarray([0, 0, 0, 4], jnp.int32),
             jnp.zeros((SLOTS,), jnp.int32),  # row 3: frozen on the device
             jnp.full((SLOTS, STOP_LEN), -1, jnp.int32))
    cache = jax.tree.map(jnp.copy, engine.cache)  # the program donates it
    return (engine.params, None, cache, i32_buf, f32_buf, carry,
            jax.random.PRNGKey(5), jnp.int32(-1),
            jnp.zeros((SLOTS, 1), jnp.int32))


def test_a_block_nobody_asked_gives_the_parents_tokens_and_no_logprobs(
        programs):
    gated, parent = programs
    toks, valid, lps, top_v, top_i = gated(())
    want = parent(())
    assert (toks == want[0]).all() and (valid == want[1]).all()
    assert valid[:, 0].all() and valid[:, 1].all()
    assert valid[:, 2].tolist() == [True, True, False, False]
    assert not valid[:, 3].any()
    assert lps.shape == want[2].shape and lps.dtype == want[2].dtype
    assert top_v.shape == want[3].shape == (STEPS, SLOTS, LOGPROB_TOPK)
    assert top_i.shape == want[4].shape and top_i.dtype == want[4].dtype
    assert not lps.any() and not top_v.any() and not top_i.any()
    assert want[2].all()  # the parent paid for them on every step


def test_a_frozen_asking_row_alone_does_not_switch_the_branch_on(programs):
    gated, parent = programs
    toks, valid, lps, top_v, top_i = gated((3,))
    assert (toks == parent(())[0]).all()
    assert not lps.any() and not top_v.any() and not top_i.any()


@pytest.mark.parametrize("asking", [(2,), (2, 3), (0, 2), (1,)],
                         ids=["one", "one-and-the-frozen", "two", "sampler"])
def test_a_live_asking_row_gets_the_parents_values_bit_for_bit(programs,
                                                               asking):
    """... on the steps some asking row is live, for the whole batch; a
    step on which every asking row is frozen takes the free branch again."""
    gated, parent = programs
    got, want = gated(asking), parent(())
    assert (got[0] == want[0]).all() and (got[1] == want[1]).all()
    live = want[1][:, list(asking)].any(axis=1)  # by step
    assert live.tolist() == ([True] * STEPS if set(asking) & {0, 1}
                             else [True, True, False, False])
    for g, w in zip(got[2:], want[2:], strict=True):
        assert g[live].tobytes() == w[live].tobytes()
        assert not g[~live].any()


@pytest.mark.parametrize("vocab", [97, 128])
def test_the_asked_branch_is_a_direct_logprob_info_call(vocab):
    rng = np.random.RandomState(vocab)
    logits = jnp.asarray(rng.randn(3, 128) * 4, jnp.float32)
    sampled = jnp.asarray(rng.randint(0, vocab, (3,)), jnp.int32)
    want = jax.jit(_logprob_info, static_argnums=2)(logits, sampled, vocab)
    gate = jax.jit(_logprobs_if_asked, static_argnums=3)
    for g, w in zip(gate(True, logits, sampled, vocab), want, strict=True):
        assert g.dtype == w.dtype
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
    for g, w in zip(gate(False, logits, sampled, vocab), want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert not np.asarray(g).any()


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            x = getattr(x, "jaxpr", x)  # a closed jaxpr's own
            if hasattr(x, "eqns"):
                yield x


def _primitives(jaxpr, skip_conds: bool = False) -> list[str]:
    """Names of the primitives of ``jaxpr`` and of what it nests."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        if not (skip_conds and eqn.primitive.name == "cond"):
            for sub in _sub_jaxprs(eqn):
                out += _primitives(sub, skip_conds)
    return out


def _conds(jaxpr) -> list:
    """The ``cond`` equations of ``jaxpr`` that lie in no other's branch."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            out.append(eqn)
        else:
            for sub in _sub_jaxprs(eqn):
                out += _conds(sub)
    return out


def test_the_decode_program_holds_one_cond_with_a_free_branch(params):
    """The step's jaxpr: beside the sampler's three-way switch ONE two-way
    ``cond`` (branches of a branch apart), whose false branch holds no pass
    over the vocabulary (no ``top_k``, ``reduce_max`` or ``exp``) and whose
    true branch holds the ``top_k``; and no ``top_k`` anywhere outside a
    branch."""
    engine = make_engine(params)
    jaxpr = engine._jit_decode.trace(
        *_block_args(engine, ()), n_steps=1).jaxpr.jaxpr
    two_way = [e for e in _conds(jaxpr) if len(e.params["branches"]) == 2]
    assert len(two_way) == 1
    free, asked = (_primitives(branch.jaxpr)
                   for branch in two_way[0].params["branches"])
    assert not {"top_k", "reduce_max", "exp", "log"} & set(free)
    assert "top_k" in asked and "exp" in asked and "reduce_max" in asked
    assert "top_k" not in _primitives(jaxpr, skip_conds=True)
    # The one switch beside it is the sampler's.
    assert sorted(len(e.params["branches"]) for e in _conds(jaxpr)) == [2, 3]


# -- through the engine ------------------------------------------------------

def record(req: Request) -> dict:
    return {"tokens": list(req.output_tokens),
            "logprobs": list(req.output_logprobs),
            "top": list(req.output_top_logprobs),
            "finish": req.finish_reason, "error": req.error}


class Staged:
    """Every decode dispatch of ``engine`` as ``(steps, rows staged as
    asking)``, recorded at the jitted call."""

    def __init__(self, engine: Engine):
        self.seen: list[tuple[int, int]] = []
        plain = engine._jit_decode

        def decode(params, lora_bufs, cache, i32, *rest, n_steps, **kw):
            marks = _slot_views(np.asarray(i32), _SLOT_I32,
                                engine.cfg.decode_slots)["logprobs"]
            self.seen.append((n_steps, int((marks > 0).sum())))
            return plain(params, lora_bufs, cache, i32, *rest,
                         n_steps=n_steps, **kw)

        decode.lower = plain.lower
        engine._jit_decode = decode

    def asked_steps(self) -> int:
        return sum(n for n, rows in self.seen if rows)


def mixed_block(logprobs) -> list[Request]:
    """A greedy row, a sampling row and a row with a bias, none asking, and
    one row asking for ``logprobs`` (None: nobody asks).  Every draw is
    seeded or greedy, so an answer depends on its request alone."""
    S = SamplingParams
    return [
        Request([3, 5, 7], 14),
        Request([2, 4, 6, 8], 12, sampling=S(temperature=0.9, top_k=5,
                                             seed=11)),
        Request([9, 8, 7], 10, logprobs=logprobs, sampling=S(
            logit_bias={7: 5.0, 9: -3.0})),
        Request([1, 2], 9, sampling=S(temperature=0.7, seed=5)),
    ]


def serve(engine: Engine, reqs: list[Request]) -> list[dict]:
    engine.start()
    try:
        for req in reqs:
            engine.submit(req)
        for req in reqs:
            assert req.done.wait(180), "request never finished"
            assert req.error is None, req.error
    finally:
        engine.stop()
    return [record(r) for r in reqs]


@pytest.fixture(scope="module")
def parents_answers(params):
    """``logprobs -> records`` of ``mixed_block`` through an engine whose
    decode program is the parent's."""
    out = {}
    with parent_program():
        for k in (None, 0, 1, 5):
            out[k] = serve(make_engine(params), mixed_block(k))
    return out


@pytest.mark.parametrize("k", [None, 0, 1, 5],
                         ids=["nobody", "sampled-only", "top-1", "top-5"])
def test_requests_receive_what_the_parent_gives_them(params, parents_answers,
                                                     k):
    """(a), (b) and (f) through the engine: tokens bit for bit whoever
    asks; the asking row's logprobs bit for bit at 0, 1 and 5 alternatives;
    nothing stored for a row that did not ask; and
    ``tpu:logprob_steps_total`` counts the steps staged with the asking row
    and no others."""
    engine = make_engine(params)
    staged = Staged(engine)
    got = serve(engine, mixed_block(k))
    want = parents_answers[k]
    assert [g["tokens"] for g in got] == [w["tokens"] for w in want]
    assert got == want
    for i, g in enumerate(got):
        if i != 2 or k is None:
            assert g["logprobs"] == [] and g["top"] == []
    if k is not None:
        assert len(got[2]["logprobs"]) == 10
        assert [len(t) for t in got[2]["top"]] == [k] * 10 if k else (
            got[2]["top"] == [])
    hist = engine.profiler.hist_state()
    assert hist["logprob_steps"] == staged.asked_steps()
    assert (hist["logprob_steps"] > 0) == (k is not None)
    if k is not None:
        # The asking row's ten tokens: one from the prefill, nine steps.
        assert 9 <= hist["logprob_steps"] < sum(n for n, _ in staged.seen)
    text = metrics.render(engine.metrics_snapshot()) + "\n"
    assert f"tpu:logprob_steps_total {hist['logprob_steps']}\n" in text
    assert engine.profiler.snapshot()["hist"]["logprob_steps"] == (
        hist["logprob_steps"])


def test_a_block_nobody_asked_fetches_no_logprobs(params):
    """The host's side of (a): the block record of a dispatch staged with
    no asking row holds no logprob array, and one that holds an asking row
    holds the three."""
    engine = make_engine(params)
    fetched = []
    process = engine._process_block

    def spy(blk, current):
        fetched.append((len(blk["lp"]),
                        any(s is not None and s.request.logprobs is not None
                            for s in blk["rows"])))
        return process(blk, current)

    engine._process_block = spy
    serve(engine, mixed_block(None))
    assert fetched and all(f == (0, False) for f in fetched)
    del fetched[:]
    engine = make_engine(params)
    process = engine._process_block
    engine._process_block = spy
    serve(engine, mixed_block(1))
    # (3, False): staged over the block in which the asking row finished.
    assert {(3, True), (0, False)} <= set(fetched) <= {
        (3, True), (3, False), (0, False)}


def test_a_reused_slot_carries_no_stale_mark(params):
    """(c): one slot, an asking request and then one that does not ask."""
    engine = make_engine(params, slots=1)
    staged = Staged(engine)
    engine.start()
    try:
        first = engine.generate(Request([3, 5, 7], 6, logprobs=2),
                                timeout_s=180)
        assert first.error is None and len(first.output_logprobs) == 6
        deadline = time.monotonic() + 60
        while engine.slots[0] is not None:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        assert not engine._slot_logprobs.any()
        asked, blocks = staged.asked_steps(), len(staged.seen)
        assert asked >= 5
        second = engine.generate(Request([3, 5, 7], 6), timeout_s=180)
        assert second.error is None
    finally:
        engine.stop()
    assert second.output_tokens == first.output_tokens
    assert second.output_logprobs == [] and second.output_top_logprobs == []
    assert len(staged.seen) > blocks
    assert all(rows == 0 for _, rows in staged.seen[blocks:])
    assert engine.profiler.hist_state()["logprob_steps"] == asked


def test_a_logprobs_request_traces_no_decode_program(params):
    """(e): the engine holds as many traces of the decode program after a
    request that asks as before it."""
    engine = make_engine(params)
    engine.start()
    try:
        plain = engine.generate(Request([3, 5, 7], 6), timeout_s=180)
        traces = engine._jit_decode._cache_size()
        held = (dict(engine._decode_variants), dict(engine._decode_traces))
        asking = engine.generate(Request([3, 5, 7], 6, logprobs=5),
                                 timeout_s=180)
    finally:
        engine.stop()
    assert plain.error is None and asking.error is None
    assert asking.output_tokens == plain.output_tokens
    assert len(asking.output_logprobs) == 6
    assert traces == 1 and engine._jit_decode._cache_size() == traces
    assert (dict(engine._decode_variants),
            dict(engine._decode_traces)) == held


# -- the chip tool's case, at a size the CPU takes ---------------------------

@pytest.mark.parametrize("shape", [(4, 1024), (2, 640)])
def test_the_decode_tail_case_runs_and_agrees_with_itself(shape, capsys):
    """``tools/onchip_pallas_check.py "decode-tail"``: both branches time,
    the asked branch's outputs are the unconditional call's (bit for bit
    here, so the digests are equal), and the ``TIME`` line names them."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import onchip_pallas_check

    out, ref, tol = onchip_pallas_check.case_decode_tail(*shape, calls=3)
    assert out.shape == ref.shape == (shape[0], 1 + LOGPROB_TOPK)
    assert onchip_pallas_check._scaled_err(out, ref) == 0.0 <= tol
    line = capsys.readouterr().out
    assert line.startswith(f"TIME   decode-tail [{shape[0]}, {shape[1]}]")
    assert "not asked" in line and "largest difference" in line
    digests = [w.strip(",;") for w in line.split()
               if len(w.strip(",;")) == 16 and w.strip(",;").isalnum()]
    assert len(digests) == 2 and digests[0] == digests[1]
    assert any(name.startswith("decode-tail [falcon-h1-34b 64x261120]")
               for name, _, _ in onchip_pallas_check.cases())
