"""Grouped prefill admission: same-bucket prompts prefill as ONE program.

Parity is the contract: prefill_batch > 1 must change HOW prompts admit
(one [P, bucket] dispatch instead of P), never WHAT any request generates —
greedy outputs, adapters, logprobs, and FIFO order all match the
one-at-a-time path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.models.lora import target_dims
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)
from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

CFG = TINY_TEST
PARAMS = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

# Mixed lengths: 4 land in the 16-bucket, 2 in the 32-bucket.
PROMPTS = [
    [5, 6, 7], [8, 9, 10, 11], [12, 13], [3, 4, 5, 6, 7],
    list(range(1, 20)), list(range(30, 55)),
]


def _serve(prefill_batch: int, lora=None,
           adapters=(None,) * len(PROMPTS)):
    engine = Engine(
        CFG, PARAMS,
        EngineConfig(decode_slots=8, max_seq_len=128,
                     prefill_buckets=(16, 32, 64),
                     decode_steps_per_sync=4,
                     prefill_batch=prefill_batch),
        lora_manager=lora, eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        reqs = [
            Request(prompt_tokens=list(p), max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.0), adapter=a)
            for p, a in zip(PROMPTS, adapters)
        ]
        for r in reqs:
            engine.submit(r)
        for r in reqs:
            assert r.done.wait(120), "request timed out"
            assert r.error is None, r.error
        return [list(r.output_tokens) for r in reqs]
    finally:
        engine.stop()


def test_grouped_outputs_match_single():
    single = _serve(1)
    grouped = _serve(4)
    assert grouped == single


def test_grouped_with_adapters_matches_single():
    def make_lora():
        lora = LoRAManager(CFG, dtype=jnp.float32)
        dims = target_dims(CFG)
        rng = np.random.RandomState(7)
        lora.load("ad-x", weights={
            t: {"a": rng.randn(CFG.n_layers, dims[t][0], 4) * 0.05,
                "b": rng.randn(CFG.n_layers, 4, dims[t][1]) * 0.05}
            for t in ("q", "v")
        }, alpha=8.0, rank=4)
        return lora

    adapters = ("ad-x", None, "ad-x", None, "ad-x", None)
    single = _serve(1, lora=make_lora(), adapters=adapters)
    grouped = _serve(4, lora=make_lora(), adapters=adapters)
    assert grouped == single
    # The adapter genuinely changes output (the parity isn't vacuous).
    base = _serve(4, lora=make_lora(), adapters=(None,) * 6)
    assert base != grouped


def test_unknown_adapter_rejected_at_submit_not_in_group():
    """Unknown adapters 404 at submit (eager resolution), so a bad adapter
    can never poison a grouped prefill; healthy requests around it serve."""
    from llm_instance_gateway_tpu.server.lora_manager import AdapterError

    lora = LoRAManager(CFG, dtype=jnp.float32)
    engine = Engine(
        CFG, PARAMS,
        EngineConfig(decode_slots=8, max_seq_len=128,
                     prefill_buckets=(16, 32),
                     prefill_batch=4),
        lora_manager=lora, eos_id=None, dtype=jnp.float32,
    )
    engine.start()
    try:
        good = Request(prompt_tokens=[1, 2, 3], max_new_tokens=4,
                       sampling=SamplingParams(temperature=0.0))
        bad = Request(prompt_tokens=[4, 5, 6], max_new_tokens=4,
                      sampling=SamplingParams(temperature=0.0),
                      adapter="no-such-adapter")
        good2 = Request(prompt_tokens=[7, 8], max_new_tokens=4,
                        sampling=SamplingParams(temperature=0.0))
        engine.submit(good)
        with pytest.raises(AdapterError):
            engine.submit(bad)
        engine.submit(good2)
        for r in (good, good2):
            assert r.done.wait(120)
            assert r.error is None and len(r.output_tokens) == 4
    finally:
        engine.stop()


def test_saturated_slots_group_through_decode_wait():
    """More requests than slots: the overflow admits through GROUPED
    prefill-ahead and still matches the one-at-a-time engine exactly."""
    def run(prefill_batch):
        engine = Engine(
            CFG, PARAMS,
            EngineConfig(decode_slots=2, max_seq_len=128,
                         prefill_buckets=(16, 32),
                         decode_steps_per_sync=4,
                         prefill_batch=prefill_batch, decode_wait_cap=8),
            eos_id=None, dtype=jnp.float32,
        )
        engine.start()
        try:
            reqs = [
                Request(prompt_tokens=[i + 1, i + 2, i + 3],
                        max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.0))
                for i in range(8)
            ]
            for r in reqs:
                engine.submit(r)
            for r in reqs:
                assert r.done.wait(180), "request timed out"
                assert r.error is None, r.error
            return [list(r.output_tokens) for r in reqs]
        finally:
            engine.stop()

    assert run(4) == run(1)


class TestCollection:
    def _engine(self, prefill_batch=4, slots=8):
        return Engine(
            CFG, PARAMS,
            EngineConfig(decode_slots=slots, max_seq_len=128,
                         prefill_buckets=(16, 32),
                         prefill_batch=prefill_batch),
            eos_id=None, dtype=jnp.float32,
        )

    def test_same_bucket_grouped_different_parks(self):
        engine = self._engine()
        head = Request(prompt_tokens=[1, 2, 3], max_new_tokens=2)
        same = Request(prompt_tokens=[4, 5], max_new_tokens=2)
        other = Request(prompt_tokens=list(range(20)), max_new_tokens=2)
        tail = Request(prompt_tokens=[6], max_new_tokens=2)
        for r in (same, other, tail):
            engine.prefill_queue.put_nowait(r)
        group = engine._collect_prefill_group(head)
        # 16-bucket head takes the 16-bucket follower; the 32-bucket prompt
        # parks as _pending (FIFO: tail stays queued behind it).
        assert group == [head, same]
        assert engine._pending is other
        assert engine.prefill_queue.qsize() == 1

    def test_group_bounded_by_free_slots(self):
        engine = self._engine(prefill_batch=8, slots=2)
        head = Request(prompt_tokens=[1], max_new_tokens=2)
        followers = [Request(prompt_tokens=[i], max_new_tokens=2)
                     for i in range(2, 6)]
        for r in followers:
            engine.prefill_queue.put_nowait(r)
        group = engine._collect_prefill_group(head)
        assert len(group) == 2  # head + 1: only 2 slots free
        assert engine.prefill_queue.qsize() == 3

    def test_cancelled_follower_skipped(self):
        engine = self._engine()
        head = Request(prompt_tokens=[1, 2], max_new_tokens=2)
        dead = Request(prompt_tokens=[3, 4], max_new_tokens=2)
        dead.cancelled.set()
        live = Request(prompt_tokens=[5, 6], max_new_tokens=2)
        for r in (dead, live):
            engine.prefill_queue.put_nowait(r)
        group = engine._collect_prefill_group(head)
        assert group == [head, live]
        assert dead.finish_reason == "cancelled"


class TestPagedGroupedAdmission:
    """Grouped prefill now admits into the PAGED pool too: same-bucket
    bursts prefill as one program, rows allocate their blocks at insert,
    and pool exhaustion parks rows (FIFO) instead of erroring them."""

    def _serve_paged(self, prefill_batch, n_blocks=None, slots=8,
                     max_new=6):
        engine = Engine(
            CFG, PARAMS,
            EngineConfig(decode_slots=slots, max_seq_len=128,
                         prefill_buckets=(16, 32, 64),
                         decode_steps_per_sync=4,
                         prefill_batch=prefill_batch,
                         paged_kv_block=16, paged_kv_blocks=n_blocks),
            lora_manager=None, eos_id=None, dtype=jnp.float32,
        )
        engine.start()
        try:
            reqs = [
                Request(prompt_tokens=list(p), max_new_tokens=max_new,
                        sampling=SamplingParams(temperature=0.0))
                for p in PROMPTS
            ]
            for r in reqs:
                engine.submit(r)
            for r in reqs:
                assert r.done.wait(120), "request timed out"
                assert r.error is None, r.error
            return [list(r.output_tokens) for r in reqs]
        finally:
            engine.stop()

    def test_paged_grouped_matches_single(self):
        want = self._serve_paged(1)
        got = self._serve_paged(4)
        assert got == want

    def test_tight_pool_parks_not_errors(self):
        """A pool too small for the whole burst at once: grouped admission
        must backpressure rows through decode_wait and still produce the
        unconstrained outputs."""
        want = self._serve_paged(1)
        got = self._serve_paged(4, n_blocks=10, slots=4)
        assert got == want
