"""Paged KV cache tests (models/paged.py + engine wiring).

The contract: paged mode produces EXACTLY the tokens the contiguous-lane
cache produces (greedy), under plain decode, chunked prefill, decode_wait
pressure, and fused blocks — while reporting vLLM-semantics block
usage and applying backpressure (not corruption) when an oversubscribed
pool runs dry.
"""

import time

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import (
    Engine,
    EngineConfig,
    Request,
    SamplingParams,
)

CFG = TINY_TEST


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(params, paged: bool, steps: int = 1,
                n_blocks: int | None = None, slots: int = 4):
    return Engine(
        CFG, params,
        EngineConfig(
            decode_slots=slots, max_seq_len=64, prefill_buckets=(8, 16),
            decode_steps_per_sync=steps,
            paged_kv_block=8 if paged else None,
            paged_kv_blocks=n_blocks,
        ),
        lora_manager=None, eos_id=None, dtype=jnp.float32,
    )


def gen(engine, prompt, max_new=8):
    req = Request(prompt_tokens=list(prompt), max_new_tokens=max_new,
                  sampling=SamplingParams(temperature=0.0))
    engine.generate(req, timeout_s=120)
    assert req.error is None, req.error
    return req.output_tokens


class TestPagedParity:
    def test_paged_matches_lanes_greedy(self, params):
        lanes = make_engine(params, paged=False)
        paged = make_engine(params, paged=True)
        lanes.start(); paged.start()
        try:
            for prompt in [(5, 6, 7), (11, 3), tuple(range(1, 14))]:
                assert gen(paged, prompt) == gen(lanes, prompt)
        finally:
            lanes.stop(); paged.stop()

    def test_paged_chunked_prefill_matches_lanes(self, params):
        """Prompt beyond the largest bucket streams through chunked prefill
        in both modes; tokens must agree."""
        lanes = make_engine(params, paged=False)
        paged = make_engine(params, paged=True)
        lanes.start(); paged.start()
        try:
            prompt = tuple((i * 7) % 250 + 1 for i in range(40))  # > bucket 16
            assert gen(paged, prompt, max_new=6) == gen(lanes, prompt, max_new=6)
        finally:
            lanes.stop(); paged.stop()

    def test_paged_fused_blocks_match_single_steps(self, params):
        """Four steps a dispatch reserve their blocks ahead of the block in
        flight (``_paged_ensure_decode``) and give one step's tokens."""
        single = make_engine(params, paged=True)
        fused = make_engine(params, paged=True, steps=4)
        single.start(); fused.start()
        try:
            prompt = (9, 2, 4)
            assert gen(fused, prompt, max_new=10) == gen(single, prompt,
                                                         max_new=10)
        finally:
            single.stop(); fused.stop()

    def test_paged_concurrent_batch_consistency(self, params):
        engine = make_engine(params, paged=True)
        engine.start()
        try:
            solo = [gen(engine, (3 + i, 9), max_new=5) for i in range(4)]
            reqs = [Request(prompt_tokens=[3 + i, 9], max_new_tokens=5,
                            sampling=SamplingParams(temperature=0.0))
                    for i in range(4)]
            for r in reqs:
                engine.submit(r)
            assert all(r.done.wait(120) for r in reqs)
            assert [r.output_tokens for r in reqs] == solo
        finally:
            engine.stop()


class TestPagedPool:
    def test_usage_reports_allocated_blocks_and_frees_on_finish(self, params):
        engine = make_engine(params, paged=True)
        engine.start()
        try:
            assert engine.metrics_snapshot()["kv_cache_usage_perc"] == 0.0
            hog = Request(prompt_tokens=[1, 2, 3], max_new_tokens=30,
                          sampling=SamplingParams(temperature=0.0))
            engine.submit(hog)
            deadline = time.monotonic() + 60
            seen = 0.0
            while time.monotonic() < deadline and len(hog.output_tokens) < 5:
                seen = max(seen, engine.metrics_snapshot()["kv_cache_usage_perc"])
                time.sleep(0.005)
            assert seen > 0.0  # blocks allocated while running
            assert hog.done.wait(60)
            deadline = time.monotonic() + 10
            while (time.monotonic() < deadline
                   and engine.metrics_snapshot()["kv_cache_usage_perc"] > 0):
                time.sleep(0.01)
            # All blocks returned to the pool at finish.
            assert engine.metrics_snapshot()["kv_cache_usage_perc"] == 0.0
        finally:
            engine.stop()

    def test_oversubscribed_pool_backpressures_admission(self, params):
        """A pool sized for ~1.5 sequences serves 3 requests correctly by
        queueing, not corrupting: results still match an unconstrained run."""
        free_run = make_engine(params, paged=True)
        tight = make_engine(params, paged=True, n_blocks=6, slots=4)
        free_run.start(); tight.start()
        try:
            prompts = [(5, 6, 7), (8, 9), (1, 2, 3, 4)]
            want = [gen(free_run, p, max_new=6) for p in prompts]
            reqs = [Request(prompt_tokens=list(p), max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.0))
                    for p in prompts]
            for r in reqs:
                tight.submit(r)
            assert all(r.done.wait(120) for r in reqs)
            assert [r.error for r in reqs] == [None, None, None]
            assert [r.output_tokens for r in reqs] == want
        finally:
            free_run.stop(); tight.stop()

    def test_finish_at_prefill_frees_blocks(self, params):
        """max_new_tokens=1 finishes at prefill without ever taking a slot;
        its allocated blocks must return to the pool (a strand here
        deadlocks later admissions on a tight pool)."""
        tight = make_engine(params, paged=True, n_blocks=2)
        tight.start()
        try:
            one = Request(prompt_tokens=[1, 2, 3], max_new_tokens=1,
                          sampling=SamplingParams(temperature=0.0))
            tight.generate(one, timeout_s=60)
            assert one.error is None and len(one.output_tokens) == 1
            deadline = time.monotonic() + 10
            while (time.monotonic() < deadline
                   and tight.metrics_snapshot()["kv_cache_usage_perc"] > 0):
                time.sleep(0.01)
            assert tight.metrics_snapshot()["kv_cache_usage_perc"] == 0.0
            # The pool is actually reusable.
            assert len(gen(tight, (4, 5, 6), max_new=6)) == 6
        finally:
            tight.stop()

    def test_prompt_larger_than_pool_rejected_at_submit(self, params):
        tight = make_engine(params, paged=True, n_blocks=2)
        tight.start()
        try:
            with pytest.raises(ValueError, match="KV blocks"):
                tight.submit(Request(
                    prompt_tokens=list(range(1, 30)),  # needs 4 blocks of 8
                    max_new_tokens=4,
                    sampling=SamplingParams(temperature=0.0)))
        finally:
            tight.stop()

    def test_pool_exhaustion_fails_growing_request_cleanly(self, params):
        """One request that outgrows a tiny pool mid-decode fails with a
        clear error; the engine survives and serves the next request."""
        tight = make_engine(params, paged=True, n_blocks=2, slots=2)
        tight.start()
        try:
            # Needs ceil((3+30)/8)=5 blocks eventually; pool has 2.
            doomed = Request(prompt_tokens=[1, 2, 3], max_new_tokens=30,
                             sampling=SamplingParams(temperature=0.0))
            tight.submit(doomed)
            assert doomed.done.wait(120)
            assert doomed.error is not None
            assert "kv pool exhausted" in doomed.error
            # Pool fully recovered; a fitting request succeeds.
            ok = gen(tight, (4, 5), max_new=6)
            assert len(ok) == 6
        finally:
            tight.stop()


class TestStreamReservation:
    def test_stream_holds_blocks_against_competitors(self, params):
        """A long-prompt stream allocates its WHOLE prompt's blocks at
        admission: short requests admitted between chunks must not drain
        the pool out from under it (the stream must never fail with
        'kv pool exhausted' after passing admission)."""
        engine = make_engine(params, paged=True, n_blocks=16, slots=2)
        # Pool: 16 blocks x 8 tokens = 128 tokens.  Stream prompt: 40
        # tokens (5 blocks) across 5 chunks of the 8-token bucket.
        engine.start()
        try:
            long_req = Request(prompt_tokens=list(range(1, 41)),
                               max_new_tokens=4,
                               sampling=SamplingParams(temperature=0.0))
            engine.submit(long_req)
            shorts = []
            for i in range(6):
                r = Request(prompt_tokens=[3 + i, 5, 7],
                            max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.0))
                shorts.append(r)
                engine.submit(r)
            assert long_req.done.wait(120)
            assert long_req.error is None, long_req.error
            assert len(long_req.output_tokens) == 4
            for r in shorts:
                assert r.done.wait(120)
                assert r.error is None, r.error
        finally:
            engine.stop()


def make_prefix_engine(params, n_blocks=24, slots=3):
    return Engine(
        CFG, params,
        EngineConfig(
            decode_slots=slots, max_seq_len=64, prefill_buckets=(8, 16),
            paged_kv_block=8, paged_kv_blocks=n_blocks, prefix_cache=True,
        ),
        lora_manager=None, eos_id=None, dtype=jnp.float32,
    )


class TestPrefixCache:
    def test_shared_prefix_reuses_blocks_with_parity(self, params):
        """Two long prompts sharing a 32-token prefix: the second must reuse
        the cached blocks (counter advances) and still produce exactly the
        tokens a prefix-cache-off engine produces."""
        prefix = list(np.random.RandomState(7).randint(1, 250, size=32))
        p1 = prefix + [11, 12, 13, 14, 15]
        p2 = prefix + [21, 22, 23]

        plain = make_engine(params, paged=True)
        plain.start()
        try:
            want1 = gen(plain, p1, max_new=5)
            want2 = gen(plain, p2, max_new=5)
        finally:
            plain.stop()

        cached = make_prefix_engine(params)
        cached.start()
        try:
            got1 = gen(cached, p1, max_new=5)
            assert cached.prefix_reused_tokens == 0  # cold cache
            got2 = gen(cached, p2, max_new=5)
            # 32 shared tokens = 4 full blocks of 8 reused.
            assert cached.prefix_reused_tokens == 32
        finally:
            cached.stop()
        assert got1 == want1
        assert got2 == want2

    def test_identical_prompt_reuses_all_but_last_block(self, params):
        prompt = list(np.random.RandomState(8).randint(1, 250, size=40))
        engine = make_prefix_engine(params)
        engine.start()
        try:
            want = gen(engine, prompt, max_new=4)
            got = gen(engine, prompt, max_new=4)
            # 40 tokens = 5 blocks; at most (n-1)//bs = 4 reused (the last
            # token always recomputes to produce fresh logits).
            assert engine.prefix_reused_tokens == 32
        finally:
            engine.stop()
        assert got == want

    def test_bucketed_prompts_share_prefix(self, params):
        """VERDICT r2 #6: prompts WITHIN the largest bucket (the shared
        system-prompt workload) must reuse cached prefix blocks on the
        normal admission path — the second prompt prefills only its
        suffix — with exact greedy parity against a prefix-off engine."""
        prefix = list(np.random.RandomState(11).randint(1, 250, size=8))
        p1 = prefix + [31, 32, 33, 34]   # 12 tokens: bucketed (max is 16)
        p2 = prefix + [41, 42, 43]       # 11 tokens, same 8-token block
        plain = make_engine(params, paged=True, n_blocks=24, slots=3)
        plain.start()
        try:
            want1 = gen(plain, p1, max_new=6)
            want2 = gen(plain, p2, max_new=6)
        finally:
            plain.stop()
        cached = make_prefix_engine(params)
        cached.start()
        try:
            got1 = gen(cached, p1, max_new=6)
            assert cached.prefix_reused_tokens == 0  # cold cache
            got2 = gen(cached, p2, max_new=6)
            # One full 8-token block mapped; only the 3-token suffix
            # (padded to its own bucket) was prefilled.
            assert cached.prefix_reused_tokens == 8
        finally:
            cached.stop()
        assert got1 == want1
        assert got2 == want2

    def test_bucketed_and_chunked_prompts_share_one_cache(self, params):
        """A long (chunk-streamed) prompt registers blocks a later SHORT
        bucketed prompt reuses, and vice versa — one content-addressed
        table spans both admission paths."""
        prefix = list(np.random.RandomState(12).randint(1, 250, size=16))
        long_p = prefix + list(range(1, 24))   # 39 tokens: chunk path
        short_p = prefix[:8] + [61, 62]        # 10 tokens: bucketed
        engine = make_prefix_engine(params)
        engine.start()
        try:
            gen(engine, long_p, max_new=3)
            before = engine.prefix_reused_tokens
            gen(engine, short_p, max_new=3)
            # short_p shares long_p's first 8-token block only.
            assert engine.prefix_reused_tokens == before + 8
        finally:
            engine.stop()

    def test_admit_gate_accounts_for_pinning_matched_evictables(self, params):
        """Mapping a zero-ref cached block PINS it — it stops being
        reclaimable — so the admission gate must not count it as available
        too.  With the double-count, the gate admitted, then the suffix
        allocation found the pool dry and errored the request instead of
        backpressuring it."""
        prefix = list(np.random.RandomState(13).randint(1, 250, size=16))
        engine = make_prefix_engine(params, n_blocks=6, slots=2)
        engine.start()
        try:
            gen(engine, prefix + [7], max_new=1)  # registers 2 full blocks
        finally:
            engine.stop()
        b = prefix + [8]  # 17 tokens: needs 3 blocks, 2 matched
        # Simulate every free block held elsewhere: only the 2 matched
        # evictables remain.  Reuse would pin both and still need a suffix
        # block; the plain path needs 3 from 2 — must NOT admit.
        held, engine._free_blocks = engine._free_blocks, []
        assert not engine._paged_can_admit(len(b), b, None)
        # One genuinely free block: reuse fits (map 2 cached + alloc 1).
        engine._free_blocks = held[:1]
        assert engine._paged_can_admit(len(b), b, None)

    def test_eviction_under_pressure_keeps_serving(self, params):
        """A small pool fills with cached prefixes; later distinct prompts
        evict LRU zero-ref blocks instead of failing."""
        engine = make_prefix_engine(params, n_blocks=12, slots=2)
        engine.start()
        try:
            outs = []
            for seed in range(5):
                prompt = list(np.random.RandomState(100 + seed)
                              .randint(1, 250, size=24))
                outs.append(gen(engine, prompt, max_new=3))
            assert all(len(o) == 3 for o in outs)
            # Pool pressure metric treats zero-ref cached blocks as free.
            snap = engine.metrics_snapshot()
            assert snap["kv_cache_usage_perc"] == 0.0
        finally:
            engine.stop()

    def test_concurrent_shared_prefix_refcounts(self, params):
        """Two in-flight requests sharing cached blocks: freeing one must
        not free the blocks under the other."""
        prefix = list(np.random.RandomState(9).randint(1, 250, size=32))
        engine = make_prefix_engine(params)
        engine.start()
        try:
            warm = gen(engine, prefix + [1, 2], max_new=3)  # populate cache
            a = Request(prompt_tokens=prefix + [3, 4], max_new_tokens=24,
                        sampling=SamplingParams(temperature=0.0))
            b = Request(prompt_tokens=prefix + [5, 6], max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.0))
            engine.submit(a)
            engine.submit(b)
            assert b.done.wait(120) and b.error is None
            assert a.done.wait(120) and a.error is None
            assert len(a.output_tokens) == 24
            assert warm is not None
        finally:
            engine.stop()

    def test_adapter_keyed_prefixes_do_not_cross(self, params):
        """Same tokens under different adapters are DIFFERENT content: the
        base-model request must not reuse adapter-context KV blocks."""
        from llm_instance_gateway_tpu.server.lora_manager import LoRAManager
        from llm_instance_gateway_tpu.models.lora import target_dims

        cfg_l = CFG
        lora = LoRAManager(cfg_l, dtype=jnp.float32)
        dims = target_dims(cfg_l)
        rng = np.random.RandomState(0)
        lora.load("tenant-a", weights={
            t: {"a": rng.randn(cfg_l.n_layers, dims[t][0], 2) * 0.3,
                "b": rng.randn(cfg_l.n_layers, 2, dims[t][1]) * 0.3}
            for t in ("q", "k", "v")
        }, alpha=8.0, rank=2)
        engine = Engine(
            cfg_l, params,
            EngineConfig(decode_slots=3, max_seq_len=64,
                         prefill_buckets=(8, 16), paged_kv_block=8,
                         paged_kv_blocks=24, prefix_cache=True),
            lora_manager=lora, eos_id=None, dtype=jnp.float32,
        )
        prompt = list(np.random.RandomState(11).randint(1, 250, size=32))
        engine.start()
        try:
            ra = Request(prompt_tokens=list(prompt), max_new_tokens=4,
                         sampling=SamplingParams(temperature=0.0),
                         adapter="tenant-a")
            engine.generate(ra, timeout_s=120)
            assert ra.error is None
            reused_after_a = engine.prefix_reused_tokens
            rb = Request(prompt_tokens=list(prompt), max_new_tokens=4,
                         sampling=SamplingParams(temperature=0.0))
            engine.generate(rb, timeout_s=120)
            assert rb.error is None
            # Different adapter identity: zero cross-tenant reuse.
            assert engine.prefix_reused_tokens == reused_after_a
            # Same adapter again: reuse kicks in.
            ra2 = Request(prompt_tokens=list(prompt), max_new_tokens=4,
                          sampling=SamplingParams(temperature=0.0),
                          adapter="tenant-a")
            engine.generate(ra2, timeout_s=120)
            assert ra2.error is None
            assert engine.prefix_reused_tokens > reused_after_a
            assert ra2.output_tokens == ra.output_tokens
        finally:
            engine.stop()


class TestPagedOnMesh:
    """Tensor-parallel paged serving: the block pool shards on kv-heads
    over the tensor axis (paged_cache_specs); tables/length replicate and
    the host allocator is unchanged."""

    def _cfg(self):
        import dataclasses
        return dataclasses.replace(
            CFG, name="paged-mesh", d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128)

    def test_tensor_parallel_paged_parity(self):
        from llm_instance_gateway_tpu.parallel.mesh import (
            MeshConfig, make_mesh)

        cfg = self._cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        ecfg = EngineConfig(decode_slots=3, max_seq_len=64,
                            prefill_buckets=(8, 16), paged_kv_block=8,
                            prefix_cache=True)
        prompts = [[5, 6, 7, 8, 9, 10, 11, 12, 31],
                   [5, 6, 7, 8, 9, 10, 11, 12, 41, 42]]

        ref = Engine(cfg, params, ecfg, eos_id=None, dtype=jnp.float32)
        ref.start()
        try:
            want = [gen(ref, p, max_new=6) for p in prompts]
            want_reuse = ref.prefix_reused_tokens
        finally:
            ref.stop()

        mesh = make_mesh(MeshConfig(tensor=2, data=1, fsdp=4))
        # fsdp=4 only soaks up the spare virtual devices; params shard on
        # (fsdp, tensor) and the pool on tensor.
        engine = Engine(cfg, params, ecfg, eos_id=None, dtype=jnp.float32,
                        mesh=mesh)
        engine.start()
        try:
            got = [gen(engine, p, max_new=6) for p in prompts]
            got_reuse = engine.prefix_reused_tokens
        finally:
            engine.stop()
        assert got == want
        # Prefix caching works identically through the sharded pool.
        assert got_reuse == want_reuse > 0

    def test_data_axis_rejected(self):
        from llm_instance_gateway_tpu.parallel.mesh import (
            MeshConfig, make_mesh)

        cfg = self._cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        mesh = make_mesh(MeshConfig(data=2, tensor=4))
        with pytest.raises(ValueError, match="data=1"):
            Engine(cfg, params,
                   EngineConfig(paged_kv_block=8),
                   eos_id=None, dtype=jnp.float32, mesh=mesh)
