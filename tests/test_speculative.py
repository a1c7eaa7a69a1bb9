"""Speculative decoding: extend_step parity + engine greedy equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST

CFG = TINY_TEST


def test_extend_step_matches_sequential_decode_steps():
    params = transformer.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    b, s_max, c = 3, 32, 4
    rng = np.random.RandomState(0)

    # Prime each lane with a short prompt via prefill+insert.
    cache = transformer.init_decode_cache(CFG, b, s_max, dtype=jnp.float32)
    starts = [5, 3, 7]
    for row, n in enumerate(starts):
        prompt = jnp.asarray([rng.randint(1, 250, size=n)], jnp.int32)
        pos = jnp.arange(n)[None]
        _, k, v = transformer.prefill(CFG, params, prompt, pos)
        cache = transformer.insert_prefill(cache, k, v, row, n)

    tokens = jnp.asarray(rng.randint(1, 250, size=(b, c)), jnp.int32)
    positions = jnp.asarray([[st + i for i in range(c)] for st in starts],
                            jnp.int32)

    # Reference: c sequential single-token decode steps.
    ref_cache = jax.tree.map(lambda x: x, cache)
    ref_logits = []
    for i in range(c):
        lg, ref_cache = transformer.decode_step(
            CFG, params, ref_cache, tokens[:, i], positions[:, i])
        ref_logits.append(lg)
    ref_logits = jnp.stack(ref_logits, axis=1)  # [B, C, V]

    got_logits, got_cache = transformer.extend_step(
        CFG, params, cache, tokens, positions)

    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(ref_cache["k"]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["v"]),
                               np.asarray(ref_cache["v"]), rtol=2e-4, atol=2e-4)


def _tiny_draft():
    # A smaller model sharing the token space (vocab) with TINY_TEST.
    return dataclasses.replace(
        CFG, name="tiny-draft", d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=64, head_dim=16,
    )


def make_engines(spec_k, draft_like_target=False, slots=3, eos_id=None,
                 **extra):
    """Build a (plain, speculative) engine pair over SHARED target params.
    ``extra`` EngineConfig fields apply to BOTH, so composition tests
    (fused steps, the adaptive planner, grouped prefill) compare like
    against like."""
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

    params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    dcfg = CFG if draft_like_target else _tiny_draft()
    dparams = (params if draft_like_target
               else transformer.init_params(dcfg, jax.random.PRNGKey(7),
                                            dtype=jnp.float32))
    ecfg = dict(decode_slots=slots, max_seq_len=96, prefill_buckets=(8, 16),
                **extra)
    plain = Engine(CFG, params, EngineConfig(**ecfg), eos_id=eos_id,
                   dtype=jnp.float32)
    spec = Engine(CFG, params, EngineConfig(**ecfg, speculative_k=spec_k),
                  eos_id=eos_id, dtype=jnp.float32,
                  draft_params=dparams, draft_cfg=dcfg)
    return plain, spec


def run_reqs(engine, prompts, max_new=12, temps=None):
    from llm_instance_gateway_tpu.server.engine import Request, SamplingParams

    reqs = []
    engine.start()
    try:
        for i, p in enumerate(prompts):
            t = 0.0 if temps is None else temps[i]
            r = Request(prompt_tokens=list(p), max_new_tokens=max_new,
                        sampling=SamplingParams(temperature=t))
            reqs.append(r)
            engine.submit(r)
        for r in reqs:
            assert r.done.wait(180)
            assert r.error is None, r.error
    finally:
        engine.stop()
    return reqs


class TestSpeculativeEngine:
    def test_greedy_parity_with_small_draft(self):
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 9, 14)]
        plain, spec = make_engines(spec_k=3)
        want = [r.output_tokens for r in run_reqs(plain, prompts)]
        got_reqs = run_reqs(spec, prompts)
        got = [r.output_tokens for r in got_reqs]
        assert got == want
        assert spec.spec_cycles > 0

    def test_perfect_draft_accepts_full_blocks(self):
        """Draft == target: every proposal accepted, so emitted tokens per
        cycle approach K+1."""
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(1, 250, size=6))]
        plain, spec = make_engines(spec_k=3, draft_like_target=True, slots=1)
        want = [r.output_tokens for r in run_reqs(plain, prompts, max_new=16)]
        got = [r.output_tokens for r in run_reqs(spec, prompts, max_new=16)]
        assert got == want
        # Prefill emits token 1; the remaining 15 arrive in
        # ~ceil(15/(K+1)) = 4 speculative cycles (+ slack for scheduling).
        assert spec.spec_cycles <= 6, spec.spec_cycles
        assert spec.spec_emitted == 15

    def test_mixed_temperature_batch(self):
        """Sampled rows coexist with greedy rows: greedy rows keep exact
        parity; sampled rows complete with the requested token count."""
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (6, 7, 8)]
        plain, spec = make_engines(spec_k=3)
        want = [r.output_tokens for r in
                run_reqs(plain, prompts, temps=[0.0, 0.0, 0.0])]
        got_reqs = run_reqs(spec, prompts, temps=[0.0, 0.9, 0.0])
        assert got_reqs[0].output_tokens == want[0]
        assert got_reqs[2].output_tokens == want[2]
        assert len(got_reqs[1].output_tokens) == 12

    def test_logprobs_recorded_through_spec_path(self):
        from llm_instance_gateway_tpu.server.engine import Request, SamplingParams

        rng = np.random.RandomState(3)
        _, spec = make_engines(spec_k=2, slots=1)
        spec.start()
        try:
            r = Request(prompt_tokens=list(rng.randint(1, 250, size=6)),
                        max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.0), logprobs=2)
            spec.submit(r)
            assert r.done.wait(180) and r.error is None
        finally:
            spec.stop()
        assert len(r.output_logprobs) == 8
        assert len(r.output_top_logprobs) == 8
        assert all(len(d) == 2 for d in r.output_top_logprobs)

    def test_config_validation(self):
        from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

        params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        with pytest.raises(ValueError, match="draft_params"):
            Engine(CFG, params, EngineConfig(speculative_k=2),
                   eos_id=None, dtype=jnp.float32)
        with pytest.raises(ValueError, match="token space"):
            Engine(CFG, params,
                   EngineConfig(speculative_k=2),
                   eos_id=None, dtype=jnp.float32,
                   draft_params=params,
                   draft_cfg=dataclasses.replace(CFG, vocab_size=640))


class TestSpeculativeMesh:
    """Speculation under a GSPMD serve mesh: the target keeps its shardings,
    the draft replicates, and greedy parity holds against the unsharded
    speculative engine."""

    def test_greedy_parity_on_mesh(self):
        from llm_instance_gateway_tpu.parallel.mesh import MeshConfig, make_mesh
        from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

        params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        dcfg = _tiny_draft()
        dparams = transformer.init_params(dcfg, jax.random.PRNGKey(7),
                                          dtype=jnp.float32)
        ecfg = EngineConfig(decode_slots=4, max_seq_len=96,
                            prefill_buckets=(8, 16), speculative_k=3)
        rng = np.random.RandomState(22)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 9, 14)]

        ref = Engine(CFG, params, ecfg, eos_id=None, dtype=jnp.float32,
                     draft_params=dparams, draft_cfg=dcfg)
        want = [r.output_tokens for r in run_reqs(ref, prompts)]

        mesh = make_mesh(MeshConfig(data=4, tensor=2))
        engine = Engine(CFG, params, ecfg, eos_id=None, dtype=jnp.float32,
                        draft_params=dparams, draft_cfg=dcfg, mesh=mesh)
        got = [r.output_tokens for r in run_reqs(engine, prompts)]
        assert got == want
        assert engine.spec_cycles > 0


class TestSpeculativeLoopComposition:
    """Speculation under the production loop shapes (VERDICT r2 #5): the
    adaptive planner, fused steps and grouped prefill must keep exact
    greedy parity with their non-speculative twins."""

    def test_greedy_parity_under_the_adaptive_planner(self):
        """``_spec_cycles_per_sync`` takes its budget from the planner."""
        rng = np.random.RandomState(10)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 9, 14)]
        plain, spec = make_engines(spec_k=3, adaptive_steps=8)
        want = [r.output_tokens for r in run_reqs(plain, prompts)]
        got = [r.output_tokens for r in run_reqs(spec, prompts)]
        assert got == want
        assert spec.spec_cycles > 0
        assert spec.spec_emitted > 0

    def test_greedy_parity_multistep(self):
        rng = np.random.RandomState(11)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (6, 8, 12)]
        plain, spec = make_engines(spec_k=2, decode_steps_per_sync=8)
        want = [r.output_tokens for r in run_reqs(plain, prompts)]
        got = [r.output_tokens for r in run_reqs(spec, prompts)]
        assert got == want
        # ceil(8/(K+1)) = 3 cycles per dispatch: fewer dispatches than tokens.
        assert spec.spec_cycles >= 3

    def test_greedy_parity_all_three_levers(self):
        """decode_steps_per_sync>1 + grouped prefill together."""
        rng = np.random.RandomState(12)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 7, 9, 11)]
        plain, spec = make_engines(
            spec_k=3, slots=4, decode_steps_per_sync=8, prefill_batch=2)
        want = [r.output_tokens for r in run_reqs(plain, prompts)]
        got = [r.output_tokens for r in run_reqs(spec, prompts)]
        assert got == want
        assert spec.spec_emitted > 0

    def test_eos_stops_inside_block(self):
        """Device-side EOS truncation: tokens proposed past an accepted EOS
        are discarded and the row freezes."""
        rng = np.random.RandomState(14)
        prompt = list(rng.randint(1, 250, size=6))
        plain, spec = make_engines(
            spec_k=3, draft_like_target=True, slots=1)
        # Discover the greedy continuation, then rerun with eos set to
        # a mid-sequence token so the stop lands inside a cycle.
        ref = run_reqs(plain, [prompt], max_new=16)[0].output_tokens
        eos = ref[6]
        plain2, spec2 = make_engines(
            spec_k=3, draft_like_target=True, slots=1, eos_id=eos)
        want = run_reqs(plain2, [prompt], max_new=16)[0]
        got = run_reqs(spec2, [prompt], max_new=16)[0]
        assert got.output_tokens == want.output_tokens
        assert got.finish_reason == want.finish_reason == "stop"


class TestSpeculativePaged:
    """Speculation over the paged KV cache (extend_step_paged): exact
    greedy parity with the non-speculative paged engine, a step and four
    fused steps a dispatch."""

    def _engines(self, spec_k, steps, slots=3):
        from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

        params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        dcfg = _tiny_draft()
        dparams = transformer.init_params(dcfg, jax.random.PRNGKey(7),
                                          dtype=jnp.float32)
        ecfg = dict(decode_slots=slots, max_seq_len=96, prefill_buckets=(8, 16),
                    paged_kv_block=8, decode_steps_per_sync=steps)
        plain = Engine(CFG, params, EngineConfig(**ecfg), eos_id=None,
                       dtype=jnp.float32)
        spec = Engine(CFG, params, EngineConfig(**ecfg, speculative_k=spec_k),
                      eos_id=None, dtype=jnp.float32,
                      draft_params=dparams, draft_cfg=dcfg)
        return plain, spec

    @pytest.mark.parametrize("steps", [1, 4], ids=["one-step", "fused"])
    def test_greedy_parity_paged(self, steps):
        rng = np.random.RandomState(20)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 9, 14)]
        plain, spec = self._engines(spec_k=3, steps=steps)
        want = [r.output_tokens for r in run_reqs(plain, prompts)]
        got = [r.output_tokens for r in run_reqs(spec, prompts)]
        assert got == want
        assert spec.spec_cycles > 0

    def test_paged_extend_matches_contiguous(self):
        """extend_step_paged vs transformer.extend_step, same rows/tokens:
        logits parity through block-table indirection."""
        from llm_instance_gateway_tpu.models import paged as paged_lib

        params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        b, s_max, block, c = 2, 32, 8, 3
        rng = np.random.RandomState(1)
        lane = transformer.init_decode_cache(CFG, b, s_max, dtype=jnp.float32)
        pagedc = paged_lib.init_paged_cache(CFG, b, s_max, 8, block,
                                            dtype=jnp.float32)
        tables = np.array(pagedc["tables"])  # writable host copy
        starts = [5, 7]
        next_free = 1
        for row, n in enumerate(starts):
            prompt = jnp.asarray([rng.randint(1, 250, size=n)], jnp.int32)
            pos = jnp.arange(n)[None]
            _, k, v = transformer.prefill(CFG, params, prompt, pos)
            lane = transformer.insert_prefill(lane, k, v, row, n)
            nb = -(-(n + c) // block)
            phys = list(range(next_free, next_free + nb))
            next_free += nb
            tables[row, :nb] = phys
            pagedc = paged_lib.insert_prefill_paged(
                dict(pagedc, tables=jnp.asarray(tables)), k, v, row,
                jnp.asarray(phys[: -(-n // block)], jnp.int32),
                jnp.asarray(tables[row], jnp.int32), n)
        tokens = jnp.asarray(rng.randint(1, 250, size=(b, c)), jnp.int32)
        positions = jnp.asarray([[s + i for i in range(c)] for s in starts],
                                jnp.int32)
        want, _ = transformer.extend_step(CFG, params, lane, tokens, positions)
        got, _ = paged_lib.extend_step_paged(CFG, params, pagedc, tokens,
                                             positions)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_mixed_batch_schedule_shrink_keeps_parity(self):
        """Regression: paged with VARIABLE dispatch sizes — a
        mixed batch (sampled row present) dispatches steps*(K+1) writes,
        then the sampled row finishes and the schedule shrinks.  The paged
        reservation must cover the in-flight larger dispatch or accepted
        KV lands in the trash block and later tokens silently corrupt."""
        from llm_instance_gateway_tpu.server.engine import (
            Request, SamplingParams)

        rng = np.random.RandomState(21)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (6, 9)]
        plain, spec = self._engines(spec_k=3, steps=4, slots=3)

        def run(engine, with_sampled):
            reqs = [Request(prompt_tokens=list(p), max_new_tokens=40,
                            sampling=SamplingParams(temperature=0.0))
                    for p in prompts]
            engine.start()
            try:
                for r in reqs:
                    engine.submit(r)
                if with_sampled:
                    # A short sampled request rides along, finishes early,
                    # and flips the spec schedule from mixed to all-greedy.
                    s = Request(prompt_tokens=[3, 4, 5], max_new_tokens=4,
                                sampling=SamplingParams(temperature=0.9))
                    engine.submit(s)
                for r in reqs:
                    assert r.done.wait(240) and r.error is None, r.error
            finally:
                engine.stop()
            return [r.output_tokens for r in reqs]

        want = run(plain, with_sampled=False)
        got = run(spec, with_sampled=True)
        assert got == want

    def test_paged_plus_data_mesh_rejected_clearly(self):
        """paged + a data-axis mesh is unsupported (the block pool has no
        batch sharding); the rejection must be a clear ValueError, not a
        shard_pytree tree mismatch — speculative or not."""
        from llm_instance_gateway_tpu.parallel.mesh import MeshConfig, make_mesh
        from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

        params = transformer.init_params(CFG, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        mesh = make_mesh(MeshConfig(data=len(jax.devices("cpu"))))
        with pytest.raises(ValueError, match="data=1"):
            Engine(CFG, params, EngineConfig(paged_kv_block=8),
                   eos_id=None, dtype=jnp.float32, mesh=mesh)
        dcfg = _tiny_draft()
        with pytest.raises(ValueError, match="data=1"):
            Engine(CFG, params,
                   EngineConfig(paged_kv_block=8, speculative_k=2),
                   eos_id=None, dtype=jnp.float32, mesh=mesh,
                   draft_params=transformer.init_params(
                       dcfg, jax.random.PRNGKey(7), dtype=jnp.float32),
                   draft_cfg=dcfg)

    def test_spec_paged_tensor_mesh_parity(self):
        """The FULL composition — speculation + paged pool + tensor mesh —
        keeps exact greedy parity with the unsharded spec+paged engine
        (the verify primitive is plain einsums over a kv-head-sharded
        pool)."""
        from llm_instance_gateway_tpu.parallel.mesh import MeshConfig, make_mesh
        from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

        cfg = dataclasses.replace(
            CFG, name="spm", d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
        dcfg = dataclasses.replace(
            cfg, name="spm-draft", d_model=32, n_layers=1, n_heads=2,
            n_kv_heads=1, d_ff=64, head_dim=16)
        dparams = transformer.init_params(dcfg, jax.random.PRNGKey(7),
                                          dtype=jnp.float32)
        ecfg = EngineConfig(decode_slots=2, max_seq_len=64,
                            prefill_buckets=(8, 16), paged_kv_block=8,
                            speculative_k=2)
        rng = np.random.RandomState(23)
        prompts = [list(rng.randint(1, 250, size=n)) for n in (5, 9)]

        ref = Engine(cfg, params, ecfg, eos_id=None, dtype=jnp.float32,
                     draft_params=dparams, draft_cfg=dcfg)
        want = [r.output_tokens for r in run_reqs(ref, prompts)]
        mesh = make_mesh(MeshConfig(tensor=2, fsdp=4))
        engine = Engine(cfg, params, ecfg, eos_id=None, dtype=jnp.float32,
                        draft_params=dparams, draft_cfg=dcfg, mesh=mesh)
        got = [r.output_tokens for r in run_reqs(engine, prompts)]
        assert got == want
        assert engine.spec_cycles > 0
