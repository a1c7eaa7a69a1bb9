"""Pool-document scheduler config + stream cancellation tests."""

import time

import jax
import jax.numpy as jnp
import pytest

from llm_instance_gateway_tpu.api.v1alpha1 import inference_pool_from_doc
from llm_instance_gateway_tpu.gateway.scheduling.config import (
    DEFAULT_CONFIG,
    from_pool_spec,
)
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST
from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig, Request


class TestPoolSchedulerConfig:
    def test_defaults_without_overrides(self):
        assert from_pool_spec({}) is DEFAULT_CONFIG

    def test_overrides_applied(self):
        cfg = from_pool_spec({"kvCacheThreshold": 0.6, "queueThresholdCritical": 2})
        assert cfg.kv_cache_threshold == 0.6
        assert cfg.queue_threshold_critical == 2
        assert cfg.queueing_threshold_lora == DEFAULT_CONFIG.queueing_threshold_lora

    def test_unknown_key_raises(self):
        with pytest.raises(ValueError, match="unknown schedulerConfig"):
            from_pool_spec({"kvThresold": 0.6})  # typo must be loud

    def test_parsed_from_pool_document(self):
        pool = inference_pool_from_doc({
            "kind": "InferencePool",
            "metadata": {"name": "p"},
            "spec": {
                "selector": {"app": "x"},
                "targetPortNumber": 8000,
                "schedulerConfig": {"queueingThresholdLoRA": 25},
            },
        })
        cfg = from_pool_spec(pool.spec.scheduler)
        assert cfg.queueing_threshold_lora == 25


class TestCancellation:
    def test_cancel_frees_slot(self):
        cfg = TINY_TEST
        params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        engine = Engine(
            cfg, params,
            EngineConfig(decode_slots=1, max_seq_len=1024, prefill_buckets=(8,),
                         decode_steps_per_sync=2),
            eos_id=None, dtype=jnp.float32,
        )
        engine.start()
        try:
            # Long enough that natural completion takes many seconds — the
            # cancel (fired at the FIRST token) must deterministically win.
            long_req = Request(prompt_tokens=[1, 2, 3], max_new_tokens=800)
            engine.submit(long_req)
            # Let it start, then cancel (client disconnect).  Generous
            # deadlines: under parallel test load the first block (incl.
            # compiles) can take tens of seconds.
            deadline = time.monotonic() + 90
            while not long_req.output_tokens and time.monotonic() < deadline:
                time.sleep(0.05)
            assert long_req.output_tokens, "first token never arrived"
            long_req.cancelled.set()
            assert long_req.done.wait(60)
            assert long_req.finish_reason == "cancelled"
            assert len(long_req.output_tokens) < 800
            # The freed slot must serve the next request normally.
            follow_up = engine.generate(
                Request(prompt_tokens=[4, 5], max_new_tokens=4), timeout_s=60
            )
            assert follow_up.error is None
            assert len(follow_up.output_tokens) == 4
        finally:
            engine.stop()


class TestHotReload:
    POOL_DOC_TMPL = {
        "kind": "InferencePool",
        "metadata": {"name": "p", "resourceVersion": "1"},
        "spec": {"selector": {"app": "x"}, "targetPortNumber": 8000,
                 "schedulerConfig": {"queueThresholdCritical": 5}},
    }

    def build(self, tmp_path):
        import yaml
        from llm_instance_gateway_tpu.gateway import bootstrap

        path = tmp_path / "pool.yaml"
        path.write_text(yaml.safe_dump(self.POOL_DOC_TMPL))
        return bootstrap.build_gateway(str(path))

    def test_pool_update_pushes_thresholds_into_scheduler(self, tmp_path):
        """A reconciled pool edit must change live scheduler thresholds."""
        from llm_instance_gateway_tpu.api.v1alpha1 import inference_pool_from_doc

        comps = self.build(tmp_path)
        assert comps.scheduler.cfg.queue_threshold_critical == 5
        updated = {
            **self.POOL_DOC_TMPL,
            "metadata": {"name": "p", "resourceVersion": "2"},
            "spec": {**self.POOL_DOC_TMPL["spec"],
                     "schedulerConfig": {"queueThresholdCritical": 17}},
        }
        assert comps.pool_reconciler.reconcile(inference_pool_from_doc(updated))
        assert comps.scheduler.cfg.queue_threshold_critical == 17

    def test_bad_reload_keeps_last_good(self, tmp_path):
        """A typo'd reloaded schedulerConfig must not crash or change state."""
        from llm_instance_gateway_tpu.api.v1alpha1 import inference_pool_from_doc

        comps = self.build(tmp_path)
        bad = {
            **self.POOL_DOC_TMPL,
            "metadata": {"name": "p", "resourceVersion": "2"},
            "spec": {**self.POOL_DOC_TMPL["spec"],
                     "schedulerConfig": {"queueThresoldCritical": 9}},
        }
        comps.pool_reconciler.reconcile(inference_pool_from_doc(bad))
        assert comps.scheduler.cfg.queue_threshold_critical == 5

    def test_fractional_int_threshold_rejected(self):
        with pytest.raises(ValueError, match="must be an integer"):
            from_pool_spec({"queueThresholdCritical": 5.9})
