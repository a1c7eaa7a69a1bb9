"""Metrics parsing + provider tests.

Parity: ``backend/vllm/metrics_test.go:14-232`` (family mapping, LoRA label
permutations, latest-series selection, error aggregation) and
``backend/provider_test.go:39-114`` (fake client injection, init snapshot).
"""

import pytest

from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.metrics_client import (
    FakePodMetricsClient,
    FetchError,
    families_to_metrics,
)
from llm_instance_gateway_tpu.gateway.provider import Provider
from llm_instance_gateway_tpu.gateway.types import Metrics, Pod
from llm_instance_gateway_tpu.utils import prom_parse

EXPOSITION = """\
# HELP tpu:num_requests_running in-flight
# TYPE tpu:num_requests_running gauge
tpu:num_requests_running 2
tpu:num_requests_waiting 7
tpu:prefill_queue_size 4
tpu:decode_queue_size 3
tpu:kv_cache_usage_perc 0.35
tpu:kv_tokens_capacity 44448
tpu:kv_tokens_free 28891
tpu:kv_parked_tokens 512
tpu:decode_tokens_per_sec 1234.5
tpu:prefix_reused_tokens 640
tpu:lora_requests_info{running_lora_adapters="sql-lora,tweet-lora",max_lora="4"} 100.0
tpu:lora_requests_info{running_lora_adapters="old-lora",max_lora="4"} 90.0
"""


class TestPromParse:
    def test_parse_families(self):
        fams = prom_parse.parse_text(EXPOSITION)
        assert fams["tpu:num_requests_waiting"][0].value == 7
        assert len(fams["tpu:lora_requests_info"]) == 2
        assert fams["tpu:lora_requests_info"][0].labels["running_lora_adapters"] == "sql-lora,tweet-lora"

    def test_parse_escapes_and_timestamps(self):
        fams = prom_parse.parse_text('m{l="a\\"b\\n"} 1.5 1700000000000\n')
        s = fams["m"][0]
        assert s.labels["l"] == 'a"b\n'
        assert s.value == 1.5 and s.timestamp_ms == 1700000000000

    def test_latest_sample_by_timestamp(self):
        fams = prom_parse.parse_text("m 1 100\nm 2 300\nm 3 200\n")
        assert prom_parse.latest_sample(fams["m"]).value == 2


class TestFamiliesToMetrics:
    def test_full_mapping(self):
        fams = prom_parse.parse_text(EXPOSITION)
        m, errs = families_to_metrics(fams, Metrics())
        assert errs == []
        assert m.running_queue_size == 2
        assert m.waiting_queue_size == 7
        assert m.prefill_queue_size == 4
        assert m.decode_queue_size == 3
        assert m.kv_cache_usage_percent == pytest.approx(0.35)
        assert m.kv_tokens_capacity == 44448
        assert m.kv_tokens_free == 28891
        assert m.kv_parked_tokens == 512
        assert m.prefix_reused_tokens == 640
        # Latest LoRA series wins (gauge value = snapshot ts, metrics.go:135-150).
        assert set(m.active_adapters) == {"sql-lora", "tweet-lora"}
        assert m.max_active_adapters == 4

    def test_missing_families_keep_stale_values_and_report(self):
        existing = Metrics(waiting_queue_size=9, kv_cache_usage_percent=0.5)
        m, errs = families_to_metrics({}, existing)
        assert m.waiting_queue_size == 9  # stale persists (provider.go:150-159)
        assert m.kv_cache_usage_percent == 0.5
        assert len(errs) == 3  # running, waiting, kv usage

    def test_clone_does_not_mutate_existing(self):
        existing = Metrics(active_adapters={"x": 1})
        fams = prom_parse.parse_text(EXPOSITION)
        m, _ = families_to_metrics(fams, existing)
        assert existing.active_adapters == {"x": 1}
        assert "sql-lora" in m.active_adapters


class TestProvider:
    def make(self, res=None, err=None, pods=("p1", "p2")):
        ds = Datastore(pods=[Pod(p, f"{p}:8000") for p in pods])
        client = FakePodMetricsClient(res=res, err=err)
        return Provider(client, ds), ds

    def test_refresh_populates_metrics(self):
        want = Metrics(waiting_queue_size=3, kv_cache_usage_percent=0.2)
        prov, _ = self.make(res={"p1": want, "p2": Metrics()})
        prov.refresh_pods_once()
        errs = prov.refresh_metrics_once()
        assert errs == []
        got = {pm.pod.name: pm.metrics for pm in prov.all_pod_metrics()}
        assert got["p1"].waiting_queue_size == 3
        assert got["p2"].waiting_queue_size == 0

    def test_fetch_error_is_nonfatal_and_keeps_stale(self):
        prov, _ = self.make(
            res={"p1": Metrics(waiting_queue_size=5)},
            err={"p2": FetchError("connection refused")},
        )
        prov.refresh_pods_once()
        errs = prov.refresh_metrics_once()
        assert any("connection refused" in e for e in errs)
        got = {pm.pod.name: pm.metrics for pm in prov.all_pod_metrics()}
        assert got["p2"].waiting_queue_size == 0  # zeroed initial, kept
        assert got["p1"].waiting_queue_size == 5

    def test_pod_removal_drops_metrics(self):
        prov, ds = self.make(res={})
        prov.refresh_pods_once()
        assert len(prov.all_pod_metrics()) == 2
        ds.delete_pod("p1")
        prov.refresh_pods_once()
        assert [pm.pod.name for pm in prov.all_pod_metrics()] == ["p2"]

    def test_init_runs_initial_refresh_then_stops(self):
        prov, _ = self.make(res={"p1": Metrics(waiting_queue_size=1)})
        prov.init(refresh_pods_interval_s=30, refresh_metrics_interval_s=30)
        try:
            assert len(prov.all_pod_metrics()) == 2
        finally:
            prov.stop()

    def test_scrape_health_tracks_freshness_and_streaks(self):
        """Tentpole: per-pod scrape freshness + failure streaks feed the
        health scorer, and failures land in the flight recorder
        (throttled: first, then every 10th)."""
        from llm_instance_gateway_tpu import events

        prov, _ = self.make(
            res={"p1": Metrics()},
            err={"p2": FetchError("connection refused")},
        )
        journal = events.EventJournal()
        prov.journal = journal
        prov.refresh_pods_once()
        for _ in range(11):
            prov.refresh_metrics_once()
        sh = prov.scrape_health()
        ok_ts, ok_streak = sh["p1"]
        assert ok_ts is not None and ok_streak == 0
        fail_ts, fail_streak = sh["p2"]
        assert fail_ts is None and fail_streak == 11
        rows = journal.events(kind=events.SCRAPE_FAILURE, limit=100)
        # Throttle: streak 1 and streak 10 only.
        assert [e["attrs"]["streak"] for e in rows] == [1, 10]
        assert all(e["attrs"]["pod"] == "p2" for e in rows)

    def test_scrape_health_forgets_removed_pods(self):
        prov, ds = self.make(
            err={"p2": FetchError("x")}, res={"p1": Metrics()})
        prov.refresh_pods_once()
        prov.refresh_metrics_once()
        assert prov.scrape_health()["p2"][1] == 1
        ds.delete_pod("p2")
        prov.refresh_pods_once()
        prov.refresh_metrics_once()
        assert "p2" not in prov.scrape_health()
