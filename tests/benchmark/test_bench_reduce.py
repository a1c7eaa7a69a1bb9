"""The yardstick's arithmetic: trace reduction, byte counts, readers."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import client, manifest, peaks, readers, shapes  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
QWEN = manifest.load_config("qwen2.5-7b")["model"]
MIXTRAL = manifest.load_config("mixtral-8x7b-d6")["model"]


# -- trace reduction ---------------------------------------------------------

def test_union_busy_window_and_gaps_of_a_hand_made_trace():
    ops = [["a", 0, 10], ["b", 5, 10], ["c", 30, 10], ["a", 40, 5],
           ["zero", 50, 0]]
    mods = [["jit_x(1)", 0, 15], ["jit_y(2)", 30, 15]]
    s = trace_reduce.summarise(ops, mods)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["window_s"] == pytest.approx(45e-9)
    assert s["device_ops"][0] == ["a", pytest.approx(15e-9)]
    assert s["idle_gaps"] == [["after_jit_x_1_before_jit_y_2",
                               pytest.approx(15e-9)]]
    assert s["modules"]["jit_x_1"]["count"] == 1


def test_no_device_operation_is_an_error_not_a_zero():
    assert "error" in trace_reduce.summarise([], [])


def test_recorded_trace_reduces_to_the_recorded_numbers():
    """A short excerpt of a real v5e trace of the decode loop (taken by the
    wrapper in this benchmark's first chip run), with the numbers the
    reduction gave then."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    s = trace_reduce.summarise(rec["ops"], rec["modules"])
    assert s["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert [n for n, _ in s["device_ops"]][:3] == rec["expect"]["top3"]
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    mod = trace_reduce.decode_module(s, rec["expect"]["min_module_s"])
    assert mod["name"] == rec["expect"]["decode_module"]


def test_decode_module_is_the_most_run_above_the_floor():
    s = {"modules": {"tiny": {"count": 500, "median_s": 0.0002},
                     "decode": {"count": 50, "median_s": 0.07},
                     "prefill": {"count": 9, "median_s": 0.11}}}
    assert trace_reduce.decode_module(s)["name"] == "decode"
    assert trace_reduce.decode_module({"modules": {}}) is None


# -- bytes from shapes -------------------------------------------------------

def test_qwen_weight_bytes_match_the_servers_report():
    # int8 projections + lm_head: 7.07 GB (server's /debug/device, PR 21).
    assert shapes.weight_bytes(QWEN) == pytest.approx(7.07e9, rel=0.005)


def test_qwen_kv_cache_is_3_76_gb_at_32_slots_of_2048():
    assert shapes.kv_bytes_per_token(QWEN) == 28 * 2 * 4 * 128 * 2
    assert shapes.kv_cache_bytes(QWEN, 32, 2048) == pytest.approx(3.758e9,
                                                                  rel=1e-3)


def test_mixtral_layer_is_1_45_gb_and_the_batch_touches_every_expert():
    assert shapes.layer_weight_bytes(MIXTRAL) == pytest.approx(1.45e9,
                                                               rel=0.01)
    assert shapes.experts_touched(8, 2, 32) == pytest.approx(8.0, abs=0.01)
    assert shapes.experts_touched(8, 2, 1) == pytest.approx(2.0)
    one = shapes.decode_step_bytes(MIXTRAL, 1, 100)
    full = shapes.decode_step_bytes(MIXTRAL, 32, 100)
    assert one < 0.35 * full


@pytest.mark.parametrize("rows,context", [(1, 0), (16, 300), (32, 2048)])
def test_decode_step_bytes_is_weights_plus_kv_in_use(rows, context):
    got = shapes.decode_step_bytes(QWEN, rows, context)
    assert got == shapes.weight_bytes(QWEN) + rows * context * 57344


def test_unknown_device_is_an_error_not_a_default():
    assert peaks.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")


# -- readers -----------------------------------------------------------------

BEFORE = """\
# TYPE tpu:decode_step_seconds histogram
tpu:decode_step_seconds_bucket{le="0.1"} 10
tpu:decode_step_seconds_bucket{le="0.25"} 10
tpu:decode_step_seconds_bucket{le="+Inf"} 10
tpu:decode_step_seconds_sum 0.8
tpu:decode_step_seconds_count 10
tpu:dispatch_steps_sum 10
tpu:dispatch_steps_count 10
tpu:dispatch_gap_seconds_sum{kind="host"} 0.1
tpu:dispatch_gap_seconds_sum{kind="idle"} 5.0
"""
AFTER = """\
tpu:decode_step_seconds_bucket{le="0.1"} 60
tpu:decode_step_seconds_bucket{le="0.25"} 110
tpu:decode_step_seconds_bucket{le="+Inf"} 110
tpu:decode_step_seconds_sum 10.8
tpu:decode_step_seconds_count 110
tpu:dispatch_steps_sum 210
tpu:dispatch_steps_count 110
tpu:dispatch_gap_seconds_sum{kind="host"} 0.5
tpu:dispatch_gap_seconds_sum{kind="idle"} 9.0
"""


def _ctx(**kw):
    ctx = {"prom_before": [BEFORE], "prom_after": [AFTER], "window_s": 10.0,
           "results": [], "t0": 100.0}
    ctx.update(kw)
    return ctx


def test_prom_delta_ratio_of_two_families():
    spec = manifest.load_metric("model.decode_step_ms")
    assert readers.prom_delta(spec["args"], _ctx()) == pytest.approx(50.0)
    spec = manifest.load_metric("model.dispatch_steps_mean")
    assert readers.prom_delta(spec["args"], _ctx()) == pytest.approx(2.0)


def test_prom_delta_share_of_the_window_is_the_replicas_mean():
    spec = manifest.load_metric("engine.host_gap_pct")
    assert readers.prom_delta(spec["args"], _ctx()) == pytest.approx(4.0)
    two = _ctx(prom_before=[BEFORE, BEFORE], prom_after=[AFTER, AFTER])
    assert readers.prom_delta(spec["args"], two) == pytest.approx(4.0)


def test_a_reader_that_finds_nothing_returns_nothing():
    args = {"num": {"family": "tpu:absent_sum"}, "den": "window_s"}
    assert readers.prom_delta(args, _ctx()) is None
    assert readers.trace_idle({}, _ctx(trace=None)) is None
    assert readers.roofline({}, _ctx(trace=None)) is None
    assert readers.client_quantile({"field": "ttft", "q": 0.5},
                                   _ctx()) is None


def test_prom_hist_quantile_interpolates_inside_the_bucket():
    args = {"family": "tpu:decode_step_seconds", "q": 0.5, "scale": 1000.0}
    # growth: 50 in (0, 0.1], 50 in (0.1, 0.25]: the median is the 0.1 edge.
    assert readers.prom_hist_quantile(args, _ctx()) == pytest.approx(100.0)
    args["q"] = 0.75
    assert readers.prom_hist_quantile(args, _ctx()) == pytest.approx(175.0)


def test_span_quantile_reads_attributes_and_lengths():
    traces = [{"spans": [
        {"name": "gateway.admission", "start": 0, "end": 1,
         "attrs": {"pick_s": 0.0005}},
        {"name": "engine.queue_wait", "start": 1.0, "end": 1.25}]}]
    ctx = _ctx(gateway_traces=traces, server_traces=[traces])
    pick = manifest.load_metric("gateway.pick_p50_us")["args"]
    assert readers.span_quantile(pick, ctx) == pytest.approx(500.0)
    wait = manifest.load_metric("server.queue_wait_p50_ms")["args"]
    assert readers.span_quantile(wait, ctx) == pytest.approx(250.0)


@pytest.mark.parametrize("q,want", [(0.0, 1.0), (0.5, 2.5), (0.9, 3.7),
                                    (1.0, 4.0)])
def test_quantile_interpolates(q, want):
    assert readers.quantile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def _result(i, due, first, last, tokens, **kw):
    r = client.Result(index=i, due=due, sent=due + 0.001, status=200,
                      t_first=first, t_last=last, tokens=tokens,
                      want_tokens=tokens, prompt_tokens=100)
    if first is not None:
        r.chunks = [(first, 1), (last, tokens - 1)]
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_client_metrics_time_from_due_and_count_failures_as_misses():
    rs = [_result(0, 100.0, 100.2, 101.2, 11, served_by="r0"),
          _result(1, 101.0, 101.4, 103.4, 21, served_by="r0"),
          _result(2, 102.0, 102.1, 102.1, 1, served_by="r1"),
          _result(3, 103.0, None, None, 0, error="HTTP 429", served_by=None),
          _result(4, 95.0, 95.1, 96.0, 10, in_window=False)]
    ctx = _ctx(results=rs, traffic={"slo": {"ttft_ms": 300, "tpot_ms": 150}},
               pod_names=["r0", "r1"])
    assert readers.client_quantile({"field": "ttft", "q": 0.5},
                                   ctx) == pytest.approx(200.0)
    assert readers.client_quantile({"field": "tpot", "q": 0.5},
                                   ctx) == pytest.approx(100.0)
    assert readers.client_quantile({"field": "late", "q": 0.9},
                                   ctx) == pytest.approx(1.0)
    # good: request 0 and 2; request 1 misses TTFT; request 3 failed.
    assert readers.client_slo_good({}, ctx) == pytest.approx(50.0)
    assert readers.client_imbalance({}, ctx) == pytest.approx(100 * 0.5 / 1.5)
    # tokens that arrived in [100, 110): all of requests 0-2, none of 4.
    assert readers.client_tokens_per_s({}, ctx) == pytest.approx(3.3)


def test_roofline_share_from_bytes_bandwidth_and_program_time():
    rs = [_result(0, 100.0, 100.2, 101.2, 64)]
    nbytes = shapes.decode_step_bytes(QWEN, 16.0, 100 + 32.0)
    ctx = _ctx(results=rs, device_kind="TPU v5 lite",
               config={"model": QWEN},
               profile_records=[[{"phase": "decode", "active": 16},
                                 {"phase": "prefill", "active": 3}]],
               trace={"modules": {"d": {"count": 40, "median_s": 0.05,
                                        "total_s": 2.0}}})
    got = readers.roofline({}, ctx)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.05)
    assert 0 < got < 100
