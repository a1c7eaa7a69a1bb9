"""What PR 37 added to the benchmark: 24 per-layer metrics of the request path
outside the engine loop, all data files over two readers that were there
(``span_quantile``, ``prom_delta``).  Each reads its number from a canned
``/metrics`` text or trace list, and nothing, without an error, from what the
parent gives (no counter, no attribute, no span); three read what the parent
records already and report there too.

Their ``per_layer`` entries waited in ``benchmark/pending/`` until PR 41
appended them to ``BENCHMARK.json``, where this file reads them; the six
``.batch`` twins list both closed-loop cells."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402

MAN = manifest.load_manifest()
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}
OPEN3 = ["qwen7b_chat", "qwen7b_doc", "olmoe_chat"]
OPEN4 = ["qwen7b_chat", "qwen7b_doc", "qwen7b_pool4_chat", "olmoe_chat"]
POOL = ["qwen7b_pool4_chat"]
CLOSED = ["mixtral_d6_batch", "glm47flash_d13_agents"]

# name -> (layer, its value over the canned change)
FIRST_TOKEN = {  # twin .pool
    "gateway.ttft_p50_ms": ("gateway routing", 40.0),
    "gateway.upstream_first_p50_ms": ("gateway routing", 36.0),
    "server.accept_p50_ms": ("server API and admission", 2.0),
    "server.prefill_span_p50_ms": ("server API and admission", 20.0),
    "server.first_write_p50_ms": ("server API and admission", 1.5),
}
PER_TOKEN = {  # twin .batch
    "gateway.relay_p50_us": ("gateway routing", 80.0),
    "server.write_lag_ms": ("server API and admission", 0.5),
    "server.loop_lag_ms": ("server API and admission", 0.25),
    "server.stalled_ms": ("server API and admission", 1000.0),
    "engine.prefill_stage_ms": ("engine loop", 3.0),
    "engine.decode_account_ms": ("engine loop", 1.0),
}
OPEN_ONLY = {  # no twin: the closed-loop cells are not listed
    "gateway.loop_lag_ms": ("gateway routing", 0.125),
    "gateway.stalled_ms": ("gateway routing", 1000.0),
}
# These read a span and two counters that were there (the engine.prefill
# span, PR 24's phase counters): the parent reports them too.
PARENT_HAS = {"server.prefill_span_p50_ms", "engine.prefill_stage_ms",
              "engine.decode_account_ms"}

CASES = ([(n, "", "tpot_p50_ms", OPEN3) for n in FIRST_TOKEN]
         + [(n, ".pool", "ttft_p50_ms", POOL) for n in FIRST_TOKEN]
         + [(n, "", "tpot_p50_ms", OPEN4) for n in PER_TOKEN]
         + [(n, ".batch", "output_tok_s", CLOSED) for n in PER_TOKEN]
         + [(n, "", "tpot_p50_ms", OPEN4) for n in OPEN_ONLY])
EXPECT = {**FIRST_TOKEN, **PER_TOKEN, **OPEN_ONLY}


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def span(name, start, length, **attrs):
    s = {"name": name, "start": start, "end": start + length}
    if attrs:
        s["attrs"] = attrs
    return s


def gateway_trace(i, new):
    """Request ``i`` of three as the gateway records it; ``new``: with what
    this PR adds to the ``gateway.stream`` record."""
    attrs = {"pod": "replica-0"}
    if new:
        attrs.update(pre_s=0.003, first_chunk_s=0.030 + 0.006 * i,
                     ttft_s=0.034 + 0.006 * i, relay_mean_s=0.00007 + 1e-5 * i,
                     relay_max_s=0.001, chunks=64,
                     loop_lag_s=0.000100 + 0.000025 * i,
                     stall_s=1.0 if i == 2 else 0.0)
    return {"trace_id": f"t{i}", "spans": [
        span("gateway.admission", 100.0 + i, 0.001, pick_s=0.0006),
        span("gateway.stream", 100.003 + i, 2.0, **attrs)]}


def server_trace(i, new):
    spans = [span("engine.queue_wait", 100.006 + i, 0.009),
             span("engine.prefill", 100.015 + i, 0.018 + 0.002 * i,
                  **({"prompt_tokens": 96, "bucket": 128, "rows": 4,
                      "stage_s": 0.003, "wait_s": 0.015, "emit_s": 0.0004}
                     if new else {})),
             span("engine.decode", 100.035 + i, 2.0)]
    if new:
        spans += [span("server.accept", 100.004 + i, 0.001 + 0.001 * i),
                  span("server.first_write", 100.035 + i, 0.0005 + 0.001 * i)]
    return {"trace_id": f"t{i}", "spans": spans}


OLD_BEFORE = (
    'tpu:engine_phase_seconds_total{phase="prefill.stage",on="host"} 1.0\n'
    'tpu:engine_phase_seconds_total{phase="decode.account",on="host"} 2.0\n'
    'tpu:prefill_seconds_count{model="m",role="collocated"} 100\n'
    'tpu:dispatch_steps_sum 1000\n')
OLD_AFTER = (
    'tpu:engine_phase_seconds_total{phase="prefill.stage",on="host"} 1.6\n'
    'tpu:engine_phase_seconds_total{phase="decode.account",on="host"} 4.0\n'
    'tpu:prefill_seconds_count{model="m",role="collocated"} 300\n'
    'tpu:dispatch_steps_sum 3000\n')
NEW_BEFORE = OLD_BEFORE + (
    'tpu:stream_write_lag_seconds_total 1.0\ntpu:stream_chunks_total 2000\n'
    'tpu:loop_lag_seconds_total 0.5\ntpu:loop_ticks_total 400\n'
    'tpu:loop_stall_seconds_total 0.0\n')
NEW_AFTER = OLD_AFTER + (
    'tpu:stream_write_lag_seconds_total 5.0\ntpu:stream_chunks_total 10000\n'
    'tpu:loop_lag_seconds_total 0.7\ntpu:loop_ticks_total 1200\n'
    'tpu:loop_stall_seconds_total 1.0\n')


def ctx_of(new: bool) -> dict:
    return {"window_s": 40.0,
            "prom_before": [NEW_BEFORE if new else OLD_BEFORE],
            "prom_after": [NEW_AFTER if new else OLD_AFTER],
            "gateway_traces": [gateway_trace(i, new) for i in range(3)],
            "server_traces": [[server_trace(i, new) for i in range(3)]]}


def test_the_manifest_holds_the_entries_after_those_that_were_there():
    assert manifest.problems(MAN) == []
    names = [m["name"] for m in MAN["per_layer"]]
    new = [n + s for n, s, _, _ in CASES]
    assert len(set(new)) == len(CASES) == 24
    assert set(new) <= set(names)
    # appended, in one block and in the order PR 37 gave them: a program's
    # PR appends and never inserts
    first = names.index(new[0])
    assert first > names.index("mla.ctx_positions_mean.batch")
    block = names[first:first + len(new)]
    assert set(block) == set(new)
    assert not os.path.exists(os.path.join(REPO, "benchmark", "pending"))


@pytest.mark.parametrize("base,suffix,moves,cells", CASES,
                         ids=[n + s for n, s, _, _ in CASES])
def test_entry_and_its_reading(base, suffix, moves, cells):
    name = base + suffix
    entry = PER_LAYER[name]
    layer, value = EXPECT[base]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        layer, moves, "lower")
    # the cells it came with, and whichever joined since: each reports
    # what the metric moves
    assert set(cells) <= set(entry["workloads"]) <= set(
        E2E[moves]["workloads"])
    spec = manifest.load_metric(name)
    assert spec["reader"] in ("span_quantile", "prom_delta")
    assert entry["source"] == ("program_span"
                               if spec["reader"] == "span_quantile"
                               else "program_counter")
    assert entry["unit"] == ("us" if name.startswith("gateway.relay")
                             else "ms")
    if suffix:  # a twin reads what its base reads
        twin = manifest.load_metric(base)
        assert (spec["reader"], spec["args"]) == (twin["reader"], twin["args"])
    assert read(name, ctx_of(new=True)) == pytest.approx(value, rel=1e-6)
    parent = read(name, ctx_of(new=False))
    if base in PARENT_HAS:
        assert parent == pytest.approx(value, rel=1e-6)
    else:
        assert parent is None


def test_a_sound_run_reads_zero_stall_not_nothing():
    ctx = ctx_of(new=True)
    ctx["prom_after"] = [NEW_AFTER.replace(
        "tpu:loop_stall_seconds_total 1.0", "tpu:loop_stall_seconds_total 0.0")]
    for t in ctx["gateway_traces"]:
        t["spans"][1]["attrs"]["stall_s"] = 0.0
    assert read("server.stalled_ms", ctx) == 0.0
    assert read("gateway.stalled_ms", ctx) == 0.0


def test_pool_sums_stalls_over_replicas_and_means_lag_over_ticks():
    ctx = ctx_of(new=True)
    ctx["prom_before"] *= 4
    ctx["prom_after"] *= 4
    assert read("server.stalled_ms", ctx) == pytest.approx(4000.0)
    assert read("server.loop_lag_ms", ctx) == pytest.approx(0.25)
    assert read("server.write_lag_ms", ctx) == pytest.approx(0.5)
