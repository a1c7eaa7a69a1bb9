"""``model.copy_ops_pct`` (PR 25): the manifest loads it, and its pattern
picks the device operations that only move data — by the names PR 24's
traces gave them — and nothing that computes."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402

MAN = manifest.load_manifest()
NAMES = ["model.copy_ops_pct", "model.copy_ops_pct.batch"]

# Device seconds of a traced 4 s window of qwen7b_chat before PR 25
# (PERF_LEDGER.jsonl, PR 24, `breakdown.device_ops`; PERF.md §5).
MOVES = [["copy.200", 0.257], ["copy.201", 0.257],
         ["bitcast_dynamic-update-slice_fusion.4", 0.264],
         ["bitcast_dynamic-update-slice_fusion.5", 0.133],
         ["dynamic-slice_bitcast_fusion.4.remat", 0.119],
         ["dynamic-slice_bitcast_fusion.5", 0.118],
         ["reshape.308.remat", 0.283], ["reshape.310.remat", 0.142]]
COMPUTES = [["while.15", 1.926], ["sort.8", 0.336],
            ["decode_attention.13", 0.136], ["add_multiply_fusion.2", 0.12],
            ["fusion.316", 0.12], ["copy-start.3", 0.01],
            ["conditional.14", 0.3]]


@pytest.mark.parametrize("name", NAMES)
def test_manifest_loads_the_metric(name):
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    assert entry["layer"] == "model step" and entry["unit"] == "%"
    assert entry["better"] == "lower" and entry["source"] == "device_trace"
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert set(entry["workloads"]) <= set(e2e[entry["moves"]]["workloads"])
    spec = manifest.load_metric(name)
    assert spec["reader"] == "trace_op_time"
    assert spec["args"]["per"] == "window"
    assert manifest.problems(MAN) == []


@pytest.mark.parametrize("name", NAMES)
def test_pattern_counts_what_moves_data_and_nothing_that_computes(name):
    spec = manifest.load_metric(name)
    ctx = {"trace": {"window_s": 4.0, "op_totals": MOVES + COMPUTES}}
    got = readers.READERS[spec["reader"]](spec["args"], ctx)
    assert got == pytest.approx(100.0 * sum(s for _, s in MOVES) / 4.0)
    assert 39.0 < got < 40.0  # the parent's share in chat


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("ctx", [
    {}, {"trace": None}, {"trace": {"window_s": 4.0}},
    {"trace": {"window_s": 4.0, "op_totals": COMPUTES}}],
    ids=["untraced", "no-trace", "no-op-totals", "nothing-moves"])
def test_nothing_to_read_leaves_the_metric_out(name, ctx):
    spec = manifest.load_metric(name)
    assert readers.READERS[spec["reader"]](spec["args"], ctx) is None
