"""What PR 57 added to the benchmark: six per-layer metrics of the prompt
programs, all data files over the reader that was there (``prom_delta``) and
three counter families of the program (``tpu:prompt_programs_total``,
``tpu:prompt_positions_total``, ``tpu:prompt_program_seconds_total``).  Each
reads its number from a canned ``/metrics`` pair, worked out by hand below, and
nothing, without an error, from what the parent gives (no such family)."""

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
CLOSED5 = ["mixtral_d6_batch", "glm47flash_d13_agents",
           "falconh1_d8_reasoners", "smallthinker_d12_longdocs",
           "lfm2_d14_assistants"]
# smallthinker_d12_longdocs streams every prompt and is not listed:
# test_bench_window.py holds that cell's lists to mixtral's plus its own four.
STREAMING = ["glm47flash_d13_agents", "lfm2_d14_assistants"]

# Over the canned window of 40 s: 60 chunk programs and 140 bucket prefills
# went out; the chunks held 7.5 s of the device's queue and the prefills
# 2.5 s; of 61,440 + 35,840 positions computed, 9,440 + 10,016 were padding.
WINDOW_S = 40.0
CHUNK_MS = 1000.0 * 7.5 / 60  # 125
PROGRAMS_PCT = 100.0 * (7.5 + 2.5) / WINDOW_S  # 25
PAD_PCT = 100.0 * (9440 + 10016) / (61440 + 35840)  # 20


def prom(chunks, prefills, chunk_s, prefill_s, chunk_real, chunk_pad,
         prefill_real, prefill_pad) -> str:
    return (
        "# TYPE tpu:prompt_programs_total counter\n"
        f'tpu:prompt_programs_total{{program="prefill"}} {prefills}\n'
        'tpu:prompt_programs_total{program="prefill_many"} 0\n'
        f'tpu:prompt_programs_total{{program="chunk"}} {chunks}\n'
        'tpu:prompt_programs_total{program="ring"} 0\n'
        f'tpu:prompt_positions_total{{program="prefill",kind="real"}} '
        f'{prefill_real}\n'
        f'tpu:prompt_positions_total{{program="prefill",kind="pad"}} '
        f'{prefill_pad}\n'
        f'tpu:prompt_positions_total{{program="chunk",kind="real"}} '
        f'{chunk_real}\n'
        f'tpu:prompt_positions_total{{program="chunk",kind="pad"}} '
        f'{chunk_pad}\n'
        f'tpu:prompt_program_seconds_total{{program="prefill"}} '
        f'{prefill_s:.6f}\n'
        f'tpu:prompt_program_seconds_total{{program="chunk"}} '
        f'{chunk_s:.6f}\n'
        'tpu:prompt_program_seconds_total{program="ring"} 0.000000\n')


OLD = 'tpu:dispatch_steps_sum 1000\ntpu:prefill_padding_tokens_total 77\n'
BEFORE = OLD + prom(10, 40, 1.25, 0.5, 9000, 1240, 8000, 2240)
AFTER = OLD + prom(70, 180, 8.75, 3.0, 9000 + 52000, 1240 + 9440,
                   8000 + 25824, 2240 + 10016)


def ctx_of(new: bool, replicas: int = 1) -> dict:
    return {"window_s": WINDOW_S,
            "prom_before": [BEFORE if new else OLD] * replicas,
            "prom_after": [AFTER if new else OLD] * replicas}


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


CASES = [
    ("model.chunk_program_ms", "", "tpot_p50_ms", ["qwen7b_doc"], "ms",
     CHUNK_MS),
    ("model.chunk_program_ms", ".batch", "output_tok_s", STREAMING, "ms",
     CHUNK_MS),
    ("model.prompt_programs_pct", "", "tpot_p50_ms", OPEN3, "%",
     PROGRAMS_PCT),
    ("model.prompt_programs_pct", ".batch", "output_tok_s", CLOSED5, "%",
     PROGRAMS_PCT),
    ("model.prompt_pad_pct", "", "tpot_p50_ms", OPEN3, "%", PAD_PCT),
    ("model.prompt_pad_pct", ".batch", "output_tok_s", CLOSED5, "%", PAD_PCT),
]


def test_the_manifest_holds_the_six_after_those_that_were_there():
    assert manifest.problems(MAN) == []
    names = [m["name"] for m in MAN["per_layer"]]
    new = [base + suffix for base, suffix, *_ in CASES]
    first = names.index(new[0])
    assert first == names.index("attn.hybrid_decode_hbm_roofline.batch") + 1
    assert names[first:first + len(new)] == new
    for name in new:
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".json"))


@pytest.mark.parametrize("base,suffix,moves,cells,unit,value", CASES,
                         ids=[b + s for b, s, *_ in CASES])
def test_entry_and_its_reading(base, suffix, moves, cells, unit, value):
    name = base + suffix
    entry = PER_LAYER[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["layer"], entry["moves"], entry["better"], entry["unit"],
            entry["source"]) == ("model step", moves, "lower", unit,
                                 "program_counter")
    # the cells it came with, and whichever join later: each reports what
    # the metric moves
    assert set(cells) <= set(entry["workloads"]) <= set(
        E2E[moves]["workloads"])
    spec = manifest.load_metric(name)
    assert spec["reader"] == "prom_delta"
    if suffix:  # a twin reads what its base reads
        twin = manifest.load_metric(base)
        assert (spec["reader"], spec["args"]) == (twin["reader"], twin["args"])
    assert read(name, ctx_of(new=True)) == pytest.approx(value, rel=1e-9)
    # the parent's exposition has no such family: nothing, and no error
    assert read(name, ctx_of(new=False)) is None


def test_a_cell_without_chunks_reads_no_chunk_time_and_zero_is_a_number():
    """chat streams nothing: 0 chunk programs over 0 is nothing to report,
    while a share of the window or of the positions that is 0 is 0."""
    quiet = prom(0, 0, 0.0, 0.0, 0, 0, 0, 0)
    ctx = {"window_s": WINDOW_S, "prom_before": [quiet],
           "prom_after": [quiet]}
    assert read("model.chunk_program_ms", ctx) is None
    assert read("model.prompt_programs_pct", ctx) == 0.0
    assert read("model.prompt_pad_pct", ctx) is None  # no position computed


def test_replicas_are_pooled():
    """A share of the window is the replicas' mean; a ratio of two counters
    pools them."""
    ctx = ctx_of(new=True, replicas=4)
    assert read("model.prompt_programs_pct", ctx) == pytest.approx(
        PROGRAMS_PCT)
    assert read("model.chunk_program_ms", ctx) == pytest.approx(CHUNK_MS)
    assert read("model.prompt_pad_pct", ctx) == pytest.approx(PAD_PCT)


def test_the_padding_family_of_the_operator_is_the_sum_of_the_pads():
    """What the engine renders: ``tpu:prefill_padding_tokens_total`` and the
    sum of ``kind="pad"`` are fed by one call (tests/test_prompt_programs.py
    holds the engine to it); here, that a reader sums the label set."""
    pads = readers.prom_value(
        AFTER, "tpu:prompt_positions_total", {"kind": "pad"})
    assert pads == 1240 + 9440 + 2240 + 10016
