"""What PR 45 added to the benchmark: the configuration
``smallthinker-21b-a3b-d12``, the traffic mix ``longdocs``, the cell
``smallthinker_d12_longdocs``, its four per-layer metrics, the benchmark's own
copy of the plain reference, ``shapes_attn`` and the check script
``reference_check_smallthinker.py``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers, shapes_attn, traffic  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
CELL = "smallthinker_d12_longdocs"
CONFIG = "smallthinker-21b-a3b-d12"
NEW = ["kv.full_positions_mean.batch", "kv.window_positions_mean.batch",
       "attn.window_decode_ops_pct.batch", "attn.decode_hbm_roofline.batch"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_cell_and_its_lists():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdocs", 1)
    assert len(cell["why"]) <= 200
    assert CELL in E2E["output_tok_s"]["workloads"]
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, "end_to_end")
            ] == ["output_tok_s", "setup_s"]
    # every ``.batch`` twin mixtral's cell reads, reads here, but the whole
    # step's roofline: shapes.py counts one full lane a layer and would
    # read over 100%
    listed = {m["name"] for m in MAN["per_layer"]
              if "mixtral_d6_batch" in m.get("workloads", ())}
    here = {m["name"] for m in MAN["per_layer"]
            if CELL in m.get("workloads", ())}
    assert listed - here == {"model.decode_step_hbm_roofline.batch"}
    assert here - listed == set(NEW)
    assert {"moe.experts_hbm_roofline.batch", "moe.experts_ops_pct.batch",
            "moe.tiles_per_expert_mean.batch", "attn.decode_ops_pct.batch",
            "kv.usage_peak_pct.batch", "device.idle_pct.batch"} <= here
    assert not any(n.startswith(("mla.", "ssm.")) for n in here)
    model = manifest.load_config(CONFIG)["model"]
    for name in here:
        assert manifest.can_report(manifest.load_metric(name), model), name
    for m in MAN["per_layer"]:  # ... and nothing of an open-loop cell does
        if m["moves"] != "output_tok_s" and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    # one configuration, one cell on one chip, four metrics
    assert [c["name"] for c in MAN["configs"]].count(CONFIG) == 1
    assert [w["config"] for w in MAN["workloads"]].count(CONFIG) == 1
    assert set(NEW) <= set(PER_LAYER)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_is_the_cells_alone(name):
    entry = PER_LAYER[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "output_tok_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in MAN["per_layer"][:40]}
    spec = manifest.load_metric(name)
    assert spec["reader"] in readers.READERS
    if spec["reader"] == "kernel_roofline":
        assert manifest.bytes_fn_problems(spec["args"]["bytes_fn"]) == []
        assert entry["unit"] == "%" and name.endswith("_hbm_roofline.batch")


def test_counter_metrics_read_a_canned_metrics_text_by_label():
    before = ('tpu:kv_positions_read_total{lanes="full"} 100\n'
              'tpu:kv_positions_read_total{lanes="window"} 50\n'
              "tpu:dispatch_steps_sum 10\n")
    after = ('tpu:kv_positions_read_total{lanes="full"} 400100\n'
             'tpu:kv_positions_read_total{lanes="window"} 262194\n'
             "tpu:dispatch_steps_sum 2010\n")
    ctx = {"prom_before": [before], "prom_after": [after], "window_s": 40.0}
    assert read("kv.full_positions_mean.batch", ctx) == pytest.approx(200.0)
    # 32 rows past the window: 32 x 4,096 a step, and no more
    assert read("kv.window_positions_mean.batch", ctx) == pytest.approx(
        32 * 4096 / 1000)
    # the parent has no such counter: nothing, and no error
    parent = {"prom_before": ["tpu:dispatch_steps_sum 10\n"],
              "prom_after": ["tpu:dispatch_steps_sum 20\n"], "window_s": 40.0}
    for name in NEW[:2]:
        assert read(name, parent) is None


def test_kernel_share_tells_the_two_kinds_of_lane_apart():
    trace = {"window_s": 4.0, "op_totals": [
        ["decode_attention_window.7", 1.5], ["decode_attention.9", 0.5],
        ["chunk_attention.3", 0.4], ["fusion.1", 1.0]]}
    assert read("attn.window_decode_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(37.5))
    # the accepted metric's regex finds both kinds of lane
    assert read("attn.decode_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(50.0))
    parent = {"trace": {"window_s": 4.0, "op_totals": [
        ["decode_attention.9", 2.0]]}}
    assert read("attn.window_decode_ops_pct.batch", parent) is None
    for name in NEW[2:]:
        assert read(name, {}) is None


def test_shapes_attn_counts_each_kind_of_lane_by_hand():
    model = manifest.load_config(CONFIG)["model"]
    assert shapes_attn.layers_by_kind(model) == (3, 9)
    assert shapes_attn.layers_by_kind({"n_layers": 28}) == (28, 0)
    assert shapes_attn.position_bytes(model) == 2048  # K and V, 4 x 128, bf16
    assert shapes_attn.row_step_bytes(model) == 2 * 28 * 128 * 2
    # one decode step of 32 rows at 6,600 positions each: 3 full layers read
    # them all, 9 window layers 4,096 of each
    inputs = {"full": 32 * 6600, "window": 32 * 4096, "steps": 1,
              "rows_mean": 32}
    by_hand = ((3 * 32 * 6600 + 9 * 32 * 4096) * 2048
               + 12 * 32 * 2 * 28 * 128 * 2)
    assert shapes_attn.window_bytes(model, inputs) == by_hand
    assert by_hand / 1e9 == pytest.approx(3.72, abs=0.01)  # the issue's 3.7 GB
    # all-full lanes would read 5.2 GB of cache
    assert 12 * 32 * 6600 * 2048 / 1e9 == pytest.approx(5.19, abs=0.01)
    # linear in its counters: a window's totals give a window's bytes
    many = {k: v * (1000 if k != "rows_mean" else 1) for k, v in inputs.items()}
    assert shapes_attn.window_bytes(model, many) == 1000 * by_hand
    # a model of one kind: every layer a full lane, the window's counter 0
    one = dict(model, layer_pattern=[])
    assert shapes_attn.window_bytes(
        one, dict(inputs, window=0)) == (
            12 * 32 * 6600 * 2048 + 12 * 32 * 2 * 28 * 128 * 2)


def test_kernel_roofline_sets_the_windows_bytes_against_both_kernels_time():
    """2,000 decode steps in the window, one a program, 250 of the programs
    in the trace: the counters' growth over the window stands against eight
    times the traced time of BOTH kinds of lane."""
    cfg = manifest.load_config(CONFIG)
    steps, rows = 2000, 32
    inputs = {"full": rows * 6600 * steps, "window": rows * 4096 * steps,
              "steps": steps, "rows_mean": rows}
    at_roofline_s = shapes_attn.window_bytes(cfg["model"], inputs) / 819e9

    def prom(full, window, n):
        return (f'tpu:kv_positions_read_total{{lanes="full"}} {full}\n'
                f'tpu:kv_positions_read_total{{lanes="window"}} {window}\n'
                f"tpu:dispatch_steps_sum {n}\ntpu:dispatch_steps_count {n}\n")

    before = prom(7, 5, 1)
    after = prom(inputs["full"] + 7, inputs["window"] + 5, steps + 1)
    trace = {"window_s": 4.0, "op_totals": [
        ["decode_attention_window.16", 0.15 * at_roofline_s],
        ["decode_attention.17", 0.10 * at_roofline_s],
        ["chunk_attention.3", 1.0]],
        "modules": {"jit_decode_block": {"count": 250, "total_s": 3.9,
                                         "median_s": 0.0156},
                    "jit_prefill_chunk": {"count": 9, "total_s": 1.3,
                                          "median_s": 0.14}}}
    ctx = {"window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "prom_before": [before], "prom_after": [after], "trace": trace,
           "profile_records": [[{"phase": "decode", "active": rows}]]}
    name = "attn.decode_hbm_roofline.batch"
    assert read(name, ctx) == pytest.approx(50.0)
    # nothing to read: no trace, no kernel in it, counters that stood still
    # or are not there (the parent's program)
    assert read(name, dict(ctx, trace=None)) is None
    assert read(name, dict(ctx, trace=dict(trace, op_totals=[
        ["chunk_attention.3", 0.2]]))) is None
    assert read(name, dict(ctx, prom_after=[before])) is None
    no_counter = "tpu:dispatch_steps_sum 1\ntpu:dispatch_steps_count 1\n"
    assert read(name, dict(ctx, prom_before=[no_counter],
                           prom_after=[no_counter.replace("1", "9")])) is None


def test_configuration_file_holds_the_catalogs_numbers():
    cfg = manifest.load_config(CONFIG)
    assert cfg["reduced"] == {"n_layers": 12}
    assert cfg["base_preset"] == "smallthinker-21b-a3b"
    assert cfg["server_args"] == [
        "--quantize", "int8", "--decode-slots", "32", "--max-seq-len",
        "16384", "--max-loras", "0"]
    model = cfg["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["d_ff"], model["vocab_size"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_ffn_hidden_size"], cfg["vocab_size"])
    assert (model["n_experts"], model["n_experts_per_token"],
            model["sliding_window"], model["norm_topk_prob"]) == (
        cfg["moe_num_primary_experts"], cfg["moe_num_active_primary_experts"],
        cfg["sliding_window_size"], cfg["norm_topk_prob"])
    assert model["moe_d_ff"] == 0  # an expert's width is d_ff, as olmoe's
    period = model["layer_pattern"]
    assert [int(k == "window") for k in period] * 13 == (
        cfg["sliding_window_layout"])
    assert [int(k != "nope") for k in period] * 13 == cfg["rope_layout"]
    assert cfg["num_hidden_layers"] == 52 and model["n_layers"] == 12
    # ``published``: the source's keys as they are and, beside them, the
    # two restatements the harness reads, each named under ``assumed``
    restated = {"num_experts": 64, "intermediate_size": 768}
    assert cfg["published"] == {
        **{k: cfg[k] for k in cfg["published"] if k not in restated},
        **restated}
    for key in restated:
        assert key not in cfg and "RESTATEMENT" in cfg["assumed"][key]
    for key in ("router_input", "router_gates", "window",
                "position_encoding", "attention", "activation",
                "secondary_experts", "ring", "tokenizer"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["max_seq_len_served"] == 16384
    assert "12 of a 52-layer" in cfg["deployment"]
    assert cfg["rehearsal"]["base_preset"] == "smallthinker-tiny"
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "SmallThinker-21BA3B-Instruct")
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg[key] == value, key  # top level: the source as published
        assert cfg["published"][key] == value, key


def test_the_server_would_report_the_files_model_group():
    """``/debug/device`` ``model_config`` is the preset's fields with
    ``reduced`` applied: every key of the file's ``model`` group equals it
    (a tuple goes over the wire as a list)."""
    import dataclasses

    from llm_instance_gateway_tpu.models import mixtral

    cfg = manifest.load_config(CONFIG)
    preset = dataclasses.replace(mixtral.CONFIGS[cfg["base_preset"]],
                                 **cfg["reduced"])
    served = json.loads(json.dumps(dict(
        dataclasses.asdict(preset), head_dim=preset.resolved_head_dim)))
    for key, value in cfg["model"].items():
        assert served[key] == value, key
    assert preset.rope_theta == cfg["rope_theta"]
    assert preset.norm_eps == cfg["rms_norm_eps"]
    assert preset.max_seq_len == cfg["max_position_embeddings"]
    assert not preset.tie_embeddings and not cfg["tie_word_embeddings"]


def test_longdocs_mix_is_a_closed_loop_at_the_slots_count():
    mix = manifest.load_traffic("longdocs")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 32, 30, 0)
    assert mix["adapters"]["count"] == 0 and mix["stream"] is True
    # the issue's medians and limits, with the narrower sigmas it allows once
    # six seeds spread by over 2.3% (the driver's check read 4%: PERF.md §6)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.1, "min": 4096, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.15, "min": 384, "max": 1536}
    cfg = manifest.load_config(CONFIG)
    slots = int(cfg["server_args"][cfg["server_args"].index(
        "--decode-slots") + 1])
    assert mix["clients"] == slots
    reqs = traffic.build_requests(mix, 3500000077, 40)
    assert len(reqs) == mix["pool_requests"] == 2048
    assert all(4096 <= r.prompt_tokens <= 8192 for r in reqs)
    assert all(384 <= r.max_tokens <= 1536 for r in reqs)
    assert max(r.prompt_tokens + r.max_tokens for r in reqs) <= 9728 < 16384
    # every prompt is over the window and over the largest bucket: the
    # windowed chunk stream is the only prefill program on the path
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert traffic.prefill_shapes(mix, buckets) == [8192]
    assert min(r.prompt_tokens for r in reqs) >= cfg["model"]["sliding_window"]


@pytest.mark.parametrize("seed", [1, 3500000077, 2 ** 31 + 11])
def test_a_turn_of_the_pool_hardly_moves_a_windows_work(seed):
    """A seed turns the pool: the same sizes in another order, and the 130
    requests a ramp and a window complete hold prompt and answer tokens
    within a few percent of any other turn's (PR 42's cell, of few very long
    requests a window, moved by tens of percent)."""
    mix = manifest.load_traffic("longdocs")
    base = traffic.build_requests(mix, 0, 40)
    reqs = traffic.build_requests(mix, seed, 40)
    size = lambda rs: sorted((r.prompt_tokens, r.max_tokens) for r in rs)  # noqa: E731
    assert size(reqs) == size(base)
    for field in ("prompt_tokens", "max_tokens"):
        total = lambda rs: sum(getattr(r, field) for r in rs[:130])  # noqa: E731
        assert abs(total(reqs) / total(base) - 1) < 0.04, field


def test_benchmarks_reference_equals_the_programs_on_smallthinker_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import smallthinker
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import (
        TINY_OLMOE_TEST,
        TINY_SMALLTHINKER_TEST as cfg,
    )

    with open(smallthinker.__file__) as f:  # a copy, not a wrapper
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert not any("llm_instance_gateway_tpu" in ln for ln in imports)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 45).astype(np.int32))
    for quantize in (False, True):
        params = transformer.init_params(
            cfg, jax.random.PRNGKey(2), dtype=jnp.float32, quantize=quantize)
        want = np.asarray(reference.forward(cfg, params, tokens))
        got = np.asarray(smallthinker.forward(cfg, params, tokens))
        assert np.max(np.abs(got - want)) < 1e-5 * np.max(np.abs(want))
        # the attention a block of queries at a time: the same numbers
        blocked = np.asarray(smallthinker.forward(cfg, params, tokens,
                                                  block=7, logits_from=40))
        np.testing.assert_allclose(blocked, got[40:], rtol=1e-4, atol=1e-5)
    scale = np.max(np.abs(want))
    low = np.asarray(smallthinker.forward(cfg, params, tokens,
                                          round_to=jnp.float8_e4m3fn))
    assert np.max(np.abs(low - want)) > 1e-2 * scale
    for wrong in smallthinker.WRONG:  # each is another function
        other = np.asarray(smallthinker.forward(cfg, params, tokens,
                                                wrong=wrong))
        assert np.max(np.abs(other - want)) > 1e-2 * scale, wrong
        # ... and the window only past the window
        assert (np.max(np.abs(other[:cfg.sliding_window] - want[:16]))
                < 1e-5 * scale) == (wrong == "no_window")
    with pytest.raises(NotImplementedError):
        smallthinker.forward(TINY_OLMOE_TEST, params, tokens)
    with pytest.raises(ValueError):
        smallthinker.forward(cfg, params, tokens, wrong="no_norm")


def test_reference_check_rehearses_on_the_tiny_preset():
    """The check script end to end on ``smallthinker-tiny`` (float32): the
    system within rounding of the reference in both passes, rows that do not
    depend on their slot, every reading taken and placed, exit 10 (a
    rehearsal is never a result)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "reference_check_smallthinker.py"),
         "--rehearse-cpu", "--readings", "--seed", str(2 ** 31 + 5)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 10, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.splitlines()
    rows = [json.loads(ln[5:]) for ln in lines
            if ln.startswith(("PASS ", "FAIL "))]
    assert [r["routing"] for r in rows] == ["pinned"] * 4 + ["drawn"] * 4
    assert json.loads(lines[-1])["ok"]
    for row in rows:
        assert row["err_max"] < 1e-5 and row["argmax_agree"] == 1.0
    for row in rows:  # every reading placed; the pinned pass's by number
        assert row["placed"]
    for row in rows[:4]:
        assert row["bf16_max"] < 0.05 < row["fp8_max"]
    for wrong in ("no_window", "rope_on_full", "router_after_norm"):
        assert rows[3][f"{wrong}_max"] > 0.05
