"""What PR 54 added to the benchmark: the configuration
``lfm2-24b-a2b-d14``, the traffic mix ``assistants``, the cell
``lfm2_d14_assistants``, its two per-layer metrics, the benchmark's own copy
of the plain reference, ``shapes_hybrid`` and the check script
``reference_check_lfm2.py``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers, shapes_hybrid, traffic  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
CELL = "lfm2_d14_assistants"
CONFIG = "lfm2-24b-a2b-d14"
NEW = ["conv.state_rows_mean.batch", "attn.hybrid_decode_hbm_roofline.batch"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_cell_and_its_lists():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "assistants", 1)
    assert CELL in E2E["output_tok_s"]["workloads"]
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, "end_to_end")
            ] == ["output_tok_s", "setup_s"]
    # what the cell has to report (a later ``benchmark`` PR may give it
    # more: this holds no list to what it is today)
    here = {m["name"] for m in MAN["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | {
        "moe.experts_touched_mean.batch", "moe.rows_per_expert_mean.batch",
        "moe.tiles_per_expert_mean.batch", "moe.experts_ops_pct.batch",
        "moe.experts_hbm_roofline.batch", "attn.decode_ops_pct.batch",
        "attn.grid_steps_mean.batch", "kv.usage_peak_pct.batch",
        "device.idle_pct.batch"} <= here
    # no reader of a latent cache or a mixer finds anything in this stack
    assert not any(n.startswith(("mla.", "ssm.")) for n in here)
    model = manifest.load_config(CONFIG)["model"]
    for name in here:
        assert manifest.can_report(manifest.load_metric(name), model), name
    for m in MAN["per_layer"]:  # ... and nothing of an open-loop cell does
        if m["moves"] != "output_tok_s" and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    assert [c["name"] for c in MAN["configs"]].count(CONFIG) == 1
    assert [w["config"] for w in MAN["workloads"]].count(CONFIG) == 1


def test_every_why_and_source_is_short_printable_ascii():
    """PR 49 fell on it: 1 to 200 characters, all printable ASCII (no dash,
    arrow or multiplication sign from outside it), on one line."""
    texts = [(f"{kind} {e['name']} {key}", e[key])
             for kind in ("configs", "workloads") for e in MAN[kind]
             for key in ("why", "source") if key in e]
    assert len(texts) == 2 * len(MAN["configs"]) + len(MAN["workloads"])
    for where, text in texts:
        assert 1 <= len(text) <= 200, (where, len(text))
        assert all(32 <= ord(ch) < 127 for ch in text), where
    for layer in {m["layer"] for m in MAN["per_layer"]}:
        assert 1 <= len(layer) <= 200 and layer.isascii(), layer


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_is_the_cells(name):
    entry = PER_LAYER[name]
    assert entry["workloads"][0] == CELL
    assert entry["moves"] == "output_tok_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in MAN["per_layer"][:40]}
    spec = manifest.load_metric(name)
    assert spec["reader"] in readers.READERS
    if spec["reader"] == "kernel_roofline":
        assert manifest.bytes_fn_problems(spec["args"]["bytes_fn"]) == []
        assert entry["unit"] == "%" and name.endswith("_hbm_roofline.batch")


def test_counter_metric_reads_a_canned_metrics_text():
    before = "tpu:conv_state_rows_total 640\ntpu:dispatch_steps_sum 10\n"
    after = "tpu:conv_state_rows_total 128640\ntpu:dispatch_steps_sum 2010\n"
    ctx = {"prom_before": [before], "prom_after": [after], "window_s": 40.0}
    assert read("conv.state_rows_mean.batch", ctx) == pytest.approx(64.0)
    # the parent has no such counter: nothing, and no error
    parent = {"prom_before": ["tpu:dispatch_steps_sum 10\n"],
              "prom_after": ["tpu:dispatch_steps_sum 20\n"], "window_s": 40.0}
    assert read("conv.state_rows_mean.batch", parent) is None
    assert read("attn.hybrid_decode_hbm_roofline.batch", {}) is None


def test_shapes_hybrid_counts_the_layers_with_lanes_by_hand():
    model = manifest.load_config(CONFIG)["model"]
    assert shapes_hybrid.lane_layers(model) == 3
    assert shapes_hybrid.lane_layers(dict(model, n_layers=40)) == 10
    assert shapes_hybrid.position_bytes(model) == 2 * 8 * 64 * 2 == 2048
    assert shapes_hybrid.row_step_bytes(model) == 2 * 32 * 64 * 2 == 8192
    # 1,000 steps of 64 rows that hold 2,100 positions each
    inputs = {"full": 64 * 2100 * 1000, "steps": 1000, "rows_mean": 64}
    assert shapes_hybrid.window_bytes(model, inputs) == (
        3 * (64 * 2100 * 1000 * 2048 + 64 * 1000 * 8192))
    # one step of one row of one position, in a stack all of lanes
    plain = {"n_layers": 2, "n_heads": 4, "n_kv_heads": 2, "head_dim": 128}
    assert shapes_hybrid.lane_layers(plain) == 2
    assert shapes_hybrid.window_bytes(
        plain, {"full": 1, "steps": 1, "rows_mean": 1}) == (
        2 * (2 * 2 * 128 * 2 + 2 * 4 * 128 * 2))
    # a "nope" layer holds lanes too, a window layer's are another kernel's
    mixed = dict(plain, n_layers=8,
                 layer_pattern=["nope", "window", "conv", "full"])
    assert shapes_hybrid.lane_layers(mixed) == 4


def test_kernel_roofline_sets_the_windows_bytes_against_the_kernels_time():
    """2,000 decode steps in the window, one a program, 250 of the programs
    in the trace: the counters' growth over the window stands against eight
    times the traced time of the kernel."""
    cfg = manifest.load_config(CONFIG)
    steps, rows = 2000, 64
    inputs = {"full": rows * 2100 * steps, "steps": steps, "rows_mean": rows}
    at_roofline_s = shapes_hybrid.window_bytes(cfg["model"], inputs) / 819e9

    def prom(full, n):
        return (f'tpu:kv_positions_read_total{{lanes="full"}} {full}\n'
                'tpu:kv_positions_read_total{lanes="window"} 0\n'
                f"tpu:dispatch_steps_sum {n}\ntpu:dispatch_steps_count {n}\n")

    before, after = prom(7, 1), prom(inputs["full"] + 7, steps + 1)
    trace = {"window_s": 4.0, "op_totals": [
        ["decode_attention.17", 0.25 * at_roofline_s],
        ["chunk_attention.3", 1.0], ["flash_attention.2", 0.5]],
        "modules": {"jit_decode_block": {"count": 250, "total_s": 2.9,
                                         "median_s": 0.0116},
                    "jit_prefill_chunk": {"count": 20, "total_s": 0.8,
                                          "median_s": 0.04}}}
    ctx = {"window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "prom_before": [before], "prom_after": [after], "trace": trace,
           "profile_records": [[{"phase": "decode", "active": rows}]]}
    name = "attn.hybrid_decode_hbm_roofline.batch"
    assert read(name, ctx) == pytest.approx(50.0)
    # nothing to read: no trace, no kernel in it (XLA's fallback), counters
    # that stood still or are not there (the parent's program)
    assert read(name, dict(ctx, trace=None)) is None
    assert read(name, dict(ctx, trace=dict(trace, op_totals=[
        ["chunk_attention.3", 0.2]]))) is None
    assert read(name, dict(ctx, prom_after=[before])) is None
    no_counter = "tpu:dispatch_steps_sum 1\ntpu:dispatch_steps_count 1\n"
    assert read(name, dict(ctx, prom_before=[no_counter],
                           prom_after=[no_counter.replace("1", "9")])) is None


def test_configuration_file_holds_the_catalogs_numbers():
    cfg = manifest.load_config(CONFIG)
    assert cfg["reduced"] == {"n_layers": 14}
    assert cfg["base_preset"] == "lfm2-24b-a2b"
    assert cfg["server_args"] == [
        "--quantize", "int8", "--decode-slots", "64", "--max-seq-len",
        "8192", "--max-loras", "0", "--stream-burst", "4"]
    model = cfg["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_ff"], model["moe_d_ff"], model["vocab_size"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["vocab_size"])
    assert model["head_dim"] * model["n_heads"] == cfg["hidden_size"]
    assert (model["n_experts"], model["n_experts_per_token"],
            model["first_k_dense"], model["norm_topk_prob"],
            model["routed_scaling_factor"], model["conv_kernel"]) == (
        cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["num_dense_layers"], cfg["norm_topk_prob"],
        cfg["routed_scaling_factor"], cfg["conv_L_cache"])
    assert cfg["use_expert_bias"] and model["router_sigmoid"]
    assert not cfg["conv_bias"]
    # the pattern is the source's layer_types, counted from layer 0
    names = {"conv": "conv", "full": "full_attention"}
    period = model["layer_pattern"]
    assert [names[period[l % 4]] for l in range(40)] == cfg["layer_types"]
    assert cfg["num_hidden_layers"] == 40 and model["n_layers"] == 14
    assert cfg["published"] == {k: cfg[k] for k in cfg["published"]}
    for key in ("head_dim", "conv_split", "conv_activation", "conv_state",
                "embeddings", "router_gates", "qk_norm", "position_encoding",
                "attention", "packed_heads", "tokenizer"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["max_seq_len_served"] == 8192
    assert "14 of a 40-layer" in cfg["deployment"]
    assert cfg["rehearsal"]["base_preset"] == "lfm2-tiny"
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg[key] == value, key  # top level: the source as published
        assert cfg["published"][key] == value, key


def test_the_server_would_report_the_files_model_group():
    """``/debug/device`` ``model_config`` is the preset's fields with
    ``reduced`` applied: every key of the file's ``model`` group equals it
    (a tuple goes over the wire as a list)."""
    import dataclasses
    import inspect

    from llm_instance_gateway_tpu.models import mixtral
    from llm_instance_gateway_tpu.server import api_http

    cfg = manifest.load_config(CONFIG)
    preset = dataclasses.replace(mixtral.CONFIGS[cfg["base_preset"]],
                                 **cfg["reduced"])
    served = json.loads(json.dumps(dict(
        dataclasses.asdict(preset), head_dim=preset.resolved_head_dim)))
    src = inspect.getsource(api_http.ModelServer)
    for key, value in cfg["model"].items():
        assert served[key] == value, key
        assert f'"{key}"' in src, key  # ... and the server reports the key
    assert preset.rope_theta == cfg["rope_parameters"]["rope_theta"]
    assert preset.norm_eps == cfg["norm_eps"]
    assert preset.max_seq_len == cfg["max_position_embeddings"]


def test_assistants_mix_is_a_closed_loop_at_the_slots_count():
    mix = manifest.load_traffic("assistants")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 64, 20, 0)
    assert mix["adapters"]["count"] == 0 and mix["stream"] is True
    # the issue's parameters, letter for letter
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.5, "min": 512, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640,
                                    "sigma": 0.4, "min": 256, "max": 1536}
    cfg = manifest.load_config(CONFIG)
    slots = int(cfg["server_args"][cfg["server_args"].index(
        "--decode-slots") + 1])
    assert mix["clients"] == slots
    reqs = traffic.build_requests(mix, 3500000077, 40)
    assert len(reqs) == mix["pool_requests"] == 4096
    assert all(512 <= r.prompt_tokens <= 4096 for r in reqs)
    assert all(256 <= r.max_tokens <= 1536 for r in reqs)
    assert max(r.prompt_tokens + r.max_tokens for r in reqs) <= 5632 < 8192
    # both prefill paths: the 512 and 1,024 buckets and the chunk stream
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert traffic.prefill_shapes(mix, buckets) == [512, 1024, 4096]
    bucketed = sum(r.prompt_tokens <= 1024 for r in reqs) / len(reqs)
    assert 0.15 < bucketed < 0.25  # a fifth: ``insert_prefill`` has work


@pytest.mark.parametrize("seed", [1, 3500000077, 2 ** 31 + 11])
def test_every_seed_offers_the_same_work_in_another_order(seed):
    """A seed turns the pool: the same sizes in another order, and the 250
    requests a ramp and a window complete hold prompt and answer tokens
    within a few percent of any other turn's."""
    mix = manifest.load_traffic("assistants")
    base = traffic.build_requests(mix, 0, 40)
    reqs = traffic.build_requests(mix, seed, 40)
    size = lambda rs: sorted((r.prompt_tokens, r.max_tokens) for r in rs)  # noqa: E731
    assert size(reqs) == size(base)
    assert [r.prompt for r in reqs] != [r.prompt for r in base]
    for field in ("prompt_tokens", "max_tokens"):
        total = lambda rs: sum(getattr(r, field) for r in rs[:250])  # noqa: E731
        assert abs(total(reqs) / total(base) - 1) < 0.05, field


def test_benchmarks_reference_equals_the_programs_on_lfm2_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import lfm2
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import (
        TINY_LFM2_TEST as cfg,
        TINY_OLMOE_TEST,
    )

    with open(lfm2.__file__) as f:  # a copy, not a wrapper
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert not any("llm_instance_gateway_tpu" in ln for ln in imports)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 45).astype(np.int32))
    for quantize in (False, True):
        params = transformer.init_params(
            cfg, jax.random.PRNGKey(2), dtype=jnp.float32, quantize=quantize)
        ours, theirs = [], []
        want = np.asarray(reference.forward(cfg, params, tokens, states=ours))
        got = np.asarray(lfm2.forward(cfg, params, tokens, states=theirs))
        assert np.max(np.abs(got - want)) < 1e-5 * np.max(np.abs(want))
        # every conv layer's last two inputs, in layer order
        assert len(theirs) == cfg.n_layers_of("conv") == 8
        np.testing.assert_allclose(np.stack(theirs)[:, 0], np.stack(ours),
                                   rtol=1e-4, atol=1e-6)
        # the attention a block of queries at a time: the same numbers
        blocked = np.asarray(lfm2.forward(cfg, params, tokens, block=7,
                                          logits_from=40))
        np.testing.assert_allclose(blocked, got[40:], rtol=1e-4, atol=1e-5)
    # the state at an earlier end is the state of the shorter sequence
    at, short = [], []
    lfm2.forward(cfg, params, tokens, states=at, state_ends=(20, 45))
    lfm2.forward(cfg, params, tokens[:20], states=short)
    np.testing.assert_allclose(np.stack(at)[:, 0], np.stack(short)[:, 0],
                               rtol=1e-4, atol=1e-6)
    scale = np.max(np.abs(want))
    low = np.asarray(lfm2.forward(cfg, params, tokens,
                                  round_to=jnp.float8_e4m3fn))
    assert np.max(np.abs(low - want)) > 1e-2 * scale
    for wrong in lfm2.WRONG:  # each is another function
        other = np.asarray(lfm2.forward(cfg, params, tokens, wrong=wrong,
                                        chunk=16))
        assert np.max(np.abs(other - want)) > 1e-3 * scale, wrong
        # ... and a dropped state only from the first edge on
        assert (np.max(np.abs(other[:16] - want[:16])) < 1e-5 * scale) == (
            wrong == "state_dropped")
    with pytest.raises(NotImplementedError):
        lfm2.forward(TINY_OLMOE_TEST, params, tokens)
    with pytest.raises(ValueError):
        lfm2.forward(cfg, params, tokens, wrong="no_norm")


def test_conv_sum_is_three_shifted_copies_by_hand():
    import numpy as np

    from benchmark.reference import lfm2

    w = np.asarray([[2.0], [3.0], [5.0]], np.float32)
    padded = np.asarray([[0.0], [0.0], [1.0], [10.0], [100.0]], np.float32)
    c = np.asarray([[1.0], [1.0], [2.0]], np.float32)
    got = np.asarray(lfm2.conv_sum(w, padded, c))
    # c_t = 2 z_(t-2) + 3 z_(t-1) + 5 z_t, then gated by C
    assert got[:, 0].tolist() == [5.0, 53.0, 2 * 532.0]


def test_reference_check_rehearses_on_the_tiny_preset():
    """The check script end to end on ``lfm2-tiny`` (float32): the system
    within rounding of the reference in both passes, logits and conv state,
    through the bucket and the stream; rows that do not depend on their
    slot; the operator's reading and every wrong function taken and, in the
    pinned pass, placed; exit 10 (a rehearsal is never a result)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "reference_check_lfm2.py"),
         "--rehearse-cpu", "--readings", "--seed", str(2 ** 31 + 5)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 10, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.splitlines()
    rows = [json.loads(ln[5:]) for ln in lines
            if ln.startswith(("PASS ", "FAIL "))]
    ops, rows = rows[:3], rows[3:]
    # the three forms the timed programs call, each with the state it writes
    assert [r["conv_operator"] for r in ops] == ["decode", "stream", "bucket"]
    for op in ops:  # float32 here: the program's sum IS the reference's
        assert op["err_units"] < 0.1 and op["placed"] and op["state_same"]
        assert op["bf16_conv_units"] > op["tol_units"] == 2
    assert [r["routing"] for r in rows] == ["pinned"] * 5 + ["drawn"] * 5
    for row in rows:
        for key in ("err_max", "err_mean", "state_err_prompt",
                    "state_err_end"):
            assert row[key] < 1e-4, (row["sequence"], key)
        assert row["fp8_max"] > 0.2 or row["fp8_mean"] > 0.2
        if row["routing"] == "pinned":
            assert row["placed"], row
    for row in rows[2:4]:  # the sequences that end just past an edge
        assert row["state_dropped_max"] > 0.2
        assert row["no_qk_norm_max"] > 0.2
    assert "rows_independent" in lines[-2]
    assert json.loads(lines[-2])["rows_independent"] is True
