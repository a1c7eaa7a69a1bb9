"""What PR 60 added to the benchmark: the configuration
``ling-3.0-flash-d12`` (one chip's share of a sixteen-chip deployment), the
traffic mix ``analysts``, the cell ``ling3flash_d12_analysts``, its five
per-layer metrics, the benchmark's own copy of the plain reference,
``shapes_kda`` and the check script ``reference_check_ling.py``."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers, shapes_kda, shapes_mla, traffic  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
CELL = "ling3flash_d12_analysts"
CONFIG = "ling-3.0-flash-d12"
NEW = ["kda.state_rows_mean.batch", "kda.decode_update_ops_pct.batch",
       "kda.decode_update_hbm_roofline.batch",
       "moe.assignments_held_pct.batch",
       "mla.hybrid_decode_attn_hbm_roofline.batch"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_cell_and_its_lists():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "analysts", 1)
    assert len(cell["why"]) <= 200
    assert CELL in E2E["output_tok_s"]["workloads"]
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, "end_to_end")
            ] == ["output_tok_s", "setup_s"]
    here = {m["name"] for m in MAN["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | {
        "moe.experts_touched_mean.batch", "moe.rows_per_expert_mean.batch",
        "moe.tiles_per_expert_mean.batch", "moe.experts_ops_pct.batch",
        "mla.decode_attn_ops_pct.batch", "mla.ctx_positions_mean.batch",
        "attn.grid_steps_mean.batch", "kv.usage_peak_pct.batch",
        "model.chunk_program_ms.batch", "model.decode_device_ms.batch",
        "device.idle_pct.batch"} <= here
    # every layer is not latent here, no lane is per head, no mixer
    assert "mla.decode_attn_hbm_roofline.batch" not in here
    assert "model.decode_step_hbm_roofline.batch" not in here
    assert not any(n.startswith(("attn.decode", "attn.window", "ssm.",
                                 "conv.")) for n in here)
    model = manifest.load_config(CONFIG)["model"]
    for name in here:
        assert manifest.can_report(manifest.load_metric(name), model), name
    for m in MAN["per_layer"]:  # ... and nothing of an open-loop cell does
        if m["moves"] != "output_tok_s" and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    assert [c["name"] for c in MAN["configs"]].count(CONFIG) == 1
    assert [w["config"] for w in MAN["workloads"]].count(CONFIG) == 1
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == ["n_experts_local", "n_layers",
                                        "vocab_size"]
    for key in ("why", "source"):
        assert 1 <= len(entry[key]) <= 200
        assert all(32 <= ord(ch) < 127 for ch in entry[key])


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


def test_counter_metrics_read_a_canned_metrics_text():
    before = ("tpu:kda_state_rows_total 640\ntpu:dispatch_steps_sum 10\n"
              "tpu:moe_assignments_total 100\n"
              "tpu:moe_assignments_routed_total 400\n")
    after = ("tpu:kda_state_rows_total 128640\ntpu:dispatch_steps_sum 2010\n"
             "tpu:moe_assignments_total 260100\n"
             "tpu:moe_assignments_routed_total 1000400\n")
    ctx = {"prom_before": [before], "prom_after": [after], "window_s": 40.0}
    assert read("kda.state_rows_mean.batch", ctx) == pytest.approx(64.0)
    assert read("moe.assignments_held_pct.batch", ctx) == pytest.approx(26.0)
    # the parent has no such counters: nothing, and no error
    parent = {"prom_before": ["tpu:dispatch_steps_sum 10\n"
                              "tpu:moe_assignments_total 100\n"],
              "prom_after": ["tpu:dispatch_steps_sum 20\n"
                             "tpu:moe_assignments_total 900\n"],
              "window_s": 40.0}
    for name in NEW:
        assert read(name, parent) is None, name
    assert read("kda.decode_update_hbm_roofline.batch", {}) is None


def test_shapes_kda_counts_by_hand():
    model = manifest.load_config(CONFIG)["model"]
    assert shapes_kda.layers_of(model, "kda") == 10
    assert shapes_kda.layers_of(model, "mla") == 2
    assert shapes_kda.layers_of(dict(model, n_layers=42), "kda") == 35
    assert shapes_kda.layers_of(dict(model, n_layers=42), "mla") == 7
    # a row: the state both ways, q, k, g, v in and o out, beta
    assert shapes_kda.row_bytes(model) == (
        2 * 32 * 128 * 128 * 4 + (5 * 32 * 128 + 32) * 4) == 4_276_352
    # 1,000 steps of 64 rows
    assert shapes_kda.window_bytes(model, {"rows": 64_000}) == (
        10 * 64_000 * 4_276_352)
    # the latent kernel: shapes_mla's layer-step, over 2 layers and not 12
    inputs = {"positions": 64 * 2100 * 1000, "steps": 1000, "rows_mean": 64}
    one = shapes_mla.layer_step_bytes(model, inputs["positions"], 64 * 1000)
    assert one == (64 * 2100 * 1000 * 576 * 2
                   + 64 * 1000 * 32 * 2 * (576 + 512))
    assert shapes_kda.latent_window_bytes(model, inputs) == 2 * one
    assert shapes_mla.window_bytes(model, inputs) == 12 * one  # six times it
    # one head of 128, one row, one step, a stack all of KDA layers
    plain = {"n_layers": 3, "layer_pattern": ["kda"], "kda_n_heads": 1,
             "kda_head_dim": 128}
    assert shapes_kda.window_bytes(plain, {"rows": 1}) == 3 * (
        2 * 128 * 128 * 4 + (5 * 128 + 1) * 4)


def test_kernel_roofline_sets_the_windows_bytes_against_the_kernels_time():
    """2,000 decode steps in the window, one a program, 250 of the programs
    in the trace: the counters' growth over the window stands against eight
    times the traced time of each kernel."""
    cfg = manifest.load_config(CONFIG)
    steps, rows = 2000, 64
    kda_s = shapes_kda.window_bytes(cfg["model"], {"rows": rows * steps}) / 819e9
    inputs = {"positions": rows * 2100 * steps, "steps": steps,
              "rows_mean": rows}
    mla_s = shapes_kda.latent_window_bytes(cfg["model"], inputs) / 819e9

    def prom(n, k):
        return (f"tpu:kda_state_rows_total {rows * n + 3}\n"
                f"tpu:latent_kv_positions_total {rows * 2100 * n + 5}\n"
                f"tpu:dispatch_steps_sum {n + k}\n"
                f"tpu:dispatch_steps_count {n + k}\n")

    trace = {"window_s": 4.0, "op_totals": [
        ["kda_decode_update.7", 0.25 * kda_s],
        ["mla_decode_attention.3", 0.2 * mla_s],
        ["moe_gmm_int8.2", 1.0]],
        "modules": {"jit_decode_block": {"count": 250, "total_s": 3.2,
                                         "median_s": 0.0128},
                    "jit_prefill_chunk": {"count": 10, "total_s": 0.6,
                                          "median_s": 0.06}}}
    ctx = {"window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "prom_before": [prom(0, 1)], "prom_after": [prom(steps, 1)],
           "trace": trace,
           "profile_records": [[{"phase": "decode", "active": rows}]]}
    assert read("kda.decode_update_hbm_roofline.batch", ctx
                ) == pytest.approx(50.0)
    assert read("mla.hybrid_decode_attn_hbm_roofline.batch", ctx
                ) == pytest.approx(62.5)
    # nothing to read: no trace, no kernel in it (XLA's fallback)
    for name in ("kda.decode_update_hbm_roofline.batch",
                 "kda.decode_update_ops_pct.batch"):
        assert read(name, dict(ctx, trace=None)) is None
        assert read(name, dict(ctx, trace=dict(trace, op_totals=[
            ["moe_gmm_int8.2", 0.2]]))) is None


def test_configuration_file_holds_the_catalogs_numbers():
    cfg = manifest.load_config(CONFIG)
    assert manifest.config_problems(cfg) == []
    assert cfg["reduced"] == {"n_layers": 12, "n_experts_local": 128,
                              "vocab_size": 39296}
    assert cfg["base_preset"] == "ling-3.0-flash"
    assert cfg["server_args"] == [
        "--quantize", "int8", "--decode-slots", "64", "--max-seq-len",
        "8192", "--max-loras", "0", "--stream-burst", "4"]
    model = cfg["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_ff"], model["moe_d_ff"], model["head_dim"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["head_dim"])
    assert (model["n_experts"], model["n_experts_per_token"],
            model["first_k_dense"], model["norm_topk_prob"],
            model["routed_scaling_factor"], model["n_group"],
            model["topk_group"], model["n_shared_experts"]) == (
        cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["first_k_dense_replace"], cfg["norm_topk_prob"],
        cfg["routed_scaling_factor"], cfg["n_group"], cfg["topk_group"],
        cfg["num_shared_experts"])
    assert (model["kv_lora_rank"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"],
            model["kda_conv"], model["kda_lower_bound"]) == (
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["short_conv_kernel_size"],
        cfg["kda_lower_bound"])
    assert cfg["q_lora_rank"] is None and model["q_lora_rank"] == 0
    assert (model["kda_n_heads"], model["kda_head_dim"]) == (32, 128)
    assert model["qk_nope_head_dim"] + model["qk_rope_head_dim"] == cfg[
        "qk_head_dim"]
    # the share: the router's width stays, a quarter is held, 4 x the slice
    # is the vocabulary
    assert model["n_experts"] == 512 and model["n_experts_local"] * 4 == 512
    assert model["vocab_size"] * 4 == cfg["vocab_size"] == 157184
    assert model["vocab_size"] % 128 == 0
    assert "4 chips share each layer" in cfg["deployment"]
    assert "quarter" in cfg["reduced_note"].lower()
    # a latent layer closes every period of layer_group_size
    period = model["layer_pattern"]
    assert len(period) == cfg["layer_group_size"] == 6
    assert [period[l % 6] for l in range(42)] == [
        "mla" if (l + 1) % 6 == 0 else "kda" for l in range(42)]
    assert cfg["num_hidden_layers"] == 42 and model["n_layers"] == 12
    # the 12 layers served have no SwiGLU clamp
    assert not any(cfg["expert_swiglu_limit_list"][:12])
    assert not any(cfg["share_expert_swiglu_limit_list"][:12])
    assert cfg["published"] == {k: cfg[k] for k in cfg["published"]}
    for key in ("kda_gate", "no_kda_lora", "kda_equations", "head_wise_gate",
                "use_qk_norm", "mla", "rope", "router", "share",
                "swiglu_limits", "unused_keys", "multi_token_prediction",
                "num_kv_heads_for_linear_attn", "drawn_vectors", "weights",
                "state", "adapters", "tokenizer"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["max_seq_len_served"] == 8192
    assert cfg["rehearsal"]["base_preset"] == "ling-tiny"
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Ling-3.0-flash")
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
    assert preset.rope_theta == cfg["rope_theta"]
    assert preset.norm_eps == cfg["rms_norm_eps"]
    assert preset.max_seq_len == cfg["max_position_embeddings"]
    assert preset.experts_held == 128 and preset.padded_vocab == 39296


def test_analysts_mix_is_a_closed_loop_at_the_slots_count():
    mix = manifest.load_traffic("analysts")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 64, 20, 0)
    assert mix["adapters"]["count"] == 0 and mix["stream"] is True
    # the issue's parameters, letter for letter
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.5, "min": 512, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.3, "min": 512, "max": 2048}
    assert mix["base_seed"] == 20261060
    cfg = manifest.load_config(CONFIG)
    slots = int(cfg["server_args"][cfg["server_args"].index(
        "--decode-slots") + 1])
    assert mix["clients"] == slots
    reqs = traffic.build_requests(mix, 3500000077, 40)
    assert len(reqs) == mix["pool_requests"] == 4096
    assert all(512 <= r.prompt_tokens <= 4096 for r in reqs)
    assert all(512 <= r.max_tokens <= 2048 for r in reqs)
    assert max(r.prompt_tokens + r.max_tokens for r in reqs) <= 6144 < 8192
    # both prefill paths: the 512 and 1,024 buckets and the chunk stream
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert traffic.prefill_shapes(mix, buckets) == [512, 1024, 4096]
    bucketed = sum(r.prompt_tokens <= 1024 for r in reqs) / len(reqs)
    assert 0.4 < bucketed < 0.6  # half: both paths have work


@pytest.mark.parametrize("seed", [1, 3500000077, 2 ** 31 + 11])
def test_every_seed_offers_the_same_work_in_another_order(seed):
    mix = manifest.load_traffic("analysts")
    base = traffic.build_requests(mix, 0, 40)
    reqs = traffic.build_requests(mix, seed, 40)
    size = lambda rs: sorted((r.prompt_tokens, r.max_tokens) for r in rs)  # noqa: E731
    assert size(reqs) == size(base)
    assert [r.prompt for r in reqs] != [r.prompt for r in base]


def test_benchmarks_reference_equals_the_programs_on_ling_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import bailing_hybrid
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import (
        TINY_LFM2_TEST,
        TINY_LING_TEST as cfg,
    )

    with open(bailing_hybrid.__file__) as f:  # a copy, not a wrapper
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert not any("llm_instance_gateway_tpu" in ln for ln in imports)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 24).astype(np.int32))
    params = transformer.init_params(
        cfg, jax.random.PRNGKey(2), dtype=jnp.float32, quantize=True)
    ours, theirs = [], []
    want = np.asarray(reference.forward(cfg, params, tokens, states=ours))
    got = np.asarray(bailing_hybrid.forward(
        cfg, params, tokens, states=theirs, state_ends=(20, 24)))
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) < 1e-4 * scale
    # every KDA layer's state [ends, H, dk, dv] and every latent layer's
    # rows [S, width], in layer order
    kda = [s for s in theirs if s.ndim == 4]
    rows = [s for s in theirs if s.ndim == 2]
    assert len(kda) == cfg.n_layers_of("kda") == 7 and len(rows) == 1
    assert rows[0].shape == (24, cfg.latent_width)
    np.testing.assert_allclose(np.stack(kda)[:, 1],
                               np.stack([s for s, _ in ours]),
                               rtol=1e-3, atol=1e-5)
    # the attention a block of queries at a time: the same numbers
    blocked = np.asarray(bailing_hybrid.forward(cfg, params, tokens, block=7,
                                                logits_from=20))
    np.testing.assert_allclose(blocked, got[20:], rtol=1e-3, atol=1e-4)
    low = np.asarray(bailing_hybrid.forward(cfg, params, tokens,
                                            round_to=jnp.float8_e4m3fn))
    assert np.max(np.abs(low - want)) > 1e-2 * scale
    for wrong in bailing_hybrid.WRONG:  # each is another function
        other = np.asarray(bailing_hybrid.forward(cfg, params, tokens,
                                                  wrong=wrong))
        assert np.max(np.abs(other - want)) > 1e-3 * scale, wrong
    with pytest.raises(NotImplementedError):
        bailing_hybrid.forward(TINY_LFM2_TEST, params, tokens)
    with pytest.raises(ValueError):
        bailing_hybrid.forward(cfg, params, tokens, wrong="no_norm")


def test_the_references_gates_keep_the_groups_rule_by_hand():
    """Four groups of four, the two best groups, top-4: group 0's single
    best expert loses with its group (``tests/test_ling.py`` holds the
    program to the same case)."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import bailing_hybrid

    cfg = types.SimpleNamespace(n_group=4, topk_group=2, n_experts=16,
                                n_experts_per_token=4, n_experts_local=0,
                                expert_first=0, router_gate_eps=1e-20,
                                routed_scaling_factor=2.0)
    scores = jnp.asarray([[0.9, 0.0, 0.0, 0.0,   0.8, 0.7, 0.1, 0.0,
                           0.8, 0.6, 0.2, 0.0,   0.5, 0.3, 0.0, 0.0]])
    w = np.asarray(bailing_hybrid.gates(cfg, scores, jnp.zeros((16,))))[0]
    assert set(np.flatnonzero(w)) == {4, 5, 8, 9}
    np.testing.assert_allclose(w[[4, 5, 8, 9]],
                               2.0 * np.array([0.8, 0.7, 0.8, 0.6]) / 2.9,
                               rtol=1e-6)
    # a share that renormalises over what it holds is another function
    cfg.n_experts_local, cfg.expert_first = 8, 0
    right = np.asarray(bailing_hybrid.gates(cfg, scores, jnp.zeros((16,))))
    wrong = np.asarray(bailing_hybrid.gates(cfg, scores, jnp.zeros((16,)),
                                            wrong="share_renormalised"))
    np.testing.assert_allclose(right[0], w)
    np.testing.assert_allclose(wrong[0, [4, 5]],
                               2.0 * np.array([0.8, 0.7]) / 1.5, rtol=1e-6)
    assert wrong[0, 8] == wrong[0, 9] == 0.0
