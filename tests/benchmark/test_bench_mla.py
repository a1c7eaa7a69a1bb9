"""What PR 35 added to the benchmark: the configuration ``glm-4.7-flash-d13``,
the traffic mix ``agents``, the cell ``glm47flash_d13_agents``, its per-layer
metrics (PR 41 added the latent kernel's roofline share and took the three
``moe.*.glm`` copies away: the cell reads the ``.batch`` twins), the
benchmark's own copy of the plain reference, ``shapes_mla`` and the check
script ``reference_check_glm.py``."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, peaks, readers, shapes_mla, traffic  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
CELL = "glm47flash_d13_agents"
# the cell's alone: they read what only a latent cache has
NEW = ["mla.decode_attn_ops_pct.batch", "mla.ctx_positions_mean.batch",
       "mla.decode_attn_hbm_roofline.batch"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_cell_and_its_lists():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash-d13", "agents", 1)
    assert CELL in E2E["output_tok_s"]["workloads"]
    # every metric of the other closed-loop cell reads here too, but for
    # what this cell gives nothing to read: a trace regex that matches none
    # of its kernels, and ``roofline``, whose shapes.py counts per-head lanes
    model = manifest.load_config(cell["config"])["model"]
    listed = [m["name"] for m in MAN["per_layer"]
              if "mixtral_d6_batch" in m.get("workloads", ())]
    assert listed
    for name in listed:
        here = manifest.can_report(manifest.load_metric(name), model)
        assert (CELL in PER_LAYER[name]["workloads"]) == here, name
    off = {n for n in listed if CELL not in PER_LAYER[n]["workloads"]}
    assert {"model.decode_step_hbm_roofline.batch",
            "attn.decode_ops_pct.batch"} <= off
    assert not any(n.startswith(("engine.", "device.", "moe.")) for n in off)
    # ... and nothing of an open-loop cell does
    for m in MAN["per_layer"]:
        if m["moves"] != "output_tok_s" and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]


@pytest.mark.parametrize("name", NEW)
def test_each_latent_metric_lists_latent_cells_and_moves_what_they_report(name):
    entry = PER_LAYER[name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "output_tok_s"
    assert set(entry["workloads"]) <= set(E2E[entry["moves"]]["workloads"])
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in MAN["per_layer"][:40]}
    spec = manifest.load_metric(name)
    assert spec["reader"] in readers.READERS
    # a cell can read it exactly where its model keeps a latent cache
    for w in MAN["workloads"]:
        model = manifest.load_config(w["config"])["model"]
        assert manifest.can_report(spec, model) == bool(
            model.get("kv_lora_rank")), w["name"]
        if w["name"] in entry["workloads"]:
            assert model.get("kv_lora_rank")


def test_counter_metric_reads_a_canned_metrics_text():
    before = ("tpu:latent_kv_positions_total 1000\n"
              "tpu:dispatch_steps_sum 10\n")
    after = ("tpu:latent_kv_positions_total 401000\n"
             "tpu:dispatch_steps_sum 20\n")
    ctx = {"prom_before": [before], "prom_after": [after], "window_s": 40.0}
    assert read("mla.ctx_positions_mean.batch", ctx) == pytest.approx(40000.0)
    # the parent has no such counter: nothing, and no error
    parent = {"prom_before": ["tpu:dispatch_steps_sum 10\n"],
              "prom_after": ["tpu:dispatch_steps_sum 20\n"], "window_s": 40.0}
    assert read("mla.ctx_positions_mean.batch", parent) is None


def test_kernel_share_reads_a_canned_trace_summary():
    trace = {"window_s": 4.0, "op_totals": [
        ["mla_decode_attention.7", 0.3], ["mla_decode_attention.9", 0.1],
        ["moe_gmm_int8.3", 1.0], ["decode_attention.13", 0.2]]}
    assert read("mla.decode_attn_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(10.0))
    assert read("moe.experts_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(25.0))
    parent = {"trace": {"window_s": 4.0, "op_totals": [["while.15", 2.0]]}}
    assert read("mla.decode_attn_ops_pct.batch", parent) is None
    assert read("mla.decode_attn_ops_pct.batch", {}) is None


def test_configuration_file_holds_the_catalogs_numbers():
    cfg = manifest.load_config("glm-4.7-flash-d13")
    assert cfg["reduced"] == {"n_layers": 13}
    assert cfg["base_preset"] == "glm-4.7-flash"
    assert "--max-loras" in cfg["server_args"]
    assert cfg["server_args"][cfg["server_args"].index("--max-loras") + 1] == "0"
    model = cfg["model"]
    assert (model["q_lora_rank"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["moe_d_ff"]) == (
        cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["moe_intermediate_size"])
    assert model["n_experts"] == cfg["n_routed_experts"] == 64
    assert model["n_experts_per_token"] == cfg["num_experts_per_tok"] == 4
    assert model["first_k_dense"] == cfg["first_k_dense_replace"] == 1
    assert model["routed_scaling_factor"] == cfg["routed_scaling_factor"]
    assert cfg["num_hidden_layers"] == 47 and model["n_layers"] == 13
    for key in ("multi_token_prediction", "rope_pairing", "adapters"):
        assert key in cfg["assumed"]
    assert "num_local_experts" not in cfg["published"]  # the source's own key
    assert "13 of 47 layers" in cfg["deployment"]
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "GLM-4.7-Flash")
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg[key] == value, key  # top level: the source as published


def test_the_server_would_report_the_files_model_group():
    """``/debug/device`` ``model_config`` is the preset's fields with
    ``reduced`` applied: every key of the file's ``model`` group equals it."""
    import dataclasses

    from llm_instance_gateway_tpu.models import mixtral

    cfg = manifest.load_config("glm-4.7-flash-d13")
    served = dataclasses.asdict(dataclasses.replace(
        mixtral.CONFIGS[cfg["base_preset"]], **cfg["reduced"]))
    served["head_dim"] = served["head_dim"] or (
        served["d_model"] // served["n_heads"])
    for key, value in cfg["model"].items():
        assert served[key] == value, key


def test_agents_mix_is_a_closed_loop_over_both_prefill_paths():
    mix = manifest.load_traffic("agents")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 32, 20, 0)
    assert mix["adapters"]["count"] == 0 and mix["stream"] is True
    reqs = traffic.build_requests(mix, 3500000077, 40)
    assert len(reqs) == mix["pool_requests"] == 640
    over = sum(r.prompt_tokens > 1024 for r in reqs) / len(reqs)
    assert 0.2 < over < 0.45  # about a third through the chunk stream
    assert all(128 <= r.prompt_tokens <= 2048 for r in reqs)
    assert all(128 <= r.max_tokens <= 1920 for r in reqs)
    assert max(r.prompt_tokens + r.max_tokens for r in reqs) < 4096
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert traffic.prefill_shapes(mix, buckets) == [128, 256, 512, 1024, 2048]


def test_shapes_mla_counts_a_layer_step():
    model = manifest.load_config("glm-4.7-flash-d13")["model"]
    # 32 rows holding 40,000 positions together
    nbytes = shapes_mla.layer_step_bytes(model, 40000, 32)
    assert nbytes == 40000 * 576 * 2 + 32 * 20 * 2 * (576 + 512)
    flops = shapes_mla.layer_step_flops(model, 40000)
    assert flops == 2.0 * 40000 * 20 * (576 + 512)
    peak = peaks.device_peaks("TPU v5 lite")
    at_roofline = nbytes / peak["hbm_bytes_per_s"]
    got = shapes_mla.roofline_share(model, 40000, 32, 2 * at_roofline, peak)
    assert got["bound"] == "hbm"
    assert got["share_pct"] == pytest.approx(50.0)


def test_kernel_roofline_sets_the_windows_bytes_against_the_kernels_time():
    """4,000 decode programs in the window, 500 of them in the trace: the
    counters' growth over the window stands against eight times the traced
    kernel time, whatever the clock says of the traced stretch."""
    cfg = manifest.load_config("glm-4.7-flash-d13")
    model = cfg["model"]
    steps, rows, positions = 4000, 32.0, 40000 * 4000
    nbytes = 13 * shapes_mla.layer_step_bytes(model, positions, rows * steps)
    assert shapes_mla.window_bytes(
        model, {"positions": positions, "steps": steps,
                "rows_mean": rows}) == nbytes
    at_roofline_s = nbytes / 819e9
    before = ("tpu:latent_kv_positions_total 7\ntpu:dispatch_steps_sum 1\n"
              "tpu:dispatch_steps_count 1\n")
    after = (f"tpu:latent_kv_positions_total {positions + 7}\n"
             f"tpu:dispatch_steps_sum {steps + 1}\n"
             f"tpu:dispatch_steps_count {steps + 1}\n")
    trace = {"window_s": 4.0, "op_totals": [
        ["mla_decode_attention.16", 0.1875 * at_roofline_s],
        ["mla_decode_attention.17", 0.0625 * at_roofline_s],
        ["moe_gmm_int8.3", 1.0]],
        "modules": {"jit_decode_block": {"count": 500, "total_s": 3.9,
                                         "median_s": 0.0088},
                    "jit_prefill": {"count": 9, "total_s": 0.3,
                                    "median_s": 0.039},
                    "jit_next_key": {"count": 900, "total_s": 0.004,
                                     "median_s": 4e-6}}}
    ctx = {"window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "prom_before": [before], "prom_after": [after], "trace": trace,
           "profile_records": [[{"phase": "decode", "active": 32},
                                {"phase": "prefill", "active": 1}]]}
    name = "mla.decode_attn_hbm_roofline.batch"
    assert read(name, ctx) == pytest.approx(50.0)
    assert read(name, dict(ctx, trace=dict(trace, window_s=9.0))) == (
        pytest.approx(50.0))  # by work, not by the clock
    # nothing to read: no trace, no kernel in it, a counter that stood still
    assert read(name, dict(ctx, trace=None)) is None
    assert read(name, dict(ctx, trace=dict(trace, op_totals=[
        ["decode_attention.13", 0.2]]))) is None
    assert read(name, dict(ctx, trace=dict(trace, modules={}))) is None
    assert read(name, dict(ctx, prom_after=[before])) is None
    assert read(name, dict(ctx, profile_records=[[]])) is None
    with pytest.raises(KeyError):  # a device with no published peak
        read(name, dict(ctx, device_kind="TPU v9"))


def test_benchmarks_reference_equals_the_programs_on_glm_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import glm4_moe_lite
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import TINY_GLM_TEST as cfg

    with open(glm4_moe_lite.__file__) as f:  # a copy, not a wrapper
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert not any("llm_instance_gateway_tpu" in ln for ln in imports)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 24).astype(np.int32))
    for quantize in (False, True):
        params = transformer.init_params(
            cfg, jax.random.PRNGKey(2), dtype=jnp.float32, quantize=quantize)
        want = np.asarray(reference.forward(cfg, params, tokens))
        got = np.asarray(glm4_moe_lite.forward(cfg, params, tokens))
        assert np.max(np.abs(got - want)) < 1e-5 * np.max(np.abs(want))
        tail = np.asarray(glm4_moe_lite.forward(cfg, params, tokens,
                                                logits_from=20))
        np.testing.assert_allclose(tail, got[20:], rtol=1e-5, atol=1e-6)
    low = np.asarray(glm4_moe_lite.forward(cfg, params, tokens,
                                           round_to=jnp.float8_e4m3fn))
    assert np.max(np.abs(low - want)) > 1e-2 * np.max(np.abs(want))
    with pytest.raises(NotImplementedError):
        from llm_instance_gateway_tpu.models.configs import TINY_OLMOE_TEST

        glm4_moe_lite.forward(TINY_OLMOE_TEST, params, tokens)
