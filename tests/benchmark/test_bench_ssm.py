"""What PR 43 added to the benchmark: the configuration ``falcon-h1-34b-d8``,
the traffic mix ``reasoners``, the cell ``falconh1_d8_reasoners``, its three
per-layer metrics, the benchmark's own copy of the plain reference,
``shapes_ssm`` and the check script ``reference_check_falconh1.py``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, peaks, readers, shapes_ssm, traffic  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
CELL = "falconh1_d8_reasoners"
NEW = ["ssm.state_rows_mean.batch", "ssm.decode_update_ops_pct.batch",
       "ssm.decode_update_hbm_roofline.batch"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_cell_and_its_lists():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-d8", "reasoners", 1)
    assert len(cell["why"]) <= 200
    assert CELL in E2E["output_tok_s"]["workloads"]
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, "end_to_end")
            ] == ["output_tok_s", "setup_s"]
    # every ``.batch`` twin reads here but what counts experts or a latent
    # cache, and the whole step's roofline, whose shapes.py counts no
    # recurrent state and would read low
    listed = {m["name"] for m in MAN["per_layer"]
              if "mixtral_d6_batch" in m.get("workloads", ())}
    here = {m["name"] for m in MAN["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"model.decode_step_hbm_roofline.batch",
            "moe.experts_touched_mean.batch", "moe.rows_per_expert_mean.batch",
            "moe.experts_ops_pct.batch", "moe.experts_hbm_roofline.batch"
            } <= listed - here
    assert set(NEW) <= here - listed
    # K/V lanes (decode_attention), the engine, the device, the server
    assert {"attn.decode_ops_pct.batch", "model.copy_ops_pct.batch",
            "model.decode_device_ms.batch", "kv.usage_peak_pct.batch",
            "device.idle_pct.batch", "engine.batch_rows_mean.batch",
            "model.lora_rows_mean.batch", "server.stalled_ms.batch",
            "gateway.relay_p50_us.batch"} <= here
    assert not any(n.startswith(("moe.", "mla.")) for n in here)
    model = manifest.load_config(cell["config"])["model"]
    for name in here:
        assert manifest.can_report(manifest.load_metric(name), model), name
    # ... and nothing of an open-loop cell does
    for m in MAN["per_layer"]:
        if m["moves"] != "output_tok_s" and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]


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


def test_counter_metric_reads_a_canned_metrics_text():
    before = "tpu:ssm_state_rows_total 640\ntpu:dispatch_steps_sum 10\n"
    after = "tpu:ssm_state_rows_total 128640\ntpu:dispatch_steps_sum 2010\n"
    ctx = {"prom_before": [before], "prom_after": [after], "window_s": 40.0}
    assert read("ssm.state_rows_mean.batch", ctx) == pytest.approx(64.0)
    # the parent has no such counter: nothing, and no error
    parent = {"prom_before": ["tpu:dispatch_steps_sum 10\n"],
              "prom_after": ["tpu:dispatch_steps_sum 20\n"], "window_s": 40.0}
    assert read("ssm.state_rows_mean.batch", parent) is None


def test_kernel_share_reads_a_canned_trace_summary():
    trace = {"window_s": 4.0, "op_totals": [
        ["ssm_decode_update.7", 1.5], ["ssm_decode_update.9", 0.5],
        ["decode_attention.13", 0.2], ["fusion.1", 1.0]]}
    assert read("ssm.decode_update_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(50.0))
    assert read("attn.decode_ops_pct.batch", {"trace": trace}) == (
        pytest.approx(5.0))
    parent = {"trace": {"window_s": 4.0, "op_totals": [["while.15", 2.0]]}}
    for name in NEW[1:]:
        assert read(name, parent) is None
        assert read(name, {}) is None


def test_shapes_ssm_counts_a_rows_state_once_each_way():
    model = manifest.load_config("falcon-h1-34b-d8")["model"]
    state = 32 * 128 * 256
    assert shapes_ssm.row_bytes(model) == (
        2 * state * 4 + (2 * 32 * 128 + 2 * 2 * 256 + 32) * 4)
    assert shapes_ssm.row_bytes(model) / (8 << 20) == pytest.approx(
        1.0, abs=0.005)  # the state both ways is all but 0.4% of it
    assert shapes_ssm.layer_step_bytes(model, 64) == (
        64 * shapes_ssm.row_bytes(model))
    # 0.54 GB a layer a step at 64 live rows, as the cell's ``why`` says
    assert shapes_ssm.layer_step_bytes(model, 64) / 1e9 == pytest.approx(
        0.539, abs=0.001)
    assert shapes_ssm.window_bytes(model, {"rows": 64 * 2000}) == (
        8 * 2000 * shapes_ssm.layer_step_bytes(model, 64))
    peak = peaks.device_peaks("TPU v5 lite")
    at_roofline = shapes_ssm.layer_step_bytes(model, 64) / peak[
        "hbm_bytes_per_s"]
    got = shapes_ssm.roofline_share(model, 64, 2 * at_roofline, peak)
    assert got["bound"] == "hbm"
    assert got["share_pct"] == pytest.approx(50.0)


def test_kernel_roofline_sets_the_windows_bytes_against_the_kernels_time():
    """2,000 decode steps in the window, one a program, 250 of the
    programs in the trace: the counter's growth over the window stands
    against eight times the traced kernel time."""
    cfg = manifest.load_config("falcon-h1-34b-d8")
    steps, rows = 2000, 64
    nbytes = shapes_ssm.window_bytes(cfg["model"], {"rows": rows * steps})
    at_roofline_s = nbytes / 819e9
    before = ("tpu:ssm_state_rows_total 7\ntpu:dispatch_steps_sum 1\n"
              "tpu:dispatch_steps_count 1\n")
    after = (f"tpu:ssm_state_rows_total {rows * steps + 7}\n"
             f"tpu:dispatch_steps_sum {steps + 1}\n"
             f"tpu:dispatch_steps_count {steps + 1}\n")
    trace = {"window_s": 4.0, "op_totals": [
        ["ssm_decode_update.16", 0.1875 * at_roofline_s],
        ["ssm_decode_update.17", 0.0625 * at_roofline_s],
        ["decode_attention.3", 1.0]],
        "modules": {"jit_decode_block": {"count": 250, "total_s": 3.9,
                                         "median_s": 0.0156},
                    "jit_prefill": {"count": 9, "total_s": 0.3,
                                    "median_s": 0.039}}}
    ctx = {"window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "prom_before": [before], "prom_after": [after], "trace": trace,
           "profile_records": [[]]}
    name = "ssm.decode_update_hbm_roofline.batch"
    assert read(name, ctx) == pytest.approx(50.0)
    assert read(name, dict(ctx, trace=dict(trace, window_s=9.0))) == (
        pytest.approx(50.0))  # by work, not by the clock
    # nothing to read: no trace, no kernel in it, a counter that stood
    # still or is not there (the parent's program)
    assert read(name, dict(ctx, trace=None)) is None
    assert read(name, dict(ctx, trace=dict(trace, op_totals=[
        ["decode_attention.13", 0.2]]))) is None
    assert read(name, dict(ctx, prom_after=[before])) is None
    no_counter = "tpu:dispatch_steps_sum 1\ntpu:dispatch_steps_count 1\n"
    assert read(name, dict(ctx, prom_before=[no_counter],
                           prom_after=[no_counter])) is None


def test_configuration_file_holds_the_catalogs_numbers():
    cfg = manifest.load_config("falcon-h1-34b-d8")
    assert cfg["reduced"] == {"n_layers": 8}
    assert cfg["base_preset"] == "falcon-h1-34b"
    args = cfg["server_args"]
    assert args == ["--quantize", "int8", "--decode-slots", "64",
                    "--max-seq-len", "2048", "--max-loras", "0"]
    model = cfg["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["d_ff"], model["vocab_size"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"],
        cfg["vocab_size"])
    assert (model["ssm_d_inner"], model["ssm_n_heads"], model["ssm_head_dim"],
            model["ssm_d_state"], model["ssm_n_groups"], model["ssm_d_conv"],
            model["ssm_chunk"]) == (
        cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"],
        cfg["mamba_chunk_size"])
    assert cfg["num_hidden_layers"] == 72 and model["n_layers"] == 8
    assert cfg["published"] == {k: cfg[k] for k in cfg["published"]}
    for key in ("state_dtype", "state_layout", "ssm_vectors",
                "ssm_multipliers_order", "head_to_group", "dt_limits",
                "conv_history", "rope_pairing", "tokenizer"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["max_seq_len_served"] == 2048
    assert "8 of 72 layers" in cfg["deployment"]
    assert cfg["rehearsal"]["base_preset"] == "falcon-h1-tiny"
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Falcon-H1-34B-Instruct")
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg[key] == value, key  # top level: the source as published


def test_the_server_would_report_the_files_model_group():
    """``/debug/device`` ``model_config`` is the preset's fields with
    ``reduced`` applied: every key of the file's ``model`` group equals it,
    and the preset carries the source's multipliers."""
    import dataclasses

    from llm_instance_gateway_tpu.models import mixtral

    cfg = manifest.load_config("falcon-h1-34b-d8")
    preset = dataclasses.replace(mixtral.CONFIGS[cfg["base_preset"]],
                                 **cfg["reduced"])
    served = dataclasses.asdict(preset)
    for key, value in cfg["model"].items():
        assert served[key] == value, key
    for key in ("embedding_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "key_multiplier",
                "lm_head_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        assert getattr(preset, key) == cfg[key], key
    assert list(preset.ssm_multipliers) == cfg["ssm_multipliers"]
    assert list(preset.mlp_multipliers) == cfg["mlp_multipliers"]
    assert preset.rope_theta == cfg["rope_theta"]
    assert preset.norm_eps == cfg["rms_norm_eps"]
    assert preset.max_seq_len == cfg["max_position_embeddings"]


def test_reasoners_mix_is_a_closed_loop_at_the_slots_count():
    mix = manifest.load_traffic("reasoners")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 64, 20, 0)
    assert mix["adapters"]["count"] == 0 and mix["stream"] is True
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.5, "min": 64, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.4, "min": 256, "max": 1024}
    assert mix["slo"] == manifest.load_traffic("agents")["slo"]
    cfg = manifest.load_config("falcon-h1-34b-d8")
    slots = int(cfg["server_args"][cfg["server_args"].index(
        "--decode-slots") + 1])
    assert mix["clients"] == slots
    reqs = traffic.build_requests(mix, 3500000077, 40)
    assert len(reqs) == mix["pool_requests"] >= 960
    assert all(64 <= r.prompt_tokens <= 512 for r in reqs)
    assert all(256 <= r.max_tokens <= 1024 for r in reqs)
    assert max(r.prompt_tokens + r.max_tokens for r in reqs) <= 1536 < 2048
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    # no prompt over the largest bucket: the chunk stream is not on the path
    assert traffic.prefill_shapes(mix, buckets) == [64, 128, 256, 512]


@pytest.mark.parametrize("seed", [1, 3500000077, 2 ** 31 + 11])
def test_every_seed_offers_the_same_work(seed):
    """A seed turns the pool: the same sizes in another order, so that the
    first 400 requests (more than a ramp and a window complete) hold nearly
    the same prompt and answer tokens whatever the turn."""
    mix = manifest.load_traffic("reasoners")
    base = traffic.build_requests(mix, 0, 40)
    reqs = traffic.build_requests(mix, seed, 40)
    size = lambda rs: sorted((r.prompt_tokens, r.max_tokens) for r in rs)  # noqa: E731
    assert size(reqs) == size(base)
    for field in ("prompt_tokens", "max_tokens"):
        total = lambda rs: sum(getattr(r, field) for r in rs[:400])  # noqa: E731
        assert abs(total(reqs) / total(base) - 1) < 0.1, field


def test_benchmarks_reference_equals_the_programs_on_falcon_h1_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import falcon_h1
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import (
        TINY_FALCON_H1_TEST as cfg,
        TINY_QWEN_TEST,
    )

    with open(falcon_h1.__file__) as f:  # a copy, not a wrapper
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert not any("llm_instance_gateway_tpu" in ln for ln in imports)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 24).astype(np.int32))
    for quantize in (False, True):
        params = transformer.init_params(
            cfg, jax.random.PRNGKey(2), dtype=jnp.float32, quantize=quantize)
        want_states, got_states = [], []
        want = np.asarray(reference.forward(cfg, params, tokens,
                                            states=want_states))
        got = np.asarray(falcon_h1.forward(cfg, params, tokens,
                                           states=got_states))
        assert np.max(np.abs(got - want)) < 1e-5 * np.max(np.abs(want))
        assert len(got_states) == cfg.n_layers
        for a, b in zip(got_states, want_states):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
        tail = np.asarray(falcon_h1.forward(cfg, params, tokens,
                                            logits_from=20))
        np.testing.assert_allclose(tail, got[20:], rtol=1e-5, atol=1e-6)
    low = np.asarray(falcon_h1.forward(cfg, params, tokens,
                                       round_to=jnp.float8_e4m3fn))
    assert np.max(np.abs(low - want)) > 1e-2 * np.max(np.abs(want))
    # a bf16 state: the logits hardly move, the state does
    states = []
    low = np.asarray(falcon_h1.forward(cfg, params, tokens,
                                       state_dtype=jnp.bfloat16,
                                       states=states))
    assert np.max(np.abs(low - want)) < 1e-3 * np.max(np.abs(want))
    drift = max(float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                for a, b in zip(states, want_states))
    assert drift > 1e-3
    with pytest.raises(NotImplementedError):
        falcon_h1.forward(TINY_QWEN_TEST, params, tokens)


def test_the_head_is_computed_a_block_of_columns_at_a_time(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import falcon_h1

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 50)), jnp.float32)
    monkeypatch.setattr(falcon_h1, "HEAD_BLOCK", 16)
    np.testing.assert_allclose(falcon_h1._head({"lm_head": w}, x), x @ w,
                               rtol=1e-5, atol=1e-6)
    quant = {"q": jnp.asarray(rng.integers(-127, 127, size=(8, 50)), jnp.int8),
             "s": jnp.asarray(rng.uniform(0.01, 0.1, size=(50,)), jnp.float32)}
    np.testing.assert_allclose(
        falcon_h1._head({"lm_head": quant}, x),
        x @ (quant["q"].astype(jnp.float32) * quant["s"]), rtol=1e-5,
        atol=1e-5)


def test_reference_check_rehearses_on_the_tiny_preset():
    """The check script end to end on ``falcon-h1-tiny`` (float32): the
    system within rounding of the reference in logits and state, rows that
    do not depend on their slot, every reading taken, exit 10 (a rehearsal
    is never a result).  The limits are placed for the published widths and
    256 decode steps: after the rehearsal's 8 a bf16 state is only 5e-3
    off, under the state's limit, so its rows may read FAIL."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "reference_check_falconh1.py"),
         "--rehearse-cpu", "--readings", "--seed", str(2 ** 31 + 5)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 10, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.splitlines()
    rows = [json.loads(ln[5:]) for ln in lines
            if ln.startswith(("PASS ", "FAIL "))]
    assert len(rows) == 2
    verdict = json.loads(lines[-1])
    assert verdict["rows_independent"]
    assert verdict["worst_max"] < 1e-5 and verdict["worst_state"] < 1e-5
    for row in rows:
        assert row["bf16_max"] < 0.025 < row["fp8_max"]
        assert row["bf16_state_state"] > 1e-3 > row["bf16_state"] / 2
        assert row["bf16_state_max"] < row["bf16_max"]
