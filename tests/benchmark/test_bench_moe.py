"""What PR 27 added to the benchmark: the configuration ``olmoe-1b-7b``, the
cell ``olmoe_chat``, six per-layer metrics of the sparse layer (PR 41: and the
expert matmuls' roofline share), the benchmark's own copy of the plain
reference, and ``shapes_moe``.  The ``moe.*`` metrics are held to a rule, not
to lists: any sparse cell may join them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers, shapes, shapes_moe  # noqa: E402

MAN = manifest.load_manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MAN["per_layer"]}
NEW = ["moe.experts_touched_mean", "moe.rows_per_expert_mean",
       "moe.experts_ops_pct"]
MOE = [m["name"] for m in MAN["per_layer"] if m["name"].startswith("moe.")]
QWEN_BYTES, MIXTRAL_BYTES = 7216468992, 8059207362.0

# /metrics of a replica before and after a window: 100 layer-steps of 64
# experts, 4,000 assignments, 4,500 experts touched.
BEFORE = """# TYPE tpu:moe_layer_steps_total counter
tpu:moe_layer_steps_total 16
tpu:moe_assignments_total 512
tpu:moe_experts_touched_total 400
"""
AFTER = """# TYPE tpu:moe_layer_steps_total counter
tpu:moe_layer_steps_total 116
tpu:moe_assignments_total 4512
tpu:moe_experts_touched_total 4900
"""
TRACE = {"window_s": 4.0, "op_totals": [
    ["moe_gmm_int8.3", 0.5], ["moe_gmm_int8.4", 0.3], ["moe_gmm.9", 0.2],
    ["while.15", 2.0], ["decode_attention.13", 0.2], ["fusion.moe", 0.1]]}


def read(name, ctx):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_manifest_has_no_problem_with_the_new_entries():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, "olmoe_chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b", "chat_olmoe", 1)
    assert "olmoe_chat" in E2E["tpot_p50_ms"]["workloads"]
    # the whole step's share counts the experts the counters say were read
    assert "olmoe_chat" in PER_LAYER[
        "model.decode_step_hbm_roofline"]["workloads"]
    assert manifest.load_metric("model.decode_step_hbm_roofline")[
        "args"]["experts"] == "counted"
    assert {n + s for n in NEW for s in ("", ".batch")} <= set(MOE)


@pytest.mark.parametrize("name", MOE)
def test_each_moe_metric_lists_sparse_cells_that_report_what_it_moves(name):
    entry = PER_LAYER[name]
    assert entry["workloads"], "a moe.* metric names its cells"
    for cell in entry["workloads"]:
        config = manifest.cell(MAN, cell)["config"]
        assert manifest.load_config(config)["model"].get("n_experts", 0) > 0
    assert set(entry["workloads"]) <= set(E2E[entry["moves"]]["workloads"])
    spec = manifest.load_metric(name)
    assert spec["reader"] in readers.READERS
    dense = manifest.load_config("qwen2.5-7b")["model"]
    assert not manifest.can_report(spec, dense)


@pytest.mark.parametrize("suffix", ["", ".batch"])
def test_counter_metrics_read_a_canned_metrics_text(suffix):
    ctx = {"prom_before": [BEFORE], "prom_after": [AFTER], "window_s": 40.0}
    assert read("moe.experts_touched_mean" + suffix, ctx) == pytest.approx(45.0)
    # rows that share one read of an expert's weights
    assert read("moe.rows_per_expert_mean" + suffix, ctx) == pytest.approx(
        4000 / 4500)
    dense = {"prom_before": ["tpu:x 1\n"], "prom_after": ["tpu:x 2\n"],
             "window_s": 40.0}
    assert read("moe.experts_touched_mean" + suffix, dense) is None
    assert read("moe.rows_per_expert_mean" + suffix, dense) is None


@pytest.mark.parametrize("suffix", ["", ".batch"])
def test_ops_share_reads_a_canned_trace_summary(suffix):
    got = read("moe.experts_ops_pct" + suffix, {"trace": TRACE})
    assert got == pytest.approx(100.0 * (0.5 + 0.3 + 0.2) / 4.0)
    # a program without the kernel (the parent): nothing, and no error
    parent = {"trace": {"window_s": 4.0, "op_totals": [["while.15", 2.0]]}}
    assert read("moe.experts_ops_pct" + suffix, parent) is None
    assert read("moe.experts_ops_pct" + suffix, {}) is None


def test_experts_roofline_reads_the_windows_totals_against_the_traced_time():
    """1,000 decode programs in the window, 100 of them in the trace: the
    touched experts' bytes of the window stand against ten times the traced
    kernel time."""
    cfg = manifest.load_config("olmoe-1b-7b")
    nbytes = shapes_moe.layer_step_bytes(2048, 1024, 4500, 4000)
    assert shapes_moe.window_bytes(
        cfg["model"], {"touched": 4500, "assignments": 4000}) == nbytes
    at_roofline_s = nbytes / 819e9
    trace = {"window_s": 4.0, "op_totals": [
        ["moe_gmm_int8.3", 0.1 * at_roofline_s],
        ["moe_gmm_int8.4", 0.1 * at_roofline_s], ["while.15", 2.0]],
        "modules": {"jit_decode_block": {"count": 100, "total_s": 0.44,
                                         "median_s": 0.0044},
                    "jit_prefill": {"count": 3, "total_s": 0.03,
                                    "median_s": 0.0106}}}
    ctx = {"prom_before": [BEFORE + "tpu:dispatch_steps_count 50\n"],
           "prom_after": [AFTER + "tpu:dispatch_steps_count 1050\n"],
           "window_s": 40.0, "config": cfg, "device_kind": "TPU v5 lite",
           "trace": trace}
    name = "moe.experts_hbm_roofline.batch"
    assert read(name, ctx) == pytest.approx(50.0)
    assert read(name, dict(ctx, trace=None)) is None
    assert read(name, dict(ctx, trace=dict(trace, op_totals=[
        ["while.15", 2.0]]))) is None
    assert read(name, dict(ctx, prom_after=ctx["prom_before"])) is None
    # four replicas' counters against replica 0's trace
    assert read(name, dict(ctx, prom_before=ctx["prom_before"] * 4,
                           prom_after=ctx["prom_after"] * 4)) == (
        pytest.approx(50.0))
    # closed loops only: an open loop's traced seconds hold other rows than
    # its window's mean (PERF.md section 6, PR 41), so olmoe_chat has none
    assert "moe.experts_hbm_roofline" not in PER_LAYER
    closed = {w["name"] for w in MAN["workloads"]
              if manifest.load_traffic(w["traffic"])["loop"] == "closed"}
    assert set(PER_LAYER[name]["workloads"]) <= closed
    # an expert's width is moe_d_ff where the model has one beside d_ff
    glm = manifest.load_config("glm-4.7-flash-d13")["model"]
    assert shapes_moe.window_bytes(glm, {"touched": 10, "assignments": 0}) == (
        10 * shapes_moe.expert_bytes(2048, 1536))


STEP_S = 0.0044


class Row:  # what ``roofline`` reads of a client's result
    in_window, tokens, prompt_tokens = True, 64, 96


def roofline_ctx(config, experts_touched=None, step_s=STEP_S):
    prom = ["", ""]
    if experts_touched is not None:
        prom = ["tpu:moe_experts_touched_total 0\n"
                "tpu:moe_layer_steps_total 0\n",
                f"tpu:moe_experts_touched_total {experts_touched * 100}\n"
                "tpu:moe_layer_steps_total 100\n"]
    return {"config": manifest.load_config(config), "window_s": 40.0,
            "device_kind": "TPU v5 lite", "results": [Row()],
            "prom_before": [prom[0]], "prom_after": [prom[1]],
            "profile_records": [[{"phase": "decode", "active": 4}]],
            # olmoe's traced 4 s since PR 40: a 4.4 ms decode program, the
            # prefills longer and rarer, the helpers far under a millisecond
            "trace": {"modules": {
                "jit_decode_block": {"count": 787, "total_s": 3.5,
                                     "median_s": step_s},
                "jit_prefill": {"count": 10, "total_s": 0.1,
                                "median_s": 0.0106},
                "jit_convert_element_type": {"count": 900, "total_s": 0.001,
                                             "median_s": 1e-6}}}}


def test_the_whole_steps_roofline_counts_the_experts_the_counters_say():
    name = "model.decode_step_hbm_roofline"
    olmoe = manifest.load_config("olmoe-1b-7b")["model"]
    got = read(name, roofline_ctx("olmoe-1b-7b", experts_touched=20))
    want = shapes.decode_step_bytes(olmoe, 4, 96 + 32, experts_read=20)
    assert got == pytest.approx(100 * want / 819e9 / STEP_S)
    more = read(name, roofline_ctx("olmoe-1b-7b", experts_touched=40))
    assert more > got
    # no counter (a parent before PR 27): nothing, not a guess
    assert read(name, roofline_ctx("olmoe-1b-7b")) is None
    # a dense model has no experts: it reads with or without the counters
    dense = read(name, roofline_ctx("qwen2.5-7b"))
    assert dense == read(name, roofline_ctx("qwen2.5-7b", experts_touched=3))
    qwen = manifest.load_config("qwen2.5-7b")["model"]
    assert dense == pytest.approx(
        100 * shapes.decode_step_bytes(qwen, 4, 128) / 819e9 / STEP_S)
    # the batch twin keeps uniform routing at the configuration's own top-k:
    # Mixtral's file states none, and 2 is the default it always had
    batch = read(name + ".batch", roofline_ctx("mixtral-8x7b-d6", 7, 0.0143))
    mixtral = manifest.load_config("mixtral-8x7b-d6")["model"]
    assert batch == pytest.approx(
        100 * shapes.decode_step_bytes(mixtral, 4, 128) / 819e9 / 0.0143)


def test_decode_step_bytes_are_what_they_were_before_pr_41():
    """Byte for byte the parent's numbers (computed on 62dcf1d) at 8 live
    rows of 300 positions: the new arguments move nothing that was there."""
    qwen = manifest.load_config("qwen2.5-7b")["model"]
    mixtral = manifest.load_config("mixtral-8x7b-d6")["model"]
    assert shapes.decode_step_bytes(qwen, 8, 300) == QWEN_BYTES
    assert shapes.decode_step_bytes(mixtral, 8, 300) == MIXTRAL_BYTES


def test_the_traffic_file_is_the_chat_mix_at_its_own_rate():
    chat, mine = manifest.load_traffic("chat"), manifest.load_traffic(
        "chat_olmoe")
    for key in ("loop", "arrival", "prompt_tokens", "output_tokens",
                "adapters", "stream", "ramp_s"):
        assert mine[key] == chat[key], key
    assert mine["edges"] == "periodic" and mine["tail_s"] == 5
    assert mine["base_seed"] != chat["base_seed"]
    assert mine["rate_rps"] == pytest.approx(0.8 * mine["knee_rps"])


def test_the_configuration_holds_the_catalogs_numbers_and_cuts_nothing():
    cfg = manifest.load_config("olmoe-1b-7b")
    catalog = {"hidden_size": 2048, "intermediate_size": 1024,
               "max_position_embeddings": 4096, "num_attention_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8,
               "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_theta": 10000,
               "vocab_size": 50304}
    for key, value in catalog.items():
        assert cfg[key] == value and cfg["published"][key] == value, key
    assert cfg["norm_topk_prob"] is False and cfg["reduced"] == {}
    model = cfg["model"]
    assert (model["n_experts"], model["n_experts_per_token"]) == (64, 8)
    assert model["qk_norm"] is True and model["norm_topk_prob"] is False
    assert model["n_layers"] == 16


def test_shapes_moe_against_hand_counted_bytes():
    # OLMoE: one expert = 3 x 2048 x 1024 int8 + (1024 + 1024 + 2048) scales
    assert shapes_moe.expert_bytes(2048, 1024) == 6_291_456 + 4 * 4096
    # Mixtral: 3 x 4096 x 14336 int8 + (14336 + 14336 + 4096) f32 scales
    assert shapes_moe.expert_bytes(4096, 14336) == 176_160_768 + 4 * 32768
    assert shapes_moe.expert_bytes(2048, 1024, "bfloat16") == 12_582_912
    # 42 experts touched by 64 rows (8 tokens x top-8) in one OLMoE layer
    assert shapes_moe.layer_step_bytes(2048, 1024, 42, 64) == (
        42 * 6_307_840 + 64 * 2 * (3 * 2048 + 3 * 1024))
    assert shapes_moe.layer_step_flops(2048, 1024, 64) == (
        2 * 64 * 3 * 2048 * 1024)
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    model = {"d_model": 2048, "d_ff": 1024}
    share = shapes_moe.roofline_share(model, 42, 64, 0.5e-3, peak)
    assert share["bound"] == "hbm"
    assert share["share_pct"] == pytest.approx(
        100 * share["bytes"] / 819e9 / 0.5e-3)
    # a 1024-token prefill of Mixtral is bound by the MXU, not by HBM
    mixtral = {"d_model": 4096, "d_ff": 14336}
    assert shapes_moe.roofline_share(
        mixtral, 8, 2048, 1e-2, peak)["bound"] == "mxu"


def test_the_benchmarks_reference_equals_the_programs_on_olmoe_tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import olmoe as bench_reference
    from llm_instance_gateway_tpu.models import reference, transformer
    from llm_instance_gateway_tpu.models.configs import TINY_OLMOE_TEST

    cfg = TINY_OLMOE_TEST
    params = transformer.init_params(cfg, jax.random.PRNGKey(2),
                                     dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, 12))
    mine = bench_reference.forward(cfg, params, tokens)
    theirs = reference.forward(cfg, params, tokens)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    # rounding the activations is a reading, not the verdict: it moves them
    low = bench_reference.forward(cfg, params, tokens, round_to=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low - theirs))) > 0


# run.py's ports and work directory are fixed, and another xdist worker
# rehearses at the same time (test_bench_harness.py): two rehearsals in one
# place answer each other's requests and overwrite each other's logs.  So
# this one runs in a network namespace and a tree of its own.  OWN_NET takes
# the namespace, brings its loopback up (SIOCSIFFLAGS, IFF_UP | IFF_RUNNING)
# and becomes the command.
OWN_NET = """
import fcntl, os, socket, struct, sys
os.unshare(os.CLONE_NEWNET)
with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
    fcntl.ioctl(s, 0x8914, struct.pack("16sH", b"lo", 0x1 | 0x40))
os.execv(sys.argv[1], sys.argv[1:])
"""


def own_place(tmp_path):
    """(command prefix, root) for a rehearsal nothing else can meet; where
    the kernel grants no namespace, the repository itself as it stands."""
    prefix = [sys.executable, "-c", OWN_NET]
    if subprocess.run(prefix + ["/bin/true"], capture_output=True).returncode:
        return [], REPO
    root = str(tmp_path / "tree")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "llm_instance_gateway_tpu"),
               os.path.join(root, "llm_instance_gateway_tpu"))
    return prefix, root


@pytest.mark.e2e
def test_olmoe_chat_rehearsal_exits_10(tmp_path):
    prefix, root = own_place(tmp_path)
    r = subprocess.run(
        prefix + [sys.executable, os.path.join(root, "benchmark", "run.py"),
                  "--workload", "olmoe_chat", "--seed", "3000000019",
                  "--seconds", "5", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=root)
    tail = r.stdout[-3000:] + r.stderr[-2000:]
    assert r.returncode == 10, tail
    assert "[FAIL]" not in r.stdout and r.stdout.count("[PASS]") == 9, tail
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL held: "), tail
    assert json.loads(last[len("REHEARSAL held: "):])["correct"] is True
