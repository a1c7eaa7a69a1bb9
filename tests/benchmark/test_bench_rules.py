"""The manifest's rules, held to scratch copies of the benchmark (PR 41).

What a program's PR may bring is files and appended entries: a per-layer
metric with its metric file (and, for a kernel's roofline share, a file of
shapes), a cell added to the lists of the metrics it reports, a configuration
cut to one chip's share of a stated deployment.  Each test here makes such a
PR in a copy of ``BENCHMARK.json`` and ``benchmark/`` and asks
``manifest.problems``; what the rules refuse, they refuse by name.  The last
test runs ``tests/benchmark/`` of this tree, unedited, over such a PR."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402

OPEN = ["qwen7b_chat", "qwen7b_doc", "qwen7b_pool4_chat", "olmoe_chat"]
SHARE = "olmoe-1b-7b-s8"       # the scratch PR's configuration
CELL = "olmoe_s8_batch"        # and its cell
SHAPES = '''"""Bytes of the flash kernel over a window (a scratch PR's file)."""


def window_bytes(model: dict, inputs: dict) -> float:
    return inputs["prompt_tokens"] * model["d_model"] * 2.0
'''


class Scratch:
    """A copy of the manifest and ``benchmark/`` that ``manifest`` reads."""

    def __init__(self, root):
        self.root = str(root)
        shutil.copytree(os.path.join(REPO, "benchmark"),
                        os.path.join(self.root, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), self.root)
        self.man = manifest.load_manifest(self.root)

    def path(self, *parts):
        return os.path.join(self.root, "benchmark", *parts)

    def write(self, kind, name, doc):
        with open(self.path(kind, name + ".json"), "w") as f:
            json.dump(doc, f, indent=1)

    def save(self):
        with open(os.path.join(self.root, "BENCHMARK.json"), "w") as f:
            json.dump(self.man, f, indent=1)

    def add_metric(self, name, spec, moves, cells, **entry):
        self.write("metrics", name, spec)
        self.man["per_layer"].append(dict(
            {"name": name, "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "kernels", "moves": moves,
             "workloads": cells}, **entry))

    def add_cell(self, name, config, traffic, like, model=None):
        """A cell that joins every list that ``like`` is on and whose metric
        it can report."""
        self.man["workloads"].append(
            {"name": name, "config": config, "traffic": traffic, "chips": 1,
             "why": "a scratch PR's cell"})
        model = model or manifest.load_config(config)["model"]
        for m in self.man["end_to_end"] + self.man["per_layer"]:
            if like in m.get("workloads", ()) and (
                    "bound" in m or manifest.can_report(
                        manifest.load_metric(m["name"]), model)):
                m["workloads"].append(name)

    def problems(self):
        return manifest.problems(self.man)


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    s = Scratch(tmp_path)
    monkeypatch.setattr(manifest, "HERE", s.path())
    assert s.problems() == []
    return s


def share_config(**changes):
    """OLMoE as one of 8 chips that share each layer would hold it: 8 of 64
    experts, an eighth of the vocabulary, 8 of 16 layers; every width, the
    router's 64 outputs and top-8 as published."""
    cfg = copy.deepcopy(manifest.load_config("olmoe-1b-7b"))
    cfg.update(
        name=SHARE, served_model=SHARE,
        reduced={"n_layers": 8, "n_experts": 8, "vocab_size": 6288},
        deployment="8 chips share each layer (8 of 64 experts and an eighth "
                   "of the vocabulary here); 8 of 16 layers, the others on "
                   "further chips as pipeline stages")
    cfg["model"].update(n_layers=8, n_experts=8, vocab_size=6288)
    for key, value in changes.items():
        group, _, field = key.partition("__")
        if field:
            cfg[group][field] = value
            if group == "reduced":
                cfg["model"][field] = value
        else:
            cfg[group] = value
    return cfg


def add_share(scratch, cfg, name=SHARE, cell=CELL):
    scratch.write("configs", name, cfg)
    scratch.man["configs"].append(
        {"name": name, "source": cfg["source"],
         "file": f"benchmark/configs/{name}.json",
         "reduced": sorted(cfg["reduced"]), "why": "a scratch PR's share"})
    scratch.add_cell(cell, name, "batch", like="mixtral_d6_batch",
                     model=cfg["model"])


# -- what passes -------------------------------------------------------------

def test_an_appended_entry_with_its_metric_file_passes(scratch):
    scratch.add_metric(
        "attn.flash_ops_pct",
        {"reader": "trace_op_time",
         "args": {"regex": "^flash_attention", "per": "window"},
         "why": "a scratch PR's metric"}, "tpot_p50_ms", OPEN)
    assert scratch.problems() == []
    assert scratch.man["per_layer"][-1]["name"] == "attn.flash_ops_pct"


def test_a_kernels_roofline_share_is_a_shapes_file_and_a_metric_file(scratch):
    with open(scratch.path("shapes_flash.py"), "w") as f:
        f.write(SHAPES)
    spec = {"reader": "kernel_roofline",
            "args": {"regex": "^flash_attention",
                     "bytes_fn": "shapes_flash:window_bytes",
                     "inputs": {"prompt_tokens": {
                         "family": "tpu:prefill_tokens_total"}}},
            "what": "a scratch PR's roofline share"}
    scratch.add_metric("attn.flash_hbm_roofline", spec, "tpot_p50_ms", OPEN,
                       better="higher")
    assert scratch.problems() == []
    # ... and a typo in either half of the name is seen before any run
    for wrong, said in (("shapes_flash:bytes", "defines no bytes"),
                        ("shapes_flsh:window_bytes", "no benchmark/")):
        spec["args"]["bytes_fn"] = wrong
        scratch.write("metrics", "attn.flash_hbm_roofline", spec)
        assert any(said in p for p in scratch.problems()), wrong


def test_a_metric_of_a_kernel_the_rule_does_not_know_is_not_judged(scratch):
    """The next configuration brings a kernel of its own: its metric lists
    its cells with no edit to ``manifest.KERNELS``."""
    spec = {"reader": "trace_op_time",
            "args": {"regex": "^window_attention", "per": "window"}}
    for w in scratch.man["workloads"]:
        model = manifest.load_config(w["config"])["model"]
        assert manifest.can_report(spec, model)
    scratch.add_metric("attn.window_ops_pct", spec, "output_tok_s",
                       ["glm47flash_d13_agents"])
    assert scratch.problems() == []


def test_a_new_cell_added_to_the_lists_passes(scratch):
    scratch.add_cell("olmoe_batch", "olmoe-1b-7b", "batch",
                     like="mixtral_d6_batch")
    assert scratch.problems() == []
    listed = [m["name"] for m in scratch.man["per_layer"]
              if "olmoe_batch" in m.get("workloads", ())]
    assert {"moe.experts_hbm_roofline.batch", "attn.decode_ops_pct.batch",
            "model.decode_step_hbm_roofline.batch"} <= set(listed)


def test_a_configuration_cut_to_one_chips_share_passes(scratch):
    cfg = share_config()
    assert manifest.config_problems(cfg) == []
    add_share(scratch, cfg)
    assert scratch.problems() == []
    # where a program keeps the router's width under n_experts, the experts
    # held here have a field of their own
    local = share_config(reduced={"n_layers": 8, "n_experts_local": 8,
                                  "vocab_size": 6288})
    local["model"].update(n_experts=64, n_experts_local=8)
    assert manifest.config_problems(local) == []


# -- what is refused, by name -------------------------------------------------

@pytest.mark.parametrize("width", [
    "d_model", "d_ff", "moe_d_ff", "head_dim", "n_heads", "n_kv_heads",
    "n_experts_per_token", "kv_lora_rank", "q_lora_rank", "v_head_dim",
    "sliding_window"])
def test_a_width_in_reduced_is_refused(scratch, width):
    cfg = share_config(**{"reduced__" + width: 64})
    found = manifest.config_problems(cfg)
    assert any(f"reduced names {width}" in p and "never a width" in p
               for p in found), found
    add_share(scratch, cfg)
    assert any(SHARE in p and f"reduced names {width}" in p
               for p in scratch.problems())


def test_a_run_refuses_a_width_cut_by_name(scratch):
    """``run.py`` loads its configuration through ``load_config``, and the
    wrapper would apply ``reduced`` as it stands: the file is refused."""
    scratch.write("configs", SHARE, share_config(reduced__d_ff=512))
    with pytest.raises(ValueError, match="reduced names d_ff"):
        manifest.load_config(SHARE)
    scratch.write("configs", SHARE, share_config())
    assert manifest.load_config(SHARE)["reduced"]["n_experts"] == 8


@pytest.mark.parametrize("changes,said", [
    ({"reduced__n_experts": 4}, "4 experts held: under the floor of 8"),
    ({"reduced__vocab_size": 3144},
     "vocab_size 3144: under an eighth of the published 50304"),
    ({"reduced__n_layers": 3},
     "3 layers after the leading dense ones: under the floor of 4"),
    ({"deployment": "one replica on one v5e chip"},
     "a share (experts or vocabulary cut), but deployment does not say"),
    ({"deployment": None, "reduced": {"n_layers": 8, "vocab_size": 6288},
      "model__n_experts": 64},
     "a share (experts or vocabulary cut), but deployment does not say"),
    ({"deployment": "4 chips share each layer"},
     "8 experts held x 4 chips is not the published 64"),
    ({"deployment": "16 chips share each layer", "reduced__n_experts": 4},
     "4 experts held: under the floor"),
    ({"reduced": {"n_layers": 8, "vocab_size": 6288}},
     "8 experts served, 64 published, and the count is not in reduced"),
    ({"reduced": {"n_layers": 8, "n_experts": 8}},
     "vocab_size differs from the published one and is not in reduced"),
    ({"reduced": {"n_experts": 8, "vocab_size": 6288}},
     "n_layers differs from the published count and is not in reduced"),
    ({"published__num_experts": 0},
     "experts are cut but published states no count of them"),
    ({"model__n_experts": 16}, "model.n_experts is not what reduced"),
], ids=["4-experts-held", "a-sixteenth-of-the-vocabulary", "3-layers",
        "a-share-without-a-deployment", "a-slice-without-a-deployment",
        "experts-x-chips-is-not-the-published-count", "16-chips-4-experts",
        "experts-cut-and-not-listed", "vocabulary-cut-and-not-listed",
        "depth-cut-and-not-listed", "no-published-count",
        "model-differs-from-reduced"])
def test_a_share_outside_the_rules_is_refused(scratch, changes, said):
    cfg = share_config(**changes)
    if changes.get("published__num_experts") == 0:
        del cfg["published"]["num_experts"]
    found = manifest.config_problems(cfg)
    assert any(said in p for p in found), found
    add_share(scratch, cfg)
    assert any(SHARE in p and said in p for p in scratch.problems())


@pytest.mark.parametrize("name,cell", [
    ("attn.decode_ops_pct.batch", "glm47flash_d13_agents"),  # runs mla_*
    ("model.decode_step_hbm_roofline.batch", "glm47flash_d13_agents"),
    ("moe.experts_ops_pct.batch", "qwen7b_chat"),  # no expert, wrong arrow
    ("mla.ctx_positions_mean.batch", "mixtral_d6_batch"),  # no latent cache
])
def test_a_cell_that_gives_the_reader_nothing_may_not_be_listed(
        scratch, name, cell):
    entry = next(m for m in scratch.man["per_layer"] if m["name"] == name)
    entry["workloads"].append(cell)
    assert any(f"{name} lists cell {cell}, which gives its reader nothing"
               in p for p in scratch.problems())


def test_every_listed_cell_gives_its_metrics_reader_something_to_read():
    man = manifest.load_manifest()
    cells = {w["name"]: manifest.load_config(w["config"])["model"]
             for w in man["workloads"]}
    for m in man["per_layer"]:
        spec = manifest.load_metric(m["name"])
        for cell in m.get("workloads", ()):
            assert manifest.can_report(spec, cells[cell]), (m["name"], cell)
    glm = cells["glm47flash_d13_agents"]
    assert "mla_decode_attention.1" in manifest.kernels_of(glm)
    assert not any(k.startswith("decode_attention")
                   for k in manifest.kernels_of(glm))
    assert "moe_gmm_int8.1" not in manifest.kernels_of(cells["qwen7b_chat"])


def test_kernel_roofline_is_a_reader_and_roofline_kept_its_arguments():
    assert "kernel_roofline" in readers.READERS
    for name in ("model.decode_step_hbm_roofline",
                 "model.decode_step_hbm_roofline.batch"):
        args = manifest.load_metric(name)["args"]
        assert (args["weights"], args["kv"]) == ("int8", "bfloat16")
        # over every helper program (under 0.1 ms), under olmoe's 4.4 ms step
        assert 0.0005 <= args["min_module_s"] <= 0.005
    assert "experts" not in manifest.load_metric(
        "model.decode_step_hbm_roofline.batch")["args"]


# -- the scratch PR under the tests, unedited ---------------------------------

def test_a_scratch_pr_passes_the_other_test_files_unedited(tmp_path):
    """One appended per-layer entry, one cell and one share-cut configuration,
    made as files and entries alone; then ``tests/benchmark/`` of this tree,
    copied and not edited, runs over it (all but this test, which would
    start itself again, and the CPU rehearsals, which take the machine's
    fixed ports)."""
    s = Scratch(tmp_path)
    shutil.copytree(os.path.join(REPO, "tests", "benchmark"),
                    s.path("..", "tests", "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("llm_instance_gateway_tpu", "pytest.ini"):
        os.symlink(os.path.join(REPO, name), os.path.join(s.root, name))
    here, manifest.HERE = manifest.HERE, s.path()
    try:
        s.add_metric(
            "attn.chunk_ops_pct",
            {"reader": "trace_op_time",
             "args": {"regex": "^chunk_attention", "per": "window"},
             "why": "a scratch PR's metric"}, "tpot_p50_ms", OPEN)
        add_share(s, share_config(name=SHARE + ".pr"), SHARE + ".pr",
                  CELL + ".pr")
        assert s.problems() == []
    finally:
        manifest.HERE = here
    s.save()
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-m",
         "not e2e", "-p", "no:cacheprovider", "-p", "no:randomly",
         "--deselect", "tests/benchmark/test_bench_rules.py::"
         "test_a_scratch_pr_passes_the_other_test_files_unedited"],
        cwd=s.root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    tail = r.stdout[-3000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    assert " passed" in r.stdout and "failed" not in r.stdout, tail
