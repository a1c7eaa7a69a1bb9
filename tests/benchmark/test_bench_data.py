"""The benchmark's manifest and data files: they load, cross-reference and
keep to the contract's names.  (The harness itself is stdlib-only; these
tests run in the suite's process.)"""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers, traffic  # noqa: E402

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MAN) == KEYS
    assert 1 <= MAN["run_seconds"] <= 51
    assert MAN["command"][:2] == ["python3", "benchmark/run.py"]
    size = os.path.getsize(os.path.join(REPO, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_manifest_cross_references():
    assert manifest.problems(MAN) == []


def test_problems_sees_a_broken_arrow():
    broken = json.loads(json.dumps(MAN))
    broken["per_layer"][0]["moves"] = "output_tok_s"
    assert any("does not report" in p for p in manifest.problems(broken))


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(REPO, "benchmark", "*", "*.json"))),
    ids=lambda p: os.path.relpath(p, REPO))
def test_data_file_loads_and_is_named_by_the_contract(path):
    with open(path) as f:
        assert isinstance(json.load(f), dict)
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", os.path.relpath(path, REPO))


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MAN["end_to_end"]:
        allowed |= {"bound"}
    else:
        allowed |= {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in MAN["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    assert set(metric) <= allowed
    assert manifest.NAME.match(metric["name"])
    assert manifest.UNIT.match(metric["unit"])
    spec = manifest.load_metric(metric["name"])
    assert spec["reader"] in readers.READERS


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in manifest.metrics_of(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_of(MAN, cell, "per_layer")
    w = manifest.cell(MAN, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    cfg = manifest.load_config(config["name"])
    # Counts may be cut (the depth, the experts held here, the vocabulary's
    # slice), never a width; a share states the published count, keeps the
    # floors and says how many chips share a layer (manifest.config_problems).
    assert set(cfg["reduced"]) <= set(manifest.REDUCIBLE)
    assert manifest.config_problems(cfg) == []
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    pub, model = cfg["published"], cfg["model"]
    assert model["d_model"] == pub["hidden_size"]
    assert model["d_ff"] == pub["intermediate_size"]
    assert model["n_heads"] == pub["num_attention_heads"]
    assert model["n_kv_heads"] == pub["num_key_value_heads"]
    assert model["head_dim"] * model["n_heads"] in (
        pub["hidden_size"], model["head_dim"] * pub["num_attention_heads"])
    for key in ("n_layers", "vocab_size"):
        source_key = {"n_layers": "num_hidden_layers"}.get(key, key)
        if key in cfg["reduced"]:
            assert model[key] == cfg["reduced"][key] <= pub[source_key]
        else:
            assert model[key] == pub[source_key]
    held = next((model[k] for k in manifest.EXPERTS_HELD if k in model), 0)
    if not set(manifest.EXPERTS_HELD) & set(cfg["reduced"]):
        # the source's own key: num_local_experts, num_experts or
        # n_routed_experts, whichever it has
        assert held == manifest.published_experts(pub)
        assert len([k for k in manifest.PUBLISHED_EXPERTS if k in pub]) <= 1


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 4)


def test_the_check_fits_its_time_limit_with_all_24_cells():
    runs = 2 + 14 * 24
    total = runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_parent_process_stays_off_jax():
    """A parent that touched JAX would hold the chip."""
    subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); import benchmark.run, "
         "benchmark.readers, benchmark.traffic, benchmark.client, "
         "benchmark.manifest, benchmark.trace_reduce; "
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules"],
        cwd=REPO, check=True, timeout=60)


# -- the generator -----------------------------------------------------------

MIXES = sorted({w["traffic"] for w in MAN["workloads"]})


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    t = manifest.load_traffic(mix)
    a = traffic.build_requests(t, 2147483999, 20)
    b = traffic.build_requests(t, 2147483999, 20)
    assert a == b
    c = traffic.build_requests(t, 5, 20)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_another_order(mix):
    t = manifest.load_traffic(mix)
    a = traffic.build_requests(t, 1, 30)
    b = traffic.build_requests(t, 2**31 + 7, 30)
    if t.get("edges") == "periodic":  # the same work in the window
        a = [r for r in a if 0 <= r.due_s < 30]
        b = [r for r in b if 0 <= r.due_s < 30]
    sizes = lambda rs: sorted(  # noqa: E731
        (r.prompt_tokens, r.max_tokens, -1 if r.adapter is None else r.adapter)
        for r in rs)
    assert sizes(a) == sizes(b) and len(a) == len(b)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_tokens <= hi for r in a)
    assert all(len(r.prompt) == r.prompt_tokens - 1 for r in a)
    if t["loop"] == "open" and t.get("edges", "cut") == "cut":
        ramp = t["ramp_s"]
        assert len(a) == round(t["rate_rps"] * (ramp + 30))
        assert a[0].due_s == -ramp and a[-1].due_s < 30
    if t["loop"] == "open":
        assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("seed", [0, 1, 17, 2**31 + 5, 3000000019])
def test_periodic_edges_measure_the_whole_cycle_once_for_every_seed(seed):
    t = dict(manifest.load_traffic("doc"), edges="periodic", ramp_s=6,
             tail_s=8)
    size = lambda r: (r.prompt_tokens, r.max_tokens)  # noqa: E731
    window = lambda rs: [r for r in rs if 0 <= r.due_s < 40]  # noqa: E731
    ref = traffic.build_requests(t, 0, 40)
    rs = traffic.build_requests(t, seed, 40)
    assert len(window(rs)) == round(t["rate_rps"] * 40)
    assert sorted(map(size, window(rs))) == sorted(map(size, window(ref)))
    assert window(rs)[0].due_s == 0.0
    # the neighbours: the cycle's end before the window, its start after it
    before = [r for r in rs if r.due_s < 0]
    after = [r for r in rs if r.due_s >= 40]
    assert before and after
    assert -6 <= before[0].due_s and after[0].due_s == 40.0
    assert after[-1].due_s < 48
    assert list(map(size, before)) == list(map(size, window(rs)))[-len(before):]
    assert list(map(size, after)) == list(map(size, window(rs)))[:len(after)]
    assert all(x.due_s <= y.due_s for x, y in zip(rs, rs[1:]))


def test_unknown_edges_are_refused():
    t = dict(manifest.load_traffic("doc"), edges="mirror")
    with pytest.raises(ValueError):
        traffic.build_requests(t, 1, 40)


def test_adapter_share_and_zipf_of_chat():
    t = manifest.load_traffic("chat")
    rs = traffic.build_requests(dict(t, rate_rps=50.0), 1, 60)
    tuned = [r.adapter for r in rs if r.adapter is not None]
    assert 0.4 < len(tuned) / len(rs) < 0.6
    counts = [tuned.count(i) for i in range(4)]
    assert counts[0] > counts[1] > counts[3]


def test_payload_addresses_adapters_by_their_inference_model():
    t = manifest.load_traffic("chat")
    rs = traffic.build_requests(t, 1, 30)
    tuned = ["bench-tuned-%d" % i for i in range(4)]
    for r in rs:
        body = traffic.payload(r, "base", tuned, True)
        assert body["model"] == ("base" if r.adapter is None
                                 else tuned[r.adapter])
        assert body["stream"] is True and body["temperature"] == 0
        assert len(body["logit_bias"]) == 32


@pytest.mark.parametrize("lo,hi,want", [
    (16, 512, [16, 32, 64, 128, 256, 512]),
    (512, 1792, [512, 1024, 1792]),
    (64, 1024, [64, 128, 256, 512, 1024]),
    (20, 200, [32, 64, 128, 200]),
    (1025, 1100, [1100]),
])
def test_prefill_shapes_touch_every_bucket_the_mix_can_meet(lo, hi, want):
    mix = {"prompt_tokens": {"dist": "lognormal", "min": lo, "max": hi}}
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert traffic.prefill_shapes(mix, buckets) == want


def test_burst_arrivals_keep_the_mean_rate():
    import random

    mix = {"rate_rps": 5.0, "arrival": "burst"}
    gaps = traffic.arrival_gaps(mix, 5000, random.Random(1))
    assert 0.85 < (len(gaps) / sum(gaps)) / 5.0 < 1.15
