"""``tpu:moe_tiles_used_total`` (PR 44), from the program to the benchmark's
line: the family is registered and rendered beside the three it joins, the
metric ``moe.tiles_per_expert_mean.batch`` reads it over the touched experts
in the two sparse closed-loop cells, a program without the counter (the
parent) gives the reader nothing to read and no error, and the on-chip
tool's two layouts of the same rows take the tiles it says they take.
``tpu:moe_tiles_laid_out_total`` (PR 61) beside it: the layout's static tile
count a layer-step, of which the expert matmul's grid walks the used."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from benchmark import manifest, readers  # noqa: E402
from llm_instance_gateway_tpu import metrics_registry  # noqa: E402
from llm_instance_gateway_tpu.ops import pallas_moe  # noqa: E402
from llm_instance_gateway_tpu.server import profiler  # noqa: E402

NAME = "moe.tiles_per_expert_mean.batch"
BEFORE = """tpu:moe_layer_steps_total 12
tpu:moe_experts_touched_total 80
tpu:moe_tiles_used_total 100
"""
AFTER = """tpu:moe_layer_steps_total 1212
tpu:moe_experts_touched_total 8480
tpu:moe_tiles_used_total 11020
"""


def read(ctx):
    spec = manifest.load_metric(NAME)
    return readers.READERS[spec["reader"]](spec.get("args", {}), ctx)


def test_the_family_is_registered_and_rendered_with_the_tally():
    families = {f.name for f in metrics_registry.SERVER_FAMILIES}
    assert "tpu:moe_tiles_used_total" in families
    assert profiler.MOE_COUNTERS[3] == "tiles_used"
    hist = {"moe": dict(zip(profiler.MOE_COUNTERS, (6, 384, 42, 51)))}
    text = "\n".join(profiler.render_profile(hist))
    assert "tpu:moe_tiles_used_total 51" in text
    assert "tpu:moe_experts_touched_total 42" in text


def test_the_laid_out_family_is_registered_and_rendered_beside_the_used():
    families = {f.name for f in metrics_registry.SERVER_FAMILIES}
    assert "tpu:moe_tiles_laid_out_total" in families
    assert profiler.MOE_COUNTERS[5] == "tiles_laid_out"
    hist = {"moe": dict(zip(profiler.MOE_COUNTERS,
                            (6, 384, 42, 51, 384, 6 * 76)))}
    text = "\n".join(profiler.render_profile(hist)) + "\n"
    assert "# TYPE tpu:moe_tiles_laid_out_total counter\n" in text
    assert "tpu:moe_tiles_laid_out_total 456\n" in text
    assert "tpu:moe_tiles_used_total 51\n" in text
    # a program from before the counter renders the five it has
    old = {"moe": dict(zip(profiler.MOE_COUNTERS[:5], (6, 384, 42, 51, 384)))}
    assert "tiles_laid_out" not in "\n".join(profiler.render_profile(old))


@pytest.mark.parametrize("program,tokens", [("decode", 4), ("prompt", 7),
                                            ("prompt", 40)])
def test_the_sixth_count_is_the_layouts_tiles_times_the_layer_steps(
        program, tokens):
    """A decode step over ``tokens`` slots (one of them dead) and a prompt of
    ``tokens`` positions: every sparse layer lays out ``n_tiles`` tiles
    whatever the routing, and uses at most that."""
    import jax
    import jax.numpy as jnp

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_OLMOE_TEST as cfg

    assert transformer.MOE_TALLY[5] == "tiles_laid_out"
    params = transformer.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
    ids = (jnp.arange(tokens, dtype=jnp.int32) * 7 + 3) % cfg.vocab_size
    if program == "decode":
        cache = transformer.with_moe_tally(cfg, transformer.init_decode_cache(
            cfg, tokens, 16, dtype=jnp.float32))
        active = jnp.arange(tokens) > 0
        _, cache = transformer.decode_step(
            cfg, params, cache, ids, jnp.zeros((tokens,), jnp.int32),
            active=active)
        tally = cache["moe"]
    else:
        tally = transformer.prefill(
            cfg, params, ids[None], jnp.arange(tokens, dtype=jnp.int32)[None],
            lengths=jnp.asarray([tokens], jnp.int32), moe_tally=True)[-1]
    k, e = cfg.n_experts_per_token, cfg.n_experts
    tiles = pallas_moe.n_tiles(tokens * k, e,
                               pallas_moe.tile_rows(tokens * k, e))
    counts = dict(zip(transformer.MOE_TALLY, (int(v) for v in tally)))
    assert counts["layer_steps"] == cfg.n_layers
    assert counts["tiles_laid_out"] == tiles * cfg.n_layers
    assert 0 < counts["tiles_used"] <= counts["tiles_laid_out"]
    live = tokens - (program == "decode")
    assert counts["assignments"] == live * k * cfg.n_layers


def test_the_metric_reads_tiles_over_touched_experts():
    ctx = {"prom_before": [BEFORE], "prom_after": [AFTER], "window_s": 40.0}
    assert read(ctx) == pytest.approx((11020 - 100) / (8480 - 80))
    # two replicas: sums over sums
    assert read({**ctx, "prom_before": [BEFORE] * 2,
                 "prom_after": [AFTER] * 2}) == pytest.approx(1.3)


def test_a_program_without_the_counter_reads_nothing():
    strip = lambda text: "\n".join(  # noqa: E731
        line for line in text.splitlines() if "tiles_used" not in line) + "\n"
    parent = {"prom_before": [strip(BEFORE)], "prom_after": [strip(AFTER)],
              "window_s": 40.0}
    assert read(parent) is None
    dense = {"prom_before": ["tpu:x 1\n"], "prom_after": ["tpu:x 2\n"],
             "window_s": 40.0}
    assert read(dense) is None


def test_the_entry_lists_the_sparse_closed_loops():
    man = manifest.load_manifest()
    assert manifest.problems(man) == []
    entry = next(m for m in man["per_layer"] if m["name"] == NAME)
    # A rule, as PR 41 made of the pins of ``tests/benchmark/``: the entry as
    # it came (PR 44), wherever later entries put it, and its list every
    # sparse closed loop, the two it came with first.
    assert dict(entry, workloads=entry["workloads"][:2]) == {
        "name": NAME, "unit": "tiles", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "output_tok_s",
        "workloads": ["mixtral_d6_batch", "glm47flash_d13_agents"]}
    assert entry["workloads"] == [
        c["name"] for c in man["workloads"]
        if c["name"] in next(m for m in man["end_to_end"]
                             if m["name"] == "output_tok_s")["workloads"]
        and manifest.load_config(c["config"])["model"].get("n_experts")]
    for cell in entry["workloads"]:
        model = manifest.load_config(manifest.cell(man, cell)["config"])[
            "model"]
        assert manifest.can_report(manifest.load_metric(NAME), model)


@pytest.fixture(scope="module")
def tool():
    import onchip_pallas_check
    return onchip_pallas_check


@pytest.mark.parametrize("shape", range(11))
def test_the_tools_two_layouts_hold_the_same_rows_in_more_tiles(tool, shape):
    """``moe-reuse``: every touched group in one tile, and the same rows
    with two groups in three and two tiles, in the layout the assignments
    are sized for (Ling's holds a quarter of them); the last shape touches
    nothing and uses no tile."""
    _, e, k, n, m, touched, held = tool.MOE_REUSE_SHAPES[shape]
    assert not pallas_moe.shape_reasons(k, n)
    tm = pallas_moe.tile_rows(m, e)
    tiles = pallas_moe.n_tiles(m, e, tm)
    used = []
    for skewed in (False, True):
        sizes = tool._group_sizes(held, e, touched, tm, skewed)
        assert int(sizes.sum()) == held <= m
        assert int((sizes > 0).sum()) == touched
        _, te, n_used = pallas_moe.tile_plan(sizes, tm, tiles)
        used.append(int(n_used))
    assert used == ([touched, touched + 3] if touched else [0, 0])
    assert used[1] <= tiles
    # the steps the tool prints are the kernel's own: its grid under the
    # chip's bound and under the interpreter's
    tk, tn = pallas_moe._blocks(k, n, 1)
    by_group = pallas_moe._by_group(tiles * tm, k, n, tn, 1)
    steps, n_live = pallas_moe._steps(te, n_used, e, by_group)
    assert tool._moe_grid_steps(k, n, e, tm, tiles, used[1], touched) == tuple(
        int(np.prod(pallas_moe._grid(n, tn, k // tk, bound, by_group)))
        for bound in (int(n_live), steps[0].shape[0]))


def test_the_tools_shapes_are_the_sparse_cells_decode_layouts(tool):
    """The layouts the issue's table reckons with: Ling's 152 tiles of 16,
    SmallThinker's 72, GLM's 68, LFM2's and OLMoE's 76, Mixtral's 11; and
    every one by tile but Mixtral's."""
    assert len(tool.MOE_REUSE_SHAPES) == 11
    layouts = {}
    for label, e, k, n, m, _, _ in tool.MOE_REUSE_SHAPES:
        tm = pallas_moe.tile_rows(m, e)
        tiles = pallas_moe.n_tiles(m, e, tm)
        tk, tn = pallas_moe._blocks(k, n, 1)
        assert pallas_moe._by_group(tiles * tm, k, n, tn, 1) == (
            label.startswith("mixtral"))
        layouts[label.split()[0]] = (tiles, tm)
    assert layouts == {
        "mixtral": (11, 16), "olmoe": (76, 16), "glm-4.7-flash": (68, 16),
        "ling-3.0-flash": (152, 16), "smallthinker": (72, 16),
        "lfm2": (76, 16)}


# ``moe-dispatch``: (assignments, layout rows, d_model) of each shape the
# tool times, as the cells' programs make them (ISSUE 46), and whether the
# model gathers there.
DISPATCH_LAYOUTS = {
    "smallthinker chunk 1024x6": (6144, 14208, 2560, True),
    "glm-4.7-flash chunk 1024x4": (4096, 12160, 2048, True),
    "mixtral prompt 1024x2": (2048, 2944, 4096, True),
    "olmoe prompt 64x8": (512, 1472, 2048, True),
    "smallthinker prompt 64x6": (384, 1344, 2560, True),
    "mixtral prompt 128x2": (256, 704, 4096, False),
    "olmoe decode 32x8": (256, 1216, 2048, False),
    "mixtral decode 32x2": (64, 176, 4096, False),
    "glm-4.7-flash decode 32x4": (128, 1088, 2048, False),
    "smallthinker decode 32x6": (192, 1152, 2560, False),
}


@pytest.mark.parametrize("shape", range(len(DISPATCH_LAYOUTS)))
def test_the_tools_dispatch_shapes_are_the_cells_layouts(tool, shape):
    from llm_instance_gateway_tpu.models import transformer

    label, t, k, e, d = tool.MOE_DISPATCH_SHAPES[shape]
    tm = pallas_moe.tile_rows(t * k, e)
    rows = pallas_moe.n_tiles(t * k, e, tm) * tm
    assert (t * k, rows, d, transformer._gathers_in(t * k, e)) == (
        DISPATCH_LAYOUTS[label])


@pytest.mark.parametrize("t", [8, 40])
def test_the_tools_dispatch_case_compares_the_two_layouts(tool, t, capsys):
    """The case's program runs here at a small width, a decode batch with
    dead rows and a prompt: it returns both layouts, equal and not empty,
    and its line names the form the model takes."""
    import numpy as np

    got, want, tol = tool.case_moe_dispatch(t, 8, 64, 128, calls=2, runs=1)
    assert tol == 0.0 and tool._scaled_err(got, want) == 0.0
    assert np.asarray(want, np.float32).any()
    line = capsys.readouterr().out
    assert f"moe-dispatch {t * 8} assignments" in line
    assert ("takes the gather" if t == 40 else "takes the scatter") in line
