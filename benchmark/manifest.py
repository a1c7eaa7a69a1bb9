"""``BENCHMARK.json`` and the data files it names, found by name:

    benchmark/configs/<config>.json   a configuration: source, sizes, server
    benchmark/traffic/<traffic>.json  a traffic mix: parameters of the one
                                      generator (benchmark/traffic.py)
    benchmark/metrics/<metric>.json   a metric: one reader and its arguments

A later PR adds a cell, a configuration, a mix or a metric by adding files
and entries and edits none.  ``problems`` is the cross-check the tests run;
``load_config`` refuses a configuration whose ``reduced`` breaks the rules, so
a run refuses it too.  The rules are stated for ANY entry (what ``reduced``
may hold, which cells a metric may list), never for the entries of one day.

Stdlib only.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# What ``reduced`` may hold: counts, never a width.  The depth; how many
# experts are held here (``n_experts``, or ``n_experts_local`` where a program
# keeps the router's published width under ``n_experts``); the vocabulary's
# slice.  The floors are the model-configs guide's (section 4).
EXPERTS_HELD = ("n_experts_local", "n_experts")
REDUCIBLE = ("n_layers", "vocab_size") + EXPERTS_HELD
PUBLISHED_EXPERTS = ("num_local_experts", "num_experts", "n_routed_experts")
MIN_EXPERTS_HELD, MIN_VOCAB_SHARE, MIN_LAYERS = 8, 8, 4
SHARED_BY = re.compile(r"(\d+) chips share (?:a|each|every) layer")
# Device operations a traced window holds, and which models run each: XLA's
# own, and the Pallas kernels by their ``pl.pallas_call(name=)``.  A model
# is "latent" (``kv_lora_rank``) or keeps per-head "lanes", and is "sparse"
# where it has experts.
KERNELS = (
    ("fusion.1", None), ("copy.1", None), ("reshape.1", None),
    ("dynamic-slice_fusion", None), ("dynamic-update-slice_fusion", None),
    ("while.1", None), ("flash_attention.1", None),
    ("chunk_attention.1", None), ("mla_decode_attention.1", "latent"),
    ("decode_attention.1", "lanes"), ("decode_attention_int8.1", "lanes"),
    ("moe_gmm.1", "sparse"), ("moe_gmm_int8.1", "sparse"))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str) -> str:
    return os.path.join(HERE, "configs", name + ".json")


def load_config(name: str) -> dict:
    """The configuration file, refused (ValueError) where its cut breaks the
    rules of ``config_problems``: a run never serves a width cut by name."""
    cfg = _load(config_file(name))
    try:
        bad = config_problems(cfg)
    except KeyError as e:
        raise ValueError(f"configs/{name}.json lacks {e}") from None
    if bad:
        raise ValueError(f"configs/{name}.json: " + "; ".join(bad))
    return cfg


def load_traffic(name: str) -> dict:
    return _load(os.path.join(HERE, "traffic", name + ".json"))


def load_metric(name: str) -> dict:
    return _load(os.path.join(HERE, "metrics", name + ".json"))


def section(config: dict, rehearse: bool) -> dict:
    """The configuration as run: on the chip the file itself, in a CPU
    rehearsal the file with its ``rehearsal`` group laid over it."""
    return dict(config, **config["rehearsal"]) if rehearse else config


def cell(manifest: dict, workload: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json (known: "
                   f"{[w['name'] for w in manifest['workloads']]})")


def metrics_of(manifest: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``workload``
    reports: those that list it, and those that list no cells."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def published_experts(published: dict) -> int:
    """The source's count of routed experts, under the source's own key."""
    for key in PUBLISHED_EXPERTS:
        if key in published:
            return published[key]
    return 0


def config_problems(cfg: dict) -> list[str]:
    """What is wrong with one configuration file's cut: ``reduced`` holds
    counts and never a width; a share (experts held here, a slice of the
    vocabulary) states the published count beside it, keeps the floors and
    says in ``deployment`` how many chips share a layer; where nothing is
    cut the served count is the published one."""
    bad: list[str] = []
    reduced, model, pub = cfg["reduced"], cfg["model"], cfg["published"]
    for key, value in reduced.items():
        if key not in REDUCIBLE:
            bad.append(f"reduced names {key}: only counts may be cut "
                       f"({', '.join(REDUCIBLE)}), never a width")
        elif model.get(key) != value:
            bad.append(f"model.{key} is not what reduced.{key} says")
    if "n_layers" in reduced:
        sparse_or_all = model["n_layers"] - model.get("first_k_dense", 0)
        if sparse_or_all < MIN_LAYERS:
            bad.append(f"{sparse_or_all} layers after the leading dense "
                       f"ones: under the floor of {MIN_LAYERS}")
        if model["n_layers"] > pub["num_hidden_layers"]:
            bad.append("more layers than published")
    elif model["n_layers"] != pub["num_hidden_layers"]:
        bad.append("n_layers differs from the published count and is not "
                   "in reduced")
    m = SHARED_BY.search(cfg.get("deployment") or "")
    chips = int(m.group(1)) if m else None
    held_key = next((k for k in EXPERTS_HELD if k in model), None)
    held = model[held_key] if held_key else 0
    want = published_experts(pub)
    if (held_key in reduced or "vocab_size" in reduced) and chips is None:
        bad.append("a share (experts or vocabulary cut), but deployment does "
                   "not say how many chips share a layer ('<n> chips share a "
                   "layer')")
    if held_key in reduced:
        if not want:
            bad.append("experts are cut but published states no count of "
                       f"them (under one of {', '.join(PUBLISHED_EXPERTS)})")
        if held < MIN_EXPERTS_HELD:
            bad.append(f"{held} experts held: under the floor of "
                       f"{MIN_EXPERTS_HELD}")
        if chips and want and held * chips != want:
            bad.append(f"{held} experts held x {chips} chips is not the "
                       f"published {want}")
    elif held != want:
        bad.append(f"{held} experts served, {want} published, and the count "
                   "is not in reduced")
    if "vocab_size" in reduced:
        if model["vocab_size"] * MIN_VOCAB_SHARE < pub["vocab_size"]:
            bad.append(f"vocab_size {model['vocab_size']}: under an eighth "
                       f"of the published {pub['vocab_size']}")
        if model["vocab_size"] > pub["vocab_size"]:
            bad.append("a larger vocabulary than published")
        if chips and model["vocab_size"] * chips < pub["vocab_size"]:
            bad.append(f"vocab_size {model['vocab_size']} x {chips} chips "
                       f"is under the published {pub['vocab_size']}")
    elif model["vocab_size"] != pub["vocab_size"]:
        bad.append("vocab_size differs from the published one and is not "
                   "in reduced")
    return bad


def traits(model: dict) -> set[str]:
    return {"latent" if model.get("kv_lora_rank") else "lanes"} | (
        {"sparse"} if model.get("n_experts") else set())


def kernels_of(model: dict) -> list[str]:
    """Names of the device operations a traced window of this model holds,
    as far as ``KERNELS`` knows the program."""
    have = traits(model)
    return [name for name, needs in KERNELS if needs is None or needs in have]


def can_report(spec: dict, model: dict) -> bool:
    """Whether a cell of this model gives the metric file's reader something
    to read, as far as the rule can know: a trace regex that finds one of the
    kernels this table knows has to find one that the model runs (a regex
    for a kernel the table does not know is not judged), a ``tpu:moe_*``
    counter needs experts and a ``tpu:latent_*`` counter a latent cache, and
    ``roofline`` (``shapes.py``) counts per-head lanes."""
    args, have = spec.get("args", {}), traits(model)
    if spec["reader"] == "roofline" and "latent" in have:
        return False
    regex = args.get("regex")
    if regex:
        known = [name for name, _ in KERNELS if re.search(regex, name)]
        if known and not set(known) & set(kernels_of(model)):
            return False
    counters = [args.get("num"), args.get("den")]
    counters += list(args.get("inputs", {}).values())
    for c in counters:
        family = c.get("family", "") if isinstance(c, dict) else ""
        if family.startswith("tpu:moe_") and "sparse" not in have:
            return False
        if family.startswith("tpu:latent_") and "latent" not in have:
            return False
    return True


def bytes_fn_problems(bytes_fn: str) -> list[str]:
    """``kernel_roofline``'s ``"<module>:<function>"`` has to name a function
    of a file under ``benchmark/`` (read as text: nothing is imported)."""
    module, _, function = bytes_fn.partition(":")
    if not (NAME.match(module) and function.isidentifier()):
        return [f"bytes_fn {bytes_fn!r} is not '<module>:<function>'"]
    try:
        with open(os.path.join(HERE, module + ".py")) as f:
            text = f.read()
    except OSError:
        return [f"bytes_fn: no benchmark/{module}.py"]
    if not re.search(rf"^def {function}\(", text, re.M):
        return [f"bytes_fn: benchmark/{module}.py defines no {function}"]
    return []


def problems(manifest: dict) -> list[str]:
    """Everything wrong with the manifest and its data files, as text."""
    from benchmark import readers

    bad: list[str] = []
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = ([w["name"] for w in manifest["workloads"]]
             + list(configs) + [m["name"] for m in manifest["end_to_end"]]
             + [m["name"] for m in manifest["per_layer"]])
    models = {}  # by cell; a configuration that does not load is reported
    for w in manifest["workloads"]:  # with its cell, below
        try:
            models[w["name"]] = load_config(w["config"])["model"]
        except (OSError, ValueError, KeyError):
            pass
    for n in names:
        if not NAME.match(n):
            bad.append(f"name {n!r} uses characters outside the contract")
    for kind in ("end_to_end", "per_layer"):
        seen = set()
        for m in manifest[kind]:
            if m["name"] in seen or (kind == "per_layer"
                                     and m["name"] in e2e):
                bad.append(f"metric {m['name']} named twice")
            seen.add(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"better of {m['name']}")
            if m["source"] not in SOURCES:
                bad.append(f"source of {m['name']}")
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append(f"{m['name']} lists unknown cell {w}")
            try:
                spec = load_metric(m["name"])
                if spec["reader"] not in readers.READERS:
                    bad.append(f"{m['name']}: unknown reader "
                               f"{spec['reader']!r}")
                if spec["reader"] == "kernel_roofline":
                    bad += [f"{m['name']}: {p}" for p in bytes_fn_problems(
                        spec["args"]["bytes_fn"])]
                for w in m.get("workloads", ()):
                    if w in models and not can_report(spec, models[w]):
                        bad.append(f"{m['name']} lists cell {w}, which "
                                   "gives its reader nothing to read")
            except (OSError, ValueError, KeyError) as e:
                bad.append(f"metrics/{m['name']}.json: {e}")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']} from {m['source']}")
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"bound of {m['name']}")
    for m in manifest["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        for w in m.get("workloads", cells):
            if "workloads" in moved and w not in moved["workloads"]:
                bad.append(f"{m['name']} moves {m['moves']}, which cell "
                           f"{w} does not report")
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
            continue
        try:
            cfg = load_config(w["config"])
            load_traffic(w["traffic"])
        except (OSError, ValueError) as e:
            bad.append(f"cell {w['name']}: {e}")
            continue
        if cfg["chips"] != w["chips"]:
            bad.append(f"cell {w['name']}: chips differ from its config's")
        if len(w["why"]) > 200:
            bad.append(f"cell {w['name']}: why over 200 characters")
        if len(metrics_of(manifest, w["name"], "end_to_end")) < 2:
            bad.append(f"cell {w['name']} reports no metric beside setup_s")
        if not metrics_of(manifest, w["name"], "per_layer"):
            bad.append(f"cell {w['name']} reports no per-layer metric")
    for c in manifest["configs"]:
        if c["file"] != f"benchmark/configs/{c['name']}.json":
            bad.append(f"config {c['name']}: file is not found by its name")
        try:
            cfg = load_config(c["name"])
        except (OSError, ValueError) as e:
            bad.append(f"config {c['name']}: {e}")
            continue
        if sorted(cfg["reduced"]) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
        if cfg["source"] != c["source"]:
            bad.append(f"config {c['name']}: source differs from its file")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']} is used by no cell")
    return bad
