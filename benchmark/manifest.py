"""``BENCHMARK.json`` and the data files it names, found by name:

    benchmark/configs/<config>.json   a configuration: source, sizes, server
    benchmark/traffic/<traffic>.json  a traffic mix: parameters of the one
                                      generator (benchmark/traffic.py)
    benchmark/metrics/<metric>.json   a metric: one reader and its arguments

A later PR adds a cell, a configuration, a mix or a metric by adding files
and entries and edits none.  ``problems`` is the cross-check the tests run.

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


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str) -> str:
    return os.path.join(HERE, "configs", name + ".json")


def load_config(name: str) -> dict:
    return _load(config_file(name))


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
