"""The operator docs name only what the tree holds.

README.md, ARCHITECTURE.md and MIGRATION.md send a reader to ``make``
targets, ``tools/*.py`` scripts, committed root ``*.json`` records and
``python -m llm_instance_gateway_tpu.<module>`` entry points.  A file that
is deleted while a doc still cites it as evidence fails here, not in front
of the reader."""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = "llm_instance_gateway_tpu"
DOCS = ("README.md", "ARCHITECTURE.md", "MIGRATION.md")


def _code(text: str) -> list[str]:
    """Inline code spans and fenced-block lines (commands live in code;
    prose says "make decode faster")."""
    fenced = re.findall(r"^```.*?$(.*?)^```", text, flags=re.S | re.M)
    rest = re.sub(r"^```.*?^```", "", text, flags=re.S | re.M)
    spans = [" ".join(s.split()) for s in re.findall(r"`([^`]+)`", rest)]
    return spans + [line for block in fenced for line in block.splitlines()]


def _make_targets() -> set[str]:
    return set(re.findall(r"^([a-z][\w-]*):", (REPO / "Makefile").read_text(),
                          flags=re.M))


def _module_exists(dotted: str) -> bool:
    path = REPO.joinpath(*dotted.split("."))
    return (path.with_suffix(".py").is_file()
            or (path / "__main__.py").is_file())


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_what_exists(doc):
    text = (REPO / doc).read_text()
    missing = []

    targets = _make_targets()
    for code in _code(text):
        for run in re.findall(r"\bmake((?: +[a-z][\w-]*)+)", code):
            missing += [f"make {t}" for t in run.split() if t not in targets]

    for name in set(re.findall(r"\btools/(\w+\.py)\b", text)):
        if not ((REPO / "tools" / name).is_file()
                or (REPO / PKG / "tools" / name).is_file()):
            missing.append(f"tools/{name}")

    # committed records at the root are named in capitals (BENCHMARK.json,
    # KV_BASELINE.json); lower-case names are a reader's own files
    for name in set(re.findall(
            r"(?<![\w/.-])([A-Z][A-Z0-9]*(?:_\w+)*\.json)\b", text)):
        if not (REPO / name).is_file():
            missing.append(name)

    for dotted in set(re.findall(rf"python3? -m ({PKG}(?:\.\w+)+)", text)):
        if not _module_exists(dotted):
            missing.append(f"python -m {dotted}")

    assert not missing, f"{doc} names what the tree does not hold: {missing}"


def test_the_scan_finds_what_it_is_for():
    """The patterns see the kinds of names the docs really use, so an empty
    ``missing`` above means "all there", not "nothing matched"."""
    text = "\n".join((REPO / d).read_text() for d in DOCS)
    code = "\n".join(_code(text))
    assert "sim-check" in re.findall(r"\bmake +([a-z][\w-]*)", code)
    assert "profile_report.py" in re.findall(r"\btools/(\w+\.py)\b", text)
    assert "TWIN_CALIBRATION.json" in re.findall(
        r"(?<![\w/.-])([A-Z][A-Z0-9]*(?:_\w+)*\.json)\b", text)
    assert f"{PKG}.gateway.loadgen" in re.findall(
        rf"python3? -m ({PKG}(?:\.\w+)+)", text)
    assert not _module_exists(f"{PKG}.no_such_module")
