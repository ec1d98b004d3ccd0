"""Docs-consistency gate (DESIGN.md §10 satellite, wired into CI):

  * every ``DESIGN.md §N`` citation anywhere under src/repro/** (and in
    examples/ and README.md) must resolve to a real ``## §N`` heading
    in DESIGN.md — docstrings are the §-citation index of this repo, so
    a dangling citation means a section was renumbered or never written;
  * README code snippets must name real things: ``python -m <module>``
    targets and ``from <module> import <names>`` lines resolve, example
    script paths exist, and CLI ``--flags`` shown next to a launcher
    are actually defined by that launcher's argparse;
  * docs/BENCHMARKS.md names every cell, configuration and metric that
    BENCHMARK.json declares.
"""
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
CITATION = re.compile(r"DESIGN\.md §(\d+)")
HEADING = re.compile(r"^## §(\d+)\b", re.M)


def _design_sections():
    return {int(n) for n in HEADING.findall((REPO / "DESIGN.md").read_text())}


def _cited(path: Path):
    return {int(n) for n in CITATION.findall(path.read_text())}


def test_design_citations_resolve():
    """Every DESIGN.md §N citation in the source tree hits a real
    heading."""
    sections = _design_sections()
    assert sections, "DESIGN.md has no '## §N' headings?"
    files = (
        list((REPO / "src" / "repro").rglob("*.py"))
        + list((REPO / "examples").glob("*.py"))
        + [REPO / "README.md"]
    )
    assert len(files) > 40  # the walk actually walked
    dangling = {}
    for f in files:
        missing = _cited(f) - sections
        if missing:
            dangling[str(f.relative_to(REPO))] = sorted(missing)
    assert not dangling, f"citations without a DESIGN.md heading: {dangling}"


def test_design_sections_are_contiguous():
    """Section numbers form 1..N with no gaps — renumbering hazards
    surface here instead of as silently-wrong citations."""
    sections = _design_sections()
    assert sections == set(range(1, max(sections) + 1))


# -- README snippet reality ---------------------------------------------------

def _readme_blocks():
    text = (REPO / "README.md").read_text()
    return re.findall(r"```[a-z]*\n(.*?)```", text, re.S)


def _module_path_exists(mod: str) -> bool:
    for root in (REPO / "src", REPO):
        p = root.joinpath(*mod.split("."))
        if (
            p.with_suffix(".py").is_file()
            or (p / "__init__.py").is_file()
            or p.is_dir()
        ):
            return True
    return False


def test_readme_modules_exist():
    """Every ``python -m X`` target, ``from X import ...`` module and
    ``examples/*.py`` path in README code blocks exists; names imported
    from repro modules are real attributes."""
    repo_pkgs = ("repro", "examples", "tests")
    missing = []
    for block in _readme_blocks():
        for mod in re.findall(r"python -m ([\w.]+)", block):
            # only repo-local packages are ours to vouch for (pytest &
            # co. are the environment's problem)
            if mod.split(".")[0] in repo_pkgs and not _module_path_exists(mod):
                missing.append(f"python -m {mod}")
        for script in re.findall(r"(examples/[\w./]+\.py)", block):
            if not (REPO / script).is_file():
                missing.append(script)
        for mod, names in re.findall(
            r"^from ([\w.]+) import ([\w, ]+)$", block, re.M
        ):
            if not _module_path_exists(mod):
                missing.append(f"from {mod} import ...")
                continue
            if mod.split(".")[0] == "repro":
                imported = __import__(mod, fromlist=["_"])
                for name in (n.strip() for n in names.split(",")):
                    if not hasattr(imported, name):
                        missing.append(f"{mod}.{name}")
    assert not missing, f"README names things that do not exist: {missing}"


# which launcher source vouches for the flags on a README command line
_FLAG_SOURCES = {
    "repro.launch.serve": "src/repro/launch/serve.py",
    "serve_viterbi": "examples/serve_viterbi.py",
    "repro.verify.farm": "src/repro/verify/farm.py",
}
_FLAG = re.compile(r"(?<!\S)(--[a-z][a-z-]*)\b")


def test_readme_flags_exist():
    """CLI flags shown in README next to a known launcher are defined
    by that launcher (underscore flags, e.g. XLA_FLAGS values, are env
    plumbing and exempt)."""
    unknown = []
    for block in _readme_blocks():
        lines = block.replace("\\\n", " ").splitlines()
        for line in lines:
            for key, src in _FLAG_SOURCES.items():
                if key in line:
                    source = (REPO / src).read_text()
                    for flag in _FLAG.findall(line):
                        if f'"{flag}"' not in source:
                            unknown.append(f"{flag} ({src})")
    assert not unknown, f"README shows undefined flags: {unknown}"


def test_bench_artifacts_documented():
    """docs/BENCHMARKS.md names every workload, configuration,
    end-to-end metric and per-layer metric BENCHMARK.json declares."""
    doc = REPO / "docs" / "BENCHMARKS.md"
    assert doc.is_file(), "docs/BENCHMARKS.md missing"
    text = doc.read_text()
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [
        entry["name"]
        for key in ("workloads", "configs", "end_to_end", "per_layer")
        for entry in spec[key]
    ]
    assert len(names) >= 10
    undocumented = [n for n in names if f"`{n}`" not in text]
    assert not undocumented, (
        f"BENCHMARK.json names missing from docs/BENCHMARKS.md: "
        f"{undocumented}"
    )


if __name__ == "__main__":  # manual gate: python tests/test_docs.py
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
