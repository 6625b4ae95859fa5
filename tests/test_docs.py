"""The documents against the tree: text only, no jax. A file a document
names exists, an environment name the package reads is in the README's
one list and the README names none that nothing reads, and the README's
status names every cell of the benchmark."""

import glob
import json
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ("README.md", "ARCHITECTURE.md", "COVERAGE.md")

# back-ticked names with a source or record suffix that are not files of
# this repo: what a run writes where its caller says, and the reference's
NOT_OF_THE_REPO = {
    "crash.json", "steps.jsonl", "trace.json", "step_account.json",
    "paddle_tpu_hang.json", "memory_optimization_transpiler.py",
}


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


def _files():
    """The tree's files: what git tracks in a checkout with its history,
    what is on disk in a bare copy of the committed files."""
    r = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True,
                       text=True)
    if r.returncode == 0 and r.stdout:
        return set(r.stdout.splitlines())
    return {os.path.relpath(os.path.join(d, f), REPO)
            for d, _, fs in os.walk(REPO) for f in fs}


def _named_files(text):
    """Back-ticked `*.py`, `*.json`, `*.jsonl`, `*.md` names; a name with
    a wildcard or a placeholder in it is a pattern, not a path."""
    names = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        names.update(re.findall(r"[\w./*<>-]+\.(?:py|jsonl|json|md)\b", span))
    return {n for n in names
            if not set(n) & set("*<>") and n not in NOT_OF_THE_REPO}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_files_that_exist(document):
    """`chip.py` may stand for `paddle_tpu/chip.py`: a name is found if
    it is the tail of a file's path."""
    files = _files()
    missing = sorted(
        n for n in _named_files(_read(document))
        if not any(f == n or f.endswith("/" + n) for f in files))
    assert not missing, f"{document} names files that are gone: {missing}"


def _names_the_package_reads():
    """PADDLE_TPU_* spelled out in the package, and the flags registry's
    `define("x", ...)`, which reads PADDLE_TPU_X."""
    names = set()
    for path in glob.glob(os.path.join(REPO, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text = f.read()
        names.update(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", text))
        names.update("PADDLE_TPU_" + n.upper()
                     for n in re.findall(r"\bdefine\(\s*\"(\w+)\"", text))
    return sorted(names)


NAMES_READ = _names_the_package_reads()


@pytest.mark.parametrize("name", NAMES_READ)
def test_an_environment_name_the_package_reads_is_in_the_readme(name):
    section = _read("README.md").split("## Environment names", 1)[1]
    section = section.split("\n## ", 1)[0]
    assert f"`{name}`" in section, \
        f"{name} is missing from README.md's 'Environment names'"


def test_the_readme_names_no_environment_name_that_nothing_reads():
    named = set(re.findall(r"\b(?:PADDLE_TPU|BENCH|SCALE)_[A-Z0-9_]*[A-Z0-9]",
                           _read("README.md")))
    stale = sorted(named - set(NAMES_READ))
    assert not stale, f"README.md names what nothing reads: {stale}"


def test_the_readme_names_every_cell_of_the_benchmark():
    status = _read("README.md").split("\n## ", 1)[0]
    cells = [w["name"]
             for w in json.loads(_read("BENCHMARK.json"))["workloads"]]
    missing = [c for c in cells if f"`{c}`" not in status]
    assert cells and not missing, f"README.md's status lacks {missing}"
