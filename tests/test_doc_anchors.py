"""Every file a document names exists.

``README.md`` and ``docs/*.md`` point at code by path. A backticked token
counts as such a pointer when its path part (what stands before ``::``, a
``:<line>`` or a space) contains a ``/`` and ends in one of ``SUFFIXES``; it
must then exist under the repo root, ``trlx_tpu/`` or ``chipbench/``.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)
SUFFIXES = (
    ".py", ".json", ".md", ".txt", ".log", ".yml", ".yaml", ".jsonl", ".toml", ".sh",
)
BASES = ("", "trlx_tpu", "chipbench")

# Not this repo's files, so not checked: the reference implementation's own
# tree, which the documents cite beside the module that took a file's place
# (this repo has no `trlx/` and no `configs/accelerate/`; its `scripts/` has
# no `benchmark.sh`).
REFERENCE_TREE = ("trlx/", "configs/accelerate/", "scripts/benchmark.sh")
# Nor what names no one file: absolute paths (a run's output directories),
# globs, and placeholders (`<checkout>/...`, `{name}`, `...`).
NOT_ONE_FILE = re.compile(r"^[/~]|[*<>{}$]|\.\.\.|…")


def anchors(text):
    """The path part of every backticked token of ``text`` that reads as a
    path to a file, in order, without repeats."""
    seen = []
    for token in re.findall(r"`([^`\n]+)`", text):
        path = re.split(r"::|:\d|\s", token.strip(), maxsplit=1)[0]
        if "/" not in path or not path.endswith(SUFFIXES):
            continue
        if NOT_ONE_FILE.search(path) or path.startswith(REFERENCE_TREE):
            continue
        if path not in seen:
            seen.append(path)
    return seen


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        found = anchors(f.read())
    assert found, f"{document}: no path found; has the pattern gone blind?"
    missing = [
        path
        for path in found
        if not any(os.path.exists(os.path.join(ROOT, base, path)) for base in BASES)
    ]
    assert not missing, f"{document} names files that do not exist: {missing}"
