"""The documents say what is in the tree: a back-ticked path that names a
file of this repository exists, a `TFDE_*` name is a registered knob, and
the README's knob table is the one `knobs.table_md()` generates."""

import functools
import os
import re

import pytest

from tfde_tpu import knobs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "WORKFLOWS.md", "MIGRATION.md")

_FENCED = re.compile(r"^```.*?^```", re.M | re.S)
_TICKED = re.compile(r"`([^`]+)`")
# a path from the root of the tree, or a bare file name: one at the root
# (`PERF.md`) or a module called by its last name (`server.py`)
_TREES = ("tools", "tfde_tpu", "benchmarks", "tests", "examples")
_PATH = re.compile(
    rf"(?:(?:{'|'.join(_TREES)})/[\w./-]*"
    r"|[\w.-]+\.(?:py|json|jsonl|md|sh))\Z")
_KNOB = re.compile(r"TFDE_[A-Z0-9_]+")
# the reference repository's entry points, which MIGRATION.md maps from
# (SURVEY.md section 1): files of another tree
_NOT_OURS = {"tf2_mnist_distributed.py", "mnist_keras_distributed.py",
             "distributed_with_keras.py"}


def _read(doc):
    with open(os.path.join(ROOT, doc)) as f:
        return f.read()


@functools.cache
def _file_names():
    names = set(os.listdir(ROOT))
    for tree in _TREES:
        for _, _, files in os.walk(os.path.join(ROOT, tree)):
            names.update(files)
    return names


def _exists(path):
    if "/" in path:
        return os.path.exists(os.path.join(ROOT, path))
    return path in _file_names()


def _paths(text):
    """Every word inside back-ticks or a fenced block that reads as a path
    of this tree, without what follows the file's name (`::test`, `:123`,
    a full stop)."""
    found = set()
    spans = _FENCED.findall(text) + _TICKED.findall(_FENCED.sub("", text))
    for span in spans:
        for word in span.split():
            word = re.sub(r"(::.*|:\d+(-\d+)?)\Z", "", word).rstrip(".,;:)")
            if _PATH.match(word) and word not in _NOT_OURS:
                found.add(word)
    return sorted(found)


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    missing = [p for p in _paths(_read(doc)) if not _exists(p)]
    assert missing == [], f"{doc} names files that are not in the tree"


@pytest.mark.parametrize("doc", DOCS)
def test_named_knobs_are_registered(doc):
    unknown = sorted({k for k in _KNOB.findall(_read(doc))
                      if not knobs.is_registered(k)})
    assert unknown == [], f"{doc} names knobs tfde_tpu/knobs.py lacks"


def test_readme_knob_table_is_the_generated_one():
    text = _read("README.md")
    head = knobs.table_md().splitlines()[0]
    start = text.index(head)
    table = text[start:].split("\n\n", 1)[0].rstrip("\n")
    assert table == knobs.table_md()
