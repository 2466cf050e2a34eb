"""Every path a living document names exists.

``README.md``, ``PARITY.md`` and each ``docs/*.md`` describe the tree as it
is: a tool, a test, a module or a record that one of them names and the tree
does not hold is a deletion that left its documents behind.  ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` are accounts of the past and are not held
to this.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

# tools/x.py, tests/test_x.py::test_y, mysticeti_tpu/ops/, benchmark/configs/<cell>.json
_IN_TREE = re.compile(
    r"(?<![\w./-])((?:tools|tests|mysticeti_tpu|benchmark)/[\w./-]*|docs/[\w-]+\.md)")
# chip_smoke.py, spans.py: a bare name, at the root or a module somewhere below it
_BARE_PY = re.compile(r"(?<![\w./<>*-])(\w+\.py)\b")
# BENCHMARK.json, PERF_LEDGER.jsonl, BENCH_r03.json, EXEC_rNN.json: the root's
# records, unless the program or a tool names the file itself (MANIFEST.json of
# a WAL directory, a tool's default --out): that one is made at run time
_ROOT_RECORD = re.compile(r"(?<![\w./<>*-])([A-Z][A-Z0-9_]*(?:_r\w\w)?\.jsonl?)\b")


@functools.lru_cache(maxsize=None)
def _tree():
    """(base names of the files below the code directories, the record-like
    names their Python sources write at run time)."""
    basenames, written = set(), set()
    for top in ("mysticeti_tpu", "tools", "tests", "benchmark"):
        for directory, _dirs, files in os.walk(os.path.join(REPO, top)):
            basenames.update(files)
            if top in ("mysticeti_tpu", "tools"):
                for name in files:
                    if name.endswith(".py"):
                        with open(os.path.join(directory, name),
                                  encoding="utf-8") as f:
                            written.update(_ROOT_RECORD.findall(f.read()))
    return basenames, written


def _missing(text):
    missing = set()
    for match in _IN_TREE.finditer(text):
        path = match.group(1).rstrip(".-")
        pattern_follows = text[match.end():match.end() + 1] in ("*", "<", "{")
        found = (
            bool(glob.glob(os.path.join(REPO, path) + "*"))
            if pattern_follows
            else os.path.exists(os.path.join(REPO, path))
        )
        if not found:
            missing.add(path)
    basenames, written = _tree()
    for match in _BARE_PY.finditer(text):
        name = match.group(1)
        if name not in basenames and not os.path.exists(os.path.join(REPO, name)):
            missing.add(name)
    for match in _ROOT_RECORD.finditer(text):
        name = match.group(1)
        if name not in written and not os.path.exists(os.path.join(REPO, name)):
            missing.add(name)
    return sorted(missing)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    assert _missing(text) == [], f"{document} names paths the tree does not hold"
