"""The census of environment switches.

The ``MYSTICETI_*`` names the package's source reads are the ones in the table
of ``docs/observability.md`` ("Environment switches"), no more and no fewer;
the eleven that existed only for the pre-chip measuring rig are named nowhere
in the package any more.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"MYSTICETI_[A-Z0-9_]+")

REMOVED = [
    "MYSTICETI_MESH_LEGACY",
    "MYSTICETI_MAX_BLOCK_TX",
    "MYSTICETI_RETAIN_ROUNDS",
    "MYSTICETI_LEADER_TIMEOUT",
    "MYSTICETI_VERIFY_WINDOW_MS",
    "MYSTICETI_VERIFY_PIPELINE_DEPTH",
    "MYSTICETI_CLOSED_LOOP",
    "MYSTICETI_CLIENT_FINALITY",
    "MYSTICETI_OVERLOAD_SCHEDULE",
    "MYSTICETI_SYNC_WAL_WRITES",
    "MYSTICETI_PERF_REPORT",
]


@functools.lru_cache(maxsize=None)
def _package_sources():
    sources = []
    pattern = os.path.join(REPO, "mysticeti_tpu", "**", "*.py")
    for path in sorted(glob.glob(pattern, recursive=True)):
        with open(path, encoding="utf-8") as f:
            sources.append((os.path.relpath(path, REPO), f.read()))
    return tuple(sources)


def _read_by_the_package():
    return {name for _path, text in _package_sources()
            for name in _NAME.findall(text)}


@functools.lru_cache(maxsize=None)
def _documented():
    with open(os.path.join(REPO, "docs", "observability.md"),
              encoding="utf-8") as f:
        text = f.read()
    section = text.split("## Environment switches", 1)[1].split("\n## ", 1)[0]
    return frozenset(
        re.findall(r"^\| `(MYSTICETI_[A-Z0-9_]+)` \|", section, re.M))


@pytest.mark.parametrize("switch", sorted(_read_by_the_package()))
def test_a_switch_the_package_reads_is_in_the_table(switch):
    assert switch in _documented()


def test_the_table_names_no_switch_the_package_does_not_read():
    assert _documented() - _read_by_the_package() == set()


@pytest.mark.parametrize("switch", REMOVED)
def test_a_removed_switch_is_named_nowhere_in_the_package(switch):
    assert [path for path, text in _package_sources() if switch in text] == []
