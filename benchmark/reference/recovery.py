"""The plain reference of a WAL recovery (configuration ``paper10cr``).

What a validator that boots on the log it left behind must find there,
read from the log's bytes alone: sets and loops over the documented
framing (``docs/wire-format.md`` section 6), nothing of the program
imported; the commit rule is ``commit_rule.py``'s, beside this file.

A log is one file, or a directory whose ``MANIFEST.json`` lists its
segments in order (``{"segments": [{"name": ...}, ...]}``); positions run
on through the segments.  A record is

    u32 magic "WAL1" | u32 crc32(payload) | u32 len | u32 tag | payload

all little endian.  The log ENDS before the first record whose magic is
not the magic, whose length runs past the end of its file or whose
payload fails its crc: that record and every byte after it - the rest of
its file and every later segment - is the torn tail, which a recovery
cuts.  Of the records before it:

* tag 1 is a block, tag 3 an own block (u64 next-entry position, then the
  block): ``u64 author | u64 round | u32 n | n x (u64 author | u64 round |
  32-byte digest) | ...``; its digest is BLAKE2b-256 of the block's whole
  serialization.  ``blocks`` counts the distinct (author, round, digest);
  ``own_round`` is the highest round among the own blocks: the validator
  signs nothing at or below it again.
* tag 5 is a batch of commits: ``u32 count | count x (leader reference |
  u32 n | n x reference | u64 height) | ...``, a reference being ``u64
  author | u64 round | 32-byte digest``.  ``commit_height`` is the highest
  height written; ``unsupported`` lists the heights at which what was
  written is not what ``commit_rule.decide`` + ``linearize`` give on the
  log's own blocks (a commit the log's DAG does not support, or another
  leader or sub-DAG at that height).

Departures, all of them things this reader does NOT rebuild, because no
number the boot reports depends on them: the pending queue and what a
proposal had consumed of it (the next-entry positions), the handler's
state and the votes' aggregators (tags 2 and 4, and the tail of tag 5),
the mempool, the execution state; checkpoints (the program replays from
its newest one and must arrive at what the whole log gives: that is the
comparison); a snapshot adoption (tag 6; the configuration has
``snapshot_catchup`` false, and such a record is reported in ``adopted``
so that a caller can refuse the comparison); the manifest's bases (each
file is read whole, in the manifest's order).
"""
from __future__ import annotations

import hashlib
import json
import os
import struct
import zlib
from typing import Dict, List, Tuple

from benchmark.reference import commit_rule

MAGIC = 0x314C4157
HEADER = struct.Struct("<IIII")
TAG_BLOCK, TAG_OWN_BLOCK, TAG_COMMIT, TAG_SNAPSHOT = 1, 3, 5, 6
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")
_REF = struct.Struct("<QQ32s")


def files_of(path: str) -> List[str]:
    """The log's files in order: the file itself, or the manifest's."""
    if os.path.isdir(path):
        with open(os.path.join(path, "MANIFEST.json")) as f:
            return [os.path.join(path, s["name"])
                    for s in json.load(f)["segments"]]
    return [path] if os.path.exists(path) else []


def records(data: bytes) -> Tuple[List[tuple], int]:
    """([(position, tag, payload)], end): the records of one file up to
    the first that fails, and where they end."""
    out, pos = [], 0
    while pos + HEADER.size <= len(data):
        magic, crc, length, tag = HEADER.unpack_from(data, pos)
        start = pos + HEADER.size
        if magic != MAGIC or start + length > len(data):
            break
        payload = data[start:start + length]
        if zlib.crc32(payload) != crc:
            break
        out.append((pos, tag, payload))
        pos = start + length
    return out, pos


def block_of(data: bytes) -> commit_rule.Block:
    author, round_ = struct.unpack_from("<QQ", data, 0)
    (count,) = _U32.unpack_from(data, 16)
    parents = [_REF.unpack_from(data, 20 + 48 * i) for i in range(count)]
    digest = hashlib.blake2b(data, digest_size=32).digest()
    return commit_rule.Block(author, round_, digest,
                             [tuple(p) for p in parents])


def commits_of(data: bytes) -> List[tuple]:
    """[(height, leader key, [sub-DAG keys])] of one commit record."""
    (count,) = _U32.unpack_from(data, 0)
    pos, out = 4, []
    for _ in range(count):
        leader = tuple(_REF.unpack_from(data, pos))
        (n,) = _U32.unpack_from(data, pos + 48)
        pos += 52
        sub_dag = [tuple(_REF.unpack_from(data, pos + 48 * i))
                   for i in range(n)]
        pos += 48 * n
        (height,) = _U64.unpack_from(data, pos)
        pos += 8
        out.append((height, leader, sub_dag))
    return out


def read(path: str) -> dict:
    """What the log at ``path`` holds before its torn tail."""
    blocks: Dict[tuple, commit_rule.Block] = {}
    commits: Dict[int, tuple] = {}
    own_round, entries, valid, torn, adopted = 0, 0, 0, 0, 0
    ended = False
    cuts: Dict[str, int] = {}  # file -> where its sound records end
    for file in files_of(path):
        with open(file, "rb") as f:
            data = f.read()
        if ended:  # written after the record that failed: unreachable
            torn += len(data)
            cuts[file] = 0
            continue
        found, end = records(data)
        cuts[file] = end
        valid += end
        if end < len(data):
            torn += len(data) - end
            ended = True
        entries += len(found)
        for _pos, tag, payload in found:
            if tag == TAG_BLOCK or tag == TAG_OWN_BLOCK:
                block = block_of(payload[8:] if tag == TAG_OWN_BLOCK
                                 else payload)
                blocks[commit_rule.key(block)] = block
                if tag == TAG_OWN_BLOCK:
                    own_round = max(own_round, block.round)
            elif tag == TAG_COMMIT:
                for height, leader, sub_dag in commits_of(payload):
                    commits[height] = (leader, sub_dag)
            elif tag == TAG_SNAPSHOT:
                adopted += 1
    return {"entries": entries, "valid_bytes": valid, "torn_bytes": torn,
            "blocks": len(blocks), "own_round": own_round,
            "commit_height": max(commits, default=0), "adopted": adopted,
            "dag": list(blocks.values()), "commits": commits, "cuts": cuts}


def unsupported(log: dict, n: int) -> List[int]:
    """The heights of ``log["commits"]`` at which the written commit is not
    the one the reference decides and sequences on the log's own blocks."""
    decided = commit_rule.decide(log["dag"], n)
    sequence = commit_rule.linearize(log["dag"], decided)
    return [height for height in sorted(log["commits"])
            if height > len(sequence)
            or log["commits"][height] != sequence[height - 1]]


def report(path: str, n: int) -> dict:
    """The four numbers a boot on ``path`` must report, and whether the
    log's DAG supports the commits written in it."""
    log = read(path)
    return {"blocks": log["blocks"], "own_round": log["own_round"],
            "commit_height": log["commit_height"],
            "torn_bytes": log["torn_bytes"],
            "unsupported": unsupported(log, n), "adopted": log["adopted"],
            "cuts": log["cuts"], "entries": log["entries"]}
