"""The plain reference of the commit rule (configuration ``paper10f3``).

Mysticeti's rule (arXiv 2310.14821, section 3: "Mysticeti-C") written
straight down over a DAG of plain records: wave length 3, one leader a
round, every round a leader round (pipelined), equal stakes.  Sets and
loops; no memo, no store; imports nothing of the program.  A block is

    Block(author, round, digest, parents)

with ``parents`` the ordered list of ``(author, round, digest)`` it names;
``key(block)`` is its own such triple.  Round 0 is genesis and has no slot.

For the slot of round r (leader ``leader(r, n)``), with voting round r + 1,
decision round r + 2 and ``quorum(n)`` = 2f + 1:

* **direct skip** - blocks of round r + 1 by 2f + 1 authors blame the slot;
* **direct commit** - blocks of round r + 2 by 2f + 1 authors are each a
  certificate of one leader block: 2f + 1 authors among the certificate's
  parents vote for it;
* **indirect** - otherwise, from the first slot at round r + 3 or later
  that is decided ``commit`` (its block is the anchor): ``commit`` if a
  certificate of a leader block is among the anchor's ancestors of round
  r + 2, else ``skip``; an undecided slot met before any such anchor
  leaves this one undecided;
* the decided sequence ends before the first undecided slot.

Where this follows the program's documented rule and not the paper's text:

1. *Blame.*  The paper: a block of round r + 1 blames the slot when it
   does not vote for the leader's block.  Here, as in the reference
   implementation the program follows (``base_committer.rs:228-249``): when
   NONE of its parents is by the slot's leader, of whatever round.  A block
   that names an older block of the leader and not the one of round r
   neither votes nor blames.
2. *Vote under equivocation.*  A block of round r + 1 votes for the FIRST
   of its parents that is by the leader at round r (the order of the
   parents decides), so it votes for one leader block at most.
3. *Ancestors of the anchor* are found one round at a time: the blocks of
   round k that a block found at round k + 1 names; a parent more than one
   round below its child is not followed from there
   (``block_store.rs: linked_to_round``).  A slot that some validator
   commits directly has a certificate on every such path (quorums
   intersect), so this cannot turn a commit into a skip.
4. *The last two rounds.*  A slot is looked at once the DAG holds a block
   two rounds above it (the program scans slots up to ``highest - 2``), so
   a skip that the blocks of round r + 1 alone would give waits for the
   first block of round r + 2.
5. *The election* is this repo's own: BLAKE2b-128 of ``b"mysticeti-tpu/
   leader"``, the round and the draw (both 8 bytes, little endian), read
   little endian, modulo the total stake; with equal stakes and one leader
   a round that number is the leader.  Round 0 elects authority 0.
6. *Sequence.*  ``linearize`` gives each committed leader's ancestors not
   yet sequenced in the program's documented order: depth first from the
   leader, a stack popped from its end, parents pushed in their listed
   order and marked when pushed; then sorted by round, ties kept in that
   order (``linearizer.rs:123-166``).
"""
from __future__ import annotations

import hashlib
from collections import namedtuple
from typing import Dict, Iterable, List, Optional, Tuple

Block = namedtuple("Block", "author round digest parents")
Slot = namedtuple("Slot", "round leader outcome block")  # block: key or None

COMMIT, SKIP = "commit", "skip"
WAVE_LENGTH = 3


def key(block: Block) -> tuple:
    return (block.author, block.round, block.digest)


def quorum(n: int) -> int:
    """2f + 1 of n equal stakes (f = (n - 1) // 3 for n = 3f + 1)."""
    return 2 * n // 3 + 1


def leader(round_: int, n: int) -> int:
    if round_ == 0:
        return 0
    seed = hashlib.blake2b(
        b"mysticeti-tpu/leader" + round_.to_bytes(8, "little")
        + (0).to_bytes(8, "little"), digest_size=16).digest()
    return int.from_bytes(seed, "little") % n


def by_round(dag: Iterable[Block]) -> Dict[int, List[Block]]:
    rounds: Dict[int, List[Block]] = {}
    for block in dag:
        rounds.setdefault(block.round, []).append(block)
    return rounds


def votes_for(block: Block, leader_block: Block) -> bool:
    """``block`` (of the voting round) votes for ``leader_block``: the
    first of its parents in the leader's slot is that block."""
    for parent in block.parents:
        if parent[0] == leader_block.author and parent[1] == leader_block.round:
            return parent == key(leader_block)
    return False


def is_certificate(block: Block, leader_block: Block,
                   blocks: Dict[tuple, Block], n: int) -> bool:
    """2f + 1 authors among ``block``'s parents vote for ``leader_block``."""
    voters = set()
    for parent in block.parents:
        if parent[1] > leader_block.round and votes_for(
                blocks[parent], leader_block):
            voters.add(parent[0])
    return len(voters) >= quorum(n)


def ancestors_at(anchor: Block, round_: int,
                 rounds: Dict[int, List[Block]]) -> List[Block]:
    """Departure 3: the blocks of ``round_`` reached from ``anchor`` one
    round at a time."""
    found = [anchor]
    for r in range(anchor.round - 1, round_ - 1, -1):
        named = {parent for block in found for parent in block.parents}
        found = [b for b in rounds.get(r, []) if key(b) in named]
        if not found:
            break
    return found


def certified(leader_blocks: List[Block], candidates: List[Block],
              blocks: Dict[tuple, Block], n: int) -> List[Block]:
    return [lb for lb in leader_blocks
            if any(is_certificate(c, lb, blocks, n) for c in candidates)]


def decide(dag: Iterable[Block], n: int) -> List[Slot]:
    """The decided leader slots of ``dag``, round 1 upward, up to the first
    that is undecided."""
    dag = list(dag)
    blocks = {key(b): b for b in dag}
    rounds = by_round(dag)
    highest = max(rounds) if rounds else 0
    need = quorum(n)
    status: Dict[int, Optional[Slot]] = {}  # None: undecided
    for r in range(highest - 2, 0, -1):
        who = leader(r, n)
        leader_blocks = [b for b in rounds.get(r, []) if b.author == who]
        # Direct skip (departure 1).
        blamers = {b.author for b in rounds.get(r + 1, [])
                   if all(parent[0] != who for parent in b.parents)}
        if len(blamers) >= need:
            status[r] = Slot(r, who, SKIP, None)
            continue
        # Direct commit.
        deciding = rounds.get(r + 2, [])
        direct = []
        if len({b.author for b in deciding}) >= need:
            for lb in leader_blocks:
                certifiers = {b.author for b in deciding
                              if is_certificate(b, lb, blocks, n)}
                if len(certifiers) >= need:
                    direct.append(lb)
        if len(direct) > 1:
            raise ValueError(f"two certified blocks in the slot of round {r}")
        if direct:
            status[r] = Slot(r, who, COMMIT, key(direct[0]))
            continue
        # Indirect: the first committed anchor a wave later or more.
        status[r] = None
        for later in range(r + WAVE_LENGTH, highest - 1):
            anchor = status[later]
            if anchor is None:
                break
            if anchor.outcome == COMMIT:
                sure = certified(
                    leader_blocks,
                    ancestors_at(blocks[anchor.block], r + 2, rounds),
                    blocks, n)
                if len(sure) > 1:
                    raise ValueError(
                        f"two certified blocks in the slot of round {r}")
                status[r] = (Slot(r, who, COMMIT, key(sure[0])) if sure
                             else Slot(r, who, SKIP, None))
                break
    decided = []
    for r in range(1, highest - 1):
        if status[r] is None:
            break
        decided.append(status[r])
    return decided


def linearize(dag: Iterable[Block], decided: List[Slot],
              ) -> List[Tuple[tuple, List[tuple]]]:
    """[(leader's key, the keys of its sub-DAG in sequence)] of the slots
    decided ``commit``, in order (departure 6).  Genesis is part of the
    first sub-DAGs, as in the program."""
    blocks = {key(b): b for b in dag}
    sequenced = set()
    out = []
    for slot in decided:
        if slot.outcome != COMMIT:
            continue
        sequenced.add(slot.block)
        stack, found = [blocks[slot.block]], []
        while stack:
            block = stack.pop()
            found.append(block)
            for parent in block.parents:
                if parent not in sequenced:
                    sequenced.add(parent)
                    stack.append(blocks[parent])
        found.sort(key=lambda b: b.round)
        out.append((slot.block, [key(b) for b in found]))
    return out
