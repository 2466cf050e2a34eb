"""``benchmark/reference/commit_rule.py`` against the system: the plain
reference's election, decisions and sequence equal ``Committee``,
``UniversalCommitter`` and the ``Linearizer`` on seeded random DAGs of 4, 7
and 10 authorities with 0 to f authors silent from a seeded round and blocks
that arrive late, so that all four outcomes (direct and indirect, commit and
skip) and undecided slots occur; and on one hand-written DAG an outcome."""
import random

import pytest

from benchmark.reference import commit_rule
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.consensus import AuthorityRound, DIRECT, INDIRECT, LeaderStatus
from mysticeti_tpu.consensus.linearizer import Linearizer
from mysticeti_tpu.consensus.universal_committer import UniversalCommitterBuilder
from mysticeti_tpu.types import StatementBlock

from helpers import DagBlockWriter

DAGS_A_SIZE = 120  # 360 DAGs in all


def ref_key(reference) -> tuple:
    return (reference.authority, reference.round, reference.digest)


def record(block: StatementBlock) -> commit_rule.Block:
    return commit_rule.Block(
        block.author(), block.round(), block.reference.digest,
        [ref_key(r) for r in block.includes])


def random_dag(rng: random.Random, n: int, rounds: int):
    """Blocks of a run in which up to f authors fall silent at a seeded
    round, every block names its author's last block first and a quorum of
    the round below, a leader's block is now and then seen by few, and a
    block left out is named later (a parent more than a round below)."""
    f = (n - 1) // 3
    need = commit_rule.quorum(n)
    silent = {a: rng.randrange(1, rounds)
              for a in rng.sample(range(n), rng.randint(0, f))}
    late_share = rng.choice([0.0, 0.2, 0.5])
    genesis = [StatementBlock.new_genesis(a) for a in range(n)]
    blocks = list(genesis)
    last_own = {a: genesis[a] for a in range(n)}
    below = list(genesis)  # the blocks of the round below
    named = set()  # keys some block already names
    for r in range(1, rounds + 1):
        alive = [a for a in range(n) if silent.get(a, rounds + 1) > r]
        # The slot below, seen by few this round?
        shunned = (commit_rule.leader(r - 1, n)
                   if rng.random() < late_share else None)
        layer = []
        for a in alive:
            own = last_own[a]
            others = [b for b in below if b.author() != a]
            rng.shuffle(others)
            if shunned is not None and rng.random() < 0.8:
                others.sort(key=lambda b: b.author() == shunned)
            extra = need - (1 if own.round() == r - 1 else 0)
            take = rng.randint(extra, max(extra, len(others)))
            if shunned is not None:
                take = extra
            parents = [own] + others[:take]
            if len({p.author() for p in parents
                    if p.round() == r - 1}) < need:
                continue  # not enough of the round below: no block
            # A block left out so far, a round or two further down.
            older = [b for b in blocks
                     if 0 < b.round() < r - 1 and b.round() >= r - 3
                     and ref_key(b.reference) not in named]
            if older and rng.random() < 0.5:
                parents.append(rng.choice(older))
            rng.shuffle(parents)
            parents.remove(own)
            parents.insert(0, own)
            refs = [p.reference for p in parents]
            block = StatementBlock.build(a, r, refs, ())
            named.update(ref_key(x) for x in refs)
            layer.append(block)
            last_own[a] = block
        if len(layer) < need:
            break
        blocks += layer
        below = layer
    return blocks


def system(blocks, committee, tmp_dir, name):
    writer = DagBlockWriter(committee, tmp_dir, name=name)
    writer.add_blocks(blocks)
    committer = (UniversalCommitterBuilder(committee, writer.block_store)
                 .with_wave_length(3).with_pipeline(True).build())
    sequence = committer.try_commit(AuthorityRound(0, 0))
    linearizer = Linearizer(writer.block_store)
    sub_dags = linearizer.handle_commit(
        [s.block for s in sequence if s.kind == LeaderStatus.COMMIT])
    return sequence, sub_dags, committer


@pytest.mark.parametrize("n", [4, 7, 10])
def test_decide_and_linearize_equal_the_system(n, tmp_path):
    committee = Committee.new_for_benchmarks(n)
    seen = {"undecided": 0, "silent": 0,
            **{(rule, outcome): 0 for rule in (DIRECT, INDIRECT)
               for outcome in (commit_rule.COMMIT, commit_rule.SKIP)}}
    for seed in range(DAGS_A_SIZE):
        rng = random.Random(1_000_003 * n + seed)
        blocks = random_dag(rng, n, rng.randint(8, 16))
        sequence, sub_dags, committer = system(
            blocks, committee, str(tmp_path), f"wal-{seed}")
        dag = [record(b) for b in blocks]
        decided = commit_rule.decide(dag, n)
        assert [(s.round, s.leader, s.outcome, s.block) for s in decided] == [
            (s.round, s.authority, s.kind,
             ref_key(s.block.reference) if s.block else None)
            for s in sequence], seed
        assert commit_rule.linearize(dag, decided) == [
            (ref_key(d.anchor), [ref_key(b.reference) for b in d.blocks])
            for d in sub_dags], seed
        highest = max(b.round() for b in blocks)
        seen["undecided"] += len(decided) < highest - 2
        seen["silent"] += len({b.author() for b in blocks
                               if b.round() == highest}) < n
        for entry in committer.ledger.records():
            seen[entry["rule"], entry["outcome"]] += 1
    # The DAGs reach every road of the rule: all four outcomes.
    assert all(count >= 10 for count in seen.values()), seen


# -- one DAG written by hand an outcome --------------------------------------
#
# Four authorities, quorum 3.  ``{round: {author: [authors of the parents,
# which are of the round below; the own block first]}}``.  The slot under
# test is round 1's, whose leader is authority 0; round 4's is authority 1.

ALL = {a: [a] + [b for b in range(4) if b != a] for a in range(4)}
# Authority 3 does not name the leader's block: one blame, three votes.
# Only 2@3 has all three voters among its parents: one certificate.
SPLIT = {
    1: ALL,
    2: {0: [0, 1, 2, 3], 1: [1, 0, 2], 2: [2, 0, 1], 3: [3, 1, 2]},
    3: {0: [0, 1, 3], 1: [1, 2, 3], 2: [2, 0, 1], 3: [3, 0, 2]},
    5: ALL,
    6: ALL,
}
HAND_WRITTEN = {
    # Every block names every block: four votes, four certificates.
    (DIRECT, commit_rule.COMMIT): {1: ALL, 2: ALL, 3: ALL},
    # The leader is silent in round 1: three blocks of round 2 blame it.
    (DIRECT, commit_rule.SKIP): {
        1: {a: ALL[a] for a in (1, 2, 3)},
        2: {1: [1, 2, 3], 2: [2, 1, 3], 3: [3, 1, 2]},
        3: {1: [1, 2, 3], 2: [2, 1, 3], 3: [3, 1, 2]},
    },
    # Round 4's leader block, committed directly by rounds 5 and 6, names
    # the one certificate 2@3 ...
    (INDIRECT, commit_rule.COMMIT): {
        **SPLIT, 4: {0: [0, 1, 3], 1: [1, 2, 0], 2: [2, 0, 1], 3: [3, 0, 1]}},
    # ... or does not.
    (INDIRECT, commit_rule.SKIP): {
        **SPLIT, 4: {0: [0, 1, 3], 1: [1, 0, 3], 2: [2, 0, 1], 3: [3, 0, 1]}},
}


def build(layers):
    assert [commit_rule.leader(r, 4) for r in (1, 4)] == [0, 1]
    below = {a: StatementBlock.new_genesis(a) for a in range(4)}
    blocks = list(below.values())
    for r in sorted(layers):
        layer = {a: StatementBlock.build(
            a, r, [below[p].reference for p in parents], ())
            for a, parents in layers[r].items()}
        blocks += layer.values()
        below = layer
    return blocks


@pytest.mark.parametrize("rule,outcome", list(HAND_WRITTEN))
def test_a_hand_written_dag_for_each_outcome(rule, outcome, tmp_path):
    blocks = build(HAND_WRITTEN[rule, outcome])
    dag = [record(b) for b in blocks]
    first = commit_rule.decide(dag, 4)[0]
    leader_block = next((ref_key(b.reference) for b in blocks
                         if b.round() == 1 and b.author() == 0), None)
    assert first == commit_rule.Slot(
        1, 0, outcome, leader_block if outcome == commit_rule.COMMIT else None)
    # The program on the same blocks: the same slot by the same rule, and
    # the same sequence after it.
    committee = Committee.new_for_benchmarks(4)
    sequence, sub_dags, committer = system(
        blocks, committee, str(tmp_path), "wal")
    (entry,) = [e for e in committer.ledger.records() if e["round"] == 1]
    assert (entry["rule"], entry["outcome"]) == (rule, outcome)
    decided = commit_rule.decide(dag, 4)
    assert [(s.round, s.outcome, s.block) for s in decided] == [
        (s.round, s.kind, ref_key(s.block.reference) if s.block else None)
        for s in sequence]
    assert commit_rule.linearize(dag, decided) == [
        (ref_key(d.anchor), [ref_key(b.reference) for b in d.blocks])
        for d in sub_dags]


def test_the_election_equals_the_committees():
    for n in (4, 10):
        committee = Committee.new_for_benchmarks(n)
        for round_ in range(1_000):
            assert commit_rule.leader(round_, n) == committee.elect_leader(
                round_), (n, round_)


def test_quorum_is_two_f_plus_one():
    for n in (4, 7, 10, 50):
        committee = Committee.new_for_benchmarks(n)
        assert commit_rule.quorum(n) == committee.quorum_threshold()
