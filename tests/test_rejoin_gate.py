"""The proposal gate and a peer that is connected and behind
(``Core.ready_new_block``, docs/fault-injection.md "Permanent faults on
sockets"): a connected leader is waited for while its newest accepted
block lies at most ``leader_liveness_horizon`` rounds below its slot
(``Core.LEADER_HORIZON_ROUNDS`` where the configuration names none).  On
hand-built stores: a peer connected and behind is not waited for, the same
peer at the frontier is, one whose connection closed is not, a slot its
leader has passed is not, nor the validator's own, and from its first
block every peer is waited for.  On the simulator: a validator down for a
hundred rounds and back on its WAL costs the others no leader timeout while
it catches up, where a gate without the horizon cost them one in every slot
it led, and every commit sequence agrees.  And the backstop's timer: one
that fires late starts again."""
import asyncio
from types import SimpleNamespace

import pytest

from mysticeti_tpu.chaos import (
    ChaosEngine,
    ChaosSimHarness,
    FaultPlan,
    LinkFault,
)
from mysticeti_tpu.commit_observer import TestCommitObserver
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters
from mysticeti_tpu.core import Core
from mysticeti_tpu.net_sync import NetworkSyncer
from mysticeti_tpu.runtime.simulated import run_simulation

from helpers import committee_and_cores

PERIOD = Parameters().wave_length
HORIZON = Core.LEADER_HORIZON_ROUNDS
LAST = 11  # round-robin of four: authority 3 leads round 11
PEER = 3


def _propose_rounds(members, rounds):
    """``members`` propose every round up to ``rounds``, each on all the
    members' blocks of the round below; the layers, round 1 first."""
    layer = []
    for core in members:
        core.run_block_handler([])
        layer.append(core.try_new_block())
    layers = [layer]
    for round_ in range(2, rounds + 1):
        below, layer = layer, []
        for core in members:
            core.add_blocks([b for b in below if b.author() != core.authority])
            block = core.try_new_block()
            assert block.round() == round_
            layer.append(block)
        layers.append(layer)
    return layers


def _peers_block(core, layers, seen, after_a_jump=False):
    """The block ``core`` proposes when it has seen the first ``seen``
    layers, one of round ``seen + 1``: the next after its block of round
    ``seen``, or - ``after_a_jump`` - after its block of round 1, as a
    validator that replays a backlog proposes."""
    core.run_block_handler([])
    core.try_new_block()
    for layer in layers[:seen - 1]:
        core.add_blocks(layer)
    if not after_a_jump and seen > 1:
        assert core.try_new_block().round() == seen
    core.add_blocks(layers[seen - 1])
    block = core.try_new_block()
    assert block.round() == seen + 1
    assert block.includes[0].round == (1 if after_a_jump else seen)
    return block


def _peers_chain(core, layers, upto):
    """The blocks ``core`` proposes when it is in step up to round
    ``upto``: one a round, each after its own of the round below."""
    core.run_block_handler([])
    chain = [core.try_new_block()]
    for layer in layers[:upto - 1]:
        core.add_blocks(layer)
        chain.append(core.try_new_block())
    assert [b.round() for b in chain] == list(range(1, upto + 1))
    return chain


@pytest.fixture
def gate(tmp_path):
    """Validator 0 at round 12 of a DAG that 0, 1 and 2 built without 3,
    its syncer never started: the slot below is 3's, and 3 is not heard."""
    committee, cores = committee_and_cores(4, str(tmp_path))
    layers = _propose_rounds(cores[:PEER], LAST)
    core = cores[0]
    core.add_blocks([b for b in layers[-1] if b.author() != 0])
    assert core.current_round() == LAST + 1
    assert core.committer.get_leaders(LAST) == [PEER]
    node = NetworkSyncer(
        core, TestCommitObserver(core.block_store, committee), None,
        parameters=Parameters())
    node.connected_authorities.insert(0)  # as ``start`` does
    return SimpleNamespace(
        node=node, core=core, layers=layers, peer=cores[PEER],
        members=lambda: sorted(node.connected_authorities.present()),
        held=lambda: not core.ready_new_block(
            PERIOD, node.connected_authorities))


def _connect(gate, peer=PEER):
    gate.node.connected_authorities.insert(peer)  # as ``_connection_task``


def test_a_peer_connected_and_behind_is_not_waited_for(gate):
    _connect(gate)
    assert gate.members() == [0, PEER]
    # Nothing of it accepted yet: its slot goes by.
    assert gate.held() is False
    # Back on its WAL: its proposals are of the rounds it is replaying.
    gate.core.add_blocks(
        _peers_chain(gate.peer, gate.layers, LAST - HORIZON - 1))
    assert gate.core.block_store.last_seen_by_authority(PEER) == (
        LAST - HORIZON - 1)
    assert gate.held() is False


def test_a_peer_that_jumps_to_the_frontier_is_waited_for_from_that_block(
        gate):
    """Its first block near the frontier follows one of rounds ago, and
    from it on the slot it leads is waited for; one that lands ON the slot
    fills it."""
    _connect(gate)
    jumped = _peers_block(gate.peer, gate.layers, LAST - 2, after_a_jump=True)
    first = gate.peer.block_store.get_block(jumped.includes[0])
    assert (first.round(), jumped.round()) == (1, LAST - 1)
    gate.core.add_blocks([first])
    assert gate.held() is False
    gate.core.add_blocks([jumped])
    assert gate.held() is True
    gate.core.add_blocks([_peers_block_at(gate, LAST)])
    assert gate.held() is False


@pytest.mark.parametrize("below", [1, HORIZON, HORIZON + 1])
def test_the_same_peer_at_the_frontier_is_waited_for(gate, below):
    """``below``: how far under its slot (round 11) the peer's newest
    accepted block lies; the horizon is the last that counts."""
    _connect(gate)
    gate.core.add_blocks(_peers_chain(gate.peer, gate.layers, LAST - below))
    if below > HORIZON:
        assert gate.held() is False
        return
    # Its slot is waited for until its block of that round is in.
    assert gate.held() is True
    gate.core.add_blocks([_peers_block_at(gate, LAST)])
    assert gate.held() is False


def _peers_block_at(gate, round_):
    """Another block of the peer, of ``round_``: hand-made, as the leader's
    block of the slot, from a second copy of its core's view."""
    from mysticeti_tpu.types import StatementBlock

    below = gate.layers[round_ - 2]
    return StatementBlock.build(
        PEER, round_, [b.reference for b in below], [],
        signer=Committee.benchmark_signers(4)[PEER])


def test_a_closed_peer_is_not_waited_for(gate):
    _connect(gate)
    gate.core.add_blocks(_peers_chain(gate.peer, gate.layers, LAST - 1))
    assert gate.members() == [0, PEER] and gate.held() is True
    gate.node.connected_authorities.remove(PEER)  # its connection task ends
    assert gate.members() == [0] and gate.held() is False


@pytest.mark.parametrize("own", [2, HORIZON + 3])
def test_a_configuration_s_own_horizon_replaces_the_program_s(tmp_path, own):
    """``leader_liveness_horizon_rounds``: 0 names ``LEADER_HORIZON_ROUNDS``,
    another number itself."""
    committee, cores = committee_and_cores(
        4, str(tmp_path),
        parameters=Parameters(leader_liveness_horizon_rounds=own))
    assert cores[0].leader_liveness_horizon == own
    layers = _propose_rounds(cores[:PEER], LAST)
    core = cores[0]
    core.add_blocks([b for b in layers[-1] if b.author() != 0])
    everyone = NetworkSyncer(
        core, TestCommitObserver(core.block_store, committee), None,
        parameters=Parameters()).connected_authorities
    for authority in range(4):
        everyone.insert(authority)
    below = 4  # between the two
    core.add_blocks(_peers_chain(cores[PEER], layers, LAST - below))
    assert core.ready_new_block(PERIOD, everyone) is (below > own)


def test_a_slot_its_leader_has_passed_is_not_waited_for(gate):
    """A validator proposes at its clock's round and never below it: once
    a block of the leader's from a round above its slot is in, the slot
    stays empty, and a wait for it would last the whole leader timeout."""
    _connect(gate)
    gate.core.add_blocks(_peers_chain(gate.peer, gate.layers, LAST - 1))
    assert gate.held() is True  # its newest lies below the slot: it may come
    gate.core.add_blocks([_peers_block_at(gate, LAST + 1)])
    assert gate.core.block_store.last_seen_by_authority(PEER) == LAST + 1
    assert gate.core.current_round() == LAST + 1
    assert gate.held() is False


def test_a_leader_does_not_wait_for_the_slot_it_jumped_over(gate):
    """The leader's own gate: its clock passed its slot in one batch, so
    the block everyone waits for is one it will never build."""
    own = gate.peer
    own.run_block_handler([])
    assert own.try_new_block().round() == 1
    for layer in gate.layers:
        own.add_blocks(layer)
    assert own.current_round() == LAST + 1 and own.last_proposed() == 1
    assert own.committer.get_leaders(LAST) == [own.authority]
    everyone = gate.node.connected_authorities
    for authority in range(4):
        everyone.insert(authority)
    assert own.ready_new_block(PERIOD, everyone) is True


@pytest.mark.parametrize("held_s", [0.0, 0.6])
def test_a_leader_timeout_that_fires_late_starts_again(gate, held_s):
    """The timer of the liveness backstop says that the round stood still
    for ``leader_timeout_s`` - unless the process did: a timer that fires
    ``LATE_TIMER_S`` after it was due (the loop held, the machine stopped)
    forces nothing, and the wait starts again."""
    import time

    node, forced, events = gate.node, [], []
    node.parameters = Parameters(leader_timeout_s=0.2)
    node.recorder = SimpleNamespace(
        record=lambda kind, **fields: events.append(kind))

    async def force_new_block(round_, connected):
        forced.append((round_, list(events)))

    node.dispatcher = SimpleNamespace(force_new_block=force_new_block)

    async def scenario():
        task = asyncio.ensure_future(node._leader_timeout_task())
        await asyncio.sleep(0.05)
        time.sleep(held_s)  # the loop is held across the timer's deadline
        while not forced:
            await asyncio.sleep(0.01)
        task.cancel()

    asyncio.run(asyncio.wait_for(scenario(), 30))
    round_, before = forced[0]
    assert round_ == node.signals.current_round + 1
    if held_s:
        assert before[:2] == ["leader-timeout-late", "leader-timeout"]
    else:
        assert "leader-timeout" in before


def test_from_its_first_block_every_peer_is_waited_for(tmp_path):
    """A fleet at boot: nobody is behind, and the leader of round 3 is
    waited for by a validator that holds a quorum of round 3 without it."""
    committee, cores = committee_and_cores(4, str(tmp_path))
    layers = _propose_rounds(cores, 3)
    core = cores[0]
    (leader,) = core.committer.get_leaders(3)
    assert leader != 0
    core.add_blocks([b for b in layers[-1] if b.author() not in (0, leader)])
    assert core.current_round() == 4
    everyone = NetworkSyncer(
        core, TestCommitObserver(core.block_store, committee), None,
        parameters=Parameters()).connected_authorities
    for authority in range(4):
        everyone.insert(authority)
    assert core.ready_new_block(PERIOD, everyone) is False
    core.add_blocks([layers[-1][leader]])
    assert core.ready_new_block(PERIOD, everyone) is True


# -- the whole node on the simulator ------------------------------------------


@pytest.mark.parametrize("gate_rule", ["the_horizon", "no_horizon"])
def test_a_validator_down_a_hundred_rounds_and_back_costs_no_timeout(
        tmp_path, monkeypatch, gate_rule):
    """Seven validators; number 6 is stopped for ten virtual seconds and
    booted again on its WAL.  The simulator's validators process a backlog
    in no time, so what keeps the returned one behind here is its links:
    for eight seconds everything sent to it arrives 3 s late (longer than
    the leader timeout), as a replay that takes that long would leave it."""
    n, back = 7, 6
    live = [a for a in range(n) if a != back]
    if gate_rule == "no_horizon":  # the gate as it was: connected is waited for
        monkeypatch.setattr(Core, "LEADER_HORIZON_ROUNDS", 1 << 40)
    plan = FaultPlan(seed=45, link_faults=[LinkFault(
        delay_p=1.0, delay_extra_s=(3.0, 3.0), dst=back, start_s=14.0,
        end_s=22.0)])

    def read(harness):
        return {
            "timeouts": [harness.metrics[a].leader_timeout_total._value.get()
                         for a in live],
            "rounds": [harness.nodes[a].core.current_round() for a in live],
            "gates": [sorted(harness.nodes[a].connected_authorities.present())
                      for a in live],
            "connected": [len(harness.nodes[a].connections) for a in live],
            "back": (harness.nodes[back].core.current_round()
                     if harness.nodes[back] is not None else None),
            # Booked to the leader of the slot that was waited for.
            "for_back": [
                harness.metrics[a].mysticeti_health_leader_timeout_total
                .labels(str(back))._value.get() for a in live],
        }

    async def scenario():
        harness = ChaosSimHarness(
            n, str(tmp_path), Parameters(leader_timeout_s=2.0),
            committee=Committee.new_for_benchmarks(n), with_metrics=True)
        await harness.start()
        engine = ChaosEngine(harness, plan).start()
        await asyncio.sleep(4.0)
        await harness.crash(back)
        await asyncio.sleep(2.0)
        reads = [read(harness)]  # t = 6: the dead one costs nothing by now
        await asyncio.sleep(8.0)
        reads.append(read(harness))  # t = 14: a hundred rounds on
        await harness.restart(back)
        await asyncio.sleep(7.5)
        reads.append(read(harness))  # t = 21.5: connected and behind
        await asyncio.sleep(8.5)
        reads.append(read(harness))  # t = 30: the links long sound again
        recoveries = harness.metrics[back].crash_recovery_total._value.get()
        engine.stop()
        await harness.stop()
        harness.checker.check()
        return reads, recoveries, harness.sequences()

    (settled, down, behind, end), recoveries, sequences = run_simulation(
        scenario(), seed=45)

    def grew(a, b, what="timeouts"):
        return [y - x for x, y in zip(a[what], b[what])]

    assert recoveries == 1
    assert grew(settled, down) == [0.0] * len(live)
    assert min(grew(settled, down, "rounds")) >= 80  # and 2 s before it
    assert behind["connected"] == [n - 1] * len(live)
    # All agree on what they committed, the returned one's prefix too.
    shortest = min(len(s) for s in sequences.values())
    assert shortest > 100
    assert len({tuple(s[:shortest]) for s in sequences.values()}) == 1
    # Connected, it is in every set at once; what keeps the wait off its
    # slots is how far behind its newest block lies.
    assert behind["gates"] == end["gates"] == [list(range(n))] * len(live)
    if gate_rule == "no_horizon":
        # Connected is waited for: every slot it leads while it is behind
        # costs every other validator the leader timeout.
        assert min(grew(down, behind)) >= 2
        # Every one of them booked to the validator that was waited for.
        assert grew(down, behind, "for_back") == grew(down, behind)
        assert max(grew(down, behind, "rounds")) < 60
        return
    assert behind["back"] < min(behind["rounds"]) - HORIZON  # 3 s behind
    assert grew(down, end) == grew(down, end, "for_back") == [0.0] * len(live)
    assert min(grew(down, behind, "rounds")) >= 75  # the pace held
    # Caught up, its slots are waited for again.
    assert end["back"] >= min(end["rounds"]) - HORIZON
