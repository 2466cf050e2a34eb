"""Permanent crash faults (docs/fault-injection.md "Permanent faults"): a
validator whose connection closed is not waited for, one that is connected
and silent is waited for until the leader timeout, and one that reconnects
is waited for again; ``connected_nodes`` follows; on the simulator ten
validators with three stopped keep the healthy pace and fire no timeout."""
import asyncio

from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.network import Connection
from mysticeti_tpu.runtime.simulated import run_simulation
from mysticeti_tpu.simulated_network import SimulatedNetwork
from mysticeti_tpu.types import AuthoritySet

from helpers import committee_and_cores
from test_spans import _build_node


def test_authority_set_remove():
    s = AuthoritySet()
    assert s.insert(3) and s.insert(9) and s.insert(511)
    assert s.remove(9) is True
    assert not s.contains(9) and s.contains(3) and s.contains(511)
    assert list(s.present()) == [3, 511] and len(s) == 2
    assert s.remove(9) is False and s.remove(200) is False
    assert s.bits == (1 << 3) | (1 << 511)
    assert s.insert(9) and s.contains(9)  # back in, as on a reconnect
    assert s.remove(3) and s.remove(9) and s.remove(511) and s.bits == 0


def test_the_gate_waits_for_a_connected_leader_and_for_no_other(tmp_path):
    """``Core.ready_new_block`` on one DAG under three sets: the leader of
    the round below connected and silent (held), its connection closed
    (goes at once), reconnected (held again); the leader's block ends the
    wait whatever the set."""
    committee, cores = committee_and_cores(4, str(tmp_path))
    period = Parameters().wave_length
    layer = []
    for core in cores:
        core.run_block_handler([])
        layer.append(core.try_new_block())
    for round_ in (2, 3):
        below, layer = layer, []
        for core in cores:
            core.add_blocks([b for b in below if b.author() != core.authority])
            block = core.try_new_block()
            assert block.round() == round_
            layer.append(block)
    (leader,) = cores[0].committer.get_leaders(3)
    waiting = cores[(leader + 1) % 4]
    others = [b for b in layer
              if b.author() not in (leader, waiting.authority)]
    waiting.add_blocks(others)  # with its own: a quorum of round 3
    assert waiting.current_round() == 4
    connected = AuthoritySet()
    for authority in range(4):
        connected.insert(authority)
    assert waiting.ready_new_block(period, connected) is False
    connected.remove(leader)
    assert waiting.ready_new_block(period, connected) is True
    connected.insert(leader)
    assert waiting.ready_new_block(period, connected) is False
    waiting.add_blocks([layer[leader]])
    assert waiting.ready_new_block(period, connected) is True


# -- the whole node on the simulator -----------------------------------------


def _value(gauge) -> float:
    return gauge._value.get()


class _Fleet:
    """``n`` validators with metrics on the simulated network."""

    def __init__(self, n, tmp_dir, leader_timeout_s):
        self.n = n
        committee = Committee.new_for_benchmarks(n)
        signers = Committee.benchmark_signers(n)
        parameters = Parameters(leader_timeout_s=leader_timeout_s)
        self.net = SimulatedNetwork(n)
        self.metrics = [Metrics() for _ in range(n)]
        self.nodes = [
            _build_node(committee, signers, a, tmp_dir, self.net, parameters,
                        self.metrics[a])
            for a in range(n)]
        self.stopped = set()

    async def start(self):
        for node in self.nodes:
            await node.start()
        await self.net.connect_all()

    async def kill(self, authority):
        """What SIGKILL does: the process is gone, its sockets close."""
        self.net.crash(authority)
        await self.nodes[authority].stop()
        self.stopped.add(authority)

    async def stop(self):
        for a, node in enumerate(self.nodes):
            if a not in self.stopped:
                await node.stop()
        self.net.close()

    def read(self, authorities):
        return {
            "timeouts": [_value(self.metrics[a].leader_timeout_total)
                         for a in authorities],
            "round": [_value(self.metrics[a].threshold_clock_round)
                      for a in authorities],
            "connected": [_value(self.metrics[a].connected_nodes)
                          for a in authorities],
            "waits": [self.nodes[a]._block_stages.totals()["leader_wait"]
                      for a in authorities],
            "gate": [sorted(self.nodes[a].connected_authorities.present())
                     for a in authorities],
        }


def test_ten_validators_three_killed_keep_the_pace_and_fire_no_timeout(
        tmp_path):
    """From 2 virtual seconds after the kill the seven run at the healthy
    pace (where every slot of a dead leader cost the 2 s timeout they ran
    at a sixth of it) and ``leader_timeout_total`` is flat on each."""
    live, dead = list(range(7)), [7, 8, 9]

    async def scenario(kill):
        fleet = _Fleet(10, str(tmp_path / ("f3" if kill else "healthy")), 2.0)
        await fleet.start()
        await asyncio.sleep(3.0)
        before = fleet.read(live)
        if kill:
            for authority in dead:
                await fleet.kill(authority)
        await asyncio.sleep(2.0)
        settled = fleet.read(live)
        await asyncio.sleep(10.0)
        end = fleet.read(live)
        await fleet.stop()
        return before, settled, end

    (tmp_path / "healthy").mkdir()
    (tmp_path / "f3").mkdir()
    _, h_settled, h_end = run_simulation(scenario(False), seed=11)
    before, settled, end = run_simulation(scenario(True), seed=11)
    assert before["connected"] == [9.0] * 7
    assert before["gate"] == [list(range(10))] * 7
    assert settled["connected"] == end["connected"] == [6.0] * 7
    # The set the gate reads holds the live peers and the validator itself.
    assert settled["gate"] == end["gate"] == [live] * 7
    assert end["timeouts"] == settled["timeouts"], (settled, end)
    healthy = min(e - s for s, e in zip(h_settled["round"], h_end["round"]))
    pace = min(e - s for s, e in zip(settled["round"], end["round"]))
    # All seven of seven pace every round (the slowest of six links, where
    # the healthy fleet waits for the seventh fastest of nine), so a little
    # under the healthy pace; with the timeout it was 10 s / (0.3 x 2 s +
    # 0.1 s) = 14 rounds.
    assert healthy >= 80 and pace >= 0.7 * healthy, (healthy, pace)
    # One leader_wait sample a proposal, none of them a timeout's.
    for s, e in zip(settled["waits"], end["waits"]):
        assert e["count"] - s["count"] >= pace - 1
        assert (e["wall_s"] - s["wall_s"]) / (e["count"] - s["count"]) < 0.2


class _Blackhole:
    """A validator that is connected and says nothing: every frame it
    sends is dropped on the wire (``SimulatedNetwork.fault_injector``)."""

    def __init__(self, silent):
        self.silent = silent

    def filter_batch(self, src, dst, batch):
        return [] if src == self.silent else [(0.0, batch)]


def test_silent_is_waited_for_closed_is_not_reconnected_is_again(tmp_path):
    """Four validators, number 3 the faulty one, in turn in step and then
    silent (its slots cost the timeout), closed (they cost nothing),
    connected again and still silent (nothing: it is in the set the gate
    reads, and its newest block lies further below its slots than the
    gate's horizon), heard again (its slots are waited for, and no timeout
    has fired for it); ``connected_nodes`` follows each step."""
    live = [0, 1, 2]

    async def scenario():
        fleet = _Fleet(4, str(tmp_path), 1.0)
        await fleet.start()
        await asyncio.sleep(2.0)
        reads = [fleet.read(live)]
        fleet.net.fault_injector = _Blackhole(3)
        await asyncio.sleep(8.0)
        reads.append(fleet.read(live))
        fleet.net.crash(3)  # its sockets close; the process lives, unheard
        await asyncio.sleep(2.0)
        reads.append(fleet.read(live))
        await asyncio.sleep(8.0)
        reads.append(fleet.read(live))
        await fleet.net.restart(3)
        await asyncio.sleep(8.0)
        reads.append(fleet.read(live))
        # Heard again, over fresh connections: what it sent on the old
        # ones, its subscriptions among it, is lost.
        fleet.net.fault_injector = None
        fleet.net.crash(3)
        await fleet.net.restart(3)
        await asyncio.sleep(8.0)
        reads.append(fleet.read(live))
        await fleet.stop()
        return reads

    healthy, silent, closed, closed_end, again, heard = run_simulation(
        scenario(), seed=23)

    def grew(a, b, what="timeouts"):
        return [y - x for x, y in zip(a[what], b[what])]

    assert healthy["connected"] == silent["connected"] == [3.0] * 3
    assert healthy["gate"] == silent["gate"] == [[0, 1, 2, 3]] * 3
    # Connected and silent: one slot in four is its own, each a timeout.
    assert all(n >= 2 for n in grew(healthy, silent)), grew(healthy, silent)
    assert closed["connected"] == closed_end["connected"] == [2.0] * 3
    assert closed["gate"] == closed_end["gate"] == [live] * 3
    # The proposal that was held for its slot when the sockets closed went
    # out then, not at the timeout.
    assert grew(silent, closed) == [0.0] * 3
    assert grew(closed, closed_end) == [0.0] * 3
    assert min(grew(closed, closed_end, "round")) >= 40
    # Connected again and unheard: the reconnect puts it back in the set,
    # and its slots still cost nothing - what it last said is rounds ago.
    assert again["connected"] == heard["connected"] == [3.0] * 3
    assert again["gate"] == [[0, 1, 2, 3]] * 3
    assert grew(closed_end, again) == [0.0] * 3
    assert min(grew(closed_end, again, "round")) >= 40
    # Heard again: the three went on at their pace while it was on its
    # way to the frontier, and wait for it there.
    assert heard["gate"] == [[0, 1, 2, 3]] * 3
    assert grew(again, heard) == [0.0] * 3
    assert min(grew(again, heard, "round")) >= 40
    # The clock saw both: a timed-out round's wait is the timeout.
    waits = [(b["wall_s"] - a["wall_s"]) / (b["count"] - a["count"])
             for a, b in zip(closed["waits"], closed_end["waits"])]
    assert all(w < 0.2 for w in waits), waits
    slow = [(b["wall_s"] - a["wall_s"]) for a, b in
            zip(healthy["waits"], silent["waits"])]
    assert all(s >= 2.0 - 1e-6 for s in slow), slow


def test_a_duplicate_connection_closed_leaves_the_kept_one_in_the_set(
        tmp_path):
    """A second connection to a peer takes the peer's slot in
    ``connections`` (a redial that raced the teardown of the first).  The
    first one closing then is no disconnect: the peer stays in the set the
    gate reads, ``connected_nodes`` stays, and the rounds go on over the
    kept link with no timeout.  The kept one closing is a disconnect; the
    validator's own index never leaves its set."""
    live = [0, 1, 2, 3]

    async def scenario():
        fleet = _Fleet(4, str(tmp_path), 1.0)
        await fleet.start()
        await asyncio.sleep(2.0)
        first = fleet.net._links[(0, 1)]
        await fleet.net._connect_pair(0, 1)  # both ends get a fresh handle
        await asyncio.sleep(0.5)
        kept = fleet.net._links[(0, 1)]
        held = [fleet.nodes[0].connections[1] is kept[0],
                fleet.nodes[1].connections[0] is kept[1]]
        reads = [fleet.read(live)]
        first[0].close()
        first[1].close()
        first[2].cancel()
        first[3].cancel()
        await asyncio.sleep(5.0)
        reads.append(fleet.read(live))
        fleet.net._sever(0, 1)  # the kept one
        # A hello that names the validator itself (any index under the
        # committee's size is let in) and goes away again.
        own = Connection(2)
        await fleet.net.node_connections[2].put(own)
        await asyncio.sleep(0.5)
        own.close()
        await asyncio.sleep(0.5)
        reads.append(fleet.read(live))
        await fleet.stop()
        return held, reads

    held, (both, kept, neither) = run_simulation(scenario(), seed=31)
    assert held == [True, True]
    assert both["gate"] == kept["gate"] == [live] * 4
    assert both["connected"] == kept["connected"] == [3.0] * 4
    assert kept["timeouts"] == both["timeouts"]
    assert min(e - s for s, e in zip(both["round"], kept["round"])) >= 20
    assert neither["gate"] == [[0, 2, 3], [1, 2, 3], live, live]
    assert neither["connected"] == [2.0, 2.0, 3.0, 3.0]
