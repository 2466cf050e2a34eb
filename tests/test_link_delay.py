"""Injected link delay (``Parameters.link_delay_ms``; network.py: DelayLine).

The program's delay line against the plain one of
``benchmark/reference/wan.py`` on seeded hand-over times under the virtual
clock of ``DeterministicLoop``; the parameter's YAML round trip and its
refusals; a 4-validator socket fleet under a 2-region table; and the
reference's finality floor against a brute-force search.
"""
import asyncio
import contextlib
import itertools
import random

import pytest

from benchmark.reference import wan
from mysticeti_tpu import network
from mysticeti_tpu.config import Identifier, Parameters
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.network import (
    Connection,
    DelayLine,
    Ping,
    Pong,
    SubscribeOwnFrom,
    TcpNetwork,
    decode_message,
)
from mysticeti_tpu.runtime.simulated import run_simulation


class RecordingWriter:
    """What ``_held_write_loop`` needs of a StreamWriter: every
    ``writelines`` with the loop's time; ``drain`` takes ``stall_s`` of it
    once, where a test sets that."""

    def __init__(self) -> None:
        self.batches = []  # (time, [message, ...])
        self.stall_s = 0.0

    def writelines(self, parts) -> None:
        now = asyncio.get_running_loop().time()
        parts = list(parts)
        assert len(parts) % 2 == 0
        messages = []
        for header, payload in zip(parts[::2], parts[1::2]):
            assert int.from_bytes(header, "little") == len(payload)
            messages.append(decode_message(payload))
        self.batches.append((now, messages))

    async def drain(self) -> None:
        stall, self.stall_s = self.stall_s, 0.0
        if stall:
            await asyncio.sleep(stall)

    def released(self) -> dict:
        """{message: time it reached the socket}, and the order."""
        return {m: at for at, batch in self.batches for m in batch}


def _no_timer(_name):
    return contextlib.nullcontext()


def _link(delay_s: float, metrics=None):
    line = DelayLine(delay_s)
    conn = Connection(1, metrics=metrics, delay_line=line)
    writer = RecordingWriter()
    task = asyncio.ensure_future(
        network._held_write_loop(conn, writer, _no_timer))
    return conn, writer, task


def _message(rng: random.Random, seq: int):
    kind = rng.random()
    if kind < 0.1:
        return Ping(seq)
    if kind < 0.2:
        return Pong(seq)
    return SubscribeOwnFrom(seq)


@pytest.mark.parametrize("seed", [1, 2, 3, 2_147_483_659])
def test_the_delay_line_releases_when_the_plain_one_does(seed):
    """Seeded hand-over times on three links of different delay, Ping and
    Pong among the frames, some handed over in bursts at one instant:
    every frame reaches the socket exactly when ``reference/wan.py``'s
    delay line says, in hand-over order on its link."""
    rng = random.Random(seed)
    delay_s = {"near": 0.0055, "far": 0.1125, "none": 0.0}
    events, at = [], 0.0
    for seq in range(600):
        if rng.random() > 0.3:  # else: a burst, handed over at one instant
            at += rng.expovariate(200.0)
        events.append((at, rng.choice(sorted(delay_s)), _message(rng, seq)))

    async def main():
        links = {name: _link(d) for name, d in delay_s.items()}
        loop = asyncio.get_running_loop()
        start = loop.time()
        handed = []
        for at, name, msg in events:
            if start + at > loop.time():
                await asyncio.sleep(start + at - loop.time())
            handed.append((loop.time(), name))
            conn = links[name][0]
            if isinstance(msg, (Ping, Pong)) or rng.random() < 0.5:
                await conn.send(msg)
            else:
                assert conn.try_send(msg)
        await asyncio.sleep(1.0)
        for _conn, _writer, task in links.values():
            task.cancel()
        return handed, {n: w for n, (_c, w, _t) in links.items()}

    handed, writers = run_simulation(main(), seed=seed, timeout_s=60.0)
    expected = wan.delay_line(handed, delay_s)
    released = {name: w.released() for name, w in writers.items()}
    for (at, name, msg), (stamp, _), due in zip(events, handed, expected):
        assert released[name][msg] == due, (name, msg)
        assert released[name][msg] >= stamp + delay_s[name]
    for name, writer in writers.items():
        order = [m for _at, batch in writer.batches for m in batch]
        assert order == [m for _at, n, m in events if n == name]
        # Bursts left together: fewer writes than frames.
        assert len(writer.batches) < len(order)


def test_a_burst_of_a_thousand_frames_takes_one_delay():
    """1,000 frames handed over at one instant leave in batches a delay
    later, all of them: not a delay each."""

    async def main():
        conn, writer, task = _link(0.1)
        loop = asyncio.get_running_loop()
        start = loop.time()
        for seq in range(1000):
            assert conn.try_send(SubscribeOwnFrom(seq))
        await asyncio.sleep(0.5)
        task.cancel()
        return start, writer

    start, writer = run_simulation(main(), seed=7, timeout_s=10.0)
    assert sum(len(batch) for _at, batch in writer.batches) == 1000
    assert {at for at, _batch in writer.batches} == {start + 0.1}
    assert len(writer.batches) <= 2


def test_a_late_write_loop_does_not_add_the_delay_twice():
    """The hold runs from the hand-over: frames that came due while the
    socket was stalled leave as soon as it is free, and a frame that was
    not yet due still waits for its own time."""

    async def main():
        conn, writer, task = _link(0.1)
        loop = asyncio.get_running_loop()
        start = loop.time()
        writer.stall_s = 0.5  # the first drain blocks until start + 0.6
        assert conn.try_send(SubscribeOwnFrom(0))
        await asyncio.sleep(0.2)
        assert conn.try_send(SubscribeOwnFrom(1))  # due at 0.3: overdue
        await asyncio.sleep(0.35)
        assert conn.try_send(SubscribeOwnFrom(2))  # due at 0.65
        await asyncio.sleep(1.0)
        task.cancel()
        return start, writer.released()

    start, released = run_simulation(main(), seed=7, timeout_s=10.0)
    at = {m.round: round(t - start, 9) for m, t in released.items()}
    assert at == {0: 0.1, 1: 0.6, 2: 0.65}


def test_ping_and_pong_take_their_turn_behind_bulk_frames():
    """No urgent lane round the line: a Ping handed over behind bulk
    frames leaves behind them, at its own due time; without a line it still
    jumps the queue."""

    async def main():
        conn, writer, task = _link(0.05)
        loop = asyncio.get_running_loop()
        start = loop.time()
        assert conn.try_send(SubscribeOwnFrom(0))
        await asyncio.sleep(0.01)
        await conn.send(Ping(1))
        assert conn.try_send(Pong(2))
        assert conn.sender.urgent_queued == 0
        await asyncio.sleep(0.2)
        task.cancel()
        plain = Connection(1)
        plain.try_send(SubscribeOwnFrom(0))
        await plain.send(Ping(1))
        return start, writer, plain.sender.get_nowait()

    start, writer, first = run_simulation(main(), seed=7, timeout_s=10.0)
    assert [(round(at - start, 9), batch) for at, batch in writer.batches] == [
        (0.05, [SubscribeOwnFrom(0)]), (0.06, [Ping(1), Pong(2)])]
    assert first == Ping(1)


def test_the_line_books_mesh_hold_and_counts_its_frames():
    from mysticeti_tpu import spans

    async def main():
        metrics = Metrics()
        stages = spans.StageClock(("mesh_hold",))
        metrics.block_stages.attach(stages)
        line = DelayLine(
            0.025, stages=stages,
            frames=metrics.mesh_delayed_frames_total.labels("1"))
        conn = Connection(1, metrics=metrics, delay_line=line)
        writer = RecordingWriter()
        task = asyncio.ensure_future(
            network._held_write_loop(conn, writer, _no_timer))
        for seq in range(8):
            assert conn.try_send(SubscribeOwnFrom(seq))
        await asyncio.sleep(0.1)
        task.cancel()
        return metrics.expose().decode()

    text = run_simulation(main(), seed=7, timeout_s=10.0)
    assert 'mesh_delayed_frames_total{peer="1"} 8.0' in text
    assert 'block_stage_seconds_count{stage="mesh_hold"} 8.0' in text
    (total,) = [float(l.split()[-1]) for l in text.splitlines()
                if l.startswith('block_stage_seconds_sum{stage="mesh_hold"}')]
    assert total == pytest.approx(8 * 0.025)


def test_an_empty_table_constructs_todays_write_loop(monkeypatch):
    """No table: no delay line on any connection, no stamp on a queued
    message, none of the new series, and the held loop is never entered."""
    metrics = Metrics()
    net = TcpNetwork(0, [("127.0.0.1", 1), ("127.0.0.1", 2)], metrics)
    assert net.link_delays_s is None and net._delay_line(1) is None
    assert Parameters().link_delays_s(0) is None
    text = metrics.expose().decode()
    assert "mesh_link_delay_seconds{" not in text
    assert "mesh_delayed_frames_total{" not in text
    assert 'stage="mesh_hold"' not in text

    async def main():
        conn = Connection(1)
        assert conn.try_send(SubscribeOwnFrom(3))
        await conn.send(SubscribeOwnFrom(4))
        return conn.sender.get_nowait(), conn.sender.get_nowait()

    assert run_simulation(main(), seed=1) == (
        SubscribeOwnFrom(3), SubscribeOwnFrom(4))

    held = TcpNetwork(0, [("127.0.0.1", 1), ("127.0.0.1", 2)], Metrics(),
                      link_delays_s=[0.0, 0.0325])
    assert held._delay_line(1).delay_s == 0.0325
    assert ('mesh_link_delay_seconds{peer="1"} 0.0325'
            in held.metrics.expose().decode())


# -- the parameter -------------------------------------------------------------


def _identifiers(n):
    return [Identifier("127.0.0.1", 1500 + i, 2500 + i) for i in range(n)]


def test_parameters_round_trip_the_table_through_yaml(tmp_path):
    table = [[0.0, 11.0, 30.5], [11.0, 0.0, 32.5], [30.5, 32.5, 0.0]]
    parameters = Parameters(identifiers=_identifiers(3), link_delay_ms=table)
    path = str(tmp_path / "parameters.yaml")
    parameters.dump(path)
    loaded = Parameters.load(path)
    assert loaded.link_delay_ms == table
    assert loaded.link_delays_s(2) == [0.0305, 0.0325, 0.0]
    # Empty stays empty, and an older file without the key loads.
    Parameters(identifiers=_identifiers(3)).dump(path)
    assert Parameters.load(path).link_delay_ms == []
    with open(path) as f:
        text = f.read().replace("link_delay_ms: []\n", "")
    with open(path, "w") as f:
        f.write(text)
    assert Parameters.load(path).link_delays_s(0) is None


@pytest.mark.parametrize("table", [
    [[0.0, 1.0], [1.0, 0.0]],                         # 2 x 2 for 3 validators
    [[0.0, 1.0, 1.0], [1.0, 0.0], [1.0, 1.0, 0.0]],   # a short row
    [[0.0, 1.0, 1.0], [1.0, 0.0, -0.5], [1.0, 1.0, 0.0]],  # a negative
    [[0.0, 1.0, 1.0], [1.0, 0.0, float("nan")], [1.0, 1.0, 0.0]],
], ids=["not-n-rows", "short-row", "negative", "nan"])
def test_parameters_refuse_a_table_that_is_not_n_by_n_or_negative(table):
    with pytest.raises(ValueError):
        Parameters(identifiers=_identifiers(3), link_delay_ms=table)


def test_the_simulator_takes_the_same_table():
    from mysticeti_tpu.scenarios import wan_latency_ranges

    table = [[0.0, 5.5, 30.0], [5.5, 0.0, 32.5], [30.0, 32.5, 0.0]]
    ranges = wan_latency_ranges([0, 0, 1], table)
    assert ranges[(0, 1)] == (0.0055, 0.0055)
    assert ranges[(2, 1)] == (0.0325, 0.0325)
    assert (1, 1) not in ranges


# -- a socket fleet under a 2-region table ---------------------------------------


def test_a_socket_fleet_under_a_two_region_table_commits(tmp_path):
    """Four validators over real localhost sockets, two in each of two
    regions: the fleet commits, every link's gauge is the table's, every
    link counted frames through its line, and each node's mesh RTT to each
    peer is no less than the table's."""
    from test_validator import _setup, _start_all, _wait_commits

    near, far = 2.0, 30.0
    regions = [0, 0, 1, 1]
    table = [[0.0 if a == b else (near if regions[a] == regions[b] else far)
              for b in range(4)] for a in range(4)]

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        parameters.link_delay_ms = table
        validators = await _start_all(committee, parameters, signers,
                                      privates, 4)
        try:
            await _wait_commits(validators, 5, 60.0)
            texts = [v.metrics.expose().decode() for v in validators]
        finally:
            for v in validators:
                await v.stop()
        return texts

    texts = asyncio.run(main())
    for a, text in enumerate(texts):
        series = {}
        for line in text.splitlines():
            if line.startswith(("mesh_", "connection_latency_",
                                "connection_send_drops_total")):
                name, value = line.rsplit(" ", 1)
                series[name] = float(value)
        for b in range(4):
            if a == b:
                continue
            peer = f'{{peer="{b}"}}'
            assert series["mesh_link_delay_seconds" + peer] == table[a][b] / 1e3
            assert series["mesh_delayed_frames_total" + peer] > 0
            samples = series["connection_latency_count" + peer]
            assert samples >= 1
            mean = series["connection_latency_sum" + peer] / samples
            assert mean >= (table[a][b] + table[b][a]) / 1e3
        assert not any(v for k, v in series.items()
                       if k.startswith("connection_send_drops_total"))


# -- the reference's floor ---------------------------------------------------------


def _paths(one_way, a, b):
    """Every simple path's length from a to b."""
    n = len(one_way)
    if a == b:
        yield 0.0
        return
    others = [c for c in range(n) if c not in (a, b)]
    for k in range(len(others) + 1):
        for via in itertools.permutations(others, k):
            hops = (a, *via, b)
            yield sum(one_way[x][y] for x, y in zip(hops, hops[1:]))


def _brute_force_floor_s(v, one_way, q):
    n = len(one_way)
    d = [[min(_paths(one_way, a, b)) for b in range(n)] for a in range(n)]
    quorums = list(itertools.combinations(range(n), q))
    best = float("inf")
    for u in range(n):
        vote = [d[v][u] + d[u][w] for w in range(n)]
        certificate = [min(max(vote[w] + d[w][x] for w in voters)
                           for voters in quorums) for x in range(n)]
        decided = min(max(certificate[x] + d[x][v] for x in certifiers)
                      for certifiers in quorums)
        best = min(best, decided)
    return best / 1e3


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_finality_floor_against_a_brute_force_search(seed):
    """On a seeded 4-node table (asymmetric, and with a link slower than
    the detour round it): the k-th-smallest form of the reference against
    a search over every leader, every quorum of voters for every certifier
    and every quorum of certifiers, over every simple relay path."""
    rng = random.Random(seed)
    table = [[0.0 if a == b else float(rng.randrange(2, 120))
              for b in range(4)] for a in range(4)]
    table[0][3] = 400.0  # slower than any detour
    for v in range(4):
        assert wan.finality_floor_s(v, table) == pytest.approx(
            _brute_force_floor_s(v, table, 3))
    assert wan.quorum_of(4) == 3 and wan.quorum_of(10) == 7


def test_the_deployments_floors_and_table():
    """The numbers ISSUE 32 and the file's docstring state, and the table's
    shape: symmetric, zero diagonal, one way = RTT / 2."""
    floors = wan.finality_floors_s()
    assert [round(f * 1e3, 1) for f in floors] == [
        160.0, 158.5, 151.5, 160.0, 160.0, 152.5, 151.0, 221.5, 231.5, 229.0]
    assert wan.lower_median(floors) == 0.160
    assert len(wan.REGIONS) == 10 and wan.one_way_ms(0, 8) == 115.0
    table = wan.one_way_table_ms()
    for a in range(10):
        assert table[a][a] == 0.0
        for b in range(10):
            assert table[a][b] == table[b][a] == wan.RTT_MS[a][b] / 2
    # The sixth-nearest peer of each validator: how long a round lasts.
    sixth = [sorted(row)[6] for row in table]
    assert min(sixth) == 45.0 and max(sixth) == 94.0
