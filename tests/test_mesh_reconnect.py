"""The socket mesh around a peer that restarts (``network.py: TcpNetwork``):
a dial whose connect is never answered - the SYN met a process being torn
down - is given up and tried again, so the worker dials the restarted peer;
and ``stop`` returns while an accepted peer keeps its end of the connection
open, as the peers of a validator that is stopped alone (a restart, an
upgrade) do."""
import asyncio

import pytest

from mysticeti_tpu import network
from mysticeti_tpu.network import HANDSHAKE_MAGIC, TcpNetwork

ADDRESSES = [("127.0.0.1", 4821), ("127.0.0.1", 4822)]


def test_a_connect_that_is_never_answered_is_given_up_and_tried_again(
        monkeypatch):
    monkeypatch.setattr(network, "HANDSHAKE_TIMEOUT_S", 0.2)
    connect = asyncio.open_connection
    calls = []

    async def open_connection(host, port):
        calls.append(port)
        if len(calls) == 1:
            await asyncio.Event().wait()  # neither accepted nor refused
        return await connect(host, port)

    monkeypatch.setattr(asyncio, "open_connection", open_connection)

    async def scenario():
        listener = await TcpNetwork.start(1, ADDRESSES)
        dialer = await TcpNetwork.start(0, ADDRESSES)
        try:
            connection = await asyncio.wait_for(dialer.connections.get(), 5)
            accepted = await asyncio.wait_for(listener.connections.get(), 5)
            return connection.peer, accepted.peer
        finally:
            await dialer.stop()
            await listener.stop()

    assert asyncio.run(scenario()) == (1, 0)
    assert len(calls) >= 2


@pytest.mark.parametrize("reads", [True, False])
def test_stop_returns_while_an_accepted_peer_keeps_its_end_open(reads):
    """``reads`` False: nobody takes the accepted connection's messages
    either (the syncer's task is gone first, as at a stop)."""

    async def scenario():
        listener = await TcpNetwork.start(1, ADDRESSES)
        reader, writer = await asyncio.open_connection(*ADDRESSES[1])
        writer.write(HANDSHAKE_MAGIC.to_bytes(4, "little")
                     + (0).to_bytes(8, "little"))
        await writer.drain()
        await reader.readexactly(4 + 12)  # the ack, framed
        connection = await asyncio.wait_for(listener.connections.get(), 5)
        assert connection.peer == 0
        if reads:
            asyncio.ensure_future(connection.recv())
        await asyncio.sleep(0.1)
        # The peer neither writes nor closes; the listener stops alone.
        await asyncio.wait_for(listener.stop(), 3)
        assert not listener._inbound
        writer.close()

    asyncio.run(scenario())
